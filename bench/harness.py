"""One run of one cell: set-up, a measured window, the check of the outputs.

The system module of the cell's configuration drives the loop that the
cell's traffic mix names.  It builds its state, warms every shape the window
uses, then enters :meth:`Run.window` and calls :meth:`Run.boundary` at the
end of each period (a save returned, a restore and its step done).  The
window opens when set-up ends and closes at the first boundary at or after
``seconds``, so it always holds whole periods.  The loop returns a function
that checks the outputs against the plain reference; the harness calls it
after the window has closed and the device's peak memory has been read.
"""

from __future__ import annotations

import contextlib
import json
import os
import sys
import time
from dataclasses import dataclass, field
from typing import Callable

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
SPANS = ("window", "step", "snapshot", "save", "read", "restore")


class CompileCounter:
    """Counts executables built, compiled or loaded from the persistent
    cache, through ``jax.monitoring``."""

    def __init__(self):
        self.n = 0

    def listen(self, event: str, duration: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.n += 1


@dataclass
class Save:
    stall_s: float  # time the loop spent inside the save call
    write_s: float  # the writer's own timer (``SaveResult.wall_s``)
    fsync_s: float = 0.0  # time inside ``os.fsync`` during the save


@dataclass
class Restore:
    resume_s: float  # restore call to fields ready on the device
    read_s: float  # time inside ``CheckpointManager.restore``


@dataclass
class Check:
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclass
class Outcome:
    checks: dict[str, Check]
    attempted: int
    failed: int
    detail: dict = field(default_factory=dict)  # printed, not judged

    @property
    def correct(self) -> bool:
        return self.failed == 0 and all(c.ok for c in self.checks.values())


@dataclass
class Run:
    """What one run records; the metric readers read it."""

    config: dict
    traffic: dict
    seed: int
    seconds: float
    workdir: str
    t_process: float  # process start, on the host clock
    trace_dir: str | None = None
    clock: Callable[[], float] = time.perf_counter
    compiles: CompileCounter = field(default_factory=CompileCounter)
    t_open: float | None = None
    t_close: float | None = None
    steps: int = 0
    saves: list[Save] = field(default_factory=list)
    restores: list[Restore] = field(default_factory=list)
    compiles_in_window: int | None = None
    trace: object | None = None  # trace_reduce.Reduction of the traced window
    marks: dict[str, float] = field(default_factory=dict)  # set-up phases, s after process start

    def mark(self, name: str) -> None:
        self.marks[name] = self.clock() - self.t_process

    @property
    def setup_s(self) -> float:
        return self.t_open - self.t_process

    @property
    def window_s(self) -> float:
        return self.t_close - self.t_open

    @contextlib.contextmanager
    def window(self):
        """The measured window; traced when the run has a ``trace_dir``."""
        import jax

        if self.trace_dir:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # host spans only, no Python calls
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        try:
            n0 = self.compiles.n
            with jax.profiler.TraceAnnotation("window"):
                self.t_open = self.clock()
                yield self
            if self.t_close is None:
                raise RuntimeError("the loop left the window without reaching a boundary")
            self.compiles_in_window = self.compiles.n - n0
        finally:
            if self.trace_dir:
                jax.profiler.stop_trace()

    def boundary(self) -> bool:
        """Called at the end of each period; True when the window closes."""
        t = self.clock()
        if t - self.t_open >= self.seconds:
            self.t_close = t
            return True
        return False


def span(name: str):
    """A host span on the profiler's clock (free when no trace is taken)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def peak_bytes(devices) -> int:
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)


def run_cell(cell, *, seed: int, seconds: float, trace: bool, workdir: str, t_process: float) -> dict:
    """Run ``cell`` once and return the result object (the last line)."""
    import jax

    from bench import trace_reduce

    devices = jax.devices()[: cell.chips]
    rec = Run(
        config=cell.config,
        traffic=cell.traffic,
        seed=seed,
        seconds=seconds,
        workdir=workdir,
        t_process=t_process,
        trace_dir=os.path.join(workdir, "trace") if trace else None,
    )
    jax.monitoring.register_event_duration_secs_listener(rec.compiles.listen)
    rec.mark("devices")
    loop = cell.system.LOOPS[cell.traffic["loop"]]
    check = loop(rec, cell.reference)
    memory = peak_bytes(devices)
    if trace:
        rec.trace = trace_reduce.reduce_dir(rec.trace_dir, spans=SPANS)
    print(json.dumps({"setup_s": rec.setup_s, "window_s": rec.window_s, "steps": rec.steps,
                      "saves": len(rec.saves), "restores": len(rec.restores),
                      "compiles_in_window": rec.compiles_in_window, "setup_marks": rec.marks}), flush=True)
    t = time.perf_counter()
    outcome: Outcome = check()
    print(json.dumps({"check_s": time.perf_counter() - t, **outcome.detail}), file=sys.stderr)

    metrics = {}
    for m in cell.per_layer if trace else cell.end_to_end:
        value = m.reader.read(rec)
        if value is not None:
            metrics[m.name] = {"value": value, "unit": m.unit}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices), "memory_peak_bytes": memory}
    result = {
        "correct": outcome.correct,
        "attempted": outcome.attempted,
        "failed": outcome.failed,
        "metrics": metrics,
        "device": device,
    }
    if trace:
        device["busy_s"] = rec.trace.busy_s
        device["window_s"] = rec.trace.window_s
        result["breakdown"] = rec.trace.breakdown()
    result["checks"] = {k: {"value": c.value, "limit": c.limit} for k, c in outcome.checks.items()}
    for k, c in outcome.checks.items():
        print(f"check {k} {c.value!r} limit {c.limit!r} {'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    print(f"check correct {outcome.correct} attempted {outcome.attempted} failed {outcome.failed}", file=sys.stderr)
    return result

"""The readers of the program's phase spans, on hand-made reductions and on
a recorded trace file."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import spec  # noqa: E402
from bench.harness import Restore, Run, Save  # noqa: E402


class FakeClock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t


def _run(seconds: float, clock: FakeClock) -> Run:
    return Run(config={}, traffic={}, seed=0, seconds=seconds, workdir="", t_process=0.0, clock=clock)


def _readers():
    bench = spec.Benchmark(str(ROOT))
    return {m.name: m.reader for m in bench.metrics}


PHASE_READERS = {  # metric: (the program's phase, what it is per)
    "stage_fetch_s": ("sim.fetch", "saves"),
    "stage_topology_s": ("sim.topology", "saves"),
    "write_data_s": ("ckpt.write", "saves"),
    "write_seal_s": ("ckpt.seal", "saves"),
    "read_verify_s": ("th5.verify", "restores"),
    "load_layout_s": ("sim.layout", "restores"),
}


@pytest.mark.parametrize("metric", sorted(PHASE_READERS))
def test_phase_metrics_read_the_program_spans(metric):
    from bench.trace_reduce import reduce_events
    from repro.obs.trace import PHASE_SPANS

    phase, per = PHASE_READERS[metric]
    assert phase in PHASE_SPANS
    # two periods; in each the phase runs twice, 0.25 s and 0.5 s, inside
    # the bench's own span, and a third time half outside the window
    spans = [("window", 0.0, 10.0)]
    for t in (1.0, 6.0):
        spans += [("snapshot", t, t + 3.0), (phase, t + 0.5, t + 0.75), (phase, t + 1.0, t + 1.5)]
    spans.append((phase, 9.5, 10.5))
    rec = _run(1.0, FakeClock())
    setattr(rec, per, [Save(2.9, 2.3), Save(2.9, 2.3)] if per == "saves" else [Restore(1.2, 0.8), Restore(1.2, 0.8)])
    rec.trace = reduce_events([[(0.0, 1.0)]], [[]], spans)
    read = _readers()[metric]
    assert read.read(rec) == pytest.approx((2 * 0.75 + 0.5) / 2)
    # a program that opens no phase spans: nothing to read, no error
    rec.trace = reduce_events([[(0.0, 1.0)]], [[]], [s for s in spans if s[0] != phase])
    assert read.read(rec) is None
    rec.trace = None
    assert read.read(rec) is None


def test_phase_metrics_reduce_the_trace_file_again(tmp_path, monkeypatch):
    """A traced run's reduction holds the bench's spans only; a phase of
    the program is read by reducing the run's trace file once more, once
    per run, and a trace without the phase reads nothing."""
    import shutil

    from bench import program_spans, trace_reduce

    probe = ROOT / "bench" / "tests" / "data" / "probe.xplane.pb"
    trace_dir = tmp_path / "trace"
    trace_dir.mkdir()
    shutil.copy(probe, trace_dir / "probe.xplane.pb")
    rec = _run(1.0, FakeClock())
    rec.trace_dir = str(trace_dir)
    rec.saves = [Save(2.9, 2.3)]
    full = trace_reduce.reduce_dir(rec.trace_dir, spans=("window", "step", "snapshot"))
    # stand-ins: the harness reduces by window and step, and snapshot
    # plays the program's phase
    monkeypatch.setattr(program_spans, "SPANS", ("window", "step"))
    monkeypatch.setattr(program_spans, "_phase_names", lambda: ("snapshot",))
    rec.trace = trace_reduce.reduce_dir(rec.trace_dir, spans=("window", "step"))
    reductions = []
    real = trace_reduce.reduce_dir
    monkeypatch.setattr(trace_reduce, "reduce_dir", lambda *a, **kw: reductions.append(1) or real(*a, **kw))
    assert program_spans.per_save(rec, "snapshot") == pytest.approx(full.spans["snapshot"].host_s)
    assert program_spans.stat(rec, "step") is rec.trace.spans["step"]  # in the harness's reduction
    assert program_spans.per_save(rec, "sim.fetch") is None
    assert len(reductions) == 1
    for metric in PHASE_READERS:  # the recorded trace has none of the program's phases
        assert _readers()[metric].read(rec) is None

"""Reduce a JAX profiler trace to device busy time, per-executable time and
idle time attributed to what the host was doing.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes.  On a TPU it
holds one plane per chip, ``/device:TPU:<n>``, whose ``XLA Ops`` line has one
event per operation and whose ``XLA Modules`` line has one event per
executable run, and a ``/host:CPU`` plane whose threads carry the
``TraceAnnotation`` spans the benchmark opens around its calls.  Times are in
nanoseconds on one clock.

- busy: the union of the intervals in which an operation ran, inside the
  host span ``window``, averaged over the chips;
- per executable: the summed durations of each ``XLA Modules`` name;
- idle by host span: the window is cut at every span boundary, each piece
  is labelled with the innermost span around it, and the piece's time with
  no operation running is charged to that label (``host`` where no span is
  open).

Device and host timestamps can disagree by about a millisecond (a v5e trace
read the device about 1.1 ms ahead), which is nothing beside the spans of
seconds that the benchmark reduces.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "window"
NO_SPAN = "host"


def merge(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Union of ``(start, end)`` intervals, sorted and disjoint."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


class Union:
    """Disjoint intervals with a prefix sum: covered length in O(log n)."""

    def __init__(self, intervals: list[tuple[float, float]]):
        merged = merge(intervals)
        self.starts = [s for s, _ in merged]
        self.ends = [e for _, e in merged]
        self.cum = [0.0]
        for s, e in merged:
            self.cum.append(self.cum[-1] + (e - s))

    def covered(self, a: float, b: float) -> float:
        """Length of the union inside ``[a, b]``."""
        if b <= a or not self.starts:
            return 0.0
        return self._upto(b) - self._upto(a)

    def _upto(self, t: float) -> float:
        i = bisect.bisect_right(self.starts, t)  # intervals starting at or before t
        if i == 0:
            return 0.0
        return self.cum[i - 1] + min(t, self.ends[i - 1]) - self.starts[i - 1]


@dataclass
class SpanStat:
    count: int = 0
    host_s: float = 0.0
    busy_s: float = 0.0  # device busy inside the spans, averaged over chips


@dataclass
class Reduction:
    window_s: float
    busy_s: float
    n_devices: int
    executables: dict[str, float] = field(default_factory=dict)  # seconds per chip
    idle_by_span: dict[str, float] = field(default_factory=dict)  # seconds per chip
    spans: dict[str, SpanStat] = field(default_factory=dict)

    @property
    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def breakdown(self, n: int = 10) -> dict:
        top = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
        return {"device_ops": top(self.executables), "idle_gaps": top(self.idle_by_span)}


def _innermost_segments(spans: list[tuple[str, float, float]], a: float, b: float):
    """Cut ``[a, b]`` at every span boundary; yield (start, end, label) with
    the label of the shortest span that covers the piece."""
    cuts = sorted({a, b} | {t for _, s, e in spans for t in (s, e) if a < t < b})
    for lo, hi in zip(cuts, cuts[1:]):
        mid = 0.5 * (lo + hi)
        covering = [(e - s, name) for name, s, e in spans if s <= mid < e]
        yield lo, hi, min(covering)[1] if covering else NO_SPAN


def reduce_events(
    device_ops: list[list[tuple[float, float]]],
    device_modules: list[list[tuple[str, float, float]]],
    host_spans: list[tuple[str, float, float]],
) -> Reduction:
    """``device_ops[d]``: (start, end) of each operation on chip d;
    ``device_modules[d]``: (name, start, end) of each executable run;
    ``host_spans``: (name, start, end) of the benchmark's spans, one of them
    ``window``.  Any time unit; the result is in that unit."""
    windows = [(s, e) for name, s, e in host_spans if name == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW!r} span, found {len(windows)}")
    a, b = windows[0]
    inner = [(name, max(s, a), min(e, b)) for name, s, e in host_spans if name != WINDOW and e > a and s < b]
    n_dev = len(device_ops)
    if n_dev == 0:
        raise ValueError("the trace has no device plane")
    unions = [Union(ops) for ops in device_ops]
    busy = sum(u.covered(a, b) for u in unions) / n_dev

    executables: dict[str, float] = {}
    for mods in device_modules:
        for name, s, e in mods:
            d = min(e, b) - max(s, a)
            if d > 0:
                key = re.sub(r"\(\d+\)$", "", name)
                executables[key] = executables.get(key, 0.0) + d / n_dev

    idle: dict[str, float] = {}
    for lo, hi, label in _innermost_segments(inner, a, b):
        gap = (hi - lo) - sum(u.covered(lo, hi) for u in unions) / n_dev
        if gap > 0:
            idle[label] = idle.get(label, 0.0) + gap

    spans: dict[str, SpanStat] = {}
    for name, s, e in inner:
        st = spans.setdefault(name, SpanStat())
        st.count += 1
        st.host_s += e - s
        st.busy_s += sum(u.covered(s, e) for u in unions) / n_dev
    return Reduction(window_s=b - a, busy_s=busy, n_devices=n_dev, executables=executables, idle_by_span=idle, spans=spans)


def reduce_profile(profile, spans: tuple[str, ...]) -> Reduction:
    """Reduce a ``jax.profiler.ProfileData``; times come out in seconds."""
    ns = 1e-9
    ops, mods, host = [], [], []
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            plane_ops, plane_mods = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    plane_ops = [(e.start_ns * ns, (e.start_ns + e.duration_ns) * ns) for e in line.events]
                elif line.name == MODULES_LINE:
                    plane_mods = [(e.name, e.start_ns * ns, (e.start_ns + e.duration_ns) * ns) for e in line.events]
            ops.append(plane_ops)
            mods.append(plane_mods)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, e.start_ns * ns, (e.start_ns + e.duration_ns) * ns) for e in line.events if e.name in spans]
    return reduce_events(ops, mods, host)


def reduce_dir(trace_dir: str, spans: tuple[str, ...]) -> Reduction:
    """Reduce the one trace that ``jax.profiler`` wrote under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, found {files}")
    return reduce_profile(ProfileData.from_file(files[0]), spans)

"""The program's own phase spans (``repro.obs.trace.Tracer.phase``: a
snapshot's copies and topology loop, a save's writes, seal and commit, a
restore's reads and CRCs, a load's transposes) in the traced window.

The harness reduces the trace by the benchmark's own spans
(``harness.SPANS``).  A per-layer metric that reads a phase of the program
takes it from that reduction where it is there, and otherwise reduces the
same trace file once more with the program's phase names added, once per
run.  A program that opens no phase spans leaves them out of the trace: the
metric then reads nothing.
"""

from __future__ import annotations

import functools

from bench import trace_reduce
from bench.harness import SPANS


def _phase_names() -> tuple[str, ...]:
    try:
        from repro.obs.trace import PHASE_SPANS
    except ImportError:  # a program that opens no phase spans
        return ()
    return PHASE_SPANS


@functools.lru_cache(maxsize=1)  # the six readers of one run share it
def _reduce(trace_dir: str, spans: tuple[str, ...]) -> trace_reduce.Reduction:
    return trace_reduce.reduce_dir(trace_dir, spans=spans)


def stat(run, name: str) -> trace_reduce.SpanStat | None:
    """Count and in-window host time of the spans called ``name``."""
    if run.trace is None:
        return None
    if name in run.trace.spans or not run.trace_dir:
        return run.trace.spans.get(name)
    return _reduce(run.trace_dir, SPANS + _phase_names()).spans.get(name)


def per_save(run, name: str) -> float | None:
    """Host time inside ``name`` over the saves in the window."""
    st = stat(run, name)
    return st.host_s / len(run.saves) if st and run.saves else None


def per_restore(run, name: str) -> float | None:
    """Host time inside ``name`` over the restores in the window."""
    st = stat(run, name)
    return st.host_s / len(run.restores) if st and run.restores else None

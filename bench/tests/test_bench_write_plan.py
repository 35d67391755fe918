"""The reader of ``write_plan_s``: host time in the program's ``ckpt.plan``
span per save, on hand-made reductions and on a recorded trace file."""

import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import spec  # noqa: E402
from bench.harness import Save  # noqa: E402
from test_bench_phase_spans import FakeClock, _run  # noqa: E402


def _metric():
    bench = spec.Benchmark(str(ROOT))
    (metric,) = [m for m in bench.metrics if m.name == "write_plan_s"]
    return metric


def test_write_plan_s_is_declared_for_the_snapshot_cell():
    (entry,) = [m for m in json.loads((ROOT / "BENCHMARK.json").read_text())["per_layer"] if m["name"] == "write_plan_s"]
    assert entry["layer"] == "writer and container"
    assert entry["moves"] == "save_stall_s"
    metric = _metric()
    assert (metric.kind, metric.source, metric.unit) == ("per_layer", "program_span", "s")
    assert metric.workloads == ["cfd_karman_snap"]


def test_write_plan_s_reads_the_plan_span_per_save():
    from bench.trace_reduce import reduce_events
    from repro.obs.trace import PHASE_SPANS

    assert "ckpt.plan" in PHASE_SPANS
    # two saves; planning takes 0.25 s in the first and 1.5 s in the
    # second, and runs a third time half outside the window
    spans = [("window", 0.0, 10.0)]
    for t, plan in ((1.0, 0.25), (6.0, 1.5)):
        spans += [("snapshot", t, t + 3.0), ("ckpt.save", t + 0.5, t + 2.5), ("ckpt.plan", t + 0.5, t + 0.5 + plan)]
    spans.append(("ckpt.plan", 9.5, 10.5))
    rec = _run(1.0, FakeClock())
    rec.saves = [Save(2.9, 2.3), Save(2.9, 2.3)]
    rec.trace = reduce_events([[(0.0, 1.0)]], [[]], spans)
    read = _metric().reader
    assert read.read(rec) == pytest.approx((0.25 + 1.5 + 0.5) / 2)
    # a program that opens no phase spans: nothing to read, no error
    rec.trace = reduce_events([[(0.0, 1.0)]], [[]], [s for s in spans if s[0] != "ckpt.plan"])
    assert read.read(rec) is None
    rec.trace = None
    assert read.read(rec) is None
    # no save in the window
    rec.trace = reduce_events([[(0.0, 1.0)]], [[]], spans)
    rec.saves = []
    assert read.read(rec) is None


def test_write_plan_s_reads_nothing_in_a_trace_without_phases(tmp_path):
    """The recorded v5e trace holds the bench's spans only, as a parent's
    traced run does: the reader reduces the file again and finds nothing."""
    from bench import program_spans, trace_reduce

    trace_dir = tmp_path / "trace"
    trace_dir.mkdir()
    shutil.copy(ROOT / "bench" / "tests" / "data" / "probe.xplane.pb", trace_dir / "probe.xplane.pb")
    rec = _run(1.0, FakeClock())
    rec.trace_dir = str(trace_dir)
    rec.saves = [Save(2.9, 2.3)]
    rec.trace = trace_reduce.reduce_dir(rec.trace_dir, spans=program_spans.SPANS)
    assert _metric().reader.read(rec) is None

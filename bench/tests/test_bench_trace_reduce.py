"""``bench/trace_reduce.py`` on hand-made events with known overlaps, and on
a small trace recorded on a TPU v5e (``data/probe.xplane.pb``: three
iterations of two jitted programs inside ``step`` spans, each followed by a
device-to-host copy and a 50 ms sleep inside ``snapshot`` spans, all inside
a ``window`` span)."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import trace_reduce as tr  # noqa: E402

PROBE = ROOT / "bench" / "tests" / "data" / "probe.xplane.pb"


def test_merge_and_covered():
    assert tr.merge([(5, 7), (0, 2), (1, 3), (3, 4), (9, 9)]) == [(0, 4), (5, 7)]
    u = tr.Union([(0, 2), (1, 3), (5, 7)])
    assert u.covered(0, 10) == 5
    assert u.covered(2, 6) == 2  # 2..3 and 5..6
    assert u.covered(3, 5) == 0
    assert u.covered(6, 6) == 0


def test_hand_made_events():
    # window 0..100; ops overlap (10..30 ∪ 20..40 = 30) and one leaks out of
    # the window (90..120 counts 10); spans: step 0..50, snapshot 50..100
    # with a nested save 60..100
    ops = [[(10, 30), (20, 40), (90, 120)]]
    mods = [[("jit_step(123)", 10, 40), ("jit_step(123)", 90, 120), ("jit_pack(9)", 95, 96)]]
    spans = [("window", 0, 100), ("step", 0, 50), ("snapshot", 50, 100), ("save", 60, 100)]
    r = tr.reduce_events(ops, mods, spans)
    assert r.window_s == 100
    assert r.busy_s == 40
    assert r.idle_pct == pytest.approx(60.0)
    assert r.executables == {"jit_step": 40, "jit_pack": 1}
    # idle: step 50 - 30 busy = 20; snapshot (50..60, outside save) 10;
    # save 40 - 10 busy = 30
    assert r.idle_by_span == {"step": 20, "snapshot": 10, "save": 30}
    assert r.spans["step"].busy_s == 30 and r.spans["save"].busy_s == 10
    assert r.breakdown()["idle_gaps"][0] == ["save", 30]


def test_two_chips_average_and_uncovered_time():
    ops = [[(0, 10)], [(0, 30)]]
    r = tr.reduce_events(ops, [[], []], [("window", 0, 40), ("step", 0, 20)])
    assert r.busy_s == 20
    assert r.idle_by_span == {"step": 5, "host": 15}


def test_needs_one_window_and_a_device():
    with pytest.raises(ValueError):
        tr.reduce_events([[(0, 1)]], [[]], [("step", 0, 1)])
    with pytest.raises(ValueError):
        tr.reduce_events([], [], [("window", 0, 1)])


def test_recorded_tpu_trace():
    from jax.profiler import ProfileData

    r = tr.reduce_profile(ProfileData.from_file(str(PROBE)), spans=("window", "step", "snapshot"))
    assert r.n_devices == 1
    assert r.window_s == pytest.approx(0.1645, abs=1e-3)
    # three pairs of programs, 196 µs and 576 µs of device time a pair; this
    # trace's device clock reads about 1.1 ms ahead of the host's, so the
    # first pair, launched as the window opened, lies before the window span
    assert r.busy_s == pytest.approx(2 * (196.0e-6 + 576.0e-6), rel=0.01)
    assert list(r.executables) == ["jit__lambda"]
    assert r.spans["step"].count == 3 and r.spans["snapshot"].count == 3
    # the sleeps inside the snapshot spans are idle time
    assert r.idle_by_span["snapshot"] > 3 * 0.05
    assert 0 < r.idle_pct < 100

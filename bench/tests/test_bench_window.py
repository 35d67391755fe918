"""The whole-period window and the metric arithmetic, on a fake clock."""

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


@pytest.mark.parametrize(
    "seconds,period,periods",
    [(10.0, 3.0, 4), (9.0, 3.0, 3), (0.5, 3.0, 1), (20.0, 5.35, 4)],
)
def test_window_closes_at_the_first_boundary_at_or_after_seconds(seconds, period, periods):
    clock = FakeClock(12.5)  # set-up ends 12.5 s after the process started
    rec = _run(seconds, clock)
    n = 0
    with rec.window():
        while True:
            clock.t += period
            n += 1
            if rec.boundary():
                break
    assert n == periods
    assert rec.setup_s == 12.5
    assert rec.window_s == pytest.approx(periods * period)
    assert rec.compiles_in_window == 0


def test_loop_metrics_over_whole_periods():
    # four periods of 10 steps (2.45 s) and a snapshot (2.9 s, of which the
    # writer's own timer says 2.3 s)
    clock = FakeClock(10.0)
    rec = _run(20.0, clock)
    with rec.window():
        while True:
            clock.t += 2.45
            rec.steps += 10
            clock.t += 2.9
            rec.saves.append(Save(stall_s=2.9, write_s=2.3, fsync_s=1.1))
            if rec.boundary():
                break
    read = _readers()
    assert rec.steps == 40
    assert read["loop_steps_per_s"].read(rec) == pytest.approx(40 / (4 * 5.35))
    assert read["save_stall_s"].read(rec) == pytest.approx(2.9)
    assert read["write_s"].read(rec) == pytest.approx(2.3)
    assert read["stage_s"].read(rec) == pytest.approx(0.6)
    assert read["fsync_s"].read(rec) == pytest.approx(1.1)
    assert read["setup_s"].read(rec) == 10.0
    for name in ("resume_s", "read_s", "load_s", "step_s", "device_idle.loop", "device_idle.steer"):
        assert read[name].read(rec) is None  # nothing of theirs in this run


def test_restore_metrics_are_means_over_the_restores():
    clock = FakeClock(5.0)
    rec = _run(3.0, clock)
    times = [(1.2, 0.8), (1.0, 0.7), (1.4, 0.9)]
    with rec.window():
        for resume, read_s in times:
            clock.t += resume + 0.25
            rec.restores.append(Restore(resume_s=resume, read_s=read_s))
            rec.steps += 1
            if rec.boundary():
                break
    read = _readers()
    assert len(rec.restores) == 3
    assert read["resume_s"].read(rec) == pytest.approx(1.2)
    assert read["read_s"].read(rec) == pytest.approx(0.8)
    assert read["load_s"].read(rec) == pytest.approx(0.4)
    assert read["loop_steps_per_s"].read(rec) is None
    assert read["fsync_s"].read(rec) is None


def test_trace_metrics_read_the_reduction():
    from bench.trace_reduce import reduce_events

    rec = _run(1.0, FakeClock())
    rec.steps = 20
    rec.saves = [Save(2.9, 2.3), Save(2.9, 2.3)]
    rec.trace = reduce_events(
        [[(0.0, 2.4), (5.0, 7.4)]],
        [[]],
        [("window", 0.0, 10.0), ("step", 0.0, 2.5), ("step", 5.0, 7.5), ("snapshot", 2.5, 5.0), ("snapshot", 7.5, 10.0)],
    )
    read = _readers()
    assert read["step_s"].read(rec) == pytest.approx(4.8 / 20)
    # one reader, device_idle.py, for both splits; the cells they are read
    # in are their entries' workloads
    assert read["device_idle.loop"].read(rec) == pytest.approx(52.0)
    assert read["device_idle.steer"].__file__ == read["device_idle.loop"].__file__


def test_a_loop_that_never_reaches_a_boundary_is_an_error():
    rec = _run(1.0, FakeClock())
    with pytest.raises(RuntimeError):
        with rec.window():
            pass

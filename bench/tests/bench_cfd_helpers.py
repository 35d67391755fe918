"""Shared by the fluid cells' CPU tests: a cell of ``BENCHMARK.json`` at a
size a test run holds, driven through the harness with the look for a chip
skipped."""

import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

SMALL = {"nx": 32, "ny": 128}  # 16 d-grids of 16 x 16, three multigrid levels
SEED = 2**33 + 17  # wider than 32 bits, as a run's seed may be


def small_cell(name: str, **config):
    from bench.spec import Benchmark

    cell = Benchmark(str(ROOT)).cell(name)
    cell.config.update(SMALL, **config)
    return cell


def run(cell, seed: int = SEED, seconds: float = 0.3) -> dict:
    from bench.harness import run_cell

    with tempfile.TemporaryDirectory() as workdir:
        return run_cell(cell, seed=seed, seconds=seconds, trace=False, workdir=workdir, t_process=time.perf_counter())


def fields_unchanged(monkeypatch):
    """Fault: the solver step returns the fields it was given (the clock
    still advances, so the loop runs on)."""
    import repro.cfd.sim as sim_mod

    def make_step(cfg):
        return lambda s: {**s, "t": s["t"] + cfg.dt}

    monkeypatch.setattr(sim_mod, "make_step", make_step)

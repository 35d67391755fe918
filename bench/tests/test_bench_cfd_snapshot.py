"""The snapshot cell's check, at a small size on the CPU: a sound run is
correct; the controls and each fault the cell can have make it not correct.

The faults are planted underneath the timed path (the look for a chip is
skipped, the rest of a run is driven as the benchmark drives it).  The
exchange between chips is not among them: the cell runs on one chip.
"""

import numpy as np
import pytest

from bench_cfd_helpers import SEED, fields_unchanged, run, small_cell


def test_sound_run_is_correct():
    r = run(small_cell("cfd_karman_snap"))
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 1
    assert r["checks"]["file_mismatch"]["value"] == 0
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {"setup_s", "loop_steps_per_s", "save_stall_s"}


def test_snapshot_cadence_is_one_advected_cell():
    cell = small_cell("cfd_karman_snap")
    c, ref = cell.config, cell.reference
    g = ref.geometry(c)
    every = cell.system.snapshot_every(c, ref, 1.0)
    assert (every - 1) * c["u_in"] * g["dt"] < g["h"] <= every * c["u_in"] * g["dt"]
    full = dict(c, nx=3072, ny=12288)
    assert cell.system.snapshot_every(full, ref, 1.0) == 39  # dt = 80 h^2 there: 38.4 steps a cell


def test_control_bf16_reference_fails_the_solver_limit():
    r = run(small_cell("cfd_karman_snap", control="bf16_reference"))
    assert not r["correct"]
    assert r["checks"]["solver_err"]["value"] > 3 * r["checks"]["solver_err"]["limit"]
    assert r["checks"]["file_mismatch"]["value"] == 0


def test_control_lossy_codec_is_not_correct():
    r = run(small_cell("cfd_karman_snap", codec="int8-blockq"))
    assert not r["correct"]
    assert r["checks"]["file_mismatch"]["value"] > 0


def test_fault_step_leaves_fields_unchanged(monkeypatch):
    fields_unchanged(monkeypatch)
    r = run(small_cell("cfd_karman_snap"))
    assert not r["correct"]
    assert r["checks"]["solver_err"]["value"] > r["checks"]["solver_err"]["limit"]


@pytest.mark.parametrize("fault", ["half_the_grids_left_out", "one_value_altered"])
def test_fault_in_what_the_snapshot_stages(monkeypatch, fault):
    from repro.cfd.sim import Simulation

    pack = Simulation._pack_cells

    def broken(self):
        cells = np.array(pack(self))
        if fault == "half_the_grids_left_out":
            cells[cells.shape[0] // 2 :] = 0.0
        else:
            cells[3, 7, 1] += 1e-3
        return cells

    monkeypatch.setattr(Simulation, "_pack_cells", broken)
    r = run(small_cell("cfd_karman_snap"))
    assert not r["correct"]
    assert r["checks"]["file_mismatch"]["value"] > 0


def test_reference_builds_the_scenario_of_the_program():
    """The bench's own state and solver settings are ``karman_vortex``'s:
    with no perturbation, the same cell types, fields, dt, h and ν."""
    import jax.numpy as jnp

    from repro.cfd.multigrid import MGConfig
    from repro.cfd.scenarios import karman_vortex

    cell = small_cell("cfd_karman_snap", perturbation=0.0)
    c, ref = cell.config, cell.reference
    cfg, state = karman_vortex(nx=c["nx"], ny=c["ny"])
    mine = ref.initial_state(c, SEED)
    assert np.array_equal(np.asarray(mine["cell_type"]), np.asarray(state["cell_type"]))
    for f in ("u", "v", "p", "T", "T_solid", "t"):
        assert mine[f].dtype == jnp.float32
        assert np.array_equal(np.asarray(mine[f]), np.asarray(state[f])), f
    g = ref.geometry(c)
    assert (g["h"], g["dt"], g["nu"]) == (cfg.h, cfg.dt, cfg.nu)
    assert cfg.mg == MGConfig(**c["mg"]) and cfg.mg_cycles == c["mg_cycles"]

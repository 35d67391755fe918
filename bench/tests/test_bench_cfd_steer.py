"""The steering cell's check, at a small size on the CPU: a sound run is
correct; the controls and each fault the cell can have make it not correct
(the exchange between chips is not among them: the cell runs on one chip)."""

import numpy as np
import pytest

from bench_cfd_helpers import fields_unchanged, run, small_cell


def test_sound_run_is_correct():
    r = run(small_cell("cfd_karman_steer"))
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 1
    assert r["checks"]["restore_mismatch"]["value"] == 0
    assert set(r["metrics"]) == {"setup_s", "resume_s"}


def test_control_bf16_reference_fails_the_solver_limit():
    r = run(small_cell("cfd_karman_steer", control="bf16_reference"))
    assert not r["correct"]
    assert r["checks"]["solver_err"]["value"] > 3 * r["checks"]["solver_err"]["limit"]


def test_control_lossy_codec_is_not_correct():
    r = run(small_cell("cfd_karman_steer", codec="int8-blockq"))
    assert not r["correct"]
    assert r["checks"]["restore_mismatch"]["value"] > 0


def test_fault_step_leaves_fields_unchanged(monkeypatch):
    fields_unchanged(monkeypatch)
    r = run(small_cell("cfd_karman_steer"))
    assert not r["correct"]
    assert r["checks"]["solver_err"]["value"] > r["checks"]["solver_err"]["limit"]


@pytest.mark.parametrize("fault", ["nothing_loaded", "half_the_grids_left_out", "one_value_altered"])
def test_fault_in_the_restore(monkeypatch, fault):
    from repro.cfd.sim import Simulation

    load = Simulation._load

    def broken(self, snap):
        if fault == "nothing_loaded":
            return
        cells = np.array(snap["current_cell_data"])
        if fault == "half_the_grids_left_out":
            cells[cells.shape[0] // 2 :] = 0.0
        else:
            cells[3, 7, 1] += 1e-3
        load(self, {**snap, "current_cell_data": cells})

    monkeypatch.setattr(Simulation, "_load", broken)
    r = run(small_cell("cfd_karman_steer"))
    assert not r["correct"]
    assert r["checks"]["restore_mismatch"]["value"] > 0

"""The snapshot's rows staged on the device: bit for bit the host packing
they replace (``to_blocked``, the interior, ``np.stack`` of the fields), in
C order as ``CheckpointManager.save`` receives them, and lossless through a
restore.

The fields are set to random values and the clock moved by hand, not
stepped: staging reads the state only, and the 40 x 160 channel (d-grids of
8², since 40 is no multiple of 16) has no multigrid hierarchy to step with.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.cfd.scenarios import karman_vortex
from repro.cfd.sim import FIELDS, Simulation, stage_rows
from repro.cfd.spacetree import to_blocked
from repro.core.checkpoint import CheckpointManager

CHANNELS = [(32, 128, 16), (40, 160, 8)]  # nx, ny, the d-grid side it gets
LAYOUTS = [(2, 2, 16), (1, 2, 8), (1, 1, 32), (3, 4, 16)]  # gx, gy, n


def host_cells(sim):
    blocks = []
    for f in FIELDS:
        b = to_blocked(sim.layout, sim.state[f])[:, 1:-1, 1:-1]
        blocks.append(np.asarray(b).reshape(sim.layout.G, -1))
    return np.stack(blocks, axis=-1)


def host_cell_type(sim):
    ct = to_blocked(sim.layout, sim.state["cell_type"].astype(jnp.float32))[:, 1:-1, 1:-1]
    return np.asarray(ct).astype(np.int8).reshape(sim.layout.G, -1)


def advance(sim, steps, rng):
    """New field values in every cell, and the clock ``steps`` steps on."""
    for f in FIELDS:
        sim.state[f] = jnp.asarray(rng.standard_normal(sim.state[f].shape, np.float32))
    sim.state["t"] = sim.state["t"] + np.float32(steps * sim.cfg.dt)


def device_fields(sim):
    return {f: np.asarray(sim.state[f]) for f in (*FIELDS, "t")}


@pytest.fixture(params=CHANNELS, ids=lambda c: f"{c[0]}x{c[1]}")
def sim(request, tmp_path):
    nx, ny, n = request.param
    cfg, state = karman_vortex(nx=nx, ny=ny)
    mgr = CheckpointManager(str(tmp_path / "run.th5"))
    sim = Simulation(cfg, state, mgr)
    assert sim.layout.n == n
    sim.rng = np.random.default_rng(nx)
    advance(sim, 2, sim.rng)
    yield sim
    mgr.close()


def test_staged_rows_are_the_host_packing(sim):
    cells = sim._pack_cells()
    ref = host_cells(sim)
    assert cells.shape == ref.shape == (sim.layout.G, sim.layout.n**2, len(FIELDS))
    assert cells.dtype == ref.dtype == np.float32
    assert cells.tobytes() == ref.tobytes()
    ct = sim._stage((sim.state["cell_type"],), dtype=jnp.int8)
    ref_ct = host_cell_type(sim)
    assert ct.dtype == np.int8 and ct.shape == ref_ct.shape
    assert np.array_equal(ct, ref_ct)
    assert len(np.unique(ct)) > 2  # walls, inflow, outflow, cylinder, fluid


def test_snapshot_hands_the_save_c_ordered_rows(sim, monkeypatch):
    saved = []
    save = sim.manager.save
    monkeypatch.setattr(sim.manager, "save", lambda step, state, **kw: saved.append(state) or save(step, state, **kw))
    cells, ct = host_cells(sim), host_cell_type(sim)
    sim.snapshot()
    advance(sim, 1, sim.rng)
    sim.snapshot()
    for state in saved:
        for name in ("current_cell_data", "previous_cell_data", "cell_type"):
            assert state[name].flags.c_contiguous, name
    assert saved[0]["current_cell_data"].tobytes() == cells.tobytes()
    assert np.array_equal(saved[0]["cell_type"], ct)
    assert saved[1]["previous_cell_data"] is saved[0]["current_cell_data"]


def test_snapshot_restore_snapshot_gives_back_the_device_fields(sim):
    a = sim.snapshot()
    at_a = device_fields(sim)
    advance(sim, 3, sim.rng)
    assert sim.restore(a) == a
    for f, want in at_a.items():
        assert np.array_equal(np.asarray(sim.state[f]), want), f
    advance(sim, 1, sim.rng)
    b = sim.snapshot()
    at_b = device_fields(sim)
    _, snap = sim.manager.restore(b)
    assert snap["current_cell_data"].tobytes() == host_cells(sim).tobytes()
    advance(sim, 2, sim.rng)
    assert sim.restore(b) == b
    for f, want in at_b.items():
        assert np.array_equal(np.asarray(sim.state[f]), want), f


def numpy_rows(fields, gx, gy, n, dtype):
    """Row ``g = i·gy + j`` is d-grid (i, j): its n x n interior cells in
    row-major order, the fields interleaved cell by cell."""
    x = np.stack(fields, axis=-1).astype(dtype)  # (gx·n, gy·n, F)
    return x.reshape(gx, n, gy, n, len(fields)).transpose(0, 2, 1, 3, 4).reshape(gx * gy, n * n * len(fields))


@pytest.mark.parametrize("gx, gy, n", LAYOUTS)
@pytest.mark.parametrize("dtype, n_fields", [(np.float32, 4), (np.int8, 1)], ids=["cells", "cell_type"])
def test_stage_rows_is_the_numpy_packing(gx, gy, n, dtype, n_fields):
    rng = np.random.default_rng(gx * 100 + gy * 10 + n)
    shape = (gx * n, gy * n)
    if dtype == np.int8:  # cell types, as the state holds them
        fields = [rng.integers(0, 5, shape).astype(np.int8)]
    else:
        fields = [rng.standard_normal(shape, np.float32) for _ in range(n_fields)]
    got = np.asarray(stage_rows(tuple(jnp.asarray(f) for f in fields), gx=gx, gy=gy, n=n, dtype=dtype))
    want = numpy_rows(fields, gx, gy, n, dtype)
    assert got.shape == want.shape == (gx * gy, n * n * n_fields)
    assert got.dtype == want.dtype
    assert got.flags.c_contiguous
    assert got.tobytes() == want.tobytes()


def test_snapshots_of_one_layout_do_not_retrace_the_stage(sim):
    sim.snapshot()
    traced = stage_rows._cache_size()
    advance(sim, 1, sim.rng)
    sim.snapshot()
    assert stage_rows._cache_size() == traced

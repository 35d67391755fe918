"""Simulation driver: mpfluid-style stepping + the paper's I/O kernel.

Snapshots follow the paper's file structure exactly (Fig. 4): per step the
state is stored as **row-per-d-grid 2-D datasets** (``current_cell_data``
= the packed (u, v, p, T) cells of every grid, ``previous_cell_data`` for
the time-reversal restart of explicit Euler, ``cell_type`` boundary
conditions) plus the topology datasets (``grid_property`` UIDs in Morton
order, ``subgrid_uid``, physical ``bounding_box``) that feed the offline
sliding window.  Rollback/branching delegates to ``core.steering``.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..core.checkpoint import CheckpointManager
from ..core.steering import BranchManager
from ..obs.trace import (
    SPAN_SIM_FETCH,
    SPAN_SIM_LAYOUT,
    SPAN_SIM_LOAD,
    SPAN_SIM_SNAPSHOT,
    SPAN_SIM_TOPOLOGY,
    TRACER,
)
from .projection import FluidConfig, make_step
from .spacetree import TreeLayout, topology_arrays

FIELDS = ("u", "v", "p", "T")


@partial(jax.jit, static_argnames=("gx", "gy", "n", "dtype"))
def stage_rows(fields: tuple, gx: int, gy: int, n: int, dtype=None) -> jax.Array:
    """(gx·n, gy·n) fields → (G, n²·F) rows in the file's row order.

    Row ``g`` is d-grid ``g`` in ``to_blocked``'s order; element
    ``[g, c·F + f]`` is field ``f`` at interior cell ``c`` (row-major in
    the d-grid), so the rows are the fields interleaved cell by cell.  The
    minor dimension is the whole row, so the copy to the host arrives
    C-ordered and needs no reordering there.
    """
    x = jnp.stack(fields, axis=-1)
    if dtype is not None:
        x = x.astype(dtype)
    return x.reshape(gx, n, gy, n, len(fields)).transpose(0, 2, 1, 3, 4).reshape(gx * gy, -1)


@dataclass
class Simulation:
    cfg: FluidConfig
    state: dict
    manager: CheckpointManager
    n_block: int = 16
    n_ranks: int = 4

    def __post_init__(self):
        self._step_fn = make_step(self.cfg)
        n = self.n_block
        while self.cfg.nx % n or self.cfg.ny % n:
            n //= 2
        self.layout = TreeLayout(gx=self.cfg.nx // n, gy=self.cfg.ny // n, n=n, h=self.cfg.h)
        self._prev_cells: np.ndarray | None = None

    # -- time stepping ------------------------------------------------------------

    def run(self, n_steps: int, snapshot_every: int = 0) -> dict:
        import jax

        for i in range(n_steps):
            if snapshot_every and i % snapshot_every == 0:
                self.snapshot()
            self.state = self._step_fn(self.state)
        jax.block_until_ready(self.state)  # honest wall-clock at loop exit
        return self.state

    @property
    def step_index(self) -> int:
        return int(round(float(self.state["t"]) / self.cfg.dt))

    # -- the paper's output layout ---------------------------------------------------

    def _stage(self, fields: tuple, dtype=None) -> np.ndarray:
        """The fields' (G, n²·F) rows, built on the device and fetched."""
        lay = self.layout
        with TRACER.phase(SPAN_SIM_FETCH) as fetch:
            rows = np.asarray(stage_rows(fields, gx=lay.gx, gy=lay.gy, n=lay.n, dtype=dtype))
            fetch.tag("bytes", rows.nbytes)
        return rows

    def _pack_cells(self) -> np.ndarray:
        """Blocked (G, n², n_fields) cell rows — the linear write buffer."""
        cells = self._stage(tuple(self.state[f] for f in FIELDS))
        return cells.reshape(self.layout.G, self.layout.n**2, len(FIELDS))  # a view

    def snapshot(self) -> int:
        with TRACER.phase(SPAN_SIM_SNAPSHOT) as snap:
            with TRACER.phase(SPAN_SIM_FETCH, bytes=self.state["t"].nbytes):
                step = self.step_index  # waits for the steps queued before it
            snap.tag("step", step)
            cells = self._pack_cells()
            prev = self._prev_cells if self._prev_cells is not None else cells
            ct = self._stage((self.state["cell_type"],), dtype=jnp.int8)  # (G, n²)
            with TRACER.phase(SPAN_SIM_TOPOLOGY, grids=self.layout.G):
                uids, subgrid, boxes, rank_of = topology_arrays(self.layout, self.n_ranks)
            self.manager.save(
                step,
                {
                    "current_cell_data": cells,
                    "previous_cell_data": prev,
                    "cell_type": ct,
                    "t": np.float64(self.state["t"]),
                },
                n_ranks=self.n_ranks,
                topology_override=(uids, subgrid, boxes),
                extra_attrs={"sim_time": float(self.state["t"]), "fields": list(FIELDS)},
            )
            self._prev_cells = cells
        return step

    # -- restart / TRS -----------------------------------------------------------------

    def restore(self, step: int | None = None) -> int:
        step, snap = self.manager.restore(step)
        self._load(snap)
        return step

    def _load(self, snap: dict) -> None:
        with TRACER.phase(SPAN_SIM_LOAD):
            cells = snap["current_cell_data"]  # (G, n², F)
            lay = self.layout
            for fi, f in enumerate(FIELDS):
                with TRACER.phase(SPAN_SIM_LAYOUT) as layout:
                    comp = (
                        cells[:, :, fi]
                        .reshape(lay.gx, lay.gy, lay.n, lay.n)
                        .transpose(0, 2, 1, 3)
                        .reshape(lay.gx * lay.n, lay.gy * lay.n)
                    )
                    layout.tag("bytes", comp.nbytes)
                self.state[f] = jnp.asarray(comp, jnp.float32)
            self.state["t"] = jnp.asarray(np.float32(snap["t"]))
            self._prev_cells = np.asarray(snap["previous_cell_data"])

    def branch(self, at_step: int, child_path: str, overlay: dict | None = None, **state_edits: Any) -> "Simulation":
        """TRS: reload ``at_step``, apply steering edits, continue in a new
        branching file (paper §4)."""
        bm = BranchManager(self.manager)
        child = bm.branch(at_step, child_path, overlay=overlay)
        _, snap = bm.restore(at_step)
        sim = Simulation(
            cfg=self.cfg,
            state=dict(self.state),
            manager=child.manager,
            n_block=self.n_block,
            n_ranks=self.n_ranks,
        )
        sim._load(snap)
        for k, v in state_edits.items():  # e.g. moved obstacle, new lamp T
            sim.state[k] = v
        return sim

"""Fault-tolerant training loop — the paper's I/O kernel as the backbone.

Features (the large-scale-runnability checklist):
  * **async checkpointing** through ``core.AsyncCheckpointer`` (compute
    never waits on pwrite — the paper's §1 'all processes have to wait'
    problem, removed);
  * **auto-resume**: on start, the newest *checksum-valid* snapshot is
    restored (torn writes are invisible thanks to shadow paging; bit-rot
    falls back one snapshot);
  * **TRS for training**: ``branch_from`` rolls back to any snapshot with a
    config overlay (e.g. lowered LR after a loss spike) in a new branching
    file — the paper's steering concept applied to LM training;
  * **straggler watchdog**: per-step wall-time EWMA; steps slower than
    ``straggler_factor``× the EWMA are logged and counted (at real scale
    the callback triggers aggregator re-election / checkpoint-exclude);
  * deterministic data: the pipeline state inside the snapshot is (seed,
    step) — resume is exact (tested).
"""

from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass
from functools import partial
from typing import Callable

import jax

from ..core.checkpoint import AsyncCheckpointer, CheckpointManager
from ..core.steering import BranchManager
from ..distributed import sharding
from ..models.common import ModelConfig
from .data import DataConfig, TokenStream
from .steps import TrainSetup, init_train_state, make_train_step


@dataclass
class TrainerConfig:
    total_steps: int = 100
    checkpoint_every: int = 20
    log_every: int = 10
    async_checkpoint: bool = True
    straggler_factor: float = 3.0
    keep_metrics: bool = True


@dataclass
class StragglerStats:
    ewma_s: float = 0.0
    flagged: int = 0
    slowest_s: float = 0.0


class Trainer:
    def __init__(
        self,
        cfg: ModelConfig,
        manager: CheckpointManager,
        *,
        setup: TrainSetup | None = None,
        data: DataConfig | None = None,
        tcfg: TrainerConfig | None = None,
        mesh=None,
    ):
        self.cfg = cfg
        self.setup = setup or TrainSetup()
        self.tcfg = tcfg or TrainerConfig()
        self.manager = manager
        self.async_ckpt = AsyncCheckpointer(manager)
        self.stream = TokenStream(cfg, data or DataConfig())
        self.mesh = mesh
        step_fn, state_specs, batch_specs = make_train_step(cfg, mesh=mesh, setup=self.setup)
        init = partial(init_train_state, cfg=cfg, setup=self.setup)
        if mesh is None:
            self.state_sharding = self.batch_sharding = None
            self.step_fn = jax.jit(step_fn, donate_argnums=0)
            self._init = jax.jit(init)
        else:
            # the state lives where the specs put it, fresh or resumed, and
            # the step keeps it there (outputs pinned to the input layout)
            state_sds = jax.eval_shape(init, jax.random.PRNGKey(0))
            state_specs = sharding.fix_specs(mesh, state_specs, state_sds)
            self.state_sharding = sharding.to_named(mesh, state_specs)
            self.batch_sharding = sharding.to_named(mesh, batch_specs)
            self.step_fn = jax.jit(
                step_fn,
                in_shardings=(self.state_sharding, self.batch_sharding),
                out_shardings=(self.state_sharding, None),
                donate_argnums=0,
            )
            self._init = jax.jit(init, out_shardings=self.state_sharding)
        self.state: dict | None = None
        self.metrics: list[dict] = []
        self.straggler = StragglerStats()

    # -- lifecycle ---------------------------------------------------------------

    def init_or_resume(self, seed: int = 0) -> int:
        """Fresh init, or restore the newest valid snapshot (auto-resume)."""
        latest = self.manager.latest_valid()
        if latest is not None:
            _, snap = self.manager.restore(latest)
            self.state = self.place(snap["train_state"])
            return int(self.state["step"])
        self.state = self._init(jax.random.PRNGKey(seed))
        return 0

    def place(self, state: dict) -> dict:
        """Host (or device) state → device arrays with this trainer's layout
        (the default device without a mesh)."""
        return jax.device_put(state, self.state_sharding)

    def _checkpoint(self, step: int) -> None:
        payload = {
            "train_state": self.state,
            "data": self.stream.state(step),
        }
        if self.tcfg.async_checkpoint:
            self.async_ckpt.save(step, payload, overwrite=True)
        else:
            self.manager.save(step, payload, overwrite=True)

    # -- the loop -----------------------------------------------------------------

    def run(self, n_steps: int | None = None, on_step: Callable | None = None) -> list[dict]:
        assert self.state is not None, "call init_or_resume() first"
        start = int(self.state["step"])
        end = start + (n_steps if n_steps is not None else self.tcfg.total_steps)
        for step in range(start, end):
            t0 = time.perf_counter()
            batch = jax.device_put(self.stream.batch(step), self.batch_sharding)
            self.state, metrics = self.step_fn(self.state, batch)
            loss = float(metrics["loss"])  # blocks → true step time
            dt = time.perf_counter() - t0
            self._watchdog(dt, step)
            if self.tcfg.keep_metrics:
                self.metrics.append({"step": step + 1, "loss": loss, "wall_s": dt})
            if on_step:
                on_step(step + 1, loss)
            if (step + 1) % self.tcfg.checkpoint_every == 0 or step + 1 == end:
                self._checkpoint(step + 1)
        self.async_ckpt.wait()
        return self.metrics

    def _watchdog(self, dt: float, step: int) -> None:
        s = self.straggler
        if s.ewma_s == 0.0:
            s.ewma_s = dt
        if dt > self.tcfg.straggler_factor * s.ewma_s:
            s.flagged += 1
            s.slowest_s = max(s.slowest_s, dt)
        s.ewma_s = 0.9 * s.ewma_s + 0.1 * dt

    # -- TRS ------------------------------------------------------------------------

    def branch_from(
        self, at_step: int, child_path: str, overlay: dict | None = None, **setup_edits
    ) -> "Trainer":
        """Roll training back to ``at_step`` and continue with altered
        hyper-parameters in a new branching file."""
        bm = BranchManager(self.manager)
        child_bm = bm.branch(at_step, child_path, overlay=overlay)
        _, snap = child_bm.restore(at_step)
        new_setup = dataclasses.replace(self.setup, **setup_edits) if setup_edits else self.setup
        t = Trainer(
            self.cfg,
            child_bm.manager,
            setup=new_setup,
            data=self.stream.dcfg,
            tcfg=self.tcfg,
            mesh=self.mesh,
        )
        t.state = t.place(snap["train_state"])
        return t

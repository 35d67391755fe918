"""Chip smoke test: the two device paths of TH5, end to end, on a TPU.

    python3 chip_smoke.py              # one chip: the cfd and train phases
    python3 chip_smoke.py --chips 4    # four chips: the cross-chip phases only

``cfd``: the Schäfer–Turek channel at 2048 × 8192 cells (65,536 d-grids of
16², about 0.55 GB per snapshot) through ``Simulation`` and a
``CheckpointManager``: steps, two snapshots, a restore that must give back
u, v, p and T bit for bit, and a TRS branch that steps and snapshots into
its own file.

``train``: ``Trainer`` on qwen3-8b at its published widths, cut to 2 layers
and one eighth of the vocabulary (about 542 M parameters; bf16 params plus
f32 master, mu and nu, about 7.6 GB).  One async save lands mid-run, the job
is killed before its next save, and a second ``Trainer`` resumes from the
file: the resumed state must equal the saved one bit for bit, and its losses
those of the uninterrupted run.

``--chips 4``: the train phase on a 2×2 (data, model) mesh, whose saves
gather every shard of the state to the host, against the same steps on one
chip.

Every phase prints one JSON line; the last line is the verdict
``{"ok": true, "device": {...}}``.  Without a TPU the script exits non-zero
and prints no verdict.  Run files live in a temporary directory removed at
exit.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
SEED = 0
# the 4-chip losses may differ from one chip by reduction order in bf16:
# at most 2 % of the loss (~5 bf16 ulps of relative error)
LOSS_BAND = 0.02


class Compiles:
    """Counts executables built (compiled or loaded from the persistent
    cache) and the seconds spent building them."""

    n = 0
    seconds = 0.0

    @classmethod
    def listen(cls, event: str, duration: float, **_) -> None:
        if event == COMPILE_EVENT:
            cls.n += 1
            cls.seconds += duration


class Killed(Exception):
    """Stands for the job dying after a step and before its checkpoint."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(what)


def log(**fields) -> None:
    print(json.dumps(fields), flush=True)


def peak_bytes() -> int:
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.devices())


def same_bits(a, b) -> bool:
    a, b = np.asarray(a), np.asarray(b)
    return a.dtype == b.dtype and a.shape == b.shape and a.tobytes() == b.tobytes()


def file_bytes(*paths: str) -> int:
    return sum(os.path.getsize(p) for p in paths if os.path.exists(p))


# -- cfd -----------------------------------------------------------------------


def cfd_phase(workdir: str, nx: int = 2048, ny: int = 8192, steps: int = 3) -> dict:
    from repro.cfd.scenarios import karman_vortex
    from repro.cfd.sim import FIELDS, Simulation
    from repro.core.checkpoint import CheckpointManager

    cfg, state = karman_vortex(nx=nx, ny=ny)
    path = os.path.join(workdir, "karman.th5")
    child_path = os.path.join(workdir, "karman_branch.th5")
    sim = Simulation(cfg, state, CheckpointManager(path, common={"scenario": "karman", "nx": nx, "ny": ny}))

    # warm-up: the first step and the first snapshot build every executable
    n0, s0 = Compiles.n, Compiles.seconds
    t = time.perf_counter()
    sim.run(1)
    first_step_s = time.perf_counter() - t
    sim.run(steps - 1)
    t = time.perf_counter()
    step_a = sim.snapshot()
    first_snapshot_s = time.perf_counter() - t
    host = {f: np.asarray(sim.state[f]) for f in FIELDS}
    compile_s = Compiles.seconds - s0
    warm_compiles = Compiles.n - n0

    n1 = Compiles.n
    t = time.perf_counter()
    sim.run(steps)
    step_s = (time.perf_counter() - t) / steps
    t = time.perf_counter()
    step_b = sim.snapshot()
    snapshot_s = time.perf_counter() - t
    written = file_bytes(path)

    t = time.perf_counter()
    check(sim.restore(step_a) == step_a, "restore returned another step")
    restored = {f: np.asarray(sim.state[f]) for f in FIELDS}
    restore_s = time.perf_counter() - t
    device = jax.devices()[0]
    for f in FIELDS:
        check(sim.state[f].devices() == {device}, f"restored {f} is not on {device}")
        check(same_bits(restored[f], host[f]), f"restored {f} differs from snapshot {step_a}")

    child = sim.branch(step_a, child_path, overlay={"trs": "replay"})
    child.run(1)
    step_c = child.snapshot()
    check(child.manager.steps() == [step_c] == [step_a + 1], "branch snapshot missing")
    after_warmup = Compiles.n - n1
    for s in (sim, child):
        for f in FIELDS:
            check(bool(jnp.isfinite(s.state[f]).all()), f"{f} not finite")
    sim.manager.close()
    child.manager.close()
    return {
        "phase": "cfd",
        "cells": nx * ny,
        "grids": sim.layout.G,
        "snapshots": [step_a, step_b, step_c],
        "compile_s": compile_s,
        "warmup_compiles": warm_compiles,
        "first_step_s": first_step_s,
        "step_s": step_s,
        "first_snapshot_s": first_snapshot_s,
        "snapshot_s": snapshot_s,
        "restore_s": restore_s,
        "bytes_written": written + file_bytes(child_path),
        "peak_bytes_in_use": peak_bytes(),
        "compiles_after_warmup": after_warmup,
        "restore_bit_identical": True,
        "branch_written": True,
        "finite": True,
    }


# -- train ---------------------------------------------------------------------


def qwen3_one_chip_share():
    """qwen3-8b at its published widths: depth cut to 2 layers (whole periods
    of its uniform pattern) and one eighth of the vocabulary, the share of one
    chip in an 8-way vocabulary-parallel layer."""
    from repro.configs import qwen3_8b

    return qwen3_8b.share(n_layers=2, vocab_div=8)


def _trainer(cfg, data, path: str, every: int, mesh=None):
    from repro.core.checkpoint import CheckpointManager
    from repro.train.trainer import Trainer, TrainerConfig

    class Recording(CheckpointManager):
        """Keeps the host tree of its last save: what a resume must give back."""

        saved = None

        def save(self, step, state, **kw):
            self.saved = state
            return super().save(step, state, **kw)

    return Trainer(
        cfg,
        Recording(path, common={"arch": cfg.name}),
        data=data,
        tcfg=TrainerConfig(checkpoint_every=every),
        mesh=mesh,
    )


def _run_until_killed(trainer, kill_at: int, hook=None) -> list[float]:
    """Run, and die after step ``kill_at`` before its checkpoint.  Returns
    the host time at which each step's state was ready."""
    ready: list[float] = []

    def on_step(step: int, loss: float) -> None:
        jax.block_until_ready(trainer.state)
        if hook:
            hook(step)
        ready.append(time.perf_counter())
        if step == kill_at:
            raise Killed

    try:
        trainer.run(kill_at - int(trainer.state["step"]), on_step=on_step)
    except Killed:
        pass
    trainer.async_ckpt.wait()  # the save already in flight still lands
    return ready


def reference_losses(cfg, data, workdir: str, steps: int) -> list[float]:
    """The first ``steps`` losses on one chip, with no checkpoint."""
    t = _trainer(cfg, data, os.path.join(workdir, "reference.th5"), every=steps + 1)
    t.init_or_resume(SEED)
    _run_until_killed(t, steps)
    t.manager.close()
    return [m["loss"] for m in t.metrics]


def _bulk(leaves: list, share: float = 0.99) -> list:
    """The largest leaves that together hold ``share`` of the elements."""
    leaves = sorted(leaves, key=lambda x: x.size, reverse=True)
    total, out, acc = sum(x.size for x in leaves), [], 0
    for x in leaves:
        if acc >= share * total:
            break
        out.append(x)
        acc += x.size
    return out


def train_phase(workdir: str, cfg, data, *, save_at: int = 3, kill_at: int = 5, mesh=None) -> dict:
    # one save mid-run, and at least two steps after the resume: the second
    # one shows whether the resumed state recompiles the step
    check(save_at < kill_at < 2 * save_at and kill_at - save_at >= 2, "bad save/kill steps")
    path = os.path.join(workdir, "train.th5")
    t1 = _trainer(cfg, data, path, every=save_at, mesh=mesh)
    check(t1.init_or_resume(SEED) == 0, "fresh run did not start at step 0")
    n0, s0 = Compiles.n, Compiles.seconds
    marks: dict = {}

    def mark(step: int) -> None:
        if step == 1:  # the first step is the warm-up
            marks["warm"] = (Compiles.n, Compiles.seconds)
        if step == save_at:  # the types of the state the save is about to stage
            marks["types"] = [jax.typeof(x) for x in jax.tree.leaves(t1.state)]

    ready = _run_until_killed(t1, kill_at, mark)
    save = t1.async_ckpt.wait()
    check(save.step == save_at, f"last save was step {save.step}")
    saved = jax.tree.leaves(t1.manager.saved["train_state"])
    n_warm, s_warm = marks["warm"]
    losses = [m["loss"] for m in t1.metrics]
    steps = [b - a for a, b in zip(ready, ready[1:])]
    step_s = statistics.median(steps)
    first = {
        "compile_s": s_warm - s0,
        "warmup_compiles": n_warm - n0,
        "first_step_s": t1.metrics[0]["wall_s"],
        "step_s": step_s,
        # the step after the save also carries its synchronous host staging
        "save_stall_s": steps[save_at - 1] - step_s,
        "save_write_s": save.wall_s,  # in the background, overlapping steps
        "compiles_after_warmup": Compiles.n - n_warm,
    }
    written = file_bytes(path)
    n_params = sum(x.size for x in jax.tree.leaves(t1.state["params"]))
    t1.manager.close()
    t1.state = None  # free the device before the second job loads
    del t1

    t = time.perf_counter()
    t2 = _trainer(cfg, data, path, every=save_at, mesh=mesh)
    start = t2.init_or_resume(SEED + 1)  # the seed is ignored on resume
    jax.block_until_ready(t2.state)
    resume_s = time.perf_counter() - t
    check(start == save_at, f"resumed at step {start}, saved {save_at}")
    leaves = jax.tree.leaves(t2.state)
    check(len(leaves) == len(saved) == len(marks["types"]), "resumed tree differs")
    for a, b, aval in zip(saved, leaves, marks["types"]):
        # the type too: a leaf that came back weakly typed (a Python scalar)
        # would build a different step program than the one that saved it
        check(jax.typeof(b) == aval, f"resumed leaf is {jax.typeof(b)}, saved {aval}")
        check(same_bits(a, b), "resumed state differs from the saved one")
    del saved, marks
    spans = None
    if mesh is not None:
        for leaf, sh in zip(leaves, jax.tree.leaves(t2.state_sharding)):
            check(leaf.sharding == sh, f"resumed leaf placed {leaf.sharding}, spec {sh}")
        spans = min(len({s.index for s in x.addressable_shards}) for x in _bulk(leaves))
        check(spans == mesh.size, f"a bulk leaf is split {spans} ways")
    del leaves

    warm: list[int] = []  # the compile count after the first resumed step
    t2.run(kill_at - start, on_step=lambda step, loss: warm or warm.append(Compiles.n))
    resumed = [m["loss"] for m in t2.metrics]  # the run ended with a save
    check(resumed == losses[save_at:], f"losses after resume {resumed} != {losses[save_at:]}")
    out = {
        "phase": "train" if mesh is None else "train_2x2",
        "params": int(n_params),
        "batch": data.batch,
        "seq_len": data.seq_len,
        **first,
        "resume_s": resume_s,
        "compiles_after_resume_warmup": Compiles.n - warm[0],
        "bytes_written": written,
        "bytes_written_total": file_bytes(path),
        "peak_bytes_in_use": peak_bytes(),
        "losses": losses,
        "resume_bit_identical": True,
        "resumed_losses_equal": True,
    }
    if spans is not None:
        out["bulk_leaves_split_ways"] = spans
    t2.manager.close()
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}", file=sys.stderr)
        return 1
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found {len(jax.devices())}", file=sys.stderr)
        return 1

    from repro.launch.compile_cache import enable_compile_cache
    from repro.train.data import DataConfig

    cache = enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(Compiles.listen)
    log(phase="setup", device_kind=dev.device_kind, devices=len(jax.devices()), compile_cache=cache)
    cfg = qwen3_one_chip_share()
    data = DataConfig(seed=SEED, batch=8, seq_len=1024)

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
        if args.chips == 1:
            log(**cfd_phase(workdir))
            log(**train_phase(workdir, cfg, data))
        else:
            from repro.launch.mesh import make_mesh

            ref = reference_losses(cfg, data, workdir, steps=5)
            res = train_phase(workdir, cfg, data, mesh=make_mesh((2, 2), ("data", "model")))
            worst = max(abs(a - b) / abs(b) for a, b in zip(res["losses"], ref))
            res.update(one_chip_losses=ref, worst_rel_loss_diff=worst, loss_band=LOSS_BAND)
            log(**res)
            check(worst <= LOSS_BAND, f"2x2 losses off by {worst:.4f} > {LOSS_BAND}")
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

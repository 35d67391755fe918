"""Trainer integration: learning, crash-resume exactness, TRS branching,
gradient compression, straggler watchdog."""

import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.core.checkpoint import CheckpointManager
from repro.distributed.compression import ErrorFeedback, int8_roundtrip
from repro.train.data import DataConfig, TokenStream
from repro.train.steps import TrainSetup
from repro.train.trainer import Trainer, TrainerConfig


def tiny_cfg():
    return get_smoke("qwen3-8b").scaled(logit_chunk=64)


def make_trainer(tmp_path, name="run.th5", **kw):
    mgr = CheckpointManager(str(tmp_path / name), common={"arch": "qwen3-smoke"})
    setup = kw.pop("setup", TrainSetup(adamw=__import__("repro.train.optim", fromlist=["AdamWConfig"]).AdamWConfig(lr=3e-3)))
    return Trainer(
        tiny_cfg(),
        mgr,
        setup=setup,
        data=DataConfig(batch=4, seq_len=64, seed=7),
        tcfg=TrainerConfig(checkpoint_every=5, **kw),
    )


def test_loss_decreases(tmp_path):
    t = make_trainer(tmp_path)
    t.init_or_resume()
    metrics = t.run(30)
    first = np.mean([m["loss"] for m in metrics[:5]])
    last = np.mean([m["loss"] for m in metrics[-5:]])
    assert last < first - 0.1, (first, last)
    t.manager.close()


def test_crash_resume_exact(tmp_path):
    """Train 10; 'crash'; resume → identical weights to an uninterrupted run."""
    t1 = make_trainer(tmp_path, "a.th5")
    t1.init_or_resume(seed=3)
    t1.run(10)  # checkpoints at 5 and 10
    w10 = jax.tree.leaves(t1.state["params"])[0].copy()
    t1.run(5)
    w15_direct = np.asarray(jax.tree.leaves(t1.state["params"])[0])
    t1.manager.close()

    # second process: resumes from step 10 snapshot and redoes 5 steps
    t2 = make_trainer(tmp_path, "a.th5")
    start = t2.init_or_resume(seed=999)  # seed ignored on resume
    assert start == 15  # latest snapshot was at 15 (end-of-run save)
    # roll back to the step-10 snapshot explicitly to replay
    _, snap = t2.manager.restore(10)
    t2.state = snap["train_state"]
    np.testing.assert_array_equal(np.asarray(jax.tree.leaves(t2.state["params"])[0]), np.asarray(w10))
    t2.run(5)
    w15_replay = np.asarray(jax.tree.leaves(t2.state["params"])[0])
    np.testing.assert_allclose(w15_replay, w15_direct, atol=1e-6)
    t2.manager.close()


def test_torn_checkpoint_resume_falls_back(tmp_path):
    t = make_trainer(tmp_path, "b.th5")
    t.init_or_resume()
    t.run(10)
    t.manager.close()
    # corrupt the newest snapshot's payload
    mgr = CheckpointManager(str(tmp_path / "b.th5"), create=False)
    newest = mgr.steps()[-1]
    meta = mgr.file.meta(f"/simulation/step_{newest:08d}/state/train_state.params.embed")
    with open(str(tmp_path / "b.th5"), "r+b") as fh:
        fh.seek(meta.offset + 5)
        fh.write(b"\xff\xff\xff")
    mgr.close()
    t2 = make_trainer(tmp_path, "b.th5")
    start = t2.init_or_resume()
    assert start == 5  # fell back to the previous valid snapshot
    t2.manager.close()


def test_trs_branch_lr_steering(tmp_path):
    """Roll back and continue with a different LR → branches diverge;
    lineage records the overlay (time-reversible steering for training)."""
    t = make_trainer(tmp_path, "root.th5")
    t.init_or_resume()
    t.run(10)
    base_loss = t.metrics[-1]["loss"]

    import dataclasses
    from repro.train.optim import AdamWConfig

    br = t.branch_from(
        5,
        str(tmp_path / "lowlr.th5"),
        overlay={"lr": 1e-5},
        adamw=AdamWConfig(lr=1e-5),
    )
    assert int(br.state["step"]) == 5
    br.run(5)
    # same step count, different trajectory
    p_main = np.asarray(jax.tree.leaves(t.state["params"])[0])
    p_branch = np.asarray(jax.tree.leaves(br.state["params"])[0])
    assert np.abs(p_main - p_branch).max() > 1e-6

    from repro.core.steering import BranchManager

    bm = BranchManager(br.manager)
    assert bm.effective_config()["lr"] == 1e-5
    assert 5 in bm.available_steps()
    t.manager.close()
    br.manager.close()


def test_data_stream_deterministic():
    cfg = tiny_cfg()
    s1 = TokenStream(cfg, DataConfig(batch=2, seq_len=32, seed=5))
    s2 = TokenStream(cfg, DataConfig(batch=2, seq_len=32, seed=5))
    b1, b2 = s1.batch(17), s2.batch(17)
    np.testing.assert_array_equal(np.asarray(b1["tokens"]), np.asarray(b2["tokens"]))
    b3 = s1.batch(18)
    assert np.abs(np.asarray(b1["tokens"]) - np.asarray(b3["tokens"])).max() > 0
    # labels are next-token shifted
    full1 = s1.batch(17)
    np.testing.assert_array_equal(
        np.asarray(full1["tokens"][:, 1:]), np.asarray(full1["labels"][:, :-1])
    )


def test_int8_compression_error_bounded():
    rng = np.random.default_rng(0)
    g = {"a": jnp.asarray(rng.standard_normal((257, 33)), jnp.float32),
         "b": jnp.asarray(rng.standard_normal(100) * 1e-3, jnp.float32)}
    out = int8_roundtrip(g)
    for k in g:
        err = np.abs(np.asarray(out[k]) - np.asarray(g[k]))
        scale = np.abs(np.asarray(g[k])).max()
        assert err.max() <= scale / 127.0 * 1.01


def test_error_feedback_converges_quadratic():
    """EF-compressed GD still converges on a quadratic bowl."""
    ef = ErrorFeedback()
    w = {"w": jnp.ones(512) * 5.0}
    target = jnp.zeros(512)
    residual = ef.init(w)
    for _ in range(200):
        grad = {"w": (w["w"] - target)}
        cgrad, residual = ef.compress(grad, residual)
        w = {"w": w["w"] - 0.1 * cgrad["w"]}
    assert float(jnp.abs(w["w"]).max()) < 1e-2


def test_straggler_watchdog(tmp_path):
    t = make_trainer(tmp_path, "c.th5")
    t.init_or_resume()
    # synthetic timings: steady 10ms with one 100ms spike
    for dt in [0.01] * 10 + [0.1] + [0.01] * 5:
        t._watchdog(dt, 0)
    assert t.straggler.flagged == 1
    assert t.straggler.slowest_s == pytest.approx(0.1)
    t.manager.close()


def test_checkpoint_returns_with_the_write_in_flight(tmp_path):
    """``_checkpoint`` stages the state and returns while its write runs on
    the saver's thread; the step is in the file once the saver is waited on."""
    release = threading.Event()

    class Held(CheckpointManager):
        def save(self, step, state, **kw):
            assert release.wait(60)
            return super().save(step, state, **kw)

    mgr = Held(str(tmp_path / "h.th5"))
    t = Trainer(tiny_cfg(), mgr, data=DataConfig(batch=4, seq_len=64, seed=7))
    t.init_or_resume()
    t._checkpoint(0)
    try:
        assert t.async_ckpt._thread.is_alive()
        assert mgr.latest_valid() is None
    finally:
        release.set()
    t.async_ckpt.wait()
    assert mgr.latest_valid() == 0
    mgr.close()


PLACEMENT_CODE = r"""
import tempfile
import jax
import numpy as np
from repro.configs import get_smoke
from repro.core.checkpoint import CheckpointManager
from repro.launch.mesh import make_test_mesh
from repro.train.data import DataConfig
from repro.train.trainer import Trainer, TrainerConfig

cfg = get_smoke("qwen3-8b").scaled(logit_chunk=64)
mesh = make_test_mesh(2, 2)

def trainer(path):
    return Trainer(cfg, CheckpointManager(path), mesh=mesh,
                   data=DataConfig(batch=4, seq_len=32, seed=7),
                   tcfg=TrainerConfig(checkpoint_every=2))

def check_placed(t):
    want = jax.tree.leaves(t.state_sharding)
    got = jax.tree.leaves(t.state)
    assert len(got) == len(want)
    for leaf, sh in zip(got, want):
        assert leaf.sharding == sh, (leaf.sharding, sh)
    # the embedding is split over the whole 2x2 mesh
    emb = t.state["params"]["embed"]
    assert len({s.device for s in emb.addressable_shards}) == 4

with tempfile.TemporaryDirectory() as d:
    path = d + "/run.th5"
    t1 = trainer(path)
    assert t1.init_or_resume(seed=3) == 0
    check_placed(t1)
    t1.run(2)
    check_placed(t1)  # the step keeps the layout
    saved = [np.asarray(x) for x in jax.tree.leaves(t1.state)]
    t1.manager.close()
    t2 = trainer(path)
    assert t2.init_or_resume() == 2
    check_placed(t2)
    for a, b in zip(saved, jax.tree.leaves(t2.state)):
        np.testing.assert_array_equal(a, np.asarray(b))
    t2.manager.close()
print("OK")
"""


def test_trainer_places_state_with_mesh_specs():
    """On a 2x2 mesh every leaf of a fresh or resumed state carries the
    sharding the train step's specs give it."""
    from tests._subproc import run_with_devices

    assert "OK" in run_with_devices(PLACEMENT_CODE, 4)

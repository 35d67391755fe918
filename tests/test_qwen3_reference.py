"""qwen3's training step against the plain reference beside the benchmark's
configuration (``bench/configs/qwen3_ref.py``), on the smoke widths with
seeded random weights: the loss of a step and the gradient it applied, as
the benchmark's train cell recovers it from Adam's first moment."""

import importlib.util
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke, qwen3_8b
from repro.train.data import DataConfig, TokenStream
from repro.train.optim import AdamWConfig
from repro.train.steps import TrainSetup, init_train_state, make_train_step

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench.systems import train as bench_train  # noqa: E402


def _reference():
    spec = importlib.util.spec_from_file_location("qwen3_ref", ROOT / "bench" / "configs" / "qwen3_ref.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def _ref_config(cfg) -> dict:
    """The program's configuration in the published config.json's keys."""
    return {theirs: getattr(cfg, ours) for theirs, ours in bench_train.WIDTHS.items()}


def _step(compute_dtype: str):
    """One program step from seeded weights on a batch of the token stream:
    its loss, the gradient it applied (reference layout) and the weights it
    took the gradient at (reference layout, float32)."""
    cfg = get_smoke("qwen3-8b").scaled(compute_dtype=compute_dtype)
    setup = TrainSetup(adamw=AdamWConfig(clip_norm=1e9))  # no clip: the raw gradient
    step_fn, _, _ = make_train_step(cfg, setup=setup)
    state = init_train_state(jax.random.PRNGKey(3), cfg, setup)
    assert jax.tree.leaves(state["params"])[0].dtype == jnp.dtype(compute_dtype)
    batch = TokenStream(cfg, DataConfig(seed=11, batch=4, seq_len=64)).batch(0)
    weights = bench_train.reference_params(jax.tree.map(np.asarray, state["params"]))
    mu = state["opt"]["mu"]
    new, metrics = jax.jit(step_fn)(state, batch)
    opt = {"b1": setup.adamw.b1, "clip_norm": setup.adamw.clip_norm}
    grads = bench_train.applied_gradient(new["opt"]["mu"], mu, float(metrics["grad_norm"]), opt)
    return cfg, float(metrics["loss"]), bench_train.reference_layout(grads), weights, batch


def _compare(compute_dtype: str):
    cfg, loss, grads, weights, batch = _step(compute_dtype)
    c = _ref_config(cfg)
    want_loss, want = jax.value_and_grad(REF.loss)(weights, batch["tokens"], batch["labels"], c)
    rel = {
        k: float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
        for (k, g), w in zip(sorted(bench_train.flat(grads).items()), [v for _, v in sorted(bench_train.flat(want).items())])
    }
    assert len(rel) == 14
    return abs(loss - float(want_loss)) / float(want_loss), rel


def test_float32_step_matches_the_reference():
    loss_err, rel = _compare("float32")
    assert loss_err <= 1e-5
    assert max(rel.values()) <= 1e-4, rel


def test_bfloat16_step_matches_the_reference_within_rounding():
    """bfloat16 keeps 8 bits of mantissa, a relative rounding of 2**-9 (2e-3)
    on every parameter, activation and gradient tensor; the loss, a mean
    over 256 tokens, lands well inside that, and each gradient leaf, built
    by products of rounded tensors through two layers and back, within
    about ten roundings."""
    loss_err, rel = _compare("bfloat16")
    assert loss_err <= 2e-3
    assert max(rel.values()) <= 2e-2, rel


def test_float8_reference_is_far_from_the_float32_one():
    """The control the train cell's limits must reject: the reference with
    its product inputs in float8_e4m3fn (3 bits of mantissa)."""
    cfg = get_smoke("qwen3-8b")
    c = _ref_config(cfg)
    weights = REF.initial_state(c, 2**33 + 5)
    batch = TokenStream(cfg, DataConfig(seed=11, batch=4, seq_len=64)).batch(0)
    f32 = jax.grad(REF.loss)(weights, batch["tokens"], batch["labels"], c)
    f8 = jax.grad(REF.loss)(weights, batch["tokens"], batch["labels"], c, jnp.float8_e4m3fn)
    worst = max(float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)) for a, b in zip(jax.tree.leaves(f8), jax.tree.leaves(f32)))
    assert worst > 0.1


def test_share_keeps_the_published_widths():
    full, cut = get_config("qwen3-8b"), qwen3_8b.share(n_layers=4, vocab_div=4)
    assert (cut.n_layers, cut.vocab_size) == (4, 37984)
    assert sum(s.n_layers for s in cut.stages) == 4
    for field in ("d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff", "qk_norm", "rope_theta", "tie_embeddings",
                  "rms_eps", "mlp_variant", "compute_dtype", "param_dtype", "remat"):
        assert getattr(cut, field) == getattr(full, field), field
    assert cut == full.scaled(n_layers=4, vocab_size=37984, stages=cut.stages)


def test_reference_attention_is_causal():
    """A label's loss does not depend on the tokens after its position: a
    sequence changed from position k on keeps the first k losses, and they
    are the losses of the sequence cut to k."""
    cfg = get_smoke("qwen3-8b")
    c = _ref_config(cfg)
    w = REF.widths(c)
    weights = REF.initial_state(c, 1)
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size, 65).astype(np.int32)
    other = tokens.copy()
    k = 40
    other[k:] = (other[k:] + 1) % cfg.vocab_size
    nll = lambda t: np.asarray(REF.token_nll(weights, t[:-1], t[1:], w))  # noqa: E731
    full, changed, cut = nll(tokens), nll(other), nll(tokens[: k + 1])
    assert np.array_equal(full[:k - 1], changed[:k - 1])  # labels up to position k - 1 are the same
    assert not np.allclose(full[k:], changed[k:])
    np.testing.assert_allclose(cut, full[:k], rtol=1e-6, atol=1e-6)


def test_the_plain_adamw_step_is_the_programs():
    """The reference's ``adamw_delta`` with its clip factor, against the
    program's ``adamw_update`` on float32 master weights, at step 7 of a
    run, with a clip that bites and one that does not."""
    from repro.train.optim import adamw_update

    keys = iter(jax.random.split(jax.random.PRNGKey(5), 8))
    shapes = {"a": (64, 32), "b": (32,)}
    draw = lambda scale: {k: scale * jax.random.normal(next(keys), s) for k, s in shapes.items()}  # noqa: E731
    master, mu, grads = draw(0.02), draw(1e-3), draw(0.1)
    nu = jax.tree.map(jnp.square, draw(1e-3))
    for clip in (0.5, 1e9):
        cfg = AdamWConfig(clip_norm=clip)
        state = {"mu": mu, "nu": nu, "count": jnp.int32(6), "master": master}
        _, new, _ = adamw_update(grads, state, jax.tree.map(lambda x: x.astype(jnp.bfloat16), master), cfg)
        scale = REF.clip_scale(grads, clip)
        consts = {k: getattr(cfg, k) for k in ("lr", "b1", "b2", "eps", "weight_decay")}
        for k in shapes:
            want = REF.adamw_delta(grads[k], mu[k], nu[k], master[k], count=7, clip_scale=scale, **consts)
            np.testing.assert_allclose(new["master"][k] - master[k], want, rtol=2e-4, atol=1e-9)

"""Plain reference of qwen3's training loss and its gradients.

Written from the published description of the Qwen3 dense block (the
``Qwen/Qwen3-8B`` config.json and its modelling code), in plain
``jax.numpy`` and float32, importing nothing of the program:

- token embedding, no scaling;
- per layer: RMSNorm, then grouped-query attention with a per-head RMSNorm
  of the queries and keys (qk-norm) before rotary embeddings (RoPE, the
  two halves of each head rotated, base ``rope_theta``), causal softmax
  scaled by 1/sqrt(head_dim), query head h reading key/value head
  h // (heads / kv_heads), output projection, residual; RMSNorm, SwiGLU
  ``down(silu(gate(x)) * up(x))``, residual;
- final RMSNorm, an untied head, and the mean token cross-entropy.

Every matrix product runs at ``Precision.HIGHEST``: a float32 product on a
TPU is otherwise made of bfloat16 passes.  ``mm_dtype`` rounds the inputs of
every product to a lower precision first (``float8_e4m3fn`` is the control
that the configuration's limits must reject); the products themselves and
everything between them stay in float32.  The backward pass's products take
the rounded operands and float32 cotangents: cotangents cast to
``float8_e4m3fn`` unscaled would mostly flush to zero, a failure of its
range rather than of its precision.

Parameters are a plain dict with the layers stacked on a leading axis
(:func:`initial_state` shows the shapes).  RMSNorm weights multiply
(published convention, 1 at init).  One sequence is computed at a time
(``jax.vmap`` over the batch), each layer under ``jax.checkpoint``, and the
attention one key/value group at a time, each group under
``jax.checkpoint``, so that a batch's gradient fits beside the model.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def widths(c: dict) -> dict:
    """The configuration's shape, in the published config.json's keys."""
    return {
        "d": c["hidden_size"],
        "ff": c["intermediate_size"],
        "heads": c["num_attention_heads"],
        "kv_heads": c["num_key_value_heads"],
        "head_dim": c["head_dim"],
        "layers": c["num_hidden_layers"],
        "vocab": c["vocab_size"],
        "theta": float(c["rope_theta"]),
        "eps": float(c["rms_norm_eps"]),
    }


def initial_state(c: dict, seed: int) -> dict:
    """Random float32 parameters from ``seed``: normal weights scaled by
    1/sqrt(fan-in), norms at 1."""
    w = widths(c)
    d, ff, h, kv, dh, n, v = (w[k] for k in ("d", "ff", "heads", "kv_heads", "head_dim", "layers", "vocab"))
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)
    ks = iter(jax.random.split(key, 16))

    def normal(shape, fan_in):
        return jax.random.normal(next(ks), shape, F32) / np.sqrt(fan_in)

    def ones(*shape):
        return jnp.ones(shape, F32)

    layers = {
        "ln1": ones(n, d),
        "wq": normal((n, d, h, dh), d),
        "wk": normal((n, d, kv, dh), d),
        "wv": normal((n, d, kv, dh), d),
        "wo": normal((n, h, dh, d), h * dh),
        "q_norm": ones(n, dh),
        "k_norm": ones(n, dh),
        "ln2": ones(n, d),
        "w_gate": normal((n, d, ff), d),
        "w_up": normal((n, d, ff), d),
        "w_down": normal((n, ff, d), ff),
    }
    return {
        "embed": normal((v, d), d),
        "layers": layers,
        "final_norm": ones(d),
        "lm_head": normal((v, d), d),
    }


def _round(x, dtype):
    """``x`` rounded to ``dtype`` in value; its gradient passes through in
    float32."""
    return x + jax.lax.stop_gradient(x.astype(dtype).astype(F32) - x)


def _mm(spec: str, a, b, mm_dtype):
    if mm_dtype != F32:
        a, b = _round(a, mm_dtype), _round(b, mm_dtype)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms_norm(x, w, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta: float):
    """x: (S, heads, head_dim), positions 0..S-1."""
    s, _, dh = x.shape
    inv = jnp.asarray(1.0 / theta ** (np.arange(0, dh, 2, dtype=np.float64) / dh), F32)
    angle = jnp.arange(s, dtype=F32)[:, None] * inv  # (S, dh/2)
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(lp: dict, x, w: dict, mm_dtype):
    """x: (S, d) → (S, d)."""
    s = x.shape[0]
    h, kv, dh = w["heads"], w["kv_heads"], w["head_dim"]
    q = _rms_norm(_mm("sd,dhk->shk", x, lp["wq"], mm_dtype), lp["q_norm"], w["eps"])
    k = _rms_norm(_mm("sd,dhk->shk", x, lp["wk"], mm_dtype), lp["k_norm"], w["eps"])
    v = _mm("sd,dhk->shk", x, lp["wv"], mm_dtype)
    q, k = _rope(q, w["theta"]), _rope(k, w["theta"])
    causal = jnp.tril(jnp.ones((s, s), bool))

    @jax.checkpoint
    def group(qg, kg, vg):  # (S, heads/kv_heads, dh), (S, dh), (S, dh)
        scores = _mm("sgk,tk->gst", qg, kg, mm_dtype) / np.sqrt(dh)
        p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return _mm("gst,tk->sgk", p, vg, mm_dtype)

    qg = q.reshape(s, kv, h // kv, dh).transpose(1, 0, 2, 3)
    out = jax.lax.map(lambda a: group(*a), (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    out = out.transpose(1, 0, 2, 3).reshape(s, h, dh)
    return _mm("shk,hkd->sd", out, lp["wo"], mm_dtype)


def _mlp(lp: dict, x, mm_dtype):
    gate = _mm("sd,df->sf", x, lp["w_gate"], mm_dtype)
    up = _mm("sd,df->sf", x, lp["w_up"], mm_dtype)
    return _mm("sf,fd->sd", jax.nn.silu(gate) * up, lp["w_down"], mm_dtype)


def token_nll(params: dict, tokens, labels, w: dict, mm_dtype=F32):
    """Negative log-likelihood of each label of one sequence: tokens,
    labels (S,) → (S,)."""

    @jax.checkpoint
    def layer(x, lp):
        x = x + _attention(lp, _rms_norm(x, lp["ln1"], w["eps"]), w, mm_dtype)
        return x + _mlp(lp, _rms_norm(x, lp["ln2"], w["eps"]), mm_dtype)

    x = params["embed"][tokens].astype(F32)
    x, _ = jax.lax.scan(lambda x, lp: (layer(x, lp), None), x, params["layers"])
    x = _rms_norm(x, params["final_norm"], w["eps"])
    logits = _mm("sd,vd->sv", x, params["lm_head"], mm_dtype)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jax.nn.logsumexp(logits, axis=-1) - picked


def nll_sum(params: dict, tokens, labels, c: dict, mm_dtype=F32):
    """Summed token negative log-likelihood of a batch: tokens, labels (B, S)."""
    w = widths(c)
    per = jax.vmap(lambda t, l: jnp.sum(token_nll(params, t, l, w, mm_dtype)))(tokens, labels)
    return jnp.sum(per)


def loss(params: dict, tokens, labels, c: dict, mm_dtype=F32):
    """Mean token cross-entropy of a batch."""
    return nll_sum(params, tokens, labels, c, mm_dtype) / tokens.size


def adamw_delta(grad, mu, nu, param, *, count: int, clip_scale, lr: float, b1: float, b2: float, eps: float,
                weight_decay: float):
    """The change one AdamW step makes to a float32 parameter: ``grad``
    scaled by ``clip_scale`` (the global-norm clip), first and second
    moments ``mu`` and ``nu`` as they were before the step, bias-corrected
    at step ``count`` (the steps taken, this one included), decoupled
    weight decay on the parameter as it is stored."""
    g = grad * clip_scale
    m = b1 * mu + (1 - b1) * g
    v = b2 * nu + (1 - b2) * g * g
    step = (m / (1 - b1**count)) / (jnp.sqrt(v / (1 - b2**count)) + eps)
    return -lr * (step + weight_decay * param)


def clip_scale(grads, clip_norm: float):
    """The factor a global-norm clip at ``clip_norm`` scales ``grads`` by."""
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    return jnp.minimum(1.0, clip_norm / jnp.maximum(norm, 1e-12))

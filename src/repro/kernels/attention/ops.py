"""jit'd public wrapper: model-layout (B,S,H,Dh) attention → flash kernel.

On TPU hardware call with ``interpret=False`` (Mosaic); on CPU the kernel
body runs in interpret mode.  ``models.attention`` routes here when
``cfg.use_pallas`` is set and no cache is involved (train/prefill)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .flash import flash_attention
from .ref import attention_ref


def mha(
    q: jax.Array,  # (B, S, H, Dh)
    k: jax.Array,  # (B, T, KV, Dh)
    v: jax.Array,
    *,
    window: int = 0,
    interpret: bool = False,
    use_ref: bool = False,
) -> jax.Array:
    B, S, H, Dh = q.shape
    KV = k.shape[2]
    if KV != H:  # GQA → expand KV heads
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, Dh)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, -1, Dh)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, -1, Dh)
    if use_ref:
        out = attention_ref(qf, kf, vf, window=window)
    else:
        out = flash_attention(qf, kf, vf, window=window, interpret=interpret)
    return out.reshape(B, H, S, Dh).transpose(0, 2, 1, 3)

"""GQA attention (full / sliding-window) with qk-norm, RoPE and KV caches.

The XLA path never materialises an (S × S) score matrix for long sequences:
queries are processed in chunks under ``lax.scan`` (each chunk sees all
keys, softmax is exact), bounding activation memory to one chunk — the
XLA-level equivalent of the Pallas flash kernel in ``repro.kernels``
(``use_pallas=True`` switches to it on real TPU hardware).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..distributed.sharding import constrain
from .common import ModelConfig
from .layers import apply_rope, init_rms, rms_norm

NEG_INF = -2.0e38  # f32-safe mask value


def init_attn(key, cfg: ModelConfig, dtype) -> dict:
    D, H, KV, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    s = 1.0 / np.sqrt(D)
    p = {
        "wq": jax.random.normal(ks[0], (D, H, Dh), dtype) * s,
        "wk": jax.random.normal(ks[1], (D, KV, Dh), dtype) * s,
        "wv": jax.random.normal(ks[2], (D, KV, Dh), dtype) * s,
        "wo": jax.random.normal(ks[3], (H, Dh, D), dtype) * (1.0 / np.sqrt(H * Dh)),
    }
    if cfg.qk_norm:
        p["q_norm"] = init_rms(Dh, dtype)
        p["k_norm"] = init_rms(Dh, dtype)
    return p


def attn_axes(cfg: ModelConfig) -> dict:
    p = {
        "wq": ("embed_fsdp", "heads", None),
        "wk": ("embed_fsdp", "kv_heads", None),
        "wv": ("embed_fsdp", "kv_heads", None),
        "wo": ("heads", None, "embed_fsdp"),
    }
    if cfg.qk_norm:
        p["q_norm"] = None
        p["k_norm"] = None
    return p


def _q_chunk_size(s_q: int) -> int:
    if s_q <= 2048:
        return s_q
    for c in (1024, 512):
        if s_q % c == 0:
            return c
    return 1024 if s_q % 1024 == 0 else s_q


def _scores_block(q, k, v, qpos, kpos, window: int, scale: float):
    """Exact attention for one query block against all keys — flat heads.

    q: (B,C,H,Dh)  k/v: (B,T,H,Dh)  qpos: (B,C)  kpos: (B,T) → (B,C,H,Dh)

    The flat-H formulation (KV heads pre-expanded when GQA meets a wider TP
    axis) gives GSPMD one evenly-shardable head dimension — the grouped
    (KV,G) einsum forced involuntary resharding on every layer.
    """
    s = jnp.einsum("bchd,bthd->bhct", q, k).astype(jnp.float32) * scale
    kp = kpos[:, None, None, :]
    qp = qpos[:, None, :, None]
    mask = (kp <= qp) & (kp >= 0)  # causal; kp<0 = unwritten ring slot
    if window > 0:
        mask &= kp > qp - window
    s = jnp.where(mask, s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhct,bthd->bchd", w, v)


def _expand_kv(t: jax.Array, H: int) -> jax.Array:
    """(B,T,KV,Dh) → (B,T,H,Dh) by repeating each KV head G times.

    Done whenever H divides evenly over the model axis but KV does not:
    replicating KV costs G× key bytes but removes all padded-shard
    resharding (the dominant wire-bytes term in the baseline audit)."""
    KV = t.shape[2]
    if KV == H:
        return t
    return jnp.repeat(t, H // KV, axis=2)


def _should_expand(H: int, KV: int) -> bool:
    from ..distributed.sharding import model_axis_size

    m = model_axis_size()
    return m > 1 and KV % m != 0 and H % m == 0


def _attend(q, k, v, qpos, kpos, window: int, remat_chunks: bool = False):
    """Chunked exact attention.  q: (B,S,H,Dh), k/v: (B,T,KV,Dh).

    ``remat_chunks`` recomputes each query chunk's scores in the backward
    pass (``remat="full"``) instead of keeping every chunk's."""
    B, S, H, Dh = q.shape
    KV = k.shape[2]
    scale = 1.0 / np.sqrt(Dh)
    if _should_expand(H, KV):
        k = constrain(_expand_kv(k, H), ("batch", None, "act_heads", None))
        v = constrain(_expand_kv(v, H), ("batch", None, "act_heads", None))
    elif KV != H:
        k = _expand_kv(k, H)
        v = _expand_kv(v, H)
    chunk = _q_chunk_size(S)
    if chunk == S:
        return _scores_block(q, k, v, qpos, kpos, window, scale)

    n_chunks = S // chunk
    qg = q.reshape(B, n_chunks, chunk, H, Dh).transpose(1, 0, 2, 3, 4)
    qpos_c = qpos.reshape(B, n_chunks, chunk).transpose(1, 0, 2)

    # without the checkpoint the scan keeps every chunk's f32 scores,
    # probabilities and mask for its transpose (qwen3-8b, 4 layers, 16 x 4096
    # tokens on a 2x2 v5e mesh: 18.7 GB a chip without, 14.1 GB with)
    block = jax.checkpoint(_scores_block, static_argnums=(5, 6)) if remat_chunks else _scores_block

    def body(carry, inp):
        qc, pc = inp
        out = block(qc, k, v, pc, kpos, window, scale)
        return carry, out

    _, outs = jax.lax.scan(body, None, (qg, qpos_c))  # (n_chunks, B, chunk, H, Dh)
    return outs.transpose(1, 0, 2, 3, 4).reshape(B, S, H, Dh)


def apply_attn(
    p: dict,
    x: jax.Array,
    positions: jax.Array,
    cfg: ModelConfig,
    *,
    window: int = 0,
    theta: float | None = None,
    cache: dict | None = None,
    cache_index: jax.Array | None = None,
    update_cache: bool = False,
):
    """Returns (y, new_cache).

    Modes:
      train:    cache=None                          — full causal self-attn
      prefill:  cache=zeros(T), update_cache=True   — causal + cache fill
      decode:   cache=filled,  update_cache=True    — S==1 token step
    """
    B, S, D = x.shape
    cdt = x.dtype
    theta = cfg.rope_theta if theta is None else theta
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(cdt))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(cdt))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(cdt))
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.rms_eps)
        k = rms_norm(k, p["k_norm"], cfg.rms_eps)
    q = apply_rope(q, positions, theta)
    k = apply_rope(k, positions, theta)
    q = constrain(q, ("batch", "seq", "act_heads", None))
    k = constrain(k, ("batch", "seq", "act_kv_heads", None))

    new_cache = cache
    if cache is not None:
        T = cache["k"].shape[1]
        ring_prefill = update_cache and S >= T
        if update_cache:
            if ring_prefill:
                # prefill into a (possibly window-sized ring) cache: keep the
                # last T tokens, slot of position p is p mod T so a later
                # decode step writes the same slot it would have.
                k_tail = k[:, S - T :].astype(cache["k"].dtype)
                v_tail = v[:, S - T :].astype(cache["v"].dtype)
                pos_tail = jnp.arange(S - T, S, dtype=jnp.int32)
                shift = (S - T) % T if T else 0
                ck = jnp.roll(k_tail, shift, axis=1)
                cv = jnp.roll(v_tail, shift, axis=1)
                cpos = jnp.roll(pos_tail, shift, axis=0)
            else:  # decode (or short prefill): insert at slot index mod T
                slot = jnp.mod(cache_index, T)
                ck = jax.lax.dynamic_update_slice(
                    cache["k"], k.astype(cache["k"].dtype), (0, slot, 0, 0)
                )
                cv = jax.lax.dynamic_update_slice(
                    cache["v"], v.astype(cache["v"].dtype), (0, slot, 0, 0)
                )
                cpos = jax.lax.dynamic_update_slice(
                    cache["pos"], positions[0].astype(jnp.int32), (slot,)
                )
            new_cache = {"k": ck, "v": cv, "pos": cpos}
        if ring_prefill:
            # a ring cache only holds the last T keys — early queries need
            # the in-window keys that were evicted, so attend over the full
            # freshly-computed k/v (train-style); the ring serves decode.
            out = _attend(q, k, v, positions, positions, window)
        else:
            kk = constrain(
                new_cache["k"].astype(cdt), ("batch", "cache_seq", "act_kv_heads", None)
            )
            vv = constrain(
                new_cache["v"].astype(cdt), ("batch", "cache_seq", "act_kv_heads", None)
            )
            kpos = jnp.broadcast_to(new_cache["pos"][None, :], (B, T))
            out = _attend(q, kk, vv, positions, kpos, window)
    else:
        out = _attend(q, k, v, positions, positions, window, remat_chunks=cfg.remat == "full")

    out = constrain(out, ("batch", "seq", "act_heads", None))
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(cdt))
    return y, new_cache


UNWRITTEN = -(2**30)  # sentinel position for never-written ring slots


def init_attn_cache(cfg: ModelConfig, batch: int, length: int, dtype=jnp.bfloat16) -> dict:
    return {
        "k": jnp.zeros((batch, length, cfg.n_kv_heads, cfg.head_dim), dtype),
        "v": jnp.zeros((batch, length, cfg.n_kv_heads, cfg.head_dim), dtype),
        "pos": jnp.full((length,), UNWRITTEN, jnp.int32),
    }


def attn_cache_specs(cfg: ModelConfig, batch: int, length: int, dtype=jnp.bfloat16) -> dict:
    shape = (batch, length, cfg.n_kv_heads, cfg.head_dim)
    return {
        "k": jax.ShapeDtypeStruct(shape, dtype),
        "v": jax.ShapeDtypeStruct(shape, dtype),
        "pos": jax.ShapeDtypeStruct((length,), jnp.int32),
    }


def cache_axes() -> dict:
    return {
        "k": ("batch", "cache_seq", "act_kv_heads", None),
        "v": ("batch", "cache_seq", "act_kv_heads", None),
        "pos": ("cache_seq",),
    }

"""Model assembly: embeddings → staged layer scan → final norm → head.

The layer stack is organised in **stages** (repeated patterns of layer
specs, see ``common.Stage``).  Parameters of each pattern slot are stacked
over the stage's repeat count and the stage runs as a single ``lax.scan``
— HLO size stays O(#distinct patterns), not O(#layers), which keeps
512-device compiles tractable and is also how remat policies are applied
(per scanned block).

Three entry points (all pure):
  * :func:`hidden_states` — shared trunk; train / prefill / decode modes.
  * :func:`logits`        — full logits (smoke tests / tiny models only).
  * caches: :func:`init_cache` / :func:`cache_specs` / :func:`cache_axes`
    build per-stage cache pytrees whose per-mixer sizes differ (full-length
    for global attention, window-sized rings for local/SWA, latent for MLA,
    O(1) states for SSD/RG-LRU).
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..distributed.sharding import constrain
from . import attention, mla, moe, rglru, ssd
from .common import LayerSpec, ModelConfig, Stage
from .layers import apply_mlp, embed_tokens, init_embed, init_mlp, init_rms, mlp_axes, rms_norm


def _dtype(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16, "float16": jnp.float16}[name]


# ---------------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------------


def _init_layer(key, spec: LayerSpec, cfg: ModelConfig, dtype) -> dict:
    km, kf = jax.random.split(key)
    p: dict[str, Any] = {"ln1": init_rms(cfg.d_model, dtype)}
    if spec.mixer in ("attn", "local"):
        p["mixer"] = attention.init_attn(km, cfg, dtype)
    elif spec.mixer == "mla":
        p["mixer"] = mla.init_mla(km, cfg, dtype)
    elif spec.mixer == "ssd":
        p["mixer"] = ssd.init_ssd(km, cfg, dtype)
    elif spec.mixer == "rglru":
        p["mixer"] = rglru.init_rglru(km, cfg, dtype)
    else:
        raise ValueError(spec.mixer)
    if spec.ffn == "mlp":
        p["ln2"] = init_rms(cfg.d_model, dtype)
        p["ffn"] = init_mlp(kf, cfg.d_model, cfg.d_ff, dtype, cfg.mlp_variant)
    elif spec.ffn == "moe":
        p["ln2"] = init_rms(cfg.d_model, dtype)
        p["ffn"] = moe.init_moe(kf, cfg, dtype)
    return p


def _layer_axes(spec: LayerSpec, cfg: ModelConfig) -> dict:
    a: dict[str, Any] = {"ln1": None}
    if spec.mixer in ("attn", "local"):
        a["mixer"] = attention.attn_axes(cfg)
    elif spec.mixer == "mla":
        a["mixer"] = mla.mla_axes(cfg)
    elif spec.mixer == "ssd":
        a["mixer"] = ssd.ssd_axes(cfg)
    elif spec.mixer == "rglru":
        a["mixer"] = rglru.rglru_axes()
    if spec.ffn == "mlp":
        a["ln2"] = None
        a["ffn"] = mlp_axes(cfg.mlp_variant)
    elif spec.ffn == "moe":
        a["ln2"] = None
        a["ffn"] = moe.moe_axes()
    return a


def init_model(key, cfg: ModelConfig) -> dict:
    dtype = _dtype(cfg.param_dtype)
    keys = jax.random.split(key, len(cfg.stages) + 2)
    params: dict[str, Any] = {}
    if cfg.n_codebooks:
        params["embed"] = (
            init_embed(keys[0], cfg.n_codebooks * cfg.codebook_vocab, cfg.d_model, dtype)
            .reshape(cfg.n_codebooks, cfg.codebook_vocab, cfg.d_model)
        )
    else:
        params["embed"] = init_embed(keys[0], cfg.vocab_size, cfg.d_model, dtype)
    stages = []
    for si, stage in enumerate(cfg.stages):
        slot_keys = jax.random.split(keys[1 + si], len(stage.pattern))
        slots = []
        for pi, spec in enumerate(stage.pattern):
            rep_keys = jax.random.split(slot_keys[pi], stage.repeat)
            slots.append(jax.vmap(lambda k: _init_layer(k, spec, cfg, dtype))(rep_keys))
        stages.append({"slots": slots})
    params["stages"] = stages
    params["final_norm"] = init_rms(cfg.d_model, dtype)
    if not cfg.tie_embeddings:
        if cfg.n_codebooks:
            params["lm_head"] = (
                init_embed(keys[-1], cfg.n_codebooks * cfg.codebook_vocab, cfg.d_model, dtype)
                .reshape(cfg.n_codebooks, cfg.codebook_vocab, cfg.d_model)
            )
        else:
            params["lm_head"] = init_embed(keys[-1], cfg.vocab_size, cfg.d_model, dtype)
    return params


def param_axes(cfg: ModelConfig) -> dict:
    axes: dict[str, Any] = {}
    axes["embed"] = (
        (None, "vocab", "embed_fsdp") if cfg.n_codebooks else ("vocab", "embed_fsdp")
    )
    stages = []
    for stage in cfg.stages:
        slots = []
        for spec in stage.pattern:
            la = _layer_axes(spec, cfg)
            # prepend the scan (repeat) axis: unsharded
            slots.append(
                jax.tree.map(
                    lambda ax: (None,) + tuple(ax) if isinstance(ax, tuple) else (None,),
                    la,
                    is_leaf=lambda ax: ax is None or isinstance(ax, tuple),
                )
            )
        stages.append({"slots": slots})
    axes["stages"] = stages
    axes["final_norm"] = (None,)
    if not cfg.tie_embeddings:
        axes["lm_head"] = axes["embed"]
    return axes


# ---------------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------------


def _mixer_cache(spec: LayerSpec, cfg: ModelConfig, batch: int, length: int, make):
    if spec.mixer == "attn":
        return make("attn", length)
    if spec.mixer == "local":
        return make("attn", min(length, cfg.local_window) if cfg.local_window else length)
    if spec.mixer == "mla":
        return make("mla", length)
    if spec.mixer == "ssd":
        return make("ssd", 0)
    if spec.mixer == "rglru":
        return make("rglru", 0)
    raise ValueError(spec.mixer)


def _cache_builders(cfg: ModelConfig, batch: int, dtype, as_specs: bool):
    def make(kind: str, length: int):
        if kind == "attn":
            fn = attention.attn_cache_specs if as_specs else attention.init_attn_cache
            return fn(cfg, batch, length, dtype)
        if kind == "mla":
            fn = mla.mla_cache_specs if as_specs else mla.init_mla_cache
            return fn(cfg, batch, length, dtype)
        if kind == "ssd":
            fn = ssd.ssd_cache_specs if as_specs else ssd.init_ssd_cache
            return fn(cfg, batch, dtype)
        if kind == "rglru":
            fn = rglru.rglru_cache_specs if as_specs else rglru.init_rglru_cache
            return fn(cfg, batch, dtype)
        raise ValueError(kind)

    return make


def _stack_over_repeat(tree, repeat: int, as_specs: bool):
    if as_specs:
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((repeat,) + tuple(s.shape), s.dtype), tree
        )
    return jax.tree.map(lambda x: jnp.broadcast_to(x[None], (repeat,) + x.shape).copy(), tree)


def _build_cache(cfg: ModelConfig, batch: int, length: int, dtype, as_specs: bool):
    make = _cache_builders(cfg, batch, dtype, as_specs)
    stages = []
    for stage in cfg.stages:
        slots = [
            _stack_over_repeat(_mixer_cache(spec, cfg, batch, length, make), stage.repeat, as_specs)
            for spec in stage.pattern
        ]
        stages.append(slots)
    return {"layers": stages, "index": jax.ShapeDtypeStruct((), jnp.int32) if as_specs else jnp.zeros((), jnp.int32)}


def init_cache(cfg: ModelConfig, batch: int, length: int, dtype=jnp.bfloat16):
    return _build_cache(cfg, batch, length, dtype, as_specs=False)


def cache_specs(cfg: ModelConfig, batch: int, length: int, dtype=jnp.bfloat16):
    return _build_cache(cfg, batch, length, dtype, as_specs=True)


def cache_axes(cfg: ModelConfig):
    def with_scan_axis(tree):
        return jax.tree.map(
            lambda ax: (None,) + tuple(ax),
            tree,
            is_leaf=lambda ax: isinstance(ax, tuple),
        )

    stages = []
    for stage in cfg.stages:
        slots = []
        for spec in stage.pattern:
            if spec.mixer in ("attn", "local"):
                slots.append(with_scan_axis(attention.cache_axes()))
            elif spec.mixer == "mla":
                slots.append(with_scan_axis(mla.mla_cache_axes()))
            elif spec.mixer == "ssd":
                slots.append(with_scan_axis(ssd.ssd_cache_axes()))
            elif spec.mixer == "rglru":
                slots.append(with_scan_axis(rglru.rglru_cache_axes()))
        stages.append(slots)
    return {"layers": stages, "index": None}


# ---------------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------------


def _apply_layer(
    lp: dict,
    spec: LayerSpec,
    cfg: ModelConfig,
    x: jax.Array,
    positions: jax.Array,
    lcache,
    cache_index,
    update_cache: bool,
):
    # explicit layer-entry reshard: one bf16 all-gather of the seq-sharded
    # residual.  Without this, XLA hoists the gather above rms_norm's f32
    # cast and moves the residual at f32 — 2× wire bytes — and re-gathers
    # per consumer (audited at ~6 residual-sized f32 collectives/layer).
    x = constrain(x, ("batch", "seq", "act_embed"))
    h = rms_norm(x, lp["ln1"], cfg.rms_eps)
    aux = jnp.zeros((), jnp.float32)
    if spec.mixer in ("attn", "local"):
        window = cfg.local_window if spec.mixer == "local" else 0
        theta = (
            cfg.global_rope_theta
            if (spec.mixer == "attn" and cfg.global_rope_theta is not None)
            else cfg.rope_theta
        )
        y, new_cache = attention.apply_attn(
            lp["mixer"], h, positions, cfg, window=window, theta=theta,
            cache=lcache, cache_index=cache_index, update_cache=update_cache,
        )
    elif spec.mixer == "mla":
        y, new_cache = mla.apply_mla(
            lp["mixer"], h, positions, cfg,
            cache=lcache, cache_index=cache_index, update_cache=update_cache,
        )
    elif spec.mixer == "ssd":
        y, new_cache = ssd.apply_ssd(lp["mixer"], h, cfg, cache=lcache, update_cache=update_cache)
    elif spec.mixer == "rglru":
        y, new_cache = rglru.apply_rglru(lp["mixer"], h, cfg, cache=lcache, update_cache=update_cache)
    else:
        raise ValueError(spec.mixer)
    x = x + y
    if spec.ffn != "none":
        h2 = rms_norm(x, lp["ln2"], cfg.rms_eps)
        if spec.ffn == "mlp":
            f = apply_mlp(lp["ffn"], h2, x.dtype)
        else:
            f, moe_aux = moe.apply_moe(lp["ffn"], h2, cfg)
            aux = aux + moe_aux["aux_loss"]
        x = x + f
    # layer-boundary residual: seq-sharded over `model` in train/prefill
    # (Megatron-SP style) so the 1-per-layer saved activations stay small
    x = constrain(x, ("batch", "res_seq", "act_embed"))
    return x, new_cache, aux


def _remat_wrap(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)  # "full": and each query chunk of attention (attention._attend)


def hidden_states(
    params: dict,
    cfg: ModelConfig,
    tokens: jax.Array,
    positions: jax.Array | None = None,
    cache: dict | None = None,
    update_cache: bool = False,
):
    """Trunk forward.  tokens: (B,S) int32 — or (B,S,nq) for codebook models.
    Returns (x_normed, new_cache, aux_loss_sum)."""
    cdt = _dtype(cfg.compute_dtype)
    if cfg.n_codebooks:
        B, S, NQ = tokens.shape
        x = jnp.zeros((B, S, cfg.d_model), cdt)
        for q in range(cfg.n_codebooks):  # sum of codebook embeddings
            x = x + embed_tokens(params["embed"][q], tokens[..., q], cdt, cfg.embed_scale)
    else:
        B, S = tokens.shape
        x = embed_tokens(params["embed"], tokens, cdt, cfg.embed_scale)
    x = constrain(x, ("batch", "seq", "act_embed"))

    if positions is None:
        base = cache["index"] if cache is not None else 0
        positions = base + jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    cache_index = cache["index"] if cache is not None else None

    aux_total = jnp.zeros((), jnp.float32)
    new_layer_caches: list[list[Any]] = []

    for si, stage in enumerate(cfg.stages):
        slot_params = params["stages"][si]["slots"]
        slot_caches = cache["layers"][si] if cache is not None else [None] * len(stage.pattern)

        def stage_body(carry, xs):
            x, aux = carry
            lps, lcs = xs
            new_lcs = []
            for pi, spec in enumerate(stage.pattern):
                x, nc, a = _apply_layer(
                    lps[pi], spec, cfg, x, positions, lcs[pi], cache_index, update_cache
                )
                new_lcs.append(nc)
                aux = aux + a
            return (x, aux), new_lcs

        body = _remat_wrap(stage_body, cfg)
        if cache is None:
            scan_xs = (slot_params, [None] * len(stage.pattern))
            (x, aux_total), _ = jax.lax.scan(
                lambda c, lp: (body(c, (lp, [None] * len(stage.pattern)))[0], None),
                (x, aux_total),
                slot_params,
            )
            new_layer_caches.append([None] * len(stage.pattern))
        else:
            (x, aux_total), new_slot_caches = jax.lax.scan(
                body, (x, aux_total), (slot_params, slot_caches)
            )
            new_layer_caches.append(new_slot_caches)

    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    x = constrain(x, ("batch", "seq", "act_embed"))

    new_cache = None
    if cache is not None:
        new_index = cache["index"] + (S if update_cache else 0)
        new_cache = {"layers": new_layer_caches, "index": new_index}
    return x, new_cache, aux_total


def head_weights(params: dict, cfg: ModelConfig) -> jax.Array:
    """(V, D) head matrix (or (nq, Vc, D) for codebook models)."""
    return params["embed"] if cfg.tie_embeddings else params["lm_head"]


def logits(params: dict, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """Full logits — only for smoke-scale models / last-position decoding."""
    w = head_weights(params, cfg).astype(x.dtype)
    if cfg.n_codebooks:
        return jnp.einsum("bsd,qvd->bsqv", x, w)
    out = jnp.einsum("bsd,vd->bsv", x, w)
    return constrain(out, ("batch", "seq", "act_vocab"))


def count_tree_params(params) -> int:
    return sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))

"""Pallas TPU kernel for the Mamba-2 SSD intra-chunk body.

Per (batch, chunk, head-block) grid cell this computes, entirely in VMEM:

    cum   = cumsum(dA)                       (Q, Hb)
    L     = exp(segsum(dA))                  (Hb, Q, Q)  decay mask
    Y     = ((C·Bᵀ) ∘ L ∘ dt) X  +  (C ∘ exp(cum)) · S_in      intra + carry-in
    S_out = Σ_q  exp(cum_last − cum_q)·dt_q · B_q ⊗ X_q        chunk state

The inter-chunk state recurrence (S/Q sequential steps) stays outside in
``lax.scan`` — it is O(S/Q · H·P·N) and latency- not compute-bound, while
the O(Q²) chunk body above is the MXU hot spot.  VMEM at the default
Q=256, Hb=8, P=64, N=128: X 0.5 MiB + B/C 0.25 MiB + L 2 MiB (f32)
+ state 0.5 MiB ≈ 3.5 MiB — comfortably under budget.

Block sizes: Q and N are multiples of 128 (MXU lanes); heads are blocked
by ``hb``.  Validated in interpret mode against ``ref.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ssd_chunk_kernel(
    x_ref,  # (1, Q, hb, P)
    da_ref,  # (1, Q, hb)
    dt_ref,  # (1, Q, hb)
    b_ref,  # (1, Q, N)   (G=1 group, shared across heads)
    c_ref,  # (1, Q, N)
    sin_ref,  # (1, hb, P, N) carry-in state
    y_ref,  # (1, Q, hb, P)
    sout_ref,  # (1, hb, P, N) carry-out contribution (pre-decay of S_in)
):
    x = x_ref[0].astype(jnp.float32)  # (Q, hb, P)
    da = da_ref[0].astype(jnp.float32)  # (Q, hb)
    dt = dt_ref[0].astype(jnp.float32)
    b = b_ref[0].astype(jnp.float32)  # (Q, N)
    c = c_ref[0].astype(jnp.float32)
    s_in = sin_ref[0].astype(jnp.float32)  # (hb, P, N)

    Q, hb = da.shape
    cum = jnp.cumsum(da, axis=0)  # (Q, hb)

    # decay matrix L[h, l, s] = exp(cum[l,h] - cum[s,h]) for l >= s
    diff = cum[:, None, :] - cum[None, :, :]  # (Q, Q, hb)
    tri = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0) >= jax.lax.broadcasted_iota(
        jnp.int32, (Q, Q), 1
    )
    L = jnp.where(tri[:, :, None], jnp.exp(diff), 0.0)  # (Q, Q, hb)

    cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)  # (Q, Q)
    M = cb[:, :, None] * L * dt[None, :, :]  # (Q_l, Q_s, hb)

    # intra-chunk output: Y[l,h,p] = Σ_s M[l,s,h] X[s,h,p]
    y_intra = jnp.einsum("lsh,shp->lhp", M, x)

    # carry-in contribution: Y += (C_l · S_in_h) * exp(cum_l)
    y_in = jnp.einsum("ln,hpn->lhp", c, s_in) * jnp.exp(cum)[:, :, None]

    # chunk state: S_out[h,p,n] = Σ_q exp(cum_last - cum_q)·dt_q · X[q,h,p]·B[q,n]
    w = jnp.exp(cum[-1:, :] - cum) * dt  # (Q, hb)
    xw = x * w[:, :, None]  # (Q, hb, P)
    s_new = jnp.einsum("qhp,qn->hpn", xw, b)
    # carry-out = decayed carry-in + chunk contribution
    sout_ref[0] = (s_in * jnp.exp(cum[-1])[:, None, None] + s_new).astype(sout_ref.dtype)
    y_ref[0] = (y_intra + y_in).astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("hb", "interpret"))
def ssd_chunk(
    x: jax.Array,  # (B, Q, H, P)
    da: jax.Array,  # (B, Q, H)
    dt: jax.Array,  # (B, Q, H)
    b: jax.Array,  # (B, Q, N)
    c: jax.Array,  # (B, Q, N)
    s_in: jax.Array,  # (B, H, P, N)
    *,
    hb: int = 8,
    interpret: bool = False,
):
    """One chunk step: returns (y (B,Q,H,P), s_out (B,H,P,N))."""
    B, Q, H, P = x.shape
    N = b.shape[-1]
    hb = min(hb, H)
    nh = -(-H // hb)
    grid = (B, nh)
    y, s_out = pl.pallas_call(
        _ssd_chunk_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, Q, hb, P), lambda i, j: (i, 0, j, 0)),
            pl.BlockSpec((1, Q, hb), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, Q, hb), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, Q, N), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, Q, N), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, hb, P, N), lambda i, j: (i, j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, Q, hb, P), lambda i, j: (i, 0, j, 0)),
            pl.BlockSpec((1, hb, P, N), lambda i, j: (i, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Q, H, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        interpret=interpret,
    )(x, da, dt, b, c, s_in)
    return y, s_out

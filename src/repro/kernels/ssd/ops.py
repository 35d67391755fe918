"""jit'd wrapper: full-sequence SSD via the Pallas chunk kernel + a host
``lax.scan`` carrying the inter-chunk state (mirrors ``models.ssd``'s
chunked algorithm with the chunk body swapped for the kernel)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .chunk import ssd_chunk
from .ref import ssd_chunk_ref


@functools.partial(jax.jit, static_argnames=("chunk", "interpret", "use_ref"))
def ssd_scan(x, dt, A, b, c, *, chunk: int = 256, interpret: bool = False, use_ref: bool = False):
    """x (B,S,H,P); dt (B,S,H); A (H,)<0; b/c (B,S,N) → y (B,S,H,P), state."""
    B, S, H, P = x.shape
    N = b.shape[-1]
    Q = min(chunk, S)
    assert S % Q == 0
    NC = S // Q
    da = dt * A  # (B,S,H)

    def to_chunks(t):
        return jnp.moveaxis(t.reshape((B, NC, Q) + t.shape[2:]), 1, 0)

    xc, dac, dtc, bc, cc = map(to_chunks, (x, da, dt, b, c))
    s0 = jnp.zeros((B, H, P, N), jnp.float32)

    def body(s, inp):
        xq, daq, dtq, bq, cq = inp
        if use_ref:
            y, s_out = ssd_chunk_ref(xq, daq, dtq, bq, cq, s)
        else:
            y, s_out = ssd_chunk(xq, daq, dtq, bq, cq, s, interpret=interpret)
        return s_out, y

    s_final, ys = jax.lax.scan(body, s0, (xc, dac, dtc, bc, cc))
    y = jnp.moveaxis(ys, 0, 1).reshape(B, S, H, P)
    return y, s_final

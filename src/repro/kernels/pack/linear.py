"""Pallas TPU kernel: d-grid → linear write-buffer pack (paper §3.2).

    "For optimised performance, a one to one mapping of data from the code
     to the HDF5 file is desirable.  For this purpose, a linear write
     buffer is initialised on each rank in which the grid data is copied."

On the TPU the copy is the halo-strip + flatten of every resident d-grid
into the rank's contiguous staging buffer (row == grid — the file layout),
which then DMAs to the host in one piece.  Each block holds 8 d-grids: it
reads their (n+2)² halo-padded fields and writes their n² interior rows.
Eight rows per block is what the TPU's (8, 128) tiling needs of the
output; one row per block is refused by the v5e compiler.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


GRIDS_PER_BLOCK = 8


def _pack_kernel(p_ref, o_ref):
    p = p_ref[...]  # (bg, n+2, n+2)
    o_ref[...] = p[:, 1:-1, 1:-1].reshape(o_ref.shape)


@functools.partial(jax.jit, static_argnames=("interpret",))
def pack_grids(p: jax.Array, *, interpret: bool = False) -> jax.Array:
    """(G, n+2, n+2) halo-padded grids → (G, n·n) linear rows."""
    G, np2, _ = p.shape
    n = np2 - 2
    bg = min(GRIDS_PER_BLOCK, G)  # G < 8: one block spans the whole array
    return pl.pallas_call(
        _pack_kernel,
        grid=(pl.cdiv(G, bg),),
        in_specs=[pl.BlockSpec((bg, np2, np2), lambda g: (g, 0, 0))],
        out_specs=pl.BlockSpec((bg, n * n), lambda g: (g, 0)),
        out_shape=jax.ShapeDtypeStruct((G, n * n), p.dtype),
        interpret=interpret,
    )(p)


def pack_grids_ref(p: jax.Array) -> jax.Array:
    G, np2, _ = p.shape
    n = np2 - 2
    return p[:, 1:-1, 1:-1].reshape(G, n * n)

"""Pallas TPU kernel: batched Jacobi/SOR sweep over d-grids (paper §2.2).

The paper's hot spot is the pressure-Poisson solve (>90 % of runtime) on
block-structured d-grids of s_x×s_y cells with a halo of 1.  The TPU
adaptation processes a *batch* of d-grids per kernel invocation: the grid
dimension runs over d-grids, each block is one (s+2)² halo-padded grid —
at the paper's favoured 16–32² grid sizes a whole padded grid (34²·f32 ≈
4.6 KiB) sits trivially in VMEM, so the block IS the d-grid and the halo
is part of the block (no neighbour re-reads; halo exchange happens between
sweeps through the space-tree exchange in ``repro.cfd``).

    p'[i,j] = (1−ω)·p[i,j] + ω/4 · (p[i±1,j] + p[i,j±1] − h²·f[i,j])

ω=1 → Jacobi; ω≈1.7 → weighted (SOR-style) sweep used by the multigrid
smoother.  Validated against ``ref.py`` in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _jacobi_kernel(p_ref, f_ref, o_ref, *, h2: float, omega: float):
    p = p_ref[0].astype(jnp.float32)  # (n+2, n+2) halo-padded
    f = f_ref[0].astype(jnp.float32)  # (n, n)
    up = p[:-2, 1:-1]
    down = p[2:, 1:-1]
    left = p[1:-1, :-2]
    right = p[1:-1, 2:]
    centre = p[1:-1, 1:-1]
    new = 0.25 * (up + down + left + right - h2 * f)
    o_ref[0] = ((1.0 - omega) * centre + omega * new).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("h2", "omega", "interpret"))
def jacobi_sweep(
    p: jax.Array,  # (G, n+2, n+2) halo-padded d-grids
    f: jax.Array,  # (G, n, n) rhs
    h2: float,
    omega: float = 1.0,
    *,
    interpret: bool = False,
) -> jax.Array:
    """One weighted-Jacobi sweep over a batch of d-grids → (G, n, n)."""
    G, np2, _ = p.shape
    n = np2 - 2
    return pl.pallas_call(
        functools.partial(_jacobi_kernel, h2=float(h2), omega=float(omega)),
        grid=(G,),
        in_specs=[
            pl.BlockSpec((1, np2, np2), lambda g: (g, 0, 0)),
            pl.BlockSpec((1, n, n), lambda g: (g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, n, n), lambda g: (g, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((G, n, n), p.dtype),
        interpret=interpret,
    )(p, f)


def _residual_kernel(p_ref, f_ref, o_ref, *, inv_h2: float):
    p = p_ref[0].astype(jnp.float32)
    f = f_ref[0].astype(jnp.float32)
    lap = (
        p[:-2, 1:-1] + p[2:, 1:-1] + p[1:-1, :-2] + p[1:-1, 2:] - 4.0 * p[1:-1, 1:-1]
    ) * inv_h2
    o_ref[0] = (f - lap).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("h2", "interpret"))
def residual(p: jax.Array, f: jax.Array, h2: float, *, interpret: bool = False) -> jax.Array:
    """r = f − ∇²p on each d-grid → (G, n, n)."""
    G, np2, _ = p.shape
    n = np2 - 2
    return pl.pallas_call(
        functools.partial(_residual_kernel, inv_h2=1.0 / float(h2)),
        grid=(G,),
        in_specs=[
            pl.BlockSpec((1, np2, np2), lambda g: (g, 0, 0)),
            pl.BlockSpec((1, n, n), lambda g: (g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, n, n), lambda g: (g, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((G, n, n), p.dtype),
        interpret=interpret,
    )(p, f)

"""Per-kernel interpret-mode validation against the pure-jnp oracles,
swept over shapes and dtypes (deliverable c)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.attention.flash import flash_attention
from repro.kernels.attention.ops import mha
from repro.kernels.attention.ref import attention_ref
from repro.kernels.pack.linear import pack_grids, pack_grids_ref
from repro.kernels.ssd.chunk import ssd_chunk
from repro.kernels.ssd.ops import ssd_scan
from repro.kernels.ssd.ref import ssd_chunk_ref
from repro.kernels.stencil.jacobi import jacobi_sweep, residual
from repro.kernels.stencil.ref import jacobi_sweep_ref, residual_ref

KEY = jax.random.PRNGKey(7)


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else dict(atol=1e-4, rtol=1e-4)


# -- flash attention ---------------------------------------------------------------


@pytest.mark.parametrize(
    "BH,S,D,window", [(4, 128, 64, 0), (2, 256, 128, 0), (2, 256, 64, 64), (3, 100, 32, 0), (1, 64, 256, 16)]
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(BH, S, D, window, dtype):
    q = jax.random.normal(KEY, (BH, S, D), dtype)
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (BH, S, D), dtype)
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (BH, S, D), dtype)
    got = flash_attention(q, k, v, window=window, blk_q=64, blk_k=64, interpret=True)
    want = attention_ref(q, k, v, window=window)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **_tol(dtype)
    )


def test_mha_gqa_expansion_matches_ref():
    B, S, H, KV, Dh = 2, 64, 8, 2, 32
    q = jax.random.normal(KEY, (B, S, H, Dh), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(KEY, 3), (B, S, KV, Dh), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(KEY, 4), (B, S, KV, Dh), jnp.float32)
    got = mha(q, k, v, interpret=True)
    want = mha(q, k, v, use_ref=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_flash_matches_model_attention_path():
    """Kernel agrees with the XLA chunked-attention used by the models."""
    from repro.models.attention import _attend

    B, S, H, Dh = 2, 128, 4, 64
    q = jax.random.normal(KEY, (B, S, H, Dh), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(KEY, 5), (B, S, H, Dh), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(KEY, 6), (B, S, H, Dh), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    want = _attend(q, k, v, pos, pos, window=0)
    got = mha(q, k, v, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4, rtol=1e-4)


# -- SSD chunk ----------------------------------------------------------------------


@pytest.mark.parametrize("B,Q,H,P,N", [(2, 64, 8, 16, 32), (1, 128, 4, 32, 64), (2, 32, 16, 8, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_chunk_matches_ref(B, Q, H, P, N, dtype):
    k = jax.random.fold_in(KEY, 10)
    x = jax.random.normal(k, (B, Q, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(k, 1), (B, Q, H))) * 0.1
    da = -dt * jnp.exp(jax.random.normal(jax.random.fold_in(k, 2), (H,)) * 0.2)
    b = jax.random.normal(jax.random.fold_in(k, 3), (B, Q, N), dtype) * 0.3
    c = jax.random.normal(jax.random.fold_in(k, 4), (B, Q, N), dtype) * 0.3
    s_in = jax.random.normal(jax.random.fold_in(k, 5), (B, H, P, N)) * 0.1
    got_y, got_s = ssd_chunk(x, da, dt, b, c, s_in, hb=4, interpret=True)
    want_y, want_s = ssd_chunk_ref(x, da, dt, b, c, s_in)
    np.testing.assert_allclose(np.asarray(got_y, np.float32), np.asarray(want_y, np.float32), **_tol(dtype))
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s), atol=3e-2 if dtype == jnp.bfloat16 else 3e-5, rtol=3e-2 if dtype == jnp.bfloat16 else 3e-5)


def test_ssd_scan_matches_model_ssd():
    """Full-sequence kernel scan == the model's chunked jnp implementation."""
    from repro.models.ssd import ssd_chunked

    B, S, H, P, N = 2, 128, 4, 16, 32
    k = jax.random.fold_in(KEY, 20)
    x = jax.random.normal(k, (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(k, 1), (B, S, H))) * 0.1
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(k, 2), (H,)) * 0.2)
    b = jax.random.normal(jax.random.fold_in(k, 3), (B, S, N)) * 0.3
    c = jax.random.normal(jax.random.fold_in(k, 4), (B, S, N)) * 0.3
    y_kernel, s_kernel = ssd_scan(x, dt, A, b, c, chunk=64, interpret=True)
    y_model, s_model = ssd_chunked(
        x, dt, A, b.reshape(B, S, 1, N), c.reshape(B, S, 1, N)
    )
    np.testing.assert_allclose(np.asarray(y_kernel), np.asarray(y_model), atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(s_kernel), np.asarray(s_model), atol=2e-4, rtol=2e-4)


# -- stencil ------------------------------------------------------------------------


@pytest.mark.parametrize("G,n", [(4, 16), (2, 32), (8, 8)])
@pytest.mark.parametrize("dtype", [jnp.float32])
@pytest.mark.parametrize("omega", [1.0, 1.7])
def test_jacobi_sweep_matches_ref(G, n, dtype, omega):
    p = jax.random.normal(KEY, (G, n + 2, n + 2), dtype)
    f = jax.random.normal(jax.random.fold_in(KEY, 1), (G, n, n), dtype)
    got = jacobi_sweep(p, f, h2=0.01, omega=omega, interpret=True)
    want = jacobi_sweep_ref(p, f, h2=0.01, omega=omega)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("G,n", [(4, 16), (1, 32)])
def test_residual_matches_ref(G, n):
    p = jax.random.normal(KEY, (G, n + 2, n + 2), jnp.float32)
    f = jax.random.normal(jax.random.fold_in(KEY, 2), (G, n, n), jnp.float32)
    got = residual(p, f, h2=0.25, interpret=True)
    want = residual_ref(p, f, h2=0.25)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4, rtol=1e-4)


def test_jacobi_converges_on_poisson():
    """Sanity: repeated sweeps reduce the residual on a 1-grid problem."""
    n = 32
    f = jnp.zeros((1, n, n), jnp.float32)
    p = jnp.zeros((1, n + 2, n + 2), jnp.float32)
    p = p.at[:, 0, :].set(1.0)  # Dirichlet boundary in the halo
    r0 = float(jnp.abs(residual(p, f, h2=1.0, interpret=True)).mean())
    for _ in range(50):
        interior = jacobi_sweep(p, f, h2=1.0, interpret=True)
        p = p.at[:, 1:-1, 1:-1].set(interior)
    r1 = float(jnp.abs(residual(p, f, h2=1.0, interpret=True)).mean())
    assert r1 < r0 * 0.2


# -- pack ---------------------------------------------------------------------------


@pytest.mark.parametrize("G,n", [(4, 16), (2, 8), (1, 32), (12, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
def test_pack_grids_matches_ref(G, n, dtype):
    if dtype == jnp.int32:
        p = jax.random.randint(KEY, (G, n + 2, n + 2), 0, 1000, dtype)
    else:
        p = jax.random.normal(KEY, (G, n + 2, n + 2), dtype)
    got = pack_grids(p, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(pack_grids_ref(p)))

"""The main path's Pallas kernels compile for a TPU v5e at real size.

Nothing runs: each kernel is lowered and compiled by the TPU compiler for a
described (not attached) v5e chip, which refuses what interpret mode lets
through — a block that breaks the (8, 128) tiling rule, or more VMEM than a
kernel may use.  The topology is described inside a fixture, never at import,
so every test worker collects the same tests and only the worker given this
file loads the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.attention.flash import flash_attention
from repro.kernels.pack.linear import pack_grids
from repro.kernels.stencil.jacobi import jacobi_sweep, residual

# d-grids of 16² with a halo of 1: the snapshot cell's 65,536 grids
GRIDS_16 = (65536, 18, 18)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _compiled_hlo(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def test_jacobi_sweep_compiles_for_v5e(one_chip):
    G, np2, _ = GRIDS_16
    p = jax.ShapeDtypeStruct(GRIDS_16, jnp.float32, sharding=one_chip)
    f = jax.ShapeDtypeStruct((G, np2 - 2, np2 - 2), jnp.float32, sharding=one_chip)
    hlo = _compiled_hlo(lambda p, f: jacobi_sweep(p, f, h2=1e-6, omega=0.8), p, f)
    assert "tpu_custom_call" in hlo


def test_residual_compiles_for_v5e(one_chip):
    G, np2, _ = GRIDS_16
    p = jax.ShapeDtypeStruct(GRIDS_16, jnp.float32, sharding=one_chip)
    f = jax.ShapeDtypeStruct((G, np2 - 2, np2 - 2), jnp.float32, sharding=one_chip)
    hlo = _compiled_hlo(lambda p, f: residual(p, f, h2=1e-6), p, f)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("shape", [GRIDS_16, (16384, 34, 34)])
def test_pack_grids_compiles_for_v5e(one_chip, shape):
    p = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    assert "tpu_custom_call" in _compiled_hlo(pack_grids, p)


def test_flash_attention_compiles_for_v5e_at_qwen3_head_dim(one_chip):
    # qwen3-8b: 32 heads of head_dim 128, one sequence of 4096 tokens
    q = jax.ShapeDtypeStruct((32, 4096, 128), jnp.bfloat16, sharding=one_chip)
    assert "tpu_custom_call" in _compiled_hlo(flash_attention, q, q, q)


@pytest.mark.parametrize("dtype, n_fields", [(jnp.float32, 4), (jnp.int8, 1)], ids=["cells", "cell_type"])
def test_snapshot_rows_leave_the_v5e_row_major(one_chip, dtype, n_fields):
    """The snapshot cell's rows (3072 x 12288 cells, d-grids of 16²) come
    out of the device pack with the row as the minor dimension, so their
    copy to the host is C-ordered and needs no reordering there."""
    from repro.cfd.sim import stage_rows

    field = jax.ShapeDtypeStruct((3072, 12288), dtype, sharding=one_chip)
    compiled = stage_rows.lower((field,) * n_fields, gx=192, gy=768, n=16, dtype=dtype).compile()
    assert compiled.out_info.shape == (192 * 768, 256 * n_fields)
    assert compiled.output_formats.layout.major_to_minor == (0, 1)


def test_attention_backward_keeps_one_query_chunk_at_qwen3_widths(one_chip):
    """The gradient of chunked attention under ``remat="full"`` recomputes
    each 1024-query chunk's scores instead of keeping them all: at qwen3-8b's heads and 2 x 4096
    tokens it needs less than one whole 4096 x 4096 float32 score matrix
    (2.65 GB against 10.07 GB when every chunk's scores are kept)."""
    from repro.models.attention import _attend

    B, S, H, KV, D = 2, 4096, 32, 8, 128
    q = jax.ShapeDtypeStruct((B, S, H, D), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((B, S, KV, D), jnp.bfloat16, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=one_chip)

    def loss(q, k, v, p):
        return jnp.sum(_attend(q, k, v, p, p, 0, remat_chunks=True).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, kv, kv, pos).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < B * H * S * S * 4

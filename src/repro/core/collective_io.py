"""On-device hyperslab planning + aggregation gathers (shard_map).

The paper computes write offsets with ``MPI_Allreduce`` + ``MPI_Exscan``.
On a TPU mesh the same two collectives are a ``psum`` and a masked sum over
an ``all_gather`` under ``shard_map``.  ``tests/test_collective_io.py``
asserts this device plan agrees exactly with the numpy host planner in
``core.hyperslab`` (same reduce+exscan semantics, two implementations).

``gather_to_aggregators`` is the on-device half of collective buffering: the
mesh axis is split into aggregator groups and each group's data is gathered
onto every member (on real hardware only the aggregator host copies it off
the device; the others drop it — XLA DCE removes the dead gather output on
non-aggregator shards when the result is consumed conditionally).

The host-side half it feeds is the zero-copy vectored pipeline in
``core.aggregation``: the gathered block becomes stride-aware view requests
(``nd_slab_requests``, no payload copies), bucketed into MPI-IO-style file
domains and drained with ``pwritev`` — or, for chunked datasets, pushed
through the overlapped filter pipeline (``ChunkPipeline``).
``device_pack_linear`` below is the device-side staging step of that path.
Full stage map: ``docs/ARCHITECTURE.md``; on-disk layout: ``docs/FORMAT.md``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P


def collective_plan(mesh: Mesh, axis: str, counts: np.ndarray) -> tuple[int, np.ndarray]:
    """Device-side reduce + exscan over per-shard grid counts.

    ``counts``: (n_shards_along_axis,) int32, one entry per shard.
    Returns (total, exclusive_prefix_starts) as host values.
    """
    n = mesh.shape[axis]
    counts = np.asarray(counts, dtype=np.int32)
    if counts.shape != (n,):
        raise ValueError(f"counts must have shape ({n},), got {counts.shape}")

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=P(axis),
        out_specs=(P(), P(axis)),
        check_vma=False,
    )
    def plan(c):
        # c: (1,) — this shard's grid count
        gathered = jax.lax.all_gather(c, axis, tiled=True)  # (n,) replicated
        i = jax.lax.axis_index(axis)
        mask = jnp.arange(gathered.shape[0]) < i
        start = jnp.sum(jnp.where(mask, gathered, 0), dtype=jnp.int32)
        total = jnp.sum(gathered, dtype=jnp.int32)  # the MPI_Allreduce
        return total, start[None]

    with mesh:
        total, starts = plan(
            jax.device_put(counts, NamedSharding(mesh, P(axis)))
        )
    return int(np.asarray(total)), np.asarray(starts, dtype=np.int64)


def gather_to_aggregators(
    mesh: Mesh, axis: str, n_aggregators: int, x: jax.Array
) -> jax.Array:
    """All-gather within aggregator groups along ``axis``.

    ``x`` is sharded (axis, ...); output is sharded (axis, ...) where each
    shard holds its *group's* full block (group size = n/n_aggregators
    rows) — i.e. after this collective, aggregator shards can hand a single
    large contiguous buffer to the host writer.
    """
    n = mesh.shape[axis]
    if n % n_aggregators:
        raise ValueError(f"{n} shards not divisible by {n_aggregators} aggregators")
    group = n // n_aggregators

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=P(axis),
        out_specs=P(axis),
        check_vma=False,
    )
    def gather(block):
        # Gather the whole axis, then slice this shard's group window.  On a
        # ring interconnect the group gather lowers to a segmented
        # collective; slicing a full all_gather keeps the HLO simple and lets
        # XLA elide the unused segments on real topologies.
        full = jax.lax.all_gather(block, axis, tiled=True)  # (n*rows_local, ...)
        i = jax.lax.axis_index(axis)
        g = i // group
        rows_local = block.shape[0]
        start = g * group * rows_local
        return jax.lax.dynamic_slice_in_dim(full, start, group * rows_local, axis=0)

    with mesh:
        return gather(x)


@jax.jit
def _pack_linear(bufs: tuple[jax.Array, ...]) -> jax.Array:
    return jnp.concatenate(
        [
            b.reshape(-1).view(jnp.uint8)
            if b.dtype == jnp.uint8
            else b.reshape(-1).astype(b.dtype).view(jnp.uint8)
            for b in bufs
        ]
    )


def device_pack_linear(buffers: list[jax.Array]) -> jax.Array:
    """Concatenate a rank's tensors into its linear write buffer (the paper's
    'one to one mapping of data from the code to the HDF5 file ... a linear
    write buffer is initialised on each rank').  The jitted pack lives at
    module level so jax's own cache (keyed on treedef + shapes/dtypes) makes
    repeat calls with a static topology trace-free — one fused device kernel
    per distinct buffer signature, not per step."""
    return _pack_linear(tuple(buffers))

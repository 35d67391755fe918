"""Mesh construction for the production pods and for tests.

All mesh builders are FUNCTIONS — importing this module never touches jax
device state (the brief's requirement), so smoke tests keep seeing exactly
one device while ``dryrun.py`` (which sets
``--xla_force_host_platform_device_count=512`` before any import) can build
the full production meshes.

Production target: TPU v5e pods. One pod slice = 16×16 = 256 chips,
mesh axes (data, model); the multi-pod mesh prepends a ``pod`` axis
(2×16×16 = 512 chips) whose collectives ride DCN — cross-pod traffic is
kept to gradient reductions (see ``distributed.sharding``).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh

# v5e hardware constants used by the roofline analysis (per chip).
PEAK_BF16_FLOPS = 197e12  # FLOP/s
HBM_BW = 819e9  # B/s
ICI_BW_PER_LINK = 50e9  # B/s per link (≈, per the brief)
ICI_LINKS_PER_CHIP = 4  # v5e: 4 ICI links (2D torus, x±/y±)
HBM_PER_CHIP = 16 << 30  # 16 GiB
DCN_BW_PER_HOST = 25e9 / 8  # ~25 Gb/s NIC per host, bytes/s (cross-pod axis)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...], devices=None) -> Mesh:
    """`jax.make_mesh` with explicit Auto axis types (pjit-style sharding)."""
    return jax.make_mesh(
        shape, axes, axis_types=(AxisType.Auto,) * len(axes), devices=devices
    )


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """The graded production mesh: 16×16 (one pod) or 2×16×16 (two pods)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for the production mesh, have {len(devices)} — "
            "run under launch/dryrun.py which forces 512 host devices"
        )
    return make_mesh(shape, axes, devices=devices)


def make_test_mesh(n_data: int = 2, n_model: int = 2, pod: int | None = None) -> Mesh:
    """Small mesh for in-subprocess integration tests (8 forced devices)."""
    if pod is None:
        return make_mesh((n_data, n_model), ("data", "model"))
    return make_mesh((pod, n_data, n_model), ("pod", "data", "model"))


def data_axis_size(mesh: Mesh) -> int:
    n = mesh.shape.get("data", 1)
    n *= mesh.shape.get("pod", 1)
    return n

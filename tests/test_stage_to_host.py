"""``_stage_to_host`` on a 1-D mesh of eight host devices: a leaf split
eight ways along its rows is assembled from its shards into one host array,
bit for bit and C-ordered."""

import json

from _subproc import run_with_devices

CODE = r"""
import json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.checkpoint import _stage_to_host
from repro.launch.mesh import make_mesh
from repro.obs.trace import SPAN_CKPT_STAGE, TRACER

mesh = make_mesh((8,), ("io",))
rows = np.arange(8 * 16 * 32, dtype=np.float32).reshape(8 * 16, 32)
leaf = jax.device_put(jnp.asarray(rows), NamedSharding(mesh, P("io")))
with TRACER.phase(SPAN_CKPT_STAGE) as staging:
    host = _stage_to_host({"rows": leaf}, staging)["rows"]
print(json.dumps({
    "shards": len(leaf.addressable_shards),
    "is_numpy": isinstance(host, np.ndarray),
    "c_contiguous": bool(host.flags.c_contiguous),
    "exact": host.dtype == rows.dtype and host.shape == rows.shape and host.tobytes() == rows.tobytes(),
}))
"""


def test_a_leaf_split_eight_ways_stages_bit_for_bit_and_c_ordered():
    got = json.loads(run_with_devices(CODE, 8).strip().splitlines()[-1])
    assert got == {"shards": 8, "is_numpy": True, "c_contiguous": True, "exact": True}

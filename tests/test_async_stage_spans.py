"""``AsyncCheckpointer.save`` opens the phases ``ckpt.stage`` and
``ckpt.wait``: staging tagged with the bytes it copied to the host and the
bytes of the leaves that more than one device held, on a tree sharded over
four forced host devices."""

import json

import pytest

from _subproc import run_with_devices

CODE = r"""
import json, tempfile
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.checkpoint import AsyncCheckpointer, CheckpointManager
from repro.launch.mesh import make_mesh
from repro.obs import TRACER

mesh = make_mesh((2, 2), ("data", "model"))
tree = {
    "split": jax.device_put(jnp.arange(64 * 8, dtype=jnp.float32).reshape(64, 8), NamedSharding(mesh, P("data", "model"))),
    "rows": jax.device_put(jnp.ones((16, 4), jnp.bfloat16), NamedSharding(mesh, P("data"))),
    "replicated": jax.device_put(jnp.zeros((5,), jnp.int32), NamedSharding(mesh, P())),
    "one_device": jax.device_put(jnp.ones((3, 3), jnp.float32), jax.devices()[0]),
    "host": np.arange(7, dtype=np.int64),
    "count": 3,
}
out = {}
for double_buffer in (True, False):
    TRACER.configure(enabled=True, sample_every=1)
    TRACER.reset()
    with tempfile.TemporaryDirectory() as d:
        with CheckpointManager(d + "/run.th5") as mgr:
            saver = AsyncCheckpointer(mgr, double_buffer=double_buffer)
            saver.save(1, tree)
            saver.save(2, tree)
            saver.wait()
    ring = TRACER.drain()
    TRACER.configure(enabled=False)
    TRACER.reset()
    mine = sorted((s for s in ring if s.name in ("ckpt.stage", "ckpt.fetch", "ckpt.assemble", "ckpt.wait")), key=lambda s: s.t0)
    out[str(double_buffer)] = [[s.name, s.tags or {}, s.parent_id, s.span_id] for s in mine]
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def spans():
    return json.loads(run_with_devices(CODE, 4).strip().splitlines()[-1])


SPLIT, ROWS, REPLICATED, ONE_DEVICE, HOST = 64 * 8 * 4, 16 * 4 * 2, 5 * 4, 3 * 3 * 4, 7 * 8


@pytest.mark.parametrize("double_buffer", ["True", "False"])
def test_stage_is_tagged_with_bytes_staged_and_gathered(spans, double_buffer):
    stages = [tags for name, tags, *_ in spans[double_buffer] if name == "ckpt.stage"]
    assert len(stages) == 2
    for tags in stages:
        assert tags["bytes"] == SPLIT + ROWS + REPLICATED + ONE_DEVICE + HOST
        # held by more than one device: the sharded leaves and the replicated one
        assert tags["gathered_bytes"] == SPLIT + ROWS + REPLICATED


STAGE = ["ckpt.stage", "ckpt.fetch", "ckpt.assemble"]


@pytest.mark.parametrize("double_buffer,order", [
    ("True", [*STAGE, "ckpt.wait", *STAGE, "ckpt.wait", "ckpt.wait"]),
    ("False", ["ckpt.wait", *STAGE, "ckpt.wait", *STAGE, "ckpt.wait"]),
])
def test_wait_joins_the_write_in_flight_after_or_before_staging(spans, double_buffer, order):
    """Double-buffered, each save stages and then joins the write before it;
    single-buffered, it joins first.  Each is a phase of its own, and the
    last is the caller's ``wait()``.  Staging's fetch and assembly nest in
    its own span."""
    assert [name for name, *_ in spans[double_buffer]] == order
    stage_id = None
    for name, _, parent, span_id in spans[double_buffer]:
        if name in ("ckpt.stage", "ckpt.wait"):
            assert parent == 0
            stage_id = span_id if name == "ckpt.stage" else None
        else:
            assert stage_id is not None and parent == stage_id

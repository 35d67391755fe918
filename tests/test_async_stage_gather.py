"""``AsyncCheckpointer`` staging on four forced host devices: every copy in
flight at once (``ckpt.fetch``), the multi-device leaves assembled from
their distinct shards into C-ordered host buffers (``ckpt.assemble``), a
fresh set each save, so a write in flight keeps the bytes it was handed."""

import json

import pytest

from _subproc import run_with_devices

CODE = r"""
import json, tempfile, threading
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.checkpoint import AsyncCheckpointer, CheckpointManager
from repro.launch.mesh import make_mesh
from repro.obs import TRACER

mesh = make_mesh((2, 2), ("data", "model"))


def make_tree(k):
    return {
        "split": jax.device_put(jnp.arange(64 * 8, dtype=jnp.float32).reshape(64, 8) + k, NamedSharding(mesh, P("data", "model"))),
        "rows": jax.device_put(jnp.full((16, 4), 1 + k, jnp.bfloat16), NamedSharding(mesh, P("data"))),
        "replicated": jax.device_put(jnp.full((5,), k, jnp.int32), NamedSharding(mesh, P())),
        "one_device": jax.device_put(jnp.full((3, 3), k, jnp.float32), jax.devices()[0]),
        "host": np.arange(7, dtype=np.int64) + k,
        "count": 3 + k,
    }


def same_bits(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return a.dtype == b.dtype and a.shape == b.shape and a.tobytes() == b.tobytes()


trees = {step: make_tree(step) for step in (1, 2, 3)}


class Blocking(CheckpointManager):
    # keeps each staged tree; the write of step 1 blocks until the save
    # after it has staged, then checks what it was handed against the
    # device tree
    release = threading.Event()
    staged = {}
    exact = {}
    c_contiguous = {}

    def save(self, step, state, **kw):
        self.staged[step] = state
        if step == 1:
            assert self.release.wait(60)
        self.exact[str(step)] = {k: same_bits(state[k], v) for k, v in trees[step].items() if k != "count"}
        self.c_contiguous[str(step)] = {k: bool(v.flags.c_contiguous) for k, v in state.items() if k != "count"}
        return super().save(step, state, **kw)


class Saver(AsyncCheckpointer):
    def _stage(self, state):
        out = super()._stage(state)
        if self._thread is not None and self._thread.is_alive():
            Blocking.release.set()
        return out


def run(double_buffer):
    Blocking.staged, Blocking.exact, Blocking.c_contiguous = {}, {}, {}
    if double_buffer:
        Blocking.release.clear()
    else:  # each save joins the write before it first: nothing to hold back
        Blocking.release.set()
    TRACER.configure(enabled=True, sample_every=1)
    TRACER.reset()
    with tempfile.TemporaryDirectory() as d:
        with Blocking(d + "/run.th5") as mgr:
            saver = Saver(mgr, double_buffer=double_buffer)
            saver.save(1, trees[1])  # its write blocks until save 2 has staged
            saver.save(2, trees[2])
            saver.wait()
            saver.save(3, trees[3])
            saver.wait()
            restored = {step: mgr.restore(step)[1] for step in trees}
    ring = TRACER.drain()
    TRACER.configure(enabled=False)
    TRACER.reset()
    staged = Blocking.staged
    return {
        "exact": Blocking.exact,
        "c_contiguous": Blocking.c_contiguous,
        "restored": {str(step): {k: same_bits(restored[step][k], v) for k, v in tree.items()}
                     for step, tree in trees.items()},
        "count": [restored[step]["count"] == 3 + step for step in trees],
        # whether a gathered leaf of a save shares memory with the save's before
        "shared_with_before": {k: [bool(np.shares_memory(staged[n + 1][k], staged[n][k])) for n in (1, 2)]
                               for k in ("split", "rows", "replicated")},
        "spans": [[s.name, s.span_id, s.parent_id, s.tags or {}] for s in sorted(ring, key=lambda s: s.t0)
                  if s.name in ("ckpt.stage", "ckpt.fetch", "ckpt.assemble", "ckpt.plan")],
    }


print(json.dumps({str(db): run(db) for db in (True, False)}))
"""

SPLIT, ROWS, REPLICATED = 64 * 8 * 4, 16 * 4 * 2, 5 * 4
GATHERED = SPLIT + ROWS + REPLICATED


@pytest.fixture(scope="module")
def staged():
    return json.loads(run_with_devices(CODE, 4).strip().splitlines()[-1])


def _stages(spans):
    return [tags for name, _, _, tags in spans if name == "ckpt.stage"]


@pytest.mark.parametrize("double_buffer", ["True", "False"])
def test_staged_leaves_are_the_device_leaves_bit_for_bit_and_c_ordered(staged, double_buffer):
    run = staged[double_buffer]
    for step in ("1", "2", "3"):
        assert all(run["exact"][step].values()), run["exact"][step]
        assert all(run["c_contiguous"][step].values()), run["c_contiguous"][step]
    plans = [tags for name, _, _, tags in run["spans"] if name == "ckpt.plan"]
    assert len(plans) == 3
    assert all(tags["copy_bytes"] == 0 for tags in plans)


@pytest.mark.parametrize("double_buffer", ["True", "False"])
def test_every_save_gathers_into_fresh_buffers(staged, double_buffer):
    run = staged[double_buffer]
    assert not any(any(shared) for shared in run["shared_with_before"].values()), run["shared_with_before"]
    for tags in _stages(run["spans"]):
        assert tags == {"bytes": tags["bytes"], "gathered_bytes": GATHERED}
    for step in ("1", "2", "3"):
        assert all(run["restored"][step].values()), run["restored"][step]
    assert all(run["count"])


def test_a_save_while_a_write_is_in_flight_gathers_into_a_fresh_set(staged):
    """Double-buffered, save 2 stages while save 1's write is blocked, into
    buffers of its own: the write then reads step 1's bytes, bit for bit,
    and so does its restore."""
    run = staged["True"]
    assert not any(first for first, _ in run["shared_with_before"].values())
    assert all(run["exact"]["1"].values()), run["exact"]["1"]
    assert all(run["restored"]["1"].values()), run["restored"]["1"]


@pytest.mark.parametrize("double_buffer", ["True", "False"])
def test_fetch_and_assemble_nest_under_stage(staged, double_buffer):
    spans = [s for s in staged[double_buffer]["spans"] if s[0] != "ckpt.plan"]
    assert [name for name, *_ in spans] == ["ckpt.stage", "ckpt.fetch", "ckpt.assemble"] * 3
    for i in range(0, 9, 3):
        (_, stage_id, stage_parent, _), fetch, assemble = spans[i : i + 3]
        assert stage_parent == 0
        assert fetch[2] == assemble[2] == stage_id

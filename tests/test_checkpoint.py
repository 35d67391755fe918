"""Checkpoint manager: snapshot roundtrip, topology, elasticity, async, resume."""

import os

import numpy as np
import pytest

from repro.core import uid
from repro.core.checkpoint import AsyncCheckpointer, CheckpointManager, split_rows
from repro.core.container import TH5File


def make_state(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "params": {
            "embed": rng.standard_normal((64, 16)).astype(np.float32),
            "layers": [
                {"w": rng.standard_normal((16, 16)).astype(np.float32), "b": np.zeros(16, np.float32)}
                for _ in range(3)
            ],
        },
        "opt": {"mu": rng.standard_normal((64, 16)).astype(np.float32), "count": np.int64(7)},
        "step": 42,
        "rng_key": np.array([1, 2], dtype=np.uint32),
        "none_field": None,
        "tuple_field": (np.float32(0.5), np.arange(4)),
    }


def assert_state_equal(a, b):
    if isinstance(a, dict):
        assert set(a) == set(b)
        for k in a:
            assert_state_equal(a[k], b[k])
    elif isinstance(a, (list, tuple)):
        assert type(a) is type(b) and len(a) == len(b)
        for x, y in zip(a, b):
            assert_state_equal(x, y)
    elif a is None:
        assert b is None
    else:
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_save_restore_roundtrip(tmp_path):
    p = str(tmp_path / "run.th5")
    state = make_state()
    with CheckpointManager(p, common={"model": "tiny"}) as mgr:
        res = mgr.save(100, state, n_ranks=4)
        assert res.bytes_data > 0
        step, got = mgr.restore()
        assert step == 100
        assert_state_equal(got, state)
        assert mgr.common()["model"] == "tiny"


def test_multiple_steps_append(tmp_path):
    p = str(tmp_path / "run.th5")
    with CheckpointManager(p) as mgr:
        for s in (10, 20, 30):
            mgr.save(s, {"x": np.full(8, s, np.float32)})
        assert mgr.steps() == [10, 20, 30]
        _, st20 = mgr.restore(20)
        np.testing.assert_array_equal(st20["x"], np.full(8, 20, np.float32))
    # reopen (resume path)
    with CheckpointManager(p, create=False) as mgr:
        assert mgr.latest_step() == 30


def test_nranks_independent_of_restore(tmp_path):
    """Write with 8 ranks, read whole; paper: restart on any process count."""
    p = str(tmp_path / "run.th5")
    state = make_state(3)
    with CheckpointManager(p) as mgr:
        mgr.save(1, state, n_ranks=8)
        _, got = mgr.restore(1)
        assert_state_equal(got, state)


def test_elastic_leaf_shard_restore(tmp_path):
    """Save under 8 ranks, restore shards under 3 ranks, reassemble."""
    p = str(tmp_path / "run.th5")
    x = np.arange(13 * 5, dtype=np.float32).reshape(13, 5)
    with CheckpointManager(p) as mgr:
        mgr.save(1, {"x": x}, n_ranks=8)
        parts = [mgr.restore_leaf_shard(1, "x", r, 3) for r in range(3)]
        np.testing.assert_array_equal(np.concatenate(parts), x)
        counts = [p_.shape[0] for p_ in parts]
        np.testing.assert_array_equal(counts, split_rows(13, 3))


def test_topology_datasets(tmp_path):
    """grid_property: rank-ordered UIDs, root chunk at row 0 (paper Fig. 4)."""
    p = str(tmp_path / "run.th5")
    with CheckpointManager(p) as mgr:
        mgr.save(5, {"a": np.zeros((16, 2), np.float32), "b": np.ones((4,), np.float32)}, n_ranks=2)
        uids, boxes, order = mgr.topology(5)
        ranks, locals_, _, _ = uid.unpack_array(uids)
        # rank-major ordering
        assert (np.diff(ranks.astype(np.int64)) >= 0).all()
        assert ranks[0] == 0 and locals_[0] == 0  # root chunk at row 0
        assert boxes.shape[1] == 3
        assert order == sorted(order)


def test_checksum_detects_corruption_and_fallback(tmp_path):
    """Bit-rot in newest snapshot → latest_valid falls back one step."""
    p = str(tmp_path / "run.th5")
    with CheckpointManager(p) as mgr:
        mgr.save(1, {"x": np.zeros(1024, np.float32)})
        mgr.save(2, {"x": np.ones(1024, np.float32)})
        meta = mgr.file.meta("/simulation/step_00000002/state/x")
        off = meta.offset
    with open(p, "r+b") as fh:
        fh.seek(off + 17)
        fh.write(b"\x55")
    with CheckpointManager(p, create=False) as mgr:
        assert mgr.latest_valid() == 1
        step, st = mgr.restore()  # auto-fallback
        assert step == 1
        np.testing.assert_array_equal(st["x"], np.zeros(1024, np.float32))


def test_torn_write_invisible(tmp_path):
    """Kill mid-save (before commit): reopened file shows only prior steps."""
    p = str(tmp_path / "run.th5")
    mgr = CheckpointManager(p)
    mgr.save(1, {"x": np.zeros(8, np.float32)})
    # simulate a crash inside save: write slabs manually without commit
    f = mgr.file
    d = f.create_dataset("/simulation/step_00000002/state/x", (8,), "<f4")
    f.write_full(d, np.ones(8, np.float32))
    os.close(f.fd)  # no commit — process died
    f._closed = True
    with CheckpointManager(p, create=False) as mgr2:
        assert mgr2.steps() == [1]
        assert mgr2.latest_valid() == 1


def test_async_checkpointer_overlap(tmp_path):
    p = str(tmp_path / "run.th5")
    with CheckpointManager(p) as mgr:
        ac = AsyncCheckpointer(mgr)
        state = {"x": np.arange(32, dtype=np.float32)}
        ac.save(1, state)
        state["x"][:] = -1  # mutate after save returns — staging must have copied
        res = ac.wait()
        assert res is not None and res.step == 1
        _, got = mgr.restore(1)
        np.testing.assert_array_equal(got["x"], np.arange(32, dtype=np.float32))


def test_async_error_surfaces(tmp_path):
    p = str(tmp_path / "run.th5")
    with CheckpointManager(p) as mgr:
        ac = AsyncCheckpointer(mgr)
        ac.save(1, {"x": np.zeros(4, np.float32)})
        ac.wait()
        ac.save(1, {"x": np.zeros(4, np.float32)})  # duplicate step → error
        with pytest.raises(ValueError):
            ac.wait()


def test_duplicate_step_rejected(tmp_path):
    p = str(tmp_path / "run.th5")
    with CheckpointManager(p) as mgr:
        mgr.save(1, {"x": np.zeros(4, np.float32)})
        with pytest.raises(ValueError):
            mgr.save(1, {"x": np.zeros(4, np.float32)})


def test_split_rows_balanced():
    np.testing.assert_array_equal(split_rows(10, 3), [4, 3, 3])
    np.testing.assert_array_equal(split_rows(2, 4), [1, 1, 0, 0])
    assert split_rows(0, 4).sum() == 0


def test_codec_policy_default_table(tmp_path):
    """CodecPolicy.default() (ROADMAP open item, first slice): the measured
    per-dtype / per-leaf-name table resolves fields to the lossy codec,
    large float leaves to shuffle+zlib, integers to plain zlib, and small
    leaves to the contiguous zero-copy path — and attaching it at manager
    construction means save() needs no per-call policy."""
    from repro.core.checkpoint import CodecPolicy

    pol = CodecPolicy.default()
    big_f32 = np.zeros((4096, 64), np.float32)
    assert pol.resolve("fields/u", big_f32) == "int8-blockq"
    assert pol.resolve("sim/fields/p", big_f32) == "int8-blockq"
    assert pol.resolve("params/w", big_f32) == "shuffle+zlib"  # dtype upgrade
    assert pol.resolve("opt/count", np.zeros((100_000,), np.int64)) == "zlib"
    assert pol.resolve("fields/mask", np.zeros((100_000,), np.int32)) == "zlib"  # lossy→lossless
    assert pol.resolve("step", np.int64(3)) == "none"  # tiny: stays contiguous
    # the classmethod constructor coexists with the `default` codec field
    assert pol.default == "zlib"

    p = str(tmp_path / "run.th5")
    rng = np.random.default_rng(5)
    state = {
        "fields": {"u": (rng.integers(0, 256, (2048, 64)) / 256).astype(np.float32)},
        "params": {"w": rng.standard_normal((2048, 64)).astype(np.float32)},
        "step": np.int64(7),
    }
    with CheckpointManager(p, codec_policy=CodecPolicy.default()) as mgr:
        res = mgr.save(0, state)  # no per-call policy
        assert res.filter_stats.n_chunks > 0  # leaves actually went chunked
        assert res.compression_ratio > 1.0
        assert mgr.file.meta("/simulation/step_00000000/state/fields.u").codec == "int8-blockq"
        assert mgr.file.meta("/simulation/step_00000000/state/params.w").codec == "shuffle+zlib"
        step, got = mgr.restore(0)
        np.testing.assert_array_equal(got["params"]["w"], state["params"]["w"])  # lossless
        from repro.core.codecs import Int8BlockQCodec

        assert (
            np.abs(got["fields"]["u"] - state["fields"]["u"]).max()
            <= Int8BlockQCodec.tolerance(state["fields"]["u"])
        )
        # an explicit per-call policy still overrides the manager's
        res2 = mgr.save(1, state, codec_policy=CodecPolicy(default="none"))
        assert res2.filter_stats.n_chunks == 0


def test_zero_d_device_array_restores_as_array(tmp_path):
    """A 0-d jax array (a train state's step counter) saved synchronously
    comes back as a 0-d int32 array, not a Python int; Python and numpy
    scalars still come back as Python scalars."""
    import jax.numpy as jnp

    state = {"step": jnp.asarray(7, jnp.int32), "t": np.float64(0.5), "n": 3}
    with CheckpointManager(str(tmp_path / "s.th5")) as mgr:
        mgr.save(0, state)
        _, got = mgr.restore(0)
    assert isinstance(got["step"], np.ndarray) and got["step"].dtype == np.int32
    assert got["step"].shape == () and int(got["step"]) == 7
    assert got["t"] == 0.5 and isinstance(got["t"], float)
    assert got["n"] == 3 and isinstance(got["n"], int)


def test_plan_counts_the_bytes_it_copies_into_c_order(tmp_path):
    """``ckpt.plan`` tags ``copy_bytes`` with the leaves planning had to
    copy into C order: a Fortran-ordered leaf counts, a C-ordered one does
    not, and both read back as saved."""
    from repro.obs import TRACER

    fortran = np.asfortranarray(np.arange(48 * 16, dtype=np.float32).reshape(48, 16))
    c = np.arange(64, dtype=np.int32).reshape(8, 8)
    assert not fortran.flags.c_contiguous and c.flags.c_contiguous
    TRACER.configure(enabled=True, sample_every=1)
    TRACER.reset()
    try:
        with CheckpointManager(str(tmp_path / "f.th5")) as mgr:
            mgr.save(0, {"f": fortran, "c": c}, n_ranks=2)
            _, got = mgr.restore(0)
        ring = TRACER.drain()
    finally:
        TRACER.configure(enabled=False)
        TRACER.reset()
    (plan,) = [s for s in ring if s.name == "ckpt.plan"]
    assert plan.tags == {"bytes": fortran.nbytes + c.nbytes, "copy_bytes": fortran.nbytes}
    np.testing.assert_array_equal(got["f"], fortran)
    np.testing.assert_array_equal(got["c"], c)

"""Zero-copy double-buffered pipeline: short-write resume, IOV_MAX chunking,
copy accounting, vectored scatter-reads, prefetch, plan cache."""

import os
import threading

import numpy as np
import pytest

from repro.core import aggregation
from repro.core.aggregation import (
    COPY_COUNTER,
    AggregationConfig,
    CollectiveWriter,
    WriteRequest,
    assign_file_domains,
    nd_slab_requests,
    pwritev_run,
)
from repro.core.checkpoint import AsyncCheckpointer, CheckpointManager
from repro.core.container import READ_COUNTER, TH5File, _advance, preadv_full
from repro.core.sliding_window import WindowPrefetcher, iter_lod_windows


# -- short-write resume (aggregation._advance + pwritev_run) -------------------


def test_advance_drops_prefix_bytes():
    bufs = [memoryview(b"abcd"), memoryview(b"efg"), memoryview(b"hi")]
    assert _advance(bufs, 0) is bufs
    assert b"".join(_advance(bufs, 3)) == b"defghi"
    assert b"".join(_advance(bufs, 4)) == b"efghi"
    assert b"".join(_advance(bufs, 6)) == b"ghi"
    assert b"".join(_advance(bufs, 9)) == b""
    # aggregation re-exports the same helper (short-write resume lives once)
    assert aggregation._advance is _advance


def _capped_pwritev(cap):
    real = os.pwritev

    def fake(fd, bufs, offset):
        take, left = [], cap
        for b in bufs:
            if left <= 0:
                break
            mv = memoryview(b)
            take.append(mv[:left])
            left -= len(take[-1])
        return real(fd, take, offset)

    return fake


def test_pwritev_run_resumes_short_writes(tmp_path, monkeypatch):
    rng = np.random.default_rng(0)
    payload = [rng.integers(0, 255, 10, dtype=np.uint8) for _ in range(5)]
    reqs = [WriteRequest(i * 10, p) for i, p in enumerate(payload)]
    path = str(tmp_path / "short.bin")
    fd = os.open(path, os.O_RDWR | os.O_CREAT, 0o644)
    try:
        monkeypatch.setattr(os, "pwritev", _capped_pwritev(7))
        wrote, calls = pwritev_run(fd, 0, reqs)
    finally:
        os.close(fd)
    assert wrote == 50
    assert calls == -(-50 // 7)  # every syscall was short: ceil(50/7) calls
    with open(path, "rb") as f:
        assert f.read() == b"".join(p.tobytes() for p in payload)


def test_pwritev_run_chunks_beyond_iov_max(tmp_path, monkeypatch):
    monkeypatch.setattr(aggregation, "_IOV_MAX", 4)
    reqs = [WriteRequest(i * 3, bytes([i % 251]) * 3) for i in range(21)]
    path = str(tmp_path / "iov.bin")
    fd = os.open(path, os.O_RDWR | os.O_CREAT, 0o644)
    try:
        wrote, calls = pwritev_run(fd, 0, reqs)
    finally:
        os.close(fd)
    assert wrote == 63
    assert calls == -(-21 // 4)  # one syscall per 4-buffer chunk
    with open(path, "rb") as f:
        assert f.read() == b"".join(bytes([i % 251]) * 3 for i in range(21))


def test_pwritev_run_large_request_list_unpatched(tmp_path):
    """> real IOV_MAX (1024) requests in one coalesced run."""
    n = 1500
    reqs = [WriteRequest(i, bytes([i % 256])) for i in range(n)]
    path = str(tmp_path / "big.bin")
    fd = os.open(path, os.O_RDWR | os.O_CREAT, 0o644)
    try:
        wrote, calls = pwritev_run(fd, 0, reqs)
    finally:
        os.close(fd)
    assert wrote == n
    assert calls >= 2  # at least two IOV_MAX batches
    with open(path, "rb") as f:
        assert f.read() == bytes(i % 256 for i in range(n))


def test_short_write_resume_through_collective_writer(tmp_path, monkeypatch):
    """End-to-end: coalesced collective write survives short pwritev."""
    counts = [3, 5, 2]
    rng = np.random.default_rng(1)
    payload = [rng.integers(0, 255, (c, 16), dtype=np.uint8) for c in counts]
    path = str(tmp_path / "cw.th5")
    with TH5File.create(path) as f:
        meta = f.create_dataset("/x", (10, 16), "<u1")
        off = 0
        reqs = []
        for p in payload:
            reqs.append([WriteRequest(meta.offset + off, p)])
            off += p.nbytes
        monkeypatch.setattr(os, "pwritev", _capped_pwritev(13))
        with CollectiveWriter(f.fd, AggregationConfig(n_aggregators=2)) as w:
            stats = w.write_collective(reqs)
        monkeypatch.undo()
        f.commit()
    assert stats.bytes_written == 160
    with TH5File.open(path) as f:
        np.testing.assert_array_equal(f.read("/x"), np.concatenate(payload))


# -- zero-copy accounting ------------------------------------------------------


def test_tp_sharded_nd_slab_is_zero_copy_at_32_ranks(tmp_path):
    """Acceptance: the coalesced zero-copy path issues ZERO payload copies in
    the TP-sharded (inner-dim) layout at 32 ranks."""
    rows, cols, n_ranks = 64, 256, 32
    cpr = cols // n_ranks
    rng = np.random.default_rng(2)
    shards = [np.ascontiguousarray(rng.random((rows, cpr), np.float32)) for _ in range(n_ranks)]
    path = str(tmp_path / "tp.th5")
    with TH5File.create(path) as f:
        meta = f.create_dataset("/w", (rows, cols), "<f4")
        COPY_COUNTER.reset()
        reqs = [
            nd_slab_requests(
                meta.offset, (rows, cols), 4,
                (slice(0, rows), slice(r * cpr, (r + 1) * cpr)), shards[r],
            )
            for r in range(n_ranks)
        ]
        with CollectiveWriter(f.fd, AggregationConfig(n_aggregators=8)) as w:
            stats = w.write_collective(reqs)
        n_copies, bytes_copied = COPY_COUNTER.snapshot()
        f.commit()
    assert n_copies == 0 and bytes_copied == 0
    assert stats.n_copies == 0 and stats.bytes_copied == 0
    assert stats.bytes_written == rows * cols * 4
    with TH5File.open(path) as f:
        np.testing.assert_array_equal(f.read("/w"), np.concatenate(shards, axis=1))


def test_nd_slab_stride_aware_views_from_parent_array():
    """An inner-dim slice of a larger array (non-contiguous overall, rows
    individually contiguous) must still produce zero-copy requests."""
    parent = np.arange(16 * 12, dtype=np.int32).reshape(16, 12)
    shard = parent[:, 4:8]  # NOT C-contiguous; each row IS contiguous
    assert not shard.flags.c_contiguous
    COPY_COUNTER.reset()
    reqs = nd_slab_requests(0, (16, 12), 4, (slice(0, 16), slice(4, 8)), shard)
    assert COPY_COUNTER.snapshot() == (0, 0)
    assert len(reqs) == 16
    for i, r in enumerate(reqs):
        assert r.nbytes == 16
        view = r.data
        assert isinstance(view, np.ndarray) and view.base is not None
        np.testing.assert_array_equal(view, parent[i, 4:8])


def test_copy_counter_tracks_payload_materialisation():
    COPY_COUNTER.reset()
    r = WriteRequest(0, np.zeros(10, np.uint8))
    r.payload()
    assert COPY_COUNTER.snapshot() == (1, 10)
    WriteRequest(0, b"abc").payload()  # bytes payloads are free
    assert COPY_COUNTER.snapshot() == (1, 10)


# -- file domains --------------------------------------------------------------


def test_assign_file_domains_balanced_and_ordered():
    reqs = [WriteRequest(i * 10, bytes(10)) for i in range(8)]
    domains = assign_file_domains(list(reversed(reqs)), 4)
    assert len(domains) == 4
    assert [len(d) for d in domains] == [2, 2, 2, 2]
    flat = [r.offset for d in domains for r in d]
    assert flat == sorted(flat)
    # never more domains than aggregators even with awkward sizes
    assert len(assign_file_domains(reqs, 3)) == 3


def test_file_domains_coalesce_tp_layout_into_fewer_syscalls(tmp_path):
    """Rank bucketing fragments column-sharded writes; file domains stitch
    whole rows back together → strictly fewer syscalls."""
    rows, cols, n_ranks = 32, 64, 16
    cpr = cols // n_ranks
    rng = np.random.default_rng(3)
    shards = [np.ascontiguousarray(rng.random((rows, cpr), np.float32)) for r in range(n_ranks)]

    def write(path, file_domains):
        with TH5File.create(path) as f:
            meta = f.create_dataset("/w", (rows, cols), "<f4")
            reqs = [
                nd_slab_requests(
                    meta.offset, (rows, cols), 4,
                    (slice(0, rows), slice(r * cpr, (r + 1) * cpr)), shards[r],
                )
                for r in range(n_ranks)
            ]
            cfg = AggregationConfig(n_aggregators=4, file_domains=file_domains)
            with CollectiveWriter(f.fd, cfg) as w:
                stats = w.write_collective(reqs)
            f.commit()
        return stats

    s_dom = write(str(tmp_path / "dom.th5"), True)
    s_rank = write(str(tmp_path / "rank.th5"), False)
    assert s_dom.bytes_written == s_rank.bytes_written == rows * cols * 4
    assert s_dom.n_syscalls < s_rank.n_syscalls
    with TH5File.open(str(tmp_path / "dom.th5")) as f1, TH5File.open(
        str(tmp_path / "rank.th5")
    ) as f2:
        np.testing.assert_array_equal(f1.read("/w"), f2.read("/w"))
        np.testing.assert_array_equal(f1.read("/w"), np.concatenate(shards, axis=1))


# -- persistent pool + async submission ----------------------------------------


def test_persistent_aggregator_pool_reused_across_steps(tmp_path):
    with TH5File.create(str(tmp_path / "p.th5")) as f:
        meta = f.create_dataset("/x", (8, 64), "<u1")
        data = np.ones((4, 64), np.uint8)
        reqs = [[WriteRequest(meta.offset, data)], [WriteRequest(meta.offset + data.nbytes, data)]]
        with CollectiveWriter(f.fd, AggregationConfig(n_aggregators=2)) as w:
            w.write_collective(reqs)
            pool = w._pool
            assert pool is not None
            w.write_collective(reqs)
            assert w._pool is pool  # no per-step spawn/teardown
        assert w._pool is None  # context exit releases the threads


def test_submit_collective_overlaps_with_caller(tmp_path):
    rng = np.random.default_rng(4)
    data = rng.integers(0, 255, (64, 128), dtype=np.uint8)
    path = str(tmp_path / "a.th5")
    with TH5File.create(path) as f:
        meta = f.create_dataset("/x", data.shape, "<u1")
        with CollectiveWriter(f.fd, AggregationConfig(n_aggregators=2)) as w:
            fut = w.submit_collective([[WriteRequest(meta.offset, data)]])
            stats = fut.result(timeout=30)
        assert stats.bytes_written == data.nbytes
        f.commit()
    with TH5File.open(path) as f:
        np.testing.assert_array_equal(f.read("/x"), data)


# -- vectored scatter reads ----------------------------------------------------


def test_preadv_full_scatter_and_short_resume(tmp_path, monkeypatch):
    path = str(tmp_path / "r.bin")
    blob = bytes(range(256)) * 4
    with open(path, "wb") as f:
        f.write(blob)
    fd = os.open(path, os.O_RDONLY)
    try:
        a = np.zeros(100, np.uint8)
        b = np.zeros(156, np.uint8)
        real = os.preadv

        def short_preadv(fd_, bufs, off):
            bufs = [memoryview(x)[:37] for x in bufs[:1]]  # 37 bytes max
            return real(fd_, bufs, off)

        monkeypatch.setattr(os, "preadv", short_preadv)
        n, calls = preadv_full(fd, [memoryview(a), memoryview(b)], 0)
    finally:
        os.close(fd)
    assert n == 256
    # 37-byte short reads never cross a buffer boundary in the fake:
    # a → 37+37+26, b → 37·4+8 = 8 resumed syscalls
    assert calls == 8
    assert bytes(a) + bytes(b) == blob[:256]


def test_read_row_indices_vectored_scatter(tmp_path):
    rng = np.random.default_rng(5)
    data = rng.random((64, 7), np.float64)
    path = str(tmp_path / "s.th5")
    with TH5File.create(path) as f:
        meta = f.create_dataset("/d", data.shape, "<f8")
        f.write_full(meta, data)
        f.commit()
        # unsorted, with duplicates and contiguous runs
        idx = [5, 3, 4, 40, 41, 42, 3, 63, 0]
        READ_COUNTER.reset()
        got = f.read_row_indices("/d", idx)
        syscalls, nbytes = READ_COUNTER.snapshot()
        np.testing.assert_array_equal(got, data[idx])
        # runs: [0],[3],[3,4,5],[40..42],[63] → 5 coalesced preadv calls
        assert syscalls == 5
        assert nbytes == len(idx) * 7 * 8
        with pytest.raises(Exception):
            f.read_row_indices("/d", [64])


def test_read_rows_into_preallocated(tmp_path):
    data = np.arange(48, dtype=np.float32).reshape(12, 4)
    path = str(tmp_path / "ri.th5")
    with TH5File.create(path) as f:
        meta = f.create_dataset("/d", data.shape, "<f4")
        f.write_full(meta, data)
        f.commit()
        out = np.empty((5, 4), np.float32)
        n = f.read_rows_into("/d", 3, 5, out)
        assert n == 5 * 4 * 4
        np.testing.assert_array_equal(out, data[3:8])
        with pytest.raises(Exception):
            f.read_rows_into("/d", 0, 5, np.empty((4, 4), np.float32))


def test_zero_sized_reads_and_writes(tmp_path):
    """Empty extents must round-trip, not crash in the byte-view casts."""
    path = str(tmp_path / "z.th5")
    with TH5File.create(path) as f:
        meta = f.create_dataset("/empty", (0, 4), "<f4")
        assert f.read("/empty").shape == (0, 4)
        assert f.read_rows("/empty", 0, 0).shape == (0, 4)
        # empty write request through the collective path writes 0 bytes
        reqs = nd_slab_requests(
            meta.offset, (8, 4), 4, (slice(0, 0), slice(0, 4)), np.empty((0, 4), np.float32)
        )
        with CollectiveWriter(f.fd, AggregationConfig(n_aggregators=2)) as w:
            stats = w.write_collective([reqs])
        assert stats.bytes_written == 0
        f.commit()
    # elastic restore with more ranks than rows → this rank owns 0 rows
    mgr = CheckpointManager(str(tmp_path / "e.th5"))
    mgr.save(0, {"w": np.ones((4, 2), np.float32)}, n_ranks=2)
    shard = mgr.restore_leaf_shard(0, "w", rank=5, n_ranks=8)
    assert shard.shape == (0, 2)
    mgr.close()


def test_write_stats_copies_not_polluted_by_concurrent_planning(tmp_path):
    """Per-write copy stats must ignore copies made by other threads during
    the write window (the double-buffer overlap submit_collective enables)."""
    data = np.zeros((512, 64), np.uint8)
    path = str(tmp_path / "cc.th5")
    with TH5File.create(path) as f:
        meta = f.create_dataset("/x", data.shape, "<u1")
        stop = threading.Event()

        def churn():  # a "step n+1 planner" making copies concurrently
            junk = WriteRequest(0, np.ones(64, np.uint8))
            while not stop.is_set():
                junk.payload()

        t = threading.Thread(target=churn, daemon=True)
        t.start()
        try:
            with CollectiveWriter(f.fd, AggregationConfig(n_aggregators=2)) as w:
                for _ in range(5):
                    stats = w.write_collective([[WriteRequest(meta.offset, data)]])
                    assert stats.n_copies == 0 and stats.bytes_copied == 0
        finally:
            stop.set()
            t.join()
        f.commit()


# -- prefetcher ----------------------------------------------------------------


def test_window_prefetcher_matches_direct_gather(tmp_path):
    rng = np.random.default_rng(6)
    data = rng.random((100, 3), np.float32)
    path = str(tmp_path / "w.th5")
    with TH5File.create(path) as f:
        meta = f.create_dataset("/d", data.shape, "<f4")
        f.write_full(meta, data)
        f.commit()
        windows = [list(range(i, i + 10)) for i in range(0, 90, 5)]
        with WindowPrefetcher(f, "/d") as pf:
            got = list(pf.iter_windows(windows))
        assert len(got) == len(windows)
        for g, w in zip(got, windows):
            np.testing.assert_array_equal(g, data[w])
        # empty window sequence is fine
        with WindowPrefetcher(f, "/d") as pf:
            assert list(pf.iter_windows([])) == []


def test_iter_lod_windows_budget(tmp_path):
    data = np.arange(200, dtype=np.float32).reshape(100, 2)
    path = str(tmp_path / "l.th5")
    with TH5File.create(path) as f:
        meta = f.create_dataset("/d", data.shape, "<f4")
        f.write_full(meta, data)
        f.commit()
        got = list(iter_lod_windows(f, "/d", [(0, 100), (50, 60)], max_rows=25))
        assert len(got[0]) <= 25  # stride-decimated to the budget
        np.testing.assert_array_equal(got[1], data[50:60])  # fits, stride 1


# -- plan cache + double-buffered checkpointing --------------------------------


def _state(seed):
    rng = np.random.default_rng(seed)
    return {
        "w": rng.random((32, 8), np.float32),
        "b": rng.random((32,), np.float32),
    }


def test_plan_cache_hits_on_static_topology(tmp_path):
    mgr = CheckpointManager(str(tmp_path / "c.th5"))
    mgr.save(0, _state(0), n_ranks=4)
    info0 = mgr.plan_cache_info()
    assert info0["hits"] == 0 and info0["misses"] == 2  # two distinct leaf plans
    mgr.save(1, _state(1), n_ranks=4)
    info1 = mgr.plan_cache_info()
    assert info1["misses"] == 2  # static topology: no re-planning at all
    assert info1["hits"] == 2
    s0, t0 = mgr.restore(0)[1], _state(0)
    np.testing.assert_array_equal(s0["w"], t0["w"])
    s1, t1 = mgr.restore(1)[1], _state(1)
    np.testing.assert_array_equal(s1["b"], t1["b"])
    mgr.close()


def test_double_buffered_async_checkpointer(tmp_path):
    mgr = CheckpointManager(str(tmp_path / "d.th5"))
    ac = AsyncCheckpointer(mgr)
    assert ac.double_buffer
    for step in range(3):
        ac.save(step, _state(step), n_ranks=2)
    ac.wait()
    for step in range(3):
        got = mgr.restore(step)[1]
        np.testing.assert_array_equal(got["w"], _state(step)["w"])
    mgr.close()


def test_async_checkpointer_single_buffer_mode(tmp_path):
    mgr = CheckpointManager(str(tmp_path / "sb.th5"))
    ac = AsyncCheckpointer(mgr, double_buffer=False)
    ac.save(0, _state(0))
    ac.save(1, _state(1))
    ac.wait()
    np.testing.assert_array_equal(mgr.restore(1)[1]["b"], _state(1)["b"])
    mgr.close()

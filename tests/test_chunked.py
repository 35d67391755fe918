"""Chunked + compressed TH5 datasets: round-trip properties, the overlapped
filter pipeline, variable-length file domains, LRU chunk cache, and the
checkpoint codec policy (docs/FORMAT.md is the layout spec)."""

import os
import tempfile
from pathlib import Path

import numpy as np
import pytest

from tests._hyp import given, settings, st

from repro.core.aggregation import (
    COPY_COUNTER,
    AggregationConfig,
    ChunkPipeline,
    CollectiveWriter,
    WriteRequest,
    assign_file_domains,
)
from repro.core.checkpoint import CheckpointManager, CodecPolicy
from repro.core.codecs import (
    CODEC_NONE,
    CODEC_ZLIB,
    Int8BlockQCodec,
    encode_chunk,
    get_codec,
)
from repro.core.container import TH5Error, TH5File


def _roundtrip(tmp_path, data, chunk_rows, codec, name="rt.th5"):
    path = str(tmp_path / name)
    with TH5File.create(path) as f:
        meta = f.create_chunked_dataset("/d", data.shape, data.dtype, chunk_rows, codec)
        f.write_chunked(meta, data)
        f.commit()
    with TH5File.open(path) as f:
        return f.read("/d", verify=True), f.meta("/d")


# -- round-trip properties (hypothesis via the tests/_hyp shim) ----------------


@settings(max_examples=30, deadline=None)
@given(
    rows=st.integers(min_value=1, max_value=70),
    cols=st.integers(min_value=1, max_value=9),
    chunk_rows=st.integers(min_value=1, max_value=80),
    codec=st.sampled_from(["none", "zlib", "zlib:6", "shuffle+zlib", "shuffle+zlib:6"]),
    dtype=st.sampled_from(["<f4", "<f8", "<i4", "<u1"]),
    seed=st.integers(min_value=0, max_value=2**16),
)
def test_lossless_roundtrip_bitexact(rows, cols, chunk_rows, codec, dtype, seed):
    """Any (shape, chunk size, lossless codec) combination round-trips
    bit-exact, including chunk_rows > rows and ragged final chunks."""
    rng = np.random.default_rng(seed)
    dt = np.dtype(dtype)
    if dt.kind == "f":
        data = (rng.integers(0, 32, (rows, cols)) / 32).astype(dt)
    else:
        data = rng.integers(0, 100, (rows, cols)).astype(dt)
    with tempfile.TemporaryDirectory() as d:  # one directory per example
        got, meta = _roundtrip(Path(d), data, chunk_rows, codec)
    np.testing.assert_array_equal(got, data)
    assert len(meta.chunks) == -(-rows // min(chunk_rows, 80))


@settings(max_examples=20, deadline=None)
@given(
    rows=st.integers(min_value=1, max_value=60),
    cols=st.integers(min_value=1, max_value=7),
    chunk_rows=st.integers(min_value=1, max_value=64),
    seed=st.integers(min_value=0, max_value=2**16),
)
def test_lossy_roundtrip_within_stored_scale_tolerance(rows, cols, chunk_rows, seed):
    rng = np.random.default_rng(seed)
    data = ((rng.random((rows, cols)) - 0.5) * 10).astype(np.float32)
    with tempfile.TemporaryDirectory() as d:
        got, _ = _roundtrip(Path(d), data, chunk_rows, "int8-blockq")
    assert np.abs(got.astype(np.float64) - data).max() <= Int8BlockQCodec.tolerance(data)


@settings(max_examples=30, deadline=None)
@given(
    n_elems=st.integers(min_value=0, max_value=4096),
    dtype=st.sampled_from(["<f4", "<f8", "<i8", "<u2", "<u1"]),
    seed=st.integers(min_value=0, max_value=2**16),
)
def test_byte_shuffle_is_a_pure_permutation(n_elems, dtype, seed):
    """shuffle∘unshuffle == identity for any element count × itemsize, and
    the shuffled buffer is byte-for-byte a permutation of the input."""
    from repro.core.codecs import byte_shuffle, byte_unshuffle

    rng = np.random.default_rng(seed)
    dt = np.dtype(dtype)
    raw = rng.integers(0, 256, n_elems * dt.itemsize, dtype=np.uint8).tobytes()
    shuf = byte_shuffle(raw, dt.itemsize)
    assert shuf.nbytes == len(raw)
    np.testing.assert_array_equal(np.sort(shuf), np.sort(np.frombuffer(raw, np.uint8)))
    np.testing.assert_array_equal(byte_unshuffle(shuf.tobytes(), dt.itemsize),
                                  np.frombuffer(raw, np.uint8))


@settings(max_examples=25, deadline=None)
@given(
    rows=st.integers(min_value=1, max_value=70),
    cols=st.integers(min_value=1, max_value=9),
    chunk_rows=st.integers(min_value=1, max_value=80),
    dtype=st.sampled_from(["<f4", "<f8"]),
    seed=st.integers(min_value=0, max_value=2**16),
)
def test_shuffle_zlib_roundtrip_bitexact(rows, cols, chunk_rows, dtype, seed):
    """The shuffle pre-filter stays bit-exact across shape × dtype × chunk
    size, including ragged final chunks and chunk_rows > rows — and the
    written chunks survive the byte-balanced file-domain split (the
    straddling-boundary case is exercised separately below)."""
    rng = np.random.default_rng(seed)
    dt = np.dtype(dtype)
    data = ((rng.integers(0, 256, (rows, cols)) / 256) * 8 - 4).astype(dt)
    with tempfile.TemporaryDirectory() as d:
        got, meta = _roundtrip(Path(d), data, chunk_rows, "shuffle+zlib")
    np.testing.assert_array_equal(got, data)
    assert len(meta.chunks) == -(-rows // min(chunk_rows, 80))


def test_shuffle_zlib_chunks_straddle_file_domain_boundaries(tmp_path):
    """shuffle+zlib chunks through the overlapped pipeline: wildly unequal
    post-filter sizes land across byte-balanced domain boundaries and still
    round-trip bit-exact under verify=True."""
    from repro.core.codecs import CODEC_SHUFFLE_ZLIB

    rng = np.random.default_rng(12)
    parts = []
    for i in range(16):  # alternate smooth (compressible) and noisy chunks
        if i % 2:
            parts.append(np.full((64, 16), float(i), np.float32))
        else:
            parts.append(rng.standard_normal((64, 16)).astype(np.float32))
    data = np.concatenate(parts)
    with TH5File.create(str(tmp_path / "svl.th5")) as f:
        meta = f.create_chunked_dataset("/d", data.shape, "<f4", 64, "shuffle+zlib")
        with ChunkPipeline(f, AggregationConfig(n_aggregators=4)) as pipe:
            fs = pipe.write(meta, data)
        f.commit()
        assert fs.n_chunks == 16
        assert len({c.nbytes for c in meta.chunks}) > 1  # genuinely variable-length
        assert CODEC_SHUFFLE_ZLIB in {c.codec_id for c in meta.chunks}
        np.testing.assert_array_equal(f.read("/d", verify=True), data)


def test_shuffle_uplift_over_plain_zlib_on_f32():
    """Ratio regression: the byte-shuffle pre-filter must compress f32 field
    data at least as well as plain zlib (in practice ~30% better — the
    committed BENCH_io.json `read` section tracks the exact uplift)."""
    rng = np.random.default_rng(7)
    field = (rng.integers(0, 1024, (2048, 64)) / 1024.0).astype(np.float32)
    plain = len(get_codec("zlib").encode(field))
    shuffled = len(get_codec("shuffle+zlib").encode(field))
    assert shuffled <= plain
    assert field.nbytes / shuffled > 1.88  # beats the committed plain-zlib ratio


def test_1d_and_ragged_final_chunk_roundtrip(tmp_path):
    data = np.arange(101, dtype=np.int64)
    got, meta = _roundtrip(tmp_path, data, chunk_rows=16, codec="zlib")
    np.testing.assert_array_equal(got, data)
    assert len(meta.chunks) == 7  # 6 full + 1 ragged
    assert meta.chunks[-1].raw_nbytes == 5 * 8


def test_incompressible_chunks_fall_back_to_none(tmp_path):
    rng = np.random.default_rng(3)
    data = rng.integers(0, 2**63, (64, 4), dtype=np.int64)  # high-entropy
    got, meta = _roundtrip(tmp_path, data, chunk_rows=16, codec="zlib")
    np.testing.assert_array_equal(got, data)
    assert all(c.codec_id == CODEC_NONE for c in meta.chunks)
    assert meta.stored_nbytes == meta.nbytes  # no space overhead

    mixed = np.zeros((64, 4), np.int64)  # all-zero: maximally compressible
    got2, meta2 = _roundtrip(tmp_path, mixed, 16, "zlib", name="rt2.th5")
    np.testing.assert_array_equal(got2, mixed)
    assert all(c.codec_id == CODEC_ZLIB for c in meta2.chunks)
    assert meta2.stored_nbytes < meta2.nbytes


def test_encode_chunk_none_is_zero_copy_view():
    arr = np.arange(32, dtype=np.float32)
    COPY_COUNTER.reset()
    payload, raw_n, raw_crc, stored_crc, cid = encode_chunk(get_codec("none"), arr)
    assert COPY_COUNTER.snapshot() == (0, 0)
    assert isinstance(payload, memoryview) and raw_n == arr.nbytes
    assert raw_crc == stored_crc and cid == CODEC_NONE


# -- partial reads + chunk cache -----------------------------------------------


def test_partial_reads_decode_only_intersecting_chunks(tmp_path):
    rng = np.random.default_rng(4)
    data = (rng.integers(0, 64, (96, 5)) / 64).astype(np.float32)
    path = str(tmp_path / "p.th5")
    with TH5File.create(path) as f:
        meta = f.create_chunked_dataset("/d", data.shape, "<f4", 16, "zlib")
        f.write_chunked(meta, data)
        f.commit()
    with TH5File.open(path) as f:
        # rows 30..50 straddle chunks 1, 2, 3 → exactly 3 decodes
        np.testing.assert_array_equal(f.read_rows("/d", 30, 20), data[30:50])
        assert f.chunk_cache.stats()["misses"] == 3
        # repeat: all hits, no new decodes
        np.testing.assert_array_equal(f.read_rows("/d", 30, 20), data[30:50])
        s = f.chunk_cache.stats()
        assert s["misses"] == 3 and s["hits"] == 3
        # scatter gather across chunks
        idx = [0, 95, 17, 18, 2]
        np.testing.assert_array_equal(f.read_row_indices("/d", idx), data[idx])
        out = np.empty((4, 5), np.float32)
        f.read_rows_into("/d", 14, 4, out)  # straddles chunks 0|1
        np.testing.assert_array_equal(out, data[14:18])
        with pytest.raises(TH5Error):
            f.read_rows_into("/d", 94, 4, np.empty((4, 5), np.float32))


def test_chunk_cache_lru_eviction(tmp_path):
    data = np.zeros((64, 8), np.float32)
    path = str(tmp_path / "lru.th5")
    with TH5File.create(path) as f:
        meta = f.create_chunked_dataset("/d", data.shape, "<f4", 8, "zlib")
        f.write_chunked(meta, data)
        f.commit()
    with TH5File.open(path) as f:
        f.chunk_cache.capacity_bytes = 3 * 8 * 8 * 4  # room for 3 decoded chunks
        f.read("/d")  # touches all 8 chunks
        s = f.chunk_cache.stats()
        assert s["entries"] == 3 and s["evictions"] == 5
        assert s["bytes"] <= f.chunk_cache.capacity_bytes


def test_verified_read_never_served_from_unverified_cache(tmp_path):
    """An unverified read (LOD playback) caches its decode; a later
    verify=True read of corrupted bytes must still raise, not return the
    poisoned cache entry."""
    data = np.arange(64, dtype=np.float32).reshape(16, 4)
    path = str(tmp_path / "corrupt.th5")
    with TH5File.create(path) as f:
        meta = f.create_chunked_dataset("/d", data.shape, "<f4", 8, "none")
        f.write_chunked(meta, data)
        rec = meta.chunks[0]
        f.commit()
    with open(path, "r+b") as fh:  # flip bytes inside chunk 0's extent
        fh.seek(rec.offset)
        fh.write(b"\xff" * 8)
    with TH5File.open(path) as f:
        f.read_row_indices("/d", [0, 1])  # unverified: populates the cache
        assert f.chunk_cache.stats()["entries"] >= 1
        with pytest.raises(Exception, match="CRC"):
            f.read("/d", verify=True)


def test_incomplete_chunked_write_raises_on_read(tmp_path):
    path = str(tmp_path / "inc.th5")
    with TH5File.create(path) as f:
        meta = f.create_chunked_dataset("/d", (32, 4), "<f4", 8, "zlib")
        # write only the first chunk's worth, then try to read everything
        payload, raw_n, rc, sc, cid = encode_chunk(get_codec("zlib"), np.zeros((8, 4), np.float32))
        f.append_chunk(meta, payload, raw_nbytes=raw_n, raw_crc32=rc, stored_crc32=sc, codec_id=cid)
        with pytest.raises(Exception, match="missing"):
            f.read("/d")
        np.testing.assert_array_equal(f.read_rows("/d", 0, 8), np.zeros((8, 4), np.float32))


def test_chunked_rejects_slab_writes_and_seal(tmp_path):
    with TH5File.create(str(tmp_path / "g.th5")) as f:
        meta = f.create_chunked_dataset("/d", (8, 4), "<f4", 4, "zlib")
        with pytest.raises(TH5Error):
            f.write_slab(meta, 0, np.zeros((8, 4), np.float32))
        f.write_chunked(meta, np.zeros((8, 4), np.float32))
        with pytest.raises(TH5Error):
            f.seal_checksum("/d")
        with pytest.raises(TH5Error):
            f.write_chunked(meta, np.zeros((8, 4), np.float32))  # already written


# -- overlapped pipeline + file domains ----------------------------------------


def test_chunk_pipeline_overlaps_encode_with_writes(tmp_path):
    rng = np.random.default_rng(5)
    data = (rng.integers(0, 128, (2048, 64)) / 128).astype(np.float32)
    with TH5File.create(str(tmp_path / "ov.th5")) as f:
        meta = f.create_chunked_dataset("/d", data.shape, "<f4", 128, "zlib")
        with ChunkPipeline(f, AggregationConfig(n_aggregators=4)) as pipe:
            fs = pipe.write(meta, data)
        f.commit()
        assert fs.n_chunks == 16
        assert fs.raw_bytes == data.nbytes
        assert 0 < fs.stored_bytes < data.nbytes
        assert fs.ratio > 1.5
        assert fs.encode_s > 0 and fs.write_s > 0
        np.testing.assert_array_equal(f.read("/d", verify=True), data)


def test_chunk_pipeline_none_codec_is_zero_copy(tmp_path):
    """The PR-1 invariant survives chunking: raw-chunk writes via the
    pipeline's file-domain route never copy payload bytes."""
    rng = np.random.default_rng(6)
    data = rng.integers(0, 255, (1024, 32), dtype=np.uint8)
    with TH5File.create(str(tmp_path / "zc.th5")) as f:
        meta = f.create_chunked_dataset("/d", data.shape, "<u1", 100, "none")
        COPY_COUNTER.reset()
        with ChunkPipeline(f, AggregationConfig(n_aggregators=4)) as pipe:
            fs = pipe.write(meta, data)
        assert COPY_COUNTER.snapshot() == (0, 0)
        assert fs.ratio == 1.0 and fs.stored_bytes == data.nbytes
        f.commit()
        np.testing.assert_array_equal(f.read("/d", verify=True), data)


def test_variable_length_chunks_straddle_file_domain_boundaries(tmp_path):
    """Post-filter chunks have wildly unequal sizes; the byte-balanced
    domain split lands mid-sequence (chunk boundaries ≠ domain boundaries)
    and the write must still round-trip."""
    rng = np.random.default_rng(7)
    # alternate incompressible and all-zero chunks → stored sizes ~4096 / ~30
    parts = []
    for i in range(16):
        if i % 2:
            parts.append(np.zeros((64, 16), np.uint8))
        else:
            parts.append(rng.integers(0, 255, (64, 16), dtype=np.uint8))
    data = np.concatenate(parts)
    with TH5File.create(str(tmp_path / "vl.th5")) as f:
        meta = f.create_chunked_dataset("/d", data.shape, "<u1", 64, "zlib")
        with ChunkPipeline(f, AggregationConfig(n_aggregators=4)) as pipe:
            fs = pipe.write(meta, data)
        f.commit()
        sizes = {c.nbytes for c in meta.chunks}
        assert len(sizes) > 1  # genuinely variable-length
        assert {c.codec_id for c in meta.chunks} == {CODEC_NONE, CODEC_ZLIB}
        np.testing.assert_array_equal(f.read("/d", verify=True), data)
    # the bucketing itself: byte-balanced domains split at request boundaries
    reqs = [WriteRequest(c.offset, bytes(c.nbytes)) for c in meta.chunks]
    domains = assign_file_domains(reqs, 4)
    assert 1 < len(domains) <= 4
    assert sum(len(d) for d in domains) == len(reqs)
    flat = [r.offset for d in domains for r in d]
    assert flat == sorted(flat)
    assert fs.n_chunks == 16


def test_variable_length_requests_through_collective_writer(tmp_path):
    """write_collective with file domains handles variable-length payloads
    (the post-filter shape) — bytes land at their exact offsets."""
    rng = np.random.default_rng(8)
    sizes = [1, 4096, 7, 2000, 64, 512, 3, 9000]
    offs = np.cumsum([0] + sizes[:-1])
    payloads = [rng.integers(0, 255, s, dtype=np.uint8) for s in sizes]
    path = str(tmp_path / "vr.th5")
    with TH5File.create(path) as f:
        meta = f.create_dataset("/d", (sum(sizes),), "<u1")
        reqs = [[WriteRequest(meta.offset + int(o), p)] for o, p in zip(offs, payloads)]
        with CollectiveWriter(f.fd, AggregationConfig(n_aggregators=3)) as w:
            stats = w.write_collective(reqs)
        assert stats.bytes_written == sum(sizes)
        f.commit()
    with TH5File.open(path) as f:
        np.testing.assert_array_equal(f.read("/d"), np.concatenate(payloads))


# -- the read-side decode pipeline ---------------------------------------------


def test_decode_pipeline_overlaps_fetch_with_inflate(tmp_path):
    """Cold multi-chunk read: stored bytes of chunk k+1 are preadv-fetched
    while chunk k inflates in the decode pool — both halves show up in the
    per-read FilterStats and the result is bit-exact."""
    rng = np.random.default_rng(13)
    data = (rng.integers(0, 128, (2048, 64)) / 128).astype(np.float32)
    path = str(tmp_path / "dp.th5")
    with TH5File.create(path) as f:
        meta = f.create_chunked_dataset("/d", data.shape, "<f4", 128, "zlib")
        with ChunkPipeline(f, AggregationConfig(n_aggregators=4)) as pipe:
            pipe.write(meta, data)
        f.commit()
    with TH5File.open(path) as f:  # fresh open: cold chunk cache
        f.set_decode_config(AggregationConfig(n_aggregators=4))  # explicit pool width
        got = f.read("/d")
        np.testing.assert_array_equal(got, data)
        rs = f.last_read_stats
        assert rs is not None and rs.n_chunks == 16
        assert rs.raw_bytes == data.nbytes and 0 < rs.stored_bytes < data.nbytes
        assert rs.decode_s > 0 and rs.fetch_s > 0 and rs.wall_s > 0
        # warm read: all cache hits → no decode work in the new stats
        f.read("/d")
        assert f.last_read_stats.n_chunks == 0
        # cumulative stats accumulated both reads
        assert f.read_stats.n_chunks == 16


def test_decode_pipeline_none_codec_read_is_zero_copy(tmp_path):
    """The PR-1/PR-2 invariant holds on the read side: raw-chunk gathers
    scatter straight into the caller's buffer — COPY_COUNTER delta 0 and no
    decode-pool work."""
    rng = np.random.default_rng(14)
    data = rng.integers(0, 255, (1024, 32), dtype=np.uint8)
    path = str(tmp_path / "zr.th5")
    with TH5File.create(path) as f:
        meta = f.create_chunked_dataset("/d", data.shape, "<u1", 100, "none")
        with ChunkPipeline(f, AggregationConfig(n_aggregators=4)) as pipe:
            pipe.write(meta, data)
        f.commit()
    with TH5File.open(path) as f:
        COPY_COUNTER.reset()
        out = np.empty_like(data)
        f.read_rows_into("/d", 0, 1024, out)
        assert COPY_COUNTER.snapshot() == (0, 0)
        np.testing.assert_array_equal(out, data)
        assert f.last_read_stats.n_chunks == 0  # fast path bypassed the pool
        assert f.chunk_cache.stats()["entries"] == 0  # and never staged a decode


def test_decode_pipeline_mixed_codec_gather(tmp_path):
    """A gather spanning none- and zlib-coded chunks routes each through its
    own path (direct scatter vs pipeline) within one read."""
    rng = np.random.default_rng(15)
    # alternate incompressible (falls back to none) and all-zero chunks
    parts = [
        rng.integers(0, 2**63, (32, 4), dtype=np.int64) if i % 2 else np.zeros((32, 4), np.int64)
        for i in range(8)
    ]
    data = np.concatenate(parts)
    path = str(tmp_path / "mx.th5")
    with TH5File.create(path) as f:
        meta = f.create_chunked_dataset("/d", data.shape, "<i8", 32, "zlib")
        f.write_chunked(meta, data)
        f.commit()
        assert {c.codec_id for c in meta.chunks} == {CODEC_NONE, CODEC_ZLIB}
    with TH5File.open(path) as f:
        np.testing.assert_array_equal(f.read("/d"), data)
        assert f.last_read_stats.n_chunks == 4  # only the zlib chunks decoded
        np.testing.assert_array_equal(f.read_rows("/d", 16, 64), data[16:80])


# -- sliding-window / LOD over compressed files --------------------------------


def test_lod_windows_over_compressed_dataset(tmp_path):
    from repro.core.sliding_window import iter_lod_windows, read_lod

    rng = np.random.default_rng(9)
    data = (rng.integers(0, 32, (256, 6)) / 32).astype(np.float32)
    path = str(tmp_path / "lod.th5")
    with TH5File.create(path) as f:
        meta = f.create_chunked_dataset("/d", data.shape, "<f4", 32, "zlib")
        f.write_chunked(meta, data)
        f.commit()
    with TH5File.open(path) as f:
        np.testing.assert_array_equal(read_lod(f, "/d", stride=4), data[::4])
        got = list(iter_lod_windows(f, "/d", [(0, 64), (32, 96), (200, 256)], max_rows=16))
        assert len(got) == 3 and all(len(g) <= 16 for g in got)
        # overlapping windows re-decode nothing: every chunk decoded once
        assert f.chunk_cache.stats()["misses"] <= 8


# -- checkpoint codec policy ---------------------------------------------------


def _mixed_state(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "fields": {"u": (rng.integers(0, 256, (256, 128)) / 256).astype(np.float32)},
        "opt": {
            "m": rng.random((256, 128)).astype(np.float32),
            "step": np.int64(11),  # tiny int leaf: must stay contiguous
        },
    }


def test_codec_policy_resolution():
    pol = CodecPolicy(default="zlib", rules=(("fields.*", "int8-blockq"),), min_chunk_bytes=64)
    assert pol.resolve("fields.u", np.zeros((64, 4), np.float32)) == "int8-blockq"
    # dtype heuristic: zlib on an f32/f64 leaf upgrades to the shuffle filter
    assert pol.resolve("opt.m", np.zeros((64, 4), np.float32)) == "shuffle+zlib"
    assert pol.resolve("opt.v", np.zeros((64, 4), np.float64)) == "shuffle+zlib"
    # ... but integer leaves keep plain zlib (shuffle buys little there)
    assert pol.resolve("opt.idx", np.zeros((64, 4), np.int32)) == "zlib"
    # lossy on an int leaf falls back to lossless (and stays unshuffled)
    assert pol.resolve("fields.mask", np.zeros((64, 4), np.int32)) == "zlib"
    # opting out of the heuristic restores plain zlib everywhere
    pol_plain = CodecPolicy(default="zlib", min_chunk_bytes=64, auto_shuffle=False)
    assert pol_plain.resolve("opt.m", np.zeros((64, 4), np.float32)) == "zlib"
    # the compression level rides through the upgrade
    pol6 = CodecPolicy(default="zlib:6", min_chunk_bytes=64)
    assert pol6.resolve("opt.m", np.zeros((64, 4), np.float32)) == "shuffle+zlib:6"
    # tiny / 0-d leaves stay on the contiguous zero-copy path
    assert pol.resolve("opt.step", np.int64(3)) == "none"
    assert pol.resolve("opt.m", np.zeros(4, np.float32)) == "none"
    assert CodecPolicy().resolve("anything", np.zeros((999, 9), np.float32)) == "none"
    assert pol.chunk_rows_for(10_000, 1 << 18) == 4  # ~1MiB target
    assert CodecPolicy(chunk_rows=64).chunk_rows_for(16, 8) == 16


def test_checkpoint_codec_policy_roundtrip(tmp_path):
    state = _mixed_state()
    pol = CodecPolicy(default="zlib", rules=(("fields.*", "int8-blockq"),), min_chunk_bytes=1024)
    with CheckpointManager(str(tmp_path / "c.th5")) as mgr:
        res = mgr.save(0, state, n_ranks=4, codec_policy=pol)
        assert res.filter_stats.n_chunks >= 2
        assert res.compression_ratio > 1.0
        assert mgr.latest_valid() == 0  # per-chunk CRC verification passes
        _, got = mgr.restore(0, verify=True)
        np.testing.assert_array_equal(got["opt"]["m"], state["opt"]["m"])  # lossless
        assert got["opt"]["step"] == state["opt"]["step"]
        u, u0 = got["fields"]["u"], state["fields"]["u"]
        assert np.abs(u.astype(np.float64) - u0).max() <= Int8BlockQCodec.tolerance(u0)
        # elastic restore reads a shard of a chunked leaf
        shard = mgr.restore_leaf_shard(0, "opt.m", rank=1, n_ranks=4)
        np.testing.assert_array_equal(shard, state["opt"]["m"][64:128])

    with CheckpointManager(str(tmp_path / "c.th5"), create=False) as mgr2:
        assert mgr2.latest_valid() == 0  # survives reopen (index round-trip)


def test_checkpoint_overwrite_invalidates_chunk_cache(tmp_path):
    with CheckpointManager(str(tmp_path / "o.th5")) as mgr:
        pol = CodecPolicy(default="zlib", min_chunk_bytes=64)
        a = {"w": np.full((64, 16), 1.0, np.float32)}
        b = {"w": np.full((64, 16), 2.0, np.float32)}
        mgr.save(0, a, codec_policy=pol)
        np.testing.assert_array_equal(mgr.restore(0)[1]["w"], a["w"])  # populates cache
        mgr.save(0, b, codec_policy=pol, overwrite=True)
        np.testing.assert_array_equal(mgr.restore(0)[1]["w"], b["w"])  # not stale


def test_save_without_policy_unchanged_zero_copy(tmp_path):
    """Default save (no codec policy) must keep the contiguous path and its
    stats shape — the PR-1 pipeline untouched."""
    with CheckpointManager(str(tmp_path / "n.th5")) as mgr:
        res = mgr.save(0, _mixed_state(), n_ranks=2)
        assert res.filter_stats.n_chunks == 0
        assert res.compression_ratio == 1.0
        for name in mgr.file.datasets():
            assert not mgr.file.meta(name).is_chunked

"""The observability plane (``repro.obs``) and its threading through the
stack.

The contract under test: spans survive explicit pool handoff in both
filter pipelines (recorded from worker threads under the submitting
trace), one remote request stitches into ONE trace shared by client,
broker and decode spans, the disabled tracer's hot path allocates nothing
beyond the no-op guard, the unified registry sees the pre-existing
counters without breaking their local-instance semantics, the Chrome
export is loadable trace-event JSON, and the broker's slow-request log
dumps a span tree over the threshold.  Plus the LatencyRecorder
regression: percentile queries are read-only and one snapshot sorts once.
"""

import gc
import json
import logging
import os
import sys
import threading

import numpy as np
import pytest

from repro.core.aggregation import (
    AggregationConfig,
    ChunkPipeline,
    CopyCounter,
    COPY_COUNTER,
)
from repro.core.container import ReadCounter, READ_COUNTER, ChunkCache, TH5File
from repro.obs import (
    NOOP_SPAN,
    REGISTRY,
    TRACER,
    MetricsRegistry,
    Tracer,
    chrome_trace_events,
    format_span_tree,
    prometheus_text,
    write_chrome_trace,
)
from repro.obs.metrics import (
    M_CACHE_HITS,
    M_CACHE_MISSES,
    M_SLOW_REQUESTS,
)
from repro.obs.trace import (
    SPAN_BROKER_REQUEST,
    SPAN_CLIENT_REQUEST,
    SPAN_DECODE_GATHER,
    SPAN_DECODE_INFLATE,
    SPAN_ENCODE_CHUNK,
    SPAN_EXECUTE,
    SPAN_QUEUE_WAIT,
    SPAN_SCHEDULE,
    SPAN_WIRE_SEND,
    SpanContext,
)
from repro.service import (
    DataService,
    RemoteDataService,
    ServiceConfig,
    ServiceServer,
    WindowQuery,
)
from repro.service import wire
from repro.service.stats import LatencyRecorder

ROWS, COLS, CHUNK_ROWS = 1024, 32, 128
DS = "/simulation/step_00000000/state/fields/u"


@pytest.fixture(autouse=True)
def _quiet_tracer():
    """Every test starts and ends with the process tracer disabled and
    empty (other suites must never see our spans)."""
    TRACER.configure(enabled=False, sample_every=1)
    TRACER.reset()
    yield
    TRACER.configure(enabled=False, sample_every=1)
    TRACER.reset()


@pytest.fixture()
def run_file(tmp_path):
    rng = np.random.default_rng(11)
    u = rng.standard_normal((ROWS, COLS)).astype(np.float32)
    path = str(tmp_path / "run.th5")
    with TH5File.create(path) as f:
        mu = f.create_chunked_dataset(DS, u.shape, "<f4", CHUNK_ROWS, "shuffle+zlib")
        with ChunkPipeline(f, AggregationConfig(n_aggregators=2)) as pipe:
            pipe.write(mu, u)
        f.commit()
    return path, u


# -- tracer core ---------------------------------------------------------------


def test_span_lifecycle_and_tree():
    tr = Tracer(enabled=True)
    root = tr.start_trace("client.request")
    assert root.trace_id and root.parent_id == 0
    with tr.use(root):
        with tr.span("decode.gather") as g:
            g.tag("chunks", 2)
            tr.record("decode.fetch", g, g.t0, g.t0 + 0.001, {"nbytes": 64})
    root.end()
    spans = tr.snapshot()
    assert [s.name for s in spans] == ["decode.fetch", "decode.gather", "client.request"]
    assert len({s.trace_id for s in spans}) == 1
    tree = format_span_tree(spans)
    # child indentation: gather under the root, fetch under gather
    assert tree.index("client.request") < tree.index("decode.gather") < tree.index("decode.fetch")
    assert "chunks=2" in tree and "nbytes=64" in tree


def test_span_end_is_idempotent():
    tr = Tracer(enabled=True)
    s = tr.start_trace("x")
    s.end()
    t1 = s.t1
    s.end()
    assert s.t1 == t1 and len(tr) == 1


def test_child_without_sampled_parent_is_noop():
    tr = Tracer(enabled=True)
    # no ambient context, no explicit parent → never a stray root
    assert tr.span("decode.gather") is NOOP_SPAN
    # a NOOP parent propagates NOOP-ness
    assert tr.span("decode.fetch", NOOP_SPAN) is NOOP_SPAN


def test_deterministic_sampling_counter_not_rng():
    tr = Tracer(enabled=True, sample_every=3)
    kept = [bool(tr.start_trace("r").trace_id) for _ in range(9)]
    assert kept == [True, False, False] * 3
    tr2 = Tracer(enabled=True, sample_every=3)
    assert [bool(tr2.start_trace("r").trace_id) for _ in range(9)] == kept


def test_ring_is_bounded():
    tr = Tracer(enabled=True, capacity=8)
    for _ in range(50):
        tr.start_trace("r").end()
    assert len(tr) == 8
    assert len(tr.drain()) == 8 and len(tr) == 0


def test_explicit_context_crosses_threads():
    tr = Tracer(enabled=True)
    root = tr.start_trace("client.request")
    ctx = root.context
    main = threading.get_ident()

    def worker():
        tr.record("decode.inflate", ctx, 1.0, 2.0)

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    root.end()
    inflate = [s for s in tr.snapshot() if s.name == "decode.inflate"][0]
    assert inflate.trace_id == root.trace_id
    assert inflate.parent_id == root.span_id
    assert inflate.thread != main  # recorded on the other thread


def test_disabled_tracer_identity_and_zero_allocation():
    """The no-op path: same singleton every call, and a span/tag/end cycle
    on the hot path allocates no objects beyond the guard."""
    tr = Tracer()  # disabled
    assert tr.span("x") is NOOP_SPAN
    assert tr.start_trace("x") is NOOP_SPAN
    assert tr.current_context() is None
    loops = tuple(range(1000))  # pre-build the iterable outside the window
    # warmup (interns, thread-local init, method caches)
    for _ in loops:
        s = tr.span("x")
        s.tag("k", 1)
        s.end()
    gc.disable()
    try:
        base = sys.getallocatedblocks()
        for _ in loops:
            s = tr.span("x")
            s.tag("k", 1)
            s.end()
        delta = sys.getallocatedblocks() - base
    finally:
        gc.enable()
    # a handful of loop-constant blocks (iterator, frame caches) are fine;
    # anything per-call would show up 1000× here
    assert delta < 20, f"disabled-tracer hot path allocated {delta} blocks over 1000 spans"


def test_phase_nests_tags_and_records_only_when_enabled():
    tr = Tracer(enabled=True)
    with tr.phase("ckpt.save", step=7) as save:
        with tr.phase("ckpt.write", bytes=64):
            pass
        with tr.phase("ckpt.commit") as commit:
            commit.tag("generation", 3)
        save.tag("bytes", 64)
    spans = {s.name: s for s in tr.snapshot()}
    assert list(spans) == ["ckpt.write", "ckpt.commit", "ckpt.save"]
    root = spans["ckpt.save"]
    assert root.parent_id == 0 and root.tags == {"step": 7, "bytes": 64}
    assert spans["ckpt.write"].tags == {"bytes": 64}
    assert spans["ckpt.commit"].tags == {"generation": 3}
    assert {spans["ckpt.write"].parent_id, spans["ckpt.commit"].parent_id} == {root.span_id}
    assert len({s.trace_id for s in spans.values()}) == 1
    assert tr.current_context() is None  # the ambient context is restored

    # an ambient context (a traced request) is the outermost phase's parent
    req = tr.start_trace("broker.request")
    with tr.use(req):
        with tr.phase("th5.read", bytes=8):
            pass
    req.end()
    read = [s for s in tr.snapshot() if s.name == "th5.read"][0]
    assert read.trace_id == req.trace_id and read.parent_id == req.span_id

    # an unsampled root: its nested phases start no roots of their own
    tr = Tracer(enabled=True, sample_every=2)
    tr.start_trace("taken").end()
    with tr.phase("sim.snapshot"):
        with tr.phase("sim.fetch"):
            pass
    assert [s.name for s in tr.snapshot()] == ["taken"]

    # disabled: no ring entry, tags still accepted
    tr = Tracer()
    with tr.phase("ckpt.save", step=1) as save:
        save.tag("bytes", 1)
        with tr.phase("ckpt.write"):
            pass
    assert len(tr) == 0 and tr.current_context() is None


def test_phase_imports_no_jax():
    """The container and the service use phases without importing JAX."""
    from tests._subproc import run_with_devices

    out = run_with_devices(
        "import sys\n"
        "from repro.core.container import TH5File\n"
        "from repro.obs.trace import TRACER\n"
        "from repro.service import DataService\n"
        "with TRACER.phase('th5.read', bytes=1) as p:\n"
        "    p.tag('bytes', 2)\n"
        "print('jax' in sys.modules)\n",
        1,
    )
    assert out.strip() == "False"


# -- metrics registry ----------------------------------------------------------


def test_registry_instruments_and_collect():
    reg = MetricsRegistry()
    reg.counter("a.hits").inc(3)
    reg.gauge("a.depth").set(7)
    h = reg.histogram("a.lat")
    h.observe(0.5)
    h.observe(1.5)
    got = reg.collect()
    assert got["a.hits"] == 3 and got["a.depth"] == 7
    assert got["a.lat.count"] == 2 and got["a.lat.sum"] == 2.0
    assert got["a.lat.min"] == 0.5 and got["a.lat.max"] == 1.5
    assert h.mean == 1.0


def test_registry_get_or_create_and_kind_conflict():
    reg = MetricsRegistry()
    assert reg.counter("x") is reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")
    with pytest.raises(ValueError):
        reg.counter("x").inc(-1)


def test_collectors_sum_and_unregister():
    reg = MetricsRegistry()
    reg.counter("n").inc(1)
    fn = lambda: {"n": 2.0, "other": 5.0}  # noqa: E731
    reg.register_collector(fn)
    got = reg.collect()
    assert got["n"] == 3.0 and got["other"] == 5.0
    reg.unregister_collector(fn)
    assert reg.collect()["n"] == 1.0


def test_copy_and_read_counter_local_instances_stay_isolated():
    """The write paths build throwaway CopyCounter()s for per-call deltas;
    their adds and resets must not leak into the registered process
    totals (and vice versa)."""
    g0 = COPY_COUNTER.snapshot()
    local = CopyCounter()
    local.add(100)
    local.reset()
    local.add(40)
    assert local.snapshot() == (1, 40)
    assert COPY_COUNTER.snapshot() == g0
    r0 = READ_COUNTER.snapshot()
    lr = ReadCounter()
    lr.add(64, 2)
    assert lr.snapshot() == (2, 64)
    assert READ_COUNTER.snapshot() == r0


def test_chunk_cache_mirrors_into_registry():
    before = REGISTRY.collect()
    cache = ChunkCache(capacity_bytes=1 << 20)
    arr = np.zeros(16, dtype="<f4")
    assert cache.get(("/d", 0)) is None
    cache.put(("/d", 0), arr)
    assert cache.get(("/d", 0)) is not None
    after = REGISTRY.collect()
    assert after[M_CACHE_HITS] - before.get(M_CACHE_HITS, 0) == 1
    assert after[M_CACHE_MISSES] - before.get(M_CACHE_MISSES, 0) == 1
    # the instance's own stats stay local truth
    assert cache.stats()["hits"] == 1 and cache.stats()["misses"] == 1


def test_prometheus_text_format():
    reg = MetricsRegistry()
    reg.counter("cache.hits").inc(5)
    reg.gauge("queue.depth").set(1.25)
    text = prometheus_text(registry=reg)
    assert "# TYPE cache_hits gauge\ncache_hits 5" in text
    assert "queue_depth 1.25" in text
    assert text.endswith("\n")


# -- exporters -----------------------------------------------------------------


def test_chrome_trace_events_and_file(tmp_path):
    tr = Tracer(enabled=True)
    root = tr.start_trace("client.request")
    with tr.use(root):
        tr.span("decode.gather").tag("n", 1).end()
    root.end()
    events = chrome_trace_events(tr.snapshot(), pid=1234)
    assert all(e["ph"] == "X" and e["pid"] == 1234 for e in events)
    gather = [e for e in events if e["name"] == "decode.gather"][0]
    root_ev = [e for e in events if e["name"] == "client.request"][0]
    assert gather["args"]["trace_id"] == root_ev["args"]["trace_id"]
    assert gather["ts"] >= root_ev["ts"]  # µs, same clock domain
    path = str(tmp_path / "trace.json")
    n = write_chrome_trace(path, tracer=tr)
    assert n == 2
    with open(path, encoding="utf-8") as fh:
        doc = json.load(fh)
    assert doc["displayTimeUnit"] == "ms"
    assert {e["name"] for e in doc["traceEvents"]} == {"client.request", "decode.gather"}


def test_span_tree_renders_orphans_as_roots():
    """A broker-side dump happens while the client's root span is still
    open on the other side of the socket: spans whose parent is absent
    must render as roots, not vanish."""
    tr = Tracer(enabled=True)
    ctx = SpanContext(0xABC, 999)  # parent 999 will never be in the buffer
    tr.record("broker.execute", ctx, 1.0, 2.0)
    tree = format_span_tree(tr.snapshot())
    assert "broker.execute" in tree


# -- pipeline pool handoff -----------------------------------------------------


def test_encode_spans_survive_pool_handoff(tmp_path):
    rng = np.random.default_rng(5)
    u = rng.standard_normal((ROWS, COLS)).astype(np.float32)
    path = str(tmp_path / "w.th5")
    TRACER.configure(enabled=True)
    main = threading.get_ident()
    with TH5File.create(path) as f:
        mu = f.create_chunked_dataset(DS, u.shape, "<f4", CHUNK_ROWS, "shuffle+zlib")
        root = TRACER.start_trace("bench.write")
        with TRACER.use(root):
            with ChunkPipeline(f, AggregationConfig(n_aggregators=2)) as pipe:
                pipe.write(mu, u)
        root.end()
    enc = [s for s in TRACER.snapshot() if s.name == SPAN_ENCODE_CHUNK]
    assert len(enc) == ROWS // CHUNK_ROWS
    assert all(s.trace_id == root.trace_id for s in enc)
    assert all(s.parent_id == root.span_id for s in enc)
    # the encodes genuinely ran on codec pool workers, not the caller
    assert any(s.thread != main for s in enc)


def test_decode_spans_survive_pool_handoff(run_file):
    path, u = run_file
    TRACER.configure(enabled=True)
    main = threading.get_ident()
    with TH5File.open(path) as f:
        f.chunk_cache.clear()
        root = TRACER.start_trace("bench.read")
        with TRACER.use(root):
            back = f.read_rows(DS, 0, ROWS)
        root.end()
    np.testing.assert_array_equal(back, u)
    spans = TRACER.snapshot()
    gathers = [s for s in spans if s.name == SPAN_DECODE_GATHER]
    inflates = [s for s in spans if s.name == SPAN_DECODE_INFLATE]
    assert len(gathers) == 1 and gathers[0].trace_id == root.trace_id
    assert len(inflates) == ROWS // CHUNK_ROWS
    assert all(s.trace_id == root.trace_id for s in inflates)
    # inflate ran in the decode pool — recorded from non-caller threads
    assert any(s.thread != main for s in inflates)
    assert gathers[0].tags["cache_misses"] == ROWS // CHUNK_ROWS


def test_untraced_reads_emit_no_spans(run_file):
    path, u = run_file
    TRACER.configure(enabled=True)  # enabled, but no root installed
    with TH5File.open(path) as f:
        f.chunk_cache.clear()
        f.read_rows(DS, 0, ROWS)
    assert len(TRACER) == 0  # children never out-sample their (absent) root


# -- service stitching ---------------------------------------------------------


def test_in_process_submit_records_phase_spans(run_file):
    path, _ = run_file
    TRACER.configure(enabled=True)
    with DataService(path, ServiceConfig(n_workers=2)) as svc:
        resp = svc.submit("cli", WindowQuery(dataset=DS, rows=(1, 2, 3))).result()
        assert resp.value.shape == (3, COLS)
    names = {s.name for s in TRACER.snapshot()}
    assert {SPAN_BROKER_REQUEST, SPAN_QUEUE_WAIT, SPAN_SCHEDULE, SPAN_EXECUTE} <= names
    roots = [s for s in TRACER.snapshot() if s.name == SPAN_BROKER_REQUEST]
    assert len({s.trace_id for s in TRACER.snapshot()}) == 1
    exe = [s for s in TRACER.snapshot() if s.name == SPAN_EXECUTE][0]
    assert exe.parent_id == roots[0].span_id
    assert exe.tags["type"] == "WindowQuery"


def test_remote_request_is_one_stitched_trace(run_file, tmp_path):
    """THE acceptance criterion: client + broker + decode spans of one
    remote request share a single trace_id."""
    import tempfile

    path, u = run_file
    TRACER.configure(enabled=True)
    with tempfile.TemporaryDirectory(prefix="th5o", dir="/tmp") as d:
        with DataService(path, ServiceConfig(n_workers=2)) as svc:
            svc.file.chunk_cache.clear()
            with ServiceServer(svc, os.path.join(d, "s.sock")) as server:
                with RemoteDataService(server.address) as remote:
                    rows = tuple(range(0, 300))
                    resp = remote.request("viewer", WindowQuery(dataset=DS, rows=rows))
                    np.testing.assert_array_equal(resp.value, u[list(rows)])
    spans = TRACER.snapshot()
    assert len({s.trace_id for s in spans}) == 1
    names = {s.name for s in spans}
    assert {
        SPAN_CLIENT_REQUEST,
        SPAN_QUEUE_WAIT,
        SPAN_SCHEDULE,
        SPAN_EXECUTE,
        SPAN_WIRE_SEND,
        SPAN_DECODE_GATHER,
        SPAN_DECODE_INFLATE,
    } <= names
    client_root = [s for s in spans if s.name == SPAN_CLIENT_REQUEST][0]
    assert client_root.parent_id == 0 and client_root.tags["ok"] is True
    # broker phases parent directly under the client's root: stitched, not
    # two traces glued by timestamps
    qw = [s for s in spans if s.name == SPAN_QUEUE_WAIT][0]
    assert qw.parent_id == client_root.span_id


def test_remote_requests_untraced_when_disabled(run_file, tmp_path):
    import tempfile

    path, _ = run_file
    with tempfile.TemporaryDirectory(prefix="th5o", dir="/tmp") as d:
        with DataService(path, ServiceConfig(n_workers=2)) as svc:
            with ServiceServer(svc, os.path.join(d, "s.sock")) as server:
                with RemoteDataService(server.address) as remote:
                    remote.request("viewer", WindowQuery(dataset=DS, rows=(0, 1)))
    assert len(TRACER) == 0


def test_slow_request_log_dumps_span_tree(run_file, caplog):
    path, _ = run_file
    TRACER.configure(enabled=True)
    slow0 = REGISTRY.collect().get(M_SLOW_REQUESTS, 0.0)
    with caplog.at_level(logging.WARNING, logger="repro.service.slowlog"):
        with DataService(path, ServiceConfig(n_workers=2, slow_request_s=0.0)) as svc:
            svc.submit("cli", WindowQuery(dataset=DS, rows=(0, 1, 2))).result()
    assert any("slow request" in r.message for r in caplog.records)
    dump = "\n".join(r.getMessage() for r in caplog.records)
    assert SPAN_QUEUE_WAIT in dump and SPAN_EXECUTE in dump  # the span tree
    assert REGISTRY.collect()[M_SLOW_REQUESTS] > slow0


def test_slow_request_log_untraced_phase_summary(run_file, caplog):
    path, _ = run_file  # tracer stays disabled
    with caplog.at_level(logging.WARNING, logger="repro.service.slowlog"):
        with DataService(path, ServiceConfig(n_workers=2, slow_request_s=0.0)) as svc:
            svc.submit("cli", WindowQuery(dataset=DS, rows=(0,))).result()
    msgs = [r.getMessage() for r in caplog.records if "slow request" in r.message]
    assert msgs and "queued=" in msgs[0] and "exec=" in msgs[0]


def test_broker_collector_reports_service_metrics(run_file):
    path, _ = run_file
    with DataService(path, ServiceConfig(n_workers=2)) as svc:
        svc.submit("cli", WindowQuery(dataset=DS, rows=(0, 1))).result()
        got = REGISTRY.collect()
        assert got["service.completed"] >= 1
        assert got["service.bytes_served"] >= 2 * COLS * 4
    # after close the collector is unregistered: no stale reads
    got2 = REGISTRY.collect()
    assert "service.inflight" not in got2 or got2["service.inflight"] == 0


# -- wire propagation helpers --------------------------------------------------


def test_wire_put_get_trace_roundtrip():
    meta = {"client": "c", "type": "WindowQuery"}
    wire.put_trace(meta, 0xDEAD, 7)
    ctx = wire.get_trace(json.loads(json.dumps(meta)))
    assert ctx == (0xDEAD, 7)


@pytest.mark.parametrize(
    "bad",
    [None, "nope", [1], [1, 2, 3], ["x", "y"], [0, 5], [-3, 5], {"a": 1}],
)
def test_wire_get_trace_rejects_malformed(bad):
    meta = {"client": "c"}
    if bad is not None:
        meta[wire.TRACE_KEY] = bad
    assert wire.get_trace(meta) is None


# -- LatencyRecorder regression (satellite 1) ----------------------------------


def test_percentile_queries_do_not_mutate_recorder_state():
    rec = LatencyRecorder(capacity=64)
    for v in (5.0, 1.0, 3.0, 2.0, 4.0):
        rec.add(v)
    raw_before = list(rec._samples)
    seen_before = rec.n
    for _ in range(3):
        rec.percentile(50)
        rec.percentiles(50, 90, 99)
    assert list(rec._samples) == raw_before  # insertion order intact
    assert rec.n == seen_before


def test_percentiles_single_sort_matches_individual_queries():
    rec = LatencyRecorder(capacity=128)
    rng = np.random.default_rng(3)
    for v in rng.random(100):
        rec.add(float(v))
    p50, p90, p99 = rec.percentiles(50, 90, 99)
    assert p50 == rec.percentile(50)
    assert p90 == rec.percentile(90)
    assert p99 == rec.percentile(99)
    assert p50 <= p90 <= p99
    # the cached sort is invalidated by the next add
    rec.add(0.0)
    assert rec.percentile(0) == 0.0


def test_service_stats_carry_p90(run_file):
    path, _ = run_file
    with DataService(path, ServiceConfig(n_workers=2)) as svc:
        for _ in range(8):
            svc.submit("cli", WindowQuery(dataset=DS, rows=(0,))).result()
        st = svc.stats()
    assert st.p50_ms <= st.p90_ms <= st.p99_ms
    assert st.p90_ms > 0
    cs = st.clients["cli"]
    assert cs.p50_ms <= cs.p90_ms <= cs.p99_ms

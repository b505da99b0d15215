"""Observability tentpole tests: Prometheus /metrics conformance,
histogram bucket/quantile math, one connected span tree across the batch
pipeline's thread hops, trace-id propagation through a 2-node remote
fan-out, and the satellite regressions (statsd ms units, O(1) finished
ring, profiler-tracer degradation)."""

import json
import re
import socket
import threading
import time
import urllib.request

import numpy as np
import pytest

from harness import run_cluster
from pilosa_tpu import pql
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.ops import SHARD_WIDTH
from pilosa_tpu.parallel import MeshEngine, make_mesh
from pilosa_tpu.util import tracing
from pilosa_tpu.util.stats import (
    DEFAULT_BUCKETS,
    Histogram,
    MetricsRegistry,
    REGISTRY,
)
from pilosa_tpu.util.statsd import StatsdClient
from pilosa_tpu.util.tracing import (
    NopTracer,
    Span,
    TraceContext,
    Tracer,
)


# -- histogram bucket/quantile math -----------------------------------------


def test_histogram_buckets_and_counts():
    h = Histogram()
    h.observe(0.0003)   # -> le=0.0005 bucket
    h.observe(0.003)    # -> le=0.005
    h.observe(0.003)
    h.observe(999.0)    # -> +Inf
    assert h.count == 4
    assert h.sum == pytest.approx(0.0003 + 0.003 + 0.003 + 999.0)
    cum = h.cumulative()
    assert cum[-1] == 4  # +Inf bucket holds the total
    # Cumulative counts are non-decreasing (the le contract).
    for a, b in zip(cum, cum[1:]):
        assert b >= a
    # An observation EXACTLY on a bound counts into that bound's bucket
    # (le is <=).
    h2 = Histogram()
    h2.observe(0.001)
    i = DEFAULT_BUCKETS.index(0.001)
    assert h2.cumulative()[i] == 1


def test_histogram_quantiles():
    h = Histogram()
    assert h.quantile(0.5) == 0.0  # empty
    for _ in range(100):
        h.observe(0.003)
    p50 = h.quantile(0.50)
    # All mass in the (0.0025, 0.005] bucket: the interpolated estimate
    # must land inside it.
    assert 0.0025 <= p50 <= 0.005
    assert h.quantile(0.50) <= h.quantile(0.95) <= h.quantile(0.99)
    # Spread: 90 fast + 10 slow -> p50 in the fast bucket, p99 in the
    # slow one.
    h3 = Histogram()
    for _ in range(90):
        h3.observe(0.0008)
    for _ in range(10):
        h3.observe(0.2)
    assert h3.quantile(0.50) <= 0.001
    assert h3.quantile(0.99) > 0.1


def test_registry_prometheus_text_conformance():
    reg = MetricsRegistry()
    reg.observe("test_latency_seconds", 0.004, op="Count")
    reg.observe("test_latency_seconds", 0.04, op="Count")
    reg.observe("test_latency_seconds", 0.004, op="TopN")
    reg.inc("test_requests_total", 3, code="200")
    reg.set_gauge("test_depth", 4)
    text = reg.prometheus_text()
    _assert_prometheus_conformant(text)
    # The series carry their labels and the histogram triplet.
    assert 'test_latency_seconds_bucket{op="Count",le="+Inf"} 2' in text
    assert 'test_latency_seconds_count{op="Count"} 2' in text
    assert 'test_latency_seconds_sum{op="Count"}' in text
    assert 'test_requests_total{code="200"} 3' in text
    assert "# TYPE test_latency_seconds histogram" in text
    assert "# TYPE test_requests_total counter" in text
    assert "# TYPE test_depth gauge" in text


_SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? -?[0-9.e+-]+(e[+-][0-9]+)?$"
)


def _assert_prometheus_conformant(text: str):
    """Text-format conformance: every line is a comment or a sample;
    histogram bucket counts are cumulative and le=+Inf equals _count."""
    buckets = {}  # (name, labels-sans-le) -> [(le, value), ...]
    counts = {}
    for line in text.strip().split("\n"):
        if line.startswith("# HELP ") or line.startswith("# TYPE "):
            continue
        assert _SAMPLE_RE.match(line), f"malformed sample line: {line!r}"
        name_labels, value = line.rsplit(" ", 1)
        m = re.match(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{(.*)\})?$", name_labels)
        name, labels = m.group(1), m.group(3) or ""
        if name.endswith("_bucket"):
            parts = [p for p in labels.split(",") if p]
            le = [p for p in parts if p.startswith("le=")]
            assert le, f"bucket sample without le: {line!r}"
            rest = ",".join(sorted(p for p in parts if not p.startswith("le=")))
            key = (name[: -len("_bucket")], rest)
            lv = le[0].split("=", 1)[1].strip('"')
            buckets.setdefault(key, []).append(
                (float("inf") if lv == "+Inf" else float(lv), float(value))
            )
        elif name.endswith("_count"):
            counts[(name[: -len("_count")], ",".join(sorted(
                p for p in labels.split(",") if p
            )))] = float(value)
    assert buckets, "no histogram series found"
    for key, series in buckets.items():
        series.sort()
        assert series[-1][0] == float("inf"), f"{key}: no +Inf bucket"
        for (_, a), (_, b) in zip(series, series[1:]):
            assert b >= a, f"{key}: bucket counts not cumulative"
        if key in counts:
            assert series[-1][1] == counts[key], (
                f"{key}: le=+Inf != _count"
            )


# -- tracing primitives ------------------------------------------------------


def test_tracer_ring_is_bounded_deque():
    t = Tracer(keep_finished=3)
    for i in range(10):
        with t.start_span(f"s{i}"):
            pass
    spans = t.finished_spans()
    assert len(spans) == 3
    assert [s.name for s in spans] == ["s7", "s8", "s9"]
    # keep_finished defaults non-zero so /debug/traces works out of the
    # box (the satellite fix).
    assert Tracer().keep_finished > 0


def test_span_trace_context_and_headers():
    t = Tracer()
    with t.start_span("outer") as outer:
        with t.start_span("inner") as inner:
            assert inner.trace_id == outer.trace_id
            assert inner.parent_span_id == outer.span_id
            headers = {}
            tracing.inject_headers(headers)
    assert headers["X-Trace-Id"] == outer.trace_id
    assert headers["X-Span-Id"] == inner.span_id
    ctx = t.extract_headers(headers)
    assert isinstance(ctx, TraceContext)
    assert ctx.trace_id == outer.trace_id
    assert t.extract_headers({}) is None
    # A remote/detached parent: same trace id, local root.
    with t.start_span("remote", parent=ctx) as remote:
        pass
    assert remote.trace_id == outer.trace_id
    assert remote.parent_span_id == inner.span_id
    assert remote.parent is None


def test_span_capture_attach_across_thread():
    """The explicit capture/attach protocol the pipeline uses: a span
    captured on one thread parents spans created on another."""
    t = Tracer()
    captured = {}
    done = threading.Event()

    def worker():
        with tracing.attach(captured["span"]):
            assert tracing.current_span() is captured["span"]
            with t.start_span("child"):
                pass
        assert tracing.current_span() is None
        done.set()

    with t.start_span("root") as root:
        captured["span"] = tracing.current_span()
        assert captured["span"] is root
        threading.Thread(target=worker).start()
        assert done.wait(10)
    assert [c.name for c in root.children] == ["child"]
    assert root.children[0].trace_id == root.trace_id


def test_span_record_stamps_finished_children():
    t = Tracer()
    with t.start_span("root") as root:
        root.record("stage", start=time.monotonic() - 0.5, duration=0.25, k=1)
    child = root.children[0]
    assert child.name == "stage"
    assert child.duration == 0.25
    assert child.tags == {"k": 1}
    assert child.trace_id == root.trace_id
    d = root.to_dict()
    assert d["children"][0]["durationMs"] == pytest.approx(250.0)


def test_slow_ring_captures_threshold_crossers():
    t = Tracer(slow_threshold=0.0)
    with t.start_span("slowish"):
        pass
    assert [s.name for s in t.slow_spans()] == ["slowish"]
    doc = t.traces()
    assert doc["recent"] and doc["slow"]


def test_nop_tracer_surface():
    t = NopTracer()
    with t.start_span("x") as span:
        assert span is None
    assert t.begin("x") is None
    assert t.traces() == {"recent": [], "slow": [], "slowThresholdMs": 100.0}


# -- statsd unit conversion (satellite regression) ---------------------------


def test_statsd_timing_converts_seconds_to_ms():
    recv = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    recv.bind(("127.0.0.1", 0))
    recv.settimeout(2)
    port = recv.getsockname()[1]
    c = StatsdClient(f"127.0.0.1:{port}")
    try:
        c.timing("lat", 0.25)
        assert recv.recv(1024).decode() == "pilosa_tpu.lat:250|ms"
        # Sub-millisecond timings keep their fraction instead of
        # truncating to 0|ms (the regression).
        c.timing("lat", 0.0005)
        assert recv.recv(1024).decode() == "pilosa_tpu.lat:0.5|ms"
        c.timing("lat", 0.0125)
        assert recv.recv(1024).decode() == "pilosa_tpu.lat:12.5|ms"
    finally:
        recv.close()
        c.close()


def test_expvar_timings_are_bounded_histograms():
    from pilosa_tpu.util import ExpvarStatsClient

    s = ExpvarStatsClient()
    for _ in range(1000):
        s.timing("q", 0.002)
    snap = s.snapshot()
    assert snap["timingCounts"]["q"] == 1000
    assert 0.001 <= snap["timings"]["q"]["p50"] <= 0.0025


# -- the pipeline span tree + HTTP surface -----------------------------------


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(4)


@pytest.fixture
def holder():
    h = Holder()
    h.open()
    idx = h.create_index("i")
    f = idx.create_field("f")
    ef = idx.existence_field()
    rows, cols = [], []
    rng = np.random.default_rng(11)
    for s in range(4):
        base = s * SHARD_WIDTH
        picks = rng.choice(SHARD_WIDTH, size=120, replace=False)
        for c in picks[:80]:
            rows.append(10)
            cols.append(base + int(c))
        for c in picks[40:]:
            rows.append(11)
            cols.append(base + int(c))
    f.import_bulk(rows, cols)
    ef.import_bulk([0] * len(cols), cols)
    return h


def _serve(holder, mesh):
    from pilosa_tpu.api import API
    from pilosa_tpu.net import serve

    eng = MeshEngine(holder, mesh)
    api = API(holder=holder, mesh_engine=eng)
    srv, _thread = serve(api, port=0)
    return eng, api, srv


def _wait_for_trace(tracer, trace_id, timeout=15):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        for s in tracer.finished_spans():
            if s.trace_id == trace_id:
                return s
        time.sleep(0.02)
    return None


def test_pipelined_query_yields_one_connected_span_tree(holder, mesh):
    """A pipelined (deferred) query crosses the HTTP handler, the
    accumulate queue, the dispatch worker, and a collect worker — and
    still yields ONE span tree under ONE trace id, with the pipeline
    stage spans attached, joined to the caller's X-Trace-Id."""
    eng, api, srv = _serve(holder, mesh)
    try:
        uri = f"http://localhost:{srv.server_address[1]}"
        sent_trace, sent_span = "cafe0123deadbeef", "0123456789abcdef"
        req = urllib.request.Request(
            f"{uri}/index/i/query",
            data=b"Count(Intersect(Row(f=10), Row(f=11)))",
            method="POST",
            headers={"X-Trace-Id": sent_trace, "X-Span-Id": sent_span},
        )
        doc = json.loads(urllib.request.urlopen(req, timeout=60).read())
        assert doc["traceID"] == sent_trace
        root = _wait_for_trace(api.tracer, sent_trace)
        assert root is not None, "trace never landed in the finished ring"
        assert root.name == "api.Query"
        assert root.parent_span_id == sent_span
        assert root.duration is not None
        names = {c.name for c in root.children}
        assert {
            "pipeline.queue_wait",
            "pipeline.lower_dispatch",
            "pipeline.device_readback",
            "pipeline.decode",
        } <= names, names
        # One trace id over every hop, and every stage child points back
        # at the root (a CONNECTED tree, not orphaned fragments).
        for c in root.children:
            assert c.trace_id == sent_trace
            assert c.parent_span_id == root.span_id
            assert c.duration is not None
        # The tree is visible at /debug/traces.
        traces = json.loads(
            urllib.request.urlopen(f"{uri}/debug/traces", timeout=30).read()
        )
        assert any(t["traceID"] == sent_trace for t in traces["recent"])
    finally:
        srv.shutdown()


def test_sync_query_stamps_trace_and_nests_executor_spans(holder, mesh):
    eng, api, srv = _serve(holder, mesh)
    try:
        uri = f"http://localhost:{srv.server_address[1]}"
        req = urllib.request.Request(
            f"{uri}/index/i/query",
            data=b"TopN(f, n=2)",  # not Count: takes the sync path
            method="POST",
        )
        doc = json.loads(urllib.request.urlopen(req, timeout=60).read())
        assert "traceID" in doc
        root = _wait_for_trace(api.tracer, doc["traceID"])
        assert root is not None and root.name == "api.Query"
        # The executor's spans nested under the handler's root.
        assert any(c.name == "executor.Execute" for c in root.children)
    finally:
        srv.shutdown()


def test_metrics_endpoint_serves_required_series(holder, mesh):
    eng, api, srv = _serve(holder, mesh)
    try:
        uri = f"http://localhost:{srv.server_address[1]}"
        req = urllib.request.Request(
            f"{uri}/index/i/query",
            data=b"Count(Intersect(Row(f=10), Row(f=11)))",
            method="POST",
        )
        urllib.request.urlopen(req, timeout=60).read()
        resp = urllib.request.urlopen(f"{uri}/metrics", timeout=30)
        assert "text/plain" in resp.headers.get("Content-Type", "")
        text = resp.read().decode()
        _assert_prometheus_conformant(text)
        for series in (
            "pilosa_query_seconds_bucket",
            "pilosa_query_op_seconds_bucket",
            "pilosa_pipeline_stage_seconds_bucket",
            "pilosa_fragment_op_seconds_bucket",
        ):
            assert series in text, f"missing series: {series}"
        # /debug/vars carries the same registry as JSON.
        dbg = json.loads(
            urllib.request.urlopen(f"{uri}/debug/vars", timeout=30).read()
        )
        assert "metrics" in dbg
        assert "pilosa_pipeline_stage_seconds" in dbg["metrics"]["histograms"]
    finally:
        srv.shutdown()


# -- the scrape surface of a default serving stack ---------------------------

# Every family an operator's dashboards and alerts are built on, by the
# document that defines it.  A default stack that has served a query and
# taken an import must expose each at /metrics.
SURFACE_SERIES = [
    # docs/observability.md
    "pilosa_query_seconds_bucket",
    "pilosa_query_op_seconds_bucket",
    "pilosa_pipeline_stage_seconds_bucket",
    'pilosa_pipeline_accum_close_total{reason="quiet"}',
    "pilosa_fragment_op_seconds_bucket",
    "pilosa_engine_cache_hits_total",
    "pilosa_engine_cache_misses_total",
    "pilosa_device_bytes_skipped_total",
    "pilosa_engine_resident_bytes",
    "pilosa_engine_evicted_bytes",
    "pilosa_engine_evictions_total",
    "pilosa_engine_stack_rebuilds_total",
    "pilosa_engine_compile_total",
    "pilosa_engine_compile_seconds",
    "pilosa_engine_compile_cache_keys",
    "pilosa_engine_heat_tracked_rows",
    "pilosa_engine_residency_gap_bytes",
    "pilosa_advisor_predictions_total",
    "pilosa_advisor_hits_total",
    "pilosa_advisor_misses_total",
    # docs/mesh.md
    "pilosa_mesh_devices",
    "pilosa_mesh_local_devices",
    "pilosa_mesh_shards_per_device",
    "pilosa_mesh_psum_dispatches_total",
    "pilosa_cluster_remote_calls_total",
    # docs/durability.md
    "pilosa_ingest_acked_unsynced_bytes",
    "pilosa_replica_reads_total",
    "pilosa_ingest_degraded_batches_total",
    "pilosa_client_retries_total",
    "pilosa_hints_queued_total",
    "pilosa_hints_replayed_total",
    "pilosa_hints_dropped_total",
    "pilosa_hints_pending",
    "pilosa_faults_injected_total",
    # docs/fusion.md
    "pilosa_engine_fused_program_programs_total",
    "pilosa_engine_fused_program_queries_total",
    "pilosa_engine_fused_program_masks_evaluated_total",
    "pilosa_engine_fused_program_masks_referenced_total",
    # docs/residency.md
    "pilosa_engine_promotions_total",
    "pilosa_engine_partial_promotions_total",
    "pilosa_engine_promotions_declined_total",
    "pilosa_engine_host_fallbacks_total",
    "pilosa_engine_resident_block_fraction",
    # docs/ingest.md
    "pilosa_ingest_batches_total",
    "pilosa_ingest_bits_total",
    "pilosa_ingest_changed_total",
    "pilosa_ingest_seconds_bucket",
    "pilosa_ingest_sync_chunks_total",
    "pilosa_ingest_sync_coalesced_total",
    "pilosa_ingest_sync_dispatches_total",
    'pilosa_cache_entries{cache_type="ranked"}',
    "pilosa_cache_recalculate_seconds_bucket",
    # docs/serving.md
    "pilosa_admission_inflight",
    "pilosa_admission_active_tenants",
    "pilosa_admission_admitted_total",
    "pilosa_admission_shed_total",
    "pilosa_server_connections",
    "pilosa_server_connections_total",
    "pilosa_server_requests_total",
]


@pytest.fixture(scope="module")
def surface():
    """One default stack (event-loop front end, its own admission
    controller, a one-device mesh engine) that answers a cardinality
    Count and a fused Count, takes a roaring and an id-pairs import over
    HTTP and reads both back; yields the /metrics text after that."""
    from pilosa_tpu.api import API
    from pilosa_tpu.net import serve
    from pilosa_tpu.roaring import codec

    h = Holder()
    h.open()
    h.create_index("smoke").create_field("f").import_bulk([1, 1, 1], [0, 5, 9])
    eng = MeshEngine(h, make_mesh(1))
    srv, _thread = serve(API(holder=h, mesh_engine=eng), port=0)
    uri = f"http://localhost:{srv.server_address[1]}"

    def post(path, data):
        req = urllib.request.Request(uri + path, data=data, method="POST")
        return json.loads(urllib.request.urlopen(req, timeout=60).read())

    try:
        assert type(srv).__name__ == "AsyncHTTPServer"
        doc = post("/index/smoke/query", b"Count(Row(f=1))")
        assert doc["results"][0] == 3 and "traceID" in doc, doc
        # An Intersect cannot take the O(1) cardinality lane: it runs as
        # a fused mesh dispatch.
        doc = post("/index/smoke/query", b"Count(Intersect(Row(f=1), Row(f=1)))")
        assert doc["results"][0] == 3, doc
        vals = np.asarray(
            [(3 << 20) | 1, (3 << 20) | 2, (3 << 20) | 70000], dtype=np.uint64
        )
        doc = post("/index/smoke/field/f/import-roaring/0", codec.serialize(vals))
        assert doc["changed"] == 3, doc
        assert post("/index/smoke/query", b"Count(Row(f=3))")["results"][0] == 3
        post(
            "/index/smoke/field/f/import",
            json.dumps(
                {"rowIDs": [7, 7, 7, 8], "columnIDs": [11, 12, 70000, 11]}
            ).encode(),
        )
        # A read of the just-written bits reflects them at once.
        assert post("/index/smoke/query", b"Count(Row(f=7))")["results"][0] == 3
        yield urllib.request.urlopen(uri + "/metrics", timeout=30).read().decode()
    finally:
        srv.shutdown()
        eng.close()


@pytest.mark.parametrize("series", SURFACE_SERIES)
def test_metrics_surface_carries_series(surface, series):
    assert series in surface, f"/metrics is missing {series}"


def test_metrics_surface_values_moved(surface):
    """The samples say what the stack did: the fused Count was one psum
    dispatch and no internal-client call, and each import path counted
    its batch."""
    _assert_prometheus_conformant(surface)
    assert 'le="+Inf"' in surface
    samples = {}
    for line in surface.splitlines():
        if line and not line.startswith("#"):
            name, _, value = line.rpartition(" ")
            samples[name] = float(value)
    assert samples["pilosa_mesh_devices"] >= 1
    assert samples["pilosa_mesh_shards_per_device"] >= 1
    assert samples["pilosa_mesh_psum_dispatches_total"] > 0
    assert samples['pilosa_ingest_batches_total{path="roaring"}'] >= 1
    assert samples['pilosa_ingest_batches_total{path="bits"}'] >= 1
    assert samples['pilosa_cache_entries{cache_type="ranked"}'] >= 1
    assert samples["pilosa_admission_admitted_total"] >= 6
    assert samples["pilosa_server_connections"] >= 1  # the scrape's own


# -- 2-node remote fan-out ---------------------------------------------------


def test_trace_id_propagates_across_remote_fanout(tmp_path):
    """A query whose shards span both nodes produces span trees on BOTH
    nodes sharing ONE trace id: the coordinator roots it, the remote
    node's root carries the coordinator's span as parentSpanID (the
    X-Trace-Id/X-Span-Id wire propagation)."""
    h = run_cluster(tmp_path, 2)
    try:
        client = h.client(0)
        client.create_index("i")
        client.create_field("i", "f")
        cols = [s * SHARD_WIDTH + 1 for s in range(8)]
        client.import_bits("i", "f", 0, [10] * len(cols), cols)
        # Pick a shard set spanning both nodes.
        owners = {
            s: h[0].cluster.shard_nodes("i", s)[0].id for s in range(8)
        }
        assert len(set(owners.values())) == 2, owners

        doc = client.query("i", "Count(Row(f=10))")
        assert doc["results"][0] == 8
        trace_id = doc.get("traceID")
        assert trace_id, doc
        coord_root = _wait_for_trace(h[0].tracer, trace_id)
        assert coord_root is not None
        # The coordinator's tree shows the remote hop.
        def walk(s):
            yield s
            for c in s.children:
                yield from walk(c)

        assert any(
            s.name == "executor.RemoteQuery" for s in walk(coord_root)
        ), [s.name for s in walk(coord_root)]
        remote_root = _wait_for_trace(h[1].tracer, trace_id)
        assert remote_root is not None, (
            "remote node recorded no span for the coordinator's trace"
        )
        assert remote_root.parent_span_id != ""
    finally:
        h.close()

"""Cluster & device observability tests (the PR-4 tentpole): the
structured event journal (ring bounding, type filtering, trace-id
linkage), health/readiness probes (/healthz always-alive, /readyz
flipping across startup and resize), the /cluster/metrics federation
(both nodes' series labeled by node id, degraded nodes reported as
scrape errors), anti-entropy pass journaling and engine HBM
introspection (eviction events + gauge flush at close)."""

import json
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from harness import run_cluster
from pilosa_tpu import pql
from pilosa_tpu.cluster.syncer import HolderSyncer
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.ops import SHARD_WIDTH
from pilosa_tpu.parallel import MeshEngine, make_mesh
from pilosa_tpu.util.events import EventJournal
from pilosa_tpu.util.stats import REGISTRY
from pilosa_tpu.util.tracing import Tracer


def _get(port, path, timeout=30):
    return urllib.request.urlopen(
        f"http://localhost:{port}{path}", timeout=timeout
    )


def _get_json(port, path):
    return json.loads(_get(port, path).read())


# -- the journal itself ------------------------------------------------------


def test_journal_ring_is_bounded_and_counts_drops():
    j = EventJournal(capacity=8, node="n0")
    for i in range(20):
        j.append("t.a", i=i)
    assert len(j) == 8
    assert j.dropped == 12
    evs = j.events()
    # Chronological, newest retained, seq strictly increasing.
    assert [e.fields["i"] for e in evs] == list(range(12, 20))
    assert all(b.seq == a.seq + 1 for a, b in zip(evs, evs[1:]))
    doc = j.to_doc()
    assert doc["capacity"] == 8 and doc["dropped"] == 12
    assert doc["events"][-1]["node"] == "n0"


def test_journal_type_filtering_and_limit():
    j = EventJournal(capacity=64)
    j.append("gossip.transition", member="x")
    j.append("gossip.reap", member="x")
    j.append("cluster.state")
    j.append("engine.evict")
    # Family prefix: "gossip" matches gossip.* but not e.g. "gossipx".
    j.append("gossipx.other")
    assert [e.type for e in j.events(type="gossip")] == [
        "gossip.transition", "gossip.reap",
    ]
    assert [e.type for e in j.events(type="gossip.reap")] == ["gossip.reap"]
    assert [e.type for e in j.events(type="engine")] == ["engine.evict"]
    assert len(j.events(limit=2)) == 2
    assert [e.type for e in j.events(limit=2)] == ["engine.evict", "gossipx.other"]
    # limit=0 means ZERO events, not the whole ring (the -0 slice trap).
    assert j.events(limit=0) == []


def test_journal_captures_ambient_trace_id():
    j = EventJournal()
    t = Tracer()
    with t.start_span("query") as span:
        ev = j.append("engine.evict", bytes=1)
    assert ev.trace_id == span.trace_id
    # Outside any span: no trace id; explicit override wins.
    assert j.append("x").trace_id == ""
    assert j.append("x", trace_id="feed").trace_id == "feed"


# -- engine residency introspection ------------------------------------------


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(2)


def _holder_two_fields():
    h = Holder()
    h.open()
    idx = h.create_index("i")
    f = idx.create_field("f")
    g = idx.create_field("g")
    f.import_bulk([1, 1, 1], [0, 5, SHARD_WIDTH + 9])
    g.import_bulk([2, 2], [1, 5])
    return h


def test_query_triggered_eviction_journals_with_trace_id(mesh):
    """An admission eviction caused by a query carries THAT query's
    trace id — the Dapper-style annotation joining the journal to
    /debug/traces."""
    holder = _holder_two_fields()
    j = EventJournal(node="n0")
    eng = MeshEngine(holder, mesh, journal=j)
    tracer = Tracer()
    call_f = pql.parse("Intersect(Row(f=1), Row(f=1))").calls[0]
    call_g = pql.parse("Intersect(Row(g=2), Row(g=2))").calls[0]
    assert eng.count("i", call_f, [0, 1]) == 3
    # Budget for ONE stack (+ summary headroom): the next admission must
    # evict "f" to fit "g".  (A budget no stack fits at all no longer
    # over-admits — it host-falls-back; tests/test_residency.py covers
    # that regime.)
    eng.max_resident_bytes = eng._resident_bytes + 4096
    with tracer.start_span("api.Query") as span:
        assert eng.count("i", call_g, [0, 1]) == 2
    evs = j.events(type="engine.evict")
    assert evs, [e.type for e in j.events()]
    ev = evs[-1]
    assert ev.fields["index"] == "i" and ev.fields["field"] == "f"
    assert ev.fields["bytes"] > 0
    assert ev.trace_id == span.trace_id
    eng.close()


def test_engine_close_journals_shutdown_and_flushes_gauges(mesh):
    holder = _holder_two_fields()
    j = EventJournal()
    eng = MeshEngine(holder, mesh, journal=j)
    call = pql.parse("Intersect(Row(f=1), Row(f=1))").calls[0]
    assert eng.count("i", call, [0, 1]) == 3
    eng.refresh_metrics()
    snap = REGISTRY.snapshot()
    assert snap["gauges"]["pilosa_engine_resident_bytes"]["_"] > 0
    eng.close()
    # One shutdown event (idempotent: a second close adds nothing), and
    # the teardown evictions do NOT flood the journal.
    closes = j.events(type="engine.close")
    assert len(closes) == 1
    assert closes[0].fields["releasedBytes"] > 0
    eng.close()
    assert len(j.events(type="engine.close")) == 1
    # Gauge state flushed: a scrape racing shutdown reads 0, not the
    # stale pre-close residency.
    snap = REGISTRY.snapshot()
    assert snap["gauges"]["pilosa_engine_resident_bytes"]["_"] == 0
    assert snap["gauges"]["pilosa_engine_evicted_bytes"]["_"] == 0
    # The registry is still readable after engine teardown.
    assert "pilosa_engine_resident_bytes 0" in REGISTRY.prometheus_text()


def test_engine_metrics_series_present_after_traffic(mesh):
    holder = _holder_two_fields()
    eng = MeshEngine(holder, mesh, journal=EventJournal())
    call = pql.parse("Intersect(Row(f=1), Row(f=1))").calls[0]
    assert eng.count("i", call, [0, 1]) == 3
    eng.refresh_metrics()
    text = REGISTRY.prometheus_text()
    assert "pilosa_engine_stack_rebuilds_total" in text
    assert "pilosa_engine_evictions_total" in text
    assert 'pilosa_engine_compile_seconds{phase="compile"}' in text
    snap = eng.cache_snapshot()
    assert snap["stackRebuilds"] >= 1
    assert snap["compileCacheKeys"] >= 1
    # The jitted count program compiled at least once in this process.
    c = REGISTRY.snapshot()["counters"]
    assert c["pilosa_engine_compile_total"]["_"] >= 1
    eng.close()


# -- health / readiness / federation over a 2-node cluster -------------------


def test_healthz_readyz_flip_across_startup_and_resize(tmp_path):
    h = run_cluster(tmp_path, 2)
    try:
        port = h[0].port
        doc = _get_json(port, "/healthz")
        assert doc["status"] == "ok" and doc["uptimeSeconds"] >= 0
        # Harness clusters come up NORMAL: ready.
        doc = _get_json(port, "/readyz")
        assert doc["ready"] is True and doc["reasons"] == []

        def readyz():
            try:
                resp = _get(port, "/readyz")
                return resp.status, json.loads(resp.read())
            except urllib.error.HTTPError as e:
                return e.code, json.loads(e.read())

        # Startup semantics: STARTING is not ready.
        h[0].cluster.set_state("STARTING")
        code, doc = readyz()
        assert code == 503 and not doc["ready"]
        assert any("STARTING" in r for r in doc["reasons"])
        # ... flips true when the state machine reaches NORMAL ...
        h[0].cluster.set_state("NORMAL")
        code, doc = readyz()
        assert code == 200 and doc["ready"]
        # ... and back to false during a resize.
        h[0].cluster.set_state("RESIZING")
        code, doc = readyz()
        assert code == 503 and not doc["ready"]
        assert any("RESIZING" in r for r in doc["reasons"])
        h[0].cluster.set_state("NORMAL")
        assert readyz()[0] == 200
        # A node configured for a mesh ([mesh] devices >= 0) names a
        # missing engine instead of passing as a host-loop node.
        api = h[0].api
        assert api.mesh_required and api.mesh_engine is not None
        eng, api.mesh_engine = api.mesh_engine, None
        try:
            code, doc = readyz()
            assert code == 503 and "mesh engine missing" in doc["reasons"]
        finally:
            api.mesh_engine = eng
        assert readyz()[0] == 200
        # Liveness is unaffected by readiness the whole way.
        assert _get_json(port, "/healthz")["status"] == "ok"
        # The state flips were journaled (cluster.state from/to).
        ev = _get_json(port, "/debug/events?type=cluster.state")
        pairs = [
            (e["fields"]["from"], e["fields"]["to"]) for e in ev["events"]
        ]
        assert ("NORMAL", "RESIZING") in pairs and ("RESIZING", "NORMAL") in pairs
    finally:
        h.close()


def test_cluster_metrics_federates_both_nodes(tmp_path):
    h = run_cluster(tmp_path, 2)
    try:
        port = h[0].port
        # Traffic on node 0 so its series are non-trivial.
        c = h.client(0)
        c.create_index("i")
        c.create_field("i", "f")
        c.import_bits("i", "f", 0, [1, 1], [0, 5])
        c.query("i", "Count(Row(f=1))")
        resp = _get(port, "/cluster/metrics")
        assert "text/plain" in resp.headers.get("Content-Type", "")
        text = resp.read().decode()
        # Every sample labeled by node; both nodes present.
        assert 'node="node0"' in text and 'node="node1"' in text
        assert 'pilosa_node_scrape_error{node="node0"} 0' in text
        assert 'pilosa_node_scrape_error{node="node1"} 0' in text
        # A specific series appears for BOTH nodes.
        for nid in ("node0", "node1"):
            assert any(
                line.startswith("pilosa_query_seconds_count")
                and f'node="{nid}"' in line
                for line in text.splitlines()
            ), nid
        # Valid exposition: no duplicate HELP/TYPE metadata.
        meta = [l for l in text.splitlines() if l.startswith("# ")]
        assert len(meta) == len(set(meta))
        # Samples parse: name{labels} value.
        for line in text.splitlines():
            if line.startswith("#") or not line:
                continue
            name, sep, value = line.rpartition(" ")
            assert sep and 'node="' in name, line
            float(value)
    finally:
        h.close()


def test_cluster_metrics_reports_degraded_node_as_scrape_error(tmp_path):
    h = run_cluster(tmp_path, 2)
    try:
        # Kill node1's HTTP listener; the federation must degrade to a
        # scrape-error marker, not fail the whole scrape.
        h[1]._http.shutdown()
        h[1]._http.server_close()
        h[1]._http = None
        text = _get(h[0].port, "/cluster/metrics?timeout=3").read().decode()
        assert 'pilosa_node_scrape_error{node="node1"} 1' in text
        assert 'pilosa_node_scrape_error{node="node0"} 0' in text
        assert 'node="node0"' in text  # local series still served
    finally:
        h.close()


def test_antientropy_pass_journaled(tmp_path):
    h = run_cluster(tmp_path, 2, replica_n=2)
    try:
        c = h.client(0)
        c.create_index("i")
        c.create_field("i", "f")
        cols = [s * SHARD_WIDTH + 1 for s in range(4)]
        c.import_bits("i", "f", 0, [1] * len(cols), cols)
        syncer = HolderSyncer(
            h[0].holder, h[0].cluster, h[0].logger, journal=h[0].journal
        )
        syncer.sync_holder()
        ev = _get_json(h[0].port, "/debug/events?type=antientropy")
        types = [e["type"] for e in ev["events"]]
        assert "antientropy.start" in types and "antientropy.end" in types
        end = [e for e in ev["events"] if e["type"] == "antientropy.end"][-1]
        assert end["fields"]["fragments"] >= 1
        assert end["fields"]["seconds"] >= 0
        for key in ("blocksSynced", "bitsSet", "bitsCleared", "errors"):
            assert key in end["fields"]
    finally:
        h.close()


def test_debug_events_limit_and_type_filter_over_http(tmp_path):
    h = run_cluster(tmp_path, 2)
    try:
        for i in range(10):
            h[0].journal.append("test.tick", i=i)
        h[0].journal.append("other.kind")
        doc = _get_json(h[0].port, "/debug/events?type=test&limit=3")
        assert [e["fields"]["i"] for e in doc["events"]] == [7, 8, 9]
        assert all(e["type"] == "test.tick" for e in doc["events"])
        assert doc["node"] == "node0"
    finally:
        h.close()

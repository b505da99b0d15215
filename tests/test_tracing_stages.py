"""The stage clock (util/tracing.py stage/waited): every path's stages
under one trace id, the drain record's counters, profiler annotations
only during a capture, the legacy pipeline series unchanged, and the
in-flight union."""

import glob
import json
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

from pilosa_tpu import pql
from pilosa_tpu.core.field import FieldOptions
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.ops import SHARD_WIDTH
from pilosa_tpu.parallel import MeshEngine, make_mesh
from pilosa_tpu.parallel.batcher import _Item
from pilosa_tpu.util import plans, tracing
from pilosa_tpu.util.stats import (
    METRIC_ENGINE_DRAIN_EVALUATED,
    METRIC_ENGINE_DRAIN_PLANE_BYTES,
    METRIC_ENGINE_DRAIN_REQUESTS,
    METRIC_ENGINE_DRAIN_SLOTS,
    METRIC_ENGINE_DRAINS,
    METRIC_HTTP_REQUEST,
    METRIC_PIPELINE_STAGE,
    METRIC_QUERY_STAGE,
    Counter,
    REGISTRY,
)
from pilosa_tpu.util.tracing import Tracer

SHARDS = 4
PLANE = SHARD_WIDTH // 8  # bytes of one row-plane of one shard


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(4)


@pytest.fixture(scope="module")
def served(mesh):
    """One holder (set field f, int field v), engine, API and HTTP
    server for the module."""
    from pilosa_tpu.api import API
    from pilosa_tpu.net import serve

    h = Holder()
    h.open()
    idx = h.create_index("i")
    f = idx.create_field("f")
    v = idx.create_field("v", FieldOptions(type="int", min=0, max=255))
    rng = np.random.default_rng(26)
    rows, cols, vcols, vals = [], [], [], []
    for s in range(SHARDS):
        base = s * SHARD_WIDTH
        picks = rng.choice(SHARD_WIDTH, size=200, replace=False)
        for k, c in enumerate(picks):
            rows.append(10 + k % 4)
            cols.append(base + int(c))
            vcols.append(base + int(c))
            vals.append(int(rng.integers(0, 256)))
    f.import_bulk(rows, cols)
    v.import_values(vcols, vals)
    idx.existence_field().import_bulk([0] * len(cols), cols)
    eng = MeshEngine(h, mesh)
    api = API(holder=h, mesh_engine=eng)
    srv, _thread = serve(api, port=0)
    yield eng, api, f"http://localhost:{srv.server_address[1]}"
    srv.shutdown()


def _post(uri, body: bytes, path="/index/i/query", timeout=60):
    req = urllib.request.Request(uri + path, data=body, method="POST")
    return json.loads(urllib.request.urlopen(req, timeout=timeout).read())


def _finished(tracer, trace_id, timeout=15):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        for s in tracer.finished_spans():
            if s.trace_id == trace_id:
                return s
        time.sleep(0.02)
    raise AssertionError(f"trace {trace_id} never finished")


def _walk(span):
    yield span
    for c in span.children:
        yield from _walk(c)


EPS = 2e-3  # two clocks are read per stamp; spans are built from several


def _assert_tiles(span):
    """Children lie inside their parent, do not overlap each other, and
    leave the parent a self time >= 0 — at every level of the tree."""
    kids = sorted(span.children, key=lambda c: c.start)
    end = span.start
    for c in kids:
        assert c.duration is not None, c.name
        assert c.trace_id == span.trace_id
        assert c.start >= span.start - EPS, (span.name, c.name)
        assert c.start + c.duration <= span.start + span.duration + EPS, (
            span.name, c.name)
        assert c.start >= end - EPS, f"{c.name} overlaps its sibling in {span.name}"
        end = c.start + c.duration
        _assert_tiles(c)
    assert sum(c.duration for c in kids) <= span.duration + EPS * max(1, len(kids))


def _stage_names(root):
    return {s.name[len("pipeline."):] for s in _walk(root)
            if s.name.startswith("pipeline.")}


PIPELINE = {"queue_wait", "lower_dispatch", "lower", "dispatch",
            "device_readback", "collect_wait", "device_get", "decode"}


def _fused_roots(eng, api):
    """Two Sums queued into one drain: the batcher's fused lane.  Each
    rides a root span of its own."""
    b = eng.batcher()
    roots, items = [], []
    for lo in (10, 11):
        root = api.tracer.begin("api.Query", index="i")
        plan = plans.begin("i", f"Sum {lo}")
        with tracing.attach(root), plans.attach(plan):
            items.append(b._submit(
                "i", None, list(range(SHARDS)), allow_direct=False, kind="sum",
                spec={"kind": "sum", "field": "v",
                      "filter": pql.parse(f"Row(f={lo})").calls[0]},
            ))
        roots.append(root)
    for it in items:
        assert it.event.wait(60) and it.error is None, it.error
    for root in roots:
        root.finish()
    return roots


@pytest.mark.parametrize("path", ["deferred", "direct", "fused"])
def test_stages_tile_one_trace(served, path):
    eng, api, uri = served
    if path == "deferred":
        doc = _post(uri, b"Count(Intersect(Row(f=10), Row(f=11)))")
        roots = [_finished(api.tracer, doc["traceID"])]
        want = PIPELINE | {"parse"}
    elif path == "direct":
        doc = _post(uri, b"Sum(Row(f=12), field=v)")
        roots = [_finished(api.tracer, doc["traceID"])]
        want = {"parse", "execute", "lower", "dispatch", "device_get", "decode"}
    else:
        roots = _fused_roots(eng, api)
        want = PIPELINE
    for root in roots:
        assert root.name == "api.Query"
        assert want <= _stage_names(root), (path, _stage_names(root))
        assert {s.trace_id for s in _walk(root)} == {root.trace_id}
        _assert_tiles(root)
        assert root.tags["path"] == path
        dispatch = next(s for s in _walk(root) if s.name == "pipeline.dispatch")
        assert {"tier", "live", "evaluated", "planes_per_request",
                "planes_per_drain"} <= set(dispatch.tags)


def test_http_clock_and_front_end_stages(served):
    eng, api, uri = served

    def count(name, **labels):
        h = REGISTRY.get_histogram(name, **labels)
        return h.count if h is not None else 0

    before = (count(METRIC_HTTP_REQUEST),
              count(METRIC_QUERY_STAGE, path="deferred", stage="http_read"),
              count(METRIC_QUERY_STAGE, path="deferred", stage="respond"),
              count(METRIC_QUERY_STAGE, path="deferred", stage="plan"))
    doc = _post(uri, b"Count(Union(Row(f=10), Row(f=13)))")
    root = _finished(api.tracer, doc["traceID"])
    deadline = time.monotonic() + 10  # the clock finishes after the last byte
    while "http_ms" not in root.tags and time.monotonic() < deadline:
        time.sleep(0.01)
    after = (count(METRIC_HTTP_REQUEST),
             count(METRIC_QUERY_STAGE, path="deferred", stage="http_read"),
             count(METRIC_QUERY_STAGE, path="deferred", stage="respond"),
             count(METRIC_QUERY_STAGE, path="deferred", stage="plan"))
    assert [a - b for a, b in zip(after, before)] == [1, 1, 1, 1]
    # First byte in -> last byte out holds the whole api.Query span.
    assert root.tags["http_ms"] >= root.duration * 1e3


def _drain_counters(op, path):
    return [
        REGISTRY.counter(METRIC_ENGINE_DRAINS, op=op, path=path).get(),
        REGISTRY.counter(METRIC_ENGINE_DRAIN_SLOTS, op=op, path=path).get(),
        REGISTRY.counter(METRIC_ENGINE_DRAIN_REQUESTS, op=op, path=path).get(),
        REGISTRY.counter(METRIC_ENGINE_DRAIN_PLANE_BYTES, op=op, path=path,
                         counted="per_request").get(),
        REGISTRY.counter(METRIC_ENGINE_DRAIN_PLANE_BYTES, op=op, path=path,
                         counted="per_drain").get(),
        REGISTRY.counter(METRIC_ENGINE_DRAIN_EVALUATED, op=op, path=path).get(),
    ]


def test_drain_record_of_three_counts_at_tier_eight(served):
    eng, _api, _uri = served
    calls = [pql.parse(t).calls[0] for t in (
        "Intersect(Row(f=10), Row(f=11))",
        "Intersect(Row(f=10), Row(f=12))",
        "Union(Row(f=11), Row(f=12), Row(f=11))",
    )]
    before = _drain_counters("Count", "dense_batch")
    out = eng.count_many("i", calls, [list(range(SHARDS))] * 3)
    plans.take_dispatch_note()
    assert len(out) == 3
    moved = [a - b for a, b in zip(_drain_counters("Count", "dense_batch"), before)]
    # one program, 8 slots, 3 requests; each Count names 2 distinct
    # planes, the drain 3; the device runs the 3 live slots.
    assert moved == [1, 8, 3, (2 + 2 + 2) * SHARDS * PLANE, 3 * SHARDS * PLANE, 3]


def test_drain_record_of_sixteen_counts_at_tier_sixty_four(served):
    """A drain of 16 on the 64-slot program: the tier is what was
    compiled for, the evaluated slots what the device runs."""
    eng, _api, _uri = served
    pairs = [(a, b) for a in range(10, 14) for b in range(10, 14)]
    calls = [pql.parse(f"Difference(Row(f={a}), Row(f={b}))").calls[0]
             for a, b in pairs]
    before = _drain_counters("Count", "dense_batch")
    out = eng.count_many("i", calls, [list(range(SHARDS))] * 16)
    plans.take_dispatch_note()
    assert len(out) == 16
    moved = [a - b for a, b in zip(_drain_counters("Count", "dense_batch"), before)]
    assert moved[:3] == [1, 64, 16] and moved[5] == 16


def test_drain_record_of_a_bsi_aggregate(served):
    eng, _api, _uri = served
    before = _drain_counters("Sum", "aggregate")
    eng.sum("i", "v", pql.parse("Row(f=10)").calls[0], list(range(SHARDS)))
    plans.take_dispatch_note()
    moved = [a - b for a, b in zip(_drain_counters("Sum", "aggregate"), before)]
    # v is 0..255: 8 planes + not-null, and the filter's one row.
    assert moved == [1, 1, 1, 10 * SHARDS * PLANE, 10 * SHARDS * PLANE, 1]


def test_no_annotation_without_a_capture(served, monkeypatch):
    eng, _api, uri = served
    made = []
    monkeypatch.setattr(tracing, "_annotation",
                        lambda *a, **k: made.append(a) or pytest.fail("made"))
    assert tracing.capturing is False
    _post(uri, b"Count(Intersect(Row(f=11), Row(f=13)))")
    _post(uri, b"Sum(Row(f=13), field=v)")
    assert made == []


def test_capture_holds_stage_annotations_and_no_python_tracer(served, tmp_path):
    from jax.profiler import ProfileData

    eng, _api, uri = served
    stop = threading.Event()

    def load():
        k = 0
        while not stop.is_set():
            k += 1
            _post(uri, f"Count(Intersect(Row(f=10), Row(f={10 + k % 4})))".encode())
            _post(uri, f"Sum(Row(f={10 + k % 4}), field=v)".encode())

    worker = threading.Thread(target=load, daemon=True)
    worker.start()
    try:
        # A starved host (the suite's other workers) can hand back a
        # capture in which no request ran at all: take another.
        for attempt in range(3):
            out = tmp_path / str(attempt)
            doc = _post(uri, b"", path=f"/debug/pprof/trace?seconds=1&dir={out}",
                        timeout=120)
            assert doc["python"] is False and tracing.capturing is False
            (pb,) = glob.glob(os.path.join(str(out), "plugins", "profile", "*",
                                           "*.xplane.pb"))
            data = ProfileData.from_file(pb)
            lines = [(plane.name, line) for plane in data.planes
                     for line in plane.lines]
            events = [(line.name, ev) for _, line in lines for ev in line.events
                      if ev.name.startswith("pilosa.")]
            if events:
                break
    finally:
        stop.set()
        worker.join(60)
    # The Python tracer's events are named "$file:line function".
    assert not any(ev.name.startswith("$") for _, line in lines
                   for ev in line.events)
    # ... and the stages run on named threads, none on a line that only
    # carries the process's name.
    assert {name for name, _ in events} <= {
        "pq-drain", "pq-dispatch", "pq-collect-0", "pq-collect-1", "pq-collect-2",
        "pq-collect-3", "http-pool", "http-reactor-0"}, {n for n, _ in events}
    names = {ev.name for _, ev in events}
    assert {"pilosa.accum_tail", "pilosa.lower", "pilosa.dispatch",
            "pilosa.device_get"} <= names, names
    # The window's close is marked on the drain worker's own line, with
    # how it ended.
    line, tail = next(e for e in events if e[1].name == "pilosa.accum_tail")
    assert line == "pq-drain"
    assert {"batch", "reason", "path"} <= set(dict(tail.stats)), dict(tail.stats)
    _, dispatch = next(e for e in events if e[1].name == "pilosa.dispatch")
    stats = dict(dispatch.stats)
    assert {"tier", "live", "evaluated", "planes_per_request",
            "planes_per_drain", "path"} <= set(stats), stats


def test_legacy_pipeline_series_move_as_before(served):
    """One deferred drain of three Counts: queue_wait once per item,
    the other three legacy stages once per drain — what
    PipelineStats.record counted before the stage clock took over."""
    eng, _api, _uri = served
    b = eng.batcher()
    b._ensure_workers()

    def counts():
        return [REGISTRY.get_histogram(METRIC_PIPELINE_STAGE, stage=s).count
                for s in ("queue_wait", "lower_dispatch", "device_readback",
                          "decode")]

    before = counts()
    items = [_Item("i", pql.parse(f"Intersect(Row(f=10), Row(f={r}))").calls[0],
                   list(range(SHARDS))) for r in (11, 12, 13)]
    b._dispatch_q.put(("count", "i", items, False))
    for it in items:
        assert it.event.wait(60) and it.error is None, it.error
    assert [a - c for a, c in zip(counts(), before)] == [3, 1, 1, 1]
    snap = b.pipeline_snapshot()["stages"]
    assert {"queue_wait", "lower_dispatch", "device_readback", "decode"} <= set(snap)
    assert snap["decode"]["count"] >= 1 and "p95Seconds" in snap["decode"]


def test_inflight_is_the_union_not_the_sum():
    c = Counter()
    inflight = tracing.Inflight(counter=c)
    inflight.begin(now=10.0)   # drain A dispatched
    inflight.begin(now=11.0)   # drain B dispatched while A is out
    inflight.end(now=12.0)     # A fetched
    inflight.end(now=13.0)     # B fetched
    assert c.get() == pytest.approx(3.0)  # [10, 13], not 2 + 2
    inflight.begin(now=20.0)
    inflight.end(now=20.5)
    assert c.get() == pytest.approx(3.5)


def test_stage_nesting_self_time_and_open_path():
    """The recorder alone: a self-time stage observes what its inner
    stages leave, takes the path they took, and leaves no span."""
    t = Tracer()

    def n(path, stage):
        h = REGISTRY.get_histogram(METRIC_QUERY_STAGE, path=path, stage=stage)
        return (h.count, h.sum) if h is not None else (0, 0.0)

    before = n("direct", "plan"), n("direct", "execute")
    with t.start_span("api.Query") as root:
        with tracing.stage("plan", self_time=True):
            with tracing.stage("execute", "direct"):
                with tracing.stage("lower"):
                    time.sleep(0.02)
            tracing.hole(0.0, 0.01)
    after = n("direct", "plan"), n("direct", "execute")
    assert after[0][0] - before[0][0] == 1 and after[1][0] - before[1][0] == 1
    assert after[1][1] - before[1][1] >= 0.02
    assert after[0][1] - before[0][1] < 0.01  # plan left out execute and the hole
    assert [c.name for c in root.children] == ["pipeline.execute"]
    assert [c.name for c in root.children[0].children] == ["pipeline.lower"]
    assert root.tags["path"] == "direct"


def test_a_stage_left_by_an_exception_records_nothing():
    h = REGISTRY.get_histogram(METRIC_QUERY_STAGE, path="direct", stage="boom")
    assert h is None
    with pytest.raises(ValueError):
        with tracing.stage("boom", "direct"):
            raise ValueError("x")
    assert REGISTRY.get_histogram(METRIC_QUERY_STAGE, path="direct",
                                  stage="boom") is None
    assert getattr(tracing._LOCAL, "stage", None) is None


def test_a_groupby_lands_on_the_direct_path_with_a_drain_record(served):
    """Served over HTTP with ?profile=1: the plan op names a device
    path, the direct path's stages and the executor's two wrap the one
    program, and the drain record counts the grouped rows' planes and
    the filter's (f's 4 rows + v's 8 planes and not-null = 13)."""
    eng, api, uri = served

    def n(path, stage):
        h = REGISTRY.get_histogram(METRIC_QUERY_STAGE, path=path, stage=stage)
        return h.count if h is not None else 0

    stages = ("group_rows", "execute", "lower", "dispatch", "device_get",
              "decode", "group_decode")
    eng.batcher()._last_fused = float("-inf")  # a fused drain just ran: not a hot pipe
    before = [n("direct", s) for s in stages]
    drains = _drain_counters("GroupBy", "group")
    doc = _post(uri, b"GroupBy(Rows(field=f), filter=Range(v > 3))",
                path="/index/i/query?profile=1")
    ops = [op for op in doc["plan"]["ops"] if "path" in op]
    assert [(op["op"], op["path"], op["groups"]) for op in ops] == [("GroupBy", "direct", 4)]
    assert sum(g["count"] for g in doc["results"][0]) > 0
    assert [n("direct", s) - b for s, b in zip(stages, before)] == [1] * len(stages)
    rose = [a - b for a, b in zip(_drain_counters("GroupBy", "group"), drains)]
    assert rose == [1, 1, 1, 13 * SHARDS * PLANE, 13 * SHARDS * PLANE, 1]
    root = _finished(api.tracer, doc["traceID"])
    assert {"group_rows", "group_decode", "execute"} <= _stage_names(root)
    _assert_tiles(root)

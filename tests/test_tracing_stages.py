"""The stage clock (util/tracing.py stage/waited): every path's stages
under one trace id, the drain record's counters, profiler annotations
only during a capture, the legacy pipeline series unchanged, the
in-flight union, the HTTP layer's stages and their closure
(``unstaged``), and the collector's clock."""

import gc
import glob
import itertools
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
    GC_GENERATIONS,
    METRIC_ENGINE_DRAIN_EVALUATED,
    METRIC_ENGINE_DRAIN_PLANE_BYTES,
    METRIC_ENGINE_DRAIN_REQUESTS,
    METRIC_ENGINE_DRAIN_SLOTS,
    METRIC_ENGINE_DRAINS,
    METRIC_GC_PAUSE,
    METRIC_HTTP_OCCUPIED,
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


@pytest.fixture(scope="module")
def threaded(served):
    """The same API behind the threaded server (no reactor)."""
    from pilosa_tpu.net import serve

    _eng, api, _uri = served
    srv, _thread = serve(api, port=0, backend="threaded")
    yield f"http://localhost:{srv.server_address[1]}"
    srv.shutdown()
    srv.server_close()


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

# The HTTP layer's stages bracket api.Query: they begin before the span
# does or end after it (util/tracing.RequestClock).
BRACKET = {"pipeline.http_read", "pipeline.prologue", "pipeline.epilogue",
           "pipeline.complete_wait", "pipeline.respond"}


def _assert_tiles(span):
    """Children lie inside their parent, do not overlap each other, and
    leave the parent a self time >= 0 — at every level of the tree."""
    kids = sorted((c for c in span.children if c.name not in BRACKET),
                  key=lambda c: c.start)
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
        tracing.settle()  # ... and its stages are recorded where somebody waits or reads
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


@pytest.fixture(scope="module")
def capture(served, tmp_path_factory):
    """One profiler capture in which two clients run rounds of load
    until every annotation the tests below read has been made inside it,
    and then one round more, so that each has closed and device work
    follows the last: a full collector pass planted inside a request's
    parse, the drain's window held open by a hot pipe, the reply's
    wake-up.  (trace dir, planes' lines, pilosa.* events)."""
    from jax.profiler import ProfileData

    eng, _api, uri = served
    batcher = eng.batcher()
    # (annotation, the wait it is written with; None: any)
    wanted = {(name, None) for name in ANNOTATED} | {
        ("accum_tail", None), ("lower", None), ("dispatch", None),
        ("device_get", None), ("write", "respond_wake")}
    seen = set()
    plant, planted = threading.Event(), threading.Event()
    rounds = itertools.count()
    annotation, parse = tracing._annotation, pql.parse

    def recording(name, path, tags):
        seen.add((name, tags.get("waited")))
        return annotation(name, path, tags)

    def collecting(text):
        if plant.is_set():
            plant.clear()
            gc.collect()
            if tracing.capturing:
                planted.set()
        return parse(text)

    def covered():
        made = list(seen)
        return planted.is_set() and all(
            any(n == name and waited in (None, w) for n, w in made)
            for name, waited in wanted)

    def client(k, sums):
        # First-seen texts (a parse each): the row pair and a bound move.
        _post(uri, (f"Count(Intersect(Row(f=10), Row(f={10 + k % 4}), "
                    f"Range(v > {k % 250})))").encode())
        if sums:
            _post(uri, f"Sum(Range(v > {k % 250}), field=v)".encode())

    def run_rounds(done):
        deadline = time.monotonic() + 60
        while not tracing.capturing and time.monotonic() < deadline:
            time.sleep(0.001)
        first = True
        while tracing.capturing:
            last = covered()
            if not (first or planted.is_set()):
                plant.set()  # after a round: device work on both sides of it
            first = False
            batcher._last_fused = time.monotonic()  # a hot pipe: the window opens
            # Texts no earlier capture sent: no memo answers them.
            k = next(rounds)
            clients = [threading.Thread(target=client, args=(2 * k + n, n == 0))
                       for n in range(2)]
            for c in clients:
                c.start()
            for c in clients:
                c.join(60)
            if last and tracing.capturing:
                done.set()
                return

    tracing._annotation, pql.parse = recording, collecting
    try:
        # A starved host (the suite's other workers) can end the capture
        # before the rounds have made every annotation: take another.
        for attempt in range(3):
            seen.clear()
            plant.clear()
            planted.clear()
            done = threading.Event()
            loader = threading.Thread(target=run_rounds, args=(done,), daemon=True)
            loader.start()
            out = tmp_path_factory.mktemp("capture") / str(attempt)
            doc = _post(uri, b"", path=f"/debug/pprof/trace?seconds=2&dir={out}",
                        timeout=120)
            assert doc["python"] is False and tracing.capturing is False
            loader.join(120)
            if done.is_set():
                break
    finally:
        plant.clear()
        tracing._annotation, pql.parse = annotation, parse
    (pb,) = glob.glob(os.path.join(str(out), "plugins", "profile", "*", "*.xplane.pb"))
    data = ProfileData.from_file(pb)
    lines = [(plane.name, line) for plane in data.planes for line in plane.lines]
    events = [(line.name, ev) for _, line in lines for ev in line.events
              if ev.name.startswith("pilosa.")]
    return str(out), lines, events


def test_capture_holds_stage_annotations_and_no_python_tracer(capture):
    _out, lines, events = capture
    # The Python tracer's events are named "$file:line function".
    assert not any(ev.name.startswith("$") for _, line in lines
                   for ev in line.events)
    # ... and the stages run on named threads, none on a line that only
    # carries the process's name (a collector pass runs where it falls).
    assert {name for name, ev in events if ev.name != "pilosa.gc"} <= {
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


# mark or stage -> the thread it is an annotation of while a capture runs
ANNOTATED = {
    "read": "http-reactor-0", "handoff": "http-reactor-0", "write": "http-reactor-0",
    "select_wait": "http-reactor-0", "prologue": "http-reactor-0",
    "accum_wait": "pq-drain", "complete": "pq-collect", "encode": "pq-collect",
    "epilogue": "http-pool",
}


@pytest.mark.parametrize("name", list(ANNOTATED))
def test_capture_holds_the_marks_of_waits_and_of_the_http_layer(capture, name):
    _out, _lines, events = capture
    on = {line for line, ev in events if ev.name == "pilosa." + name}
    assert any(line.startswith(ANNOTATED[name]) for line in on), (name, on)
    stats = [dict(ev.stats) for _, ev in events if ev.name == "pilosa." + name]
    tags = {"select_wait": {"open", "writing"}, "accum_wait": {"queued"},
            "handoff": {"route"}, "complete": {"batch"}}.get(name, set())
    assert all(tags <= set(s) for s in stats), stats[:3]


def test_trace_gaps_names_the_owners_of_a_cpu_capture(capture):
    """scripts/trace_gaps.py on the capture (the host plane's XLA
    executions in the device's place): the new stages and waits are
    listed, the planted pass is an owner named gc, and work, waits,
    client and unowned add up to the idle time."""
    import importlib.util

    spec = importlib.util.spec_from_file_location("trace_gaps", os.path.join(
        os.path.dirname(__file__), "..", "scripts", "trace_gaps.py"))
    trace_gaps = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(trace_gaps)
    out, _lines, _events = capture
    doc = trace_gaps.report(out, allow_host=True)
    listed = {row["stage"].split(":")[0] for row in doc["stages"]}
    assert {"read", "handoff", "complete", "write", "accum_wait", "select_wait",
            "gc"} <= listed, listed
    tot = doc["totals"]
    assert any(row["stage"] == "gc" for row in tot["work"]), tot["work"]
    assert all(trace_gaps._is_wait(row["stage"]) for row in tot["waits"])
    assert {"respond_wake:wait", "accum_wait"} <= {
        row["stage"] for row in doc["stages"]}, doc["stages"]
    owned = (sum(r["seconds"] for r in tot["work"]) + sum(r["seconds"] for r in tot["waits"])
             + tot["client"]["seconds"] + tot["unowned"]["seconds"])
    assert owned == pytest.approx(tot["idle_s"], rel=1e-9)
    assert tot["idle_s"] > 0 and tot["gaps"] >= len(doc["gaps"])
    json.dumps(doc)  # --json carries the same


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


# -- the HTTP layer's stages and their closure -------------------------------

PATHS = ("deferred", "direct", "solo", "fused", "host")
REACTOR_ONLY = ("read", "handoff", "respond_wake")
EVERY_REQUEST = ("http_read", "prologue", "encode", "write", "respond", "unstaged")


def _hist(name, **labels):
    h = REGISTRY.get_histogram(name, **labels)
    return (h.count, h.sum) if h is not None else (0, 0.0)


def _stage_deltas(path, names, before=None):
    now = {n: _hist(METRIC_QUERY_STAGE, path=path, stage=n) for n in names}
    now["http"] = _hist(METRIC_HTTP_REQUEST)
    if before is None:
        return now
    return {n: (now[n][0] - before[n][0], now[n][1] - before[n][1]) for n in now}


@pytest.fixture
def clocks(monkeypatch):
    """Every RequestClock whose stages are recorded, after they are."""
    done = []
    record = tracing.RequestClock._record

    def spy(self):
        record(self)
        done.append(self)

    monkeypatch.setattr(tracing.RequestClock, "_record", spy)
    return done


def _clock_of(clocks, trace_id, timeout=15):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        tracing.settle()  # as a scrape would
        for c in clocks:
            if c.span is not None and c.span.trace_id == trace_id:
                return c
        time.sleep(0.01)
    raise AssertionError(f"the clock of trace {trace_id} never finished")


def _across_paths(stage):
    """(count, sum) of one stage over every path."""
    return tuple(map(sum, zip(*(_hist(METRIC_QUERY_STAGE, path=p, stage=stage)
                                for p in PATHS))))


def _union(intervals):
    total, end = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b > end:
            total += b - max(a, end)
            end = b
    return total


# kind -> (request body with {a} {b} two rows of f, the path it takes,
#          whether a drain answers it)
REQUESTS = {
    "deferred_count": ("Count(Xor(Row(f={a}), Row(f={b})))", "deferred", True),
    "direct_three_sums": ("Sum(Xor(Row(f={a}), Row(f={b})), field=v)"
                          "Sum(Union(Row(f={a}), Row(f={b})), field=v)"
                          "Sum(Difference(Row(f={a}), Row(f={b})), field=v)", "direct", False),
    "groupby": ("GroupBy(Rows(field=f), filter=Xor(Row(f={a}), Row(f={b})))", "direct", False),
    "memo_hit": ("Count(Xor(Row(f={a}), Row(f={b})))", "host", False),
}


@pytest.mark.parametrize("backend", ["async", "threaded"])
@pytest.mark.parametrize("kind", list(REQUESTS))
def test_the_stages_close_over_the_request(served, threaded, clocks, backend, kind):
    """Σ top-level stages + unstaged = the request's
    pilosa_http_request_seconds observation; read + handoff = http_read;
    encode + respond_wake + write = respond; each observed once under
    the request's path, the reactor's three only behind the reactor.
    The text is parsed once, and the executor entered once more where
    the deferred path declined it (its one attempt), on either server."""
    eng, api, uri = served
    uri = uri if backend == "async" else threaded
    text, path, drained = REQUESTS[kind]
    # Rows no other request of this file combines this way; one pair a backend.
    body = text.format(a=12, b=13 if backend == "async" else 11).encode()
    eng.batcher()._last_fused = float("-inf")  # not a hot pipe: a lone run goes direct
    names = EVERY_REQUEST + REACTOR_ONLY + ("complete_wait", "epilogue", "complete")
    if kind == "memo_hit":
        # The text deferred_count sent (first here, where that case was deselected).
        doc = _post(uri, body)
        _clock_of(clocks, doc["traceID"])
    before = _stage_deltas(path, names)
    entered = [_across_paths(n)[0] for n in ("parse", "plan")]
    doc = _post(uri, body)
    clock = _clock_of(clocks, doc["traceID"])
    assert clock.span.tags.get("path", "host") == path
    if drained:
        # ``complete`` is the drain's, recorded when its callback loop
        # ends: the reply it holds may reach the client first.
        deadline = time.monotonic() + 15
        while (_hist(METRIC_QUERY_STAGE, path=path, stage="complete")[0]
               == before["complete"][0] and time.monotonic() < deadline):
            time.sleep(0.01)
    moved = _stage_deltas(path, names, before)
    declined = path not in ("deferred", "host")
    assert [_across_paths(n)[0] - b for n, b in zip(("parse", "plan"), entered)] == [
        1, 1 + declined]
    http_n, http_s = moved.pop("http")
    assert http_n == 1
    want = {n: 1 for n in EVERY_REQUEST}
    want.update({n: int(backend == "async") for n in REACTOR_ONLY})
    want.update(complete_wait=int(drained), complete=int(drained),
                epilogue=int(not drained))
    assert {n: c for n, (c, _s) in moved.items()} == want
    # The closure, from the trees the request's plan holds.
    trees = [(st.t0, st.t1) for st in clock.plan._stage_trees]
    assert _union(trees) + moved["unstaged"][1] == pytest.approx(http_s, abs=1e-6)
    assert {st.name for st in clock.plan._stage_trees} >= {
        "http_read", "prologue", "respond", "complete_wait" if drained else "epilogue"}
    parts = sum(moved[n][1] for n in ("encode", "respond_wake", "write"))
    assert parts == pytest.approx(moved["respond"][1], abs=1e-6)
    if backend == "async":
        assert moved["read"][1] + moved["handoff"][1] == pytest.approx(
            moved["http_read"][1], abs=1e-6)
    # ... and they ride the request's span: parts under their wholes.
    root = clock.span
    kids = {c.name: c for c in root.children}
    assert {"pipeline.http_read", "pipeline.prologue", "pipeline.respond"} <= set(kids)
    assert [c.name for c in kids["pipeline.respond"].children] == (
        ["pipeline.encode", "pipeline.respond_wake", "pipeline.write"]
        if backend == "async" else ["pipeline.encode", "pipeline.write"])
    if backend == "async":
        handoff = kids["pipeline.http_read"].children[1]
        assert handoff.name == "pipeline.handoff"
        assert handoff.tags["route"] == ("inline" if path in ("deferred", "host") else "pool")
    assert root.tags["unstaged_ms"] == pytest.approx(moved["unstaged"][1] * 1e3, abs=1e-3)


def test_a_clock_no_handler_entered_observes_no_stage():
    """Process mode: the worker's reactor makes the clock, the handler
    that would enter it lives in another process."""
    before = _hist(METRIC_HTTP_REQUEST)[0]
    series = dict(REGISTRY._hists.get(METRIC_QUERY_STAGE, {}))
    counts = {k: h.count for k, h in series.items()}
    t = time.monotonic()
    clock = tracing.RequestClock(t, t)
    assert tracing.OCCUPIED.depth >= 1
    clock.completing()
    clock.finish()
    clock.finish()  # once
    tracing.settle()
    assert _hist(METRIC_HTTP_REQUEST)[0] == before + 1
    after = REGISTRY._hists.get(METRIC_QUERY_STAGE, {})
    assert {k: h.count for k, h in after.items()} == counts


def test_occupied_is_the_union_of_the_open_clocks():
    c = Counter()
    occupied = tracing.Inflight(counter=c)
    occupied.begin(now=10.0)
    occupied.begin(now=10.5)
    assert occupied.depth == 2
    occupied.end(now=11.0)
    occupied.end(now=12.0)
    assert c.get() == pytest.approx(2.0)
    # A first byte read before the last reply's end does not count twice.
    occupied.begin(now=11.5)
    occupied.end(now=12.5)
    assert c.get() == pytest.approx(2.5)


def test_an_abandoned_request_leaves_the_server_unoccupied():
    depth = tracing.OCCUPIED.depth
    clock = tracing.RequestClock(time.monotonic())
    assert tracing.OCCUPIED.depth == depth + 1
    clock.abandon()
    clock.abandon()
    clock.finish()
    assert tracing.OCCUPIED.depth == depth


def test_occupied_seconds_rise_with_a_request(served):
    _eng, _api, uri = served
    c = REGISTRY.counter(METRIC_HTTP_OCCUPIED)
    before = c.get()
    _post(uri, b"Count(Union(Row(f=10), Row(f=12), Row(f=13)))")
    deadline = time.monotonic() + 10
    while c.get() == before and time.monotonic() < deadline:
        time.sleep(0.01)
    assert c.get() > before
    text = urllib.request.urlopen(uri + "/metrics", timeout=30).read().decode()
    assert "pilosa_http_occupied_seconds_total " in text


# -- the collector's clock ---------------------------------------------------


def test_gc_clock_is_installed_once_and_gone_with_the_last_server():
    clock = tracing.GcClock()
    assert clock._on_gc not in gc.callbacks
    clock.install()
    clock.install()
    assert gc.callbacks.count(clock._on_gc) == 1
    clock.uninstall()
    assert gc.callbacks.count(clock._on_gc) == 1
    clock.uninstall()
    assert clock._on_gc not in gc.callbacks


def test_a_server_holds_the_gc_clock_from_serve_to_server_close(served):
    from pilosa_tpu.net import serve

    _eng, api, _uri = served
    refs = tracing.GC._refs
    assert refs >= 1 and gc.callbacks.count(tracing.GC._on_gc) == 1  # the module's server
    srv, _thread = serve(api, port=0)
    try:
        assert tracing.GC._refs == refs + 1
        assert gc.callbacks.count(tracing.GC._on_gc) == 1
    finally:
        srv.shutdown()  # ends in server_close
    srv.server_close()  # a second close takes nothing more
    assert tracing.GC._refs == refs
    for g in GC_GENERATIONS:
        assert REGISTRY.get_histogram(METRIC_GC_PAUSE, generation=str(g)) is not None


@pytest.mark.parametrize("generation", GC_GENERATIONS)
def test_a_forced_pass_inside_a_request_is_counted_and_tagged(
        served, monkeypatch, generation):
    """gc.collect(g) inside the parse stage: one more observation of
    generation g; a full pass leaves gc_ms on that stage's span."""
    _eng, api, uri = served
    parse = pql.parse
    made = []
    monkeypatch.setattr(tracing, "_annotation",
                        lambda *a, **k: made.append(a) or pytest.fail("made"))

    def collecting(text):
        gc.collect(generation)
        return parse(text)

    monkeypatch.setattr(pql, "parse", collecting)
    h = REGISTRY.get_histogram(METRIC_GC_PAUSE, generation=str(generation))
    gc.disable()  # no pass of its own making between the two readings
    try:
        tracing.GC.flush()
        before = h.count
        doc = _post(uri, f"Count(Intersect(Row(f=1{generation}), Row(f=13), Row(f=10)))".encode())
        root = _finished(api.tracer, doc["traceID"])
        tracing.GC.flush()
        after = h.count
    finally:
        gc.enable()
    assert after == before + 1
    (parsed,) = [s for s in _walk(root) if s.name == "pipeline.parse"]
    assert parsed.tags["cache"] == "miss"
    assert ("gc_ms" in parsed.tags) == (generation == 2)
    assert made == []  # no capture: no annotation for the pass either


@pytest.mark.parametrize("backend", ["async", "threaded"])
def test_no_annotation_on_a_request_without_a_capture(served, threaded, monkeypatch, backend):
    """Every mark and stage of a request's path, on both servers."""
    _eng, _api, uri = served
    uri = uri if backend == "async" else threaded
    made = []
    monkeypatch.setattr(tracing, "_annotation",
                        lambda *a, **k: made.append(a) or pytest.fail("made"))
    assert tracing.capturing is False
    _post(uri, b"Count(Difference(Row(f=11), Row(f=13)))")
    _post(uri, b"Sum(Difference(Row(f=13), Row(f=10)), field=v)")
    _post(uri, b"GroupBy(Rows(field=f), filter=Row(f=13))")
    assert tracing.mark("select_wait", open=0) is tracing.mark("accum_wait", queued=1)
    assert made == []


def test_finish_stops_the_clock_and_settle_records_the_stages():
    """The writer's thread only stops the clock; the stages are recorded
    by whoever waits for the device or reads them next, once, and by the
    finishing thread itself once SETTLE_AT requests have piled up."""
    tracing.settle()
    t = Tracer()

    def request():
        now = time.monotonic()
        clock = tracing.RequestClock(now, now)
        clock.handler_entered()
        clock.span = t.begin("api.Query")
        clock.span.tags["path"] = "settled"
        clock.executing()
        clock.executed()
        with tracing.encoding(clock):
            pass
        clock.completing()
        clock.finish()
        return clock

    n = lambda stage: _hist(METRIC_QUERY_STAGE, path="settled", stage=stage)[0]
    http = _hist(METRIC_HTTP_REQUEST)[0]
    clock = request()
    assert _hist(METRIC_HTTP_REQUEST)[0] == http + 1 and clock.t_done is not None
    assert n("respond") == 0 and "http_ms" not in clock.span.tags
    tracing.settle()
    tracing.settle()
    assert [n(s) for s in ("http_read", "read", "handoff", "prologue", "epilogue",
                           "respond", "encode", "respond_wake", "write", "unstaged")] == [1] * 10
    assert {"http_ms", "unstaged_ms"} <= set(clock.span.tags)
    for _ in range(tracing.SETTLE_AT):
        request()
    assert n("respond") == 1 + tracing.SETTLE_AT and not tracing._FINISHED

"""Pipelined multi-batch execution (round-6 tentpole): the
stage-decoupled CountBatcher keeps multiple fused batches genuinely in
flight; the executor/API/HTTP layers thread result futures through so
completion callbacks — not parked handler threads — resolve pending
responses; responses on a pipelined connection stay in request order;
mixed read+write streams stay correct.  Plus regressions for the
round-6 satellite fixes: _signature literal-only masking, resize
membership-before-NORMAL ordering, and join/leave queued during an
active resize job."""

import json
import socket
import threading
import time

import numpy as np
import pytest

from pilosa_tpu import pql
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.executor import Executor
from pilosa_tpu.ops import SHARD_WIDTH
from pilosa_tpu.parallel import MeshEngine, make_mesh
from pilosa_tpu.parallel.batcher import CountBatcher


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(8)


@pytest.fixture
def holder():
    h = Holder()
    h.open()
    idx = h.create_index("i")
    f = idx.create_field("f")
    ef = idx.existence_field()
    rows, cols = [], []
    rng = np.random.default_rng(7)
    for s in range(8):
        base = s * SHARD_WIDTH
        picks = rng.choice(SHARD_WIDTH, size=300, replace=False)
        for c in picks[:200]:
            rows.append(10)
            cols.append(base + int(c))
        for c in picks[100:]:
            rows.append(11)
            cols.append(base + int(c))
    f.import_bulk(rows, cols)
    ef.import_bulk([0] * len(cols), cols)
    return h


def _call(q):
    return pql.parse(q).calls[0]


# -- stage-decoupled pipeline: batches in flight ---------------------------


class _SlowDev:
    """A fake device future whose host readback blocks until the stub
    engine's release gate opens — models a batch executing on device /
    in the readback transport."""

    def __init__(self, eng, values):
        self._eng = eng
        self._values = values

    def __array__(self, dtype=None):
        self._eng.release.wait(30)
        with self._eng.lock:
            self._eng.unread -= 1
        return np.asarray(self._values, dtype=dtype or np.int32)


class _StubEngine:
    """count_many_async returns instantly (the dispatch stage never
    waits on the device); readbacks block until ``release`` opens, so
    the test can observe how many batches the pipeline keeps in flight."""

    def __init__(self):
        self.lock = threading.Lock()
        self.release = threading.Event()
        self.unread = 0
        self.max_unread = 0
        self.dispatched_groups = []

    def count_many_async(self, index, calls, shards_list):
        with self.lock:
            self.unread += 1
            self.max_unread = max(self.max_unread, self.unread)
        self.dispatched_groups.append([str(c) for c in calls])
        # Answer = the row id queried, so correctness is checkable.
        vals = [int(str(c).split("=")[1].rstrip(")")) for c in calls]
        return _SlowDev(self, vals)

    def count(self, index, call, shards):
        return int(str(call).split("=")[1].rstrip(")"))


def test_two_batches_genuinely_in_flight():
    """Device execution (an unread readback) of batch k overlaps both
    the DISPATCH of batch k+1 and the ACCUMULATION of batch k+2 — the
    round-6 pipeline guarantee (round 5 ran one batch at a time)."""
    eng = _StubEngine()
    b = CountBatcher(eng, max_inflight=4)
    # Distinct field names -> distinct structure signatures -> one
    # group (= one fused batch) each.
    wave1 = [b.submit_async("i", _call(f"Row(f{k}=5)"), [0]) for k in range(2)]
    deadline = time.monotonic() + 10
    while eng.unread < 2 and time.monotonic() < deadline:
        time.sleep(0.005)
    assert eng.unread >= 2, "second batch did not dispatch while first unread"
    # Accumulation keeps accepting while both batches are on "device":
    # a third group dispatches too (depth 4 > 2 in flight).
    wave2 = b.submit_async("i", _call("Row(f9=7)"), [0])
    deadline = time.monotonic() + 10
    while eng.unread < 3 and time.monotonic() < deadline:
        time.sleep(0.005)
    assert eng.unread >= 3
    eng.release.set()
    for it in wave1 + [wave2]:
        assert it.event.wait(30)
        assert it.error is None
    assert wave1[0].result == 5 and wave2.result == 7
    assert eng.max_unread >= 3
    snap = b.pipeline_snapshot()
    assert snap["gauges"]["inflight_max"] >= 3
    assert snap["depth"] == 4
    assert {"queue_wait", "lower_dispatch", "device_readback"} <= set(
        snap["stages"]
    )


def test_inflight_depth_is_bounded():
    """The dispatch stage blocks on the (depth+1)'th batch: with depth 2
    and 4 distinct groups queued, at most 2 are ever unread at once."""
    eng = _StubEngine()
    b = CountBatcher(eng, max_inflight=2)
    items = [
        b.submit_async("i", _call(f"Row(g{k}={k})"), [0]) for k in range(4)
    ]
    deadline = time.monotonic() + 10
    while eng.unread < 2 and time.monotonic() < deadline:
        time.sleep(0.005)
    time.sleep(0.25)  # give an over-eager dispatcher time to violate
    assert eng.max_unread <= 2, "pipeline exceeded its configured depth"
    eng.release.set()
    for k, it in enumerate(items):
        assert it.event.wait(30) and it.error is None
        assert it.result == k
    assert b.pipeline_snapshot()["gauges"]["inflight_max"] <= 2


# -- signature regression (satellite: literal-only masking) ----------------


def test_signature_masks_only_argument_literals():
    sig = CountBatcher._signature
    # Digit runs inside IDENTIFIERS are structure: f1 and f2 are
    # different fields with different stacks and must not share a group.
    assert sig("i", _call("Row(f1=3)")) != sig("i", _call("Row(f2=3)"))
    # Literals in argument position are data: same program structure.
    assert sig("i", _call("Row(f1=3)")) == sig("i", _call("Row(f1=4)"))
    assert sig("i", _call("Row(f=3)")) == sig("i", _call("Row(f=999)"))
    assert sig("i", _call("Intersect(Row(f=10), Row(f=11))")) == sig(
        "i", _call("Intersect(Row(f=3), Row(f=4))")
    )
    # BSI conditions mask their bound values too.
    assert sig("i", _call("Range(v > 300)")) == sig("i", _call("Range(v > 7)"))
    # Timestamp literals are program structure (view cover), not data.
    assert sig(
        "i", _call("Range(t=7, 2018-01-01T00:00, 2018-04-01T00:00)")
    ) != sig("i", _call("Range(t=7, 2018-01-01T00:00, 2018-02-01T00:00)"))


def test_digit_field_batches_fuse_correctly(holder, mesh):
    """End-to-end: digit-bearing field names group separately but still
    answer correctly through the batcher."""
    idx = holder.index("i")
    f1 = idx.create_field("f1")
    f1.import_bulk([3] * 50, list(range(50)))
    f2 = idx.create_field("f2")
    f2.import_bulk([3] * 20, list(range(0, 200, 10)))
    eng = MeshEngine(holder, mesh)
    b = eng.batcher()
    shards = list(range(8))
    items = [
        b.submit_async("i", _call("Row(f1=3)"), shards),
        b.submit_async("i", _call("Row(f2=3)"), shards),
    ]
    for it in items:
        assert it.event.wait(60) and it.error is None
    assert items[0].result == 50
    assert items[1].result == 20


# -- executor/API futures ---------------------------------------------------


def test_execute_async_matches_sync(holder, mesh):
    eng = MeshEngine(holder, mesh)
    ex = Executor(holder, mesh_engine=eng)
    multi = (
        "Count(Row(f=10))"
        "Count(Intersect(Row(f=10), Row(f=11)))"
        "Count(Union(Row(f=10), Row(f=11)))"
    )
    want = ex.execute("i", multi).results
    fut = ex.execute_async("i", multi)
    assert fut is not None
    assert fut.result(60).results == want


def test_execute_async_declines_non_count(holder, mesh):
    eng = MeshEngine(holder, mesh)
    ex = Executor(holder, mesh_engine=eng)
    assert ex.execute_async("i", "TopN(f, n=2)") is None
    assert ex.execute_async("i", "Set(1, f=10)") is None
    assert ex.execute_async("i", "Count(Row(f=10))Set(1, f=10)") is None
    plain = Executor(holder)  # no mesh engine: nothing to pipeline
    assert plain.execute_async("i", "Count(Row(f=10))") is None


def test_execute_async_error_converges_to_sync(holder, mesh):
    """An async item that fails at lower time falls back to the sync
    path, so both paths surface the SAME outcome (here: the host path's
    field-not-found error, not a pipeline-internal one)."""
    eng = MeshEngine(holder, mesh)
    ex = Executor(holder, mesh_engine=eng)
    q = "Count(Intersect(Row(f=10), Row(missingfield=1)))"
    try:
        ex.execute("i", q)
        sync_err = None
    except Exception as e:  # noqa: BLE001
        sync_err = type(e)
    fut = ex.execute_async("i", q)
    assert fut is not None
    if sync_err is None:
        fut.result(60)
    else:
        with pytest.raises(sync_err):
            fut.result(60)


def test_execute_async_callback_fires(holder, mesh):
    eng = MeshEngine(holder, mesh)
    ex = Executor(holder, mesh_engine=eng)
    fired = threading.Event()
    out = []
    fut = ex.execute_async("i", "Count(Row(f=10))")
    fut.add_done_callback(lambda f: (out.append(f.result(0).results), fired.set()))
    assert fired.wait(60)
    assert out[0] == ex.execute("i", "Count(Row(f=10))").results


# -- mixed read+write streams ----------------------------------------------


def test_mixed_read_write_stream_stays_correct(holder, mesh):
    """A writer adds bits while a reader streams deferred Counts: every
    observed count is monotone nondecreasing (adds only — the engine's
    dispatch lock orders scatter-sync against batched dispatch), and
    the quiesced pipeline answer equals the host executor's."""
    idx = holder.index("i")
    f = idx.field("f")
    eng = MeshEngine(holder, mesh)
    ex = Executor(holder, mesh_engine=eng)
    q = "Count(Union(Row(f=10), Row(f=11)))"
    base = ex.execute_async("i", q).result(60).results[0]

    stop = threading.Event()
    errors, seen = [], []

    def writer():
        try:
            n = 0
            while not stop.is_set() and n < 40:
                n += 1
                cols = [
                    s * SHARD_WIDTH + 5000 + (n * 13 + s) % 3000
                    for s in range(8)
                ]
                f.import_bulk([10] * len(cols), cols)
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    def reader():
        try:
            while not stop.is_set():
                fut = ex.execute_async("i", q)
                assert fut is not None
                seen.append(fut.result(60).results[0])
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    w = threading.Thread(target=writer)
    r = threading.Thread(target=reader)
    w.start()
    r.start()
    w.join(60)
    time.sleep(0.1)
    stop.set()
    r.join(60)
    assert not w.is_alive() and not r.is_alive(), "worker deadlocked"
    assert not errors, errors
    assert seen and seen[0] >= base
    for a, b in zip(seen, seen[1:]):
        assert b >= a, (a, b)
    plain = Executor(holder)
    assert (
        ex.execute_async("i", q).result(60).results
        == plain.execute("i", q).results
    )


# -- HTTP deferral ----------------------------------------------------------


def _serve(holder, mesh):
    from pilosa_tpu.api import API
    from pilosa_tpu.net import serve

    eng = MeshEngine(holder, mesh)
    api = API(holder=holder, mesh_engine=eng)
    srv, _thread = serve(api, port=0)
    return eng, api, srv


def test_http_deferred_counts_resolve_and_report(holder, mesh):
    """Concurrent HTTP Counts ride the deferred path: correct answers,
    fused batches, and pipeline telemetry visible at /debug/vars."""
    import urllib.request

    eng, api, srv = _serve(holder, mesh)
    uri = f"http://localhost:{srv.server_address[1]}"
    try:
        q = b"Count(Intersect(Row(f=10), Row(f=11)))"

        def once():
            req = urllib.request.Request(
                f"{uri}/index/i/query", data=q, method="POST"
            )
            return json.loads(
                urllib.request.urlopen(req, timeout=60).read()
            )["results"][0]

        want = once()
        results, errs = [], []

        def client():
            try:
                for _ in range(4):
                    results.append(once())
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        threads = [threading.Thread(target=client) for _ in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert not errs
        assert len(results) == 48 and set(results) == {want}
        assert eng._batcher is not None and eng._batcher.batches > 0
        dbg = json.loads(
            urllib.request.urlopen(f"{uri}/debug/vars", timeout=30).read()
        )
        assert "pipeline" in dbg
        assert dbg["pipeline"]["batchedQueries"] > 0
        assert dbg["pipeline"]["depth"] >= 1
    finally:
        srv.shutdown()


def test_http_pipelined_connection_keeps_order(holder, mesh):
    """SIX requests sent back-to-back on ONE connection before reading:
    deferred Counts interleaved with synchronous routes come back in
    request order with the right bodies (the per-connection response
    sequencer), proving the handler thread is free to read pipelined
    requests while earlier queries are still on device."""
    eng, api, srv = _serve(holder, mesh)
    port = srv.server_address[1]
    try:
        count_q = b"Count(Row(f=10))"
        want = api.query(
            __import__(
                "pilosa_tpu.api", fromlist=["QueryRequest"]
            ).QueryRequest("i", count_q.decode())
        ).results[0]

        def post(body):
            return (
                b"POST /index/i/query HTTP/1.1\r\nHost: l\r\n"
                b"Content-Type: application/json\r\n"
                b"Content-Length: " + str(len(body)).encode() + b"\r\n\r\n"
                + body
            )

        get_version = b"GET /version HTTP/1.1\r\nHost: l\r\n\r\n"
        reqs = [post(count_q), get_version, post(count_q), post(count_q),
                get_version, post(count_q)]
        s = socket.create_connection(("localhost", port), timeout=60)
        try:
            s.sendall(b"".join(reqs))
            fh = s.makefile("rb")
            bodies = []
            for _ in reqs:
                line = fh.readline()
                assert line.startswith(b"HTTP/1.1 200"), line
                clen = 0
                while True:
                    h = fh.readline()
                    if h in (b"\r\n", b""):
                        break
                    if h.lower().startswith(b"content-length:"):
                        clen = int(h.split(b":")[1])
                bodies.append(json.loads(fh.read(clen)))
        finally:
            s.close()
        assert [b.get("results", [None])[0] for b in bodies] == [
            want, None, want, want, None, want
        ]
        assert "version" in bodies[1] and "version" in bodies[4]
    finally:
        srv.shutdown()


# -- resize satellite regressions -------------------------------------------


class _RecordingClient:
    """Cluster client stub: records every broadcast with the sender's
    membership + state AT SEND TIME (the ordering under test)."""

    def __init__(self, cluster_ref, log):
        self._cluster_ref = cluster_ref
        self._log = log

    def send_message(self, msg):
        c = self._cluster_ref[0]
        self._log.append(
            (msg.get("type"), sorted(n.id for n in c.nodes), c.state)
        )


def _make_cluster(tmp_path, log):
    from pilosa_tpu.cluster.cluster import Cluster, Node

    holder = Holder()
    holder.open()
    idx = holder.create_index("i")
    f = idx.create_field("f")
    rows, cols = [], []
    for s in range(8):
        rows.append(1)
        cols.append(s * SHARD_WIDTH)
    f.import_bulk(rows, cols)
    ref = []
    c = Cluster(
        Node("n1", "http://n1", is_coordinator=True),
        path=str(tmp_path / "topology"),
        client_factory=lambda uri: _RecordingClient(ref, log),
    )
    ref.append(c)
    c.holder = holder
    c.state = "NORMAL"
    return c


def test_resize_applies_membership_before_normal(tmp_path, monkeypatch):
    """On a successful join resize the membership change + node-status
    broadcast land BEFORE the set-state NORMAL broadcast: a peer must
    never observe NORMAL while still holding the pre-resize topology
    (the lost-write window)."""
    from pilosa_tpu.cluster.cluster import Cluster, Node

    log = []
    c = _make_cluster(tmp_path, log)

    def deliver(self, node, ins):
        self.mark_resize_complete({"jobId": ins["jobId"], "node": ins["node"]})
        return True

    monkeypatch.setattr(Cluster, "_deliver_instruction", deliver)
    c.add_node(Node("n2", "http://n2"))
    assert [n.id for n in c.nodes] == ["n1", "n2"]
    assert c.state == "NORMAL"
    types = [t for t, _m, _s in log]
    assert "node-status" in types and "set-state" in types
    status_i = types.index("node-status")
    normal_i = max(
        i for i, (t, _m, s) in enumerate(log)
        if t == "set-state" and s != "RESIZING"
    )
    assert status_i < normal_i, log
    # At node-status time the joiner was already a member and the
    # cluster had NOT yet left RESIZING.
    _t, members, state = log[status_i]
    assert members == ["n1", "n2"]
    assert state == "RESIZING"


def test_join_during_resize_is_queued_not_dropped(tmp_path, monkeypatch):
    """A join arriving while a resize job is running queues and lands
    once the job finishes (round-6 satellite: it was silently dropped)."""
    from pilosa_tpu.cluster.cluster import Cluster, Node

    log = []
    c = _make_cluster(tmp_path, log)
    gate = threading.Event()
    first = threading.Event()

    def deliver(self, node, ins):
        if not first.is_set():
            first.set()
            gate.wait(30)
        self.mark_resize_complete({"jobId": ins["jobId"], "node": ins["node"]})
        return True

    monkeypatch.setattr(Cluster, "_deliver_instruction", deliver)
    t = threading.Thread(target=lambda: c.add_node(Node("n2", "http://n2")))
    t.start()
    assert first.wait(30), "first resize never delivered its instruction"
    # Second join arrives mid-job: must queue, not vanish.
    c.add_node(Node("n3", "http://n3"))
    assert c.node_by_id("n3") is None  # not yet — job 1 still running
    assert c._pending_node_actions, "join was dropped, not queued"
    gate.set()
    t.join(30)
    deadline = time.monotonic() + 30
    while c.node_by_id("n3") is None and time.monotonic() < deadline:
        time.sleep(0.02)
    assert c.node_by_id("n3") is not None, "queued join never landed"
    assert [n.id for n in c.nodes] == ["n1", "n2", "n3"]
    # Membership lands while job 2 is still RESIZING (by design); the
    # job's epilogue restores NORMAL moments later.
    while c.state != "NORMAL" and time.monotonic() < deadline:
        time.sleep(0.02)
    assert c.state == "NORMAL"

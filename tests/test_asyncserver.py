"""Event-loop serving tier (net/aserver.py + net/admission.py): HTTP
edge cases the reactor must get right — pipelining with mid-stream
errors, slow-loris read timeouts, oversized-body rejection, keep-alive
semantics — plus admission control (tenant fairness under a hog,
queue-full shedding) and the tentpole's observable win: cross-connection
batch coalescing."""

import json
import socket
import threading
import time

import numpy as np
import pytest

from pilosa_tpu.api import API
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.net import serve
from pilosa_tpu.net.admission import AdmissionController
from pilosa_tpu.net.aserver import AsyncHTTPServer
from pilosa_tpu.ops import SHARD_WIDTH
from pilosa_tpu.parallel import MeshEngine, make_mesh


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(8)


N_ROWS = 18  # rows 10..27: enough distinct Intersect pairs to dodge the
# result memo in the coalescing test (a repeated identical Count is
# memo-served and never reaches the batcher — correct, but not what
# that test measures).


def _holder():
    h = Holder()
    h.open()
    idx = h.create_index("i")
    f = idx.create_field("f")
    rows, cols = [], []
    rng = np.random.default_rng(11)
    for s in range(8):
        base = s * SHARD_WIDTH
        for r in range(10, 10 + N_ROWS):
            picks = rng.choice(SHARD_WIDTH, size=64, replace=False)
            for c in picks:
                rows.append(r)
                cols.append(base + int(c))
    f.import_bulk(rows, cols)
    return h


def _post(body, path=b"/index/i/query", extra=b""):
    return (
        b"POST " + path + b" HTTP/1.1\r\nHost: l\r\n" + extra
        + b"Content-Length: " + str(len(body)).encode() + b"\r\n\r\n" + body
    )


def _read_response(fh):
    """(status, headers dict, body bytes) off a buffered reader."""
    line = fh.readline()
    if not line:
        return None, {}, b""
    status = int(line.split()[1])
    headers = {}
    clen = 0
    while True:
        h = fh.readline()
        if h in (b"\r\n", b""):
            break
        k, _, v = h.decode().partition(":")
        headers[k.strip().lower()] = v.strip()
        if k.strip().lower() == "content-length":
            clen = int(v)
    return status, headers, fh.read(clen)


class _GateHandler:
    """Stub route table: every request parks on ``gate`` (a blocking
    'engine'), so tests control exactly how many requests are in
    flight.  No handle_async — everything routes through the worker
    pool, like a sync query or import would."""

    allowed_origins = []

    def __init__(self):
        self.gate = threading.Event()
        self.entered = threading.Semaphore(0)

    def handle(self, method, path, query, body, headers):
        self.entered.release()
        self.gate.wait(30)
        return 200, "application/json", b"{}"


def _start(srv):
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    return srv.server_address[1]


# -- HTTP edge cases --------------------------------------------------------


def test_pipelined_requests_with_mid_stream_error(mesh):
    """Three requests pipelined before reading; the middle one 404s.
    Responses come back in request order with the right statuses — an
    error must not wedge or reorder its pipelined neighbors."""
    eng = MeshEngine(_holder(), mesh)
    api = API(holder=eng.holder, mesh_engine=eng)
    srv, _ = serve(api, port=0)
    try:
        q = b"Count(Intersect(Row(f=10), Row(f=11)))"
        s = socket.create_connection(("localhost", srv.server_address[1]), timeout=30)
        s.sendall(
            _post(q)
            + _post(b"{}", path=b"/index/i/no-such-route")
            + _post(q)
        )
        fh = s.makefile("rb")
        st1, _, b1 = _read_response(fh)
        st2, _, b2 = _read_response(fh)
        st3, _, b3 = _read_response(fh)
        s.close()
        assert (st1, st2, st3) == (200, 404, 200)
        want = json.loads(b1)["results"]
        assert json.loads(b3)["results"] == want
        assert "error" in json.loads(b2)
    finally:
        srv.shutdown()


def test_slow_loris_partial_headers_hits_read_timeout():
    """A connection that dribbles half a header block and stalls is
    dropped at the read timeout — it never holds a slot, a thread, or a
    parse buffer for longer than the bound."""
    h = _GateHandler()
    h.gate.set()
    srv = AsyncHTTPServer("localhost", 0, read_timeout=0.5)
    srv.handler = h
    port = _start(srv)
    try:
        s = socket.create_connection(("localhost", port), timeout=30)
        s.sendall(b"POST /index/i/query HTTP/1.1\r\nHost: l\r\nConte")
        s.settimeout(10.0)
        t0 = time.monotonic()
        assert s.recv(1024) == b"", "slow-loris connection was not dropped"
        assert time.monotonic() - t0 < 8.0
        s.close()
        # A HEALTHY connection under the same config still serves.
        s2 = socket.create_connection(("localhost", port), timeout=30)
        s2.sendall(b"GET /x HTTP/1.1\r\nHost: l\r\n\r\n")
        st, _, _ = _read_response(s2.makefile("rb"))
        assert st == 200
        s2.close()
    finally:
        srv.shutdown()


def test_oversized_body_rejected_before_buffering():
    """A Content-Length beyond the bound answers 413 IMMEDIATELY — the
    client gets the rejection before it has sent the body, and the
    connection closes instead of reading megabytes to discard them."""
    h = _GateHandler()
    h.gate.set()
    srv = AsyncHTTPServer("localhost", 0, max_body_bytes=1024)
    srv.handler = h
    port = _start(srv)
    try:
        s = socket.create_connection(("localhost", port), timeout=30)
        s.sendall(
            b"POST /index/i/query HTTP/1.1\r\nHost: l\r\n"
            b"Content-Length: 10485760\r\n\r\n"
        )  # headers only: the 10 MB body is never sent
        fh = s.makefile("rb")
        st, headers, body = _read_response(fh)
        assert st == 413, (st, body)
        assert b"exceeds" in body
        assert fh.read(1) == b"", "connection must close after 413"
        s.close()
    finally:
        srv.shutdown()


def test_duplicate_content_length_rejected():
    """Two Content-Length headers are the request-smuggling primitive
    (RFC 7230 §3.3.3): the reactor answers 400 and closes instead of
    picking one and desyncing body framing against a front proxy."""
    h = _GateHandler()
    h.gate.set()
    srv = AsyncHTTPServer("localhost", 0)
    srv.handler = h
    port = _start(srv)
    try:
        s = socket.create_connection(("localhost", port), timeout=30)
        s.sendall(
            b"POST /x HTTP/1.1\r\nHost: l\r\n"
            b"Content-Length: 2\r\nContent-Length: 12\r\n\r\nhi"
        )
        fh = s.makefile("rb")
        st, _, body = _read_response(fh)
        assert st == 400, (st, body)
        assert b"duplicate" in body
        assert fh.read(1) == b"", "connection must close after framing error"
        s.close()
    finally:
        srv.shutdown()


def test_keep_alive_vs_connection_close(mesh):
    """HTTP/1.1 default keep-alive serves many requests on one socket;
    Connection: close answers, then closes."""
    eng = MeshEngine(_holder(), mesh)
    api = API(holder=eng.holder, mesh_engine=eng)
    srv, _ = serve(api, port=0)
    try:
        port = srv.server_address[1]
        s = socket.create_connection(("localhost", port), timeout=30)
        fh = s.makefile("rb")
        for _ in range(3):  # sequential keep-alive round trips
            s.sendall(_post(b"Count(Row(f=10))"))
            st, headers, body = _read_response(fh)
            assert st == 200
            assert "close" not in headers.get("connection", "")
        s.sendall(_post(b"Count(Row(f=10))", extra=b"Connection: close\r\n"))
        st, headers, body = _read_response(fh)
        assert st == 200
        assert headers.get("connection") == "close"
        assert fh.read(1) == b"", "server kept a Connection: close socket open"
        s.close()
    finally:
        srv.shutdown()


# -- admission control ------------------------------------------------------


def test_admission_controller_fair_share_math():
    adm = AdmissionController(max_inflight=8, fair_start=0.25,
                              weights={"gold": 3.0})
    # Below fair_start everything is admitted.
    assert adm.admit("free") is None
    # A lone tenant may fill the whole pipe (work-conserving)...
    for _ in range(7):
        assert adm.admit("free") is None
    # ...and saturating it sheds 429 on ITS OWN quota.
    assert adm.admit("free") == (429, "tenant_fair")
    # A second tenant is under its share -> admitted into the burst
    # headroom even though inflight == max_inflight.
    assert adm.admit("gold") is None
    # gold's share: 3/(1+3) * 8 = 6 -> five more admits, then 429.
    for _ in range(5):
        assert adm.admit("gold") is None
    assert adm.admit("gold") == (429, "tenant_fair")
    # Hard cap: fill to hard_limit with fresh under-share tenants, then
    # everything sheds 503.
    i = 0
    while adm.inflight < adm.hard_limit:
        assert adm.admit(f"t{i}") is None
        i += 1
    assert adm.admit("t_next") == (503, "overload")
    # Releases restore admission.
    for _ in range(8):
        adm.release("free")
    assert adm.admit("another") is None
    snap = adm.snapshot()
    assert snap["maxInflight"] == 8 and "tenants" in snap


def test_tenant_fairness_under_a_hog_tenant():
    """E2E: a hog floods slow requests and saturates its share; its
    next request sheds 429 while a light tenant arriving at the full
    pipe is still admitted and completes."""
    h = _GateHandler()
    adm = AdmissionController(max_inflight=8, fair_start=0.25, weights={})
    srv = AsyncHTTPServer("localhost", 0, admission=adm, pool_workers=32,
                          queue_depth=64)
    srv.handler = h
    port = _start(srv)

    def request(tenant, out):
        try:
            s = socket.create_connection(("localhost", port), timeout=30)
            s.sendall(_post(
                b"{}", path=b"/x",
                extra=b"X-Pilosa-Tenant: " + tenant + b"\r\n",
            ))
            st, _, body = _read_response(s.makefile("rb"))
            out.append((st, body))
            s.close()
        except Exception as e:  # noqa: BLE001
            out.append(("err", repr(e)))

    try:
        hog_results: list = []
        hogs = [
            threading.Thread(target=request, args=(b"hog", hog_results))
            for _ in range(8)
        ]
        for t in hogs:
            t.start()
        for _ in range(8):  # all 8 hog requests are inside the handler
            assert h.entered.acquire(timeout=10)
        assert adm.inflight == 8
        # Hog's 9th: over its share -> fast 429, no engine work.
        ninth: list = []
        request(b"hog", ninth)
        assert ninth[0][0] == 429, ninth
        assert json.loads(ninth[0][1])["shed"] == "tenant_fair"
        # Light tenant at a full pipe: admitted (burst headroom), parks
        # in the handler, completes once the gate opens.
        light_results: list = []
        lt = threading.Thread(target=request, args=(b"light", light_results))
        lt.start()
        assert h.entered.acquire(timeout=10), "light tenant was not admitted"
        h.gate.set()
        lt.join(30)
        for t in hogs:
            t.join(30)
        assert light_results and light_results[0][0] == 200, light_results
        assert all(st == 200 for st, _ in hog_results), hog_results
        assert adm.inflight == 0  # releases are exactly paired
    finally:
        h.gate.set()
        srv.shutdown()


def test_full_submit_queue_sheds_503():
    """The worker-pool submit queue is BOUNDED: with one worker parked
    and the queue full, the next blocking request sheds 503
    (queue_full) instead of growing an unbounded backlog."""
    h = _GateHandler()
    adm = AdmissionController(max_inflight=64)
    srv = AsyncHTTPServer("localhost", 0, admission=adm, pool_workers=1,
                          queue_depth=1)
    srv.handler = h
    port = _start(srv)
    try:
        results: list = []

        def request(out):
            s = socket.create_connection(("localhost", port), timeout=30)
            s.sendall(_post(b"{}", path=b"/x"))
            st, _, body = _read_response(s.makefile("rb"))
            out.append((st, body))
            s.close()

        t1 = threading.Thread(target=request, args=(results,))
        t1.start()
        assert h.entered.acquire(timeout=10)  # worker 1 is parked
        t2 = threading.Thread(target=request, args=(results,))
        t2.start()
        deadline = time.monotonic() + 10
        while srv.pool._q.qsize() < 1:  # second job sits in the queue
            assert time.monotonic() < deadline
            time.sleep(0.01)
        shed: list = []
        request(shed)
        assert shed[0][0] == 503, shed
        assert json.loads(shed[0][1])["shed"] == "queue_full"
        h.gate.set()
        t1.join(30)
        t2.join(30)
        assert [st for st, _ in results] == [200, 200]
        assert adm.inflight == 0
    finally:
        h.gate.set()
        srv.shutdown()


def test_probes_bypass_admission_and_pool_saturation(mesh):
    """/healthz, /readyz, and /metrics must answer EXACTLY when the
    node is overloaded: they bypass admission (a liveness probe shed
    503 would get a healthy-but-loaded node restarted) and run inline
    on the reactor when the worker pool is saturated."""
    import urllib.error
    import urllib.request

    eng = MeshEngine(_holder(), mesh)
    api = API(holder=eng.holder, mesh_engine=eng)
    adm = AdmissionController(max_inflight=1, fair_start=0.0)
    srv, _ = serve(api, port=0, admission=adm, pool_workers=1, queue_depth=1)
    try:
        port = srv.server_address[1]
        # Saturate admission directly: one admit fills max_inflight=1
        # (hard cap = 1 + 8 burst, so fill that too).
        for i in range(adm.hard_limit):
            assert adm.admit(f"t{i}") is None
        # A data route sheds...
        req = urllib.request.Request(
            f"http://localhost:{port}/index/i/query",
            data=b"Count(Row(f=10))", method="POST",
        )
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(req, timeout=30)
        assert e.value.code in (429, 503)
        # ...while the probes still answer.
        for path in ("/healthz", "/readyz", "/metrics"):
            with urllib.request.urlopen(
                f"http://localhost:{port}{path}", timeout=30
            ) as resp:
                assert resp.status == 200, path
        for i in range(adm.hard_limit):
            adm.release(f"t{i}")
        # Phase 2 needs two concurrent ADMITTED requests to saturate
        # the 1-worker pool; widen the admission bound so only the pool
        # is the constraint under test now.
        adm.max_inflight = 64
        # Saturate the 1-worker pool with a long profile capture plus a
        # queued second job: probes fall back to inline execution on
        # the reactor and still answer promptly.
        def _pool_job(path):
            # Retry a transient queue_full 503: with queue_depth=1 and
            # an elastic worker mid-transition on a loaded host, the
            # submit can race the previous phase's drain — the point
            # under test is probe behavior under saturation, not this
            # setup request's first-try luck.
            for _ in range(50):
                try:
                    urllib.request.urlopen(
                        f"http://localhost:{port}{path}", timeout=60
                    ).read()
                    return
                except urllib.error.HTTPError as e:
                    if e.code != 503:
                        raise
                    time.sleep(0.05)

        slow = threading.Thread(
            target=_pool_job, args=("/debug/pprof/profile?seconds=3",),
        )
        slow.start()
        deadline = time.monotonic() + 10
        while not (srv.pool._workers == 1 and srv.pool._idle == 0):
            assert time.monotonic() < deadline, "profile job never started"
            time.sleep(0.01)
        queued = threading.Thread(target=_pool_job, args=("/debug/pprof",))
        queued.start()
        deadline = time.monotonic() + 10
        while srv.pool._q.qsize() < 1:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        t0 = time.monotonic()
        health = json.loads(urllib.request.urlopen(
            f"http://localhost:{port}/healthz", timeout=30
        ).read())
        assert health["status"] == "ok"
        assert time.monotonic() - t0 < 2.0, "probe waited on the pool"
        slow.join(60)
        queued.join(60)
    finally:
        srv.shutdown()
        eng.close()


# -- cross-connection coalescing (the tentpole's observable win) ------------


def _drive(port, bodies_per_conn):
    """One closed-loop connection per entry of ``bodies_per_conn``;
    each connection plays its own request list, request/response."""
    errs: list = []

    def worker(bodies):
        try:
            s = socket.create_connection(("localhost", port), timeout=60)
            fh = s.makefile("rb")
            for body in bodies:
                s.sendall(_post(body))
                st, _, resp = _read_response(fh)
                assert st == 200, resp
            s.close()
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    threads = [
        threading.Thread(target=worker, args=(b,)) for b in bodies_per_conn
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    assert not errs, errs


def _unique_pairs(n):
    """n distinct ordered (a, b) row pairs -> distinct Count bodies of
    ONE structure (signature-compatible, memo-distinct)."""
    out = []
    for k in range(n):
        a = 10 + (k % N_ROWS)
        b = 10 + ((k // N_ROWS + k + 1) % N_ROWS)
        if a == b:
            b = 10 + ((b - 10 + 1) % N_ROWS)
        out.append(
            f"Count(Intersect(Row(f={a}), Row(f={b})))".encode()
        )
    return out


def test_cross_connection_coalescing_beats_single_connection(mesh):
    """Batch occupancy under 16 concurrent connections must EXCEED the
    single-connection occupancy: the reactor feeds every live
    connection's queries into one accumulate stage, so fused batches
    carry many connections' work (the acceptance criterion's
    PipelineStats evidence).  Every request is a DISTINCT query of one
    structure, so nothing is memo-served and everything reaches the
    batcher."""

    def occupancy(n_conns, per_conn):
        eng = MeshEngine(_holder(), mesh)
        api = API(holder=eng.holder, mesh_engine=eng)
        srv, _ = serve(api, port=0)
        try:
            port = srv.server_address[1]
            _drive(port, [_unique_pairs(2)])  # warm compile
            # Model an accelerator's per-dispatch floor (queue cost
            # plus a readback round-trip): on
            # the instant CPU test mesh every query would ride alone
            # and NEITHER phase could fuse.  The floor is what makes
            # concurrent arrivals pile into one drain — exactly the
            # production condition the batcher exists for.
            orig = eng.count_many_async

            def with_dispatch_floor(index, calls, shards_list):
                time.sleep(0.03)
                return orig(index, calls, shards_list)

            eng.count_many_async = with_dispatch_floor
            eng._batcher.batches = 0
            eng._batcher.batched_queries = 0
            bodies = _unique_pairs(n_conns * per_conn + 8)[8:]
            _drive(
                port,
                [
                    bodies[i * per_conn : (i + 1) * per_conn]
                    for i in range(n_conns)
                ],
            )
            b = eng._batcher
            assert b.batches > 0
            return b.batched_queries / b.batches
        finally:
            srv.shutdown()
            eng.close()

    occ1 = occupancy(1, 24)
    occ16 = occupancy(16, 4)
    assert occ16 > occ1, (occ1, occ16)
    assert occ16 >= 2.0, occ16  # genuinely fused across connections


# -- pooled internal client -------------------------------------------------


def test_internal_client_reuses_pooled_connections(mesh):
    """InternalClient keep-alive pooling: many sequential calls ride
    ONE TCP connection (the server's accepted-connection counter moves
    by exactly one)."""
    from pilosa_tpu.net import InternalClient

    eng = MeshEngine(_holder(), mesh)
    api = API(holder=eng.holder, mesh_engine=eng)
    srv, _ = serve(api, port=0)
    try:
        before = srv._c_accepted.get()
        client = InternalClient(f"http://localhost:{srv.server_address[1]}")
        for _ in range(5):
            assert client.status()["state"] == "NORMAL"
        client.query("i", "Count(Row(f=10))")
        assert srv._c_accepted.get() - before == 1
        client.close()
    finally:
        srv.shutdown()


# -- backend parity ---------------------------------------------------------


@pytest.mark.parametrize("backend", ["async", "threaded"])
def test_response_ordering_and_probes_on_both_backends(mesh, backend):
    """The acceptance parametrization: deferred Counts interleaved with
    synchronous routes stay in request order, and the observability
    surfaces (/metrics, /healthz, /readyz, traceID stamping) behave
    identically on the reactor and the threaded oracle."""
    import urllib.request

    eng = MeshEngine(_holder(), mesh)
    api = API(holder=eng.holder, mesh_engine=eng)
    srv, _ = serve(api, port=0, backend=backend)
    try:
        port = srv.server_address[1]
        q = b"Count(Row(f=10))"
        s = socket.create_connection(("localhost", port), timeout=60)
        s.sendall(
            _post(q)
            + b"GET /version HTTP/1.1\r\nHost: l\r\n\r\n"
            + _post(q) + _post(q)
            + b"GET /healthz HTTP/1.1\r\nHost: l\r\n\r\n"
            + _post(q)
        )
        fh = s.makefile("rb")
        bodies = []
        for _ in range(6):
            st, _, body = _read_response(fh)
            assert st == 200
            bodies.append(json.loads(body))
        s.close()
        counts = [b["results"][0] for b in bodies if "results" in b]
        assert len(counts) == 4 and len(set(counts)) == 1
        assert all("traceID" in b for b in bodies if "results" in b)
        assert "version" in bodies[1]
        assert bodies[4]["status"] == "ok"
        # Probe + metrics parity.
        text = urllib.request.urlopen(
            f"http://localhost:{port}/metrics", timeout=30
        ).read().decode()
        for series in (
            "pilosa_query_seconds_bucket",
            "pilosa_pipeline_stage_seconds_bucket",
            "pilosa_admission_shed_total",
            "pilosa_server_connections",
        ):
            assert series in text, f"{backend} /metrics lacks {series}"
        rdy = json.loads(urllib.request.urlopen(
            f"http://localhost:{port}/readyz", timeout=30
        ).read())
        assert rdy["ready"] is True
        dbg = json.loads(urllib.request.urlopen(
            f"http://localhost:{port}/debug/vars", timeout=30
        ).read())
        if backend == "async":
            assert dbg["server"]["backend"] == "async"
            assert "admission" in dbg["server"]
    finally:
        srv.shutdown()
        eng.close()

"""Process-per-core serving mode (docs/serving.md "Process mode"):
worker processes behind SO_REUSEPORT forwarding decoded frames over
AF_UNIX to the device-owner process, the cross-process admission and
metrics aggregation, the supervisor's kill/respawn/readyz behavior, and
the net/wire.py fast-encode extension the workers use."""

import http.client
import json
import os
import signal
import threading
import time
import urllib.error
import urllib.request

import pytest

from pilosa_tpu.api import API
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.net import serve
from pilosa_tpu.net.admission import AdmissionController
from pilosa_tpu.net.procserver import ProcessHTTPServer
from pilosa_tpu.net.wire import fast_result_values, fast_results_bytes
from pilosa_tpu.util.stats import merge_expositions


@pytest.fixture(scope="module")
def engine_api():
    """One holder + mesh engine for the module: every process-mode
    server shares the single device owner (this test process)."""
    from pilosa_tpu.parallel import MeshEngine, make_mesh

    holder = Holder()
    holder.open()
    idx = holder.create_index("p")
    f = idx.create_field("f")
    f.import_bulk([1, 1, 1, 2], [0, 5, 9, 5])
    eng = MeshEngine(holder, make_mesh(1))
    api = API(holder=holder, mesh_engine=eng)
    yield api, eng


@pytest.fixture
def proc_server(engine_api):
    api, eng = engine_api
    srv, _ = serve(
        api, port=0, workers=2,
        admission=AdmissionController(max_inflight=64, fair_start=0.25),
    )
    assert isinstance(srv, ProcessHTTPServer)
    assert srv.wait_ready(60), "workers never connected"
    yield api, eng, srv
    srv.shutdown()


def _post(port, body, path="/p/query", headers=None, timeout=30):
    req = urllib.request.Request(
        f"http://localhost:{port}/index{path}", data=body, method="POST"
    )
    for k, v in (headers or {}).items():
        req.add_header(k, v)
    return json.loads(urllib.request.urlopen(req, timeout=timeout).read())


def _get(port, path, timeout=30):
    return urllib.request.urlopen(
        f"http://localhost:{port}{path}", timeout=timeout
    ).read().decode()


# -- net/wire.py fast-path extension (satellite) -----------------------------


def test_fast_results_bytes_byte_identical_to_json_dumps():
    """The TopN (id, count) pair fast path must produce the EXACT bytes
    the generic result_to_json + json.dumps walk produces."""
    cases = [
        [3],
        [3, 0, 12],
        [[(10, 2), (11, 1)]],
        [[]],
        [7, [(1, 5)], 9],
    ]
    for results in cases:
        generic = {
            "results": [
                r if isinstance(r, int)
                else [{"id": i, "count": c} for i, c in r]
                for r in results
            ]
        }
        assert fast_results_bytes(results) == json.dumps(generic).encode()
        generic["traceID"] = "abc123"
        assert (
            fast_results_bytes(results, "abc123")
            == json.dumps(generic).encode()
        )


def test_fast_result_values_rejects_non_fast_shapes():
    class Resp:
        column_attr_sets = None

        def __init__(self, results):
            self.results = results

    assert fast_result_values(Resp([1, 2])) == [1, 2]
    assert fast_result_values(Resp([[(1, 2)]])) == [[(1, 2)]]
    assert fast_result_values(Resp([True])) is None  # bool is not an int here
    assert fast_result_values(Resp([[("key", 2)]])) is None  # keyed TopN
    assert fast_result_values(Resp([{"x": 1}])) is None
    assert fast_result_values(Resp([[(1, 2, 3)]])) is None
    r = Resp([1])
    r.column_attr_sets = []
    assert fast_result_values(r) is None


# -- util/stats.merge_expositions --------------------------------------------


def test_merge_expositions_sums_and_appends():
    primary = "\n".join([
        "# HELP m_total m",
        "# TYPE m_total counter",
        "m_total 3",
        'm_total{a="x"} 1',
        "# HELP h h",
        "# TYPE h histogram",
        'h_bucket{le="1"} 2',
        'h_bucket{le="+Inf"} 4',
        "h_sum 1.5",
        "h_count 4",
    ]) + "\n"
    w1 = "m_total 2\n" + 'h_bucket{le="1"} 1\n' + "h_count 1\nh_sum 0.25\n"
    w2 = (
        'm_total{a="x"} 5\n'
        "# HELP only_worker_total w\n# TYPE only_worker_total counter\n"
        "only_worker_total 7\n"
    )
    out = merge_expositions(primary, {"w1": w1, "w2": w2})
    assert "m_total 5" in out
    assert 'm_total{a="x"} 6' in out
    assert 'h_bucket{le="1"} 3' in out
    assert 'h_bucket{le="+Inf"} 4' in out  # untouched by w1/w2
    assert "h_count 5" in out and "h_sum 1.75" in out
    assert "# TYPE only_worker_total counter" in out
    assert "only_worker_total 7" in out


def test_merge_expositions_preserves_openmetrics_tail_and_exemplars():
    primary = "\n".join([
        "# TYPE h histogram",
        'h_bucket{le="1"} 2 # {trace_id="t1"} 0.5 123.0',
        "h_count 2",
        "h_sum 1.0",
        "# EOF",
    ]) + "\n"
    out = merge_expositions(primary, {"w": 'h_bucket{le="1"} 3\nnew_total 1\n'})
    # Summed value, exemplar suffix kept, # EOF stays LAST.
    assert 'h_bucket{le="1"} 5 # {trace_id="t1"} 0.5 123.0' in out
    assert out.rstrip().endswith("# EOF")
    assert out.index("new_total 1") < out.index("# EOF")


# -- process mode end-to-end --------------------------------------------------


def test_workers_zero_is_the_plain_reactor(engine_api):
    """workers=0 (the default) must keep the in-process reactor —
    byte-identical pre-process-mode behavior."""
    from pilosa_tpu.net.aserver import AsyncHTTPServer

    api, _eng = engine_api
    srv, _ = serve(api, port=0, workers=0)
    try:
        assert isinstance(srv, AsyncHTTPServer)
    finally:
        srv.shutdown()


def test_process_query_roundtrip_and_topn(proc_server):
    api, eng, srv = proc_server
    port = srv.server_address[1]
    doc = _post(port, b"Count(Row(f=1))")
    assert doc["results"] == [3]
    assert doc.get("traceID")
    # TopN rides the RESULT_FAST pair frame; the WORKER encodes it.
    doc = _post(port, b"TopN(f, n=2)")
    assert doc["results"][0] == [
        {"id": 1, "count": 3}, {"id": 2, "count": 1},
    ]
    # Generic JSON path (Row -> columns) via RESPONSE frames.
    doc = _post(port, b"Row(f=1)")
    assert doc["results"][0]["columns"] == [0, 5, 9]
    # Error statuses map identically cross-process.
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(port, b"Row(f=1)", path="/missing/query")
    assert e.value.code == 404
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(port, b"NotACall???")
    assert e.value.code == 400
    # ?profile=1 returns the engine-recorded plan inline (full JSON
    # path: a profiled response never takes the fast frame).
    req = urllib.request.Request(
        f"http://localhost:{port}/index/p/query?profile=1",
        data=b"Count(Intersect(Row(f=1), Row(f=2)))", method="POST",
    )
    doc = json.loads(urllib.request.urlopen(req, timeout=30).read())
    assert doc.get("plan") and doc["plan"]["traceID"] == doc["traceID"]


def test_process_metrics_aggregate_and_debug_vars(proc_server):
    api, eng, srv = proc_server
    port = srv.server_address[1]
    for _ in range(3):
        _post(port, b"Count(Row(f=1))")
    text = _get(port, "/metrics")
    assert 'pilosa_process_up{proc="engine"} 1' in text
    assert 'pilosa_process_up{proc="worker-0"} 1' in text
    assert 'pilosa_process_up{proc="worker-1"} 1' in text
    assert 'pilosa_process_rss_bytes{proc="engine"}' in text
    # Worker-side serving counters sum into the node exposition: the
    # queries above arrived via worker reactors, so the aggregated
    # inline-path counter must be positive (the engine's own is 0).
    inline = [
        ln for ln in text.splitlines()
        if ln.startswith("pilosa_server_requests_total") and 'path="inline"' in ln
    ]
    assert inline and float(inline[0].rsplit(" ", 1)[1]) >= 3, inline
    conns = [
        ln for ln in text.splitlines()
        if ln.startswith("pilosa_server_connections_total")
    ]
    assert conns and float(conns[0].rsplit(" ", 1)[1]) >= 3, conns
    # Engine-side admission series render through the same scrape.
    assert "pilosa_admission_admitted_total" in text
    # /debug/vars carries the process-mode server snapshot.
    vars_doc = json.loads(_get(port, "/debug/vars"))
    assert vars_doc["server"]["backend"] == "process"
    assert vars_doc["server"]["workers"] == 2
    assert sorted(vars_doc["server"]["connected"]) == [0, 1]


def test_cross_worker_arrivals_coalesce(proc_server):
    """Concurrent queries entering via BOTH worker processes must fuse
    into shared device batches — the cross-process extension of the
    reactor's cross-connection coalescing (batcher counter)."""
    api, eng, srv = proc_server
    port = srv.server_address[1]

    def counter():
        b = eng._batcher
        if b is None:
            return 0
        return b.pipeline.snapshot()["counters"].get(
            "cross_worker_fused_batches", 0
        )

    # Distinct Intersect trees per request: same batch SIGNATURE (the
    # batcher masks argument literals), but each dodges the O(1)
    # cardinality lane AND the result memo — every query must flow
    # through the accumulate stage.
    nonce = iter(range(1, 1 << 20))
    start = counter()
    deadline = time.monotonic() + 60
    while counter() == start:
        assert time.monotonic() < deadline, (
            "no fused batch ever spanned two worker processes"
        )
        errs = []

        def client():
            try:
                c = http.client.HTTPConnection("localhost", port, timeout=30)
                for _ in range(8):
                    body = (
                        f"Count(Intersect(Row(f=1), Row(f={next(nonce)})))"
                    ).encode()
                    c.request("POST", "/index/p/query", body=body)
                    r = c.getresponse()
                    assert r.status == 200, r.status
                    r.read()
                c.close()
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        threads = [threading.Thread(target=client) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not errs, errs
    assert counter() > start


def test_admission_is_global_across_workers(proc_server):
    """The hog-tenant 429 fires however the hog's requests are spread
    over worker processes: the ONE controller lives in the device
    owner.  Saturating the hog's weighted-fair share engine-side makes
    the shed deterministic; the request still travels worker -> AF_UNIX
    -> admission."""
    api, eng, srv = proc_server
    port = srv.server_address[1]
    adm = srv.admission
    for _ in range(64):
        assert adm.admit("hog") is None
    try:
        disp0 = eng.fused_dispatches
        sheds = 0
        # Fresh connections spread over both workers' listeners.
        for _ in range(6):
            try:
                _post(
                    port, b"Count(Row(f=1))",
                    headers={"X-Pilosa-Tenant": "hog"},
                )
                raise AssertionError("hog request was not shed")
            except urllib.error.HTTPError as e:
                assert e.code == 429, e.code
                doc = json.loads(e.read())
                assert doc["shed"] == "tenant_fair", doc
                sheds += 1
        assert sheds == 6
        assert eng.fused_dispatches == disp0, "shed request reached the engine"
        # A light tenant is still admitted while the hog sheds.
        assert _post(
            port, b"Count(Row(f=1))", headers={"X-Pilosa-Tenant": "light"}
        )["results"] == [3]
    finally:
        for _ in range(64):
            adm.release("hog")


def test_worker_kill_respawn_readyz_and_surviving_acks(proc_server):
    """SIGKILL one worker mid-load: the supervisor respawns it, readyz
    flips not-ready then recovers, and clients on the SURVIVING worker
    lose zero in-flight acks (connection-level failures are allowed
    only for clients of the killed worker)."""
    api, eng, srv = proc_server
    port = srv.server_address[1]
    pids0 = dict(srv.worker_pids())
    assert len(pids0) == 2
    victim_wid, victim_pid = sorted(pids0.items())[0]

    results = {}
    lock = threading.Lock()
    stop_at = 30

    def client(cid):
        ok, conn_err = 0, None
        try:
            c = http.client.HTTPConnection("localhost", port, timeout=60)
            for _ in range(stop_at):
                c.request("POST", "/index/p/query", body=b"Count(Row(f=1))")
                r = c.getresponse()
                assert r.status == 200, r.status
                doc = json.loads(r.read())
                assert doc["results"] == [3], doc
                ok += 1
        except (
            ConnectionError, http.client.HTTPException, OSError
        ) as e:
            conn_err = e
        with lock:
            results[cid] = (ok, conn_err)

    threads = [
        threading.Thread(target=client, args=(i,)) for i in range(6)
    ]
    for t in threads:
        t.start()
    time.sleep(0.3)  # mid-load
    os.kill(victim_pid, signal.SIGKILL)
    # readyz flips while the worker is gone (the reader thread sees the
    # EOF immediately; the respawn takes >= the supervisor backoff).
    deadline = time.monotonic() + 10
    while not srv.not_ready_reasons():
        assert time.monotonic() < deadline, "readyz never flipped"
        time.sleep(0.01)
    assert any("workers" in r for r in srv.not_ready_reasons())
    for t in threads:
        t.join(120)
    assert len(results) == 6
    completed = [cid for cid, (ok, e) in results.items() if e is None]
    broken = [cid for cid, (ok, e) in results.items() if e is not None]
    # Every thread either fully completed (surviving worker: zero lost
    # acks) or died with a CONNECTION error (it was on the victim).
    for cid in completed:
        assert results[cid][0] == stop_at, results[cid]
    assert completed, "no client survived the kill"
    # The kernel may have parked every connection on one listener; only
    # clients of the victim may break, and never with a bad response.
    assert len(broken) <= 6
    # Respawn: same wid, new pid, readyz recovers.
    assert srv.wait_ready(60), "respawned worker never reconnected"
    assert srv.worker_pids()[victim_wid] != victim_pid
    assert srv.restarts >= 1
    rdy = json.loads(_get(port, "/readyz"))
    assert rdy["ready"] is True, rdy
    # The respawned worker serves traffic (new connections reach it
    # eventually; any single request works regardless of landing spot).
    assert _post(port, b"Count(Row(f=1))")["results"] == [3]
    # A scrape after the respawn shows every process up again.
    text = _get(port, "/metrics")
    assert 'pilosa_process_up{proc="worker-0"} 1' in text
    assert 'pilosa_process_up{proc="worker-1"} 1' in text


def test_config_workers_and_pool_workers_keys(tmp_path):
    """[server] workers is the PROCESS count (default 0); the blocking
    pool ceiling moved to pool-workers / SERVER_POOL_WORKERS."""
    from pilosa_tpu.config import Config

    cfg = Config()
    assert cfg.server_workers == 0
    assert cfg.server_pool_workers == 256
    p = tmp_path / "c.toml"
    p.write_text('[server]\nworkers = 4\npool-workers = 32\n')
    cfg.load_file(str(p))
    assert cfg.server_workers == 4
    assert cfg.server_pool_workers == 32
    cfg.load_env({
        "PILOSA_TPU_SERVER_WORKERS": "2",
        "PILOSA_TPU_SERVER_POOL_WORKERS": "16",
    })
    assert cfg.server_workers == 2
    assert cfg.server_pool_workers == 16
    out = cfg.to_toml()
    assert "workers = 2" in out and "pool-workers = 16" in out

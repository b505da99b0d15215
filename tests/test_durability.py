"""Durability & warm-start: the [storage] ack contract (an acked write
is replayable at its configured level BY CONSTRUCTION), atomic
persistence writes with corrupt-tolerant loaders, the InternalClient
retry/backoff budget, and the overlapped warm-start lifecycle
(docs/durability.md)."""

import json
import os
import socket
import threading
import time

import pytest

from pilosa_tpu.core.fragment import (
    ACK_FSYNCED,
    ACK_LOGGED,
    ACK_RECEIVED,
    Fragment,
)
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.net.client import ClientError, InternalClient
from pilosa_tpu.util.stats import (
    METRIC_CLIENT_RETRIES,
    METRIC_INGEST_ACKED_UNSYNCED,
    REGISTRY,
)


def _unsynced() -> float:
    return REGISTRY.get_gauge(METRIC_INGEST_ACKED_UNSYNCED) or 0.0


# -- [storage] ack levels ---------------------------------------------------


def test_ack_logged_flushes_op_before_ack(tmp_path):
    """At ack=logged the op-log bytes reach the OS before set_bit
    returns: a second reader (what a post-SIGKILL restart is) sees the
    op in the FILE immediately — no close(), no flush by the test."""
    p = str(tmp_path / "frag")
    f = Fragment("i", "f", "standard", 0, path=p, ack=ACK_LOGGED)
    base = os.path.getsize(p)
    assert f.set_bit(1, 7)
    assert os.path.getsize(p) > base, "acked op not visible to the OS"

    # The very same file replayed by a successor recovers the bit —
    # the fragment is dropped WITHOUT close (SIGKILL simulation).
    g = Fragment("i", "f", "standard", 0, path=p, ack=ACK_LOGGED)
    assert g.bit(1, 7)
    g.close()
    f._closed = True  # silence the abandoned instance


def test_ack_received_buffers_and_exposes_window(tmp_path):
    """At ack=received the acked tail may still sit in userspace: the
    file does NOT grow, and the loss window is exported as
    pilosa_ingest_acked_unsynced_bytes; a snapshot (which rewrites the
    file atomically) retires the window."""
    p = str(tmp_path / "frag")
    f = Fragment("i", "f", "standard", 0, path=p, ack=ACK_RECEIVED)
    base = os.path.getsize(p)
    before = _unsynced()
    assert f.set_bit(1, 7)
    assert os.path.getsize(p) == base, "received-level op hit the OS early"
    assert _unsynced() > before, "loss window not exported"

    # A successor reading the file now MISSES the bit — that is the
    # documented received-level window.
    g = Fragment("i", "f", "standard", 0, path=p + ".copy")
    del g
    peek = Fragment("i2", "f", "standard", 0)
    del peek
    raw = open(p, "rb").read()
    assert len(raw) == base

    f.snapshot()
    assert _unsynced() <= before, "snapshot did not retire the window"
    assert f.bit(1, 7)
    f.close()


def test_ack_fsynced_no_window(tmp_path):
    p = str(tmp_path / "frag")
    f = Fragment("i", "f", "standard", 0, path=p, ack=ACK_FSYNCED)
    before = _unsynced()
    base = os.path.getsize(p)
    assert f.set_bit(3, 9)
    assert os.path.getsize(p) > base
    assert _unsynced() == before, "fsynced level must not report a window"
    f.close()


def test_ack_unknown_level_rejected(tmp_path):
    with pytest.raises(ValueError):
        Fragment("i", "f", "standard", 0, ack="sometimes")


def test_holder_threads_ack_to_fragments(tmp_path):
    h = Holder(str(tmp_path / "h"), ack=ACK_FSYNCED)
    h.open()
    idx = h.create_index("i")
    f = idx.create_field("f")
    f.set_bit(1, 5)
    frag = h.fragment("i", "f", "standard", 0)
    assert frag is not None and frag.ack == ACK_FSYNCED
    h.close()


# -- atomic persistence + corrupt-tolerant loaders --------------------------


def test_cache_flush_atomic_and_corrupt_tolerated(tmp_path):
    p = str(tmp_path / "frag")
    f = Fragment("i", "f", "standard", 0, path=p)
    for c in range(10):
        f.set_bit(2, c)
    f.flush_cache()
    assert os.path.exists(p + ".cache")
    assert not os.path.exists(p + ".cache.tmp"), "temp file left behind"
    f.close()

    # Torn/corrupt cache file (crash predating the atomic writer):
    # reopen LOADS the fragment anyway, rebuilds the cache from row
    # counts, and drops the corrupt file.
    with open(p + ".cache", "w") as fh:
        fh.write('{"pairs": [[1,')  # torn JSON
    g = Fragment("i", "f", "standard", 0, path=p)
    assert g.row_count(2) == 10
    assert not os.path.exists(p + ".cache"), "corrupt cache not dropped"
    # Structurally-wrong JSON (not a dict of pairs) is tolerated too.
    with open(p + ".cache", "w") as fh:
        json.dump({"pairs": 17}, fh)
    g.close()
    h = Fragment("i", "f", "standard", 0, path=p)
    assert h.row_count(2) == 10
    h.close()


def test_topology_corrupt_tolerated(tmp_path):
    from pilosa_tpu.cluster import Cluster, Node

    d = tmp_path / "node"
    d.mkdir()
    (d / ".topology").write_text('{"nodes": [{"id": ')  # torn JSON
    c = Cluster(Node("n0", "http://localhost:1"), path=str(d))
    assert [n.id for n in c.nodes] == ["n0"], "corrupt topology not tolerated"
    # And the atomic writer round-trips.
    c.save_topology()
    c2 = Cluster(Node("n0", "http://localhost:1"), path=str(d))
    assert [n.id for n in c2.nodes] == ["n0"]
    assert not os.path.exists(str(d / ".topology.tmp"))


# -- InternalClient retry budget --------------------------------------------


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_client_connect_retries_with_backoff():
    """A dead endpoint consumes exactly the retry budget (counted in
    pilosa_client_retries_total), with capped backoff, then surfaces a
    ClientError — bounded, not a storm and not an instant give-up."""
    port = _free_port()  # nothing listening: connect refused instantly
    c = InternalClient(f"http://127.0.0.1:{port}", timeout=5.0, retries=2)
    before = REGISTRY.counter(METRIC_CLIENT_RETRIES).get()
    t0 = time.monotonic()
    with pytest.raises(ClientError):
        c.health()
    elapsed = time.monotonic() - t0
    assert REGISTRY.counter(METRIC_CLIENT_RETRIES).get() - before == 2
    assert elapsed < 3.0, f"backoff unbounded: {elapsed:.1f}s"
    assert elapsed >= 0.02, "no backoff at all between retries"


def test_client_retry_recovers_when_node_comes_back():
    """The point of the budget: a connect refused while a node restarts
    is retried after backoff and SUCCEEDS once the listener is back."""
    port = _free_port()
    result = {}

    def late_server():
        time.sleep(0.15)  # inside the retry window, after attempt 1
        srv = socket.socket()
        srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        srv.bind(("127.0.0.1", port))
        srv.listen(1)
        result["srv"] = srv
        conn, _ = srv.accept()
        conn.recv(65536)
        conn.sendall(
            b"HTTP/1.1 200 OK\r\nContent-Length: 2\r\n"
            b"Connection: close\r\n\r\n{}"
        )
        conn.close()

    t = threading.Thread(target=late_server, daemon=True)
    t.start()
    c = InternalClient(f"http://127.0.0.1:{port}", timeout=10.0, retries=4)
    assert c.health() == {}
    t.join(timeout=5)
    result["srv"].close()


def test_client_attempt_timeout_bounds_each_dial():
    c = InternalClient(
        "http://127.0.0.1:9", timeout=30.0, attempt_timeout=0.5, retries=0
    )
    assert c.attempt_timeout == 0.5
    # The socket-level timeout each attempt runs under is the attempt
    # timeout, not the whole-request deadline.
    assert c._connect().timeout == 0.5


# -- warm-start -------------------------------------------------------------


def _make_holder_with_data(path, n_shards=3):
    from pilosa_tpu.ops import SHARD_WIDTH

    h = Holder(str(path))
    h.open()
    idx = h.create_index("i")
    f = idx.create_field("f")
    rows, cols = [], []
    for s in range(n_shards):
        for c in range(50):
            rows.append(1)
            cols.append(s * SHARD_WIDTH + c * 31)
    f.import_bulk(rows, cols)
    return h


def test_holder_parallel_open_equivalent(tmp_path):
    h = _make_holder_with_data(tmp_path / "h")
    truth = {
        (i, f, v, s)
        for i, idx in h.indexes.items()
        for f, fl in idx.fields.items()
        for v, vw in fl.views.items()
        for s in vw.fragments
    }
    count = h.fragment("i", "f", "standard", 0).row_count(1)
    h.close()

    h2 = Holder(str(tmp_path / "h"))
    h2.open(workers=4)
    got = {
        (i, f, v, s)
        for i, idx in h2.indexes.items()
        for f, fl in idx.fields.items()
        for v, vw in fl.views.items()
        for s in vw.fragments
    }
    assert got == truth
    assert h2.fragment("i", "f", "standard", 0).row_count(1) == count
    h2.close()


def test_engine_warm_start_builds_residency(tmp_path):
    from pilosa_tpu.parallel import MeshEngine, make_mesh
    from pilosa_tpu import pql

    h = _make_holder_with_data(tmp_path / "h")
    eng = MeshEngine(h, make_mesh(1))
    try:
        assert eng.warm_state is None
        ws = eng.warm_start()
        assert ws["done"] is True
        # One stack per (field, view) with fragments (the auto existence
        # field has no views here: import_bulk went straight to field f).
        assert ws["built"] == ws["total"] == 1
        assert ("i", "f", "standard") in eng._stacks
        # The warmed stack serves bit-exact counts.
        q = pql.parse("Row(f=1)").calls[0]
        shards = h.local_shards("i")
        assert eng.count("i", q, shards) == 3 * 50
    finally:
        eng.close()
        h.close()


def test_warm_admit_falls_back_when_data_moved(tmp_path):
    """A write landing between the warm prefetch's host assembly and
    the admit must not publish a stale stack: the token re-check under
    the engine locks falls back to the authoritative locked build."""
    from pilosa_tpu.parallel import MeshEngine, make_mesh
    from pilosa_tpu import pql

    h = _make_holder_with_data(tmp_path / "h", n_shards=1)
    eng = MeshEngine(h, make_mesh(1))
    try:
        key = ("i", "f", "standard")
        canonical = eng.canonical_shards("i")
        assembled = eng._assemble_host(*key, canonical)
        # Racing write AFTER assembly, BEFORE admit.
        h.index("i").field("f").set_bit(1, 4096 * 7)
        assert eng._warm_admit(key, canonical, assembled)
        q = pql.parse("Row(f=1)").calls[0]
        assert eng.count("i", q, canonical) == 50 + 1
    finally:
        eng.close()
        h.close()


def test_readyz_reports_warming_lifecycle(tmp_path):
    """A server restarted onto an existing data dir warm-starts in the
    background and /readyz carries the warming record (done=True,
    fraction 1.0 once resident) — the orchestrator-visible lifecycle."""
    import urllib.request

    from pilosa_tpu.config import Config
    from pilosa_tpu.server import Server

    cfg = Config()
    cfg.data_dir = str(tmp_path / "node")
    cfg.bind = "localhost:0"
    srv = Server(cfg)
    srv.open(port_override=0)
    idx = srv.holder.create_index("i")
    idx.create_field("f").set_bit(1, 5)
    port_written = srv.port
    del port_written
    srv.close()

    cfg2 = Config()
    cfg2.data_dir = str(tmp_path / "node")
    cfg2.bind = "localhost:0"
    srv2 = Server(cfg2)
    srv2.open(port_override=0)
    try:
        deadline = time.monotonic() + 30
        doc = None
        while time.monotonic() < deadline:
            try:
                with urllib.request.urlopen(
                    f"http://localhost:{srv2.port}/readyz", timeout=5
                ) as resp:
                    doc = json.loads(resp.read())
                    break
            except urllib.error.HTTPError as e:  # 503 while warming
                doc = json.loads(e.read())
                if doc.get("warming", {}).get("done"):
                    break
            time.sleep(0.05)
        assert doc is not None and doc.get("ready"), doc
        assert "warming" in doc, "warm-start record missing from /readyz"
        assert doc["warming"]["done"] is True
        assert doc["warming"]["fraction"] == 1.0
        assert doc["warming"]["built"] >= 1
    finally:
        srv2.close()

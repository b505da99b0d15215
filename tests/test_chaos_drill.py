"""The SIGKILL-mid-ingest chaos drill (docs/durability.md "Chaos
runbook"): a REAL 3-process cluster formed over SWIM gossip, replicas=2,
ack=logged.  One replica is SIGKILLed (-9, no cleanup) while a writer
streams imports and a paced reader hammers Counts through the
coordinator.  Asserts the three serving-through-failure invariants:

1. Zero lost ACKED bits: every import batch that returned 200 is
   readable afterwards — on the survivors immediately, and on the
   SIGKILLed node after restart + anti-entropy (ack=logged makes the
   op-log/snapshot OS-durable BEFORE the ack, so -9 cannot lose it).
2. Continuous availability: reads never error through the kill — the
   mapper hedges the dead node's shards onto surviving replicas.
3. Convergent recovery: the restarted node (same data dir, same ports)
   reports warming -> ready on /readyz, rejoins via gossip, and
   anti-entropy converges it to bit-exact state.

This drill is the in-process/subprocess lane and runs EVERYWHERE — no
capability gate.  Only the true multi-process psum lane (collective
meshes) stays gated on the cross-process-collectives probe; a
companion test here pins the probe contract (cached, real error as the
skip reason)."""

import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))
    )
    return env


# The chaos node bootstrap: n0 is the coordinator, replicas=2,
# ack=logged, fast gossip + anti-entropy.
CHAOS_NODE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "scripts", "chaos_node.py",
)


def _get(port, path, timeout=10):
    with urllib.request.urlopen(
        f"http://localhost:{port}{path}", timeout=timeout
    ) as resp:
        return json.loads(resp.read())


def _post(port, path, body, timeout=30, headers=None):
    req = urllib.request.Request(
        f"http://localhost:{port}{path}", data=body, method="POST"
    )
    req.add_header("Content-Type", "application/json")
    for k, v in (headers or {}).items():
        req.add_header(k, v)
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def _boot(tmp_path, script, i, ports, gports):
    return subprocess.Popen(
        [
            sys.executable, str(script), f"n{i}", str(ports[i]),
            str(gports[i]), str(gports[0]), str(tmp_path / f"n{i}"),
            "--ack", "logged", "--ae-interval", "1.5",
        ],
        env=_env(), stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True,
    )


def _await_ready(procs, want, deadline=120):
    end = time.time() + deadline
    ready = set()
    while len(ready) < want and time.time() < end:
        for i, p in enumerate(procs):
            if i in ready or p is None:
                continue
            assert p.poll() is None, (
                f"server {i} died:\n{p.stdout.read()}\n{p.stderr.read()}"
            )
            if p.stdout.readline().startswith("READY"):
                ready.add(i)
    assert len(ready) >= want, "servers did not come up"


def test_sigkill_mid_ingest_drill(tmp_path):
    from pilosa_tpu.ops import SHARD_WIDTH

    ports = [_free_port() for _ in range(3)]
    gports = [_free_port() for _ in range(3)]
    script = CHAOS_NODE
    procs = [_boot(tmp_path, script, i, ports, gports) for i in range(3)]
    try:
        _await_ready(procs, 3)

        # Membership + NORMAL via gossip alone.
        end = time.time() + 30
        while time.time() < end:
            sts = [_get(ports[i], "/status") for i in range(3)]
            if all(len(s["nodes"]) == 3 for s in sts) and all(
                s["state"] == "NORMAL" for s in sts
            ):
                break
            time.sleep(0.2)
        else:
            pytest.fail(f"membership never converged: {sts}")

        _post(ports[0], "/index/i", b"{}")
        _post(ports[0], "/index/i/field/f", b'{"options": {"type": "set"}}')

        n_shards = 6
        acked = set()
        write_errors = []
        stop_writing = threading.Event()

        def writer():
            """Stream small import batches; record cols ONLY when the
            batch ACKED (200).  A failed batch is never counted — its
            bits may or may not have partially applied."""
            seq = 0
            while not stop_writing.is_set():
                batch = [
                    (s, seq * 64 + k)
                    for s in range(n_shards)
                    for k in range(4)
                ]
                cols = [s * SHARD_WIDTH + c for s, c in batch]
                seq += 1
                try:
                    _post(
                        ports[0], "/index/i/field/f/import",
                        json.dumps(
                            {"rowIDs": [1] * len(cols), "columnIDs": cols}
                        ).encode(),
                        timeout=30,
                    )
                    acked.update(cols)
                except Exception as e:  # noqa: BLE001 — not acked, not counted
                    write_errors.append(str(e))
                time.sleep(0.05)

        read_errors = []
        reads = []
        stop_reading = threading.Event()

        def reader():
            """Paced Counts through the coordinator: with replicas=2
            and hedging, these must NEVER error through the kill."""
            while not stop_reading.is_set():
                try:
                    out = _post(
                        ports[0], "/index/i/query",
                        b"Count(Row(f=1))", timeout=60,
                    )
                    reads.append(out["results"][0])
                except Exception as e:  # noqa: BLE001
                    read_errors.append(str(e))
                time.sleep(0.05)

        wt = threading.Thread(target=writer)
        rt = threading.Thread(target=reader)
        wt.start()
        rt.start()

        time.sleep(1.5)  # steady state under load
        # SIGKILL a replica — no shutdown hooks, no flush, nothing.
        os.kill(procs[1].pid, signal.SIGKILL)
        procs[1].wait(timeout=10)

        # The cluster degrades, detection lands, ingest keeps acking
        # (DOWN owner skipped; survivors take the writes).
        end = time.time() + 30
        while time.time() < end:
            if _get(ports[0], "/status")["state"] == "DEGRADED":
                break
            time.sleep(0.2)
        else:
            pytest.fail("coordinator never saw DEGRADED")
        acked_at_detection = len(acked)
        time.sleep(2.0)  # keep ingesting + reading against the dead node
        assert len(acked) > acked_at_detection, (
            "ingest did not keep acking through the failure "
            f"(write errors: {write_errors[-3:]})"
        )

        # Restart the SIGKILLed node: same data dir, same ports.
        procs[1] = _boot(tmp_path, script, 1, ports, gports)
        _await_ready([None, procs[1], None], 1)

        # readyz flips warming -> ready (warm-start record present).
        end = time.time() + 60
        rz = None
        while time.time() < end:
            try:
                with urllib.request.urlopen(
                    f"http://localhost:{ports[1]}/readyz", timeout=5
                ) as resp:
                    rz = json.loads(resp.read())
                    break
            except urllib.error.HTTPError as e:
                rz = json.loads(e.read())
            except Exception:  # noqa: BLE001 — still booting
                pass
            time.sleep(0.2)
        assert rz is not None and rz.get("ready"), f"never ready: {rz}"
        assert rz.get("warming", {}).get("done") is True, rz
        stop_writing.set()
        wt.join()

        # Cluster heals to NORMAL.
        end = time.time() + 30
        while time.time() < end:
            if _get(ports[0], "/status")["state"] == "NORMAL":
                break
            time.sleep(0.2)
        else:
            pytest.fail("cluster never healed to NORMAL")

        # Continuous availability: ZERO read errors across the whole
        # drill — kill, blip, detection, restart (invariant 2).
        stop_reading.set()
        rt.join()
        assert reads, "reader made no progress"
        assert not read_errors, (
            f"{len(read_errors)} reads failed during the drill: "
            f"{read_errors[:3]}"
        )

        # Zero lost ACKED bits + convergent recovery (invariants 1+3):
        # every acked column is present in Row(f=1) — cluster-wide, and
        # (after anti-entropy) in the restarted node's LOCAL truth for
        # the shards it OWNS (clean_holder drops the rest by design).
        shards = sorted({c // SHARD_WIDTH for c in acked})

        def owners(s):
            with urllib.request.urlopen(
                f"http://localhost:{ports[0]}/internal/fragment/nodes"
                f"?index=i&shard={s}", timeout=10,
            ) as resp:
                return {n["id"] for n in json.loads(resp.read())}

        n1_shards = [s for s in shards if "n1" in owners(s)]
        assert n1_shards, "placement gave n1 no shards?"
        n1_acked = {c for c in acked if c // SHARD_WIDTH in n1_shards}

        def local_cols(port, over):
            out = _post(
                port, "/index/i/query",
                json.dumps(
                    {"query": "Row(f=1)", "remote": True, "shards": over}
                ).encode(),
                timeout=60,
            )
            return set(out["results"][0]["columns"])

        assert acked, "nothing was acked"
        # (1) The IMMEDIATE guarantee: every acked bit is present on a
        # SURVIVING owner of its shard right now — the ack was made
        # durable there before it returned.  (A shard whose primary is
        # the freshly-rejoined n1 may serve a bounded-stale answer
        # cluster-wide until anti-entropy lands — that's the eventual
        # half, polled below.)
        survivor_truth = set()
        for s in shards:
            peer = next(i for i in (0, 2) if f"n{i}" in owners(s))
            survivor_truth |= local_cols(ports[peer], [s])
        missing_now = acked - survivor_truth
        assert not missing_now, (
            f"{len(missing_now)} ACKED bits absent from the surviving "
            "owners — lost at ack time"
        )

        # (2) The EVENTUAL guarantee: anti-entropy converges the
        # restarted node to hold every acked bit of its owned shards,
        # bit-exact with its surviving co-owner, and the cluster-wide
        # query returns everything.
        end = time.time() + 45  # anti-entropy interval is 1.5s
        diverged = ["unchecked"]
        while time.time() < end:
            missing = n1_acked - local_cols(ports[1], n1_shards)
            if not missing:
                diverged = [
                    s for s in n1_shards
                    if local_cols(ports[1], [s]) != local_cols(
                        ports[next(
                            i for i in (0, 2) if f"n{i}" in owners(s)
                        )], [s],
                    )
                ]
                if not diverged:
                    break
            time.sleep(0.5)
        else:
            pytest.fail(
                f"no convergence: missing {len(missing)} acked bits, "
                f"diverged shards {diverged}"
            )
        missing_cluster = acked - set(
            _post(ports[0], "/index/i/query", b"Row(f=1)", timeout=60)[
                "results"
            ][0]["columns"]
        )
        assert not missing_cluster, (
            f"{len(missing_cluster)} ACKED bits lost cluster-wide after "
            "convergence"
        )
    finally:
        for p in procs:
            if p is None:
                continue
            try:
                p.kill()
            except ProcessLookupError:
                pass
        for p in procs:
            if p is not None:
                p.communicate(timeout=30)


def test_partition_heal_drill(tmp_path):
    """The hinted-handoff acceptance drill (docs/durability.md "Hinted
    handoff"): a REAL 3-process gossip cluster is PARTITIONED — n1 cut
    from {n0, n2} via the deterministic fault plane at runtime (POST
    /debug/faults, one rule body to every node) — instead of killed.
    Asserts, in order:

    1. Destructive writes become ACKABLE under single-owner failure:
       every Clear on an n1-owned shard driven through the degraded
       window acks (0% before hinted handoff), each miss durably queued
       (pilosa_hints_queued_total > 0, pending visible in /debug/vars).
    2. Replay-before-readmission: at the moment n0 releases n1's
       bounded-read quarantine, n1's local truth ALREADY reflects the
       clears — the replay landed first.
    3. Zero reverted clears: after heal + two further anti-entropy
       intervals, no cleared bit resurfaces on ANY replica (the
       majority-tie-to-set merge never ran against the stale node).
    """
    from pilosa_tpu.ops import SHARD_WIDTH

    ports = [_free_port() for _ in range(3)]
    gports = [_free_port() for _ in range(3)]

    def boot(i):
        return subprocess.Popen(
            [
                sys.executable, str(CHAOS_NODE), f"n{i}", str(ports[i]),
                str(gports[i]), str(gports[0]), str(tmp_path / f"n{i}"),
                "--ack", "logged", "--ae-interval", "1.5",
                # The drill heals and measures recovery: the production
                # 15s holddown would dominate; the fast setting is the
                # documented drill tradeoff (docs/durability.md).
                "--recovery-holddown-ms", "500",
            ],
            env=_env(), stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True,
        )

    procs = [boot(i) for i in range(3)]
    try:
        _await_ready(procs, 3)
        end = time.time() + 30
        while time.time() < end:
            sts = [_get(ports[i], "/status") for i in range(3)]
            if all(len(s["nodes"]) == 3 for s in sts) and all(
                s["state"] == "NORMAL" for s in sts
            ):
                break
            time.sleep(0.2)
        else:
            pytest.fail(f"membership never converged: {sts}")

        _post(ports[0], "/index/i", b"{}")
        _post(ports[0], "/index/i/field/f", b'{"options": {"type": "set"}}')
        n_shards = 6
        cols = [
            s * SHARD_WIDTH + k for s in range(n_shards) for k in range(16)
        ]
        _post(
            ports[0], "/index/i/field/f/import",
            json.dumps(
                {"rowIDs": [1] * len(cols), "columnIDs": cols}
            ).encode(),
            timeout=60,
        )
        end = time.time() + 30
        while time.time() < end:
            oracle = _post(
                ports[0], "/index/i/query", b"Count(Row(f=1))", timeout=60
            )["results"][0]
            if oracle == len(cols):
                break
            time.sleep(0.3)
        assert oracle == len(cols), (oracle, len(cols))

        def owners(s):
            with urllib.request.urlopen(
                f"http://localhost:{ports[0]}/internal/fragment/nodes"
                f"?index=i&shard={s}", timeout=10,
            ) as resp:
                return {n["id"] for n in json.loads(resp.read())}

        n1_shards = [s for s in range(n_shards) if "n1" in owners(s)]
        assert n1_shards, "placement gave n1 no shards?"

        # Partition n1 from {n0, n2}: ONE deterministic rule body,
        # POSTed to every node — each enforces only its own side.
        partition = json.dumps({
            "seed": 3,
            "rules": [{
                "action": "partition",
                "a": [f"127.0.0.1:{ports[1]}", f"127.0.0.1:{gports[1]}"],
                "b": [
                    f"127.0.0.1:{ports[0]}", f"127.0.0.1:{gports[0]}",
                    f"127.0.0.1:{ports[2]}", f"127.0.0.1:{gports[2]}",
                ],
            }],
        }).encode()
        for p in ports:
            _post(p, "/debug/faults", partition)

        end = time.time() + 30
        while time.time() < end:
            if _get(ports[0], "/status")["state"] == "DEGRADED":
                break
            time.sleep(0.2)
        else:
            pytest.fail("partition verdict never landed on n0")

        # (1) Destructive writes through the degraded window: EVERY
        # clear on an n1-owned shard must ack — this exact shape failed
        # loudly before hinted handoff.
        cleared = []
        for s in n1_shards:
            col = s * SHARD_WIDTH  # k=0, seeded above
            out = _post(
                ports[0], "/index/i/query", f"Clear({col}, f=1)".encode(),
                timeout=30,
            )
            assert out["results"][0] is True, (s, out)
            cleared.append(col)
        # Reads keep answering exactly through the partition (hedging).
        out = _post(ports[0], "/index/i/query", b"Count(Row(f=1))", timeout=60)
        assert out["results"][0] == oracle - len(cleared)

        # The misses are durably queued and visible.
        dv = _get(ports[0], "/debug/vars")
        assert dv.get("hints", {}).get("pending", {}).get("n1") == len(
            cleared
        ), dv.get("hints")
        with urllib.request.urlopen(
            f"http://localhost:{ports[0]}/metrics", timeout=10
        ) as resp:
            metrics = resp.read().decode()
        queued = [
            ln for ln in metrics.splitlines()
            if ln.startswith("pilosa_hints_queued_total")
        ]
        assert queued and float(queued[0].rsplit(" ", 1)[1]) >= len(cleared)

        # Heal: empty rule tables everywhere.
        for p in ports:
            _post(p, "/debug/faults", json.dumps({"rules": []}).encode())

        # (2) Replay-before-readmission: poll n0's quarantine view of
        # n1; the FIRST time it reads released, n1's local truth must
        # already hold every clear.
        def n1_local_count():
            return _post(
                ports[1], "/index/i/query",
                json.dumps({
                    "query": "Count(Row(f=1))", "remote": True,
                    "shards": n1_shards,
                }).encode(), timeout=30,
            )["results"][0]

        expect_n1 = 16 * len(n1_shards) - len(cleared)
        end = time.time() + 60
        released = False
        while time.time() < end:
            hb = _get(ports[0], "/debug/vars").get("clusterHeartbeats", {})
            q = hb.get("n1", {}).get("quarantined")
            if q is False:
                released = True
                got = n1_local_count()
                if got != expect_n1:
                    import urllib.request as _ur
                    for pi in (0, 1, 2):
                        with _ur.urlopen(
                            f"http://localhost:{ports[pi]}/debug/events?limit=400",
                            timeout=10,
                        ) as r:
                            ev = json.loads(r.read())
                        for e in ev.get("events", []):
                            t = e.get("type", "")
                            if ("hint" in t or "quarantine" in t
                                    or "antientropy" in t or "write" in t):
                                print(f"EV[n{pi}]", e, flush=True)
                    for s in n1_shards:
                        out_s = _post(
                            ports[1], "/index/i/query",
                            json.dumps({"query": "Row(f=1)", "remote": True,
                                        "shards": [s]}).encode(), timeout=30,
                        )["results"][0]["columns"]
                        print(f"N1 shard {s} cols:", out_s[:4], "...",
                              len(out_s), flush=True)
                assert got == expect_n1, (
                    "bounded-read quarantine released BEFORE the hint "
                    "replay landed on n1"
                )
                break
            time.sleep(0.2)
        assert released, f"n1 quarantine never released: {hb}"
        assert not _get(ports[0], "/debug/vars").get("hints", {}).get(
            "pending"
        )

        # (3) Zero reverted clears: stable through two further
        # anti-entropy intervals on every replica and cluster-wide.
        end = time.time() + 30
        while time.time() < end:
            if _get(ports[0], "/status")["state"] == "NORMAL":
                break
            time.sleep(0.2)
        else:
            pytest.fail("cluster never healed to NORMAL")
        time.sleep(3.2)  # two 1.5s anti-entropy intervals
        assert n1_local_count() == expect_n1, "clear reverted on n1"
        out = _post(ports[0], "/index/i/query", b"Count(Row(f=1))", timeout=60)
        assert out["results"][0] == oracle - len(cleared), (
            "anti-entropy resurrected a cleared bit"
        )
        with urllib.request.urlopen(
            f"http://localhost:{ports[0]}/metrics", timeout=10
        ) as resp:
            metrics = resp.read().decode()
        replayed = [
            ln for ln in metrics.splitlines()
            if ln.startswith("pilosa_hints_replayed_total")
        ]
        assert replayed and float(
            replayed[0].rsplit(" ", 1)[1]
        ) >= len(cleared)
    finally:
        for p in procs:
            try:
                p.kill()
            except ProcessLookupError:
                pass
        for p in procs:
            p.communicate(timeout=30)


def test_capability_probe_contract():
    """The multi-process psum lane's gate (the ONLY remaining
    environmental gate on the chaos suites): the probe is cached for
    the session and, when the environment can't run cross-process
    collectives, its skip reason carries the probe's ACTUAL error —
    never a bare 'skipped'."""
    from capabilities import multiprocess_collectives

    ok, reason = multiprocess_collectives()
    if ok:
        assert reason == ""
    else:
        # The reason is the harvested real error line (or the explicit
        # timeout verdict) — asserting non-empty + specific keeps a
        # future refactor from silently degrading the skip message.
        assert reason
        assert reason != "skipped"
    # Cached: the second call must not pay two interpreter boots.
    t0 = time.monotonic()
    assert multiprocess_collectives() == (ok, reason)
    assert time.monotonic() - t0 < 0.1

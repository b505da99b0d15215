#!/usr/bin/env python3
"""chip_smoke.py — the served path, end to end, on the chip.

Starts ``python -m pilosa_tpu server`` (default configuration: ``[storage]
ack = logged``, default device budget, all local devices) as its ONLY JAX
child, loads a 960-shard index (1,006,632,960 columns — the scale of
Pilosa's documented "billion taxi rides" deployment) through the public
``import-roaring`` route on several connections, answers every query
family over HTTP with ``?profile=1`` — single requests, a 64-query
burst the batcher must batch, a mixed burst it may fuse, and a write
read back — and compares each answer with a plain NumPy reference over
the same generated words.  It then proves the chip answered: every checked query is first-seen (no memo hit), every
plan's ``path`` is the expected one and never ``host_fallback``,
``pilosa_engine_host_fallbacks_total`` is 0, and the psum-dispatch
counter advanced.

This process never imports JAX: a parent that touched JAX would hold the
chip and the server child could not.  The platform is asserted from the
child, through ``/debug/vars`` ``mesh``: anything but ``tpu`` exits
non-zero with nothing on stdout (``--allow-cpu`` is the sandbox rehearsal
and prints ``"platform": "cpu"``).  No phase is wrapped in a catch that
lets the run end 0.  A passing run writes two lines to stdout: the JSON
report (scale, resident bytes, set-up seconds, per-query outcomes), then,
last, the verdict ``{"ok": true, "device": {"platform", "kind", "count"}}``
with exactly those keys, the device as the server's JAX reports it.
"""

import argparse
import http.client
import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
INDEX = "smoke"
SHARD_WIDTH = 1 << 20
W64 = SHARD_WIDTH // 64  # uint64 words per shard row
BLOCK64 = 256  # uint64 words per 2 KiB occupancy block (512 uint32)
N_BLOCKS = W64 // BLOCK64  # 64 blocks per shard row
FULL_SHARDS = 960
F_ROWS = (1, 2, 3, 4, 5, 6)  # set field f: uniform 50 % density
S_ROWS = (1, 2, 3, 4)  # set field s: 4 blocks per shard, 2 shared
V_DEPTH = 8  # int field v, min 0 max 255: 8 value planes + not-null
CONNECTIONS = 8  # ingest and burst concurrency
DEADLINE_S = 1150  # the contract allows 1200, compilation included
T0 = time.monotonic()


class SmokeFailure(Exception):
    """A phase failed; the run ends non-zero with nothing on stdout."""


def log(msg):
    print(f"[{time.monotonic() - T0:7.1f}s] {msg}", file=sys.stderr, flush=True)


def popcount(words) -> int:
    return int(np.bitwise_count(words).sum())


# -- data: generated per shard from (seed, shard) ---------------------------


class Data:
    """The generated columns, kept as the NumPy reference reads them."""

    def __init__(self, shards: int):
        self.f = np.zeros((len(F_ROWS), shards, W64), np.uint64)
        self.s = np.zeros((len(S_ROWS), shards, W64), np.uint64)
        self.v = np.zeros((shards, SHARD_WIDTH), np.uint8)
        self.v_exists = np.zeros((shards, W64), np.uint64)

    def row(self, field: str, row_id: int):
        return getattr(self, field)[row_id - 1]


def make_shard(data: Data, seed: int, shard: int) -> dict:
    """Fill shard ``shard`` of ``data``; returns {(field, query string):
    roaring bytes} for the three imports."""
    rng = np.random.default_rng([seed, shard])
    data.f[:, shard] = rng.integers(0, 1 << 64, (len(F_ROWS), W64), dtype=np.uint64)
    # s: every row sits in 4 of the shard's 64 blocks; all rows share the
    # first two, so a two-row Intersect survives in 2/64 of the blocks.
    blocks = rng.permutation(N_BLOCKS)
    for i in range(len(S_ROWS)):
        for b in (*blocks[:2], *blocks[2 + 2 * i: 4 + 2 * i]):
            data.s[i, shard, b * BLOCK64:(b + 1) * BLOCK64] = rng.integers(
                0, 1 << 64, BLOCK64, dtype=np.uint64
            )
    values = rng.integers(0, 256, SHARD_WIDTH, dtype=np.uint8)
    exists = rng.integers(0, 1 << 64, W64, dtype=np.uint64)
    data.v[shard] = values
    data.v_exists[shard] = exists
    planes = {
        k: np.packbits((values >> k) & 1, bitorder="little").view(np.uint64) & exists
        for k in range(V_DEPTH)
    }
    planes[V_DEPTH] = exists  # BSI not-null row
    return {
        ("f", ""): roaring({r: data.f[i, shard] for i, r in enumerate(F_ROWS)}),
        ("s", ""): roaring({r: data.s[i, shard] for i, r in enumerate(S_ROWS)}),
        ("v", "?view=bsig_v"): roaring(planes),
    }


def roaring(rows: dict) -> bytes:
    """{row id: uint64[W64]} -> Pilosa roaring bytes, one bitmap container
    per occupied 2^16-bit chunk, encoded straight from the words (format:
    pilosa_tpu/roaring/codec.py header)."""
    keys, counts, chunks = [], [], []
    for r, words in sorted(rows.items()):
        c = words.reshape(16, 1024)
        n = np.bitwise_count(c).sum(axis=1)
        for k in np.nonzero(n)[0]:
            keys.append(r * 16 + int(k))
            counts.append(int(n[k]))
            chunks.append(c[k].tobytes())
    hdr = np.zeros(len(keys), dtype=[("key", "<u8"), ("typ", "<u2"), ("n1", "<u2")])
    hdr["key"], hdr["typ"], hdr["n1"] = keys, 2, np.asarray(counts) - 1
    first = 8 + 16 * len(keys)
    offsets = (first + 8192 * np.arange(len(keys))).astype("<u4")
    head = np.array([12348, len(keys)], "<u4").tobytes()
    return b"".join([head, hdr.tobytes(), offsets.tobytes(), *chunks])


# -- HTTP -------------------------------------------------------------------


class Client:
    """One persistent connection to the server child."""

    def __init__(self, port: int, child: subprocess.Popen):
        self.conn = http.client.HTTPConnection("127.0.0.1", port, timeout=600)
        self.child = child

    def call(self, method: str, path: str, body: bytes = None) -> bytes:
        if self.child.poll() is not None:
            raise SmokeFailure(f"server child exited with {self.child.returncode}")
        self.conn.request(method, path, body=body)
        resp = self.conn.getresponse()
        payload = resp.read()
        if resp.status != 200:
            raise SmokeFailure(f"{method} {path}: HTTP {resp.status} {payload[:300]!r}")
        return payload

    def query(self, pql: str) -> tuple:
        doc = json.loads(self.call("POST", f"/index/{INDEX}/query?profile=1", pql.encode()))
        return doc["results"][0], doc["plan"]

    def metrics(self) -> dict:
        out = {}
        for line in self.call("GET", "/metrics").decode().splitlines():
            if line and not line.startswith("#"):
                name, _, value = line.rpartition(" ")
                out[name] = float(value)
        return out

    def debug_vars(self) -> dict:
        return json.loads(self.call("GET", "/debug/vars"))


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def wait_ready(client: Client, timeout: float = 300.0):
    end = time.monotonic() + timeout
    while True:
        try:
            client.call("GET", "/readyz")
            return
        except (OSError, http.client.HTTPException, SmokeFailure):
            if client.child.poll() is not None:
                raise SmokeFailure(f"server child exited with {client.child.returncode}")
            if time.monotonic() > end:
                raise SmokeFailure("server not ready in time")
            client.conn.close()
            time.sleep(0.25)


def on_connections(port, child, jobs, work):
    """Run ``work(client, job)`` for every job on CONNECTIONS connections
    (one per pool thread); returns results in job order.  The first
    failure is re-raised and the jobs not yet started are dropped."""
    local = threading.local()

    def run(job):
        if not hasattr(local, "client"):
            local.client = Client(port, child)
        return work(local.client, job)

    pool = ThreadPoolExecutor(CONNECTIONS)
    try:
        return list(pool.map(run, jobs))
    finally:
        pool.shutdown(wait=True, cancel_futures=True)


# -- the plain NumPy reference ----------------------------------------------


def tree_words(data: Data, op: str, a: int, b: int):
    x, y = data.row("f", a), data.row("f", b)
    return {"Intersect": x & y, "Union": x | y, "Difference": x & ~y}[op]


def values_under(data: Data, mask=None) -> list:
    """Per shard, the not-null values of v (under ``mask`` words if given)."""
    keep = data.v_exists if mask is None else data.v_exists & mask
    return [
        data.v[sh][np.unpackbits(keep[sh].view(np.uint8), bitorder="little").astype(bool)]
        for sh in range(len(data.v))
    ]


def ref_sum(per_shard: list) -> dict:
    return {"value": sum(int(v.sum(dtype=np.int64)) for v in per_shard),
            "count": sum(int(v.size) for v in per_shard)}


def ref_extreme(per_shard: list, pick) -> dict:
    """Min/Max (``pick`` = np.min / np.max) as Pilosa's
    ValCount.smaller/larger reduce defines them: the extreme value, with
    the count of the FIRST shard (ascending) that attains it."""
    ext = int(pick([pick(v) for v in per_shard if v.size]))
    first = next(v for v in per_shard if v.size and pick(v) == ext)
    return {"value": ext, "count": int((first == ext).sum())}


def ref_top(data: Data, n: int, src=None) -> list:
    counts = {r: popcount(data.row("f", r) if src is None else data.row("f", r) & src)
              for r in F_ROWS}
    ranked = sorted(counts.items(), key=lambda kv: (-kv[1], -kv[0]))[:n]
    return [{"id": r, "count": c} for r, c in ranked]


def ref_groupby(data: Data, outer: str, inner: str) -> list:
    rows = {"f": F_ROWS, "s": S_ROWS}
    return [
        {"group": [{"field": outer, "rowID": a}, {"field": inner, "rowID": b}],
         "count": popcount(data.row(outer, a) & data.row(inner, b))}
        for a in rows[outer] for b in rows[inner]
    ]


# -- checks -----------------------------------------------------------------


def plan_paths(plan: dict) -> list:
    return [op["path"] for op in plan["ops"] if "path" in op]


def device_answered(got, want, plan) -> bool:
    """The answer equals the reference and came first-seen (no memo hit)
    from a plan that never fell back to the host tier."""
    return (
        got == want
        and "host_fallback" not in plan_paths(plan)
        and not any(op.get("memo") == "hit" for op in plan["ops"])
    )


def check(client, report, name, pql, want, expect_path):
    """One checked query.  ``expect_path`` None marks a host lane by
    design: the plan must carry no device path at all."""
    got, plan = client.query(pql)
    paths = plan_paths(plan)
    ok = device_answered(got, want, plan) and (
        expect_path in paths if expect_path else not paths
    )
    entry = {"ok": ok, "path": paths, "ms": plan["durationMs"],
             "stages_ms": plan["stagesMs"]}
    for op in plan["ops"]:
        for k in ("kernel", "blocks_surviving", "blocks_total", "batch_size"):
            if k in op:
                entry[k] = op[k]
    report[name] = entry
    if not ok:
        entry.update(pql=pql, got=repr(got)[:200], want=repr(want)[:200])
        raise SmokeFailure(f"{name}: {json.dumps(entry)}")
    log(f"ok {name}: path={paths} {plan['durationMs']:.0f} ms")


def check_burst(port, child, report, name, wants: dict):
    """``wants`` ({pql: reference}) fired at once on 8 connections; every
    answer is checked, and the paths the batcher chose are reported."""
    plans = on_connections(port, child, list(wants), lambda c, q: c.query(q))
    for (pql, want), (got, plan) in zip(wants.items(), plans):
        if not device_answered(got, want, plan) or not plan_paths(plan):
            raise SmokeFailure(f"{name} {pql}: got {repr(got)[:200]}, "
                               f"want {repr(want)[:200]}, plan {plan['ops']}")
    paths = [p for _, plan in plans for p in plan_paths(plan)]
    report[name] = {
        "ok": True, "queries": len(wants),
        "paths": {p: paths.count(p) for p in sorted(set(paths))},
        "max_batch_size": max(op.get("batch_size", 1) for _, pl in plans for op in pl["ops"]),
    }
    log(f"ok {name}: {report[name]}")


# -- phases -----------------------------------------------------------------


def load(args, port, child, data: Data) -> float:
    client = Client(port, child)
    client.call("POST", f"/index/{INDEX}", b"{}")
    client.call("POST", f"/index/{INDEX}/field/f", b"{}")
    client.call("POST", f"/index/{INDEX}/field/s", b"{}")
    client.call("POST", f"/index/{INDEX}/field/v",
                json.dumps({"options": {"type": "int", "min": 0, "max": 255}}).encode())
    t0 = time.monotonic()

    def load_shard(conn, shard):
        for (field, q), body in make_shard(data, args.seed, shard).items():
            conn.call("POST", f"/index/{INDEX}/field/{field}/import-roaring/{shard}{q}", body)
        if shard % 96 == 95:
            log(f"loaded shard {shard + 1}/{args.shards}")

    on_connections(port, child, list(range(args.shards)), load_shard)
    return time.monotonic() - t0


def queries(port, child, data: Data, report: dict):
    client = Client(port, child)
    q_first = "Count(Intersect(Row(f=1), Row(f=2)))"
    first_ref = popcount(tree_words(data, "Intersect", 1, 2))
    check(client, report, "count_intersect", q_first, first_ref, "dense")
    tree = (data.row("f", 1) | data.row("f", 2)) & ~data.row("f", 3) ^ data.row("f", 4)
    check(client, report, "count_tree4",
          "Count(Xor(Difference(Union(Row(f=1), Row(f=2)), Row(f=3)), Row(f=4)))",
          popcount(tree), "dense")

    check(client, report, "count_sparse", "Count(Intersect(Row(s=1), Row(s=2)))",
          popcount(data.row("s", 1) & data.row("s", 2)), "sparse")
    sp = report["count_sparse"]
    if not sp["blocks_surviving"] * 4 <= sp["blocks_total"]:
        raise SmokeFailure(f"sparse plan kept too many blocks: {sp}")

    live = values_under(data)
    check(client, report, "sum", "Sum(field=v)", ref_sum(live), "direct")
    check(client, report, "min", "Min(field=v)", ref_extreme(live, np.min), "direct")
    check(client, report, "max", "Max(field=v)", ref_extreme(live, np.max), "direct")
    check(client, report, "count_range", "Count(Range(v > 100))",
          sum(int((v > 100).sum()) for v in live), "dense")

    # TopN without a source is the rank-cache lane: host by design.
    check(client, report, "topn", "TopN(f, n=4)", ref_top(data, 4), None)
    check(client, report, "topn_src", "TopN(f, Row(s=1), n=2)",
          ref_top(data, 2, src=data.row("s", 1)), "direct")
    check(client, report, "groupby", "GroupBy(Rows(field=f), Rows(field=s))",
          ref_groupby(data, "f", "s"), "direct")

    # One drain of different kinds is what whole-program fusion
    # (fused_tree) serves.  Whether concurrent arrivals share a drain is
    # the batcher's timing, so the paths are reported, not required; the
    # references are ready first so the burst below is still hot when
    # these fire.
    mixed = {
        "Sum(Row(f=1), field=v)": ref_sum(values_under(data, data.row("f", 1))),
        "Max(Row(f=2), field=v)":
            ref_extreme(values_under(data, data.row("f", 2)), np.max),
        "TopN(f, Row(s=2), n=3)": ref_top(data, 3, src=data.row("s", 2)),
        "GroupBy(Rows(field=s), Rows(field=f))": ref_groupby(data, "s", "f"),
        "Count(Union(Row(s=3), Row(f=5)))": popcount(data.row("s", 3) | data.row("f", 5)),
        "Count(Difference(Row(f=6), Row(s=4)))":
            popcount(data.row("f", 6) & ~data.row("s", 4)),
        "Count(Range(v < 17))": sum(int((v < 17).sum()) for v in live),
        "Count(Xor(Row(f=3), Row(f=4)))": popcount(data.row("f", 3) ^ data.row("f", 4)),
    }

    # 64 distinct Count trees, one structure per op so concurrent arrivals
    # share a count_batch_tree compile group; Intersect (1,2)/(2,1) are
    # held back for the first and the post-write queries.  With 8
    # closed-loop connections all but the first arrival queue behind a
    # dispatch in flight, so at least one drain must have batched.
    held = (("Intersect", 1, 2), ("Intersect", 2, 1))
    trees = [(op, a, b) for op in ("Intersect", "Union", "Difference")
             for a in F_ROWS for b in F_ROWS if a != b and (op, a, b) not in held][:64]
    check_burst(port, child, report, "burst",
                {f"Count({op}(Row(f={a}), Row(f={b})))": popcount(tree_words(data, op, a, b))
                 for op, a, b in trees})
    burst = report["burst"]
    burst["ok"] = "dense_batch" in burst["paths"] and burst["max_batch_size"] > 1
    if not burst["ok"]:
        raise SmokeFailure(f"burst never batched: {burst}")
    check_burst(port, child, report, "mixed", mixed)

    # Write, acknowledged, read back: a column absent from row 1 and
    # present in row 2, so the first Count moves by exactly one.
    before = client.debug_vars()["engineCaches"]
    candidates = ~data.f[0, 0] & data.f[1, 0]
    word = int(np.nonzero(candidates)[0][0])
    col = word * 64 + int(candidates[word]).bit_length() - 1
    got, _ = client.query(f"Set({col}, f=1)")
    if got is not True:
        raise SmokeFailure(f"Set({col}, f=1) not acknowledged as a change: {got}")
    got, plan = client.query(q_first)  # same text: the memo entry is repaired
    report["write_repeat"] = {"ok": got == first_ref + 1, "path": plan_paths(plan)}
    if got != first_ref + 1:
        raise SmokeFailure(f"repeat after write: got {got}, want {first_ref + 1}")
    # First-seen text over the written row: must dispatch, so the stack
    # must have taken the write — by the donated scatter, not a rebuild.
    check(client, report, "write_readback", "Count(Intersect(Row(f=2), Row(f=1)))",
          first_ref + 1, "dense")
    after = client.debug_vars()["engineCaches"]
    moved = report["write_readback"]
    moved["stack_rebuilds"] = after["stackRebuilds"] - before["stackRebuilds"]
    moved["stack_updates"] = after["stackUpdates"] - before["stackUpdates"]
    if moved["stack_rebuilds"] != 0 or moved["stack_updates"] < 1:
        raise SmokeFailure(f"write did not scatter in place: {moved}")


def run(args, port, child) -> dict:
    reduced = [] if args.shards == FULL_SHARDS else [f"shards {args.shards} < {FULL_SHARDS}"]
    client = Client(port, child)
    wait_ready(client)

    # Who holds the devices, asked of the process that does.
    dv = client.debug_vars()
    mesh = dv["mesh"]
    log(f"server mesh: {mesh['platform']} / {mesh['deviceKind']} x {mesh['devices']}; "
        f"compile cache {dv['compileCacheDir']}; native {dv['native']}")
    if mesh["platform"] != "tpu" and not (args.allow_cpu and mesh["platform"] == "cpu"):
        raise SmokeFailure(f"server runs on platform {mesh['platform']!r}, not tpu")
    native = "built" if set(dv["native"].values()) == {"built"} else "unavailable"
    if native != "built":
        raise SmokeFailure(f"native libraries unavailable: {dv['native']}")

    data = Data(args.shards)
    load_s = load(args, port, child, data)
    log(f"load: {args.shards} shards x {len(F_ROWS) + len(S_ROWS) + V_DEPTH + 1} "
        f"row-planes in {load_s:.1f} s over {CONNECTIONS} connections")

    client = Client(port, child)  # the first idled past the server's keep-alive
    m0 = client.metrics()
    report = {}
    t0 = time.monotonic()
    queries(port, child, data, report)
    query_s = time.monotonic() - t0

    # The chip answered.
    m1 = client.metrics()
    dv = client.debug_vars()
    mesh = dv["mesh"]
    per_device = [d.get("bytes_in_use") for d in mesh["perDevice"]]
    facts = {
        "host_fallbacks": int(m1["pilosa_engine_host_fallbacks_total"]),
        "psum_dispatches": int(m1["pilosa_mesh_psum_dispatches_total"]
                               - m0.get("pilosa_mesh_psum_dispatches_total", 0)),
        "mesh_devices": int(m1["pilosa_mesh_devices"]),
        "resident_bytes": int(m1["pilosa_engine_resident_bytes"]),
    }
    if facts["host_fallbacks"] or facts["psum_dispatches"] <= 0 \
            or facts["mesh_devices"] != mesh["devices"]:
        raise SmokeFailure(f"the device path did not answer: {facts}")
    if not reduced and facts["resident_bytes"] < 2 << 30:
        raise SmokeFailure(f"only {facts['resident_bytes']} B resident in HBM")
    if len(per_device) > 1 and None not in per_device \
            and max(per_device) - min(per_device) > 0.02 * max(per_device):
        raise SmokeFailure(f"per-device bytes differ: {per_device}")
    return {
        "ok": True,
        "platform": mesh["platform"], "device_kind": mesh["deviceKind"],
        "n_devices": mesh["devices"],
        "shards": args.shards, "columns": args.shards * SHARD_WIDTH,
        "shards_per_device": int(m1["pilosa_mesh_shards_per_device"]),
        **facts, "per_device_bytes_in_use": per_device,
        "ingest_route": "POST /index/{i}/field/{f}/import-roaring/{shard}",
        "connections": CONNECTIONS,
        "load_s": round(load_s, 1), "query_s": round(query_s, 1),
        "compile_s": round(sum(v for k, v in m1.items()
                               if k.startswith("pilosa_engine_compile_seconds")), 1),
        "compiles": int(m1["pilosa_engine_compile_total"]),
        "compile_cache_dir": dv["compileCacheDir"], "native": native,
        "sparse_kernel": report["count_sparse"].get("kernel"),
        "seed": args.seed, "reduced": reduced, "queries": report,
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--shards", type=int, default=FULL_SHARDS,
                    help="cut of scale, printed under 'reduced'")
    ap.add_argument("--allow-cpu", action="store_true",
                    help="sandbox rehearsal: accept a CPU server (never the default)")
    args = ap.parse_args()

    if "jax" in sys.modules:
        raise SmokeFailure("the smoke's own process must stay off JAX")
    if not os.path.isdir(os.path.join(HERE, "pilosa_tpu")):
        raise SmokeFailure(f"no pilosa_tpu package beside {__file__}")

    def on_alarm(signum, frame):
        raise SmokeFailure(f"not done after {DEADLINE_S} s")

    signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(DEADLINE_S)

    env = dict(os.environ, PYTHONPATH=HERE)
    if args.allow_cpu:
        env["JAX_PLATFORMS"] = "cpu"
    port = free_port()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        server_log = os.path.join(tmp, "server.log")
        with open(server_log, "wb") as out:
            child = subprocess.Popen(
                [sys.executable, "-m", "pilosa_tpu", "server",
                 "-d", os.path.join(tmp, "data"), "-b", f"127.0.0.1:{port}"],
                cwd=HERE, env=env, stdout=out, stderr=subprocess.STDOUT,
                start_new_session=True,
            )
        try:
            report = run(args, port, child)
        except BaseException:
            with open(server_log, "rb") as f:
                tail = f.read()[-6000:].decode(errors="replace")
            print(f"--- server log tail ---\n{tail}", file=sys.stderr, flush=True)
            raise
        finally:
            signal.alarm(0)
            if child.poll() is None:
                os.killpg(child.pid, signal.SIGTERM)
                try:
                    child.wait(30)
                except subprocess.TimeoutExpired:
                    os.killpg(child.pid, signal.SIGKILL)
                    child.wait(30)
    print(json.dumps(report))
    print(json.dumps({"ok": True, "device": {
        "platform": report["platform"], "kind": report["device_kind"],
        "count": report["n_devices"]}}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        sys.exit(1)

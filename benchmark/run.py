#!/usr/bin/env python3
"""benchmark/run.py — one cell of BENCHMARK.json on the served path.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Starts ``python -m pilosa_tpu server`` (default configuration) as its only
child and only JAX process, creates the cell's schema, ingests the
deployment through ``import-roaring`` while it adds each shard's raw
columns into the joint table (the plain reference), warms up with the mix
itself, measures the window with the mix's loop, stops the child, and
compares every reply of the window with the table.  The last line of
stdout is the result.  However the run ends (its end, a failure, its
deadline, a signal), every process it started is ended first, and a run
that cannot end one fails naming it (lib/served.py).  Everything that
belongs to one configuration, mix, template or per-layer metric lives in a
file of its own that this program finds by the name in BENCHMARK.json
(benchmark/README.md).

This process never imports JAX.  A server that is not on ``tpu`` with the
cell's number of chips ends the run non-zero with no result; ``--rehearse``
(CPU server, 8 shards unless ``--shards`` says otherwise) is the sandbox
rehearsal and prints ``"platform": "cpu"``.

``--trace 1`` splits the window in two: the /metrics deltas are read over
the first part, which no profiler disturbs; then one trace is taken
through the server's own route, and inside it, between two pauses in
which nothing is in flight, the loop runs TRACED_S seconds more.  Every
device operation of the trace then belongs to a request of that part, so
bytes and device seconds are counted over the same requests.
"""

import argparse
import importlib.util
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import numpy as np  # noqa: E402

from lib import served  # noqa: E402
from lib.served import BenchFailure, Client, log  # noqa: E402

INGEST_CONNECTIONS = 8
TRACE_SECONDS = 8  # what the route is asked for (it caps at 10)
TRACE_START_S = 3.0  # pause after the POST: the profiler starts meanwhile
TRACED_S = 3.0  # the loop's run inside the trace; the rest is its tail
DEADLINE_S = 1150  # the contract allows 1200 for a run that compiles
REHEARSAL_SHARDS = 8
SERVER_ARGV = [sys.executable, "-m", "pilosa_tpu", "server"]
ENDING_SIGNALS = (signal.SIGALRM, signal.SIGTERM, signal.SIGINT, signal.SIGHUP)


def load_module(path: str):
    name = "bench_" + os.path.relpath(path, HERE).replace(os.sep, "_").replace("-", "_")[:-3]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.isfile(path):
        raise BenchFailure(f"no such file: {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(*parts):
    path = os.path.join(*parts)
    if not os.path.isfile(path):
        raise BenchFailure(f"no such file: {path}")
    with open(path) as f:
        return json.load(f)


class Cell:
    """What BENCHMARK.json and the files it names say about one workload."""

    def __init__(self, workload: str, traffic: str = None):
        self.bench = load_json(ROOT, "BENCHMARK.json")
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if workload not in cells:
            raise BenchFailure(f"no workload {workload!r} in BENCHMARK.json")
        self.workload = cells[workload]
        config = next(c for c in self.bench["configs"] if c["name"] == self.workload["config"])
        self.cfg = load_json(ROOT, config["file"])
        self.gen = load_module(os.path.join(ROOT, config["file"][:-5] + ".py"))
        self.mix = load_json(HERE, "traffic", (traffic or self.workload["traffic"]) + ".json")
        self.loop = load_module(os.path.join(HERE, "loops", self.mix["loop"] + ".py"))
        self.control = load_module(os.path.join(HERE, "controls", self.cfg["control"] + ".py"))
        tdir = os.path.join(HERE, "queries", self.cfg["name"])
        sys.path.insert(0, tdir)
        self.templates = {
            t: load_module(os.path.join(tdir, t + ".py")) for t in self.mix["templates"]
        }
        self.path = f"/index/{self.cfg['index']}/query"

    def metrics(self, group: str) -> list:
        name = self.workload["name"]
        return [m for m in self.bench[group] if name in m.get("workloads", [name])]


class Request:
    __slots__ = ("template", "key", "calls", "wire")

    def __init__(self, template, key, calls, path):
        self.template = template
        self.key = key
        self.calls = calls
        body = " ".join(calls).encode()
        self.wire = (f"POST {path} HTTP/1.1\r\nHost: bench\r\n"
                     f"Content-Length: {len(body)}\r\n\r\n").encode() + body


class Traffic:
    """The one general generator.  Every seed sends the same requests in
    another order, so that no seed changes the work: each template's
    requests come from a stream of their own that does not depend on
    ``--seed`` and are handed out in chunks that the seed shuffles; the
    templates take turns in blocks that hold each exactly by its weight,
    shuffled by the seed.  No call text is sent twice in a run."""

    BLOCK = 60  # requests in which every template appears by its weight
    CHUNK = 64  # requests of one template whose order the seed shuffles

    def __init__(self, cell: Cell, seed: int):
        self.cell = cell
        self.order = np.random.default_rng([seed, 0x7261666669])  # not a shard's stream
        self.names = list(cell.mix["templates"])
        w = np.array([cell.mix["templates"][t] for t in self.names], float)
        share = w / w.sum() * self.BLOCK
        self.per_block = np.floor(share).astype(int)
        for i in np.argsort(share - self.per_block)[::-1][:self.BLOCK - self.per_block.sum()]:
            self.per_block[i] += 1  # largest remainders first
        self.streams = {t: np.random.default_rng([0x706F70, i])
                        for i, t in enumerate(self.names)}
        self.ready = {t: [] for t in self.names}
        self.block = []
        self.sent_texts = set()
        self.redraws = 0

    def _draw(self, name: str) -> Request:
        mod, rng = self.cell.templates[name], self.streams[name]
        for _ in range(1000):
            calls, key = mod.draw(rng, self.cell.cfg)
            if self.sent_texts.isdisjoint(calls):
                self.sent_texts.update(calls)
                return Request(name, key, calls, self.cell.path)
            self.redraws += 1
        raise BenchFailure(f"template {name}: no unsent call text in 1000 draws")

    def next(self, name: str = None) -> Request:
        if name is None:
            if not self.block:
                self.block = [t for t, n in zip(self.names, self.per_block) for _ in range(n)]
                self.order.shuffle(self.block)
            name = self.block.pop()
        if not self.ready[name]:
            self.ready[name] = [self._draw(name) for _ in range(self.CHUNK)]
            self.order.shuffle(self.ready[name])
        return self.ready[name].pop()


# -- set-up -----------------------------------------------------------------


def create_schema(client: Client, cfg: dict):
    """The configuration's index and fields.  Its optional ``index_options``
    is the body of the index's POST; a set field's optional ``options``
    (``cacheType``, ``cacheSize``, ``keys``, ``timeQuantum``) is its
    field's, and an int field's range always is."""
    index_body = {"options": cfg["index_options"]} if "index_options" in cfg else {}
    client.call("POST", f"/index/{cfg['index']}", json.dumps(index_body).encode())
    for f in cfg["fields"]:
        opts = dict(f.get("options", {}))
        if f["type"] == "int":
            opts.update(type="int", min=f["min"], max=f["max"])
        client.call("POST", f"/index/{cfg['index']}/field/{f['name']}",
                    json.dumps({"options": opts} if opts else {}).encode())


def ingest(cell: Cell, seed: int, shards: int, port: int, child, lost=None):
    """Every shard through import-roaring; returns the joint table (and,
    for the control, the table that never saw the ``lost`` shards)."""
    cfg, gen = cell.cfg, cell.gen
    table, control = gen.Table(cfg), (gen.Table(cfg) if lost is not None else None)
    lock = threading.Lock()
    done = [0]

    def load_shard(conn, shard):
        imports, contribution = gen.make_shard(seed, shard, cfg)
        for field, q, body in imports:
            conn.call("POST",
                      f"/index/{cfg['index']}/field/{field}/import-roaring/{shard}{q}", body)
        with lock:
            table.add(contribution)
            if control is not None and shard not in lost:
                control.add(contribution)
            done[0] += 1
            if done[0] % 16 == 0:
                log(f"loaded {done[0]}/{shards} shards; server RSS {rss_mb(child.pid)} MB, "
                    f"harness RSS {rss_mb(os.getpid())} MB")

    served.on_connections(port, child, list(range(shards)), load_shard,
                          cfg.get("ingest_connections", INGEST_CONNECTIONS))
    table.finish()
    if control is not None:
        control.finish()
    return table, control


def rss_mb(pid: int) -> int:
    try:
        with open(f"/proc/{pid}/status") as f:
            return next(int(ln.split()[1]) // 1024 for ln in f if ln.startswith("VmRSS"))
    except (OSError, StopIteration):
        return -1


def compile_total(m: dict) -> float:
    return sum(v for k, v in m.items() if k.startswith("pilosa_engine_compile_total"))


def warm_up(loop, traffic: Traffic, admin: Client, spec: dict) -> dict:
    """The mix's ``warm_up``: every template alone in bursts of each of
    ``widths`` connections, twice (the server compiles one program per
    filter structure and batch tier), then the mix itself on the window's
    connections until no program has compiled for ``quiet_s`` (at least
    ``min_s``).  A mix that still compiles after ``max_s`` goes on to the
    window as it is: engine.compiles_in_window and the notes show it."""
    t0 = time.monotonic()
    last_rise, seen, sent = t0, compile_total(admin.metrics()), 0

    def checked(out):
        bad = [e for e in out if e.status != 200]
        if bad:
            raise BenchFailure(f"warm-up: HTTP {bad[0].status} {bad[0].body[:300]!r} "
                               f"to {bad[0].request.calls}")
        return len(out)

    for name in traffic.names:
        for width in sorted(spec["widths"]) * 2:
            sent += checked(loop.run(0, lambda: traffic.next(name), width)[0])
        log(f"warm-up: {name} swept, {compile_total(admin.metrics()):.0f} compiles")
    quiet = True
    while True:
        sent += checked(loop.run(1.0, traffic.next)[0])
        now, total = time.monotonic(), compile_total(admin.metrics())
        if total > seen:
            log(f"warm-up: {total:.0f} compiles after {sent} requests")
            seen, last_rise = total, now
        if now - t0 >= spec["min_s"] and now - last_rise >= spec["quiet_s"]:
            break
        if now - t0 > spec["max_s"]:
            quiet = False
            log(f"warm-up: programs still compiling after {spec['max_s']} s "
                f"({seen:.0f} compiles); the window will compile too")
            break
    return {"seconds": time.monotonic() - t0, "requests": sent, "compiles": seen,
            "went_quiet": quiet}


def plan_paths(plan: dict) -> list:
    return [op["path"] for op in plan["ops"] if "path" in op]


def device_lane_misses(cell: Cell, traffic: Traffic, admin: Client, table) -> list:
    """One checked request per template with ?profile=1 (never in the
    window: it changes the request): the plan must show a device path, no
    host_fallback and no memo hit, and the answer must be the table's."""
    misses = []
    for name, mod in cell.templates.items():
        req = traffic.next(name)
        doc = json.loads(admin.call("POST", cell.path + "?profile=1",
                                    " ".join(req.calls).encode()))
        paths = plan_paths(doc["plan"])
        ok = (doc["results"] == mod.answer(table, req.key)
              and paths and "host_fallback" not in paths
              and not any(op.get("memo") == "hit" for op in doc["plan"]["ops"]))
        log(f"profiled {name}: paths={paths} ok={ok}")
        if not ok:
            misses.append({"template": name, "calls": req.calls, "paths": paths,
                           "got": repr(doc["results"])[:300]})
    return misses


# -- the window -------------------------------------------------------------


def take_trace(port, child, trace_dir, box):
    """One trace through the server's own route, on a connection of its
    own: the route starts the profiler, sleeps, stops it, then replies."""
    c = Client(port, child, timeout=300)
    try:
        box["reply"] = json.loads(c.call(
            "POST", f"/debug/pprof/trace?seconds={TRACE_SECONDS}&dir={trace_dir}"))
    except Exception as e:  # reported by the caller: a traced run without a trace fails
        box["error"] = repr(e)
    c.close()


def judge(cell: Cell, exchanges: list, table, answers_from=None) -> dict:
    """Every reply of the window against the table.  ``answers_from`` (the
    control) stands in the program's place: its answers are judged instead
    of the replies."""
    wrong, unanswered, examples = set(), 0, []
    for e in exchanges:
        mod = cell.templates[e.request.template]
        want = mod.answer(table, e.request.key)
        if answers_from is not None:
            got = mod.answer(answers_from, e.request.key)
        elif e.t_done is None or e.status != 200:
            unanswered += 1
            continue
        else:
            got = json.loads(e.body).get("results")
        if got != want:
            wrong.add(id(e))
            if len(examples) < 3:
                examples.append({"calls": e.request.calls, "got": repr(got)[:200],
                                 "want": repr(want)[:200]})
    return {"wrong": wrong, "unanswered": unanswered, "examples": examples}


def percentile(sorted_values: list, q: float) -> float:
    """Nearest-rank percentile of an ascending list."""
    return sorted_values[min(len(sorted_values) - 1, int(np.ceil(q * len(sorted_values))) - 1)]


def reduce_trace(trace_dir: str, allow_host: bool) -> dict:
    """Reduce the .xplane.pb in a short-lived child (JAX on the CPU, after
    the server has exited; this process stays off JAX)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, os.path.join(HERE, "lib", "trace_reduce.py"), trace_dir]
    if allow_host:
        cmd.append("--allow-host")
    p = subprocess.run(cmd, env=env, capture_output=True, text=True, timeout=300)
    if p.returncode != 0:
        raise BenchFailure(f"trace reduction failed: {p.stderr[-2000:]}")
    return json.loads(p.stdout.splitlines()[-1])


def run(args, cell: Cell, tmp: str, port: int, child) -> dict:
    cfg = cell.cfg
    shards = args.shards or cfg["shards"]
    admin = Client(port, child)
    served.wait_ready(admin)
    mesh = admin.debug_vars()["mesh"]
    log(f"server mesh: {mesh['platform']} / {mesh['deviceKind']} x {mesh['devices']}")
    platform = "cpu" if args.rehearse else "tpu"  # --rehearse is the CPU rehearsal
    if mesh["platform"] != platform or mesh["devices"] != cell.workload["chips"]:
        raise BenchFailure(f"server runs on {mesh['platform']} x {mesh['devices']}, "
                           f"the cell asks for {platform} x {cell.workload['chips']}")
    peaks = load_json(HERE, "lib", "peaks.json")
    if mesh["deviceKind"] not in peaks and not args.rehearse:
        raise BenchFailure(f"device kind {mesh['deviceKind']!r} is not in lib/peaks.json")

    create_schema(admin, cfg)
    lost = cell.control.lost_shards(args.seed, shards) if args.control else None
    t = time.monotonic()
    table, control = ingest(cell, args.seed, shards, port, child, lost)
    ingest_s = time.monotonic() - t
    log(f"ingest: {shards} shards in {ingest_s:.1f} s")
    admin = Client(port, child)  # the first idled past the server's keep-alive

    traffic = Traffic(cell, args.seed)
    loop = cell.loop.Loop(port, cell.mix)
    warm = warm_up(loop, traffic, admin, cell.mix["warm_up"])
    log(f"warm-up: {warm}")
    misses = device_lane_misses(cell, traffic, admin, table)

    def in_use():
        per = [d.get("bytes_in_use", 0) for d in admin.debug_vars()["mesh"]["perDevice"]]
        return max(per) if per else 0

    mem = in_use()
    trace_dir, box, traced = os.path.join(tmp, "trace"), {}, []
    untraced_s = args.seconds - (TRACE_START_S + TRACED_S if args.trace else 0)
    if untraced_s < 1:
        raise BenchFailure(f"--trace 1 needs --seconds over {TRACE_START_S + TRACED_S + 1}")
    m0 = admin.metrics()
    setup_s = time.monotonic() - served.T0
    exchanges, t_open, t_close = loop.run(untraced_s, traffic.next)
    m1 = admin.metrics()  # nothing is in flight: the loop waits for its replies
    if args.trace:
        tracer = threading.Thread(target=take_trace, args=(port, child, trace_dir, box))
        tracer.start()
        time.sleep(TRACE_START_S)
        traced, _, t_close = loop.run(TRACED_S, traffic.next)
        exchanges = exchanges + traced
        tracer.join()
        m1_traced = admin.metrics()
    mem = max(mem, in_use())
    resident = int(m1.get("pilosa_engine_resident_bytes", 0))
    loop.close()
    admin.close()
    served.stop_server(child, port)  # frees the chip before the reference and the reducer run

    verdict = judge(cell, exchanges, table)

    def sound(e):
        return e.t_done is not None and e.status == 200 and id(e) not in verdict["wrong"]

    answered = [e for e in exchanges if sound(e)]
    program_wrong = len(verdict["wrong"])
    if control is not None:  # the control stands in the program's place
        verdict = judge(cell, exchanges, table, answers_from=control)
    in_window = [e for e in answered if e.t_done <= t_close]
    replied = answered or [e for e in exchanges if e.t_done is not None]
    if not replied:
        raise BenchFailure("no request of the window was answered at all")
    lat = sorted((e.t_done - e.t_send) * 1e3 for e in replied)  # all wrong: still a line
    end_to_end = {
        "query_rate": (len(in_window) / args.seconds, "queries/s"),
        "query_p50_ms": (percentile(lat, 0.50), "ms"),
        "query_p95_ms": (percentile(lat, 0.95), "ms"),
        "setup_s": (setup_s, "s"),
    }
    # What a window of half the length would have read, from the same run:
    # PERF.md compares the spreads of the two lengths with it.
    t_half = t_open + args.seconds / 2
    half = sorted((e.t_done - e.t_send) * 1e3 for e in replied if e.t_send < t_half)
    first_half = {
        "query_rate": sum(e.t_done <= t_half for e in answered) / (args.seconds / 2),
        "query_p50_ms": percentile(half, 0.50), "query_p95_ms": percentile(half, 0.95),
    } if half and not args.trace else None
    checks = {
        "wrong_answers": {"value": len(verdict["wrong"]), "limit": 0},
        "unanswered": {"value": verdict["unanswered"], "limit": 0},
        "device_lane_misses": {"value": len(misses), "limit": 0},
    }
    device = {"platform": mesh["platform"], "kind": mesh["deviceKind"],
              "count": mesh["devices"], "memory_peak_bytes": mem or resident}
    result = {
        "correct": all(c["value"] <= c["limit"] for c in checks.values()),
        "attempted": len(exchanges),
        "failed": len(exchanges) - len(answered),
    }
    ctx = {"m0": m0, "m1": m1, "turnaround_s": loop.turnaround, "chips": mesh["devices"],
           "peaks": peaks.get(mesh["deviceKind"]),
           "plane_shard_bytes": shards * served.PLANE_BYTES}
    notes = {"ingest_s": ingest_s, "warm_up": warm,
             "redraws": traffic.redraws, "resident_bytes": resident,
             "compared": len(exchanges), "examples": verdict["examples"],
             "lane_misses": misses, "seed": args.seed, "shards": shards,
             "control": cell.cfg["control"] if args.control else None,
             "program_wrong_answers": program_wrong,
             "compiles_in_window": compile_total(m1) - compile_total(m0),
             "first_half": first_half}

    if args.trace:
        if "error" in box or "reply" not in box:
            raise BenchFailure(f"the trace route failed: {box}")
        trace = reduce_trace(trace_dir, allow_host=args.rehearse)
        # Every device operation of the trace belongs to a request of the
        # traced part, if the profiler was running before its first send.
        # The device worked at least from the first reply to the last send;
        # a trace that holds less began late.
        sent = [e for e in traced if sound(e)]
        client_s = max(e.t_done for e in sent) - min(e.t_send for e in sent)
        at_least_s = max(e.t_send for e in sent) - min(e.t_done for e in sent)
        if trace["span_s"] < at_least_s - 0.05 and not args.rehearse:
            raise BenchFailure(f"the trace holds {trace['span_s']:.2f} s of device work, the "
                               f"device worked {at_least_s:.2f} s or more in the traced part: "
                               f"the profiler took over {TRACE_START_S} s to start")
        ctx.update(trace=trace, traced_s=client_s, traced=[
            cell.templates[e.request.template].planes(e.request.key) for e in sent])
        notes["trace"] = {"requests": len(sent), "client_s": client_s,
                          "span_s": trace["span_s"], "program_s": trace["program_s"],
                          "compiles": compile_total(m1_traced) - compile_total(m1),
                          "lines_seen": trace["seen"],
                          "programs": dict(sorted(trace["modules"].items(),
                                                  key=lambda kv: -kv[1]["seconds"])[:12])}
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["span_s"]
        result["breakdown"] = trace["breakdown"]
    layers = {}
    for m in cell.metrics("per_layer"):
        spec = load_json(HERE, "metrics", m["name"] + ".json")
        reader = load_module(os.path.join(HERE, "readers", spec["reader"] + ".py"))
        value = reader.read(ctx, spec.get("params", {}))
        if value is not None:  # a reader that finds nothing to read returns nothing
            layers[m["name"]] = {"value": value, "unit": m["unit"]}
    if args.trace:
        metrics = layers
    else:
        names = {m["name"] for m in cell.metrics("end_to_end")}
        metrics = {k: {"value": v, "unit": u} for k, (v, u) in end_to_end.items() if k in names}
        notes["per_layer_untraced"] = {k: v["value"] for k, v in layers.items()}
    notes["end_to_end_seen"] = {k: v for k, (v, _) in end_to_end.items()}
    result.update(metrics=metrics, device=device, notes=notes, checks=checks)
    return result


def main(argv=None, server_argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="sandbox rehearsal: CPU server, 8 shards (never the default)")
    ap.add_argument("--shards", type=int, default=0,
                    help="cut of scale for a rehearsal or a test, with --rehearse only")
    ap.add_argument("--traffic", default=None,
                    help="try another mix of benchmark/traffic on the cell's configuration "
                         "(not a cell: for a mix that is not one yet)")
    ap.add_argument("--control", action="store_true",
                    help="judge the configuration's control in the program's place")
    args = ap.parse_args(argv)
    if args.shards and not args.rehearse:
        raise BenchFailure("--shards goes with --rehearse only")
    if args.rehearse and not args.shards:
        args.shards = REHEARSAL_SHARDS
    if "jax" in sys.modules:
        raise BenchFailure("the benchmark's own process must stay off JAX")
    if not os.path.isdir(os.path.join(ROOT, "pilosa_tpu")):
        raise BenchFailure(f"no pilosa_tpu package in {ROOT}")
    cell = Cell(args.workload, args.traffic)

    def on_signal(signum, frame):
        """Whatever ends the run from outside ends it through the
        ``finally`` below; the first signal is the one that counts."""
        for s in ENDING_SIGNALS:
            signal.signal(s, signal.SIG_IGN)
        raise BenchFailure(f"not done after {DEADLINE_S} s" if signum == signal.SIGALRM
                           else f"ended by {signal.Signals(signum).name}")

    for s in ENDING_SIGNALS:
        signal.signal(s, on_signal)
    signal.alarm(DEADLINE_S)
    port = served.free_port()
    tmp = tempfile.mkdtemp(prefix="pilosa_bench_")
    server_log = os.path.join(tmp, "server.log")
    child = served.start_server(server_argv or SERVER_ARGV, ROOT,
                                os.path.join(tmp, "data"), port, server_log,
                                cpu_devices=cell.workload["chips"] if args.rehearse else 0)
    try:
        result = run(args, cell, tmp, port, child)
    except BaseException:
        with open(server_log, "rb") as f:
            tail = f.read()[-6000:].decode(errors="replace")
        print(f"--- server log tail ---\n{tail}", file=sys.stderr, flush=True)
        raise
    finally:
        signal.alarm(0)
        for s in ENDING_SIGNALS:  # nothing cuts the last sweep short
            signal.signal(s, signal.SIG_IGN)
        try:
            served.stop_server(child, port)  # a run that leaves a process has no result
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BenchFailure as e:
        print(f"benchmark FAILED: {e}", file=sys.stderr, flush=True)
        sys.exit(1)

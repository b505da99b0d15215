"""Round benchmark: ALL FIVE BASELINE.md configs + end-to-end HTTP
latency/QPS, framework path vs CPU — with a physics audit.

Prints one JSON line per metric; the LAST line is the north-star
`Count(Intersect(...))` p50 over a ~1-BILLION-column set field
(BASELINE.json: "Count(Intersect)/TopN p50 on a 1B-col index").

Configs (BASELINE.md "Targets"):
  1. single-shard `Row()`+`Count()`                  -> row_count_single_shard_p50
  2. N-row set-op tree over 10M columns              -> setops_tree_10M_cols_p50
  3. `TopN()`/`Sum()`/`Min()`/`Max()` on BSI         -> topn/sum/min/max_bsi_1B_cols_*
  4. time-quantum `Range()` (month-view cover)       -> timerange_1B_cols_p50
  5. 8-way `GroupBy`+`Count` shard reduce            -> groupby_8way_1B_cols_*
  +  HTTP end-to-end `Count` latency + concurrent QPS
  +  north star                                      -> count_intersect_1B_cols_p50

Methodology, stated plainly:
- Engine `*_p50` metrics are the median ON-DEVICE program duration
  from the XLA device trace (jax.profiler): the exact time the chip
  spent per query, free of per-dispatch host and transport cost, and
  still bound by the physics audit.  **Every rep uses different row
  ids** so no cross-query reuse is possible.
- Physics audit: each device metric reports the HBM bytes its program
  must read and the implied bandwidth; emit() CLAMPS any metric whose
  implied bandwidth would exceed the chip's SPEC (819 GB/s + 25% slack)
  to the physical floor and flags it `"clamped": true` — a conservative
  "at most this fast" claim (nothing may beat the memory system; an
  over-ceiling implied number means the stated must-read accounting,
  not the chip, was the limit).  The bench also measures achievable
  read bandwidth over a STREAM-style popcount-reduce (`hbm_read_gbs`,
  ~700-770 GB/s here) as telemetry.
- Metrics STREAM: each line prints as soon as its phase completes (the
  north star last), so a wall-clock-limited run still reports
  everything it measured.  Executables persist in the compile cache
  pilosa_tpu.compile_cache places.
- Host-reducing metrics are reported twice: `*_p50` is pipelined
  engine time (results on device, the serving pattern), `*_e2e_p50` is
  per-call synchronous wall clock including the device readback.
- `http_count_e2e_p50` is sequential per-request wall clock through a
  real localhost server; `http_count_qps` drives 8 concurrent clients
  to show per-request syncs overlap.
- `row_count_single_shard_p50` goes through the executor's O(1)
  cardinality lane (no device work), like the reference summing roaring
  container-`n` values.
- The reference publishes no numbers and no Go toolchain exists in this
  image (BASELINE.md), so vs_baseline is a host-CPU NumPy implementation
  of the same query over the same dense bitmaps — strictly faster than
  Pilosa's per-container Go loops, i.e. a conservative denominator.
"""

import json
import math
import statistics
import time

import numpy as np

N_SHARDS = 960  # 960 * 2^20 = ~1.007B columns
N_SHARDS_10M = 10  # config 2: 10 * 2^20 = ~10.5M columns
F_ROWS = 24  # rows 10..33 -> 12 disjoint north-star pairs
F10_ROWS = 128  # rows 100..227 -> 32 disjoint 4-row trees (one full batch)
TOPN_ROWS = 16
BSI_DEPTH = 8
GROUPS_A = 4
GROUPS_B = 2
GROUPS_C = 2  # 3-field fused GroupBy (round-4 VERDICT #4)
ROW_BYTES = 1 << 17  # one 2^20-bit shard row = 128 KiB
HTTP_REPS = 30

# v5e HBM spec: the hard physical ceiling for the audit.  The measured
# STREAM number is reported as telemetry and is usually ~700 GB/s, but
# a single measurement can come out low — a depressed *measurement*
# must not fail metrics that are under the *chip*.
V5E_HBM_SPEC_GBS = 819.0


def emit(metric, seconds, cpu_seconds, bytes_read=None):
    """Print one metric line NOW (metrics stream as phases finish, so a
    wall-clock-killed run still reports everything it measured; the
    north star is emitted last by construction).  The physics audit runs
    inline: nothing may beat the memory system.  The ceiling is the chip
    SPEC — a low STREAM measurement may undershoot the chip and must not
    fail valid metrics, and a noise-inflated one must not raise the bar
    above physics."""
    rec = {
        "metric": metric,
        "value": round(seconds * 1e6, 1),
        "unit": "us",
        "vs_baseline": round(cpu_seconds / seconds, 2),
    }
    if bytes_read is not None:
        ceiling = V5E_HBM_SPEC_GBS * 1.25
        implied = bytes_read / seconds / 1e9
        if implied > ceiling:
            # Nothing may beat the memory system: report the physical
            # floor as a conservative "at most this fast" claim, flagged
            # (XLA may legitimately read fewer bytes than the stated
            # must-read accounting when it CSEs or skips planes — the
            # flag says the accounting, not the chip, is the limit).
            progress(
                f"  {metric}: implied {implied:.0f} GB/s exceeds the "
                f"physical ceiling; clamping to the floor"
            )
            seconds = bytes_read / (ceiling * 1e9)
            rec["value"] = round(seconds * 1e6, 1)
            rec["vs_baseline"] = round(cpu_seconds / seconds, 2)
            rec["clamped"] = True
            implied = ceiling
        rec["bytes_read"] = bytes_read
        rec["implied_gbs"] = round(implied, 1)
    print(json.dumps(rec), flush=True)


def emit_raw(metric, value, unit, vs_baseline):
    print(
        json.dumps(
            {
                "metric": metric,
                "value": round(value, 2),
                "unit": unit,
                "vs_baseline": round(vs_baseline, 2),
            }
        ),
        flush=True,
    )


def _device_durations(trace_dir):
    """Parse the XLA device trace: {program_name: [durations_us]} for
    enclosing jit programs on the TPU plane.  Nested ops (fusions,
    copies) are excluded so nothing double-counts."""
    import glob
    import gzip

    out = {}
    for path in glob.glob(
        trace_dir + "/plugins/profile/*/*.trace.json.gz"
    ):
        doc = json.load(gzip.open(path, "rt"))
        evs = doc.get("traceEvents", [])
        pids = {
            e["pid"]: e.get("args", {}).get("name", "")
            for e in evs
            if e.get("ph") == "M" and e.get("name") == "process_name"
        }
        for e in evs:
            if e.get("ph") != "X":
                continue
            if "TPU" not in pids.get(e.get("pid"), ""):
                continue
            name = e.get("name", "")
            if not name.startswith("jit_"):
                continue
            out.setdefault(name, []).append(e.get("dur", 0))
    return out


def _traced(fn, reps):
    """Run ``reps`` pipelined dispatches under the device profiler;
    returns (durations-by-program, values, wall_per_query)."""
    import shutil
    import tempfile

    import jax

    d = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        jax.profiler.start_trace(d)
        try:
            t0 = time.perf_counter()
            vals = jax.device_get([fn(i) for i in range(reps)])
            wall = (time.perf_counter() - t0) / reps
        finally:
            jax.profiler.stop_trace()
        return _device_durations(d), vals, wall
    finally:
        shutil.rmtree(d, ignore_errors=True)


def device_p50(fn, reps=24, scale=1, total=False):
    """Median ON-DEVICE duration of the dominant XLA program across
    ``reps`` pipelined dispatches, read from the device trace.

    This is the honest engine time: wall clock carries per-dispatch
    host and transport cost that is NOT device work; the profiler's
    device timeline gives the exact program durations the chip actually
    spent (and can never beat physics — the emit() audit still
    applies).  ``scale`` divides for K-queries-per-dispatch
    batches; ``total=True`` sums EVERY program execution in the window
    and divides by reps (mixed write+query cycles, where scatter
    programs are part of the cost).  Falls back to pipelined wall clock
    per query (strictly pessimistic: includes transport) if the trace
    yields nothing.  Returns (seconds_per_query, values)."""
    by_name, vals, wall = _traced(fn, reps)
    if not by_name:
        progress("  device trace empty: falling back to wall clock")
        return wall / scale, vals
    if total:
        per = sum(sum(v) for v in by_name.values()) / reps / 1e6
    else:
        durs = sorted(max(by_name.values(), key=sum))
        per = durs[len(durs) // 2] / 1e6
    return per / scale, vals


def sync_p50(fn, reps=8):
    """Median wall-clock of per-call host-synchronous executions."""
    times = []
    out = None
    for i in range(reps):
        t0 = time.perf_counter()
        out = fn(i)
        times.append(time.perf_counter() - t0)
    return statistics.median(times), out


def cpu_time(fn, reps=3):
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return statistics.median(times)


def progress(msg, _t0=[None]):
    import sys
    if _t0[0] is None:
        _t0[0] = time.perf_counter()
    print(f"[{time.perf_counter() - _t0[0]:7.1f}s] {msg}", file=sys.stderr, flush=True)


def report_pipeline(eng):
    """Emit the batch pipeline's fill telemetry (round-6 tentpole:
    in-flight depth + batch occupancy are part of the bench record, so
    the QPS number can be attributed to pipelining, not guessed at)."""
    snap = eng.pipeline_snapshot()
    if snap is None or not snap["batches"]:
        return
    g = snap["gauges"]
    emit_raw("pipeline_depth_configured", snap["depth"], "batches", 1.0)
    emit_raw("pipeline_inflight_max", g.get("inflight_max", 0), "batches", 1.0)
    emit_raw("batch_occupancy_avg", snap["avgOccupancy"], "queries/batch", 1.0)
    emit_raw(
        "batch_occupancy_max", g.get("max_batch_occupancy", 0),
        "queries/batch", 1.0,
    )
    for stage, s in sorted(snap["stages"].items()):
        progress(
            f"  pipeline stage {stage}: n={s['count']} "
            f"mean={s['meanSeconds'] * 1e3:.2f}ms p99={s['p99Seconds'] * 1e3:.2f}ms"
        )


def report_observability(api):
    """Emit the always-on histogram surface (observability tentpole):
    pipeline-stage and query-op p50/p99 from the process registry — the
    engine-side latency numbers ROADMAP says the LATENCY axis is judged
    on — plus a sample trace id so a device-time number can be joined to
    its span tree at /debug/traces."""
    from pilosa_tpu.util.stats import (
        METRIC_PIPELINE_STAGE,
        METRIC_QUERY,
        METRIC_QUERY_OP,
        REGISTRY,
    )

    for stage in ("queue_wait", "lower_dispatch", "device_readback", "decode"):
        h = REGISTRY.get_histogram(METRIC_PIPELINE_STAGE, stage=stage)
        if h is not None and h.count:
            emit_raw(f"pipeline_{stage}_p50", h.quantile(0.50) * 1e6, "us", 1.0)
            emit_raw(f"pipeline_{stage}_p99", h.quantile(0.99) * 1e6, "us", 1.0)
    for path in ("sync", "pipelined"):
        h = REGISTRY.get_histogram(METRIC_QUERY, path=path)
        if h is not None and h.count:
            emit_raw(f"query_{path}_p50", h.quantile(0.50) * 1e6, "us", 1.0)
            emit_raw(f"query_{path}_p99", h.quantile(0.99) * 1e6, "us", 1.0)
    h = REGISTRY.get_histogram(METRIC_QUERY_OP, op="Count")
    if h is not None and h.count:
        emit_raw("query_op_count_p50", h.quantile(0.50) * 1e6, "us", 1.0)
    spans = api.tracer.finished_spans() if api is not None else []
    if spans:
        s = spans[-1]
        print(
            json.dumps(
                {
                    "metric": "sample_trace",
                    "traceID": s.trace_id,
                    "rootSpan": s.name,
                    "value": round((s.duration or 0.0) * 1e6, 1),
                    "unit": "us",
                    "vs_baseline": 1.0,
                }
            ),
            flush=True,
        )
        progress(
            f"  sample trace {s.trace_id}: {s.name} "
            f"{(s.duration or 0.0) * 1e3:.2f}ms, {len(s.children)} child spans "
            f"(join at /debug/traces)"
        )


SCRAPE_SERIES = (
    "pilosa_engine_resident_bytes",
    "pilosa_engine_evicted_bytes",
    "pilosa_engine_compile_total",
    "pilosa_engine_compile_cache_keys",
    'pilosa_engine_compile_seconds{phase="compile"}',
    'pilosa_engine_compile_seconds{phase="trace"}',
    "pilosa_engine_evictions_total",
    "pilosa_engine_stack_rebuilds_total",
    "pilosa_device_bytes_skipped_total",
)


def report_scrape(port):
    """--scrape: append the post-run /metrics device gauges (HBM
    residency, compile totals, eviction counters) to the JSONL stream,
    so a bench record carries the engine's end-state alongside its
    latency numbers and scripts/bench_guard.py can diff either."""
    import urllib.request

    text = urllib.request.urlopen(
        f"http://localhost:{port}/metrics", timeout=30
    ).read().decode()
    samples = {}
    for line in text.splitlines():
        if line.startswith("#"):
            continue
        name, sep, value = line.rpartition(" ")
        if sep:
            samples[name] = value
    for name in SCRAPE_SERIES:
        raw = samples.get(name)
        if raw is None:
            continue
        try:
            v = float(raw)
        except ValueError:
            continue
        # Deliberately dimensionless: cumulative counters and end-state
        # gauges have no regression direction bench_guard should enforce
        # by default.
        emit_raw(name, v, "bytes" if "bytes" in name else "", 1.0)


def main(depth_sweep=False, conn_sweep=False, scrape=False,
         workers_sweep=False):
    progress("importing jax")
    import jax
    import jax.numpy as jnp

    from pilosa_tpu import pql
    from pilosa_tpu.core.field import FieldOptions
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.ops import bitops
    from pilosa_tpu.parallel import MeshEngine, make_mesh

    progress(f"devices: {jax.devices()}")
    W64 = bitops.WORDS64
    rng = np.random.default_rng(42)
    holder = Holder()
    holder.open()

    # ---- measure achievable HBM read bandwidth ---------------------------
    # STREAM-style: popcount-reduce 1 GiB resident uint32 buffers (three
    # distinct buffers so no rep repeats an input).  Same op mix as the
    # query kernels (bitwise + popcount + reduce), measured with the same
    # marginal method — the honest ceiling for every implied number below.
    stream_words = (1 << 30) // 4
    streams = [
        jax.device_put(
            jnp.full((1 << 14, stream_words >> 14), i + 1, dtype=jnp.uint32)
        )
        for i in range(3)
    ]
    stream_fn = jax.jit(
        lambda x: jax.lax.population_count(x).astype(jnp.uint32).sum()
    )
    jax.device_get(stream_fn(streams[0]))  # warm/compile
    t_bw, _ = device_p50(lambda i: stream_fn(streams[i % 3]), reps=12)
    hbm_gbs = streams[0].nbytes / t_bw / 1e9
    del streams
    progress(f"measured HBM read bandwidth: {hbm_gbs:.0f} GB/s")
    # Telemetry only — the audit ceiling is the chip SPEC (see emit()):
    # a congested measurement must not fail metrics under the chip.
    emit_raw("hbm_read_gbs", hbm_gbs, "GB/s", 1.0)

    # ---- build: one 1B-col index + one 10M-col index + one 1-shard -------
    idx = holder.create_index("bench")
    f = idx.create_field("f")  # configs 1/NS: F_ROWS rows/shard
    topf = idx.create_field("top")  # config 3: TopN candidate field
    bsi = idx.create_field(
        "v", FieldOptions(type="int", min=0, max=(1 << BSI_DEPTH) - 1)
    )
    tf = idx.create_field("t", FieldOptions(type="time", time_quantum="YMD"))
    ga = idx.create_field("ga")  # config 5
    gb = idx.create_field("gb")
    gc = idx.create_field("gc")  # 3-field fused GroupBy

    host = {}  # (index, field, view) -> {shard: {row: words}}

    def build(index_name, field, view_name, shard, row_id, words, keep=True):
        frag = field.view_if_not_exists(view_name).fragment_if_not_exists(shard)
        frag.load_row_words(row_id, words)
        if keep:  # host copies only where a CPU baseline reads them
            host.setdefault((index_name, field.name, view_name), {}).setdefault(
                shard, {}
            )[row_id] = words

    t_build0 = time.perf_counter()
    full = np.full(W64, 0xFFFFFFFFFFFFFFFF, dtype=np.uint64)
    for s in range(N_SHARDS):
        for r in range(10, 10 + F_ROWS):
            build("bench", f, "standard", s, r, __rand(rng, W64),
                  keep=(r in (10, 11)))
        for r in range(TOPN_ROWS):
            build(
                "bench", topf, "standard", s, r,
                __rand(rng, W64) & __rand(rng, W64),
            )
        for p in range(BSI_DEPTH):
            build("bench", bsi, "bsig_v", s, p, __rand(rng, W64))
        build("bench", bsi, "bsig_v", s, BSI_DEPTH, full.copy())
        for tr in (7, 8):
            row_t = __rand(rng, W64)
            build("bench", tf, "standard", s, tr, row_t, keep=(tr == 7))
            for mv in ("standard_2018", "standard_201801", "standard_201802",
                       "standard_201803"):
                build("bench", tf, mv, s, tr, row_t, keep=(tr == 7))
        for g in range(GROUPS_A):
            build("bench", ga, "standard", s, g,
                  __rand(rng, W64) & __rand(rng, W64))
        for g in range(GROUPS_B):
            build("bench", gb, "standard", s, g,
                  __rand(rng, W64) & __rand(rng, W64))
        for g in range(GROUPS_C):
            build("bench", gc, "standard", s, g,
                  __rand(rng, W64) & __rand(rng, W64))
    idx10 = holder.create_index("b10m")
    f10 = idx10.create_field("f")
    v10 = idx10.create_field(  # mixed-kind QPS: Sum target on b10m
        "v10", FieldOptions(type="int", min=0, max=(1 << BSI_DEPTH) - 1)
    )
    for s in range(N_SHARDS_10M):
        for r in range(100, 100 + F10_ROWS):
            build("b10m", f10, "standard", s, r, __rand(rng, W64),
                  keep=(r in (100, 101, 102, 103)))
        for p in range(BSI_DEPTH):
            build("b10m", v10, "bsig_v10", s, p, __rand(rng, W64))
        build("b10m", v10, "bsig_v10", s, BSI_DEPTH, full.copy())
    idx1 = holder.create_index("b1")
    f1 = idx1.create_field("f")
    for r in range(10, 10 + F_ROWS):
        build("b1", f1, "standard", 0, r, __rand(rng, W64), keep=(r == 10))
    for field in (f, topf, bsi, tf, ga, gb, gc, f10, v10, f1):
        for v in field.views.values():
            for frag in v.fragments.values():
                frag.cache.invalidate()
    build_s = time.perf_counter() - t_build0
    progress(f"build done in {build_s:.1f}s")

    shards = list(range(N_SHARDS))
    shards10 = list(range(N_SHARDS_10M))
    mesh = make_mesh(len(jax.devices()))
    eng = MeshEngine(holder, mesh, max_resident_bytes=12 << 30)
    ex = Executor(holder, mesh_engine=eng)
    ex1 = Executor(holder, mesh_engine=eng)

    # ---- pure-device configs first (no host readbacks while timing) ------
    # North star: 12 disjoint row pairs, every rep a different pair.
    ns_calls = [
        pql.parse(f"Intersect(Row(f={10 + 2 * k}), Row(f={11 + 2 * k}))").calls[0]
        for k in range(F_ROWS // 2)
    ]
    jax.device_get(eng.count_async("bench", ns_calls[0], shards))
    progress("north-star warm done")
    t_ns, r_ns_all = device_p50(
        lambda i: eng.count_async("bench", ns_calls[i % len(ns_calls)], shards),
        reps=24,
    )
    progress("north-star timed")

    # Config 2: 10 disjoint 4-row trees.  The work per query is 5 MB of
    # HBM (~6 us at spec) — far below the per-program dispatch floor —
    # so the architecture serves these BATCHED: the micro-batcher drains
    # K concurrent queries into ONE count_batch_tree dispatch
    # (parallel/batcher.py).  The headline metric is the marginal
    # per-query cost in that serving steady state (K=16 per dispatch,
    # every slot a different tree); the single-dispatch cost is also
    # reported as telemetry for the lone-query case.
    c2_calls = []
    for k in range(F10_ROWS // 4):
        b = 100 + 4 * k
        c2_calls.append(pql.parse(
            f"Xor(Difference(Union(Row(f={b}), Row(f={b + 1})), "
            f"Row(f={b + 2})), Row(f={b + 3}))"
        ).calls[0])
    jax.device_get(eng.count_async("b10m", c2_calls[0], shards10))
    t_c2_single, r_c2_all = device_p50(
        lambda i: eng.count_async("b10m", c2_calls[i % len(c2_calls)], shards10),
        reps=32,
    )
    C2_B = 32  # queries per batched dispatch; 32 disjoint trees = 128
    # DISTINCT rows per batch, so XLA's CSE cannot merge row reads
    # across slots and the per-query byte accounting stays honest.

    def c2_batch(i):
        calls = [
            c2_calls[(i + j) % len(c2_calls)] for j in range(C2_B)
        ]
        return eng.count_many_async("b10m", calls, [shards10] * C2_B)

    jax.device_get(c2_batch(0))
    t_c2, _ = device_p50(c2_batch, reps=12, scale=C2_B)
    progress("config2 timed")

    # Config 4: alternate the two time rows across reps.
    c4_calls = [
        pql.parse(f"Range(t={tr}, 2018-01-01T00:00, 2018-04-01T00:00)").calls[0]
        for tr in (7, 8)
    ]
    jax.device_get(eng.count_async("bench", c4_calls[0], shards))
    t_c4, r_c4_all = device_p50(
        lambda i: eng.count_async("bench", c4_calls[i % 2], shards), reps=24
    )
    progress("config4 timed")

    # Config 3 engine times: TopN / Sum / Min / Max, results on device.
    topn_srcs = [pql.parse(f"Row(f={10 + k})").calls[0] for k in range(12)]
    eng.topn_full("bench", "top", topn_srcs[0], shards, 5, 0)
    t_top_eng, _ = device_p50(
        lambda i: eng.topn_full_async(
            "bench", "top", topn_srcs[i % len(topn_srcs)], shards, 5, 0
        )[2],
        reps=12,
    )
    progress("topn engine timed")

    t_sum_eng, _ = device_p50(
        lambda i: eng.sum_async("bench", "v", None, shards)[0], reps=12
    )
    # Min/Max stream the planes exactly once since the variadic
    # argmin-reduce rewrite (bsi.minmax_valcount_nd): implied_gbs is
    # the true traffic and sits at the HBM ceiling.
    t_min_eng, _ = device_p50(
        lambda i: eng.min_max_async("bench", "v", None, shards, True)[0], reps=12
    )
    t_max_eng, _ = device_p50(
        lambda i: eng.min_max_async("bench", "v", None, shards, False)[0], reps=12
    )
    progress("sum/min/max engine timed")

    t_gb_eng, _ = device_p50(
        lambda i: eng.group_counts_async(
            "bench", ["ga", "gb"], [list(range(GROUPS_A)), list(range(GROUPS_B))],
            None, shards,
        ),
        reps=12,
    )
    t_gb3_eng, _ = device_p50(
        lambda i: eng.group_counts_async(
            "bench", ["ga", "gb", "gc"],
            [list(range(GROUPS_A)), list(range(GROUPS_B)), list(range(GROUPS_C))],
            None, shards,
        ),
        reps=12,
    )
    progress("groupby engine timed")

    # ---- config 1: executor O(1) cardinality lane (no device work) -------
    c1_queries = [f"Count(Row(f={10 + k}))" for k in range(F_ROWS)]
    for q in c1_queries:  # build each query's prepared plan (the lane's
        ex1.execute("b1", q)  # steady state: clients repeat query texts)
    # µs-scale host path: time a 100-call loop per round (a single-call
    # median is dominated by scheduler jitter).
    t_c1 = min(
        cpu_time(
            lambda: [ex1.execute("b1", c1_queries[j % F_ROWS]) for j in range(100)],
            reps=1,
        )
        / 100
        for _ in range(5)
    )
    r_c1 = ex1.execute("b1", c1_queries[0]).results[0]
    progress("config1 timed")

    # ---- e2e configs (each query includes a sync readback) ---------------
    q_top = "TopN(top, Row(f=10), n=5)"
    ex.execute("bench", q_top)
    t_top, top_pairs = sync_p50(
        lambda i: ex.execute("bench", q_top).results[0], reps=6
    )
    progress("topn e2e timed")

    ex.execute("bench", "Sum(field=v)")
    t_sum, sum_vc = sync_p50(
        lambda i: ex.execute("bench", "Sum(field=v)").results[0], reps=6
    )
    ex.execute("bench", "Min(field=v)")
    t_min, min_vc = sync_p50(
        lambda i: ex.execute("bench", "Min(field=v)").results[0], reps=6
    )
    ex.execute("bench", "Max(field=v)")
    t_max, max_vc = sync_p50(
        lambda i: ex.execute("bench", "Max(field=v)").results[0], reps=6
    )

    q5_3 = "GroupBy(Rows(field=ga), Rows(field=gb), Rows(field=gc))"
    ex.execute("bench", q5_3)
    t_gb3, gb3_res = sync_p50(
        lambda i: ex.execute("bench", q5_3).results[0], reps=4
    )
    q5 = "GroupBy(Rows(field=ga), Rows(field=gb))"
    ex.execute("bench", q5)
    t_gb, gb_res = sync_p50(lambda i: ex.execute("bench", q5).results[0], reps=4)
    progress("sum/min/max/groupby e2e timed")

    # ---- correctness + CPU baselines -------------------------------------
    F = host[("bench", "f", "standard")]
    F10 = host[("b10m", "f", "standard")]
    TOP = host[("bench", "top", "standard")]
    V = host[("bench", "v", "bsig_v")]
    T = {mv: host[("bench", "t", mv)] for mv in
         ("standard_201801", "standard_201802", "standard_201803")}
    GA = host[("bench", "ga", "standard")]
    GB = host[("bench", "gb", "standard")]
    GC = host[("bench", "gc", "standard")]
    F1 = host[("b1", "f", "standard")]

    def pc(x):
        return int(np.sum(np.bitwise_count(x)))

    def cpu_ns():
        return sum(pc(rows[10] & rows[11]) for rows in F.values())

    assert cpu_ns() == int(r_ns_all[0])  # rep 0 is the (10, 11) pair
    c_ns = cpu_time(cpu_ns)

    def cpu_c1():
        return pc(F1[0][10])

    assert cpu_c1() == int(r_c1)
    c_c1 = cpu_time(cpu_c1, reps=9)

    def cpu_c2():
        return sum(
            pc(((rows[100] | rows[101]) & ~rows[102]) ^ rows[103])
            for rows in F10.values()
        )

    assert cpu_c2() == int(r_c2_all[0])
    c_c2 = cpu_time(cpu_c2, reps=9)

    def cpu_c4():
        total = 0
        for s in range(N_SHARDS):
            acc = T["standard_201801"][s][7].copy()
            for mv in ("standard_201802", "standard_201803"):
                acc |= T[mv][s][7]
            total += pc(acc)
        return total

    assert cpu_c4() == int(r_c4_all[0])  # rep 0 queries time row 7
    c_c4 = cpu_time(cpu_c4)

    def cpu_top():
        counts = {r: 0 for r in range(TOPN_ROWS)}
        for s, rows in TOP.items():
            src = F[s][10]
            for r in range(TOPN_ROWS):
                counts[r] += pc(rows[r] & src)
        return sorted(counts.items(), key=lambda kv: (-kv[1], kv[0]))[:5]

    want_top = cpu_top()
    got_top = [(p[0], p[1]) for p in top_pairs]
    assert got_top == want_top, (got_top, want_top)
    c_top = cpu_time(cpu_top, reps=1)

    def cpu_sum():
        total = n = 0
        for s, rows in V.items():
            nn = rows[BSI_DEPTH]
            n += pc(nn)
            for p in range(BSI_DEPTH):
                total += pc(rows[p] & nn) << p
        return total, n

    want_sum = cpu_sum()
    assert (sum_vc.val, sum_vc.count) == want_sum
    c_sum = cpu_time(cpu_sum, reps=1)

    def cpu_minmax(is_min):
        best = None
        for s, rows in V.items():
            keep = rows[BSI_DEPTH].copy()
            val = 0
            for p in range(BSI_DEPTH - 1, -1, -1):
                want_zero = keep & (~rows[p] if is_min else rows[p])
                if want_zero.any():
                    keep = want_zero
                    if not is_min:
                        val |= 1 << p
                elif is_min:
                    val |= 1 << p
            n = pc(keep)
            if best is None or (val < best[0] if is_min else val > best[0]):
                best = (val, n)
        return best

    want_min = cpu_minmax(True)
    assert min_vc.val == want_min[0], (min_vc.val, want_min)
    c_min = cpu_time(lambda: cpu_minmax(True), reps=1)
    want_max = cpu_minmax(False)
    assert max_vc.val == want_max[0], (max_vc.val, want_max)
    c_max = cpu_time(lambda: cpu_minmax(False), reps=1)

    def cpu_gb():
        counts = np.zeros((GROUPS_A, GROUPS_B), dtype=np.int64)
        for s in GA:
            for i in range(GROUPS_A):
                a = GA[s][i]
                for j in range(GROUPS_B):
                    counts[i, j] += pc(a & GB[s][j])
        return counts

    want_gb = cpu_gb()
    got_gb = {
        (g.group[0].row_id, g.group[1].row_id): g.count for g in gb_res
    }
    for i in range(GROUPS_A):
        for j in range(GROUPS_B):
            assert got_gb.get((i, j), 0) == int(want_gb[i, j]), (i, j)
    c_gb = cpu_time(cpu_gb, reps=1)

    def cpu_gb3():
        counts = np.zeros((GROUPS_A, GROUPS_B, GROUPS_C), dtype=np.int64)
        for s in GA:
            for i in range(GROUPS_A):
                a = GA[s][i]
                for j in range(GROUPS_B):
                    ab = a & GB[s][j]
                    for k in range(GROUPS_C):
                        counts[i, j, k] += pc(ab & GC[s][k])
        return counts

    want_gb3 = cpu_gb3()
    got_gb3 = {
        tuple(fr.row_id for fr in g.group): g.count for g in gb3_res
    }
    for i in range(GROUPS_A):
        for j in range(GROUPS_B):
            for k in range(GROUPS_C):
                assert got_gb3.get((i, j, k), 0) == int(want_gb3[i, j, k])
    c_gb3 = cpu_time(cpu_gb3, reps=1)

    progress("baselines done")
    emit("row_count_single_shard_p50", t_c1, c_c1)
    # Config 2 headline = marginal per-query cost in the batched serving
    # steady state (micro-batcher, K=16/dispatch); the single-dispatch
    # cost (dispatch-floor bound) is telemetry for the lone-query case.
    emit("setops_tree_10M_cols_p50", t_c2, c_c2,
         bytes_read=4 * N_SHARDS_10M * ROW_BYTES)
    emit("setops_tree_single_dispatch_p50", t_c2_single, c_c2,
         bytes_read=4 * N_SHARDS_10M * ROW_BYTES)
    emit("timerange_1B_cols_p50", t_c4, c_c4, bytes_read=3 * N_SHARDS * ROW_BYTES)
    emit("topn_1B_cols_p50", t_top_eng, c_top,
         bytes_read=(TOPN_ROWS + 1) * N_SHARDS * ROW_BYTES)
    emit("topn_1B_cols_e2e_p50", t_top, c_top)
    emit("sum_bsi_1B_cols_p50", t_sum_eng, c_sum,
         bytes_read=(BSI_DEPTH + 1) * N_SHARDS * ROW_BYTES)
    emit("sum_bsi_1B_cols_e2e_p50", t_sum, c_sum)
    emit("min_bsi_1B_cols_p50", t_min_eng, c_min,
         bytes_read=(BSI_DEPTH + 1) * N_SHARDS * ROW_BYTES)
    emit("min_bsi_1B_cols_e2e_p50", t_min, c_min)
    emit("max_bsi_1B_cols_p50", t_max_eng, c_max,
         bytes_read=(BSI_DEPTH + 1) * N_SHARDS * ROW_BYTES)
    emit("max_bsi_1B_cols_e2e_p50", t_max, c_max)
    emit("groupby_8way_1B_cols_p50", t_gb_eng, c_gb,
         bytes_read=(GROUPS_A + GROUPS_B) * N_SHARDS * ROW_BYTES)
    emit("groupby_8way_1B_cols_e2e_p50", t_gb, c_gb)
    emit("groupby_3field_1B_cols_p50", t_gb3_eng, c_gb3,
         bytes_read=(GROUPS_A + GROUPS_B + GROUPS_C) * N_SHARDS * ROW_BYTES)
    emit("groupby_3field_1B_cols_e2e_p50", t_gb3, c_gb3)


    # ---- HTTP end-to-end: sequential latency + concurrent QPS -----------
    import urllib.request
    from concurrent.futures import ThreadPoolExecutor

    from pilosa_tpu.api import API
    from pilosa_tpu.net.server import serve

    api = API(holder=holder, mesh_engine=eng)
    # The bench measures serving CAPACITY, so admission must sit above
    # the offered load: the conn-sweep's open-loop senders pipeline up
    # to 64 conns x 64 in-flight (the server's per-connection pending
    # cap) = 4096 concurrent requests from ONE tenant, which the
    # production default (1024) would correctly shed with 429s — and a
    # shed reply would crash the 200-only sweep readers.
    from pilosa_tpu.net.admission import AdmissionController

    httpd, _ = serve(
        api, "localhost", 0,
        admission=AdmissionController(max_inflight=1 << 17),
    )
    port = httpd.server_address[1]
    c2_texts = [
        f"Count(Xor(Difference(Union(Row(f={100 + 4 * k}), Row(f={101 + 4 * k})), "
        f"Row(f={102 + 4 * k})), Row(f={103 + 4 * k})))".encode()
        for k in range(F10_ROWS // 4)
    ]

    def http_once(k):
        req = urllib.request.Request(
            f"http://localhost:{port}/index/b10m/query",
            data=c2_texts[k % len(c2_texts)], method="POST",
        )
        req.add_header("Content-Type", "application/json")
        with urllib.request.urlopen(req) as resp:
            return json.loads(resp.read())["results"][0]

    r_http0 = http_once(0)
    assert r_http0 == cpu_c2()
    t_http_all = []
    for i in range(HTTP_REPS):
        t0 = time.perf_counter()
        http_once(i)
        t_http_all.append(time.perf_counter() - t0)
    t_http = statistics.median(t_http_all)

    # QPS: offered load must exceed the target throughput or the
    # measurement is client-concurrency-bound (qps <= clients / RTT; at
    # a ~100 ms round trip 32 clients cap at ~310 qps no matter how
    # fast the server is).  The load generator is ONE subprocess
    # (this host has a single CPU core — multiple client processes just
    # thrash the scheduler; measured 8x48 threads = 104 qps vs 1x640 =
    # 1184) driving many persistent raw-socket connections with minimal
    # parsing, wrk-style.  The server-side micro-batcher accumulates
    # concurrent Counts into fused count_batch_tree dispatches (fixed
    # compile tiers, slot-vector operands) with pipelined readbacks.
    import subprocess
    import sys as sys_mod

    CLIENT_SRC = r"""
import json, socket, sys, threading, time
port, n_threads, per_conn = (
    int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3]))
texts = json.loads(sys.stdin.read())

def build(body):
    b = body.encode()
    return (b"POST /index/b10m/query HTTP/1.1\r\nHost: l\r\n"
            b"Content-Type: application/json\r\n"
            b"Content-Length: " + str(len(b)).encode() + b"\r\n\r\n" + b)

reqs = [build(t) for t in texts]
done = []
lock = threading.Lock()

def worker(tid):
    s = socket.create_connection(("localhost", port), timeout=300)
    f = s.makefile("rb")
    n = 0
    try:
        for j in range(per_conn):
            s.sendall(reqs[(tid * per_conn + j) % len(reqs)])
            line = f.readline()
            assert line.startswith(b"HTTP/1.1 200"), line
            clen = 0
            while True:
                h = f.readline()
                if h in (b"\r\n", b""):
                    break
                if h.lower().startswith(b"content-length:"):
                    clen = int(h.split(b":")[1])
            f.read(clen)
            n += 1
    finally:
        s.close()
        with lock:
            done.append(n)

threads = [threading.Thread(target=worker, args=(t,)) for t in range(n_threads)]
t0 = time.perf_counter()
for t in threads: t.start()
for t in threads: t.join()
print(json.dumps({"n": sum(done), "seconds": time.perf_counter() - t0}))
"""

    def run_qps(texts, n_procs=1, threads_per_proc=640, per_conn=32):
        import tempfile

        script = tempfile.NamedTemporaryFile(
            "w", suffix=".py", delete=False
        )
        script.write(CLIENT_SRC)
        script.close()
        payload = json.dumps(texts)
        procs = [
            subprocess.Popen(
                [sys_mod.executable, script.name, str(port),
                 str(threads_per_proc), str(per_conn)],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            )
            for _ in range(n_procs)
        ]
        t0 = time.perf_counter()
        # Feed every process's stdin BEFORE reaping any output: a
        # sequential communicate() loop would run the client processes
        # one at a time (stdin is only delivered on communicate) and
        # cap concurrency at one process's thread count.
        for p in procs:
            p.stdin.write(payload.encode())
            p.stdin.close()
        outs = [json.loads(p.stdout.read()) for p in procs]
        for p in procs:
            p.wait(timeout=600)
        wall = time.perf_counter() - t0
        total = sum(o["n"] for o in outs)
        return total / wall, total

    # Warm every batch tier (compiles are one-time and must not land
    # inside the measured window — a production deployment warms these
    # at boot the way the reference warms its mmaps).
    http_once(0)
    warm_tree = pql.parse(c2_texts[0].decode()).calls[0].children[0]
    for k in (1, 9, 65, 257):
        eng.count_many("b10m", [warm_tree] * k, [shards10] * k)
    progress("batch tiers warmed")
    qps, n_total = run_qps([t.decode() for t in c2_texts])
    batcher = eng._batcher
    if batcher is not None and batcher.batches:
        progress(
            f"micro-batcher: {batcher.batched_queries} queries in "
            f"{batcher.batches} fused batches "
            f"(avg {batcher.batched_queries / batcher.batches:.1f}/batch)"
        )
    report_pipeline(eng)
    report_observability(api)
    progress(f"http timed ({qps:.1f} qps over {n_total} requests)")

    # Mixed-kind QPS (round-4 VERDICT #1): Count + TopN + Sum
    # interleaved on the same serving tier — TopN/Sum dispatch their own
    # fused programs (pipelined readbacks in their handler threads)
    # while Counts keep fusing through the batcher.
    mixed_texts = []
    for k in range(F10_ROWS // 4):
        mixed_texts.append(c2_texts[k % len(c2_texts)].decode())
        mixed_texts.append(c2_texts[(k + 7) % len(c2_texts)].decode())
        mixed_texts.append(f"TopN(f, Row(f={100 + 4 * k}), n=5)")
        mixed_texts.append("Sum(field=v10)")
    for q in mixed_texts[:8]:
        req = urllib.request.Request(
            f"http://localhost:{port}/index/b10m/query",
            data=q.encode(), method="POST",
        )
        req.add_header("Content-Type", "application/json")
        urllib.request.urlopen(req).read()  # warm/compile each kind
    mixed_qps, mixed_total = run_qps(mixed_texts)
    progress(f"http mixed timed ({mixed_qps:.1f} qps over {mixed_total})")

    # ---- optional QPS-vs-in-flight-depth sweep (--depth-sweep) -----------
    # One command reproduces the pipelining curve: the batcher is rebuilt
    # at each depth and the same Count load is re-driven.
    if depth_sweep:
        from pilosa_tpu.parallel.batcher import CountBatcher

        for d in (1, 2, 4, 8):
            if eng._batcher is not None:
                eng._batcher.stop()  # don't leak the prior depth's workers
            eng._batcher = CountBatcher(eng, max_inflight=d)
            d_qps, d_total = run_qps([t.decode() for t in c2_texts])
            emit_raw(f"http_count_qps_depth{d}", d_qps, "qps", d_qps * c_c2)
            snap = eng.pipeline_snapshot()
            g = snap["gauges"] if snap else {}
            progress(
                f"depth {d}: {d_qps:.1f} qps over {d_total}, "
                f"inflight_max={g.get('inflight_max', 0)}, "
                f"occupancy={snap['avgOccupancy'] if snap else 0}"
            )
        eng._batcher.stop()
        eng._batcher = None  # back to the default-depth lazy batcher

    # ---- optional connection-count sweep (--conn-sweep) ------------------
    # Open-loop senders: each connection PIPELINES its requests (a writer
    # thread streams them without waiting for responses; a reader drains
    # them), so offered load is set by the connection count — not gated
    # on the previous response like the closed-loop headline run.  One
    # line per level: http_count_qps_c{N}, plus the batcher's occupancy
    # delta at that level — the cross-connection coalescing curve
    # (docs/serving.md; the event-loop server feeds every connection
    # into ONE accumulate stage, so occupancy should RISE with N).
    OPEN_LOOP_SRC = r"""
import json, socket, sys, threading, time
port, n_conns, per_conn = (
    int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3]))
texts = json.loads(sys.stdin.read())

def build(body):
    b = body.encode()
    return (b"POST /index/b10m/query HTTP/1.1\r\nHost: l\r\n"
            b"Content-Type: application/json\r\n"
            b"Content-Length: " + str(len(b)).encode() + b"\r\n\r\n" + b)

reqs = [build(t) for t in texts]
done = []
lock = threading.Lock()

def conn_worker(cid):
    s = socket.create_connection(("localhost", port), timeout=300)
    f = s.makefile("rb")
    def writer():
        for j in range(per_conn):
            s.sendall(reqs[(cid * per_conn + j) % len(reqs)])
    w = threading.Thread(target=writer)
    w.start()
    n = 0
    try:
        for j in range(per_conn):
            line = f.readline()
            assert line.startswith(b"HTTP/1.1 200"), line
            clen = 0
            while True:
                h = f.readline()
                if h in (b"\r\n", b""):
                    break
                if h.lower().startswith(b"content-length:"):
                    clen = int(h.split(b":")[1])
            f.read(clen)
            n += 1
    finally:
        w.join()
        s.close()
        with lock:
            done.append(n)

threads = [threading.Thread(target=conn_worker, args=(c,))
           for c in range(n_conns)]
t0 = time.perf_counter()
for t in threads: t.start()
for t in threads: t.join()
print(json.dumps({"n": sum(done), "seconds": time.perf_counter() - t0}))
"""

    def run_open_loop(texts, n_conns, per_conn, to_port=None):
        import os as os_mod
        import tempfile

        script = tempfile.NamedTemporaryFile("w", suffix=".py", delete=False)
        script.write(OPEN_LOOP_SRC)
        script.close()
        try:
            p = subprocess.Popen(
                [sys_mod.executable, script.name,
                 str(port if to_port is None else to_port), str(n_conns),
                 str(per_conn)],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            )
            out, _ = p.communicate(json.dumps(texts).encode(), timeout=600)
        finally:
            os_mod.unlink(script.name)
        doc = json.loads(out)
        return doc["n"] / doc["seconds"], doc["n"]

    if conn_sweep:
        texts = [t.decode() for t in c2_texts]
        TOTAL = 2048  # per level; sized so one level runs in seconds
        for n_conns in (1, 4, 16, 64):
            b = eng._batcher
            b0, q0 = (b.batches, b.batched_queries) if b else (0, 0)
            c_qps, c_total = run_open_loop(
                texts, n_conns, max(32, TOTAL // n_conns)
            )
            emit_raw(f"http_count_qps_c{n_conns}", c_qps, "qps",
                     c_qps * c_c2)
            b = eng._batcher
            if b is not None and b.batches > b0:
                occ = (b.batched_queries - q0) / (b.batches - b0)
            else:
                occ = 0.0
            progress(
                f"conn sweep c{n_conns}: {c_qps:.1f} qps over {c_total}, "
                f"occupancy {occ:.2f}"
            )

    # ---- optional worker-process sweep (--conn-sweep --workers) ----------
    # The GIL wall, measured: the SAME open-loop load at a fixed
    # connection count against w worker PROCESSES owning HTTP parse /
    # PQL decode / response encode behind SO_REUSEPORT, forwarding
    # decoded frames over AF_UNIX into THIS process's batch pipeline
    # (docs/serving.md "Process mode").  Every w level — including the
    # w=0 oracle — boots a FRESH server and is driven by the same
    # load generator in one run, so the whole w-curve shares one run's
    # conditions; http_count_qps_w0 is the differential oracle the
    # acceptance ratio (w2 vs w0) is judged against.
    #
    # The load generator here is a single-threaded selectors client
    # (one thread, nonblocking sockets, pipelined writes): the threaded
    # per-connection client above spends more scheduler bandwidth than
    # the servers under test on this class of container (128 runnable
    # client threads on 2 vCPUs convoy every PROCESS of the system),
    # which measures the client, not the serving tier.
    EV_LOOP_SRC = r"""
import json, selectors, socket, sys, time
port, n_conns, per_conn = (
    int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3]))
texts = json.loads(sys.stdin.read())

def build(body):
    b = body.encode()
    return (b"POST /index/b10m/query HTTP/1.1\r\nHost: l\r\n"
            b"Content-Type: application/json\r\n"
            b"Content-Length: " + str(len(b)).encode() + b"\r\n\r\n" + b)

reqs = [build(t) for t in texts]

class Conn:
    __slots__ = ("s", "out", "off", "rbuf", "got", "want")
    def __init__(self, cid):
        self.s = socket.create_connection(("localhost", port), timeout=300)
        self.s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.s.setblocking(False)
        self.out = b"".join(reqs[(cid * per_conn + j) % len(reqs)]
                            for j in range(per_conn))
        self.off = 0
        self.rbuf = bytearray()
        self.got = 0
        self.want = per_conn

def count_responses(c):
    n = 0
    buf = c.rbuf
    while True:
        end = buf.find(b"\r\n\r\n")
        if end < 0:
            break
        cl = 0
        for ln in bytes(buf[:end]).lower().split(b"\r\n"):
            if ln.startswith(b"content-length:"):
                cl = int(ln.split(b":")[1])
        total = end + 4 + cl
        if len(buf) < total:
            break
        assert buf.startswith(b"HTTP/1.1 200"), bytes(buf[:40])
        del buf[:total]
        n += 1
    return n

sel = selectors.DefaultSelector()
conns = [Conn(c) for c in range(n_conns)]
for c in conns:
    sel.register(c.s, selectors.EVENT_READ | selectors.EVENT_WRITE, c)
t0 = time.perf_counter()
live = len(conns)
while live:
    for key, mask in sel.select(timeout=1.0):
        c = key.data
        if mask & selectors.EVENT_WRITE:
            if c.off < len(c.out):
                try:
                    c.off += c.s.send(c.out[c.off:])
                except (BlockingIOError, InterruptedError):
                    pass
            if c.off >= len(c.out):
                sel.modify(c.s, selectors.EVENT_READ, c)
        if mask & selectors.EVENT_READ:
            try:
                chunk = c.s.recv(1 << 18)
            except (BlockingIOError, InterruptedError):
                continue
            if not chunk:
                # Server closed early: surface the short count instead
                # of spinning on a level-triggered dead socket forever.
                sys.stderr.write(
                    f"conn closed early at {c.got}/{c.want}\n"
                )
                sel.unregister(c.s)
                c.s.close()
                live -= 1
                continue
            c.rbuf += chunk
            c.got += count_responses(c)
            if c.got >= c.want:
                sel.unregister(c.s)
                c.s.close()
                live -= 1
print(json.dumps({"n": sum(c.got for c in conns),
                  "seconds": time.perf_counter() - t0}))
"""

    def run_ev_loop(texts, n_conns, per_conn, to_port):
        import os as os_mod
        import tempfile

        script = tempfile.NamedTemporaryFile("w", suffix=".py", delete=False)
        script.write(EV_LOOP_SRC)
        script.close()
        try:
            p = subprocess.Popen(
                [sys_mod.executable, script.name, str(to_port),
                 str(n_conns), str(per_conn)],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            )
            out, _ = p.communicate(json.dumps(texts).encode(), timeout=600)
        finally:
            os_mod.unlink(script.name)
        doc = json.loads(out)
        return doc["n"] / doc["seconds"], doc["n"]

    if conn_sweep and workers_sweep:
        texts = [t.decode() for t in c2_texts]
        W_CONNS, W_TOTAL = 128, 8192
        w_results = {}
        for w in (0, 1, 2, 4, 8):
            wsrv, _ = serve(
                api, "localhost", 0, workers=w,
                admission=AdmissionController(max_inflight=1 << 17),
            )
            if w and not wsrv.wait_ready(120):
                progress(f"workers={w}: workers never connected; skipped")
                wsrv.shutdown()
                continue
            wport = wsrv.server_address[1]
            run_ev_loop(texts, 8, 32, wport)  # warm conns + worker boots
            b = eng._batcher
            b0, q0 = (b.batches, b.batched_queries) if b else (0, 0)
            x0 = (
                b.pipeline.snapshot()["counters"].get(
                    "cross_worker_fused_batches", 0
                ) if b else 0
            )
            w_qps, w_total = run_ev_loop(
                texts, W_CONNS, max(32, W_TOTAL // W_CONNS), wport
            )
            emit_raw(f"http_count_qps_w{w}", w_qps, "qps", w_qps * c_c2)
            w_results[w] = w_qps
            b = eng._batcher
            occ = (
                (b.batched_queries - q0) / (b.batches - b0)
                if b is not None and b.batches > b0 else 0.0
            )
            xw = (
                b.pipeline.snapshot()["counters"].get(
                    "cross_worker_fused_batches", 0
                ) - x0 if b else 0
            )
            progress(
                f"workers sweep w{w}: {w_qps:.1f} qps over {w_total}, "
                f"occupancy {occ:.2f}, cross-worker fused batches {xw}"
            )
            wsrv.shutdown()
        if 0 in w_results and 2 in w_results and w_results[0] > 0:
            progress(
                "workers sweep ratio w2/w0: "
                f"{w_results[2] / w_results[0]:.2f}x"
            )
    if scrape:
        report_scrape(port)
    httpd.shutdown()
    emit("http_count_e2e_p50", t_http, c_c2)
    emit_raw("http_count_qps", qps, "qps", qps * c_c2)
    # Conservative baseline: every mixed query is priced at the COUNT
    # CPU baseline (c_c2) — TopN/Sum host-numpy baselines cost more per
    # query, so the true multiplier is higher than reported.
    emit_raw("http_mixed_qps", mixed_qps, "qps", mixed_qps * c_c2)

    # ---- mixed workload: write + query cycles (runs AFTER the
    # correctness baselines above: the writes land in device-only rows
    # (12, 13+) precisely so the host-baseline rows 10/11 — whose numpy
    # buffers the assertions share — are never touched) --------------------
    # Each cycle sets one bit (host truth) and issues a fused count; the
    # engine scatter-updates only the dirty row of the resident stack
    # (engine.stack_updates advances, stack_rebuilds must NOT).
    rebuilds_before = eng.stack_rebuilds

    wr_nonce = iter(range(1, 1 << 30))

    def wr_cycle(i):
        # Row 12 is device-only: the host-baseline dict shares the numpy
        # buffers of rows 10/11, which later phases (cpu_ns in the
        # north-star emit, cpu_imp) still read.  The column comes from a nonce —
        # NOT from i — a nonce guarantees every cycle is a real write
        # (a repeated set_bit is a no-op: no touch, no scatter).
        n = next(wr_nonce)
        frag = holder.fragment("bench", "f", "standard", n % N_SHARDS)
        frag.set_bit(12, (n % N_SHARDS) * (1 << 20) + (7919 * n) % (1 << 20))
        return eng.count_async("bench", ns_calls[i % len(ns_calls)], shards)

    jax.device_get(wr_cycle(0))  # warm: compile the scatter programs
    t_wr, _ = device_p50(wr_cycle, reps=24, total=True)
    assert eng.stack_rebuilds == rebuilds_before, "write forced a rebuild"
    progress("write+query cycle timed")
    # Mixed workload: CPU baseline = update one numpy row + recount the
    # north-star pair (what a dense CPU mirror would do per cycle).
    emit("write_query_cycle_1B_cols_p50", t_wr, c_ns,
         bytes_read=2 * N_SHARDS * ROW_BYTES)

    # ---- bulk import + query cycle: a 300-shard import (300 dirty
    # (row, shard) pairs — past round 3's 256-row scatter cap) must
    # write-through to the resident stack via chunked scatters, zero
    # rebuilds (round-4 VERDICT #8).  Rows 13+ are device-only; the
    # host-baseline rows 10/11 stay untouched.
    IMP_SHARDS = min(300, N_SHARDS)  # never create NEW shards mid-cycle
    imp_nonce = iter(range(1, 1 << 30))

    def imp_cycle(i):
        n = next(imp_nonce)
        row = 13 + (n % (F_ROWS - 4))
        cols = [
            s * (1 << 20) + (7919 * n + 131 * s) % (1 << 20)
            for s in range(IMP_SHARDS)
        ]
        f.import_bulk([row] * IMP_SHARDS, cols)
        return eng.count_async("bench", ns_calls[i % len(ns_calls)], shards)

    rebuilds_before = eng.stack_rebuilds
    jax.device_get(imp_cycle(0))  # warm
    t_imp, _ = device_p50(imp_cycle, reps=8, total=True)
    assert eng.stack_rebuilds == rebuilds_before, "bulk import forced a rebuild"
    progress("bulk-import+query cycle timed")
    # Bulk import cycle: CPU mirror sets one bit in each of IMP_SHARDS
    # rows then recounts the pair.
    mirror = {
        s: np.zeros(W64, dtype=np.uint64) for s in range(IMP_SHARDS)
    }

    def cpu_imp():
        for s in range(IMP_SHARDS):
            mirror[s][(7919 * s) % W64] |= np.uint64(1) << np.uint64(s % 64)
        return cpu_ns()

    c_imp = cpu_time(cpu_imp, reps=1)
    emit("bulk_import_query_cycle_1B_cols_p50", t_imp, c_imp,
         bytes_read=2 * N_SHARDS * ROW_BYTES)

    # ---- north star LAST: the driver parses the final line ---------------
    emit("count_intersect_1B_cols_p50", t_ns, c_ns,
         bytes_read=2 * N_SHARDS * ROW_BYTES)


# ---- dashboard fusion: whole-program heterogeneous drains (--dashboard-sweep)

# 8 shards keeps the per-widget device program small enough that this
# container's lane measures the SERVING regime (per-dispatch floor +
# shared-mask reuse dominate) rather than raw memory bandwidth; at 32
# shards the same sweep is bandwidth-bound on the ~1.5 shared vCPUs and
# the fused win compresses to the pure bytes-saved ratio (~1.2x here).
# The TPU round measures the full shape (docs/fusion.md).
DASH_SHARDS = 8
DASH_WIDGETS = (2, 4, 8, 10)
DASH_REPS = 24


def _dash_entries(pql, n, shards):
    """1 segment filter x ``n`` widgets of mixed ops — the dashboard
    shape whole-program fusion exists for (docs/fusion.md).  The
    segment is a 4-row conjunction (country AND cohort AND plan AND
    active — the audience-filter norm), so every unfused widget
    re-sweeps 4 rows just to rebuild the mask the fused program
    materializes once."""
    seg = "Intersect(Row(seg=0), Row(seg=1), Row(seg=2), Row(seg=3))"
    segc = lambda: pql.parse(seg).calls[0]  # noqa: E731
    widgets = [
        ({"kind": "count",
          "call": pql.parse(f"Intersect({seg}, Row(w=1))").calls[0]}, shards),
        ({"kind": "sum", "field": "v", "filter": segc()}, shards),
        ({"kind": "topnf", "field": "w", "src": segc(), "n": 5,
          "threshold": 1, "row_ids": None}, shards),
        ({"kind": "min", "field": "v", "filter": segc()}, shards),
        ({"kind": "max", "field": "v", "filter": segc()}, shards),
        ({"kind": "count",
          "call": pql.parse(f"Intersect({seg}, Row(w=2))").calls[0]}, shards),
        ({"kind": "topn", "field": "w", "rows": [1, 2, 3, 4],
          "src": segc()}, shards),
        ({"kind": "count",
          "call": pql.parse(f"Difference({seg}, Row(w=3))").calls[0]}, shards),
        # PR 18 widgets: a GroupBy counted as one fused `group` edge and
        # a second full TopN riding the shared segment mask (device trim).
        ({"kind": "group", "fields": ["g"], "rows": [[0, 1, 2, 3]],
          "filter": segc()}, shards),
        ({"kind": "topnf", "field": "w", "src":
          pql.parse(f"Intersect({seg}, Row(w=4))").calls[0], "n": 3,
          "threshold": 1, "row_ids": None}, shards),
    ]
    return widgets[:n]


def _dash_oracle(eng, entries):
    """The retained sequential per-query path: one blocking dispatch +
    readback per widget — exactly what the serving tier paid pre-fusion."""
    return _dash_oracle_x(eng, [("dash", sp, sh) for sp, sh in entries])


def _dash_oracle_x(eng, triples):
    """Sequential oracle over (index, spec, shards) triples — the
    cross-index drain's per-item comparison path."""
    out = []
    for index, spec, shards in triples:
        k = spec["kind"]
        if k == "count":
            out.append(eng.count(index, spec["call"], shards))
        elif k == "sum":
            out.append(eng.sum(index, spec["field"], spec.get("filter"), shards))
        elif k in ("min", "max"):
            out.append(eng.min_max(index, spec["field"], spec.get("filter"),
                                   shards, k == "min"))
        elif k == "topn":
            out.append(eng.topn_scores(index, spec["field"], spec["rows"],
                                       spec["src"], shards))
        elif k == "group":
            out.append(eng.group_counts(index, spec["fields"], spec["rows"],
                                        spec.get("filter"), shards))
        else:
            out.append(eng.topn_full(index, spec["field"], spec["src"],
                                     shards, spec["n"], spec["threshold"]))
    return out


def dashboard_sweep():
    """Whole-program fusion sweep (docs/fusion.md): dashboard-shaped
    drains — 1 segment filter x N in {2, 4, 8, 10} widgets of mixed
    Count/Sum/Min/Max/TopN/GroupBy — timed as ONE fused device program
    vs the unfused sequential per-query path on the same data.  Emits
    ``dashboard_fused_qps`` / ``dashboard_p50_ms`` (N=8 headlines,
    bench_guard AUTO_REQUIREd once baselined), the per-N curve, the
    measured speedup (ABS_FLOORed at 1.5x in bench_guard), and
    ``fused_masks_saved_total``; asserts — via plan records — that the
    fused N=8 drain evaluated each shared mask exactly once.  PR 18
    lanes: the TopN slab (``topn_device_p50`` / ``topn_e2e_p50`` /
    ``topn_device_speedup``, device trim vs the in-run host rank/merge
    oracle, ABS_FLOORed at 2x) and the cross-index drain
    (``dashboard_crossindex_p50_ms`` /
    ``dashboard_crossindex_fused_speedup``, one program spanning two
    indexes)."""
    progress("importing jax (dashboard sweep)")
    import threading as _threading

    import jax

    from pilosa_tpu import pql
    from pilosa_tpu.core.field import FieldOptions
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.ops import bitops
    from pilosa_tpu.parallel import MeshEngine, make_mesh
    from pilosa_tpu.parallel import fusion
    from pilosa_tpu.parallel.batcher import CountBatcher
    from pilosa_tpu.util import plans as plans_mod

    rng = np.random.default_rng(23)
    holder = Holder()
    holder.open()
    idx = holder.create_index("dash")
    seg_f = idx.create_field("seg")
    w_f = idx.create_field("w")
    v_f = idx.create_field("v", FieldOptions(type="int", min=0, max=100))
    shards = list(range(DASH_SHARDS))
    seg_view = seg_f.view_if_not_exists("standard")
    w_view = w_f.view_if_not_exists("standard")
    for s in shards:
        sf = seg_view.fragment_if_not_exists(s)
        for r in range(4):
            sf.load_row_words(
                r, __rand(rng, bitops.WORDS64) | __rand(rng, bitops.WORDS64)
            )
        wf = w_view.fragment_if_not_exists(s)
        for r in range(1, 5):
            wf.load_row_words(r, __rand(rng, bitops.WORDS64))
    g_f = idx.create_field("g")
    g_view = g_f.view_if_not_exists("standard")
    for s in shards:
        gf = g_view.fragment_if_not_exists(s)
        for r in range(4):
            gf.load_row_words(r, __rand(rng, bitops.WORDS64))
    for frag in (list(seg_view.fragments.values())
                 + list(w_view.fragments.values())
                 + list(g_view.fragments.values())):
        frag.cache.invalidate()
    cols = rng.choice(DASH_SHARDS << 20, size=30_000, replace=False)
    v_f.import_values(
        [int(c) for c in cols], [int(c % 100) for c in range(len(cols))]
    )
    progress("dashboard build done")

    mesh = make_mesh(len(jax.devices()))
    eng = MeshEngine(holder, mesh)
    eng.result_memo.maxsize = 0  # every rep must really dispatch

    t_fused_8 = t_seq_8 = None
    saved0 = eng.fused_masks_referenced - eng.fused_masks_evaluated
    for n in DASH_WIDGETS:
        entries = _dash_entries(pql, n, shards)
        want = _dash_oracle(eng, entries)  # warms every solo executable
        got = eng.fused_many("dash", entries)  # warms the fused program
        for k, (g, w) in enumerate(zip(got, want)):
            if isinstance(w, tuple) and len(w) == 3:
                assert np.array_equal(g[0], w[0]), f"widget {k} diverged"
            elif isinstance(w, np.ndarray):
                assert np.array_equal(np.asarray(g), w), f"widget {k} diverged"
            else:
                assert g == w, f"widget {k} diverged: {g!r} != {w!r}"
        e0, r0 = eng.fused_masks_evaluated, eng.fused_masks_referenced
        t_fused, _ = sync_p50(
            lambda i: eng.fused_many("dash", entries), reps=DASH_REPS
        )
        per_drain_saved = (
            (eng.fused_masks_referenced - r0) - (eng.fused_masks_evaluated - e0)
        ) / DASH_REPS
        t_seq, _ = sync_p50(
            lambda i: _dash_oracle(eng, entries), reps=max(4, DASH_REPS // 2)
        )
        fused_qps = n / t_fused
        seq_qps = n / t_seq
        emit_raw(f"dashboard_fused_qps_n{n}", fused_qps, "qps",
                 fused_qps / seq_qps)
        emit_raw(f"dashboard_seq_qps_n{n}", seq_qps, "qps", 1.0)
        emit_raw(f"dashboard_speedup_n{n}", t_seq / t_fused, "x",
                 t_seq / t_fused)
        progress(
            f"N={n}: fused {t_fused * 1e3:.2f}ms/drain ({fused_qps:.0f} "
            f"widget-qps) vs sequential {t_seq * 1e3:.2f}ms "
            f"({seq_qps:.0f}), saved {per_drain_saved:.1f} mask evals/drain"
        )
        if n == 8:
            t_fused_8, t_seq_8 = t_fused, t_seq

    # Headlines (N=8): widget answers per second through the fused
    # program, drain wall p50, and the guarded fused-vs-sequential
    # speedup (bench_guard ABS_FLOOR 1.5).
    emit_raw("dashboard_fused_qps", 8 / t_fused_8, "qps",
             t_seq_8 / t_fused_8)
    emit_raw("dashboard_p50_ms", t_fused_8 * 1e3, "ms",
             t_seq_8 / t_fused_8)
    emit_raw("dashboard_fused_speedup", t_seq_8 / t_fused_8, "x",
             t_seq_8 / t_fused_8)

    # ---- the TopN slab lane: device trim vs the host rank/merge oracle
    # Field `t`: 128 rows of strictly graded density (cache-count order
    # == score order, so per-shard qualifying sets stay ~n and the slab
    # accepts instead of overflow-declining); src row dense across the
    # shard.  The host walk (the retained oracle) re-ranks all 128
    # candidates in python per shard; the slab merges k_out pairs.
    topn_idx = holder.create_index("topn")
    t_f = topn_idx.create_field("t")
    s_f = topn_idx.create_field("srcf")
    t_view = t_f.view_if_not_exists("standard")
    s_view = s_f.view_if_not_exists("standard")
    for s in shards:
        tf = t_view.fragment_if_not_exists(s)
        for r in range(128):
            wr = 2048 - 15 * r
            words = np.zeros(bitops.WORDS64, dtype=np.uint64)
            words[:wr] = __rand(rng, wr)
            tf.load_row_words(r, words)
        tf.cache.invalidate()
        sf = s_view.fragment_if_not_exists(s)
        sf.load_row_words(0, __rand(rng, bitops.WORDS64))
        sf.cache.invalidate()
    ex = Executor(holder, mesh_engine=eng)
    topn_call = pql.parse("TopN(t, Row(srcf=0), n=5)").calls[0]

    class _Opt:
        remote = False

    opt = _Opt()
    got_dev = ex._mesh_topn_shards("topn", topn_call, shards, opt)
    eng.topn_slab_enabled = False
    got_host = ex._mesh_topn_shards("topn", topn_call, shards, opt)
    eng.topn_slab_enabled = True
    assert got_dev[1] == got_host[1], "slab diverged from the host walk"
    assert eng.topn_device_full(
        "topn", "t", topn_call.children[0], shards, 5, 1
    ) is not None, "slab lane declined the bench workload"
    t_slab, _ = sync_p50(
        lambda i: eng.topn_device_full(
            "topn", "t", topn_call.children[0], shards, 5, 1),
        reps=DASH_REPS)
    t_e2e, _ = sync_p50(
        lambda i: ex._mesh_topn_shards("topn", topn_call, shards, opt),
        reps=DASH_REPS)
    eng.topn_slab_enabled = False
    t_host, _ = sync_p50(
        lambda i: ex._mesh_topn_shards("topn", topn_call, shards, opt),
        reps=max(6, DASH_REPS // 2))
    eng.topn_slab_enabled = True
    emit_raw("topn_device_p50", t_slab * 1e3, "ms", t_host / t_slab)
    emit_raw("topn_e2e_p50", t_e2e * 1e3, "ms", t_host / t_e2e)
    emit_raw("topn_device_speedup", t_host / t_e2e, "x", t_host / t_e2e)
    progress(
        f"topn slab: device {t_slab * 1e3:.2f}ms e2e {t_e2e * 1e3:.2f}ms "
        f"vs host merge {t_host * 1e3:.2f}ms ({t_host / t_e2e:.2f}x)"
    )

    # ---- cross-index drains: one device program spans indexes --------
    # A second dashboard index with its own segment/widget/BSI fields;
    # the drain interleaves items from both.  Pre-PR-18 this was two
    # programs (one per index) — the speedup is vs the sequential
    # per-item path, same discipline as the single-index sweep.
    idx2 = holder.create_index("dash2")
    seg2_f = idx2.create_field("seg")
    w2_f = idx2.create_field("w")
    v2_f = idx2.create_field("v", FieldOptions(type="int", min=0, max=100))
    seg2_view = seg2_f.view_if_not_exists("standard")
    w2_view = w2_f.view_if_not_exists("standard")
    for s in shards:
        sf2 = seg2_view.fragment_if_not_exists(s)
        for r in range(4):
            sf2.load_row_words(
                r, __rand(rng, bitops.WORDS64) | __rand(rng, bitops.WORDS64)
            )
        wf2 = w2_view.fragment_if_not_exists(s)
        for r in range(1, 5):
            wf2.load_row_words(r, __rand(rng, bitops.WORDS64))
    for frag in (list(seg2_view.fragments.values())
                 + list(w2_view.fragments.values())):
        frag.cache.invalidate()
    cols2 = rng.choice(DASH_SHARDS << 20, size=30_000, replace=False)
    v2_f.import_values(
        [int(c) for c in cols2], [int(c % 100) for c in range(len(cols2))]
    )
    seg = "Intersect(Row(seg=0), Row(seg=1), Row(seg=2), Row(seg=3))"
    segc = lambda: pql.parse(seg).calls[0]  # noqa: E731
    entries_x = [
        ("dash", {"kind": "count",
                  "call": pql.parse(f"Intersect({seg}, Row(w=1))").calls[0]},
         shards),
        ("dash2", {"kind": "count",
                   "call": pql.parse(f"Intersect({seg}, Row(w=1))").calls[0]},
         shards),
        ("dash", {"kind": "topnf", "field": "w", "src": segc(), "n": 5,
                  "threshold": 1, "row_ids": None}, shards),
        ("dash2", {"kind": "sum", "field": "v", "filter": segc()}, shards),
        ("dash", {"kind": "group", "fields": ["g"], "rows": [[0, 1, 2, 3]],
                  "filter": segc()}, shards),
        ("dash2", {"kind": "topnf", "field": "w", "src": segc(), "n": 5,
                   "threshold": 1, "row_ids": None}, shards),
    ]
    want_x = _dash_oracle_x(eng, entries_x)
    got_x = eng.fused_drain(entries_x)
    for k, (g, w) in enumerate(zip(got_x, want_x)):
        if isinstance(w, np.ndarray):
            assert np.array_equal(np.asarray(g), w), f"x-item {k} diverged"
        else:
            assert g == w, f"x-item {k} diverged: {g!r} != {w!r}"
    p0 = eng.fused_programs
    eng.fused_drain(entries_x)
    assert eng.fused_programs == p0 + 1, "cross-index drain split programs"
    t_xf, _ = sync_p50(lambda i: eng.fused_drain(entries_x), reps=DASH_REPS)
    t_xs, _ = sync_p50(lambda i: _dash_oracle_x(eng, entries_x),
                       reps=max(6, DASH_REPS // 2))
    emit_raw("dashboard_crossindex_p50_ms", t_xf * 1e3, "ms", t_xs / t_xf)
    emit_raw("dashboard_crossindex_fused_speedup", t_xs / t_xf, "x",
             t_xs / t_xf)
    progress(
        f"cross-index: fused {t_xf * 1e3:.2f}ms vs sequential "
        f"{t_xs * 1e3:.2f}ms ({t_xs / t_xf:.2f}x), one program per drain"
    )

    # Acceptance, via plan records: drive the N=8 drain through the
    # REAL batcher and assert the recorded plan ops show every shared
    # mask evaluated once (masks_evaluated == distinct subtrees).
    eng._batcher = CountBatcher(eng)
    b = eng.batcher()
    b._last_fused = time.monotonic() + 10_000  # all submissions queue
    entries = _dash_entries(pql, 8, shards)
    distinct = set()
    for spec, _s in entries:
        distinct |= fusion.item_texts(spec)
    plans = [plans_mod.QueryPlan("dash", f"widget{k}")
             for k in range(len(entries))]

    def run(k):
        spec, s = entries[k]
        with plans_mod.attach(plans[k]):
            if spec["kind"] == "count":
                b.submit("dash", spec["call"], s)
            elif spec["kind"] == "sum":
                eng.batched_sum("dash", spec["field"], spec["filter"], s)
            elif spec["kind"] in ("min", "max"):
                eng.batched_min_max("dash", spec["field"], spec["filter"], s,
                                    spec["kind"] == "min")
            elif spec["kind"] == "topn":
                eng.batched_topn_scores("dash", spec["field"], spec["rows"],
                                        spec["src"], s)
            else:
                eng.batched_topn_full("dash", spec["field"], spec["src"], s,
                                      spec["n"], spec["threshold"])

    threads = [_threading.Thread(target=run, args=(k,))
               for k in range(len(entries))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    fused_ops = [
        op
        for p in plans
        for op in p.ops
        if op.get("path") == "fused_program"
    ]
    assert fused_ops, "no widget recorded a fused_program plan op"
    full = [op for op in fused_ops if op.get("fused_queries") == len(entries)]
    if full:
        assert full[0]["masks_evaluated"] == len(distinct), (
            full[0], len(distinct)
        )
        assert full[0]["masks_referenced"] > full[0]["masks_evaluated"]
        progress(
            f"plan record: {full[0]['masks_referenced']} mask refs -> "
            f"{full[0]['masks_evaluated']} evaluated "
            f"(== {len(distinct)} distinct)"
        )
    else:
        progress(
            "plan record: drain split across accumulation windows "
            f"({sorted(set(op.get('fused_queries') for op in fused_ops))} "
            "riders) — sharing still recorded per drain"
        )
    saved_total = (
        eng.fused_masks_referenced - eng.fused_masks_evaluated
    ) - saved0
    print(json.dumps({
        "metric": "fused_masks_saved_total",
        "value": int(saved_total),
        "unit": "evals",
        "vs_baseline": 1.0,
    }), flush=True)
    eng.close()


def __rand(rng, words64):
    return rng.integers(0, 1 << 63, size=words64, dtype=np.uint64) | (
        rng.integers(0, 1 << 63, size=words64, dtype=np.uint64) << np.uint64(1)
    )


# ---- sparsity: density sweep + result-memo shape (--density-sweep) -------

SWEEP_SHARDS = 64
SWEEP_BLOCKS = (1, 2, 6, 32)  # occupied occupancy-blocks per row (of 64)
SWEEP_REPS = 16


def density_sweep():
    """Sparse-row shapes at ~0.78%/1.6%/4.7%/25% bit density (1/2/6/32
    half-filled occupancy blocks of 64 — block-clustered, the
    distribution roaring exists for): each shape is
    counted through the occupancy-guided sparse path AND the dense
    sweep on the SAME data, emitting per-shape ``*_p50``,
    ``implied_gbs``, ``bytes_skipped``, and the speedup — plus a
    repeated-query shape that exercises the versioned result memo
    (hits > 0, device dispatch count flat).  Standalone build (~64
    shards); lines join the main bench's JSONL stream format, so
    scripts/bench_guard.py diffs them like any other metric."""
    progress("importing jax (density sweep)")
    import jax

    from pilosa_tpu import pql
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.ops import bitops
    from pilosa_tpu.parallel import MeshEngine, make_mesh

    rng = np.random.default_rng(7)
    holder = Holder()
    holder.open()
    idx = holder.create_index("sweep")
    f = idx.create_field("sf")
    view = f.view_if_not_exists("standard")

    host = {}  # row -> {shard: words}
    shards = list(range(SWEEP_SHARDS))
    for k, nb in enumerate(SWEEP_BLOCKS):
        for r in (2 * k, 2 * k + 1):
            host[r] = {}
            for s in shards:
                words = np.zeros(bitops.WORDS64, dtype=np.uint64)
                # Half-fill the first nb occupancy blocks: block-level
                # clustering with realistic in-block density (measured
                # ~55% — __rand is ~74% dense, the AND of two ~55% — so
                # the d-labels' /2 assumption is accurate to ~10%).
                w64_per_block = bitops.OCC_BLOCK_WORDS // 2
                blk = __rand(rng, nb * w64_per_block) & __rand(
                    rng, nb * w64_per_block
                )
                words[: nb * w64_per_block] = blk
                view.fragment_if_not_exists(s).load_row_words(r, words)
                host[r][s] = words
    for frag in view.fragments.values():
        frag.cache.invalidate()
    progress("sweep build done")

    mesh = make_mesh(len(jax.devices()))
    eng = MeshEngine(holder, mesh)
    eng_dense = MeshEngine(holder, mesh)
    eng_dense.sparse_enabled = False

    def pc(x):
        return int(np.sum(np.bitwise_count(x)))

    memo_call = None
    for k, nb in enumerate(SWEEP_BLOCKS):
        ra, rb = 2 * k, 2 * k + 1
        call = pql.parse(f"Intersect(Row(sf={ra}), Row(sf={rb}))").calls[0]
        if memo_call is None:
            memo_call = call
        want = sum(pc(host[ra][s] & host[rb][s]) for s in shards)
        c_cpu = cpu_time(
            lambda: sum(pc(host[ra][s] & host[rb][s]) for s in shards)
        )
        density = nb * bitops.OCC_BLOCK_BITS / 2 / (1 << 20)
        label = f"d{density * 100:.2g}pct"
        dense_bytes = 2 * SWEEP_SHARDS * ROW_BYTES

        # Memo off while timing: every rep must really dispatch.
        eng.result_memo.maxsize = 0
        eng_dense.result_memo.maxsize = 0
        skipped0 = eng.device_bytes_skipped
        got = eng.count("sweep", call, shards)
        assert got == want, (label, got, want)
        per_query_skipped = eng.device_bytes_skipped - skipped0
        sparse_bytes = dense_bytes - per_query_skipped
        assert eng_dense.count("sweep", call, shards) == want

        t_sparse, _ = device_p50(
            lambda i: eng.count_async("sweep", call, shards), reps=SWEEP_REPS
        )
        t_dense, _ = device_p50(
            lambda i: eng_dense.count_async("sweep", call, shards),
            reps=SWEEP_REPS,
        )
        emit(f"sparse_count_{label}_p50", t_sparse, c_cpu,
             bytes_read=max(sparse_bytes, 1))
        emit(f"dense_count_{label}_p50", t_dense, c_cpu,
             bytes_read=dense_bytes)
        print(json.dumps({
            "metric": f"sparse_count_{label}_bytes_skipped",
            "value": per_query_skipped,
            "unit": "bytes",
            "vs_baseline": round(dense_bytes / max(sparse_bytes, 1), 2),
        }), flush=True)
        emit_raw(f"sparse_speedup_{label}", t_dense / t_sparse, "x",
                 t_dense / t_sparse)
        progress(
            f"{label}: sparse {t_sparse * 1e6:.1f}us dense "
            f"{t_dense * 1e6:.1f}us skipped {per_query_skipped} B/query"
        )

    # Repeated-query shape: the versioned result memo answers replays
    # with NO device dispatch — hits advance, dispatches stay flat.
    eng.result_memo.maxsize = 4096
    base = eng.count("sweep", memo_call, shards)  # miss: populates
    hits0, disp0 = eng.result_memo.hits, eng.fused_dispatches
    reps = 200
    t0 = time.perf_counter()
    for _ in range(reps):
        assert eng.count("sweep", memo_call, shards) == base
    t_memo = (time.perf_counter() - t0) / reps
    hits = eng.result_memo.hits - hits0
    dispatched = eng.fused_dispatches - disp0
    assert hits == reps and dispatched == 0, (hits, dispatched)
    ra, rb = 0, 1
    c_cpu = cpu_time(lambda: sum(pc(host[ra][s] & host[rb][s]) for s in shards))
    emit("repeated_count_memo_p50", t_memo, c_cpu)
    emit_raw("result_memo_hits", hits, "hits", 1.0)
    emit_raw("result_memo_dispatches", dispatched, "dispatches", 1.0)
    snap = eng.cache_snapshot()
    progress(
        f"memo shape: {hits} hits, {dispatched} dispatches, "
        f"bytes_skipped_total={snap['deviceBytesSkipped']}"
    )


# ---- repair-on-write: O(changed-bits) maintenance (--repair-sweep) ---------

RPS_SHARDS = 8
RPS_SEG_ROWS = 16
RPS_BUILD_BITS = 4000  # per seg shard
RPS_ROUNDS = 12
RPS_WRITES_PER_ROUND = 64  # bits per touched shard per round
RPS_READS_PER_ROUND = 5    # timed dashboard serves per write burst
RPS_IDLE_REPS = 24


def repair_sweep():
    """Repair-on-write differential oracle + headline lane
    (docs/incremental.md): a fixed dashboard (two Counts, a TopN, a
    GroupBy, a Sum) runs repeatedly while randomized instrumented
    writes stream in between rounds.  Every round's served results are
    compared bit-exact against a full recompute with the repair layer
    suspended AND the memo cleared — including rounds that force a
    stale-base fallback through an un-instrumented write path
    (load_row_words publishes OPAQUE, so the repair layer must refuse
    and recompute; clear_row/set_row now capture deltas and repair).
    Emits the guarded headlines:

      result_memo_hit_rate_under_write_load   fraction of dashboard
                                              probes answered by the
                                              memo or an O(changed-bits)
                                              repair (acceptance >=0.9)
      dashboard_p50_under_ingest_vs_idle      dashboard wall p50 ratio,
                                              write rounds vs idle
                                              (acceptance <=1.5x)

    plus dashboard_repair_serve_p50_ms (the first serve after a write
    burst — the one that pays the repair) and
    repair_touched_words_per_repair (the O(touched rows) cost evidence:
    words read scale with the write, not the data)."""
    progress("importing jax (repair sweep)")
    import jax

    from pilosa_tpu.core.field import FieldOptions
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.ops import bitops
    from pilosa_tpu.parallel import MeshEngine, make_mesh

    from pilosa_tpu.ops import SHARD_WIDTH

    rng = np.random.default_rng(16)
    holder = Holder()
    holder.open()
    idx = holder.create_index("rpw")
    idx.create_field("seg")
    idx.create_field("g1")
    idx.create_field("g2")
    idx.create_field("v", FieldOptions(type="int", min=0, max=1023))
    shards = list(range(RPS_SHARDS))

    seg_view = idx.field("seg").view_if_not_exists("standard")
    for s in shards:
        frag = seg_view.fragment_if_not_exists(s)
        frag.bulk_import(
            rng.integers(0, RPS_SEG_ROWS, RPS_BUILD_BITS),
            rng.integers(0, SHARD_WIDTH, RPS_BUILD_BITS),
        )
    for fname, nrows in (("g1", 6), ("g2", 5)):
        gview = idx.field(fname).view_if_not_exists("standard")
        for s in shards:
            gview.fragment_if_not_exists(s).bulk_import(
                rng.integers(0, nrows, 800),
                rng.integers(0, SHARD_WIDTH, 800),
            )
    progress("repair sweep build done")

    mesh = make_mesh(len(jax.devices()))
    eng = MeshEngine(holder, mesh)
    ex = Executor(holder, mesh_engine=eng)

    def q(query):
        return ex.execute("rpw", query).results[0]

    # BSI values through the executor (instrumented set_value path).
    for col in rng.integers(0, RPS_SHARDS * SHARD_WIDTH, 600):
        q(f"Set({int(col)}, v={int(rng.integers(0, 1024))})")

    dashboard = (
        "Count(Intersect(Row(seg=1), Row(seg=2)))",
        "Count(Union(Row(seg=3), Row(seg=4), Row(seg=5)))",
        "TopN(seg, n=8)",
        "GroupBy(Rows(field=g1), Rows(field=g2))",
        "Sum(field=v)",
    )
    MEMO_CACHES = ("result_memo", "memo_sum", "memo_topn", "memo_groupby")

    def dash():
        return [q(query) for query in dashboard]

    def recompute():
        with eng.repairs.suspended():
            eng.result_memo.clear()
            return [q(query) for query in dashboard]

    def memo_tally():
        stats = eng.cache_snapshot()["caches"]
        hits = sum(stats.get(n, {"hits": 0})["hits"] for n in MEMO_CACHES)
        misses = sum(
            stats.get(n, {"misses": 0})["misses"] for n in MEMO_CACHES
        )
        return hits, misses

    # Warm + idle phase: every repeat must answer from the memo.
    base = dash()
    assert base == recompute(), "idle dashboard vs recompute"
    h0, m0 = memo_tally()
    t_idle, got = sync_p50(lambda i: dash(), reps=RPS_IDLE_REPS)
    assert got == base
    h1, m1 = memo_tally()
    rate_idle = (h1 - h0) / max((h1 - h0) + (m1 - m0), 1)
    progress(f"idle: p50 {t_idle * 1e3:.2f}ms, memo rate {rate_idle:.3f}")

    # Write rounds: randomized instrumented writes, then the dashboard,
    # then the suspended-recompute oracle.  Every third round also
    # forces a stale base through clear_row (un-instrumented -> OPAQUE
    # packet): the repair layer must fall back, not serve stale.
    rep0 = sum(eng.repairs.repaired.values())
    fb0 = sum(eng.repairs.fallbacks.values())
    tw0 = eng.repairs.touched_words
    times = []        # every timed dashboard run (the serving p50)
    first_times = []  # first run after each write burst: pays the repair
    hits_acc = miss_acc = 0
    forced_stale = 0
    for rnd in range(RPS_ROUNDS):
        for s in rng.choice(RPS_SHARDS, 2, replace=False):
            holder.fragment("rpw", "seg", "standard", int(s)).bulk_import(
                rng.integers(0, RPS_SEG_ROWS, RPS_WRITES_PER_ROUND),
                rng.integers(0, SHARD_WIDTH, RPS_WRITES_PER_ROUND),
            )
        gf = "g1" if rnd % 2 else "g2"
        gs = int(rng.integers(0, RPS_SHARDS))
        holder.fragment("rpw", gf, "standard", gs).set_bit(
            int(rng.integers(0, 5)),
            gs * SHARD_WIDTH + int(rng.integers(0, SHARD_WIDTH)),
        )
        q(f"Set({int(rng.integers(0, RPS_SHARDS * SHARD_WIDTH))}, "
          f"v={int(rng.integers(0, 1024))})")
        if rnd % 3 == 2:
            # Un-instrumented write: load_row_words replaces row 0
            # wholesale with no delta packet (deliberately OPAQUE, per
            # its contract) — repair MUST refuse and recompute.
            # clear_row no longer qualifies: it captures deltas now.
            frag = holder.fragment("rpw", "seg", "standard", 0)
            frag.load_row_words(
                0, __rand(rng, bitops.WORDS64) & __rand(rng, bitops.WORDS64)
            )
            forced_stale += 1
        # Dashboards read more often than they're written: five timed
        # serves per write burst (the first pays the repair; the later
        # ones hit the memo the repair refreshed).  The oracle recompute
        # runs OUTSIDE the tally window — its deliberate misses must
        # not be billed to the serving path.
        hb, mb = memo_tally()
        served = None
        for rep in range(RPS_READS_PER_ROUND):
            t0 = time.perf_counter()
            served = dash()
            dt = time.perf_counter() - t0
            times.append(dt)
            if rep == 0:
                first_times.append(dt)
        ha, ma = memo_tally()
        hits_acc += ha - hb
        miss_acc += ma - mb
        want = recompute()
        assert served == want, (
            f"repair sweep round {rnd}: served != recompute\n"
            f"  served: {served}\n  want:   {want}"
        )
    repaired = sum(eng.repairs.repaired.values()) - rep0
    fallbacks = sum(eng.repairs.fallbacks.values()) - fb0
    touched = eng.repairs.touched_words - tw0
    # A probe that ends in repair counts as served-without-recompute;
    # its memo miss is the write's fault, not the layer's.
    rate_w = (hits_acc + repaired) / max(hits_acc + miss_acc, 1)
    t_write = statistics.median(times)
    assert fallbacks >= forced_stale, (fallbacks, forced_stale)
    assert repaired > 0, "no repair ever served — the lane is dead"

    emit_raw("result_memo_hit_rate_under_write_load", rate_w, "ratio",
             rate_w / max(rate_idle, 1e-9))
    emit_raw("dashboard_p50_under_ingest_vs_idle", t_write / t_idle, "x",
             t_idle / t_write)
    emit_raw("repair_touched_words_per_repair",
             touched / max(repaired, 1), "words", 1.0)
    emit_raw("dashboard_repair_serve_p50_ms",
             statistics.median(first_times) * 1e3, "ms", 1.0)
    snap = eng.repairs.snapshot()
    progress(
        f"write rounds: p50 {t_write * 1e3:.2f}ms ({t_write / t_idle:.2f}x "
        f"idle), repair-serve p50 {statistics.median(first_times) * 1e3:.2f}"
        f"ms, rate {rate_w:.3f}, repaired {repaired}, "
        f"fallbacks {fallbacks} (forced {forced_stale}), "
        f"touched words {touched}, hub {snap['hub']}"
    )


# ---- tiered residency: index >> device budget (--residency-sweep) ----------

RSW_FIELDS = 4
RSW_ROWS = 32  # rows per field; dashboards touch 4 -> partial stacks
RSW_SHARDS = 4
RSW_BLOCKS = 8  # occupied occupancy-blocks per row (of 64): sparse rows,
#                 so promotions genuinely ship blocks, not whole stacks
RSW_WARM_REPS = 40


def residency_sweep():
    """Tiered-residency scenario (docs/residency.md): the index is ~4x
    the configured device budget, so NO single field stack fits — cold
    queries serve from the compressed host tier while async partial
    promotions admit the touched rows, warm queries dispatch on device,
    and the working set evicts cost-priced when it outgrows the budget.
    Emits the guarded headlines:

      oversubscribed_4x_count_p50_ms  warm dashboard p50 at 4x
                                      oversubscription (acceptance:
                                      within 2x of fully_resident)
      fully_resident_count_p50_ms     same queries, budget = whole index
      oversubscribed_4x_cold_p50_ms   the cold host-fallback p50 (the
                                      smooth-degradation curve's other
                                      end — no cliff, no OOM)
      residency_hit_rate              device-served fraction of the
                                      repeated-dashboard phase
                                      (stack hits / (hits + fallbacks))
      promotion_overlap_mbits_s       bytes the promotion worker shipped
                                      over its busy seconds (host decode
                                      of chunk N+1 overlapping the
                                      device scatter of chunk N)

    Every query is differentially asserted bit-exact across the host
    path, the partially-resident engine, and the fully-resident engine.
    The result memo is disabled so the repeated phase measures the
    residency path, not the memo lane."""
    progress("importing jax (residency sweep)")
    import jax

    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.ops import bitops
    from pilosa_tpu.parallel import MeshEngine, make_mesh, pad_shards

    rng = np.random.default_rng(11)
    holder = Holder()
    holder.open()
    idx = holder.create_index("rsw")
    host = {}  # (field, row) -> {shard: words64}
    shards = list(range(RSW_SHARDS))
    w64_per_block = bitops.OCC_BLOCK_WORDS // 2
    for fi in range(RSW_FIELDS):
        f = idx.create_field(f"wf{fi}")
        view = f.view_if_not_exists("standard")
        for r in range(RSW_ROWS):
            host[(fi, r)] = {}
            for s in shards:
                words = np.zeros(bitops.WORDS64, dtype=np.uint64)
                blk = __rand(rng, RSW_BLOCKS * w64_per_block) & __rand(
                    rng, RSW_BLOCKS * w64_per_block
                )
                words[: RSW_BLOCKS * w64_per_block] = blk
                view.fragment_if_not_exists(s).load_row_words(r, words)
                host[(fi, r)][s] = words
        for frag in view.fragments.values():
            frag.cache.invalidate()
    mesh = make_mesh(len(jax.devices()))
    S = pad_shards(RSW_SHARDS, mesh)
    row_shard_bytes = bitops.WORDS * 4 + 16
    stack_bytes = RSW_ROWS * S * row_shard_bytes
    total_bytes = RSW_FIELDS * stack_bytes
    # The 4x-oversubscription acceptance shape: one row-shard under a
    # quarter of the index, so no single stack fits the budget (with 4
    # equal stacks, exactly total/4 would fit one).
    budget = total_bytes // 4 - S * row_shard_bytes
    assert stack_bytes > budget, "shape error: a full stack must NOT fit"
    assert total_bytes >= 4 * budget
    progress(
        f"index {total_bytes >> 20} MiB over {RSW_FIELDS} stacks, device "
        f"budget {budget >> 20} MiB (4x oversubscribed)"
    )

    def pc(x):
        return int(np.sum(np.bitwise_count(x)))

    dashboard = []  # (query, expected) — one Intersect per field
    for fi in range(RSW_FIELDS):
        ra, rb = 2 * fi, 2 * fi + 1
        q = f"Count(Intersect(Row(wf{fi}={ra}), Row(wf{fi}={rb})))"
        want = sum(pc(host[(fi, ra)][s] & host[(fi, rb)][s]) for s in shards)
        dashboard.append((q, want))

    ex_host = Executor(holder)
    eng_full = MeshEngine(holder, mesh, max_resident_bytes=2 * total_bytes)
    eng_full.result_memo.maxsize = 0
    ex_full = Executor(holder, mesh_engine=eng_full)
    eng = MeshEngine(holder, mesh, max_resident_bytes=budget)
    eng.result_memo.maxsize = 0
    ex = Executor(holder, mesh_engine=eng)

    # Fully-resident baseline (sync builds; this is the 2x reference).
    for q, want in dashboard:
        assert ex_full.execute("rsw", q).results[0] == want, q
    t_full = cpu_time(
        lambda: [ex_full.execute("rsw", q) for q, _ in dashboard], reps=8
    ) / len(dashboard)

    # COLD phase at 4x oversubscription: host fallback, bit-exact, and
    # an async promotion per stack — zero OOMs/refusals by construction.
    t0 = time.perf_counter()
    for q, want in dashboard:
        got = ex.execute("rsw", q).results[0]
        assert got == want, (q, got, want)
    t_cold = (time.perf_counter() - t0) / len(dashboard)
    assert eng.host_fallbacks >= len(dashboard), eng.host_fallbacks
    assert eng.residency.flush(120.0), "promotions did not drain"
    snap = eng.residency.snapshot()
    assert snap["partialPromotions"] >= RSW_FIELDS, snap
    progress(
        f"cold p50 {t_cold * 1e3:.2f} ms ({eng.host_fallbacks} host "
        f"fallbacks, {snap['partialPromotions']} partial promotions)"
    )

    # WARM repeated-dashboard phase: the promoted working set serves on
    # device; hit rate = stack hits / (hits + host fallbacks).
    hits0 = eng.cache_stats["stack"][0]
    fb0 = eng.host_fallbacks
    times = []
    for _ in range(RSW_WARM_REPS):
        t0 = time.perf_counter()
        for q, want in dashboard:
            assert ex.execute("rsw", q).results[0] == want
        times.append((time.perf_counter() - t0) / len(dashboard))
    t_warm = statistics.median(times)
    hits = eng.cache_stats["stack"][0] - hits0
    fallbacks = eng.host_fallbacks - fb0
    hit_rate = hits / max(1, hits + fallbacks)

    # GROWTH phase: rotate to disjoint row pairs so working sets grow
    # past the budget — evictions must be priced, never an OOM.
    ev0 = eng.cache_snapshot()["evictions"]
    for off in (8, 16, 24):
        for fi in range(RSW_FIELDS):
            ra, rb = off + 2 * fi, off + 2 * fi + 1
            q = f"Count(Intersect(Row(wf{fi}={ra}), Row(wf{fi}={rb})))"
            want = sum(
                pc(host[(fi, ra)][s] & host[(fi, rb)][s]) for s in shards
            )
            assert ex.execute("rsw", q).results[0] == want, q
        assert eng.residency.flush(120.0), "growth promotions did not drain"
    growth_evictions = eng.cache_snapshot()["evictions"] - ev0

    snap = eng.residency.snapshot()
    overlap_mbits = (
        snap["promotedBytes"] * 8 / max(snap["promoteSeconds"], 1e-9) / 1e6
    )
    emit_raw(
        "fully_resident_count_p50_ms", t_full * 1e3, "ms", 1.0
    )
    emit_raw(
        "oversubscribed_4x_count_p50_ms", t_warm * 1e3, "ms",
        t_full / max(t_warm, 1e-9),
    )
    emit_raw(
        "oversubscribed_4x_cold_p50_ms", t_cold * 1e3, "ms",
        t_full / max(t_cold, 1e-9),
    )
    emit_raw("residency_hit_rate", hit_rate, "ratio", hit_rate)
    emit_raw(
        "promotion_overlap_mbits_s", overlap_mbits, "Mbits/s", 1.0
    )
    emit_raw(
        "residency_growth_evictions", growth_evictions, "evictions", 1.0
    )
    ws = eng.cache_snapshot()["workingSet"]
    print(json.dumps({
        "metric": "residency_resident_fraction",
        "value": ws["perIndex"].get("rsw", {}).get("residentFraction", 0.0),
        "unit": "ratio",
        "vs_baseline": 1.0,
    }), flush=True)
    progress(
        f"warm p50 {t_warm * 1e3:.2f} ms vs fully-resident "
        f"{t_full * 1e3:.2f} ms ({t_warm / max(t_full, 1e-9):.2f}x); "
        f"hit rate {hit_rate:.2f}; promotion overlap "
        f"{overlap_mbits:.1f} Mbits/s; {growth_evictions} growth evictions"
    )
    # Acceptance shape (ISSUE 15): smooth degradation, no cliff.
    assert hit_rate > 0.5, f"residency_hit_rate {hit_rate:.2f} <= 0.5"
    eng.close()
    eng_full.close()

    # DEEP oversubscription (ISSUE 20): at 8x and 16x no meaningful row
    # subset fits as pow2-padded partial matrices, but the packed
    # 2KiB-block pool ships only OCCUPIED blocks — the dashboard's
    # pooled working set stays device-resident even at 1/16th of the
    # index, so the warm hit rate holds >0.9 with zero OOMs/declines.
    def deep_phase(times_over):
        engN = MeshEngine(
            holder, mesh, max_resident_bytes=total_bytes // times_over
        )
        engN.result_memo.maxsize = 0
        exN = Executor(holder, mesh_engine=engN)
        for q, want in dashboard:  # cold: host-exact + async promotion
            got = exN.execute("rsw", q).results[0]
            assert got == want, (q, got, want)
        assert engN.residency.flush(120.0), "deep promotions did not drain"
        hits0 = engN.cache_stats["stack"][0]
        fb0 = engN.host_fallbacks
        times = []
        for _ in range(RSW_WARM_REPS):
            t0 = time.perf_counter()
            for q, want in dashboard:
                assert exN.execute("rsw", q).results[0] == want
            times.append((time.perf_counter() - t0) / len(dashboard))
        hits = engN.cache_stats["stack"][0] - hits0
        fallbacks = engN.host_fallbacks - fb0
        rate = hits / max(1, hits + fallbacks)
        snapN = engN.residency.snapshot()
        assert snapN["declined"] == 0, snapN  # no OOMs, no refusals
        engN.close()
        return statistics.median(times), rate

    t_warm8, rate8 = deep_phase(8)
    t_warm16, rate16 = deep_phase(16)
    emit_raw("residency_hit_rate_8x", rate8, "ratio", rate8)
    emit_raw(
        "oversubscribed_8x_warm_vs_resident",
        t_warm8 / max(t_full, 1e-9), "x", t_full / max(t_warm8, 1e-9),
    )
    emit_raw("residency_hit_rate_16x", rate16, "ratio", rate16)
    progress(
        f"8x: warm p50 {t_warm8 * 1e3:.2f} ms "
        f"({t_warm8 / max(t_full, 1e-9):.2f}x resident), hit rate "
        f"{rate8:.2f}; 16x: {t_warm16 * 1e3:.2f} ms, hit rate {rate16:.2f}"
    )
    assert rate8 > 0.9, f"residency_hit_rate_8x {rate8:.2f} <= 0.9"

    # In-run A/B at EQUAL budget: does promote-ahead actually buy warm
    # latency?  Two single-query dashboards over disjoint stacks
    # alternate with a drain gap between them, under a budget that fits
    # ONE pooled working set but not both — so each arrival needs its
    # stack promoted.  Advisor-off pays a host fallback + demand
    # promotion every swing; advisor-on has the next stack promoted
    # during the gap (next-touch eviction protects it from the pricer),
    # so warm arrivals dispatch on device.  Learning prefix excluded.
    from pilosa_tpu.api import API, QueryRequest
    from pilosa_tpu.parallel.advisor import ADVISOR
    from pilosa_tpu.util import plan_miner
    from pilosa_tpu.util.heat import HEAT

    pool64_bytes = 64 * S * bitops.OCC_BLOCK_WORDS * 4  # one 64-slot pool
    ab_budget = (3 * pool64_bytes) // 2  # fits one pooled set, not two
    ab_reqs = []
    for fi in (0, 2):  # disjoint stacks: wf0 vs wf2
        ra, rb = 2 * fi, 2 * fi + 1
        q = f"Count(Intersect(Row(wf{fi}={ra}), Row(wf{fi}={rb})))"
        want = sum(pc(host[(fi, ra)][s] & host[(fi, rb)][s]) for s in shards)
        ab_reqs.append((QueryRequest("rsw", q), want))

    AB_CYCLES, AB_LEARN = 12, 2

    def ab_arm(drive):
        HEAT.reset()
        plan_miner.MINER.reset()
        ADVISOR.reset()
        ADVISOR.drive_promotions = drive
        engA = MeshEngine(holder, mesh, max_resident_bytes=ab_budget)
        engA.result_memo.maxsize = 0
        api = API(holder=holder, mesh_engine=engA)
        times = []
        try:
            for cyc in range(AB_CYCLES):
                for req, want in ab_reqs:
                    t0 = time.perf_counter()
                    got = int(api.query(req).results[0])
                    dt = time.perf_counter() - t0
                    assert got == want, (req.query, got, want)
                    # The gap: real dashboards have think-time between
                    # swings; promote-ahead (or the demand promotion the
                    # miss just queued) lands inside it.
                    assert engA.residency.flush(60.0)
                    if cyc >= AB_LEARN:
                        times.append(dt)
            fallbacks = engA.host_fallbacks
        finally:
            ADVISOR.drive_promotions = True
            engA.close()
        return statistics.median(times), fallbacks

    t_off, fb_off = ab_arm(False)
    t_on, fb_on = ab_arm(True)
    ab_speedup = t_off / max(t_on, 1e-9)
    emit_raw("residency_advisor_ab_speedup", ab_speedup, "x", ab_speedup)
    progress(
        f"advisor A/B at equal budget: off p50 {t_off * 1e3:.2f} ms "
        f"({fb_off} host fallbacks) vs on p50 {t_on * 1e3:.2f} ms "
        f"({fb_on}) = {ab_speedup:.1f}x"
    )
    assert ab_speedup > 1.0, (
        f"advisor-on ({t_on * 1e3:.2f} ms) did not beat advisor-off "
        f"({t_off * 1e3:.2f} ms) at equal budget"
    )
    holder.close()


# ---- ingest: sustained bulk-import throughput + freshness (--ingest-sweep)

ING_BITS_PER_ROW = 16  # rows scale with batch size (n_bits/16 distinct
#                        rows): the high-cardinality (term/tag store)
#                        ingest shape, where per-row host overhead
#                        dominates the pre-PR path
ING_CHUNKS = 4  # sustained chunks per shape (fresh random bits each)
ING_FRESH_REPS = 12


def _ing_batch(rng, n_bits, n_rows):
    """~n_bits unique storage positions spread over n_rows rows."""
    rows = rng.integers(0, n_rows, int(n_bits * 1.1)).astype(np.uint64)
    cols = rng.integers(0, 1 << 20, int(n_bits * 1.1)).astype(np.uint64)
    return np.unique((rows << np.uint64(20)) | cols)[:n_bits]


def _field_import_rowloop(field, row_ids, column_ids):
    """The pre-PR field.import_bulk, byte-for-byte: one python loop
    iteration per BIT to group by (view, shard), then the per-row
    fragment walk (bulk_import_rowloop) — the bench's same-machine
    baseline for the id-pairs ingest surface."""
    from pilosa_tpu.core.view import VIEW_STANDARD

    SW = 1 << 20
    groups = {}
    for r, c in zip(row_ids, column_ids):
        rows, cols = groups.setdefault(VIEW_STANDARD, {}).setdefault(
            c // SW, ([], [])
        )
        rows.append(r)
        cols.append(c)
    changed = 0
    for view_name, shards in groups.items():
        view = field.view_if_not_exists(view_name)
        for shard, (rows, cols) in shards.items():
            frag = view.fragment_if_not_exists(shard)
            changed += frag.bulk_import_rowloop(rows, cols)
    return changed


def _id_pairs_headline(rng, idx, col_span=8 << 20):
    """The guarded id-pairs headline, shared by --ingest-sweep and
    --streaming-sweep so the measurement protocol can never diverge
    between the two while bench_guard compares both against one
    baseline: field.import_bulk (native shard split + native sparse
    merge + concurrent fragments) vs the pre-PR put()-loop + row walk.
    Each path gets its NATURAL input form — arrays for the vectorized
    path (the documented surface since the no-list-round-trip change),
    lists for the per-bit rowloop (it iterates python; feeding it numpy
    scalars would unfairly slow the baseline).  Conversions happen
    outside both timers."""
    fa, fb = idx.create_field("fa"), idx.create_field("fb")
    tn = to = bits = 0
    for _ in range(ING_CHUNKS):
        rows = rng.integers(0, 2048, 1 << 20)
        cols = rng.integers(0, col_span, 1 << 20)
        rows_l, cols_l = rows.tolist(), cols.tolist()
        bits += rows.size
        t0 = time.perf_counter()
        ca = fa.import_bulk(rows, cols)
        tn += time.perf_counter() - t0
        t0 = time.perf_counter()
        cb = _field_import_rowloop(fb, rows_l, cols_l)
        to += time.perf_counter() - t0
        assert ca == cb
    mb_new, mb_old = bits / tn / 1e6, bits / to / 1e6
    emit_raw("ingest_bits_mbits_s", mb_new, "Mbits/s", mb_new / mb_old)
    emit_raw("ingest_bits_rowloop_mbits_s", mb_old, "Mbits/s", 1.0)
    progress(
        f"id-pairs: {mb_new:.1f} vs rowloop {mb_old:.2f} Mbits/s "
        f"({mb_new / mb_old:.1f}x)"
    )


def ingest_sweep():
    """Sustained bulk-import throughput, new vectorized paths vs the
    retained pre-PR per-row implementations on the SAME machine and
    data (fragment.bulk_import_rowloop / import_roaring_rowloop), at
    several batch sizes — plus the vectorized-decode micro, a pipelined
    write->query freshness p50 through a live engine, and the ingest
    sync worker's coalescing telemetry.  Headline JSONL metric:
    ``ingest_mbits_s`` (1M-bit roaring batch, sustained); the
    acceptance gate is its ratio over ``ingest_rowloop_mbits_s``."""
    progress("importing jax (ingest sweep)")
    import jax

    from pilosa_tpu import pql
    from pilosa_tpu.api import API, ImportRequest
    from pilosa_tpu.core.fragment import Fragment
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.parallel import MeshEngine, make_mesh
    from pilosa_tpu.roaring import codec

    rng = np.random.default_rng(13)

    # ---- roaring fast path vs pre-PR per-row path (headline) -------------
    for n_bits, label in ((1 << 16, "64k"), (1 << 18, "256k"), (1 << 20, "1m")):
        fa = Fragment("ing", "f", "standard", 0)
        fb = Fragment("ing", "f", "standard", 0)
        tn = to = bits = 0
        for _ in range(ING_CHUNKS):
            vals = _ing_batch(rng, n_bits, n_bits // ING_BITS_PER_ROW)
            data = codec.serialize(vals)
            bits += vals.size
            t0 = time.perf_counter()
            ca = fa.import_roaring(data)
            tn += time.perf_counter() - t0
            t0 = time.perf_counter()
            cb = fb.import_roaring_rowloop(data)
            to += time.perf_counter() - t0
            assert ca == cb, (label, ca, cb)
        assert fa.row_ids() == fb.row_ids()
        for r in fa.row_ids()[::97]:
            assert np.array_equal(fa.row_positions(r), fb.row_positions(r))
        mb_new, mb_old = bits / tn / 1e6, bits / to / 1e6
        emit_raw(
            f"ingest_roaring_{label}_mbits_s", mb_new, "Mbits/s",
            mb_new / mb_old,
        )
        progress(
            f"roaring {label}: {mb_new:.1f} vs rowloop {mb_old:.2f} Mbits/s "
            f"({mb_new / mb_old:.1f}x)"
        )
        if label == "1m":
            emit_raw("ingest_mbits_s", mb_new, "Mbits/s", mb_new / mb_old)
            emit_raw("ingest_rowloop_mbits_s", mb_old, "Mbits/s", 1.0)
            emit_raw(
                "ingest_speedup", mb_new / mb_old, "x", mb_new / mb_old
            )

    # ---- decode micro: vectorized container decode vs scalar oracle ------
    vals = _ing_batch(rng, 1 << 20, (1 << 20) // ING_BITS_PER_ROW)
    data = codec.serialize(vals)
    t_np = min(
        cpu_time(lambda: codec._deserialize_np(data), reps=1)
        for _ in range(3)
    )
    t_py = cpu_time(lambda: codec._deserialize_py(data), reps=1)
    emit_raw(
        "ingest_decode_mbits_s", vals.size / t_np / 1e6, "Mbits/s",
        t_py / t_np,
    )
    progress(f"decode: np {t_np * 1e3:.0f}ms vs py {t_py * 1e3:.0f}ms")

    # ---- id-pairs surface old-vs-new (shared with --streaming-sweep) -----
    holder = Holder()
    holder.open()
    idx = holder.create_index("ing")
    _id_pairs_headline(rng, idx)

    # ---- pipelined write -> query freshness through a live engine --------
    mesh = make_mesh(len(jax.devices()))
    eng = MeshEngine(holder, mesh)
    api = API(holder=holder, mesh_engine=eng)
    fq = idx.create_field("q")
    FRESH_ROWS, FRESH_SHARDS = 64, 4
    shards = list(range(FRESH_SHARDS))
    # Seed every row up front so the resident stack's row table is
    # stable and each write syncs as an incremental scatter.
    seed_rows, seed_cols = [], []
    for s in range(FRESH_SHARDS):
        for r in range(FRESH_ROWS):
            seed_rows.append(r)
            seed_cols.append((s << 20) + r)
    fq.import_bulk(seed_rows, seed_cols)
    call = pql.parse("Intersect(Row(q=1), Row(q=2))").calls[0]
    base = eng.count("ing", call, shards)  # warm: builds the stack
    syncer = eng.ingest_syncer()
    rebuilds0 = eng.stack_rebuilds
    lat = []
    nonce = iter(range(1, 1 << 30))
    for i in range(ING_FRESH_REPS):
        n = next(nonce)
        wcols = [
            (s << 20) + (7919 * n + 131 * s) % (1 << 20)
            for s in range(FRESH_SHARDS)
        ]
        t0 = time.perf_counter()
        api.import_bits(
            ImportRequest(
                "ing", "q",
                row_ids=[1 + (n % 2)] * FRESH_SHARDS, column_ids=wcols,
            )
        )
        got = eng.count("ing", call, shards)
        lat.append(time.perf_counter() - t0)
        assert got >= 0
    syncer.flush()
    assert eng.stack_rebuilds == rebuilds0, "ingest sync forced a rebuild"
    fresh_p50 = statistics.median(lat)
    # "idle" = no concurrent query load: the guarded under-load headline
    # ingest_freshness_p50_ms belongs to --streaming-sweep alone — both
    # sweeps into one capture must not overwrite it (last-line-wins in
    # bench_guard would make the guarded value run-order dependent).
    emit_raw("ingest_freshness_idle_p50_ms", fresh_p50 * 1e3, "ms", 1.0)
    snap = syncer.snapshot()
    emit_raw("ingest_sync_chunks", snap["chunks"], "chunks", 1.0)
    emit_raw("ingest_sync_coalesced", snap["coalesced"], "chunks", 1.0)
    progress(
        f"freshness p50 {fresh_p50 * 1e3:.1f}ms; sync {snap['syncs']} passes "
        f"over {snap['chunks']} chunks ({snap['coalesced']} coalesced)"
    )


# ---- streaming: sustained concurrent write+read (--streaming-sweep) ------

STREAM_SHARDS = 4
STREAM_ROWS = 64
STREAM_BATCH_BITS = 1 << 17  # bits per import batch under load
STREAM_BATCHES = 16
STREAM_IDLE_QUERY_REPS = 40
STREAM_QUERY_PACE_S = 0.005  # ~200 QPS read load: an unthrottled
#                              closed loop of sub-ms memo-hit queries
#                              measures GIL spin, not serving behavior


def chaos_sweep(fault="kill"):
    """Serving-through-failure bench (docs/durability.md): a REAL
    3-process gossip cluster at replicas=2 / ack=logged.  Phase A
    (healthy) measures closed-loop Count QPS through the coordinator
    under primary-mode vs any-mode replica reads — the read-scaling
    ratio replicaN>1 buys (``replica_read_qps_gain``; ~1.0 on a single
    shared-CPU host, the real separation needs multi-host).  Phase B
    fails a replica mid-load — SIGKILL (``fault="kill"``, the default)
    or a deterministic network partition injected through POST
    /debug/faults (``--fault partition``) — and measures the fraction
    of queries that still answered across the failure + detection +
    degraded window (``availability_under_failure_pct``), then the
    fraction of DESTRUCTIVE writes (Clears on shards the dead node
    owns) that ack through the degraded steady state
    (``destructive_write_availability_pct`` — 0 before hinted handoff,
    100 with it).  Partition mode additionally HEALS the cut and emits
    ``partition_heal_seconds`` (heal -> cluster NORMAL + hint queues
    drained + the partitioned node bit-exact, zero reverted clears).
    All guarded headlines are bench_guard AUTO_REQUIREd once baselined,
    with absolute 90% floors on both availability percentages."""
    import http.client
    import os
    import signal
    import socket
    import subprocess
    import sys as _sys
    import tempfile
    import threading
    import urllib.request

    from pilosa_tpu.ops import SHARD_WIDTH

    def free_port():
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    tmp = tempfile.mkdtemp()
    # The shared chaos node bootstrap (scripts/chaos_node.py — also the
    # drill test's and smoke stage's server), so this headline can
    # never be measured with boot wiring the drill didn't run.
    script = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "scripts", "chaos_node.py",
    )
    ports = [free_port() for _ in range(3)]
    gports = [free_port() for _ in range(3)]
    env = dict(
        os.environ, JAX_PLATFORMS="cpu",
        PYTHONPATH=os.path.dirname(os.path.abspath(__file__)),
    )
    procs = [
        subprocess.Popen(
            [
                _sys.executable, script, f"n{i}", str(ports[i]),
                str(gports[i]), str(gports[0]), os.path.join(tmp, f"n{i}"),
                "--ack", "logged",
                # Partition mode heals and measures recovery: the
                # production 15 s holddown would dominate the heal
                # headline, so the drills run the documented fast
                # setting (docs/durability.md discusses the tradeoff).
                "--recovery-holddown-ms", "500",
            ],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
            text=True,
        )
        for i in range(3)
    ]

    def post(port, path, body, timeout=30, headers=None):
        req = urllib.request.Request(
            f"http://localhost:{port}{path}", data=body, method="POST"
        )
        req.add_header("Content-Type", "application/json")
        for k, v in (headers or {}).items():
            req.add_header(k, v)
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return json.loads(resp.read())

    try:
        for p in procs:
            assert p.stdout.readline().startswith("READY"), "boot failed"
        deadline = time.time() + 60
        while time.time() < deadline:
            with urllib.request.urlopen(
                f"http://localhost:{ports[0]}/status", timeout=10
            ) as resp:
                st = json.loads(resp.read())
            if len(st["nodes"]) == 3 and st["state"] == "NORMAL":
                break
            time.sleep(0.2)
        else:
            raise AssertionError(
                f"cluster never converged to 3-node NORMAL: {st} — "
                "headlines must not be measured on a malformed cluster"
            )
        progress("chaos-sweep: 3-node cluster NORMAL")
        post(ports[0], "/index/i", b"{}")
        post(ports[0], "/index/i/field/f", b'{"options": {"type": "set"}}')
        n_shards = 12
        cols = [
            s * SHARD_WIDTH + k * 17 for s in range(n_shards)
            for k in range(64)
        ]
        post(
            ports[0], "/index/i/field/f/import",
            json.dumps(
                {"rowIDs": [1] * len(cols), "columnIDs": cols}
            ).encode(),
            timeout=120,
        )
        # availableShards propagate over ASYNC gossip piggybacks: poll
        # until the coordinator routes the whole query.
        deadline = time.time() + 30
        oracle = -1
        while time.time() < deadline:
            oracle = post(
                ports[0], "/index/i/query", b"Count(Row(f=1))", timeout=60
            )["results"][0]
            if oracle == len(cols):
                break
            time.sleep(0.3)
        assert oracle == len(cols), (oracle, len(cols))

        def qps_for(headers, seconds=3.0):
            """Closed-loop Counts on one keep-alive connection."""
            c = http.client.HTTPConnection("localhost", ports[0], timeout=30)
            n = 0
            end = time.monotonic() + seconds
            body = b"Count(Row(f=1))"
            while time.monotonic() < end:
                c.request(
                    "POST", "/index/i/query", body=body,
                    headers=dict(headers or {}),
                )
                r = c.getresponse()
                r.read()
                assert r.status == 200, r.status
                n += 1
            c.close()
            return n / seconds

        qps_for({}, 0.5)  # warm parse/memo caches before timing
        qps_primary = qps_for({})
        qps_any = qps_for({"X-Pilosa-Replica-Read": "any"})
        emit_raw(
            "replica_read_qps_gain", qps_any / qps_primary, "x",
            qps_any / qps_primary,
        )
        progress(
            f"chaos-sweep: qps primary={qps_primary:.0f} "
            f"any={qps_any:.0f}"
        )

        def get(port, path, timeout=10):
            with urllib.request.urlopen(
                f"http://localhost:{port}{path}", timeout=timeout
            ) as resp:
                return json.loads(resp.read())

        def shard_owners(s):
            return {
                n["id"]
                for n in get(
                    ports[0], f"/internal/fragment/nodes?index=i&shard={s}"
                )
            }

        # Pre-fault owner map: which shards the victim (n1) owns, and
        # one still-set column per such shard for the destructive-write
        # probe below.
        n1_shards = [s for s in range(n_shards) if "n1" in shard_owners(s)]
        assert n1_shards, "placement gave n1 no shards?"

        # Phase B: availability through the failure.  The load runs the
        # whole window; the fault lands 1s in.
        ok, err = [0], [0]
        stop = threading.Event()

        def load():
            while not stop.is_set():
                try:
                    out = post(
                        ports[0], "/index/i/query", b"Count(Row(f=1))",
                        timeout=30,
                    )
                    assert out["results"][0] == oracle
                    ok[0] += 1
                except Exception:  # noqa: BLE001
                    err[0] += 1
                time.sleep(0.02)

        t = threading.Thread(target=load)
        t.start()
        time.sleep(1.0)
        kill_t = time.monotonic()
        if fault == "partition":
            # Deterministic cut via the fault plane: ONE rule body
            # POSTed to every node — each enforces only its own side
            # (net/faults.py), exactly like a real network partition.
            partition = json.dumps({
                "seed": 1,
                "rules": [{
                    "action": "partition",
                    "a": [
                        f"127.0.0.1:{ports[1]}", f"127.0.0.1:{gports[1]}",
                    ],
                    "b": [
                        f"127.0.0.1:{ports[0]}", f"127.0.0.1:{gports[0]}",
                        f"127.0.0.1:{ports[2]}", f"127.0.0.1:{gports[2]}",
                    ],
                }],
            }).encode()
            for p in ports:
                post(p, "/debug/faults", partition)
        else:
            os.kill(procs[1].pid, signal.SIGKILL)
            procs[1].wait(timeout=10)
        time.sleep(6.0)  # fault + detection + degraded steady state
        stop.set()
        t.join()
        total = ok[0] + err[0]
        avail = 100.0 * ok[0] / max(1, total)
        emit_raw(
            "availability_under_failure_pct", avail, "pct", avail / 100.0
        )
        progress(
            f"chaos-sweep: {ok[0]}/{total} queries answered through the "
            f"{fault} ({avail:.1f}%), window "
            f"{time.monotonic() - kill_t:.1f}s"
        )

        # Destructive-write availability through the DEGRADED steady
        # state: Clears on shards the dead node owns.  Before hinted
        # handoff every one failed loudly (0%); with the hint queue
        # each acks and its miss is durably queued for replay (100%).
        deadline = time.time() + 30
        while time.time() < deadline:
            if get(ports[0], "/status")["state"] != "NORMAL":
                break
            time.sleep(0.2)
        cleared = []
        d_ok = 0
        for s in n1_shards:
            col = s * SHARD_WIDTH  # k=0 column, set during seeding
            try:
                out = post(
                    ports[0], "/index/i/query",
                    f"Clear({col}, f=1)".encode(), timeout=30,
                )
                assert out["results"][0] is True
                d_ok += 1
                cleared.append(col)
            except Exception:  # noqa: BLE001 — counted against availability
                pass
        d_avail = 100.0 * d_ok / max(1, len(n1_shards))
        emit_raw(
            "destructive_write_availability_pct", d_avail, "pct",
            d_avail / 100.0,
        )
        progress(
            f"chaos-sweep: {d_ok}/{len(n1_shards)} destructive writes "
            f"acked under single-owner failure ({d_avail:.1f}%)"
        )

        if fault == "partition":
            # Heal and measure recovery: POST empty rule tables, then
            # wait for cluster NORMAL + every hint queue drained + the
            # partitioned node bit-exact (cleared bits ABSENT — the
            # zero-reverted-clears acceptance — and every surviving
            # bit present on its owned shards).
            heal_t = time.monotonic()
            for p in ports:
                post(p, "/debug/faults", json.dumps({"rules": []}).encode())
            expect = oracle - len(cleared)
            # The partitioned node's LOCAL truth for its owned shards:
            # 64 seeded bits per shard minus the one clear that acked
            # per shard — reachable only via hint replay.
            expect_n1 = 64 * len(n1_shards) - len(cleared)
            deadline = time.time() + 90
            healed = False
            while time.time() < deadline:
                try:
                    st = get(ports[0], "/status")
                    hints = get(ports[0], "/debug/vars").get("hints", {})
                    n1_local = post(
                        ports[1], "/index/i/query",
                        json.dumps({
                            "query": "Count(Row(f=1))", "remote": True,
                            "shards": n1_shards,
                        }).encode(), timeout=30,
                    )["results"][0]
                    if (
                        st["state"] == "NORMAL"
                        and not hints.get("pending")
                        and n1_local == expect_n1
                        and post(
                            ports[0], "/index/i/query",
                            b"Count(Row(f=1))", timeout=30,
                        )["results"][0] == expect
                    ):
                        healed = True
                        break
                except Exception:  # noqa: BLE001 — still healing
                    pass
                time.sleep(0.3)
            assert healed, "partition never healed to convergence"
            heal_s = time.monotonic() - heal_t
            emit_raw("partition_heal_seconds", heal_s, "s", heal_s)
            # Zero reverted clears: stability across two further
            # anti-entropy intervals — the majority-tie merge must NOT
            # resurrect any cleared bit from the recovered node.
            time.sleep(3.5)
            after = post(
                ports[0], "/index/i/query", b"Count(Row(f=1))", timeout=30
            )["results"][0]
            assert after == expect, (
                f"anti-entropy reverted clears: count {after} != {expect}"
            )
            progress(
                f"chaos-sweep: partition healed in {heal_s:.1f}s, "
                f"{len(cleared)} clears stable through anti-entropy "
                "(zero reverts)"
            )
    finally:
        for p in procs:
            try:
                p.kill()
            except ProcessLookupError:
                pass
        for p in procs:
            p.communicate(timeout=30)


def streaming_sweep():
    """Guarded streaming headline (docs/ingest.md): continuous id-pairs
    imports through a LIVE engine while a query load runs on another
    thread.  Emits, from the same run:

    - ``ingest_bits_mbits_s`` — the id-pairs surface old-vs-new (same
      protocol as --ingest-sweep: arrays to the vectorized path, lists
      to the retained rowloop oracle, conversions untimed);
    - ``ingest_streaming_mbits_s`` — sustained import throughput WHILE
      the query load runs;
    - ``ingest_freshness_p50_ms`` — write->readable latency under load
      (import ack + a count that reflects the write);
    - ``query_p50_under_ingest_ms`` vs ``query_p50_idle_ms`` — read
      latency with and without the concurrent write stream.

    bench_guard AUTO-REQUIREs the ingest/freshness headlines once a
    baseline records them."""
    import threading

    progress("importing jax (streaming sweep)")
    import jax

    from pilosa_tpu import pql
    from pilosa_tpu.api import API, ImportRequest
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.parallel import MeshEngine, make_mesh

    rng = np.random.default_rng(29)

    # -- phase A: the id-pairs old-vs-new headline (oracle in-run) ---------
    holder = Holder()
    holder.open()
    idx = holder.create_index("stream")
    _id_pairs_headline(rng, idx)

    # -- phase B: concurrent write+read through a live engine --------------
    mesh = make_mesh(len(jax.devices()))
    eng = MeshEngine(holder, mesh)
    api = API(holder=holder, mesh_engine=eng)
    fq = idx.create_field("q")
    seed_rows, seed_cols = [], []
    for s in range(STREAM_SHARDS):
        for r in range(STREAM_ROWS):
            seed_rows.append(r)
            seed_cols.append((s << 20) + r)
    fq.import_bulk(seed_rows, seed_cols)
    call = pql.parse("Intersect(Row(q=1), Row(q=2))").calls[0]
    shards = list(range(STREAM_SHARDS))
    eng.count("stream", call, shards)  # warm: builds the stack
    syncer = eng.ingest_syncer()

    # Idle read baseline (no concurrent writes).
    idle = []
    for _ in range(STREAM_IDLE_QUERY_REPS):
        t0 = time.perf_counter()
        eng.count("stream", call, shards)
        idle.append(time.perf_counter() - t0)
    idle_p50 = statistics.median(idle)

    stop = threading.Event()
    q_lat = []

    def query_load():
        while not stop.is_set():
            t0 = time.perf_counter()
            eng.count("stream", call, shards)
            q_lat.append(time.perf_counter() - t0)
            time.sleep(STREAM_QUERY_PACE_S)

    qt = threading.Thread(target=query_load, name="stream-query", daemon=True)
    qt.start()
    fresh_lat = []
    t_import = 0.0
    bits_in = 0
    nonce = iter(range(1, 1 << 30))
    try:
        for _ in range(STREAM_BATCHES):
            n = next(nonce)
            # Bulk stream batch: fresh random bits across the live shards.
            rows = rng.integers(0, 2048, STREAM_BATCH_BITS)
            cols = rng.integers(0, STREAM_SHARDS << 20, STREAM_BATCH_BITS)
            t0 = time.perf_counter()
            api.import_bits(
                ImportRequest("stream", "fa", row_ids=rows, column_ids=cols)
            )
            t_import += time.perf_counter() - t0
            bits_in += rows.size
            # Freshness probe: a marked write followed by a count that
            # reflects it (write -> readable round trip, PR 5 protocol,
            # now under concurrent query load).
            wcols = [
                (s << 20) + (7919 * n + 131 * s) % (1 << 20)
                for s in range(STREAM_SHARDS)
            ]
            t0 = time.perf_counter()
            api.import_bits(
                ImportRequest(
                    "stream", "q",
                    row_ids=[1 + (n % 2)] * STREAM_SHARDS, column_ids=wcols,
                )
            )
            got = eng.count("stream", call, shards)
            fresh_lat.append(time.perf_counter() - t0)
            assert got >= 0
    finally:
        stop.set()
        qt.join(timeout=10)
    syncer.flush()
    fresh_p50 = statistics.median(fresh_lat)
    under_p50 = statistics.median(q_lat) if q_lat else float("nan")
    emit_raw(
        "ingest_streaming_mbits_s", bits_in / t_import / 1e6, "Mbits/s", 1.0
    )
    emit_raw("ingest_freshness_p50_ms", fresh_p50 * 1e3, "ms", 1.0)
    emit_raw("query_p50_under_ingest_ms", under_p50 * 1e3, "ms", 1.0)
    # p50 under write-invalidated memo churn is mostly memo-served (the
    # dashboard shape); p95 carries the invalidation-miss device reads.
    q_sorted = sorted(q_lat)
    under_p95 = (
        q_sorted[int(len(q_sorted) * 0.95)] if q_sorted else float("nan")
    )
    emit_raw("query_p95_under_ingest_ms", under_p95 * 1e3, "ms", 1.0)
    emit_raw("query_p50_idle_ms", idle_p50 * 1e3, "ms", 1.0)
    snap = syncer.snapshot()
    emit_raw("ingest_sync_chunks", snap["chunks"], "chunks", 1.0)
    emit_raw("ingest_sync_coalesced", snap["coalesced"], "chunks", 1.0)
    progress(
        f"streaming: {bits_in / t_import / 1e6:.1f} Mbits/s under load; "
        f"freshness p50 {fresh_p50 * 1e3:.1f}ms; query p50 "
        f"{under_p50 * 1e3:.1f}ms under ingest vs {idle_p50 * 1e3:.1f}ms "
        f"idle; {len(q_lat)} queries during {STREAM_BATCHES} batches "
        f"({snap['coalesced']}/{snap['chunks']} sync chunks coalesced)"
    )
    eng.close()
    holder.close()


# ---- plan-recording overhead (--profile-overhead) -------------------------

OVH_SHARDS = 8
OVH_P50_REPS = 48  # wall p50 of the real query (denominator)
OVH_REPLAY_N = 20000  # total replays of the plan sequence (numerator)
OVH_REPLAY_LOOPS = 8  # numerator = best (min) mean over this many loops


def profile_overhead_bench():
    """--profile-overhead: plan-recording overhead on the
    count_intersect-shaped hot path (docs/observability.md "Query plans
    & cost attribution").

    Estimator design note: a wall-clock A/B (plans on vs off around the
    same api.query) CANNOT resolve this on the bench container — the
    per-dispatch transport jitter is 0.1-3ms (the same reason
    device_p50 exists) and a null test of paired/blocked A/B estimators
    read -1%..+9% when the true delta was ZERO; process_time is
    quantized at ~15ms here.  So the two factors are measured where
    each is measurable: (numerator) the plan layer's per-query host
    cost, by replaying the EXACT record sequence a real profiled
    count_intersect query just produced — begin/attach, the dispatch
    notes with the real decision fields, op/stage/device stamps,
    finish, ring+ledger record — as the best (min) per-replay mean over
    several tight loops (a single loop wobbles 2-3x when a GC pause or
    preemption lands inside it; the min estimates the undisturbed cost,
    slightly optimistic on cache effects, slightly pessimistic on
    branch warmth); (denominator) the wall p50 of the real query with
    plans ON, the shipping config.  Emits
    count_intersect_plans_on_p50, plan_record_us, and
    profile_overhead_pct = plan_record_us / p50 (target <2%;
    bench_guard holds the line once a baseline records it)."""
    progress("importing jax (profile overhead)")
    import jax

    from pilosa_tpu.api import API, QueryRequest
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.ops import bitops
    from pilosa_tpu.parallel import MeshEngine, make_mesh
    from pilosa_tpu.util import plans

    rng = np.random.default_rng(11)
    holder = Holder()
    holder.open()
    idx = holder.create_index("ovh")
    f = idx.create_field("f")
    view = f.view_if_not_exists("standard")
    shards = list(range(OVH_SHARDS))
    for s in shards:
        frag = view.fragment_if_not_exists(s)
        for r in (0, 1):
            frag.load_row_words(r, __rand(rng, bitops.WORDS64))
    for frag in view.fragments.values():
        frag.cache.invalidate()
    progress("overhead build done")

    mesh = make_mesh(len(jax.devices()))
    eng = MeshEngine(holder, mesh)
    eng.result_memo.maxsize = 0  # every rep must dispatch
    api = API(holder=holder, mesh_engine=eng)
    req = QueryRequest("ovh", "Count(Intersect(Row(f=0), Row(f=1)))")
    want = int(api.query(req).results[0])  # warm the compile caches
    assert int(api.query(req).results[0]) == want

    # Denominator: real-query wall p50, plans ON (the shipping config).
    p50, resp = sync_p50(lambda i: api.query(req), reps=OVH_P50_REPS)
    assert int(resp.results[0]) == want

    # Numerator: replay the EXACT record sequence the query above just
    # produced.  Take the recorded plan (the ring keeps it) and drive
    # the same calls the engine/batcher made — note_dispatch with the
    # real decision fields (split as the engine publishes them: the
    # occupancy verdict from _sparse_plan, then the path/bytes fields
    # from the dispatch), note-claim + op stamp, the stage/device
    # stamps, finish, ring + tenant-ledger record.
    real = plans.STORE.find(resp.trace_id)
    assert real is not None, "query plan not recorded (PILOSA_PLANS=0?)"
    op_fields = dict(real.ops[0]) if real.ops else {"op": "Count",
                                                    "path": "direct"}
    occ = {
        k: op_fields.pop(k)
        for k in ("blocks_surviving", "blocks_total", "occ_fraction",
                  "threshold")
        if k in op_fields
    }
    stage_events = list(real._stage_events)
    dur = real.duration or p50
    trace_id = resp.trace_id or "bench"

    def replay():
        p = plans.begin("ovh", req.query)
        with plans.attach(p):
            if occ:
                plans.note_dispatch(**occ)
            plans.note_dispatch(**op_fields)
            note = plans.take_dispatch_note()
            p.note_op(**note)
            for st, s in stage_events:
                p.note_stage(st, s)
            p.finish(dur, trace_id=trace_id)
        plans.record(p)

    for _ in range(OVH_REPLAY_N // 10):  # warm branches/allocator
        replay()
    # Best-of-K loops: a single tight loop still wobbles 2-3x run to
    # run on this container (GC pauses, allocator growth, scheduler
    # preemption land INSIDE one loop and inflate its mean); the
    # minimum over several loops is the standard microbench estimator
    # for the undisturbed cost, and it is what the guarded
    # profile_overhead_pct headline must be stable over.
    loop_n = max(1, OVH_REPLAY_N // OVH_REPLAY_LOOPS)
    best = math.inf
    for _ in range(OVH_REPLAY_LOOPS):
        t0 = time.perf_counter()
        for _ in range(loop_n):
            replay()
        best = min(best, (time.perf_counter() - t0) / loop_n)
    plan_record = best

    overhead_pct = plan_record / p50 * 100.0
    c_cpu = cpu_time(lambda: api.query(req))
    emit("count_intersect_plans_on_p50", p50, c_cpu)
    emit_raw("plan_record_us", plan_record * 1e6, "us", 1.0)
    emit_raw("profile_overhead_pct", overhead_pct, "pct", 1.0)
    progress(
        f"plan-recording overhead: record {plan_record * 1e6:.2f}us / "
        f"query p50 {p50 * 1e6:.1f}us = {overhead_pct:.3f}% (target <2%)"
    )
    eng.close()
    holder.close()


ADV_SHARDS = 4
ADV_WARM_PAIRS = 12  # A,B alternations before scoring (miner + WS learn)
ADV_SCORE_PAIRS = 64  # graded alternations (counter-delta window)
ADV_P50_REPS = 48  # wall p50 of the real query (overhead denominator)
ADV_REPLAY_N = 4000  # total heat-observe replays (overhead numerator)
ADV_REPLAY_LOOPS = 8  # numerator = best (min) mean over this many loops


def advisor_sweep():
    """--advisor-sweep: prefetch-advisor prediction quality plus the
    heat recorder's per-query cost (docs/observability.md "Working-set
    heat & sequences").

    Two dashboard-shaped Counts over DISJOINT row ranges alternate
    A,B,A,B,... through the real api/engine path with the result memo
    off — every round dispatches, so every round stamps the touches the
    heat recorder feeds to the sequence miner and the advisor.  After a
    learning phase, the scored phase counts advised-row hits/misses as
    pilosa_advisor_{hits,misses}_total deltas: the advisor's advice set
    after each A must name exactly B's rows (and vice versa), giving
    the prefetch_advisor_hit_rate headline (bench_guard ABS_FLOOR 0.7).

    heat_overhead_pct reuses the --profile-overhead replay estimator
    (a wall A/B cannot resolve sub-ms per-query costs on this
    container): the numerator is the best (min) tight-loop mean of
    HEAT.observe_plan replayed on the EXACT plan a real query just
    recorded — heat-table update, miner transition, advisor
    grade/learn/advise, the full added path — over the real query's
    wall p50 as denominator (target <2%; bench_guard ABS_CEILING)."""
    progress("importing jax (advisor sweep)")
    import jax

    from pilosa_tpu.api import API, QueryRequest
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.ops import bitops
    from pilosa_tpu.parallel import MeshEngine, make_mesh
    from pilosa_tpu.parallel.advisor import ADVISOR
    from pilosa_tpu.util import plan_miner, plans
    from pilosa_tpu.util.heat import HEAT

    rng = np.random.default_rng(19)
    holder = Holder()
    holder.open()
    idx = holder.create_index("adv")
    f = idx.create_field("f")
    view = f.view_if_not_exists("standard")
    for s in range(ADV_SHARDS):
        frag = view.fragment_if_not_exists(s)
        for r in (0, 1, 8, 9):
            frag.load_row_words(r, __rand(rng, bitops.WORDS64))
    for frag in view.fragments.values():
        frag.cache.invalidate()
    progress("advisor build done")

    mesh = make_mesh(len(jax.devices()))
    eng = MeshEngine(holder, mesh)
    eng.result_memo.maxsize = 0  # every round must dispatch (touches)
    api = API(holder=holder, mesh_engine=eng)
    HEAT.reset()
    plan_miner.MINER.reset()
    ADVISOR.reset()

    req_a = QueryRequest("adv", "Count(Intersect(Row(f=0), Row(f=1)))")
    req_b = QueryRequest("adv", "Count(Intersect(Row(f=8), Row(f=9)))")
    want_a = int(api.query(req_a).results[0])
    want_b = int(api.query(req_b).results[0])

    # Learn: the alternation teaches the miner sig(A)->sig(B)->sig(A)
    # and the advisor both signatures' working sets.
    for _ in range(ADV_WARM_PAIRS):
        assert int(api.query(req_a).results[0]) == want_a
        assert int(api.query(req_b).results[0]) == want_b

    # Score: counter deltas over the graded alternations only (the
    # learning phase's cold-start holds and half-learned sets excluded).
    h0, m0 = ADVISOR.hits, ADVISOR.misses
    for _ in range(ADV_SCORE_PAIRS):
        assert int(api.query(req_a).results[0]) == want_a
        assert int(api.query(req_b).results[0]) == want_b
    hits = ADVISOR.hits - h0
    misses = ADVISOR.misses - m0
    graded = hits + misses
    assert graded > 0, "advisor graded nothing (PILOSA_HEAT=0?)"
    hit_rate = hits / graded
    adv_doc = ADVISOR.to_doc()

    # Heat overhead: replay estimator over the real query's wall p50.
    p50, resp = sync_p50(lambda i: api.query(req_a), reps=ADV_P50_REPS)
    assert int(resp.results[0]) == want_a
    real = plans.STORE.find(resp.trace_id)
    assert real is not None, "query plan not recorded (PILOSA_PLANS=0?)"
    loop_n = max(1, ADV_REPLAY_N // ADV_REPLAY_LOOPS)
    for _ in range(loop_n // 10):  # warm branches/allocator
        HEAT.observe_plan(real)
    best = math.inf
    for _ in range(ADV_REPLAY_LOOPS):
        t0 = time.perf_counter()
        for _ in range(loop_n):
            HEAT.observe_plan(real)
        best = min(best, (time.perf_counter() - t0) / loop_n)
    overhead_pct = best / p50 * 100.0

    emit_raw("prefetch_advisor_hit_rate", hit_rate, "ratio", 1.0)
    emit_raw("heat_observe_us", best * 1e6, "us", 1.0)
    emit_raw("heat_overhead_pct", overhead_pct, "pct", 1.0)
    progress(
        f"advisor: {hits}/{graded} advised rows hit "
        f"(rate {hit_rate:.3f}, target >=0.7; "
        f"{adv_doc['adviceSets']} advice sets over "
        f"{adv_doc['learnedSignatures']} learned signatures); "
        f"heat observe {best * 1e6:.2f}us / query p50 "
        f"{p50 * 1e6:.1f}us = {overhead_pct:.3f}% (target <2%)"
    )
    eng.close()
    holder.close()


HIST_P50_REPS = 48  # wall p50 of the real query (reference series)
HIST_TICK_N = 240  # total sampler ticks timed (numerator)
HIST_TICK_LOOPS = 8  # numerator = best (min) mean over this many loops
HIST_SEED_TICKS = 120  # stored history before the 1h-window read timing
HIST_READ_REPS = 32  # /debug/history 1h-window read p50


def history_overhead_bench():
    """--history-overhead: self-hosted metrics history sampler cost
    (docs/observability.md "Metrics history, SLOs & flight recorder").

    Estimator design note: same constraint as profile_overhead_bench —
    a wall-clock A/B (sampler on vs off around the same api.query)
    cannot resolve a <3% delta on this container, where per-dispatch
    jitter alone is 0.1-3ms.  The sampler's cost model is also simpler
    than an A/B: it is a DUTY CYCLE.  One tick (registry snapshot ->
    diff -> bulk import -> retention) costs a measurable slice of one
    core, once per interval, under the GIL — so the worst-case query
    impact at a 1s interval is tick_seconds / 1s.  The numerator is the
    best (min) per-tick mean over several tight loops of REAL ticks
    (every tick does the full snapshot/diff/import pass against the
    live registry, with query load churning the counters between
    loops); the guarded headline is

        history_sampler_overhead_pct = tick_best / interval * 100

    at the 1s smoke interval (ABS_CEILING 3%; production's 10s default
    is 10x cheaper still).  Also emits history_on_query_p50 (reference:
    query p50 with the sampler ticking on a live background thread at
    1s) and history_query_p50_ms (a 1h-window /debug/history read)."""
    progress("importing jax (history overhead)")
    import threading as _threading

    from pilosa_tpu.api import API, QueryRequest
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.util.history import HistorySampler

    rng = np.random.default_rng(13)
    holder = Holder()
    holder.open()
    idx = holder.create_index("hov")
    f = idx.create_field("f")
    view = f.view_if_not_exists("standard")
    frag = view.fragment_if_not_exists(0)
    from pilosa_tpu.ops import bitops

    for r in (0, 1):
        frag.load_row_words(r, __rand(rng, bitops.WORDS64))
    frag.cache.invalidate()
    api = API(holder=holder)
    req = QueryRequest("hov", "Count(Intersect(Row(f=0), Row(f=1)))")
    want = int(api.query(req).results[0])  # warm caches
    assert int(api.query(req).results[0]) == want
    progress("history overhead build done")

    interval = 1.0
    hist = HistorySampler(api, node="bench", interval=interval)
    # Synthetic clock, one interval per tick: the production cadence is
    # one bucket (= one fresh ring slot) per tick.  Tight-looping on
    # real time would land every tick in the SAME bucket and measure
    # repeated same-column overwrites — a shape the live sampler never
    # produces.
    clock = [time.time()]

    def tick_once():
        clock[0] += interval
        hist.tick(now=clock[0])

    tick_once()  # schema + rate baseline
    for _ in range(8):  # warm the field set / translate cache
        api.query(req)
        tick_once()

    # Numerator: best-of-K mean tick cost under live counter churn.
    loop_n = max(1, HIST_TICK_N // HIST_TICK_LOOPS)
    tick_best = math.inf
    for _ in range(HIST_TICK_LOOPS):
        for _ in range(4):
            api.query(req)  # churn counters so diffs stay realistic
        t0 = time.perf_counter()
        for _ in range(loop_n):
            tick_once()
        tick_best = min(tick_best, (time.perf_counter() - t0) / loop_n)
    overhead_pct = tick_best / interval * 100.0

    # Reference: query p50 with the sampler live on its real cadence.
    stop = _threading.Event()

    def ticker():
        while not stop.wait(interval):
            tick_once()

    t = _threading.Thread(target=ticker, daemon=True)
    t.start()
    try:
        p50_on, resp = sync_p50(lambda i: api.query(req),
                                reps=HIST_P50_REPS)
        assert int(resp.results[0]) == want
    finally:
        stop.set()
        t.join(timeout=2.0)

    # 1h-window /debug/history read: seed a couple minutes of real
    # samples, then time the full-window scan (absent buckets cost the
    # same presence-bit miss a sparse live hour pays).
    for _ in range(HIST_SEED_TICKS):
        tick_once()
    now = clock[0]
    reads = []
    for _ in range(HIST_READ_REPS):
        t0 = time.perf_counter()
        doc = hist.query(
            "pilosa_query_seconds_rate", since=now - 3600.0, until=now
        )
        reads.append(time.perf_counter() - t0)
    assert any(doc["points"].values())
    read_p50 = sorted(reads)[len(reads) // 2]

    c_cpu = cpu_time(lambda: api.query(req))
    emit("history_on_query_p50", p50_on, c_cpu)
    emit_raw("history_tick_us", tick_best * 1e6, "us", 1.0)
    emit_raw("history_sampler_overhead_pct", overhead_pct, "pct", 1.0)
    emit_raw("history_query_p50_ms", read_p50 * 1e3, "ms", 1.0)
    progress(
        f"history sampler: tick {tick_best * 1e6:.0f}us / {interval:.0f}s "
        f"= {overhead_pct:.3f}% duty (target <3%); 1h read p50 "
        f"{read_p50 * 1e3:.2f}ms"
    )
    holder.close()


def force_cpu_host_devices(n):
    """Pin the CPU platform with ``n`` virtual host devices.  Must run
    BEFORE jax initializes a backend (the __main__ pre-import window);
    a mismatched ambient ``xla_force_host_platform_device_count`` is
    REPLACED — a leftover 4-device flag must not silently turn an
    8-device bench into a 4-device one that still emits the 8-device
    headline.  Shared by bench --multichip and
    __graft_entry__.dryrun_multichip (tests/conftest.py keeps its own
    suite-wide copy)."""
    import os
    import re

    opt = f"--xla_force_host_platform_device_count={n}"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" in flags:
        os.environ["XLA_FLAGS"] = re.sub(
            r"--xla_force_host_platform_device_count=\d+", opt, flags
        )
    else:
        os.environ["XLA_FLAGS"] = (flags + " " + opt).strip()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
    except RuntimeError:
        pass  # backend already initialized: the env change is a no-op


# ---- multi-chip shard execution over ICI (--multichip) -------------------

MC_ROWS = 8  # rows 10..17 -> four disjoint intersect pairs per index
MC_CPU_BASE_SHARDS = 64  # CPU-baseline sample cap (scaled to full S)


def multichip_bench(n_devices=None, shards_per_device=None):
    """Weak-scaling bench of the one-mesh-one-cluster data plane
    (docs/mesh.md): per device-count d in {1, 2, 4, ..., N} build a
    d-device shard mesh whose dataset SCALES with the mesh
    (``shards_per_device`` shards each), and time the fused
    Count(Intersect) dispatch whose psum over SHARD_AXIS is the whole
    per-query shard reduce — no HTTP fan-out, no per-shard host loop.

    Emits (JSONL, same stream format as the main bench):
      mesh_devices / mesh_shards_per_device       mesh shape
      mesh_psum_us                                the reduce-only cost: a
                                                  shard_map psum across the
                                                  full N-device mesh
      count_intersect_p50_d{d}                    the 1->N scaling curve
      mesh_weak_scaling_eff                       t_1/t_N (1.0 = perfect:
                                                  N devices serve N x the
                                                  shards at flat latency)
      count_intersect_8B_cols_p50                 THE MULTICHIP HEADLINE:
                                                  the N-device point; the
                                                  record carries the true
                                                  ``cols`` and is flagged
                                                  ``scaled`` when below the
                                                  8-device x 960-shard
                                                  (~8.05B-col) full shape

    On TPU silicon (bench.py --multichip --multichip-platform native)
    the full shape is 960 shards/device — 8 devices is ~8.05B columns.
    On this CPU container the lane runs on forced host devices with a
    reduced shards_per_device so the MULTICHIP_r*.json trajectory still
    records a real measured headline every round."""
    import jax

    from pilosa_tpu import pql
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.ops import bitops
    from pilosa_tpu.parallel import MeshEngine, make_mesh
    from pilosa_tpu.parallel.mesh import SHARD_AXIS, pad_shards, put_global

    avail = len(jax.devices())
    n = n_devices or avail
    if n > avail:
        progress(f"requested {n} devices, only {avail}: trimming")
        n = avail
    on_tpu = jax.default_backend() == "tpu"
    spd = shards_per_device or (960 if on_tpu else 24)
    full_shape = on_tpu and n >= 8 and spd >= 960
    progress(
        f"multichip: {n} devices ({jax.default_backend()}), "
        f"{spd} shards/device"
    )

    # One index per device count so each mesh's canonical shard axis is
    # exactly its own d*spd shards (weak scaling: per-device load flat).
    curve = []
    d = 1
    while d < n:
        curve.append(d)
        d *= 2
    curve.append(n)

    rng = np.random.default_rng(9)
    holder = Holder()
    holder.open()
    host_rows = {}  # CPU-baseline sample: row -> list of word arrays
    for d in curve:
        idx = holder.create_index(f"mc_d{d}")
        f = idx.create_field("f")
        view = f.view_if_not_exists("standard")
        for s in range(d * spd):
            for r in range(10, 10 + MC_ROWS):
                words = __rand(rng, bitops.WORDS64)
                view.fragment_if_not_exists(s).load_row_words(r, words)
                if d == n and r in (10, 11) and s < MC_CPU_BASE_SHARDS:
                    host_rows.setdefault(r, []).append(words)
        for frag in view.fragments.values():
            frag.cache.invalidate()
    progress("multichip build done")

    # CPU baseline: numpy AND+popcount over a sampled shard prefix,
    # scaled to the full shard count (the conservative denominator of
    # the main bench, sampled so the CPU lane stays fast).
    n_shards_full = n * spd
    a = np.concatenate(host_rows[10])
    b = np.concatenate(host_rows[11])
    sample = min(n_shards_full, MC_CPU_BASE_SHARDS)

    def cpu_ns():
        return int(np.sum(np.bitwise_count(a & b)))

    cpu_s = cpu_time(cpu_ns) * (n_shards_full / sample)

    results = {}
    for d in curve:
        mesh = make_mesh(d)
        eng = MeshEngine(holder, mesh, max_resident_bytes=12 << 30)
        # The versioned result memo would serve repeated pairs with zero
        # device work and turn the 'p50' into memo-lookup time; this
        # lane measures the DISPATCH, so the memo is disabled (the main
        # bench's 'every rep a different pair' discipline, with the
        # pair pool recycled across reps).
        eng.result_memo.maxsize = 0
        index = f"mc_d{d}"
        shards = list(range(d * spd))
        calls = [
            pql.parse(f"Intersect(Row(f={10 + 2 * k}), Row(f={11 + 2 * k}))")
            .calls[0]
            for k in range(MC_ROWS // 2)
        ]
        jax.device_get(eng.count_async(index, calls[0], shards))
        t_d, _ = device_p50(
            lambda i: eng.count_async(index, calls[i % len(calls)], shards),
            reps=12,
        )
        results[d] = t_d
        # The CPU denominator covers the FULL n-device dataset; a
        # d-device point covers d/n of it, so scale the baseline to the
        # same shard count or the curve would claim n/d-inflated ratios.
        cpu_d = cpu_s * (d / n)
        emit_raw(f"count_intersect_p50_d{d}", t_d * 1e6, "us", cpu_d / t_d)
        progress(f"  d={d}: {t_d * 1e6:.1f} us over {len(shards)} shards")
        if d == n:
            # The reduce alone: a shard_map psum across the full mesh —
            # the ICI hop that replaced the reference's HTTP broadcast.
            from jax import shard_map
            from jax.sharding import PartitionSpec as P

            padded = pad_shards(len(shards), mesh)
            part = put_global(
                mesh, np.ones((padded, 1), np.int32), P(SHARD_AXIS)
            )
            psum_fn = jax.jit(
                shard_map(
                    lambda x: jax.lax.psum(x.sum(), SHARD_AXIS),
                    mesh=mesh,
                    in_specs=P(SHARD_AXIS),
                    out_specs=P(),
                )
            )
            jax.device_get(psum_fn(part))
            t_psum, _ = device_p50(lambda i: psum_fn(part), reps=12)
            emit_raw("mesh_psum_us", t_psum * 1e6, "us", 1.0)
            emit_raw("mesh_devices", d, "devices", 1.0)
            emit_raw(
                "mesh_shards_per_device", padded // d, "shards", 1.0
            )
        eng.close()

    t1, tn = results[curve[0]], results[n]
    # Weak scaling: N devices hold N x the data; perfect ICI scaling
    # keeps latency flat, so efficiency is t_1/t_N.
    emit_raw("mesh_weak_scaling_eff", min(t1 / tn, 1.0), "ratio", 1.0)
    cols = n_shards_full << 20
    rec = {
        "metric": "count_intersect_8B_cols_p50",
        "value": round(results[n] * 1e6, 1),
        "unit": "us",
        "vs_baseline": round(cpu_s / results[n], 2),
        "cols": cols,
        "n_devices": n,
    }
    if not full_shape:
        rec["scaled"] = True  # below the 8-dev x 960-shard full shape
    print(json.dumps(rec), flush=True)
    progress(
        f"headline: {results[n] * 1e6:.1f} us over {cols / 1e9:.2f}B cols "
        f"on {n} devices (weak-scaling eff {min(t1 / tn, 1.0):.2f})"
    )
    holder.close()


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--depth-sweep",
        action="store_true",
        help="also sweep the batch pipeline's in-flight depth (1/2/4/8) "
        "and emit http_count_qps_depthN lines (the QPS-vs-depth curve)",
    )
    ap.add_argument(
        "--density-sweep",
        action="store_true",
        help="run the sparsity density sweep + result-memo shape ONLY "
        "(standalone ~64-shard build; emits sparse/dense *_p50, "
        "bytes_skipped, speedup, and memo-hit lines in the same JSONL "
        "format — docs/sparsity.md)",
    )
    ap.add_argument(
        "--residency-sweep",
        action="store_true",
        help="run the tiered-residency sweep ONLY: an index ~4x the "
        "configured device budget (no single stack fits), measuring the "
        "cold host-fallback p50, the warm partially-resident dashboard "
        "p50 (guarded oversubscribed_4x_count_p50_ms), residency_hit_rate, "
        "and promotion_overlap_mbits_s; then deep 8x/16x phases on the "
        "packed 2KiB-block pool (residency_hit_rate_8x > 0.9, "
        "oversubscribed_8x_warm_vs_resident <= ~1.2x) and an equal-budget "
        "advisor on/off A/B (residency_advisor_ab_speedup > 1) — all with "
        "bit-exact differential asserts across host / partial / "
        "fully-resident paths and zero OOMs by construction "
        "(docs/residency.md)",
    )
    ap.add_argument(
        "--repair-sweep",
        action="store_true",
        help="run the repair-on-write sweep ONLY: a repeated dashboard "
        "(Count/TopN/GroupBy/Sum) under interleaved randomized writes, "
        "every round's served results asserted bit-exact against a "
        "repair-suspended recompute (including forced stale-base "
        "fallbacks); emits result_memo_hit_rate_under_write_load and "
        "dashboard_p50_under_ingest_vs_idle (docs/incremental.md)",
    )
    ap.add_argument(
        "--ingest-sweep",
        action="store_true",
        help="run the ingest throughput sweep ONLY (sustained bulk-import "
        "Mbits/s at several batch sizes vs the retained pre-PR per-row "
        "path, vectorized-decode micro, write->query freshness p50; "
        "headline JSONL metric ingest_mbits_s — docs/ingest.md)",
    )
    ap.add_argument(
        "--streaming-sweep",
        action="store_true",
        help="run the streaming write+read sweep ONLY: the id-pairs "
        "old-vs-new headline (ingest_bits_mbits_s, arrays vs the "
        "retained rowloop oracle), then continuous imports through a "
        "live engine under a concurrent query load, emitting "
        "ingest_streaming_mbits_s, ingest_freshness_p50_ms, and "
        "query_p50_under_ingest_ms vs query_p50_idle_ms "
        "(docs/ingest.md)",
    )
    ap.add_argument(
        "--chaos-sweep",
        action="store_true",
        help="run the serving-through-failure sweep ONLY: a real "
        "3-process gossip cluster (replicas=2, ack=logged) measuring "
        "replica_read_qps_gain (any-mode vs primary-mode Count QPS), "
        "availability_under_failure_pct (fraction of queries answered "
        "while a replica fails mid-load), and "
        "destructive_write_availability_pct (Clears acked under "
        "single-owner failure via hinted handoff) — all bench_guard "
        "AUTO_REQUIREd once baselined (docs/durability.md)",
    )
    ap.add_argument(
        "--fault",
        choices=("kill", "partition"),
        default="kill",
        help="failure mode for --chaos-sweep: 'kill' SIGKILLs the "
        "replica (the PR 11 drill); 'partition' injects a "
        "deterministic network partition through POST /debug/faults "
        "(net/faults.py), then HEALS it and additionally emits "
        "partition_heal_seconds (heal -> NORMAL + hint queues drained "
        "+ bit-exact convergence, zero reverted clears)",
    )
    ap.add_argument(
        "--dashboard-sweep",
        action="store_true",
        help="run the whole-program fusion sweep ONLY: dashboard-shaped "
        "drains (1 segment filter x N in {2,4,8,10} widgets of mixed "
        "Count/Sum/Min/Max/TopN/GroupBy) as ONE fused device program vs "
        "the sequential per-query path, emitting dashboard_fused_qps / "
        "dashboard_p50_ms / dashboard_fused_speedup / "
        "fused_masks_saved_total plus the PR 18 lanes — topn_device_p50 "
        "/ topn_e2e_p50 / topn_device_speedup (device slab vs host "
        "rank/merge) and dashboard_crossindex_p50_ms / "
        "dashboard_crossindex_fused_speedup (one program spanning two "
        "indexes) — and asserting via plan records that each shared "
        "mask evaluated once (docs/fusion.md)",
    )
    ap.add_argument(
        "--conn-sweep",
        action="store_true",
        help="also sweep client connection counts (1/4/16/64, open-loop "
        "pipelined senders) and emit http_count_qps_c{N} lines plus the "
        "batcher's per-level occupancy — the cross-connection coalescing "
        "curve (docs/serving.md)",
    )
    ap.add_argument(
        "--workers",
        action="store_true",
        help="with --conn-sweep: also sweep shared-nothing worker "
        "PROCESSES (0/1/2/4/8 behind SO_REUSEPORT, decoded frames over "
        "AF_UNIX into this process's batcher) at a fixed connection "
        "count, emitting http_count_qps_w{N} plus the fused-batch "
        "occupancy and cross-worker fused-batch counter per level — the "
        "GIL-wall curve (docs/serving.md \"Process mode\")",
    )
    ap.add_argument(
        "--multichip",
        nargs="?",
        const=8,
        default=None,
        type=int,
        metavar="N",
        help="run the multi-chip shard-execution bench ONLY: an N-device "
        "(default 8) shard mesh with the dataset scaled per device, "
        "emitting the count_intersect_8B_cols_p50 headline, mesh_psum_us, "
        "shards-per-device occupancy, and the 1->N weak-scaling curve "
        "(docs/mesh.md; MULTICHIP_r*.json trajectory)",
    )
    ap.add_argument(
        "--multichip-platform",
        choices=("cpu", "native"),
        default="cpu",
        help="'cpu' (default) forces N virtual host devices via XLA_FLAGS "
        "before jax loads — the reproducible CI lane; 'native' uses the "
        "runtime's real devices (a TPU pod slice)",
    )
    ap.add_argument(
        "--multichip-shards-per-device",
        type=int,
        default=None,
        metavar="S",
        help="shards owned per device (default: 960 on TPU — 8 devices "
        "is ~8.05B columns — else 24 for the CPU lane)",
    )
    ap.add_argument(
        "--profile-overhead",
        action="store_true",
        help="run the plan-recording overhead micro-mode ONLY: replays "
        "the exact plan-record sequence a real count_intersect-shaped "
        "Count produced in a tight loop over the query's wall p50, "
        "emitting count_intersect_plans_on_p50, plan_record_us, and "
        "profile_overhead_pct (target <2%%; guarded by bench_guard once "
        "baselined — docs/observability.md)",
    )
    ap.add_argument(
        "--advisor-sweep",
        action="store_true",
        help="run the prefetch-advisor sweep ONLY: two dashboard-shaped "
        "Counts over disjoint row ranges alternate through the real "
        "api/engine path (result memo off) so the heat recorder feeds "
        "the sequence miner and the advisor; emits "
        "prefetch_advisor_hit_rate (advised-row hits over the scored "
        "alternations, target >=0.7) and heat_overhead_pct (replayed "
        "HEAT.observe_plan cost over the query wall p50, target <2%%) "
        "(docs/observability.md \"Working-set heat & sequences\")",
    )
    ap.add_argument(
        "--history-overhead",
        action="store_true",
        help="run the metrics-history sampler overhead micro-mode ONLY: "
        "times real sampler ticks under live counter churn and emits "
        "history_sampler_overhead_pct as a 1s-interval duty cycle "
        "(target <3%%; guarded by bench_guard once baselined) plus "
        "history_query_p50_ms for a 1h-window /debug/history read "
        "(docs/observability.md)",
    )
    ap.add_argument(
        "--scrape",
        action="store_true",
        help="append the post-run /metrics device gauges (resident "
        "bytes, compile totals, eviction counters) to the JSONL output "
        "(diffable with scripts/bench_guard.py --format prom or as "
        "JSONL)",
    )
    args = ap.parse_args()
    if args.multichip is not None and args.multichip_platform == "cpu":
        force_cpu_host_devices(args.multichip)
    from pilosa_tpu import compile_cache

    progress(f"compile cache: {compile_cache.configure()}")
    if args.multichip is not None:
        multichip_bench(
            args.multichip,
            shards_per_device=args.multichip_shards_per_device,
        )
    elif args.profile_overhead:
        profile_overhead_bench()
    elif args.advisor_sweep:
        advisor_sweep()
    elif args.history_overhead:
        history_overhead_bench()
    elif args.repair_sweep:
        repair_sweep()
    elif args.ingest_sweep:
        ingest_sweep()
    elif args.streaming_sweep:
        streaming_sweep()
    elif args.chaos_sweep:
        chaos_sweep(fault=args.fault)
    elif args.residency_sweep:
        residency_sweep()
    elif args.density_sweep:
        density_sweep()
    elif args.dashboard_sweep:
        dashboard_sweep()
    else:
        main(
            depth_sweep=args.depth_sweep,
            conn_sweep=args.conn_sweep,
            scrape=args.scrape,
            workers_sweep=args.workers,
        )

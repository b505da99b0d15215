"""Stats clients: counters/gauges/timings with tag scoping.

Mirror of the reference's StatsClient interface (stats/stats.go:31-66) with
nop / expvar-style in-memory / multi backends (stats/stats.go:69-283).  A
statsd backend can be registered by the server layer when a host agent is
configured (statsd/statsd.go) — network emission is optional and off by
default.
"""

from __future__ import annotations

import bisect
import re
import threading
import time
from typing import Dict, List, Optional, Tuple


# Fixed log-spaced latency buckets (seconds), 100 us .. 60 s: wide enough
# for the O(1) cardinality lane at the bottom and a wedged collective at
# the top.  Fixed buckets (not reservoirs) keep observe() O(log B) with
# bounded memory — the always-on requirement.
DEFAULT_BUCKETS: Tuple[float, ...] = (
    0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
    0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
)


class Histogram:
    """Fixed-bucket latency histogram with Prometheus-style cumulative
    export and linear-interpolation quantile estimation.  Thread-safe;
    observe() is a bisect + one locked increment.

    Exemplars (OpenMetrics): ``observe(v, exemplar=trace_id)`` keeps the
    most recent (trace id, value, wall time) PER BUCKET — bounded memory
    (one slot per bucket, allocated lazily on the first exemplar), and
    exactly what links a p99 bucket spike in Grafana to the concrete
    plan at /debug/plans."""

    __slots__ = ("buckets", "_counts", "sum", "count", "_lock", "_exemplars")

    def __init__(self, buckets: Tuple[float, ...] = DEFAULT_BUCKETS):
        self.buckets = tuple(buckets)
        self._counts = [0] * (len(self.buckets) + 1)  # +1: the +Inf bucket
        self.sum = 0.0
        self.count = 0
        self._lock = threading.Lock()
        self._exemplars = None  # lazy: [ (trace_id, value, wall_ts) | None ]

    def observe(self, value: float, exemplar: Optional[str] = None):
        i = bisect.bisect_left(self.buckets, value)
        with self._lock:
            self._counts[i] += 1
            self.sum += value
            self.count += 1
            if exemplar:
                ex = self._exemplars
                if ex is None:
                    ex = self._exemplars = [None] * (len(self.buckets) + 1)
                ex[i] = (exemplar, value, time.time())

    def exemplars(self) -> Optional[list]:
        """A consistent copy of the per-bucket exemplar slots (None when
        no exemplar was ever attached)."""
        with self._lock:
            return None if self._exemplars is None else list(self._exemplars)

    def counts(self) -> List[int]:
        with self._lock:
            return list(self._counts)

    def export(self) -> Tuple[List[int], float, int]:
        """One consistent (counts, sum, count) triple taken under the
        lock — the Prometheus exposition must not mix bucket counts from
        one instant with a _count from another (le="+Inf" == _count is
        an invariant consumers validate)."""
        with self._lock:
            return list(self._counts), self.sum, self.count

    def cumulative(self) -> List[int]:
        """Cumulative per-bucket counts (Prometheus ``le`` semantics):
        entry i counts observations <= buckets[i]; the final entry is
        the total (le="+Inf")."""
        out = []
        total = 0
        for c in self.counts():
            total += c
            out.append(total)
        return out

    def _quantile_of(self, counts: List[int], total: int, q: float) -> float:
        if total == 0:
            return 0.0
        rank = q * total
        cum = 0.0
        for i, c in enumerate(counts):
            cum += c
            if cum >= rank and c > 0:
                hi = self.buckets[i] if i < len(self.buckets) else self.buckets[-1]
                lo = self.buckets[i - 1] if i > 0 else 0.0
                frac = (rank - (cum - c)) / c
                return lo + (hi - lo) * min(1.0, max(0.0, frac))
        return self.buckets[-1]

    def quantile(self, q: float) -> float:
        """Estimated q-quantile (0..1) by linear interpolation inside
        the containing bucket — the standard Prometheus histogram_quantile
        estimate.  Returns 0.0 on an empty histogram; observations in
        the +Inf bucket clamp to the top finite bound."""
        counts, _, total = self.export()
        return self._quantile_of(counts, total, q)

    def snapshot(self) -> dict:
        counts, total_sum, count = self.export()  # one consistent view
        return {
            "count": count,
            "sumSeconds": round(total_sum, 6),
            "meanSeconds": round(total_sum / count, 6) if count else 0.0,
            "p50": round(self._quantile_of(counts, count, 0.50), 6),
            "p95": round(self._quantile_of(counts, count, 0.95), 6),
            "p99": round(self._quantile_of(counts, count, 0.99), 6),
        }


class Counter:
    """One monotonic counter series with a cached handle: ``inc()`` is a
    single locked add on the series' OWN lock, so hot paths (engine
    cache probes, per-dispatch byte accounting) resolve the series once
    and never touch the global registry lock again."""

    __slots__ = ("value", "_lock")

    def __init__(self):
        self.value = 0.0
        self._lock = threading.Lock()

    def inc(self, value: float = 1.0):
        with self._lock:
            self.value += value

    def get(self) -> float:
        with self._lock:
            return self.value


_NAME_RE = re.compile(r"[^a-zA-Z0-9_:]")


def _prom_name(name: str) -> str:
    name = _NAME_RE.sub("_", name)
    if name and name[0].isdigit():
        name = "_" + name
    return name


def _prom_float(v: float) -> str:
    """Prometheus number formatting: shortest round-trippable text."""
    return f"{v:.10g}"


class MetricsRegistry:
    """Name + labels -> histogram/counter/gauge, exported as Prometheus
    text (the /metrics surface) and as a JSON snapshot (merged into
    /debug/vars).  Label sets are sorted tuples so label order never
    splits a series."""

    def __init__(self):
        self._lock = threading.Lock()
        # name -> {sorted-label-tuple: Histogram}
        self._hists: Dict[str, Dict[tuple, Histogram]] = {}
        self._counters: Dict[str, Dict[tuple, Counter]] = {}
        self._gauges: Dict[str, Dict[tuple, float]] = {}
        self._help: Dict[str, str] = {}

    @staticmethod
    def _labelkey(labels: dict) -> tuple:
        return tuple(sorted(labels.items()))

    def histogram(self, name: str, help: str = "", **labels) -> Histogram:
        """Get-or-create the histogram series (registering it makes the
        series visible at /metrics even before the first observation)."""
        key = self._labelkey(labels)
        with self._lock:
            if help and name not in self._help:
                self._help[name] = help
            series = self._hists.setdefault(name, {})
            h = series.get(key)
            if h is None:
                h = series[key] = Histogram()
            return h

    def observe(self, name: str, seconds: float, **labels):
        self.histogram(name, **labels).observe(seconds)

    def counter(self, name: str, help: str = "", **labels) -> Counter:
        """Get-or-create the counter series handle (registering it makes
        the series visible at /metrics with value 0 before the first
        increment).  Resolve ONCE per hot path and call ``inc()`` on the
        handle — that pays only the per-series lock, never this
        registry lock."""
        key = self._labelkey(labels)
        with self._lock:
            if help and name not in self._help:
                self._help[name] = help
            series = self._counters.setdefault(name, {})
            c = series.get(key)
            if c is None:
                c = series[key] = Counter()
            return c

    def inc(self, name: str, value: float = 1.0, **labels):
        self.counter(name, **labels).inc(value)

    def set_gauge(self, name: str, value: float, **labels):
        key = self._labelkey(labels)
        with self._lock:
            self._gauges.setdefault(name, {})[key] = value

    def get_histogram(self, name: str, **labels) -> Optional[Histogram]:
        with self._lock:
            return self._hists.get(name, {}).get(self._labelkey(labels))

    @staticmethod
    def _fmt_labels(key: tuple, extra: str = "") -> str:
        def esc(v) -> str:
            return str(v).replace("\\", "\\\\").replace('"', '\\"')

        parts = [f'{_prom_name(k)}="{esc(v)}"' for k, v in key]
        if extra:
            parts.append(extra)
        return "{" + ",".join(parts) + "}" if parts else ""

    def get_gauge(self, name: str, **labels) -> Optional[float]:
        with self._lock:
            return self._gauges.get(name, {}).get(self._labelkey(labels))

    def prometheus_text(self, openmetrics: bool = False) -> str:
        """The whole registry in Prometheus text exposition format.

        ``openmetrics=True`` is the exemplar escape hatch: ``_bucket``
        samples carry their most recent exemplar in OpenMetrics syntax
        (``# {trace_id="..."} value timestamp``) and the exposition ends
        with ``# EOF``.  Classic Prometheus text (the default) stays
        exemplar-free — exemplars are only legal in the OpenMetrics
        format, and classic-format consumers reject the suffix."""
        with self._lock:
            hists = {n: dict(s) for n, s in self._hists.items()}
            counters = {n: dict(s) for n, s in self._counters.items()}
            gauges = {n: dict(s) for n, s in self._gauges.items()}
            helps = dict(self._help)
        lines: List[str] = []
        for name in sorted(hists):
            pname = _prom_name(name)
            lines.append(f"# HELP {pname} {helps.get(name, name)}")
            lines.append(f"# TYPE {pname} histogram")
            for key in sorted(hists[name]):
                h = hists[name][key]
                counts, h_sum, h_count = h.export()  # one consistent view
                exemplars = h.exemplars() if openmetrics else None
                cum, running = [], 0
                for c in counts:
                    running += c
                    cum.append(running)

                def ex_suffix(i: int) -> str:
                    if exemplars is None or exemplars[i] is None:
                        return ""
                    tid, val, ts = exemplars[i]
                    esc = str(tid).replace("\\", "\\\\").replace('"', '\\"')
                    return (
                        f' # {{trace_id="{esc}"}} '
                        f"{_prom_float(val)} {_prom_float(ts)}"
                    )

                for i, bound in enumerate(h.buckets):
                    le = self._fmt_labels(key, f'le="{_prom_float(bound)}"')
                    lines.append(f"{pname}_bucket{le} {cum[i]}{ex_suffix(i)}")
                le = self._fmt_labels(key, 'le="+Inf"')
                lines.append(
                    f"{pname}_bucket{le} {cum[-1]}{ex_suffix(len(h.buckets))}"
                )
                lbl = self._fmt_labels(key)
                lines.append(f"{pname}_sum{lbl} {_prom_float(h_sum)}")
                lines.append(f"{pname}_count{lbl} {h_count}")
        for name in sorted(counters):
            pname = _prom_name(name)
            # OpenMetrics counter families exclude the type suffix in
            # HELP/TYPE and require the ``_total`` suffix on samples;
            # classic exposition uses the sample name throughout.  Our
            # counters are all registered with a ``_total`` name, so the
            # sample lines are identical in both formats.
            fam = pname
            if openmetrics and fam.endswith("_total"):
                fam = fam[: -len("_total")]
            lines.append(f"# HELP {fam} {helps.get(name, name)}")
            lines.append(f"# TYPE {fam} counter")
            for key in sorted(counters[name]):
                lbl = self._fmt_labels(key)
                lines.append(
                    f"{pname}{lbl} {_prom_float(counters[name][key].get())}"
                )
        for name in sorted(gauges):
            pname = _prom_name(name)
            lines.append(f"# HELP {pname} {helps.get(name, name)}")
            lines.append(f"# TYPE {pname} gauge")
            for key in sorted(gauges[name]):
                lbl = self._fmt_labels(key)
                lines.append(f"{pname}{lbl} {_prom_float(gauges[name][key])}")
        if openmetrics:
            lines.append("# EOF")
        return "\n".join(lines) + "\n"

    def snapshot(self) -> dict:
        """JSON view (histograms as count/sum/quantiles) for /debug/vars."""
        with self._lock:
            hists = {n: dict(s) for n, s in self._hists.items()}
            counters = {n: dict(s) for n, s in self._counters.items()}
            gauges = {n: dict(s) for n, s in self._gauges.items()}

        def label_str(key: tuple) -> str:
            return ",".join(f"{k}={v}" for k, v in key) or "_"

        return {
            "histograms": {
                n: {label_str(k): h.snapshot() for k, h in s.items()}
                for n, s in hists.items()
            },
            "counters": {
                n: {label_str(k): c.get() for k, c in s.items()}
                for n, s in counters.items()
            },
            "gauges": {
                n: {label_str(k): v for k, v in s.items()}
                for n, s in gauges.items()
            },
        }

    def collect_rates(self, prev, now: Optional[float] = None,
                      snapshot: Optional[dict] = None):
        """Counter snapshot -> per-second rates since ``prev``.

        ``prev`` is the opaque state returned by the previous call (or
        ``None`` on the first call, which yields no rates — a rate needs
        two samples).  Returns ``(rates, state)`` where ``rates`` maps
        ``family -> {label_str: per_second}`` and ``state`` must be fed
        back next call.  Shared by the history sampler and /debug/vars.
        Monotonic-reset safe via :func:`diff_rates`.
        """
        if now is None:
            now = time.time()
        snap = snapshot if snapshot is not None else self.snapshot()
        counters = snap.get("counters", {})
        state = {"ts": now, "counters": counters}
        if not prev or not prev.get("counters"):
            return {}, state
        dt = now - float(prev.get("ts", now))
        rates = diff_rates(prev["counters"], counters, dt)
        return rates, state


# The process-wide metrics registry: always-on, exported at GET /metrics
# and merged into /debug/vars.  Series names:
#   pilosa_query_seconds{path=...}          whole-query latency
#   pilosa_query_op_seconds{op=...}         per-PQL-op latency
#   pilosa_pipeline_stage_seconds{stage=...} batch-pipeline stage latency
#   pilosa_fragment_op_seconds{op=...}      fragment-level op latency
REGISTRY = MetricsRegistry()

METRIC_QUERY = "pilosa_query_seconds"
METRIC_QUERY_OP = "pilosa_query_op_seconds"
METRIC_PIPELINE_STAGE = "pilosa_pipeline_stage_seconds"
#   pilosa_pipeline_accum_close_total{reason}  how each accumulation window
#       of the batcher ended: "quiet" (no arrival for the burst's quiet
#       interval), "full" (max_batch queued), "deadline" (arrivals never
#       went quiet for ACCUM_WINDOW), "idle_lone" (one query, idle pipe:
#       no window at all)
METRIC_PIPELINE_ACCUM_CLOSE = "pilosa_pipeline_accum_close_total"
# The stage clock (util/tracing.py stage/waited): one series per (path,
# stage) from socket to socket; the four legacy deferred-path stages also
# keep feeding METRIC_PIPELINE_STAGE with the values they always had.
#   pilosa_query_stage_seconds{path,stage}  per-stage host time of a query
#   pilosa_http_request_seconds             first byte in -> last byte out
METRIC_QUERY_STAGE = "pilosa_query_stage_seconds"
METRIC_HTTP_REQUEST = "pilosa_http_request_seconds"
# One drain record per device dispatch (engine._note_drain):
#   pilosa_engine_drains_total{op,path}                 programs dispatched
#   pilosa_engine_drain_slots_total{op,path}            slots compiled for (tier)
#   pilosa_engine_drain_requests_total{op,path}         requests answered (live)
#   pilosa_engine_drain_evaluated_slots_total{op,path}  slots the device runs:
#       the drain's unique entries on the batched Count program (slots past
#       them are skipped on the device), the slots compiled for elsewhere
#   pilosa_engine_drain_plane_bytes_total{op,path,counted}
#       counted="per_request": sum over the live requests of the distinct
#       row-planes each names; "per_drain": the distinct row-planes of the
#       whole drain (what a program reading each plane once would read);
#       both x shards x 128 KiB
#   pilosa_engine_device_inflight_seconds_total  union of [jitted call
#       returned, its device_get returned] over all query dispatches
#   pilosa_uptime_seconds                        monotonic since boot
METRIC_ENGINE_DRAINS = "pilosa_engine_drains_total"
METRIC_ENGINE_DRAIN_SLOTS = "pilosa_engine_drain_slots_total"
METRIC_ENGINE_DRAIN_REQUESTS = "pilosa_engine_drain_requests_total"
METRIC_ENGINE_DRAIN_EVALUATED = "pilosa_engine_drain_evaluated_slots_total"
METRIC_ENGINE_DRAIN_PLANE_BYTES = "pilosa_engine_drain_plane_bytes_total"
#   pilosa_engine_group_combos_total             GroupBy combinations the
#       device evaluated (the count tensor's size, every dispatch)
METRIC_ENGINE_GROUP_COMBOS = "pilosa_engine_group_combos_total"
#   pilosa_engine_group_sum_passes_total         popcount passes of aggregated
#       GroupBys (aggregate=Sum(field=v)): combinations x (v's depth + 2),
#       every dispatch
METRIC_ENGINE_GROUP_SUM_PASSES = "pilosa_engine_group_sum_passes_total"
#   pilosa_engine_group_prefix_steps_total{state}  prefix steps of the
#       GroupBy program's Pallas body, a (tile, prefix) each, read back
#       beside the tensor: state="skipped" where a row of the prefix has
#       no bit under the filter in the tile, state="scored" the rest;
#       the XLA body tests nothing and counts nothing
METRIC_ENGINE_GROUP_PREFIX_STEPS = "pilosa_engine_group_prefix_steps_total"
GROUP_PREFIX_STATES = ("skipped", "scored")
#   pilosa_executor_group_results_total{form}    GroupBy results by the form
#       they left the executor in: form="columns" where the device path
#       handed out a GroupColumns, form="objects" where one was turned into
#       GroupCount objects (once a result) or the host iterator answered
METRIC_EXECUTOR_GROUP_RESULTS = "pilosa_executor_group_results_total"
GROUP_RESULT_FORMS = ("columns", "objects")
METRIC_ENGINE_DEVICE_INFLIGHT = "pilosa_engine_device_inflight_seconds_total"
METRIC_UPTIME = "pilosa_uptime_seconds"
#   pilosa_http_occupied_seconds_total   seconds in which the server held a
#       query request at all: the union of [first byte in, last byte out]
#       over all requests (util/tracing.OCCUPIED); over the uptime it stands
#       above the in-flight share, and 100 % less it is the client's
#       turnaround and the socket on the server's clock
#   pilosa_gc_pause_seconds{generation}  pauses of the Python collector
#       (util/tracing.GC: a gc.callbacks hook for as long as a server serves)
METRIC_HTTP_OCCUPIED = "pilosa_http_occupied_seconds_total"
METRIC_GC_PAUSE = "pilosa_gc_pause_seconds"
GC_GENERATIONS = (0, 1, 2)
METRIC_FRAGMENT_OP = "pilosa_fragment_op_seconds"
#   pilosa_engine_cache_hits_total{cache=...}   engine cache hits
#   pilosa_engine_cache_misses_total{cache=...} engine cache misses
#   pilosa_device_bytes_skipped_total           HBM bytes the sparse path skipped
METRIC_ENGINE_CACHE_HITS = "pilosa_engine_cache_hits_total"
METRIC_ENGINE_CACHE_MISSES = "pilosa_engine_cache_misses_total"
METRIC_DEVICE_BYTES_SKIPPED = "pilosa_device_bytes_skipped_total"
# -- whole-program fusion (docs/fusion.md) ----------------------------------
#   pilosa_engine_fused_program_programs_total   fused heterogeneous drains
#                                                dispatched as ONE program
#   pilosa_engine_fused_program_queries_total    queries that rode them
#   pilosa_engine_fused_program_masks_evaluated_total  distinct Row subtrees
#                                                materialized (mask slots)
#   pilosa_engine_fused_program_masks_referenced_total subtree references the
#                                                drain asked for; the gap to
#                                                masks_evaluated is the
#                                                evaluations fusion saved
METRIC_ENGINE_FUSED_PROGRAMS = "pilosa_engine_fused_program_programs_total"
METRIC_ENGINE_FUSED_QUERIES = "pilosa_engine_fused_program_queries_total"
METRIC_ENGINE_FUSED_MASKS_EVAL = (
    "pilosa_engine_fused_program_masks_evaluated_total"
)
METRIC_ENGINE_FUSED_MASKS_REF = (
    "pilosa_engine_fused_program_masks_referenced_total"
)
#   pilosa_engine_fused_program_edges_total{kind=}  per-kind edges that rode
#                                                fused programs (count, topn,
#                                                topnf device trim, group, …)
METRIC_ENGINE_FUSED_EDGES = "pilosa_engine_fused_program_edges_total"
# -- cluster & device observability (docs/observability.md) -----------------
#   pilosa_engine_resident_bytes            gauge: HBM held by resident stacks
#   pilosa_engine_evicted_bytes             gauge: evicted-but-still-live
#                                           device buffers (weakref backlog)
#   pilosa_engine_evictions_total           counter: stack evictions
#   pilosa_engine_stack_rebuilds_total      counter: full stack (re)builds
#   pilosa_engine_compile_total             counter: XLA backend compiles
#   pilosa_engine_compile_seconds{phase=}   counter: cumulative trace/lower/
#                                           compile seconds (recompile storms
#                                           show as a slope)
#   pilosa_engine_compile_cache_keys        gauge: distinct live compile keys
#   pilosa_gossip_state_transitions_total{from,to}  gossip member flaps
METRIC_ENGINE_RESIDENT_BYTES = "pilosa_engine_resident_bytes"
METRIC_ENGINE_EVICTED_BYTES = "pilosa_engine_evicted_bytes"
METRIC_ENGINE_EVICTIONS = "pilosa_engine_evictions_total"
METRIC_ENGINE_REBUILDS = "pilosa_engine_stack_rebuilds_total"
# -- tiered residency (docs/residency.md) -----------------------------------
#   pilosa_engine_promotions_total          async working-set promotions that
#                                           made a stack FULLY resident
#   pilosa_engine_partial_promotions_total  promotions that admitted only the
#                                           touched row/block subset of a
#                                           stack (device as a cache over the
#                                           compressed host tier)
#   pilosa_engine_promotions_declined_total promotion requests declined (the
#                                           working set would not fit the
#                                           device budget even partially)
#   pilosa_engine_promoted_bytes_total      device bytes shipped by the
#                                           promotion worker (its wall-clock
#                                           busy seconds live in the manager
#                                           snapshot — the ratio is the
#                                           host-decode/device-upload overlap
#                                           throughput)
#   pilosa_engine_host_fallbacks_total      queries served from the host tier
#                                           because their stack was not (yet)
#                                           resident — each enqueued an async
#                                           promote instead of blocking
#   pilosa_engine_resident_block_fraction   gauge: occupancy blocks resident
#                                           on device / blocks in the full
#                                           row universe, over known stacks
METRIC_ENGINE_PROMOTIONS = "pilosa_engine_promotions_total"
METRIC_ENGINE_PARTIAL_PROMOTIONS = "pilosa_engine_partial_promotions_total"
METRIC_ENGINE_PROMOTIONS_DECLINED = "pilosa_engine_promotions_declined_total"
METRIC_ENGINE_PROMOTED_BYTES = "pilosa_engine_promoted_bytes_total"
METRIC_ENGINE_HOST_FALLBACKS = "pilosa_engine_host_fallbacks_total"
METRIC_ENGINE_RESIDENT_BLOCK_FRACTION = "pilosa_engine_resident_block_fraction"
# ``pilosa_engine_promotions_total`` carries a {cause=} label naming WHY
# the stack moved: "reactive" (a query missed and the residency worker
# chased it), "warm_start" (EWMA-ordered restart admission), "advisor"
# (reserved — the predictive follow-on promotes ahead of traffic).
PROMOTION_CAUSES = ("reactive", "warm_start", "advisor")
# -- working-set telemetry (docs/observability.md) --------------------------
#   pilosa_engine_heat_tracked_rows         gauge: rows with live heat state
#                                           across all heat tables
#   pilosa_engine_residency_gap_bytes       gauge: bytes of HOT rows NOT
#                                           resident on device — the single
#                                           number that says "promotion is
#                                           behind traffic" (0 when the
#                                           working set is device-resident)
#   pilosa_advisor_predictions_total        rows the prefetch advisor
#                                           predicted the next query touches
#   pilosa_advisor_hits_total               predicted rows the next query
#                                           actually touched
#   pilosa_advisor_misses_total             predicted rows it did not
METRIC_ENGINE_HEAT_TRACKED_ROWS = "pilosa_engine_heat_tracked_rows"
METRIC_ENGINE_RESIDENCY_GAP = "pilosa_engine_residency_gap_bytes"
METRIC_ADVISOR_PREDICTIONS = "pilosa_advisor_predictions_total"
METRIC_ADVISOR_HITS = "pilosa_advisor_hits_total"
METRIC_ADVISOR_MISSES = "pilosa_advisor_misses_total"
METRIC_ENGINE_COMPILE = "pilosa_engine_compile_total"
METRIC_ENGINE_COMPILE_SECONDS = "pilosa_engine_compile_seconds"
METRIC_ENGINE_COMPILE_KEYS = "pilosa_engine_compile_cache_keys"
METRIC_GOSSIP_TRANSITIONS = "pilosa_gossip_state_transitions_total"
COMPILE_PHASES = ("trace", "lower", "compile")

# -- ingest surface (docs/ingest.md) ----------------------------------------
#   pilosa_ingest_batches_total{path=}      bulk-import batches accepted
#   pilosa_ingest_bits_total{path=}         bits/values submitted to them
#   pilosa_ingest_changed_total             bits the imports actually flipped
#   pilosa_ingest_seconds{path=}            per-batch apply latency histogram
#   pilosa_ingest_sync_chunks_total         ingest chunks notified to the
#                                           device-sync worker
#   pilosa_ingest_sync_coalesced_total      notifies absorbed into an
#                                           already-pending sync (overlap win)
#   pilosa_ingest_sync_dispatches_total     warm-sync passes the worker ran
METRIC_INGEST_BATCHES = "pilosa_ingest_batches_total"
METRIC_INGEST_BITS = "pilosa_ingest_bits_total"
METRIC_INGEST_CHANGED = "pilosa_ingest_changed_total"
METRIC_INGEST_SECONDS = "pilosa_ingest_seconds"
METRIC_INGEST_SYNC_CHUNKS = "pilosa_ingest_sync_chunks_total"
METRIC_INGEST_SYNC_COALESCED = "pilosa_ingest_sync_coalesced_total"
METRIC_INGEST_SYNC_DISPATCHES = "pilosa_ingest_sync_dispatches_total"
INGEST_PATHS = ("bits", "values", "roaring")
# The history sampler's own writes land under path="system" — NOT in the
# headline INGEST_PATHS tuple — so --ingest-sweep numbers and the sampled
# pilosa_ingest_* rate series can never be polluted by the sampler itself
# (the self-observation guard, docs/observability.md).
INGEST_PATH_SYSTEM = "system"

# -- durability & serving-through-failure (docs/durability.md) --------------
#   pilosa_ingest_acked_unsynced_bytes      gauge: op-log bytes ACKED to a
#                                           writer but not yet handed to
#                                           the OS — the SIGKILL loss
#                                           window at ack=received;
#                                           always 0 at logged/fsynced
#                                           (those flush/fsync before
#                                           the ack returns)
#   pilosa_replica_reads_total{route=}      reads the mapper routed off the
#                                           local node: route=primary (the
#                                           shard's first owner), replica
#                                           (a non-primary owner chosen by
#                                           replica-read=any/bounded), or
#                                           hedge (re-routed after a peer
#                                           failure mid-query)
#   pilosa_ingest_degraded_batches_total    import batches acked with one or
#                                           more DOWN owners skipped (the
#                                           survivors took the write;
#                                           anti-entropy seeds the dead
#                                           owner on recovery)
#   pilosa_client_retries_total             InternalClient connect-phase
#                                           retries (capped backoff budget)
METRIC_INGEST_ACKED_UNSYNCED = "pilosa_ingest_acked_unsynced_bytes"
METRIC_REPLICA_READS = "pilosa_replica_reads_total"
METRIC_INGEST_DEGRADED_BATCHES = "pilosa_ingest_degraded_batches_total"
METRIC_CLIENT_RETRIES = "pilosa_client_retries_total"

# -- hinted handoff (docs/durability.md "Hinted handoff") -------------------
#   pilosa_hints_queued_total               writes to a DOWN owner durably
#                                           queued as hint records for replay
#   pilosa_hints_replayed_total             hint records acked by their
#                                           recovered target
#   pilosa_hints_dropped_total{reason=}     hint records dropped WITHOUT
#                                           replay (overflow | expired |
#                                           rejected | node_removed |
#                                           io_error | rolled_back) — each
#                                           drop is a fall-back to the PR 11
#                                           skip-or-fail-loud policy
#                                           (rolled_back = the unwind of a
#                                           destructive write whose gate
#                                           failed after partial enqueue)
#   pilosa_hints_pending                    gauge: queued records awaiting
#                                           replay (all targets)
#   pilosa_hints_pending_bytes              gauge: their on-disk bytes
#                                           (bounded by [cluster]
#                                           hint-max-bytes)
METRIC_HINTS_QUEUED = "pilosa_hints_queued_total"
METRIC_HINTS_REPLAYED = "pilosa_hints_replayed_total"
METRIC_HINTS_DROPPED = "pilosa_hints_dropped_total"
METRIC_HINTS_PENDING = "pilosa_hints_pending"
METRIC_HINTS_PENDING_BYTES = "pilosa_hints_pending_bytes"
HINT_DROP_REASONS = (
    "overflow", "expired", "rejected", "node_removed", "io_error",
    "rolled_back",
)

# -- fault plane (docs/durability.md "Fault plane") -------------------------
#   pilosa_faults_injected_total{action=}   deterministic fault-plane
#                                           injections at the client/gossip
#                                           boundaries (drop | delay | error
#                                           | partition)
METRIC_FAULTS_INJECTED = "pilosa_faults_injected_total"

# -- per-tenant cost attribution (docs/observability.md) --------------------
#   pilosa_tenant_queries_total{tenant=}        queries executed
#   pilosa_tenant_device_seconds_total{tenant=} attributed device-seconds
#                                               (each query's share of every
#                                               fused dispatch it rode)
#   pilosa_tenant_bytes_touched_total{tenant=}  device bytes its plans read
#   pilosa_tenant_bytes_skipped_total{tenant=}  bytes its sparse plans skipped
#   pilosa_tenant_sheds_total{tenant=}          admission sheds charged to it
# Series are created lazily per tenant (bounded by TenantLedger's
# cardinality cap; util/plans.py).
METRIC_TENANT_QUERIES = "pilosa_tenant_queries_total"
METRIC_TENANT_DEVICE_SECONDS = "pilosa_tenant_device_seconds_total"
METRIC_TENANT_BYTES_TOUCHED = "pilosa_tenant_bytes_touched_total"
METRIC_TENANT_BYTES_SKIPPED = "pilosa_tenant_bytes_skipped_total"
METRIC_TENANT_SHEDS = "pilosa_tenant_sheds_total"

# -- TopN rank-cache maintenance (docs/ingest.md) ---------------------------
#   pilosa_cache_recalculate_seconds{path=} histogram: ranked-cache
#                                           recalculation latency
#                                           (full | merge — the incremental
#                                           sorted-batch path)
#   pilosa_cache_entries{cache_type=}       gauge: live cache entries summed
#                                           over every fragment cache of
#                                           that type (pull-time refresh)
METRIC_CACHE_RECALC = "pilosa_cache_recalculate_seconds"
METRIC_CACHE_ENTRIES = "pilosa_cache_entries"

PIPELINE_STAGES = ("queue_wait", "lower_dispatch", "device_readback", "decode")

# -- serving tier (docs/serving.md) -----------------------------------------
#   pilosa_admission_inflight               gauge: requests admitted, not done
#   pilosa_admission_active_tenants         gauge: tenants with in-flight work
#   pilosa_admission_admitted_total         counter: requests admitted
#   pilosa_admission_shed_total{reason=}    counter: fast-rejected requests
#                                           (overload|tenant_fair|queue_full)
#   pilosa_server_connections               gauge: live HTTP connections
#   pilosa_server_connections_total         counter: connections accepted
#   pilosa_server_requests_total{path=}     counter: requests by dispatch path
#                                           (inline = reactor fast path,
#                                           pool = blocking worker, shed)
# -- mesh data plane (docs/mesh.md) -----------------------------------------
#   pilosa_mesh_devices                     gauge: devices in the shard mesh
#   pilosa_mesh_local_devices               gauge: devices addressable from
#                                           THIS process (the node's
#                                           placement weight)
#   pilosa_mesh_shards_per_device           gauge: padded shard-axis
#                                           occupancy per device (max over
#                                           resident indexes)
#   pilosa_mesh_psum_dispatches_total       counter: fused collective
#                                           dispatches (the psum-IS-the-
#                                           reduce path)
#   pilosa_cluster_remote_calls_total       counter: internal-client HTTP
#                                           requests (query fan-out AND
#                                           cluster control plane: schema/
#                                           status/federation/resize).  On
#                                           a single node it stays 0; the
#                                           per-query fan-out signal is
#                                           executor.remote_fanouts
METRIC_MESH_DEVICES = "pilosa_mesh_devices"
METRIC_MESH_LOCAL_DEVICES = "pilosa_mesh_local_devices"
METRIC_MESH_SHARDS_PER_DEVICE = "pilosa_mesh_shards_per_device"
METRIC_MESH_PSUM_DISPATCHES = "pilosa_mesh_psum_dispatches_total"
METRIC_CLUSTER_REMOTE_CALLS = "pilosa_cluster_remote_calls_total"

# -- process mode (docs/serving.md "Process mode") ---------------------------
#   pilosa_process_up{proc=}                1 while the process answers the
#                                           scrape-time stats probe (engine:
#                                           always 1; a wedged worker shows 0
#                                           BEFORE the supervisor reaps it)
#   pilosa_process_rss_bytes{proc=}         resident set size per process
METRIC_PROCESS_UP = "pilosa_process_up"
METRIC_PROCESS_RSS = "pilosa_process_rss_bytes"

METRIC_ADMISSION_INFLIGHT = "pilosa_admission_inflight"
METRIC_ADMISSION_TENANTS = "pilosa_admission_active_tenants"
METRIC_ADMISSION_ADMITTED = "pilosa_admission_admitted_total"
METRIC_ADMISSION_SHED = "pilosa_admission_shed_total"
METRIC_SERVER_CONNECTIONS = "pilosa_server_connections"
METRIC_SERVER_CONNECTIONS_TOTAL = "pilosa_server_connections_total"
METRIC_SERVER_REQUESTS = "pilosa_server_requests_total"
#   pilosa_server_errors_total              counter: 5xx responses served
#                                           (includes fault-plane injected
#                                           errors) — the numerator of the
#                                           error-rate SLO (util/slo.py)
METRIC_SERVER_ERRORS = "pilosa_server_errors_total"
SHED_REASONS = ("overload", "tenant_fair", "queue_full")
SERVER_REQUEST_PATHS = ("inline", "pool", "shed")

# -- self-hosted metrics history (docs/observability.md) ---------------------
#   pilosa_history_samples_total            series values the sampler wrote
#                                           into the _system index
#   pilosa_history_ticks_total              sampler passes completed
#   pilosa_history_views_dropped_total      time-quantum views retired by
#                                           retention
#   pilosa_history_dropped_total{reason=}   series values NOT stored
#                                           (stride | clamp | error)
#   pilosa_history_tick_seconds             histogram: cost of one sampler
#                                           pass
#   pilosa_slo_burn_total{slo=}             SLO burn events journaled
METRIC_HISTORY_SAMPLES = "pilosa_history_samples_total"
METRIC_HISTORY_TICKS = "pilosa_history_ticks_total"
METRIC_HISTORY_VIEWS_DROPPED = "pilosa_history_views_dropped_total"
METRIC_HISTORY_DROPPED = "pilosa_history_dropped_total"
METRIC_HISTORY_TICK_SECONDS = "pilosa_history_tick_seconds"
METRIC_SLO_BURN = "pilosa_slo_burn_total"
HISTORY_DROP_REASONS = ("stride", "clamp", "error")

# Engine cache names labelling the hit/miss counter series (engine.py
# resolves one handle pair per name at construction).  The memo_* names
# are the per-op-kind result-memo tallies (Sum/Min/Max/TopN/GroupBy ride
# the same versioned memo as fused Counts, docs/incremental.md).
ENGINE_CACHES = (
    "stack", "mask", "zeros", "scalar", "canonical", "result_memo",
    "batch_cse", "fused_plan",
    "memo_sum", "memo_min", "memo_max", "memo_topn", "memo_groupby",
)

# -- repair-on-write materialized results (docs/incremental.md) --------------
#   pilosa_result_repairs_total{kind=}        memo entries advanced to the
#                                             current version tokens in
#                                             O(changed bits) instead of
#                                             recomputed
#   pilosa_result_repair_fallbacks_total{kind=} repair attempts that fell
#                                             back to a full recompute
#                                             (opaque write, coverage hole,
#                                             structural change, lost race)
#   pilosa_result_repair_seconds              host time per repair attempt
#   pilosa_result_repair_touched_words_total  64-bit words a repair actually
#                                             read — the O(touched) evidence
#                                             vs the index's total words
#   pilosa_cq_active                          live continuous-query
#                                             subscriptions (POST /cq)
#   pilosa_cq_deltas_total                    result deltas streamed to
#                                             continuous-query subscribers
METRIC_RESULT_REPAIRS = "pilosa_result_repairs_total"
METRIC_RESULT_REPAIR_FALLBACKS = "pilosa_result_repair_fallbacks_total"
METRIC_RESULT_REPAIR_SECONDS = "pilosa_result_repair_seconds"
METRIC_RESULT_REPAIR_TOUCHED_WORDS = "pilosa_result_repair_touched_words_total"
METRIC_CQ_ACTIVE = "pilosa_cq_active"
METRIC_CQ_DELTAS = "pilosa_cq_deltas_total"
REPAIR_KINDS = ("count", "sum", "topn", "groupby", "minmax")

# Pre-register the always-on surface so /metrics exposes every required
# series (with zero counts) from process start — scrape checks must not
# depend on traffic having flowed first.
for _stage in PIPELINE_STAGES:
    REGISTRY.histogram(
        METRIC_PIPELINE_STAGE,
        help="Batch-pipeline stage latency (seconds)",
        stage=_stage,
    )
REGISTRY.histogram(
    METRIC_HTTP_REQUEST,
    help="Query request, first byte in to last byte out (seconds)",
)
REGISTRY.counter(
    METRIC_ENGINE_DEVICE_INFLIGHT,
    help="Seconds in which at least one query dispatch was in flight",
)
REGISTRY.set_gauge(METRIC_UPTIME, 0)
REGISTRY.histogram(
    METRIC_FRAGMENT_OP, help="Fragment-level op latency (seconds)", op="row"
)
for _cache in ENGINE_CACHES:
    REGISTRY.counter(
        METRIC_ENGINE_CACHE_HITS, help="Engine cache hits", cache=_cache
    )
    REGISTRY.counter(
        METRIC_ENGINE_CACHE_MISSES, help="Engine cache misses", cache=_cache
    )
REGISTRY.counter(
    METRIC_DEVICE_BYTES_SKIPPED,
    help="Device HBM bytes skipped by occupancy-guided sparse dispatches",
)
REGISTRY.counter(
    METRIC_ENGINE_GROUP_COMBOS,
    help="GroupBy combinations evaluated on the device",
)
REGISTRY.counter(
    METRIC_ENGINE_GROUP_SUM_PASSES,
    help="Popcount passes of aggregated GroupBys: combinations x (depth + 2)",
)
for _state in GROUP_PREFIX_STATES:
    REGISTRY.counter(
        METRIC_ENGINE_GROUP_PREFIX_STEPS,
        help="Prefix steps of the GroupBy program's Pallas body, by outcome",
        state=_state,
    )
for _form in GROUP_RESULT_FORMS:
    REGISTRY.counter(
        METRIC_EXECUTOR_GROUP_RESULTS,
        help="GroupBy results by the form they left the executor in",
        form=_form,
    )
for _kind in REPAIR_KINDS:
    REGISTRY.counter(
        METRIC_RESULT_REPAIRS,
        help="Materialized results repaired in-place from write deltas",
        kind=_kind,
    )
    REGISTRY.counter(
        METRIC_RESULT_REPAIR_FALLBACKS,
        help="Repair attempts that fell back to full recompute",
        kind=_kind,
    )
REGISTRY.histogram(
    METRIC_RESULT_REPAIR_SECONDS,
    help="Host time per materialized-result repair attempt (seconds)",
)
REGISTRY.counter(
    METRIC_RESULT_REPAIR_TOUCHED_WORDS,
    help="64-bit words read by result repairs (O(touched), not O(index))",
)
REGISTRY.set_gauge(METRIC_CQ_ACTIVE, 0)
REGISTRY.counter(
    METRIC_CQ_DELTAS, help="Result deltas streamed to continuous queries"
)
REGISTRY.counter(
    METRIC_ENGINE_FUSED_PROGRAMS,
    help="Heterogeneous drains compiled+dispatched as one fused program",
)
REGISTRY.counter(
    METRIC_ENGINE_FUSED_QUERIES,
    help="Queries that rode a fused whole-program dispatch",
)
REGISTRY.counter(
    METRIC_ENGINE_FUSED_MASKS_EVAL,
    help="Distinct Row-subtree masks materialized inside fused programs",
)
REGISTRY.counter(
    METRIC_ENGINE_FUSED_MASKS_REF,
    help="Row-subtree mask references fused programs were asked for",
)
REGISTRY.set_gauge(METRIC_ENGINE_RESIDENT_BYTES, 0)
REGISTRY.set_gauge(METRIC_ENGINE_EVICTED_BYTES, 0)
REGISTRY.set_gauge(METRIC_ENGINE_COMPILE_KEYS, 0)
REGISTRY.counter(
    METRIC_ENGINE_EVICTIONS, help="Engine field-stack evictions"
)
REGISTRY.counter(
    METRIC_ENGINE_REBUILDS, help="Engine full field-stack (re)builds"
)
for _cause in PROMOTION_CAUSES:
    REGISTRY.counter(
        METRIC_ENGINE_PROMOTIONS,
        help="Async residency promotions completing a FULL stack",
        cause=_cause,
    )
REGISTRY.counter(
    METRIC_ENGINE_PARTIAL_PROMOTIONS,
    help="Async residency promotions admitting a partial (working-set) stack",
)
REGISTRY.counter(
    METRIC_ENGINE_PROMOTIONS_DECLINED,
    help="Promotion requests declined (would not fit the device budget)",
)
REGISTRY.counter(
    METRIC_ENGINE_PROMOTED_BYTES,
    help="Device bytes shipped by the residency promotion worker",
)
REGISTRY.counter(
    METRIC_ENGINE_HOST_FALLBACKS,
    help="Queries served from the host tier while their stack promotes",
)
REGISTRY.set_gauge(METRIC_ENGINE_RESIDENT_BLOCK_FRACTION, 1.0)
REGISTRY.set_gauge(METRIC_ENGINE_HEAT_TRACKED_ROWS, 0)
REGISTRY.set_gauge(METRIC_ENGINE_RESIDENCY_GAP, 0)
REGISTRY.counter(
    METRIC_ADVISOR_PREDICTIONS,
    help="Rows the prefetch advisor predicted the next query would touch",
)
REGISTRY.counter(
    METRIC_ADVISOR_HITS,
    help="Advisor-predicted rows the next query actually touched",
)
REGISTRY.counter(
    METRIC_ADVISOR_MISSES,
    help="Advisor-predicted rows the next query did not touch",
)
REGISTRY.counter(
    METRIC_ENGINE_COMPILE, help="XLA backend compiles observed in-process"
)
for _phase in COMPILE_PHASES:
    REGISTRY.counter(
        METRIC_ENGINE_COMPILE_SECONDS,
        help="Cumulative JAX trace/lower/compile seconds",
        phase=_phase,
    )
for _path in INGEST_PATHS:
    REGISTRY.counter(
        METRIC_INGEST_BATCHES, help="Bulk-import batches accepted", path=_path
    )
    REGISTRY.counter(
        METRIC_INGEST_BITS, help="Bits submitted to bulk imports", path=_path
    )
    REGISTRY.histogram(
        METRIC_INGEST_SECONDS,
        help="Bulk-import batch apply latency (seconds)",
        path=_path,
    )
REGISTRY.counter(
    METRIC_INGEST_CHANGED, help="Bits bulk imports actually changed"
)
REGISTRY.counter(
    METRIC_INGEST_SYNC_CHUNKS,
    help="Ingest chunks notified to the device-sync worker",
)
REGISTRY.counter(
    METRIC_INGEST_SYNC_COALESCED,
    help="Ingest sync notifies coalesced into a pending pass",
)
REGISTRY.counter(
    METRIC_INGEST_SYNC_DISPATCHES,
    help="Warm-sync passes the ingest sync worker ran",
)
REGISTRY.set_gauge(METRIC_INGEST_ACKED_UNSYNCED, 0)
for _route in ("primary", "replica", "hedge", "last_resort"):
    REGISTRY.counter(
        METRIC_REPLICA_READS,
        help="Reads routed off-node by the shard mapper",
        route=_route,
    )
REGISTRY.counter(
    METRIC_HINTS_QUEUED,
    help="Writes to DOWN owners durably queued as hint records",
)
REGISTRY.counter(
    METRIC_HINTS_REPLAYED,
    help="Hint records acked by their recovered target",
)
for _reason in HINT_DROP_REASONS:
    REGISTRY.counter(
        METRIC_HINTS_DROPPED,
        help="Hint records dropped without replay (policy fallback)",
        reason=_reason,
    )
REGISTRY.set_gauge(METRIC_HINTS_PENDING, 0)
REGISTRY.set_gauge(METRIC_HINTS_PENDING_BYTES, 0)
for _action in ("drop", "delay", "error", "partition"):
    REGISTRY.counter(
        METRIC_FAULTS_INJECTED,
        help="Deterministic fault-plane injections",
        action=_action,
    )
REGISTRY.counter(
    METRIC_INGEST_DEGRADED_BATCHES,
    help="Import batches acked with DOWN owners skipped (anti-entropy heals)",
)
REGISTRY.counter(
    METRIC_CLIENT_RETRIES,
    help="InternalClient connect-phase retries (capped backoff budget)",
)
for _path in ("full", "merge"):
    REGISTRY.histogram(
        METRIC_CACHE_RECALC,
        help="Ranked-cache recalculation latency (seconds)",
        path=_path,
    )
for _ct in ("ranked", "lru", "none"):
    REGISTRY.set_gauge(METRIC_CACHE_ENTRIES, 0, cache_type=_ct)
REGISTRY.set_gauge(METRIC_MESH_DEVICES, 0)
REGISTRY.set_gauge(METRIC_MESH_LOCAL_DEVICES, 0)
REGISTRY.set_gauge(METRIC_MESH_SHARDS_PER_DEVICE, 0)
REGISTRY.counter(
    METRIC_MESH_PSUM_DISPATCHES,
    help="Fused mesh collective dispatches (psum over the shard axis)",
)
REGISTRY.counter(
    METRIC_CLUSTER_REMOTE_CALLS,
    help="Internal-client HTTP requests (query fan-out + control plane)",
)
REGISTRY.set_gauge(METRIC_ADMISSION_INFLIGHT, 0)
REGISTRY.set_gauge(METRIC_ADMISSION_TENANTS, 0)
REGISTRY.set_gauge(METRIC_SERVER_CONNECTIONS, 0)
REGISTRY.counter(
    METRIC_ADMISSION_ADMITTED, help="Requests admitted to the engine"
)
for _reason in SHED_REASONS:
    REGISTRY.counter(
        METRIC_ADMISSION_SHED,
        help="Requests shed before engine work",
        reason=_reason,
    )
REGISTRY.counter(
    METRIC_SERVER_CONNECTIONS_TOTAL, help="HTTP connections accepted"
)
for _p in SERVER_REQUEST_PATHS:
    REGISTRY.counter(
        METRIC_SERVER_REQUESTS,
        help="HTTP requests by dispatch path",
        path=_p,
    )
REGISTRY.counter(
    METRIC_SERVER_ERRORS,
    help="HTTP 5xx responses served (incl. fault-plane injections)",
)
REGISTRY.counter(
    METRIC_INGEST_BATCHES,
    help="Bulk-import batches accepted",
    path=INGEST_PATH_SYSTEM,
)
REGISTRY.counter(
    METRIC_INGEST_BITS,
    help="Bits submitted to bulk imports",
    path=INGEST_PATH_SYSTEM,
)
REGISTRY.histogram(
    METRIC_INGEST_SECONDS,
    help="Bulk-import batch apply latency (seconds)",
    path=INGEST_PATH_SYSTEM,
)
REGISTRY.counter(
    METRIC_HISTORY_SAMPLES,
    help="Series values the history sampler stored in _system",
)
REGISTRY.counter(
    METRIC_HISTORY_TICKS, help="History sampler passes completed"
)
REGISTRY.counter(
    METRIC_HISTORY_VIEWS_DROPPED,
    help="_system time-quantum views retired by retention",
)
for _reason in HISTORY_DROP_REASONS:
    REGISTRY.counter(
        METRIC_HISTORY_DROPPED,
        help="Series values the sampler could not store",
        reason=_reason,
    )
REGISTRY.histogram(
    METRIC_HISTORY_TICK_SECONDS,
    help="Cost of one history sampler pass (seconds)",
)
del _stage, _cache, _phase, _path, _reason, _p


def _iter_samples(text: str):
    """Yield ``(key, value, exemplar_suffix)`` per sample line of a
    Prometheus/OpenMetrics exposition.  ``key`` is the exact
    ``name{labels}`` string as rendered (label order is deterministic —
    every process renders through this module's registry, so identical
    series produce identical keys); ``exemplar_suffix`` is the
    OpenMetrics `` # {...} v ts`` tail when present, else ``""``."""
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        suffix = ""
        if " # {" in line:
            head, _, tail = line.rpartition(" # {")
            line, suffix = head, " # {" + tail
        key, sep, value = line.rpartition(" ")
        if not sep:
            continue
        try:
            v = float(value)
        except ValueError:
            continue
        yield key, v, suffix


def _exposition_meta(text: str) -> Dict[str, List[str]]:
    """Metric family -> its # HELP/# TYPE lines, from one exposition."""
    out: Dict[str, List[str]] = {}
    for line in text.splitlines():
        line = line.strip()
        if not line.startswith("#"):
            continue
        parts = line.split(None, 3)
        if len(parts) >= 3 and parts[1] in ("HELP", "TYPE"):
            out.setdefault(parts[2], []).append(line)
    return out


def merge_expositions(primary: str, others: Dict[str, str]) -> str:
    """Sum per-process registry expositions into ONE whole-node
    exposition (the process-mode /metrics surface, docs/serving.md).

    ``primary`` is the device-owner's exposition — classic or
    OpenMetrics; exemplar suffixes and the trailing ``# EOF`` are
    preserved.  ``others`` maps a process label to that process's
    CLASSIC exposition (the worker registries).  Samples sharing an
    exact ``name{labels}`` key are SUMMED — counters, gauges, and
    histogram ``_bucket``/``_sum``/``_count`` lines are all additive
    across processes (every process shares DEFAULT_BUCKETS, so bucket
    sums stay cumulative-consistent).  Worker-only series are appended
    with their own HELP/TYPE before any ``# EOF`` — the same
    merge-don't-duplicate metadata discipline as the /cluster/metrics
    federation."""
    add: Dict[str, float] = {}
    extra_order: List[str] = []
    extra_meta: Dict[str, List[str]] = {}
    for text in others.values():
        for key, v, _suffix in _iter_samples(text):
            if key in add:
                add[key] += v
            else:
                add[key] = v
                extra_order.append(key)
        for fam, meta in _exposition_meta(text).items():
            extra_meta.setdefault(fam, meta)
    out: List[str] = []
    for line in primary.splitlines():
        stripped = line.strip()
        if not stripped or stripped.startswith("#"):
            out.append(line)
            continue
        suffix = ""
        sample = stripped
        if " # {" in sample:
            head, _, tail = sample.rpartition(" # {")
            sample, suffix = head, " # {" + tail
        key, sep, value = sample.rpartition(" ")
        delta = add.pop(key, None) if sep else None
        if delta is None:
            out.append(line)
            continue
        try:
            total = float(value) + delta
        except ValueError:
            out.append(line)
            continue
        out.append(f"{key} {_prom_float(total)}{suffix}")
    # Worker-only series, grouped by family, metadata emitted once —
    # and NEVER for a family the primary already declared: Prometheus'
    # text parser rejects the whole exposition on a second HELP/TYPE
    # line for the same name (a worker-only LABEL SET of an
    # engine-known family must ride the primary's metadata).
    tail_lines: List[str] = []
    emitted_meta: set = set(_exposition_meta(primary))
    for key in extra_order:
        if key not in add:
            continue  # summed into a primary line above
        fam = _prom_name(key.split("{", 1)[0])
        base = fam
        for strip in ("_bucket", "_sum", "_count"):
            if base.endswith(strip):
                base = base[: -len(strip)]
        for meta_name in (base, fam):
            if meta_name in extra_meta and meta_name not in emitted_meta:
                emitted_meta.add(meta_name)
                tail_lines.extend(extra_meta[meta_name])
                break
        tail_lines.append(f"{key} {_prom_float(add[key])}")
    if tail_lines:
        if out and out[-1].strip() == "# EOF":
            out[-1:-1] = tail_lines
        else:
            out.extend(tail_lines)
    return "\n".join(out) + "\n"


def diff_rates(prev_counters: dict, cur_counters: dict,
               dt: float) -> Dict[str, Dict[str, float]]:
    """Per-second rates from two counter snapshots taken ``dt`` apart.

    Both snapshots use the ``snapshot()["counters"]`` shape
    (``family -> {label_str: cumulative}``).  Monotonic-reset safe: a
    counter that went DOWN (process restart, registry reset) contributes
    its current value as the delta — the post-reset accumulation is the
    best available estimate and never goes negative.  Label churn is
    handled conservatively: a label set absent from ``prev`` is skipped
    (its rate appears one interval later), a label set absent from
    ``cur`` emits nothing.
    """
    if dt <= 0:
        return {}
    out: Dict[str, Dict[str, float]] = {}
    for family, cur in cur_counters.items():
        prev = prev_counters.get(family)
        if prev is None:
            continue
        fam_out = {}
        for label_str, cur_v in cur.items():
            if label_str not in prev:
                continue
            d = cur_v - prev[label_str]
            if d < 0:
                d = cur_v
            fam_out[label_str] = d / dt
        if fam_out:
            out[family] = fam_out
    return out


def snapshot_from_exposition(text: str) -> dict:
    """Parse a classic Prometheus exposition back into the
    ``MetricsRegistry.snapshot()`` shape.

    The process-mode history sampler runs in the device-owner process
    but must see the WHOLE node, so it samples the merged exposition
    from ``aggregate_metrics`` instead of the local registry.  Counters
    and gauges map directly (via # TYPE metadata); histograms are
    reconstructed from their cumulative ``_bucket`` lines against
    DEFAULT_BUCKETS so p50/p95 come out of the same quantile math
    ``Histogram.snapshot`` uses.
    """
    types: Dict[str, str] = {}
    for fam, meta in _exposition_meta(text).items():
        for line in meta:
            parts = line.split()
            if len(parts) >= 4 and parts[1] == "TYPE":
                types[fam] = parts[3]
    counters: Dict[str, Dict[str, float]] = {}
    gauges: Dict[str, Dict[str, float]] = {}
    # histogram family -> label_str -> {"buckets": {le: v}, "sum": s,
    # "count": c}
    hraw: Dict[str, Dict[str, dict]] = {}

    def split_key(key: str):
        if "{" in key:
            name, _, rest = key.partition("{")
            labels = rest.rstrip("}")
            pairs = []
            for part in re.findall(r'([A-Za-z0-9_]+)="((?:[^"\\]|\\.)*)"',
                                   labels):
                k, v = part
                v = v.replace('\\"', '"').replace("\\n", "\n")
                v = v.replace("\\\\", "\\")
                pairs.append((k, v))
            return name, pairs
        return key, []

    def label_str(pairs) -> str:
        return ",".join(f"{k}={v}" for k, v in pairs) or "_"

    for key, v, _suffix in _iter_samples(text):
        name, pairs = split_key(key)
        base = name
        kind = None
        for strip in ("_bucket", "_sum", "_count"):
            if name.endswith(strip) and types.get(name[: -len(strip)]) == \
                    "histogram":
                base = name[: -len(strip)]
                kind = strip
                break
        if kind is not None:
            le = None
            core = [(k, lv) for k, lv in pairs if k != "le"]
            for k, lv in pairs:
                if k == "le":
                    le = lv
            ent = hraw.setdefault(base, {}).setdefault(
                label_str(core), {"buckets": {}, "sum": 0.0, "count": 0.0}
            )
            if kind == "_bucket" and le is not None:
                ent["buckets"][le] = v
            elif kind == "_sum":
                ent["sum"] = v
            elif kind == "_count":
                ent["count"] = v
            continue
        t = types.get(name)
        if t == "counter":
            counters.setdefault(name, {})[label_str(pairs)] = v
        elif t == "gauge":
            gauges.setdefault(name, {})[label_str(pairs)] = v

    histograms: Dict[str, Dict[str, dict]] = {}
    for fam, series in hraw.items():
        out = histograms.setdefault(fam, {})
        for ls, ent in series.items():
            h = Histogram()
            cumulative = [
                ent["buckets"].get(_prom_float(b), 0.0)
                for b in DEFAULT_BUCKETS
            ]
            cumulative.append(ent["buckets"].get("+Inf", ent["count"]))
            prev = 0.0
            for i, c in enumerate(cumulative):
                h._counts[i] = max(0, int(round(c - prev)))
                prev = max(prev, c)
            h.count = int(ent["count"])
            h.sum = float(ent["sum"])
            out[ls] = h.snapshot()
    return {"histograms": histograms, "counters": counters,
            "gauges": gauges}


class StatsClient:
    """Interface; also usable as a base class."""

    def with_tags(self, *tags: str) -> "StatsClient":
        return self

    def tags(self) -> List[str]:
        return []

    def count(self, name: str, value: int = 1, rate: float = 1.0, tags=None):
        pass

    def count_with_custom_tags(self, name, value, rate, tags):
        self.count(name, value, rate, tags)

    def gauge(self, name: str, value: float, rate: float = 1.0):
        pass

    def histogram(self, name: str, value: float, rate: float = 1.0):
        pass

    def set(self, name: str, value: str, rate: float = 1.0):
        pass

    def timing(self, name: str, value_seconds: float, rate: float = 1.0):
        pass

    def open(self):
        pass

    def close(self):
        pass


class NopStatsClient(StatsClient):
    pass


class ExpvarStatsClient(StatsClient):
    """In-memory, inspectable backend (the reference's expvar client,
    stats/stats.go:117-214): exposed by the HTTP layer at /debug/vars."""

    def __init__(self, _tags: Optional[List[str]] = None, _root=None):
        self._tags = _tags or []
        if _root is None:
            _root = {"lock": threading.Lock(), "counters": {}, "gauges": {},
                     "timings": {}, "sets": {}, "children": {}}
        self._root = _root

    def _scope(self, name: str) -> str:
        if not self._tags:
            return name
        return ",".join(sorted(self._tags)) + ":" + name

    def with_tags(self, *tags: str) -> "ExpvarStatsClient":
        return ExpvarStatsClient(sorted(set(self._tags) | set(tags)), self._root)

    def tags(self) -> List[str]:
        return list(self._tags)

    def count(self, name, value: int = 1, rate: float = 1.0, tags=None):
        key = self._scope(name)
        if tags:
            key += "," + ",".join(tags)
        with self._root["lock"]:
            self._root["counters"][key] = self._root["counters"].get(key, 0) + value

    def gauge(self, name, value: float, rate: float = 1.0):
        with self._root["lock"]:
            self._root["gauges"][self._scope(name)] = value

    def histogram(self, name, value: float, rate: float = 1.0):
        # Fixed-bucket Histogram, not an unbounded list: timing series
        # on a serving tier grow forever otherwise.
        with self._root["lock"]:
            h = self._root["timings"].get(self._scope(name))
            if h is None:
                h = self._root["timings"][self._scope(name)] = Histogram()
        h.observe(value)

    def set(self, name, value: str, rate: float = 1.0):
        with self._root["lock"]:
            self._root["sets"][self._scope(name)] = value

    def timing(self, name, value_seconds: float, rate: float = 1.0):
        self.histogram(name, value_seconds, rate)

    def snapshot(self) -> Dict[str, dict]:
        with self._root["lock"]:
            timings = dict(self._root["timings"])
            return {
                "counters": dict(self._root["counters"]),
                "gauges": dict(self._root["gauges"]),
                "sets": dict(self._root["sets"]),
                "timingCounts": {k: h.count for k, h in timings.items()},
                "timings": {k: h.snapshot() for k, h in timings.items()},
            }


class PipelineStats:
    """Depth gauges and batch-occupancy counters of the pipelined query
    path (parallel/batcher.py).  The stage timings themselves live in
    the registry (``pilosa_pipeline_stage_seconds``, fed by the stage
    clock in util/tracing.py — one observe a record); ``snapshot()``
    reads count, mean and quantiles from those series, so its
    ``stages`` are the process's, not one batcher's.  Thread-safe."""

    def __init__(self):
        self._lock = threading.Lock()
        self._gauges: Dict[str, float] = {}
        self._counters: Dict[str, int] = {}

    def gauge(self, name: str, value: float):
        with self._lock:
            self._gauges[name] = value

    def gauge_max(self, name: str, value: float):
        """Keep the high-water mark (e.g. max observed in-flight depth)."""
        with self._lock:
            if value > self._gauges.get(name, float("-inf")):
                self._gauges[name] = value

    def incr(self, name: str, value: int = 1):
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + value

    def add_delta(self, name: str, delta: int):
        """Adjust a gauge by ``delta`` and track its high-water twin
        (``<name>_max``) in the same critical section — the pattern for
        in-flight depth counters."""
        with self._lock:
            v = self._gauges.get(name, 0) + delta
            self._gauges[name] = v
            if v > self._gauges.get(name + "_max", 0):
                self._gauges[name + "_max"] = v
            return v

    def snapshot(self) -> Dict[str, dict]:
        with self._lock:
            gauges = dict(self._gauges)
            counters = dict(self._counters)
        stages = {}
        for stage in PIPELINE_STAGES:
            h = REGISTRY.get_histogram(METRIC_PIPELINE_STAGE, stage=stage)
            snap = h.snapshot() if h is not None else None
            if snap and snap["count"]:
                stages[stage] = {
                    "count": snap["count"],
                    "totalSeconds": snap["sumSeconds"],
                    "meanSeconds": snap["meanSeconds"],
                    "p50Seconds": snap["p50"],
                    "p95Seconds": snap["p95"],
                    "p99Seconds": snap["p99"],
                }
        return {
            "stages": stages,
            "gauges": gauges,
            "counters": counters,
        }


class MultiStatsClient(StatsClient):
    """Fan out to several backends (stats/stats.go:217-283)."""

    def __init__(self, clients: List[StatsClient]):
        self.clients = clients

    def with_tags(self, *tags: str) -> "MultiStatsClient":
        return MultiStatsClient([c.with_tags(*tags) for c in self.clients])

    def count(self, name, value: int = 1, rate: float = 1.0, tags=None):
        for c in self.clients:
            c.count(name, value, rate, tags)

    def gauge(self, name, value: float, rate: float = 1.0):
        for c in self.clients:
            c.gauge(name, value, rate)

    def histogram(self, name, value: float, rate: float = 1.0):
        for c in self.clients:
            c.histogram(name, value, rate)

    def set(self, name, value: str, rate: float = 1.0):
        for c in self.clients:
            c.set(name, value, rate)

    def timing(self, name, value_seconds: float, rate: float = 1.0):
        for c in self.clients:
            c.timing(name, value_seconds, rate)

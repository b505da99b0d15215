"""Cross-request Count micro-batcher: a bounded multi-batch pipeline.

The reference amortizes small queries with goroutines over shared mmap'd
fragments (executor.go mapReduce :2183) — concurrency is nearly free, so
100 concurrent Counts cost ~one Count.  On an accelerator the analogous
amortization must happen BEFORE program launch: each JAX dispatch pays a
fixed floor (~100-400 us through the dispatch queue), so 100 concurrent
single-Count HTTP requests executed one dispatch each would serialize
100 floors.  This batcher drains concurrent arrivals into ONE
kernels.count_batch_tree dispatch: K answers for one floor + one
readback.

With one fused batch at a time, device compute, host lowering, and
the readback RTT serialize — the rate is capped at batch_size x
readbacks_per_second.  The path is decoupled into STAGES with their
own worker loops and a bounded number of fused batches in flight:

  accumulate  submit() queues arrivals; the drain worker gives
              concurrent arrivals a short window to pile into one drain
              (submit threads + ``count-batch-drain``)
  lower +     the drain worker groups a drain by (index, structure)
  dispatch    signature and hands groups to ``count-batch-dispatch``,
              which lowers + enqueues each group as one fused device
              program WITHOUT waiting for the device (the engine's
              donation contract serializes lower+enqueue under its
              dispatch lock, so they share one loop — the point is they
              overlap every OTHER batch's device execution and readback)
  collect     a pool of ``count-batch-collect-N`` workers block in
              jax.device_get, decode the answer vector, and resolve the
              submitters' futures (HTTP completion callbacks fire here)

In-flight depth is bounded by a semaphore (``max_inflight``, default
DEFAULT_INFLIGHT): the dispatch worker BLOCKS
on the (depth+1)'th batch, so under overload the queue accumulates a
full readback period of arrivals and batch size self-tunes to
arrival_rate x readback_time / depth, while depth batches overlap in the
transport + device.  Every stage is recorded by one call of the stage
clock (util/tracing.py ``stage``/``waited``: histogram, span, plan and,
during a capture, profiler annotation); in-flight depth and batch
occupancy are tracked in a util.stats.PipelineStats (``pipeline``
attribute; surfaced by /debug/vars).

Policy: pass-through when idle (a lone query runs on its own thread with
zero added latency — exactly the unbatched path), batch under load.
This is batching-by-backpressure: no artificial delay window, batch size
adapts to the actual concurrency.
"""

from __future__ import annotations

import queue as queue_mod
import threading
import time
from typing import Callable, List, Optional

from ..util import plans as plans_mod
from ..util import tracing
from ..util.stats import METRIC_PIPELINE_ACCUM_CLOSE, REGISTRY, PipelineStats
from .fusion import OP_NAMES

# Submission-origin tag (process-per-core serving mode, docs/serving.md
# "Process mode"): the device-owner's per-worker IPC reader threads each
# stamp their worker's identity here ONCE, so every item they submit
# carries it and the dispatch loop can count fused batches whose riders
# arrived via DIFFERENT worker processes — the cross-process analogue of
# the reactor's cross-connection coalescing evidence.  Unset (None, the
# in-process reactor / direct API case) items simply don't contribute.
_ORIGIN = threading.local()


def set_submit_origin(origin: Optional[str]):
    """Tag every subsequent submit from THIS thread with ``origin``."""
    _ORIGIN.value = origin


def submit_origin() -> Optional[str]:
    return getattr(_ORIGIN, "value", None)


class _Item:
    """One submitted query item — a Count tree (``kind == "count"``) or
    an aggregate op spec (sum/min/max/topn/topnf riding the same drain,
    docs/fusion.md) — resolved by the collect stage (or inline on the
    direct path).  ``add_done_callback`` lets the HTTP
    layer resolve a pending response without parking a thread in
    ``wait``.  The submitter's current span is captured here — the
    explicit trace handoff across the accumulate/dispatch/collect
    thread hops (stage workers stamp their timings onto it)."""

    __slots__ = (
        "index",
        "call",
        "shards",
        "kind",
        "spec",
        "plan_extra",
        "event",
        "result",
        "error",
        "t_submit",
        "t_decoded",
        "span",
        "plan",
        "memo_note",
        "memo_key",
        "origin",
        "_callbacks",
    )

    def __init__(self, index, call, shards, kind="count", spec=None):
        self.index = index
        self.call = call
        self.shards = shards
        self.kind = kind
        # Op spec for non-count items ({"kind", "field", "filter", ...});
        # count items keep spec None and the dispatch stage synthesizes
        # {"kind": "count", "call"} only when a drain actually fuses.
        self.spec = spec
        # Per-item plan-note extras stamped by the fused planner (op
        # name, mask_shared_with, footprint share).
        self.plan_extra = None
        self.event = threading.Event()
        self.result: Optional[int] = None
        self.error: Optional[BaseException] = None
        self.t_submit = time.monotonic()
        # When the decode stage of the drain that answered this item
        # ended (None on the direct path, a memo hit or an error): where
        # its request's complete_wait starts.
        self.t_decoded: Optional[float] = None
        self.span = tracing.current_span()
        # The submitter's query plan, captured exactly like the span:
        # stage workers stamp decisions and timings onto it across the
        # accumulate/dispatch/collect thread hops (util/plans.py).
        self.plan = plans_mod.current_plan()
        # ("miss", reason) computed at submit time — the memo status the
        # dispatch-note fan-out merges into this item's plan op.
        self.memo_note = None
        # Result-memo key computed at SUBMIT time (engine.memo_probe):
        # the collect stage stores the answer under the version tokens
        # the query began with, never newer ones.
        self.memo_key = None
        # Which serving process submitted this item (None outside
        # process mode) — the cross-worker fusing evidence.
        self.origin = submit_origin()
        self._callbacks: List[Callable] = []

    def done(self) -> bool:
        return self.event.is_set()

    def add_done_callback(self, fn: Callable[["_Item"], None]):
        """Run ``fn(self)`` when the item resolves (immediately if it
        already has).  Callbacks run on the resolving thread (a collect
        worker) — keep them short.  Append-then-claim over the GIL-atomic
        list keeps registration lock-free against a concurrent resolve:
        whichever side removes the callback from the list runs it."""
        self._callbacks.append(fn)
        if self.event.is_set():
            try:
                self._callbacks.remove(fn)
            except ValueError:
                return  # the resolver claimed (and ran) it
            fn(self)

    def _resolve(self):
        self.event.set()
        while self._callbacks:
            try:
                fn = self._callbacks.pop()
            except IndexError:
                break
            try:
                fn(self)
            except Exception:  # noqa: BLE001 — a bad callback must not
                pass  # poison its batchmates' completions


class CountBatcher:
    # Bail out of a wait after this long — the workers catch all
    # exceptions, so a hit means the engine itself wedged (e.g. a stuck
    # collective); surface an error instead of blocking the HTTP thread
    # forever.
    WAIT_TIMEOUT = 300.0
    # After a real (>=2 query) fused batch, keep routing arrivals through
    # the queue for this long: under sustained concurrency the direct
    # path would otherwise steal leadership after every batch and
    # serialize a 1-answer readback between every K-answer one (halving
    # throughput when the readback RTT dominates).  A lone caller never
    # triggers it — size-1 drains don't refresh the window — so idle
    # latency is untouched.
    HOT_WINDOW = 0.25

    # Accumulation window: once the queue is non-empty, give concurrent
    # arrivals a moment to pile into the SAME drain before dispatching.
    # Readback round trips serialize in the transport, so throughput is
    # (answers per readback) x (readbacks per second) — an eager worker
    # fragments arrivals into many small batches and caps throughput at
    # the readback rate; a short accumulation multiplies it by K.  Idle
    # single queries never pass through here (direct path), so this
    # costs latency only when the system is already saturated.  The
    # event-loop server feeds the queue from EVERY live connection
    # (docs/serving.md).
    #
    # The window closes when the burst has gone QUIET: nothing has
    # arrived for a quiet interval read off the queue's own timestamps
    # (every item carries ``t_submit``), and the drain worker sleeps
    # until ``last arrival + quiet``, not in fixed steps.  While the
    # pipe is idle (``_live == 0``) every millisecond waited is an idle
    # device, so the interval is QUIET_GAPS x the mean of the burst's
    # last QUIET_SPAN inter-arrival gaps, held between QUIET_MIN (one
    # thread wake-up and a straggler's jitter) and QUIET_MAX; a gap the
    # burst has not shown yet counts as QUIET_MAX / QUIET_GAPS, so a
    # lone arrival waits the ceiling and a burst earns the short
    # patience over its first QUIET_SPAN gaps.  While a batch is in
    # flight the interval is QUIET_MAX: waiting costs a query nothing
    # it could have had (it could not dispatch ahead of the in-flight
    # batch's slot anyway) and sustained unsynchronised load does not
    # fragment into small drains.  QUIET_MAX is the step of the fixed
    # poll this replaced (which closed 5-10 ms after the last arrival),
    # so no drain leaves later than it did then.  ACCUM_WINDOW bounds a
    # window under arrivals that never go quiet.  Seconds.
    #
    # The multiple, the span and the floor are constants of the
    # algorithm, not settings, checked against the taxi cell on one v5e
    # (PERF.md section 6, PR 32): sixteen arrivals ~0.54 ms apart, but
    # the reactor reads its sockets in batches with 0.8 ms (p99 2.7 ms)
    # between them, so the mean spans more than one batch: 4 x the mean
    # of 4 gaps split one burst in ten there, of 8 gaps one in
    # twenty-five.
    ACCUM_WINDOW = 0.15
    QUIET_MAX = 0.005
    QUIET_MIN = 0.001
    QUIET_GAPS = 4
    QUIET_SPAN = 8

    # Fused batches allowed in flight at once (the pipeline depth): the
    # dispatch worker blocks on the (depth+1)'th batch, so the queue
    # accumulates while depth batches overlap lowering, device
    # execution, and readback.  A depth of 2 leaves the device idle
    # whenever both readbacks are in the transport; >=4 keeps a batch
    # in every stage of the pipe.  The constructor can override it.
    DEFAULT_INFLIGHT = 4

    def __init__(self, engine, max_batch: int = 512, max_inflight: Optional[int] = None):
        self.engine = engine
        self.max_batch = max_batch
        if max_inflight is None:
            max_inflight = self.DEFAULT_INFLIGHT
        self.max_inflight = max(1, int(max_inflight))
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._queue: List[_Item] = []
        self._busy = False
        self._inflight = threading.Semaphore(self.max_inflight)
        self._last_fused = 0.0  # monotonic time of the last >=2 batch
        self._workers_started = False
        # Grouped batches ready to lower+dispatch, and dispatched device
        # futures awaiting readback.
        self._dispatch_q: "queue_mod.Queue" = queue_mod.Queue()
        self._collect_q: "queue_mod.Queue" = queue_mod.Queue()
        # Batches dispatched but not yet collected (heuristic read by
        # the drain loop's accumulate decision).  Writes are
        # read-modify-write from the dispatch thread AND every collect
        # worker, so they take ``_lock``; a lost update would leave the
        # counter skewed forever.  Reads stay lock-free (stale by at
        # most one transition — fine for a heuristic).
        self._live = 0
        # Telemetry the tests assert on.
        self.batches = 0
        self.batched_queries = 0
        self._stopped = False
        self.pipeline = PipelineStats()
        self.pipeline.gauge("depth_configured", self.max_inflight)
        # How each accumulation window ended (``_drain_loop``).
        self._closes = {
            reason: REGISTRY.counter(
                METRIC_PIPELINE_ACCUM_CLOSE,
                help="Batcher accumulation windows closed, by how they ended",
                reason=reason,
            )
            for reason in ("quiet", "full", "deadline", "idle_lone")
        }

    # -- accumulate stage ---------------------------------------------------

    def submit(self, index: str, call, shards) -> int:
        """Count one tree; returns the count.  A result-memo hit (same
        query + shards, no intervening write — engine.memo_probe)
        answers here with no queue, no device, no thread handoff.
        Otherwise lone callers run directly (no handoff); callers
        arriving while a dispatch is in flight — or within the hot
        window after a fused batch — are queued and answered from the
        next fused batch."""
        probed = getattr(self.engine, "memo_probe", None) is not None
        key, hit = self._memo_probe(index, call, shards)
        memo_note = self._plan_memo_note(probed, key, hit)
        if hit is not None:
            return int(hit)
        item = self._submit(index, call, shards, allow_direct=True,
                            memo_key=key, memo_note=memo_note)
        if item is None:
            return self._direct(index, call, shards, key, probed, memo_note)
        return self._wait([item], "batched count")[0]

    def _wait(self, items: List["_Item"], what: str) -> list:
        """Block a sync submitter on its queued items; their results in
        order, or the first one's error.  The pipeline's workers record
        the stages of that interval, so it is a hole in whatever stage
        the calling thread is inside."""
        ok = all([it.event.wait(self.WAIT_TIMEOUT) for it in items])
        tracing.hole(items[0].t_submit, time.monotonic())
        if not ok:
            raise RuntimeError(f"{what} timed out (engine wedged?)")
        for it in items:
            if it.error is not None:
                raise it.error
        return [it.result for it in items]

    def submit_async(self, index: str, call, shards) -> _Item:
        """Queue one Count into the pipeline and return its future
        (_Item).  Never takes the direct path — the caller is handing
        off completion (an HTTP deferral), so blocking here would defeat
        it; a lone async query in an idle pipe is dispatched at once
        (inside the hot window it waits one quiet interval for peers).
        A memo hit returns an already-resolved future."""
        key, hit = self._memo_probe(index, call, shards)
        memo_note = self._plan_memo_note(
            getattr(self.engine, "memo_probe", None) is not None, key, hit
        )
        if hit is not None:
            item = _Item(index, call, list(shards))
            item.result = int(hit)
            item._resolve()
            return item
        return self._submit(index, call, shards, allow_direct=False,
                            memo_key=key, memo_note=memo_note)

    def submit_op(self, index: str, kind: str, spec: dict, shards):
        """One aggregate op (sum/min/max/topn/topnf) through the batch
        lane: a run of one (``submit_ops``).  Returns the op's standard
        result shape; raises the item's own error on failure."""
        return self.submit_ops(index, [(kind, spec)], shards)[0]

    def submit_ops(self, index: str, ops, shards) -> list:
        """A run of independent aggregate ops ``[(kind, spec), ...]``
        over the same shards through the batch lane; results in call
        order.  Each call probes the memo first (a hit is answered and
        leaves the run).  Then ONE decision for the rest: a lone caller
        runs them directly (``_direct_ops``: zero added latency, every
        call dispatched before the one readback); a caller arriving
        while the pipe is busy queues each call as an item of its own
        into the drain, where the planner fuses them with their
        drain-mates into ONE device program (docs/fusion.md), and waits
        for all of them."""
        results: list = [None] * len(ops)
        rest = []  # (position in the run, kind, spec, memo key)
        for k, (kind, spec) in enumerate(ops):
            key, hit = self._memo_probe_op(index, kind, spec, shards)
            if hit is not None:
                plan = plans_mod.current_plan()
                if plan is not None:
                    plan.note_op(
                        op=OP_NAMES.get(kind, kind), path="memo", memo="hit"
                    )
                results[k] = hit
            else:
                rest.append((k, kind, spec, key))
        if not rest:
            return results

        def queue(call, allow_direct):
            _k, kind, spec, key = call
            return self._submit(index, None, shards, allow_direct,
                                kind=kind, spec=spec, memo_key=key)

        first = queue(rest[0], True)
        if first is None:  # leadership taken: the whole run goes direct
            outs = self._direct_ops(index, rest, shards)
        else:  # behind a queued call the others queue too, in call order
            outs = self._wait(
                [first] + [queue(call, False) for call in rest[1:]],
                "batched op",
            )
        for (k, *_), out in zip(rest, outs):
            results[k] = out
        return results

    def _memo_probe_op(self, index, kind, spec, shards):
        """engine.memo_probe_op, duck-typed like _memo_probe: the
        versioned memo (and its repair layer) now answers repeat
        Sum/Min/Max/TopN the way it answers repeat Counts."""
        probe = getattr(self.engine, "memo_probe_op", None)
        if probe is None:
            return None, None
        return probe(index, kind, spec, shards)

    def _direct_ops(self, index, rest, shards) -> list:
        """The lone caller's run; its results in order.  Every call
        is lowered and dispatched on its existing per-op program before
        anything is read back, then ONE ``device_get`` brings every
        result to the host (each leaf's copy is started before any is
        waited for), then each call is decoded and stored in the memo.
        The whole blocking run is the direct path's one ``execute``
        stage; the engine's ``lower`` and ``dispatch`` nest inside it
        once a call, ``device_get`` once a run, ``decode`` once a call.
        Each call keeps its own drain record and its own plan op."""
        eng = self.engine
        notes: list = []
        results: list = []
        execute = tracing.stage("execute", "direct")
        try:
            with execute:
                devs, decoders = [], []
                since = None
                for _k, kind, spec, _key in rest:
                    try:
                        dev, dec = eng.solo_op_async(index, kind, spec, shards)
                    finally:
                        notes.append(plans_mod.take_dispatch_note())
                    if since is None and dev is not None:
                        since = time.monotonic()
                    devs.append(dev)
                    decoders.append(dec)
                # An op that answers without device work (missing
                # field or stack) has no device result: None stays None.
                host = devs if since is None else eng._fetch(devs, since)
                for dec, got in zip(decoders, host):
                    with tracing.stage("decode"):
                        results.append(dec(got))
            store = getattr(eng, "memo_store_op", None)
            if store is not None:
                for (_k, kind, spec, key), out in zip(rest, results):
                    if key is not None:
                        store(key, kind, spec, out)
            return results
        finally:
            plan = plans_mod.current_plan()
            if plan is not None:
                # One op a call that reached the engine, with what its
                # dispatch published (a failed one's host_fallback stamp
                # included); the blocking run is the query's device
                # attribution (it held dispatch and readback alone).
                for (_k, kind, _spec, _key), note in zip(rest, notes):
                    d = dict(note) if note else {}
                    d.setdefault("op", OP_NAMES.get(kind, kind))
                    d.setdefault("path", "direct")
                    plan.note_op(**d)
                plan.note_device_seconds(execute.t1 - execute.t0)
            with self._lock:
                self._busy = False
                if self._queue:
                    self._cond.notify_all()

    def _plan_memo_note(self, probed: bool, key, hit):
        """Plan-record the memo outcome on the SUBMIT thread (the plan
        is ambient here; the dispatch workers only see items).  A hit is
        a complete op record by itself — no dispatch will follow; a miss
        becomes a ("miss", reason) note the dispatch fan-out merges into
        the eventual op record."""
        plan = plans_mod.current_plan()
        if plan is None or not probed:
            return None
        if hit is not None:
            plan.note_op(op="Count", path="memo", memo="hit")
            return None
        reason = "ineligible"
        if key is not None:
            memo = getattr(self.engine, "result_memo", None)
            if memo is not None and hasattr(memo, "miss_reason"):
                reason = memo.miss_reason(key)
        return ("miss", reason)

    def _memo_probe(self, index, call, shards):
        """engine.memo_probe, duck-typed: the batcher also runs against
        stub engines (tests) that predate the result memo."""
        probe = getattr(self.engine, "memo_probe", None)
        if probe is None:
            return None, None
        return probe(index, call, shards)

    def _submit(self, index, call, shards, allow_direct: bool, memo_key=None,
                memo_note=None, kind="count", spec=None):
        with self._lock:
            hot = time.monotonic() - self._last_fused < self.HOT_WINDOW
            if allow_direct and not self._busy and not self._queue and not hot:
                self._busy = True
                return None  # caller runs the direct path
            item = _Item(index, call, list(shards), kind=kind, spec=spec)
            item.memo_key = memo_key
            item.memo_note = memo_note
            self._queue.append(item)
            self._ensure_workers()
            # Wake the drain worker on the empty->non-empty transition
            # and for the arrival that fills a drain only (it times its
            # own sleeps during accumulation): per-submit notify_all
            # was measurable lock churn at ~1k submits/s on a
            # single-core host.
            n = len(self._queue)
            if n == 1 or n == self.max_batch:
                self._cond.notify_all()
        return item

    def _direct(self, index, call, shards, memo_key=None, probed=False,
                memo_note=None) -> int:
        execute = tracing.stage("execute", "direct")
        try:
            with execute:
                if probed:
                    # submit() already probed (and missed): hand the key
                    # through so count_async stores the result without a
                    # second key walk or a double-counted miss.
                    return self.engine.count(
                        index, call, shards, memo_key=memo_key
                    )
                return self.engine.count(index, call, shards)
        finally:
            # Plan record for the unbatched path: the engine published
            # its dispatch decisions to this thread's note; the whole
            # blocking call is this query's device attribution (it held
            # the dispatch + readback alone).
            note = plans_mod.take_dispatch_note()
            plan = plans_mod.current_plan()
            if plan is not None:
                d = dict(note) if note else {"op": "Count", "path": "direct"}
                if memo_note is not None:
                    d["memo"], d["memo_reason"] = memo_note
                plan.note_op(**d)
                plan.note_device_seconds(execute.t1 - execute.t0)
            with self._lock:
                self._busy = False
                if self._queue:
                    self._cond.notify_all()

    def _ensure_workers(self):
        if self._workers_started:
            return
        self._workers_started = True
        threading.Thread(
            target=self._drain_loop, daemon=True, name="count-batch-drain"
        ).start()
        threading.Thread(
            target=self._dispatch_loop, daemon=True, name="count-batch-dispatch"
        ).start()
        for i in range(self.max_inflight):
            threading.Thread(
                target=self._collect_loop,
                daemon=True,
                name=f"count-batch-collect-{i}",
            ).start()

    # -- drain stage (accumulate -> grouped batches) ------------------------

    def stop(self):
        """Shut down the stage workers (drain/dispatch/collect).  Used
        when a batcher is REPLACED (tests rebuild one per depth) —
        without it each discarded batcher leaks 2+depth daemon
        threads for the life of the process.  In-queue items resolve
        before the workers exit; new submits after stop() would queue
        forever, so only call on a batcher no longer reachable from the
        engine."""
        self._stopped = True
        with self._lock:
            self._cond.notify_all()
        if self._workers_started:
            self._dispatch_q.put(None)
            for _ in range(self.max_inflight):
                self._collect_q.put(None)

    def _drain_loop(self):
        tracing.name_thread("pq-drain")
        queue = self._queue
        while not self._stopped:
            with self._lock:
                while not queue:
                    if self._stopped:
                        return
                    self._cond.wait(timeout=60.0)
                # A lone queued query in an IDLE pipe (no batch in flight,
                # outside the hot window) dispatches immediately: the
                # accumulation window exists to fuse CONCURRENT arrivals,
                # and a lone caller paying a quiet interval would tax idle
                # latency for nothing.  But when a batch is already in
                # flight (``_live``), waiting costs this query nothing — it
                # could not dispatch ahead of the in-flight batch's slot
                # anyway — and the window lets its peers pile in.  Without
                # this, sustained load that happened to arrive one-at-a-time
                # between drain wakeups would never bootstrap the first
                # fused batch (the hot window only opens AFTER one).
                if len(queue) > 1 or self._live > 0 or (
                    time.monotonic() - self._last_fused < self.HOT_WINDOW
                ):
                    reason = self._accumulate()
                else:
                    reason = "idle_lone"
                batch = queue[: self.max_batch]
                del queue[: len(batch)]
            self._closes[reason].inc()
            groups = self._plan_drain(batch)
            # accum_tail: last arrival of the drain -> the drain handed
            # to the dispatch worker — what the window's close decision
            # cost the drain beyond its own arrivals.  The wait is the
            # worker asleep on the condition; the annotation marks the
            # hand-off, with how the window ended.
            with tracing.stage(
                "accum_tail", self._PATHS[groups[0][0]],
                t0=batch[-1].t_submit, batch=len(batch), reason=reason,
            ):
                for group in groups:
                    self._dispatch_q.put(group + (False,))

    def _accumulate(self) -> str:
        """Hold the window open until the burst in the queue has gone
        quiet (the comment over ``ACCUM_WINDOW``); returns how it closed.
        Called with ``_lock`` held; every wait releases it.  The worker
        sleeps to ``last + quiet`` and reads the queue again — sooner
        (every QUIET_MIN) while a burst is still showing its first
        QUIET_SPAN gaps, each of which shortens the interval."""
        queue = self._queue
        deadline = time.monotonic() + self.ACCUM_WINDOW
        while True:
            n = len(queue)
            if n >= self.max_batch:
                return "full"
            now = time.monotonic()
            if now >= deadline:
                return "deadline"
            last = queue[-1].t_submit
            quiet = ceiling = self.QUIET_MAX
            wake = deadline
            if self._live == 0:
                # QUIET_GAPS x the mean of the last QUIET_SPAN gaps; a
                # gap the burst has not shown yet counts as the ceiling's.
                span = self.QUIET_SPAN
                k = min(n - 1, span)
                shown = self.QUIET_GAPS * (last - queue[-1 - k].t_submit)
                quiet = (shown + (span - k) * ceiling) / span
                quiet = min(max(quiet, self.QUIET_MIN), ceiling)
                if k < span:  # a young burst shows a gap with every arrival
                    wake = now + self.QUIET_MIN
            if now >= last + quiet:
                return "quiet"
            with tracing.mark("accum_wait", queued=n):
                self._cond.wait(min(last + quiet, wake) - now)

    def _plan_drain(self, batch):
        """The whole-program planning stage between accumulate and
        lowering (docs/fusion.md).  Pure-Count runs keep the proven
        per-(index, structure) grouping — fixed-tier executables, batch
        CSE, the sparse scalar detour all intact.  A drain carrying
        aggregate items plans heterogeneously instead: every aggregate,
        plus every Count that SHARES a Row subtree with one (the
        dashboard shape: one segment filter fanned into N widgets),
        becomes ONE fused group lowered to a single device program that
        materializes each distinct mask once.  Fused-eligible items
        from DIFFERENT indexes pool into the same group — the planner
        keys mask slots and stacks per index, so a dashboard spanning
        indexes still compiles to ONE program.  A fused group of one
        falls back to the op's existing solo program — no 1-item fused
        executables minted."""
        groups = []
        by_index: dict = {}
        for it in batch:
            by_index.setdefault(it.index, []).append(it)
        eng = self.engine
        cross_index = getattr(eng, "fused_drain_async", None) is not None
        fusion_ok = (
            cross_index
            or getattr(eng, "fused_many_async", None) is not None
        ) and not getattr(eng, "multiproc", False)
        fused_all: list = []
        for index, items in by_index.items():
            aggs = [it for it in items if it.kind != "count"]
            counts = [it for it in items if it.kind == "count"]
            # An aggregated GroupBy keeps to its solo program: the fused
            # "group" edge knows a count tensor only.
            alone = [it for it in aggs if it.spec.get("aggregate")]
            groups.extend(("solo", index, [it]) for it in alone)
            aggs = [it for it in aggs if not it.spec.get("aggregate")]
            if aggs and fusion_ok:
                from .fusion import item_texts, subtree_texts

                agg_texts = set()
                for it in aggs:
                    agg_texts |= item_texts(it.spec)
                fused_items = list(aggs)
                rest = []
                for it in counts:
                    if agg_texts & subtree_texts(it.call):
                        fused_items.append(it)
                    else:
                        rest.append(it)
                counts = rest
                if cross_index:
                    fused_all.extend(fused_items)
                elif len(fused_items) == 1:
                    groups.append(("solo", index, fused_items))
                else:
                    groups.append(("fused", index, fused_items))
            elif aggs:
                # No fused support on this engine (stub/multi-process):
                # each aggregate runs its own pipelined solo dispatch.
                for it in aggs:
                    groups.append(("solo", index, [it]))
            by_sig: dict = {}
            for it in counts:
                by_sig.setdefault(
                    self._signature(it.index, it.call), []
                ).append(it)
            for _sig, its in by_sig.items():
                groups.append(("count", index, its))
        if fused_all:
            if len(fused_all) == 1:
                groups.append(("solo", fused_all[0].index, fused_all))
            else:
                # index=None: the entries carry their own index each.
                groups.append(("fused", None, fused_all))
        return groups

    # -- lower+dispatch stage -----------------------------------------------

    def _dispatch_loop(self):
        tracing.name_thread("pq-dispatch")
        while True:
            got = self._dispatch_q.get()
            if got is None:
                return  # stop() sentinel
            gkind, index, items, retried = got
            # Blocks when ``max_inflight`` batches are already in the
            # pipe — the backpressure that lets the accumulate stage
            # self-tune batch size under overload.
            self._inflight.acquire()
            with self._lock:
                self._live += 1
            self.pipeline.add_delta("inflight", 1)
            path = self._PATHS[gkind]
            if not retried:
                now = time.monotonic()
                for it in items:
                    tracing.waited("queue_wait", path, it.t_submit, now, (it,))
            try:
                decoders = None
                weights = None
                lowering = tracing.stage(
                    "lower_dispatch", path, items, batch=len(items)
                )
                with lowering:
                    dev, decoders, weights = self._lower_dispatch(
                        gkind, index, items
                    )
                note = plans_mod.take_dispatch_note()
                if gkind == "solo":
                    # The per-op aggregate dispatches publish no op or
                    # path of their own; name the lane so the plan still
                    # says which path ran.
                    note = dict(note or ())
                    note.setdefault(
                        "op", OP_NAMES.get(items[0].kind, items[0].kind)
                    )
                    note.setdefault("path", "solo")
                self._stamp_plans(items, note, weights)
            except BaseException as batch_err:  # noqa: BLE001 — the loop
                # must survive anything; a dead dispatch worker wedges
                # every later submit at WAIT_TIMEOUT.
                # A failed dispatch may have half-written its plan note
                # (e.g. occupancy stamped, then lowering raised): clear
                # it so the next batch on this thread starts clean.
                plans_mod.take_dispatch_note()
                with self._lock:
                    self._live -= 1
                self.pipeline.add_delta("inflight", -1)
                self._inflight.release()
                self._handle_batch_failure(gkind, index, items, retried, batch_err)
                continue
            if not items or (gkind == "solo" and dev is None):
                # Every fused item failed at build, or the solo op
                # answered without device work (missing field/stack):
                # nothing to collect — resolve and free the slot here.
                for it in items:
                    it.result = decoders[0](None)
                    it._resolve()
                with self._lock:
                    self._live -= 1
                self.pipeline.add_delta("inflight", -1)
                self._inflight.release()
                continue
            self.batches += 1
            self.batched_queries += len(items)
            self.pipeline.incr("batches")
            self.pipeline.incr("batched_queries", len(items))
            self.pipeline.gauge_max("max_batch_occupancy", len(items))
            if len(items) >= 2:
                # Cross-request coalescing evidence: how many batches
                # actually fused (the engine's own
                # pilosa_engine_fused_program_*_total series count the
                # programs and the queries that rode them).
                self.pipeline.incr("fused_batches")
                self._last_fused = time.monotonic()
                # Process mode: a fused batch whose riders arrived via
                # DIFFERENT worker processes proves the cross-process
                # coalescing property (tests/test_procserver.py).
                origins = {it.origin for it in items if it.origin}
                if len(origins) >= 2:
                    self.pipeline.incr("cross_worker_fused_batches")
            # In flight from the jitted call's return to the collect
            # worker's device_get (pilosa_engine_device_inflight_seconds_total).
            tracing.INFLIGHT.begin(lowering.t1)
            self._collect_q.put(
                (dev, items, time.monotonic(), decoders, weights, path)
            )

    # Drain group kind -> the stage clock's path label.
    _PATHS = {"count": "deferred", "fused": "fused", "solo": "solo"}

    def _lower_dispatch(self, gkind, index, items: List[_Item]):
        """Lower one drain group and enqueue its device program WITHOUT
        waiting for the device: (device result, decoders, weights).
        Fused items that failed at build resolve here and leave
        ``items`` (in place: the lower_dispatch stage rides on it)."""
        if gkind == "count":
            dev = self.engine.count_many_async(
                index,
                [it.call for it in items],
                [it.shards for it in items],
            )
            return dev, None, None
        if gkind == "solo":  # one aggregate on its existing per-op program
            it0 = items[0]
            dev, dec = self.engine.solo_op_async(
                it0.index, it0.kind, it0.spec, it0.shards
            )
            return dev, [dec], None
        specs = [
            it.spec
            if it.spec is not None
            else {"kind": "count", "call": it.call}
            for it in items
        ]
        drain = getattr(self.engine, "fused_drain_async", None)
        if drain is not None:
            fd = drain([
                (it.index, sp, it.shards)
                for it, sp in zip(items, specs)
            ])
        else:
            fd = self.engine.fused_many_async(
                index,
                [(sp, it.shards)
                 for it, sp in zip(items, specs)],
            )
        live_items, decoders, weights = [], [], []
        for i, it in enumerate(items):
            if fd.errors[i] is not None:
                it.error = fd.errors[i]
                it._resolve()
                continue
            it.plan_extra = fd.item_notes[i]
            live_items.append(it)
            decoders.append(fd.decoders[i])
            weights.append(fd.weights[i])
        items[:] = live_items
        return fd.dev, decoders, weights

    def _handle_batch_failure(self, gkind, index, items: List[_Item],
                              retried, batch_err):
        """One bad tree (unlowerable argument shape, unknown field) must
        not fail its batchmates — but a serial per-item retry would
        stall the pipeline for minutes on a 512-item group (each retry
        pays a full readback).  Instead split FAST: probe each item's
        LOWERING (host work, no dispatch) to attribute the error, then
        re-enqueue the survivors as ONE batch (marked ``retried`` so a
        dispatch-level failure can't loop forever).  The failed group's
        in-flight slot is released BEFORE this runs — re-enqueueing
        while holding it would deadlock a depth-1 pipeline."""
        if retried:
            for it in items:
                if it.error is None:
                    it.error = batch_err
                it._resolve()
            return
        good = []
        import contextlib

        probe_mode = getattr(
            self.engine, "probe_residency", contextlib.nullcontext
        )
        for it in items:
            try:
                # Probe mode: a residency fallback re-raised here is
                # ATTRIBUTION for a failure the dispatch already
                # counted — it must not count a second host fallback
                # per item (the hit-rate denominator).
                with probe_mode():
                    if it.kind == "count":
                        from .engine import _Lowering

                        lw = _Lowering(
                            self.engine,
                            self.engine.canonical_shards(it.index),
                            slot_vector=True,
                        )
                        if hasattr(self.engine, "_collect_row_hints"):
                            lw.row_hints = self.engine._collect_row_hints(
                                it.index, it.call
                            )
                        self.engine._lower(it.index, it.call, lw)
                    else:
                        self.engine.probe_fused_item(
                            it.index, it.spec, it.shards
                        )
                plans_mod.take_dispatch_note()  # probe leftovers: discard
                good.append(it)
            except Exception as e:  # noqa: BLE001
                # The probe may have stamped a dispatch note explaining
                # WHY this item failed (e.g. the residency layer's
                # path=host_fallback with the stack's resident
                # fraction) — fan it onto the item's plan so ?profile=1
                # and the /debug/plans analyzer see it even though the
                # answer comes from the executor's fallback.
                note = plans_mod.take_dispatch_note()
                if it.plan is not None and note is not None:
                    it.plan.note_op(**plans_mod.rider_note(note, 1))
                it.error = e
                it._resolve()
        if good and len(good) < len(items):
            if gkind == "fused" and len(good) == 1:
                # A fused group that shrank to one survivor takes the
                # op's existing lane — never mint a 1-item fused
                # executable (_plan_drain's invariant holds on retry).
                gkind = "count" if good[0].kind == "count" else "solo"
                index = good[0].index  # pooled groups carry index=None
            self._dispatch_q.put((gkind, index, good, True))
        else:
            # Nothing attributable (a dispatch-level failure): fail the
            # whole group with the batch error.
            for it in good or items:
                if it.error is None:
                    it.error = batch_err
                it._resolve()

    @staticmethod
    def _stamp_plans(items: List[_Item], note, weights=None):
        """Fan the engine's dispatch note out to every rider's plan.
        Byte tallies divide by each rider's FOOTPRINT share when the
        fused planner measured one (``weights``) — a 1-mask Count rider
        no longer pays for an 8-plane Sum neighbor — and evenly
        otherwise; the planner's per-item extras (op name,
        mask_shared_with, path) overlay the shared note."""
        if note is None:
            return
        n = len(items)
        total_w = sum(weights) if weights else 0.0
        for i, it in enumerate(items):
            if it.plan is None:
                continue
            frac = (weights[i] / total_w) if total_w else None
            d = plans_mod.rider_note(note, n, frac=frac)
            if it.plan_extra is not None:
                d.update(it.plan_extra)
                if frac is not None:
                    d["fused_cost_frac"] = round(frac, 4)
            if it.memo_note is not None:
                d["memo"], d["memo_reason"] = it.memo_note
            it.plan.note_op(**d)

    # -- collect stage ------------------------------------------------------

    def _collect_loop(self):
        import jax
        import numpy as np

        tracing.name_thread(
            threading.current_thread().name.replace("count-batch", "pq")
        )
        while True:
            got = self._collect_q.get()
            if got is None:
                return  # stop() sentinel
            dev, items, t_dispatched, decoders, weights, path = got
            t_taken = time.monotonic()
            try:
                # device_readback stays as it was defined: put into
                # _collect_q -> device_get returned.  Its two parts are
                # the wait for a collect worker and the blocking get.
                readback = tracing.stage(
                    "device_readback", path, items, t0=t_dispatched
                )
                with readback:
                    tracing.waited(
                        "collect_wait", None, t_dispatched, t_taken, None
                    )
                    # The device has its work: the time to record what
                    # the last drain's requests left for a thread that
                    # waits anyway.
                    tracing.settle()
                    with tracing.stage("device_get"):
                        if decoders is None:
                            out = np.asarray(jax.device_get(dev))
                        else:
                            out = jax.device_get(dev)
                decode = tracing.stage("decode", path, items)
                with decode:
                    for i, it in enumerate(items):
                        it.result = (
                            int(out[i]) if decoders is None
                            else decoders[i](out)
                        )
                        # Populate the result memo under the tokens read
                        # at submit time (engine.memo_probe's ordering
                        # note).  Counts hand the tree through so the
                        # repair layer can register the entry's
                        # footprint; aggregate ops store through the
                        # per-kind op memo.
                        if it.memo_key is not None:
                            if it.kind == "count":
                                self.engine.memo_store(
                                    it.memo_key, it.result, call=it.call
                                )
                            else:
                                self.engine.memo_store_op(
                                    it.memo_key, it.kind, it.spec, it.result
                                )
                # Device-cost attribution: the batch held one device
                # slot for the readback window; each rider is charged
                # its FOOTPRINT share when the fused planner measured
                # one (masks + reduce rows it actually swept, shared
                # masks split among sharers), an even share otherwise
                # (the tenant ledger sums these into
                # pilosa_tenant_device_seconds_total).
                window = readback.t1 - t_dispatched
                total_w = sum(weights) if weights else 0.0
                for i, it in enumerate(items):
                    it.t_decoded = decode.t1
                    if it.plan is not None:
                        it.plan.note_device_seconds(
                            window * weights[i] / total_w
                            if total_w
                            else window / max(1, len(items))
                        )
            except BaseException as e:  # noqa: BLE001
                for it in items:
                    it.error = e
            finally:
                tracing.INFLIGHT.end()
                with self._lock:
                    self._live -= 1
                self.pipeline.add_delta("inflight", -1)
                self._inflight.release()
                # The riders' completion callbacks, one after another on
                # this worker (the HTTP layer encodes each reply here):
                # once a drain, and on no rider, whose reply may have
                # left before the loop ends.  A rider's own wait for its
                # turn is its request's complete_wait.
                with tracing.stage("complete", path, (), batch=len(items)):
                    for it in items:
                        it._resolve()

    # -- signatures / telemetry ---------------------------------------------

    @staticmethod
    def _signature(index, call) -> tuple:
        """Batch-group key: index + the call tree with integer LITERALS
        masked.  Entries of one fused dispatch must share a STRUCTURE
        (field names, operators, nesting) so the padded batch program's
        compile key is independent of which rows/values were asked —
        row ids are traced operands (engine slot vector), so any batch
        of the same signature and tier reuses one executable.

        Only digits in ARGUMENT position (preceded by '=', '(', ',',
        '[', '<', '>', or whitespace) are masked: digit runs inside
        identifiers are part of the structure — masking them made
        ``Row(f1=3)`` and ``Row(f2=3)`` collide into one group, whose
        mixed field stacks then compiled per-drain programs (silently
        defeating fixed-tier reuse for digit-bearing field names).

        Timestamp literals (segments touching '-'/':'/'T') are NOT
        masked: a time Range lowers to one leaf per covered view, so
        different spans are different program structures and must not
        share a group."""
        import re

        def mask(m):
            s, e = m.start(), m.end()
            ctx = m.string[max(0, s - 1) : e + 1]
            if "-" in ctx or ":" in ctx or "T" in ctx:
                return m.group()
            return "#"

        return (
            index,
            re.sub(r"(?<=[=(,\[<>\s])\d+", mask, str(call)),
        )

    def pipeline_snapshot(self) -> dict:
        """Stage timings + depth gauges + occupancy, for /debug/vars."""
        snap = self.pipeline.snapshot()
        snap["depth"] = self.max_inflight
        snap["batches"] = self.batches
        snap["batchedQueries"] = self.batched_queries
        snap["avgOccupancy"] = (
            round(self.batched_queries / self.batches, 2) if self.batches else 0.0
        )
        return snap

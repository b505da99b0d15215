"""Tiered residency: device memory as a working-set cache (docs/residency.md).

The compressed host tier (roaring snapshots + the sparse RowStore) is
the at-rest format, exactly as the reference treats mmap'd fragments
(fragment.go:50-51); device HBM holds only the WORKING SET.  This module
is the control plane of that cache:

* ``ResidencyManager`` — a bounded async promotion queue + worker.  A
  cache miss in ``MeshEngine.field_stack`` whose full stack would not
  fit the device budget does NOT block (or over-admit and OOM): it
  enqueues a promote request here, raises ``ResidencyMiss``, and the
  executor serves the query from the host tier.  The worker then
  promotes the touched rows — host assembly of chunk N+1 overlapping
  the device scatter of chunk N, the IngestSyncer pattern — so the
  NEXT query over that working set dispatches on device.

* Request coalescing — repeated misses on the same stack merge their
  row sets into one pending request (a dashboard's widgets converge to
  one promotion), and a declined promotion arms a cooldown so a stack
  that can never fit doesn't spin the worker.

* Accounting — bytes a promotion has allocated on device but not yet
  committed count against the engine's admission checks
  (``inflight_bytes``), so concurrent admissions can't stack on top of
  an in-flight upload and blow the budget.

The engine side (partial stacks, the resident-block mask, cost-priced
eviction, the version-token commit gate) lives in engine.py — this
module owns only queueing, threading, and telemetry, so it stays
import-cycle-free and testable against stub engines.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, Optional, Set, Tuple

from ..util import tracing
from ..util.stats import (
    METRIC_ENGINE_HOST_FALLBACKS,
    METRIC_ENGINE_PARTIAL_PROMOTIONS,
    METRIC_ENGINE_PROMOTED_BYTES,
    METRIC_ENGINE_PROMOTIONS,
    METRIC_ENGINE_PROMOTIONS_DECLINED,
    REGISTRY,
)

Key = Tuple[str, str, str]  # (index, field, view)

# Seconds a key stays un-requestable after a DECLINED promotion: the
# stack cannot fit even partially, so re-enqueueing it per query would
# only burn the worker; the host tier keeps serving meanwhile.
DECLINE_COOLDOWN = 5.0

# Bound on distinct keys queued at once — a scan over thousands of cold
# fields must not grow an unbounded promotion backlog; overflow misses
# simply stay on the host tier until the queue drains.
MAX_PENDING = 64


class ResidencyManager:
    """Async promotion queue + worker for one MeshEngine."""

    def __init__(self, engine):
        self._engine = engine
        self._cv = threading.Condition()
        # key -> [rows, cause, trace_id]: rows is the requested row set
        # or None meaning "full stack required" (aggregate paths: BSI
        # planes, TopN candidates) — None absorbs any row set it merges
        # with.  cause/trace_id record WHY the first request fired (the
        # engine.promotion journal event + the {cause=} label on
        # pilosa_engine_promotions_total): the first cause wins a merge
        # and the first non-empty trace id is kept.
        self._pending: "Dict[Key, list]" = {}
        # key -> (deadline, declined_request_was_full): a declined FULL
        # promotion must not absorb later row-hinted requests — the
        # partial working set may well fit even though the whole stack
        # never will (a declined PARTIAL means the budget is truly too
        # small, so everything cools down).
        self._cooldown: Dict[Key, tuple] = {}
        self._inflight_bytes = 0
        self._busy = False
        self._closed = False
        self._thread: Optional[threading.Thread] = None
        # Telemetry (mirrored to the pilosa_engine_* series).
        self.promotions = 0
        self.partial_promotions = 0
        self.declined = 0
        self.dropped = 0  # queue-overflow requests (host tier serves)
        self.promoted_bytes = 0
        self.promote_seconds = 0.0
        # Full-promotion counters resolve per cause at inc time (the
        # {cause=} label on pilosa_engine_promotions_total).
        self._c_full: Dict[str, object] = {}
        self._c_partial = REGISTRY.counter(METRIC_ENGINE_PARTIAL_PROMOTIONS)
        self._c_declined = REGISTRY.counter(METRIC_ENGINE_PROMOTIONS_DECLINED)
        self._c_bytes = REGISTRY.counter(METRIC_ENGINE_PROMOTED_BYTES)
        self._c_fallbacks = REGISTRY.counter(METRIC_ENGINE_HOST_FALLBACKS)

    # -- request side (engine miss paths) -----------------------------------

    def request(self, key: Key, rows: Optional[Set[int]] = None,
                cause: str = "reactive",
                trace_id: Optional[str] = None) -> bool:
        """Enqueue (or merge into) a promotion for ``key``.  ``rows`` is
        the row-id working set the triggering query touched; None means
        the whole stack is required.  ``cause`` labels the promotion's
        origin ("reactive" | "warm_start") and ``trace_id``
        joins it to the triggering query's trace (defaulting to the
        ambient span, so an engine miss inherits its query's trace
        without plumbing).  Returns False when the request was absorbed
        by a cooldown or the queue bound (the host tier keeps serving
        either way).  Never blocks on device work."""
        if trace_id is None:
            span = tracing.current_span()
            trace_id = span.trace_id if span is not None else ""
        with self._cv:
            if self._closed:
                return False
            now = time.monotonic()
            cd = self._cooldown.get(key)
            if cd is not None:
                deadline, full_decline = cd
                if deadline > now and not (full_decline and rows is not None):
                    return False
                del self._cooldown[key]
            if key in self._pending:
                cur = self._pending[key]
                if rows is None:
                    cur[0] = None
                elif cur[0] is not None:
                    cur[0].update(rows)
                if not cur[2] and trace_id:
                    cur[2] = trace_id
            else:
                if len(self._pending) >= MAX_PENDING:
                    self.dropped += 1
                    return False
                self._pending[key] = [
                    None if rows is None else set(rows), cause, trace_id,
                ]
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._run, name="residency-promote", daemon=True
                )
                self._thread.start()
            self._cv.notify()
            return True

    def _full_counter(self, cause: str):
        c = self._c_full.get(cause)
        if c is None:
            c = self._c_full[cause] = REGISTRY.counter(
                METRIC_ENGINE_PROMOTIONS, cause=cause
            )
        return c

    def note_host_fallback(self):
        """One query served from the host tier while its stack promotes
        (the engine's miss paths call this alongside ``request``)."""
        self._c_fallbacks.inc()

    # -- admission accounting ------------------------------------------------

    def inflight_bytes(self) -> int:
        """Device bytes promotions have allocated but not yet committed
        into the engine's resident accounting — counted by every
        admission check so concurrent admits can't overshoot the budget
        on top of an in-flight upload."""
        with self._cv:
            return self._inflight_bytes

    def add_inflight(self, n: int):
        with self._cv:
            self._inflight_bytes += int(n)

    def sub_inflight(self, n: int):
        with self._cv:
            self._inflight_bytes = max(0, self._inflight_bytes - int(n))

    # -- worker --------------------------------------------------------------

    def _run(self):
        while True:
            with self._cv:
                while not self._pending and not self._closed:
                    self._cv.wait()
                if self._closed:
                    return
                key = next(iter(self._pending))
                rows, cause, trace_id = self._pending.pop(key)
                self._busy = True
            try:
                t0 = time.perf_counter()
                try:
                    outcome, shipped = self._engine._promote(
                        key, rows, cause=cause, trace_id=trace_id
                    )
                except Exception as e:  # noqa: BLE001 — worker survives
                    self._engine._log(f"residency promote {key}: {e!r}")
                    outcome, shipped = "declined", 0
                self.promote_seconds += time.perf_counter() - t0
                if shipped:
                    self.promoted_bytes += shipped
                    self._c_bytes.inc(shipped)
                if outcome == "full":
                    self.promotions += 1
                    self._full_counter(cause).inc()
                elif outcome == "partial":
                    self.partial_promotions += 1
                    self._c_partial.inc()
                elif outcome == "declined":
                    self.declined += 1
                    self._c_declined.inc()
                    with self._cv:
                        self._cooldown[key] = (
                            time.monotonic() + DECLINE_COOLDOWN,
                            rows is None,
                        )
                # "skipped": already resident / index gone — nothing to do.
            finally:
                with self._cv:
                    self._busy = False
                    self._cv.notify_all()

    # -- lifecycle / introspection -------------------------------------------

    def flush(self, timeout: float = 30.0) -> bool:
        """Block until the queue is drained and the worker idle; False
        on timeout.  Tests only."""
        deadline = time.monotonic() + timeout
        with self._cv:
            while self._pending or self._busy:
                left = deadline - time.monotonic()
                if left <= 0:
                    return False
                self._cv.wait(left)
            return True

    def snapshot(self) -> dict:
        with self._cv:
            return {
                "pendingPromotions": len(self._pending),
                "inflightBytes": self._inflight_bytes,
                "busy": self._busy,
                "promotions": self.promotions,
                "partialPromotions": self.partial_promotions,
                "declined": self.declined,
                "dropped": self.dropped,
                "promotedBytes": self.promoted_bytes,
                "promoteSeconds": round(self.promote_seconds, 6),
                "cooldowns": len(self._cooldown),
            }

    def close(self):
        with self._cv:
            self._closed = True
            self._pending.clear()
            self._cv.notify_all()
            t = self._thread
        if t is not None:
            t.join(timeout=5)

"""API façade between transport and engine.

Mirror of the reference's API struct (api.go:39-1158): every HTTP route and
CLI command lands here.  Single-node by default; when a cluster is
attached, methods validate against cluster state and imports route to
shard owners (api.go validate :93, Import :787-894).
"""

from __future__ import annotations

import csv
import datetime as dt
import io
import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from . import __version__, pql
from .util import fanout, plans, tracing
from .util.stats import (
    INGEST_PATH_SYSTEM,
    INGEST_PATHS,
    METRIC_INGEST_BATCHES,
    METRIC_INGEST_BITS,
    METRIC_INGEST_CHANGED,
    METRIC_INGEST_DEGRADED_BATCHES,
    METRIC_INGEST_SECONDS,
    METRIC_QUERY,
    REGISTRY,
)
from .core import timequantum
from .core.index import SYSTEM_INDEX
from .core.field import FieldOptions
from .core.fragment import SHARD_WIDTH
from .core.holder import Holder
from .core.translate import TranslateFile
from .core.view import VIEW_STANDARD, view_bsi_name
from .executor import ExecOptions, Executor, QueryResponse
from .executor.executor import Error as ExecError
from .executor.translate import QueryTranslator


class ApiError(Exception):
    pass


class NotFoundError(ApiError):
    pass


class QueryRequest:
    """handler.go:21-47."""

    def __init__(
        self,
        index: str,
        query: str,
        shards: Optional[List[int]] = None,
        column_attrs: bool = False,
        exclude_row_attrs: bool = False,
        exclude_columns: bool = False,
        remote: bool = False,
        trace_context=None,
        profile: bool = False,
        tenant: str = "default",
        replica_read: str = "",
        freshness_ms: Optional[float] = None,
        clock=None,
    ):
        self.index = index
        self.query = query
        self.shards = shards
        self.column_attrs = column_attrs
        self.exclude_row_attrs = exclude_row_attrs
        self.exclude_columns = exclude_columns
        self.remote = remote
        # Replica-read routing override + freshness bound for this
        # request (X-Pilosa-Replica-Read / X-Pilosa-Freshness-Ms;
        # docs/durability.md) — "" / None defer to [cluster] config.
        self.replica_read = replica_read
        self.freshness_ms = freshness_ms
        # Incoming tracing.TraceContext (X-Trace-Id/X-Span-Id headers):
        # the handler sets it so a remote fan-out joins the caller's
        # trace instead of rooting a fresh one.
        self.trace_context = trace_context
        # ?profile=1: return the recorded QueryPlan inline with the
        # response (docs/observability.md); ``tenant`` keys the plan's
        # cost-ledger attribution (X-Pilosa-Tenant, else the index name
        # — the same key admission fairness uses).
        self.profile = profile
        self.tenant = tenant or "default"
        # The HTTP layer's tracing.RequestClock (first byte in -> last
        # byte out); the API hands it the request's root span so its
        # http_read/respond stages carry the path the query took.
        self.clock = clock


class ImportRequest:
    """internal/public.proto ImportRequest."""

    def __init__(
        self,
        index: str,
        field: str,
        shard: int = 0,
        row_ids: Optional[List[int]] = None,
        column_ids: Optional[List[int]] = None,
        row_keys: Optional[List[str]] = None,
        column_keys: Optional[List[str]] = None,
        timestamps: Optional[List[Optional[int]]] = None,
    ):
        self.index = index
        self.field = field
        self.shard = shard
        # `is None` (not truthiness): id/timestamp vectors may be numpy
        # arrays.
        self.row_ids = row_ids if row_ids is not None else []
        self.column_ids = column_ids if column_ids is not None else []
        self.row_keys = row_keys or []
        self.column_keys = column_keys or []
        self.timestamps = timestamps if timestamps is not None else []


class ImportValueRequest:
    def __init__(
        self,
        index: str,
        field: str,
        shard: int = 0,
        column_ids: Optional[List[int]] = None,
        column_keys: Optional[List[str]] = None,
        values: Optional[List[int]] = None,
    ):
        self.index = index
        self.field = field
        self.shard = shard
        self.column_ids = column_ids if column_ids is not None else []
        self.column_keys = column_keys or []
        self.values = values if values is not None else []


class API:
    def __init__(
        self,
        holder: Optional[Holder] = None,
        translate_store: Optional[TranslateFile] = None,
        cluster=None,
        node=None,
        stats=None,
        tracer=None,
        mesh_engine=None,
        long_query_time: float = 0.0,
        logger=None,
        journal=None,
    ):
        from .util import NopLogger, Tracer, events as events_mod

        self.long_query_time = long_query_time
        self.logger = logger if logger is not None else NopLogger()
        # Structured event journal served at GET /debug/events.  Default
        # resolution order: an explicit per-node journal (Server wires
        # its own through every component), else the engine's (so a
        # standalone API+engine pair shares one), else the process
        # global.
        if journal is None:
            journal = getattr(mesh_engine, "journal", None) or events_mod.JOURNAL
        self.journal = journal
        # Gossip transport handle for the readiness probe's convergence
        # check; set by the server after _setup_gossip (None when no
        # gossip is configured).
        self.gossip = None
        # Admission controller handle (net/admission.py), wired by
        # net.serve() on the event-loop backend: lets API-level surfaces
        # (debug snapshots, operator tooling) read shed state without a
        # reference to the HTTP server object.
        self.admission = None
        # Process-mode server handle (net/procserver.py), wired by
        # net.serve() when [server] workers > 0: readiness folds the
        # worker-process health into /readyz.
        self.process_server = None
        # Tracing is always-on at the serving tier: the default is a
        # real span tracer (cheap — a few object allocations per query)
        # so /debug/traces works out of the box; pass a NopTracer to
        # opt out explicitly.
        if tracer is None:
            tracer = Tracer()
        self.tracer = tracer
        # Whole-query latency series, registered at boot (so /metrics
        # always exposes them) with the handles cached — the per-query
        # path must pay only the per-series lock, not the registry's.
        self._h_query_sync = REGISTRY.histogram(
            METRIC_QUERY, help="Whole-query latency (seconds)", path="sync"
        )
        self._h_query_pipelined = REGISTRY.histogram(
            METRIC_QUERY, path="pipelined"
        )
        # Ingest surface handles (docs/ingest.md), resolved once: the
        # import hot paths pay per-series locks only.
        self._ingest_series = {
            path: (
                REGISTRY.counter(METRIC_INGEST_BATCHES, path=path),
                REGISTRY.counter(METRIC_INGEST_BITS, path=path),
                REGISTRY.histogram(METRIC_INGEST_SECONDS, path=path),
            )
            for path in INGEST_PATHS + (INGEST_PATH_SYSTEM,)
        }
        self._ingest_changed = REGISTRY.counter(METRIC_INGEST_CHANGED)
        # Self-observation surfaces (docs/observability.md), wired by the
        # Server when [observability] enables them: the history sampler
        # (util/history.py) and the SLO watcher (util/slo.py).
        self.history = None
        self.slo = None
        self.holder = holder if holder is not None else Holder()
        if not self.holder.opened:
            self.holder.open()
        self.translate_store = (
            translate_store if translate_store is not None else TranslateFile()
        )
        self.cluster = cluster
        self._node = node
        self.executor = Executor(
            self.holder,
            cluster=cluster,
            node=node,
            translator=QueryTranslator(self.translate_store),
            stats=stats,
            tracer=tracer,
            mesh_engine=mesh_engine,
        )
        self.mesh_engine = mesh_engine
        # Set by the server when ``[mesh] devices >= 0``: /readyz then
        # names a missing engine instead of passing a host-loop node.
        self.mesh_required = False
        # Multi-host collective replay worker (lazy; see
        # mesh_collective_accept).  ``_mesh_pending`` holds accepted-but-
        # uncommitted two-phase dispatches: did -> (payload, expiry Timer).
        self._mesh_replay_q = None
        self._mesh_replay_lock = threading.Lock()
        self._mesh_pending: Dict[str, tuple] = {}
        # Sequencer state (mesh_ticket): only consulted on the node the
        # deployment designates as sequencer.
        self._mesh_ticket_lock = threading.Lock()
        self._mesh_ticket_next = 0
        # Continuous queries (net/cq.py), created on first POST /cq —
        # most deployments never pay the sweeper thread.
        self._cq = None
        self._cq_lock = threading.Lock()
        if cluster is not None:
            self.attach_cluster(cluster, node)

    def attach_cluster(self, cluster, node=None):
        """Wire the cluster into the executor and install the create-shard
        broadcast hook (view.go:226 CreateShardMessage)."""
        self.cluster = cluster
        self._node = node if node is not None else cluster.node
        self.executor.cluster = cluster
        if cluster.holder is None:
            cluster.holder = self.holder

        def on_create_shard(index, field, shard):
            # The reference gossips CreateShardMessage asynchronously
            # (view.go:226 SendAsync); falls back to the HTTP fan-out
            # when no gossip transport is attached.
            cluster.send_async(
                {
                    "type": "create-shard",
                    "index": index,
                    "field": field,
                    "shard": shard,
                }
            )

        self.holder.set_on_create_shard(on_create_shard)

    @property
    def cq(self):
        """Continuous-query manager, created on first use."""
        if self._cq is None:
            with self._cq_lock:
                if self._cq is None:
                    from .net.cq import CQManager

                    self._cq = CQManager(self)
        return self._cq

    # -- queries (api.go Query :102) ---------------------------------------

    def query(self, req: QueryRequest) -> QueryResponse:
        opt = ExecOptions(
            remote=req.remote,
            exclude_row_attrs=req.exclude_row_attrs,
            exclude_columns=req.exclude_columns,
            column_attrs=req.column_attrs,
            replica_read=getattr(req, "replica_read", ""),
            freshness_ms=getattr(req, "freshness_ms", None),
        )
        start = time.monotonic()
        parent = getattr(req, "trace_context", None)
        # Per-query plan record (util/plans.py): decisions stamp onto it
        # from the executor/engine/batcher while the span carries the
        # timing tree.  Remote replays are excluded — the initiator's
        # plan already attributes the whole query, and a replay plan
        # would double-charge the tenant ledger.
        plan = None if req.remote else plans.begin(
            req.index, req.query, tenant=getattr(req, "tenant", "default"),
            profile=getattr(req, "profile", False),
        )
        with self.tracer.start_span(
            "api.Query", parent=parent, index=req.index, remote=req.remote
        ) as span, plans.attach(plan):
            clock = req.clock
            if clock is not None:
                clock.span, clock.plan = span, plan
                clock.executing()  # the prologue stage ends
            resp = self.executor.execute(req.index, req.query, req.shards, opt)
            if clock is not None:
                clock.executed()  # the epilogue stage starts
        with tracing.mark("epilogue"):
            elapsed = time.monotonic() - start
            trace_id = span.trace_id if span is not None else None
            self._h_query_sync.observe(elapsed, exemplar=trace_id)
            if plan is not None:
                plan.finish(elapsed, trace_id=trace_id)
                plans.record(plan)
                if plan.profile:
                    resp.plan = plan.to_dict()
            if span is not None:
                resp.trace_id = span.trace_id
        # Long-query logging (api.go:1021, server LongQueryTime).
        if self.long_query_time and elapsed > self.long_query_time:
            self.logger.printf(
                "%.3fs > %.1fs: %s %s (trace %s)",
                elapsed,
                self.long_query_time,
                req.index,
                req.query[:200],
                span.trace_id if span is not None else "-",
            )
        return resp

    def fast_counts(self, index: str, query: str, tenant: str = "default"):
        """Serving-boundary memo lane: ``(values, trace_id)`` when every
        top-level Count of ``query`` answers from the versioned result
        memo (executor.memo_counts), else None.  The process-mode
        device-owner calls this before building any request machinery —
        a repeat dashboard query costs the engine a parse-cache hit and
        K memo lookups, nothing else.  Tenant query accounting and the
        pipelined-latency histogram still move (weighted-fair shares
        judge measured load, and a memo hit IS a served query); the
        span tree and plan ring are skipped — recording "memo hit,
        ~0 device-seconds" per repeat at this rate would be pure
        overhead on the one GIL process mode exists to relieve."""
        t0 = time.monotonic()
        vals = self.executor.memo_counts(index, query)
        if vals is None:
            return None
        plans.LEDGER.account_queries(tenant, len(vals))
        trace_id = tracing.new_id()
        self._h_query_pipelined.observe(time.monotonic() - t0)
        return vals, trace_id

    def query_async(self, req: QueryRequest):
        """Deferred query: returns a future (result/add_done_callback ->
        QueryResponse) when the executor can pipeline the request
        (all-Count queries through the batch pipeline), else None — the
        caller falls back to the synchronous ``query``.  The HTTP layer
        uses this to resolve responses from completion callbacks instead
        of holding a handler thread per in-flight query."""
        opt = ExecOptions(
            remote=req.remote,
            exclude_row_attrs=req.exclude_row_attrs,
            exclude_columns=req.exclude_columns,
            column_attrs=req.column_attrs,
            replica_read=getattr(req, "replica_read", ""),
            freshness_ms=getattr(req, "freshness_ms", None),
        )
        start = time.monotonic()
        parent = getattr(req, "trace_context", None)
        # Deferred span: begun here, finished by the completion callback
        # on a collect worker.  attach() makes it the submit path's
        # current span so the batcher items capture it (the explicit
        # handoff across the pipeline's thread hops).
        span = self.tracer.begin(
            "api.Query", parent=parent, index=req.index, pipelined=True
        )
        plan = None if req.remote else plans.begin(
            req.index, req.query, tenant=getattr(req, "tenant", "default"),
            profile=getattr(req, "profile", False),
        )
        if plan is not None:
            plan.pipelined = True
        with tracing.attach(span), plans.attach(plan):
            if req.clock is not None:
                req.clock.executing()  # the prologue stage ends
            fut = self.executor.execute_async(
                req.index, req.query, req.shards, opt
            )
            if req.clock is not None:
                req.clock.executed()
        if fut is None:
            # Declined (sync fallback): discard the provisional span —
            # left attached it would sit unfinished in a live parent's
            # tree, and query() roots its own span for the retry.
            if span is not None and span.parent is not None:
                try:
                    span.parent.children.remove(span)
                except ValueError:
                    pass
            return None
        fut.trace_span = span
        fut.query_plan = plan
        if req.clock is not None:
            req.clock.span, req.clock.plan = span, plan

        def _finish(_f):
            elapsed = time.monotonic() - start
            if span is not None:
                span.finish()
            if plan is not None:
                plan.finish(
                    elapsed,
                    trace_id=span.trace_id if span is not None else None,
                )
                plans.record(plan)
            self._h_query_pipelined.observe(
                elapsed, exemplar=span.trace_id if span is not None else None
            )
            if self.long_query_time and elapsed > self.long_query_time:
                self.logger.printf(
                    "%.3fs > %.1fs: %s %s (trace %s)",
                    elapsed,
                    self.long_query_time,
                    req.index,
                    str(req.query)[:200],
                    span.trace_id if span is not None else "-",
                )

        fut.add_done_callback(_finish)
        return fut

    # -- schema (api.go :129-386, 625-687) ---------------------------------

    def create_index(
        self, name: str, keys: bool = False, track_existence: bool = True
    ):
        idx = self.holder.create_index(
            name, keys=keys, track_existence=track_existence
        )
        self._broadcast(
            {
                "type": "create-index",
                "index": name,
                "cid": idx.creation_id,
                "meta": {"keys": keys},
            }
        )
        return idx

    def index(self, name: str):
        idx = self.holder.index(name)
        if idx is None:
            raise NotFoundError(f"index not found: {name}")
        return idx

    def delete_index(self, name: str):
        idx = self.holder.index(name)
        cid = idx.creation_id if idx is not None else ""
        # Tombstone contained fields too: a delayed create-field broadcast
        # for the dead incarnation must not attach to a recreated index.
        field_cids = (
            [f.creation_id for f in idx.fields.values()]
            if idx is not None
            else []
        )
        self.holder.delete_index(name)
        self.holder.tombstone(cid)
        for fcid in field_cids:
            self.holder.tombstone(fcid)
        self._broadcast(
            {
                "type": "delete-index",
                "index": name,
                "cid": cid,
                "fieldCids": field_cids,
            }
        )

    def create_field(self, index_name: str, field_name: str, options=None):
        idx = self.index(index_name)
        if isinstance(options, dict):
            options = FieldOptions.from_dict(options)
        f = idx.create_field(field_name, options)
        self._broadcast(
            {
                "type": "create-field",
                "index": index_name,
                "field": field_name,
                "cid": f.creation_id,
                "meta": f.options.to_dict(),
            }
        )
        return f

    def field(self, index_name: str, field_name: str):
        f = self.index(index_name).field(field_name)
        if f is None:
            raise NotFoundError(f"field not found: {field_name}")
        return f

    def delete_field(self, index_name: str, field_name: str):
        idx = self.index(index_name)
        f = idx.field(field_name)
        cid = f.creation_id if f is not None else ""
        idx.delete_field(field_name)
        self.holder.bump_shard_epoch(index_name)
        self.holder.tombstone(cid)
        self._broadcast(
            {
                "type": "delete-field",
                "index": index_name,
                "field": field_name,
                "cid": cid,
            }
        )

    def schema(self) -> List[dict]:
        return self.holder.schema()

    def views(self, index_name: str, field_name: str) -> List[str]:
        return sorted(self.field(index_name, field_name).views)

    def delete_view(self, index_name: str, field_name: str, view_name: str):
        f = self.field(index_name, field_name)
        v = f.views.pop(view_name, None)
        if v is None:
            raise NotFoundError(f"view not found: {view_name}")
        v.close()
        self.holder.bump_shard_epoch(index_name)
        import os
        import shutil

        if v.path and os.path.isdir(v.path):
            shutil.rmtree(v.path)
        self._broadcast(
            {
                "type": "delete-view",
                "index": index_name,
                "field": field_name,
                "view": view_name,
            }
        )

    # -- imports (api.go Import :787, ImportValue :895, ImportRoaring :290) -

    def _check_writable(self):
        """Reject writes while the cluster resizes (api.go validate :93:
        apiImport/apiImportValue are methodsNormal-only — absent from
        the RESIZING method set).  A write accepted mid-resize could
        land on a fragment already copied to its new owner and vanish
        when the old copy is cleaned; clients retry after the (bounded)
        resize completes."""
        if self.cluster is not None and self.cluster.state == "RESIZING":
            raise ApiError("cluster is resizing: writes are rejected")

    def _ingest_done(self, path: str, index_name: str, bits: int, t0: float,
                     changed: Optional[int] = None, remote: bool = False):
        """Record one applied ingest batch (pilosa_ingest_* series) and
        notify the engine's device-sync worker so resident stacks
        scatter-update behind this write instead of on the next query's
        critical path (docs/ingest.md).  ``remote`` replays (a
        coordinator already counted the user-facing batch) skip the
        series — otherwise a cluster import double-counts, once at the
        coordinator and again at each forwarded owner — but still
        notify the local sync worker."""
        if index_name == SYSTEM_INDEX:
            # Self-observation guard: the history sampler's own writes go
            # through this exact path, so without rerouting they would
            # inflate the headline pilosa_ingest_* series the sampler is
            # recording — a feedback loop.  path="system" keeps them
            # visible but out of every headline tuple.
            path = INGEST_PATH_SYSTEM
        if not remote:
            batches, bits_c, hist = self._ingest_series[path]
            batches.inc()
            bits_c.inc(bits)
            hist.observe(time.monotonic() - t0)
            if changed:
                self._ingest_changed.inc(changed)
        eng = self.mesh_engine
        if eng is not None:
            eng.ingest_syncer().notify(index_name)

    def _live_owners(
        self, index: str, shard: int, clear: bool = False, hint_op=None,
        rollback=None,
    ):
        """A shard's owners with DOWN ones skipped — the DEGRADED write
        policy (docs/durability.md): survivors take the write, the ack
        is made durable on them, and each DOWN owner's miss is durably
        QUEUED as a hint record (hinted handoff) for replay on
        recovery.  ``hint_op`` builds the replayable op payload lazily
        (once per shard, only when an owner is actually DOWN).  Raises
        when every owner is DOWN (nothing can make the ack durable).
        ``clear`` marks a bit-REMOVING import — anti-entropy's
        majority-tie-to-set merge would re-SET the removed bits once
        the dead owner (still holding them) recovers, silently undoing
        the acked write — so those ack ONLY when every miss was
        absorbed by the hint queue, and fail loudly on overflow/expiry
        (the PR 11 fallback).  Callers pass clear=True for explicit
        ?clear=true imports AND for implicitly destructive ones
        (mutex/bool fields displace the previous row, BSI value imports
        rewrite bit planes).  Returns
        (live_owners, skipped_count, hinted_count)."""
        owners = self.cluster.shard_nodes(index, shard)
        live = [n for n in owners if n.state != "DOWN"]
        down = [n for n in owners if n.state == "DOWN"]
        if not live:
            raise ApiError(
                f"import unavailable: every owner of shard {shard} is "
                f"DOWN ({', '.join(n.id for n in owners)})"
            )
        hinted = 0
        # (node id, seq) enqueues awaiting rollback.  ``rollback`` is
        # CALLER-owned and spans the whole import: the gate failing on
        # shard B must also unwind shard A's hints — the grouping loop
        # runs before any apply, so the entire batch fails un-acked and
        # every absorbed miss is a phantom.
        fresh = rollback if rollback is not None else []
        hints = getattr(self.cluster, "hints", None)
        if down and hints is not None and hint_op is not None:
            op = hint_op()
            for n in down:
                seq = hints.enqueue(n.id, index, shard, op)
                if seq:
                    hinted += 1
                    fresh.append((n.id, seq))
        if clear and hinted < len(down):
            # All-or-nothing for destructive imports: the batch is about
            # to FAIL (no ack), so any miss already absorbed — THIS
            # shard's or an earlier one's — must not survive to replay
            # an import that never happened.
            for nid, seq in fresh:
                hints.discard(nid, [seq])
            del fresh[:]
            raise ApiError(
                f"clear import unavailable: owner of shard {shard} is "
                "DOWN, the hint queue could not absorb the miss, and a "
                "degraded bit-removing import would be reverted by "
                "anti-entropy on its recovery"
            )
        return live, len(down) - hinted, hinted

    def _discard_hint_rollback(self, fresh):
        """Unwind a failed import batch's queued hints — every shard's,
        whatever raised (a later shard's all-owners-DOWN error, a
        fan-out failure): the client got no ack, so no absorbed miss
        may survive to replay."""
        hints = getattr(self.cluster, "hints", None)
        if hints is None:
            return
        for nid, seq in fresh:
            hints.discard(nid, [seq])
        del fresh[:]

    def _import_destructive(self, f, clear: bool) -> bool:
        """Does this import REMOVE bits on apply?  Explicit clears do;
        so do set-imports into mutex/bool fields (last-write-wins
        displaces the column's previous row)."""
        from .core.field import FIELD_TYPE_BOOL, FIELD_TYPE_MUTEX

        return clear or f.options.type in (FIELD_TYPE_MUTEX, FIELD_TYPE_BOOL)

    def _note_degraded(self, index: str, skipped: int, hinted: int = 0):
        """Record how a degraded import fan-out handled its DOWN
        owners: ``hinted`` misses are queued for replay (the new
        normal), ``skipped`` ones fell back to the PR 11 anti-entropy
        seeding (hint queue absent or full).  Only a true skip counts
        the degraded-batches series — a hinted batch is not degraded,
        its replay is deterministic."""
        if hinted:
            self.journal.append(
                "ingest.hinted", index=index, hintedOwners=hinted,
            )
        if not skipped:
            return
        REGISTRY.inc(METRIC_INGEST_DEGRADED_BATCHES)
        self.journal.append(
            "ingest.degraded", index=index, skippedOwners=skipped,
        )

    def import_bits(
        self, req: ImportRequest, remote: bool = False, clear: bool = False
    ):
        """Bulk bit import: translate keys, group bits by shard, forward
        each shard group to every replica of its owner set, apply locally
        when this node is an owner (api.go Import :787-894).  ``clear``
        removes the given bits instead (the handler's ?clear=true,
        http/handler.go:1002)."""
        self._check_writable()
        idx = self.index(req.index)
        f = self.field(req.index, req.field)
        # Keep the caller's arrays as-is (field.import_bulk is
        # array-native); only the per-bit cluster grouping below and key
        # translation need python lists.
        col_ids = req.column_ids
        row_ids = req.row_ids
        if req.column_keys:
            if not idx.keys:
                raise ApiError("importing keys into unkeyed index")
            col_ids = self.translate_store.translate_columns_to_uint64(
                req.index, req.column_keys
            )
        if req.row_keys:
            if not f.options.keys:
                raise ApiError("importing keys into unkeyed field")
            row_ids = self.translate_store.translate_rows_to_uint64(
                req.index, req.field, req.row_keys
            )
        # .tolist() for the same json.dumps reason as the id vectors
        # (None entries survive the object-array round trip).
        timestamps = (
            np.asarray(req.timestamps).tolist()
            if any(t for t in req.timestamps)
            else []
        )
        # Validate BEFORE any mutation (field.go Import validation): a
        # late ValueError from field.import_bulk would land after the
        # existence field already recorded the columns (phantom
        # existence bits) and after part of the cluster fan-out applied.
        if timestamps:
            if clear:
                raise ValueError(
                    "import clear is not supported with timestamps"
                )
            if not f.time_quantum():
                raise ValueError(
                    f"field {req.field!r} has no time quantum: cannot "
                    "import with timestamps"
                )

        t0 = time.monotonic()
        if self.cluster is None or remote:
            self._import_local(idx, f, row_ids, col_ids, timestamps, clear)
            self._ingest_done("bits", req.index, len(col_ids), t0,
                              remote=remote)
            return

        # Group by shard, forward to owners (api.go:835-860).  Locally
        # owned groups merge into ONE local apply (field.import_bulk
        # re-splits by shard and fans fragments out concurrently); the
        # remote per-(shard, node) RPCs run through the bounded import
        # fan-out instead of serially awaiting each round trip.
        # .tolist() (not list()) so numpy inputs become python ints — the
        # remote per-shard slices go through InternalClient's json.dumps,
        # which rejects np.int64 scalars.
        col_ids = np.asarray(col_ids).tolist()
        row_ids = np.asarray(row_ids).tolist()
        groups: Dict[int, list] = {}
        for i, c in enumerate(col_ids):
            groups.setdefault(c // SHARD_WIDTH, []).append(i)
        local_idxs: list = []
        remote_jobs = []
        skipped_owners = 0
        hinted_owners = 0
        hint_rollback: list = []  # spans every shard of this batch
        try:
            for shard, idxs in sorted(groups.items()):
                s_rows = [row_ids[i] for i in idxs]
                s_cols = [col_ids[i] for i in idxs]
                s_ts = [timestamps[i] for i in idxs] if timestamps else []
                live, skipped, hinted = self._live_owners(
                    req.index, shard,
                    clear=self._import_destructive(f, clear),
                    hint_op=lambda r=s_rows, c=s_cols, t=s_ts: {
                        "kind": "import_bits", "field": req.field,
                        "rows": r, "cols": c, "ts": t or None,
                        "clear": clear,
                    },
                    rollback=hint_rollback,
                )
                skipped_owners += skipped
                hinted_owners += hinted
                for node in live:
                    if node.id == self.cluster.node.id:
                        local_idxs.extend(idxs)
                    else:
                        remote_jobs.append(
                            lambda n=node, s=shard, r=s_rows, c=s_cols,
                            t=s_ts: (
                                self.cluster.client(n).import_bits(
                                    req.index,
                                    req.field,
                                    s,
                                    r,
                                    c,
                                    timestamps=t or None,
                                    remote=True,
                                    clear=clear,
                                )
                            )
                        )
            if local_idxs:
                remote_jobs.append(
                    lambda: self._import_local(
                        idx,
                        f,
                        [row_ids[i] for i in local_idxs],
                        [col_ids[i] for i in local_idxs],
                        [timestamps[i] for i in local_idxs]
                        if timestamps else [],
                        clear,
                    )
                )
            fanout.run_fanout(remote_jobs)
        except Exception:
            # The batch is failing un-acked, WHEREVER it raised — a
            # later shard's all-owners-DOWN error, a fan-out failure:
            # unwind every hint it queued (phantoms otherwise).
            self._discard_hint_rollback(hint_rollback)
            raise
        self._note_degraded(req.index, skipped_owners, hinted_owners)
        self._ingest_done("bits", req.index, len(col_ids), t0)

    def _import_local(self, idx, f, row_ids, col_ids, timestamps, clear=False):
        ts = None
        if timestamps:
            # ImportRequest.Timestamps are epoch-NANOSECONDS, matching the
            # reference wire format (api.go:874 `time.Unix(0, ts)`).
            ts = [
                dt.datetime.fromtimestamp(
                    t / 1e9, dt.timezone.utc
                ).replace(tzinfo=None)
                if t
                else None
                for t in timestamps
            ]
        # Clears do NOT retract existence: other fields may still hold
        # the column (handler clear semantics affect only this field).
        ef = idx.existence_field()
        # len() (not truthiness): col_ids may be a numpy array now.
        if not clear and ef is not None and len(col_ids):
            ef.import_bulk(np.zeros(len(col_ids), dtype=np.int64), col_ids)
        f.import_bulk(row_ids, col_ids, ts, clear=clear)

    def import_values(
        self,
        req: ImportValueRequest,
        remote: bool = False,
        clear: bool = False,
        fresh: bool = False,
    ):
        self._check_writable()
        idx = self.index(req.index)
        f = self.field(req.index, req.field)
        # .tolist() (not list()): numpy inputs must become python ints
        # before the cluster fan-out's json.dumps (same as import_bits).
        col_ids = np.asarray(req.column_ids).tolist()
        if req.column_keys:
            if not idx.keys:
                raise ApiError("importing keys into unkeyed index")
            col_ids = self.translate_store.translate_columns_to_uint64(
                req.index, req.column_keys
            )

        def apply_local(cols, values):
            ef = idx.existence_field()
            if not clear and ef is not None and len(cols):
                ef.import_bulk([0] * len(cols), cols)
            # fresh (set-only BSI write) is a local caller's guarantee
            # about local columns — it never rides the cluster fan-out.
            f.import_values(cols, values, clear=clear, fresh=fresh)

        t0 = time.monotonic()
        if self.cluster is None or remote:
            apply_local(col_ids, req.values)
            self._ingest_done("values", req.index, len(col_ids), t0,
                              remote=remote)
            return
        vals = np.asarray(req.values).tolist()
        groups: Dict[int, list] = {}
        for i, c in enumerate(col_ids):
            groups.setdefault(c // SHARD_WIDTH, []).append(i)
        local_idxs: list = []
        remote_jobs = []
        skipped_owners = 0
        hinted_owners = 0
        hint_rollback: list = []  # spans every shard of this batch
        try:
            for shard, idxs in sorted(groups.items()):
                cols = [col_ids[i] for i in idxs]
                values = [vals[i] for i in idxs]
                # BSI value imports rewrite bit planes (they CLEAR bits
                # even on the set path): ackable under a DOWN owner
                # only via the hint queue.
                live, skipped, hinted = self._live_owners(
                    req.index, shard, clear=True,
                    hint_op=lambda c=cols, v=values: {
                        "kind": "import_values", "field": req.field,
                        "cols": c, "values": v, "clear": clear,
                    },
                    rollback=hint_rollback,
                )
                skipped_owners += skipped
                hinted_owners += hinted
                for node in live:
                    if node.id == self.cluster.node.id:
                        local_idxs.extend(idxs)
                    else:
                        remote_jobs.append(
                            lambda n=node, s=shard, c=cols, v=values: (
                                self.cluster.client(n).import_values(
                                    req.index, req.field, s, c, v,
                                    remote=True, clear=clear,
                                )
                            )
                        )
            if local_idxs:
                remote_jobs.append(
                    lambda: apply_local(
                        [col_ids[i] for i in local_idxs],
                        [vals[i] for i in local_idxs],
                    )
                )
            fanout.run_fanout(remote_jobs)
        except Exception:
            # Same unwind as import_bits: no ack, no surviving hints.
            self._discard_hint_rollback(hint_rollback)
            raise
        self._note_degraded(req.index, skipped_owners, hinted_owners)
        self._ingest_done("values", req.index, len(col_ids), t0)

    def import_roaring(
        self,
        index_name: str,
        field_name: str,
        shard: int,
        data: bytes,
        view: str = VIEW_STANDARD,
        clear: bool = False,
    ) -> int:
        """Union (or clear) a serialized roaring bitmap into a fragment —
        the fast ingest path (api.go:290-349, ImportRoaringRequest.Clear).
        The container payload is decoded ONCE (vectorized codec) and the
        positions shared with both the fragment merge and the existence
        field, where this previously paid two full decodes."""
        self._check_writable()
        t0 = time.monotonic()
        idx = self.index(index_name)
        f = self.field(index_name, field_name)
        v = f.view_if_not_exists(view)
        frag = v.fragment_if_not_exists(shard)
        from .roaring import codec

        positions = codec.deserialize(data).values
        n = frag.import_roaring(data, clear=clear, values=positions)
        ef = idx.existence_field()
        if ef is not None and not clear and positions.size:
            base = shard * SHARD_WIDTH
            cols = (positions % SHARD_WIDTH).astype(np.int64) + base
            ef.import_bulk(np.zeros(len(cols), dtype=np.int64), cols)
        self._ingest_done(
            "roaring", index_name, int(positions.size), t0, changed=n
        )
        return n

    # -- export (api.go ExportCSV :416) ------------------------------------

    def export_csv(self, index_name: str, field_name: str, shard: int, w) -> None:
        idx = self.index(index_name)
        f = self.field(index_name, field_name)
        frag = self.holder.fragment(index_name, field_name, VIEW_STANDARD, shard)
        if frag is None:
            raise NotFoundError("fragment not found")
        writer = csv.writer(w)
        base = shard * SHARD_WIDTH
        for row_id in frag.row_ids():
            for pos in frag.row_positions(row_id):
                col = base + int(pos)
                if f.options.keys:
                    row_out = self.translate_store.translate_row_to_string(
                        index_name, field_name, row_id
                    )
                else:
                    row_out = row_id
                if idx.keys:
                    col_out = self.translate_store.translate_column_to_string(
                        index_name, col
                    )
                else:
                    col_out = col
                writer.writerow([row_out, col_out])

    # -- shards / fragments (api.go :493-563, 992-1010) --------------------

    def shard_nodes(self, index_name: str, shard: int) -> List[dict]:
        if self.cluster is not None:
            return [n.to_dict() for n in self.cluster.shard_nodes(index_name, shard)]
        return [self.node()]

    def max_shards(self) -> Dict[str, int]:
        out = {}
        for name, idx in self.holder.indexes.items():
            shards = list(idx.available_shards())
            out[name] = max(shards) if shards else 0
        return out

    def available_shards_by_index(self) -> Dict[str, List[int]]:
        return {
            name: [int(s) for s in idx.available_shards()]
            for name, idx in self.holder.indexes.items()
        }

    def fragment_blocks(
        self, index_name: str, field_name: str, view_name: str, shard: int
    ):
        frag = self.holder.fragment(index_name, field_name, view_name, shard)
        if frag is None:
            raise NotFoundError("fragment not found")
        return [
            {"id": blk, "checksum": digest.hex()}
            for blk, digest in frag.checksum_blocks()
        ]

    def fragment_block_data(
        self, index_name: str, field_name: str, view_name: str, shard: int, block: int
    ):
        frag = self.holder.fragment(index_name, field_name, view_name, shard)
        if frag is None:
            raise NotFoundError("fragment not found")
        rows, cols = frag.block_data(block)
        return {"rows": rows.tolist(), "cols": cols.tolist()}

    def delete_available_shard(self, index_name, field_name, shard: int):
        self.field(index_name, field_name).remove_available_shard(shard)

    def recalculate_caches(self):
        for idx in self.holder.indexes.values():
            for f in idx.fields.values():
                for v in f.views.values():
                    for frag in v.fragments.values():
                        frag.cache.recalculate()
        self._broadcast({"type": "recalculate-caches"})

    # -- attr diff (api.go :689-786) ----------------------------------------

    def index_attr_diff(self, index_name: str, blocks: List[dict]) -> Dict[int, dict]:
        idx = self.index(index_name)
        return _attr_diff(idx.column_attr_store, blocks)

    def field_attr_diff(
        self, index_name: str, field_name: str, blocks: List[dict]
    ) -> Dict[int, dict]:
        f = self.field(index_name, field_name)
        return _attr_diff(f.row_attr_store, blocks)

    # -- cluster admin (api.go :564-623, 1057-1123) ------------------------

    def hosts(self) -> List[dict]:
        if self.cluster is not None:
            return [n.to_dict() for n in self.cluster.nodes]
        return [self.node()]

    def node(self) -> dict:
        if self._node is not None:
            return self._node.to_dict()
        return {"id": "local", "uri": "http://localhost:10101", "isCoordinator": True}

    def state(self) -> str:
        if self.cluster is not None:
            return self.cluster.state
        return "NORMAL"

    def readiness(self) -> Tuple[bool, List[str]]:
        """Readiness verdict with reason strings (the GET /readyz
        contract): ready iff the holder is open, the engine (when
        configured) exists and has not been closed, the cluster state is NORMAL,
        and gossip has converged (no member stuck in SUSPECT).  A node
        that answers /healthz (alive) but not /readyz should be kept in
        the pool but taken out of rotation — e.g. while a resize is
        redistributing fragments."""
        reasons: List[str] = []
        if not self.holder.opened:
            reasons.append("holder not opened")
        eng = self.mesh_engine
        if eng is None and self.mesh_required:
            reasons.append("mesh engine missing")
        elif eng is not None and getattr(eng, "_closed", False):
            reasons.append("engine closed")
        # Overlapped warm-start (docs/durability.md): while residency is
        # being re-established from snapshots the node ANSWERS queries
        # (host path), but reports warming so orchestrators keep it out
        # of rotation until the working set is resident.
        ws = self.warm_status()
        if ws is not None and not ws["done"]:
            reasons.append(
                f"warming: residency {ws['fraction']:.0%} "
                f"({ws['built']}/{ws['total']} stacks)"
            )
        if self.cluster is not None and self.cluster.state != "NORMAL":
            reasons.append(f"cluster state {self.cluster.state}")
        gossip = self.gossip
        if gossip is not None:
            suspects = sorted(
                mid for mid, state in gossip.member_states().items()
                if state == "suspect"
            )
            if suspects:
                reasons.append(
                    "gossip not converged: suspect " + ",".join(suspects)
                )
        # Process mode: a missing/crashed worker process degrades
        # readiness until the supervisor's respawn reconnects it.
        ps = self.process_server
        if ps is not None:
            reasons.extend(ps.not_ready_reasons())
        return (not reasons), reasons

    def warm_status(self) -> Optional[dict]:
        """The engine's warm-start progress snapshot (None when no
        warm-start has been requested this boot): {"done", "fraction",
        "built", "total", "skipped"} — served in the /readyz body and
        folded into the readiness verdict."""
        eng = self.mesh_engine
        if eng is None:
            return None
        ws = getattr(eng, "warm_state", None)
        if ws is None:
            return None
        total = ws.get("total") or 0
        return {
            "done": bool(ws.get("done")),
            "built": int(ws.get("built", 0)),
            "total": int(ws.get("total", 0)),
            "skipped": int(ws.get("skipped", 0)),
            "fraction": (
                1.0 if not total else min(1.0, ws.get("built", 0) / total)
            ),
        }

    def version(self) -> str:
        return __version__

    def info(self) -> dict:
        return {"shardWidth": SHARD_WIDTH}

    def cluster_message(self, msg: dict):
        """Receive a broadcast control-plane message (server.go:485-580)."""
        typ = msg.get("type")
        # Gossip delivery is AT-LEAST-ONCE and unordered (dedup ids
        # eventually expire while peers may still retransmit), so every
        # handler here must be idempotent.  Schema messages carry the
        # object's creation_id ("cid"): creates skip tombstoned ids and
        # adopt the originator's id; deletes tombstone the id and only
        # remove a local object of that same incarnation — a redelivered
        # or reordered delete can't destroy a recreated object, and
        # clock skew is irrelevant (no wall-clock comparison).
        if typ == "create-index":
            self._apply_create_index(msg)
        elif typ == "delete-index":
            cid = msg.get("cid", "")
            self.holder.tombstone(cid)
            for fcid in msg.get("fieldCids", []):
                self.holder.tombstone(fcid)
            idx = self.holder.index(msg["index"])
            if idx is not None and (not cid or idx.creation_id == cid):
                for f in idx.fields.values():
                    self.holder.tombstone(f.creation_id)
                self.holder.delete_index(msg["index"])
        elif typ == "create-field":
            self._apply_create_field(msg["index"], msg)
        elif typ == "delete-field":
            cid = msg.get("cid", "")
            self.holder.tombstone(cid)
            idx = self.holder.index(msg["index"])
            f = idx.field(msg["field"]) if idx is not None else None
            if f is not None and (not cid or f.creation_id == cid):
                idx.delete_field(msg["field"])
                self.holder.bump_shard_epoch(msg["index"])
        elif typ == "create-shard":
            idx = self.holder.index(msg["index"])
            f = idx.field(msg["field"]) if idx else None
            if f is not None:
                from .roaring import Bitmap

                f.add_remote_available_shards(Bitmap([msg["shard"]]))
        elif typ == "node-status":
            from .roaring import Bitmap

            # A NodeStatus exchange is a heartbeat: record receipt plus
            # the sender's per-index data-version tokens — the evidence
            # bounded replica reads run on (docs/durability.md).
            if self.cluster is not None:
                sender = msg.get("node", {}).get("id")
                if sender:
                    self.cluster.note_heartbeat(
                        sender,
                        msg.get("versions") or None,
                        ae_passes=msg.get("aePasses"),
                        # Peer-advertised pending-hint counts (hinted
                        # handoff): quarantine release + the syncer's
                        # defer-own-pass check consume these.  A status
                        # WITHOUT the field (pre-hint peer) leaves the
                        # previous advertisement untouched.
                        pending_hints=msg.get("pendingHints"),
                    )

            # Anti-entropy schema reconciliation: adopt the sender's
            # tombstones FIRST (so a delete this node missed applies here
            # instead of this node's stale schema resurrecting it
            # elsewhere), then merge creations, skipping anything
            # tombstoned on either side.
            for cid in msg.get("tombstones", []):
                if self.holder.is_tombstoned(cid):
                    continue
                self.holder.tombstone(cid)
                for iname, idx in list(self.holder.indexes.items()):
                    if idx.creation_id == cid:
                        for f in idx.fields.values():
                            self.holder.tombstone(f.creation_id)
                        self.holder.delete_index(iname)
                        break
                    for fname, f in list(idx.fields.items()):
                        if f.creation_id == cid:
                            idx.delete_field(fname)
                            self.holder.bump_shard_epoch(iname)
                            break
            for index_name, info in msg.get("indexes", {}).items():
                idx = self._apply_create_index(
                    {
                        "index": index_name,
                        "cid": info.get("cid", ""),
                        "meta": {"keys": info.get("keys", False)},
                    }
                )
                if idx is None:
                    continue
                for field_name, finfo in info.get("fields", {}).items():
                    f = self._apply_create_field(
                        index_name,
                        {
                            "field": field_name,
                            "cid": finfo.get("cid", ""),
                            "meta": finfo.get("options", {}),
                        },
                    )
                    if f is not None:
                        f.add_remote_available_shards(
                            Bitmap(finfo.get("availableShards", []))
                        )
            # RESIZING is coordinator-granted: if the coordinator's
            # periodic status says the resize is over but this node
            # missed the set-state NORMAL broadcast (one lost POST — or
            # a coordinator that died mid-job and restarted), adopt its
            # state instead of staying wedged in RESIZING forever
            # (mergeClusterStatus parity, cluster.go:1530-1570).
            if (
                self.cluster is not None
                and self.cluster.state == "RESIZING"
                and msg.get("node", {}).get("isCoordinator")
                and msg.get("state") not in (None, "", "RESIZING")
            ):
                self.cluster.set_state(msg["state"])
        elif typ == "recalculate-caches":
            for idx in self.holder.indexes.values():
                for f in idx.fields.values():
                    for v in f.views.values():
                        for frag in v.fragments.values():
                            frag.cache.recalculate()
        elif self.cluster is not None:
            self.cluster.receive_message(msg)

    def _apply_create_index(self, msg: dict):
        """Idempotent remote create-index: skip tombstoned incarnations,
        adopt the originator's creation_id on fresh creates, and converge
        to min(local, remote) cid when both sides created the same name
        concurrently (otherwise ids diverge forever and later deletes are
        silently ignored on half the cluster).  Returns the index or None
        (tombstoned)."""
        cid = msg.get("cid", "")
        if self.holder.is_tombstoned(cid):
            return None
        existing = self.holder.index(msg["index"])
        idx = self.holder.create_index_if_not_exists(
            msg["index"], keys=msg.get("meta", {}).get("keys", False)
        )
        if cid and (existing is None or cid < idx.creation_id):
            idx.creation_id = cid
            idx.save_meta()
        return idx

    def _apply_create_field(self, index_name: str, msg: dict):
        """Idempotent remote create-field (see _apply_create_index)."""
        cid = msg.get("cid", "")
        if self.holder.is_tombstoned(cid):
            return None
        idx = self.holder.index(index_name)
        if idx is None:
            return None
        existing = idx.field(msg["field"])
        f = idx.create_field_if_not_exists(
            msg["field"], FieldOptions.from_dict(msg.get("meta", {}))
        )
        if cid and (existing is None or cid < f.creation_id):
            f.creation_id = cid
            f.save_meta()
        return f

    def set_coordinator(self, node_id: str):
        if self.cluster is None:
            raise ApiError("not clustered")
        return self.cluster.set_coordinator(node_id)

    def remove_node(self, node_id: str):
        if self.cluster is None:
            raise ApiError("not clustered")
        return self.cluster.remove_node(node_id)

    def resize_abort(self):
        if self.cluster is None:
            raise ApiError("not clustered")
        self.cluster.abort_resize()

    # -- translation (api.go :1124-1166) ------------------------------------

    def get_translate_data(self, offset: int) -> bytes:
        return self.translate_store.reader(offset)

    # Accepted-but-uncommitted dispatches expire after this many seconds:
    # an initiator that died between accept and commit must not leave a
    # pending entry (let alone a dispatched collective) behind.  Must
    # comfortably exceed the initiator's whole accept fan-out (35 s/peer
    # waits, server._broadcast_dispatch) so a slow-but-successful handoff
    # can never race its own expiry.
    MESH_PENDING_TIMEOUT = 120.0
    # Replay readbacks wait at most this long for the collective to
    # complete before the worker moves on (a stuck psum is logged, not a
    # permanent wedge of the replay worker).
    MESH_REPLAY_TIMEOUT = 120.0

    def mesh_ticket(self) -> int:
        """Issue the next dense collective sequence number (this node is
        the mesh sequencer; route /internal/mesh/ticket).  Tickets give
        collectives a global order so ANY node can initiate
        (parallel/seqgate.py)."""
        with self._mesh_ticket_lock:
            seq = self._mesh_ticket_next
            self._mesh_ticket_next += 1
            return seq

    def mesh_collective_accept(self, payload: dict):
        """Accept a multi-host collective dispatch descriptor from a peer
        (route /internal/mesh/dispatch): validate NOW (so a bad dispatch
        fails the initiator's synchronous handoff with a 400 instead of
        hanging its psum), then replay on the worker thread —
        deterministic lowering over identical holder state yields the
        identical program, so the cross-process rendezvous completes
        (parallel/multihost.py).  Kinds mirror the engine's fused paths:
        count / sum / minmax / topn / topn_scores / group.

        Handoff is two-phase (server._broadcast_dispatch): ``phase:
        "accept"`` validates and registers the dispatch under its ``did``
        without entering it; ``"commit"`` moves it to the replay queue;
        ``"abort"`` (or expiry) drops it.  A payload with no ``did`` is a
        direct single-phase dispatch (in-process callers/tests)."""
        phase = payload.get("phase", "accept")
        if phase in ("commit", "abort"):
            return self._mesh_collective_resolve(payload, phase)
        if self.mesh_engine is None:
            raise ApiError("mesh engine not available")
        from . import pql as pql_mod

        kind = payload.get("kind")
        required = {
            "count": ("query",),
            "eval": ("query",),
            "count_batch": ("queries", "shardsList"),
            "sum": ("field",),
            "minmax": ("field", "isMin"),
            "topn": ("field", "src", "n", "minThreshold", "cands"),
            "topn_scores": ("field", "rows", "src"),
            "group": ("fields", "rows"),
        }.get(kind)
        if required is None:
            raise ApiError(f"unknown collective kind: {kind}")
        missing = [k for k in required if k not in payload]
        if missing:
            raise ApiError(f"collective {kind} missing: {missing}")
        idx = self.holder.index(payload.get("index", ""))
        if idx is None:
            raise NotFoundError(f"index not found: {payload.get('index')}")
        # Data-plane parity: the replay recomputes the canonical shard
        # axis from the LOCAL holder, so a shard created on the initiator
        # but not yet gossiped here would yield mismatched collective
        # shapes across processes — a hang instead of an error.  The
        # initiator ships its canonical list; reject divergence NOW so
        # its fan-out fails with a clean 400 (same pattern as the pinned
        # TopN candidate set).
        canon = payload.get("canon")
        if canon is not None:
            mine = self.mesh_engine.canonical_shards(payload["index"])
            if [int(s) for s in canon] != [int(s) for s in mine]:
                raise ApiError(
                    f"canonical shard axis diverged: initiator={canon} "
                    f"local={mine} (retry after anti-entropy)"
                )
        # Field existence/type checks: a replay that silently declines to
        # dispatch (e.g. unknown field -> None) would strand the
        # initiator's collective, so reject at accept time.
        for fname in (
            [payload["field"]] if "field" in payload else payload.get("fields", [])
        ):
            f = idx.field(fname)
            if f is None:
                raise NotFoundError(f"field not found: {fname}")
            if kind in ("sum", "minmax") and f.bsi_group(fname) is None:
                raise ApiError(f"field is not BSI: {fname}")
        # Parse every call text ONCE up front: a syntax error (or an
        # empty required text) must surface to the initiator as a 400,
        # not strand its collective; the parsed calls ride the queue so
        # the worker doesn't re-parse.  Only the optional filter may be
        # absent/None.
        payload = dict(payload)
        payload["_calls"] = {}
        for key in ("query", "src", "filter"):
            text = payload.get(key)
            if text is None and (key == "filter" or key not in required):
                continue
            if not text:
                raise ApiError(f"collective {kind}: empty {key}")
            q = pql_mod.parse(text)
            if len(q.calls) != 1:
                raise ApiError("collective dispatch carries exactly one call")
            payload["_calls"][key] = q.calls[0]
        if kind == "count_batch":
            if len(payload["queries"]) != len(payload["shardsList"]):
                raise ApiError("count_batch: queries/shardsList length mismatch")
            if not payload["queries"]:
                raise ApiError("count_batch: empty batch")
            batch_calls = []
            for text in payload["queries"]:
                q = pql_mod.parse(text)
                if len(q.calls) != 1:
                    raise ApiError(
                        "collective dispatch carries exactly one call"
                    )
                batch_calls.append(q.calls[0])
            payload["_batch_calls"] = batch_calls
        self._ensure_mesh_worker()
        did = payload.get("did")
        if did is None:
            self._mesh_replay_q.put(payload)  # single-phase (in-process)
            return True
        timer = threading.Timer(
            self.MESH_PENDING_TIMEOUT, self._mesh_pending_expire, args=(did,)
        )
        timer.daemon = True
        with self._mesh_replay_lock:
            self._mesh_pending[did] = (payload, timer)
        timer.start()
        return True

    def _ensure_mesh_worker(self):
        with self._mesh_replay_lock:
            if self._mesh_replay_q is None:
                import queue as queue_mod

                self._mesh_replay_q = queue_mod.Queue()
                t = threading.Thread(
                    target=self._mesh_replay_loop, daemon=True,
                    name="mesh-replay",
                )
                t.start()

    def _mesh_collective_resolve(self, payload: dict, phase: str):
        """Commit or abort a pending two-phase dispatch.  Sequenced
        dispatches (symmetric initiation) run on their own thread gated
        by the engine's SeqGate — ticket order, not commit-arrival
        order; unsequenced ones keep the FIFO replay worker."""
        did = payload.get("did")
        with self._mesh_replay_lock:
            entry = self._mesh_pending.pop(did, None)
        if entry is None:
            if phase == "abort":
                # Unknown did is a no-op — but an abort that carries a
                # ticket must still skip it, or the gate stalls there:
                # accept may have failed HERE while other peers took the
                # ticket into their streams.
                seq = payload.get("seq")
                if seq is not None and self.mesh_engine is not None:
                    self.mesh_engine.seq_gate.skip(int(seq))
                return True
            raise ApiError(f"unknown or expired dispatch: {did}")
        pending, timer = entry
        timer.cancel()
        seq = pending.get("seq")
        if phase == "commit":
            if seq is not None:
                threading.Thread(
                    target=self._mesh_seq_replay, args=(pending,),
                    daemon=True, name=f"mesh-seq-{seq}",
                ).start()
            else:
                self._mesh_replay_q.put(pending)
        elif seq is not None:
            self.mesh_engine.seq_gate.skip(int(seq))
        return True

    def _mesh_pending_expire(self, did: str):
        with self._mesh_replay_lock:
            entry = self._mesh_pending.pop(did, None)
        if entry is not None:
            pending, _timer = entry
            seq = pending.get("seq")
            if seq is not None and self.mesh_engine is not None:
                self.mesh_engine.seq_gate.skip(int(seq))
            self.logger.printf(
                "mesh dispatch %s expired uncommitted (initiator died "
                "mid-handoff?); dropped without dispatching", did
            )

    def _mesh_seq_replay(self, payload: dict):
        """Execute one committed sequenced dispatch: enter the gate at
        its ticket, dispatch, exit, then do the bounded readback.  Gate
        entry — not a FIFO queue — defines cross-process order, so
        commits may arrive in any order."""
        seq = int(payload["seq"])
        gate = self.mesh_engine.seq_gate
        try:
            if not gate.enter(seq):
                self.logger.printf(
                    "mesh seq %d was force-skipped before replay "
                    "(initiator may hang)", seq,
                )
                return
            try:
                dev = self._mesh_replay_dispatch(payload)
            finally:
                gate.exit(seq)
            self._mesh_replay_readback(dev, payload)
        except Exception as e:  # noqa: BLE001
            self.logger.printf("mesh seq replay failed: %s", e)

    def _mesh_replay_loop(self):
        """Replays peer dispatches in arrival order (the initiating node
        serializes its own collectives under the engine lock and hands
        them off in order, so arrival order IS initiation order)."""
        import jax

        while True:
            payload = self._mesh_replay_q.get()
            try:
                with self.mesh_engine.collective_lock:
                    dev = self._mesh_replay_dispatch(payload)
                self._mesh_replay_readback(dev, payload)
            except Exception as e:
                self.logger.printf("mesh replay failed: %s", e)
            finally:
                # Replayed dispatches publish plan notes like any engine
                # dispatch, but no query on this thread ever claims them
                # — the initiator's plan attributes the whole query.
                # Drop the note so it can't accrue fields across
                # unrelated replays in this long-lived thread's TLS.
                plans.take_dispatch_note()

    def _mesh_replay_readback(self, dev, payload: dict):
        """Bounded wait for a replayed collective's result: a collective
        some process never joins (e.g. commit reached us but not a third
        peer) must not wedge the worker forever.  device_get is
        uncancellable, so it waits on a side thread; on timeout we log
        and move on (the leaked thread ends if/when the runtime
        unsticks).  Errors inside the thread are captured and logged —
        a bare thread would route them to excepthook/stderr, invisible
        to the server logger."""
        import jax

        if dev is None:
            # The initiator dispatched and is blocked in its collective;
            # a declined replay strands it.  Accept-time validation
            # makes this unreachable for known schema; scream if it
            # happens anyway.
            self.logger.printf(
                "mesh replay DID NOT DISPATCH (initiator may hang): %r",
                {k: v for k, v in payload.items() if k != "_calls"},
            )
            return
        err: list = []

        def _get():
            try:
                jax.device_get(dev)
            except Exception as e:  # noqa: BLE001
                err.append(e)

        waiter = threading.Thread(target=_get, daemon=True)
        waiter.start()
        waiter.join(self.MESH_REPLAY_TIMEOUT)
        if waiter.is_alive():
            self.logger.printf(
                "mesh replay collective STUCK >%ss (peer missing from "
                "rendezvous?): %r",
                self.MESH_REPLAY_TIMEOUT,
                {k: v for k, v in payload.items() if k != "_calls"},
            )
        elif err:
            self.logger.printf("mesh replay readback failed: %s", err[0])

    def _mesh_replay_dispatch(self, payload: dict):
        """Enter the same fused dispatch the initiator described; returns
        the device result (or None when nothing dispatched)."""
        eng = self.mesh_engine
        kind = payload["kind"]
        index = payload["index"]
        shards = payload.get("shards")
        if shards is None:
            idx = self.holder.index(index)
            shards = [int(s) for s in idx.available_shards()] if idx else []

        def call_of(key):
            return payload["_calls"].get(key)  # parsed at accept time

        if kind == "count":
            return eng.count_async(index, call_of("query"), shards, broadcast=False)
        if kind == "eval":
            stack, _ = eng.bitmap_stack(
                index, call_of("query"), shards, broadcast=False
            )
            # The replay only needs to JOIN the collective, not consume
            # the bitmap: wait on a 4-byte dependent slice instead of
            # pulling the whole replicated [S, WORDS] stack to host on
            # every peer (that's index-sized traffic per query).
            return None if stack is None else stack[0, 0]
        if kind == "count_batch":
            return eng.count_many_async(
                index,
                payload["_batch_calls"],
                payload["shardsList"],
                broadcast=False,
            )
        if kind == "sum":
            res = eng.sum_async(
                index, payload["field"], call_of("filter"), shards, broadcast=False
            )
            return None if res is None else res[0]
        if kind == "minmax":
            res = eng.min_max_async(
                index, payload["field"], call_of("filter"), shards,
                payload["isMin"], broadcast=False,
            )
            return None if res is None else res[0]
        if kind == "topn":
            res = eng.topn_full_async(
                index, payload["field"], call_of("src"), shards,
                payload["n"], payload["minThreshold"],
                row_ids=payload.get("rowIds"), broadcast=False,
                replay_cands=payload["cands"],
            )
            return None if res is None else res[2]
        if kind == "topn_scores":
            res = eng.topn_scores_async(
                index, payload["field"], payload["rows"], call_of("src"),
                shards, broadcast=False,
            )
            return None if res is None else res[0]
        if kind == "group":
            res = eng.group_counts_async(
                index, payload["fields"], payload["rows"], call_of("filter"),
                shards, broadcast=False,
                aggregate=payload.get("aggregate"),
                traced=payload.get("traced"),
            )
            return None if res is None else res[0]
        raise ApiError(f"unknown collective kind: {kind}")

    def translate_keys(self, index: str, field: str, keys: List[str]) -> List[int]:
        if field:
            return self.translate_store.translate_rows_to_uint64(index, field, keys)
        return self.translate_store.translate_columns_to_uint64(index, keys)

    # -- internals ----------------------------------------------------------

    def _broadcast(self, msg: dict):
        if self.cluster is not None:
            self.cluster.send_sync(msg)


def _attr_diff(store, blocks: List[dict]) -> Dict[int, dict]:
    """Attrs in local blocks whose checksums differ from the peer's
    (api.go:689-786)."""
    peer = {b["id"]: bytes.fromhex(b["checksum"]) for b in blocks}
    out: Dict[int, dict] = {}
    for blk, digest in store.blocks():
        if peer.get(blk) == digest:
            continue
        out.update(store.block_data(blk))
    return out

"""The query engine: PQL call dispatch + per-shard kernels + shard reduce.

Re-design of the reference's executor (executor.go:84-2890) for TPU:

- Per-call dispatch mirrors executeCall (executor.go:256-295).
- Per-shard work runs as device kernels over the fragment's dense HBM
  matrix (ops.bitops / ops.bsi) instead of roaring container loops.
- ``map_reduce`` is the seam the cluster layer plugs into: shards are
  grouped by owning node (single-node: all local), local shards execute
  as batched device work, remote nodes receive the serialized call
  (executor.go mapReduce :2183-2321).

Results use the same shapes as the reference: Row for bitmap calls,
ValCount for Sum/Min/Max, (id, count) pair lists for TopN, RowIdentifiers
for Rows, GroupCount list for GroupBy (a GroupColumns, the same sequence
held as columns, where the device counted), bool for mutations.
"""

from __future__ import annotations

import datetime as dt
import functools
import itertools
import math
import threading
import time
from collections import OrderedDict
from collections.abc import Sequence
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from ..util.stats import (
    GROUP_RESULT_FORMS,
    METRIC_EXECUTOR_GROUP_RESULTS,
    METRIC_QUERY_OP,
    METRIC_REPLICA_READS,
    REGISTRY,
)

# Per-op histogram handles, cached so the dispatch path never takes the
# global registry lock (GIL-atomic dict ops; a racing first-call for the
# same op resolves to the same registry series either way).
_OP_HISTS: Dict[str, object] = {}


def _op_hist(op: str):
    h = _OP_HISTS.get(op)
    if h is None:
        h = _OP_HISTS[op] = REGISTRY.histogram(
            METRIC_QUERY_OP,
            help="Per-PQL-op execution latency (seconds)",
            op=op,
        )
    return h


# GroupBy results by the form they left the executor in: handles of the
# series stats.py registers at import.
_GROUP_RESULTS = {
    form: REGISTRY.counter(METRIC_EXECUTOR_GROUP_RESULTS, form=form)
    for form in GROUP_RESULT_FORMS
}

from .. import ops, pql
from ..parallel.errors import PeerlessMeshError
from ..util import plans as plans_mod
from ..util import tracing as tracing_mod
from ..core.field import FIELD_TYPE_BOOL, FIELD_TYPE_INT, FIELD_TYPE_MUTEX, FIELD_TYPE_SET, FIELD_TYPE_TIME
from ..core.fragment import SHARD_WIDTH
from ..core import cache as cache_mod
from ..core import fragment as frag_mod
from ..core import timequantum
from ..core.row import Row
from ..core.view import VIEW_STANDARD, view_bsi_name
from ..pql import BETWEEN, EQ, GT, GTE, LT, LTE, NEQ, Call, Condition, Query

TIME_FORMAT = "%Y-%m-%dT%H:%M"  # pilosa.TimeFormat

# Call name -> the executor lane that takes a run of consecutive calls
# as a whole (Executor._execute): calls of one lane form one run.
_RUN_LANES = {
    "Count": "_mesh_count_many",
    "Sum": "_mesh_aggregate_run",
    "Min": "_mesh_aggregate_run",
    "Max": "_mesh_aggregate_run",
}

DEFAULT_MIN_THRESHOLD = 1
DEFAULT_FIELD = "general"
DEFAULT_MAX_WRITES_PER_REQUEST = 5000


class Error(Exception):
    pass


class IndexNotFoundError(Error):
    pass


class FieldNotFoundError(Error):
    pass


class ExecOptions:
    """executor.go execOptions."""

    __slots__ = (
        "remote",
        "exclude_row_attrs",
        "exclude_columns",
        "column_attrs",
        "replica_read",
        "freshness_ms",
    )

    def __init__(
        self,
        remote: bool = False,
        exclude_row_attrs: bool = False,
        exclude_columns: bool = False,
        column_attrs: bool = False,
        replica_read: str = "",
        freshness_ms: Optional[float] = None,
    ):
        self.remote = remote
        self.exclude_row_attrs = exclude_row_attrs
        self.exclude_columns = exclude_columns
        self.column_attrs = column_attrs
        # Per-request replica-read override (X-Pilosa-Replica-Read):
        # "" defers to the cluster's configured [cluster] replica-read.
        self.replica_read = replica_read
        # Per-request freshness bound for ``bounded`` mode
        # (X-Pilosa-Freshness-Ms); None defers to [cluster] freshness-ms.
        self.freshness_ms = freshness_ms

    def copy(self) -> "ExecOptions":
        return ExecOptions(
            self.remote,
            self.exclude_row_attrs,
            self.exclude_columns,
            self.column_attrs,
            self.replica_read,
            self.freshness_ms,
        )


class ValCount:
    """Sum/Min/Max result (executor.go ValCount :2652-2696)."""

    __slots__ = ("val", "count")

    def __init__(self, val: int = 0, count: int = 0):
        self.val = val
        self.count = count

    def add(self, other: "ValCount") -> "ValCount":
        return ValCount(self.val + other.val, self.count + other.count)

    def smaller(self, other: "ValCount") -> "ValCount":
        if self.count == 0 or (other.val < self.val and other.count > 0):
            return other
        return ValCount(self.val, self.count)

    def larger(self, other: "ValCount") -> "ValCount":
        if self.count == 0 or (other.val > self.val and other.count > 0):
            return other
        return ValCount(self.val, self.count)

    def __eq__(self, other):
        return (
            isinstance(other, ValCount)
            and self.val == other.val
            and self.count == other.count
        )

    def __repr__(self):
        return f"ValCount(val={self.val}, count={self.count})"

    def to_dict(self):
        return {"value": self.val, "count": self.count}


class FieldRow:
    """One (field, row) of a GroupBy group (executor.go:976-1001)."""

    __slots__ = ("field", "row_id", "row_key")

    def __init__(self, field: str, row_id: int = 0, row_key: str = ""):
        self.field = field
        self.row_id = row_id
        self.row_key = row_key

    def __eq__(self, other):
        return (
            isinstance(other, FieldRow)
            and self.field == other.field
            and self.row_id == other.row_id
            and self.row_key == other.row_key
        )

    def __repr__(self):
        return f"FieldRow({self.field}.{self.row_key or self.row_id})"

    def to_dict(self):
        if self.row_key:
            return {"field": self.field, "rowKey": self.row_key}
        return {"field": self.field, "rowID": self.row_id}


class GroupCount:
    """One group of a GroupBy reply.  ``sum`` is None unless the call
    gave ``aggregate=Sum(field=...)``: then it is the sum of that field
    over the group's columns that have a value."""

    __slots__ = ("group", "count", "sum")

    def __init__(self, group: List[FieldRow], count: int,
                 sum: Optional[int] = None):
        self.group = group
        self.count = count
        self.sum = sum

    def compare(self, other: "GroupCount") -> int:
        """Order by row ids, field-major (executor.go Compare :1043)."""
        for a, b in zip(self.group, other.group):
            if a.row_id < b.row_id:
                return -1
            if a.row_id > b.row_id:
                return 1
        return 0

    def __eq__(self, other):
        return (
            isinstance(other, GroupCount)
            and self.group == other.group
            and self.count == other.count
            and self.sum == other.sum
        )

    def __repr__(self):
        if self.sum is None:
            return f"GroupCount({self.group}, count={self.count})"
        return f"GroupCount({self.group}, count={self.count}, sum={self.sum})"

    def to_dict(self):
        d = {"group": [g.to_dict() for g in self.group], "count": self.count}
        if self.sum is not None:
            d["sum"] = self.sum
        return d


class GroupAxes:
    """What a GroupBy's count tensor is indexed by: the grouped fields'
    names and, a field, the sorted vector of its row ids; combination
    ``i`` of the row-major product is one possible group.  The executor
    keeps one a (index, fields, shards) for as long as it keeps the row
    vectors (``Executor._group_axes``), so what a consumer derives from
    the axes alone can stay with them: ``reply_texts`` is the JSON reply
    encoder's (net/wire.py), None until it has filled it.  Axes that a
    ``Rows`` child's ``previous`` / ``limit`` / ``column`` cut for one
    request are made for that request and not ``kept``: nothing is
    written on them."""

    __slots__ = ("fields", "rows", "shape", "size", "reply_texts", "kept")

    def __init__(self, fields, rows, kept=True):
        self.fields = tuple(fields)
        self.rows = tuple(rows)
        self.shape = tuple(len(vec) for vec in self.rows)
        self.size = math.prod(self.shape)  # combinations
        self.reply_texts = None
        self.kept = kept


class GroupColumns(Sequence):
    """A GroupBy result over row ids, held as columns: group ``i`` is
    combination ``flat[i]`` of ``axes`` (``rows[d][i]`` is its row of
    ``fields[d]``), counted ``counts[i]`` times and, under
    ``aggregate=Sum(...)``, summing to ``sums[i]`` (``sums`` is None
    without the argument); ``flat``, ``counts`` and ``sums`` are integer
    vectors of one length, in the nested-iterator order.
    What the device path hands out: the reply encoder formats the
    vectors as they are (net/wire.py), and slicing cuts them.  To
    everything else it is the ``GroupCount`` list of the same groups:
    the first ``__iter__`` or ``__getitem__(int)`` builds that list,
    once, and from then on the objects are the result (they can be
    written to: key translation, a merge), so ``objects`` is what an
    encoder tests before it trusts the columns.  A field with keys never
    stays columnar: ``translate`` turns it into the list."""

    __slots__ = ("axes", "flat", "counts", "sums", "objects")

    def __init__(self, axes: GroupAxes, flat, counts, objects=None, sums=None):
        self.axes = axes
        self.flat = flat
        self.counts = counts
        self.sums = sums
        self.objects: Optional[List[GroupCount]] = objects

    @property
    def fields(self):
        return self.axes.fields

    @property
    def rows(self):
        """One vector of row ids a field."""
        ix = np.unravel_index(self.flat, self.axes.shape)
        return [vec[i] for vec, i in zip(self.axes.rows, ix)]

    def _materialise(self) -> List[GroupCount]:
        if self.objects is None:
            _GROUP_RESULTS["objects"].inc()
            cols = []
            for f, col in zip(self.fields, self.rows):
                col = col.tolist()
                # One FieldRow a (field, row), shared by its groups.
                frs = {r: FieldRow(f, r) for r in set(col)}
                cols.append([frs[r] for r in col])
            sums = (
                itertools.repeat(None) if self.sums is None
                else self.sums.tolist()
            )
            self.objects = [
                GroupCount(list(group), n, s)
                for group, n, s in zip(zip(*cols), self.counts.tolist(), sums)
            ]
        return self.objects

    def __len__(self):
        return len(self.counts)

    def __getitem__(self, i):
        if isinstance(i, slice):
            return GroupColumns(
                self.axes,
                self.flat[i],
                self.counts[i],
                None if self.objects is None else self.objects[i],
                None if self.sums is None else self.sums[i],
            )
        return self._materialise()[i]

    def __iter__(self):
        return iter(self._materialise())

    def __eq__(self, other):
        if not isinstance(other, (list, GroupColumns)):
            return NotImplemented
        return self._materialise() == list(other)

    __hash__ = None

    def __repr__(self):
        return f"GroupColumns({list(self.fields)}, groups={len(self)})"


class RowIdentifiers:
    """Rows() result (executor.go:822-827)."""

    __slots__ = ("rows", "keys")

    def __init__(self, rows: List[int], keys: Optional[List[str]] = None):
        self.rows = rows
        self.keys = keys or []

    def __eq__(self, other):
        return (
            isinstance(other, RowIdentifiers)
            and self.rows == other.rows
            and self.keys == other.keys
        )

    def __repr__(self):
        return f"RowIdentifiers(rows={self.rows}, keys={self.keys})"

    def to_dict(self):
        d = {"rows": self.rows}
        if self.keys:
            d["keys"] = self.keys
        return d


class ColumnAttrSet:
    __slots__ = ("id", "key", "attrs")

    def __init__(self, id: int, attrs: dict, key: str = ""):
        self.id = id
        self.attrs = attrs
        self.key = key

    def to_dict(self):
        d = {"id": self.id, "attrs": self.attrs}
        if self.key:
            d = {"key": self.key, "attrs": self.attrs}
        return d


class QueryResponse:
    __slots__ = ("results", "column_attr_sets", "trace_id", "plan")

    def __init__(self, results=None, column_attr_sets=None):
        self.results = results if results is not None else []
        self.column_attr_sets = column_attr_sets
        # Stamped by the API layer when tracing is on, surfaced as the
        # response's "traceID" so clients can join /debug/traces.
        self.trace_id: Optional[str] = None
        # The recorded QueryPlan dict when the request asked ?profile=1
        # (util/plans.py), surfaced as the response's "plan".
        self.plan: Optional[dict] = None


def _merge_row_ids(a: List[int], b: List[int], limit: int) -> List[int]:
    """Sorted-unique merge with limit (executor.go RowIDs.merge :833)."""
    out: List[int] = []
    i = j = 0
    while i < len(a) and j < len(b) and len(out) < limit:
        if a[i] < b[j]:
            out.append(a[i])
            i += 1
        elif a[i] > b[j]:
            out.append(b[j])
            j += 1
        else:
            out.append(a[i])
            i += 1
            j += 1
    while i < len(a) and len(out) < limit:
        out.append(a[i])
        i += 1
    while j < len(b) and len(out) < limit:
        out.append(b[j])
        j += 1
    return out


def _merge_group_counts(
    a: List[GroupCount], b: List[GroupCount], limit: int
) -> List[GroupCount]:
    """executor.go mergeGroupCounts :1013."""
    limit = min(limit, len(a) + len(b))
    out: List[GroupCount] = []
    i = j = 0
    while i < len(a) and j < len(b) and len(out) < limit:
        c = a[i].compare(b[j])
        if c < 0:
            out.append(a[i])
            i += 1
        elif c == 0:
            a[i].count += b[j].count
            if a[i].sum is not None and b[j].sum is not None:
                a[i].sum += b[j].sum
            out.append(a[i])
            i += 1
            j += 1
        else:
            out.append(b[j])
            j += 1
    while i < len(a) and len(out) < limit:
        out.append(a[i])
        i += 1
    while j < len(b) and len(out) < limit:
        out.append(b[j])
        j += 1
    return out


_MAXINT = (1 << 63) - 1
_NO_ROWS = np.empty(0, dtype=np.uint64)

_WRITE_CALLS = {"Set", "Clear", "SetRowAttrs", "SetColumnAttrs", "Store", "ClearRow"}

# Write calls that REMOVE bits (directly, or by overwriting a row):
# these must never ack in DEGRADED mode — anti-entropy's majority-tie-
# to-set merge re-SETS the removed bits when the dead owner recovers
# still holding them, silently undoing the acked write
# (docs/durability.md "Writes under failure").
_DESTRUCTIVE_CALLS = {"Clear", "ClearRow", "Store"}


def _call_cacheable(c: Call) -> bool:
    """True when a parsed call can be safely reused across executions:
    read-only and free of string/bool args anywhere in the tree (key
    translation rewrites those in place, executor/translate.py:67-98)."""
    if c.name in _WRITE_CALLS:
        return False
    for v in c.args.values():
        if isinstance(v, (str, bool)):
            return False
        if isinstance(v, list) and any(isinstance(x, (str, bool)) for x in v):
            return False
        if isinstance(v, Condition) and isinstance(v.value, (str, bool)):
            return False
    return all(_call_cacheable(ch) for ch in c.children)


class _QueryFuture:
    """Future for a deferred all-Count query (Executor.execute_async):
    resolves to a QueryResponse once every batched item lands.  On ANY
    item error it falls back to a full synchronous re-execution on a
    fresh thread — the sync path has per-call fallbacks (host path on
    unlowerable argument shapes, peerless meshes) the pipeline skips,
    so an async error must converge to the sync answer, not surface an
    error the sync path wouldn't have returned.  The fallback thread is
    fresh, never a batcher collect worker: re-executing there could
    block the pool that resolves other batches."""

    __slots__ = (
        "_executor",
        "_index",
        "_query",
        "_shards",
        "_opt",
        "_slots",
        "_items",
        "_event",
        "_response",
        "_error",
        "_callbacks",
        "_cb_lock",
        "_draining",
        "_pending",
        "_lock",
        "trace_span",
        "query_plan",
    )

    def __init__(self, executor, index, query, shards, opt, slots, items):
        self.trace_span = None  # set by api.query_async for stamping
        self.query_plan = None  # set by api.query_async (util/plans.py)
        self._executor = executor
        self._index = index
        self._query = query
        self._shards = shards
        self._opt = opt
        self._slots = slots
        self._items = items  # [(result slot, batcher _Item), ...]
        self._event = threading.Event()
        self._response: Optional[QueryResponse] = None
        self._error: Optional[BaseException] = None
        self._callbacks: list = []
        self._cb_lock = threading.Lock()
        self._draining = False
        self._pending = len(items)
        self._lock = threading.Lock()
        if not items:
            self._finish_ok()  # every call hit the O(1) lane
        else:
            for _k, it in items:
                it.add_done_callback(self._item_done)

    def _item_done(self, _item):
        with self._lock:
            self._pending -= 1
            if self._pending > 0:
                return
        if any(it.error is not None for _k, it in self._items):
            threading.Thread(
                target=self._fallback, daemon=True, name="query-fallback"
            ).start()
            return
        for k, it in self._items:
            self._slots[k] = it.result
        self._finish_ok()

    def _finish_ok(self):
        self._response = QueryResponse(list(self._slots))
        self._resolve()

    def _fallback(self):
        try:
            self._response = self._executor.execute(
                self._index, self._query, self._shards, self._opt
            )
        except BaseException as e:  # noqa: BLE001
            self._error = e
        self._resolve()

    def _resolve(self):
        self._event.set()
        # FIFO drain under _cb_lock: registration order is completion
        # order, so api.query_async's _finish — which stamps and
        # records the query plan — runs BEFORE the HTTP layer's payload
        # callback that may embed that plan (?profile=1).  The
        # _draining flag closes the race where a late registrant sees
        # the event set while an earlier callback is still mid-flight
        # on this thread and would otherwise run itself inline ahead of
        # it; callbacks themselves run OUTSIDE the lock.
        while True:
            with self._cb_lock:
                if not self._callbacks:
                    self._draining = False
                    break
                self._draining = True
                fn = self._callbacks.pop(0)
            try:
                fn(self)
            except Exception:  # noqa: BLE001
                pass

    def done(self) -> bool:
        return self._event.is_set()

    @property
    def t_decoded(self) -> Optional[float]:
        """When the last drain that answered an item of this query had
        decoded it (None where no drain did): the start of the request's
        ``complete_wait`` (util/tracing.RequestClock)."""
        times = [it.t_decoded for _k, it in self._items if it.t_decoded is not None]
        return max(times) if times else None

    def add_done_callback(self, fn):
        """Run ``fn(self)`` on resolution — immediately when already
        resolved AND fully drained; if the resolver is still draining
        earlier callbacks, enqueue behind them instead (ordering is the
        ?profile=1 contract: the plan recorder registered first must
        finish before the payload encoder reads the plan)."""
        with self._cb_lock:
            if not self._event.is_set() or self._draining:
                self._callbacks.append(fn)
                return
        fn(self)

    def result(self, timeout: Optional[float] = None) -> QueryResponse:
        if not self._event.wait(
            timeout if timeout is not None else 310.0
        ):
            raise Error("deferred query timed out (pipeline wedged?)")
        if self._error is not None:
            raise self._error
        return self._response


class Executor:
    """Single-node query executor; the cluster layer overrides ``_mapper``
    routing (executor.go:34-60)."""

    def __init__(
        self,
        holder,
        cluster=None,
        node=None,
        client=None,
        translator=None,
        max_writes_per_request: int = DEFAULT_MAX_WRITES_PER_REQUEST,
        stats=None,
        tracer=None,
        mesh_engine=None,
    ):
        self.holder = holder
        self.cluster = cluster
        self.node = node
        self.client = client
        self.translator = translator
        self.max_writes_per_request = max_writes_per_request
        # Optional fused device path (parallel.MeshEngine): local shards of
        # supported read calls execute as one sharded dispatch instead of
        # the per-shard python loop.
        self.mesh_engine = mesh_engine
        from ..util.stats import NopStatsClient
        from ..util.tracing import NopTracer

        self.stats = stats if stats is not None else NopStatsClient()
        self.tracer = tracer if tracer is not None else NopTracer()
        # Pre-register the core op series so /metrics exposes it from
        # boot (Counts routed through the batch pipeline are timed by
        # the pipeline-stage series, not this one).
        _op_hist("Count")
        # Parsed-query LRU: a hot query stream re-sends the same PQL text,
        # and for the O(1) small-query path the parse would dominate.
        # Only side-effect-free numeric read queries are cached (string/
        # bool args are rewritten in place by key translation, and write
        # calls must re-validate per execution).
        self._parse_cache: "OrderedDict[str, Query]" = OrderedDict()
        self._parse_lock = threading.Lock()
        # (index, query-text) -> Row Call | False: prepared plans for the
        # O(1) Count(Row) lane (False = checked, not eligible).
        self._fast_plans: "OrderedDict[Tuple[str, str], object]" = OrderedDict()
        # index -> (shard_epoch, default shard list): available_shards()
        # walks every field's bitmap, too slow for the O(1) lane.
        self._fast_shards: Dict[str, Tuple[int, List[int]]] = {}
        # Identical concurrent aggregate queries collapse into ONE fused
        # dispatch (parallel/singleflight.py): readback round trips
        # serialize in the transport, so N clients asking the same
        # TopN/Sum simultaneously must not burn N slots for one answer.
        from ..parallel.singleflight import SingleFlight

        self._sflight = SingleFlight()
        self._group_rows_cache: Dict[tuple, tuple] = {}
        self._group_axes_cache: Dict[tuple, GroupAxes] = {}
        # Remote fan-out tally: one per peer RPC issued by the mapper.
        # With capacity-weighted ownership (cluster.place_partition) a
        # query whose shards are all locally owned must leave this at 0
        # — the fused mesh dispatch's psum IS the reduce (docs/mesh.md);
        # tests assert on it alongside the client-level
        # pilosa_cluster_remote_calls_total counter.
        self.remote_fanouts = 0

    _PARSE_CACHE_MAX = 512

    def _parse_cached(self, s: str) -> Query:
        """PQL text -> calls: the ``parse`` stage, parse-cache hits
        included and tagged."""
        with tracing_mod.stage("parse", cache="hit") as st:
            with self._parse_lock:
                q = self._parse_cache.get(s)
                if q is not None:
                    self._parse_cache.move_to_end(s)
                    return q
            st.tags["cache"] = "miss"
            q = pql.parse(s)
            if all(_call_cacheable(c) for c in q.calls):
                with self._parse_lock:
                    self._parse_cache[s] = q
                    while len(self._parse_cache) > self._PARSE_CACHE_MAX:
                        self._parse_cache.popitem(last=False)
            return q

    # -- entry point (executor.go Execute :84) -----------------------------

    def execute(
        self,
        index: str,
        query,
        shards: Optional[List[int]] = None,
        opt: Optional[ExecOptions] = None,
    ) -> QueryResponse:
        # The ``plan`` stage is the executor's own host time: this call
        # minus the stages inside it (parse, and whatever lane each call
        # takes through the batcher).
        with tracing_mod.stage("plan", self_time=True):
            return self._execute_entry(index, query, shards, opt)

    def _execute_entry(self, index, query, shards, opt) -> QueryResponse:
        # O(1) small-query lane: a bare Count(Row(f=n)) on a single node
        # answers from maintained row cardinalities without touching the
        # dispatch stack (reference analogue: summing roaring container
        # ``n`` fields instead of materializing the row).
        if (
            opt is None
            and self.cluster is None
            and self.translator is None
            and isinstance(query, str)
        ):
            resp, parsed = self._execute_fast_count(index, query, shards)
            if resp is not None:
                return resp
            if parsed is not None:
                query = parsed  # don't re-parse on the outer path
        with self.tracer.start_span("executor.Execute", index=index):
            return self._execute_outer(index, query, shards, opt)

    # -- deferred execution (pipelined serving) ----------------------------

    def execute_async(self, index, query, shards=None, opt=None):
        """Deferred execution for all-Count queries: every Count is
        either answered from the O(1) cardinality lane or queued into
        the engine's bounded batch pipeline, and a future
        (result/add_done_callback) is returned WITHOUT waiting for the
        device.  Returns None when the query isn't eligible — the
        caller runs the synchronous ``execute`` path.  This is the seam
        the HTTP layer uses to stop parking a handler thread per
        in-flight query: completion callbacks resolve pending responses
        when the fused batch's readback lands."""
        with tracing_mod.stage("plan", self_time=True) as st:
            fut = self._execute_async(index, query, shards, opt)
            if fut is not None:
                st.path = "deferred"
        return fut

    def _execute_async(self, index, query, shards, opt):
        eng = self.mesh_engine
        if eng is None or eng._peerless_multiproc:
            return None
        if opt is not None and (opt.remote or opt.column_attrs):
            return None
        try:
            if isinstance(query, str):
                query = self._parse_cached(query)
        except Exception:  # noqa: BLE001 — sync path surfaces the error
            return None
        calls = query.calls
        if not calls or any(
            c.name != "Count" or len(c.children) != 1 for c in calls
        ):
            return None
        idx = self.holder.index(index)
        if idx is None:
            return None  # sync path raises IndexNotFoundError
        opt = opt or ExecOptions()
        try:
            if not opt.remote and self.translator is not None:
                # In-place key->id rewrite, same as the sync prologue
                # (idempotent: a later sync fallback re-translates ints
                # as no-ops).  translate_results is safely skipped:
                # Count results are plain ints, never key-translated.
                self.translator.translate_calls(index, idx, calls)
            if not shards:
                shards = self._default_shards(index) or [0]
            if self.cluster is not None:
                local = set(self._local_shards(index, shards, opt.remote))
                if any(s not in local for s in shards):
                    return None  # remote shards: the sync mapper splits
            children = [c.children[0] for c in calls]
            if not all(eng.lowerable(ch) for ch in children):
                return None
            # Two passes: probe every fast-lane answer FIRST, so a late
            # surprise in this (fallible, host-side) pass aborts to the
            # sync path with ZERO batcher items enqueued — bailing after
            # an enqueue would orphan in-flight device work and execute
            # the query twice.  The second pass is queue appends only.
            slots: list = [None] * len(calls)
            for k, ch in enumerate(children):
                slots[k] = self._count_from_cardinalities(
                    index, ch, shards, opt.remote
                )
        except Exception:  # noqa: BLE001 — any surprise: sync path decides
            return None
        items = [
            (k, eng.batched_count_async(index, ch, shards))
            for k, ch in enumerate(children)
            if slots[k] is None
        ]
        self.stats.count("Count", len(calls), tags=[f"index:{index}"])
        return _QueryFuture(self, index, query, shards, opt, slots, items)

    def memo_counts(self, index, query: str):
        """Serving-boundary memo lane: the list of counts when EVERY
        top-level Count of ``query`` hits the engine's versioned result
        memo against the index's full shard set, else None (the caller
        runs the full deferred path).  This is what the process-mode
        device-owner answers a repeat dashboard query with — parse-cache
        hit + memo lookups, no executor machinery, no batcher touch —
        so the single device-owner GIL spends its microseconds only on
        queries that need the device.  Correctness matches the batcher's
        memo fast path exactly: the key carries the version token of
        every referenced view, so any write re-keys its readers
        (engine._memo_key).  Hit counters move only when the lane
        answers; a partial hit falls through and the full path counts
        its own probes."""
        eng = self.mesh_engine
        if (
            eng is None
            or self.cluster is not None
            or self.translator is not None
            or getattr(eng, "memo_probe", None) is None
            or eng._peerless_multiproc
        ):
            return None
        try:
            q = self._parse_cached(query)
            calls = q.calls
            if not calls or any(
                c.name != "Count" or len(c.children) != 1 for c in calls
            ):
                return None
            shards = self._default_shards(index) or [0]
            memo = eng.result_memo
            out = []
            for c in calls:
                key = eng._memo_key(index, c.children[0], shards)
                if key is None:
                    return None
                v = memo.get(key)
                if v is None:
                    return None
                out.append(int(v))
        except Exception:  # noqa: BLE001 — any surprise: full path decides
            return None
        for _ in calls:
            eng._cache_hit("result_memo")
        return out

    def _execute_fast_count(self, index, query, shards):
        """O(1)-lane probe: returns (response, parsed).  ``response`` is
        set when the lane answered; otherwise ``parsed`` (when available)
        lets the caller skip re-parsing.  Eligibility and counting both
        live in _count_from_cardinalities — one implementation for the
        prepared lane and the generic Count path."""
        key = (index, query)
        plan = self._fast_plans.get(key)
        parsed = None
        if plan is None:
            try:
                parsed = self._parse_cached(query)
            except Exception:
                return None, None  # outer path surfaces the parse error
            plan = False
            if (
                len(parsed.calls) == 1
                and parsed.calls[0].name == "Count"
                and len(parsed.calls[0].children) == 1
            ):
                ch = parsed.calls[0].children[0]
                # Structural eligibility is static per query text; field
                # shape/type stays dynamic (checked per execution by
                # _count_from_cardinalities).
                if ch.name == "Row" and not ch.children and len(ch.args) == 1:
                    (row_val,) = ch.args.values()
                    if isinstance(row_val, int) and not isinstance(row_val, bool):
                        plan = ch
            with self._parse_lock:
                self._fast_plans[key] = plan
                while len(self._fast_plans) > self._PARSE_CACHE_MAX:
                    self._fast_plans.popitem(last=False)
        if plan is False:
            return None, parsed
        if not shards:  # same default as _execute: every available shard
            try:
                shards = self._default_shards(index)
            except IndexNotFoundError:
                return None, parsed
        total = self._count_from_cardinalities(index, plan, shards)
        if total is None:
            return None, parsed
        return QueryResponse([total]), parsed

    def _execute_outer(self, index, query, shards, opt):
        if not index:
            raise Error("index required")
        if isinstance(query, str):
            query = self._parse_cached(query)
        idx = self.holder.index(index)
        if idx is None:
            raise IndexNotFoundError(index)
        if (
            self.max_writes_per_request > 0
            and query.write_call_n() > self.max_writes_per_request
        ):
            raise Error("too many writes in a single request")
        opt = opt or ExecOptions()

        if not opt.remote and self.translator is not None:
            self.translator.translate_calls(index, idx, query.calls)

        results = self._execute(index, query, shards, opt)
        resp = QueryResponse(results)

        if opt.column_attrs:
            ids: List[int] = []
            for r in results:
                if isinstance(r, Row):
                    ids = _merge_row_ids(ids, r.columns().tolist(), _MAXINT)
            sets = []
            for cid in ids:
                attrs = idx.column_attr_store.attrs(cid)
                if attrs:
                    sets.append(ColumnAttrSet(cid, attrs))
            if self.translator is not None and idx.keys:
                for col in sets:
                    col.key = self.translator.translate_column_to_string(
                        index, col.id
                    )
                    col.id = 0
            resp.column_attr_sets = sets

        if not opt.remote and self.translator is not None:
            self.translator.translate_results(index, idx, query.calls, results)
        return resp

    def _default_shards(self, index: str) -> List[int]:
        """The index's full available-shard list, cached against
        (shard epoch, field availability versions): available_shards()
        unions one Bitmap per field per call (its np.unique dominated
        the serving tier under load) while the shard set changes only
        on fragment create/remove (epoch) or NodeStatus merges
        (per-field avail_version)."""
        idx = self.holder.index(index)
        if idx is None:
            raise IndexNotFoundError(index)
        token = (
            self.holder.shard_epoch(index),
            sum(f.avail_version for f in idx.fields.values()),
            len(idx.fields),
        )
        cached = self._fast_shards.get(index)
        if cached is not None and cached[0] == token:
            return cached[1]
        shards = [int(s) for s in idx.available_shards()]
        self._fast_shards[index] = (token, shards)
        return shards

    def _execute(self, index, query: Query, shards, opt) -> list:
        needs = any(
            c.name not in ("Set", "Clear", "SetRowAttrs", "SetColumnAttrs")
            for c in query.calls
        )
        if not shards and needs:
            shards = self._default_shards(index)
            if not shards:
                shards = [0]

        # Bulk SetRowAttrs optimization (executor.go:146-149,1995).
        if query.calls and all(c.name == "SetRowAttrs" for c in query.calls):
            return self._execute_bulk_set_row_attrs(index, query.calls, opt)

        # Runs: CONSECUTIVE Count() calls (pql.Query carries Calls []
        # and the reference executes them per request, ast.go:27)
        # evaluate as ONE fused device dispatch, and consecutive
        # Sum/Min/Max calls are dispatched together and read back ONCE —
        # consecutive only, because a write call between two reads must
        # be visible to the second.  A run of one takes the per-call
        # path untouched.
        results: list = []
        i = 0
        n = len(query.calls)
        while i < n:
            j = i + 1
            lane = _RUN_LANES.get(query.calls[i].name)
            if lane is not None and self.mesh_engine is not None:
                while j < n and _RUN_LANES.get(query.calls[j].name) == lane:
                    j += 1
            run = query.calls[i:j]
            batch = None
            if j - i >= 2:
                batch = self._execute_run(lane, index, run, shards, opt)
            if batch is None:
                # A run of one; or the whole run declined (remote
                # shards, an unlowerable tree): execute it per-call ONCE
                # — re-screening every suffix would be O(n^2).
                batch = [
                    self._execute_call(index, c, shards, opt) for c in run
                ]
            results.extend(batch)
            i = j
        return results

    def _execute_run(self, lane: str, index, run, shards, opt):
        """A run of two or more calls of one lane (``_RUN_LANES``)
        through the mesh engine together; the results in call order, or
        None when the lane declines the whole run."""
        names = list(dict.fromkeys(c.name for c in run))
        t0 = time.monotonic()
        with self.tracer.start_span(
            "executor." + "+".join(names), index=index, batch=len(run)
        ):
            batch = getattr(self, lane)(index, run, shards, opt)
        dt = time.monotonic() - t0
        for name in names:
            _op_hist(name).observe(dt)
        return batch

    # -- dispatch (executor.go executeCall :245-295) -----------------------

    def _execute_call(self, index: str, c: Call, shards, opt):
        t0 = time.monotonic()
        try:
            with self.tracer.start_span(f"executor.{c.name}", index=index):
                return self._dispatch_call(index, c, shards, opt)
        finally:
            dt = time.monotonic() - t0
            sp = tracing_mod.current_span()
            _op_hist(c.name).observe(
                dt, exemplar=sp.trace_id if sp is not None else None
            )
            # Per-op plan entry for the host-path ops (TopN, Sum,
            # GroupBy, ...): Count's decision record is stamped by the
            # engine/batcher seam with the real dispatch detail.
            p = plans_mod.current_plan()
            if p is not None and c.name not in ("Count", "Explain"):
                p.note_op(op=c.name, seconds=round(dt, 6))

    def _dispatch_call(self, index: str, c: Call, shards, opt):
        self._validate_call_args(c)
        name = c.name
        # Writes are rejected while the cluster resizes (api.go validate
        # :93: apiQuery/apiImport live in methodsNormal, absent from
        # ClusterStateResizing's set): a write accepted mid-resize could
        # land on a fragment already point-in-time copied to its new
        # owner and vanish when the old copy is cleaned.  Reads keep
        # serving — they route on the pre-resize topology, which is
        # correct until the job completes.
        if (
            name in _WRITE_CALLS
            and self.cluster is not None
            and self.cluster.state == "RESIZING"
        ):
            raise Error("cluster is resizing: writes are rejected")
        self.stats.count(name, 1, tags=[f"index:{index}"])
        if name == "Sum":
            return self._execute_sum(index, c, shards, opt)
        if name == "Min":
            return self._execute_min(index, c, shards, opt)
        if name == "Max":
            return self._execute_max(index, c, shards, opt)
        if name == "Clear":
            return self._execute_clear_bit(index, c, opt)
        if name == "ClearRow":
            return self._execute_clear_row(index, c, shards, opt)
        if name == "Store":
            return self._execute_set_row(index, c, shards, opt)
        if name == "Count":
            return self._execute_count(index, c, shards, opt)
        if name == "Explain":
            return self._execute_explain(index, c, shards, opt)
        if name == "Set":
            return self._execute_set(index, c, opt)
        if name == "SetRowAttrs":
            self._execute_set_row_attrs(index, c, opt)
            return None
        if name == "SetColumnAttrs":
            self._execute_set_column_attrs(index, c, opt)
            return None
        if name == "TopN":
            return self._execute_topn(index, c, shards, opt)
        if name == "Rows":
            return self._execute_rows(index, c, shards, opt)
        if name == "GroupBy":
            return self._execute_group_by(index, c, shards, opt)
        if name == "Options":
            return self._execute_options_call(index, c, shards, opt)
        return self._execute_bitmap_call(index, c, shards, opt)

    def _validate_call_args(self, c: Call):
        ids = c.args.get("ids")
        if ids is not None and not isinstance(ids, list):
            raise Error("ids must be a list")

    @staticmethod
    def _field_arg(c: Call) -> str:
        """field=row argument with the reference's error shape
        (executor.go wraps pql.Call.FieldArg errors per call)."""
        try:
            return c.field_arg()
        except ValueError:
            raise Error(f"{c.name}() argument required: field") from None

    # -- map/reduce over shards (executor.go mapReduce :2183) --------------

    def map_reduce(self, index, shards, call, opt, map_fn, reduce_fn):
        """Per-shard map + reduce (executor.go mapReduce :2183-2321).

        Single-node (or remote re-entry): every shard maps locally.  With
        a cluster, shards group by owning node; remote groups execute the
        serialized call on their peer in one RPC (remoteExec :2142) and
        the partial merges into the same reduce.  A failed peer's shards
        retry on the next replica (executor.go :2216-2231)."""
        if self.cluster is None or opt.remote:
            result = None
            for shard in shards:
                result = reduce_fn(result, map_fn(shard))
            return result
        # Hedge budget shared across the whole fan-out (including
        # recursion after peer failures): a query may re-route its shards
        # past at most replica_n extra peers before erroring — so replica
        # hedging is bounded and can never retry-storm a flapping
        # cluster.  One failed peer consumes one unit regardless of how
        # many shards re-route.
        budget = {"left": max(2, self.cluster.replica_n)}
        return self._mapper(
            index, shards, call, opt, map_fn, reduce_fn, set(), budget
        )

    def _read_route(self, index, shard, owners, call, opt, hinted=None):
        """Pick this shard's execution target among its owners
        (docs/durability.md "Replica reads").  Local ownership always
        wins (zero-hop).  Writes pin to strict replica order — their
        replication fan-out handles owner death explicitly.  For reads,
        DOWN owners are deprioritized (a dead primary must not eat a
        round-trip per query before the hedge kicks in) and the
        configured mode picks among the live ones:

          primary — first live owner in replica order (reference
                    behavior + proactive DOWN skip)
          any     — deterministic per-shard rotation across live owners
                    (replicaN>1 scales reads, not just failover)
          bounded — the ``any`` rotation filtered by the freshness bound
                    (cluster.replica_fresh); no fresh replica -> first
                    live owner."""
        cluster = self.cluster
        me = cluster.node.id
        local = next((n for n in owners if n.id == me), None)
        is_write = call is not None and call.name in _WRITE_CALLS
        if local is not None and not is_write:
            return local  # reads: local ownership always wins (zero-hop)
        alive = [n for n in owners if n.state != "DOWN"]
        if not alive:
            if is_write:
                # No replica can make the ack durable: the same loud
                # failure as _write_replicated — a write must never
                # take the last-resort READ path below (it would count
                # as a read, bypass the destructive gate, and be
                # forwarded to a node the detector says is dead).
                # Unwind earlier shards' hints like every sibling
                # raise: the write fails un-acked.
                self._discard_hinted(hinted)
                raise Error(
                    f"write unavailable: every owner of shard {shard} "
                    f"is DOWN ({', '.join(n.id for n in owners)})"
                )
            # All owners DOWN: the last resort keeps replica order —
            # counted, journaled, and stamped onto the plan so the
            # /debug/plans analyzer can say WHY this read went to a
            # node the failure detector distrusts.
            REGISTRY.inc(METRIC_REPLICA_READS, route="last_resort")
            cluster.journal.append(
                "replica.last_resort", index=index, shard=shard,
                owners=[n.id for n in owners],
            )
            p = plans_mod.current_plan()
            if p is not None:
                p.note_op(
                    op=call.name if call is not None else "read",
                    last_resort=True, shard=shard,
                )
            return owners[0]
        if is_write:
            # The DOWN-owner check runs even when this node owns the
            # shard locally: a write applied here while a CO-owner is
            # DOWN still needs that co-owner's miss queued (or, for
            # destructive calls without a queue, the loud failure) —
            # the pre-hint local-win fast path silently skipped it.
            if call.name in _DESTRUCTIVE_CALLS and len(alive) < len(owners):
                # Hinted handoff (docs/durability.md): the miss queues
                # durably for replay on recovery instead of failing the
                # write — the recovered owner receives the clear BEFORE
                # anti-entropy can merge against it.  Only when the
                # queue cannot absorb it (no manager / overflow /
                # expiry) does this fall back to PR 11's loud failure.
                down = [n for n in owners if n.state == "DOWN"]
                h = self._hint_down_writes(
                    index, shard, down, call, shards=[shard],
                    dedup=hinted, all_or_nothing=True,
                )
                if h < len(down):
                    # The whole call fails un-acked: earlier shards'
                    # hints (routing runs before ANY shard maps, so
                    # nothing has applied) are phantoms — unwind them.
                    self._discard_hinted(hinted)
                    raise Error(
                        f"{call.name} unavailable: an owner of shard "
                        f"{shard} is DOWN, the hint queue could not "
                        "absorb the miss, and a degraded bit-removing "
                        "write would be reverted by anti-entropy on "
                        "its recovery"
                    )
            return local if local is not None else alive[0]
        mode = (opt.replica_read or cluster.replica_read) if opt else (
            cluster.replica_read
        )
        if mode == "any" and len(alive) > 1:
            return alive[shard % len(alive)]
        if mode == "bounded" and len(alive) > 1:
            bound = (
                opt.freshness_ms
                if opt is not None and opt.freshness_ms is not None
                else cluster.freshness_ms
            )
            k = shard % len(alive)
            for n in alive[k:] + alive[:k]:
                if cluster.replica_fresh(n.id, index, bound):
                    return n
        return alive[0]

    def _mapper(
        self, index, shards, call, opt, map_fn, reduce_fn, down_ids, budget
    ):
        by_node = {}
        for s in shards:
            owners = [
                n
                for n in self.cluster.shard_nodes(index, s)
                if n.id not in down_ids
            ]
            if not owners:
                if call is not None and call.name in _WRITE_CALLS:
                    # Same unwind as the sibling raise paths: the
                    # write fails un-acked, so hints queued by earlier
                    # routing/transport handling must not replay.
                    self._discard_hinted(budget.get("hinted"))
                raise Error(f"no available node for shard {s}")
            # The hinted-dedup set rides the shared budget dict: a
            # hedge recursion re-routes shards through _read_route
            # again, and a (node, shard) miss already queued must not
            # be double-queued as a second hint.
            target = self._read_route(
                index, s, owners, call, opt,
                hinted=budget.setdefault("hinted", {}),
            )
            # [target, shards, every-shard-routed-to-its-primary?] —
            # the primary verdict is recorded HERE, where the owners
            # list is already in hand, so the metric label below never
            # recomputes placement.
            entry = by_node.setdefault(target.id, [target, [], True])
            entry[1].append(s)
            entry[2] = entry[2] and owners[0].id == target.id

        result = None
        me = self.cluster.node.id
        # Encode once: the remote fan-out ships the SAME query text to
        # every peer, and str(call) re-serializes the whole tree — O(tree)
        # per node adds up on wide clusters.
        call_text = str(call)
        for node_id, (node, node_shards, is_primary) in sorted(
            by_node.items()
        ):
            if node_id == me:
                for shard in node_shards:
                    result = reduce_fn(result, map_fn(shard))
                continue
            REGISTRY.inc(
                METRIC_REPLICA_READS,
                route="hedge" if down_ids else (
                    "primary" if is_primary else "replica"
                ),
            )
            try:
                self.remote_fanouts += 1
                t_rpc = time.monotonic()
                with self.tracer.start_span(
                    "executor.RemoteQuery", node=node_id, shards=len(node_shards)
                ):
                    doc = self.cluster.client(node).query(
                        index, call_text, shards=node_shards, remote=True
                    )
                p = plans_mod.current_plan()
                if p is not None:
                    # Per-node fan-out latency attribution: the plan's
                    # "which peer was slow" record.
                    p.note_fanout(
                        node_id, time.monotonic() - t_rpc, len(node_shards)
                    )
            except Exception as e:
                # Classify before hedging.  An HTTP ERROR RESPONSE
                # proves the peer's serving plane is up: a 4xx (except
                # 429) is a deterministic request error every replica
                # would repeat — re-raise, don't hide it behind a
                # hedge; a 429/5xx shed hedges to another replica but
                # must NOT mark the node DOWN (one shed from a loaded
                # peer would otherwise exile it — degraded writes,
                # quarantine, holddown — for RECOVERY_HOLDDOWN per
                # occurrence).  404 also hedges without a verdict: a
                # schema-lagged peer may not know the index yet while
                # its replica does.  Only a TRANSPORT failure (no
                # status: refused/reset/timeout) is a failure verdict.
                code = getattr(e, "code", None)
                if (
                    code is not None
                    and 400 <= code < 500
                    and code not in (404, 429)
                ):
                    raise
                if code is None:
                    self.cluster.node_failed(node_id)
                    if call is not None and call.name in _WRITE_CALLS:
                        # A write whose forward died in transport: the
                        # peer may have missed it entirely, and the
                        # recursion below re-routes these shards to
                        # another replica — so the miss must be queued
                        # as a hint NOW (replayed idempotently on
                        # recovery) or a destructive call would leave
                        # the failed owner holding bits anti-entropy
                        # will resurrect.  Unabsorbable destructive
                        # misses fail loudly: the client never got an
                        # ack, so nothing acked can be lost.
                        failed = self.cluster.node_by_id(node_id)
                        dedup = budget.setdefault("hinted", {})
                        h = 0
                        if failed is not None:
                            for s in node_shards:
                                h += self._hint_down_writes(
                                    index, s, [failed], call,
                                    shards=[s], dedup=dedup,
                                    all_or_nothing=(
                                        call.name in _DESTRUCTIVE_CALLS
                                    ),
                                )
                        if (
                            call.name in _DESTRUCTIVE_CALLS
                            and h < len(node_shards)
                        ):
                            # Failing the whole call: unwind every hint
                            # it queued (this group's AND earlier
                            # routing's) — the client gets an error,
                            # so none of them may replay.
                            self._discard_hinted(dedup)
                            raise Error(
                                f"{call.name} unavailable: the forward "
                                f"to {node_id} failed in transport and "
                                "the hint queue could not absorb the "
                                "miss — a partial bit-removing write "
                                "would be reverted by anti-entropy on "
                                "its recovery"
                            ) from e
                budget["left"] -= 1
                if budget["left"] < 0:
                    if call is not None and call.name in _WRITE_CALLS:
                        # Same unwind as the destructive gate: the
                        # write is failing un-acked.
                        self._discard_hinted(budget.get("hinted"))
                    raise Error(
                        f"replica hedge budget exhausted at node "
                        f"{node_id}: {e}"
                    ) from e
                sub = self._mapper(
                    index,
                    node_shards,
                    call,
                    opt,
                    map_fn,
                    reduce_fn,
                    down_ids | {node_id},
                    budget,
                )
                if sub is not None:
                    result = reduce_fn(result, sub)
                continue
            from ..net.wire import result_from_json

            v = result_from_json(call.name, doc["results"][0])
            result = reduce_fn(result, v)
        return result

    # -- bitmap calls ------------------------------------------------------

    def _execute_bitmap_call(self, index, c, shards, opt) -> Row:
        row = self._mesh_bitmap_row(index, c, shards, opt)
        if row is None:

            def map_fn(shard):
                return self._execute_bitmap_call_shard(index, c, shard)

            def reduce_fn(prev, v):
                if prev is None:
                    prev = Row()
                prev.merge(v)
                return prev

            row = self.map_reduce(index, shards, c, opt, map_fn, reduce_fn)
        if row is None:
            row = Row()

        # Attach row attributes for Row() (executor.go:491-530).
        if c.name == "Row":
            if opt.exclude_row_attrs:
                row.attrs = {}
            else:
                idx = self.holder.index(index)
                if idx is not None:
                    field_name = self._field_arg(c)
                    fld = idx.field(field_name)
                    if fld is not None and fld.row_attr_store is not None:
                        row_id, ok = c.uint_arg(field_name)
                        if ok:
                            row.attrs = fld.row_attr_store.attrs(row_id)
        if opt.exclude_columns:
            row.segments = {}
        return row

    def _execute_bitmap_call_shard(self, index, c: Call, shard: int) -> Row:
        name = c.name
        if name == "Row":
            return self._execute_row_shard(index, c, shard)
        if name == "Difference":
            return self._execute_nary_shard(index, c, shard, "difference")
        if name == "Intersect":
            return self._execute_nary_shard(index, c, shard, "intersect")
        if name == "Range":
            return self._execute_range_shard(index, c, shard)
        if name == "Union":
            return self._execute_nary_shard(index, c, shard, "union", empty_ok=True)
        if name == "Xor":
            return self._execute_nary_shard(index, c, shard, "xor", empty_ok=True)
        if name == "Not":
            return self._execute_not_shard(index, c, shard)
        raise Error(f"unknown call: {name}")

    def _execute_row_shard(self, index, c: Call, shard: int) -> Row:
        idx = self.holder.index(index)
        if idx is None:
            raise IndexNotFoundError(index)
        field_name = self._field_arg(c)
        f = idx.field(field_name)
        if f is None:
            raise FieldNotFoundError(field_name)
        row_id, ok = c.uint_arg(field_name)
        if not ok:
            raise Error("Row() must specify a row")
        frag = self.holder.fragment(index, field_name, VIEW_STANDARD, shard)
        if frag is None:
            return Row()
        return frag.row(row_id)

    def _execute_nary_shard(
        self, index, c: Call, shard: int, op: str, empty_ok: bool = False
    ) -> Row:
        if not c.children and not empty_ok:
            raise Error(f"empty {c.name} query is currently not supported")
        other = Row()
        for i, child in enumerate(c.children):
            row = self._execute_bitmap_call_shard(index, child, shard)
            if i == 0:
                other = row
            else:
                other = getattr(other, op)(row)
        return other

    def _execute_not_shard(self, index, c: Call, shard: int) -> Row:
        if len(c.children) != 1:
            raise Error("Not() requires a single input row")
        idx = self.holder.index(index)
        if idx is None:
            raise IndexNotFoundError(index)
        if idx.existence_field() is None:
            raise Error(f"index does not support existence tracking: {index}")
        from ..core.index import EXISTENCE_FIELD_NAME

        frag = self.holder.fragment(index, EXISTENCE_FIELD_NAME, VIEW_STANDARD, shard)
        existence = frag.row(0) if frag is not None else Row()
        row = self._execute_bitmap_call_shard(index, c.children[0], shard)
        return existence.difference(row)

    # -- Range (executor.go :1233-1440) ------------------------------------

    def _execute_range_shard(self, index, c: Call, shard: int) -> Row:
        if c.has_condition_arg():
            return self._execute_bsi_range_shard(index, c, shard)

        field_name = self._field_arg(c)
        idx = self.holder.index(index)
        if idx is None:
            raise IndexNotFoundError(index)
        f = idx.field(field_name)
        if f is None:
            raise FieldNotFoundError(field_name)
        row_id, ok = c.uint_arg(field_name)
        if not ok:
            raise Error("Range() must specify a row")
        start_str = c.args.get("_start")
        end_str = c.args.get("_end")
        if not isinstance(start_str, str):
            raise Error("Range() start time required")
        if not isinstance(end_str, str):
            raise Error("Range() end time required")
        try:
            start = dt.datetime.strptime(start_str, TIME_FORMAT)
            end = dt.datetime.strptime(end_str, TIME_FORMAT)
        except ValueError:
            raise Error("cannot parse Range() time")
        q = f.time_quantum()
        if not q:
            return Row()
        row = Row()
        for view_name in timequantum.views_by_time_range(
            VIEW_STANDARD, start, end, q
        ):
            frag = self.holder.fragment(index, field_name, view_name, shard)
            if frag is None:
                continue
            row = row.union(frag.row(row_id))
        return row

    def _execute_bsi_range_shard(self, index, c: Call, shard: int) -> Row:
        if len(c.args) == 0:
            raise Error("Range(): condition required")
        if len(c.args) > 1:
            raise Error("Range(): too many arguments")
        (field_name, cond), = c.args.items()
        if not isinstance(cond, Condition):
            raise Error(f"Range(): {field_name}: expected condition argument")
        f = self.holder_field(index, field_name)
        bsig = f.bsi_group(field_name)
        if bsig is None:
            raise Error(f"field not found: {field_name}")
        frag = self.holder.fragment(
            index, field_name, view_bsi_name(field_name), shard
        )
        if frag is None:
            return Row()

        import jax.numpy as jnp

        from ..ops import bsi as bsi_ops

        depth = bsig.bit_depth()
        planes = frag.device_planes(depth)

        def wrap(words):
            return Row({shard: words})

        if cond.op == NEQ and cond.value is None:
            # `!= null` (executor.go:1355-1369)
            return wrap(bsi_ops.not_null(planes))
        if cond.op == BETWEEN:
            predicates = cond.int_slice_value()
            if len(predicates) != 2:
                raise Error(
                    "Range(): BETWEEN condition requires exactly two integer values"
                )
            lo, hi, out_of_range = bsig.base_value_between(*predicates)
            if out_of_range:
                return Row()
            if predicates[0] <= bsig.min and predicates[1] >= bsig.max:
                return wrap(bsi_ops.not_null(planes))
            return wrap(
                bsi_ops.range_between(
                    planes,
                    jnp.asarray(bsi_ops.to_bits(lo, depth)),
                    jnp.asarray(bsi_ops.to_bits(hi, depth)),
                )
            )

        if not isinstance(cond.value, int) or isinstance(cond.value, bool):
            raise Error("Range(): conditions only support integer values")
        value = cond.value
        base, out_of_range = bsig.base_value(cond.op, value)
        if out_of_range and cond.op != NEQ:
            return Row()
        # Whole-range LT/GT collapse to the not-null row (executor.go:1420).
        if (
            (cond.op == LT and value > bsig.max)
            or (cond.op == LTE and value >= bsig.max)
            or (cond.op == GT and value < bsig.min)
            or (cond.op == GTE and value <= bsig.min)
        ):
            return wrap(bsi_ops.not_null(planes))
        if out_of_range and cond.op == NEQ:
            return wrap(bsi_ops.not_null(planes))

        bits = jnp.asarray(bsi_ops.to_bits(base, depth))
        if cond.op == EQ:
            return wrap(bsi_ops.range_eq(planes, bits))
        if cond.op == NEQ:
            return wrap(bsi_ops.range_neq(planes, bits))
        if cond.op in (LT, LTE):
            return wrap(bsi_ops.range_lt(planes, bits, cond.op == LTE))
        if cond.op in (GT, GTE):
            return wrap(bsi_ops.range_gt(planes, bits, cond.op == GTE))
        raise Error(f"Range(): unsupported operator {cond.op}")

    def holder_field(self, index: str, field_name: str):
        idx = self.holder.index(index)
        if idx is None:
            raise IndexNotFoundError(index)
        f = idx.field(field_name)
        if f is None:
            raise FieldNotFoundError(field_name)
        return f

    # -- Count / Sum / Min / Max -------------------------------------------

    def _execute_count(self, index, c: Call, shards, opt) -> int:
        if len(c.children) != 1:
            raise Error("Count() requires a single bitmap input")

        fast = self._count_from_cardinalities(
            index, c.children[0], shards, opt.remote
        )
        if fast is not None:
            return fast

        fused = self._mesh_count(index, c.children[0], shards, opt)

        def map_fn(shard):
            row = self._execute_bitmap_call_shard(index, c.children[0], shard)
            return row.count()

        if fused is not None:
            local_shards, fused_count = fused
            # NOTE: the base map_fn stays in force for the remote
            # fan-out below.  A topology change between the fused
            # dispatch and map_reduce (resize mid-query) can re-route a
            # "remote" shard back to THIS node; it was never covered by
            # the fused count (remote excludes local_shards), so the
            # host loop serving it is exact — a raise here failed reads
            # during any resize that raced a fused count.

            remote = [s for s in shards if s not in local_shards]
            if remote:
                p = plans_mod.current_plan()
                if p is not None:
                    p.note_op(
                        op="Count", path="fanout_split",
                        local_shards=len(local_shards),
                        remote_shards=len(remote),
                    )
            result = (
                self.map_reduce(
                    index,
                    remote,
                    c,
                    opt,
                    map_fn,
                    lambda p, v: (p or 0) + v,
                )
                if remote
                else 0
            )
            return (result or 0) + fused_count

        # No fused local dispatch (engine absent, not lowerable, or no
        # locally-owned shards): the whole Count runs through the
        # host-loop / remote fan-out map-reduce.  Record the
        # coordinator-side split so the plan still names a path — the
        # per-peer RPC latencies land via map_reduce's note_fanout.
        p = plans_mod.current_plan()
        if p is not None:
            if self.cluster is not None:
                local = set(self._local_shards(index, shards, opt.remote))
            else:
                local = set(shards)
            n_local = sum(1 for s in shards if s in local)
            n_remote = len(shards) - n_local
            p.note_op(
                op="Count", path="fanout" if n_remote else "host",
                local_shards=n_local, remote_shards=n_remote,
            )
        result = self.map_reduce(
            index, shards, c, opt, map_fn, lambda p, v: (p or 0) + v
        )
        return result or 0

    def _execute_explain(self, index, c: Call, shards, opt) -> dict:
        """``Explain(<query>)``: plan WITHOUT dispatching (the EXPLAIN /
        dry-run half of docs/observability.md "Query plans & cost
        attribution").  Reports the path the real execution would take —
        fast-cardinality lane, memo, occupancy-guided sparse vs dense
        (projected from exact host-side fragment occupancy), or the host
        loop — plus shard locality, touching neither the device nor the
        memo contents."""
        if len(c.children) != 1:
            raise Error("Explain() requires a single query input")
        child = c.children[0]
        doc: dict = {"dryRun": True, "query": str(child)}
        target = child
        if child.name == "Count" and len(child.children) == 1:
            target = child.children[0]
            inner = target
            doc["fastCardinalityEligible"] = bool(
                inner.name == "Row" and not inner.children
                and len(inner.args) == 1
                and isinstance(next(iter(inner.args.values()), None), int)
                and not isinstance(next(iter(inner.args.values()), None), bool)
            )
        if self.cluster is not None:
            local = set(self._local_shards(index, shards, opt.remote))
            doc["localShards"] = sum(1 for s in shards if s in local)
            doc["remoteShards"] = sum(1 for s in shards if s not in local)
        else:
            doc["localShards"] = len(shards)
            doc["remoteShards"] = 0
        eng = self.mesh_engine
        if eng is None:
            doc.update(op=child.name, plannedPath="host", lowerable=False)
            return doc
        doc.update(eng.explain_count(index, target, shards))
        if doc.get("remoteShards"):
            doc["plannedPath"] = f"{doc.get('plannedPath', 'dense')}+fanout"
        return doc

    def _count_from_cardinalities(self, index, child: Call, shards, remote=False):
        """O(1)-per-shard Count of an unfiltered Row: sum the maintained
        per-row cardinalities (rowstore counts) with ZERO device work —
        the analogue of the reference summing roaring container ``n``
        fields (roaring.go Count).  Applies only to a bare
        ``Row(field=id)`` over locally-owned shards; anything with a
        filter tree, time bounds, or remote shards returns None."""
        if child.name != "Row" or child.children or len(child.args) != 1:
            return None
        (field_name, row_val), = child.args.items()
        if isinstance(row_val, bool) or not isinstance(row_val, int):
            return None
        idx = self.holder.index(index)
        f = idx.field(field_name) if idx is not None else None
        if f is None or f.options.type == FIELD_TYPE_INT:
            return None
        if self.cluster is not None:
            local = set(self._local_shards(index, shards, remote))
            if any(s not in local for s in shards):
                return None
        view = f.view(VIEW_STANDARD)
        p = plans_mod.current_plan()
        if p is not None:
            # This lane WILL answer (every gate passed): O(1) host-side
            # cardinality sum, zero device work.
            p.note_op(op="Count", path="fast_cardinality")
        if view is None:
            return 0
        frags = view.fragments  # resolve once, not per shard
        total = 0
        for s in shards:
            frag = frags.get(s)
            if frag is not None:
                total += frag.row_count(row_val)
        return total

    def _mesh_count(self, index, child: Call, shards, opt):
        """Fused Count over the local shard set via the mesh engine;
        returns (local_shards, count) or None when unsupported."""
        if self.mesh_engine is None:
            return None
        local = self._local_shards(index, shards, opt.remote)
        if not local:
            return None
        try:
            return set(local), self.mesh_engine.batched_count(index, child, local)
        except PeerlessMeshError:
            # Multi-process mesh with no peer broadcast configured:
            # the per-shard path is the correct fallback.
            return None
        except ValueError:
            # Unsupported call shape: fall back to the per-shard path.
            return None

    def _mesh_bitmap_row(self, index, c, shards, opt):
        """Fused bitmap materialization on a MULTI-PROCESS mesh: the
        eval collective replays on peers and the result all-gathers back
        (engine.bitmap_stack's replicated path), so row-materializing
        queries no longer fall back to the host loop there (r3 VERDICT
        missing #1).  Single-process keeps the host per-shard path —
        segments already live on this host, and the host loop avoids a
        device round-trip.  Returns a Row, or None to fall back."""
        eng = self.mesh_engine
        if eng is None or not eng.multiproc or opt.remote:
            return None
        if not eng.lowerable(c):
            return None
        if self.cluster is not None:
            local = set(self._local_shards(index, shards))
            if any(s not in local for s in shards):
                return None
        try:
            return eng.bitmap_row(index, c, shards)
        except (ValueError, PeerlessMeshError):
            # Claim any half-written dispatch note (e.g. the residency
            # layer's host_fallback stamp) so it cannot merge into the
            # NEXT query's plan on this pooled thread (the hazard
            # documented at _mesh_count_many's finally).
            plans_mod.take_dispatch_note()
            return None  # unsupported argument shape / peer outage: host path

    def _mesh_count_many(self, index, calls, shards, opt):
        """A run of consecutive Count() calls as ONE batched fused
        dispatch (engine.count_many); per-call O(1) cardinality answers
        are peeled off first.  Returns the list of counts in call order,
        or None to fall back to the per-call path (unsupported shapes,
        remote shards, peerless multi-process mesh)."""
        if self.mesh_engine is None or opt.remote:
            return None
        children = []
        for c in calls:
            if len(c.children) != 1 or not self.mesh_engine.lowerable(
                c.children[0]
            ):
                return None
            children.append(c.children[0])
        if self.cluster is not None:
            local = set(self._local_shards(index, shards))
            if any(s not in local for s in shards):
                return None  # remote shards: the per-call path splits
        results: list = [None] * len(children)
        rem_idx, rem_calls = [], []
        plan = plans_mod.current_plan()
        # Where this query's plan op list stood before the peel: on a
        # decline the per-call fallback re-executes EVERY call (stamping
        # its own fast_cardinality ops), so the peel pass's stamps must
        # be unwound or each peeled Count appears twice in the plan.
        # Safe: the whole batch attempt runs on this one thread and no
        # item of this query is in the batcher yet, so nothing else can
        # have appended ops since the mark.
        ops_mark = len(plan.ops) if plan is not None else 0
        for k, ch in enumerate(children):
            fast = self._count_from_cardinalities(index, ch, shards)
            if fast is not None:
                results[k] = fast
            else:
                rem_idx.append(k)
                rem_calls.append(ch)
        if rem_calls:
            t0 = time.monotonic()
            try:
                try:
                    counts = self.mesh_engine.count_many(
                        index, rem_calls, [list(shards)] * len(rem_calls)
                    )
                finally:
                    # Claim the note on EVERY exit: a half-written note
                    # left in this pooled thread's TLS would be merged
                    # into the next unrelated query's dispatch record.
                    note = plans_mod.take_dispatch_note()
            except (PeerlessMeshError, ValueError):
                if plan is not None:
                    del plan.ops[ops_mark:]
                return None
            # The consecutive-Count batch dispatched on THIS thread:
            # stamp the claimed note once per fused call.  The blocking
            # dispatch+readback is the query's one "execute" stage and
            # its whole device attribution (same accounting as the
            # batcher's direct path).
            elapsed = time.monotonic() - t0
            if plan is not None and note is not None:
                d = plans_mod.rider_note(note, len(rem_calls))
                for _ in rem_calls:
                    plan.note_op(**d)
                plan.note_stage("execute", elapsed)
                plan.note_device_seconds(elapsed)
            for k, v in zip(rem_idx, counts):
                results[k] = v
        self.stats.count("Count", len(calls), tags=[f"index:{index}"])
        return results

    def _bsi_shard_ctx(self, index, c: Call, shard: int):
        """(fragment, bsig, filter_words) for Sum/Min/Max shard kernels."""
        field_name = c.args.get("field")
        if not field_name:
            raise Error(f"{c.name}(): field required")
        if len(c.children) > 1:
            raise Error(f"{c.name}() only accepts a single bitmap input")
        idx = self.holder.index(index)
        f = idx.field(field_name) if idx is not None else None
        if f is None:
            return None
        bsig = f.bsi_group(field_name)
        if bsig is None:
            return None
        frag = self.holder.fragment(
            index, field_name, view_bsi_name(field_name), shard
        )
        if frag is None:
            return None
        import jax.numpy as jnp

        from ..ops import bitops

        if c.children:
            filt = self._execute_bitmap_call_shard(index, c.children[0], shard)
            seg = filt.segment(shard)
            words = (
                jnp.zeros(bitops.WORDS, dtype=jnp.uint32)
                if seg is None
                else jnp.asarray(seg)
            )
        else:
            words = jnp.full(bitops.WORDS, 0xFFFFFFFF, dtype=jnp.uint32)
        return frag, bsig, words

    def _execute_sum(self, index, c: Call, shards, opt) -> ValCount:
        from ..ops import bsi as bsi_ops

        fused = self._mesh_sum(index, c, shards, opt)
        if fused is not None:
            local_shards, fused_vc = fused
            remote = [s for s in shards if s not in local_shards]
            if remote:
                rest = self._execute_sum(index, c, remote, opt)
                fused_vc = fused_vc.add(rest)
            return ValCount() if fused_vc.count == 0 else fused_vc

        def map_fn(shard):
            ctx = self._bsi_shard_ctx(index, c, shard)
            if ctx is None:
                return ValCount()
            frag, bsig, filt = ctx
            depth = bsig.bit_depth()
            counts, n = bsi_ops.sum_counts(frag.device_planes(depth), filt)
            counts = np.asarray(counts)
            total = sum(int(counts[i]) << i for i in range(depth))
            n = int(n)
            return ValCount(total + n * bsig.min, n)

        result = self.map_reduce(
            index, shards, c, opt, map_fn, lambda p, v: (p or ValCount()).add(v)
        )
        result = result or ValCount()
        return ValCount() if result.count == 0 else result

    @staticmethod
    def _aggregate_flight(seq, index, c: Call, local) -> tuple:
        """Single-flight key of one fused Sum/Min/Max (the call's text
        names the op).  ``seq`` is the write sequence read BEFORE any
        derived state (shard lists, row sets) was computed, so a leader
        that computed stale derivations keys as pre-write and can never
        share with a post-write waiter."""
        return ("aggregate", seq, index, str(c), tuple(local))

    def _mesh_sum(self, index, c: Call, shards, opt):
        """Fused BSI Sum over the local shard set; (local_shards, ValCount)
        or None when unsupported."""
        if self.mesh_engine is None:
            return None
        field_name = c.args.get("field")
        if not field_name or len(c.children) > 1:
            return None
        seq = frag_mod.WRITE_SEQ.v  # first (see _aggregate_flight)
        local = self._local_shards(index, shards, opt.remote)
        if not local:
            return None
        filter_call = c.children[0] if c.children else None
        try:
            # batched_sum routes through the engine's batch lane: a lone
            # caller runs the blocking sum program exactly as before;
            # concurrent callers coalesce into a fused whole-program
            # dispatch with their drain-mates (docs/fusion.md).
            total, n = self._sflight.do(
                self._aggregate_flight(seq, index, c, local),
                lambda: self.mesh_engine.batched_sum(
                    index, field_name, filter_call, local
                ),
            )
        except (ValueError, PeerlessMeshError):
            return None
        return set(local), ValCount(total, n)

    def _mesh_aggregate_run(self, index, calls, shards, opt):
        """A run of consecutive Sum/Min/Max calls — independent reads of
        the same committed state — offered whole to the engine's batch
        lane (engine.batched_ops): a lone caller dispatches every call's
        own program before it reads any back, so the run pays ONE
        readback round trip; under concurrent traffic each call queues
        as it would alone.  Returns the ValCounts in call order, or None
        to fall back to the per-call path: a call _mesh_sum /
        _mesh_min_max would not take, an unlowerable filter, remote
        shards, a peer re-entry, a multi-process mesh (its collectives
        are ordered a call at a time), or an identical call already in
        flight from another request (joining it there costs nothing;
        waiting for it here would be a second readback)."""
        eng = self.mesh_engine
        if eng is None or eng.multiproc or opt.remote:
            return None
        ops = []
        for c in calls:
            self._validate_call_args(c)
            field_name = c.args.get("field")
            if not field_name or len(c.children) > 1:
                return None
            filter_call = c.children[0] if c.children else None
            if filter_call is not None and not eng.lowerable(filter_call):
                return None
            kind = c.name.lower()
            ops.append(
                (kind, {"kind": kind, "field": field_name,
                        "filter": filter_call})
            )
        seq = frag_mod.WRITE_SEQ.v  # first (see _aggregate_flight)
        local = self._local_shards(index, shards)
        if not local or len(local) != len(shards):
            return None  # remote shards: the per-call path splits
        plan = plans_mod.current_plan()
        # As in _mesh_count_many: a decline re-executes EVERY call on
        # the per-call path, so the ops this attempt stamped are unwound.
        ops_mark = len(plan.ops) if plan is not None else 0
        try:
            try:
                out = self._sflight.do_all(
                    [self._aggregate_flight(seq, index, c, local)
                     for c in calls],
                    lambda: eng.batched_ops(index, ops, local),
                )
            finally:
                # A half-written note must not reach the next query on
                # this pooled thread (_mesh_count_many's finally).
                plans_mod.take_dispatch_note()
        except (ValueError, PeerlessMeshError):
            out = None
        if out is None:
            if plan is not None:
                del plan.ops[ops_mark:]
            return None
        for c in calls:
            self.stats.count(c.name, 1, tags=[f"index:{index}"])
        return [ValCount(v, n) if n else ValCount() for v, n in out]

    def _execute_min_max(self, index, c: Call, shards, opt, is_min: bool) -> ValCount:
        from ..ops import bsi as bsi_ops

        fused = self._mesh_min_max(index, c, shards, opt, is_min)
        if fused is not None:
            local_shards, fused_vc = fused
            remote = [s for s in shards if s not in local_shards]
            if remote:
                rest = self._execute_min_max(index, c, remote, opt, is_min)
                fused_vc = (
                    fused_vc.smaller(rest) if is_min else fused_vc.larger(rest)
                )
            return ValCount() if fused_vc.count == 0 else fused_vc

        def map_fn(shard):
            ctx = self._bsi_shard_ctx(index, c, shard)
            if ctx is None:
                return ValCount()
            frag, bsig, filt = ctx
            depth = bsig.bit_depth()
            planes = frag.device_planes(depth)
            hi, lo, n = (
                bsi_ops.min_valcount(planes, filt)
                if is_min
                else bsi_ops.max_valcount(planes, filt)
            )
            n = int(n)
            if n == 0:
                return ValCount()
            return ValCount(((int(hi) << 31) | int(lo)) + bsig.min, n)

        def reduce_fn(p, v):
            p = p or ValCount()
            return p.smaller(v) if is_min else p.larger(v)

        result = self.map_reduce(index, shards, c, opt, map_fn, reduce_fn)
        result = result or ValCount()
        return ValCount() if result.count == 0 else result

    def _mesh_min_max(self, index, c: Call, shards, opt, is_min: bool):
        if self.mesh_engine is None:
            return None
        field_name = c.args.get("field")
        if not field_name or len(c.children) > 1:
            return None
        seq = frag_mod.WRITE_SEQ.v  # first (see _aggregate_flight)
        local = self._local_shards(index, shards, opt.remote)
        if not local:
            return None
        filter_call = c.children[0] if c.children else None
        try:
            val, n = self._sflight.do(
                self._aggregate_flight(seq, index, c, local),
                lambda: self.mesh_engine.batched_min_max(
                    index, field_name, filter_call, local, is_min
                ),
            )
        except (ValueError, PeerlessMeshError):
            return None
        return set(local), ValCount(val, n)

    def _execute_min(self, index, c, shards, opt):
        return self._execute_min_max(index, c, shards, opt, True)

    def _execute_max(self, index, c, shards, opt):
        return self._execute_min_max(index, c, shards, opt, False)

    # -- TopN (executor.go :694-828) ---------------------------------------

    def _execute_topn(self, index, c: Call, shards, opt) -> List[Tuple[int, int]]:
        ids_arg, _ = c.uint_slice_arg("ids")
        n, _ = c.uint_arg("n")

        fused = self._mesh_topn_full(index, c, shards, opt)
        if fused is not None:
            return fused

        pairs = self._execute_topn_shards(index, c, shards, opt)
        if not pairs or ids_arg or opt.remote:
            return pairs

        # Phase 2: refetch exact counts for the merged candidate ids
        # (executor.go :715-733).  merge_pairs already deduped the ids
        # across shards, so this is one sorted encode — and the fan-out
        # mapper serializes the refetch call ONCE for all peers.
        other = c.clone()
        other.args["ids"] = sorted(r for r, _ in pairs)
        trimmed = self._execute_topn_shards(index, other, shards, opt)
        if n and n < len(trimmed):
            trimmed = trimmed[:n]
        return trimmed

    def _mesh_topn_full(self, index, c: Call, shards, opt):
        """Single-dispatch TopN: both reference phases (approximate
        candidate scan + exact recount, executor.go :694-733) collapse
        into one device program with one tiny readback — exact totals
        for every cache candidate, gated and trimmed on device.  Applies
        when every requested shard is local and no attribute/Tanimoto
        filter needs host candidate metadata; otherwise returns None and
        the two-phase composition path runs.  Remote (re-entrant) calls
        also fall through: peers must return untrimmed phase pairs for
        the coordinator's merge."""
        if self.mesh_engine is None or opt.remote:
            return None
        if c.args.get("attrName") or c.args.get("attrValues"):
            return None
        tanimoto, _ = c.uint_arg("tanimotoThreshold")
        if tanimoto > 0:
            return None
        if len(c.children) > 1:
            raise Error("TopN() can only have one input bitmap")
        seq = frag_mod.WRITE_SEQ.v  # first (see _aggregate_flight)
        local = set(self._local_shards(index, shards, opt.remote))
        if any(s not in local for s in shards):
            return None
        field_name = c.args.get("_field") or DEFAULT_FIELD
        n, _ = c.uint_arg("n")
        row_ids, _ = c.uint_slice_arg("ids")
        min_threshold, _ = c.uint_arg("threshold")
        if min_threshold <= 0:
            min_threshold = DEFAULT_MIN_THRESHOLD
        try:
            if not c.children:
                # Cache-only TopN rides the versioned result memo: a
                # probe miss first tries the repair layer (count-table
                # maintained from write deltas, re-ranked on serve), and
                # only then pays the full device scan.
                eng = self.mesh_engine
                probe = getattr(eng, "memo_probe_topn", None)
                key = None
                if probe is not None:
                    key, hit = probe(
                        index, field_name, shards, n, min_threshold,
                        row_ids or None,
                    )
                    if hit is not None:
                        p = plans_mod.current_plan()
                        if p is not None:
                            p.note_op(op="TopN", path="memo", memo="hit")
                        return [tuple(pr) for pr in hit]
                out = eng.topn_cache_only(
                    index, field_name, shards, n, min_threshold, row_ids or None
                )
                if key is not None and out is not None:
                    eng.memo_store_topn(
                        key, field_name, n, min_threshold, row_ids or None, out
                    )
                return out
            out = self._sflight.do(
                ("topn", seq, index, str(c), tuple(sorted(local))),
                lambda: self.mesh_engine.batched_topn_full(
                    index,
                    field_name,
                    c.children[0],
                    shards,
                    n,
                    min_threshold,
                    row_ids or None,
                ),
            )
            # Copy: waiters share the flight's list and callers may trim.
            return list(out) if isinstance(out, list) else out
        except (ValueError, PeerlessMeshError):
            # topn_cache_only is a DIRECT engine call (no batcher finally
            # to claim its note): drop any host_fallback stamp here so it
            # cannot leak into the next query's plan on this thread.
            plans_mod.take_dispatch_note()
            return None

    def _execute_topn_shards(self, index, c, shards, opt):
        def map_fn(shard):
            return self._execute_topn_shard(index, c, shard)

        def reduce_fn(prev, v):
            return cache_mod.merge_pairs([prev or [], v])

        fused = self._mesh_topn_shards(index, c, shards, opt)
        if fused is not None:
            local_shards, pairs = fused
            remote = [s for s in shards if s not in local_shards]
            if remote:
                rpairs = (
                    self.map_reduce(index, remote, c, opt, map_fn, reduce_fn)
                    or []
                )
                pairs = cache_mod.merge_pairs([pairs, rpairs])
            pairs.sort(key=cache_mod.pair_sort_key)
            return pairs

        pairs = self.map_reduce(index, shards, c, opt, map_fn, reduce_fn) or []
        pairs.sort(key=cache_mod.pair_sort_key)
        return pairs

    def _local_shards(self, index, shards, remote: bool = False):
        """The locally-owned subset of ``shards`` (all of them when there
        is no cluster).  ``remote=True`` — a peer re-entry — returns ALL
        requested shards: the initiator already routed them here, and
        re-filtering against this node's possibly NEWER topology (a
        resize admitting a node mid-query) would wrongly drop shards the
        old placement assigned to us (executor.go mapper: Remote=true
        executes the given shards verbatim)."""
        if self.cluster is None or remote:
            return list(shards)
        return [
            s
            for s in shards
            if self.cluster.owns_shard(self.cluster.node.id, index, s)
        ]

    def _mesh_topn_shards(self, index, c: Call, shards, opt):
        """Batched TopN phase 1 over the LOCAL shard subset: the
        per-candidate src intersection counts for every local shard in one
        sharded dispatch pair, then the reference's per-shard heap walk
        runs host-side on the precomputed scores.  Remote shards are
        looped/RPC'd by the caller (the _mesh_count composition pattern).
        Returns (local_shard_set, pairs) or None."""
        if self.mesh_engine is None or len(c.children) != 1:
            return None
        shards = self._local_shards(index, shards, opt.remote)
        if not shards:
            return None
        field_name = c.args.get("_field") or DEFAULT_FIELD
        n, _ = c.uint_arg("n")
        attr_name = c.args.get("attrName", "")
        row_ids, _ = c.uint_slice_arg("ids")
        min_threshold, _ = c.uint_arg("threshold")
        attr_values = c.args.get("attrValues")
        tanimoto, _ = c.uint_arg("tanimotoThreshold")
        if tanimoto > 100:
            raise Error("Tanimoto Threshold is from 1 to 100 only")
        if min_threshold <= 0:
            min_threshold = DEFAULT_MIN_THRESHOLD

        # Device slab fast path: the per-shard candidate walk
        # (threshold gates + top-k) runs INSIDE the sharded program and
        # each shard returns a fixed-width slab, so the host merge is
        # bounded by k_out * |shards| pairs instead of the full
        # candidate union.  Declines (None) — attribute/Tanimoto
        # filters need host metadata, ids= bypasses the cache walk,
        # slab overflow needs the exact walk — fall through to the
        # host-walk body below, which is retained verbatim as the
        # differential oracle.
        if (
            not row_ids
            and not attr_name
            and not attr_values
            and tanimoto == 0
            and n > 0
            and getattr(self.mesh_engine, "topn_slab_enabled", False)
        ):
            seq = frag_mod.WRITE_SEQ.v  # before derived state
            try:
                out = self._sflight.do(
                    ("topn_slab", seq, index, str(c), tuple(sorted(shards))),
                    lambda: self.mesh_engine.topn_device_full(
                        index, field_name, c.children[0], shards,
                        int(n), min_threshold,
                    ),
                )
            except (ValueError, PeerlessMeshError):
                plans_mod.take_dispatch_note()
                out = None
            if out is not None:
                p = plans_mod.current_plan()
                if p is not None:
                    p.note_op(op="TopN", path="device_slab",
                              topkDevice=int(n))
                # Copy: waiters share the flight's list.
                return set(shards), list(out)

        frags = {}
        cand_set = set()
        for s in shards:
            frag = self.holder.fragment(index, field_name, VIEW_STANDARD, s)
            if frag is None:
                continue
            pairs = (
                [(r, frag.row_count(r)) for r in row_ids]
                if row_ids
                else list(frag.cache.top())
            )
            frags[s] = frag
            cand_set.update(r for r, _ in pairs)
        if not frags:
            return set(shards), []
        candidates = sorted(cand_set)
        p = plans_mod.current_plan()
        if p is not None:
            p.note_op(op="TopN", path="host_merge",
                      candidates=len(candidates))
        try:
            scored = self.mesh_engine.batched_topn_scores(
                index, field_name, candidates, c.children[0], shards
            )
        except (ValueError, PeerlessMeshError):
            return None
        if scored is None:
            return set(shards), []
        scores, src_counts, shard_pos = scored
        cand_pos = {r: i for i, r in enumerate(candidates)}

        all_pairs = []
        for s in shards:
            frag = frags.get(s)
            si = shard_pos.get(s)
            if frag is None or si is None:
                continue
            per_shard = {
                r: int(scores[si, cand_pos[r]]) for r in cand_set
            }
            all_pairs.append(
                frag.top(
                    n=int(n),
                    row_ids=row_ids or None,
                    min_threshold=min_threshold,
                    filter_name=attr_name,
                    filter_values=attr_values,
                    tanimoto_threshold=tanimoto,
                    src_counts=per_shard,
                    src_count_total=int(src_counts[si]),
                )
            )
        pairs = cache_mod.merge_pairs(all_pairs)
        pairs.sort(key=cache_mod.pair_sort_key)
        return set(shards), pairs

    def _execute_topn_shard(self, index, c: Call, shard: int):
        field_name = c.args.get("_field") or DEFAULT_FIELD
        n, _ = c.uint_arg("n")
        attr_name = c.args.get("attrName", "")
        row_ids, _ = c.uint_slice_arg("ids")
        min_threshold, _ = c.uint_arg("threshold")
        attr_values = c.args.get("attrValues")
        tanimoto, _ = c.uint_arg("tanimotoThreshold")
        if tanimoto > 100:
            raise Error("Tanimoto Threshold is from 1 to 100 only")
        src = None
        if len(c.children) == 1:
            src = self._execute_bitmap_call_shard(index, c.children[0], shard)
        elif len(c.children) > 1:
            raise Error("TopN() can only have one input bitmap")
        frag = self.holder.fragment(index, field_name, VIEW_STANDARD, shard)
        if frag is None:
            return []
        if min_threshold <= 0:
            min_threshold = DEFAULT_MIN_THRESHOLD
        return frag.top(
            n=int(n),
            src=src,
            row_ids=row_ids or None,
            min_threshold=min_threshold,
            filter_name=attr_name,
            filter_values=attr_values,
            tanimoto_threshold=tanimoto,
        )

    # -- Rows / GroupBy (executor.go :897-1170) ----------------------------

    def _execute_rows(self, index, c: Call, shards, opt) -> List[int]:
        col, ok = c.uint_arg("column")
        if ok:
            shards = [col // SHARD_WIDTH]
        limit_arg, has_limit = c.uint_arg("limit")
        limit = limit_arg if has_limit else _MAXINT

        def map_fn(shard):
            return self._execute_rows_shard(index, c, shard)

        def reduce_fn(prev, v):
            return _merge_row_ids(prev or [], v, limit)

        return self.map_reduce(index, shards, c, opt, map_fn, reduce_fn) or []

    def _execute_rows_shard(self, index, c: Call, shard: int) -> List[int]:
        idx = self.holder.index(index)
        if idx is None:
            raise IndexNotFoundError(index)
        field_name = c.args.get("field")
        if not isinstance(field_name, str):
            raise Error("Rows() argument required: field")
        f = idx.field(field_name)
        if f is None:
            raise FieldNotFoundError(field_name)
        frag = self.holder.fragment(index, field_name, VIEW_STANDARD, shard)
        if frag is None:
            return []
        previous, has_prev = c.uint_arg("previous")
        start = previous + 1 if has_prev else 0
        column = None
        col, ok = c.uint_arg("column")
        if ok:
            if col // SHARD_WIDTH != shard:
                return []
            column = col
        limit_arg, has_limit = c.uint_arg("limit")
        return frag.rows_filtered(
            start=start, column=column, limit=limit_arg if has_limit else None
        )

    # What GroupBy implements beside its Rows children.  ``previous`` (a
    # list) is the translator's (translate._translate_group_by).
    _GROUP_BY_ARGS = frozenset(
        ("limit", "offset", "filter", "aggregate", "previous")
    )

    def _group_aggregate(self, idx, c: Call) -> Optional[str]:
        """The int field F of ``aggregate=Sum(field=F)``, None without
        the argument; anything else as ``aggregate`` is an error."""
        agg = c.args.get("aggregate")
        if agg is None:
            return None
        if (
            not isinstance(agg, Call)
            or agg.name != "Sum"
            or agg.children
            or set(agg.args) != {"field"}
            or not isinstance(agg.args["field"], str)
        ):
            raise Error("GroupBy(): aggregate must be Sum(field=<int field>)")
        fname = agg.args["field"]
        f = idx.field(fname)
        if f is None:
            raise FieldNotFoundError(fname)
        if f.bsi_group(fname) is None:
            raise Error(f"GroupBy(): aggregate field '{fname}' is not an int field")
        return fname

    def _execute_group_by(
        self, index, c: Call, shards, opt
    ) -> Sequence[GroupCount]:
        if not c.children:
            raise Error("need at least one child call")
        unknown = sorted(set(c.args) - self._GROUP_BY_ARGS)
        if unknown:
            raise Error(f"GroupBy(): unknown argument '{unknown[0]}'")
        limit_arg, has_limit = c.uint_arg("limit")
        limit = limit_arg if has_limit else _MAXINT
        filter_call = c.call_arg("filter")

        idx = self.holder.index(index)
        if idx is None:
            raise IndexNotFoundError(index)
        for child in c.children:
            if child.name != "Rows":
                raise Error(
                    f"'{child.name}' is not a valid child query for GroupBy, "
                    "must be 'Rows'"
                )
            # An unknown field is an error up front (executor.go GroupBy
            # "Unknown Field"), not a silent empty result.
            fname = child.args.get("field")
            if not isinstance(fname, str) or idx.field(fname) is None:
                raise FieldNotFoundError(str(fname))
        aggregate = self._group_aggregate(idx, c)

        # The host iterator's row filters: the full result of every child
        # that has limit/column, over all the shards.  Resolved once, and
        # only where the iterator runs or the device path needs them.
        @functools.cache
        def child_rows() -> List[Optional[List[int]]]:
            out: List[Optional[List[int]]] = [None] * len(c.children)
            for i, child in enumerate(c.children):
                _, has_lim = child.uint_arg("limit")
                _, has_col = child.uint_arg("column")
                if has_lim or has_col:
                    out[i] = self._execute_rows(index, child, shards, opt)
            return out

        def host(over):
            rows = child_rows()
            if any(r is not None and not r for r in rows):
                return []

            def map_fn(shard):
                return self._execute_group_by_shard(
                    index, c, filter_call, shard, rows, aggregate
                )

            def reduce_fn(prev, v):
                return _merge_group_counts(prev or [], v, limit)

            return self.map_reduce(index, over, c, opt, map_fn, reduce_fn) or []

        fused = self._mesh_group_by(
            index, c, filter_call, shards, opt, aggregate, child_rows
        )
        if fused is not None:
            local_shards, results = fused
            remote = [s for s in shards if s not in local_shards]
            if remote:
                results = _merge_group_counts(results, host(remote), limit)
        else:
            results = host(shards)
            _GROUP_RESULTS["objects"].inc()

        # A GroupColumns stays one under both cuts (vector slices).
        offset, has_offset = c.uint_arg("offset")
        if has_offset and offset < len(results):
            results = results[offset:]
        if has_limit and limit < len(results):
            results = results[:limit]
        return results

    def _group_axis(self, index, child: Call, shards, all_local, child_rows, i):
        """One axis of the tensor, from a ``Rows`` child: (row list,
        row vector, whether the child cut it).  A plain ``Rows(field=f)``
        and a child with ``previous`` alone take the field's kept rows
        (the start of a page is a cut of the result, ``_group_start``);
        ``limit`` cuts the kept vector where every shard is local (no
        walk of a shard), else it and ``column`` are what
        ``_execute_rows`` resolved for the host iterator."""
        rows, vec = self._group_rows(index, child.args["field"], shards)
        lim, has_lim = child.uint_arg("limit")
        _, has_col = child.uint_arg("column")
        if not (has_lim or has_col):
            return rows, vec, False
        if has_col or not all_local:
            rows = child_rows()[i]
            return rows, np.asarray(rows, dtype=np.uint64), True
        prev, has_prev = child.uint_arg("previous")
        lo = int(np.searchsorted(vec, prev, side="right")) if has_prev else 0
        vec = vec[lo:lo + lim]
        return vec.tolist(), vec, True

    @staticmethod
    def _group_start(children, row_vecs) -> int:
        """Flat index of the first combination a GroupBy lists, given
        its children's ``previous``: the nested iterator seeks child i
        to ``previous`` (the last child past it), and where a seek does
        not land on that row the later children start from their first
        (executor.go newGroupByIterator).  Over sorted row vectors that
        is the least combination not below the bound tuple in
        lexicographic order, and row-major order is that order."""
        start, last = 0, len(children) - 1
        for i, (child, vec) in enumerate(zip(children, row_vecs)):
            prev, has_prev = child.uint_arg("previous")
            start *= len(vec)
            if not has_prev:
                continue  # at its first row: position 0, go on matching
            bound = prev + 1 if i == last else prev
            j = int(np.searchsorted(vec, bound, side="left"))
            start += j  # j == len(vec) carries into the field before
            if j == len(vec) or int(vec[j]) != bound:
                for v in row_vecs[i + 1:]:
                    start *= len(v)
                break
        return start

    def _mesh_group_by(self, index, c: Call, filter_call, shards, opt,
                       aggregate=None, child_rows=None):
        """Fused GroupBy over the LOCAL shard subset: all group-combination
        counts in one sharded dispatch; remote shards are looped/RPC'd by
        the caller and merged (the _mesh_count composition pattern).
        Applies to any number of ``Rows`` children whose tensor fits the
        engine's cap: a child's ``limit`` / ``column`` (with or without
        ``previous``) cuts its axis to the rows ``_execute_rows`` names,
        which reach the program as a traced index vector, and
        ``previous`` alone moves the start of the listing
        (``_group_start``).  With ``aggregate`` (the int field of
        ``aggregate=Sum(field=...)``) the program also counts every
        group under the measure's planes and the result carries
        ``sums``.  The merged list is then truncated to `limit` like the
        reference's progressive merge.  Returns (local_shard_set,
        results) or None."""
        if self.mesh_engine is None or not c.children:
            return None
        seq = frag_mod.WRITE_SEQ.v  # BEFORE row_lists: a leader with
        # stale row sets must key as pre-write (see _aggregate_flight)
        all_shards = shards
        shards = self._local_shards(index, shards, opt.remote)
        if not shards:
            return None
        fields = [child.args["field"] for child in c.children]
        # The count TENSOR rides the versioned result memo (the
        # assembled list never does — limit/offset assembly below reruns
        # on every serve, so a memo hit cannot drift from a recompute).
        # An aggregated tensor stays out of it: the memo's tokens and
        # repair.py's entry know the grouped fields' count tensor only.
        eng = self.mesh_engine
        probe = getattr(eng, "memo_probe_groupby", None)
        key = hit = None
        if probe is not None and aggregate is None:
            qsig = str(c)
            if filter_call is not None:
                qsig += "|flt:" + str(filter_call)
            key, hit = probe(index, qsig, fields, filter_call, shards)
        with tracing_mod.stage("group_rows"):
            all_local = len(shards) == len(all_shards)
            row_lists, row_vecs, cut = zip(*(
                self._group_axis(index, child, shards, all_local, child_rows, i)
                for i, child in enumerate(c.children)
            ))
        if any(not rows for rows in row_lists):
            return set(shards), []
        shape = tuple(len(rows) for rows in row_lists)
        if hit is not None and tuple(np.asarray(hit).shape) == shape:
            p = plans_mod.current_plan()
            if p is not None:
                p.note_op(op="GroupBy", path="memo", memo="hit")
            counts = hit
        else:
            try:
                counts = self._sflight.do(
                    # row_lists are DERIVED from fragment state already
                    # versioned by WRITE_SEQ, so they need not (and must
                    # not — O(total rows) hashing per query) join the key.
                    ("groupby", seq, index, str(c), tuple(sorted(shards))),
                    # Through the batcher: a GroupBy arriving alongside
                    # a dashboard drain rides the SAME fused program as
                    # its drain-mates (a "group" edge); lone callers
                    # take the batcher's idle direct path (solo_op_async
                    # → group_counts_async, one readback).
                    lambda: self.mesh_engine.batched_group_counts(
                        index, fields, row_lists, filter_call, shards,
                        aggregate, cut,
                    ),
                )
            except (ValueError, PeerlessMeshError):
                # Direct engine call: claim any half-written dispatch note
                # (residency host_fallback) before falling back, so it
                # cannot merge into an unrelated query's plan.
                plans_mod.take_dispatch_note()
                return None
            if counts is not None and key is not None:
                eng.memo_store_groupby(
                    key, fields, row_lists, filter_call, counts
                )
        if counts is None:
            return None
        limit_arg, has_limit = c.uint_arg("limit")
        start = self._group_start(c.children, row_vecs)
        with tracing_mod.stage(
            "group_decode" if aggregate is None else "group_aggregate"
        ):
            # np.flatnonzero walks the count tensor in row-major order —
            # exactly the nested-iterator order of the reference
            # (executor.go:2726), so cutting at ``limit`` non-zero groups
            # is the progressive limit truncation.  No Python step a
            # group: the index vector and the counts are the result.
            cells = None
            if aggregate is not None:
                cells = np.asarray(counts).reshape(math.prod(shape), -1)
                counts = cells[:, -1]
            counts = np.asarray(counts).reshape(shape).ravel()
            flat = np.flatnonzero(counts > 0)
            if start:
                flat = flat[np.searchsorted(flat, start):]
            if has_limit:
                flat = flat[:limit_arg]
            if not len(flat):
                return set(shards), []
            if any(cut):
                axes = GroupAxes(fields, row_vecs, kept=False)
            else:
                axes = self._group_axes(index, fields, shards, row_vecs)
            results = GroupColumns(
                axes,
                flat,
                counts[flat],
                sums=None if cells is None else self._group_sums(
                    index, aggregate, cells[flat]
                ),
            )
        _GROUP_RESULTS["columns"].inc()
        return set(shards), results

    def _group_sums(self, index, aggregate: str, cells: np.ndarray):
        """A sum a row of ``cells`` (int32[n, depth + 2]: a group's
        popcounts under each value plane, then under the not-null
        plane, then its count): Σ_b 2^b · cells[:, b] + min · cells[:,
        depth], in integers.  int64 holds it while depth + 31 bits and
        the base's do (a cell is an int32 count of columns); a deeper
        field is summed in Python's own integers."""
        bsig = self.holder.index(index).field(aggregate).bsi_group(aggregate)
        depth = cells.shape[1] - 2
        wide = depth <= 31 and abs(bsig.min) < (1 << 31)
        planes = cells[:, :depth].astype(np.int64 if wide else object)
        weights = np.array([1 << b for b in range(depth)],
                           dtype=np.int64 if wide else object)
        have = cells[:, depth].astype(np.int64 if wide else object)
        return planes @ weights + have * bsig.min if depth else have * bsig.min

    # (index, field, shards) -> (version token, sorted row ids, the same
    # as a vector): the GroupBy axes of a field over a shard set, kept
    # until the field's standard view or the index's shard set changes.
    GROUP_ROWS_CACHE = 64
    # (index, fields, shards) -> GroupAxes over those vectors.  Few: an
    # entry can hold wire.GROUP_TEXTS_MAX reply texts (~8 MB).
    GROUP_AXES_CACHE = 8

    def _group_axes(self, index, fields, shards, row_vecs) -> GroupAxes:
        """The kept axes of a GroupBy over ``fields``: the same object
        for as long as ``_group_rows`` hands out the same vectors, so
        that what rides on it (``GroupAxes.reply_texts``) goes when a
        write or a new shard moves a field's version."""
        key = (index, tuple(fields), tuple(shards))
        axes = self._group_axes_cache.get(key)
        if axes is None or any(
            kept is not vec for kept, vec in zip(axes.rows, row_vecs)
        ):
            if len(self._group_axes_cache) >= self.GROUP_AXES_CACHE:
                self._group_axes_cache.clear()
            axes = self._group_axes_cache[key] = GroupAxes(fields, row_vecs)
        return axes

    def _group_rows(self, index, field, shards) -> Tuple[List[int], np.ndarray]:
        """Sorted distinct row ids of ``field`` over ``shards``: the
        axis of a GroupBy's count tensor, as the list the engine lowers
        and as the vector the result's column is gathered from.  The
        walk is one ``frag.row_ids()`` a shard; a request that finds the
        view at the version it was walked at takes both as they are."""
        idx = self.holder.index(index)
        f = idx.field(field) if idx is not None else None
        view = f.views.get(VIEW_STANDARD) if f is not None else None
        if view is None:
            return [], _NO_ROWS
        token = (self.holder.shard_epoch(index), view.gen, view.version)
        key = (index, field, tuple(shards))
        hit = self._group_rows_cache.get(key)
        if hit is not None and hit[0] == token:
            return hit[1], hit[2]
        rows = set()
        for s in shards:
            frag = self.holder.fragment(index, field, VIEW_STANDARD, s)
            if frag is not None:
                rows.update(frag.row_ids())
        out = sorted(rows)
        vec = np.asarray(out, dtype=np.uint64)
        if len(self._group_rows_cache) >= self.GROUP_ROWS_CACHE:
            self._group_rows_cache.clear()
        self._group_rows_cache[key] = (token, out, vec)
        return out, vec

    def _execute_group_by_shard(
        self, index, c: Call, filter_call, shard, child_rows, aggregate=None
    ) -> List[GroupCount]:
        filter_row = None
        if filter_call is not None:
            filter_row = self._execute_bitmap_call_shard(index, filter_call, shard)
        iterator = _GroupByIterator.create(
            self, child_rows, c.children, filter_row, index, shard
        )
        if iterator is None:
            return []
        if aggregate is not None:
            iterator.measure = self._shard_measure(index, aggregate, shard)
        limit_arg, has_limit = c.uint_arg("limit")
        limit = limit_arg if has_limit else _MAXINT
        results: List[GroupCount] = []
        while len(results) < limit:
            gc, done = iterator.next()
            if done:
                break
            if gc.count > 0:
                results.append(gc)
        return results

    def _shard_measure(self, index, aggregate: str, shard: int):
        """(shard, planes uint32[depth + 1, WORDS] or None, min) of an
        int field's fragment: what the host iterator sums a group
        under (fragment.go sum, by plane popcounts)."""
        bsig = self.holder.index(index).field(aggregate).bsi_group(aggregate)
        frag = self.holder.fragment(
            index, aggregate, view_bsi_name(aggregate), shard
        )
        planes = None
        if frag is not None:
            planes = np.asarray(frag.device_planes(bsig.bit_depth()))
        return shard, planes, bsig.min

    # -- Options (executor.go :317) ----------------------------------------

    def _execute_options_call(self, index, c: Call, shards, opt):
        opt_copy = opt.copy()
        if "columnAttrs" in c.args:
            v, _ = c.bool_arg("columnAttrs")
            opt.column_attrs = v  # applies to the whole response
        if "excludeRowAttrs" in c.args:
            opt_copy.exclude_row_attrs, _ = c.bool_arg("excludeRowAttrs")
        if "excludeColumns" in c.args:
            opt_copy.exclude_columns, _ = c.bool_arg("excludeColumns")
        if "shards" in c.args:
            s = c.args["shards"]
            if not isinstance(s, list) or any(
                isinstance(x, bool) or not isinstance(x, int) for x in s
            ):
                raise Error("Query(): shards must be a list of unsigned integers")
            shards = [int(x) for x in s]
        if len(c.children) != 1:
            raise Error("Options() requires exactly one child call")
        return self._execute_call(index, c.children[0], shards, opt_copy)

    # -- writes ------------------------------------------------------------

    def _execute_set(self, index, c: Call, opt) -> bool:
        col_id, ok = c.uint_arg("_col")
        if not ok:
            raise Error("Set() column argument 'col' required")
        field_name = self._field_arg(c)
        idx = self.holder.index(index)
        if idx is None:
            raise IndexNotFoundError(index)
        f = idx.field(field_name)
        if f is None:
            raise FieldNotFoundError(field_name)

        ef = idx.existence_field()
        if ef is not None:
            ef.set_bit(0, col_id)

        if f.options.type == FIELD_TYPE_INT:
            value, ok = c.int_arg(field_name)
            if not ok:
                raise Error("Set() row argument required")
            # A BSI Set rewrites value planes — it CLEARS bits, so it
            # must not ack degraded (see _write_replicated).
            return self._write_replicated(
                index, c, col_id, opt, lambda: f.set_value(col_id, value),
                destructive=True,
            )

        row_id, ok = c.uint_arg(field_name)
        if not ok:
            raise Error("Set() row argument required")
        timestamp = None
        ts = c.args.get("_timestamp")
        if isinstance(ts, str):
            try:
                timestamp = dt.datetime.strptime(ts, TIME_FORMAT)
            except ValueError:
                raise Error(f"invalid date: {ts}")
        if f.options.type == FIELD_TYPE_BOOL and row_id not in (0, 1):
            raise Error("bool field rows must be 0 or 1")
        # Mutex/bool sets implicitly CLEAR the column's previous row.
        return self._write_replicated(
            index, c, col_id, opt,
            lambda: f.set_bit(row_id, col_id, timestamp),
            destructive=f.options.type in (FIELD_TYPE_MUTEX, FIELD_TYPE_BOOL),
        )

    def _execute_clear_bit(self, index, c: Call, opt) -> bool:
        field_name = self._field_arg(c)
        idx = self.holder.index(index)
        if idx is None:
            raise IndexNotFoundError(index)
        f = idx.field(field_name)
        if f is None:
            raise FieldNotFoundError(field_name)
        row_id, ok = c.uint_arg(field_name)
        if not ok:
            raise Error("Clear() row argument required")
        col_id, ok = c.uint_arg("_col")
        if not ok:
            raise Error("Clear() col argument required")
        return self._write_replicated(
            index, c, col_id, opt, lambda: f.clear_bit(row_id, col_id),
            destructive=True,
        )

    def _hint_down_writes(
        self, index, shard, down, call, shards=None, dedup=None,
        all_or_nothing=False,
    ):
        """Durably queue the missed write for each DOWN owner (hinted
        handoff, docs/durability.md): the hint record carries the
        serialized call, replayed with remote=True against the
        recovered owner by the HintManager's worker.  Returns how many
        of ``down`` were absorbed — the caller applies the PR 11
        fallback policy to the rest.  ``dedup`` ({(node, shard): seq}
        scoped to one logical write) keeps a hedge-recursion re-route
        from double-queuing the same miss.  ``all_or_nothing`` (the
        destructive-gate contract) ROLLS BACK this call's fresh
        enqueues and returns 0 when any of ``down`` could not be
        absorbed: the caller is about to fail the write without an
        ack, and a surviving partial hint would replay an op that
        never happened onto one replica."""
        hints = getattr(self.cluster, "hints", None)
        if hints is None:
            return 0
        op = {"kind": "query", "query": str(call)}
        if shards is not None:
            op["shards"] = [int(s) for s in shards]
        n = 0
        fresh = []  # (node_id, dedup key, seq) queued by THIS call
        for node in down:
            key = (node.id, shard)
            if dedup is not None and key in dedup:
                n += 1  # already queued by an earlier route of this write
                continue
            seq = hints.enqueue(node.id, index, shard, op)
            if seq:
                n += 1
                fresh.append((node.id, key, seq))
                if dedup is not None:
                    dedup[key] = seq
        if all_or_nothing and n < len(down):
            for node_id, key, seq in fresh:
                hints.discard(node_id, [seq])
                if dedup is not None:
                    dedup.pop(key, None)
            return 0
        if n:
            self._note_hinted(index, call.name, shard, n)
        return n

    def _discard_hinted(self, dedup):
        """Unwind EVERY hint a failing logical write queued — across
        all its shards and targets (the per-call all_or_nothing rolls
        back only the current shard's batch; the write erroring at a
        LATER shard must not leave earlier shards' hints to replay an
        op the client never got an ack for)."""
        hints = getattr(self.cluster, "hints", None)
        if hints is None or not dedup:
            return
        for (node_id, _shard), seq in list(dedup.items()):
            hints.discard(node_id, [seq])
        dedup.clear()

    def _note_hinted(self, index, op_name, shard, n):
        """One hinted write: journal + plan stamp (the analyzer's
        "owner DOWN: queued as hint" annotation feeds off the op
        note; the pilosa_hints_* series are counted by the manager)."""
        self.cluster.journal.append(
            "write.hinted", index=index, op=op_name, shard=int(shard),
            owners=int(n),
        )
        p = plans_mod.current_plan()
        if p is not None:
            p.note_op(op=op_name, hinted=int(n), shard=int(shard))

    def _write_replicated(
        self, index, c: Call, col_id: int, opt, local_fn,
        destructive: bool = False,
    ):
        """Apply a single-bit write on every replica of the column's shard:
        locally when this node is an owner, forwarded otherwise
        (executor.go executeSetBitField :1865-1898).  Single-node: just
        local.

        DEGRADED policy (docs/durability.md): an owner the failure
        detector has marked DOWN has the miss durably QUEUED as a hint
        record for replay on recovery (hinted handoff) — the surviving
        owners take the write now and the recovered owner receives it
        before anti-entropy can merge against it.  When the hint queue
        cannot absorb the miss (no manager / overflow / expiry) the
        policy falls back verbatim to PR 11: purely-ADDITIVE sets skip
        the dead owner (anti-entropy seeds it on recovery — majority
        ties resolve to set, so the survivor's bit wins) while
        DESTRUCTIVE writes fail loudly — a Clear, or any write that
        implicitly clears bits (mutex/bool sets displacing the previous
        row, BSI sets rewriting value planes), acked on the lone
        survivor would be partially REVERTED by that same tie rule when
        the dead owner recovers still holding the old bits.  Every
        owner DOWN fails loudly: there is no replica to make the ack
        durable on.  An owner that is not yet marked DOWN but fails the
        forward also fails the write loudly — the client never got an
        ack, so nothing acked can be lost."""
        if self.cluster is None:
            return local_fn()
        shard = col_id // SHARD_WIDTH
        owners = self.cluster.shard_nodes(index, shard)
        if opt.remote:
            # Directed delivery (replication forward or hint replay):
            # the sender already ran the degraded-write policy — apply
            # locally when this node is an owner, no re-gating (a
            # replay must land even while some OTHER owner is DOWN).
            if any(n.id == self.cluster.node.id for n in owners):
                return bool(local_fn())
            return False
        live = [n for n in owners if n.state != "DOWN"]
        down = [n for n in owners if n.state == "DOWN"]
        if not live:
            raise Error(
                f"write unavailable: every owner of shard {shard} is DOWN "
                f"({', '.join(n.id for n in owners)})"
            )
        hinted = 0
        if down:
            hinted = self._hint_down_writes(
                index, shard, down, c, all_or_nothing=destructive,
            )
        if destructive and hinted < len(down):
            raise Error(
                f"{c.name} unavailable: owner of shard {shard} is DOWN, "
                "the hint queue could not absorb the miss, and a "
                "degraded bit-removing write would be reverted by "
                "anti-entropy's majority-tie-to-set merge on recovery"
            )
        ret = False
        for node in live:
            if node.id == self.cluster.node.id:
                if local_fn():
                    ret = True
                continue
            doc = self.cluster.client(node).query(index, str(c), remote=True)
            if doc["results"][0]:
                ret = True
        return ret

    def _forward_to_all(self, index, c: Call, opt):
        """Forward an attr write to every other node (executor.go
        :1964-1993)."""
        if self.cluster is None or opt.remote:
            return
        for node in self.cluster.nodes:
            if node.id == self.cluster.node.id:
                continue
            self.cluster.client(node).query(index, str(c), remote=True)

    def _execute_clear_row(self, index, c: Call, shards, opt) -> bool:
        field_name = self._field_arg(c)
        f = self.holder_field(index, field_name)
        if f.options.type not in (
            FIELD_TYPE_SET,
            FIELD_TYPE_TIME,
            FIELD_TYPE_MUTEX,
            FIELD_TYPE_BOOL,
        ):
            raise Error(
                f"ClearRow() is not supported on {f.options.type} field types"
            )
        row_id, ok = c.uint_arg(field_name)
        if not ok:
            raise Error("ClearRow() row argument required")

        def map_fn(shard):
            changed = False
            for view in f.views.values():
                frag = view.fragment(shard)
                if frag is not None:
                    changed |= frag.clear_row(row_id)
            return changed

        return bool(
            self.map_reduce(
                index, shards, c, opt, map_fn, lambda p, v: bool(p) or v
            )
        )

    def _execute_set_row(self, index, c: Call, shards, opt) -> bool:
        field_name = self._field_arg(c)
        f = self.holder_field(index, field_name)
        if f.options.type != FIELD_TYPE_SET:
            raise Error(
                f"Store() is not supported on {f.options.type} field types"
            )
        row_id, ok = c.uint_arg(field_name)
        if not ok:
            raise Error("Store() row argument required")
        if len(c.children) != 1:
            raise Error("Store() requires a source row")

        def map_fn(shard):
            src = self._execute_bitmap_call_shard(index, c.children[0], shard)
            view = f.view_if_not_exists(VIEW_STANDARD)
            frag = view.fragment_if_not_exists(shard)
            return frag.set_row(src, row_id)

        return bool(
            self.map_reduce(
                index, shards, c, opt, map_fn, lambda p, v: bool(p) or v
            )
        )

    def _execute_set_row_attrs(self, index, c: Call, opt):
        field_name = c.args.get("_field")
        f = self.holder_field(index, field_name)
        row_id, ok = c.uint_arg("_row")
        if not ok:
            raise Error("SetRowAttrs() row field required")
        attrs = {
            k: v for k, v in c.args.items() if k not in ("_field", "_row")
        }
        f.row_attr_store.set_attrs(row_id, attrs)
        self._forward_to_all(index, c, opt)

    def _execute_bulk_set_row_attrs(self, index, calls: List[Call], opt):
        by_field: Dict[str, Dict[int, dict]] = {}
        for c in calls:
            field_name = c.args.get("_field")
            f = self.holder_field(index, field_name)
            row_id, ok = c.uint_arg("_row")
            if not ok:
                raise Error("SetRowAttrs() row field required")
            attrs = {
                k: v for k, v in c.args.items() if k not in ("_field", "_row")
            }
            by_field.setdefault(field_name, {}).setdefault(row_id, {}).update(
                attrs
            )
        for field_name, m in by_field.items():
            f = self.holder_field(index, field_name)
            f.row_attr_store.set_bulk_attrs(m)
        for c in calls:
            self._forward_to_all(index, c, opt)
        return [None] * len(calls)

    def _execute_set_column_attrs(self, index, c: Call, opt):
        idx = self.holder.index(index)
        if idx is None:
            raise IndexNotFoundError(index)
        col, ok = c.uint_arg("_col")
        if not ok:
            raise Error("SetColumnAttrs() column required")
        attrs = {
            k: v for k, v in c.args.items() if k not in ("_col", "field")
        }
        idx.column_attr_store.set_attrs(col, attrs)
        self._forward_to_all(index, c, opt)


class _GroupByIterator:
    """Multi-field row-combination walker (executor.go:2726-2890)."""

    def __init__(self):
        self.row_iters = []
        self.rows: List[Tuple[Optional[Row], int]] = []
        self.fields: List[FieldRow] = []
        self.filter: Optional[Row] = None
        self.done = False
        # (shard, planes, min) under aggregate=Sum(...): next() then
        # sums every listed group (Executor._shard_measure).
        self.measure = None

    @classmethod
    def create(
        cls, executor, child_rows, children: List[Call], filter_row, index, shard
    ) -> Optional["_GroupByIterator"]:
        gbi = cls()
        gbi.filter = filter_row
        gbi.rows = [(None, 0)] * len(children)
        ignore_prev = False
        for i, call in enumerate(children):
            field_name = call.args["field"]
            gbi.fields.append(FieldRow(field_name))
            frag = executor.holder.fragment(
                index, field_name, VIEW_STANDARD, shard
            )
            if frag is None:
                return None
            it = frag.row_iterator(
                wrap=(i != 0), row_ids_filter=child_rows[i] or None
            )
            gbi.row_iters.append(it)
            prev, has_prev = call.uint_arg("previous")
            if has_prev and not ignore_prev:
                if i == len(children) - 1:
                    prev += 1
                it.seek(prev)
            next_row, row_id, wrapped = it.next()
            if next_row is None:
                gbi.done = True
                return gbi
            gbi.rows[i] = (next_row, row_id)
            if has_prev and row_id != prev:
                ignore_prev = True
            if wrapped:
                for j in range(i - 1, -1, -1):
                    next_row, row_id, w2 = gbi.row_iters[j].next()
                    if next_row is None:
                        gbi.done = True
                        return gbi
                    gbi.rows[j] = (next_row, row_id)
                    if not w2:
                        break

        if gbi.filter is not None and gbi.rows:
            r, i0 = gbi.rows[0]
            gbi.rows[0] = (r.intersect(gbi.filter), i0)
        for i in range(1, len(gbi.rows) - 1):
            r, rid = gbi.rows[i]
            gbi.rows[i] = (r.intersect(gbi.rows[i - 1][0]), rid)
        return gbi

    def _next_at_idx(self, i: int):
        nr, row_id, wrapped = self.row_iters[i].next()
        if nr is None:
            self.done = True
            return
        if wrapped and i != 0:
            self._next_at_idx(i - 1)
            if self.done:
                return
        if i == 0 and self.filter is not None:
            self.rows[i] = (nr.intersect(self.filter), row_id)
        elif i == 0 or i == len(self.rows) - 1:
            self.rows[i] = (nr, row_id)
        else:
            self.rows[i] = (nr.intersect(self.rows[i - 1][0]), row_id)

    def next(self) -> Tuple[Optional[GroupCount], bool]:
        if self.done:
            return None, True
        if len(self.rows) == 1:
            count = self.rows[-1][0].count()
        else:
            count = self.rows[-1][0].intersection_count(self.rows[-2][0])
        group = [
            FieldRow(f.field, rid)
            for f, (_, rid) in zip(self.fields, self.rows)
        ]
        ret = GroupCount(group, count)
        if self.measure is not None:
            ret.sum = self._sum() if count else 0
        self._next_at_idx(len(self.rows) - 1)
        return ret, False

    def _sum(self) -> int:
        """The measure summed over the current group's columns that
        have a value: Σ_b 2^b · popcount(group & notnull & plane_b) +
        min · popcount(group & notnull)."""
        shard, planes, base = self.measure
        row = self.rows[-1][0]
        if len(self.rows) > 1:
            row = row.intersect(self.rows[-2][0])
        seg = row.segment(shard)
        if planes is None or seg is None:
            return 0
        have = np.asarray(seg) & planes[-1]
        counts = np.bitwise_count(planes[:-1] & have).sum(axis=1).tolist()
        return int(np.bitwise_count(have).sum()) * base + sum(
            n << b for b, n in enumerate(counts)
        )

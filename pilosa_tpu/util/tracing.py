"""Tracing: spans around every hot path, with real trace contexts.

Mirror of the reference's global Tracer / Span (tracing/tracing.go:11-66),
grown into a propagating tracer: every span carries a ``trace_id`` +
``span_id``, and a parent may be either a live Span (same thread of
control) or a detached TraceContext (a thread hop or a remote peer).
The pipelined query path (parallel/batcher.py) crosses three worker
threads between accept and reply, so the "current span" can no longer be
an implicit ``threading.local`` owned by one Tracer: the slot is
module-level (``current_span``/``attach``), captured explicitly at
submit time and re-attached wherever the work resumes.

Cross-node: ``inject_headers``/``extract_headers`` carry the context as
``X-Trace-Id``/``X-Span-Id`` HTTP headers (the reference sends Jaeger's
uber-trace-id the same way, tracing/opentracing/opentracing.go), so a
remote shard fan-out joins the initiator's trace.

The stage clock (``stage``/``waited``, below the tracers) is the one
recorder of a query's host time from socket to socket: a histogram
series per (path, stage), a child span on each rider, a stage stamp on
each rider's plan and — only while a profiler capture runs
(``capturing``) — a live ``jax.profiler.TraceAnnotation`` on the device
trace's clock, the TPU equivalent of the reference's Jaeger adapter.
"""

from __future__ import annotations

import gc
import os
import random
import threading
import time
from collections import deque
from contextlib import contextmanager, nullcontext
from typing import Dict, List, Optional

from . import plans as plans_mod
from .stats import (
    GC_GENERATIONS,
    METRIC_ENGINE_DEVICE_INFLIGHT,
    METRIC_GC_PAUSE,
    METRIC_HTTP_OCCUPIED,
    METRIC_HTTP_REQUEST,
    METRIC_PIPELINE_STAGE,
    METRIC_QUERY_STAGE,
    PIPELINE_STAGES,
    REGISTRY,
)

# Module-level current-span slot: shared by every Tracer so code that
# only has *a* span (a batcher worker, an internal HTTP client) can
# resolve the ambient one without holding a tracer reference.
_LOCAL = threading.local()

# Trace/span ids need uniqueness, not unpredictability: a Mersenne
# PRNG seeded once from the OS beats ``uuid4`` — whose per-call
# ``os.urandom`` is a SYSCALL, ~50 µs on sandboxed kernels and the
# single largest line item of a memo-hit query — by ~40x.  getrandbits
# on a Random instance mutates its state in one C call under the GIL,
# so concurrent callers are safe.  Spawned worker processes
# (net/worker.py) re-import this module and reseed independently.
_RNG = random.Random(int.from_bytes(os.urandom(16), "big"))


def new_id() -> str:
    """A 16-hex-char random id (trace ids and span ids alike)."""
    return f"{_RNG.getrandbits(64):016x}"


def current_span() -> Optional["Span"]:
    """The span the calling thread is currently inside, if any."""
    return getattr(_LOCAL, "current", None)


@contextmanager
def attach(span: Optional["Span"]):
    """Make ``span`` the calling thread's current span for the duration
    of the block — the explicit re-attach half of a thread hop (the
    capture half is just ``current_span()`` on the submitting thread).
    ``attach(None)`` is a no-op block, so callers need not branch on
    tracing being enabled."""
    prev = getattr(_LOCAL, "current", None)
    _LOCAL.current = span if span is not None else prev
    try:
        yield span
    finally:
        _LOCAL.current = prev


def inject_headers(headers: Dict[str, str]):
    """Stamp the calling thread's current span into outbound request
    headers (X-Trace-Id/X-Span-Id/X-Trace-Name) — the single wire-
    propagation implementation (the internal HTTP client calls it
    without a tracer)."""
    cur = getattr(_LOCAL, "current", None)
    if cur is not None:
        headers["X-Trace-Id"] = cur.trace_id
        headers["X-Span-Id"] = cur.span_id
        headers["X-Trace-Name"] = cur.name


class TraceContext:
    """A detached (trace id, span id) pair: what survives a thread hop
    or an HTTP hop when the Span object itself cannot."""

    __slots__ = ("trace_id", "span_id")

    def __init__(self, trace_id: str, span_id: str = ""):
        self.trace_id = trace_id
        self.span_id = span_id

    def __repr__(self):
        return f"TraceContext({self.trace_id}, {self.span_id})"


class Span:
    __slots__ = (
        "name",
        "tags",
        "start",
        "start_wall",
        "duration",
        "_children",
        "_staged",
        "parent",
        "trace_id",
        "span_id",
        "parent_span_id",
        "_tracer",
    )

    def __init__(self, name: str, tags: Optional[dict] = None, parent=None,
                 tracer: Optional["Tracer"] = None):
        self.name = name
        self.tags = tags or {}
        self.start = time.monotonic()
        self.start_wall = time.time()
        self.duration = None
        self._children: List["Span"] = []
        # Finished stage trees (the stage clock's) not yet turned into
        # child spans: a stage costs the query one list append here,
        # and its spans are made when somebody looks (``children``).
        self._staged: list = []
        self.span_id = new_id()
        self._tracer = tracer
        if isinstance(parent, Span):
            self.parent = parent
            self.trace_id = parent.trace_id
            self.parent_span_id = parent.span_id
            if tracer is None:
                self._tracer = parent._tracer
        elif isinstance(parent, TraceContext):
            # A remote/detached parent: this span roots a LOCAL tree but
            # rides the caller's trace id, so /debug/traces on every
            # node involved shows trees sharing one trace id.
            self.parent = None
            self.trace_id = parent.trace_id
            self.parent_span_id = parent.span_id
        else:
            self.parent = None
            self.trace_id = new_id()
            self.parent_span_id = ""

    @property
    def children(self) -> List["Span"]:
        """The child spans, in the order they were attached; staged
        stage trees become ``pipeline.<stage>`` children first."""
        staged = self._staged
        while staged:
            try:
                staged.pop(0)._to_span(self)
            except IndexError:  # another reader took it
                break
        return self._children

    def context(self) -> TraceContext:
        return TraceContext(self.trace_id, self.span_id)

    def set_tag(self, key: str, value):
        self.tags[key] = value

    def child(self, name: str, **tags) -> "Span":
        """Start a child span attached to this span (explicit-parent
        form for worker threads; finish() it when done)."""
        span = Span(name, tags, self)
        self._children.append(span)
        return span

    def record(self, name: str, start: Optional[float] = None,
               duration: float = 0.0, **tags) -> "Span":
        """Append an already-measured child span: ``start`` is a
        time.monotonic timestamp (defaults to now - duration).  This is
        how the pipeline stamps per-stage timings onto a query's tree
        without holding a span open across worker threads."""
        if start is None:
            start = time.monotonic() - duration
        # No clock is read: the child's wall time follows from this
        # span's two clocks (the stage recorder stamps several children
        # per query from here).
        span = Span.__new__(Span)
        span.name = name
        span.tags = tags
        span.start = start
        span.start_wall = self.start_wall + (start - self.start)
        span.duration = duration
        span._children = []
        span._staged = []
        span.parent = self
        span.trace_id = self.trace_id
        span.span_id = new_id()
        span.parent_span_id = self.span_id
        span._tracer = self._tracer
        self._children.append(span)
        return span

    def finish(self):
        self.duration = time.monotonic() - self.start
        if self.parent is None and self._tracer is not None:
            self._tracer._record_finished(self)

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "traceID": self.trace_id,
            "spanID": self.span_id,
            "parentSpanID": self.parent_span_id,
            "tags": self.tags,
            "startTime": self.start_wall,
            "durationMs": None if self.duration is None else self.duration * 1e3,
            "children": [
                c.to_dict() for c in sorted(self.children, key=lambda c: c.start)
            ],
        }


class Tracer:
    """Collects span trees; cheap enough to keep always-on.  Finished
    root spans land in two rings: ``recent`` (the last ``keep_finished``)
    and ``slow`` (the last ``keep_slow`` whose duration crossed
    ``slow_threshold`` seconds) — the /debug/traces surface.

    ``keep_finished`` defaults non-zero so /debug/traces works out of
    the box on any tracer-enabled server."""

    DEFAULT_KEEP = 64
    DEFAULT_KEEP_SLOW = 32
    DEFAULT_SLOW_THRESHOLD = 0.100  # seconds

    def __init__(self, keep_finished: int = DEFAULT_KEEP,
                 keep_slow: int = DEFAULT_KEEP_SLOW,
                 slow_threshold: float = DEFAULT_SLOW_THRESHOLD):
        self.keep_finished = keep_finished
        self.slow_threshold = slow_threshold
        # O(1) ring eviction: the old list.pop(0) was O(n) per finished
        # span, paid on every query at serving rates.
        self._finished: "deque[Span]" = deque(maxlen=max(1, keep_finished))
        self._slow: "deque[Span]" = deque(maxlen=max(1, keep_slow))
        self._lock = threading.Lock()

    @contextmanager
    def start_span(self, name: str, parent=None, **tags):
        """Span around the block; nests under the thread's current span
        unless an explicit ``parent`` (Span or TraceContext) is given."""
        if parent is None:
            parent = getattr(_LOCAL, "current", None)
        span = Span(name, tags, parent, tracer=self)
        if isinstance(parent, Span):
            parent._children.append(span)
        prev = getattr(_LOCAL, "current", None)
        _LOCAL.current = span
        try:
            yield span
        finally:
            span.finish()
            _LOCAL.current = prev

    def begin(self, name: str, parent=None, **tags) -> Optional[Span]:
        """Start a span WITHOUT scoping it to this thread: the deferred
        form for work whose completion happens on another thread (the
        caller — or a completion callback — must finish() it).  Nests
        under the thread's current span unless ``parent`` is given."""
        if parent is None:
            parent = getattr(_LOCAL, "current", None)
        span = Span(name, tags, parent, tracer=self)
        if isinstance(parent, Span):
            parent._children.append(span)
        return span

    def _record_finished(self, span: Span):
        if not self.keep_finished:
            return
        with self._lock:
            self._finished.append(span)
            if span.duration is not None and span.duration >= self.slow_threshold:
                self._slow.append(span)

    def finished_spans(self) -> List[Span]:
        with self._lock:
            return list(self._finished)

    def slow_spans(self) -> List[Span]:
        with self._lock:
            return list(self._slow)

    def traces(self) -> dict:
        """The /debug/traces document: recent + slow root span trees."""
        with self._lock:
            recent = list(self._finished)
            slow = list(self._slow)
        return {
            "recent": [s.to_dict() for s in recent],
            "slow": [s.to_dict() for s in slow],
            "slowThresholdMs": self.slow_threshold * 1e3,
        }

    # HTTP header propagation for cross-node traces
    # (tracing/tracing.go:18-28); the inject half is module-level.
    def extract_headers(self, headers: Dict[str, str]) -> Optional[TraceContext]:
        """TraceContext from incoming request headers, or None.  Header
        dicts may arrive with original casing; check both forms."""
        trace_id = headers.get("X-Trace-Id") or headers.get("x-trace-id")
        if not trace_id:
            return None
        span_id = headers.get("X-Span-Id") or headers.get("x-span-id") or ""
        return TraceContext(trace_id, span_id)


class NopTracer(Tracer):
    @contextmanager
    def start_span(self, name: str, parent=None, **tags):
        yield None

    def begin(self, name: str, parent=None, **tags):
        return None


# -- the stage clock ---------------------------------------------------------

# True while POST /debug/pprof/trace holds a profiler capture (the route
# sets and clears it).  Off, a stage costs this one attribute test more
# than its bookkeeping; on, every stage brackets its block with a live
# TraceAnnotation so the host's work lands on the device trace's clock.
capturing = False

# (path, stage) -> Histogram and stage -> legacy Histogram: handles
# resolved once (GIL-atomic dict reads), so a record pays the series'
# own lock and never the registry's.
_STAGE_HISTS: Dict[tuple, object] = {}
_LEGACY_HISTS: Dict[str, object] = {}
_HTTP_HIST = REGISTRY.histogram(METRIC_HTTP_REQUEST)


def _stage_hist(path: str, name: str):
    h = _STAGE_HISTS.get((path, name))
    if h is None:
        h = _STAGE_HISTS[(path, name)] = REGISTRY.histogram(
            METRIC_QUERY_STAGE,
            help="Per-stage host time of a query, socket to socket (seconds)",
            path=path, stage=name,
        )
    return h


def _annotation(name: str, path: Optional[str], tags: dict):
    """A live TraceMe for the calling thread (a TraceMe cannot be
    written after the fact, which is why ``stage`` wraps the work)."""
    from jax.profiler import TraceAnnotation

    return TraceAnnotation("pilosa." + name, path=path or "", **tags)


_NOTHING = nullcontext()


def mark(name: str, **tags):
    """``with tracing.mark(name, **tags):`` around a moment that is no
    stage of its own: a thread's sleep (``accum_wait``, ``select_wait``)
    or the part of a timestamp-form stage that a thread does work in
    (``read``, ``handoff``, ``write``).  A live ``pilosa.<name>``
    annotation while a capture runs, so that an idle gap can be put
    down to it (scripts/trace_gaps.py); nothing at all otherwise, and
    never a histogram."""
    if capturing:
        return _annotation(name, None, tags)
    return _NOTHING


def name_thread(name: Optional[str] = None):
    """Give the calling OS thread a name (the Python thread's, cut to
    the kernel's 15 characters): the profiler names a host line after
    its OS thread, and CPython before 3.14 leaves every thread it
    starts with the process's (``python``).  Linux only; elsewhere a
    no-op."""
    try:
        import ctypes

        name = name or threading.current_thread().name
        ctypes.CDLL(None).prctl(15, name.encode()[:15], 0, 0, 0)  # PR_SET_NAME
    except Exception:  # noqa: BLE001 — a nameless line is only less readable
        pass


class Ambient:
    """The calling thread's own span and plan as a stage rider: what a
    blocking (direct or host) call rides on."""

    __slots__ = ("span", "plan")

    def __init__(self):
        self.span = getattr(_LOCAL, "current", None)
        self.plan = plans_mod.current_plan()


class stage:
    """``with tracing.stage(name, path, riders, **tags):`` around one
    stage of a query, on the thread that does the work.  On exit it
    observes ``pilosa_query_stage_seconds{path,stage}`` (and, for the
    four legacy stages of the deferred path,
    ``pilosa_pipeline_stage_seconds{stage}``), records a
    ``pipeline.<name>`` child on each rider's span and stamps each
    distinct plan once; while a capture runs the block is also a
    ``pilosa.<name>`` TraceAnnotation carrying ``path`` and ``tags``.

    ``riders`` are objects with ``.span`` and ``.plan`` (the batcher's
    items).  Left out, a stage nested in another on the same thread
    rides with the outer's riders and path and becomes a child of the
    outer stage's span; with no outer it rides the thread's own span
    and plan (``Ambient``), and a path left open is the path of the
    stages inside it, else the one already stamped on the request's
    root span, else ``host``.  ``self_time=True`` observes the block
    minus the stages (and ``hole``s) inside it and leaves no span of
    its own — the executor's ``plan`` stage, whose time in the tree is
    the executor spans' own — so the stages inside it ride the thread's
    span again.  A block left by an exception records nothing.  A
    slotted class, not a @contextmanager: this sits on the per-query
    hot path (cf. plans.attach).

    ``under`` names the enclosing stage outright, whatever stage the
    thread is inside: ``RequestClock``'s ``encode`` runs on the thread
    that finished the request and belongs to that request's ``respond``,
    which no thread is inside."""

    __slots__ = ("name", "path", "riders", "tags", "t0", "t1", "inner",
                 "hole_s", "self_time", "shared", "observed", "_outer",
                 "_prev", "_ann")

    def __init__(self, name: str, path: Optional[str] = None, riders=None,
                 t0: Optional[float] = None, self_time: bool = False,
                 under: Optional["stage"] = None, **tags):
        self.name = name
        self.path = path
        self.riders = riders
        self.tags = tags
        self.t0 = t0
        self.t1 = None
        self.inner: Optional[list] = None
        self.hole_s = 0.0
        self.self_time = self_time
        self.shared = False  # rides (and nests) with the enclosing stage
        self._outer = under
        self._ann = None

    def _adopt(self, outer: Optional["stage"]):
        """Path and riders left open come from the enclosing stage."""
        if outer is not None and self.path is None:
            self.path = outer.path
        if self.riders is None:
            if outer is not None and not outer.self_time:
                self.riders = outer.riders
            else:
                self.riders = (Ambient(),)

    def __enter__(self):
        prev = self._prev = getattr(_LOCAL, "stage", None)
        outer = self._outer
        if outer is None:
            outer = self._outer = prev
        self._adopt(outer)
        _LOCAL.stage = self
        if capturing:
            self._ann = _annotation(self.name, self.path, self.tags)
            self._ann.__enter__()
        if self.t0 is None:
            self.t0 = time.monotonic()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.t1 = time.monotonic()
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        _LOCAL.stage = self._prev
        if exc_type is None:
            self._done()
        # A finished stage lives on in rings (spans, plans): it keeps no
        # way back to what enclosed it, or every tree would be a cycle
        # that only the collector's full pass frees.
        self._outer = self._prev = None
        return False

    def _done(self):
        """Record now, or with the enclosing stage when that ends."""
        outer = self._outer
        if outer is None:
            _emit(self, self.path or _open_path(self))
            return
        self.shared = self.riders is outer.riders
        if outer.inner is None:
            outer.inner = [self]
        else:
            outer.inner.append(self)

    def _to_span(self, parent: "Span"):
        """This finished stage as a ``pipeline.<name>`` child of
        ``parent``, the stages that rode with it as its children."""
        span = parent.record(
            "pipeline." + self.name, start=self.t0,
            duration=self.t1 - self.t0, **self.tags
        )
        for inner in self.inner or ():
            if inner.shared:
                inner._to_span(span)


def waited(name: str, path: Optional[str], t0: float, t1: float, riders):
    """The timestamp form of ``stage`` for a wait no thread performs
    (``queue_wait``, ``collect_wait``): records [t0, t1] as if a stage
    had wrapped it, under the calling thread's enclosing stage if there
    is one.  No annotation: a gap's owner is whatever span a host
    thread was inside meanwhile."""
    st = stage(name, path, riders, t0=t0)
    st.t1 = t1
    st._outer = getattr(_LOCAL, "stage", None)
    st._adopt(st._outer)
    st._done()
    st._outer = None  # as a stage that has exited


def hole(t0: float, t1: float):
    """[t0, t1] of the enclosing stage belongs to stages recorded on
    other threads (a blocking wait on the pipeline): a ``self_time``
    stage leaves it out."""
    outer = getattr(_LOCAL, "stage", None)
    if outer is not None:
        outer.hole_s += t1 - t0


def _root(span: "Span") -> "Span":
    while span.parent is not None:
        span = span.parent
    return span


def _open_path(st: "stage") -> str:
    """The path of a stage that was opened without one (the front end
    and the executor do not know which lane a call will take): the
    last nested stage's, else the one stamped on the request's root
    span by a stage recorded on another thread, else ``host``."""
    for inner in reversed(st.inner or ()):
        if inner.path is not None:
            return inner.path
    for r in st.riders:
        if r.span is not None:
            return _root(r.span).tags.get("path", "host")
    return "host"


def _emit(st: "stage", path: str, exemplar: Optional[str] = None):
    """Record one finished stage tree: its histograms now, and one
    append on each rider's span and on each distinct plan — the child
    spans and the plan's ``stagesMs`` are made from the tree when
    somebody looks (``Span.children``, ``QueryPlan.stages``)."""
    explicit = st.path is not None
    riders = st.riders
    if exemplar is None:
        for r in riders:
            if r.span is not None:
                exemplar = r.span.trace_id
                break
    path = _observe(st, path, exemplar)
    seen_plans = None
    for r in riders:
        span = r.span
        if span is not None and not st.self_time:
            if explicit:
                _root(span).tags["path"] = path
            span._staged.append(st)
        plan = r.plan
        if plan is not None:
            if seen_plans is None:
                seen_plans = {id(plan)}
            elif id(plan) in seen_plans:
                continue
            else:
                seen_plans.add(id(plan))
            plan._stage_trees.append(st)
    st.riders = None  # the tree outlives the query in the rings: drop the items


def _observe(st: "stage", path: str, exemplar: Optional[str]) -> str:
    """Observe the histograms of ``st`` and of the stages nested in it
    (those with riders of their own are recorded on their own)."""
    path = st.path = st.path or path
    observed = st.t1 - st.t0
    inner = st.inner
    if st.self_time:
        observed -= st.hole_s
        for i in inner or ():
            observed -= i.t1 - i.t0
        if observed < 0.0:
            observed = 0.0
    st.observed = observed
    name = st.name
    h = _STAGE_HISTS.get((path, name))
    if h is None:
        h = _stage_hist(path, name)
    h.observe(observed)
    if path == "deferred" and name in PIPELINE_STAGES:
        h = _LEGACY_HISTS.get(name)
        if h is None:
            h = _LEGACY_HISTS[name] = REGISTRY.histogram(
                METRIC_PIPELINE_STAGE, stage=name
            )
        h.observe(observed, exemplar=exemplar)
    if inner:
        for i in inner:
            if i.shared:
                _observe(i, path, exemplar)
                i.riders = None
            else:
                _emit(i, path, exemplar if not st.self_time else None)
    return path


_NO_TAGS: dict = {}


class _Record:
    """A stage known by its two timestamps, as ``RequestClock._record``
    records it: what ``Span.children``, ``QueryPlan.stages`` and the
    closure read of a finished stage tree, and nothing else (a request
    makes eight of them)."""

    __slots__ = ("name", "t0", "t1", "observed", "tags", "inner", "shared")

    def __init__(self, name: str, t0: float, t1: float, tags: dict = _NO_TAGS,
                 inner: Optional[list] = None, shared: bool = False):
        self.name = name
        self.t0 = t0
        self.t1 = t1
        self.observed = t1 - t0
        self.tags = tags
        self.inner = inner
        self.shared = shared

    _to_span = stage._to_span


def _covered(intervals, lo: float, hi: float) -> float:
    """Seconds of [lo, hi] inside the union of ``intervals``."""
    total, end = 0.0, lo
    for a, b in sorted(intervals):
        if b > hi:
            b = hi
        if a < end:
            a = end
        if b > a:
            total += b - a
            end = b
    return total


def _top_level(span: "Span"):
    """The stage intervals recorded on ``span``: its staged trees and,
    where somebody has walked it already, its ``pipeline.*`` children."""
    for st in list(span._staged):
        yield st.t0, st.t1
    for c in span._children:
        if c.name.startswith("pipeline.") and c.duration is not None:
            yield c.start, c.start + c.duration


class RequestClock:
    """One query request on the HTTP layer's clock: made at the first
    byte, handed to the handler (``headers[CLOCK]``) and the API
    (``QueryRequest.clock``), finished when the last byte of the reply
    has been handed to the socket.  It is a stage rider (``span``,
    ``plan``: the API sets both).  ``finish``, on the writer's thread,
    observes ``pilosa_http_request_seconds`` and stops the clock;
    ``settle`` then records, under the path the request's root span was
    stamped with, the stages only the HTTP layer sees:

    ``http_read``      first byte -> handler entered for the last time
      ``read``         ... -> the reactor's ``_dispatch`` entered
      ``handoff``      ... -> handler entered (``route=inline|pool``)
    ``prologue``       handler entered -> the executor entered
    ``epilogue``       the executor returned -> ``result_ready``, where no
                       drain answered (else that is ``complete_wait``)
    ``complete_wait``  the drain's ``decode`` ended -> ``result_ready``
    ``respond``        ``result_ready`` -> last byte handed to the socket
      ``encode``       the payload's encoding (``encoding()``: a real stage)
      ``respond_wake`` ... -> the reactor's ``_complete`` entered
      ``write``        ... -> ``finish``

    and ``unstaged``: the request's seconds less the union of every
    top-level stage recorded for it.  The threaded server has no
    reactor: no ``read`` / ``handoff`` / ``respond_wake`` there.  While
    the clock is open the server is occupied (``OCCUPIED``)."""

    __slots__ = ("t0", "t_dispatch", "route", "t_handler", "t_exec",
                 "t_executed", "t_decoded", "t_result", "t_complete", "t_done",
                 "span", "plan", "inner", "_mark", "_open")

    # As the stage that encloses ``encode`` (``stage(under=clock)``):
    # the path is the request's, known when the stages are recorded;
    # nothing rides yet.
    path = None
    riders = ()
    self_time = False

    def __init__(self, t0: float, t_dispatch: Optional[float] = None):
        self.t0 = t0
        self.t_dispatch = t_dispatch
        self.route = "inline"
        self.t_handler = None
        self.t_exec = None
        self.t_executed = None
        self.t_decoded = None
        self.t_result = None
        self.t_complete = None
        self.t_done = None
        self.span = None
        self.plan = None
        self.inner = None  # [the finished encode stage]
        self._mark = None
        self._open = True
        OCCUPIED.begin(t0)

    def _note(self, name: Optional[str] = None, **tags):
        """While a capture runs: leave the mark this request's thread
        is inside and enter ``name`` (the parts of ``handoff`` and
        ``prologue`` that a thread works in begin and end in different
        functions, so the clock carries the annotation between them)."""
        if self._mark is not None:
            self._mark.__exit__(None, None, None)
            self._mark = None
        if name is not None and capturing:
            self._mark = mark(name, **tags)
            self._mark.__enter__()

    def pooled(self):
        """A pool thread has taken the request (the pool route).  What
        went before since ``_dispatch`` was entered (the declined
        attempt on the reactor, then the pool's queue) is written on the
        mark of this thread's own part of ``handoff`` as ``waited_us``:
        an annotation cannot be written after the fact."""
        if capturing:
            self._note("handoff", route="pool", waited="handoff",
                       waited_us=int((time.monotonic() - self.t_dispatch) * 1e6))

    def handler_entered(self):
        # The last entry counts: a request the reactor's deferred
        # attempt declines enters the handler again on a pool thread.
        self.t_handler = time.monotonic()
        if capturing or self._mark is not None:
            self._note("prologue")

    def executing(self):
        """The executor is entered next (the last time counts, as for
        the handler): ``prologue`` ends."""
        if self._mark is not None:
            self._note()
        self.t_exec = time.monotonic()

    def executed(self):
        """The executor has returned (the last time counts): what the
        API does with the answer before the handler has it (the span's
        and the plan's finish, the plan's record) is ``epilogue``."""
        self.t_executed = time.monotonic()

    def result_ready(self, t_decoded: Optional[float] = None):
        """The handler has the result; ``t_decoded`` is when the
        drain's ``decode`` ended, where a drain answered."""
        self.t_result = time.monotonic()
        self.t_decoded = t_decoded

    def encoding(self) -> "stage":
        """``with clock.encoding():`` around the payload's encoding, on
        the thread that has the result (after ``result_ready``): a stage
        of its own, kept to be recorded under ``respond``."""
        return stage("encode", t0=self.t_result, under=self)

    def completing(self) -> int:
        """The writer has the rendered reply (the reactor's
        ``_complete``, the threaded sequencer's ``complete``); the
        microseconds it took to come here from the encoding's end."""
        now = self.t_complete = time.monotonic()
        since = self.inner[0].t1 if self.inner else self.t_result
        return int((now - since) * 1e6) if since is not None else 0

    def abandon(self):
        """The connection went before the reply: nothing is observed."""
        if self._open:
            self._open = False
            OCCUPIED.end()
        if self._mark is not None:
            self._note()

    def finish(self):
        """The last byte has been handed to the socket.  On the writer's
        thread (the reactor: the next request waits behind it) only the
        clock is stopped; the stages are recorded by ``settle``."""
        if not self._open:
            return
        self._open = False
        if self._mark is not None:  # a handler left by an error
            self._note()
        now = self.t_done = time.monotonic()
        OCCUPIED.end(now)
        _HTTP_HIST.observe(now - self.t0)
        if self.t_handler is not None:
            _FINISHED.append(self)
            if len(_FINISHED) >= SETTLE_AT:
                settle()

    def _record(self):
        """The stages and the closure of a finished request (``settle``)."""
        t0, now, t_handler = self.t0, self.t_done, self.t_handler
        span = self.span
        path = span.tags.get("path", "host") if span is not None else "host"
        reactor = self.t_dispatch is not None
        trees = [_Record("http_read", t0, t_handler, inner=[
            _Record("read", t0, self.t_dispatch, shared=True),
            _Record("handoff", self.t_dispatch, t_handler,
                    {"route": self.route}, shared=True),
        ] if reactor else None)]
        if self.t_exec is not None and self.t_exec >= t_handler:
            trees.append(_Record("prologue", t_handler, self.t_exec))
        t_result = self.t_result
        if t_result is not None:
            if self.t_decoded is not None:
                trees.append(_Record("complete_wait", self.t_decoded, t_result))
            elif self.t_executed is not None and self.t_executed >= t_handler:
                trees.append(_Record("epilogue", self.t_executed, t_result))
            parts, t = [], t_result
            if self.inner:
                encode = self.inner[0]
                encode.observed = encode.t1 - encode.t0
                parts.append(encode)
                t = encode.t1
            if self.t_complete is not None:
                if reactor:  # the reply crosses to the reactor's thread
                    parts.append(_Record("respond_wake", t, self.t_complete, shared=True))
                    t = self.t_complete
                parts.append(_Record("write", t, now, shared=True))
            trees.append(_Record("respond", t_result, now, inner=parts))
        plan = self.plan
        for tree in trees:
            _stage_hist(path, tree.name).observe(tree.observed)
            for part in tree.inner or ():
                _stage_hist(path, part.name).observe(part.observed)
            if span is not None:
                span._staged.append(tree)
            if plan is not None:
                plan._stage_trees.append(tree)
        if span is None:
            return
        span.tags["http_read_ms"] = round((t_handler - t0) * 1e3, 3)
        span.tags["http_ms"] = round((now - t0) * 1e3, 3)
        # The closure: what no stage recorded for this request holds.
        if plan is not None:
            intervals = [(st.t0, st.t1) for st in plan._stage_trees]
        else:
            intervals = _top_level(span)
        unstaged = max(0.0, now - t0 - _covered(intervals, t0, now))
        _stage_hist(path, "unstaged").observe(unstaged)
        span.tags["unstaged_ms"] = round(unstaged * 1e3, 3)


# Finished clocks whose stages are yet to be recorded, and how many may
# wait before the finishing thread records them itself (a server that
# never waits for the device: memo hits only).
_FINISHED: "deque[RequestClock]" = deque()
SETTLE_AT = 64


def settle():
    """Record the HTTP layer's stages of every request that has finished
    since the last call.  Called where a thread is about to wait for
    the device anyway (the collect worker's and the direct path's
    ``device_get``), so that the records (eight objects, eleven
    observations and a union a request) cost no request its place on
    the reactor; and wherever they are read (a scrape, ``/debug/traces``,
    ``/debug/plans``).  Any thread may call it; a clock is recorded
    once."""
    while _FINISHED:
        try:
            clock = _FINISHED.popleft()
        except IndexError:  # another thread took the last
            return
        clock._record()


def encoding(clock: Optional[RequestClock], t_decoded: Optional[float] = None):
    """``with tracing.encoding(req.clock):`` — ``result_ready`` and the
    ``encode`` stage of a request that has a clock; nothing without."""
    if clock is None:
        return _NOTHING
    clock.result_ready(t_decoded)
    return clock.encoding()


# The key under which the HTTP servers hand a request's clock to the
# handler in its headers dict: no header name holds a colon.
CLOCK = ":clock"


class Inflight:
    """Seconds in which a depth counter stood above zero: the union of
    the intervals [``begin``, ``end``].  ``INFLIGHT`` is the host having
    given the device anything at all (over all query dispatches, [jitted
    call returned, its device_get returned]); ``OCCUPIED`` the server
    holding a query request at all (a ``RequestClock`` made and not
    finished).  The union's closed part goes to the counter whenever the
    depth returns to zero (and at scrape time, ``flush``)."""

    def __init__(self, counter=None):
        self._lock = threading.Lock()
        self._depth = 0
        self._since = 0.0
        self._counter = counter or REGISTRY.counter(METRIC_ENGINE_DEVICE_INFLIGHT)

    @property
    def depth(self) -> int:
        return self._depth

    def begin(self, now: Optional[float] = None):
        with self._lock:
            if self._depth == 0:
                # Not before the last interval's end: a ``now`` read
                # earlier than this call may lie inside it.
                now = time.monotonic() if now is None else now
                if now > self._since:
                    self._since = now
            self._depth += 1

    def end(self, now: Optional[float] = None):
        with self._lock:
            self._depth -= 1
            if self._depth == 0:
                now = time.monotonic() if now is None else now
                if now > self._since:
                    self._counter.inc(now - self._since)
                    self._since = now

    def flush(self):
        """Count the open interval up to now (a scrape mid-drain)."""
        with self._lock:
            if self._depth > 0:
                now = time.monotonic()
                if now > self._since:
                    self._counter.inc(now - self._since)
                    self._since = now


INFLIGHT = Inflight()
OCCUPIED = Inflight(REGISTRY.counter(
    METRIC_HTTP_OCCUPIED,
    help="Seconds in which the server held a query request at all",
))


class GcClock:
    """The collector's pauses: one ``gc.callbacks`` hook, installed by a
    serving process for as long as it serves (``install`` /
    ``uninstall``, counted: two servers of one process share it).  A
    pass costs the hook two clock reads and a list append; ``flush``
    (scrape time) observes what is pending into
    ``pilosa_gc_pause_seconds{generation}``.  A pass of generation 2
    adds its ``gc_ms`` to the tags of the stage the allocating thread
    is inside, so the slow ring says which request paid; while a
    capture runs a pass of generation >= 1 is a ``pilosa.gc``
    annotation, the innermost stage of whatever it interrupts (the pass
    runs on the allocating thread, and no second pass starts inside it:
    one slot is enough)."""

    PENDING_MAX = 4096

    def __init__(self):
        self._lock = threading.Lock()
        self._refs = 0
        self._t = 0.0
        self._ann = None
        self._pending: list = []
        self._hists: Dict[int, object] = {}

    def install(self):
        with self._lock:
            self._refs += 1
            if self._refs == 1:
                for g in GC_GENERATIONS:
                    self._hists[g] = REGISTRY.histogram(
                        METRIC_GC_PAUSE,
                        help="Pauses of the Python collector, by generation (seconds)",
                        generation=str(g),
                    )
                gc.callbacks.append(self._on_gc)

    def uninstall(self):
        with self._lock:
            self._refs -= 1
            if self._refs == 0:
                try:
                    gc.callbacks.remove(self._on_gc)
                except ValueError:
                    pass
        self.flush()

    def _on_gc(self, phase: str, info: dict):
        if phase == "start":
            if capturing and info["generation"]:
                self._ann = _annotation("gc", None, {"generation": info["generation"]})
                self._ann.__enter__()
            self._t = time.monotonic()
            return
        seconds = time.monotonic() - self._t
        generation = info["generation"]
        pending = self._pending
        pending.append((generation, seconds))
        if generation:
            if self._ann is not None:
                self._ann.__exit__(None, None, None)
                self._ann = None
            if generation == 2:
                st = getattr(_LOCAL, "stage", None)
                if st is not None:
                    st.tags["gc_ms"] = round(
                        st.tags.get("gc_ms", 0.0) + seconds * 1e3, 3)
            if len(pending) > self.PENDING_MAX:
                self.flush()

    def flush(self):
        """Observe the pending pauses (appends race this only at the
        list's end, which the cut leaves alone)."""
        pending = self._pending
        n = len(pending)
        done = pending[:n]
        del pending[:n]
        for generation, seconds in done:
            h = self._hists.get(generation)
            if h is not None:
                h.observe(seconds)


GC = GcClock()

"""Pipelined multi-batch execution (round-6 tentpole): the
stage-decoupled CountBatcher keeps multiple fused batches genuinely in
flight; the executor/API/HTTP layers thread result futures through so
completion callbacks — not parked handler threads — resolve pending
responses; responses on a pipelined connection stay in request order;
mixed read+write streams stay correct.  Plus regressions for the
round-6 satellite fixes: _signature literal-only masking, resize
membership-before-NORMAL ordering, and join/leave queued during an
active resize job."""

import contextlib
import json
import socket
import threading
import time

import numpy as np
import pytest

from pilosa_tpu import pql
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.executor import Executor
from pilosa_tpu.ops import SHARD_WIDTH
from pilosa_tpu.parallel import MeshEngine, make_mesh
from pilosa_tpu.parallel.batcher import CountBatcher


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(8)


@pytest.fixture
def holder():
    h = Holder()
    h.open()
    idx = h.create_index("i")
    f = idx.create_field("f")
    ef = idx.existence_field()
    rows, cols = [], []
    rng = np.random.default_rng(7)
    for s in range(8):
        base = s * SHARD_WIDTH
        picks = rng.choice(SHARD_WIDTH, size=300, replace=False)
        for c in picks[:200]:
            rows.append(10)
            cols.append(base + int(c))
        for c in picks[100:]:
            rows.append(11)
            cols.append(base + int(c))
    f.import_bulk(rows, cols)
    ef.import_bulk([0] * len(cols), cols)
    return h


def _call(q):
    return pql.parse(q).calls[0]


# -- stage-decoupled pipeline: batches in flight ---------------------------


class _SlowDev:
    """A fake device future whose host readback blocks until the stub
    engine's release gate opens — models a batch executing on device /
    in the readback transport."""

    def __init__(self, eng, values):
        self._eng = eng
        self._values = values

    def __array__(self, dtype=None):
        self._eng.release.wait(30)
        with self._eng.lock:
            self._eng.unread -= 1
        return np.asarray(self._values, dtype=dtype or np.int32)


class _StubEngine:
    """count_many_async returns instantly (the dispatch stage never
    waits on the device); readbacks block until ``release`` opens, so
    the test can observe how many batches the pipeline keeps in flight."""

    def __init__(self):
        self.lock = threading.Lock()
        self.release = threading.Event()
        self.unread = 0
        self.max_unread = 0
        self.dispatched_groups = []

    def count_many_async(self, index, calls, shards_list):
        with self.lock:
            self.unread += 1
            self.max_unread = max(self.max_unread, self.unread)
        self.dispatched_groups.append([str(c) for c in calls])
        # Answer = the row id queried, so correctness is checkable.
        vals = [int(str(c).split("=")[1].rstrip(")")) for c in calls]
        return _SlowDev(self, vals)

    def count(self, index, call, shards):
        return int(str(call).split("=")[1].rstrip(")"))


def test_two_batches_genuinely_in_flight():
    """Device execution (an unread readback) of batch k overlaps both
    the DISPATCH of batch k+1 and the ACCUMULATION of batch k+2 — the
    round-6 pipeline guarantee (round 5 ran one batch at a time)."""
    eng = _StubEngine()
    b = CountBatcher(eng, max_inflight=4)
    # Distinct field names -> distinct structure signatures -> one
    # group (= one fused batch) each.
    wave1 = [b.submit_async("i", _call(f"Row(f{k}=5)"), [0]) for k in range(2)]
    deadline = time.monotonic() + 10
    while eng.unread < 2 and time.monotonic() < deadline:
        time.sleep(0.005)
    assert eng.unread >= 2, "second batch did not dispatch while first unread"
    # Accumulation keeps accepting while both batches are on "device":
    # a third group dispatches too (depth 4 > 2 in flight).
    wave2 = b.submit_async("i", _call("Row(f9=7)"), [0])
    deadline = time.monotonic() + 10
    while eng.unread < 3 and time.monotonic() < deadline:
        time.sleep(0.005)
    assert eng.unread >= 3
    eng.release.set()
    for it in wave1 + [wave2]:
        assert it.event.wait(30)
        assert it.error is None
    assert wave1[0].result == 5 and wave2.result == 7
    assert eng.max_unread >= 3
    snap = b.pipeline_snapshot()
    assert snap["gauges"]["inflight_max"] >= 3
    assert snap["depth"] == 4
    assert {"queue_wait", "lower_dispatch", "device_readback"} <= set(
        snap["stages"]
    )


def test_inflight_depth_is_bounded():
    """The dispatch stage blocks on the (depth+1)'th batch: with depth 2
    and 4 distinct groups queued, at most 2 are ever unread at once."""
    eng = _StubEngine()
    b = CountBatcher(eng, max_inflight=2)
    items = [
        b.submit_async("i", _call(f"Row(g{k}={k})"), [0]) for k in range(4)
    ]
    deadline = time.monotonic() + 10
    while eng.unread < 2 and time.monotonic() < deadline:
        time.sleep(0.005)
    time.sleep(0.25)  # give an over-eager dispatcher time to violate
    assert eng.max_unread <= 2, "pipeline exceeded its configured depth"
    eng.release.set()
    for k, it in enumerate(items):
        assert it.event.wait(30) and it.error is None
        assert it.result == k
    assert b.pipeline_snapshot()["gauges"]["inflight_max"] <= 2


# -- the accumulation window's close decision ------------------------------


def _closes(b, since=None):
    """The process-wide close counters by reason, or their rise since
    an earlier reading (no other batcher drains meanwhile)."""
    return {r: int(c.get()) - (since[r] if since else 0)
            for r, c in b._closes.items()}


class _VirtualWindow:
    """The real ``CountBatcher._drain_loop`` and ``_submit`` on a virtual
    clock, in the calling thread: arrivals happen at given times, a wait
    on the condition jumps the clock to its timeout or to the arrival
    that notifies, so every close time is exact and no case leans on the
    container's scheduling.  No stage worker runs: ``live`` and ``hot``
    are what the test says they are.  ``handoffs`` are (time, arrival
    times of the drain's items, how the window closed), in order."""

    def __init__(self, monkeypatch, arrivals, live=0, hot=True, max_batch=512):
        from pilosa_tpu.parallel import batcher as mod

        self.now = 1000.0
        self.arrivals = [self.now + a for a in arrivals]
        self.handoffs = []
        self.notified = False
        b = self.b = CountBatcher(_StubEngine(), max_batch=max_batch)
        b._workers_started = True  # the loop below is the only worker
        b._cond = self
        b._live = live
        b._last_fused = float("inf") if hot else 0.0
        self.call = _call("Row(f=1)")
        window = self

        class _Clock:
            @staticmethod
            def monotonic():
                return window.now

        class _Stage:
            def __init__(self, name, path, t0=None, **tags):
                assert (name, path) == ("accum_tail", "deferred")
                self.t0, self.tags = t0, tags

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                n = self.tags["batch"]
                times = window.submitted[window.taken:window.taken + n]
                window.taken += n
                assert times[-1] == self.t0  # t0 is the drain's last arrival
                window.handoffs.append((window.now, times, self.tags["reason"]))

        class _Tracing:
            stage = _Stage
            mark = staticmethod(lambda name, **tags: contextlib.nullcontext())
            current_span = staticmethod(lambda: None)
            name_thread = staticmethod(lambda name=None: None)

        self.submitted, self.taken = [], 0
        monkeypatch.setattr(mod, "time", _Clock)
        monkeypatch.setattr(mod, "tracing", _Tracing)
        before = _closes(b)
        b._drain_loop()
        self.closes = _closes(b, before)

    def _arrive(self):
        self.now = self.arrivals.pop(0)
        self.submitted.append(self.now)
        self.b._submit("i", self.call, [0], allow_direct=False)

    def notify_all(self):
        self.notified = True

    def wait(self, timeout=None):
        b = self.b
        b._lock.release()
        try:
            if timeout is None or timeout >= 60.0:  # the empty queue's wait
                if not self.arrivals:
                    b._stopped = True
                    return
                self._arrive()
                return
            end = self.now + timeout
            self.notified = False
            while self.arrivals and self.arrivals[0] <= end and not self.notified:
                self._arrive()
            if not self.notified:
                self.now = end
        finally:
            b._lock.acquire()

    def handoff_of_each_item(self):
        return [t for t, times, _ in self.handoffs for _ in times]


def _fixed_poll_rule(arrivals, live, hot, max_batch, window=0.15, poll=0.005):
    """The window this one replaced, as a reference on the same virtual
    clock: wake on the first arrival, sleep in fixed ``poll`` steps,
    close when one whole step passed with the depth unchanged.  Returns
    each item's hand-off time."""
    out, taken, now = [], 0, 0.0

    def depth():
        return sum(1 for a in arrivals[taken:] if a <= now)

    while taken < len(arrivals):
        now = max(now, arrivals[taken])
        if depth() > 1 or live or hot:
            deadline, prev = now + window, -1
            while now < deadline:
                d = depth()
                if d >= max_batch or d == prev:
                    break
                prev = d
                now += poll
        n = min(depth(), max_batch)
        out += [now] * n
        taken += n
    return out


def _burst(n, gap, start=0.0):
    return [start + k * gap for k in range(n)]


def _random_schedule(seed):
    """Bursts of 1-24 arrivals, 0.05-8 ms apart, 0-40 ms between bursts."""
    rng = np.random.default_rng(seed)
    out, t = [], 0.0
    for _ in range(int(rng.integers(1, 6))):
        t += float(rng.uniform(0.0, 0.040))
        gap = float(rng.choice([5e-5, 3e-4, 5e-4, 2e-3, 4.9e-3, 5.1e-3, 8e-3]))
        for _ in range(int(rng.integers(1, 25))):
            out.append(t)
            t += gap * float(rng.uniform(0.5, 1.5))
    return out


_MS = 1e-3
# (arrivals, live, hot, max_batch) -> what the window must have done.
_WINDOW_CASES = {
    # (i) a burst of 16 at 0.5 ms gaps into an idle pipe: ONE drain of 16,
    # closed a quiet interval of 4 gaps after its last arrival — inside
    # the ceiling, well under the 5-10 ms the fixed poll took.
    "burst16_idle": dict(
        arrivals=_burst(16, 0.5 * _MS), sizes=[16], reasons=["quiet"],
        tails=[2.0 * _MS],
    ),
    # The floor: gaps far under a thread wake-up still wait QUIET_MIN.
    "burst16_idle_tiny_gaps": dict(
        arrivals=_burst(16, 0.02 * _MS), sizes=[16], reasons=["quiet"],
        tails=[CountBatcher.QUIET_MIN],
    ),
    # A burst earns the short patience over its first QUIET_SPAN gaps (one
    # it has not shown counts as the ceiling's): two arrivals that happen
    # to come 50 us apart do not send the pair off ahead of their peers.
    "early_gaps_do_not_split": dict(
        arrivals=[0.0, 0.05 * _MS, 1.5 * _MS, 1.55 * _MS, 3.0 * _MS],
        sizes=[5], reasons=["quiet"], tails=[4.0 * _MS],
    ),
    # (ii) arrivals at least a ceiling apart close a ceiling after each:
    # never later than the fixed poll did, idle or busy.
    "sparse_idle": dict(
        arrivals=_burst(4, 6.0 * _MS), sizes=[1, 1, 1, 1],
        reasons=["quiet"] * 4, tails=[CountBatcher.QUIET_MAX] * 4,
    ),
    "sparse_busy": dict(
        arrivals=_burst(4, 6.0 * _MS), live=1, sizes=[1, 1, 1, 1],
        reasons=["quiet"] * 4, tails=[CountBatcher.QUIET_MAX] * 4,
    ),
    # (iii) while a batch is in flight the window keeps the ceiling's
    # patience: two runs 2.5 ms apart coalesce into one drain, as they did
    # under the fixed poll (an idle pipe lets the first run go).
    "pause_busy_coalesces": dict(
        arrivals=_burst(10, 0.3 * _MS) + _burst(5, 0.3 * _MS, 5.2 * _MS),
        live=1, sizes=[15], reasons=["quiet"], tails=[CountBatcher.QUIET_MAX],
    ),
    "pause_idle_lets_go": dict(
        arrivals=_burst(10, 0.3 * _MS) + _burst(5, 0.3 * _MS, 5.2 * _MS),
        sizes=[10, 5], reasons=["quiet", "quiet"], tails=[1.2 * _MS, 3.1 * _MS],
    ),
    # (iv) continuous arrivals run to max_batch (the filling arrival wakes
    # the worker: no tail) or to the outer deadline, as before.
    "continuous_full": dict(
        arrivals=_burst(192, 0.2 * _MS), max_batch=64, sizes=[64, 64, 64],
        reasons=["full"] * 3, tails=[0.0] * 3,
    ),
    "continuous_deadline_busy": dict(
        arrivals=_burst(100, 2.0 * _MS), live=1, sizes=[76, 24],
        reasons=["deadline", "quiet"], tails=[0.0, CountBatcher.QUIET_MAX],
    ),
    "continuous_deadline_idle": dict(
        arrivals=_burst(100, 2.0 * _MS), sizes=[76, 24],
        reasons=["deadline", "quiet"], tails=[0.0, CountBatcher.QUIET_MAX],
    ),
    # (v) a lone query in an idle pipe leaves at once: no window, no tail.
    "lone_idle": dict(
        arrivals=[0.0], hot=False, sizes=[1], reasons=["idle_lone"],
        tails=[0.0],
    ),
    # Inside the hot window a lone query waits the ceiling for peers.
    "lone_hot": dict(
        arrivals=[0.0], sizes=[1], reasons=["quiet"],
        tails=[CountBatcher.QUIET_MAX],
    ),
}


@pytest.mark.parametrize("case", sorted(_WINDOW_CASES))
def test_accumulation_window_close_decision(case, monkeypatch):
    spec = dict(_WINDOW_CASES[case])
    arrivals = spec["arrivals"]
    live, hot = spec.get("live", 0), spec.get("hot", True)
    max_batch = spec.get("max_batch", 512)
    w = _VirtualWindow(monkeypatch, arrivals, live, hot, max_batch)
    assert [len(times) for _, times, _ in w.handoffs] == spec["sizes"]
    assert [reason for _, _, reason in w.handoffs] == spec["reasons"]
    tails = [t - times[-1] for t, times, _ in w.handoffs]
    assert tails == pytest.approx(spec["tails"], abs=1e-9)
    assert sum(w.closes.values()) == len(w.handoffs)
    for reason in set(spec["reasons"]):
        assert w.closes[reason] == spec["reasons"].count(reason)
    # No window outlasts the ceiling after its last arrival, and no item
    # is handed off later than the fixed poll handed it off.
    assert max(tails) <= CountBatcher.QUIET_MAX + 1e-9
    was = _fixed_poll_rule(arrivals, live, hot, max_batch)
    now = [t - 1000.0 for t in w.handoff_of_each_item()]
    assert len(now) == len(was) == len(arrivals)
    assert all(n <= o + 1e-9 for n, o in zip(now, was)), (now, was)


@pytest.mark.parametrize("live", [0, 1], ids=["idle", "busy"])
def test_accumulation_window_never_later_than_fixed_poll(live, monkeypatch):
    """Over 150 random schedules of bursts and pauses: every item leaves
    no later than the fixed poll would have sent it, and while a batch is
    in flight the drains are never more, nor smaller, than they were."""
    for seed in range(150):
        arrivals = _random_schedule(seed)
        with monkeypatch.context() as mp:
            w = _VirtualWindow(mp, arrivals, live=live, max_batch=64)
        was = _fixed_poll_rule(arrivals, live, True, 64)
        now = [t - 1000.0 for t in w.handoff_of_each_item()]
        assert len(now) == len(arrivals), seed
        late = [(n, o) for n, o in zip(now, was) if n > o + 1e-9]
        assert not late, (seed, late[:3])


def test_accumulation_window_on_real_threads():
    """The same decision with the real condition, clock and workers:
    sixteen back-to-back submits into an idle pipe, then a lone one
    after the hot window.  Only what no scheduling hiccup can change is
    asserted: everything resolves, each window is counted once with its
    reason, and the stage clock saw one ``accum_tail`` a drain."""
    from pilosa_tpu.util.stats import METRIC_QUERY_STAGE, REGISTRY

    eng = _StubEngine()
    eng.release.set()
    b = CountBatcher(eng)
    hist = REGISTRY.histogram(METRIC_QUERY_STAGE, path="deferred", stage="accum_tail")
    count0 = hist.snapshot()["count"]
    before = _closes(b)
    try:
        items = [b.submit_async("i", _call(f"Row(f={k})"), [0]) for k in range(16)]
        for k, it in enumerate(items):
            assert it.event.wait(30) and it.error is None and it.result == k
        time.sleep(CountBatcher.HOT_WINDOW + 0.1)
        lone = b.submit_async("i", _call("Row(f=99)"), [0])
        assert lone.event.wait(30) and lone.result == 99
        closes = _closes(b, before)
        assert closes["idle_lone"] >= 1  # the lone one, at least
        assert closes["full"] == closes["deadline"] == 0
        assert sum(closes.values()) == b.batches
        assert b.batched_queries == 17
        deadline = time.monotonic() + 10  # the stage ends after the hand-off
        while hist.snapshot()["count"] - count0 < b.batches:
            assert time.monotonic() < deadline
            time.sleep(0.005)
        assert hist.snapshot()["count"] - count0 == b.batches
    finally:
        b.stop()


# -- signature regression (satellite: literal-only masking) ----------------


def test_signature_masks_only_argument_literals():
    sig = CountBatcher._signature
    # Digit runs inside IDENTIFIERS are structure: f1 and f2 are
    # different fields with different stacks and must not share a group.
    assert sig("i", _call("Row(f1=3)")) != sig("i", _call("Row(f2=3)"))
    # Literals in argument position are data: same program structure.
    assert sig("i", _call("Row(f1=3)")) == sig("i", _call("Row(f1=4)"))
    assert sig("i", _call("Row(f=3)")) == sig("i", _call("Row(f=999)"))
    assert sig("i", _call("Intersect(Row(f=10), Row(f=11))")) == sig(
        "i", _call("Intersect(Row(f=3), Row(f=4))")
    )
    # BSI conditions mask their bound values too.
    assert sig("i", _call("Range(v > 300)")) == sig("i", _call("Range(v > 7)"))
    # Timestamp literals are program structure (view cover), not data.
    assert sig(
        "i", _call("Range(t=7, 2018-01-01T00:00, 2018-04-01T00:00)")
    ) != sig("i", _call("Range(t=7, 2018-01-01T00:00, 2018-02-01T00:00)"))


def test_digit_field_batches_fuse_correctly(holder, mesh):
    """End-to-end: digit-bearing field names group separately but still
    answer correctly through the batcher."""
    idx = holder.index("i")
    f1 = idx.create_field("f1")
    f1.import_bulk([3] * 50, list(range(50)))
    f2 = idx.create_field("f2")
    f2.import_bulk([3] * 20, list(range(0, 200, 10)))
    eng = MeshEngine(holder, mesh)
    b = eng.batcher()
    shards = list(range(8))
    items = [
        b.submit_async("i", _call("Row(f1=3)"), shards),
        b.submit_async("i", _call("Row(f2=3)"), shards),
    ]
    for it in items:
        assert it.event.wait(60) and it.error is None
    assert items[0].result == 50
    assert items[1].result == 20


# -- executor/API futures ---------------------------------------------------


def test_execute_async_matches_sync(holder, mesh):
    eng = MeshEngine(holder, mesh)
    ex = Executor(holder, mesh_engine=eng)
    multi = (
        "Count(Row(f=10))"
        "Count(Intersect(Row(f=10), Row(f=11)))"
        "Count(Union(Row(f=10), Row(f=11)))"
    )
    want = ex.execute("i", multi).results
    fut, _ = ex.execute_async("i", multi)
    assert fut is not None
    assert fut.result(60).results == want


def test_execute_async_declines_non_count(holder, mesh):
    eng = MeshEngine(holder, mesh)
    ex = Executor(holder, mesh_engine=eng)
    assert ex.execute_async("i", "TopN(f, n=2)")[0] is None
    assert ex.execute_async("i", "Set(1, f=10)")[0] is None
    assert ex.execute_async("i", "Count(Row(f=10))Set(1, f=10)")[0] is None
    plain = Executor(holder)  # no mesh engine: nothing to pipeline
    assert plain.execute_async("i", "Count(Row(f=10))")[0] is None


def test_execute_async_error_converges_to_sync(holder, mesh):
    """An async item that fails at lower time falls back to the sync
    path, so both paths surface the SAME outcome (here: the host path's
    field-not-found error, not a pipeline-internal one)."""
    eng = MeshEngine(holder, mesh)
    ex = Executor(holder, mesh_engine=eng)
    q = "Count(Intersect(Row(f=10), Row(missingfield=1)))"
    try:
        ex.execute("i", q)
        sync_err = None
    except Exception as e:  # noqa: BLE001
        sync_err = type(e)
    fut, _ = ex.execute_async("i", q)
    assert fut is not None
    if sync_err is None:
        fut.result(60)
    else:
        with pytest.raises(sync_err):
            fut.result(60)


def test_execute_async_callback_fires(holder, mesh):
    eng = MeshEngine(holder, mesh)
    ex = Executor(holder, mesh_engine=eng)
    fired = threading.Event()
    out = []
    fut, _ = ex.execute_async("i", "Count(Row(f=10))")
    fut.add_done_callback(lambda f: (out.append(f.result(0).results), fired.set()))
    assert fired.wait(60)
    assert out[0] == ex.execute("i", "Count(Row(f=10))").results


# -- mixed read+write streams ----------------------------------------------


def test_mixed_read_write_stream_stays_correct(holder, mesh):
    """A writer adds bits while a reader streams deferred Counts: every
    observed count is monotone nondecreasing (adds only — the engine's
    dispatch lock orders scatter-sync against batched dispatch), and
    the quiesced pipeline answer equals the host executor's."""
    idx = holder.index("i")
    f = idx.field("f")
    eng = MeshEngine(holder, mesh)
    ex = Executor(holder, mesh_engine=eng)
    q = "Count(Union(Row(f=10), Row(f=11)))"
    base = ex.execute_async("i", q)[0].result(60).results[0]

    stop = threading.Event()
    errors, seen = [], []

    def writer():
        try:
            n = 0
            while not stop.is_set() and n < 40:
                n += 1
                cols = [
                    s * SHARD_WIDTH + 5000 + (n * 13 + s) % 3000
                    for s in range(8)
                ]
                f.import_bulk([10] * len(cols), cols)
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    def reader():
        try:
            while not stop.is_set():
                fut, _ = ex.execute_async("i", q)
                assert fut is not None
                seen.append(fut.result(60).results[0])
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    w = threading.Thread(target=writer)
    r = threading.Thread(target=reader)
    w.start()
    r.start()
    w.join(60)
    time.sleep(0.1)
    stop.set()
    r.join(60)
    assert not w.is_alive() and not r.is_alive(), "worker deadlocked"
    assert not errors, errors
    assert seen and seen[0] >= base
    for a, b in zip(seen, seen[1:]):
        assert b >= a, (a, b)
    plain = Executor(holder)
    assert (
        ex.execute_async("i", q)[0].result(60).results
        == plain.execute("i", q).results
    )


# -- HTTP deferral ----------------------------------------------------------


def _serve(holder, mesh):
    from pilosa_tpu.api import API
    from pilosa_tpu.net import serve

    eng = MeshEngine(holder, mesh)
    api = API(holder=holder, mesh_engine=eng)
    srv, _thread = serve(api, port=0)
    return eng, api, srv


def test_http_deferred_counts_resolve_and_report(holder, mesh):
    """Concurrent HTTP Counts ride the deferred path: correct answers,
    fused batches, and pipeline telemetry visible at /debug/vars."""
    import urllib.request

    eng, api, srv = _serve(holder, mesh)
    uri = f"http://localhost:{srv.server_address[1]}"
    try:
        q = b"Count(Intersect(Row(f=10), Row(f=11)))"

        def once():
            req = urllib.request.Request(
                f"{uri}/index/i/query", data=q, method="POST"
            )
            return json.loads(
                urllib.request.urlopen(req, timeout=60).read()
            )["results"][0]

        want = once()
        results, errs = [], []

        def client():
            try:
                for _ in range(4):
                    results.append(once())
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        threads = [threading.Thread(target=client) for _ in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert not errs
        assert len(results) == 48 and set(results) == {want}
        assert eng._batcher is not None and eng._batcher.batches > 0
        dbg = json.loads(
            urllib.request.urlopen(f"{uri}/debug/vars", timeout=30).read()
        )
        assert "pipeline" in dbg
        assert dbg["pipeline"]["batchedQueries"] > 0
        assert dbg["pipeline"]["depth"] >= 1
    finally:
        srv.shutdown()


def test_http_pipelined_connection_keeps_order(holder, mesh):
    """SIX requests sent back-to-back on ONE connection before reading:
    deferred Counts interleaved with synchronous routes come back in
    request order with the right bodies (the per-connection response
    sequencer), proving the handler thread is free to read pipelined
    requests while earlier queries are still on device."""
    eng, api, srv = _serve(holder, mesh)
    port = srv.server_address[1]
    try:
        count_q = b"Count(Row(f=10))"
        want = api.query(
            __import__(
                "pilosa_tpu.api", fromlist=["QueryRequest"]
            ).QueryRequest("i", count_q.decode())
        ).results[0]

        def post(body):
            return (
                b"POST /index/i/query HTTP/1.1\r\nHost: l\r\n"
                b"Content-Type: application/json\r\n"
                b"Content-Length: " + str(len(body)).encode() + b"\r\n\r\n"
                + body
            )

        get_version = b"GET /version HTTP/1.1\r\nHost: l\r\n\r\n"
        reqs = [post(count_q), get_version, post(count_q), post(count_q),
                get_version, post(count_q)]
        s = socket.create_connection(("localhost", port), timeout=60)
        try:
            s.sendall(b"".join(reqs))
            fh = s.makefile("rb")
            bodies = []
            for _ in reqs:
                line = fh.readline()
                assert line.startswith(b"HTTP/1.1 200"), line
                clen = 0
                while True:
                    h = fh.readline()
                    if h in (b"\r\n", b""):
                        break
                    if h.lower().startswith(b"content-length:"):
                        clen = int(h.split(b":")[1])
                bodies.append(json.loads(fh.read(clen)))
        finally:
            s.close()
        assert [b.get("results", [None])[0] for b in bodies] == [
            want, None, want, want, None, want
        ]
        assert "version" in bodies[1] and "version" in bodies[4]
    finally:
        srv.shutdown()


# -- resize satellite regressions -------------------------------------------


class _RecordingClient:
    """Cluster client stub: records every broadcast with the sender's
    membership + state AT SEND TIME (the ordering under test)."""

    def __init__(self, cluster_ref, log):
        self._cluster_ref = cluster_ref
        self._log = log

    def send_message(self, msg):
        c = self._cluster_ref[0]
        self._log.append(
            (msg.get("type"), sorted(n.id for n in c.nodes), c.state)
        )


def _make_cluster(tmp_path, log):
    from pilosa_tpu.cluster.cluster import Cluster, Node

    holder = Holder()
    holder.open()
    idx = holder.create_index("i")
    f = idx.create_field("f")
    rows, cols = [], []
    for s in range(8):
        rows.append(1)
        cols.append(s * SHARD_WIDTH)
    f.import_bulk(rows, cols)
    ref = []
    c = Cluster(
        Node("n1", "http://n1", is_coordinator=True),
        path=str(tmp_path / "topology"),
        client_factory=lambda uri: _RecordingClient(ref, log),
    )
    ref.append(c)
    c.holder = holder
    c.state = "NORMAL"
    return c


def test_resize_applies_membership_before_normal(tmp_path, monkeypatch):
    """On a successful join resize the membership change + node-status
    broadcast land BEFORE the set-state NORMAL broadcast: a peer must
    never observe NORMAL while still holding the pre-resize topology
    (the lost-write window)."""
    from pilosa_tpu.cluster.cluster import Cluster, Node

    log = []
    c = _make_cluster(tmp_path, log)

    def deliver(self, node, ins):
        self.mark_resize_complete({"jobId": ins["jobId"], "node": ins["node"]})
        return True

    monkeypatch.setattr(Cluster, "_deliver_instruction", deliver)
    c.add_node(Node("n2", "http://n2"))
    assert [n.id for n in c.nodes] == ["n1", "n2"]
    assert c.state == "NORMAL"
    types = [t for t, _m, _s in log]
    assert "node-status" in types and "set-state" in types
    status_i = types.index("node-status")
    normal_i = max(
        i for i, (t, _m, s) in enumerate(log)
        if t == "set-state" and s != "RESIZING"
    )
    assert status_i < normal_i, log
    # At node-status time the joiner was already a member and the
    # cluster had NOT yet left RESIZING.
    _t, members, state = log[status_i]
    assert members == ["n1", "n2"]
    assert state == "RESIZING"


def test_join_during_resize_is_queued_not_dropped(tmp_path, monkeypatch):
    """A join arriving while a resize job is running queues and lands
    once the job finishes (round-6 satellite: it was silently dropped)."""
    from pilosa_tpu.cluster.cluster import Cluster, Node

    log = []
    c = _make_cluster(tmp_path, log)
    gate = threading.Event()
    first = threading.Event()

    def deliver(self, node, ins):
        if not first.is_set():
            first.set()
            gate.wait(30)
        self.mark_resize_complete({"jobId": ins["jobId"], "node": ins["node"]})
        return True

    monkeypatch.setattr(Cluster, "_deliver_instruction", deliver)
    t = threading.Thread(target=lambda: c.add_node(Node("n2", "http://n2")))
    t.start()
    assert first.wait(30), "first resize never delivered its instruction"
    # Second join arrives mid-job: must queue, not vanish.
    c.add_node(Node("n3", "http://n3"))
    assert c.node_by_id("n3") is None  # not yet — job 1 still running
    assert c._pending_node_actions, "join was dropped, not queued"
    gate.set()
    t.join(30)
    deadline = time.monotonic() + 30
    while c.node_by_id("n3") is None and time.monotonic() < deadline:
        time.sleep(0.02)
    assert c.node_by_id("n3") is not None, "queued join never landed"
    assert [n.id for n in c.nodes] == ["n1", "n2", "n3"]
    # Membership lands while job 2 is still RESIZING (by design); the
    # job's epilogue restores NORMAL moments later.
    while c.state != "NORMAL" and time.monotonic() < deadline:
        time.sleep(0.02)
    assert c.state == "NORMAL"

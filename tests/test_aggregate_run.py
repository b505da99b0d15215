"""A run of consecutive Sum/Min/Max calls of one request is dispatched
together on the batcher's direct path and read back once
(Executor._mesh_aggregate_run -> CountBatcher.submit_ops ->
_direct_ops): every shape of request answers, result for result, as the
per-call path does on the same data; a lone caller's run of three pays
one device_get; the plan of a profiled request keeps one device-path op
a call."""

import json
import threading
import time
import types
import urllib.request

import numpy as np
import pytest

from pilosa_tpu import pql
from pilosa_tpu.core import fragment as frag_mod
from pilosa_tpu.core.field import FieldOptions
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.executor import Executor
from pilosa_tpu.executor.executor import ExecOptions
from pilosa_tpu.ops import SHARD_WIDTH
from pilosa_tpu.parallel import MeshEngine, make_mesh
from pilosa_tpu.util import plans
from pilosa_tpu.util.stats import (
    METRIC_ENGINE_DRAIN_REQUESTS,
    METRIC_QUERY_STAGE,
    REGISTRY,
)

SHARDS = 4
ALL = list(range(SHARDS))


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(4)


def _holder():
    """Set field f (rows 10-13) and int field v over four shards; the
    same data every time it is called."""
    h = Holder()
    h.open()
    idx = h.create_index("i")
    f = idx.create_field("f")
    v = idx.create_field("v", FieldOptions(type="int", min=0, max=255))
    rng = np.random.default_rng(30)
    rows, cols, vals = [], [], []
    for s in range(SHARDS):
        picks = rng.choice(SHARD_WIDTH, size=200, replace=False)
        for k, c in enumerate(picks):
            rows.append(10 + k % 4)
            cols.append(s * SHARD_WIDTH + int(c))
            vals.append(int(rng.integers(0, 256)))
    f.import_bulk(rows, cols)
    v.import_values(cols, vals)
    idx.existence_field().import_bulk([0] * len(cols), cols)
    return h, cols, rows


class _Side:
    """One holder with its engine and executor."""

    def __init__(self, mesh):
        self.holder, self.cols, self.rows = _holder()
        self.eng = MeshEngine(self.holder, mesh)
        self.ex = Executor(self.holder, mesh_engine=self.eng)

    def per_call(self, calls):
        """Every call a request of its own, in order: the per-call path."""
        return [self.ex.execute("i", c).results[0] for c in calls]

    def request(self, calls):
        return self.ex.execute("i", " ".join(calls)).results

    def close(self):
        self.eng.close()


@pytest.fixture
def twins(mesh):
    """Two sides over equal data: the reference runs call by call on
    one, the run whole on the other."""
    ref, run = _Side(mesh), _Side(mesh)
    yield ref, run
    ref.close()
    run.close()


def _stage_count(stage, path="direct"):
    h = REGISTRY.get_histogram(METRIC_QUERY_STAGE, path=path, stage=stage)
    return h.count if h is not None else 0


def _drain_requests():
    return sum(
        REGISTRY.counter(
            METRIC_ENGINE_DRAIN_REQUESTS, op=op, path="aggregate"
        ).get()
        for op in ("Sum", "Min", "Max")
    )


def _sum(row):
    return f"Sum(Row(f={row}), field=v)"


class _StubCluster:
    """This node owns every shard but ``remote``."""

    state = "NORMAL"
    replica_n = 1

    def __init__(self, remote):
        self.node = types.SimpleNamespace(id="n0")
        self._remote = set(remote)

    def owns_shard(self, node_id, index, shard):
        return shard not in self._remote


def _cluster_of_one_holder(side, remote):
    """A stub cluster whose remote node serves from this very holder:
    the mapper's RPC is the host loop over the same fragments."""
    side.ex.cluster = _StubCluster(remote)

    def map_reduce(index, shards, call, opt, map_fn, reduce_fn):
        result = None
        for s in shards:
            result = reduce_fn(result, map_fn(s))
        return result

    side.ex.map_reduce = map_reduce


def _unlowerable(side, text):
    """Lowering the filter ``text`` raises as an argument-shape error
    does: only at lower time, past the static pre-screen."""
    lower = side.eng._lower_filter

    def _lower_filter(index, filter_call, lw):
        if filter_call is not None and str(filter_call) == text:
            raise ValueError("planted: no lowering for this filter")
        return lower(index, filter_call, lw)

    side.eng._lower_filter = _lower_filter


def _plan_ops(side, fn, calls):
    plan = plans.begin("i", " ".join(calls))
    with plans.attach(plan):
        out = fn(calls)
    return out, plan


# name -> (calls, readbacks the run's request pays on the direct path,
#          set-up applied to both sides or None)
CASES = {
    "three_sums": ([_sum(10), _sum(11), _sum(12)], 1, None),
    "sum_min_max": (
        [_sum(10), "Min(Row(f=11), field=v)", "Max(field=v)"], 1, None),
    "filtered": (
        ["Sum(Intersect(Row(f=10), Range(v > 100)), field=v)",
         "Max(Union(Row(f=11), Row(f=12)), field=v)",
         "Min(Not(Row(f=13)), field=v)", "Sum(field=v)"], 1, None),
    # Two runs of one: the write ends the first and the second sees it
    # (repaired from the first's memo entry, so no second readback).
    "sums_round_a_set": ([_sum(10), "Set({col}, f=10)", _sum(10)], 1, None),
    "sum_pairs_round_a_set": (
        [_sum(10), _sum(11), "Set({col}, f=10)", _sum(12), _sum(10)], 2, None),
    # The whole run falls back: the two that lower read back alone.
    "unlowerable_in_the_middle": (
        [_sum(10), "Sum(Xor(Row(f=10), Row(f=11)), field=v)", _sum(12)], 2,
        lambda side: _unlowerable(side, "Xor(Row(f=10), Row(f=11))")),
    # The hit is answered and leaves the run; the other two go together.
    "one_a_memo_hit": (
        [_sum(10), _sum(11), _sum(12)], 1,
        lambda side: side.per_call([_sum(11)])),
    # Declined whole: each call's local part reads back alone.
    "remote_shards": (
        [_sum(10), "Min(Row(f=11), field=v)", _sum(12)], 3,
        lambda side: _cluster_of_one_holder(side, {SHARDS - 1})),
    "no_device_work": (
        [_sum(10), "Sum(Row(f=10), field=nosuch)", _sum(12)], 1, None),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_run_answers_as_the_per_call_path(twins, name):
    ref, run = twins
    calls, readbacks, setup = CASES[name]
    # A column of row 11 with a value: Set(col, f=10) moves Sum(Row(f=10)).
    col = ref.cols[ref.rows.index(11)]
    calls = [c.format(col=col) for c in calls]
    if setup is not None:
        setup(ref)
        setup(run)
    want, ref_plan = _plan_ops(ref, ref.per_call, calls)
    before = _stage_count("device_get")
    got, run_plan = _plan_ops(run, run.request, calls)
    assert got == want
    assert _stage_count("device_get") - before == readbacks
    if name == "sums_round_a_set":
        assert got[1] is True and got[2].count == got[0].count + 1
    if name == "sum_pairs_round_a_set":
        assert got[2] is True and got[4].count == got[0].count + 1
    if name == "one_a_memo_hit":
        assert [o.get("memo") for o in run_plan.ops].count("hit") == 1
    if name in ("unlowerable_in_the_middle", "remote_shards"):
        # The declined attempt's ops were unwound: the plan reads as the
        # per-call path's, no op twice.
        assert [(o.get("op"), o.get("path")) for o in run_plan.ops] == [
            (o.get("op"), o.get("path")) for o in ref_plan.ops]
    # Nothing of either request is left on the pooled thread.
    assert plans.take_dispatch_note() is None


def test_busy_pipe_queues_every_call_of_the_run(twins):
    """While another caller holds the direct path the run's calls queue,
    each an item of its own, and the drain answers them."""
    ref, run = twins
    calls = [_sum(10), "Min(Row(f=11), field=v)", _sum(12)]
    want = ref.per_call(calls)
    b = run.eng.batcher()
    b._busy = True  # a leader is in flight
    before, queued = _stage_count("device_get"), b.batched_queries
    try:
        got = run.request(calls)
    finally:
        b._busy = False
    assert got == want
    assert b.batched_queries - queued == 3
    assert _stage_count("device_get") == before  # none on the direct path


def test_identical_call_in_flight_declines_the_run(twins):
    """A twin of one call already flying from another request: the run
    declines whole, untouched, and the per-call path joins the twin."""
    ref, run = twins
    calls = [_sum(10), _sum(11)]
    parsed = pql.parse(" ".join(calls)).calls
    sf = run.ex._sflight
    key = run.ex._aggregate_flight(frag_mod.WRITE_SEQ.v, "i", parsed[1], ALL)
    release = threading.Event()
    other = threading.Thread(
        target=sf.do, args=(key, lambda: release.wait(60) and (7, 1)))
    other.start()
    try:
        deadline = time.monotonic() + 30
        while key not in sf._flights and time.monotonic() < deadline:
            time.sleep(0.01)
        before, led = _stage_count("dispatch"), sf.flights
        assert run.ex._mesh_aggregate_run(
            "i", parsed, ALL, ExecOptions()) is None
        assert (_stage_count("dispatch"), sf.flights) == (before, led)
    finally:
        release.set()
        other.join()
    assert run.request(calls) == ref.per_call(calls)


def test_lone_run_of_three_reads_back_once(twins):
    """The counting case: three `lower`, three `dispatch`, ONE
    `device_get`, three `decode` and one `execute` on path `direct`,
    three drain records, and the in-flight interval begun once."""
    _ref, run = twins
    calls = [_sum(10), _sum(11), _sum(12)]
    stages = ("execute", "lower", "dispatch", "device_get", "decode")
    before = {s: _stage_count(s) for s in stages}
    drains = _drain_requests()
    plan = plans.begin("i", " ".join(calls))
    with plans.attach(plan):
        run.request(calls)
    rose = {s: _stage_count(s) - before[s] for s in stages}
    assert rose == {"execute": 1, "lower": 3, "dispatch": 3,
                    "device_get": 1, "decode": 3}
    assert _drain_requests() - drains == 3
    executes = [t for t in plan._stage_trees if t.name == "execute"]
    assert len(executes) == 1
    inner = [i.name for i in executes[0].inner]
    assert inner == ["lower", "dispatch"] * 3 + ["device_get"] + ["decode"] * 3


def test_profiled_three_sum_request_lists_three_device_ops(twins):
    """What benchmark/run.py device_lane_misses reads of `?profile=1`:
    three ops each with a device path, no host_fallback, no memo hit,
    and `execute` noted once."""
    from pilosa_tpu.api import API
    from pilosa_tpu.net import serve

    ref, run = twins
    calls = [_sum(10), _sum(11), _sum(12)]
    api = API(holder=run.holder, mesh_engine=run.eng)
    srv, _thread = serve(api, port=0)
    try:
        req = urllib.request.Request(
            f"http://localhost:{srv.server_address[1]}/index/i/query?profile=1",
            data=" ".join(calls).encode(), method="POST")
        doc = json.loads(urllib.request.urlopen(req, timeout=60).read())
    finally:
        srv.shutdown()
    assert doc["results"] == [
        {"value": r.val, "count": r.count} for r in ref.per_call(calls)]
    ops = doc["plan"]["ops"]
    assert [(op["op"], op.get("path")) for op in ops] == [("Sum", "direct")] * 3
    assert all(op["bytes_touched"] > 0 for op in ops)
    assert not any(op.get("memo") == "hit" for op in ops)
    stages = doc["plan"]["stagesMs"]
    assert {"execute", "lower", "dispatch", "device_get", "decode"} <= set(stages)
    assert stages["device_get"] <= stages["execute"] <= doc["plan"]["durationMs"]
    assert doc["plan"]["deviceSeconds"] * 1e3 == pytest.approx(
        stages["execute"], abs=1e-2)  # the run's one execute, once

"""Batched multi-query dispatch (round-4 VERDICT #1): K Count trees in
one device program — engine parity, executor multi-call batching,
write-barrier semantics, the cross-request micro-batcher, and the
count_batch collective replay."""

import threading

import numpy as np
import pytest

from pilosa_tpu import pql
from pilosa_tpu.core.field import FieldOptions
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.executor import Executor
from pilosa_tpu.ops import SHARD_WIDTH
from pilosa_tpu.parallel import MeshEngine, make_mesh


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(8)


@pytest.fixture
def holder():
    h = Holder()
    h.open()
    idx = h.create_index("i")
    f = idx.create_field("f")
    v = idx.create_field("v", FieldOptions(type="int", min=0, max=1000))
    ef = idx.existence_field()
    rows, cols = [], []
    rng = np.random.default_rng(11)
    for s in range(8):
        base = s * SHARD_WIDTH
        picks = rng.choice(SHARD_WIDTH, size=400, replace=False)
        for c in picks[:250]:
            rows.append(10)
            cols.append(base + int(c))
        for c in picks[150:]:
            rows.append(11)
            cols.append(base + int(c))
    f.import_bulk(rows, cols)
    ef.import_bulk([0] * len(cols), cols)
    v.import_values(cols[:200], [int(x % 700) for x in range(200)])
    return h


QUERIES = [
    "Row(f=10)",
    "Intersect(Row(f=10), Row(f=11))",
    "Union(Row(f=10), Row(f=11))",
    "Difference(Row(f=10), Row(f=11))",
    "Xor(Row(f=10), Row(f=11))",
    "Range(v > 300)",
    "Intersect(Row(f=10), Range(v < 200))",
]


def _call(q):
    return pql.parse(q).calls[0]


def _force_batch_mode(eng):
    """Instantiate the batcher eagerly (batching is now the only mode —
    the round-4 RTT-probe overlap escape hatch is gone)."""
    from pilosa_tpu.parallel.batcher import CountBatcher

    eng._batcher = CountBatcher(eng)


def test_count_many_matches_singles(holder, mesh):
    eng = MeshEngine(holder, mesh)
    shards = list(range(8))
    calls = [_call(q) for q in QUERIES]
    want = [eng.count("i", c, shards) for c in calls]
    got = eng.count_many("i", calls, [shards] * len(calls))
    assert got == want
    # K answers came from ONE batched dispatch (plus the singles above).
    before = eng.fused_dispatches
    eng.count_many("i", calls, [shards] * len(calls))
    assert eng.fused_dispatches == before + 1


@pytest.mark.parametrize("k", [1, 2, 3, 5, 8])
def test_count_many_pow2_padding(holder, mesh, k):
    """Non-power-of-two batches pad by repeating the last program; the
    padding slots must not leak into the returned counts."""
    eng = MeshEngine(holder, mesh)
    shards = list(range(8))
    calls = [_call(QUERIES[i % len(QUERIES)]) for i in range(k)]
    want = [eng.count("i", c, shards) for c in calls]
    assert eng.count_many("i", calls, [shards] * k) == want


def test_count_many_per_query_shards(holder, mesh):
    """Each query in the batch applies ITS OWN shard mask."""
    eng = MeshEngine(holder, mesh)
    c = _call("Row(f=10)")
    per_shard = [eng.count("i", c, [s]) for s in range(8)]
    got = eng.count_many("i", [c] * 8, [[s] for s in range(8)])
    assert got == per_shard
    assert sum(per_shard) == eng.count("i", c, list(range(8)))


def test_executor_multicall_count_batches(holder, mesh):
    eng = MeshEngine(holder, mesh)
    ex = Executor(holder, mesh_engine=eng)
    plain = Executor(holder)
    multi = "".join(f"Count({q})" for q in QUERIES)
    want = plain.execute("i", multi).results
    before = eng.fused_dispatches
    got = ex.execute("i", multi).results
    assert got == want
    # All non-fast-lane Counts went through one batched dispatch.
    assert eng.fused_dispatches == before + 1


def test_executor_write_between_counts_not_batched(holder, mesh):
    """A Set between two Counts is a barrier: the second Count must see
    the write (consecutive-run batching only)."""
    eng = MeshEngine(holder, mesh)
    ex = Executor(holder, mesh_engine=eng)
    # A column inside an EXISTING shard (shard sets resolve once per
    # request, matching the reference) on a row (77) with no bits yet.
    free_col = 5
    q = (
        "Count(Union(Row(f=10), Row(f=77)))"
        f"Set({free_col}, f=77)"
        "Count(Union(Row(f=10), Row(f=77)))"
    )
    res = ex.execute("i", q).results
    assert res[1] is True
    assert res[2] == res[0] + 1


def test_executor_multicall_falls_back_on_batch_failure(holder, mesh):
    """If the batched dispatch rejects the run (ValueError at lower
    time), the per-call path still answers every Count correctly."""
    eng = MeshEngine(holder, mesh)
    ex = Executor(holder, mesh_engine=eng)
    plain = Executor(holder)
    multi = "Count(Intersect(Row(f=10), Row(f=11)))Count(Row(f=11))"
    want = plain.execute("i", multi).results

    def boom(*a, **kw):
        raise ValueError("forced batch failure")

    eng.count_many = boom
    assert ex.execute("i", multi).results == want


def test_batcher_concurrent_submits_fuse(holder, mesh):
    """Concurrent submits while a dispatch is in flight drain into one
    batched program (batching-by-backpressure)."""
    eng = MeshEngine(holder, mesh)
    _force_batch_mode(eng)
    # Memo off: this test is about FUSING, and with the result memo on
    # the repeated queries below would (correctly) never reach the
    # batcher at all (tests/test_sparsity.py covers that path).
    eng.result_memo.maxsize = 0
    calls = [_call(q) for q in QUERIES]
    shards = list(range(8))
    want = {str(c): eng.count("i", c, shards) for c in calls}
    # Warm the compile caches so the race below is about batching, not
    # first-compile stalls.
    eng.count_many("i", calls, [shards] * len(calls))

    results = {}
    errs = []

    def worker(c):
        try:
            results[str(c)] = eng.batched_count("i", c, shards)
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    threads = [
        threading.Thread(target=worker, args=(c,)) for c in calls * 8
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert not errs
    assert results == want
    assert eng._batcher is not None
    assert eng._batcher.batched_queries > 0  # some fusing happened


def test_http_concurrent_counts_batch(holder, mesh):
    """Concurrent HTTP Count queries drain through the micro-batcher:
    correct answers, and at least one fused multi-query batch happened
    (the serving-tier QPS fix — per-request dispatch floors amortize)."""
    import json
    import urllib.request

    from pilosa_tpu.api import API
    from pilosa_tpu.net import serve

    eng = MeshEngine(holder, mesh)
    _force_batch_mode(eng)
    api = API(holder=holder, mesh_engine=eng)
    srv, thread = serve(api, port=0)
    uri = f"http://localhost:{srv.server_address[1]}"
    try:
        q = b"Count(Intersect(Row(f=10), Row(f=11)))"
        want = json.loads(
            urllib.request.urlopen(
                urllib.request.Request(
                    f"{uri}/index/i/query", data=q, method="POST"
                ),
                timeout=60,
            ).read()
        )["results"][0]

        results, errs = [], []

        def client():
            try:
                for _ in range(4):
                    req = urllib.request.Request(
                        f"{uri}/index/i/query", data=q, method="POST"
                    )
                    body = json.loads(
                        urllib.request.urlopen(req, timeout=60).read()
                    )
                    results.append(body["results"][0])
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        threads = [threading.Thread(target=client) for _ in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert not errs
        assert len(results) == 64 and set(results) == {want}
        assert eng._batcher is not None
        assert eng._batcher.batched_queries > 0
    finally:
        srv.shutdown()


def test_count_batch_collective_replay(holder, mesh):
    """The count_batch kind replays through the API accept path
    (single-phase, in-process) and dispatches once."""
    import time

    from pilosa_tpu.api import API

    api = API(holder=holder, mesh_engine=MeshEngine(holder, mesh))
    payload = {
        "kind": "count_batch",
        "index": "i",
        "queries": ["Row(f=10)", "Intersect(Row(f=10), Row(f=11))"],
        "shardsList": [list(range(8)), list(range(8))],
    }
    assert api.mesh_collective_accept(dict(payload))
    deadline = time.time() + 10
    while api.mesh_engine.fused_dispatches < 1 and time.time() < deadline:
        time.sleep(0.02)
    assert api.mesh_engine.fused_dispatches == 1

    from pilosa_tpu.api import ApiError

    with pytest.raises(ApiError, match="length mismatch"):
        api.mesh_collective_accept(
            dict(payload, queries=["Row(f=10)"], did=None)
        )
    with pytest.raises(ApiError, match="empty batch"):
        api.mesh_collective_accept(
            dict(payload, queries=[], shardsList=[])
        )


def test_count_many_missing_rows_uniform_program(holder, mesh):
    """A row id that doesn't exist lowers to the SAME batch program as
    one that does (presence is a -1 slot value, not structure): counts
    are 0 for missing rows and the executable cache must not grow per
    present/absent pattern (r5 review: compile-key stability)."""
    eng = MeshEngine(holder, mesh)
    shards = list(range(8))
    mixes = [
        [_call("Row(f=10)"), _call("Row(f=999)")],
        [_call("Row(f=999)"), _call("Row(f=10)")],
        [_call("Row(f=999)"), _call("Row(f=998)")],
    ]
    want10 = eng.count("i", _call("Row(f=10)"), shards)
    for calls in mixes:
        got = eng.count_many("i", calls, [shards] * 2)
        want = [want10 if "999" not in str(c) and "998" not in str(c) else 0
                for c in calls]
        assert got == want, (calls, got)


def test_batcher_poisoned_batch_splits_fast(holder, mesh):
    """One unlowerable query in a drain must fail ONLY its submitter;
    the survivors re-dispatch as one batch (not a serial per-item
    retry that would stall the worker)."""
    import threading

    eng = MeshEngine(holder, mesh)
    _force_batch_mode(eng)
    b = eng._batcher
    shards = list(range(8))
    good_calls = [_call(q) for q in QUERIES[:3]]
    want = [eng.count("i", c, shards) for c in good_calls]
    bad = _call("Row(nosuchfield=1)")

    results = {}
    errors = {}

    def submit(tag, call):
        try:
            results[tag] = b.submit("i", call, shards)
        except Exception as e:  # noqa: BLE001
            errors[tag] = e

    # Occupy the direct path so everything else queues into ONE drain.
    blocker = threading.Thread(target=submit, args=("b0", good_calls[0]))
    blocker.start()
    threads = [
        threading.Thread(target=submit, args=(f"g{i}", c))
        for i, c in enumerate(good_calls)
    ] + [threading.Thread(target=submit, args=("bad", bad))]
    for t in threads:
        t.start()
    for t in threads + [blocker]:
        t.join(timeout=60)
    assert "bad" in errors, "unlowerable query did not error"
    for i in range(3):
        assert results.get(f"g{i}") == want[i], (i, results, errors)


def test_singleflight_collapses_identical_aggregates(holder, mesh):
    """N concurrent identical Sum/TopN queries produce ONE fused
    dispatch per burst (request collapsing): correct answers for every
    caller, engine dispatch count stays ~constant, and results are not
    cached across bursts (a write between bursts is visible)."""
    eng = MeshEngine(holder, mesh)
    ex = Executor(holder, mesh_engine=eng)
    want_sum = ex.execute("i", "Sum(field=v)").results[0]
    want_top = ex.execute("i", "TopN(f, Row(f=11), n=2)").results[0]

    results, errs = [], []

    def worker(q, exp):
        try:
            got = ex.execute("i", q).results[0]
            results.append(got == exp)
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    # The warm-up runs above memoized both queries (the Sum/TopN memo
    # lanes would answer all 24 workers with zero flights) — clear the
    # memo and hold repair off so the burst truly needs computation.
    eng.result_memo.clear()
    before = eng.fused_dispatches
    # Barrier: all workers release together so flight overlap is
    # deterministic, not a thread-spawn race.
    barrier = threading.Barrier(24)

    def gated(q, exp):
        barrier.wait(30)
        worker(q, exp)

    threads = [
        threading.Thread(target=gated, args=("Sum(field=v)", want_sum))
        for _ in range(12)
    ] + [
        threading.Thread(
            target=gated, args=("TopN(f, Row(f=11), n=2)", want_top)
        )
        for _ in range(12)
    ]
    with eng.repairs.suspended():
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
    assert not errs and all(results), (errs, results)
    assert ex._sflight.shared > 0, "no requests were collapsed"
    # Far fewer dispatches than callers (leaders only; bursts may split).
    assert eng.fused_dispatches - before < 24

    # NOT a cache: a write bumps WRITE_SEQ, so the next SUM (a
    # singleflighted path) reflects it instead of joining a stale
    # flight's key space.
    s1 = ex.execute("i", "Sum(field=v)").results[0]
    ex.execute("i", "Set(123, v=9)")
    s2 = ex.execute("i", "Sum(field=v)").results[0]
    assert (s2.val, s2.count) == (s1.val + 9, s1.count + 1)


def _subset_entries(k, text="Union(Row(f=10), Row(f=11))"):
    """k distinct drain entries of ONE structure: the same Count over k
    different non-empty subsets of the 8 shards (an entry is keyed by
    text and shard set), so every slot's answer is its own."""
    return [(_call(text), [s for s in range(8) if (i + 1) >> s & 1])
            for i in range(k)]


def _captured_batch_program(eng, entries):
    """(arguments of the one kernels.count_batch_tree call, its answers)
    for a count_many of ``entries``."""
    from pilosa_tpu.parallel import kernels as k_mod

    seen = []
    orig = k_mod.count_batch_tree
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(
            k_mod, "count_batch_tree",
            lambda *args: seen.append(args) or orig(*args),
        )
        out = eng.count_many(
            "i", [c for c, _ in entries], [s for _, s in entries]
        )
    (args,) = seen
    return args, out


@pytest.mark.parametrize(
    "tier,n_live",
    [(64, 2), (64, 9), (64, 16), (64, 63), (64, 64), (8, 2), (8, 8)],
)
def test_count_batch_tree_runs_only_live_slots(holder, mesh, tier, n_live):
    """The tier is the program's capacity; the traced live count is what
    it runs.  A full drain's program, handed a smaller n_live, answers 0
    in every slot at or beyond it — whatever rows those slots name —
    and leaves the slots below it as they were."""
    from pilosa_tpu.parallel import kernels as k_mod

    eng = MeshEngine(holder, mesh)
    args, full = _captured_batch_program(eng, _subset_entries(tier))
    kmesh, progs, specs, n, *operands = args
    assert len(progs) == tier and int(n) == tier
    assert all(full), "every slot of the full drain has rows to count"
    got = np.asarray(k_mod.count_batch_tree(
        kmesh, progs, specs, eng._scalar(n_live), *operands))
    assert got[:n_live].tolist() == full[:n_live]
    assert not got[n_live:].any()


@pytest.mark.parametrize("n_live", [2, 7, 8, 9, 16, 63, 64])
def test_count_many_matches_scalar_count_at_live_counts(holder, mesh, n_live):
    """n_live unique entries — present rows, a missing row, per-entry
    shard subsets — plus duplicates that fan back out through the CSE
    mapping: every caller gets the scalar count's answer, and the drain
    took the tier it should."""
    from pilosa_tpu.util import plans

    eng = MeshEngine(holder, mesh)
    uniq = _subset_entries(n_live - 1) + [
        (_call("Union(Row(f=10), Row(f=999))"), list(range(8)))]
    entries = uniq + [uniq[0], uniq[-1], uniq[n_live // 2]]
    order = np.random.default_rng(n_live).permutation(len(entries))
    entries = [entries[i] for i in order]
    want = [eng.count("i", c, s) for c, s in entries]
    plans.take_dispatch_note()
    got = eng.count_many("i", [c for c, _ in entries], [s for _, s in entries])
    note = plans.take_dispatch_note()
    assert got == want
    assert (note["tier"], note["cse_unique"], note["cse_deduped"]) == (
        8 if n_live <= 8 else 64, n_live, 3)


def test_batch_program_keeps_dynamic_control_flow(holder, mesh):
    """The tier-64 program skips dead slots by control flow the device
    executes (a conditional per slot, or a loop bounded by the live
    count) — unrolled back into 64 unconditional slots it would read
    every pad slot's planes again."""
    from pilosa_tpu.parallel import kernels as k_mod

    eng = MeshEngine(holder, mesh)
    args, _ = _captured_batch_program(eng, _subset_entries(9))
    assert len(args[1]) == 64
    hlo = k_mod.count_batch_tree.lower(*args).compile().as_text()
    assert " conditional(" in hlo or " while(" in hlo


def test_batch_tier_compile_key_stability(holder, mesh):
    """THE round-5 serving guarantee: batched count programs compile per
    (structure, tier), never per drain size — distinct batch sizes
    within one tier reuse one executable (round 4 compiled a fresh ~2 s
    program per distinct size, the entire QPS shortfall).  Pinned via
    the jit executable-cache size."""
    from pilosa_tpu.parallel import kernels as k_mod

    eng = MeshEngine(holder, mesh)
    shards = list(range(8))
    c = _call("Intersect(Row(f=10), Row(f=11))")
    base = eng.count("i", c, shards)

    def run(k):
        # DISTINCT queries per slot: identical entries would CSE down
        # to one unique and take the scalar count path, never building
        # the batch program this test pins (tests/test_sparsity.py
        # covers that route).  Missing row ids are fine — presence is
        # slot-vector data, and the structure is what compiles.
        calls = [
            _call(f"Intersect(Row(f=10), Row(f={1000 + i}))")
            for i in range(k)
        ]
        got = eng.count_many("i", calls, [shards] * k)
        assert got == [0] * k

    pads0 = _pad_counts()
    run(9)  # tier 64: compiles once
    size_after_first = k_mod.count_batch_tree._cache_size()
    # All tier 64, different raw sizes: every drain's pads are replayed
    # from entry 0's lowering, first with the replay cache cold for its
    # size, then warm — and neither compiles.
    sizes = (10, 17, 23, 41, 64)
    for k in sizes + sizes:
        run(k)
    assert k_mod.count_batch_tree._cache_size() == size_after_first, (
        "a drain size within the tier compiled a new executable"
    )
    pads = _pad_counts()
    assert pads["lowered"] == pads0["lowered"]
    assert pads["replayed"] - pads0["replayed"] == sum(
        64 - k for k in (9,) + sizes + sizes)
    # Different ROW IDS in the same structure also reuse it (ids are
    # slot-vector data), including PRESENT rows mixed with missing.
    mixed = [
        _call(f"Intersect(Row(f={2000 + i}), Row(f=11))") for i in range(11)
    ] + [c]
    got = eng.count_many("i", mixed, [shards] * 12)
    assert got == [0] * 11 + [base]
    assert k_mod.count_batch_tree._cache_size() == size_after_first
    # A new TIER adds at most one executable (zero when an earlier test
    # in this process already compiled this structure at tier 8 — the
    # cache is process-global, which is itself the point).
    run(2)  # tier 8
    size_tier8 = k_mod.count_batch_tree._cache_size()
    assert size_tier8 <= size_after_first + 1
    for k in (5, 3, 5, 7):  # tier 8, replayed: no new executable
        run(k)
    assert k_mod.count_batch_tree._cache_size() == size_tier8
    assert _pad_counts()["lowered"] == pads0["lowered"]


# Entry i of a drain, by the leaf kind its prog holds: the text varies
# with i where the kind allows, so a pad that copied another entry than
# entry 0 would show.  Each entry also takes its own shard subset
# (_pad_entries), so every entry is unique and none is CSE'd away.
PAD_KINDS = {
    "row": (lambda i: f"Row(f={10 + i % 2})", {"rowm"}),
    "missing_row": (lambda i: f"Row(f={999 if i % 2 == 0 else 10})", {"rowm"}),
    "union_band": (
        lambda i: f"Union(Row(f={10 + i % 2}), Row(f=11), Row(f={998 - i % 3}))",
        {"or", "rowm"},
    ),
    "range_cmp": (lambda i: f"Range(v > {300 + i})", {"range"}),
    "range_between": (lambda i: f"Range(v >< [{100 + i}, {600 - i}])", {"between"}),
    "not_null": (
        lambda i: f"Intersect(Range(v >= 0), Row(f={10 + i % 2}))",
        {"and", "row", "rowm"},
    ),
    "not": (lambda i: f"Not(Row(f={10 + i % 2}))", {"andnot", "rowm"}),
    "time_range": (
        lambda i: f"Range(t={1 + i % 2}, 2018-01-01T00:00, 2018-03-01T00:00)",
        {"or", "row"},
    ),
    "zero": (lambda i: f"Union(Row(f={10 + i % 2}), Range(v > 5000))", {"or", "zero"}),
}


def _pad_entries(kind, k):
    text = PAD_KINDS[kind][0]
    return [(_call(text(i)), [s for s in range(8) if (i + 1) >> s & 1])
            for i in range(k)]


def _add_time_field(holder):
    """A YM time field ``t`` with rows 1 and 2 set in both months that
    ``PAD_KINDS["time_range"]`` covers, on every shard."""
    import datetime as dt

    t = holder.index("i").create_field(
        "t", FieldOptions(type="time", time_quantum="YM"))
    rows, cols, stamps = [], [], []
    for s in range(8):
        for j in range(40):
            rows.append(1 + j % 2)
            cols.append(s * SHARD_WIDTH + 7 * j)
            stamps.append(dt.datetime(2018, 1 + j % 3, 5))
    t.import_bulk(rows, cols, stamps)


def _leaf_kinds(prog):
    kind = prog[0]
    if kind in ("and", "or", "andnot", "xor"):
        return {kind}.union(*(_leaf_kinds(p) for p in prog[1:]))
    return {kind}


def _pad_counts():
    from pilosa_tpu.util.stats import METRIC_ENGINE_BATCH_PAD_SLOTS, REGISTRY

    return {how: REGISTRY.counter(METRIC_ENGINE_BATCH_PAD_SLOTS, lowering=how).get()
            for how in ("replayed", "lowered")}


def _drain_with_pad_counts(eng, entries):
    before = _pad_counts()
    args, answers = _captured_batch_program(eng, entries)
    after = _pad_counts()
    return args, answers, {h: after[h] - before[h] for h in after}


@pytest.mark.parametrize("k", [2, 5, 9, 16, 63, 65])
@pytest.mark.parametrize("kind", sorted(PAD_KINDS))
def test_pad_replay_matches_relowering(holder, mesh, monkeypatch, kind, k):
    """The pad slots replayed from entry 0's lowering are, byte for byte,
    what lowering entry 0 again for each gave: the same progs and specs
    (the compile key), operands of the same count, shapes, dtypes and
    values, the same slot vector, the same answers.  The re-lowering is
    what a prog the renumbering cannot walk still takes: emptied here."""
    from pilosa_tpu.parallel import engine as engine_mod

    _add_time_field(holder)
    entries = _pad_entries(kind, k)
    tier = 8 if k <= 8 else 64 if k <= 64 else 256
    got, got_n, got_pads = _drain_with_pad_counts(
        MeshEngine(holder, mesh), entries)
    with monkeypatch.context() as mp:
        mp.setattr(engine_mod, "_PAD_REFS", {})
        want, want_n, want_pads = _drain_with_pad_counts(
            MeshEngine(holder, mesh), entries)
    assert got_pads == {"replayed": tier - k, "lowered": 0}
    assert want_pads == {"replayed": 0, "lowered": tier - k}
    _, progs, specs, n, *ops = got
    _, w_progs, w_specs, w_n, *w_ops = want
    assert len(progs) == tier and int(n) == int(w_n) == k
    assert PAD_KINDS[kind][1] <= _leaf_kinds(progs[0][0])
    assert progs == w_progs and specs == w_specs
    assert len(ops) == len(w_ops)
    for a, b in zip(ops, w_ops):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
        assert np.array_equal(np.asarray(a), np.asarray(b))
    if kind == "missing_row":
        assert int(np.asarray(ops[0])[0]) == -1
    assert got_n == want_n


def test_pad_slots_counter(holder, mesh, monkeypatch):
    """A tier-64 drain of 9 counts its 55 pad slots as replayed; where
    entry 0's prog holds a leaf kind the renumbering does not know, they
    are lowered again, counted so, and the answers stay the scalar
    counts."""
    from pilosa_tpu.parallel import engine as engine_mod

    entries = _pad_entries("union_band", 9)
    want = [MeshEngine(holder, mesh).count("i", c, s) for c, s in entries]
    _, got, pads = _drain_with_pad_counts(MeshEngine(holder, mesh), entries)
    assert pads == {"replayed": 55, "lowered": 0}
    assert got == want
    monkeypatch.delitem(engine_mod._PAD_REFS, "rowm")
    _, got, pads = _drain_with_pad_counts(MeshEngine(holder, mesh), entries)
    assert pads == {"replayed": 0, "lowered": 55}
    assert got == want

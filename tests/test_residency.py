"""Tiered residency: device as a working-set cache (docs/residency.md).

Covers the PR 15 tentpole end to end on a 1-device CPU mesh sized so a
full stack genuinely does not fit the configured device budget:

* cold miss -> host-tier fallback (bit-exact) + async partial promotion
  -> repeat query dispatches on device;
* differential equality across fully-resident, partially-resident, and
  host-fallback paths for the same queries;
* the eviction/promotion races ISSUE 15 names: a write landing during
  an in-flight promotion reconciles through the token re-check, and an
  eviction under a cached fused plan never frees a donated buffer the
  plan still references;
* admission accounting (occupancy summaries + in-flight promotion
  buffers count against the budget), cost-priced eviction ordering,
  and warm-start's EWMA priority + working-set-target stop.
"""

import threading
import time

import numpy as np
import pytest

from pilosa_tpu import pql
from pilosa_tpu.core.field import FieldOptions
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.executor import Executor
from pilosa_tpu.parallel import MeshEngine, make_mesh
from pilosa_tpu.parallel.errors import PeerlessMeshError, ResidencyMiss
from pilosa_tpu.parallel.residency import ResidencyManager
from pilosa_tpu.util import plans as plans_mod
from pilosa_tpu.util.stats import METRIC_ENGINE_PROMOTIONS, REGISTRY

# One (row, shard) of device words + the occupancy/block-mask summaries
# (engine._row_shard_bytes): the sizing unit for budgets below.
ROW_SHARD = 32768 * 4 + 16

N_ROWS = 16


@pytest.fixture(scope="module")
def mesh1():
    # 1 device -> S (padded shard axis) == 1 for single-shard data, so
    # budgets stay small and precise.
    return make_mesh(1)


@pytest.fixture
def holder():
    h = Holder()
    h.open()
    return h


def build_oversub(holder, n_rows=N_ROWS):
    """One shard, ``n_rows`` rows with distinct overlapping bit sets —
    a full stack of n_rows * ROW_SHARD bytes."""
    idx = holder.create_index("i")
    f = idx.create_field("f")
    rows, cols = [], []
    for r in range(n_rows):
        for c in range(0, 400 + 10 * r, 2):
            rows.append(r)
            cols.append(c)
    f.import_bulk(rows, cols)
    return idx


QUERIES = [
    "Count(Intersect(Row(f=10), Row(f=11)))",
    "Count(Union(Row(f=10), Row(f=11)))",
    "Count(Difference(Row(f=11), Row(f=10)))",
    "Count(Xor(Row(f=10), Row(f=11)))",
]


def _fresh_engine(holder, mesh, budget):
    eng = MeshEngine(holder, mesh, max_resident_bytes=budget)
    # Every query in these tests must really consult residency, not the
    # result memo.
    eng.result_memo.maxsize = 0
    return eng


def test_cold_miss_host_fallback_then_partial_promotion(holder, mesh1):
    build_oversub(holder)
    # Full stack = 16 row-shards; budget fits ~4 -> working-set regime.
    eng = _fresh_engine(holder, mesh1, 4 * ROW_SHARD + 4096)
    ex_host = Executor(holder)
    ex = Executor(holder, mesh_engine=eng)
    q = QUERIES[0]
    want = ex_host.execute("i", q).results[0]

    # Cold: the engine declines (ResidencyMiss), the executor serves
    # from the host tier, and a partial promotion is enqueued.
    got = ex.execute("i", q).results[0]
    assert got == want
    assert eng.host_fallbacks >= 1
    assert eng.residency.flush(30.0)
    snap = eng.residency.snapshot()
    assert snap["partialPromotions"] >= 1
    assert snap["promotedBytes"] > 0

    # Repeat: the promoted working set serves ON DEVICE — no new host
    # fallback, a fused dispatch happens, and the stack is partial.
    fb0, disp0 = eng.host_fallbacks, eng.fused_dispatches
    assert ex.execute("i", q).results[0] == want
    assert eng.host_fallbacks == fb0
    assert eng.fused_dispatches > disp0
    stack = eng._stacks[("i", "f", "standard")]
    assert stack.partial
    assert 0.0 < stack.resident_fraction() < 1.0
    assert stack.block_mask is not None
    # Resident-block invariant: every occupied block is device-valid.
    assert not np.any(stack.occ & ~stack.block_mask)
    eng.close()


def test_differential_full_partial_host(holder, mesh1):
    """Bit-exact results across the three serving paths for the same
    queries (the ISSUE 15 acceptance differential)."""
    build_oversub(holder)
    ex_host = Executor(holder)
    eng_full = _fresh_engine(holder, mesh1, 64 * ROW_SHARD)
    ex_full = Executor(holder, mesh_engine=eng_full)
    eng_part = _fresh_engine(holder, mesh1, 4 * ROW_SHARD + 4096)
    ex_part = Executor(holder, mesh_engine=eng_part)
    for q in QUERIES:
        want = ex_host.execute("i", q).results[0]
        assert ex_full.execute("i", q).results[0] == want, q
        assert ex_part.execute("i", q).results[0] == want, (q, "cold")
    assert eng_part.residency.flush(30.0)
    for q in QUERIES:
        want = ex_host.execute("i", q).results[0]
        assert ex_part.execute("i", q).results[0] == want, (q, "warm")
    assert eng_part._stacks[("i", "f", "standard")].partial
    # The full engine never fell back; the partial one promoted.
    assert eng_full.host_fallbacks == 0
    assert eng_part.residency.snapshot()["partialPromotions"] >= 1
    eng_full.close()
    eng_part.close()


def test_uncovered_row_grows_working_set(holder, mesh1):
    build_oversub(holder)
    eng = _fresh_engine(holder, mesh1, 8 * ROW_SHARD + 4096)
    ex = Executor(holder, mesh_engine=eng)
    ex_host = Executor(holder)
    assert (
        ex.execute("i", QUERIES[0]).results[0]
        == ex_host.execute("i", QUERIES[0]).results[0]
    )
    assert eng.residency.flush(30.0)
    stack = eng._stacks[("i", "f", "standard")]
    assert set(stack.row_index) == {10, 11}
    # A query over rows OUTSIDE the promoted set falls back (correctly)
    # and grows the working set to old + new rows.
    q2 = "Count(Intersect(Row(f=2), Row(f=3)))"
    fb0 = eng.host_fallbacks
    assert ex.execute("i", q2).results[0] == ex_host.execute("i", q2).results[0]
    assert eng.host_fallbacks > fb0
    assert eng.residency.flush(30.0)
    stack = eng._stacks[("i", "f", "standard")]
    assert {2, 3, 10, 11} <= set(stack.row_index)
    fb1 = eng.host_fallbacks
    assert ex.execute("i", q2).results[0] == ex_host.execute("i", q2).results[0]
    assert eng.host_fallbacks == fb1  # served on device now
    eng.close()


def test_absent_row_zero_then_write_invalidates(holder, mesh1):
    """A promoted-but-empty row lowers to zero on device; a write that
    CREATES the row drops the absent marker through the incremental
    sync, so the next query falls back + re-promotes instead of reading
    a stale zero."""
    idx = build_oversub(holder)
    eng = _fresh_engine(holder, mesh1, 4 * ROW_SHARD + 4096)
    ex = Executor(holder, mesh_engine=eng)
    q = "Count(Intersect(Row(f=99), Row(f=10)))"
    assert ex.execute("i", q).results[0] == 0
    assert eng.residency.flush(30.0)
    stack = eng._stacks[("i", "f", "standard")]
    assert 99 in stack.absent_rows
    # Device-served zero for the absent row.
    fb0 = eng.host_fallbacks
    assert ex.execute("i", q).results[0] == 0
    assert eng.host_fallbacks == fb0
    # Write creates row 99 overlapping row 10.
    idx.field("f").import_bulk([99, 99], [0, 2])
    assert ex.execute("i", q).results[0] == 2
    assert eng.residency.flush(30.0)
    assert ex.execute("i", q).results[0] == 2
    eng.close()


def test_write_during_promotion_token_recheck(holder, mesh1):
    """ISSUE 15 satellite: a write landing during an in-flight partial
    promotion must reconcile through the authoritative path (token
    re-check + incremental sync), never serve the pre-write bits."""
    idx = build_oversub(holder)
    eng = _fresh_engine(holder, mesh1, 4 * ROW_SHARD + 4096)
    ex = Executor(holder, mesh_engine=eng)
    ex_host = Executor(holder)
    orig = eng._assemble_pool_chunk
    wrote = threading.Event()

    def racing(chunk_rows, row_index, slot_of, frags, occ):
        out = orig(chunk_rows, row_index, slot_of, frags, occ)
        if not wrote.is_set():
            wrote.set()
            # Lands AFTER the chunk was read, BEFORE commit: the
            # committed stack's sync point predates this write.
            idx.field("f").import_bulk([10, 11], [100001, 100001])
        return out

    eng._assemble_pool_chunk = racing
    q = QUERIES[0]
    ex.execute("i", q)  # cold -> host + enqueue
    assert eng.residency.flush(30.0)
    assert wrote.is_set()
    want = ex_host.execute("i", q).results[0]  # post-write truth
    got = ex.execute("i", q).results[0]
    assert got == want
    eng.close()


def test_eviction_under_cached_fused_plan(holder, mesh1):
    """Extend the PR 12 eviction-purge coverage to the cost-priced
    loop: evicting a stack a cached fused plan references must purge
    the plan (no donated-buffer crash on the next dispatch) and keep
    results exact."""
    build_oversub(holder, n_rows=2)
    eng = _fresh_engine(holder, mesh1, 64 * ROW_SHARD)
    entries = [
        ({"kind": "count", "call": pql.parse("Intersect(Row(f=0), Row(f=1))").calls[0]},
         [0]),
        ({"kind": "count", "call": pql.parse("Union(Row(f=0), Row(f=1))").calls[0]},
         [0]),
    ]
    first = eng.fused_many("i", entries)
    assert eng._fused_plans  # cached
    with eng._dispatch_lock, eng._stacks_lock:
        eng._evict_for(eng.max_resident_bytes)  # cost-priced: evicts all
        assert not eng._stacks
    assert not eng._fused_plans  # purge rode the eviction
    assert eng.fused_many("i", entries) == first
    eng.close()


def test_admission_counts_summaries_and_inflight(holder, mesh1):
    build_oversub(holder, n_rows=2)
    eng = _fresh_engine(holder, mesh1, 64 * ROW_SHARD)
    stack = eng.field_stack("i", "f", "standard")
    # Satellite fix: the occupancy summary counts against the budget,
    # not just mat.nbytes.
    assert stack.footprint > stack.matrix.nbytes
    assert eng._resident_bytes == stack.footprint
    # In-flight promotion buffers count too.
    assert eng._admissible(0)
    eng.residency.add_inflight(eng.max_resident_bytes)
    assert not eng._admissible(1)
    eng.residency.sub_inflight(eng.max_resident_bytes)
    assert eng._admissible(0)
    eng.close()


def test_cost_priced_eviction_prefers_cold_tenants(mesh1):
    h = Holder()
    h.open()
    for name in ("hot", "cold"):
        f = h.create_index(name).create_field("f")
        f.import_bulk([1], [0])
    g = h.index("hot").create_field("g")
    g.import_bulk([1], [0])
    # Budget for two stacks (hot/f, cold/f); admitting hot/g must evict
    # the COLD tenant's stack even though hot/f is older in LRU order.
    eng = MeshEngine(h, mesh1, max_resident_bytes=2 * ROW_SHARD + 4096)
    eng.cost_of_index = lambda index: {"hot": 5.0}.get(index, 0.0)
    eng.field_stack("hot", "f", "standard")
    eng.field_stack("cold", "f", "standard")
    assert len(eng._stacks) == 2
    eng.field_stack("hot", "g", "standard")
    assert ("cold", "f", "standard") not in eng._stacks
    assert ("hot", "f", "standard") in eng._stacks
    eng.close()


def test_ledger_cost_ewma_feeds_default_pricing(mesh1):
    h = Holder()
    h.open()
    h.create_index("t1").create_field("f").import_bulk([1], [0])
    plans_mod.LEDGER.reset()
    plans_mod.LEDGER.seed_costs({"t1": 0.25})
    eng = MeshEngine(h, mesh1)
    assert eng._index_cost("t1") == pytest.approx(0.25)
    assert eng._index_cost("unknown") == 0.0
    plans_mod.LEDGER.reset()
    eng.close()


def test_warm_start_orders_by_cost_and_stops_at_target(mesh1):
    h = Holder()
    h.open()
    for name in ("aa", "bb", "cc"):
        f = h.create_index(name).create_field("f")
        f.import_bulk([1], [0])
    # Target (90% of budget) fits TWO stacks; three candidates.  "bb"
    # is the hot tenant and must warm FIRST; warming stops at the
    # target instead of racing the cap.
    eng = MeshEngine(h, mesh1, max_resident_bytes=int(2.5 * ROW_SHARD / 0.9))
    eng.cost_of_index = lambda index: {"bb": 9.0, "cc": 1.0}.get(index, 0.0)
    state = eng.warm_start()
    assert state["done"]
    assert state["built"] == 2
    assert state["skipped"] == state["total"] - 2
    order = [k[0] for k in eng._stacks]
    assert order[0] == "bb"  # hottest tenant warmed first
    assert order[1] == "cc"
    eng.close()


def test_aggregate_requires_full_stack(holder, mesh1):
    """Sum over an oversubscribed BSI stack serves from the host tier
    (full promotion declined/pending), bit-exact vs the host path."""
    idx = build_oversub(holder)
    v = idx.create_field("v", FieldOptions(type="int", min=0, max=1000))
    v.import_values(list(range(50)), [int(3 * c) % 1000 for c in range(50)])
    eng = _fresh_engine(holder, mesh1, 4 * ROW_SHARD + 4096)
    ex = Executor(holder, mesh_engine=eng)
    ex_host = Executor(holder)
    q = "Sum(field=v)"
    assert ex.execute("i", q).results == ex_host.execute("i", q).results
    eng.close()


def test_residency_miss_type_and_metrics_surface(holder, mesh1):
    build_oversub(holder)
    eng = _fresh_engine(holder, mesh1, 2 * ROW_SHARD + 4096)
    # The typed contract every executor fallback site relies on.
    assert issubclass(ResidencyMiss, PeerlessMeshError)
    with pytest.raises(ResidencyMiss):
        eng.count("i", pql.parse("Intersect(Row(f=1), Row(f=2))").calls[0], [0])
    eng.refresh_metrics()
    text = REGISTRY.prometheus_text()
    for series in (
        "pilosa_engine_promotions_total",
        "pilosa_engine_partial_promotions_total",
        "pilosa_engine_promotions_declined_total",
        "pilosa_engine_host_fallbacks_total",
        "pilosa_engine_resident_block_fraction",
    ):
        assert series in text, series
    snap = eng.cache_snapshot()
    assert snap["hostFallbacks"] >= 1
    assert "pendingPromotions" in snap["workingSet"]
    assert "evictionPressure" in snap["workingSet"]
    assert snap["workingSet"]["deviceBudgetBytes"] == eng.max_resident_bytes
    # Once the promotion lands the stack is PARTLY resident, and both
    # the gauge and /debug/vars' working set say so.
    assert eng.residency.flush(30.0)
    eng.refresh_metrics()
    frac = REGISTRY.get_gauge("pilosa_engine_resident_block_fraction")
    assert 0.0 < frac < 1.0, frac
    per = eng.cache_snapshot()["workingSet"]["perIndex"]["i"]
    assert per["partialStacks"] >= 1, per
    assert 0.0 < per["residentFraction"] < 1.0, per
    eng.close()


def test_host_fallback_plan_annotation(holder, mesh1):
    """The /debug/plans analyzer renders the residency note the engine
    stamps at miss time (ISSUE 15 satellite: 'host fallback: stack NN%
    resident')."""
    build_oversub(holder)
    eng = _fresh_engine(holder, mesh1, 2 * ROW_SHARD + 4096)
    ex = Executor(holder, mesh_engine=eng)
    plan = plans_mod.begin("i", QUERIES[0], tenant="i")
    with plans_mod.attach(plan):
        ex.execute("i", QUERIES[0])
    assert plan is not None
    notes = plans_mod.analyze(plan)
    assert any("host fallback" in n and "resident" in n for n in notes), notes
    eng.close()


def test_block_pool_fuzz_promote_evict_sync(holder, mesh1):
    """ISSUE 20 satellite: randomized differential fuzz of the packed
    block pool — rotating row pairs churn promote/evict cycles under a
    4-row budget while random writes land across the full occupancy
    range (virgin blocks, recycled slots, zero-covered tails), and
    every query must stay bit-exact vs the host path."""
    rng = np.random.default_rng(7)
    idx = build_oversub(holder)
    eng = _fresh_engine(holder, mesh1, 4 * ROW_SHARD + 4096)
    ex = Executor(holder, mesh_engine=eng)
    ex_host = Executor(holder)
    ops = ("Intersect", "Union", "Difference", "Xor")
    for it in range(30):
        r1, r2 = (int(r) for r in rng.choice(N_ROWS, 2, replace=False))
        q = f"Count({ops[it % 4]}(Row(f={r1}), Row(f={r2})))"
        assert (
            ex.execute("i", q).results[0]
            == ex_host.execute("i", q).results[0]
        ), (it, q)
        if it % 3 == 0:
            # Random writes, spanning the whole shard so new occupancy
            # blocks appear on already-promoted rows (pool slot alloc +
            # zero-fill cover paths in the incremental sync).
            n = int(rng.integers(1, 6))
            idx.field("f").import_bulk(
                [int(r) for r in rng.integers(0, N_ROWS, n)],
                [int(c) for c in rng.integers(0, 1_000_000, n)],
            )
        if it % 7 == 0:
            assert eng.residency.flush(30.0)
    assert eng.residency.flush(30.0)
    snap = eng.residency.snapshot()
    assert snap["partialPromotions"] >= 1
    assert eng.cache_snapshot()["evictions"] >= 1  # the churn was real
    for q in QUERIES:
        assert (
            ex.execute("i", q).results[0]
            == ex_host.execute("i", q).results[0]
        ), q
    eng.close()


def test_write_during_promote_ahead_race(holder, mesh1):
    """A write landing during a block-pool promotion that no query is
    waiting on reconciles through the token re-check + incremental
    sync, and the journal records the promotion with its cause."""
    from pilosa_tpu.util.events import EventJournal

    idx = build_oversub(holder)
    journal = EventJournal()
    eng = MeshEngine(
        holder, mesh1, max_resident_bytes=4 * ROW_SHARD + 4096,
        journal=journal,
    )
    eng.result_memo.maxsize = 0
    ex = Executor(holder, mesh_engine=eng)
    ex_host = Executor(holder)
    orig = eng._assemble_pool_chunk
    wrote = threading.Event()

    def racing(chunk_rows, row_index, slot_of, frags, occ):
        out = orig(chunk_rows, row_index, slot_of, frags, occ)
        if not wrote.is_set():
            wrote.set()
            idx.field("f").import_bulk([10, 11], [100001, 100001])
        return out

    eng._assemble_pool_chunk = racing
    # A request with no query driving it: nothing serves from the host
    # tier meanwhile, so only the commit's token gate sees the write.
    assert eng.residency.request(
        ("i", "f", "standard"), {10, 11}, cause="reactive"
    )
    assert eng.residency.flush(30.0)
    assert wrote.is_set()
    promos = journal.events(type="engine.promotion")
    assert any(e.fields.get("cause") == "reactive" for e in promos), promos
    # The first query reconciles through the token gate and serves the
    # post-write truth, never the pre-write snapshot the upload read.
    want = ex_host.execute("i", QUERIES[0]).results[0]
    assert ex.execute("i", QUERIES[0]).results[0] == want
    eng.close()


def test_promotion_declined_cooldown(holder, mesh1):
    """A stack that cannot fit even partially declines (counted) and
    cools down instead of spinning the worker; the host tier keeps
    serving bit-exact."""
    build_oversub(holder)
    # Below even the MINIMUM block-pool tier (8 slots x 2 KiB): the
    # pow2-row era used ROW_SHARD // 2 here, but a pool serves a
    # 2-row working set in ~16 KiB, so "cannot fit even partially"
    # now means a budget under that floor.
    eng = _fresh_engine(holder, mesh1, 4096)
    ex = Executor(holder, mesh_engine=eng)
    ex_host = Executor(holder)
    q = QUERIES[0]
    want = ex_host.execute("i", q).results[0]
    assert ex.execute("i", q).results[0] == want
    assert eng.residency.flush(30.0)
    deadline = time.monotonic() + 10.0
    while (
        eng.residency.snapshot()["declined"] < 1
        and time.monotonic() < deadline
    ):
        time.sleep(0.02)
    snap = eng.residency.snapshot()
    assert snap["declined"] >= 1
    assert snap["cooldowns"] >= 1
    # Still correct, still host-served.
    assert ex.execute("i", q).results[0] == want
    eng.close()


def test_promote_ahead_spares_host_fallbacks(mesh1):
    """Promotion on demand alone, at a budget that holds one block pool
    and a half: two dashboards over disjoint fields alternate, so every
    swing needs a promotion and every query of the graded cycles falls
    back to the host tier, with only reactive promotions journaled.
    Every answer is the NumPy popcount's."""
    from pilosa_tpu.api import API, QueryRequest
    from pilosa_tpu.ops import bitops
    from pilosa_tpu.util.events import EventJournal

    h = Holder()
    h.open()
    idx = h.create_index("ab")
    rng = np.random.default_rng(11)
    shards = (0, 1)
    words_set = 8 * bitops.OCC_BLOCK_WORDS // 2  # 8 occupied blocks a row
    reqs = []
    for name in ("fa", "fb"):
        view = idx.create_field(name).view_if_not_exists("standard")
        want = 0
        for s in shards:
            pair = []
            for r in (0, 1):
                words = np.zeros(bitops.WORDS64, dtype=np.uint64)
                words[:words_set] = rng.integers(
                    0, 2**63, words_set, dtype=np.uint64
                )
                view.fragment_if_not_exists(s).load_row_words(r, words)
                pair.append(words)
            want += int(np.sum(np.bitwise_count(pair[0] & pair[1])))
        for frag in view.fragments.values():
            frag.cache.invalidate()
        reqs.append((
            QueryRequest("ab", f"Count(Intersect(Row({name}=0), Row({name}=1)))"),
            want,
        ))
    pool64 = 64 * len(shards) * bitops.OCC_BLOCK_WORDS * 4
    cycles, warm = 8, 2  # the first two cycles are not graded
    journal = EventJournal()
    eng = MeshEngine(
        h, mesh1, max_resident_bytes=(3 * pool64) // 2, journal=journal
    )
    eng.result_memo.maxsize = 0  # the residency path, not the memo lane
    api = API(holder=h, mesh_engine=eng)
    try:
        for cyc in range(cycles):
            if cyc == warm:
                fb0 = eng.host_fallbacks
            for req, want in reqs:
                assert int(api.query(req).results[0]) == want
                assert eng.residency.flush(60.0)
        fallbacks = eng.host_fallbacks - fb0
    finally:
        eng.close()
    causes = {
        e.fields.get("cause") for e in journal.events(type="engine.promotion")
    }
    assert causes == {"reactive"}, causes
    assert fallbacks == 2 * (cycles - warm), fallbacks


@pytest.mark.parametrize("cause", ["reactive", "warm_start"])
def test_full_promotion_counter_labeled_by_cause(cause):
    """The per-cause promotions counter and cause/trace plumbing
    through the residency queue (stub engine: no device work)."""
    calls = []

    class StubEngine:
        def _promote(self, key, rows, cause="reactive", trace_id=""):
            calls.append((key, rows, cause, trace_id))
            return "full", 123

        def _log(self, msg):
            pass

    c = REGISTRY.counter(METRIC_ENGINE_PROMOTIONS, cause=cause)
    c0 = c.get()
    rm = ResidencyManager(StubEngine())
    assert rm.request(("i", "f", "standard"), None,
                      cause=cause, trace_id="abc123")
    assert rm.flush(10.0)
    assert calls == [(("i", "f", "standard"), None, cause, "abc123")]
    assert c.get() == c0 + 1
    assert rm.promoted_bytes == 123
    rm.close()

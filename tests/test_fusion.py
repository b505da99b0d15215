"""Whole-program query compilation (docs/fusion.md): a heterogeneous
Count/Sum/Min/Max/TopN drain fused into ONE device program must be
bit-exact vs the sequential per-query oracle — including sparse-path
masks (the per-mask occupancy peel), memo-hit riders, and the fused
psum reduce over the 8-device test mesh — and the fused executable's
compile key must depend only on the drain's (op-kind, mask-slot)
multiset, never on row ids or arrival order."""

import threading
import time

import numpy as np
import pytest

from pilosa_tpu import pql
from pilosa_tpu.core.field import FieldOptions
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.executor import Executor
from pilosa_tpu.ops import SHARD_WIDTH
from pilosa_tpu.ops.bitops import OCC_BLOCK_BITS
from pilosa_tpu.parallel import MeshEngine, make_mesh
from pilosa_tpu.parallel import fusion, kernels
from pilosa_tpu.parallel.batcher import CountBatcher
from pilosa_tpu.util import plans as plans_mod

N_SHARDS = 8
SHARDS = list(range(N_SHARDS))


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(8)


def _call(q):
    return pql.parse(q).calls[0]


@pytest.fixture
def holder():
    """Segment field f (dense rows 10/11 + a SPARSE row 12 clustered in
    two occupancy blocks), widget field w, BSI field v — the dashboard
    shape."""
    h = Holder()
    h.open()
    idx = h.create_index("i")
    f = idx.create_field("f")
    w = idx.create_field("w")
    v = idx.create_field("v", FieldOptions(type="int", min=0, max=1000))
    ef = idx.existence_field()
    rng = np.random.default_rng(17)
    rows, cols = [], []
    for s in range(N_SHARDS):
        base = s * SHARD_WIDTH
        picks = rng.choice(SHARD_WIDTH, size=700, replace=False)
        for c in picks[:500]:
            rows.append(10)
            cols.append(base + int(c))
        for c in picks[250:]:
            rows.append(11)
            cols.append(base + int(c))
        # Row 12: clustered into 2 of 64 blocks -> sparse-path eligible.
        for b in (3, 40):
            for c in rng.choice(OCC_BLOCK_BITS, size=30, replace=False):
                rows.append(12)
                cols.append(base + b * OCC_BLOCK_BITS + int(c))
    f.import_bulk(rows, cols)
    ef.import_bulk([0] * len(cols), cols)
    w.import_bulk(
        [5] * 400 + [6] * 400 + [7] * 200, cols[:1000]
    )
    v.import_values(cols[:800], [int(x % 700) for x in range(800)])
    return h


SEG = "Row(f=10)"


def dashboard_entries(n_widgets=4, seg=SEG):
    """1 segment filter x N widgets of mixed ops — the fused planner's
    target workload."""
    segc = _call(seg)
    widgets = [
        ({"kind": "count", "call": _call(f"Intersect({seg}, Row(w=5))")},
         SHARDS),
        ({"kind": "sum", "field": "v", "filter": _call(seg)}, SHARDS),
        ({"kind": "topnf", "field": "w", "src": _call(seg), "n": 3,
          "threshold": 1, "row_ids": None}, SHARDS),
        ({"kind": "min", "field": "v", "filter": _call(seg)}, SHARDS),
        ({"kind": "max", "field": "v", "filter": _call(seg)}, SHARDS),
        ({"kind": "count", "call": _call(f"Intersect({seg}, Row(w=6))")},
         SHARDS),
        ({"kind": "topn", "field": "w", "rows": [5, 6, 7],
          "src": _call(seg)}, SHARDS),
        ({"kind": "count", "call": _call(f"Difference({seg}, Row(w=7))")},
         SHARDS),
    ]
    assert segc is not None
    return widgets[:n_widgets]


def oracle(eng, entries):
    """The retained sequential per-query path, one dispatch per item."""
    out = []
    for spec, shards in entries:
        k = spec["kind"]
        if k == "count":
            out.append(eng.count("i", spec["call"], shards))
        elif k == "sum":
            out.append(eng.sum("i", spec["field"], spec.get("filter"), shards))
        elif k in ("min", "max"):
            out.append(
                eng.min_max("i", spec["field"], spec.get("filter"), shards,
                            k == "min")
            )
        elif k == "topn":
            out.append(
                eng.topn_scores("i", spec["field"], spec["rows"],
                                spec["src"], shards)
            )
        else:
            out.append(
                eng.topn_full("i", spec["field"], spec["src"], shards,
                              spec.get("n") or 0, spec.get("threshold") or 1,
                              spec.get("row_ids"))
            )
    return out


def assert_results_equal(got, want):
    for i, (g, w) in enumerate(zip(got, want)):
        if isinstance(w, tuple) and len(w) == 3 and isinstance(
            w[0], np.ndarray
        ):
            assert np.array_equal(g[0], w[0]), f"item {i} scores"
            assert np.array_equal(np.asarray(g[1]), np.asarray(w[1])), (
                f"item {i} src counts"
            )
            assert g[2] == w[2], f"item {i} shard pos"
        else:
            assert g == w, f"item {i}: {g!r} != {w!r}"


# -- differential correctness ------------------------------------------------


@pytest.mark.parametrize("n", [2, 4, 8])
def test_fused_mixed_drain_bit_exact(holder, mesh, n):
    """The headline differential: mixed dashboards of every op kind,
    fused program vs sequential oracle, over the 8-device psum mesh."""
    eng = MeshEngine(holder, mesh)
    assert int(mesh.devices.size) == 8  # the fused psum is a real reduce
    entries = dashboard_entries(n)
    want = oracle(eng, entries)
    before = eng.fused_dispatches
    got = eng.fused_many("i", entries)
    assert_results_equal(got, want)
    # The whole drain was ONE fused dispatch.
    assert eng.fused_dispatches == before + 1
    assert eng.fused_programs >= 1


def test_fused_per_query_shard_subsets(holder, mesh):
    """Each rider applies its OWN shard mask inside the fused program."""
    eng = MeshEngine(holder, mesh)
    entries = [
        ({"kind": "count", "call": _call(f"Intersect({SEG}, Row(w=5))")},
         [0, 2]),
        ({"kind": "sum", "field": "v", "filter": _call(SEG)}, [1, 3, 5]),
        ({"kind": "min", "field": "v", "filter": _call(SEG)}, SHARDS),
    ]
    want = [
        eng.count("i", entries[0][0]["call"], [0, 2]),
        eng.sum("i", "v", _call(SEG), [1, 3, 5]),
        eng.min_max("i", "v", _call(SEG), SHARDS, True),
    ]
    assert_results_equal(eng.fused_many("i", entries), want)


def test_fused_shared_mask_evaluated_once(holder, mesh):
    """The acceptance shape: N=8 mixed drain sharing one segment filter
    evaluates each distinct mask ONCE — masks_evaluated == distinct
    subtrees, masks_referenced counts what the sequential path would
    have evaluated."""
    eng = MeshEngine(holder, mesh)
    entries = dashboard_entries(8)
    e0, r0 = eng.fused_masks_evaluated, eng.fused_masks_referenced
    eng.fused_many("i", entries)
    evaluated = eng.fused_masks_evaluated - e0
    referenced = eng.fused_masks_referenced - r0
    # Distinct subtrees in the 8-widget dashboard: Row(f=10), Row(w=5),
    # Row(w=6), Row(w=7), the two Intersects and one Difference = 7.
    distinct = set()
    for spec, _ in entries:
        distinct |= fusion.item_texts(spec)
    assert evaluated == len(distinct)
    assert referenced > evaluated  # sharing actually happened
    assert eng.fused_masks_referenced - r0 == referenced


def test_fused_sparse_mask_peels_per_mask(holder, mesh):
    """The sparse block-occupancy planner keeps working per-mask inside
    a fused drain: an unshared low-occupancy Count peels onto the
    block-gather kernels (bytes skipped counted) while its drain-mates
    stay fused — and every answer is still bit-exact."""
    eng = MeshEngine(holder, mesh)
    sparse_q = _call("Row(f=12)")  # 2/64 blocks occupied
    entries = [
        ({"kind": "count", "call": sparse_q}, SHARDS),
        ({"kind": "sum", "field": "v", "filter": _call(SEG)}, SHARDS),
        ({"kind": "count", "call": _call(f"Intersect({SEG}, Row(w=5))")},
         SHARDS),
    ]
    want = oracle(eng, entries)
    skipped0 = eng.device_bytes_skipped
    sparse0 = eng.sparse_dispatches
    got = eng.fused_many("i", entries)
    assert_results_equal(got, want)
    assert eng.sparse_dispatches > sparse0
    assert eng.device_bytes_skipped > skipped0
    # Sharing would forbid the peel: the same sparse row INSIDE a shared
    # subtree stays in the fused program (still bit-exact).
    entries2 = [
        ({"kind": "count", "call": _call("Row(f=12)")}, SHARDS),
        ({"kind": "sum", "field": "v", "filter": _call("Row(f=12)")}, SHARDS),
    ]
    want2 = oracle(eng, entries2)
    sparse1 = eng.sparse_dispatches
    got2 = eng.fused_many("i", entries2)
    assert_results_equal(got2, want2)
    assert eng.sparse_dispatches == sparse1  # shared mask: no peel


def test_fused_error_item_isolated(holder, mesh):
    """One bad item (unknown field) fails alone; drain-mates answer."""
    eng = MeshEngine(holder, mesh)
    entries = [
        ({"kind": "count", "call": _call("Row(nope=1)")}, SHARDS),
        ({"kind": "sum", "field": "v", "filter": _call(SEG)}, SHARDS),
    ]
    fd = eng.fused_many_async("i", entries)
    assert fd.errors[0] is not None
    assert fd.errors[1] is None
    import jax

    host = jax.device_get(fd.dev)
    assert fd.decoders[1](host) == eng.sum("i", "v", _call(SEG), SHARDS)


def test_fused_missing_bsi_field_empty_result(holder, mesh):
    """A Sum/Min over a non-BSI field mirrors the oracle's (0, 0)."""
    eng = MeshEngine(holder, mesh)
    entries = [
        ({"kind": "sum", "field": "w", "filter": _call(SEG)}, SHARDS),
        ({"kind": "count", "call": _call(SEG)}, SHARDS),
        ({"kind": "sum", "field": "v", "filter": _call(SEG)}, SHARDS),
    ]
    got = eng.fused_many("i", entries)
    assert got[0] == (0, 0)
    assert got[1] == eng.count("i", _call(SEG), SHARDS)
    assert got[2] == eng.sum("i", "v", _call(SEG), SHARDS)


# -- compile-key property ----------------------------------------------------


def test_compile_key_multiset_reuse(holder, mesh):
    """Two drains with the same (op-kind, mask-slot) multiset — but
    different row ids AND different arrival order — reuse ONE fused
    executable; a different multiset compiles a new one."""
    eng = MeshEngine(holder, mesh)

    def drain(seg_row, w1, w2):
        return [
            ({"kind": "count",
              "call": _call(f"Intersect(Row(f={seg_row}), Row(w={w1}))")},
             SHARDS),
            ({"kind": "sum", "field": "v",
              "filter": _call(f"Row(f={seg_row})")}, SHARDS),
            ({"kind": "count",
              "call": _call(f"Intersect(Row(f={seg_row}), Row(w={w2}))")},
             SHARDS),
        ]

    eng.fused_many("i", drain(10, 5, 6))
    n1 = kernels.fused_tree._cache_size()
    e2 = drain(11, 6, 5)
    e2 = [e2[2], e2[0], e2[1]]  # permuted arrival order
    got = eng.fused_many("i", e2)
    assert kernels.fused_tree._cache_size() == n1  # reused
    want = [
        eng.count("i", e2[0][0]["call"], SHARDS),
        eng.count("i", e2[1][0]["call"], SHARDS),
        eng.sum("i", "v", _call("Row(f=11)"), SHARDS),
    ]
    assert_results_equal(got, want)
    # A different multiset (extra op kind) is a new program.
    extra = drain(10, 5, 6) + [
        ({"kind": "min", "field": "v", "filter": _call(SEG)}, SHARDS)
    ]
    eng.fused_many("i", extra)
    assert kernels.fused_tree._cache_size() == n1 + 1


def test_fused_plan_cache_invalidated_by_peeled_field_write(holder, mesh):
    """Review regression: the sparse-peeled Count's stack lowers through
    its OWN _Lowering, so its version token must still gate the cached
    plan — a write to the peeled field followed by an out-of-drain read
    (which re-syncs and DONATES the old matrix) must rebuild the plan,
    not re-dispatch stale occupancy over a dead buffer."""
    eng = MeshEngine(holder, mesh)
    sparse_q = _call("Row(f=12)")
    entries = [
        ({"kind": "count", "call": sparse_q}, SHARDS),
        ({"kind": "sum", "field": "v", "filter": _call(SEG)}, SHARDS),
    ]
    got1 = eng.fused_many("i", entries)
    assert got1[0] == eng.count("i", sparse_q, SHARDS)
    # Write a NEW occupancy block into the peeled row, then force the
    # stack to re-sync (donating the old matrix) via an oracle read.
    frag = holder.fragment("i", "f", "standard", 0)
    frag.set_bit(12, 55 * OCC_BLOCK_BITS + 7)
    want = eng.count("i", sparse_q, SHARDS)
    got2 = eng.fused_many("i", entries)
    assert got2[0] == want  # fresh answer, no stale block list, no crash
    assert got2[1] == eng.sum("i", "v", _call(SEG), SHARDS)


def test_fused_plan_cache_hits_across_arrival_orders(holder, mesh):
    """Review regression: the plan-cache key is canonical, so the same
    dashboard arriving in ANY thread interleaving reuses one plan (and
    the decoders map back to arrival order)."""
    eng = MeshEngine(holder, mesh)
    base = dashboard_entries(4)
    want = oracle(eng, base)
    eng.fused_many("i", base)  # build + cache
    misses0 = eng.cache_stats["fused_plan"][1]
    perm = [base[2], base[0], base[3], base[1]]
    got = eng.fused_many("i", perm)
    assert eng.cache_stats["fused_plan"][1] == misses0  # pure hit
    assert_results_equal(got, [want[2], want[0], want[3], want[1]])


# -- batcher integration -----------------------------------------------------


def _hot(batcher):
    """Force the queue path deterministically: a permanently-hot window
    makes every submit queue into the drain instead of running direct."""
    batcher._last_fused = time.monotonic() + 10_000


def _submit_together(batcher, jobs):
    """Run each of ``jobs`` (name -> callable, each queueing one item)
    on a thread of its own, all released at once, and return their
    results by name.  The accumulation window fuses what arrives
    inside its quiet interval (5 ms at most), and threads on a busy
    host can arrive further apart than that: here the window waits
    for all of them — it closes full at ``len(jobs)``, and until then
    stays open for the whole ACCUM_WINDOW."""
    batcher.max_batch = len(jobs)
    batcher.QUIET_MIN = batcher.QUIET_MAX = batcher.ACCUM_WINDOW
    gate = threading.Barrier(len(jobs))
    results = {}

    def run(name, fn):
        gate.wait(30)
        results[name] = fn()

    threads = [
        threading.Thread(target=run, args=job) for job in jobs.items()
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    return results


def test_batcher_heterogeneous_drain(holder, mesh):
    """Concurrent mixed submissions drain into fused programs through
    the real accumulate/dispatch/collect pipeline, bit-exact."""
    eng = MeshEngine(holder, mesh)
    eng._batcher = CountBatcher(eng)
    b = eng.batcher()
    count_q = _call(f"Intersect({SEG}, Row(w=5))")
    want_count = eng.count("i", count_q, SHARDS)
    want_sum = eng.sum("i", "v", _call(SEG), SHARDS)
    want_min = eng.min_max("i", "v", _call(SEG), SHARDS, True)
    want_tf = eng.topn_full("i", "w", _call(SEG), SHARDS, 3, 1)
    _hot(b)
    results = _submit_together(b, {
        "count": lambda: b.submit("i", count_q, SHARDS),
        "sum": lambda: eng.batched_sum("i", "v", _call(SEG), SHARDS),
        "min": lambda: eng.batched_min_max("i", "v", _call(SEG), SHARDS, True),
        "tf": lambda: eng.batched_topn_full(
            "i", "w", _call(SEG), SHARDS, 3, 1),
    })
    assert results["count"] == want_count
    assert results["sum"] == want_sum
    assert results["min"] == want_min
    assert results["tf"] == want_tf
    assert eng.fused_programs >= 1
    eng.close()


def test_batcher_memo_hit_rider_in_fused_drain(holder, mesh):
    """A repeat Count answers from the memo at submit time while its
    fused drain-mates dispatch — the hit never re-enters the program."""
    eng = MeshEngine(holder, mesh)
    eng._batcher = CountBatcher(eng)
    b = eng.batcher()
    count_q = _call(f"Intersect({SEG}, Row(w=5))")
    want_count = b.submit("i", count_q, SHARDS)  # populates the memo
    hits0 = eng.result_memo.hits
    _hot(b)
    results = {}

    def run(name, fn):
        results[name] = fn()

    q0 = eng.fused_program_queries
    threads = [
        threading.Thread(target=run, args=(
            "count", lambda: b.submit("i", count_q, SHARDS))),
        threading.Thread(target=run, args=(
            "sum", lambda: eng.batched_sum("i", "v", _call(SEG), SHARDS))),
        threading.Thread(target=run, args=(
            "max", lambda: eng.batched_min_max(
                "i", "v", _call(SEG), SHARDS, False))),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert results["count"] == want_count
    assert eng.result_memo.hits > hits0
    assert results["sum"] == eng.sum("i", "v", _call(SEG), SHARDS)
    assert results["max"] == eng.min_max("i", "v", _call(SEG), SHARDS, False)
    # The memo-hit Count never became a fused-program rider.
    assert eng.fused_program_queries - q0 <= 2
    eng.close()


def test_batcher_solo_aggregate_reuses_per_op_program(holder, mesh):
    """A drain that fuses down to ONE aggregate takes the existing
    per-op executable (solo lane), not a 1-item fused program."""
    eng = MeshEngine(holder, mesh)
    eng._batcher = CountBatcher(eng)
    b = eng.batcher()
    _hot(b)
    p0 = eng.fused_programs
    got = eng.batched_sum("i", "v", _call(SEG), SHARDS)
    assert got == eng.sum("i", "v", _call(SEG), SHARDS)
    assert eng.fused_programs == p0
    eng.close()


def test_batcher_direct_path_idle_aggregate(holder, mesh):
    """A lone aggregate on an idle pipe runs the blocking single-op
    program directly — zero batcher machinery, same answer."""
    eng = MeshEngine(holder, mesh)
    eng._batcher = CountBatcher(eng)
    got = eng.batched_min_max("i", "v", _call(SEG), SHARDS, False)
    assert got == eng.min_max("i", "v", _call(SEG), SHARDS, False)
    assert eng.fused_programs == 0
    eng.close()


def test_batcher_bad_op_isolated_from_drain(holder, mesh):
    """An aggregate whose filter can't lower fails alone; the fused
    drain-mates still answer."""
    eng = MeshEngine(holder, mesh)
    eng._batcher = CountBatcher(eng)
    b = eng.batcher()
    _hot(b)
    results, errors = {}, {}

    def run(name, fn):
        try:
            results[name] = fn()
        except Exception as e:  # noqa: BLE001
            errors[name] = e

    threads = [
        threading.Thread(target=run, args=(
            "bad", lambda: eng.batched_sum(
                "i", "v", _call("Row(missing_field=1)"), SHARDS))),
        threading.Thread(target=run, args=(
            "sum", lambda: eng.batched_sum("i", "v", _call(SEG), SHARDS))),
        threading.Thread(target=run, args=(
            "min", lambda: eng.batched_min_max(
                "i", "v", _call(SEG), SHARDS, True))),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert "bad" in errors
    assert results["sum"] == eng.sum("i", "v", _call(SEG), SHARDS)
    assert results["min"] == eng.min_max("i", "v", _call(SEG), SHARDS, True)
    eng.close()


# -- weighted device-cost attribution ---------------------------------------


def test_fused_cost_attribution_weighted_by_footprint(holder, mesh):
    """The PR 9 fix: riders of one fused dispatch are charged by their
    mask/reduce FOOTPRINT, not an even split — a 1-mask Count rider
    pays less than the 9-plane Sum it rode with."""
    eng = MeshEngine(holder, mesh)
    eng._batcher = CountBatcher(eng)
    b = eng.batcher()
    _hot(b)
    plans = {
        "count": plans_mod.QueryPlan("i", "count"),
        "sum": plans_mod.QueryPlan("i", "sum"),
    }

    def planned(name, fn):
        def run():
            with plans_mod.attach(plans[name]):
                return fn()

        return run

    count_q = _call("Intersect(Row(f=11), Row(w=6))")
    _submit_together(b, {
        "count": planned("count", lambda: b.submit("i", count_q, SHARDS)),
        "sum": planned("sum", lambda: eng.batched_sum(
            "i", "v", _call("Row(f=11)"), SHARDS)),
    })
    assert eng.fused_programs >= 1
    dev_count = plans["count"].device_seconds
    dev_sum = plans["sum"].device_seconds
    assert dev_count > 0 and dev_sum > 0
    # Sum sweeps its shared 1-row mask half + 9 BSI planes; the Count
    # sweeps the half-shared mask + one widget row: ~4x lighter.
    assert dev_sum > dev_count
    op = next(o for o in plans["sum"].ops if o.get("path") == "fused_program")
    assert op["mask_shared_with"] >= 1
    assert 0 < op["fused_cost_frac"] < 1
    eng.close()


def test_rider_note_frac_division():
    note = {"path": "fused_program", "bytes_touched": 1000}
    even = plans_mod.rider_note(note, 4)
    assert even["bytes_touched"] == 250
    frac = plans_mod.rider_note(note, 4, frac=0.8)
    assert frac["bytes_touched"] == 800


def test_analyzer_annotates_mask_sharing():
    p = plans_mod.QueryPlan("i", "q")
    p.note_op(op="Sum", path="fused_program", mask_shared_with=3,
              masks_evaluated=2, masks_referenced=7)
    notes = plans_mod.analyze(p)
    assert any("mask shared with 3" in n for n in notes)
    assert any("5 evaluation(s) saved" in n for n in notes)


# -- executor routing --------------------------------------------------------


def test_executor_dashboard_concurrent_bit_exact(holder, mesh):
    """End to end through the executor: a concurrent mixed dashboard
    (Count/Sum/Min/Max/TopN as separate queries, the HTTP arrival
    shape) fuses through the batch lane and every response matches the
    host-path executor oracle."""
    eng = MeshEngine(holder, mesh)
    eng._batcher = CountBatcher(eng)
    ex = Executor(holder, mesh_engine=eng)
    plain = Executor(holder)
    queries = [
        f"Count(Intersect({SEG}, Row(w=5)))",
        f"Sum({SEG}, field=v)",
        f"Min({SEG}, field=v)",
        f"Max({SEG}, field=v)",
        f"TopN(w, {SEG}, n=3)",
        f"Count(Intersect({SEG}, Row(w=6)))",
    ]
    want = [plain.execute("i", q).results for q in queries]
    _hot(eng.batcher())
    results = [None] * len(queries)

    def run(k):
        results[k] = ex.execute("i", queries[k]).results

    threads = [
        threading.Thread(target=run, args=(k,)) for k in range(len(queries))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    for k in range(len(queries)):
        assert results[k] == want[k], f"query {k}: {queries[k]}"
    eng.close()


def test_executor_aggregates_still_exact_sequential(holder, mesh):
    """The solo/direct routing keeps sequential aggregate execution
    byte-identical to the host path (no batcher in the way when idle)."""
    eng = MeshEngine(holder, mesh)
    ex = Executor(holder, mesh_engine=eng)
    plain = Executor(holder)
    for q in (
        f"Sum({SEG}, field=v)",
        f"Min({SEG}, field=v)",
        f"Max({SEG}, field=v)",
        f"TopN(w, {SEG}, n=2)",
        "TopN(w, n=2)",
    ):
        assert ex.execute("i", q).results == plain.execute("i", q).results, q
    eng.close()


# -- fused-program metrics ---------------------------------------------------


def test_fused_program_metric_series(holder, mesh):
    from pilosa_tpu.util.stats import (
        METRIC_ENGINE_FUSED_MASKS_EVAL,
        METRIC_ENGINE_FUSED_MASKS_REF,
        METRIC_ENGINE_FUSED_PROGRAMS,
        METRIC_ENGINE_FUSED_QUERIES,
        REGISTRY,
    )

    eng = MeshEngine(holder, mesh)
    c0 = {
        name: REGISTRY.counter(name).get()
        for name in (
            METRIC_ENGINE_FUSED_PROGRAMS,
            METRIC_ENGINE_FUSED_QUERIES,
            METRIC_ENGINE_FUSED_MASKS_EVAL,
            METRIC_ENGINE_FUSED_MASKS_REF,
        )
    }
    eng.fused_many("i", dashboard_entries(4))
    assert REGISTRY.counter(METRIC_ENGINE_FUSED_PROGRAMS).get() == (
        c0[METRIC_ENGINE_FUSED_PROGRAMS] + 1
    )
    assert REGISTRY.counter(METRIC_ENGINE_FUSED_QUERIES).get() == (
        c0[METRIC_ENGINE_FUSED_QUERIES] + 4
    )
    assert REGISTRY.counter(METRIC_ENGINE_FUSED_MASKS_EVAL).get() > (
        c0[METRIC_ENGINE_FUSED_MASKS_EVAL]
    )
    assert REGISTRY.counter(METRIC_ENGINE_FUSED_MASKS_REF).get() > (
        c0[METRIC_ENGINE_FUSED_MASKS_REF]
    )
    snap = eng.cache_snapshot()
    assert snap["fusedPrograms"] >= 1
    assert snap["fusedMasksReferenced"] >= snap["fusedMasksEvaluated"]


# -- cross-index drains ------------------------------------------------------


def _add_index_j(holder):
    """Second index for cross-index drains: segment field g, widget
    field u, disjoint rng stream from index i."""
    idx = holder.create_index("j")
    g = idx.create_field("g")
    u = idx.create_field("u")
    ef = idx.existence_field()
    rng = np.random.default_rng(23)
    rows, cols = [], []
    for s in range(N_SHARDS):
        base = s * SHARD_WIDTH
        picks = rng.choice(SHARD_WIDTH, size=400, replace=False)
        for c in picks[:300]:
            rows.append(4)
            cols.append(base + int(c))
        for c in picks[150:]:
            rows.append(5)
            cols.append(base + int(c))
    g.import_bulk(rows, cols)
    ef.import_bulk([0] * len(cols), cols)
    u.import_bulk([2] * 500, cols[:500])


def test_cross_index_fused_drain_bit_exact(holder, mesh):
    """A drain spanning TWO indexes — counts, a device-trim TopN, a
    GroupBy edge, a Sum — compiles to ONE fused program (mask slots
    keyed (index, subtree)) and every item is bit-exact vs its
    per-index sequential oracle."""
    _add_index_j(holder)
    eng = MeshEngine(holder, mesh)
    seg_i = _call(SEG)
    seg_j = _call("Row(g=4)")
    entries = [
        ("i", {"kind": "count", "call": _call(f"Intersect({SEG}, Row(w=5))")},
         SHARDS),
        ("j", {"kind": "count", "call": _call("Intersect(Row(g=4), Row(u=2))")},
         SHARDS),
        ("i", {"kind": "topnf", "field": "w", "src": seg_i, "n": 3,
               "threshold": 1, "row_ids": None}, SHARDS),
        ("j", {"kind": "group", "fields": ["g"], "rows": [[4, 5]],
               "filter": _call("Row(u=2)")}, SHARDS),
        ("i", {"kind": "sum", "field": "v", "filter": seg_i}, SHARDS),
    ]
    want = [
        eng.count("i", entries[0][1]["call"], SHARDS),
        eng.count("j", entries[1][1]["call"], SHARDS),
        eng.topn_full("i", "w", seg_i, SHARDS, 3, 1),
        eng.group_counts("j", ["g"], [[4, 5]], _call("Row(u=2)"), SHARDS),
        eng.sum("i", "v", seg_i, SHARDS),
    ]
    p0 = eng.fused_programs
    got = eng.fused_drain(entries)
    assert eng.fused_programs == p0 + 1  # ONE program spans both indexes
    assert got[0] == want[0]
    assert got[1] == want[1]
    assert got[2] == want[2]
    assert np.array_equal(np.asarray(got[3]), np.asarray(want[3]))
    assert got[4] == want[4]
    # The plan-note satellite: every item is stamped crossIndex, the
    # TopN edge records its device trim, the GroupBy its combo width.
    fd = eng.fused_drain_async(entries)
    plans_mod.take_dispatch_note()
    notes = fd.item_notes
    assert all(n.get("crossIndex") for n in notes)
    assert notes[2].get("topkDevice")
    assert notes[3].get("fusedGroupBy") == 2
    assert seg_j is not None
    eng.close()


def test_cross_index_fused_plan_cache_reuse(holder, mesh):
    """The cross-index drain's plan caches and revalidates like the
    single-index one: a second dispatch of the same drain shape reuses
    the compiled plan; a write to EITHER index invalidates it."""
    _add_index_j(holder)
    eng = MeshEngine(holder, mesh)
    entries = [
        ("i", {"kind": "count", "call": _call(SEG)}, SHARDS),
        ("j", {"kind": "count", "call": _call("Row(g=4)")}, SHARDS),
    ]
    want = eng.fused_drain(entries)
    n0 = len(eng._fused_plans)
    assert eng.fused_drain(entries) == want
    assert len(eng._fused_plans) == n0  # reused, not replanned
    holder.index("j").field("g").set_bit(4, 3 * SHARD_WIDTH + 7)
    got = eng.fused_drain(entries)
    assert got[0] == want[0]
    assert got[1] == eng.count("j", _call("Row(g=4)"), SHARDS)
    eng.close()


def test_cross_index_batcher_pools_one_program(holder, mesh):
    """End to end through the batcher: concurrent submissions against
    DIFFERENT indexes land in one drain and fuse into one program."""
    _add_index_j(holder)
    eng = MeshEngine(holder, mesh)
    # The oracle counts below would otherwise seed the result memo and
    # the submissions would answer as memo-hit riders, never fusing.
    eng.result_memo.maxsize = 0
    eng._batcher = CountBatcher(eng)
    b = eng.batcher()
    ci = _call(f"Intersect({SEG}, Row(w=5))")
    cj = _call("Intersect(Row(g=4), Row(u=2))")
    want_i = eng.count("i", ci, SHARDS)
    want_j = eng.count("j", cj, SHARDS)
    want_sum = eng.sum("i", "v", _call(SEG), SHARDS)
    _hot(b)
    p0 = eng.fused_programs
    results = _submit_together(b, {
        "ci": lambda: b.submit("i", ci, SHARDS),
        "cj": lambda: b.submit("j", cj, SHARDS),
        "sum": lambda: eng.batched_sum("i", "v", _call(SEG), SHARDS),
    })
    assert results["ci"] == want_i
    assert results["cj"] == want_j
    assert results["sum"] == want_sum
    assert eng.fused_programs >= p0 + 1
    eng.close()


# -- the plan miner ----------------------------------------------------------


def test_plan_miner_windows_and_savings():
    from pilosa_tpu.util import plan_miner

    plans = [
        {"index": "i", "query": f"Count(Intersect({SEG}, Row(w=5)))",
         "startTime": 100.0},
        {"index": "i", "query": f"Count(Intersect({SEG}, Row(w=6)))",
         "startTime": 101.0},
        {"index": "i", "query": f"Sum({SEG}, field=v)", "startTime": 102.0},
        {"index": "i", "query": f"TopN(w, {SEG}, n=3)", "startTime": 103.0},
        # Same subtree in a LATER window: no cross-window sharing.
        {"index": "i", "query": f"Min({SEG}, field=v)", "startTime": 900.0},
        # Different index: never shares with "i".
        {"index": "j", "query": f"Sum({SEG}, field=v)", "startTime": 104.0},
        # Unparseable (truncated) plan text is skipped, not fatal.
        {"index": "i", "query": "Count(Intersect(Row(f=1", "startTime": 105.0},
    ]
    r = plan_miner.mine(plans, window_s=60.0)
    assert r["queries"] == 6
    assert r["projectedEvalsSaved"] == 3  # Row(f=10) x4 in window 1
    top = r["topShared"][0]
    assert top["mask"] == SEG and top["evals_saved"] == 3
    assert r["maskEvaluations"] - r["distinctMasks"] == 3
    text = plan_miner.render(r)
    assert "fusion would save 3" in text


def test_plan_miner_flatten_dedupes():
    from pilosa_tpu.util import plan_miner

    p = {"traceID": "t1", "startTime": 1.0, "query": "Count(Row(f=1))"}
    doc = {"recent": [p], "slow": {"Count": [dict(p)]}}
    assert len(plan_miner.flatten_plans(doc)) == 1


def test_plan_miner_matches_fused_planner_canonicalization(holder, mesh):
    """The miner's projection and the fused planner agree: distinct
    masks mined from a dashboard's query texts == masks_evaluated when
    the same dashboard actually fuses."""
    from pilosa_tpu.util import plan_miner

    eng = MeshEngine(holder, mesh)
    entries = dashboard_entries(8)
    texts = {
        "count": lambda s: f"Count({s['call']})",
        "sum": lambda s: f"Sum({s['filter']}, field={s['field']})",
        "min": lambda s: f"Min({s['filter']}, field={s['field']})",
        "max": lambda s: f"Max({s['filter']}, field={s['field']})",
        "topn": lambda s: f"TopN({s['field']}, {s['src']}, n=3)",
        "topnf": lambda s: f"TopN({s['field']}, {s['src']}, n=3)",
    }
    plans = [
        {"index": "i", "query": texts[spec["kind"]](spec), "startTime": 50.0}
        for spec, _ in entries
    ]
    r = plan_miner.mine(plans, window_s=60.0)
    e0 = eng.fused_masks_evaluated
    eng.fused_many("i", entries)
    assert r["distinctMasks"] == eng.fused_masks_evaluated - e0

"""Sparsity-aware execution: occupancy summaries, block-skipping
kernels, batch CSE, and the versioned result memo (docs/sparsity.md).

Differential discipline: occupancy summaries must stay EXACT against
stack contents across every write path (a false negative makes the
block-skipping kernel silently drop set bits — a correctness bug), the
result memo must never serve a stale hit after a write, and the CSE'd
batch must return byte-identical answers to the unfused path."""

import numpy as np
import pytest

from pilosa_tpu import pql
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.ops import SHARD_WIDTH
from pilosa_tpu.ops.bitops import (
    OCC_BLOCK_BITS,
    OCC_BLOCKS,
    OCC_BLOCK_WORDS,
    WORDS,
    occupancy64,
    occupancy64_from_positions,
)
from pilosa_tpu.parallel import MeshEngine, make_mesh
from pilosa_tpu.parallel.engine import _ResultMemo
from pilosa_tpu.roaring import codec

N_SHARDS = 8


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(8)


@pytest.fixture
def holder():
    h = Holder()
    h.open()
    return h


def build_clustered(holder, rows_blocks, n_shards=N_SHARDS, index="i",
                    field="f"):
    """Field whose row r occupies exactly ``rows_blocks[r]`` occupancy
    blocks per shard (clustered bits — the shape roaring exists for)."""
    idx = holder.index(index) or holder.create_index(index)
    f = idx.field(field) or idx.create_field(field)
    rng = np.random.default_rng(11)
    row_ids, cols = [], []
    for s in range(n_shards):
        base = s * SHARD_WIDTH
        for r, blocks in rows_blocks.items():
            for b in blocks:
                picks = rng.choice(OCC_BLOCK_BITS, size=40, replace=False)
                for c in picks:
                    row_ids.append(r)
                    cols.append(base + b * OCC_BLOCK_BITS + int(c))
    f.import_bulk(row_ids, cols)
    return f


def stack_occ_expected(holder, index, field, view, stack):
    want = np.zeros_like(stack.occ)
    for si, s in enumerate(stack.shards):
        frag = holder.fragment(index, field, view, s)
        if frag is None:
            continue
        for r, ri in stack.row_index.items():
            want[ri, si] = np.uint64(frag.row_occupancy(r))
    return want


# -- occupancy primitives ---------------------------------------------------


def test_occupancy_primitives():
    words = np.zeros(WORDS, dtype=np.uint32)
    assert occupancy64(words) == 0
    words[0] = 1  # block 0
    words[5 * OCC_BLOCK_WORDS + 3] = 0x10  # block 5
    words[63 * OCC_BLOCK_WORDS] = 2  # block 63
    want = (1 << 0) | (1 << 5) | (1 << 63)
    assert occupancy64(words) == want
    # positions form agrees with the dense form
    pos = np.array(
        [0, 5 * OCC_BLOCK_BITS + 100, 63 * OCC_BLOCK_BITS + 1], dtype=np.uint32
    )
    assert occupancy64_from_positions(pos) == want
    assert occupancy64_from_positions(np.empty(0, dtype=np.uint32)) == 0


def test_fragment_sync_snapshot_carries_exact_occupancy():
    from pilosa_tpu.core.fragment import Fragment

    frag = Fragment("i", "f", "standard", 0)
    frag.set_bit(3, 5)
    v0 = frag._version
    # Word-level dirty: occupancy must reflect the NEW block too.
    frag.set_bit(3, 7 * OCC_BLOCK_BITS + 9)
    _, dirty = frag.sync_snapshot(v0)
    assert dirty[3][0] == "words"
    assert dirty[3][3] == frag.row_occupancy(3) == (1 << 0) | (1 << 7)
    # Clearing a block's only bit must DROP its occupancy bit (a
    # conservative summary here would be tolerable; a missing bit never).
    v1 = frag._version
    frag.clear_bit(3, 7 * OCC_BLOCK_BITS + 9)
    _, dirty = frag.sync_snapshot(v1)
    assert dirty[3][3] == frag.row_occupancy(3) == 1


# -- occupancy differential across write paths ------------------------------


def test_stack_occupancy_exact_across_writes(holder, mesh):
    build_clustered(holder, {10: (0, 3), 11: (3, 9)})
    eng = MeshEngine(holder, mesh)
    stack = eng.field_stack("i", "f", "standard")
    assert stack.occ is not None
    np.testing.assert_array_equal(
        stack.occ, stack_occ_expected(holder, "i", "f", "standard", stack)
    )

    # set: a bit in a previously-empty block, incremental scatter sync.
    frag2 = holder.fragment("i", "f", "standard", 2)
    frag2.set_bit(10, 2 * SHARD_WIDTH + 50 * OCC_BLOCK_BITS + 1)
    rebuilds = eng.stack_rebuilds
    stack = eng.field_stack("i", "f", "standard")
    assert eng.stack_rebuilds == rebuilds  # synced, not rebuilt
    np.testing.assert_array_equal(
        stack.occ, stack_occ_expected(holder, "i", "f", "standard", stack)
    )

    # clear: the block's only remaining bit drops its occupancy bit.
    frag2.clear_bit(10, 2 * SHARD_WIDTH + 50 * OCC_BLOCK_BITS + 1)
    stack = eng.field_stack("i", "f", "standard")
    assert eng.stack_rebuilds == rebuilds
    assert not stack.occ[stack.row_index[10], 2] & np.uint64(1 << 50)
    np.testing.assert_array_equal(
        stack.occ, stack_occ_expected(holder, "i", "f", "standard", stack)
    )

    # bulk import into EXISTING rows across shards: still incremental.
    f = holder.index("i").field("f")
    rows, cols = [], []
    for s in range(N_SHARDS):
        rows.append(11)
        cols.append(s * SHARD_WIDTH + 33 * OCC_BLOCK_BITS + s)
    f.import_bulk(rows, cols)
    stack = eng.field_stack("i", "f", "standard")
    assert eng.stack_rebuilds == rebuilds
    np.testing.assert_array_equal(
        stack.occ, stack_occ_expected(holder, "i", "f", "standard", stack)
    )

    # import_roaring into an existing row: incremental, exact.
    pos = np.asarray(
        [10 * SHARD_WIDTH + 44 * OCC_BLOCK_BITS + 7], dtype=np.uint64
    )
    holder.fragment("i", "f", "standard", 0).import_roaring(
        codec.serialize(pos)
    )
    stack = eng.field_stack("i", "f", "standard")
    assert eng.stack_rebuilds == rebuilds
    assert stack.occ[stack.row_index[10], 0] & np.uint64(1 << 44)
    np.testing.assert_array_equal(
        stack.occ, stack_occ_expected(holder, "i", "f", "standard", stack)
    )

    # evict-then-rebuild: the rebuilt summary is exact from scratch.
    with eng._dispatch_lock, eng._stacks_lock:
        eng._evict(("i", "f", "standard"))
    stack = eng.field_stack("i", "f", "standard")
    assert eng.stack_rebuilds == rebuilds + 1
    np.testing.assert_array_equal(
        stack.occ, stack_occ_expected(holder, "i", "f", "standard", stack)
    )


# -- sparse-vs-dense differential -------------------------------------------


def test_sparse_count_matches_dense(holder, mesh):
    build_clustered(holder, {10: (0, 3), 11: (3, 9), 12: (20,)})
    idx = holder.index("i")
    idx.existence_field().import_bulk(
        [0] * N_SHARDS, [s * SHARD_WIDTH for s in range(N_SHARDS)]
    )
    eng = MeshEngine(holder, mesh)
    dense = MeshEngine(holder, mesh)
    dense.sparse_enabled = False
    shards = list(range(N_SHARDS))
    queries = [
        "Row(f=10)",
        "Intersect(Row(f=10), Row(f=11))",
        "Union(Row(f=10), Row(f=12))",
        "Difference(Row(f=11), Row(f=10))",
        "Xor(Row(f=10), Row(f=11))",
        "Intersect(Row(f=10), Row(f=12))",  # disjoint blocks: 0 survivors
        "Not(Row(f=10))",
        "Union(Row(f=10), Row(f=999))",  # missing row: zero leaf
    ]
    for q in queries:
        call = pql.parse(q).calls[0]
        # memo off: every iteration must really evaluate
        eng.result_memo.maxsize = 0
        dense.result_memo.maxsize = 0
        assert eng.count("i", call, shards) == dense.count("i", call, shards), q
    assert eng.sparse_dispatches > 0
    assert eng.device_bytes_skipped > 0
    assert dense.sparse_dispatches == 0
    # requested-shard subsets stay correct through the block lists
    eng.result_memo.maxsize = 0
    call = pql.parse("Intersect(Row(f=10), Row(f=11))").calls[0]
    assert eng.count("i", call, [1, 4]) == dense.count("i", call, [1, 4])


@pytest.mark.parametrize("blocks", [1, 2, 6, 32])
def test_density_points_sparse_dense_and_numpy_agree(holder, mesh, blocks):
    """The density points ROADMAP S6 (b) carries for the cell to come:
    rows half-filling 1 / 2 / 6 / 32 of a shard's 64 occupancy blocks.
    The occupancy-guided engine, the dense sweep and a NumPy popcount of
    the host words give one answer; at or under the threshold (a quarter
    of the blocks) the sparse form runs and skips bytes, above it the
    dense sweep does."""
    rng = np.random.default_rng(7)
    f = holder.create_index("i").create_field("f")
    shards = list(range(N_SHARDS))
    cols = {0: [], 1: []}
    for s in shards:
        for b in range(blocks):
            picks = s * SHARD_WIDTH + b * OCC_BLOCK_BITS + rng.choice(
                OCC_BLOCK_BITS, size=OCC_BLOCK_BITS // 2, replace=False
            )
            # Row 0 and row 1 share the middle half of the picks.
            cols[0] += picks[: 3 * len(picks) // 4].tolist()
            cols[1] += picks[len(picks) // 4:].tolist()
    f.import_bulk([0] * len(cols[0]) + [1] * len(cols[1]), cols[0] + cols[1])
    want = len(set(cols[0]) & set(cols[1]))
    assert want == N_SHARDS * blocks * OCC_BLOCK_BITS // 4
    eng = MeshEngine(holder, mesh)
    dense = MeshEngine(holder, mesh)
    dense.sparse_enabled = False
    call = pql.parse("Intersect(Row(f=0), Row(f=1))").calls[0]
    assert eng.count("i", call, shards) == want
    assert dense.count("i", call, shards) == want
    assert dense.sparse_dispatches == 0
    took_sparse = blocks / 64 <= eng.sparse_threshold
    assert (eng.sparse_dispatches > 0) == took_sparse
    assert (eng.device_bytes_skipped > 0) == took_sparse


def test_dense_rows_keep_dense_path(holder, mesh):
    """Above the density threshold the dense sweep runs (the earlier
    Pallas deletion note applies to IT; sparsity is a different
    roofline — docs/sparsity.md selection rule)."""
    idx = holder.create_index("i")
    f = idx.create_field("f")
    rng = np.random.default_rng(5)
    rows, cols = [], []
    for s in range(4):
        for c in rng.choice(SHARD_WIDTH, size=2000, replace=False):
            rows.append(10 + (int(c) & 1))
            cols.append(s * SHARD_WIDTH + int(c))
    f.import_bulk(rows, cols)  # uniform bits: ~every block occupied
    eng = MeshEngine(holder, mesh)
    call = pql.parse("Intersect(Row(f=10), Row(f=11))").calls[0]
    eng.count("i", call, list(range(4)))
    assert eng.sparse_dispatches == 0
    assert eng.device_bytes_skipped == 0


def test_sparse_plan_leaves_bsi_to_dense(holder, mesh):
    from pilosa_tpu.core.field import FieldOptions

    idx = holder.create_index("i")
    f = idx.create_field("f")
    idx.create_field("v", FieldOptions(type="int", min=0, max=100))
    f.import_bulk([10] * 4, list(range(4)))
    idx.field("v").set_value(0, 7)
    eng = MeshEngine(holder, mesh)
    call = pql.parse("Range(v > 3)").calls[0]
    n = eng.count("i", call, [0])
    assert n == 1
    assert eng.sparse_dispatches == 0  # BSI trees take the dense path


# -- result memo ------------------------------------------------------------


def test_result_memo_hit_and_invalidation_on_write(holder, mesh):
    build_clustered(holder, {10: (0, 1), 11: (1, 2)})
    eng = MeshEngine(holder, mesh)
    shards = list(range(N_SHARDS))
    call = pql.parse("Intersect(Row(f=10), Row(f=11))").calls[0]
    base = eng.count("i", call, shards)
    fd = eng.fused_dispatches
    hits0 = eng.result_memo.hits
    assert eng.count("i", call, shards) == base
    assert eng.fused_dispatches == fd, "repeat dispatched despite memo"
    assert eng.result_memo.hits == hits0 + 1
    # Different shard subset: its own key, real dispatch.
    sub = eng.count("i", call, [0, 1])
    assert eng.fused_dispatches == fd + 1
    assert eng.count("i", call, [0, 1]) == sub
    assert eng.fused_dispatches == fd + 1
    # A write must invalidate: serve the NEW result (a stale hit here is
    # a correctness bug, not a perf bug).  The write's delta is captured
    # on the bus (core/delta.py), so the entry is REPAIRED to the new
    # tokens in O(changed bits) — correct value, no recompute dispatch.
    col = 3 * SHARD_WIDTH + 123  # a col in neither row's bits
    holder.fragment("i", "f", "standard", 3).set_bit(10, col)
    holder.fragment("i", "f", "standard", 3).set_bit(11, col)
    got = eng.count("i", call, shards)
    assert got == base + 1, "stale memo hit after a write"
    assert eng.fused_dispatches == fd + 1, "repaired count re-dispatched"
    assert eng.repairs.repaired["count"] >= 1
    # With the repair layer suspended the same miss takes the full
    # recompute path — the pre-repair contract still holds underneath.
    holder.fragment("i", "f", "standard", 3).set_bit(10, col + 1)
    with eng.repairs.suspended():
        got2 = eng.count("i", call, shards)
    assert got2 == base + 1
    assert eng.fused_dispatches == fd + 2


def test_result_memo_through_batcher(holder, mesh):
    build_clustered(holder, {10: (0,), 11: (0,)})
    eng = MeshEngine(holder, mesh)
    shards = list(range(N_SHARDS))
    call = pql.parse("Intersect(Row(f=10), Row(f=11))").calls[0]
    base = eng.batched_count("i", call, shards)
    fd = eng.fused_dispatches
    assert eng.batched_count("i", call, shards) == base
    assert eng.fused_dispatches == fd  # served by the memo probe
    it = eng.batched_count_async("i", call, shards)
    assert it.done() and it.result == base  # resolved future, no queue
    assert eng.fused_dispatches == fd


def test_result_memo_disabled(holder, mesh):
    build_clustered(holder, {10: (0,)})
    eng = MeshEngine(holder, mesh)
    eng.result_memo = _ResultMemo(0)
    call = pql.parse("Row(f=10)").calls[0]
    shards = list(range(N_SHARDS))
    a = eng.count("i", call, shards)
    fd = eng.fused_dispatches
    assert eng.count("i", call, shards) == a
    assert eng.fused_dispatches == fd + 1  # every repeat dispatches


# -- batch CSE ---------------------------------------------------------------


def test_batch_cse_one_eval_per_duplicate(holder, mesh):
    build_clustered(holder, {10: (0, 1), 11: (1, 2), 12: (4,)})
    eng = MeshEngine(holder, mesh)
    shards = list(range(N_SHARDS))
    qa = pql.parse("Intersect(Row(f=10), Row(f=11))").calls[0]
    qb = pql.parse("Row(f=12)").calls[0]
    # Unfused ground truth.
    dense = MeshEngine(holder, mesh)
    dense.sparse_enabled = False
    want_a = dense.count("i", qa, shards)
    want_b = dense.count("i", qb, shards)
    calls = [qa, qb, qa, qa, qb, qa]
    fd = eng.fused_dispatches
    deduped0 = eng.batch_cse_deduped
    res = eng.count_many("i", calls, [shards] * len(calls))
    assert eng.fused_dispatches == fd + 1  # ONE fused dispatch
    assert eng.batch_cse_deduped == deduped0 + 4  # 6 entries, 2 unique
    assert res == [want_a, want_b, want_a, want_a, want_b, want_a]
    # Same queries, different shard subsets: NOT deduped together.
    res2 = eng.count_many("i", [qa, qa], [shards, [0]])
    assert eng.batch_cse_deduped == deduped0 + 4
    assert res2[0] == want_a and res2[1] == dense.count("i", qa, [0])


def test_single_unique_batch_takes_sparse_path(holder, mesh):
    """A drain that CSE's to one unique query (the lone-query HTTP
    pipeline, repeated-dashboard drains) routes through the scalar
    count path where block skipping applies; every caller slot still
    gets the answer."""
    build_clustered(holder, {10: (0, 1), 11: (1,)})
    eng = MeshEngine(holder, mesh)
    eng.result_memo.maxsize = 0
    shards = list(range(N_SHARDS))
    call = pql.parse("Intersect(Row(f=10), Row(f=11))").calls[0]
    dense = MeshEngine(holder, mesh)
    dense.sparse_enabled = False
    want = dense.count("i", call, shards)
    sd0 = eng.sparse_dispatches
    res = eng.count_many("i", [call] * 5, [shards] * 5)
    assert res == [want] * 5
    assert eng.sparse_dispatches == sd0 + 1
    # Mixed drains (2+ uniques) stay on the fixed-tier batch program.
    other = pql.parse("Row(f=10)").calls[0]
    sd1 = eng.sparse_dispatches
    res2 = eng.count_many("i", [call, other], [shards] * 2)
    assert eng.sparse_dispatches == sd1
    assert res2 == [want, dense.count("i", other, shards)]


# -- lifecycle / counters ----------------------------------------------------


def test_engine_close_releases_caches(holder, mesh):
    build_clustered(holder, {10: (0,), 11: (0,)})
    eng = MeshEngine(holder, mesh)
    shards = list(range(N_SHARDS))
    eng.count("i", pql.parse("Intersect(Row(f=10), Row(f=11))").calls[0], shards)
    eng.batched_count("i", pql.parse("Row(f=10)").calls[0], shards)
    assert eng._stacks and eng._masks and eng._scalars
    assert len(eng.result_memo) > 0
    batcher = eng._batcher
    eng.close()
    assert not eng._stacks and not eng._masks and not eng._scalars
    assert not eng._zeros and not eng._canonical and not eng._topn_cands
    assert len(eng.result_memo) == 0
    assert eng._resident_bytes == 0 and not eng._pending_free
    assert eng._batcher is None
    if batcher is not None:
        assert batcher._stopped
    snap = eng.cache_snapshot()
    assert snap["closed"] and snap["stacks"] == 0
    # Idempotent.
    eng.close()


def test_cache_hit_miss_counters_and_metrics_series(holder, mesh):
    from pilosa_tpu.util.stats import REGISTRY

    build_clustered(holder, {10: (0,)})
    eng = MeshEngine(holder, mesh)
    shards = list(range(N_SHARDS))
    call = pql.parse("Row(f=10)").calls[0]
    eng.result_memo.maxsize = 0  # count real dispatches
    eng.count("i", call, shards)
    mask_hits0 = eng.cache_stats["mask"][0]
    stack_hits0 = eng.cache_stats["stack"][0]
    eng.count("i", call, shards)
    assert eng.cache_stats["mask"][0] > mask_hits0
    assert eng.cache_stats["stack"][0] > stack_hits0
    assert eng.cache_stats["mask"][1] >= 1  # first build was a miss
    text = REGISTRY.prometheus_text()
    for series in (
        'pilosa_engine_cache_hits_total{cache="mask"}',
        'pilosa_engine_cache_misses_total{cache="mask"}',
        'pilosa_engine_cache_hits_total{cache="result_memo"}',
        'pilosa_engine_cache_hits_total{cache="batch_cse"}',
        "pilosa_device_bytes_skipped_total",
    ):
        assert series in text, series
    snap = eng.cache_snapshot()
    assert snap["caches"]["mask"]["hits"] == eng.cache_stats["mask"][0]


def test_debug_vars_carries_engine_caches(holder, mesh):
    import json
    import urllib.request

    from pilosa_tpu.api import API
    from pilosa_tpu.net import serve

    build_clustered(holder, {10: (0,)})
    eng = MeshEngine(holder, mesh)
    api = API(holder=holder, mesh_engine=eng)
    srv, _ = serve(api, port=0)
    try:
        port = srv.server_address[1]
        req = urllib.request.Request(
            f"http://localhost:{port}/index/i/query",
            data=b"Count(Intersect(Row(f=10), Row(f=10)))",
            method="POST",
        )
        urllib.request.urlopen(req, timeout=60).read()
        doc = json.loads(
            urllib.request.urlopen(
                f"http://localhost:{port}/debug/vars", timeout=30
            ).read()
        )
        assert "engineCaches" in doc
        assert "caches" in doc["engineCaches"]
        assert "deviceBytesSkipped" in doc["engineCaches"]
    finally:
        srv.shutdown()


# -- Pallas kernel (interpret mode) -----------------------------------------


def test_pallas_block_kernel_interpret_matches_numpy(mesh):
    import jax.numpy as jnp

    from pilosa_tpu.parallel import sparse

    rng = np.random.default_rng(0)
    R, S = 4, 2 * sparse.SHARD_GROUP * mesh.devices.size
    mat = np.zeros((R, S, WORDS), dtype=np.uint32)
    bidx = np.zeros((S, 4), np.int32)
    bn = np.zeros(S, np.int32)
    for s in range(S):
        # Per-shard block lists differ, so a wrong sublane pick shows.
        blocks = np.sort(rng.choice(64, size=s % 5, replace=False))
        bidx[s, : len(blocks)] = blocks
        bn[s] = len(blocks)
        for r in (0, 1, 2):
            for b in blocks:
                mat[r, s, b * OCC_BLOCK_WORDS:(b + 1) * OCC_BLOCK_WORDS] = (
                    rng.integers(0, 1 << 32, OCC_BLOCK_WORDS, dtype=np.uint32)
                )
    prog = ("andnot", ("and", ("row", 0, 0), ("row", 0, 1)),
            ("xor", ("row", 0, 2), ("zero",)))
    rv = jnp.asarray(np.array([0, 1, 2], np.int32))
    mask = np.full((S, 1), 0xFFFFFFFF, np.uint32)
    mask[3] = 0  # a gated shard must add nothing
    live = mask[:, 0] != 0
    want = int(np.sum(np.bitwise_count((mat[0] & mat[1] & ~mat[2])[live])))
    args = (jnp.asarray(mask), jnp.asarray(bidx), jnp.asarray(bn), rv,
            jnp.asarray(mat))
    got = sparse.count_tree_blocks_pallas(mesh, prog, True, *args)
    assert int(got) == want
    assert int(sparse.count_tree_blocks(mesh, prog, *args)) == want


"""Mesh/shard_map parallel path tests on the 8-device virtual CPU mesh."""

import numpy as np
import pytest

from pilosa_tpu import pql
from pilosa_tpu.core.field import FieldOptions
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.executor import Executor
from pilosa_tpu.executor.executor import ValCount
from pilosa_tpu.ops import SHARD_WIDTH
from pilosa_tpu.parallel import MeshEngine, make_mesh, pad_shards


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(8)


@pytest.fixture
def holder():
    h = Holder()
    h.open()
    return h


def build_data(holder, n_shards=8):
    idx = holder.create_index("i")
    f = idx.create_field("f")
    v = idx.create_field("v", FieldOptions(type="int", min=0, max=1000))
    ef = idx.existence_field()
    rows, cols, vals_c, vals_v = [], [], [], []
    rng = np.random.default_rng(7)
    for s in range(n_shards):
        base = s * SHARD_WIDTH
        picks = rng.choice(SHARD_WIDTH, size=500, replace=False)
        for c in picks[:300]:
            rows.append(10)
            cols.append(base + int(c))
        for c in picks[200:]:
            rows.append(11)
            cols.append(base + int(c))
        for c in picks[:50]:
            vals_c.append(base + int(c))
            vals_v.append(int(rng.integers(0, 1000)))
    f.import_bulk(rows, cols)
    ef.import_bulk([0] * len(cols), cols)
    v.import_values(vals_c, vals_v)
    return idx


def test_mesh_count_matches_executor(holder, mesh):
    build_data(holder)
    ex = Executor(holder)
    eng = MeshEngine(holder, mesh)
    shards = list(range(8))
    for q in [
        "Row(f=10)",
        "Intersect(Row(f=10), Row(f=11))",
        "Union(Row(f=10), Row(f=11))",
        "Difference(Row(f=10), Row(f=11))",
        "Xor(Row(f=10), Row(f=11))",
        "Not(Row(f=10))",
    ]:
        call = pql.parse(q).calls[0]
        want = ex.execute("i", f"Count({q})").results[0]
        got = eng.count("i", call, shards)
        assert got == want, q


def test_mesh_range_count(holder, mesh):
    build_data(holder)
    ex = Executor(holder)
    eng = MeshEngine(holder, mesh)
    shards = list(range(8))
    for q in [
        "Range(v > 500)",
        "Range(v <= 300)",
        "Range(v == 7)",
        "Range(v != null)",
        "Range(100 < v < 900)",
    ]:
        call = pql.parse(q).calls[0]
        want = ex.execute("i", f"Count({q})").results[0]
        got = eng.count("i", call, shards)
        assert got == want, q


def test_mesh_bitmap_row_matches(holder, mesh):
    build_data(holder)
    ex = Executor(holder)
    eng = MeshEngine(holder, mesh)
    call = pql.parse("Intersect(Row(f=10), Row(f=11))").calls[0]
    want = ex.execute("i", "Intersect(Row(f=10), Row(f=11))").results[0]
    got = eng.bitmap_row("i", call, list(range(8)))
    assert got.columns().tolist() == want.columns().tolist()


def test_mesh_sum(holder, mesh):
    build_data(holder)
    ex = Executor(holder)
    eng = MeshEngine(holder, mesh)
    want = ex.execute("i", "Sum(field=v)").results[0]
    total, n = eng.sum("i", "v", None, list(range(8)))
    assert (total, n) == (want.val, want.count)
    # Filtered.
    filt = pql.parse("Row(f=10)").calls[0]
    want = ex.execute("i", "Sum(Row(f=10), field=v)").results[0]
    total, n = eng.sum("i", "v", filt, list(range(8)))
    assert (total, n) == (want.val, want.count)


def test_mesh_cache_invalidation(holder, mesh):
    build_data(holder)
    eng = MeshEngine(holder, mesh)
    ex = Executor(holder)
    call = pql.parse("Row(f=10)").calls[0]
    before = eng.count("i", call, list(range(8)))
    ex.execute("i", f"Set({3*SHARD_WIDTH + 99}, f=10)")
    after = eng.count("i", call, list(range(8)))
    assert after == before + 1


def test_pad_shards(mesh):
    assert pad_shards(1, mesh) == 8
    assert pad_shards(8, mesh) == 8
    assert pad_shards(9, mesh) == 16


def test_pad_shards_edges(mesh):
    """Zero shards still pads to one full mesh round; a mesh of one pads
    to the identity."""
    assert pad_shards(0, mesh) == 8
    m1 = make_mesh(1)
    for n in (0, 1, 2, 7):
        assert pad_shards(n, m1) == max(n, 1)


def test_shard_owner_differential(mesh):
    """shard_owner vs a direct python owner map (contiguous blocks of
    padded/n_dev per device), for shard counts NOT divisible by the mesh
    size and for a mesh of 1."""
    from pilosa_tpu.parallel.mesh import shard_owner

    for m in (mesh, make_mesh(1)):
        n_dev = int(m.devices.size)
        for n_shards in (1, 2, 7, 8, 9, 13, 16, 100):
            padded = pad_shards(n_shards, m)
            per_dev = padded // n_dev
            want = {p: p // per_dev for p in range(padded)}
            got = {p: shard_owner(p, padded, m) for p in range(padded)}
            assert got == want, (n_dev, n_shards)
            assert set(got.values()) <= set(range(n_dev))


def test_shard_owner_rejects_bad_padding(mesh):
    from pilosa_tpu.parallel.mesh import shard_owner

    with pytest.raises(ValueError):
        shard_owner(0, 0, mesh)  # would divide by zero
    with pytest.raises(ValueError):
        shard_owner(0, 9, mesh)  # not a multiple of the mesh size


def test_stack_sharded_edges(mesh):
    """Non-divisible shard counts zero-pad; a mesh of 1 round-trips; an
    empty shard list is a loud ValueError, not an IndexError."""
    from pilosa_tpu.parallel.mesh import stack_sharded

    arrays = [np.full(4, i + 1, dtype=np.uint32) for i in range(3)]
    out = np.asarray(stack_sharded(arrays, mesh))
    assert out.shape == (8, 4)
    for i in range(3):
        assert (out[i] == i + 1).all()
    assert (out[3:] == 0).all()  # padding shards are zero

    m1 = make_mesh(1)
    out1 = np.asarray(stack_sharded(arrays, m1))
    assert out1.shape == (3, 4)
    assert (out1 == np.stack(arrays)).all()

    with pytest.raises(ValueError, match="empty shard list"):
        stack_sharded([], mesh)


def test_mesh_uneven_shards(holder, mesh):
    """Shard count not a multiple of mesh size: padding shards are zero."""
    idx = holder.create_index("i")
    f = idx.create_field("f")
    cols = [0, SHARD_WIDTH + 1, 2 * SHARD_WIDTH + 2]
    f.import_bulk([5, 5, 5], cols)
    eng = MeshEngine(holder, mesh)
    call = pql.parse("Row(f=5)").calls[0]
    assert eng.count("i", call, [0, 1, 2]) == 3


def test_residency_eviction(holder, mesh):
    """The HBM residency manager evicts cold stacks under budget pressure."""
    idx = holder.create_index("i")
    for name in ("a", "b", "c"):
        f = idx.create_field(name)
        f.import_bulk([1], [0])
    from pilosa_tpu.parallel.engine import MeshEngine

    stack_bytes = 8 * 1 * 32768 * 4  # S=8(padded), R=1 rows, WORDS, u32
    # Budget for exactly two stacks; the occupancy summaries (8 B per
    # row-shard) count against the cap too since the tiered-residency
    # accounting fix, so give them headroom.
    budget = 2 * stack_bytes + 4096
    eng = MeshEngine(holder, mesh, max_resident_bytes=budget)
    eng.field_stack("i", "a", "standard")
    eng.field_stack("i", "b", "standard")
    assert len(eng._stacks) == 2
    eng.field_stack("i", "c", "standard")  # evicts "a" (LRU)
    assert len(eng._stacks) == 2
    keys = [k[1] for k in eng._stacks]
    assert keys == ["b", "c"]
    assert eng._resident_bytes <= budget
    # Evicted stacks rebuild transparently.
    call = pql.parse("Row(a=1)").calls[0]
    assert eng.count("i", call, [0]) == 1


def test_executor_with_mesh_engine(holder, mesh):
    """Executor fast paths (Count/Sum) through the fused engine give the
    same answers as the per-shard path."""
    build_data(holder)
    plain = Executor(holder)
    fused = Executor(holder, mesh_engine=MeshEngine(holder, mesh))
    for q in [
        "Count(Intersect(Row(f=10), Row(f=11)))",
        "Count(Not(Row(f=10)))",
        "Count(Range(v > 500))",
        "Sum(field=v)",
        "Sum(Row(f=10), field=v)",
    ]:
        assert fused.execute("i", q).results == plain.execute("i", q).results, q


def test_executor_mesh_topn(holder, mesh):
    """Batched TopN phase-1 matches the per-shard path AND is actually
    taken (no silent fallback)."""
    build_data(holder)
    plain = Executor(holder)
    engine = MeshEngine(holder, mesh)
    calls = []
    # The dispatching forms: the sync wrappers and the batch lane's
    # direct path (solo_op_async) both go through them.
    for name in ("topn_scores_async", "topn_full_async", "topn_cache_only"):
        orig = getattr(engine, name)
        setattr(
            engine,
            name,
            (lambda o: lambda *a, **k: calls.append(1) or o(*a, **k))(orig),
        )
    fused = Executor(holder, mesh_engine=engine)
    # Candidate including a row id absent from the data (99).
    for q in [
        "TopN(f, Row(f=11), n=3)",
        "TopN(f, Row(f=11))",
        "TopN(f, Row(f=11), ids=[10, 11, 99])",
        "TopN(f, Row(f=11), threshold=100)",
        "TopN(f, Row(f=11), tanimotoThreshold=30)",
    ]:
        calls.clear()
        assert fused.execute("i", q).results == plain.execute("i", q).results, q
        assert calls, f"mesh path not used for {q}"


def test_executor_mesh_group_by(holder, mesh):
    """Fused GroupBy matches the iterator path (and is actually taken)."""
    idx = holder.create_index("i")
    a = idx.create_field("a")
    b = idx.create_field("b")
    rng = np.random.default_rng(5)
    rows, cols = [], []
    for s in range(4):
        base = s * SHARD_WIDTH
        for r in range(5):
            for c in rng.choice(1000, size=60, replace=False):
                rows.append(r)
                cols.append(base + int(c))
    a.import_bulk(rows, cols)
    b.import_bulk([r % 3 for r in rows], cols)

    cfield = idx.create_field("c")
    cfield.import_bulk([r % 2 for r in rows], cols)
    dfield = idx.create_field("d")
    dfield.import_bulk([(r + 1) % 2 for r in rows], cols)

    engine = MeshEngine(holder, mesh)
    calls = []
    orig = engine.group_counts_async  # under group_counts and the batch lane
    engine.group_counts_async = (
        lambda *x, **k: calls.append(1) or orig(*x, **k))
    plain = Executor(holder)
    fused = Executor(holder, mesh_engine=engine)
    for q in [
        "GroupBy(Rows(field=a))",
        "GroupBy(Rows(field=a), Rows(field=b))",
        "GroupBy(Rows(field=a), Rows(field=b), limit=4)",
        "GroupBy(Rows(field=a), Rows(field=b), filter=Row(a=1))",
        "GroupBy(Rows(field=a), limit=2, offset=1)",
        # 3- and 4-field combinations: the flattened-combination-axis
        # kernel (round-4 VERDICT #4); row-major emit order must match
        # the host iterator exactly, including limit truncation.
        "GroupBy(Rows(field=a), Rows(field=b), Rows(field=c))",
        "GroupBy(Rows(field=a), Rows(field=b), Rows(field=c), Rows(field=d))",
        "GroupBy(Rows(field=a), Rows(field=b), Rows(field=c), limit=7)",
        "GroupBy(Rows(field=a), Rows(field=b), Rows(field=c), filter=Row(a=1))",
    ]:
        calls.clear()
        assert fused.execute("i", q).results == plain.execute("i", q).results, q
        assert calls, f"mesh path not used for {q}"
    # previous args are the device path's too since PR 36: the
    # iterator's seek is a cut of the row-major listing.
    q = "GroupBy(Rows(field=a, previous=1), Rows(field=b, previous=0))"
    calls.clear()
    assert fused.execute("i", q).results == plain.execute("i", q).results
    assert calls
    # A combination count past the old trace-time cap (30 > the 8 this
    # test used to set) answers on the device with the iterator's
    # result: nothing in the program grows with it.  The earlier run of
    # this exact query memoized its tensor — clear the memo (and keep
    # repair out) so group_counts is really consulted.
    assert not hasattr(engine, "MAX_GROUP_COMBOS")
    engine.result_memo.clear()
    q = "GroupBy(Rows(field=a), Rows(field=b), Rows(field=c))"  # 5*3*2=30
    devs = []
    engine.group_counts_async = (
        lambda *x, **k: devs.append(orig(*x, **k)) or devs[-1])
    with engine.repairs.suspended():
        assert fused.execute("i", q).results == plain.execute("i", q).results
    assert len(devs) == 1 and devs[0][0].shape == (32,) and devs[0][1] == (5, 3, 2)  # the device array: the tensor, then two step counts


def test_mesh_time_range(holder, mesh):
    """Time-quantum Range fuses into the mesh dispatch."""
    idx = holder.create_index("i")
    f = idx.create_field("t", FieldOptions(type="time", time_quantum="YMD"))
    ex = Executor(holder)
    ex.execute(
        "i",
        f"""
        Set(1, t=10, 2018-01-05T00:00)
        Set({SHARD_WIDTH+2}, t=10, 2018-02-10T00:00)
        Set(3, t=10, 2019-06-01T00:00)
        """,
    )
    eng = MeshEngine(holder, mesh)
    fused = Executor(holder, mesh_engine=eng)
    for q in [
        "Count(Range(t=10, 2018-01-01T00:00, 2018-12-31T00:00))",
        "Count(Range(t=10, 2017-01-01T00:00, 2020-01-01T00:00))",
        "Count(Range(t=10, 2019-01-01T00:00, 2019-12-31T00:00))",
        "Count(Union(Range(t=10, 2018-01-01T00:00, 2018-03-01T00:00), Row(t=10)))",
    ]:
        assert fused.execute("i", q).results == ex.execute("i", q).results, q


def test_executor_mesh_min_max(holder, mesh):
    build_data(holder)
    plain = Executor(holder)
    fused = Executor(holder, mesh_engine=MeshEngine(holder, mesh))
    for q in [
        "Min(field=v)",
        "Max(field=v)",
        "Min(Row(f=10), field=v)",
        "Max(Row(f=10), field=v)",
    ]:
        assert fused.execute("i", q).results == plain.execute("i", q).results, q


def test_executor_mesh_min_max_deep_bsi(holder, mesh):
    """bit_depth > 31 exercises the (hi, lo) split of the variadic
    argmin/argmax reduce: values straddling the 31-bit boundary, ties
    on both sides, and a filter that empties the considered set."""
    idx = holder.create_index("i")
    v = idx.create_field(
        "big", FieldOptions(type="int", min=0, max=(1 << 40))
    )
    f = idx.create_field("f")
    vals = {
        1: (1 << 39) + 7,
        2: 5,
        3: (1 << 39) + 7,  # tie with col 1 (hi side)
        4: 5,               # tie with col 2 (lo side)
        5: (1 << 35) + 123,
        SHARD_WIDTH + 1: 5,  # cross-shard tie with cols 2/4 at the min
        2 * SHARD_WIDTH + 9: (1 << 40) - 1,
    }
    v.import_values(list(vals), [vals[c] for c in vals])
    f.import_bulk([10] * 3, [1, 3, 5])
    plain = Executor(holder)
    fused = Executor(holder, mesh_engine=MeshEngine(holder, mesh))
    for q in [
        "Min(field=big)",
        "Max(field=big)",
        "Min(Row(f=10), field=big)",
        "Max(Row(f=10), field=big)",
        "Min(Row(f=99), field=big)",  # empty filter: count 0
    ]:
        got = fused.execute("i", q).results
        want = plain.execute("i", q).results
        assert got == want, (q, got, want)
    # Reference parity on cross-shard ties: ValCount.smaller keeps the
    # FIRST shard's count (executor.go:2676 — other only wins on
    # strictly-smaller val), so the shard-1 tie column is not added:
    # count is shard 0's 2, not 3.
    assert fused.execute("i", "Min(field=big)").results[0] == ValCount(5, 2)
    assert (
        fused.execute("i", "Max(field=big)").results[0].val
        == (1 << 40) - 1
    )
    # hi-side tie: cols 1 and 3 share (1<<39)+7, the max among Row(f=10).
    vc = fused.execute("i", "Max(Row(f=10), field=big)").results[0]
    assert (vc.val, vc.count) == ((1 << 39) + 7, 2)


def test_fused_topn_many_candidates_chunking(holder, mesh):
    """> VARIADIC_CHUNK candidate rows: the variadic scoring reduce
    chunks (kernels.VARIADIC_CHUNK) and results stay exact."""
    from pilosa_tpu.parallel import kernels as k_mod

    idx = holder.create_index("i")
    f = idx.create_field("f")
    src = idx.create_field("s")
    n_rows = k_mod.VARIADIC_CHUNK + 9
    rows, cols = [], []
    rng = np.random.default_rng(3)
    for r in range(n_rows):
        for c in rng.choice(2 * SHARD_WIDTH, size=5 + (r % 7), replace=False):
            rows.append(r)
            cols.append(int(c))
    f.import_bulk(rows, cols)
    src.import_bulk([0] * (SHARD_WIDTH // 256), list(range(0, SHARD_WIDTH, 256)))
    plain = Executor(holder)
    fused = Executor(holder, mesh_engine=MeshEngine(holder, mesh))
    for q in [f"TopN(f, n={n_rows})", "TopN(f, Row(s=0), n=20)"]:
        got = fused.execute("i", q).results
        want = plain.execute("i", q).results
        assert got == want, (q, got, want)


def test_fused_topn_ties_thresholds(holder, mesh):
    """Fused full-TopN semantics: cross-shard tie ordering (-count, -id),
    threshold gating, n=0 (no trim), and ids= (never truncate) all match
    the per-shard two-phase path bit for bit."""
    idx = holder.create_index("i")
    f = idx.create_field("f")
    src = idx.create_field("s")
    rows, cols, srows, scols = [], [], [], []
    # Rows 1..6 engineered so several aggregate counts tie exactly:
    # per-shard counts differ but totals collide (rows 2/5 and 3/4).
    per_shard = {
        1: [30, 0, 10],  # total 40
        2: [10, 10, 10],  # total 30 (ties row 5)
        3: [20, 0, 0],  # total 20 (ties row 4)
        4: [0, 0, 20],  # total 20
        5: [0, 30, 0],  # total 30
        6: [1, 1, 0],  # total 2 (thresholded out at >=3)
    }
    for s in range(3):
        base = s * SHARD_WIDTH
        for r, picks in per_shard.items():
            for c in range(picks[s]):
                rows.append(r)
                cols.append(base + c)
        for c in range(200):
            srows.append(0)
            scols.append(base + c)
    f.import_bulk(rows, cols)
    src.import_bulk(srows, scols)
    for field in (f, src):
        for v in field.views.values():
            for frag in v.fragments.values():
                frag.cache.recalculate()

    plain = Executor(holder)
    fused = Executor(holder, mesh_engine=MeshEngine(holder, mesh))
    for q in [
        "TopN(f, Row(s=0), n=3)",
        "TopN(f, Row(s=0), n=4)",  # trim lands inside the 20/20 tie
        "TopN(f, Row(s=0))",  # n=0: all positive candidates
        "TopN(f, Row(s=0), threshold=3)",
        "TopN(f, Row(s=0), threshold=25)",
        "TopN(f, Row(s=0), ids=[2, 3, 5, 99])",
        "TopN(f, n=2)",  # no src: cache-only path
        "TopN(f)",
        "TopN(f, threshold=21)",
        "TopN(f, ids=[1, 4, 99])",
    ]:
        got = fused.execute("i", q).results
        want = plain.execute("i", q).results
        assert got == want, (q, got, want)
    # Tie order inside a trimmed result is (count desc, id desc).
    top4 = fused.execute("i", "TopN(f, Row(s=0), n=4)").results[0]
    assert top4 == [(1, 40), (5, 30), (2, 30), (4, 20)]


def test_incremental_stack_sync(holder, mesh):
    """Write deltas of any size scatter into the resident HBM stack
    instead of re-uploading the whole view (SURVEY "mutability on an
    accelerator": op-log batching -> device scatter).  Rebuilds happen
    only for shape changes (new rows)."""
    build_data(holder)
    eng = MeshEngine(holder, mesh)
    # Repair-on-write would serve every re-count below WITHOUT a
    # dispatch (test_repair.py owns that contract); this test pins the
    # scatter-sync machinery, so it must observe real dispatches.
    eng.repairs._suspended = 1
    ex = Executor(holder)
    call = pql.parse("Row(f=10)").calls[0]
    shards = list(range(8))
    base = eng.count("i", call, shards)
    assert (eng.stack_rebuilds, eng.stack_updates) == (1, 0)

    # Point writes across several shards (set two, clear one of them
    # back): ONE incremental sync, no rebuild.
    ex.execute("i", f"Set({3 * SHARD_WIDTH + 99}, f=10)")
    ex.execute("i", f"Set({5 * SHARD_WIDTH + 98}, f=10)")
    ex.execute("i", f"Clear({5 * SHARD_WIDTH + 98}, f=10)")
    assert eng.count("i", call, shards) == base + 1
    assert (eng.stack_rebuilds, eng.stack_updates) == (1, 1)

    # Repeated write/read cycles keep using the scatter path.
    for k in range(3):
        ex.execute("i", f"Set({k}, f=11)")
        eng.count("i", call, shards)
    assert eng.stack_rebuilds == 1 and eng.stack_updates == 4

    # A brand-new row id changes the stack shape: full rebuild.
    ex.execute("i", "Set(7, f=999)")
    got = eng.count("i", pql.parse("Row(f=999)").calls[0], shards)
    assert got == 1
    assert eng.stack_rebuilds == 2

    # A long burst of single-bit writes to one row (round 3's 512-entry
    # deque overflowed here and forced a rebuild): the per-row mutation
    # log covers any number of writes — incremental sync, no rebuild.
    frag = holder.fragment("i", "f", "standard", 0)
    for i in range(600):
        frag.set_bit(10, (i * 17) % SHARD_WIDTH)
    want_after = eng.count("i", call, shards)
    oracle = sum(
        holder.fragment("i", "f", "standard", s).row_count(10)
        for s in range(8)
        if holder.fragment("i", "f", "standard", s) is not None
    )
    assert want_after == oracle
    assert eng.stack_rebuilds == 2  # still only the new-row rebuild
    assert eng.stack_updates == 5


def test_failed_incremental_sync_evicts_stack(holder, mesh, monkeypatch):
    """A scatter chunk that raises mid-sync leaves cached.matrix
    donated/invalidated; the stack must be EVICTED so the next query
    rebuilds cleanly instead of crashing forever (r4 ADVICE)."""
    from pilosa_tpu.parallel import engine as engine_mod

    build_data(holder)
    eng = MeshEngine(holder, mesh)
    eng.repairs._suspended = 1  # the count must DISPATCH (sync path)
    ex = Executor(holder)
    call = pql.parse("Row(f=10)").calls[0]
    shards = list(range(8))
    base = eng.count("i", call, shards)
    assert eng.stack_rebuilds == 1

    # Dirty one row, then fail the sync AFTER the scatter has really
    # donated cached.matrix: the wrapper calls through (the donation
    # consumes the stack's buffer) and raises before the result is
    # stored back — exactly the mid-chain failure the eviction guards.
    ex.execute("i", "Set(123456, f=10)")
    real_words = engine_mod._scatter_words_donated
    real_rows = engine_mod._scatter_rows_donated

    def boom_words(*a, **kw):
        real_words(*a, **kw)
        raise RuntimeError("transient device OOM")

    def boom_rows(*a, **kw):
        real_rows(*a, **kw)
        raise RuntimeError("transient device OOM")

    monkeypatch.setattr(engine_mod, "_scatter_words_donated", boom_words)
    monkeypatch.setattr(engine_mod, "_scatter_rows_donated", boom_rows)
    with pytest.raises(RuntimeError, match="transient device OOM"):
        eng.count("i", call, shards)

    # Stack was evicted: the next query (scatters restored) rebuilds
    # and answers correctly.
    monkeypatch.undo()
    assert eng.count("i", call, shards) == base + 1
    assert eng.stack_rebuilds == 2


def test_word_level_sync_payload(holder, mesh):
    """Point writes sync as WORD deltas (a few bytes), not whole
    128 KiB rows; whole-row events (dense load, word-log overflow) fall
    back to row payloads — and both produce correct counts."""
    from pilosa_tpu.core.fragment import Fragment
    from pilosa_tpu.ops import bitops

    frag = Fragment("i", "f", "standard", 0)
    frag.set_bit(0, 5)
    v0 = frag._version
    # Two point writes in the same device word + one in another word.
    frag.set_bit(0, 6)
    frag.set_bit(0, 40)
    ver, dirty = frag.sync_snapshot(v0)
    kind, widxs, vals, occ = dirty[0]
    assert kind == "words"
    assert widxs.tolist() == [0, 1]  # cols 6 and 40 -> words 0 and 1
    assert vals.dtype == np.uint32 and len(vals) == 2
    assert vals[0] == frag.row_words(0)[0]
    assert occ == frag.row_occupancy(0) == 1  # all bits in block 0
    # A dense row load is a whole-row event.
    frag.load_row_words(1, np.ones(bitops.WORDS64, dtype=np.uint64))
    _, dirty = frag.sync_snapshot(ver)
    assert dirty[1][0] == "row"
    # Word-log overflow on one row falls back to a row payload.
    v1 = frag._version
    for c in range(0, (frag.WORD_LOG_MAX + 10) * 32, 32):
        frag.set_bit(2, c % SHARD_WIDTH)
    _, dirty = frag.sync_snapshot(v1)
    assert dirty[2][0] == "row"

    # End-to-end: engine counts stay correct through the word path.
    build_data(holder)
    eng = MeshEngine(holder, mesh)
    eng.repairs._suspended = 1  # pin the word-scatter path, not repair
    ex = Executor(holder)
    call = pql.parse("Row(f=10)").calls[0]
    shards = list(range(8))
    base = eng.count("i", call, shards)
    ex.execute("i", f"Set({2 * SHARD_WIDTH + 500}, f=10)")
    ex.execute("i", f"Set({6 * SHARD_WIDTH + 501}, f=10)")
    assert eng.count("i", call, shards) == base + 2
    assert eng.stack_updates == 1 and eng.stack_rebuilds == 1


def test_bulk_import_write_through(holder, mesh):
    """A bulk import dirtying MANY rows across every shard (well past
    the old 256-row scatter cap) write-throughs to the resident stack
    with chunked scatters — zero full rebuilds (round-4 VERDICT #8)."""
    build_data(holder)
    idx = holder.index("i")
    big = idx.create_field("big")
    n_rows, n_shards = 80, 8
    rng = np.random.default_rng(3)
    rows, cols = [], []
    for s in range(n_shards):
        for r in range(n_rows):
            for c in rng.choice(1000, size=5, replace=False):
                rows.append(r)
                cols.append(s * SHARD_WIDTH + int(c))
    big.import_bulk(rows, cols)

    eng = MeshEngine(holder, mesh)
    eng.repairs._suspended = 1  # pin write-through scatters, not repair
    ex = Executor(holder, mesh_engine=eng)
    q = "Count(Union(Row(big=0), Row(big=1)))"
    base = ex.execute("i", q).results[0]
    assert eng.stack_rebuilds == 1

    # Second import touches EVERY (row, shard) pair: 640 dirty rows.
    rows2, cols2 = [], []
    for s in range(n_shards):
        for r in range(n_rows):
            rows2.append(r)
            cols2.append(s * SHARD_WIDTH + 1000 + r)
    big.import_bulk(rows2, cols2)

    got = ex.execute("i", q).results[0]
    assert got == base + 2 * n_shards  # rows 0 and 1 gained one bit/shard
    assert eng.stack_rebuilds == 1, "bulk import forced a rebuild"
    assert eng.stack_updates == 1

    # One more mixed import: the SECOND incremental sync of the same
    # stack (re-entering the chunk loop on an already-donated lineage)
    # must also be rebuild-free and correct.
    rows3 = [0, 3, 79] * n_shards
    cols3 = [
        s * SHARD_WIDTH + 1500 + r
        for s in range(n_shards)
        for r in (0, 3, 79)
    ]
    big.import_bulk(rows3, cols3)
    plain = Executor(holder)
    for r in (0, 3, 79):
        # Union forces the device path (a bare Count(Row) would answer
        # from the O(1) cardinality lane without touching the stack).
        qq = f"Count(Union(Row(big={r}), Row(big=7)))"
        assert ex.execute("i", qq).results == plain.execute("i", qq).results
    assert eng.stack_rebuilds == 1
    assert eng.stack_updates == 2


def test_put_global_pins_row_major_layout(mesh):
    """jax 0.9's device_put otherwise adopts the compiler-preferred
    shard-axis-major layout for [R, S, W] stacks, which makes every
    fused dispatch open with a full-stack relayout copy on TPU (~9 ms
    against 335 us of compute, measured).  Lock the pin."""
    import numpy as np

    from pilosa_tpu.parallel.mesh import SHARD_AXIS, put_global
    from jax.sharding import PartitionSpec as P

    arr = put_global(
        mesh, np.zeros((4, 8, 64), dtype=np.uint32), P(None, SHARD_AXIS)
    )
    fmt = getattr(arr, "format", None)
    if fmt is None or fmt.layout is None:
        pytest.skip("jax without Format introspection")
    assert tuple(fmt.layout.major_to_minor) == (0, 1, 2)


def test_sum_zero_bit_depth(holder, mesh):
    """A BSI group with max == min has bit_depth 0 (no value planes):
    Sum is count * base and must not crash the fused kernel
    (r5 review: jnp.stack of zero planes)."""
    idx = holder.create_index("i")
    v = idx.create_field("k", FieldOptions(type="int", min=7, max=7))
    v.import_values([1, 2, SHARD_WIDTH + 3], [7, 7, 7])
    plain = Executor(holder)
    fused = Executor(holder, mesh_engine=MeshEngine(holder, mesh))
    want = plain.execute("i", "Sum(field=k)").results
    got = fused.execute("i", "Sum(field=k)").results
    assert got == want == [ValCount(21, 3)]

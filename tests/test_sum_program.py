"""The BSI Sum program's two bodies (``kernels.sum_tree``): the Pallas
one a TPU runs (here in interpret mode) and the XLA one every other
backend runs, each against the per-shard reference ``ops/bsi.sum_counts``
over the same planes and the same filter, the filter evaluated shard by
shard with the walks of ``ops/bsi.py``.  The compile of the Pallas body
for a v5e is tests/test_tpu_compile.py's."""

import functools

import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from pilosa_tpu.core.field import FieldOptions
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.executor import Executor
from pilosa_tpu.ops import SHARD_WIDTH
from pilosa_tpu.ops import bsi as bsi_ops
from pilosa_tpu.parallel import MeshEngine, kernels, make_mesh
from pilosa_tpu.parallel.mesh import SHARD_AXIS, put_global
from pilosa_tpu.util.stats import METRIC_ENGINE_SUM_PROGRAMS, REGISTRY

W = 256  # words a shard row: two 128-word lane chunks
Q_DEPTH = 6  # the filter's BSI field: values 0..63, 7 planes

# Operands after the measure: a set field's stack, two row indices into
# it, the filter field's planes, then predicate bits (==, >< lo, >< hi, <).
ROWS, ROW_A, ROW_B, QPL, EQ, LO, HI, LT = range(8)
Q = ("slice", 0, Q_DEPTH + 1)
FILTERS = {
    "ones": ("ones",),
    "row": ("row", ROWS, ROW_A),
    "range_eq": ("range", "eq", QPL, Q, EQ),
    "range_between": ("between", QPL, Q, LO, HI),
    # ssb.flight1_stream's Q1.3: Intersect(Row, Row, Range ==, Range ><)
    "flight1": ("and", ("row", ROWS, ROW_A), ("row", ROWS, ROW_B),
                ("range", "eq", QPL, Q, EQ), ("between", QPL, Q, LO, HI)),
    "union": ("or", ("row", ROWS, ROW_A), ("range", "lt", QPL, Q, LT)),
    "difference": ("andnot", ("range", "gte", QPL, Q, LT), ("row", ROWS, ROW_B)),
}


def _bsi_planes(values, present):
    """uint32[depth + 1, S, W] BSI planes of int ``values`` [S, W * 32]."""
    depth = Q_DEPTH
    bits = [(values >> i) & 1 & present for i in range(depth)] + [present]
    return np.stack([np.packbits(b.astype(bool), axis=-1, bitorder="little").view(np.uint32)
                     for b in bits])


def _reference_filter(prog, ops, s):
    """The filter of shard ``s``, evaluated on its own rows by the
    reference walks."""
    kind = prog[0]
    if kind == "row":
        return ops[prog[1]][int(ops[prog[2]]), s]
    if kind == "range":
        walk = {"eq": bsi_ops.range_eq, "lt": lambda p, b: bsi_ops.range_lt(p, b, False),
                "gte": lambda p, b: bsi_ops.range_gt(p, b, True)}[prog[1]]
        return np.asarray(walk(ops[prog[2]][:, s], ops[prog[4]]))
    if kind == "between":
        return np.asarray(bsi_ops.range_between(ops[prog[1]][:, s], ops[prog[3]], ops[prog[4]]))
    subs = [_reference_filter(p, ops, s) for p in prog[1:]]
    out = subs[0]
    for x in subs[1:]:
        out = {"and": out & x, "or": out | x, "andnot": out & ~x}[kind]
    return out


def _case(depth, n_shards, seed):
    rng = np.random.default_rng(seed)
    measure = rng.integers(0, 2**32, (depth + 1, n_shards, W), dtype=np.uint32)
    present = rng.random((n_shards, W * 32)) < 0.9
    ops = [
        rng.integers(0, 2**32, (5, n_shards, W), dtype=np.uint32),
        np.int32(1), np.int32(3),
        _bsi_planes(rng.integers(0, 64, (n_shards, W * 32)), present),
        bsi_ops.to_bits(12, Q_DEPTH), bsi_ops.to_bits(7, Q_DEPTH),
        bsi_ops.to_bits(40, Q_DEPTH), bsi_ops.to_bits(33, Q_DEPTH),
    ]
    return measure, ops


def _reference(measure, ops, prog, requested):
    depth = measure.shape[0] - 1
    counts, n = np.zeros(depth, np.int64), 0
    for s in requested:
        f = (np.full(W, 0xFFFFFFFF, np.uint32) if prog == ("ones",)
             else _reference_filter(prog, ops, s))
        c, k = bsi_ops.sum_counts(measure[:, s], f)
        counts += np.asarray(c, np.int64)
        n += int(k)
    return counts, n


def _run(mesh, prog, measure, ops, requested, pallas):
    n_shards = measure.shape[1]
    mask = np.zeros((n_shards, 1), np.uint32)
    mask[list(requested)] = 0xFFFFFFFF
    specs = tuple(P(None, SHARD_AXIS) if np.ndim(x) == 3 else P() for x in ops)
    counts, n = kernels.sum_tree(
        mesh, prog, specs, ("slice", 0, measure.shape[0]), pallas,
        put_global(mesh, mask, P(SHARD_AXIS)),
        put_global(mesh, measure, P(None, SHARD_AXIS)),
        *[put_global(mesh, np.asarray(x), spec) for x, spec in zip(ops, specs)])
    return np.asarray(counts, np.int64), int(n)


@pytest.fixture
def interpret(monkeypatch):
    """The Pallas body in interpret mode (the CPU has no Mosaic)."""
    monkeypatch.setattr(kernels, "sum_counts_pallas",
                        functools.partial(kernels.sum_counts_pallas, interpret=True))


# Every filter at the cell's depth; every depth under no filter and
# under the cell's; on four devices, 10 shards each, a whole shard tile
# and a partial one.
CASES = [(1, 12, f, 24) for f in FILTERS]
CASES += [(1, 12, f, d) for f in ("ones", "flight1") for d in (0, 1, 7, 33)]
CASES += [(4, 40, f, 24) for f in ("ones", "union", "difference")]
CASES += [(4, 40, "flight1", d) for d in (0, 24, 33)]


@pytest.mark.parametrize("n_dev,n_shards,filt,depth", CASES,
                         ids=[f"{n}dev-{s}shards-{f}-depth{d}" for n, s, f, d in CASES])
def test_both_bodies_are_the_per_shard_reference(interpret, n_dev, n_shards, filt, depth):
    """Both bodies answer ``bsi.sum_counts`` summed over the requested
    shards: depth 0 (no value plane), 1, 7, 24 (the cell's measure) and
    33 (past 32 bits); every filter kind the lowering makes; a shard
    mask that leaves every third shard out; a shard block that is not a
    whole number of the Pallas body's shard tiles."""
    measure, ops = _case(depth, n_shards, seed=depth * 31 + n_shards)
    requested = [s for s in range(n_shards) if s % 3 != 1]
    prog = FILTERS[filt]
    want = _reference(measure, ops, prog, requested)
    assert want[1] > 0
    mesh = make_mesh(n_dev)
    for pallas in (False, True):
        got = _run(mesh, prog, measure, ops, requested, pallas)
        assert got[1] == want[1] and (got[0] == want[0]).all(), (pallas, got, want)


def test_pallas_body_reads_a_word_tile_at_a_time(interpret, monkeypatch):
    """Two word tiles a shard block (a lane chunk each): the
    accumulators carry across the tiles as across the chunks.  The tile
    width is read while ``sum_tree`` traces, so the jit cache is cleared
    and the shard count is one no other case uses."""
    monkeypatch.setattr(kernels, "SUM_TILE_WORDS", 128)
    kernels.sum_tree.clear_cache()
    measure, ops = _case(24, 13, seed=5)
    try:
        for filt in ("flight1", "difference"):
            want = _reference(measure, ops, FILTERS[filt], range(13))
            got = _run(make_mesh(1), FILTERS[filt], measure, ops, range(13), True)
            assert got[1] == want[1] and (got[0] == want[0]).all()
    finally:
        kernels.sum_tree.clear_cache()


def test_a_leaf_the_body_does_not_take_is_evaluated_first(interpret):
    """A maskable row of the batched lowering (its slot vector operand
    0, one slot naming no row) and a zero stack are evaluated by XLA
    into a row of their own, which the kernel reads as one more input;
    a BSI leaf with a missing plane is walked in the kernel, the plane
    read as zeros."""
    measure, ops = _case(7, 12, seed=9)
    ops = [np.array([3, -1], np.int32)] + ops  # every index below moves by one
    gathered = ("gather", (0, 1, 2, 3, 4, -1, 6))
    prog = ("or", ("rowm", 1, ("sv", 0)), ("rowm", 1, ("sv", 1)),
            ("and", ("zero", 1), ("row", 1, 2)), ("range", "lt", 4, gathered, 8))
    mesh = make_mesh(1)
    got = {p: _run(mesh, prog, measure, ops, range(12), p) for p in (False, True)}
    assert got[True][1] == got[False][1] > 0
    assert (got[True][0] == got[False][0]).all()


@pytest.mark.parametrize("missing", [2, 7], ids=["value_plane", "not_null_plane"])
def test_a_measure_with_a_missing_plane_runs_the_pallas_body(interpret, missing):
    """A gathered measure layout with -1 for a plane: the Pallas body
    reads that plane as zeros, as ``gather_planes`` does: a missing
    value plane counts 0, a missing not-null plane considers nothing."""
    measure, ops = _case(7, 12, seed=13)
    rows = tuple(-1 if i == missing else i for i in range(8))
    zeroed = measure.copy()
    zeroed[missing] = 0
    want = _reference(zeroed, ops, FILTERS["flight1"], range(12))
    mesh = make_mesh(1)
    mask = put_global(mesh, np.full((12, 1), 0xFFFFFFFF, np.uint32), P(SHARD_AXIS))
    specs = tuple(P(None, SHARD_AXIS) if np.ndim(x) == 3 else P() for x in ops)
    for pallas in (False, True):
        counts, n = kernels.sum_tree(
            mesh, FILTERS["flight1"], specs, ("gather", rows), pallas, mask,
            put_global(mesh, measure, P(None, SHARD_AXIS)),
            *[put_global(mesh, np.asarray(x), s) for x, s in zip(ops, specs)])
        assert int(n) == want[1] and (np.asarray(counts) == want[0]).all(), pallas
    assert (want[1] == 0) == (missing == 7)


# Rows of a wide Union: its inputs with the depth-7 measure's 8 planes
# are the cap, one more, and far more.
WIDE = [kernels.SUM_MAX_INPUTS - 8, kernels.SUM_MAX_INPUTS - 7, 600]


@pytest.mark.parametrize("n_rows", WIDE, ids=["at_the_cap", "past_the_cap", "600_rows"])
def test_a_filter_past_the_input_cap_is_evaluated_first(interpret, n_rows):
    """``Union(Row(d=..), ..)`` over as many distinct rows as the body
    takes inputs (SUM_MAX_INPUTS: the tiles of a call fill at most half
    its VMEM) is evaluated in the kernel; past that, the whole filter is
    evaluated by XLA into one row, and the kernel reads the measure and
    that row.  Both bodies answer the reference either way."""
    measure, ops = _case(7, 12, seed=17)
    # the batch's row-index vector: slots alternate rows 1 and 3
    slots = np.array([(1, 3)[j % 2] for j in range(n_rows)], np.int32)
    prog = ("or",) + tuple(("row", 1, ("sv", j)) for j in range(n_rows))
    wide_ops = [slots] + ops
    ins, measure_in, tree = kernels._sum_inputs(
        prog, wide_ops, measure, ("slice", 0, 8))
    inputs = 8 + (n_rows if n_rows + 8 <= kernels.SUM_MAX_INPUTS else 1)
    assert len(ins.planes) == inputs
    assert (tree[0] == "t") == (n_rows + 8 > kernels.SUM_MAX_INPUTS)
    want = _reference(measure, ops, ("or", ("row", ROWS, ROW_A), ("row", ROWS, ROW_B)),
                      range(12))
    mesh = make_mesh(1)
    for pallas in (False, True):
        got = _run(mesh, prog, measure, wide_ops, range(12), pallas)
        assert got[1] == want[1] and (got[0] == want[0]).all(), pallas


def test_engine_dispatches_the_pallas_body_and_counts_it(interpret):
    """Under the engine, with the flag a TPU backend sets: the executor's
    Sum answers as on the XLA body, and each dispatch counts its body in
    ``pilosa_engine_sum_programs_total``."""
    h = Holder()
    h.open()
    try:
        idx = h.create_index("i")
        rng = np.random.default_rng(11)
        cols = rng.choice(4 * SHARD_WIDTH, 3000, replace=False).tolist()
        idx.create_field("d").import_bulk(rng.integers(0, 3, len(cols)).tolist(), cols)
        for name, hi in (("q", 50), ("v", 10_000_000)):
            f = idx.create_field(name, FieldOptions(type="int", min=0, max=hi))
            f.import_values(cols, rng.integers(0, hi + 1, len(cols)).tolist())
        eng = MeshEngine(h, make_mesh(1))
        eng.result_memo.maxsize = 0  # every Sum dispatches
        ex = Executor(h, mesh_engine=eng)
        queries = ["Sum(field=v)", "Sum(Row(d=1), field=v)",
                   "Sum(Intersect(Row(d=2), Range(q == 7), Range(q >< [3, 40])), field=v)"]
        def answers():
            return [(r.val, r.count) for q in queries for r in ex.execute("i", q).results]

        want = answers()
        eng._sum_pallas = True  # what a TPU backend sets
        counter = REGISTRY.counter(METRIC_ENGINE_SUM_PROGRAMS, body="pallas")
        before = counter.get()
        assert answers() == want
        assert counter.get() - before == len(queries)
        assert min(count for _, count in want) > 0
        eng.close()
    finally:
        h.close()

"""GroupBy past the old 1,024-combination cap, on the device path.

Taxi query 4's shape at a small size: fields of 10, 7 and 51 rows (3,570
combinations) over 3 shards, with columns that hold several rows of one
field, so no row of a field excludes another.  The reference is plain
numpy: per-column row membership, then counts by explicit loops over the
combinations; it never sees a bitmap.  Every case asserts the device
program answered (a plan op with a device path, no host_fallback)."""

import functools
import gc
import json
import time
import urllib.request

import numpy as np
import pytest

from pilosa_tpu.core.field import FieldOptions
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.executor import Executor
from pilosa_tpu.executor.executor import (
    FieldRow,
    GroupAxes,
    GroupColumns,
    GroupCount,
    QueryResponse,
    _merge_group_counts,
)
from pilosa_tpu.net import wire
from pilosa_tpu.ops import SHARD_WIDTH
from pilosa_tpu.parallel import MeshEngine, kernels, make_mesh
from pilosa_tpu.util import plans
from pilosa_tpu.pql import parse
from pilosa_tpu.util.stats import METRIC_EXECUTOR_GROUP_RESULTS, REGISTRY

SHARDS = 3
COLS = 600  # columns a shard, scattered over the shard's width
DIMS = {"pc": 10, "yr": 7, "mi": 51}
FIELDS = tuple(DIMS)


@pytest.fixture(scope="module")
def data():
    """(holder, member, cols, amount): member[f] is bool[rows, n], the
    row membership of every column; ~15 % of the columns hold a second
    row of a field."""
    rng = np.random.default_rng(34)
    h = Holder()
    h.open()
    idx = h.create_index("i")
    cols = np.concatenate([
        s * SHARD_WIDTH + rng.choice(SHARD_WIDTH, COLS, replace=False)
        for s in range(SHARDS)])
    n = len(cols)
    member = {}
    for name, k in DIMS.items():
        m = np.zeros((k, n), bool)
        m[rng.integers(0, k, n), np.arange(n)] = True
        extra = rng.random(n) < 0.15
        m[rng.integers(0, k, n)[extra], np.arange(n)[extra]] = True
        member[name] = m
        rows, where = np.nonzero(m)
        idx.create_field(name).import_bulk(rows.tolist(), cols[where].tolist())
    amount = rng.integers(0, 1024, n)
    v = idx.create_field("amt", FieldOptions(type="int", min=0, max=1023))
    v.import_values(cols.tolist(), amount.tolist())
    yield h, member, cols, amount
    h.close()


@pytest.fixture(scope="module", params=[1, 4], ids=["1dev", "4dev"])
def served(request, data):
    eng = MeshEngine(data[0], make_mesh(request.param))
    eng.result_memo.maxsize = 0  # every case reaches the program
    yield Executor(data[0], mesh_engine=eng), eng
    eng.close()


def reference(member, keep, fields=FIELDS):
    """[(row ids, count)] with count > 0 in row-major order, by explicit
    loops over the combinations."""
    out = []
    dims = [member[f].shape[0] for f in fields]
    for combo in np.ndindex(*dims):
        hit = keep.copy()
        for f, r in zip(fields, combo):
            hit &= member[f][r]
        if hit.any():
            out.append((combo, int(hit.sum())))
    return out


def run(ex, q, shards=None):
    """(reply as [(row ids, count)], plan op paths)."""
    plans.take_dispatch_note()  # what an earlier test's engine call left on this thread
    plan = plans.begin("i", q, profile=True)
    with plans.attach(plan):
        res = ex.execute("i", q, shards=shards).results[0]
    got = [(tuple(fr.row_id for fr in gc.group), gc.count) for gc in res]
    return got, [op.get("path") for op in plan.to_dict()["ops"] if "path" in op]


ROWS = "Rows(field=pc), Rows(field=yr), Rows(field=mi)"
CASES = {
    "no_filter": (f"GroupBy({ROWS})", None, {}),
    "row_filter": (f"GroupBy({ROWS}, filter=Row(pc=1))", "pc1", {}),
    "range_filter": (f"GroupBy({ROWS}, filter=Range(amt >< [100, 700]))", "amt", {}),
    "limit": (f"GroupBy({ROWS}, limit=25)", None, {"limit": 25}),
    "offset": (f"GroupBy({ROWS}, limit=40, offset=13)", None, {"limit": 40, "offset": 13}),
    "shard_subset": (f"GroupBy({ROWS})", None, {"shards": [0, 2]}),
}


@pytest.mark.parametrize("case", CASES)
def test_reply_is_the_numpy_reference(served, data, case):
    ex, _ = served
    _, member, cols, amount = data
    q, flt, opt = CASES[case]
    keep = np.ones(len(cols), bool)
    if flt == "pc1":
        keep = member["pc"][1].copy()
    elif flt == "amt":
        keep = (amount >= 100) & (amount <= 700)
    if "shards" in opt:
        keep &= np.isin(cols // SHARD_WIDTH, opt["shards"])
    want = reference(member, keep)
    assert len(want) > 150  # the nest is well filled at this size
    if "limit" in opt:  # the progressive limit, then the offset (executor.go)
        want = want[:opt["limit"]][opt.get("offset", 0):]
    got, paths = run(ex, q, shards=opt.get("shards"))
    assert got == want
    assert paths and "host_fallback" not in paths and "memo" not in paths


def test_3570_combinations_take_a_device_path(served):
    ex, eng = served
    before = eng._group_combos_counter.get()
    got, paths = run(ex, f"GroupBy({ROWS}, filter=Range(amt >< [3, 900]))")
    assert got and paths == ["direct"]  # the batcher's direct path: one program
    assert eng._group_combos_counter.get() - before == 10 * 7 * 51
    assert not hasattr(eng, "MAX_GROUP_COMBOS")


def _lowered(dims, shards=8, words=256):
    f = np.zeros((shards, words), np.uint32)
    rows = [np.zeros((k, shards, words), np.uint32) for k in dims]
    return f, rows


@pytest.mark.parametrize("body", ["xla", "pallas"])
def test_trace_does_not_grow_with_the_combination_count(body):
    """The jaxpr of 2 x 2 x 51 = 204 combinations and of 10 x 7 x 51 =
    3,570 have the same number of equations in the XLA body, and in the
    Pallas body differ by the liveness test of each further prefix ROW
    (13 of them, the same few equations each; up to GROUP_UNROLL_WHOLE
    rows are unrolled): nothing is unrolled per combination (the Pallas
    body unrolls its inner loop over the last field's rows, at most
    GROUP_UNROLL_WHOLE of them)."""
    import jax

    def size(dims):
        f, rows = _lowered(dims)
        if body == "xla":
            fn = lambda f, *r: kernels._group_counts_xla(f, list(r))  # noqa: E731
        else:
            fn = lambda f, *r: kernels._group_counts_pallas(  # noqa: E731
                f, list(r), 128, interpret=True)
        return str(jax.make_jaxpr(fn)(f, *rows)).count(" = ")  # equations, however the printer wraps them

    a_row = 0 if body == "xla" else size((3, 2, 51)) - size((2, 2, 51))
    assert a_row < 10
    assert size((10, 7, 51)) == size((2, 2, 51)) + 13 * a_row
    assert size((4, 5, 200)) == size((2, 2, 200)) + 5 * a_row  # a last field past the whole unroll
    if body == "pallas":  # past GROUP_UNROLL_WHOLE prefix rows the tests are loops, one a 32-bit word of a field
        assert size((33, 63, 5)) == size((34, 62, 5))


@pytest.mark.parametrize("dims,acc_groups", [
    ((5,), 4096), ((3, 4), 4096), ((3, 2, 5), 4096), ((2, 2, 2, 3), 4096),
    ((3, 3, 4), 8),  # 36 groups through 8-group accumulators: five passes
], ids=["1field", "2fields", "3fields", "4fields", "passes"])
def test_pallas_body_is_the_xla_body(monkeypatch, dims, acc_groups):
    """The TPU body in interpret mode against the XLA body, on random
    planes (the compile for a v5e is tests/test_tpu_compile.py's)."""
    monkeypatch.setattr(kernels, "GROUP_ACC_GROUPS", acc_groups)
    rng = np.random.default_rng(sum(dims))
    f = rng.integers(0, 2**32, (8, 256), dtype=np.uint32)
    rows = [rng.integers(0, 2**32, (k, 8, 256), dtype=np.uint32) for k in dims]
    want = np.asarray(kernels._group_counts_xla(f, rows))
    assert want.sum() > 0
    for tile_words in (128, 256):
        got, _ = kernels._group_counts_pallas(f, rows, tile_words, interpret=True)
        assert (np.asarray(got) == want).all()


def _skip_planes(filt, measure):
    """(f, rows, planes) over [16, 512] words, four [8, 256] tiles:
    three fields of 4, 3 and 5 rows (``wide_fields``: 40, 30 and 5, so
    that the liveness bits fill several words and a field lies across
    two), a column in one row of each (and a tenth of them in a
    second), and the filter named by ``filt``."""
    rng = np.random.default_rng(37)
    S, W, dims = 16, 512, (40, 30, 5) if filt == "wide_fields" else (4, 3, 5)
    rows = []
    for k in dims:
        cat = rng.integers(0, k, (S, W * 32))
        also = np.where(rng.random((S, W * 32)) < 0.1, rng.integers(0, k, (S, W * 32)), cat)
        rows.append(np.stack([
            np.packbits((cat == i) | (also == i), axis=1, bitorder="little").view(np.uint32)
            for i in range(k)]))
    f = rng.integers(0, 2**32, (S, W), dtype=np.uint32)
    if filt == "empty":
        f[:] = 0
    elif filt in ("two_rows", "wide_fields"):  # what SSB's Q3.3 does: a where on the grouped attributes
        for r in rows[:3 if measure else 2]:  # ... on columns that hold no other row of the field
            f &= (r[0] | r[2]) & ~np.bitwise_or.reduce(np.delete(r, [0, 2], axis=0))
    elif filt == "some_tiles":  # two of the four tiles hold no column of the filter
        f[:8, :256] = 0
        f[8:, 256:] = 0
    planes = rng.integers(0, 2**32, (4, S, W), dtype=np.uint32) if measure else None
    return f, rows, planes


def _reckoned_steps(f, pre_rows, tile_words):
    """(skipped, scored) by the rule of the Pallas body, in numpy: a
    prefix is scored in a tile where every one of its rows has a bit
    under the filter there."""
    S, W = f.shape
    skipped = scored = 0
    for i in range(0, S, kernels.GROUP_TILE_SHARDS):
        for j in range(0, W, tile_words):
            t = np.s_[i:i + kernels.GROUP_TILE_SHARDS, j:j + tile_words]
            live = [[bool((f[t] & r[k][t]).any()) for k in range(r.shape[0])] for r in pre_rows]
            for combo in np.ndindex(*[r.shape[0] for r in pre_rows]):
                if all(live[n][k] for n, k in enumerate(combo)):
                    scored += 1
                else:
                    skipped += 1
    return skipped, scored


@pytest.mark.parametrize("filt", ["all_live", "empty", "two_rows", "some_tiles", "wide_fields"])
@pytest.mark.parametrize("measure", [False, True], ids=["count", "measure"])
def test_pallas_body_skips_a_prefix_with_a_dead_row_and_says_so(monkeypatch, measure, filt):
    """A prefix one of whose rows has no bit under the filter in the
    tile is not scored: the tensor stays the XLA body's, and the two
    step counts are what numpy reckons from the tiles, over four tiles
    and several accumulator passes."""
    monkeypatch.setattr(kernels, "GROUP_ACC_GROUPS", 25)  # 12 x 5 in 3 passes; 60 x 5 in 12
    f, rows, planes = _skip_planes(filt, measure)
    want = np.asarray(kernels._group_counts_xla(f, rows, planes))
    got, steps = kernels._group_counts_pallas(f, rows, 256, interpret=True, planes=planes)
    assert (np.asarray(got) == want).all()
    assert (want.sum() > 0) == (filt != "empty")
    skipped, scored = _reckoned_steps(f, rows if measure else rows[:-1], 256)
    assert tuple(np.asarray(steps)) == (skipped, scored)
    n_pre = int(np.prod([r.shape[0] for r in (rows if measure else rows[:-1])]))
    assert skipped + scored == 4 * n_pre
    if filt == "all_live":
        assert skipped == 0
    elif filt == "empty":
        assert scored == 0
    elif filt in ("two_rows", "wide_fields"):
        assert scored == 4 * (8 if measure else 4)
    else:
        assert (skipped, scored) == (2 * n_pre, 2 * n_pre)
    # the XLA loop tests nothing and counts nothing
    assert tuple(np.asarray(kernels.group_counts_local(f, rows, False, planes)[1])) == (0, 0)


def test_prefix_steps_reach_their_counter_and_nothing_else_sees_them(monkeypatch):
    """The Pallas body (interpret mode) under the engine: the skipped
    and scored steps of a GroupBy ride its one readback into
    ``pilosa_engine_group_prefix_steps_total``; the executor, the result
    memo and the fused ``group`` edge see the count tensor alone."""
    monkeypatch.setattr(kernels, "_group_counts_pallas",
                        functools.partial(kernels._group_counts_pallas, interpret=True))
    rng = np.random.default_rng(7)
    h = Holder()
    h.open()
    idx = h.create_index("k")
    n_shards, tile_bits = 8, 2048 * 32
    # columns in the first five of a shard's sixteen tiles only; field b's row 2 in the first alone
    cols = np.concatenate([s * SHARD_WIDTH + rng.choice(5 * tile_bits, 300, replace=False)
                           for s in range(n_shards)])
    a, b = rng.integers(0, 4, len(cols)), rng.integers(0, 2, len(cols))
    b[(cols % SHARD_WIDTH < tile_bits) & (rng.random(len(cols)) < 0.5)] = 2
    c = rng.integers(0, 2, len(cols))
    for name, vals in (("a", a), ("b", b), ("c", c)):
        idx.create_field(name).import_bulk(vals.tolist(), cols.tolist())
    eng = MeshEngine(h, make_mesh(1))
    eng._group_pallas = True  # what a TPU backend sets
    ex = Executor(h, mesh_engine=eng)
    q = "GroupBy(Rows(field=a), Rows(field=b), filter=Row(c=1))"
    # a (4 rows) is the inner loop, b's rows the prefixes: 16 tiles x 3
    tile = (cols % SHARD_WIDTH) // tile_bits
    scored = sum(bool(((tile == t) & (b == k) & (c == 1)).any()) for t in range(16) for k in range(3))
    assert scored == 2 * 5 + 1

    def steps():
        return tuple(cn.get() for cn in eng._group_prefix_steps_counters)

    want = [((ra, rb), int(((a == ra) & (b == rb) & (c == 1)).sum())) for ra in range(4) for rb in range(3)]
    before = steps()
    got = [(tuple(fr.row_id for fr in gc.group), gc.count) for gc in ex.execute("k", q).results[0]]
    assert got == [w for w in want if w[1]]
    assert steps() == (before[0] + 48 - scored, before[1] + scored)
    # the same text again is the memo's: no dispatch, no steps, the same groups
    hits = eng.cache_stats["memo_groupby"][0]
    again = [(tuple(fr.row_id for fr in gc.group), gc.count) for gc in ex.execute("k", q).results[0]]
    assert again == got and eng.cache_stats["memo_groupby"][0] == hits + 1
    assert steps() == (before[0] + 48 - scored, before[1] + scored)
    # group_counts_async leaves ONE array on the device, the tensor and then the two counts;
    # group_host resolves its readback to the tensor
    flt = parse("Row(c=1)").calls[0]
    shards = list(range(n_shards))
    dev, dims = eng.group_counts_async("k", ["a", "b"], [[0, 1, 2, 3], [0, 1, 2]], flt, shards)
    assert dev.shape == (4 * 3 + 2,) and dims == (4, 3)
    assert tuple(np.asarray(dev)[-2:]) == (48 - scored, scored)
    solo = eng.group_counts("k", ["a", "b"], [[0, 1, 2, 3], [0, 1, 2]], flt, shards)
    assert isinstance(solo, np.ndarray) and solo.tolist() == [[w[1] for w in want[i * 3:i * 3 + 3]] for i in range(4)]
    # a fused drain's group edge hands out the tensor alone, and counts no step
    before = steps()
    fused = eng.fused_drain([
        ("k", {"kind": "count", "call": parse("Intersect(Row(a=1), Row(c=1))").calls[0]}, shards),
        ("k", {"kind": "group", "fields": ["a", "b"], "rows": [[0, 1, 2, 3], [0, 1, 2]], "filter": flt}, shards),
    ])
    assert fused[0] == int(((a == 1) & (c == 1)).sum())
    assert np.array_equal(np.asarray(fused[1]), solo) and steps() == before
    eng.close()
    h.close()


def test_compile_time_is_bounded_at_3570_combinations(served):
    """A first-seen filter structure at 3,570 combinations compiles in
    seconds (the old body's 3,570 reduce operands did not compile)."""
    ex, _ = served
    t = time.monotonic()
    got, _ = run(ex, f"GroupBy({ROWS}, filter=Union(Row(yr=1), Row(yr=2), Row(pc=3)))")
    assert got and time.monotonic() - t < 60


def _groups(fields, n, key=False):
    return [GroupCount([FieldRow(f, row_id=i * 7 + d, row_key="k" if key and d == 0 else "")
                        for d, f in enumerate(fields)], i + 1) for i in range(n)]


def _columns(fields, n):
    """``_groups(fields, n)`` as the device path hands it out: the
    diagonal of axes of n rows a field."""
    i = np.arange(n)
    axes = GroupAxes(fields, [(i * 7 + d).astype(np.uint64) for d in range(len(fields))])
    return GroupColumns(axes, np.ravel_multi_index((i,) * len(fields), axes.shape),
                        (i + 1).astype(np.int32))


def _nest(shape=(10, 7, 51), empty=0.1, fields=FIELDS):
    """A count tensor's groups as the device path hands them out, about
    ``empty`` of the combinations at 0."""
    rng = np.random.default_rng(sum(shape))
    counts = rng.integers(1, 1 << 20, int(np.prod(shape))).astype(np.int32)
    counts[rng.random(counts.size) < empty] = 0
    axes = GroupAxes(fields, [np.arange(k, dtype=np.uint64) * 3 for k in shape])
    flat = np.flatnonzero(counts)
    return GroupColumns(axes, flat, counts[flat])


def _forms():
    return tuple(REGISTRY.counter(METRIC_EXECUTOR_GROUP_RESULTS, form=f).get()
                 for f in ("columns", "objects"))


def _iterated(cols):
    list(cols)
    return cols


REPLIES = {
    "one_field": (lambda: [_columns(["pc"], 4)], True),
    "two_fields": (lambda: [_columns(["pc", "yr"], 4)], True),
    "three_fields": (lambda: [_columns(["pc", "yr", "mi"], 5)], True),
    "limit": (lambda: [_columns(["pc", "yr", "mi"], 9)[:4]], True),
    "offset": (lambda: [_columns(["pc", "yr", "mi"], 9)[3:][:4]], True),
    "one_group": (lambda: [_columns(["pc", "yr"], 1)], True),
    # two GroupBys in one request; a name json escapes
    "two_calls": (lambda: [_columns(["a"], 1), _columns(['q"%d\\', "b"], 3)], True),
    "3570_groups": (lambda: [_columns(["pc", "yr", "mi"], 3570)], True),
    # a well-filled nest: from the texts kept with its axes
    "nest": (lambda: [_nest()], True),
    "full_nest": (lambda: [_nest(empty=0)], True),
    "nest_limit_offset": (lambda: [_nest()[:3000][40:]], True),
    "nest_twice": (lambda: [_nest((4, 5), fields=("a%s", 'b"'))] * 2, True),
    "thin_nest": (lambda: [_nest(empty=0.9)], True),  # a format a group
    "nest_cut_to_none": (lambda: [_nest()[5000:]], True),
    "empty": (lambda: [[]], True),  # no group: the executor hands out a plain list
    "empty_beside_full": (lambda: [_columns(["pc"], 2), []], False),
    "mixed": (lambda: [_columns(["pc"], 2), 7], False),  # with a Count
    "objects": (lambda: [_groups(["pc"], 2)], False),  # the host iterator's list
    "row_key": (lambda: [_groups(["pc"], 2, key=True)], False),
    "handed_out": (lambda: [_iterated(_columns(["pc"], 2))], False),  # its objects may have been written to
}


@pytest.mark.parametrize("trace_id", [None, "abc123"], ids=["plain", "traceID"])
@pytest.mark.parametrize("case", REPLIES)
def test_group_reply_bytes_are_json_dumps_bytes(case, trace_id):
    """The columnar GroupBy reply is the generic encoder's, byte for
    byte, and builds no object on the way; anything else is declined."""
    make, fast = REPLIES[case]
    resp = QueryResponse(results=make())
    before = _forms()
    got = wire.count_response_bytes(resp, trace_id)
    assert _forms() == before  # encoding hands out no object
    want = wire.response_to_json(resp)
    if trace_id:
        want["traceID"] = trace_id
    assert (got == json.dumps(want).encode()) if fast else got is None


@pytest.mark.parametrize("empty,kept", [(0.1, True), (0.74, True), (0.76, False)])
def test_reply_texts_are_kept_for_a_well_filled_nest_only(empty, kept, monkeypatch):
    """A reply that lists a quarter of its axes' combinations writes
    the texts before each combination's count onto the axes, where the
    next finds them; a thinner one, or axes past GROUP_TEXTS_MAX, never."""
    cols = _nest(empty=empty)
    assert (4 * len(cols) >= 3570) == kept
    wire.count_response_bytes(QueryResponse(results=[cols]), None)
    texts = cols.axes.reply_texts
    assert (texts is not None and len(texts) == 3570) == kept
    wire.count_response_bytes(QueryResponse(results=[cols[10:]]), None)
    assert cols.axes.reply_texts is texts
    monkeypatch.setattr(wire, "GROUP_TEXTS_MAX", 3569)
    full = _nest(empty=0)
    resp = QueryResponse(results=[full])
    got = wire.count_response_bytes(resp, None)
    assert full.axes.reply_texts is None
    assert got == json.dumps(wire.response_to_json(resp)).encode()


def test_axes_and_their_texts_go_when_a_grouped_field_is_written():
    """The executor hands every request the same axes until a write
    moves a grouped field's version; the reply after it is written from
    new texts and lists the new row."""
    h = Holder()
    h.open()
    idx = h.create_index("w")
    idx.create_field("a").import_bulk([0, 0, 1, 1], [1, 2, 2, 3])
    idx.create_field("b").import_bulk([0, 1, 2, 2], [1, 2, 2, 3])
    eng = MeshEngine(h, make_mesh(1))
    eng.result_memo.maxsize = 0
    ex = Executor(h, mesh_engine=eng)
    q = "GroupBy(Rows(field=a), Rows(field=b))"

    def reply():
        resp = ex.execute("w", q)
        cols = resp.results[0]
        assert type(cols) is GroupColumns
        got = wire.count_response_bytes(resp, None)
        assert got == json.dumps(wire.response_to_json(resp)).encode()
        return cols.axes, json.loads(got)["results"][0]

    try:
        axes, first = reply()
        assert axes.shape == (2, 3) and len(first) == 5 and axes.reply_texts is not None
        again, second = reply()
        assert again is axes and second == first
        ex.execute("w", "Set(3, a=5)")
        moved, third = reply()
        assert moved is not axes and moved.shape == (3, 3) and moved.reply_texts is not None
        assert third == first + [{"group": [{"field": "a", "rowID": 5},
                                            {"field": "b", "rowID": 2}], "count": 1}]
        assert ex._group_axes_cache[("w", ("a", "b"), (0,))] is moved
    finally:
        eng.close()
        h.close()


@pytest.mark.parametrize("cut", [slice(None), slice(3, None), slice(None, 4), slice(2, 7),
                                 slice(9, None)], ids=str)
def test_columns_are_the_group_count_list(cut):
    """Element for element, and under the executor's offset and limit
    cuts, which stay vector slices."""
    want = _groups(["pc", "yr", "mi"], 9)
    cols = _columns(["pc", "yr", "mi"], 9)[cut]
    assert type(cols) is GroupColumns and cols.objects is None
    assert len(cols) == len(want[cut])
    assert cols == want[cut] and want[cut] == cols and not cols != want[cut]
    assert [g for g in cols] == want[cut]
    assert all(cols[i] == g for i, g in enumerate(want[cut]))
    assert cols[1:].objects == cols.objects[1:]  # handed out: the objects are the result
    assert cols != want[cut] + want[:1] and cols != 7


def test_columns_hand_out_their_objects_once():
    cols = _columns(["pc", "yr"], 6)
    before = _forms()
    first = cols[0]
    assert list(cols)[0] is first and cols[0] is first  # a merge writes to what it was handed
    assert _forms() == (before[0], before[1] + 1)
    first.count += 5
    assert _merge_group_counts(cols, _groups(["pc", "yr"], 2), 100)[0].count == 7


@pytest.mark.parametrize("texts_max", [1 << 16, 0], ids=["kept_texts", "format_a_group"])
def test_the_reply_of_3570_groups_builds_no_object_a_group(monkeypatch, texts_max):
    """Tensor to bytes under 100 GC-tracked objects, with the result and
    the payload both still held (the parent: a GroupCount, a list and a
    tuple a group, ~11,000), and never 100 at once on the way: no
    collection falls due at a threshold of 100."""
    monkeypatch.setattr(wire, "GROUP_TEXTS_MAX", texts_max)
    rng = np.random.default_rng(35)
    counts = rng.integers(1, 1 << 20, 3570).astype(np.int32)
    axes = GroupAxes(FIELDS, [np.arange(k, dtype=np.uint64) for k in (10, 7, 51)])

    def reply():
        flat = np.flatnonzero(counts > 0)
        cols = GroupColumns(axes, flat, counts[flat])
        return cols, wire.count_response_bytes(QueryResponse(results=[cols]), None)

    reply()  # whatever a first call keeps
    gc.collect()
    gc.disable()
    try:
        before = len(gc.get_objects())
        held = reply()
        made = len(gc.get_objects()) - before
    finally:
        gc.enable()
    assert len(json.loads(held[1])["results"][0]) == 3570
    assert made < 100 and (axes.reply_texts is not None) == bool(texts_max)

    due = []
    note = lambda phase, info: due.append(info["generation"]) if phase == "start" else None  # noqa: E731
    threshold = gc.get_threshold()
    for _ in range(3):  # another thread of the test process may allocate meanwhile
        gc.collect()
        del due[:]
        gc.callbacks.append(note)
        gc.set_threshold(100, *threshold[1:])
        try:
            reply()
        finally:
            gc.set_threshold(*threshold)
            gc.callbacks.remove(note)
        if not due:
            break
    assert not due


@pytest.fixture(scope="module")
def routed(data):
    """The data behind an API and an HTTP server on one device, with a
    keyed field ``kd`` beside it."""
    from pilosa_tpu.api import API, QueryRequest
    from pilosa_tpu.net import serve

    eng = MeshEngine(data[0], make_mesh(1))
    eng.result_memo.maxsize = 0
    api = API(holder=data[0], mesh_engine=eng)
    api.create_field("i", "kd", {"type": "set", "keys": True})
    cols = data[2]
    api.query(QueryRequest("i", " ".join(
        f'Set({int(c)}, kd="{"near" if n % 3 else "far"}")' for n, c in enumerate(cols[::40]))))
    srv, _thread = serve(api, port=0)
    yield api, f"http://localhost:{srv.server_address[1]}/index/i/query"
    srv.shutdown()
    eng.close()


def _post(uri, q, headers=()):
    req = urllib.request.Request(uri, data=q.encode(), method="POST", headers=dict(headers))
    return urllib.request.urlopen(req, timeout=120).read()


def _as_json(want, fields=FIELDS):
    return [{"group": [{"field": f, "rowID": r} for f, r in zip(fields, rows)], "count": n}
            for rows, n in want]


def test_json_route_sends_the_columns_and_hands_out_no_object(routed, data):
    _, uri = routed
    want = reference(data[1], np.ones(len(data[2]), bool))
    before = _forms()
    payload = _post(uri, f"GroupBy({ROWS})")
    assert _forms() == (before[0] + 1, before[1])
    doc = json.loads(payload)
    assert doc["results"] == [_as_json(want)]
    assert payload == json.dumps(doc).encode()  # the generic encoder's bytes
    payload = _post(uri, f"GroupBy({ROWS}, limit=40, offset=13) GroupBy(Rows(field=yr))")
    assert _forms() == (before[0] + 3, before[1])
    doc = json.loads(payload)
    assert doc["results"][0] == _as_json(want[:40][13:]) and len(doc["results"][1]) == 7
    assert payload == json.dumps(doc).encode()


def _profiled(uri, want):
    doc = json.loads(_post(uri + "?profile=1", f"GroupBy({ROWS})"))
    assert [op["path"] for op in doc["plan"]["ops"] if "path" in op] == ["direct"]
    return doc["results"] == [_as_json(want)]


def _protobuf(uri, want):
    from pilosa_tpu.net import proto

    doc = proto.decode_query_response(
        _post(uri, f"GroupBy({ROWS})", {"Accept": proto.CONTENT_TYPE}))
    return not doc["err"] and doc["results"] == [
        [GroupCount([FieldRow(f, r) for f, r in zip(FIELDS, rows)], n) for rows, n in want]]


def _keyed(uri, want):
    doc = json.loads(_post(uri, "GroupBy(Rows(field=kd), Rows(field=yr))"))
    groups = doc["results"][0]
    keys = [g["group"][0] for g in groups]
    return (all(set(k) == {"field", "rowKey"} for k in keys)
            and {k["rowKey"] for k in keys} == {"near", "far"}
            and len(groups) == 14
            and sum(g["count"] for g in groups) >= SHARDS * COLS // 40)  # a column, a year or two


@pytest.mark.parametrize("route", [_profiled, _protobuf, _keyed], ids=lambda f: f.__name__)
def test_routes_that_walk_the_groups_answer_as_before(routed, data, route):
    """?profile=1, the protobuf reply and a keyed field take the result
    as GroupCount objects: the device path's columns, handed out once."""
    _, uri = routed
    before = _forms()
    assert route(uri, reference(data[1], np.ones(len(data[2]), bool)))
    assert _forms() == (before[0] + 1, before[1] + 1)


def test_merge_with_a_remote_partial_answers_as_before(served, data, monkeypatch):
    """Shards the device path does not hold come from the mapper (here
    the host iterator, as a remote node's partial would) and merge into
    the columns' objects."""
    ex, _ = served
    want = reference(data[1], np.ones(len(data[2]), bool))
    local = ex._local_shards
    monkeypatch.setattr(ex, "_local_shards", lambda index, shards, remote: local(index, shards, remote)[:2])
    before = _forms()
    got, paths = run(ex, f"GroupBy({ROWS})")
    assert got == want and paths and "host_fallback" not in paths
    assert _forms() == (before[0] + 1, before[1] + 1)


def test_host_iterator_counts_as_objects_and_equals_the_columns(served, data):
    """A child with ``limit`` is the device path's since PR 36 (columns
    over axes made for the request); what the host iterator answers (an
    executor without an engine) is a list, counted as objects, and its
    list is the device path's columns, group for group."""
    ex, _ = served
    before = _forms()
    q = "GroupBy(Rows(field=pc, limit=3), Rows(field=yr))"
    res = ex.execute("i", q).results[0]
    assert type(res) is GroupColumns and len(res) == 21 and not res.axes.kept
    assert _forms() == (before[0] + 1, before[1])
    listed = Executor(data[0]).execute("i", q).results[0]
    assert type(listed) is list and _forms() == (before[0] + 1, before[1] + 1)
    assert res == listed
    before = _forms()
    host = Executor(data[0]).execute("i", f"GroupBy({ROWS}, limit=300, offset=7)").results[0]
    cols = ex.execute("i", f"GroupBy({ROWS}, limit=300, offset=7)").results[0]
    assert type(host) is list and type(cols) is GroupColumns and len(cols) == 293
    assert cols == host and cols[5:50] == host[5:50]


def test_fused_group_edge_counts_3570_combinations(served):
    """A drain's ``group`` edge runs the solo program's body: the same
    tensor, beside a Count in one fused program."""
    from pilosa_tpu import pql

    _, eng = served
    shards = list(range(SHARDS))
    rows = [list(range(k)) for k in DIMS.values()]
    flt = pql.parse("Row(pc=1)").calls[0]
    solo = eng.group_counts("i", list(FIELDS), rows, flt, shards)
    assert solo.shape == (10, 7, 51) and solo.sum() > 0
    group, count = eng.fused_many("i", [
        ({"kind": "group", "fields": list(FIELDS), "rows": rows, "filter": flt}, shards),
        ({"kind": "count", "call": pql.parse("Row(yr=2)").calls[0]}, shards),
    ])
    assert np.array_equal(np.asarray(group).reshape(solo.shape), solo)
    assert count == eng.count("i", pql.parse("Row(yr=2)").calls[0], shards)


@pytest.mark.parametrize("dims", [(5, 2, 3), (2, 6, 3), (4, 4)], ids=str)
def test_the_widest_field_is_scored_last_and_the_tensor_keeps_its_order(dims):
    """group_counts_local moves the widest field to the inner loop and
    hands the counts back in the caller's row-major order."""
    rng = np.random.default_rng(len(dims))
    f = rng.integers(0, 2**32, (2, 128), dtype=np.uint32)
    rows = [rng.integers(0, 2**32, (k, 2, 128), dtype=np.uint32) for k in dims]
    want = np.asarray(kernels._group_counts_xla(f, rows))
    got = np.asarray(kernels.group_counts_local(f, rows, False)[0])
    assert (got == want).all() and want.sum() > 0

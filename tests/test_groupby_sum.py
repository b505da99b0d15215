"""GroupBy(..., aggregate=Sum(field=v)) with row-restricted Rows children,
on the device path.

SSB flight 3's shape at a small size: set fields of 6, 12 and 5 rows
(row ids from 1) over 3 shards, with columns that hold several rows of
one field; an int field with nulls (``v``), one with a non-zero ``min``
(``w``) and one for range filters (``q``).  The reference is plain
numpy: per-column row membership, then counts and sums by explicit loops
over the combinations; it never sees a bitmap.  Every device case also
equals the host iterator (an executor without an engine) and asserts the
device program answered (a plan op with a device path, no
host_fallback)."""

import itertools
import json
import threading
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
from pilosa_tpu.parallel import MeshEngine, fusion, kernels, make_mesh
from pilosa_tpu.util import plans
from pilosa_tpu.util.stats import METRIC_EXECUTOR_GROUP_RESULTS, REGISTRY

SHARDS = 3
COLS = 500  # columns a shard, scattered over the shard's width
DIMS = {"a": 6, "b": 12, "c": 5}
MEASURES = {"v": (0, 1000), "w": (-50, 1000)}  # v has nulls, w a non-zero min


@pytest.fixture(scope="module")
def data():
    """(holder, member, cols, values): member[f] is bool[rows, n] (row
    id r is member row r - 1); values[f] is (int[n], has bool[n])."""
    rng = np.random.default_rng(36)
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
        idx.create_field(name).import_bulk((rows + 1).tolist(), cols[where].tolist())
    values = {}
    for name, (lo, hi) in MEASURES.items():
        val = rng.integers(lo, hi + 1, n)
        has = rng.random(n) < 0.8 if name == "v" else np.ones(n, bool)
        values[name] = (val, has)
        idx.create_field(name, FieldOptions(type="int", min=lo, max=hi)).import_values(
            cols[has].tolist(), val[has].tolist())
    q = rng.integers(0, 64, n)
    values["q"] = (q, np.ones(n, bool))
    idx.create_field("q", FieldOptions(type="int", min=0, max=63)).import_values(
        cols.tolist(), q.tolist())
    yield h, member, cols, values
    h.close()


@pytest.fixture(scope="module", params=[1, 4], ids=["1dev", "4dev"])
def served(request, data):
    eng = MeshEngine(data[0], make_mesh(request.param))
    eng.result_memo.maxsize = 0  # every case reaches the program
    yield Executor(data[0], mesh_engine=eng), eng
    eng.close()


@pytest.fixture(scope="module")
def one(data):
    eng = MeshEngine(data[0], make_mesh(1))
    eng.result_memo.maxsize = 0
    yield Executor(data[0], mesh_engine=eng), eng
    eng.close()


def reference(data, fields, rowsets, keep, measure="v"):
    """[(row ids, count, sum)] with count > 0 in row-major order, by
    explicit loops over the combinations."""
    _, member, _, values = data
    val, has = values[measure]
    out = []
    for combo in itertools.product(*rowsets):
        hit = keep.copy()
        for f, r in zip(fields, combo):
            hit &= member[f][r - 1]
        if hit.any():
            out.append((combo, int(hit.sum()), int(val[hit & has].sum())))
    return out


def run(ex, q, shards=None):
    """(reply as [(row ids, count, sum)], plan ops)."""
    plans.take_dispatch_note()  # what an earlier test's engine call left on this thread
    plan = plans.begin("i", q, profile=True)
    with plans.attach(plan):
        res = ex.execute("i", q, shards=shards).results[0]
    got = [(tuple(fr.row_id for fr in g.group), g.count, g.sum) for g in res]
    return got, [op for op in plan.to_dict()["ops"] if "path" in op]


ALL = {f: range(1, k + 1) for f, k in DIMS.items()}
ABC = "Rows(field=a), Rows(field=b), Rows(field=c)"
CUT = "Rows(field=a, previous=2, limit=3), Rows(field=b, previous=4, limit=5), Rows(field=c, limit=4)"
CUT_ROWS = [range(3, 6), range(5, 10), range(1, 5)]
# case -> (children, filter text or None, reference row sets, keep(values, member), measure, options)
CASES = {
    "no_filter": (ABC, None, list(ALL.values()), None, "v", {}),
    "row_filter": (ABC, "Row(a=2)", list(ALL.values()), lambda v, m: m["a"][1], "v", {}),
    "range_filter": (ABC, "Range(q >< [5, 50])", list(ALL.values()),
                     lambda v, m: (v["q"][0] >= 5) & (v["q"][0] <= 50), "v", {}),
    "union_filters": (ABC, "Intersect(Union(Row(a=1), Row(a=4)), Union(Row(b=2), Row(b=7)))",
                      list(ALL.values()),
                      lambda v, m: (m["a"][0] | m["a"][3]) & (m["b"][1] | m["b"][6]), "v", {}),
    "previous_limit": (CUT, "Range(q >< [5, 50])", CUT_ROWS,
                       lambda v, m: (v["q"][0] >= 5) & (v["q"][0] <= 50), "v", {}),
    "first_rows": ("Rows(field=a, previous=0, limit=3), Rows(field=b, limit=2)", None,
                   [range(1, 4), range(1, 3)], None, "v", {"fields": "ab"}),
    "nonzero_min": (CUT, None, CUT_ROWS, None, "w", {}),
    "limit": (ABC, None, list(ALL.values()), None, "v", {"limit": 25}),
    "offset": (CUT, None, CUT_ROWS, None, "w", {"limit": 40, "offset": 13}),
    "shard_subset": (ABC, None, list(ALL.values()), None, "v", {"shards": [0, 2]}),
}


@pytest.mark.parametrize("case", CASES)
def test_reply_is_the_numpy_reference_and_the_host_iterator(served, data, case):
    ex, _ = served
    children, flt, rowsets, keep_of, measure, opt = CASES[case]
    _, member, cols, values = data
    keep = np.ones(len(cols), bool) if keep_of is None else keep_of(values, member).copy()
    if "shards" in opt:
        keep &= np.isin(cols // SHARD_WIDTH, opt["shards"])
    want = reference(data, opt.get("fields", "abc"), rowsets, keep, measure)
    assert len(want) > 5
    if "limit" in opt:  # the progressive limit, then the offset (executor.go)
        want = want[:opt["limit"]][opt.get("offset", 0):]
    q = f"GroupBy({children}"
    q += f", filter={flt}" if flt else ""
    q += "".join(f", {k}={opt[k]}" for k in ("limit", "offset") if k in opt)
    q += f", aggregate=Sum(field={measure}))"
    got, ops = run(ex, q, shards=opt.get("shards"))
    assert got == want
    assert [op["path"] for op in ops] == ["direct"] and ops[0]["memo"] == "skipped"
    listed, host_ops = run(Executor(data[0]), q, shards=opt.get("shards"))
    assert listed == want and not host_ops


def test_a_column_child_is_the_rows_of_that_column(served, data):
    ex, _ = served
    _, member, cols, _ = data
    col = int(cols[7])
    rows = [int(r) + 1 for r in np.nonzero(member["b"][:, 7])[0]]
    q = f"GroupBy(Rows(field=a), Rows(field=b, column={col}), aggregate=Sum(field=w))"
    got, ops = run(ex, q)
    assert got == reference(data, "ab", [ALL["a"], rows], np.ones(len(cols), bool), "w")
    assert got and [op["path"] for op in ops] == ["direct"]
    assert got == run(Executor(data[0]), q)[0]


PAGES = [
    "Rows(field=a, previous=2), Rows(field=b, previous=4)",
    "Rows(field=a, previous=2), Rows(field=b, previous=40)",  # past b's last row: carries
    "Rows(field=a), Rows(field=b, previous=4), Rows(field=c, previous=2)",
    "Rows(field=a, previous=3), Rows(field=b, previous=12), Rows(field=c, previous=5)",
    "Rows(field=a, previous=6), Rows(field=b, previous=12), Rows(field=c, previous=5)",  # the end
    "Rows(field=a, previous=0), Rows(field=b, limit=3, previous=2), Rows(field=c, previous=2)",
    "Rows(field=a, previous=9)",
    "Rows(field=c, previous=2)",
]


@pytest.mark.parametrize("aggregate", ["", ", aggregate=Sum(field=v)"], ids=["count", "sum"])
@pytest.mark.parametrize("children", PAGES)
def test_previous_alone_starts_the_listing_where_the_iterator_does(one, data, children, aggregate):
    """``previous`` without ``limit`` is the iterator's seek: the device
    path lists the same suffix of the row-major order."""
    ex, _ = one
    q = f"GroupBy({children}{aggregate})"
    got, ops = run(ex, q)
    assert got == run(Executor(data[0]), q)[0]
    assert [op["path"] for op in ops] == ["direct"]


def test_an_axis_that_changes_between_requests_compiles_once(one, data):
    """Row ids, ``previous`` and range bounds are operands: two requests
    of one structure and one set of widths share a program, also where
    an axis happens to start at the field's first row."""
    ex, _ = one
    text = ("GroupBy(Rows(field=a, previous={}, limit=3), Rows(field=b, previous={}, limit=5), "
            "filter=Range(q >< [{}, 60]), aggregate=Sum(field=v))")
    assert run(ex, text.format(2, 4, 3))[0]
    compiled = kernels.group_tree._cache_size()
    for p1, p2, lo in ((0, 0, 1), (3, 7, 9), (1, 0, 20)):
        got, ops = run(ex, text.format(p1, p2, lo))
        q = data[3]["q"][0]
        assert got == reference(data, "ab", [range(p1 + 1, p1 + 4), range(p2 + 1, p2 + 6)],
                                (q >= lo) & (q <= 60))
    assert kernels.group_tree._cache_size() == compiled


def test_concurrent_callers_through_the_batcher(one, data):
    """Eight threads, each its own aggregated GroupBy beside Counts and
    Sums that fuse: every reply is the reference's (an aggregated
    GroupBy keeps to its solo program in a mixed drain)."""
    ex, _ = one
    out, errs = {}, []

    def caller(k):
        try:
            q = (f"GroupBy(Rows(field=a, previous={k % 3}, limit=3), Rows(field=c), "
                 f"filter=Range(q >< [{k}, 60]), aggregate=Sum(field=w))")
            for _ in range(3):
                ex.execute("i", f"Count(Intersect(Row(a=1), Range(q > {k})))")
                ex.execute("i", f"Sum(Row(b={k + 1}), field=v)")
                res = ex.execute("i", q).results[0]
            out[k] = [(tuple(fr.row_id for fr in g.group), g.count, g.sum) for g in res]
        except Exception as e:  # noqa: BLE001 — reported below
            errs.append(e)

    threads = [threading.Thread(target=caller, args=(k,)) for k in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    for k in range(8):
        assert out[k] == reference(
            data, "ac", [range(k % 3 + 1, k % 3 + 4), ALL["c"]],
            (data[3]["q"][0] >= k) & (data[3]["q"][0] <= 60), "w")


def test_a_fused_drain_declines_an_aggregated_group_by(one):
    """Handed one directly, the fused planner declines it (the edge
    knows counts only); its drain-mates are answered."""
    _, eng = one
    from pilosa_tpu import pql

    shards = list(range(SHARDS))
    group, count = eng.fused_many("i", [
        ({"kind": "group", "fields": ["a"], "rows": [[1, 2, 3]], "filter": None,
          "aggregate": "v"}, shards),
        ({"kind": "count", "call": pql.parse("Row(a=2)").calls[0]}, shards),
    ])
    assert group is fusion.DECLINED
    assert count == eng.count("i", pql.parse("Row(a=2)").calls[0], shards)


def test_a_write_to_the_measure_shows_in_the_next_reply(data):
    """The aggregated tensor stays out of the result memo: with the memo
    on, a write to the measure between two requests is summed."""
    h = Holder()
    h.open()
    idx = h.create_index("m")
    idx.create_field("g").import_bulk([1, 1, 2], [1, 2, 3])
    idx.create_field("v", FieldOptions(type="int", min=0, max=100)).import_values(
        [1, 2, 3], [10, 20, 30])
    eng = MeshEngine(h, make_mesh(1))
    ex = Executor(h, mesh_engine=eng)
    q = "GroupBy(Rows(field=g), aggregate=Sum(field=v))"
    try:
        assert eng.result_memo.maxsize > 0
        first = [g.to_dict() for g in ex.execute("m", q).results[0]]
        assert [(g["count"], g["sum"]) for g in first] == [(2, 30), (1, 30)]
        assert [g.to_dict() for g in ex.execute("m", q).results[0]] == first
        ex.execute("m", "Set(2, v=75)")
        again = [g.to_dict() for g in ex.execute("m", q).results[0]]
        assert [(g["count"], g["sum"]) for g in again] == [(2, 85), (1, 30)]
    finally:
        eng.close()
        h.close()


@pytest.mark.parametrize("q,says", [
    ("GroupBy(Rows(field=a), having=Count(Row(a=1)))", "unknown argument 'having'"),
    ("GroupBy(Rows(field=a), agregate=Sum(field=v))", "unknown argument 'agregate'"),
    ("GroupBy(Rows(field=a), aggregate=Sum(field=a))", "not an int field"),
    ("GroupBy(Rows(field=a), aggregate=Min(field=v))", "aggregate must be Sum"),
    ("GroupBy(Rows(field=a), aggregate=Sum(Row(a=1), field=v))", "aggregate must be Sum"),
    ("GroupBy(Rows(field=a), aggregate=Sum(field=nope))", "nope"),
])
@pytest.mark.parametrize("engine", [True, False], ids=["device", "host"])
def test_what_group_by_does_not_implement_is_refused_by_name(one, data, q, says, engine):
    ex = one[0] if engine else Executor(data[0])
    with pytest.raises(Exception, match=says):
        ex.execute("i", q)


@pytest.fixture(scope="module")
def flight3():
    """Flight 3's nest at a small size: 10 x 10 x 6 rows under a 24-bit
    measure, 2 shards."""
    rng = np.random.default_rng(3)
    h = Holder()
    h.open()
    idx = h.create_index("f3")
    n = 400
    cols = np.concatenate([s * SHARD_WIDTH + rng.choice(SHARD_WIDTH, n, replace=False)
                           for s in range(2)])
    place = {f: rng.integers(0, 20, len(cols)) for f in ("cc", "sc")}
    year = rng.integers(0, 7, len(cols))
    for f, v in (*place.items(), ("yr", year - 1)):
        idx.create_field(f).import_bulk((v + 1).tolist(), cols.tolist())
    rev = rng.integers(0, 1 << 24, len(cols))
    idx.create_field("rev", FieldOptions(type="int", min=0, max=(1 << 24) - 1)).import_values(
        cols.tolist(), rev.tolist())
    eng = MeshEngine(h, make_mesh(1))
    eng.result_memo.maxsize = 0
    yield Executor(h, mesh_engine=eng), eng, place, year, rev
    eng.close()
    h.close()


def test_600_combinations_under_26_planes_take_a_device_path(flight3):
    ex, eng, place, year, rev = flight3
    q = ("GroupBy(Rows(field=cc, previous=10, limit=10), Rows(field=sc, previous=0, limit=10), "
         "Rows(field=yr, limit=6), aggregate=Sum(field=rev))")
    passes = eng._group_sum_passes_counter.get()
    combos = eng._group_combos_counter.get()
    plans.take_dispatch_note()
    plan = plans.begin("f3", q, profile=True)
    with plans.attach(plan):
        res = ex.execute("f3", q).results[0]
    ops = [op for op in plan.to_dict()["ops"] if "path" in op]
    assert [op["path"] for op in ops] == ["direct"] and ops[0]["groups"] == 600
    assert ops[0]["memo"] == "skipped" and "aggregate" in ops[0]["memo_reason"]
    assert eng._group_sum_passes_counter.get() - passes == 600 * 26
    assert eng._group_combos_counter.get() - combos == 600
    want = {}
    for c, s, y, r in zip(place["cc"], place["sc"], year, rev):
        if c >= 10 and s < 10 and y < 6:
            n, v = want.get((c + 1, s + 1, y), (0, 0))
            want[c + 1, s + 1, y] = (n + 1, v + int(r))
    assert {tuple(fr.row_id for fr in g.group): (g.count, g.sum) for g in res} == want
    assert type(res) is GroupColumns and not res.axes.kept


def test_a_tensor_over_max_groups_cells_is_the_host_iterators(flight3, monkeypatch):
    """MAX_GROUPS bounds the tensor read back in cells: groups x
    (depth + 2).  600 groups fit alone and not with 26 planes each."""
    ex, eng, *_ = flight3
    monkeypatch.setattr(eng, "MAX_GROUPS", 600 * 25)
    children = "Rows(field=cc, limit=10), Rows(field=sc, limit=10), Rows(field=yr, limit=6)"
    assert type(ex.execute("f3", f"GroupBy({children})").results[0]) is GroupColumns
    res = ex.execute("f3", f"GroupBy({children}, aggregate=Sum(field=rev))").results[0]
    assert type(res) is list and res and all(g.sum is not None for g in res)


def _pc(x):
    return int(np.bitwise_count(x).sum())


@pytest.mark.parametrize("dims,depth,acc", [((3, 4, 5), 7, 4096), ((6,), 0, 4096), ((2, 3), 24, 40),
                                            ((5, 5), 3, 7)], ids=str)
def test_both_bodies_count_every_plane_of_every_combination(dims, depth, acc, monkeypatch):
    """The Pallas body (interpret mode; several accumulator passes where
    ``acc`` is small) and the XLA loop against popcounts in numpy."""
    monkeypatch.setattr(kernels, "GROUP_ACC_GROUPS", acc)
    rng = np.random.default_rng(depth)
    S, W = 8, 256
    f = rng.integers(0, 2**32, (S, W), dtype=np.uint32)
    rows = [rng.integers(0, 2**32, (k, S, W), dtype=np.uint32) for k in dims]
    planes = rng.integers(0, 2**32, (depth + 1, S, W), dtype=np.uint32)
    want = np.zeros(dims + (depth + 2,), np.int64)
    for combo in np.ndindex(*dims):
        m = f.copy()
        for r, k in zip(rows, combo):
            m &= r[k]
        have = m & planes[depth]
        want[combo] = [_pc(have & planes[b]) for b in range(depth)] + [_pc(have), _pc(m)]
    for got in (kernels._group_counts_xla(f, rows, planes),
                kernels._group_counts_pallas(f, rows, 128, interpret=True, planes=planes)[0],
                kernels.group_counts_local(f, rows, False, planes)[0]):
        assert np.array_equal(np.asarray(got).reshape(want.shape), want)


def _summed(n=9, kept=True, deep=False, full=False):
    """Groups on the diagonal of three axes (``full``: every combination
    of them), with sums (past an int64's reach when ``deep``: Python
    integers in an object vector)."""
    i = np.arange(n)
    axes = GroupAxes(["pc", "yr", 'q"%d'], [(i * 7 + d).astype(np.uint64) for d in range(3)],
                     kept=kept)
    flat = np.arange(axes.size) if full else np.ravel_multi_index((i,) * 3, axes.shape)
    sums = (np.arange(len(flat)) + 1) * 1000003 - 5
    if deep:
        sums = np.array([int(s) << 70 for s in sums], dtype=object)
    return GroupColumns(axes, flat, (np.arange(len(flat)) + 1).astype(np.int32), sums=sums)


SUMMED = {  # case -> (the result, whether its reply is written from texts kept with the axes)
    "kept_texts": (lambda: _summed(full=True), True),
    "kept_texts_cut": (lambda: _summed(full=True)[100:700], True),
    "made_for_the_request": (lambda: _summed(full=True, kept=False), False),
    "format_a_group": (lambda: _summed(), False),
    "cut": (lambda: _summed()[2:7], False),
    "deep_sums": (lambda: _summed(deep=True), False),
    "deep_sums_kept_texts": (lambda: _summed(4, deep=True, full=True), True),
    "one_group": (lambda: _summed(1), True),
}


@pytest.mark.parametrize("case", SUMMED)
def test_summed_reply_bytes_are_json_dumps_bytes(case):
    make, texts = SUMMED[case]
    cols = make()
    resp = QueryResponse(results=[cols])
    got = wire.count_response_bytes(resp, "abc")
    assert cols.objects is None  # encoding hands out no object
    want = dict(wire.response_to_json(resp), traceID="abc")
    assert got == json.dumps(want).encode()
    assert all(set(g) == {"group", "count", "sum"} for g in want["results"][0])
    assert (cols.axes.reply_texts is not None) == texts


def test_columns_with_sums_are_the_group_count_list():
    cols = _summed()
    want = [GroupCount([FieldRow(f, i * 7 + d) for d, f in enumerate(cols.fields)], i + 1,
                       (i + 1) * 1000003 - 5) for i in range(9)]
    assert cols[3:] == want[3:] and cols == want
    assert cols[2].sum == want[2].sum and want[2].to_dict()["sum"] == 3000004
    assert "sum" not in GroupCount([], 1).to_dict() and GroupCount([], 1) != GroupCount([], 1, 0)
    merged = _merge_group_counts(list(cols), [GroupCount(list(want[0].group), 2, 7)], 100)
    assert (merged[0].count, merged[0].sum) == (3, 1000005) and len(merged) == 9


def test_a_deep_measure_is_summed_in_python_integers():
    """depth + 31 bits pass an int64: a 45-bit measure's sums are exact."""
    h = Holder()
    h.open()
    idx = h.create_index("d")
    idx.create_field("g").import_bulk([1, 1, 2], [1, 2, SHARD_WIDTH + 3])
    big = (1 << 45) - 1
    idx.create_field("v", FieldOptions(type="int", min=-7, max=big)).import_values(
        [1, 2, SHARD_WIDTH + 3], [big, big - 1, -7])
    eng = MeshEngine(h, make_mesh(1))
    try:
        for ex in (Executor(h, mesh_engine=eng), Executor(h)):
            res = ex.execute("d", "GroupBy(Rows(field=g), aggregate=Sum(field=v))").results[0]
            assert [(g.count, g.sum) for g in res] == [(2, 2 * big - 1), (1, -7)]
    finally:
        eng.close()
        h.close()


# -- the routes ---------------------------------------------------------------


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


def _forms():
    return tuple(REGISTRY.counter(METRIC_EXECUTOR_GROUP_RESULTS, form=f).get()
                 for f in ("columns", "objects"))


CUT_AB = "Rows(field=a, previous=1, limit=2), Rows(field=c, limit=2)"


def test_json_reply_with_aggregate_is_this_text(routed, data):
    _, uri = routed
    want = reference(data, "ac", [range(2, 4), range(1, 3)], np.ones(len(data[2]), bool), "w")
    text = "[" + ", ".join(
        '{"group": [{"field": "a", "rowID": %d}, {"field": "c", "rowID": %d}], '
        '"count": %d, "sum": %d}' % (*rows, n, s) for rows, n, s in want) + "]"
    before = _forms()
    body = _post(uri, f"GroupBy({CUT_AB}, aggregate=Sum(field=w))")
    assert _forms() == (before[0] + 1, before[1])  # columns to the socket, no object
    assert body.startswith(b'{"results": [' + text.encode() + b'], "traceID": "') and len(want) == 4


def test_json_reply_without_aggregate_is_todays_text(routed, data):
    """No ``aggregate``: no ``"sum"``, byte for byte the reply of before."""
    _, uri = routed
    want = reference(data, "ac", [range(2, 4), range(1, 3)], np.ones(len(data[2]), bool))
    text = "[" + ", ".join(
        '{"group": [{"field": "a", "rowID": %d}, {"field": "c", "rowID": %d}], "count": %d}'
        % (*rows, n) for rows, n, _ in want) + "]"
    assert _post(uri, f"GroupBy({CUT_AB})").startswith(
        b'{"results": [' + text.encode() + b'], "traceID": "')
    whole = json.loads(_post(uri, "GroupBy(Rows(field=a), Rows(field=c))"))["results"][0]
    assert len(whole) == 30 and all(set(g) == {"group", "count"} for g in whole)


def test_protobuf_reply_carries_the_sum(routed, data):
    from pilosa_tpu.net import proto

    _, uri = routed
    want = reference(data, "ac", [range(2, 4), range(1, 3)], np.ones(len(data[2]), bool), "w")
    doc = proto.decode_query_response(_post(
        uri, f"GroupBy({CUT_AB}, aggregate=Sum(field=w))", {"Accept": proto.CONTENT_TYPE}))
    assert not doc["err"] and doc["results"] == [[
        GroupCount([FieldRow("a", r[0]), FieldRow("c", r[1])], n, s) for r, n, s in want]]
    assert any(s < 0 for _, _, s in reference(
        data, "a", [ALL["a"]], data[3]["w"][0] < 0, "w"))  # int64 on the wire: negative sums
    plain = proto.decode_query_response(_post(
        uri, f"GroupBy({CUT_AB})", {"Accept": proto.CONTENT_TYPE}))
    assert [g.sum for g in plain["results"][0]] == [None] * 4


def test_negative_sums_round_trip_the_protobuf(routed, data):
    from pilosa_tpu.net import proto

    _, uri = routed
    q = "GroupBy(Rows(field=a), filter=Range(w < 0), aggregate=Sum(field=w))"
    doc = proto.decode_query_response(_post(uri, q, {"Accept": proto.CONTENT_TYPE}))
    want = reference(data, "a", [ALL["a"]], data[3]["w"][0] < 0, "w")
    assert [(g.count, g.sum) for g in doc["results"][0]] == [(n, s) for _, n, s in want]
    assert all(s < 0 for _, _, s in want)


def test_a_keyed_field_is_translated_beside_its_sum(routed, data):
    _, uri = routed
    groups = json.loads(_post(
        uri, "GroupBy(Rows(field=kd), aggregate=Sum(field=w))"))["results"][0]
    assert {g["group"][0]["rowKey"] for g in groups} == {"near", "far"}
    cols = data[2][::40]
    val = data[3]["w"][0][::40]
    far = np.arange(len(cols)) % 3 == 0
    by_key = {g["group"][0]["rowKey"]: (g["count"], g["sum"]) for g in groups}
    assert by_key == {"far": (int(far.sum()), int(val[far].sum())),
                      "near": (int((~far).sum()), int(val[~far].sum()))}


def test_remote_partials_decode_with_their_sums():
    doc = [{"group": [{"field": "a", "rowID": 3}], "count": 2, "sum": -9},
           {"group": [{"field": "a", "rowKey": "k"}], "count": 1}]
    got = wire.result_from_json("GroupBy", doc)
    assert got == [GroupCount([FieldRow("a", 3)], 2, -9), GroupCount([FieldRow("a", 0, "k")], 1)]
    assert [g.to_dict() for g in got] == doc


def test_merge_with_a_remote_partial_adds_the_sums(one, data, monkeypatch):
    """Shards the device path does not hold come from the mapper (here
    the host iterator, as a remote node's partial would), whose row
    filters are resolved over every shard, and merge into the columns'
    objects."""
    ex, _ = one
    local = ex._local_shards
    monkeypatch.setattr(ex, "_local_shards",
                        lambda index, shards, remote: local(index, shards, remote)[:2])
    q = f"GroupBy({CUT}, filter=Range(q >< [5, 50]), aggregate=Sum(field=w))"
    want = reference(data, "abc", CUT_ROWS,
                     (data[3]["q"][0] >= 5) & (data[3]["q"][0] <= 50), "w")
    got, ops = run(ex, q)
    assert got == want and [op["path"] for op in ops] == ["direct"]


@pytest.mark.parametrize("q,says", [
    ("GroupBy(Rows(field=a), having=Count(Row(a=1)))", "having"),
    ("GroupBy(Rows(field=a), aggregate=Sum(field=a))", "not an int field"),
])
def test_a_refused_argument_is_an_http_400_that_names_it(routed, q, says):
    import urllib.error

    _, uri = routed
    with pytest.raises(urllib.error.HTTPError) as err:
        _post(uri, q)
    assert err.value.code == 400 and says in err.value.read().decode()

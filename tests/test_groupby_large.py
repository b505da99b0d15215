"""GroupBy past the old 1,024-combination cap, on the device path.

Taxi query 4's shape at a small size: fields of 10, 7 and 51 rows (3,570
combinations) over 3 shards, with columns that hold several rows of one
field, so no row of a field excludes another.  The reference is plain
numpy: per-column row membership, then counts by explicit loops over the
combinations; it never sees a bitmap.  Every case asserts the device
program answered (a plan op with a device path, no host_fallback)."""

import json
import time

import numpy as np
import pytest

from pilosa_tpu.core.field import FieldOptions
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.executor import Executor
from pilosa_tpu.executor.executor import FieldRow, GroupCount, QueryResponse
from pilosa_tpu.net import wire
from pilosa_tpu.ops import SHARD_WIDTH
from pilosa_tpu.parallel import MeshEngine, kernels, make_mesh
from pilosa_tpu.util import plans

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
    3,570 have the same number of equations, in both bodies: nothing is
    unrolled per combination (the Pallas body unrolls its inner loop over
    the last field's rows, at most GROUP_UNROLL_WHOLE of them)."""
    import jax

    def size(dims):
        f, rows = _lowered(dims)
        if body == "xla":
            fn = lambda f, *r: kernels._group_counts_xla(f, list(r))  # noqa: E731
        else:
            fn = lambda f, *r: kernels._group_counts_pallas(  # noqa: E731
                f, list(r), 128, interpret=True)
        return len(str(jax.make_jaxpr(fn)(f, *rows)).splitlines())

    assert size((10, 7, 51)) == size((2, 2, 51))
    assert size((4, 5, 200)) == size((2, 2, 200))  # a last field past the whole unroll


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
        got = kernels._group_counts_pallas(f, rows, tile_words, interpret=True)
        assert (np.asarray(got) == want).all()


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


@pytest.mark.parametrize("results,fast", [
    ([_groups(["pc", "yr", "mi"], 5)], True),
    ([_groups(["a"], 1), _groups(['q"%d\\', "b"], 3)], True),  # two calls; a name json escapes
    ([_groups(["pc"], 2, key=True)], False),  # a row key: the generic encoder
    ([_groups(["pc"], 2), 7], False),  # mixed with a Count
    ([_groups(["pc"], 2), []], False),  # an empty GroupBy beside a full one
], ids=["three_fields", "two_calls", "row_key", "mixed", "empty"])
def test_group_reply_bytes_are_json_dumps_bytes(results, fast):
    """The GroupBy reply's fast encoder gives the generic encoder's bytes,
    or declines."""
    resp = QueryResponse(results=results)
    for trace_id in (None, "abc123"):
        want = wire.response_to_json(resp)
        if trace_id:
            want["traceID"] = trace_id
        got = wire.count_response_bytes(resp, trace_id)
        assert (got == json.dumps(want).encode()) if fast else got is None


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
    got = np.asarray(kernels.group_counts_local(f, rows, False))
    assert (got == want).all() and want.sum() > 0

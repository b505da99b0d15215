"""Tanimoto TopN on the device: ``TopN(f, Row(f=id), tanimotoThreshold=t)``
with no ``n`` as one program on the field's narrow form
(``kernels.tanimoto_topn`` through ``MeshEngine.tanimoto_async`` and the
executor's ``_mesh_topn_tanimoto``), against ``fragment.top``'s host walk
(an executor without the engine) and a NumPy reference written here, pair
list for pair list: seeded fingerprints in analog series, 5,006 molecules
x 256 bits, every threshold 1-100 (the window's and the gate's edges
among them, an exact ``c·100 = t·(r + s − c)`` included), the error above
100, a query row with no bits or no id, the slab overflow and a cache
that holds fewer rows than the field (both the host walk's), the named
declines, a write between two searches, the plan and the one compile;
and a field too many rows deep for a dense stack, whose other calls
answer without one."""

import json
import urllib.request

import numpy as np
import pytest

from pilosa_tpu.core import fragment as frag_mod
from pilosa_tpu.core.field import FieldOptions
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.executor import Executor
from pilosa_tpu.executor.executor import Error
from pilosa_tpu.ops import SHARD_WIDTH
from pilosa_tpu.parallel import MeshEngine, kernels, make_mesh
from pilosa_tpu.parallel.engine import MeshEngine as _Engine
from pilosa_tpu.util import plans
from pilosa_tpu.util.stats import (
    METRIC_ENGINE_TOPN_HITS,
    METRIC_ENGINE_TOPN_ROWS_SCORED,
    METRIC_ENGINE_TOPN_SLAB_OVERFLOW,
    METRIC_EXECUTOR_TOPN_DECLINES,
    REGISTRY,
)

BITS = 256
SERIES_MOLECULES = 2900
BIG = 2100  # one series of more members than the slab holds


def _series(rng, weights, n_members, bits_lo, bits_hi, flips_hi):
    parent = np.zeros(BITS, bool)
    parent[rng.choice(BITS, int(rng.integers(bits_lo, bits_hi)), replace=False, p=weights)] = True
    out = np.repeat(parent[None], n_members, axis=0)
    for m in out:
        m[rng.choice(BITS, int(rng.integers(1, flips_hi + 1)), replace=False)] ^= True
    return out


def _edges():
    """Rows whose scores fall on the edges: a 15-bit query Q15 and a
    15-bit row sharing 10 of its bits (Tanimoto 10/20 = 50 exactly:
    100·c = t·(r + s − c) at t = 50); a 20-bit query Q20, a 10-bit and an
    11-bit part of it and a 40-bit row holding it (r = s·t/100 and
    r = s·100/t at t = 50)."""
    rows = np.zeros((6, BITS), bool)
    rows[0, 200:215] = True  # Q15
    rows[1, 200:210] = True
    rows[1, 240:245] = True  # shares 10 of Q15's 15
    rows[2, 216:236] = True  # Q20
    rows[3, 216:226] = True  # 10 of Q20
    rows[4, 216:227] = True  # 11 of Q20
    rows[5, 216:236] = True
    rows[5, 236:256] = True  # Q20 and 20 more
    return rows


@pytest.fixture(scope="module")
def data():
    """(holder, fingerprints bool[M, BITS], ids of the edge rows)."""
    rng = np.random.default_rng(40)
    weights = 1.0 / np.arange(1, BITS + 1)
    weights = weights[rng.permutation(BITS)] / weights.sum()
    parts, n = [], 0
    while n < SERIES_MOLECULES:
        k = min(int(min(rng.zipf(2.0), 60)), SERIES_MOLECULES - n)
        parts.append(_series(rng, weights, k, 8, 41, 4))
        n += k
    parts.append(_series(rng, weights, BIG, 24, 25, 1))
    parts.append(_edges())
    fp = np.concatenate(parts)
    h = Holder()
    h.open()
    idx = h.create_index("c")
    rows, cols = np.nonzero(fp)
    idx.create_field("fp", FieldOptions(cache_size=10000)).import_bulk(rows.tolist(), cols.tolist())
    # The same rows under a cache too small to hold them.
    idx.create_field("small", FieldOptions(cache_size=100)).import_bulk(rows.tolist(), cols.tolist())
    # The same rows over two shards.
    wide = np.where(rows % 2 == 0, cols, cols + SHARD_WIDTH)
    idx.create_field("wide", FieldOptions(cache_size=10000)).import_bulk(rows.tolist(), wide.tolist())
    yield h, fp, SERIES_MOLECULES + BIG
    h.close()


@pytest.fixture(scope="module")
def lanes(data):
    """(device executor, host executor, engine)."""
    eng = MeshEngine(data[0], make_mesh(1), max_resident_bytes=64 << 20)
    eng.result_memo.maxsize = 0
    yield Executor(data[0], mesh_engine=eng), Executor(data[0]), eng
    eng.close()


def reference(fp, q, t):
    """The NumPy search: every molecule's shared bits with q, the walk's
    gate in integers, the survivors by count down, then id down."""
    if q >= len(fp) or not fp[q].any():
        return []
    r = fp.sum(axis=1).astype(np.int64)
    c = (fp & fp[q]).sum(axis=1).astype(np.int64)
    s = int(r[q])
    hit = (r * 100 > s * t) & (r * t < s * 100) & (c > 0) & (c * 100 > t * (r + s - c))
    ids = np.flatnonzero(hit)
    order = np.lexsort((-ids, -c[ids]))
    return [(int(i), int(c[i])) for i in ids[order]]


def run(ex, q, field="fp", extra=""):
    """(pairs, plan ops with a path) of one TopN."""
    text = f"TopN({field}, Row({field}={q}), tanimotoThreshold={{t}}{extra})"
    return lambda t: _run(ex, text.format(t=t))


def _run(ex, text):
    plans.take_dispatch_note()  # what an earlier test's engine call left on this thread
    plan = plans.begin("c", text, profile=True)
    with plans.attach(plan):
        res = ex.execute("c", text).results[0]
    return [(int(i), int(n)) for i, n in res], [op for op in plan.to_dict()["ops"] if "path" in op]


def _declines():
    return {r: REGISTRY.counter(METRIC_EXECUTOR_TOPN_DECLINES, reason=r).get()
            for r in ("n", "attr", "ids", "src", "shards", "cache", "budget", "overflow")}


def _edge_thresholds(fp, q):
    """The thresholds at which some row's standing changes for query q:
    integer t with 100·c = t·(r + s − c), 100·r = s·t or r·t = 100·s."""
    r = fp.sum(axis=1).astype(np.int64)
    c = (fp & fp[q]).sum(axis=1).astype(np.int64)
    s = int(r[q])
    ts = set()
    for num, den in ((100 * c, r + s - c), (100 * r, np.full_like(r, s)),
                     (np.full_like(r, 100 * s), r)):
        ok = (den > 0) & (num % np.maximum(den, 1) == 0)
        ts |= {int(v) for v in (num[ok] // den[ok]) if 1 <= v <= 100}
    return sorted(ts)


def test_an_exact_gate_equality_is_in_the_data(data):
    _, fp, edge = data
    r, s, c = fp[edge + 1].sum(), fp[edge].sum(), (fp[edge] & fp[edge + 1]).sum()
    assert (r, s, c) == (15, 15, 10) and 100 * c == 50 * (r + s - c)
    assert 50 in _edge_thresholds(fp, edge)


@pytest.mark.parametrize("pick", ["edge_q15", "edge_q20", "edge_share10", "series_a", "series_b",
                                  "series_c", "big_series_member", "edge_q20_superset"])
def test_device_lane_host_walk_and_numpy_agree_at_every_threshold(data, lanes, pick):
    _, fp, edge = data
    dev, host, _ = lanes
    q = {"edge_q15": edge, "edge_q20": edge + 2, "edge_share10": edge + 1, "series_a": 3,
         "series_b": 777, "series_c": 2500, "big_series_member": SERIES_MOLECULES + 7,
         "edge_q20_superset": edge + 5}[pick]
    on_dev, on_host = run(dev, q), run(host, q)
    walked = set(_edge_thresholds(fp, q)[::4]) | {1, 50, 70, 90, 100}
    for t in range(1, 101):
        want = reference(fp, q, t)
        if len(want) > kernels.TANIMOTO_SLAB:  # the host walk's (test_a_slab_overflow...)
            continue
        got, ops = on_dev(t)
        assert got == want, t
        assert [op["path"] for op in ops] == ["tanimoto"] and ops[0]["hits"] == len(want)
        if t in walked:
            assert on_host(t)[0] == want, t


def test_threshold_100_answers_nothing_and_over_100_is_an_error(data, lanes):
    dev, host, _ = lanes
    assert run(dev, 5)(100)[0] == run(host, 5)(100)[0] == []
    for ex in (dev, host):
        with pytest.raises(Error, match="from 1 to 100"):
            run(ex, 5)(101)


def test_a_query_row_with_no_bits_or_no_id_matches_nothing(data, lanes):
    dev, host, _ = lanes
    for q in (len(data[1]) + 10, 10**6):  # ids the field has no row for
        assert run(dev, q)(70)[0] == run(host, q)(70)[0] == []


def test_a_slab_overflow_is_answered_by_the_host_walk(data, lanes):
    _, fp, _ = data
    dev, _, _ = lanes
    q = SERIES_MOLECULES + 3
    want = reference(fp, q, 30)
    assert len(want) > kernels.TANIMOTO_SLAB
    before = _declines()["overflow"]
    overflows = REGISTRY.counter(METRIC_ENGINE_TOPN_SLAB_OVERFLOW).get()
    got, ops = run(dev, q)(30)
    assert got == want
    assert _declines()["overflow"] == before + 1
    assert REGISTRY.counter(METRIC_ENGINE_TOPN_SLAB_OVERFLOW).get() == overflows + 1
    assert ops[0]["path"] == "tanimoto" and ops[0]["overflow"] is True
    assert {"op": "TopN", "path": "host_walk", "declined": "overflow"} in ops


def test_a_cache_smaller_than_the_field_is_walked_on_the_host(data, lanes):
    """cacheSize 100 < 5,006 rows: the walk ranks the cache's rows only,
    so the lane declines and the reply is the walk's."""
    dev, host, _ = lanes
    before = _declines()["cache"]
    for t in (40, 70):
        got, ops = run(dev, 3, field="small")(t)
        assert got == run(host, 3, field="small")(t)[0]
        assert {"op": "TopN", "path": "host_walk", "declined": "cache"} in ops
    assert _declines()["cache"] == before + 2


@pytest.mark.parametrize("reason,field,extra", [
    ("n", "fp", ", n=5"),
    ("attr", "fp", ', attrName="kind", attrValues=["a"]'),
    ("ids", "fp", ", ids=[1, 2, 3, 5]"),
    ("shards", "wide", ""),
])
def test_what_the_lane_does_not_take_is_declined_by_name(data, lanes, reason, field, extra):
    dev, host, _ = lanes
    before = _declines()
    got, ops = run(dev, 5, field=field, extra=extra)(60)
    assert got == run(host, 5, field=field, extra=extra)(60)[0]
    after = _declines()
    assert after[reason] == before[reason] + 1
    assert sum(after.values()) == sum(before.values()) + 1
    assert {"op": "TopN", "path": "host_walk", "declined": reason} in ops


def test_a_source_that_is_not_one_row_of_the_field_is_declined(data, lanes):
    dev, host, _ = lanes
    text = "TopN(fp, Intersect(Row(fp=5), Row(fp=6)), tanimotoThreshold=40)"
    before = _declines()["src"]
    assert _run(dev, text)[0] == _run(host, text)[0]
    assert _declines()["src"] == before + 1


def test_a_write_between_two_searches_is_seen():
    """A Set on a molecule moves its count and its shared bits: the next
    search answers from the written field, not a stale narrow form."""
    rng = np.random.default_rng(7)
    fp = np.zeros((300, BITS), bool)
    base = rng.random(BITS) < 0.1
    for i in range(300):
        fp[i] = base ^ (rng.random(BITS) < 0.03)
    h = Holder()
    h.open()
    rows, cols = np.nonzero(fp)
    h.create_index("c").create_field("fp", FieldOptions(cache_size=1000)).import_bulk(
        rows.tolist(), cols.tolist())
    eng = MeshEngine(h, make_mesh(1))
    eng.result_memo.maxsize = 0
    ex = Executor(h, mesh_engine=eng)
    try:
        assert run(ex, 0)(80)[0] == reference(fp, 0, 80)
        col = int(np.flatnonzero(~fp[1])[0])
        ex.execute("c", f"Set({col}, fp=1)")
        fp[1, col] = True
        col0 = int(np.flatnonzero(~fp[0])[0])
        ex.execute("c", f"Set({col0}, fp=0)")
        fp[0, col0] = True
        for t in (60, 80, 90):
            got, ops = run(ex, 0)(t)
            assert got == reference(fp, 0, t) and ops[0]["path"] == "tanimoto"
    finally:
        eng.close()
        h.close()


def test_the_served_plan_shows_the_lane_and_one_program_serves_every_query(data):
    """POST /index/c/query?profile=1: one op ``TopN`` on path
    ``tanimoto`` with the rows it scored and the hits, no
    host_fallback; thresholds and query rows are operands, so twelve
    more searches compile nothing; the counters moved."""
    from pilosa_tpu.api import API
    from pilosa_tpu.net import serve

    h, fp, edge = data
    eng = MeshEngine(h, make_mesh(1))
    eng.result_memo.maxsize = 0
    api = API(holder=h, mesh_engine=eng)
    srv, _thread = serve(api, port=0)
    uri = f"http://localhost:{srv.server_address[1]}/index/c/query"

    def post(text, profile=False):
        req = urllib.request.Request(uri + ("?profile=1" if profile else ""),
                                     data=text.encode(), method="POST")
        return json.loads(urllib.request.urlopen(req, timeout=120).read())

    try:
        doc = post(f"TopN(fp, Row(fp={edge}), tanimotoThreshold=49)", profile=True)
        want = reference(fp, edge, 49)
        assert [(p["id"], p["count"]) for p in doc["results"][0]] == want
        ops = [op for op in doc["plan"]["ops"] if "path" in op]
        assert len(ops) == 1 and ops[0]["op"] == "TopN" and ops[0]["path"] == "tanimoto"
        assert ops[0]["rows"] == len(fp) and ops[0]["hits"] == len(want)
        compiled = kernels.tanimoto_topn._cache_size()
        scored = REGISTRY.counter(METRIC_ENGINE_TOPN_ROWS_SCORED).get()
        hits = REGISTRY.counter(METRIC_ENGINE_TOPN_HITS).get()
        total = 0
        for q, t in zip((3, 99, 777, 1500, 2222, 2800, edge, edge + 2, 40, 41, 42, 43),
                        (70, 75, 80, 85, 90, 50, 50, 50, 10, 99, 1, 65)):
            got = post(f"TopN(fp, Row(fp={q}), tanimotoThreshold={t})")["results"][0]
            assert [(p["id"], p["count"]) for p in got] == reference(fp, q, t)
            total += len(got)
        assert kernels.tanimoto_topn._cache_size() == compiled
        assert REGISTRY.counter(METRIC_ENGINE_TOPN_ROWS_SCORED).get() == scored + 12 * len(fp)
        assert REGISTRY.counter(METRIC_ENGINE_TOPN_HITS).get() == hits + total
    finally:
        srv.shutdown()
        eng.close()


def test_narrow_width_is_the_largest_column_rounded_up_to_4096_bits():
    assert [frag_mod.narrow_words(c) for c in (0, 4095, 4096, 10000, SHARD_WIDTH - 1)] == [
        128, 128, 256, 384, 32768]


def test_a_field_too_deep_for_a_dense_stack_answers_without_one(monkeypatch):
    """2,000 rows: 262 MB as a dense stack or mirror, over a 16 MB device
    budget and mirror bound.  Row, Count(Row) and Count(Intersect) answer
    on the host (the engine's residency declines, counted as host
    fallbacks), a Row upload is one row, and the Tanimoto TopN answers on
    the narrow form (2 MB); no path asks for the dense stack or the
    fragment's dense mirror."""
    monkeypatch.setattr(frag_mod.Fragment, "DENSE_MIRROR_MAX_BYTES", 16 << 20)

    def no_mirror(self):
        raise AssertionError(f"dense mirror of {self.field} asked for")

    real_assemble = _Engine._assemble_host

    def no_dense_stack(self, index, field, view, canonical):
        if field == "deep":
            raise AssertionError("dense stack of deep asked for")
        return real_assemble(self, index, field, view, canonical)

    monkeypatch.setattr(frag_mod.Fragment, "_sync_device", no_mirror)
    monkeypatch.setattr(_Engine, "_assemble_host", no_dense_stack)
    rng = np.random.default_rng(11)
    fp = rng.random((2000, BITS)) < 0.08
    fp[6] = fp[5] ^ (rng.random(BITS) < 0.02)
    h = Holder()
    h.open()
    rows, cols = np.nonzero(fp)
    h.create_index("c").create_field("deep", FieldOptions(cache_size=5000)).import_bulk(
        rows.tolist(), cols.tolist())
    eng = MeshEngine(h, make_mesh(1), max_resident_bytes=16 << 20)
    eng.result_memo.maxsize = 0
    ex = Executor(h, mesh_engine=eng)
    try:
        fallbacks = eng.host_fallbacks
        res = ex.execute("c", "Row(deep=5) Count(Row(deep=6)) "
                         "Count(Intersect(Row(deep=5), Row(deep=6)))").results
        assert res[0].columns().tolist() == np.flatnonzero(fp[5]).tolist()
        assert res[1] == int(fp[6].sum()) and res[2] == int((fp[5] & fp[6]).sum())
        assert eng.host_fallbacks > fallbacks
        got, ops = run(ex, 5, field="deep")(60)
        assert got == reference(fp, 5, 60) and ops[0]["path"] == "tanimoto"
    finally:
        eng.close()
        h.close()

"""High-throughput ingest: differential discipline (docs/ingest.md).

The vectorized bulk paths (sort-once bulk_import, two-merge
import_values, packed-key import_roaring, vectorized roaring decode)
must be BIT-EXACT against the retained pre-PR per-row implementations
(bulk_import_rowloop / import_roaring_rowloop) and against per-bit
set_bit/clear_bit oracles on randomized batches — including mutex
last-write-wins, clear imports, occupancy-bitmap exactness after the
pipelined device sync, and the codec fuzz round-trip of the vectorized
decode vs the scalar oracle."""

import numpy as np
import pytest

from pilosa_tpu import pql
from pilosa_tpu.core import Fragment, SHARD_WIDTH
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.parallel import MeshEngine, make_mesh
from pilosa_tpu.roaring import codec
from pilosa_tpu.util import fanout as fanout_mod
from pilosa_tpu.util.stats import REGISTRY


def make_frag(**kw):
    return Fragment("i", "f", "standard", 0, path=None, **kw)


def frag_state(f):
    return {r: f.row_positions(r).tolist() for r in f.row_ids()}


def assert_twins(a, b):
    """Full storage equality incl. counts, occupancy, and mutex owners."""
    assert a.row_ids() == b.row_ids()
    for r in a.row_ids():
        assert np.array_equal(a.row_positions(r), b.row_positions(r)), r
        assert a.row_count(r) == b.row_count(r), r
        assert a.row_occupancy(r) == b.row_occupancy(r), r


# -- bulk_import ------------------------------------------------------------


@pytest.mark.parametrize("seed", range(4))
def test_bulk_import_differential_vs_rowloop(seed):
    rng = np.random.default_rng(seed)
    n = 3000
    rows = rng.integers(0, 60, n)
    cols = rng.integers(0, SHARD_WIDTH, n)
    a, b = make_frag(), make_frag()
    assert a.bulk_import(rows, cols) == b.bulk_import_rowloop(
        rows.tolist(), cols.tolist()
    )
    assert_twins(a, b)
    # clear a random subset plus misses (absent rows/cols)
    sel = rng.random(n) < 0.5
    crows = np.concatenate([rows[sel], rng.integers(90, 99, 50)])
    ccols = np.concatenate([cols[sel], rng.integers(0, SHARD_WIDTH, 50)])
    assert a.bulk_import(crows, ccols, clear=True) == b.bulk_import_rowloop(
        crows.tolist(), ccols.tolist(), clear=True
    )
    assert_twins(a, b)


def test_bulk_import_vs_per_bit_oracle():
    rng = np.random.default_rng(7)
    rows = rng.integers(0, 8, 500)
    cols = rng.integers(0, 4096, 500)
    a, b = make_frag(), make_frag()
    changed = a.bulk_import(rows, cols)
    oracle = sum(b.set_bit(int(r), int(c)) for r, c in zip(rows, cols))
    assert changed == oracle
    assert_twins(a, b)


def test_bulk_import_accepts_numpy_arrays():
    # Satellite fix: arrays no longer round-trip through a python list.
    rows = np.arange(10, dtype=np.int64)
    cols = np.arange(10, dtype=np.int64) * 7
    a, b = make_frag(), make_frag()
    assert a.bulk_import(rows, cols) == 10
    assert b.bulk_import(rows.tolist(), cols.tolist()) == 10
    assert_twins(a, b)


def test_bulk_import_dense_rows_word_delta_path():
    """Rows past SPARSE_MAX take the dense word-delta branch; counts and
    positions must stay exact through promote + further merges."""
    rng = np.random.default_rng(3)
    a, b = make_frag(), make_frag()
    for _ in range(3):
        cols = rng.integers(0, 40000, 3000)  # 3k bits in one row: promotes
        rows = np.zeros(cols.size, dtype=np.int64)
        assert a.bulk_import(rows, cols) == b.bulk_import_rowloop(
            rows.tolist(), cols.tolist()
        )
    assert_twins(a, b)
    # and clear back below the demote threshold
    pos = a.row_positions(0)
    half = pos[: pos.size // 2].astype(np.int64)
    assert a.bulk_import(
        np.zeros(half.size, dtype=np.int64), half, clear=True
    ) == b.bulk_import_rowloop([0] * half.size, half.tolist(), clear=True)
    assert_twins(a, b)


def test_bulk_import_mutex_last_write_wins():
    rng = np.random.default_rng(11)
    n = 1200
    rows = rng.integers(0, 20, n)
    cols = rng.integers(0, 2000, n)  # heavy column collisions
    a, b = make_frag(mutex=True), make_frag(mutex=True)
    c = make_frag(mutex=True)
    assert a.bulk_import(rows, cols) == b.bulk_import_rowloop(
        rows.tolist(), cols.tolist()
    )
    for r, col in zip(rows.tolist(), cols.tolist()):
        c.set_bit(r, col)  # per-bit mutex oracle
    assert_twins(a, b)
    assert_twins(a, c)
    for col in np.unique(cols).tolist():
        assert a.row_containing(col) == c.row_containing(col)
    # a second batch reassigning columns must clear previous owners
    rows2 = rng.integers(0, 20, n)
    assert a.bulk_import(rows2, cols) == b.bulk_import_rowloop(
        rows2.tolist(), cols.tolist()
    )
    assert_twins(a, b)


# -- import_values / set_value / clear_value --------------------------------


@pytest.mark.parametrize("clear", [False, True])
def test_import_values_differential(clear):
    rng = np.random.default_rng(5)
    depth = 8
    n = 800
    cols = rng.integers(0, 5000, n)
    vals = rng.integers(0, 1 << depth, n)
    a, b = make_frag(), make_frag()
    if clear:  # seed both with values so the clear has bits to remove
        a.import_values(cols, vals, depth)
        b.import_values(cols.tolist(), vals.tolist(), depth)
    a.import_values(cols, vals, depth, clear=clear)
    # oracle: per-column plane writes with last-write-wins dedup
    last = {}
    for col, v in zip(cols.tolist(), vals.tolist()):
        last[col] = v
    for col, v in last.items():
        for i in range(depth):
            if (v >> i) & 1:
                b.set_bit(i, col)
            else:
                b.clear_bit(i, col)
        if clear:
            b.clear_bit(depth, col)
        else:
            b.set_bit(depth, col)
    assert_twins(a, b)


def test_set_value_then_read():
    f = make_frag()
    assert f.set_value(100, 8, 177)
    assert f.value(100, 8) == (177, True)
    f.set_value(100, 8, 12)
    assert f.value(100, 8) == (12, True)
    assert not f.set_value(100, 8, 12)  # idempotent re-set: no change


def test_clear_value_clears_all_planes():
    """Reference semantics (fragment.go clearValue calls setValueBase
    with value=0): clearing removes the value's PLANE bits, not just the
    not-null bit — previously the planes were re-written like set."""
    f = make_frag()
    f.set_value(100, 8, 0xFF)
    f.set_value(200, 8, 0xFF)
    assert f.clear_value(100, 8, 0xFF)
    assert f.value(100, 8) == (0, False)
    for i in range(9):
        assert not f.bit(i, 100), f"plane {i} bit survived clear_value"
    # the sibling column's planes are untouched
    assert f.value(200, 8) == (0xFF, True)
    assert not f.clear_value(100, 8, 0xFF)  # already clear: no change


# -- import_roaring ---------------------------------------------------------


@pytest.mark.parametrize("seed", range(3))
def test_import_roaring_differential(seed):
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, 50, 4000).astype(np.uint64)
    cols = rng.integers(0, SHARD_WIDTH, 4000).astype(np.uint64)
    vals = np.unique((rows << np.uint64(20)) | cols)
    data = codec.serialize(vals)
    a, b = make_frag(), make_frag()
    assert a.import_roaring(data) == b.import_roaring_rowloop(data)
    assert_twins(a, b)
    # clear import: remove a subset (plus keys that miss entirely)
    sub = np.unique(
        np.concatenate(
            [vals[:: 3], (np.uint64(77) << np.uint64(20)) + np.arange(5, dtype=np.uint64)]
        )
    )
    cdata = codec.serialize(sub)
    assert a.import_roaring(cdata, clear=True) == b.import_roaring_rowloop(
        cdata, clear=True
    )
    assert_twins(a, b)


def test_import_roaring_predecoded_values():
    vals = np.asarray([1, 2, (5 << 20) | 9], dtype=np.uint64)
    data = codec.serialize(vals)
    a, b = make_frag(), make_frag()
    assert a.import_roaring(data, values=codec.deserialize(data).values) == 3
    assert b.import_roaring(data) == 3
    assert_twins(a, b)


# -- codec fuzz -------------------------------------------------------------


@pytest.mark.parametrize("seed", range(6))
def test_codec_decode_fuzz_np_vs_scalar(seed):
    """Randomized container mixes (array/run/bitmap per 65k key range)
    plus a random op-log tail: the vectorized decoder must match the
    scalar oracle exactly, values and op_n both."""
    rng = np.random.default_rng(seed)
    pieces = []
    for key in range(int(rng.integers(1, 6))):
        kind = rng.integers(0, 3)
        if kind == 0:  # array
            lows = rng.choice(1 << 16, size=int(rng.integers(1, 3000)), replace=False)
        elif kind == 1:  # run
            start = int(rng.integers(0, 1000))
            lows = np.arange(start, start + int(rng.integers(4100, 9000)))
        else:  # bitmap
            lows = rng.choice(1 << 16, size=6000, replace=False)
        pieces.append(
            (np.uint64(key) << np.uint64(16)) | np.sort(lows).astype(np.uint64)
        )
    vals = np.unique(np.concatenate(pieces))
    data = codec.serialize(vals)
    ops = []
    for _ in range(int(rng.integers(0, 200))):
        typ = int(rng.integers(0, 2))
        v = int(rng.integers(0, 6 << 16))
        ops.append(codec.encode_op(typ, v))
    blob = data + b"".join(ops)
    d_np = codec._deserialize_np(blob)
    d_py = codec._deserialize_py(blob)
    assert d_np.op_n == d_py.op_n
    assert np.array_equal(d_np.values, d_py.values)


def test_codec_decode_corruption_parity():
    vals = np.arange(100, dtype=np.uint64)
    data = codec.serialize(vals)
    blob = data + codec.encode_op(0, 500)
    # torn tail raises in both decoders
    for cut in (3, 7, 12):
        with pytest.raises(ValueError):
            codec._deserialize_np(blob[:-cut])
        with pytest.raises(ValueError):
            codec._deserialize_py(blob[:-cut])
    # corrupt op checksum
    bad = blob[:-1] + bytes([blob[-1] ^ 0xFF])
    with pytest.raises(ValueError):
        codec._deserialize_np(bad)
    with pytest.raises(ValueError):
        codec._deserialize_py(bad)
    # deserialize() (the serving entry) routes through the vectorized path
    assert np.array_equal(
        codec.deserialize(blob).values, codec._deserialize_py(blob).values
    )


# -- pipelined device sync --------------------------------------------------


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(8)


def _stack_occ_expected(holder, index, field, view, stack):
    want = np.zeros_like(stack.occ)
    for si, s in enumerate(stack.shards):
        frag = holder.fragment(index, field, view, s)
        if frag is None:
            continue
        for r, ri in stack.row_index.items():
            want[ri, si] = np.uint64(frag.row_occupancy(r))
    return want


def test_ingest_syncer_occupancy_exact(mesh):
    """Chunks applied through the ingest sync worker leave the resident
    stack's words AND occupancy bitmaps exactly equal to host truth —
    and never force a rebuild once the row table is stable."""
    holder = Holder()
    holder.open()
    idx = holder.create_index("i")
    f = idx.create_field("f")
    rng = np.random.default_rng(2)
    n_shards = 4
    # seed all rows so the stack row table is stable
    rows, cols = [], []
    for s in range(n_shards):
        for r in range(16):
            rows.append(r)
            cols.append((s << 20) + r)
    f.import_bulk(rows, cols)
    eng = MeshEngine(holder, mesh)
    call = pql.parse("Intersect(Row(f=1), Row(f=2))").calls[0]
    shards = list(range(n_shards))
    eng.count("i", call, shards)  # builds the stack
    syncer = eng.ingest_syncer()
    rebuilds0 = eng.stack_rebuilds
    for _ in range(5):
        n = 600
        brows = rng.integers(0, 16, n).tolist()
        bcols = (
            rng.integers(0, n_shards, n) * (1 << 20)
            + rng.integers(0, 1 << 20, n)
        ).tolist()
        f.import_bulk(brows, bcols)
        syncer.notify("i")
    assert syncer.flush(timeout=30)
    assert eng.stack_rebuilds == rebuilds0
    assert syncer.chunks == 5
    stack = eng.field_stack("i", "f", "standard")
    mat = np.asarray(stack.matrix)
    for s in range(n_shards):
        frag = holder.fragment("i", "f", "standard", s)
        for r, ri in stack.row_index.items():
            assert np.array_equal(mat[ri, s], frag.row_words(r)), (r, s)
    assert np.array_equal(stack.occ, _stack_occ_expected(
        holder, "i", "f", "standard", stack
    ))
    eng.close()


def test_ingest_syncer_coalesces_and_closes(mesh):
    holder = Holder()
    holder.open()
    idx = holder.create_index("c")
    idx.create_field("f").import_bulk([1, 2], [3, 4])
    eng = MeshEngine(holder, mesh)
    syncer = eng.ingest_syncer()
    # No resident stacks: notifies drain as no-op syncs, never block.
    for _ in range(4):
        syncer.notify("c")
    assert syncer.flush(timeout=10)
    snap = syncer.snapshot()
    assert snap["chunks"] == 4 and snap["pending"] == 0
    eng.close()  # close() stops the worker
    syncer.notify("c")  # after close: ignored, no deadlock
    assert syncer.flush(timeout=2)


# -- API surface: metrics, fan-out, existence ------------------------------


def _counter(name, **labels):
    c = REGISTRY.counter(name, **labels)
    return c.get()


def test_api_ingest_metrics_and_notify(mesh):
    from pilosa_tpu.api import API, ImportRequest, ImportValueRequest
    from pilosa_tpu.core.field import FieldOptions

    holder = Holder()
    holder.open()
    idx = holder.create_index("m")
    idx.create_field("f")
    idx.create_field("v", FieldOptions(type="int", min=0, max=255))
    eng = MeshEngine(holder, mesh)
    api = API(holder=holder, mesh_engine=eng)
    b0 = _counter("pilosa_ingest_batches_total", path="bits")
    r0 = _counter("pilosa_ingest_batches_total", path="roaring")
    v0 = _counter("pilosa_ingest_batches_total", path="values")
    api.import_bits(ImportRequest("m", "f", row_ids=[1, 1], column_ids=[5, 9]))
    api.import_values(
        ImportValueRequest("m", "v", column_ids=[1, 2], values=[7, 9])
    )
    vals = np.asarray([(2 << 20) | 5], dtype=np.uint64)
    n = api.import_roaring("m", "f", 0, codec.serialize(vals))
    assert n == 1
    assert _counter("pilosa_ingest_batches_total", path="bits") == b0 + 1
    assert _counter("pilosa_ingest_batches_total", path="roaring") == r0 + 1
    assert _counter("pilosa_ingest_batches_total", path="values") == v0 + 1
    syncer = eng.ingest_syncer()
    assert syncer.chunks >= 3  # every import notified the sync worker
    # roaring import also fed the existence field from the SAME decode
    ef = idx.existence_field()
    if ef is not None:
        assert ef.row(0).count() >= 1
    eng.close()


@pytest.mark.parametrize("width", [1, 4])
def test_field_import_multi_shard_fanout(width, monkeypatch):
    monkeypatch.setattr(fanout_mod, "DEFAULT_IMPORT_FANOUT", width)
    holder = Holder()
    holder.open()
    idx = holder.create_index(f"fan{width}")
    f = idx.create_field("f")
    rng = np.random.default_rng(9)
    rows = rng.integers(0, 30, 5000)
    cols = rng.integers(0, 6 << 20, 5000)  # spans 6 shards
    changed = f.import_bulk(rows.tolist(), cols.tolist())
    # serial oracle on a twin field
    g = idx.create_field("g")
    want = 0
    for s in np.unique(cols // SHARD_WIDTH).tolist():
        sel = (cols // SHARD_WIDTH) == s
        frag = g.view_if_not_exists("standard").fragment_if_not_exists(int(s))
        want += frag.bulk_import_rowloop(
            rows[sel].tolist(), cols[sel].tolist()
        )
    assert changed == want
    for s in np.unique(cols // SHARD_WIDTH).tolist():
        fa = f.view_if_not_exists("standard").fragments[int(s)]
        fb = g.view_if_not_exists("standard").fragments[int(s)]
        assert frag_state(fa) == frag_state(fb)


def test_cluster_import_bits_accepts_numpy_arrays(tmp_path):
    """The cluster fan-out paths must serialize numpy inputs: the
    per-shard slices go through InternalClient's json.dumps, which
    rejects np.int64 scalars — list(ndarray) kept them, .tolist()
    converts (arrays are the documented import-request surface).
    Covers bits (ids + timestamps) and values."""
    from pilosa_tpu.api import ImportRequest, ImportValueRequest
    from pilosa_tpu.core.field import FieldOptions
    from harness import run_cluster

    h = run_cluster(tmp_path, 2)
    try:
        client = h.client(0)
        client.create_index("npi")
        client.create_field("npi", "f")
        cols = np.array(
            [1, SHARD_WIDTH + 2, 2 * SHARD_WIDTH + 3, 5 * SHARD_WIDTH + 4],
            dtype=np.int64,
        )
        rows = np.full(cols.size, 10, dtype=np.int64)
        # In-process API call with arrays while a cluster is attached:
        # some shard groups fan out over HTTP to node 1.
        h[0].api.import_bits(
            ImportRequest("npi", "f", row_ids=rows, column_ids=cols)
        )
        res = client.query("npi", "Count(Row(f=10))")
        assert res["results"][0] == cols.size
        # time field + numpy timestamps ride the same fan-out
        h[0].api.create_field(
            "npi", "t", FieldOptions(type="time", time_quantum="YMD")
        )
        ts = np.full(cols.size, 1136188800000000000, dtype=np.int64)
        h[0].api.import_bits(
            ImportRequest(
                "npi", "t", row_ids=rows, column_ids=cols, timestamps=ts
            )
        )
        assert client.query("npi", "Count(Row(t=10))")["results"][0] == (
            cols.size
        )
        # int field + numpy values
        h[0].api.create_field(
            "npi", "v", FieldOptions(type="int", min=0, max=255)
        )
        h[0].api.import_values(
            ImportValueRequest(
                "npi", "v", column_ids=cols,
                values=np.full(cols.size, 7, dtype=np.int64),
            )
        )
        out = client.query("npi", "Sum(field=v)")["results"][0]
        assert out == {"value": 7 * cols.size, "count": cols.size}
    finally:
        h.close()

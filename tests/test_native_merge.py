"""Native sparse-merge + array-native rank cache: differential fuzz
coverage (docs/ingest.md).

Three implementations of the bulk-ingest merge must stay bit-exact:
the C++ kernels (native/sparse_merge.cpp), the numpy fallback
(RowStore._merge_np and friends), and the retained pre-vectorization
rowloop oracle (Fragment.bulk_import_rowloop).  The array-native
RankCache must match the dict-based reference semantics (with the
zero-pops fix) across admission thresholds, the 1.1x trim, debounce,
and top() tie ordering.
"""

import numpy as np
import pytest

from pilosa_tpu import native
from pilosa_tpu.core import cache as cache_mod, rowstore
from pilosa_tpu.core.cache import RankCache, pair_sort_key, THRESHOLD_FACTOR
from pilosa_tpu.core.fragment import Fragment
from pilosa_tpu.util.stats import METRIC_CACHE_RECALC, REGISTRY

HAVE_NATIVE = native.load_merge() is not None


@pytest.fixture
def rng():
    return np.random.default_rng(1234)


@pytest.fixture
def use_numpy_merge(monkeypatch):
    """Force the numpy fallback regardless of toolchain (simulates a
    missing .so without touching the filesystem)."""
    monkeypatch.setattr(rowstore, "_MERGE", False)
    yield
    # monkeypatch restores _MERGE; nothing cached beyond the module var.


def _rand_batch(rng, n_bits, n_rows, span=1 << 20):
    rows = rng.integers(0, n_rows, n_bits).astype(np.int64)
    cols = rng.integers(0, span, n_bits).astype(np.int64)
    return rows, cols


def _assert_fragments_equal(fa: Fragment, fb: Fragment, ctx=""):
    assert fa.row_ids() == fb.row_ids(), ctx
    for r in fa.row_ids():
        np.testing.assert_array_equal(
            fa.row_positions(r), fb.row_positions(r), err_msg=f"{ctx} row {r}"
        )
        assert fa.row_count(r) == fb.row_count(r), (ctx, r)
    assert sorted(fa.cache.top()) == sorted(fb.cache.top()), ctx


# ---- merge differential: native == numpy == rowloop oracle ---------------


@pytest.mark.parametrize(
    "n_rows,mutex",
    [(4, False), (64, False), (5000, False), (48, True)],
    ids=["dense-promote", "mid", "sparse-wide", "mutex-lww"],
)
def test_bulk_import_three_way_differential(rng, monkeypatch, n_rows, mutex):
    """bulk_import (native when available) == bulk_import (numpy
    fallback) == bulk_import_rowloop, across unions, clears, fresh rows
    and dense<->sparse promotions, on the same random data."""
    frags = [
        Fragment("t", "f", "standard", 0, mutex=mutex) for _ in range(3)
    ]
    for i in range(6):
        n_bits = int(rng.integers(2000, 30000))
        rows, cols = _rand_batch(rng, n_bits, n_rows)
        clear = (not mutex) and i in (3, 5)
        changed = []
        for k, f in enumerate(frags):
            monkeypatch.setattr(rowstore, "_MERGE", False if k == 1 else None)
            if k == 2:
                changed.append(f.bulk_import_rowloop(rows, cols, clear=clear))
            else:
                changed.append(f.bulk_import(rows, cols, clear=clear))
        assert changed[0] == changed[1] == changed[2], (i, changed)
        _assert_fragments_equal(frags[0], frags[1], f"native-vs-numpy {i}")
        _assert_fragments_equal(frags[0], frags[2], f"native-vs-rowloop {i}")


def test_import_roaring_differential(rng, monkeypatch):
    from pilosa_tpu.roaring import codec

    fa = Fragment("t", "f", "standard", 0)
    fb = Fragment("t", "f", "standard", 0)
    for i in range(3):
        rows = rng.integers(0, 700, 20000).astype(np.uint64)
        cols = rng.integers(0, 1 << 20, 20000).astype(np.uint64)
        vals = np.unique((rows << np.uint64(20)) | cols)
        data = codec.serialize(vals)
        monkeypatch.setattr(rowstore, "_MERGE", None)
        ca = fa.import_roaring(data, clear=i == 2)
        cb = fb.import_roaring_rowloop(data, clear=i == 2)
        assert ca == cb, i
    _assert_fragments_equal(fa, fb, "roaring")


def test_fallback_is_automatic_when_loader_absent(rng, monkeypatch):
    """With the loader returning None (no .so), the numpy path engages
    transparently and stays bit-exact with a natively-built fragment."""
    rows, cols = _rand_batch(rng, 8000, 100)
    fa = Fragment("t", "f", "standard", 0)
    monkeypatch.setattr(native, "load_merge", lambda: None)
    monkeypatch.setattr(rowstore, "_MERGE", None)  # force re-resolve
    assert rowstore._merge_lib() is None
    fa.bulk_import(rows, cols)
    monkeypatch.undo()
    fb = Fragment("t", "f", "standard", 0)
    fb.bulk_import(rows, cols)
    _assert_fragments_equal(fa, fb, "loader-absent")


@pytest.mark.skipif(not HAVE_NATIVE, reason="no C++ toolchain")
def test_shard_split_native_matches_argsort(rng, monkeypatch):
    """field._shard_groups: the native counting sort and the argsort
    fallback produce identical (shard, slices) groupings, including
    within-shard order (last-write-wins depends on it)."""
    from pilosa_tpu.core.holder import Holder

    rows = rng.integers(0, 500, 40000).astype(np.int64)
    cols = rng.integers(0, 6 << 20, 40000).astype(np.int64)

    def groups_of(field):
        return [
            (f.shard, c.tolist(), r.tolist())
            for f, c, r in type(field)._shard_groups(
                field.view_if_not_exists("standard"), cols, rows
            )
        ]

    holder = Holder()
    holder.open()
    idx = holder.create_index("split")
    fa, fb = idx.create_field("fa"), idx.create_field("fb")
    monkeypatch.setattr(rowstore, "_MERGE", None)
    ga = groups_of(fa)
    monkeypatch.setattr(rowstore, "_MERGE", False)
    gb = groups_of(fb)
    assert ga == gb
    holder.close()


def test_word_log_compaction_sync_exact(rng):
    """Sync correctness across word-log record compaction: a sync point
    older than the compacted records still ships every dirty word
    (over-stamped versions only re-ship idempotently, never drop)."""
    frag = Fragment("t", "f", "standard", 0)
    rows, cols = _rand_batch(rng, 4000, 32)
    frag.bulk_import(rows, cols)
    v0 = frag._version
    written = []
    for i in range(frag.WORD_LOG_RECORDS + 4):  # forces >=1 compaction
        r, c = int(rng.integers(0, 32)), int(rng.integers(0, 1 << 20))
        frag.set_bit(r, c)
        written.append((r, c))
    assert len(frag._word_log) < frag.WORD_LOG_RECORDS + 4  # compacted
    _, dirty = frag.sync_snapshot(v0)
    for r, c in written:
        upd = dirty[r]
        if upd[0] == "row":
            words = upd[1]
        else:
            _, widxs, vals, _ = upd
            assert np.all(np.diff(widxs) > 0)  # sorted unique at sync
            words = np.zeros(32768, dtype=np.uint32)
            words[widxs] = vals
        assert (int(words[c >> 5]) >> (c & 31)) & 1, (r, c)


# ---- RankCache: array-native == reference semantics ----------------------


class OracleRankCache:
    """The pre-array dict implementation, with the intended zero-pops
    semantics on every path (the bug the PR fixes)."""

    def __init__(self, max_entries):
        self.max_entries = max_entries
        self.threshold_buffer = int(THRESHOLD_FACTOR * max_entries)
        self.threshold_value = 0
        self.entries = {}
        self.rankings = []

    def _put(self, row_id, n):
        if n < self.threshold_value and n > 0:
            return
        if n == 0:
            self.entries.pop(row_id, None)
        else:
            self.entries[row_id] = n

    def add(self, row_id, n):
        # Early return BEFORE the recalculate, exactly like the
        # original: a rejected add does not refresh the rankings.
        if n < self.threshold_value and n > 0:
            return
        self._put(row_id, n)
        self.recalculate()

    bulk_add = _put

    def bulk_update(self, row_ids, counts):
        for r, n in zip(
            np.asarray(row_ids).tolist(), np.asarray(counts).tolist()
        ):
            self._put(r, n)

    def invalidate(self):
        self.recalculate()

    def recalculate(self):
        rankings = sorted(self.entries.items(), key=pair_sort_key)
        remove = []
        if len(rankings) > self.max_entries:
            self.threshold_value = rankings[self.max_entries][1]
            remove = rankings[self.max_entries :]
            rankings = rankings[: self.max_entries]
        else:
            self.threshold_value = 1
        self.rankings = rankings
        if len(self.entries) > self.threshold_buffer:
            for rid, _ in remove:
                self.entries.pop(rid, None)

    def top(self):
        return self.rankings

    def get(self, r):
        return self.entries.get(r, 0)

    def ids(self):
        return sorted(self.entries)

    def __len__(self):
        return len(self.entries)


def test_rank_cache_fuzz_parity(rng):
    """Array-native RankCache == the dict reference across scalar adds,
    rowloop-style bulk_adds, vectorized bulk_updates (monotone and not),
    zero clears, admission thresholds, trim at 1.1x, and top()
    tie-break ordering — after every step."""
    for trial in range(25):
        k = int(rng.integers(1, 40))
        a = RankCache(k, debounce_seconds=0)
        b = OracleRankCache(k)
        for step in range(40):
            op = int(rng.integers(0, 4))
            if op == 0:
                rid, n = int(rng.integers(0, 200)), int(rng.integers(0, 30))
                a.add(rid, n)
                b.add(rid, n)
            elif op == 1:
                for _ in range(int(rng.integers(1, 8))):
                    rid = int(rng.integers(0, 200))
                    n = int(rng.integers(0, 30))
                    a.bulk_add(rid, n)
                    b.bulk_add(rid, n)
                a.invalidate()
                b.invalidate()
            elif op == 2:  # arbitrary bulk (may shrink counts / clear)
                ids = np.unique(rng.integers(0, 200, int(rng.integers(1, 50))))
                cnts = rng.integers(0, 40, ids.size)
                a.bulk_update(ids, cnts)
                b.bulk_update(ids, cnts)
                a.invalidate()
                b.invalidate()
            else:  # monotone growth: exercises the incremental merge path
                ids = np.unique(rng.integers(0, 200, int(rng.integers(1, 50))))
                cnts = np.array(
                    [b.get(int(i)) + int(rng.integers(1, 5)) for i in ids]
                )
                a.bulk_update(ids, cnts)
                b.bulk_update(ids, cnts)
                a.invalidate()
                b.invalidate()
            assert a.top() == b.top(), (trial, step)
            assert a.threshold_value == b.threshold_value, (trial, step)
            assert a.ids() == b.ids(), (trial, step)
            assert len(a) == len(b), (trial, step)


def test_rank_cache_zero_pops_on_every_path():
    """Regression (the bulk_add zero-drop bug): a count of zero evicts
    the entry on the scalar, bulk_add, AND masked bulk_update paths —
    even when the admission threshold is positive."""
    for path in ("add", "bulk_add", "bulk_update"):
        c = RankCache(3, debounce_seconds=0)
        for i in range(10):
            c.bulk_add(i, i + 1)
        c.recalculate()
        assert c.threshold_value == 7  # 0 would be admitted, 1..6 not
        assert c.get(9) == 10
        if path == "add":
            c.add(9, 0)
        elif path == "bulk_add":
            c.bulk_add(9, 0)
        else:
            c.bulk_update(np.array([9]), np.array([0]))
        c.recalculate()
        assert c.get(9) == 0, path
        assert 9 not in c.ids(), path
        assert all(rid != 9 for rid, _ in c.top()), path


def test_rank_cache_cleared_row_evicted_through_fragment(rng):
    """End-to-end: a row cleared during a bulk import leaves the
    fragment's ranked cache (pre-fix it survived with a stale count)."""
    frag = Fragment("t", "f", "standard", 0)
    rows, cols = _rand_batch(rng, 2000, 8)
    frag.bulk_import(rows, cols)
    target = frag.row_ids()[0]
    assert any(rid == target for rid, _ in frag.cache.top())
    pos = frag.row_positions(target).astype(np.int64)
    frag.bulk_import(
        np.full(pos.size, target, dtype=np.int64), pos, clear=True
    )
    assert frag.row_count(target) == 0
    assert all(rid != target for rid, _ in frag.cache.top())
    assert frag.cache.get(target) == 0


def test_rank_cache_debounce():
    c = RankCache(10, debounce_seconds=60.0)
    c.add(1, 5)  # first recalculate stamps _update_time
    c.add(2, 9)  # debounced: rankings stay stale
    assert c.top() == [(1, 5)]
    c.recalculate()
    assert c.top() == [(2, 9), (1, 5)]


def test_rank_cache_no_python_sorted_on_bulk_path(rng, monkeypatch):
    """The bulk-import maintenance path must not fall back to python
    sorted() over the entries (the pre-PR recalculate)."""
    import builtins

    c = RankCache(1000, debounce_seconds=0)
    ids = np.arange(500, dtype=np.int64)
    c.bulk_update(ids, rng.integers(1, 100, 500))
    c.recalculate()

    def banned(*a, **k):  # pragma: no cover - failure path
        raise AssertionError("python sorted() on the bulk path")

    monkeypatch.setattr(builtins, "sorted", banned)
    c.bulk_update(ids, rng.integers(100, 200, 500))
    c.invalidate()
    assert len(c.top()) == 500


def test_cache_maintenance_metrics():
    hist = REGISTRY.get_histogram(METRIC_CACHE_RECALC, path="full")
    before = hist.export()[2]
    c = RankCache(10, debounce_seconds=0)
    c.bulk_update(np.arange(5), np.arange(1, 6))
    c.recalculate()
    assert hist.export()[2] > before
    cache_mod.refresh_entries_gauges()
    snap = REGISTRY.snapshot()["gauges"]["pilosa_cache_entries"]
    assert snap.get("cache_type=ranked", 0) >= 5


@pytest.mark.skipif(not HAVE_NATIVE, reason="no C++ toolchain")
def test_shard_split_native_wide_span(rng, monkeypatch):
    """A batch touching few DISTINCT shards that are far apart (span way
    past the direct-index table bound) must still take the native path —
    the sparse distinct-shard table — and match the argsort fallback
    exactly, within-shard order included."""
    from pilosa_tpu.core.field import Field
    from pilosa_tpu.core.holder import Holder

    far = (Field._NATIVE_SPLIT_MAX_SHARDS + 7) << 20
    n = 20000
    pick = rng.random(n) < 0.5
    cols = np.where(
        pick,
        rng.integers(0, 1 << 20, n),
        rng.integers(far, far + (1 << 20), n),
    ).astype(np.int64)
    rows = rng.integers(0, 50, n).astype(np.int64)

    def groups_of(field):
        return [
            (f.shard, c.tolist(), r.tolist())
            for f, c, r in type(field)._shard_groups(
                field.view_if_not_exists("standard"), cols, rows
            )
        ]

    holder = Holder()
    holder.open()
    idx = holder.create_index("wide")
    fa, fb = idx.create_field("fa"), idx.create_field("fb")
    monkeypatch.setattr(rowstore, "_MERGE", None)
    ga = groups_of(fa)
    monkeypatch.setattr(rowstore, "_MERGE", False)
    gb = groups_of(fb)
    assert ga == gb
    assert {s for s, _, _ in ga} == {0, Field._NATIVE_SPLIT_MAX_SHARDS + 7}
    holder.close()


def test_word_log_tiered_compaction_no_reship():
    """Tail compaction must not restamp already-synced history: a
    compacted record becomes a TIER that keeps its version, so an
    incremental sync after later compactions ships only words dirtied
    past the sync point (pre-tiering, every WORD_LOG_RECORDS batches
    restamped the whole accumulated log and the next sync reshipped
    it all)."""
    frag = Fragment("t", "f", "standard", 0)
    frag.set_bit(0, 32 * 7)  # device word 7
    for i in range(frag.WORD_LOG_RECORDS - 1):
        frag.set_bit(0, 32 * (100 + i))  # words 100..114
    assert frag._word_log_tiers == 1  # pre-sync history compacted
    v0, d0 = frag.sync_snapshot(0)
    assert 0 in d0  # everything shipped once
    for i in range(frag.WORD_LOG_RECORDS):
        frag.set_bit(0, 32 * (200 + i))  # words 200..215
    assert frag._word_log_tiers == 2  # second compaction tiered, not merged
    _, dirty = frag.sync_snapshot(v0)
    kind, widxs, _, _ = dirty[0]
    assert kind == "words"
    got = set(widxs.tolist())
    assert got == set(range(200, 200 + frag.WORD_LOG_RECORDS))
    assert 7 not in got and 100 not in got  # synced history NOT reshipped

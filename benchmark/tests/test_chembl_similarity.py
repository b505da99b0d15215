"""What chembl-similarity's files have to agree on, at the rehearsal's
size: the import bodies hold exactly the generated fingerprints, the
table's search (common word + posting lists, the threshold bound) is the
plain AND + popcount search over every molecule, and ``planes(key)``
reckons the scan in 128 KiB pages.  Fast, no JAX, no server."""

import functools
import importlib.util
import json
import os

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def _load(*parts):
    path = os.path.join(ROOT, "benchmark", *parts)
    spec = importlib.util.spec_from_file_location("bench_chem_" + parts[-1][:-3].replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


GEN = _load("configs", "chembl-similarity.py")
TEMPLATE = _load("queries", "chembl-similarity", "similar.py")
with open(os.path.join(ROOT, "benchmark", "configs", "chembl-similarity.json")) as f:
    CFG = json.load(f)
M = CFG["rehearsal"]["molecules"]
SEED = 2147483789


@functools.lru_cache(maxsize=1)
def table():
    """The reference at the rehearsal's size, made once (the module's
    cases are also collected by tests/test_benchmark_harness.py, which
    takes its ``test_`` functions and no fixture)."""
    offsets, positions = GEN.fingerprints(SEED, M)
    t = GEN.Table(CFG)
    t.add((offsets, positions))
    t.finish()
    return t


def test_the_bodies_hold_the_fingerprints_and_nothing_else():
    from pilosa_tpu.roaring import codec

    offsets, positions = GEN.fingerprints(SEED, M)
    lens = np.diff(offsets)
    assert lens.min() >= 20 and lens.max() <= GEN.ARRAY_MAX and M == lens.size
    got = []
    for lo in range(0, M, 4096):  # bodies of another cut decode to the same bits
        hi = min(lo + 4096, M)
        got.append(codec.deserialize(GEN.roaring_rows(lo, lens[lo:hi],
                                                      positions[offsets[lo]:offsets[hi]])).values)
    want = (np.repeat(np.arange(M, dtype=np.uint64), lens) << np.uint64(20)) + positions.astype(np.uint64)
    assert np.array_equal(np.concatenate(got), want)


@pytest.mark.parametrize("t", [1, 50, 70, 75, 80, 85, 90, 99, 100])
def test_the_search_is_the_plain_scan_at_every_threshold(t):
    rng = np.random.default_rng([SEED, t])
    for q in rng.integers(0, M, 12).tolist():
        assert table().search(q, t) == table().answer_dense(q, t), (q, t)


def test_answer_planes_and_draw_agree_with_the_configuration():
    ref = table()
    rng = np.random.default_rng(3)
    calls, key = TEMPLATE.draw(rng, dict(CFG, rehearsal={"molecules": M}))
    q, t, m = key
    assert calls == [f"TopN(fingerprint, Row(fingerprint={q}), tanimotoThreshold={t})"]
    assert t in TEMPLATE.THRESHOLDS and 0 <= q < m
    got = TEMPLATE.answer(ref, (q, t, M))
    assert got == [ref.answer_dense(q, t)] and {"id": q, "count": int(ref.counts[q])} in got[0]
    assert [len(p) for p in TEMPLATE.planes((q, t, 1_700_000))] == [6693]
    assert [len(p) for p in TEMPLATE.planes((q, t, M))] == [-(-M * 516 // 131072)]
    assert TEMPLATE.answer(ref, (M + 5, 70, M)) == [[]]

"""TopN(fingerprint, Row(fingerprint=<id>), tanimotoThreshold=<T>): the
molecules whose Tanimoto similarity to molecule <id> exceeds T, every
one of them (no n), id uniform over the molecules, T uniform over
{70, 75, 80, 85, 90}.

planes(key) reckons the search's bytes in the reader's 128 KiB unit: it
scores every molecule, so it reads the whole fingerprint matrix (4,096
bits = 512 B a molecule) and the count vector (4 B a molecule):
ceil(M x 516 / 131,072) pages, 6,693 at 1.7 M molecules.  This reckons
whole rows: a program that read only the query's non-zero words would
read less than it counts and show above 100 % of the roofline."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_configs_chembl_similarity",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "..", "configs",
                 "chembl-similarity.py"))
_cfg = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_cfg)

THRESHOLDS = (70, 75, 80, 85, 90)
PAGE_BYTES = 131072
ROW_BYTES = _cfg.BITS // 8 + 4


def draw(rng, schema):
    m = _cfg.molecules(schema)
    q, t = int(rng.integers(0, m)), int(rng.choice(THRESHOLDS))
    field = schema["fields"][0]["name"]
    return [f"TopN({field}, Row({field}={q}), tanimotoThreshold={t})"], (q, t, m)


def answer(table, key):
    q, t, _ = key
    return [table.search(q, t)]


def planes(key):
    pages = -(-key[2] * ROW_BYTES // PAGE_BYTES)
    return [{("fingerprint", k) for k in range(pages)}]

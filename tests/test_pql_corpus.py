"""The parser against a recorded corpus: every text's parse, value types
and ``Call.args`` order included, or the fact that it is rejected.

``tests/pql_corpus.jsonl`` was recorded with the character-stepped
parser the token scanner replaced, and a case fails on any difference
from it.  Its texts: the matrices of tests/test_pql.py and
tests/test_pql_spec.py plus a few grammar corners, 50 draws of every
query template under benchmark/queries/ at fixed seeds (the request body
the benchmark would send: its calls joined by a space), and seeded
single-character edits of all of them (a character deleted, duplicated
or swapped with the next) so that rejection is covered too.

Record it again only for a deliberate change of the grammar:
``PYTHONPATH=. python tests/test_pql_corpus.py`` rewrites the file with the parser
of the tree it runs in.
"""

import ast
import importlib.util
import json
import os
import sys

import pytest

from pilosa_tpu import pql
from pilosa_tpu.pql import Call, Condition

HERE = os.path.dirname(os.path.abspath(__file__))
CORPUS = os.path.join(HERE, "pql_corpus.jsonl")
DRAWS = 50  # per benchmark template


def canonical_value(v) -> str:
    # bool before int: True is an int to isinstance.
    if v is None:
        return "null"
    if isinstance(v, bool):
        return "true" if v else "false"
    if isinstance(v, int):
        return f"i:{v}"
    if isinstance(v, float):
        return f"f:{v!r}"
    if isinstance(v, str):
        return json.dumps(v)
    if isinstance(v, list):
        return "[" + ",".join(canonical_value(x) for x in v) + "]"
    if isinstance(v, Call):
        return canonical_call(v)
    if isinstance(v, Condition):
        return f"cond({v.op} {canonical_value(v.value)})"
    raise TypeError(f"unexpected value {v!r}")


def canonical_call(c: Call) -> str:
    """Name, children in order, then args in insertion order."""
    children = ", ".join(canonical_call(ch) for ch in c.children)
    args = ", ".join(f"{k}={canonical_value(v)}" for k, v in c.args.items())
    return f"{c.name}({children} | {args})"


def canonical(text: str):
    """A text's canonical parse: one string a call, or None if rejected."""
    try:
        q = pql.parse(text)
    except pql.ParseError:
        return None
    return [canonical_call(c) for c in q.calls]


def _load():
    with open(CORPUS) as f:
        return [json.loads(line) for line in f]


ENTRIES = _load() if os.path.exists(CORPUS) else []


@pytest.mark.parametrize("entry", ENTRIES, ids=[e["id"] for e in ENTRIES])
def test_corpus_entry(entry):
    assert canonical(entry["text"]) == entry["parse"]


def test_corpus_covers_acceptance_and_rejection():
    parsed = sum(e["parse"] is not None for e in ENTRIES)
    assert parsed > 700 and len(ENTRIES) - parsed > 300


# -- recording -----------------------------------------------------------------

# Corners of the grammar the test matrices leave out.
CORNERS = [
    "Row(f=null )", "Row(f=true\t)", "Row(f=false\n, g=1)", "Row(f=nullx)", "Row(f=truex)",
    "Row(f=[null, true, false])", "Row(f=[])", "Row(f=[ ])", "Row(f=[1,2, 3 ])", "Row(f=[1,])",
    "Row(f=12ab)", "Row(f=1.5x)", "Row(f=-3)", "Row(f=-3.)", "Row(f=.5)", "Row(f=-.5)",
    "Row(f=1.)", "Row(f=007)", "Row(f=0x1F)", "Row(f=a:b-c_d)", "Row(f=2010-01-01T00:00)",
    "Row(f=Row(g=1))", "Row(f=Row (g=1))", "Row(f=[Row(g=1), 2])", "Row(f = 1)",
    "Row(f='a\\'b')", 'Row(f="a\\\\b")', 'Row(f="a\\nb")', "Row(f='unterminated)",
    'Row(f="x", g=\'y\')', "Row(from=1, f=2)", "Rows(field=f, previous=3, limit=2)",
    "Row(_row=1, _col=2)", "Row(_field=f, x=1)", "Row(_start=1)", "Row(_x=1)",
    "Row(f=1,)", "Row(f=1 , )", "Row(f=1,,)", "Row(,)", "Row( )", "Row(\n)",
    "Count(Row(f=1),)", "Count(Row(f=1), )", "Count(Row(f=1), f=2,)",
    "Count(Row(f=1), f=2, Row(g=1))", "Count(f=2, Row(g=1))", "Count(Row(f=1) Row(g=1))",
    "Union(Row(a=1),Row(b=2),Row(c=3))", "A() B()C()", "  Row(f=1)  ", "\n\tRow(f=1)\r\n",
    "Row (f=1)", "Row(f=1))", "Row((f=1)", "row(f=1)", "R0w(f=1)", "1Row(f=1)", "Row_x(f=1)",
    "Row-x(f=1)", "Row(f-1=2)", "Row(f_1=2)", "Row(1f=2)", "Row(f==1)", "Row(f=>1)",
    "Row(f <> 1)", "Row(f >< [1, 2])", "Row(f >< 1)", "Row(f == null)",
    "Range(f=1)", "Range(f = 1)", "Range(f > 1)", "Range(f == Row(g=1))", "Range(1 < f < 2)",
    "Range(1<f<2)", "Range(-1 <= f <= -2)", "Range(1 < f)", "Range(1 < f < x)",
    "Range(1 < f < 2, g=1)", "Range(1 > f > 2)", "Range(01 < f < 2)", "Range(f=1, g=2)",
    "Range(f=1, 2010-01-01T00:00)", "Range(f=1, 2010-01-01T00:00, 2010-01-02T00:00, x=1)",
    "Range(f=[1,2], '2010-01-01T00:00', '2010-01-02T00:00')",
    "Range(f=1, \"2010-01-01T00:00', '2010-01-02T00:00')",
    "Range(f=1, 2010-01-01T00:00, 2010-13-02T00:00)", "Range(Row(f=1))", "Range()",
    "Range(_row=1, 2010-01-01T00:00, 2010-01-02T00:00)",
    "Set(1, f=2, 2010-01-01T00:00)", "Set(1, f=2, '2010-01-01T00:00')",
    "Set(1, f=2, \"2010-01-01T00:00\")", "Set(1, f=2, '2010-01-01T00:00\")",
    "Set(1, f=2, g=3, 2010-01-01T00:00)", "Set(1, f=2, 2010-01-01T00:00, g=3)",
    "Set(1, f=2,)", "Set(1, f=2, )", "Set(1)", "Set(1,f=2)", "Set( 1 , f = 2 )",
    "Set(01, f=2)", "Set(-1, f=2)", "Set(f=2)", "Set(_col=2, f=10)", "Set(1, f=Row(g=1))",
    "Set(1, f=2, Row(g=1))", "Set('a\\'b', f=2)",
    "SetRowAttrs(f, 1, a=1)", "SetRowAttrs(f, 1)", "SetRowAttrs(f,1,a=1,)", "SetRowAttrs(1, 1, a=1)",
    "SetRowAttrs(f-x, 'k', a=null)", "SetRowAttrs(_field=f, _row=1, a=1)",
    "SetColumnAttrs(1, a=1, b=2)", "SetColumnAttrs(1)", "SetColumnAttrs(f=1)",
    "Clear(1, f=1)", "Clear(1, f=1, )", "Clear(1)", "Clear(_col=1, f=1)",
    "ClearRow(f=1)", "ClearRow(f=1, g=2)", "ClearRow(f > 1)", "ClearRow()",
    "Store(Row(f=1), g=2)", "Store(Row(f=1), g=2, h=3)", "Store(Row(f=1))", "Store(g=2)",
    "Store(Row(f=1), Row(g=1))", "Store(Row(f=1),g=2)",
    "TopN(f)", "TopN(f, n=1)", "TopN(f,)", "TopN(f, )", "TopN(f, Row(g=1))",
    "TopN(f, Row(g=1), n=2, Row(h=1))", "TopN(f, n=2, Row(g=1))", "TopN(Row(g=1), f)",
    "TopN(f-g_h, n=1)", "TopN(_field=f, n=1)", "TopN(1, n=1)", "TopN()",
    "GroupBy(Rows(field=a), Rows(field=b), filter=Row(f=1), aggregate=Sum(field=v))",
    "Options(Row(f=1), shards=[0, 1], excludeColumns=true)",
    "Count(Intersect(Row(f=1), Range(g >< [1, 2])))",
]


def _draws():
    """(id, text) for DRAWS draws of every benchmark query template."""
    import numpy as np

    root = os.path.dirname(HERE)
    qroot = os.path.join(root, "benchmark", "queries")
    out = []
    for cfg_name in sorted(os.listdir(qroot)):
        qdir = os.path.join(qroot, cfg_name)
        with open(os.path.join(root, "benchmark", "configs", cfg_name + ".json")) as f:
            schema = json.load(f)
        sys.path.insert(0, qdir)
        try:
            for fname in sorted(os.listdir(qdir)):
                if not fname.endswith(".py"):
                    continue
                spec = importlib.util.spec_from_file_location(
                    "corpus_" + cfg_name.replace("-", "_") + "_" + fname[:-3],
                    os.path.join(qdir, fname))
                mod = importlib.util.module_from_spec(spec)
                spec.loader.exec_module(mod)
                if not hasattr(mod, "draw"):
                    continue  # a module the templates share
                rng = np.random.default_rng([0x50514C, len(out)])
                for i in range(DRAWS):
                    calls, _ = mod.draw(rng, schema)
                    out.append((f"bench.{cfg_name}.{fname[:-3]}.{i}", " ".join(calls)))
        finally:
            sys.path.remove(qdir)
    return out


def _matrix():
    """(id, text) for the texts of the two parser test files."""
    import re

    import test_pql_spec

    out = [(f"spec.working.{n}", q) for n, q, _ in test_pql_spec.WORKING]
    out += [(f"spec.errors.{n}", q) for n, q in test_pql_spec.ERRORS]
    out += [(f"spec.deep.{n}", q) for n, q, _ in test_pql_spec.DEEP]
    with open(os.path.join(HERE, "test_pql.py")) as f:
        tree = ast.parse(f.read())
    # The string constants test_pql.py hands to one() or pql.parse().
    seen = set()
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call) and node.args
                and isinstance(node.args[0], ast.Constant) and isinstance(node.args[0].value, str)):
            continue
        fn = node.func
        if (isinstance(fn, ast.Name) and fn.id == "one") or (
                isinstance(fn, ast.Attribute) and fn.attr == "parse"):
            text = node.args[0].value
            if text not in seen:
                seen.add(text)
                out.append((f"pql.{len(seen)}", text))
    out += [(f"corner.{i}", q) for i, q in enumerate(CORNERS)]
    return out


def _mutations(base, per_text, seed):
    """Seeded single-character edits of each text."""
    import numpy as np

    rng = np.random.default_rng(seed)
    out, done = [], set()
    for id_, text in base:
        if len(text) < 2:
            continue
        for k in range(per_text):
            for _ in range(100):
                kind = ("delete", "duplicate", "swap")[(k + int(rng.integers(0, 3))) % 3]
                i = int(rng.integers(0, len(text) - (kind == "swap")))
                if kind == "delete":
                    m = text[:i] + text[i + 1:]
                elif kind == "duplicate":
                    m = text[:i] + text[i] + text[i:]
                else:
                    m = text[:i] + text[i + 1] + text[i] + text[i + 2:]
                edit = f"edit.{id_}.{kind}@{i}"
                if m != text and edit not in done:
                    done.add(edit)
                    out.append((edit, m))
                    break
    return out


def record():
    draws = _draws()
    matrix = _matrix()
    texts = matrix + draws + _mutations(matrix, 3, 0x4D) + _mutations(draws, 1, 0x44)
    ids = [id_ for id_, _ in texts]
    assert len(set(ids)) == len(ids), "duplicate corpus ids"
    with open(CORPUS, "w") as f:
        for id_, text in texts:
            f.write(json.dumps({"id": id_, "text": text, "parse": canonical(text)}) + "\n")
    return len(texts)


if __name__ == "__main__":
    print(record(), "texts recorded in", CORPUS)

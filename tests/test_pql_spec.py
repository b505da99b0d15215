"""PQL grammar spec sweeps, ported from the reference parser matrices
(pql/pqlpeg_test.go:57 TestPEGWorking, :277 TestPEGErrors, :321
TestPQLDeepEquality).  Each case asserts the same accept/reject decision
and — for the deep-equality matrix — the same AST the Go PEG produces."""

import pytest

from pilosa_tpu import pql
from pilosa_tpu.pql import BETWEEN, EQ, GT, GTE, LT, LTE, NEQ, Call, Condition

# --- TestPEGWorking: (input, expected call count) -------------------------

WORKING = [
    ("Empty", "", 0),
    ("Set", "Set(2, f=10)", 1),
    ("SetWithColKeySingleQuote", "Set('foo', f=10)", 1),
    ("SetWithColKeyDoubleQuote", 'Set("foo", f=10)', 1),
    ("SetTime", "Set(2, f=1, 1999-12-31T00:00)", 1),
    ("DoubleSet", "Set(1, a=4)Set(2, a=4)", 2),
    ("DoubleSetSpc", "Set(1, a=4) Set(2, a=4)", 2),
    ("DoubleSetNewline", "Set(1, a=4) \n Set(2, a=4)", 2),
    ("SetWithArbCall", "Set(1, a=4)Blerg(z=ha)", 2),
    ("SetArbSet", "Set(1, a=4)Blerg(z=ha)Set(2, z=99)", 3),
    ("ArbSetArb", "Arb(q=1, a=4)Set(1, z=9)Arb(z=99)", 3),
    ("SetStringArg", "Set(1, a=zoom)", 1),
    ("SetManyArgs", "Set(1, a=4, b=5)", 1),
    ("SetManyMixedArgs", "Set(1, a=4, bsd=haha)", 1),
    ("SetTimestamp", "Set(1, a=4, 2017-04-03T19:34)", 1),
    ("UnionEmpty", "Union()", 1),
    ("UnionOneRow", "Union(Row(a=1))", 1),
    ("UnionTwoRows", "Union(Row(a=1), Row(z=44))", 1),
    ("UnionNested", "Union(Intersect(Row(), Union(Row(), Row())), Row())", 1),
    ("TopNNoArgs", "TopN(boondoggle)", 1),
    ("TopNWithArgs", "TopN(boon, doggle=9)", 1),
    ("DoubleQuotedArgs", """B(a="zm''e")""", 1),
    ("SingleQuotedArgs", '''B(a='zm""e')''', 1),
    ("SetRowAttrs", "SetRowAttrs(blah, 9, a=47)", 1),
    ("SetRowAttrs2args", "SetRowAttrs(blah, 9, a=47, b=bval)", 1),
    ("SetRowAttrsRowKeySingle", "SetRowAttrs(blah, 'rowKey', a=47)", 1),
    ("SetRowAttrsRowKeyDouble", 'SetRowAttrs(blah, "rowKey", a=47)', 1),
    ("SetColumnAttrs", "SetColumnAttrs(9, a=47)", 1),
    ("SetColumnAttrs2args", "SetColumnAttrs(9, a=47, b=bval)", 1),
    ("SetColumnAttrsColKeySingle", "SetColumnAttrs('colKey', a=47)", 1),
    ("SetColumnAttrsColKeyDouble", 'SetColumnAttrs("colKey", a=47)', 1),
    ("Clear", "Clear(1, a=53)", 1),
    ("Clear2args", "Clear(1, a=53, b=33)", 1),
    ("TopN", "TopN(myfield, n=44)", 1),
    ("TopNBitmap", "TopN(myfield, Row(a=47), n=10)", 1),
    ("RangeLT", "Range(a < 4)", 1),
    ("RangeGT", "Range(a > 4)", 1),
    ("RangeLTE", "Range(a <= 4)", 1),
    ("RangeGTE", "Range(a >= 4)", 1),
    ("RangeEQ", "Range(a == 4)", 1),
    ("RangeNEQ", "Range(a != null)", 1),
    ("RangeLTLT", "Range(4 < a < 9)", 1),
    ("RangeLTLTE", "Range(4 < a <= 9)", 1),
    ("RangeLTELT", "Range(4 <= a < 9)", 1),
    ("RangeLTELTE", "Range(4 <= a <= 9)", 1),
    ("RangeTime", "Range(a=4, 2010-07-04T00:00, 2010-08-04T00:00)", 1),
    (
        "RangeTimeQuotes",
        """Range(a=4, '2010-07-04T00:00', "2010-08-04T00:00")""",
        1,
    ),
    ("DashedFrame", "Set(1, my-frame=9)", 1),
    ("Newlines", "Set(\n1,\nmy-frame\n=9)", 1),
    # pqlpeg_test.go:34 — `falsen0` must lex as a string, not `false` + junk.
    ("FalsePrefixWord", "C(a=falsen0)", 1),
    # pqlpeg_test.go:50 TestOldPQL — legacy call names still parse.
    ("OldPQLSetBit", "SetBit(f=11, col=1)", 1),
]


@pytest.mark.parametrize(
    "query,ncalls",
    [(q, n) for _, q, n in WORKING],
    ids=[name for name, _, _ in WORKING],
)
def test_peg_working(query, ncalls):
    q = pql.parse(query)
    assert len(q.calls) == ncalls


# --- TestPEGErrors: inputs the grammar must reject ------------------------

ERRORS = [
    ("SetNoParens", "Set"),
    ("SetBadTimestamp", "Set(1, a=4, 2017-94-03T19:34)"),
    ("SetTimestampNoArg", "Set(1, 2017-04-03T19:34)"),
    ("SetStartingComma", "Set(, 1, a=4)"),
    ("StartingCommaArb", "Zeeb(, a=4)"),
    ("SetRowAttrs0args", "SetRowAttrs(blah, 9)"),
    ("Clear0args", "Clear(9)"),
    ("RangeTimeGT", "Range(a>4, 2010-07-04T00:00, 2010-08-04T00:00)"),
    ("RangeTimeOneStamp", "Range(a=4, 2010-07-04T00:00)"),
]


@pytest.mark.parametrize(
    "query", [q for _, q in ERRORS], ids=[name for name, _ in ERRORS]
)
def test_peg_errors(query):
    with pytest.raises(pql.ParseError, match=r" at line \d+, col \d+$"):
        pql.parse(query)


# --- TestPQLDeepEquality: exact AST matches -------------------------------


def C(name, args=None, children=None):
    c = Call(name)
    c.args = args or {}
    c.children = children or []
    return c


DEEP = [
    (
        "Set",
        "Set(1, a=7, 2010-07-08T14:44)",
        C("Set", {"a": 7, "_col": 1, "_timestamp": "2010-07-08T14:44"}),
    ),
    (
        "SetRowAttrs",
        "SetRowAttrs(myfield, 9, z=4)",
        C("SetRowAttrs", {"z": 4, "_field": "myfield", "_row": 9}),
    ),
    (
        "SetRowAttrsRowKeySingle",
        "SetRowAttrs(myfield, 'rowKey', z=4)",
        C("SetRowAttrs", {"z": 4, "_field": "myfield", "_row": "rowKey"}),
    ),
    (
        "SetRowAttrsRowKeyDouble",
        'SetRowAttrs(myfield, "rowKey", z=4)',
        C("SetRowAttrs", {"z": 4, "_field": "myfield", "_row": "rowKey"}),
    ),
    (
        "SetColumnAttrs",
        "SetColumnAttrs(9, z=4)",
        C("SetColumnAttrs", {"z": 4, "_col": 9}),
    ),
    (
        "SetColumnAttrsColKeySingle",
        "SetColumnAttrs('colKey', z=4)",
        C("SetColumnAttrs", {"z": 4, "_col": "colKey"}),
    ),
    (
        "SetColumnAttrsColKeyDouble",
        'SetColumnAttrs("colKey", z=4)',
        C("SetColumnAttrs", {"z": 4, "_col": "colKey"}),
    ),
    ("Clear", "Clear(1, a=7)", C("Clear", {"a": 7, "_col": 1})),
    (
        "TopN",
        "TopN(myfield, Row(), a=7)",
        C("TopN", {"a": 7, "_field": "myfield"}, [C("Row")]),
    ),
    ("RangeEQ", "Range(a==7)", C("Range", {"a": Condition(EQ, 7)})),
    ("RangeLT", "Range(a<7)", C("Range", {"a": Condition(LT, 7)})),
    ("RangeLTE", "Range(a<=7)", C("Range", {"a": Condition(LTE, 7)})),
    ("RangeGTE", "Range(a>=7)", C("Range", {"a": Condition(GTE, 7)})),
    ("RangeGT", "Range(a>7)", C("Range", {"a": Condition(GT, 7)})),
    ("RangeNEQ", "Range(a!=null)", C("Range", {"a": Condition(NEQ, None)})),
    # ast.go:82 endConditional — low++ on '<', high++ on '<=': the stored
    # BETWEEN bounds are inclusive-low / exclusive-high normalized.
    (
        "RangeLTELT",
        "Range(4 <= a < 9)",
        C("Range", {"a": Condition(BETWEEN, [4, 9])}),
    ),
    (
        "RangeLTLT",
        "Range(4 < a < 9)",
        C("Range", {"a": Condition(BETWEEN, [5, 9])}),
    ),
    (
        "RangeLTELTE",
        "Range(4 <= a <= 9)",
        C("Range", {"a": Condition(BETWEEN, [4, 10])}),
    ),
    (
        "RangeLTLTE",
        "Range(4 < a <= 9)",
        C("Range", {"a": Condition(BETWEEN, [5, 10])}),
    ),
    ("Sum", "Sum(field=f)", C("Sum", {"field": "f"})),
    ("WeirdDash", "Sum(field-=f)", C("Sum", {"field-": "f"})),
    (
        "SumChild",
        "Sum(Row(), field=f)",
        C("Sum", {"field": "f"}, [C("Row")]),
    ),
    (
        "MinChild",
        "Min(Row(), field=f)",
        C("Min", {"field": "f"}, [C("Row")]),
    ),
    (
        "MaxChild",
        "Max(Row(), field=f)",
        C("Max", {"field": "f"}, [C("Row")]),
    ),
    (
        "OptionsWrapper",
        "Options(Row(f1=123), excludeRowAttrs=true)",
        C(
            "Options",
            {"excludeRowAttrs": True},
            [C("Row", {"f1": 123})],
        ),
    ),
    (
        "GroupBy",
        "GroupBy(Rows(), filter=Row(a=1))",
        C("GroupBy", {"filter": C("Row", {"a": 1})}, [C("Rows")]),
    ),
]


@pytest.mark.parametrize(
    "query,expect",
    [(q, e) for _, q, e in DEEP],
    ids=[name for name, _, _ in DEEP],
)
def test_deep_equality(query, expect):
    q = pql.parse(query)
    assert len(q.calls) == 1
    assert q.calls[0] == expect


def test_quoted_strings_with_escapes_and_operators():
    # pqlpeg_test.go:10 — pathological quoted strings survive one pass.
    q = pql.parse(
        r'''Row(field="http://zoo9.com=\\'hello' and \"hello\"")'''
    )
    assert q.calls[0].args["field"] == '''http://zoo9.com=\\'hello' and "hello"'''


def test_unescaped_interior_quote_rejected():
    # pqlpeg_test.go:19 — an interior unescaped double quote is an error.
    with pytest.raises(pql.ParseError):
        pql.parse('SetRowAttrs(attr="http://zoo9.com" and "hello\\"")extra"')


def test_roundtrip_stability_over_matrix():
    """str(parse(q)) reparses to the same AST for every working case."""
    for _, query, _ in DEEP:
        q = pql.parse(query)
        assert pql.parse(str(q)) == q, query

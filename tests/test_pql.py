"""PQL parser tests (modeled on pql/pql_test.go and the grammar in
pql/pql.peg)."""

import pytest

from pilosa_tpu import pql
from pilosa_tpu.pql import BETWEEN, EQ, GT, GTE, LT, LTE, NEQ, Call, Condition


def one(q):
    query = pql.parse(q)
    assert len(query.calls) == 1
    return query.calls[0]


def test_set():
    c = one("Set(2, f=10)")
    assert c.name == "Set"
    assert c.args == {"_col": 2, "f": 10}


def test_set_with_timestamp():
    c = one("Set(2, f=10, 2010-01-02T03:04)")
    assert c.args == {"_col": 2, "f": 10, "_timestamp": "2010-01-02T03:04"}


def test_set_string_col():
    c = one('Set("foo", f="bar")')
    assert c.args == {"_col": "foo", "f": "bar"}


def test_row():
    c = one("Row(f=10)")
    assert c.name == "Row"
    assert c.args == {"f": 10}


def test_nested_calls():
    c = one("Count(Intersect(Row(f=10), Row(g=20)))")
    assert c.name == "Count"
    assert len(c.children) == 1
    inner = c.children[0]
    assert inner.name == "Intersect"
    assert [ch.name for ch in inner.children] == ["Row", "Row"]
    assert inner.children[1].args == {"g": 20}


def test_multiple_calls():
    q = pql.parse("Set(1, f=1)\nSet(2, f=2) Row(f=1)")
    assert [c.name for c in q.calls] == ["Set", "Set", "Row"]


def test_topn():
    c = one("TopN(f, n=5)")
    assert c.args == {"_field": "f", "n": 5}
    c = one('TopN(f, Row(g=10), n=12, attrName="category", attrValues=[80,81])')
    assert c.args["_field"] == "f"
    assert c.args["attrName"] == "category"
    assert c.args["attrValues"] == [80, 81]
    assert c.children[0].name == "Row"


def test_topn_no_args():
    c = one("TopN(f)")
    assert c.args == {"_field": "f"}


def test_range_conditions():
    assert one("Range(foo == 20)").args == {"foo": Condition(EQ, 20)}
    assert one("Range(foo != 20)").args == {"foo": Condition(NEQ, 20)}
    assert one("Range(foo < 20)").args == {"foo": Condition(LT, 20)}
    assert one("Range(foo <= 20)").args == {"foo": Condition(LTE, 20)}
    assert one("Range(foo > 20)").args == {"foo": Condition(GT, 20)}
    assert one("Range(foo >= 20)").args == {"foo": Condition(GTE, 20)}
    assert one("Range(foo != null)").args == {"foo": Condition(NEQ, None)}
    assert one("Range(foo >< [10, 20])").args == {
        "foo": Condition(BETWEEN, [10, 20])
    }


def test_range_conditional():
    # ast.go endConditional :82: low++ on '<', high++ on '<='.
    assert one("Range(0 < other < 1000)").args == {
        "other": Condition(BETWEEN, [1, 1000])
    }
    assert one("Range(0 <= other <= 1000)").args == {
        "other": Condition(BETWEEN, [0, 1001])
    }
    assert one("Range(-10 < x <= 10)").args == {
        "x": Condition(BETWEEN, [-9, 11])
    }


def test_range_time():
    c = one("Range(f=10, 2010-01-01T00:00, 2010-01-02T03:04)")
    assert c.args == {
        "f": 10,
        "_start": "2010-01-01T00:00",
        "_end": "2010-01-02T03:04",
    }


def test_set_row_attrs():
    c = one('SetRowAttrs(f, 10, foo="bar", baz=123, active=true, x=null)')
    assert c.args == {
        "_field": "f",
        "_row": 10,
        "foo": "bar",
        "baz": 123,
        "active": True,
        "x": None,
    }


def test_set_column_attrs():
    c = one('SetColumnAttrs(7, foo="bar")')
    assert c.args == {"_col": 7, "foo": "bar"}


def test_clear_and_clear_row():
    assert one("Clear(2, f=10)").args == {"_col": 2, "f": 10}
    assert one("ClearRow(f=10)").args == {"f": 10}


def test_store():
    c = one("Store(Row(f=10), f=20)")
    assert c.children[0].name == "Row"
    assert c.args == {"f": 20}


def test_options():
    c = one("Options(Row(f=10), excludeColumns=true, shards=[0, 2])")
    assert c.args["excludeColumns"] is True
    assert c.args["shards"] == [0, 2]


def test_group_by_with_filter_call_arg():
    c = one("GroupBy(Rows(field=a), Rows(field=b), filter=Row(f=10), limit=7)")
    assert [ch.name for ch in c.children] == ["Rows", "Rows"]
    assert isinstance(c.args["filter"], Call)
    assert c.args["filter"].name == "Row"
    assert c.args["limit"] == 7


def test_bare_word_and_quoted_values():
    c = one("Rows(field=f)")
    assert c.args == {"field": "f"}
    c = one('Row(f="has space")')
    assert c.args == {"f": "has space"}
    c = one("Row(f='single')")
    assert c.args == {"f": "single"}


def test_float_and_negative_values():
    assert one("F(x=1.5)").args == {"x": 1.5}
    assert one("F(x=-3)").args == {"x": -3}


def test_escaped_quotes():
    c = one('F(x="a\\"b")')
    assert c.args == {"x": 'a"b'}


def test_call_string_roundtrip():
    q = 'Count(Intersect(Row(f=10), Row(g=20)))'
    assert pql.parse(str(pql.parse(q))) == pql.parse(q)
    q2 = "Range(0 < other < 1000)"
    assert pql.parse(str(pql.parse(q2))) == pql.parse(q2)


def test_parse_errors():
    with pytest.raises(pql.ParseError):
        pql.parse("Row(f=")
    with pytest.raises(pql.ParseError):
        pql.parse("Row(f=10")
    with pytest.raises(pql.ParseError):
        pql.parse("42")


@pytest.mark.parametrize(
    "text,keys,field",
    [
        ("Row(from=1, f=2)", ["from", "f"], "from"),
        ("Rows(field=f, previous=3, limit=2)", ["field", "previous", "limit"], "field"),
        ("Set(1, f=2, 2010-01-01T00:00)", ["_col", "f", "_timestamp"], "f"),
        ("Set(_col=2, f=10)", ["_col", "f"], "f"),
        ("SetRowAttrs(f, 1, a=1)", ["_field", "_row", "a"], "a"),
        ("TopN(f, Row(g=1), n=5)", ["_field", "n"], "n"),
        ("Range(f=1, 2010-01-01T00:00, 2010-01-02T00:00)", ["f", "_start", "_end"], "f"),
        ("GroupBy(Rows(field=a), limit=7, filter=Row(f=10))", ["limit", "filter"], "limit"),
        ("Row(_row=1, g=2)", ["_row", "g"], "g"),
    ],
)
def test_args_keep_the_order_of_the_text(text, keys, field):
    """Call.args holds the keys in the order the text gives them (the
    positional ones where their form puts them), and field_arg() is the
    first key that is not reserved."""
    c = one(text)
    assert list(c.args) == keys
    assert c.field_arg() == field


@pytest.mark.parametrize(
    "text,where",
    [
        ("Row(f=", "line 1, col 7"),
        ("Row(f=10", "line 1, col 9"),
        ("42", "line 1, col 1"),
        ("Row(f=1)\nRow(g=)", "line 2, col 7"),
        ("Row(f=1)\n  Row(g=1, h)", "line 2, col 12"),
        ("Count(Row(f=1),)", "line 1, col 16"),
        ("Row(f=[1,])", "line 1, col 10"),
        ("Set(1, a=4, 2017-94-03T19:34)", "line 1, col 11"),
    ],
)
def test_parse_errors_name_line_and_column(text, where):
    with pytest.raises(pql.ParseError, match=f"at {where}$"):
        pql.parse(text)


def test_unterminated_string_is_named():
    with pytest.raises(pql.ParseError, match="^unterminated string at line 1, col 7$"):
        pql.parse("Row(f='open)")
    with pytest.raises(pql.ParseError, match="^unterminated string at line 1, col 5$"):
        pql.parse('Set("open, f=1)')


def test_write_call_n():
    q = pql.parse("Set(1, f=1) Row(f=1) Clear(1, f=1)")
    assert q.write_call_n() == 2

"""PQL parser — one scan of the text over the reference PEG grammar.

The productions are the reference's (pql/pql.peg:8-84, pql/pql.peg.go).
Each step matches one compiled pattern at the current position (leading
whitespace is part of every pattern) and takes the production its named
group says.  Where the PEG relies on ordered choice — Range's timerange /
conditional / arg, the positional forms of Set, SetRowAttrs,
SetColumnAttrs, Clear and TopN against the generic argument list, a call
or an argument in that list — the next one to three tokens decide it, so
nothing is parsed twice except the rare Range whose arg is not its last.
The commonest shapes take one match each: a call whose one argument has
a number for its value (``Row(f=1)``), and a list of numbers.
``ParseError`` is raised only for a text that is rejected.
"""

from __future__ import annotations

import re
from typing import NoReturn

from ..util.stats import METRIC_PQL_PARSES, REGISTRY
from .ast import BETWEEN, Call, Condition, Query

# The quantifiers are possessive (``*+``) where what follows can never
# start with a character they matched: giving one back could not make a
# match, and the engine is spared the attempt.
_WS = r"[ \t\n\r]*+"
_IDENT = r"[A-Za-z][A-Za-z0-9]*+"  # a call's name
_FIELD = r"[A-Za-z][A-Za-z0-9_-]*+"
# An argument's key: a reserved name (a prefix is enough, as in the
# reference), else a field.
_KEYS = r"_(?:row|col|start|end|timestamp|field)|" + _FIELD
# Longest match first: '><' and the two-character operators before '<' / '>'.
_OPS = r"><|<=|>=|==|!=|<|>|="
_KEY = f"(?P<key>{_KEYS})"
_OP = f"(?P<op>{_OPS})"
_INT = r"-?(?:0|[1-9][0-9]*+)"
# A number, unless word characters follow it (no backtracking into a
# shorter number).
_NUM = r"(?>-?(?:[0-9]+(?:\.[0-9]*)?|\.[0-9]+))(?![A-Za-z0-9_:-])"
_TIMESTAMP = r"[0-9]{4}-[01][0-9]-[0-3][0-9]T[0-9]{2}:[0-9]{2}"
_DQ = r'"(?P<dq>[^"\\]*+(?:\\[\s\S][^"\\]*+)*+)"'
_SQ = r"'(?P<sq>[^'\\]*+(?:\\[\s\S][^'\\]*+)*+)'"


def _timestamp(g: str) -> str:
    """A timestamp, bare or quoted, in groups g1 / g2 / g3."""
    return f"(?:\"(?P<{g}1>{_TIMESTAMP})\"|'(?P<{g}2>{_TIMESTAMP})'|(?P<{g}3>{_TIMESTAMP}))"


# A value, the PEG's ordered choice (pql.peg item :42-51): null / true /
# false only before a delimiter, a number, a call, a bare word, a quoted
# string; or a list.  An argument's pattern scans its value too.
_VALUE = (
    f"{_WS}(?:(?P<lit>null|true|false)(?=[ \\t\\n]*(?:[,)\\]]|\\Z))"
    f"|(?P<num>{_NUM})"
    f"|(?P<vcall>{_IDENT}){_WS}\\("
    r"|(?P<word>[A-Za-z0-9_:-]++)"
    f"|{_DQ}|{_SQ}|(?P<list>\\[))"
)
# A key, its operator and (if one can start there) its value.
_ARG = f"{_KEY}{_WS}{_OP}(?:{_VALUE})?"
# A call whose one argument has a number for its value, Row(f=1) (the
# commonest call by far), in one match.  Every positional form reads
# such a call as the generic argument list does.
_LEAF = (
    f"(?P<leaf>{_IDENT}){_WS}\\({_WS}(?P<lkey>{_KEYS}){_WS}(?P<lop>{_OPS})"
    f"{_WS}(?P<lnum>{_NUM}){_WS}\\)"
)
_CALL_OR_ARG = f"(?:{_LEAF}|(?P<call>{_IDENT}){_WS}\\(|{_ARG})"

_SKIP_WS = re.compile(_WS)
_END = re.compile(_WS + r"\Z")
_CLOSE = re.compile(_WS + r"\)")
_COMMA_CLOSE = re.compile(_WS + r"(?:," + _WS + r")?\)")
_COMMA = re.compile(_WS + ",")
# A call: its name and the open paren.
_OPEN = re.compile(f"{_WS}(?P<name>{_IDENT}){_WS}\\(")
# The first element of an argument list: a call or an argument.
_ELEMENT = re.compile(_WS + _CALL_OR_ARG)
# The element after a call: none (no comma), a call, an argument, or (no
# group) junk.
_NEXT_ELEMENT = re.compile(f"{_WS},{_WS}{_CALL_OR_ARG}?")
_FIRST_ARG = re.compile(f"{_WS}{_ARG}")
# The argument after an argument: none (the comma, if any, is the
# caller's), an argument, or a letter or '_' that starts no key — a text
# the grammar rejects in every place an argument list ends.
_NEXT_ARG = re.compile(f"{_WS},{_WS}(?:{_ARG}|(?P<bad>[A-Za-z_]))")
_LIST_ITEM = re.compile(_VALUE)
# A list of numbers and its close bracket, in one match.
_NUMBERS = re.compile(f"{_WS}(?P<numbers>{_NUM}(?:{_WS},{_WS}{_NUM})*+){_WS}\\]")
_LIST_EMPTY = re.compile(_WS + r"\]")
_LIST_NEXT = re.compile(_WS + r"(?:(?P<comma>,)|(?P<end>\]))")
# A positional column or row: an unsigned integer or a quoted string; a
# lone quote is an unterminated string.
_POSITION = re.compile(f"{_WS}(?:(?P<uint>0|[1-9][0-9]*)|{_DQ}|{_SQ}|(?P<quote>[\"']))")
_FIELD_COMMA = re.compile(f"{_WS}(?P<field>{_FIELD}){_WS},")
_TOPN_FIELD = re.compile(f"{_WS}(?P<field>{_FIELD}){_WS}(?:(?P<comma>,)|(?P<close>\\)))")
_SET_TIMESTAMP = re.compile(f"{_WS},{_WS}{_timestamp('t')}")
_TIMERANGE = re.compile(f"{_WS},{_WS}{_timestamp('s')}{_WS},{_WS}{_timestamp('e')}{_WS}\\)")
_CONDITIONAL = re.compile(
    f"{_WS}(?P<lo>{_INT}){_WS}(?P<op1><=|<){_WS}(?P<field>{_FIELD})"
    f"{_WS}(?P<op2><=|<){_WS}(?P<hi>{_INT}){_WS}\\)"
)
_UNESCAPE = {"dq": re.compile(r'\\(["\\])'), "sq": re.compile(r"\\(['\\])")}
_LITERALS = {"null": None, "true": True, "false": False}


class ParseError(Exception):
    def __init__(self, msg: str, pos: int = -1, src: str = ""):
        if pos >= 0:
            line = src.count("\n", 0, pos) + 1
            col = pos - (src.rfind("\n", 0, pos) + 1) + 1
            msg = f"{msg} at line {line}, col {col}"
        super().__init__(msg)


def _matched(m: re.Match, g: str) -> str:
    """The text of whichever of groups g1 / g2 / g3 matched."""
    return m.group(g + "1") or m.group(g + "2") or m.group(g + "3")


def _string(m: re.Match, kind: str) -> str:
    s = m.group(kind)
    return _UNESCAPE[kind].sub(r"\1", s) if "\\" in s else s


def _number(text: str):
    return float(text) if "." in text else int(text)


def _leaf(m: re.Match) -> Call:
    name, key, op, num = m.group("leaf", "lkey", "lop", "lnum")
    value = _number(num)
    return Call(name, {key: value if op == "=" else Condition(op, value)})


class _Descent:
    """Recursive descent over the tokens the patterns above scan: each
    production starts at a position of the text and returns the position
    after what it read."""

    __slots__ = ("src",)

    def __init__(self, src: str):
        self.src = src

    def fail(self, msg: str, pos: int) -> NoReturn:
        raise ParseError(msg, _SKIP_WS.match(self.src, pos).end(), self.src)

    def query(self) -> Query:
        src, calls, pos = self.src, [], 0
        while True:
            m = _OPEN.match(src, pos)
            if m is None:
                if _END.match(src, pos) is None:
                    self.fail("expected call", pos)
                return Query(calls)
            call, pos = self.call(m.group("name"), m.end())
            calls.append(call)

    # -- calls (pql.peg Call :9-18) ----------------------------------------

    def call(self, name: str, pos: int):
        """The call named ``name`` whose open paren ends before ``pos``:
        its positional form where the next tokens fit it, else the generic
        argument list."""
        call = Call(name)
        form = _POSITIONAL.get(name)
        if form is not None:
            end = form(self, call, pos)
            if end is not None:
                return call, end
        pos = self.allargs(call, pos)
        m = _COMMA_CLOSE.match(self.src, pos)
        if m is None:
            self.fail("expected ')'", pos)
        return call, m.end()

    def close(self, pos: int) -> int:
        m = _CLOSE.match(self.src, pos)
        if m is None:
            self.fail("expected ')'", pos)
        return m.end()

    def position(self, pos: int, what: str):
        """A positional column or row; None if the next token is neither."""
        m = _POSITION.match(self.src, pos)
        if m is None:
            return None, pos
        kind = m.lastgroup
        if kind == "quote":
            self.fail("unterminated string", pos)
        if kind == "uint":
            value = int(m.group("uint"))
        else:
            value = _string(m, kind)
        c = _COMMA.match(self.src, m.end())
        if c is None:
            self.fail(f"expected ',' after the {what}", m.end())
        return value, c.end()

    def column_form(self, call: Call, pos: int):
        """Set / SetColumnAttrs / Clear (col, args...[, Set's timestamp]):
        taken when a column comes first, which no argument list can start
        with."""
        col, pos = self.position(pos, "column")
        if col is None:
            return None
        call.args["_col"] = col
        pos = self.args(call, self.arg_head(pos))
        if call.name == "Set":
            m = _SET_TIMESTAMP.match(self.src, pos)
            if m is not None:
                call.args["_timestamp"] = _matched(m, "t")
                pos = m.end()
        return self.close(pos)

    def set_row_attrs_form(self, call: Call, pos: int):
        """SetRowAttrs(field, row, attrs...): taken when a field and a comma
        come first, which no argument list can start with."""
        m = _FIELD_COMMA.match(self.src, pos)
        if m is None:
            return None
        call.args["_field"] = m.group("field")
        row, pos = self.position(m.end(), "row")
        if row is None:
            self.fail("expected integer or quoted string", m.end())
        call.args["_row"] = row
        return self.close(self.args(call, self.arg_head(pos)))

    def topn_form(self, call: Call, pos: int):
        """TopN(field[, allargs]): taken when a field and a comma or the
        close paren come first."""
        m = _TOPN_FIELD.match(self.src, pos)
        if m is None:
            return None
        call.args["_field"] = m.group("field")
        if m.lastgroup == "close":
            return m.end()
        return self.close(self.allargs(call, m.end()))

    def range_form(self, call: Call, pos: int):
        """Range(timerange / conditional / arg) (pql.peg:31-36): an arg, a
        timerange if two timestamps follow its value, else a conditional
        (it starts with a number, which no arg does).  It must be all there
        is: if more follows, the generic argument list reads the call
        again."""
        src = self.src
        m = _FIRST_ARG.match(src, pos)
        if m is None:
            m = _CONDITIONAL.match(src, pos)
            if m is None:
                return None
            # ast.go endConditional :82: low++ on '<', high++ on '<='.
            low = int(m.group("lo")) + (m.group("op1") == "<")
            high = int(m.group("hi")) + (m.group("op2") == "<=")
            call.args[m.group("field")] = Condition(BETWEEN, [low, high])
            return m.end()
        value, end = self.value(m)
        key, op = m.group("key", "op")
        c = _CLOSE.match(src, end)
        if c is not None:
            call.args[key] = value if op == "=" else Condition(op, value)
            return c.end()
        if op == "=":
            t = _TIMERANGE.match(src, end)
            if t is not None:
                call.args[key] = value
                call.args["_start"] = _matched(t, "s")
                call.args["_end"] = _matched(t, "e")
                return t.end()
        return None

    # -- argument lists ------------------------------------------------------

    def allargs(self, call: Call, pos: int) -> int:
        """allargs <- Call (comma Call)* (comma args)? / args / sp"""
        src = self.src
        m = _ELEMENT.match(src, pos)
        if m is None:
            return pos
        kind = m.lastgroup
        children = call.children
        while True:
            if kind == "lnum":
                children.append(_leaf(m))
                pos = m.end()
            elif kind == "call":
                child, pos = self.call(m.group("call"), m.end())
                children.append(child)
            else:
                return self.args(call, m)
            m = _NEXT_ELEMENT.match(src, pos)
            if m is None:
                return pos
            kind = m.lastgroup
            if kind is None:
                self.fail("expected call or argument", m.end())

    def arg_head(self, pos: int) -> re.Match:
        m = _FIRST_ARG.match(self.src, pos)
        if m is None:
            self.fail("expected argument", pos)
        return m

    def args(self, call: Call, m: re.Match) -> int:
        """args <- arg (comma args)? — ``m`` scanned the first argument;
        returns the position before a comma that no argument follows."""
        src, args = self.src, call.args
        while True:
            value, pos = self.value(m)
            key, op = m.group("key", "op")
            args[key] = value if op == "=" else Condition(op, value)
            m = _NEXT_ARG.match(src, pos)
            if m is None:
                return pos
            if m.lastgroup == "bad":
                self.fail("expected argument", m.start("bad"))

    # -- values ------------------------------------------------------------

    def value(self, m: re.Match):
        """The value ``m`` scanned (an argument's, or a list item's) and the
        position after it."""
        kind = m.lastgroup
        if kind == "num":
            return _number(m.group(kind)), m.end()
        if kind == "word":
            return m.group(kind), m.end()
        if kind == "vcall":
            return self.call(m.group(kind), m.end())
        if kind == "lit":
            return _LITERALS[m.group(kind)], m.end()
        if kind == "list":
            return self.list(m.end())
        if kind == "dq" or kind == "sq":
            return _string(m, kind), m.end()
        self.no_value(m.end())

    def no_value(self, pos: int) -> NoReturn:
        m = _SKIP_WS.match(self.src, pos)
        quoted = self.src[m.end():m.end() + 1] in ("'", '"')
        self.fail("unterminated string" if quoted else "expected value", pos)

    def list(self, pos: int):
        src = self.src
        m = _NUMBERS.match(src, pos)
        if m is not None:
            return [_number(t.strip(" \t\n\r")) for t in m.group("numbers").split(",")], m.end()
        m = _LIST_EMPTY.match(src, pos)
        if m is not None:
            return [], m.end()
        out = []
        while True:
            m = _LIST_ITEM.match(src, pos)
            if m is None or m.lastgroup == "list":
                self.no_value(pos)
            item, pos = self.value(m)
            out.append(item)
            m = _LIST_NEXT.match(src, pos)
            if m is None:
                self.fail("expected ',' or ']'", pos)
            pos = m.end()
            if m.lastgroup == "end":
                return out, pos


# The calls with a positional form; Store and ClearRow have one in the PEG,
# but whatever it accepts the generic argument list reads the same.
_POSITIONAL = {
    "Set": _Descent.column_form,
    "SetRowAttrs": _Descent.set_row_attrs_form,
    "SetColumnAttrs": _Descent.column_form,
    "Clear": _Descent.column_form,
    "TopN": _Descent.topn_form,
    "Range": _Descent.range_form,
}


# Every parse in the process, whoever asks: a served request's text is
# parsed once, and the parse travels with the request.
_PARSES = REGISTRY.counter(
    METRIC_PQL_PARSES, help="PQL texts parsed (pql.parse calls)"
)


def parse(src: str) -> Query:
    """Parse a PQL query string into a Query AST."""
    _PARSES.inc()
    return _Descent(src).query()

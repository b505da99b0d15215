"""Wire serialization of query results.

JSON shapes mirror the reference's MarshalJSON implementations
(http/handler.go QueryResponse :30-77, row.go, executor.go FieldRow
:982-1001): Row -> {attrs, columns|keys}, ValCount -> {value, count},
TopN pairs -> [{id|key, count}], Rows -> {rows|keys}, GroupBy ->
[{group, count}].
"""

from __future__ import annotations

import itertools
import json

import numpy as np

from ..core.row import Row
from ..executor import FieldRow, GroupColumns, GroupCount, RowIdentifiers, ValCount


def result_to_json(result):
    if result is None:
        return None
    if isinstance(result, Row):
        out = {"attrs": result.attrs or {}}
        if result.keys is not None:
            out["keys"] = result.keys
        else:
            out["columns"] = [int(c) for c in result.columns()]
        return out
    if isinstance(result, bool):
        return result
    if isinstance(result, int):
        return result
    if isinstance(result, ValCount):
        return result.to_dict()
    if isinstance(result, RowIdentifiers):
        return result.to_dict()
    if isinstance(result, GroupColumns):
        result = list(result)  # the generic walk is over GroupCounts
    if isinstance(result, list):
        if result and isinstance(result[0], tuple):
            # TopN pairs: (id_or_key, count)
            return [
                {("key" if isinstance(i, str) else "id"): i, "count": c}
                for i, c in result
            ]
        if result and isinstance(result[0], GroupCount):
            return [g.to_dict() for g in result]
        return result
    return result


def response_to_json(resp) -> dict:
    out = {"results": [result_to_json(r) for r in resp.results]}
    if resp.column_attr_sets is not None:
        out["columnAttrs"] = [c.to_dict() for c in resp.column_attr_sets]
    return out


def fast_result_values(resp):
    """The response's results as fast-encodable plain values, or None.

    A result qualifies when it is a plain int (the batched Count tier)
    or a TopN ``(id, count)`` pair list with integer ids — the classic
    dashboard payload, which previously always took the generic
    ``result_to_json`` walk.  Keyed TopN (string ids), Rows, ValCount,
    bools, and attr-carrying responses disqualify (``None``): callers
    fall back to the generic encoder.  The returned structure is also
    what the process-mode RESULT_FAST frame carries (net/ipc.py), so
    the device-owner ships values and the WORKER does the JSON encode.
    """
    if resp.column_attr_sets is not None:
        return None
    results = resp.results
    out = []
    for r in results:
        if type(r) is int:
            out.append(r)
        elif type(r) is list:
            for pair in r:
                if (
                    type(pair) is not tuple
                    or len(pair) != 2
                    or type(pair[0]) is not int
                    or type(pair[1]) is not int
                ):
                    return None
            out.append(r)
        else:
            return None
    return out


def fast_results_bytes(results, trace_id=None) -> bytes:
    """Exact ``json.dumps`` bytes for a fast-qualifying results list
    (see ``fast_result_values``): ints render as-is, pair lists as
    ``[{"id": i, "count": c}, ...]`` — byte-identical to the generic
    encoder's output, without the per-response dict builds."""
    parts = []
    for r in results:
        if type(r) is int:
            parts.append(str(r))
        else:
            parts.append(
                "["
                + ", ".join(
                    '{"id": %d, "count": %d}' % (i, c) for i, c in r
                )
                + "]"
            )
    return _response_bytes(parts, trace_id)


def _response_bytes(parts, trace_id) -> bytes:
    """``{"results": [<parts>], "traceID": ...}`` as json.dumps spaces it."""
    body = '{"results": [' + ", ".join(parts) + "]"
    if trace_id:
        body += f', "traceID": "{trace_id}"'
    return (body + "}").encode()


def count_response_bytes(resp, trace_id=None):
    """Fast-path JSON encoding for int / TopN-pair responses: builds
    the exact bytes ``json.dumps`` would produce for
    ``{"results": [...], "traceID": ...}`` without the generic
    ``result_to_json`` walk — at 10k+ responses/second the per-response
    dict build + dispatch chain is measurable host work on the collect
    path.  Returns None when any result doesn't qualify (bool is not an
    int here: it serializes as true/false) or the response carries
    column attributes — callers fall back to the generic encoder."""
    results = fast_result_values(resp)
    if results is None:
        return group_response_bytes(resp, trace_id)
    return fast_results_bytes(results, trace_id)


# The most combinations whose reply texts are kept with a GroupBy's axes
# (~130 bytes each for three fields: ~8 MB).
GROUP_TEXTS_MAX = 1 << 16


def _group_texts(axes):
    """For every combination of ``axes`` in row-major order, the text
    that precedes its count in ``json.dumps`` of the reply's list, led
    by the text that ends the group before it: the reply is these
    alternating with the counts, less the first three characters."""
    cells = [
        ['{"field": %s, "rowID": %d}' % (json.dumps(f), r) for r in vec.tolist()]
        for f, vec in zip(axes.fields, axes.rows)
    ]
    return np.array(
        [
            '}, {"group": [' + ", ".join(c) + '], "count": '
            for c in itertools.product(*cells)
        ],
        dtype=object,
    )


def _group_list_json(groups: GroupColumns) -> str:
    """Exact ``json.dumps`` text of one GroupBy result held as columns
    (``[g.to_dict() for g in groups]``), with no object a group on the
    way from the count tensor to the socket.  A reply that lists a good
    share of its axes' combinations (taxi query 4: ~3,500 of 3,570, half
    a megabyte) is one gather from the texts kept with the axes and one
    join; the texts are written at the first such reply and go with the
    axes.  Any other (and every reply over axes made for one request,
    which keep nothing) is one format string and one ``%`` a group over
    the row vectors.  With ``sums`` a group's ``"sum"`` follows its
    ``"count"``."""
    n = len(groups)
    if not n:
        return "[]"
    axes = groups.axes
    sums = groups.sums
    if axes.kept and axes.size <= GROUP_TEXTS_MAX and 4 * n >= axes.size:
        texts = axes.reply_texts
        if texts is None:
            texts = axes.reply_texts = _group_texts(axes)
        parts = [None] * (2 * n)
        parts[0::2] = texts[groups.flat].tolist()
        if sums is None:
            parts[1::2] = map(str, groups.counts.tolist())
        else:
            parts[1::2] = [
                '%d, "sum": %d' % t
                for t in zip(groups.counts.tolist(), sums.tolist())
            ]
        return "[" + "".join(parts)[3:] + "}]"
    fmt = '{"group": [' + ", ".join(
        '{"field": %s, "rowID": %%d}' % json.dumps(f).replace("%", "%%")
        for f in groups.fields
    ) + '], "count": %d' + ("}" if sums is None else ', "sum": %d}')
    cols = [col.tolist() for col in groups.rows]
    cols.append(groups.counts.tolist())
    if sums is not None:
        cols.append(sums.tolist())
    return "[" + ", ".join([fmt % t for t in zip(*cols)]) + "]"


def group_response_bytes(resp, trace_id=None):
    """``count_response_bytes`` for a response whose every result is a
    GroupBy result still held as columns (``GroupColumns``: row ids, no
    keys, by construction); None otherwise, and once a result's objects
    have been handed out, which may have been written to since."""
    if resp.column_attr_sets is not None or not resp.results:
        return None
    for r in resp.results:
        if type(r) is not GroupColumns or r.objects is not None:
            return None
    return _response_bytes(
        [_group_list_json(r) for r in resp.results], trace_id
    )


def result_from_json(call_name: str, doc):
    """Decode a remote node's partial result back into executor types
    (the JSON analogue of encoding/proto's QueryResponse decode used by
    remoteExec, executor.go:2142-2158)."""
    if doc is None:
        return None
    if isinstance(doc, bool):
        return doc
    if isinstance(doc, (int, float)):
        return int(doc)
    if isinstance(doc, dict):
        if "columns" in doc or ("attrs" in doc and "keys" not in doc):
            row = Row.from_columns(doc.get("columns", []))
            row.attrs = doc.get("attrs") or None
            return row
        if "value" in doc and "count" in doc:
            return ValCount(doc["value"], doc["count"])
        if "rows" in doc or "keys" in doc:
            return RowIdentifiers(doc.get("rows", []), doc.get("keys"))
    if isinstance(doc, list):
        if not doc:
            return [] if call_name in ("TopN", "Rows", "GroupBy") else doc
        first = doc[0]
        if isinstance(first, dict) and "count" in first and "id" in first:
            return [(d["id"], d["count"]) for d in doc]
        if isinstance(first, dict) and "count" in first and "key" in first:
            return [(d["key"], d["count"]) for d in doc]
        if isinstance(first, dict) and "group" in first:
            return [
                GroupCount(
                    [
                        FieldRow(
                            g["field"], g.get("rowID", 0), g.get("rowKey", "")
                        )
                        for g in d["group"]
                    ],
                    d["count"],
                    d.get("sum"),
                )
                for d in doc
            ]
        return [int(x) for x in doc]
    return doc

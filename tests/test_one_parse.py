"""A request's PQL text is parsed once: the deferred attempt parses it
and decides on the calls; a request it declines goes to the sync path
with that parse (and the attempt's span and plan), offered to the
deferred path once (``pilosa_pql_parses_total`` counts every parse)."""

import json
import urllib.request

import numpy as np
import pytest

from pilosa_tpu import pql
from pilosa_tpu.api import API, ImportRequest, QueryRequest
from pilosa_tpu.core.field import FieldOptions
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.executor import Executor
from pilosa_tpu.net.wire import response_to_json
from pilosa_tpu.ops import SHARD_WIDTH
from pilosa_tpu.parallel import MeshEngine, make_mesh
from pilosa_tpu.util import plans
from pilosa_tpu.util.stats import METRIC_PQL_PARSES, REGISTRY

SHARDS = 4
KEYS = ["red", "red", "blue", "green", "red"]
COLUMN_KEYS = ["a", "b", "c", "d", "e"]


@pytest.fixture(scope="module")
def served():
    """Index ``i`` (set fields f and g, int field v) and index ``k``
    (keys, a keyed set field kf) behind both servers."""
    from pilosa_tpu.net import serve

    h = Holder()
    h.open()
    idx = h.create_index("i")
    f = idx.create_field("f")
    g = idx.create_field("g")
    v = idx.create_field("v", FieldOptions(type="int", min=0, max=255))
    rng = np.random.default_rng(39)
    cols = [s * SHARD_WIDTH + int(c) for s in range(SHARDS)
            for c in rng.choice(SHARD_WIDTH, size=120, replace=False)]
    f.import_bulk([10 + k % 4 for k in range(len(cols))], cols)
    g.import_bulk([1 + k % 3 for k in range(len(cols))], cols)
    v.import_values(cols, [int(x) for x in rng.integers(0, 256, len(cols))])
    idx.existence_field().import_bulk([0] * len(cols), cols)
    eng = MeshEngine(h, make_mesh(4))
    api = API(holder=h, mesh_engine=eng)
    api.create_index("k", keys=True)
    api.create_field("k", "kf", {"type": "set", "keys": True})
    api.import_bits(ImportRequest("k", "kf", row_keys=KEYS, column_keys=COLUMN_KEYS))
    uris = {}
    servers = []
    for backend in ("async", "threaded"):
        srv, _thread = serve(api, port=0, backend=backend)
        servers.append(srv)
        uris[backend] = f"http://localhost:{srv.server_address[1]}"
    yield eng, api, uris
    for srv in servers:
        srv.shutdown()
        srv.server_close()


def _post(uri, index, text):
    req = urllib.request.Request(
        f"{uri}/index/{index}/query", data=text.encode(), method="POST")
    return json.loads(urllib.request.urlopen(req, timeout=60).read())


def _parses():
    return REGISTRY.counter(METRIC_PQL_PARSES).get()


REQUESTS = {
    "deferred_count": ("i", "Count(Intersect(Row(f={a}), Row(g={b})))", False),
    "declined_sums": ("i", "Sum(Row(f={a}), field=v)Sum(Row(g={b}), field=v)"
                           "Sum(Union(Row(f={a}), Row(g={b})), field=v)", True),
    "aggregated_groupby": ("i", "GroupBy(Rows(field=f, previous={a}), Rows(field=g), "
                                "filter=Range(v > {b}), aggregate=Sum(field=v))", True),
    "keyed_count": ("k", 'Count(Union(Row(kf="red"), Row(kf="{c}")))', False),
    # Longer than the plan's 512-character cut of the text.
    "past_the_cut": ("i", "Count(Union(" + ", ".join(
        ["Row(f={a})", "Row(g={b})"] * 25) + "))", False),
}


@pytest.mark.parametrize("backend", ["async", "threaded"])
@pytest.mark.parametrize("kind", list(REQUESTS))
def test_a_request_is_parsed_once(served, monkeypatch, backend, kind):
    """One parse a request on every path, and one offer to the
    deferred path, which a declined request does not get again on the
    pool thread."""
    _eng, api, uris = served
    index, text, declined = REQUESTS[kind]
    ex = api.executor
    offers = []
    attempt = ex.execute_async

    def counted(*a, **k):
        out = attempt(*a, **k)
        offers.append(out[0] is None)
        return out

    monkeypatch.setattr(ex, "execute_async", counted)
    first = {"async": (11, 2, "blue"), "threaded": (12, 3, "green")}[backend]
    body = text.format(a=first[0], b=first[1], c=first[2])
    before = _parses()
    doc = _post(uris[backend], index, body)
    assert _parses() - before == 1
    assert offers == [declined]
    plan = plans.STORE.find(doc["traceID"])
    assert plan.pipelined is not declined
    # The reply is the sync path's answer to the text.
    want = response_to_json(api.query(QueryRequest(index, body)))
    assert json.dumps(doc["results"]) == json.dumps(want["results"])
    if kind == "keyed_count":
        assert doc["results"] == [KEYS.count("red") + KEYS.count(first[2])]


def test_a_declined_attempt_hands_over_the_parse_untranslated(served, monkeypatch):
    """A keyed Count the deferred path declines after its key
    translation (here: not lowerable) hands the sync path the parse as
    parsed; the sync path translates it and answers."""
    eng, api, _uris = served
    ex = api.executor
    monkeypatch.setattr(eng, "lowerable", lambda call: False)
    text = 'Count(Row(kf="blue"))Count(Row(kf="red"))'
    fut, parsed = ex.execute_async("k", text)
    assert fut is None
    assert [c.children[0].args["kf"] for c in parsed.calls] == ["blue", "red"]
    before = _parses()
    resp = ex.execute("k", parsed)
    assert resp.results == [KEYS.count("blue"), KEYS.count("red")]
    assert _parses() == before


def test_the_cardinality_lane_answers_a_parsed_count(served, monkeypatch):
    """``Executor.execute`` with a parse still tries the O(1) lane."""
    _eng, api, _uris = served
    ex = Executor(api.holder)

    def outer(*a, **k):
        raise AssertionError("the O(1) lane did not answer")

    monkeypatch.setattr(ex, "_execute_outer", outer)
    want = api.query(QueryRequest("i", "Count(Row(f=13))")).results
    before = _parses()
    assert ex.execute("i", pql.parse("Count(Row(f=13))")).results == want
    assert _parses() == before + 1  # the test's own parse

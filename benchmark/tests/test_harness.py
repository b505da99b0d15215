"""What BENCHMARK.json and the files it names have to agree on, and what
the harness sends for a schema.  Fast, no JAX, no server: run with the
rest of ``python -m pytest benchmark/tests -q``."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

import run  # noqa: E402
from lib import served  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELLS = [w["name"] for w in BENCH["workloads"]]
CONFIGS = [c["name"] for c in BENCH["configs"]]


@pytest.mark.parametrize("workload", CELLS)
def test_cell_loads_with_every_file_it_names(workload):
    cell = run.Cell(workload)
    assert cell.cfg["name"] == cell.workload["config"]
    assert callable(cell.gen.make_shard) and callable(cell.gen.Table)
    assert callable(cell.loop.Loop) and callable(cell.control.lost_shards)
    assert set(cell.templates) == set(cell.mix["templates"])
    traffic = run.Traffic(cell, 2147483659)
    for name, mod in cell.templates.items():
        req = traffic.next(name)
        assert req.calls and len(mod.planes(req.key)) == len(req.calls)
        assert callable(mod.answer)
    for m in cell.metrics("per_layer"):
        spec = run.load_json(run.HERE, "metrics", m["name"] + ".json")
        assert callable(run.load_module(
            os.path.join(run.HERE, "readers", spec["reader"] + ".py")).read)
        assert (spec["unit"], spec["layer"], spec["moves"]) == (m["unit"], m["layer"], m["moves"])


@pytest.mark.parametrize("config", CONFIGS)
def test_configuration_agrees_with_itself_and_its_cells(config):
    entry = next(c for c in BENCH["configs"] if c["name"] == config)
    cfg = run.load_json(ROOT, entry["file"])
    planes = sum(f["rows"] if f["type"] == "set" else f["depth"] + 1 for f in cfg["fields"])
    assert cfg["row_planes"] == planes
    assert cfg["resident_bytes"] == planes * cfg["shards"] * served.PLANE_BYTES
    assert cfg["columns"] == cfg["shards"] * served.SHARD_WIDTH
    assert set(cfg["reduced"]) == set(entry["reduced"])
    cells = [w for w in BENCH["workloads"] if w["config"] == config]
    assert cells and all(w["chips"] == cfg["chips"] for w in cells)


def test_at_most_half_of_the_cells_ask_for_four_chips():
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(CELLS) // 2)


class Recorder:
    def __init__(self):
        self.posts = []

    def call(self, method, path, body=None):
        self.posts.append((method, path, body))


SET, INT = {"name": "s", "type": "set", "rows": 3}, {"name": "v", "type": "int", "min": 0,
                                                     "max": 10, "depth": 4}


@pytest.mark.parametrize("cfg,bodies", [
    ({"index": "i", "fields": [SET, INT]},  # no optional key: the bodies sent before PR 33
     [b"{}", b"{}", b'{"options": {"type": "int", "min": 0, "max": 10}}']),
    ({"index": "i", "index_options": {"keys": True},
      "fields": [dict(SET, options={"cacheType": "ranked", "cacheSize": 2000000}),
                 dict(SET, name="t", options={"timeQuantum": "YMDH"}), INT]},
     [b'{"options": {"keys": true}}',
      b'{"options": {"cacheType": "ranked", "cacheSize": 2000000}}',
      b'{"options": {"timeQuantum": "YMDH"}}',
      b'{"options": {"type": "int", "min": 0, "max": 10}}']),
], ids=["today", "options"])
def test_create_schema_sends_what_the_configuration_says(cfg, bodies):
    rec = Recorder()
    run.create_schema(rec, cfg)
    assert [p for _, p, _ in rec.posts] == ["/index/i"] + [
        f"/index/i/field/{f['name']}" for f in cfg["fields"]]
    assert [b for _, _, b in rec.posts] == bodies

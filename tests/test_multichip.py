"""Forced-8-device CPU lane for the one-mesh-one-cluster data plane
(docs/mesh.md): a query over mesh-sharded stacks must be bit-exact vs
BOTH the single-device host loop and the HTTP fan-out oracle, and a
query whose shards are all locally owned must perform ZERO
internal-client HTTP calls — the psum over SHARD_AXIS is the whole
reduce.

The differential runs in a SUBPROCESS with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` pinned in its
environment (the tests/capabilities.py probe pattern), so the lane
holds even where the ambient conftest/device configuration changes.
"""

from __future__ import annotations

import os
import subprocess
import sys

import pytest

from pilosa_tpu import pql
from pilosa_tpu.cluster import Cluster, Node
from pilosa_tpu.core.field import FieldOptions
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.executor import Executor
from pilosa_tpu.ops import SHARD_WIDTH
from pilosa_tpu.parallel import MeshEngine, make_mesh

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The subprocess differential: 8 virtual devices, an 8-shard dataset,
# three execution paths — fused mesh dispatch, single-device host loop,
# and a 2-node HTTP fan-out cluster — asserted bit-exact on every
# supported call shape.
_DIFFERENTIAL = r"""
import numpy as np

from pilosa_tpu import pql
from pilosa_tpu.core.field import FieldOptions
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.executor import Executor
from pilosa_tpu.ops import SHARD_WIDTH
from pilosa_tpu.parallel import MeshEngine, make_mesh

import jax
assert len(jax.devices()) == 8, jax.devices()

N_SHARDS = 8
rng = np.random.default_rng(11)


def build(holder):
    idx = holder.create_index("i")
    f = idx.create_field("f")
    v = idx.create_field("v", FieldOptions(type="int", min=0, max=255))
    rows, cols = [], []
    for s in range(N_SHARDS):
        base = s * SHARD_WIDTH
        picks = rng.choice(4096, size=128, replace=False)
        for c in picks[:96]:
            rows.append(1)
            cols.append(base + int(c))
        for c in picks[48:]:
            rows.append(2)
            cols.append(base + int(c))
    f.import_bulk(rows, cols)
    vcols = [s * SHARD_WIDTH + c for s in range(N_SHARDS) for c in range(32)]
    v.import_values(vcols, [(i * 53) % 251 for i in range(len(vcols))])
    for field in (f, v):
        for vw in field.views.values():
            for frag in vw.fragments.values():
                frag.cache.recalculate()
    return rows, cols, vcols


holder = Holder()
holder.open()
rows, cols, vcols = build(holder)

mesh = make_mesh(8)
eng = MeshEngine(holder, mesh)
fused = Executor(holder, mesh_engine=eng)
host = Executor(holder)
QUERIES = [
    "Count(Intersect(Row(f=1), Row(f=2)))",
    "Count(Union(Row(f=1), Row(f=2)))",
    "Count(Difference(Row(f=1), Row(f=2)))",
    "Sum(field=v)",
    "Min(field=v)",
    "Max(field=v)",
    "TopN(f, n=2)",
    "Count(Range(v > 100))",
]

# Path 1 vs 2: fused mesh dispatch == single-device host loop.
mesh_results = {}
for q in QUERIES:
    before = eng.fused_dispatches
    got = fused.execute("i", q).results[0]
    want = host.execute("i", q).results[0]
    assert got == want, (q, got, want)
    if q.startswith("Count("):
        assert eng.fused_dispatches > before, f"not fused: {q}"
    mesh_results[q] = got

# Path 3: the HTTP fan-out oracle — a real 2-node loopback cluster with
# the SAME data imported over the wire; every query must agree
# bit-exactly with the mesh answers.
import sys, tempfile
sys.path.insert(0, r"@TESTS_DIR@")
from harness import run_cluster

with tempfile.TemporaryDirectory() as td:
    from pathlib import Path
    h = run_cluster(Path(td), 2)
    try:
        client = h.client(0)
        client.create_index("i")
        client.create_field("i", "f")
        client.create_field(
            "i", "v", {"type": "int", "min": 0, "max": 255}
        )
        client.import_bits("i", "f", 0, rows, cols)
        client.import_values(
            "i", "v", 0, vcols, [(i * 53) % 251 for i in range(len(vcols))]
        )
        # Both nodes own part of the shard set: the oracle genuinely
        # fans out over HTTP.
        c0 = h[0].cluster
        local0 = [
            s for s in range(N_SHARDS)
            if c0.owns_shard(c0.node.id, "i", s)
        ]
        assert 0 < len(local0) < N_SHARDS, local0
        from pilosa_tpu.net.wire import result_from_json
        for q in QUERIES:
            doc = client.query("i", q)
            call = pql.parse(q).calls[0]
            got = result_from_json(call.name, doc["results"][0])
            want = mesh_results[q]
            if hasattr(want, "to_dict"):
                want = want.to_dict()
            if hasattr(got, "to_dict"):
                got = got.to_dict()
            if isinstance(want, list):  # TopN pair lists
                want = [p.to_dict() if hasattr(p, "to_dict") else p for p in want]
                got = [p.to_dict() if hasattr(p, "to_dict") else p for p in got]
            assert got == want, (q, got, want)
    finally:
        h.close()

print("MULTICHIP-DIFFERENTIAL-OK", flush=True)
"""


def _subprocess_env() -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    # Repo root ONLY: an ambient PYTHONPATH must not swap the package
    # under test.
    env["PYTHONPATH"] = _REPO_ROOT
    return env


def test_multichip_differential_subprocess(tmp_path):
    """8 forced host devices in a clean interpreter: fused mesh answers
    == single-device host loop == HTTP fan-out cluster, bit-exact."""
    script = tmp_path / "differential.py"
    script.write_text(
        _DIFFERENTIAL.replace("@TESTS_DIR@", os.path.join(_REPO_ROOT, "tests"))
    )
    proc = subprocess.run(
        [sys.executable, str(script)],
        env=_subprocess_env(),
        capture_output=True,
        text=True,
        timeout=280,
        cwd=_REPO_ROOT,
    )
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    assert "MULTICHIP-DIFFERENTIAL-OK" in proc.stdout, proc.stdout


# -- in-process: zero-HTTP + metrics (conftest pins the 8-device mesh) -----


class _CountingClientFactory:
    """Client factory that fails loudly if the executor ever tries to
    open an internal-client connection."""

    def __init__(self):
        self.created = 0

    def __call__(self, uri):
        self.created += 1
        raise AssertionError(f"internal client dialed for {uri}")


def _one_node_cluster(holder, factory):
    node = Node("n0", "http://localhost:1", is_coordinator=True, devices=8)
    c = Cluster(node=node, replica_n=1, client_factory=factory)
    c.nodes = [node]
    c.holder = holder
    c.state = "NORMAL"
    return c


def _build_local(holder, n_shards=8):
    idx = holder.create_index("i")
    f = idx.create_field("f")
    rows, cols = [], []
    for s in range(n_shards):
        base = s * SHARD_WIDTH
        for c in range(64):
            rows.append(1)
            cols.append(base + c)
        for c in range(32, 96):
            rows.append(2)
            cols.append(base + c)
    f.import_bulk(rows, cols)
    return f


def test_local_query_zero_http_calls():
    """A query whose shards are ALL locally owned lowers to one fused
    mesh dispatch — the psum IS the reduce — with ZERO internal-client
    HTTP calls (the factory raises if ever invoked) and answers
    bit-exact vs the clusterless host oracle."""
    from pilosa_tpu.util.stats import METRIC_CLUSTER_REMOTE_CALLS, REGISTRY

    holder = Holder()
    holder.open()
    _build_local(holder)
    factory = _CountingClientFactory()
    cluster = _one_node_cluster(holder, factory)
    eng = MeshEngine(holder, make_mesh(8))
    ex = Executor(holder, cluster=cluster, mesh_engine=eng)
    oracle = Executor(holder)

    remote_calls = REGISTRY.counter(METRIC_CLUSTER_REMOTE_CALLS)
    before_remote = remote_calls.get()
    before_fused = eng.fused_dispatches
    for q in (
        "Count(Intersect(Row(f=1), Row(f=2)))",
        "Count(Union(Row(f=1), Row(f=2)))",
    ):
        got = ex.execute("i", q).results[0]
        want = oracle.execute("i", q).results[0]
        assert got == want, (q, got, want)
    assert factory.created == 0
    assert remote_calls.get() == before_remote
    assert ex.remote_fanouts == 0
    assert eng.fused_dispatches > before_fused
    eng.close()


def test_mesh_metrics_exported():
    """The pilosa_mesh_* series (devices, shards-per-device occupancy,
    psum dispatch counter) are present and move with fused dispatches."""
    from pilosa_tpu.util.stats import (
        METRIC_MESH_PSUM_DISPATCHES,
        REGISTRY,
    )

    holder = Holder()
    holder.open()
    _build_local(holder)
    eng = MeshEngine(holder, make_mesh(8))
    ex = Executor(holder, mesh_engine=eng)
    psum = REGISTRY.counter(METRIC_MESH_PSUM_DISPATCHES)
    before = psum.get()
    # An Intersect tree: the bare-Row O(1) cardinality lane must not
    # swallow the dispatch this test is counting.
    assert (
        ex.execute("i", "Count(Intersect(Row(f=1), Row(f=1)))").results[0]
        == 8 * 64
    )
    assert psum.get() > before
    eng.refresh_metrics()
    text = REGISTRY.prometheus_text()
    lines = {
        ln.split(" ")[0]: float(ln.split(" ")[1])
        for ln in text.splitlines()
        if ln.startswith("pilosa_mesh_")
    }
    assert lines["pilosa_mesh_devices"] == 8
    assert lines["pilosa_mesh_local_devices"] == 8
    assert lines["pilosa_mesh_shards_per_device"] >= 1
    assert lines["pilosa_mesh_psum_dispatches_total"] > 0
    eng.close()


def test_weighted_local_shards_route_to_mesh():
    """With capacity-weighted ownership, the 8-device node's local shard
    set is the supermajority — and every local shard routes through the
    fused path (no host loop), while the executor still composes remote
    shards over the mapper (asserted structurally: _local_shards honors
    the weighted placement)."""
    holder = Holder()
    holder.open()
    _build_local(holder)
    me = Node("big", "http://localhost:1", devices=8)
    peer = Node("small", "http://localhost:2", devices=1)
    c = Cluster(node=me, replica_n=1)
    c.nodes = sorted([me, peer], key=lambda n: n.id)
    c.holder = holder
    c.state = "NORMAL"
    ex = Executor(holder, cluster=c)
    local = ex._local_shards("i", list(range(8)))
    assert len(local) >= 6, local  # ~8/9 of shards in expectation
    # And the peer's view agrees — the two ownership maps partition the
    # shard space (no orphan, no double-own at replica_n=1).
    remote = [
        s for s in range(8) if c.owns_shard("small", "i", s)
    ]
    assert sorted(local + remote) == list(range(8))

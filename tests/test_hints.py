"""Hinted handoff (docs/durability.md "Hinted handoff"): writes to a
DOWN owner durably queue as per-(node, index, shard) hint records and a
replay worker drains them to the recovered owner BEFORE bounded reads or
anti-entropy readmit it — destructive writes become ackable under
single-owner failure, and the queue bound makes degradation explicit
(overflow/expiry falls back verbatim to the PR 11 skip-or-fail-loud
policy).

The in-process lane: a real multi-node harness cluster with a
HintManager attached to the coordinator, replay driven synchronously
(``replay_pending``) so every ordering assertion is deterministic.  The
multi-process partition drill lives in test_chaos_drill.py."""

import json
import os
import time

import pytest

from pilosa_tpu.api import ApiError, ImportRequest, QueryRequest
from pilosa_tpu.cluster.hints import HintManager
from pilosa_tpu.cluster.syncer import HolderSyncer
from pilosa_tpu.executor.executor import Error as ExecError
from pilosa_tpu.ops import SHARD_WIDTH
from pilosa_tpu.util.stats import (
    METRIC_HINTS_DROPPED,
    METRIC_HINTS_QUEUED,
    METRIC_HINTS_REPLAYED,
    REGISTRY,
)

from harness import run_cluster

N_SHARDS = 8


def _hints_counters():
    return {
        "queued": REGISTRY.counter(METRIC_HINTS_QUEUED).get(),
        "replayed": REGISTRY.counter(METRIC_HINTS_REPLAYED).get(),
        "overflow": REGISTRY.counter(
            METRIC_HINTS_DROPPED, reason="overflow"
        ).get(),
        "expired": REGISTRY.counter(
            METRIC_HINTS_DROPPED, reason="expired"
        ).get(),
    }


def _delta(before):
    after = _hints_counters()
    return {k: after[k] - before[k] for k in before}


def _setup(tmp_path, n=3, replica_n=2):
    h = run_cluster(tmp_path, n, replica_n=replica_n)
    client = h.client(0)
    client.create_index("i")
    client.create_field("i", "f")
    cols = [s * SHARD_WIDTH + 3 for s in range(N_SHARDS)]
    h[0].api.import_bits(
        ImportRequest("i", "f", row_ids=[1] * len(cols), column_ids=cols)
    )
    return h, cols


def _attach_hints(h, i=0, **kw):
    """Wire a HintManager onto node i's cluster (the harness default is
    hints=None — the PR 11 policy — so tests opt in explicitly).  The
    replay worker is NOT started; tests drive replay synchronously."""
    kw.setdefault("journal", h[i].journal)
    mgr = HintManager(h[i].data_dir, node_id=h[i].node_id, **kw)
    mgr.cluster = h[i].cluster
    h[i].cluster.hints = mgr
    return mgr


def _shard_owned_by(h, owners):
    for s in range(64):
        ids = {n.id for n in h[0].cluster.shard_nodes("i", s)}
        if ids == owners:
            return s
    pytest.skip(f"no shard owned by exactly {owners} in 64 probes")


def _frag_bit(srv, shard, row, col):
    frag = srv.holder.fragment("i", "f", "standard", shard)
    return frag is not None and frag.bit(row, col)


def test_all_owners_down_last_resort_read_is_observable(tmp_path):
    """ISSUE satellite: the all-owners-DOWN read path falls back to the
    primary in replica order — no longer silently: counted as
    pilosa_replica_reads_total{route="last_resort"}, journaled, and
    annotated by the /debug/plans analyzer."""
    from pilosa_tpu.util.stats import METRIC_REPLICA_READS

    h, _ = _setup(tmp_path)
    try:
        s = _shard_owned_by(h, {"node1", "node2"})
        h[0].cluster.node_failed("node1")
        h[0].cluster.node_failed("node2")
        before = REGISTRY.counter(
            METRIC_REPLICA_READS, route="last_resort"
        ).get()
        resp = h[0].api.query(
            QueryRequest("i", "Count(Row(f=1))", shards=[s], profile=True)
        )
        # The verdict is wrong in-process (both servers actually serve),
        # so the last-resort read still answers exactly.
        assert resp.results[0] == 1
        assert (
            REGISTRY.counter(METRIC_REPLICA_READS, route="last_resort").get()
            > before
        )
        assert any(
            e.fields.get("shard") == s
            for e in h[0].journal.events("replica.last_resort")
        )
        assert any(
            a.startswith("all owners DOWN: last-resort primary read")
            for a in resp.plan["annotations"]
        ), resp.plan["annotations"]
    finally:
        h.close()


def test_destructive_clear_acks_and_queues_under_down_owner(tmp_path):
    """THE tentpole behavior: a Clear whose shard has a DOWN owner used
    to fail loudly (anti-entropy would revert it); with a hint queue it
    ACKS — survivors apply now, the miss queues durably — and replay
    delivers the clear to the recovered owner, after which no replica
    holds the bit."""
    h, _ = _setup(tmp_path)
    try:
        s = _shard_owned_by(h, {"node1", "node2"})
        col = s * SHARD_WIDTH + 3
        by_id = {srv.node_id: srv for srv in h.servers}
        assert _frag_bit(by_id["node1"], s, 1, col)
        assert _frag_bit(by_id["node2"], s, 1, col)

        mgr = _attach_hints(h)
        h[0].cluster.node_failed("node1")
        before = _hints_counters()
        assert h[0].api.query(
            QueryRequest("i", f"Clear({col}, f=1)")
        ).results[0] is True
        assert mgr.pending("node1") == 1
        assert _delta(before)["queued"] == 1
        # The survivor applied the clear; the DOWN owner (its server is
        # actually alive in-process — only the verdict marks it) still
        # holds the bit: exactly the pre-replay divergence.
        assert not _frag_bit(by_id["node2"], s, 1, col)
        assert _frag_bit(by_id["node1"], s, 1, col)

        # Recovery + replay: the hint lands, the queue drains, the file
        # is gone, and the recovered owner no longer holds the bit.
        h[0].cluster.node_recovered("node1")
        assert mgr.replay_pending() == 1
        assert mgr.pending("node1") == 0
        assert _delta(before)["replayed"] == 1
        assert not _frag_bit(by_id["node1"], s, 1, col)
        assert not os.path.exists(
            os.path.join(h[0].data_dir, ".hints", "node1.log")
        )
    finally:
        h.close()


def test_clear_import_acks_and_replays_under_down_owner(tmp_path):
    """The bulk path: an explicit clear-import with a DOWN owner acks
    (per-shard import_bits hint records) and replay converges the
    recovered owner bit-exactly."""
    h, cols = _setup(tmp_path)
    try:
        mgr = _attach_hints(h)
        h[0].cluster.node_failed("node1")
        n1_shards = [
            s for s in range(N_SHARDS)
            if any(
                n.id == "node1" for n in h[0].cluster.shard_nodes("i", s)
            )
        ]
        assert n1_shards, "placement gave node1 no shards?"
        clear_cols = [s * SHARD_WIDTH + 3 for s in n1_shards]
        h[0].api.import_bits(
            ImportRequest(
                "i", "f", row_ids=[1] * len(clear_cols),
                column_ids=clear_cols,
            ),
            clear=True,
        )
        assert mgr.pending("node1") == len(n1_shards)
        by_id = {srv.node_id: srv for srv in h.servers}
        # Not yet delivered to the DOWN owner.
        assert any(
            _frag_bit(by_id["node1"], s, 1, s * SHARD_WIDTH + 3)
            for s in n1_shards
        )
        h[0].cluster.node_recovered("node1")
        assert mgr.replay_pending() == 1
        for s in n1_shards:
            assert not _frag_bit(by_id["node1"], s, 1, s * SHARD_WIDTH + 3)
    finally:
        h.close()


def test_overflow_falls_back_to_pr11_policy(tmp_path):
    """The bound makes degradation EXPLICIT: with the queue full, a
    destructive write fails loudly (the pre-hint policy) with the drop
    counted as overflow, and an additive set still acks by skipping the
    dead owner (anti-entropy seeds it later)."""
    h, _ = _setup(tmp_path)
    try:
        mgr = _attach_hints(h, max_bytes=1)  # nothing fits
        h[0].cluster.node_failed("node1")
        s = _shard_owned_by(h, {"node1", "node2"})
        col = s * SHARD_WIDTH + 3
        before = _hints_counters()
        with pytest.raises(ExecError, match="Clear unavailable"):
            h[0].api.query(QueryRequest("i", f"Clear({col}, f=1)"))
        with pytest.raises(ApiError, match="clear import unavailable"):
            h[0].api.import_bits(
                ImportRequest("i", "f", row_ids=[1], column_ids=[col]),
                clear=True,
            )
        d = _delta(before)
        assert d["overflow"] >= 2
        assert d["queued"] == 0
        assert mgr.pending("node1") == 0
        # Additive set: skip-and-ack, exactly as before hints existed.
        assert h[0].api.query(
            QueryRequest("i", f"Set({col + 1}, f=1)")
        ).results[0] is True
    finally:
        h.close()


def test_partial_destructive_hint_rolls_back_on_gate_failure(tmp_path):
    """All-or-nothing for destructive writes: with TWO owners DOWN and
    room for only ONE hint record, the Clear fails loudly (no ack) and
    the one absorbed hint is ROLLED BACK — a hint surviving a failed
    write would replay an op that never happened onto one replica."""
    h, _ = _setup(tmp_path, n=3, replica_n=3)
    try:
        # replica_n=3 of 3 nodes: node0 (live) + node1/node2 DOWN.
        mgr = _attach_hints(h, max_bytes=150)  # one ~120B record fits
        h[0].cluster.node_failed("node1")
        h[0].cluster.node_failed("node2")
        col = 3
        before = _hints_counters()
        with pytest.raises(ExecError, match="Clear unavailable"):
            h[0].api.query(QueryRequest("i", f"Clear({col}, f=1)"))
        assert mgr.pending("node1") == 0 and mgr.pending("node2") == 0, (
            "a failed destructive write left an orphaned hint"
        )
        d = _delta(before)
        assert d["queued"] == 1  # one record WAS absorbed...
        rolled = REGISTRY.counter(
            METRIC_HINTS_DROPPED, reason="rolled_back"
        ).get()
        assert rolled >= 1  # ...and unwound under its own reason
    finally:
        h.close()


def test_multi_shard_import_rollback_spans_earlier_shards(tmp_path):
    """The cross-shard half of all-or-nothing: a clear-import whose
    FIRST shard's hint fits but whose SECOND overflows must fail the
    whole batch AND unwind shard one's hint — the grouping loop runs
    before any apply, so every absorbed miss is a phantom."""
    h, _ = _setup(tmp_path)
    try:
        h[0].cluster.node_failed("node1")
        n1_shards = [
            s for s in range(N_SHARDS)
            if any(
                n.id == "node1" for n in h[0].cluster.shard_nodes("i", s)
            )
        ]
        if len(n1_shards) < 2:
            pytest.skip("placement gave node1 fewer than 2 shards")
        # Budget sized for ONE per-shard import hint record (~170 B),
        # not two.
        mgr = _attach_hints(h, max_bytes=200)
        cols = [s * SHARD_WIDTH + 3 for s in n1_shards[:2]]
        with pytest.raises(ApiError, match="clear import unavailable"):
            h[0].api.import_bits(
                ImportRequest(
                    "i", "f", row_ids=[1, 1], column_ids=cols
                ),
                clear=True,
            )
        assert mgr.pending("node1") == 0, (
            "the earlier shard's hint survived a failed batch"
        )
        rolled = REGISTRY.counter(
            METRIC_HINTS_DROPPED, reason="rolled_back"
        ).get()
        assert rolled >= 1
    finally:
        h.close()


def test_all_owners_down_write_fails_loudly_not_last_resort(tmp_path):
    """A WRITE whose every owner is DOWN must fail loudly like
    _write_replicated — never ride the last-resort READ path (which
    would mislabel the metric and bypass the destructive gate)."""
    from pilosa_tpu.util.stats import METRIC_REPLICA_READS

    h, _ = _setup(tmp_path)
    try:
        s = _shard_owned_by(h, {"node1", "node2"})
        h[0].cluster.node_failed("node1")
        h[0].cluster.node_failed("node2")
        before = REGISTRY.counter(
            METRIC_REPLICA_READS, route="last_resort"
        ).get()
        with pytest.raises(ExecError, match="write unavailable"):
            h[0].api.query(
                QueryRequest("i", "ClearRow(f=1)", shards=[s])
            )
        assert (
            REGISTRY.counter(METRIC_REPLICA_READS, route="last_resort").get()
            == before
        ), "a write counted as a last-resort READ"
    finally:
        h.close()


def test_hint_records_are_durable_and_torn_tail_tolerated(tmp_path):
    """The [storage] ack promise applies to hints: at ``logged`` an
    enqueued record survives coordinator SIGKILL (simulated by
    reconstructing the manager over the same directory), seq stamps
    resume monotonically, and a torn tail — SIGKILL mid-append — keeps
    the intact prefix like the fragment op-log replay."""
    h, _ = _setup(tmp_path)
    try:
        mgr = _attach_hints(h)
        h[0].cluster.node_failed("node1")
        s = _shard_owned_by(h, {"node1", "node2"})
        for k in range(3):
            h[0].api.query(
                QueryRequest("i", f"Clear({s * SHARD_WIDTH + 3 + k}, f=1)")
            )
        assert mgr.pending("node1") == 3
        mgr.close()

        # "SIGKILL" + restart: a fresh manager over the same dir.
        mgr2 = HintManager(h[0].data_dir, node_id="node0")
        assert mgr2.pending("node1") == 3
        seqs = [r["seq"] for r in mgr2._queues["node1"].records]
        assert seqs == sorted(seqs) and len(set(seqs)) == 3
        mgr2.close()

        # Torn tail: garbage appended mid-record keeps the 3 intact.
        p = os.path.join(h[0].data_dir, ".hints", "node1.log")
        with open(p, "ab") as f:
            f.write(b'{"seq": 99, "index": "i", "trunc')
        mgr3 = HintManager(h[0].data_dir, node_id="node0")
        assert mgr3.pending("node1") == 3
        # The truncation repaired the file on disk too.
        with open(p, "rb") as f:
            lines = [ln for ln in f.read().split(b"\n") if ln]
        assert len(lines) == 3 and all(json.loads(ln) for ln in lines)
        mgr3.close()
    finally:
        h.close()


def test_expiry_drops_and_falls_back(tmp_path):
    """hint-max-age: records older than the bound are dropped (counted,
    journaled) — the fallback policy owns the outcome from there."""
    h, _ = _setup(tmp_path)
    try:
        mgr = _attach_hints(h, max_age=0.05)
        h[0].cluster.node_failed("node1")
        s = _shard_owned_by(h, {"node1", "node2"})
        before = _hints_counters()
        h[0].api.query(QueryRequest("i", f"Clear({s * SHARD_WIDTH + 3}, f=1)"))
        assert mgr.pending("node1") == 1
        time.sleep(0.08)
        assert mgr.expire() == 1
        assert mgr.pending("node1") == 0
        assert _delta(before)["expired"] == 1
    finally:
        h.close()


def test_quarantine_holds_until_hints_drained(tmp_path):
    """Replay-before-readmission: a recovered node's bounded-read
    quarantine does NOT release on anti-entropy progress alone while
    un-replayed hints for it exist — locally queued OR peer-advertised
    — and releases exactly once when both conditions land."""
    h, _ = _setup(tmp_path)
    try:
        mgr = _attach_hints(h)
        c0 = h[0].cluster
        c0.recovery_holddown = 0.0
        c0.node_failed("node1")
        s = _shard_owned_by(h, {"node1", "node2"})
        h[0].api.query(QueryRequest("i", f"Clear({s * SHARD_WIDTH + 3}, f=1)"))
        assert mgr.pending("node1") == 1

        # Recovery + AE progress, but the hint is still queued: held.
        c0.note_heartbeat("node1", ae_passes=0)  # baseline
        c0.note_heartbeat("node1", ae_passes=1)
        assert not c0.replica_fresh("node1", "i", 1e9)
        assert "node1" in c0._read_quarantine

        # Drain, then the SAME evidence releases — exactly once.
        assert mgr.replay_pending() == 1
        c0.note_heartbeat("node1", ae_passes=1)
        assert "node1" not in c0._read_quarantine

        def releases():
            return [
                e for e in h[0].journal.events("cluster.quarantine.release")
                if e.fields.get("node") == "node1"
            ]

        assert len(releases()) == 1
        c0.note_heartbeat("node1", ae_passes=2)
        assert len(releases()) == 1  # no double release

        # Peer-ADVERTISED hints hold it too: re-quarantine, drain
        # locally, but node2 says it still holds 3 hints for node1.
        c0.node_failed("node1")
        c0.note_heartbeat("node2", pending_hints={"node1": 3})
        c0.note_heartbeat("node1", ae_passes=2)
        c0.note_heartbeat("node1", ae_passes=3)
        assert "node1" in c0._read_quarantine
        assert c0.hints_pending_for("node1") == 3
        # node2's advertisement clears (its queue drained): released.
        c0.note_heartbeat("node2", pending_hints={})
        c0.note_heartbeat("node1", ae_passes=3)
        assert "node1" not in c0._read_quarantine
    finally:
        h.close()


def test_syncer_replay_before_antientropy_ordering(tmp_path):
    """The anti-entropy half of the ordering: (a) a replica we hold
    hints for is EXCLUDED from merges until its queue drains, (b) our
    own pass DEFERS (journaled, ae_passes unchanged) while any peer
    advertises hints for us — the majority-tie-to-set merge can never
    run against a replica missing a queued clear."""
    h, _ = _setup(tmp_path)
    try:
        mgr = _attach_hints(h)
        c0 = h[0].cluster
        syncer = HolderSyncer(h[0].holder, c0, journal=h[0].journal)

        s = _shard_owned_by(h, {"node0", "node1"})
        assert any(n.id == "node1" for n in syncer._replicas("i", s))
        c0.node_failed("node1")
        h[0].api.query(QueryRequest("i", f"Set({s * SHARD_WIDTH + 77}, f=1)"))
        assert mgr.pending("node1") == 1
        c0.node_recovered("node1")
        # Alive again, but hints are still pending: node1 stays
        # excluded from merges.
        assert not any(n.id == "node1" for n in syncer._replicas("i", s))
        assert mgr.replay_pending() == 1
        assert any(n.id == "node1" for n in syncer._replicas("i", s))

        # (b) a peer holds hints for THIS node: the pass defers.  The
        # syncer's synchronous pre-pass check fetches node2's REAL
        # /status advertisement, so the hint must exist in node2's
        # actual manager (a hand-set advertisement would be overwritten
        # by the refresh — that refresh IS the race fix).
        mgr2 = _attach_hints(h, i=2)
        assert mgr2.enqueue(
            "node0", "i", 0, {"kind": "query", "query": "Clear(0, f=1)"}
        )
        before = c0.ae_passes
        syncer.sync_holder()
        assert c0.ae_passes == before
        assert h[0].journal.events("antientropy.deferred")
        # Advertisement cleared (node2's queue dropped): the pass runs
        # and counts again.
        mgr2.drop_node("node0")
        syncer.sync_holder()
        assert c0.ae_passes == before + 1
    finally:
        h.close()


def test_bsi_value_import_hints_under_down_owner(tmp_path):
    """BSI value imports rewrite bit planes (destructive even on the
    set path): with a DOWN owner they ack via the hint queue and the
    replay delivers the exact planes."""
    h, _ = _setup(tmp_path)
    try:
        h.client(0).create_field("i", "v", {"type": "int", "min": 0, "max": 1000})
        mgr = _attach_hints(h)
        from pilosa_tpu.api import ImportValueRequest

        h[0].cluster.node_failed("node1")
        s = _shard_owned_by(h, {"node1", "node2"})
        col = s * SHARD_WIDTH + 9
        h[0].api.import_values(
            ImportValueRequest("i", "v", column_ids=[col], values=[42])
        )
        assert mgr.pending("node1") == 1
        h[0].cluster.node_recovered("node1")
        assert mgr.replay_pending() == 1
        by_id = {srv.node_id: srv for srv in h.servers}
        out = by_id["node1"].api.query(
            QueryRequest(
                "i", f"Count(Range(v == 42))", shards=[s], remote=True
            )
        )
        assert out.results[0] == 1
    finally:
        h.close()


def test_write_replicated_hint_survives_for_additive_sets(tmp_path):
    """Additive sets hint too (faster convergence than waiting for a
    full anti-entropy pass), and the degraded-batches counter does NOT
    tick for a hinted batch — hinting is not degradation."""
    from pilosa_tpu.util.stats import METRIC_INGEST_DEGRADED_BATCHES

    h, _ = _setup(tmp_path)
    try:
        mgr = _attach_hints(h)
        h[0].cluster.node_failed("node1")
        s = _shard_owned_by(h, {"node1", "node2"})
        col = s * SHARD_WIDTH + 200
        before = REGISTRY.counter(METRIC_INGEST_DEGRADED_BATCHES).get()
        h[0].api.import_bits(
            ImportRequest("i", "f", row_ids=[1], column_ids=[col])
        )
        assert mgr.pending("node1") == 1
        assert (
            REGISTRY.counter(METRIC_INGEST_DEGRADED_BATCHES).get() == before
        ), "a hinted batch must not count as degraded"
        h[0].cluster.node_recovered("node1")
        mgr.replay_pending()
        by_id = {srv.node_id: srv for srv in h.servers}
        assert _frag_bit(by_id["node1"], s, 1, col)
    finally:
        h.close()

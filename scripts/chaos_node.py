"""One pilosa-tpu node for the chaos drills.

tests/test_chaos_drill.py spawns its cluster members through this file.
The node id ``n0`` is the coordinator; every other
node seeds from SEED_PORT.  Fast failure detection (0.2 s probes,
suspicion x2) and a short anti-entropy interval make the drills land
in seconds instead of minutes.

  python scripts/chaos_node.py NODE_ID HTTP_PORT GOSSIP_PORT \
      SEED_PORT DATA_DIR [--replicas 2] [--ack logged] \
      [--ae-interval 1.5] [--recovery-holddown-ms 15000] \
      [--hint-max-bytes N] [--replica-read MODE]

``--recovery-holddown-ms`` matters for the partition drills: the
default 15 s holddown (docs/durability.md) is the production guard
against acceptor-wedged flapping, but a heal-and-measure drill wants
recovery within a couple of gossip probes.  ``--hint-max-bytes 0``
disables hinted handoff (the PR 11 skip-or-fail-loud policy) so a
drill can demonstrate the before/after.

Prints ``READY <node_id>`` on stdout once serving, then sleeps until
killed — the callers SIGKILL/terminate it by design.
"""

from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("node_id")
    ap.add_argument("http_port", type=int)
    ap.add_argument("gossip_port", type=int)
    ap.add_argument("seed_port", type=int)
    ap.add_argument("data_dir")
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--ack", default="logged")
    ap.add_argument("--ae-interval", type=float, default=1.5)
    ap.add_argument("--recovery-holddown-ms", type=float, default=15000.0)
    ap.add_argument("--hint-max-bytes", type=int, default=None)
    ap.add_argument("--replica-read", default=None)
    args = ap.parse_args()

    from pilosa_tpu.config import Config
    from pilosa_tpu.server import Server

    cfg = Config()
    cfg.data_dir = args.data_dir
    cfg.bind = f"localhost:{args.http_port}"
    cfg.cluster_coordinator = args.node_id == "n0"
    cfg.cluster_replicas = args.replicas
    cfg.storage_ack = args.ack
    cfg.anti_entropy_interval = args.ae_interval
    cfg.cluster_recovery_holddown_ms = args.recovery_holddown_ms
    if args.hint_max_bytes is not None:
        cfg.cluster_hint_max_bytes = args.hint_max_bytes
    if args.replica_read is not None:
        cfg.cluster_replica_read = args.replica_read
    cfg.gossip_port = args.gossip_port
    if args.node_id != "n0":
        cfg.gossip_seeds = [f"127.0.0.1:{args.seed_port}"]
    cfg.gossip_probe_interval = 0.2
    cfg.gossip_probe_timeout = 0.2
    cfg.gossip_suspicion_mult = 2
    srv = Server(cfg)
    srv.node_id = args.node_id
    srv.open()
    print(f"READY {args.node_id}", flush=True)
    time.sleep(600)


if __name__ == "__main__":
    main()

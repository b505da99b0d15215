#!/usr/bin/env python3
"""Mine recorded query plans for shared Row subtrees — the fusion
sizing evidence (docs/fusion.md "Sizing the win on real traffic").

    # live server
    python scripts/plan_miner.py --url http://localhost:10101 --window 60
    # saved dump
    curl -s localhost:10101/debug/plans?limit=128 > plans.json
    python scripts/plan_miner.py --file plans.json --json

Reports, per time window: distinct masks, total mask evaluations the
per-query execution paid, and the evaluations a whole-program fuse
would have saved — the same canonicalization the fused planner uses,
so the projection is directly comparable to the live
``pilosa_engine_fused_program_masks_{evaluated,referenced}_total``
counters after the traffic rides the fused path."""

from __future__ import annotations

import argparse
import json
import sys
import urllib.request


def main(argv=None) -> int:
    sys.path.insert(0, __file__.rsplit("/", 2)[0])
    from pilosa_tpu.util import plan_miner

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    src = ap.add_mutually_exclusive_group(required=True)
    src.add_argument("--url", help="server base URL (fetches /debug/plans)")
    src.add_argument("--file", help="saved /debug/plans JSON document")
    ap.add_argument(
        "--window", type=float, default=60.0,
        help="sharing window in seconds (default 60; 0 = one window)",
    )
    ap.add_argument(
        "--limit", type=int, default=128,
        help="plans to request from a live server (default 128)",
    )
    ap.add_argument("--top", type=int, default=20,
                    help="top shared subtrees to list (default 20)")
    ap.add_argument("--json", action="store_true",
                    help="emit the raw JSON report")
    args = ap.parse_args(argv)

    if args.url:
        url = args.url.rstrip("/") + f"/debug/plans?limit={args.limit}"
        with urllib.request.urlopen(url, timeout=30) as resp:
            doc = json.load(resp)
    else:
        with open(args.file) as f:
            doc = json.load(f)
    plans = plan_miner.flatten_plans(doc)
    report = plan_miner.mine(plans, window_s=args.window, top=args.top)
    if args.json:
        json.dump(report, sys.stdout, indent=2)
        print()
    else:
        print(plan_miner.render(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())

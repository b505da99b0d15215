"""Shared-subtree miner over recorded query plans (docs/fusion.md).

The ROADMAP's "size the win before building" evidence tool for
whole-program fusion: scan the plans recorded at ``GET /debug/plans``
(PR 9) for Row subtrees repeated across DIFFERENT queries within a time
window, and report fusion-opportunity stats — distinct masks, total
mask evaluations the per-query execution paid, and the evaluations a
whole-program fuse of each window would have saved.  This is the same
canonicalization the fused planner hash-conses masks by
(``parallel/fusion.subtree_texts``), so the report's "projected saves"
is exactly what ``pilosa_engine_fused_program_masks_*_total`` will
record once the traffic rides the fused path — the claim is checkable
on real traffic, before and after.

``scripts/plan_miner.py`` is the CLI wrapper (live server or a saved
/debug/plans dump)."""

from __future__ import annotations

from typing import Dict, Iterable, List

# Top-level call names whose first child is a mask (Row) tree; a bare
# bitmap call is its own mask.
_MASK_PARENTS = ("Count", "Sum", "Min", "Max", "TopN", "Rows", "GroupBy")
_BITMAP_CALLS = ("Row", "Union", "Intersect", "Difference", "Xor", "Not",
                 "Range")


def _mask_trees(call) -> list:
    """The mask (Row-tree) roots a top-level call evaluates."""
    if call.name in _BITMAP_CALLS:
        return [call]
    if call.name in _MASK_PARENTS and call.children:
        return [ch for ch in call.children if ch.name in _BITMAP_CALLS]
    return []


def plan_masks(query_text: str) -> List[str]:
    """Every mask-subtree text a recorded query evaluates (one entry
    per OCCURRENCE — repeats across the query's own calls count).
    Unparseable / truncated plan texts yield []."""
    from ..parallel.fusion import subtree_texts
    from ..pql import parser as pql_parser

    try:
        q = pql_parser.parse(query_text)
    except Exception:  # noqa: BLE001 — recorded text may be truncated
        return []
    out: List[str] = []
    for call in q.calls:
        for tree in _mask_trees(call):
            # Every subtree is a potential shared mask: the fused
            # planner hash-conses at all levels, so mine at all levels.
            out.extend(sorted(subtree_texts(tree)))
    return out


def flatten_plans(doc) -> List[dict]:
    """Plan dicts from a /debug/plans document (recent ring + slow
    retention, deduped), a bare list, or {"plans": [...]}."""
    if isinstance(doc, list):
        plans = list(doc)
    else:
        plans = list(doc.get("recent") or doc.get("plans") or [])
        for worst in (doc.get("slow") or {}).values():
            plans.extend(worst)
    seen = set()
    out = []
    for p in plans:
        key = (p.get("traceID"), p.get("startTime"), p.get("query"))
        if key in seen:
            continue
        seen.add(key)
        out.append(p)
    return out


def mine(plans: Iterable[dict], window_s: float = 60.0,
         top: int = 20) -> dict:
    """Fusion-opportunity report over recorded plans.

    Plans are bucketed into ``window_s`` windows by their recorded
    ``startTime`` (a fused drain can only merge queries that are in
    flight together; a window approximates a drain's reach across a
    dashboard burst).  Within each (index, window), a mask subtree
    occurring k times costs the sequential path k evaluations and a
    fused drain exactly 1 — so ``projected_evals_saved`` sums (k - 1)
    over every repeated subtree."""
    windows: Dict[tuple, Dict[str, int]] = {}
    mask_queries: Dict[tuple, set] = {}
    n_queries = 0
    for p in plans:
        text = p.get("query")
        if not text:
            continue
        masks = plan_masks(text)
        if not masks:
            continue
        n_queries += 1
        ts = float(p.get("startTime") or 0.0)
        wkey = (p.get("index"), int(ts // window_s) if window_s else 0)
        bucket = windows.setdefault(wkey, {})
        for m in masks:
            bucket[m] = bucket.get(m, 0) + 1
            mask_queries.setdefault((p.get("index"), m), set()).add(
                (text, wkey[1])
            )
    total_evals = 0
    distinct = 0
    saved = 0
    per_mask: Dict[tuple, dict] = {}
    for (index, w), bucket in windows.items():
        for m, k in bucket.items():
            total_evals += k
            distinct += 1
            saved += k - 1
            agg = per_mask.setdefault(
                (index, m),
                {"mask": m, "index": index, "occurrences": 0,
                 "windows": 0, "evals_saved": 0},
            )
            agg["occurrences"] += k
            agg["windows"] += 1
            agg["evals_saved"] += k - 1
    for (index, m), agg in per_mask.items():
        agg["queries"] = len(
            {q for q, _w in mask_queries.get((index, m), ())}
        )
    ranked = sorted(
        per_mask.values(),
        key=lambda a: (-a["evals_saved"], -a["occurrences"], a["mask"]),
    )
    return {
        "windowSeconds": window_s,
        "windows": len(windows),
        "queries": n_queries,
        "distinctMasks": distinct,
        "maskEvaluations": total_evals,
        "projectedEvalsSaved": saved,
        "projectedSavedFraction": (
            round(saved / total_evals, 4) if total_evals else 0.0
        ),
        "topShared": ranked[: max(0, int(top))],
    }


def render(report: dict) -> str:
    """Human-readable report table."""
    lines = [
        f"plans mined: {report['queries']} queries over "
        f"{report['windows']} window(s) of {report['windowSeconds']:g}s",
        f"mask evaluations: {report['maskEvaluations']} "
        f"({report['distinctMasks']} distinct) — fusion would save "
        f"{report['projectedEvalsSaved']} "
        f"({100 * report['projectedSavedFraction']:.1f}%)",
    ]
    if report["topShared"]:
        lines.append("top shared subtrees (evals saved / occurrences / "
                     "distinct queries):")
        for a in report["topShared"]:
            if a["evals_saved"] <= 0:
                continue
            lines.append(
                f"  {a['evals_saved']:6d} / {a['occurrences']:6d} / "
                f"{a['queries']:4d}  [{a['index']}] {a['mask']}"
            )
    return "\n".join(lines)

"""Whole-program query compilation: plan a heterogeneous drain into ONE
device program (docs/fusion.md).

Batch-CSE (engine._dispatch_count_batch) dedups *identical* Counts and
the result memo serves *repeats*; this module handles the remaining —
and, for dashboard traffic, dominant — shape: Count/Sum/Min/Max/TopN
queries that *share Row sub-expressions* without being identical.  The
planner canonicalizes every query's Row subtree by text, hash-conses
shared subtrees into MASK SLOTS (each evaluated once on device), and
lowers the whole drain to one ``kernels.fused_tree`` dispatch that fans
each materialized mask into every consuming reduce.

Compile-key discipline (the fixed-tier scheme, generalized): the fused
executable is keyed on the multiset of (op-kind, mask-slot) edges —
mask-slot progs carry row ids as traced slot-vector data, the slot list
and each op kind's edge list pad to pow2 tiers, and lowering follows
item order deterministically — so two drains with the same sharing
topology reuse one executable regardless of which rows they ask about.

The sparse block-occupancy planner keeps working per-mask: a Count
whose tree shares nothing with its drain-mates is probed against the
engine's occupancy summaries and, when eligible, peels onto the
block-gather kernels (its own small dispatch riding the same drain);
shared masks stay in the fused program where materializing once is the
win.

Decode helpers here are the single source of truth for turning each
op's device output back into the engine's public result shapes — the
fused path, the batcher's solo (pipelined single-op) path, and the
engine's synchronous wrappers must never drift apart, and
tests/test_fusion.py pins them differentially against the sequential
oracle.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from jax.sharding import PartitionSpec as P

from ..util import plans as plans_mod
from ..util import tracing
from . import kernels
from .mesh import put_global

# Sentinel a decoder returns when the fused path declines an item the
# caller must re-route (e.g. a TopN whose candidate union exceeds
# MAX_TOPN_CANDIDATES falls back to the two-phase composition).
DECLINED = object()

# Op-kind display names for plan records.
OP_NAMES = {
    "count": "Count",
    "sum": "Sum",
    "min": "Min",
    "max": "Max",
    "topn": "TopN",
    "topnf": "TopN",
    "group": "GroupBy",
    "tanimoto": "TopN",
}


def op_signature(kind: str, spec: dict) -> str:
    """Canonical text of an aggregate op spec — the result-memo's
    signature for non-Count ops (engine.memo_key_op), same discipline
    as _entry_sort_key's build ordering text."""
    if kind in ("sum", "min", "max"):
        return f"{kind}|{spec['field']}|{spec.get('filter')}"
    if kind == "topn":
        return f"topn|{spec['field']}|{spec['src']}|{list(spec.get('rows') or ())}"
    if kind == "group":
        return (
            f"group|{list(spec.get('fields') or ())}|"
            f"{[list(r) for r in spec.get('rows') or ()]}|{spec.get('filter')}"
        )
    return (
        f"topnf|{spec['field']}|{spec.get('src')}|{spec.get('n')}|"
        f"{spec.get('threshold')}|{spec.get('row_ids')}"
    )


def op_fields(kind: str, spec: dict, collect_fields):
    """Every field an op's version tokens must cover: the aggregated
    field itself plus the filter/src tree's fields (walked by the
    engine's collector).  None when the tree isn't walkable — the op
    then skips the memo entirely, correctness first."""
    if kind == "group":
        fields = set(spec.get("fields") or ())
    else:
        fields = {spec["field"]}
    tree = (
        spec.get("filter")
        if kind in ("sum", "min", "max", "group")
        else spec.get("src")
    )
    if tree is not None:
        sub = collect_fields(tree)
        if sub is None:
            return None
        fields |= sub
    return fields

def _pow2(n: int) -> int:
    return max(1, 1 << (max(1, n) - 1).bit_length())


def subtree_texts(call, out=None) -> set:
    """Canonical text of every subtree of a call tree — the sharing key
    the planner (and the /debug/plans miner) hash-cons masks by."""
    if out is None:
        out = set()
    if call is None:
        return out
    out.add(str(call))
    for ch in call.children:
        subtree_texts(ch, out)
    return out


def item_texts(spec: dict) -> set:
    """The subtree texts of one drain item's mask tree(s)."""
    kind = spec["kind"]
    if kind == "count":
        return subtree_texts(spec["call"])
    if kind in ("sum", "min", "max", "group"):
        return subtree_texts(spec.get("filter"))
    return subtree_texts(spec.get("src"))


def _entry_sort_key(entry) -> tuple:
    """Canonical build order: the planner lowers entries in THIS order
    (not arrival order), so two drains carrying the same multiset of
    (index, op-kind, mask) items produce byte-identical fspecs — and
    reuse one executable — no matter how their queries interleaved on
    the wire.  The compile-key property test pins this.  ``entry`` is
    an (index, spec, shards) triple (cross-index drains sort by index
    within an op kind)."""
    index, spec, shards = entry
    kind = spec["kind"]
    if kind == "count":
        t = str(spec["call"])
    elif kind in ("sum", "min", "max"):
        t = f"{spec['field']}|{spec.get('filter')}"
    elif kind == "topn":
        t = f"{spec['field']}|{spec['src']}|{list(spec.get('rows') or ())}"
    elif kind == "group":
        t = (
            f"{list(spec.get('fields') or ())}|"
            f"{[list(r) for r in spec.get('rows') or ()]}|{spec.get('filter')}"
        )
    else:
        t = (
            f"{spec['field']}|{spec['src']}|{spec.get('n')}|"
            f"{spec.get('threshold')}|{spec.get('row_ids')}"
        )
    return (kind, str(index), t, tuple(shards))


# -- decode helpers (shared by fused, solo, and sync paths) ------------------


def decode_sum(host, depth: int, base_min: int):
    """(counts[D], n) device pair -> (total, count), exactly
    MeshEngine.sum's host assembly."""
    counts, n = host
    counts = np.asarray(counts)
    total = sum(int(counts[i]) << i for i in range(depth))
    n = int(n)
    return total + n * base_min, n


def decode_min_max(host, canonical, base_min: int, is_min: bool):
    """(hi[S], lo[S], counts[S]) -> (value, count), exactly
    MeshEngine.min_max's ValCount reduce."""
    his, los, counts = host
    best_val, best_n = 0, 0
    for si in range(len(canonical)):
        n = int(counts[si])
        if n == 0:
            continue
        val = (int(his[si]) << 31) | int(los[si])
        if best_n == 0 or (val < best_val if is_min else val > best_val):
            best_val, best_n = val, n
    if best_n == 0:
        return 0, 0
    return best_val + base_min, best_n


def decode_topn_scores(host, present, pos: dict):
    """(scores[K, S], src_counts[S]) -> (scores[S, K], src_counts, pos),
    exactly MeshEngine.topn_scores' host transform."""
    dev_scores, dev_counts = host
    scores = np.array(dev_scores).T
    scores[:, ~present] = 0
    return scores, dev_counts, pos


def decode_topn_full(host, cands, n_out):
    """The solo fused-TopN readback (device-trimmed or full totals),
    exactly MeshEngine.topn_full's host decode."""
    from ..core import cache as cache_mod

    if host is None:
        return []
    if n_out is None:
        totals = np.asarray(host)
        pairs = [
            (cands[k], int(totals[k]))
            for k in range(len(cands))
            if totals[k] > 0
        ]
        pairs.sort(key=cache_mod.pair_sort_key)
        return pairs
    vals, top_idx = host
    return [
        (cands[int(i)], int(v))
        for v, i in zip(vals, top_idx)
        if v > 0 and int(i) < len(cands)
    ]


def decode_topn_full_scores(host, host_cnt, cands, threshold: int, n_out):
    """Host-side replica of topn_full_tree's gates + trim over a fused
    per-shard score matrix: gate = (row_count >= thr) & (score >= thr)
    per (candidate, shard), totals summed over shards, then the same
    descending-value lowest-index-tie trim jax.lax.top_k applies.  Bit
    equality with the device-trim path is pinned by test_fusion.py."""
    from ..core import cache as cache_mod

    scores, _src_counts = host
    scores = np.asarray(scores).astype(np.int64)
    thr = max(int(threshold), 1)
    gate = (host_cnt.T >= thr) & (scores >= thr)
    totals = np.where(gate, scores, 0).sum(axis=1)
    if n_out is None:
        pairs = [
            (cands[k], int(totals[k]))
            for k in range(len(cands))
            if totals[k] > 0
        ]
        pairs.sort(key=cache_mod.pair_sort_key)
        return pairs
    order = np.argsort(-totals, kind="stable")[: int(n_out)]
    return [
        (cands[int(i)], int(totals[int(i)]))
        for i in order
        if totals[int(i)] > 0 and int(i) < len(cands)
    ]


# -- the planner -------------------------------------------------------------


class FusedDispatch:
    """One dispatched fused drain: the device result pytree, a per-item
    decoder over its fetched host twin, per-item device-cost weights
    (footprint-proportional — the attribution fix for the even split),
    per-item plan-note extras, and per-item build errors."""

    __slots__ = ("dev", "decoders", "weights", "item_notes", "errors")

    def __init__(self, dev, decoders, weights, item_notes, errors):
        self.dev = dev
        self.decoders = decoders
        self.weights = weights
        self.item_notes = item_notes
        self.errors = errors


class FusedPlan:
    """A compiled drain plan, REUSABLE across dispatches: the static
    fspec + operand list + decoders, plus the stack version tokens that
    gate reuse.  Dashboards repeat — the same drain shape arrives every
    refresh tick — so the engine caches plans keyed on the drain's
    canonical entry keys and re-dispatches without re-lowering, exactly
    the field-stack/TopN-candidate invalidation discipline: any write
    to a referenced view bumps its version token and the plan rebuilds
    (``MeshEngine._fused_plan_for``)."""

    __slots__ = (
        "index", "indexes", "fspec", "specs", "operands", "decoders",
        "weights", "item_notes", "errors", "sparse", "have_fused",
        "n_items", "fused_riders", "masks_evaluated", "masks_referenced",
        "planes_per_request", "planes_per_drain", "stack_tokens",
        "canonical", "cacheable", "edge_kinds",
    )


def dispatch(engine, plan: FusedPlan) -> FusedDispatch:
    """Dispatch a (possibly cached) fused plan: peeled sparse masks on
    the block-gather kernels, the fused program as one kernels.fused_tree
    call, dispatch-note + counters.  Must run under the engine's
    dispatch lock (the caller is MeshEngine.fused_many_async)."""
    extras = []
    for splan, mask in plan.sparse:
        extras.append(engine._dispatch_sparse(splan, mask))
        # The peeled item's note was captured into its item_notes at
        # build time; drop the fresh TLS note so it can't pollute the
        # shared batch note below.
        plans_mod.take_dispatch_note()
    if plan.have_fused:
        engine._note_fused_dispatch()
        # The drain record: the tier is the mask slots compiled for,
        # live the items that ride the program.
        drain = engine._note_drain(
            "Fused", "fused_program", len(plan.fspec[0]), plan.fused_riders,
            plan.planes_per_request, plan.planes_per_drain,
        )
        with tracing.stage("dispatch", **drain):
            fused_out = kernels.fused_tree(
                engine.mesh, plan.fspec, plan.specs, *plan.operands
            )
    else:
        fused_out = ()
    plans_mod.note_dispatch(
        path="fused_program",
        fused=True,
        fused_queries=plan.n_items,
        masks_evaluated=plan.masks_evaluated,
        masks_referenced=plan.masks_referenced,
        masks_tier=len(plan.fspec[0]) if plan.have_fused else 0,
        bytes_touched=plan.planes_per_drain[1] * engine.PLANE_BYTES,
        fused_indexes=len(plan.indexes),
    )
    # Counters record what actually rode a fused program: a drain whose
    # items all resolved const/peeled/errored dispatched no program and
    # must not inflate the queries-per-program ratio.
    if plan.have_fused:
        engine.fused_programs += 1
        engine.fused_program_queries += plan.fused_riders
        engine.fused_masks_evaluated += plan.masks_evaluated
        engine.fused_masks_referenced += plan.masks_referenced
        engine._fused_counters[0].inc()
        if plan.fused_riders:
            engine._fused_counters[1].inc(plan.fused_riders)
        if plan.masks_evaluated:
            engine._fused_counters[2].inc(plan.masks_evaluated)
        if plan.masks_referenced:
            engine._fused_counters[3].inc(plan.masks_referenced)
        # Per-kind edge counters (satellite observability: how much of
        # the fused traffic is counts vs device-trim TopN vs GroupBy).
        edge_counter = getattr(engine, "_fused_edge_counter", None)
        if edge_counter is not None:
            for ekind, n in plan.edge_kinds.items():
                if n:
                    edge_counter(ekind).inc(n)
        combos = sum(
            n.get("fusedGroupBy", 0) for n in plan.item_notes if n
        )
        if combos:  # per request, as group_counts_async counts them
            engine._group_combos_counter.inc(combos)
    return FusedDispatch(
        (fused_out, tuple(extras)), plan.decoders, plan.weights,
        plan.item_notes, plan.errors,
    )


def _slot_rows(prog) -> int:
    """Shard rows a slot's OWN prog sweeps (mrefs cost nothing here —
    their slots carry their own cost)."""
    kind = prog[0]
    if kind in ("row", "rowm", "rowb"):
        return 1
    if kind == "range":
        pspec = prog[3]
        return pspec[2] if pspec[0] == "slice" else len(pspec[1])
    if kind == "between":
        pspec = prog[2]
        return pspec[2] if pspec[0] == "slice" else len(pspec[1])
    if kind in ("zero", "mref", "ones"):
        return 0
    return sum(_slot_rows(p) for p in prog[1:])


def _slot_refs(prog, out: set):
    """Slot indices a prog references directly."""
    if not isinstance(prog, tuple):
        return out
    if prog[0] == "mref":
        out.add(prog[1])
        return out
    for p in prog[1:]:
        if isinstance(p, tuple):
            _slot_refs(p, out)
    return out


def _item_hints(engine, index, spec) -> dict:
    """Row-hint map of ONE fused item: every (index, field, view) stack
    the item reads -> the row ids it reads there (None = the whole
    stack, e.g. a BSI plane walk or a TopN candidate sweep).  Feeds
    the drain lowering's ``row_hints`` — so a fused item missing a
    partial stack requests promotion of exactly its rows, not the full
    stack."""
    from ..core.view import VIEW_STANDARD, view_bsi_name

    kind = spec["kind"]
    hints: dict = {}
    if kind == "count":
        hints = engine._collect_row_hints(index, spec["call"])
    elif kind in ("sum", "min", "max"):
        hints[(index, spec["field"], view_bsi_name(spec["field"]))] = None
        if spec.get("filter") is not None:
            engine._collect_row_hints(index, spec["filter"], hints)
    elif kind == "topn":
        hints[(index, spec["field"], VIEW_STANDARD)] = {
            int(r) for r in spec["rows"]
        }
        engine._collect_row_hints(index, spec["src"], hints)
    elif kind == "topnf":
        # Ranked-cache candidate sweep: the whole standard stack.
        hints[(index, spec["field"], VIEW_STANDARD)] = None
        engine._collect_row_hints(index, spec["src"], hints)
    elif kind == "group":
        for fname, rows in zip(
            spec.get("fields") or (), spec.get("rows") or ()
        ):
            hints[(index, fname, VIEW_STANDARD)] = {int(r) for r in rows}
        if spec.get("filter") is not None:
            engine._collect_row_hints(index, spec["filter"], hints)
    return hints


def merge_hints(into: dict, hints: dict) -> dict:
    """Merge one item's hint map into a drain-wide map: None (whole
    stack) dominates, row sets union."""
    for key, rows in hints.items():
        if rows is None or into.get(key, ()) is None:
            into[key] = None
        else:
            into.setdefault(key, set()).update(rows)
    return into


def build(engine, entries: List[tuple]) -> FusedPlan:
    """Plan one heterogeneous drain (no dispatch — ``dispatch()`` runs
    the plan, possibly many times).  ``entries`` is a list of
    (index, spec, shards) triples — a drain may SPAN indexes and still
    compile to ONE program: mask slots are hash-consed per
    (index, subtree text), every edge consumes operands shaped to its
    own index's shard axis, and the kernel reduces each edge to
    replicated outputs before stacking.  Must run under the engine's
    dispatch lock (the caller is MeshEngine.fused_drain_async)."""
    from .engine import _Lowering

    n_items = len(entries)
    canonicals: dict = {}
    lw = _Lowering(engine, None, slot_vector=True)
    lw.canonical_map = canonicals

    slots: list = []          # lowered progs, dependency order
    slot_of: Dict[tuple, int] = {}  # (index, subtree text) -> slot
    slot_hits: List[int] = []  # textual references per slot
    refs_total = [0]

    def lower_shared(index, call):
        """Hash-consing lowering: every distinct (index, subtree text)
        becomes one mask slot; repeats resolve to ("mref", j).
        Combinators recurse through the cache so INNER shared subtrees
        (the dashboard's segment filter inside N Intersects) share
        too.  The index rides the key so a cross-index drain never
        aliases same-text subtrees of different indexes."""
        refs_total[0] += 1
        lw.current_index = index
        key = (index, str(call))
        j = slot_of.get(key)
        if j is not None:
            slot_hits[j] += 1
            return ("mref", j)
        name = call.name
        if name in ("Union", "Intersect", "Difference", "Xor") and call.children:
            op = {
                "Union": "or",
                "Intersect": "and",
                "Difference": "andnot",
                "Xor": "xor",
            }[name]
            prog = (op,) + tuple(
                lower_shared(index, ch) for ch in call.children
            )
        elif name == "Not" and call.children:
            from ..core.index import EXISTENCE_FIELD_NAME

            exist = engine._lower_row(index, EXISTENCE_FIELD_NAME, 0, lw)
            prog = ("andnot", exist, lower_shared(index, call.children[0]))
        else:
            prog = engine._lower(index, call, lw)
        j = len(slots)
        slots.append(prog)
        slot_of[key] = j
        slot_hits.append(1)
        return ("mref", j)

    # Pre-compute each item's subtree texts for the peel decision (a
    # Count sharing nothing may take the occupancy-guided sparse path).
    # Sharing is decided from a one-pass occurrence map — a pairwise
    # set-intersection sweep is O(n^2) and this runs under the engine
    # dispatch lock.  Texts are keyed per index: equal texts in
    # different indexes are NOT shared masks.
    texts = [
        {(idx, t) for t in item_texts(spec)} for idx, spec, _ in entries
    ]
    text_items: Dict[str, int] = {}
    for ts in texts:
        for t in ts:
            text_items[t] = text_items.get(t, 0) + 1
    # Stacks consumed OUTSIDE the fused lowering (the sparse peels use
    # their own _Lowering): they must join the plan's version-token
    # gate too, or a write to a peeled Count's field would not be
    # detected and a cached plan would re-dispatch stale (or donated)
    # matrices and stale occupancy block lists.
    peel_stacks: dict = {}

    count_edges: list = []    # (slot, i_mask)
    agg_edges: list = []      # static edge tuples, build order
    agg_arity: list = []
    edge_of: Dict[tuple, tuple] = {}  # dedup key -> ("count"|"agg", idx)
    sparse: list = []         # peeled (sparse_plan, mask) pairs
    # Per item: ("count", edge_idx) | ("agg", edge_idx, decode_fn) |
    # ("extra", idx) | ("const", value) | ("error", exc)
    routes: list = [None] * n_items
    top_slot: List[Optional[int]] = [None] * n_items
    reduce_rows = [0.0] * n_items
    item_notes: list = [None] * n_items
    sparse_notes: list = [None] * n_items
    extra_notes: list = [None] * n_items  # per-item plan-note stamps

    from ..core.view import VIEW_STANDARD, view_bsi_name

    # Empty-canonical (no shards) per-index const results — cross-index
    # drains route these INSIDE the build so one empty index never
    # blanks its drain-mates.
    _EMPTY = {
        "count": 0, "sum": (0, 0), "min": (0, 0), "max": (0, 0),
        "topn": None, "topnf": [], "group": DECLINED,
    }

    # Row hints for the WHOLE drain, merged across items before any
    # stack fetch: a fused item missing a partial (pool) stack then
    # requests promotion of exactly the drain's touched rows instead of
    # the full stack — previously fused drains promoted full stacks
    # only (None hint), defeating block-granular residency for
    # dashboard traffic.  Best effort: a malformed item raises again in
    # its own lowering below and routes to ("error", ...).
    for idx_h, spec_h, _ in entries:
        try:
            merge_hints(lw.row_hints, _item_hints(engine, idx_h, spec_h))
        except Exception:  # noqa: BLE001
            pass

    # Canonical build order (compile-key discipline): slot numbering and
    # edge order follow the sorted entries, never arrival order.
    order = sorted(range(n_items), key=lambda k: _entry_sort_key(entries[k]))
    for i in order:
        index, spec, shards = entries[i]
        kind = spec["kind"]
        lw.current_index = index
        try:
            canonical = lw.canonical_for(index)
            if not canonical:
                routes[i] = ("const", _EMPTY[kind])
                continue
            if kind == "count":
                call = spec["call"]
                shared = any(text_items[t] > 1 for t in texts[i])
                if not shared and engine.sparse_enabled and not engine.multiproc:
                    # Per-mask sparse planning survives fusion: an
                    # unshared low-occupancy Count peels onto the
                    # block-gather kernels instead of paying the fused
                    # program's dense sweep.
                    lw1 = _Lowering(engine, canonical)
                    lw1.row_hints = lw.row_hints
                    prog1 = engine._lower(index, call, lw1)
                    mask1 = engine._mask_words(shards, canonical)
                    plan = engine._sparse_plan(prog1, lw1, shards, canonical)
                    peel_stacks.update(lw1._stacks)
                    if plan is not None:
                        # Claim the occupancy-probe note for THIS item
                        # only — the shared batch note must not charge
                        # batchmates the skipped bytes.  dispatch() adds
                        # the sparse-path fields the real dispatch notes.
                        probe_note = plans_mod.take_dispatch_note() or {}
                        probe_note.update(
                            path="sparse", fused=True,
                            bytes_skipped=int(plan[5]),
                        )
                        sparse_notes[i] = probe_note
                        routes[i] = ("extra", len(sparse))
                        sparse.append((plan, mask1))
                        # Peeled items ride the drain's readback window
                        # but sweep only their surviving blocks; a small
                        # flat footprint keeps their share honest.
                        reduce_rows[i] = 0.25
                        continue
                    plans_mod.take_dispatch_note()  # drop the occupancy probe
                ref = lower_shared(index, call)
                j = ref[1]
                top_slot[i] = j
                i_mask = lw.add_mask(engine._mask_words(shards, canonical))
                ekey = ("count", j, i_mask)
                hit = edge_of.get(ekey)
                if hit is None:
                    hit = edge_of[ekey] = ("count", len(count_edges))
                    count_edges.append((j, i_mask))
                routes[i] = hit
            elif kind in ("sum", "min", "max"):
                field = spec["field"]
                filter_call = spec.get("filter")
                idx_obj = engine.holder.index(index)
                f = idx_obj.field(field) if idx_obj is not None else None
                bsig = f.bsi_group(field) if f is not None else None
                stack = (
                    lw.stack_for(index, field, view_bsi_name(field))
                    if bsig is not None
                    else None
                )
                if bsig is None or stack is None:
                    routes[i] = ("const", (0, 0))
                    continue
                depth = bsig.bit_depth()
                if filter_call is None:
                    ms = -1
                else:
                    ms = lower_shared(index, filter_call)[1]
                    top_slot[i] = ms
                i_mask = lw.add_mask(engine._mask_words(shards, canonical))
                i_pm = lw.add_matrix(stack.matrix)
                pspec = engine._plane_spec(stack, depth)
                if kind == "sum":
                    edge = ("sum", ms, i_mask, i_pm, pspec)
                    dec = _SumDecode(depth, bsig.min)
                else:
                    edge = ("minmax", ms, i_mask, i_pm, pspec, kind == "min")
                    dec = _MinMaxDecode(
                        list(canonical), bsig.min, kind == "min"
                    )
                ekey = edge + (field,)
                hit = edge_of.get(ekey)
                if hit is None:
                    hit = edge_of[ekey] = (
                        "agg", len(agg_edges), dec
                    )
                    agg_edges.append(edge)
                    agg_arity.append(2 if kind == "sum" else 3)
                routes[i] = hit
                reduce_rows[i] = depth + 1
            elif kind in ("topn", "topnf"):
                field = spec["field"]
                src = spec["src"]
                stack = lw.stack_for(index, field, VIEW_STANDARD)
                if stack is None:
                    routes[i] = (
                        ("const", None) if kind == "topn" else ("const", [])
                    )
                    continue
                if kind == "topn":
                    rows = list(spec["rows"])
                    present = np.asarray(
                        [r in stack.row_index for r in rows], dtype=bool
                    )
                    K_pad = _pow2(len(rows)) if rows else 1
                    idx_np = np.asarray(
                        [stack.row_index.get(r, 0) for r in rows]
                        + [0] * (K_pad - len(rows)),
                        dtype=np.int32,
                    )
                    dec = _TopNScoresDecode(
                        len(rows), present, dict(stack.pos)
                    )
                    dedup_rows = tuple(rows)
                    n_out = thr = None
                    device = False
                else:
                    row_ids = spec.get("row_ids")
                    entry = engine._topn_candidates(
                        index, field, stack, row_ids
                    )
                    if not entry.cands:
                        routes[i] = ("const", [])
                        continue
                    if len(entry.cands) > engine.MAX_TOPN_CANDIDATES:
                        routes[i] = ("const", DECLINED)
                        continue
                    K_pad = entry.host_cnt.shape[1]
                    idx_np = np.asarray(
                        [stack.row_index.get(r, 0) for r in entry.cands]
                        + [0] * (K_pad - len(entry.cands)),
                        dtype=np.int32,
                    )
                    n = int(spec.get("n") or 0)
                    n_out = min(n, K_pad) if n and not row_ids else None
                    thr = max(int(spec.get("threshold") or 1), 1)
                    device = n_out is not None and bool(
                        getattr(engine, "topn_device_trim", True)
                    )
                    if device:
                        # Device trim: the gate + exact psum totals +
                        # top_k run INSIDE the fused program and the
                        # host decodes n (id, count) pairs instead of
                        # re-ranking K candidates per readback
                        # (decode_topn_full_scores stays as the
                        # differential oracle — flip
                        # engine.topn_device_trim to compare).
                        dec = _TopNDeviceDecode(list(entry.cands), n_out)
                    else:
                        dec = _TopNFullDecode(
                            entry.host_cnt, list(entry.cands), thr, n_out
                        )
                    dedup_rows = tuple(entry.cands)
                ms = lower_shared(index, src)[1]
                top_slot[i] = ms
                i_mask = lw.add_mask(engine._mask_words(shards, canonical))
                i_cm = lw.add_matrix(stack.matrix)
                ekey = (
                    kind, ms, i_mask, i_cm, field, dedup_rows, n_out, thr,
                    device,
                )
                hit = edge_of.get(ekey)
                if hit is None:
                    i_ix = lw.add_replicated(
                        put_global(engine.mesh, idx_np, P())
                    )
                    if device:
                        edge = (
                            "topnf", ms, i_mask, i_cm, i_ix,
                            lw.add_matrix(entry.dev_cnt),
                            lw.add_replicated(engine._scalar(thr)),
                            n_out,
                        )
                    else:
                        edge = ("topn", ms, i_mask, i_cm, i_ix)
                    hit = edge_of[ekey] = ("agg", len(agg_edges), dec)
                    agg_edges.append(edge)
                    agg_arity.append(2)
                routes[i] = hit
                reduce_rows[i] = K_pad
                if device:
                    extra_notes[i] = {"topkDevice": int(n_out)}
            elif kind == "group":
                fields = list(spec.get("fields") or ())
                row_lists = [list(r) for r in spec.get("rows") or ()]
                filter_call = spec.get("filter")
                if not fields or spec.get("aggregate"):
                    # The edge emits a count tensor only: an aggregated
                    # GroupBy runs its solo program (the batcher never
                    # fuses one); handed one directly, decline.
                    routes[i] = ("const", DECLINED)
                    continue
                combos = 1
                for rows in row_lists:
                    combos *= max(len(rows), 1)
                if combos > engine.MAX_GROUPS:
                    # Same bound as group_counts_async, on the count
                    # tensor read back: the host iterator handles it
                    # (DECLINED -> None at the batched entry point).
                    routes[i] = ("const", DECLINED)
                    continue
                g_mats = []
                g_idx = []
                g_dims = []
                missing = False
                for fname, rows in zip(fields, row_lists):
                    stack = lw.stack_for(index, fname, VIEW_STANDARD)
                    if stack is None:
                        missing = True
                        break
                    engine._require_full_stack(
                        index, fname, VIEW_STANDARD, stack
                    )
                    t = tuple(stack.row_index.get(r, 0) for r in rows)
                    # Gather-free whole-row-table lists stay static
                    # compile keys; arbitrary subsets ride traced
                    # operands (group_tree's idx_specs discipline).
                    if kernels.gather_free(t):
                        g_idx.append(t)
                    else:
                        g_idx.append(
                            lw.add_replicated(
                                put_global(
                                    engine.mesh,
                                    np.asarray(t, dtype=np.int32),
                                    P(),
                                )
                            )
                        )
                    g_mats.append(lw.add_matrix(stack.matrix))
                    g_dims.append(len(rows))
                if missing:
                    routes[i] = ("const", DECLINED)
                    continue
                if filter_call is None:
                    ms = -1
                else:
                    ms = lower_shared(index, filter_call)[1]
                    top_slot[i] = ms
                i_mask = lw.add_mask(engine._mask_words(shards, canonical))
                edge = (
                    "group", ms, i_mask, tuple(g_mats), tuple(g_idx),
                    engine._group_pallas,
                )
                ekey = edge + (
                    tuple(fields),
                    tuple(tuple(r) for r in row_lists),
                )
                hit = edge_of.get(ekey)
                if hit is None:
                    dec = _GroupDecode(tuple(g_dims))
                    hit = edge_of[ekey] = ("agg", len(agg_edges), dec)
                    agg_edges.append(edge)
                    agg_arity.append(1)
                routes[i] = hit
                reduce_rows[i] = float(sum(g_dims))
                extra_notes[i] = {"fusedGroupBy": int(combos)}
            else:
                raise ValueError(f"unknown fused item kind: {kind!r}")
        except Exception as e:  # noqa: BLE001 — one bad item must not
            routes[i] = ("error", e)  # fail its drain-mates
    lw.finish()

    # -- sharing accounting + footprint weights -----------------------------
    reach_cache: Dict[int, frozenset] = {}

    def reachable(j: int) -> frozenset:
        got = reach_cache.get(j)
        if got is None:
            acc = {j}
            for r in _slot_refs(slots[j], set()):
                acc |= reachable(r)
            got = reach_cache[j] = frozenset(acc)
        return got

    sharers: Dict[int, int] = {}
    item_reach: List[frozenset] = []
    for i in range(n_items):
        r = reachable(top_slot[i]) if top_slot[i] is not None else frozenset()
        item_reach.append(r)
        for j in r:
            sharers[j] = sharers.get(j, 0) + 1
    weights = []
    for i in range(n_items):
        w = reduce_rows[i]
        for j in item_reach[i]:
            w += _slot_rows(slots[j]) / sharers[j]
        weights.append(max(w, 0.25))

    masks_evaluated = len(slots)
    masks_referenced = refs_total[0]
    indexes = sorted({idx for idx, _, _ in entries})
    for i in range(n_items):
        if routes[i] is None or routes[i][0] == "error":
            continue
        shared_with = (
            sharers.get(top_slot[i], 1) - 1 if top_slot[i] is not None else 0
        )
        note = {
            "op": OP_NAMES[entries[i][1]["kind"]],
            "path": "fused_program",
            "mask_shared_with": shared_with,
        }
        if len(indexes) > 1:
            note["crossIndex"] = True
        if extra_notes[i] is not None:
            note.update(extra_notes[i])
        if sparse_notes[i] is not None:
            note.update(sparse_notes[i])
            note["op"] = "Count"
            note["path"] = "sparse"
        item_notes[i] = note

    # -- tier padding (compile-key discipline) ------------------------------
    M = len(slots)
    if slots:
        slots = slots + [slots[0]] * (_pow2(M) - M)
    n_count = len(count_edges)
    if count_edges:
        count_edges = count_edges + [count_edges[0]] * (
            _pow2(n_count) - n_count
        )
    padded_aggs = list(agg_edges)
    for k in ("sum", "minmax", "topn", "topnf", "group"):
        kind_edges = [e for e in agg_edges if e[0] == k]
        if kind_edges:
            padded_aggs.extend(
                [kind_edges[0]] * (_pow2(len(kind_edges)) - len(kind_edges))
            )

    # -- plan assembly ------------------------------------------------------
    # Output positions: counts vector first (when present), then each
    # REAL aggregate edge's components in build order (padding appended
    # after, so real positions are stable).
    base = 1 if count_edges else 0
    agg_pos = []
    off = base
    for a in agg_arity:
        agg_pos.append(off)
        off += a

    decoders: list = [None] * n_items
    errors: list = [None] * n_items
    for i in range(n_items):
        r = routes[i]
        if r is None:
            errors[i] = RuntimeError("fused planner produced no route")
            continue
        tag = r[0]
        if tag == "error":
            errors[i] = r[1]
        elif tag == "const":
            decoders[i] = _Const(r[1])
        elif tag == "extra":
            decoders[i] = _Extra(r[1])
        elif tag == "count":
            decoders[i] = _Count(r[1])
        else:  # ("agg", edge_idx, decode_fn)
            decoders[i] = _Agg(agg_pos[r[1]], agg_arity[r[1]], r[2])

    plan = FusedPlan()
    plan.index = indexes[0] if len(indexes) == 1 else None
    plan.indexes = indexes
    plan.have_fused = bool(count_edges or agg_edges)
    plan.fspec = (tuple(slots), tuple(count_edges), tuple(padded_aggs))
    plan.specs = tuple(lw.specs)
    plan.operands = list(lw.operands)
    plan.decoders = decoders
    plan.weights = weights
    plan.item_notes = item_notes
    plan.errors = errors
    plan.sparse = sparse
    plan.n_items = n_items
    plan.fused_riders = sum(
        1 for r in routes if r is not None and r[0] in ("count", "agg")
    )
    plan.masks_evaluated = masks_evaluated
    plan.masks_referenced = masks_referenced
    # The drain record's reckoning (engine._note_drain): the row-planes
    # each item names, summed, and the distinct ones of the whole drain
    # (lw.row_hints is the merge of the items' hints).
    per_item = []
    for idx, spec, _ in entries:
        try:
            per_item.append(engine._hint_planes(_item_hints(engine, idx, spec)))
        except Exception:  # noqa: BLE001 — a malformed item rides as an error
            pass
    plan.planes_per_request = (
        sum(p[0] for p in per_item), sum(p[1] for p in per_item)
    )
    plan.planes_per_drain = engine._hint_planes(lw.row_hints)
    # Real (unpadded) per-kind edge census for the fused-program edge
    # counters (padding is a compile-key artifact, not traffic).
    plan.edge_kinds = {}
    if n_count:
        plan.edge_kinds["count"] = n_count
    for e in agg_edges:
        plan.edge_kinds[e[0]] = plan.edge_kinds.get(e[0], 0) + 1
    # Reuse gates: each index's canonical shard axis and every
    # referenced stack's version token (the field-stack invalidation
    # discipline — any write to a referenced view re-keys its stack and
    # fails the probe, so a cached plan can never serve stale operands).
    plan.canonical = {
        idx: list(lw.canonical_for(idx)) for idx in indexes
    }
    plan.stack_tokens = {
        key: (st is None, None if st is None else st.versions)
        for key, st in {**peel_stacks, **lw._stacks}.items()
    }
    plan.cacheable = not any(errors)
    return plan


# Decoder objects (closures would capture loop vars; these are explicit
# and picklable-ish for debugging).


class _Const:
    __slots__ = ("v",)

    def __init__(self, v):
        self.v = v

    def __call__(self, host):
        return self.v


class _Extra:
    __slots__ = ("i",)

    def __init__(self, i):
        self.i = i

    def __call__(self, host):
        return int(np.asarray(host[1][self.i]))


class _Count:
    __slots__ = ("i",)

    def __init__(self, i):
        self.i = i

    def __call__(self, host):
        return int(np.asarray(host[0][0])[self.i])


class _Agg:
    __slots__ = ("pos", "arity", "dec")

    def __init__(self, pos, arity, dec):
        self.pos = pos
        self.arity = arity
        self.dec = dec

    def __call__(self, host):
        parts = host[0][self.pos : self.pos + self.arity]
        return self.dec(parts)


class _SumDecode:
    __slots__ = ("depth", "base_min")

    def __init__(self, depth, base_min):
        self.depth = depth
        self.base_min = base_min

    def __call__(self, parts):
        return decode_sum(parts, self.depth, self.base_min)


class _MinMaxDecode:
    __slots__ = ("canonical", "base_min", "is_min")

    def __init__(self, canonical, base_min, is_min):
        self.canonical = canonical
        self.base_min = base_min
        self.is_min = is_min

    def __call__(self, parts):
        return decode_min_max(parts, self.canonical, self.base_min, self.is_min)


class _TopNScoresDecode:
    __slots__ = ("k", "present", "pos")

    def __init__(self, k, present, pos):
        self.k = k
        self.present = present
        self.pos = pos

    def __call__(self, parts):
        scores, counts = parts
        # Trim the pow2 candidate padding before the standard transform.
        scores = np.asarray(scores)[: max(self.k, 0)]
        return decode_topn_scores((scores, counts), self.present, self.pos)


class _TopNFullDecode:
    __slots__ = ("host_cnt", "cands", "thr", "n_out")

    def __init__(self, host_cnt, cands, thr, n_out):
        self.host_cnt = host_cnt
        self.cands = cands
        self.thr = thr
        self.n_out = n_out

    def __call__(self, parts):
        return decode_topn_full_scores(
            parts, self.host_cnt, self.cands, self.thr, self.n_out
        )


class _TopNDeviceDecode:
    """Decode a device-trimmed fused TopN edge: (vals[n], ids[n]) where
    the gate + exact totals + top_k all ran on device — the host maps
    candidate indices back to row ids, nothing else.  Bit-exact vs
    _TopNFullDecode (the retained host oracle) by the shared top_k
    tie-break over id-descending candidates; pinned differentially in
    tests/test_topn_device.py."""

    __slots__ = ("cands", "n_out")

    def __init__(self, cands, n_out):
        self.cands = cands
        self.n_out = n_out

    def __call__(self, parts):
        return decode_topn_full(parts, self.cands, self.n_out)


class _GroupDecode:
    """Reshape a fused GroupBy edge's flattened int32[prod(K_i)] counts
    back to the per-field [K1, ..., Kn] tensor group_counts returns."""

    __slots__ = ("dims",)

    def __init__(self, dims):
        self.dims = dims

    def __call__(self, parts):
        (flat,) = parts
        return np.asarray(flat).reshape(self.dims)

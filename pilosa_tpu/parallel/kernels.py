"""Sharded query programs: per-shard device work + ICI collectives.

The reference executes a query per shard in a goroutine and reduces
results over channels/HTTP (executor.go mapReduce :2183-2321).  Here the
shard axis lives on the device mesh and EVERY query is ONE jitted
``shard_map`` dispatch: the engine lowers a PQL call tree to a static
``prog`` (a nested tuple over a flat operand list, see engine._Lowering)
and these programs evaluate it — row gathers, BSI plane walks, candidate
gathers, set algebra, popcounts — fused in the body, with an XLA
collective (``psum``) riding ICI for the reduce.

Nothing here materializes intermediates eagerly: TopN candidate
gathers, BSI plane slices, and filter trees all happen INSIDE the
compiled body (an eager ``stack[:, idxs, :]`` on a 960-shard stack
copies gigabytes per query through the dispatch queue — measured 650 ms
per TopN before this moved in-body).

Field-stack operands are ``uint32[R, S, WORDS]`` — rows MAJOR, the
shard axis S second (sharded over the mesh), so a row slice is a
contiguous per-device HBM block: slicing a non-major axis measured ~7x
slower on v5e (95 vs 705 GB/s effective).  Padding shards are zero.  ``mask`` is the requested-shard
``uint32[S, 1]`` (broadcasts against the word axis); a filter prog of
``("ones",)`` means mask-only.

These are plain-XLA programs: the batched Count program reads its
planes at 89 % of a v5e's HBM roofline (PERF.md §5, taxi cell), and no
hand-written kernel layer is kept for the dense sweep.  The one
exception is GroupBy (``group_tree``), whose bound is the vector unit
and not HBM: XLA re-reads the last field's planes for every prefix of
the nest, a Pallas kernel holds a tile of every operand in VMEM and
reads each plane once (PERF.md §6, PR 34).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..ops import bsi as bsi_ops
from .mesh import SHARD_AXIS


def _pc(x):
    return jax.lax.population_count(x).astype(jnp.int32)


def gather_planes(mat, pspec):
    """uint32[R, S, W] -> uint32[depth+1, S, W] per the static layout:
    a contiguous major-axis slice when possible, else a gather with
    -1 => zeros."""
    if pspec[0] == "slice":
        _, start, n = pspec
        return jax.lax.slice_in_dim(mat, start, start + n, axis=0)
    idxs = pspec[1]
    planes = [mat[i] if i >= 0 else jnp.zeros_like(mat[0]) for i in idxs]
    return jnp.stack(planes, axis=0)


def apply_prog(prog, operands, slots=None):
    """Evaluate a lowered bitmap tree over the local shard block.

    ``slots`` is the fused whole-program mask-slot table (fused_tree):
    a ``("mref", j)`` leaf reads the already-evaluated value of mask
    slot j, which is how a Row subtree shared by several queries of one
    fused program is materialized exactly once."""
    kind = prog[0]
    if kind == "mref":
        return slots[prog[1]]
    if kind == "zero":
        return operands[prog[1]][0]
    if kind == "row":
        mat = operands[prog[1]]
        ref = prog[2]
        # ("sv", j): STATIC slot j of the batch's row-index vector
        # (operand 0, engine._Lowering slot_vector mode); otherwise a
        # replicated scalar operand index.
        idx = operands[0][ref[1]] if isinstance(ref, tuple) else operands[ref]
        return jax.lax.dynamic_index_in_dim(mat, idx, axis=0, keepdims=False)
    if kind == "rowb":
        # Block-pool row gather (tiered residency, docs/residency.md
        # "Predictive promotion & block pool"): the matrix is a packed
        # 2 KiB-block pool uint32[Pcap, S_local, OCC_BLOCK_WORDS] and
        # prog[2] names a replicated int32[OCC_BLOCKS] slot vector
        # mapping each of the row's occupancy blocks to its pool slot.
        # Slot 0 is the reserved all-zero block, so absent blocks (and
        # whole absent rows, via an all-zero vector) read as zeros —
        # presence is DATA, and the compile key depends only on the
        # pool's capacity tier, never the row set.
        mat = operands[prog[1]]
        srow = operands[prog[2]]
        blocks = jnp.take(mat, srow, axis=0)  # [OCC_BLOCKS, S_local, BW]
        return jnp.transpose(blocks, (1, 0, 2)).reshape(mat.shape[1], -1)
    if kind == "rowm":
        # Maskable row gather (batched mode): slot index -1 means the
        # row id doesn't exist — gather row 0 and zero the result, so
        # presence is DATA and every drain compiles one program.
        mat = operands[prog[1]]
        idx = operands[0][prog[2][1]]
        row = jax.lax.dynamic_index_in_dim(
            mat, jnp.maximum(idx, 0), axis=0, keepdims=False
        )
        return jnp.where(idx >= 0, row, jnp.zeros_like(row))
    if kind == "range":
        _, rk, i_mat, pspec, i_bits = prog
        planes = gather_planes(operands[i_mat], pspec)
        bits = operands[i_bits]
        fns = {
            "eq": lambda p: bsi_ops.range_eq(p, bits),
            "neq": lambda p: bsi_ops.range_neq(p, bits),
            "lt": lambda p: bsi_ops.range_lt(p, bits, False),
            "lte": lambda p: bsi_ops.range_lt(p, bits, True),
            "gt": lambda p: bsi_ops.range_gt(p, bits, False),
            "gte": lambda p: bsi_ops.range_gt(p, bits, True),
        }
        return jax.vmap(fns[rk], in_axes=1)(planes)
    if kind == "between":
        _, i_mat, pspec, i_lo, i_hi = prog
        planes = gather_planes(operands[i_mat], pspec)
        lo, hi = operands[i_lo], operands[i_hi]
        return jax.vmap(lambda p: bsi_ops.range_between(p, lo, hi), in_axes=1)(planes)
    subs = [apply_prog(p, operands, slots) for p in prog[1:]]
    out = subs[0]
    for s in subs[1:]:
        if kind == "or":
            out = jnp.bitwise_or(out, s)
        elif kind == "and":
            out = jnp.bitwise_and(out, s)
        elif kind == "andnot":
            out = jnp.bitwise_and(out, jnp.bitwise_not(s))
        elif kind == "xor":
            out = jnp.bitwise_xor(out, s)
        else:
            raise ValueError(f"bad op {kind}")
    return out


def gather_free(idxs) -> bool:
    """True when a static index tuple needs no gather: identity (slice)
    or full-reverse (lax.rev).  ONLY such tuples may be jit-static —
    arbitrary tuples as compile keys would recompile per distinct
    client-controlled id set and grow the executable cache without
    bound; those stay traced operands instead."""
    lst = list(idxs)
    return lst == list(range(len(lst))) or lst == list(
        range(len(lst) - 1, -1, -1)
    )


def gather_rows(mat, idxs):
    """Candidate-row extraction from a rows-major uint32[R, S, W] stack.
    ``idxs`` is either a gather-free static tuple (identity -> slice,
    full-reverse -> lax.rev; the ~125 GB/s materialized gather becomes a
    ~400+ GB/s reindex) or a traced int32[K] vector (jnp.take)."""
    if isinstance(idxs, tuple):
        K, R = len(idxs), mat.shape[0]
        lst = list(idxs)
        if lst == list(range(K)):
            return jax.lax.slice_in_dim(mat, 0, K, axis=0)
        if K == R and lst == list(range(R - 1, -1, -1)):
            return jax.lax.rev(mat, (0,))
        raise ValueError("static idxs must be gather-free (see gather_free)")
    return jnp.take(mat, idxs, axis=0)


# Rows a traced axis of a GroupBy copies one dynamic slice each; a longer
# one is one ``jnp.take`` (see ``slice_rows``).
SLICE_ROWS_MAX = 64


def slice_rows(mat, idxs):
    """``gather_rows`` for a traced int32[K] of a GroupBy axis that a
    ``Rows`` child cut: K dynamic slices (K is static), each a copy of
    one [S, W] row.  ``jnp.take`` on a [250, S, W] stack compiles, for a
    v5e, to a copy of the whole stack first (2.1 GB of scratch at 64
    shards for 10 rows of it: tests/test_tpu_compile.py)."""
    if idxs.shape[0] > SLICE_ROWS_MAX:
        return jnp.take(mat, idxs, axis=0)
    return jnp.stack([
        jax.lax.dynamic_index_in_dim(mat, idxs[k], 0, keepdims=False)
        for k in range(idxs.shape[0])
    ])


def replicate_shards(x, n_dev, axis=0):
    """[.., S_local, ..] -> replicated [.., S_total, ..]: scatter the
    local block at this device's offset and psum.  Equivalent to a tiled
    all_gather, but psum outputs are INFERRED replicated by shard_map's
    vma check on every jax version (tiled all_gather is not)."""
    i = jax.lax.axis_index(SHARD_AXIS)
    local = x.shape[axis]
    shape = list(x.shape)
    shape[axis] = local * n_dev
    out = jnp.zeros(tuple(shape), x.dtype)
    out = jax.lax.dynamic_update_slice_in_dim(out, x, i * local, axis=axis)
    return jax.lax.psum(out, SHARD_AXIS)


def _filter(prog, mask, ops):
    """Masked filter row: the evaluated tree & mask, or the bare mask
    (uint32[S, 1], broadcasting) for prog ("ones",)."""
    if prog == ("ones",):
        return mask
    return jnp.bitwise_and(apply_prog(prog, ops), mask)


# Operand cap per variadic lax.reduce: beyond this the reductions chunk
# (each chunk re-reads the shared operand once — negligible for the
# shared src row vs K candidate planes) to bound compile time.
VARIADIC_CHUNK = 64


def _sum_many(ops_list, axes):
    """K popcount-style reductions over SHARED inputs in ONE pass each:
    a variadic ``lax.reduce`` with an elementwise-add combiner.  XLA
    fuses the virtual elementwise operands (pc(a & b), ...) into the
    reduce loop, so every distinct input plane streams from HBM exactly
    once — where K separate ``jnp.sum`` calls re-read the shared
    operand K times.  Returns a list of reduced arrays in input
    order."""
    out = []
    for c in range(0, len(ops_list), VARIADIC_CHUNK):
        chunk = tuple(ops_list[c : c + VARIADIC_CHUNK])
        outs = jax.lax.reduce(
            chunk,
            tuple(jnp.int32(0) for _ in chunk),
            lambda a, b: tuple(x + y for x, y in zip(a, b)),
            axes,
        )
        out.extend(outs if isinstance(outs, (tuple, list)) else [outs])
    return out


# Above this candidate count the variadic form's K unrolled gather+pc
# nodes make XLA compile time scale with K (MAX_TOPN_CANDIDATES is
# 4096); the broadcast form compiles O(1) and its src re-reads are
# amortized over the much larger candidate plane read at that size.
SCORE_VARIADIC_MAX = 128


def score_rows(cands, src):
    """Per-candidate masked popcount scores: uint32[K, S, W] x
    uint32[S, W] -> int32[K, S] (fragment.go top :1089's per-candidate
    intersection counts).  Small candidate sets (the serving norm) use
    the one-pass variadic reduce — src streamed once per
    VARIADIC_CHUNK candidates, 756 GB/s measured; very large sets fall
    back to the broadcast form to keep compile time bounded."""
    K = cands.shape[0]
    if K > SCORE_VARIADIC_MAX:
        return jnp.sum(_pc(jnp.bitwise_and(cands, src[None, :, :])), axis=-1)
    ops_list = [_pc(cands[k] & src) for k in range(K)]
    return jnp.stack(_sum_many(ops_list, (1,)), axis=0)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def count_tree(mesh, prog, specs, mask, *operands):
    """Count(tree): fused eval + popcount + psum -> replicated int32."""

    def body(m, *ops):
        row = jnp.bitwise_and(apply_prog(prog, ops), m)
        return jax.lax.psum(jnp.sum(_pc(row)), SHARD_AXIS)

    return shard_map(
        body, mesh=mesh, in_specs=(P(SHARD_AXIS),) + specs, out_specs=P()
    )(mask, *operands)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def count_batch_tree(mesh, progs, specs, n_live, *operands):
    """K Count(tree) queries in ONE dispatch: each program evaluates +
    popcounts over the shared operand list (field stacks appear once no
    matter how many queries touch them) and a single psum reduces the
    stacked int32[K] — K answers for one dispatch-floor cost + one
    readback.  This is the serving-tier answer to the JAX per-program
    dispatch floor (~100-400 us): small queries batch K-for-one instead
    of paying it each (BASELINE config #2).

    ``progs`` is a static tuple of (prog, i_mask) pairs — i_mask the
    operand index of that query's requested-shard mask (uint32[S, 1]).
    Its length is the batch TIER (engine.BATCH_TIERS): the program's
    capacity and, with the structure, its whole compile key.  ``n_live``
    is a traced replicated int32 scalar: slot j runs only if j < n_live
    and answers 0 otherwise, so device time follows the drain's live
    count, never the tier.  The branch is real control flow on purpose:
    the engine fills the slots past n_live by re-lowering query 0, row
    ids are slot-vector DATA, and XLA cannot CSE those copies away —
    unconditioned, a tier-64 run read 64 slots' planes for 16 requests
    (61.5 ms against 15.4 on a v5e, PERF.md section 6, PR 27).
    n_live is the same on every device, so the psum stays outside the
    branches and uniform."""

    def body(n, *ops):
        def count(prog, i_mask):
            row = jnp.bitwise_and(apply_prog(prog, ops), ops[i_mask])
            return jnp.sum(_pc(row))

        def skipped():
            # Per-device like the count it stands in for (the branches
            # of a cond must agree on what varies over the mesh).
            return jax.lax.pcast(jnp.int32(0), (SHARD_AXIS,), to="varying")

        outs = [
            jax.lax.cond(
                j < n, functools.partial(count, prog, i_mask), skipped
            )
            for j, (prog, i_mask) in enumerate(progs)
        ]
        return jax.lax.psum(jnp.stack(outs), SHARD_AXIS)

    return shard_map(
        body, mesh=mesh, in_specs=(P(),) + specs, out_specs=P()
    )(n_live, *operands)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def eval_tree(mesh, prog, specs, mask, *operands):
    """Evaluate a tree to its masked uint32[S, WORDS] row stack."""

    def body(m, *ops):
        return jnp.bitwise_and(apply_prog(prog, ops), m)

    return shard_map(
        body, mesh=mesh, in_specs=(P(SHARD_AXIS),) + specs,
        out_specs=P(SHARD_AXIS),
    )(mask, *operands)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def eval_tree_replicated(mesh, prog, specs, mask, *operands):
    """Evaluate a tree to its masked uint32[S, WORDS] row stack,
    REPLICATED to every process: the multi-process variant of eval_tree
    (a sharded output's remote blocks are unaddressable to the
    initiator's device_get, so bitmap materialization on a multi-host
    mesh all-gathers the result over the interconnect — the analogue of
    the reference's remoteExec returning row segments over HTTP,
    executor.go:2142)."""

    def body(m, *ops):
        out = jnp.bitwise_and(apply_prog(prog, ops), m)
        return replicate_shards(out, mesh.shape[SHARD_AXIS], axis=0)

    return shard_map(
        body, mesh=mesh, in_specs=(P(SHARD_AXIS),) + specs, out_specs=P()
    )(mask, *operands)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def topn_tree(mesh, prog, specs, mask, cand_mat, idxs, *operands):
    """TopN phase-1 in ONE dispatch: evaluate the src tree, gather the
    candidate rows in-body, score every candidate per shard
    (fragment.go top :1018/:1089) -> (scores int32[K, S],
    src_counts int32[S]), replicated."""

    def body(m, cmat, ix, *ops):
        src = _filter(prog, m, ops)
        cands = jnp.take(cmat, ix, axis=0)
        srcb = jnp.broadcast_to(src, cmat.shape[1:])
        scores = score_rows(cands, srcb)
        counts = jnp.sum(_pc(srcb), axis=-1)
        # Replicated outputs (tiny int matrices): on a multi-process mesh
        # the caller's device_get only sees addressable shards, so
        # sharded outputs would silently drop remote shards.
        n_dev = mesh.shape[SHARD_AXIS]
        return (
            replicate_shards(scores, n_dev, axis=1),
            replicate_shards(counts, n_dev, axis=0),
        )

    return shard_map(
        body,
        mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(None, SHARD_AXIS), P()) + specs,
        out_specs=(P(), P()),
    )(mask, cand_mat, idxs, *operands)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def topn_full_tree(mesh, prog, specs, n_out, cand_idxs, mask, cand_mat, cnt, thr, *operands):
    """FULL TopN in ONE dispatch: evaluate the src tree, gather + score
    every cache candidate per shard, apply fragment.top's per-shard
    gates (row-count >= threshold AND score >= threshold, which also
    encodes count > 0 since threshold >= 1), psum the exact
    per-candidate totals over ICI, and trim to the top ``n_out`` on
    device — the reference's two-phase TopN (executor.go :694-733:
    approximate phase 1 + exact phase-2 recount) collapsed into one
    program with one tiny readback.

    Candidates are ordered id-DESCENDING by the caller so ``top_k``'s
    stable lowest-index tie-break reproduces the (-count, -id) pair
    sort (cache.go bitmapPairs).  ``n_out=None`` skips the trim and
    returns the full int32[K] totals (the ids= / no-n mode).

    ``cand_idxs`` is a gather-free STATIC tuple when the candidate set
    is the whole row table (the common case), or None — in which case
    the FIRST entry of ``operands``/``specs`` is a traced int32[K]
    index vector (arbitrary, client-controlled candidate sets must not
    become compile keys)."""

    def body(m, cmat, cn, th, *ops):
        if cand_idxs is None:
            ix, *rest = ops
            cands = gather_rows(cmat, ix)
        else:
            rest = ops
            cands = gather_rows(cmat, cand_idxs)
        src = _filter(prog, m, tuple(rest))
        scores = score_rows(cands, jnp.broadcast_to(src, cands.shape[1:]))
        gate = jnp.logical_and(cn >= th, scores >= th)
        totals = jax.lax.psum(
            jnp.sum(jnp.where(gate, scores, 0), axis=1), SHARD_AXIS
        )
        if n_out is None:
            return totals
        vals, top_idx = jax.lax.top_k(totals, n_out)
        return vals, top_idx

    out_specs = P() if n_out is None else (P(), P())
    return shard_map(
        body,
        mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(None, SHARD_AXIS), P(None, SHARD_AXIS), P())
        + specs,
        out_specs=out_specs,
    )(mask, cand_mat, cnt, thr, *operands)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5))
def topn_slab_tree(
    mesh, prog, specs, n_sel, k_out, cand_idxs, mask, cand_mat, cnt, thr,
    *operands,
):
    """Per-shard threshold-prune + top-k SLAB: fragment.top's sequential
    heap walk (fragment.go :1018-1106), vectorized per shard on device.

    The walk visits the shard's ranked-cache pairs in (count desc, id
    desc) order, pushes candidates with score >= threshold until the
    heap holds ``n_sel``, then keeps pushing scores >= the heap min T
    (never popping) and breaks at the first count < T.  Because score
    <= count and the heap min never decreases once full, the emitted
    set is EXACTLY {candidates with score >= T}, where T is the min
    score of the first ``n_sel`` score-qualifying candidates in walk
    order — or the raw threshold when fewer than ``n_sel`` qualify.
    That closed form is what this kernel computes, per shard, with no
    host loop.

    ``cnt`` must be the shard's CACHE counts with cache MEMBERSHIP
    (0 when a candidate is not in that shard's ranked cache): the walk
    only ever visits the shard's own cached pairs.  Candidates are
    id-DESCENDING so both the stable -cnt argsort (walk order) and
    ``top_k``'s lowest-index tie-break reproduce the (-count, -id)
    pair sort.

    Returns (vals int32[S, k_out], idx int32[S, k_out],
    qual int32[S]), replicated.  ``qual[s]`` counts the walk's FULL
    output for shard s; qual > k_out marks a slab overflow — the
    caller falls back to the exact host walk rather than truncate, so
    the merged result is bit-exact by construction.  The compile key
    is (prog, specs, n_sel, k_out, cand_idxs): n and the pow2 k tier
    are static, candidate ids ride data operands."""

    def body(m, cmat, cn, th, *ops):
        if cand_idxs is None:
            ix, *rest = ops
            cands = gather_rows(cmat, ix)
        else:
            rest = ops
            cands = gather_rows(cmat, cand_idxs)
        src = _filter(prog, m, tuple(rest))
        scores = score_rows(cands, jnp.broadcast_to(src, cands.shape[1:]))
        g = jnp.where(jnp.logical_and(cn >= th, scores >= th), scores, 0)
        # Walk order per shard: stable argsort of -cnt over the
        # id-descending candidate axis == (count desc, id desc).
        order = jnp.argsort(-cn, axis=0)
        g_ord = jnp.take_along_axis(g, order, axis=0)
        q = g_ord > 0
        nq = jnp.sum(q, axis=0)
        if n_sel:
            c = jnp.cumsum(q, axis=0)
            a = jnp.where(
                q & (c <= n_sel), g_ord, jnp.iinfo(jnp.int32).max
            )
            t_phase_a = jnp.min(a, axis=0)
            t = jnp.where(nq >= n_sel, t_phase_a, th)
        else:
            # n=0: no trim — the full gated set (T = threshold).
            t = jnp.broadcast_to(th, nq.shape)
        keep = g >= t[None, :]
        qual = jnp.sum(keep, axis=0)
        vals, idx = jax.lax.top_k(jnp.where(keep, g, 0).T, k_out)
        n_dev = mesh.shape[SHARD_AXIS]
        return (
            replicate_shards(vals, n_dev, axis=0),
            replicate_shards(idx, n_dev, axis=0),
            replicate_shards(qual, n_dev, axis=0),
        )

    return shard_map(
        body,
        mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(None, SHARD_AXIS), P(None, SHARD_AXIS), P())
        + specs,
        out_specs=(P(), P(), P()),
    )(mask, cand_mat, cnt, thr, *operands)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def sum_tree(mesh, prog, specs, pspec, mask, plane_mat, *operands):
    """BSI Sum in ONE dispatch: plane slice + filter tree + weighted
    popcounts (fragment.go sum :716-742) -> (int32[D] plane counts,
    int32 considered), replicated.  The Σ 2^i·counts[i] assembly stays
    host-side in arbitrary precision."""

    def body(m, pm, *ops):
        f = _filter(prog, m, ops)
        p = gather_planes(pm, pspec)
        consider = jnp.bitwise_and(p[-1], f)
        # ONE variadic reduce over D+1 popcount operands: the not-null
        # plane (inside ``consider``) loads once per element and is
        # reused across every masked plane instead of re-read per plane
        # (the 553 GB/s vs 755 gap of the two-reduction form).
        depth = p.shape[0] - 1
        ops_list = [_pc(p[i] & consider) for i in range(depth)]
        ops_list.append(_pc(consider))
        outs = _sum_many(ops_list, (0, 1))
        # depth 0 (a BSI group with max == min): no value planes, the
        # total is count * base — jnp.stack([]) would raise.
        counts = (
            jnp.stack(outs[:depth]) if depth else jnp.zeros(0, jnp.int32)
        )
        return (
            jax.lax.psum(counts, SHARD_AXIS),
            jax.lax.psum(outs[depth], SHARD_AXIS),
        )

    return shard_map(
        body,
        mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(None, SHARD_AXIS)) + specs,
        out_specs=(P(), P()),
    )(mask, plane_mat, *operands)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def minmax_tree(mesh, prog, specs, pspec, is_min, mask, plane_mat, *operands):
    """BSI Min/Max in ONE dispatch: word-local per-shard walks
    (fragment.go min/max :745-806 re-founded as bsi.min_valcount — no
    per-plane reduction barriers, one fused pass over the planes) ->
    (hi uint32[S], lo uint32[S], counts int32[S]) with
    value = (hi << 31) | lo, replicated for the host ValCount reduce."""

    def body(m, pm, *ops):
        f = _filter(prog, m, ops)
        p = gather_planes(pm, pspec)
        fb = jnp.broadcast_to(f, p.shape[1:])
        # Direct ND call (no vmap): the variadic argmin-reduce keeps
        # the shard axis as a batch axis and streams the planes ONCE
        # (755 GB/s measured vs 380 for the 3-reduction form).
        hi, lo, counts = bsi_ops.minmax_valcount_nd(p, fb, is_min)
        # Replicated (see topn_tree/replicate_shards): the host ValCount
        # reduce needs EVERY shard's value, including remote processes'.
        n_dev = mesh.shape[SHARD_AXIS]
        return (
            replicate_shards(hi, n_dev, axis=0),
            replicate_shards(lo, n_dev, axis=0),
            replicate_shards(counts, n_dev, axis=0),
        )

    return shard_map(
        body,
        mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(None, SHARD_AXIS)) + specs,
        out_specs=(P(), P(), P()),
    )(mask, plane_mat, *operands)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def fused_tree(mesh, fspec, specs, *operands):
    """Whole-program heterogeneous drain: N queries of mixed op kinds in
    ONE dispatch, with every distinct Row subtree materialized exactly
    once (docs/fusion.md).  The device-side generalization of the
    reference's per-shard map + mapReduce tree (executor.go:2183): where
    count_batch_tree fuses K Counts of one structure, this fuses an
    entire dashboard — Count/Sum/Min/Max/TopN reduces that SHARE filter
    masks — into one program.

    ``fspec`` is the static plan (engine/fusion.py build):

      (mask_slots, count_edges, agg_edges)

    * ``mask_slots``: tuple of lowered progs in dependency order; slot j
      may reference earlier slots via ``("mref", i)`` leaves (the
      hash-cons seam — apply_prog reads the slot table).  Each slot is
      evaluated ONCE into ``uint32[S, W]`` no matter how many queries
      (or other slots) reference it; XLA dead-codes padded duplicates.
    * ``count_edges``: tuple of ``(slot, i_mask)`` — per-edge masked
      popcount, stacked and reduced in ONE psum (int32[n_counts]).
      Slots may belong to DIFFERENT indexes (cross-index drains): every
      edge reduces to replicated scalars/vectors before stacking, so
      mixed per-index shard shapes coexist in one program.
    * ``agg_edges``: tuple of per-edge static descriptors consuming a
      slot (or the bare shard mask when slot < 0, the ("ones",) filter):
        ("sum",    slot, i_mask, i_planes, pspec)       -> counts[D], n
        ("minmax", slot, i_mask, i_planes, pspec, min)  -> hi[S], lo[S], n[S]
        ("topn",   slot, i_mask, i_cands, i_idxs)       -> scores[K,S], src[S]
        ("topnf",  slot, i_mask, i_cands, i_idxs, i_cnt, i_thr, n_sel)
                                                        -> vals[n], ids[n]
        ("group",  slot, i_mask, (i_mat, ...), (idxs | i_idx, ...), pallas)
                                                        -> counts[prod(K_i)]
      Each edge body is the corresponding single-op kernel's body
      verbatim (sum_tree / minmax_tree / topn_tree / topn_full_tree /
      group_tree) with the evaluated slot as its filter row —
      bit-exactness vs the solo programs is by construction, and
      tests/test_fusion.py pins it differentially.  "topnf" runs full
      TopN with the gate + exact psum totals + top-k trim ON DEVICE
      (the dashboard lane's device trim); "group" emits the flattened
      combination tensor (host decode reshapes), per-field row indices
      static tuples when gather-free else traced operand refs.

    Outputs are a flat tuple, replicated: the count vector first (when
    any count edges exist), then each aggregate edge's components in
    edge order.  The compile key is (mesh, fspec, specs) — mask slots
    and per-kind edge lists are padded to pow2 tiers by the planner and
    row ids ride the traced slot vector, so a drain of the same
    (op-kind, mask-slot) multiset reuses one executable regardless of
    which rows it asks about."""
    mask_slots, count_edges, agg_edges = fspec
    n_dev = mesh.shape[SHARD_AXIS]

    def body(*ops):
        slot_vals = []
        for sp in mask_slots:
            slot_vals.append(apply_prog(sp, ops, slot_vals))

        def masked(slot, i_mask):
            if slot < 0:
                return ops[i_mask]  # ("ones",): the bare shard mask
            return jnp.bitwise_and(slot_vals[slot], ops[i_mask])

        outs = []
        if count_edges:
            cs = [
                jnp.sum(_pc(masked(slot, i_mask)))
                for slot, i_mask in count_edges
            ]
            outs.append(jax.lax.psum(jnp.stack(cs), SHARD_AXIS))
        for e in agg_edges:
            kind = e[0]
            if kind == "sum":
                _, slot, i_mask, i_pm, pspec = e
                f = masked(slot, i_mask)
                p = gather_planes(ops[i_pm], pspec)
                consider = jnp.bitwise_and(p[-1], f)
                depth = p.shape[0] - 1
                ops_list = [_pc(p[i] & consider) for i in range(depth)]
                ops_list.append(_pc(consider))
                sums = _sum_many(ops_list, (0, 1))
                counts = (
                    jnp.stack(sums[:depth])
                    if depth
                    else jnp.zeros(0, jnp.int32)
                )
                outs.append(jax.lax.psum(counts, SHARD_AXIS))
                outs.append(jax.lax.psum(sums[depth], SHARD_AXIS))
            elif kind == "minmax":
                _, slot, i_mask, i_pm, pspec, is_min = e
                f = masked(slot, i_mask)
                p = gather_planes(ops[i_pm], pspec)
                fb = jnp.broadcast_to(f, p.shape[1:])
                hi, lo, counts = bsi_ops.minmax_valcount_nd(p, fb, is_min)
                outs.append(replicate_shards(hi, n_dev, axis=0))
                outs.append(replicate_shards(lo, n_dev, axis=0))
                outs.append(replicate_shards(counts, n_dev, axis=0))
            elif kind == "topn":
                _, slot, i_mask, i_cm, i_ix = e
                src = masked(slot, i_mask)
                cands = jnp.take(ops[i_cm], ops[i_ix], axis=0)
                srcb = jnp.broadcast_to(src, cands.shape[1:])
                scores = score_rows(cands, srcb)
                counts = jnp.sum(_pc(srcb), axis=-1)
                outs.append(replicate_shards(scores, n_dev, axis=1))
                outs.append(replicate_shards(counts, n_dev, axis=0))
            elif kind == "topnf":
                # topn_full_tree's body: gate + exact psum totals +
                # device trim.  Candidates id-descending; psum output is
                # replicated so top_k needs no replicate_shards.
                _, slot, i_mask, i_cm, i_ix, i_cnt, i_thr, n_sel = e
                src = masked(slot, i_mask)
                cands = jnp.take(ops[i_cm], ops[i_ix], axis=0)
                scores = score_rows(
                    cands, jnp.broadcast_to(src, cands.shape[1:])
                )
                gate = jnp.logical_and(
                    ops[i_cnt] >= ops[i_thr], scores >= ops[i_thr]
                )
                totals = jax.lax.psum(
                    jnp.sum(jnp.where(gate, scores, 0), axis=1), SHARD_AXIS
                )
                vals, top_idx = jax.lax.top_k(totals, n_sel)
                outs.append(vals)
                outs.append(top_idx)
            elif kind == "group":
                # group_tree's body with a flattened output (the host
                # decoder reshapes to the per-field dims).
                _, slot, i_mask, i_mats, gidx, pallas = e
                f = masked(slot, i_mask)
                grows = []
                for i_pm, gspec in zip(i_mats, gidx):
                    gix = gspec if isinstance(gspec, tuple) else ops[gspec]
                    grows.append(gather_rows(ops[i_pm], gix))
                gcounts, _ = group_counts_local(f, grows, pallas)
                outs.append(jax.lax.psum(gcounts, SHARD_AXIS))
            else:
                raise ValueError(f"bad fused edge {kind}")
        return tuple(outs)

    n_out = (1 if count_edges else 0)
    for e in agg_edges:
        n_out += {"sum": 2, "minmax": 3, "topn": 2, "topnf": 2, "group": 1}[
            e[0]
        ]
    # A Pallas group edge switches the varying-axes check off, as in
    # group_tree: every output is a psum (or top_k of one) regardless.
    pallas = any(e[0] == "group" and e[5] for e in agg_edges)
    return shard_map(
        body, mesh=mesh, in_specs=specs, out_specs=(P(),) * n_out,
        check_vma=not pallas,
    )(*operands)


# -- GroupBy ------------------------------------------------------------------
#
# counts[k1, ..., kn] = popcount(filter & r1[k1] & ... & rn[kn]) over every
# column.  Both bodies below evaluate the nest BY PREFIX: the product of all
# fields but the last is walked (the XLA body: one flat loop, its row indices
# from div/mod of the counter; the Pallas body: a loop a prefix field, nested),
# each prefix mask is then scored against every row of the last field.
# Neither unrolls anything per combination (the Pallas body unrolls its inner
# loop over at most GROUP_UNROLL_WHOLE rows of the last field), so the trace
# and the compile time do not grow with prod(K).
#
# What the Pallas body does NOT score: a prefix one of whose rows has no bit
# under the filter anywhere in the column tile.  Its mask is all-zero there,
# so its passes could only add zeros to accumulators that were zeroed: the
# answer is exact whatever the field's type (a set field may hold several
# rows a column, so the filter's text says nothing; the planes do).  SSB's
# Q3.3 / Q3.4, and every drill-down report, filter on the attributes they
# group by: 576-596 of their 600 prefixes are empty in every tile.  A grid
# step first marks one liveness bit a row of every prefix field
# (``any(f & row)``: sum(Ki) AND + OR passes on the vector unit, the bits
# OR-ed into one int32 a 32 rows, ONE roll tree and reduce to a scalar a
# word, kept in SMEM), then walks the nest under ``pl.when(bit)`` at every
# level, so a dead outer row skips its whole subtree on one scalar test.
# The test sits outside the scored loop's dependency path on purpose: a
# vector -> scalar reduce costs ~240 cycles on a v5e, as much as scoring a
# prefix against 26 planes, so neither the prefix mask itself is tested
# before its branch nor the last field's rows (count mode's inner loop
# stays branch-free); PERF.md section 6, PR 37, has every form timed.  The
# kernel counts the prefix steps it skipped and scored; they leave
# ``group_tree`` appended to the tensor.  The XLA body tests nothing.
#
# With a measure (``GroupBy(..., aggregate=Sum(field=v))``) the BSI view's
# planes are one more, innermost axis of the tensor: every combination's
# mask is scored against each of v's ``depth`` value planes under the
# not-null plane, against the not-null plane, and alone:
#   cells[k1, ..., kn, b]         = popcount(mask & notnull & plane_b)
#   cells[k1, ..., kn, depth]     = popcount(mask & notnull)
#   cells[k1, ..., kn, depth + 1] = popcount(mask)          (the count)
# so a group's sum is Σ_b 2^b · cells[..., b] (+ base · cells[..., depth]),
# assembled on the host in integers.  A cell is an int32 popcount over one
# device's columns, psum'ed over the mesh: exact while an index holds
# fewer than 2^31 columns (2,048 shards).

# Pallas body: words of one column tile (lanes), shards of one tile
# (sublanes).  The tile's blocks — filter + every group row — are double
# buffered in VMEM; GROUP_IN_BYTES bounds them, and a wider row table takes
# a narrower tile.
GROUP_TILE_SHARDS = 8
GROUP_TILE_WORDS = (2048, 1024, 512, 256, 128)
GROUP_IN_BYTES = 24 << 20
# Groups whose (8, 128) int32 lane accumulators stay in VMEM through one
# pass over the columns (4 KiB each: 16 MiB).  A count tensor within it —
# taxi query 4's 3,570 — reads every plane from HBM exactly once; a larger
# one takes ceil(groups / GROUP_ACC_GROUPS) passes.
GROUP_ACC_GROUPS = 4096
GROUP_VMEM_LIMIT = 100 << 20
# Rows of the last field scored a step of the inner loop, unrolled by
# hand (Mosaic unrolls a loop wholly or not at all): a last field up to
# GROUP_UNROLL_WHOLE rows is unrolled whole, a wider one GROUP_UNROLL rows
# a step.  One row a step leaves the vector unit waiting on the loop:
# 48.5 ms at taxi query 4's shape on a v5e, 24.9 at three, 20.0 at
# eight, 17.2 with the 51 unrolled whole (PERF.md section 6, PR 34).
GROUP_UNROLL = 16
GROUP_UNROLL_WHOLE = 64


def group_tile_words(dims) -> int:
    """Column-tile width of the Pallas body for these field widths, or 0
    when no tile fits VMEM (the XLA body answers)."""
    if dims[-1] > GROUP_ACC_GROUPS:
        return 0
    planes = sum(dims) + 1
    for w in GROUP_TILE_WORDS:
        if 2 * planes * GROUP_TILE_SHARDS * w * 4 <= GROUP_IN_BYTES:
            return w
    return 0


def _prefix_rows(p, dims):
    """Row index per prefix field of flat prefix ``p`` (row-major)."""
    ks = []
    for d in reversed(dims):
        ks.append(p % d)
        p = p // d
    return ks[::-1]


def _group_counts_xla(f, rows, planes=None):
    """Per-device GroupBy counts, plain XLA: f uint32[S, W], rows a list
    of uint32[Ki, S, W] -> int32[prod(K)] (row-major).  One loop step a
    prefix: the prefix mask, then a broadcast popcount-reduce over the
    last field's rows.  Every step re-reads the last field's planes, so
    this is the body for backends without the Pallas one (the CPU) and
    for shapes it declines, not the fast path on a TPU.  With ``planes``
    (a measure's uint32[depth + 1, S, W]) every field is part of the
    prefix and a step scores the measure's planes: int32[prod(K) *
    (depth + 2)], the cells of the header comment."""
    pre_rows = rows if planes is not None else rows[:-1]
    pre_dims = tuple(r.shape[0] for r in pre_rows)
    last = rows[-1]

    def one(p):
        pre = f
        for r, k in zip(pre_rows, _prefix_rows(p, pre_dims)):
            pre = pre & jax.lax.dynamic_index_in_dim(r, k, 0, keepdims=False)
        if planes is not None:
            have = pre & planes[-1]
            return jnp.concatenate([
                jnp.sum(_pc(planes[:-1] & have[None]), axis=(1, 2)),
                jnp.stack([jnp.sum(_pc(have)), jnp.sum(_pc(pre))]),
            ])
        return jnp.sum(_pc(last & pre[None]), axis=(1, 2))

    n_pre = 1
    for d in pre_dims:
        n_pre *= d
    if not pre_dims:
        return one(jnp.int32(0))
    return jax.lax.map(one, jnp.arange(n_pre, dtype=jnp.int32)).reshape(-1)


def _group_counts_pallas(f, rows, tile_words, interpret=False, planes=None):
    """Per-device GroupBy counts as ONE Pallas kernel: ``_group_counts_xla``'s
    tensor, and beside it int32[2], the prefix steps skipped and scored
    (with ``planes``, the measure's planes take the
    last field's place in the inner loop: a combination's mask stays in
    registers while its depth + 2 cells are scored, unrolled whole).  Grid = (passes, shard tiles, word tiles); a
    step holds the tile of the filter and of EVERY group row in VMEM, so
    each plane leaves HBM once a pass, and the kernel's work is the
    prod(K) AND + popcount passes over the tile on the vector unit:
    per prefix, its mask stays in vector registers while the last
    field's rows stream past it from VMEM, each adding a lane-wise
    partial into its group's (8, 128) accumulator.  The accumulators are
    the output block, resident across a pass; lanes are summed outside.
    A prefix with a row that is dead in the tile is skipped (the header
    comment): a step is one (grid step, prefix), and the two counts are
    summed over the grid in an SMEM output."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    dims = tuple(r.shape[0] for r in rows)
    if planes is not None:
        depth = planes.shape[0] - 1
        dims += (depth + 2,)
        rows = list(rows) + [planes]
    pre_dims, k_last = dims[:-1], dims[-1]
    n_pre = 1
    for d in pre_dims:
        n_pre *= d
    S, W = f.shape
    ts, tw = GROUP_TILE_SHARDS, tile_words
    if S % ts or W % tw:
        raise ValueError(f"[{S}, {W}] is not a whole number of [{ts}, {tw}] tiles")
    unroll = k_last if k_last <= GROUP_UNROLL_WHOLE else GROUP_UNROLL
    pre_block = max(1, min(n_pre, GROUP_ACC_GROUPS // k_last))
    passes = -(-n_pre // pre_block)
    lanes = tw // 128
    # Row k of prefix field i is bit offsets[i] + k of the liveness words;
    # a step of it is strides[i] prefixes of the row-major nest.
    offsets = [sum(pre_dims[:i]) for i in range(len(pre_dims))]
    strides = [1] * len(pre_dims)
    for i in range(len(pre_dims) - 2, -1, -1):
        strides[i] = strides[i + 1] * pre_dims[i + 1]
    n_words = -(-sum(pre_dims) // 32)

    def fold(x, op):
        """[ts, tw] -> [ts, 128]: the tile's 128-lane chunks under op."""
        part = x[:, 0:128]
        for j in range(1, lanes):
            part = op(part, x[:, j * 128:(j + 1) * 128])
        return part

    def kernel(f_ref, *refs):
        row_refs, acc_ref, steps_ref, live_ref, scored_ref = refs[:-4], *refs[-4:]
        first = (pl.program_id(1) == 0) & (pl.program_id(2) == 0)

        @pl.when(first)
        def _zero():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        @pl.when(first & (pl.program_id(0) == 0))
        def _zero_steps():
            steps_ref[0, 0] = jnp.int32(0)
            steps_ref[0, 1] = jnp.int32(0)

        # This pass's prefixes; the last pass may hold fewer than a block.
        p0 = pl.program_id(0) * pre_block
        p1 = jnp.minimum(p0 + pre_block, n_pre)
        last_ref = row_refs[-1]

        # The liveness words of this tile.  A row's test is 16 ANDs and an
        # OR tree down to one vreg; the rows' bits are OR-ed together on
        # the vector unit, so a word costs ONE roll tree and reduce to a
        # scalar (~240 cycles on a v5e), not one a row.
        words = []
        for w in range(n_words):
            terms = []
            for ref, d, off in zip(row_refs[:-1], pre_dims, offsets):
                lo, hi = max(off, 32 * w), min(off + d, 32 * w + 32)
                if lo >= hi:
                    continue

                def mark(k, ref=ref, off=off):
                    part = fold(f_ref[...] & ref[k], jnp.bitwise_or)
                    bit = jnp.left_shift(jnp.int32(1), off + k - 32 * w)
                    return jnp.where(part != 0, bit, 0)

                if sum(pre_dims) <= GROUP_UNROLL_WHOLE:
                    # few rows in all: unrolled, so that their chains of
                    # ANDs and ORs overlap (a loop waits out each one)
                    terms += [mark(k) for k in range(lo - off, hi - off)]
                else:
                    terms.append(jax.lax.fori_loop(
                        lo - off, hi - off, lambda k, bits: bits | mark(k),
                        jnp.zeros((ts, 128), jnp.int32)))
            while len(terms) > 1:
                terms = [x | y for x, y in zip(terms[::2], terms[1::2])] + terms[len(terms) & ~1:]
            bits = terms[0]
            for axis, size in ((1, 128), (0, ts)):
                shift = size // 2
                while shift:
                    bits = bits | pltpu.roll(bits, shift, axis)
                    shift //= 2
            words.append(jnp.max(bits[0:1, :]))
            live_ref[w] = words[w]

        def live_test(i):
            """k -> whether row k of prefix field i is live in this tile:
            a field whose bits lie in one word tests that scalar, a wider
            one reads its word from SMEM."""
            first, last = offsets[i] >> 5, (offsets[i] + pre_dims[i] - 1) >> 5
            if first == last:
                return lambda k: ((words[first] >> ((offsets[i] + k) & 31)) & 1) != 0
            return lambda k: ((live_ref[(offsets[i] + k) >> 5] >> ((offsets[i] + k) & 31)) & 1) != 0

        def score(pre, base):
            """The prefix mask against the last field's rows, or the
            measure's planes: cells base .. base + k_last of this pass."""
            def add(k, mask):
                acc_ref[base + k] += fold(_pc(mask), jnp.add)

            if planes is not None:
                have = pre & last_ref[depth]
                for b in range(depth):
                    add(b, have & last_ref[b])
                add(depth, have)
                add(depth + 1, pre)
                return

            def score_one(k):
                add(k, pre & last_ref[k])

            def score_many(i, c):
                for u in range(unroll):
                    score_one(i * unroll + u)
                return c

            jax.lax.fori_loop(0, k_last // unroll, score_many, 0)
            for k in range(k_last - k_last % unroll, k_last):
                score_one(k)

        scored_ref[0] = jnp.int32(0)

        def walk(i, pre, p):
            """The nest below a node: prefix field i onward, ``pre`` the
            mask so far, ``p`` the node's first prefix.  One loop a field,
            cut to this pass's [p0, p1); no div/mod a prefix, and a dead
            row skips its whole subtree on one scalar test."""
            if i == len(pre_dims):
                score(pre, (p - p0) * k_last)
                scored_ref[0] += 1
                return
            stride, live = strides[i], live_test(i)
            k_lo = jnp.maximum(p0 - p, 0) // stride
            k_hi = jnp.minimum((p1 - 1 - p) // stride + 1, pre_dims[i])

            def child(k, c):
                @pl.when(live(k))
                def _():
                    walk(i + 1, pre & row_refs[i][k], p + k * stride)

                return c

            jax.lax.fori_loop(k_lo, k_hi, child, 0)

        walk(0, f_ref[...], jnp.int32(0))
        scored = scored_ref[0]
        steps_ref[0, 0] += p1 - p0 - scored
        steps_ref[0, 1] += scored

    def tile(k):
        return pl.BlockSpec((k, ts, tw), lambda g, i, j: (0, i, j))

    out, steps = pl.pallas_call(
        kernel,
        grid=(passes, S // ts, W // tw),
        in_specs=[pl.BlockSpec((ts, tw), lambda g, i, j: (i, j))]
        + [tile(r.shape[0]) for r in rows],
        out_specs=[
            pl.BlockSpec(
                (pre_block * k_last, ts, 128), lambda g, i, j: (g, 0, 0)
            ),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(
                (passes * pre_block * k_last, ts, 128), jnp.int32
            ),
            jax.ShapeDtypeStruct((1, 2), jnp.int32),
        ],
        scratch_shapes=[pltpu.SMEM((max(1, n_words),), jnp.int32), pltpu.SMEM((1,), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=GROUP_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(f, *rows)
    return jnp.sum(out, axis=(1, 2))[: n_pre * k_last], steps[0]


def group_counts_local(f, rows, pallas, planes=None):
    """The per-device GroupBy body both callers share (``group_tree``
    and the fused program's ``group`` edge): the Pallas kernel where the
    backend has it (``pallas``) and the local block is whole tiles, the
    XLA loop otherwise.  ``(counts, steps)``: int32[prod(K)], or with a
    measure's ``planes`` int32[prod(K) * (depth + 2)] (the header
    comment's cells; the planes are the inner loop, so the fields keep
    their order), and int32[2], the prefix steps the Pallas body
    skipped and scored (zeros from the XLA loop, which tests nothing)."""
    dims = tuple(r.shape[0] for r in rows)
    no_steps = jnp.zeros(2, jnp.int32)
    if planes is not None:
        f = jnp.broadcast_to(f, planes.shape[1:])
        tw = group_tile_words(dims + (planes.shape[0] + 1,)) if pallas else 0
        if tw and f.shape[0] % GROUP_TILE_SHARDS == 0 and f.shape[1] % tw == 0:
            return _group_counts_pallas(f, rows, tw, planes=planes)
        return _group_counts_xla(f, rows, planes), no_steps
    # The widest field goes last: the inner loop is over its rows, the
    # outer one over the product of the others (taxi query 4's nest as
    # 51 x 10 x 7 ran 26 ms on a v5e against 17 as 10 x 7 x 51).
    n = len(rows)
    last = max(range(n), key=lambda i: (dims[i], i))
    order = [i for i in range(n) if i != last] + [last]
    rows = [rows[i] for i in order]
    f = jnp.broadcast_to(f, rows[0].shape[1:])
    tw = group_tile_words(tuple(dims[i] for i in order)) if pallas else 0
    if tw and f.shape[0] % GROUP_TILE_SHARDS == 0 and f.shape[1] % tw == 0:
        counts, steps = _group_counts_pallas(f, rows, tw)
    else:
        counts, steps = _group_counts_xla(f, rows), no_steps
    if order == list(range(n)):
        return counts, steps
    back = sorted(range(n), key=order.__getitem__)
    counts = counts.reshape([dims[i] for i in order]).transpose(back)
    return counts.reshape(-1), steps


def split_group_steps(host, dims):
    """``group_tree``'s array on the host -> (the tensor shaped
    ``dims``, its (skipped, scored) prefix steps)."""
    return host[:-2].reshape(dims), (int(host[-2]), int(host[-1]))


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5))
def group_tree(mesh, prog, specs, idx_specs, pallas, pspec, mask, *operands):
    """N-field GroupBy in ONE dispatch: every (K1 x K2 x ... x Kn) group
    combination counted (executeGroupByShard's nested iterator,
    executor.go:1056/2726-2890, as one count tensor) + one psum ->
    int32[K1 * ... * Kn + 2], replicated: the count tensor, row-major,
    and after it the prefix steps the Pallas body skipped and scored
    over every tile, pass and device (``_group_counts_pallas``; zeros
    from the XLA body) -- ONE array, so that the steps ride the
    tensor's readback (``split_group_steps`` parts them on the host).

    ``idx_specs`` is a static tuple with one slot per field: a
    gather-free index tuple, or None meaning the field's row indices
    arrive as a traced int32[Ki] operand (client-controlled subsets must
    not become compile keys).  The first ``n`` operands after ``mask``
    are the field stacks, then (``pspec`` not None) the measure's BSI
    stack, then the traced index vectors for the None slots, then the
    filter-tree operands.  ``pallas`` selects the TPU body (the engine
    sets it from the backend).

    ``pspec`` is None, or the static plane layout (``gather_planes``) of
    the measure of ``aggregate=Sum(field=v)``: the tensor is then
    int32[K1, ..., Kn, depth + 2], a combination's popcounts under each
    value plane, under the not-null plane, and alone (its count) — the
    host assembles Σ 2^b · cells[..., b] in integers.

    The program is one per (filter structure, field widths, measure
    depth): nothing is unrolled per combination
    (``group_counts_local``), so there is no cap on prod(K) here; the
    engine bounds the tensor it reads back (MeshEngine.MAX_GROUPS)."""
    n = len(idx_specs)
    n_mats = n + (pspec is not None)

    def body(m, *ops):
        mats = ops[:n]
        rest = list(ops[n_mats:])
        idxs = [
            spec if spec is not None else rest.pop(0) for spec in idx_specs
        ]
        f = _filter(prog, m, tuple(rest))
        rows = [
            gather_rows(mats[i], idxs[i]) if idx_specs[i] is not None
            else slice_rows(mats[i], idxs[i])
            for i in range(n)
        ]  # [Ki, S, W]
        dims = tuple(r.shape[0] for r in rows)
        planes = None
        if pspec is not None:
            planes = gather_planes(ops[n], pspec)
            dims += (planes.shape[0] + 1,)
        counts, steps = group_counts_local(f, rows, pallas, planes)
        return jax.lax.psum(jnp.concatenate([counts, steps]), SHARD_AXIS)

    # check_vma off with the Pallas body: pallas_call's output carries no
    # varying-axes type (sparse.count_tree_blocks_pallas); the psum makes
    # the result replicated regardless.
    return shard_map(
        body,
        mesh=mesh,
        in_specs=(P(SHARD_AXIS),) + (P(None, SHARD_AXIS),) * n_mats + specs,
        out_specs=P(),
        check_vma=not pallas,
    )(mask, *operands)

"""Occupancy-guided sparse query kernels: read only occupied blocks.

The reference's whole reason for roaring bitmaps is to never touch empty
regions (SURVEY §2.1: container ops skip absent containers).  Our dense
``uint32[R, S, WORDS]`` device layout lost that: the dense sweep reads
every word of every operand row, and the batched Count program already
reads them at 89 % of the HBM roofline (PERF.md §5, taxi cell) — the
remaining device-side lever is reading FEWER BYTES.

This module is that lever for the dominant count/intersect sweep.  The
engine keeps an EXACT per-(row, shard) block-occupancy bitmap on every
resident stack (``bitops.OCC_BLOCKS`` fixed blocks of
``OCC_BLOCK_WORDS`` uint32 words; built at residency time, maintained by
the scatter-sync write path — engine._FieldStack.occ).  At dispatch the
engine combines the leaves' occupancy through the query tree host-side
(AND intersects, OR/XOR unions, ANDNOT keeps the left side), and when
the surviving block fraction is under a density threshold it ships tiny
per-shard block lists and dispatches one of the kernels here instead of
the dense ``kernels.count_tree``:

- ``count_tree_blocks``: plain-XLA block gather — one ``lax.gather``
  per leaf slices the listed ``(row, shard, block)`` windows straight
  out of the ``[R, S, WORDS]`` stack before the fused popcount.  The
  portable form (CPU meshes, ``JAX_PLATFORMS=cpu`` tier-1, pods) and
  the tests' oracle.  The stack is never reshaped to a block view
  first: under the TPU's (8, 128) tiling of ``[S, WORDS]`` that
  reshape is a relayout of every row of the stack, not a bitcast.
- ``count_tree_blocks_pallas``: a TPU Pallas kernel that scalar-
  prefetches the block lists and explicitly DMAs the occupied blocks
  HBM->VMEM (grid over (local shard, block slot); the operand stacks
  stay in HBM/ANY memory space and are never streamed wholesale).
  The DMA window is the tile-aligned ``(SHARD_GROUP shards, 512
  words)`` slab holding the wanted block — the smallest window the
  (8, 128) tiling admits — and the kernel selects the shard's sublane
  from it.  Selected on TPU backends when the per-device shard count
  is a multiple of ``SHARD_GROUP`` (engine._dispatch_sparse).

The earlier "Pallas was deleted" note in kernels.py applies only to the
DENSE sweep, where a hand pipeline tied XLA's fusion at the same
roofline; block skipping is a different roofline — the win is bytes not
touched, which XLA's dense fusion cannot express.

Program form: ``prog`` is a NORMALIZED static tree (engine._sparse_plan)
— leaves ``("row", mat_slot, row_slot)`` / ``("zero",)``, interior nodes
``("and"|"or"|"andnot"|"xor", ...)``.  Row indices travel in ONE traced
int32 vector (``rowvec``), block lists as traced ``int32[S, Kb]`` +
``int32[S]`` (padded to power-of-two Kb tiers), so the compile key is
(structure, Kb tier) — never the row ids or the block pattern.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..ops.bitops import OCC_BLOCK_WORDS
from .mesh import SHARD_AXIS


def _pc(x):
    return jax.lax.population_count(x).astype(jnp.int32)


_BLOCK_GATHER = jax.lax.GatherDimensionNumbers(
    offset_dims=(2,), collapsed_slice_dims=(0, 1), start_index_map=(0, 1, 2)
)


def _combine(prog, leaf, zeros):
    """Trace-time evaluation of a normalized sparse prog: ``leaf(mat_slot,
    row_slot)`` yields a row leaf's gathered blocks, ``zeros`` the value
    of a ``("zero",)`` leaf; interior nodes are the set algebra."""
    kind = prog[0]
    if kind == "zero":
        return zeros
    if kind == "row":
        return leaf(prog[1], prog[2])
    subs = [_combine(p, leaf, zeros) for p in prog[1:]]
    out = subs[0]
    for s in subs[1:]:
        if kind == "or":
            out = out | s
        elif kind == "and":
            out = out & s
        elif kind == "andnot":
            out = out & ~s
        elif kind == "xor":
            out = out ^ s
        else:
            raise ValueError(f"bad sparse op {kind}")
    return out


def _gather_blocks(mat, row, bidx):
    """``uint32[S_local, Kb, BW]``: the listed blocks of one row — one
    gather of (1, 1, BW) windows at (row, shard, block * BW) from the
    stack as it lies in HBM.  Indexing the row and taking a
    [S, OCC_BLOCKS, BW] view first costs a stack-sized temp on TPU (see
    the module docstring)."""
    shard = jax.lax.broadcasted_iota(jnp.int32, bidx.shape, 0)
    idx = jnp.stack(
        [jnp.broadcast_to(row, bidx.shape), shard, bidx * OCC_BLOCK_WORDS],
        axis=-1,
    )
    return jax.lax.gather(
        mat, idx, _BLOCK_GATHER,
        slice_sizes=(1, 1, OCC_BLOCK_WORDS),
        mode=jax.lax.GatherScatterMode.CLIP,
    )


@functools.partial(jax.jit, static_argnums=(0, 1))
def count_tree_blocks(mesh, prog, mask, blk_idx, blk_n, rowvec, *mats):
    """Count(tree) over OCCUPIED blocks only (XLA form): gather the
    per-shard listed blocks of every leaf row, fuse the set algebra +
    popcount over just those, and psum.  ``blk_idx int32[S, Kb]`` lists
    block ids per canonical shard (slots >= ``blk_n[s]`` are padding:
    they gather block 0 — a cached re-read — and their counts are
    zeroed).  ``mask`` is the requested-shard uint32[S, 1] gate (block
    lists for unrequested shards are already empty; the gate keeps the
    dense-path contract anyway)."""

    def body(m, bidx, bn, rv, *ms):
        Kb = bidx.shape[1]
        out = _combine(
            prog,
            lambda mslot, rslot: _gather_blocks(ms[mslot], rv[rslot], bidx),
            jnp.zeros(bidx.shape + (OCC_BLOCK_WORDS,), jnp.uint32),
        )
        pc = jnp.sum(_pc(out), axis=-1)  # [S_local, Kb]
        valid = jnp.arange(Kb, dtype=jnp.int32)[None, :] < bn[:, None]
        pc = jnp.where(valid, pc, 0)
        per_shard = jnp.where(m[:, 0] != 0, jnp.sum(pc, axis=1), 0)
        return jax.lax.psum(jnp.sum(per_shard), SHARD_AXIS)

    return shard_map(
        body,
        mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS), P())
        + (P(None, SHARD_AXIS),) * len(mats),
        out_specs=P(),
    )(mask, blk_idx, blk_n, rowvec, *mats)


# -- Pallas TPU kernel ------------------------------------------------------


def _prog_leaves(prog, out=None):
    """Static (mat_slot, row_slot) leaf list in evaluation order."""
    if out is None:
        out = []
    if prog[0] == "row":
        out.append((prog[1], prog[2]))
    elif prog[0] not in ("zero",):
        for p in prog[1:]:
            _prog_leaves(p, out)
    return out


# Shards per DMA window: the sublane count of the (8, 128) tiling XLA
# gives the stack's trailing [S, WORDS] dims on TPU.  A DMA source must
# be tile-aligned, so one shard's block travels with its group's.
SHARD_GROUP = 8


def _pallas_shard_count(prog, bidx, bn, rowvec, mats, interpret=False):
    """Per-device block-skipping count: Pallas kernel over one local
    shard block.  Grid = (S_local, Kb); the block lists and row indices
    are SCALAR-PREFETCH operands (available before the body runs, per
    the Pallas TPU scalar-prefetch contract), the stacks stay in ANY
    (HBM) memory space, and each grid step DMAs the listed block of
    each leaf row — as the aligned ``(SHARD_GROUP, BW)`` slab around it
    — into VMEM scratch, then combines + popcounts the shard's own
    sublane.  Padding slots (j >= bn[s]) and unrequested shards
    (bn == 0) do no DMA and add nothing.  ``S_local`` must be a
    multiple of ``SHARD_GROUP``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    leaves = tuple(dict.fromkeys(_prog_leaves(prog)))  # distinct, in order
    n_leaf = max(1, len(leaves))
    S_local, Kb = bidx.shape
    if S_local % SHARD_GROUP:
        raise ValueError(
            f"{S_local} local shards is not a multiple of {SHARD_GROUP}"
        )

    def kernel(bidx_ref, bn_ref, rv_ref, *rest):
        mats_refs = rest[: len(mats)]
        out_ref, scratch, sems = rest[len(mats):]
        s = pl.program_id(0)
        j = pl.program_id(1)

        @pl.when((s == 0) & (j == 0))
        def _init():
            out_ref[0, 0] = 0

        @pl.when(j < bn_ref[s])
        def _work():
            group = pl.multiple_of((s // SHARD_GROUP) * SHARD_GROUP, SHARD_GROUP)
            word = pl.multiple_of(
                bidx_ref[s * Kb + j] * OCC_BLOCK_WORDS, OCC_BLOCK_WORDS
            )
            copies = []
            for li, (mslot, rslot) in enumerate(leaves):
                cp = pltpu.make_async_copy(
                    mats_refs[mslot].at[
                        rv_ref[rslot],
                        pl.ds(group, SHARD_GROUP),
                        pl.ds(word, OCC_BLOCK_WORDS),
                    ],
                    scratch.at[li],
                    sems.at[li],
                )
                cp.start()
                copies.append(cp)
            for cp in copies:
                cp.wait()
            lane = s % SHARD_GROUP
            val = _combine(
                prog,
                lambda *leaf: scratch[leaves.index(leaf), pl.ds(lane, 1), :],
                jnp.zeros((1, OCC_BLOCK_WORDS), jnp.uint32),
            )
            out_ref[0, 0] += jnp.sum(_pc(val))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        # bidx (flat, so SMEM holds S*Kb words, not S lane-padded rows),
        # bn, rowvec
        num_scalar_prefetch=3,
        grid=(S_local, Kb),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY) for _ in mats],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        scratch_shapes=[
            pltpu.VMEM((n_leaf, SHARD_GROUP, OCC_BLOCK_WORDS), jnp.uint32),
            pltpu.SemaphoreType.DMA((n_leaf,)),
        ],
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.int32),
        grid_spec=grid_spec,
        interpret=interpret,
    )(bidx.reshape(-1), bn, rowvec, *mats)
    return out[0, 0]


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def count_tree_blocks_pallas(mesh, prog, interpret, mask, blk_idx, blk_n, rowvec, *mats):
    """Count(tree) over occupied blocks with the DMAs hand-issued
    (TPU).  Same contract as ``count_tree_blocks``; ``mask`` folds into
    the block counts so gated shards do zero DMA."""

    def body(m, bidx, bn, rv, *ms):
        bn = jnp.where(m[:, 0] != 0, bn, 0)
        total = _pallas_shard_count(prog, bidx, bn, rv, ms, interpret=interpret)
        return jax.lax.psum(total, SHARD_AXIS)

    # check_vma off: pallas_call's output carries no varying-axes type,
    # and the interpreter's own loop mixes replicated indices into the
    # varying stacks; the psum makes the result replicated regardless.
    return shard_map(
        body,
        mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS), P())
        + (P(None, SHARD_AXIS),) * len(mats),
        out_specs=P(),
        check_vma=False,
    )(mask, blk_idx, blk_n, rowvec, *mats)

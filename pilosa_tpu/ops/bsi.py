"""BSI (bit-sliced index) device kernels.

The reference stores an int field as bitDepth+1 rows (rows 0..bitDepth-1 =
value bit-planes, row bitDepth = not-null) and answers EQ/NEQ/LT/GT/Between/
Sum/Min/Max with sequences of bitmap ops carrying keep/exclude sets
(fragment.go:716-985).  Those loops are data-dependent on the *predicate*
bits, not the data — so here each algorithm is reformulated branch-free with
``jnp.where`` selects over traced predicate bits and unrolled over the
statically-shaped plane matrix ``uint32[bit_depth+1, WORDS]``.  One compiled
kernel per bit-depth serves every predicate value (no recompiles on the
query path), and XLA fuses each unrolled step into a handful of passes over
HBM.

Kernels return device values; weighted sums (which may exceed 32 bits) are
assembled host-side from per-plane counts.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import bitops


def to_bits(value: int, depth: int):
    """Host-side: predicate value -> uint32[depth] bit vector.  Predicates
    can exceed 32 bits (bit-depth up to 63) and x64 is off on device, so
    kernels take the bits as a small traced array rather than a scalar —
    same compiled kernel for every predicate value."""
    import numpy as np

    return np.array([(value >> i) & 1 for i in range(max(depth, 1))], dtype=np.uint32)


def _bit(pred_bits, i):
    return pred_bits[i]


@jax.jit
def range_eq(planes, pred_bits):
    """Columns whose value == predicate.  planes: uint32[depth+1, WORDS];
    pred_bits: uint32[depth] predicate bit vector (see to_bits)."""
    depth = planes.shape[0] - 1
    b = planes[depth]
    for i in range(depth - 1, -1, -1):
        row = planes[i]
        bit = _bit(pred_bits, i)
        b = jnp.where(bit == 1, b & row, b & ~row)
    return b


@jax.jit
def range_neq(planes, pred_bits):
    depth = planes.shape[0] - 1
    return planes[depth] & ~range_eq(planes, pred_bits)


@functools.partial(jax.jit, static_argnums=(2,))
def range_lt(planes, pred_bits, allow_equality: bool):
    """Columns whose value < predicate (<= when allow_equality).

    Mirrors fragment.go rangeLT's leading-zeros + keep-set walk, with the
    per-bit branches turned into selects.
    """
    depth = planes.shape[0] - 1
    b = planes[depth]
    keep = jnp.zeros_like(b)
    lz = jnp.bool_(True)  # still in the leading-zeros prefix of the predicate
    for i in range(depth - 1, -1, -1):
        row = planes[i]
        bit = _bit(pred_bits, i)
        if i == 0 and not allow_equality:
            return jnp.where(bit == 0, keep, b & ~(row & ~keep))
        # bit==0: in the leading-zero prefix drop all columns with this bit
        # set; afterwards drop set columns not already kept.
        b_bit0 = jnp.where(lz, b & ~row, b & ~(row & ~keep))
        b = jnp.where(bit == 0, b_bit0, b)
        if i > 0:
            keep = jnp.where(bit == 1, keep | (b & ~row), keep)
        lz = lz & (bit == 0)
    return b


@functools.partial(jax.jit, static_argnums=(2,))
def range_gt(planes, pred_bits, allow_equality: bool):
    """Columns whose value > predicate (>= when allow_equality)."""
    depth = planes.shape[0] - 1
    b = planes[depth]
    keep = jnp.zeros_like(b)
    for i in range(depth - 1, -1, -1):
        row = planes[i]
        bit = _bit(pred_bits, i)
        if i == 0 and not allow_equality:
            return jnp.where(bit == 1, keep, b & ~((b & ~row) & ~keep))
        b = jnp.where(bit == 1, b & ~((b & ~row) & ~keep), b)
        if i > 0:
            keep = jnp.where(bit == 0, keep | (b & row), keep)
    return b


@jax.jit
def range_between(planes, pred_bits_min, pred_bits_max):
    """Columns with predicate_min <= value <= predicate_max
    (fragment.go rangeBetween's fused GTE/LTE walk)."""
    depth = planes.shape[0] - 1
    b = planes[depth]
    keep1 = jnp.zeros_like(b)  # GTE side
    keep2 = jnp.zeros_like(b)  # LTE side
    for i in range(depth - 1, -1, -1):
        row = planes[i]
        bit1 = _bit(pred_bits_min, i)
        bit2 = _bit(pred_bits_max, i)
        b = jnp.where(bit1 == 1, b & ~((b & ~row) & ~keep1), b)
        if i > 0:
            keep1 = jnp.where(bit1 == 0, keep1 | (b & row), keep1)
        b = jnp.where(bit2 == 0, b & ~(row & ~keep2), b)
        if i > 0:
            keep2 = jnp.where(bit2 == 1, keep2 | (b & ~row), keep2)
    return b


@jax.jit
def not_null(planes):
    return planes[planes.shape[0] - 1]


@jax.jit
def sum_counts(planes, filter_row):
    """Per-plane intersection counts with (not-null & filter).

    Returns (counts int32[depth], consider_count int32).  The weighted sum
    Σ 2^i * counts[i] is assembled host-side in arbitrary precision
    (fragment.go sum :716-742).
    """
    depth = planes.shape[0] - 1
    consider = planes[depth] & filter_row
    if depth == 0:
        # max == min: no value planes; the total is count * base.
        return jnp.zeros(0, jnp.int32), bitops.popcount(consider)
    counts = jnp.stack(
        [bitops.popcount_and(planes[i], consider) for i in range(depth)]
    )
    return counts, bitops.popcount(consider)


@jax.jit
def min_flags(planes, filter_row):
    """Branch-free min walk (fragment.go min :745-774).

    Returns (flags bool[depth], count int32): flags[i] set means bit i of
    the min value is 1; count is the number of columns attaining the min.
    """
    depth = planes.shape[0] - 1
    consider = planes[depth] & filter_row
    flags = []
    for i in range(depth - 1, -1, -1):
        x = consider & ~planes[i]
        c = bitops.popcount(x)
        took = c > 0
        consider = jnp.where(took, x, consider)
        flags.append(~took)  # bit of min is 1 when no column had it unset
    flags.reverse()
    return jnp.stack(flags), bitops.popcount(consider)


@jax.jit
def max_flags(planes, filter_row):
    """Branch-free max walk (fragment.go max :776-806)."""
    depth = planes.shape[0] - 1
    consider = planes[depth] & filter_row
    flags = []
    for i in range(depth - 1, -1, -1):
        x = consider & planes[i]
        c = bitops.popcount(x)
        took = c > 0
        consider = jnp.where(took, x, consider)
        flags.append(took)
    flags.reverse()
    return jnp.stack(flags), bitops.popcount(consider)


def minmax_valcount_nd(planes, filter_row, is_min: bool):
    """Word-local min/max walk + ONE-PASS variadic argmin/argmax reduce
    -> (hi uint32, lo uint32, count int32) per leading batch cell;
    value = (hi << 31) | lo.

    The walk runs INSIDE each 32-bit word (the per-word branch is
    ``sel != 0`` — elementwise), keeping a word-local candidate mask and
    value.  The former formulation then took THREE separate reductions
    (min value, then attain mask, then count), which XLA implemented by
    re-walking the planes.
    Here the shard min and its attaining-column count come from ONE
    variadic ``lax.reduce`` over (hi, lo, count) word triples with a
    lexicographic-argmin combiner that merges counts on ties: XLA fuses
    the walk into the reduce's operands and the planes stream exactly
    once (no cell runs Min/Max yet: its share of the roofline is not
    measured).

    ``planes`` is uint32[depth+1, ..., W]; ``filter_row`` broadcasts
    against planes[0].  The reduce runs over the LAST axis; leading
    batch axes (the shard axis in kernels.minmax_tree) are preserved.
    The value splits into two uint32 halves (bits 0..30 in lo, bits
    31..62 in hi) because bit_depth may reach 63 and x64 is off on
    device.  count 0 means no column considered (hi/lo then carry the
    neutral element, as before)."""
    depth = planes.shape[0] - 1
    keep0 = planes[depth] & filter_row
    keep = keep0
    lo = jnp.zeros(keep.shape, jnp.uint32)
    hi = jnp.zeros(keep.shape, jnp.uint32)
    for i in range(depth - 1, -1, -1):
        sel = keep & (~planes[i] if is_min else planes[i])
        has = sel != 0
        keep = jnp.where(has, sel, keep)
        # min: result bit i is 1 when NO candidate word-column had it
        # unset; max: 1 when some candidate had it set.
        bit_on = ~has if is_min else has
        bit = jnp.uint32(1 << i) if i < 31 else jnp.uint32(1 << (i - 31))
        add = jnp.where(bit_on, bit, jnp.uint32(0))
        if i < 31:
            lo = lo | add
        else:
            hi = hi | add
    valid = keep0 != 0
    neutral = jnp.uint32(0xFFFFFFFF) if is_min else jnp.uint32(0)
    hi_v = jnp.where(valid, hi, neutral)
    lo_v = jnp.where(valid, lo, neutral)
    cnt_w = jnp.where(
        valid, jax.lax.population_count(keep).astype(jnp.int32), 0
    )
    axis = hi_v.ndim - 1
    if jax.default_backend() != "tpu":
        # NON-TPU: the CPU backend's compile explodes (XLA slow-compile
        # alarm, minutes at depth >= ~31, even across an
        # optimization_barrier) when the unrolled walk feeds the
        # variadic reduce's combiner; use plain chained reductions
        # there — CPU is the oracle/test path, not the perf path.
        ext = jnp.max if not is_min else jnp.min
        best_hi = ext(hi_v, axis=axis)
        in_hi = hi_v == jnp.expand_dims(best_hi, axis)
        lo_masked = jnp.where(in_hi, lo_v, neutral)
        best_lo = ext(lo_masked, axis=axis)
        attain = in_hi & (lo_v == jnp.expand_dims(best_lo, axis))
        count = jnp.sum(jnp.where(attain, cnt_w, 0), axis=axis)
        return best_hi, best_lo, count

    def comb(a, b):
        # TPU: ONE variadic lexicographic argmin/argmax reduce — XLA
        # fuses the walk into the reduce operands so the planes stream
        # exactly once (755 GB/s measured vs 380 for the chained form).
        ahi, alo, ac = a
        bhi, blo, bc = b
        if is_min:
            a_wins = (ahi < bhi) | ((ahi == bhi) & (alo < blo))
        else:
            a_wins = (ahi > bhi) | ((ahi == bhi) & (alo > blo))
        eq = (ahi == bhi) & (alo == blo)
        return (
            jnp.where(a_wins, ahi, bhi),
            jnp.where(a_wins, alo, blo),
            jnp.where(eq, ac + bc, jnp.where(a_wins, ac, bc)),
        )

    return jax.lax.reduce(
        (hi_v, lo_v, cnt_w),
        (neutral, neutral, jnp.int32(0)),
        comb,
        (axis,),
    )


@jax.jit
def min_valcount(planes, filter_row):
    """Single-shard min -> (hi, lo, count) scalars (see
    minmax_valcount_nd; kept as the host per-fragment entry point)."""
    return minmax_valcount_nd(planes, filter_row, True)


@jax.jit
def max_valcount(planes, filter_row):
    """Single-shard max -> (hi, lo, count) scalars."""
    return minmax_valcount_nd(planes, filter_row, False)

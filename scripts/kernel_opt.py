"""Kernel-bandwidth experiments on the real chip (r4 VERDICT weak #2-4).

Measures the three below-stream kernels at bench shapes and candidate
restructurings, with device-trace timing (same method as bench.py).
Findings drive kernels.py/bsi.py; this script is the decision record.

Run (on a TPU): python scripts/kernel_opt.py
"""

import functools
import glob
import gzip
import json
import shutil
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from pilosa_tpu.ops import bsi as bsi_ops
from pilosa_tpu.parallel import kernels
from pilosa_tpu.parallel.mesh import SHARD_AXIS

S, W = 960, 32768
DEPTH = 8
HBM = 755.8  # measured read ceiling GB/s


def device_ms(fn, reps=12):
    jax.block_until_ready(fn(0))
    d = tempfile.mkdtemp(prefix="kopt_")
    try:
        jax.profiler.start_trace(d)
        try:
            jax.block_until_ready([fn(i) for i in range(reps)])
        finally:
            jax.profiler.stop_trace()
        out = {}
        for path in glob.glob(d + "/plugins/profile/*/*.trace.json.gz"):
            doc = json.load(gzip.open(path, "rt"))
            evs = doc.get("traceEvents", [])
            pids = {
                e["pid"]: e.get("args", {}).get("name", "")
                for e in evs
                if e.get("ph") == "M" and e.get("name") == "process_name"
            }
            for e in evs:
                if e.get("ph") != "X" or "TPU" not in pids.get(e.get("pid"), ""):
                    continue
                if not e.get("name", "").startswith("jit_"):
                    continue
                out.setdefault(e["name"], []).append(e.get("dur", 0))
        if not out:
            return None
        durs = sorted(max(out.values(), key=sum))
        return durs[len(durs) // 2] / 1e3
    finally:
        shutil.rmtree(d, ignore_errors=True)


def report(name, ms, gb):
    gbs = gb / (ms / 1e3)
    print(f"{name:34s} {ms:8.3f} ms  {gbs:7.1f} GB/s  ({gbs / HBM * 100:4.0f}% of stream)")
    return gbs


mesh = Mesh(np.array(jax.devices()[:1]), (SHARD_AXIS,))
rng = np.random.default_rng(7)

print("building operands...")
planes = jnp.asarray(
    np.concatenate(
        [
            rng.integers(0, 1 << 32, size=(DEPTH, S, W), dtype=np.uint32),
            np.full((1, S, W), 0xFFFFFFFF, dtype=np.uint32),
        ]
    )
)
mask = jnp.asarray(np.full((S, 1), 0xFFFFFFFF, dtype=np.uint32))
cands = jnp.asarray(
    rng.integers(0, 1 << 32, size=(16, S, W), dtype=np.uint32)
    & rng.integers(0, 1 << 32, size=(16, S, W), dtype=np.uint32)
)
src = jnp.asarray(rng.integers(0, 1 << 32, size=(S, W), dtype=np.uint32))
ga = jnp.asarray(rng.integers(0, 1 << 32, size=(4, S, W), dtype=np.uint32))
gb_ = jnp.asarray(rng.integers(0, 1 << 32, size=(2, S, W), dtype=np.uint32))
gc = jnp.asarray(rng.integers(0, 1 << 32, size=(2, S, W), dtype=np.uint32))
cnt = jnp.asarray(rng.integers(0, 1000, size=(16, S), dtype=np.int32))
thr = jnp.int32(1)
jax.block_until_ready((planes, cands, src, ga, gb_, gc))

GB_MM = planes.nbytes / 1e9
GB_TOP = (cands.nbytes + src.nbytes) / 1e9
GB_G3 = (ga.nbytes + gb_.nbytes + gc.nbytes) / 1e9

_pc = lambda x: jax.lax.population_count(x).astype(jnp.int32)

# ---------------- min/max --------------------------------------------------
print(f"\n== BSI min ({GB_MM:.2f} GB nominal) ==")

pspec = ("slice", 0, DEPTH + 1)


def mm_current(i):
    return kernels.minmax_tree(
        mesh, ("ones",), (), pspec, True, mask, planes
    )


report("minmax current (vmap word-local)", device_ms(mm_current), GB_MM)


@functools.partial(jax.jit, static_argnums=(0,))
def mm_v2(mesh, mask, pm):
    """depth<=31: single uint32 accumulator, no vmap, fused reductions."""

    def body(m, p):
        depth = p.shape[0] - 1
        keep0 = p[depth] & jnp.broadcast_to(m, p.shape[1:])
        keep = keep0
        lo = jnp.zeros(keep.shape, jnp.uint32)
        for i in range(depth - 1, -1, -1):
            zeros = keep & ~p[i]
            has0 = zeros != 0
            keep = jnp.where(has0, zeros, keep)
            lo = lo | jnp.where(has0, jnp.uint32(0), jnp.uint32(1 << i))
        valid = keep0 != 0
        full = jnp.uint32(0xFFFFFFFF)
        min_lo = jnp.min(jnp.where(valid, lo, full), axis=1)  # [S]
        attain = valid & (lo == min_lo[:, None])
        count = jnp.sum(jnp.where(attain, _pc(keep), 0), axis=1)
        return (
            jax.lax.psum(min_lo * 0, SHARD_AXIS) + min_lo,
            jax.lax.psum(count * 0, SHARD_AXIS) + count,
        )

    return shard_map(
        body, mesh=mesh, in_specs=(P(SHARD_AXIS), P(None, SHARD_AXIS)),
        out_specs=(P(SHARD_AXIS), P(SHARD_AXIS)),
    )(mask, pm)


report("minmax v2 (no-vmap single-acc)", device_ms(lambda i: mm_v2(mesh, mask, planes)), GB_MM)


@functools.partial(jax.jit, static_argnums=(0,))
def mm_v3(mesh, mask, pm):
    """Two-kernel: min via walk only (no count), then count in 2nd pass
    reading planes again is silly — instead derive count from lo alone:
    count = popcount of keep where lo == min; keep recomputable from
    attain columns... here: fuse min+count but compute per-shard min
    via a segmented reshape reduction (words-major blocks)."""

    def body(m, p):
        depth = p.shape[0] - 1
        keep0 = p[depth] & jnp.broadcast_to(m, p.shape[1:])
        keep = keep0
        lo = jnp.zeros(keep.shape, jnp.uint32)
        for i in range(depth - 1, -1, -1):
            zeros = keep & ~p[i]
            has0 = zeros != 0
            keep = jnp.where(has0, zeros, keep)
            lo = lo | jnp.where(has0, jnp.uint32(0), jnp.uint32(1 << i))
        valid = keep0 != 0
        full = jnp.uint32(0xFFFFFFFF)
        lo_v = jnp.where(valid, lo, full)
        # one pass: min and argmin-ish count folded via two reductions
        # XLA sibling-fuses these (same inputs).
        min_lo = jnp.min(lo_v, axis=1)
        count = jnp.sum(
            jnp.where(lo_v == min_lo[:, None], _pc(keep), 0), axis=1
        )
        return min_lo, count

    return shard_map(
        body, mesh=mesh, in_specs=(P(SHARD_AXIS), P(None, SHARD_AXIS)),
        out_specs=(P(SHARD_AXIS), P(SHARD_AXIS)),
    )(mask, pm)


report("minmax v3 (sibling reduce)", device_ms(lambda i: mm_v3(mesh, mask, planes)), GB_MM)

# ---------------- TopN scoring --------------------------------------------
print(f"\n== TopN full ({GB_TOP:.2f} GB nominal) ==")


def top_current(i):
    return kernels.topn_full_tree(
        mesh, ("ones",), (), 5, tuple(range(15, -1, -1)), mask, cands, cnt, thr
    )


report("topn current", device_ms(top_current), GB_TOP)


@functools.partial(jax.jit, static_argnums=(0, 1))
def top_v2(mesh, n_out, mask, cmat, cn, th):
    """Chunked scan over the word axis: each step loads src chunk once
    and scores ALL K candidates against it from VMEM."""

    def body(m, cmat, cn, th):
        K = cmat.shape[0]
        src_ = jnp.broadcast_to(m, cmat.shape[1:])
        TW = 4096
        nW = W // TW
        # [K, S, nW, TW] -> scan over nW
        cm = cmat.reshape(K, S, nW, TW).transpose(2, 0, 1, 3)
        sr = src_.reshape(S, nW, TW).transpose(1, 0, 2)

        def step(acc, xs):
            cchunk, schunk = xs
            acc = acc + jnp.sum(
                _pc(cchunk & schunk[None, :, :]), axis=-1
            )
            return acc, None

        scores, _ = jax.lax.scan(
            step,
            jax.lax.pvary(jnp.zeros((K, S), jnp.int32), (SHARD_AXIS,)),
            (cm, sr),
        )
        gate = jnp.logical_and(cn >= th, scores >= th)
        totals = jax.lax.psum(
            jnp.sum(jnp.where(gate, scores, 0), axis=1), SHARD_AXIS
        )
        vals, idx = jax.lax.top_k(totals, n_out)
        return (vals, idx)

    return shard_map(
        body, mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(None, SHARD_AXIS), P(None, SHARD_AXIS), P()),
        out_specs=(P(), P()),
    )(mask, cmat, cn, th)


# gather-free identity candidates == full reverse in current; use src=ones
report("topn v2 (word-chunk scan)", device_ms(lambda i: top_v2(mesh, 5, src, cands, cnt, thr)), GB_TOP)


@functools.partial(jax.jit, static_argnums=(0, 1))
def top_v3(mesh, n_out, mask, cmat, cn, th):
    """Flat X-axis chunking (S folded into the chunk axis)."""

    def body(m, cmat, cn, th):
        K = cmat.shape[0]
        src_ = jnp.broadcast_to(m, cmat.shape[1:])
        X = S * W
        C = 1 << 21  # 2M words: 8 MB src chunk + K x 8 MB cand rows? no - K*C*4
        nC = X // C
        cm = cmat.reshape(K, nC, C).transpose(1, 0, 2)
        sr = src_.reshape(nC, C)

        def step(acc, xs):
            cchunk, schunk = xs
            return acc + jnp.sum(_pc(cchunk & schunk[None, :]), axis=-1), None

        flat, _ = jax.lax.scan(
            step,
            jax.lax.pvary(jnp.zeros((K,), jnp.int32), (SHARD_AXIS,)),
            (cm, sr),
        )
        # NOTE: loses per-shard gating - measures bandwidth shape only.
        totals = jax.lax.psum(flat, SHARD_AXIS)
        vals, idx = jax.lax.top_k(totals, n_out)
        return (vals, idx)

    return shard_map(
        body, mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(None, SHARD_AXIS), P(None, SHARD_AXIS), P()),
        out_specs=(P(), P()),
    )(mask, cmat, cn, th)


report("topn v3 (flat-chunk, no gate)", device_ms(lambda i: top_v3(mesh, 5, src, cands, cnt, thr)), GB_TOP)

# ---------------- 3-field GroupBy ------------------------------------------
print(f"\n== GroupBy 3-field ({GB_G3:.2f} GB nominal) ==")


def g3_current(i):
    return kernels.groupn_tree(
        mesh, ("ones",), (),
        (tuple(range(4)), tuple(range(2)), tuple(range(2))),
        mask, ga, gb_, gc,
    )


report("groupn current (broadcast)", device_ms(g3_current), GB_G3)


@functools.partial(jax.jit, static_argnums=(0,))
def g3_v2(mesh, mask, a, b, c):
    """Word-chunk scan: per chunk, all 16 combos from VMEM-resident
    chunk loads."""

    def body(m, a, b, c):
        TW = 4096
        nW = W // TW
        at = a.reshape(4, S, nW, TW).transpose(2, 0, 1, 3)
        bt = b.reshape(2, S, nW, TW).transpose(2, 0, 1, 3)
        ct = c.reshape(2, S, nW, TW).transpose(2, 0, 1, 3)

        def step(acc, xs):
            ac, bc, cc = xs
            inter = (
                ac[:, None, None]
                & bc[None, :, None]
                & cc[None, None, :]
            )  # [4,2,2,S,TW]
            return acc + jnp.sum(_pc(inter), axis=(-2, -1)), None

        counts, _ = jax.lax.scan(
            step,
            jax.lax.pvary(jnp.zeros((4, 2, 2), jnp.int32), (SHARD_AXIS,)),
            (at, bt, ct),
        )
        return jax.lax.psum(counts, SHARD_AXIS)

    return shard_map(
        body, mesh=mesh,
        in_specs=(P(SHARD_AXIS),) + (P(None, SHARD_AXIS),) * 3,
        out_specs=P(),
    )(mask, a, b, c)


report("groupn v2 (word-chunk scan)", device_ms(lambda i: g3_v2(mesh, mask, ga, gb_, gc)), GB_G3)


@functools.partial(jax.jit, static_argnums=(0,))
def g3_v3(mesh, mask, a, b, c):
    """Pairwise staging: ab = a&b materialized once ([8,S,W] write),
    then ab&c reduce - trades an 8-plane write+read for the re-reads."""

    def body(m, a, b, c):
        ab = a[:, None] & b[None, :]  # [4,2,S,W]
        inter = ab[:, :, None] & c[None, None, :]
        return jax.lax.psum(
            jnp.sum(_pc(inter), axis=(-2, -1)), SHARD_AXIS
        )

    return shard_map(
        body, mesh=mesh,
        in_specs=(P(SHARD_AXIS),) + (P(None, SHARD_AXIS),) * 3,
        out_specs=P(),
    )(mask, a, b, c)


report("groupn v3 (pairwise stage)", device_ms(lambda i: g3_v3(mesh, mask, ga, gb_, gc)), GB_G3)

print("\ndone")

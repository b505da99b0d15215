"""Head-to-head: Pallas VMEM-pipelined sweep vs plain-XLA fusion on a
TPU chip, for the fragment-matrix TopN-scoring sweep
(counts[i] = popcount(mat[i] & row), fragment.go top :1089).

DECISION (round 4, on-device trace timing on a TPU v5 lite; the record
of that run is no longer kept — re-run this script on the chip to
reproduce): XLA's fused and+popcount+reduce and the hand-written Pallas
VMEM pipeline ran the sweep at the same streaming bandwidth at every
size from 64 to 8192 rows.  The kernel is memory-bound and XLA's fusion
already saturates HBM, so a hand pipeline has no headroom to buy.  The
dense query paths therefore use the XLA kernels (ops.bitops,
parallel.kernels).  Pallas tiling note: the output must use a
(block,128) broadcast tile — a (block,1) column tile lane-pads into a
whole-result VMEM allocation and OOMs above 2k rows.

Run (on a TPU): PYTHONPATH=. python scripts/pallas_vs_xla.py
"""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

WORDS = 32768  # uint32 words per 2^20-bit shard row


def _pc(x):
    return jax.lax.population_count(x).astype(jnp.int32)


@jax.jit
def matrix_and_popcount_xla(matrix, row):
    return jnp.sum(_pc(jnp.bitwise_and(matrix, row[None, :])), axis=-1)


def _and_popcount_kernel(mat_ref, row_ref, out_ref):
    inter = jnp.bitwise_and(mat_ref[:, :], row_ref[:, :])
    counts = jnp.sum(_pc(inter), axis=-1)
    out_ref[:, :] = jnp.broadcast_to(counts[:, None], out_ref.shape)


@functools.partial(jax.jit, static_argnums=(2,))
def matrix_and_popcount_pallas(matrix, row, block: int):
    from jax.experimental import pallas as pl

    n_rows, words = matrix.shape
    out = pl.pallas_call(
        _and_popcount_kernel,
        out_shape=jax.ShapeDtypeStruct((n_rows, 128), jnp.int32),
        grid=(n_rows // block,),
        in_specs=[
            pl.BlockSpec((block, words), lambda i: (i, 0)),
            pl.BlockSpec((1, words), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block, 128), lambda i: (i, 0)),
    )(matrix, row[None, :])
    return out[:, 0]


def timeit(fn, *args, iters=30, warmup=5):
    """Median ON-DEVICE program duration via bench.py's device-trace
    helper — wall clock carries per-dispatch host cost that buries the
    kernel time."""
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from bench import device_p50

    for _ in range(warmup):
        r = fn(*args)
    jax.block_until_ready(r)
    per, _ = device_p50(lambda i: fn(*args), reps=iters)
    return per


def main():
    rng = np.random.default_rng(0)
    out = {
        "device": str(jax.devices()[0]),
        "note": (
            "matrix_and_popcount sweep (TopN scoring); median ON-DEVICE "
            "program duration from the XLA device trace"
        ),
        "results": [],
    }
    for n_rows in (64, 512, 2048, 8192):
        mat = jnp.asarray(
            rng.integers(0, 2**32, (n_rows, WORDS), dtype=np.uint64).astype(
                np.uint32
            )
        )
        row = jnp.asarray(
            rng.integers(0, 2**32, (WORDS,), dtype=np.uint64).astype(np.uint32)
        )
        want = np.asarray(matrix_and_popcount_xla(mat, row))
        got = np.asarray(matrix_and_popcount_pallas(mat, row, 8))
        assert np.array_equal(want, got), "pallas mismatch"
        gb = mat.nbytes / 1e9
        t_x = timeit(matrix_and_popcount_xla, mat, row)
        t_p = timeit(lambda m, r: matrix_and_popcount_pallas(m, r, 8), mat, row)
        rec = {
            "n_rows": n_rows,
            "bytes_gb": round(gb, 3),
            "xla_us": round(t_x * 1e6, 1),
            "pallas_us": round(t_p * 1e6, 1),
            "xla_gbps": round(gb / t_x, 1),
            "pallas_gbps": round(gb / t_p, 1),
        }
        print(rec, flush=True)
        out["results"].append(rec)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/pallas_vs_xla.json", "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()

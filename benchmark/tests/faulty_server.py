"""``python -m pilosa_tpu server`` with one fault planted under the timed
path, for tests/test_faults.py.  Usage: faulty_server.py <fault> server ...

  answer   one device answer in twenty is altered where it is produced
           (the Count and Sum programs' outputs, before decode)
  half     half of the shards are left out of every query
  exchange the exchange between chips is left out: every in-mesh psum hands
           all devices the first device's part alone (a cell on one device
           has no exchange, and reads right under this fault)
"""

import itertools
import sys


def plant(fault: str):
    if fault == "answer":
        from pilosa_tpu.parallel import kernels

        tick = itertools.count()

        def altered(orig, pick):
            def call(*args, **kw):
                out = orig(*args, **kw)
                if next(tick) % 20 == 0:
                    return pick(out)
                return out
            return call

        kernels.count_tree = altered(kernels.count_tree, lambda o: o + 1)
        kernels.count_batch_tree = altered(kernels.count_batch_tree, lambda o: o + 1)
        kernels.sum_tree = altered(kernels.sum_tree, lambda o: (o[0], o[1] + 1))
    elif fault == "half":
        from pilosa_tpu.executor.executor import Executor

        whole = Executor._default_shards
        Executor._default_shards = lambda self, index: (
            lambda s: s[: max(1, len(s) // 2)])(whole(self, index))
    elif fault == "exchange":
        import jax
        import jax.numpy as jnp

        whole = jax.lax.psum

        def first_part_only(x, axis_name, **kw):
            first = jax.lax.axis_index(axis_name) == 0
            return whole(jax.tree.map(lambda a: jnp.where(first, a, 0), x), axis_name, **kw)

        jax.lax.psum = first_part_only
    else:
        raise SystemExit(f"unknown fault {fault!r}")


if __name__ == "__main__":
    plant(sys.argv.pop(1))
    from pilosa_tpu.cli import main

    sys.exit(main())

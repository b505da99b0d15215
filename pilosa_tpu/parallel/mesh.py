"""Device mesh + shard placement.

The TPU-native replacement for the reference's cluster shard routing
(cluster.go shardNodes :840, jump-hash :905): shards are laid out
contiguously along a 1-D ``jax.sharding.Mesh`` axis so that the per-query
shard reduce (executor.go mapReduce :2183) becomes a single ``psum`` over
ICI instead of goroutine fan-out + HTTP.

Placement math: query shards are packed into a ``[n_shards_padded, ...]``
leading axis, padded to a multiple of the mesh size; device d owns the
contiguous block ``[d*k, (d+1)*k)``.  Contiguity keeps each device's
working set dense in HBM and the reduce a pure tree over the mesh axis
(SURVEY.md §5 long-axis note).
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.experimental.layout import Format, Layout
from jax.sharding import Mesh, NamedSharding, PartitionSpec

SHARD_AXIS = "shard"


def make_mesh(n_devices: Optional[int] = None, devices=None) -> Mesh:
    """1-D mesh over the shard axis.  ``n_devices`` trims/validates against
    the available device count (virtual CPU devices in tests)."""
    if devices is None:
        devices = jax.devices()
    if n_devices is not None:
        if n_devices > len(devices):
            raise ValueError(
                f"requested {n_devices} devices, only {len(devices)} available"
            )
        devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (SHARD_AXIS,))


def shard_sharding(mesh: Mesh) -> NamedSharding:
    """Leading axis split over the shard mesh axis."""
    return NamedSharding(mesh, PartitionSpec(SHARD_AXIS))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


def _row_major_format(sh: NamedSharding, ndim: int):
    """Pin device layout to row-major for ndim>=2 operands.  jax 0.9's
    device_put otherwise asks the compiler for a 'preferred' layout —
    for [R, S, W] stacks that is shard-axis-major {2,0,1} — while the
    row-gather kernels compute in row-major {2,1,0}; the mismatch makes
    XLA open every dispatch with a full-stack relayout copy (a 2.9 GB
    stack -> ~9 ms/query where the actual fused count is ~335 us).
    Pinning the put keeps argument layout == fusion layout, and plain
    jit adopts the argument's layout, so no copy anywhere."""
    if ndim < 2:
        return sh
    return Format(Layout(major_to_minor=tuple(range(ndim))), sh)


def put_global(mesh: Mesh, arr, spec: PartitionSpec):
    """Place a host array on the mesh with ``spec``: each addressable
    device receives exactly the block it owns, straight from host
    memory — nothing is staged whole on one device first, and in a
    multi-process runtime (jax.distributed) each process contributes
    only its own devices' blocks, the only legal way to build shard_map
    operands on a pod.  Layout is pinned row-major (see
    _row_major_format)."""
    host = np.asarray(arr)
    fmt = _row_major_format(NamedSharding(mesh, spec), host.ndim)
    return jax.make_array_from_callback(host.shape, fmt, lambda idx: host[idx])


def describe(mesh: Mesh) -> dict:
    """Where this mesh runs, as JAX reports it: the /debug/vars ``mesh``
    block and the server's start-up line.  ``bytes_in_use`` /
    ``bytes_limit`` come from ``memory_stats()`` and are absent on
    backends that keep none (CPU)."""
    devices = list(mesh.devices.flat)
    per_device = []
    for d in devices:
        if d.process_index != jax.process_index():
            continue
        stats = d.memory_stats() or {}
        per_device.append({
            "id": d.id,
            **{k: stats[k] for k in ("bytes_in_use", "bytes_limit") if k in stats},
        })
    return {
        "platform": devices[0].platform,
        "deviceKind": devices[0].device_kind,
        "devices": len(devices),
        "perDevice": per_device,
    }


def pad_shards(n_shards: int, mesh: Mesh) -> int:
    """Shard count padded up to a multiple of the mesh size."""
    n_dev = mesh.devices.size
    return max(((n_shards + n_dev - 1) // n_dev) * n_dev, n_dev)


def shard_owner(shard_index: int, n_shards_padded: int, mesh: Mesh) -> int:
    """Mesh position owning a (packed) shard index.  ``n_shards_padded``
    must be a positive multiple of the mesh size (what ``pad_shards``
    returns) — anything else is a caller bug surfaced loudly, not a
    ZeroDivisionError deep in a dispatch."""
    n_dev = int(mesh.devices.size)
    if n_shards_padded < n_dev or n_shards_padded % n_dev:
        raise ValueError(
            f"n_shards_padded={n_shards_padded} is not a positive "
            f"multiple of the mesh size {n_dev} (use pad_shards)"
        )
    per_dev = n_shards_padded // n_dev
    return shard_index // per_dev


def stack_sharded(arrays: Sequence[np.ndarray], mesh: Mesh, pad_to: Optional[int] = None):
    """Stack per-shard host arrays into a device array sharded over the
    mesh axis, zero-padding to the mesh multiple.  An empty shard list
    has no element shape/dtype to build from and is rejected explicitly
    (callers short-circuit empty queries before placement)."""
    n = len(arrays)
    if n == 0:
        raise ValueError("stack_sharded: empty shard list")
    padded = pad_to if pad_to is not None else pad_shards(n, mesh)
    base = np.asarray(arrays[0])
    out = np.zeros((padded,) + base.shape, dtype=base.dtype)
    for i, a in enumerate(arrays):
        out[i] = a
    return put_global(mesh, out, PartitionSpec(SHARD_AXIS))

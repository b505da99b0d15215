"""Mesh-engine error types.

A separate module so the class has a single import-cycle-free home: the
executor imports it at module scope while the engine (which also
re-exports it for back-compat) is imported lazily inside functions.
Note the parallel package __init__ still pulls in the engine (and thus
jax) — this module does not make the import path jax-free, it just
keeps the error type independent of engine-module load order."""


class PeerlessMeshError(RuntimeError):
    """A collective cannot proceed on a multi-process mesh — no peer
    broadcast configured, or the broadcast handoff failed (peer down,
    rejected, commit lost).  Entering the collective would hang forever,
    so fused paths fall back to the per-shard host path instead: peer
    outage degrades to local service, never to a hung psum."""


class ResidencyMiss(PeerlessMeshError):
    """The query's field stack (or the rows/blocks it touches) is not
    device-resident and would not fit the device budget as a whole — an
    async promotion of the touched working set has been ENQUEUED and the
    query must serve from the compressed host tier instead of blocking
    on (or OOMing) a device upload (docs/residency.md).  Subclasses
    PeerlessMeshError deliberately: every fused engine path the executor
    guards already degrades to the bit-exact per-shard host loop on that
    type, so a cold stack costs latency, never correctness or a 500."""

    def __init__(self, msg: str, key=None, resident_fraction: float = 0.0):
        super().__init__(msg)
        self.key = key
        self.resident_fraction = resident_fraction


class NarrowDecline(ValueError):
    """The device's Tanimoto TopN lane does not take this call
    (``MeshEngine.tanimoto_async``): the host walk answers it.
    ``reason`` names why, as ``pilosa_executor_topn_declines_total``
    counts it: ``shards`` (the field's rows lie in more than one local
    shard), ``cache`` (its ranked cache does not hold every row, so the
    walk ranks a subset), ``budget`` (its narrow form does not fit the
    device budget)."""

    def __init__(self, reason: str):
        super().__init__(f"Tanimoto TopN declined: {reason}")
        self.reason = reason

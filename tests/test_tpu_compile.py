"""AOT-compile for a TPU v5e, on the CPU, every device program
chip_smoke.py dispatches — and bound each one's scratch memory.

libtpu hands out a compile-only ``v5e:2x2`` topology without a chip, so
Mosaic refusals (the Pallas block kernel) and layout-driven stack-sized
temps (a reshape that is a bitcast on CPU and a relayout under the
TPU's (8, 128) tiling) fail tier-1 here instead of serving a fallback
on the chip.  The programs are not hand-built: the real engine runs the
smoke's query set over a tiny index with its kernel entry points
recorded, and each recorded call is re-lowered with its own static
arguments at the smoke's shapes — ``[8, 960, 32768]`` stacks on 1 and 4
devices.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import topologies
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from pilosa_tpu.core.field import FieldOptions
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.executor import Executor
from pilosa_tpu.ops import SHARD_WIDTH
from pilosa_tpu.ops.bitops import OCC_BLOCK_BITS, WORDS
from pilosa_tpu.parallel import MeshEngine, make_mesh
from pilosa_tpu.parallel import engine as engine_mod
from pilosa_tpu.parallel import kernels, sparse
from pilosa_tpu.parallel.mesh import SHARD_AXIS
from pilosa_tpu.pql import parse

TINY_SHARDS = 24  # recorded shard axis; no other operand dim equals it
FULL_SHARDS = 960
ROW_BYTES = WORDS * 4  # one (row, shard)

# (module, entry point) -> number of leading static (non-array) arguments
ENTRY_POINTS = {
    (kernels, "count_tree"): 3, (kernels, "count_batch_tree"): 3,
    (kernels, "sum_tree"): 4, (kernels, "minmax_tree"): 5,
    (kernels, "topn_full_tree"): 5, (kernels, "topn_slab_tree"): 6,
    (kernels, "group_tree"): 6, (kernels, "fused_tree"): 3,
    (sparse, "count_tree_blocks"): 2,
}


@pytest.fixture(scope="module")
def topology():
    return topologies.get_topology_desc(topology_name="v5e:2x2", platform="tpu")


@pytest.fixture(scope="module")
def recorded():
    """{(entry point, static args): (jitted fn, args)} from the real
    engine answering the smoke's query set over a 24-shard index (f: 8
    rows in every occupancy block, s: 4 rows clustered in one block, v:
    int 0..255)."""
    h = Holder()
    h.open()
    idx = h.create_index("i")
    f, s = idx.create_field("f"), idx.create_field("s")
    v = idx.create_field("v", FieldOptions(type="int", min=0, max=255))
    rng = np.random.default_rng(3)
    every_block = np.arange(0, SHARD_WIDTH, OCC_BLOCK_BITS)
    for field, rows, starts in ((f, range(1, 9), every_block), (s, range(1, 5), [0])):
        rs, cs = [], []
        for sh in range(TINY_SHARDS):
            for r in rows:
                for start in starts:
                    rs.append(r)
                    cs.append(sh * SHARD_WIDTH + int(start + rng.integers(OCC_BLOCK_BITS)))
        field.import_bulk(rs, cs)
    cols = [sh * SHARD_WIDTH + int(c) for sh in range(TINY_SHARDS)
            for c in rng.choice(SHARD_WIDTH, size=40, replace=False)]
    v.import_values(cols, [int(x) for x in rng.integers(0, 256, len(cols))])

    calls = {}
    mp = pytest.MonkeyPatch()

    def record(mod, name, n_static):
        fn = getattr(mod, name)

        def wrapper(*args):
            calls.setdefault((name, args[1:n_static]), (fn, args[n_static:]))
            return fn(*args)

        mp.setattr(mod, name, wrapper)

    for (mod, name), n_static in ENTRY_POINTS.items():
        record(mod, name, n_static)
    try:
        eng = MeshEngine(h, make_mesh(1))
        ex = Executor(h, mesh_engine=eng)
        shards = list(range(TINY_SHARDS))
        for q in (
            "Count(Intersect(Row(f=1), Row(f=2)))",
            "Count(Intersect(Row(s=1), Row(s=2)))",
            "Sum(field=v)", "Min(field=v)", "Count(Range(v > 100))",
            "TopN(f, Row(s=1), n=2)", "GroupBy(Rows(field=f), Rows(field=s))",
        ):
            ex.execute("i", q)
        src = parse("Row(s=1)").calls[0]
        eng.topn_device_full("i", "f", src, shards, 2, 1)
        eng.count_many(
            "i", [parse(f"Union(Row(f={a}), Row(f={a + 1}))").calls[0] for a in range(1, 5)],
            [shards] * 4)
        # Nine distinct Counts: the tier-64 batched program, 9 slots live.
        eng.count_many(
            "i", [parse(f"Union(Row(f={a}), Row(s={b}))").calls[0]
                  for a in range(1, 4) for b in range(1, 4)],
            [shards] * 9)
        eng.fused_many("i", [
            ({"kind": "count", "call": parse("Intersect(Row(f=3), Row(s=2))").calls[0]}, shards),
            ({"kind": "sum", "field": "v", "filter": parse("Row(f=1)").calls[0]}, shards),
            ({"kind": "topnf", "field": "f", "src": src, "n": 3, "threshold": 1,
              "row_ids": None}, shards),
            ({"kind": "group", "fields": ["s", "f"], "rows": [[1, 2, 3, 4], list(range(1, 9))],
              "filter": None}, shards),
        ])
        eng.close()
    finally:
        mp.undo()
        h.close()
    missing = {name for _, name in ENTRY_POINTS} - {name for name, _ in calls}
    assert not missing, f"the engine never dispatched {sorted(missing)}"
    return calls


def _abstract(arg, mesh):
    """A recorded operand at the smoke's size on ``mesh``: the shard
    axis grows to 960, the placement keeps its spec."""
    shape = tuple(FULL_SHARDS if d == TINY_SHARDS else d for d in arg.shape)
    spec = arg.sharding.spec if isinstance(arg.sharding, NamedSharding) else P()
    return jax.ShapeDtypeStruct(shape, arg.dtype, sharding=NamedSharding(mesh, spec))


def _temp_bytes(fn, mesh, static, arrays):
    compiled = fn.lower(mesh, *static, *[_abstract(a, mesh) for a in arrays]).compile()
    return compiled.memory_analysis().temp_size_in_bytes


# Scratch each program may hold per device, in rows of its local shard
# block (S_local x 128 KiB) — always fewer than the operand rows it
# reads (a Count reads >= 2, the BSI walks 9 planes, TopN/GroupBy every
# candidate row).  Observed at this commit: 0 everywhere except
# minmax_tree (2.0 on one device: the walk's running keep-sets).
TEMP_ROWS = {
    "count_tree": 1, "count_batch_tree": 1,
    "count_tree_blocks": 1, "count_tree_blocks_pallas": 1,
    "sum_tree": 1, "minmax_tree": 3, "topn_full_tree": 1, "topn_slab_tree": 1,
    # The XLA body's loop holds a prefix mask; the Pallas body's lane
    # accumulators ([groups, 8, 128] int32) are summed outside it.
    "group_tree": 2, "group_tree_pallas": 2,
    # KNOWN DEBT, pinned so it cannot grow (ROADMAP S4(c)): the fused
    # "topnf" edge gathers its candidates by a TRACED index (jnp.take)
    # and XLA materializes the gather — 23.75 rows = 2.99 GB of temp on
    # one device for this 21-row drain, where solo topn_full_tree
    # (static gather-free slice) holds none.
    "fused_tree": 25,
}


@pytest.mark.parametrize("n_dev", [1, 4])
def test_smoke_programs_compile_for_v5e(topology, recorded, n_dev):
    mesh = Mesh(np.asarray(topology.devices[:n_dev]), (SHARD_AXIS,))
    row_block = FULL_SHARDS // n_dev * ROW_BYTES
    temps = {}
    for (name, static), (fn, arrays) in recorded.items():
        temps[name, static] = _temp_bytes(fn, mesh, static, arrays)
        if name == "count_tree_blocks":
            # The Pallas form of the same plan (TPU backends select it;
            # the CPU engine above ran the XLA form): Mosaic must take it.
            temps["count_tree_blocks_pallas", static] = _temp_bytes(
                sparse.count_tree_blocks_pallas, mesh, (*static, False), arrays)
        if name == "group_tree":
            # Likewise the GroupBy program's Pallas body (static 4).
            temps["group_tree_pallas", static] = _temp_bytes(
                fn, mesh, (*static[:3], True, *static[4:]), arrays)
        if name == "fused_tree":
            # ... and the fused program with its group edge on that body.
            slots, counts, aggs = static[0]
            aggs = tuple(e[:5] + (True,) if e[0] == "group" else e for e in aggs)
            assert any(e[0] == "group" for e in aggs)
            temps["fused_tree", ("pallas",) + static] = _temp_bytes(
                fn, mesh, ((slots, counts, aggs), *static[1:]), arrays)
    over = {k: v for k, v in temps.items() if v >= TEMP_ROWS[k[0]] * row_block}
    assert not over, f"temp >= allowed operand rows ({row_block} B each): {over}"
    # The sparse forms exist to read fewer bytes than a row.
    for key, temp in temps.items():
        if key[0].startswith("count_tree_blocks"):
            assert temp < row_block // 16, (key, temp)


def test_batched_count_branches_hold_no_operand_copies_on_v5e(topology, recorded):
    """The tier-64 batched Count program runs a slot only below its
    traced live count (real control flow in the compiled HLO), and a
    branch reads the resident stacks in place.  Against the same slots
    evaluated unconditionally — the form that read every pad slot's
    planes on the chip — it holds 43 KB more scratch a slot (2.7 MB:
    each branch keeps its own reduce scratch); one operand row copied
    into one branch would be a row block, 126 MB."""
    mesh = Mesh(np.asarray(topology.devices[:1]), (SHARD_AXIS,))
    (static, arrays), = [
        (static, arrays) for (name, static), (_, arrays) in recorded.items()
        if name == "count_batch_tree" and len(static[0]) == 64]
    progs, specs = static

    @jax.jit
    def every_slot(*operands):
        def body(*ops):
            return jax.lax.psum(jnp.stack([
                jnp.sum(jax.lax.population_count(
                    kernels.apply_prog(prog, ops) & ops[i_mask]).astype(jnp.int32))
                for prog, i_mask in progs]), SHARD_AXIS)
        return kernels.shard_map(body, mesh=mesh, in_specs=specs, out_specs=P())(*operands)

    n_live, *operands = [_abstract(a, mesh) for a in arrays]
    compiled = kernels.count_batch_tree.lower(mesh, *static, n_live, *operands).compile()
    assert compiled.as_text().count(" conditional(") == 64
    unconditional = every_slot.lower(*operands).compile()
    extra = (compiled.memory_analysis().temp_size_in_bytes
             - unconditional.memory_analysis().temp_size_in_bytes)
    assert extra < FULL_SHARDS * ROW_BYTES // 16, extra


@pytest.mark.parametrize("n_dev", [1, 4])
def test_scatter_jits_update_in_place_on_v5e(topology, n_dev):
    """Both donated scatters alias the whole [8, 960, 32768] stack:
    no stack-sized temp, so a write never copies the field."""
    mesh = Mesh(np.asarray(topology.devices[:n_dev]), (SHARD_AXIS,))

    def sds(shape, dtype, spec=P()):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, spec))

    stack = sds((8, FULL_SHARDS, WORDS), jnp.uint32, P(None, SHARD_AXIS))
    ids = sds((8,), jnp.int32)
    jits = engine_mod._scatter_jits(mesh)
    for name, extra in (
        ("rows_donated", (ids, ids, sds((8, WORDS), jnp.uint32))),
        ("words_donated", (ids, ids, ids, sds((8,), jnp.uint32))),
    ):
        ma = jits[name].lower(mesh, stack, *extra).compile().memory_analysis()
        stack_bytes = 8 * FULL_SHARDS // n_dev * ROW_BYTES
        assert ma.alias_size_in_bytes >= stack_bytes, (name, ma.alias_size_in_bytes)
        assert ma.temp_size_in_bytes < ROW_BYTES * 16, (name, ma.temp_size_in_bytes)


@pytest.mark.parametrize("n_dev", [1, 4])
@pytest.mark.parametrize("dims", [(6, 10, 10), (6, 5, 5)], ids=str)
def test_aggregated_group_by_compiles_for_v5e_at_flight_3s_shapes(topology, n_dev, dims):
    """``group_tree`` with a measure (aggregate=Sum over a 24-bit field:
    26 popcount passes a combination) on its Pallas body, at
    ssb.flight3_stream's shapes: 64 shards, three traced axes of 6 x 10
    x 10 or 6 x 5 x 5 rows out of 7-, 250- and 25-row stacks.  Mosaic
    must take it, and the program's scratch is the rows its traced axes
    name, sliced and stacked (twice 26 of them), plus the lane
    accumulators: ``jnp.take`` on the 250-row stacks held a copy of a
    whole stack, 2.1 GB on one device."""
    mesh = Mesh(np.asarray(topology.devices[:n_dev]), (SHARD_AXIS,))
    shards, depth = 64, 24

    def sds(shape, dtype, spec=P()):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, spec))

    stacks = [sds((k, shards, WORDS), jnp.uint32, P(None, SHARD_AXIS))
              for k in (7, 250 if dims[1] == 10 else 25, 250 if dims[1] == 10 else 25)]
    planes = sds((depth + 1, shards, WORDS), jnp.uint32, P(None, SHARD_AXIS))
    mask = sds((shards, 1), jnp.uint32, P(SHARD_AXIS))
    compiled = kernels.group_tree.lower(
        mesh, ("ones",), (P(),) * 3, (None,) * 3, True, ("slice", 0, depth + 1),
        mask, *stacks, planes, *[sds((k,), jnp.int32) for k in dims]).compile()
    assert "tpu_custom_call" in compiled.as_text()
    row_block = shards // n_dev * ROW_BYTES
    accumulators = kernels.GROUP_ACC_GROUPS * 8 * 128 * 4 * 4  # four passes' lanes, summed outside
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * sum(dims) * row_block + accumulators


@pytest.mark.parametrize("n_dev", [1, 4])
@pytest.mark.parametrize("shards,dims", [(256, (10, 7, 51)), (64, (40, 30, 5))], ids=["query4", "wide_prefix_fields"])
def test_group_by_compiles_for_v5e_at_taxi_query_4s_nest(topology, n_dev, shards, dims):
    """``group_tree`` on its Pallas body at taxi.q4_stream's shape: 256
    shards, whole static axes of 10 x 7 x 51 rows (3,570 combinations
    in one accumulator pass), the widest scored innermost.  Mosaic must
    take the liveness bits (a roll tree and one reduce to a scalar a
    grid step, SMEM scratch) and the nested walk under ``pl.when``; the
    program holds no copy of a stack: its scratch is the lane
    accumulators, summed outside the kernel, and the two step counts.
    And with prefix fields of 40 and 30 rows: past GROUP_UNROLL_WHOLE
    rows the liveness tests are loops, their bits fill three words, and
    a field that lies across two reads its word from SMEM by a traced
    index."""
    mesh = Mesh(np.asarray(topology.devices[:n_dev]), (SHARD_AXIS,))

    def sds(shape, dtype, spec=P()):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, spec))

    stacks = [sds((k, shards, WORDS), jnp.uint32, P(None, SHARD_AXIS)) for k in dims]
    mask = sds((shards, 1), jnp.uint32, P(SHARD_AXIS))
    compiled = kernels.group_tree.lower(
        mesh, ("ones",), (), tuple(tuple(range(k)) for k in dims), True, None,
        mask, *stacks).compile()
    assert "tpu_custom_call" in compiled.as_text()
    accumulators = int(np.prod(dims)) * 8 * 128 * 4
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * accumulators + shards // n_dev * ROW_BYTES

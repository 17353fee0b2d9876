"""What the TPU's compiler says, asked without a TPU.

Every case compiles one main-path program for a *described* v5e:2x2 (the
TPU compiler is installed here; no chip is attached) at the shapes of one
default batch, n = 2^17, with ``interpret=False``. A kernel that passes the
Pallas interpreter can still be refused here — the hash engine's kernels
are (ops/pallas_hash.TPU_REFUSAL), which is why that engine is not
selectable on a TPU backend, and one case pins that gate.

The multi-operand int64 sorts of the sort engine (build_side, grouped_merge
on up to `ops/sort.ONE_SORT_MAX_KEYS` keys) are accepted too but take
minutes each to compile, so they stay out of this file; CHANGES.md has
their verdicts and seconds. Past that many keys a sort is a loop of
one-key sorts, and one case compiles it.

The topology is described inside a module-scoped fixture — never at import:
only one process may load the TPU library, and under xdist every worker
imports every test file. Compiles happen in the test's own process, with the
persistent compilation cache off (a described-device executable cannot be
read back without a chip).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

N = 1 << 17


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    # the verdict asked for is the one the chip's full pipeline gives, not
    # the suite's cheap compiles (tests/conftest.py)
    cheap = jax.config.read("jax_disable_most_optimizations")
    jax.config.update("jax_disable_most_optimizations", False)
    yield t
    jax.config.update("jax_disable_most_optimizations", cheap)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _placed(tree, sharding):
    """The same pytree, each shape leaf placed by `sharding` (static
    arguments pass through)."""
    return jax.tree_util.tree_map(
        lambda x: (jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)
                   if hasattr(x, "shape") else x),
        tree)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


@pytest.mark.parametrize("groups,n_states", [(64, 2), (128, 16)])
def test_grouped_sums_kernel_compiles(one_chip, groups, n_states):
    """The MXU limb-split group-by (PRESTO_TPU_PALLAS=1): the one Pallas
    kernel selectable on a TPU backend. (128, 16) is the widest the
    direct-domain path asks for (_MASK_SLOTS groups, Q1-sized state list)."""
    from presto_tpu.ops import pallas_groupby

    compiled = jax.jit(
        lambda g, *s: pallas_groupby.grouped_sums(
            g, list(s), groups, interpret=False)
    ).lower(_sds((N,), jnp.int32, one_chip),
            *[_sds((N,), jnp.int64, one_chip)] * n_states).compile()
    assert "tpu_custom_call" in compiled.as_text()


_PROBE_TEXTS = {}


def _probe_program_text(one_chip, program):
    """The compiled text of one join-probe program (below), compiled once
    a process."""
    if program in _PROBE_TEXTS:
        return _PROBE_TEXTS[program]
    from presto_tpu.batch import Batch, Column
    from presto_tpu.ops import join
    from presto_tpu.types import BIGINT

    def batch(names, n):
        return Batch(names, [BIGINT] * len(names),
                     [Column(jax.ShapeDtypeStruct((n,), jnp.int64))
                      for _ in names],
                     jax.ShapeDtypeStruct((n,), jnp.bool_), {})

    two = program.endswith("_on_two_keys")
    pk, bk = (["pk", "pk2"], ["bk", "bk2"]) if two else (["pk"], ["bk"])
    table = jax.eval_shape(lambda b: join.build_side(b, bk),
                           batch(bk + ["payload"], N if two else 12 * N))
    probe = batch(pk + ["v"], N)
    if program.startswith("probe_expand"):
        lo, counts, offsets, *_ = jax.eval_shape(
            lambda t, p: join.probe_counts(t, p, pk, bk), table, probe)
        fn = lambda t, p, lo, c, o, base: join.probe_expand(  # noqa: E731
            t, p, pk, bk, lo, c, o, base, N)
        args = (table, probe, lo, counts, offsets,
                jax.ShapeDtypeStruct((), jnp.int64))
    elif program.startswith("probe_counts"):
        fn = lambda t, p: join.probe_counts(t, p, pk, bk)  # noqa: E731
        args = (table, probe)
    else:
        fn = lambda t, p: join.probe_unique(  # noqa: E731
            t, p, ["pk"], ["bk"])
        args = (table, probe)
    compiled = jax.jit(fn).lower(*_placed(args, one_chip)).compile()
    assert compiled.memory_analysis() is not None
    _PROBE_TEXTS[program] = compiled.as_text()
    return _PROBE_TEXTS[program]


@pytest.mark.parametrize("program", [
    "probe_unique", "probe_expand", "probe_expand_on_two_keys",
    "probe_counts", "probe_counts_on_two_keys", "bucket_directory"])
def test_directory_probe_compiles(one_chip, program):
    """The sort engine's join probe (ops/join.py) at sf1_q3's shapes: a
    batch of 2^17 int64 keys against a sorted build of twelve such batches
    — the directory gathers, the halving loop over 32-bit fingerprints
    bounded by a device scalar, the verification of the lanes from there
    bounded by another (one fingerprint and one key a round, the key's 64
    bits as two 32-bit gathers); the slot-to-row scatter and running sum; and the
    directory itself over hashes already sorted (the build's sort stays out
    of this file, as above). probe_counts is the halving search over the
    64-bit hashes and a read of the run's end: no key column is gathered.
    On two keys it is sf1_q9's counting pass and expand: partsupp's batch
    against lineitem's chain of 2^17 lanes."""
    from presto_tpu.ops import join

    if program == "bucket_directory":
        compiled = jax.jit(join._bucket_directory).lower(
            _sds((12 * N,), jnp.int64, one_chip),
            _sds((), jnp.int64, one_chip)).compile()
        assert compiled.memory_analysis() is not None
        return
    text = _probe_program_text(one_chip, program)
    # no binary search of the whole build or of the prefix sums is left:
    # the unique probe's loops are the halving inside a bucket and the
    # verification, the counting pass's the halving alone; the expand has
    # none
    loops = {"probe_unique": 2, "probe_counts": 1,
             "probe_counts_on_two_keys": 1}.get(program, 0)
    assert text.count(" while(") == loops
    if program == "probe_unique":
        # every gather is of the whole batch: 15 before the fingerprint
        assert text.count(" gather(") <= 6
    if program.startswith("probe_counts"):
        # a count is its run's width: a second key adds no gather
        one_key = _probe_program_text(one_chip, "probe_counts")
        assert text.count(" gather(") == one_key.count(" gather(")


def test_wide_grouped_merge_compiles_to_one_sort_in_a_loop(one_chip):
    """The sort engine's merge of TPC-DS Q67's finest grouping set: eight
    keys (five int32 dictionary codes, three int64), a table of N and a
    batch of N. Its nine key operands are one stable one-key sort in a
    loop (ops/sort.lex_sort), where one nine-key lax.sort compiled for
    825 s at N."""
    from presto_tpu.ops.grouping import KeyCol, StateCol, grouped_merge

    dtypes = [jnp.int32] * 4 + [jnp.int64] * 3 + [jnp.int32]

    def merge(keys, x, live):
        kout, sout, out_live, n_groups = grouped_merge(
            [KeyCol(k, None) for k in keys], [StateCol(x, None, "sum")],
            live, N, engine="sort")
        return [k.values for k in kout], sout[0].values, out_live, n_groups

    compiled = jax.jit(merge).lower(
        [_sds((2 * N,), d, one_chip) for d in dtypes],
        _sds((2 * N,), jnp.int64, one_chip),
        _sds((2 * N,), jnp.bool_, one_chip)).compile()
    text = compiled.as_text()
    assert text.count(" sort(") == 1
    assert compiled.memory_analysis() is not None


def test_q6_scan_filter_aggregate_chain_compiles(one_chip, monkeypatch):
    """TPC-H Q6's leaf fragment as the engine really builds it: run Q6 on
    the CPU over 8 batches of 2^17 rows, record the fused fragment-step
    programs (exec/fragment_jit.py) with the arguments they were called
    with, and hand the same programs, at the same shapes, to the TPU
    compiler."""
    import pandas as pd

    from presto_tpu.catalog.memory import MemoryConnector
    from presto_tpu.connector import Catalog
    from presto_tpu.exec import ExecConfig, LocalRunner, programs
    from presto_tpu.types import DATE, DecimalType

    rows = 8 * N
    conn = MemoryConnector()
    conn.add_table(
        "lineitem",
        pd.DataFrame({
            "l_extendedprice": np.full(rows, 1000.0),
            "l_discount": np.full(rows, 0.06),
            "l_quantity": np.full(rows, 10, np.int64),
            "l_shipdate": np.full(rows, 8800, np.int64)}),
        types={"l_extendedprice": DecimalType(15, 2),
               "l_discount": DecimalType(15, 2), "l_shipdate": DATE})
    cat = Catalog()
    cat.register("m", conn, default=True)

    calls = []
    real_wrap = programs.wrap

    def spying_wrap(entry, node_stats, node_kind, key):
        wrapped = real_wrap(entry, node_stats, node_kind, key)

        def spy(*a, **k):
            shapes = jax.tree_util.tree_map(
                lambda x: (jax.ShapeDtypeStruct(x.shape, x.dtype)
                           if hasattr(x, "shape") else x), (a, k))
            calls.append((key, entry.jfn, shapes))
            return wrapped(*a, **k)

        spy._entry = entry
        return spy

    monkeypatch.setattr(programs, "wrap", spying_wrap)
    programs.reset(counters_only=False)
    out = LocalRunner(cat, ExecConfig()).run("""
        select sum(l_extendedprice * l_discount) as revenue from lineitem
        where l_shipdate >= date '1994-01-01'
          and l_shipdate < date '1995-01-01'
          and l_discount between 0.05 and 0.07 and l_quantity < 24""")
    assert len(out) == 1

    steps = {key: (jfn, shapes) for key, jfn, shapes in calls
             if key.startswith("fragment")}
    assert steps, sorted({k for k, _, _ in calls})
    for key, (jfn, (a, k)) in steps.items():
        leaves = jax.tree_util.tree_leaves((a, k))
        assert any(getattr(x, "shape", ())[-1:] == (N,) for x in leaves), key
        jfn.lower(*_placed(a, one_chip), **_placed(k, one_chip)).compile()


def test_all_to_all_exchange_compiles_on_four_device_mesh(topo):
    """One HASH-repartition exchange of the mesh data plane
    (parallel/mesh_exec.py): partition layout → fused lane pack → ONE
    all_to_all → unpack, as a shard_map program over the 2x2's four chips,
    2^17 rows per device."""
    from presto_tpu.batch import Batch, Column
    from presto_tpu.ops.partition import partition_layout
    from presto_tpu.parallel import lanes
    from presto_tpu.parallel.mesh import WORKERS, shard_map
    from presto_tpu.parallel.mesh_exec import _fused_all_to_all
    from presto_tpu.types import BIGINT, DATE

    n_dev = len(topo.devices)
    assert n_dev == 4
    mesh = Mesh(np.array(topo.devices), (WORKERS,))
    rows = NamedSharding(mesh, P(WORKERS))
    per_cap = N // n_dev
    glob = Batch(["k", "d"], [BIGINT, DATE],
                 [Column(_sds((n_dev * N,), jnp.int64, rows)),
                  Column(_sds((n_dev * N,), jnp.int32, rows))],
                 _sds((n_dev * N,), jnp.bool_, rows), {})
    plan = lanes.plan_lanes(glob)

    def exchange(b):
        sperm, dest, _counts, routed, _ovf = partition_layout(
            b, ["k"], n_dev, per_cap)
        bufs = lanes.pack_partitioned(b, plan, sperm, dest, routed,
                                      n_dev * per_cap)
        return lanes.unpack_batch(b, plan,
                                  _fused_all_to_all(bufs, n_dev, per_cap))

    compiled = jax.jit(shard_map(
        exchange, mesh=mesh, in_specs=(P(WORKERS),),
        out_specs=P(WORKERS), check_vma=False)).lower(glob).compile()
    assert "all-to-all" in compiled.as_text()


def test_hash_engine_is_a_loud_error_on_a_tpu_backend(monkeypatch):
    """The hash engine's kernels are refused by the TPU compiler, so with
    the backend reported as TPU: `auto` answers sort and says why (EXPLAIN
    shows it), a forced `hash` raises at plan install, and the multiway
    join declines fanout legs. Steered here, in the test — the program has
    no option for it."""
    from presto_tpu.catalog.tpch import tpch_catalog
    from presto_tpu.exec import ExecConfig, LocalRunner
    from presto_tpu.ops import pallas_hash
    from presto_tpu.plan import stats

    sql = ("select l_returnflag, sum(l_quantity) as s from lineitem "
           "group by l_returnflag")
    cat = tpch_catalog(0.01)
    assert "[engine=hash: est" in LocalRunner(cat, ExecConfig()).explain(sql)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert not pallas_hash.use_interpret()
    plan = LocalRunner(cat, ExecConfig()).explain(sql)
    assert "[engine=sort: hash engine not selectable on tpu" in plan
    agg = LocalRunner(cat, ExecConfig()).plan(sql).root.child
    assert stats.choose_breaker_engine_observed(agg, 8.0, 1e6)[0] == "sort"
    with pytest.raises(stats.HashEngineUnavailable,
                       match="group_insert.*32-bit element types"):
        LocalRunner(cat, ExecConfig(breaker_engine="hash")).run(sql)
    with pytest.raises(stats.HashEngineUnavailable):
        from presto_tpu.parallel.mesh import make_mesh
        from presto_tpu.parallel.mesh_exec import MeshExecutor

        MeshExecutor(cat, make_mesh(2),
                     ExecConfig(breaker_engine="hash")).run(sql)

"""ICI distributed-aggregate tests on a virtual 8-device CPU mesh.

Analog of the reference's no-cluster shuffle protocol tests (reference:
RapidsShuffleClientSuite/RapidsShuffleServerSuite driven with mocked
transports — SURVEY.md §4.2): the full exchange runs in one process, here
with real XLA collectives over virtual devices instead of mocks.
"""

import numpy as np
import pyarrow as pa
import pytest

import jax
from jax.sharding import Mesh

from spark_rapids_tpu import dtypes as dt
from spark_rapids_tpu.columnar.batch import from_arrow
from spark_rapids_tpu.expr import ir
from spark_rapids_tpu.plan.logical import Schema, Field
from spark_rapids_tpu.shuffle import ici


def _mesh():
    devs = np.array(jax.devices()[:8])
    return Mesh(devs, ("data",))


def _run_distributed_agg(table, key_names, aggs_builder, n=None):
    mesh = _mesh()
    schema = Schema.from_arrow(table.schema)
    groupings = [ir.bind(ir.UnresolvedAttribute(k), schema.names,
                         schema.dtypes, schema.nullables)
                 for k in key_names]
    aggregates = aggs_builder(schema)
    out_names = key_names + [f"a{i}" for i in range(len(aggregates))]
    batch = from_arrow(table, min_bucket=8 * 8)
    if batch.capacity % 8 != 0:
        pytest.skip("capacity not divisible")
    step, out_dtypes = ici.make_distributed_agg_step(
        mesh, "data", schema, groupings, aggregates, out_names)
    leaves, counts = ici.shard_batch(batch, mesh, "data")
    out_leaves, out_rows = step(leaves, counts)
    # reassemble the 8 output shards into one arrow table
    out_rows = np.asarray(out_rows)
    n_dev = 8
    per_dev_cap = out_leaves[0][0].shape[0] // n_dev
    from spark_rapids_tpu.columnar.batch import DeviceColumn, DeviceBatch, \
        to_arrow
    tables = []
    for d in range(n_dev):
        cols = []
        for leaf, dty in zip(out_leaves, out_dtypes):
            sl = slice(d * per_dev_cap, (d + 1) * per_dev_cap)
            if len(leaf) == 3:
                cols.append(DeviceColumn(dty, leaf[0][sl], leaf[1][sl],
                                         leaf[2][sl]))
            else:
                cols.append(DeviceColumn(dty, leaf[0][sl], leaf[1][sl],
                                         None))
        tables.append(to_arrow(DeviceBatch(out_names, cols,
                                           int(out_rows[d]))))
    return pa.concat_tables(tables)


def _sorted_pylist(t, keys):
    rows = list(zip(*[t.column(i).to_pylist()
                      for i in range(t.num_columns)]))
    return sorted(rows, key=lambda r: tuple(
        (v is None, str(v)) for v in r))


def test_distributed_sum_count():
    rng = np.random.default_rng(0)
    n = 500
    table = pa.table({
        "k": pa.array(rng.integers(0, 23, n), type=pa.int32()),
        "v": pa.array(rng.integers(-100, 100, n), type=pa.int64()),
    })

    def aggs(schema):
        v = ir.bind(ir.UnresolvedAttribute("v"), schema.names,
                    schema.dtypes, schema.nullables)
        out = [ir.Sum(v), ir.Count(v), ir.Min(v), ir.Max(v)]
        for a in out:
            a.resolve()
        return out

    got = _run_distributed_agg(table, ["k"], aggs)

    # oracle via pandas
    pd = table.to_pandas().groupby("k").agg(
        a0=("v", "sum"), a1=("v", "count"), a2=("v", "min"),
        a3=("v", "max")).reset_index()
    want = pa.Table.from_pandas(pd, preserve_index=False)
    assert got.num_rows == want.num_rows
    assert _sorted_pylist(got, ["k"]) == _sorted_pylist(want, ["k"])


def test_distributed_agg_disjoint_shards():
    """Each device's output shard must hold a disjoint set of keys
    (hash-partitioned), i.e. no group appears twice globally."""
    rng = np.random.default_rng(1)
    n = 300
    table = pa.table({
        "k": pa.array(rng.integers(0, 40, n), type=pa.int64()),
        "v": pa.array(rng.normal(size=n)),
    })

    def aggs(schema):
        v = ir.bind(ir.UnresolvedAttribute("v"), schema.names,
                    schema.dtypes, schema.nullables)
        out = [ir.Count(v)]
        for a in out:
            a.resolve()
        return out

    got = _run_distributed_agg(table, ["k"], aggs)
    keys = got.column("k").to_pylist()
    assert len(keys) == len(set(keys)), "duplicate group across shards"
    want = table.to_pandas().groupby("k")["v"].count()
    assert dict(zip(keys, got.column("a0").to_pylist())) == \
        want.to_dict()


def test_distributed_string_keys():
    rng = np.random.default_rng(2)
    n = 200
    words = ["alpha", "beta", "gamma", "delta", "x", ""]
    table = pa.table({
        "k": pa.array([words[i] for i in rng.integers(0, len(words), n)]),
        "v": pa.array(rng.integers(0, 50, n), type=pa.int64()),
    })

    def aggs(schema):
        v = ir.bind(ir.UnresolvedAttribute("v"), schema.names,
                    schema.dtypes, schema.nullables)
        out = [ir.Sum(v), ir.Count(None)]
        for a in out:
            a.resolve()
        return out

    got = _run_distributed_agg(table, ["k"], aggs)
    pd = table.to_pandas().groupby("k").agg(
        a0=("v", "sum"), a1=("v", "size")).reset_index()
    want = pa.Table.from_pandas(pd, preserve_index=False)
    assert got.num_rows == want.num_rows
    assert _sorted_pylist(got, ["k"]) == _sorted_pylist(want, ["k"])


# ---------------------------------------------------------------------------
# Planner-driven distributed execution: queries built through the public
# DataFrame API run end-to-end over the ICI data plane (transport='ici'),
# with TpuShuffleExchangeExec routing rows through one lax.all_to_all over
# the 8-virtual-device mesh.  The reference analog is a query running
# through RapidsShuffleManager's UCX plane
# (RapidsShuffleInternalManager.scala:90-186) instead of Spark's sort
# shuffle.
# ---------------------------------------------------------------------------

from spark_rapids_tpu import TpuSparkSession
import spark_rapids_tpu.api.functions as F
from tests.parity import assert_tables_equal

_ICI_CONF = {
    "spark.rapids.tpu.shuffle.transport": "ici",
    "spark.rapids.tpu.sql.variableFloatAgg.enabled": True,
}


def _cpu_collect(fn):
    s = TpuSparkSession({"spark.rapids.tpu.sql.enabled": False})
    return fn(s)


def _ici_collect(fn, extra_conf=None):
    conf = dict(_ICI_CONF)
    conf.update(extra_conf or {})
    s = TpuSparkSession(conf)
    captured = []
    s.add_plan_listener(captured.append)
    out = fn(s)
    return out, captured


def _assert_has_ici_exchange(captured):
    from spark_rapids_tpu.shuffle.exchange import TpuShuffleExchangeExec
    found = []
    captured[-1].plan.foreach(
        lambda n: found.append(n) if isinstance(n, TpuShuffleExchangeExec)
        else None)
    assert found, "no TpuShuffleExchangeExec in plan"
    assert all(x.transport == "ici" for x in found)


def _agg_query(n_parts):
    rng = np.random.default_rng(7)
    n = 700
    tbl = pa.table({
        "k": pa.array(rng.integers(0, 31, n), type=pa.int32()),
        "v": pa.array(rng.integers(-50, 50, n), type=pa.int64()),
        "s": pa.array([f"w{i % 5}" for i in range(n)]),
    })

    def q(s):
        df = s.create_dataframe(tbl, num_partitions=n_parts)
        return df.group_by("k").agg(
            F.sum("v").alias("sv"), F.count("*").alias("c"),
            F.min("s").alias("ms")).collect()
    return q


@pytest.mark.slow
def test_planned_distributed_groupby_parity():
    q = _agg_query(4)
    cpu = _cpu_collect(q)
    tpu, captured = _ici_collect(q)
    _assert_has_ici_exchange(captured)
    assert_tables_equal(cpu, tpu, ignore_order=True)


@pytest.mark.slow
def test_planned_distributed_join_parity():
    rng = np.random.default_rng(8)
    n = 600
    left = pa.table({
        "k": pa.array(rng.integers(0, 40, n), type=pa.int64()),
        "x": pa.array(rng.normal(size=n)),
    })
    right = pa.table({
        "k": pa.array(np.arange(0, 50, dtype=np.int64)),
        "tag": pa.array([f"t{i}" for i in range(50)]),
    })

    def q(s):
        # force a shuffled (non-broadcast) join so both sides exchange
        s.set_conf("spark.rapids.tpu.sql.autoBroadcastJoinThreshold", -1)
        a = s.create_dataframe(left, num_partitions=3)
        b = s.create_dataframe(right, num_partitions=2)
        return a.join(b, on="k", how="inner").collect()

    cpu = _cpu_collect(q)
    tpu, captured = _ici_collect(
        q, {"spark.rapids.tpu.sql.autoBroadcastJoinThreshold": -1})
    _assert_has_ici_exchange(captured)
    assert_tables_equal(cpu, tpu, ignore_order=True)


@pytest.mark.parametrize("how", [
    pytest.param("left", marks=pytest.mark.slow),
    pytest.param("full", marks=pytest.mark.slow),
    "leftsemi", "leftanti"])
def test_planned_distributed_join_types(how):
    rng = np.random.default_rng(9)
    left = pa.table({
        "k": pa.array(rng.integers(0, 25, 300), type=pa.int32()),
        "x": pa.array(rng.integers(0, 9, 300), type=pa.int64()),
    })
    right = pa.table({
        "k": pa.array(rng.integers(10, 35, 200), type=pa.int32()),
        "y": pa.array(rng.integers(0, 9, 200), type=pa.int64()),
    })

    def q(s):
        s.set_conf("spark.rapids.tpu.sql.autoBroadcastJoinThreshold", -1)
        a = s.create_dataframe(left, num_partitions=3)
        b = s.create_dataframe(right, num_partitions=3)
        return a.join(b, on="k", how=how).collect()

    cpu = _cpu_collect(q)
    tpu, _ = _ici_collect(
        q, {"spark.rapids.tpu.sql.autoBroadcastJoinThreshold": -1})
    assert_tables_equal(cpu, tpu, ignore_order=True)


def test_planned_repartition_roundtrip():
    tbl = pa.table({
        "a": pa.array(np.arange(123, dtype=np.int64)),
        "s": pa.array([f"row-{i}" if i % 7 else None for i in range(123)]),
    })

    def q(s):
        df = s.create_dataframe(tbl, num_partitions=2)
        return df.repartition(5, "a").collect()

    cpu = _cpu_collect(q)
    tpu, captured = _ici_collect(q)
    _assert_has_ici_exchange(captured)
    assert_tables_equal(cpu, tpu, ignore_order=True)


@pytest.mark.slow
def test_planned_distributed_agg_then_join():
    """Composite: distributed agg feeding a distributed join."""
    rng = np.random.default_rng(11)
    facts = pa.table({
        "k": pa.array(rng.integers(0, 20, 400), type=pa.int64()),
        "v": pa.array(rng.integers(0, 100, 400), type=pa.int64()),
    })
    dims = pa.table({
        "k": pa.array(np.arange(20, dtype=np.int64)),
        "w": pa.array(np.arange(20, dtype=np.int64) * 10),
    })

    def q(s):
        s.set_conf("spark.rapids.tpu.sql.autoBroadcastJoinThreshold", -1)
        f = s.create_dataframe(facts, num_partitions=4)
        d = s.create_dataframe(dims, num_partitions=2)
        g = f.group_by("k").agg(F.sum("v").alias("sv"))
        return g.join(d, on="k", how="inner").collect()

    cpu = _cpu_collect(q)
    tpu, _ = _ici_collect(
        q, {"spark.rapids.tpu.sql.autoBroadcastJoinThreshold": -1})
    assert_tables_equal(cpu, tpu, ignore_order=True)


def test_ring_broadcast_batch_replicates():
    """collective_permute plane: n_dev-1 ppermute ring hops replicate a
    sharded build batch to every device (reference analog: tag-matched
    per-peer pulls, UCXConnection.scala:385)."""
    rng = np.random.default_rng(33)
    t = pa.table({
        "k": pa.array(rng.integers(0, 99, 333), type=pa.int64()),
        "s": pa.array([f"s{i % 11}" for i in range(333)]),
    })
    batch = from_arrow(t)
    bmap = ici.ring_broadcast_batch(batch)
    assert len(bmap) == len(jax.devices())
    from spark_rapids_tpu.columnar.batch import to_arrow
    for d, b in bmap.items():
        got = to_arrow(b)
        assert got.num_rows == 333
        # replication preserves multiset content (ring order is by shard)
        assert sorted(got.column("k").to_pylist()) == \
            sorted(t.column("k").to_pylist())
        assert sorted(got.column("s").to_pylist()) == \
            sorted(t.column("s").to_pylist())


@pytest.mark.slow
def test_planned_broadcast_join_ici_ring():
    """Broadcast hash join with the build side replicated over the
    ppermute ring instead of one mesh broadcast — planner-reachable via
    spark.rapids.tpu.shuffle.transport=ici_ring."""
    rng = np.random.default_rng(22)
    n = 400
    facts = pa.table({
        "k": pa.array(rng.integers(0, 25, n), type=pa.int64()),
        "v": pa.array(rng.normal(size=n)),
    })
    dims = pa.table({
        "k": pa.array(np.arange(0, 30, dtype=np.int64)),
        "tag": pa.array([f"d{i}" for i in range(30)]),
    })

    def q(s):
        f = s.create_dataframe(facts, num_partitions=3)
        d = s.create_dataframe(dims)
        g = f.repartition(4, "k")
        return g.join(d, on="k", how="inner").collect()

    cpu = _cpu_collect(q)
    tpu, captured = _ici_collect(
        q, {"spark.rapids.tpu.shuffle.transport": "ici_ring"})
    from spark_rapids_tpu.exec.tpu_join import TpuBroadcastHashJoinExec
    joins = []
    captured[-1].plan.foreach(
        lambda x: joins.append(x)
        if isinstance(x, TpuBroadcastHashJoinExec) else None)
    assert joins, "no TpuBroadcastHashJoinExec in plan"
    assert all(j.transport == "ici_ring" for j in joins)
    assert any(j.metrics.extra.get("ici_ring_hops") == 7
               for j in joins), [j.metrics.extra for j in joins]
    assert_tables_equal(cpu, tpu, ignore_order=True)


def test_planned_broadcast_join_ici():
    """Broadcast hash join over the mesh: the build side replicates to
    every device with ONE mesh broadcast (ici.broadcast_batch,
    GpuBroadcastExchangeExec analog) and each ICI-distributed stream
    shard joins against its LOCAL copy."""
    rng = np.random.default_rng(21)
    n = 500
    facts = pa.table({
        "k": pa.array(rng.integers(0, 30, n), type=pa.int64()),
        "v": pa.array(rng.normal(size=n)),
    })
    dims = pa.table({
        "k": pa.array(np.arange(0, 40, dtype=np.int64)),
        "tag": pa.array([f"d{i}" for i in range(40)]),
    })

    def q(s):
        # distribute the stream side through an ICI exchange, then
        # broadcast-join the small dim table (under the threshold)
        f = s.create_dataframe(facts, num_partitions=3)
        d = s.create_dataframe(dims)
        g = f.repartition(4, "k")
        return g.join(d, on="k", how="inner").collect()

    cpu = _cpu_collect(q)
    tpu, captured = _ici_collect(q)
    from spark_rapids_tpu.exec.tpu_join import TpuBroadcastHashJoinExec
    joins = []
    captured[-1].plan.foreach(
        lambda x: joins.append(x)
        if isinstance(x, TpuBroadcastHashJoinExec) else None)
    assert joins, "no TpuBroadcastHashJoinExec in plan"
    assert all(j.transport == "ici" for j in joins)
    assert any(j.metrics.extra.get("ici_broadcast_devices") == 8
               for j in joins), [j.metrics.extra for j in joins]
    assert_tables_equal(cpu, tpu, ignore_order=True)


def test_planned_distributed_total_sort():
    """Total ORDER BY across shards: range exchange on the sort keys
    (riding the ICI plane) + per-shard sorts; partition-ordered
    concatenation must equal the global sort."""
    from spark_rapids_tpu import col
    rng = np.random.default_rng(13)
    n = 500
    tbl = pa.table({
        "k": pa.array(rng.integers(-40, 40, n), type=pa.int64()),
        "i": pa.array(np.arange(n, dtype=np.int64)),  # total tiebreak
        "s": pa.array([f"s{i % 9}" if i % 11 else None
                       for i in range(n)]),
    })

    def q(s):
        df = s.create_dataframe(tbl, num_partitions=4)
        return df.sort(col("k").desc(), col("i")).collect()

    cpu = _cpu_collect(q)
    tpu, captured = _ici_collect(q)
    _assert_has_ici_exchange(captured)
    from spark_rapids_tpu.exec.tpu_sort import TpuSortExec
    from spark_rapids_tpu.shuffle.exchange import (RangePartitioning,
                                                   TpuShuffleExchangeExec)
    sorts, exchs = [], []
    captured[-1].plan.foreach(
        lambda x: sorts.append(x) if isinstance(x, TpuSortExec)
        else exchs.append(x) if isinstance(x, TpuShuffleExchangeExec)
        else None)
    assert sorts and all(x.partitionwise for x in sorts)
    assert any(isinstance(x.partitioning, RangePartitioning)
               for x in exchs)
    # exact order parity, not just same multiset
    assert_tables_equal(cpu, tpu, ignore_order=False)


@pytest.mark.slow
def test_planned_distributed_window_parity():
    """Window over PARTITION BY keys: hash exchange on the keys (ICI
    plane) + per-shard window evaluation."""
    from spark_rapids_tpu.api.window import Window
    rng = np.random.default_rng(14)
    n = 400
    tbl = pa.table({
        "g": pa.array(rng.integers(0, 12, n), type=pa.int32()),
        "o": pa.array(rng.permutation(n).astype(np.int64)),
        "v": pa.array(rng.integers(-30, 30, n), type=pa.int64()),
    })

    def q(s):
        df = s.create_dataframe(tbl, num_partitions=4)
        w = Window.partition_by("g").order_by("o")
        return df.select(
            "g", "o", "v",
            F.row_number().over(w).alias("rn"),
            F.sum("v").over(w).alias("rs"),
            F.lag("v").over(w).alias("lg")).collect()

    cpu = _cpu_collect(q)
    tpu, captured = _ici_collect(q)
    _assert_has_ici_exchange(captured)
    from spark_rapids_tpu.exec.tpu_window import TpuWindowExec
    wins = []
    captured[-1].plan.foreach(
        lambda x: wins.append(x) if isinstance(x, TpuWindowExec)
        else None)
    assert wins and all(x.partitionwise for x in wins)
    assert_tables_equal(cpu, tpu, ignore_order=True)


def test_planned_distributed_generate_parity():
    """Generate (explode) downstream of an ICI hash exchange: rows fan
    out per shard after the collective moves them."""
    rng = np.random.default_rng(21)
    n = 240
    tbl = pa.table({
        "k": pa.array(rng.integers(0, 10, n), type=pa.int64()),
        "arr": pa.array([[int(x) for x in
                          rng.integers(0, 50, rng.integers(0, 4))]
                         if i % 7 else None for i in range(n)],
                        type=pa.list_(pa.int64())),
    })

    def q(s):
        from spark_rapids_tpu import col
        df = s.create_dataframe(tbl, num_partitions=3)
        return (df.repartition(4, col("k"))
                .select("k", F.explode("arr").alias("x")).collect())

    cpu = _cpu_collect(q)
    tpu, captured = _ici_collect(q)
    _assert_has_ici_exchange(captured)
    from spark_rapids_tpu.exec.generate import TpuGenerateExec
    gens = []
    captured[-1].plan.foreach(
        lambda x: gens.append(x) if isinstance(x, TpuGenerateExec)
        else None)
    assert gens, captured[-1].plan.tree_string()
    assert_tables_equal(cpu, tpu, ignore_order=True)


def test_planned_distributed_expand_parity():
    """Expand (N projections per row) over ICI-exchanged shards,
    composed at the physical level (no frontend constructs Expand yet):
    exchange -> expand -> host, vs a pyarrow oracle."""
    import jax
    from jax.sharding import Mesh
    from spark_rapids_tpu.columnar.batch import to_arrow
    from spark_rapids_tpu.config import RapidsTpuConf
    from spark_rapids_tpu.exec.cpu import CpuScanExec
    from spark_rapids_tpu.exec.tpu_basic import (HostToDeviceExec,
                                                 TpuExpandExec)
    from spark_rapids_tpu.plan.logical import Field, Schema
    from spark_rapids_tpu.shuffle.exchange import (HashPartitioning,
                                                   TpuShuffleExchangeExec)

    rng = np.random.default_rng(22)
    n = 300
    tbl = pa.table({
        "k": pa.array(rng.integers(0, 8, n), type=pa.int64()),
        "v": pa.array(rng.integers(0, 100, n), type=pa.int64()),
    })
    conf = RapidsTpuConf({"spark.rapids.tpu.shuffle.transport": "ici"})
    h2d = HostToDeviceExec(CpuScanExec(tbl, num_partitions=3))
    names = ["k", "v"]
    dts = [f.dtype for f in h2d.schema.fields]

    def b(name):
        return ir.bind(ir.UnresolvedAttribute(name), names, dts,
                       [True, True])
    exch = TpuShuffleExchangeExec(h2d, HashPartitioning(4, [b("k")]),
                                  conf)
    lit0 = ir.Literal(0, dt.INT64)
    lit1 = ir.Literal(1, dt.INT64)
    out_schema = Schema([Field("k", dt.INT64, True),
                         Field("v", dt.INT64, True),
                         Field("gid", dt.INT64, False)])
    expand = TpuExpandExec(exch, [[b("k"), b("v"), lit0],
                                  [b("k"), b("v"), lit1]], out_schema)
    got = []
    for it in expand.execute():
        got.extend(to_arrow(x) for x in it)
    merged = pa.concat_tables([g for g in got if g.num_rows])
    assert merged.num_rows == 2 * n
    exp = pa.concat_tables([
        tbl.append_column("gid", pa.array(np.zeros(n, np.int64))),
        tbl.append_column("gid", pa.array(np.ones(n, np.int64)))])
    keys = [("k", "ascending"), ("v", "ascending"), ("gid", "ascending")]
    assert merged.sort_by(keys).equals(exp.sort_by(keys))


def test_planned_distributed_global_limit():
    """Global LIMIT over ICI-exchanged partitions (no sort): row count
    is exact and every row comes from the full result set."""
    rng = np.random.default_rng(23)
    n = 500
    tbl = pa.table({
        "k": pa.array(rng.integers(0, 37, n), type=pa.int64()),
        "v": pa.array(rng.integers(0, 1000, n), type=pa.int64()),
    })

    def q(s):
        df = s.create_dataframe(tbl, num_partitions=4)
        return (df.group_by("k").agg(F.sum("v").alias("sv"))
                .limit(11).collect())

    def full(s):
        df = s.create_dataframe(tbl, num_partitions=4)
        return df.group_by("k").agg(F.sum("v").alias("sv")).collect()

    tpu, captured = _ici_collect(q)
    _assert_has_ici_exchange(captured)
    assert tpu.num_rows == 11
    allowed = set(zip(_cpu_collect(full).column("k").to_pylist(),
                      _cpu_collect(full).column("sv").to_pylist()))
    got = set(zip(tpu.column("k").to_pylist(),
                  tpu.column("sv").to_pylist()))
    assert got <= allowed and len(got) == 11


@pytest.mark.slow
def test_planned_distributed_aqe_skew_split():
    """AQE skew-split over the ICI plane: the adaptive join reader
    splits the hot partition into per-map slices while the other side
    replicates, with full parity."""
    from spark_rapids_tpu.exec.adaptive import (SkewSplitSpec,
                                                TpuAdaptiveJoinReaderExec)
    rng = np.random.default_rng(24)
    n = 20_000
    keys = np.where(rng.random(n) < 0.6, 7,
                    rng.integers(0, 300, n)).astype(np.int64)
    fact = pa.table({"k": keys,
                     "v": pa.array(rng.integers(0, 100, n))})
    dim = pa.table({"k2": np.arange(300, dtype=np.int64),
                    "w": pa.array(rng.integers(0, 9, 300))})
    conf = {
        "spark.rapids.tpu.sql.autoBroadcastJoinThreshold": -1,
        "spark.rapids.tpu.sql.shuffle.partitions": 8,
        "spark.rapids.tpu.sql.adaptive.advisoryPartitionSizeInBytes":
            64 << 10,
        "spark.rapids.tpu.sql.adaptive.skewJoin."
        "skewedPartitionThresholdInBytes": 32 << 10,
    }

    def q(s):
        from spark_rapids_tpu import col
        f = s.create_dataframe(fact, num_partitions=4)
        d = s.create_dataframe(dim)
        return (f.join(d, col("k") == col("k2"))
                .group_by("k").agg(F.sum("v").alias("sv"),
                                   F.count("*").alias("c")).collect())

    cpu = _cpu_collect(q)
    tpu, captured = _ici_collect(q, conf)
    _assert_has_ici_exchange(captured)
    readers = []
    captured[-1].plan.foreach(
        lambda x: readers.append(x)
        if isinstance(x, TpuAdaptiveJoinReaderExec) else None)
    assert readers, captured[-1].plan.tree_string()
    specs = readers[0].state.specs
    assert specs and any(isinstance(s[0], SkewSplitSpec) for s in specs), \
        specs
    assert_tables_equal(cpu, tpu, ignore_order=True)


def test_planned_distributed_sort_then_limit():
    """ORDER BY + LIMIT over the distributed sort keeps global order
    (limit drains range partitions in partition order)."""
    from spark_rapids_tpu import col
    rng = np.random.default_rng(15)
    tbl = pa.table({
        "k": pa.array(rng.permutation(300).astype(np.int64)),
    })

    def q(s):
        df = s.create_dataframe(tbl, num_partitions=3)
        return df.sort(col("k")).limit(17).collect()

    cpu = _cpu_collect(q)
    tpu, _ = _ici_collect(q)
    assert_tables_equal(cpu, tpu, ignore_order=False)
    assert tpu.column("k").to_pylist() == list(range(17))


# ---------------------------------------------------------------------------
# the exchange in place: rows that already lie on the mesh
# ---------------------------------------------------------------------------

def _mesh_of(n, monkeypatch):
    mesh = Mesh(np.array(jax.devices()[:n]), ("shuffle",))
    monkeypatch.setattr(ici, "_DEFAULT_MESH", mesh)
    return list(mesh.devices.flat)


def _placed_inputs(devices, rows, targets_of, strings=False, empty=()):
    """One batch a device (None on the ``empty`` ones), committed there,
    with its target partitions."""
    import jax.numpy as jnp
    batches, targets, sent = [], [], []
    for d, dev in enumerate(devices):
        if d in empty:
            batches.append(None)
            targets.append(None)
            continue
        k = np.arange(rows, dtype=np.int64) + 1_000_000 * d
        cols = {"k": pa.array(k), "v": pa.array(k * 0.5)}
        if strings:
            cols["s"] = pa.array([f"row-{x}-" + "x" * (x % 7) for x in k])
        b = jax.device_put(from_arrow(pa.table(cols), min_bucket=16), dev)
        t = np.full(b.capacity, len(devices), np.int32)
        t[:rows] = targets_of(d, np.arange(rows))
        batches.append(b)
        targets.append(jax.device_put(jnp.asarray(t), dev))
        sent.append((k, t[:rows]))
    return batches, targets, sent


@pytest.mark.parametrize("case", ["uniform", "skewed", "one-chip-empty",
                                  "strings", "all-to-one", "between-rungs"])
def test_exchange_placed_sizes_buckets_by_the_counted_rows(case,
                                                           monkeypatch):
    from spark_rapids_tpu.columnar.batch import bucket_rows, to_arrow
    from spark_rapids_tpu.exec import placement
    from spark_rapids_tpu.obs import registry
    n_dev, rows = 4, 1000
    devices = _mesh_of(n_dev, monkeypatch)
    targets_of = {
        "uniform": lambda d, i: i % n_dev,
        # chip 0 receives 70% of every sender's rows
        "skewed": lambda d, i: np.where(i % 10 < 7, 0, 1 + i % 3),
        "one-chip-empty": lambda d, i: (i + d) % n_dev,
        "strings": lambda d, i: (i * 7 + d) % n_dev,
        "all-to-one": lambda d, i: np.full(len(i), 2),
        # 300 rows a peer at most: between the ladder's 256 and 1024
        "between-rungs": lambda d, i: np.minimum(i // 300, n_dev - 1),
    }[case]
    batches, targets, sent = _placed_inputs(
        devices, rows, targets_of, strings=case == "strings",
        empty=(3,) if case == "one-chip-empty" else ())
    view = registry.get_registry().view()
    out, counted = ici.exchange_placed(batches, targets, 16)
    counts = counted["rows"]
    assert counts.shape == (n_dev, n_dev)
    received = counts.sum(axis=0)
    assert received.sum() == sum(len(k) for k, _ in sent)
    # the buckets have the power of two of the fullest one, not the
    # sender's capacity nor the ladder's rung above it ...
    fullest = int(counts.max())
    assert counted["bucket_rows"] == min(
        1 << (max(fullest, 16) - 1).bit_length(), 1024)
    assert fullest <= counted["bucket_rows"] < 2 * max(fullest, 16)
    assert view.delta()["counters"]["exchange.ici.sendSlots"] == \
        n_dev * n_dev * counted["bucket_rows"]
    # ... and a receiver holds its rows at their own tier
    for d, b in enumerate(out):
        if not received[d]:
            assert b is None
            continue
        assert int(b.num_rows) == received[d]
        assert b.capacity == bucket_rows(int(received[d]), 16)
        assert placement.device_of(b) == devices[d]
        got = to_arrow(b)
        assert set(got.column("__part__").to_pylist()) == {d}
        # a stable partition: sender by sender, each in its own order
        want = np.concatenate([k[t == d] for k, t in sent])
        assert np.array_equal(got.column("k").to_numpy(), want)
        assert np.array_equal(got.column("v").to_numpy(), want * 0.5)
        if case == "strings":
            by_k = dict(zip(got.column("k").to_pylist(),
                            got.column("s").to_pylist()))
            assert all(s == f"row-{k}-" + "x" * (k % 7)
                       for k, s in by_k.items())
    if case == "skewed":
        assert received[0] == 0.7 * n_dev * rows
        assert out[0].capacity == 4096 and out[1].capacity == 1024
    if case == "uniform":
        # 250 rows a peer: 1024 slots a receiver where the sender's
        # capacity would have made it 4096
        assert counted["bucket_rows"] == 256
        assert counted["capacities"] == [1024] * n_dev
    if case == "between-rungs":
        # 300 rows a peer: 512 slots a bucket, where the ladder's rung
        # above 300 is 1024
        assert fullest == 300 and counted["bucket_rows"] == 512
        assert counted["capacities"] == [4096, 4096, 4096, 1024]


def test_bucketize_with_a_counted_bucket_capacity():
    import jax.numpy as jnp
    n = 500
    b = from_arrow(pa.table({"k": pa.array(np.arange(n, dtype=np.int64))}),
                   min_bucket=16)
    target = jnp.asarray(np.arange(b.capacity) % 4, dtype=jnp.int32)
    wide, counts = ici.bucketize(b, target, 4)
    narrow, counts2 = ici.bucketize(b, target, 4, bucket_cap=128)
    assert wide[0].data.shape == (4, b.capacity)
    assert narrow[0].data.shape == (4, 128)
    assert np.array_equal(np.asarray(counts), np.asarray(counts2))
    for p in range(4):
        c = int(counts[p])
        assert np.array_equal(np.asarray(narrow[0].data[p, :c]),
                              np.asarray(wide[0].data[p, :c]))
        assert not np.asarray(narrow[0].validity[p, c:]).any()


def test_exchange_step_holds_no_sort():
    """The step places rows by a prefix sum a target and compacts by
    copies of runs: its lowered text holds no sort and no scatter but
    the one of the row index."""
    mesh = Mesh(np.array(jax.devices()[:4]), ("shuffle",))
    batch = from_arrow(pa.table({
        "k": pa.array(np.arange(64) % 4, pa.int32()),
        "s": pa.array([f"x{i}" for i in range(64)]),
        "v": pa.array(np.arange(64, dtype=np.float64))}))
    aug = ici.with_capacity(batch, 64)
    leaves, counts = ici.shard_batch(aug, mesh, "shuffle")
    step = ici.make_exchange_step(mesh, "shuffle", aug.names, aug.dtypes,
                                  ("test_no_sort", 16), 16, 64)
    text = step.lower(leaves, counts).as_text()
    assert "module @jit_ici_exchange " in text
    assert "stablehlo.sort" not in text
    assert text.count('"stablehlo.scatter"') == 1


@pytest.mark.parametrize("counts,out_cap", [
    ((5, 0, 16, 3), 64), ((0, 0, 0, 0), 16), ((16, 16, 16, 16), 64),
    ((0, 7, 0, 0), 16), ((2, 9, 0, 1), 32)],
    ids=["an-empty-block", "nothing", "full", "one-block", "small-out"])
def test_reassemble_puts_the_blocks_rows_in_front_unchanged(counts,
                                                            out_cap):
    """Nulls, strings and empty blocks come through the receiver's
    compaction as the stable compaction of each block's first
    ``counts[b]`` slots gives them, slots past the rows cleared."""
    import jax.numpy as jnp
    from spark_rapids_tpu.columnar.batch import DeviceColumn
    n, bcap, width = 4, 16, 5
    rng = np.random.default_rng(sum(counts) + out_cap)
    data = rng.integers(-99, 99, (n, bcap)).astype(np.int64)
    validity = rng.random((n, bcap)) < 0.8
    sdata = rng.integers(1, 255, (n, bcap, width)).astype(np.uint8)
    slen = rng.integers(0, width + 1, (n, bcap)).astype(np.int32)
    svalid = rng.random((n, bcap)) < 0.7
    stacked = [DeviceColumn(dt.INT64, jnp.asarray(data),
                            jnp.asarray(validity)),
               DeviceColumn(dt.STRING, jnp.asarray(sdata),
                            jnp.asarray(svalid), jnp.asarray(slen))]
    got = jax.jit(lambda cols, c: ici.reassemble(["k", "s"], cols, c,
                                                 out_cap))(
        stacked, jnp.asarray(counts, jnp.int32))
    total = sum(counts)
    assert int(got.num_rows) == total and got.capacity == out_cap
    live = np.concatenate([np.arange(b * bcap, b * bcap + c)
                           for b, c in enumerate(counts)]).astype(int)

    def want(a, fill=0):
        flat = a.reshape((n * bcap,) + a.shape[2:])
        out = np.full((out_cap,) + a.shape[2:], fill, a.dtype)
        out[:total] = flat[live]
        return out
    k, s = got.columns
    np.testing.assert_array_equal(np.asarray(k.data), want(data))
    np.testing.assert_array_equal(np.asarray(k.validity),
                                  want(validity, False))
    np.testing.assert_array_equal(np.asarray(s.data), want(sdata))
    np.testing.assert_array_equal(np.asarray(s.lengths), want(slen))
    np.testing.assert_array_equal(np.asarray(s.validity),
                                  want(svalid, False))


def test_exchange_placed_keeps_nulls_and_strings_with_an_empty_chip(
        monkeypatch):
    """Null keys, null strings and a chip that holds nothing: every
    receiver holds its senders' rows in their order, nulls where they
    were."""
    import jax.numpy as jnp
    from spark_rapids_tpu.columnar.batch import to_arrow
    n_dev = 4
    devices = _mesh_of(n_dev, monkeypatch)
    batches, targets, sent = [], [], []
    for d, dev in enumerate(devices):
        if d == 1:
            batches.append(None)
            targets.append(None)
            continue
        rows = 100 + 37 * d
        k = np.arange(rows) + 1000 * d
        t = pa.table({
            "k": pa.array(k, mask=k % 5 == 0),
            "s": pa.array([None if x % 3 == 0 else "s" * (x % 9) + str(x)
                           for x in k])})
        b = jax.device_put(from_arrow(t, min_bucket=16), dev)
        tg = np.zeros(b.capacity, np.int32)
        tg[:rows] = (k * 7) % n_dev
        batches.append(b)
        targets.append(jax.device_put(jnp.asarray(tg), dev))
        sent.append((t, tg[:rows]))
    out, counted = ici.exchange_placed(batches, targets, 16)
    for d, b in enumerate(out):
        want = pa.concat_tables([t.filter(pa.array(tg == d))
                                 for t, tg in sent])
        got = to_arrow(b)
        assert got.column("k").to_pylist() == want.column("k").to_pylist()
        assert got.column("s").to_pylist() == want.column("s").to_pylist()


def test_exchange_fill_pct_is_rows_over_send_slots_inside_the_window():
    """The benchmark's reader (benchmark/metrics/exchange_fill_pct.py):
    100 x rows in / send slots of the window's counters; nothing where
    the program has neither counter or the window exchanged nothing."""
    import importlib.util
    import os
    from spark_rapids_tpu.obs import registry
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "metrics",
        "exchange_fill_pct.py")
    spec = importlib.util.spec_from_file_location("exchange_fill_pct", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    registry.get_registry().inc("exchange.ici.sendSlots", 0)
    assert mod.read({"counters": {}}) is None
    assert mod.read({"counters": {
        "exchange.ici.rowsIn": 5_500_000,
        "exchange.ici.sendSlots": 16 * 524_288}}) == \
        pytest.approx(65.565, abs=1e-3)


@pytest.mark.parametrize("n_parts", [2, 4, 7])
def test_range_bounds_are_the_samples_weighted_quantiles(n_parts):
    from spark_rapids_tpu.shuffle.exchange import _range_bounds
    rng = np.random.default_rng(n_parts)
    # two words a key; a chip of 3000 rows and one of 1000, 64 samples each
    samples = [(3000, np.stack([np.zeros(64, np.uint64),
                                np.sort(rng.integers(0, 1000, 64)
                                        ).astype(np.uint64)])),
               (1000, np.stack([np.zeros(64, np.uint64),
                                np.sort(rng.integers(1000, 2000, 64)
                                        ).astype(np.uint64)]))]
    bounds = _range_bounds(samples, n_parts)
    assert bounds.shape == (2, n_parts - 1) and bounds.dtype == np.uint64
    keys = bounds[1].astype(np.int64)
    assert (np.diff(keys) >= 0).all()
    # three quarters of the rows lie under 1000: so do that share of
    # the bounds
    under = (keys < 1000).sum()
    assert abs(under - 0.75 * (n_parts - 1)) <= 1


def test_a_host_built_frame_still_exchanges_from_one_device(monkeypatch):
    """Everything on one device of a mesh of several: the same exchange,
    the other chips sending nothing; nothing moves before it."""
    from spark_rapids_tpu import TpuSparkSession
    from spark_rapids_tpu.obs import registry
    _mesh_of(4, monkeypatch)
    spark = TpuSparkSession({
        "spark.rapids.tpu.shuffle.transport": "ici",
        "spark.rapids.tpu.sql.agg.exchange.enabled": True,
        "spark.rapids.tpu.sql.shuffle.partitions": 4})
    try:
        rng = np.random.default_rng(3)
        t = pa.table({"k": pa.array(rng.integers(0, 50, 4000)),
                      "v": pa.array(rng.integers(0, 9, 4000))})
        view = registry.get_registry().view()
        got = spark.create_dataframe(t).group_by("k").agg(
            __import__("spark_rapids_tpu").functions.sum("v").alias("s")
        ).collect().sort_by("k")
        moved = view.delta()["counters"]
        want = t.group_by("k").aggregate([("v", "sum")]).sort_by("k")
        assert got.column("s").to_pylist() == \
            want.column("v_sum").to_pylist()
        assert moved["exchange.ici.exchanges"] >= 1
        assert moved.get("exchange.ici.movedBatches", 0) == 0
        assert moved["kernel.dispatches.exch_counts"] == \
            4 * moved["exchange.ici.exchanges"]
    finally:
        from spark_rapids_tpu.mem import device as devmgr
        devmgr.initialize(2)


@pytest.mark.parametrize("rows,cap", [(5, 16), (16, 16), (17, 64),
                                      (100, 128), (0, 16)])
def test_with_capacity_shrinks_by_a_prefix_slice_to_the_gathers_batch(
        rows, cap):
    """A receiver cut to the tier of its rows (exchange_placed) is the
    batch ``slice_span``'s gather gives, column for column, and its
    program holds no gather."""
    import jax.numpy as jnp
    from spark_rapids_tpu.shuffle.exchange import prefix_span, slice_span
    rng = np.random.default_rng(rows)
    t = pa.table({
        "k": pa.array(rng.integers(0, 9, rows).astype(np.int32),
                      mask=rng.random(rows) < 0.2),
        "v": pa.array(rng.random(rows)),
        "s": pa.array([None if i % 5 == 0 else "x" * (i % 7)
                       for i in range(rows)], type=pa.string()),
        "b": pa.array(rng.random(rows) < 0.5),
    })
    big = ici.with_capacity(from_arrow(t), 256)        # a grow: gathers
    assert big.capacity == 256 and int(big.num_rows) == rows
    got = ici.with_capacity(big, cap)
    want = slice_span(big, jnp.int32(0), jnp.int32(rows), cap)
    assert got.capacity == cap and int(got.num_rows) == rows
    for g, w in zip(jax.tree_util.tree_leaves(got.columns),
                    jax.tree_util.tree_leaves(want.columns)):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    text = jax.jit(lambda b, c: prefix_span(b, c, cap)).lower(
        big, jnp.int32(rows)).as_text()
    assert "stablehlo.gather" not in text
    assert "stablehlo.gather" in jax.jit(
        lambda b, c: slice_span(b, jnp.int32(0), c, cap)).lower(
            big, jnp.int32(rows)).as_text()

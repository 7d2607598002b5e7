"""The aggregate sizes its partials by their group count, not by their
input's capacity (exec/tpu_aggregate._shrink_partials): one read-back of
the counts at the pipeline breaker, a head slice to the count's tier,
and everything after the update runs at that size."""

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu import TpuSparkSession, col, functions as F
from spark_rapids_tpu.columnar.batch import bucket_rows
from spark_rapids_tpu.exec import kernel_cache as kc
from spark_rapids_tpu.exec import tpu_aggregate as agg
from spark_rapids_tpu.obs import registry as obsreg
from tests.parity import assert_tables_equal, with_cpu_session

_CONF = {"spark.rapids.tpu.sql.variableFloatAgg.enabled": True}
_MOVED = ("device.reads.agg.countWait", "agg.partials.shrunk",
          "agg.partials.rowsCut", "kernel.cache.misses",
          "kernel.cache.compiles", "kernel.dispatches.agg_shrink")


def _table(n, n_groups, seed=5, null_keys=False):
    rng = np.random.default_rng(seed)
    k = rng.integers(0, n_groups, n)
    k[:n_groups] = np.arange(n_groups)      # every group is there
    keys = pa.array(k, type=pa.int64())
    if null_keys:
        keys = pa.array([None if i % 7 == 0 else int(x)
                         for i, x in enumerate(k)], type=pa.int64())
    return pa.table({
        "k": keys,
        "v": pa.array(rng.integers(-50, 50, n), type=pa.int64()),
        "w": pa.array(rng.normal(size=n))})


def _grouped(t, parts, cond=None):
    def q(s):
        df = s.create_dataframe(t, num_partitions=parts)
        if cond is not None:
            df = df.filter(cond)
        return df.group_by("k").agg(F.count("*").alias("c"),
                                    F.sum("v").alias("sv"),
                                    F.max("w").alias("mw"))
    return q


def _global(t, parts):
    def q(s):
        return s.create_dataframe(t, num_partitions=parts).agg(
            F.count("*").alias("c"), F.sum("v").alias("sv"),
            F.max("w").alias("mw"))
    return q


class _Seen:
    """What the aggregate handed on: the capacities that went into and
    came out of its concatenation, and of the batches it emitted."""

    def __init__(self, monkeypatch):
        self.concat_in, self.concat_out, self.emitted = [], [], []
        real_concat, real_execute = (agg.concat_batches,
                                     agg.TpuHashAggregateExec.execute)

        def concat(batches, *a, **kw):
            out = real_concat(batches, *a, **kw)
            self.concat_in.append([b.capacity for b in batches])
            self.concat_out.append(out.capacity)
            return out

        def execute(exec_):
            def watch(it):
                for b in it:
                    self.emitted.append(b.capacity)
                    yield b
            return [watch(it) for it in real_execute(exec_)]

        monkeypatch.setattr(agg, "concat_batches", concat)
        monkeypatch.setattr(agg.TpuHashAggregateExec, "execute", execute)


def _run(q, conf=None):
    """(answer, the counters that moved) of one run on the TPU engine."""
    view = obsreg.get_registry().view()
    got = q(TpuSparkSession({**_CONF, **(conf or {})})).collect()
    d = view.delta()["counters"]
    return got, {k: d.get(k, 0) for k in _MOVED}


def _check(q, got):
    assert_tables_equal(with_cpu_session(lambda s: q(s).collect()), got,
                        ignore_order=True)


def test_few_groups_cut_every_partial_to_its_tier(monkeypatch):
    """Four batches of 4096-row capacity with five groups each: the
    merge's input is the bucketed sum of four 16-row tiers, and the
    exec's output keeps that capacity."""
    seen = _Seen(monkeypatch)
    q = _grouped(_table(4 * 3000, 5), 4)
    got, moved = _run(q)
    _check(q, got)
    assert seen.concat_in == [[16] * 4], seen.concat_in
    assert seen.concat_out == [bucket_rows(4 * 16)] == [64]
    assert seen.emitted == [64]
    assert moved["device.reads.agg.countWait"] == 1
    assert moved["agg.partials.shrunk"] == 4
    assert moved["agg.partials.rowsCut"] == 4 * (4096 - 16)


def test_groups_that_fill_their_tier_are_left_alone(monkeypatch):
    """The bypass: where a partial's groups fill its capacity's tier
    nothing is sliced; the one read is the only thing that happened, and
    the concatenation is keyed by the capacities it always had."""
    seen = _Seen(monkeypatch)
    # 4 x 300 rows (capacity 1024), 1100 keys: each batch holds close to
    # 300 distinct ones, above the 256-row tier
    q = _grouped(_table(4 * 300, 1100, seed=9), 4)
    got, moved = _run(q)
    _check(q, got)
    assert seen.concat_in == [[1024] * 4], seen.concat_in
    assert seen.concat_out == [4096]
    assert moved["device.reads.agg.countWait"] == 1
    assert moved["agg.partials.shrunk"] == 0
    assert moved["agg.partials.rowsCut"] == 0
    assert moved["kernel.dispatches.agg_shrink"] == 0


def test_single_partial_is_cut_too(monkeypatch):
    """One input batch takes the shortcut round the merge; its partial
    is cut all the same, or a one-file table keeps its capacity's tail."""
    seen = _Seen(monkeypatch)
    q = _grouped(_table(3000, 5), 1)
    got, moved = _run(q)
    _check(q, got)
    assert seen.concat_in == []
    assert seen.emitted == [16]
    assert (moved["device.reads.agg.countWait"], moved["agg.partials.shrunk"]) \
        == (1, 1)


@pytest.mark.parametrize("case", ["global", "global_empty", "empty",
                                  "null_keys", "filter_keeps_none"])
def test_edges_answer_like_the_cpu(monkeypatch, case):
    seen = _Seen(monkeypatch)
    t = _table(4 * 300, 5, null_keys=(case == "null_keys"))
    q = {"global": _global(t, 4),
         "global_empty": _global(t.slice(0, 0), 1),
         "empty": _grouped(t.slice(0, 0), 1),
         "null_keys": _grouped(t, 4),
         "filter_keeps_none": _grouped(t, 4, col("v") > 1000)}[case]
    got, moved = _run(q)
    _check(q, got)
    if case == "global":
        # one row a partial by construction: cut without a read
        assert seen.concat_in == [[16] * 4]
        assert moved["device.reads.agg.countWait"] == 0
        assert moved["agg.partials.shrunk"] == 4
    if case in ("null_keys", "filter_keeps_none"):
        assert seen.concat_in == [[16] * 4]
        assert seen.emitted == [64]
        assert moved["device.reads.agg.countWait"] == 1
    if case == "filter_keeps_none":
        assert got.num_rows == 0


@pytest.mark.parametrize("parts", [2, 8])
def test_one_count_read_an_aggregate(parts):
    """However many batches: one read.  And a second run whose group
    count differs inside the tier builds and compiles nothing."""
    q = _grouped(_table(parts * 300, 5), parts)
    got, moved = _run(q)
    _check(q, got)
    assert moved["device.reads.agg.countWait"] == 1
    assert moved["agg.partials.shrunk"] == parts
    q2 = _grouped(_table(parts * 300, 11, seed=6), parts)
    got2, moved2 = _run(q2)
    _check(q2, got2)
    assert moved2["device.reads.agg.countWait"] == 1
    assert moved2["kernel.cache.misses"] == 0, moved2
    assert moved2["kernel.cache.compiles"] == 0, moved2


def test_per_partition_reads_once_a_partition():
    """Over a hash exchange on the keys every partition aggregates on
    its own: one read each, and the same answer."""
    conf = {"spark.rapids.tpu.sql.agg.exchange.enabled": True,
            "spark.rapids.tpu.sql.shuffle.partitions": 3}
    q = _grouped(_table(4 * 300, 40), 4)
    got, moved = _run(q, conf)
    _check(q, got)
    assert got.num_rows == 40
    assert moved["device.reads.agg.countWait"] == 3


def test_retained_state_merges_with_a_cut_delta(monkeypatch):
    """The incremental path: a host table of merged partials from an
    earlier run goes first (its count is host-known, nothing to read for
    it), the delta's partials are cut, the sink gets the merged state,
    and retained + delta equals one run over everything."""
    from spark_rapids_tpu.exec import incremental as inc
    from spark_rapids_tpu.plan import logical as lp

    def stamped(s, t, retained, sink):
        df = _grouped(t, 2)(s)
        node = df.plan
        while not isinstance(node, lp.Aggregate):
            node = node.children[0]
        node._incremental = {"sink": sink, "retained": retained,
                             "delta": retained is not None}
        return df

    old, new = _table(600, 5, seed=1), _table(600, 7, seed=2)
    first = inc.PartialSink()
    stamped(TpuSparkSession(_CONF), old, None, first).collect()
    assert first.table is not None and first.table.num_rows == 5

    seen = _Seen(monkeypatch)
    second = inc.PartialSink()
    view = obsreg.get_registry().view()
    got = stamped(TpuSparkSession(_CONF), new, first.table,
                  second).collect()
    moved = view.delta()["counters"]
    whole = _grouped(pa.concat_tables([old, new]), 2)
    _check(whole, got)
    assert seen.concat_in == [[16, 16, 16]], seen.concat_in
    assert moved.get("device.reads.agg.countWait") == 1
    assert moved.get("agg.partials.shrunk") == 2
    assert second.table.num_rows == 7


def test_the_read_has_a_span_in_the_querys_tree():
    """Tracing on: ``agg.countWait`` (the wait for the counts) lies in
    ``agg.shrink`` (the wait and the cuts' dispatches), inside the
    query's tree, so a device trace can lay the idle gap at the read to
    it; the cuts' programs are named ``jit_agg_shrink``."""
    from spark_rapids_tpu.obs import trace
    q = _grouped(_table(4 * 300, 5), 4)
    s = TpuSparkSession({**_CONF,
                         "spark.rapids.tpu.obs.trace.enabled": True})
    try:
        q(s).collect()
        spans = s.last_query_profile().spans
    finally:
        trace.configure(False)
        trace.clear()
    by_id = {sp["id"]: sp for sp in spans}
    (wait,) = [sp for sp in spans if sp["name"] == "agg.countWait"]
    step = by_id[wait["parent"]]
    assert step["name"] == "agg.shrink"
    assert step["ts_ns"] <= wait["ts_ns"] and \
        wait["ts_ns"] + wait["dur_ns"] <= step["ts_ns"] + step["dur_ns"]
    assert wait["query"] == step["query"] is not None
    above = step
    while above["parent"]:
        above = by_id[above["parent"]]
    assert above["name"] == "query"
    assert kc.program_name("agg_shrink") == "jit_agg_shrink"

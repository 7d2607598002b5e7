"""In-query reuse: aggregate subplans of one query that are equal by
``plan/digest``'s node hash are computed once and served to every
place the text wrote them (plan/optimizer.mark_equal_aggregates,
plan/overrides._tie_reused_subplans, exec/reuse.TpuReusedSubplanExec;
docs/work_sharing.md).  q65 writes its (store, item) block twice: once
under ``sb``'s average, the build side of a join, and once as ``sc``,
that join's stream side."""

import pickle
import threading

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as papq
import pytest

from spark_rapids_tpu import TpuSparkSession, col, functions as F
from spark_rapids_tpu.columnar.batch import from_arrow, to_arrow
from spark_rapids_tpu.exec.base import (TpuExec, collect_plan_metrics,
                                        merge_plan_metrics)
from spark_rapids_tpu.exec.reuse import TpuReusedSubplanExec
from spark_rapids_tpu.exec.tpu_aggregate import TpuHashAggregateExec
from spark_rapids_tpu.mem import spill
from spark_rapids_tpu.obs import registry, trace as obstrace
from spark_rapids_tpu.plan import logical as lp
from spark_rapids_tpu.sched import cancel as sched_cancel
from tests.parity import collect_plans

_ROWS = 3000          # a file, and a scan batch
_FILES = 3
_CONF = {"spark.rapids.tpu.sql.variableFloatAgg.enabled": True,
         "spark.rapids.tpu.sql.reader.batchSizeRows": _ROWS}


def _write(root, seed):
    rng = np.random.default_rng(seed)
    root.mkdir()
    frames = []
    for f in range(_FILES):
        t = pa.table({
            "k": pa.array([None if i % 17 == 0 else int(v) for i, v in
                           enumerate(rng.integers(0, 40, _ROWS))],
                          pa.int32()),
            "j": pa.array(rng.integers(0, 9, _ROWS), pa.int32()),
            "m": pa.array(rng.integers(1170, 1190, _ROWS), pa.int32()),
            "v": rng.uniform(0, 100, _ROWS)})
        frames.append(t.to_pandas())
        papq.write_table(t, str(root / f"part-{f}.parquet"))
    return pd.concat(frames, ignore_index=True)


@pytest.fixture(scope="module")
def data(tmp_path_factory):
    root = tmp_path_factory.mktemp("reuse")
    return {"t": (str(root / "t"), _write(root / "t", 4)),
            "u": (str(root / "u"), _write(root / "u", 5))}


def _session(data, **conf):
    s = TpuSparkSession({**_CONF, **conf})
    for name, (path, _) in data.items():
        s.register_view(name, s.read.parquet(path))
    return s


def _run(s, df, limit_s=180):
    """Collect on a thread with a time limit (a reader that waits for
    a producer that waits for the reader would not come back); returns
    the answer, the executed plan's nodes and the counters moved."""
    captured = collect_plans(s)
    view = registry.get_registry().view()
    out = {}

    def run():
        try:
            out["got"] = df.collect()
        except BaseException as e:       # noqa: BLE001 - shown below
            out["error"] = e
    th = threading.Thread(target=run, daemon=True)
    th.start()
    th.join(limit_s)
    assert not th.is_alive(), "the query did not finish"
    if "error" in out:
        raise out["error"]
    nodes = []
    captured[-1].plan.foreach(nodes.append)
    return out["got"], nodes, view.delta()["counters"]


def _of(nodes, cls):
    return [n for n in nodes if isinstance(n, cls)]


# -- q65's shape -----------------------------------------------------------

_BLOCK = ("(select k, j, {fn}(v) as revenue from {table} "
          "where m between {lo} and {lo}+11 group by k, j)")


def _q65(second=None):
    first = _BLOCK.format(**{"fn": "sum", "table": "t", "lo": 1176})
    second = _BLOCK.format(**{"fn": "sum", "table": "t", "lo": 1176,
                              **(second or {})})
    return (f"select sc.k, sc.j, sc.revenue, sb.ave from "
            f"(select k, avg(revenue) as ave from {first} sa "
            f"group by k) sb, {second} sc "
            f"where sb.k = sc.k and sc.revenue <= 0.9 * sb.ave "
            f"order by sc.k, sc.j")


def _q65_want(t, u=None, fn="sum", lo=1176):
    def block(frame, fn, lo):
        f = frame[(frame.m >= lo) & (frame.m <= lo + 11)]
        return f.groupby(["k", "j"], dropna=False).v.agg(fn) \
            .reset_index(name="revenue")
    sa = block(t, "sum", 1176)
    sb = sa.groupby("k", dropna=False).revenue.mean() \
        .reset_index(name="ave").dropna(subset=["k"])
    sc = block(t if u is None else u, fn, lo).dropna(subset=["k"])
    j = sc.merge(sb, on="k")
    j = j[j.revenue <= 0.9 * j.ave].sort_values(["k", "j"])
    return j[["k", "j", "revenue", "ave"]].reset_index(drop=True)


def _same(got, want):
    got = got.to_pandas()
    assert list(got.columns) == list(want.columns)
    assert len(got) == len(want) and len(got) > 0
    for c in want.columns:
        np.testing.assert_allclose(got[c].to_numpy(dtype=float),
                                   want[c].to_numpy(dtype=float),
                                   rtol=1e-9)


@pytest.fixture(scope="module")
def q65_runs(data):
    """The twin text, and a text whose second block takes ``max``."""
    s = _session(data)
    return {"twin": _run(s, s.sql(_q65())),
            "max": _run(s, s.sql(_q65({"fn": "max"})))}


def test_q65_shape_answers_as_pandas_does(data, q65_runs):
    _same(q65_runs["twin"][0], _q65_want(data["t"][1]))
    _same(q65_runs["max"][0], _q65_want(data["t"][1], fn="max"))


def test_q65_shape_plans_one_aggregate_over_the_fact_scan(q65_runs):
    _, nodes, moved = q65_runs["twin"]
    assert moved.get("plan.reuse.subplans") == 1
    assert moved.get("exec.reuse.served") == 1
    # sb's average and the block, once
    assert len(_of(nodes, TpuHashAggregateExec)) == 2
    owner, ref = _of(nodes, TpuReusedSubplanExec)
    assert owner.children and not ref.children and ref._source is owner
    scans = [n for n in nodes if type(n).__name__ == "TpuParquetScanExec"]
    assert len(scans) == 1
    assert scans[0].metrics.num_output_batches == _FILES


def test_q65_shape_runs_the_block_once(q65_runs):
    """Against the text whose second block differs: one scan batch
    less is a decode and an update less, for every batch, and a
    merge."""
    _, nodes, moved = q65_runs["twin"]
    _, other_nodes, other = q65_runs["max"]
    assert other.get("plan.reuse.subplans", 0) == 0
    assert not _of(other_nodes, TpuReusedSubplanExec)
    assert len(_of(other_nodes, TpuHashAggregateExec)) == 3

    def updates(ns):
        return sum(n.metrics.num_output_batches for n in ns
                   if type(n).__name__ == "TpuParquetScanExec")
    assert updates(nodes) == _FILES and updates(other_nodes) == 2 * _FILES
    assert moved["kernel.dispatches"] <= \
        other["kernel.dispatches"] - 2 * _FILES
    assert moved["agg.merge.rowsIn"] < other["agg.merge.rowsIn"]


# -- a WITH name referenced twice; three occurrences ------------------------

_WITH = ("with sa as (select k, j, sum(v) as revenue from t group by k, j) "
         "select x.k, x.j, x.revenue, y.revenue as yrev from sa x, sa y "
         "where x.k = y.k and x.j = y.j order by x.k, x.j")

_THREE = ("with sa as (select k, j, sum(v) as revenue from t group by k, j) "
          "select x.k, x.j, x.revenue, y.revenue as yrev, z.revenue as zrev "
          "from sa x, sa y, sa z where x.k = y.k and x.j = y.j "
          "and x.k = z.k and x.j = z.j order by x.k, x.j")


@pytest.mark.parametrize("text,served", [(_WITH, 1), (_THREE, 2)],
                         ids=["with-twice", "three-occurrences"])
def test_a_shared_name_is_computed_once(data, text, served):
    s = _session(data)
    got, nodes, moved = _run(s, s.sql(text))
    assert moved.get("plan.reuse.subplans") == served
    assert moved.get("exec.reuse.served") == served
    assert len(_of(nodes, TpuHashAggregateExec)) == 1
    assert len(_of(nodes, TpuReusedSubplanExec)) == served + 1
    want = data["t"][1].dropna(subset=["k"]).groupby(["k", "j"]).v.sum() \
        .reset_index(name="revenue").sort_values(["k", "j"])
    got = got.to_pandas()
    assert got.k.tolist() == want.k.tolist()
    for c in got.columns[2:]:
        np.testing.assert_allclose(got[c], want.revenue, rtol=1e-9)


# -- what is NOT one computation -------------------------------------------

def _draw(df):
    return df.with_column("r", F.rand(7)).group_by("k") \
        .agg(F.sum("r").alias("s"))


def _same_frame(pdf):
    return pdf


def _opaque(df):
    return df.map_in_pandas(_same_frame, df.schema).group_by("k") \
        .agg(F.sum("v").alias("s"))


def _twice(block, s):
    t = s.sql("select k, v from t")
    right = block(t).select(col("k").alias("rk"), col("s").alias("rs"))
    return block(t).join(right, on=col("k") == col("rk"))


@pytest.mark.parametrize("second,want", [
    ({"lo": 1177}, dict(lo=1177)),
    ({"fn": "max"}, dict(fn="max")),
    ({"table": "u"}, dict(u=True)),
], ids=["literal", "aggregate-function", "files"])
def test_blocks_that_differ_are_computed_apart(data, second, want):
    s = _session(data)
    got, nodes, moved = _run(s, s.sql(_q65(second)))
    assert moved.get("plan.reuse.subplans", 0) == 0
    assert moved.get("exec.reuse.served", 0) == 0
    assert not _of(nodes, TpuReusedSubplanExec)
    assert len(_of(nodes, TpuHashAggregateExec)) == 3
    if want.pop("u", False):
        want["u"] = data["u"][1]
    _same(got, _q65_want(data["t"][1], **want))


@pytest.mark.parametrize("block", [_draw, _opaque],
                         ids=["rand", "opaque-function"])
def test_a_block_that_is_no_function_of_its_inputs_is_never_merged(
        data, block):
    """``rand()`` written twice is two draws: what the result cache
    would not serve (plan/digest.plan_fingerprint), this does not."""
    from spark_rapids_tpu.plan import digest, optimizer
    s = _session(data)
    df = _twice(block, s)
    plan = optimizer.prune_columns(df.plan)
    aggs = [n for n in digest.walk(plan) if isinstance(n, lp.Aggregate)]
    hashes = digest.node_hashes(plan)
    assert len(aggs) == 2 and hashes[id(aggs[0])] == hashes[id(aggs[1])]
    assert optimizer.mark_equal_aggregates(plan) is plan
    view = registry.get_registry().view()
    assert "TpuReusedSubplanExec" not in df.explain_string("physical")
    assert view.delta()["counters"].get("plan.reuse.subplans", 0) == 0


def test_the_pass_never_edits_the_plan_it_was_given(data):
    """A DataFrame's plan is planned again at every collect."""
    from spark_rapids_tpu.plan import digest, optimizer
    s = _session(data)
    plan = optimizer.prune_columns(
        optimizer.rewrite_implicit_joins(s.sql(_q65()).plan))
    before = plan.tree_string()
    marked = optimizer.mark_equal_aggregates(plan)
    assert marked is not plan and plan.tree_string() == before
    assert not [n for n in digest.walk(plan) if hasattr(n, "_reuse")]
    stamps = [n._reuse for n in digest.walk(marked)
              if isinstance(n, lp.Aggregate) and hasattr(n, "_reuse")]
    assert len(stamps) == 2 and stamps[0] == stamps[1]
    assert digest.plan_digest(marked) == digest.plan_digest(plan)


# -- each occurrence's own names -------------------------------------------

def test_each_occurrence_keeps_its_own_output_names(data):
    s = _session(data)
    pair = ("(select k, sum(v) as sv from t group by k) x, "
            "(select k, sum(v) as sv from t group by k) y")
    got, nodes, moved = _run(s, s.sql(
        f"select x.k, y.k as yk, x.sv, y.sv as ysv from {pair} "
        "where x.k = y.k order by x.k"))
    assert moved.get("plan.reuse.subplans") == 1
    assert got.column_names == ["k", "yk", "sv", "ysv"]
    want = data["t"][1].dropna(subset=["k"]).groupby("k").v.sum()
    assert got.column("k").to_pylist() == got.column("yk").to_pylist() \
        == [int(k) for k in want.index]
    np.testing.assert_allclose(got.column("sv").to_numpy(), want,
                               rtol=1e-9)
    np.testing.assert_allclose(got.column("ysv").to_numpy(), want,
                               rtol=1e-9)


def test_aggregates_that_differ_in_names_only_are_one(data):
    """The hash leaves output names out; the node that stands for the
    second occurrence hands the batches on under that one's names."""
    s = _session(data)
    t = s.sql("select k, v from t")
    x = t.group_by("k").agg(F.sum("v").alias("sx"))
    y = t.group_by("k").agg(F.sum("v").alias("sy")) \
        .select(col("k").alias("yk"), col("sy"))
    got, nodes, moved = _run(s, x.join(y, on=col("k") == col("yk"))
                             .sort("k"))
    assert moved.get("plan.reuse.subplans") == 1
    owner, ref = _of(nodes, TpuReusedSubplanExec)
    assert owner.schema.names == ["k", "sx"]
    assert ref.schema.names == ["k", "sy"]
    assert got.column_names == ["k", "sx", "yk", "sy"]
    want = data["t"][1].dropna(subset=["k"]).groupby("k").v.sum()
    assert got.column("yk").to_pylist() == [int(k) for k in want.index]
    np.testing.assert_allclose(got.column("sy").to_numpy(), want,
                               rtol=1e-9)


# -- shared buffers are not donated ----------------------------------------

def test_a_project_and_a_filter_over_the_reused_node_leave_it_whole(data):
    """``sql.fusion.donateInputs`` at its default: the stage over the
    first occurrence computes new columns from the held batches, and
    the second occurrence still reads them."""
    s = _session(data)
    got, nodes, moved = _run(s, s.sql(
        "select x.k, x.d, y.e from "
        "(select k, sv * 2 as d from (select k, sum(v) as sv from t "
        " group by k) a where sv > 0) x, "
        "(select k, sv + 1 as e from (select k, sum(v) as sv from t "
        " group by k) b where sv > 1) y "
        "where x.k = y.k order by x.k"))
    assert moved.get("plan.reuse.subplans") == 1
    assert moved.get("exec.reuse.served") == 1
    stages = [n for n in nodes if type(n).__name__ == "TpuFusedStageExec"
              and isinstance(n.children[0], TpuReusedSubplanExec)]
    assert len(stages) == 2 and all(n._donate_enabled for n in stages)
    from spark_rapids_tpu.exec.fused_stage import donate_ok
    assert not any(donate_ok(n.children[0], True) for n in stages)
    want = data["t"][1].dropna(subset=["k"]).groupby("k").v.sum()
    np.testing.assert_allclose(got.column("d").to_numpy(), want * 2,
                               rtol=1e-9)
    np.testing.assert_allclose(got.column("e").to_numpy(), want + 1,
                               rtol=1e-9)


# -- more than one partition, more than one task ---------------------------

@pytest.mark.parametrize("tasks", [1, 4])
def test_partitions_and_concurrent_tasks(data, tasks):
    s = _session(data, **{
        "spark.rapids.tpu.sql.concurrentTpuTasks": tasks,
        "spark.rapids.tpu.sql.agg.exchange.enabled": True,
        "spark.rapids.tpu.sql.shuffle.partitions": 4})
    got, nodes, moved = _run(s, s.sql(
        "select k, j, sum(revenue) as r from ("
        " select k, j, sum(v) as revenue from t group by k, j"
        " union all"
        " select k, j, sum(v) as revenue from t group by k, j) b "
        "group by k, j order by k, j"))
    owner, ref = _of(nodes, TpuReusedSubplanExec)
    assert owner.partitions == 4 and ref.partitions == 4
    assert moved.get("plan.reuse.subplans") == 1
    # eight partition readers, one of which computed
    assert moved.get("exec.reuse.served") == 7
    want = data["t"][1].groupby(["k", "j"], dropna=False).v.sum() \
        .reset_index(name="r")
    want = pd.concat([want[want.k.isna()], want[want.k.notna()]])
    got = got.to_pandas()
    assert len(got) == len(want)
    np.testing.assert_allclose(got.r, 2 * want.r.to_numpy(), rtol=1e-9)


# -- the exec on its own: failure, cancellation, executions ----------------

class _Source(TpuExec):
    """A device source of ``parts`` partitions that can be held at its
    first batch and made to fail there."""

    def __init__(self, parts=1, batches=2, fail=False):
        super().__init__()
        self.parts, self.batches, self.fail = parts, batches, fail
        self.gate = threading.Event()
        self.gate.set()
        self.entered = threading.Event()
        self.executions = 0

    @property
    def schema(self):
        return lp.Schema([lp.Field("a", from_arrow(
            pa.table({"a": pa.array([1], pa.int64())})).dtypes[0], True)])

    def execute(self):
        self.executions += 1

        def run(p):
            for i in range(self.batches):
                self.entered.set()
                assert self.gate.wait(60)
                if self.fail:
                    raise ValueError("the producer failed")
                yield from_arrow(pa.table(
                    {"a": pa.array([p * 10 + i] * 3, pa.int64())}))
        return [run(p) for p in range(self.parts)]


def _pair(src, names=("b",)):
    owner = TpuReusedSubplanExec(src.schema, "feedfacecafe", src.parts,
                                 child=src)
    schema = lp.Schema([lp.Field(n, f.dtype, f.nullable)
                        for n, f in zip(names, src.schema.fields)])
    ref = TpuReusedSubplanExec(schema, "feedfacecafe", src.parts,
                               source=owner)
    owner.consumers = 2
    return owner, ref


def _values(it):
    return [to_arrow(b).column(0).to_pylist()[0] for b in it]


def _held_buffers():
    return len(spill.get_catalog()._buffers) if spill.is_enabled() else 0


def test_nothing_runs_at_execute_and_the_first_pull_computes():
    src = _Source(parts=2)
    owner, ref = _pair(src)
    view = registry.get_registry().view()
    before = _held_buffers()
    rits, oits = ref.execute(), owner.execute()
    assert src.executions == 0 and len(rits) == len(oits) == 2
    first = next(rits[1])            # the referring node pulls first
    assert src.executions == 1 and first.names == ["b"]
    assert _held_buffers() == before + (4 if spill.is_enabled() else 0)
    assert _values(oits[0]) == [0, 1] and _values(oits[1]) == [10, 11]
    assert _values(rits[0]) == [0, 1]
    assert _held_buffers() > before or not spill.is_enabled()
    assert _values(rits[1]) == [11]
    # the last reader is done: nothing is held
    assert owner._held.parts == [] and _held_buffers() == before
    assert src.executions == 1
    assert view.delta()["counters"]["exec.reuse.served"] == 3


@pytest.mark.parametrize("kind", ["fails", "cancelled"])
def test_a_waiting_consumer_sees_the_producers_end(kind):
    src = _Source(fail=(kind == "fails"))
    src.gate.clear()
    owner, ref = _pair(src)
    before = _held_buffers()
    tok = sched_cancel.CancelToken(7)
    errors = {}

    def pull(name, node):
        with sched_cancel.install(tok):
            try:
                for it in node.execute():
                    for _ in it:
                        pass
            except BaseException as e:      # noqa: BLE001 - asserted
                errors[name] = e
    was = obstrace.is_enabled()
    obstrace.configure(True)
    mark = obstrace.mark()
    try:
        producer = threading.Thread(target=pull, args=("producer", owner))
        producer.start()
        assert src.entered.wait(60)
        consumer = threading.Thread(target=pull, args=("consumer", ref))
        consumer.start()
        while not owner._held or len(owner._held.attached) < 2:
            pass
        consumer.join(0.3)
        assert consumer.is_alive()          # it waits for the producer
        if kind == "cancelled":
            tok.cancel("test")
            src.fail = True    # the producer unwinds at its next step
        src.gate.set()
        producer.join(60)
        consumer.join(60)
        assert not producer.is_alive() and not consumer.is_alive()
        waits = [sp for sp in obstrace.spans_since(mark)
                 if sp[2] == "reuse.wait"]
    finally:
        obstrace.configure(was)
    assert isinstance(errors["producer"], ValueError)
    assert errors["consumer"] is errors["producer"]
    assert owner._held.parts == [] and _held_buffers() == before
    assert len(waits) == 1


def test_a_cancelled_reader_lets_the_result_go():
    src = _Source(batches=3)
    owner, ref = _pair(src)
    before = _held_buffers()
    tok = sched_cancel.CancelToken(8)
    rit, = ref.execute()
    oit, = owner.execute()
    with sched_cancel.install(tok):
        assert next(oit) is not None
        tok.cancel("test")
        with pytest.raises(sched_cancel.QueryCancelledError):
            next(oit)
    assert owner._held.parts == [] and _held_buffers() == before
    with pytest.raises(sched_cancel.QueryCancelledError):
        next(rit)


def test_a_result_belongs_to_one_execution():
    src = _Source()
    owner, ref = _pair(src)
    for n in (1, 2):
        assert [_values(it) for it in ref.execute()] == [[0, 1]]
        assert [_values(it) for it in owner.execute()] == [[0, 1]]
        assert src.executions == n and owner._held.parts == []
    # a reader that executes again before the others came: a new one
    ref.execute()
    assert [_values(it) for it in ref.execute()] == [[0, 1]]
    assert src.executions == 3


def test_held_state_does_not_travel(data):
    src = _Source()
    src.gate = src.entered = None            # events do not pickle
    owner, ref = _pair(src)
    owner._held = object()
    o2, r2 = pickle.loads(pickle.dumps((owner, ref)))
    assert o2._held is None and r2._source is o2 and o2.consumers == 2


def test_the_same_dataframe_collected_twice_computes_once_each(data):
    s = _session(data)
    df = s.sql(_WITH)
    for _ in range(2):
        got, nodes, moved = _run(s, df)
        assert moved.get("plan.reuse.subplans") == 1
        assert moved.get("exec.reuse.served") == 1
        scan, = [n for n in nodes
                 if type(n).__name__ == "TpuParquetScanExec"]
        assert scan.metrics.num_output_batches == _FILES
        owner, _ = _of(nodes, TpuReusedSubplanExec)
        assert owner._held.parts == []
    assert got.num_rows > 0


# -- what the plan says ------------------------------------------------------

def test_explain_names_the_reused_subplan_and_metrics_round_trip(data):
    s = _session(data)
    df = s.sql(_q65())
    text = df.explain_string("physical")
    assert text.count("TpuHashAggregateExec(fusedFilter") == 2  # one + named
    lines = [ln.strip() for ln in text.splitlines()
             if "TpuReusedSubplanExec" in ln]
    assert len(lines) == 2
    assert "computed once for 2 consumers" in lines[0]
    assert lines[1].startswith("*TpuReusedSubplanExec(reuses subplan ")
    assert "TpuHashAggregateExec(fusedFilter=" in lines[1]
    tag = lines[0].split("subplan ")[1][:8]
    assert f"reuses subplan {tag}" in lines[1]
    got, nodes, _ = _run(s, df)
    plan = nodes[0]
    recorded = collect_plan_metrics(plan)
    assert [r["name"] for r in recorded] == [type(n).__name__
                                             for n in nodes]
    ref = _of(nodes, TpuReusedSubplanExec)[1]
    at = nodes.index(ref)
    assert recorded[at]["batches"] == 1 and recorded[at]["rows"] > 0
    # a fragment shipped to an executor has the same pre-order and
    # carries no result
    from spark_rapids_tpu.exec.tpu_basic import TpuUnionExec
    owner = _of(nodes, TpuReusedSubplanExec)[0]
    owner._held = object()
    fragment = TpuUnionExec([owner, ref])
    recorded = collect_plan_metrics(fragment)
    twin = pickle.loads(pickle.dumps(fragment))
    assert twin.children[1]._source is twin.children[0]
    assert twin.children[0]._held is None
    for n in (twin.children[0], twin.children[0].children[0],
              twin.children[1]):
        n.metrics = type(n.metrics)()
    merge_plan_metrics(twin, recorded)
    again = collect_plan_metrics(twin)
    assert [(r["name"], r["rows"], r["batches"]) for r in again[:3]] == \
        [(r["name"], r["rows"], r["batches"]) for r in recorded[:3]]
    assert again[-1]["rows"] == recorded[-1]["rows"] > 0
    prof = s.last_query_profile()
    assert "TpuReusedSubplanExec(reuses subplan" in str(prof.to_dict()["plan"])

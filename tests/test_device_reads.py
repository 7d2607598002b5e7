"""The host's reads of device values (columnar/batch.read_host): on
each benchmark cell's statement, at the rehearsal's scale of the cell's
own data and conf, every host copy of a device value passes through
``read_host``, every read is counted under its site and, with tracing
on, is a ``device.read`` span that says what it read."""

import os
import sys

import jax
import jax.numpy as jnp
import pytest

from spark_rapids_tpu.columnar.batch import read_host
from spark_rapids_tpu.obs import registry, trace
from tests.host_copies import copies_outside_read_host

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(CHECKOUT, "benchmark")
# the benchmark's rehearsal scale (benchmark/run.py REHEARSE_SCALE)
SCALE = 0.01


@pytest.fixture(scope="module")
def bench():
    sys.path.insert(0, BENCH)
    try:
        import cells
        import datagen
        yield {"cells": cells, "datagen": datagen}
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(autouse=True)
def _tracer_off_after():
    yield
    trace.configure(False)
    trace.clear()


def _counters():
    return registry.get_registry().view()


def _reads(moved):
    return {k: v for k, v in moved.items() if k.startswith("device.reads.")}


@pytest.mark.parametrize("cell", ["tpch-sf1.q1", "tpcds-sf1.q3",
                                  "tpcds-sf10.q65"])
def test_every_host_copy_of_a_cells_statement_is_a_named_read(
        bench, tmp_path, cell):
    from spark_rapids_tpu import TpuSparkSession
    c = bench["cells"].Cell(cell)
    root = str(tmp_path)
    bench["datagen"].generate(c.config["datagen"], root,
                              c.scaled_tables(SCALE), 2**31 + 37)
    spark = TpuSparkSession({**c.conf,
                             "spark.rapids.tpu.obs.trace.enabled": True})
    for t in c.tables:
        spark.register_view(t, spark.read.parquet(os.path.join(root, t)))
    (stmt,) = c.statements
    view = _counters()
    with copies_outside_read_host() as outside:
        got = spark.sql(stmt.sql).collect()
    moved = view.delta()["counters"]
    assert got.num_rows > 0
    assert outside == [], outside[0]
    reads = _reads(moved)
    assert moved["device.reads"] == sum(reads.values()) > 0
    spans = [sp for sp in spark.last_query_profile().spans
             if sp["cat"] == "device.read"]
    assert {sp["name"] for sp in spans} <= \
        {k[len("device.reads."):] for k in reads}
    for sp in spans:
        assert sp["args"]["chips"] == [jax.devices()[0].id]
        assert sp["args"]["values"] >= 1
        assert sp["chip"] is None        # one chip
    if cell != "tpch-sf1.q1":
        # the direct joins' probe counts: total and maxm in one call
        assert reads["device.reads.join.countWait"] >= 2
        assert any(sp["name"] == "join.countWait" and
                   sp["args"]["values"] == 2 for sp in spans)


def test_a_value_the_host_knows_is_no_read():
    view = _counters()
    assert read_host(7, "test.hostWait") == 7
    x = jnp.arange(4, dtype=jnp.int32) + 1
    a, b, n = read_host([x, x.sum(), 3], "test.someWait")
    assert a.tolist() == [1, 2, 3, 4] and int(b) == 10 and n == 3
    moved = view.delta()["counters"]
    assert moved["device.reads"] == moved["device.reads.test.someWait"] \
        == 1
    assert "device.reads.test.hostWait" not in moved


def test_a_read_is_a_span_when_tracing_is_on():
    trace.configure(True, 4096)
    trace.clear()
    devs = jax.devices()[:2]
    got = read_host([jax.device_put(jnp.int32(5), d) for d in devs],
                    "test.pairWait")
    assert [int(v) for v in got] == [5, 5]
    (sp,) = [s for s in trace.snapshot() if s[2] == "test.pairWait"]
    assert sp[3] == "device.read"
    assert sp[7] == {"chips": sorted(d.id for d in devs), "values": 2}

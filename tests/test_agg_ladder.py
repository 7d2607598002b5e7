"""The aggregate's capacity ladder (exec/tpu_aggregate._on_ladder): the
update and the merge run at the lowest of cap/4, cap/2 and cap that
holds the live rows.  Capacity tiers stand 4x apart, so without the
cap/2 rung a batch between a quarter and a half of its tier (TPC-H Q1's
1.5M rows at 4,194,304) ran every sort pass, gather and scan at full
capacity."""

import numpy as np
import pyarrow as pa
import pytest

import jax
import jax.numpy as jnp

from spark_rapids_tpu import TpuSparkSession, col, functions as F
from spark_rapids_tpu.exec import kernel_cache as kc
from spark_rapids_tpu.exec import tpu_aggregate as agg
from tests.parity import assert_tables_equal, with_cpu_session

_CONF = {"spark.rapids.tpu.sql.variableFloatAgg.enabled": True}
_CAP = 1024          # rungs 256 and 512 once the threshold is lowered


@pytest.fixture
def low_ladder(monkeypatch):
    """The ladder engaged at suite scale; no program traced under
    another threshold is reused, before or after."""
    monkeypatch.setattr(agg, "_LADDER_MIN_RUNG", 8)
    kc.clear()
    yield
    kc.clear()


def _rung_of(nr, cap=_CAP, seen=None):
    """The rung ``_on_ladder`` runs at, as a value; ``seen`` collects
    the rungs it traces."""
    def at(cap2):
        if seen is not None:
            seen.append(cap2)
        return jnp.int32(cap2)
    return agg._on_ladder(cap, nr, at)


@pytest.mark.parametrize("nr,want", [
    (0, 256), (1, 256), (256, 256), (257, 512), (400, 512), (512, 512),
    (513, 1024), (1024, 1024)])
@pytest.mark.parametrize("traced", [False, True],
                         ids=["host_count", "device_count"])
def test_lowest_rung_that_holds_the_rows(low_ladder, nr, want, traced):
    if traced:
        got = jax.jit(_rung_of)(jnp.int32(nr))
    else:
        got = _rung_of(nr)
    assert int(got) == want


def test_host_count_traces_one_rung_only(low_ladder):
    seen = []
    _rung_of(np.int64(300), seen=seen)
    assert seen == [512]


def test_device_count_is_one_switch_of_three(low_ladder):
    text = jax.jit(_rung_of).lower(jnp.int32(3)).as_text()
    assert text.count("stablehlo.case") == 1
    assert text.count("stablehlo.while") == 0
    seen = []
    jax.make_jaxpr(lambda nr: _rung_of(nr, seen=seen))(jnp.int32(3))
    assert seen == [256, 512, 1024]


@pytest.mark.parametrize("cap", [16, 1024, 1 << 19])
def test_below_the_threshold_nothing_branches(cap):
    # the default threshold: cap/4 under 262,144 runs at cap, as before
    seen = []
    out = jax.jit(lambda nr: _rung_of(nr, cap, seen))(jnp.int32(1))
    assert seen == [cap] and int(out) == cap


def test_default_threshold_engages_at_a_million_rows():
    seen = []
    jax.make_jaxpr(lambda nr: _rung_of(nr, 1 << 20, seen))(jnp.int32(1))
    assert seen == [1 << 18, 1 << 19, 1 << 20]


def _table(n, seed=11):
    rng = np.random.default_rng(seed)
    flags = np.array(["A", "N", "R"])
    return pa.table({
        "f": pa.array(flags[rng.integers(0, 3, n)]),
        "k": pa.array(rng.integers(0, 5, n), type=pa.int64()),
        "v": pa.array(rng.integers(-9, 9, n), type=pa.int64()),
        "w": pa.array(rng.normal(size=n) * 1e3)})


def _q1_like(t, thresh):
    def q(s):
        df = s.create_dataframe(t)
        if thresh is not None:
            df = df.filter(col("v") > thresh)
        return df.group_by("f", "k").agg(
            F.count("*").alias("c"), F.sum("w").alias("sw"),
            F.avg("w").alias("aw"), F.min("v").alias("mn"),
            F.max("w").alias("mx"))
    return q


# cap 1024 (1000 rows): v > 7 keeps 1/18, v > 1 keeps 7/18, v > -10 all
@pytest.mark.parametrize("thresh,over,rung",
                         [(7, 0, 256), (1, 256, 512), (-10, 512, 1024)],
                         ids=["quarter", "half", "full"])
def test_fused_filter_update_equals_cpu_at_every_rung(
        low_ladder, monkeypatch, thresh, over, rung):
    t = _table(1000)
    assert over < int((t["v"].to_numpy() > thresh).sum()) <= rung
    cpu = with_cpu_session(lambda s: _q1_like(t, thresh)(s).collect())
    laddered, real = [], agg._on_ladder
    monkeypatch.setattr(agg, "_on_ladder", lambda cap, nr, at: (
        laddered.append((cap, isinstance(nr, jax.core.Tracer))),
        real(cap, nr, at))[1])
    out = _q1_like(t, thresh)(TpuSparkSession(_CONF)).collect()
    assert_tables_equal(cpu, out, ignore_order=True, approx_float=True)
    assert (1024, True) in laddered     # the update's count is traced


# no filter: the batch-shaped ladder (`_laddered`) with host-known
# counts; 200 and 256 rows make a 256-row batch, the others a 1024-row one
@pytest.mark.parametrize("n", [200, 256, 257, 500, 512, 513, 1000],
                         ids=lambda n: f"rows{n}")
def test_batch_ladder_update_and_merge_equal_cpu(low_ladder, n):
    q = _q1_like(_table(n, seed=n), None)
    cpu = with_cpu_session(lambda s: q(s).collect())
    out = q(TpuSparkSession(_CONF)).collect()
    assert_tables_equal(cpu, out, ignore_order=True, approx_float=True)


def test_rungs_agree_bit_for_bit_on_integer_aggregates(low_ladder,
                                                       monkeypatch):
    """The same live rows at cap/2 and at full capacity (the ladder
    off): counts, integer sums and extrema are exact, so the answers are
    equal, not close."""
    t = _table(1000, seed=3)

    def q(s):
        return (s.create_dataframe(t).filter(col("v") > 1)
                .group_by("f", "k")
                .agg(F.count("*").alias("c"), F.sum("v").alias("sv"),
                     F.min("v").alias("mn"), F.max("w").alias("mx")))
    on = q(TpuSparkSession(_CONF)).collect()
    monkeypatch.setattr(agg, "_LADDER_MIN_RUNG", 1 << 18)
    kc.clear()
    off = q(TpuSparkSession(_CONF)).collect()
    assert_tables_equal(off, on, ignore_order=True)

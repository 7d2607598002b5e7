"""Shape-erased kernel ABI tests (exec/kernel_abi.py).

Contract: erasure NEVER changes results — only how many programs get
compiled.  These tests pin

  * the tier ladders (capacity + var-len width, ABI on/off),
  * parity sweeps at capacity-tier boundaries (tier, tier +- 1) with
    nulls and strings in play,
  * width-bucketed string round-trips at width-tier boundaries,
  * null-validity preservation under the dispatch-time pad,
  * the collapse itself: the same query over a renamed same-layout
    schema / a different value range compiles ZERO new programs,
  * hint bucketing soundness on the erased view.
"""

from __future__ import annotations

import numpy as np
import pyarrow as pa
import pytest

import jax.numpy as jnp

from spark_rapids_tpu import TpuSparkSession, col, functions as F
from spark_rapids_tpu import dtypes as dt
from spark_rapids_tpu.columnar.batch import (DeviceBatch, DeviceColumn,
                                             from_arrow, to_arrow)
from spark_rapids_tpu.exec import kernel_abi
from spark_rapids_tpu.obs import registry as obsreg


def _session(**extra) -> TpuSparkSession:
    conf = {"spark.rapids.tpu.sql.variableFloatAgg.enabled": True}
    conf.update(extra)
    return TpuSparkSession(conf)


@pytest.fixture(autouse=True)
def _default_abi():
    """Every test in this module starts from the default ABI config
    (another module's last session may have flipped the process-global
    state)."""
    prev = (kernel_abi._enabled, kernel_abi._tier_stride,
            kernel_abi._width_stride, kernel_abi._bucket_hints)
    kernel_abi._enabled = True
    kernel_abi._tier_stride = 2
    kernel_abi._width_stride = 2
    kernel_abi._bucket_hints = True
    yield
    (kernel_abi._enabled, kernel_abi._tier_stride,
     kernel_abi._width_stride, kernel_abi._bucket_hints) = prev


# ---------------------------------------------------------------------------
# tier ladders
# ---------------------------------------------------------------------------

def test_tier_ladders():
    # default stride 2: capacities 16, 64, 256, 1024, ...
    assert [kernel_abi.tier_rows(n) for n in (1, 16, 17, 64, 65, 1024,
                                              1025)] == \
        [16, 16, 64, 64, 256, 1024, 4096]
    # widths 1, 4, 16, 64, ...
    assert [kernel_abi.tier_strlen(n) for n in (0, 1, 2, 4, 5, 16,
                                                17)] == \
        [1, 1, 4, 4, 16, 16, 64]
    # every tier is a legacy pow2 value (no new shape classes)
    for n in range(1, 5000, 37):
        t = kernel_abi.tier_rows(n)
        assert t >= n and (t & (t - 1)) == 0
    # disabled: the legacy every-pow2 ladders
    kernel_abi._enabled = False
    assert [kernel_abi.tier_rows(n) for n in (17, 65, 1025)] == \
        [32, 128, 2048]
    assert kernel_abi.tier_strlen(5) == 8


def _stride_ladder(n: int, stride: int, min_bucket: int = 16) -> int:
    """The ladder as the stride alone gives it (no dense part)."""
    cap, lo = 1, max(n, min_bucket, 1)
    while cap < lo:
        cap <<= stride
    return cap


_DENSE = 1 << 20
# row counts around every rung of every stride up to 2**25, and the
# cells' own (a reader batch of q65, q3 and Q1; q65's partial groups)
_LADDER_PROBES = sorted(
    {max(1, (1 << e) + d) for e in range(26) for d in (-1, 0, 1)} |
    {1_800_061, 1_440_202, 1_500_304, 5_332_700, 3_000_000, 300_000})


@pytest.mark.parametrize("stride", [1, 2, 3])
def test_ladder_below_the_dense_start_is_the_strides(stride):
    kernel_abi._tier_stride = stride
    rungs = {kernel_abi.tier_rows(n) for n in _LADDER_PROBES}
    strided = {1 << e for e in range(0, 20, stride) if (1 << e) >= 16}
    # the least rung is min_bucket's own rung of the stride's ladder
    assert {r for r in rungs if r < _DENSE} == \
        {r for r in strided if r >= _stride_ladder(1, stride)}
    top = max(strided)              # the last strided rung under 2**20
    for n in _LADDER_PROBES:
        if n <= top:
            assert kernel_abi.tier_rows(n) == _stride_ladder(n, stride)
    # whatever lies over it goes to the dense part's first rung
    assert kernel_abi.tier_rows(top + 1) == _DENSE


@pytest.mark.parametrize("stride", [1, 2, 3])
@pytest.mark.parametrize("n,tier", [
    (1_048_576, 1_048_576), (1_048_577, 2_097_152),
    (1_800_061, 2_097_152), (2_097_152, 2_097_152),
    (2_097_153, 4_194_304), (5_332_700, 8_388_608),
    (16_777_217, 33_554_432)])
def test_ladder_is_every_power_of_two_from_the_dense_start(stride, n,
                                                           tier):
    kernel_abi._tier_stride = stride
    assert kernel_abi.tier_rows(n) == tier
    assert kernel_abi.is_tier(tier)
    assert kernel_abi.tier_rows(n, min_bucket=64) == tier


@pytest.mark.parametrize("stride", [1, 2, 3])
def test_dense_ladder_is_never_above_the_strides(stride):
    kernel_abi._tier_stride = stride
    for n in _LADDER_PROBES:
        for floor in (1, 8, 16, 64):
            t = kernel_abi.tier_rows(n, floor)
            assert max(n, floor) <= t <= _stride_ladder(n, stride, floor)
            assert t & (t - 1) == 0
            assert kernel_abi.is_tier(t)


@pytest.mark.parametrize("n,legacy", [
    (300_000, 524_288), (1_048_577, 2_097_152), (1_800_061, 2_097_152),
    (2_097_153, 4_194_304), (5_332_700, 8_388_608)])
def test_disabled_abi_keeps_the_legacy_ladder(n, legacy):
    kernel_abi._enabled = False
    assert kernel_abi.tier_rows(n) == legacy
    assert kernel_abi.tier_rows(n, min_bucket=64) == legacy


def test_erase_leaves_a_half_rung_batch_unpadded():
    """2,097,152 is a rung since the ladder is dense from 1,048,576: a
    reader batch born there is dispatched as it is, buffers shared
    (before, erase padded it to 4,194,304 at every dispatch)."""
    cap, n = 2_097_152, 1_800_061
    assert kernel_abi.is_tier(cap) and kernel_abi.tier_rows(n) == cap
    valid = jnp.arange(cap) < n
    b = DeviceBatch(["k"], [DeviceColumn(
        dt.INT32, jnp.zeros(cap, dtype=jnp.int32), valid)], n)
    eb = kernel_abi.erase(b)
    assert eb.capacity == cap and eb.num_rows == n
    assert eb.columns[0].data is b.columns[0].data
    assert eb.columns[0].validity is b.columns[0].validity


def test_bucket_vbits():
    assert kernel_abi.bucket_vbits(None) is None
    assert kernel_abi.bucket_vbits(8) == 16
    assert kernel_abi.bucket_vbits(16) == 16
    assert kernel_abi.bucket_vbits(24) == 32
    assert kernel_abi.bucket_vbits(40) == 56
    assert kernel_abi.bucket_vbits(56) == 56
    assert kernel_abi.bucket_vbits(63) is None
    kernel_abi._bucket_hints = False
    assert kernel_abi.bucket_vbits(8) == 8


# ---------------------------------------------------------------------------
# parity at capacity-tier boundaries
# ---------------------------------------------------------------------------

def _boundary_query(s, n):
    rows = list(range(n))
    df = s.create_dataframe(
        {"k": [i % 5 for i in rows],
         "x": [float(i % 97) if i % 11 else None for i in rows],
         "s": [f"name{i % 13}" if i % 7 else None for i in rows]},
        num_partitions=1)
    return (df.with_column("y", col("x") * 3.0 - 1.0)
              .filter(col("y") > 30.0)
              .group_by("k")
              .agg(F.count("*").alias("n"), F.sum("y").alias("sy"),
                   F.max("s").alias("ms"))
              .sort("k"))


@pytest.mark.parametrize("n", [255, 256, 257, 1023, 1024, 1025])
def test_tier_boundary_parity(n):
    """Exact tier size and tier size +- 1 must agree with the
    ABI-disabled oracle bit-for-bit (nulls + strings in play)."""
    got = _boundary_query(_session(), n).collect()
    oracle = _boundary_query(_session(
        **{"spark.rapids.tpu.kernel.abi.enabled": False}), n).collect()
    assert got.equals(oracle), (
        f"n={n}: ABI on/off diverge\n{got.to_pydict()}\n"
        f"{oracle.to_pydict()}")


# ---------------------------------------------------------------------------
# width-bucketed strings + pad/slice validity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("width", [3, 4, 5, 15, 16, 17, 63, 64, 65])
def test_string_width_tier_roundtrip(width):
    vals = [("x" * width) if i % 3 else None for i in range(40)]
    vals[7] = ""                       # empty string != null
    t = pa.table({"s": pa.array(vals, type=pa.string())})
    b = from_arrow(t)
    # born at a width tier covering the longest string
    assert b.columns[0].max_len >= width
    assert b.columns[0].max_len == \
        kernel_abi.tier_strlen(b.columns[0].max_len)
    back = to_arrow(b)
    assert back.column("s").to_pylist() == vals


def test_pad_to_tier_preserves_validity_and_rows():
    """A batch with a NON-tier capacity (hand-built) pads at erase
    time: padding rows validity-False/data-zero, live rows and
    num_rows untouched, string width padded to its tier."""
    cap, n = 48, 37                    # 48 is not a tier
    data = jnp.arange(cap, dtype=jnp.int64)
    valid = jnp.arange(cap) < n
    sdata = jnp.zeros((cap, 5), dtype=jnp.uint8) + 65   # width 5: no tier
    slens = jnp.where(valid, 3, 0).astype(jnp.int32)
    b = DeviceBatch(
        ["v", "s"],
        [DeviceColumn(dt.INT64, jnp.where(valid, data, 0), valid,
                      vbits=8),
         DeviceColumn(dt.STRING, jnp.where(valid[:, None], sdata, 0),
                      valid, slens)],
        n)
    eb = kernel_abi.erase(b)
    assert eb.names == ["_c0", "_c1"]
    assert eb.capacity == kernel_abi.tier_rows(cap) == 64
    assert eb.num_rows == n
    assert eb.columns[1].max_len == kernel_abi.tier_strlen(5) == 16
    assert eb.columns[0].vbits == 16           # bucketed from 8
    v = np.asarray(eb.columns[0].validity)
    assert v[:n].all() and not v[n:].any()
    d = np.asarray(eb.columns[0].data)
    assert (d[n:] == 0).all()
    ln = np.asarray(eb.columns[1].lengths)
    assert (ln[n:] == 0).all() and (ln[:n] == 3).all()
    sd = np.asarray(eb.columns[1].data)
    assert (sd[:, 5:] == 0).all()              # width padding zeroed
    # round-trip through download: padding never leaks into results
    back = to_arrow(DeviceBatch(b.names, eb.columns, n))
    assert back.num_rows == n
    assert back.column("v").to_pylist() == list(range(n))


def test_erase_is_buffer_sharing_when_born_at_tier():
    t = pa.table({"a": pa.array(np.arange(100, dtype=np.int64))})
    b = from_arrow(t)                  # born at tier capacity
    eb = kernel_abi.erase(b)
    assert eb.columns[0].data is b.columns[0].data
    assert eb.num_rows == b.num_rows
    # disabled ABI: erase is the identity
    kernel_abi._enabled = False
    assert kernel_abi.erase(b) is b


# ---------------------------------------------------------------------------
# the collapse itself
# ---------------------------------------------------------------------------

def _serving_query(df, k, x):
    return (df.with_column("y", col(x) * 2.0 + 1.0)
              .filter(col("y") > 20.0)
              .group_by(k)
              .agg(F.count("*").alias("n"), F.sum("y").alias("sy"))
              .sort(k))


def test_renamed_schema_compiles_zero_new_programs():
    """The headline erased-ABI property: a same-layout schema under
    different column names shares EVERY program except agg_final
    (which bakes the real output names by design)."""
    s = _session()

    def data(names, scale, n):
        return s.create_dataframe(
            {names[0]: [(i % 7) * scale for i in range(n)],
             names[1]: [float(i % 100) for i in range(n)]},
            num_partitions=2)

    first = _serving_query(data(("k", "x"), 1, 2000), "k", "x").collect()
    view = obsreg.get_registry().view()
    second = _serving_query(data(("a", "b"), 1, 2000), "a", "b").collect()
    d = view.delta()["counters"]
    fresh = {k: int(v) for k, v in d.items()
             if k.startswith("kernel.cache.misses.") and v}
    assert set(fresh) <= {"kernel.cache.misses.agg_final"}, fresh
    assert d.get("kernel.cache.memHits", 0) > 0
    assert first.column(1).to_pylist() == second.column(1).to_pylist()


def test_renamed_join_schema_compiles_zero_new_programs():
    """The erased ABI extended into the join ``emit`` family (PR 14):
    the same join over renamed same-layout schemas shares EVERY
    program — the join kernels key on canonical __l*/__r* positional
    names + erased layout keys, capacities route through bucket_rows,
    and dispatch-boundary hints bucket via kernel_abi.erase."""
    s = _session()

    def data(kn, vn, n, seed):
        return s.create_dataframe(
            {kn: [(i * 7 + seed) % 13 for i in range(n)],
             vn: [float(i % 50) for i in range(n)]})

    def q(left, right, kl):
        return left.join(right, on=kl).sort(kl).collect()

    first = q(data("k", "lv", 300, 0),
              data("k", "rv", 200, 3).select(
                  col("k"), col("rv")), "k")
    view = obsreg.get_registry().view()
    second = q(data("a", "x1", 300, 0),
               data("a", "y1", 200, 3).select(
                   col("a"), col("y1")), "a")
    d = view.delta()["counters"]
    fresh = {k: int(v) for k, v in d.items()
             if k.startswith("kernel.cache.misses.") and v}
    # agg_final bakes real names by design; nothing in the join
    # families (emit/count/probe_*/semi/join_pack/cross) may re-mint
    assert not {k for k in fresh if "emit" in k or "count" in k or
                "probe" in k or "semi" in k or "join" in k or
                "cross" in k}, fresh
    assert set(fresh) <= {"kernel.cache.misses.agg_final"}, fresh
    assert first.column(1).to_pylist() == second.column(1).to_pylist()
    assert first.column(2).to_pylist() == second.column(2).to_pylist()


def test_value_range_drift_compiles_zero_new_programs():
    """Value ranges inside one ABI hint bucket share programs: the
    precise vbits (8 vs 16 here) both bucket to 16."""
    s = _session()

    def data(scale, n):
        return s.create_dataframe(
            {"k": [(i % 7) * scale for i in range(n)],
             "x": [float(i % 100) for i in range(n)]},
            num_partitions=2)

    _serving_query(data(1, 2000), "k", "x").collect()     # vbits 8
    view = obsreg.get_registry().view()
    _serving_query(data(900, 2000), "k", "x").collect()   # vbits 16
    d = view.delta()["counters"]
    assert d.get("kernel.cache.compiles", 0) == 0, dict(d)


def test_capacity_within_tier_compiles_zero_new_programs():
    """Row counts whose legacy pow2 caps differ but share one tier
    (1100 -> 2048 legacy / 4096 tier; 2100 -> 4096 both) share every
    program under the ABI."""
    s = _session()

    def data(n):
        return s.create_dataframe(
            {"k": [i % 7 for i in range(n)],
             "x": [float(i % 100) for i in range(n)]},
            num_partitions=1)

    _serving_query(data(2100), "k", "x").collect()
    view = obsreg.get_registry().view()
    _serving_query(data(1100), "k", "x").collect()
    d = view.delta()["counters"]
    assert d.get("kernel.cache.compiles", 0) == 0, dict(d)


def test_layout_key_has_no_names():
    t = pa.table({"alpha": pa.array(np.arange(32, dtype=np.int64)),
                  "beta": pa.array(["ab"] * 32)})
    t2 = pa.table({"x": pa.array(np.arange(32, dtype=np.int64)),
                   "y": pa.array(["cd"] * 32)})
    k1 = kernel_abi.layout_key(from_arrow(t))
    k2 = kernel_abi.layout_key(from_arrow(t2))
    assert k1 == k2
    assert "alpha" not in repr(k1)

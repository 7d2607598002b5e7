"""The aggregate's sorted-group reductions (``tpu_aggregate._SortedCtx``:
``take_sorted`` -> segmented scan or cumsum -> ``take(end_pos)``)
against a plain per-group numpy loop.

Float inputs are multiples of 1/4 whose sums stay far under 2^53, so a
group's sum is exact in any order and the comparison is bit for bit
without the oracle knowing how the scan pairs its operands
(tests/test_scans.py pins that order)."""

import numpy as np
import pytest

import jax.numpy as jnp

from spark_rapids_tpu import dtypes as dt
from spark_rapids_tpu.exec.tpu_aggregate import _group_ctx
from spark_rapids_tpu.expr.eval_tpu import ColVal

_NP_OPS = {"add": np.add, "min": np.minimum, "max": np.maximum}
_JNP_OPS = {"add": jnp.add, "min": jnp.minimum, "max": jnp.maximum}


def _ctx(keys: np.ndarray, n: int):
    """The grouping of ``keys[:n]`` and, group by group in the
    context's own order, the original rows each group holds."""
    cap = keys.shape[0]
    kv = ColVal(dt.INT64, jnp.asarray(keys), jnp.ones(cap, bool), None)
    ctx = _group_ctx([kv], cap, n)
    ng = int(ctx.n_groups)
    order = np.asarray(ctx.order)
    first = keys[order[np.asarray(ctx.start_pos)[:ng]]]
    assert len(set(first.tolist())) == ng == len(set(keys[:n].tolist()))
    rows = [np.flatnonzero((keys == k) & (np.arange(cap) < n))
            for k in first]
    return ctx, ng, rows


def _per_group(rows, fn, np_t):
    return np.asarray([fn(r) for r in rows], dtype=np_t)


def _quarters(rng, lo, hi, n):
    return rng.integers(lo * 4, hi * 4, n) / 4.0


@pytest.mark.parametrize("cap,np_t,op,ident", [
    (1024, np.float64, "add", 0.0),
    (1 << 17, np.float64, "add", 0.0),      # the blocked carry
    (1024, np.int64, "min", np.iinfo(np.int64).max),
    (1 << 17, np.int64, "max", np.iinfo(np.int64).min),
    (1024, np.int32, "add", 0),
    (1 << 17, np.uint64, "min", np.iinfo(np.uint64).max),
])
def test_seg_scan_reduce_against_group_loop(cap, np_t, op, ident):
    rng = np.random.default_rng(cap % 97)
    n = cap - 37
    keys = rng.integers(0, 40, cap).astype(np.int64)
    if np.dtype(np_t).kind == "f":
        vals = _quarters(rng, -1_000_000, 1_000_000, cap).astype(np_t)
    else:
        vals = rng.integers(0, 1000, cap).astype(np_t)
    ctx, ng, rows = _ctx(keys, n)
    # the caller's contract: rows that do not exist hold the identity
    x = jnp.where(ctx.row_mask, jnp.asarray(vals),
                  jnp.asarray(ident, dtype=np_t))
    got = np.asarray(ctx.seg_scan_reduce(
        ctx.take_sorted(x), _JNP_OPS[op], ident))[:ng]
    want = _per_group(rows, lambda r: _NP_OPS[op].reduce(vals[r]), np_t)
    assert got.dtype == want.dtype and np.array_equal(got, want)


def test_all_reductions_against_group_loop():
    rng = np.random.default_rng(17)
    cap, n = 4096, 3700
    keys = rng.integers(0, 23, cap).astype(np.int64)
    fvals = _quarters(rng, -100_000, 100_000, cap)
    ivals = rng.integers(-500, 500, cap).astype(np.int64)
    ctx, ng, rows = _ctx(keys, n)
    f, iv = jnp.asarray(fvals), jnp.asarray(ivals)
    mask = ctx.row_mask
    sub_np = ivals % 3 == 0
    sub = mask & jnp.asarray(sub_np)
    # only the real groups: slots past n_groups hold whatever the
    # formulation leaves there, masked by group_exists before anything
    # leaves the aggregate (_append_buffers)
    got = [np.asarray(a)[:ng] for a in (
        ctx.seg_sum(f, mask, out_np=np.float64),
        ctx.seg_sum(iv, mask, out_np=np.int64),
        ctx.seg_sum(iv, mask, out_np=np.int64, narrow_bits=10),
        ctx.seg_count(mask),
        ctx.seg_count(sub),
        ctx.seg_min_of(f, mask, np.inf),
        ctx.seg_max_of(iv, mask, np.iinfo(np.int64).min))]
    want = [
        _per_group(rows, lambda r: fvals[r].sum(), np.float64),
        _per_group(rows, lambda r: ivals[r].sum(), np.int64),
        _per_group(rows, lambda r: ivals[r].sum(), np.int64),
        _per_group(rows, len, np.int64),
        _per_group(rows, lambda r: sub_np[r].sum(), np.int64),
        _per_group(rows, lambda r: fvals[r].min(), np.float64),
        _per_group(rows, lambda r: ivals[r].max(), np.int64)]
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and np.array_equal(g, w)


def test_float_sum_of_a_group_across_three_blocks():
    # cap 2^17 float64: four blocks of the scan; the second group holds
    # about three quarters of the rows, so the running (flag, value)
    # pair is carried over every block edge inside it
    rng = np.random.default_rng(5)
    cap = 1 << 17
    keys = np.full(cap, 2, np.int64)
    keys[rng.choice(cap, cap // 8, replace=False)] = 1
    keys[rng.choice(cap, cap // 8, replace=False)] = 3
    vals = _quarters(rng, -1_000_000_000, 1_000_000_000, cap)
    ctx, ng, rows = _ctx(keys, cap)
    assert ng == 3 and len(rows[1]) > 5 * cap // 8
    got = np.asarray(ctx.seg_sum(jnp.asarray(vals), ctx.row_mask,
                                 out_np=np.float64))[:ng]
    want = _per_group(rows, lambda r: vals[r].sum(), np.float64)
    assert np.array_equal(got.view(np.uint64), want.view(np.uint64))


def test_string_min_and_first_against_pandas():
    # string MIN (word-wise u64 tie-break through seg_scan_reduce) and
    # first (an index-min pick with a traced identity), through a
    # session
    import pandas as pd
    from spark_rapids_tpu import TpuSparkSession, functions as F
    df = pd.DataFrame({
        "k": [i % 5 for i in range(400)],
        "s": [f"v{(i * 7) % 17:03d}" for i in range(400)],
        "x": [float(i % 50) for i in range(400)]})
    s = TpuSparkSession({
        "spark.rapids.tpu.sql.variableFloatAgg.enabled": True})
    got = (s.create_dataframe(df).group_by("k")
           .agg(F.min("s").alias("ms"), F.sum("x").alias("sx"),
                F.first("s").alias("fs"), F.count("*").alias("c"))
           .sort("k")).collect().to_pandas()
    g = df.groupby("k")
    want = pd.DataFrame({
        "k": sorted(df.k.unique()), "ms": g.s.min().values,
        "sx": g.x.sum().values, "fs": g.s.first().values,
        "c": g.size().values})
    assert got.k.tolist() == want.k.tolist()
    assert got.ms.tolist() == want.ms.tolist()
    assert got.sx.tolist() == want.sx.tolist()
    assert got.fs.tolist() == want.fs.tolist()
    assert got.c.tolist() == want.c.tolist()

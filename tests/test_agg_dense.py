"""The aggregate update's dense branch (exec/tpu_aggregate._DenseCtx): a
batch of at most ``_DENSE_MAX_GROUPS`` groups reduces every buffer by
masked dense reductions over the value vectors where they are, instead
of gathering each into key order, scanning it and gathering end
positions.  One ``lax.cond`` on the device's group count picks; the
branch is built only at the capacity ladder's scale and only where every
spec can reduce through a _DenseCtx.

Each case runs the update three ways on the same batch: as built (the
dense branch there), with the branch left out (the sorted form, the
parent's program) and a numpy oracle.  Integer sums, counts and extremes
are exact; a float sum is another association of the same additions."""

import numpy as np
import pyarrow as pa
import pytest

import jax
import jax.numpy as jnp

from spark_rapids_tpu import TpuSparkSession, col, functions as F
from spark_rapids_tpu.columnar.batch import from_arrow, to_arrow
from spark_rapids_tpu.exec import kernel_cache as kc
from spark_rapids_tpu.exec import tpu_aggregate as agg
from spark_rapids_tpu.expr import ir
from spark_rapids_tpu.obs import registry
from tests.parity import assert_tables_equal, with_cpu_session

_CONF = {"spark.rapids.tpu.sql.variableFloatAgg.enabled": True}
_CAP = 1024          # rungs 256 and 512 once the threshold is lowered
_B = agg._DENSE_BLOCK
_G = 2 * _B          # the dense branch's room in these tests: two blocks


@pytest.fixture
def low_gate(monkeypatch):
    """The ladder's gate, and with it the dense branch's, opened at
    suite scale, and the branch's room (``_DENSE_MAX_GROUPS``, 1024 by
    default) cut to what a 900-row table can fill; no program traced
    under another gate is reused."""
    monkeypatch.setattr(agg, "_LADDER_MIN_RUNG", 8)
    monkeypatch.setattr(agg, "_DENSE_MAX_GROUPS", _G)
    kc.clear()
    yield
    kc.clear()


def _counters():
    return dict(registry.get_registry().snapshot()["counters"])


def _moved(before, name):
    return _counters().get(name, 0) - before.get(name, 0)


# -- the update kernel, three ways -------------------------------------

def _bound(batch, e):
    return ir.bind(e, batch.names, [c.dtype for c in batch.columns],
                   [not c.nonnull for c in batch.columns])


def _aggs(batch, value_cols):
    out = [ir.Count(None)]
    for name in value_cols:
        for cls in (ir.Sum, ir.Count, ir.Average, ir.Min, ir.Max):
            out.append(cls(ir.UnresolvedAttribute(name)))
    out = [_bound(batch, a) for a in out]
    for a in out:
        a.resolve()
    return out


_PROGRAMS = {}       # update kernels by what they were traced for


def _update(table, keys, value_cols, thresh=None, dense=True,
            capacity=_CAP):
    """The partial of one update over ``table`` as an Arrow table (the
    padding stripped).  The fused filter is always ``sel > 0`` over a
    shifted ``sel``, so cases of one schema share their two programs
    (as built, and with the dense branch left out)."""
    if thresh is not None:
        shifted = table["sel"].to_numpy() - thresh
        table = table.set_column(table.schema.get_field_index("sel"),
                                 "sel", pa.array(shifted))
    batch = from_arrow(table, capacity=capacity)
    sig = (tuple(batch.names), tuple(
        (str(c.dtype), c.nonnull, c.vbits, tuple(c.data.shape[1:]))
        for c in batch.columns), tuple(keys), tuple(value_cols),
        thresh is not None, dense, capacity, agg._LADDER_MIN_RUNG,
        agg._DENSE_MAX_GROUPS)
    if sig not in _PROGRAMS:
        groupings = [_bound(batch, ir.UnresolvedAttribute(k))
                     for k in keys]
        aggs = _aggs(batch, value_cols)
        specs = [agg.make_spec(a) for a in aggs]
        cond = None if thresh is None else _bound(batch, ir.GreaterThan(
            ir.UnresolvedAttribute("sel"), ir.Literal(0)))
        assert agg._dense_built(batch.capacity, bool(keys), specs), \
            "the gate is open and every spec is dense-capable"

        def update(b):
            real = agg._dense_built
            if not dense:
                agg._dense_built = lambda *a: False
            try:
                return agg.update_aggregate(b, groupings, aggs, specs,
                                            cond)
            finally:
                agg._dense_built = real
        _PROGRAMS[sig] = jax.jit(update)
    part = _PROGRAMS[sig](batch)
    assert part.capacity == batch.capacity
    return to_arrow(part)


def _oracle(table, keys, value_cols, thresh):
    """numpy oracle of the partial's buffers: {key tuple: [count(*),
    then per value column sum, n valid, min, max]}; a key is None where
    null, NaN keys are one group, -0.0 is 0.0."""
    n = table.num_rows
    live = np.ones(n, bool) if thresh is None else \
        table["sel"].to_numpy(zero_copy_only=False) > thresh
    kcols = []
    for k in keys:
        vals = table[k].to_pylist()
        kcols.append([None if v is None else
                      ("nan" if isinstance(v, float) and v != v else
                       (0.0 if v == 0 else v)) for v in vals])
    out = {}
    for i in np.nonzero(live)[0]:
        out.setdefault(tuple(kc_[i] for kc_ in kcols), []).append(i)
    res = {}
    for key, rows in out.items():
        rec = [len(rows)]
        for name in value_cols:
            vals = [table[name][int(i)].as_py() for i in rows]
            vals = [v for v in vals if v is not None]
            is_f = pa.types.is_floating(table[name].type)
            arr = np.array(vals, np.float64 if is_f else np.int64)
            rec.append((arr.sum() if len(arr) else None, len(arr)))
            if not len(arr):
                rec.append((None, None))
            elif is_f:
                nn = arr[~np.isnan(arr)]
                has_nan = len(nn) < len(arr)
                # Spark: NaN is the greatest value
                rec.append((nn.min() if len(nn) else np.nan,
                            np.nan if has_nan else nn.max()))
            else:
                rec.append((arr.min(), arr.max()))
        res[key] = rec
    return res


def _same(a, b, exact):
    if a is None or b is None:
        return a is None and b is None
    if isinstance(a, float) and isinstance(b, float) and \
            (a != a or b != b):
        return a != a and b != b
    if exact or a in (np.inf, -np.inf) or b in (np.inf, -np.inf):
        return a == b
    return abs(a - b) <= 1e-12 * max(abs(a), abs(b), 1e-300)


def _check(table, keys, value_cols, thresh=None, capacity=_CAP,
           groups=None):
    dense = _update(table, keys, value_cols, thresh, True, capacity)
    srt = _update(table, keys, value_cols, thresh, False, capacity)
    assert dense.schema.names == srt.schema.names
    assert dense.num_rows == srt.num_rows        # groups, in key order
    if groups is not None:
        assert dense.num_rows == groups
    for name in dense.schema.names:
        d, s = dense[name].to_pylist(), srt[name].to_pylist()
        is_f = pa.types.is_floating(dense[name].type)
        assert dense[name].type == srt[name].type, name
        for x, y in zip(d, s):
            assert _same(x, y, not is_f), (name, d, s)

    want = _oracle(table, keys, value_cols, thresh)
    assert dense.num_rows == len(want)
    nk = len(keys)
    for r in range(dense.num_rows):
        key = []
        for i in range(nk):
            v = dense[f"__k{i}"][r].as_py()
            key.append(None if v is None else
                       ("nan" if isinstance(v, float) and v != v else v))
        rec = want[tuple(key)]
        assert dense["__a0_0"][r].as_py() == rec[0]
        ai = 1
        for vi, name in enumerate(value_cols):
            (sm, cnt), (mn, mx) = rec[1 + 2 * vi], rec[2 + 2 * vi]
            is_f = pa.types.is_floating(table[name].type)

            def got(a, b):
                return dense[f"__a{a}_{b}"][r].as_py()
            # sum: (s, valid iff a value), its count; count; avg's
            # (sum f64, count); min; max
            assert _same(got(ai, 0), None if sm is None else
                         (float(sm) if is_f else int(sm)), not is_f)
            assert got(ai, 1) == cnt and got(ai + 1, 0) == cnt
            assert _same(got(ai + 2, 0), float(sm) if cnt else 0.0,
                         False)
            assert got(ai + 2, 1) == cnt
            assert _same(got(ai + 3, 0), None if mn is None else
                         (float(mn) if is_f else int(mn)), True)
            assert _same(got(ai + 4, 0), None if mx is None else
                         (float(mx) if is_f else int(mx)), True)
            ai += 5
    return dense


def _table(n, n_groups, seed=0, key="int", specials=False,
           null_keys=False, null_values=False, all_null_group=False):
    rng = np.random.default_rng(seed + 7 * n_groups)
    g = rng.integers(0, max(n_groups, 1), n)
    if n >= n_groups:
        g[:n_groups] = np.arange(n_groups)       # every group present
    f = rng.normal(size=n) * 1e3
    if specials:
        f[rng.random(n) < 0.05] = np.nan
        f[rng.random(n) < 0.03] = np.inf
        f[rng.random(n) < 0.03] = -np.inf
        f[rng.random(n) < 0.05] = -0.0
    fmask = (rng.random(n) < 0.2) if null_values else np.zeros(n, bool)
    if all_null_group:
        fmask = fmask | (g == 0)
    sel = rng.integers(-9, 9, n)
    sel[:n_groups] = 8           # no filter below empties a group
    cols = {
        "sel": pa.array(sel, type=pa.int64()),
        "i32": pa.array(rng.integers(-2**31, 2**31 - 1, n).astype(
            np.int32), mask=fmask),
        "i64": pa.array(rng.integers(-2**50, 2**50, n), mask=fmask),
        "f64": pa.array(f, mask=fmask),
    }
    kmask = (rng.random(n) < 0.15) if null_keys else None
    if key == "int":
        cols["k"] = pa.array(g * 3 - 4, type=pa.int64(), mask=kmask)
    elif key == "str":
        cols["k"] = pa.array(np.array(
            [f"g{i:03d}" for i in range(max(n_groups, 1))])[g],
            mask=kmask)
    elif key == "float":
        pool = np.array([np.nan, 0.0, -0.0, 1.5, -np.inf, 2.5, 3.5, 4.5,
                         5.5, 6.5, 7.5, 8.5])[:max(n_groups, 1) + 1]
        cols["k"] = pa.array(pool[g], mask=kmask)
    return pa.table(cols)


_VALUES = ["i32", "i64", "f64"]

_CASES = {
    # group counts: none, one, Q1's four, the most the branch takes,
    # one more (that batch takes the sorted form)
    "groups0_filter_keeps_nothing": dict(n=900, n_groups=3, thresh=100,
                                         groups=0),
    "groups1": dict(n=900, n_groups=1, groups=1),
    "groups4": dict(n=900, n_groups=4, groups=4),
    "groups_max": dict(n=900, n_groups=_G, groups=_G),
    "groups_max_plus_1_takes_sorted": dict(n=900, n_groups=_G + 1,
                                           groups=_G + 1),
    "groups4_fused_filter": dict(n=900, n_groups=4, thresh=1, groups=4),
    "groups_max_fused_filter": dict(n=900, n_groups=_G, thresh=-5,
                                    groups=_G),
    "groups_max_plus_1_fused_filter": dict(n=900, n_groups=_G + 1,
                                           thresh=-5, groups=_G + 1),
    # nulls
    "null_keys": dict(n=700, n_groups=3, null_keys=True, groups=4),
    "null_keys_fused_filter": dict(n=700, n_groups=3, null_keys=True,
                                   thresh=0, groups=4),
    "null_values": dict(n=700, n_groups=4, null_values=True, thresh=-3),
    "all_null_group": dict(n=700, n_groups=3, all_null_group=True,
                           thresh=-8, groups=3),
    # float specials in the values
    "nan_inf_negzero_values": dict(n=900, n_groups=4, specials=True),
    "nan_inf_negzero_values_fused_filter": dict(
        n=900, n_groups=4, specials=True, null_values=True, thresh=0),
    # the filter's extremes
    "filter_keeps_one_row": dict(n=900, n_groups=4, thresh="one",
                                 groups=1),
    "filter_keeps_every_row": dict(n=900, n_groups=4, thresh=-100,
                                   groups=4),
    # each rung of the ladder (live rows 50 of 256, ~350 of 512, 900)
    "rung_quarter": dict(n=900, n_groups=4, thresh=7),
    "rung_half": dict(n=900, n_groups=4, thresh=1),
    "rung_full": dict(n=900, n_groups=4, thresh=-10),
    "rung_quarter_host_count": dict(n=200, n_groups=4),
    "rung_half_host_count": dict(n=400, n_groups=4),
    # keys: strings (Q1's), floats with NaN and -0.0, two keys
    "string_key": dict(n=900, n_groups=4, key="str", thresh=0, groups=4),
    "float_key_nan_negzero": dict(n=900, n_groups=5, key="float",
                                  thresh=-4),
    "two_keys": dict(n=900, n_groups=2, keys=["k", "k2"], thresh=-2,
                     groups=4),
    "one_row_batch": dict(n=1, n_groups=1, groups=1),
}


@pytest.mark.parametrize("case", list(_CASES))
def test_dense_update_equals_sorted_and_oracle(low_gate, case):
    c = dict(_CASES[case])
    thresh = c.pop("thresh", None)
    groups = c.pop("groups", None)
    keys = c.pop("keys", ["k"])
    t = _table(c.pop("n"), c.pop("n_groups"), seed=len(case), **c)
    if "k2" in keys:
        k2 = np.arange(t.num_rows) % 2
        t = t.append_column("k2", pa.array(k2.astype(np.int32)))
    if thresh == "one":
        sel = np.full(t.num_rows, -9, np.int64)
        sel[t.num_rows // 2] = 5
        t = t.set_column(t.schema.get_field_index("sel"), "sel",
                         pa.array(sel))
        thresh = 0
    _check(t, keys, _VALUES, thresh, groups=groups)


@pytest.mark.parametrize("n_groups", [_B - 1, _B, _B + 1, 2 * _B + 3,
                                      4 * _B, 4 * _B + 1])
def test_the_dense_reduce_walks_only_the_blocks_the_groups_reach(
        low_gate, monkeypatch, n_groups):
    """With room for four blocks of ``_DENSE_BLOCK`` slots the dense
    reduce walks ceil(n / block) of them; one group more than the room
    takes the sorted form."""
    monkeypatch.setattr(agg, "_DENSE_MAX_GROUPS", 4 * _B)
    t = _table(900, n_groups, seed=1, null_values=True, specials=True)
    _check(t, ["k"], ["i64", "f64"], thresh=-6, groups=n_groups,
           capacity=4 * _CAP)


@pytest.mark.parametrize("n_groups", [agg._DENSE_MAX_GROUPS,
                                      agg._DENSE_MAX_GROUPS + 1])
def test_the_default_room(monkeypatch, n_groups):
    """The constant as shipped (only the ladder's gate is lowered): a
    batch that fills the room reduces densely and equals the sorted form
    and the oracle; one group more takes the sorted form.  Seen through
    a dense context whose counts come out 1000 too high."""
    room = agg._DENSE_MAX_GROUPS
    monkeypatch.setattr(agg, "_LADDER_MIN_RUNG", 8)
    t = _table(3 * room, n_groups, seed=4, null_values=True)
    _check(t, ["k"], ["f64"], thresh=-3, groups=n_groups,
           capacity=8 * room)
    real = agg._DenseCtx.seg_count
    monkeypatch.setattr(agg._DenseCtx, "seg_count",
                        lambda self, mask: real(self, mask) + 1000)
    part = _update(t, ["k"], ["i64"], capacity=8 * room)
    assert part["__a0_0"].to_numpy().sum() == \
        3 * room + (1000 * n_groups if n_groups <= room else 0)


# -- which branch the device takes ---------------------------------------

@pytest.mark.parametrize("n_groups,dense", [(1, True), (_G, True),
                                            (_G + 1, False)])
@pytest.mark.parametrize("fused", [False, True], ids=["batch", "fused"])
def test_group_count_on_the_device_picks_the_branch(
        low_gate, monkeypatch, n_groups, dense, fused):
    """A dense context whose counts come out 1000 too high: the answer
    is off by that exactly where the dense branch ran."""
    real = agg._DenseCtx.seg_count
    monkeypatch.setattr(agg._DenseCtx, "seg_count",
                        lambda self, mask: real(self, mask) + 1000)
    t = _table(900, n_groups, seed=2)
    # (its own program: this seg_count is traced into it)
    part = _update(t, ["k"], ["i64"], -100 if fused else None,
                   capacity=2 * _CAP)
    counts = part["__a0_0"].to_numpy()
    assert len(counts) == n_groups and counts.sum() == \
        900 + (1000 * n_groups if dense else 0)


def _lowered(table, aggs_of, keys=("k",), thresh=None, capacity=_CAP):
    batch = from_arrow(table, capacity=capacity)
    groupings = [_bound(batch, ir.UnresolvedAttribute(k)) for k in keys]
    aggs = [_bound(batch, a) for a in aggs_of]
    for a in aggs:
        a.resolve()
    specs = [agg.make_spec(a) for a in aggs]
    cond = None if thresh is None else _bound(batch, ir.GreaterThan(
        ir.UnresolvedAttribute("sel"), ir.Literal(thresh)))
    text = jax.jit(lambda b: agg.update_aggregate(
        b, groupings, aggs, specs, cond)).lower(batch).as_text()
    return text, specs, batch.capacity


@pytest.mark.parametrize("what,builds", [
    ("numeric_min", True), ("string_min", False), ("string_max", False),
    ("first", False), ("last", False), ("global", False)])
def test_a_spec_the_dense_form_cannot_reduce_builds_no_branch(
        low_gate, what, builds):
    t = _table(300, 3, key="str").append_column(
        "s", pa.array([f"v{i % 7}" for i in range(300)]))
    a = {"numeric_min": ir.Min(ir.UnresolvedAttribute("f64")),
         "string_min": ir.Min(ir.UnresolvedAttribute("s")),
         "string_max": ir.Max(ir.UnresolvedAttribute("s")),
         "first": ir.First(ir.UnresolvedAttribute("i64")),
         "last": ir.Last(ir.UnresolvedAttribute("i64")),
         "global": ir.Sum(ir.UnresolvedAttribute("f64"))}[what]
    keys = () if what == "global" else ("k",)
    # a fused filter's count is traced: the ladder is one switch of
    # three rungs, and each rung holds the dense cond or does not
    text, specs, cap = _lowered(
        t, [ir.Count(None), ir.Sum(ir.UnresolvedAttribute("i32")), a],
        keys, thresh=0)
    assert agg._dense_built(cap, bool(keys), specs) is builds
    assert text.count("stablehlo.case") == (4 if builds else 1)


# -- below the gate nothing changes --------------------------------------

@pytest.mark.parametrize("cap", [64, 256, 1 << 16, 1 << 19])
def test_below_the_gate_the_update_is_the_program_it_was(monkeypatch,
                                                         cap):
    """At the default gate a batch under 1,048,576 rows (TPC-DS q3's
    aggregate runs at 64-256) builds neither the ladder nor the dense
    branch: the lowered text has no branch at all and is the text with
    the dense code unreachable, so such programs compile nothing new."""
    t = _table(40, 4, key="str")
    aggs = [ir.Count(None), ir.Sum(ir.UnresolvedAttribute("f64")),
            ir.Average(ir.UnresolvedAttribute("f64")),
            ir.Min(ir.UnresolvedAttribute("i32"))]
    text, specs, got_cap = _lowered(t, aggs, thresh=-1, capacity=cap)
    assert got_cap == cap and not agg._dense_built(cap, True, specs)
    assert text.count("stablehlo.case") == 0
    assert "stablehlo.if" not in text

    def unreachable(*a, **k):
        raise AssertionError("dense code reached below the gate")
    monkeypatch.setattr(agg, "_dense_ctx", unreachable)
    monkeypatch.setattr(agg, "_dense_built", lambda *a: False)
    again, _, _ = _lowered(t, aggs, thresh=-1, capacity=cap)
    assert again == text


def test_default_gate_builds_the_branch_at_a_million_rows():
    specs = [agg.make_spec(ir.Count(None))]
    assert not agg._dense_built((1 << 20) - 1, True, specs)
    assert agg._dense_built(1 << 20, True, specs)
    assert not agg._dense_built(1 << 20, False, specs)    # global


# -- the counters, from the read that is there already ------------------

def _q(t, n_aggs="q1", thresh=None, keys=("k",)):
    def q(s):
        df = s.create_dataframe(t)
        if thresh is not None:
            df = df.filter(col("sel") > thresh)
        if not keys:
            return df.agg(F.sum("f64").alias("s"))
        if n_aggs == "first":
            return df.group_by(*keys).agg(F.first("i64").alias("fi"))
        return df.group_by(*keys).agg(
            F.count("*").alias("c"), F.sum("f64").alias("sf"),
            F.avg("f64").alias("af"), F.sum("i64").alias("si"),
            F.min("i32").alias("mn"), F.max("f64").alias("mx"))
    return q


@pytest.mark.parametrize("what,dense,srt", [
    ("groups4", 1, 0), ("groups4_fused", 1, 0), ("groups_max", 1, 0),
    ("groups_max_plus_1", 0, 1), ("first_builds_no_branch", 0, 1),
    ("global_counts_nothing", 0, 0)])
def test_counters_ride_the_partials_one_read(low_gate, what, dense, srt):
    n_groups = {"groups_max": _G, "groups_max_plus_1": _G + 1}.get(what, 4)
    t = _table(900, n_groups, seed=5, null_values=True)
    q = _q(t, "first" if what.startswith("first") else "q1",
           0 if what.endswith("fused") else None,
           () if what.startswith("global") else ("k",))
    cpu = with_cpu_session(lambda s: q(s).collect())
    before = _counters()
    out = q(TpuSparkSession(_CONF)).collect()
    assert _moved(before, "agg.update.dense") == dense
    assert _moved(before, "agg.update.sorted") == srt
    assert _moved(before, "device.reads.agg.countWait") == \
        (0 if what.startswith("global") else 1)
    if not what.startswith("first"):
        assert_tables_equal(cpu, out, ignore_order=True,
                            approx_float=True)


def test_below_the_gate_neither_counter_moves():
    t = _table(900, 4, seed=6)
    before = _counters()
    out = _q(t)(TpuSparkSession(_CONF)).collect()
    assert out.num_rows == 4
    assert _moved(before, "device.reads.agg.countWait") == 1
    assert _moved(before, "agg.update.dense") == 0
    assert _moved(before, "agg.update.sorted") == 0


def test_dense_ctx_group_ids_in_original_row_space():
    """Rows the sort never saw (filtered, padding) belong to no slot."""
    order = jnp.asarray([5, 2, 7, 0, 1, 3, 4, 6], jnp.int32)
    ctx = agg._SortedCtx(
        order=order, new=None,
        gid_sorted=jnp.asarray([0, 0, 1, 0, 0, 0, 0, 0], jnp.int32),
        start_pos=None, end_pos=None,
        sorted_mask=jnp.arange(8) < 3, cap=8,
        row_mask=jnp.ones((8,), bool), n_groups=jnp.int32(2))
    d = agg._dense_ctx(ctx, 4)
    assert d.cap == 4 and int(d.n_groups) == 2
    assert np.asarray(d.gid).tolist() == [4, 4, 0, 4, 4, 0, 4, 1]
    assert np.asarray(d.seg_count(d.row_mask)).tolist() == [2, 1, 0, 0]

"""``exec/scans.py``'s 64-bit prefix scans against a numpy oracle, bit
for bit.

``seg_scan`` and ``cumsum`` run wide dtypes as a ``lax.scan`` over
blocks of ``_BLOCK`` rows: one ``associative_scan`` a block, the running
prefix carried from block to block.  A float result depends on that
order (the left fold of the carries, and the recursion's pairing inside
a block), and every float64 SUM the aggregate returns goes through it,
so the order is pinned here: the oracle walks the blocks one after the
other in numpy and pairs inside a block as ``lax.associative_scan`` is
documented to (reduce neighbouring pairs, scan the halves, interleave).
A change of the block size, of the blocks' order or of the pairing
moves bits and fails these tests.  NaN compares equal to NaN whatever
its sign bit (``inf - inf`` has none that is specified); everything
else compares by its 64 bits, so -0.0 is not 0.0.

Rows: one row past a block (32,769), two whole blocks (65,536) and
thirty-two (1,048,576); no segment starts on a block's first row, so
segments cross every block edge.
"""

from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from spark_rapids_tpu import TpuSparkSession  # noqa: F401  (x64 on)
from spark_rapids_tpu.exec import scans

_B = scans._BLOCK
_ROWS = [_B + 1, 2 * _B, 32 * _B]
_OPS = {"add": (np.add, jnp.add), "minimum": (np.minimum, jnp.minimum),
        "maximum": (np.maximum, jnp.maximum)}


# ---------------------------------------------------------------------------
# the oracle
# ---------------------------------------------------------------------------

def _pairwise_scan(combine, elems):
    """Inclusive scan of a tuple of arrays in ``associative_scan``'s
    pairing: combine neighbours, scan the n/2 results, fill in the even
    positions from them."""
    n = elems[0].shape[0]
    if n < 2:
        return elems
    reduced = combine(tuple(e[0:-1:2] for e in elems),
                      tuple(e[1::2] for e in elems))
    odd = _pairwise_scan(combine, reduced)
    rest = tuple(e[2::2] for e in elems)
    even = combine(tuple(o[0:-1] for o in odd) if n % 2 == 0 else odd,
                   rest)
    out = []
    for e, ev, od in zip(elems, even, odd):
        o = np.empty(n, e.dtype)
        o[0] = e[0]
        o[2::2] = ev
        o[1::2] = od
        out.append(o)
    return tuple(out)


def _blocks(x, fill):
    g = -(-x.shape[0] // _B)
    pad = np.full(g * _B - x.shape[0], fill, dtype=x.dtype)
    return np.concatenate([x, pad]).reshape(g, _B)


def _seg_scan_oracle(op, flags, vals, identity):
    def combine(a, b):
        (fa, va), (fb, vb) = a, b
        return fa | fb, np.where(fb, vb, op(va, vb))

    n = vals.shape[0]
    if vals.dtype.itemsize < 8 or n <= _B:
        return _pairwise_scan(combine, (flags, vals))[1]
    carry = (np.zeros((), bool), np.full((), identity, vals.dtype))
    rows = []
    for f, v in zip(_blocks(flags, True), _blocks(vals, identity)):
        pf, pv = _pairwise_scan(combine, (f, v))
        of, ov = combine((np.broadcast_to(carry[0], pf.shape),
                          np.broadcast_to(carry[1], pv.shape)), (pf, pv))
        carry = (of[-1], ov[-1])
        rows.append(ov)
    return np.concatenate(rows)[:n]


def _cumsum_oracle(x):
    def combine(a, b):
        return (a[0] + b[0],)

    n = x.shape[0]
    if x.dtype.itemsize < 8:
        return np.cumsum(x, dtype=x.dtype)
    if n <= _B:
        return _pairwise_scan(combine, (x,))[0]
    carry = np.zeros((), x.dtype)
    rows = []
    for row in _blocks(x, 0):
        s = _pairwise_scan(combine, (row,))[0] + carry
        carry = s[-1]
        rows.append(s)
    return np.concatenate(rows)[:n]


# ---------------------------------------------------------------------------
# data and comparison
# ---------------------------------------------------------------------------

def _values(rng, n, np_t, finite=False):
    if np.dtype(np_t).kind != "f":
        info = np.iinfo(np_t)
        # sums wrap: two's complement, the same in numpy and on device
        return rng.integers(info.min // 4, info.max // 4, n).astype(np_t)
    vals = rng.uniform(-1e6, 1e6, n).astype(np_t)
    at = rng.choice(n, 40, replace=False)
    if not finite:
        vals[at[:4]] = np.nan
        vals[at[4:8]] = np.inf
        vals[at[8:12]] = -np.inf
    vals[at[12:26]] = -0.0
    vals[at[26:]] = 0.0
    return vals


def _flags(rng, n):
    """A segment every 700 rows or so, none starting where a block
    does: every block edge is inside a segment."""
    flags = np.zeros(n, bool)
    flags[rng.choice(n, max(n // 700, 8), replace=False)] = True
    flags[::_B] = False
    flags[0] = True
    return flags


def _identity(name, np_t):
    if name == "add":
        return np_t(0)
    if np.dtype(np_t).kind == "f":
        return np_t(np.inf if name == "minimum" else -np.inf)
    info = np.iinfo(np_t)
    return np_t(info.max if name == "minimum" else info.min)


def _assert_same_bits(got, want):
    assert got.dtype == want.dtype and got.shape == want.shape
    if got.dtype.kind == "f":
        nan = np.isnan(want)
        assert np.array_equal(np.isnan(got), nan)
        got, want = got[~nan], want[~nan]
    bits = np.dtype(f"u{got.dtype.itemsize}")
    diff = np.flatnonzero(got.view(bits) != want.view(bits))
    assert diff.size == 0, (
        f"{diff.size} rows differ, the first at {diff[0]}: "
        f"{got[diff[0]]!r} against {want[diff[0]]!r}")


def _run_seg_scan(name, np_t, n, seed):
    np_op, jnp_op = _OPS[name]
    rng = np.random.default_rng(seed)
    vals, flags = _values(rng, n, np_t), _flags(rng, n)
    identity = _identity(name, np_t)
    with np.errstate(all="ignore"):
        want = _seg_scan_oracle(np_op, flags, vals, identity)
    got = jax.jit(partial(scans.seg_scan, jnp_op, identity=identity))(
        jnp.asarray(flags), jnp.asarray(vals))
    _assert_same_bits(np.asarray(got), want)
    return flags, vals, want


# ---------------------------------------------------------------------------
# tests
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", _ROWS)
@pytest.mark.parametrize("np_t", [np.float64, np.int64],
                         ids=["float64", "int64"])
@pytest.mark.parametrize("name", list(_OPS))
def test_seg_scan_blocked(name, np_t, n):
    flags, vals, want = _run_seg_scan(name, np_t, n, seed=n % 251)
    # the oracle itself, where the answer does not hang on the order:
    # a segment's last row holds its minimum or maximum
    if name != "add" and np.dtype(np_t).kind != "f":
        starts = np.flatnonzero(flags)
        ends = np.append(starts[1:], n) - 1
        red = (np.minimum if name == "minimum" else np.maximum).reduceat(
            vals, starts)
        assert np.array_equal(want[ends], red)


@pytest.mark.parametrize("np_t", [np.float32, np.int32],
                         ids=["float32", "int32"])
def test_seg_scan_narrow_dtype_is_one_scan(np_t):
    # under 8 bytes an element there is no block loop at any size
    _run_seg_scan("add", np_t, 2 * _B, seed=3)


@pytest.mark.parametrize("np_t", [np.float64, np.int64],
                         ids=["float64", "int64"])
def test_seg_scan_within_one_block(np_t):
    _run_seg_scan("add", np_t, _B, seed=4)


@pytest.mark.parametrize("n", _ROWS)
@pytest.mark.parametrize("np_t", [np.float64, np.int64],
                         ids=["float64", "int64"])
def test_cumsum_blocked(np_t, n):
    # finite but for the last rows: a NaN ends what a running sum
    # over all rows can show
    vals = _values(np.random.default_rng(n % 241), n, np_t, finite=True)
    if np.dtype(np_t).kind == "f":
        vals[0] = -0.0
        vals[-3:] = [np.inf, -np.inf, 1.0]
    with np.errstate(all="ignore"):
        want = _cumsum_oracle(vals)
    got = np.asarray(jax.jit(scans.cumsum)(jnp.asarray(vals)))
    _assert_same_bits(got, want)
    if np.dtype(np_t).kind != "f":
        assert np.array_equal(want, np.cumsum(vals, dtype=np_t))


def test_cumsum_narrow_dtype_is_jnp_cumsum():
    vals = np.random.default_rng(5).integers(-1000, 1000, 2 * _B
                                             ).astype(np.int32)
    got = np.asarray(jax.jit(scans.cumsum)(jnp.asarray(vals)))
    _assert_same_bits(got, _cumsum_oracle(vals))

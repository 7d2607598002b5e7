"""TPU hash-aggregate exec.

Analog of ``GpuHashAggregateExec`` (reference: aggregate.scala:302-997):
per-batch *update* aggregation, buffered partial results, concat, *merge*
aggregation, then a final projection — the exact three-phase flow of the
reference (see comments at aggregate.scala:326-421), with cudf's
``Table.groupBy.aggregate`` replaced by a TPU-friendly sort-based segmented
reduction:

  1. encode grouping keys to total-order uint64 keys (exec/sortkeys.py)
  2. one stable ``jnp.lexsort`` brings equal keys adjacent
  3. group boundaries -> segment ids; ``jax.ops.segment_{sum,min,max}``
     computes every aggregate in fixed-shape space
  4. group count is the only host sync (the new batch's num_rows)

Aggregate functions follow the reference's update/merge pair structure
(reference: AggregateFunctions.scala:531 — each ``CudfAggregate`` declares
updateAggregate and mergeAggregate).  NaN/-0.0 key canonicalization matches
Spark's NormalizeFloatingNumbers semantics (parity-critical).

Two forms of the update's segment reduction, one chosen a batch ON THE
DEVICE by the group count the key sort has just produced
(``update_aggregate``: one ``lax.cond`` on ``ctx.n_groups``; no conf):

  * sorted (_SortedCtx): each value vector is gathered into key order,
    scanned, and read at the groups' end positions.  Its cost does not
    depend on the group count, so it is the form for many groups, and
    the only form of the merge, of string ``min``/``max`` and of
    ``first``/``last``.
  * dense (_DenseCtx), for a batch of at most ``_DENSE_MAX_GROUPS``
    groups: the keys are still sorted, the VALUES NEVER MOVE.  One
    set-scatter carries the sorted group ids back to original row
    space, and every buffer is one masked dense reduce a group slot
    over the vector where it is; the group keys' representatives are
    gathered at slot count, not at capacity.  Low-cardinality GROUP BY
    (flags, status, category) is the commonest reporting shape, and
    TPC-H Q1's four groups paid a sort's worth of gathers: 12 value and
    mask gathers into key order and 12 capacity-long end-position
    gathers, 0.71 s of each 1.49-s update, which now takes 0.45 s (one
    v5e chip; PERF.md §5 and §6, PR 31).

The dense branch is built only for a grouped update at the capacity
ladder's scale (``_dense_built``): below it a program's text is what it
was, so small suites and small batches compile nothing further.
``_shrink_partials`` counts which form each such update took
(``agg.update.dense`` / ``agg.update.sorted``) from the read it makes
anyway.  A float sum's association differs between the forms (a tree
over rows against a left fold of blocks): its last bits, not its
precision; integer sums, counts, ``min`` and ``max`` are exact in both.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from spark_rapids_tpu import dtypes as dt
from spark_rapids_tpu.columnar.batch import (DeviceBatch, DeviceColumn,
                                             bucket_rows, concat_batches,
                                             read_host)
from spark_rapids_tpu.exec.base import PhysicalPlan, TpuExec, timed
from spark_rapids_tpu.exec import scans, sortkeys
from spark_rapids_tpu.expr import eval_tpu, ir
from spark_rapids_tpu.expr.eval_tpu import ColVal
from spark_rapids_tpu.plan.logical import Schema

_BIG = np.int64(1 << 62)

# capacity ladder engages only when cap/4 reaches this rung size: below
# it the extra branches' compile time would dominate small-batch suites
# (tests may lower it to cover every branch)
_LADDER_MIN_RUNG = 1 << 18

# an update whose batch holds at most this many groups reduces in
# original row space (_DenseCtx); the branch is built only where the
# ladder engages, so programs below that gate keep their text.  Set from
# one chip reading (PERF.md §6, PR 31: the update of a Q1-shaped batch
# of n groups, as built and with the branch left out, one v5e chip): the
# largest power of two at which the dense form costs at most half the
# sorted one, 261.5 against 707.8 ms at 1024 and 445.2 against 703.7 at
# 2048 (81.6 against 818.4 at Q1's own four groups)
_DENSE_MAX_GROUPS = 1024
# group slots a dense pass reduces at once: the six reductions of a
# Q1 batch cost the same for 4, 8 and 16 slots (4.2 ms) and more from
# 32 on (6.5), so 16 is the widest block that is free for few groups
_DENSE_BLOCK = 16


@dataclass
class _SortedCtx:
    """Sorted-space grouping context shared by all aggregate updates in
    one kernel.

    Rows are ordered by grouping key (stable LSD radix over packed
    digits, sortkeys.radix_order_digits) so equal keys are adjacent and
    every segment reduction becomes SCATTER-FREE dense work: a masked
    take into sorted order, a cumsum or segmented associative scan, and
    one gather at group-end positions.  Measured on the bench chip,
    dynamic scatter-adds run ~7x slower than gathers (~290 ms vs ~40 ms
    per 4M elements), which made the round-3 scatter-based
    segment_sum formulation the whole aggregate cost."""

    order: jnp.ndarray        # sorted row order (original indices)
    new: jnp.ndarray          # sorted space: row starts a new group
    gid_sorted: jnp.ndarray   # group id per sorted row
    start_pos: jnp.ndarray    # [cap] sorted-space first row of group g
    end_pos: jnp.ndarray      # [cap] sorted-space last row of group g
    sorted_mask: jnp.ndarray  # sorted-space "row exists"
    cap: int
    row_mask: jnp.ndarray     # original-space "row exists"
    n_groups: jnp.ndarray     # scalar
    # narrow fast path: the fully-packed sorted u32 key, and (when the
    # single key is invertibly encoded) its (vbits, nullable, dtype)
    # layout — lets gather_group_keys reconstruct representative keys
    # arithmetically instead of through original-row gathers
    sorted_key: Optional[jnp.ndarray] = None
    key_inverse: Optional[Tuple] = None

    # -- scatter-free segment reductions -------------------------------
    #
    # Cost discipline (all numbers measured on the bench chip, see
    # PERF.md): gathers dominate — ~7.6 ms per 1M u32/i32/f64 lookups
    # and 3x that for x64-emulated i64 — so every reduction pre-masks
    # in ORIGINAL row space (dense elementwise, ~1 ms per 4M) and pays
    # exactly ONE value gather into sorted space; i64 end-position
    # gathers are narrowed to i32 whenever a vbits hint bounds the sum.
    # Even so a reduction costs two capacity-long gathers a 32-bit
    # half (in, and ``end_pos`` out) whatever the group count: for a
    # handful of groups _DenseCtx below reduces without either, and
    # update_aggregate takes it when ``n_groups`` allows.  This form
    # stays for every batch of more groups, where a pass a group would
    # cost more than the gathers.
    def take_sorted(self, x: jnp.ndarray) -> jnp.ndarray:
        return jnp.take(x, self.order, axis=0)

    def seg_sum(self, x: jnp.ndarray, mask: jnp.ndarray,
                out_np=None, narrow_bits: Optional[int] = None
                ) -> jnp.ndarray:
        """Per-group sum over rows where mask (both original space).

        ``x`` stays in its input dtype through the gather (narrow
        gathers are 3x cheaper than emulated-i64 ones) and widens to
        ``out_np`` after.  Integers use global cumsum + end-position
        differences (exact under two's-complement wraparound); a
        ``narrow_bits`` hint with narrow_bits+log2(cap) <= 31 keeps the
        whole chain in native i32.  Floats use the segmented scan: a
        global float cumsum would leak +/-inf and rounding error across
        group boundaries through the differences."""
        out_np = out_np or x.dtype
        if jnp.issubdtype(jnp.dtype(out_np), jnp.floating):
            # cast before the gather: f64 gathers are native-cheap while
            # i64 ones pay the pair emulation (and per-element casts
            # commute with the gather)
            xm = jnp.where(mask, x.astype(out_np),
                           jnp.zeros((), out_np))
            return jnp.take(
                scans.seg_scan(jnp.add, self.new,
                               self.take_sorted(xm), 0), self.end_pos)
        narrow = (narrow_bits is not None and
                  narrow_bits + max(self.cap - 1, 1).bit_length() <= 31)
        if narrow:
            xm = jnp.where(mask, x, jnp.zeros((), x.dtype)
                           ).astype(jnp.int32)
            c = jnp.cumsum(self.take_sorted(xm))
        else:
            xm = jnp.where(mask, x, jnp.zeros((), x.dtype))
            c = scans.cumsum(self.take_sorted(xm).astype(out_np))
        ce = jnp.take(c, self.end_pos)
        return (ce - jnp.concatenate([ce[:1] * 0, ce[:-1]])
                ).astype(out_np)

    def seg_count(self, mask: jnp.ndarray) -> jnp.ndarray:
        # counts fit int32 (cap < 2^31): the native 32-bit cumsum skips
        # the blocked 64-bit scan entirely; widen at the end
        if mask is self.row_mask:   # COUNT(*): already have it sorted
            xs = self.sorted_mask.astype(jnp.int32)
        else:
            xs = self.take_sorted(mask).astype(jnp.int32)
        c = jnp.cumsum(xs)
        ce = jnp.take(c, self.end_pos)
        return (ce - jnp.concatenate([ce[:1] * 0, ce[:-1]])
                ).astype(jnp.int64)

    def seg_scan_reduce(self, x_sorted: jnp.ndarray, op,
                        identity) -> jnp.ndarray:
        """Segmented reduce via associative scan over sorted rows; the
        caller pre-fills excluded rows with op's identity (also passed
        here so the capacity-blocked scan can pad with it)."""
        return jnp.take(
            scans.seg_scan(op, self.new, x_sorted, identity),
            self.end_pos)

    def seg_min_of(self, x: jnp.ndarray, mask: jnp.ndarray,
                   fill) -> jnp.ndarray:
        return self._seg_extreme(x, mask, fill, jnp.minimum)

    def seg_max_of(self, x: jnp.ndarray, mask: jnp.ndarray,
                   fill) -> jnp.ndarray:
        return self._seg_extreme(x, mask, fill, jnp.maximum)

    def _seg_extreme(self, x, mask, fill, op) -> jnp.ndarray:
        xm = jnp.where(mask, x, jnp.asarray(fill, dtype=x.dtype))
        return jnp.take(
            scans.seg_scan(op, self.new, self.take_sorted(xm), fill),
            self.end_pos)


@dataclass
class _DenseCtx:
    """Original-row-space grouping context for a batch of FEW groups:
    the same reductions as _SortedCtx (the specs cannot tell them
    apart), with the values left where they are.

    ``gid`` names each value row's group (key order, as
    ``gid_sorted``), ``cap`` where the row is in none; a reduction is
    a masked dense reduce over the whole vector, ``_DENSE_BLOCK`` group
    slots a pass, and its result is ``[cap]`` long, ``cap`` being the
    slot count (``_DENSE_MAX_GROUPS``) and not a row capacity.  No
    value is gathered into key order, nothing is scanned, no
    ``[capacity]`` end-position gather fetches a handful of numbers.
    The cost grows with groups x rows, which is why the sorted form
    stays for every batch of more groups (update_aggregate picks by
    ``n_groups``)."""

    gid: jnp.ndarray          # [rows] original-space group id
    cap: int                  # group slots
    row_mask: jnp.ndarray     # original-space "row exists"
    n_groups: jnp.ndarray     # scalar, <= cap

    def _reduce(self, x, mask, fill, red) -> jnp.ndarray:
        """[cap]: ``red`` over the rows of each group slot, ``fill``
        standing in for rows outside it or not under ``mask`` (rows
        outside ``row_mask`` carry no group id).  One pass over ``x`` a
        block of ``_DENSE_BLOCK`` slots, and only the blocks the
        batch's groups reach: four groups cost one pass whatever
        ``cap`` is; slots past the last block keep ``fill``."""
        fill = jnp.asarray(fill, dtype=x.dtype)
        blk = min(_DENSE_BLOCK, self.cap)
        lanes = jnp.arange(blk, dtype=jnp.int32)[:, None]

        def block(b, out):
            member = (self.gid[None, :] - b * blk) == lanes
            if mask is not self.row_mask:
                member = member & mask[None, :]
            return jax.lax.dynamic_update_slice(
                out, red(jnp.where(member, x[None, :], fill), axis=1),
                (b * blk,))

        return jax.lax.fori_loop(
            0, (self.n_groups + blk - 1) // blk, block,
            jnp.full((self.cap,), fill))

    def seg_sum(self, x: jnp.ndarray, mask: jnp.ndarray,
                out_np=None, narrow_bits: Optional[int] = None
                ) -> jnp.ndarray:
        """As _SortedCtx.seg_sum, in ``out_np`` throughout (integers
        exact under wraparound, native i32 under the same
        ``narrow_bits`` bound); a float sum is a tree over the rows
        where the sorted form folds blocks left to right, so its last
        bits differ and its precision does not."""
        out_np = out_np or x.dtype
        rows = self.gid.shape[0]
        narrow = (not jnp.issubdtype(jnp.dtype(out_np), jnp.floating) and
                  narrow_bits is not None and
                  narrow_bits + max(rows - 1, 1).bit_length() <= 31)
        acc = jnp.int32 if narrow else out_np
        return self._reduce(x.astype(acc), mask, 0,
                            functools.partial(jnp.sum, dtype=acc)
                            ).astype(out_np)

    def seg_count(self, mask: jnp.ndarray) -> jnp.ndarray:
        return self._reduce(jnp.ones(self.gid.shape, jnp.int32), mask, 0,
                            functools.partial(jnp.sum, dtype=jnp.int32)
                            ).astype(jnp.int64)

    def seg_min_of(self, x: jnp.ndarray, mask: jnp.ndarray,
                   fill) -> jnp.ndarray:
        return self._reduce(x, mask, fill, jnp.min)

    def seg_max_of(self, x: jnp.ndarray, mask: jnp.ndarray,
                   fill) -> jnp.ndarray:
        return self._reduce(x, mask, fill, jnp.max)


class _AggSpec:
    """update/merge/finalize triple for one aggregate function."""

    n_buffers = 1
    # update() reads its ctx through seg_sum / seg_count / seg_min_of /
    # seg_max_of, row_mask and cap alone, so a _DenseCtx can stand in
    dense_ok = False

    def __init__(self, agg: ir.AggregateExpression):
        self.agg = agg

    def update(self, v: Optional[ColVal], ctx: _SortedCtx
               ) -> List[Tuple[jnp.ndarray, jnp.ndarray]]:
        raise NotImplementedError

    def merge(self, bufs: List[DeviceColumn], ctx: _SortedCtx
              ) -> List[Tuple[jnp.ndarray, jnp.ndarray]]:
        raise NotImplementedError

    def finalize(self, bufs: List[DeviceColumn]) -> ColVal:
        raise NotImplementedError

    def buffer_dtypes(self) -> List[dt.DType]:
        raise NotImplementedError


class _CountSpec(_AggSpec):
    dense_ok = True

    def buffer_dtypes(self):
        return [dt.INT64]

    def update(self, v, ctx):
        if v is None or v.nonnull:  # COUNT(*) / provably null-free
            mask = ctx.row_mask
        else:
            mask = v.validity & ctx.row_mask
        c = ctx.seg_count(mask)
        return [(c, jnp.ones((ctx.cap,), dtype=jnp.bool_))]

    def merge(self, bufs, ctx):
        c = ctx.seg_sum(bufs[0].data, ctx.row_mask)
        return [(c, jnp.ones((ctx.cap,), dtype=jnp.bool_))]

    def finalize(self, bufs):
        return ColVal(dt.INT64, bufs[0].data,
                      jnp.ones_like(bufs[0].validity))


class _SumSpec(_AggSpec):
    n_buffers = 2  # sum, valid-input count
    dense_ok = True

    def buffer_dtypes(self):
        return [self.agg.dtype, dt.INT64]

    def _sum(self, data, validity, ctx, narrow_bits=None):
        tgt = self.agg.dtype.to_np()
        mask = validity if validity is ctx.row_mask \
            else validity & ctx.row_mask
        s = ctx.seg_sum(data, mask, out_np=tgt, narrow_bits=narrow_bits)
        c = ctx.seg_count(mask)
        return [(s, c > 0), (c, jnp.ones((ctx.cap,), dtype=jnp.bool_))]

    def update(self, v, ctx):
        return self._sum(v.data,
                         ctx.row_mask if v.nonnull else v.validity,
                         ctx, narrow_bits=sortkeys.narrow_int_bits(v))

    def merge(self, bufs, ctx):
        tgt = self.agg.dtype.to_np()
        s = ctx.seg_sum(bufs[0].data, bufs[0].validity & ctx.row_mask,
                        out_np=tgt)
        c = ctx.seg_sum(bufs[1].data, ctx.row_mask, out_np=np.int64)
        return [(s, c > 0), (c, jnp.ones((ctx.cap,), dtype=jnp.bool_))]

    def finalize(self, bufs):
        return ColVal(self.agg.dtype, bufs[0].data, bufs[0].validity)


class _MinMaxSpec(_AggSpec):
    def __init__(self, agg, is_min: bool):
        super().__init__(agg)
        self.is_min = is_min
        # string extremes tie-break word by word in sorted space
        self.dense_ok = not agg.dtype.is_string

    def buffer_dtypes(self):
        return [self.agg.dtype]

    def _reduce_string(self, data, validity, lengths, ctx):
        """String min/max: word-wise segmented tie-break — per uint64
        key word (most significant first), keep the rows matching the
        group's extreme, then pick the first survivor.  All segmented
        steps are scan+gather (scatter-free); cudf's GpuMin/GpuMax are
        type-generic (reference: AggregateFunctions.scala:531)."""
        considered = validity & ctx.row_mask
        sv = ColVal(self.agg.dtype, data, considered, lengths)
        words = sortkeys.encode_keys(sv, True, nulls_first=False)[1:]
        cand_s = ctx.take_sorted(considered)
        umax = jnp.uint64(0xFFFFFFFFFFFFFFFF)
        for w in words:
            wv_s = ctx.take_sorted(w if self.is_min else ~w)
            best = ctx.seg_scan_reduce(
                jnp.where(cand_s, wv_s, umax), jnp.minimum, umax)
            cand_s = cand_s & (wv_s == jnp.take(best, ctx.gid_sorted))
        i = jnp.arange(ctx.cap, dtype=jnp.int64)
        win = ctx.seg_scan_reduce(jnp.where(cand_s, i, _BIG),
                                  jnp.minimum, _BIG)
        found = ctx.seg_count(considered) > 0
        orig = jnp.take(ctx.order, jnp.clip(win, 0, ctx.cap - 1))
        val = jnp.where(found[:, None], jnp.take(data, orig, axis=0), 0)
        lens = jnp.where(found, jnp.take(lengths, orig), 0)
        return [(val, found, lens)]

    def _reduce(self, data, validity, lengths, ctx):
        d = self.agg.dtype
        tgt = d.to_np()
        considered = validity if validity is ctx.row_mask \
            else validity & ctx.row_mask
        if d.is_string:
            return self._reduce_string(data, validity, lengths, ctx)
        if d.is_floating:
            isnan = jnp.isnan(data)
            non_nan = considered & ~isnan
            fill = np.array(np.inf if self.is_min else -np.inf, dtype=tgt)
            red = ctx.seg_min_of(data, non_nan, fill) if self.is_min \
                else ctx.seg_max_of(data, non_nan, fill)
            has_non_nan = ctx.seg_count(non_nan) > 0
            has_nan = ctx.seg_count(considered & isnan) > 0
            has_any = has_non_nan | has_nan
            nan = np.array(np.nan, dtype=tgt)
            if self.is_min:
                # Spark: NaN is greatest -> min prefers non-NaN
                val = jnp.where(has_non_nan, red, nan)
            else:
                # max: any NaN wins
                val = jnp.where(has_nan, nan, red)
            return [(jnp.where(has_any, val, 0), has_any)]
        if d.is_bool:
            x = data.astype(jnp.int32)
            red = ctx.seg_min_of(x, considered, 1) if self.is_min \
                else ctx.seg_max_of(x, considered, 0)
            has = ctx.seg_count(considered) > 0
            return [(red.astype(bool) & has, has)]
        info = np.iinfo(tgt)
        x = data.astype(tgt)
        red = ctx.seg_min_of(x, considered, info.max) if self.is_min \
            else ctx.seg_max_of(x, considered, info.min)
        has = ctx.seg_count(considered) > 0
        return [(jnp.where(has, red, 0), has)]

    def update(self, v, ctx):
        return self._reduce(v.data,
                            ctx.row_mask if v.nonnull else v.validity,
                            v.lengths, ctx)

    def merge(self, bufs, ctx):
        return self._reduce(bufs[0].data, bufs[0].validity,
                            bufs[0].lengths, ctx)

    def finalize(self, bufs):
        return ColVal(self.agg.dtype, bufs[0].data, bufs[0].validity,
                      bufs[0].lengths)


class _AverageSpec(_AggSpec):
    n_buffers = 2  # sum f64, count i64
    dense_ok = True

    def buffer_dtypes(self):
        return [dt.FLOAT64, dt.INT64]

    def update(self, v, ctx):
        considered = ctx.row_mask if v.nonnull \
            else v.validity & ctx.row_mask
        s = ctx.seg_sum(v.data, considered, out_np=np.float64)
        c = ctx.seg_count(considered)
        ones = jnp.ones((ctx.cap,), dtype=jnp.bool_)
        return [(s, ones), (c, ones)]

    def merge(self, bufs, ctx):
        s = ctx.seg_sum(bufs[0].data, ctx.row_mask, out_np=np.float64)
        c = ctx.seg_sum(bufs[1].data, ctx.row_mask, out_np=np.int64)
        ones = jnp.ones((ctx.cap,), dtype=jnp.bool_)
        return [(s, ones), (c, ones)]

    def finalize(self, bufs):
        c = bufs[1].data
        nz = c > 0
        avg = jnp.where(nz, bufs[0].data / jnp.where(nz, c, 1), 0.0)
        return ColVal(dt.FLOAT64, avg, nz)


class _FirstLastSpec(_AggSpec):
    n_buffers = 2  # value, found-flag

    def __init__(self, agg, is_first: bool):
        super().__init__(agg)
        self.is_first = is_first
        self.ignore_nulls = agg.ignore_nulls

    def buffer_dtypes(self):
        return [self.agg.dtype, dt.BOOL]

    def _pick(self, data, validity, lengths, considered, ctx):
        """In sorted space, pick first/last considered row per group.

        Stable radix sort preserves input order within a group, so
        'first in sorted order' == 'first in input/partial order'.
        """
        i = jnp.arange(ctx.cap, dtype=jnp.int64)
        considered_s = ctx.take_sorted(considered)
        if self.is_first:
            win = ctx.seg_scan_reduce(
                jnp.where(considered_s, i, _BIG), jnp.minimum, _BIG)
            found = win < _BIG
        else:
            win = ctx.seg_scan_reduce(
                jnp.where(considered_s, i, jnp.int64(-1)), jnp.maximum,
                jnp.int64(-1))
            found = win >= 0
        j = jnp.clip(win, 0, ctx.cap - 1)
        orig = jnp.take(ctx.order, j)  # original row index of the winner
        val = jnp.take(data, orig, axis=0)
        vvalid = jnp.take(validity, orig) & found
        if data.ndim == 2:
            val = jnp.where(found[:, None], val, 0)
        else:
            val = jnp.where(found, val, 0)
        if lengths is not None:
            lens = jnp.where(found, jnp.take(lengths, orig), 0)
            return [(val, vvalid, lens), (found, jnp.ones_like(found))]
        return [(val, vvalid), (found, jnp.ones_like(found))]

    def update(self, v, ctx):
        considered = ctx.row_mask & (v.validity if self.ignore_nulls
                                     else jnp.ones_like(v.validity))
        return self._pick(v.data, v.validity, v.lengths, considered, ctx)

    def merge(self, bufs, ctx):
        considered = ctx.row_mask & bufs[1].data.astype(bool)
        if self.ignore_nulls:
            considered = considered & bufs[0].validity
        return self._pick(bufs[0].data, bufs[0].validity, bufs[0].lengths,
                          considered, ctx)

    def finalize(self, bufs):
        return ColVal(self.agg.dtype, bufs[0].data, bufs[0].validity,
                      bufs[0].lengths)


def make_spec(agg: ir.AggregateExpression) -> _AggSpec:
    if isinstance(agg, ir.Count):
        return _CountSpec(agg)
    if isinstance(agg, ir.Sum):
        return _SumSpec(agg)
    if isinstance(agg, ir.Min):
        return _MinMaxSpec(agg, True)
    if isinstance(agg, ir.Max):
        return _MinMaxSpec(agg, False)
    if isinstance(agg, ir.Average):
        return _AverageSpec(agg)
    if isinstance(agg, ir.First):
        return _FirstLastSpec(agg, True)
    if isinstance(agg, ir.Last):
        return _FirstLastSpec(agg, False)
    raise NotImplementedError(type(agg).__name__)


# ---------------------------------------------------------------------------
# Pure kernel functions (shared by the exec and the ICI distributed path)
# ---------------------------------------------------------------------------

def normalize_key(v: ColVal) -> ColVal:
    """NaN/-0.0 canonicalization for grouping keys (Spark
    NormalizeFloatingNumbers semantics)."""
    if v.dtype.is_floating:
        x = jnp.where(jnp.isnan(v.data),
                      jnp.array(np.nan, dtype=v.data.dtype), v.data)
        x = jnp.where(x == 0.0, jnp.zeros_like(x), x)
        return ColVal(v.dtype, x, v.validity, v.lengths)
    return v


def sorted_group_ctx(key_vals: List[ColVal],
                     batch: DeviceBatch) -> _SortedCtx:
    """Batch-shaped wrapper over _group_ctx (rows are prefix-dense:
    row i exists iff i < num_rows)."""
    return _group_ctx(key_vals, batch.capacity, batch.num_rows)


def _group_ctx(key_vals: List[ColVal], cap: int,
               n_rows) -> _SortedCtx:
    """Group rows by key: stable LSD radix sort over bit-packed key
    digits brings equal keys adjacent, boundaries mark group starts, and
    every downstream reduction is scan+gather (see _SortedCtx).

    The radix formulation (sortkeys.radix_order_digits) compiles ONE
    single-key u32 sort for any key arity — the catastrophic multi-
    operand XLA sort compile (20-180 s measured) that forced round 3's
    hash-probe grouping is gone, and so are that path's per-iteration
    scatter rounds."""
    row_mask = jnp.arange(cap) < n_rows
    i32 = jnp.arange(cap, dtype=jnp.int32)
    if not key_vals:
        # global aggregation: one group holding every selected row (no
        # sort needed; the single segment spans the whole capacity so a
        # fused-filter mask with gaps still sums correctly)
        end = jnp.full((cap,), 0, jnp.int32).at[0].set(cap - 1)
        return _SortedCtx(
            order=i32, new=(i32 == 0), gid_sorted=jnp.zeros_like(i32),
            start_pos=jnp.zeros((cap,), jnp.int32), end_pos=end,
            sorted_mask=row_mask, cap=cap, row_mask=row_mask,
            n_groups=jnp.int32(1))

    fields = [(1, (~row_mask).astype(jnp.uint64))]  # padding sorts last
    total_bits = 1
    eff_nullables = []
    for ki, v in enumerate(key_vals):
        # drop the null flag only on the propagated no-null hint —
        # schema nullability is metadata and can be stale (a falsely
        # non-nullable key would group null rows with the zero value)
        nullable = not v.nonnull
        eff_nullables.append(nullable)
        kf = sortkeys.encode_fields(v, True, True, nullable=nullable)
        fields.extend(kf)
        total_bits += sum(w for w, _ in kf)
    digits = sortkeys.fields_to_digits(fields)

    if digits.shape[0] == 1:
        # narrow-key fast path (vbits hints pack every key + null flags
        # + the padding bit into one u32): ONE direct stable pair sort,
        # and because the padding flag is the MSB of the key itself,
        # sorted_mask and group boundaries come from the sorted keys —
        # zero digit gathers (measured: each 1M-row digit gather costs
        # as much as 5 pair sorts)
        ks, order = jax.lax.sort(
            (digits[0], i32), num_keys=1, is_stable=True)
        sorted_mask = (ks >> jnp.uint32(total_bits - 1)) == 0
        new = jnp.concatenate(
            [jnp.ones((1,), jnp.bool_), ks[1:] != ks[:-1]])
        new = new & sorted_mask
        sorted_key_u32 = ks
    else:
        order = sortkeys.radix_order_digits(digits)
        sorted_mask = jnp.take(row_mask, order)
        new = i32 == 0
        for di in range(digits.shape[0]):
            ds = jnp.take(digits[di], order)
            new = new | jnp.concatenate(
                [jnp.ones((1,), jnp.bool_), ds[1:] != ds[:-1]])
        new = new & sorted_mask
        sorted_key_u32 = None
    gid_sorted = jnp.cumsum(new.astype(jnp.int32)) - 1
    gid_sorted = jnp.maximum(gid_sorted, 0)
    n_groups = jnp.sum(new.astype(jnp.int32))

    nxt_real = jnp.concatenate([sorted_mask[1:],
                                jnp.zeros((1,), jnp.bool_)])
    nxt_new = jnp.concatenate([new[1:], jnp.ones((1,), jnp.bool_)])
    is_end = sorted_mask & (nxt_new | ~nxt_real)
    # unique-index set-scatters (cheap, unlike add/min/max scatters)
    start_pos = jnp.zeros((cap,), jnp.int32).at[
        jnp.where(new, gid_sorted, cap)].set(i32, mode="drop")
    end_pos = jnp.zeros((cap,), jnp.int32).at[
        jnp.where(is_end, gid_sorted, cap)].set(i32, mode="drop")
    key_inverse = None
    if sorted_key_u32 is not None and len(key_vals) == 1:
        v0 = key_vals[0]
        vb = sortkeys.narrow_int_bits(v0)
        if vb is not None:
            key_inverse = (vb, eff_nullables[0], v0.dtype, v0.vbits)
    return _SortedCtx(order=order, new=new, gid_sorted=gid_sorted,
                      start_pos=start_pos, end_pos=end_pos,
                      sorted_mask=sorted_mask, cap=cap,
                      row_mask=row_mask, n_groups=n_groups,
                      sorted_key=sorted_key_u32, key_inverse=key_inverse)


def gather_group_keys(key_vals: List[ColVal],
                      ctx: _SortedCtx) -> List[DeviceColumn]:
    """Representative key row per group (first sorted row)."""
    if not key_vals:
        return []
    group_exists = jnp.arange(ctx.cap) < ctx.n_groups
    if ctx.key_inverse is not None:
        # single narrow int key: unbias the packed sorted key at group
        # starts — one u32 gather replaces the order gather + per-key
        # data/validity gathers (the data gather is 3x a u32 gather for
        # int64 keys under x64 pair emulation)
        vb, nullable, kdt, kvbits = ctx.key_inverse
        kg = jnp.take(ctx.sorted_key, ctx.start_pos)
        value = (kg & jnp.uint32((1 << vb) - 1)).astype(jnp.int64) - \
            jnp.int64(1 << (vb - 1))
        valid = group_exists
        if nullable:
            valid = valid & (((kg >> jnp.uint32(vb)) & 1) == 1)
        data = jnp.where(valid, value, 0).astype(kdt.to_np())
        return [DeviceColumn(kdt, data, valid, vbits=kvbits,
                             nonnull=not nullable)]
    orig = jnp.take(ctx.order, ctx.start_pos)
    return [v.to_column().gather(orig, group_exists) for v in key_vals]


def _append_buffers(cols, names, bufs_per_spec, specs, ctx):
    for ai, (spec, bufs) in enumerate(zip(specs, bufs_per_spec)):
        for bi, (buf, bdt) in enumerate(zip(bufs, spec.buffer_dtypes())):
            data, valid = buf[0], buf[1]
            lengths = buf[2] if len(buf) > 2 else None
            group_exists = jnp.arange(ctx.cap) < ctx.n_groups
            cols.append(DeviceColumn(
                bdt, jnp.where(group_exists, data.astype(bdt.to_np()), 0)
                if data.ndim == 1 else data,
                valid & group_exists,
                jnp.where(group_exists, lengths, 0)
                if lengths is not None else None))
            names.append(f"__a{ai}_{bi}")


def _slice_batch(batch: DeviceBatch, n2: int) -> DeviceBatch:
    cols = [DeviceColumn(
        c.dtype, c.data[:n2], c.validity[:n2],
        None if c.lengths is None else c.lengths[:n2],
        None if c.elem_validity is None else c.elem_validity[:n2],
        c.vbits, c.nonnull)
        for c in batch.columns]
    return DeviceBatch(batch.names, cols, batch.num_rows)


def _pad_rows(a, cap: int):
    if a is None or a.shape[0] >= cap:
        return a
    return jnp.concatenate(
        [a, jnp.zeros((cap - a.shape[0],) + a.shape[1:], a.dtype)])


def _pad_batch(batch: DeviceBatch, cap: int) -> DeviceBatch:
    pad = functools.partial(_pad_rows, cap=cap)
    cols = [DeviceColumn(c.dtype, pad(c.data), pad(c.validity),
                         pad(c.lengths), pad(c.elem_validity),
                         c.vbits, c.nonnull)
            for c in batch.columns]
    return DeviceBatch(batch.names, cols, batch.num_rows)


def _ladder_engages(cap: int) -> bool:
    """Only at real-workload scale: each further branch adds the
    kernel's compile time again, which would dominate small-batch
    suites."""
    return cap // 4 >= _LADDER_MIN_RUNG


def _on_ladder(cap: int, nr, at):
    """Capacity ladder: run ``at(cap2)`` at the lowest of cap/4, cap/2
    and cap that holds the ``nr`` live rows — every sort pass, gather
    and scan scales with capacity, not live rows, and capacity tiers
    stand 4x apart below 1,048,576 and 2x from there up, so a batch
    just over a tier or behind a selective filter carries mostly
    padding.  ``at`` pads
    its outputs back to ``cap``.  Host-known row counts pick the rung
    in Python; traced counts pick via one lax.switch (every branch
    compiles once; safe since exec/scans.py keeps 64-bit scans out of
    the pathological in-control-flow cumsum lowering)."""
    if not _ladder_engages(cap):
        return at(cap)
    rungs = (cap // 4, cap // 2, cap)
    if isinstance(nr, (int, np.integer)):
        return at(next(r for r in rungs if int(nr) <= r))
    over = sum((nr > r).astype(jnp.int32) for r in rungs[:-1])
    return jax.lax.switch(over, [functools.partial(at, r) for r in rungs])


def _laddered(batch: DeviceBatch, fn):
    """``fn`` over the batch cut to its rung of the capacity ladder
    (live rows are prefix-dense), outputs padded back to capacity."""
    cap = batch.capacity

    def at(cap2: int) -> DeviceBatch:
        if cap2 == cap:
            return fn(batch)
        return _pad_batch(fn(_slice_batch(batch, cap2)), cap)

    return _on_ladder(cap, batch.num_rows, at)


def _dense_built(cap: int, grouped: bool,
                 specs: Sequence[_AggSpec]) -> bool:
    """Whether the update of a batch at capacity ``cap`` carries the
    dense branch: a grouped update whose every spec can reduce through
    a _DenseCtx, at or over the capacity ladder's own gate.  Below the
    gate the program's text stays what it was (one more branch is one
    more compile of the kernel, and a small batch's gathers are
    cheap)."""
    return (grouped and _ladder_engages(cap) and
            all(s.dense_ok for s in specs))


def _dense_ctx(ctx: _SortedCtx, slots: int) -> _DenseCtx:
    """The sorted context's groups as ids in ORIGINAL row space:
    ``ctx.order`` (composed with the fused filter's selection where
    there is one) says which row each sorted position came from, so one
    unique-index set-scatter carries ``gid_sorted`` back; rows the sort
    never saw, filtered or padding, keep ``slots`` and match no
    group."""
    rows = ctx.row_mask.shape[0]
    gid = jnp.full((rows,), slots, jnp.int32).at[
        jnp.where(ctx.sorted_mask, ctx.order, rows)].set(
            ctx.gid_sorted, mode="drop")
    return _DenseCtx(gid=gid, cap=slots, row_mask=ctx.row_mask,
                     n_groups=ctx.n_groups)


def _gather_val(v: ColVal, sel: jnp.ndarray,
                live: jnp.ndarray) -> ColVal:
    """Gather a value vector through a selected-row index map (the
    fused-filter permutation compact); rows beyond the live count zero
    out.  Hint-driven narrowing: i64 gathers cost 3x an i32 one under
    the pair emulation, so vbits<=32 data gathers through an i32 view
    and widens after; nonnull columns skip the validity gather (sel
    maps live outputs to live source rows)."""
    vb = sortkeys.narrow_int_bits(v)
    if (vb is not None and vb <= 32 and v.data.ndim == 1 and
            np.dtype(v.dtype.to_np()).itemsize == 8):
        data = jnp.take(v.data.astype(jnp.int32), sel
                        ).astype(v.data.dtype)
    else:
        data = jnp.take(v.data, sel, axis=0)
    data = jnp.where(live if data.ndim == 1 else live[:, None], data,
                     jnp.zeros((), data.dtype))
    validity = live if v.nonnull else jnp.take(v.validity, sel) & live
    lengths = None if v.lengths is None else \
        jnp.where(live, jnp.take(v.lengths, sel), 0)
    ev = None if v.elem_validity is None else \
        jnp.take(v.elem_validity, sel, axis=0) & live[:, None]
    return ColVal(v.dtype, data, validity, lengths, ev, vbits=v.vbits,
                  nonnull=v.nonnull)


def update_aggregate(batch: DeviceBatch,
                     groupings: Sequence[ir.Expression],
                     aggregates: Sequence[ir.AggregateExpression],
                     specs: Sequence[_AggSpec],
                     condition: Optional[ir.Expression] = None
                     ) -> DeviceBatch:
    """Per-batch update phase: groupBy().aggregate(updateAggs) analog.

    ``condition`` is a fused pre-filter (Filter directly under the
    aggregate): the filter compacts ONLY the evaluated key/agg value
    vectors (tpu_basic.compact would move every batch column), and the
    prefix-dense survivors let the capacity ladder run the sort-based
    grouping at a rung sized to the SELECTED rows — for the q6 bench's
    25%-selective filter that is cap/4 for every sort pass, gather and
    scan."""
    def run(kv, av, cap2, nr, sel_s=None, full_mask=None):
        """One grouped update at capacity cap2.  In the fused-filter
        path ``av`` stays in ORIGINAL row space: the sorted-space value
        gather composes the selection map with the sort order
        (sel∘order -> original rows), so each value vector pays ONE
        rung-sized gather total instead of a rung compact + a sorted
        gather."""
        from dataclasses import replace as _dc_replace
        ctx = _group_ctx(kv, cap2, nr)
        # (here, and not beside the update below, so that a program
        # without the dense branch keeps the text it always had)
        cols = None if dense else gather_group_keys(kv, ctx)
        vctx = ctx
        if sel_s is not None:
            vctx = _dc_replace(ctx, order=jnp.take(sel_s, ctx.order),
                               row_mask=full_mask)

        def update(uctx):
            return [spec.update(v, uctx) for v, spec in zip(av, specs)]

        if dense:
            # few groups: one algorithm (segment reduction) whose
            # cheapest form depends on the segment count, so the
            # choice is made where the count is, on the device.  The
            # dense side works at ``slots`` rows throughout, the group
            # keys' gathers included, and pads to the rung
            slots = min(_DENSE_MAX_GROUPS, cap2)

            def few_groups():
                few = _dc_replace(ctx, start_pos=ctx.start_pos[:slots],
                                  cap=slots)
                return jax.tree_util.tree_map(
                    lambda a: _pad_rows(a, cap2),
                    (gather_group_keys(kv, few),
                     update(_dense_ctx(vctx, slots))))

            cols, bufs_per_spec = jax.lax.cond(
                ctx.n_groups <= slots, few_groups,
                lambda: (gather_group_keys(kv, ctx), update(vctx)))
        else:
            bufs_per_spec = update(vctx)
        names = [f"__k{i}" for i in range(len(cols))]
        _append_buffers(cols, names, bufs_per_spec, specs, ctx)
        return DeviceBatch(names, cols, ctx.n_groups)

    dense = _dense_built(batch.capacity, bool(groupings), specs)

    def eval_vals(b: DeviceBatch):
        kv = [normalize_key(eval_tpu.evaluate(g, b))
              for g in groupings]
        av = [eval_tpu.evaluate(a.child, b)
              if a.child is not None else None for a in aggregates]
        return kv, av

    if condition is None:
        # batch-shaped ladder: expression evaluation itself runs at the
        # rung when live rows fit (strings/regex children are per-row
        # elementwise work worth 4x)
        def run_batch(b: DeviceBatch) -> DeviceBatch:
            kv, av = eval_vals(b)
            return run(kv, av, b.capacity, b.num_rows)
        return _laddered(batch, run_batch)

    # fused filter: the condition must see every row, so evaluate at
    # full capacity — then compact the PERMUTATION, not the data: one
    # int32 scatter builds the selected-row index map, and every value
    # vector gathers through it at the ladder rung (gathers at rung
    # cost ~1/4 of full-capacity scatters per vector; measured, the
    # per-vector scatter compact was ~310 ms of the 668 ms q6 pipeline)
    key_vals, agg_vals = eval_vals(batch)
    cap = batch.capacity
    cv = eval_tpu.evaluate(condition, batch)
    keep = cv.data.astype(jnp.bool_) & cv.validity & batch.row_mask()
    n_rows = jnp.sum(keep.astype(jnp.int32))
    # selected-row index map via ONE single-operand u32 sort (surviving
    # row positions ascend, so the sort is the stable compaction);
    # measured ~3x cheaper than the full-capacity scatter it replaces
    pos = jnp.where(keep, jnp.arange(cap, dtype=jnp.uint32),
                    jnp.uint32(0xFFFFFFFF))
    sel = jnp.sort(pos).astype(jnp.int32)

    def gather_keys(cap2):
        s = sel[:cap2]
        live = jnp.arange(cap2) < n_rows
        return [_gather_val(v, s, live) for v in key_vals], s

    def at(cap2: int) -> DeviceBatch:
        kv, s = gather_keys(cap2)
        return _pad_batch(run(kv, agg_vals, cap2, n_rows, s, keep), cap)

    return _on_ladder(cap, n_rows, at)


def merge_aggregate(batch: DeviceBatch, n_keys: int,
                    specs: Sequence[_AggSpec]) -> DeviceBatch:
    """Merge phase over concatenated partials: mergeAggs analog."""
    def run(b: DeviceBatch) -> DeviceBatch:
        key_cols = b.columns[:n_keys]
        key_vals = [ColVal(c.dtype, c.data, c.validity, c.lengths,
                            vbits=c.vbits, nonnull=c.nonnull)
                    for c in key_cols]
        ctx = sorted_group_ctx(key_vals, b)
        cols = gather_group_keys(key_vals, ctx)
        names = list(b.names[:n_keys])
        bufs_per_spec = []
        off = n_keys
        for spec in specs:
            bufs = b.columns[off:off + spec.n_buffers]
            off += spec.n_buffers
            bufs_per_spec.append(spec.merge(bufs, ctx))
        _append_buffers(cols, names, bufs_per_spec, specs, ctx)
        return DeviceBatch(names, cols, ctx.n_groups)
    return _laddered(batch, run)


def finalize_aggregate(batch: DeviceBatch, n_keys: int,
                       specs: Sequence[_AggSpec],
                       out_names: Sequence[str]) -> DeviceBatch:
    """Final projection from buffer columns to output columns."""
    cols = list(batch.columns[:n_keys])
    off = n_keys
    for spec in specs:
        bufs = batch.columns[off:off + spec.n_buffers]
        off += spec.n_buffers
        cols.append(spec.finalize(bufs).to_column())
    return DeviceBatch(list(out_names), cols, batch.num_rows)


class TpuHashAggregateExec(TpuExec):
    def __init__(self, child: PhysicalPlan,
                 groupings: Sequence[ir.Expression],
                 aggregates: Sequence[ir.AggregateExpression],
                 schema: Schema, per_partition: bool = False):
        super().__init__()
        self.children = (child,)
        self.groupings = list(groupings)
        self.aggregates = list(aggregates)
        self.specs = [make_spec(a) for a in self.aggregates]
        self._schema = schema
        # per_partition: aggregate each child partition independently
        # (the distributed plan shape over a hash exchange on the keys)
        self.per_partition = per_partition
        # a Filter that sat directly below this aggregate, fused in by
        # the overrides post-pass: rows failing it are MASKED instead
        # of compacted (compact costs one full-capacity gather per
        # column; the sort-based grouping is capacity-proportional
        # either way)
        self.fused_condition: Optional[ir.Expression] = None
        # execs the whole-stage fusion pass inlined into this
        # aggregate's prologue (plan/fusion.py R2)
        self.fused_prologue_execs: int = 0
        # the subset of those that are REAL savings vs the fusion-off
        # baseline: a lone filter directly under the aggregate is
        # absorbed by the legacy _fuse_filters_into_aggregates post-pass
        # either way, so counting it would overstate fusion's benefit
        self.fused_prologue_saved: int = 0
        self._update_kernel = None
        self._merge_kernel = None

    @property
    def schema(self) -> Schema:
        return self._schema

    def simple_string(self) -> str:
        if self.fused_condition is not None:
            return (f"TpuHashAggregateExec(fusedFilter="
                    f"{self.fused_condition.sql()})")
        return "TpuHashAggregateExec"

    def _update_impl(self, batch: DeviceBatch) -> DeviceBatch:
        return update_aggregate(batch, self.groupings, self.aggregates,
                                self.specs, self.fused_condition)

    def _merge_impl(self, batch: DeviceBatch) -> DeviceBatch:
        return merge_aggregate(batch, len(self.groupings), self.specs)

    def _final_impl(self, batch: DeviceBatch) -> DeviceBatch:
        return finalize_aggregate(batch, len(self.groupings), self.specs,
                                  self._schema.names)

    # ------------------------------------------------------------------
    def execute(self):
        if self._update_kernel is None:
            import functools
            import types
            from spark_rapids_tpu.exec import kernel_cache as kc
            # update/merge kernels never read the output schema names
            # (they emit static __k*/__a* buffer names); only agg_final
            # bakes the real names in — so names ride ONLY its key, and
            # the same aggregation under different output aliases
            # shares the expensive update/merge sorts (shape-erased ABI)
            sig = (kc.exprs_sig(self.groupings),
                   kc.exprs_sig(self.aggregates))
            # only the UPDATE kernel evaluates the fused condition;
            # merge/final kernels are identical across filters and must
            # share one compile (aggregate sorts cost ~17-20 s each)
            usig = sig + (kc.expr_sig(self.fused_condition)
                          if self.fused_condition is not None else None,)
            shim = types.SimpleNamespace(
                groupings=self.groupings, aggregates=self.aggregates,
                specs=self.specs, _schema=self._schema,
                fused_condition=self.fused_condition)
            cls = type(self)
            self._update_kernel = kc.get_kernel(
                ("agg_update", usig),
                lambda: functools.partial(cls._update_impl, shim))
            self._merge_kernel = kc.get_kernel(
                ("agg_merge", sig),
                lambda: functools.partial(cls._merge_impl, shim))
            self._final_kernel = kc.get_kernel(
                ("agg_final", sig, tuple(self._schema.names)),
                lambda: functools.partial(cls._final_impl, shim))

        # incremental-maintenance stamp (exec/incremental.py, threaded
        # through the planner): "retained" is a host table of merged
        # partial state from a previous run to fold into THIS run's
        # merge, "sink" captures this run's merged partials (pre-
        # finalize) for the next delta.  Never honored per_partition:
        # each partition merges independently there, so seeding every
        # partition with the retained state would multiply it in.
        inc = getattr(self, "_incremental", None)
        if inc is not None and self.per_partition:
            inc = None

        def run(its):
            from spark_rapids_tpu.exec import kernel_abi
            from spark_rapids_tpu.mem.spill import register_or_hold
            from spark_rapids_tpu.obs import registry as obsreg
            reg = obsreg.get_registry()
            # buffered partials stay spillable between update and merge
            # (reference: aggregate.scala buffers partial results;
            # SpillableColumnarBatch keeps them evictable)
            partials: List = []
            n_updates = 0
            if inc is not None and inc.get("retained") is not None:
                # the retained state merges FIRST, preserving the
                # old-batches-then-new-batches partial order a full
                # recompute would have produced
                from spark_rapids_tpu.columnar.batch import from_arrow
                retained_b = from_arrow(inc["retained"])
                reg.inc("incremental.retainedRows",
                        int(retained_b.num_rows))
                partials.append(register_or_hold(retained_b))
            try:
                for it in its:
                    for b in it:
                        # skip empty batches only when the count is
                        # already host-side: forcing a device sync here
                        # would serialize the whole pipeline per batch
                        nr = b.num_rows
                        if isinstance(nr, (int, np.integer)) \
                                and nr == 0 and self.groupings:
                            continue
                        # shape-erased ABI: the update kernel reads
                        # columns by ordinal only (groupings/aggregates
                        # are BoundReference trees) and emits its own
                        # static __k*/__a* buffer names, so the input
                        # erases with no restamp needed
                        with timed(self.metrics, "agg.update"):
                            partial = self._update_kernel(
                                kernel_abi.erase(b))
                        if self.fused_prologue_saved:
                            reg.inc("fusion.dispatchesSaved",
                                    self.fused_prologue_saved)
                        n_updates += 1
                        if inc is not None and inc.get("delta"):
                            # a delta-restricted scan's update batches
                            # ARE the delta cost — the serve-tier
                            # counter and the per-query profile section
                            # both read this
                            reg.inc("incremental.deltaBatches")
                            reg.inc("serve.incremental.deltaBatches")
                        partials.append(register_or_hold(partial))
                        # the handle alone keeps the partial alive, so
                        # the cut below can release it
                        del partial
                if not partials:
                    if self.groupings:
                        return  # grouped agg over empty input -> no rows
                    # global agg over empty -> one row (count=0, sum=null)
                    empty = _make_empty_buffer_batch(self)
                    if inc is not None and inc.get("sink") is not None:
                        from spark_rapids_tpu.columnar.batch import \
                            to_arrow
                        inc["sink"].table = to_arrow(empty)
                        inc["sink"].update_batches = n_updates
                    yield self._final_kernel(empty)
                    return
                held = _shrink_partials(partials, bool(self.groupings),
                                        self.specs, n_updates)
                if len(partials) == 1:
                    merged = partials[0].get()
                else:
                    whole = concat_batches([p.get() for p in partials])
                    with timed(self.metrics, "agg.merge"):
                        merged = self._merge_kernel(whole)
                    reg.inc("agg.merge.rowsIn", held)
                if inc is not None and inc.get("sink") is not None:
                    # freeze the pre-finalize merged state host-side:
                    # the next append-only drift merges forward from
                    # this instead of rescanning the whole dataset.
                    # The host conversion syncs once at the END of the
                    # pipeline (finalize is the only dispatch left).
                    from spark_rapids_tpu.columnar.batch import to_arrow
                    with timed(self.metrics, "agg.partialCapture"):
                        inc["sink"].table = to_arrow(merged)
                        inc["sink"].update_batches = n_updates
                    reg.inc("incremental.partialsCaptured")
                out = self._final_kernel(merged)
                self.metrics.add_rows(out.num_rows)
                yield out
                if len(partials) > 1:
                    # the consumer is done with the batch, so the count
                    # is long computed: no wait, no dispatch
                    reg.inc("agg.merge.groupsOut", int(read_host(
                        out.num_rows, "agg.mergeCountWait")))
            finally:
                for p in partials:
                    p.close()

        if self.per_partition:
            return [run([it]) for it in self.children[0].execute()]
        return [run(self.children[0].execute())]


def _shrink_partials(partials: List, grouped: bool,
                     specs: Sequence[_AggSpec] = (),
                     n_updates: int = 0) -> int:
    """Size the buffered partials by what they hold.  An update emits
    its partial at the input batch's capacity with its group count on
    the device, so four groups out of a 4M-row batch sit in a 4M-row
    buffer, and the concatenation, the merge and everything downstream
    of the aggregate would run at the sum of those capacities.

    Called once, after the last input batch has been dispatched (the
    aggregate is a pipeline breaker: the host has nothing left to
    enqueue): ONE read-back of all the counts, then every partial whose
    tier ``bucket_rows(n_groups)`` is below its capacity is cut down to
    that tier, in place in ``partials``, the cut batch taking the full
    one's spill handle so the large buffers are released before the
    merge.  Rows are prefix-dense, so the cut is a head slice that keeps
    the columns' hints.  Cutting to the tier and not to the count keeps
    every capacity on the ABI ladder: a group count that wanders inside
    a tier compiles nothing.  ``num_rows`` stays the device scalar, so
    ``concat_batches`` keeps its no-sync path, keyed by capacities.
    Where the groups fill their tier nothing is cut and the read is the
    only cost.  A global aggregate's partial holds one row by
    construction and needs no read.

    The same read says which form each of the last ``n_updates``
    partials (the updates' own; a retained one comes first) reduced in:
    the update carried the dense branch or not by its batch's capacity
    and ``specs`` (_dense_built), and took it by its group count.
    Counted here as ``agg.update.dense`` / ``agg.update.sorted``, for
    batches at the ladder's scale; below it no update has a choice and
    neither counter moves.

    Returns the rows the partials hold together (counted as
    ``agg.partials.groups`` where they were read): what a merge of
    them takes in."""
    from spark_rapids_tpu.columnar.batch import read_row_counts
    from spark_rapids_tpu.exec import kernel_abi, kernel_cache as kc
    from spark_rapids_tpu.mem.spill import register_or_hold
    from spark_rapids_tpu.obs import registry as obsreg, trace as obstrace
    reg = obsreg.get_registry()
    # the step's own span, round the wait and the cuts' dispatches: the
    # device idles while the host does either, and a trace lays those
    # gaps to the innermost span that covers them
    with obstrace.span("agg.shrink"):
        batches = [p.get() for p in partials]
        if not grouped:
            counts = [1] * len(batches)
        else:
            counts = read_row_counts(batches, "agg.countWait")
            first = len(batches) - n_updates
            for b, n in zip(batches[first:], counts[first:]):
                if _ladder_engages(b.capacity):   # else: no choice built
                    dense = n <= _DENSE_MAX_GROUPS and \
                        _dense_built(b.capacity, True, specs)
                    reg.inc("agg.update.dense" if dense
                            else "agg.update.sorted")
        shrunk = rows_cut = 0
        for i, (b, n) in enumerate(zip(batches, counts)):
            tier = bucket_rows(n)
            if tier >= b.capacity:
                continue
            fn = kc.get_kernel(
                ("agg_shrink", kernel_abi.erased_key(b), tier),
                lambda: _slice_batch, static_argnames=("n2",))
            cut = fn(kernel_abi.erase(b, pad=False), n2=tier)
            partials[i].close()
            partials[i] = register_or_hold(
                DeviceBatch(b.names, cut.columns, cut.num_rows))
            shrunk += 1
            rows_cut += b.capacity - tier
    if shrunk:
        reg.inc("agg.partials.shrunk", shrunk)
        reg.inc("agg.partials.rowsCut", rows_cut)
    held = int(sum(counts))
    if grouped:
        reg.inc("agg.partials.groups", held)
    return held


def _make_empty_buffer_batch(exec_: TpuHashAggregateExec) -> DeviceBatch:
    """Buffer-layout batch for a global aggregate over zero rows."""
    cap = 16
    cols, names = [], []
    for ai, spec in enumerate(exec_.specs):
        for bi, bdt in enumerate(spec.buffer_dtypes()):
            if bdt.is_string:
                cols.append(DeviceColumn(
                    bdt, jnp.zeros((cap, 1), dtype=jnp.uint8),
                    jnp.zeros((cap,), dtype=jnp.bool_),
                    jnp.zeros((cap,), dtype=jnp.int32)))
                names.append(f"__a{ai}_{bi}")
                continue
            data = jnp.zeros((cap,), dtype=bdt.to_np())
            # count buffers are valid-0; value buffers are null
            valid = jnp.zeros((cap,), dtype=jnp.bool_)
            if bdt == dt.INT64 and isinstance(
                    exec_.specs[ai], (_CountSpec, _SumSpec, _AverageSpec)) \
                    and bi == (0 if isinstance(exec_.specs[ai], _CountSpec)
                               else 1):
                valid = jnp.zeros((cap,), dtype=jnp.bool_).at[0].set(True)
            cols.append(DeviceColumn(bdt, data, valid, None))
            names.append(f"__a{ai}_{bi}")
    return DeviceBatch(names, cols, 1)

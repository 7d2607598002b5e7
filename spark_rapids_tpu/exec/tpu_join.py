"""TPU equi-join execs.

Reference analog: ``GpuShuffledHashJoinExec``/``GpuBroadcastHashJoinExec``
build one hash table from the build side and probe per stream batch via
``Table.onColumns(keys).innerJoin/leftJoin/fullJoin`` (reference:
shims/spark300/.../GpuHashJoin.scala:193-326); SortMergeJoin is *replaced by*
the shuffled hash join (reference: shims/spark300/.../GpuSortMergeJoinExec.scala).

On TPU, the hash table becomes a sort: both sides' keys are encoded into
total-order words (exec/sortkeys.py), one stable lexsort of the combined
rows groups equal keys together with build rows ahead of stream rows, and
segment arithmetic yields each stream row's contiguous build-match range.
The data-dependent output size (SURVEY.md §7 hard part #1) is handled with
the two-pass count-then-emit pattern: pass 1 computes the exact match
count (one scalar host sync), the host picks a power-of-two output bucket,
pass 2 re-runs the (cached) emit kernel at that static capacity.

SQL semantics: null join keys never match (a key group shares one null
pattern, so null-key groups are simply masked); float keys are normalized
(NaN==NaN, -0.0==0.0) to match Spark's NormalizeFloatingNumbers behavior.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from spark_rapids_tpu import dtypes as dt
from spark_rapids_tpu.columnar.batch import (DeviceBatch, DeviceColumn,
                                             _combined_hints, bucket_rows,
                                             concat_batches, read_host)
from spark_rapids_tpu.exec import scans, sortkeys
from spark_rapids_tpu.exec.base import (PhysicalPlan, REQUIRE_SINGLE_BATCH,
                                        TpuExec, timed)
from spark_rapids_tpu.exec.tpu_basic import compact
from spark_rapids_tpu.exec.tpu_aggregate import normalize_key
from spark_rapids_tpu.expr import eval_tpu, ir
from spark_rapids_tpu.expr.eval_tpu import ColVal
from spark_rapids_tpu.plan.logical import Schema

_BIG = np.int64(1 << 62)
_BIG32 = np.int32(np.iinfo(np.int32).max)  # > any position (cap-1)


def _gather(child: PhysicalPlan) -> Optional[DeviceBatch]:
    """Coalesce all of a child's partitions into one batch (build-side
    RequireSingleBatch, reference: GpuHashJoin build side).

    Each arriving batch registers with the spill catalog so the
    accumulating build side stays evictable until the concat
    (reference: build side held as LazySpillableColumnarBatch,
    GpuHashJoin.scala / SpillableColumnarBatch.scala:169)."""
    from spark_rapids_tpu.exec.placement import drain_by_chip
    from spark_rapids_tpu.mem.spill import register_or_hold
    its = child.execute()
    parts = [[] for _ in its]
    # partitions of different chips side by side (one loop here on one
    # chip); partition order is kept
    drain_by_chip(its, lambda p, b: parts[p].append(register_or_hold(b)),
                  stage="join")
    handles = [h for part in parts for h in part]
    if not handles:
        return None
    try:
        return concat_batches([h.get() for h in handles])
    finally:
        for h in handles:
            h.close()


def _canon_side(batch: DeviceBatch, prefix: str) -> DeviceBatch:
    """Shape-erased ABI at the join dispatch boundary (the PR 12 erase
    extended into the join ``emit`` family): bucket value-range hints
    to the coarse ABI table and pad stragglers to capacity tiers
    (``kernel_abi.erase``), then rename positionally with the side's
    static ``__l*``/``__r*`` prefix — the join kernels reference key
    columns by those canonical names, and keeping the two sides'
    prefixes distinct means the emitted build+stream column set never
    carries duplicate names.  Joins that differ only in schema names
    or precise value ranges share one program; the renamed-join-schema
    rerun test pins zero new programs."""
    from spark_rapids_tpu.exec import kernel_abi
    names = [f"{prefix}{i}" for i in range(batch.num_cols)]
    eb = kernel_abi.erase(batch)
    return DeviceBatch(names, eb.columns, eb.num_rows)


def _side_key(batch: DeviceBatch):
    """Erased cache-key component for one (already canonical) side —
    layout only under the ABI, the legacy named schema_key otherwise
    (so flipping kernel.abi.enabled between sessions cannot serve a
    kernel traced under the other ABI)."""
    from spark_rapids_tpu.exec import kernel_abi
    return kernel_abi.erased_key(batch)


def _key_vals(batch: DeviceBatch, key_names: Sequence[str]) -> List[ColVal]:
    out = []
    for k in key_names:
        c = batch.column(k)
        out.append(normalize_key(ColVal(c.dtype, c.data, c.validity,
                                        c.lengths, vbits=c.vbits,
                                        nonnull=c.nonnull)))
    return out


def _concat_colvals(a: ColVal, b: ColVal) -> ColVal:
    """Concatenate two key columns (for the combined build+stream space).

    Mismatched numeric key pairs are promoted to the common type before
    comparison (Spark's implicit cast), never truncated to one side's type.
    """
    if a.dtype.is_string:
        wa, wb = a.data.shape[1], b.data.shape[1]
        w = max(wa, wb)
        da = jnp.pad(a.data, ((0, 0), (0, w - wa)))
        db = jnp.pad(b.data, ((0, 0), (0, w - wb)))
        return ColVal(a.dtype, jnp.concatenate([da, db]),
                      jnp.concatenate([a.validity, b.validity]),
                      jnp.concatenate([a.lengths, b.lengths]))
    out_dt = a.dtype if a.dtype == b.dtype else dt.promote(a.dtype, b.dtype)
    tgt = out_dt.to_np()
    vb, nn = _combined_hints([a, b])
    merged = ColVal(out_dt,
                    jnp.concatenate([a.data.astype(tgt),
                                     b.data.astype(tgt)]),
                    jnp.concatenate([a.validity, b.validity]),
                    vbits=vb, nonnull=nn)
    # re-normalize: an int->float promotion can introduce nothing new, but
    # float inputs promoted from float32 need canonical NaN/-0.0 again
    return normalize_key(merged)


def _narrow_key_codes(combined, pad: int):
    """Equality-preserving per-row key code for narrow hinted keys.

    When every join key is integer-backed with a vbits range hint and
    the biased fields + null flags pack into 62 bits, the combined code
    itself IS the group value — equal keys share a code — so the
    hash-grouping while_loop (linear-probe scatter claims over a 2x
    table, the joins' dominant pre-sort cost) is skipped entirely.
    None -> caller falls back to hash_group_ids."""
    fields = []
    total = 0
    for v in combined:
        vb = sortkeys.narrow_int_bits(v)
        if vb is None or vb > 32:
            return None
        kf = sortkeys.encode_fields(v, True, True, nullable=True)
        fields.extend(kf)
        total += sum(w for w, _ in kf)
    if not fields or total > 62:         # code << 1 | side fits u64
        return None
    code = None
    for w, vals in fields:               # MSB-first fold
        code = vals if code is None else \
            (code << jnp.uint64(w)) | vals
    return jnp.pad(code, (0, pad))


def _join_sort_key(build: DeviceBatch, stream: DeviceBatch,
                   build_keys: Sequence[str],
                   stream_keys: Sequence[str], seg0=None):
    """(combined keys, exists, side, hash group ids, packed sort key)
    for the combined build+stream row space.

    Equal-key adjacency WITHOUT a multi-word lexsort: hash-group the
    combined keys (scatter build, compile-cheap), then the caller sorts
    ONE u64 word of (group id, side) — XLA sort compile cost scales with
    operand count, and at SQL batch sizes a multi-word lexsort compiles
    for minutes."""
    cap_b, cap_s = build.capacity, stream.capacity
    # pad the combined space to a power-of-two capacity so the shared
    # sort kernel is keyed on a handful of buckets, not on every
    # (cap_b + cap_s) sum the suite produces
    cap2 = bucket_rows(cap_b + cap_s)
    pad = cap2 - (cap_b + cap_s)
    bk = _key_vals(build, build_keys)
    sk = _key_vals(stream, stream_keys)
    combined = [_concat_colvals(b, s) for b, s in zip(bk, sk)]
    exists = jnp.pad(jnp.concatenate([build.row_mask(),
                                      stream.row_mask()]), (0, pad))
    side = jnp.pad(jnp.concatenate([
        jnp.zeros((cap_b,), dtype=jnp.uint64),
        jnp.ones((cap_s,), dtype=jnp.uint64)]), (0, pad))
    if seg0 is None:
        seg0 = _narrow_key_codes(combined, pad)
    if seg0 is None:
        key_groups = [sortkeys.encode_keys(v, True, True)
                      for v in combined]
        words = [jnp.pad(w, (0, pad)) for g in key_groups for w in g]
        seg0, _ = sortkeys.hash_group_ids(words, exists)
    packed = (seg0.astype(jnp.uint64) << jnp.uint64(1)) | side
    packed = jnp.where(exists, packed, jnp.uint64(0xFFFFFFFFFFFFFFFF))
    null_key = jnp.zeros((cap_b + cap_s,), dtype=jnp.bool_)
    for v in combined:
        null_key = null_key | ~v.validity
    null_key = jnp.pad(null_key, (0, pad))
    return null_key, exists, side, seg0, packed


class _JoinCtx:
    """Combined sorted space over build+stream rows."""

    def __init__(self, build: DeviceBatch, stream: DeviceBatch,
                 build_keys: Sequence[str], stream_keys: Sequence[str],
                 order=None, seg0=None):
        self.cap_b = build.capacity
        self.cap_s = stream.capacity
        null_key, exists, side, seg0, packed = _join_sort_key(
            build, stream, build_keys, stream_keys, seg0=seg0)
        cap = int(packed.shape[0])   # bucketed combined capacity
        self.cap = cap

        # the stable sort of the packed key normally runs OUTSIDE this
        # (jitted) kernel via sortkeys.shared_lexsort — embedding it
        # would recompile a minutes-scale XLA sort per join schema
        if order is None:
            order = jnp.lexsort((packed,))  # stable
        seg_sorted_raw = jnp.take(seg0, order)
        exists_sorted = jnp.take(exists, order)
        new_group = jnp.concatenate(
            [jnp.ones((1,), dtype=jnp.bool_),
             (seg_sorted_raw[1:] != seg_sorted_raw[:-1]) |
             (exists_sorted[1:] != exists_sorted[:-1])])
        seg = jnp.cumsum(new_group.astype(jnp.int32)) - 1

        self.order = order
        self.seg = seg
        sorted_exists = jnp.take(exists, order)
        sorted_side = jnp.take(side, order)
        self.sorted_null_key = jnp.take(null_key, order)
        self.is_build = sorted_exists & (sorted_side == 0)
        self.is_stream = sorted_exists & (sorted_side == 1)
        # counts/positions fit i32 (cap < 2^31), and every per-group
        # reduction is SCATTER-FREE sorted-space work (cumsum diffs +
        # one set-scatter of group end positions + a segmented i32
        # min-scan) — segment_sum/min scatter-adds at full capacity
        # measured ~100 ms each per 4M rows (PERF.md)
        pos = jnp.arange(cap, dtype=jnp.int32)
        nxt_new = jnp.concatenate([new_group[1:],
                                   jnp.ones((1,), jnp.bool_)])
        end_pos = jnp.zeros((cap,), jnp.int32).at[
            jnp.where(nxt_new, seg, cap)].set(pos, mode="drop")

        def per_group_count(mask):
            c = jnp.cumsum(mask.astype(jnp.int32))
            ce = jnp.take(c, end_pos)
            return ce - jnp.concatenate([ce[:1] * 0, ce[:-1]])

        match_build = self.is_build & ~self.sorted_null_key
        self.b_count = per_group_count(match_build)
        run_min = scans.seg_scan(
            jnp.minimum, new_group,
            jnp.where(match_build, pos, _BIG32), _BIG32)
        self.build_start = jnp.take(run_min, end_pos)
        match_stream = self.is_stream & ~self.sorted_null_key
        self.s_count = per_group_count(match_stream)

        # per sorted-row match count (stream rows only)
        self.m = jnp.where(self.is_stream & ~self.sorted_null_key,
                           jnp.take(self.b_count, seg), 0)


def _pairs_layout(ctx: _JoinCtx, outer: bool, with_incl: bool = True):
    """Per-sorted-row emission count + inclusive cumsum (i32: the emit
    kernel only runs after the host has checked the i64 total fits)."""
    m_out = ctx.m
    if outer:
        m_out = jnp.where(ctx.is_stream, jnp.maximum(ctx.m, 1), 0)
    else:
        m_out = jnp.where(ctx.is_stream, ctx.m, 0)
    incl = jnp.cumsum(m_out) if with_incl else None
    return m_out, incl


def _count_kernel(build, stream, order, seg0, build_keys, stream_keys,
                  how):
    ctx = _JoinCtx(build, stream, build_keys, stream_keys, order=order,
                   seg0=seg0)
    outer = how in ("left", "right", "full")
    m_out, _ = _pairs_layout(ctx, outer, with_incl=False)
    # the TRUE pair total needs i64: per-row counts fit i32 but a
    # many-to-many join's total is bounded by cap_b*cap_s, not cap.
    # A plain i64 reduction is safe anywhere (only i64 *scans* trip the
    # scoped-VMEM lowering); the host refuses totals past the i32 range
    # before the emit kernel's i32 cumsum ever sees them.
    total = jnp.sum(m_out, dtype=jnp.int64)
    if how == "full":
        unmatched_build = ctx.is_build & \
            (jnp.take(ctx.s_count, ctx.seg) == 0)
        total = total + jnp.sum(unmatched_build, dtype=jnp.int64)
    return total


def _emit_kernel(build, stream, order, seg0, build_keys, stream_keys,
                 how, out_cap,
                 build_names, stream_names, build_first_in_output):
    """Pass 2: materialize the joined batch at static capacity out_cap."""
    ctx = _JoinCtx(build, stream, build_keys, stream_keys, order=order,
                   seg0=seg0)
    outer = how in ("left", "right", "full")
    m_out, incl = _pairs_layout(ctx, outer)
    total_pairs = incl[-1]

    k = jnp.arange(out_cap, dtype=jnp.int32)
    # slot -> sorted stream row: scatter each emitting row's index at
    # its first output slot, forward-fill with a running max (row
    # indices ascend along slots).  Replaces searchsorted, whose
    # log2(cap) binary-search gathers per slot cost ~300 ms at 2M
    starts = incl - m_out
    has = m_out > 0
    marks = jnp.zeros((out_cap,), jnp.int32).at[
        jnp.where(has, starts, out_cap)].max(
        jnp.arange(ctx.cap, dtype=jnp.int32), mode="drop")
    r = jax.lax.cummax(marks)
    r = jnp.clip(r, 0, ctx.cap - 1)
    j = k - jnp.take(starts, r)
    valid_pair = k < total_pairs

    stream_orig = jnp.take(ctx.order, r) - ctx.cap_b
    stream_orig = jnp.clip(stream_orig, 0, ctx.cap_s - 1)
    has_match = jnp.take(ctx.m, r) > 0
    bpos = jnp.clip(jnp.take(ctx.build_start, jnp.take(ctx.seg, r)) + j,
                    0, ctx.cap - 1)
    build_orig = jnp.clip(jnp.take(ctx.order, bpos), 0, ctx.cap_b - 1)

    stream_valid = valid_pair
    build_valid = valid_pair & has_match

    if how == "full":
        # append unmatched build rows after the pairs (rank->row map via
        # cumsum+scatter, no sort)
        unmatched = ctx.is_build & (jnp.take(ctx.s_count, ctx.seg) == 0)
        u_count = jnp.sum(unmatched.astype(jnp.int32), dtype=jnp.int32)
        u_dest = jnp.where(
            unmatched, jnp.cumsum(unmatched.astype(jnp.int32)) - 1,
            ctx.cap)
        u_order = jnp.zeros((ctx.cap,), dtype=jnp.int32).at[u_dest].set(
            jnp.arange(ctx.cap, dtype=jnp.int32), mode="drop")
        tail_idx = jnp.clip(k - total_pairs, 0, ctx.cap - 1)
        in_tail = (k >= total_pairs) & (k < total_pairs + u_count)
        tail_sorted_pos = jnp.take(u_order, tail_idx)
        tail_build_orig = jnp.clip(
            jnp.take(ctx.order, tail_sorted_pos), 0, ctx.cap_b - 1)
        build_orig = jnp.where(in_tail, tail_build_orig, build_orig)
        build_valid = build_valid | in_tail
        stream_valid = valid_pair  # tail rows have null stream side
        total_out = total_pairs + u_count
    else:
        total_out = total_pairs

    s_cols = [c.gather(stream_orig, stream_valid) for c in stream.columns]
    b_cols = [c.gather(build_orig, build_valid) for c in build.columns]
    if build_first_in_output:
        names = list(build_names) + list(stream_names)
        cols = b_cols + s_cols
    else:
        names = list(stream_names) + list(build_names)
        cols = s_cols + b_cols
    return DeviceBatch(names, cols, total_out)


def _semi_kernel(build, stream, order, seg0, build_keys, stream_keys,
                 anti: bool):
    ctx = _JoinCtx(build, stream, build_keys, stream_keys, order=order,
                   seg0=seg0)
    # scatter per-sorted-row match count back to original stream rows
    m_orig = jnp.zeros((ctx.cap,), dtype=jnp.int32).at[ctx.order].set(ctx.m)
    m_stream = m_orig[ctx.cap_b:ctx.cap_b + ctx.cap_s]
    keep = (m_stream == 0) if anti else (m_stream > 0)
    return compact(stream, keep)



# ---------------------------------------------------------------------------
# Direct-address probe path (integer keys whose RANGE fits the table)
# ---------------------------------------------------------------------------
#
# When every join key is integer-backed and the build side's keys span
# few enough values, the hash table of cudf's hash join
# (GpuHashJoin.scala:193-326) becomes a DENSE direct-address table
# addressed by ``key - base``: one i32 scatter per build row, ONE gather
# per stream row to find its match range.  This removes the
# combined-space sort entirely — the sort-merge path's dominant cost is
# the (cap_b + cap_s)-sized sort plus ~10 bookkeeping gathers per row;
# the probe path pays 1-2 table gathers per stream row and
# per-output-column gathers only.
#
# What picks the path is the keys' range, not their magnitude: TPC-DS
# ``d_date_sk`` runs from 2,415,022 and spans 73,049 values.  The range
# is ONE read of the build keys' min and max where the build side is
# complete (a pipeline breaker: the host has nothing of this join left
# to enqueue), handed to ``_join_pair`` by each caller.  ``base``
# and ``extent`` are runtime arguments and the table's size a capacity
# tier, so a new range mints no new program.  Strings, floats, full
# outer joins and ranges past the table's limit take the sort-merge
# path.

_DIRECT_MAX_ENTRIES = 1 << 22   # direct table <= 4M entries (2 x 16 MiB i32)


class _KeyRange:
    """Host-side facts of one build side's keys for the direct table:
    ``base`` and ``extent`` (int64 arrays, one entry a key; the table
    is addressed by the mixed-radix number of ``key - base`` under
    ``extent``) and ``entries``, the table's size tier."""

    __slots__ = ("base", "extent", "entries")

    def __init__(self, base, extent, entries: int):
        self.base = np.asarray(base, dtype=np.int64)
        self.extent = np.asarray(extent, dtype=np.int64)
        self.entries = entries

    @classmethod
    def fit(cls, lo: Sequence[int], hi: Sequence[int]
            ) -> Optional["_KeyRange"]:
        """The range ``[lo, hi]`` a key (Python ints), or None where
        the table would pass its limit."""
        extent = [h - l + 1 for l, h in zip(lo, hi)]
        entries = 1
        for e in extent:
            entries *= e
        if entries > _DIRECT_MAX_ENTRIES:
            return None
        return cls(lo, extent, bucket_rows(entries))


def _direct_key_widths(lschema: Schema, rschema: Schema,
                       left_keys: Sequence[str],
                       right_keys: Sequence[str]
                       ) -> Optional[Tuple[bool, ...]]:
    """Static (schema-only) eligibility for the direct table: per key
    pair whether ``key - base`` needs 64-bit arithmetic, or None where
    a key is no integer (strings, floats, bools, dates, nested: the
    sort-merge path)."""
    wide = []
    for lk, rk in zip(left_keys, right_keys):
        ld, rd = lschema.field(lk).dtype, rschema.field(rk).dtype
        for d in (ld, rd):
            if d.is_string or d.is_floating or d.is_bool or \
                    d.is_nested or d.is_temporal:
                return None
        out_dt = ld if ld == rd else dt.promote(ld, rd)
        if not out_dt.is_numeric or out_dt.is_floating:
            return None
        wide.append(np.dtype(out_dt.to_np()).itemsize > 4)
    return tuple(wide) if wide else None


def _range_kernel(build: DeviceBatch, key_pos: Sequence[int]):
    """``[[min, max], ...]`` (int64) of each build key over the rows
    that can match: live, no key null.  An empty side reads min > max."""
    valid = build.row_mask()
    for i in key_pos:
        valid = valid & build.columns[i].validity
    out = []
    for i in key_pos:
        d = build.columns[i].data
        info = jnp.iinfo(d.dtype)
        out.append(jnp.stack([
            jnp.min(jnp.where(valid, d, info.max)).astype(jnp.int64),
            jnp.max(jnp.where(valid, d, info.min)).astype(jnp.int64)]))
    return jnp.stack(out)


def _direct_codes(cols: Sequence[ColVal], wide: Sequence[bool], base,
                  extent):
    """Table address of each row, and whether the row has one: every
    key non-null and inside ``[base, base + extent)``.  The difference
    wraps, so one unsigned compare is the whole range check whatever
    the key's magnitude."""
    code = ok = None
    for i, v in enumerate(cols):
        if wide[i]:
            d = jax.lax.bitcast_convert_type(
                v.data.astype(jnp.int64) - base[i], jnp.uint64)
            inside = d < extent[i].astype(jnp.uint64)
        else:
            d = jax.lax.bitcast_convert_type(
                v.data.astype(jnp.int32) - base[i].astype(jnp.int32),
                jnp.uint32)
            inside = d < extent[i].astype(jnp.uint32)
        inside = inside & v.validity
        d = jnp.where(inside, d.astype(jnp.int32), 0)
        code = d if code is None else \
            code * extent[i].astype(jnp.int32) + d
        ok = inside if ok is None else ok & inside
    return code, ok


def _probe_tables(build: DeviceBatch, stream: DeviceBatch,
                  build_keys: Sequence[str], stream_keys: Sequence[str],
                  entries: int, wide, base, extent):
    """Shared probe-side prologue: per-side table addresses, valid
    masks, and the dense per-address build count table.  A null key
    has no address, so it matches nothing from either side."""
    bcode, bok = _direct_codes(_key_vals(build, build_keys), wide, base,
                               extent)
    scode, sok = _direct_codes(_key_vals(stream, stream_keys), wide,
                               base, extent)
    bvalid = build.row_mask() & bok
    svalid = stream.row_mask() & sok
    cnt = jnp.zeros((entries,), jnp.int32).at[
        jnp.where(bvalid, bcode, entries)].add(1, mode="drop")
    m = jnp.where(svalid, jnp.take(cnt, scode), 0)
    return bcode, scode, bvalid, svalid, cnt, m


def _probe_count_kernel(build, stream, base, extent, build_keys,
                        stream_keys, how, entries, wide):
    """(total output rows i64, max per-stream-row match count i32)."""
    _, _, _, _, _, m = _probe_tables(build, stream, build_keys,
                                     stream_keys, entries, wide, base,
                                     extent)
    m_out = jnp.where(stream.row_mask(), jnp.maximum(m, 1), 0) \
        if how == "left" else m
    return jnp.sum(m_out, dtype=jnp.int64), jnp.max(m)


def _probe_emit_unique_kernel(build, stream, base, extent, build_keys,
                              stream_keys, how, out_cap, build_names,
                              stream_names, build_first_in_output,
                              entries, wide):
    """Emit when every build key is unique (max match count <= 1): the
    dense table maps code -> build row directly, output rows are stream
    rows (left: in place; inner: compacted), no expansion machinery."""
    bcode, scode, bvalid, svalid, _cnt, _m = _probe_tables(
        build, stream, build_keys, stream_keys, entries, wide, base,
        extent)
    cap_b, cap_s = build.capacity, stream.capacity
    # row+1 sentinel table: 0 = no build row, ONE gather gives both the
    # match flag and the row
    rows1 = jnp.zeros((entries,), jnp.int32).at[
        jnp.where(bvalid, bcode, entries)].set(
        jnp.arange(cap_b, dtype=jnp.int32) + 1, mode="drop")
    hit = jnp.where(svalid, jnp.take(rows1, scode), 0)
    matched = hit > 0
    build_row = jnp.clip(hit - 1, 0, cap_b - 1)

    if how in ("left", "inner_inplace"):
        # inner_inplace: the host saw total == stream rows (FK join,
        # every stream row matched) — output rows ARE the stream rows,
        # so skip the compaction and all stream-column gathers
        s_cols = list(stream.columns)
        b_cols = [c.gather(build_row, matched) for c in build.columns]
        total_out = stream.num_rows
    else:
        keep = matched
        # stable compaction of (stream cols, gathered build cols) to
        # out_cap (cumsum destinations + scatter, the compact() idiom)
        count = jnp.sum(keep.astype(jnp.int32))
        dest = jnp.where(keep, jnp.cumsum(keep.astype(jnp.int32)) - 1,
                         out_cap)
        src = jnp.zeros((out_cap,), jnp.int32).at[dest].set(
            jnp.arange(cap_s, dtype=jnp.int32), mode="drop")
        out_valid = jnp.arange(out_cap, dtype=jnp.int32) < count
        s_cols = [c.gather(src, out_valid) for c in stream.columns]
        br = jnp.take(build_row, src)
        b_cols = [c.gather(br, out_valid) for c in build.columns]
        total_out = count
    if build_first_in_output:
        names = list(build_names) + list(stream_names)
        cols = b_cols + s_cols
    else:
        names = list(stream_names) + list(build_names)
        cols = s_cols + b_cols
    return DeviceBatch(names, cols, total_out)


def _probe_emit_dup_kernel(build, stream, border, base, extent,
                           build_keys, stream_keys, how, out_cap,
                           build_names, stream_names,
                           build_first_in_output, entries, wide):
    """Emit with duplicated build keys: build rows grouped by code via
    the (small) build-side sort ``border``, match ranges from the dense
    start/count tables, output expansion via cumsum + set-scatter +
    cummax forward fill (no combined-space sort)."""
    bcode, scode, bvalid, svalid, cnt, m = _probe_tables(
        build, stream, build_keys, stream_keys, entries, wide, base,
        extent)
    cap_b, cap_s = build.capacity, stream.capacity
    starts_tbl = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(cnt)[:-1]])
    # build rows grouped by code: border sorts (invalid-last) bcode
    grouped_rows = border                  # sorted build row ids
    st = jnp.where(svalid, jnp.take(starts_tbl, scode), 0)

    m_out = jnp.where(stream.row_mask(), jnp.maximum(m, 1), 0) \
        if how == "left" else m
    incl = jnp.cumsum(m_out)
    total_out = incl[-1]
    starts_out = incl - m_out
    has = m_out > 0
    k = jnp.arange(out_cap, dtype=jnp.int32)
    marks = jnp.zeros((out_cap,), jnp.int32).at[
        jnp.where(has, starts_out, out_cap)].max(
        jnp.arange(cap_s, dtype=jnp.int32), mode="drop")
    r = jnp.clip(jax.lax.cummax(marks), 0, cap_s - 1)
    j = k - jnp.take(starts_out, r)
    valid_pair = k < total_out
    has_match = jnp.take(m, r) > 0
    bpos = jnp.clip(jnp.take(st, r) + j, 0, cap_b - 1)
    build_row = jnp.clip(jnp.take(grouped_rows, bpos), 0, cap_b - 1)
    s_cols = [c.gather(r, valid_pair) for c in stream.columns]
    b_cols = [c.gather(build_row, valid_pair & has_match)
              for c in build.columns]
    if build_first_in_output:
        names = list(build_names) + list(stream_names)
        cols = b_cols + s_cols
    else:
        names = list(stream_names) + list(build_names)
        cols = s_cols + b_cols
    return DeviceBatch(names, cols, total_out)


def _probe_semi_kernel(build, stream, base, extent, build_keys,
                       stream_keys, anti, entries, wide):
    _, _, _, _, _, m = _probe_tables(build, stream, build_keys,
                                     stream_keys, entries, wide, base,
                                     extent)
    keep = (m == 0) if anti else (m > 0)
    return compact(stream, keep & stream.row_mask())


class _BroadcastBuildMixin:
    """Caches the one-time gather of the broadcast (build) side."""

    def _init_build(self, build_side: str) -> None:
        self.build_side = build_side
        self._built = None
        self._built_range = None
        self._build_done = False
        import threading
        self._build_lock = threading.Lock()

    def _build(self):
        # concurrent stream partitions must gather the build side once;
        # the cached copy is held through the whole probe phase, so it
        # stays registered with the spill catalog and is rematerialized
        # per probe (reference: broadcast build kept as
        # SpillableColumnarBatch, GpuBroadcastExchangeExec)
        from spark_rapids_tpu.mem.spill import register_or_hold
        from spark_rapids_tpu.obs import trace as obstrace
        with self._build_lock:
            if not self._build_done:
                side = 1 if self.build_side == "right" else 0
                with obstrace.span("join.build"):
                    built = _gather(self.children[side])
                    if built is not None:
                        self._built_range = self._built_key_range(built)
                self._built = None if built is None \
                    else register_or_hold(built)
                self._build_done = True
        return None if self._built is None else self._built.get()

    def _built_key_range(self, built: DeviceBatch):
        """A hash join's facts about the gathered side's keys (read
        once, here, where the side is complete); nothing for a
        nested-loop join."""
        return None


class _HashJoinBase(TpuExec):
    """Shared probe machinery for shuffled and broadcast hash joins."""

    def __init__(self, left: PhysicalPlan, right: PhysicalPlan,
                 left_keys: Sequence[str], right_keys: Sequence[str],
                 how: str, condition: Optional[ir.Expression],
                 schema: Schema):
        super().__init__()
        self.children = (left, right)
        self.left_keys = list(left_keys)
        self.right_keys = list(right_keys)
        self.how = how
        self.condition = condition
        self._schema = schema
        self._kernels = {}
        # which keys need 64-bit arithmetic in the direct table; None:
        # the sort-merge path (a key that is no integer, a full outer
        # join, whose unmatched build rows the probe kernels do not emit)
        self._direct_wide = None if how == "full" else \
            _direct_key_widths(left.schema, right.schema,
                               self.left_keys, self.right_keys)

    @property
    def schema(self) -> Schema:
        return self._schema

    def _sort_order(self, build: DeviceBatch, stream: DeviceBatch,
                    bkeys, skeys) -> jnp.ndarray:
        """Combined-space sort order via the SHARED per-capacity sort
        kernel (the expensive compile), fed by a cheap per-schema pack
        kernel."""
        from spark_rapids_tpu.exec import kernel_cache as kc
        pkey = ("join_pack", tuple(bkeys), tuple(skeys),
                _side_key(build), _side_key(stream))
        if pkey not in self._kernels:
            self._kernels[pkey] = kc.get_kernel(
                pkey, lambda: lambda b, s: _join_sort_key(
                    b, s, bkeys, skeys)[3:5])
        seg0, packed = self._kernels[pkey](build, stream)
        order = sortkeys.shared_lexsort(jnp.reshape(packed, (1, -1)))
        return order, seg0

    def _build_is_left(self, build_side: str) -> bool:
        """Which child ``_join_pair`` builds on: semi/anti on the
        right, right outer on the left (left outer, sides swapped)."""
        return self.how not in ("semi", "anti") and \
            (build_side == "left" or self.how == "right")

    def _key_range(self, build: DeviceBatch, build_is_left: bool
                   ) -> Optional[_KeyRange]:
        """What the direct table needs to know of a complete build side
        (its real names, as the child gave it): each key's base and
        extent and the table's tier, or None where the join takes the
        sort-merge path (a key that is no integer, a full outer join, a
        range past the table's limit).

        ONE read of the keys' min and max (none for a side the host
        knows to be empty).  The read blocks until the build side has
        run, so callers make it where that side is complete and before
        the stream side is pulled: once a build, not once a stream
        batch."""
        if self._direct_wide is None:
            return None
        from spark_rapids_tpu.exec import kernel_abi, kernel_cache as kc
        keys = self.left_keys if build_is_left else self.right_keys
        pos = tuple(build.names.index(k) for k in keys)
        if isinstance(build.num_rows, (int, np.integer)) and \
                not build.num_rows:
            return _KeyRange.fit([0] * len(pos), [0] * len(pos))
        eb = kernel_abi.erase(build)
        fn = kc.get_kernel(("join_range", pos, _side_key(eb)),
                           lambda: lambda b: _range_kernel(b, pos))
        got = read_host(fn(eb), "join.rangeWait")
        lo, hi = [int(v) for v in got[:, 0]], [int(v) for v in got[:, 1]]
        if any(l > h for l, h in zip(lo, hi)):
            lo, hi = [0] * len(pos), [0] * len(pos)   # nothing to match
        return _KeyRange.fit(lo, hi)

    def _probe_pair(self, build: DeviceBatch, stream: DeviceBatch,
                    bkeys, skeys, emit_how: str, build_first: bool,
                    kr: _KeyRange):
        """Direct-address probe join: count -> host picks the unique or
        duplicated-build-key emit variant."""
        from spark_rapids_tpu.exec import kernel_cache as kc
        wide, entries = self._direct_wide, kr.entries
        sig = (entries, wide, emit_how, tuple(bkeys), tuple(skeys),
               _side_key(build), _side_key(stream))
        ckey = ("probe_count",) + sig
        if ckey not in self._kernels:
            self._kernels[ckey] = kc.get_kernel(
                ckey, lambda: lambda b, s, base, ext: _probe_count_kernel(
                    b, s, base, ext, bkeys, skeys, emit_how, entries,
                    wide))
        with timed(self.metrics, "join.probeCount"):
            total, maxm = self._kernels[ckey](build, stream, kr.base,
                                              kr.extent)
            total, maxm = (int(v) for v in read_host([total, maxm],
                                                     "join.countWait"))
        if total >= (1 << 31):
            raise MemoryError(
                f"join output of {total} rows exceeds the single-batch "
                f"2^31 limit; repartition the inputs")
        if maxm <= 1:
            emit_variant = emit_how
            if emit_how == "inner" and \
                    isinstance(stream.num_rows, (int, np.integer)) and \
                    total == int(stream.num_rows):
                emit_variant = "inner_inplace"   # FK join: all rows match
            out_cap = bucket_rows(stream.capacity) if emit_variant != "inner" \
                else bucket_rows(total)
            ekey = ("probe_emit_u", emit_variant, out_cap,
                    build_first) + sig
            if ekey not in self._kernels:
                self._kernels[ekey] = kc.get_kernel(
                    ekey, lambda: lambda b, s, base, ext:
                    _probe_emit_unique_kernel(
                        b, s, base, ext, bkeys, skeys, emit_variant,
                        out_cap, build.names, stream.names, build_first,
                        entries, wide))
            with timed(self.metrics, "join.probeEmit"):
                out = self._kernels[ekey](build, stream, kr.base,
                                          kr.extent)
        else:
            out_cap = bucket_rows(total)
            pkey = ("probe_bpack",) + sig
            if pkey not in self._kernels:
                def bpack(b, s, base, ext):
                    bcode, _, bvalid, _, _, _ = _probe_tables(
                        b, s, bkeys, skeys, entries, wide, base, ext)
                    key = jnp.where(bvalid, bcode.astype(jnp.uint64),
                                    jnp.uint64(0xFFFFFFFF))
                    return jnp.reshape(key, (1, -1))
                self._kernels[pkey] = kc.get_kernel(pkey,
                                                    lambda: bpack)
            ekey = ("probe_emit_d", out_cap, build_first) + sig
            if ekey not in self._kernels:
                self._kernels[ekey] = kc.get_kernel(
                    ekey, lambda: lambda b, s, o, base, ext:
                    _probe_emit_dup_kernel(
                        b, s, o, base, ext, bkeys, skeys, emit_how,
                        out_cap, build.names, stream.names, build_first,
                        entries, wide))
            with timed(self.metrics, "join.probeEmit"):
                border = sortkeys.shared_lexsort(
                    self._kernels[pkey](build, stream, kr.base,
                                        kr.extent))
                out = self._kernels[ekey](build, stream, border,
                                          kr.base, kr.extent)
        out = DeviceBatch(self._schema.names, out.columns, out.num_rows)
        if self.condition is not None:
            v = eval_tpu.evaluate(self.condition, out)
            out = compact(out, v.data.astype(jnp.bool_) & v.validity)
        self.metrics.add_rows(out.num_rows)
        self.metrics.add_batches()
        yield out

    def _join_pair(self, left: DeviceBatch, right: DeviceBatch,
                   key_range: Optional[_KeyRange],
                   build_side: str = "right"):
        """Join two single batches; yields 0 or 1 output batches.
        ``key_range`` is :meth:`_key_range` of the side
        :meth:`_build_is_left` names, made by the caller once a build
        (None: the sort-merge path)."""
        from spark_rapids_tpu.exec import kernel_cache as kc
        from spark_rapids_tpu.obs import registry as obsreg
        how = self.how
        build_is_left = self._build_is_left(build_side)
        kr = key_range
        reg = obsreg.get_registry()
        if kr is None:
            reg.inc("join.path.sortMerge")
        else:
            reg.inc("join.path.direct")
            reg.gauge_max("join.table.entries", kr.entries)
        # canonicalize both sides at the dispatch boundary: positional
        # __l*/__r* names (dodges duplicate-name lookups AND erases the
        # user schema from the kernel identity) + ABI hint bucketing /
        # tier padding (_canon_side)
        lkeys = [f"__l{left.names.index(k)}" for k in self.left_keys]
        rkeys = [f"__r{right.names.index(k)}" for k in self.right_keys]
        left = _canon_side(left, "__l")
        right = _canon_side(right, "__r")

        if how in ("semi", "anti"):
            if kr is not None:
                wide, entries = self._direct_wide, kr.entries
                key = ("probe_semi", how, entries, wide, tuple(lkeys),
                       tuple(rkeys), _side_key(left),
                       _side_key(right))
                if key not in self._kernels:
                    self._kernels[key] = kc.get_kernel(
                        key, lambda: lambda b, s, base, ext:
                        _probe_semi_kernel(
                            b, s, base, ext, rkeys, lkeys,
                            how == "anti", entries, wide))
                with timed(self.metrics, "join.semi"):
                    out = self._kernels[key](right, left, kr.base,
                                             kr.extent)
            else:
                key = ("semi", how, tuple(lkeys), tuple(rkeys),
                       _side_key(left), _side_key(right))
                if key not in self._kernels:
                    self._kernels[key] = kc.get_kernel(
                        key, lambda: lambda b, s, o, g: _semi_kernel(
                            b, s, o, g, rkeys, lkeys, how == "anti"))
                with timed(self.metrics, "join.semi"):
                    order, seg0 = self._sort_order(right, left, rkeys,
                                                   lkeys)
                    out = self._kernels[key](right, left, order, seg0)
            self.metrics.add_rows(out.num_rows)
            self.metrics.add_batches()
            yield DeviceBatch(self._schema.names, out.columns,
                              out.num_rows)
            return

        if build_is_left:
            # right outer == left outer with sides swapped
            build, stream = left, right
            bkeys, skeys = lkeys, rkeys
            emit_how = "left" if how == "right" else how
            build_first = True
        else:
            build, stream = right, left
            bkeys, skeys = rkeys, lkeys
            emit_how = how
            build_first = False

        if kr is not None:
            yield from self._probe_pair(build, stream, bkeys, skeys,
                                        emit_how, build_first, kr)
            return
        ckey = ("count", emit_how, tuple(bkeys), tuple(skeys),
                _side_key(build), _side_key(stream))
        if ckey not in self._kernels:
            self._kernels[ckey] = kc.get_kernel(
                ckey, lambda: lambda b, s, o, g: _count_kernel(
                    b, s, o, g, bkeys, skeys, emit_how))
        with timed(self.metrics, "join.count"):
            order, seg0 = self._sort_order(build, stream, bkeys, skeys)
            total = int(self._kernels[ckey](build, stream, order,
                                            seg0))
        if total >= (1 << 31):
            # the emit kernel's per-row layout runs in i32 (i64 chains
            # are 3-14x slower under the pair emulation); a >2^31-row
            # single join output cannot be materialized as one batch
            # anyway — fail loudly instead of wrapping silently
            raise MemoryError(
                f"join output of {total} rows exceeds the single-batch "
                f"2^31 limit; repartition the inputs")
        out_cap = bucket_rows(total)
        ekey = ("emit", emit_how, out_cap, tuple(bkeys), tuple(skeys),
                build_first, _side_key(build), _side_key(stream))
        if ekey not in self._kernels:
            self._kernels[ekey] = kc.get_kernel(
                ekey, lambda: lambda b, s, o, g: _emit_kernel(
                    b, s, o, g, bkeys, skeys, emit_how, out_cap,
                    build.names, stream.names, build_first))
        with timed(self.metrics, "join.emit"):
            out = self._kernels[ekey](build, stream, order, seg0)
        out = DeviceBatch(self._schema.names, out.columns, out.num_rows)
        if self.condition is not None:
            v = eval_tpu.evaluate(self.condition, out)
            out = compact(out, v.data.astype(jnp.bool_) & v.validity)
        self.metrics.add_rows(out.num_rows)
        self.metrics.add_batches()
        yield out


def _gather_partition(it) -> Optional[DeviceBatch]:
    batches = [b for b in it if int(b.num_rows)]
    return concat_batches(batches) if batches else None


class TpuShuffledHashJoinExec(_HashJoinBase):
    """Equi-join over co-partitioned children (hash exchanges inserted by
    the planner); each partition pair joins independently with the build
    partition coalesced to one batch, like the reference's build side
    (GpuHashJoin build on single coalesced batch).  Also accepts
    single-partition children (the degenerate pre-exchange shape)."""

    def execute(self):
        lits = self.children[0].execute()
        rits = self.children[1].execute()
        assert len(lits) == len(rits), \
            f"join children not co-partitioned: {len(lits)} vs {len(rits)}"
        # planner-stamped out-of-core resolution (join_partition.
        # resolve_oocore); unstamped execs — hand-built tests, the
        # knob off — keep today's unconditional gather exactly
        oocore = getattr(self, "_oocore", None)

        def run_streamed(lit, rit):
            """inner/left/semi/anti: build side coalesced once, STREAM
            side probes per batch (reference: GpuHashJoin.scala:193-326
            streams the probe side) — the stream partition is never
            concatenated into one giant batch.  Cost note: each probe
            batch re-groups the combined build+batch key space (the
            sort-based formulation has no persistent hash table);
            coalesce goals keep probe batches per partition few.
            """
            from spark_rapids_tpu.mem.spill import register_or_hold
            from spark_rapids_tpu.obs import trace as obstrace
            if oocore is not None:
                rbs = [b for b in rit if int(b.num_rows)]
                build_bytes = sum(int(b.nbytes()) for b in rbs)
                if rbs and build_bytes > oocore["budget"]:
                    from spark_rapids_tpu.exec import join_partition
                    yield from join_partition.grace_join(
                        self, lit, rbs, build_bytes, oocore,
                        build_is_left=False, gathered=False)
                    return
                right = concat_batches(rbs) if rbs else None
            else:
                right = _gather_partition(rit)
            if right is None:
                if self.how == "inner":
                    # nothing can match — but the stream iterator must
                    # still drain: AQE readers release their
                    # spill-catalog claims inside the generator body
                    for _ in lit:
                        pass
                    return
                right = _empty_like(self.children[1].schema)
            # the build partition is held across the whole stream probe
            # loop — keep it spillable between probe batches
            with obstrace.span("join.build"):
                kr = self._key_range(right, False)
            with register_or_hold(right) as rh:
                for lb in lit:
                    if not int(lb.num_rows):
                        continue
                    yield from self._join_pair(lb, rh.get(), kr)

        def run_gathered(lit, rit):
            """right/full: unmatched-build emission needs every stream
            batch, so the pair joins as two single batches."""
            if oocore is not None:
                lbs = [b for b in lit if int(b.num_rows)]
                rbs = [b for b in rit if int(b.num_rows)]
                # _join_pair's build-side resolution: right-outer
                # builds on the LEFT (swapped-sides left outer), full
                # builds on the right
                build_is_left = self.how == "right"
                bbs = lbs if build_is_left else rbs
                build_bytes = sum(int(b.nbytes()) for b in bbs)
                if bbs and build_bytes > oocore["budget"]:
                    from spark_rapids_tpu.exec import join_partition
                    yield from join_partition.grace_join(
                        self, rbs if build_is_left else lbs, bbs,
                        build_bytes, oocore,
                        build_is_left=build_is_left, gathered=True)
                    return
                left = concat_batches(lbs) if lbs else None
                right = concat_batches(rbs) if rbs else None
            else:
                left = _gather_partition(lit)
                right = _gather_partition(rit)
            if left is None or right is None:
                if left is not None or right is not None:
                    left = left if left is not None else \
                        _empty_like(self.children[0].schema)
                    right = right if right is not None else \
                        _empty_like(self.children[1].schema)
                else:
                    return
            build_is_left = self._build_is_left("right")
            kr = self._key_range(left if build_is_left else right,
                                 build_is_left)
            yield from self._join_pair(left, right, kr)

        run = run_gathered if self.how in ("right", "full") \
            else run_streamed
        return [run(l, r) for l, r in zip(lits, rits)]


class TpuBroadcastHashJoinExec(_BroadcastBuildMixin, _HashJoinBase):
    """Equi-join with the build side broadcast: gathered once across all
    its partitions, then probed per stream batch so the stream side stays
    partitioned (reference: GpuBroadcastHashJoinExec — broadcast host
    batch -> device once per task, then probe per batch)."""

    def __init__(self, *args, build_side: str = "right",
                 transport: str = "local"):
        super().__init__(*args)
        self._init_build(build_side)
        # Spark's build-side validity, as the planner applies it
        # (left/semi/anti broadcast the right, right outer the left):
        # the pair's table is built on the broadcast side, so its key
        # range is read once a build and never once a stream batch
        assert self._build_is_left(build_side) == (build_side == "left"), \
            f"a {self.how} join cannot broadcast its {build_side} side"
        # 'ici': replicate the build side over the device mesh with one
        # mesh broadcast so each stream shard joins against its LOCAL
        # copy (GpuBroadcastExchangeExec analog) instead of depending on
        # a single in-process batch
        self.transport = transport
        self._bcast_map = None
        import threading
        self._bcast_lock = threading.Lock()

    def _built_key_range(self, built: DeviceBatch):
        return self._key_range(built, self.build_side == "left")

    def _build_broadcast(self):
        built = self._build()   # takes _build_lock itself
        with self._bcast_lock:
            if self._bcast_map is None:
                from spark_rapids_tpu.shuffle import ici
                if built is None:
                    self._bcast_map = {}
                elif self.transport == "ici_ring":
                    # point-to-point plane: ppermute ring rotation
                    self._bcast_map = ici.ring_broadcast_batch(built)
                    self.metrics.extra["ici_ring_hops"] = \
                        max(len(self._bcast_map) - 1, 0)
                else:
                    self._bcast_map = ici.broadcast_batch(built)
                    self.metrics.extra["ici_broadcast_devices"] = \
                        len(self._bcast_map)
        return self._bcast_map

    def _build_for(self, stream_batch: DeviceBatch):
        """The build-side copy colocated with this stream batch."""
        if self.transport not in ("ici", "ici_ring"):
            return self._build()
        bmap = self._build_broadcast()
        if not bmap:
            return None
        if stream_batch.columns:
            devs = stream_batch.columns[0].data.devices()
            for d in devs:
                if d in bmap:
                    return bmap[d]
        return next(iter(bmap.values()))

    def execute(self):
        stream_side = 0 if self.build_side == "right" else 1
        sits = self.children[stream_side].execute()

        def run(sit):
            # materialize (and for ICI, broadcast) the build side BEFORE
            # pulling any stream batch: stream scans hold the TPU
            # semaphore across their yield, and the build side's own
            # scan acquiring it then would deadlock the task pool
            if self.transport in ("ici", "ici_ring"):
                self._build_broadcast()
            else:
                self._build()
            for sb in sit:
                if not int(read_host(sb.num_rows, "join.streamRowsWait")):
                    continue
                build = self._build_for(sb)
                b = build if build is not None else \
                    _empty_like(self.children[1 - stream_side].schema)
                # read once, in _build; an empty side is host-known
                kr = self._built_range if build is not None else \
                    self._built_key_range(b)
                if self.build_side == "right":
                    yield from self._join_pair(sb, b, kr, "right")
                else:
                    yield from self._join_pair(b, sb, kr, "left")

        return [run(it) for it in sits]


def _empty_like(schema: Schema) -> DeviceBatch:
    """A 0-row device batch (for outer joins against an empty side)."""
    from spark_rapids_tpu.columnar.batch import from_arrow
    import pyarrow as pa
    t = pa.Table.from_arrays(
        [pa.array([], type=f.dtype.to_arrow()) for f in schema.fields],
        names=schema.names)
    return from_arrow(t)


class _NestedLoopBase(TpuExec):
    """Shared cross-product kernel (Table.crossJoin + filter analog)."""

    def __init__(self, left: PhysicalPlan, right: PhysicalPlan,
                 condition: Optional[ir.Expression], schema: Schema):
        super().__init__()
        self.children = (left, right)
        self.condition = condition
        self._schema = schema
        self._kernels = {}

    @property
    def schema(self) -> Schema:
        return self._schema

    def _cross_pair(self, left: DeviceBatch, right: DeviceBatch):
        nl, nr = int(left.num_rows), int(right.num_rows)
        if nl == 0 or nr == 0:
            return
        from spark_rapids_tpu.exec import kernel_cache as kc
        from spark_rapids_tpu.obs import registry as obsreg
        obsreg.get_registry().inc("join.path.product")
        # same dispatch-boundary canonicalization as the hash joins:
        # the kernel builds its output with positional names (the
        # condition reads by ordinal), the real schema restamps after
        left = _canon_side(left, "__l")
        right = _canon_side(right, "__r")
        n_out = left.num_cols + right.num_cols
        out_cap = bucket_rows(nl * nr)
        key = ("cross", out_cap, kc.expr_sig(self.condition),
               _side_key(left), _side_key(right))
        if key not in self._kernels:
            def impl(l, r):
                total = l.num_rows * r.num_rows
                k = jnp.arange(out_cap, dtype=jnp.int64)
                li = jnp.clip(k // jnp.maximum(r.num_rows, 1), 0,
                              l.capacity - 1)
                ri = jnp.clip(k % jnp.maximum(r.num_rows, 1), 0,
                              r.capacity - 1)
                valid = k < total
                cols = [c.gather(li, valid) for c in l.columns] + \
                    [c.gather(ri, valid) for c in r.columns]
                out = DeviceBatch([f"_c{i}" for i in range(n_out)],
                                  cols, total)
                if self.condition is not None:
                    v = eval_tpu.evaluate(self.condition, out)
                    out = compact(out, v.data.astype(jnp.bool_) &
                                  v.validity)
                return out
            self._kernels[key] = kc.get_kernel(key, lambda: impl)
        with timed(self.metrics, "join.nestedLoop"):
            out = self._kernels[key](left, right)
        out = DeviceBatch(self._schema.names, out.columns, out.num_rows)
        self.metrics.add_rows(out.num_rows)
        self.metrics.add_batches()
        yield out


class TpuBroadcastNestedLoopJoinExec(_BroadcastBuildMixin, _NestedLoopBase):
    """Cross join (+ optional condition) with one side broadcast
    (reference: GpuBroadcastNestedLoopJoinExec.scala:311).  The stream
    side keeps its partitioning; the build side is gathered once."""

    def __init__(self, left: PhysicalPlan, right: PhysicalPlan,
                 condition: Optional[ir.Expression], schema: Schema,
                 build_side: str = "right"):
        super().__init__(left, right, condition, schema)
        self._init_build(build_side)

    def execute(self):
        stream_side = 0 if self.build_side == "right" else 1
        sits = self.children[stream_side].execute()

        def run(sit):
            build = self._build()
            if build is None:
                return
            for sb in sit:
                if not int(sb.num_rows):
                    continue
                if stream_side == 0:
                    yield from self._cross_pair(sb, build)
                else:
                    yield from self._cross_pair(build, sb)

        return [run(it) for it in sits]


class TpuCartesianProductExec(_NestedLoopBase):
    """Partition-pairwise cross join: output partition (i, j) crosses left
    partition i with right partition j (reference:
    GpuCartesianProductExec.scala:304 — pairwise cross join with
    serialized-batch RDD)."""

    def execute(self):
        lits = self.children[0].execute()
        rits = self.children[1].execute()
        # right partitions are iterated once per left partition: gather
        # each right partition lazily and cache (the serialized-batch
        # broadcast-to-all-pairs role)
        rcache: dict = {}

        def right_batch(j: int, rit) -> Optional[DeviceBatch]:
            if j not in rcache:
                rcache[j] = _gather_partition(rit)
            return rcache[j]

        def run(i, lit, j, rit):
            left = _gather_partition(lit) if (i, "l") not in rcache else \
                rcache[(i, "l")]
            rcache[(i, "l")] = left
            right = right_batch(j, rit)
            if left is None or right is None:
                return
            yield from self._cross_pair(left, right)

        return [run(i, lit, j, rit)
                for i, lit in enumerate(lits)
                for j, rit in enumerate(rits)]

"""Shuffle exchange execs + the four partitionings.

Reference analogs:
  * ``GpuShuffleExchangeExec`` (reference:
    org/.../execution/GpuShuffleExchangeExec.scala:143) — partitions each
    batch on-device, then moves slices through a shuffle data plane.
  * The four partitionings — ``GpuHashPartitioning`` (murmur3 pmod,
    GpuHashPartitioning.scala:29), ``GpuRangePartitioning`` (sampled bounds,
    GpuRangePartitioning.scala:169), ``GpuRoundRobinPartitioning``
    (GpuRoundRobinPartitioning.scala:97), ``GpuSinglePartitioning``
    (GpuSinglePartitioning.scala:61), sliced on device exactly like
    ``GpuPartitioning.sliceInternalOnGpu`` (GpuPartitioning.scala:45).
  * The local block store + Arrow IPC serializer is the default data plane
    (Spark sort-shuffle + GpuColumnarBatchSerializer analog); the reader
    side concatenates slices per output partition, the
    ``ShuffleCoalesceExec`` role (ShuffleCoalesceExec.scala:199).

TPU-first departures from the reference:
  * Slicing is one reorder + contiguous ranges (a stable argsort by target
    partition), not N cudf ``contiguous_split`` buffers — XLA keeps it one
    fused gather.
  * Range partitioning needs no reservoir sampling (reference:
    SamplingUtils.scala:120): the exchange materializes its input anyway,
    so bounds come from an exact rank — a total-order lexsort rank split
    into even spans, with each equal-key group snapped to one partition
    (segment-head cohesion). Exactly balanced, same contract as Spark's
    RangePartitioner (equal keys co-located, partitions ordered).
"""

from __future__ import annotations

import itertools
import threading
from dataclasses import dataclass
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np
import pyarrow as pa

import jax
import jax.numpy as jnp

from spark_rapids_tpu import config as cfg
from spark_rapids_tpu import dtypes as dt
from spark_rapids_tpu.columnar.batch import (DeviceBatch, DeviceColumn,
                                             _bcast, bucket_rows,
                                             concat_batches, from_arrow,
                                             read_host, to_arrow)
from spark_rapids_tpu.exec import sortkeys
from spark_rapids_tpu.exec.base import (PhysicalPlan, TpuExec, timed,
                                        timed_extra)
from spark_rapids_tpu.exec.cpu import concat_tables, _empty_table
from spark_rapids_tpu.expr import eval_cpu, eval_tpu, ir
from spark_rapids_tpu.expr.eval_tpu import ColVal
from spark_rapids_tpu.plan.logical import Schema, SortOrder
from spark_rapids_tpu.sched import cancel as _cancel
from spark_rapids_tpu.shuffle.serializer import (deserialize_table,
                                                 get_codec, serialize_table)


# ---------------------------------------------------------------------------
# Partitioning specs
# ---------------------------------------------------------------------------

@dataclass
class Partitioning:
    num_partitions: int

    def exprs(self) -> List[ir.Expression]:
        return []

    def cache_sig(self) -> Any:
        """Kernel-cache signature: everything the compiled target kernel
        closes over.  Subclasses with extra compile-time state (sort
        direction, null ordering) must extend this."""
        from spark_rapids_tpu.exec import kernel_cache as kc
        return kc.exprs_sig(self.exprs())


@dataclass
class SinglePartitioning(Partitioning):
    pass


@dataclass
class HashPartitioning(Partitioning):
    keys: List[ir.Expression] = None

    def exprs(self) -> List[ir.Expression]:
        return list(self.keys)


@dataclass
class RoundRobinPartitioning(Partitioning):
    pass


@dataclass
class RangePartitioning(Partitioning):
    orders: List[SortOrder] = None

    def exprs(self) -> List[ir.Expression]:
        return [o.expr for o in self.orders]

    def cache_sig(self) -> Any:
        # ascending / nulls-first are baked into the compiled range-target
        # kernel (sortkeys.encode_keys) — they must be part of the key or
        # an ASC kernel gets reused for a DESC order on the same expr.
        from spark_rapids_tpu.exec import kernel_cache as kc
        return tuple((kc.expr_sig(o.expr), o.ascending,
                      o.nulls_first_resolved) for o in self.orders)


class _ReleasingIter:
    """Partition-reader wrapper that fires a release callback exactly once
    — on exhaustion, on ``close()``, or at garbage collection — so an
    abandoned (never-iterated) reader still gives up its claim on the
    exchange's device-resident shards."""

    def __init__(self, gen, release):
        self._gen = gen
        self._release = release
        self._released = False

    def __iter__(self):
        return self

    def __next__(self):
        try:
            return next(self._gen)
        except BaseException:
            self._do_release()
            raise

    def _do_release(self):
        if not self._released:
            self._released = True
            self._release()

    def close(self):
        self._gen.close()
        self._do_release()

    def __del__(self):
        self._do_release()


# ---------------------------------------------------------------------------
# Device-side target computation
# ---------------------------------------------------------------------------

def hash_targets(batch: DeviceBatch, keys: Sequence[ir.Expression],
                 n_parts: int) -> jnp.ndarray:
    """Spark murmur3(seed=42) pmod targets (GpuHashPartitioning analog)."""
    from spark_rapids_tpu.expr.eval_tpu import hash_colval
    cap = batch.capacity
    h = jnp.full((cap,), np.int32(42), dtype=jnp.int32)
    for k in keys:
        v = eval_tpu.evaluate(k, batch)
        h = hash_colval(v, h)
    m = h % np.int32(n_parts)
    return jnp.where(m < 0, m + n_parts, m).astype(jnp.int32)


def range_targets_from_order(batch: DeviceBatch,
                             orders: Sequence[SortOrder],
                             order: jnp.ndarray,
                             n_parts: int) -> jnp.ndarray:
    """Exact-rank range targets with equal-key group cohesion, with the
    (expensive, shared-kernel) sort already done; re-derives key groups
    for boundary detection only."""
    key_groups = []
    for o in orders:
        v = eval_tpu.evaluate(o.expr, batch)
        key_groups.append(sortkeys.encode_keys(
            v, o.ascending, o.nulls_first_resolved))
    return _range_spans(batch, key_groups, order, n_parts)


def _range_spans(batch: DeviceBatch, key_groups, order: jnp.ndarray,
                 n_parts: int) -> jnp.ndarray:
    exists = batch.row_mask()
    cap = batch.capacity
    n = batch.num_rows
    # rank r of sorted position -> span r*n_parts//n; group cohesion: every
    # row of an equal-key group takes the group head's span
    new_group = sortkeys.group_boundaries(key_groups, order, exists)
    seg = jnp.cumsum(new_group.astype(jnp.int32)) - 1
    pos = jnp.arange(cap, dtype=jnp.int64)
    head_pos = jax.ops.segment_min(
        jnp.where(jnp.take(exists, order), pos, np.int64(1 << 62)), seg,
        num_segments=cap)
    span = (jnp.take(head_pos, seg) * n_parts) // jnp.maximum(n, 1)
    span = jnp.clip(span, 0, n_parts - 1).astype(jnp.int32)
    # scatter back to original row order
    target = jnp.zeros((cap,), dtype=jnp.int32).at[order].set(span)
    return target


def round_robin_targets(batch: DeviceBatch, n_parts: int,
                        start: jnp.ndarray) -> jnp.ndarray:
    cap = batch.capacity
    return ((jnp.arange(cap, dtype=jnp.int32) + start.astype(jnp.int32))
            % np.int32(n_parts))


def partition_batch(batch: DeviceBatch, target: jnp.ndarray, n_parts: int
                    ) -> Tuple[DeviceBatch, jnp.ndarray]:
    """Reorder rows so each output partition is one contiguous span.

    Returns (reordered batch, per-partition counts).  One stable argsort —
    the XLA formulation of cudf contiguous_split
    (GpuPartitioning.sliceInternalOnGpu analog).
    """
    cap = batch.capacity
    exists = batch.row_mask()
    t = jnp.where(exists, target, n_parts)  # padding parks after all spans
    counts = jnp.zeros((n_parts,), dtype=jnp.int32).at[t].add(
        exists.astype(jnp.int32), mode="drop")
    order = jnp.argsort(t, stable=True)
    cols = [c.gather(order, jnp.take(exists, order))
            for c in batch.columns]
    return DeviceBatch(batch.names, cols, batch.num_rows), counts


def slice_span(batch: DeviceBatch, offset: jnp.ndarray, count: jnp.ndarray,
               out_cap: int) -> DeviceBatch:
    """Extract rows [offset, offset+count) into a fresh bucketed batch."""
    idx = offset + jnp.arange(out_cap, dtype=jnp.int32)
    valid = jnp.arange(out_cap, dtype=jnp.int32) < count
    idx = jnp.clip(idx, 0, batch.capacity - 1)
    cols = [c.gather(idx, valid) for c in batch.columns]
    return DeviceBatch(batch.names, cols, count)


def prefix_span(batch: DeviceBatch, count: jnp.ndarray,
                out_cap: int) -> DeviceBatch:
    """``slice_span(batch, 0, count, out_cap)`` for an ``out_cap`` at
    most the batch's capacity, by a static prefix slice and a mask: a
    gather pays for every slot (0.34 s a chip for four columns at
    2,097,152 slots, PERF.md §6), a slice is a copy."""
    valid = jnp.arange(out_cap, dtype=jnp.int32) < count
    cols = []
    for c in batch.columns:
        data = c.data[:out_cap]
        data = jnp.where(_bcast(valid, data), data,
                         jnp.zeros((), data.dtype))
        cols.append(DeviceColumn(
            c.dtype, data, c.validity[:out_cap] & valid,
            None if c.lengths is None
            else jnp.where(valid, c.lengths[:out_cap], 0),
            None if c.elem_validity is None
            else c.elem_validity[:out_cap] & valid[:, None],
            c.vbits))
    return DeviceBatch(batch.names, cols, count)


# ---------------------------------------------------------------------------
# Local shuffle block store (default data plane)
# ---------------------------------------------------------------------------

class ShuffleBlockStore:
    """In-process map-output store of serialized Arrow slices.

    Plays the role of Spark's sort-shuffle files + block manager for the
    default path (one executor); blocks are keyed (map_idx, reduce_idx)
    like shuffle block ids.
    """

    def __init__(self, codec_name: str):
        self.codec = get_codec(codec_name)
        self._blocks: Dict[Tuple[int, int], bytes] = {}
        self.bytes_written = 0

    def put(self, map_idx: int, reduce_idx: int, table: pa.Table) -> None:
        if table.num_rows == 0:
            return
        data = serialize_table(table, self.codec)
        self.bytes_written += len(data)
        from spark_rapids_tpu.obs import registry as obsreg
        obsreg.get_registry().inc_many(
            ("shuffle.bytesWritten", len(data)),
            ("shuffle.blocksWritten", 1))
        self._blocks[(map_idx, reduce_idx)] = data

    def fetch(self, reduce_idx: int) -> List[pa.Table]:
        out = []
        for (m, r), data in sorted(self._blocks.items()):
            if r == reduce_idx:
                out.append(deserialize_table(data))
        return out


class ShuffleMapTaskError(Exception):
    """A shipped map stage failed deterministically: the executor is
    healthy and replied ``ok=False`` (task exception, unknown op).
    Deliberately NOT a RuntimeError/OSError: the pipelined submit
    ladder retries (and hard-kills + respawns) only on those transport
    shapes — killing a healthy shared executor over a task bug would
    wipe concurrent exchanges' map output for a failure a re-run
    cannot fix — and the read side propagates this raw instead of
    degrading to the CPU block store, exactly as the sequential
    (depth=0) barrier path surfaces the same failure."""


class _MapOutputTracker:
    """Per-map completion book for the pipelined exchange (the
    MapOutputTracker role at map-task granularity).

    Submit threads report each ``(executor_id, map_id)`` the moment the
    executor's ``map_done`` event lands (the blocks are already in its
    catalog); reducers iterate :meth:`events` and fetch each completed
    map's output immediately instead of barriering on the whole map
    stage.  The completed list is append-only and deduplicated, so a
    map-stage RE-RUN after an executor death re-announces the same pairs
    harmlessly — readers key their fetched state by the pair.
    """

    def __init__(self):
        self._cond = threading.Condition(threading.Lock())
        self._completed: List[Tuple[str, int]] = []
        self._seen = set()
        self._open_execs = 0
        self._failed: Optional[BaseException] = None
        self._bucket_bytes: Optional[List[int]] = None

    def record_sizes(self, map_id: int, sizes: Sequence[int]) -> None:
        """Aggregate one map task's per-reduce-bucket output sizes as it
        completes (MapOutputStatistics accumulation,
        MapOutputTracker.registerMapOutput analog).  The running totals
        are what skew detection consults BEFORE any reducer fetches, so
        a hot bucket can be split while its blocks are still per-map."""
        with self._cond:
            if self._bucket_bytes is None:
                self._bucket_bytes = [0] * len(sizes)
            for i, s in enumerate(sizes):
                self._bucket_bytes[i] += int(s)

    def bucket_totals(self) -> Optional[List[int]]:
        """Aggregated per-reduce-bucket bytes across all completed maps
        (None until the first map reports)."""
        with self._cond:
            return None if self._bucket_bytes is None \
                else list(self._bucket_bytes)

    def open_exec(self) -> None:
        with self._cond:
            self._open_execs += 1

    def map_done(self, executor_id: str, map_id: int) -> None:
        with self._cond:
            key = (executor_id, map_id)
            if key not in self._seen:
                self._seen.add(key)
                self._completed.append(key)
            self._cond.notify_all()

    def exec_done(self, executor_id: str, map_ids) -> None:
        """Final (authoritative) map list for one executor's stage —
        covers a stage whose events were lost or a non-streaming
        re-submit."""
        with self._cond:
            for m in map_ids:
                key = (executor_id, m)
                if key not in self._seen:
                    self._seen.add(key)
                    self._completed.append(key)
            self._open_execs -= 1
            self._cond.notify_all()

    def fail(self, exc: BaseException) -> None:
        """A submit thread died (task failure / respawn crash-loop):
        readers must surface it instead of waiting out the timeout."""
        with self._cond:
            if self._failed is None:
                self._failed = exc
            self._open_execs -= 1
            self._cond.notify_all()

    @property
    def open_execs(self) -> int:
        """Map stages still in flight (submit thread neither finished
        nor failed) — the read-side recovery ladder checks this before
        degrading: a fetch that raced a mid-stage death should spend
        its retry budget on the submit thread's in-flight re-run, not
        prematurely fall back."""
        with self._cond:
            return self._open_execs

    def batches(self, timeout_s: float, token=None):
        """Yield LISTS of ``(executor_id, map_id)`` completions in
        announce order — everything newly available per step, blocking
        only when nothing is — until every opened executor's stage
        finished.  Batching lets a reader fetch all of one executor's
        already-completed maps in ONE do_fetch round trip (the
        per-peer fetch pattern of the sequential path), paying per-map
        round trips only for maps that genuinely trickle in.
        ``timeout_s`` bounds the NO-PROGRESS wait (a wedged-but-alive
        executor surfaces as a shuffle timeout, which escalates
        through the standard recovery ladder); ``None`` waits
        indefinitely (``pipeline.timeoutMs=0`` — dead executors still
        surface through :meth:`fail`).  A fired CancelToken raises at
        the next wait tick (the wait is chunked so cancellation lands
        promptly)."""
        import time as _time
        from spark_rapids_tpu.shuffle.iterator import \
            RapidsShuffleTimeoutException
        i = 0
        while True:
            with self._cond:
                # wall-clock no-progress deadline, re-stamped only per
                # DELIVERED batch (each yield step re-enters here): a
                # condition wakeup that brought no new completion —
                # e.g. a crash-looping executor's re-run re-announcing
                # already-seen map ids — must not push the bound out,
                # or a genuinely wedged sibling stage never escalates
                t0 = _time.monotonic()
                while (i >= len(self._completed) and
                       self._open_execs > 0 and self._failed is None):
                    if token is not None and token.is_cancelled:
                        token.check()
                    self._cond.wait(timeout=0.1)
                    if i < len(self._completed):
                        break   # real progress: deliver it
                    if timeout_s is not None and \
                            _time.monotonic() - t0 >= timeout_s:
                        raise RapidsShuffleTimeoutException(
                            "pipelined shuffle: no map completion "
                            f"for {timeout_s}s "
                            f"({self._open_execs} stages open)")
                if i < len(self._completed):
                    batch = self._completed[i:]
                    i = len(self._completed)
                else:
                    if self._failed is not None:
                        exc = self._failed
                        if isinstance(exc, (RuntimeError, OSError)) \
                                and not isinstance(
                                    exc, _cancel.QueryCancelledError):
                            # transport-side map-stage loss that
                            # exhausted the submit retry ladder:
                            # surface as fetch-failed so the read
                            # side's ONE recovery ladder
                            # (fetch_with_recovery) owns it — re-run
                            # anything recoverable, else degrade to
                            # the CPU block store when cpuFallback
                            # allows, matching the depth=0 path's
                            # behavior for a lost executor.  Task
                            # failures (ShuffleMapTaskError) and
                            # cancellation stay raw: both must fail
                            # the query exactly like the sequential
                            # barrier path, never fall back.
                            from spark_rapids_tpu.shuffle.iterator \
                                import RapidsShuffleFetchFailedException
                            raise RapidsShuffleFetchFailedException(
                                "pipelined shuffle: map stage lost: "
                                f"{exc}") from exc
                        raise exc
                    return
            yield batch


# per-exchange reduce-bucket size distribution (bytes) — byte-scaled
# bounds, not the registry's default ms bounds
_BUCKET_BYTE_BOUNDS = (1 << 10, 16 << 10, 64 << 10, 256 << 10, 1 << 20,
                       4 << 20, 16 << 20, 64 << 20, 256 << 20)

# skew_map_side() is idempotent under this module lock (a per-instance
# lock would break plan-fragment pickling for the process transport)
_SKEW_MAP_LOCK = threading.Lock()


class SkewMapOutput:
    """Map output held back at per-(map, reduce-bucket) granularity.

    The default reduce path concats every bucket before the join sees
    it; this keeps blocks separate through the map-output tracker so a
    hot bucket can be re-planned (split / replicated) BEFORE the reduce
    concat — the window Spark's AQE exploits via MapOutputStatistics
    (OptimizeSkewedJoin reads them between stages).  ``totals`` /
    ``row_counts`` come from the tracker's aggregation, not a second
    pass over the blocks."""

    def __init__(self, exchange: "TpuShuffleExchangeExec", host: bool,
                 store: Optional[ShuffleBlockStore],
                 dev: Optional[List[List[DeviceBatch]]],
                 totals: List[int], row_counts: List[int]):
        self.exchange = exchange
        self.host = host
        self.store = store
        self.dev = dev
        self.totals = totals
        self.row_counts = row_counts

    def fetch(self, pidx: int) -> List[DeviceBatch]:
        """All of reduce bucket ``pidx`` as device batches (one uploaded
        batch for the host plane, the raw slices for the device plane)."""
        ex = self.exchange
        if self.host:
            tables = [t for t in self.store.fetch(pidx) if t.num_rows]
            if not tables:
                return []
            t = concat_tables(tables, ex.schema)
            with timed(ex.metrics, "exchange.upload"):
                return [from_arrow(t, ex.min_bucket)]
        return [s for s in self.dev[pidx] if int(s.num_rows)]

class CpuShuffleExchangeExec(PhysicalPlan):
    """Host-side exchange (the stock-Spark role for fallback parity)."""

    def __init__(self, child: PhysicalPlan, partitioning: Partitioning):
        super().__init__()
        self.children = (child,)
        self.partitioning = partitioning

    @property
    def schema(self) -> Schema:
        return self.children[0].schema

    def _targets(self, table: pa.Table, start: int) -> np.ndarray:
        p = self.partitioning
        n = table.num_rows
        if isinstance(p, SinglePartitioning):
            return np.zeros(n, dtype=np.int64)
        if isinstance(p, RoundRobinPartitioning):
            # `start` carries the running row offset so the round-robin
            # wheel keeps turning across input batches
            return (np.arange(n, dtype=np.int64) + start) % p.num_partitions
        if isinstance(p, HashPartitioning):
            h = eval_cpu.evaluate(ir.Murmur3Hash(list(p.keys), 42), table)
            m = np.asarray(h.data, dtype=np.int64) % p.num_partitions
            return np.where(m < 0, m + p.num_partitions, m)
        if isinstance(p, RangePartitioning):
            # same exact-rank + group-cohesion contract as the device path
            import pyarrow.compute as pc
            vals = [eval_cpu.evaluate(o.expr, table) for o in p.orders]
            # stable multi-key order built least-significant-key-first
            # (identical technique to CpuSortExec)
            order = np.arange(n)
            for v, o in zip(reversed(vals), reversed(p.orders)):
                arr = eval_cpu.to_arrow_array(v).take(pa.array(order))
                oi = pc.sort_indices(
                    arr,
                    sort_keys=[("", "ascending" if o.ascending
                                else "descending")],
                    null_placement="at_start" if o.nulls_first_resolved
                    else "at_end")
                order = order[np.asarray(oi)]

            # vectorized equal-key group heads over the sorted order:
            # adjacent-row equality per key (nulls equal, NaN==NaN,
            # -0.0==0.0), then a prefix-max of new-group positions
            same = np.ones(n, dtype=bool)
            for v in vals:
                sv = v.data[order]
                sm = v.valid[order]
                if np.issubdtype(np.asarray(v.data).dtype, np.floating):
                    x = sv.astype(np.float64)
                    x = np.where(x == 0.0, 0.0, x)  # fold -0.0
                    eq = (x[1:] == x[:-1]) | (np.isnan(x[1:]) &
                                              np.isnan(x[:-1]))
                else:
                    eq = sv[1:] == sv[:-1]
                pair_eq = np.concatenate(
                    [[True], (sm[1:] & sm[:-1] & eq) |
                     (~sm[1:] & ~sm[:-1])])
                same &= pair_eq
            pos = np.arange(n, dtype=np.int64)
            heads = np.maximum.accumulate(np.where(same, 0, pos))
            heads[0] = 0
            span = (heads * p.num_partitions) // max(n, 1)
            target = np.zeros(n, dtype=np.int64)
            target[order] = np.clip(span, 0, p.num_partitions - 1)
            return target
        raise NotImplementedError(type(p).__name__)

    def execute(self):
        n_parts = self.partitioning.num_partitions
        state = {"slices": None}
        lock = threading.Lock()

        def input_batches():
            """(map_idx, table) pairs; range partitioning needs the global
            rank, so its whole input coalesces into one logical map task."""
            if isinstance(self.partitioning, RangePartitioning):
                all_t = []
                for it in self.children[0].execute():
                    all_t.extend(t for t in it if t.num_rows)
                t = concat_tables(all_t, self.schema)
                if t.num_rows:
                    yield 0, t
                return
            for m, it in enumerate(self.children[0].execute()):
                for t in it:
                    if t.num_rows:
                        yield m, t

        def materialize():
            # readers may run on concurrent tasks; one thread materializes
            with lock:
                return _materialize_locked()

        def _materialize_locked():
            if state["slices"] is not None:
                return state["slices"]
            slices: List[List[pa.Table]] = [[] for _ in range(n_parts)]
            rows_seen = 0
            for m, t in input_batches():
                tgt = self._targets(t, rows_seen)
                rows_seen += t.num_rows
                order = np.argsort(tgt, kind="stable")
                sorted_t = t.take(pa.array(order))
                counts = np.bincount(tgt, minlength=n_parts)
                off = 0
                for pidx in range(n_parts):
                    c = int(counts[pidx])
                    if c:
                        slices[pidx].append(sorted_t.slice(off, c))
                    off += c
            state["slices"] = slices
            return slices

        def reader(pidx: int) -> Iterator[pa.Table]:
            parts = materialize()[pidx]
            out = concat_tables(parts, self.schema)
            self.metrics.num_output_rows += out.num_rows
            yield out

        return [reader(p) for p in range(n_parts)]


# sort keys a chip hands over for the range bounds of a placed exchange
_RANGE_SAMPLE = 1024


def _range_bounds(samples, n_parts: int) -> np.ndarray:
    """``[words, n_parts - 1]`` bounds from each chip's sample of sort
    words (``(rows, [words, sample])``; a chip's sample stands for its
    rows): the keys at the weighted quantiles, most significant word
    first."""
    words = np.concatenate([s for _, s in samples], axis=1)
    weight = np.concatenate([np.full(s.shape[1], n / s.shape[1])
                             for n, s in samples])
    order = np.lexsort(words[::-1])
    share = np.cumsum(weight[order]) / max(weight.sum(), 1e-300)
    at = [min(int(np.searchsorted(share, (k + 1) / n_parts)),
              len(order) - 1) for k in range(n_parts - 1)]
    return words[:, order[at]]


class TpuShuffleExchangeExec(TpuExec):
    """Device-side exchange.

    transport='device': slices stay HBM-resident, handed to readers as
    DeviceBatches (the RapidsShuffleManager device-store analog for one
    process, RapidsShuffleInternalManager.scala:90-155).
    transport='local': each slice is downloaded, Arrow-IPC-serialized with
    the configured codec into the block store, and re-uploaded on read (the
    default sort-shuffle path analog, honest about the host round trip).
    transport='manager': slices are written through the accelerated
    TpuShuffleManager — device-resident ShuffleBufferCatalog on simulated
    executors, fetched back over the transport SPI's tag-matched
    client/server protocol (the full RapidsShuffleManager data plane,
    RapidsShuffleInternalManager.scala:90-186).
    transport='process': map stages execute in spawned executor OS
    processes (shuffle/executor_proc.py) that register output in their
    own catalogs and serve reducer pulls over TcpShuffleTransport, with
    fetch-failed -> map-stage-retry on executor death — the planned
    query genuinely crosses process boundaries (the executor-JVM fleet,
    RapidsShuffleInternalManager.scala:90-186 + UCX.scala:53-533).
    """

    def __init__(self, child: PhysicalPlan, partitioning: Partitioning,
                 conf_obj):
        super().__init__()
        self.children = (child,)
        self.partitioning = partitioning
        self.conf_obj = conf_obj
        self.transport = str(conf_obj.get(cfg.SHUFFLE_TRANSPORT))
        self.codec_name = str(conf_obj.get(cfg.SHUFFLE_COMPRESSION_CODEC))
        self.min_bucket = conf_obj.get(cfg.MIN_BUCKET_ROWS)
        self._kernels: Dict[Any, Any] = {}
        self._skew_out: Optional[SkewMapOutput] = None

    @property
    def schema(self) -> Schema:
        return self.children[0].schema

    def _target_fn(self):
        """(batch, start) -> per-row target partition ids; `start` is the
        running row offset (only round-robin consumes it, as a traced
        operand so one compiled kernel serves every batch)."""
        p = self.partitioning
        if isinstance(p, SinglePartitioning):
            return lambda b, st: jnp.zeros((b.capacity,), dtype=jnp.int32)
        if isinstance(p, RoundRobinPartitioning):
            return lambda b, st: round_robin_targets(b, p.num_partitions,
                                                     st)
        if isinstance(p, HashPartitioning):
            return lambda b, st: hash_targets(b, p.keys, p.num_partitions)
        # RangePartitioning never reaches here: _compute_targets routes
        # it through the shared-sort split (keys kernel ->
        # sortkeys.shared_lexsort -> range_targets_from_order) so the
        # minutes-scale XLA sort compile is never embedded per-schema
        raise NotImplementedError(type(p).__name__)

    def _compute_targets(self, batch: DeviceBatch,
                         rows_seen: int) -> jnp.ndarray:
        """Per-row target partition ids (padding rows -> n_parts), with
        any sort routed through the SHARED per-capacity kernels
        (sortkeys.shared_lexsort) instead of recompiling a sort inside
        every (partitioning, schema) kernel."""
        from spark_rapids_tpu.exec import kernel_cache as kc
        p = self.partitioning
        n_parts = p.num_partitions
        if isinstance(p, RangePartitioning):
            rkey = ("exch_rkeys", p.cache_sig(), batch.schema_key())
            if rkey not in self._kernels:
                orders = p.orders

                def keys_impl(b):
                    groups = [sortkeys.encode_keys(
                        eval_tpu.evaluate(o.expr, b), o.ascending,
                        o.nulls_first_resolved) for o in orders]
                    return sortkeys.stack_sort_words(groups,
                                                     b.row_mask())
                self._kernels[rkey] = kc.get_kernel(rkey,
                                                    lambda: keys_impl)
            wm = self._kernels[rkey](batch)
            order = sortkeys.shared_lexsort(wm)
            skey = ("exch_rspan", p.cache_sig(), n_parts,
                    batch.schema_key())
            if skey not in self._kernels:
                orders = p.orders

                def span_impl(b, o):
                    t = range_targets_from_order(b, orders, o, n_parts)
                    return jnp.where(b.row_mask(), t,
                                     jnp.int32(n_parts))
                self._kernels[skey] = kc.get_kernel(skey,
                                                    lambda: span_impl)
            return self._kernels[skey](batch, order)
        key = ("exch_target", type(p).__name__, n_parts,
               p.cache_sig(), batch.schema_key())
        if key not in self._kernels:
            tf = self._target_fn()

            def adj_targets(b, st):
                return jnp.where(b.row_mask(), tf(b, st),
                                 jnp.int32(n_parts))
            self._kernels[key] = kc.get_kernel(key,
                                               lambda: adj_targets)
        return self._kernels[key](
            batch, jnp.asarray(rows_seen, dtype=jnp.int32))

    def _partition_one(self, batch: DeviceBatch, rows_seen: int
                       ) -> Tuple[DeviceBatch, np.ndarray]:
        from spark_rapids_tpu.exec import kernel_cache as kc
        n_parts = self.partitioning.num_partitions
        akey = ("exch_apply", n_parts, batch.schema_key())
        if akey not in self._kernels:
            def apply_order(b, t, order):
                counts = jnp.zeros((n_parts,), dtype=jnp.int32
                                   ).at[t].add(
                    (t < n_parts).astype(jnp.int32), mode="drop")
                exists = b.row_mask()
                cols = [c.gather(order, jnp.take(exists, order))
                        for c in b.columns]
                return DeviceBatch(b.names, cols, b.num_rows), counts
            self._kernels[akey] = kc.get_kernel(akey,
                                                lambda: apply_order)
        with timed(self.metrics, "exchange.partition"):
            t = self._compute_targets(batch, rows_seen)
            order = sortkeys.shared_partition_order(t)
            reordered, counts = self._kernels[akey](batch, t, order)
        return reordered, np.asarray(counts)

    def _slice(self, reordered: DeviceBatch, offset: int, count: int
               ) -> DeviceBatch:
        from spark_rapids_tpu.exec import kernel_cache as kc
        out_cap = bucket_rows(count, self.min_bucket)
        key = ("exch_slice", out_cap, reordered.schema_key())
        if key not in self._kernels:
            self._kernels[key] = kc.get_kernel(
                key, lambda: lambda b, o, c: slice_span(b, o, c,
                                                        out_cap))
        return self._kernels[key](reordered,
                                  jnp.asarray(offset, dtype=jnp.int32),
                                  jnp.asarray(count, dtype=jnp.int32))

    def _input_batches(self):
        """Device input batches for an in-process map side; range
        partitioning needs the global rank, so its whole input coalesces
        into one batch (same contract as total sort)."""
        if isinstance(self.partitioning, RangePartitioning):
            all_b = []
            for it in self.children[0].execute():
                all_b.extend(b for b in it if int(b.num_rows))
            if all_b:
                yield concat_batches(all_b)
            return
        for it in self.children[0].execute():
            for b in it:
                if int(b.num_rows):
                    yield b

    def skew_map_side(self) -> SkewMapOutput:
        """Run this exchange's map side WITHOUT the reduce-side concat:
        the same device partition/slice pipeline as :meth:`execute`, but
        blocks stay per (map, reduce-bucket) and every map's per-bucket
        sizes aggregate at a map-output tracker as it completes.  The
        skew join reader consults the tracker's totals to split hot
        buckets before any reduce fetch.  Supported for the in-process
        planes only ('local', 'device') — the shipped transports fall
        back to the adaptive reader at planning time."""
        with _SKEW_MAP_LOCK:
            if self._skew_out is not None:
                return self._skew_out
            from spark_rapids_tpu.obs import registry as obsreg
            n_parts = self.partitioning.num_partitions
            host = self.transport == "local"
            store = ShuffleBlockStore(self.codec_name) if host else None
            dev: List[List[DeviceBatch]] = [[] for _ in range(n_parts)]
            tracker = _MapOutputTracker()
            tracker.open_exec()
            rows = [0] * n_parts
            m = 0
            rows_seen = 0
            for batch in self._input_batches():
                _cancel.check_current()  # per-batch map-side checkpoint
                reordered, counts = self._partition_one(batch, rows_seen)
                rows_seen += int(batch.num_rows)
                off = 0
                sizes = [0] * n_parts
                for pidx in range(n_parts):
                    c = int(counts[pidx])
                    if c:
                        s = self._slice(reordered, off, c)
                        if host:
                            t = to_arrow(s)
                            store.put(m, pidx, t)
                            sizes[pidx] = int(t.nbytes)
                        else:
                            dev[pidx].append(s)
                            # occupancy-scaled: bucket padding must not
                            # mask (or fake) a size skew
                            sizes[pidx] = int(
                                s.nbytes() * (c / max(int(s.capacity),
                                                      1)))
                        rows[pidx] += c
                    off += c
                tracker.record_sizes(m, sizes)
                tracker.map_done("local", m)
                m += 1
            tracker.exec_done("local", range(m))
            totals = tracker.bucket_totals() or [0] * n_parts
            reg = obsreg.get_registry()
            for tb in totals:
                reg.observe_bucket("shuffle.exchange.bucketBytes",
                                   float(tb),
                                   bounds=_BUCKET_BYTE_BOUNDS)
            if store is not None:
                self.metrics.extra["bytes_written"] = store.bytes_written
            self._skew_out = SkewMapOutput(self, host, store, dev,
                                           totals, rows)
            return self._skew_out

    # two simulated executors: map task m lands on exec-(m % 2), so every
    # read exercises both the local-catalog and the remote-fetch paths
    _MANAGER_EXECUTORS = 2

    def run_map_stage(self, shuffle_id: int, catalog, n_execs: int,
                      exec_idx: int, on_map_done=None) -> List[int]:
        """Map side of this exchange inside ONE executor process
        (RapidsCachingWriter.write analog,
        RapidsShuffleInternalManager.scala:90-155): executes this
        executor's share of input partitions (map task = input partition,
        ``p % n_execs == exec_idx``), partitions each batch on device,
        and registers the slices in the executor-local catalog.  Returns
        the completed map ids.

        ``on_map_done(map_id)`` fires after EACH map task's slices are
        fully registered (the pipelined exchange's per-map completion
        notification: reducers may start fetching that map id the moment
        it fires, while later maps are still running)."""
        n_parts = self.partitioning.num_partitions
        its = self.children[0].execute()
        if isinstance(self.partitioning, RangePartitioning):
            # global-rank bounds need the whole input (same contract as
            # the in-process path): one map task, on executor 0
            if exec_idx != 0:
                return []
            batches = []
            for it in its:
                batches.extend(b for b in it if int(b.num_rows))
            shares = [(0, batches and [concat_batches(batches)] or [])]
        else:
            shares = [(p, its[p]) for p in range(len(its))
                      if p % n_execs == exec_idx]
        maps: List[int] = []
        for map_id, it in shares:
            rows_seen = 0
            for batch in it:
                _cancel.check_current()  # per-batch map-side checkpoint
                if not int(batch.num_rows):
                    continue
                reordered, counts = self._partition_one(batch, rows_seen)
                rows_seen += int(batch.num_rows)
                off = 0
                for pidx in range(n_parts):
                    c = int(counts[pidx])
                    if c:
                        catalog.register_batch(
                            shuffle_id, map_id, pidx,
                            self._slice(reordered, off, c))
                    off += c
            maps.append(map_id)
            if on_map_done is not None:
                on_map_done(map_id)
        return maps

    _process_sids = itertools.count(1)

    def _execute_process(self):
        """Cross-process data plane: map stages run in spawned executor
        daemons (shuffle/executor_proc.py) whose catalogs serve reducer
        pulls over ``TcpShuffleTransport``; this (driver) process runs
        only the reduce side through the standard client/iterator state
        machines.  A dead executor surfaces as fetch-failed and its map
        stage is re-run on a respawned executor (the Spark stage-retry
        semantics, RapidsShuffleIterator.scala:188)."""
        from spark_rapids_tpu.shuffle import faults
        from spark_rapids_tpu.shuffle.catalogs import \
            ShuffleReceivedBufferCatalog
        from spark_rapids_tpu.shuffle.client import RapidsShuffleClient
        from spark_rapids_tpu.shuffle.iterator import (
            RapidsShuffleFetchFailedException, RapidsShuffleIterator,
            RapidsShuffleTimeoutException, RemoteSource)
        from spark_rapids_tpu.shuffle.procpool import get_executor_pool
        from spark_rapids_tpu.shuffle.tcp import TcpShuffleTransport

        n_parts = self.partitioning.num_partitions
        n_execs = max(int(self.conf_obj.get(
            cfg.SHUFFLE_PROCESS_EXECUTORS)), 1)
        nested_transport = str(self.conf_obj.get(
            cfg.SHUFFLE_PROCESS_NESTED_TRANSPORT))
        max_retries = int(self.conf_obj.get(cfg.SHUFFLE_FETCH_MAX_RETRIES))
        backoff_ms = float(self.conf_obj.get(
            cfg.SHUFFLE_FETCH_RETRY_BACKOFF_MS))
        cpu_fallback = bool(self.conf_obj.get(cfg.SHUFFLE_CPU_FALLBACK))
        pipeline_depth = max(0, int(self.conf_obj.get(
            cfg.SHUFFLE_PIPELINE_DEPTH)))
        _pipeline_timeout_ms = float(self.conf_obj.get(
            cfg.SHUFFLE_PIPELINE_TIMEOUT_MS))
        # 0 = wait indefinitely, the sequential barrier's semantics: a
        # DEAD executor still surfaces promptly (its submit thread
        # fails the tracker); only a wedged-but-alive one waits — the
        # same hang depth=0 has always had on its pipe reads
        pipeline_timeout_s = None if _pipeline_timeout_ms <= 0 \
            else max(1.0, _pipeline_timeout_ms / 1000.0)
        tcp_conf_extra = {
            "connect_timeout_ms": self.conf_obj.get(
                cfg.SHUFFLE_CONNECT_TIMEOUT_MS),
            "read_timeout_ms": self.conf_obj.get(
                cfg.SHUFFLE_READ_TIMEOUT_MS),
            # the iterator already retries whole fetch attempts
            # (fetch.maxRetries); nesting the full budget here would
            # square the connect attempts to a dead peer
            "connect_max_retries": 1 if max_retries > 0 else 0,
            "connect_backoff_ms": backoff_ms,
            # compressed wire leg: the driver's clients negotiate the
            # per-frame DATA codec in their HELLO; executor servers
            # honor whatever the client announced (tcp.wire_codec)
            "data_codec": self.codec_name,
        }
        faults.install_plan_from_conf(self.conf_obj)
        stats = faults.get_fault_stats()
        # per-exchange recovery-stats attribution: every thread doing
        # work for THIS exchange (submit threads, readers, pipeline
        # thunks, the TCP reader threads of connections they dial)
        # increments this scope alongside the process counters, so the
        # stamped per-query view is exact even with concurrent
        # exchanges in one process (the old snapshot-delta bled)
        scope = faults.StatsScope()
        state = {"done": False, "sid": None, "pool": None,
                 "transport": None, "received": None, "maps": {},
                 "clients": {}, "reads_left": n_parts, "epoch": 0,
                 "fb_store": None}
        lock = threading.Lock()
        fb_lock = threading.Lock()  # guards only the fallback store

        def stamp_fault_stats() -> None:
            """Per-query ShuffleFaultStats view, attributed exactly:
            the counts in this exchange's StatsScope (incremented by
            its own threads and connections), into Metrics.extra (the
            explain/metrics surface)."""
            snap = scope.snapshot()
            for k in faults.ShuffleFaultStats.FIELDS:
                self.metrics.extra[f"shuffle.{k}"] = snap.get(k, 0)
            if state.get("recover_error"):
                self.metrics.extra["shuffle.recover_error"] = \
                    state["recover_error"]

        def check_map_stage_faults(pool, submitted_idx) -> None:
            """FaultPlan consultation per completed map-stage submission
            (generalizes the old one-off procpool.kill test hook): a
            KILL event hard-kills the targeted executor (rule arg) or
            the one that just ran."""
            plan = faults.get_fault_plan()
            if plan is None:
                return
            ev = plan.check("procpool.map_stage")
            if ev is not None and ev.action == faults.FaultAction.KILL:
                pool.kill(ev.arg if ev.arg is not None else submitted_idx)

        def client_for(eid: str):
            """One RapidsShuffleClient per peer (its transfer-tag counter
            must be shared by every fetch on the connection); rebuilt if
            the connection died (ShuffleEnv.client_for idiom).  The dial
            itself (connect timeouts + backoff sleeps) runs OUTSIDE the
            exchange lock so a dead peer can't serialize every reader
            behind its connect attempts; only cache access locks."""
            with lock:
                c = state["clients"].get(eid)
                if c is not None and not getattr(c.connection, "closed",
                                                 False):
                    return c
                state["clients"].pop(eid, None)
                transport = state["transport"]
                received = state["received"]
            try:
                conn = transport.make_client(eid)
            except KeyError:
                # peer vanished from the address book (killed before it
                # was ever dialed): a data-plane error, so the fetch
                # fails and recovery runs — not a caller crash
                from spark_rapids_tpu.shuffle.tcp import \
                    _DeadClientConnection
                conn = _DeadClientConnection(f"unknown peer {eid}")
            c = RapidsShuffleClient(conn, received)
            with lock:
                cur = state["clients"].get(eid)
                if cur is not None and not getattr(
                        cur.connection, "closed", False):
                    winner = cur  # a concurrent dial won; use its client
                else:
                    state["clients"][eid] = c
                    winner = None
            if winner is not None:
                # don't leak the losing dial's socket — but the
                # transport may have deduped and handed us the winner's
                # own connection, which must stay open
                close = getattr(conn, "close", None)
                if conn is not winner.connection and close is not None:
                    try:
                        close()
                    except OSError:
                        pass
                return winner
            return c

        def submit(pool, exec_idx: int, sid: int, on_map=None):
            """Ship this exchange's map stage for executor ``exec_idx``;
            returns completed map ids (raises on task failure).  With
            ``on_map`` set, the task streams per-map completion events
            and ``on_map(map_id)`` fires for each BEFORE the final
            reply — the pipelined map/fetch overlap signal."""
            import time as _time
            from spark_rapids_tpu.obs import trace as obstrace
            h = pool.handle(exec_idx)
            trace_on = obstrace.is_enabled()
            clock_offset = None
            if trace_on:
                # NTP-style alignment: the handle brackets a
                # lightweight clock op INSIDE its per-call lock (so
                # another query's in-flight map stage can't inflate the
                # measured round trip) and maps the executor clock into
                # the driver domain as midpoint - t_ns, error bounded
                # by half a pipe round trip — microseconds, vs the
                # multi-ms spans it places
                clock_offset = h.clock_sync()
            task = {"op": "map_stage", "exchange": self,
                    "shuffle_id": sid, "n_execs": n_execs,
                    "exec_idx": exec_idx, "trace": trace_on,
                    "stream": on_map is not None}
            if on_map is None:
                reply = h.call(task)
            else:
                reply = h.call_stream(
                    task, lambda ev: on_map(int(ev["map_id"]))
                    if ev.get("event") == "map_done" else None)
            t_recv = _time.perf_counter_ns()
            if not reply.get("ok"):
                msg = (f"map stage on {h.executor_id} failed: "
                       f"{reply.get('error')}\n"
                       f"{reply.get('traceback', '')}")
                if reply.get("transport"):
                    # pipe/process death: retryable (the pipelined
                    # ladder kills + respawns + re-runs on this shape)
                    raise RuntimeError(msg)
                raise ShuffleMapTaskError(msg)
            # executor-side Metrics come home with the map results and
            # merge into THIS driver-side tree by plan node id — without
            # this, everything timed/counted inside the shipped fragment
            # is invisible to the query profile.  skip_root: the driver
            # already times the whole map stage on this exchange node
            # (exchange.mapStages), so the executor copy's own node time
            # must not land on top.  Merging is additive across submits:
            # a map stage RE-RUN after an executor death re-executed the
            # work, so its metrics count again — the
            # shuffle.mapStageReruns stamp (recover()) flags profiles
            # where subtree rows exceed rows delivered for that reason.
            from spark_rapids_tpu.exec.base import merge_plan_metrics
            merge_plan_metrics(self, reply.get("metrics"),
                               skip_root=True)
            # executor-side SPANS come home too (trace stitching): shift
            # them into the driver's clock domain and merge as labeled
            # executor lanes, so map stages render as real lanes in the
            # query's Chrome trace.  Fallback alignment when the clock
            # probe failed: assume zero reply transit (clock_ns was
            # stamped at reply construction).
            if trace_on and reply.get("spans"):
                off = clock_offset
                if off is None and reply.get("clock_ns"):
                    off = t_recv - int(reply["clock_ns"])
                if off is not None:
                    obstrace.record_foreign(
                        reply["spans"], off,
                        label=f"executor-{exec_idx} "
                              f"pid={reply.get('pid', '?')}")
            return h, reply["maps"]

        def install_exchange_state(pool, sid, peers) -> None:
            """The ONE state-setup block both launch modes share (the
            sequential barrier and the pipelined start_maps must not
            drift): received catalog, transport with the complete
            address book, and the process_executors stamp — fleet
            size, identically in both modes regardless of how many
            executors end up owning map output.  Caller holds
            ``lock``."""
            state["sid"] = sid
            state["pool"] = pool
            state["received"] = ShuffleReceivedBufferCatalog()
            state["transport"] = TcpShuffleTransport(
                f"driver-{sid}",
                dict(tcp_conf_extra, peers=peers, seed=sid))
            self.metrics.extra["process_executors"] = n_execs

        def materialize():
            """Sequential (depth=0) map-side barrier: every map stage
            completes before any reducer fetches."""
            with lock:
                if state["done"]:
                    return
                pool = get_executor_pool(n_execs, nested_transport)
                sid = next(self._process_sids)
                with timed(self.metrics), \
                        timed_extra(self.metrics, "exchange.mapStages"):
                    # map stages run concurrently across the fleet; each
                    # handle's pipe is independent; the submit threads
                    # inherit this query's CancelToken explicitly
                    results: List[Any] = [None] * n_execs
                    tok = _cancel.current()

                    def run(e):
                        try:
                            with _cancel.install(tok), \
                                    faults.attribute_to(scope):
                                results[e] = submit(pool, e, sid)
                        except BaseException as ex:
                            results[e] = ex
                    ts = [threading.Thread(target=run, args=(e,))
                          for e in range(n_execs)]
                    for t in ts:
                        t.start()
                    for t in ts:
                        t.join()
                    for e, r in enumerate(results):
                        if isinstance(r, BaseException):
                            raise r
                        h, mids = r
                        if mids:
                            state["maps"][h.executor_id] = (e, list(mids))
                    # address book BEFORE fault consultation: a killed
                    # executor must stay addressable so its death
                    # surfaces as a (recoverable) connect failure, not
                    # an unknown peer
                    peers = pool.peers()
                    # deterministic consultation order: after the join,
                    # sequentially per executor index
                    for e in range(n_execs):
                        check_map_stage_faults(pool, e)
                install_exchange_state(pool, sid, peers)
                state["done"] = True

        tracker = _MapOutputTracker()

        def start_maps():
            """Pipelined map-side launch: spawn the fleet, install the
            address book (executor ports are known at spawn), and ship
            every map stage WITHOUT joining — per-map completions flow
            into the tracker, and reducers begin fetching a map id the
            moment it lands.  Submit threads are daemons: a wedged
            executor must not pin interpreter exit (the tracker's
            no-progress timeout escalates the read side through the
            standard recovery ladder instead)."""
            with lock:
                if state["done"]:
                    return
                pool = get_executor_pool(n_execs, nested_transport)
                sid = next(self._process_sids)
                # spawn all handles up front: the address book must be
                # complete before any reducer dials a peer
                for e in range(n_execs):
                    pool.handle(e)
                peers = pool.peers()
                install_exchange_state(pool, sid, peers)
                tok = _cancel.current()
                import time as _time
                map_t0 = _time.perf_counter_ns()
                map_done_lock = threading.Lock()
                map_remaining = [n_execs]

                def mark_submit_done():
                    # ONE fleet-wide map-stage wall (first launch ->
                    # last submit out), stamped by the last thread:
                    # the sequential path times its barrier as one
                    # wall, and the profile's shuffle_map_s must stay
                    # comparable across modes — per-thread sums would
                    # inflate it ~n_execs-fold for concurrent stages.
                    # Called strictly BEFORE the tracker event that
                    # can release the last reader, so a finished
                    # query's profile always carries the stamp.
                    with map_done_lock:
                        map_remaining[0] -= 1
                        last = map_remaining[0] == 0
                    if last:
                        self.metrics.add_extra(
                            "exchange.mapStages",
                            _time.perf_counter_ns() - map_t0)

                def run(e):
                    eid = f"exec-{e}"
                    try:
                        with _cancel.install(tok), \
                                faults.attribute_to(scope):
                            run_attempts(e, eid)
                    except BaseException as ex:
                        mark_submit_done()
                        tracker.fail(ex)

                def run_attempts(e: int, eid: str) -> None:
                    # A submit can die MID-map-stage here (the
                    # sequential path can't: its kills land after the
                    # join barrier) — a chaos kill or crash takes the
                    # pipe down while maps are still streaming.  Retry
                    # bounded like the read ladder: pool.handle()
                    # respawns the executor (same id, fresh catalog,
                    # NEW port) and the re-run re-registers every map —
                    # the tracker dedupes re-announced ids, and readers
                    # whose fetches raced the death retry through their
                    # own ladder once add_peer repoints the address
                    # book.  EVERY retry starts by hard-killing the
                    # executor: the re-run is idempotent only against
                    # a FRESH catalog (register_batch appends, never
                    # dedupes — re-running into a surviving catalog
                    # would duplicate the failed attempt's partial
                    # registrations and silently double rows), and the
                    # forced respawn's NEW port means readers racing
                    # the window fail loudly on the stale address
                    # instead of silently fetching from a half-empty
                    # catalog.  An aliveness check can't replace this:
                    # Popen.poll() reads stale None while the killing
                    # thread holds the waitpid lock.  Cancellation is
                    # never retried.
                    last: Optional[BaseException] = None
                    for _attempt in range(n_execs + 2):
                        try:
                            h, mids = submit(
                                pool, e, sid,
                                on_map=lambda m: tracker.map_done(
                                    eid, m))
                        except _cancel.QueryCancelledError:
                            raise
                        except (RuntimeError, OSError) as ex:
                            last = ex
                            self.metrics.add_extra(
                                "shuffle.mapStageReruns", 1)
                            try:
                                pool.kill(e)
                            except Exception:
                                pass   # already gone
                            continue
                        with lock:
                            if mids:
                                state["maps"][h.executor_id] = \
                                    (e, list(mids))
                            # respawn = same executor id, new port
                            state["transport"].add_peer(
                                h.executor_id, "127.0.0.1", h.port)
                        check_map_stage_faults(pool, e)
                        mark_submit_done()
                        tracker.exec_done(h.executor_id, mids)
                        return
                    raise last
                for e in range(n_execs):
                    tracker.open_exec()
                    threading.Thread(target=run, args=(e,),
                                     daemon=True,
                                     name=f"shuffle-map-{e}").start()
                state["done"] = True

        def recover(seen_epoch: int) -> bool:
            """Re-run map stages lost with dead executors on respawned
            ones (MapOutputTracker invalidation + stage retry).  Returns
            True if the caller should retry its read — because this call
            recovered something, or a concurrent reader already did."""
            with lock:
                if state["epoch"] != seen_epoch:
                    return True
                pool = state["pool"]
                live = {h.executor_id for h in
                        pool.live_handles().values()}
                lost = [(eid, ei) for eid, (ei, _) in state["maps"].items()
                        if eid not in live]
                for eid, exec_idx in lost:
                    # re-submit BEFORE dropping the dead entry: if the
                    # respawn itself fails, readers must keep seeing the
                    # dead peer and failing loudly — removing it first
                    # would let them silently return partial results
                    h, mids = submit(pool, exec_idx, state["sid"])
                    self.metrics.add_extra("shuffle.mapStageReruns", 1)
                    del state["maps"][eid]
                    if mids:
                        state["maps"][h.executor_id] = (exec_idx,
                                                        list(mids))
                    state["transport"].add_peer(h.executor_id,
                                                "127.0.0.1", h.port)
                    check_map_stage_faults(pool, exec_idx)
                if lost:
                    state["epoch"] += 1
                return bool(lost)

        def fallback_tables(pidx: int) -> List[pa.Table]:
            """CPU-fallback read: recompute the map side in-process into
            a host ShuffleBlockStore (the stock sort-shuffle path) and
            serve the partition from it — the reference's
            fall-back-to-Spark-shuffle contract when the accelerated
            data plane is unrecoverable."""
            stats.incr("fallbacks")

            class _StoreCatalog:
                """register_batch adapter: lets run_map_stage write the
                host block store, so the fallback recompute shares the
                EXACT distributed map-side code path — identical
                row->partition mapping by construction (round-robin's
                per-map-task rows_seen reset included).  The store key
                is a fresh sequence number per registered block: the
                store's (map, reduce) key would otherwise overwrite
                earlier batches of a multi-batch map task (the real
                catalog appends a new block per call)."""

                def __init__(self, store):
                    self.store = store
                    self._seq = itertools.count()

                def register_batch(self, _sid, _map_id, reduce_id,
                                   batch):
                    self.store.put(next(self._seq), reduce_id,
                                   to_arrow(batch))

            # dedicated lock: the (potentially long) map-side recompute
            # must not stall healthy readers that only need the
            # exchange-wide lock for cache/bookkeeping accesses
            with fb_lock:
                store = state["fb_store"]
                if store is None:
                    store = ShuffleBlockStore(self.codec_name)
                    self.run_map_stage(0, _StoreCatalog(store),
                                       n_execs=1, exec_idx=0)
                    state["fb_store"] = store
            return store.fetch(pidx)

        def release():
            with lock:
                state["reads_left"] -= 1
                if state["reads_left"] != 0:
                    return
                pf = state.get("prefetcher")
            # last reader out.  Drain the pipeline FIRST, outside the
            # exchange lock (running thunks acquire it): abandoned
            # partition iterators release without ever consuming, and
            # tearing the transport down under a still-fetching
            # background thunk would drive it through the whole
            # recovery ladder (retries, map-stage re-runs, CPU-fallback
            # recompute) for a result nobody reads — close() cancels
            # pending thunks and waits out + cleans up running ones.
            if pf is not None:
                pf.close()
            with lock:
                # free the executor-resident map output
                # (ShuffleManager.unregisterShuffle analog — the pool is
                # a long-lived fleet, so blocks must not accumulate)
                if state["pool"] is not None:
                    for h in state["pool"].live_handles().values():
                        h.call({"op": "unregister",
                                "shuffle_id": state["sid"]})
                if state["transport"] is not None:
                    state["transport"].shutdown()

        def fetch_with_recovery(pidx: int, attempt) -> List[pa.Table]:
            """The ONE read-side recovery ladder — both the sequential
            reader and the pipelined read_partition run their fetch
            attempts through it, so the depth=0 oracle path and the
            pipelined path cannot diverge: retry ``attempt()`` up to
            ``n_execs + 2`` times, re-running dead executors' map
            stages between attempts, then degrade to the CPU block
            store (or raise the typed exceptions)."""
            for _attempt in range(n_execs + 2):
                with lock:
                    epoch = state["epoch"]
                try:
                    return attempt()
                except (RapidsShuffleFetchFailedException,
                        RapidsShuffleTimeoutException):
                    try:
                        recovered = recover(epoch)
                    except Exception as rec_exc:
                        # respawn itself crash-looped: not recovered,
                        # but keep the cause visible (the fallback or
                        # the raise below must not erase a product bug)
                        recovered = False
                        state["recover_error"] = (
                            f"{type(rec_exc).__name__}: {rec_exc}")
                    if not recovered and tracker.open_execs > 0:
                        # nothing recover() can re-run, but a submit
                        # thread is STILL mid-ladder on this stage (a
                        # mid-stage death races the readers before
                        # state["maps"] carries the executor): its
                        # kill+respawn+re-run will re-announce the
                        # maps and repoint the address book — keep
                        # the bounded read retries pointed at that
                        # instead of prematurely degrading
                        continue
                    if not recovered:
                        # nothing dead: a real protocol failure —
                        # degrade to the CPU block store instead of
                        # failing the query (fall-back-to-Spark-shuffle
                        # contract)
                        if cpu_fallback:
                            return [t for t in fallback_tables(pidx)
                                    if t.num_rows]
                        stamp_fault_stats()
                        raise
            # map-stage retries exhausted (crash-looping executor):
            # CPU fallback if allowed, else surface the failure — an
            # empty yield would silently drop rows
            if cpu_fallback:
                return [t for t in fallback_tables(pidx)
                        if t.num_rows]
            stamp_fault_stats()
            raise RapidsShuffleFetchFailedException(
                f"shuffle {state['sid']} reduce {pidx}: map stage "
                f"retries exhausted after {n_execs + 2} attempts")

        def reader(pidx: int) -> Iterator[DeviceBatch]:
            materialize()

            def attempt() -> List[pa.Table]:
                with lock:
                    sid = state["sid"]
                    recv = state["received"]
                    maps = dict(state["maps"])
                # clients dialed outside the lock (client_for locks
                # only around its cache accesses)
                remotes = [
                    RemoteSource(eid, client_for(eid), list(mids),
                                 refresh=lambda e=eid: client_for(e))
                    for eid, (_ei, mids) in sorted(maps.items())]
                if not remotes:
                    return []
                it = RapidsShuffleIterator(
                    sid, pidx, None, remotes, recv, timeout_s=30.0,
                    max_retries=max_retries,
                    retry_backoff_ms=backoff_ms)
                with timed_extra(self.metrics, "exchange.transfer"):
                    return [t for t in it if t.num_rows]

            with faults.attribute_to(scope):
                tables = fetch_with_recovery(pidx, attempt)
            stamp_fault_stats()
            if not tables:
                return
            t = concat_tables(tables, self.schema)
            with timed(self.metrics), \
                    timed_extra(self.metrics, "exchange.upload"):
                b = from_arrow(t, self.min_bucket)
            self.metrics.num_output_rows += t.num_rows
            self.metrics.add_batches()
            yield b

        # ------------------------------------------------------------------
        # Pipelined read side (shuffle.pipeline.depth > 0): one bounded
        # look-ahead stage fetches + decodes + uploads reduce partition
        # k+1 while partition k is being consumed (the ScanPrefetcher
        # shape), and each partition's fetch starts per map id as the
        # tracker announces it — map compute, DCN transfer, and reduce-
        # side decode overlap instead of paying three sequential walls.
        # ------------------------------------------------------------------

        def fetch_maps(eid: str, mids: List[int],
                       pidx: int) -> List[pa.Table]:
            """Fetch a batch of completed map tasks' blocks for
            ``pidx`` from one executor through the standard per-peer
            iterator state machine (all of PR 1's retry/cancel/
            leak-free paths apply; one metadata + transfer round trip
            covers the whole batch)."""
            with lock:
                sid = state["sid"]
                recv = state["received"]
            it = RapidsShuffleIterator(
                sid, pidx, None,
                [RemoteSource(eid, client_for(eid), list(mids),
                              refresh=lambda: client_for(eid))],
                recv, timeout_s=30.0, max_retries=max_retries,
                retry_backoff_ms=backoff_ms)
            return [t for t in it if t.num_rows]

        def read_partition(pidx: int):
            """Pipeline thunk body for one reduce partition: stream map
            completions, fetch each map's output as it lands, then
            decode + upload once and register the prepared batch with
            the spill catalog (pressure-aware: the admission
            controller's handle_memory_pressure can push prepared
            partitions to host/disk instead of stalling admission).
            Returns (spillable-or-plain handle, row count), or (None, 0)
            for an empty partition."""
            start_maps()
            token = _cancel.current()
            # per-executor accumulation: fetched map ids (dedup across
            # retry attempts — the tracker replays announcements) and
            # their tables in map-execution order.  A map task's blocks
            # register in catalog order and one executor's map_done
            # events announce in execution order, so per-eid table
            # order is deterministic regardless of how the completions
            # were batched into fetches.
            fetched: Dict[str, set] = {}
            got: Dict[str, List[pa.Table]] = {}

            def attempt() -> List[pa.Table]:
                for batch in tracker.batches(pipeline_timeout_s,
                                             token=token):
                    by_eid: Dict[str, List[int]] = {}
                    for eid, mid in batch:
                        if mid not in fetched.setdefault(eid, set()):
                            by_eid.setdefault(eid, []).append(mid)
                    for eid in sorted(by_eid):
                        mids = sorted(by_eid[eid])
                        # only the fetch itself is transfer wall;
                        # waiting on the tracker is map-side time
                        with timed_extra(self.metrics,
                                         "exchange.transfer"):
                            ts = fetch_maps(eid, mids, pidx)
                        # mark fetched only on success: a failed group
                        # fetch delivers nothing (the iterator's error
                        # path frees partials) and retries whole
                        fetched[eid].update(mids)
                        got.setdefault(eid, []).extend(ts)
                # deterministic assembly — executors sorted, each
                # executor's stream in map-execution order — matching
                # the per-peer registration order the sequential path
                # fetches in, so depth=0 and pipelined results agree
                return [t for eid in sorted(got) for t in got[eid]]

            with faults.attribute_to(scope):
                tables = fetch_with_recovery(pidx, attempt)
            if not tables:
                return (None, 0)
            t = concat_tables(tables, self.schema)
            with timed_extra(self.metrics, "exchange.upload"):
                b = from_arrow(t, self.min_bucket)
            # in-flight prepared partitions register at shuffle-input
            # priority: under memory pressure they spill device->host->
            # disk through the standard tiers instead of pinning HBM
            # while the consumer is still partitions away
            from spark_rapids_tpu.mem import spill as _spill
            handle = _spill.register_or_hold(
                b, priority=_spill.INPUT_FROM_SHUFFLE_PRIORITY)
            return (handle, t.num_rows)

        def _cleanup_prepared(res) -> None:
            handle = res[0] if isinstance(res, tuple) else None
            if handle is not None:
                handle.close()

        def pipelined_readers():
            from spark_rapids_tpu.exec.scans import (
                SHUFFLE_PIPELINE_KEYS, ScanPrefetcher)
            prefetcher = ScanPrefetcher(
                [lambda p=p: read_partition(p) for p in range(n_parts)],
                depth=pipeline_depth, metrics=self.metrics,
                cleanup=_cleanup_prepared,
                labels=[f"reduce{p}" for p in range(n_parts)],
                keys=SHUFFLE_PIPELINE_KEYS,
                thread_name="shuffle-pipeline")
            with lock:
                # release() drains this before transport teardown, so
                # abandoned readers can't strand a mid-fetch thunk
                state["prefetcher"] = prefetcher

            def piped_reader(pidx: int) -> Iterator[DeviceBatch]:
                try:
                    handle, nrows = prefetcher.get(pidx)
                finally:
                    prefetcher.part_done()
                stamp_fault_stats()
                if handle is None:
                    return
                try:
                    with timed(self.metrics):
                        b = handle.get()  # unspills if pressure moved
                finally:
                    # close() even when the unspill raises (HBM OOM /
                    # disk-tier IO error): the catalog entry and any
                    # disk payload must not stay pinned until GC
                    handle.close()
                self.metrics.num_output_rows += nrows
                self.metrics.add_batches()
                yield b

            return [_ReleasingIter(piped_reader(p), release)
                    for p in range(n_parts)]

        if pipeline_depth > 0:
            return pipelined_readers()
        return [_ReleasingIter(reader(p), release)
                for p in range(n_parts)]

    def _execute_ici(self):
        """ICI data plane: the whole exchange is ONE lax.all_to_all over
        the device mesh (reference: the UCX peer-to-peer transport,
        UCX.scala:53-533, restructured as a collective per SURVEY.md §5).

        Rows route to the device owning their target partition
        (partition p lives on device p % n_dev); reducer p's reader then
        sub-splits its device's received rows by the carried '__part__'
        column, staying on that device — so downstream per-partition
        kernels (join probe, per-partition aggregate) execute distributed
        across the mesh.

        The map side runs where its rows lie (exec/placement): the
        child's partitions are drained with the chips side by side, the
        batches are grouped by the device they are committed to, each
        chip concatenates its own and computes their targets, and
        ``ici.exchange_placed`` takes them from there with buckets sized
        by the counted rows.  A child whose batches all lie on one
        device (a host-built DataFrame, a one-partition scan) is the
        same exchange with the other chips sending nothing.
        """
        from spark_rapids_tpu.exec import placement
        from spark_rapids_tpu.obs import registry as obsreg
        from spark_rapids_tpu.obs import trace as obstrace
        from spark_rapids_tpu.shuffle import ici
        n_parts = self.partitioning.num_partitions
        state = {"done": False, "dev": None, "n_dev": 1,
                 "reads_left": n_parts}
        lock = threading.Lock()

        def materialize():
            with lock:
                return _materialize_locked()

        def _materialize_locked():
            if state["done"]:
                return
            devices = list(ici.get_default_mesh().devices.flat)
            n_dev = len(devices)
            info = {"partitioning": type(self.partitioning).__name__}
            its = self.children[0].execute()
            parts: List[List[DeviceBatch]] = [[] for _ in its]
            placement.drain_by_chip(
                its, lambda p, b: parts[p].append(b), n_dev,
                stage="exchange")
            batches = [b for part in parts for b in part
                       if int(read_host(b.num_rows,
                                        "exchange.mapRowsWait"))]
            if batches:
                # the span carries what the exchange counted
                with timed(self.metrics), \
                        obstrace.span("exchange.ici", args=info):
                    state["dev"] = self._exchange_ici(
                        batches, devices, info)
                state["n_dev"] = n_dev
                self.metrics.extra["ici_devices"] = n_dev
                obsreg.get_registry().inc_many(
                    ("exchange.ici.exchanges", 1),
                    ("exchange.ici.rowsIn", info["rows_in"]),
                    ("exchange.ici.bytesIn", info["bytes_in"]),
                    ("exchange.ici.bucketRows", info["bucket_rows"]),
                    ("exchange.ici.movedBatches", info["moved"]))
                for k in ("rows_in", "bucket_rows", "moved"):
                    self.metrics.extra[f"ici_{k}"] = info[k]
            state["done"] = True

        def release():
            # last reducer out (iterated, closed, OR collected unread)
            # drops the device-resident shards so a multi-stage query —
            # including early-exit/limit plans that abandon partition
            # iterators — doesn't pin every exchange in HBM
            with lock:
                state["reads_left"] -= 1
                if state["reads_left"] == 0:
                    state["dev"] = None

        def reader(pidx: int) -> Iterator[DeviceBatch]:
            materialize()
            if state["dev"] is None:
                return
            b = state["dev"][pidx % state["n_dev"]]
            if b is None:
                return
            if n_parts <= state["n_dev"]:
                # the device owns this partition alone: every row it
                # received is the reader's
                out = b
            else:
                from spark_rapids_tpu.exec import kernel_cache as kc
                key = ("ici_extract", b.schema_key())
                if key not in self._kernels:
                    def extract(batch, pid):
                        from spark_rapids_tpu.exec.tpu_basic import compact
                        part = batch.columns[-1].data
                        return compact(batch, part == pid)
                    self._kernels[key] = kc.get_kernel(
                        key, lambda: extract)
                with timed(self.metrics, "exchange.iciExtract"):
                    out = self._kernels[key](b, jnp.int32(pidx))
            if not int(read_host(out.num_rows, "exchange.partRowsWait")):
                return
            out = DeviceBatch(out.names[:-1], out.columns[:-1],
                              out.num_rows)  # drop __part__
            self.metrics.add_rows(out.num_rows)
            self.metrics.add_batches()
            yield out

        return [_ReleasingIter(reader(p), release)
                for p in range(n_parts)]

    def _exchange_ici(self, batches: List[DeviceBatch], devices: list,
                      info: dict) -> List[Optional[DeviceBatch]]:
        """One ICI exchange over the drained map-side batches; fills
        ``info`` with what was counted (the ``exchange.ici`` span's
        arguments and the counters)."""
        from spark_rapids_tpu.exec import placement
        from spark_rapids_tpu.shuffle import ici
        index = {d: i for i, d in enumerate(devices)}
        homes = [index.get(placement.device_of(b)) for b in batches]
        info["rows_in"] = sum(
            int(read_host(b.num_rows, "exchange.mapRowsWait"))
            for b in batches)
        info["bytes_in"] = sum(
            int(read_host(b.num_rows, "exchange.mapRowsWait")) * sum(
                a.dtype.itemsize * int(np.prod(a.shape[1:]))
                for c in b.columns
                for a in (c.data, c.validity, c.lengths, c.elem_validity)
                if a is not None) for b in batches)
        # a batch on no device of the mesh joins chip 0's
        info["moved"] = sum(1 for h in homes if h is None)
        per_chip: List[List[DeviceBatch]] = [[] for _ in devices]
        for b, h in zip(batches, homes):
            if h is None:
                h, b = 0, jax.device_put(b, devices[0])
            per_chip[h].append(b)
        held = [concat_batches(bs) if bs else None for bs in per_chip]
        targets = self._placed_targets(held)
        dev, counted = ici.exchange_placed(held, targets, self.min_bucket)
        info["bucket_rows"] = counted["bucket_rows"]
        info["capacities"] = counted["capacities"]
        info["received"] = [int(n) for n in counted["rows"].sum(axis=0)]
        return dev

    def _placed_targets(self, held: List[Optional[DeviceBatch]]
                        ) -> List[Optional[jnp.ndarray]]:
        """Per-row target partitions of each chip's batch, computed on
        that chip.  Hash and round-robin targets are a row's own; range
        targets need bounds every chip agrees on, so each chip hands
        over a sample of its sort keys, the bounds are the sample's
        quantiles (Spark's RangePartitioner) and each chip places its
        rows between them."""
        p = self.partitioning
        if not isinstance(p, RangePartitioning):
            out, seen = [], 0
            for g in held:
                out.append(None if g is None
                           else self._compute_targets(g, seen))
                seen += 0 if g is None else int(
                    read_host(g.num_rows, "exchange.heldRowsWait"))
            return out
        from spark_rapids_tpu.exec import kernel_cache as kc
        n_parts = p.num_partitions
        orders = p.orders

        def keys_impl(b):
            groups = [sortkeys.encode_keys(
                eval_tpu.evaluate(o.expr, b), o.ascending,
                o.nulls_first_resolved) for o in orders]
            return sortkeys.stack_sort_words(groups, b.row_mask())

        def sample_impl(wm, num_rows):
            # evenly spaced live rows (they lie at the front)
            at = ((jnp.arange(_RANGE_SAMPLE, dtype=jnp.int64) * 2 + 1)
                  * num_rows.astype(jnp.int64)) // (2 * _RANGE_SAMPLE)
            return jnp.take(wm, at.astype(jnp.int32), axis=1)

        def place_impl(wm, bounds, num_rows):
            # a row's target: the bounds its key lies above, words
            # compared from the least significant up
            target = jnp.zeros((wm.shape[1],), jnp.int32)
            for k in range(n_parts - 1):
                above = jnp.zeros((wm.shape[1],), jnp.bool_)
                for w in range(wm.shape[0] - 1, -1, -1):
                    above = (wm[w] > bounds[w, k]) | (
                        (wm[w] == bounds[w, k]) & above)
                target = target + above.astype(jnp.int32)
            live = jnp.arange(wm.shape[1]) < num_rows
            return jnp.where(live, target, jnp.int32(n_parts))

        words, samples = [], []
        for g in held:
            if g is None:
                words.append(None)
                continue
            wm = kc.get_kernel(
                ("exch_rkeys", p.cache_sig(), g.schema_key()),
                lambda: keys_impl)(g)
            words.append(wm)
            samples.append((int(read_host(
                g.num_rows, "exchange.heldRowsWait")), kc.get_kernel(
                ("exch_rsample", wm.shape), lambda: sample_impl)(
                    wm, jnp.asarray(g.num_rows, dtype=jnp.int32))))
        drawn = read_host([s for _, s in samples], "exchange.countWait")
        bounds = _range_bounds([(n, s) for (n, _), s in
                                zip(samples, drawn)], n_parts)
        return [None if g is None else kc.get_kernel(
            ("exch_rplace", n_parts, wm.shape), lambda: place_impl)(
                wm, bounds, jnp.asarray(g.num_rows, dtype=jnp.int32))
                for g, wm in zip(held, words)]

    def execute(self):
        if self.transport in ("ici", "ici_ring"):
            return self._execute_ici()
        if self.transport == "process":
            return self._execute_process()
        n_parts = self.partitioning.num_partitions
        state = {"done": False, "store": None, "dev_slices": None,
                 "mgr": None, "sid": None, "reads_left": n_parts}
        lock = threading.Lock()

        def materialize():
            with lock:
                return _materialize_locked()

        def _materialize_locked():
            if state["done"]:
                return
            host = self.transport == "local"
            mgr_mode = self.transport == "manager"
            store = ShuffleBlockStore(self.codec_name) if host else None
            if mgr_mode:
                from spark_rapids_tpu.shuffle.manager import \
                    get_shuffle_manager
                state["mgr"] = get_shuffle_manager(self.conf_obj)
                state["sid"] = state["mgr"].new_shuffle_id()
            dev_slices: List[List[DeviceBatch]] = \
                [[] for _ in range(n_parts)]

            m = 0
            rows_seen = 0
            for batch in self._input_batches():
                _cancel.check_current()  # per-batch map-side checkpoint
                reordered, counts = self._partition_one(batch, rows_seen)
                rows_seen += int(batch.num_rows)
                off = 0
                map_parts: List[Optional[DeviceBatch]] = [None] * n_parts
                for pidx in range(n_parts):
                    c = int(counts[pidx])
                    if c:
                        s = self._slice(reordered, off, c)
                        if host:
                            store.put(m, pidx, to_arrow(s))
                        elif mgr_mode:
                            map_parts[pidx] = s
                        else:
                            dev_slices[pidx].append(s)
                    off += c
                if mgr_mode:
                    state["mgr"].write_map_output(
                        f"exec-{m % self._MANAGER_EXECUTORS}",
                        state["sid"], m, map_parts)
                m += 1
            state["store"] = store
            state["dev_slices"] = dev_slices
            state["done"] = True
            if store is not None:
                self.metrics.extra["bytes_written"] = store.bytes_written

        def reader(pidx: int) -> Iterator[DeviceBatch]:
            materialize()
            if self.transport == "manager":
                # reducer pidx runs "on" exec-(pidx % N): its local blocks
                # come straight from the device catalog, the rest arrive
                # via the tag-matched transport protocol
                try:
                    tables = list(state["mgr"].read_partition(
                        f"exec-{pidx % self._MANAGER_EXECUTORS}",
                        state["sid"], pidx))
                    tables = [t for t in tables if t.num_rows]
                    if not tables:
                        return
                    t = concat_tables(tables, self.schema)
                    with timed(self.metrics, "exchange.upload"):
                        b = from_arrow(t, self.min_bucket)
                    self.metrics.num_output_rows += t.num_rows
                    self.metrics.add_batches()
                finally:
                    # last reducer out frees the device-resident blocks
                    # (ShuffleManager.unregisterShuffle analog)
                    with lock:
                        state["reads_left"] -= 1
                        if state["reads_left"] == 0:
                            state["mgr"].unregister_shuffle(state["sid"])
                yield b
            elif self.transport == "local":
                tables = state["store"].fetch(pidx)
                if not tables:
                    return
                # ShuffleCoalesce: concat host-serialized slices, upload once
                t = concat_tables(tables, self.schema)
                with timed(self.metrics, "exchange.upload"):
                    b = from_arrow(t, self.min_bucket)
                self.metrics.num_output_rows += t.num_rows
                self.metrics.add_batches()
                yield b
            else:
                slices = state["dev_slices"][pidx]
                if not slices:
                    return
                with timed(self.metrics, "exchange.concat"):
                    b = concat_batches(slices)
                self.metrics.add_rows(b.num_rows)
                self.metrics.add_batches()
                yield b

        return [reader(p) for p in range(n_parts)]


class CpuCoalescePartitionsExec(PhysicalPlan):
    """Merge contiguous input partitions into at most n output partitions
    by chaining their iterators — no shuffle, no data movement
    (GpuCoalesceExec analog)."""

    def __init__(self, child: PhysicalPlan, num_partitions: int):
        super().__init__()
        self.children = (child,)
        self.num_partitions = num_partitions

    @property
    def schema(self) -> Schema:
        return self.children[0].schema

    def execute(self):
        its = self.children[0].execute()
        n = min(self.num_partitions, len(its)) or 1
        groups = np.array_split(np.arange(len(its)), n)
        return [itertools.chain.from_iterable(its[i] for i in g)
                for g in groups if len(g)]


class TpuCoalescePartitionsExec(TpuExec):
    """Device-currency twin of CpuCoalescePartitionsExec."""

    def __init__(self, child: PhysicalPlan, num_partitions: int):
        super().__init__()
        self.children = (child,)
        self.num_partitions = num_partitions

    @property
    def schema(self) -> Schema:
        return self.children[0].schema

    def execute(self):
        its = self.children[0].execute()
        n = min(self.num_partitions, len(its)) or 1
        groups = np.array_split(np.arange(len(its)), n)
        return [itertools.chain.from_iterable(its[i] for i in g)
                for g in groups if len(g)]

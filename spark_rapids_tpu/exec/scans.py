"""Capacity-safe prefix scans for wide (8-byte) dtypes, plus the
pipelined scan prefetcher (bounded look-ahead host prep for file
scans — see ScanPrefetcher below).

TPU emulates 64-bit integers (and x64 floats) as pairs of 32-bit
lanes, and both stock prefix-scan formulations break at capacity
(every number below measured on the bench chip):

- ``jnp.cumsum`` lowers to a pair reduce-window that requests a FIXED
  ~19.09 MiB scoped-VMEM allocation whenever it sits inside ANY
  control flow (lax.scan/cond/fori_loop bodies) — even a 32k-element
  int64 cumsum inside a scan body fails against the 16 MiB scoped
  limit, while the same op at top level compiles.
- ``lax.associative_scan`` compiles in every context, but at full
  capacity its log2(n) split recursion explodes compile time
  (4M int64: 1107 s).

The blocked form threads the needle: a ``lax.scan`` over fixed-size
blocks whose body runs ONE block-sized ``associative_scan`` and
carries the running prefix — 4M int64 compiles in ~1.5 s and scoped
VMEM stays ~block-sized.

What is blocked is the recursion's DEPTH (log2(_BLOCK) levels whatever
the capacity); the blocks' ORDER is kept, because a float result
depends on the left fold of the carries.  Measured since (PERF.md §6,
PR 29; one v5e chip, and the TPU compiler run for a described v5e):
the ~1.5 s still holds (4M int64 1.4 s, 4M float64 5.3 s); inside
TPC-H Q1's aggregate update a 4M-row float64 ``seg_scan`` is one
``while`` of 128 trips and takes 24 ms (190 us a trip for the 371
operations of its body), so the five such loops are 3.9% of a program
whose time is its full-capacity gathers — the loop is not worth
unrolling for speed.  Running every block's recursion at once gives
the same bits, but laid out ``[_BLOCK, g]`` (one ``associative_scan``
along axis 0) the compiler did not finish one 4M-row scan in 40
minutes; laid out ``[g, _BLOCK]`` (along axis 1) the float64 scan took
4.7 s and the int64 one had not finished after 14 minutes.

Reference analog: none needed — cudf's prefix scans run on a GPU whose
scratch is not a compile-time-bounded scoped space; this module is the
TPU formulation of the same segmented-reduction building block.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp

from spark_rapids_tpu.obs import registry as obsreg
from spark_rapids_tpu.obs import trace as obstrace
from spark_rapids_tpu.sched import cancel as _cancel

_BLOCK = 1 << 15          # per-step scan length


@dataclass(frozen=True)
class PrefetchKeys:
    """Span/registry names one ScanPrefetcher instance emits under.

    The prefetcher started life scan-only; the shuffle pipeline reuses
    it (the exchange's bounded look-ahead over reduce partitions) with
    its own name set — ``shuffle.pipeline.prefetch``/``stall`` spans
    and ``shuffle.pipeline.stalls``/``overlapNs`` counters — so traces
    and /metrics keep the two pipelines distinguishable."""

    span_prefetch: str = "scan.prefetch"
    span_stall: str = "scan.prefetchStall"
    prefetch_ns: str = "scan.prefetchNs"
    stalls: str = "scan.prefetchStalls"
    stall_ns: str = "scan.prefetchStallNs"
    overlap_ns: str = "scan.prefetchOverlapNs"
    cat: str = "scan"


SHUFFLE_PIPELINE_KEYS = PrefetchKeys(
    span_prefetch="shuffle.pipeline.prefetch",
    span_stall="shuffle.pipeline.stall",
    prefetch_ns="shuffle.pipeline.prefetchNs",
    stalls="shuffle.pipeline.stalls",
    stall_ns="shuffle.pipeline.stallNs",
    overlap_ns="shuffle.pipeline.overlapNs",
    cat="shuffle")


class ScanPrefetcher:
    """Bounded look-ahead runner for scan host prep.

    Given one thunk per scan batch (each performing host-side prep +
    device upload — e.g. ``io/parquet_fused.prepare_fused`` — and NO
    device->host read, per PERF.md's no-mid-stream-read discipline),
    runs up to ``depth`` of them ahead of the consumer on a small
    thread pool, so batch k+1's footer/page walks and packed-page
    uploads overlap batch k's dispatch-only device decode.

    ``get(i)`` returns thunk i's result exactly once, blocking if it
    isn't ready (counted into ``metrics.extra['scan.prefetchStalls']``
    — a stall means the consumer outran the prepared window).
    Consumers may arrive out of order (partition iterators drain on a
    task pool); an index past the submitted window forces submission
    so no ``get`` can deadlock.  A thunk's exception is re-raised at
    its ``get``.  In-flight prepared-but-unconsumed batches — and so
    the held host artifacts and uploaded page buffers — are bounded by
    ``max(depth, concurrent consumers)``: the forced submissions mean
    a task pool wider than ``depth`` raises the bound to its own
    width (the engine's pool is ``concurrentTpuTasks``, default 2).

    Abandonment safety: if the consumer never drains every index (an
    error mid-query, a short-circuiting collect), ``close()`` — also
    wired as a GC finalizer — cancels undispatched thunks and runs
    ``cleanup`` on every prepared-but-unconsumed result (e.g. closing
    file handles), then shuts the pool down."""

    def __init__(self, thunks: Sequence[Callable[[], object]],
                 depth: int, metrics=None,
                 cleanup: Optional[Callable[[object], None]] = None,
                 labels: Optional[Sequence[str]] = None,
                 keys: Optional[PrefetchKeys] = None,
                 thread_name: str = "scan-prefetch"):
        import concurrent.futures as cf
        import weakref
        self._thunks: List[Callable[[], object]] = list(thunks)
        # per-thunk source labels (file/row-group ids) so stall spans
        # name WHAT stalled — an anonymous stall count makes prefetch
        # tuning guesswork
        self._labels: List[str] = list(labels or ())
        self._depth = max(1, int(depth))
        self._metrics = metrics
        self._keys = keys or PrefetchKeys()
        self._lock = threading.Lock()
        self._futures = {}
        # per-thunk prefetch wall (ns), consumed by get()'s overlap
        # accounting: background work that completed before (or ran
        # beyond) the consumer's arrival is genuinely overlapped time
        self._durs = {}
        self._next = 0
        self._consumed = 0
        self._parts_done = 0
        self._pool: Optional[object] = None
        # cancellation: capture the submitting query's token here (the
        # prefetch pool's threads don't inherit thread-locals) and
        # install it around every thunk — a cancelled query stops
        # prepping/uploading look-ahead batches at the next checkpoint
        self._token = _cancel.current()
        if self._thunks:
            self._pool = cf.ThreadPoolExecutor(
                max_workers=self._depth,
                thread_name_prefix=thread_name)
            # args must not reference self (that would pin it forever)
            self._finalizer = weakref.finalize(
                self, ScanPrefetcher._close_impl, self._lock,
                self._futures, self._pool, cleanup)
            with self._lock:
                self._fill_locked()

    def _span_args(self, i: int) -> dict:
        args = {"batch": i}
        if i < len(self._labels):
            args["src"] = self._labels[i]
        return args

    def _run_thunk(self, i: int):
        """Thunk wrapper: the thread inherits the query's CancelToken,
        and the prefetch work itself shows up in the trace (prep+upload
        of batch i on the prefetch thread) and in the registry's
        prefetch histogram."""
        t0 = time.perf_counter_ns()
        try:
            with _cancel.install(self._token):
                _cancel.check_current()
                return self._thunks[i]()
        finally:
            dur = time.perf_counter_ns() - t0
            with self._lock:
                self._durs[i] = dur
            obstrace.record(self._keys.span_prefetch, t0, dur,
                            cat=self._keys.cat,
                            args=self._span_args(i))
            obsreg.get_registry().observe(self._keys.prefetch_ns, dur)

    def _fill_locked(self) -> None:
        while (self._next < len(self._thunks) and
               len(self._futures) < self._depth):
            i = self._next
            self._next += 1
            self._futures[i] = self._pool.submit(self._run_thunk, i)

    def part_done(self) -> None:
        """Consumer-side completion mark, called once per index from
        the partition iterator's ``finally`` (success OR failure).
        Once every consumer has finished, prepared-but-unconsumed
        results are released deterministically — without waiting for
        the GC finalizer — covering queries that die mid-drain."""
        with self._lock:
            self._parts_done += 1
            done = self._parts_done >= len(self._thunks)
        if done:
            self.close()

    def get(self, i: int):
        _cancel.check_current()   # don't block on a cancelled query
        with self._lock:
            # out-of-order consumer past the window: submit through i
            while self._next <= i:
                j = self._next
                self._next += 1
                self._futures[j] = self._pool.submit(self._run_thunk, j)
            fut = self._futures.pop(i)
        stalled = not fut.done()
        t0 = 0
        if stalled:
            # the consumer outran the prepared window: a stall, timed
            # so the profile shows where the pipeline starved (same
            # name in Metrics.extra and the registry: PrefetchKeys
            # owns it once)
            if self._metrics is not None:
                self._metrics.add_extra(self._keys.stalls, 1)
            obsreg.get_registry().inc(self._keys.stalls)
            t0 = time.perf_counter_ns()
        try:
            return fut.result()
        finally:
            stall_ns = 0
            if stalled:
                stall_ns = time.perf_counter_ns() - t0
                # the stall span names its source (path#rg), so a trace
                # shows WHICH batch the consumer starved on
                obstrace.record(self._keys.span_stall, t0, stall_ns,
                                cat=self._keys.cat,
                                args=self._span_args(i))
                obsreg.get_registry().inc(self._keys.stall_ns, stall_ns)
            with self._lock:
                self._consumed += 1
                dur = self._durs.pop(i, 0)
                self._fill_locked()
                if self._consumed >= len(self._thunks):
                    self._pool.shutdown(wait=False)
            # overlapped time = background prefetch wall the consumer
            # did NOT wait out: a thunk that was ready at get() overlaps
            # in full; a stalled get overlaps only the head start.  This
            # is the pipeline's headline (overlapNs == 0 means the
            # look-ahead bought nothing).
            overlap = dur - stall_ns
            if overlap > 0:
                obsreg.get_registry().inc(self._keys.overlap_ns, overlap)

    @staticmethod
    def _close_impl(lock, futures, pool, cleanup) -> None:
        with lock:
            pending = list(futures.values())
            futures.clear()
        for fut in pending:
            if not fut.cancel() and cleanup is not None:
                try:
                    cleanup(fut.result())
                except Exception:
                    pass   # the thunk itself failed: nothing to clean
        pool.shutdown(wait=False)

    def close(self) -> None:
        """Release prepared-but-unconsumed results (idempotent)."""
        if self._pool is not None:
            self._finalizer()


def _to_blocks(x: jnp.ndarray, fill) -> jnp.ndarray:
    n = x.shape[0]
    g = -(-n // _BLOCK)
    pad = g * _BLOCK - n
    if pad:
        x = jnp.concatenate(
            [x, jnp.full((pad,), fill, dtype=x.dtype)])
    return x.reshape(g, _BLOCK)


def cumsum(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive 1-D cumsum safe for wide dtypes in any context."""
    if x.dtype.itemsize < 8:
        return jnp.cumsum(x)
    n = x.shape[0]
    if n <= _BLOCK:
        return jax.lax.associative_scan(jnp.add, x)

    def body(carry, row):
        s = jax.lax.associative_scan(jnp.add, row) + carry
        return s[-1], s

    _, rows = jax.lax.scan(body, jnp.zeros((), x.dtype),
                           _to_blocks(x, 0))
    return rows.reshape(-1)[:n]


def seg_scan(op, flags: jnp.ndarray, vals: jnp.ndarray, identity
             ) -> jnp.ndarray:
    """Inclusive SEGMENTED scan: within each run started where ``flags``
    is True, accumulate ``vals`` with the associative ``op`` (whose
    identity element is ``identity`` — callers pre-fill excluded
    positions with it, and block padding uses it).  The value at a
    segment's last position is the segment reduction."""

    def combine(a, b):
        fa, va = a
        fb, vb = b
        return fa | fb, jnp.where(fb, vb, op(va, vb))

    n = vals.shape[0]
    if vals.dtype.itemsize < 8 or n <= _BLOCK:
        _f, s = jax.lax.associative_scan(combine, (flags, vals))
        return s
    fb_ = _to_blocks(flags, True)          # padding starts a new run
    vb_ = _to_blocks(vals, identity)

    def body(carry, xs):
        pf, pv = jax.lax.associative_scan(combine, xs)
        cf = jnp.broadcast_to(carry[0], pf.shape)
        cv = jnp.broadcast_to(carry[1], pv.shape)
        of, ov = combine((cf, cv), (pf, pv))
        return (of[-1], ov[-1]), ov

    init = (jnp.zeros((), jnp.bool_),
            jnp.full((), identity, vals.dtype))
    _, rows = jax.lax.scan(body, init, (fb_, vb_))
    return rows.reshape(-1)[:n]

"""Physical plan contracts.

Analog of ``trait GpuExec extends SparkPlan`` (reference: GpuExec.scala:58-102:
``supportsColumnar=true``, ``doExecuteColumnar(): RDD[ColumnarBatch]``, and the
batching contracts ``coalesceAfter``/``childrenCoalesceGoal``/``outputBatching``)
plus the CoalesceGoal machinery (reference: GpuCoalesceBatches.scala:94-130).

Execution model: ``execute()`` returns one Python iterator per partition.
CPU execs yield ``pyarrow.Table`` batches; TPU execs yield ``DeviceBatch``.
The planner guarantees the currencies never mix without an explicit
transition exec (HostToDeviceExec / DeviceToHostExec — the
GpuRowToColumnar/GpuColumnarToRow analogs).
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, Iterator, List, Optional, Tuple

from spark_rapids_tpu.obs import trace as _trace
from spark_rapids_tpu.plan.logical import Schema
from spark_rapids_tpu.sched import cancel as _cancel


# ---------------------------------------------------------------------------
# Coalesce goals (reference: GpuCoalesceBatches.scala:94-130)
# ---------------------------------------------------------------------------

class CoalesceGoal:
    pass


@dataclass(frozen=True)
class TargetSize(CoalesceGoal):
    bytes: int


class RequireSingleBatch(CoalesceGoal):
    """Operator needs its whole input in one batch (total sort, hash-join
    build side, final agg without partials; reference: GpuSortExec.scala:76)."""


REQUIRE_SINGLE_BATCH = RequireSingleBatch()


# ---------------------------------------------------------------------------
# Metrics (reference: GpuMetricNames, GpuExec.scala:27-56)
#
# Unit contract: every time-valued metric is NANOSECONDS internally —
# ``total_time_ns`` and every ``extra`` key written by ``timed_extra``
# (keys end in "Time"/"Ns" by convention).  Seconds exist only at
# report time, via the explicit ``total_time_s`` / ``extra_s``
# conversions (and the QueryProfile's ``*_s`` rendering).
# ---------------------------------------------------------------------------

@dataclass
class Metrics:
    _rows_host: int = 0
    num_output_batches: int = 0
    total_time_ns: int = 0
    peak_dev_memory: int = 0
    extra: Dict[str, float] = field(default_factory=dict)
    _rows_pending: list = field(default_factory=list)
    _rows_lock: Any = field(default_factory=threading.Lock)

    def add_rows(self, nr) -> None:
        """Count output rows WITHOUT forcing a device sync: traced/device
        counts buffer and resolve lazily when the metric is read (a
        mid-pipeline int() would serialize the whole async pipeline —
        and on remote-device runtimes a single early read-back degrades
        every later dispatch).  Thread-safe: partition iterators of one
        exec run concurrently under the task pool."""
        with self._rows_lock:
            if isinstance(nr, int):
                self._rows_host += nr
            else:
                self._rows_pending.append(nr)

    def add_batches(self, n: int = 1) -> None:
        """Locked batch-count increment: partition iterators run
        concurrently under the task pool, so a bare ``+=`` loses counts
        to read-modify-write races."""
        with self._rows_lock:
            self.num_output_batches += n

    def add_extra(self, key: str, n: float) -> None:
        with self._rows_lock:
            self.extra[key] = self.extra.get(key, 0) + n

    def add_time_ns(self, ns: int) -> None:
        """Locked total_time_ns accumulation (partition iterators run
        concurrently under the task pool)."""
        with self._rows_lock:
            self.total_time_ns += ns

    def max_peak(self, v: int) -> None:
        """Locked high-water update of peak_dev_memory (concurrent
        executor-reply merges race an unlocked read-modify-write)."""
        with self._rows_lock:
            if v > self.peak_dev_memory:
                self.peak_dev_memory = v

    @property
    def total_time_s(self) -> float:
        """Report-time seconds conversion (ns internally)."""
        return self.total_time_ns / 1e9

    def extra_s(self, key: str) -> float:
        """Report-time seconds view of a time-valued ``extra`` entry
        (``timed_extra`` accumulates nanoseconds)."""
        return self.extra.get(key, 0) / 1e9

    @property
    def num_output_rows(self) -> int:
        with self._rows_lock:
            if self._rows_pending:
                from spark_rapids_tpu.columnar.batch import read_host
                self._rows_host += sum(int(read_host(x, "metrics.rowsWait"))
                                       for x in self._rows_pending)
                self._rows_pending.clear()
            return self._rows_host

    # plans ship to executor processes (shuffle/executor_proc.py); the
    # lock is process-local state and pending device scalars must be
    # resolved before crossing the boundary
    def __getstate__(self):
        d = dict(self.__dict__)
        d.pop("_rows_lock", None)
        if d.get("_rows_pending"):
            d["_rows_host"] = self.num_output_rows
            d["_rows_pending"] = []
        return d

    def __setstate__(self, d):
        self.__dict__.update(d)
        self._rows_lock = threading.Lock()
        if not hasattr(self, "_rows_pending"):
            self._rows_pending = []

    @num_output_rows.setter
    def num_output_rows(self, v) -> None:
        with self._rows_lock:
            self._rows_pending.clear()
            self._rows_host = int(v)


class PhysicalPlan:
    """Base physical node."""

    children: Tuple["PhysicalPlan", ...] = ()

    def __init__(self):
        self.metrics = Metrics()

    def __getstate__(self):
        # plan fragments ship to executor processes
        # (shuffle/executor_proc.py); jitted-kernel caches (any _kernel*
        # attribute) are process-local and must not travel
        d = dict(self.__dict__)
        for k, v in list(d.items()):
            if k.startswith("_") and "kernel" in k:
                d[k] = {} if isinstance(v, dict) else None
        return d

    @property
    def schema(self) -> Schema:
        raise NotImplementedError

    @property
    def is_tpu(self) -> bool:
        return False

    def execute(self) -> List[Iterator[Any]]:
        """One iterator of batches per partition."""
        raise NotImplementedError

    # batching contracts -----------------------------------------------------
    def children_coalesce_goal(self) -> List[Optional[CoalesceGoal]]:
        return [None] * len(self.children)

    def output_batching(self) -> Optional[CoalesceGoal]:
        return None

    # display ---------------------------------------------------------------
    def simple_string(self) -> str:
        return type(self).__name__

    def tree_string(self, indent: int = 0) -> str:
        pad = "  " * indent
        lines = [f"{pad}{'*' if self.is_tpu else ' '}{self.simple_string()}"]
        for c in self.children:
            lines.append(c.tree_string(indent + 1))
        return "\n".join(lines)

    def foreach(self, fn) -> None:
        fn(self)
        for c in self.children:
            c.foreach(fn)


class TpuExec(PhysicalPlan):
    """Marker base for device-side execs (GpuExec analog)."""

    @property
    def is_tpu(self) -> bool:
        return True


class _Timed:
    """Accumulates elapsed ns into ``metrics.total_time_ns`` and, when
    tracing is enabled and a span name was given, records the interval
    as a span (obs/trace.py; the disabled path costs one bool check).

    Entry doubles as the engine's per-batch cooperative cancellation
    checkpoint: every exec's batch loop opens ``timed`` around its
    device work, so a fired CancelToken (sched/cancel.py) unwinds the
    query here at batch granularity — one thread-local read + one bool
    check when no cancellation is pending."""

    __slots__ = ("metrics", "name", "t0", "sid")

    def __init__(self, metrics: Metrics, name: Optional[str]):
        self.metrics = metrics
        self.name = name

    def __enter__(self):
        _cancel.check_current()
        # a named span is open on this thread until exit, so what runs
        # inside (kernel.compile, a nested operator) hangs under it
        self.sid = _trace.open_span() \
            if self.name is not None and _trace.is_enabled() else 0
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *a):
        dur = time.perf_counter_ns() - self.t0
        self.metrics.add_time_ns(dur)
        if self.sid:
            _trace.close_span(self.sid, self.name, self.t0, dur)
        elif self.name is not None:
            _trace.record(self.name, self.t0, dur)


def timed(metrics: Metrics, name: Optional[str] = None):
    return _Timed(metrics, name)


class _TimedExtra:
    __slots__ = ("metrics", "key", "t0", "sid")

    def __init__(self, metrics: Metrics, key: str):
        self.metrics = metrics
        self.key = key

    def __enter__(self):
        _cancel.check_current()   # prefetch-thread batch checkpoint
        self.sid = _trace.open_span() if _trace.is_enabled() else 0
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *a):
        dur = time.perf_counter_ns() - self.t0
        self.metrics.add_extra(self.key, dur)
        if self.sid:
            _trace.close_span(self.sid, self.key, self.t0, dur)
        else:
            _trace.record(self.key, self.t0, dur)


def timed_extra(metrics: Metrics, key: str):
    """Time a sub-phase into ``Metrics.extra[key]`` (NANOSECONDS; read
    back in seconds via ``Metrics.extra_s``) WITHOUT touching
    total_time_ns — for phases that overlap the operator's main timing
    (scan host prep / upload running on a prefetch thread while the
    consumer's ``timed`` covers the dispatch).  Also recorded as a span
    named ``key`` when tracing is enabled."""
    return _TimedExtra(metrics, key)


# ---------------------------------------------------------------------------
# Executor-side metrics round trip (shuffle/executor_proc.py ships plan
# fragments whose Metrics would otherwise never return to the driver)
# ---------------------------------------------------------------------------

def collect_plan_metrics(plan: PhysicalPlan) -> List[dict]:
    """Flatten a plan tree's Metrics in pre-order (``foreach`` order).
    The pre-order index IS the plan node id: the driver's tree and the
    executor's unpickled copy share the structure, so index + class
    name key the merge."""
    out: List[dict] = []

    def one(n: PhysicalPlan) -> None:
        m = n.metrics
        out.append({
            "name": type(n).__name__,
            "rows": int(m.num_output_rows),
            "batches": int(m.num_output_batches),
            "time_ns": int(m.total_time_ns),
            "peak_dev_memory": int(m.peak_dev_memory),
            "extra": {k: v for k, v in m.extra.items()
                      if isinstance(v, (int, float))},
        })
    plan.foreach(one)
    return out


def merge_plan_metrics(plan: PhysicalPlan,
                       recorded: Optional[List[dict]],
                       skip_root: bool = False) -> None:
    """Merge executor-side metrics back into the driver-side tree
    (keyed by pre-order node id + class name; a shape mismatch drops
    the payload rather than corrupting driver metrics).  Additive, so
    every executor's share of a map stage accumulates.

    ``skip_root``: leave the root node untouched — the process-shuffle
    driver already times the whole map stage on its own exchange node,
    so merging the executor copy's exchange-node time on top would
    double-count the same work."""
    if not recorded:
        return
    nodes: List[PhysicalPlan] = []
    plan.foreach(nodes.append)
    if len(nodes) != len(recorded):
        return
    for i, (n, r) in enumerate(zip(nodes, recorded)):
        if (skip_root and i == 0) or r.get("name") != type(n).__name__:
            continue
        m = n.metrics
        if r.get("rows"):
            m.add_rows(int(r["rows"]))
        if r.get("batches"):
            m.add_batches(int(r["batches"]))
        if r.get("time_ns"):
            m.add_time_ns(int(r["time_ns"]))
        if r.get("peak_dev_memory"):
            m.max_peak(int(r["peak_dev_memory"]))
        for k, v in (r.get("extra") or {}).items():
            m.add_extra(k, v)

"""Device manager + task concurrency gate.

Analogs:
  * ``TpuDeviceManager`` — GpuDeviceManager.initializeGpuAndMemory
    (reference: GpuDeviceManager.scala:31-307): one accelerator per executor,
    memory pool sizing.  On TPU, XLA owns the HBM allocator; our arena
    accounting (mem/spill.py) tracks registered batch bytes on top of it and
    triggers spill when over budget.
  * ``tpu_semaphore`` — GpuSemaphore.acquireIfNecessary
    (reference: GpuSemaphore.scala:27-161): bounds how many tasks
    concurrently build device working sets.

On a mesh of several chips the slots and the budget count per chip (the
reference runs one executor a GPU, each with its own semaphore and
pool): a task thread says which chip it works for (``task_chip``, set
by ``exec/placement.drain_by_chip`` and by a placed scan partition) and
``tpu_semaphore`` takes a slot of that chip's gate.  A thread that says
nothing works for chip 0, so on one device nothing changes.  The tracer
stamps a span with the jax device id of the chip its thread works for
(:func:`span_chip`).
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict, List, Optional, Sequence

from spark_rapids_tpu.obs import registry as obsreg
from spark_rapids_tpu.obs import trace as obstrace
from spark_rapids_tpu.sched import cancel as _cancel
from spark_rapids_tpu.sched.admission import TaskGate

_LOCK = threading.Lock()
_GATES: Dict[int, TaskGate] = {}       # chip index -> its slots
_SLOTS = 2
_CHIPS = 1
_CHIP_IDS: List[int] = []              # jax device id of each chip, in mesh order
_TLS = threading.local()               # .chip: the chip this thread works for


def initialize(concurrent_tasks: int, chips: int = 1,
               device_ids: Optional[Sequence[int]] = None) -> None:
    """``chips``: the mesh devices partitions are placed on (1 where
    they are not, ``exec/placement.mesh_devices``); ``device_ids``
    their jax device ids in mesh order (default ``0 .. chips-1``)."""
    global _SLOTS, _CHIPS
    with _LOCK:
        _SLOTS = max(1, int(concurrent_tasks))
        _CHIPS = max(1, int(chips))
        _CHIP_IDS[:] = [] if _CHIPS == 1 else \
            [int(i) for i in (device_ids if device_ids is not None
                              else range(_CHIPS))]
        _GATES.clear()


def slots() -> int:
    """Task slots a chip (``concurrentTpuTasks``)."""
    return _SLOTS


def chips() -> int:
    """The chips whose tasks run side by side (1: no placement)."""
    return _CHIPS


def current_chip() -> int:
    return getattr(_TLS, "chip", None) or 0


def chip_device_id(chip: int) -> Optional[int]:
    """The jax device id of mesh device ``chip`` (the id that names its
    trace plane, ``/device:TPU:<id>``); None on one chip."""
    return _CHIP_IDS[chip] if 0 <= chip < len(_CHIP_IDS) else None


def span_chip() -> Optional[int]:
    """The jax device id of the chip this thread works for; None on a
    thread that works for no single chip, and on one chip."""
    chip = getattr(_TLS, "chip", None)
    return None if chip is None else chip_device_id(chip)


@contextlib.contextmanager
def task_chip(chip: int):
    """This thread works for mesh device ``chip`` until the block ends:
    its ``tpu_semaphore`` acquisitions count against that chip."""
    prev = getattr(_TLS, "chip", None)
    _TLS.chip = int(chip)
    try:
        yield
    finally:
        _TLS.chip = prev


def _get() -> TaskGate:
    chip = current_chip()
    with _LOCK:
        gate = _GATES.get(chip)
        if gate is None:
            gate = _GATES[chip] = TaskGate(_SLOTS)
        return gate


@contextlib.contextmanager
def tpu_semaphore(metrics=None):
    """Acquire one device-concurrency slot, measuring acquisition count
    and acquire-blocked nanoseconds so concurrency-limit starvation is
    visible per query: process-wide into the metrics registry
    (``semaphore.acquires`` / ``semaphore.waitNs``), per-exec into
    ``metrics.extra`` when the caller passes its Metrics, and as a
    ``semaphore.wait`` span when tracing is on.  Per-acquisition
    bookkeeping cost: a non-blocking acquire, a clock read, and ONE
    registry-lock dict update (plus the caller's Metrics lock when
    passed) — sub-microsecond against the multi-ms device dispatches
    the semaphore gates.

    The slot source is the scheduler's re-entrant
    :class:`~spark_rapids_tpu.sched.admission.TaskGate`: a thread that
    already holds a slot (scan prefetch finishing under an exchange)
    re-enters for FREE — no second slot (which deadlocked at 1 slot)
    and no double-counted blocked-ns; re-entries count into
    ``semaphore.reentries`` instead of ``semaphore.acquires``.  A
    cancelled query raises at the acquire instead of taking (or
    waiting on) a slot."""
    _cancel.check_current()
    gate = _get()
    wait_ns, reentrant = gate.acquire()
    reg = obsreg.get_registry()
    if reentrant:
        reg.inc("semaphore.reentries")
        if metrics is not None:
            metrics.add_extra("semaphore.reentries", 1)
        try:
            yield
        finally:
            gate.release()
        return
    if wait_ns:
        obstrace.record("semaphore.wait",
                        time.perf_counter_ns() - wait_ns, wait_ns,
                        cat="semaphore")
        reg.inc_many(("semaphore.acquires", 1),
                     ("semaphore.waitNs", wait_ns))
    else:
        reg.inc("semaphore.acquires")
    if metrics is not None:
        metrics.add_extra("semaphore.acquires", 1)
        if wait_ns:
            metrics.add_extra("semaphore.waitNs", wait_ns)
    try:
        yield
    finally:
        gate.release()


# memory budget assumed on a platform that is not a TPU
_HOST_PLATFORM_BUDGET = 8 << 30


class TpuDeviceManager:
    """Holds device handles + memory budget (XLA owns the real allocator)."""

    _instance: Optional["TpuDeviceManager"] = None

    def __init__(self, pool_fraction: float = 0.9):
        import jax
        self.devices = jax.devices()
        self.default_device = self.devices[0]
        self.pool_fraction = pool_fraction
        if self.default_device.platform == "tpu":
            # the budget is the device's own limit or start-up fails:
            # a guessed pool would admit work against memory that may
            # not exist
            self.hbm_budgets = [
                int(d.memory_stats()["bytes_limit"] * pool_fraction)
                for d in self.devices]
        else:
            # host platforms (the CPU test platform) report no limit
            self.hbm_budgets = [_HOST_PLATFORM_BUDGET] * len(self.devices)
        # what one chip may hold: a task's working set lies on one chip,
        # so the smallest chip's budget bounds it wherever it runs
        self.hbm_budget = min(self.hbm_budgets)

    @classmethod
    def get(cls) -> "TpuDeviceManager":
        if cls._instance is None:
            cls._instance = TpuDeviceManager()
        return cls._instance

    @property
    def platform(self) -> str:
        return self.default_device.platform


def memory_peaks() -> List[int]:
    """``peak_bytes_in_use`` of every device, in ``jax.devices()``'s
    order (0 where the backend reports none): on a host of several chips
    the fullest device's peak says nothing of the others."""
    import jax
    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in jax.devices()]

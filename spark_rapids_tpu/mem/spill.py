"""Spill framework: Device -> Host -> Disk tiered batch storage.

Reference analog (SURVEY.md §2b): ``RapidsBufferCatalog`` chaining
Device/Host/Disk stores (RapidsBufferCatalog.scala:34-210,
RapidsBufferStore.scala:40-351), priority-ordered synchronous spill on
allocation failure (DeviceMemoryEventHandler.scala:42-70,
SpillPriorities.scala), and ``SpillableColumnarBatch`` handles that let
operators hold batches that remain spillable
(SpillableColumnarBatch.scala:169).

TPU adaptation: XLA owns the HBM allocator, so instead of an RMM callback
the catalog enforces a *budget*: every registered batch counts toward a
device-bytes ceiling, and crossing it (or an explicit ``spill_to_fit``)
synchronously spills lowest-priority buffers device->host->disk.  The host
tier stages its numpy copies inside the native HostArena
(mem/host_arena.py); overflowing the host budget falls through to disk
(.npz files under the spill dir, RapidsDiskStore analog).
"""

from __future__ import annotations

import enum
import heapq
import itertools
import os
import tempfile
import threading
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from spark_rapids_tpu import dtypes as dt
from spark_rapids_tpu.columnar.batch import DeviceBatch, DeviceColumn
from spark_rapids_tpu.mem.host_arena import HostArena
from spark_rapids_tpu.obs import recorder as obsrec
from spark_rapids_tpu.obs import registry as obsreg


class StorageTier(enum.IntEnum):
    DEVICE = 0
    HOST = 1
    DISK = 2


# spill priorities (reference: SpillPriorities.scala)
ACTIVE_ON_DECK_PRIORITY = 1 << 40
ACTIVE_BATCHING_PRIORITY = 1 << 30
INPUT_FROM_SHUFFLE_PRIORITY = 0
OUTPUT_FOR_SHUFFLE_PRIORITY = -(1 << 30)
# grace-join build/probe partitions parked while another partition is
# being joined: the coldest data in the process — they spill first
GRACE_JOIN_PARTITION_PRIORITY = -(1 << 31)


@dataclass
class _HostPayload:
    """Host copy of a batch: numpy arrays (arena-backed when possible)."""

    names: List[str]
    dtypes: List[dt.DType]
    num_rows: int
    arrays: List[Tuple[np.ndarray, np.ndarray, Optional[np.ndarray]]]
    allocations: List = field(default_factory=list)

    def nbytes(self) -> int:
        total = 0
        for d, v, l in self.arrays:
            total += d.nbytes + v.nbytes + (l.nbytes if l is not None else 0)
        return total

    def close(self):
        self.arrays = []
        for a in self.allocations:
            a.close()
        self.allocations = []


class _Buffer:
    def __init__(self, buffer_id: int, batch: DeviceBatch, priority: int):
        self.id = buffer_id
        self.priority = priority
        self.tier = StorageTier.DEVICE
        self.device_batch: Optional[DeviceBatch] = batch
        self.host: Optional[_HostPayload] = None
        self.disk_path: Optional[str] = None
        self.size = batch.nbytes()
        # num_rows may be a traced device scalar (a jitted kernel's
        # output); int() here would block the whole async pipeline on a
        # synchronous device->host round trip per registered batch — the
        # r2 bench's dominant cost.  Defer the read to spill time, when
        # we download the data anyway.
        self._meta = (list(batch.names),
                      [c.dtype for c in batch.columns], batch.num_rows)
        self.lock = threading.Lock()
        self.closed = False

    @property
    def meta(self):
        names, dtypes, nr = self._meta
        if not isinstance(nr, (int, np.integer)):
            nr = int(nr)
            self._meta = (names, dtypes, nr)
        return (names, dtypes, nr)


class BufferCatalog:
    """Singleton-ish catalog managing registered spillable batches."""

    def __init__(self, device_budget: int = 4 << 30,
                 host_budget: int = 8 << 30,
                 spill_dir: Optional[str] = None,
                 host_arena: Optional[HostArena] = None):
        self.device_budget = device_budget
        self.host_budget = host_budget
        self.spill_dir = spill_dir or tempfile.mkdtemp(
            prefix="rapids_tpu_spill_")
        self.host_arena = host_arena or HostArena(
            min(host_budget, 1 << 30))
        self._buffers: Dict[int, _Buffer] = {}
        self._ids = itertools.count()
        # RLock: SpillableBatch.__del__ may fire during a GC triggered
        # inside a catalog method that already holds the lock
        self._lock = threading.RLock()
        self.device_bytes = 0
        self.host_bytes = 0
        self.spilled_device_bytes = 0  # metrics (memoryBytesSpilled analog)
        self.spilled_disk_bytes = 0
        self._hwm_trackers: List["HighWaterTracker"] = []

    # -- registration ------------------------------------------------------
    def register(self, batch: DeviceBatch,
                 priority: int = ACTIVE_BATCHING_PRIORITY
                 ) -> "SpillableBatch":
        with self._lock:
            bid = next(self._ids)
            buf = _Buffer(bid, batch, priority)
            self._buffers[bid] = buf
            self.device_bytes += buf.size
            self._note_device_bytes_locked()
        obsreg.get_registry().gauge_max("spill.deviceBytesHwm",
                                        self.device_bytes)
        self._maybe_spill()
        return SpillableBatch(self, bid)

    # -- per-window device-bytes high water (admission refinement) ---------
    def _note_device_bytes_locked(self) -> None:
        for t in self._hwm_trackers:
            t._note(self.device_bytes)

    def track_high_water(self) -> "HighWaterTracker":
        """Open a device-bytes high-water window (the scheduler's
        estimate-refinement probe: one per running query).  Under
        concurrency the window sees OTHER queries' registered bytes too
        — a conservative over-estimate, which is the safe direction for
        admission control."""
        with self._lock:
            t = HighWaterTracker(self, self.device_bytes)
            self._hwm_trackers.append(t)
            return t

    def _end_high_water(self, t: "HighWaterTracker") -> None:
        with self._lock:
            if t in self._hwm_trackers:
                self._hwm_trackers.remove(t)

    # -- spill logic -------------------------------------------------------
    def _spill_candidates(self) -> List[_Buffer]:
        with self._lock:
            cands = [b for b in self._buffers.values()
                     if b.tier == StorageTier.DEVICE and not b.closed]
        # lowest priority spills first (reference: HashedPriorityQueue order)
        return sorted(cands, key=lambda b: b.priority)

    def _maybe_spill(self) -> None:
        if self.device_bytes <= self.device_budget:
            return
        need = self.device_bytes - self.device_budget
        self.spill_to_fit(need)

    def spill_to_fit(self, bytes_needed: int) -> int:
        """Synchronously spill device buffers until bytes_needed freed
        (DeviceMemoryEventHandler.onAllocFailure analog)."""
        freed = 0
        for buf in self._spill_candidates():
            if freed >= bytes_needed:
                break
            freed += self._spill_one(buf)
        return freed

    def _spill_one(self, buf: _Buffer) -> int:
        with buf.lock:
            if buf.tier != StorageTier.DEVICE or buf.closed:
                return 0
            batch = buf.device_batch
            payload = _device_to_host(batch, self.host_arena)
            buf.host = payload
            buf.device_batch = None
            buf.tier = StorageTier.HOST
            size = buf.size
        with self._lock:
            self.device_bytes -= size
            self.host_bytes += payload.nbytes()
            self.spilled_device_bytes += size
        reg = obsreg.get_registry()
        reg.inc("spill.events")
        reg.inc("spill.deviceToHostBytes", size)
        reg.gauge_max("spill.hostBytesHwm", self.host_bytes)
        obsrec.record_event("spill.deviceToHost", buffer=buf.id,
                            bytes=size, host_bytes=self.host_bytes)
        self._maybe_spill_host()
        return size

    def _maybe_spill_host(self) -> None:
        while self.host_bytes > self.host_budget:
            with self._lock:
                cands = [b for b in self._buffers.values()
                         if b.tier == StorageTier.HOST and not b.closed]
            if not cands:
                return
            victim = min(cands, key=lambda b: b.priority)
            self._spill_to_disk(victim)

    def _spill_to_disk(self, buf: _Buffer) -> None:
        with buf.lock:
            if buf.tier != StorageTier.HOST or buf.closed:
                return
            path = os.path.join(self.spill_dir, f"buf_{buf.id}.npz")
            arrays = {}
            for i, (d, v, l) in enumerate(buf.host.arrays):
                arrays[f"d{i}"] = d
                arrays[f"v{i}"] = v
                if l is not None:
                    arrays[f"l{i}"] = l
            np.savez(path, **arrays)
            nbytes = buf.host.nbytes()
            buf.host.close()
            buf.host = None
            buf.disk_path = path
            buf.tier = StorageTier.DISK
        with self._lock:
            self.host_bytes -= nbytes
            self.spilled_disk_bytes += nbytes
        reg = obsreg.get_registry()
        reg.inc("spill.events")
        reg.inc("spill.hostToDiskBytes", nbytes)
        obsrec.record_event("spill.hostToDisk", buffer=buf.id,
                            bytes=nbytes)

    # -- access ------------------------------------------------------------
    def acquire(self, buffer_id: int) -> DeviceBatch:
        """Materialize the batch on device (unspilling as needed)."""
        buf = self._buffers[buffer_id]
        with buf.lock:
            assert not buf.closed, "buffer already closed"
            if buf.tier == StorageTier.DEVICE:
                return buf.device_batch
            if buf.tier == StorageTier.DISK:
                self._disk_to_host_locked(buf)
            obsreg.get_registry().inc("spill.unspills")
            batch = _host_to_device(buf.host, buf.meta)
            # promote back to device tier
            nbytes = buf.host.nbytes()
            buf.host.close()
            buf.host = None
            buf.device_batch = batch
            buf.tier = StorageTier.DEVICE
        with self._lock:
            self.host_bytes -= nbytes
            self.device_bytes += buf.size
            self._note_device_bytes_locked()
        self._maybe_spill()
        return batch

    def _disk_to_host_locked(self, buf: _Buffer) -> None:
        names, dtypes, num_rows = buf.meta
        loaded = np.load(buf.disk_path)
        arrays = []
        for i, d in enumerate(dtypes):
            arrays.append((loaded[f"d{i}"], loaded[f"v{i}"],
                           loaded[f"l{i}"] if f"l{i}" in loaded else None))
        buf.host = _HostPayload(names, dtypes, num_rows, arrays)
        os.unlink(buf.disk_path)
        buf.disk_path = None
        buf.tier = StorageTier.HOST
        with self._lock:
            self.host_bytes += buf.host.nbytes()

    def tier_of(self, buffer_id: int) -> StorageTier:
        return self._buffers[buffer_id].tier

    def spill_buffer(self, buffer_id: int) -> int:
        """Targeted spill of ONE registered buffer device->host
        (grace-join partitions demote themselves while parked instead
        of waiting for global pressure).  Returns device bytes freed
        (0 when already off-device or closed)."""
        buf = self._buffers.get(buffer_id)
        if buf is None:
            return 0
        return self._spill_one(buf)

    def release(self, buffer_id: int) -> None:
        buf = self._buffers.pop(buffer_id, None)
        if buf is None:
            return
        with buf.lock:
            buf.closed = True
            if buf.tier == StorageTier.DEVICE:
                with self._lock:
                    self.device_bytes -= buf.size
            elif buf.tier == StorageTier.HOST:
                with self._lock:
                    self.host_bytes -= buf.host.nbytes()
                buf.host.close()
            elif buf.disk_path and os.path.exists(buf.disk_path):
                os.unlink(buf.disk_path)
            buf.device_batch = None


class HighWaterTracker:
    """One device-bytes high-water window over the catalog (see
    :meth:`BufferCatalog.track_high_water`)."""

    __slots__ = ("_catalog", "_start", "_peak", "_closed")

    def __init__(self, catalog: "BufferCatalog", start_bytes: int):
        self._catalog = catalog
        self._start = start_bytes
        self._peak = start_bytes
        self._closed = False

    def _note(self, device_bytes: int) -> None:
        if device_bytes > self._peak:
            self._peak = device_bytes

    def peak(self) -> int:
        return self._peak

    def delta(self) -> int:
        """Peak GROWTH over the window (peak - start): what this
        query's run added on top of whatever was already resident
        (cached blobs, other queries' working sets) — the admission
        estimate refines on this, not the absolute catalog peak, so a
        cheap query that merely ran next to a heavyweight one is not
        booked at the neighbour's footprint."""
        return self._peak - self._start

    def close(self) -> None:
        if not self._closed:
            self._closed = True
            self._catalog._end_high_water(self)


class SpillableBatch:
    """Operator-held handle to a batch that remains spillable
    (SpillableColumnarBatch analog)."""

    def __init__(self, catalog: BufferCatalog, buffer_id: int):
        self._catalog = catalog
        self._id = buffer_id
        self._closed = False

    def get(self) -> DeviceBatch:
        return self._catalog.acquire(self._id)

    @property
    def tier(self) -> StorageTier:
        return self._catalog.tier_of(self._id)

    def spill(self) -> int:
        """Demote this batch off the device tier now (see
        :meth:`BufferCatalog.spill_buffer`)."""
        if self._closed:
            return 0
        return self._catalog.spill_buffer(self._id)

    def close(self) -> None:
        if not self._closed:
            self._catalog.release(self._id)
            self._closed = True

    def __del__(self):
        # abandoned handles (e.g. a limit short-circuiting an adaptive
        # join's readers) must not pin catalog entries forever
        try:
            self.close()
        except Exception:
            pass

    def __enter__(self):
        return self

    def __exit__(self, *a):
        self.close()


class PlainBatchHandle:
    """SpillableBatch-shaped holder (get/close) used by operators that
    buffer batches when the spill catalog is disabled."""

    def __init__(self, batch: DeviceBatch):
        self._batch = batch

    def get(self) -> DeviceBatch:
        return self._batch

    @property
    def tier(self) -> StorageTier:
        return StorageTier.DEVICE

    def spill(self) -> int:
        return 0  # nowhere to go with the catalog disabled

    def close(self) -> None:
        self._batch = None

    def __enter__(self):
        return self

    def __exit__(self, *a):
        self.close()


def register_or_hold(batch: DeviceBatch,
                     priority: Optional[int] = None):
    """Register `batch` in the global spill catalog when enabled, else
    wrap it in a PlainBatchHandle; either way the caller gets a
    get()/close() handle.  ``priority`` overrides the catalog's
    default spill priority (e.g. INPUT_FROM_SHUFFLE_PRIORITY for
    prepared pipelined-shuffle partitions)."""
    if not is_enabled():
        return PlainBatchHandle(batch)
    if priority is None:
        return get_catalog().register(batch)
    return get_catalog().register(batch, priority=priority)


# ---------------------------------------------------------------------------
# device <-> host payload conversion
# ---------------------------------------------------------------------------

def _device_to_host(batch: DeviceBatch, arena: HostArena) -> _HostPayload:
    arrays = []
    allocations = []
    for c in batch.columns:
        d = np.asarray(c.data)
        v = np.asarray(c.validity)
        l = np.asarray(c.lengths) if c.lengths is not None else None
        # stage through the native arena when a block fits (pinned-pool
        # analog); otherwise keep the plain numpy copy
        alloc = arena.alloc(d.nbytes)
        if alloc is not None:
            staged = alloc.as_numpy(d.dtype, d.shape)
            np.copyto(staged, d)
            d = staged
            allocations.append(alloc)
        arrays.append((d, v, l))
    return _HostPayload(list(batch.names),
                        [c.dtype for c in batch.columns],
                        int(batch.num_rows), arrays, allocations)


def _host_to_device(payload: _HostPayload, meta) -> DeviceBatch:
    names, dtypes, num_rows = meta
    cols = []
    for (d, v, l), dty in zip(payload.arrays, dtypes):
        cols.append(DeviceColumn(
            dty, jnp.asarray(d), jnp.asarray(v),
            jnp.asarray(l) if l is not None else None))
    return DeviceBatch(names, cols, num_rows)


# ---------------------------------------------------------------------------
# process-wide catalog (GpuShuffleEnv-style executor singleton; reference:
# GpuShuffleEnv.scala:26-108, RapidsBufferCatalog.init)
# ---------------------------------------------------------------------------

_GLOBAL: Optional[BufferCatalog] = None
_GLOBAL_ENABLED = True
_GLOBAL_LOCK = threading.Lock()


def init_catalog(device_budget: int, host_budget: int,
                 spill_dir: Optional[str] = None) -> BufferCatalog:
    global _GLOBAL, _GLOBAL_ENABLED
    with _GLOBAL_LOCK:
        _GLOBAL = BufferCatalog(device_budget, host_budget,
                                spill_dir or None)
        _GLOBAL_ENABLED = True
        return _GLOBAL


def disable_catalog() -> None:
    """spark.rapids.tpu.memory.spill.enabled=false: operators hold batches
    directly, nothing is registered or spilled."""
    global _GLOBAL_ENABLED
    with _GLOBAL_LOCK:
        _GLOBAL_ENABLED = False


def is_enabled() -> bool:
    with _GLOBAL_LOCK:
        return _GLOBAL_ENABLED


def hbm_oom_recover(e: BaseException) -> bool:
    """Alloc-failure-driven spill (DeviceMemoryEventHandler.onAllocFailure
    analog, reference: DeviceMemoryEventHandler.scala:42-70).

    XLA owns HBM, so instead of an in-allocator callback the engine
    catches the failed dispatch/read, synchronously spills EVERY
    device-tier registered buffer to host, and tells the caller to
    retry.  Returns True when the error is an HBM exhaustion and bytes
    were actually freed."""
    msg = str(e)
    if "RESOURCE_EXHAUSTED" not in msg and \
            "out of memory" not in msg.lower():
        return False
    cat = get_catalog()
    freed = cat.spill_to_fit(1 << 62)     # evict the whole device tier
    # and the scans' uploaded page sets, which the next scan makes again
    from spark_rapids_tpu.io import scan_cache
    freed += scan_cache.pressure_spill()
    if freed > 0:
        # the flight recorder bundles a SUCCESSFUL query whose window
        # moved this counter — surviving only by evicting the whole
        # device tier is a diagnosis waiting to happen
        obsreg.get_registry().inc("mem.oomRetries")
        obsrec.record_event("mem.oomRetry", freed_bytes=freed,
                            error=msg[:200])
    return freed > 0


# ---------------------------------------------------------------------------
# Auxiliary pressure spillers (in-flight shuffle buffers, etc.)
# ---------------------------------------------------------------------------

_PRESSURE_SPILLERS: List = []   # weakref.ref to objects w/ pressure_spill
_PRESSURE_LOCK = threading.Lock()


def register_pressure_spiller(obj) -> None:
    """Register an object exposing ``pressure_spill(bytes_needed) ->
    bytes_freed`` with the admission-pressure hook.  Held by weakref:
    a shuffle's received-buffer catalog (the main client) registers at
    construction and simply drops out when the exchange releases it —
    no unregister ceremony on the error paths."""
    import weakref
    with _PRESSURE_LOCK:
        _PRESSURE_SPILLERS[:] = [r for r in _PRESSURE_SPILLERS
                                 if r() is not None]
        _PRESSURE_SPILLERS.append(weakref.ref(obj))


def _aux_pressure_spill(bytes_needed: int) -> int:
    freed = 0
    with _PRESSURE_LOCK:
        refs = list(_PRESSURE_SPILLERS)
    for r in refs:
        if freed >= bytes_needed:
            break
        obj = r()
        if obj is None:
            continue
        try:
            freed += int(obj.pressure_spill(bytes_needed - freed))
        except Exception:
            # a broken spiller must not fail admission — but it must
            # be auditable: 0 aux bytes with errors ticking is
            # "spiller broken", not "nothing pending"
            obsreg.get_registry().inc("spill.pressureAuxErrors")
    return freed


def handle_memory_pressure(bytes_needed: int) -> int:
    """Admission-control memory-pressure hook: when the scheduler
    admits a query into the top of the memory budget, proactively
    spill lowest-priority registered device batches so real HBM backs
    the newly admitted estimate (the DeviceMemoryEventHandler role,
    driven from admission instead of an alloc failure).  When the
    device tier alone can't cover it, auxiliary spillers run —
    in-flight received shuffle payloads move host->disk (pipelined
    shuffle buffers respond to pressure instead of stalling
    admission).  Returns bytes freed; a no-op while spill is
    disabled."""
    if not is_enabled() or bytes_needed <= 0:
        return 0
    device_freed = get_catalog().spill_to_fit(int(bytes_needed))
    aux_freed = 0
    if device_freed < bytes_needed:
        aux_freed = _aux_pressure_spill(
            int(bytes_needed) - device_freed)
    # tier-split accounting: device bytes are reclaimed HBM backing;
    # aux bytes are host RAM moved to disk (received shuffle payloads)
    # — capacity tuning must not read the second as the first (the
    # summed return feeds sched.pressureSpillBytes as total relief)
    reg = obsreg.get_registry()
    if device_freed:
        reg.inc("spill.pressureDeviceBytes", device_freed)
    if aux_freed:
        reg.inc("spill.pressureAuxBytes", aux_freed)
    if device_freed or aux_freed:
        reg.inc("spill.pressureSpills")
    return device_freed + aux_freed


def get_catalog() -> BufferCatalog:
    global _GLOBAL
    with _GLOBAL_LOCK:
        if _GLOBAL is None:
            _GLOBAL = BufferCatalog()
        return _GLOBAL

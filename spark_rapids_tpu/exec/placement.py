"""Which chip a partition's work runs on, on a mesh of several chips.

The reference runs one executor a GPU: each scans its own file splits,
aggregates, and shuffles device to device (SURVEY.md §2g, §5).  Here one
process holds every chip of the host, so the same layout is a rule over
partition numbers: **partition ``p`` belongs to mesh device
``p % n_dev``** (the rule ``TpuShuffleExchangeExec._execute_ici`` states
for its reducers).  A scan under the ICI transport uploads partition
``p``'s pages to that device, so its decode and every operator up to
the exchange run there because their inputs are committed there; the
exchange takes each chip's batches where they lie.

Nothing here is a knob: the transport and the mesh's size decide.  On
one device ``mesh_devices`` is empty and every caller keeps its
one-device path.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Callable, List, Optional, Sequence

from spark_rapids_tpu import config as cfg
from spark_rapids_tpu.mem import device as devmgr
from spark_rapids_tpu.obs import trace as obstrace
from spark_rapids_tpu.sched import cancel as _cancel


def mesh_devices(conf) -> list:
    """The mesh's devices in mesh order where partitions are placed (the
    ICI transport on a mesh of more than one device), else ``[]``."""
    if str(conf.get(cfg.SHUFFLE_TRANSPORT)) not in ("ici", "ici_ring"):
        return []
    from spark_rapids_tpu.shuffle import ici
    devs = list(ici.get_default_mesh().devices.flat)
    return devs if len(devs) > 1 else []


def device_of(batch):
    """The one device a batch's first column lies on; ``None`` for a
    batch with no columns or one spread over several devices."""
    if not batch.columns:
        return None
    devs = batch.columns[0].data.devices()
    return next(iter(devs)) if len(devs) == 1 else None


def drain_by_chip(its: Sequence, sink: Callable,
                  n_dev: Optional[int] = None, *, stage: str) -> None:
    """Drain partition iterators with the partitions of different chips
    side by side: partition ``p`` is a task of chip ``p % n_dev``, a
    chip runs at most ``concurrentTpuTasks`` of its tasks at a time and
    takes them in partition order.  ``sink(p, batch)`` is called for
    every batch on the task's thread.  ``n_dev`` defaults to the chips
    the session places partitions on; on one chip (or one partition) it
    is a plain loop on the caller's thread.

    The drain is a barrier: a chip whose tasks end first waits for its
    peers.  With tracing on, each such chip gets a ``chip.peerWait``
    span (cat ``query``, stamped with the chip, args ``stage``: what
    the caller drains for) from its last task's end to the drain's
    end; the slowest chip gets none."""
    n_dev = devmgr.chips() if n_dev is None else n_dev
    if n_dev <= 1 or len(its) <= 1:
        for p, it in enumerate(its):
            for b in it:
                sink(p, b)
        return
    queues = [deque(p for p in range(len(its)) if p % n_dev == c)
              for c in range(n_dev)]
    tok = _cancel.current()
    errors: List[BaseException] = []
    # when each chip's last task thread ended (a chip with no task: the
    # drain's start)
    t_start = time.perf_counter_ns()
    ended = [t_start] * n_dev
    lock = threading.Lock()

    def work(chip: int) -> None:
        # task threads inherit the query's CancelToken explicitly
        # (threads don't propagate thread-locals)
        try:
            with _cancel.install(tok), devmgr.task_chip(chip):
                while not errors:
                    try:
                        p = queues[chip].popleft()
                    except IndexError:
                        return
                    try:
                        for b in its[p]:
                            sink(p, b)
                    except BaseException as e:
                        errors.append(e)
        finally:
            t = time.perf_counter_ns()
            with lock:
                ended[chip] = max(ended[chip], t)

    threads = [threading.Thread(target=work, args=(c,),
                                name=f"tpu-task-chip{c}-{k}")
               for c in range(n_dev)
               for k in range(min(devmgr.slots(), len(queues[c])))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]
    if obstrace.is_enabled():
        t_end = time.perf_counter_ns()
        last = max(ended)
        for chip, t in enumerate(ended):
            if t < last:
                obstrace.record("chip.peerWait", t, t_end - t, cat="query",
                                args={"stage": stage},
                                chip=devmgr.chip_device_id(chip))

"""In-query reuse: a subplan a query wrote more than once is computed
once and served to every place that reads it.

Reference analog: Spark's ``ReuseExchange`` rule and the
``ReusedExchangeExec`` leaf it plants where the second exchange stood
(the reference plugin inherits both).  Here the unit is an aggregate
(``plan/optimizer.mark_equal_aggregates`` finds them,
``plan/overrides._tie_reused_subplans`` places this exec): a breaker
whose result is whole in HBM when it yields and small beside its
input.  See docs/work_sharing.md.

The plan stays a tree.  The first occurrence keeps its subtree under a
:class:`TpuReusedSubplanExec` that owns the computation; every later
occurrence is a childless :class:`TpuReusedSubplanExec` that refers to
the first and carries its own output names.
"""

from __future__ import annotations

import threading
from typing import Iterator, List, Optional

from spark_rapids_tpu.columnar.batch import DeviceBatch
from spark_rapids_tpu.exec.base import PhysicalPlan, TpuExec
from spark_rapids_tpu.plan.logical import Schema
from spark_rapids_tpu.sched import cancel as _cancel

# guards the swap of an owner's ``_held`` only; the computation has the
# result's own lock
_ATTACH_LOCK = threading.Lock()


class _Held:
    """One execution's result of a reused subplan: per-partition lists
    of spill handles, computed by the first consumer that pulls."""

    def __init__(self) -> None:
        self.lock = threading.Lock()
        # consumers (by identity) whose execute() joined this execution
        self.attached: set = set()
        self.parts: Optional[List[list]] = None
        self.error: Optional[BaseException] = None
        # partition iterators handed out and not yet finished
        self.open = 0

    def let_go(self) -> None:
        """Caller holds ``lock``."""
        for part in self.parts or ():
            for h in part:
                h.close()
        self.parts = []


class TpuReusedSubplanExec(TpuExec):
    """One of the places a reused subplan is read from.

    With a child it is the occurrence that was kept: whichever consumer
    pulls first drains the child under the result's lock
    (``_BroadcastBuildMixin._build``'s idiom) and holds the batches
    through ``mem/spill.register_or_hold``; every consumer is then
    handed those same batches, under its own schema's names.  With
    ``source`` it stands where another occurrence was, and reads from
    that one.

    Nothing runs at ``execute()``: a consumer may sit on the stream
    side of the join whose build side holds the other, and its
    ``execute()`` comes before the build drains.  The result belongs
    to one execution (a consumer that executes again opens a new one),
    is let go when the last consumer's last partition is done and at
    once where the producer fails or a consumer is cancelled, and does
    not travel when the plan is pickled.  Shared buffers: this class is
    not among ``fused_stage._DONATE_SAFE_PRODUCERS``."""

    def __init__(self, schema: Schema, key: str, partitions: int = 1,
                 child: Optional[PhysicalPlan] = None,
                 source: Optional["TpuReusedSubplanExec"] = None):
        super().__init__()
        assert (child is None) != (source is None)
        self.children = () if child is None else (child,)
        self._schema = schema
        self.key = key
        self.partitions = partitions
        self._source = source
        # occurrences that read this result, itself included
        self.consumers = 1
        self._held: Optional[_Held] = None

    def __getstate__(self):
        d = super().__getstate__()
        d["_held"] = None
        return d

    @property
    def schema(self) -> Schema:
        return self._schema

    def simple_string(self) -> str:
        tag = f"subplan {self.key[:8]}"
        if self._source is None:
            return (f"TpuReusedSubplanExec({tag}, computed once for "
                    f"{self.consumers} consumers)")
        return (f"TpuReusedSubplanExec(reuses {tag}: "
                f"{self._source.children[0].simple_string()})")

    # ------------------------------------------------------------------
    def _attach(self, consumer: "TpuReusedSubplanExec") -> _Held:
        with _ATTACH_LOCK:
            held = self._held
            if held is None or id(consumer) in held.attached:
                held = self._held = _Held()
            held.attached.add(id(consumer))
            held.open += self.partitions
            return held

    def _compute(self, held: _Held) -> None:
        """Caller holds ``held.lock``."""
        from spark_rapids_tpu.exec.placement import drain_by_chip
        from spark_rapids_tpu.mem.spill import register_or_hold
        its = self.children[0].execute()
        parts = held.parts = [[] for _ in its]
        try:
            # partitions of different chips side by side (one loop here
            # on one chip)
            drain_by_chip(its, lambda p, b: parts[p].append(
                register_or_hold(b)), stage="reuse")
            assert len(parts) == self.partitions, \
                (len(parts), self.partitions)
        except BaseException as e:
            held.error = e
            held.let_go()
            raise

    def _serve(self, held: _Held, p: int,
               consumer: "TpuReusedSubplanExec") -> Iterator[DeviceBatch]:
        from spark_rapids_tpu.obs import registry as obsreg, \
            trace as obstrace
        names = consumer._schema.names
        try:
            if not held.lock.acquire(blocking=False):
                with obstrace.span("reuse.wait"):
                    held.lock.acquire()
            try:
                if held.error is not None:
                    raise held.error
                if held.parts is None:
                    self._compute(held)
                else:
                    obsreg.get_registry().inc("exec.reuse.served")
                part = list(held.parts[p])
            finally:
                held.lock.release()
            for h in part:
                try:
                    _cancel.check_current()
                    b = h.get()
                except BaseException as e:
                    with held.lock:
                        held.error = held.error or e
                        held.let_go()
                    raise
                consumer.metrics.add_rows(b.num_rows)
                consumer.metrics.add_batches()
                yield DeviceBatch(names, b.columns, b.num_rows)
        finally:
            with held.lock:
                held.open -= 1
                if not held.open and \
                        len(held.attached) == self.consumers:
                    held.let_go()

    def execute(self):
        owner = self if self._source is None else self._source
        held = owner._attach(self)
        return [owner._serve(held, p, self)
                for p in range(owner.partitions)]

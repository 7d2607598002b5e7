"""Whole-stage fused exec: one kernel for a collapsed Project/Filter chain.

The per-node execution model pays one jitted dispatch per exec per batch
(its cost is not measured on the attached chip) and materializes full
padded intermediate columns in HBM between every Project/Filter.
``TpuFusedStageExec`` is the engine's whole-stage-codegen analog
(reference: Spark's WholeStageCodegenExec; the reference plugin's tiered
project / combined filter-project, basicPhysicalOperators.scala): the
planner pass in :mod:`spark_rapids_tpu.plan.fusion` collapses a maximal
chain of dispatch-only execs into one node whose single cached kernel

  1. evaluates the AND-combination of every filter condition in the
     chain (each rewritten over the stage INPUT schema, so conditions
     from different chain depths compose without materializing the
     columns between them),
  2. performs at most ONE stream compaction, and
  3. evaluates the composed output projection — a fused filter->project
     pays zero intermediate materialization.  Projection and compaction
     order per stage by WIDTH: compaction costs one full-capacity
     scatter per column (the engine's dominant compaction cost, see the
     ``agg.fusedFilter`` rationale in config.py), so when the composed
     output is narrower than the stage input the kernel projects first
     and compacts only the output columns; otherwise it compacts the
     input first.  Both orders are sound — every fusable expression is
     row-wise, so evaluating it on rows the filter drops is harmless
     (see below) and ``compact``'s keep-mask applies unchanged on
     either side of the projection.

Rewriting upper-chain expressions over the stage input is sound because
every fusable expression is row-wise and position-independent (the
fusion pass bars MonotonicallyIncreasingID / Rand from chains — their
values depend on row position, which compaction changes); evaluating a
condition on rows a lower filter would have dropped is harmless under
the engine's total-function semantics (x/0 is NULL, never a fault), and
AND is commutative, so the combined keep-set is exactly the chain's.

A stage whose composed projection is pure column selection (every
output a BoundReference, no condition) runs in **passthrough** mode:
zero dispatches, host-side column pick/rename only — the common
``prune_columns`` select below a sort/window stops costing a kernel
launch entirely.

Input-buffer donation (``sql.fusion.donateInputs``, stamped per-plan
by ``TpuOverrides.apply`` as ``_donate_enabled`` on every node): when
the producing exec is known not to retain its yielded batches, the
stage (and the plain project/filter execs) jits with ``donate_argnums``
so XLA reuses the input batch's HBM for the output — deep chains stop
holding two copies of every intermediate.
"""

from __future__ import annotations

import warnings
from typing import List, Optional, Sequence

import jax.numpy as jnp

from spark_rapids_tpu.columnar.batch import DeviceBatch
from spark_rapids_tpu.exec.base import PhysicalPlan, TpuExec, timed
from spark_rapids_tpu.expr import eval_tpu, ir
from spark_rapids_tpu.plan.logical import Schema

_warn_filter_installed = False


def _install_donation_warn_filter() -> None:
    """jax warns per-compile when a donated buffer's shape has no
    output to reuse it for (e.g. a string column whose max_len bucket
    changed); partial reuse is exactly the intent, so the warning is
    noise — but only processes that actually build a donating kernel
    should mutate the global warnings filter (an import side effect
    would suppress it for the user's own unrelated jax code too)."""
    global _warn_filter_installed
    if not _warn_filter_installed:
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        _warn_filter_installed = True

# producers whose yielded batches are fresh per batch and never
# re-served (caches, broadcast builds and shuffle catalogs may alias
# buffers they hand out — donating those would corrupt a later read)
_DONATE_SAFE_PRODUCERS = frozenset({
    "HostToDeviceExec", "TpuProjectExec", "TpuFilterExec",
    "TpuFusedStageExec", "TpuRangeExec", "TpuParquetScanExec",
    "TpuOrcScanExec", "TpuCsvScanExec",
})


def donate_ok(child: PhysicalPlan, enabled: bool) -> bool:
    """May a consumer donate the batches ``child`` yields?

    ``enabled`` is the consumer's PLAN-STAMPED donation flag
    (``sql.fusion.donateInputs``, stamped on every node by
    ``TpuOverrides.apply``): each session's plans carry their own
    setting, so a later session with a different conf cannot flip an
    earlier session's behavior, and plan fragments shipped to executor
    processes (shuffle/executor_proc.py) honor the driver's conf with
    no pickled-conf side channel.  An un-stamped plan (hand-built in a
    test) never donates.

    A passthrough fused stage forwards its child's column buffers BY
    REFERENCE (zero-dispatch host-side pick), so the donation decision
    must look through it to the transitive producer — a pure select
    over a cache/shuffle read must not launder those aliased buffers
    into the donate-safe set.  A passthrough that DUPLICATES a column
    (select(a, a.alias(a2))) yields the same device array as two batch
    leaves; donating that batch is an XLA error ("attempt to donate the
    same buffer twice"), so it bars donation outright.  Only the
    host-side passthrough pick can introduce such leaf aliasing: a
    KERNEL-produced batch never does — XLA's copy-insertion guarantees
    entry-computation output leaves are distinct buffers even when two
    outputs compute the same value (checked empirically on this jax:
    jit(lambda x: (x*2, x*2)) returns distinct buffer pointers)."""
    if not enabled:
        return False
    while isinstance(child, TpuFusedStageExec) and child.is_passthrough:
        ords = [e.ordinal for e in child.out_exprs]
        if len(set(ords)) < len(ords):
            return False
        child = child.children[0]
    # shared-scan multicast (io/scan_share): a fused parquet scan with
    # sharing enabled may hand the SAME decoded batch to several
    # queries and retains it in the multicast window — donating such a
    # batch would invalidate every other holder's copy.  The bar used
    # to be static (any shared-capable scan barred every batch); it is
    # now per-batch: the scan stamps each yielded batch with its share
    # entry and ``dispatch`` donates only after ``ScanShare.try_steal``
    # proves this pipeline is the batch's sole holder — solo scans
    # recover donation, genuinely multicast batches stay barred.
    return type(child).__name__ in _DONATE_SAFE_PRODUCERS


def batch_donate_ok(b: DeviceBatch, reg) -> bool:
    """Per-batch half of the donation decision (see donate_ok): True
    unless ``b`` is a shared-scan batch some other query holds (or may
    yet claim from the retention window)."""
    e = getattr(b, "_scan_share_entry", None)
    if e is None:
        return True
    from spark_rapids_tpu.io import scan_share
    share = scan_share.peek_share()
    if share is not None and share.try_steal(e):
        reg.inc("fusion.donationsRecovered")
        return True
    reg.inc("fusion.donationsBarred")
    return False


def rows_detached(b: DeviceBatch) -> DeviceBatch:
    """Shallow copy whose ``num_rows`` leaf is a dummy zero — the
    donated argument to a kernel.  The real count rides as a separate
    NON-donated argument: producers lazily buffer their output's
    ``num_rows`` device scalar in ``Metrics._rows_pending`` (exec/base
    ``add_rows``), and XLA invalidates every leaf of a donated pytree,
    so donating the count would leave the metric pointing at a deleted
    array (resolution then raises, or silently loses the per-node row
    counts in the query profile)."""
    d = DeviceBatch(b.names, b.columns, 0)
    d._capacity = b._capacity  # zero-column batches can't derive it
    return d


def rows_arg(nr):
    """The real row count as the kernel's non-donated argument,
    coerced to the dtype ``DeviceBatch.tree_flatten`` uses for host
    ints so traces are shape-stable."""
    return jnp.int32(nr) if isinstance(nr, int) else nr


def canonical_names(n: int) -> List[str]:
    """Positional output names baked into cached kernels; the exec
    restamps its real schema names host-side after each dispatch, so
    aliasing cannot fragment the compile cache (satellite: kernel-cache
    key hygiene)."""
    return [f"_c{i}" for i in range(n)]


def build_kernel(exec_obj, key, impl_factory, donate: bool):
    """Kernel memoized on ``exec_obj._kernel`` with the donate flag
    folded into both the cache key and the rebuild guard — shared by
    TpuProjectExec / TpuFilterExec / TpuFusedStageExec so donation
    semantics live in ONE place.  The handle is memoized, so rebuild
    when the flag flipped between two executions of the same
    instance: a stale donating kernel fed an
    un-detached batch would invalidate buffers the caller still treats
    as live.  Donating kernels skip the HBM-OOM retry wrapper (the
    retry would replay already-consumed buffers)."""
    if exec_obj._kernel is None or \
            getattr(exec_obj, "_kernel_donate", None) is not donate:
        from spark_rapids_tpu.exec import kernel_cache as kc
        if donate:
            _install_donation_warn_filter()
        exec_obj._kernel = kc.get_kernel(
            key + (donate,), impl_factory, oom_retry=not donate,
            **({"donate_argnums": (0,)} if donate else {}))
        exec_obj._kernel_donate = donate
    return exec_obj._kernel


def dispatch(exec_obj, label: str, donate: bool, reg,
             b: DeviceBatch, pid: int, offset: int,
             key=None, impl_factory=None):
    """One per-batch kernel launch with the donation calling convention
    (detached row count as a separate non-donated arg), the
    shape-erased ABI (kernel_abi.erase: canonical positional names,
    bucketed hints, capacity/width padded to tier — the caller restamps
    its real schema names after), and donation bookkeeping.  The erased
    view shares the input's buffers unless padding engaged, so donation
    still releases the producer's HBM.

    When ``key``/``impl_factory`` are passed and the static decision
    allowed donation, the refcount-aware shared-scan gate runs per
    batch: a batch another query holds dispatches through the
    non-donating twin kernel (one cache lookup), everything else keeps
    its donation."""
    from spark_rapids_tpu.exec import kernel_abi
    if donate and key is not None:
        donate = batch_donate_ok(b, reg)
        build_kernel(exec_obj, key, impl_factory, donate)
    eb = kernel_abi.erase(b)
    nr = b.num_rows
    with timed(exec_obj.metrics, label):
        out = exec_obj._kernel(
            rows_detached(eb) if donate else eb,
            rows_arg(nr), jnp.int32(pid), jnp.int64(offset))
    if donate:
        exec_obj.metrics.add_extra("fusion.donatedBatches", 1)
        reg.inc("fusion.donatedDispatches")
    return out


class TpuFusedStageExec(TpuExec):
    """One collapsed Project/Filter chain (see module docstring)."""

    def __init__(self, child: PhysicalPlan,
                 out_exprs: Sequence[ir.Expression], schema: Schema,
                 condition: Optional[ir.Expression] = None,
                 fused: Sequence[str] = ()):
        super().__init__()
        self.children = (child,)
        self.out_exprs = list(out_exprs)
        self._schema = schema
        self.condition = condition
        # display names of the execs this stage replaced (top-down)
        self.fused = tuple(fused)
        self._kernel = None

    @property
    def schema(self) -> Schema:
        return self._schema

    @property
    def is_passthrough(self) -> bool:
        """Pure column selection: no condition and every output a plain
        BoundReference — runs with ZERO kernel dispatches."""
        return self.condition is None and all(
            isinstance(e, ir.BoundReference) for e in self.out_exprs)

    def n_fused(self) -> int:
        return len(self.fused)

    def simple_string(self) -> str:
        mode = "passthrough" if self.is_passthrough else (
            "filter+project" if self.condition is not None else "project")
        return (f"TpuFusedStageExec({mode}, fused={len(self.fused)}: "
                f"{'+'.join(self.fused)})")

    # ------------------------------------------------------------------
    def _impl(self, batch: DeviceBatch, nr, pid, offset) -> DeviceBatch:
        from spark_rapids_tpu.exec import context
        from spark_rapids_tpu.exec.tpu_basic import compact
        # nr is the real row count, passed OUTSIDE the (possibly
        # donated) batch pytree — see rows_detached
        batch.num_rows = nr
        with context.task_context(pid, offset):
            keep = None
            if self.condition is not None:
                v = eval_tpu.evaluate(self.condition, batch)
                keep = v.data.astype(jnp.bool_) & v.validity
                if len(self.out_exprs) >= len(batch.columns):
                    batch = compact(batch, keep)
                    keep = None
            cols = [eval_tpu.evaluate(e, batch).to_column()
                    for e in self.out_exprs]
        out = DeviceBatch(canonical_names(len(cols)), cols,
                          batch.num_rows)
        return compact(out, keep) if keep is not None else out

    def _execute_passthrough(self):
        from spark_rapids_tpu.obs import registry as obsreg
        names = self._schema.names
        ords = [e.ordinal for e in self.out_exprs]
        saved = len(self.fused)

        def run(it):
            reg = obsreg.get_registry()
            for b in it:
                with timed(self.metrics, "fused.passthrough"):
                    out = DeviceBatch(names, [b.columns[i] for i in ords],
                                      b.num_rows)
                e = getattr(b, "_scan_share_entry", None)
                if e is not None:
                    # column buffers are forwarded by reference: the
                    # share stamp must survive for the downstream
                    # donation gate
                    out._scan_share_entry = e
                reg.inc("fusion.dispatchesSaved", saved)
                self.metrics.add_batches()
                self.metrics.add_rows(out.num_rows)
                yield out
        return [run(it) for it in self.children[0].execute()]

    def execute(self):
        if self.is_passthrough:
            return self._execute_passthrough()
        import functools
        import types
        from spark_rapids_tpu.exec import kernel_cache as kc
        from spark_rapids_tpu.obs import registry as obsreg
        donate = donate_ok(self.children[0],
                           getattr(self, "_donate_enabled", False))
        # detach from self: the cached closure must not pin the exec
        # instance (and through it the whole child plan subtree)
        shim = types.SimpleNamespace(out_exprs=self.out_exprs,
                                     condition=self.condition)
        key = ("fused_stage", kc.exprs_sig(self.out_exprs),
               kc.expr_sig(self.condition))
        factory = lambda: functools.partial(type(self)._impl, shim)  # noqa: E731
        build_kernel(self, key, factory, donate)

        names = self._schema.names
        # dispatches saved per batch: the chain would have cost one
        # dispatch per fused exec, the stage costs one
        saved = max(0, len(self.fused) - 1)

        def run(pid, it):
            reg = obsreg.get_registry()
            for b in it:
                out = dispatch(self, "fused.eval", donate, reg,
                               b, pid, 0, key=key,
                               impl_factory=factory)
                out = DeviceBatch(names, out.columns, out.num_rows)
                if saved:
                    reg.inc("fusion.dispatchesSaved", saved)
                self.metrics.add_batches()
                self.metrics.add_rows(out.num_rows)
                yield out
        return [run(pid, it) for pid, it in
                enumerate(self.children[0].execute())]

"""Shape-erased kernel ABI: the dispatch-boundary contract that bounds
the compile bill.

The TPC-DS-99 compile bill — 2,639 distinct (kernel, shape) programs
(PERF.md) — is breadth, not any single runaway kernel: jax.jit compiles
one program per (pytree structure, argument shapes/dtypes), and the
engine's DeviceBatch pytree leaks THREE kinds of query-specific detail
into that identity that never change a kernel's semantics:

  1. **Column names.**  ``DeviceBatch.tree_flatten`` carries the name
     tuple as treedef aux data, so two batches with identical layouts
     but different schemas trace two programs — even though every
     expression reads columns by ordinal (``BoundReference.ordinal``)
     and PR 4 already made kernel OUTPUT names positional.  The erased
     ABI extends that to inputs: batches are renamed to canonical
     positional ``_c0.._cn`` before dispatch and the exec restamps its
     real schema host-side after (the "positional dtype-class
     arguments" of the ABI).

  2. **Value-range hints.**  ``DeviceColumn.vbits`` rides the treedef
     in 7 buckets (8..56); the narrow fast paths it unlocks only branch
     on coarse thresholds (<=16 single-digit sorts, <=32 i32 gathers/
     segment sums, <64 packed radix fields), so the precise buckets buy
     nothing but program churn.  The ABI re-buckets hints to
     {16, 32, 56} at the dispatch boundary (a WEAKER bound is always
     sound — vbits is an upper bound on value magnitude).

  3. **Shape spread.**  Row capacities and string/list widths bucket to
     every power of two; the ABI quantizes both ladders to every
     ``2**stride``-th rung (default stride 2: capacities 16, 64, 256,
     1024, ... and widths 1, 4, 16, 64, ...).  The capacity ladder is
     coarse only where padding is cheap: from ``_DENSE_TIER_START``
     (1,048,576 rows) up every power of two is a rung (1,048,576,
     2,097,152, 4,194,304, 8,388,608, ...), because every sort pass,
     gather and scan of a program runs over capacity, not live rows —
     a reader batch of 1.8M rows born at 4,194,304 slots cost each
     program above it tenths of a second a call, where one more
     executable costs its build once.  Batches are BORN at tier
     capacities (``columnar.batch.bucket_rows`` delegates here), and
     ``pad_to_tier`` pads stragglers (hand-built batches, batches born
     under a different conf) host-side at dispatch — padding rows keep
     the batch contract (validity False, data zeroed) and ``num_rows``
     is untouched, so slicing back is the existing logical-length read
     every kernel already performs via ``row_mask()``.

Every tier value is a SUBSET of the legacy power-of-two ladder and
every bucketed hint is a weakening of a legacy bucket, so the erased
ABI introduces no shape class the kernels have not always handled —
it only collapses many classes into fewer.

Batched multi-column signatures: kernels that treat a batch purely as
a column container (pack/download-compact/concat in columnar/batch.py)
key on :func:`layout_key` — the positional (dtype, width, validity
layout) sequence — instead of the schema, so any two batches with the
same physical layout share one program regardless of column names.

Decimal note: the engine's dtype set has no decimal (GpuOverrides
parity — decimals fall back to CPU at planning); when decimal columns
land they are specified to ride the same integer-backed vbits buckets
(scale static in the expression signature, precision bucketed like
vbits), so the tier tables here are already their contract.

Configuration is process-wide, last session wins (the obs configure
idiom): ``kernel.abi.enabled`` master switch, ``kernel.abi.tierStride``
/ ``kernel.abi.widthStride`` for the two shape ladders,
``kernel.abi.bucketHints`` for hint re-bucketing.  This module is an
import leaf below columnar/batch (which imports it for the tier
ladders); it imports the batch types lazily inside functions.
"""

from __future__ import annotations

from typing import Any, List, Optional, Tuple

_enabled = True          # kernel.abi.enabled default
_tier_stride = 2         # capacity ladder: every 2**stride-th pow2 rung
# ... below this capacity; from it up every power of two is a rung.  The
# last stride-2 rung at or under the reader's batch limit (1 << 21 rows,
# sql.reader.batchSizeRows), and the scale from which a half-empty tier
# costs tenths of a second a program
_DENSE_TIER_START = 1 << 20
_width_stride = 2        # string/list max_len ladder
_bucket_hints = True     # re-bucket vbits at the ABI boundary

# the ABI hint buckets: chosen so every narrow fast path keeps its
# branch — <=16 single-digit sort / direct-bin groups, <=32 i32
# gather + segment sums, <=56 packed radix fields under 64 bits
ABI_VBIT_BUCKETS = (16, 32, 56)

# canonical positional input names (PR 4 introduced the same scheme for
# kernel OUTPUT names; the erased ABI applies it to inputs too).  The
# prefix matches fused_stage.canonical_names so an erased batch fed
# through a chain of erased kernels is a fixed point.
_CANON = [f"_c{i}" for i in range(64)]


def configure(conf) -> None:
    """Session-init hook (api/session.py).  Last session wins."""
    global _enabled, _tier_stride, _width_stride, _bucket_hints
    from spark_rapids_tpu import config as cfg
    _enabled = bool(conf.get(cfg.KERNEL_ABI_ENABLED))
    _tier_stride = max(1, int(conf.get(cfg.KERNEL_ABI_TIER_STRIDE)))
    _width_stride = max(1, int(conf.get(cfg.KERNEL_ABI_WIDTH_STRIDE)))
    _bucket_hints = bool(conf.get(cfg.KERNEL_ABI_BUCKET_HINTS))


def is_enabled() -> bool:
    return _enabled


# ---------------------------------------------------------------------------
# tier ladders (pure int math; see docs/kernels.md for the tier table)
# ---------------------------------------------------------------------------

def tier_rows(n: int, min_bucket: int = 16) -> int:
    """Smallest capacity tier >= max(n, min_bucket): power-of-two
    rungs restricted to every ``tierStride``-th step of ONE canonical
    ladder anchored at 1 (stride 2: 1, 4, 16, 64, 256, ...) below
    ``_DENSE_TIER_START``, every power of two from there up.  All
    tiers are powers of two, so the result is always a legacy-valid
    capacity, and never above what the stride alone would give.

    ``min_bucket`` is a FLOOR, not a ladder anchor: a caller-specific
    anchor (bucket_rows(n, 32)) would mint an offset ladder (32, 128,
    512, ...) that ``erase``'s canonical quantization never matches —
    every dispatch of every batch born there would pay a full-batch
    host pad.  Rounding the floor up to the canonical rung instead
    (32 -> 64) keeps all capacities on one ladder; returning a larger
    floor is always valid."""
    if not _enabled:
        cap = max(int(min_bucket), 1)
        n = max(int(n), 1)
        while cap < n:
            cap <<= 1
        return cap
    cap = 1
    lo = max(int(n), int(min_bucket), 1)
    step = 1 << _tier_stride
    while cap < lo:
        nxt = cap * step
        cap = nxt if nxt <= _DENSE_TIER_START \
            else max(cap * 2, _DENSE_TIER_START)
    return cap


def tier_strlen(n: int) -> int:
    """String/list width tier >= n (ladder 1, 4, 16, 64, ... under the
    default widthStride=2; legacy pow2 when the ABI is disabled)."""
    if n <= 0:
        return 1
    cap = 1
    step = 1 << (_width_stride if _enabled else 1)
    while cap < n:
        cap *= step
    return cap


def is_tier(cap: int, min_bucket: int = 16) -> bool:
    return cap == tier_rows(cap, min_bucket=min(min_bucket, cap))


def bucket_vbits(vb: Optional[int]) -> Optional[int]:
    """ABI hint bucket for a precise vbits value (weaker bound, always
    sound); identity when the ABI or hint bucketing is off."""
    if vb is None or not (_enabled and _bucket_hints):
        return vb
    for b in ABI_VBIT_BUCKETS:
        if vb <= b:
            return b
    return None


def canonical_input_names(n: int) -> List[str]:
    if n <= len(_CANON):
        return _CANON[:n]
    return _CANON + [f"_c{i}" for i in range(len(_CANON), n)]


# ---------------------------------------------------------------------------
# batch erasure at the dispatch boundary
# ---------------------------------------------------------------------------

def _erase_column(c, strip_hints: bool = False):
    """Hint-bucketed (or, for kernels that never read hints,
    hint-stripped) view of one column — shares every buffer."""
    from dataclasses import replace
    if strip_hints:
        if c.vbits is None and not c.nonnull:
            return c
        return replace(c, vbits=None, nonnull=False)
    vb = bucket_vbits(c.vbits)
    if vb == c.vbits:
        return c
    return replace(c, vbits=vb)


def _pad_column(c, cap: int, width: Optional[int]):
    """Pad one column's buffers to ``cap`` rows (and 2-D payloads to
    ``width``) with the batch contract's zeros/False — host-side eager
    ops, dispatched outside any jit trace."""
    import jax.numpy as jnp

    def pad(a, w=None):
        if a is None:
            return None
        grow_rows = cap - a.shape[0]
        grow_w = 0 if (w is None or a.ndim < 2) else w - a.shape[1]
        if grow_rows <= 0 and grow_w <= 0:
            return a
        spec = [(0, max(grow_rows, 0))] + \
            [(0, max(grow_w, 0))] * (a.ndim - 1)
        return jnp.pad(a, spec)

    from dataclasses import replace
    return replace(c, data=pad(c.data, width), validity=pad(c.validity),
                   lengths=pad(c.lengths),
                   elem_validity=pad(c.elem_validity, width))


def erase(batch, pad: bool = True, strip_hints: bool = False):
    """The shape-erased view of a batch for kernel dispatch: canonical
    positional names, ABI-bucketed hints, and (``pad=True``) capacity /
    var-len widths padded up to their tiers.  Shares the input's
    buffers whenever no padding is needed (the overwhelmingly common
    case — batches are born at tier shapes); ``num_rows`` (host int or
    traced scalar) passes through untouched, so the logical row count
    — the slice-back half of pad/slice — is exactly the ``row_mask()``
    contract every kernel already honors.

    Callers that rely on input names surviving the kernel (filter's
    compact keeps batch names) must restamp their real schema after
    dispatch; project/fused-stage already do.

    ``pad=False`` is for kernels whose HOST-side epilogue reads the
    original buffer shapes back (the pack/download path): names and
    hints erase, shapes stay.  ``strip_hints=True`` removes hints
    outright instead of bucketing them — only for kernels that never
    read vbits/nonnull (pack: pure buffer concatenation), where even a
    bucketed hint on the treedef would re-trace an identical
    program."""
    if not _enabled:
        return batch
    from spark_rapids_tpu.columnar.batch import DeviceBatch
    cols = [_erase_column(c, strip_hints) for c in batch.columns]
    if pad and cols:
        cap = tier_rows(batch.capacity, min_bucket=min(16, batch.capacity))
        widths = [tier_strlen(c.max_len) if c.dtype.has_lengths else None
                  for c in cols]
        if cap != batch.capacity or any(
                w is not None and w != c.max_len
                for w, c in zip(widths, cols)):
            cols = [_pad_column(c, cap, w)
                    for c, w in zip(cols, widths)]
    out = DeviceBatch.__new__(DeviceBatch)
    out.names = canonical_input_names(len(cols))
    out.columns = cols
    out.num_rows = batch.num_rows
    out._capacity = cols[0].capacity if cols else batch._capacity
    return out


def layout_key(batch) -> Tuple:
    """Positional physical-layout signature of a batch — the
    schema-erased replacement for ``DeviceBatch.schema_key()`` in
    kernel-cache keys of column-container kernels (pack, download
    compact, no-sync concat): per column (dtype, var-len width,
    has-elem-validity) plus the capacity.  No names — any two batches
    with this layout share one program."""
    return (batch._capacity,
            tuple((c.dtype.name,
                   c.max_len if c.dtype.has_lengths else 0,
                   c.elem_validity is not None)
                  for c in batch.columns))


def erased_key(batch) -> Any:
    """``layout_key`` under the ABI, the legacy named ``schema_key``
    otherwise (so flipping ``kernel.abi.enabled`` between sessions of
    one process cannot serve a kernel traced under the other ABI)."""
    if _enabled:
        return ("abi", layout_key(batch))
    return (batch.schema_key(),
            tuple(c.elem_validity is not None for c in batch.columns))

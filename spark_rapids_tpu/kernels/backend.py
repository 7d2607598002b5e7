"""Kernel-backend selection: hand-written Pallas kernels vs composed XLA.

The engine's hot decode/aggregate paths are gather-bandwidth-bound at
the XLA level (PERF.md round-4b cost model: i64 gathers 22 ms/M values,
64-bit scatters ~14x i32) and XLA-level reformulations are exhausted
(ROADMAP open item 2).  This package holds purpose-built Pallas kernels
for exactly those shapes — Eiger's purpose-built-analytics-primitives
argument (arXiv:2607.04489) applied to the three measured walls:

  * ``decode.unpack`` / ``decode.expand`` — dense phase-decomposed
    RLE/bit-unpack for Parquet streams (kernels/decode.py)
  * ``scan.filterDecode`` — fused dictionary-decode + filter that never
    materializes decoded values for filtered-out rows
    (kernels/filter_decode.py)
  * ``agg.segreduce`` — single-pass segmented reduction for the
    sorted-key grouped aggregate (kernels/segreduce.py)

Selection contract (the ``sql.fusion.enabled`` pattern end to end):

  * ``spark.rapids.tpu.kernel.backend`` picks ``xla`` (default, the
    composed-array-op paths — every program of which the TPU compiler
    accepts) or ``pallas``.
  * The choice is PER CALL SITE and STATIC: a shape or dtype a Pallas
    kernel doesn't cover is deselected before dispatch and takes the
    XLA path for THAT kernel only — never the whole query
    (GPU-join-on-Hadoop, arXiv:1904.11201: fallback cliffs dominate
    when the fast path isn't universally applicable and degradation is
    coarse-grained).  A kernel that WAS selected and that the compiler
    then refuses raises: nothing reroutes at run time, and a missing
    Pallas extension is an error when ``pallas`` was asked for.
  * Every selection is observable: ``kernel.backend.pallas.hits`` and
    ``kernel.backend.pallas.fallbacks`` (plus reason- and family-tagged
    variants ``...fallbacks.<family>.<reason>``) in the metrics
    registry, and per-dispatch attribution via the
    ``kernel.dispatches.<family>.<backend>`` counters
    (exec/kernel_cache.py).

Counting semantics: hits/fallbacks are SELECTION events.  Host-side
call sites (per-column stream expansion, scan prepare) select once per
call, so those counters track per-batch work; selections made while
TRACING a cached kernel (the aggregate's segmented reductions) count
once per compile — the per-dispatch ground truth is always
``kernel.dispatches.<family>.<backend>``.

Interpret mode: Pallas kernels run under ``interpret=True`` when the
active jax backend is not a TPU (``kernel.pallas.interpret`` = auto),
so CPU CI (`JAX_PLATFORMS=cpu`) executes the REAL kernel bodies and
the parity gates exercise actual kernel semantics, not a skip.  On a
TPU ``auto`` always compiles; a backend probe that raises propagates.
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Optional

XLA = "xla"
PALLAS = "pallas"

_lock = threading.Lock()
_default_backend = XLA
_interpret_mode = "auto"        # auto | true | false
_tile_bytes = 4 << 20           # kernel.pallas.tileBytes default
# memoized resolution of interpret='auto' (the active-jax-backend
# probe): jax.default_backend() is a per-dispatch cost the tile-plan /
# kernel-selection hot path must not pay, and the platform cannot
# change mid-process.  Pinned modes ('true'/'false') bypass the memo.
_auto_interpret: Optional[bool] = None


def configure(conf) -> None:
    """Session-init hook: install the process default backend from
    ``spark.rapids.tpu.kernel.backend`` (the scan-cache ``configure``
    idiom — every new session re-asserts its own conf, so a prior
    session's setting never leaks into an unconfigured one).  Plans
    additionally carry a per-plan ``_kernel_backend`` stamp
    (plan/overrides.py), which wins over this default wherever a plan
    node is in scope."""
    from spark_rapids_tpu import config as cfg
    global _default_backend, _interpret_mode, _tile_bytes
    backend = str(conf.get(cfg.KERNEL_BACKEND) or XLA).strip().lower()
    if backend not in (XLA, PALLAS):
        raise ValueError(
            f"spark.rapids.tpu.kernel.backend must be 'xla' or "
            f"'pallas', got {backend!r}")
    mode = str(conf.get(cfg.KERNEL_PALLAS_INTERPRET)
               or "auto").strip().lower()
    tb_raw = conf.get(cfg.KERNEL_PALLAS_TILE_BYTES)
    tb = int(tb_raw) if tb_raw is not None else (4 << 20)
    if tb < (64 << 10):
        raise ValueError(
            f"spark.rapids.tpu.kernel.pallas.tileBytes must be at "
            f"least 64 KiB, got {tb}")
    with _lock:
        _default_backend = backend
        _interpret_mode = mode
        _tile_bytes = tb


def default_backend() -> str:
    with _lock:
        return _default_backend


def set_default_backend(backend: str) -> None:
    """Test/bench hook (sessions should go through :func:`configure`)."""
    global _default_backend
    with _lock:
        _default_backend = backend


@contextmanager
def backend_override(backend: str):
    """Scoped default-backend override for benches and tests."""
    prev = default_backend()
    set_default_backend(backend)
    try:
        yield
    finally:
        set_default_backend(prev)


def resolve(stamped: Optional[str] = None) -> str:
    """The backend in effect at a call site: the plan-stamped value
    when the caller has one (``_kernel_backend``), else the process
    default."""
    if stamped in (XLA, PALLAS):
        return stamped
    return default_backend()


def interpret() -> bool:
    """Run Pallas kernels in interpreter mode?  ``auto`` (default):
    interpret when the active jax backend is not a TPU — so tier-1
    CPU runs execute the genuine kernel bodies — and compile on a TPU.
    The knob pins it for debugging (``true``) or to force Mosaic
    compilation (``false``).

    The ``auto`` probe (``jax.default_backend()``) is memoized: the
    active platform cannot change mid-process — only the pinned modes
    bypass the memo (they are a plain mode-string compare anyway).  A
    probe that raises propagates: answering "interpret" there would
    run a TPU's kernels interpreted without anyone asking for it."""
    global _auto_interpret
    with _lock:
        mode = _interpret_mode
    if mode in ("true", "1", "yes", "on"):
        return True
    if mode in ("false", "0", "no", "off"):
        return False
    if _auto_interpret is None:
        import jax
        _auto_interpret = jax.default_backend() != "tpu"
    return _auto_interpret


def tile_bytes() -> int:
    """Per-tile byte budget of the HBM->VMEM streaming tiler
    (``kernel.pallas.tileBytes``) — the knob kernels/tiling.py plans
    grids against.  Part of every tiled kernel's cache key (via the
    tile plan's block/tile shapes), so flipping it mid-process can
    never serve a stale grid."""
    with _lock:
        return _tile_bytes


@contextmanager
def tile_bytes_override(n: int):
    """Scoped tileBytes override for benches and tile-boundary tests
    (forcing multi-tile grids on small buffers)."""
    global _tile_bytes
    with _lock:
        prev = _tile_bytes
        _tile_bytes = int(n)
    try:
        yield
    finally:
        with _lock:
            _tile_bytes = prev


def hit(family: str, n: int = 1) -> None:
    """Record a Pallas selection (see the counting-semantics note in
    the module docstring)."""
    from spark_rapids_tpu.obs import registry as obsreg
    obsreg.get_registry().inc_many(
        ("kernel.backend.pallas.hits", n),
        (f"kernel.backend.pallas.hits.{family}", n))


def fallback(family: str, reason: str, n: int = 1) -> None:
    """Record a pallas->xla per-kernel fallback with its reason tag."""
    from spark_rapids_tpu.obs import registry as obsreg
    obsreg.get_registry().inc_many(
        ("kernel.backend.pallas.fallbacks", n),
        (f"kernel.backend.pallas.fallbacks.{family}.{reason}", n))


def record_tiles(family: str, n_tiles: int, tile_nbytes: int) -> None:
    """Count one tiled-kernel selection's streaming volume: how many
    HBM->VMEM source tiles the grid walks and how many bytes they
    cover.  These counters replaced the retired whole-buffer residency
    fallbacks (``dense_too_large``/``dict_too_large``/``src_too_large``
    reasons): a buffer past the old gates now shows up as a large tile
    count instead of an XLA fallback.  Same counting semantics as
    :func:`hit` — host call sites count per batch, trace-time call
    sites once per compile."""
    from spark_rapids_tpu.obs import registry as obsreg
    obsreg.get_registry().inc_many(
        ("kernel.pallas.tiles", n_tiles),
        (f"kernel.pallas.tiles.{family}", n_tiles),
        ("kernel.pallas.tileBytes", n_tiles * tile_nbytes),
        (f"kernel.pallas.tileBytes.{family}", n_tiles * tile_nbytes))


def selection_snapshot() -> dict:
    """The ``kernel.backend.*`` selection counters carved from the
    registry as plain ints — the ``/compiles`` endpoint's selection
    block, so compile-bill readers see WHICH backend's programs they
    are looking at (a pallas-requested family that silently fell back
    everywhere compiles XLA programs) next to the churn report."""
    from spark_rapids_tpu.obs import registry as obsreg
    counters = obsreg.get_registry().snapshot()["counters"]
    return {k: int(v) for k, v in sorted(counters.items())
            if k.startswith("kernel.backend.")}


def choose(family: str, backend: str, supported: bool,
           reason: str = "unsupported") -> str:
    """Resolve one call site's backend: ``pallas`` only when requested
    AND the kernel covers this shape/dtype — a static decision, made
    and counted before dispatch.  An uncovered shape is an observable
    per-kernel deselection to ``xla``.  Nothing here (or after it)
    catches a compile failure of a selected kernel: it raises, and so
    does the Pallas import when ``pallas`` was asked for."""
    if backend != PALLAS:
        return XLA
    if not supported:
        fallback(family, reason)
        return XLA
    from jax.experimental import pallas  # noqa: F401
    from jax.experimental.pallas import tpu  # noqa: F401
    hit(family)
    return PALLAS

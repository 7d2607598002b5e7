"""Process-wide compiled-kernel cache.

The engine plans a FRESH exec tree for every ``collect()`` (the reference
does too — Spark re-plans each action), so per-instance ``jax.jit``
handles would recompile identical kernels on every query.  This cache
keys jitted callables on a canonical (operator, expression-tree,
parameter) signature so the XLA compile cost is paid once per
(operator, schema, batch-bucket) per process — the compile-cache
contract of SURVEY.md §7 ("XLA computations compiled per (operator,
schema, batch-bucket)").

jax.jit itself re-traces per input shape bucket under one cached handle,
so batch capacity does not belong in the key.
"""

from __future__ import annotations

import functools
import re
import threading
import time as _time
from collections import OrderedDict
from typing import Any, Callable

import jax

from spark_rapids_tpu.expr import ir

def share_executables_across_chips() -> None:
    """One persistent-cache entry for a single-device program, whichever
    chip of the host runs it.

    jax keys the persistent cache on the compile options with their
    device assignment and on the devices' topology, and leaves the
    assignment out on GPU only (``jax/_src/cache_key.py``,
    ``strip_device_assignment=(backend.platform == "gpu")``).  On a mesh
    of several TPU chips every operator's program is placed on each chip
    in turn (``exec/placement``), so each would be built and stored once
    a chip: four builds and four copies of a 17-MiB aggregate update,
    past the cache's size limit.  The executable does not depend on the
    chip: it is loaded with the assignment of the call that reads it
    (``deserialize_executable`` takes the devices and the options of
    that call).  So a single-device program is keyed as if it ran on the
    host's first device; that chip's own key, and so every key of a
    one-chip host, stays what it was.  A program over several devices
    keeps jax's key.  Idempotent."""
    from jax._src import cache_key
    import numpy as np
    if getattr(cache_key, "_spark_rapids_tpu_shared", False):
        return
    options, accelerator = (cache_key._hash_serialized_compile_options,
                            cache_key._hash_accelerator_config)

    def hash_options(hash_obj, compile_options,
                     strip_device_assignment=False):
        da = compile_options.device_assignment
        single = da is not None and \
            da.replica_count() * da.computation_count() == 1
        return options(hash_obj, compile_options,
                       strip_device_assignment or single)

    def hash_accelerator(hash_obj, accelerators):
        if accelerators.size == 1:
            first = accelerators.flat[0].client.local_devices()[0]
            accelerators = np.array([first])
        return accelerator(hash_obj, accelerators)

    cache_key._hash_serialized_compile_options = hash_options
    cache_key._hash_accelerator_config = hash_accelerator
    cache_key._spark_rapids_tpu_shared = True


_MAX_ENTRIES = 1024
_CACHE: "OrderedDict[Any, Any]" = OrderedDict()
_LOCK = threading.Lock()
# objects keyed by identity in _value_sig; pinned so CPython can't hand
# their address to a different value while a cache key references it
_ID_PINNED: dict = {}


# output-name attributes cannot change a compiled program: a
# BoundReference reads by ordinal and an Alias only labels its child,
# so identical projections under different aliases must share one
# compile (kernels that DO emit names either take them from the input
# batch at runtime or carry an explicit name tuple in their cache key)
_NAME_ATTRS = ("ref_name", "alias", "attr_name")


def expr_sig(e) -> Any:
    """Canonical hashable signature of an expression tree (class, dtype,
    scalar params, children) — the kernel-cache key component for any
    closed-over expression.  Canonical: ordinals and dtypes only, never
    column/alias names."""
    if e is None:
        return None
    if isinstance(e, ir.Expression):
        parts = [type(e).__name__,
                 e.dtype.name if e.dtype is not None else "?",
                 bool(e.nullable)]
        for k in sorted(e.__dict__):
            if k in ("children", "dtype", "nullable") or k in _NAME_ATTRS:
                continue
            parts.append((k, _value_sig(e.__dict__[k])))
        parts.append(tuple(expr_sig(c) for c in e.children))
        return tuple(parts)
    return _value_sig(e)


def _value_sig(v) -> Any:
    if isinstance(v, (str, int, float, bool, bytes, type(None))):
        return v
    if isinstance(v, (list, tuple)):
        return tuple(_value_sig(x) for x in v)
    if isinstance(v, ir.Expression):
        return expr_sig(v)
    import numpy as _np
    if isinstance(v, _np.ndarray):
        # repr() truncates large arrays ('...') so two big IN-lists could
        # collide; hash the full buffer instead.
        import hashlib
        return ("ndarray", str(v.dtype), v.shape,
                hashlib.sha1(_np.ascontiguousarray(v).tobytes())
                .hexdigest())
    if hasattr(v, "name") and not callable(v):  # DType-like
        return getattr(v, "name")
    if callable(v):
        # UDF payloads etc. — unique per object, no cross-instance reuse
        return ("callable", id(v))
    d = getattr(v, "__dict__", None)
    if d is not None:  # WindowFrame / SortOrder-like value objects
        return (type(v).__name__,) + tuple(
            (k, _value_sig(x)) for k, x in sorted(d.items()))
    # unknown opaque object: content hash when picklable; identity as a
    # last resort — with the object PINNED so its address can't be
    # recycled into a different value aliasing this cache key
    try:
        import hashlib
        import pickle
        return ("pickle", type(v).__name__,
                hashlib.sha1(pickle.dumps(v)).hexdigest())
    except Exception:
        _ID_PINNED.setdefault(id(v), v)
        return ("id", type(v).__name__, id(v))


def exprs_sig(exprs) -> Any:
    return tuple(expr_sig(e) for e in exprs)


def _shape_sig(args, kwargs):
    # the treedef rides the signature as the OBJECT (hashable, eq by
    # structure) — repr'ing it per dispatch would dominate the
    # always-on compile observatory's per-call cost
    def leaf_sig(x):
        shp = getattr(x, "shape", None)
        dty = getattr(x, "dtype", None)
        return (tuple(shp), str(dty)) if shp is not None else repr(x)[:32]
    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
    return (treedef, tuple(leaf_sig(x) for x in leaves))


def _chip_of(args, kwargs):
    """The device the call's first array is committed to, where a
    session places partitions over several chips (a program is built or
    read back once a chip there); None on one chip."""
    from spark_rapids_tpu.mem import device as devmgr
    if devmgr.chips() <= 1:
        return None
    for x in jax.tree_util.tree_leaves((args, kwargs)):
        devs = getattr(x, "devices", None)
        if devs is not None:
            devs = devs()
            return next(iter(devs)).id if len(devs) == 1 else None
    return None


class _ShapeSeen:
    """Per-wrapper first-call-per-shape detector (jax.jit retraces per
    ``_shape_sig`` bucket) — the ONE implementation shared by all
    kernel-call wrappers so their notion of "first call" cannot drift.
    ``claim`` marks-and-returns-first atomically: recording wrappers
    fire at most once per shape even under races."""

    def __init__(self):
        self._seen = set()
        self._lock = threading.Lock()

    def claim(self, sig) -> bool:
        """True exactly once per sig (atomic check-and-mark)."""
        with self._lock:
            if sig in self._seen:
                return False
            self._seen.add(sig)
            return True


def _replay_payload(inner: Callable, jit_kwargs: dict,
                    args, kwargs, family: str = None) -> "str | None":
    """Pickle (traceable, jit kwargs, abstract argument shapes, kernel
    family: the program's name, see :func:`jit_named`) into a
    base64 replay payload for the precompile corpus — everything the
    AOT precompile service (sched/precompile.py) needs to re-lower and
    re-compile this exact program in a fresh process, with no data, no
    plan, no session.  Arguments map to ``jax.ShapeDtypeStruct`` leaves
    (static kwargs — ints routed through ``static_argnames`` — stay
    concrete).  Traceables are usually picklable (module functions, or
    ``functools.partial`` over a class method + an expression-holding
    shim — the same things the executor protocol already ships); ones
    that are not return None and the program is recorded without a
    payload (counted as skipped at replay time)."""
    import base64
    import pickle
    import zlib

    def to_sds(x):
        shp = getattr(x, "shape", None)
        dty = getattr(x, "dtype", None)
        if shp is None or dty is None:
            return x
        return jax.ShapeDtypeStruct(tuple(shp), dty)
    try:
        sds = jax.tree_util.tree_map(to_sds, (args, kwargs))
        raw = pickle.dumps({"fn": inner, "jit": jit_kwargs,
                            "args": sds[0], "kwargs": sds[1],
                            "family": family},
                           protocol=pickle.HIGHEST_PROTOCOL)
        if len(raw) > (2 << 20):
            return None          # pathological payload: skip, don't bloat
        return base64.b64encode(zlib.compress(raw, 6)).decode("ascii")
    except Exception:
        return None


def load_replay_payload(payload: str):
    """Inverse of :func:`_replay_payload` (the precompile service's
    decode half; lives here so the pickle format has one owner)."""
    import base64
    import pickle
    import zlib
    return pickle.loads(zlib.decompress(base64.b64decode(payload)))


def jit_replayed(spec: dict) -> Callable:
    """The jitted callable of a loaded replay payload, built as
    ``get_kernel`` built the original: the module's name is part of
    what jax's persistent cache hashes, so a program warmed under
    another name is never hit."""
    return jit_named(spec["fn"], spec.get("family") or "other",
                     **(spec.get("jit") or {}))


def _observe_compiles(key: Any, fn: Callable,
                      replay_src=None) -> Callable:
    """Compile-observatory wrapper (obs/compile.py): the first call of
    each (key, arg-shape) program is where jax.jit traces + compiles
    (or reloads from the persistent XLA cache), so that call is timed
    and recorded as a CompileEvent with its cache tier and the
    triggering query's id + plan digest.  Wraps the jitted callable
    DIRECTLY (inside the OOM/dispatch-counter wrappers) so the measured
    wall is the compile, not the counters; an OOM-retry replay of the
    same shape is by definition not a first call and never re-records.

    Installed only when the observatory is enabled at BUILD time
    (get_kernel): a disabled process pays nothing at all.  Once
    installed, the wrapper tracks first calls even through a
    mid-process disable (``record_compile`` itself no-ops then) — so a
    later re-enable cannot misreport an already-compiled shape's next
    dispatch as a microsecond 'fresh compile'.  Kernels BUILT while
    disabled stay unobserved for their lifetime."""
    from spark_rapids_tpu.obs import compile as obscompile
    fam = _family(key)
    seen = _ShapeSeen()

    def wrapped(*args, **kwargs):
        sig = _shape_sig(args, kwargs)
        if not seen.claim((sig, _chip_of(args, kwargs))):
            return fn(*args, **kwargs)
        probe = obscompile.probe_begin()
        t0 = _time.perf_counter_ns()
        try:
            return fn(*args, **kwargs)
        finally:
            # record in finally: a first call that compiles and THEN
            # raises (HBM OOM mid-execution) still paid the compile —
            # the OOM-retry replay is warm and would never re-record,
            # so skipping here would lose the event entirely
            dur = _time.perf_counter_ns() - t0
            replay = None
            if replay_src is not None and obscompile.corpus_path() \
                    and obscompile.corpus_replay_enabled():
                replay = _replay_payload(replay_src[0], replay_src[1],
                                         args, kwargs, family=fam)
            obscompile.record_compile(
                key=key, family=fam, leaves=sig[1],
                t0_ns=t0, dur_ns=dur,
                tier=obscompile.classify_tier(probe),
                replay=replay, build=obscompile.build_split(probe))
    return wrapped


def _with_oom_recovery(fn):
    """Retry a kernel dispatch once after an HBM-exhaustion error, with
    the spill catalog's synchronous device-tier eviction in between (the
    RMM onAllocFailure retry loop, DeviceMemoryEventHandler.scala:42-70,
    restructured for an allocator the engine doesn't own)."""
    def run(*args, **kwargs):
        try:
            return fn(*args, **kwargs)
        except Exception as e:
            from spark_rapids_tpu.mem import spill as _spill
            if not _spill.hbm_oom_recover(e):
                raise
            return fn(*args, **kwargs)
    return run


def _family(key: Any) -> str:
    if isinstance(key, tuple) and key and isinstance(key[0], str):
        return key[0]
    return "other"


def program_name(family: str) -> str:
    """``jit_<family>``: what a device trace, an HLO dump and the
    persistent cache call a kernel family's programs.  The family cut
    to ``[a-z0-9_]``, nothing else: no key hash, no operator id, no
    shape, so one family at one set of shapes stays one executable.
    jax puts the ``jit_`` before the traced function's name."""
    return "jit_" + _function_name(family)


def _function_name(family: str) -> str:
    return re.sub(r"[^a-z0-9_]", "_", str(family).lower()) or "other"


def jit_named(inner: Callable, family: str, **jit_kwargs) -> Callable:
    """``jax.jit(inner, **jit_kwargs)`` whose program is named after
    its kernel family (:func:`program_name`).  jax names a program
    after the traced function's ``__name__``; a ``functools.partial``
    or a closure has none worth reading (``jit__unknown``,
    ``jit__lambda``, ``jit_kernel``), so the traceable is wrapped in a
    function that carries the family's.  ``functools.wraps`` keeps
    ``inner``'s signature visible, which is what ``static_argnames``
    and ``donate_argnums`` are resolved against."""
    @functools.wraps(inner)
    def named(*args, **kwargs):
        return inner(*args, **kwargs)
    named.__name__ = named.__qualname__ = _function_name(family)
    return jax.jit(named, **jit_kwargs)


def _count_dispatches(key: Any, fn: Callable) -> Callable:
    """Per-call registry counters: ``kernel.dispatches`` is the ground
    truth the fusion layer's dispatch-reduction claims are measured
    against (the benchmark's ``dispatches_per_query`` reads it and
    tests assert the fused-vs-unfused delta on it; one lock bump per
    ~72 ms dispatch is noise)."""
    from spark_rapids_tpu.obs import accounting as _acct
    from spark_rapids_tpu.obs import registry as _obsreg
    fam = _family(key)
    pairs = (("kernel.dispatches", 1), (f"kernel.dispatches.{fam}", 1))

    def wrapped(*args, **kwargs):
        _obsreg.get_registry().inc_many(*pairs)
        # ledger: every dispatch bills the owning tenant with the SAME
        # n as the global counter — the CI exactness gate's invariant
        _acct.charge("kernel.dispatches", 1)
        return fn(*args, **kwargs)
    return wrapped


def get_kernel(key: Any, builder: Callable[[], Callable],
               oom_retry: bool = True, **jit_kwargs) -> Callable:
    """Return the cached jitted kernel for ``key``, building+jitting via
    ``builder`` on first use (LRU-bounded).

    ``oom_retry=False`` skips the HBM-OOM retry wrapper — required when
    the kernel donates input buffers (a retry would replay arguments
    the failed dispatch may already have consumed).  Call sites that
    donate must fold the donation into ``key``: the same signature
    jitted with and without ``donate_argnums`` is two executables.

    Cache-tier counters (the compile-observatory split): an in-memory
    hit here bumps ``kernel.cache.memHits`` (``kernel.cache.hits`` is
    its documented legacy alias, key granularity); a miss invokes the
    builder (``kernel.cache.misses``, distinct KEYS built), after which
    each first (key, shape) call classifies as ``kernel.cache.compiles``
    (fresh XLA compile) or ``kernel.cache.persistentHits`` (persistent-
    cache reload) via obs/compile.py — note the granularity: one key
    can lazily compile several shape-bucket programs, so misses is not
    the sum of the two program-tier counters."""
    from spark_rapids_tpu.obs import registry as _obsreg
    fam = _family(key)
    with _LOCK:
        fn = _CACHE.get(key)
        if fn is not None:
            _CACHE.move_to_end(key)
            _obsreg.get_registry().inc_many(
                ("kernel.cache.hits", 1),
                (f"kernel.cache.hits.{fam}", 1),
                ("kernel.cache.memHits", 1))
            return fn
    _obsreg.get_registry().inc_many(
        ("kernel.cache.misses", 1), (f"kernel.cache.misses.{fam}", 1))
    inner = builder()
    fn = jit_named(inner, fam, **jit_kwargs)
    from spark_rapids_tpu.obs import compile as _obscompile
    observed = _obscompile.is_enabled()
    if observed:
        fn = _observe_compiles(
            key, fn, replay_src=(inner, jit_kwargs))
    if oom_retry:
        fn = _with_oom_recovery(fn)
    fn = _count_dispatches(key, fn)
    with _LOCK:
        cur = _CACHE.setdefault(key, fn)
        if len(_CACHE) > _MAX_ENTRIES:
            _CACHE.popitem(last=False)
    return cur


def clear() -> None:
    _CACHE.clear()
    _ID_PINNED.clear()


def clear_compile_state() -> None:
    """Drop every cached executable (this cache + jax's internal ones)
    so their memory mappings release; the persistent compile cache
    makes re-loading cheap."""
    import gc

    import jax
    clear()
    jax.clear_caches()
    gc.collect()


_maps_calls = 0
_maps_guard_disabled = False


def _count_maps() -> int:
    with open("/proc/self/maps", "rb") as f:
        return f.read().count(b"\n")


def maybe_clear_for_map_pressure(threshold: int = 40000,
                                 every: int = 16,
                                 force_check: bool = False) -> bool:
    """Executor-longevity guard: every loaded XLA executable costs
    memory mappings, and a long-lived process compiling many queries
    would hit ``vm.max_map_count`` (65530) and SIGSEGV — round 2's
    reproducible suite-killer.  Samples /proc/self/maps every ``every``
    calls (the scan itself costs ~ms) and clears cached executables
    past ``threshold``; if clearing doesn't actually reduce the count
    (mappings owned by something else), the guard disables itself
    instead of thrashing recompiles.  (The reference gets this bound
    for free from the JVM's code-cache management.)"""
    global _maps_calls, _maps_guard_disabled
    if _maps_guard_disabled:
        return False
    _maps_calls += 1
    if not force_check and _maps_calls % every:
        return False
    try:
        n = _count_maps()
    except OSError:
        _maps_guard_disabled = True
        return False
    if n <= threshold:
        return False
    clear_compile_state()
    try:
        if _count_maps() > 0.9 * threshold:
            _maps_guard_disabled = True
    except OSError:
        _maps_guard_disabled = True
    return True

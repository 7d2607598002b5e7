"""Process-wide scan-plan cache: memoized host-prep artifacts.

The CPU half of the device parquet scan — footer parse, Thrift
page-header walks, RLE run-boundary tables (``ChunkPlan``) — is pure
O(pages+runs) host work that the engine redoes from scratch on every
``collect()`` (its share of the end-to-end wall on the attached chip
is not measured).  This cache is the host-side sibling of
``exec/kernel_cache.py`` and the analog of the reference's footer
cache (reference: GpuParquetScan caches parsed footers per file so the
multi-file reader clips row groups without re-reading the tail):

  * entries key on ``(path, mtime_ns, size)`` for files — any rewrite
    of the file changes the stamp and invalidates every cached
    artifact for it — or on a content digest for in-memory parquet
    blobs (the ``df.cache()`` decode path);
  * per file the cache holds the parsed footer (``FooterInfo``) and
    every ``ChunkPlan`` walked so far, keyed by
    ``(row_group, leaf_index, out_dtype, allow_mixed)``;
  * unsupported chunks cache NEGATIVELY (the ``UnsupportedChunk`` is
    replayed) so a warm scan doesn't re-walk pages only to fall back
    to host Arrow again;
  * eviction is LRU at file granularity under a byte budget
    (``spark.rapids.tpu.sql.scan.metadataCache.maxBytes``) — run
    tables and packed buffers are the dominant cost and are accounted
    per plan;
  * what the budget has left over holds the *assembled upload sets* of
    whole scan batches (``get_assembled`` / ``put_assembled``): a batch
    of cached plans is packed into its upload arrays and uploaded
    once, not once a query.  They are derived data resident in HBM, so
    they are the first to go, never push a plan out, and are all
    dropped under memory pressure.  A set lies in one chip's HBM, so
    the room is counted a chip: a scan that places its partitions over
    a mesh (``exec/placement``) names the device in the set's key and
    to ``put_assembled``, and each chip holds what the budget leaves.

Lookups stat the file every time (µs against ms-scale walks), so an
overwritten file is never served stale plans.  All entry points are
thread-safe: concurrent partition iterators and the host-prep thread
pool hit the cache simultaneously.  Plan computation runs OUTSIDE the
lock — two threads may race to walk the same chunk (both count as
misses; last insert wins), which is benign because plans are treated
as immutable after construction.
"""

from __future__ import annotations

import hashlib
import io as _io
import os
import sys
import threading
from collections import OrderedDict
from typing import Any, Dict, Optional, Tuple

import pyarrow.parquet as papq

from spark_rapids_tpu.obs import registry as _obsreg

_LOCK = threading.RLock()
_ENABLED = True
_MAX_BYTES = 4 << 30

# skey -> _FileEntry, LRU order (oldest first)
_FILES: "OrderedDict[Tuple, _FileEntry]" = OrderedDict()
# abspath -> last skey (so a rewritten file's stale entry purges
# immediately instead of lingering until eviction)
_PATH_KEY: Dict[str, Tuple] = {}
_TOTAL_BYTES = 0

# batch key -> (assembled upload set, bytes, device), LRU order (oldest
# first); the bytes held a device (None: the default device)
_ASSEMBLED: "OrderedDict[Tuple, Tuple[Any, int, Any]]" = OrderedDict()
_ASSEMBLED_BYTES: Dict[Any, int] = {}

_HITS = 0
_MISSES = 0
_EVICTIONS = 0
_INVALIDATIONS = 0


class FooterInfo:
    """Cached parquet footer: standalone metadata + Arrow schema.

    Duck-types the slice of ``pyarrow.parquet.ParquetFile`` the scan
    paths use (``.metadata``, ``.schema_arrow``, ``.read_row_group``,
    ``.close``) WITHOUT holding an open file descriptor — a scan over
    thousands of files must not pin thousands of fds."""

    __slots__ = ("path", "metadata", "schema_arrow", "cache_key",
                 "_leaf_of")

    def __init__(self, path: str, metadata, schema_arrow,
                 cache_key: Optional[Tuple] = None):
        self.path = path
        self.metadata = metadata
        self.schema_arrow = schema_arrow
        # the (path, mtime, size) stamp this footer was parsed under —
        # chunk plans derived THROUGH this footer must key on it (a
        # re-stat at plan time could pick up a newer stamp and cache
        # plans built from stale byte offsets under the new key)
        self.cache_key = cache_key
        self._leaf_of: Optional[dict] = None

    @property
    def num_row_groups(self) -> int:
        return self.metadata.num_row_groups

    def leaf_of(self) -> dict:
        if self._leaf_of is None:
            from spark_rapids_tpu.io.device_parquet import leaf_index_map
            self._leaf_of = leaf_index_map(self)
        return self._leaf_of

    def read_row_group(self, rg: int, columns=None):
        """Host Arrow read for fallback columns (transient open)."""
        pf = papq.ParquetFile(self.path)
        try:
            return pf.read_row_group(rg, columns=columns)
        finally:
            pf.close()

    def close(self) -> None:  # ParquetFile-compatible no-op
        pass

    def nbytes(self) -> int:
        try:
            return int(self.metadata.serialized_size) + 4096
        except Exception:
            return 1 << 16


class _FileEntry:
    __slots__ = ("footer", "plans", "nbytes")

    def __init__(self):
        self.footer: Optional[FooterInfo] = None
        # (rg, leaf_idx, dtype_name, allow_mixed) -> ChunkPlan | Exception
        self.plans: Dict[Tuple, Any] = {}
        self.nbytes = 0


# ---------------------------------------------------------------------------
# Configuration / stats
# ---------------------------------------------------------------------------

def configure(enabled: bool, max_bytes: int) -> None:
    """Session bootstrap hook (api/session.py)."""
    global _ENABLED, _MAX_BYTES
    with _LOCK:
        _ENABLED = bool(enabled)
        _MAX_BYTES = int(max_bytes)
        if not _ENABLED:
            _clear_locked()
        else:
            _evict_locked()


def enabled() -> bool:
    return _ENABLED


def stats() -> Dict[str, int]:
    with _LOCK:
        return {"hits": _HITS, "misses": _MISSES,
                "evictions": _EVICTIONS,
                "invalidations": _INVALIDATIONS,
                "entries": len(_FILES), "bytes": _TOTAL_BYTES,
                "assembled": len(_ASSEMBLED),
                "assembled_bytes": sum(_ASSEMBLED_BYTES.values()),
                "assembled_bytes_by_device": dict(_ASSEMBLED_BYTES)}


def clear() -> None:
    with _LOCK:
        _clear_locked()


def _clear_locked() -> None:
    global _TOTAL_BYTES
    _FILES.clear()
    _PATH_KEY.clear()
    _TOTAL_BYTES = 0
    _ASSEMBLED.clear()
    _ASSEMBLED_BYTES.clear()


# ---------------------------------------------------------------------------
# Keys
# ---------------------------------------------------------------------------

def file_key(path: str) -> Optional[Tuple]:
    """Cache key of an on-disk file: (abspath, mtime_ns, size) — the
    spark-rapids footer-cache invalidation contract.  None when the
    path can't be stat'ed (the caller skips caching)."""
    try:
        st = os.stat(path)
    except OSError:
        return None
    return ("file", os.path.abspath(path), st.st_mtime_ns, st.st_size)


def blob_key(blob) -> Optional[Tuple]:
    """Cache key of an in-memory parquet blob (df.cache() path):
    content digest, so a re-materialized relation with identical bytes
    still hits and freed-and-reused ids can never alias."""
    if not isinstance(blob, (bytes, bytearray, memoryview)):
        return None
    return ("blob", hashlib.sha1(blob).hexdigest(), len(blob))


def source_key(src) -> Optional[Tuple]:
    """file_key for paths, blob_key for byte blobs."""
    if isinstance(src, str):
        return file_key(src)
    return blob_key(src)


def source_stamps(paths) -> Optional[Tuple[Tuple, ...]]:
    """Current content stamps for a set of scan sources: the sorted
    tuple of ``file_key`` stamps — the same (path, mtime_ns, size)
    invalidation contract the scan-plan cache keys on, exposed so the
    serving tier's result-set cache can key whole query results on it
    (serve/result_cache.py).  None when any path can't be stat'ed: a
    result derived from an unstampable source must not be cached."""
    out = []
    for p in paths:
        k = file_key(p)
        if k is None:
            return None
        out.append(k)
    return tuple(sorted(out))


class StampDelta:
    """Classification of an (old, new) source-stamp-set pair — the
    incremental result-maintenance admissibility verdict
    (exec/incremental.py).  ``kind`` is one of:

      * ``unchanged`` — identical stamp sets;
      * ``append``    — every old file's (path, mtime_ns, size) stamp
        holds verbatim and >= 1 new path appeared: the ONLY drift shape
        whose delta can be recomputed from the new files alone;
      * ``rewrite``   — some old path's stamp moved (size grew, shrank,
        or an mtime-only touch: content equality is unknowable from the
        stamp, so a touch classifies conservatively as a rewrite);
      * ``shrink``    — some old path vanished from the new set (file
        deleted or renamed away);
      * ``mixed``     — both rewrites/shrinks AND appends at once.

    Per-file attribution rides along so fallback counters and the
    /resultcache inspection can say WHICH file broke incrementality."""

    __slots__ = ("kind", "appended", "rewritten", "deleted")

    def __init__(self, kind: str, appended, rewritten, deleted):
        self.kind = kind
        self.appended = tuple(appended)
        self.rewritten = tuple(rewritten)
        self.deleted = tuple(deleted)

    def __repr__(self) -> str:
        return (f"StampDelta({self.kind}, +{len(self.appended)} "
                f"~{len(self.rewritten)} -{len(self.deleted)})")


def classify_stamp_delta(old_stamps, new_stamps) -> StampDelta:
    """Classify drift between two ``source_stamps`` tuples (see
    :class:`StampDelta`).  Both arguments are iterables of
    ("file", abspath, mtime_ns, size) stamps; paths, not live files,
    are compared — a deleted file shows up as a missing path here, it
    never re-raises the ``os.stat`` failure (the caller obtained the
    new stamps through :func:`source_stamps`, whose contract is None on
    any unstatable path)."""
    old_by_path = {s[1]: s for s in old_stamps}
    new_by_path = {s[1]: s for s in new_stamps}
    appended = sorted(p for p in new_by_path if p not in old_by_path)
    deleted = sorted(p for p in old_by_path if p not in new_by_path)
    rewritten = sorted(p for p, s in old_by_path.items()
                       if p in new_by_path and new_by_path[p] != s)
    if not appended and not deleted and not rewritten:
        return StampDelta("unchanged", (), (), ())
    if appended and not deleted and not rewritten:
        return StampDelta("append", appended, (), ())
    if appended:
        return StampDelta("mixed", appended, rewritten, deleted)
    if deleted:
        return StampDelta("shrink", (), rewritten, deleted)
    return StampDelta("rewrite", (), rewritten, ())


def handle_key(pf, src) -> Optional[Tuple]:
    """Plan-cache key for chunks walked through the open handle ``pf``:
    the stamp captured when the footer was parsed (FooterInfo), NOT a
    fresh stat — so a file rewritten mid-scan can never get plans built
    from the stale footer's offsets cached under the new file's key.
    Handles without a pinned stamp (a plain ParquetFile, an uncached
    FooterInfo) return None: their open-time stamp is unknowable, and
    caching under a fresh stat could poison a newer stamp with plans
    derived from the handle's older footer."""
    return getattr(pf, "cache_key", None)


# ---------------------------------------------------------------------------
# Entry management
# ---------------------------------------------------------------------------

def _purge_stale_locked(skey: Tuple) -> None:
    """Drop a previous-stamp entry for the same path (file rewritten).

    Only a FRESHER stamp may purge/repoint: a scan still pinned to an
    older footer (handle_key) must not evict the rewritten file's new
    entry — old- and new-stamp entries coexist until the old one ages
    out of the LRU."""
    global _TOTAL_BYTES, _INVALIDATIONS
    if skey[0] != "file":
        return
    prev = _PATH_KEY.get(skey[1])
    if prev is None or prev == skey:
        _PATH_KEY[skey[1]] = skey
        return
    if skey[2] < prev[2]:     # incoming mtime_ns older than recorded
        return
    entry = _FILES.pop(prev, None)
    if entry is not None:
        _TOTAL_BYTES -= entry.nbytes
        _INVALIDATIONS += 1
    for akey in [k for k in _ASSEMBLED if any(s == prev for s, _ in k[0])]:
        _drop_assembled_locked(akey)
    _PATH_KEY[skey[1]] = skey


def _probe_locked(skey: Tuple) -> Optional["_FileEntry"]:
    """Lookup WITHOUT creating: a miss that then fails to parse/walk
    must leave no empty entry behind (they would accumulate for every
    corrupt/vanished file stamp)."""
    _purge_stale_locked(skey)
    entry = _FILES.get(skey)
    if entry is not None:
        _FILES.move_to_end(skey)
    return entry


def _entry_locked(skey: Tuple) -> "_FileEntry":
    entry = _probe_locked(skey)
    if entry is None:
        entry = _FileEntry()
        _FILES[skey] = entry
    return entry


def _drop_assembled_locked(akey: Tuple) -> None:
    _, nbytes, device = _ASSEMBLED.pop(akey)
    _ASSEMBLED_BYTES[device] -= nbytes
    if not _ASSEMBLED_BYTES[device]:
        del _ASSEMBLED_BYTES[device]


def _evict_locked() -> None:
    global _TOTAL_BYTES, _EVICTIONS
    for device in [d for d, held in _ASSEMBLED_BYTES.items()
                   if _TOTAL_BYTES + held > _MAX_BYTES]:
        # that chip's sets, oldest first, until it is inside the budget
        for akey in [k for k, v in _ASSEMBLED.items() if v[2] == device]:
            if _TOTAL_BYTES + _ASSEMBLED_BYTES.get(device, 0) <= _MAX_BYTES:
                break
            _drop_assembled_locked(akey)
    while _TOTAL_BYTES > _MAX_BYTES and len(_FILES) > 1:
        old_key, old = _FILES.popitem(last=False)
        _TOTAL_BYTES -= old.nbytes
        _EVICTIONS += 1
        if old_key[0] == "file" and _PATH_KEY.get(old_key[1]) == old_key:
            del _PATH_KEY[old_key[1]]


def _account_locked(entry: "_FileEntry", delta: int) -> None:
    global _TOTAL_BYTES
    entry.nbytes += delta
    _TOTAL_BYTES += delta
    _evict_locked()


def _plan_nbytes(plan) -> int:
    """Byte cost of one cached ChunkPlan (packed streams + value
    buffers dominate; run-table python lists cost ~40 B/run)."""
    if isinstance(plan, Exception):
        return 256
    n = 512
    for b in (plan.def_packed, plan.val_packed):
        n += len(b or b"")
    for a in (plan.plain_np, plan.dict_np, plan.dict_lens):
        if a is not None:
            n += int(a.nbytes)
    for rt in (plan.def_runs, plan.val_runs):
        if rt is not None:
            n += 40 * len(rt.counts)
    return n


# ---------------------------------------------------------------------------
# Public lookups
# ---------------------------------------------------------------------------

def _count(metrics, key: str) -> None:
    if metrics is not None:
        metrics.add_extra(key, 1)


def get_footer(path: str, metrics=None) -> FooterInfo:
    """Parsed footer for ``path``, cached on (path, mtime, size).

    Falls through to a direct parse (uncached) when the cache is off
    or the file can't be stat'ed."""
    skey = file_key(path) if _ENABLED else None
    if skey is not None:
        with _LOCK:
            entry = _probe_locked(skey)
            if entry is not None and entry.footer is not None:
                count_plan_hits(metrics)
                return entry.footer
    md = papq.read_metadata(path)
    footer = FooterInfo(path, md, md.schema.to_arrow_schema(),
                        cache_key=skey)
    if skey is not None:
        _bump_misses(metrics)
        with _LOCK:
            entry = _entry_locked(skey)
            if entry.footer is None:
                entry.footer = footer
                _account_locked(entry, footer.nbytes())
            else:
                footer = entry.footer
    return footer


def get_chunk_plan(skey: Optional[Tuple], src, rg: int, leaf_idx: int,
                   out_dtype, allow_mixed: bool, pf, metrics=None):
    """ChunkPlan for one (source, row_group, leaf column), cached.

    ``src`` is a path or parquet blob; ``pf`` anything exposing
    ``.metadata`` (a ParquetFile or FooterInfo).  Re-raises a cached
    ``UnsupportedChunk`` without re-walking pages.  With the cache off
    or ``skey`` None the walk runs uncached."""
    from spark_rapids_tpu.io import parquet_meta as pm
    from spark_rapids_tpu.io.device_parquet import (UnsupportedChunk,
                                                    plan_chunk)

    pkey = (rg, leaf_idx, out_dtype.name, bool(allow_mixed))
    use_cache = _ENABLED and skey is not None
    if use_cache:
        with _LOCK:
            entry = _probe_locked(skey)
            cached = entry.plans.get(pkey) if entry is not None else None
        if cached is not None:
            count_plan_hits(metrics)
            if isinstance(cached, Exception):
                # fresh instance per raise: the cached one is shared
                raise type(cached)(*cached.args)
            return cached
        _bump_misses(metrics)
    try:
        chunk = pm.read_chunk_pages(src, rg, leaf_idx, parquet_file=pf)
        plan = plan_chunk(chunk, out_dtype, allow_mixed=allow_mixed)
    except UnsupportedChunk as e:
        # negative-cache ONLY the deterministic verdict, stripped of
        # its traceback (frames pin the whole compressed chunk bytes,
        # and concurrent re-raises would race on __traceback__);
        # transient IO/parse errors must stay uncached and retryable
        if use_cache:
            neg = UnsupportedChunk(*e.args)
            with _LOCK:
                entry = _entry_locked(skey)
                if pkey not in entry.plans:
                    entry.plans[pkey] = neg
                    _account_locked(entry, _plan_nbytes(neg))
        raise
    if use_cache:
        with _LOCK:
            entry = _entry_locked(skey)
            if pkey not in entry.plans:
                entry.plans[pkey] = plan
                _account_locked(entry, _plan_nbytes(plan))
            else:
                got = entry.plans[pkey]
                if not isinstance(got, Exception):
                    plan = got
    return plan


def get_assembled(key: Optional[Tuple]):
    """The uploaded upload set of one scan batch, where an earlier scan
    left it (``put_assembled``), else None.

    ``key`` is ``(((stamp, row_group), ...), ...)``: the batch's sources
    under the stamps their footers were parsed under (``handle_key``),
    then whatever else the set depends on; None (a source with no
    stamp) is never cached."""
    if not _ENABLED or key is None:
        return None
    with _LOCK:
        hit = _ASSEMBLED.get(key)
        if hit is not None:
            _ASSEMBLED.move_to_end(key)
    _obsreg.get_registry().inc("scan.assembledCacheHits" if hit is not None
                               else "scan.assembledCacheMisses")
    return None if hit is None else hit[0]


def put_assembled(key: Optional[Tuple], made, nbytes: int,
                  device=None) -> None:
    """Keep ``made`` (resident on ``device``, ``nbytes`` of its HBM; None:
    the default device) for the next scan of the same batch.  It takes
    only the room the plans leave in that chip's budget, pushing the
    chip's older sets out; one that does not fit there is not kept.  A
    set goes with its file's stamp, and all of them under memory
    pressure (``pressure_spill``)."""
    global _SPILLER_REGISTERED
    if key is None:
        return
    with _LOCK:
        if not _SPILLER_REGISTERED:
            from spark_rapids_tpu.mem import spill
            spill.register_pressure_spiller(sys.modules[__name__])
            _SPILLER_REGISTERED = True
        # a ``configure`` that turned the cache off meanwhile stays
        # the last word
        if _ENABLED and key not in _ASSEMBLED and \
                _TOTAL_BYTES + nbytes <= _MAX_BYTES:
            _ASSEMBLED[key] = (made, int(nbytes), device)
            _ASSEMBLED_BYTES[device] = \
                _ASSEMBLED_BYTES.get(device, 0) + int(nbytes)
            _evict_locked()


def pressure_spill(bytes_needed: int = 1 << 62) -> int:
    """``mem/spill``'s pressure hook, this module being the spiller:
    let go of assembled sets, oldest first, until ``bytes_needed`` of
    HBM are free of them (the next scan makes them again; one in
    flight keeps its own reference until its decode is enqueued).
    Returns the bytes dropped."""
    freed = 0
    with _LOCK:
        while _ASSEMBLED and freed < bytes_needed:
            akey = next(iter(_ASSEMBLED))
            freed += _ASSEMBLED[akey][1]
            _drop_assembled_locked(akey)
    return freed


_SPILLER_REGISTERED = False


def count_plan_hits(metrics, n: int = 1) -> None:
    """``n`` chunk plans served from the cache: one by a lookup, or
    all the plans an assembled set (``get_assembled``) was made of."""
    global _HITS
    with _LOCK:
        _HITS += n
    if metrics is not None:
        metrics.add_extra("scan.planCacheHits", n)
    # mirrored into the unified metrics registry: the scan-cache
    # counters were one of the three disjoint stat channels the obs
    # layer folds together (obs/registry.py)
    _obsreg.get_registry().inc("scan.planCacheHits", n)


def _bump_misses(metrics) -> None:
    global _MISSES
    with _LOCK:
        _MISSES += 1
    _count(metrics, "scan.planCacheMisses")
    _obsreg.get_registry().inc("scan.planCacheMisses")


def open_source(path: str, metrics=None):
    """Footer-backed handle for a scan source: the cached FooterInfo
    when the cache is on, else a real ParquetFile (caller must close)."""
    if _ENABLED and file_key(path) is not None:
        return get_footer(path, metrics=metrics)
    return papq.ParquetFile(path)


def blob_footer(blob) -> papq.ParquetFile:
    """ParquetFile over an in-memory blob (footers for blobs are cheap
    enough to re-parse; the expensive page walks cache via blob_key)."""
    return papq.ParquetFile(_io.BytesIO(blob))

"""Compile observatory: per-compile attribution, shape-churn analytics,
and a precompile corpus.

The TPC-DS-99 compile bill — 2,639 distinct (kernel, shape) programs,
3,431 s cold / 613 s warm (PERF.md) — is the ROADMAP's named
serving-SLO blocker, yet compilation was the one hot path the obs
stack could not see: a compile surfaced only as an anomalously long
dispatch span with no family, shape signature, cache tier, or
triggering query attached.  This module is the instrument the
shape-erased-ABI refactor (ROADMAP item 2) will be driven by.

Every first call of a (kernel-cache key, arg-shape) program through
``exec/kernel_cache.get_kernel`` records one **CompileEvent**:

  * kernel family + cache-key repr + canonical shape/dtype signature
  * first-call wall, ``kernel.compile.wallNs``: everything the first
    call took, which is NOT compile time (tracing, lowering, the
    backend compile or the read-back of a cached executable, and the
    dispatch — an async one returns at once, one the device has to
    queue waits).  The parts jax itself reports are split out as
    ``build`` on the event and in the ``kernel.build.*`` counters
  * cache tier — ``fresh`` (a real XLA compile) vs ``persistent`` (the
    executable reloaded from the persistent XLA compilation cache),
    classified from jax's own ``/jax/compilation_cache/*`` monitoring
    events counted thread-locally around the call (in-memory kernel
    cache hits never reach this module at all — they are counted as
    ``kernel.cache.memHits`` by get_kernel)
  * the triggering query id (from the thread's installed CancelToken)
    and its canonical plan digest (registered by sched/service at
    submit time)

Events land in a bounded ring plus process-lifetime aggregates:
per-family program/signature-cardinality counts (with a width-bucketed
projection estimating the collapse a shape-erased ABI would buy) and a
bounded per-query attribution table.  Surfaces:

  * ``kernel.compile`` spans in the Chrome trace (compiles stop
    masquerading as slow dispatches)
  * ``kernel.compile.*`` counters + the ``kernel.compile.wallMs``
    histogram on ``/metrics``, and the cache-tier split
    ``kernel.cache.memHits`` / ``.persistentHits`` / ``.compiles``
  * the build split, four process counters fed by jax's own duration
    events for EVERY program jax builds in the process (the engine's
    kernels, and the eager ``jnp`` operations round them):
    ``kernel.build.traceNs`` (function to jaxpr), ``.lowerNs`` (jaxpr
    to MLIR module), ``.compileNs`` (backend compile of a program the
    persistent cache did not hold) and ``.loadNs`` (a program read
    back from the persistent cache)
  * the ``/compiles`` endpoint route (obs/server.py): live ledger
    table + churn report + per-query attribution
  * a "compile" QueryProfile section and ``compile_s`` in
    ``wall_breakdown`` (obs/profile.py)
  * flight-recorder ``compile.storm`` events when one query compiles
    more than ``obs.compile.stormThreshold`` programs (once per query)
  * the precompile corpus: ``obs.compile.corpusPath`` appends one
    JSONL record of (plan digest, kernel signature set) per distinct
    plan — the replay artifact for an AOT precompile service

Disabled path (``obs.compile.enabled=false``): the get_kernel wrapper
checks one module bool and dispatches straight through — no shape
signature is computed.  Configuration is process-wide, last session
wins (the trace/recorder configure idiom).

Layering: this module imports only obs siblings at load time.  Query
attribution needs the scheduler's thread-local CancelToken, which is
imported inside the lookup function only — sched imports obs at module
level, never the reverse, so the package stays an import leaf.
"""

from __future__ import annotations

import json
import threading
import time
from collections import OrderedDict, deque
from typing import Any, Dict, List, Optional, Sequence, Tuple

from spark_rapids_tpu.obs import recorder as obsrec
from spark_rapids_tpu.obs import registry as obsreg
from spark_rapids_tpu.obs import trace as obstrace

DEFAULT_RING_EVENTS = 4096
DEFAULT_STORM_THRESHOLD = 64

# bounds on the process-lifetime aggregates: the TPC-DS-99 bill is
# ~2.6k programs, so these caps are headroom, not expected operation —
# past them a family's signature sets stop growing and flag overflow
# (counts keep accumulating; only *distinctness* saturates)
_MAX_SIGS_PER_FAMILY = 8192
_MAX_QUERIES = 256
_MAX_PROGRAMS_PER_QUERY = 1024

TIER_FRESH = "fresh"
TIER_PERSISTENT = "persistent"

_enabled = True                       # obs.compile.enabled default
_storm_threshold = DEFAULT_STORM_THRESHOLD
_corpus_path = ""
_corpus_replay = True                 # obs.compile.corpusReplay default

_LOCK = threading.Lock()
_ring: deque = deque(maxlen=DEFAULT_RING_EVENTS)
_seq = 0
# family -> {programs, fresh, persistent, wall_ns, sigs, bucketed,
#            sig_overflow}
_families: Dict[str, Dict[str, Any]] = {}
# query id -> {digest, compiled, persistent, wall_ns, storm, programs}
_queries: "OrderedDict[int, Dict[str, Any]]" = OrderedDict()
# accounting closure for the per-query table: fresh compiles recorded
# with NO token on the thread (engine warm-up, direct exec paths), and
# compiled counts lost to finished-record eviction — so
# sum(per_query kernels_compiled) + unattributed + evicted always
# equals the kernel.cache.compiles counter (the bench attribution
# cross-check leans on this identity)
_unattributed_fresh = 0
_evicted_compiled = 0
_corpus_seen: set = set()
_corpus_lock = threading.Lock()


def configure(enabled: bool,
              ring_events: int = DEFAULT_RING_EVENTS,
              storm_threshold: int = DEFAULT_STORM_THRESHOLD,
              corpus_path: str = "",
              corpus_replay: bool = True) -> None:
    """Session-init hook (``obs.compile.*`` knobs; last session wins).
    Resizing the ring preserves its newest events; process-lifetime
    aggregates are never reset by reconfiguration."""
    global _enabled, _storm_threshold, _corpus_path, _ring, \
        _corpus_replay
    with _LOCK:
        ring_events = max(16, int(ring_events))
        if ring_events != (_ring.maxlen or 0):
            _ring = deque(_ring, maxlen=ring_events)
        _enabled = bool(enabled)
        _storm_threshold = max(1, int(storm_threshold))
        _corpus_path = str(corpus_path or "")
        _corpus_replay = bool(corpus_replay)
    if _enabled:
        # from session start, so the build split sees what set-up
        # builds before the first kernel goes through get_kernel
        _ensure_listener()


def corpus_replay_enabled() -> bool:
    return _corpus_replay


def is_enabled() -> bool:
    return _enabled


def reset() -> None:
    """Test hook: drop the ring, aggregates, query table and corpus
    dedup state (configuration is left alone)."""
    global _seq, _unattributed_fresh, _evicted_compiled
    with _LOCK:
        _ring.clear()
        _families.clear()
        _queries.clear()
        _seq = 0
        _unattributed_fresh = 0
        _evicted_compiled = 0
    with _corpus_lock:
        _corpus_seen.clear()


# ---------------------------------------------------------------------------
# cache-tier classification: jax monitoring events, counted per thread
# ---------------------------------------------------------------------------
# jax's compiler records '/jax/compilation_cache/cache_hits' when an
# executable is RELOADED from the persistent compilation cache and
# '.../cache_misses' when it actually compiles (both synchronously on
# the compiling thread).  A thread-local counter pair bracketing the
# first call therefore classifies the tier exactly — concurrent
# compiles on other threads cannot bleed into this thread's window.

_tls = threading.local()
_listener_installed = False

# -- the build split ---------------------------------------------------------
# jax reports how long each step of building a program took, on the
# building thread, as the step ends (jax 0.9.0: _src/dispatch.py,
# _src/compiler.py).  A jit traced inside another's trace reports its
# own trace duration first, and the outer one's holds it, so only the
# outermost counts: the scalar listener sees each step START (jax
# records the start time under the same name).  The backend-compile
# bracket spans the persistent-cache lookup too; where the lookup gave
# an executable (its retrieval event comes first), the whole bracket is
# load, else compile: one first call adds to one of the two.
BUILD_PARTS = ("trace", "lower", "compile", "load")
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


def _jax_cache_listener(event: str, **kwargs) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _tls.pc_hits = getattr(_tls, "pc_hits", 0) + 1
    elif event == "/jax/compilation_cache/cache_misses":
        _tls.pc_misses = getattr(_tls, "pc_misses", 0) + 1


def _jax_start_listener(event: str, value, **kwargs) -> None:
    if event == _TRACE_EVENT:
        _tls.trace_depth = getattr(_tls, "trace_depth", 0) + 1


def _add_build(part: str, secs: float) -> None:
    ns = int(secs * 1e9)
    built = getattr(_tls, "built", None)
    if built is None:
        built = _tls.built = dict.fromkeys(BUILD_PARTS, 0)
    built[part] += ns
    obsreg.get_registry().inc(f"kernel.build.{part}Ns", ns)


def _jax_duration_listener(event: str, duration: float, **kwargs) -> None:
    if event == _TRACE_EVENT:
        depth = getattr(_tls, "trace_depth", 1) - 1
        _tls.trace_depth = max(depth, 0)
        if depth <= 0:
            _add_build("trace", duration)
    elif event == _LOWER_EVENT:
        _add_build("lower", duration)
    elif event == _RETRIEVAL_EVENT:
        _tls.retrieved = True
    elif event == _BACKEND_EVENT:
        retrieved, _tls.retrieved = getattr(_tls, "retrieved", False), False
        _add_build("load" if retrieved else "compile", duration)


def _ensure_listener() -> None:
    global _listener_installed
    if _listener_installed:
        return
    with _LOCK:
        if _listener_installed:
            return
        try:
            from jax._src import monitoring
            monitoring.register_event_listener(_jax_cache_listener)
            monitoring.register_scalar_listener(_jax_start_listener)
            monitoring.register_event_duration_secs_listener(
                _jax_duration_listener)
        except Exception:
            pass                      # tier degrades to 'fresh' for all
        _listener_installed = True


def probe_begin() -> Tuple[int, int, Dict[str, int]]:
    """Snapshot this thread's persistent-cache event counters and what
    it has spent building programs, before a potential compile; pass
    the result to :func:`classify_tier` and :func:`build_split`."""
    _ensure_listener()
    return (getattr(_tls, "pc_hits", 0), getattr(_tls, "pc_misses", 0),
            dict(getattr(_tls, "built", None) or ()))


def build_split(probe) -> Dict[str, int]:
    """``{"traceNs", "lowerNs", "compileNs", "loadNs"}`` this thread
    spent since ``probe``: the parts of a first call that jax itself
    timed.  What is left of the call's wall is dispatch and waiting."""
    built = getattr(_tls, "built", None) or {}
    return {f"{part}Ns": built.get(part, 0) - probe[2].get(part, 0)
            for part in BUILD_PARTS}


def classify_tier(probe) -> str:
    """``fresh`` when any real XLA compile happened in the window,
    ``persistent`` when the window saw only persistent-cache reloads.
    A window with neither event (persistent cache not configured, or a
    program jax already held in memory) reports ``fresh`` — the
    conservative reading for a compile-bill instrument."""
    h0, m0 = probe[:2]
    if getattr(_tls, "pc_misses", 0) - m0 > 0:
        return TIER_FRESH
    if getattr(_tls, "pc_hits", 0) - h0 > 0:
        return TIER_PERSISTENT
    return TIER_FRESH


# ---------------------------------------------------------------------------
# query attribution
# ---------------------------------------------------------------------------

def _current_query_id() -> Optional[int]:
    # function-level import: see the layering note in the module
    # docstring (sched.cancel itself imports nothing from obs, so this
    # cannot cycle at runtime either)
    try:
        from spark_rapids_tpu.sched import cancel as _cancel
        tok = _cancel.current()
        return tok.query_id if tok is not None else None
    except Exception:
        return None


def _new_query_rec() -> Dict[str, Any]:
    return {"digest": None, "compiled": 0, "persistent": 0,
            "wall_ns": 0, "storm": False, "finished": False,
            "programs": []}


def _evict_queries_locked() -> None:
    """Bound the per-query table by evicting FINISHED records oldest
    first — a long-running query's record (its digest binding and
    accumulating attribution) must survive any number of short
    neighbours completing around it.  Live records are bounded by the
    scheduler's own queue/concurrency caps, so skipping them cannot
    grow the table unboundedly."""
    global _evicted_compiled
    if len(_queries) <= _MAX_QUERIES:
        return
    for qid in list(_queries):
        if _queries[qid]["finished"]:
            _evicted_compiled += _queries[qid]["compiled"]
            del _queries[qid]
            if len(_queries) <= _MAX_QUERIES:
                return


def _query_rec_locked(qid: Optional[int]) -> Optional[Dict[str, Any]]:
    if qid is None:
        return None
    q = _queries.get(qid)
    if q is None:
        # attribution without registration (a query path that bypassed
        # sched/service): track it anyway, digest unknown
        q = _queries[qid] = _new_query_rec()
        _evict_queries_locked()
    return q


def register_query(query_id: int, plan_digest: Optional[str]) -> None:
    """Bind a query id to its canonical plan digest for the lifetime of
    the query (called by sched/service.submit for every submission, so
    compile events fired on any thread carrying the query's CancelToken
    can be stamped with both)."""
    if query_id is None:
        return
    with _LOCK:
        q = _query_rec_locked(query_id)
        if q is not None and plan_digest is not None:
            q["digest"] = plan_digest


def finish_query(query_id: int) -> None:
    """Query-completion hook (sched/service worker, success or not):
    emits the precompile-corpus record for a distinct plan digest that
    compiled at least one program.  The per-query attribution record
    stays in the bounded table for the /queries + /compiles surfaces.
    Never raises."""
    try:
        with _LOCK:
            q = _queries.get(query_id)
            path = _corpus_path
            if q is None:
                return
            q["finished"] = True        # now evictable (_MAX_QUERIES)
            _evict_queries_locked()
            digest = q["digest"]
            programs = list(q["programs"])
        if not path or not digest or not programs:
            return
        with _corpus_lock:
            if digest in _corpus_seen:
                return
            record = {"plan_digest": digest, "query_id": query_id,
                      "ts_unix": time.time(),
                      "programs": programs}
            with open(path, "a") as f:
                f.write(json.dumps(record, default=str) + "\n")
            # mark seen only AFTER the append succeeded: a transient
            # write failure must leave the record emittable by the
            # plan's next completion, not drop it for the process life
            _corpus_seen.add(digest)
        obsreg.get_registry().inc("kernel.compile.corpusPlans")
    except Exception:
        pass


def query_stats(query_id: int) -> Optional[Dict[str, Any]]:
    """Per-query compile attribution (None when the query never
    compiled nor registered): fresh-compiled program count, persistent
    reload count, compile wall ms, storm flag."""
    with _LOCK:
        q = _queries.get(query_id)
        if q is None:
            return None
        return {"kernels_compiled": q["compiled"],
                "persistent_reloads": q["persistent"],
                "compile_ms": q["wall_ns"] / 1e6,
                "storm": q["storm"]}


def row_fields(query_id: int) -> Dict[str, Any]:
    """The ``kernels_compiled``/``compile_ms`` field pair shared by the
    ``/queries`` table rows and the slow-query JSONL — ONE derivation
    (fresh compiles + persistent reloads, both paid on the query's
    wall; null when zero) so the two surfaces cannot drift."""
    stats = query_stats(query_id)
    compiled = (stats["kernels_compiled"] +
                stats["persistent_reloads"]) if stats else 0
    compile_ms = stats["compile_ms"] if stats else 0.0
    return {"kernels_compiled": compiled or None,
            "compile_ms": round(compile_ms, 3) if compile_ms else None}


# ---------------------------------------------------------------------------
# signatures + width-bucketing projection
# ---------------------------------------------------------------------------

def _leaf_str(leaf: Any) -> str:
    if isinstance(leaf, tuple) and len(leaf) == 2 and \
            isinstance(leaf[0], tuple):
        shape, dty = leaf
        return f"{dty}[{','.join(str(d) for d in shape)}]"
    return str(leaf)[:32]


def canonical_signature(leaves: Sequence[Any]) -> str:
    """Compact ``dtype[shape]`` rendering of a program's argument
    leaves — the shape/dtype signature CompileEvents carry."""
    return ";".join(_leaf_str(x) for x in leaves)


def _pow2_bucket(n: int) -> int:
    return n if n <= 1 else 1 << (int(n) - 1).bit_length()


def _dtype_class(dty: str) -> str:
    d = str(dty)
    for cls in ("int", "uint", "float", "bool", "complex"):
        if d.startswith(cls):
            return cls
    return d


def _bucket_leaf(leaf: Any) -> Any:
    if isinstance(leaf, tuple) and len(leaf) == 2 and \
            isinstance(leaf[0], tuple):
        shape, dty = leaf
        return (tuple(_pow2_bucket(d) for d in shape),
                _dtype_class(dty))
    return "op"


def _bucket_key(key: Any) -> Any:
    """Width-bucketed projection of a kernel-cache key: integer
    components >= 16 (capacities, widths, row counts that leaked into
    keys) round up to powers of two.  This models what a shape-erased
    ABI with width-bucketed layouts would collapse — an ESTIMATE for
    the churn report, not a semantic statement about the keys."""
    if isinstance(key, tuple):
        return tuple(_bucket_key(k) for k in key)
    if isinstance(key, bool):
        return key
    if isinstance(key, int) and key >= 16:
        return _pow2_bucket(key)
    return key


# ---------------------------------------------------------------------------
# the ledger
# ---------------------------------------------------------------------------

def record_compile(key: Any, family: str,
                   leaves: Sequence[Any], t0_ns: int, dur_ns: int,
                   tier: str, replay: Optional[str] = None,
                   build: Optional[Dict[str, int]] = None) -> None:
    """Record one CompileEvent (called by the kernel-cache observe
    wrapper on the first call of each (key, shape) program).
    ``dur_ns`` is the first call's wall, whatever happened in it;
    ``build`` (:func:`build_split`) the parts of it jax timed.
    ``replay`` is the optional AOT replay payload (base64, built by
    kernel_cache._replay_payload) that rides the program's corpus
    record only — never the ring or the /compiles events (payloads are
    KBs each)."""
    if not _enabled:
        return
    global _seq
    qid = _current_query_id()
    sig = canonical_signature(leaves)
    key_repr = repr(key)[:200]
    storm_fired = None
    try:
        bkey = _bucket_key(key)
        bleaves = tuple(_bucket_leaf(x) for x in leaves)
    except Exception:
        bkey, bleaves = key_repr, sig
    with _LOCK:
        _seq += 1
        q = _query_rec_locked(qid)
        digest = q["digest"] if q is not None else None
        evt = {"seq": _seq, "ts_unix": time.time(),
               "family": family, "key": key_repr,
               "signature": sig, "tier": tier,
               "wall_ms": round(dur_ns / 1e6, 3),
               "query_id": qid, "plan_digest": digest}
        if build:
            evt["build"] = build
        _ring.append(evt)
        fam = _families.get(family)
        if fam is None:
            fam = _families[family] = {
                "programs": 0, "fresh": 0, "persistent": 0,
                "wall_ns": 0, "wall_fresh_ns": 0,
                "wall_persistent_ns": 0, "sigs": set(),
                "bucketed": set(), "sig_overflow": False}
        fam["programs"] += 1
        eff_tier = tier if tier in (TIER_FRESH, TIER_PERSISTENT) \
            else TIER_FRESH
        fam[eff_tier] += 1
        fam["wall_ns"] += int(dur_ns)
        # per-tier wall split: the persistent share is the "warm
        # compile" bill a replica restart pays (reload, not re-compile)
        # — the number the precompile service exists to move off the
        # serving path (tracked per run in BENCH_trend.json)
        fam["wall_fresh_ns" if eff_tier == TIER_FRESH
            else "wall_persistent_ns"] += int(dur_ns)
        if len(fam["sigs"]) < _MAX_SIGS_PER_FAMILY:
            fam["sigs"].add((key_repr, sig))
            fam["bucketed"].add((bkey, bleaves))
        else:
            fam["sig_overflow"] = True
        if q is None:
            if tier != TIER_PERSISTENT:
                global _unattributed_fresh
                _unattributed_fresh += 1
        else:
            if tier == TIER_PERSISTENT:
                q["persistent"] += 1
            else:
                q["compiled"] += 1
            q["wall_ns"] += int(dur_ns)
            if len(q["programs"]) < _MAX_PROGRAMS_PER_QUERY:
                prog = {"family": family, "key": key_repr,
                        "signature": sig}
                if replay is not None:
                    prog["replay"] = replay
                q["programs"].append(prog)
            total = q["compiled"] + q["persistent"]
            if total > _storm_threshold and not q["storm"]:
                q["storm"] = True
                storm_fired = total
    # registry counters + trace span outside the ledger lock (both
    # have their own locking; holding two at once buys nothing)
    tier_counter = ("kernel.cache.compiles" if tier != TIER_PERSISTENT
                    else "kernel.cache.persistentHits")
    obsreg.get_registry().inc_many(
        ("kernel.compile.events", 1),
        (f"kernel.compile.events.{family}", 1),
        ("kernel.compile.wallNs", int(dur_ns)),
        (tier_counter, 1))
    obsreg.get_registry().observe("kernel.compile.wallMs", dur_ns / 1e6)
    # ledger: compile wall bills the owning tenant (same qid binding;
    # one bool inside when accounting is off)
    from spark_rapids_tpu.obs import accounting as _acct
    _acct.charge_qid(qid, "kernel.compile.wallNs", int(dur_ns))
    obstrace.record("kernel.compile", t0_ns, dur_ns, cat="kernel",
                    args={"family": family, "tier": tier, "query": qid,
                          "signature": sig, **(build or {})})
    if storm_fired is not None:
        obsreg.get_registry().inc("kernel.compile.storms")
        obsrec.record_event("compile.storm", query=qid,
                            programs=storm_fired,
                            threshold=_storm_threshold,
                            plan_digest=digest)


# ---------------------------------------------------------------------------
# reports
# ---------------------------------------------------------------------------

def _churn_rows_locked() -> List[Dict[str, Any]]:
    rows = []
    for family, a in _families.items():
        distinct = len(a["sigs"])
        bucketed = len(a["bucketed"])
        rows.append({
            "family": family,
            "programs": a["programs"],
            "fresh": a["fresh"],
            "persistent": a["persistent"],
            "compile_wall_ms": round(a["wall_ns"] / 1e6, 3),
            "distinct_signatures": distinct,
            "est_programs_width_bucketed": bucketed,
            "est_collapse_savings": distinct - bucketed,
            "sig_overflow": a["sig_overflow"],
        })
    rows.sort(key=lambda r: (-r["distinct_signatures"],
                             -r["compile_wall_ms"], r["family"]))
    return rows


def _totals_locked() -> Dict[str, Any]:
    fresh = sum(a["fresh"] for a in _families.values())
    persistent = sum(a["persistent"] for a in _families.values())
    wall_ns = sum(a["wall_ns"] for a in _families.values())
    distinct = sum(len(a["sigs"]) for a in _families.values())
    bucketed = sum(len(a["bucketed"]) for a in _families.values())
    return {"events": fresh + persistent, "fresh": fresh,
            "persistent": persistent,
            "compile_wall_ms": round(wall_ns / 1e6, 3),
            "compile_wall_fresh_ms": round(sum(
                a["wall_fresh_ns"] for a in _families.values()) / 1e6,
                3),
            "compile_wall_persistent_ms": round(sum(
                a["wall_persistent_ns"]
                for a in _families.values()) / 1e6, 3),
            "distinct_programs": distinct,
            "width_bucketed_projection": bucketed,
            "families": len(_families),
            "queries_tracked": len(_queries),
            # closure terms for the attribution identity (see the
            # _unattributed_fresh comment): per-query compiled totals
            # + these two == the kernel.cache.compiles counter
            "unattributed_fresh": _unattributed_fresh,
            "evicted_compiled": _evicted_compiled}


def _events_locked(max_events: Optional[int]) -> List[Dict[str, Any]]:
    out = list(_ring)
    if max_events is None:
        return out
    return out[-max_events:] if max_events > 0 else []


def churn_report() -> List[Dict[str, Any]]:
    """Shape-churn analytics, ranked by signature cardinality: for each
    kernel family, the distinct (key, shape) program count, the
    estimated program count after width-bucketing (powers-of-two shape
    dims + dtype classes + bucketed key capacities), and the estimated
    collapse savings — the candidates ROADMAP item 2's shape-erased
    ABI should attack first."""
    with _LOCK:
        return _churn_rows_locked()


def totals() -> Dict[str, Any]:
    with _LOCK:
        return _totals_locked()


def events(max_events: Optional[int] = None) -> List[Dict[str, Any]]:
    """The newest ``max_events`` ring events (all when None; an
    explicit 0 means none — a scraper asking for totals only)."""
    with _LOCK:
        return _events_locked(max_events)


def snapshot(max_events: int = 256) -> Dict[str, Any]:
    """The ``/compiles`` endpoint payload: config, totals, the newest
    ring events, per-query attribution, and the churn report —
    assembled under ONE lock acquisition so a scrape racing a compile
    cannot observe totals/events/churn from different instants."""
    with _LOCK:
        per_query = {
            str(qid): {"plan_digest": q["digest"],
                       "kernels_compiled": q["compiled"],
                       "persistent_reloads": q["persistent"],
                       "compile_ms": round(q["wall_ns"] / 1e6, 3),
                       "storm": q["storm"]}
            for qid, q in _queries.items()}
        return {"enabled": _enabled, "ring_capacity": _ring.maxlen,
                "storm_threshold": _storm_threshold,
                "corpus_path": _corpus_path or None,
                "totals": _totals_locked(),
                "events": _events_locked(max_events),
                "per_query": per_query,
                "churn": _churn_rows_locked()}


def corpus_path() -> str:
    return _corpus_path

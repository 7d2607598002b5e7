"""Low-overhead span tracer with a Chrome trace-event exporter.

Design constraints (the engine's hot paths run this per batch):

  * **Zero-allocation no-op path when disabled** — ``span()`` returns a
    shared singleton context manager and ``record()`` returns
    immediately; the only cost is one module-global bool check.
  * **Bounded memory** — spans land in a ring buffer
    (``collections.deque`` with ``maxlen``); when a query outruns the
    buffer the oldest spans drop, never the process.
  * **Thread-safe** — partition iterators drain on the task pool and
    prefetch threads record concurrently; ``deque.append`` is atomic
    and the monotonic sequence counter hands out carve marks.

Spans are recorded at *exit* with monotonic-ns timestamps (so recording
order is children-before-parents); the Chrome exporter re-derives the
nesting per thread from the intervals and emits matched ``B``/``E``
event pairs a Perfetto / chrome://tracing load renders as a flame
graph.

**One tree a query.**  Every span carries an id minted at *entry*, the
id of its ``parent``, the ``query`` it works for and the ``chip`` its
thread works for.  The parent is the innermost span open on the thread
at entry (a thread-local stack that ``span()`` and ``exec/base``'s
``timed``/``timed_extra`` push and pop); the query comes from the
thread's ``CancelToken`` (``sched/cancel.current()``), so prefetch,
task-pool and streamer threads label their spans with the query they
work for; the chip is the jax device id ``mem/device.span_chip`` gives
(None on one chip and on a thread that works for no single chip: serve,
planner).  A span with a query and no open parent hangs under that
query's root
(:func:`root_id`): ``serve.request`` for a served query, ``query``
otherwise.  :func:`query_spans` is a query's tree; ``mark()`` /
``spans_since()`` stay for callers that want a window of the ring
whatever the query (the executor's reply).
"""

from __future__ import annotations

import itertools
import json
import threading
import time
from collections import OrderedDict, deque
from typing import Any, Dict, List, Optional, Sequence, Tuple

DEFAULT_BUFFER_SPANS = 65536

# one span record (indices 0-7 are the original eight; 8-11 appended):
#   (seq, tid, name, cat, t0_ns, dur_ns, depth, args,
#    span id, parent span id (0: none), query id (None: no query),
#    chip: jax device id (None: no single chip))
Span = Tuple[int, int, str, str, int, int, int, Optional[Dict[str, Any]],
             int, int, Optional[int], Optional[int]]
SID, PARENT, QUERY, CHIP = 8, 9, 10, 11

_enabled = False
_ring: deque = deque(maxlen=DEFAULT_BUFFER_SPANS)
_seq = itertools.count()          # record order: the carve marks
_ids = itertools.count(1)         # span ids, minted at entry
_lock = threading.Lock()
_tls = threading.local()          # .stack: ids of the spans open here

# query id -> [id of the query's root span, whether it is recorded].
# Minted by whoever asks first: the children that hang under it usually
# end (and are recorded) before the root itself.  Bounded; a query that
# old has left the ring.
_MAX_ROOTS = 4096
_roots: "OrderedDict[int, list]" = OrderedDict()

# cross-process stitching state: synthetic lane ids for spans merged
# from other processes (executor map stages), plus human labels the
# Chrome exporter renders as thread_name metadata.  Real tids are
# CPython thread idents (pthread pointers, far above this range), so
# small synthetic ids cannot collide with them.  Bounded: labels embed
# executor pids, so a long-lived driver restarting pools mints fresh
# keys — past _MAX_LANES the oldest mapping evicts (its spans keep the
# label in args["lane"]; only the chrome thread_name metadata for a
# lane that old is lost).
_MAX_LANES = 1024
_lane_ids = itertools.count(1)
_lane_map: Dict[Tuple[str, int], int] = {}   # (label, foreign tid) -> lane
_lane_counts: Dict[str, int] = {}            # label -> lanes minted
_tid_labels: Dict[int, str] = {}


def configure(enabled: bool, buffer_spans: Optional[int] = None) -> None:
    """Process-wide tracer switch (called by TpuSparkSession from the
    ``spark.rapids.tpu.obs.trace.*`` knobs; last session wins, the
    scan-cache ``configure`` idiom)."""
    global _enabled, _ring
    with _lock:
        if buffer_spans is not None and \
                int(buffer_spans) != (_ring.maxlen or 0):
            _ring = deque(_ring, maxlen=max(16, int(buffer_spans)))
        _enabled = bool(enabled)


def is_enabled() -> bool:
    return _enabled


def clear() -> None:
    with _lock:
        _ring.clear()
        _roots.clear()


def mark() -> int:
    """Monotonic carve mark: ``spans_since(mark())`` returns only spans
    recorded after this call (per-query span windows)."""
    return next(_seq)


_current_token = None
_span_chip = None


def current_chip() -> Optional[int]:
    """The jax device id of the chip this thread works for
    (``mem/device.span_chip``; ``mem`` imports this module, hence the
    late import)."""
    global _span_chip
    if _span_chip is None:
        from spark_rapids_tpu.mem.device import span_chip
        _span_chip = span_chip
    return _span_chip()


def current_query() -> Optional[int]:
    """The query this thread works for: the id on its installed
    ``CancelToken`` (None outside any query).  ``sched.cancel`` imports
    nothing of ``obs``; the package ``sched`` does, hence the late
    import."""
    global _current_token
    if _current_token is None:
        from spark_rapids_tpu.sched import cancel
        _current_token = cancel.current
    tok = _current_token()
    return tok.query_id if tok is not None else None


def _root_locked(query: int) -> list:
    root = _roots.get(query)
    if root is None:
        root = _roots[query] = [next(_ids), False]
        while len(_roots) > _MAX_ROOTS:
            _roots.popitem(last=False)
    return root


def root_id(query: int) -> int:
    """The id of ``query``'s root span, minted on the first ask."""
    with _lock:
        return _root_locked(query)[0]


def record_root(name: str, t0_ns: int, dur_ns: int, query: int,
                cat: str = "query",
                args: Optional[Dict[str, Any]] = None) -> None:
    """Record ``query``'s root span: the one every span of the query
    with no open parent hangs under.  A second root of one query (a
    coalesced batch answers several requests from one execution) hangs
    under the first."""
    if not _enabled:
        return
    with _lock:
        root = _root_locked(query)
        first, root[1] = not root[1], True
    if first:
        record(name, t0_ns, dur_ns, cat, args, depth=0, parent=0,
               query=query, sid=root[0])
    else:
        record(name, t0_ns, dur_ns, cat, args, depth=1, parent=root[0],
               query=query)


def _parent_here(stack, query: Optional[int]) -> int:
    """Where a span with no parent of its own hangs: under the
    innermost span open on this thread, else under its query's root."""
    if stack:
        return stack[-1]
    return root_id(query) if query is not None else 0


def open_span() -> int:
    """Entry half of a span for callers that keep their own timing
    (``exec/base``): mints the id and pushes it on this thread's stack
    of open spans.  Call only when enabled, and hand the id to
    :func:`close_span`."""
    sid = next(_ids)
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    stack.append(sid)
    return sid


def close_span(sid: int, name: str, t0_ns: int, dur_ns: int,
               cat: str = "exec",
               args: Optional[Dict[str, Any]] = None) -> None:
    """Exit half: pops ``sid`` (and anything left open above it) and
    records the span under what is then innermost."""
    stack = getattr(_tls, "stack", None)
    if stack and sid in stack:
        del stack[stack.index(sid):]
    record(name, t0_ns, dur_ns, cat, args,
           depth=len(stack) + 1 if stack else 1, sid=sid)


def record(name: str, t0_ns: int, dur_ns: int, cat: str = "exec",
           args: Optional[Dict[str, Any]] = None,
           depth: Optional[int] = None, parent: Optional[int] = None,
           query: Optional[int] = None, sid: Optional[int] = None,
           chip: Optional[int] = None) -> None:
    """Record one completed span. No-op (one bool check) when disabled.

    ``parent`` defaults to the innermost span open on this thread, else
    to the query's root; ``query`` and ``chip`` to the thread's (see the
    module docstring).  ``sid`` is given by who minted the id at entry
    (:func:`open_span`, :func:`record_root`)."""
    if not _enabled:
        return
    stack = getattr(_tls, "stack", None)
    if depth is None:
        depth = len(stack) if stack else 0
    if query is None:
        query = current_query()
    if sid is None:
        sid = next(_ids)
    if parent is None:
        parent = _parent_here(stack, query)
    if chip is None:
        chip = current_chip()
    _ring.append((next(_seq), threading.get_ident(), name, cat,
                  int(t0_ns), int(dur_ns), depth, args, sid, parent,
                  query, chip))


class _NoopSpan:
    """Shared do-nothing context manager for the disabled path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


_NOOP = _NoopSpan()


class _Span:
    __slots__ = ("name", "cat", "args", "t0", "sid")

    def __init__(self, name: str, cat: str,
                 args: Optional[Dict[str, Any]]):
        self.name = name
        self.cat = cat
        self.args = args

    def __enter__(self):
        self.sid = open_span()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *a):
        dur = time.perf_counter_ns() - self.t0
        close_span(self.sid, self.name, self.t0, dur, self.cat,
                   self.args)
        return False


def span(name: str, cat: str = "exec",
         args: Optional[Dict[str, Any]] = None):
    """``with span("scan.decode"):`` — a nested, thread-local span.
    Returns the shared no-op singleton when tracing is disabled."""
    if not _enabled:
        return _NOOP
    return _Span(name, cat, args)


def record_foreign(spans: Sequence[Span], offset_ns: int,
                   label: str) -> int:
    """Merge spans recorded in ANOTHER process into this ring (the
    cross-process trace stitch): each foreign timestamp is shifted by
    ``offset_ns`` (foreign clock -> this process's perf_counter_ns
    domain, aligned by the caller from the request/reply envelope) and
    each foreign thread maps to a stable synthetic lane labeled
    ``label`` (``label/t0``, ``label/t1``, ... when the foreign process
    used several threads) that the Chrome exporter names via
    thread_name metadata — executor map stages render as their own
    lanes in Perfetto.  The spans join the tree of the query the
    calling thread works for: ids are minted anew, links among the
    foreign spans are kept, and those whose parent did not come along
    hang under the caller's open span (else the query's root).  Returns
    the number of spans merged.  No-op when tracing is disabled."""
    if not _enabled or not spans:
        return 0
    query = current_query()
    above = _parent_here(getattr(_tls, "stack", None), query)
    # children were recorded before their parents: mint every id first
    ids = {s[SID]: next(_ids) for s in spans if len(s) > SID}
    n = 0
    with _lock:
        for s in spans:
            seq_, ftid, name, cat, t0, dur, depth, args = s[:8]
            sid = ids[s[SID]] if len(s) > SID else next(_ids)
            parent = ids.get(s[PARENT], above) if len(s) > PARENT \
                else above
            key = (label, ftid)
            lane = _lane_map.get(key)
            if lane is None:
                lane = next(_lane_ids)
                _lane_map[key] = lane
                nth = _lane_counts.get(label, 0)
                _lane_counts[label] = nth + 1
                _tid_labels[lane] = (label if nth == 0
                                     else f"{label}/t{nth}")
                while len(_lane_map) > _MAX_LANES:
                    old_key = next(iter(_lane_map))
                    _tid_labels.pop(_lane_map.pop(old_key), None)
                    # drop a label's mint counter with its last lane —
                    # labels embed executor pids, so a long-lived
                    # driver would otherwise leak one counter per pool
                    # generation forever
                    old_label = old_key[0]
                    if all(k[0] != old_label for k in _lane_map):
                        _lane_counts.pop(old_label, None)
            a = dict(args) if args else {}
            a.setdefault("lane", _tid_labels[lane])
            _ring.append((next(_seq), lane, name, cat,
                          int(t0) + int(offset_ns), int(dur),
                          int(depth), a, sid, parent, query,
                          s[CHIP] if len(s) > CHIP else None))
            n += 1
    return n


def lane_label(tid: int) -> Optional[str]:
    """Human label of a synthetic (stitched) lane; None for real
    threads."""
    return _tid_labels.get(tid)


def snapshot() -> List[Span]:
    with _lock:
        return list(_ring)


def spans_since(seq_mark: int) -> List[Span]:
    return [s for s in snapshot() if s[0] >= seq_mark]


def query_spans(query: int) -> List[Span]:
    """Every span in the ring that worked for ``query``, whichever
    thread recorded it and whenever: the query's tree, as far as the
    ring still holds it."""
    return [s for s in snapshot() if s[QUERY] == query]


def span_dicts(spans: Sequence[Span]) -> List[Dict[str, Any]]:
    """JSON-friendly rendering (the QueryProfile ``spans`` section)."""
    out = []
    for seq, tid, name, cat, t0, dur, depth, args, sid, parent, query, \
            chip in spans:
        d = {"name": name, "cat": cat, "tid": tid, "ts_ns": t0,
             "dur_ns": dur, "depth": depth, "id": sid,
             "parent": parent, "query": query, "chip": chip}
        if args:
            d["args"] = args
        out.append(d)
    return out


# ---------------------------------------------------------------------------
# Chrome trace-event export
# ---------------------------------------------------------------------------

def chrome_trace(spans: Optional[Sequence[Span]] = None
                 ) -> Dict[str, Any]:
    """Render spans as a Chrome trace-event object (the ``traceEvents``
    duration-event format: matched ``B``/``E`` pairs, ``ts`` in
    microseconds).

    Spans were recorded at exit (children before parents), so per
    thread the nesting forest is rebuilt from the intervals: pre-order
    sort ``(t0, -t1, seq)``, then an explicit stack walk emits every
    ``E`` exactly when the next span starts outside it — matched pairs
    by construction, properly nested for stack-based (per-thread)
    producers."""
    if spans is None:
        spans = snapshot()
    events: List[Dict[str, Any]] = []
    by_tid: Dict[int, List[Span]] = {}
    for s in spans:
        by_tid.setdefault(s[1], []).append(s)
    # stitched executor lanes get their human name (thread_name
    # metadata events — Perfetto renders the label on the lane)
    for tid in sorted(by_tid):
        label = _tid_labels.get(tid)
        if label is not None:
            events.append({"name": "thread_name", "ph": "M", "pid": 0,
                           "tid": tid, "args": {"name": label}})
    for tid, ss in sorted(by_tid.items()):
        ivs = sorted(((s[4], s[4] + s[5], s[0], s) for s in ss),
                     key=lambda x: (x[0], -x[1], x[2]))
        stack: List[Tuple[int, int, int, Span]] = []

        def emit(ph: str, s: Span, ts_ns: int) -> None:
            ev = {"name": s[2], "cat": s[3], "ph": ph, "pid": 0,
                  "tid": tid, "ts": ts_ns / 1e3}
            if ph == "B" and s[CHIP] is not None:
                ev["args"] = {**(s[7] or {}), "chip": s[CHIP]}
            elif ph == "B" and s[7]:
                ev["args"] = s[7]
            events.append(ev)

        for t0, t1, _seq, s in ivs:
            while stack and stack[-1][1] <= t0:
                pt0, pt1, _pseq, ps = stack.pop()
                emit("E", ps, pt1)
            emit("B", s, t0)
            stack.append((t0, t1, _seq, s))
        while stack:
            pt0, pt1, _pseq, ps = stack.pop()
            emit("E", ps, pt1)
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def dump_chrome_trace(path: str,
                      spans: Optional[Sequence[Span]] = None) -> str:
    """Write the Chrome trace JSON to ``path`` (open it in Perfetto or
    chrome://tracing).  Returns the path."""
    with open(path, "w") as f:
        json.dump(chrome_trace(spans), f)
    return path

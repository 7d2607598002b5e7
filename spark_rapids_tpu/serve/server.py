"""The serving front-end: a long-lived TCP server over QueryService.

One ``ServeServer`` per engine session when ``serve.enabled=true``
(api/session.py keeps it on ``session.serve_server``; ``serve.port=0``
binds ephemeral, discover via ``serve_server.port``).  Layering::

    ServeClient ──wire──> ServeServer ──submit(meta)──> QueryService
                             │                             (PR 5)
                             ├─ ServeSession  (conf overlay, fair share,
                             │                 prepared statements,
                             │                 idle eviction,
                             │                 resume token)
                             └─ result_cache  (digest+stamp keyed)

Per connection a reader thread owns the socket's inbound side; query
ops submit asynchronously and a per-query streamer thread delivers
CHUNK frames under the client's credit (wire.py) — the reader stays
responsive for CREDIT and cancel frames while results stream.  A dead
socket cancels every in-flight query through PR 5's CancelToken, so an
abandoned query releases its admission slot, drains its prefetcher and
frees its spill-catalog entries exactly like an explicit cancel.

Fair share: at most ``serve.session.maxInFlight`` queries per session
may be in flight; past it the request is refused with a typed
``FairShareExceeded`` error (back-pressure to THAT client) instead of
queueing — one greedy client cannot monopolize ``sched.memoryBudget``.

Hardening contract (the reference's graceful-degradation bar applied
to the front door): every byte off the wire is hostile until
validated.  Frame lengths are bounded before allocation
(``serve.wire.maxFrameBytes``), per-connection reads carry a
whole-frame progress deadline (``serve.wire.readTimeoutMs``, the
slowloris defense), streamer writes carry a zero-progress stall bound
(``serve.wire.writeStallMs``), and every malformed frame is answered
with a reason-coded ERR + ``serve.wire.malformedFrames.<reason>``
counter instead of a dead reader thread.  A malformed-frame storm
(``serve.wire.stormThreshold``) dumps one flight-recorder bundle with
reason "protocol".

Drain + resume: :meth:`ServeServer.drain` stops accepting, lets
in-flight streams finish inside ``serve.drain.deadlineMs``, cancels
stragglers with a typed ``Draining`` error, and tears down
leak-audited (streamer threads joined, admission slots released,
credit state dropped).  Sessions carry resume tokens and CHUNK frames
carry sequence numbers, so a :class:`ServeClient` that reconnects
after the drain re-attaches its session and resumes a stream from the
last chunk it holds — served duplicate-free from the process-global
retained-stream window (``serve.stream.retainBytes``) or the result
cache, both of which survive the drain/restart cycle.
"""

from __future__ import annotations

import itertools
import math
import os
import socket
import threading
import time
import weakref
from collections import OrderedDict
from typing import Any, Dict, Optional, Tuple

from spark_rapids_tpu.obs import recorder as obsrec
from spark_rapids_tpu.obs import registry as obsreg
from spark_rapids_tpu.obs import trace as obstrace
from spark_rapids_tpu.serve import faults as serve_faults
from spark_rapids_tpu.serve import result_cache, wire
from spark_rapids_tpu.serve.faults import ServeFaultAction
from spark_rapids_tpu.serve.statements import (PreparedStatement,
                                               StatementError)

# a streamer blocked on client credit longer than this aborts: a
# wedged consumer must not pin its result table and fair-share slot
# forever (idle eviction only covers sessions with nothing in flight)
_STREAM_STALL_S = 300.0

# socket tick: reader recv / streamer send block at most this long per
# syscall, so deadline checks, drain flags and stop events are always
# observed promptly without dedicated watchdog threads
_TICK = 0.1


class ServeError(Exception):
    """Typed server-side request failure; ``code`` rides the ERR frame."""

    def __init__(self, code: str, msg: str):
        super().__init__(msg)
        self.code = code


# ---------------------------------------------------------------------------
# Process-global resume state: survives a drain/restart cycle inside
# the process (the single-replica analog of an external session store)
# ---------------------------------------------------------------------------

_RESUME_LOCK = threading.Lock()
# resume token -> the hello overlay, so a re-hello after the original
# session was evicted/drained can mint an equivalent session (bounded
# LRU: tokens are cheap, but unbounded would be a leak by another name)
_RESUME_SESSIONS: "OrderedDict[str, Dict[str, Any]]" = OrderedDict()
_RESUME_CAP = 4096

# (resume token, stream id) -> retained stream entry: either a pinned
# result table (byte-accounted against _RETAIN_CAP) or a zero-cost
# reference into the result cache.  This is the window a reconnecting
# client resumes from; the client's finish_stream ack releases it.
_RETAIN_LOCK = threading.Lock()
_RETAINED: "OrderedDict[Tuple[str, str], Dict[str, Any]]" = OrderedDict()
_RETAINED_BYTES = 0
_RETAIN_CAP = 128 << 20


def _register_resume(token: str, overlay: Dict[str, Any]) -> None:
    with _RESUME_LOCK:
        _RESUME_SESSIONS.pop(token, None)
        _RESUME_SESSIONS[token] = dict(overlay or {})
        while len(_RESUME_SESSIONS) > _RESUME_CAP:
            _RESUME_SESSIONS.popitem(last=False)


def _resume_overlay(token: str) -> Optional[Dict[str, Any]]:
    with _RESUME_LOCK:
        overlay = _RESUME_SESSIONS.get(token)
        if overlay is not None:
            _RESUME_SESSIONS.move_to_end(token)
        return dict(overlay) if overlay is not None else None


def _publish_retained_locked() -> None:
    reg = obsreg.get_registry()
    reg.set_gauge("serve.retainedStreams", len(_RETAINED))
    reg.set_gauge("serve.retainedStreamBytes", _RETAINED_BYTES)


def _retain_stream(token: Optional[str], stream_id: Optional[str],
                   table=None, cache_ref: Optional[Tuple] = None) -> None:
    """Retain one stream's replay source under (token, stream_id):
    either the table itself (byte-accounted, LRU-evicted past
    ``serve.stream.retainBytes``) or a result-cache reference (zero
    retained bytes — the cache already pins the table)."""
    global _RETAINED_BYTES
    if not token or not stream_id:
        return
    nb = 0
    if table is not None and cache_ref is None:
        try:
            nb = int(table.nbytes)
        except Exception:
            nb = 1 << 20
        if nb > _RETAIN_CAP:
            return
    key = (token, str(stream_id))
    with _RETAIN_LOCK:
        old = _RETAINED.pop(key, None)
        if old is not None:
            _RETAINED_BYTES -= old["nbytes"]
        _RETAINED[key] = {"table": None if cache_ref else table,
                          "cache_ref": cache_ref, "nbytes": nb}
        _RETAINED_BYTES += nb
        while _RETAINED_BYTES > _RETAIN_CAP and _RETAINED:
            _, ev = _RETAINED.popitem(last=False)
            _RETAINED_BYTES -= ev["nbytes"]
        _publish_retained_locked()


def _lookup_stream(token: Optional[str], stream_id: str):
    """The retained table for (token, stream_id), or None (evicted,
    acked, or never retained).  Cache-backed entries resolve through
    ``result_cache.peek`` — non-counting, so resume traffic does not
    inflate the hit-rate the zero-dispatch CI gate asserts on."""
    if not token:
        return None
    key = (token, str(stream_id))
    with _RETAIN_LOCK:
        ent = _RETAINED.get(key)
        if ent is not None:
            _RETAINED.move_to_end(key)
    if ent is None:
        return None
    if ent["cache_ref"] is not None:
        ck, names, stamps = ent["cache_ref"]
        return result_cache.peek(ck, names, stamps)
    return ent["table"]


def _release_stream(token: Optional[str], stream_id: str) -> bool:
    global _RETAINED_BYTES
    if not token:
        return False
    with _RETAIN_LOCK:
        ent = _RETAINED.pop((token, str(stream_id)), None)
        if ent is not None:
            _RETAINED_BYTES -= ent["nbytes"]
        _publish_retained_locked()
    return ent is not None


def retained_stats() -> Dict[str, int]:
    with _RETAIN_LOCK:
        return {"entries": len(_RETAINED), "bytes": _RETAINED_BYTES}


def clear_retained() -> None:
    global _RETAINED_BYTES
    with _RETAIN_LOCK:
        _RETAINED.clear()
        _RETAINED_BYTES = 0
        _publish_retained_locked()
    with _RESUME_LOCK:
        _RESUME_SESSIONS.clear()


class ServeSession:
    """Server-side client session: id, conf overlay, prepared
    statements, the fair-share in-flight gate, and the resume token a
    reconnecting client re-attaches with."""

    __slots__ = ("session_id", "priority", "timeout_ms",
                 "estimate_bytes", "max_inflight", "statements",
                 "inflight", "last_active", "created_unix", "closed",
                 "client_addr", "resume_token", "overlay", "_lock")

    def __init__(self, session_id: str, overlay: Dict[str, Any],
                 max_inflight: int, client_addr: str,
                 resume_token: Optional[str] = None):
        self.session_id = session_id
        self.overlay = dict(overlay or {})
        self.priority = int(self.overlay.get("priority", 0) or 0)
        t = self.overlay.get("timeoutMs")
        self.timeout_ms = int(t) if t else None
        e = self.overlay.get("estimateBytes")
        self.estimate_bytes = int(e) if e else None
        self.max_inflight = max(1, int(max_inflight))
        self.statements: Dict[str, PreparedStatement] = {}
        self.inflight = 0
        self.created_unix = time.time()
        self.last_active = time.monotonic()
        self.closed = False
        self.client_addr = client_addr
        self.resume_token = resume_token or os.urandom(12).hex()
        self._lock = threading.Lock()

    def touch(self) -> None:
        self.last_active = time.monotonic()

    def try_begin_query(self) -> str:
        """Atomically claim one fair-share slot: ``"ok"``, or the
        typed refusal — ``"closed"`` (the session was evicted; the
        caller answers SessionExpired) vs ``"full"`` (fair share;
        FairShareExceeded).  The tri-state closes the janitor race:
        eviction and admission serialize on the session lock, so a
        request can never slip a query into a session being torn
        down."""
        with self._lock:
            if self.closed:
                return "closed"
            if self.inflight >= self.max_inflight:
                return "full"
            self.inflight += 1
            return "ok"

    def end_query(self) -> None:
        with self._lock:
            self.inflight = max(0, self.inflight - 1)
            self.last_active = time.monotonic()

    def try_close_if_idle(self, idle_s: float) -> bool:
        """Janitor-side half of the eviction race fix: close only if
        nothing is in flight AND the idle clock expired, atomically
        under the same lock ``try_begin_query`` claims slots with.  An
        in-flight stream therefore always finishes before teardown;
        only NEW requests on an evicted session see SessionExpired."""
        with self._lock:
            if self.closed:
                return True
            if self.inflight > 0:
                return False
            if time.monotonic() - self.last_active <= idle_s:
                return False
            self.closed = True
            return True

    def force_close(self) -> None:
        with self._lock:
            self.closed = True

    def describe(self) -> Dict[str, Any]:
        return {"session_id": self.session_id,
                "priority": self.priority,
                "timeout_ms": self.timeout_ms,
                "estimate_bytes": self.estimate_bytes,
                "max_inflight": self.max_inflight,
                "inflight": self.inflight,
                "statements": sorted(self.statements),
                "client_addr": self.client_addr}


# receipt time of the request the connection's reader thread is
# handling (``perf_counter_ns``, the tracer's clock): every path from
# ``_handle_request`` to an ``_Inflight`` (sql, execute, the batcher's
# offer, resume) runs on that thread, so the ``serve.request`` span
# starts at receipt whichever path built the ``_Inflight``
_RECEIPT = threading.local()


class _Inflight:
    """One query being answered on one connection: its future (None for
    a result-cache hit or a resumed stream) and the client-credit
    window."""

    def __init__(self, tag: int, future, credit: int,
                 template: Optional[str] = None):
        self.tag = tag
        self.future = future
        self._credit = max(0, int(credit))
        self._cv = threading.Condition()
        self.aborted = False
        self.abort_code: Optional[str] = None
        # SLO attribution: request receipt time + statement template
        # (None for ad-hoc sql / resumes) — e2e and first-chunk
        # latency observe against these at stream time
        self.t0_ns = time.monotonic_ns()
        self.template = template
        # the same instant for the span tree (``serve.request``), and
        # whether that span is recorded
        self.req_ns = getattr(_RECEIPT, "ns", None) \
            or time.perf_counter_ns()
        self.traced = False

    def add_credit(self, n: int) -> None:
        with self._cv:
            self._credit += max(0, int(n))
            self._cv.notify_all()

    def abort(self, code: Optional[str] = None) -> None:
        with self._cv:
            self.aborted = True
            if code and self.abort_code is None:
                self.abort_code = code
            self._cv.notify_all()

    def take_credit(self) -> bool:
        """Block until one CHUNK of credit is available; False when the
        stream aborted (disconnect/cancel/drain) or stalled out."""
        deadline = time.monotonic() + _STREAM_STALL_S
        with self._cv:
            while True:
                if self.aborted:
                    return False
                if self._credit > 0:
                    self._credit -= 1
                    return True
                if time.monotonic() >= deadline:
                    self.aborted = True
                    return False
                self._cv.wait(timeout=0.25)


class _ChunkFeed:
    """Per-flight relay of the leader stream's ENCODED result chunks.

    Single-flight followers used to block on the whole flight result and
    then re-chunk + re-encode it per follower; subscribing here instead
    lets a follower send chunk N the moment the leader's streamer has
    encoded it — follower first-chunk latency tracks the leader's (both
    observe ``slo.firstChunkMs``) and the Arrow slice+encode work is
    paid once per flight.  Payloads are buffered, so a follower joining
    mid-stream replays from chunk 1; a leader stream that dies before
    publishing everything aborts the feed and followers fall back to
    whole-result streaming from their own (settled) futures, resuming
    after the chunks already sent."""

    _STALL_S = 5.0

    def __init__(self):
        self._cond = threading.Condition()
        self._chunks: list = []
        self._done = False
        self._aborted = False
        self.rows = 0
        self.total = 0

    def publish(self, payload) -> None:
        with self._cond:
            if self._done or self._aborted:
                return
            self._chunks.append(payload)
            self._cond.notify_all()

    def finish(self, rows: int, total: int) -> None:
        with self._cond:
            if self._aborted:
                return
            self.rows, self.total = int(rows), int(total)
            self._done = True
            self._cond.notify_all()

    def abort(self) -> None:
        """No-op after finish(): the leader's error-path net calls this
        unconditionally."""
        with self._cond:
            if not self._done:
                self._aborted = True
            self._cond.notify_all()

    def next(self, i: int) -> Tuple[str, Any]:
        """('chunk', payload) for index ``i``, ('done', None) past the
        final chunk, ('abort', None) on a dead or stalled leader."""
        with self._cond:
            self._cond.wait_for(
                lambda: i < len(self._chunks) or self._done
                or self._aborted,
                timeout=self._STALL_S)
            if i < len(self._chunks):
                return "chunk", self._chunks[i]
            if self._done:
                return "done", None
            return "abort", None


class _Conn:
    __slots__ = ("sock", "wlock", "addr", "alive", "session",
                 "inflight", "closed_cleanly", "streamers", "_lock")

    def __init__(self, sock: socket.socket, addr: str):
        self.sock = sock
        self.wlock = threading.Lock()
        self.addr = addr
        self.alive = True
        self.session: Optional[ServeSession] = None
        self.inflight: Dict[int, _Inflight] = {}
        self.closed_cleanly = False
        self.streamers: list = []
        self._lock = threading.Lock()

    def track(self, infl: _Inflight) -> None:
        with self._lock:
            self.inflight[infl.tag] = infl

    def untrack(self, tag: int) -> None:
        with self._lock:
            self.inflight.pop(tag, None)

    def take_all(self) -> list:
        with self._lock:
            out = list(self.inflight.values())
            self.inflight.clear()
        return out

    def add_streamer(self, t: threading.Thread) -> None:
        with self._lock:
            self.streamers = [s for s in self.streamers
                              if s.is_alive()] + [t]

    def live_streamers(self) -> list:
        with self._lock:
            return [s for s in self.streamers if s.is_alive()]


class ServeServer:
    """See module docstring.  One per engine session; ``shutdown()`` is
    idempotent and also fires when the engine session is collected."""

    def __init__(self, session, port: Optional[int] = None):
        import hashlib

        from spark_rapids_tpu import config as cfg
        global _RETAIN_CAP
        conf = session.conf
        self._engine_ref = weakref.ref(session)
        # semantics stamp: the engine session's result-affecting SQL
        # configuration participates in every result-cache key, so a
        # later session in the same process with different semantics
        # knobs (float-agg ordering, incompat ops, cast behavior…) can
        # never be served a result this session computed — the cache
        # itself is process-global.  Over-invalidation (a knob that
        # doesn't really change results) only costs a miss.
        sql_conf = sorted((k, repr(v)) for k, v in
                          conf._settings.items()
                          if k.startswith("spark.rapids.tpu.sql"))
        self._semantics_stamp = hashlib.sha1(
            repr(sql_conf).encode()).hexdigest()[:16]
        self._max_inflight = int(conf.get(cfg.SERVE_SESSION_MAX_INFLIGHT))
        self._idle_timeout_s = max(
            0.05, int(conf.get(cfg.SERVE_SESSION_IDLE_TIMEOUT_MS)) / 1e3)
        self._chunk_rows = max(
            1, int(conf.get(cfg.SERVE_STREAM_CHUNK_ROWS)))
        self._max_frame_bytes = max(
            1 << 10, int(conf.get(cfg.SERVE_WIRE_MAX_FRAME_BYTES)))
        self._read_timeout_s = max(
            0.05, int(conf.get(cfg.SERVE_WIRE_READ_TIMEOUT_MS)) / 1e3)
        self._write_stall_s = max(
            0.05, int(conf.get(cfg.SERVE_WIRE_WRITE_STALL_MS)) / 1e3)
        self._storm_threshold = max(
            1, int(conf.get(cfg.SERVE_WIRE_STORM_THRESHOLD)))
        self._drain_deadline_ms = max(
            0, int(conf.get(cfg.SERVE_DRAIN_DEADLINE_MS)))
        _RETAIN_CAP = max(0, int(conf.get(cfg.SERVE_STREAM_RETAIN_BYTES)))
        # seeded chaos plan for this server's lifetime (fresh=True:
        # a restarted server re-arms the same spec rather than
        # inheriting an exhausted schedule)
        serve_faults.install_plan_from_conf(conf, fresh=True)
        result_cache.configure(
            bool(conf.get(cfg.SERVE_RESULT_CACHE_ENABLED)),
            int(conf.get(cfg.SERVE_RESULT_CACHE_MAX_BYTES)))
        # incremental result maintenance (exec/incremental.py): delta
        # scans + retained aggregate partials over the result cache,
        # plus the background stamp-polling refresher
        from spark_rapids_tpu.exec.incremental import \
            IncrementalMaintainer
        self.maintainer = IncrementalMaintainer(session)
        # micro-batched prepared-statement dispatch (serve/batching.py);
        # None when serve.batch.enabled is off — the one-knob revert
        self._batcher = None
        if bool(conf.get(cfg.SERVE_BATCH_ENABLED)):
            from spark_rapids_tpu.serve.batching import StatementBatcher
            self._batcher = StatementBatcher(
                self, int(conf.get(cfg.SERVE_BATCH_WINDOW_MS)),
                int(conf.get(cfg.SERVE_BATCH_MAX_STATEMENTS)))
        # token auth: non-empty allowlist means every hello must carry
        # a matching auth_token or the connection gets a typed
        # AuthFailed ERR before any session exists
        self._auth_tokens = frozenset(
            t.strip() for t in
            str(conf.get(cfg.SERVE_AUTH_TOKENS) or "").split(",")
            if t.strip())
        # optional TLS: both PEM paths or neither — exactly one is a
        # misconfiguration that must not silently serve plaintext
        cert = str(conf.get(cfg.SERVE_TLS_CERT_FILE) or "").strip()
        key = str(conf.get(cfg.SERVE_TLS_KEY_FILE) or "").strip()
        self._ssl_ctx = None
        if bool(cert) != bool(key):
            raise ValueError(
                "serve.tls.certFile and serve.tls.keyFile must be set "
                "together (exactly one is set)")
        if cert:
            import ssl
            ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
            ctx.load_cert_chain(certfile=cert, keyfile=key)
            self._ssl_ctx = ctx
        # fleet store (attached by api/session.py when fleet.enabled):
        # prepared-statement specs publish here so ANY replica can
        # re-materialize a statement it never prepared — the router's
        # failover replay and cross-replica execute both lean on it
        self._store = getattr(session, "fleet_store", None)
        # statement ids carry a per-process nonce once a fleet store is
        # attached: two replicas both minting "stmt-00001" would alias
        # in the shared registry.  Storeless servers keep the legacy
        # format (the one-knob-revert byte-for-byte contract).
        self._stmt_nonce = os.urandom(3).hex() \
            if self._store is not None else ""
        self._sessions: Dict[str, ServeSession] = {}
        self._lock = threading.Lock()
        self._session_seq = itertools.count(1)
        self._stmt_seq = itertools.count(1)
        self._stop = threading.Event()
        self._draining = False
        self._drained = threading.Event()
        self._conns: set = set()
        self._conns_lock = threading.Lock()
        self._streamer_count = 0
        self._malformed = 0
        self._storm_dumped = False
        host = str(conf.get(cfg.SERVE_HOST))
        self._lsock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._lsock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        bind_port = int(port if port is not None
                        else conf.get(cfg.SERVE_PORT))
        self._lsock.bind((host, bind_port))
        self._lsock.listen(128)
        self.host = host
        self.port = self._lsock.getsockname()[1]
        reg = obsreg.get_registry()
        reg.set_gauge("serve.connections", 0)
        reg.set_gauge("serve.streamerThreads", 0)
        self._accept_thread = threading.Thread(
            target=self._accept_loop, name=f"serve-accept-{self.port}",
            daemon=True)
        self._accept_thread.start()
        self._janitor = threading.Thread(
            target=self._janitor_loop, name=f"serve-janitor-{self.port}",
            daemon=True)
        self._janitor.start()
        self._finalizer = weakref.finalize(
            session, ServeServer._static_shutdown, self._lsock,
            self._stop)

    # -- lifecycle ---------------------------------------------------------
    @staticmethod
    def _static_shutdown(lsock, stop) -> None:
        stop.set()
        # shutdown() before close(): a thread blocked in accept() holds
        # an in-syscall reference that keeps the LISTEN socket — and the
        # port — alive past close(); shutdown wakes it so a successor
        # can rebind immediately
        try:
            lsock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            lsock.close()
        except OSError:
            pass

    def shutdown(self) -> None:
        self._draining = True
        if self._batcher is not None:
            self._batcher.flush_all()
        self._static_shutdown(self._lsock, self._stop)
        self.maintainer.shutdown()
        with self._lock:
            sessions = list(self._sessions.values())
            self._sessions.clear()
        for s in sessions:
            s.force_close()
        # release the materialized results: the cache is process-global
        # and would otherwise pin up to its whole byte budget of
        # pa.Tables after the serving session is gone (the semantics
        # stamp already guarantees a later session can't be served
        # stale semantics; this is purely about memory).  The retained
        # stream window goes with it — full shutdown, unlike drain(),
        # means no process-local successor will answer a resume.
        result_cache.clear()
        clear_retained()
        reg = obsreg.get_registry()
        reg.set_gauge("serve.activeSessions", 0)
        reg.set_gauge("serve.connections", 0)
        reg.set_gauge("serve.streamerThreads", 0)

    def drain(self, deadline_ms: Optional[int] = None) -> Dict[str, Any]:
        """Graceful shutdown preserving resume state: stop accepting,
        refuse new work with a typed ``Draining`` error, let in-flight
        streams finish inside the deadline, cancel stragglers with a
        typed abort, join every streamer thread, release every
        admission slot and credit window, close every connection.
        Resume tokens, the retained-stream window and the result cache
        survive — a successor ``ServeServer`` on the same port (see
        ``session.restart_serve_server``) answers re-hellos and
        resume_stream requests as if the drain never happened."""
        if deadline_ms is None:
            deadline_ms = self._drain_deadline_ms
        already = self._draining
        self._draining = True
        if already and self._drained.is_set():
            return {"drained": True, "cancelled": 0, "already": True}
        reg = obsreg.get_registry()
        reg.inc("serve.drains")
        obsrec.record_event("serve.drainStarted", port=self.port,
                            deadline_ms=deadline_ms)
        # parked batch windows flush NOW: their items hold fair-share
        # slots the phase-1 wait below watches
        if self._batcher is not None:
            self._batcher.flush_all()
        # shutdown() wakes a blocked accept(); without it the accept
        # thread's in-syscall reference keeps the port bound and the
        # successor server's bind fails with EADDRINUSE
        try:
            self._lsock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            self._lsock.close()
        except OSError:
            pass
        self._accept_thread.join(timeout=2.0)
        # phase 1: wait for in-flight streams to finish on their own
        deadline = time.monotonic() + max(0, int(deadline_ms)) / 1e3
        while time.monotonic() < deadline:
            with self._conns_lock:
                busy = any(c.inflight for c in self._conns)
            if not busy:
                break
            time.sleep(0.02)
        # phase 2: cancel stragglers with the typed Draining abort (the
        # streamer's last act on a live socket is an ERR the client can
        # key its reconnect-and-resume on)
        with self._conns_lock:
            conns = list(self._conns)
        cancelled = 0
        for conn in conns:
            for infl in conn.take_all():
                infl.abort("Draining")
                if infl.future is not None:
                    infl.future.cancel("server draining")
                cancelled += 1
        # phase 3: leak-audited teardown — join every streamer before
        # declaring the drain done, so "zero streamer threads" is a
        # fact, not a hope
        for conn in conns:
            for t in conn.live_streamers():
                t.join(timeout=10.0)
        self._stop.set()
        for conn in conns:
            conn.alive = False
            conn.closed_cleanly = True
            try:
                conn.sock.close()
            except OSError:
                pass
        # reader threads unregister themselves on exit; wait for the
        # registry to empty so "drained" implies a clean leak audit
        # rather than racing the last thread's finally block
        conn_deadline = time.monotonic() + 2.0
        while time.monotonic() < conn_deadline:
            with self._conns_lock:
                if not self._conns:
                    break
            time.sleep(0.01)
        self._janitor.join(timeout=2.0)
        self.maintainer.shutdown()
        with self._lock:
            sessions = list(self._sessions.values())
            self._sessions.clear()
        for s in sessions:
            # closed for NEW work on this instance; the resume-token
            # registry (_register_resume at hello) lets a successor
            # re-mint an equivalent session
            s.force_close()
        reg.set_gauge("serve.activeSessions", 0)
        reg.set_gauge("serve.connections", 0)
        reg.set_gauge("serve.streamerThreads", 0)
        self._drained.set()
        obsrec.record_event("serve.drainFinished", port=self.port,
                            cancelled=cancelled)
        return {"drained": True, "cancelled": cancelled}

    def leak_stats(self) -> Dict[str, int]:
        """Live leak-audit counters (tests + the CI chaos gate assert
        these return to zero after drain)."""
        with self._conns_lock:
            conns = list(self._conns)
            streamers = self._streamer_count
        return {"connections": len(conns),
                "streamer_threads": streamers,
                "inflight": sum(len(c.inflight) for c in conns),
                "sessions": len(self.sessions()),
                "retained_streams": retained_stats()["entries"],
                "retained_bytes": retained_stats()["bytes"]}

    def state(self) -> str:
        """Lifecycle state for /healthz: ``serving`` → ``draining`` →
        ``drained``.  The fleet router polls this to take a replica
        out of placement rotation BEFORE it stops answering."""
        if self._drained.is_set():
            return "drained"
        if self._draining:
            return "draining"
        return "serving"

    def inflight_count(self) -> int:
        with self._conns_lock:
            return sum(len(c.inflight) for c in self._conns)

    def _engine(self):
        eng = self._engine_ref()
        if eng is None:
            raise ServeError("ServerStopping",
                             "engine session gone; server stopping")
        return eng

    # -- session registry --------------------------------------------------
    def sessions(self) -> Dict[str, ServeSession]:
        with self._lock:
            return dict(self._sessions)

    def _publish_sessions(self) -> None:
        obsreg.get_registry().set_gauge("serve.activeSessions",
                                        len(self._sessions))

    def _open_session(self, overlay: Dict[str, Any], addr: str,
                      resume_token: Optional[str] = None) -> ServeSession:
        sid = f"s-{next(self._session_seq):05d}"
        sess = ServeSession(sid, overlay or {}, self._max_inflight, addr,
                            resume_token=resume_token)
        with self._lock:
            self._sessions[sid] = sess
            self._publish_sessions()
        _register_resume(sess.resume_token, sess.overlay)
        reg = obsreg.get_registry()
        reg.inc("serve.sessions")
        obsrec.record_event("serve.sessionOpened", session=sid,
                            client_addr=addr,
                            resumed=resume_token is not None)
        return sess

    def _evict(self, sess: ServeSession, reason: str) -> None:
        with self._lock:
            cur = self._sessions.get(sess.session_id)
            if cur is not sess:
                return
            del self._sessions[sess.session_id]
            self._publish_sessions()
        sess.force_close()
        obsreg.get_registry().inc("serve.sessionsEvicted")
        obsrec.record_event("serve.sessionEvicted",
                            session=sess.session_id, reason=reason)

    def _janitor_loop(self) -> None:
        interval = min(2.0, max(0.02, self._idle_timeout_s / 4))
        while not self._stop.wait(interval):
            for sess in list(self.sessions().values()):
                # the close decision is atomic with slot admission
                # (ServeSession.try_close_if_idle), so a session with a
                # query still streaming is never torn down under it
                if sess.try_close_if_idle(self._idle_timeout_s):
                    self._evict(sess, "idle-timeout")

    # -- accept / per-connection reader ------------------------------------
    def _accept_loop(self) -> None:
        while not self._stop.is_set():
            try:
                sock, addr = self._lsock.accept()
            except OSError:
                return
            wire.set_low_latency(sock)
            ev = serve_faults.check("accept")
            if ev is not None:
                if ev.action is ServeFaultAction.CLOSE:
                    try:
                        sock.close()
                    except OSError:
                        pass
                    continue
                if ev.action is ServeFaultAction.DELAY:
                    time.sleep(ev.delay_s)
            threading.Thread(
                target=self._serve_conn,
                args=(sock, f"{addr[0]}:{addr[1]}"),
                name=f"serve-conn-{addr[1]}", daemon=True).start()

    def _register_conn(self, conn: _Conn) -> None:
        with self._conns_lock:
            self._conns.add(conn)
            obsreg.get_registry().set_gauge("serve.connections",
                                            len(self._conns))

    def _unregister_conn(self, conn: _Conn) -> None:
        with self._conns_lock:
            self._conns.discard(conn)
            obsreg.get_registry().set_gauge("serve.connections",
                                            len(self._conns))

    def _note_malformed(self, conn: _Conn, reason: str) -> None:
        reg = obsreg.get_registry()
        reg.inc("serve.wire.malformedFrames")
        reg.inc(f"serve.wire.malformedFrames.{reason}")
        obsrec.record_event("serve.malformedFrame", reason=reason,
                            client=conn.addr)
        with self._lock:
            self._malformed += 1
            storm = (self._malformed >= self._storm_threshold
                     and not self._storm_dumped)
            if storm:
                self._storm_dumped = True
        if storm:
            rec = obsrec.get_recorder()
            if rec is not None:
                try:
                    rec.dump_bundle(None, reason="protocol")
                except Exception:
                    pass

    def _serve_conn(self, sock: socket.socket, addr: str) -> None:
        if self._ssl_ctx is not None:
            # handshake on the per-connection thread (never the accept
            # loop — a stalled handshake must not block other accepts),
            # under the frame-progress deadline as its time bound
            try:
                sock.settimeout(self._read_timeout_s)
                sock = self._ssl_ctx.wrap_socket(sock, server_side=True)
            except (OSError, ValueError) as e:
                obsreg.get_registry().inc("serve.tlsHandshakeFailures")
                obsrec.record_event("serve.tlsHandshakeFailed",
                                    client=addr, error=str(e))
                try:
                    sock.close()
                except OSError:
                    pass
                return
        conn = _Conn(sock, addr)
        self._register_conn(conn)
        try:
            sock.settimeout(_TICK)
        except OSError:
            pass
        try:
            while not self._stop.is_set():
                try:
                    frame = wire.read_frame(
                        sock, max_frame_bytes=self._max_frame_bytes,
                        frame_timeout_s=self._read_timeout_s)
                except wire.ServeWireError as e:
                    if not conn.alive or self._stop.is_set():
                        return
                    self._note_malformed(conn, e.reason)
                    if e.reason in ("unknownKind", "badPayload"):
                        # frame boundary intact: answer and keep going
                        self._send_err(conn, getattr(e, "tag", 0),
                                       "ProtocolError", str(e),
                                       reason=e.reason)
                        continue
                    # oversized / truncated / timeout desync or kill
                    # the stream: best-effort typed ERR, then close
                    self._send_err(conn, 0, "ProtocolError", str(e),
                                   reason=e.reason)
                    return
                if frame is wire.IDLE:
                    continue
                if frame is None:
                    return
                kind, tag, payload = frame
                if kind == wire.CREDIT:
                    try:
                        msg = wire.decode_msg(payload)
                    except wire.ServeWireError as e:
                        self._note_malformed(conn, e.reason)
                        self._send_err(conn, tag, "ProtocolError",
                                       str(e), reason=e.reason)
                        continue
                    infl = conn.inflight.get(tag)
                    if infl is not None:
                        infl.add_credit(int(msg.get("n", 1)))
                elif kind == wire.REQ:
                    try:
                        msg = wire.decode_msg(payload)
                    except wire.ServeWireError as e:
                        self._note_malformed(conn, e.reason)
                        self._send_err(conn, tag, "ProtocolError",
                                       str(e), reason=e.reason)
                        continue
                    if not self._handle_request(conn, tag, msg):
                        return
                else:
                    # well-formed frame of a kind a client must never
                    # send (RESP/CHUNK/ERR/END): typed refusal, and the
                    # stream is still in sync so the connection lives
                    self._note_malformed(conn, "unknownKind")
                    self._send_err(conn, tag, "ProtocolError",
                                   f"unexpected frame kind {kind} "
                                   f"from client", reason="unknownKind")
        except wire.WireError:
            pass
        finally:
            self._on_disconnect(conn)
            self._unregister_conn(conn)
            try:
                sock.close()
            except OSError:
                pass

    def _on_disconnect(self, conn: _Conn) -> None:
        conn.alive = False
        pending = conn.take_all()
        for infl in pending:
            infl.abort()
            if infl.future is not None:
                infl.future.cancel("client disconnected")
        if conn.session is not None:
            conn.session.touch()
        if not conn.closed_cleanly:
            obsreg.get_registry().inc("serve.clientDisconnects")
            if pending:
                obsrec.record_event(
                    "serve.disconnectCancelled",
                    session=getattr(conn.session, "session_id", None),
                    cancelled=len(pending))

    # -- request dispatch --------------------------------------------------
    def _send_resp(self, conn: _Conn, tag: int,
                   obj: Dict[str, Any]) -> None:
        wire.send_frame(conn.sock, conn.wlock, wire.RESP, tag,
                        wire.encode_msg(obj),
                        stall_s=self._write_stall_s)

    def _send_err(self, conn: _Conn, tag: int, code: str, msg: str,
                  reason: Optional[str] = None) -> None:
        obj: Dict[str, Any] = {"type": code, "error": msg}
        if reason:
            obj["reason"] = reason
        try:
            wire.send_frame(conn.sock, conn.wlock, wire.ERR, tag,
                            wire.encode_msg(obj),
                            stall_s=self._write_stall_s)
        except wire.WireError:
            pass

    def _handle_request(self, conn: _Conn, tag: int,
                        msg: Dict[str, Any]) -> bool:
        """Dispatch one REQ; returns False when the connection should
        close (the ``close`` op)."""
        _RECEIPT.ns = time.perf_counter_ns()
        op = str(msg.get("op", ""))
        reg = obsreg.get_registry()
        reg.inc("serve.requests")
        try:
            if self._draining and op in ("hello", "sql", "prepare",
                                         "execute", "resume_stream"):
                raise ServeError(
                    "Draining",
                    "server is draining; reconnect and resume shortly")
            if op == "hello":
                self._handle_hello(conn, tag, msg)
                return True
            if op == "ping":
                self._send_resp(conn, tag, {"ok": True})
                return True
            if op == "close":
                conn.closed_cleanly = True
                if conn.session is not None and \
                        bool(msg.get("end_session", True)):
                    self._evict(conn.session, "client-close")
                self._send_resp(conn, tag, {"ok": True})
                return False
            sess = self._session_of(conn)
            sess.touch()
            if op == "sql":
                plan = self._parse(str(msg.get("sql", "")))
                self._start_query(conn, tag, sess, plan,
                                  int(msg.get("credit", 8)),
                                  stream_id=msg.get("stream_id"))
            elif op == "prepare":
                stmt = self._prepare(sess, msg)
                self._send_resp(conn, tag, stmt.describe())
            elif op == "execute":
                stmt = self._statement_of(sess, msg)
                if self._batcher is not None and \
                        self._batcher.offer(conn, tag, sess, stmt, msg):
                    pass   # parked in the batching window; flush answers
                else:
                    plan = stmt.bind(msg.get("params") or {})
                    self._start_query(conn, tag, sess, plan,
                                      int(msg.get("credit", 8)),
                                      stream_id=msg.get("stream_id"),
                                      template=stmt.sql)
            elif op == "resume_stream":
                self._start_resume(conn, tag, sess, msg)
            elif op == "finish_stream":
                released = _release_stream(
                    sess.resume_token, str(msg.get("stream_id", "")))
                self._send_resp(conn, tag, {"ok": True,
                                            "released": released})
            elif op == "close_statement":
                sid = str(msg.get("statement_id", ""))
                sess.statements.pop(sid, None)
                self._send_resp(conn, tag, {"ok": True})
            elif op == "cancel":
                target = int(msg.get("request", -1))
                infl = conn.inflight.get(target)
                cancelled = False
                if infl is not None:
                    infl.abort()
                    if infl.future is not None:
                        cancelled = infl.future.cancel(
                            "cancelled by client")
                self._send_resp(conn, tag, {"cancelled": cancelled})
            elif op == "session_info":
                self._send_resp(conn, tag, sess.describe())
            else:
                raise ServeError("UnknownOp",
                                 f"unknown request op {op!r}")
        except ServeError as e:
            self._send_err(conn, tag, e.code, str(e))
        except StatementError as e:
            self._send_err(conn, tag, "StatementError", str(e))
        except wire.WireError:
            raise
        except Exception as e:
            self._send_err(conn, tag, type(e).__name__, str(e))
        return True

    def _handle_hello(self, conn: _Conn, tag: int,
                      msg: Dict[str, Any]) -> None:
        if self._auth_tokens:
            presented = str(msg.get("auth_token") or "")
            if presented not in self._auth_tokens:
                obsreg.get_registry().inc("serve.authFailures")
                obsrec.record_event("serve.authFailed",
                                    client=conn.addr,
                                    presented=bool(presented))
                raise ServeError(
                    "AuthFailed",
                    "hello rejected: missing or unknown auth_token "
                    "(serve.auth.tokens)")
        token = str(msg.get("resume") or "") or None
        sess: Optional[ServeSession] = None
        resumed = False
        if token:
            with self._lock:
                for cand in self._sessions.values():
                    if cand.resume_token == token and not cand.closed:
                        sess = cand
                        break
            if sess is not None:
                resumed = True       # live re-attach: statements intact
            else:
                overlay = _resume_overlay(token)
                if overlay is not None:
                    # the original session is gone (evicted or drained)
                    # but the token is known: mint an equivalent session
                    # under the SAME token; the client replays prepared
                    # statements it still holds text for
                    sess = self._open_session(overlay, conn.addr,
                                              resume_token=token)
                    resumed = True
        if sess is None:
            sess = self._open_session(msg.get("conf") or {}, conn.addr)
        conn.session = sess
        sess.touch()
        self._send_resp(conn, tag, {
            "session_id": sess.session_id,
            "protocol": wire.PROTOCOL_VERSION,
            "engine": "spark-rapids-tpu",
            "resume_token": sess.resume_token,
            "resumed": resumed,
            "statements": sorted(sess.statements)})

    def _session_of(self, conn: _Conn) -> ServeSession:
        sess = conn.session
        if sess is None:
            raise ServeError("NoSession",
                             "send a hello request before queries")
        ev = serve_faults.check("session.lookup")
        if ev is not None and ev.action is ServeFaultAction.FAIL:
            raise ServeError(
                "SessionExpired",
                f"session {sess.session_id} lookup failed "
                f"(fault injection); re-hello with your resume token")
        if sess.closed or sess.session_id not in self.sessions():
            raise ServeError(
                "SessionExpired",
                f"session {sess.session_id} was evicted "
                f"(idle > {self._idle_timeout_s:.1f}s or closed); "
                f"send a new hello")
        return sess

    def _statement_of(self, sess: ServeSession,
                      msg: Dict[str, Any]) -> PreparedStatement:
        sid = str(msg.get("statement_id", ""))
        stmt = sess.statements.get(sid)
        if stmt is None and self._store is not None:
            stmt = self._statement_from_store(sess, sid)
        if stmt is None:
            raise ServeError("UnknownStatement",
                             f"no prepared statement {sid!r} in "
                             f"session {sess.session_id}")
        return stmt

    def _statement_from_store(self, sess: ServeSession,
                              sid: str) -> Optional[PreparedStatement]:
        """Re-materialize a statement a SIBLING replica prepared: the
        fleet's shared statement-template registry means an execute
        routed (or failed over) to a replica that never saw the prepare
        still resolves the id."""
        import json as _json
        if not sid:
            return None
        try:
            raw = self._store.get("stmt", sid)
            if raw is None:
                return None
            spec = _json.loads(raw.decode("utf-8"))
            stmt = PreparedStatement(sid, str(spec["sql"]),
                                     spec.get("declared_types") or {},
                                     self._engine().catalog)
        except Exception:
            return None
        sess.statements[sid] = stmt
        obsreg.get_registry().inc("serve.statementsAdopted")
        return stmt

    def _parse(self, sql: str):
        if not sql.strip():
            raise ServeError("EmptyStatement", "empty sql")
        from spark_rapids_tpu.sql import parse_sql
        return parse_sql(sql, self._engine().catalog)

    def _prepare(self, sess: ServeSession,
                 msg: Dict[str, Any]) -> PreparedStatement:
        sql = str(msg.get("sql", ""))
        if not sql.strip():
            raise ServeError("EmptyStatement", "empty sql")
        nonce = f"{self._stmt_nonce}-" if self._stmt_nonce else ""
        stmt_id = f"stmt-{nonce}{next(self._stmt_seq):05d}"
        stmt = PreparedStatement(stmt_id, sql, msg.get("params") or {},
                                 self._engine().catalog)
        sess.statements[stmt_id] = stmt
        obsreg.get_registry().inc("serve.statementsPrepared")
        if self._store is not None:
            import json as _json
            try:
                self._store.put("stmt", stmt_id, _json.dumps(
                    {"sql": stmt.sql,
                     "declared_types": dict(stmt.declared_types)}
                ).encode("utf-8"))
            except Exception:
                obsreg.get_registry().inc("fleet.store.errors")
        return stmt

    # -- query execution + streaming ---------------------------------------
    def _begin_or_raise(self, sess: ServeSession) -> None:
        state = sess.try_begin_query()
        if state == "closed":
            raise ServeError(
                "SessionExpired",
                f"session {sess.session_id} was closed; "
                f"re-hello with your resume token")
        if state != "ok":
            raise ServeError(
                "FairShareExceeded",
                f"session {sess.session_id} already has "
                f"{sess.max_inflight} queries in flight "
                f"(serve.session.maxInFlight)")

    def _spawn_streamer(self, conn: _Conn, tag: int, target,
                        args: tuple) -> None:
        with self._conns_lock:
            self._streamer_count += 1
            obsreg.get_registry().set_gauge("serve.streamerThreads",
                                            self._streamer_count)

        def run() -> None:
            try:
                target(*args)
            finally:
                with self._conns_lock:
                    self._streamer_count -= 1
                    obsreg.get_registry().set_gauge(
                        "serve.streamerThreads", self._streamer_count)

        t = threading.Thread(target=run, name=f"serve-stream-{tag}",
                             daemon=True)
        conn.add_streamer(t)
        t.start()

    def _start_query(self, conn: _Conn, tag: int, sess: ServeSession,
                     plan, credit: int,
                     stream_id: Optional[str] = None,
                     template: Optional[str] = None) -> None:
        self._begin_or_raise(sess)
        try:
            digest = cache_key = names = stamps = None
            cacheable = False
            fp_cacheable = False
            submit_plan, inc_ctx = plan, None
            try:
                from spark_rapids_tpu.exec import incremental
                from spark_rapids_tpu.plan.digest import plan_fingerprint
                fp = plan_fingerprint(plan)
                digest = fp.digest
                fp_cacheable = fp.cacheable
                # cache entries key on (semantics stamp, plan digest):
                # the profile//queries surface the pure digest, the
                # cache must also see the session's SQL conf
                cache_key = f"{self._semantics_stamp}:{fp.digest}"
                names = tuple(plan.schema.names)
                if fp.cacheable and result_cache.enabled():
                    # stamps come from the LIVE expansion of the scan's
                    # source roots (not the frozen read()-time file
                    # list) so a file appended to a watched dataset
                    # invalidates — and delta-refreshes — the entry
                    stamps = incremental.current_stamps(plan)
                    cacheable = stamps is not None
            except Exception:
                cacheable = False
            if cacheable:
                # miss counting is deferred to after submission: a miss
                # that joins an in-flight single-flight execution is a
                # dedup, not a second miss
                hit = result_cache.lookup(cache_key, names, stamps,
                                          count_miss=False)
                if hit is not None:
                    # ledger: a cache hit never passes the scheduler,
                    # so the tenant is charged directly (same name as
                    # the global counter result_cache.lookup bumped)
                    from spark_rapids_tpu.obs import accounting as acct
                    acct.charge_tenant(sess.session_id, template,
                                       digest,
                                       "serve.resultCacheHits", 1)
                    infl = _Inflight(tag, None, credit,
                                     template=template)
                    conn.track(infl)
                    self._spawn_streamer(
                        conn, tag, self._stream_cached,
                        (conn, sess, infl, hit, stream_id,
                         (cache_key, names, stamps)))
                    return
                # incremental maintenance decides full-capture vs delta
                # (and re-pins watched scans to the live file set so
                # the executed plan reads what the stamps describe)
                submit_plan, inc_ctx = self.maintainer.prepare(
                    plan, cache_key, names, stamps)
            eng = self._engine()
            meta = {"session_id": sess.session_id,
                    "client_addr": sess.client_addr}
            if template is not None:
                meta["statement_template"] = template
            if digest is not None:
                meta["plan_digest"] = digest  # already computed here
                meta["plan_cacheable"] = fp_cacheable
            if inc_ctx is not None and inc_ctx.mode == "delta":
                # a delta run merges retained partials in finish();
                # fanning one execution to two delta contexts would
                # double-merge — delta runs never join a flight
                meta["no_dedup"] = True
            fut = eng.scheduler.submit(
                submit_plan, priority=sess.priority,
                timeout_ms=sess.timeout_ms,
                estimate_bytes=sess.estimate_bytes,
                meta=meta)
            is_follower = getattr(fut, "dedup_of", None) is not None
            if not is_follower and getattr(fut, "_flight", None) \
                    is not None:
                # flight leader: install the chunk relay BEFORE the
                # streamer spawns, so every follower joining after this
                # point finds it (a follower racing this install just
                # takes the whole-result path — slower, never wrong)
                fut._flight.chunk_feed = _ChunkFeed()
            if cacheable:
                miss_name = ("serve.resultCacheDedupedFollowers"
                             if is_follower
                             else "serve.resultCacheMisses")
                obsreg.get_registry().inc(miss_name)
                from spark_rapids_tpu.obs import accounting as acct
                acct.charge_tenant(sess.session_id, template, digest,
                                   miss_name, 1)
            infl = _Inflight(tag, fut, credit, template=template)
            conn.track(infl)
            self._spawn_streamer(
                conn, tag, self._stream_result,
                (conn, sess, infl, cache_key, names, stamps,
                 cacheable and not is_follower, plan,
                 None if is_follower else inc_ctx, stream_id))
        except BaseException:
            sess.end_query()
            raise

    def _start_resume(self, conn: _Conn, tag: int, sess: ServeSession,
                      msg: Dict[str, Any]) -> None:
        stream_id = str(msg.get("stream_id", ""))
        after_seq = max(0, int(msg.get("after_seq", 0)))
        credit = int(msg.get("credit", 8))
        if not stream_id:
            raise ServeError("BadRequest",
                             "resume_stream requires stream_id")
        table = _lookup_stream(sess.resume_token, stream_id)
        if table is None:
            raise ServeError(
                "ResumeUnavailable",
                f"no retained stream {stream_id!r} for this session; "
                f"re-execute the original request")
        self._begin_or_raise(sess)
        reg = obsreg.get_registry()
        reg.inc("serve.resumedStreams")
        obsrec.record_event("serve.streamResumed",
                            session=sess.session_id,
                            stream_id=stream_id, after_seq=after_seq)
        infl = _Inflight(tag, None, credit)
        conn.track(infl)
        release = self._releaser(conn, sess, infl)

        def run() -> None:
            try:
                self._stream_table(conn, infl, table, cache_hit=True,
                                   query_id=None, release=release,
                                   after_seq=after_seq)
            finally:
                release()

        self._spawn_streamer(conn, tag, run, ())

    @staticmethod
    def _releaser(conn: _Conn, sess: ServeSession, infl: _Inflight):
        """Once-only release of the query's fair-share slot + in-flight
        tracking.  Called just BEFORE the END frame goes out (so a
        client that pipelines its next query the instant END arrives
        can never race a still-held slot into FairShareExceeded) and
        again from the streamer's finally as the error-path net."""
        done = threading.Event()

        def release() -> None:
            if not done.is_set():
                done.set()
                conn.untrack(infl.tag)
                sess.end_query()
        return release

    def _stream_cached(self, conn: _Conn, sess: ServeSession,
                       infl: _Inflight, table,
                       stream_id: Optional[str],
                       cache_ref: Optional[Tuple]) -> None:
        release = self._releaser(conn, sess, infl)
        try:
            # a cache-backed retention costs zero retained bytes: the
            # cache already pins the table, resume peeks it by key
            _retain_stream(sess.resume_token, stream_id,
                           cache_ref=cache_ref)
            self._stream_table(conn, infl, table, cache_hit=True,
                               query_id=None, release=release)
        finally:
            release()

    def _stream_result(self, conn: _Conn, sess: ServeSession,
                       infl: _Inflight, cache_key, names, stamps,
                       cacheable: bool, plan=None, inc_ctx=None,
                       stream_id: Optional[str] = None) -> None:
        fut = infl.future
        release = self._releaser(conn, sess, infl)
        reg = obsreg.get_registry()
        feed = fed = None
        fl = getattr(fut, "_flight", None)
        is_leader = fl is not None and \
            getattr(fut, "dedup_of", None) is None
        if fl is not None and not is_leader:
            # follower: subscribe per-chunk to the leader stream's feed.
            # Nothing is retained for resume while the feed streams — a
            # disconnect mid-feed resolves as ResumeUnavailable and the
            # client re-executes from last_seq (its sequence filter
            # keeps the replay duplicate-free), trading the rare
            # disconnect's cost for first-chunk latency that tracks the
            # leader chunk-for-chunk
            feed = fl.chunk_feed
        try:
            if feed is not None:
                reg.inc("serve.dedup.chunkFeedStreams")
                t_feed = time.perf_counter_ns()
                status, fed = self._stream_from_feed(conn, infl, feed,
                                                     fut.query_id,
                                                     release)
                if status in ("done", "dead"):
                    self._trace_request(infl, fut.query_id, t_feed)
                    return
                # leader stream died or stalled before finishing: fall
                # back to whole-result streaming off this follower's own
                # future, resuming after the chunks already sent
                reg.inc("serve.dedup.chunkFeedFallbacks")
            try:
                table = fut.result()
            except BaseException as e:
                # a live connection always gets a terminal frame (an
                # explicitly cancelled stream included — only a dead
                # socket goes unanswered), or the client would wait on
                # a stream that will never end.  A drain-cancelled
                # query reports the typed Draining code the client's
                # reconnect-and-resume keys on.
                if conn.alive:
                    self._send_err(conn, infl.tag,
                                   infl.abort_code or type(e).__name__,
                                   str(e))
                return
            if inc_ctx is not None:
                # the maintainer owns caching for maintained runs
                # (result + partial state under verified stamps) and
                # replaces a torn delta result with a full recompute
                try:
                    table = self.maintainer.finish(inc_ctx, table)
                except BaseException as e:
                    if inc_ctx.mode == "delta":
                        # a delta result whose stamp verification (or
                        # torn-result recompute) failed must never be
                        # streamed as if it were the full answer
                        if conn.alive:
                            self._send_err(conn, infl.tag,
                                           type(e).__name__, str(e))
                        return
                    # capture-mode maintenance is bookkeeping only: the
                    # computed table itself is the plain full result
            elif cacheable:
                # only freeze the result when the sources still carry
                # the pre-execution stamps: a file rewritten mid-query
                # must not cache a half-old result under either stamp
                from spark_rapids_tpu.exec import incremental
                try:
                    post = incremental.current_stamps(plan) \
                        if plan is not None else None
                except Exception:
                    post = None
                if post is not None and post == stamps:
                    result_cache.insert(cache_key, names, stamps,
                                        table)
            # retain the materialized result for resume BEFORE the
            # first chunk goes out: a drain or disconnect at any point
            # of the stream finds the replay source already in place
            _retain_stream(sess.resume_token, stream_id, table=table)
            self._stream_table(conn, infl, table, cache_hit=False,
                               query_id=fut.query_id, release=release,
                               after_seq=fed or 0,
                               observe_first=not fed,
                               feed=fl.chunk_feed if is_leader
                               and fl.had_followers else None)
        finally:
            if is_leader and fl.chunk_feed is not None:
                # error-path net: no-op when the stream finished cleanly
                fl.chunk_feed.abort()
            release()
            # a query that failed streamed nothing: its tree still
            # gets its root (no-op after ``_stream_table``)
            self._trace_request(infl, fut.query_id)

    @staticmethod
    def _trace_request(infl: _Inflight, query_id,
                       t_stream: Optional[int] = None) -> None:
        """The serve layer's two spans of one request, once: the
        ``serve.request`` root of the query's span tree (request
        receipt to the END frame) and, where chunks went out,
        ``serve.stream`` (chunk encode and send, ``t_stream`` to now).
        A request that ran no query (result-cache hit, resumed stream)
        has both with no query id."""
        if infl.traced or not obstrace.is_enabled():
            return
        infl.traced = True
        now = time.perf_counter_ns()
        # no span is open and no token installed on a streamer thread:
        # ``serve.stream`` hangs under the query's root, or under none
        if t_stream is not None:
            obstrace.record("serve.stream", t_stream, now - t_stream,
                            cat="serve", query=query_id)
        if query_id is None:
            obstrace.record("serve.request", infl.req_ns,
                            now - infl.req_ns, cat="serve")
        else:
            obstrace.record_root("serve.request", infl.req_ns,
                                 now - infl.req_ns, query_id,
                                 cat="serve")

    def _stream_from_feed(self, conn: _Conn, infl: _Inflight,
                          feed: _ChunkFeed, query_id, release
                          ) -> Tuple[str, int]:
        """Stream a follower's response straight off the leader flight's
        encoded-chunk feed (sends END itself on success).  Returns
        ``('done', n)`` after a complete stream, ``('dead', n)`` when
        this follower's connection/credit is gone, ``('abort', n)`` when
        the LEADER's stream died or stalled — the caller falls back to
        whole-result streaming with ``after_seq=n``."""
        from spark_rapids_tpu.obs import accounting as acct
        reg = obsreg.get_registry()
        sent = 0
        try:
            while True:
                kind, payload = feed.next(sent)
                if kind == "abort":
                    return "abort", sent
                if kind == "done":
                    break
                if not conn.alive or not infl.take_credit():
                    if conn.alive:
                        code = infl.abort_code or "StreamAborted"
                        self._send_err(
                            conn, infl.tag, code,
                            "server draining; reconnect and resume"
                            if code == "Draining"
                            else "stream cancelled or stalled")
                    return "dead", sent
                wire.send_frame(conn.sock, conn.wlock, wire.CHUNK,
                                infl.tag,
                                wire.encode_chunk(sent + 1, payload),
                                stall_s=self._write_stall_s)
                sent += 1
                if sent == 1:
                    acct.observe_slo(
                        "slo.firstChunkMs",
                        (time.monotonic_ns() - infl.t0_ns) / 1e6,
                        template=infl.template)
                reg.inc_many(("serve.streamedBatches", 1),
                             ("serve.dedup.fedChunks", 1))
            if conn.alive and not infl.aborted:
                release()
                wire.send_frame(
                    conn.sock, conn.wlock, wire.END, infl.tag,
                    wire.encode_msg({"rows": feed.rows,
                                     "chunks": sent,
                                     "cache_hit": False,
                                     "query_id": query_id,
                                     "last_seq": feed.total}),
                    stall_s=self._write_stall_s)
                acct.observe_slo(
                    "slo.latencyMs",
                    (time.monotonic_ns() - infl.t0_ns) / 1e6,
                    template=infl.template)
            return "done", sent
        except wire.ServeWireError as e:
            if e.reason == "writeStall":
                reg.inc("serve.wire.writeStalls")
                obsrec.record_event("serve.writeStall",
                                    client=conn.addr, tag=infl.tag)
            infl.abort()
            try:
                conn.sock.close()
            except OSError:
                pass
            return "dead", sent
        except wire.WireError:
            infl.abort()
            try:
                conn.sock.close()
            except OSError:
                pass
            return "dead", sent

    def _stream_table(self, conn: _Conn, infl: _Inflight, table,
                      cache_hit: bool, query_id, release,
                      after_seq: int = 0, observe_first: bool = True,
                      feed: Optional[_ChunkFeed] = None) -> None:
        reg = obsreg.get_registry()
        t_stream = time.perf_counter_ns()
        chunks = wire.table_chunks(table, self._chunk_rows)
        total = max(1, math.ceil(max(1, table.num_rows)
                                 / self._chunk_rows))
        sent = 0
        seq = 0
        try:
            for payload in chunks:
                seq += 1
                if feed is not None:
                    # relay the encoded payload to flight followers
                    # BEFORE this stream's own credit/fault gates: a
                    # stalled leader client must not hold back chunks
                    # already paid for
                    feed.publish(payload)
                if seq <= after_seq:
                    # resume replay: chunks the client already acked
                    # are skipped, never re-sent — duplicate-freedom
                    # is by sequence number, not client-side dedupe
                    continue
                if not conn.alive or not infl.take_credit():
                    if conn.alive:
                        # aborted mid-stream (explicit cancel, drain,
                        # or credit stall) on a live connection:
                        # terminate the client's stream explicitly
                        code = infl.abort_code or "StreamAborted"
                        self._send_err(
                            conn, infl.tag, code,
                            "server draining; reconnect and resume"
                            if code == "Draining"
                            else "stream cancelled or stalled")
                    return
                ev = serve_faults.check("stream.chunk")
                if ev is not None:
                    if ev.action is ServeFaultAction.DROP:
                        # the client sees a sequence hole and resumes
                        continue
                    if ev.action is ServeFaultAction.CLOSE:
                        try:
                            conn.sock.close()
                        except OSError:
                            pass
                        infl.abort()
                        return
                    if ev.action in (ServeFaultAction.DELAY,
                                     ServeFaultAction.SLOW):
                        # SLOW on the server streamer = a degraded
                        # chunk send (the sentinel probe's latency
                        # injection); DELAY keeps its one-shot stall
                        time.sleep(ev.delay_s)
                wire.send_frame(conn.sock, conn.wlock, wire.CHUNK,
                                infl.tag, wire.encode_chunk(seq, payload),
                                stall_s=self._write_stall_s)
                sent += 1
                if sent == 1 and observe_first:
                    from spark_rapids_tpu.obs import accounting as acct
                    acct.observe_slo(
                        "slo.firstChunkMs",
                        (time.monotonic_ns() - infl.t0_ns) / 1e6,
                        template=infl.template)
                reg.inc("serve.streamedBatches")
            if feed is not None:
                feed.finish(table.num_rows, total)
            if conn.alive and not infl.aborted:
                release()
                wire.send_frame(
                    conn.sock, conn.wlock, wire.END, infl.tag,
                    wire.encode_msg({"rows": table.num_rows,
                                     "chunks": sent,
                                     "cache_hit": cache_hit,
                                     "query_id": query_id,
                                     "last_seq": total}),
                    stall_s=self._write_stall_s)
                # serve-side e2e: request receipt -> END frame (the
                # sched layer skips serve-attributed queries, so one
                # observation per request, never two)
                from spark_rapids_tpu.obs import accounting as acct
                acct.observe_slo(
                    "slo.latencyMs",
                    (time.monotonic_ns() - infl.t0_ns) / 1e6,
                    template=infl.template)
        except wire.ServeWireError as e:
            # a write stall is the peer's fault, and the partial frame
            # desynced the stream: typed counter, abort, close
            if e.reason == "writeStall":
                reg.inc("serve.wire.writeStalls")
                obsrec.record_event("serve.writeStall",
                                    client=conn.addr, tag=infl.tag)
            infl.abort()
            try:
                conn.sock.close()
            except OSError:
                pass
        except wire.WireError:
            infl.abort()
        finally:
            self._trace_request(infl, query_id, t_stream)

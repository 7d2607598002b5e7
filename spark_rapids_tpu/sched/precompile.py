"""Background AOT precompile service: replay the compile corpus off
the serving path.

The compile observatory (obs/compile.py) appends one JSONL record per
distinct plan digest to ``obs.compile.corpusPath``, and — with
``obs.compile.corpusReplay`` on — each program record carries a replay
payload: the pickled traceable, its jit kwargs, and the abstract
argument shapes (``jax.ShapeDtypeStruct`` leaves) of the exact program
the serving path compiled.  This service walks that corpus in a fresh
process and re-lowers + re-compiles every payload through jax's AOT
API:

  * programs already in the persistent XLA compilation cache RELOAD —
    the "warm compile" cost (613 s for the full TPC-DS-99 suite,
    PERF.md) is paid HERE, on a background thread, instead of on the
    first queries a restarted replica serves;
  * programs missing from the cache compile fresh and are WRITTEN, so
    a corpus alone can warm an empty cache for a brand-new replica.

Low-priority contract: between programs the service sleeps
``sched.precompile.idleWaitMs`` and, whenever the scheduler has live
(queued or running) queries, it pauses until the queue drains — replay
never competes with serving for the compile threads or the device.

What replay does NOT do: it does not touch the in-process kernel cache
(exec/kernel_cache) — the serving path still traces each kernel on
first use, but that trace's compile classifies ``persistent`` (a cache
read, milliseconds) instead of ``fresh`` (the CI corpus-replay gate
asserts exactly this on ``/compiles``).

Registry counters: ``sched.precompile.plans`` / ``.programs`` /
``.warmed`` / ``.skipped`` (no payload) / ``.failed`` / ``.dedup``.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

from spark_rapids_tpu.obs import recorder as obsrec
from spark_rapids_tpu.obs import registry as obsreg


class PrecompileService:
    """Replays a precompile corpus JSONL (see module docstring).

    ``start()`` launches the replay on a daemon thread (the session
    init path); ``replay()`` runs it synchronously (tests, CI gates);
    ``wait(timeout)`` blocks until the background replay finishes."""

    def __init__(self, session, corpus_path: str,
                 idle_wait_ms: int = 25):
        self._session = session
        self.corpus_path = str(corpus_path or "")
        self.idle_wait_s = max(0, int(idle_wait_ms)) / 1e3
        self._done = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._stop = False
        self._stats = {"plans": 0, "programs": 0, "warmed": 0,
                       "skipped": 0, "failed": 0, "dedup": 0,
                       "wall_s": 0.0}
        self._lock = threading.Lock()

    # -- lifecycle ----------------------------------------------------------
    def start(self) -> None:
        if self._thread is not None:
            return
        self._thread = threading.Thread(
            target=self._run, name="sched-precompile", daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop = True

    def wait(self, timeout: Optional[float] = None) -> bool:
        """Block until the background replay finishes (True) or the
        timeout elapses (False).  Synchronous ``replay()`` callers
        don't need this."""
        return self._done.wait(timeout)

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return dict(self._stats)

    # -- replay -------------------------------------------------------------
    def _run(self) -> None:
        try:
            self.replay()
        finally:
            self._done.set()

    def _busy(self) -> bool:
        """Live (queued or running) queries in this session's
        scheduler — the signal replay yields to."""
        try:
            return self._session._query_service.has_live_queries()
        except Exception:
            return False

    def _yield_to_serving(self) -> None:
        while not self._stop and self._busy():
            time.sleep(self.idle_wait_s or 0.005)

    def replay(self) -> Dict[str, Any]:
        """Walk the corpus once, lower+compile every replayable
        program (deduplicated on (key, signature) across records).
        Returns the stats dict; never raises on per-program failures
        (counted as ``failed``)."""
        from spark_rapids_tpu.exec import kernel_cache as kc
        t0 = time.perf_counter()
        reg = obsreg.get_registry()
        seen = set()
        records = []
        # a DIRECTORY corpus replays every *.jsonl inside it — the
        # fleet warm-join shape, where each replica appends its own
        # corpus file under the shared store's corpus/ dir and the
        # (key, signature) dedup below collapses the overlap
        paths: List[str] = []
        if os.path.isdir(self.corpus_path):
            try:
                paths = sorted(
                    os.path.join(self.corpus_path, n)
                    for n in os.listdir(self.corpus_path)
                    if n.endswith(".jsonl"))
            except OSError:
                paths = []
        elif self.corpus_path:
            paths = [self.corpus_path]
        for path in paths:
            try:
                with open(path) as f:
                    for line in f:
                        line = line.strip()
                        if not line:
                            continue
                        try:
                            records.append(json.loads(line))
                        except Exception:
                            continue      # torn tail line: skip
            except OSError:
                continue
        obsrec.record_event("precompile.start",
                            corpus=self.corpus_path,
                            plans=len(records))
        for rec in records:
            if self._stop:
                break
            with self._lock:
                self._stats["plans"] += 1
            reg.inc("sched.precompile.plans")
            for prog in rec.get("programs") or []:
                if self._stop:
                    break
                dedup = (prog.get("key"), prog.get("signature"))
                if dedup in seen:
                    with self._lock:
                        self._stats["dedup"] += 1
                    reg.inc("sched.precompile.dedup")
                    continue
                seen.add(dedup)
                with self._lock:
                    self._stats["programs"] += 1
                reg.inc("sched.precompile.programs")
                payload = prog.get("replay")
                if not payload:
                    with self._lock:
                        self._stats["skipped"] += 1
                    reg.inc("sched.precompile.skipped")
                    continue
                self._yield_to_serving()
                try:
                    spec = kc.load_replay_payload(payload)
                    jitted = kc.jit_replayed(spec)
                    jitted.lower(*spec["args"],
                                 **(spec["kwargs"] or {})).compile()
                    with self._lock:
                        self._stats["warmed"] += 1
                    reg.inc("sched.precompile.warmed")
                except Exception:
                    with self._lock:
                        self._stats["failed"] += 1
                    reg.inc("sched.precompile.failed")
                if self.idle_wait_s:
                    time.sleep(self.idle_wait_s)
        with self._lock:
            self._stats["wall_s"] = round(time.perf_counter() - t0, 3)
            stats = dict(self._stats)
        obsrec.record_event("precompile.done", **stats)
        return stats

"""TpuSparkSession: the engine entry point.

Plays the combined role of SparkSession + the reference's plugin bootstrap
(reference: SQLPlugin.scala:28-31, Plugin.scala:111-212): holds the conf,
initializes the device and concurrency semaphore, plans queries, and applies
the TPU overrides.
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import os
import threading
from typing import Any, Dict, List, Optional, Sequence, Union

import pyarrow as pa

from spark_rapids_tpu import config as cfg
from spark_rapids_tpu.config import RapidsTpuConf
from spark_rapids_tpu.api.dataframe import DataFrame
from spark_rapids_tpu.exec.base import PhysicalPlan
from spark_rapids_tpu.exec.cpu import concat_tables
from spark_rapids_tpu.mem import device as devmgr
from spark_rapids_tpu.plan import logical as lp
from spark_rapids_tpu.plan.overrides import (OverrideResult, TpuOverrides,
                                             assert_is_on_tpu)
from spark_rapids_tpu.plan.planner import plan_cpu


class TpuSparkSession:
    _active: Optional["TpuSparkSession"] = None
    _lock = threading.Lock()
    # shared across sessions — see the note at self._query_ids
    _QUERY_IDS = itertools.count(1)

    def __init__(self, conf: Optional[Dict[str, Any]] = None):
        self.conf = RapidsTpuConf(conf)
        from spark_rapids_tpu.exec import placement
        mesh = placement.mesh_devices(self.conf)
        devmgr.initialize(self.conf.get(cfg.CONCURRENT_TPU_TASKS),
                          chips=len(mesh), device_ids=[d.id for d in mesh])
        # -- fleet shared cache plane (fleet/store.py): attach BEFORE
        # the compile cache and compile observatory configure, so the
        # shared compile-cache directory and corpus directory take
        # effect for this whole session.  fleet.enabled=false (default)
        # leaves every downstream path byte-for-byte unchanged.
        self._fleet_store = None
        if self.conf.get(cfg.FLEET_ENABLED):
            from spark_rapids_tpu.fleet.store import store_from_url
            self._fleet_store = store_from_url(
                str(self.conf.get(cfg.FLEET_STORE_URL) or ""))
            from spark_rapids_tpu.serve import result_cache as _rc
            _rc.configure_store(
                self._fleet_store,
                int(self.conf.get(cfg.FLEET_STORE_MAX_ENTRY_BYTES)))
            corpus_dir = self._fleet_store.corpus_dir()
            if corpus_dir and not str(self.conf.get(
                    cfg.OBS_COMPILE_CORPUS_PATH) or ""):
                # each replica appends its OWN corpus file under the
                # shared corpus/ dir; a joining replica replays the
                # whole directory (sched/precompile.py)
                self.conf.set(
                    cfg.OBS_COMPILE_CORPUS_PATH.key,
                    os.path.join(corpus_dir,
                                 f"corpus-{os.getpid()}.jsonl"))
        import spark_rapids_tpu as _pkg
        _pkg._enable_compile_cache(
            self._fleet_store.compile_cache_dir()
            if self._fleet_store is not None else None)
        from spark_rapids_tpu.mem import spill
        if self.conf.get(cfg.MEM_SPILL_ENABLED):
            spill.init_catalog(
                self.conf.get(cfg.MEM_DEVICE_LIMIT),
                self.conf.get(cfg.MEM_HOST_SPILL_LIMIT),
                self.conf.get(cfg.MEM_SPILL_DIR) or None)
        else:
            spill.disable_catalog()
        from spark_rapids_tpu.io import scan_cache
        scan_cache.configure(
            self.conf.get(cfg.SCAN_METADATA_CACHE_ENABLED),
            self.conf.get(cfg.SCAN_METADATA_CACHE_MAX_BYTES))
        from spark_rapids_tpu.exec import kernel_abi
        kernel_abi.configure(self.conf)
        from spark_rapids_tpu.pyworker import pool as pyworker_pool
        pyworker_pool.configure(self.conf)
        from spark_rapids_tpu.shuffle import faults
        faults.install_plan_from_conf(self.conf, fresh=True)
        from spark_rapids_tpu.obs import trace as obs_trace
        obs_trace.configure(
            bool(self.conf.get(cfg.OBS_TRACE_ENABLED)),
            int(self.conf.get(cfg.OBS_TRACE_BUFFER_SPANS)))
        from spark_rapids_tpu.obs import compile as obs_compile
        obs_compile.configure(
            bool(self.conf.get(cfg.OBS_COMPILE_ENABLED)),
            ring_events=int(self.conf.get(cfg.OBS_COMPILE_RING_EVENTS)),
            storm_threshold=int(self.conf.get(
                cfg.OBS_COMPILE_STORM_THRESHOLD)),
            corpus_path=str(self.conf.get(
                cfg.OBS_COMPILE_CORPUS_PATH) or ""),
            corpus_replay=bool(self.conf.get(
                cfg.OBS_COMPILE_CORPUS_REPLAY)))
        from spark_rapids_tpu.obs import accounting as obs_accounting
        obs_accounting.configure(
            bool(self.conf.get(cfg.OBS_ACCOUNTING_ENABLED)))
        with TpuSparkSession._lock:
            TpuSparkSession._active = self
        self._plan_listeners: List = []
        self._query_listeners: List = []
        self._views: Dict[str, lp.LogicalPlan] = {}
        # PROCESS-global query ids (class attribute): the compile
        # observatory, profile ring and /queries table key on qid, and
        # per-session counters made two sessions' query 1 collide in
        # the observatory's per-query attribution — session 2's corpus
        # record inherited session 1's programs (found by
        # tests/test_precompile.py's corpusReplay-knob test)
        self._query_ids = TpuSparkSession._QUERY_IDS
        # per-query profiles: bounded ring keyed by query id, plus the
        # most recently COMPLETED one — concurrent collects no longer
        # race a single last-profile slot
        self._profile_lock = threading.Lock()
        self._profiles: "collections.OrderedDict[int, Any]" = \
            collections.OrderedDict()
        self._profile_ring = max(1, int(self.conf.get(
            cfg.SCHED_PROFILE_RING)))
        self._last_profile = None
        from spark_rapids_tpu.sched.service import QueryService
        self._query_service = QueryService(self)
        # -- always-on operational layer (obs/server.py, obs/recorder.py):
        # both fully off by default — no socket, no recorder ring, the
        # event hooks cost one bool check
        from spark_rapids_tpu.obs import recorder as obs_recorder
        self._recorder = None
        rec_dir = str(self.conf.get(cfg.OBS_RECORDER_DIR) or "")
        if rec_dir:
            # configuring REPLACES any previous session's recorder
            # (whose listener then stands down via _stale()); a session
            # with no recorder dir leaves an existing recorder alone —
            # helper sessions (bench oracles, tests) must not disarm a
            # live sibling's flight recorder
            self._recorder = obs_recorder.configure(
                rec_dir,
                max_events=int(self.conf.get(
                    cfg.OBS_RECORDER_MAX_EVENTS)),
                config_snapshot=dict(self.conf._settings))
            self._query_listeners.append(self._recorder)
        if not self.conf.get(cfg.OBS_PROFILE_ENABLED) and (
                rec_dir or int(self.conf.get(cfg.OBS_SLOW_QUERY_MS))):
            # both features ride the QueryProfile assembly path; with
            # profiling off they would be silently inert
            import logging
            logging.getLogger("spark_rapids_tpu.obs").warning(
                "obs.recorder.dir / obs.slowQueryMs are configured but "
                "obs.profile.enabled=false: flight-recorder bundles "
                "and the slow-query log require per-query profiles "
                "and will not fire")
        self._obs_server = None
        if self.conf.get(cfg.OBS_HTTP_ENABLED):
            from spark_rapids_tpu.obs.server import ObsHttpServer
            self._obs_server = ObsHttpServer(
                self, host=str(self.conf.get(cfg.OBS_HTTP_HOST)),
                port=int(self.conf.get(cfg.OBS_HTTP_PORT)))
        # -- multi-tenant serving front-end (serve/server.py): off by
        # default — no socket, no threads, no result-cache mutation
        self._serve_server = None
        if self.conf.get(cfg.SERVE_ENABLED):
            from spark_rapids_tpu.serve.server import ServeServer
            self._serve_server = ServeServer(self)
        # -- AOT precompile service (sched/precompile.py): off by
        # default — replays a previous process's compile corpus through
        # lower+compile at low priority so a replica restart warms the
        # persistent XLA cache off the serving path
        # -- drift sentinel (obs/sentinel.py): off by default — no
        # thread runs; on, it samples the registry on an interval and
        # emits one "slo" bundle per sustained-breach episode
        self._sentinel = None
        if self.conf.get(cfg.OBS_SENTINEL_ENABLED):
            from spark_rapids_tpu.obs.sentinel import DriftSentinel
            self._sentinel = DriftSentinel(
                interval_ms=int(self.conf.get(
                    cfg.OBS_SENTINEL_INTERVAL_MS)),
                rules=str(self.conf.get(cfg.OBS_SENTINEL_RULES) or ""),
                jsonl_path=str(self.conf.get(
                    cfg.OBS_SENTINEL_PATH) or ""),
                jsonl_max_bytes=int(self.conf.get(
                    cfg.OBS_SLOW_QUERY_MAX_BYTES)))
            self._sentinel.start()
        self._precompile_service = None
        if self.conf.get(cfg.SCHED_PRECOMPILE_ENABLED):
            from spark_rapids_tpu.sched.precompile import \
                PrecompileService
            corpus = (str(self.conf.get(
                cfg.SCHED_PRECOMPILE_CORPUS_PATH) or "") or
                str(self.conf.get(cfg.OBS_COMPILE_CORPUS_PATH) or ""))
            if self._fleet_store is not None:
                # warm-join: replay the WHOLE shared corpus directory
                # (every replica's appends), not just this replica's
                # own emission file
                shared = self._fleet_store.corpus_dir()
                if shared and not str(self.conf.get(
                        cfg.SCHED_PRECOMPILE_CORPUS_PATH) or ""):
                    corpus = shared
            self._precompile_service = PrecompileService(
                self, corpus,
                idle_wait_ms=int(self.conf.get(
                    cfg.SCHED_PRECOMPILE_IDLE_WAIT_MS)))
            self._precompile_service.start()

    # -- builder-compatible construction -----------------------------------
    class Builder:
        def __init__(self):
            self._conf: Dict[str, Any] = {}

        def config(self, key: str, value: Any) -> "TpuSparkSession.Builder":
            self._conf[key] = value
            return self

        def getOrCreate(self) -> "TpuSparkSession":
            return TpuSparkSession(self._conf)

        get_or_create = getOrCreate

    builder = Builder()

    @classmethod
    def active(cls) -> "TpuSparkSession":
        if cls._active is None:
            cls._active = TpuSparkSession()
        return cls._active

    # -- conf --------------------------------------------------------------
    def set_conf(self, key: str, value: Any) -> None:
        self.conf.set(key, value)

    def get_conf(self, key: str, default: Any = None) -> Any:
        return self.conf.get_raw(key, default)

    # -- data sources ------------------------------------------------------
    def create_dataframe(self, data, schema: Optional[Sequence[str]] = None,
                         num_partitions: int = 1) -> DataFrame:
        if isinstance(data, pa.Table):
            table = data
        elif hasattr(data, "to_dict") and hasattr(data, "columns"):
            table = pa.Table.from_pandas(data)  # pandas DataFrame
        elif isinstance(data, dict):
            table = pa.table(data)
        elif isinstance(data, list):
            if schema is None:
                raise ValueError("schema (column names) required for lists")
            cols = list(zip(*data)) if data else [[] for _ in schema]
            table = pa.table({n: list(c) for n, c in zip(schema, cols)})
        else:
            raise TypeError(f"cannot create DataFrame from {type(data)}")
        return DataFrame(lp.InMemoryScan(table, num_partitions), self)

    createDataFrame = create_dataframe

    def range(self, start: int, end: Optional[int] = None, step: int = 1,
              num_partitions: int = 1) -> DataFrame:
        if end is None:
            start, end = 0, start
        return DataFrame(lp.Range(start, end, step, num_partitions), self)

    @property
    def read(self) -> "DataFrameReader":
        return DataFrameReader(self)

    # -- SQL ---------------------------------------------------------------
    def sql(self, query: str) -> DataFrame:
        """Parse and plan a SQL query against registered temp views
        (the ``spark.sql(...)`` surface; in the reference Spark's own
        parser runs and the plugin only sees physical plans)."""
        from spark_rapids_tpu.sql import parse_sql
        return DataFrame(parse_sql(query, self._views), self)

    def register_view(self, name: str, df: DataFrame) -> None:
        self._views[name.lower()] = df.plan

    def drop_view(self, name: str) -> None:
        self._views.pop(name.lower(), None)

    @property
    def catalog(self) -> Dict[str, lp.LogicalPlan]:
        return dict(self._views)

    # -- planning & execution ----------------------------------------------
    def _plan_physical(self, plan: lp.LogicalPlan) -> OverrideResult:
        from spark_rapids_tpu.plan import optimizer
        plan = optimizer.rewrite_implicit_joins(plan)
        if self.conf.get(cfg.COLUMN_PRUNING):
            plan = optimizer.prune_columns(plan)
        plan = optimizer.mark_equal_aggregates(plan)
        cpu_plan = plan_cpu(plan, self.conf)
        result = TpuOverrides.apply(cpu_plan, self.conf)
        if self.conf.test_enabled:
            assert_is_on_tpu(result.plan, self.conf.test_allowed_non_tpu)
        for listener in self._plan_listeners:
            listener(result)
        return result

    def _drain_partitions(self, its) -> List:
        """Drain partition iterators, one task per partition on a thread
        pool sized by ``concurrentTpuTasks`` (the Spark task model:
        executor task slots gated by GpuSemaphore, reference:
        GpuSemaphore.scala:101-135).  Output preserves partition order.
        """
        n_tasks = int(self.conf.get(cfg.CONCURRENT_TPU_TASKS))
        if devmgr.chips() > 1 and len(its) > 1:
            # a mesh of several chips: partition p is chip p % n_dev's
            # task, the slots count a chip (exec/placement)
            from spark_rapids_tpu.exec.placement import drain_by_chip
            parts: List[List] = [[] for _ in its]
            drain_by_chip(its, lambda p, b: parts[p].append(b),
                          stage="collect")
            return [x for part in parts for x in part]
        if len(its) <= 1 or n_tasks <= 1:
            out: List = []
            for it in its:
                out.extend(it)
            return out
        from concurrent.futures import ThreadPoolExecutor
        from spark_rapids_tpu.sched import cancel as sched_cancel
        tok = sched_cancel.current()

        def drain(it):
            # task threads inherit the query's CancelToken explicitly
            # (pool threads don't propagate thread-locals)
            with sched_cancel.install(tok):
                return list(it)
        with ThreadPoolExecutor(
                max_workers=min(n_tasks, len(its)),
                thread_name_prefix="tpu-task") as pool:
            parts = list(pool.map(drain, its))
        return [x for p in parts for x in p]

    # -- scheduler surface ---------------------------------------------------
    def _next_query_id(self) -> int:
        return next(self._query_ids)

    @property
    def scheduler(self):
        """The session's QueryService (sched/service.py): admission
        stats, controller, estimate book."""
        return self._query_service

    def submit(self, df_or_plan, priority: int = 0,
               timeout_ms: Optional[int] = None,
               estimate_bytes: Optional[int] = None):
        """Submit a query for asynchronous execution; returns a
        QueryFuture (result/cancel/done, profile attached on
        completion).  Accepts a DataFrame or a logical plan.  Higher
        ``priority`` admits first; ``timeout_ms`` overrides
        ``sched.defaultTimeoutMs``; ``estimate_bytes`` overrides the
        admission working-set estimate."""
        plan = getattr(df_or_plan, "plan", df_or_plan)
        return self._query_service.submit(
            plan, priority=priority, timeout_ms=timeout_ms,
            estimate_bytes=estimate_bytes)

    def _execute(self, plan: lp.LogicalPlan) -> pa.Table:
        """The blocking action path: literally ``submit().result()``
        through the concurrent query scheduler (sched/service.py) —
        admission control, deadline, cancellation, and per-query
        profile attribution all apply to plain ``collect()`` too.

        An interrupt of the blocking wait (Ctrl-C in a REPL) cancels
        the submitted query: pre-scheduler, collect ran on the calling
        thread and unwound with the interrupt — a worker that kept
        running headless, holding its admission slot, would regress
        that.  (cancel() is a no-op when the raise came from the query
        itself, which has already finished.)"""
        fut = self._query_service.submit(plan)
        try:
            return fut.result()
        except BaseException:
            fut.cancel("blocking collect interrupted")
            raise

    def _execute_attributed(self, plan: lp.LogicalPlan,
                            query_id: Optional[int] = None,
                            sched_extra: Optional[Dict[str, Any]] = None,
                            plan_digest: Optional[str] = None):
        """Execute an action with the observability envelope: a
        QueryRun captures wall phases, the per-query registry delta and
        span window; the assembled QueryProfile lands in the profile
        ring / ``last_query_profile()`` and fans out to the registered
        query listeners (on success AND on failure).  Returns
        ``(table, profile)`` (profile None when profiling is off).
        Called by the QueryService worker with the query's CancelToken
        already installed on the thread."""
        run = None
        if self.conf.get(cfg.OBS_PROFILE_ENABLED):
            from spark_rapids_tpu.obs.profile import QueryRun
            run = QueryRun(query_id if query_id is not None
                           else self._next_query_id(),
                           sched_extra=sched_extra,
                           plan_digest=plan_digest)
        try:
            result, table = self._execute_inner(plan, run)
        except BaseException as e:
            if run is not None:
                # run.planned was stashed right after planning, so a
                # failure profile still carries the plan tree and the
                # explain report whenever planning itself succeeded
                self._finish_query(run, run.planned, None, e)
            raise
        prof = None
        if run is not None:
            prof = self._finish_query(run, result, table, None)
        elif self.conf.get(cfg.OBS_TRACE_ENABLED):
            # tracing without profiling: the chromePath contract still
            # holds (the whole ring stands in for the query window)
            from spark_rapids_tpu.obs import trace as obs_trace
            chrome = str(self.conf.get(cfg.OBS_TRACE_CHROME_PATH) or "")
            if chrome and obs_trace.is_enabled():
                with contextlib.suppress(OSError):
                    obs_trace.dump_chrome_trace(chrome)
        return table, prof

    def _finish_query(self, run, result, table,
                      error: Optional[BaseException]):
        from spark_rapids_tpu.obs import listener as obs_listener
        from spark_rapids_tpu.obs import trace as obs_trace
        prof = run.finish(result=result, table=table, error=error)
        with self._profile_lock:
            self._profiles[run.query_id] = prof
            while len(self._profiles) > self._profile_ring:
                self._profiles.popitem(last=False)
            # completion order under the lock: "last" is the most
            # recently COMPLETED query, stable under concurrent collects
            self._last_profile = prof
        obs_listener.notify(self._query_listeners, prof, error)
        self._maybe_log_slow_query(prof)
        chrome = str(self.conf.get(cfg.OBS_TRACE_CHROME_PATH) or "")
        if chrome and obs_trace.is_enabled():
            with contextlib.suppress(OSError):
                prof.dump_chrome_trace(chrome)
        return prof

    def _record_rejection(self, query_id: int,
                          error: BaseException, req,
                          meta: Optional[Dict[str, Any]] = None) -> None:
        """A query refused BEFORE admission (queue-full rejection)
        never reaches the profile assembly path, so without this hook
        neither the flight recorder nor the slow-query log would ever
        see it — serving overload would be undiagnosable.  Build a
        stub QueryProfile with the same schema (status ``rejected``),
        put it through the ring, the listener fan-out (the flight
        recorder bundles it under reason ``rejected``) and the
        slow-query log.  Never raises."""
        try:
            from spark_rapids_tpu.obs import listener as obs_listener
            from spark_rapids_tpu.obs.profile import QueryProfile
            meta = dict(meta or {})
            sched = {"sched.estimateBytes": getattr(req, "estimate", 0),
                     "sched.priority": getattr(req, "priority", 0)}
            if meta.get("session_id") is not None:
                sched["sched.sessionId"] = meta["session_id"]
            prof = QueryProfile(
                query_id=query_id,
                status="rejected",
                error=f"{type(error).__name__}: {error}",
                result_rows=None, wall_ns=0, phases={}, plan=None,
                metrics={"sched": sched},
                wall_breakdown={}, explain_lines=[], spans=[],
                plan_digest=meta.get("plan_digest"))
            with self._profile_lock:
                self._profiles[query_id] = prof
                while len(self._profiles) > self._profile_ring:
                    self._profiles.popitem(last=False)
            obs_listener.notify(self._query_listeners, prof, error)
            self._maybe_log_slow_query(prof)
        except Exception:
            pass

    def _record_dedup_follower(self, query_id: int, leader_qid: int,
                               state, error: Optional[BaseException],
                               meta: Optional[Dict[str, Any]],
                               wall_ns: int, result) -> Any:
        """Stub QueryProfile for a single-flight follower: the follower
        never executed, so instead of an empty or duplicated profile it
        records a pointer at the leader's query id
        (``sched.dedup.leaderQueryId``) whose profile holds the real
        execution.  Rings, notifies the listener fan-out and the
        slow-query log (rows carry ``deduped: true``) exactly like the
        rejection stub.  Returns the profile (None on any failure) —
        the caller attaches it to the follower future."""
        try:
            from spark_rapids_tpu.obs import listener as obs_listener
            from spark_rapids_tpu.obs.profile import QueryProfile
            meta = dict(meta or {})
            sched = {"sched.dedup.leaderQueryId": leader_qid,
                     "sched.deduped": 1}
            if meta.get("session_id") is not None:
                sched["sched.sessionId"] = meta["session_id"]
            status = getattr(state, "value", str(state))
            nrows = None
            try:
                if result is not None:
                    nrows = int(result.num_rows)
            except Exception:
                nrows = None
            prof = QueryProfile(
                query_id=query_id,
                status=status,
                error=None if error is None
                else f"{type(error).__name__}: {error}",
                result_rows=nrows, wall_ns=int(wall_ns), phases={},
                plan=None,
                metrics={"sched": sched,
                         "sharing": {"sched.dedup.leaderQueryId":
                                     leader_qid}},
                wall_breakdown={}, explain_lines=[], spans=[],
                plan_digest=meta.get("plan_digest"))
            with self._profile_lock:
                self._profiles[query_id] = prof
                while len(self._profiles) > self._profile_ring:
                    self._profiles.popitem(last=False)
                self._last_profile = prof
            obs_listener.notify(self._query_listeners, prof, error)
            self._maybe_log_slow_query(prof)
            return prof
        except Exception:
            return None

    def _maybe_log_slow_query(self, prof) -> None:
        """Structured slow-query log: one JSONL record per query at or
        over ``obs.slowQueryMs`` (failures included — a query that died
        slowly is still slow; ``rejected`` queries log regardless of
        wall, an instant rejection being exactly the overload signal
        the log exists for), appended to ``obs.slowQueryPath`` or
        routed through the ``spark_rapids_tpu.obs.slowquery`` logger.
        Never fails the query."""
        threshold_ms = int(self.conf.get(cfg.OBS_SLOW_QUERY_MS))
        if threshold_ms <= 0:
            return
        if prof.status != "rejected" and \
                prof.wall_ns < threshold_ms * 1e6:
            return
        try:
            import json as _json
            import time as _time
            # one rendering of the profile exists (to_dict): the log
            # record is a field subset of it plus the log-only extras,
            # so the two JSON surfaces cannot drift apart
            d = prof.to_dict()
            # exact token-based attribution (obs/compile.row_fields —
            # the same derivation the /queries rows use, so the two
            # surfaces cannot drift), NOT the profile's registry-window
            # delta: a concurrent neighbour's compiles would bleed into
            # the window and misidentify this query as compile-bound
            from spark_rapids_tpu.obs import compile as obs_compile
            record = {"ts_unix": _time.time(),
                      "threshold_ms": threshold_ms,
                      "session_id": prof.metrics.get("sched", {}).get(
                          "sched.sessionId"),
                      "queue_wait_s": prof.metrics.get("sched", {}).get(
                          "sched.queueWaitNs", 0) / 1e9}
            record.update(obs_compile.row_fields(prof.query_id))
            for key in ("query_id", "plan_digest", "status", "error",
                        "wall_s", "result_rows", "phases",
                        "wall_breakdown"):
                record[key] = d[key]
            leader = prof.metrics.get("sched", {}).get(
                "sched.dedup.leaderQueryId")
            if leader is not None:
                record["deduped"] = True
                record["leader_query_id"] = leader
            line = _json.dumps(record, default=str)
            from spark_rapids_tpu.obs import recorder as obs_recorder
            from spark_rapids_tpu.obs import registry as obsreg
            obsreg.get_registry().inc("obs.slowQueries")
            obs_recorder.record_event("query.slow",
                                      query=prof.query_id,
                                      wall_s=record["wall_s"])
            path = str(self.conf.get(cfg.OBS_SLOW_QUERY_PATH) or "")
            if path:
                from spark_rapids_tpu.obs import jsonl as obs_jsonl
                obs_jsonl.rotating_append(
                    path, line,
                    int(self.conf.get(cfg.OBS_SLOW_QUERY_MAX_BYTES)))
            else:
                import logging
                logging.getLogger(
                    "spark_rapids_tpu.obs.slowquery").warning(line)
        except Exception:
            pass

    def _phase(self, run, name: str):
        return run.phase(name) if run is not None \
            else contextlib.nullcontext()

    def _execute_inner(self, plan: lp.LogicalPlan, run):
        # executor-longevity guard (see kernel_cache docstring)
        from spark_rapids_tpu.exec import kernel_cache
        kernel_cache.maybe_clear_for_map_pressure()
        from spark_rapids_tpu.exec.context import set_input_file
        set_input_file("")  # fresh query: no stale input_file_name()
        with self._phase(run, "plan"):
            result = self._plan_physical(plan)
        if run is not None:
            run.planned = result
        p = result.plan
        from spark_rapids_tpu.exec.tpu_basic import DeviceToHostExec
        if isinstance(p, DeviceToHostExec):
            # defer ALL device->host downloads behind one completion
            # barrier: the async pipeline runs dispatch-only end to end
            # (a mid-stream read-back would serialize it — and on
            # remote-device runtimes permanently degrade dispatch)
            from spark_rapids_tpu.columnar.batch import to_arrow_all
            with self._phase(run, "execute"):
                batches = self._drain_partitions(p.children[0].execute())
            with self._phase(run, "collect"):
                tables = to_arrow_all(batches)
                table = concat_tables(tables, p.schema)
            # the terminal download exec never ran execute(); stamp it
            # with the collected result so the profile's root rows are
            # the rows the user got
            p.metrics.add_rows(table.num_rows)
            p.metrics.add_batches(len(tables))
            return result, table
        with self._phase(run, "execute"):
            tables = self._drain_partitions(p.execute())
        with self._phase(run, "collect"):
            table = concat_tables(tables, result.plan.schema)
        return result, table

    def _execute_device(self, plan: lp.LogicalPlan):
        """ColumnarRdd-style handoff: device batches, no host round-trip."""
        from spark_rapids_tpu.exec.tpu_basic import (DeviceToHostExec,
                                                     HostToDeviceExec)
        result = self._plan_physical(plan)
        p = result.plan
        if isinstance(p, DeviceToHostExec):
            p = p.children[0]  # strip the terminal download
        else:
            p = HostToDeviceExec(p, self.conf.get(cfg.MIN_BUCKET_ROWS))
        return self._drain_partitions(p.execute())

    # plan-capture hook for tests (ExecutionPlanCaptureCallback analog,
    # reference: Plugin.scala:214-303)
    def add_plan_listener(self, fn) -> None:
        self._plan_listeners.append(fn)

    def remove_plan_listener(self, fn) -> None:
        self._plan_listeners.remove(fn)

    # -- observability surface ---------------------------------------------
    @property
    def obs_server(self):
        """The live telemetry endpoint (obs/server.ObsHttpServer) when
        ``obs.http.enabled=true``; None otherwise.  ``obs_server.port``
        is the bound port (ephemeral under ``obs.http.port=0``)."""
        return self._obs_server

    @property
    def flight_recorder(self):
        """The flight recorder (obs/recorder.FlightRecorder) when
        ``obs.recorder.dir`` is set; None otherwise."""
        return self._recorder

    @property
    def fleet_store(self):
        """The shared fleet store (fleet/store.FleetStore) when this
        session was created with ``fleet.enabled=true``; None
        otherwise.  The serve tier shares its statement registry and
        result cache through it; the compile cache and precompile
        corpus ride its directories when file-backed."""
        return self._fleet_store

    @property
    def serve_server(self):
        """The multi-tenant serving front-end (serve/server.ServeServer)
        when ``serve.enabled=true``; None otherwise.
        ``serve_server.port`` is the bound port (ephemeral under
        ``serve.port=0``)."""
        return self._serve_server

    def restart_serve_server(self, drain_deadline_ms=None):
        """Drain the current serving front-end and start a successor on
        the SAME port — the in-process replica-swap primitive the drain/
        resume contract exists for.  The drain lets in-flight streams
        finish (then cancels stragglers with a typed ``Draining``
        error); resume tokens, the retained-stream window and the
        result cache survive, so clients reconnect, re-attach their
        sessions and resume streams against the successor.  Returns the
        new ServeServer."""
        from spark_rapids_tpu.serve.server import ServeServer
        old = self._serve_server
        port = None
        if old is not None:
            port = old.port
            old.drain(drain_deadline_ms)
        self._serve_server = ServeServer(self, port=port)
        return self._serve_server

    @property
    def sentinel(self):
        """The drift sentinel (obs/sentinel.DriftSentinel) when this
        session was created with ``obs.sentinel.enabled=true``; None
        otherwise.  ``sentinel.stats()`` reports
        ticks/breaches/episodes; ``sentinel.stop()`` halts the
        watcher thread."""
        return self._sentinel

    @property
    def precompile_service(self):
        """The background AOT precompile service
        (sched/precompile.PrecompileService) when this session was
        created with ``sched.precompile.enabled=true``; None otherwise.
        ``precompile_service.wait()`` blocks until the initial corpus
        replay finishes; ``.stats()`` reports plans/programs/warmed/
        skipped/failed."""
        return self._precompile_service

    def last_query_profile(self):
        """The QueryProfile of the most recently COMPLETED action (None
        before the first action, or while
        ``obs.profile.enabled=false`` has kept new profiles from being
        assembled).  Under concurrent collects this is completion
        order, not submission order — use :meth:`query_profile` with a
        QueryFuture's ``query_id`` for a specific query."""
        with self._profile_lock:
            return self._last_profile

    def query_profile(self, query_id: int):
        """The QueryProfile for ``query_id`` from the bounded per-query
        ring (``sched.profileRing`` entries; None once evicted or when
        profiling is off)."""
        with self._profile_lock:
            return self._profiles.get(query_id)

    def register_query_listener(self, listener) -> None:
        """Register a QueryExecutionListener analog: ``on_success(
        profile)`` / ``on_failure(profile, exception)`` fire after
        every action (obs/listener.py)."""
        self._query_listeners.append(listener)

    def remove_query_listener(self, listener) -> None:
        self._query_listeners.remove(listener)


class DataFrameReader:
    def __init__(self, session: TpuSparkSession):
        self.session = session
        self._options: Dict[str, Any] = {}

    def option(self, key: str, value: Any) -> "DataFrameReader":
        self._options[key] = value
        return self

    def _scan(self, fmt: str, paths) -> DataFrame:
        from spark_rapids_tpu.io.readers import (expand_paths, infer_schema,
                                                 _partition_fields)
        from spark_rapids_tpu.plan.logical import Field, Schema
        if isinstance(paths, str):
            paths = [paths]
        files, part_values = expand_paths(fmt, list(paths))
        if not files:
            raise FileNotFoundError(f"no {fmt} files under {paths}")
        schema = infer_schema(fmt, files, self._options)
        pfields = _partition_fields(part_values)
        if pfields:
            schema = Schema(list(schema.fields) +
                            [Field(k, d, True) for k, d in pfields])
        if self._options.get("columns"):
            schema = Schema([schema.field(c)
                             for c in self._options["columns"]])
        opts = dict(self._options)
        opts["part_values"] = part_values
        opts["part_fields"] = pfields
        # the pre-expansion roots: the serving tier's incremental
        # maintenance re-expands them at lookup time so files appended
        # to a watched directory appear in the stamp set instead of
        # being invisible to this frozen file list
        # (exec/incremental.current_files)
        opts["source_roots"] = [os.path.abspath(p) for p in paths]
        return DataFrame(
            lp.FileScan(fmt, files, schema, opts), self.session)

    def parquet(self, *paths) -> DataFrame:
        return self._scan("parquet", list(paths))

    def csv(self, *paths, header: bool = True, sep: str = ","
            ) -> DataFrame:
        self._options.setdefault("header", header)
        self._options.setdefault("sep", sep)
        return self._scan("csv", list(paths))

    def orc(self, *paths) -> DataFrame:
        return self._scan("orc", list(paths))

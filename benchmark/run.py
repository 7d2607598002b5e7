"""One run of one cell of the benchmark.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

One process holds the chip and hosts the session, its serve front end
and the clients.  Set-up: device check, data from the seed, session and
views, each statement's first executions.  Then the window, driven from
the client's side over the real socket.  Then, with the session shut
down, the plain reference and the comparison that decides ``correct``.
The last line of standard output is the result; the lines before it are
observations.  ``--rehearse`` (tiny rows, CPU allowed, no device
number) is for the rehearsal on a machine with no chip and is never in
the driver's command.
"""

import time
T_START = time.perf_counter()          # set-up counts from here

import argparse                        # noqa: E402
import json                            # noqa: E402
import os                              # noqa: E402
import shutil                          # noqa: E402
import sys                             # noqa: E402
import tempfile                        # noqa: E402
import threading                       # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.dirname(HERE)]

import cells                           # noqa: E402
import compare as cmp                  # noqa: E402
import datagen                         # noqa: E402
import placement                       # noqa: E402
import tracered                        # noqa: E402
import traffic                         # noqa: E402

REHEARSE_SCALE = 0.01
# the client gives up on a stream with no frame for 600 s by default; a
# first execution on an empty compile cache can take longer
WARMUP_TIMEOUT_S = 1150.0
QUERY_TIMEOUT_S = 330.0
COMPARE_AT_MOST = 64


def say(**obj) -> None:
    print(json.dumps(obj, default=str), flush=True)


def require_device(chips: int, rehearse: bool) -> dict:
    """First act: what jax finds attached.  Anything but ``chips`` TPU
    devices ends the run before any data is made."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if rehearse:
        if len(devs) < chips:
            sys.exit(f"benchmark: the cell needs {chips} device(s), jax "
                     f"reports {len(devs)}; nothing was run")
        return info
    if info["platform"] != "tpu":
        sys.exit(f"benchmark: jax found no TPU (platform="
                 f"{info['platform']!r}); nothing was run")
    if len(devs) != chips:
        sys.exit(f"benchmark: the cell needs {chips} chip(s), jax reports "
                 f"{len(devs)}; nothing was run")
    return info


def memory_peak() -> int:
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())


class Served:
    """The system under test from the client's side: a session with
    its serve front end, one ``ServeClient`` a client thread."""

    def __init__(self, cell, root: str, clients: int, trace: bool):
        from spark_rapids_tpu import TpuSparkSession
        from spark_rapids_tpu.serve.client import ServeClient
        conf = {"spark.rapids.tpu.serve.enabled": True, **cell.conf}
        if trace:
            # the program's own spans, for the traced run's readers
            conf["spark.rapids.tpu.obs.trace.enabled"] = True
        self.spark = TpuSparkSession(conf)
        for table in cell.tables:
            self.spark.register_view(
                table, self.spark.read.parquet(os.path.join(root, table)))
        self.clients = [ServeClient("127.0.0.1",
                                    self.spark.serve_server.port)
                        for _ in range(clients)]
        self._prepared = [{} for _ in range(clients)]

    def send(self, k: int, index: int, stmt, bindings: dict,
             due=None, timeout: float = QUERY_TIMEOUT_S) -> dict:
        """One query from client ``k``: ends when the last Arrow chunk
        is in the client's hands.  Never raises: a query that fails is
        a failed query."""
        import jax
        client = self.clients[k]
        rec = {"index": index, "client": k, "stmt": stmt.name,
               "bindings": bindings, "due": due, "error": None,
               "table": None, "profile": None}
        with jax.profiler.TraceAnnotation("bench.query", stmt=stmt.name,
                                          index=index):
            rec["t_submit"] = time.perf_counter()
            try:
                if stmt.mode == "prepared":
                    if stmt.name not in self._prepared[k]:
                        self._prepared[k][stmt.name] = client.prepare(
                            stmt.sql, params=stmt.params).statement_id
                    stream = client.execute_stream(
                        self._prepared[k][stmt.name], bindings,
                        timeout=timeout)
                else:
                    stream = client.sql_stream(stmt.sql, timeout=timeout)
                rec["table"] = stream.read_all()
                rec["t_done"] = time.perf_counter()
                rec["profile"] = self.spark.query_profile(
                    (stream.summary or {}).get("query_id"))
            except Exception as e:    # the boundary: count it, go on
                rec["t_done"] = time.perf_counter()
                rec["error"] = f"{type(e).__name__}: {e}"
        rec["wall_s"] = rec["t_done"] - rec["t_submit"]
        rec["late_s"] = 0.0 if due is None else rec["t_submit"] - due
        rec["latency_s"] = rec["t_done"] - (rec["t_submit"] if due is None
                                            else due)
        return rec

    def close(self) -> None:
        for c in self.clients:
            try:
                c.close()
            except Exception as e:
                print(f"benchmark: closing a client: {e}", file=sys.stderr)
        self.spark.serve_server.shutdown()


class Tracer:
    """The profiler inside the window: from ``from_s`` seconds after
    the first timed submission (0: from just before it) until the next
    query is answered.  A cell sets ``trace_from_s`` where the profiler
    cannot hold a whole query (it takes seconds to stop for each second
    it traced a program with long loops; PERF.md has the reading)."""

    def __init__(self, trace_dir: str, from_s: float):
        self.dir, self.from_s = trace_dir, from_s
        self._lock = threading.Lock()
        self._on = False
        self.anchor_ns = self.started_ns = self.stopped_ns = None
        self.stop_s = None
        self._timer = threading.Timer(from_s, self._start)

    def _start(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        with self._lock:
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._on = True
            with jax.profiler.TraceAnnotation("bench.anchor"):
                self.anchor_ns = time.perf_counter_ns()
            self.started_ns = time.perf_counter_ns()

    def open_window(self) -> None:
        """Called just before the first timed submission."""
        if self.from_s > 0:
            self._timer.start()
        else:
            self._start()

    def stop(self, answered: int = 0) -> None:
        """``traffic.run``'s ``on_done``: the query that was in flight
        when the trace started is answered."""
        import jax
        with self._lock:
            if not self._on:
                return
            self._on = False
            self.stopped_ns = time.perf_counter_ns()
            jax.profiler.stop_trace()
            self.stop_s = (time.perf_counter_ns() - self.stopped_ns) / 1e9

    def finish(self) -> None:
        """Stops what still runs; a trace that never started stays
        off."""
        self._timer.cancel()
        self.stop()


def counters():
    from spark_rapids_tpu.obs import registry
    return registry.get_registry().view()


def counter_delta(view) -> dict:
    """Every counter that moved since ``view`` was taken (one that did
    not is absent)."""
    return dict(view.delta()["counters"])


def warm_up(cell, served, seed: int) -> dict:
    """Each statement's first execution on every client's connection
    (a prepared statement is prepared per connection): trace,
    executable load or compile, run.  All of it is set-up.  Once is
    enough: Q1's second execution took what its third did (PERF.md)."""
    walls = []
    for stmt in cell.statements:
        req = traffic.Requests([stmt], seed ^ 0x5EED)
        for k in range(len(served.clients)):
            _, _, bindings = req.next()
            rec = served.send(k, -1, stmt, bindings,
                              timeout=WARMUP_TIMEOUT_S)
            if rec["error"]:
                raise SystemExit(f"benchmark: warm-up of {stmt.name} "
                                 f"failed: {rec['error']}")
            walls.append(rec["wall_s"])
    return {"first_answer_s": walls[0], "warmup_walls_s": walls}


def host_spans(records) -> list:
    """The program's own spans of the window's queries, as
    ``(name, t0_ns, dur_ns)`` on the host's monotonic clock."""
    out = []
    for r in records:
        for sp in (r["profile"].spans if r["profile"] else ()):
            out.append((sp["name"], float(sp["ts_ns"]),
                        float(sp["dur_ns"])))
    return out


def coverage(records: list, start_ns: float, end_ns: float):
    """Which queries the interval touches and what share of each, by
    time (1.0 for a whole one, 0.7 for the last seven tenths), and the
    seconds of those queries that lie outside it."""
    covered, outside = [], 0.0
    for r in records:
        ns = min(r["t_done"] * 1e9, end_ns) - max(r["t_submit"] * 1e9,
                                                   start_ns)
        if ns > 0:
            covered.append((r["index"], ns / (r["wall_s"] * 1e9)))
            outside += r["wall_s"] - ns / 1e9
    return covered, outside


def traced_window(tracer, xplane: str, records: list, spans: list):
    """The trace's reduction over the traced window: from the start of
    the trace (the first timed submission, where it started before
    that) to its stop, with the window's ``coverage`` of the queries
    (``queries`` is the sum of the shares)."""
    start = max(tracer.started_ns, min(r["t_submit"] for r in records) * 1e9)
    trace = tracered.reduce(tracered.read_planes(xplane), spans,
                            tracer.anchor_ns, (start, tracer.stopped_ns))
    if trace is not None:
        trace["covered"], trace["not_traced_s"] = coverage(
            records, start, tracer.stopped_ns)
        trace["queries"] = sum(share for _, share in trace["covered"])
    return trace


def judge(cell, root: str, records: list, seed: int) -> dict:
    """Every answer of the window (a sample drawn from the seed past
    ``COMPARE_AT_MOST``) against the plain reference on the same files,
    and every query's placement.  Marks each record ``ok``; returns the
    numbers compared, each the worst over the queries."""
    import numpy as np
    by_name = {s.name: s for s in cell.statements}
    checks = {"unanswered": 0, "rows_diff": 0, "key_mismatch": 0,
              "float_rel_err": 0.0, "off_tpu_ops": 0,
              "host_decoded_cols": 0, "missing_ops": 0}
    answered = [r for r in records if r["error"] is None]
    checks["unanswered"] = len(records) - len(answered)
    sample = answered
    if len(answered) > COMPARE_AT_MOST:
        pick = np.random.default_rng([int(seed), 0xC0FFEE]).choice(
            len(answered), COMPARE_AT_MOST, replace=False)
        sample = [answered[i] for i in sorted(pick)]
    wanted = {}
    rtol = float(cell.limits["float_rel_err"])
    for r in records:
        r["ok"] = r["error"] is None
    for r in sample:
        stmt = by_name[r["stmt"]]
        key = (stmt.name, json.dumps(r["bindings"], sort_keys=True))
        if key not in wanted:
            wanted[key] = stmt.reference.compute(root, r["bindings"])
        nums = cmp.compare(r["table"], wanted[key], stmt.spec, rtol)
        nums.update(placement.check(r["profile"],
                                    stmt.spec["need_operators"]))
        r["checks"] = nums
        for k, v in nums.items():
            checks[k] = max(checks[k], v)
            if v > cell.limits.get(k, 0):
                r["ok"] = False
    return checks, len(sample)


def main(argv=None, served_cls=Served) -> int:
    """``served_cls`` is the system under test; the tests put a broken
    one in its place and see ``correct`` come out false."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny rows, CPU allowed, no device number")
    args = ap.parse_args(argv)

    cell = cells.Cell(args.workload)
    device = require_device(cell.chips, args.rehearse)
    import jax
    from spark_rapids_tpu import TpuSparkSession   # noqa: F401  (fail early)
    say(phase="device", **device, cell=cell.name, seed=args.seed,
        seconds=args.seconds, trace=args.trace, rehearse=args.rehearse)

    root = tempfile.mkdtemp(prefix="bench_data_")
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace \
        else None
    served = None
    try:
        t0 = time.perf_counter()
        tables = cell.scaled_tables(REHEARSE_SCALE if args.rehearse else 1)
        data = datagen.generate(cell.config["datagen"], root, tables,
                                args.seed)
        say(phase="data", tables=data, wall_s=time.perf_counter() - t0)

        loop = cell.workload["loop"]
        served = served_cls(cell, root, int(loop.get("clients", 1)),
                            bool(args.trace))
        warm = warm_up(cell, served, args.seed)
        say(phase="warm_up", **warm)

        # -- the window ------------------------------------------------
        requests = traffic.Requests(cell.statements, args.seed)
        tracer = None
        if args.trace:
            tracer = Tracer(trace_dir,
                            float(cell.workload.get("trace_from_s", 0)))
            tracer.open_window()
        view = counters()
        setup_s = time.perf_counter() - T_START
        records = traffic.run(loop, requests, served.send, args.seconds,
                              on_done=tracer.stop if tracer else None)
        if tracer:
            tracer.finish()
        window_counters = counter_delta(view)
        peak = memory_peak()
        spans = host_spans(records) if args.trace else []
        served.close()
        served = None

        # -- after the window: reference, comparison, metrics ------------
        t0 = time.perf_counter()
        checks, compared = judge(cell, root, records, args.seed)
        judge_s = time.perf_counter() - t0
        trace = None
        if args.trace:
            xplane = tracered.find_xplane(trace_dir)
            if xplane and tracer.stopped_ns:
                t0 = time.perf_counter()
                trace = traced_window(tracer, xplane, records, spans)
                say(phase="trace_file", bytes=os.path.getsize(xplane),
                    stop_s=tracer.stop_s,
                    read_s=time.perf_counter() - t0)
        completed = [r for r in records if r["error"] is None]
        run = {
            "cell": cell, "device": device, "root": root,
            "records": records, "completed": completed,
            "window_wall_s": (max(r["t_done"] for r in records)
                              - min(r["t_submit"] for r in records)),
            "setup_s": setup_s, "first_answer_s": warm["first_answer_s"],
            "counters": window_counters, "memory_peak_bytes": peak,
            "trace": trace,
            "peaks": None if device["platform"] != "tpu"
            else cells.peaks(device["kind"]),
        }
        say(phase="window", queries=len(records),
            walls_s=[r["wall_s"] for r in records],
            late_s=max(r["late_s"] for r in records),
            counters=window_counters, compared=compared, judge_s=judge_s,
            errors=[r["error"] for r in records if r["error"]][:3])
        if args.trace and completed and completed[0]["profile"]:
            say(phase="first_query_profile",
                wall_breakdown=completed[0]["profile"].wall_breakdown,
                phases=completed[0]["profile"].phases)
        if trace:
            say(phase="trace", **{k: v for k, v in trace.items()
                                  if k not in ("device_ops", "idle_gaps",
                                               "device_programs",
                                               "covered")})

        metrics = {}
        for m in cell.metrics["per_layer" if args.trace else "end_to_end"]:
            value = cells.reader(m["name"])(run)
            if value is not None:     # nothing to read: left out
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    finally:
        if served is not None:
            served.close()
        shutil.rmtree(root, ignore_errors=True)
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    failed = sum(1 for r in records if not r["ok"])
    limits = {k: cell.limits.get(k, 0) for k in checks}
    within = all(checks[k] <= limits[k] for k in limits)
    dev = {**device, "memory_peak_bytes": peak}
    result = {"correct": bool(within and failed == 0 and completed),
              "attempted": len(records), "failed": failed,
              "metrics": metrics, "device": dev}
    if trace:
        dev["busy_s"], dev["window_s"] = trace["busy_s"], trace["window_s"]
        # at most ten entries: the four largest programs, each with all
        # its operations, then the largest single operations
        ops = (trace["device_programs"][:4] + trace["device_ops"])[:10]
        if trace["not_traced_s"] > 0.05 * trace["window_s"]:
            # the trace covers part of a query: say so where the
            # ledger's reader looks
            ops = ops[:9] + [["not traced: the rest of the traced queries",
                              trace["not_traced_s"]]]
        result["breakdown"] = {"device_ops": ops,
                               "idle_gaps": trace["idle_gaps"]}
    result["checks"] = {k: {"value": checks[k], "limit": limits[k]}
                        for k in limits}
    for k, v in result["checks"].items():
        print(f"check {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Measure the full-suite compile bill (PERF.md "compile bill").

Runs every TPC-DS-like query once on the attached device with
``SRT_COMPILE_LOG`` instrumentation enabled (exec/kernel_cache.py):
each first (kernel, arg-shape) call is timed — that wall is trace +
XLA compile.  Prints one JSON
line: total queries, wall, compile events, total compile seconds, and
the top-10 most expensive kernels.

``--churn-report`` additionally reads the compile observatory's ledger
(obs/compile.py) after the suite and emits the shape-churn analysis:
a ranked collapse-candidate table (family, distinct signatures,
estimated programs after width-bucketing) plus per-query compile
attribution whose total is asserted to match the ``/metrics``
``kernel.cache.compiles`` counter exactly — the instrument ROADMAP
item 2's shape-erased ABI refactor is driven by.

``--abi-report`` (implies the churn ledger read) compares the ACTUAL
distinct-program count the suite compiled against the churn report's
width-bucketed projection — the collapse the shape-erased ABI
(exec/kernel_abi.py) promised vs what it delivered — and APPENDS one
compile-bill record (program count, fresh/warm compile seconds) to the
rolling ``BENCH_trend.json`` series so the collapse is tracked per run.

Run: ``python bench_compile_bill.py [--sf 0.002] [--churn-report]
[--abi-report]`` (set JAX_PLATFORMS and the device as usual; the
driver's bench chip is the target).
"""

import json
import os
import sys
import time

os.environ.setdefault("SRT_COMPILE_LOG", "1")


def _churn_table(rows) -> str:
    """Human-readable ranked collapse-candidate table (stderr; the
    machine-readable rows ride the JSON line on stdout)."""
    hdr = (f"{'family':<20} {'programs':>8} {'distinct':>8} "
           f"{'bucketed':>8} {'savings':>8} {'wall_ms':>10}")
    lines = [hdr, "-" * len(hdr)]
    for r in rows:
        lines.append(
            f"{r['family']:<20} {r['programs']:>8} "
            f"{r['distinct_signatures']:>8} "
            f"{r['est_programs_width_bucketed']:>8} "
            f"{r['est_collapse_savings']:>8} "
            f"{r['compile_wall_ms']:>10.1f}")
    return "\n".join(lines)


def main() -> None:
    sf = 0.002
    if "--sf" in sys.argv:
        sf = float(sys.argv[sys.argv.index("--sf") + 1])
    backend = "xla"
    if "--backend" in sys.argv:   # kernel.backend for the whole suite
        backend = sys.argv[sys.argv.index("--backend") + 1]
    abi_report = "--abi-report" in sys.argv
    churn = "--churn-report" in sys.argv or abi_report
    limit = 0    # --limit N: first N queries only (smoke verification)
    if "--limit" in sys.argv:
        limit = int(sys.argv[sys.argv.index("--limit") + 1])

    from spark_rapids_tpu import TpuSparkSession
    from spark_rapids_tpu.bench import tpcds
    from spark_rapids_tpu.exec import kernel_cache as kc

    data = tpcds.generate(sf, seed=13)
    s = TpuSparkSession(
        {"spark.rapids.tpu.sql.variableFloatAgg.enabled": True,
         "spark.rapids.tpu.kernel.backend": backend})
    tables = tpcds.setup(s, data)

    from spark_rapids_tpu.obs import compile as obscompile
    from spark_rapids_tpu.obs import registry as obsreg

    # compiles before the suite loop (session warm-up, setup) are not
    # attributable to any suite query; the attribution cross-check
    # below is over the loop window
    compiles_before = obsreg.get_registry().counter(
        "kernel.cache.compiles")

    t0 = time.perf_counter()
    errors = {}
    # per-query dispatch + newly-compiled-kernel counts carved from the
    # obs registry (snapshot deltas), so the whole-stage fusion layer's
    # dispatch reduction shows up per query next to the compile bill
    per_query = {}
    names = sorted(tpcds.QUERIES, key=lambda q: int(q[1:]))
    if limit:
        names = names[:limit]
    for name in names:
        view = obsreg.get_registry().view()
        try:
            tpcds.QUERIES[name](tables).collect()
        except Exception as e:   # report, keep measuring the rest
            errors[name] = f"{type(e).__name__}: {e}"
        d = view.delta()["counters"]
        per_query[name] = {
            "dispatches": int(d.get("kernel.dispatches", 0)),
            "kernels_compiled": int(d.get("kernel.cache.misses", 0)),
            # program granularity (the compile observatory's cache-tier
            # split): fresh XLA compiles + persistent-cache reloads +
            # the compile wall this query paid
            "compiled_programs":
                int(d.get("kernel.cache.compiles", 0)),
            "persistent_reloads":
                int(d.get("kernel.cache.persistentHits", 0)),
            "compile_ms":
                round(d.get("kernel.compile.wallNs", 0) / 1e6, 1),
            "fused_stages": int(d.get("fusion.stages", 0)),
            "dispatches_saved":
                int(d.get("fusion.dispatchesSaved", 0)),
        }
    wall = time.perf_counter() - t0
    reg_totals = obsreg.get_registry().snapshot()["counters"]

    log = kc.dump_compile_log()
    total_compile = sum(dt for _, _, dt in log)
    by_kernel = {}
    for key, _, dt in log:
        by_kernel[key] = by_kernel.get(key, 0.0) + dt
    top = sorted(by_kernel.items(), key=lambda kv: -kv[1])[:10]

    result = {
        "metric": "TPC-DS 99-query compile bill "
                  f"(sf={sf}, one fresh process)",
        "queries": len(names),
        "errors": errors,
        "suite_wall_s": round(wall, 1),
        "compile_events": len(log),
        "compile_total_s": round(total_compile, 1),
        "dispatches_total": int(reg_totals.get("kernel.dispatches", 0)),
        "distinct_kernels":
            int(reg_totals.get("kernel.cache.misses", 0)),
        "fusion_dispatches_saved":
            int(reg_totals.get("fusion.dispatchesSaved", 0)),
        # which kernel backend actually RAN, per dispatching family
        # (kernel.dispatches.<family>.<pallas|xla>) plus the selection
        # counters with fallback reasons — the per-backend compile/
        # dispatch trend the kernel.backend knob is judged by
        "kernel_backend": backend,
        "backend_dispatches": {
            k: int(v) for k, v in sorted(reg_totals.items())
            if k.startswith("kernel.dispatches.") and
            (k.endswith(".pallas") or k.endswith(".xla"))},
        "pallas_selection": {
            k: int(v) for k, v in sorted(reg_totals.items())
            if k.startswith("kernel.backend.pallas.")},
        "per_query": per_query,
        "top10": [{"kernel": k[:100], "s": round(v, 1)}
                  for k, v in top],
    }

    if churn:
        snap = obscompile.snapshot(max_events=0)
        rows = snap["churn"]
        attr_total = sum(q["compiled_programs"]
                         for q in per_query.values())
        counter_total = int(reg_totals.get("kernel.cache.compiles", 0))
        window_total = counter_total - int(compiles_before)
        # the LEDGER's token-based per-query attribution must account
        # for every fresh compile the process made: the registry
        # deltas above are window accounting and would sum to the
        # counter even with attribution broken, but the ledger only
        # counts what a CancelToken actually claimed.  The identity
        # closes over the ledger's own unattributed/evicted tallies
        # (compiles outside any query, records evicted past the table
        # bound) — an attribution gap beyond those means compiles
        # escaped the observatory (the acceptance contract)
        ledger_attr = sum(q["kernels_compiled"]
                          for q in snap["per_query"].values())
        closure = (snap["totals"]["unattributed_fresh"] +
                   snap["totals"]["evicted_compiled"])
        assert ledger_attr + closure == counter_total, (
            f"ledger per-query compile attribution ({ledger_attr} "
            f"+ {closure} unattributed/evicted) != "
            f"kernel.cache.compiles counter ({counter_total}) — "
            f"compiles are escaping query attribution")
        assert attr_total == window_total, (
            f"per-query registry deltas ({attr_total}) != "
            f"kernel.cache.compiles over the suite window "
            f"({window_total} = {counter_total} - {compiles_before})")
        result["churn_report"] = rows
        result["churn_attribution"] = {
            "per_query_compiled_total": attr_total,
            "ledger_attributed_total": ledger_attr,
            "ledger_closure_unattributed_or_evicted": closure,
            "kernel_cache_compiles_counter": counter_total,
            "pre_suite_compiles": int(compiles_before),
            "ledger_totals": snap["totals"],
        }
        print("== shape-churn collapse candidates "
              "(ranked by distinct signatures) ==", file=sys.stderr)
        print(_churn_table(rows), file=sys.stderr)
        print(f"attribution: per-query compiled total {attr_total} == "
              f"kernel.cache.compiles window {window_total}",
              file=sys.stderr)

    if churn and abi_report:
        from spark_rapids_tpu.exec import kernel_abi
        totals = snap["totals"]
        actual = totals["distinct_programs"]
        projected = totals["width_bucketed_projection"]
        result["abi_report"] = {
            "abi_enabled": kernel_abi.is_enabled(),
            "distinct_programs": actual,
            "width_bucketed_projection": projected,
            # >1: residual churn the projection says remains erasable;
            # ~1: the ABI delivered the projected collapse
            "actual_vs_projection_ratio":
                round(actual / max(projected, 1), 3),
            "compile_fresh_s":
                round(totals["compile_wall_fresh_ms"] / 1e3, 2),
            "warm_compile_s":
                round(totals["compile_wall_persistent_ms"] / 1e3, 2),
            "families": [
                {"family": r["family"],
                 "distinct": r["distinct_signatures"],
                 "projected": r["est_programs_width_bucketed"]}
                for r in rows],
        }
        result["trend_path"] = _append_compile_trend(result)
        print(f"abi report: {actual} distinct programs vs "
              f"{projected} projected "
              f"(x{result['abi_report']['actual_vs_projection_ratio']}),"
              f" fresh {result['abi_report']['compile_fresh_s']}s / "
              f"warm {result['abi_report']['warm_compile_s']}s",
              file=sys.stderr)

    print(json.dumps(result), flush=True)


def _git_commit() -> str:
    import subprocess
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True,
            cwd=os.path.dirname(os.path.abspath(__file__))
        ).stdout.strip() or None
    except Exception:
        return None


def _append_compile_trend(result: dict,
                          out_name: str = "BENCH_trend.json") -> str:
    """Append one compile-bill record to the rolling trend series via
    bench.py's ONE series writer (append_trend_record: runs list,
    temp-file + os.replace, corrupt-file preservation).  Records are
    tagged ``kind: "compile_bill"`` so trend readers can split them
    from the bench runs."""
    import time as _t
    from bench import append_trend_record
    abi = result.get("abi_report") or {}
    record = {
        "kind": "compile_bill",
        "pr": os.environ.get("SRT_BENCH_PR"),
        "commit": _git_commit(),
        "generated_unix": _t.time(),
        "queries": result["queries"],
        "suite_wall_s": result["suite_wall_s"],
        "kernel_backend": result["kernel_backend"],
        "abi_enabled": abi.get("abi_enabled"),
        # the collapse, tracked per run
        "distinct_programs": abi.get("distinct_programs"),
        "width_bucketed_projection":
            abi.get("width_bucketed_projection"),
        "compile_fresh_s": abi.get("compile_fresh_s"),
        "warm_compile_s": abi.get("warm_compile_s"),
        "compile_total_s": result["compile_total_s"],
    }
    return append_trend_record(record, out_name)


if __name__ == "__main__":
    main()

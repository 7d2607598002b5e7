"""chip_smoke.py — the served SQL path, once, on the attached TPU.

The quickest proof that the system still starts on the chip.  One
process, normal entry points only: a ``TpuSparkSession`` with
``serve.enabled`` answers a ``ServeClient`` over the real socket — a
q6-class prepared statement under three bindings and one q3-class
join — over a TPC-DS ``store_sales`` slice at SF10's row count, and
every answer is compared with a plain pyarrow/pandas computation on the
same files.  Every operator must be a ``Tpu*Exec``; a CPU fallback, a
host-decoded scan column or a wrong answer exits non-zero.

    python chip_smoke.py                 # one chip, 28.8M rows
    python chip_smoke.py --chips 4       # ONLY the placed ICI path: q65

With ``--chips 4`` it runs what the benchmark's four-chip cell runs
(``tpcds-sf10.agg-ici4``): TPC-DS q65's text over the SF10 files of
``benchmark/configs/tpcds-sf10-store-ici4.json``, the fact table's scan
batches on the chips that scan them and the exchanges over ICI, against
that configuration's pandas reference, so the smoke and the cell
exercise one path.

Lines printed before the last are observations (one JSON object each),
not metrics.  The last line is the result.  With no TPU the script
exits non-zero before loading any data, and it never picks a platform
itself.
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

SF10_ROWS = 28_800_000      # TPC-DS SF10 store_sales
HERE = os.path.dirname(os.path.abspath(__file__))
ICI_CONFIG = "tpcds-sf10-store-ici4"     # --chips 4: the cell's deployment
ROWS_PER_FILE = 1_800_000   # one scan batch (reader.batchSizeRows 2^21)
DICT_COLUMNS = ["ss_sold_date_sk", "ss_item_sk", "ss_quantity"]
RTOL = 1e-9                 # float aggregates (the benchmark's limit)

Q6_SQL = ("select ss_item_sk, count(*) as cnt, sum(ss_quantity) as qty, "
          "avg(ss_ext_sales_price) as aesp from store_sales "
          "where ss_sales_price > :lo group by ss_item_sk")
Q6_BINDINGS = (150.0, 100.0, 180.0)
Q3_SQL = ("select d_year, i_brand_id as brand_id, i_brand as brand, "
          "sum(ss_ext_sales_price) as sum_agg "
          "from date_dim join store_sales on d_date_sk = ss_sold_date_sk "
          "join item on ss_item_sk = i_item_sk "
          "where d_moy = 11 and i_manufact_id <= 100 "
          "group by d_year, i_brand, i_brand_id "
          "order by d_year asc, sum_agg desc, brand_id asc limit 100")
BASE_CONF = {
    "spark.rapids.tpu.serve.enabled": True,             # port 0
    "spark.rapids.tpu.serve.resultCache.enabled": False,
    "spark.rapids.tpu.sql.variableFloatAgg.enabled": True,
}


def say(**obj) -> None:
    print(json.dumps(obj, default=str), flush=True)


def require_tpu(chips: int) -> dict:
    """First act: ask jax what is attached.  Anything but ``chips``
    TPU devices ends the run before any data is made."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: jax found no TPU (platform="
                 f"{devs[0].platform!r}); nothing was run")
    if len(devs) != chips:
        sys.exit(f"chip_smoke: --chips {chips} but jax reports "
                 f"{len(devs)} device(s); nothing was run")
    return device_info()


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------

def _gen_store_sales(n: int, seed: int):
    """q6-class fact slice: sold date fk, item fk, price, qty."""
    import numpy as np
    import pyarrow as pa
    rng = np.random.default_rng(seed)
    return pa.table({
        "ss_sold_date_sk": pa.array(
            rng.integers(1, 1827, n).astype(np.int64)),
        "ss_item_sk": pa.array(
            rng.integers(1, 18001, n).astype(np.int64)),
        "ss_quantity": pa.array(rng.integers(1, 101, n).astype(np.int32)),
        "ss_list_price": np.round(rng.uniform(1.0, 200.0, n), 2),
        "ss_sales_price": np.round(rng.uniform(0.2, 200.0, n), 2),
        "ss_ext_sales_price": np.round(rng.uniform(1.0, 20000.0, n), 2),
    })


def make_data(root: str, rows: int, seed: int) -> dict:
    """A ``store_sales`` slice (six columns, dictionary-encoded keys)
    in files of one scan batch each, plus
    ``item`` and ``date_dim`` from the TPC-DS generator sized to cover
    the fact's key ranges (18,000 items, 1,826 days)."""
    from concurrent.futures import ThreadPoolExecutor

    import pyarrow.parquet as papq

    from spark_rapids_tpu.bench import tpcds

    files = max(1, rows // ROWS_PER_FILE)
    per = rows // files
    sizes = [per + (rows - per * files if i == files - 1 else 0)
             for i in range(files)]
    os.makedirs(os.path.join(root, "store_sales"))

    def write(i: int) -> int:
        path = os.path.join(root, "store_sales", f"part-{i:04d}.parquet")
        papq.write_table(_gen_store_sales(sizes[i], seed + 1 + i),
                         path, use_dictionary=DICT_COLUMNS)
        return os.path.getsize(path)

    with ThreadPoolExecutor(max_workers=min(8, files)) as pool:
        nbytes = sum(pool.map(write, range(files)))
    dims = tpcds.generate(0.1, seed=seed)
    for name in ("item", "date_dim"):
        os.makedirs(os.path.join(root, name))
        path = os.path.join(root, name, "part-0000.parquet")
        papq.write_table(dims[name], path)
        nbytes += os.path.getsize(path)
    return {"rows": rows, "files": files, "rows_per_file": per,
            "item_rows": dims["item"].num_rows,
            "date_dim_rows": dims["date_dim"].num_rows,
            "parquet_bytes": nbytes}


# ---------------------------------------------------------------------------
# the plain reference: pyarrow/pandas on the same files
# ---------------------------------------------------------------------------

def reference_q6(root: str, lo: float):
    import pyarrow.compute as pc
    import pyarrow.dataset as pads
    t = pads.dataset(os.path.join(root, "store_sales")).to_table(
        columns=["ss_item_sk", "ss_quantity", "ss_ext_sales_price"],
        filter=pc.field("ss_sales_price") > lo)
    g = t.group_by("ss_item_sk").aggregate(
        [([], "count_all"), ("ss_quantity", "sum"),
         ("ss_ext_sales_price", "mean")])
    return g.rename_columns(
        {"count_all": "cnt", "ss_quantity_sum": "qty",
         "ss_ext_sales_price_mean": "aesp"}).sort_by("ss_item_sk")


def reference_q3(root: str):
    import pyarrow as pa
    import pyarrow.compute as pc
    import pyarrow.dataset as pads
    import pyarrow.parquet as papq
    dd = papq.read_table(os.path.join(root, "date_dim"),
                         columns=["d_date_sk", "d_year", "d_moy"]
                         ).to_pandas()
    dd = dd[dd.d_moy == 11]
    it = papq.read_table(os.path.join(root, "item"),
                         columns=["i_item_sk", "i_brand_id", "i_brand",
                                  "i_manufact_id"]).to_pandas()
    it = it[it.i_manufact_id <= 100]
    ss = pads.dataset(os.path.join(root, "store_sales")).to_table(
        columns=["ss_sold_date_sk", "ss_item_sk", "ss_ext_sales_price"],
        filter=(pc.field("ss_sold_date_sk").isin(
                    pa.array(dd.d_date_sk.values)) &
                pc.field("ss_item_sk").isin(
                    pa.array(it.i_item_sk.values)))).to_pandas()
    j = ss.merge(dd, left_on="ss_sold_date_sk", right_on="d_date_sk") \
          .merge(it, left_on="ss_item_sk", right_on="i_item_sk")
    g = j.groupby(["d_year", "i_brand", "i_brand_id"], as_index=False) \
         .agg(sum_agg=("ss_ext_sales_price", "sum"))
    g = g.rename(columns={"i_brand_id": "brand_id", "i_brand": "brand"})
    g = g.sort_values(["d_year", "sum_agg", "brand_id"],
                      ascending=[True, False, True]).head(100)
    return g[["d_year", "brand_id", "brand", "sum_agg"]] \
        .reset_index(drop=True)


def assert_same(what: str, got, want, exact, approx) -> None:
    """Integer/string columns exact, float aggregates within RTOL.
    ``got``/``want`` are pyarrow tables in the same row order."""
    import numpy as np
    if got.num_rows != want.num_rows:
        raise AssertionError(f"{what}: {got.num_rows} rows, reference "
                             f"has {want.num_rows}")
    for c in exact:
        if got.column(c).to_pylist() != want.column(c).to_pylist():
            raise AssertionError(f"{what}: column {c} differs from the "
                                 f"reference")
    for c in approx:
        a = got.column(c).to_numpy(zero_copy_only=False)
        b = want.column(c).to_numpy(zero_copy_only=False)
        if not np.allclose(a, b, rtol=RTOL, atol=0.0, equal_nan=True):
            worst = float(np.max(np.abs(a - b) / np.abs(b)))
            raise AssertionError(f"{what}: column {c} off the reference "
                                 f"by up to {worst:.3e} relative")


# ---------------------------------------------------------------------------
# the served path
# ---------------------------------------------------------------------------

def start_session(root: str, conf: dict = None):
    from spark_rapids_tpu import TpuSparkSession
    spark = TpuSparkSession({**BASE_CONF, **(conf or {})})
    for name in ("store_sales", "item", "date_dim"):
        spark.register_view(
            name, spark.read.parquet(os.path.join(root, name)))
    return spark


def assert_on_device(what: str, profile, need: tuple) -> list:
    """Every operator of the executed plan is a ``Tpu*Exec`` (the root
    download aside), the operators in ``need`` are there, nothing was
    tagged off the TPU and no scan column was decoded on the host — a
    silent fallback still returns right answers.  Returns the plan's
    nodes."""
    off = [ln.strip() for ln in profile.explain_lines
           if ln.strip().startswith("!")]
    if off:
        raise AssertionError(f"{what}: CPU fallbacks in the plan: {off}")
    nodes = []

    def walk(node, root=False):
        nodes.append(node)
        if not root and not (node.is_tpu and node.name.startswith("Tpu")):
            raise AssertionError(f"{what}: {node.name} did not run on "
                                 f"the TPU")
        if node.extra.get("fallbackColumns"):
            raise AssertionError(
                f"{what}: {node.name} decoded "
                f"{node.extra['fallbackColumns']} column(s) on the host")
        for ch in node.children:
            walk(ch)
    walk(profile.plan, root=True)
    names = [n.name for n in nodes]
    for frag in need:
        if not any(frag in n for n in names):
            raise AssertionError(f"{what}: no {frag} in the executed "
                                 f"plan {names}")
    return nodes


_COUNTERS = ("kernel.dispatches", "kernel.compile.events",
             "kernel.cache.compiles", "kernel.cache.persistentHits")


def timed(fn):
    """(result, wall seconds, counter deltas) of one served execution."""
    from spark_rapids_tpu.obs import registry as obsreg
    view = obsreg.get_registry().view()
    t0 = time.perf_counter()
    out = fn()
    wall = time.perf_counter() - t0
    d = view.delta()["counters"]
    obs = {k: int(d.get(k, 0)) for k in _COUNTERS}
    obs["compile_wall_s"] = d.get("kernel.compile.wallNs", 0) / 1e9
    return out, wall, obs


def serve_phase(root: str) -> None:
    """Session with serve.enabled -> ServeClient over the socket: the
    q6-class prepared statement under three bindings, then the q3-class
    join; parity and placement asserted per execution."""
    import pyarrow as pa

    from spark_rapids_tpu import config as cfg
    from spark_rapids_tpu.mem import host_arena
    from spark_rapids_tpu.serve.client import ServeClient

    spark = start_session(root)
    say(phase="session",
        shuffle_transport=spark.conf.get(cfg.SHUFFLE_TRANSPORT),
        host_arena="native/arena.cpp" if host_arena.native_available()
        else "python shim")
    client = ServeClient("127.0.0.1", spark.serve_server.port)
    try:
        stmt = client.prepare(Q6_SQL, params={"lo": "double"})
        for i, lo in enumerate(Q6_BINDINGS):
            got, wall, obs = timed(
                lambda: client.execute(stmt.statement_id, {"lo": lo}))
            assert_on_device(f"q6[lo={lo}]", spark.last_query_profile(),
                             ("ParquetScan", "HashAggregate"))
            assert_same(f"q6[lo={lo}]", got.sort_by("ss_item_sk"),
                        reference_q6(root, lo),
                        exact=("ss_item_sk", "cnt", "qty"),
                        approx=("aesp",))
            say(phase="q6", execution=i + 1, lo=lo, rows=got.num_rows,
                wall_s=wall, **obs)
        got, wall, obs = timed(lambda: client.sql(Q3_SQL))
        assert_on_device("q3", spark.last_query_profile(),
                         ("ParquetScan", "Join", "HashAggregate", "Sort"))
        assert_same("q3", got, pa.Table.from_pandas(reference_q3(root)),
                    exact=("d_year", "brand_id", "brand"),
                    approx=("sum_agg",))
        say(phase="q3", execution=1, rows=got.num_rows, wall_s=wall, **obs)
    finally:
        client.close()
        spark.serve_server.shutdown()


def donation_reload_check() -> None:
    """A donating program written to the persistent compile cache and
    reloaded from it must alias its buffers correctly (an older jax did
    not; the engine persists donating kernels, so this has to hold on
    the chip too — tests/test_fusion.py pins the same on the CPU)."""
    import jax
    import jax.numpy as jnp

    def k(ai, af, p):
        return ai + 0, af * 1.0, p + ai.astype(p.dtype)
    n = 1 << 20
    af = jnp.linspace(0.0, 1.0, n, dtype=jnp.float32)
    p = jnp.ones(n, dtype=jnp.float32)

    def run():
        return [x.tolist() for x in jax.jit(k, donate_argnums=(0,))(
            jnp.arange(n, dtype=jnp.int32), af, p)]
    want = run()
    jax.clear_caches()          # the re-jit reloads from the cache
    if run() != want:
        raise AssertionError("a donating executable reloaded from the "
                             "persistent cache mis-applied its aliasing")
    say(phase="donation_reload", ok=True)


def observations() -> None:
    """Process totals after the statements: compile tiers, peak
    device memory."""
    import jax

    from spark_rapids_tpu.obs import registry as obsreg
    c = obsreg.get_registry().snapshot()["counters"]
    say(phase="compile_totals",
        cache_dir=jax.config.jax_compilation_cache_dir,
        events=int(c.get("kernel.compile.events", 0)),
        fresh=int(c.get("kernel.cache.compiles", 0)),
        persistent=int(c.get("kernel.cache.persistentHits", 0)),
        compile_wall_s=c.get("kernel.compile.wallNs", 0) / 1e9,
        kernel_dispatches=int(c.get("kernel.dispatches", 0)))
    for d in jax.devices():
        stats = d.memory_stats() or {}
        say(phase="device_memory", device=d.id,
            bytes_in_use=stats.get("bytes_in_use"),
            peak_bytes_in_use=stats.get("peak_bytes_in_use"),
            bytes_limit=stats.get("bytes_limit"))


# ---------------------------------------------------------------------------
# four chips: q65 through the placed ICI path (the benchmark's four-chip cell)
# ---------------------------------------------------------------------------

def _bench(*parts) -> str:
    return os.path.join(HERE, "benchmark", *parts)


def _bench_module(name: str, path: str):
    import importlib.util
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def ici_config() -> dict:
    with open(_bench("configs", f"{ICI_CONFIG}.json")) as f:
        return json.load(f)


def make_q65_data(root: str, rows, seed: int) -> dict:
    """The configuration's four tables from its own generator;
    ``rows`` (None: the configuration's 28,800,991) is ``store_sales``'
    row count, the file counts are the configuration's."""
    sys.path.insert(0, _bench())
    try:
        import datagen
    finally:
        sys.path.remove(_bench())
    conf = ici_config()
    tables = {t: dict(spec) for t, spec in conf["tables"].items()}
    if rows:
        tables["store_sales"]["rows"] = int(rows)
    return datagen.generate(conf["datagen"], root, tables, seed)


def ici_phase(root: str, conf: dict = None) -> None:
    """q65's text under the four-chip configuration's conf
    (``shuffle.transport=ici``, one shuffle partition a chip) on the
    mesh of every attached device: the answer against the
    configuration's pandas reference, every operator on the TPU, every
    exchange over the whole mesh with no input moved to another chip
    first, the fact table's scan batches spread over the chips; prints
    each device's peak bytes."""
    import jax

    from spark_rapids_tpu import TpuSparkSession
    from spark_rapids_tpu.mem.device import memory_peaks
    from spark_rapids_tpu.obs import registry as obsreg
    from spark_rapids_tpu.serve.client import ServeClient

    n_dev = len(jax.devices())
    deployed = ici_config()
    with open(_bench("sql", ICI_CONFIG, "q65.sql")) as f:
        sql = " ".join(f.read().split())
    reference = _bench_module("reference_q65_ici4",
                              _bench("reference", ICI_CONFIG, "q65.py"))
    compare = _bench_module("bench_compare", _bench("compare.py"))
    spark = TpuSparkSession({**BASE_CONF, **deployed["conf"],
                             **(conf or {})})
    for name in deployed["tables"]:
        spark.register_view(
            name, spark.read.parquet(os.path.join(root, name)))
    client = ServeClient("127.0.0.1", spark.serve_server.port)
    try:
        for execution in (1, 2):
            view = obsreg.get_registry().view()
            got, wall, obs = timed(lambda: client.sql(sql))
            moved = view.delta()["counters"]
            nodes = assert_on_device(
                "q65[ici]", spark.last_query_profile(),
                tuple(reference.SPEC["need_operators"]))
            spans = [n.extra["ici_devices"] for n in nodes
                     if "ici_devices" in n.extra]
            if not spans or set(spans) != {n_dev}:
                raise AssertionError(f"q65[ici]: exchanges spanned "
                                     f"{spans} devices, not {n_dev}")
            files = deployed["tables"]["store_sales"]["files"]
            placed = {k: int(moved.get(k, 0)) for k in (
                "scan.placed.chips", "scan.placed.batches",
                "exchange.ici.exchanges", "exchange.ici.rowsIn",
                "exchange.ici.bucketRows", "exchange.ici.movedBatches")}
            if placed["scan.placed.chips"] < min(n_dev, files) or \
                    placed["exchange.ici.movedBatches"]:
                raise AssertionError(f"q65[ici]: not placed: {placed}")
            say(phase="ici_q65", execution=execution, rows=got.num_rows,
                wall_s=wall, **obs, **placed)
        nums = compare.compare(got, reference.compute(root, {}),
                               reference.SPEC, RTOL)
        if nums["rows_diff"] or nums["key_mismatch"] or \
                nums["float_rel_err"] > RTOL:
            raise AssertionError(f"q65[ici]: off the reference: {nums}")
        say(phase="ici_q65_vs_reference", **nums)
        # the CPU rehearsal's devices report no memory stats; a TPU's
        # must, and every one must have held bytes
        peaks = memory_peaks()
        say(phase="ici_device_bytes", peak_bytes_in_use=peaks)
        if jax.devices()[0].platform == "tpu" and 0 in peaks:
            raise AssertionError(f"a device held no bytes after the "
                                 f"placed query: {peaks}")
    finally:
        client.close()
        spark.serve_server.shutdown()


# ---------------------------------------------------------------------------

def run(rows: int, seed: int, device: dict, ici: bool) -> None:
    """Every phase after the device check; any failure propagates.
    ``device`` is what the caller's device check reported — the script
    passes ``require_tpu()``'s answer, the CPU rehearsal test its own."""
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    say(phase="device", **device, bytes_limit=stats.get("bytes_limit"))
    # outside the checkout and outside the output directory: the chip
    # tool copies both whole
    root = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        t0 = time.perf_counter()
        if ici:
            say(phase="data", seed=seed,
                tables=make_q65_data(root, rows, seed),
                wall_s=time.perf_counter() - t0)
            ici_phase(root)
        else:
            say(phase="data", seed=seed, **make_data(root, rows, seed),
                wall_s=time.perf_counter() - t0)
            serve_phase(root)
            donation_reload_check()
        observations()
    finally:
        shutil.rmtree(root, ignore_errors=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run ONLY q65 through the placed ICI path "
                         "on four chips (the SF10 files)")
    ap.add_argument("--rows", type=int, default=None,
                    help="store_sales rows (default: SF10's 28.8M; the "
                         "four-chip configuration's 28,800,991 with "
                         "--chips 4)")
    ap.add_argument("--seed", type=int, default=22)
    args = ap.parse_args(argv)
    device = require_tpu(args.chips)
    rows = args.rows or (None if args.chips == 4 else SF10_ROWS)
    run(rows, args.seed, device, ici=args.chips == 4)


if __name__ == "__main__":
    main()

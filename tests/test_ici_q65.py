"""TPC-DS q65 on a mesh of four (the eight virtual devices of
``conftest.py``, four of them meshed): the deployment of the benchmark's
``tpcds-sf10-store-ici4`` at a hundredth of its fact rows.  The fact
table's scan batches lie on the chips that scan them (partition ``p`` on
chip ``p % 4``), the exchange takes them where they lie, and the answer
is the plain reference's and the one-chip path's."""

import json
import os
import sys

import numpy as np
import pyarrow.parquet as papq
import pytest

import jax
from jax.sharding import Mesh

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(CHECKOUT, "benchmark")
CONFIG = "tpcds-sf10-store-ici4"
ONE_CHIP = "tpcds-sf10-store"
CHIPS = 4
# a hundredth of the fact rows in the configuration's 16 files, one scan
# batch a file
FACT_ROWS = 288_009
BATCH_ROWS = 20_000


def _config(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


def _sql(name):
    with open(os.path.join(BENCH, "sql", name, "q65.sql"), "rb") as f:
        return f.read()


@pytest.fixture(scope="module")
def bench_modules():
    """The benchmark's generator, reference and comparison, by path."""
    sys.path.insert(0, BENCH)
    try:
        import cells
        import compare
        import datagen
        yield {"datagen": datagen, "compare": compare,
               "reference": cells._module(os.path.join(
                   BENCH, "reference", CONFIG, "q65.py"), "ref_q65_ici4")}
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(scope="module")
def root(tmp_path_factory, bench_modules):
    conf = _config(CONFIG)
    tables = {t: dict(s) for t, s in conf["tables"].items()}
    tables["store_sales"]["rows"] = FACT_ROWS
    out = str(tmp_path_factory.mktemp("q65_ici4"))
    bench_modules["datagen"].generate(conf["datagen"], out, tables, 35)
    return out


@pytest.fixture()
def mesh4(monkeypatch):
    """A mesh of the first four devices in the default mesh's place."""
    from spark_rapids_tpu.shuffle import ici
    mesh = Mesh(np.array(jax.devices()[:CHIPS]), ("shuffle",))
    monkeypatch.setattr(ici, "_DEFAULT_MESH", mesh)
    yield list(mesh.devices.flat)
    from spark_rapids_tpu.mem import device as devmgr
    devmgr.initialize(2)


def _session(root, conf):
    from spark_rapids_tpu import TpuSparkSession
    spark = TpuSparkSession({
        **conf, "spark.rapids.tpu.sql.reader.batchSizeRows": BATCH_ROWS})
    for t in ("store_sales", "item", "store", "date_dim"):
        spark.register_view(t, spark.read.parquet(os.path.join(root, t)))
    return spark


def _counters():
    from spark_rapids_tpu.obs import registry
    return registry.get_registry().view()


@pytest.fixture()
def four_chips(root, mesh4):
    return _session(root, _config(CONFIG)["conf"])


def test_the_configuration_is_the_one_chip_configuration_on_four_chips():
    ici4, one = _config(CONFIG), _config(ONE_CHIP)
    assert ici4["tables"] == one["tables"]
    assert ici4["datagen"] == one["datagen"]
    assert _sql(CONFIG) == _sql(ONE_CHIP)
    assert ici4["chips"] == CHIPS
    added = {"spark.rapids.tpu.shuffle.transport": "ici",
             "spark.rapids.tpu.sql.shuffle.partitions": CHIPS}
    assert ici4["conf"] == {**one["conf"], **added}
    assert set(one["guarantees"]) < set(ici4["guarantees"])
    assert set(one["assumed"]) | {"partitions", "file_placement"} \
        == set(ici4["assumed"])


def test_q65_on_four_chips_equals_the_reference(four_chips, root,
                                                bench_modules):
    view = _counters()
    got = four_chips.sql(_sql(CONFIG).decode()).collect()
    moved = view.delta()["counters"]
    ref = bench_modules["reference"]
    nums = bench_modules["compare"].compare(
        got, ref.compute(root, {}), ref.SPEC, 1e-9)
    assert nums == {"rows_diff": 0, "key_mismatch": 0,
                    "float_rel_err": pytest.approx(0, abs=1e-9)}
    assert got.num_rows == 100
    # the block's GROUP BY, sb's GROUP BY and the ORDER BY, each taken
    # where its rows lay
    assert moved["exchange.ici.exchanges"] == 3
    assert moved.get("exchange.ici.movedBatches", 0) == 0
    assert moved["kernel.dispatches.exch_counts"] == 3 * CHIPS
    assert moved["scan.placed.chips"] >= CHIPS
    shapes = ref.exchange_shapes(root)
    assert [s["name"] for s in shapes] == ["block", "sb", "order_by"]
    assert moved["exchange.ici.rowsIn"] == sum(s["rows"] for s in shapes)


def test_every_scan_batch_lies_on_chip_p_mod_4(four_chips, root, mesh4):
    df = four_chips.read.parquet(os.path.join(root, "store_sales")) \
        .select("ss_item_sk", "ss_sales_price")
    batches = four_chips._execute_device(df.plan)
    files = _config(CONFIG)["tables"]["store_sales"]["files"]
    assert len(batches) == files
    homes = [b.columns[0].data.devices() for b in batches]
    assert homes == [{mesh4[p % CHIPS]} for p in range(files)]
    # every column of a batch lies with the first
    for b, home in zip(batches, homes):
        assert all(a.devices() == home for c in b.columns
                   for a in (c.data, c.validity))
    share = [sum(1 for h in homes if h == {d}) for d in mesh4]
    assert max(share) - min(share) <= 1
    assert sum(int(b.num_rows) for b in batches) == FACT_ROWS


def test_the_upload_sets_are_kept_on_the_chips_that_decode_them(
        four_chips, root, mesh4):
    from spark_rapids_tpu.io import scan_cache
    scan_cache.clear()
    df = four_chips.read.parquet(os.path.join(root, "store_sales")) \
        .select("ss_store_sk")
    four_chips._execute_device(df.plan)
    held = scan_cache.stats()["assembled_bytes_by_device"]
    assert set(held) == set(mesh4)
    assert max(held.values()) <= 1.5 * min(held.values())
    view = _counters()
    four_chips._execute_device(df.plan)
    moved = view.delta()["counters"]
    # kept where it lies: served from there (or from the shared-scan
    # window's decoded batches), nothing packed or uploaded again
    assert "scan.bytesUploaded" not in moved, moved
    assert "scan.assembledCacheMisses" not in moved, moved


def test_no_exchange_input_changes_chip_before_the_collective(
        four_chips, monkeypatch, mesh4):
    from spark_rapids_tpu.exec import placement
    from spark_rapids_tpu.shuffle import exchange, ici
    seen = []
    inner, placed = (exchange.TpuShuffleExchangeExec._exchange_ici,
                     ici.exchange_placed)

    def watch_inputs(self, batches, devices, info):
        seen.append(("in", [placement.device_of(b) for b in batches]))
        return inner(self, batches, devices, info)

    def watch_placed(held, targets, *a, **kw):
        seen.append(("held", [None if b is None else placement.device_of(b)
                              for b in held]))
        return placed(held, targets, *a, **kw)
    monkeypatch.setattr(exchange.TpuShuffleExchangeExec, "_exchange_ici",
                        watch_inputs)
    monkeypatch.setattr(ici, "exchange_placed", watch_placed)
    four_chips.sql(_sql(CONFIG).decode()).collect()
    ins = [devs for kind, devs in seen if kind == "in"]
    helds = [devs for kind, devs in seen if kind == "held"]
    assert len(ins) == len(helds) == 3
    for devs, held in zip(ins, helds):
        # what a chip hands to the collective is what lay on it
        assert set(devs) <= set(mesh4)
        assert held == [d if d in devs else None for d in mesh4]
    # the first exchange takes four scan batches' rows from every chip
    assert sorted(ins[0], key=lambda d: d.id) == sorted(
        mesh4 * 4, key=lambda d: d.id)


def test_four_chips_answer_is_the_one_chip_answer_row_for_row(
        four_chips, root):
    sql = _sql(CONFIG).decode()
    four = four_chips.sql(sql).collect()
    one_session = _session(root, _config(ONE_CHIP)["conf"])
    view = _counters()
    one = one_session.sql(sql).collect()
    moved = view.delta()["counters"]
    # nothing of the placement on the one-chip path
    assert not [k for k in moved if k.startswith(("scan.placed",
                                                  "exchange.ici"))]
    assert four.schema.names == one.schema.names
    for name in four.schema.names:
        a, b = four.column(name).to_pylist(), one.column(name).to_pylist()
        if isinstance(a[0], float):
            np.testing.assert_allclose(a, b, rtol=1e-9, atol=0)
        else:
            assert a == b, name


@pytest.mark.parametrize("transport,devices", [
    ("device", 8), ("local", 8), ("ici", 1)],
    ids=["device-transport", "local-transport", "ici-on-one-device"])
def test_the_one_device_path_places_nothing(root, monkeypatch, transport,
                                            devices):
    """No mesh of several chips under the ICI transport: every scan
    batch on the default device, one gate, the plan what it was."""
    from spark_rapids_tpu.exec import placement
    from spark_rapids_tpu.mem import device as devmgr
    from spark_rapids_tpu.shuffle import ici
    monkeypatch.setattr(ici, "_DEFAULT_MESH", Mesh(
        np.array(jax.devices()[:devices]), ("shuffle",)))
    conf = {**_config(ONE_CHIP)["conf"],
            "spark.rapids.tpu.shuffle.transport": transport}
    spark = _session(root, conf)
    assert placement.mesh_devices(spark.conf) == []
    assert devmgr.chips() == 1
    view = _counters()
    df = spark.read.parquet(os.path.join(root, "store_sales")) \
        .select("ss_item_sk")
    batches = spark._execute_device(df.plan)
    assert {d for b in batches for d in b.columns[0].data.devices()} \
        == {jax.devices()[0]}
    assert not [k for k in view.delta()["counters"]
                if k.startswith("scan.placed")]
    plan = spark.sql(_sql(ONE_CHIP).decode()).explain_string("physical")
    default = _session(root, _config(ONE_CHIP)["conf"]).sql(
        _sql(ONE_CHIP).decode()).explain_string("physical")
    if transport != "ici":
        assert plan == default


def test_a_sort_over_placed_batches_is_a_total_order(four_chips, root):
    """The range exchange under a sort: bounds from each chip's sample,
    rows placed between them where they lie."""
    view = _counters()
    got = four_chips.sql(
        "select ss_ticket_number, ss_item_sk, ss_sales_price "
        "from store_sales where ss_sales_price > 180 "
        "order by ss_sales_price desc, ss_ticket_number, ss_item_sk"
    ).collect()
    moved = view.delta()["counters"]
    want = papq.read_table(
        os.path.join(root, "store_sales"),
        columns=["ss_ticket_number", "ss_item_sk", "ss_sales_price"]
    ).to_pandas()
    want = want[want.ss_sales_price > 180].sort_values(
        ["ss_sales_price", "ss_ticket_number", "ss_item_sk"],
        ascending=[False, True, True])
    assert got.column("ss_ticket_number").to_pylist() == \
        want.ss_ticket_number.tolist()
    assert got.column("ss_item_sk").to_pylist() == want.ss_item_sk.tolist()
    assert moved["kernel.dispatches.exch_rplace"] == CHIPS
    assert moved.get("exchange.ici.movedBatches", 0) == 0


def test_every_host_copy_on_four_chips_is_a_named_read(four_chips):
    """The four-chip cell's statement copies nothing to the host outside
    ``columnar/batch.read_host``; each of its three exchanges reads the
    chips' per-peer counts once, and the sort's also its key samples.
    With tracing on, the spans of the chips' task threads carry their
    chip, and each barrier's waits are ``chip.peerWait`` spans."""
    from spark_rapids_tpu.obs import trace
    from tests.host_copies import copies_outside_read_host
    trace.configure(True, 65536)
    trace.clear()
    try:
        view = _counters()
        with copies_outside_read_host() as outside:
            four_chips.sql(_sql(CONFIG).decode()).collect()
        moved = view.delta()["counters"]
        spans = four_chips.last_query_profile().spans
    finally:
        trace.configure(False)
        trace.clear()
    assert outside == [], outside[0]
    assert moved["device.reads"] == sum(
        v for k, v in moved.items() if k.startswith("device.reads."))
    assert moved["device.reads.exchange.countWait"] == 4
    ids = {d.id for d in jax.devices()[:CHIPS]}
    assert {sp["chip"] for sp in spans} - {None} <= ids
    assert any(sp["chip"] is not None and sp["cat"] == "device.read"
               for sp in spans)
    for sp in spans:
        if sp["name"] == "chip.peerWait":
            assert sp["chip"] in ids and sp["args"]["stage"] in (
                "exchange", "reuse", "join", "collect")


def test_task_slots_count_a_chip():
    """Two chips' gates are two gates: a held slot of chip 0 does not
    keep chip 1's task waiting, and a thread that names no chip works
    for chip 0."""
    from spark_rapids_tpu.mem import device as devmgr
    devmgr.initialize(1, chips=2)
    try:
        assert (devmgr.slots(), devmgr.chips()) == (1, 2)
        with devmgr.tpu_semaphore():
            assert devmgr._get().available() == 0
            with devmgr.task_chip(1):
                assert devmgr.current_chip() == 1
                assert devmgr._get().available() == 1
                with devmgr.tpu_semaphore():
                    assert devmgr._get().available() == 0
            assert devmgr.current_chip() == 0
    finally:
        devmgr.initialize(2)
    assert devmgr.chips() == 1


def test_every_device_reports_its_own_peak_and_budget():
    from spark_rapids_tpu.mem import device as devmgr
    peaks = devmgr.memory_peaks()
    assert len(peaks) == len(jax.devices())
    assert all(isinstance(p, int) and p >= 0 for p in peaks)
    mgr = devmgr.TpuDeviceManager()
    assert len(mgr.hbm_budgets) == len(jax.devices())
    assert mgr.hbm_budget == min(mgr.hbm_budgets)


def test_the_assembled_sets_budget_counts_a_chip():
    """A chip over the budget lets go of its own oldest sets and of no
    other chip's."""
    from spark_rapids_tpu.io import scan_cache
    before = scan_cache._MAX_BYTES
    scan_cache.clear()
    scan_cache.configure(True, 1000)
    try:
        for chip, key in (("a", 1), ("b", 2), ("a", 3), ("a", 4)):
            scan_cache.put_assembled(
                (((("file", str(key)), 0),), key), object(), 400,
                device=chip)
        held = scan_cache.stats()["assembled_bytes_by_device"]
        assert held == {"a": 800, "b": 400}
        assert scan_cache.get_assembled(((((("file", "1"), 0),), 1))) \
            is None
        assert scan_cache.get_assembled(((((("file", "2"), 0),), 2))) \
            is not None
        assert scan_cache.pressure_spill() == 1200
        assert scan_cache.stats()["assembled_bytes_by_device"] == {}
    finally:
        scan_cache.configure(True, before)
        scan_cache.clear()


@pytest.mark.parametrize("n_dev,slots", [(4, 2), (8, 3), (1, 2)])
def test_drain_by_chip_keeps_every_batch_of_every_partition(n_dev, slots):
    """More task threads than cores, a short switch interval: every
    partition's batches arrive whole and in order, a chip never runs
    more than its slots, and one failing partition fails the drain."""
    import threading

    from spark_rapids_tpu.exec.placement import drain_by_chip
    from spark_rapids_tpu.mem import device as devmgr
    devmgr.initialize(slots, chips=n_dev)
    before = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        lock = threading.Lock()
        running, worst = [0] * n_dev, [0] * n_dev

        def part(p, fail=False):
            chip = p % n_dev
            assert devmgr.current_chip() == (chip if n_dev > 1 else 0)
            with lock:
                running[chip] += 1
                worst[chip] = max(worst[chip], running[chip])
            try:
                for k in range(50):
                    if fail and k == 25:
                        raise ValueError(f"partition {p}")
                    yield (p, k)
            finally:
                with lock:
                    running[chip] -= 1
        got = [[] for _ in range(64)]
        drain_by_chip([part(p) for p in range(64)],
                      lambda p, b: got[p].append(b), stage="test")
        assert got == [[(p, k) for k in range(50)] for p in range(64)]
        assert max(worst) <= (slots if n_dev > 1 else 1)
        with pytest.raises(ValueError, match="partition 5"):
            drain_by_chip([part(p, fail=p == 5) for p in range(16)],
                          lambda p, b: None, stage="test")
    finally:
        sys.setswitchinterval(before)
        devmgr.initialize(2)


_KEY_PROBE = """
import numpy as np, jax, jax.numpy as jnp
from jax._src import cache_key, compiler, xla_bridge
from spark_rapids_tpu.exec import kernel_cache
module = jax.jit(lambda x: x * 2 + 1).lower(jnp.arange(8)).compiler_ir()
backend = xla_bridge.get_backend()
def key(ids):
    devs = np.array([jax.devices()[i] for i in ids])
    opts = compiler.get_compile_options(
        num_replicas=1, num_partitions=len(ids),
        device_assignment=np.array([ids]))
    return cache_key.get(module, devs, opts, backend)
def keys():
    return [key([i]) for i in range(4)] + [key([0, 1]), key([2, 3])]
before = keys()
kernel_cache.share_executables_across_chips()
kernel_cache.share_executables_across_chips()      # idempotent
after = keys()
assert len(set(before[:4])) == 4, "jax keys a program once a device"
assert set(after[:4]) == {before[0]}, "one entry, the first device's own"
assert after[4:] == before[4:] and after[4] != after[5], \\
    "a program over several devices keeps jax's key"
print("ok")
"""


def test_a_single_device_program_has_one_cache_key_whichever_chip_runs_it():
    """``share_executables_across_chips`` in a process of its own (the
    patch stays for a process's life): chips 1-3 read chip 0's entry,
    chip 0's key is what jax gave it."""
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=CHECKOUT,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", _KEY_PROBE], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip().endswith("ok")

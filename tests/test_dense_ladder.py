"""The capacity ladder's dense part (exec/kernel_abi._DENSE_TIER_START)
at suite scale: with its start patched down to 64 every power of two
from 64 up is a rung (64, 128, 256, 512, ...), so a scan batch of 100
rows is born at 128 slots and the join, the aggregate's ladder, the
partials' cut, their concatenation, the sort and the ICI exchange all
run at rungs that are not powers of four.  The answers are the default
ladder's, and the scan counts the rows and slots it emitted."""

import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as papq
import pytest

import jax
from jax.sharding import Mesh

from spark_rapids_tpu import TpuSparkSession
from spark_rapids_tpu.exec import kernel_abi
from spark_rapids_tpu.exec import kernel_cache as kc
from spark_rapids_tpu.exec import tpu_aggregate as agg
from spark_rapids_tpu.obs import registry as obsreg
from tests.parity import assert_tables_equal

# one scan batch a file: 100 rows -> 128 slots, 129 -> 256, 70 -> 128
_FILE_ROWS = (100, 117, 129, 70, 128)
_CONF = {"spark.rapids.tpu.sql.variableFloatAgg.enabled": True,
         "spark.rapids.tpu.serve.resultCache.enabled": False,
         "spark.rapids.tpu.sql.reader.batchSizeRows": 130}
_ICI = {"spark.rapids.tpu.shuffle.transport": "ici",
        "spark.rapids.tpu.sql.shuffle.partitions": 4}

_JOIN_AGG_SORT = """
    select d.name, f.g, sum(f.v) as sv, count(*) as n, min(f.k) as lo
    from fact f join dim d on f.k = d.k
    where d.keep = 1
    group by d.name, f.g order by sv desc, d.name, f.g"""
_AGG = "select g, sum(v) as sv, count(k) as n from fact group by g"
_SORT = "select k, g, v from fact order by v desc, g limit 150"


def _write(root: str) -> None:
    rng = np.random.default_rng(36)
    os.makedirs(os.path.join(root, "fact"))
    os.makedirs(os.path.join(root, "dim"))
    for i, n in enumerate(_FILE_ROWS):
        k = rng.integers(0, 24, n).astype(np.int32)
        papq.write_table(pa.table({
            "k": pa.array(k, mask=rng.random(n) < 0.05),
            "g": pa.array(rng.integers(0, 37, n).astype(np.int32)),
            "v": pa.array(np.round(rng.random(n) * 100, 2)),
        }), os.path.join(root, "fact", f"part-{i}.parquet"))
    papq.write_table(pa.table({
        "k": pa.array(np.arange(20, dtype=np.int32)),
        "name": pa.array([f"store {i % 7}" for i in range(20)]),
        "keep": pa.array((np.arange(20) % 3 != 0).astype(np.int32)),
    }), os.path.join(root, "dim", "part-0.parquet"))


def _session(root: str, conf=None) -> TpuSparkSession:
    spark = TpuSparkSession({**_CONF, **(conf or {})})
    for t in ("fact", "dim"):
        spark.register_view(t, spark.read.parquet(os.path.join(root, t)))
    return spark


@pytest.fixture(scope="module")
def roots(tmp_path_factory):
    """The same files twice: what one ladder's scan decoded or kept is
    never served to the other's."""
    out = []
    for name in ("default", "dense"):
        root = str(tmp_path_factory.mktemp(f"ladder_{name}"))
        _write(root)
        out.append(root)
    return out


@pytest.fixture(scope="module")
def default_answers(roots):
    """Every statement under the ladder as it ships (module scope: made
    before any test patches it)."""
    assert kernel_abi._DENSE_TIER_START == 1 << 20
    spark = _session(roots[0])
    return {q: spark.sql(q).collect() for q in (_JOIN_AGG_SORT, _AGG,
                                                _SORT)}


@pytest.fixture
def dense_from_64(monkeypatch, default_answers):
    """The dense part from 64 rows for one test, the aggregate's own
    ladder engaged at that scale too; no program traced under another
    threshold is reused, before or after."""
    monkeypatch.setattr(kernel_abi, "_DENSE_TIER_START", 64)
    monkeypatch.setattr(agg, "_LADDER_MIN_RUNG", 8)
    kc.clear()
    yield
    kc.clear()


@pytest.fixture
def mesh4(monkeypatch):
    """A mesh of the first four virtual devices in the default mesh's
    place (as tests/test_ici_q65.py)."""
    from spark_rapids_tpu.shuffle import ici
    mesh = Mesh(np.array(jax.devices()[:4]), ("shuffle",))
    monkeypatch.setattr(ici, "_DEFAULT_MESH", mesh)
    yield list(mesh.devices.flat)
    from spark_rapids_tpu.mem import device as devmgr
    devmgr.initialize(2)


def test_patched_ladder_has_the_odd_rungs(dense_from_64):
    assert [kernel_abi.tier_rows(n) for n in
            (1, 17, 64, 65, 100, 129, 257, 513, 1025)] == \
        [16, 64, 64, 128, 128, 256, 512, 1024, 2048]
    assert kernel_abi.is_tier(128) and kernel_abi.is_tier(512)


def test_scan_batches_are_born_at_the_odd_rung(dense_from_64, roots):
    spark = _session(roots[1])
    view = obsreg.get_registry().view()
    batches = spark._execute_device(
        spark.read.parquet(os.path.join(roots[1], "fact")).plan)
    assert [(int(b.num_rows), b.capacity) for b in batches] == \
        [(100, 128), (117, 128), (129, 256), (70, 128), (128, 128)]
    moved = view.delta()["counters"]
    assert moved["scan.batch.rows"] == sum(_FILE_ROWS)
    assert moved["scan.batch.slots"] == 4 * 128 + 256


def test_scan_counts_rows_and_slots_under_the_default_ladder(roots):
    spark = _session(roots[0])
    view = obsreg.get_registry().view()
    spark.sql(_AGG).collect()
    moved = view.delta()["counters"]
    assert moved["scan.batch.rows"] == sum(_FILE_ROWS)
    assert moved["scan.batch.slots"] == 256 * len(_FILE_ROWS)


@pytest.mark.parametrize("query", [_JOIN_AGG_SORT, _AGG, _SORT],
                         ids=["join_agg_sort", "agg_partials", "sort"])
def test_answers_at_odd_rungs_are_the_default_ladders(
        dense_from_64, default_answers, roots, query):
    spark = _session(roots[1])
    view = obsreg.get_registry().view()
    got = spark.sql(query).collect()
    moved = view.delta()["counters"]
    assert_tables_equal(default_answers[query], got,
                        ignore_order=query is _AGG)
    assert moved["scan.batch.slots"] >= 4 * 128 + 256   # born odd
    if query is _JOIN_AGG_SORT:
        assert moved["join.path.direct"] >= 1
        assert "join.path.sortMerge" not in moved
    if query is not _SORT:
        # five partials, cut to their tier and merged
        assert moved["kernel.dispatches.agg_update"] == len(_FILE_ROWS)
        assert moved["kernel.dispatches.agg_merge"] >= 1


@pytest.mark.parametrize("query", [_JOIN_AGG_SORT, _AGG],
                         ids=["join_agg_sort", "agg_partials"])
def test_ici_exchange_at_odd_rungs_gives_the_default_answers(
        dense_from_64, mesh4, monkeypatch, default_answers, roots, query):
    from spark_rapids_tpu.shuffle import ici
    placed, receivers = ici.exchange_placed, []

    def watch(*a, **kw):
        out, info = placed(*a, **kw)
        receivers.extend(info["capacities"])
        return out, info
    monkeypatch.setattr(ici, "exchange_placed", watch)
    spark = _session(roots[1], _ICI)
    view = obsreg.get_registry().view()
    got = spark.sql(query).collect()
    moved = view.delta()["counters"]
    assert_tables_equal(default_answers[query], got,
                        ignore_order=query is _AGG)
    assert moved["exchange.ici.exchanges"] >= 1
    assert moved.get("exchange.ici.movedBatches", 0) == 0
    # a receiver of 65-128 rows is cut to 128 slots, no rung of the
    # stride's ladder
    assert 128 in receivers, receivers


def test_scan_fill_pct_is_rows_over_slots_inside_the_window():
    """The benchmark's reader (benchmark/metrics/scan_fill_pct.py):
    100 x rows / slots of the window's counters; nothing where the
    window decoded no batch."""
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "metrics",
        "scan_fill_pct.py")
    spec = importlib.util.spec_from_file_location("scan_fill_pct", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    obsreg.get_registry().inc("scan.batch.slots", 0)
    assert mod.read({"counters": {}}) is None
    assert mod.read({"counters": {
        "scan.batch.rows": 16 * 1_800_061,
        "scan.batch.slots": 16 * 2_097_152}}) == \
        pytest.approx(85.834, abs=1e-3)

"""``tpcds-sf10.agg-ici4``: the four-chip cell rehearsed on four
virtual CPU devices, its four readers (``metrics/exchange_device_s.py``,
``exchange_roofline.py``, ``exchange_rows.py``,
``chip_busy_min_pct.py``) each on a synthetic ``run``, and the seconds
the roofline is held against (``exchange_bytes.py``).

The cell needs four devices even to rehearse, and
``test_rehearse.py`` starts every listed cell with this process's
environment: importing this module gives that environment four virtual
CPU devices where nothing else has set a count."""

import json
import os
import subprocess
import sys
import types

import pytest

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")

import cells                       # noqa: E402
import exchange_bytes              # noqa: E402
from conftest import CHECKOUT      # noqa: E402

CELL = "tpcds-sf10.agg-ici4"
PEAKS = {"hbm_bytes_per_s": 819e9, "ici_bits_per_s": 1600e9}
SHAPES = [{"name": "block", "rows": 5_730_000, "row_bytes": 16},
          {"name": "sb", "rows": 3_410_000, "row_bytes": 12},
          {"name": "order_by", "rows": 330_000, "row_bytes": 250.0}]


def test_exchange_seconds_from_shapes():
    got = exchange_bytes.statement_exchange_seconds(SHAPES, 4, PEAKS)
    total = 5_730_000 * 16 + 3_410_000 * 12 + 330_000 * 250.0
    assert got["exchanges"] == 3 and got["bytes"] == total
    assert got["hbm_s"] == pytest.approx(2 * total / (4 * 819e9))
    # three quarters of the rows change chips, each chip sends its share
    assert got["ici_s"] == pytest.approx(0.75 * total / (4 * 200e9))
    assert got["least_s"] == got["hbm_s"] + got["ici_s"]
    one = exchange_bytes.statement_exchange_seconds(SHAPES, 1, PEAKS)
    assert one["ici_s"] == 0


def a_run(programs, shapes=SHAPES, share=1.0, chips=4, busy=None,
          counters=None):
    reference = types.SimpleNamespace(exchange_shapes=lambda root: shapes) \
        if shapes is not None else types.SimpleNamespace()
    stmt = types.SimpleNamespace(name="q65", reference=reference)
    trace = None if programs is None else {
        "covered": [(0, share)], "queries": share, "chips": chips,
        "device_programs": programs, "window_s": 5.0,
        "busy_s_per_chip": busy or [4.0, 4.5, 3.0, 4.2]}
    return {"trace": trace, "root": "/nowhere",
            "completed": [{"index": 0, "stmt": "q65"}],
            "cell": types.SimpleNamespace(statements=[stmt]),
            "counters": counters or {}, "peaks": PEAKS}


PROGRAMS = [["jit_probe_emit_u:*", 1.1], ["jit_ici_exchange:*", 0.9],
            ["jit_pq_fused6:*", 0.8], ["jit_exch_target:*", 0.05],
            ["jit_exch_counts:*", 0.01], ["jit_ici_extract:*", 0.04],
            ["jit_exchange:*", 9.0], ["jit_icicle:*", 9.0]]


def test_device_seconds_reads_the_exchange_programs():
    assert cells.reader("exchange_device_s")(a_run(PROGRAMS)) == \
        pytest.approx(1.0)
    # two queries touched: seconds a query
    run = a_run(PROGRAMS)
    run["trace"]["covered"] = [(0, 1.0), (1, 0.5)]
    assert cells.reader("exchange_device_s")(run) == pytest.approx(0.5)


def test_roofline_is_a_share_under_a_hundred():
    least = exchange_bytes.statement_exchange_seconds(
        SHAPES, 4, PEAKS)["least_s"]
    got = cells.reader("exchange_roofline")(a_run(PROGRAMS))
    assert got == pytest.approx(100 * least / 1.0)
    assert 0 < got < 100, "a share of a roofline over 100% is a fault"
    # a third of a query traced: a third of its exchanges against what ran
    part = a_run([["jit_ici_exchange:*", 0.5]], share=1 / 3)
    assert cells.reader("exchange_roofline")(part) == \
        pytest.approx(100 * least / 3 / 0.5)
    assert cells.reader("exchange_roofline")(part) < 100


@pytest.mark.parametrize("metric", ["exchange_device_s",
                                    "exchange_roofline"])
@pytest.mark.parametrize("run", [
    a_run(None), a_run([["jit_pq_fused6:*", 0.3],
                        ["jit_exchange:*", 2.0]])],
    ids=["no-trace", "no-exchange-program"])
def test_trace_readers_have_nothing_to_read(metric, run):
    assert cells.reader(metric)(run) is None


def test_roofline_needs_the_reference_s_shapes():
    assert cells.reader("exchange_roofline")(
        a_run(PROGRAMS, shapes=None)) is None


def test_exchange_rows_reads_the_counter():
    from spark_rapids_tpu.obs import registry
    read = cells.reader("exchange_rows")
    reg = registry.get_registry()
    if "exchange.ici.rowsIn" not in reg.snapshot()["counters"]:
        # a program with no such counter (the parent commit)
        assert read(a_run(None, counters={})) is None
    reg.inc("exchange.ici.rowsIn", 0)
    assert read(a_run(None, counters={"exchange.ici.rowsIn": 18.0})) == 18
    two = a_run(None, counters={"exchange.ici.rowsIn": 18.0})
    two["completed"] = two["completed"] * 2
    assert read(two) == 9
    assert read(a_run(None, counters={})) == 0


def test_chip_busy_min_is_the_least_busy_chip(capsys):
    read = cells.reader("chip_busy_min_pct")
    assert read(a_run(PROGRAMS)) == pytest.approx(60.0)
    seen = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert seen["phase"] == "chips"
    assert seen["busy_s_per_chip"] == [4.0, 4.5, 3.0, 4.2]
    assert "peak_bytes_per_chip" in seen and seen["exchanges"] == []
    spans = [{"name": "exchange.ici", "ts_ns": 2, "args": {"rows_in": 5}},
             {"name": "agg.update", "ts_ns": 0},
             {"name": "exchange.ici", "ts_ns": 1, "args": {"rows_in": 9}}]
    with_spans = a_run(PROGRAMS)
    with_spans["completed"][0]["profile"] = types.SimpleNamespace(
        spans=spans)
    read(with_spans)
    seen = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert seen["exchanges"] == [{"rows_in": 9}, {"rows_in": 5}]
    assert read(a_run(None)) is None
    assert capsys.readouterr().out == ""
    one = a_run(PROGRAMS, chips=1, busy=[2.5])
    assert read(one) == pytest.approx(50.0)


def test_the_cell_rehearsed_on_four_virtual_devices():
    def run(trace):
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS="--xla_force_host_platform_device_count=4")
        proc = subprocess.run(
            [sys.executable, "benchmark/run.py", "--workload", CELL,
             "--seed", "3500000017", "--seconds", "1", "--trace",
             str(trace), "--rehearse"], cwd=CHECKOUT, env=env,
            capture_output=True, text=True, timeout=1500)
        assert proc.returncode == 0, proc.stderr[-3000:]
        return json.loads(proc.stdout.strip().splitlines()[-1])
    timed = run(0)
    assert timed["correct"] and timed["failed"] == 0 < timed["attempted"]
    assert set(timed["metrics"]) == {"query_s", "setup_s"}
    assert timed["device"]["count"] == 4
    assert timed["checks"]["missing_ops"] == {"value": 0, "limit": 0}
    traced = run(1)
    assert traced["correct"]
    assert traced["metrics"]["exchange_rows"]["value"] > 0
    # no device number from a CPU run
    assert not {"exchange_device_s", "exchange_roofline",
                "chip_busy_min_pct", "peak_hbm_gb"} & set(traced["metrics"])


def test_fewer_devices_than_the_cell_needs_ends_the_run():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "1", "--seconds", "1", "--trace", "0", "--rehearse"],
        cwd=CHECKOUT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode != 0 and "needs 4 device" in proc.stderr
    assert proc.stdout.strip() == ""

"""The command end to end, as the driver starts it, on the CPU with
``--rehearse``: every cell whose files are in the tree, the exit
without a chip, and a later PR's cell added as new files and new
``BENCHMARK.json`` entries only."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH, CHECKOUT

END_TO_END = {"query_s", "setup_s"}


def run_cell(checkout, name, trace=0, rehearse=True, seed=7):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, "benchmark/run.py", "--workload", name, "--seed",
           str(seed), "--seconds", "1", "--trace", str(trace)]
    return subprocess.run(cmd + (["--rehearse"] if rehearse else []),
                          cwd=checkout, env=env, capture_output=True,
                          text=True, timeout=900)


def copy_of_the_benchmark(tmp_path):
    """A checkout holding the engine by a link, and a copy of the
    benchmark to which cells can be added."""
    root = tmp_path / "checkout"
    root.mkdir()
    os.symlink(os.path.join(CHECKOUT, "spark_rapids_tpu"),
               root / "spark_rapids_tpu")
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(CHECKOUT, "BENCHMARK.json"), root)
    return root


def last_line(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def cells_listed():
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


@pytest.mark.parametrize("name", cells_listed())
def test_cell_end_to_end(tmp_path, name):
    root = copy_of_the_benchmark(tmp_path)
    timed = last_line(run_cell(root, name, 0))
    assert timed["correct"] and timed["failed"] == 0 < timed["attempted"]
    assert set(timed["metrics"]) == END_TO_END
    assert set(timed) == {"correct", "attempted", "failed", "metrics",
                          "device", "checks"}
    assert timed["device"]["platform"] == "cpu"
    traced = run_cell(root, name, 1)
    line = last_line(traced)
    assert line["correct"] and not set(line["metrics"]) & END_TO_END
    assert {"plan_ms", "dispatches_per_query", "first_answer_s"} \
        <= set(line["metrics"])
    # no device number from a CPU run
    assert not {"stream_roofline", "device_idle_pct", "peak_hbm_gb"} \
        & set(line["metrics"])
    assert "busy_s" not in line["device"] and "breakdown" not in line
    assert traced.stderr.strip().splitlines()[-1].startswith("check ")


def test_without_a_chip_nothing_is_run(tmp_path):
    proc = run_cell(CHECKOUT, "tpch-sf1.q1", rehearse=False)
    assert proc.returncode != 0 and "no TPU" in proc.stderr
    assert proc.stdout.strip() == ""
    bare = tmp_path / "bare"
    bare.mkdir()
    shutil.copytree(BENCH, bare / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(CHECKOUT, "BENCHMARK.json"), bare)
    proc = run_cell(bare, "tpch-sf1.q1")
    assert proc.returncode != 0 and '"correct"' not in proc.stdout


Q6_SQL = """select l_suppkey, count(*) as cnt, sum(l_quantity) as qty,
       avg(l_extendedprice) as aep
from lineitem where l_extendedprice > :lo group by l_suppkey
"""
Q6_REFERENCE = '''
import os
import pyarrow.compute as pc
import pyarrow.dataset as pads

SPEC = {"keys": ["l_suppkey"], "exact": ["cnt"], "approx": ["qty", "aep"],
        "ordered": False, "order_float": None, "limit": None,
        "reads": {"lineitem": ["l_suppkey", "l_quantity",
                               "l_extendedprice"]},
        "need_operators": ["ParquetScan", "HashAggregate"]}


def compute(root, bindings, float_dtype="float64"):
    t = pads.dataset(os.path.join(root, "lineitem")).to_table(
        columns=["l_suppkey", "l_quantity", "l_extendedprice"],
        filter=pc.field("l_extendedprice") > bindings["lo"])
    for i in (1, 2):
        t = t.set_column(i, t.column_names[i],
                         t.column(i).cast(float_dtype))
    return t.group_by("l_suppkey").aggregate(
        [([], "count_all"), ("l_quantity", "sum"),
         ("l_extendedprice", "mean")]).rename_columns(
        {"count_all": "cnt", "l_quantity_sum": "qty",
         "l_extendedprice_mean": "aep"}).sort_by("l_suppkey")
'''
Q6_CELL = {
    "name": "tpch-sf1.q6-bindings", "config": "tpch-sf1-lineitem",
    "traffic": "q6-bindings.closed-1", "tables": ["lineitem"],
    "loop": {"kind": "closed", "clients": 1},
    "statements": [{"name": "q6", "weight": 1, "mode": "prepared",
                    "params": {"lo": "double"},
                    "bindings": {"lo": {"low": 5000.0, "high": 90000.0,
                                        "decimals": 2}}}],
    "limits": {"float_rel_err": 1e-9},
    "why": "one client, a prepared q6-class aggregate, a fresh :lo from "
           "5,000.00 to 90,000.00 each execution: bindings as arguments"}


def test_a_later_cell_is_new_files_and_new_entries_only(tmp_path):
    """README.md's worked example, ``tpch-sf1.q6-bindings``."""
    root = copy_of_the_benchmark(tmp_path)
    bench_dir = root / "benchmark"
    before = {p: p.read_bytes() for p in bench_dir.rglob("*") if p.is_file()}
    (bench_dir / "sql" / "tpch-sf1-lineitem" / "q6.sql").write_text(Q6_SQL)
    (bench_dir / "reference" / "tpch-sf1-lineitem" / "q6.py") \
        .write_text(Q6_REFERENCE)
    (bench_dir / "workloads" / "tpch-sf1.q6-bindings.json") \
        .write_text(json.dumps(Q6_CELL))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append(
        {"name": Q6_CELL["name"], "config": Q6_CELL["config"],
         "traffic": Q6_CELL["traffic"], "chips": 1, "why": Q6_CELL["why"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    line = last_line(run_cell(root, "tpch-sf1.q6-bindings", trace=1))
    assert line["correct"] and line["attempted"] > 0
    # each binding is a new program today (ROADMAP A2): the cell's layer
    # metric reads them
    assert line["metrics"]["window_programs"]["value"] >= 0
    assert all(p.read_bytes() == b for p, b in before.items())

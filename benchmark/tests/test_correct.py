"""What decides ``correct`` has been shown to fail.

The control: the plain reference computed in float32 put in the
program's place fails a number of the comparison.  The
faults: a run driven through the harness with the timed path broken
underneath (an answer altered where it is produced; half of the fact
table left out of the scan) ends with ``correct`` false, and a sound
run with ``correct`` true.  At a size a test run can hold; the readings
at the cells' own sizes are in PERF.md.
"""

import json
import os
import shutil
import tempfile

import pyarrow as pa
import pyarrow.compute as pc
import pytest

import cells
import compare
import control
import datagen
import run

with open(os.path.join(cells.CHECKOUT, "BENCHMARK.json")) as f:
    CELLS = [w["name"] for w in json.load(f)["workloads"]]


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("seed", [11, 2147483659, 4000000007])
def test_control_is_not_correct(name, seed):
    cell = cells.Cell(name)
    root = tempfile.mkdtemp(prefix="bench_test_")
    try:
        datagen.generate(cell.config["datagen"], root,
                         cell.scaled_tables(0.05), seed)
        for stmt in cell.statements:
            nums = control.control(cell, root, stmt, {})
            assert any(v > cell.limits.get(k, 0) for k, v in nums.items()), \
                nums
            same = compare.compare(
                stmt.reference.compute(root, {}).slice(
                    0, stmt.spec.get("limit")),
                stmt.reference.compute(root, {}), stmt.spec,
                float(cell.limits["float_rel_err"]))
            assert not any(same.values()), same
    finally:
        shutil.rmtree(root, ignore_errors=True)


def test_rows_may_swap_only_inside_a_tie():
    spec = {"keys": ["k"], "exact": [], "approx": ["v"], "ordered": True,
            "order_float": "v", "limit": 3}
    want = pa.table({"k": [1, 2, 3, 4], "v": [9.0, 5.0, 5.0 * (1 + 1e-13),
                                              1.0]})
    tie = pa.table({"k": [1, 3, 2], "v": [9.0, 5.0, 5.0]})
    assert compare.compare(tie, want, spec, 1e-9) == \
        {"rows_diff": 0, "key_mismatch": 0,
         "float_rel_err": pytest.approx(1e-13, rel=1e-2)}
    swapped = pa.table({"k": [2, 1, 3], "v": [5.0, 9.0, 5.0]})
    assert compare.compare(swapped, want, spec, 1e-9)["key_mismatch"] == 2
    beyond = pa.table({"k": [1, 2, 4], "v": [9.0, 5.0, 1.0]})
    assert compare.compare(beyond, want, spec, 1e-9)["key_mismatch"] == 1
    twice = pa.table({"k": [1, 2, 2], "v": [9.0, 5.0, 5.0]})
    assert compare.compare(twice, want, spec, 1e-9)["key_mismatch"] == 1
    short = pa.table({"k": [1, 2], "v": [9.0, 5.0]})
    assert compare.compare(short, want, spec, 1e-9)["rows_diff"] == 1


class AnswerAltered(run.Served):
    """One float of every answer moved by a millionth where the client
    takes it off the wire (an integer by one where it has no float)."""

    def send(self, k, index, stmt, bindings, due=None, **kw):
        rec = super().send(k, index, stmt, bindings, due, **kw)
        t = rec["table"]
        col = (stmt.spec["approx"] or stmt.spec["exact"])[-1]
        moved = pc.multiply(t.column(col), 1.000001) if stmt.spec["approx"] \
            else pc.add(t.column(col), 1)
        rec["table"] = t.set_column(t.column_names.index(col), col, moved)
        return rec


class HalfLeftOut(run.Served):
    """The session sees every second file of the largest table only;
    the reference reads them all."""

    def __init__(self, cell, root, clients, trace):
        half = root + "_half"
        fact = max(cell.tables, key=lambda t: cell.tables[t]["rows"])
        for table in cell.tables:
            os.makedirs(os.path.join(half, table))
            for i, f in enumerate(sorted(os.listdir(
                    os.path.join(root, table)))):
                if table != fact or i % 2 == 0:
                    os.link(os.path.join(root, table, f),
                            os.path.join(half, table, f))
        self._half = half
        super().__init__(cell, half, clients, trace)

    def close(self):
        super().close()
        shutil.rmtree(self._half, ignore_errors=True)


def result_of(capsys, name, served_cls, seed=5):
    capsys.readouterr()
    assert run.main(["--workload", name, "--seed", str(seed), "--seconds",
                     "1", "--trace", "0", "--rehearse"],
                    served_cls=served_cls) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("name", CELLS)
def test_faults_come_out_not_correct(capsys, name):
    sound = result_of(capsys, name, run.Served)
    assert sound["correct"] and sound["failed"] == 0 and sound["attempted"]
    assert list(sound)[-1] == "checks"
    altered = result_of(capsys, name, AnswerAltered)
    assert not altered["correct"]
    assert altered["failed"] == altered["attempted"] > 0
    assert altered["checks"]["float_rel_err"]["value"] > \
        altered["checks"]["float_rel_err"]["limit"]
    half = result_of(capsys, name, HalfLeftOut)
    assert not half["correct"] and half["failed"] == half["attempted"]

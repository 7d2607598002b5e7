"""The reduction from a trace to numbers: on intervals worked out by
hand, and on a small trace recorded on the chip and kept beside this
file (``tiny.xplane.pb.gz``: one traced star-join query over 28,804
fact rows on one TPU v5e)."""

import gzip
import os
import shutil

import pytest

import tracered

from conftest import HERE

MS = 1e6


def planes():
    ops = [("%while.9 = (s32[8]) while(...)", 10 * MS, 25 * MS),
           ("%fusion.1 = s32[8] fusion(...)", 12 * MS, 10 * MS),  # inside
           ("%all-to-all.3 = s32[8] all-to-all(...)", 50 * MS, 5 * MS),
           ("%fusion.1 = s32[8] fusion(...)", 70 * MS, 10 * MS),
           ("%before = s32[] constant(0)", 0.0, 2 * MS)]
    return {"devices": {
        "/device:TPU:0": {"ops": ops, "modules": [
            ("jit_a(11)", 0.0, 60 * MS), ("jit_b(22)", 60 * MS, 40 * MS)]},
        "/device:TPU:1": {"ops": [("%fusion.1 = s32[8] fusion(...)",
                                   10 * MS, 90 * MS)],
                          "modules": [("jit_a(11)", 10 * MS, 90 * MS)]}},
            "bench": [("bench.anchor", 1 * MS, 0.1 * MS, {}),
                      ("bench.query", 10 * MS, 90 * MS, {"stmt": "q"})]}


def test_union_merges_overlaps():
    assert tracered.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == \
        [[0, 3], [5, 8]]


def test_reduce_by_hand():
    # host span on the host clock: the anchor reads 1,000 ms there and
    # 1 ms in the trace, so 1,039 ms on the host is 40 ms in the trace
    r = tracered.reduce(planes(), [("scan.hostPrepTime", 1039 * MS,
                                    8 * MS)], anchor_host_ns=1000 * MS)
    assert r["chips"] == 2 and r["queries"] == 1
    assert r["window_s"] == pytest.approx(0.090)
    # device 0: [10,35] + [50,55] + [70,80] = 40 ms; device 1: 90 ms
    assert r["busy_s_per_chip"] == pytest.approx([0.040, 0.090])
    assert r["busy_s"] == pytest.approx(0.065)
    # an operation goes by its program and its instruction; the one
    # inside the while is the while's
    ops = dict(r["device_ops"])
    assert ops == {"jit_a:%while.9": pytest.approx(0.025 / 2),
                   "jit_a:%all-to-all.3": pytest.approx(0.005 / 2),
                   "jit_b:%fusion.1": pytest.approx(0.010 / 2),
                   "jit_a:%fusion.1": pytest.approx(0.090 / 2)}
    assert sum(ops.values()) == pytest.approx(r["busy_s"])
    assert dict(r["device_programs"]) == {
        "jit_a:*": pytest.approx((0.025 + 0.005 + 0.090) / 2),
        "jit_b:*": pytest.approx(0.010 / 2)}
    gaps = dict(r["idle_gaps"])
    # [35,50] lies inside the program's span; [55,70] and [80,100] only
    # inside the query's
    assert gaps["scan.hostPrepTime"] == pytest.approx(0.015 / 2)
    assert gaps["bench.query"] == pytest.approx(0.035 / 2)
    assert r["longest_gap_s"] == pytest.approx(0.020)


def test_no_device_plane_gives_nothing():
    assert tracered.reduce({"devices": {}, "bench": []}) is None


def test_recorded_trace(tmp_path):
    path = str(tmp_path / "tiny.xplane.pb")
    with gzip.open(os.path.join(HERE, "tiny.xplane.pb.gz")) as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    r = tracered.reduce(tracered.read_planes(path))
    assert r["chips"] == 1 and r["queries"] == 1 and r["ops_line"]
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["busy_s"] == pytest.approx(RECORDED["busy_s"], rel=1e-9)
    assert r["window_s"] == pytest.approx(RECORDED["window_s"], rel=1e-9)
    assert r["device_ops"][0][0] == RECORDED["top_op"]
    assert sum(t for _, t in r["device_ops"]) <= r["busy_s"]
    assert [row["line"] for row in tracered.describe(path)
            if row["plane"] == "/device:TPU:0" and row["events"]] == \
        ["XLA Modules", "XLA Ops", "Async XLA Ops"]


# what the reduction gave for the recorded trace on the day it was
# recorded (my chip run, PR 25); a change that moves them changed the
# yardstick
RECORDED = {"busy_s": 0.042265721, "window_s": 0.080401274,
            "top_op": "jit__unknown:%while.4"}

"""The readers of the metrics that read program names, the span tree
and the build counters (``families.py`` and its eight
``metrics/<name>.py``), each on a synthetic ``run``; then the traced
rehearsal prints the host ones and no device number."""

import types

import pytest

import cells
import families
from test_rehearse import copy_of_the_benchmark, last_line, run_cell

DEVICE = ("decode_device_s", "agg_device_s", "concat_device_s",
          "sort_device_s")
HOST = ("serve_self_ms", "download_ms", "build_trace_s", "build_load_s")


def span(name, sid, parent, t0, dur):
    return {"name": name, "id": sid, "parent": parent, "ts_ns": t0,
            "dur_ns": dur, "query": 1}


def profile(spans):
    return types.SimpleNamespace(spans=spans)


def a_run(programs=None, spans=None, counters=None):
    trace = None if programs is None else {
        "covered": [(0, 0.7)], "device_programs": programs}
    done = [{"profile": profile(spans)}] if spans is not None else []
    return {"trace": trace, "completed": done, "counters": counters or {}}


@pytest.mark.parametrize("program,metric", [
    ("jit_pq_fused6:*", "decode_device_s"),
    ("jit_decode_dict_gather:*", "decode_device_s"),
    ("jit_agg_update:*", "agg_device_s"),
    ("jit_agg_merge", "agg_device_s"),
    ("jit_agg_final:*", "agg_device_s"),
    ("jit_concat:*", "concat_device_s"),
    ("jit_sort_apply:*", "sort_device_s"),
    ("jit_sort_keys:*", "sort_device_s"),
    ("jit_shared_digit_sort:*", "sort_device_s"),
    ("jit_concatenate:*", None),          # jax's own eager program
    ("jit_shared_lexsort4:*", None),      # a join's, no metric yet
    ("jit_expand:*", None),               # the Expand operator
    ("jit__unknown:*", None),             # the parent's names
    ("jit__apply_impl:*", None),
    ("?:*", None),
])
def test_a_program_counts_towards_its_familys_metric(program, metric):
    assert families.metric_of(program) == metric


def test_device_readers_sum_their_families_over_the_queries_touched():
    programs = [["jit_sort_apply:*", 11.6], ["jit_concat:*", 8.0],
                ["jit_agg_merge:*", 3.5], ["jit_agg_update:*", 3.0],
                ["jit_shared_digit_sort:*", 0.8], ["jit_pq_fused6:*", 0.3],
                ["jit_pack_batch:*", 0.01]]
    run = a_run(programs)
    got = {m: cells.reader(m)(run) for m in DEVICE}
    assert got == {"decode_device_s": 0.3, "agg_device_s": 6.5,
                   "concat_device_s": 8.0,
                   "sort_device_s": pytest.approx(12.4)}
    assert families.outside(run["trace"]) == [["jit_pack_batch:*", 0.01]]
    run["trace"]["covered"] = [(0, 1.0), (1, 0.5)]
    assert cells.reader("concat_device_s")(run) == 4.0


@pytest.mark.parametrize("metric", DEVICE)
def test_device_readers_give_nothing_where_there_is_nothing(metric):
    read = cells.reader(metric)
    assert read(a_run()) is None                      # no device trace
    assert read(a_run([])) is None                    # no program ran
    # the parent commit's names belong to no family
    assert read(a_run([["jit__unknown:*", 7.1],
                       ["jit__concat_nosync_impl:*", 8.0]])) is None


def test_serve_self_is_the_root_less_the_union_of_its_children():
    spans = [
        span("serve.request", 1, 0, 1000, 10_000_000),
        span("sched.queueWait", 2, 1, 1000, 1_000_000),
        span("query.plan", 3, 1, 1_001_000, 2_000_000),
        span("query.collect", 4, 1, 2_001_000, 5_000_000),   # overlaps
        span("collect.download", 5, 4, 2_500_000, 300_000),  # grandchild
        span("serve.stream", 6, 1, 9_001_000, 9_000_000),    # runs past
    ]
    run = a_run(spans=spans)
    # children cover [1000, 7_001_000) and [9_001_000, 10_001_000)
    assert cells.reader("serve_self_ms")(run) == pytest.approx(2.0)
    assert cells.reader("download_ms")(run) == pytest.approx(0.3)


def test_span_readers_give_nothing_on_the_parents_eight_field_spans():
    old = [{"name": "query.collect", "cat": "query", "tid": 1,
            "ts_ns": 5, "dur_ns": 7, "depth": 0}]
    for run in (a_run(spans=old), a_run(spans=[]), a_run()):
        assert cells.reader("serve_self_ms")(run) is None
        assert cells.reader("download_ms")(run) is None


def test_build_readers_take_the_windows_share_off_the_process(monkeypatch):
    from spark_rapids_tpu.obs import registry
    reg = registry.MetricsRegistry()
    monkeypatch.setattr(registry, "get_registry", lambda: reg)
    run = a_run(counters={"kernel.build.traceNs": 1e9})
    assert cells.reader("build_trace_s")(run) is None   # no such counter
    assert cells.reader("build_load_s")(run) is None
    reg.inc("kernel.build.traceNs", 4e9)
    reg.inc("kernel.build.lowerNs", 2e9)
    reg.inc("kernel.build.compileNs", 9e9)
    assert cells.reader("build_trace_s")(run) == 5.0
    assert cells.reader("build_load_s")(run) == 0.0     # an empty cache


def test_traced_rehearsal_prints_the_host_metrics_and_no_device_one(
        tmp_path):
    root = copy_of_the_benchmark(tmp_path)
    line = last_line(run_cell(root, "tpch-sf1.q1", 1))
    assert line["correct"]
    assert set(HOST) <= set(line["metrics"])
    assert not set(DEVICE) & set(line["metrics"])
    for name in HOST:
        assert line["metrics"][name]["value"] >= 0
    assert line["metrics"]["serve_self_ms"]["value"] <= \
        line["metrics"]["serve_overhead_ms"]["value"]

"""The join layer's three readers (``metrics/join_device_s.py``,
``join_roofline.py``, ``join_sortmerge_joins.py``) and the bytes they
are held against (``join_bytes.py``), each on a synthetic ``run``."""

import types

import pytest

import cells
import join_bytes

SHAPES = [{"build_rows": 6_000, "stream_rows": 2_880_404, "key_bytes": 4,
           "table_entries": 73_049, "out_rows": 220_000,
           "out_row_bytes": 16},
          {"build_rows": 18, "stream_rows": 220_000, "key_bytes": 4,
           "table_entries": 17_000, "out_rows": 240, "out_row_bytes": 36.5}]


@pytest.mark.parametrize("program,mine", [
    ("jit_probe_count:*", True), ("jit_probe_emit_u:*", True),
    ("jit_probe_emit_d:*", True), ("jit_probe_bpack:*", True),
    ("jit_probe_semi:*", True), ("jit_join_pack:*", True),
    ("jit_join_range:*", True), ("jit_count:*", True),
    ("jit_emit:*", True), ("jit_semi:*", True), ("jit_cross:*", True),
    ("jit_grace_apply:*", True),
    ("jit_concat:*", False), ("jit_pq_fused6:*", False),
    ("jit_agg_update:*", False), ("jit_shared_lexsort4:*", False),
    ("jit_counter:*", False), ("jit_emitter:*", False),
    ("jit__unknown:*", False), ("?:*", False),
])
def test_a_program_is_the_join_layers_by_its_family(program, mine):
    from metrics import join_device_s
    assert join_device_s.is_join_program(program) == mine


def test_join_bytes_from_shapes():
    first, second = (join_bytes.join_bytes(s) for s in SHAPES)
    assert first == (6_000 + 2_880_404) * 4 + 2 * 4 * 73_049 \
        + 220_000 * 16
    assert second == int((18 + 220_000) * 4 + 2 * 4 * 17_000 + 240 * 36.5)
    total = join_bytes.statement_join_bytes(SHAPES)
    assert total == {"joins": 2, "bytes_by_join": [first, second],
                     "least_bytes": first + second}


def a_run(programs, shapes=SHAPES, share=1.0):
    reference = types.SimpleNamespace(join_shapes=lambda root: shapes) \
        if shapes is not None else types.SimpleNamespace()
    stmt = types.SimpleNamespace(name="q3", reference=reference)
    trace = None if programs is None else {
        "covered": [(0, share)], "queries": share, "chips": 1,
        "device_programs": programs}
    return {"trace": trace, "root": "/nowhere",
            "completed": [{"index": 0, "stmt": "q3"}],
            "cell": types.SimpleNamespace(statements=[stmt]),
            "counters": {}, "peaks": {"hbm_bytes_per_s": 819e9}}


def test_device_readers_read_the_join_programs():
    run = a_run([["jit_pq_fused6:*", 0.30], ["jit_probe_emit_u:*", 0.08],
                 ["jit_probe_count:*", 0.02], ["jit_agg_update:*", 0.01]])
    assert cells.reader("join_device_s")(run) == pytest.approx(0.10)
    least = join_bytes.statement_join_bytes(SHAPES)["least_bytes"] / 819e9
    got = cells.reader("join_roofline")(run)
    assert got == pytest.approx(100 * least / 0.10)
    assert 0 < got < 100
    # half a query traced: half its bytes against what ran in that half
    half = a_run([["jit_probe_emit_u:*", 0.05]], share=0.5)
    assert cells.reader("join_roofline")(half) == \
        pytest.approx(100 * 0.5 * least / 0.05)


@pytest.mark.parametrize("run", [
    a_run(None), a_run([["jit_pq_fused6:*", 0.3]]),
], ids=["no-trace", "no-join-program"])
def test_device_readers_have_nothing_to_read(run):
    assert cells.reader("join_device_s")(run) is None
    assert cells.reader("join_roofline")(run) is None


def test_roofline_needs_the_references_shapes():
    run = a_run([["jit_probe_count:*", 0.02]], shapes=None)
    assert cells.reader("join_device_s")(run) == pytest.approx(0.02)
    assert cells.reader("join_roofline")(run) is None


def test_sortmerge_joins_reads_the_programs_counter():
    from spark_rapids_tpu.obs import registry
    read = cells.reader("join_sortmerge_joins")
    run = a_run(None)
    reg = registry.get_registry()
    if not any(n.startswith("join.path.")
               for n in reg.snapshot()["counters"]):
        assert read(run) is None       # a program with no such counter
    reg.inc("join.path.direct", 4)
    assert read(run) == 0
    run["counters"] = {"join.path.sortMerge": 2, "join.path.direct": 2}
    run["completed"] = run["completed"] * 2
    assert read(run) == 1.0

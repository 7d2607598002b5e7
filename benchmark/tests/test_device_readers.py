"""The device layer's reads and the chips' waits for their peers
(``metrics/device_reads_per_query.py``, ``peer_wait_s.py``), each on a
synthetic ``run``: a run of a program with the counter and the spans, a
parent's run with neither, and a one-chip run."""

import types

import pytest

import cells


def a_run(spans_a_query, counters=None, chips=4):
    """Two completed queries, each with its own copy of the spans."""
    return {"completed": [
        {"index": i, "stmt": "q65",
         "profile": types.SimpleNamespace(spans=[dict(s) for s in
                                                 spans_a_query])}
        for i in range(2)],
        "cell": types.SimpleNamespace(chips=chips),
        "counters": counters or {}}


def span(name, dur_s, **chip):
    """A span dict as ``obs/trace.span_dicts`` renders it; ``chip=``
    absent is a parent's span, which has no such field."""
    return {"name": name, "cat": "query", "ts_ns": 0,
            "dur_ns": int(dur_s * 1e9), **chip}


@pytest.fixture()
def registry(monkeypatch):
    """A registry of its own, empty: what a program shows that has
    counted nothing yet."""
    from spark_rapids_tpu.obs import registry as obsreg
    fresh = obsreg.MetricsRegistry()
    monkeypatch.setattr(obsreg, "get_registry", lambda: fresh)
    return fresh


def test_reads_per_query_read_the_counter(registry):
    read = cells.reader("device_reads_per_query")
    registry.inc_many(("device.reads", 3), ("device.reads.join.countWait", 3))
    run = a_run([], {"device.reads": 17, "device.reads.join.countWait": 9,
                     "device.reads.agg.countWait": 8})
    assert read(run) == 8.5
    # the counter exists and the window read nothing
    assert read(a_run([], {})) == 0


def test_reads_per_query_have_nothing_to_read(registry):
    # a parent's program has no such counter
    assert cells.reader("device_reads_per_query")(
        a_run([], {"kernel.dispatches": 50})) is None
    run = a_run([])
    run["completed"] = []
    registry.inc("device.reads")
    assert cells.reader("device_reads_per_query")(run) is None


def test_peer_wait_sums_the_chips_waits_over_chips_and_queries():
    read = cells.reader("peer_wait_s")
    run = a_run([span("chip.peerWait", 0.2, chip=0),
                 span("chip.peerWait", 0.1, chip=2),
                 span("exchange.ici", 0.5, chip=None),
                 span("join.countWait", 0.01, chip=3)])
    assert read(run) == pytest.approx((0.2 + 0.1) * 2 / 4 / 2)
    # chips stamped, nobody waited
    assert read(a_run([span("join.countWait", 0.01, chip=1)])) == 0


@pytest.mark.parametrize("spans,chips", [
    ([span("exchange.ici", 0.5), span("reuse.wait", 0.2)], 4),
    ([span("exchange.ici", 0.5, chip=None),
      span("join.countWait", 0.01, chip=None)], 1),
    ([], 4),
], ids=["parent", "one-chip", "no-spans"])
def test_peer_wait_has_nothing_to_read(spans, chips):
    assert cells.reader("peer_wait_s")(a_run(spans, chips=chips)) is None

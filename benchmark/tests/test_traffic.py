"""The traffic generator on a system that answers at once."""

import time

import traffic


class Stmt:
    def __init__(self, name, weight, bindings=None):
        self.name, self.weight, self.bindings = name, weight, bindings or {}


def answer(k, index, stmt, bindings, due=None):
    now = time.perf_counter()
    return {"index": index, "client": k, "stmt": stmt.name,
            "bindings": bindings, "due": due, "t_submit": now}


def test_every_seed_sends_the_same_mix_in_another_order():
    stmts = [Stmt("a", 3), Stmt("b", 1, {"lo": {"low": 1.0, "high": 2.0,
                                                 "decimals": 2}})]
    orders = []
    for seed in (1, 2, 4000000007):
        req = traffic.Requests(stmts, seed)
        drawn = [req.next() for _ in range(8)]
        orders.append([s.name for _, s, _ in drawn])
        assert sorted(orders[-1]) == ["a"] * 6 + ["b"] * 2
        assert all(1.0 <= b["lo"] <= 2.0 for _, s, b in drawn
                   if s.name == "b")
    assert len({tuple(o) for o in orders}) > 1
    again = traffic.Requests(stmts, 1)
    assert [again.next()[1].name for _ in range(8)] == orders[0]


def test_closed_loop_starts_nothing_after_the_window():
    req = traffic.Requests([Stmt("a", 1)], 3)
    t0 = time.perf_counter()
    recs = traffic.run({"kind": "closed", "clients": 2}, req, answer, 0.05)
    assert len(recs) > 2 and {r["client"] for r in recs} == {0, 1}
    assert [r["index"] for r in recs] == list(range(len(recs)))
    assert all(r["t_submit"] - t0 < 0.05 + 0.02 for r in recs)


def test_open_loop_sends_at_the_rate_whatever_the_system_does():
    req = traffic.Requests([Stmt("a", 1)], 3)
    recs = traffic.run({"kind": "open", "clients": 2, "rate_per_s": 100.0},
                       req, answer, 0.2)
    assert len(recs) == 20
    due = [r["due"] for r in recs]
    gaps = [b - a for a, b in zip(due, due[1:])]
    assert all(abs(g - 0.01) < 1e-9 for g in gaps)
    assert all(r["t_submit"] >= r["due"] for r in recs)


def test_coverage_of_a_trace_that_starts_inside_a_query():
    import run
    recs = [{"index": 0, "t_submit": 1.0, "t_done": 11.0, "wall_s": 10.0},
            {"index": 1, "t_submit": 11.0, "t_done": 21.0, "wall_s": 10.0}]
    covered, outside = run.coverage(recs, 4e9, 11.001e9)
    assert [i for i, _ in covered] == [0, 1]
    assert covered[0][1] == 0.7 and covered[1][1] < 1e-3
    assert abs(outside - (3.0 + 9.999)) < 1e-6
    assert run.coverage(recs, 0.0, 30e9) == ([(0, 1.0), (1, 1.0)], 0.0)

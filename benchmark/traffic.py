"""The one traffic generator: a cell's ``workloads/<cell>.json`` says
which statements, in what shares, with bindings drawn how, from how many
clients, in a closed loop or an open one at a fixed rate, evenly spaced.

Every seed gives the same multiset of statements in another order: a
cycle holds each statement ``weight`` times and is shuffled from the
seed, cycle after cycle.  Bindings are drawn from the seed too.
"""

import threading
import time

import numpy as np


def draw_binding(rule: dict, rng) -> float:
    """Uniform between ``low`` and ``high``, rounded to ``decimals``."""
    v = rng.uniform(float(rule["low"]), float(rule["high"]))
    return round(v, int(rule["decimals"])) if "decimals" in rule else v


class Requests:
    """The endless sequence of (statement, bindings) of one run."""

    def __init__(self, statements, seed: int):
        self._statements = statements
        self._rng = np.random.default_rng([int(seed), 0x7AFF1C])
        self._cycle = []
        self._lock = threading.Lock()
        self.issued = 0

    def next(self):
        with self._lock:
            if not self._cycle:
                order = [i for i, s in enumerate(self._statements)
                         for _ in range(s.weight)]
                self._cycle = [order[j] for j in
                               self._rng.permutation(len(order))]
            stmt = self._statements[self._cycle.pop()]
            bindings = {k: draw_binding(r, self._rng)
                        for k, r in stmt.bindings.items()}
            index = self.issued
            self.issued += 1
            return index, stmt, bindings


def closed_loop(requests, send, clients: int, seconds: float,
                on_done=None) -> list:
    """``clients`` callers, each sending its next request when the last
    is answered.  No request starts once ``seconds`` have passed; the
    window ends when the last in flight is answered."""
    records, lock = [], threading.Lock()
    t0 = time.perf_counter()

    def client(k: int) -> None:
        while time.perf_counter() - t0 < seconds:
            index, stmt, bindings = requests.next()
            rec = send(k, index, stmt, bindings, due=None)
            with lock:
                records.append(rec)
                n = len(records)
            if on_done is not None:
                on_done(n)
    _run_threads(client, clients)
    return sorted(records, key=lambda r: r["index"])


def open_loop(requests, send, clients: int, seconds: float, rate: float,
              on_done=None) -> list:
    """Requests fall due evenly spaced, ``rate`` a second for
    ``seconds``, whatever the system does; ``clients`` connections
    carry them.  A request's latency counts from when it was due, and
    ``late_s`` says how late the generator sent it."""
    n = max(1, int(seconds * rate))
    due = np.arange(n) / rate
    plan = [requests.next() for _ in range(n)]
    records, lock, cursor = [], threading.Lock(), iter(range(n))
    t0 = time.perf_counter()

    def client(k: int) -> None:
        while True:
            with lock:
                i = next(cursor, None)
            if i is None:
                return
            wait = t0 + due[i] - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            index, stmt, bindings = plan[i]
            rec = send(k, index, stmt, bindings, due=t0 + due[i])
            with lock:
                records.append(rec)
                done = len(records)
            if on_done is not None:
                on_done(done)
    _run_threads(client, clients)
    return sorted(records, key=lambda r: r["index"])


def _run_threads(target, n: int) -> None:
    errors = []

    def guarded(k: int) -> None:
        try:
            target(k)
        except BaseException as e:   # re-raised in the caller below
            errors.append(e)
    threads = [threading.Thread(target=guarded, args=(k,),
                                name=f"bench-client-{k}")
               for k in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]


def run(loop: dict, requests, send, seconds: float, on_done=None) -> list:
    kind = loop.get("kind", "closed")
    clients = int(loop.get("clients", 1))
    if kind == "closed":
        return closed_loop(requests, send, clients, seconds, on_done)
    if kind == "open":
        return open_loop(requests, send, clients, seconds,
                         float(loop["rate_per_s"]), on_done)
    raise ValueError(f"unknown loop kind {kind!r}")

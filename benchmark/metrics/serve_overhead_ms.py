"""``serve_overhead_ms`` (layer: serve): per query, the client's wall
less the program's own ``plan`` + ``execute`` + ``collect`` phases of
that query's profile: the wire, the front end, the scheduler's queue
and the chunks' way back.  Mean over the window's queries."""


def read(run):
    gaps = [r["wall_s"] - sum(r["profile"].phases.get(p, 0) for p in
                              ("plan", "execute", "collect")) / 1e9
            for r in run["completed"] if r["profile"] is not None]
    return 1e3 * sum(gaps) / len(gaps) if gaps else None

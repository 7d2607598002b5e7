"""``peer_wait_s`` (layer: exchange): the seconds a chip waits for its
peers at a barrier (``chip.peerWait``: a span for each chip that ended
before the last one at an ``exec/placement.drain_by_chip``, from its
end to the drain's), summed over the spans of the queries completed
and divided by the chips and the queries.  Nothing where no span
carries a chip: one chip, or a program that stamps none."""


def read(run):
    spans = [sp for r in run["completed"] if r["profile"] is not None
             for sp in r["profile"].spans]
    if not any(sp.get("chip") is not None for sp in spans):
        return None
    ns = sum(sp["dur_ns"] for sp in spans if sp["name"] == "chip.peerWait")
    return ns / 1e9 / run["cell"].chips / len(run["completed"])

"""``join_device_s`` (layer: operators: join): seconds of the traced
interval in the join families' programs, over the queries the interval
touches: ``jit_probe_*`` (the direct-address table: count, emit, semi,
the build-side pack), ``jit_join_pack`` and ``jit_join_range``,
``jit_count``, ``jit_emit``, ``jit_semi`` (sort-merge), ``jit_cross``
and ``jit_grace_*``.  A family belongs here where its name is one of
the prefixes, alone or followed by ``_`` or digits (the rule of
``families.metric_of``; ``families.FAMILIES`` has no join entry and is
not this file's to edit).  The sorts a join dispatches
(``jit_shared_*``) are the sort layer's.  Nothing without a device
trace or where none of these programs is among those handed over."""

import re

PREFIXES = ("probe", "join", "count", "emit", "semi", "cross", "grace")


def is_join_program(program: str) -> bool:
    name = program.split(":", 1)[0]
    return name.startswith("jit_") and any(
        re.fullmatch(re.escape(p) + r"(_.*|\d*)", name[4:])
        for p in PREFIXES)


def read(run):
    trace = run["trace"]
    if not trace or not trace.get("covered"):
        return None
    mine = [t for name, t in trace["device_programs"]
            if is_join_program(name)]
    return sum(mine) / len(trace["covered"]) if mine else None

"""``exchange_device_s`` (layer: exchange): seconds of the traced
interval in the exchange's programs, mean over the chips, over the
queries the interval touches: ``jit_ici_*`` (the ``all_to_all`` step
``jit_ici_exchange`` with its bucketing and reassembly, the readers'
``jit_ici_extract``) and ``jit_exch_*`` (the targets, the range keys,
the per-chip counts).  A family belongs here where its name is one of
the prefixes, alone or followed by ``_`` or digits (the rule of
``families.metric_of``; ``families.FAMILIES`` has no exchange entry and
is not this file's to edit).  Nothing without a device trace or where
none of these programs is among those handed over."""

import re

PREFIXES = ("ici", "exch")


def is_exchange_program(program: str) -> bool:
    name = program.split(":", 1)[0]
    return name.startswith("jit_") and any(
        re.fullmatch(re.escape(p) + r"(_.*|\d*)", name[4:])
        for p in PREFIXES)


def read(run):
    trace = run["trace"]
    if not trace or not trace.get("covered"):
        return None
    mine = [t for name, t in trace["device_programs"]
            if is_exchange_program(name)]
    return sum(mine) / len(trace["covered"]) if mine else None

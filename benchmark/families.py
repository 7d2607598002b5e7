"""What the readers of PR 26's metrics share: which per-layer metric a
device program's time belongs to, a query's span tree, the build
counters of set-up.

The program names every device program ``jit_<kernel family>``
(``exec/kernel_cache.jit_named``; ``docs/observability.md`` lists the
families), so a trace's ``XLA Modules`` line says which operator's
kernels ran.  ``tracered.reduce`` hands over the ten largest programs
of the traced interval as ``[name + ":*", seconds]``; a statement that
runs more than ten leaves the smallest out (PERF.md, open questions).
A family belongs to a metric where its name is one of the metric's
prefixes, alone or followed by ``_`` or digits: ``concat`` takes
``jit_concat`` and not jax's own ``jit_concatenate``.
"""

import re

FAMILIES = {
    "decode_device_s": ("pq_fused", "decode"),
    "agg_device_s": ("agg_update", "agg_merge", "agg_final"),
    "concat_device_s": ("concat",),
    "sort_device_s": ("sort", "shared_digit_sort"),
}


def metric_of(program: str):
    """The metric that ``jit_<family>`` (or ``jit_<family>:*``) counts
    towards, or ``None``."""
    name = program.split(":", 1)[0]
    if not name.startswith("jit_"):
        return None
    for metric, prefixes in FAMILIES.items():
        for p in prefixes:
            if re.fullmatch(re.escape(p) + r"(_.*|\d*)", name[4:]):
                return metric
    return None


def device_seconds(run, metric: str):
    """Seconds the metric's programs ran inside the traced interval,
    over the queries the interval touches.  ``None`` without a device
    trace, or where no such program ran."""
    trace = run["trace"]
    if not trace or not trace.get("covered"):
        return None
    mine = [t for name, t in trace["device_programs"]
            if metric_of(name) == metric]
    return sum(mine) / len(trace["covered"]) if mine else None


def outside(trace) -> list:
    """The handed-over programs that no metric counts, with their
    seconds: what the four family metrics leave of the busy time."""
    return [[name, t] for name, t in trace["device_programs"]
            if metric_of(name) is None]


def span_tree(profile):
    """``(root, children)`` of a query's span tree: the
    ``serve.request`` span and the spans whose ``parent`` it is.
    ``(None, [])`` where the program records no such tree."""
    spans = profile.spans if profile is not None else []
    root = next((s for s in spans if s["name"] == "serve.request"
                 and "id" in s), None)
    if root is None:
        return None, []
    return root, [s for s in spans if s.get("parent") == root["id"]]


def setup_build_seconds(run, *parts):
    """Seconds of the program's ``kernel.build.<part>Ns`` counters
    spent before the window: the process's total when read (after the
    window) less what moved inside the window.  0 where the program has
    the build counters and this one never moved (no load on an empty
    cache); ``None`` where it has none of them."""
    from spark_rapids_tpu.obs import registry
    total = registry.get_registry().snapshot()["counters"]
    if not any(n.startswith("kernel.build.") for n in total):
        return None
    return sum(total.get(n, 0) - run["counters"].get(n, 0)
               for n in (f"kernel.build.{p}Ns" for p in parts)) / 1e9

"""The placement guarantees of a configuration, read from the profile
of an executed query: no operator off the TPU, no scan column decoded
on the host, the operators the statement needs are there.  A silent
fallback still returns right answers, so answers alone prove nothing
about where the work ran."""


def check(profile, need_operators) -> dict:
    """Counts that all have to be 0: ``off_tpu_ops``,
    ``host_decoded_cols``, ``missing_ops``."""
    if profile is None or profile.plan is None:
        return {"off_tpu_ops": 1, "host_decoded_cols": 0,
                "missing_ops": len(need_operators)}
    off = sum(1 for ln in profile.explain_lines
              if ln.strip().startswith("!"))
    host_cols = 0
    names = []

    def walk(node, root=False):
        nonlocal off, host_cols
        names.append(node.name)
        # the root is the download to the host
        if not root and not (node.is_tpu and node.name.startswith("Tpu")):
            off += 1
        host_cols += int(node.extra.get("fallbackColumns") or 0)
        for ch in node.children:
            walk(ch)
    walk(profile.plan, root=True)
    missing = sum(1 for frag in need_operators
                  if not any(frag in n for n in names))
    return {"off_tpu_ops": off, "host_decoded_cols": host_cols,
            "missing_ops": missing}


def node_extras(profile, key: str) -> float:
    """Sum of one ``Metrics.extra`` value over the plan's nodes."""
    total = 0.0

    def walk(node):
        nonlocal total
        total += float(node.extra.get(key) or 0)
        for ch in node.children:
            walk(ch)
    if profile is not None and profile.plan is not None:
        walk(profile.plan)
    return total

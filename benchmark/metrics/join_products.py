"""``join_products`` (layer: plan): pairs of batches joined as a
product (``join.path.product``: a join the plan left without a key)
inside the window, over the queries completed.  0 is the expected
reading for a statement whose equalities tie all its relations
together, in whatever order its FROM lists them.  Nothing where the
program counts no join order (it has no ``plan.rewrite.reorderedJoins``
or ``join.path.product`` counter)."""

COUNTERS = ("join.path.product", "plan.rewrite.reorderedJoins")


def read(run):
    from spark_rapids_tpu.obs import registry
    total = registry.get_registry().snapshot()["counters"]
    n = len(run["completed"])
    if not n or not any(name in total for name in COUNTERS):
        return None
    return run["counters"].get("join.path.product", 0) / n

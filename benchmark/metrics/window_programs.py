"""``window_programs`` (layer: compile): programs built or loaded
inside the window, ``kernel.cache.compiles`` +
``kernel.cache.persistentHits``.  A count; 0 where the statement
repeats, since warm-up ran every shape."""


def read(run):
    c = run["counters"]
    return c.get("kernel.cache.compiles", 0) \
        + c.get("kernel.cache.persistentHits", 0)

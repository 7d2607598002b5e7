"""``build_load_s`` (layer: compile): seconds set-up spent reading
executables back from the persistent compile cache
(``kernel.build.loadNs``).  0 on an empty cache, where the time goes
to ``kernel.build.compileNs`` instead.  Nothing where the program has
no such counters."""

import families


def read(run):
    return families.setup_build_seconds(run, "load")

"""``build_trace_s`` (layer: compile): seconds set-up spent tracing
functions to jaxprs and lowering them to MLIR modules
(``kernel.build.traceNs`` + ``kernel.build.lowerNs``): host work that
no compile cache saves.  Nothing where the program has no such
counters."""

import families


def read(run):
    return families.setup_build_seconds(run, "trace", "lower")

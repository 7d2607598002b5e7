"""``setup_s``: process start to the first timed submission: imports,
device check, data made from the seed and written, session and views,
each statement's first executions (trace, executable load or compile,
run)."""


def read(run):
    return run["setup_s"]

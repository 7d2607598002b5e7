"""``first_answer_s`` (layer: compile): wall of the first statement's
first execution in the process, inside set-up: trace, executable load
from the persistent cache (or compile, on an empty one), run."""


def read(run):
    return run["first_answer_s"]

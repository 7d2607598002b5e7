"""``query_s``: the drained window's wall over the queries completed in
it.  The window starts with the first timed submission, starts no query
once ``--seconds`` have passed, and ends when the last query in flight
has delivered its last chunk: all the work over all the time.  With
several closed-loop clients it is the inverse of the completed rate."""


def read(run):
    n = len(run["completed"])
    return run["window_wall_s"] / n if n else None

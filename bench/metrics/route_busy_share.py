"""``route_busy_share.<job>``: device time in the routing programs (the
level router, and the raw-score walk of boosting) over device busy time
in the window."""
from bench import trace


def read(name, run):
    if run.trace is None:
        return None
    busy = trace.busy_s(run.trace)
    route = trace.program_s(run.trace, "route")
    if busy <= 0 or route is None:
        return None
    return route / busy

"""``idle_share.<job>``: the share of the traced window in which no
operation ran on the device (1 - busy / window, averaged over chips)."""
from bench import trace


def read(name, run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return 1.0 - trace.busy_s(run.trace) / run.trace.window_s

"""``walk_busy_share.<job>``: device seconds of the ops in the program's
``boost.walk`` scope (boosting's raw-score update) over the device's busy
seconds in the window, averaged over chips.  The scope's ops are merged
into intervals before they are summed, so a loop op that encloses its
body's ops on the trace counts once.  A program without the scope reads
nothing."""
from bench import trace, xplane

SCOPE = "boost.walk"


def read(name, run):
    tr = xplane.for_run(run)
    if tr is None or not tr.ops:
        return None
    busy = trace.busy_s(run.trace)
    w0, w1 = run.trace.w0, run.trace.w1
    per = [sum(e - s for s, e in xplane.busy(
               [op for op in ops if xplane.in_scope(op[3], SCOPE)], w0, w1))
           for ops in tr.ops.values()]
    if busy <= 0 or not any(per):
        return None
    return sum(per) / len(per) * 1e-9 / busy

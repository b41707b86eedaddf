"""``sync_idle_share.<job>``: the share of the window in which the device
was idle inside a ``udt.sync`` span, the level loop's read of a level
chunk's child count (averaged over chips)."""
import bisect

from bench import xplane


def read(name, run):
    tr = xplane.for_run(run)
    if tr is None or not tr.ops or run.trace.window_s <= 0:
        return None
    syncs = xplane.window_spans(tr, run.trace, "udt.sync")
    if not syncs:
        return None
    w0, w1 = run.trace.w0, run.trace.w1
    per = []
    for ops in tr.ops.values():
        busy = xplane.busy(ops, w0, w1)
        starts = [s for s, _ in busy]
        idle = 0.0
        for s, e, _ in syncs:
            e = min(e, w1)
            covered = 0.0
            for bs, be in busy[max(0, bisect.bisect_right(starts, s) - 1):
                               bisect.bisect_left(starts, e)]:
                covered += max(0.0, min(e, be) - max(s, bs))
            idle += (e - s) - covered
        per.append(idle)
    return sum(per) / len(per) * 1e-9 / run.trace.window_s

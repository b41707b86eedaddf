"""``syncs_per_tree.<job>``: the level loop's device-to-host reads
(``udt.sync`` spans) in the window, per tree fitted in it."""
from bench import xplane


def read(name, run):
    tr = xplane.for_run(run)
    trees = len(run.window.units)
    if tr is None or not trees or not xplane.window_spans(tr, run.trace,
                                                          "udt.tree"):
        return None
    return len(xplane.window_spans(tr, run.trace, "udt.sync")) / trees

"""``h2d_bytes_per_tree.<job>``: bytes ``build_tree`` sent from host
arrays to the device (the ``bytes`` of its ``udt.put`` spans) in the
window, per tree fitted in it."""
from bench import xplane


def read(name, run):
    tr = xplane.for_run(run)
    trees = len(run.window.units)
    if tr is None or not trees or not xplane.window_spans(tr, run.trace,
                                                          "udt.tree"):
        return None
    puts = xplane.window_spans(tr, run.trace, "udt.put")
    return sum(args.get("bytes", 0) for _, _, args in puts) / trees

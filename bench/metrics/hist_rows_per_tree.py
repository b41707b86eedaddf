"""``hist_rows_per_tree.<job>``: rows the level steps' feature histograms
took in (the ``rows`` of the program's ``udt.hist`` spans) in the window,
per tree fitted in it.  A program without those spans reads nothing."""
from bench import xplane


def read(name, run):
    tr = xplane.for_run(run)
    trees = len(run.window.units)
    if tr is None or not trees:
        return None
    spans = xplane.window_spans(tr, run.trace, "udt.hist")
    if not spans:
        return None
    return sum(args.get("rows", 0) for _, _, args in spans) / trees

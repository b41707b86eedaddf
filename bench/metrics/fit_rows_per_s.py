"""``fit_rows_per_s``: training rows times trees grown, over the
window: every whole tree (a UDT fit, or a boosting round) that ended in
it, over all of its time."""


def read(name, run):
    return run.window.rows / run.window.length

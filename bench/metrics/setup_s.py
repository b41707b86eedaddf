"""``setup_s``: process start to the opening of the window (data,
binning, transfer to the device, warm-up and every compile or cache
load), on the host clock."""


def read(name, run):
    return run.window.t0 - run.process_start

"""``binning_s``: host seconds in the program's binning during set-up
(``fit_bins`` and ``transform``), on the host clock."""


def read(name, run):
    return run.setup.get("binning")

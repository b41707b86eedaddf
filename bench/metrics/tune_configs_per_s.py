"""``tune_configs_per_s``: hyper-parameter configurations priced by the
window's whole ``tune`` calls, over all of its time."""


def read(name, run):
    return run.window.configs / run.window.length

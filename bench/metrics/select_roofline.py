"""``select_roofline.<job>``: the least time the chip needs for the
window's split selection (``selection_bytes``: each level's histograms
read once, at the chip's HBM bandwidth) over the device time of the ops
in the level step's ``level.select`` scope, in percent."""
import numpy as np

from bench import peaks, xplane


def selection_bytes(tree: dict, k: int, n_bins: int, channels: int) -> int:
    """Least HBM bytes of one tree's split selection: each level's [S, K,
    B, channels] float32 histograms read once, S being the nodes it
    holds: summed over the levels, S is the tree's node count."""
    nodes = int((np.asarray(tree["depth"]) >= 1).sum())
    return nodes * k * n_bins * channels * 4


def read(name, run):
    tr = xplane.for_run(run)
    if tr is None or not run.work.get("trees"):
        return None
    t = xplane.scope_s(tr, run.trace, "level.select")
    if not t:
        return None
    w = run.work
    least = sum(selection_bytes(tree, w["k"], w["n_bins"], w["channels"])
                for tree in w["trees"])
    bw = peaks.peaks(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * (least / bw) / t

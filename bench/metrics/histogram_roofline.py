"""``histogram_roofline.<job>``: the least time the chip needs for the
window's histogram passes (``counts.histogram_bytes`` at the chip's HBM
bandwidth) over the device time of the ops in the level step's
``level.histogram`` scope, in percent."""
from bench import counts, peaks, xplane


def read(name, run):
    tr = xplane.for_run(run)
    if tr is None or not run.work.get("trees"):
        return None
    t = xplane.scope_s(tr, run.trace, "level.histogram")
    if not t:
        return None
    w = run.work
    least = sum(counts.histogram_bytes(tree, w["k"], w["n_bins"],
                                       w["channels"]) for tree in w["trees"])
    bw = peaks.peaks(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * (least / bw) / t

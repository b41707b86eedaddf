"""``level_step_roofline.<job>``: the least time the chip needs for the
window's histogram passes (bench/counts.py, at the chip's HBM bandwidth)
over the device time of the level-step programs, in percent."""
from bench import counts, peaks, trace


def read(name, run):
    if run.trace is None or not run.work.get("trees"):
        return None
    t = trace.program_s(run.trace, "level_step")
    if not t:
        return None
    w = run.work
    least = sum(counts.histogram_bytes(tr, w["k"], w["n_bins"],
                                       w["channels"]) for tr in w["trees"])
    bw = peaks.peaks(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * (least / bw) / t

"""The reduction from a profiler trace to device times.

A traced run writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it.  Each chip is a plane
``/device:TPU:<n>`` whose ``XLA Ops`` line holds every operation the chip
ran and whose ``XLA Modules`` line holds every program execution, named
after the jitted function.  The host planes hold the benchmark's spans:
``bench.window`` around the measured window and one span per unit of
work, so that an idle gap can be put down to what the host was doing.

Everything is clipped to the window span and averaged over the chips.
Program names are matched by the one table ``PROGRAMS``.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

import numpy as np

from bench.harness import WINDOW_SPAN

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")

# layer -> substrings of the jitted programs' names (module names carry
# the Python function's name, e.g. ``jit__chunk_step_impl``)
PROGRAMS = {
    # core/tree.py: histogram, selection and node update of a level chunk
    "level_step": ("_chunk_step",),
    # core/tree.py level router; core/predict.py walk (boosting's
    # raw-score update)
    "route": ("_route_step", "jit__walk"),
    # core/tuning.py grid counts; core/predict.py path tables
    "toot_grid": ("_grid_counts", "jit__paths"),
}


@dataclasses.dataclass
class Chip:
    name: str
    ops: list          # (name, start_ns, end_ns)
    modules: list      # (name, start_ns, end_ns)


@dataclasses.dataclass
class Summary:
    chips: list
    host: list         # (name, start_ns, end_ns)
    w0: float
    w1: float

    @property
    def window_s(self) -> float:
        return (self.w1 - self.w0) * 1e-9


def _events(line):
    out = []
    for e in line.events:
        s = float(e.start_ns)
        out.append((e.name, s, s + float(e.duration_ns)))
    return out


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str) -> Summary:
    """The summary of the trace in ``path`` (a file, or a directory the
    profiler wrote into)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find_xplane(path)
    data = ProfileData.from_file(path)
    chips, host = [], []
    for plane in data.planes:
        lines = {ln.name: ln for ln in plane.lines}
        if DEVICE_PLANE.match(plane.name):
            chips.append(Chip(
                plane.name,
                _events(lines[OPS_LINE]) if OPS_LINE in lines else [],
                _events(lines[MODULES_LINE]) if MODULES_LINE in lines
                else []))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host += _events(ln)
    windows = [(s, e) for n, s, e in host if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace {path}")
    w0, w1 = windows[-1]
    return Summary(chips, host, w0, w1)


def _clip(events, w0, w1):
    return [(n, max(s, w0), min(e, w1)) for n, s, e in events
            if e > w0 and s < w1]


def _union(intervals):
    """Merged (start, end) intervals, sorted."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_s(summary: Summary) -> float:
    """Seconds in which an operation ran, per chip, averaged over chips."""
    if not summary.chips:
        return 0.0
    per = []
    for c in summary.chips:
        ops = _clip(c.ops or c.modules, summary.w0, summary.w1)
        per.append(sum(e - s for s, e in _union([(s, e) for _, s, e in ops])))
    return float(np.mean(per)) * 1e-9


def program_s(summary: Summary, layer: str):
    """Device seconds in the programs of ``layer`` (see ``PROGRAMS``),
    averaged over chips; None where the trace has no program line."""
    keys = PROGRAMS[layer]
    per = []
    for c in summary.chips:
        if not c.modules:
            continue
        mods = _clip(c.modules, summary.w0, summary.w1)
        per.append(sum(e - s for n, s, e in mods
                       if any(k in n for k in keys)))
    return float(np.mean(per)) * 1e-9 if per else None


def _module_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def breakdown(summary: Summary, top: int = 10) -> dict:
    """The programs that took most device time in the window, and the
    longest idle gaps named by the host span that covers each (the
    innermost one), from the first chip."""
    if not summary.chips:
        return {"device_ops": [], "idle_gaps": []}
    chip = summary.chips[0]
    src = chip.modules or chip.ops
    tot: dict = {}
    for n, s, e in _clip(src, summary.w0, summary.w1):
        key = _module_name(n)
        tot[key] = tot.get(key, 0.0) + (e - s) * 1e-9
    device_ops = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    busy = _union([(s, e) for _, s, e in
                   _clip(chip.ops or chip.modules, summary.w0, summary.w1)])
    gaps, t = [], summary.w0
    for s, e in busy + [[summary.w1, summary.w1]]:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    host = [(n, s, e) for n, s, e in summary.host if n != WINDOW_SPAN]
    named = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = 0.5 * (s + e)
        cover = [(he - hs, n) for n, hs, he in host if hs <= mid <= he]
        named.append([min(cover)[1] if cover else "no host span",
                      (e - s) * 1e-9])
    return {"device_ops": [[n, v] for n, v in device_ops],
            "idle_gaps": named}

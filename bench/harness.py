"""The general harness: it finds a cell's configuration, traffic mix and
metric readers by the names in ``BENCHMARK.json``, times set-up and the
measured window on the host clock, and assembles the result line.

What belongs to one configuration, mix or metric lives in a file of its
own, found by name:

* ``bench/configs/<config>.json`` the deployment's sizes as run, and
  ``bench/configs/<config>.py`` its data generator;
* ``bench/mixes/<traffic>.json`` the traffic mix: the job it runs
  (``bench/jobs/<job>.py``) and that job's parameters;
* ``bench/metrics/<family>.py`` the reader of every metric whose name is
  ``<family>`` or starts with ``<family>.``.

A job module gives ``setup(cell, seed, phases)``, ``run_window(state,
window, phases)``, ``release(state)``, ``check(state)`` and ``work(state)``
(see ``bench/jobs/udt_fit.py``).  A reader gives ``read(name, run)`` and
returns a number, or None where it finds nothing to read.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib
import json
import os
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
MIXES = BENCH / "mixes"
WINDOW_SPAN = "bench.window"


def load_spec(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def load_mix(name: str, mix_dir: Path = MIXES) -> dict:
    """The traffic mix ``<mix_dir>/<name>.json``."""
    path = Path(mix_dir) / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix file {path}")
    with open(path) as f:
        mix = json.load(f)
    if "job" not in mix:
        raise ValueError(f"traffic mix {path} names no job")
    return mix


def find_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` (kind: configs, jobs, metrics)."""
    return importlib.import_module(f"bench.{kind}.{name}")


def metric_reader(name: str):
    return find_module("metrics", name.split(".")[0])


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    data: object          # the configuration's generator module
    mix: dict
    job: object           # the mix's job module
    end_to_end: list
    per_layer: list


def load_cell(name: str, spec: dict | None = None, root: Path = ROOT,
              mix_dir: Path = MIXES) -> Cell:
    spec = spec if spec is not None else load_spec(root)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    with open(root / conf["file"]) as f:
        config = json.load(f)
    mix = load_mix(w["traffic"], mix_dir)

    def mine(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                data=find_module("configs", w["config"]), mix=mix,
                job=find_module("jobs", mix["job"]),
                end_to_end=mine(spec["end_to_end"]),
                per_layer=mine(spec["per_layer"]))


class Phases:
    """Set-up broken into named phases on the host clock, each also a
    span in the profiler's trace when one is running."""

    def __init__(self):
        self.seconds: dict = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"bench.setup.{name}"):
            yield
        self.seconds[name] = (self.seconds.get(name, 0.0)
                              + time.perf_counter() - t0)


@dataclasses.dataclass
class Unit:
    rows: int
    configs: int


class Window:
    """The measured window.  It opens when the job says so and closes at
    the end of the first whole unit of work (a tree, a round, a ``tune``
    call) that ends ``seconds`` or more after it opened.  Each unit is a
    span in the trace, so that idle gaps can be put down to it."""

    def __init__(self, seconds: float, unit_name: str,
                 trace_dir: str | None = None):
        self.seconds = float(seconds)
        self.unit_name = unit_name
        self.trace_dir = trace_dir
        self.units: list = []
        self.t0 = self.t1 = None
        self._spans: list = []

    def _enter(self, name):
        import jax
        span = jax.profiler.TraceAnnotation(name)
        span.__enter__()
        self._spans.append(span)

    def _exit(self):
        self._spans.pop().__exit__(None, None, None)

    def open(self) -> None:
        import jax
        if self.trace_dir is not None:
            jax.profiler.start_trace(self.trace_dir)
        self._enter(WINDOW_SPAN)
        self.t0 = time.perf_counter()
        self._enter(self.unit_name)

    def unit_done(self, rows: int = 0, configs: int = 0) -> bool:
        """Record a finished unit (its outputs ready); True once the
        window has closed."""
        t = time.perf_counter()
        self._exit()
        self.units.append(Unit(rows, configs))
        if t - self.t0 < self.seconds:
            self._enter(self.unit_name)
            return False
        self.t1 = t
        self._exit()
        if self.trace_dir is not None:
            import jax
            jax.profiler.stop_trace()
        return True

    @property
    def closed(self) -> bool:
        return self.t1 is not None

    @property
    def length(self) -> float:
        return self.t1 - self.t0

    @property
    def rows(self) -> int:
        return sum(u.rows for u in self.units)

    @property
    def configs(self) -> int:
        return sum(u.configs for u in self.units)


@dataclasses.dataclass
class Check:
    """One number compared, beside its limit (a number passes at or
    under its limit)."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read."""
    cell: Cell
    process_start: float
    setup: dict
    window: Window
    trace: object | None
    work: dict
    device_kind: str


def peak_memory(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             process_start: float, devices, trace_dir: str | None = None):
    """One run of one cell on ``devices``: set-up, the window, the check.
    Returns the result line's dict (checks last) and the Run."""
    phases = Phases()
    job = cell.job
    state = job.setup(cell, seed, phases)
    window = Window(seconds, f"bench.{cell.mix['job']}.unit",
                    trace_dir if trace else None)
    job.run_window(state, window, phases)
    if not window.closed:
        raise RuntimeError("the job returned before its window closed")
    kind = devices[0].device_kind
    mem = peak_memory(devices)
    job.release(state)
    attempted, failed, checks = job.check(state)
    summary = None
    if trace:
        from bench import trace as trace_mod
        summary = trace_mod.load(trace_dir)
    run = Run(cell=cell, process_start=process_start, setup=phases.seconds,
              window=window, trace=summary, work=job.work(state),
              device_kind=kind)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = metric_reader(m["name"]).read(m["name"], run)
        if value is None:
            if not trace:
                raise RuntimeError(f"no reading of end-to-end metric "
                                   f"{m['name']}")
            continue
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": mem}
    line = {"correct": bool(checks) and all(c.ok for c in checks)
            and failed == 0,
            "attempted": attempted, "failed": failed, "metrics": metrics,
            "device": device}
    if summary is not None:
        from bench import trace as trace_mod
        device["busy_s"] = trace_mod.busy_s(summary)
        device["window_s"] = summary.window_s
        line["breakdown"] = trace_mod.breakdown(summary)
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in checks}
    return line, run


def setup_lines(phases: dict, window: Window, process_start: float) -> str:
    parts = ", ".join(f"{k}={v:.3f}" for k, v in phases.items())
    return (f"setup: {parts}; window opened "
            f"{window.t0 - process_start:.3f}s after start")


def env_cache_dir(root: Path = ROOT) -> str:
    """JAX's persistent compile cache: ``JAX_COMPILATION_CACHE_DIR`` when
    set, else one fixed directory inside the checkout."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")

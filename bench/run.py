"""Run one cell of the chip benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix and metrics come from
``BENCHMARK.json`` at the root of the checkout.  The run makes its data
from ``--seed``, sets up (timed), measures for ``--seconds`` whole units
of work, checks what the window produced against the plain reference
(bench/reference.py), and prints as its last line of standard output one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer metrics
from a profiler trace of the window), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared with its limit.
The same numbers end standard error.

Without a TPU, or with fewer chips than the cell asks for, it exits
nonzero before any phase and prints no result.  JAX's persistent
compile cache is ``JAX_COMPILATION_CACHE_DIR`` when set, else
``.jax_cache/`` in the checkout.
"""
import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def fail(msg: str):
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    sys.exit(2)


def require_chips(n: int):
    """The devices a cell runs on; exits nonzero without a TPU."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"no TPU: JAX's devices are on {devices[0].platform!r}; the "
             "benchmark runs only on a TPU")
    if len(devices) < n:
        fail(f"the cell needs {n} TPU chips, JAX finds {len(devices)}")
    return devices


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    return args


def main(argv=None) -> None:
    args = parse(argv)
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        fail(f"the program under test is missing: no {src}/repro")
    sys.path[:0] = [ROOT, src]
    # the TPU runtime's logs stay inside the checkout
    logs = os.environ.setdefault("TPU_LOG_DIR",
                                 os.path.join(ROOT, "bench_out", "tpu_logs"))
    os.makedirs(logs, exist_ok=True)
    from bench import harness
    cell = harness.load_cell(args.workload)
    import jax
    devices = require_chips(cell.chips)
    jax.config.update("jax_compilation_cache_dir", harness.env_cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    trace_dir = os.path.join(ROOT, "bench_out", "trace", args.workload)
    shutil.rmtree(trace_dir, ignore_errors=True)
    line, run = harness.run_cell(cell, args.seed, args.seconds,
                                 bool(args.trace), PROCESS_START,
                                 devices[:cell.chips], trace_dir)
    print(harness.setup_lines(run.setup, run.window, PROCESS_START),
          flush=True)
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()

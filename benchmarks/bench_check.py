"""check-gate: the repro.check contract table as a blocking CI gate.

Contract: every declared performance contract in ``repro.check.contracts``
must hold — the static-analysis twin of the perf gates.  Where the bench
gates measure (scatter-work ratios, compile counts, parity), this gate
*proves structure*: the sharded level step carries exactly one
histogram-sized collective, the GOSS sampler moves no rows across shards,
the serve lowering donates its batch buffer, no hot path hides a host
callback or an f64.  Nothing executes — the whole table traces in
seconds, so regressions surface before any benchmark runs.

Runs ``python -m repro.check --gate`` in a CPU subprocess
(``JAX_PLATFORMS=cpu``): the distributed contracts want 8 forced host
devices, which must be set before jax import — the driver process has long
since imported jax (same pattern as bench_dist_goss).  Standalone:
``python -m benchmarks.bench_check --gate``.
"""
from __future__ import annotations

import os
import subprocess
import sys


def gate() -> int:
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = src + (os.pathsep + env["PYTHONPATH"]
                               if env.get("PYTHONPATH") else "")
    env.pop("XLA_FLAGS", None)          # let __main__ force 8 devices
    # the contracts trace on forced host devices; on a machine with a chip
    # the child must never compete with a parent that already holds it
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-m", "repro.check", "--gate"],
                       env=env, text=True, capture_output=True, timeout=900)
    sys.stdout.write(r.stdout)
    sys.stderr.write(r.stderr)
    return r.returncode


def main() -> None:
    sys.exit(gate())


if __name__ == "__main__":
    main()

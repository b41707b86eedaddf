"""Benchmark orchestrator: one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full | --smoke | --gate]

Prints ``name,...`` CSV per row.  --full uses paper-scale dataset sizes
(minutes on CPU); the default is a reduced-scale pass that exercises every
benchmark path; --smoke is the artifact pass (tiny shapes, seconds: one
dataset per roster plus the sibling-subtraction report, BENCH_*.json
artifacts uploaded by the workflow).  --gate is the consolidated blocking
CI driver: it runs EVERY registered bench gate (each still runnable
standalone via ``python -m benchmarks.bench_<name> --gate``), prints one
per-gate pass/fail table — appended to ``$GITHUB_STEP_SUMMARY`` when set —
and exits nonzero if any gate fails, so the workflow needs exactly one
blocking step instead of one copy-pasted step per gate."""
from __future__ import annotations

import os
import sys
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from benchmarks import bench_selection, bench_udt_cls, bench_udt_reg
from benchmarks import (bench_chaos, bench_check, bench_dist_goss,
                        bench_goss, bench_kdd99, bench_kernels,
                        bench_logistic, bench_serve_forest,
                        bench_subtraction, bench_toot)

# every blocking gate, in dependency-light-first order; each entry is
# (name, module) where module.gate() returns 0 (pass) / 1 (fail)
GATES = (
    ("check", bench_check),
    ("subtraction", bench_subtraction),
    ("goss", bench_goss),
    ("logistic", bench_logistic),
    ("dist_goss", bench_dist_goss),
    ("serve_forest", bench_serve_forest),
    ("kdd99", bench_kdd99),
    ("toot", bench_toot),
    ("chaos", bench_chaos),
)


def run_gates() -> int:
    """Run every registered gate, emit one summary table, return worst rc.

    A gate that raises counts as failed but never stops the others — CI
    should always report the COMPLETE pass/fail picture, not the first
    casualty."""
    results = []
    for name, mod in GATES:
        print(f"\n=== gate: {name} "
              f"(python -m benchmarks.{mod.__name__.split('.')[-1]} "
              "--gate) ===")
        try:
            rc = int(mod.gate())
        except SystemExit as e:       # tolerate gates that sys.exit()
            rc = int(e.code or 0)
        except Exception:
            traceback.print_exc()
            rc = 1
        results.append((name, rc))

    rows = ["| gate | status |", "| --- | --- |"]
    rows += [f"| {name} | {'pass' if rc == 0 else '**FAIL**'} |"
             for name, rc in results]
    table = "\n".join(rows)
    n_fail = sum(1 for _, rc in results if rc)
    verdict = (f"{len(results)} gates, {n_fail} failed"
               if n_fail else f"all {len(results)} gates passed")
    print(f"\n{table}\n\nbench-gate: {verdict}")
    summary = os.environ.get("GITHUB_STEP_SUMMARY")
    if summary:
        with open(summary, "a") as f:
            f.write(f"### Bench gates — {verdict}\n\n{table}\n")
    return 1 if n_fail else 0


def main() -> None:
    if "--gate" in sys.argv:
        sys.exit(run_gates())
    full = "--full" in sys.argv
    smoke = "--smoke" in sys.argv
    scale = 1.0 if full else 0.1

    print("# paper Table 5 — selection scaling (us per call)")
    if smoke:
        bench_selection.run(sizes=(1_000, 2_000))
    else:
        bench_selection.run(sizes=(2_000, 4_000, 8_000, 16_000) if not full
                            else (10_000, 25_000, 50_000, 100_000))

    print("# paper Table 6 — UDT classification roster (synthetic re-gen)")
    print("udt_cls,name,m,k,c,full_nodes,full_depth,train_ms,tune_ms,"
          "n_configs,acc,tuned_nodes,tuned_depth,retrain_ms,naive_tune_est_ms")
    roster = (bench_udt_cls.ROSTER[:1] if smoke
              else bench_udt_cls.ROSTER if full else bench_udt_cls.ROSTER[:4])
    for name in roster:
        bench_udt_cls.run_one(name, scale=1.0 if full else scale)

    print("# paper Table 7 — UDT regression roster")
    print("udt_reg,name,m,k,full_nodes,full_depth,train_ms,tune_ms,"
          "n_configs,mae,rmse")
    roster = (bench_udt_reg.ROSTER[:1] if smoke
              else bench_udt_reg.ROSTER if full else bench_udt_reg.ROSTER[:2])
    for name in roster:
        bench_udt_reg.run_one(name, scale=1.0 if full else scale)

    print("# sibling histogram subtraction (writes BENCH_subtraction.json)")
    if smoke:
        bench_subtraction.run(**bench_subtraction.SMOKE)
    elif full:
        bench_subtraction.run()
    else:   # reduced-scale default, like the roster benches above
        bench_subtraction.run(m=8_000, k=8, c=3, max_depth=7,
                              onehot_m=3_000)

    print("# GOSS-sampled boosting (writes BENCH_goss.json)")
    if smoke:
        bench_goss.run(**bench_goss.SMOKE)
    elif full:
        bench_goss.run()
    else:   # reduced-scale default
        bench_goss.run(m=8_000, k=8, n_trees=10, max_depth=6)

    print("# Newton-step logistic boosting (writes BENCH_logistic.json)")
    if smoke:
        bench_logistic.run(**bench_logistic.SMOKE)
    elif full:
        bench_logistic.run()
    else:   # reduced-scale default
        bench_logistic.run(m=8_000, k=8, n_trees=10, max_depth=6)

    print("# distributed GOSS boosting, forced 8-device mesh subprocess "
          "(writes BENCH_dist_goss.json)")
    if smoke:
        bench_dist_goss.run(**bench_dist_goss.SMOKE)
    elif full:
        bench_dist_goss.run()
    else:   # reduced-scale default
        bench_dist_goss.run(m=8_000, k=8, n_trees=8, max_depth=6)

    print("# KDD99 multiclass softmax boosting (writes BENCH_kdd99.json)")
    if smoke:
        bench_kdd99.run(**bench_kdd99.SMOKE)
    elif full:
        bench_kdd99.run()
    else:   # reduced-scale default
        bench_kdd99.run(m=20_000, n_trees=8, max_depth=6)

    print("# TOOT design-space sweep vs retrain oracle "
          "(writes BENCH_toot.json)")
    if smoke:
        bench_toot.run(**bench_toot.SMOKE)
    elif full:
        bench_toot.run()
    else:   # reduced-scale default
        bench_toot.run(m=8_000, k=8, ens_trees=8)

    print("# chaos harness: fault injection + resume parity "
          "(writes BENCH_chaos.json)")
    bench_chaos.run(**bench_chaos.SMOKE)    # one scenario at every scale

    print("# multi-tenant forest serving (writes BENCH_serve.json)")
    if smoke:
        bench_serve_forest.run(**bench_serve_forest.SMOKE)
    elif full:
        bench_serve_forest.run()
    else:   # reduced-scale default
        bench_serve_forest.run(m=8_000, k=8, n_requests=100)

    if not smoke:
        print("# kernel micro-bench")
        bench_kernels.main()


if __name__ == "__main__":
    main()

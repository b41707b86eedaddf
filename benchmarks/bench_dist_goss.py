"""Distributed GOSS benchmark: scatter work, collective bytes and quality
for the mesh-sharded boosted-ensemble loop (Newton logistic + GOSS +
sibling subtraction + slot_scatter composed on a forced-host device mesh).

    PYTHONPATH=src python -m benchmarks.bench_dist_goss [--smoke | --gate]

Measures three fits of the same logistic task at smoke shapes on a 4x2
(data x model) mesh of 8 forced host CPU devices:

  * the single-shard GOSS loop (the PR 3/4 path) — the quality reference;
  * the sharded GOSS loop (``fit(mesh=...)``) — per-shard-quota sampling
    with the scalar threshold merge, weights in the in-kernel channel;
  * the sharded UNSAMPLED loop — the scatter-work denominator.

Scatter work counts the example rows each level's histogram pass actually
accumulates (the builder's own per-level BuildState, exactly as
bench_goss; assign = -1 rows — the shard-local GOSS rejection mask — are
inert, so the sharded GOSS fit's root pass covers only the selected
quota).  Collective bytes are accounted per level from the same states:
``rows_hist * K_pad * B * C * 4`` where ``rows_hist`` is the packed pair
count ``width/2`` whenever the parent cache rode along, else the full
width — the dense/packed ratio is the sibling-subtraction halving of the
per-level histogram collective, and with slot_scatter on the packed bytes
are additionally split over the data shards (reported as
``collective_bytes_per_shard``).  Both numbers are deterministic functions
of the built trees, not wall-clocks.

The measurement runs in a worker subprocess on the CPU
(``JAX_PLATFORMS=cpu``) so the forced 8-device XLA_FLAGS never leak into
the caller (benchmarks/run.py --smoke runs in a 1-device process by
design) and the worker never competes for an accelerator.  Writes
BENCH_dist_goss.json for the cross-PR perf trajectory.  ``--gate`` is the blocking CI mode: it re-runs
the smoke shapes into a throwaway path (no self-ratcheting, same rule as
the other gates) and exits nonzero when the sharded scatter-work ratio
drops below the 2x floor / materially below the committed baseline, the
sharded AUC falls below the single-shard AUC by more than the tolerance
(or below the absolute floor), or the collective-bytes ratio loses the
subtraction halving.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# the one definition of the CI smoke-gate shapes (benchmarks/run.py --smoke
# and the --gate mode both use it, so artifacts stay comparable)
SMOKE = dict(m=6_000, k=6, n_trees=10, max_depth=5, n_bins=32,
             top_rate=0.1, other_rate=0.1, seed=0)

MIN_RATIO = 2.0        # sharded unsampled/GOSS scatter-work floor
AUC_DROP = 0.05        # auc_dist >= auc_single - AUC_DROP
AUC_FLOOR = 0.68       # absolute floor (base-rate predictor scores 0.5)
COLLECTIVE_FLOOR = 1.5  # dense/packed per-level collective bytes
BASELINE_SLACK = 0.95  # tolerated fraction of the committed baseline ratio


def _measure(m, k, n_trees, max_depth, n_bins, top_rate, other_rate, seed):
    """Worker-side measurement (requires the forced 8-device XLA_FLAGS to
    be set BEFORE jax import — only ever called in the subprocess)."""
    import numpy as np

    from benchmarks.bench_goss import (_fit_counting, _fit_states,
                                       _level_rows)
    from benchmarks.bench_logistic import auc
    from repro.core import GossConfig, GradientBoostedTrees, TreeConfig
    from repro.core import fit_bins, transform
    from repro.data import make_classification, train_val_test_split

    import jax
    from jax.sharding import Mesh
    from repro.core.distributed import DistConfig

    assert len(jax.devices()) == 8, len(jax.devices())
    mesh = Mesh(np.asarray(jax.devices()).reshape(4, 2), ("data", "model"))
    dist = DistConfig(data_axes=("data",), model_axis="model")
    d_shards, f_shards = 4, 2

    cols, y = make_classification(m, k, 2, seed=seed, teacher_depth=6,
                                  noise=0.1)
    (tr_c, tr_y), (va_c, va_y), _ = train_val_test_split(cols, y, seed=seed)
    table = fit_bins(tr_c, max_num_bins=n_bins)
    vb = transform(va_c, table)
    tr_y = tr_y.astype(np.float32)
    cfg = TreeConfig(max_depth=max_depth, task="regression_variance")
    goss = GossConfig(top_rate=top_rate, other_rate=other_rate)
    mk = lambda g: GradientBoostedTrees(n_trees=n_trees, config=cfg,
                                        seed=seed, loss="logistic", goss=g)

    # single-shard GOSS loop: the quality reference
    single = mk(goss)
    _, single_s = _fit_counting(single, table, tr_y)
    auc_single = auc(va_y, single.predict_proba(vb))

    # sharded GOSS loop
    dist_goss = mk(goss)
    goss_states, dist_s = _fit_states(dist_goss, table, tr_y, mesh=mesh,
                                      dist=dist)
    goss_rows = _level_rows(goss_states)
    auc_dist = auc(va_y, dist_goss.predict_proba(vb))

    # sharded unsampled loop: the scatter-work denominator
    dist_full = mk(None)
    full_states, full_s = _fit_states(dist_full, table, tr_y, mesh=mesh,
                                      dist=dist)
    full_rows = _level_rows(full_states)
    auc_full = auc(va_y, dist_full.predict_proba(vb))

    # per-level collective bytes from the sharded GOSS fit's own states:
    # packed = width/2 whenever the parent cache rode along (subtraction),
    # dense = the no-subtraction psum of the full level histogram.
    k_pad = table.bins.shape[1] + (-table.bins.shape[1]) % f_shards
    row_bytes = k_pad * n_bins * 3 * 4                  # [K, B, C] f32
    packed = dense = 0
    for states in goss_states:
        packed += row_bytes                             # root level
        dense += row_bytes
        for st in states:
            width = st.level_end - st.level_start
            if width <= 0:
                break
            sub_on = st.phist is not None and width % 2 == 0
            packed += (width // 2 if sub_on else width) * row_bytes
            dense += width * row_bytes

    return dict(
        config=dict(m=m, k=k, n_trees=n_trees, max_depth=max_depth,
                    n_bins=n_bins, top_rate=top_rate, other_rate=other_rate,
                    seed=seed, d_shards=d_shards, f_shards=f_shards),
        total_full_rows=sum(full_rows), total_goss_rows=sum(goss_rows),
        scatter_work_ratio=round(sum(full_rows) / max(sum(goss_rows), 1), 3),
        auc_single=round(auc_single, 4), auc_dist=round(auc_dist, 4),
        auc_full=round(auc_full, 4),
        collective_bytes_packed=packed, collective_bytes_dense=dense,
        collective_ratio=round(dense / max(packed, 1), 3),
        collective_bytes_per_shard=packed // d_shards,
        wall_single_s=round(single_s, 2), wall_dist_goss_s=round(dist_s, 2),
        wall_dist_full_s=round(full_s, 2),
    )


def _run_worker(shapes: dict) -> dict:
    """Spawn the forced-8-device measurement subprocess and parse its
    report (the orchestrating process must keep seeing 1 device)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    # the worker counts on forced host devices; on a machine with a chip
    # it must never compete with a parent that already holds the chip
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src"), root] +
        ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    r = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--worker",
         json.dumps(shapes)],
        env=env, capture_output=True, text=True, timeout=1800)
    if r.returncode != 0:
        raise RuntimeError(f"dist-goss worker failed:\n{r.stdout}\n{r.stderr}")
    line = [ln for ln in r.stdout.splitlines()
            if ln.startswith("DIST_GOSS_REPORT:")][-1]
    return json.loads(line[len("DIST_GOSS_REPORT:"):])


def run(m=20_000, k=10, n_trees=12, max_depth=6, n_bins=64, top_rate=0.1,
        other_rate=0.1, seed=0, out="BENCH_dist_goss.json"):
    report = _run_worker(dict(m=m, k=k, n_trees=n_trees, max_depth=max_depth,
                              n_bins=n_bins, top_rate=top_rate,
                              other_rate=other_rate, seed=seed))
    with open(out, "w") as f:
        json.dump(report, f, indent=2)
    print("dist_goss,metric,full,goss")
    print(f"dist_goss,scatter_rows,{report['total_full_rows']},"
          f"{report['total_goss_rows']}")
    print(f"dist_goss,auc,{report['auc_full']},{report['auc_dist']}")
    print(f"dist_goss_total,scatter {report['total_full_rows']} -> "
          f"{report['total_goss_rows']} ({report['scatter_work_ratio']}x "
          f"less), auc single {report['auc_single']} / sharded "
          f"{report['auc_dist']}, per-level collective "
          f"{report['collective_bytes_dense']} -> "
          f"{report['collective_bytes_packed']} B "
          f"({report['collective_ratio']}x, "
          f"{report['collective_bytes_per_shard']} B/shard), wall "
          f"{report['wall_dist_full_s']}s -> {report['wall_dist_goss_s']}s "
          f"(single-shard {report['wall_single_s']}s), -> {out}")
    return report


def gate(baseline_path="BENCH_dist_goss.json"):
    """Blocking CI gate: smoke run vs the committed baseline.

    Blocks on the sharded scatter-work ratio (>= the 2x floor and >=
    BASELINE_SLACK of the committed baseline), the sharded-vs-single AUC
    (>= auc_single - AUC_DROP and >= the absolute floor), and the
    per-level collective-bytes ratio (the subtraction halving must survive
    the weighted sharded loop).  Writes its own report to a throwaway path
    so a regressed run can never ratchet the committed baseline down (the
    bench_subtraction no-self-ratchet rule)."""
    baseline = None
    if os.path.exists(baseline_path):
        with open(baseline_path) as f:
            baseline = json.load(f)
    report = run(**SMOKE, out=os.path.join(
        tempfile.gettempdir(), "BENCH_dist_goss_gate.json"))
    ratio = report["scatter_work_ratio"]
    ok = ratio >= MIN_RATIO
    lines = [f"dist-goss-gate: sharded scatter-work ratio {ratio}x "
             f"(floor {MIN_RATIO}x) -> {'OK' if ok else 'FAIL'}"]
    want_auc = round(max(report["auc_single"] - AUC_DROP, AUC_FLOOR), 4)
    auc_ok = report["auc_dist"] >= want_auc
    ok = ok and auc_ok
    lines.append(f"dist-goss-gate: sharded auc {report['auc_dist']} "
                 f"(single-shard {report['auc_single']}, require >= "
                 f"{want_auc}) -> {'OK' if auc_ok else 'FAIL'}")
    coll_ok = report["collective_ratio"] >= COLLECTIVE_FLOOR
    ok = ok and coll_ok
    lines.append(f"dist-goss-gate: per-level collective ratio "
                 f"{report['collective_ratio']}x (floor {COLLECTIVE_FLOOR}x,"
                 f" {report['collective_bytes_per_shard']} B/shard) -> "
                 f"{'OK' if coll_ok else 'FAIL'}")
    if baseline is None:
        lines.append(f"dist-goss-gate: no baseline at {baseline_path} "
                     "(floor checks only)")
    elif baseline.get("config") != report["config"]:
        lines.append("dist-goss-gate: baseline config differs "
                     "(floor checks only)")
    else:
        want = BASELINE_SLACK * baseline["scatter_work_ratio"]
        rel_ok = ratio >= want
        ok = ok and rel_ok
        lines.append(f"dist-goss-gate: baseline ratio "
                     f"{baseline['scatter_work_ratio']}x, require >= "
                     f"{round(want, 3)}x -> {'OK' if rel_ok else 'FAIL'}")
    print("\n".join(lines))
    return 0 if ok else 1


def main():
    if "--worker" in sys.argv:
        shapes = json.loads(sys.argv[sys.argv.index("--worker") + 1])
        print("DIST_GOSS_REPORT:" + json.dumps(_measure(**shapes)))
        return
    if "--gate" in sys.argv:
        sys.exit(gate())
    if "--smoke" in sys.argv:
        return run(**SMOKE)
    return run()


if __name__ == "__main__":
    main()

"""Bring-up smoke run: the system's main path on a TPU at KDD99-10% width.

    python chip_smoke.py                # one chip (the default)
    python chip_smoke.py --four-chips   # the sharded paths on a 2x2 mesh

One chip, at the paper's own dataset width (the seeded synthetic KDD99-10%
twin: 494,021 rows x 41 columns, 3 categorical, 5 superclasses):

  * UDT: one full tree (paper protocol) on the default backends
    (``segment`` / ``jnp``) and again on the Pallas backends; the trees
    must be bit-identical, and the Pallas level step must hold a compiled
    Mosaic kernel (``tpu_custom_call``), never an interpreted one;
  * kernels: one histogram call and one split-scan call on the chip's
    arrays, equal to the ``kernels/ref.py`` oracles;
  * TOOT: ``tune`` on a held-out validation draw, checked against trees
    retrained at two grid cells;
  * boosting: a softmax ``GradientBoostedTrees`` whose training accuracy
    beats the base rate;
  * serving: a ``ForestServer`` answering requests for a binary logistic
    GBT (normal vs attack), each answer equal to the link of
    ``predict_raw`` on the same rows.

``--four-chips`` runs only the sharded paths on a ``jax.make_mesh`` 2x2
(data x model) mesh:

  * ``build_tree_distributed`` bit-identical to ``build_tree``;
  * a logistic ``fit(mesh=...)`` of depth-2 trees whose trees answer as
    the single-device fit's do, round by round, up to float summation
    order (this check carries the sharded boosting loop);
  * the sharded GOSS draw at full width: each data shard's amplified
    weight sums to its row count, and no unselected row outranks the top
    set;
  * a sharded GOSS fit whose validation AUC stays within the tolerance of
    ``benchmarks/bench_dist_goss.py`` of the single-device fit (both
    reach 1.0 on the synthetic twin, so this one only shows it runs).

Every reading printed before the last line is a bring-up reading on the
named device, not a benchmark number.  The last line is one JSON object,
``{"ok": true, "device": {...}}``.  Without a TPU the script exits nonzero
before any phase runs; any failed phase ends the run nonzero.

The persistent compile cache goes to ``JAX_COMPILATION_CACHE_DIR`` when
that is set, else to ``.jax_cache/`` next to this script.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

M_KDD99 = 494_021          # rows of the KDD99 10% subset
SEED = 0                   # data, boosting and sampling seed
# four chips: the sharded boosting loop against the one-device loop.
# float32 sums over 494,021 rows taken in another order differ by about
# sqrt(M) * 2**-24 ~ 4e-5 relative; a fault in the weight channel, the
# shard coverage or the score walk moves leaf values and split gains by
# whole percents.
PARITY_RTOL = 1e-3
N_BINS = 64                # fit_bins(max_num_bins=...): plus a missing bin
TREE_FIELDS = ("feat", "op", "tbin", "label", "count", "left", "right",
               "leaf")


def log(msg: str) -> None:
    print(msg, flush=True)


class Reading:
    """Prints each phase's numbers tagged with the device they came from."""

    def __init__(self, device_kind: str):
        self.device_kind = device_kind

    def __call__(self, phase: str, **values) -> None:
        body = ", ".join(f"{k}={v}" for k, v in values.items())
        log(f"[{phase}] {body} (bring-up reading on {self.device_kind}, "
            "not a benchmark number)")


def timed(fn, *args, **kwargs):
    """(result, seconds) with every output array ready."""
    import jax
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def same_tree(a, b) -> bool:
    import numpy as np
    if a.n_nodes != b.n_nodes:
        return False
    n = a.n_nodes
    return all(np.array_equal(np.asarray(getattr(a, f)[:n]),
                              np.asarray(getattr(b, f)[:n]))
               for f in TREE_FIELDS)


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------

def make_data(m: int, seed: int, read: Reading) -> dict:
    """The KDD99-10% twin binned for training, plus a held-out validation
    draw (a different seed of the same generator) binned with its bins."""
    import numpy as np
    from repro.core import fit_bins, transform
    from repro.data.kdd99 import SUPERCLASSES, synth_kdd99
    t0 = time.perf_counter()
    cols, y = synth_kdd99(m=m, seed=seed)
    table = fit_bins(cols, max_num_bins=N_BINS)
    vcols, vy = synth_kdd99(m=max(m // 10, 1_000), seed=seed + 1)
    vbins = transform(vcols, table)
    read("data", rows=table.bins.shape[0], features=table.bins.shape[1],
         bins=table.n_bins, categorical=int((table.n_cat > 0).sum()),
         classes=len(SUPERCLASSES), val_rows=len(vy),
         setup_s=round(time.perf_counter() - t0, 3))
    return dict(table=table, y=np.asarray(y), vbins=vbins, vy=np.asarray(vy),
                n_classes=len(SUPERCLASSES), seed=seed)


# ---------------------------------------------------------------------------
# one-chip phases
# ---------------------------------------------------------------------------

def phase_udt(d: dict, read: Reading) -> dict:
    """One full tree per backend pair; classification counts are integers,
    so the trees must agree bit for bit."""
    from repro.core import TreeConfig, build_tree
    table, y, c = d["table"], d["y"], d["n_classes"]
    cfg = TreeConfig(max_depth=64)
    cfg_p = TreeConfig(max_depth=64, hist_backend="pallas",
                       select_backend="pallas")
    # first builds compile every level-step shape; second builds are warm
    full, s_jnp = timed(build_tree, table, y, cfg, n_classes=c)
    full_p, s_pal = timed(build_tree, table, y, cfg_p, n_classes=c)
    check(same_tree(full, full_p), "UDT: pallas-backend tree differs from "
          "the default-backend tree")
    _, w_jnp = timed(build_tree, table, y, cfg, n_classes=c)
    _, w_pal = timed(build_tree, table, y, cfg_p, n_classes=c)
    read("udt", nodes=full.n_nodes, depth=full.max_tree_depth,
         first_build_s_segment_jnp=round(s_jnp, 3),
         first_build_s_pallas=round(s_pal, 3),
         warm_build_s_segment_jnp=round(w_jnp, 3),
         warm_build_s_pallas=round(w_pal, 3), trees_bit_identical=True)
    return dict(full=full)


def pallas_step_text(d: dict, num_slots: int = 16) -> str:
    """Compiled text of one Pallas-backend level step at the real shapes."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core.tree import _chunk_step, _init_arrays
    table, c = d["table"], d["n_classes"]
    m, k = table.bins.shape
    stats = np.eye(c, dtype=np.float32)[d["y"]]
    args = (jnp.asarray(table.bins), jnp.asarray(stats),
            jnp.zeros((m,), jnp.int32), jnp.zeros((m,), jnp.float32),
            jnp.zeros((m,), jnp.int32), _init_arrays(2 * m + 1),
            jnp.zeros((1, 1, 1, 1), jnp.float32), jnp.asarray(table.n_num),
            jnp.asarray(table.n_cat), jnp.int32(0), jnp.int32(1),
            jnp.int32(1), jnp.int32(1))
    kw = dict(num_slots=num_slots, n_bins=int(table.n_bins),
              heuristic="info_gain", task="classification",
              min_samples_split=2, min_samples_leaf=1, max_depth=64,
              max_nodes=2 * m + 1, hist_backend="pallas",
              select_backend="pallas", n_label_bins=1)
    return _chunk_step.lower(*args, **kw).compile().as_text()


def chunked_histogram_ref(bins, stats, slot, *, num_slots, n_bins,
                          rows=4096):
    """``ref.histogram_ref`` summed over row chunks: its one-hot tensor is
    [M, K, S*B], far past device memory at full width in one piece.
    Integer counts make the chunked sum exact."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ref
    m, k = bins.shape
    n = -(-m // rows)
    pad = n * rows - m
    bins = jnp.pad(bins, ((0, pad), (0, 0))).reshape(n, rows, k)
    stats = jnp.pad(stats, ((0, pad), (0, 0))).reshape(n, rows, -1)
    slot = jnp.pad(slot, (0, pad), constant_values=-1).reshape(n, rows)

    @jax.jit
    def run(bins, stats, slot):
        def body(acc, x):
            return acc + ref.histogram_ref(*x, num_slots=num_slots,
                                           n_bins=n_bins), None
        init = jnp.zeros((num_slots, k, n_bins, stats.shape[-1]),
                         jnp.float32)
        return jax.lax.scan(body, init, (bins, stats, slot))[0]
    return run(bins, stats, slot)


def phase_kernels(d: dict, read: Reading, num_slots: int = 16) -> None:
    """One histogram and one split-scan kernel call at full width against
    the ``kernels/ref.py`` oracles, on device arrays."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops, ref
    table, c = d["table"], d["n_classes"]
    m, _ = table.bins.shape
    b = int(table.n_bins)
    rng = np.random.default_rng(d["seed"])
    bins = jnp.asarray(table.bins)
    stats = jnp.asarray(np.eye(c, dtype=np.float32)[d["y"]])
    slot = jnp.asarray(rng.integers(-1, num_slots, size=m), jnp.int32)
    hist, s_hist = timed(ops.histogram, bins, stats, slot,
                         num_slots=num_slots, n_bins=b)
    want = chunked_histogram_ref(bins, stats, slot, num_slots=num_slots,
                                 n_bins=b)
    check(np.array_equal(np.asarray(hist), np.asarray(want)),
          "kernels: histogram differs from ref.histogram_ref")
    n_num, n_cat = jnp.asarray(table.n_num), jnp.asarray(table.n_cat)
    (sc, tb, op), s_scan = timed(ops.split_scan, hist, n_num, n_cat)
    sc0, tb0, op0 = ref.split_scan_ref(hist, n_num, n_cat)
    check(np.allclose(np.asarray(sc), np.asarray(sc0), rtol=1e-5, atol=1e-5),
          "kernels: split_scan scores differ from ref.split_scan_ref")
    check(np.array_equal(np.asarray(tb), np.asarray(tb0))
          and np.array_equal(np.asarray(op), np.asarray(op0)),
          "kernels: split_scan (bin, op) differ from ref.split_scan_ref")
    # on a TPU the Pallas step must hold a compiled Mosaic kernel; anywhere
    # else the kernels run interpreted and no Mosaic call may appear
    on_tpu = jax.devices()[0].platform == "tpu"
    check(("tpu_custom_call" in pallas_step_text(d)) == on_tpu,
          "kernels: the Pallas level step's compiled Mosaic kernel "
          f"(tpu_custom_call) does not match the platform (tpu={on_tpu})")
    read("kernels", hist_shape=tuple(hist.shape),
         hist_first_call_s=round(s_hist, 3),
         split_scan_first_call_s=round(s_scan, 3), hist_equals_ref=True,
         split_scan_equals_ref=True, step_has_tpu_custom_call=on_tpu)


def toot_cells(grid) -> list:
    """One fixed interior cell of the grid, checked besides the winner."""
    nd, ns = grid.metric.shape
    return [(nd // 2, ns // 3)]


def phase_toot(d: dict, full, read: Reading) -> None:
    """Training-Only-Once Tuning priced from the one full tree, checked
    against trees retrained at the winning cell and one more cell."""
    import numpy as np
    from repro.core import TreeConfig, build_tree, predict_bins, tune
    table, y, vb, vy = d["table"], d["y"], d["vbins"], d["vy"]
    res, s_tune = timed(tune, full, vb, vy, table.n_num, train_size=len(y))
    grid = res.grid
    best = (int(np.flatnonzero(grid.dmax == res.best_dmax)[0]),
            int(np.flatnonzero(grid.smin == res.best_smin)[0]))
    for i, j in [best] + toot_cells(grid):
        dmax, smin = int(grid.dmax[i]), int(grid.smin[j])
        rt = build_tree(table, y, TreeConfig(max_depth=dmax,
                                             min_samples_split=smin),
                        n_classes=d["n_classes"])
        pred_rt = np.asarray(predict_bins(rt, vb, table.n_num))
        pred_pruned = np.asarray(predict_bins(
            full, vb, table.n_num, max_depth=dmax, min_samples_split=smin))
        check(np.array_equal(pred_rt, pred_pruned),
              f"TOOT: retrain at (max_depth={dmax}, min_samples_split="
              f"{smin}) predicts differently from the pruned full tree")
        acc = float((pred_rt == vy).mean())
        check(acc == float(grid.metric[i, j]),
              f"TOOT: grid metric {grid.metric[i, j]} != retrained "
              f"accuracy {acc} at ({dmax}, {smin})")
    read("toot", configs=res.n_configs, tune_s=round(s_tune, 3),
         best_max_depth=res.best_dmax, best_min_split=res.best_smin,
         val_acc=round(res.best_metric, 4), retrain_cells_equal=2)


def phase_boosting(d: dict, read: Reading, n_trees: int = 5) -> None:
    """Softmax boosting over the 5 superclasses beats the base rate."""
    import numpy as np
    from repro.core import GradientBoostedTrees, TreeConfig
    table, y = d["table"], d["y"]
    gbt = GradientBoostedTrees(
        n_trees=n_trees, loss="softmax", seed=d["seed"],
        config=TreeConfig(max_depth=6, task="regression_variance"))
    _, s_fit = timed(gbt.fit, table, y)
    acc = float((gbt.predict(table.bins) == y).mean())
    base = float(np.bincount(y).max() / len(y))
    check(acc > base, f"boosting: training accuracy {acc} does not beat "
          f"the base rate {base}")
    val_acc = float((gbt.predict(d["vbins"]) == d["vy"]).mean())
    read("boosting", rounds=n_trees, class_trees=len(gbt.trees),
         fit_s=round(s_fit, 3), train_acc=round(acc, 4),
         base_rate=round(base, 4), val_acc=round(val_acc, 4))


def phase_serving(d: dict, read: Reading) -> None:
    """A binary logistic GBT (normal vs attack) behind a ForestServer:
    every answer equals the link of ``predict_raw`` on the same rows."""
    import numpy as np
    from repro.core import GradientBoostedTrees, TreeConfig
    from repro.serve import BatchPolicy, ForestServer, ModelRegistry
    table, vb = d["table"], d["vbins"]
    gbt = GradientBoostedTrees(
        n_trees=8, loss="logistic", seed=d["seed"],
        config=TreeConfig(max_depth=6, task="regression_variance"))
    gbt.fit(table, (d["y"] != 0).astype(np.float32))
    registry = ModelRegistry(capacity=2)
    mid = registry.add("kdd99-attack", gbt)
    server = ForestServer(registry, BatchPolicy(buckets=(1, 8, 64, 512)))
    sizes = (1, 8, 64, 512, 300)
    edges = np.cumsum((0,) + sizes)
    check(edges[-1] <= len(vb), "serving: validation draw too small")
    reqs = [vb[a:b] for a, b in zip(edges[:-1], edges[1:])]
    t0 = time.perf_counter()
    pending = [server.submit(mid, r) for r in reqs]
    server.flush()
    got = [p.result() for p in pending]
    s_serve = time.perf_counter() - t0
    for r, g in zip(reqs, got):
        # predict_proba is the loss link applied to predict_raw's scores
        check(np.array_equal(g, gbt.predict_proba(r)), "serving: "
              "ForestServer answer differs from the link of predict_raw "
              "on the same rows")
    read("serving", requests=len(reqs), rows=int(edges[-1]),
         compiles=server.compile_count, first_flush_s=round(s_serve, 3),
         answers_equal_predict_raw=True)


def run_single_chip(m: int = M_KDD99, seed: int = SEED, read=None) -> None:
    """Every one-chip phase; raises on the first failed check."""
    read = read or Reading("unknown")
    d = make_data(m, seed, read)
    full = phase_udt(d, read)["full"]
    phase_kernels(d, read)
    phase_toot(d, full, read)
    phase_boosting(d, read)
    phase_serving(d, read)


# ---------------------------------------------------------------------------
# four-chip phase
# ---------------------------------------------------------------------------

def logistic_gbt(seed: int, n_trees: int = 10, max_depth: int = 6,
                 goss=None):
    """The binary (normal vs attack) boosted ensemble the four-chip phase
    fits on one device and on the mesh."""
    from repro.core import GradientBoostedTrees, TreeConfig
    return GradientBoostedTrees(
        n_trees=n_trees, loss="logistic", seed=seed, goss=goss,
        config=TreeConfig(max_depth=max_depth, task="regression_variance"))


def round_parity(one, mesh_fit, table) -> tuple[int, bool]:
    """Round by round, the mesh fit's tree must answer every training row
    as the one-device fit's tree does, to ``PARITY_RTOL`` of the leaf
    scale: each round's tree is grown from the raw scores the earlier
    rounds' sharded walks left, so this holds the sharded build, its
    weight channel and the score walk together.

    Another order of summation may break a near-tie between two splits the
    other way; from that round on the two fits grow different (equally
    good) trees.  Such a round ends the comparison only if its best-split
    gains agree to ``PARITY_RTOL``; any other difference fails.  Returns
    (rounds that matched, whether a tie ended the comparison); at least
    the first round must match."""
    import numpy as np
    from repro.core import predict_bins
    for k, (ta, tb) in enumerate(zip(one.trees, mesh_fit.trees)):
        pa = np.asarray(predict_bins(ta, table.bins, table.n_num))
        pb = np.asarray(predict_bins(tb, table.bins, table.n_num))
        if np.abs(pa - pb).max() <= PARITY_RTOL * np.abs(pa).max():
            continue
        ga, gb = float(ta.score[0]), float(tb.score[0])
        check(k > 0 and abs(ga - gb) <= PARITY_RTOL * abs(ga),
              f"four chips: round {k} of the sharded fit grew another tree "
              f"(root gain {gb} against {ga}, leaf gap "
              f"{np.abs(pa - pb).max()})")
        return k, True
    return len(one.trees), False


def sampler_check(mesh, dist, yb, raw, goss, seed: int) -> dict:
    """The sharded GOSS draw at full width, held to what the per-shard
    quota scheme guarantees whatever the accumulation order:

      * each data shard's selected weight (top rows at 1, drawn rows at
        the exact ``r_s / q_oth`` amplification) sums to the shard's
        valid row count, so no amplification error and no dropped shard;
      * no unselected row outranks a top-set row in leverage ``|g| sqrt(h)``.

    ``raw`` is a fitted ensemble's score, so the leverage is not the tied
    round-0 constant.  The mask's agreement with the single-device
    reference ``goss_sample_sharded_ref`` is reported."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.distributed import make_sharded_sampler
    from repro.core.forest import goss_sample_sharded_ref
    from repro.core.losses import get_loss
    d = int(np.prod([mesh.shape[a] for a in dist.data_axes]))
    m = len(yb)
    m_pad = -(-m // d) * d
    lo = get_loss("logistic")
    q_top, q_oth = goss.shard_quota(m, d)
    sampler = make_sharded_sampler(mesh, dist, lo, goss, m, q_top, q_oth)
    rows = NamedSharding(mesh, P(dist.data_axes))
    y_p = np.pad(yb.astype(np.float32), (0, m_pad - m))
    raw_p = np.pad(raw.astype(np.float32), (0, m_pad - m))
    key = jax.random.PRNGKey(seed)
    _, w, assign0 = sampler(jax.device_put(y_p, rows),
                            jax.device_put(raw_p, rows), key)
    g, h = lo.grad_hess(jnp.asarray(y_p), jnp.asarray(raw_p))
    sel = np.asarray(assign0) == 0
    w_goss = np.where(sel, np.asarray(w) / np.asarray(h), 0.0)
    lv = np.abs(np.asarray(g * jnp.sqrt(h)))
    valid = np.arange(m_pad) < m
    shard_w = w_goss.reshape(d, -1).sum(axis=1)
    shard_rows = valid.reshape(d, -1).sum(axis=1)
    check(np.allclose(shard_w, shard_rows, rtol=1e-4, atol=0),
          f"four chips: sharded GOSS weight per shard {shard_w} != valid "
          f"rows per shard {shard_rows}")
    top = sel & np.isclose(w_goss, 1.0, rtol=1e-5, atol=0)
    out = valid & ~sel
    check(lv[top].min() >= lv[out].max() * (1 - 1e-6),
          f"four chips: an unselected row outranks the top set "
          f"({lv[out].max()} > {lv[top].min()})")
    w_ref = np.asarray(goss_sample_sharded_ref(
        g * jnp.sqrt(h), key, d_shards=d, m_valid=m, q_top=q_top,
        q_oth=q_oth))
    return dict(selected=int(sel.sum()), top=int(top.sum()),
                shard_weight=[round(float(x), 2) for x in shard_w],
                shard_rows=[int(x) for x in shard_rows],
                mask_mismatch_vs_ref=int(((w_ref > 0) != sel).sum()))


def run_four_chips(m: int = M_KDD99, seed: int = SEED, read=None) -> None:
    """The sharded paths on a 2x2 (data x model) ``jax.make_mesh`` mesh."""
    import jax
    import numpy as np
    from benchmarks.bench_dist_goss import AUC_DROP, AUC_FLOOR
    from benchmarks.bench_logistic import auc
    from repro.core import GossConfig, TreeConfig, build_tree
    from repro.core.distributed import DistConfig, build_tree_distributed
    read = read or Reading("unknown")
    d = make_data(m, seed, read)
    table, y, c = d["table"], d["y"], d["n_classes"]
    mesh = jax.make_mesh((2, 2), ("data", "model"))
    dist = DistConfig(data_axes=("data",), model_axis="model")
    cfg = TreeConfig(max_depth=64)
    local, s_local = timed(build_tree, table, y, cfg, n_classes=c)
    sharded, s_dist = timed(build_tree_distributed, table, y, cfg,
                            mesh=mesh, dist=dist, n_classes=c)
    check(same_tree(local, sharded), "four chips: build_tree_distributed "
          "differs from build_tree")
    read("dist-tree", mesh=dict(mesh.shape), nodes=local.n_nodes,
         build_s_local=round(s_local, 3), build_s_sharded=round(s_dist, 3),
         trees_bit_identical=True)

    yb = (y != 0).astype(np.float32)
    vb, vyb = d["vbins"], (d["vy"] != 0).astype(int)

    stumps = dict(n_trees=10, max_depth=2)
    one, s_one = timed(logistic_gbt(seed, **stumps).fit, table, yb)
    mesh_fit, s_mesh = timed(logistic_gbt(seed, **stumps).fit, table, yb,
                             mesh=mesh, dist=dist)
    matched, tie = round_parity(one, mesh_fit, table)
    read("dist-fit", rounds=stumps["n_trees"], rounds_matched=matched,
         ended_by_tie=tie, fit_s_single=round(s_one, 3),
         fit_s_sharded=round(s_mesh, 3))

    goss = GossConfig(top_rate=0.1, other_rate=0.1)
    read("dist-goss-draw", **sampler_check(
        mesh, dist, yb, one.predict_raw(table.bins), goss, seed))

    def goss_fit(**kw):
        gbt, s = timed(logistic_gbt(seed, goss=goss).fit, table, yb, **kw)
        p = gbt.predict_proba(vb)
        q = np.clip(p, 1e-7, 1 - 1e-7)
        logloss = float(-np.mean(vyb * np.log(q) + (1 - vyb) * np.log(1 - q)))
        return auc(vyb, p), logloss, s

    auc_single, ll_single, s_single = goss_fit()
    auc_dist, ll_dist, s_sharded = goss_fit(mesh=mesh, dist=dist)
    want = max(auc_single - AUC_DROP, AUC_FLOOR)
    check(auc_dist >= want, f"four chips: sharded GOSS AUC {auc_dist} < "
          f"{want} (single-device {auc_single})")
    read("dist-goss", auc_single=round(auc_single, 4),
         auc_sharded=round(auc_dist, 4), auc_required=round(want, 4),
         val_logloss_single=ll_single, val_logloss_sharded=ll_dist,
         fit_s_single=round(s_single, 3), fit_s_sharded=round(s_sharded, 3))


# ---------------------------------------------------------------------------

def require_tpu(n_chips: int):
    """The devices to run on; exits nonzero (no phase run) without a TPU."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found: JAX's devices are on platform "
                 f"{devices[0].platform!r} ({len(devices)} device(s)); "
                 "this script runs only on a TPU")
    if len(devices) < n_chips:
        sys.exit(f"chip_smoke: --four-chips needs 4 TPU devices, found "
                 f"{len(devices)}")
    return devices


def use_compile_cache() -> None:
    """``JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it itself), else
    one fixed directory inside the checkout."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded paths on a 2x2 mesh")
    args = ap.parse_args()
    n_chips = 4 if args.four_chips else 1
    devices = require_tpu(n_chips)
    use_compile_cache()
    read = Reading(devices[0].device_kind)
    t0 = time.perf_counter()
    if args.four_chips:
        run_four_chips(read=read)
    else:
        run_single_chip(read=read)
    log(f"[total] wall_s={round(time.perf_counter() - t0, 3)}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()

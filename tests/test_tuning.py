"""Training-Only-Once Tuning: the paper's central claim is that a full tree
pruned at predict-time with (max_depth, min_split) behaves EXACTLY like a
tree retrained with those hyper-parameters ("the tree would be built with
exactly the same pattern").

PR 8 extends the contract to the full design space: ``sweep`` prices the
(max_depth x min_samples_split x min_child_weight) grid — min_child_weight
is exact because the builder applies it as a post-selection stopping rule —
plus the ensemble ``n_rounds`` prefix axis, all bit-identical to
retrain-per-config oracles, with a per-cell cost model
(``prune_stats``-parity node counts) and a non-dominated Pareto front."""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import (fit_bins, transform, build_tree, TreeConfig,
                        predict_bins, tune, toot_grid, prune_stats,
                        sweep, SweepSpace, pareto_front,
                        GradientBoostedTrees, GossConfig)
from repro.data import make_classification, make_regression, train_val_test_split


@pytest.fixture(scope="module")
def setup():
    cols, y = make_classification(3000, 8, 3, seed=7, n_cat_features=2)
    (tr_c, tr_y), (va_c, va_y), (te_c, te_y) = train_val_test_split(cols, y)
    table = fit_bins(tr_c, max_num_bins=64)
    full = build_tree(table, tr_y, TreeConfig(max_depth=64), n_classes=3)
    vb = transform(va_c, table)
    return table, full, tr_y, vb, va_y


def test_toot_equals_retrain(setup):
    """For sampled grid points, predict(full_tree, dmax, smin) must equal
    predict(retrained_tree(dmax, smin)) on the validation set."""
    table, full, tr_y, vb, va_y = setup
    for dmax, smin in [(3, 0), (6, 25), (10, 50), (full.max_tree_depth, 2)]:
        p_once = np.asarray(predict_bins(full, vb, table.n_num,
                                         max_depth=dmax,
                                         min_samples_split=max(smin, 2)))
        retrained = build_tree(
            table, tr_y,
            TreeConfig(max_depth=dmax, min_samples_split=max(smin, 2)),
            n_classes=3)
        p_retrain = np.asarray(predict_bins(retrained, vb, table.n_num))
        np.testing.assert_array_equal(p_once, p_retrain)


def test_grid_matches_pointwise_predict(setup):
    table, full, tr_y, vb, va_y = setup
    grid = toot_grid(full, vb, va_y, table.n_num, train_size=len(tr_y))
    # check a handful of random cells against direct Algorithm-7 predicts
    rng = np.random.default_rng(0)
    for _ in range(6):
        i = rng.integers(0, len(grid.dmax))
        j = rng.integers(0, len(grid.smin))
        pred = np.asarray(predict_bins(full, vb, table.n_num,
                                       max_depth=int(grid.dmax[i]),
                                       min_samples_split=int(grid.smin[j])))
        acc = (pred == va_y).mean()
        assert grid.metric[i, j] == pytest.approx(acc, abs=1e-6)


def test_tune_improves_or_matches_full(setup):
    table, full, tr_y, vb, va_y = setup
    res = tune(full, vb, va_y, table.n_num, train_size=len(tr_y))
    full_acc = (np.asarray(predict_bins(full, vb, table.n_num)) == va_y).mean()
    assert res.best_metric >= full_acc - 1e-9
    assert res.n_configs >= 200          # paper: ~200 min_split values alone


def test_prune_stats_shrink(setup):
    table, full, tr_y, vb, va_y = setup
    res = tune(full, vb, va_y, table.n_num, train_size=len(tr_y))
    n_full = full.n_nodes
    n_pruned, d_pruned = prune_stats(full, res.best_dmax, res.best_smin)
    assert n_pruned <= n_full
    assert d_pruned <= full.max_tree_depth


def test_toot_regression_rmse():
    cols, y = make_regression(2000, 6, seed=3)
    (tr_c, tr_y), (va_c, va_y), _ = train_val_test_split(cols, y)
    table = fit_bins(tr_c, max_num_bins=64)
    tree = build_tree(table, tr_y, TreeConfig(max_depth=32, task="regression"))
    vb = transform(va_c, table)
    grid = toot_grid(tree, vb, va_y, table.n_num, train_size=len(tr_y),
                     classification=False)
    best = grid.metric.max()
    # tuned RMSE beats the constant (root mean) predictor
    root_rmse = np.sqrt(((tr_y.mean() - va_y) ** 2).mean())
    assert -best < root_rmse


def test_default_smin_sweep_has_200_values(setup):
    """Paper protocol: min_split swept 0 .. 4% of the train set in steps of
    0.02% — exactly 200 values at the true 0.02% step (an off-by-one made
    it 201 values, i.e. an endpoint-inclusive grid)."""
    table, full, tr_y, vb, va_y = setup
    grid = toot_grid(full, vb, va_y, table.n_num, train_size=len(tr_y))
    assert grid.metric.shape[1] == 200
    np.testing.assert_array_equal(
        grid.smin, np.round(np.arange(200) * (0.0002 * len(tr_y))))


# ---------------------------------------------------------------------------
# PR 8: the 3-axis design space, the ensemble prefix axis, Pareto fronts
# ---------------------------------------------------------------------------

SPACE_3AX = SweepSpace(dmax_values=(3, 8, 64), smin_values=(0, 5, 25, 60),
                       mcw_values=(0.0, 4.0, 20.0))


def test_mcw_stopping_rule_toot_parity(setup):
    """min_child_weight obeys the same Training-Only-Once contract as the
    other axes: the full tree pruned at predict time with mcw equals the
    tree retrained with TreeConfig(min_child_weight=mcw) — which only
    holds because the builder applies mcw AFTER split selection (a
    candidate mask would change which split wins)."""
    table, full, tr_y, vb, va_y = setup
    for mcw in (3.0, 25.0, 100.0):
        p_once = np.asarray(predict_bins(full, vb, table.n_num,
                                         min_child_weight=mcw))
        retrained = build_tree(
            table, tr_y, TreeConfig(max_depth=64, min_child_weight=mcw),
            n_classes=3)
        assert retrained.n_nodes < full.n_nodes
        p_retrain = np.asarray(predict_bins(retrained, vb, table.n_num))
        np.testing.assert_array_equal(p_once, p_retrain)


def test_sweep_matches_retrain_oracle_3axis(setup):
    """Every cell of the (dmax x smin x mcw) sweep is bit-identical to the
    brute-force retrain-per-config oracle, and the dominance-count cost
    model matches the BFS ``prune_stats`` cell-for-cell."""
    table, full, tr_y, vb, va_y = setup
    res = sweep(full, vb, va_y, table.n_num, space=SPACE_3AX,
                train_size=len(tr_y))
    assert res.metric.shape == (3, 4, 3)
    assert res.n_configs == 36
    for i, d in enumerate(SPACE_3AX.dmax_values):
        for j, s in enumerate(SPACE_3AX.smin_values):
            for k, w in enumerate(SPACE_3AX.mcw_values):
                rt = build_tree(
                    table, tr_y,
                    TreeConfig(max_depth=int(d), min_samples_split=int(s),
                               min_child_weight=float(w)), n_classes=3)
                acc = (np.asarray(predict_bins(rt, vb, table.n_num))
                       == va_y).mean()
                assert res.metric[i, j, k] == acc, (d, s, w)
                pn, pd = prune_stats(full, int(d), int(s), float(w))
                assert res.n_nodes[i, j, k] == pn, (d, s, w)


def test_sweep_ensemble_n_rounds_prefix_matches_retrain():
    """The ensemble sweep's n_rounds axis IS retraining: sequential PRNG
    key splitting makes the first r trees of one fit bit-identical to the
    r-round refit, and the sweep's scan accumulates raw scores in fit
    order — so every (r, dmax, smin, mcw) cell equals refitting with
    n_trees=r and serving with the pruning axes as runtime
    hyper-parameters."""
    import jax.numpy as jnp
    cols, y = make_classification(1500, 6, 2, seed=5, n_cat_features=1)
    (tr_c, tr_y), (va_c, va_y), _ = train_val_test_split(cols, y)
    table = fit_bins(tr_c, max_num_bins=32)
    vb = transform(va_c, table)
    lr = 0.3
    mk = lambda r: GradientBoostedTrees(
        n_trees=r, learning_rate=lr,
        config=TreeConfig(max_depth=5, task="regression_variance"),
        loss="logistic", seed=0, goss=GossConfig(0.3, 0.2))
    ens = mk(5).fit(table, tr_y)
    space = SweepSpace(dmax_values=(2, 5), smin_values=(0, 30),
                       mcw_values=(0.0, 4.0), n_rounds_values=(1, 3, 5))
    res = ens.sweep(vb, va_y, space=space, train_size=len(tr_y))
    assert res.metric.shape == (3, 2, 2, 2)
    for ri, r in enumerate(space.n_rounds_values):
        refit = mk(int(r)).fit(table, tr_y)
        for i, d in enumerate(space.dmax_values):
            for j, s in enumerate(space.smin_values):
                for k, w in enumerate(space.mcw_values):
                    raw = jnp.full((len(va_y),), jnp.float32(refit.base))
                    for t in refit.trees:       # fit-order accumulation
                        raw = raw + jnp.float32(lr) * predict_bins(
                            t, vb, table.n_num, max_depth=int(d),
                            min_samples_split=int(s),
                            min_child_weight=float(w), num_steps=5)
                    acc = (np.asarray(raw > 0).astype(int) == va_y).mean()
                    assert res.metric[ri, i, j, k] == acc, (r, d, s, w)
    # cost axes: nodes are prefix sums of per-round pruned counts
    for ri, r in enumerate(space.n_rounds_values):
        for i, d in enumerate(space.dmax_values):
            pn = sum(prune_stats(t, int(d), 0, 0.0)[0]
                     for t in ens.trees[:int(r)])
            assert res.n_nodes[ri, i, 0, 0] == pn


def test_tune_breaks_metric_ties_toward_cheapest(setup):
    """Flat argmax over a TOOT grid is arbitrary w.r.t. cost; the tuned
    cell must carry the SMALLEST pruned node count among all exact-metric
    ties (and still the max metric)."""
    table, full, tr_y, vb, va_y = setup
    res = tune(full, vb, va_y, table.n_num, train_size=len(tr_y))
    grid = res.grid
    best = grid.metric.max()
    assert res.best_metric == best
    ties = np.argwhere(grid.metric == best)
    assert len(ties) >= 2, "fixture regression: grid should have flat ties"
    tie_nodes = [prune_stats(full, int(grid.dmax[i]), int(grid.smin[j]))[0]
                 for i, j in ties]
    assert res.best_nodes == min(tie_nodes)
    assert prune_stats(full, res.best_dmax, res.best_smin)[0] == res.best_nodes


def test_sweep_front_prices_cost_quality(setup):
    """The returned front is non-dominated over (metric up, nodes down,
    bytes down) and covers the whole grid (every cell is weakly dominated
    by some front point)."""
    table, full, tr_y, vb, va_y = setup
    res = sweep(full, vb, va_y, table.n_num, space=SPACE_3AX,
                train_size=len(tr_y))
    pts = [(p.metric, p.n_nodes, p.walk_bytes) for p in res.front]
    for a in pts:
        for b in pts:
            if a is b:
                continue
            assert not (b[0] >= a[0] and b[1] <= a[1] and b[2] <= a[2]
                        and b != a)
    m, n, w = (res.metric.ravel(), res.n_nodes.ravel(),
               res.walk_bytes.ravel())
    for idx in range(m.size):
        assert any(p[0] >= m[idx] and p[1] <= n[idx] and p[2] <= w[idx]
                   for p in pts)
    assert res.best.metric == res.metric.max()


def _gather_grid_oracle(lab, cnt, cmc, y, valid, smin, mcw, dmax,
                        classification):
    """numpy copy of the grid's gather formulation: the stop index per
    (row, smin, mcw) from two prefix counts, then one take along the path
    per dmax (regression in float64)."""
    m, t_len = lab.shape
    idx_s = (cnt[:, :, None] >= smin[None, None, :]).sum(1)
    pass_w = (mcw[None, None, :] <= 0) | (cmc[:, :, None] > mcw[None, None, :])
    stop = np.minimum(idx_s[:, :, None], pass_w.sum(1)[:, None, :])
    out = []
    for d in dmax:
        idx = np.clip(np.minimum(stop, d - 1), 0, t_len - 1)
        pred = np.take_along_axis(lab, idx.reshape(m, -1),
                                  axis=1).reshape(stop.shape)
        yy, vv = y[:, None, None], valid[:, None, None]
        if classification:
            out.append(((pred == yy) & vv).sum(0))
        else:
            out.append(np.where(vv, (pred.astype(np.float64) - yy) ** 2,
                                0.0).sum(0))
    return np.stack(out)


@pytest.mark.parametrize("classification", [True, False],
                         ids=["cls", "reg"])
@pytest.mark.parametrize("t_len", [1, 2, 7])
def test_grid_counts_match_gather_oracle(t_len, classification):
    """The prefix-sum grid equals the gather formulation on random
    monotone path tables: rows switched off, dmax with 0, values past T,
    duplicates and out of order, smin out of order with duplicates and the
    sharded pad's Int32.max sentinel, mcw with 0, a negative value and
    several values.  Classification counts are exact; regression sums
    hold to 1e-5 of a float64 oracle."""
    import jax.numpy as jnp
    from repro.core.tuning import _grid_counts
    rng = np.random.default_rng(17 + t_len)
    m = 500
    cnt = -np.sort(-rng.integers(1, 120, size=(m, t_len)), axis=1)
    cmc = rng.integers(0, 30, size=(m, t_len)).astype(np.float32)
    cmc[rng.random((m, t_len)) < 0.2] = np.inf
    cmc = np.minimum.accumulate(cmc, axis=1)
    if classification:
        lab = rng.integers(0, 3, size=(m, t_len)).astype(np.float32)
        y = rng.integers(0, 3, size=m).astype(np.float32)
    else:
        lab = rng.normal(0, 10, size=(m, t_len)).astype(np.float32)
        y = rng.normal(0, 10, size=m).astype(np.float32)
    valid = rng.random(m) > 0.15
    smin = np.array([40, 0, 7, 40, 119, 2, np.iinfo(np.int32).max, 65],
                    dtype=np.int32)
    mcw = np.array([0.0, 5.0, -1.0, 20.0, 5.0], dtype=np.float32)
    dmax = np.array([3, 0, 9, 1, 3, t_len + 4, 2, t_len], dtype=np.int32)
    got = np.asarray(_grid_counts(
        *map(jnp.asarray, (lab, cnt.astype(np.int32), cmc, y, valid, smin,
                           mcw, dmax)), classification=classification))
    want = _gather_grid_oracle(lab, cnt, cmc, y, valid, smin, mcw, dmax,
                               classification)
    assert got.shape == (len(dmax), len(smin), len(mcw))
    if classification:
        assert got.dtype == np.int32
        np.testing.assert_array_equal(got, want)
    else:
        np.testing.assert_allclose(got, want, rtol=1e-5)


def test_pareto_front_property_non_dominated():
    """hypothesis: for arbitrary (metric, nodes, bytes) grids the front is
    mutually non-dominated AND every input point is weakly dominated by a
    front point."""
    pytest.importorskip("hypothesis")  # CI installs it; degrade locally
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=60, deadline=None)
    @given(st.lists(
        st.tuples(st.integers(0, 8), st.integers(1, 9), st.integers(1, 9)),
        min_size=1, max_size=40))
    def check(points):
        m = np.array([p[0] for p in points], dtype=np.float64)
        n = np.array([p[1] for p in points], dtype=np.int64)
        b = np.array([p[2] for p in points], dtype=np.int64)
        configs = [{"i": k} for k in range(len(points))]
        front = pareto_front(m, n, b, configs)
        assert front
        trip = [(f.metric, f.n_nodes, f.walk_bytes) for f in front]
        assert len(set(trip)) == len(trip)
        for a in trip:
            assert not any(
                x != a and x[0] >= a[0] and x[1] <= a[1] and x[2] <= a[2]
                for x in trip)
        for k in range(len(points)):
            assert any(t[0] >= m[k] and t[1] <= n[k] and t[2] <= b[k]
                       for t in trip)

    check()


SHARD_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax
from jax.sharding import Mesh

from repro.core import (fit_bins, transform, build_tree, TreeConfig, sweep,
                        SweepSpace)
from repro.core.distributed import DistConfig
from repro.data import make_classification, train_val_test_split

assert len(jax.devices()) == 8

cols, y = make_classification(1100, 6, 3, seed=2, n_cat_features=1)
(tr_c, tr_y), (va_c, va_y), _ = train_val_test_split(cols, y)
table = fit_bins(tr_c, max_num_bins=32)
full = build_tree(table, tr_y, TreeConfig(max_depth=64), n_classes=3)
vb = transform(va_c, table)

# smin count NOT divisible by the model-axis size, M not divisible by the
# data-axis size: both paddings (sentinel smin, masked rows) are exercised
space = SweepSpace(dmax_values=(3, 8, 64),
                   smin_values=(0, 3, 7, 11, 25, 50, 75),
                   mcw_values=(0.0, 5.0))
local = sweep(full, vb, va_y, table.n_num, space=space, train_size=len(tr_y))
mesh = Mesh(np.asarray(jax.devices()).reshape(4, 2), ("data", "model"))
shard = sweep(full, vb, va_y, table.n_num, space=space, train_size=len(tr_y),
              mesh=mesh, dist=DistConfig())
np.testing.assert_array_equal(local.metric, shard.metric)
np.testing.assert_array_equal(local.n_nodes, shard.n_nodes)
np.testing.assert_array_equal(local.walk_bytes, shard.walk_bytes)
assert local.front == shard.front
assert local.best == shard.best
print("SHARD_SWEEP_OK")
"""


@pytest.mark.slow
def test_sweep_sharded_grid_parity_forced_8dev():
    """The mesh-sharded grid (rows over the data axes, smin slices over
    the model axis, one int32 psum) is bit-identical to the single-device
    sweep — integer correct-prediction counts make the psum
    order-independent.  Runs in a subprocess so the 8 placeholder CPU
    devices never leak into other tests."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", SHARD_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=1200)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    assert "SHARD_SWEEP_OK" in r.stdout

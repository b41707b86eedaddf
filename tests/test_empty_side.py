"""A candidate split's side that holds no row reads exactly 0, so
``min_samples_leaf`` masks it.

Each side of a candidate is summed over the bins that go to it
(``core/split.py``, the Pallas ``split_scan``), never formed as the node
total minus the other side, and a weighted ``regression_variance`` level
step clears the bins of a derived sibling (parent - smaller child) whose
row count is 0.  In float32 either difference leaves a rounding residue on
an empty side, which passes ``min_samples_leaf`` and, under a Newton
target, outscores every real split.  The checks are the reference's own
(``bench/reference.py``)."""
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import TreeConfig, build_tree, fit_bins
from repro.core.split import _candidate_stats, best_splits, best_splits_kernel
from repro.kernels.split_scan import split_scan_pallas

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import reference as ref  # noqa: E402


def newton_hist(seed, s=32, k=4, b=256):
    """float32 Newton histograms (sum h, sum h*z, sum h*z^2) of ``s`` nodes
    whose hessian sums run from 1e6 to 1e7 (a HIGGS root, 10.5M rows at
    h <= 0.25, holds 2.6e6), over 255 numeric bins and an empty missing
    bin, and the bin where the odd nodes' target steps.  The even nodes'
    Newton target is nearly constant, so no real split gains much there:
    a residue side with a target unlike its node's would win.  The odd
    nodes' target steps at one bin of one feature, a clear best split."""
    rng = np.random.default_rng(seed)
    out = np.zeros((s, k, b, 3), np.float32)
    steps = {}
    for i in range(s):
        hsum = 10 ** rng.uniform(6, 7)
        if i % 2:
            steps[i] = (int(rng.integers(k)), int(rng.integers(20, 235)))
        for f in range(k):
            w = rng.gamma(0.7, size=b - 1)
            h = (w / w.sum() * hsum).astype(np.float32)
            z = 4.0 + rng.normal(0, 1e-4, b - 1)
            if steps.get(i, (None,))[0] == f:
                z[steps[i][1] + 1:] += 0.5
            out[i, f, :b - 1] = np.stack([h, h * z, h * z * z], 1)
    return out, steps


def side_weights(hist, feat, op, tbin, n_num):
    """float64 hessian sums of the chosen split's two sides."""
    h = hist[feat, :, 0].astype(np.float64)
    ids = np.arange(h.shape[0])
    numeric = ids < n_num[feat]
    go = np.where(op == 0, numeric & (ids <= tbin),
                  np.where(op == 1, numeric & (ids > tbin), ids == tbin))
    return h[go].sum(), h[~go].sum()


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_newton_histograms_choose_no_empty_side(seed):
    hist, steps = newton_hist(seed)
    s, k = hist.shape[:2]
    n_num = np.full(k, 255, np.int32)
    n_cat = np.zeros(k, np.int32)
    args = (jnp.asarray(hist), jnp.asarray(n_num), jnp.asarray(n_cat))
    d = best_splits(*args, heuristic="sse", min_leaf=1)
    score, tbin, op = split_scan_pallas(*args, heuristic="sse", min_leaf=1,
                                        interpret=True)
    score, tbin, op = map(np.asarray, (score, tbin, op))
    for i in range(s):
        f = int(d.feat[i])
        sides = side_weights(hist[i], f, int(d.op[i]), int(d.bin[i]), n_num)
        assert min(sides) >= 1, (i, sides)
        assert float(d.pos_stats[i, 0]) > 0 and float(d.neg_stats[i, 0]) > 0
        fp = int(np.argmax(score[i]))
        assert min(side_weights(hist[i], fp, int(op[i, fp]),
                                int(tbin[i, fp]), n_num)) >= 1, i
        assert score[i, fp] == pytest.approx(float(d.score[i]), rel=1e-6)
        if i in steps:
            # both backends take the step, "<=" first among equal scores
            assert (f, int(d.op[i]), int(d.bin[i])) == (
                fp, int(op[i, fp]), int(tbin[i, fp])) == (*steps[i][:1], 0,
                                                           steps[i][1]), i


def one_feature(masses, n_num, n_cat):
    """[1, 1, B, 3] moment histogram of one node and one feature with
    hessian mass ``masses`` per bin and a Newton target of 4 up to bin 30
    and 4.5 past it."""
    h = np.asarray(masses, np.float64)
    z = 4 + 0.5 * (np.arange(len(h)) > 30)
    hist = np.stack([h, h * z, h * z * z], -1)[None, None].astype(np.float32)
    return (jnp.asarray(hist), jnp.asarray([n_num], jnp.int32),
            jnp.asarray([n_cat], jnp.int32))


def numeric_masses(seed=37):
    """64 numeric bins, 2..59 holding 1e4 to 3e6 each (3.1e7 in all): a
    node total minus its prefix leaves a float32 residue of 2 here."""
    m = np.zeros(64, np.float32)
    m[2:60] = 10 ** np.random.default_rng(seed).uniform(4, 6.5, 58)
    return m


# each family's candidates whose negative side holds no row: "<=" at and
# past the last populated numeric bin, ">" below the first populated one,
# "=" at the one populated category (no numeric or missing mass)
FAMILIES = {
    "le": (numeric_masses(), 64, 0, 0, [59, 60, 63]),
    "gt": (numeric_masses(), 64, 0, 1, [0, 1]),
    "eq": ([0, 0, 0, 0, 0, 7654321.7, 0, 0], 4, 3, 2, [5]),
}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_an_empty_side_reads_exactly_zero(family):
    masses, n_num, n_cat, o, empty_at = FAMILIES[family]
    hist, nn, nc = one_feature(masses, n_num, n_cat)
    pos, neg, valid = _candidate_stats(hist, nn, nc)
    pos, neg, valid = (np.asarray(a) for a in (pos, neg, valid))
    total = np.asarray(hist)[0, 0].astype(np.float64).sum(0)
    for b in empty_at:
        assert valid[o, 0, b]
        assert np.array_equal(neg[o, 0, 0, b], np.zeros(3, np.float32)), b
        np.testing.assert_allclose(pos[o, 0, 0, b], total, rtol=1e-6)
    # neither backend chooses a split with an empty side
    d = best_splits(hist, nn, nc, heuristic="sse", min_leaf=1)
    k = best_splits_kernel(hist, nn, nc, heuristic="sse", min_leaf=1)
    for dec in (d, k):
        if float(dec.score[0]) > -1e38:
            sides = side_weights(np.asarray(hist)[0], 0, int(dec.op[0]),
                                 int(dec.bin[0]), np.asarray(nn))
            assert min(sides) >= 1, sides
    assert (int(d.feat[0]), int(d.op[0]), int(d.bin[0])) == (
        int(k.feat[0]), int(k.op[0]), int(k.bin[0]))
    assert float(d.score[0]) == pytest.approx(float(k.score[0]), rel=1e-6)


def subtracted_stats(hist, n_num):
    """Each candidate's negative side as the node total minus its
    positive side, and ``>`` as the numeric total minus the prefix."""
    hist = np.asarray(hist)
    b = hist.shape[2]
    ids = np.arange(b)
    is_num = ids[None, :] < n_num[:, None]
    tot = hist.sum(axis=2, keepdims=True, dtype=np.float32)
    prefix = np.cumsum(hist * is_num[None, :, :, None], axis=2,
                       dtype=np.float32)
    pos = np.stack([prefix, prefix[:, :, -1:] - prefix, hist])
    return pos, tot[None] - pos


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_integer_counts_give_the_subtracted_form_bit_for_bit(seed):
    """Class counts below 2**24 are exact in float32, so summing a side
    over its bins and subtracting give the same floats and the same
    decisions, in both backends (the classification trees stay as they
    were)."""
    rng = np.random.default_rng(seed)
    s, k, b, c = 8, 6, 33, 5
    n_num = rng.integers(1, b - 4, k).astype(np.int32)
    n_cat = rng.integers(0, 4, k).astype(np.int32)
    counts = rng.integers(0, 40_000, (s, k, b, c))
    counts *= rng.random((s, k, b, 1)) < 0.6         # empty bins
    hist = counts.astype(np.float32)
    pos, neg, _ = _candidate_stats(jnp.asarray(hist), jnp.asarray(n_num),
                                   jnp.asarray(n_cat))
    pos_s, neg_s = subtracted_stats(hist, n_num)
    assert np.array_equal(np.asarray(pos), pos_s)
    assert np.array_equal(np.asarray(neg), neg_s)
    args = (jnp.asarray(hist), jnp.asarray(n_num), jnp.asarray(n_cat))
    for heuristic in ("info_gain", "gini"):
        d = best_splits(*args, heuristic=heuristic, min_leaf=3)
        kd = best_splits_kernel(*args, heuristic=heuristic, min_leaf=3)
        for f in ("feat", "op", "bin"):
            np.testing.assert_array_equal(np.asarray(getattr(d, f)),
                                          np.asarray(getattr(kd, f)))


# (backend, rows, hessian weight scale, seed): in each, a side formed as a
# difference (a node total minus a side, or a bin of a sibling derived as
# its parent's histogram minus the smaller child's) held only a rounding
# residue and was chosen
BUILDS = [("segment", 20_000, 100.0, 1), ("segment", 20_000, 100.0, 5),
          ("pallas", 100_000, 1000.0, 0), ("pallas", 100_000, 1000.0, 2)]


@pytest.mark.parametrize("backend,m,scale,seed", BUILDS,
                         ids=[f"{b}-{m}-{s}" for b, m, _, s in BUILDS])
def test_a_newton_tree_passes_the_check_node_by_node(backend, m, scale,
                                                     seed):
    """One boosting round's tree: hessian weights and a Newton target,
    grown with sibling subtraction, checked node by node against the
    float64 reference: every split valid, every leaf stopped by a rule
    or with no valid split."""
    rng = np.random.default_rng(seed)
    x = np.stack([rng.integers(0, 3, m), rng.integers(0, 2, m),
                  rng.normal(size=m), rng.normal(size=m)], 1).astype(float)
    z = (4 + 0.1 * rng.normal(size=m)).astype(np.float32)
    w = (scale * rng.uniform(0.5, 1.5, m)).astype(np.float32)
    table = fit_bins([x[:, j] for j in range(4)], max_num_bins=63)
    cfg = TreeConfig(max_depth=6, task="regression_variance",
                     hist_backend=backend,
                     select_backend="jnp" if backend == "segment"
                     else "pallas")
    tree = ref.host_tree(build_tree(table, z, cfg, sample_weight=w))
    stats = np.stack([w, w * z, w * z * z], 1).astype(np.float64)
    chk = ref.check_tree(tree, ref.Rows(table.bins, int(table.n_bins)),
                         stats, np.asarray(table.n_num),
                         np.asarray(table.n_cat),
                         ref.Rules("newton", max_depth=6))
    assert chk.nodes_off == 0, chk.notes
    assert (tree["left"] >= 0).sum() > 10
    assert ref.scaled_max(chk.value_gaps) < 1e-4

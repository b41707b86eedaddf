"""Training-Only-Once Tuning (paper section 3) as a design-space engine.

Train ONE full model; then price the entire hyper-parameter design space
against the validation set without retraining.  The trick: record each
validation example's root->leaf path once.  Along a path

  * node counts are non-increasing, so for any ``min_samples_split`` the
    stopping index is a prefix count (``sum(count >= smin)``);
  * the running minimum of each node's lighter-child count is
    non-increasing (a cumulative min restores monotonicity the raw
    per-node statistic lacks), so ``min_child_weight`` is a SECOND prefix
    cutoff — exact because the builder applies min_child_weight as a
    post-selection stopping rule, never a candidate mask (TreeConfig);
  * ``max_depth`` is a clamp.

So a walk leaves path position t exactly when both gates hold at t, and
a single tree's metric at every depth is a prefix sum over t of masked
row sums: ``sweep`` prices the whole ``(max_depth x min_samples_split x
min_child_weight)`` grid on device in O(M T) elementwise work per
(smin, mcw) cell, with no gather over examples.  For
``GradientBoostedTrees`` it adds ``n_rounds`` as a prefix sum over
per-round path tables (round r's trees never depend on predict-time
pruning, and the fit's PRNG key splits sequentially per round, so the
first r trees of one fit ARE the retrained r-round ensemble).

Cost joins quality as a first-class objective: each cell's pruned node
count and predicted serve bytes (``serve.pack.walk_bytes_per_request``
at the pruned depth) come from a host-side dominance count over per-node
reachability thresholds, and ``SweepResult.front`` is the non-dominated
cost/quality Pareto set.

The paper's protocol (section 4): max_depth swept 1..full tree depth;
min_split swept 0..4% of the training set in steps of 0.02% (200 values).

Exactness contract (what the toot-gate blocks on): classification metrics
are computed as int32 correct-prediction counts on device and divided
host-side in float64, so a sweep cell is bit-identical to retraining with
that cell's hyper-parameters and measuring accuracy — single-device and
mesh-sharded (integer psums are order-independent).  Regression cells sum
squared error in f32 and are compared to tolerance instead.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.predict import WALK_FIELDS, _paths, stack_trees
from repro.core.tree import Tree

__all__ = ["ToolGrid", "toot_grid", "tune", "prune_stats", "TuneResult",
           "SweepSpace", "SweepResult", "ParetoPoint", "sweep",
           "path_tables", "pareto_front", "default_smin_values"]


class ToolGrid(NamedTuple):
    dmax: np.ndarray      # [Nd]
    smin: np.ndarray      # [Ns]
    metric: np.ndarray    # [Nd, Ns] accuracy (cls) or -RMSE (reg): higher=better


@dataclasses.dataclass
class TuneResult:
    best_dmax: int
    best_smin: int
    best_metric: float
    grid: ToolGrid
    n_configs: int
    # pruned node count of the winning config (fields with defaults append
    # at the end: positional construction predates them)
    best_nodes: int = -1


class ParetoPoint(NamedTuple):
    metric: float        # higher is better (accuracy / -RMSE)
    n_nodes: int         # pruned node count (summed over rounds)
    walk_bytes: int      # predicted serve.pack.walk_bytes_per_request
    config: dict         # the hyper-parameters that price to this point


@dataclasses.dataclass(frozen=True)
class SweepSpace:
    """The design space ``sweep`` prices.  ``None`` axes resolve to the
    paper protocol: max_depth 1..full depth, min_samples_split the
    200-value 0..4% ramp, min_child_weight disabled (a single 0.0), and —
    ensembles — n_rounds 1..n_trees."""
    dmax_values: tuple | None = None
    smin_values: tuple | None = None
    mcw_values: tuple = (0.0,)
    n_rounds_values: tuple | None = None   # ensembles only


@dataclasses.dataclass
class SweepResult:
    dmax: np.ndarray            # [Nd]
    smin: np.ndarray            # [Ns]
    mcw: np.ndarray             # [Nw]
    n_rounds: np.ndarray | None  # [R] (None for single trees)
    metric: np.ndarray          # [Nd,Ns,Nw] or [R,Nd,Ns,Nw]; higher=better
    n_nodes: np.ndarray         # same shape, pruned node count per cell
    walk_bytes: np.ndarray      # same shape, predicted serve bytes/request
    front: list                 # non-dominated ParetoPoint, metric-desc
    best: ParetoPoint           # max metric; ties -> cheapest (see tune)
    n_configs: int


def default_smin_values(train_size: int) -> np.ndarray:
    """Paper protocol: min_split 0 .. 4% of the train set in steps of
    0.02% — exactly 200 values at the true step (0, 0.02%, ..., 3.98%;
    the 4% endpoint is the 201st grid line and is excluded)."""
    return np.round(np.arange(200) * (0.0002 * train_size)).astype(np.int32)


# ---------------------------------------------------------------------------
# path tables: one root->leaf walk per example, three [M, T] tables
# ---------------------------------------------------------------------------

def _node_child_min(arrays):
    """Per node: the lighter child's recorded count (f32; +inf on leaves).

    This is the statistic the builder's min_child_weight stopping rule and
    the predict walk's runtime gate both compare — ``Tree.count`` holds the
    rounded weight sum, so all three sides compare identical values."""
    left, right = arrays["left"], arrays["right"]
    internal = (~arrays["leaf"]) & (left >= 0)
    cnt = arrays["count"]
    mc = jnp.minimum(cnt[jnp.maximum(left, 0)],
                     cnt[jnp.maximum(right, 0)]).astype(jnp.float32)
    return jnp.where(internal, mc, jnp.inf)


def path_tables(tree: Tree, val_bins, n_num, *, num_steps: int | None = None):
    """Record each validation example's path once: ``(lab, cnt, cmc)``
    [M, T] device tables (stay-at-leaf past the leaf).

    ``lab``/``cnt`` are the path nodes' labels and counts; ``cmc`` is the
    running minimum of the lighter-child count along the path — the
    cumulative min is what makes the min_child_weight axis a prefix
    cutoff (the raw per-node statistic is not monotone along a path)."""
    arrays = tree._asdict()
    arrays = {k: jnp.asarray(arrays[k]) for k in WALK_FIELDS}
    steps = num_steps if num_steps is not None else max(1, tree.max_tree_depth)
    nodes = _paths(arrays, jnp.asarray(val_bins), jnp.asarray(n_num),
                   num_steps=max(1, steps))                      # [M, T]
    lab = arrays["label"][nodes]
    cnt = arrays["count"][nodes]
    cmc = jax.lax.cummin(_node_child_min(arrays)[nodes], axis=1)
    return lab, cnt, cmc


# ---------------------------------------------------------------------------
# the grid kernel (shared body: local jit AND the shard_map'd mesh twin in
# core.distributed.make_sharded_grid_counts wrap exactly this function)
# ---------------------------------------------------------------------------

def _stop_indices(cnt, cmc, smin, mcw):
    """First-failing path index per (example, smin) and (example, mcw).

    Each gate fails monotonically along a path (counts and cmc are
    non-increasing), so the first failure is a prefix count and the walk's
    stopping index for a cell is the min over gates."""
    idx_s = (cnt[:, :, None] >= smin[None, None, :]).sum(1).astype(jnp.int32)
    # mcw <= 0 disables the gate entirely — same rule as the predict walk
    pass_w = (mcw[None, None, :] <= 0) | (cmc[:, :, None] > mcw[None, None, :])
    idx_w = pass_w.sum(1).astype(jnp.int32)
    return idx_s, idx_w                                  # [M,Ns], [M,Nw]


def _grid_counts_body(lab, cnt, cmc, y, valid, smin, mcw, dmax, *,
                      classification: bool = True):
    """[Nd, Ns, Nw] per-cell totals: int32 correct-prediction counts
    (classification — summation-order independent, so the sharded psum is
    bit-exact) or f32 SSE sums (regression).

    A row's walk under cell (s, w) stops at path index ``stop`` and under
    ``dmax`` d answers at ``k = clip(min(stop, d - 1), 0, T - 1)``.  Both
    gates fail monotonically along a path, so the walk leaves position t
    exactly when the gates hold at t, and the total at every k is one
    prefix sum over t of two masked row sums:

        E[t] = sum_m [stop == t] v[m, t]    rows that answer at t for k > t
        F[t] = sum_m [stop >= t] v[m, t]    rows clamped at t when k == t
        total[k] = E[0] + ... + E[k-1] + F[k]

    with ``v`` the row's correct-prediction indicator or squared error —
    non-negative terms, so the f32 sums do not cancel.  No gather over
    rows; the peak intermediate is the [M, T, Ns, Nw] gate mask, and the
    depth axis reads the [T, Ns, Nw] totals."""
    m, t_len = lab.shape
    ns, nw = smin.shape[0], mcw.shape[0]
    # descends[m, t, s, w]: both gates hold at position t, stop > t (mcw
    # <= 0 disables its gate entirely — same rule as the predict walk)
    descends = ((cnt[:, :-1, None, None] >= smin[None, None, :, None])
                & ((mcw <= 0) | (cmc[:, :-1, None, None] > mcw)))
    reach = jnp.concatenate([jnp.ones((m, 1, ns, nw), bool), descends],
                            axis=1)                     # [M,T,Ns,Nw] stop>=t
    if classification:
        v = ((lab == y[:, None]) & valid[:, None]).astype(jnp.int32)
    else:
        v = jnp.where(valid[:, None], (lab - y[:, None]) ** 2, 0.0)
    v = v[:, :, None, None]
    clamped = jnp.where(reach, v, 0).sum(axis=0)                # F [T,Ns,Nw]
    stopped = jnp.where(reach[:, :-1] & ~descends, v[:, :-1], 0).sum(axis=0)
    before = jnp.cumsum(stopped, axis=0)                        # [T-1,Ns,Nw]
    totals = clamped + jnp.concatenate([jnp.zeros_like(clamped[:1]), before])
    return totals[jnp.clip(dmax - 1, 0, t_len - 1)]             # [Nd,Ns,Nw]


_grid_counts = functools.partial(
    jax.jit, static_argnames=("classification",))(_grid_counts_body)


@functools.partial(jax.jit, static_argnames=("logistic",))
def _ensemble_grid_counts(labs, cnts, cmcs, y, valid, smin, mcw, dmax,
                          lr, base, *, logistic: bool = True):
    """[R, Nd, Ns, Nw] per-prefix totals for a boosted ensemble.

    A ``lax.scan`` over rounds carries the accumulated raw scores for
    EVERY (dmax, smin, mcw) cell and emits the totals after each round —
    the n_rounds axis is a prefix sum over round contributions.  The
    carry update ``raw + lr * contrib`` is element-wise f32 in fit order,
    so prefix r's raw scores are bit-identical to sequentially
    accumulating the retrained r-round ensemble's per-tree predictions."""
    r, m, t_len = labs.shape
    nd, ns, nw = dmax.shape[0], smin.shape[0], mcw.shape[0]

    def contrib(lab, cnt, cmc):
        idx_s, idx_w = _stop_indices(cnt, cmc, smin, mcw)
        stop = jnp.minimum(idx_s[:, :, None], idx_w[:, None, :])

        def per_dmax(d):
            idx = jnp.clip(jnp.minimum(stop, d - 1), 0, t_len - 1)
            return jnp.take_along_axis(lab, idx.reshape(m, ns * nw), axis=1)

        return jax.lax.map(per_dmax, dmax)                # [Nd, M, Ns*Nw]

    def round_step(raw, xs):
        lab, cnt, cmc = xs
        raw = raw + lr * contrib(lab, cnt, cmc)
        if logistic:
            ok = ((raw > 0) == (y[None, :, None] > 0.5)) \
                & valid[None, :, None]
            out = ok.sum(axis=1).astype(jnp.int32)        # [Nd, Ns*Nw]
        else:
            err = jnp.where(valid[None, :, None],
                            (raw - y[None, :, None]) ** 2, 0.0)
            out = err.sum(axis=1)
        return raw, out

    raw0 = jnp.full((nd, m, ns * nw), base, dtype=jnp.float32)
    _, outs = jax.lax.scan(round_step, raw0, (labs, cnts, cmcs))
    return outs.reshape(r, nd, ns, nw)


# ---------------------------------------------------------------------------
# the cost model: pruned node count / depth per cell, host-side
# ---------------------------------------------------------------------------

def _node_thresholds(tree: Tree):
    """Per-node reachability thresholds (host numpy).

    Node u is visited by the pruned walk under ``(dmax, smin, mcw)`` iff
    every STRICT ancestor descends, i.e.

        depth[u] <= dmax  and  pcount[u] >= smin  and  mcw < pmc[u]

    where ``pcount`` is the parent's count (counts are non-increasing
    along a path, so the parent carries the ancestor minimum; +inf at the
    root) and ``pmc`` the min over strict ancestors of the
    lighter-child count (+inf at the root).  Parents precede children in
    node-id order (level-synchronous allocation), so one forward pass
    computes both.  Semantics match ``prune_stats``' BFS exactly."""
    n = tree.n_nodes
    depth = np.asarray(tree.depth)[:n].astype(np.int64)
    count = np.asarray(tree.count)[:n].astype(np.float64)
    left = np.asarray(tree.left)[:n]
    right = np.asarray(tree.right)[:n]
    leaf = np.asarray(tree.leaf)[:n]
    parent = np.asarray(tree.parent)[:n]
    internal = (~leaf) & (left >= 0)
    mc = np.full(n, np.inf)
    mc[internal] = np.minimum(count[left[internal]], count[right[internal]])
    pcount = np.full(n, np.inf)
    pmc = np.full(n, np.inf)
    for u in range(1, n):
        p = parent[u]
        pcount[u] = count[p]
        pmc[u] = min(pmc[p], mc[p])
    return depth, pcount, pmc


def _cost_grids(tree: Tree, dmax_values, smin_values, mcw_values):
    """Pruned ``(node count, max depth)`` for EVERY grid cell at once.

    Each node contributes to the axis-aligned box of cells that reach it
    (its thresholds are per-axis, independent), so the whole grid is a 3D
    dominance count: bucket each node at its threshold indices, then
    running-sum (count) / running-max (depth) along each axis —
    O(n_nodes + grid) instead of a BFS per cell.  Grids may repeat values
    in any order (the paper's smin ramp rounds to duplicates); internal
    computation uses the unique-sorted axes and scatters back."""
    depth, pcount, pmc = _node_thresholds(tree)
    ds, d_inv = np.unique(np.asarray(dmax_values), return_inverse=True)
    ss, s_inv = np.unique(np.asarray(smin_values), return_inverse=True)
    ws, w_inv = np.unique(np.asarray(mcw_values, dtype=np.float64),
                          return_inverse=True)
    nd, ns, nw = len(ds), len(ss), len(ws)
    # the walk's mcw gate passes when mcw <= 0 regardless of pmc; pmc > 0
    # always in practice (counts are floored by min_samples_leaf), but
    # mirror the rule exactly by clamping pmc just above zero.
    pmc = np.where(pmc > 0, pmc, np.nextafter(0, 1))
    di = np.searchsorted(ds, depth, side="left")         # first dmax >= depth
    si = np.searchsorted(ss, pcount, side="right") - 1   # last smin <= pcount
    wi = np.searchsorted(ws, pmc, side="left") - 1       # last mcw < pmc
    keep = (di < nd) & (si >= 0) & (wi >= 0)
    di, si, wi, dep = di[keep], si[keep], wi[keep], depth[keep]

    g = np.zeros((nd, ns, nw), dtype=np.int64)
    np.add.at(g, (di, si, wi), 1)
    g = np.cumsum(g, axis=0)
    g = np.flip(np.cumsum(np.flip(g, 1), axis=1), 1)
    g = np.flip(np.cumsum(np.flip(g, 2), axis=2), 2)

    h = np.zeros((nd, ns, nw), dtype=np.int64)
    np.maximum.at(h, (di, si, wi), dep)
    h = np.maximum.accumulate(h, axis=0)
    h = np.flip(np.maximum.accumulate(np.flip(h, 1), axis=1), 1)
    h = np.flip(np.maximum.accumulate(np.flip(h, 2), axis=2), 2)

    sel = np.ix_(d_inv, s_inv, w_inv)
    return g[sel], h[sel]


def _predicted_record_bytes(trees) -> int:
    """Per-ensemble packed record width predicted from the models' actual
    field ranges — the same per-field int8->int16->int32 overflow rule
    ``serve.pack.pack_stacked`` applies at pack time."""
    from repro.serve.pack import predict_record_bytes
    n_feat = max(int(np.asarray(t.feat)[:t.n_nodes].max()) + 1
                 for t in trees)
    n_bins = max(int(np.asarray(t.tbin)[:t.n_nodes].max()) + 1
                 for t in trees)
    max_loff = 0
    for t in trees:
        left = np.asarray(t.left)[:t.n_nodes]
        node = np.arange(t.n_nodes)
        split = left >= 0
        if split.any():
            max_loff = max(max_loff, int((left[split] - node[split]).max()))
    return predict_record_bytes(n_feat=max(1, n_feat),
                                n_bins=max(1, n_bins), max_loff=max_loff)


# ---------------------------------------------------------------------------
# Pareto front
# ---------------------------------------------------------------------------

def pareto_front(metric, n_nodes, walk_bytes, configs) -> list:
    """Non-dominated set over (maximize metric, minimize n_nodes, minimize
    walk_bytes), metric-descending.

    ``configs`` is a sequence (same flat order as the raveled grids) of
    config dicts.  Exact duplicate (metric, nodes, bytes) triples keep
    the first config in grid order.  Sort by metric descending, then
    sweep a (nodes, bytes) staircase: a point is dominated iff an
    already-accepted point (whose metric is >= by sort order) has both
    nodes <= and bytes <= — O(n log n)."""
    import bisect
    m = np.asarray(metric, dtype=np.float64).ravel()
    n = np.asarray(n_nodes, dtype=np.int64).ravel()
    b = np.asarray(walk_bytes, dtype=np.int64).ravel()
    order = np.lexsort((np.arange(m.size), b, n, -m))
    front: list[ParetoPoint] = []
    stair_n: list[int] = []      # accepted nodes, ascending
    stair_b: list[int] = []      # min bytes among accepted with nodes <= n
    seen = set()
    for i in order:
        key = (m[i], int(n[i]), int(b[i]))
        if key in seen:
            continue
        j = bisect.bisect_right(stair_n, int(n[i]))
        if j > 0 and stair_b[j - 1] <= int(b[i]):
            continue                                     # dominated
        seen.add(key)
        front.append(ParetoPoint(float(m[i]), int(n[i]), int(b[i]),
                                 dict(configs[i])))
        j = bisect.bisect_left(stair_n, int(n[i]))
        stair_n.insert(j, int(n[i]))
        prev = stair_b[j - 1] if j > 0 else np.iinfo(np.int64).max
        stair_b.insert(j, min(prev, int(b[i])))
        for k in range(j + 1, len(stair_b)):
            stair_b[k] = min(stair_b[k], stair_b[k - 1])
    return front


def _best_cell(metric, n_nodes, walk_bytes):
    """Flat index of the best cell: max metric, ties broken toward the
    cheapest config (smallest pruned node count, then fewest predicted
    serve bytes, then FIRST in grid order — np.argmin's tie rule)."""
    m = np.asarray(metric)
    tie = m == m.max()
    big = np.iinfo(np.int64).max
    cost_n = np.where(tie, np.asarray(n_nodes, dtype=np.int64), big)
    cost_n_min = cost_n.min()
    cost_b = np.where(cost_n == cost_n_min,
                      np.asarray(walk_bytes, dtype=np.int64), big)
    return int(np.argmin(cost_b.ravel()))


# ---------------------------------------------------------------------------
# sweep: the public design-space API
# ---------------------------------------------------------------------------

def _resolve_axes(space: SweepSpace, full_depth: int, train_size: int):
    dv = (np.arange(1, full_depth + 1, dtype=np.int32)
          if space.dmax_values is None
          else np.asarray(space.dmax_values, dtype=np.int32))
    sv = (default_smin_values(train_size) if space.smin_values is None
          else np.asarray(space.smin_values, dtype=np.int32))
    wv = np.asarray(space.mcw_values, dtype=np.float32)
    if dv.size == 0 or sv.size == 0 or wv.size == 0:
        raise ValueError("every SweepSpace axis needs at least one value")
    return dv, sv, wv


def _metric_grid_tree(tree, val_bins, y_val, n_num, dv, sv, wv,
                      classification, mesh, dist):
    lab, cnt, cmc = path_tables(tree, val_bins, n_num)
    m = lab.shape[0]
    yv = jnp.asarray(np.asarray(y_val), dtype=jnp.float32)
    if mesh is None:
        totals = _grid_counts(lab, cnt, cmc, yv, jnp.ones((m,), bool),
                              jnp.asarray(sv), jnp.asarray(wv),
                              jnp.asarray(dv), classification=classification)
    else:
        from repro.core import distributed as dist_mod
        dist = dist_mod.DistConfig() if dist is None else dist
        totals = dist_mod.sharded_grid_counts(
            mesh, dist, lab, cnt, cmc, yv, sv, wv, dv,
            classification=classification)
    totals = np.asarray(totals)
    if classification:
        return totals.astype(np.float64) / m
    return -np.sqrt(totals.astype(np.float64) / m)


class _CellConfigs:
    """Lazy flat-index -> config-dict view over the grid axes (a design
    space has up to hundreds of thousands of cells; only the front's few
    survivors ever materialise their dict)."""

    def __init__(self, names, values, shape):
        self.names = names
        self.values = [np.asarray(v) for v in values]
        self.shape = shape

    def __getitem__(self, flat):
        idx = np.unravel_index(int(flat), self.shape)
        return {n: v[i].item()
                for n, v, i in zip(self.names, self.values, idx)}


def sweep(model, val_bins, y_val, n_num=None, *, space: SweepSpace | None = None,
          train_size: int | None = None, classification: bool = True,
          mesh=None, dist=None) -> SweepResult:
    """Price the full design space from one fitted model: "fit once, price
    every config, return the front".

    ``model`` is a fitted ``Tree`` or ``GradientBoostedTrees``.  For a
    single tree every cell is bit-identical to retraining with that
    cell's ``TreeConfig`` and evaluating on the validation set.  For an
    ensemble the ``n_rounds`` axis is exactly retraining (the first r
    rounds of one fit ARE the r-round refit); the pruning axes price
    predict-time pruning of every round's trees — the deployment-exact
    semantics of serving the ensemble at those runtime hyper-parameters
    (retraining WITH pruned early rounds would shift later rounds'
    targets, which no training-once scheme can price).

    ``mesh``/``dist`` (single trees only) shard the grid over the mesh:
    path-table rows over ``dist.data_axes``, the smin axis over
    ``dist.model_axis`` — each shard prices its grid slice against its
    row shard, one int32 psum + gather assembles the full grid.
    """
    space = space or SweepSpace()
    if isinstance(model, Tree):
        if n_num is None:
            raise ValueError("sweep(tree, ...) needs n_num (the per-feature "
                             "numeric-bin counts, e.g. table.n_num)")
        return _sweep_tree(model, val_bins, y_val, n_num, space, train_size,
                           classification, mesh, dist)
    if hasattr(model, "trees") and hasattr(model, "learning_rate"):
        if mesh is not None:
            raise ValueError("the mesh-sharded sweep path covers single "
                             "trees; price the ensemble per-device (the "
                             "n_rounds scan is already one fused kernel)")
        return _sweep_ensemble(model, val_bins, y_val, n_num, space,
                               train_size)
    raise TypeError(f"sweep() wants a Tree or GradientBoostedTrees, got "
                    f"{type(model).__name__}")


def _sweep_tree(tree, val_bins, y_val, n_num, space, train_size,
                classification, mesh, dist):
    n_train = train_size if train_size is not None else int(tree.count[0])
    dv, sv, wv = _resolve_axes(space, max(1, tree.max_tree_depth), n_train)
    metric = _metric_grid_tree(tree, val_bins, y_val, n_num, dv, sv, wv,
                               classification, mesh, dist)
    nodes, pdepth = _cost_grids(tree, dv, sv, wv)
    rb = _predicted_record_bytes([tree])
    from repro.serve.pack import walk_bytes_per_request
    wb = walk_bytes_per_request(1, pdepth, rb)
    configs = _CellConfigs(
        ("max_depth", "min_samples_split", "min_child_weight"),
        (dv, sv, wv), metric.shape)
    front = pareto_front(metric, nodes, wb, configs)
    bi = _best_cell(metric, nodes, wb)
    best = ParetoPoint(float(metric.ravel()[bi]), int(nodes.ravel()[bi]),
                       int(wb.ravel()[bi]), dict(configs[bi]))
    return SweepResult(dmax=dv, smin=sv, mcw=wv, n_rounds=None,
                       metric=metric, n_nodes=nodes, walk_bytes=wb,
                       front=front, best=best, n_configs=metric.size)


def _sweep_ensemble(ens, val_bins, y_val, n_num, space, train_size):
    lo = ens._fitted_loss()
    if getattr(lo, "n_classes", 0):
        raise NotImplementedError("sweep() prices scalar-loss ensembles; "
                                  "multiclass softmax rounds stack C trees "
                                  "per round (open item)")
    logistic = lo.link_id == 1
    trees = ens.trees
    r_total = len(trees)
    if n_num is None:
        n_num = ens.n_num
    n_train = (train_size if train_size is not None
               else int(round(float(np.asarray(trees[0].count)[0]))))
    full_depth = max(max(1, t.max_tree_depth) for t in trees)
    dv, sv, wv = _resolve_axes(space, full_depth, n_train)
    rv = (np.arange(1, r_total + 1, dtype=np.int32)
          if space.n_rounds_values is None
          else np.asarray(space.n_rounds_values, dtype=np.int32))
    if rv.size == 0 or rv.min() < 1 or rv.max() > r_total:
        raise ValueError(f"n_rounds_values must lie in 1..{r_total}")

    stacked = stack_trees(trees)                       # [R, N] WALK_FIELDS
    bins = jnp.asarray(val_bins)
    nn = jnp.asarray(n_num)
    nodes_rt = jax.vmap(
        lambda ta: _paths(ta, bins, nn, num_steps=full_depth))(stacked)
    gather = jax.vmap(lambda a, nd: a[nd])             # [R,N],[R,M,T]->[R,M,T]
    labs = gather(stacked["label"], nodes_rt)
    cnts = gather(stacked["count"], nodes_rt)
    mc = jax.vmap(_node_child_min)(stacked)            # [R, N]
    cmcs = jax.lax.cummin(gather(mc, nodes_rt), axis=2)

    m = bins.shape[0]
    yv = jnp.asarray(np.asarray(y_val), dtype=jnp.float32)
    totals = _ensemble_grid_counts(
        labs, cnts, cmcs, yv, jnp.ones((m,), dtype=bool),
        jnp.asarray(sv), jnp.asarray(wv), jnp.asarray(dv),
        jnp.float32(ens.learning_rate), jnp.float32(ens.base),
        logistic=logistic)                             # [R_total,Nd,Ns,Nw]
    totals = np.asarray(totals)[rv - 1]                # [R,Nd,Ns,Nw]
    if logistic:
        metric = totals.astype(np.float64) / m
    else:
        metric = -np.sqrt(totals.astype(np.float64) / m)

    # cost: per-round cost grids, prefix-summed (count) / prefix-maxed
    # (depth -> serve num_steps) over rounds
    per_round = [_cost_grids(t, dv, sv, wv) for t in trees]
    nodes_prefix = np.cumsum(np.stack([n for n, _ in per_round]), axis=0)
    steps_prefix = np.maximum.accumulate(
        np.stack([d for _, d in per_round]), axis=0)
    nodes = nodes_prefix[rv - 1]
    rb = _predicted_record_bytes(trees)
    from repro.serve.pack import walk_bytes_per_request
    wb = walk_bytes_per_request(rv[:, None, None, None],
                                steps_prefix[rv - 1], rb)
    configs = _CellConfigs(
        ("n_rounds", "max_depth", "min_samples_split", "min_child_weight"),
        (rv, dv, sv, wv), metric.shape)
    front = pareto_front(metric, nodes, wb, configs)
    bi = _best_cell(metric, nodes, wb)
    best = ParetoPoint(float(metric.ravel()[bi]), int(nodes.ravel()[bi]),
                       int(wb.ravel()[bi]), dict(configs[bi]))
    return SweepResult(dmax=dv, smin=sv, mcw=wv, n_rounds=rv,
                       metric=metric, n_nodes=nodes, walk_bytes=wb,
                       front=front, best=best, n_configs=metric.size)


# ---------------------------------------------------------------------------
# the original 2-axis surface (kept: tests, docs and the logistic bench
# drive it) — now a thin view over the 3-axis kernel
# ---------------------------------------------------------------------------

def toot_grid(tree: Tree, val_bins, y_val, n_num, *,
              dmax_values=None, smin_values=None, train_size: int | None = None,
              classification: bool = True) -> ToolGrid:
    """Score the (max_depth x min_samples_split) grid with one path pass."""
    n = train_size if train_size is not None else int(tree.count[0])
    space = SweepSpace(
        dmax_values=None if dmax_values is None else tuple(
            np.asarray(dmax_values).tolist()),
        smin_values=None if smin_values is None else tuple(
            np.asarray(smin_values).tolist()))
    dv, sv, wv = _resolve_axes(space, max(1, tree.max_tree_depth), n)
    metric = _metric_grid_tree(tree, val_bins, y_val, n_num, dv, sv, wv,
                               classification, None, None)
    return ToolGrid(np.asarray(dv), np.asarray(sv), metric[:, :, 0])


def tune(tree: Tree, val_bins, y_val, n_num, *, train_size=None,
         classification=True, dmax_values=None, smin_values=None) -> TuneResult:
    """Pick the best (max_depth, min_samples_split) cell.

    Flat metric ties are broken DETERMINISTICALLY toward the cheapest
    config — smallest pruned node count, then first in grid order — not
    np.argmax's arbitrary-w.r.t.-cost first-flat-index rule (many
    neighbouring cells of a TOOT grid price to identical accuracy, and
    the cheaper tree serves fewer bytes for free)."""
    grid = toot_grid(tree, val_bins, y_val, n_num, train_size=train_size,
                     classification=classification, dmax_values=dmax_values,
                     smin_values=smin_values)
    nodes, _ = _cost_grids(tree, grid.dmax, grid.smin, np.zeros(1))
    nodes2 = nodes[:, :, 0]
    tie = grid.metric == grid.metric.max()
    cost = np.where(tie, nodes2, np.iinfo(np.int64).max)
    i, j = np.unravel_index(int(np.argmin(cost)), grid.metric.shape)
    return TuneResult(int(grid.dmax[i]), int(grid.smin[j]),
                      float(grid.metric[i, j]), grid,
                      n_configs=grid.metric.size,
                      best_nodes=int(nodes2[i, j]))


def prune_stats(tree: Tree, dmax: int, smin: int, mcw: float = 0.0):
    """Node count / depth of the pruned tree (reachable under the tuned
    hyper-parameters), computed host-side by BFS — reporting parity with the
    paper's 'tuned tree' columns, and the oracle ``_cost_grids`` must match
    cell-for-cell (tests/test_tuning.py)."""
    left = np.asarray(tree.left)
    right = np.asarray(tree.right); leaf = np.asarray(tree.leaf)
    count = np.asarray(tree.count); depth = np.asarray(tree.depth)
    n, max_d, stack = 0, 0, [0]
    while stack:
        u = stack.pop()
        n += 1
        max_d = max(max_d, int(depth[u]))
        stops = (leaf[u] or left[u] < 0 or count[u] < smin
                 or depth[u] >= dmax
                 or (mcw > 0
                     and min(count[left[u]], count[right[u]]) <= mcw))
        if not stops:
            stack.append(int(left[u])); stack.append(int(right[u]))
    return n, max_d

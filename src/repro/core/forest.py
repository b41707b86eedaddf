"""Beyond-paper: tree ensembles reusing Superfast Selection.

The paper's O(M) selection makes per-tree cost O(K M depth); ensembles just
multiply tree count, so both bagging (random forest) and gradient boosting
drop out of the same machinery:

  * RandomForest: bootstrap rows + feature subsampling per tree.  Feature
    subsampling reuses the padded-feature mechanism (excluded features get
    n_num = n_cat = 0 and are never selectable) so ALL trees share one
    binned table and one compiled step.  Prediction stacks every tree's
    WALK_FIELDS and votes in ONE vmapped device walk (a single host
    transfer for the whole forest) — only the per-tree ``n_num`` vectors
    are retained after fit, never the bootstrapped bins.
  * GradientBoostedTrees: Newton-step boosting (the XGBoost-hist
    structure with the paper's selection inside), generic in the loss via
    core.losses.  Each round fits a ``regression_variance`` tree to the
    Newton target ``z = -g/h`` with ``sample_weight = h``: the in-kernel
    weight channel makes every leaf label ``-sum(g)/sum(h)`` — an exact
    Newton step — and the variance split score ``(sum g)^2 / sum h`` —
    the XGBoost gain — with no new kernel code (see core/losses.py for
    the equivalence).  ``loss="squared"`` has h = 1 and reduces to the
    original residual-fitting path bit for bit; ``loss="logistic"``
    opens binary classification with sigmoid-linked probabilities.

Both ensembles go through ``build_tree`` unchanged, so they inherit the
sibling-subtraction fast path (TreeConfig.sibling_subtraction, on by
default): per-tree histogram scatter work drops >= 2x per level, which
multiplies across the whole ensemble.  Hessian weights ride the same
float-tolerance subtraction contract as GOSS weights (``regression_
variance`` stays eligible; see core.tree._subtract_eligible), so Newton
boosting, GOSS, and subtraction all compose.

``GradientBoostedTrees`` additionally supports GOSS (Gradient-based
One-Side Sampling, cf. LightGBM and the random-sampling split finding of
arXiv:2108.08790) via ``GossConfig``: each tree trains on the top-``a``
fraction of examples by Newton leverage ``|g|*sqrt(h)`` (plain |gradient|
when the hessian is constant) plus a ``b`` fraction sampled from the
remainder, the latter weighted by ``(1-a)/b`` so weighted statistics stay
unbiased — see GossConfig for the math; the GOSS weight multiplies the
hessian weight on the sampled rows.  The boosting loop is device-resident:
raw scores, gradients/hessians, the ranking, the sampling, and the link
function all stay jax Arrays across trees, and ensemble prediction batches
every tree's walk on device with a single host transfer at the end.

``fit(mesh=..., dist=DistConfig(...))`` runs the SAME round loop sharded
over the mesh (core.distributed): examples over ``dist.data_axes``,
features over ``dist.model_axis``, with every per-round array staying
sharded across rounds and each tree built by ``DistributedBuilder`` — so
sibling subtraction, GOSS and slot_scatter compose mesh-wide.  The GOSS
draw becomes the per-shard-quota scheme (``_goss_shard_boundary`` /
``_goss_shard_weights``): one local ``top_k`` per shard, a scalar ``pmax``
threshold merge as the ONLY sampling collective, and per-shard stratified
remainder draws with the exact ``r_s / q_oth`` amplification — selected
indices and weights never leave their shard (a weight/assign mask, not a
gather), shapes stay static, and the draw is deterministic under the fit
seed.  ``goss_sample_sharded_ref`` is the bit-identical single-device
reference used by the parity tests.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import obs
from repro.core.binning import BinnedTable
from repro.core.losses import get_loss
from repro.core.predict import (WALK_FIELDS, _walk, predict_bins,
                                stack_trees, walk_class_trees)
from repro.core.tree import (Tree, TreeConfig, build_tree,
                             build_trees_batched)

__all__ = ["RandomForest", "GradientBoostedTrees", "GossConfig",
           "goss_sample_sharded_ref"]


def _round(r: int, rows: int):
    """The span of boosting round ``r`` over ``rows`` training rows, which
    raises the ``rounds`` counter as it opens."""
    return obs.counted("boost.round", "rounds", round=r, rows=rows)


def _validate_fit_inputs(table: BinnedTable, y, sample_weight=None) -> None:
    """Reject non-finite training inputs LOUDLY at fit entry, naming the
    offending column/row — silently training on a poisoned column yields
    NaN leaf labels that only surface (if ever) at predict time.

    ``table.bins`` is int32 after core.binning (raw-feature NaNs land in
    the missing bin BY DESIGN, so integer bins are always valid); a float
    bins array means the caller bypassed ``fit_bins``, and any non-finite
    entry there is a corrupted pipeline, not a missing value.  Labels are
    checked when float (regression / boosting targets); sample weights
    must be finite and non-negative (they enter the histogram weight
    channel, where a NaN poisons every statistic of its node)."""
    bins = table.bins
    if np.issubdtype(np.dtype(bins.dtype), np.floating):
        b = np.asarray(bins)
        bad = ~np.isfinite(b)
        if bad.any():
            col = int(np.argmax(bad.any(axis=0)))
            meta = (table.metas[col] if table.metas is not None
                    and col < len(table.metas) else None)
            name = f" ({meta.name!r})" if meta is not None else ""
            raise ValueError(
                f"non-finite feature values in column {col}{name}: "
                f"{int(bad[:, col].sum())} of {b.shape[0]} rows (first at "
                f"row {int(np.argmax(bad[:, col]))}).  Binned features "
                "must be finite — raw NaNs belong in the missing bin "
                "(core.binning.fit_bins), a non-finite *bin* is a "
                "corrupted pipeline.")
    y_arr = np.asarray(y)
    if np.issubdtype(y_arr.dtype, np.floating):
        bad = ~np.isfinite(y_arr)
        if bad.any():
            raise ValueError(
                f"non-finite labels: {int(bad.sum())} of {y_arr.shape[0]} "
                f"rows (first at row {int(np.argmax(bad))}) — refusing to "
                "train NaN trees")
    if sample_weight is not None:
        sw = np.asarray(sample_weight, dtype=np.float32)
        bad = ~np.isfinite(sw) | (sw < 0)
        if bad.any():
            raise ValueError(
                f"sample_weight must be finite and non-negative: "
                f"{int(bad.sum())} of {sw.shape[0]} rows violate this "
                f"(first at row {int(np.argmax(bad))})")


def _subsample_table(table: BinnedTable, feat_mask: np.ndarray) -> BinnedTable:
    """Mask out features by zeroing their bin ranges (never selectable)."""
    return BinnedTable(
        bins=table.bins,
        n_num=np.where(feat_mask, table.n_num, 0).astype(np.int32),
        n_cat=np.where(feat_mask, table.n_cat, 0).astype(np.int32),
        metas=table.metas, n_bins=table.n_bins)


@functools.partial(jax.jit, static_argnames=("num_steps", "n_classes"))
def _forest_votes(stacked, n_nums, bins, *, num_steps, n_classes):
    """Batched Algorithm-7 walk + vote counts for the whole forest: one
    vmap over the stacked [T, max_nodes] tree arrays AND the per-tree
    feature masks (n_num differs per tree under feature subsampling), one
    [M, C] one-hot vote reduction — callers transfer the [M, C] counts (or
    their argmax) once.  Integer vote counts are exact in f32 and argmax
    takes the first maximum, so class predictions reproduce the per-tree
    host loop bit for bit."""
    no_limit = jnp.int32(1 << 30)
    per_tree = jax.vmap(
        lambda ta, nn: _walk(ta, bins, nn, no_limit, jnp.int32(0),
                             jnp.float32(0.0),
                             num_steps=num_steps))(stacked, n_nums)  # [T, M]
    return jax.nn.one_hot(per_tree.astype(jnp.int32), n_classes,
                          dtype=jnp.float32).sum(axis=0)            # [M, C]


@dataclasses.dataclass
class RandomForest:
    n_trees: int = 10
    max_features: float = 0.7         # fraction of features per tree
    bootstrap: bool = True
    config: TreeConfig = dataclasses.field(
        default_factory=lambda: TreeConfig(max_depth=24))
    seed: int = 0

    def fit(self, table: BinnedTable, y, n_classes: int | None = None, *,
            sample_weight=None, level_callback=None, mesh=None, dist=None):
        """Fit the forest on int class labels ``y``.

        The unified estimator signature (shared with GradientBoostedTrees):
        everything after ``y`` is keyword-only — ``sample_weight`` ([M]
        f32, entering each tree's weight channel under the bootstrap),
        ``level_callback`` (per-level BuildState hook), and ``mesh`` /
        ``dist`` (each tree built by ``build_tree_distributed`` over the
        mesh).  ``n_classes`` is inferred from the labels; passing it
        positionally still works as a one-release deprecation shim.
        """
        if n_classes is not None:
            warnings.warn(
                "passing n_classes to RandomForest.fit is deprecated and "
                "will be removed in the next release; it is now inferred "
                "from the labels", DeprecationWarning, stacklevel=2)
        # drop the stacked-walk cache FIRST: a refit that fails midway must
        # never leave predict serving the previous fit's trees
        self._stacked = None            # predict's lazy stacked-walk cache
        _validate_fit_inputs(table, y, sample_weight)
        rng = np.random.default_rng(self.seed)
        m, k = table.bins.shape
        y = np.asarray(y)
        self.n_classes = (int(n_classes) if n_classes is not None
                          else int(y.max()) + 1)
        sw = (np.asarray(sample_weight, dtype=np.float32)
              if sample_weight is not None else None)
        if mesh is not None:
            from repro.core.distributed import (DistConfig,
                                                build_tree_distributed)
            dist = dist if dist is not None else DistConfig()
        self.trees: list[Tree] = []
        # predict only needs each tree's feature mask (n_num); retaining the
        # bootstrapped [M, K] bins per tree was an M*K*T memory leak.
        self.n_nums: list[np.ndarray] = []
        for _ in range(self.n_trees):
            fm = rng.uniform(size=k) < self.max_features
            if not fm.any():
                fm[rng.integers(0, k)] = True
            sub = _subsample_table(table, fm)
            if self.bootstrap:
                idx = rng.integers(0, m, size=m)
                sub = BinnedTable(bins=sub.bins[idx], n_num=sub.n_num,
                                  n_cat=sub.n_cat, metas=sub.metas,
                                  n_bins=sub.n_bins)
                yy, ww = y[idx], (sw[idx] if sw is not None else None)
            else:
                yy, ww = y, sw
            if mesh is not None:
                tree = build_tree_distributed(
                    sub, yy, self.config, mesh=mesh, dist=dist,
                    n_classes=self.n_classes, sample_weight=ww,
                    level_callback=level_callback)
            else:
                tree = build_tree(sub, yy, self.config,
                                  n_classes=self.n_classes,
                                  sample_weight=ww,
                                  level_callback=level_callback)
            self.trees.append(tree)
            self.n_nums.append(sub.n_num)
        return self

    def _votes(self, bins) -> jax.Array:
        if getattr(self, "_stacked", None) is None:
            self._stacked = (
                stack_trees(self.trees),
                jnp.stack([jnp.asarray(nn) for nn in self.n_nums]),
                max(1, max(t.max_tree_depth for t in self.trees)))
        stacked, n_nums, steps = self._stacked
        return _forest_votes(stacked, n_nums, jnp.asarray(bins),
                             num_steps=steps, n_classes=self.n_classes)

    # -- the unified predict triple (device + host variants) ---------------
    def predict_raw_device(self, bins) -> jax.Array:
        """Per-class vote COUNTS [M, C] as a device Array — the forest's
        raw score.  The stacked [T, max_nodes] tree arrays and [T, K]
        feature masks are built once on first use (trees are immutable
        after fit)."""
        return self._votes(bins)

    def predict_proba_device(self, bins) -> jax.Array:
        """Vote FRACTIONS [M, C] (counts / n_trees) as a device Array."""
        return self._votes(bins) / jnp.float32(self.n_trees)

    def predict_device(self, bins) -> jax.Array:
        """Majority-vote class ids [M] as a device Array (argmax of the
        vote counts; ties go to the lowest class id)."""
        return jnp.argmax(self._votes(bins), axis=1).astype(jnp.int32)

    def predict_raw(self, bins):
        return np.asarray(self.predict_raw_device(bins))

    def predict_proba(self, bins):
        return np.asarray(self.predict_proba_device(bins))

    def predict(self, bins):
        """Batched forest prediction (class ids [M]); ONE device->host
        transfer for the whole forest."""
        return np.asarray(self.predict_device(bins))


@dataclasses.dataclass(frozen=True)
class GossConfig:
    """Gradient-based One-Side Sampling for GradientBoostedTrees.

    Each boosting round keeps the ``top_rate`` (= ``a``) fraction of
    examples with the largest |gradient| at weight 1, plus an
    ``other_rate`` (= ``b``) fraction sampled uniformly from the remaining
    small-gradient examples, weighted by the amplification factor

        w = (1 - a) / b

    so that any weighted statistic over the sample — a histogram channel, a
    node count, a label sum — is an unbiased estimate of the same statistic
    over the full data: the (1-a)M small-gradient examples are represented
    by bM draws, each standing in for exactly (1-a)/b of them.  The weight
    enters the histogram scatter itself (``build_tree(sample_weight=...)``
    -> the in-kernel weight channel of kernels/histogram.py), so the
    amplification is exact, not a post-selection rescale.

    Under a non-constant hessian (Newton boosting, core.losses) the ranking
    statistic is ``|g| * sqrt(h)``: the gradient magnitude damped by the
    square root of the local curvature, so near-saturated examples (h -> 0,
    where the Newton working response g/h explodes but carries almost no
    weight in the fitted leaves) do not crowd the kept set the way raw |g|
    — let alone the outlier-chasing |g|/sqrt(h) — would let them.  The
    GOSS weight multiplies the hessian weight on the sampled rows, so the
    weighted moments stay unbiased estimates of the full-data ``sum h`` /
    ``sum h z`` channels whatever the ranking.  For constant-hessian losses
    the statistic reduces to |g|, LightGBM's original GOSS ranking.

    Composition with sibling subtraction: a weighted build's histogram
    channels are float weighted sums, which keeps subtraction eligible only
    under the float-tolerance contract — i.e. for the boosted-ensemble task
    ``regression_variance`` (see core.tree._subtract_eligible).  Weighted
    *classification* would break its bit-exactness contract, so sampling
    disables subtraction eligibility there.  In the supported composed mode
    the smaller-child scatter runs over just the (a + b)M sampled rows:
    the two reductions multiply (~2x from subtraction, ~1/(a+b) from GOSS).
    """
    top_rate: float = 0.2
    other_rate: float = 0.1

    def __post_init__(self):
        if not 0.0 <= self.top_rate < 1.0:
            raise ValueError(f"top_rate must be in [0, 1), got {self.top_rate}")
        # tiny slack so e.g. (0.9, 0.1) survives 1.0 - 0.9 != 0.1 in floats
        if not 0.0 < self.other_rate <= 1.0 - self.top_rate + 1e-9:
            raise ValueError("other_rate must be in (0, 1 - top_rate], got "
                             f"{self.other_rate}")

    @property
    def amplification(self) -> float:
        """The small-gradient sample weight ``(1 - a) / b``."""
        return (1.0 - self.top_rate) / self.other_rate

    def sample_sizes(self, m: int) -> tuple[int, int]:
        """(top_n, other_n) for an [M] gradient vector — static per fit, so
        every tree of the ensemble shares one compiled build.  ``other_n``
        is 0 when the top set already covers every row (ceil rounding at
        tiny M): re-drawing an already-selected row would double-count it."""
        top_n = min(m, int(math.ceil(self.top_rate * m)))
        other_n = min(m - top_n, max(1, int(math.ceil(self.other_rate * m))))
        return top_n, other_n

    def shard_quota(self, m: int, d_shards: int) -> tuple[int, int]:
        """Static per-shard (top, other) quotas for the sharded draw: ceil
        splits of ``sample_sizes`` so the union covers at least the global
        sample whatever the shard count.  Static per fit — every round and
        every shard share one compiled sampling step."""
        top_n, other_n = self.sample_sizes(m)
        ceil_div = lambda a: -(-a // d_shards) if a else 0
        return ceil_div(top_n), ceil_div(other_n)


@functools.partial(jax.jit, static_argnames=("top_n", "other_n", "amp"))
def _goss_sample(grad, key, *, top_n, other_n, amp):
    """Device-side GOSS draw: indices [top_n + other_n] and their weights.

    ``grad`` is the ranking statistic (the raw gradient, or the Newton
    leverage ``g * sqrt(h)`` — only |grad| matters).  The top-|gradient|
    set comes from one ``top_k``; the uniform remainder re-uses ``top_k``
    over random keys with the top set masked out (an O(M log M)-free
    approximation of choice-without-replacement that stays fully on device
    and is deterministic under a fixed PRNG key).
    """
    scores = jax.random.uniform(key, grad.shape)
    if top_n:
        _, top_idx = jax.lax.top_k(jnp.abs(grad), top_n)
        scores = scores.at[top_idx].set(-1.0)
    else:
        top_idx = jnp.zeros((0,), dtype=jnp.int32)
    if other_n:
        _, other_idx = jax.lax.top_k(scores, other_n)
    else:
        other_idx = jnp.zeros((0,), dtype=jnp.int32)
    idx = jnp.concatenate([top_idx.astype(jnp.int32),
                           other_idx.astype(jnp.int32)])
    w = jnp.concatenate([jnp.ones((top_n,), jnp.float32),
                         jnp.full((other_n,), amp, jnp.float32)])
    return idx, w


# ---------------------------------------------------------------------------
# sharded GOSS (core.distributed.make_sharded_sampler): per-shard quota
# top_k + a scalar pmax threshold merge + per-shard stratified remainder.
# The two stage functions below are the WHOLE per-shard computation; the
# mesh sampler runs them inside shard_map with lax.pmax between, and
# ``goss_sample_sharded_ref`` runs them vmapped over contiguous row blocks
# with a plain max — bit-identical selections (tests/test_dist_goss.py),
# which is what makes single-device parity of the distributed fit testable.
# ---------------------------------------------------------------------------

def _goss_shard_boundary(lv, q_top: int):
    """This shard's quota boundary: the ``q_top``-th largest leverage.

    ``lv`` must carry -1 for invalid/padding rows (|leverage| >= 0 for
    valid ones).  The pmax merge of these boundaries over the data shards
    is >= the true global top-``top_n`` cut (pigeonhole: some shard holds
    >= q_top of the global top rows), so rows clearing the merged
    threshold are certifiably inside the global top set.  +inf when the
    top quota is empty (top_rate = 0)."""
    if q_top == 0:
        return jnp.float32(jnp.inf)
    return jax.lax.top_k(lv, q_top)[0][-1]


def _goss_shard_weights(lv, u, tau, q_top: int, q_oth: int):
    """Per-shard GOSS weights under the merged global threshold ``tau``.

    The top set is the intersection of this shard's local top-``q_top``
    rows with ``{leverage >= tau}``, at weight 1: the threshold makes the
    set globally consistent (every member is certifiably inside the true
    global top-``top_n``), the quota caps it at ``q_top`` rows per shard —
    including under mass leverage ties (a logistic round 0 with balanced
    classes has IDENTICAL leverage everywhere; an uncapped threshold set
    would then keep all M rows and forfeit the sampling reduction, where
    ``top_k``'s deterministic tie-break keeps exactly the quota).
    From the remainder — valid rows outside the top set — ``q_oth`` rows
    are drawn uniformly (``u`` must carry -1 outside the remainder pool)
    and weighted by the EXACT per-shard amplification ``r_s / q_oth``
    (``r_s`` = remainder size): the stratified analogue of GOSS's global
    ``(1-a)/b``, unbiased per shard, and the total selected weight over
    the mesh is exactly M.  Unselected rows get weight 0 (inert in the
    histogram scatter and the router — the shard-local selection mask)."""
    if q_top:
        _, ti = jax.lax.top_k(lv, q_top)
        in_quota = jnp.zeros(lv.shape, bool).at[ti].set(True)
        top = in_quota & (lv >= tau) & (lv >= 0)
    else:
        top = jnp.zeros(lv.shape, bool)
    w = top.astype(jnp.float32)
    if q_oth == 0:
        return w
    pool = (lv >= 0) & ~top
    u = jnp.where(pool, u, -1.0)
    r = pool.sum(dtype=jnp.int32)
    _, oi = jax.lax.top_k(u, q_oth)
    drawn = jnp.zeros_like(pool).at[oi].set(True) & pool
    amp = r.astype(jnp.float32) / jnp.maximum(jnp.minimum(q_oth, r), 1)
    return w + drawn.astype(jnp.float32) * amp


@functools.partial(jax.jit,
                   static_argnames=("d_shards", "m_valid", "q_top", "q_oth"))
def goss_sample_sharded_ref(rank, key, *, d_shards, m_valid, q_top, q_oth):
    """Single-device reference of the sharded GOSS draw: [m_pad] weights
    (0 = unselected), bit-identical to ``make_sharded_sampler``'s
    ``w_goss`` for the same key.  Rows are split into ``d_shards``
    contiguous blocks — the layout of ``P(data_axes)`` sharding — and each
    block runs the same per-shard stages with ``fold_in(key, block)``."""
    m_pad = rank.shape[0]
    m_loc = m_pad // d_shards
    lv = jnp.where(jnp.arange(m_pad) < m_valid, jnp.abs(rank), -1.0)
    lv = lv.reshape(d_shards, m_loc)
    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(
        jnp.arange(d_shards, dtype=jnp.int32))
    u = jax.vmap(lambda kk: jax.random.uniform(kk, (m_loc,)))(keys)
    u = jnp.where(lv >= 0, u, -1.0)
    tau = jnp.max(jax.vmap(
        lambda x: _goss_shard_boundary(x, q_top))(lv))
    w = jax.vmap(
        lambda a, b: _goss_shard_weights(a, b, tau, q_top, q_oth))(lv, u)
    return w.reshape(m_pad)


@functools.partial(jax.jit, static_argnums=0, static_argnames=("num_steps",))
def _raw_update(walk, raw, lr, tree, bins, n_num, *, num_steps):
    """Boosting's raw-score update ``raw + lr * walk(tree)``, one program
    whose ops carry the ``boost.walk`` scope in their ``tf_op`` path (a
    scope around an eager call reaches no compiled op).  ``walk`` is
    ``predict_bins``, taken as a static argument so that the function the
    module holds at call time is the one traced."""
    with jax.named_scope("boost.walk"):
        return raw + lr * walk(tree, bins, n_num, num_steps=num_steps)


@functools.partial(jax.jit, static_argnames=("num_steps",))
def _ensemble_predict(stacked, bins, n_num, lr, base, *, num_steps):
    """Batched Algorithm-7 walk over every tree of the ensemble: one vmap
    over the stacked [T, max_nodes] tree arrays, one [T, M] leaf-label
    tensor, one weighted reduction — the whole ensemble prediction is a
    single device computation (callers transfer the [M] result once)."""
    no_limit = jnp.int32(1 << 30)
    per_tree = jax.vmap(
        lambda ta: _walk(ta, bins, n_num, no_limit, jnp.int32(0),
                         jnp.float32(0.0),
                         num_steps=num_steps))(stacked)        # [T, M]
    return base + lr * per_tree.sum(axis=0)


@functools.partial(jax.jit, static_argnames=("num_steps", "n_classes"))
def _ensemble_predict_multiclass(stacked, bins, n_num, lr, base, *,
                                 num_steps, n_classes):
    """Multiclass twin of ``_ensemble_predict``: the stacked [R*C,
    max_nodes] arrays hold R rounds of C class-trees round-major (the
    order ``fit`` appends them), so one vmapped walk + a [R, C, M]
    reshape-reduce yields the per-class raw scores.  Returns CLASS-LAST
    [M, C] — the prediction-surface layout (core.losses module docs)."""
    no_limit = jnp.int32(1 << 30)
    per_tree = jax.vmap(
        lambda ta: _walk(ta, bins, n_num, no_limit, jnp.int32(0),
                         jnp.float32(0.0),
                         num_steps=num_steps))(stacked)        # [R*C, M]
    per_class = per_tree.reshape(-1, n_classes,
                                 per_tree.shape[1]).sum(axis=0)  # [C, M]
    return (base[:, None] + lr * per_class).T                    # [M, C]


@dataclasses.dataclass
class GradientBoostedTrees:
    """Newton-step gradient boosting with variance-split UDTs.

    ``loss`` selects the objective (core.losses: "squared" regression,
    "logistic" binary classification, or a loss instance).  Every round
    fits a ``regression_variance`` tree to the Newton target ``z = -g/h``
    with ``sample_weight = h`` — leaf labels are exact Newton steps
    ``-sum(g)/sum(h)`` via the weight channel, and
    ``config.min_child_weight`` bounds the per-child hessian sum (the
    XGBoost parameter of the same name).  Constant-hessian losses skip the
    weight channel when unsampled, so ``loss="squared"`` reproduces the
    pre-Newton residual-fitting path exactly.

    The fit loop is device-resident: raw scores, gradients/hessians, the
    GOSS leverage ranking and the sample draw all stay jax Arrays from
    tree to tree — the only per-tree host traffic is the builder's
    level-loop scalars.  With ``goss`` set, each tree trains on the GOSS
    subset with the exact ``(1-a)/b`` weight channel multiplied onto the
    hessian weights (see GossConfig); tree shapes are static across
    rounds, so the whole ensemble reuses one compiled build + one compiled
    predict step.

    ``loss="softmax"`` (or ``SoftmaxLoss(n_classes)``) opens MULTICLASS
    boosting: raw scores become class-first [C, M], each round fits one
    tree per class on its ``(z_c, h_c)`` channel, and the K class-trees of
    a round are batched through ONE vmapped build
    (core.tree.build_trees_batched) against the shared binned table — a
    round costs ~one build and exactly one compiled level step, not K.
    Under GOSS the round's shared row draw ranks by the cross-class
    leverage norm ``sqrt(sum_c g_c^2 h_c)`` and each class multiplies its
    own hessians onto the shared amplification weights.

    The predict surface is the unified triple (device + host variants):
    ``predict_raw`` — raw scores ([M], or class-last [M, C] for softmax);
    ``predict_proba`` — link-applied probabilities ([M] sigmoid for
    "logistic", [M, C] softmax; rejected for regression losses);
    ``predict`` — class ids for classification losses, raw values for
    regression.
    """
    n_trees: int = 20
    learning_rate: float = 0.3
    config: TreeConfig = dataclasses.field(
        default_factory=lambda: TreeConfig(max_depth=6,
                                           task="regression_variance"))
    goss: GossConfig | None = None
    loss: str = "squared"
    seed: int = 0

    def _resolve_loss(self, y):
        """``get_loss`` on ``self.loss``; the bare name "softmax" infers
        ``n_classes`` from the labels (pass ``SoftmaxLoss(n_classes=...)``
        or ``get_loss("softmax", n_classes=...)`` to pin it)."""
        if isinstance(self.loss, str) and self.loss == "softmax":
            return get_loss(self.loss, n_classes=int(np.asarray(y).max()) + 1)
        return get_loss(self.loss)

    def fit(self, table: BinnedTable, y, *, sample_weight=None,
            level_callback=None, mesh=None, dist=None,
            round_callback=None, resume_from=None):
        """Fit the ensemble (unified estimator signature: everything after
        ``y`` is keyword-only).  ``sample_weight`` ([M] f32) scales each
        example's gradient and hessian — it rides the weight channel, so
        the Newton target stays invariant while every fitted statistic
        becomes its weighted estimate.  With ``mesh`` set the whole round
        loop runs sharded over ``dist.data_axes`` / ``dist.model_axis``
        (see ``_fit_sharded`` and core.distributed): same API, same trees
        up to the documented weighted-moment tolerance.

        Preemption safety (repro.checkpoint.round_ckpt): ``round_callback``
        receives a ``RoundState`` after every completed round — pass a
        ``RoundCheckpointer`` to persist it; ``resume_from`` (a checkpoint
        directory or a restored ``RoundCheckpoint``) re-enters the loop at
        the checkpointed round with the saved trees / raw scores / PRNG
        carry.  The sequential ``jax.random.split`` discipline makes the
        resumed fit BIT-IDENTICAL to an uninterrupted one, on the local
        and the mesh path alike; a checkpoint whose config digest does not
        match this fit raises ``CheckpointMismatchError``."""
        # drop the stacked-walk cache FIRST: a refit that fails midway must
        # never leave predict serving the previous fit's trees
        self._stacked = None                    # predict_device's lazy cache
        _validate_fit_inputs(table, y, sample_weight)
        lo = self._loss = self._resolve_loss(y)
        digest = None
        if round_callback is not None or resume_from is not None:
            from repro.checkpoint.round_ckpt import fit_digest
            digest = fit_digest(self, table, y, sample_weight,
                                mesh=mesh, dist=dist)
        if mesh is not None:
            return self._fit_sharded(table, y, mesh, dist, level_callback,
                                     sample_weight,
                                     round_callback=round_callback,
                                     resume_from=resume_from, digest=digest)
        if getattr(lo, "is_multiclass", False):
            return self._fit_multiclass(table, y, lo, sample_weight,
                                        level_callback,
                                        round_callback=round_callback,
                                        resume_from=resume_from,
                                        digest=digest)
        bins = jnp.asarray(table.bins)
        m = bins.shape[0]
        y = jnp.asarray(y, dtype=jnp.float32)
        sw = (jnp.asarray(sample_weight, dtype=jnp.float32)
              if sample_weight is not None else None)
        base = lo.base_score(y)
        self.n_num = np.asarray(table.n_num)
        n_num_d = jnp.asarray(self.n_num)
        dev_table = dataclasses.replace(table, bins=bins)
        raw = jnp.broadcast_to(base, y.shape)   # additive scores, pre-link
        key = jax.random.PRNGKey(self.seed)
        if self.goss is not None:
            top_n, other_n = self.goss.sample_sizes(m)
            amp = self.goss.amplification
        self.trees: list[Tree] = []
        num_steps = max(1, self.config.max_depth)
        start, raw, key = self._apply_resume(resume_from, digest, raw, key)
        for r in range(start, self.n_trees):
            with _round(r, m):
                g, h = lo.grad_hess(y, raw)
                # a row weight scales g and h alike, so the Newton target
                # is weight-invariant; the weight enters through the h
                # channel (and the leverage ranking) only.
                z = lo.newton_target(g, h)
                if sw is not None:
                    g, h = g * sw, h * sw
                use_w = sw is not None or not lo.constant_hessian
                if self.goss is None:
                    tree = build_tree(
                        dev_table, z, self.config,
                        sample_weight=h if use_w else None,
                        level_callback=level_callback)
                else:
                    key, sub = jax.random.split(key)
                    rank = g * jnp.sqrt(h) if use_w else g
                    idx, w = _goss_sample(rank, sub, top_n=top_n,
                                          other_n=other_n, amp=amp)
                    if use_w:
                        w = w * jnp.take(h, idx)  # GOSS amp x hessian weight
                    sub_table = dataclasses.replace(
                        table, bins=jnp.take(bins, idx, axis=0))
                    tree = build_tree(sub_table, jnp.take(z, idx),
                                      self.config, sample_weight=w,
                                      level_callback=level_callback)
                self.trees.append(tree)
                # full-data raw scores update on device; num_steps is the
                # static depth bound so no per-tree host sync happens here
                raw = _raw_update(predict_bins, raw, self.learning_rate,
                                  tree, bins, n_num_d, num_steps=num_steps)
            if round_callback is not None:
                round_callback(self._round_state(r + 1, raw, key, digest))
        self.base = float(base)                 # one scalar sync at the end
        return self

    def _round_state(self, completed: int, raw, key, digest):
        from repro.checkpoint.round_ckpt import RoundState
        return RoundState(round=completed, trees=self.trees, raw=raw,
                          key=key, digest=digest)

    def _apply_resume(self, resume_from, digest, raw, key):
        """Swap in a round checkpoint's (trees, raw, key) carry, after the
        digest check.  Returns ``(start_round, raw, key)``; restored trees
        stay host arrays (stack_trees / the serve pack re-device them)."""
        if resume_from is None:
            return 0, raw, key
        from repro.checkpoint.round_ckpt import resolve_resume
        ck = resolve_resume(resume_from, digest)
        self.trees = list(ck.trees)
        return ck.round, jnp.asarray(ck.raw), jnp.asarray(ck.key)

    def _fit_multiclass(self, table: BinnedTable, y, lo, sample_weight,
                        level_callback, *, round_callback=None,
                        resume_from=None, digest=None):
        """The softmax round loop: raw scores are class-first [C, M], each
        round's per-class gradients/hessians come from ONE ``grad_hess``
        over the class axis, and the K class-trees are built by ONE
        vmapped ``build_trees_batched`` call — the round costs ~one build
        and one compiled level step regardless of C.  The score update
        walks all K class-trees in one vmapped pass
        (``predict.walk_class_trees``) straight off the builder's stacked
        arrays; trees are appended round-major (round r's class-c tree at
        index ``r * C + c``), the layout the stacked multiclass predict
        reshapes by."""
        bins = jnp.asarray(table.bins)
        m = bins.shape[0]
        n_classes = lo.n_classes
        y_i = jnp.asarray(y, dtype=jnp.int32)
        sw = (jnp.asarray(sample_weight, dtype=jnp.float32)
              if sample_weight is not None else None)
        base = lo.base_score(y_i)               # [C] class log-priors
        self.n_num = np.asarray(table.n_num)
        n_num_d = jnp.asarray(self.n_num)
        dev_table = dataclasses.replace(table, bins=bins)
        raw = jnp.broadcast_to(base[:, None], (n_classes, m))
        key = jax.random.PRNGKey(self.seed)
        if self.goss is not None:
            top_n, other_n = self.goss.sample_sizes(m)
            amp = self.goss.amplification
        self.trees: list[Tree] = []
        num_steps = max(1, self.config.max_depth)
        lr = jnp.float32(self.learning_rate)
        start, raw, key = self._apply_resume(resume_from, digest, raw, key)
        for r in range(start, self.n_trees):
            with _round(r, m):
                g, h = lo.grad_hess(y_i, raw)   # [C, M] each
                z = lo.newton_target(g, h)
                if sw is not None:
                    g, h = g * sw[None], h * sw[None]
                if self.goss is None:
                    round_trees, arrays = build_trees_batched(
                        dev_table, z, self.config, sample_weight=h,
                        level_callback=level_callback)
                else:
                    # ONE shared row draw per round (all class-trees see
                    # the same sampled rows — one subset gather, one build
                    # shape), ranked by the cross-class Newton leverage
                    # norm sqrt(sum_c g_c^2 h_c) = the L2 norm of the
                    # per-class |g_c| sqrt(h_c) leverages; each class then
                    # multiplies its own hessians onto the shared
                    # amplification weights.
                    key, sub = jax.random.split(key)
                    rank = jnp.sqrt(jnp.sum(g * g * h, axis=0))
                    idx, w = _goss_sample(rank, sub, top_n=top_n,
                                          other_n=other_n, amp=amp)
                    sub_table = dataclasses.replace(
                        table, bins=jnp.take(bins, idx, axis=0))
                    round_trees, arrays = build_trees_batched(
                        sub_table, jnp.take(z, idx, axis=1), self.config,
                        sample_weight=w[None] * jnp.take(h, idx, axis=1),
                        level_callback=level_callback)
                self.trees.extend(round_trees)
                raw = raw + lr * walk_class_trees(
                    {f: arrays[f] for f in WALK_FIELDS}, bins, n_num_d,
                    num_steps=num_steps)
            if round_callback is not None:
                round_callback(self._round_state(r + 1, raw, key, digest))
        self.base = np.asarray(base, dtype=np.float32)   # [C], one sync
        return self

    def _fit_sharded(self, table: BinnedTable, y, mesh, dist,
                     level_callback, sample_weight=None, *,
                     round_callback=None, resume_from=None, digest=None):
        """The mesh-wide round loop: every per-round array — raw scores,
        gradients/hessians, the leverage ranking, the GOSS draw, the build
        weights and the score update — is a device Array sharded with
        ``P(dist.data_axes)`` from the first round to the last.  The table
        is staged ONCE (core.distributed.DistributedBuilder); each round's
        sampling is the per-shard-quota draw with a scalar pmax threshold
        merge (no cross-shard row gather, static shapes, deterministic
        under the fit seed); each tree is built by the same sharded level
        step as ``build_tree_distributed`` with the weights entering the
        in-kernel channel shard-locally; and the full-data score update
        walks the (data, model)-sharded bins feature-parallel
        (``make_sharded_walk``).  Host traffic per round is only the
        builder's level-loop scalars.

        Multiclass (softmax): raw scores are class-first [C, m_pad]
        sharded ``P(None, data_axes)`` — the class axis is replicated, the
        example axis sharded — the sampler emits per-class ``(z, w)``
        channels off ONE shared row draw, and the K class-trees are built
        by ``DistributedBuilder.build_batched``: the SAME vmapped level
        step as the local multiclass build, run inside shard_map, so a
        round costs one sharded build and one compile regardless of C."""
        from repro.core.distributed import (DistConfig, DistributedBuilder,
                                            make_sharded_sampler,
                                            make_sharded_walk)
        if self.config.task != "regression_variance":
            raise ValueError("the boosted-ensemble loop fits "
                             "'regression_variance' trees; got task="
                             f"{self.config.task!r}")
        dist = dist if dist is not None else DistConfig()
        lo = self._loss
        multiclass = getattr(lo, "is_multiclass", False)
        y_np = np.asarray(y, dtype=np.float32)
        m = y_np.shape[0]
        builder = DistributedBuilder(table, self.config, mesh=mesh,
                                     dist=dist)
        y_d = builder._stage_rows(y_np, 0.0, np.float32)
        sw_d = (builder._stage_rows(
                    np.asarray(sample_weight, dtype=np.float32), 0.0,
                    np.float32)
                if sample_weight is not None else None)
        if multiclass:
            base = np.asarray(lo.base_score(jnp.asarray(y_np)),
                              dtype=np.float32)          # [C] log-priors
            raw = builder._stage_class_rows(
                np.broadcast_to(base[:, None],
                                (lo.n_classes, builder.m_pad)),
                0.0, np.float32)
        else:
            base = float(lo.base_score(jnp.asarray(y_np)))
            raw = builder._stage_rows(
                np.full(builder.m_pad, base, np.float32), 0.0, np.float32)
        q_top, q_oth = ((0, 0) if self.goss is None
                        else self.goss.shard_quota(m, builder.d_shards))
        sampler = make_sharded_sampler(mesh, dist, lo, self.goss, m,
                                       q_top, q_oth,
                                       weighted=sw_d is not None)
        num_steps = max(1, self.config.max_depth)
        walk = make_sharded_walk(mesh, dist, num_steps,
                                 classes=lo.n_classes if multiclass else 0)
        lr = jnp.float32(self.learning_rate)
        key = jax.random.PRNGKey(self.seed)
        self.n_num = np.asarray(table.n_num)
        self.trees: list[Tree] = []
        use_w = (self.goss is not None or not lo.constant_hessian
                 or sw_d is not None)
        start = 0
        if resume_from is not None:
            from repro.checkpoint.round_ckpt import resolve_resume
            ck = resolve_resume(resume_from, digest)
            self.trees = list(ck.trees)
            start = ck.round
            key = jnp.asarray(ck.key)
            # re-stage the checkpointed raw scores into the sharded
            # [m_pad] / [C, m_pad] layout (f32 host round-trips are exact)
            stage = (builder._stage_class_rows if multiclass
                     else builder._stage_rows)
            raw = stage(np.asarray(ck.raw, dtype=np.float32), 0.0,
                        np.float32)
        for r in range(start, self.n_trees):
            with _round(r, m):
                key, sub = jax.random.split(key)
                args = (y_d, raw, sub) + ((sw_d,) if sw_d is not None else ())
                z, w, assign0 = sampler(*args)
                if multiclass:
                    round_trees, arrays = builder.build_batched(
                        z, sample_weight=w if use_w else None,
                        assign=assign0, level_callback=level_callback)
                    self.trees.extend(round_trees)
                    raw = walk(raw, {f: arrays[f] for f in WALK_FIELDS},
                               builder.bins_d, builder.n_num_d, lr)
                else:
                    tree = builder.build(
                        z, sample_weight=w if use_w else None,
                        assign=assign0, level_callback=level_callback)
                    self.trees.append(tree)
                    raw = walk(raw,
                               {f: getattr(tree, f) for f in WALK_FIELDS},
                               builder.bins_d, builder.n_num_d, lr)
            if round_callback is not None:
                round_callback(self._round_state(r + 1, raw, key, digest))
        self.base = base
        return self

    def _fitted_loss(self):
        """The loss INSTANCE the fit ran with (``fit`` caches it as
        ``self._loss`` — for softmax that carries the inferred n_classes);
        falls back to resolving ``self.loss`` for unfitted estimators."""
        lo = getattr(self, "_loss", None)
        return lo if lo is not None else get_loss(self.loss)

    def predict_raw_device(self, bins) -> jax.Array:
        """Raw (pre-link) ensemble scores as a device Array: [M] additive
        scores for scalar losses, class-last [M, C] softmax logits for
        multiclass.  The stacked [T, max_nodes] tree arrays AND the device
        copy of the feature mask ``n_num`` are built once on first use
        (trees are immutable after fit; re-converting n_num per call was a
        per-batch host->device transfer), so a serving loop pays only the
        jitted walk per batch."""
        if getattr(self, "_stacked", None) is None:
            self._stacked = (stack_trees(self.trees), jnp.asarray(self.n_num))
        stacked, n_num_d = self._stacked
        lo = self._fitted_loss()
        num_steps = max(1, self.config.max_depth)
        if getattr(lo, "is_multiclass", False):
            return _ensemble_predict_multiclass(
                stacked, jnp.asarray(bins), n_num_d,
                jnp.float32(self.learning_rate), jnp.asarray(self.base),
                num_steps=num_steps, n_classes=lo.n_classes)       # [M, C]
        return _ensemble_predict(
            stacked, jnp.asarray(bins), n_num_d,
            jnp.float32(self.learning_rate), jnp.float32(self.base),
            num_steps=num_steps)                                   # [M]

    def predict_proba_device(self, bins) -> jax.Array:
        """Link-applied class probabilities as a device Array: [M] sigmoid
        P(y=1) for the logistic loss, [M, C] softmax for multiclass.
        Rejected for regression losses (identity link, link_id 0) — raw
        scores are not probabilities; use ``predict``/``predict_raw``."""
        lo = self._fitted_loss()
        if lo.link_id == 0:
            raise ValueError(
                f"loss {lo.name!r} is a regression objective (identity "
                "link); it has no class probabilities — use predict / "
                "predict_raw")
        return lo.link(self.predict_raw_device(bins))

    def predict_device(self, bins) -> jax.Array:
        """The estimator's prediction as a device Array: class ids [M]
        int32 for classification losses (argmax over softmax classes; the
        decision threshold raw > 0 <=> p > 0.5 for logistic), raw values
        [M] for regression."""
        raw = self.predict_raw_device(bins)
        lo = self._fitted_loss()
        if getattr(lo, "is_multiclass", False):
            return jnp.argmax(raw, axis=1).astype(jnp.int32)
        if lo.link_id == 1:
            return (raw > 0).astype(jnp.int32)
        return raw

    def sweep(self, val_bins, y_val, **kwargs):
        """Price the ensemble's design space — ``(n_rounds x max_depth x
        min_samples_split x min_child_weight)`` — from this one fit and
        return the cost/quality Pareto front.  Delegates to
        ``core.tuning.sweep`` (see there for the exactness contract:
        n_rounds is exactly retraining, the pruning axes are predict-time
        pruning of every round's trees).  Keyword arguments pass through
        (``space=SweepSpace(...)``, ``train_size=...``)."""
        from repro.core import tuning
        return tuning.sweep(self, val_bins, y_val, **kwargs)

    def predict_raw(self, bins):
        return np.asarray(self.predict_raw_device(bins))

    def predict_proba(self, bins):
        return np.asarray(self.predict_proba_device(bins))

    def predict(self, bins):
        """Batched ensemble prediction; ONE device->host transfer for the
        whole forest (the per-tree transfer loop was the old hot spot).
        Class ids for classification losses, raw values for regression."""
        return np.asarray(self.predict_device(bins))

    def export_stacked(self):
        """Export the fitted ensemble for the serving layer (repro.serve).

        Returns ``(tables, n_num, meta)``:

          * ``tables`` — the stacked ``[T, max_nodes]`` WALK_FIELDS node
            arrays (core.predict.stack_trees — the exact arrays
            ``predict_device`` walks),
          * ``n_num`` — the ``[K]`` numeric-bin-count feature mask,
          * ``meta`` — the serving scalars: ``learning_rate``, ``base``
            (the raw base score F0 — a float, or the [C] log-prior list
            for softmax), ``link_id`` (core.losses serving ABI: 0 identity
            / 1 sigmoid / 2 softmax — the registry currently REJECTS id 2,
            see serve.registry), ``n_classes`` (1 for scalar losses),
            ``num_steps`` (the static walk bound ``max(1,
            config.max_depth)`` that ``predict_device`` uses) and ``loss``
            (the loss name, informational).

        The serve layer packs these tables into the narrow int8/int16
        node-record layout (serve.pack) and concatenates tenants along a
        model axis (serve.registry); routed serving predictions are
        bit-identical to ``predict_device`` on the same rows (tested)."""
        lo = self._fitted_loss()
        multiclass = getattr(lo, "is_multiclass", False)
        base = ([float(b) for b in np.asarray(self.base)] if multiclass
                else float(self.base))
        return (stack_trees(self.trees), np.asarray(self.n_num),
                dict(learning_rate=float(self.learning_rate),
                     base=base, link_id=int(lo.link_id),
                     n_classes=int(lo.n_classes) if multiclass else 1,
                     num_steps=max(1, self.config.max_depth), loss=lo.name))

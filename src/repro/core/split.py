"""Superfast Selection (paper Algorithms 2 & 4), fully vectorised.

Given the per-node histograms ``H[S, K, B, C]`` (one O(M) pass, see
``histogram.py``), a prefix sum along the bin axis makes EVERY candidate
split an O(C) evaluation:

  * numeric  "<= v" : pos = prefix[b],           neg = tot - pos
  * numeric  ">  v" : pos = tot_num - prefix[b], neg = tot - pos
  * categorical "=" : pos = H[b],                neg = tot - pos

Note "<=" and ">" are NOT complements when categorical / missing values are
present (both comparisons evaluate False on them, paper Table 3), which is
why the paper -- and we -- score both directions.  Missing-bin counts only
ever appear on the negative side, implementing "leave missing untouched".

Everything here is branch-free jnp so it runs under jit/vmap/shard_map and
lowers to the Pallas fused kernel (kernels/split_scan.py) on TPU.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import heuristics as H

__all__ = ["SplitDecision", "best_splits", "OP_LE", "OP_GT", "OP_EQ", "NEG_INF"]

OP_LE, OP_GT, OP_EQ = 0, 1, 2
NEG_INF = -3.4e38


class SplitDecision(NamedTuple):
    score: jax.Array     # [S] f32, NEG_INF if no valid split
    feat: jax.Array      # [S] i32
    bin: jax.Array       # [S] i32 threshold/category bin
    op: jax.Array        # [S] i32 in {OP_LE, OP_GT, OP_EQ}
    pos_stats: jax.Array  # [S, C] statistics of the positive child
    neg_stats: jax.Array  # [S, C] statistics of the negative child


def _candidate_stats(hist, n_num, n_cat):
    """Build pos/neg stat tensors for all three candidate families.

    hist: [S, K, B, C];  n_num, n_cat: [K] ints.
    Returns pos, neg of shape [3, S, K, B, C] and validity mask [3, K, B].
    """
    s, k, b, c = hist.shape
    bin_ids = jnp.arange(b, dtype=jnp.int32)
    is_num = bin_ids[None, :] < n_num[:, None]                      # [K,B]
    is_cat = (bin_ids[None, :] >= n_num[:, None]) & (
        bin_ids[None, :] < (n_num + n_cat)[:, None])                # [K,B]

    tot = hist.sum(axis=2, keepdims=True)                           # [S,K,1,C]
    num_hist = hist * is_num[None, :, :, None]
    prefix = jnp.cumsum(num_hist, axis=2)                           # [S,K,B,C]
    tot_num = prefix[:, :, -1:, :]                                  # [S,K,1,C]

    pos_le = prefix
    pos_gt = tot_num - prefix
    pos_eq = hist
    pos = jnp.stack([pos_le, pos_gt, pos_eq])                       # [3,S,K,B,C]
    neg = tot[None] - pos
    # the last numeric candidate "<= max" is degenerate only if there are no
    # categorical/missing counts; generic emptiness masking below handles it.
    valid = jnp.stack([is_num, is_num, is_cat])                     # [3,K,B]
    return pos, neg, valid


@functools.partial(jax.jit, static_argnames=("heuristic", "min_leaf"))
def best_splits(hist: jax.Array, n_num: jax.Array, n_cat: jax.Array, *,
                heuristic: str = "info_gain", min_leaf: int = 1) -> SplitDecision:
    """Select the best split for every node slot (Algorithm 4, batched).

    hist: [S, K, B, C] statistics; for classification C = #classes and the
    example count of a side is ``stats.sum(-1)``; for regression moments the
    count is channel 0.

    Weighted histograms (GOSS-sampled boosting) need NO changes here, which
    is what makes the ``(1-a)/b`` amplification exact rather than a post-hoc
    rescale: every heuristic is a function of the channel sums alone, and a
    weighted channel sum IS the unbiased estimate of the full-data sum, so
    the scored gain is exactly the gain of the estimated full-data split.
    The count channels are then float *weighted* counts: ``min_leaf``
    bounds the estimated full-data example count of each side (LightGBM's
    semantics).

    Newton boosting (core.losses) rides the identical mechanism with
    hessians as the weights: the moment channels become ``(sum h,
    sum h*z, sum h*z^2)`` with ``z = -g/h``, so the "sse" score
    ``(sum h*z)^2 / sum h`` of a side IS the XGBoost split gain
    ``(sum g)^2 / sum h``.

    ``min_child_weight`` is deliberately NOT a candidate mask here: it is a
    post-selection STOPPING rule applied by the tree builder
    (core.tree._chunk_step_impl) to the WINNING split's child counts.
    Masking candidates would make which split wins depend on the value — a
    different candidate is selected when the best one is masked — which
    breaks the Training-Only-Once property that a full tree pruned at
    predict time equals the tree retrained with that value (core/tuning.py
    prices the whole min_child_weight axis from one tree on exactly this
    contract).
    """
    h_fn = H.get(heuristic)
    s, k, b, c = hist.shape
    pos, neg, valid = _candidate_stats(hist, n_num, n_cat)

    moment = heuristic == "sse"
    cnt_pos = pos[..., 0] if moment else pos.sum(-1)                # [3,S,K,B]
    cnt_neg = neg[..., 0] if moment else neg.sum(-1)

    score = h_fn(pos, neg)                                          # [3,S,K,B]
    ok = (valid[:, None]
          & (cnt_pos >= min_leaf) & (cnt_neg >= min_leaf))
    score = jnp.where(ok, score, NEG_INF)

    flat = score.transpose(1, 0, 2, 3).reshape(s, 3 * k * b)        # [S, 3KB]
    best = jnp.argmax(flat, axis=1)
    best_score = jnp.take_along_axis(flat, best[:, None], axis=1)[:, 0]
    op = (best // (k * b)).astype(jnp.int32)
    feat = ((best // b) % k).astype(jnp.int32)
    tbin = (best % b).astype(jnp.int32)

    sel = lambda t: t.transpose(1, 0, 2, 3, 4).reshape(s, 3 * k * b, c)
    pos_stats = jnp.take_along_axis(sel(pos), best[:, None, None], axis=1)[:, 0]
    neg_stats = jnp.take_along_axis(sel(neg), best[:, None, None], axis=1)[:, 0]
    return SplitDecision(best_score, feat, tbin, op, pos_stats, neg_stats)


def best_splits_kernel(hist: jax.Array, n_num: jax.Array, n_cat: jax.Array, *,
                       heuristic: str = "info_gain",
                       min_leaf: int = 1) -> SplitDecision:
    """Kernel-backed selection: the fused Pallas split-scan produces the best
    candidate per (slot, feature); the tiny cross-feature argmax happens
    here.  pos/neg child stats are not materialised (the tree builder derives
    child statistics at the child's own level).

    Ties break as in ``best_splits``' flat op-major argmax: among the
    features whose best score is the maximum, the lowest (op, feature)
    wins.  Each feature's own (op, bin) is already the lowest among its
    maxima, so both backends pick the same split."""
    from repro.kernels import ops as kops
    score_kf, bin_kf, op_kf = kops.split_scan(hist, n_num, n_cat,
                                              heuristic=heuristic,
                                              min_leaf=min_leaf)
    s, k = score_kf.shape
    top = score_kf.max(axis=1, keepdims=True)
    key = jnp.where(score_kf >= top,
                    op_kf * k + jnp.arange(k, dtype=jnp.int32), 3 * k)
    feat = jnp.argmin(key, axis=1).astype(jnp.int32)
    take = lambda a: jnp.take_along_axis(a, feat[:, None], axis=1)[:, 0]
    c = hist.shape[-1]
    zeros = jnp.zeros((s, c), dtype=hist.dtype)
    return SplitDecision(take(score_kf), feat, take(bin_kf),
                         take(op_kf), zeros, zeros)


def evaluate_predicate(xbin: jax.Array, n_num_of_feat: jax.Array,
                       op: jax.Array, tbin: jax.Array) -> jax.Array:
    """Paper Table 3 comparison semantics on bin ids.

    xbin is the example's bin id for the split feature.  Numeric predicates
    are False for categorical/missing bins (their ids are >= n_num); equality
    is False unless the ids match exactly (missing id never equals a
    candidate id).  Broadcasts over leading dims.
    """
    is_numeric = xbin < n_num_of_feat
    le = is_numeric & (xbin <= tbin)
    gt = is_numeric & (xbin > tbin)
    eq = xbin == tbin
    return jnp.where(op == OP_LE, le, jnp.where(op == OP_GT, gt, eq))

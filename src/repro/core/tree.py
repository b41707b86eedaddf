"""Ultrafast Decision Tree (paper Algorithm 5), level-synchronous on TPU.

The paper grows the tree with a node queue and filters per-feature sorted
value lists down the tree.  The TPU-native formulation grows the tree
**breadth-first, one level per step**: every level performs

  1. ONE histogram pass (Superfast statistics collection, O(M*K) scatter
     work) -- chunked over node slots so the [S, K, B, C] working set stays
     bounded (VMEM-sized on TPU).  With sibling subtraction (the default)
     the pass touches only the examples of the SMALLER child of each split
     pair; the co-child's histogram is derived from the cached parent level
     as H_parent - H_small, cutting per-level scatter work >= 2x,
  2. prefix-sum split selection for every active node at once (O(S*K*B*C)),
  3. ONE routing pass updating each example's node assignment (O(M)).

Total work for a balanced tree: O(K * M * depth) = O(K M log M) -- the
paper's complexity, with fixed shapes and `jit`-compiled steps throughout.
Node ids are allocated level-contiguously, so "which slot does example i
update" is just `assign[i] - chunk_start`.

The builder is resumable: the carried state (tree arrays + assignment
vector + level cursor) is checkpointed per level (see checkpoint/), which is
the fault-tolerance story for the distributed build.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import obs
from repro.core import split as split_mod
from repro.core.binning import BinnedTable
from repro.core.histogram import (node_histogram,
                                  node_histogram_smaller_child,
                                  node_histogram_sibling_fused,
                                  class_stats, moment_stats)
from repro.core.split import best_splits, evaluate_predicate, NEG_INF

__all__ = ["TreeConfig", "Tree", "build_tree", "build_trees_batched",
           "BuildState"]


@dataclasses.dataclass(frozen=True)
class TreeConfig:
    max_depth: int = 64               # root has depth 1 (paper convention)
    max_nodes: int = 0                # 0 -> auto (2*M/min_split bounded)
    min_samples_split: int = 2
    min_samples_leaf: int = 1
    heuristic: str = "info_gain"
    task: str = "classification"      # | "regression" (paper label-split)
                                      # | "regression_variance" (beyond-paper)
    n_label_bins: int = 256           # label binning for regression
    hist_backend: str = "segment"
    select_backend: str = "jnp"       # "jnp" | "pallas" (fused split-scan)
    hist_budget_bytes: int = 1 << 28  # bounds the [S,K,B,C] chunk
    chunk_slots: int = 0              # 0 -> auto from hist_budget_bytes
    # Sibling histogram subtraction (LightGBM's trick, level-synchronous):
    # cache the previous level's H[S,K,B,C], scatter only the smaller child
    # of each split pair and derive the co-child as H_parent - H_small --
    # >= 2x less per-level scatter work on balanced trees.  Bit-exact for
    # classification (integer counts in f32 below 2**24 examples); float
    # moment channels agree to accumulation-order tolerance.  The label-split
    # "regression" task recomputes its per-level pseudo-class statistics, so
    # subtraction does not apply there.
    sibling_subtraction: bool = True
    sub_cache_bytes: int = 1 << 28    # skip caching levels wider than this
    # A post-selection STOPPING rule: the node keeps its unconstrained best
    # split, but becomes a leaf when that split's lighter child carries
    # <= min_child_weight (rounded) weight.  It is deliberately NOT a
    # candidate mask (see best_splits' docstring) — masking would change
    # WHICH split wins and break the Training-Only-Once property that
    # core/tuning.py relies on to price the whole min_child_weight axis
    # from one full tree.  Under GOSS weights the count is the amplified
    # estimate of the full-data example count; under Newton boosting
    # (core.losses, where sample_weight = h) it IS the hessian sum, i.e.
    # XGBoost's min_child_weight as a pre-pruning rule.  0.0 disables it;
    # jnp select backend only (the Pallas path drops child stats).
    min_child_weight: float = 0.0


class Tree(NamedTuple):
    """Flat tree arrays (max_nodes slots; n_nodes valid)."""
    feat: jax.Array      # i32, -1 for leaves
    op: jax.Array        # i32 {OP_LE, OP_GT, OP_EQ}, -1 for leaves
    tbin: jax.Array      # i32 threshold / category bin
    score: jax.Array     # f32 split heuristic
    label: jax.Array     # f32 (class id for cls; mean target for regression)
    count: jax.Array     # i32 examples reaching the node
    depth: jax.Array     # i32, root = 1
    left: jax.Array      # i32 child id or -1
    right: jax.Array     # i32 child id or -1
    leaf: jax.Array      # bool
    parent: jax.Array    # i32 parent id, -1 for the root
    n_nodes: int

    @property
    def max_tree_depth(self) -> int:
        d = np.asarray(self.depth[: self.n_nodes])
        return int(d.max()) if d.size else 0


class BuildState(NamedTuple):
    """Per-level resumable build state (fault-tolerance checkpoint unit).

    ``phist`` / ``phist_base`` carry the completed level's full histogram
    chunks (concatenated to [level_width, K, B, C], base node id
    ``phist_base``) so a resumed build can keep using sibling subtraction.
    They are optional: resuming without them just recomputes the first
    level's histograms in full (bit-identical for classification)."""
    arrays: dict
    assign: jax.Array
    level_start: int
    level_end: int
    next_free: int
    depth: int
    phist: jax.Array | None = None
    phist_base: int = -1


def _hist_channels(c: int, task: str, weighted: bool) -> int:
    """Channels of a level step's histograms: the task's ``c`` statistics,
    and for a weighted ``regression_variance`` build one more, the row
    count that tells an empty bin from a rounding residue."""
    return c + int(weighted and task == "regression_variance")


def _auto_chunk_slots(k: int, b: int, c: int, budget: int) -> int:
    s = max(1, budget // max(1, k * b * c * 4))
    return int(min(4096, s))


def _init_arrays(max_nodes: int):
    i32 = lambda fill: jnp.full((max_nodes,), fill, dtype=jnp.int32)
    return dict(
        feat=i32(-1), op=i32(-1), tbin=i32(-1),
        score=jnp.full((max_nodes,), NEG_INF, dtype=jnp.float32),
        label=jnp.zeros((max_nodes,), dtype=jnp.float32),
        count=i32(0), depth=i32(0), left=i32(-1), right=i32(-1),
        leaf=jnp.zeros((max_nodes,), dtype=bool), parent=i32(-1),
    )


# ---------------------------------------------------------------------------
# regression label split (paper Algorithm 6): per-node best binary partition
# of the (binned) labels by SSE; turns regression into 2-class selection.
# ---------------------------------------------------------------------------

def _label_split_thresholds(lhist):
    """lhist: [S, Bl, 3] (count, sum_y, sum_y2) per label bin.

    Returns (tstar [S] best label-bin threshold, mean [S], count [S],
    sse [S] total node SSE)."""
    cnt = jnp.cumsum(lhist[..., 0], axis=1)          # [S,Bl]
    sy = jnp.cumsum(lhist[..., 1], axis=1)
    tot_c = cnt[:, -1:]
    tot_s = sy[:, -1:]
    rc = tot_c - cnt
    rs = tot_s - sy
    score = (sy * sy / jnp.where(cnt > 0, cnt, 1.0)
             + rs * rs / jnp.where(rc > 0, rc, 1.0))
    score = jnp.where((cnt > 0) & (rc > 0), score, NEG_INF)
    tstar = jnp.argmax(score, axis=1).astype(jnp.int32)
    tot_c0 = jnp.where(tot_c[:, 0] > 0, tot_c[:, 0], 1.0)
    mean = tot_s[:, 0] / tot_c0
    sum_y2 = jnp.cumsum(lhist[..., 2], axis=1)[:, -1]
    sse = sum_y2 - tot_s[:, 0] * tot_s[:, 0] / tot_c0
    return tstar, mean, tot_c[:, 0], sse


# ---------------------------------------------------------------------------
# one chunk of one level: histogram -> Superfast Selection -> node updates
# ---------------------------------------------------------------------------

_CHUNK_STEP_STATICS = ("num_slots", "n_bins", "heuristic", "task",
                       "min_samples_split", "min_samples_leaf", "max_depth",
                       "max_nodes", "hist_backend", "select_backend",
                       "n_label_bins", "data_axes", "model_axis",
                       "slot_scatter", "use_sub", "want_hist", "weighted",
                       "min_child_weight")


def _chunk_step_impl(bins, stats, lbins, y, assign, arrays, phist_pairs, n_num,
                n_cat, chunk_start, chunk_n, next_free, depth, weights=None, *,
                num_slots, n_bins, heuristic, task, min_samples_split,
                min_samples_leaf, max_depth, max_nodes, hist_backend,
                select_backend, n_label_bins, data_axes=(), model_axis=None,
                slot_scatter=False, use_sub=False, want_hist=False,
                weighted=False, min_child_weight=0.0):
    """Process node slots [chunk_start, chunk_start+chunk_n).

    Returns (arrays, counts, hist): ``counts`` is int32[2], the children
    the chunk allocated and the rows its feature histogram took in (the
    chunk's rows, or under subtraction those of each pair's smaller child;
    summed over data shards).  All shapes static; chunk_start / chunk_n /
    next_free / depth are dynamic scalars so one compilation serves the
    whole build.

    ``use_sub`` enables sibling subtraction: ``phist_pairs`` holds the
    parent histogram of sibling pair ``j = slot // 2`` ([num_slots//2, K, B,
    C], gathered by ``_parent_rows``), statistics are scattered only for
    the smaller child of each pair, and the co-child's histogram is
    ``H_parent - H_small`` -- branch-free under jit.  On the single-shard
    pallas backend the derivation is FUSED into the histogram kernel's
    epilogue (node_histogram_sibling_fused); under ``slot_scatter`` the
    packed pair axis is reduce_scattered and ``phist_pairs`` arrives
    sharded over (pair, feature), so both halvings compose.  ``want_hist``
    returns the chunk's full histogram so the build loop can cache it for
    the next level (a scalar 0 otherwise).

    ``weighted`` + ``weights`` ([M] f32) switch on the per-example weight
    channel: histograms accumulate ``w[i] * stats[i]`` (in-kernel on the
    pallas backend; a ``regression_variance`` step weights its moment rows
    itself and adds each bin's row count, ``_hist_channels``), so every
    count / label / purity statistic below is the
    GOSS-amplified unbiased estimate of its full-data value, and
    ``min_samples_split`` / ``min_samples_leaf`` bound the estimated
    full-data counts.  Under data parallelism the weights arrive sharded
    like every other example row and multiply BEFORE the per-level
    collective, so the sharded GOSS loop (core.distributed) weights for
    free.  Float-accumulated weighted counts are rounded to
    the NEAREST int before the int32 node-count cast, so an estimate of
    2.9999997 does not spuriously trip ``min_samples_split=3`` (truncation
    was the old behaviour).  The smaller-child choice stays on RAW routed
    rows (scatter cost is rows, not weight).
    """
    s = num_slots
    k_local = bins.shape[1]
    scatter_on = bool(slot_scatter and data_axes)
    # subtraction and slot_scatter COMPOSE: the packed [s/2] smaller-child
    # histogram is reduce_scattered over the data axes and each shard
    # derives its co-child slots from its pair-shard of the parent cache
    # (phist_pairs arrives sharded over the pair axis in that mode).
    assert not use_sub or task in ("classification", "regression_variance")

    def reduce_data(x):
        """Data-parallel histogram reduction.

        slot_scatter (perf iteration, EXPERIMENTS.md §Perf/udt): instead of
        all-reducing the full [S, K, B, C] histogram to every data shard and
        selecting redundantly, reduce_scatter it along the SLOT axis — half
        the collective bytes of a ring all-reduce and 1/dsize of the
        selection compute per device; the per-slot decisions (a few scalars
        per node) are all-gathered afterwards by ``regather``."""
        if scatter_on:
            for ax in data_axes:
                x = jax.lax.psum_scatter(x, ax, scatter_dimension=0,
                                         tiled=True)
            return x
        for ax in data_axes:
            x = jax.lax.psum(x, ax)
        return x

    def regather(tree):
        """Reassemble per-slot-shard results back to the full slot axis."""
        if not scatter_on:
            return tree

        def g(a):
            for ax in reversed(data_axes):
                a = jax.lax.all_gather(a, ax, axis=0, tiled=True)
            return a

        return jax.tree.map(g, tree)

    # min_child_weight is a post-selection STOPPING rule (see best_splits'
    # docstring): the winning split's smaller-child count decides whether
    # the node splits at all.  ``child_min_count`` extracts that count —
    # rounded to the nearest int, the SAME scale Tree.count records — so
    # the builder's stop test and the predict-time pruning walk
    # (core.predict / core.tuning) compare identical values, which is what
    # makes the Training-Only-Once pricing of the mcw axis exact.
    moment_task = task in ("regression", "regression_variance")

    def child_min_count(dec):
        cp = dec.pos_stats[:, 0] if moment_task else dec.pos_stats.sum(-1)
        cn = dec.neg_stats[:, 0] if moment_task else dec.neg_stats.sum(-1)
        return jnp.minimum(jnp.round(cp), jnp.round(cn))            # [S] f32

    def select(hist, n_num_, n_cat_, *, heuristic, min_leaf):
        if select_backend == "pallas":
            dec = split_mod.best_splits_kernel(hist, n_num_, n_cat_,
                                               heuristic=heuristic,
                                               min_leaf=min_leaf)
        else:
            dec = best_splits(hist, n_num_, n_cat_, heuristic=heuristic,
                              min_leaf=min_leaf)
        if model_axis is None:
            return dec, child_min_count(dec)
        # feature-parallel: each shard picked its best LOCAL feature; a tiny
        # all-gather of [S] tuples + argmax yields the global winner.
        # Tie-breaking must match the single-device flat argmax exactly
        # (max score, then lowest global candidate index op-major) so the
        # distributed build reproduces the local tree bit-for-bit —
        # histogram counts are integers, hence psum-order independent.
        my = jax.lax.axis_index(model_axis)
        n_shards = jax.lax.axis_size(model_axis)
        k_tot = k_local * n_shards
        feat_g = dec.feat + my * k_local
        flat_idx = (dec.op * k_tot + feat_g) * n_bins + dec.bin   # global order
        # row 5 carries the LOCAL winner's smaller-child count so the
        # global pick also yields the winning shard's stop-rule statistic
        # (dec.pos/neg_stats stay local — only the scalar count is needed).
        cand = jnp.stack([dec.score,
                          feat_g.astype(jnp.float32),
                          dec.bin.astype(jnp.float32),
                          dec.op.astype(jnp.float32),
                          flat_idx.astype(jnp.float32),
                          child_min_count(dec)])                  # [6, S]
        allc = jax.lax.all_gather(cand, model_axis)               # [P, 6, S]
        best_score = allc[:, 0].max(axis=0)                       # [S]
        is_max = allc[:, 0] >= best_score[None]
        key = jnp.where(is_max, allc[:, 4], jnp.float32(3e38))
        win = jnp.argmin(key, axis=0)                             # [S]
        pick = lambda j: jnp.take_along_axis(allc[:, j], win[None], axis=0)[0]
        return split_mod.SplitDecision(
            pick(0), pick(1).astype(jnp.int32), pick(2).astype(jnp.int32),
            pick(3).astype(jnp.int32), dec.pos_stats, dec.neg_stats), pick(5)
    # the named scopes put each op's device time down to a part of the
    # step in a profiler trace (its ``tf_op`` path); they change no op
    histogram_scope = jax.named_scope("level.histogram")
    select_scope = jax.named_scope("level.select")
    update_scope = jax.named_scope("level.update")
    with histogram_scope:
        slot_of_node = assign - chunk_start
        slot = jnp.where((slot_of_node >= 0) & (slot_of_node < chunk_n),
                         slot_of_node, -1)
    slot_ids = jnp.arange(s, dtype=jnp.int32)
    in_chunk = slot_ids < chunk_n
    node_ids = jnp.where(in_chunk, chunk_start + slot_ids, max_nodes)

    # a weighted regression_variance step weights its own rows (below)
    w = weights if weighted and task != "regression_variance" else None

    def build_hist(stats_rows):
        """One level-chunk histogram: full scatter, or smaller-child scatter
        plus sibling subtraction when the parent cache is available.  Also
        returns the rows it histograms, over every data shard."""
        if not use_sub:
            rows = (slot >= 0).sum(dtype=jnp.int32)
            for ax in data_axes:
                rows = jax.lax.psum(rows, ax)
            return reduce_data(node_histogram(
                bins, stats_rows, slot, num_slots=s, n_bins=n_bins,
                backend=hist_backend, weights=w)), rows
        # per-node routed-example counts decide which child to scatter; the
        # psum makes the argmin globally consistent across data shards.
        cnt = jax.ops.segment_sum(jnp.ones_like(slot, dtype=jnp.float32),
                                  slot, num_segments=s)
        for ax in data_axes:
            cnt = jax.lax.psum(cnt, ax)
        small_is_left = cnt[0::2] <= cnt[1::2]               # [s/2]
        compute = jnp.stack([small_is_left, ~small_is_left],
                            axis=1).reshape(s)
        rows = jnp.where(compute, cnt, 0.0).sum().astype(jnp.int32)
        if not data_axes:
            # single shard: on pallas the subtraction and the pair
            # interleave run in the kernel's epilogue, so the derived
            # sibling never materialises in HBM and no jnp derivation op
            # is emitted; other backends take the same function's jnp
            # subtract+interleave fallback.  Slots past chunk_n gather
            # garbage parent rows; every downstream write drops them
            # (node_ids == max_nodes there).
            return node_histogram_sibling_fused(
                bins, stats_rows, slot, compute, phist_pairs, num_slots=s,
                n_bins=n_bins, backend=hist_backend, weights=w), rows
        h_small = node_histogram_smaller_child(
            bins, stats_rows, slot, compute, num_slots=s, n_bins=n_bins,
            backend=hist_backend, weights=w)                 # [s/2,K,B,C]
        if scatter_on:
            # composed mode: reduce_scatter the PACKED pair axis -- half
            # the collective bytes of the dense slot_scatter AND half the
            # scatter work -- then derive co-children locally from the
            # pair-sharded parent rows.  My pairs are the tiled block at
            # the flattened data-shard index (psum_scatter tiling order).
            h_small = reduce_data(h_small)                   # [s/2/d,...]
            per = h_small.shape[0]
            idx = jnp.int32(0)
            for ax in data_axes:
                idx = idx * jax.lax.axis_size(ax) + jax.lax.axis_index(ax)
            sl = jax.lax.dynamic_slice(small_is_left, (idx * per,), (per,))
        else:
            h_small = reduce_data(h_small)                   # psum [s/2,...]
            sl = small_is_left
        # slots past chunk_n have no parent row; their lanes carry garbage
        # that every downstream write drops (node_ids == max_nodes there).
        h_der = phist_pairs - h_small
        slb = sl[:, None, None, None]
        return jnp.stack([jnp.where(slb, h_small, h_der),
                          jnp.where(slb, h_der, h_small)],
                         axis=1).reshape(2 * h_small.shape[0], k_local,
                                         n_bins, stats_rows.shape[-1]), rows

    if task == "regression":
        # Algorithm 6: per-node label split -> per-example pseudo class.
        with histogram_scope:
            lhist = reduce_data(node_histogram(
                lbins[:, None], moment_stats(y), slot, num_slots=s,
                n_bins=n_label_bins, backend=hist_backend)[:, 0])   # [S,Bl,3]
            tstar, mean, count_f, sse = _label_split_thresholds(lhist)
            tstar, label, count_f, sse = regather((tstar, mean, count_f,
                                                   sse))
            pseudo = (lbins <= tstar[jnp.clip(slot, 0, s - 1)]).astype(
                jnp.int32)
            stats = class_stats(pseudo, 2)
        with update_scope:
            count = jnp.round(count_f).astype(jnp.int32)
            pure = sse <= 1e-10 * jnp.maximum(count_f, 1.0)
        with histogram_scope:
            hist, rows = build_hist(stats)
        with select_scope:
            dec, mc = select(hist, n_num, n_cat, heuristic=heuristic,
                             min_leaf=min_samples_leaf)
            dec, mc = regather((dec, mc))
    elif task == "regression_variance":
        with histogram_scope:
            if weighted:
                # weighted moments (w, wy, wy^2) and the row count.  A bin
                # of a derived sibling (parent - smaller child) that holds
                # none of its rows keeps a float rounding residue in each
                # weighted channel, but its count is exact (below 2**24
                # rows, as subtraction requires) and reads 0, so the bin
                # is cleared: an empty side then sums to exactly 0.
                hist, rows = build_hist(jnp.concatenate(
                    [moment_stats(y) * weights[:, None],
                     jnp.ones_like(y)[:, None]], axis=1))
                hist = jnp.where(hist[..., 3:] > 0, hist, 0.0)
            else:
                hist, rows = build_hist(moment_stats(y))
        with update_scope:
            tot = hist[:, 0].sum(axis=1)                            # [S,3]
            count_f = tot[:, 0]
            safe = jnp.where(count_f > 0, count_f, 1.0)
            label = tot[:, 1] / safe
            count = jnp.round(count_f).astype(jnp.int32)
            pure = ((tot[:, 2] - tot[:, 1] ** 2 / safe)
                    <= 1e-10 * jnp.maximum(count_f, 1.0))
        with select_scope:
            dec, mc = select(hist, n_num, n_cat, heuristic="sse",
                             min_leaf=min_samples_leaf)
            count, label, pure, dec, mc = regather((count, label, pure, dec,
                                                    mc))
    else:
        with histogram_scope:
            hist, rows = build_hist(stats)
        with update_scope:
            tot = hist[:, 0].sum(axis=1)                            # [S,C]
            count = jnp.round(tot.sum(-1)).astype(jnp.int32)
            label = jnp.argmax(tot, axis=-1).astype(jnp.float32)
            pure = tot.max(-1) == tot.sum(-1)
        with select_scope:
            dec, mc = select(hist, n_num, n_cat, heuristic=heuristic,
                             min_leaf=min_samples_leaf)
            count, label, pure, dec, mc = regather((count, label, pure, dec,
                                                    mc))

    with update_scope:
        no_split = dec.score <= NEG_INF / 2
        is_leaf = (in_chunk & (pure | no_split
                               | (count < min_samples_split)
                               | (depth >= max_depth)))
        if min_child_weight:
            # stopping rule, not a candidate mask: the node keeps its
            # unconstrained best split but becomes a leaf when that split's
            # lighter child carries <= min_child_weight (rounded) weight.
            # mc is garbage where no_split holds — already a leaf there.
            is_leaf = is_leaf | (in_chunk & (mc <= min_child_weight))
        wants_split = in_chunk & ~is_leaf

        # allocate children; respect the node budget (overflow -> leaf)
        offs = jnp.cumsum(wants_split.astype(jnp.int32)) - 1
        left = next_free + 2 * offs
        right = left + 1
        fits = right < max_nodes
        is_leaf = is_leaf | (wants_split & ~fits)
        wants_split = wants_split & fits
        counts = jnp.stack([2 * wants_split.sum(dtype=jnp.int32), rows])

        left = jnp.where(wants_split, left, -1)
        right = jnp.where(wants_split, right, -1)

        def upd(name, vals, ids=node_ids):
            arrays[name] = arrays[name].at[ids].set(vals, mode="drop")

        # child -> parent back-pointers: next level's sibling subtraction
        # gathers each pair's parent histogram row through these.
        for child in (left, right):
            upd("parent", node_ids,
                ids=jnp.where(wants_split, child, max_nodes))

        upd("feat", jnp.where(wants_split, dec.feat, -1))
        upd("op", jnp.where(wants_split, dec.op, -1))
        upd("tbin", jnp.where(wants_split, dec.bin, -1))
        upd("score", jnp.where(wants_split, dec.score, NEG_INF))
        upd("label", label)
        upd("count", count)
        upd("depth", jnp.full((s,), depth, dtype=jnp.int32))
        upd("left", left)
        upd("right", right)
        upd("leaf", is_leaf)
        hist_out = hist if want_hist else jnp.zeros((), dtype=jnp.float32)
        return arrays, counts, hist_out


# the jitted form every single-tree builder calls; the batched (multiclass)
# step below and the sharded variants (core.distributed) re-enter the SAME
# traced body through _chunk_step_impl, so the level-step semantics cannot
# drift between the three entry points.
_chunk_step = functools.partial(
    jax.jit, static_argnames=_CHUNK_STEP_STATICS)(_chunk_step_impl)


@functools.partial(jax.jit, static_argnames=_CHUNK_STEP_STATICS)
def _chunk_step_classes(bins, stats, lbins, y, assign, arrays, phist_pairs,
                        n_num, n_cat, chunk_start, chunk_n, next_free, depth,
                        weights=None, *, num_slots, n_bins, heuristic, task,
                        min_samples_split, min_samples_leaf, max_depth,
                        max_nodes, hist_backend, select_backend, n_label_bins,
                        data_axes=(), model_axis=None, slot_scatter=False,
                        use_sub=False, want_hist=False, weighted=False,
                        min_child_weight=0.0):
    """The multiclass level-chunk step: ONE vmap of ``_chunk_step_impl``
    over a leading class axis, so the K class-trees of a boosting round
    cost one compilation and one batched device step per level chunk.

    Batched (leading ``[C]``/``[C, ...]`` axis): the targets ``y``, the
    example assignments, the tree arrays, the parent histogram pairs, the
    weights, and the ``chunk_start`` / ``chunk_n`` / ``next_free`` cursor
    vectors (each class's frontier advances at its own width).  Shared
    across classes (closed over, no batch axis): the binned table, the
    feature vectors, and the scalar ``depth`` — the per-class builds run
    the SAME level in lockstep, which is what keeps the static
    ``use_sub`` / ``want_hist`` flags common to every lane.  Classes whose
    frontier is exhausted (or shorter than the widest class's) ride along
    with ``chunk_n = 0`` lanes: every slot is out-of-chunk there, all
    writes drop, and both counts are 0 — inert by the same mechanism
    that drops past-the-end slots in the single-tree step."""
    kw = dict(num_slots=num_slots, n_bins=n_bins, heuristic=heuristic,
              task=task, min_samples_split=min_samples_split,
              min_samples_leaf=min_samples_leaf, max_depth=max_depth,
              max_nodes=max_nodes, hist_backend=hist_backend,
              select_backend=select_backend, n_label_bins=n_label_bins,
              data_axes=data_axes, model_axis=model_axis,
              slot_scatter=slot_scatter, use_sub=use_sub,
              want_hist=want_hist, weighted=weighted,
              min_child_weight=min_child_weight)
    if weighted:
        def one(yv, a, ar, pp, cs, cn, nf, w):
            return _chunk_step_impl(bins, stats, lbins, yv, a, ar, pp, n_num,
                                    n_cat, cs, cn, nf, depth, w, **kw)
        return jax.vmap(one)(y, assign, arrays, phist_pairs, chunk_start,
                             chunk_n, next_free, weights)

    def one(yv, a, ar, pp, cs, cn, nf):
        return _chunk_step_impl(bins, stats, lbins, yv, a, ar, pp, n_num,
                                n_cat, cs, cn, nf, depth, None, **kw)
    return jax.vmap(one)(y, assign, arrays, phist_pairs, chunk_start,
                         chunk_n, next_free)


def _node_predicate(bins, f, op, tbin, n_num, model_axis):
    """Per-example split-predicate evaluation, feature-parallel when the
    bins are sharded over ``model_axis``: only the shard owning each
    example's winning feature ``f`` evaluates, and one bit per example is
    psum'd across the model axis (the paper-technique collective that the
    dry-run measures).  The ONE copy of this logic — the level router
    below and the sharded ensemble walk (core.distributed
    .make_sharded_walk) both descend through it, so their routing
    semantics cannot drift apart."""
    if model_axis is None:
        xb = jnp.take_along_axis(bins, f[:, None], axis=1)[:, 0]
        return evaluate_predicate(xb, n_num[f], op, tbin)
    k_local = bins.shape[1]
    my = jax.lax.axis_index(model_axis)
    mine = (f // k_local) == my
    f_l = jnp.where(mine, f % k_local, 0)
    xb = jnp.take_along_axis(bins, f_l[:, None], axis=1)[:, 0]
    local = evaluate_predicate(xb, n_num[f_l], op, tbin) & mine
    return jax.lax.psum(local.astype(jnp.int32), model_axis) > 0


@functools.partial(jax.jit, static_argnames=("model_axis",))
def _route_step(bins, assign, arrays, n_num, level_start, level_end, *,
                model_axis=None):
    with jax.named_scope("level.route"):
        node = assign
        left = arrays["left"][node]
        active = (node >= level_start) & (node < level_end) & (left >= 0)
        f = jnp.maximum(arrays["feat"][node], 0)
        pos = _node_predicate(bins, f, arrays["op"][node],
                              arrays["tbin"][node], n_num, model_axis)
        nxt = jnp.where(pos, left, arrays["right"][node])
        return jnp.where(active, nxt, node)


@functools.partial(jax.jit, static_argnames=("model_axis",))
def _route_step_classes(bins, assign, arrays, n_num, level_start, level_end,
                        *, model_axis=None):
    """Batched router for the multiclass build: one vmap of the single-tree
    routing step over the class axis of (assign [C, M], tree arrays
    [C, ...], level cursors [C]); the bins and feature vectors are shared.
    Each class routes through ITS OWN tree's split records, so the class
    frontiers diverge structurally while staying in depth lockstep."""
    def one(a, ar, s, e):
        return _route_step(bins, a, ar, n_num, s, e, model_axis=model_axis)
    return jax.vmap(one)(assign, arrays, level_start, level_end)


# ---------------------------------------------------------------------------
# host-driven level loop (paper Algorithm 5's queue, one level per tick)
# ---------------------------------------------------------------------------

def _prepare(table: BinnedTable, y, config: TreeConfig,
             n_classes: int | None):
    """Input prep shared by the local and distributed builders.

    ``table.bins`` / ``y`` may be numpy OR jax arrays; the
    ``regression_variance`` task does no host work (no label binning; a
    host ``y`` goes to the device with the caller's other inputs), which
    is what lets the boosted-ensemble loop in core.forest hand residuals
    in as device Arrays tree after tree.  The two paper tasks keep their
    host-side prep (classification needs the class count, label-split
    regression pre-bins the labels once)."""
    bins = table.bins
    m, k = bins.shape
    if config.task == "regression_variance":
        yv = (jnp.asarray(y, dtype=jnp.float32) if isinstance(y, jax.Array)
              else np.asarray(y, dtype=np.float32))
        # stats / lbins are dead operands for this task (the moment rows are
        # formed from yv inside the level step); zeros keep the jit
        # signature uniform and cost one deferred fill each.
        return (bins, jnp.zeros((m, 3), jnp.float32),
                jnp.zeros((m,), jnp.int32), yv, 3, 1)
    if config.task == "classification":
        y = np.asarray(y)
        c = int(n_classes if n_classes is not None else int(y.max()) + 1)
        stats = np.eye(c, dtype=np.float32)[np.asarray(y, dtype=np.int64)]
        lbins = np.zeros((m,), dtype=np.int32)
        yv = np.zeros((m,), dtype=np.float32)
        n_label_bins = 1
    else:
        yv = np.asarray(y, dtype=np.float32)
        c = 2
        stats = np.zeros((m, c), dtype=np.float32)
        # bin the labels once (the paper pre-sorts them once) for Alg. 6
        yy = np.asarray(y, dtype=np.float64)
        uniq = np.unique(yy)
        if uniq.size > config.n_label_bins:
            edges = np.unique(np.quantile(
                yy, np.linspace(0, 1, config.n_label_bins), method="nearest"))
        else:
            edges = uniq
        lb = np.minimum(np.searchsorted(edges, yy, side="left"),
                        len(edges) - 1)
        lbins = lb.astype(np.int32)
        n_label_bins = int(len(edges))
    return bins, stats, lbins, yv, c, n_label_bins


def _subtract_eligible(config: TreeConfig, m: int,
                       weighted: bool = False) -> bool:
    """Single source of truth for the sibling-subtraction gate (the local
    and distributed builders must agree or their bit-identical-tree
    contract breaks).  The label-split "regression" task recomputes its
    pseudo-class statistics every level, so the parent cache is invalid;
    past 2**24 examples float32 integer-count accumulation can round, so
    the derived sibling would no longer be bit-identical to a recompute.

    Weighted builds (GOSS): every channel becomes a float weighted sum, so
    a derived sibling is only accumulation-order close to a recompute.
    ``regression_variance`` — the boosted-ensemble task — already carries
    that tolerance contract on its float moment channels, so sampling
    composes with subtraction there (the smaller-child scatter then runs
    over the sampled subset only).  Weighted *classification* would
    silently downgrade its bit-exactness contract, so subtraction is
    disabled for it instead."""
    if weighted and config.task != "regression_variance":
        return False
    return (config.sibling_subtraction and config.task != "regression"
            and m < 1 << 24)


def _take_rows(x, idx, axis=0):
    """``x[idx]`` along ``axis`` (an index array or a slice).

    On a mesh with ``Explicit`` axes (the ``jax.make_mesh`` default) the
    level caches arrive sharded along their row axis, and a row gather or
    an uneven row slice has no unambiguous output sharding.  The result is
    therefore replicated along ``axis`` and keeps ``x``'s sharding on every
    other axis (the sharded level step's in_specs reshard it).  Off a mesh
    this is plain indexing."""
    sh = jax.typeof(x).sharding
    spec = list(sh.spec) + [None] * (x.ndim - len(sh.spec))
    spec[axis] = None
    key = (slice(None),) * axis + (idx,)
    return x.at[key].get(out_sharding=NamedSharding(sh.mesh, P(*spec)))


def _parent_rows(parent, cache, cs, s):
    """Gather each sibling pair's parent histogram row for one level chunk.

    ``cache`` is (base_node_id, H[level_width, K, B, C]) of the previous
    level.  Pairs past the chunk's valid region gather garbage rows; every
    consumer of those slots drops its writes, so no masking is needed."""
    base, hist = cache
    pid = jnp.take(parent,
                   jnp.int32(cs) + jnp.arange(0, s, 2, dtype=jnp.int32),
                   mode="fill", fill_value=-1)
    idx = jnp.clip(pid - base, 0, hist.shape[0] - 1)
    return _take_rows(hist, idx)


def _grow(step, route, arrays, assign, s_cap, max_nodes, level_callback,
          cursors=(0, 1, 1, 1), subtract=None, cache=None,
          max_depth=1 << 30):
    """The level-synchronous queue (paper Algorithm 5), host-driven.

    ``step(arrays, assign, cs, cn, next_free, depth, num_slots, phist_pairs,
    use_sub, want_hist)`` returns (arrays, counts, hist), ``counts`` the
    chunk's children and histogram rows; ``route(assign, arrays, start,
    end)`` returns the new per-example node assignment.
    ``cursors`` resumes a checkpointed build from the start of a level
    (fault tolerance).

    ``subtract = (row_bytes, budget)`` enables sibling subtraction:
    each level's full histogram is cached (unless wider than
    ``budget / row_bytes`` slots) and the next level scatters only the
    smaller child of each split pair.  ``cache`` seeds the parent-level
    histogram when resuming.

    Each level is a ``udt.level`` span (``depth``, ``width``, ``slots``),
    each read of a chunk's counts a ``udt.sync`` span, and after it a
    zero-length ``udt.hist`` span (``rows``) raises ``hist_rows``."""
    level_start, level_end, next_free, depth = cursors
    while level_start < level_end:
        width = level_end - level_start
        # slot count adapts to the frontier (bounded by the VMEM/HBM
        # budget); jit caches one compilation per power-of-two size.
        s = min(s_cap, max(16, 1 << (width - 1).bit_length()))
        # children are allocated in sibling pairs at (level_start + 2j,
        # level_start + 2j + 1); with even s and chunks starting at
        # level_start + i*s, pairs never straddle a chunk.  An odd s_cap
        # (user chunk_slots / unlucky auto budget) would misalign them, so
        # round down; only the root level (width 1, no parent) and a
        # degenerate s == 1 fall outside the pair layout.
        if subtract is not None and s % 2 and s > 1:
            s -= 1
        with obs.span("udt.level", depth=depth, width=width, slots=s):
            paired = s % 2 == 0
            use = (subtract is not None and cache is not None and paired
                   and width % 2 == 0)
            # depth >= max_depth forces every node here to a leaf, so this
            # level has no children and caching its histogram would be
            # wasted
            want = (subtract is not None and paired and depth < max_depth
                    and width * subtract[0] <= subtract[1])
            hists = []
            for cs in range(level_start, level_end, s):
                cn = min(s, level_end - cs)
                pp = (_parent_rows(arrays["parent"], cache, cs, s) if use
                      else None)
                arrays, counts, h = step(arrays, assign, cs, cn, next_free,
                                         depth, s, pp, use, want)
                with obs.counted("udt.sync", "syncs"):
                    n_children, rows = (int(v) for v in np.asarray(counts))
                next_free += n_children
                with obs.counted("udt.hist", "hist_rows", rows, rows=rows):
                    pass
                if want:
                    hists.append(h)
            cache = ((level_start,
                      _take_rows(jnp.concatenate(hists, axis=0),
                                 slice(0, width)))
                     if want else None)
            assign = route(assign, arrays, level_start, level_end)
            level_start, level_end = level_end, next_free
            depth += 1
            if level_callback is not None:
                level_callback(BuildState(
                    arrays, assign, level_start, level_end, next_free, depth,
                    cache[1] if cache is not None else None,
                    cache[0] if cache is not None else -1))
    return arrays, next_free


def _parent_rows_batched(parent, cache, cs, s):
    """Per-class parent histogram rows: ``cache`` is (base [C], H[C, W, K,
    B, C']) of the previous level; ``cs`` is the per-class chunk start.
    One vmap of ``_parent_rows`` over the class axis."""
    base, hist = cache
    return jax.vmap(lambda p, b, h, c: _parent_rows(p, (b, h), c, s))(
        parent, jnp.asarray(base, dtype=jnp.int32), hist,
        jnp.asarray(cs, dtype=jnp.int32))


def _grow_batched(step, route, arrays, assign, s_cap, max_nodes,
                  level_callback, n_stack, subtract=None, max_depth=1 << 30):
    """The level-synchronous queue for ``n_stack`` trees grown in DEPTH
    LOCKSTEP through one batched step (the multiclass boosting round).

    Identical control flow to ``_grow`` with the scalar level cursors
    replaced by per-class ``[C]`` vectors: every class is at the same
    depth, but each has its own frontier ``[level_start[c], level_end[c])``
    and node allocator ``next_free[c]``.  The chunk count per level is
    driven by the WIDEST class; narrower (or finished) classes ride the
    extra chunks with ``chunk_n = 0`` inert lanes.  Chunking is transparent
    to the built trees (per-slot selection results and the sequential
    pair allocation are independent of the chunk size), so each lane's
    tree is bit-identical to the tree ``_grow`` would build for that class
    alone — the parity contract tests/test_softmax.py asserts.

    ``step(arrays, assign, cs, cn, next_free, depth, num_slots, phist_pairs,
    use_sub, want_hist)`` takes ``cs`` / ``cn`` / ``next_free`` as [C] int
    vectors and returns (arrays, counts [C, 2], hist [C, s, K, B, C']);
    ``route(assign, arrays, start, end)`` routes every class.
    ``level_callback`` (optional) receives a BuildState whose cursor fields
    are [C] numpy vectors and whose array fields carry the class axis.

    Sibling subtraction: past the root every class's level width is even
    (children are allocated in sibling pairs) or zero, so the static
    ``use_sub`` / ``want_hist`` flags are shared across classes; the
    cached level histogram is padded to the widest class and per-class
    garbage rows are dropped by the same out-of-chunk mechanism as the
    single-tree build.  Spans as in ``_grow``; ``width`` is the widest
    class's."""
    level_start = np.zeros(n_stack, dtype=np.int64)
    level_end = np.ones(n_stack, dtype=np.int64)
    next_free = np.ones(n_stack, dtype=np.int64)
    depth = 1
    cache = None
    while (level_start < level_end).any():
        widths = level_end - level_start
        wmax = int(widths.max())
        s = min(s_cap, max(16, 1 << (wmax - 1).bit_length()))
        if subtract is not None and s % 2 and s > 1:
            s -= 1
        with obs.span("udt.level", depth=depth, width=wmax, slots=s):
            paired = s % 2 == 0
            use = (subtract is not None and cache is not None and paired
                   and bool((widths % 2 == 0).all()))
            want = (subtract is not None and paired and depth < max_depth
                    and wmax * subtract[0] <= subtract[1])
            hists = []
            for i in range(0, wmax, s):
                cs = level_start + i
                cn = np.clip(level_end - cs, 0, min(s, wmax - i))
                pp = (_parent_rows_batched(arrays["parent"], cache, cs, s)
                      if use else None)
                arrays, counts, h = step(arrays, assign, cs, cn, next_free,
                                         depth, s, pp, use, want)
                with obs.counted("udt.sync", "syncs"):
                    counts = np.asarray(counts, dtype=np.int64)
                next_free = next_free + counts[:, 0]
                rows = int(counts[:, 1].sum())
                with obs.counted("udt.hist", "hist_rows", rows, rows=rows):
                    pass
                if want:
                    hists.append(h)
            cache = ((level_start.copy(),
                      _take_rows(jnp.concatenate(hists, axis=1),
                                 slice(0, wmax), axis=1))
                     if want else None)
            assign = route(assign, arrays, level_start, level_end)
            level_start, level_end = level_end, next_free.copy()
            depth += 1
            if level_callback is not None:
                level_callback(BuildState(
                    arrays, assign, level_start, level_end, next_free, depth,
                    cache[1] if cache is not None else None,
                    cache[0] if cache is not None else -1))
    return arrays, next_free


@obs.spanned("udt.tree")
def build_trees_batched(table: BinnedTable, z, config: TreeConfig,
                        sample_weight=None, assign0=None,
                        level_callback=None):
    """Build one ``regression_variance`` tree per row of ``z`` [C, M]
    through ONE vmapped level-synchronous build — the multiclass boosting
    round's K class-trees for ~the cost (and exactly the compile count) of
    a single tree.

    ``z`` holds each class's Newton target on the SHARED binned table;
    ``sample_weight`` (optional [C, M]) its per-class hessian channel;
    ``assign0`` (optional [C, M] or [M] int32, -1 = inert row) seeds the
    example assignment — the GOSS selection mask, shared or per-class.
    Returns ``(trees, arrays)``: the per-class ``Tree`` views and the
    underlying stacked ``[C, max_nodes]`` arrays (the boosting loop feeds
    those straight into the vmapped score-update walk without restacking).

    Each returned tree is bit-identical to ``build_tree(table, z[c], ...,
    sample_weight=sample_weight[c])`` run per class with the same chunk
    size (see ``_grow_batched``); the mesh twin is
    ``core.distributed.DistributedBuilder.build_batched``."""
    if config.task != "regression_variance":
        raise ValueError("build_trees_batched fits 'regression_variance' "
                         f"trees (the boosting round task); got task="
                         f"{config.task!r}")
    if config.min_child_weight and config.select_backend == "pallas":
        raise ValueError("min_child_weight needs select_backend='jnp' (the "
                         "fused split-scan kernel has no weight floor)")
    n = obs.host_bytes(table.bins, z, sample_weight, assign0, table.n_num,
                       table.n_cat)
    with obs.counted("udt.put", "h2d_bytes", n, bytes=n):
        bins = jnp.asarray(table.bins)
        z = jnp.asarray(z, dtype=jnp.float32)
        weights = (jnp.asarray(sample_weight, dtype=jnp.float32)
                   if sample_weight is not None else None)
        n_num = jnp.asarray(table.n_num)
        n_cat = jnp.asarray(table.n_cat)
        if assign0 is not None:
            assign0 = jnp.asarray(assign0, dtype=jnp.int32)
    m, k = bins.shape
    b = int(table.n_bins)
    n_stack = z.shape[0]
    # stats / lbins are dead operands for regression_variance (shared,
    # no class axis); see _prepare.
    stats = jnp.zeros((m, 3), jnp.float32)
    lbins = jnp.zeros((m,), jnp.int32)
    c = _hist_channels(3, config.task, weights is not None)

    max_nodes = config.max_nodes or min(2 * m + 1, 1 << 22)
    s_cap = config.chunk_slots or _auto_chunk_slots(
        k, b, c, config.hist_budget_bytes)
    arrays = {k_: jnp.broadcast_to(v[None], (n_stack,) + v.shape)
              for k_, v in _init_arrays(max_nodes).items()}
    if assign0 is None:
        assign = jnp.zeros((n_stack, m), dtype=jnp.int32)
    else:
        assign = jnp.broadcast_to(assign0, (n_stack, m))
    subtract = ((k * b * c * 4, config.sub_cache_bytes)
                if _subtract_eligible(config, m, weights is not None)
                else None)

    kw = dict(n_bins=b, heuristic=config.heuristic, task=config.task,
              min_samples_split=config.min_samples_split,
              min_samples_leaf=config.min_samples_leaf,
              max_depth=config.max_depth, max_nodes=max_nodes,
              hist_backend=config.hist_backend,
              select_backend=config.select_backend,
              n_label_bins=1, weighted=weights is not None,
              min_child_weight=config.min_child_weight)
    dummy_pp = jnp.zeros((n_stack, 1, 1, 1, 1), dtype=jnp.float32)

    def step(arrays, assign, cs, cn, next_free, depth, num_slots, pp,
             use_sub, want_hist):
        return _chunk_step_classes(
            bins, stats, lbins, z, assign, arrays,
            pp if use_sub else dummy_pp, n_num, n_cat,
            jnp.asarray(cs, dtype=jnp.int32),
            jnp.asarray(cn, dtype=jnp.int32),
            jnp.asarray(next_free, dtype=jnp.int32), jnp.int32(depth),
            weights, num_slots=num_slots, use_sub=use_sub,
            want_hist=want_hist, **kw)

    def route(assign, arrays, start, end):
        return _route_step_classes(bins, assign, arrays, n_num,
                                   jnp.asarray(start, dtype=jnp.int32),
                                   jnp.asarray(end, dtype=jnp.int32))

    arrays, n_nodes = _grow_batched(step, route, arrays, assign, s_cap,
                                    max_nodes, level_callback, n_stack,
                                    subtract=subtract,
                                    max_depth=config.max_depth)
    trees = [Tree(n_nodes=int(n_nodes[c]),
                  **{k_: v[c] for k_, v in arrays.items()})
             for c in range(n_stack)]
    return trees, arrays


@obs.spanned("udt.tree")
def build_tree(table: BinnedTable, y, config: TreeConfig = TreeConfig(),
               n_classes: int | None = None,
               level_callback=None, resume: "BuildState | None" = None,
               sample_weight=None) -> Tree:
    """Train a UDT.  ``y`` is int class ids (classification) or float
    targets (regression modes).  ``level_callback(BuildState)`` is invoked
    after each completed level (checkpointing / progress hooks).

    ``sample_weight`` (optional [M] f32 — GOSS's per-example amplification,
    a Newton boosting round's hessians, or their product) weights every
    histogram row, so node counts, labels and split scores become the
    weighted — for GOSS, unbiased full-data — estimates;
    ``min_samples_split`` / ``min_samples_leaf`` then bound weighted counts
    (rounded to nearest) and ``min_child_weight`` leaf-ifies nodes whose
    winning split's lighter child carries too little weight (= hessian sum
    under Newton boosting; a stopping rule, see TreeConfig).  Supported for
    "classification" (disables the sibling-subtraction fast path: its
    bit-exactness contract does not survive float weights) and
    "regression_variance" (subtraction stays on under the float-tolerance
    contract); the label-split "regression" task re-derives pseudo-classes
    per level and is unsupported.  The mesh twin of this function is
    ``core.distributed.DistributedBuilder.build`` / ``build_tree_
    distributed``, which accepts the same ``sample_weight`` sharded over
    the data axes."""
    if sample_weight is not None and config.task == "regression":
        raise ValueError("sample_weight is unsupported for the label-split "
                         "'regression' task (use 'regression_variance')")
    if config.min_child_weight and config.select_backend == "pallas":
        raise ValueError("min_child_weight needs select_backend='jnp' (the "
                         "fused split-scan kernel has no weight floor)")
    bins_np, stats_np, lbins_np, yv_np, c, n_label_bins = _prepare(
        table, y, config, n_classes)
    m, k = bins_np.shape
    b = int(table.n_bins)
    n = obs.host_bytes(bins_np, stats_np, lbins_np, yv_np, sample_weight,
                       table.n_num, table.n_cat)
    with obs.counted("udt.put", "h2d_bytes", n, bytes=n):
        bins = jnp.asarray(bins_np)
        stats = jnp.asarray(stats_np)
        lbins = jnp.asarray(lbins_np)
        yv = jnp.asarray(yv_np)
        weights = (jnp.asarray(sample_weight, dtype=jnp.float32)
                   if sample_weight is not None else None)
        n_num = jnp.asarray(table.n_num)
        n_cat = jnp.asarray(table.n_cat)

    c = _hist_channels(c, config.task, weights is not None)
    max_nodes = config.max_nodes or min(2 * m + 1, 1 << 22)
    s_cap = config.chunk_slots or _auto_chunk_slots(
        k, b, c, config.hist_budget_bytes)
    cache = None
    if resume is not None:
        arrays = {k_: jnp.asarray(v) for k_, v in resume.arrays.items()}
        assign = jnp.asarray(resume.assign)
        cursors = (resume.level_start, resume.level_end, resume.next_free,
                   resume.depth)
        if resume.phist is not None:
            cache = (resume.phist_base, jnp.asarray(resume.phist))
    else:
        arrays = _init_arrays(max_nodes)
        assign = jnp.zeros((m,), dtype=jnp.int32)
        cursors = (0, 1, 1, 1)

    subtract = ((k * b * c * 4, config.sub_cache_bytes)
                if _subtract_eligible(config, m, weights is not None)
                else None)

    kw = dict(n_bins=b, heuristic=config.heuristic, task=config.task,
              min_samples_split=config.min_samples_split,
              min_samples_leaf=config.min_samples_leaf,
              max_depth=config.max_depth, max_nodes=max_nodes,
              hist_backend=config.hist_backend,
              select_backend=config.select_backend,
              n_label_bins=n_label_bins, weighted=weights is not None,
              min_child_weight=config.min_child_weight)
    dummy_pp = jnp.zeros((1, 1, 1, 1), dtype=jnp.float32)

    def step(arrays, assign, cs, cn, next_free, depth, num_slots, pp,
             use_sub, want_hist):
        return _chunk_step(bins, stats, lbins, yv, assign, arrays,
                           pp if use_sub else dummy_pp, n_num,
                           n_cat, jnp.int32(cs), jnp.int32(cn),
                           jnp.int32(next_free), jnp.int32(depth), weights,
                           num_slots=num_slots, use_sub=use_sub,
                           want_hist=want_hist, **kw)

    def route(assign, arrays, start, end):
        return _route_step(bins, assign, arrays, n_num, jnp.int32(start),
                           jnp.int32(end))

    arrays, n_nodes = _grow(step, route, arrays, assign, s_cap, max_nodes,
                            level_callback, cursors, subtract=subtract,
                            cache=cache, max_depth=config.max_depth)
    return Tree(n_nodes=n_nodes, **arrays)

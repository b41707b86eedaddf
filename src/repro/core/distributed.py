"""Distributed UDT build: the paper's technique on the production mesh.

Parallelism (mirrors how distributed tree-boosting systems scale, but with
jax-native collectives instead of MPI/NCCL):

  * **data parallel** over the ``('pod', 'data')`` mesh axes: each device
    holds an example shard and builds local ``H[S, K_l, B, C]`` histograms;
    one ``psum`` per level chunk merges them.  Collective bytes per chunk =
    ``S*K*B*C*4`` — independent of M, which is exactly why binned Superfast
    Selection scales (the paper's O(N*C) intermediate-statistics insight is
    what makes the collective small).
  * **feature parallel** over the ``'model'`` axis: features are sharded;
    each shard runs Superfast Selection on its own features and a tiny
    ``all_gather`` of per-node (score, feat, bin, op) tuples + argmax picks
    the global winner.  Routing is one psum'd bit per example (only the
    winning feature's owner evaluates the predicate).

Both compose; the multi-pod dry-run lowers this exact step.  The build is
level-synchronous, so fault tolerance = checkpoint the (arrays, assign,
cursor) state each level and restart from the last completed level
(checkpoint/tree_ckpt.py).

Sharded GOSS sampling (the boosted-ensemble loop, core.forest)
--------------------------------------------------------------
``make_sharded_sampler`` runs the per-round GOSS draw mesh-wide without
ever moving an example row between shards:

  * each data shard ranks its local rows by the Newton leverage
    ``|g| * sqrt(h)`` and takes a **static per-shard quota**
    ``q_top = ceil(top_n / d)`` via one local ``top_k``;
  * the only collective is the **threshold merge**: each shard's quota
    boundary (its ``q_top``-th largest leverage) is ``pmax``-merged over the
    data axes — ONE scalar per data axis, not O(M).  Every row anywhere
    with leverage >= the merged threshold is *certifiably* inside the true
    global top-``top_n`` set (pigeonhole: some shard holds >= ``q_top``
    global-top rows, so the merged boundary is >= the global cut), and
    each shard holds at most ``q_top`` of them, so the kept set needs no
    cross-shard rebalance;
  * the small-leverage remainder is sampled **per shard**: ``q_oth`` uniform
    draws from the shard's non-top rows, weighted by the exact per-shard
    amplification ``r_s / q_oth`` (``r_s`` = that shard's remainder size) —
    the stratified analogue of GOSS's global ``(1-a)/b``, and unbiased per
    stratum, so the total selected weight is exactly M.

Selected indices and weights stay shard-local as an [m_loc] weight/assign
mask (weight 0 / assign -1 rows are inert in the histogram scatter and the
router), so there is NO all_to_all, NO dynamic-shape gather, and every
shape is static; the draw is deterministic under the fit seed via
``fold_in(key, data_shard_index)``.  ``core.forest.goss_sample_sharded_ref``
is the bit-identical single-device reference (tests/test_dist_goss.py).

Collective-bytes accounting for the composed boosting round: with sibling
subtraction + ``slot_scatter`` both on, the per-level histogram collective
reduce_scatters the packed smaller-child pair axis — <= ``S/2 * K * B * C``
bytes split over the data shards — the sampling merge adds O(d) scalar
bytes per round, and the score update (``make_sharded_walk``) psums one
routing bit per example per walk step over the model axis only.  Nothing
in the round loop scales collective traffic with M.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.binning import BinnedTable
from repro.core.tree import (Tree, TreeConfig, _auto_chunk_slots, _chunk_step,
                             _chunk_step_classes, _grow, _grow_batched,
                             _init_arrays, _node_predicate, _prepare,
                             _route_step, _route_step_classes,
                             _subtract_eligible)

__all__ = ["DistConfig", "DistributedBuilder", "build_tree_distributed",
           "make_sharded_step", "make_sharded_sampler", "make_sharded_walk",
           "make_sharded_grid_counts", "sharded_grid_counts"]


@dataclasses.dataclass(frozen=True)
class DistConfig:
    """Mesh layout for the distributed build and the sharded boosting loop.

    ``data_axes`` names the mesh axes examples are sharded over (rows of
    the [M, K] binned table, targets, weights, assignments — everything
    ``P(data_axes)``); ``model_axis`` names the feature-sharding axis, or
    ``None`` for data-parallel only.  Passed to
    ``GradientBoostedTrees.fit(mesh=..., dist=DistConfig(...))`` and to
    ``build_tree_distributed`` / ``DistributedBuilder``; the axis names
    must exist in the mesh.  The compiled level step is cached per
    (mesh, DistConfig, static kwargs) — see ``_STEP_CACHE`` — so one
    DistConfig instance reused across an ensemble compiles once.
    """
    data_axes: tuple = ("data",)       # example-sharding mesh axes
    model_axis: str | None = "model"   # feature-sharding mesh axis (or None)
    # Two COMPOSABLE ways to shrink the per-level histogram collective:
    #   slot_scatter  -- reduce_scatter the histogram chunk over its leading
    #                    axis (half the bytes of a ring all-reduce, 1/dsize
    #                    of the selection compute per device);
    #   sibling subtraction (TreeConfig.sibling_subtraction) -- scatter only
    #    the packed smaller-child histogram ([S/2,K,B,C]: half the bytes
    #    AND half the scatter work).
    # With both on, the packed [S/2] pair axis is reduce_scattered and each
    # shard derives its co-child slots from its (pair, feature)-sharded
    # slice of the parent cache, so the per-level collective covers
    # S/2 x K x B x C bytes split dsize ways.  When the pair count does not
    # divide the data-shard count for a given chunk size, that chunk falls
    # back to the psum + subtraction path (still exact).
    slot_scatter: bool = True          # reduce_scatter histograms over slots


def _pad_to(x, mult, axis, fill):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return np.pad(x, widths, constant_values=fill)


def _freeze_kw(kw: dict) -> tuple:
    return tuple(sorted(kw.items()))


# Module-level caches for ALL the jitted sharded functions (level step,
# router, round sampler, ensemble walk).  A per-call cache (the pre-PR-5
# state) meant every build_tree_distributed call minted fresh jax.jit
# objects, so an ensemble of T trees retraced + recompiled the level step T
# times — and a refit (hyper-parameter sweep, back-to-back bench fits)
# would recompile the sampler/walk too; keyed on (mesh, dist, static
# config) the SAME jit object serves every same-shape build and jax's own
# trace cache makes the compile happen once (tests/test_dist_goss.py
# asserts this for the step cache).
_STEP_CACHE: dict = {}
_ROUTE_CACHE: dict = {}
_SAMPLER_CACHE: dict = {}
_WALK_CACHE: dict = {}
_CACHE_CAP = 64       # per-cache entry bound: a sweep over many distinct
                      # configs/shapes evicts oldest-first instead of
                      # pinning compiled executables (and their Mesh
                      # references) for the whole process lifetime


def _cache_put(cache: dict, key, fn):
    if len(cache) >= _CACHE_CAP:
        cache.pop(next(iter(cache)))      # dicts iterate insertion-first
    cache[key] = fn
    return fn


def make_sharded_step(mesh: Mesh, dist: DistConfig, kw: dict, num_slots: int,
                      use_sub: bool = False, want_hist: bool = False,
                      weighted: bool = False, classes: int = 0):
    """Build (or fetch from the module cache) the shard_map'd level-chunk
    step for a given slot count.

    ``use_sub`` / ``want_hist`` select the sibling-subtraction variants: the
    parent histogram rows come in (and the cached level histogram goes out)
    sharded over the feature axis -- and, when slot_scatter composes, over
    the pair/slot axis too -- so the cache memory scales with K/f_shards
    (x 1/d_shards composed) per device and the per-level collective covers
    only the packed smaller-child histogram.

    ``weighted`` appends a per-example [M] float32 weight channel, sharded
    with ``P(dist.data_axes)`` like every other example row — GOSS's
    amplification and a Newton round's hessians enter the in-kernel weight
    channel of the histogram pass shard-locally, so weighting adds ZERO
    collective bytes.

    ``classes`` > 0 selects the MULTICLASS batched step: per-class operands
    (targets, assignments, tree arrays, weights, cursor vectors) carry a
    leading replicated ``[C]`` axis — examples stay sharded over
    ``dist.data_axes``, so the specs just gain a leading ``None`` — and the
    per-shard body is ``tree._chunk_step_classes``: the SAME vmapped
    ``_chunk_step_impl`` as the local batched build, run inside shard_map.
    Every collective (the histogram psum / tiled psum_scatter, the
    selection all_gather) batches through its vmap rule per class, so a
    multiclass round keeps the single-class collective structure at C
    times the bytes — and ONE compile regardless of C.

    This is also what launch/dryrun.py lowers for the UDT rows of the
    roofline table (the paper-technique cell)."""
    cache_key = (mesh, dist, _freeze_kw(kw), num_slots, use_sub, want_hist,
                 weighted, classes)
    hit = _STEP_CACHE.get(cache_key)
    if hit is not None:
        return hit
    dspec = P(dist.data_axes)          # examples
    fspec = P(None, dist.model_axis)   # [M, K] -> features on model axis
    rep = P()
    cspec = P(None, dist.data_axes)    # [C, M] class-first example rows

    d_shards = max(1, int(np.prod([mesh.shape[a] for a in dist.data_axes])))
    # slot_scatter needs the reduce_scattered leading axis to divide the
    # data-shard count: the full [S] slot axis without subtraction, the
    # packed [S/2] pair axis with it (composition).
    scatter_ok = (dist.slot_scatter and num_slots % d_shards == 0
                  and (not use_sub or (num_slots // 2) % d_shards == 0))
    # the parent cache / cached-level histogram live on the full slot axis;
    # under composition they are additionally sharded over the data axes
    # (slot-major tiling, matching psum_scatter's tiled order).  The
    # multiclass variants carry the replicated class axis in front.
    sspec = (P(dist.data_axes, dist.model_axis) if scatter_ok else fspec)
    sspec_c = (P(None, dist.data_axes, dist.model_axis) if scatter_ok
               else P(None, None, dist.model_axis))
    hspec = sspec_c if classes else sspec
    step_kw = dict(kw, num_slots=num_slots, data_axes=dist.data_axes,
                   model_axis=dist.model_axis, slot_scatter=scatter_ok,
                   use_sub=use_sub, want_hist=want_hist, weighted=weighted)
    inner = _chunk_step_classes if classes else _chunk_step

    if weighted:
        def body(bins, stats, lbins, yv, assign, arrays, pp, n_num, n_cat,
                 cs, cn, nf, depth, weights):
            return inner(bins, stats, lbins, yv, assign, arrays, pp,
                         n_num, n_cat, cs, cn, nf, depth,
                         weights=weights, **step_kw)
    else:
        def body(bins, stats, lbins, yv, assign, arrays, pp, n_num, n_cat,
                 cs, cn, nf, depth):
            return inner(bins, stats, lbins, yv, assign, arrays, pp,
                         n_num, n_cat, cs, cn, nf, depth, **step_kw)

    rspec = cspec if classes else dspec              # per-example rows
    in_specs = (P(dist.data_axes, dist.model_axis),  # bins [M,K]
                dspec,                               # stats [M,C]
                dspec,                               # lbins [M]
                rspec,                               # yv [M] / z [C,M]
                rspec,                               # assign
                rep,                                 # tree arrays (replicated)
                hspec if use_sub else rep,           # parent hist pairs
                P(dist.model_axis),                  # n_num [K]
                P(dist.model_axis),                  # n_cat [K]
                rep, rep, rep, rep)                  # cursors + depth
    if weighted:
        in_specs = in_specs + (rspec,)               # sample weights
    out_specs = (rep, rep, hspec if want_hist else rep)
    sharded = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                            out_specs=out_specs, check_vma=False)
    fn = jax.jit(sharded)
    return _cache_put(_STEP_CACHE, cache_key, fn)


def make_sharded_route(mesh: Mesh, dist: DistConfig, classes: int = 0):
    """The sharded level router; ``classes`` > 0 selects the multiclass
    variant (assign [C, M] class-first, per-class tree arrays and cursor
    vectors, ``tree._route_step_classes`` inside the shard)."""
    cache_key = (mesh, dist, classes)
    hit = _ROUTE_CACHE.get(cache_key)
    if hit is not None:
        return hit
    inner = _route_step_classes if classes else _route_step

    def body(bins, assign, arrays, n_num, start, end):
        return inner(bins, assign, arrays, n_num, start, end,
                     model_axis=dist.model_axis)

    rspec = P(None, dist.data_axes) if classes else P(dist.data_axes)
    in_specs = (P(dist.data_axes, dist.model_axis), rspec,
                P(), P(dist.model_axis), P(), P())
    fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                               out_specs=rspec, check_vma=False))
    return _cache_put(_ROUTE_CACHE, cache_key, fn)


def _data_shard_index(data_axes):
    """Flattened data-shard index of the calling shard (mesh-major order,
    matching the contiguous row-block layout of ``P(data_axes)``)."""
    idx = jnp.int32(0)
    for ax in data_axes:
        idx = idx * jax.lax.axis_size(ax) + jax.lax.axis_index(ax)
    return idx


def make_sharded_sampler(mesh: Mesh, dist: DistConfig, loss, goss,
                         m: int, q_top: int, q_oth: int,
                         weighted: bool = False):
    """Jitted per-round sampling step of the sharded boosting loop.

    Returns ``fn(y, raw, key) -> (z, w, assign0)`` over [m_pad] arrays
    sharded with ``P(dist.data_axes)``: the Newton target ``z = -g/h``, the
    build weight ``w`` (GOSS amplification x hessian; 0 drops the row) and
    the initial node assignment (0 selected / -1 inert).  With ``goss``
    None every valid row is selected at its hessian weight.

    ``weighted`` appends a sharded [m_pad] sample-weight operand —
    ``fn(y, raw, key, sw)`` — scaling each row's g and h AFTER the Newton
    target is formed (z is weight-invariant; the weight rides the h
    channel and the leverage ranking, mirroring the local loop).

    Multiclass losses (``loss.is_multiclass``) take ``raw`` class-first
    [C, m_pad] sharded ``P(None, data_axes)`` and return (z, w, assign0)
    in the same layout: ONE shared row draw per round ranked by the
    cross-class leverage norm ``sqrt(sum_c g_c^2 h_c)``, each class
    multiplying its own hessians onto the shared amplification weights —
    the sharded twin of the local ``_fit_multiclass`` draw.

    The GOSS draw is the per-shard-quota scheme described in the module
    docstring: one local ``top_k`` per shard, one scalar ``pmax`` threshold
    merge per data axis, per-shard uniform remainder draws with the exact
    ``r_s / q_oth`` amplification.  No cross-shard row traffic, no dynamic
    shapes; deterministic under ``key`` via the data-shard index fold-in.
    """
    from repro.core.forest import _goss_shard_boundary, _goss_shard_weights
    cache_key = (mesh, dist, loss, goss, m, q_top, q_oth, weighted)
    hit = _SAMPLER_CACHE.get(cache_key)
    if hit is not None:
        return hit
    dspec = P(dist.data_axes)
    multiclass = getattr(loss, "is_multiclass", False)
    rspec = P(None, dist.data_axes) if multiclass else dspec

    def sample(y, raw, key, sw):
        g, h = loss.grad_hess(y, raw)
        z = loss.newton_target(g, h)
        if sw is not None:
            # trailing-axis broadcast covers both [m] and [C, m] channels
            g, h = g * sw, h * sw
        m_loc = y.shape[0]
        idx = _data_shard_index(dist.data_axes)
        rows = idx * m_loc + jnp.arange(m_loc, dtype=jnp.int32)
        valid = rows < m
        if goss is None:
            w = jnp.where(valid, h, 0.0).astype(jnp.float32)
            assign0 = jnp.where(valid, 0, -1).astype(jnp.int32)
            if multiclass:
                assign0 = jnp.broadcast_to(assign0, z.shape)
            return z, w, assign0
        if multiclass:
            rank = jnp.sqrt(jnp.sum(g * g * h, axis=0))
        elif weighted or not loss.constant_hessian:
            rank = g * jnp.sqrt(h)
        else:
            rank = g
        u = jax.random.uniform(jax.random.fold_in(key, idx), (m_loc,))
        lv = jnp.where(valid, jnp.abs(rank), -1.0)
        u = jnp.where(valid, u, -1.0)
        tau = _goss_shard_boundary(lv, q_top)
        for ax in dist.data_axes:
            tau = jax.lax.pmax(tau, ax)
        w_goss = _goss_shard_weights(lv, u, tau, q_top, q_oth)
        if multiclass:
            w = (w_goss[None] * h).astype(jnp.float32)
            assign0 = jnp.broadcast_to(
                jnp.where(w_goss > 0, 0, -1).astype(jnp.int32), z.shape)
            return z, w, assign0
        keep_h = weighted or not loss.constant_hessian
        w = (w_goss * h if keep_h else w_goss).astype(jnp.float32)
        assign0 = jnp.where(w_goss > 0, 0, -1).astype(jnp.int32)
        return z, w, assign0

    if weighted:
        def body(y, raw, key, sw):
            return sample(y, raw, key, sw)
        in_specs = (dspec, rspec, P(), dspec)
    else:
        def body(y, raw, key):
            return sample(y, raw, key, None)
        in_specs = (dspec, rspec, P())

    fn = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=in_specs,
        out_specs=(rspec, rspec, rspec), check_vma=False))
    return _cache_put(_SAMPLER_CACHE, cache_key, fn)


def make_sharded_walk(mesh: Mesh, dist: DistConfig, num_steps: int,
                      classes: int = 0):
    """Jitted sharded raw-score update: ``fn(raw, arrays, bins, n_num, lr)``
    returns ``raw + lr * leaf_label`` with the Algorithm-7 walk evaluated on
    the (data, model)-sharded bins.

    Mirrors ``predict._walk`` (no depth/min-split limits — the ensemble
    update always walks to the leaf) but keeps the bins feature-sharded:
    each step descends through ``tree._node_predicate`` — the SAME
    feature-parallel predicate the level router uses (one psum'd bit per
    example over the model axis) — so the raw scores never leave their
    data shard and the boosting loop's score state stays device-resident
    across rounds.

    ``classes`` > 0 selects the multiclass variant: ``raw`` is class-first
    [C, m_pad] (``P(None, data_axes)``), ``arrays`` carries the [C,
    max_nodes] stacked class-trees of one round, and the walk vmaps over
    the class axis — the sharded twin of ``predict.walk_class_trees``."""
    cache_key = (mesh, dist, num_steps, classes)
    hit = _WALK_CACHE.get(cache_key)
    if hit is not None:
        return hit
    dspec = P(dist.data_axes)
    rspec = P(None, dist.data_axes) if classes else dspec

    def walk_one(raw, arrays, bins, n_num, lr):
        node0 = jnp.zeros((bins.shape[0],), dtype=jnp.int32)

        def step(_, node):
            can = (~arrays["leaf"][node]) & (arrays["left"][node] >= 0)
            f = jnp.maximum(arrays["feat"][node], 0)
            pos = _node_predicate(bins, f, arrays["op"][node],
                                  arrays["tbin"][node], n_num,
                                  dist.model_axis)
            nxt = jnp.where(pos, arrays["left"][node], arrays["right"][node])
            return jnp.where(can, nxt, node)

        node = jax.lax.fori_loop(0, num_steps, step, node0)
        return raw + lr * arrays["label"][node]

    if classes:
        def body(raw, arrays, bins, n_num, lr):
            return jax.vmap(
                lambda r, ar: walk_one(r, ar, bins, n_num, lr))(raw, arrays)
    else:
        body = walk_one

    in_specs = (rspec, P(), P(dist.data_axes, dist.model_axis),
                P(dist.model_axis), P())
    fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                               out_specs=rspec, check_vma=False))
    return _cache_put(_WALK_CACHE, cache_key, fn)


_GRID_CACHE: dict = {}


def make_sharded_grid_counts(mesh: Mesh, dist: DistConfig, *,
                             classification: bool = True):
    """Jitted mesh-sharded TOOT design-space kernel:
    ``fn(lab, cnt, cmc, y, valid, smin, mcw, dmax)`` prices the whole
    (dmax x smin x mcw) grid against the sharded validation path tables.

    The body IS ``core.tuning._grid_counts_body`` — the same function the
    local jitted kernel wraps — run inside shard_map with the path-table
    rows [M, T] sharded over ``dist.data_axes`` and the smin axis sharded
    over ``dist.model_axis`` (the feature axis carries no features here;
    it is reused as the grid-slice axis so the sweep composes with
    ``DistributedBuilder``'s mesh with zero re-sharding of the mesh
    itself).  Each shard prices its [Nd, Ns/f, Nw] slice against its row
    shard; ONE int32 psum over the data axes totals the
    correct-prediction counts (order-independent, so the sharded grid is
    bit-identical to the single-device grid), and the out_spec's
    model-axis sharding makes the final gather implicit in the first
    host read.  Collective bytes: Nd*Ns*Nw*4 per data axis — independent
    of M, the same property that makes the histogram psum small."""
    cache_key = (mesh, dist, classification)
    hit = _GRID_CACHE.get(cache_key)
    if hit is not None:
        return hit
    from repro.core.tuning import _grid_counts_body
    dspec = P(dist.data_axes)

    def body(lab, cnt, cmc, y, valid, smin, mcw, dmax):
        out = _grid_counts_body(lab, cnt, cmc, y, valid, smin, mcw, dmax,
                                classification=classification)
        return jax.lax.psum(out, dist.data_axes)

    in_specs = (dspec, dspec, dspec, dspec, dspec,
                P(dist.model_axis), P(), P())
    out_specs = P(None, dist.model_axis, None)
    fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                               out_specs=out_specs, check_vma=False))
    return _cache_put(_GRID_CACHE, cache_key, fn)


def sharded_grid_counts(mesh: Mesh, dist: DistConfig, lab, cnt, cmc, y,
                        smin, mcw, dmax, *, classification: bool = True):
    """Host convenience over ``make_sharded_grid_counts``: pad the example
    rows to the data-shard count (masked inert via ``valid``) and the smin
    axis to the feature-shard count (sentinel Int32.max, trimmed from the
    result), invoke the cached kernel, return the [Nd, Ns, Nw] totals."""
    d_shards = max(1, int(np.prod([mesh.shape[a] for a in dist.data_axes])))
    f_shards = mesh.shape[dist.model_axis] if dist.model_axis else 1
    m = np.asarray(lab).shape[0]
    ns = np.asarray(smin).shape[0]
    lab_p = _pad_to(np.asarray(lab, dtype=np.float32), d_shards, 0, 0.0)
    cnt_p = _pad_to(np.asarray(cnt), d_shards, 0, 0)
    cmc_p = _pad_to(np.asarray(cmc, dtype=np.float32), d_shards, 0, 0.0)
    y_p = _pad_to(np.asarray(y, dtype=np.float32), d_shards, 0, 0.0)
    valid = _pad_to(np.ones(m, dtype=bool), d_shards, 0, False)
    smin_p = _pad_to(np.asarray(smin, dtype=np.int32), f_shards, 0,
                     np.iinfo(np.int32).max)
    fn = make_sharded_grid_counts(mesh, dist, classification=classification)
    out = fn(jnp.asarray(lab_p), jnp.asarray(cnt_p), jnp.asarray(cmc_p),
             jnp.asarray(y_p), jnp.asarray(valid), jnp.asarray(smin_p),
             jnp.asarray(mcw, dtype=jnp.float32),
             jnp.asarray(dmax, dtype=jnp.int32))
    return np.asarray(out)[:, :ns, :]


class DistributedBuilder:
    """Stage a BinnedTable on the mesh once; build many trees from it.

    ``build_tree_distributed`` restages (pads + device_puts) the [M, K]
    bins on every call, which is fine for one tree but would serialise a
    host round-trip per round of a boosted ensemble.  The builder stages
    the table, the feature vectors and the dead-constant statistic rows at
    construction; ``build`` then accepts per-round targets / weights /
    assignments either as host arrays (padded and placed here) or as
    already-sharded [m_pad] device arrays (the device-resident loop of
    ``GradientBoostedTrees.fit(mesh=...)`` — no host staging per tree).

    Weight-0 / assign -1 rows are inert end to end (dropped by the
    histogram scatter, never routed), which is how the sharded GOSS draw
    expresses its selection without gathering rows across shards.
    """

    def __init__(self, table: BinnedTable, config: TreeConfig = TreeConfig(),
                 *, mesh: Mesh, dist: DistConfig = DistConfig(),
                 n_classes: int | None = None):
        if config.min_child_weight and config.select_backend == "pallas":
            raise ValueError("min_child_weight needs select_backend='jnp' "
                             "(the fused split-scan kernel has no weight "
                             "floor)")
        self.table, self.config = table, config
        self.mesh, self.dist = mesh, dist
        m, k = table.bins.shape
        self.m, self.k, self.b = int(m), int(k), int(table.n_bins)
        self.d_shards = max(1, int(np.prod(
            [mesh.shape[a] for a in dist.data_axes])))
        self.f_shards = mesh.shape[dist.model_axis] if dist.model_axis else 1

        # pad examples with slot -1 sentinels (assign = -1 keeps them inert)
        # and features with all-missing columns (never selectable)
        bins_p = _pad_to(_pad_to(np.asarray(table.bins), self.d_shards, 0, 0),
                         self.f_shards, 1, 0)
        self.m_pad, self.k_pad = bins_p.shape
        if self.k_pad > self.k:   # padded features: all values in missing bin
            bins_p[:, self.k:] = 0

        if config.task == "classification":
            if n_classes is None:
                raise ValueError("DistributedBuilder needs n_classes for "
                                 "classification (build_tree_distributed "
                                 "infers it from y)")
            self.c = int(n_classes)
        elif config.task == "regression_variance":
            self.c = 3
        else:
            self.c = 2
        self.n_classes = n_classes

        self._rows = NamedSharding(mesh, P(dist.data_axes))
        put = lambda x, spec: jax.device_put(x, NamedSharding(mesh, spec))
        self.bins_d = put(bins_p, P(dist.data_axes, dist.model_axis))
        self.n_num_d = put(_pad_to(np.asarray(table.n_num), self.f_shards,
                                   0, 0), P(dist.model_axis))
        self.n_cat_d = put(_pad_to(np.asarray(table.n_cat), self.f_shards,
                                   0, 0), P(dist.model_axis))
        if config.task == "regression_variance":
            # stats / lbins are dead operands for this task (the moment rows
            # are formed from yv inside the level step); staged once.
            self._stats_d = put(np.zeros((self.m_pad, 3), np.float32),
                                P(dist.data_axes))
            self._lbins_d = put(np.zeros((self.m_pad,), np.int32),
                                P(dist.data_axes))

        self.max_nodes = config.max_nodes or min(2 * self.m + 1, 1 << 22)
        self.s_cap = config.chunk_slots or _auto_chunk_slots(
            self.k_pad, self.b, self.c, config.hist_budget_bytes)
        assign0 = np.full((self.m_pad,), -1, dtype=np.int32)
        assign0[:self.m] = 0            # padding rows never join any node
        self._assign0 = assign0
        self._route = make_sharded_route(mesh, dist)
        self._dummy_pp = jnp.zeros((1, 1, 1, 1), dtype=jnp.float32)

    def _stage_rows(self, x, fill, dtype):
        """Shard a per-example vector over the data axes: host [m] input is
        padded to m_pad here; an already-padded device array (the
        device-resident loop) is just re-placed (a no-op when it already
        carries the right sharding)."""
        if isinstance(x, jax.Array) and x.shape[0] == self.m_pad:
            # astype matches the host path's coercion (an int/f64 target
            # must not flow into the f32 moment channels); identity — the
            # same array object — when the dtype already agrees.
            return jax.device_put(x.astype(dtype), self._rows)
        return jax.device_put(
            _pad_to(np.asarray(x, dtype), self.d_shards, 0, fill),
            self._rows)

    def _stage_class_rows(self, x, fill, dtype):
        """Shard a class-first [C, m] matrix over the data axes with the
        class axis replicated (``P(None, data_axes)`` — the multiclass
        training layout); host input is padded to [C, m_pad] here, an
        already-padded device array (the sharded multiclass round loop)
        is just re-placed."""
        spec = NamedSharding(self.mesh, P(None, self.dist.data_axes))
        if isinstance(x, jax.Array) and x.shape[-1] == self.m_pad:
            return jax.device_put(x.astype(dtype), spec)
        return jax.device_put(
            _pad_to(np.asarray(x, dtype), self.d_shards, 1, fill), spec)

    def build(self, y, sample_weight=None, assign=None,
              level_callback=None) -> Tree:
        """Build one tree.  ``y`` / ``sample_weight`` / ``assign`` are host
        [m] arrays or sharded [m_pad] device arrays (see class docstring);
        ``assign`` defaults to every valid row active at the root, and a
        caller-supplied assignment (the GOSS selection mask) must keep
        padding rows at -1."""
        config, dist, mesh = self.config, self.dist, self.mesh
        weighted = sample_weight is not None
        if weighted and config.task == "regression":
            raise ValueError("sample_weight is unsupported for the "
                             "label-split 'regression' task (use "
                             "'regression_variance')")
        if config.task == "regression_variance":
            yv_d = self._stage_rows(y, 0.0, np.float32)
            stats_d, lbins_d = self._stats_d, self._lbins_d
            c, n_label_bins = 3, 1
        else:
            _, stats_np, lbins_np, yv_np, c, n_label_bins = _prepare(
                self.table, np.asarray(y), config, self.n_classes)
            stats_d = self._stage_rows(np.asarray(stats_np), 0.0, np.float32)
            lbins_d = self._stage_rows(lbins_np, 0, np.int32)
            yv_d = self._stage_rows(yv_np, 0.0, np.float32)
        w_d = (self._stage_rows(sample_weight, 0.0, np.float32)
               if weighted else None)
        assign_d = (self._stage_rows(assign, -1, np.int32)
                    if assign is not None
                    else jax.device_put(self._assign0, self._rows))

        kw = dict(n_bins=self.b, heuristic=config.heuristic, task=config.task,
                  min_samples_split=config.min_samples_split,
                  min_samples_leaf=config.min_samples_leaf,
                  max_depth=config.max_depth, max_nodes=self.max_nodes,
                  hist_backend=config.hist_backend,
                  select_backend=config.select_backend,
                  n_label_bins=n_label_bins,
                  min_child_weight=config.min_child_weight)

        # sibling subtraction halves both scatter work and collective bytes
        # and COMPOSES with slot_scatter (packed pair axis reduce_scattered,
        # parent cache sharded over (slot, feature)).  The budget gate
        # conservatively uses the feature-shard row bytes.  Weighted builds
        # (GOSS / Newton hessians) keep eligibility only under the
        # float-tolerance contract — same gate as the local builder.
        subtract = (((self.k_pad // self.f_shards) * self.b * c * 4,
                     config.sub_cache_bytes)
                    if _subtract_eligible(config, self.m, weighted)
                    else None)

        def step(arrays, assign_, cs, cn, next_free, depth, num_slots, pp,
                 use_sub, want_hist):
            fn = make_sharded_step(mesh, dist, kw, num_slots, use_sub,
                                   want_hist, weighted)
            args = [self.bins_d, stats_d, lbins_d, yv_d, assign_, arrays,
                    pp if use_sub else self._dummy_pp, self.n_num_d,
                    self.n_cat_d, jnp.int32(cs), jnp.int32(cn),
                    jnp.int32(next_free), jnp.int32(depth)]
            if weighted:
                args.append(w_d)
            return fn(*args)

        def route(assign_, arrays, start, end):
            return self._route(self.bins_d, assign_, arrays, self.n_num_d,
                               jnp.int32(start), jnp.int32(end))

        arrays = _init_arrays(self.max_nodes)
        arrays, n_nodes = _grow(step, route, arrays, assign_d, self.s_cap,
                                self.max_nodes, level_callback,
                                subtract=subtract,
                                max_depth=config.max_depth)
        return Tree(n_nodes=n_nodes, **arrays)

    def build_batched(self, z, sample_weight=None, assign=None,
                      level_callback=None):
        """Build one ``regression_variance`` tree per row of ``z`` [C, m]
        through ONE vmapped sharded level-synchronous build — the mesh
        twin of ``core.tree.build_trees_batched`` (a multiclass boosting
        round's K class-trees for one compile and one sharded step per
        level chunk).

        ``z`` / ``sample_weight`` / ``assign`` are host [C, m] arrays or
        sharded [C, m_pad] device arrays in the class-first
        ``P(None, data_axes)`` layout (the sharded multiclass sampler's
        outputs feed in unchanged).  Returns ``(trees, arrays)`` exactly
        like the local batched build: per-class ``Tree`` views plus the
        stacked [C, max_nodes] arrays the batched score-update walk
        (``make_sharded_walk(classes=C)``) consumes directly."""
        config, dist, mesh = self.config, self.dist, self.mesh
        if config.task != "regression_variance":
            raise ValueError("build_batched fits 'regression_variance' "
                             "trees (the boosting round task); got task="
                             f"{config.task!r}")
        weighted = sample_weight is not None
        z_d = self._stage_class_rows(z, 0.0, np.float32)
        n_stack = int(z_d.shape[0])
        w_d = (self._stage_class_rows(sample_weight, 0.0, np.float32)
               if weighted else None)
        assign_d = (self._stage_class_rows(assign, -1, np.int32)
                    if assign is not None
                    else self._stage_class_rows(
                        np.broadcast_to(self._assign0,
                                        (n_stack, self.m_pad)), -1, np.int32))

        kw = dict(n_bins=self.b, heuristic=config.heuristic, task=config.task,
                  min_samples_split=config.min_samples_split,
                  min_samples_leaf=config.min_samples_leaf,
                  max_depth=config.max_depth, max_nodes=self.max_nodes,
                  hist_backend=config.hist_backend,
                  select_backend=config.select_backend, n_label_bins=1,
                  min_child_weight=config.min_child_weight)
        subtract = (((self.k_pad // self.f_shards) * self.b * 3 * 4,
                     config.sub_cache_bytes)
                    if _subtract_eligible(config, self.m, weighted)
                    else None)
        arrays = {k_: jnp.broadcast_to(v[None], (n_stack,) + v.shape)
                  for k_, v in _init_arrays(self.max_nodes).items()}
        dummy_pp = jnp.zeros((n_stack, 1, 1, 1, 1), dtype=jnp.float32)

        def step(arrays_, assign_, cs, cn, next_free, depth, num_slots, pp,
                 use_sub, want_hist):
            fn = make_sharded_step(mesh, dist, kw, num_slots, use_sub,
                                   want_hist, weighted, classes=n_stack)
            args = [self.bins_d, self._stats_d, self._lbins_d, z_d, assign_,
                    arrays_, pp if use_sub else dummy_pp, self.n_num_d,
                    self.n_cat_d, jnp.asarray(cs, dtype=jnp.int32),
                    jnp.asarray(cn, dtype=jnp.int32),
                    jnp.asarray(next_free, dtype=jnp.int32),
                    jnp.int32(depth)]
            if weighted:
                args.append(w_d)
            return fn(*args)

        route_fn = make_sharded_route(mesh, dist, classes=n_stack)

        def route(assign_, arrays_, start, end):
            return route_fn(self.bins_d, assign_, arrays_, self.n_num_d,
                            jnp.asarray(start, dtype=jnp.int32),
                            jnp.asarray(end, dtype=jnp.int32))

        arrays, n_nodes = _grow_batched(step, route, arrays, assign_d,
                                        self.s_cap, self.max_nodes,
                                        level_callback, n_stack,
                                        subtract=subtract,
                                        max_depth=config.max_depth)
        trees = [Tree(n_nodes=int(n_nodes[c]),
                      **{k_: v[c] for k_, v in arrays.items()})
                 for c in range(n_stack)]
        return trees, arrays


def build_tree_distributed(table: BinnedTable, y,
                           config: TreeConfig = TreeConfig(),
                           mesh: Mesh | None = None,
                           dist: DistConfig = DistConfig(),
                           n_classes: int | None = None,
                           level_callback=None, sample_weight=None) -> Tree:
    """Distributed UDT training.  Produces the SAME tree as build_tree
    (tests/test_distributed.py asserts exact agreement) while sharding
    examples over ``dist.data_axes`` and features over ``dist.model_axis``.

    ``sample_weight`` (optional [M] f32) shards with ``P(dist.data_axes)``
    and enters the in-kernel weight channel exactly as in the local
    builder — GOSS amplification, Newton hessians, or their product — with
    the same task gating (see ``build_tree``).  One-shot wrapper around
    ``DistributedBuilder``; ensemble loops should hold a builder instead
    so the table is staged once."""
    if config.task == "classification" and n_classes is None:
        n_classes = int(np.asarray(y).max()) + 1
    builder = DistributedBuilder(table, config, mesh=mesh, dist=dist,
                                 n_classes=n_classes)
    return builder.build(y, sample_weight=sample_weight,
                         level_callback=level_callback)

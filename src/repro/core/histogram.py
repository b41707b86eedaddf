"""Histogram construction: the one-pass statistics collection of Superfast
Selection (paper Algorithm 4 lines 2-9), batched over nodes and features.

``node_histogram`` produces ``H[S, K, B, C]`` where ``S`` is the number of
node *slots* in the current level chunk, ``K`` features, ``B`` bins and ``C``
statistics channels (class counts for classification; ``(count, sum_y,
sum_y2)`` moments for variance regression; 2 pseudo-classes for the paper's
regression label-split).  This is the single O(M) pass that replaces the
O(M*N) rescan of generic selection.

Backends:
  * ``segment``  - scatter-add of the live rows only (CPU / default): rows
                   with a slot are compacted and scattered in fixed-size
                   tiles, so a level costs its live rows, not all M
  * ``onehot``   - one-hot matmul; the MXU-native formulation (TPUs have no
                   atomics, so GPU-style shared-memory histogramming does not
                   transfer; a (B x Mt)@(Mt x C) matmul does)
  * ``pallas``   - tiled Pallas kernel implementing the onehot form in VMEM
                   (kernels/histogram.py)

``node_histogram_smaller_child`` is the sibling-subtraction entry point
(LightGBM's histogram trick in level-synchronous form): the tree builder
scatters statistics only for the smaller child of every split pair and
derives the co-child as ``H_parent - H_small``.  Skipped slots are never
materialised -- the pair axis is *packed*, so the scatter target (and the
per-level collective in the distributed build) is half the size.

``node_histogram_sibling_fused`` goes one step further on the pallas
backend: it hands the parent rows to the kernel and the derivation plus the
pair interleave happen in the kernel's epilogue straight out of VMEM, so
the derived sibling never exists in HBM as a separate tensor (the
single-shard fast path of the tree builder).

All three entry points take an optional ``weights`` [M] channel (GOSS's
``(1-a)/b`` amplification): rows accumulate ``w[i] * stats[i]``, applied
in-kernel on the pallas backend.  ``weights=None`` traces the identical
unweighted computation, preserving the bit-exactness contracts above.
Under the distributed build the weight channel is shard-local — each data
shard weights its own rows before the per-level collective — so the
mesh-wide GOSS / Newton boosting loop (core.forest ``fit(mesh=...)``)
adds ZERO collective bytes to the histogram reduction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["node_histogram", "node_histogram_smaller_child",
           "node_histogram_sibling_fused", "class_stats", "moment_stats"]


def class_stats(labels: jax.Array, n_classes: int) -> jax.Array:
    """[M] int labels -> [M, C] one-hot float32 statistic rows."""
    return jax.nn.one_hot(labels, n_classes, dtype=jnp.float32)


def moment_stats(y: jax.Array) -> jax.Array:
    """[M] float targets -> [M, 3] (1, y, y^2) moment rows."""
    y = y.astype(jnp.float32)
    return jnp.stack([jnp.ones_like(y), y, y * y], axis=-1)


def _weighted(stats, weights):
    """Apply the optional per-example weight channel to statistic rows.

    ``weights=None`` is the identity and emits NO op, so the unweighted
    path's jaxpr (and its bit-exactness contract) is untouched."""
    if weights is None:
        return stats
    return stats * weights[:, None].astype(jnp.float32)


# the most updates one tile of the compacted ``segment`` scatter passes: a
# tile is T = _TILE_UPDATES // (K*C) rows, rounded down to a power of two
# (2,048 rows at KDD99 width: 41 features x 5 classes)
_TILE_UPDATES = 1 << 19


def _tile_rows(m, k, c):
    """Rows of one scatter tile: a power of two, at most ``m``."""
    return min(m, 1 << max(0, (_TILE_UPDATES // (k * c)).bit_length() - 1))


def _segment_backend(bins, stats, slot, num_slots, n_bins, weights=None):
    m, k = bins.shape
    stats = _weighted(stats, weights)
    c = stats.shape[-1]
    sb = num_slots * n_bins
    size = k * c * sb
    # Only rows with a live slot reach the scatter: their indices are
    # compacted to the front (in row order) and taken T at a time, so a
    # level costs its live rows, not all M (rows of finished leaves, other
    # chunks and the larger sibling of every pair stay out).
    t = _tile_rows(m, k, c)
    padded = -(-m // t) * t
    live = slot >= 0
    n_live = live.sum(dtype=jnp.int32)
    pos = jnp.where(live, jnp.cumsum(live, dtype=jnp.int32) - 1, padded)
    order = jnp.zeros((padded,), jnp.int32).at[pos].set(
        jnp.arange(m, dtype=jnp.int32), mode="drop")
    base = jnp.arange(k * c, dtype=jnp.int32).reshape(k, c, 1) * sb
    lane = jnp.arange(t, dtype=jnp.int32)

    def tile(carry):
        i, h = carry
        rows = jax.lax.dynamic_slice(order, (i * t,), (t,))
        s_t = jnp.where(i * t + lane < n_live, slot[rows], -1)
        # ONE scalar scatter over every (feature, channel, row) triple.  A
        # scatter of [T, C] rows (the channel as the update window) is laid
        # out on a TPU with C padded to 128 lanes; flat scalar updates keep
        # the layout dense.  Padding past n_live goes out of range and drops.
        seg = jnp.where(s_t < 0, size,
                        base + s_t * n_bins + bins[rows].T[:, None, :])
        upd = jnp.broadcast_to(stats[rows].T[None], (k, c, t))
        return i + 1, h.at[seg.reshape(-1)].add(upd.reshape(-1), mode="drop")

    _, h = jax.lax.while_loop(lambda carry: carry[0] * t < n_live, tile,
                              (jnp.int32(0), jnp.zeros((size,), jnp.float32)))
    return h.reshape(k, c, num_slots, n_bins).transpose(2, 0, 3, 1)


def _onehot_backend(bins, stats, slot, num_slots, n_bins, weights=None):
    m, k = bins.shape
    stats = _weighted(stats, weights)
    c = stats.shape[-1]
    base = slot * n_bins
    idx = jnp.where(slot[:, None] < 0, num_slots * n_bins, base[:, None] + bins)
    oh = jax.nn.one_hot(idx, num_slots * n_bins, dtype=jnp.float32)  # [M,K,SB]
    h = jnp.einsum("mks,mc->ksc", oh, stats)               # MXU matmul form
    return h.reshape(k, num_slots, n_bins, c).transpose(1, 0, 2, 3)


def _pallas_backend(bins, stats, slot, num_slots, n_bins, weights=None):
    from repro.kernels import ops as kops
    return kops.histogram(bins, stats, slot, num_slots=num_slots,
                          n_bins=n_bins, weights=weights)


_BACKENDS = {
    "segment": _segment_backend,
    "onehot": _onehot_backend,
    "pallas": _pallas_backend,
}


@functools.partial(jax.jit, static_argnames=("num_slots", "n_bins", "backend"))
def node_histogram(bins: jax.Array, stats: jax.Array, slot: jax.Array, *,
                   num_slots: int, n_bins: int,
                   backend: str = "segment", weights=None) -> jax.Array:
    """Accumulate per-(node-slot, feature, bin) statistic rows.

    Args:
      bins:  [M, K] int32 bin ids (output of core.binning).
      stats: [M, C] float32 statistic rows per example.
      slot:  [M] int32 node slot in [0, num_slots) or -1 if the example's
             node is not in the current chunk (finalised leaf / other chunk).
      weights: optional [M] float32 per-example weight channel: rows
             accumulate ``w[i] * stats[i]`` (GOSS's ``(1-a)/b`` amplification
             is exact because it enters before accumulation, not as a
             post-hoc rescale).  ``None`` traces the identical unweighted
             computation (jaxpr-asserted in tests/test_goss.py).
    Returns:
      H: [num_slots, K, n_bins, C] float32.
    """
    return _BACKENDS[backend](bins, stats, slot, num_slots, n_bins, weights)


def _packed_slot(slot, compute, num_slots):
    """Raw slot ids -> packed pair ids: the computed child of pair ``j``
    (slots ``2j``, ``2j+1``) maps to ``j``; every other row maps to -1."""
    slot_map = jnp.where(compute, jnp.arange(num_slots, dtype=jnp.int32) // 2,
                         -1)
    return jnp.where(slot >= 0, slot_map[jnp.clip(slot, 0, num_slots - 1)], -1)


@functools.partial(jax.jit, static_argnames=("num_slots", "n_bins", "backend"))
def node_histogram_smaller_child(bins: jax.Array, stats: jax.Array,
                                 slot: jax.Array, compute: jax.Array, *,
                                 num_slots: int, n_bins: int,
                                 backend: str = "segment",
                                 weights=None) -> jax.Array:
    """Scatter statistics only for the per-pair "compute me" child slots.

    The level-synchronous builder allocates children in sibling pairs at
    slots ``(2j, 2j+1)``.  ``compute`` is a [num_slots] bool mask selecting
    exactly one slot of each pair (the child with fewer routed examples);
    rows whose slot is masked out are dropped, and the computed child of
    pair ``j`` lands in *packed* slot ``j``.

    Returns:
      H_small: [num_slots // 2, K, n_bins, C] float32 -- the histogram of
      the computed (smaller) child of each pair.  The caller derives the
      sibling as ``H_parent[j] - H_small[j]``; for integer-count channels
      (classification one-hots, moment channel 0) the subtraction is exact
      in float32 below 2**24 examples, so the derived histogram is
      bit-identical to a full recompute.  Float moment channels (sum_y,
      sum_y2) agree to accumulation-order tolerance.  With a ``weights``
      channel every channel is a float weighted sum, so the whole contract
      downgrades to accumulation-order tolerance (see
      core.tree._subtract_eligible for how the builder gates on this).
    """
    if num_slots % 2:
        raise ValueError("pair packing needs an even slot count")
    return _BACKENDS[backend](bins, stats,
                              _packed_slot(slot, compute, num_slots),
                              num_slots // 2, n_bins, weights)


@functools.partial(jax.jit, static_argnames=("num_slots", "n_bins", "backend"))
def node_histogram_sibling_fused(bins: jax.Array, stats: jax.Array,
                                 slot: jax.Array, compute: jax.Array,
                                 phist_pairs: jax.Array, *,
                                 num_slots: int, n_bins: int,
                                 backend: str = "pallas",
                                 weights=None) -> jax.Array:
    """Smaller-child scatter + in-kernel sibling derivation, in one pass.

    ``phist_pairs`` [num_slots//2, K, B, C] holds each sibling pair's parent
    histogram row; ``compute`` is the per-slot "scatter me" mask of
    ``node_histogram_smaller_child``.  Returns the FULL [num_slots, K, B, C]
    child histogram: the computed child's block is the packed scatter, its
    sibling is ``H_parent - H_small``.

    On the ``pallas`` backend the subtraction and the pair interleave run in
    the kernel's epilogue straight out of VMEM (kernels/histogram.py), so no
    derived-sibling tensor and no jnp subtraction appear between the kernel
    and the selection scan.  Other backends (and the parity oracle for the
    fused kernel) take the reference jnp path: packed scatter, subtract,
    interleave.  Exactness contract as ``node_histogram_smaller_child``:
    bit-identical for integer-count channels below 2**24 examples,
    accumulation-order tolerance for float moment channels (and for ALL
    channels when a ``weights`` channel is given — ``phist_pairs`` must then
    carry the same weighted statistics).
    """
    if num_slots % 2:
        raise ValueError("pair packing needs an even slot count")
    small_is_left = compute[0::2]                            # [pairs]
    if backend == "pallas":
        from repro.kernels import ops as kops
        return kops.histogram(bins, stats,
                              _packed_slot(slot, compute, num_slots),
                              num_slots=num_slots // 2, n_bins=n_bins,
                              phist=phist_pairs, side=small_is_left,
                              weights=weights)
    h_small = node_histogram_smaller_child(bins, stats, slot, compute,
                                           num_slots=num_slots, n_bins=n_bins,
                                           backend=backend, weights=weights)
    h_der = phist_pairs - h_small
    sl = small_is_left[:, None, None, None]
    return jnp.stack([jnp.where(sl, h_small, h_der),
                      jnp.where(sl, h_der, h_small)],
                     axis=1).reshape(num_slots, bins.shape[1], n_bins,
                                     stats.shape[-1])

"""Pure-jnp oracles for the Pallas kernels (the ``ref.py`` contract).

These are deliberately straight-line jnp with no tiling so they serve as the
ground truth for tests/test_kernels.py shape/dtype sweeps.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import heuristics as H
from repro.core.split import NEG_INF, _candidate_stats

__all__ = ["histogram_ref", "sibling_ref", "split_scan_ref"]


def histogram_ref(bins, stats, slot, *, num_slots, n_bins, weights=None):
    """H[S, K, B, C] += w[i] * stats[i] at (slot[i], k, bins[i,k]) — scatter
    oracle.  ``weights`` (optional [M] f32) is the per-example weight channel
    (GOSS amplification); ``None`` is the exact unweighted path (no multiply
    appears in the trace)."""
    m, k = bins.shape
    c = stats.shape[-1]
    if weights is not None:
        stats = stats * weights[:, None].astype(jnp.float32)
    idx = jnp.where(slot[:, None] < 0, num_slots * n_bins,
                    slot[:, None] * n_bins + bins)          # [M,K]
    oh = jax.nn.one_hot(idx, num_slots * n_bins, dtype=jnp.float32)
    h = jnp.einsum("mks,mc->ksc", oh, stats)
    return h.reshape(k, num_slots, n_bins, c).transpose(1, 0, 2, 3)


def sibling_ref(bins, stats, slot, slot_map, phist, side, *, num_pairs,
                n_bins, weights=None):
    """Oracle for the fused sibling-derivation epilogue.

    Packed smaller-child scatter (raw slots remapped through ``slot_map``,
    -1 drops the row), co-child derived as ``phist - H_small``, the pair
    interleaved to the full [2*num_pairs, K, B, C] child axis with
    ``side[j]`` nonzero meaning the computed child is the left slot.
    ``weights`` is the optional per-example weight channel; ``phist`` must
    have been accumulated from the same weighted statistics."""
    n_in = slot_map.shape[0]
    packed = jnp.where((slot >= 0) & (slot < n_in),
                       slot_map[jnp.clip(slot, 0, n_in - 1)], -1)
    h_small = histogram_ref(bins, stats, packed, num_slots=num_pairs,
                            n_bins=n_bins, weights=weights)
    h_der = phist - h_small
    sl = (side != 0)[:, None, None, None]
    k = bins.shape[1]
    return jnp.stack([jnp.where(sl, h_small, h_der),
                      jnp.where(sl, h_der, h_small)],
                     axis=1).reshape(2 * num_pairs, k, n_bins,
                                     stats.shape[-1])


def split_scan_ref(hist, n_num, n_cat, *, heuristic="info_gain", min_leaf=1):
    """Fused prefix-sum -> heuristic -> per-(slot,feature) argmax oracle.

    hist: [S,K,B,C].  Returns (score[S,K], bin[S,K], op[S,K]) — the best
    candidate per (node-slot, feature); the cross-feature argmax is a trivial
    postlude the kernel leaves to the caller.
    """
    h_fn = H.get(heuristic)
    s, k, b, c = hist.shape
    # the unfused path's candidate sides, each summed over its own bins
    pos, neg, valid = _candidate_stats(hist, n_num, n_cat)  # [3,S,K,B,C]
    moment = heuristic == "sse"
    cnt_p = pos[..., 0] if moment else pos.sum(-1)
    cnt_n = neg[..., 0] if moment else neg.sum(-1)
    score = h_fn(pos, neg)
    ok = valid[:, None] & (cnt_p >= min_leaf) & (cnt_n >= min_leaf)
    score = jnp.where(ok, score, NEG_INF)                   # [3,S,K,B]

    flat = score.transpose(1, 2, 0, 3).reshape(s, k, 3 * b)
    best = jnp.argmax(flat, axis=-1)
    best_score = jnp.take_along_axis(flat, best[..., None], axis=-1)[..., 0]
    return best_score, (best % b).astype(jnp.int32), (best // b).astype(jnp.int32)

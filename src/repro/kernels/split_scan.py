"""Pallas TPU kernel: fused prefix-sum -> heuristic -> argmax split scan.

Superfast Selection's inner loop (paper Algorithm 4 lines 10-36).  The
unfused jnp path materialises pos/neg tensors of shape [3, S, K, B, C] in
HBM — 6x the histogram's own footprint — making selection memory-bound.
This kernel keeps one node slot's [K, B, C] histogram block in VMEM, runs
the bin-axis sums, evaluates the heuristic for all 3 candidate
families, and reduces to a single (score, bin, op) triple per (node-slot,
feature).  HBM traffic drops from O(S*K*B*C * 7) to O(S*K*B*C + S*K) (read
once, write 3 scalars).

Layout: hist arrives as [S, K, B, C] (bins on sublanes, classes on lanes,
so the heuristics reduce over the lane axis exactly as the jnp path
does), the grid is (S, K-blocks), and each program walks its features with
a ``fori_loop``.  Each side of a candidate is summed over the bins that
go to it, as in ``core/split.py``: the bin-axis sums are matmuls with 0/1
matrices (exact at HIGHEST precision for integer counts below 2**24).
Per-feature ``n_num`` / ``n_cat`` sit in SMEM; the per-feature winners
are assembled into lane-dense [1, Kb] output rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import heuristics as H
from repro.core.split import NEG_INF

__all__ = ["split_scan_pallas"]

# features per grid program; the output row block is [1, Kb], which the TPU
# tiling accepts when Kb is all of K or a multiple of 128 lanes
FEATURE_BLOCK = 128


def _scan_kernel(nnum_ref, ncat_ref, hist_ref, score_ref, bin_ref, op_ref, *,
                 heuristic: str, min_leaf: int, n_bins: int, n_feat: int):
    h_fn = H.get(heuristic)
    moment = heuristic == "sse"
    kb = pl.program_id(1)
    bin_ids = jax.lax.broadcasted_iota(jnp.int32, (n_bins, 1), 0)
    row = jax.lax.broadcasted_iota(jnp.int32, (n_bins, n_bins), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (n_bins, n_bins), 1)
    # m @ x sums, for each bin i, the bins j that m[i, j] marks: up to i
    # (the inclusive prefix), after i, or every bin but i
    upto = (col <= row).astype(jnp.float32)
    above = (col > row).astype(jnp.float32)
    apart = (col != row).astype(jnp.float32)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, n_feat), 1)

    def scan(m, x):
        return jax.lax.dot_general(
            m, x, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)             # [B, C]

    def one_feature(f, carry):
        best_s, best_b, best_o = carry
        hist = hist_ref[f]                                  # [B, C] f32
        n_num = nnum_ref[kb * n_feat + f]
        n_cat = ncat_ref[kb * n_feat + f]
        is_num = bin_ids < n_num                            # [B, 1]
        is_cat = (bin_ids >= n_num) & (bin_ids < n_num + n_cat)

        # each side is a sum over the bins that go to it (core/split.py),
        # so a side that holds no row reads exactly 0
        num_hist = jnp.where(is_num, hist, 0.0)
        other = jnp.where(is_num, 0.0, hist).sum(axis=0, keepdims=True)
        prefix = scan(upto, num_hist)                       # numeric <= b
        suffix = scan(above, num_hist)                      # numeric > b
        rest = scan(apart, hist)                            # every bin but b

        def family(pos, neg, valid):
            cnt_p = pos[:, :1] if moment else pos.sum(-1, keepdims=True)
            cnt_n = neg[:, :1] if moment else neg.sum(-1, keepdims=True)
            s = h_fn(pos, neg)[:, None]                     # [B, 1]
            ok = valid & (cnt_p >= min_leaf) & (cnt_n >= min_leaf)
            return jnp.where(ok, s, NEG_INF)

        fams = (family(prefix, suffix + other, is_num),
                family(suffix, prefix + other, is_num),
                family(hist, rest, is_cat))
        # first maximum in op-major (op, bin) order == argmax over the
        # flattened [3, B] scores, as ref.split_scan_ref takes it
        top = functools.reduce(
            jnp.maximum, [s.max(axis=0, keepdims=True) for s in fams])
        first = [jnp.where(s >= top, bin_ids, n_bins).min(axis=0,
                                                          keepdims=True)
                 for s in fams]                             # 3 x [1, 1]
        op = jnp.where(first[0] < n_bins, 0,
                       jnp.where(first[1] < n_bins, 1, 2))
        tbin = jnp.where(op == 0, first[0],
                         jnp.where(op == 1, first[1], first[2]))
        mine = lanes == f
        return (jnp.where(mine, top, best_s), jnp.where(mine, tbin, best_b),
                jnp.where(mine, op, best_o))

    init = (jnp.full((1, n_feat), NEG_INF, jnp.float32),
            jnp.zeros((1, n_feat), jnp.int32),
            jnp.zeros((1, n_feat), jnp.int32))
    best_s, best_b, best_o = jax.lax.fori_loop(0, n_feat, one_feature, init)
    score_ref[...] = best_s
    bin_ref[...] = best_b
    op_ref[...] = best_o


@functools.partial(jax.jit, static_argnames=("heuristic", "min_leaf", "interpret"))
def split_scan_pallas(hist, n_num, n_cat, *, heuristic: str = "info_gain",
                      min_leaf: int = 1, interpret: bool = False):
    """hist [S,K,B,C] f32 -> (score [S,K] f32, bin [S,K] i32, op [S,K] i32).

    The cross-feature argmax (one [S,K] reduction) is left to the caller so
    the kernel's outputs match ref.split_scan_ref exactly.
    """
    s, k, b, c = hist.shape
    kb = k if k <= FEATURE_BLOCK else FEATURE_BLOCK
    n_kb = -(-k // kb)
    k_pad = n_kb * kb
    # padded features have no numeric and no categorical bins: never valid
    pad = lambda v: jnp.pad(v.astype(jnp.int32), (0, k_pad - k))
    hist = jnp.pad(hist, ((0, 0), (0, k_pad - k), (0, 0), (0, 0)))
    kern = functools.partial(_scan_kernel, heuristic=heuristic,
                             min_leaf=min_leaf, n_bins=b, n_feat=kb)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    row = pl.BlockSpec((None, 1, kb), lambda si, ki: (si, 0, ki))
    score, tbin, op = pl.pallas_call(
        kern,
        grid=(s, n_kb),
        in_specs=[smem, smem,
                  pl.BlockSpec((None, kb, b, c),
                               lambda si, ki: (si, ki, 0, 0))],
        out_specs=[row, row, row],
        out_shape=[
            jax.ShapeDtypeStruct((s, 1, k_pad), jnp.float32),
            jax.ShapeDtypeStruct((s, 1, k_pad), jnp.int32),
            jax.ShapeDtypeStruct((s, 1, k_pad), jnp.int32),
        ],
        interpret=interpret,
    )(pad(n_num), pad(n_cat), hist)
    return score[:, 0, :k], tbin[:, 0, :k], op[:, 0, :k]

"""Pallas TPU kernel: node/feature/bin histogram via one-hot MXU matmul.

GPU tree-boosting systems build histograms with shared-memory atomics.  TPUs
have no atomics; the TPU-native formulation turns the scatter into a matmul
on the 128x128 systolic MXU:

    for each tile of Mt examples, for each feature k:
        onehot[S*B, Mt]  = (iota[:, None] == joint_idx_k[None, :])
        H_k[C, S*B]     += statsT[C, Mt] @ onehot.T          (MXU)

Layout notes (TPU tiling: last dim = 128 lanes, 2nd-to-last = 8 sublanes):
  * every per-example operand keeps the examples on the lanes: bins as
    [K, M] (a [kb, Mt] block, kb = 8 features or all of K), the statistics
    as [C, M], the slot ids and weights as [1, M] rows.  The one-hot tile
    puts the joint (slot, bin) index on sublanes, so a [1, Mt] index row
    broadcasts down it with no relayout, and the matmul contracts both
    operands over their lane (example) axis.
  * the kernel accumulates H in [C, S*B] layout so the huge S*B axis sits on
    the lanes; the public wrapper (ops.py) transposes back to [S,K,B,C].
  * grid = (K / kb, n_slot_chunks, n_example_tiles); the example axis is the
    innermost (sequential) dimension, so each [kb, C, Sc*B] output block
    stays resident in VMEM across the whole example stream (one HBM
    write-back per (feature block, slot-chunk), the classic
    reduction-friendly grid order).  A ``fori_loop`` walks the kb features
    of a block, so only one one-hot tile is live at a time.
  * VMEM working set = onehot tile (Sc*B x Mt f32) + output block; the
    wrapper picks Sc so this fits the ~16 MiB VMEM budget.
  * the matmul runs at HIGHEST precision: the one-hot side is exact in any
    precision, but float statistics (moments, weights) are not exact in
    one bf16 pass.

Optional per-example weight channel (``weights`` input): GOSS-sampled
boosting accumulates ``w[i] * stats[i]`` rows, with the amplified
small-gradient weight ``(1-a)/b`` applied to the [C, Mt] stats tile in VMEM
right before the matmul — the weighted-stats tensor never exists in HBM and
``weights=None`` compiles the exact pre-weighting kernel.

Fused sibling-derivation epilogue (``phist``/``side`` inputs): the
sibling-subtraction builder scatters only the smaller child of each split
pair (the caller remaps slot ids onto the packed pair axis) and derives
the co-child as ``H_parent - H_small``.  Without fusion that derivation
is a jnp subtract/interleave *after* the kernel, so every derived
sibling round-trips through HBM.  With ``phist`` given:

  * ``num_slots`` counts packed *pairs*; the smaller-child block accumulates
    in a VMEM scratch buffer ([kb, C, Sc*B], persistent across the
    sequential example-tile axis) instead of the output ref,
  * ``phist`` arrives pre-transposed to the kernel layout [K, n_sc, C, Sc*B]
    (one parent row per pair, the exact layout of a packed kernel output)
    and is block-sliced per (feature block, slot-chunk) like the output,
  * after the last example tile the epilogue reads the parent block, forms
    ``derived = parent - small`` in VMEM, and stores the (left, right)
    child blocks [kb, 2, C, Sc*B], choosing per lane with ``side[j]``
    repeated over pair j's B lanes (which side the computed child lands
    on) — a lane-wise select, so no relayout inside the kernel.  Derived
    siblings therefore never exist in HBM as a separate tensor and the
    level step's jaxpr carries no jnp sibling subtraction; the wrapper's
    final transpose interleaves the pairs into the full slot axis.

Checked in interpret mode against ref.histogram_ref / ref.sibling_ref
(tests/test_kernels.py), and compiled by Mosaic for a v5e chip at
KDD99-10% width (tests/test_tpu_compile.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["histogram_pallas", "DEFAULT_EXAMPLE_TILE", "TPU_VMEM_BYTES"]

DEFAULT_EXAMPLE_TILE = 512

# per-core VMEM on current TPU generations (~16 MB; see the accelerator
# memory hierarchy: HBM -> VMEM -> compute).  Every pallas_call's resident
# blocks (input tiles + output block + scratch) must fit well under this;
# repro.check's ScratchBudget rule estimates each kernel's block bytes
# from its traced ref avals against this cap, so a BlockSpec / tile-size
# change that would spill VMEM fails the check-gate instead of Mosaic.
TPU_VMEM_BYTES = 16 * 2 ** 20


# features per grid program: the bins block is [kb, Mt], and the TPU
# tiling wants its second-minor dim a multiple of 8 sublanes (or all of K)
FEATURE_BLOCK = 8


def _hist_kernel(bins_ref, stats_t_ref, slot_ref, *refs, n_bins: int,
                 slot_chunk: int, n_feat: int, n_tiles: int,
                 has_weights: bool, fused: bool):
    refs = list(refs)
    weights_ref = refs.pop(0) if has_weights else None
    phist_ref, side_ref = ((refs.pop(0), refs.pop(0)) if fused
                           else (None, None))
    out_ref = refs.pop(0)
    # fused mode accumulates in scratch so the output ref can hold the
    # [kb, 2, C, Sc*B] (left, right) child block written once by the
    # epilogue
    acc_ref = refs.pop(0) if fused else out_ref
    # grid axis 0 is the feature block (its blocks are pre-sliced, so the
    # kernel never reads that program id)
    sc = pl.program_id(1)       # slot chunk
    t = pl.program_id(2)        # example tile (innermost, sequential)

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    slot = slot_ref[...]                                     # [1, Mt] i32
    stats_t = stats_t_ref[...]                               # [C, Mt] f32
    if has_weights:
        # per-example weight channel (GOSS amplification): scale the [C, Mt]
        # stats tile once in VMEM; the weighted rows then flow through the
        # same one-hot matmul (and epilogue) as the unweighted path, so the
        # widened M x C weighted-stats tensor never exists in HBM.
        stats_t = stats_t * weights_ref[...]                 # [C, Mt]

    # padded rows carry slot -1, so they fall outside every chunk
    local = slot - sc * slot_chunk
    in_chunk = (local >= 0) & (local < slot_chunk)
    sb = slot_chunk * n_bins
    mt = slot.shape[1]
    # one-hot with the joint (slot, bin) index on sublanes and the examples
    # on lanes, so the [1, Mt] index rows broadcast without a relayout
    rows = jax.lax.broadcasted_iota(jnp.int32, (sb, mt), 0)

    def one_feature(f, carry):
        bins = bins_ref[pl.ds(f, 1), :]                      # [1, Mt] i32
        joint = jnp.where(in_chunk, local * n_bins + bins, -1)
        onehot = (rows == joint).astype(jnp.float32)         # [SB, Mt]
        acc_ref[f] += jax.lax.dot_general(
            stats_t, onehot, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)              # [C, SB]
        return carry

    jax.lax.fori_loop(0, n_feat, one_feature, 0)

    if fused:
        @pl.when(t == n_tiles - 1)
        def _sibling_epilogue():
            # derive the co-child from the cached parent block and emit the
            # (left, right) pair blocks straight from VMEM (nothing but the
            # final store touches HBM).  side_ref holds side[j] repeated
            # over pair j's B lanes, so no lane relayout is needed.
            small = acc_ref[...]                             # [kb, C, SB]
            derived = phist_ref[...] - small
            sl = side_ref[...] != 0                          # [1, SB]
            out_ref[:, 0] = jnp.where(sl, small, derived)
            out_ref[:, 1] = jnp.where(sl, derived, small)


@functools.partial(jax.jit, static_argnames=(
    "num_slots", "n_bins", "slot_chunk", "example_tile", "interpret"))
def histogram_pallas(bins, stats, slot, *, num_slots: int, n_bins: int,
                     slot_chunk: int = 16, example_tile: int = DEFAULT_EXAMPLE_TILE,
                     interpret: bool = False, weights=None, phist=None,
                     side=None):
    """bins [M,K] i32, stats [M,C] f32, slot [M] i32 -> H [S,K,B,C] f32.

    ``weights`` (optional [M] f32) accumulates ``w[i] * stats[i]`` instead of
    ``stats[i]``: the per-example weight channel of GOSS-sampled boosting.
    The multiply happens on the [C, Mt] stats tile in VMEM, so weighting adds
    no HBM traffic; ``None`` compiles the identical kernel as before (the
    unweighted path stays bit-exact by construction).

    ``phist`` (optional [num_slots, K, B, C]) switches on the fused
    sibling-derivation epilogue: ``num_slots`` then counts packed *pairs*
    (``slot`` holds packed pair ids in [0, num_slots)), ``phist[j]`` is
    pair j's parent histogram row and ``side`` ([num_slots] i32, nonzero = the
    computed child is the left slot) fixes the interleave.  Returns the full
    [2*num_slots, K, B, C] child histogram with the co-child derived
    in-kernel as ``phist - H_small`` (see the module docstring).

    ``example_tile`` must be a multiple of 128 when compiled for a TPU
    (``interpret=False``); interpret mode takes any tile.
    """
    fused = phist is not None
    m, k = bins.shape
    c = stats.shape[-1]
    kb = k if k <= FEATURE_BLOCK else FEATURE_BLOCK
    n_kb = -(-k // kb)
    k_pad = n_kb * kb
    n_sc = -(-num_slots // slot_chunk)
    n_t = -(-m // example_tile)
    m_pad = n_t * example_tile

    bins_t = jnp.pad(bins, ((0, m_pad - m), (0, k_pad - k))).T   # [Kp, Mp]
    stats_t = jnp.pad(stats, ((0, m_pad - m), (0, 0))).T         # [C, Mp]
    slot_p = jnp.pad(slot, (0, m_pad - m), constant_values=-1)[None]

    row_spec = pl.BlockSpec((1, example_tile), lambda kf, sc, t: (0, t))
    in_specs = [
        pl.BlockSpec((kb, example_tile), lambda kf, sc, t: (kf, t)),
        pl.BlockSpec((c, example_tile), lambda kf, sc, t: (0, t)),
        row_spec,
    ]
    operands = [bins_t, stats_t, slot_p]
    if weights is not None:
        w_p = jnp.pad(weights.astype(jnp.float32), (0, m_pad - m))[None]
        in_specs.append(row_spec)
        operands.append(w_p)

    sb = slot_chunk * n_bins
    s_pad = n_sc * slot_chunk
    block = lambda kf, sc, t: (kf, sc, 0, 0)
    scratch_shapes = []
    if fused:
        # parent rows, pre-transposed to the packed kernel output layout
        # [Kp, n_sc, C, Sc*B] so the per-(feature block, slot-chunk)
        # BlockSpec is the same shape as a packed output block
        ph = jnp.pad(phist, ((0, s_pad - num_slots), (0, k_pad - k),
                             (0, 0), (0, 0)))
        ph = ph.reshape(n_sc, slot_chunk, k_pad, n_bins, c)
        ph = ph.transpose(2, 0, 4, 1, 3).reshape(k_pad, n_sc, c, sb)
        in_specs.append(pl.BlockSpec((kb, None, c, sb), block))
        operands.append(ph)
        # side[j] repeated over pair j's B lanes: [n_sc, 1, Sc*B]
        side_p = jnp.pad(side.astype(jnp.int32), (0, s_pad - num_slots))
        side_l = jnp.repeat(side_p, n_bins).reshape(n_sc, 1, sb)
        in_specs.append(pl.BlockSpec((None, 1, sb),
                                     lambda kf, sc, t: (sc, 0, 0)))
        operands.append(side_l)
        scratch_shapes = [pltpu.VMEM((kb, c, sb), jnp.float32)]
        out_spec = pl.BlockSpec((kb, None, 2, c, sb),
                                lambda kf, sc, t: (kf, sc, 0, 0, 0))
        out_shape = (k_pad, n_sc, 2, c, sb)
    else:
        out_spec = pl.BlockSpec((kb, None, c, sb), block)
        out_shape = (k_pad, n_sc, c, sb)

    out = pl.pallas_call(
        functools.partial(_hist_kernel, n_bins=n_bins, slot_chunk=slot_chunk,
                          n_feat=kb, n_tiles=n_t,
                          has_weights=weights is not None, fused=fused),
        grid=(n_kb, n_sc, n_t),
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct(out_shape, jnp.float32),
        scratch_shapes=scratch_shapes,
        interpret=interpret,
    )(*operands)

    if fused:
        # out[kf, sc, side, ch, j*B + bin] -> full slot 2*(sc*Sc + j) + side
        h = out.reshape(k_pad, n_sc, 2, c, slot_chunk, n_bins)
        h = h.transpose(1, 4, 2, 0, 5, 3).reshape(2 * s_pad, k_pad, n_bins, c)
        return h[:2 * num_slots, :k]
    h = out.reshape(k_pad, n_sc, c, slot_chunk, n_bins)
    h = h.transpose(1, 3, 0, 4, 2).reshape(s_pad, k_pad, n_bins, c)
    return h[:num_slots, :k]

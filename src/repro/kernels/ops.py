"""Jit'd public wrappers around the Pallas kernels.

Each call decides from the platform it runs on whether the kernel is
compiled by Mosaic or interpreted: on a TPU the kernels always compile,
on any other backend (the CPU test suite) they run in Pallas interpret
mode, where the kernel body executes as plain XLA for correctness.  The
decision is made when the wrapper is called, never at import, so
importing this module does not start a JAX backend.
"""
from __future__ import annotations

import jax

from repro.kernels.histogram import histogram_pallas
from repro.kernels.split_scan import split_scan_pallas

__all__ = ["histogram", "split_scan", "interpret_mode"]


def interpret_mode() -> bool:
    """True unless the default backend is a TPU (Mosaic compiles there)."""
    return jax.default_backend() != "tpu"


def histogram(bins, stats, slot, *, num_slots, n_bins, slot_chunk=None,
              weights=None, phist=None, side=None):
    """H[S,K,B,C] via the one-hot-MXU Pallas kernel (see kernels/histogram.py).

    slot_chunk defaults so the per-program onehot tile (Sc*B x Mt f32) stays
    within a ~4 MiB VMEM budget.  ``weights`` ([M] f32 or None) is the
    per-example weight channel: rows accumulate ``w[i] * stats[i]`` (the
    multiply runs in-kernel on the VMEM stats tile).

    ``phist``/``side`` select the fused sibling-derivation epilogue:
    ``num_slots`` then counts packed pairs, ``phist`` [num_slots,K,B,C] is
    the per-pair parent row and the kernel returns the full
    [2*num_slots,K,B,C] child histogram with the co-child derived in VMEM
    (no post-kernel jnp subtraction).  The fused epilogue additionally holds
    the parent block and the 2x-wide (left, right) output block in VMEM, so
    the auto slot_chunk charges each packed slot double.
    """
    if slot_chunk is None:
        budget_lanes = (4 << 20) // (4 * 512)               # Mt=512 rows
        per_slot = (2 if phist is not None else 1) * max(1, n_bins)
        slot_chunk = max(1, min(num_slots, budget_lanes // per_slot))
    return histogram_pallas(bins, stats, slot, num_slots=num_slots,
                            n_bins=n_bins, slot_chunk=slot_chunk,
                            interpret=interpret_mode(), weights=weights,
                            phist=phist, side=side)


def split_scan(hist, n_num, n_cat, *, heuristic="info_gain", min_leaf=1):
    """Fused selection scan (see kernels/split_scan.py)."""
    return split_scan_pallas(hist, n_num, n_cat, heuristic=heuristic,
                             min_leaf=min_leaf,
                             interpret=interpret_mode())

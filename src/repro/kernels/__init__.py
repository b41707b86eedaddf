"""Pallas TPU kernels for Superfast Selection's two hot spots:

  histogram.py   one-hot MXU matmul histogram (no TPU atomics -> matmul)
  split_scan.py  fused prefix-sum -> heuristic -> argmax selection scan

Each kernel ships with a jit'd wrapper (ops.py: compiled by Mosaic on a
TPU, interpreted elsewhere) and a pure-jnp oracle (ref.py);
tests/test_kernels.py sweeps shapes/dtypes against the oracles in interpret
mode and tests/test_tpu_compile.py compiles both kernels for a v5e chip.
"""
from repro.kernels import ops, ref  # noqa: F401

"""The least work of one level-synchronous tree fit, from the fitted
tree's structure and the rows that reached each node.

It counts the work, not what today's kernels do: the cheapest
level-synchronous plan histograms the root's rows, then at each later
level only the smaller child of each sibling pair (the larger one is the
parent's histogram minus it).  Each such row is read once: its K bins at
the narrowest integer that holds ``n_bins``, ``channels`` float32
statistics and an int32 node id.  Each level writes its [S, K, B,
channels] float32 histograms, S being the nodes it holds.
"""
from __future__ import annotations

import numpy as np


def bin_bytes(n_bins: int) -> int:
    """Bytes of the narrowest unsigned integer that holds every bin id."""
    for width in (1, 2, 4):
        if n_bins <= 1 << (8 * width):
            return width
    return 8


def histogram_bytes(tree: dict, k: int, n_bins: int, channels: int) -> int:
    """Least HBM bytes of one tree's histogram passes.  ``tree`` holds
    ``depth``, ``left``, ``right`` and ``rows`` (rows per node)."""
    depth, left, right = tree["depth"], tree["left"], tree["right"]
    rows = np.asarray(tree["rows"], dtype=np.int64)
    per_row = k * bin_bytes(n_bins) + 4 * channels + 4
    read = int(rows[0])
    written = 0
    for d in range(1, int(depth.max()) + 1):
        width = int((depth == d).sum())
        written += width * k * n_bins * channels * 4
    split = np.flatnonzero(left >= 0)
    read += int(np.minimum(rows[left[split]], rows[right[split]]).sum())
    return read * per_row + written

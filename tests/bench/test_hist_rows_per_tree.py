"""``hist_rows_per_tree.fit``, the rows the level steps histogram per
tree: on a traced CPU fit of a tiny ``kdd99-udt`` it equals the least
rows ``bench/counts.py`` counts (the root's, then each smaller child),
and of a program without ``udt.hist`` spans it reads nothing."""
import gzip
import sys
import types
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, trace  # noqa: E402

NAME = "hist_rows_per_tree.fit"
SCOPES_GZ = Path(__file__).parent / "data" / "v5e_scopes.xplane.pb.gz"


def least_rows(tree):
    """The root's rows, then the smaller child of every split pair."""
    rows, left, right = (np.asarray(tree[f]) for f in ("rows", "left",
                                                        "right"))
    split = np.flatnonzero(left >= 0)
    return int(rows[0] + np.minimum(rows[left[split]],
                                    rows[right[split]]).sum())


def test_a_traced_tiny_fit_reads_the_least_rows_per_tree(tmp_path):
    import jax
    cell = harness.load_cell("kdd99-udt")
    cell.config = dict(cell.config, rows=3_000, val_rows=500,
                       bin_sample_rows=3_000)
    cell.per_layer = [m for m in cell.per_layer if m["name"] == NAME]
    assert cell.per_layer, "the metric is not in BENCHMARK.json's cell"
    line, run = harness.run_cell(cell, 2 ** 31 + 5, 0.5, True, 0.0,
                                 jax.devices(), str(tmp_path / "trace"))
    assert line["correct"], line["checks"]
    trees = run.work["trees"]
    want = np.mean([least_rows(t) for t in trees])
    assert line["metrics"][NAME] == {"value": want, "unit": "rows/tree"}
    assert len(run.window.units) >= 1
    # under the rows every level would take without compaction
    depth = int(np.asarray(trees[0]["depth"]).max())
    assert 3_000 <= want < 3_000 * depth


def test_a_program_without_hist_spans_reads_nothing(tmp_path):
    """A fit recorded on a v5e chip by a program that marks its levels and
    reads but has no ``udt.hist`` span, as the parent of this metric."""
    pb = tmp_path / "v5e_scopes.xplane.pb"
    pb.write_bytes(gzip.decompress(SCOPES_GZ.read_bytes()))
    run = types.SimpleNamespace(
        trace=trace.load(str(tmp_path)),
        window=types.SimpleNamespace(trace_dir=str(tmp_path), units=[None]))
    assert harness.metric_reader(NAME).read(NAME, run) is None

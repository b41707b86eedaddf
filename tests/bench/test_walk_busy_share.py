"""``walk_busy_share.boost``: the device time of boosting's raw-score
update (the ops in the program's ``boost.walk`` scope) over the busy
device time of the window.  A CPU trace has no device plane, so the
traced CPU fit here lends its window and round spans to a device plane
made of the update program's own compiled ops; a chip trace of a program
without the scope reads nothing."""
import gzip
import re
import sys
import types
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, trace, xplane  # noqa: E402

NAME = "walk_busy_share.boost"
SCOPES_GZ = Path(__file__).parent / "data" / "v5e_scopes.xplane.pb.gz"
CHIP = "/device:TPU:0"


def read(run):
    return harness.metric_reader(NAME).read(NAME, run)


def update_op_paths():
    """The scope paths (``tf_op`` on a chip trace) of the compiled
    raw-score update's instructions, parameters left out."""
    import jax.numpy as jnp
    from repro.core import GradientBoostedTrees, TreeConfig, fit_bins
    from repro.core import forest
    rng = np.random.default_rng(0)
    x = rng.normal(size=(256, 3))
    y = (x[:, 0] > 0).astype(np.float32)
    table = fit_bins([x[:, j] for j in range(3)], max_num_bins=8)
    gbt = GradientBoostedTrees(n_trees=1, loss="logistic", config=TreeConfig(
        max_depth=3, task="regression_variance")).fit(table, y)
    text = forest._raw_update.lower(
        forest.predict_bins, jnp.zeros(256), 0.3, gbt.trees[0],
        jnp.asarray(table.bins), jnp.asarray(table.n_num),
        num_steps=3).compile().as_text()
    return [m.group(1) for line in text.splitlines()
            if " = " in line and "parameter(" not in line
            for m in [re.search(r'op_name="([^"]+)"', line)] if m]


def test_a_traced_tiny_boosting_fit_reads_the_walk_share(tmp_path,
                                                         monkeypatch):
    import jax
    cell = harness.load_cell("higgs-boost")
    cell.config = dict(cell.config, rows=4_000, bin_sample_rows=4_000)
    cell.per_layer = [m for m in cell.per_layer if m["name"] == NAME]
    assert cell.per_layer, "the metric is not in BENCHMARK.json's cell"
    line, run = harness.run_cell(cell, 2 ** 31 + 7, 0.5, True, 0.0,
                                 jax.devices(), str(tmp_path / "trace"))
    assert line["correct"], line["checks"]
    tr = xplane.for_run(run)
    rounds = xplane.window_spans(tr, run.trace, "boost.round")
    assert len(rounds) >= len(run.window.units) >= 1
    paths = update_op_paths()
    assert len(paths) > 10
    assert all(xplane.in_scope(p, "boost.walk") for p in paths)
    # each window round: a level step for its first 4/5, then the
    # update's instructions back to back; a loop op spans the update and
    # encloses its body's ops, as on a chip
    ops, walk = [], 0.0
    for s, e, _ in rounds:
        cut = s + 0.8 * (e - s)
        ops.append(("fusion", s, cut, "jit(_chunk_step_impl)/level.histogram"))
        ops.append(("while", cut, e, "jit(_raw_update)/boost.walk/while"))
        step = (e - cut) / len(paths)
        ops += [(f"op{i}", cut + i * step, cut + (i + 1) * step, p)
                for i, p in enumerate(paths)]
        walk += e - cut
    busy = sum(e - s for s, e, _ in rounds)
    run.trace.chips = [trace.Chip(CHIP, [op[:3] for op in ops], [])]
    monkeypatch.setattr(xplane, "for_run",
                        lambda r: xplane.Trace({CHIP: ops}, tr.host))
    assert np.isclose(read(run), walk / busy, rtol=1e-9)
    assert np.isclose(read(run), 0.2, rtol=1e-6)


def test_the_reader_merges_and_clips_the_scope_ops_per_chip(monkeypatch):
    scope = "jit(_raw_update)/boost.walk"
    ops = {
        CHIP: [("fusion", 0, 500, "jit(_chunk_step_impl)/level.histogram"),
               ("while", 600, 800, scope + "/while"),
               ("gather", 610, 700, scope + "/while/body/gather"),
               ("select", 700, 790, scope + "/while/body/select"),
               ("mul", 950, 1100, scope + "/mul"),        # clipped at 1000
               ("add", -100, -50, scope + "/add")],       # before the window
        "/device:TPU:1": [("fusion", 0, 1000, "jit(_route_step)/level.route"),
                          ("gather", 200, 400, scope + "/gather")],
    }
    chips = [trace.Chip(n, [op[:3] for op in v], []) for n, v in ops.items()]
    run = types.SimpleNamespace(
        trace=trace.Summary(chips, [], 0.0, 1000.0),
        window=types.SimpleNamespace(trace_dir="unused", units=[None]))
    monkeypatch.setattr(xplane, "for_run",
                        lambda r: xplane.Trace(ops, []))
    # chip 0: walk 200 + 50 of busy 750; chip 1: 200 of 1000
    assert np.isclose(read(run), (250 + 200) / 2 / ((750 + 1000) / 2))
    # a window with no op in the scope reads nothing
    run.trace.w0, run.trace.w1 = 0.0, 150.0
    assert read(run) is None


def test_a_program_without_the_walk_scope_reads_nothing(tmp_path):
    """A UDT fit recorded on a v5e chip: level scopes, no ``boost.walk``."""
    pb = tmp_path / "v5e_scopes.xplane.pb"
    pb.write_bytes(gzip.decompress(SCOPES_GZ.read_bytes()))
    run = types.SimpleNamespace(
        trace=trace.load(str(tmp_path)),
        window=types.SimpleNamespace(trace_dir=str(tmp_path), units=[None]))
    assert run.trace.chips and trace.busy_s(run.trace) > 0
    assert read(run) is None

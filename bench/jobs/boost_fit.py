"""Job ``boost_fit``: one logistic boosting fit through the program's
``GradientBoostedTrees.fit``, round after round until the window closes.

Set-up makes the table from the seed, finds bin edges with the program's
``fit_bins`` on a sample of rows and bins every row with the same edges
(``bin_rows`` of the configuration; both timed as ``binning``), and puts
the bins and labels on the device.  The fit's first ``warmup_rounds``
rounds compile, or load, every program a round runs; the window opens at
the end of the last of them.  ``round_callback`` marks each round once
its raw scores are ready; a round is one unit of ``rows`` rows.  When the
window closes the callback stops the fit.

Check, once the window has closed: the program's bin edges and the
sample's bins must equal the reference's binning of the raw sample
(``bins_off``).  The reference recomputes every row's
raw score from the base score and the fitted trees in float64, and from
it each round's gradients and hessians.  For every round it routes the
rows down the round's tree and compares each node's value with the
Newton step -G/H of its rows (``value_gap``) and each leaf decision with
the stopping rules (``nodes_off``); on the first round of the window it
scores every split against every candidate of its node (``split_gap``);
and it compares the fit's raw scores after the last round with its own
(``score_gap``).
"""
from __future__ import annotations

import dataclasses
import sys

import numpy as np

from bench import reference as ref
from bench.harness import Check

# the check's limits; PERF.md gives the readings each was set from
LIMITS = {"bins_off": 0, "nodes_off": 0, "value_gap": 2e-3,
          "split_gap": 2e-3, "score_gap": 1e-4}


class WindowClosed(Exception):
    """Raised from the round callback to end the fit."""


def bin_table(cell, x, phases):
    """The host BinnedTable: edges from ``fit_bins`` on the first
    ``bin_sample_rows`` rows, every row binned with them."""
    from repro.core import fit_bins
    conf = cell.config
    with phases("binning"):
        sample = x[:conf["bin_sample_rows"]]
        fitted = fit_bins([sample[:, j] for j in range(x.shape[1])],
                          max_num_bins=conf["max_num_bins"])
        missing = [mt.missing_bin for mt in fitted.metas]
        bins = cell.data.bin_rows(x, [mt.edges for mt in fitted.metas],
                                  fitted.n_num, missing)
    return dataclasses.replace(fitted, bins=bins)


def setup(cell, seed, phases):
    import jax
    from repro.core import GradientBoostedTrees, TreeConfig
    conf, mix = cell.config, cell.mix
    with phases("data"):
        x, y = cell.data.synth(conf["rows"], seed, conf["signal_share"])
    table = bin_table(cell, x, phases)
    sample = x[:conf["bin_sample_rows"]].copy()
    del x
    with phases("transfer"):
        bins = jax.device_put(table.bins)
        y_d = jax.device_put(y)
        jax.block_until_ready((bins, y_d))
    gbt = GradientBoostedTrees(
        n_trees=mix["max_rounds"], loss=mix["loss"],
        learning_rate=mix["learning_rate"], seed=seed % (1 << 31),
        config=TreeConfig(max_depth=mix["max_depth"],
                          task="regression_variance",
                          hist_backend=conf["hist_backend"],
                          select_backend=conf["select_backend"]))
    return dict(cell=cell, table=table, y=y, gbt=gbt, sample=sample,
                table_d=dataclasses.replace(table, bins=bins), y_d=y_d,
                window_rounds=[])


def run_window(state, window, phases):
    import jax
    mix = state["cell"].mix
    m = len(state["y"])
    warm = mix["warmup_rounds"]
    warm_span = phases("warmup")
    warm_span.__enter__()

    def on_round(rs):
        jax.block_until_ready(rs.raw)
        if rs.round < warm:
            return
        if rs.round == warm:
            warm_span.__exit__(None, None, None)
            window.open()
            return
        state["window_rounds"].append(rs.round)
        if window.unit_done(rows=m):
            state["raw"] = rs.raw
            state["trees"] = list(rs.trees)
            raise WindowClosed

    try:
        state["gbt"].fit(state["table_d"], state["y_d"],
                         round_callback=on_round)
    except WindowClosed:
        pass


def release(state):
    state["trees"] = [ref.host_tree(t) for t in state["trees"]]
    state["raw"] = np.asarray(state["raw"], dtype=np.float64)
    for k in ("table_d", "y_d", "gbt"):
        state.pop(k)


def newton_rows(y, raw, eps=1e-6):
    """Boosting's per-row statistics (h, -g, g^2/h) of the logistic loss
    at raw scores ``raw``, with its hessian floor."""
    p = 1.0 / (1.0 + np.exp(-raw))
    g = p - y
    h = np.maximum(p * (1.0 - p), eps)
    return np.stack([h, -g, g * g / h], axis=1)


def base_score(y, eps=1e-6):
    p = np.clip(np.mean(y, dtype=np.float64), eps, 1 - eps)
    return np.log(p) - np.log1p(-p)


def check_rounds(trees, raw_fit, table, y, mix, split_round):
    """The numbers compared for ``trees`` (host arrays, in fit order)
    whose rows' raw scores after the last are ``raw_fit``; every split of
    round ``split_round`` is scored against every candidate.  Returns
    (nodes_off, value_gap, split_gap, score_gap, rows per node of each
    round, rounds that failed)."""
    rules = ref.Rules("newton", max_depth=mix["max_depth"])
    rows_ref = ref.Rows(table.bins, int(table.n_bins))
    raw = np.full(len(y), base_score(y))
    nodes_off, values, splits, rows, bad = 0, [], [], [], []
    for r, tree in enumerate(trees):
        chk = ref.check_tree(tree, rows_ref, newton_rows(y, raw),
                             table.n_num, table.n_cat, rules,
                             split_nodes=None if r == split_round else [])
        nodes_off += chk.nodes_off
        values += chk.value_gaps
        splits += chk.split_gaps
        rows.append(chk.row_counts)
        if chk.nodes_off:
            bad.append(r)
        for note in chk.notes[:5]:
            print(f"check: round {r} {note}", file=sys.stderr)
        raw = raw + mix["learning_rate"] * tree["label"][chk.final_nodes]
    score_gap = float(np.max(np.abs(raw_fit - raw))
                      / max(np.max(np.abs(raw)), 1e-30))
    return (nodes_off, ref.scaled_max(values), ref.scaled_max(splits),
            score_gap, rows, bad)


def bins_off(table, sample, max_num_bins):
    """The program's edges and the sample's bins against the reference's
    binning of the raw sample."""
    cols = [sample[:, j] for j in range(sample.shape[1])]
    layouts = [ref.column_layout(c, max_num_bins) for c in cols]
    return ref.table_off(table.bins[:len(sample)], table.n_num, table.n_cat,
                         cols, layouts, edges=[m.edges for m in table.metas])


def check(state):
    mix = state["cell"].mix
    trees = state["trees"]
    first = mix["warmup_rounds"]
    nodes_off, vgap, sgap, scgap, rows, bad = check_rounds(
        trees, state["raw"], state["table"], state["y"], mix, first)
    state["row_counts"] = rows
    off = bins_off(state["table"], state["sample"],
                   state["cell"].config["max_num_bins"])
    checks = [Check("bins_off", float(off), LIMITS["bins_off"]),
              Check("nodes_off", float(nodes_off), LIMITS["nodes_off"]),
              Check("value_gap", vgap, LIMITS["value_gap"]),
              Check("split_gap", sgap, LIMITS["split_gap"]),
              Check("score_gap", scgap, LIMITS["score_gap"])]
    window = len(state["window_rounds"])
    failed = (window if not all(c.ok for c in checks if c.name != "nodes_off")
              else len([r for r in bad if r >= first]))
    return window, failed, checks


def work(state):
    """The window rounds' trees with the rows that reached each node."""
    table = state["table"]
    first = state["cell"].mix["warmup_rounds"]
    return dict(trees=[dict(t, rows=c) for t, c in
                       zip(state["trees"][first:],
                           state["row_counts"][first:])],
                k=table.bins.shape[1], n_bins=int(table.n_bins), channels=2)

"""Job ``tune``: Training-Only-Once Tuning calls, one after another,
through the program's ``tune`` on one full tree.

Set-up takes the configuration's fixed training table and validation
draw, each in an order drawn from the seed, bins them with the program's
``fit_bins`` and ``transform`` (both timed as ``binning``), grows the full
tree with ``build_tree`` and calls ``tune`` once to compile, or load,
its programs.  The window then calls ``tune`` over the paper's grid
(depths 1 .. full depth x ``smin_steps`` values of min_samples_split);
each call is one unit of that many configurations.

Check: both binned tables must equal the reference's binning of the raw
columns (``bins_off``); every call of the window must return the first
call's grid and
choice, and the first is compared with the reference's grid, computed by
pruning the same full tree row by row, cell by cell, and with the cell
the reference chooses (highest accuracy, then fewest nodes, then first
in grid order).
"""
from __future__ import annotations

import numpy as np

from bench import reference as ref
from bench.harness import Check
from bench.jobs import udt_fit

# exact comparisons; PERF.md gives the readings
LIMITS = {"bins_off": 0, "calls_differ": 0, "grid_off": 0, "best_off": 0}


def setup(cell, seed, phases):
    import jax
    from repro.core import build_tree, transform, tune
    table_d, table, y, cols = udt_fit.make_table(cell, seed, phases)
    conf = cell.config
    c = conf["classes"]
    with phases("data"):
        vcols, vy = cell.data.draw(conf, conf["val_rows"], seed + 1,
                                   offset=1)
    with phases("binning"):
        vbins = transform(vcols, table)
    with phases("transfer"):
        vbins_d = jax.device_put(vbins)
        vbins_d.block_until_ready()
    with phases("full_tree"):
        full = build_tree(table_d, y, udt_fit.tree_config(cell), n_classes=c)
        jax.block_until_ready(full)
    vy = np.asarray(vy)
    with phases("warmup"):
        tune(full, vbins_d, vy, table.n_num, train_size=len(y))
    return dict(cell=cell, table=table, y=y, full=full, vbins=vbins,
                vbins_d=vbins_d, vy=vy, cols=cols, vcols=vcols, results=[])


def run_window(state, window, phases):
    from repro.core import tune
    full, vb, vy, n_num = (state["full"], state["vbins_d"], state["vy"],
                           state["table"].n_num)
    m = len(state["y"])
    window.open()
    while True:
        res = tune(full, vb, vy, n_num, train_size=m)
        state["results"].append(res)
        if window.unit_done(configs=res.n_configs):
            break


def release(state):
    state["full"] = ref.host_tree(state["full"])
    state.pop("vbins_d")


def grid_axes(tree, train_size, mix):
    """The paper's grid: depths 1 .. the full tree's depth, and
    ``smin_steps`` values of min_samples_split from 0 in steps of
    ``smin_step_share`` of the training rows."""
    dmax = np.arange(1, int(tree["depth"].max()) + 1)
    smin = np.round(np.arange(mix["smin_steps"])
                    * (mix["smin_step_share"] * train_size)).astype(np.int64)
    return dmax, smin


def check(state):
    results, full = state["results"], state["full"]
    first = results[0]
    same = [np.array_equal(r.grid.metric, first.grid.metric)
            and (r.best_dmax, r.best_smin) == (first.best_dmax,
                                                first.best_smin)
            for r in results[1:]]
    dmax, smin = grid_axes(full, len(state["y"]), state["cell"].mix)
    vrows = ref.Rows(state["vbins"], int(state["table"].n_bins))
    counts = ref.toot_counts(full, vrows, state["vy"],
                             state["table"].n_num, dmax, smin)
    want = counts / len(state["vy"])
    g = first.grid
    if (np.array_equal(g.dmax, dmax) and np.array_equal(g.smin, smin)
            and g.metric.shape == want.shape):
        grid_off = int((g.metric != want).sum())
    else:
        grid_off = int(want.size)
    i, j = ref.toot_best(counts, full, dmax, smin)
    best_off = int((first.best_dmax, first.best_smin)
                   != (int(dmax[i]), int(smin[j])))
    table = state["table"]
    bins_off = udt_fit.bins_off(state) + ref.table_off(
        state["vbins"], table.n_num, table.n_cat, state["vcols"],
        state["layouts"])
    checks = [Check("bins_off", float(bins_off), LIMITS["bins_off"]),
              Check("calls_differ", float(len(same) - sum(same)),
                    LIMITS["calls_differ"]),
              Check("grid_off", float(grid_off), LIMITS["grid_off"]),
              Check("best_off", float(best_off), LIMITS["best_off"])]
    failed = (len(results) if grid_off or best_off or bins_off
              else len(same) - sum(same))
    return len(results), failed, checks


def work(state):
    return dict(calls=len(state["results"]))

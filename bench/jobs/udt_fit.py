"""Job ``udt_fit``: full UDT fits, one after another, through the
program's ``build_tree`` on one binned table held on the device.

Set-up takes the configuration's fixed table in the order the seed
draws (``draw`` of the configuration), bins it with the program's
``fit_bins`` (timed as ``binning``), puts the bins on the device and
builds one tree to compile, or load, every level-step shape the fits
use.  The window then fits tree after tree; each is one unit of
``rows`` training rows.

Check: the program's binned table must equal the reference's binning of
the raw columns (``bins_off``); every tree of the window must equal the
first bit for bit, and the first is checked node by node against the
reference (counts, labels, leaf decisions, and each split against every
candidate of its node).
"""
from __future__ import annotations

import dataclasses
import sys

import numpy as np

from bench import reference as ref
from bench.harness import Check

# the check's limits; PERF.md gives the readings each was set from
LIMITS = {"bins_off": 0, "trees_differ": 0, "nodes_off": 0,
          "split_gap": 5e-3}


def tree_config(cell):
    from repro.core import TreeConfig
    mix, conf = cell.mix, cell.config
    return TreeConfig(max_depth=mix["max_depth"],
                      min_samples_split=mix["min_samples_split"],
                      hist_backend=conf["hist_backend"],
                      select_backend=conf["select_backend"])


def host_table(cell, seed, phases):
    """(raw columns, the program's host BinnedTable, labels) of the run."""
    from repro.core import fit_bins
    conf = cell.config
    with phases("data"):
        cols, y = cell.data.draw(conf, conf["rows"], seed)
    with phases("binning"):
        table = fit_bins(cols, max_num_bins=conf["max_num_bins"])
    return cols, table, np.asarray(y)


def make_table(cell, seed, phases):
    """(device table, host table, labels, raw columns) of the run."""
    import jax
    cols, table, y = host_table(cell, seed, phases)
    with phases("transfer"):
        bins = jax.device_put(table.bins)
        bins.block_until_ready()
    return dataclasses.replace(table, bins=bins), table, y, cols


def setup(cell, seed, phases):
    import jax
    from repro.core import build_tree
    table_d, table, y, cols = make_table(cell, seed, phases)
    c = cell.config["classes"]
    cfg = tree_config(cell)
    with phases("warmup"):
        jax.block_until_ready(build_tree(table_d, y, cfg, n_classes=c))
    return dict(cell=cell, table_d=table_d, table=table, y=y, c=c, cfg=cfg,
                cols=cols, trees=[])


def run_window(state, window, phases):
    import jax
    from repro.core import build_tree
    table_d, y, cfg, c = (state[k] for k in ("table_d", "y", "cfg", "c"))
    window.open()
    while True:
        tree = build_tree(table_d, y, cfg, n_classes=c)
        jax.block_until_ready(tree)
        state["trees"].append(tree)
        if window.unit_done(rows=len(y)):
            break


def release(state):
    state["trees"] = [ref.host_tree(t) for t in state["trees"]]
    state.pop("table_d")


def bins_off(state):
    """The program's training table against the reference's binning."""
    table, conf = state["table"], state["cell"].config
    state["layouts"] = [ref.column_layout(c, conf["max_num_bins"])
                        for c in state["cols"]]
    return ref.table_off(table.bins, table.n_num, table.n_cat,
                         state["cols"], state["layouts"])


def check(state):
    trees, table, y = state["trees"], state["table"], state["y"]
    first = trees[0]
    differ = sum(not all(np.array_equal(t[f], first[f]) for f in ref.FIELDS)
                 if t["feat"].shape == first["feat"].shape else True
                 for t in trees[1:])
    rules = ref.Rules("classification", max_depth=state["cfg"].max_depth,
                      min_samples_split=state["cfg"].min_samples_split)
    stats = np.eye(state["c"])[y]
    rows = ref.Rows(table.bins, int(table.n_bins))
    chk = ref.check_tree(first, rows, stats, table.n_num, table.n_cat, rules)
    state["row_counts"] = chk.row_counts
    print(f"check: tree 0 has {len(first['feat'])} nodes, depth "
          f"{int(first['depth'].max())}", file=sys.stderr)
    for note in chk.notes[:5]:
        print(f"check: tree 0 {note}", file=sys.stderr)
    checks = [Check("bins_off", float(bins_off(state)), LIMITS["bins_off"]),
              Check("trees_differ", float(differ), LIMITS["trees_differ"]),
              Check("nodes_off", float(chk.nodes_off), LIMITS["nodes_off"]),
              Check("split_gap", chk.split_gap(), LIMITS["split_gap"])]
    failed = (len(trees) if not (checks[0].ok and checks[2].ok
                                 and checks[3].ok) else differ)
    return len(trees), failed, checks


def work(state):
    """What the per-layer counts read: each window tree's node structure
    with the rows that reached each node, and the table's widths."""
    table = state["table"]
    return dict(trees=[dict(t, rows=state["row_counts"])
                       for t in state["trees"]],
                k=table.bins.shape[1], n_bins=int(table.n_bins),
                channels=state["c"])

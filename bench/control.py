"""The control of each cell's check, and planted faults at the cell's size.

The control is the reference put in the program's place and computed in
the nearest precision below the one the configuration states (float32
histograms and counts, so bfloat16).  Its answers go through the cell's
own check, which has to find them not correct; the smallest reading it
gives is the upper reading each limit is set below.  The benchmark's own
runs never run it.

For boosting, ``--fault`` also puts the float64 reference in the
program's place with one fault planted: ``half_the_rows`` (the second
half of the rows left out of every histogram), ``state_unchanged`` (raw
scores never updated after a round) or ``answer_altered`` (the root value
of the last round's tree moved by 1); ``sound`` plants none.

    python3 bench/control.py --workload <cell> --seed <n> [--seed <n> ...]
        [--fault control --fault half_the_rows ...]

prints one JSON line per seed and fault with the numbers compared.  It
needs no chip (the reference runs on the host) but runs at the cell's
own size.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import types

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BOOST_FAULTS = ("control", "sound", "half_the_rows", "state_unchanged",
                "answer_altered")
_MADE: dict = {}


def _made(cell, seed):
    """The cell's host data for ``seed``, made as its job makes it, once
    per process (the dict also keeps what the faults share)."""
    key = (cell.name, cell.config["rows"], seed)
    if key not in _MADE:
        from bench.harness import Phases
        _MADE.clear()
        if cell.mix["job"] == "boost_fit":
            from bench.jobs import boost_fit
            conf = cell.config
            x, y = cell.data.synth(conf["rows"], seed, conf["signal_share"])
            table = boost_fit.bin_table(cell, x, Phases())
            _MADE[key] = dict(table=table, y=y,
                              sample=x[:conf["bin_sample_rows"]].copy())
        else:
            from bench.jobs import udt_fit
            cols, table, y = udt_fit.host_table(cell, seed, Phases())
            _MADE[key] = dict(table=table, y=y, cols=cols)
    return _MADE[key]


def udt_state(cell, seed):
    from bench import reference as ref
    st = dict(_made(cell, seed), cell=cell)
    table, y = st["table"], st["y"]
    c = cell.config["classes"]
    rules = ref.Rules("classification", max_depth=cell.mix["max_depth"],
                      min_samples_split=cell.mix["min_samples_split"])
    tree = ref.grow(ref.Rows(table.bins, int(table.n_bins)), np.eye(c)[y],
                    table.n_num, table.n_cat, rules, rounding=ref.bf16)
    cfg = types.SimpleNamespace(max_depth=rules.max_depth,
                                min_samples_split=rules.min_split)
    return dict(st, c=c, cfg=cfg, trees=[tree])


def tune_state(cell, seed):
    """The tune job's state with the control's grid in place of the
    program's, priced from the full tree the reference grows."""
    from bench import reference as ref
    from bench.jobs import tune
    from repro.core import transform
    st = dict(_made(cell, seed), cell=cell)
    table, y = st["table"], st["y"]
    conf = cell.config
    vcols, vy = cell.data.draw(conf, conf["val_rows"], seed + 1, offset=1)
    vbins = transform(vcols, table)
    rules = ref.Rules("classification", max_depth=cell.mix["max_depth"])
    full = ref.grow(ref.Rows(table.bins, int(table.n_bins)),
                    np.eye(conf["classes"])[y], table.n_num, table.n_cat,
                    rules)
    dmax, smin = tune.grid_axes(full, len(y), cell.mix)
    vrows = ref.Rows(vbins, int(table.n_bins))
    counts = ref.toot_counts(full, vrows, np.asarray(vy), table.n_num, dmax,
                             smin, accumulate=lambda ok: ref.bf16(ok.sum()))
    i, j = ref.toot_best(counts, full, dmax, smin)
    grid = types.SimpleNamespace(metric=counts / len(vy), dmax=dmax,
                                 smin=smin)
    res = types.SimpleNamespace(grid=grid, best_dmax=int(dmax[i]),
                                best_smin=int(smin[j]))
    return dict(st, full=full, vbins=vbins, vcols=vcols, vy=np.asarray(vy),
                results=[res])


def _rounds(cell, made, fault):
    """(trees, raw scores after the last) of the reference's rounds in the
    program's place: in bfloat16 for the control, else float64 with the
    second half of the rows left out for ``half_the_rows``."""
    from bench import reference as ref
    from bench.jobs import boost_fit
    mix = cell.mix
    table, y = made["table"], made["y"]
    r = ref.bf16 if fault == "control" else (lambda a: a)
    rules = ref.Rules("newton", max_depth=mix["max_depth"])
    raw = np.full(len(y), boost_fit.base_score(y))
    trees = []
    rows = ref.Rows(table.bins, int(table.n_bins))
    for _ in range(mix["warmup_rounds"] + 1):
        stats = r(boost_fit.newton_rows(y, raw))
        if fault == "half_the_rows":
            stats[len(y) // 2:] = 0.0
        tree = ref.grow(rows, stats, table.n_num, table.n_cat, rules,
                        rounding=ref.bf16 if fault == "control" else None)
        trees.append(tree)
        leaf = ref.route(rows, table.n_num, tree)[-1]
        raw = r(raw + mix["learning_rate"] * tree["label"][leaf])
    return trees, raw


def boost_state(cell, seed, fault="control"):
    """Boosting rounds grown by the reference in the program's place: the
    control, or the float64 reference with ``fault`` planted.  The sound
    rounds are grown once per seed and shared by the faults built on
    them."""
    from bench.jobs import boost_fit
    made = _made(cell, seed)
    if fault in ("control", "half_the_rows"):
        trees, raw = _rounds(cell, made, fault)
    else:
        if "sound" not in made:
            made["sound"] = _rounds(cell, made, "sound")
        trees, raw = made["sound"]
        if fault == "answer_altered":
            label = trees[-1]["label"].copy()
            label[0] += 1.0
            trees = trees[:-1] + [dict(trees[-1], label=label)]
        elif fault == "state_unchanged":
            # raw scores never move, so every round grows the first tree
            trees = [trees[0]] * len(trees)
            raw = np.full(len(made["y"]), boost_fit.base_score(made["y"]))
    first = cell.mix["warmup_rounds"] + 1
    return dict(made, cell=cell, trees=trees, raw=raw,
                window_rounds=list(range(first, len(trees) + 1)))


STATES = {"udt_fit": (lambda cell, seed, _: udt_state(cell, seed),
                      ("control",)),
          "tune": (lambda cell, seed, _: tune_state(cell, seed),
                   ("control",)),
          "boost_fit": (boost_state, BOOST_FAULTS)}


def run(cell, seed, fault="control"):
    """The numbers the cell's check reads from the answers of the control
    (or of the reference with ``fault`` planted)."""
    make, faults = STATES[cell.mix["job"]]
    if fault not in faults:
        raise ValueError(f"no fault {fault!r} for job {cell.mix['job']!r}; "
                         f"have {faults}")
    attempted, failed, checks = cell.job.check(make(cell, seed, fault))
    return {"correct": all(c.ok for c in checks) and failed == 0,
            "checks": {c.name: {"value": c.value, "limit": c.limit}
                       for c in checks}}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, action="append", required=True)
    ap.add_argument("--fault", action="append")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness
    cell = harness.load_cell(args.workload)
    for seed in args.seed:
        for fault in args.fault or ["control"]:
            t0 = time.perf_counter()
            out = run(cell, seed, fault)
            out.update(workload=args.workload, seed=seed, fault=fault,
                       seconds=time.perf_counter() - t0)
            print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()

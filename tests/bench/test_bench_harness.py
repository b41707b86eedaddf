"""The chip benchmark's harness on the CPU, at tiny sizes.

The benchmark itself runs only on a TPU.  Here its pieces run without
one: the trace reduction on a small trace recorded on a v5e chip, the
least-work count against a hand count, HIGGS's vectorised binning
against the program's ``transform``, the peaks table, the refusals, each
cell's set-up and window through the harness's own functions, the
controls (which the check must find not correct) and the program broken
underneath in the ways each cell's check must catch.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import control, counts, harness, peaks, trace  # noqa: E402

DATA = Path(__file__).parent / "data"
TINY = {"kdd99-udt": 3_000, "kdd99-toot": 3_000, "higgs-boost": 4_000}


def spec_with_higgs():
    """BENCHMARK.json with the ``higgs-boost`` cell put back as it stood
    before the program failed its check at full size (PERF.md, Open
    questions): its files stay, driven here on the CPU."""
    spec = harness.load_spec()
    spec["configs"].append({"name": "higgs",
                            "file": "bench/configs/higgs.json"})
    spec["workloads"].append({"name": "higgs-boost", "config": "higgs",
                              "traffic": "boost_logistic", "chips": 1})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] != "binning_s" and "kdd99-udt" in m.get("workloads",
                                                              []):
            m["workloads"].append("higgs-boost")
    return spec


def tiny_cell(name, rows=None):
    cell = harness.load_cell(name, spec=spec_with_higgs())
    rows = rows or TINY[name]
    cell.config = dict(cell.config, rows=rows, val_rows=max(rows // 6, 500),
                       bin_sample_rows=rows)
    return cell


def run_tiny(name, seconds=0.5, seed=2 ** 31 + 11, rows=None):
    import jax
    cell = tiny_cell(name, rows)
    line, run = harness.run_cell(cell, seed, seconds, False, 0.0,
                                 jax.devices())
    return line, run


# ---------------------------------------------------------------------------
# the yardstick's pieces
# ---------------------------------------------------------------------------

def test_trace_reduction_on_a_recorded_v5e_trace():
    """A window of three units on one v5e chip: each ran a jitted
    ``_chunk_step_impl`` and a jitted ``_route_step`` with a 10 ms host
    sleep between them, so the device idles in every unit."""
    s = trace.load(str(DATA / "v5e_probe.xplane.pb"))
    assert len(s.chips) == 1 and s.chips[0].name == "/device:TPU:0"
    assert 0 < s.window_s < 1.0
    busy = trace.busy_s(s)
    assert 0 < busy < s.window_s
    # three 10 ms sleeps with nothing on the device
    assert s.window_s - busy >= 0.03
    step = trace.program_s(s, "level_step")
    route = trace.program_s(s, "route")
    assert step > 0 and route > 0
    # a program's span also holds the short gaps between its operations
    assert busy * 0.99 <= step + route <= busy * 1.01
    assert trace.program_s(s, "toot_grid") == 0
    b = trace.breakdown(s)
    names = [n for n, _ in b["device_ops"]]
    assert any("_chunk_step_impl" in n for n in names)
    assert any("_route_step" in n for n in names)
    assert len(b["idle_gaps"]) <= 10
    gaps = [g for g in b["idle_gaps"] if g[1] >= 0.009]
    assert len(gaps) >= 3
    # the innermost host span over each: the profiler's Python tracer
    # names the sleep itself
    assert all("sleep" in g[0] for g in gaps)


def test_least_histogram_bytes_by_hand():
    # root 100 rows -> (60, 40); 60 -> (10, 50); 40 -> (25, 15)
    tree = dict(depth=np.array([1, 2, 2, 3, 3, 3, 3]),
                left=np.array([1, 3, 5, -1, -1, -1, -1]),
                right=np.array([2, 4, 6, -1, -1, -1, -1]),
                rows=np.array([100, 60, 40, 10, 50, 25, 15]))
    k, b, c = 3, 65, 5
    per_row = k * 1 + 4 * c + 4           # uint8 bins, 5 stats, node id
    rows_read = 100 + 40 + 10 + 15        # root, then each smaller child
    hist = (1 + 2 + 4) * k * b * c * 4
    assert counts.histogram_bytes(tree, k, b, c) == rows_read * per_row + hist
    assert counts.bin_bytes(256) == 1 and counts.bin_bytes(257) == 2


def test_higgs_vectorised_binning_equals_the_programs_transform():
    from repro.core import fit_bins, transform
    from bench.configs import higgs
    x, _ = higgs.synth(20_000, 3)
    x[::97, 5] = np.nan
    fitted = fit_bins([x[:5_000, j] for j in range(x.shape[1])],
                      max_num_bins=255)
    got = higgs.bin_rows(x, [m.edges for m in fitted.metas], fitted.n_num,
                         [m.missing_bin for m in fitted.metas])
    want = transform([x[:, j] for j in range(x.shape[1])], fitted)
    assert np.array_equal(got, want)


def test_a_split_with_an_empty_side_is_off():
    """A boosting split that sends every row one way breaks
    ``min_samples_leaf`` whatever the node's weight."""
    from bench import reference as ref
    rng = np.random.default_rng(0)
    bins = np.zeros((500, 1), dtype=np.int32)
    h = rng.uniform(0.1, 0.25, size=500)
    g = rng.normal(size=500)
    stats = np.stack([h, -g, g * g / h], axis=1)
    value = -g.sum() / h.sum()
    tree = dict(feat=np.array([0, -1, -1]), op=np.array([0, -1, -1]),
                tbin=np.array([2, -1, -1]), label=np.array([value, value,
                                                           0.0]),
                count=np.array([0, 0, 0]), depth=np.array([1, 2, 2]),
                left=np.array([1, -1, -1]), right=np.array([2, -1, -1]),
                leaf=np.array([False, True, True]))
    chk = ref.check_tree(tree, ref.Rows(bins, 4), stats, np.array([3]),
                         np.array([0]), ref.Rules("newton", max_depth=6))
    assert chk.nodes_off == 1
    assert "chosen split invalid" in chk.notes[0]


def test_peaks_refuse_an_unknown_device_kind():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v9 imaginary")


def test_refuses_a_machine_without_a_tpu():
    import jax
    if jax.devices()[0].platform == "tpu":
        pytest.skip("a TPU is attached")
    sys.path.insert(0, str(ROOT / "bench"))
    import run as bench_run
    with pytest.raises(SystemExit) as e:
        bench_run.require_chips(1)
    assert e.value.code != 0


def test_refuses_a_checkout_without_the_program(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's own files
    exits nonzero and prints no result."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for p in spec["paths"]:
        dst = tmp_path / p
        dst.mkdir(parents=True)
        for f in (ROOT / p).rglob("*"):
            if f.is_file() and "__pycache__" not in f.parts:
                out = dst / f.relative_to(ROOT / p)
                out.parent.mkdir(parents=True, exist_ok=True)
                out.write_bytes(f.read_bytes())
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "kdd99-udt", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, capture_output=True,
                       text=True, timeout=60,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_a_new_mix_file_is_found_by_name(tmp_path):
    (tmp_path / "udt_fit_shallow.json").write_text(json.dumps(
        {"job": "udt_fit", "max_depth": 4, "min_samples_split": 2}))
    spec = harness.load_spec()
    spec["workloads"].append({"name": "kdd99-shallow", "config":
                              "kdd99_10pct", "traffic": "udt_fit_shallow",
                              "chips": 1})
    cell = harness.load_cell("kdd99-shallow", spec=spec, mix_dir=tmp_path)
    assert cell.mix["max_depth"] == 4
    assert cell.job.__name__ == "bench.jobs.udt_fit"
    assert [m["name"] for m in cell.end_to_end] == ["setup_s"]


def test_every_metric_has_a_reader_and_every_cell_its_files():
    spec = harness.load_spec()
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(harness.metric_reader(m["name"]).read)
    for w in spec["workloads"]:
        cell = harness.load_cell(w["name"], spec=spec)
        assert cell.end_to_end and cell.per_layer
        assert "setup_s" in [m["name"] for m in cell.end_to_end]


# ---------------------------------------------------------------------------
# each cell through the harness, sound and broken
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(TINY))
def test_cell_sets_up_and_runs_a_window_on_cpu(name):
    line, run = run_tiny(name)
    assert line["correct"], line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert list(line)[-1] == "checks"
    units = {"kdd99-udt": "fit_rows_per_s", "higgs-boost": "fit_rows_per_s",
             "kdd99-toot": "tune_configs_per_s"}
    assert set(line["metrics"]) == {units[name], "setup_s"}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert "binning" in run.setup and "warmup" in run.setup


@pytest.mark.parametrize("name,rows", [("kdd99-udt", 20_000),
                                       ("kdd99-toot", 3_000),
                                       ("higgs-boost", 4_000)])
def test_the_control_is_not_correct(name, rows):
    """The reference in the program's place, in bfloat16."""
    out = control.run(tiny_cell(name, rows), 5)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("fault", [f for f in control.BOOST_FAULTS
                                   if f != "sound"])
def test_boosting_faults_in_the_references_place_are_not_correct(fault):
    out = control.run(tiny_cell("higgs-boost"), 5, fault)
    assert not out["correct"], out["checks"]


def test_the_sound_reference_in_the_programs_place_is_correct():
    out = control.run(tiny_cell("higgs-boost"), 5, "sound")
    assert out["correct"], out["checks"]


def test_every_seed_gets_the_same_kdd_rows_in_its_own_order():
    cell = tiny_cell("kdd99-udt")
    (a, ya), (b, yb) = (cell.data.draw(cell.config, 3_000, s)
                        for s in (7, 2 ** 31 + 7))
    assert not np.array_equal(a[0], b[0])
    for ca, cb in zip(a, b):
        assert sorted(ca) == sorted(cb)
    assert np.array_equal(np.sort(ya), np.sort(yb))
    again, _ = cell.data.draw(cell.config, 3_000, 7)
    assert all(np.array_equal(np.asarray(x), np.asarray(z))
               for x, z in zip(a, again))


def test_reference_binning_equals_the_programs_on_mixed_columns():
    from repro.core import fit_bins, transform
    from bench import reference as ref
    rng = np.random.default_rng(0)
    num = rng.normal(size=2_000).astype(np.float32)
    num[::50] = np.nan
    few = rng.integers(0, 5, size=2_000).astype(np.float32)
    mixed = list(rng.choice(["a", "b", "7.5", "c"], size=2_000))
    cols = [num, few, mixed]
    table = fit_bins(cols, max_num_bins=16)
    lays = [ref.column_layout(c, 16) for c in cols]
    assert ref.table_off(table.bins, table.n_num, table.n_cat, cols, lays,
                         edges=[m.edges for m in table.metas]) == 0
    new = [num[::-1].copy(), few, list(rng.choice(["a", "z"], size=2_000))]
    assert ref.table_off(transform(new, table), table.n_num, table.n_cat,
                         new, lays) == 0
    table.bins[3, 0] += 1
    assert ref.table_off(table.bins, table.n_num, table.n_cat, cols,
                         lays) == 1


def _alter_tree(tree):
    return tree._replace(feat=tree.feat.at[0].set((tree.feat[0] + 1) % 7))


def _half_rows(build):
    import dataclasses

    def half(table, y, *a, **kw):
        m = table.bins.shape[0] // 2
        return build(dataclasses.replace(table, bins=table.bins[:m]),
                     np.asarray(y)[:m], *a, **kw)
    return half


def _bins_altered(mp):
    import repro.core
    fit_bins = repro.core.fit_bins

    def altered(*a, **kw):
        table = fit_bins(*a, **kw)
        table.bins[0, 0] = (table.bins[0, 0] + 1) % table.n_bins
        return table
    mp.setattr(repro.core, "fit_bins", altered)


def _udt_faults(mp, fault):
    import repro.core
    import repro.core.tree as tree_mod
    build = repro.core.build_tree
    if fault == "bins_altered":
        _bins_altered(mp)
    elif fault == "answer_altered":
        mp.setattr(repro.core, "build_tree",
                   lambda *a, **kw: _alter_tree(build(*a, **kw)))
    elif fault == "half_the_rows":
        mp.setattr(repro.core, "build_tree", _half_rows(build))
    elif fault == "state_unchanged":
        mp.setattr(tree_mod, "_route_step", lambda b, assign, *a, **k: assign)


def _toot_faults(mp, fault):
    import repro.core
    tune = repro.core.tune

    def altered(*a, **kw):
        res = tune(*a, **kw)
        res.grid.metric[0, 0] += 1.0 / len(a[2])
        return res

    def half(tree, vb, vy, *a, **kw):
        m = len(vy) // 2
        return tune(tree, vb[:m], vy[:m], *a, **kw)
    if fault == "bins_altered":
        _bins_altered(mp)
        return
    mp.setattr(repro.core, "tune",
               altered if fault == "answer_altered" else half)


def _boost_faults(mp, fault):
    import jax.numpy as jnp
    import repro.core.forest as forest
    build = forest.build_tree
    if fault == "bins_altered":
        import repro.core
        fit_bins = repro.core.fit_bins

        def edges_moved(*a, **kw):
            table = fit_bins(*a, **kw)
            meta = table.metas[0]
            meta.edges = meta.edges.copy()
            meta.edges[0] = np.nextafter(meta.edges[0], -np.inf)
            return table
        mp.setattr(repro.core, "fit_bins", edges_moved)
    elif fault == "answer_altered":
        def altered(*a, **kw):
            t = build(*a, **kw)
            return t._replace(label=t.label.at[0].add(1.0))
        mp.setattr(forest, "build_tree", altered)
    elif fault == "half_the_rows":
        def half(table, z, cfg, sample_weight=None, **kw):
            m = z.shape[0]
            keep = (jnp.arange(m) < m // 2).astype(jnp.float32)
            return build(table, z, cfg, sample_weight=sample_weight * keep,
                         **kw)
        mp.setattr(forest, "build_tree", half)
    elif fault == "state_unchanged":
        mp.setattr(forest, "predict_bins",
                   lambda tree, bins, *a, **kw: jnp.zeros(bins.shape[0]))


FAULTS = [("kdd99-udt", f, _udt_faults) for f in
          ("answer_altered", "half_the_rows", "state_unchanged",
           "bins_altered")] + [
          ("kdd99-toot", f, _toot_faults) for f in
          ("answer_altered", "half_the_rows", "bins_altered")] + [
          ("higgs-boost", f, _boost_faults) for f in
          ("answer_altered", "half_the_rows", "state_unchanged",
           "bins_altered")]


@pytest.mark.parametrize("name,fault,patch", FAULTS,
                         ids=[f"{n}-{f}" for n, f, _ in FAULTS])
def test_a_broken_program_is_not_correct(monkeypatch, name, fault, patch):
    patch(monkeypatch, fault)
    line, _ = run_tiny(name, seconds=0.2)
    assert not line["correct"], line["checks"]

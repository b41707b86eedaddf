"""The wire reader of profiler traces and the per-layer readers of the
program's spans and scopes, on traces recorded on a v5e chip.

``v5e_probe.xplane.pb`` holds three units of a program without spans
(see ``test_bench_harness.py``); ``v5e_scopes.xplane.pb.gz`` one
20,000-row UDT fit of the program with its spans and scopes, recorded by
``data/record_v5e_scopes.py``.
"""
import gzip
import shutil
import sys
import types
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, trace, xplane  # noqa: E402
from bench.metrics import select_roofline  # noqa: E402

DATA = Path(__file__).parent / "data"
PROBE = DATA / "v5e_probe.xplane.pb"
SCOPES_GZ = DATA / "v5e_scopes.xplane.pb.gz"
# the fit recorded in SCOPES_GZ: rows of the twin, levels of its tree
SCOPES_ROWS, SCOPES_LEVELS = 20_000, 10
STEP_SCOPES = ("level.histogram", "level.select", "level.update")
NEW_METRICS = ("histogram_roofline.fit", "select_roofline.fit",
               "sync_idle_share.fit", "syncs_per_tree.fit",
               "h2d_bytes_per_tree.fit", "compiles.fit", "compiles.tune")
# the hand-counted tree of test_least_histogram_bytes_by_hand
HAND_TREE = dict(depth=np.array([1, 2, 2, 3, 3, 3, 3]),
                 left=np.array([1, 3, 5, -1, -1, -1, -1]),
                 right=np.array([2, 4, 6, -1, -1, -1, -1]),
                 rows=np.array([100, 60, 40, 10, 50, 25, 15]))


def fake_run(tmp_path, pb, trees, units=3):
    """What a metric reader reads of a traced run whose trace is ``pb``."""
    shutil.copy(pb, tmp_path)
    return types.SimpleNamespace(
        trace=trace.load(str(tmp_path)), setup={},
        window=types.SimpleNamespace(trace_dir=str(tmp_path),
                                     units=[None] * units),
        work=dict(trees=trees, k=41, n_bins=65, channels=5),
        device_kind="TPU v5 lite")


@pytest.fixture(scope="module")
def scopes(tmp_path_factory):
    """``v5e_scopes.xplane.pb``, unpacked from its committed gzip."""
    pb = tmp_path_factory.mktemp("scopes") / "v5e_scopes.xplane.pb"
    pb.write_bytes(gzip.decompress(SCOPES_GZ.read_bytes()))
    return pb


def profile_data_events(pb):
    """(XLA Ops of the first TPU plane, every host event) as
    ``ProfileData`` reads them: (name, start_ns, end_ns)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(pb))
    ops, host = [], []
    for plane in data.planes:
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.end_ns) for e in line.events]
            if plane.name == "/device:TPU:0" and line.name == trace.OPS_LINE:
                ops += evs
            elif plane.name.startswith("/host:"):
                host += evs
    return ops, host


@pytest.mark.parametrize("which", ["probe", "scopes"])
def test_the_wire_reader_equals_profile_data_event_for_event(which, request):
    pb = PROBE if which == "probe" else request.getfixturevalue("scopes")
    ops, host = profile_data_events(pb)
    tr = xplane.load(str(pb))
    assert list(tr.ops) == ["/device:TPU:0"]
    assert [(n, s, e) for n, s, e, _ in tr.ops["/device:TPU:0"]] == ops
    assert [(n, s, e) for n, s, e, _ in tr.host] == host
    assert len(ops) > 10 and len(host) > 10


def test_the_wire_reader_keeps_each_ops_scope_path():
    tf_ops = {tf for _, _, _, tf in xplane.load(str(PROBE)).ops[
        "/device:TPU:0"]}
    assert "jit(_chunk_step_impl)/dot_general" in tf_ops
    assert "jit(_route_step)/jit(sort)/sort" in tf_ops
    assert "" in tf_ops                      # ops XLA made itself


def test_a_scope_is_one_whole_component_of_the_path():
    assert xplane.in_scope("jit(f)/level.select/argmax", "level.select")
    assert xplane.in_scope("jit(f)/vmap(g)/level.select", "level.select")
    assert not xplane.in_scope("jit(f)/level.selection/x", "level.select")
    assert not xplane.in_scope("jit(f)/xlevel.select", "level.select")
    assert not xplane.in_scope("", "level.select")


def test_least_selection_bytes_by_hand():
    # 7 nodes on 3 levels, each level's [S, K, B, C] f32 histograms
    k, b, c = 3, 65, 5
    assert (select_roofline.selection_bytes(HAND_TREE, k, b, c)
            == 7 * k * b * c * 4)
    tail = dict(HAND_TREE, depth=np.r_[HAND_TREE["depth"], 0, 0])
    assert (select_roofline.selection_bytes(tail, k, b, c)
            == 7 * k * b * c * 4)


# every device_trace metric the benchmark had before the program's own
# spans, as its reader gives it on the probe trace at that commit
BEFORE_SPANS = {"idle_share.fit": 0.9759717843831998,
                "level_step_roofline.fit": 2.2593202220064295,
                "route_busy_share.fit": 0.9322409839210353,
                "idle_share.tune": 0.9759717843831998,
                "toot_grid_ms.tune": 0.0}


def test_the_older_readers_read_the_probe_as_before(tmp_path):
    run = fake_run(tmp_path, PROBE, [HAND_TREE] * 3)
    for name, want in BEFORE_SPANS.items():
        assert harness.metric_reader(name).read(name, run) == want, name


def test_the_new_readers_read_nothing_of_a_program_without_spans(
        tmp_path, monkeypatch):
    """The probe's program has no spans and no scopes, as a parent commit
    without ``repro.core.obs`` has none."""
    import importlib.util
    find_spec = importlib.util.find_spec
    monkeypatch.setattr(importlib.util, "find_spec",
                        lambda n, *a: None if n == "repro.core.obs"
                        else find_spec(n, *a))
    run = fake_run(tmp_path, PROBE, [HAND_TREE] * 3)
    for name in NEW_METRICS:
        assert harness.metric_reader(name).read(name, run) is None, name


def test_every_new_metric_is_in_the_benchmark():
    spec = harness.load_spec()
    per_layer = {m["name"]: m for m in spec["per_layer"]}
    for name in NEW_METRICS:
        assert per_layer[name]["source"] == "device_trace"
    assert per_layer["compiles.tune"]["workloads"] == ["kdd99-toot"]


def step_split(pb):
    """(device seconds of the ops run inside ``_chunk_step`` executions,
    {scope: seconds of those ops in it}, executions) in the window."""
    s = trace.load(str(pb))
    tr = xplane.load(str(pb))
    [chip] = s.chips
    steps = [(a, b) for n, a, b in chip.modules
             if "_chunk_step" in n and b > s.w0 and a < s.w1]
    total, split = 0.0, dict.fromkeys(STEP_SCOPES, 0.0)
    for _, a, b, tf_op in tr.ops[chip.name]:
        if any(a0 <= a < b0 for a0, b0 in steps):
            total += (b - a) * 1e-9
            for scope in STEP_SCOPES:
                if xplane.in_scope(tf_op, scope):
                    split[scope] += (b - a) * 1e-9
    return total, split, len(steps)


def test_the_scopes_cover_the_level_step(scopes):
    total, split, _ = step_split(scopes)
    assert all(v > 0 for v in split.values()), split
    assert sum(split.values()) >= 0.95 * total
    tr = xplane.load(str(scopes))
    route = [tf for _, _, _, tf in tr.ops["/device:TPU:0"]
             if xplane.in_scope(tf, "level.route")]
    assert route and all("_route_step" in tf for tf in route)


def test_the_new_readers_on_a_traced_fit(tmp_path, scopes):
    """One tree of the twin: 41 columns and 5 classes."""
    _, _, steps = step_split(scopes)
    run = fake_run(tmp_path, scopes, [HAND_TREE], units=1)

    def read(name):
        return harness.metric_reader(name).read(name, run)
    # one level chunk a level at this width, each ending in one read
    assert read("syncs_per_tree.fit") == steps == SCOPES_LEVELS
    # the class one-hot, label bins and label vector; n_num and n_cat
    assert read("h2d_bytes_per_tree.fit") == (SCOPES_ROWS * (5 * 4 + 4 + 4)
                                              + 2 * 41 * 4)
    assert read("compiles.fit") == read("compiles.tune") == 0
    assert 0 < read("sync_idle_share.fit") <= read("idle_share.fit")
    for name in ("histogram_roofline.fit", "select_roofline.fit"):
        assert 0 < read(name) < 100, name

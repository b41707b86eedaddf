"""The fit path's spans, scopes and counters (``repro.core.obs``): a
traced CPU fit's spans nest and agree with the process counters, the
compile counter counts compiles, and the level step's lowered text names
its scopes."""
import contextlib
import functools
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import TreeConfig, build_tree, build_trees_batched, fit_bins
from repro.core import obs, tree as tree_mod

ROOT = Path(__file__).resolve().parents[1]


def small_table(m=600, k=4, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(m, k))
    y = (x[:, 0] > 0).astype(int) + (x[:, 1] > 0.5)
    return fit_bins([x[:, j] for j in range(k)], max_num_bins=8), y, x


def traced(fn, tmp_path):
    """Run ``fn`` under the profiler; (its result, the counters' deltas,
    the trace's host events as (name, start, end, args))."""
    sys.path.insert(0, str(ROOT))
    from bench import trace, xplane
    before = obs.counters()
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = jax.block_until_ready(fn())
    finally:
        jax.profiler.stop_trace()
    after = obs.counters()
    host = xplane.load(trace.find_xplane(str(tmp_path))).host
    return out, {k: after[k] - before[k] for k in after}, host


def inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_compile_event_is_jaxs():
    from jax._src import dispatch
    assert obs.COMPILE_EVENT == dispatch.BACKEND_COMPILE_EVENT


@pytest.mark.parametrize("batched", [False, True],
                         ids=["build_tree", "build_trees_batched"])
def test_a_traced_fit_has_nested_spans_that_agree_with_the_counters(
        tmp_path, batched):
    table, y, x = small_table()
    cfg = TreeConfig(max_depth=5)
    if batched:
        cfg = TreeConfig(max_depth=5, task="regression_variance")
        z = np.stack([x[:, 0], x[:, 1]]).astype(np.float32)
        fit = lambda: build_trees_batched(table, z, cfg)[0][0]  # noqa: E731
    else:
        fit = lambda: build_tree(table, y, cfg, n_classes=3)  # noqa: E731
    fit()                                   # compile outside the trace
    tree, delta, host = traced(fit, tmp_path)
    spans = {n: [(n, s, e, a) for n2, s, e, a in host if n2 == n]
             for n in ("udt.tree", "udt.level", "udt.put", "udt.sync")}
    [root] = spans["udt.tree"]
    levels, puts, syncs = spans["udt.level"], spans["udt.put"], spans[
        "udt.sync"]
    assert all(inside(sp, root) for sp in levels + puts)
    assert all(any(inside(s, lv) for lv in levels) for s in syncs)
    # one level chunk per level at this width, each ending in one read
    depth = tree.max_tree_depth
    assert len(levels) == len(syncs) == depth == delta["syncs"]
    assert [a["depth"] for *_, a in levels] == list(range(1, depth + 1))
    assert all(a["slots"] >= a["width"] for *_, a in levels)
    # every byte sent from the host is counted once
    [put] = puts
    assert put[3]["bytes"] == delta["h2d_bytes"] > 0
    if batched:
        assert delta["h2d_bytes"] == (table.bins.nbytes + z.nbytes
                                      + table.n_num.nbytes
                                      + table.n_cat.nbytes)
    else:
        m = len(y)
        assert delta["h2d_bytes"] == (table.bins.nbytes + m * 3 * 4 + m * 4
                                      + m * 4 + table.n_num.nbytes
                                      + table.n_cat.nbytes)
    assert delta["compiles"] == 0


def test_the_mesh_builder_marks_the_same_spans(tmp_path):
    """``DistributedBuilder`` runs the same level loop; each host array it
    stages for a build is a ``udt.put`` of its own."""
    from repro.core.distributed import build_tree_distributed
    table, y, _ = small_table()
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    fit = lambda: build_tree_distributed(  # noqa: E731
        table, y, TreeConfig(max_depth=5), mesh=mesh, n_classes=3)
    fit()
    tree, delta, host = traced(fit, tmp_path)
    [root] = [ev for ev in host if ev[0] == "udt.tree"]
    inner = [ev for ev in host if ev[0] in ("udt.level", "udt.put")]
    assert all(inside(ev, root) for ev in inner)
    levels = [ev for ev in inner if ev[0] == "udt.level"]
    syncs = [ev for ev in host if ev[0] == "udt.sync"]
    assert len(levels) == len(syncs) == tree.max_tree_depth == delta["syncs"]
    puts = [ev[3]["bytes"] for ev in inner if ev[0] == "udt.put"]
    assert len(puts) == 4                   # stats, label bins, labels, assign
    assert sum(puts) == delta["h2d_bytes"] > 0


def test_arrays_already_on_the_device_send_no_bytes():
    import dataclasses
    table, y, _ = small_table()
    table = dataclasses.replace(table, bins=jnp.asarray(table.bins))
    cfg = TreeConfig(max_depth=3)
    build_tree(table, y, cfg, n_classes=3)
    before = obs.counters()["h2d_bytes"]
    build_tree(table, y, cfg, n_classes=3)
    sent = obs.counters()["h2d_bytes"] - before
    m = len(y)
    assert sent == (m * 3 * 4 + m * 4 + m * 4 + table.n_num.nbytes
                    + table.n_cat.nbytes)
    assert obs.host_bytes(jnp.zeros(4), None, np.zeros(4, np.int8)) == 4


def test_compiles_rise_on_a_new_shape_and_stay_on_a_repeat():
    f = jax.jit(lambda v: v * 3 + 1)
    before = obs.counters()["compiles"]
    f(np.zeros(1237, np.float32)).block_until_ready()
    assert obs.counters()["compiles"] == before + 1
    f(np.ones(1237, np.float32)).block_until_ready()
    assert obs.counters()["compiles"] == before + 1
    f(np.zeros(1238, np.float32)).block_until_ready()
    assert obs.counters()["compiles"] == before + 2


def test_a_compile_inside_a_trace_is_a_span(tmp_path):
    f = jax.jit(lambda v: v - 7)
    _, delta, host = traced(lambda: f(np.zeros(977, np.float32)), tmp_path)
    spans = [a for n, _, _, a in host if n == "repro.compile"]
    assert delta["compiles"] == len(spans) >= 1
    assert any("<lambda>" in a.get("fun", "") for a in spans)


@pytest.mark.parametrize("task", ["classification", "regression",
                                  "regression_variance"])
def test_the_lowered_level_step_names_its_scopes(task):
    table, y, x = small_table(m=256)
    cfg = TreeConfig(task=task)
    yy = y if task == "classification" else x[:, 2]
    bins, stats, lbins, yv, c, nlb = tree_mod._prepare(table, yy, cfg, 3)
    m = len(y)
    kw = dict(num_slots=16, n_bins=int(table.n_bins),
              heuristic="info_gain", task=task, min_samples_split=2,
              min_samples_leaf=1, max_depth=8, max_nodes=64,
              hist_backend="segment", select_backend="jnp",
              n_label_bins=nlb)
    i32 = jnp.int32
    lowered = tree_mod._chunk_step.lower(
        jnp.asarray(bins), jnp.asarray(stats), jnp.asarray(lbins),
        jnp.asarray(yv), jnp.zeros(m, i32), tree_mod._init_arrays(64),
        jnp.zeros((1, 1, 1, 1)), jnp.asarray(table.n_num),
        jnp.asarray(table.n_cat), i32(0), i32(1), i32(1), i32(1), **kw)
    text = lowered.as_text(debug_info=True)
    for scope in ("level.histogram", "level.select", "level.update"):
        assert scope in text, scope
    route = tree_mod._route_step.lower(
        jnp.asarray(bins), jnp.zeros(m, i32), tree_mod._init_arrays(64),
        jnp.asarray(table.n_num), i32(0), i32(1))
    assert "level.route" in route.as_text(debug_info=True)


def test_the_scopes_change_no_op(monkeypatch):
    """The level step compiles to the same ops and fusions with its scopes
    as without them: only the ops' metadata differs."""
    table, y, _ = small_table(m=256)
    bins, stats, lbins, yv, c, nlb = tree_mod._prepare(
        table, y, TreeConfig(), 3)
    args = (jnp.asarray(bins), jnp.asarray(stats), jnp.asarray(lbins),
            jnp.asarray(yv), jnp.zeros(len(y), jnp.int32),
            tree_mod._init_arrays(64), jnp.zeros((1, 1, 1, 1)),
            jnp.asarray(table.n_num), jnp.asarray(table.n_cat),
            jnp.int32(0), jnp.int32(1), jnp.int32(1), jnp.int32(1))
    kw = dict(num_slots=16, n_bins=int(table.n_bins),
              heuristic="info_gain", task="classification",
              min_samples_split=2, min_samples_leaf=1, max_depth=8,
              max_nodes=64, hist_backend="segment", select_backend="jnp",
              n_label_bins=nlb)

    def compiled():
        # a fresh function object, so that jit traces it anew
        step = jax.jit(functools.partial(tree_mod._chunk_step_impl),
                       static_argnames=tree_mod._CHUNK_STEP_STATICS)
        text = step.lower(*args, **kw).compile().as_text()
        # the instructions, without their metadata (the stack-frame
        # tables the metadata points into hold no instruction)
        return [re.sub(r", metadata=\{[^}]*\}", "", line)
                for line in text.splitlines() if " = " in line]

    scoped = compiled()
    assert len(scoped) > 50
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    assert compiled() == scoped


def test_counters_lose_no_update_across_threads():
    import threading
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        before = obs.counters()["syncs"]

        def work():
            for _ in range(2_000):
                with obs.counted("udt.sync", "syncs"):
                    pass
        threads = [threading.Thread(target=work) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(switch)
    assert obs.counters()["syncs"] - before == 8 * 2_000


def least_rows(tree):
    """``bench/counts.py``'s rule: the root's rows, then the smaller child
    of every split pair (the larger is its parent's histogram minus it)."""
    count = np.asarray(tree.count)[:tree.n_nodes]
    left = np.asarray(tree.left)[:tree.n_nodes]
    right = np.asarray(tree.right)[:tree.n_nodes]
    split = np.flatnonzero(left >= 0)
    return int(count[0] + np.minimum(count[left[split]],
                                     count[right[split]]).sum())


def level_chunks(trees, slots):
    """Chunks of ``slots`` nodes a level, the widest tree's at each level
    (trees grown in lockstep share their chunks)."""
    depths = [np.asarray(t.depth)[:t.n_nodes] for t in trees]
    top = max(int(d.max()) for d in depths)
    return sum(-(-max(int((d == lv).sum()) for d in depths) // slots)
               for lv in range(1, top + 1))


@pytest.mark.parametrize("builder", ["build_tree", "build_trees_batched",
                                     "mesh"])
def test_hist_rows_are_the_root_and_each_smaller_child(tmp_path, builder):
    """The rows the level steps histogram, read in the reads the loop
    already makes: the root's, then the smaller child of each sibling pair,
    on levels split into 16-slot chunks, each chunk one read."""
    table, y, x = small_table(m=2_000)
    if builder == "build_trees_batched":
        cfg = TreeConfig(max_depth=8, chunk_slots=16,
                         task="regression_variance")
        z = np.stack([x[:, 0], x[:, 1] * x[:, 2]]).astype(np.float32)
        fit = lambda: build_trees_batched(table, z, cfg)[0]  # noqa: E731
    elif builder == "mesh":
        from repro.core.distributed import build_tree_distributed
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        cfg = TreeConfig(max_depth=8, chunk_slots=16)
        fit = lambda: [build_tree_distributed(  # noqa: E731
            table, y, cfg, mesh=mesh, n_classes=3)]
    else:
        cfg = TreeConfig(max_depth=8, chunk_slots=16)
        fit = lambda: [build_tree(table, y, cfg, n_classes=3)]  # noqa: E731
    fit()
    trees, delta, host = traced(fit, tmp_path)
    spans = [a["rows"] for n, _, _, a in host if n == "udt.hist"]
    assert sum(spans) == delta["hist_rows"] == sum(least_rows(t)
                                                   for t in trees)
    assert delta["hist_rows"] < len(y) * max(t.max_tree_depth for t in trees)
    # one read per level chunk, the widest class's under the batched build
    chunks = level_chunks(trees, 16)
    assert len(spans) == delta["syncs"] == chunks
    assert chunks > max(t.max_tree_depth for t in trees)


@pytest.mark.parametrize("path", ["single", "multiclass", "mesh"])
def test_each_boosting_round_is_a_span_that_raises_rounds(tmp_path, path):
    """Three rounds of ``GradientBoostedTrees.fit`` open three
    ``boost.round`` spans, rounds 0, 1, 2 over every row, and raise the
    ``rounds`` counter by 3; each round's trees are grown inside it."""
    from repro.core import GradientBoostedTrees
    table, y, _ = small_table()
    loss, kw = "logistic", {}
    if path == "multiclass":
        loss = "softmax"
    else:
        y = (y > 0).astype(np.float32)
    if path == "mesh":
        kw = dict(mesh=jax.make_mesh((1, 1), ("data", "model")))
    cfg = TreeConfig(max_depth=3, task="regression_variance")
    fit = lambda: GradientBoostedTrees(  # noqa: E731
        n_trees=3, loss=loss, learning_rate=0.3, config=cfg).fit(
            table, y, **kw).trees[-1].label
    fit()
    _, delta, host = traced(fit, tmp_path)
    rounds = [ev for ev in host if ev[0] == "boost.round"]
    assert [a["round"] for *_, a in rounds] == [0, 1, 2]
    assert all(a["rows"] == len(y) for *_, a in rounds)
    assert delta["rounds"] == 3
    trees = [ev for ev in host if ev[0] == "udt.tree"]
    assert trees and all(any(inside(t, r) for r in rounds) for t in trees)
    assert delta["compiles"] == 0


def test_the_raw_score_update_names_its_scope():
    """Every instruction of the compiled raw-score update carries the
    ``boost.walk`` scope in its op metadata (parameters aside), and the
    update adds the walk to the scores as the fit's own eager sum does."""
    from repro.core import GradientBoostedTrees, forest
    from repro.core.predict import predict_bins
    table, y, _ = small_table(m=256)
    y = (y > 0).astype(np.float32)
    gbt = GradientBoostedTrees(n_trees=1, loss="logistic", config=TreeConfig(
        max_depth=3, task="regression_variance")).fit(table, y)
    tree, bins = gbt.trees[0], jnp.asarray(table.bins)
    n_num, raw = jnp.asarray(table.n_num), jnp.linspace(-1.0, 1.0, 256)
    args = (predict_bins, raw, 0.3, tree, bins, n_num)
    lowered = forest._raw_update.lower(*args, num_steps=3)
    assert "boost.walk" in lowered.as_text(debug_info=True)
    text = lowered.compile().as_text()
    names = [m.group(1) for line in text.splitlines()
             if " = " in line and "parameter(" not in line
             for m in [re.search(r'op_name="([^"]+)"', line)] if m]
    assert len(names) > 10
    assert all("boost.walk" in n.split("/") for n in names)
    want = raw + 0.3 * predict_bins(tree, bins, n_num, num_steps=3)
    np.testing.assert_allclose(forest._raw_update(*args, num_steps=3), want,
                               rtol=1e-6, atol=1e-7)

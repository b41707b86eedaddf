"""Sibling histogram subtraction: exactness of H_parent - H_small vs a full
recompute of the large child, at the histogram level and through the whole
level-synchronous builder.

The exactness contract (see core/histogram.py): integer-count channels
(classification one-hots, moment channel 0) are sums of exactly-representable
values, so the subtraction is bit-identical to a recompute in float32 below
2**24 examples; float moment channels (sum_y, sum_y2) agree to
accumulation-order tolerance.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import (TreeConfig, build_tree, class_stats, fit_bins,
                        moment_stats, node_histogram,
                        node_histogram_smaller_child,
                        node_histogram_sibling_fused)
from repro.data import make_classification, make_hybrid_table

BACKENDS = ["segment", "onehot"]


def _random_pair_case(rng, m, pairs, k, b, c, *, skew, empty_frac, kind):
    """One property-test case: M examples routed to 2*pairs child slots.

    ``skew`` biases examples toward one side of each pair (the regime where
    subtraction saves the most work), ``empty_frac`` makes some pairs
    entirely one-sided (an empty sibling), and a categorical/missing-style
    bin layout concentrates mass in the top bins like core.binning does.
    """
    pair = rng.integers(0, pairs, size=m)
    side_bias = rng.uniform(size=pairs)
    side = (rng.uniform(size=m) < (skew + (1 - 2 * skew) * side_bias[pair]))
    one_sided = rng.uniform(size=pairs) < empty_frac
    side = np.where(one_sided[pair], 0, side.astype(np.int64))
    slot = (2 * pair + side).astype(np.int32)
    slot[rng.uniform(size=m) < 0.1] = -1          # inactive examples
    bins = rng.integers(0, b, size=(m, k))
    missing = rng.uniform(size=(m, k)) < 0.15     # missing/categorical bins
    bins = np.where(missing, b - 1, bins).astype(np.int32)
    if kind == "class":
        stats = class_stats(jnp.asarray(rng.integers(0, c, size=m)), c)
    else:
        stats = moment_stats(jnp.asarray(rng.normal(size=m) * 10))
    return jnp.asarray(bins), jnp.asarray(stats), jnp.asarray(slot), pair


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("kind", ["class", "moment"])
@pytest.mark.parametrize("seed", range(6))
def test_subtraction_identity_property(backend, kind, seed):
    rng = np.random.default_rng(seed)
    m = int(rng.integers(50, 800))
    pairs = int(rng.integers(1, 9))
    k = int(rng.integers(1, 5))
    b = int(rng.integers(3, 20))
    c = int(rng.integers(2, 6))
    skew = float(rng.uniform(0, 0.45))
    bins, stats, slot, pair = _random_pair_case(
        rng, m, pairs, k, b, c, skew=skew, empty_frac=0.25, kind=kind)
    s = 2 * pairs

    h_child = node_histogram(bins, stats, slot, num_slots=s, n_bins=b,
                             backend=backend)
    # the parent histogram exactly as the previous level scattered it: one
    # slot per pair, accumulated over the union of both children's examples
    h_parent = node_histogram(bins, stats,
                              jnp.where(slot >= 0, slot // 2, -1),
                              num_slots=pairs, n_bins=b, backend=backend)

    cnt = np.asarray(jnp.zeros(s).at[np.maximum(np.asarray(slot), 0)].add(
        np.asarray(slot) >= 0))
    small_is_left = cnt[0::2] <= cnt[1::2]
    compute = np.stack([small_is_left, ~small_is_left], 1).reshape(s)
    h_small = node_histogram_smaller_child(
        bins, stats, slot, jnp.asarray(compute), num_slots=s, n_bins=b,
        backend=backend)

    # 1) the packed scatter equals the full scatter's computed-child rows
    # (bit-equal on integer channels; the onehot backend's matmul may
    # accumulate float moments in a different order for the packed shape)
    want_small = np.stack([np.asarray(h_child)[2 * j + int(~small_is_left[j])]
                           for j in range(pairs)])
    if kind == "class":
        np.testing.assert_array_equal(np.asarray(h_small), want_small)
    else:
        np.testing.assert_allclose(np.asarray(h_small), want_small,
                                   rtol=1e-5, atol=1e-4)
        np.testing.assert_array_equal(np.asarray(h_small)[..., 0],
                                      want_small[..., 0])

    # 2) subtraction reproduces the large sibling
    derived = np.asarray(h_parent) - np.asarray(h_small)
    want_large = np.stack([np.asarray(h_child)[2 * j + int(small_is_left[j])]
                           for j in range(pairs)])
    if kind == "class":
        np.testing.assert_array_equal(derived, want_large)
    else:
        np.testing.assert_allclose(derived, want_large, rtol=1e-4, atol=1e-2)
        # moment channel 0 is an integer count: exact even in float32
        np.testing.assert_array_equal(derived[..., 0], want_large[..., 0])


def test_smaller_child_pallas_matches_segment():
    rng = np.random.default_rng(7)
    bins, stats, slot, _ = _random_pair_case(rng, 300, 4, 3, 9, 3,
                                             skew=0.3, empty_frac=0.25,
                                             kind="class")
    # mixed left/right computed slots so the slot remap is exercised
    # at both even and odd source slots (the ~small_is_left case)
    compute = jnp.asarray([True, False, False, True, False, True, True,
                           False])
    a = node_histogram_smaller_child(bins, stats, slot, compute, num_slots=8,
                                     n_bins=9, backend="segment")
    p = node_histogram_smaller_child(bins, stats, slot, compute, num_slots=8,
                                     n_bins=9, backend="pallas")
    np.testing.assert_allclose(np.asarray(p), np.asarray(a),
                               rtol=1e-5, atol=1e-5)


def _fused_case_inputs(rng, m, pairs, k, b, c, *, skew, empty_frac, kind):
    """Shared setup for the fused-epilogue parity tests: a random pair case
    plus its true parent histogram (the union of each pair's children, as
    the previous level scattered it) and the smaller-child compute mask."""
    bins, stats, slot, _ = _random_pair_case(
        rng, m, pairs, k, b, c, skew=skew, empty_frac=empty_frac, kind=kind)
    s = 2 * pairs
    h_parent = node_histogram(bins, stats,
                              jnp.where(slot >= 0, slot // 2, -1),
                              num_slots=pairs, n_bins=b, backend="segment")
    cnt = np.asarray(jnp.zeros(s).at[np.maximum(np.asarray(slot), 0)].add(
        np.asarray(slot) >= 0))
    small_is_left = cnt[0::2] <= cnt[1::2]
    compute = jnp.asarray(
        np.stack([small_is_left, ~small_is_left], 1).reshape(s))
    return bins, stats, slot, compute, h_parent


@pytest.mark.parametrize("kind", ["class", "moment"])
@pytest.mark.parametrize("seed", range(5))
def test_fused_epilogue_matches_jnp_derivation(kind, seed):
    """The kernel-fused sibling block (interpret mode) vs the jnp
    ``H_parent - H_small`` path: bit-identical for classification counts,
    documented tolerance (and exact integer channel 0) for float moments."""
    rng = np.random.default_rng(100 + seed)
    m = int(rng.integers(50, 800))
    pairs = int(rng.integers(1, 9))
    k = int(rng.integers(1, 5))
    b = int(rng.integers(3, 20))
    c = int(rng.integers(2, 6))
    bins, stats, slot, compute, h_parent = _fused_case_inputs(
        rng, m, pairs, k, b, c, skew=float(rng.uniform(0, 0.45)),
        empty_frac=0.25, kind=kind)
    s = 2 * pairs
    fused = node_histogram_sibling_fused(bins, stats, slot, compute,
                                         h_parent, num_slots=s, n_bins=b,
                                         backend="pallas")
    want = node_histogram_sibling_fused(bins, stats, slot, compute,
                                        h_parent, num_slots=s, n_bins=b,
                                        backend="segment")
    assert fused.shape == (s, k, b, c if kind == "class" else 3)
    if kind == "class":
        np.testing.assert_array_equal(np.asarray(fused), np.asarray(want))
    else:
        np.testing.assert_allclose(np.asarray(fused), np.asarray(want),
                                   rtol=1e-5, atol=1e-4)
        np.testing.assert_array_equal(np.asarray(fused)[..., 0],
                                      np.asarray(want)[..., 0])


@pytest.mark.parametrize("kind", ["class", "moment"])
def test_fused_epilogue_empty_and_skewed_siblings(kind):
    """Degenerate pair shapes: most pairs entirely one-sided (the derived
    sibling is the whole parent or empty) and a heavy routing skew."""
    rng = np.random.default_rng(42)
    bins, stats, slot, compute, h_parent = _fused_case_inputs(
        rng, 600, 6, 3, 11, 4, skew=0.48, empty_frac=0.7, kind=kind)
    fused = node_histogram_sibling_fused(bins, stats, slot, compute,
                                         h_parent, num_slots=12, n_bins=11,
                                         backend="pallas")
    want = node_histogram_sibling_fused(bins, stats, slot, compute,
                                        h_parent, num_slots=12, n_bins=11,
                                        backend="segment")
    if kind == "class":
        np.testing.assert_array_equal(np.asarray(fused), np.asarray(want))
    else:
        np.testing.assert_allclose(np.asarray(fused), np.asarray(want),
                                   rtol=1e-5, atol=1e-4)
        np.testing.assert_array_equal(np.asarray(fused)[..., 0],
                                      np.asarray(want)[..., 0])


def test_fused_epilogue_level_step_jaxpr_has_no_jnp_derivation():
    """Acceptance gate: with the pallas backend the level step's jaxpr
    contains the histogram pallas_call but NO jnp subtraction over the
    packed [S/2, K, B, C] pair axis — the sibling derivation happens only
    inside the kernel epilogue.  Walks the trace with the shared
    repro.check walker, pallas body excluded (in-kernel ops are the point
    of the fusion)."""
    import jax

    from repro.check import iter_eqns
    from repro.core.tree import _chunk_step, _init_arrays

    m, k, b, c, s, max_nodes = 64, 3, 8, 2, 8, 64
    rng = np.random.default_rng(0)
    args = (jnp.asarray(rng.integers(0, b, size=(m, k)), jnp.int32),
            jnp.asarray(np.eye(c, dtype=np.float32)[
                rng.integers(0, c, size=m)]),
            jnp.zeros((m,), jnp.int32),                 # lbins
            jnp.zeros((m,), jnp.float32),               # y
            jnp.asarray(rng.integers(0, s, size=m), jnp.int32),  # assign
            _init_arrays(max_nodes),
            jnp.ones((s // 2, k, b, c), jnp.float32),   # phist_pairs
            jnp.full((k,), b, jnp.int32),
            jnp.zeros((k,), jnp.int32),
            jnp.int32(0), jnp.int32(s), jnp.int32(s), jnp.int32(2))
    kw = dict(num_slots=s, n_bins=b, heuristic="info_gain",
              task="classification", min_samples_split=2,
              min_samples_leaf=1, max_depth=5, max_nodes=max_nodes,
              hist_backend="pallas", select_backend="jnp", n_label_bins=1,
              use_sub=True, want_hist=True)
    jaxpr = jax.make_jaxpr(lambda *a: _chunk_step(*a, **kw))(*args)
    eqns = list(iter_eqns(jaxpr.jaxpr, enter_pallas=False))
    assert any(e.primitive.name == "pallas_call" for e in eqns)
    packed = {(s // 2, k, b, c)}
    bad = [e for e in eqns if e.primitive.name == "sub"
           and any(tuple(v.aval.shape) in packed for v in e.invars)]
    assert not bad, f"jnp sibling derivation survived fusion: {bad}"


def test_builder_subtraction_pallas_backend():
    """Tiny end-to-end build on the Pallas (interpret-mode) backend: the
    subtraction tree must match the recompute tree bit-for-bit."""
    cols, y = make_classification(300, 4, 2, seed=8)
    table = fit_bins(cols, max_num_bins=16)
    cfg = dict(max_depth=5, hist_backend="pallas", chunk_slots=16)
    on = build_tree(table, y, TreeConfig(**cfg), n_classes=2)
    off = build_tree(table, y, TreeConfig(**cfg, sibling_subtraction=False),
                     n_classes=2)
    assert on.n_nodes == off.n_nodes
    for f in ("feat", "op", "tbin", "count", "left", "right", "leaf"):
        np.testing.assert_array_equal(np.asarray(getattr(on, f)),
                                      np.asarray(getattr(off, f)), err_msg=f)


@pytest.mark.parametrize("backend", BACKENDS)
def test_builder_subtraction_tree_identical(backend):
    """End-to-end: subtraction on vs off yields the bit-identical
    classification tree (hybrid features: numeric + categorical + missing),
    including with multi-chunk levels."""
    cols, y = make_classification(1500, 6, 3, seed=3, n_cat_features=2,
                                  missing_frac=0.05)
    table = fit_bins(cols, max_num_bins=32)
    for chunk_slots in (0, 16):
        on = build_tree(table, y, TreeConfig(max_depth=12,
                                             hist_backend=backend,
                                             chunk_slots=chunk_slots),
                        n_classes=3)
        off = build_tree(table, y, TreeConfig(max_depth=12,
                                              hist_backend=backend,
                                              chunk_slots=chunk_slots,
                                              sibling_subtraction=False),
                         n_classes=3)
        assert on.n_nodes == off.n_nodes
        assert on.max_tree_depth >= 7       # deep enough to exercise caching
        for f in ("feat", "op", "tbin", "label", "count", "left", "right",
                  "leaf", "parent"):
            np.testing.assert_array_equal(np.asarray(getattr(on, f)),
                                          np.asarray(getattr(off, f)), err_msg=f)
        np.testing.assert_allclose(np.asarray(on.score),
                                   np.asarray(off.score), atol=1e-5)


def test_builder_odd_chunk_slots():
    """An odd chunk_slots (user-set or unlucky auto budget) must not break
    the pair layout: the builder rounds the slot count down to even and
    still produces the recompute tree."""
    cols, y = make_classification(800, 5, 3, seed=6)
    table = fit_bins(cols, max_num_bins=32)
    odd = build_tree(table, y, TreeConfig(max_depth=10, chunk_slots=15),
                     n_classes=3)
    ref = build_tree(table, y, TreeConfig(max_depth=10, chunk_slots=15,
                                          sibling_subtraction=False),
                     n_classes=3)
    assert odd.n_nodes == ref.n_nodes
    np.testing.assert_array_equal(np.asarray(odd.feat), np.asarray(ref.feat))


def test_builder_subtraction_hybrid_rule_recovered():
    cols, y = make_hybrid_table(600, seed=4)
    table = fit_bins(cols)
    on = build_tree(table, y, TreeConfig(max_depth=32), n_classes=2)
    off = build_tree(table, y, TreeConfig(max_depth=32,
                                          sibling_subtraction=False),
                     n_classes=2)
    assert on.n_nodes == off.n_nodes
    np.testing.assert_array_equal(np.asarray(on.tbin), np.asarray(off.tbin))


def test_builder_resume_with_phist_cache():
    """Resuming from a BuildState that carries the histogram cache keeps the
    subtraction fast path and reproduces the straight build exactly."""
    cols, y = make_classification(1000, 6, 3, seed=5, n_cat_features=1)
    table = fit_bins(cols, max_num_bins=32)
    cfg = TreeConfig(max_depth=10)
    full = build_tree(table, y, cfg, n_classes=3)
    states = []
    build_tree(table, y, cfg, n_classes=3, level_callback=states.append)
    mid = states[len(states) // 2]
    assert mid.phist is not None            # the cache rode along
    resumed = build_tree(table, y, cfg, n_classes=3, resume=mid)
    assert resumed.n_nodes == full.n_nodes
    for f in ("feat", "op", "tbin", "count", "left", "right", "leaf",
              "parent"):
        np.testing.assert_array_equal(np.asarray(getattr(full, f)),
                                      np.asarray(getattr(resumed, f)))

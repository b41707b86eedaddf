"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import numpy as np
import jax.numpy as jnp
import pytest
pytest.importorskip("hypothesis")  # CI installs it; degrade to skips locally
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref

SHAPES = [
    # (M, K, B, C, S)
    (64, 1, 4, 2, 1),
    (300, 5, 17, 4, 6),
    (128, 3, 33, 2, 9),       # odd bins, slot count > slot_chunk
    (1000, 2, 8, 26, 3),      # many classes
    (37, 7, 5, 3, 2),         # M not divisible by tile
    (200, 11, 6, 3, 4),       # K past one feature block (padded features)
]


def _mk(m, k, b, c, s, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    bins = jnp.asarray(rng.integers(0, b, size=(m, k)), dtype=jnp.int32)
    stats = jnp.asarray(rng.uniform(size=(m, c)).astype(dtype))
    slot = jnp.asarray(rng.integers(-1, s, size=(m,)), dtype=jnp.int32)
    return bins, stats, slot


@pytest.mark.parametrize("m,k,b,c,s", SHAPES)
def test_histogram_kernel_matches_ref(m, k, b, c, s):
    bins, stats, slot = _mk(m, k, b, c, s)
    got = ops.histogram(bins, stats, slot, num_slots=s, n_bins=b)
    want = ref.histogram_ref(bins, stats, slot, num_slots=s, n_bins=b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("m,k,b,c,s", SHAPES[:3])
@pytest.mark.parametrize("tile", [64, 256])
def test_histogram_kernel_tile_invariance(m, k, b, c, s, tile):
    from repro.kernels.histogram import histogram_pallas
    bins, stats, slot = _mk(m, k, b, c, s, seed=1)
    got = histogram_pallas(bins, stats, slot, num_slots=s, n_bins=b,
                           slot_chunk=2, example_tile=tile, interpret=True)
    want = ref.histogram_ref(bins, stats, slot, num_slots=s, n_bins=b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_histogram_mass_conservation():
    bins, stats, slot = _mk(500, 4, 16, 3, 8, seed=2)
    h = np.asarray(ops.histogram(bins, stats, slot, num_slots=8, n_bins=16))
    active = np.asarray(slot) >= 0
    want = np.asarray(stats)[active].sum(0)
    np.testing.assert_allclose(h.sum(axis=(0, 2)),
                               np.tile(want, (4, 1)), rtol=1e-4)


@pytest.mark.parametrize("m,k,b,c,s", SHAPES)
@pytest.mark.parametrize("heur", ["info_gain", "gini", "chi_square"])
def test_split_scan_matches_ref(m, k, b, c, s, heur):
    rng = np.random.default_rng(42)
    hist = jnp.asarray(rng.poisson(2, size=(s, k, b, c)), dtype=jnp.float32)
    n_num = jnp.asarray(rng.integers(0, b, size=(k,)), dtype=jnp.int32)
    n_cat = jnp.asarray(np.minimum(rng.integers(0, 4, size=(k,)),
                                   b - np.asarray(n_num)), dtype=jnp.int32)
    s1, b1, o1 = ops.split_scan(hist, n_num, n_cat, heuristic=heur)
    s0, b0, o0 = ref.split_scan_ref(hist, n_num, n_cat, heuristic=heur)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s0),
                               rtol=1e-5, atol=1e-5)
    # bin/op must agree wherever the best score is unique
    ties = np.isclose(np.asarray(s1), np.asarray(s0), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(b1)[ties & (np.asarray(b1) == np.asarray(b0))],
                                  np.asarray(b0)[ties & (np.asarray(b1) == np.asarray(b0))])


def test_split_scan_sse_moments():
    rng = np.random.default_rng(3)
    s, k, b = 4, 3, 12
    hist = np.zeros((s, k, b, 3), dtype=np.float32)
    cnt = rng.poisson(5, size=(s, k, b)).astype(np.float32)
    mu = rng.normal(size=(s, k, b)).astype(np.float32)
    hist[..., 0] = cnt
    hist[..., 1] = cnt * mu
    hist[..., 2] = cnt * (mu ** 2 + 0.1)
    hist = jnp.asarray(hist)
    n_num = jnp.full((k,), b, dtype=jnp.int32)
    n_cat = jnp.zeros((k,), dtype=jnp.int32)
    s1, b1, o1 = ops.split_scan(hist, n_num, n_cat, heuristic="sse")
    s0, b0, o0 = ref.split_scan_ref(hist, n_num, n_cat, heuristic="sse")
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s0), rtol=1e-4)


def _mk_fused(m, k, b, c, pairs, seed=0):
    """Random fused-epilogue case: slots over 2*pairs, one computed child
    per pair, arbitrary parent rows (the oracle subtracts whatever it is
    handed, so parents need not be consistent unions here)."""
    rng = np.random.default_rng(seed)
    bins, stats, slot = _mk(m, k, b, c, 2 * pairs, seed=seed)
    compute = np.zeros(2 * pairs, dtype=bool)
    side = rng.integers(0, 2, size=pairs)
    compute[2 * np.arange(pairs) + side] = True
    slot_map = jnp.asarray(
        np.where(compute, np.arange(2 * pairs) // 2, -1), dtype=jnp.int32)
    phist = jnp.asarray(rng.uniform(1, 9, size=(pairs, k, b, c)),
                        dtype=jnp.float32)
    return bins, stats, slot, slot_map, phist, jnp.asarray(1 - side)


def _remap(slot, slot_map):
    """Raw slot ids -> packed pair ids, as the builder hands the kernel."""
    return jnp.where(slot >= 0, slot_map[jnp.clip(slot, 0, None)], -1)


@pytest.mark.parametrize("m,k,b,c,p", SHAPES)
def test_histogram_fused_sibling_matches_ref(m, k, b, c, p):
    bins, stats, slot, slot_map, phist, side = _mk_fused(m, k, b, c, p,
                                                         seed=p)
    got = ops.histogram(bins, stats, _remap(slot, slot_map), num_slots=p,
                        n_bins=b, phist=phist, side=side)
    want = ref.sibling_ref(bins, stats, slot, slot_map, phist, side,
                           num_pairs=p, n_bins=b)
    assert got.shape == (2 * p, k, b, c)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("tile", [64, 256])
@pytest.mark.parametrize("slot_chunk", [2, 5])
def test_histogram_fused_sibling_tile_invariance(tile, slot_chunk):
    from repro.kernels.histogram import histogram_pallas
    m, k, b, c, p = 300, 3, 7, 4, 6
    bins, stats, slot, slot_map, phist, side = _mk_fused(m, k, b, c, p,
                                                         seed=5)
    got = histogram_pallas(bins, stats, _remap(slot, slot_map), num_slots=p,
                           n_bins=b, slot_chunk=slot_chunk, example_tile=tile,
                           interpret=True, phist=phist, side=side)
    want = ref.sibling_ref(bins, stats, slot, slot_map, phist, side,
                           num_pairs=p, n_bins=b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 200), st.integers(1, 4), st.integers(2, 20),
       st.integers(1, 5), st.integers(1, 7), st.integers(0, 10_000))
def test_property_histogram_random_shapes(m, k, b, c, s, seed):
    bins, stats, slot = _mk(m, k, b, c, s, seed=seed)
    got = ops.histogram(bins, stats, slot, num_slots=s, n_bins=b)
    want = ref.histogram_ref(bins, stats, slot, num_slots=s, n_bins=b)
    assert got.shape == (s, k, b, c)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_importing_repro_starts_no_backend():
    """The kernels pick interpret vs compiled mode when called, so no
    module of the package (``repro.kernels.ops`` and ``repro.check``
    included) starts a JAX backend as it is imported."""
    import os
    import subprocess
    import sys
    script = (
        "import importlib, pkgutil, repro\n"
        "from jax._src import xla_bridge\n"
        "for m in pkgutil.walk_packages(repro.__path__, 'repro.'):\n"
        "    if not m.name.endswith('__main__'):\n"
        "        importlib.import_module(m.name)\n"
        "    assert not xla_bridge.backends_are_initialized(), m.name\n"
        "print('NO_BACKEND_OK')\n")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    assert "NO_BACKEND_OK" in r.stdout

"""Deviceless TPU compiles of the Pallas kernels and the default level
step at KDD99-10% width.

Interpret mode (what the rest of the suite runs) never meets the TPU's
Mosaic compiler, which refuses block shapes off the (8, 128) tiling, 1-D
iotas, unsupported in-kernel ops and VMEM overflows.  These tests compile
both kernels with ``interpret=False`` for a *described* v5e chip (no device
needed), at the paper's KDD99-10% shape: 494,021 rows x 41 features, 65
bins (``fit_bins(max_num_bins=64)`` plus the missing-value bin), 5
classes, 16 node slots.  The default (``segment`` histogram) level step
is compiled the same way and held to one v5e chip's 16 GiB of HBM by the
compiler's own memory analysis: its scatter once needed 11 GB for one
tree and 52 GB for the class-batched softmax step.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, so describing it while
modules are collected would make parallel test workers disagree about
which tests exist.
"""
import functools
import os

import pytest

M, K, B, C, S = 494_021, 41, 65, 5, 16
V5E_HBM_BYTES = 16 * 2 ** 30


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:             # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back without one, so
    # keep it out of the persistent cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    import jax
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _shapes(one_chip):
    import jax
    import jax.numpy as jnp
    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    return dict(bins=sd((M, K), jnp.int32), stats=sd((M, C), jnp.float32),
                slot=sd((M,), jnp.int32), weights=sd((M,), jnp.float32),
                compute=sd((S,), jnp.bool_),
                phist=sd((S // 2, K, B, C), jnp.float32),
                side=sd((S // 2,), jnp.int32))


@pytest.mark.parametrize("variant", ["plain", "weights_slot_map", "fused",
                                     "fused_weights"])
def test_histogram_compiles_for_v5e(one_chip, variant):
    from repro.kernels.histogram import histogram_pallas
    x = _shapes(one_chip)
    if variant == "plain":
        fn = functools.partial(histogram_pallas, num_slots=S, n_bins=B,
                               slot_chunk=S, interpret=False)
        _compile(fn, x["bins"], x["stats"], x["slot"])
        return
    # the sibling-subtraction builder's call: raw slot ids packed onto the
    # pair axis in front of the kernel
    from repro.core.histogram import _packed_slot
    names = ["bins", "stats", "slot", "compute"]
    if variant != "fused":
        names.append("weights")
    if variant.startswith("fused"):
        names += ["phist", "side"]

    def fn(*args):
        kw = dict(zip(names, args))
        slot = _packed_slot(kw.pop("slot"), kw.pop("compute"), S)
        return histogram_pallas(kw.pop("bins"), kw.pop("stats"), slot,
                                num_slots=S // 2, n_bins=B,
                                slot_chunk=S // 2, interpret=False, **kw)
    _compile(fn, *(x[n] for n in names))


@pytest.mark.parametrize("heuristic,c", [("info_gain", C), ("gini", C),
                                         ("sse", 3)])
def test_split_scan_compiles_for_v5e(one_chip, heuristic, c):
    import jax
    import jax.numpy as jnp
    from repro.kernels.split_scan import split_scan_pallas
    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    fn = functools.partial(split_scan_pallas, heuristic=heuristic,
                           interpret=False)
    _compile(fn, sd((S, K, B, c), jnp.float32), sd((K,), jnp.int32),
             sd((K,), jnp.int32))


@pytest.mark.parametrize("step", ["tree", "softmax"])
def test_segment_level_step_fits_v5e_hbm(one_chip, step):
    """The default-backend level step at a sibling-subtraction level (32
    slots, parent histograms cached): one classification tree, and the
    class-batched step of 5-class softmax boosting (weighted variance
    moments, one vmapped lane per class)."""
    import jax
    import jax.numpy as jnp
    from repro.core.tree import (_chunk_step, _chunk_step_classes,
                                 _hist_channels, _init_arrays)
    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    s, n_nodes = 32, 2 * M + 1
    lanes = () if step == "tree" else (C,)
    c = C if step == "tree" else 3
    # the cached parent histograms carry a weighted step's row count too
    hc = c if step == "tree" else _hist_channels(c, "regression_variance",
                                                 True)
    arrays = {f: sd(lanes + a.shape, a.dtype)
              for f, a in _init_arrays(n_nodes).items()}
    args = [sd((M, K), jnp.int32), sd((M, c), jnp.float32),
            sd((M,), jnp.int32), sd(lanes + (M,), jnp.float32),
            sd(lanes + (M,), jnp.int32), arrays,
            sd(lanes + (s // 2, K, B, hc), jnp.float32),
            sd((K,), jnp.int32), sd((K,), jnp.int32)]
    args += [sd(lanes, jnp.int32)] * 3 + [sd((), jnp.int32)]
    kw = dict(num_slots=s, n_bins=B, min_samples_split=2,
              min_samples_leaf=1, max_nodes=n_nodes, hist_backend="segment",
              select_backend="jnp", n_label_bins=1, use_sub=True,
              want_hist=True)
    if step == "tree":
        fn = _chunk_step
        kw.update(heuristic="info_gain", task="classification", max_depth=64)
    else:
        fn = _chunk_step_classes
        args.append(sd(lanes + (M,), jnp.float32))
        kw.update(heuristic="sse", task="regression_variance", max_depth=6,
                  weighted=True)
    mem = fn.lower(*args, **kw).compile().memory_analysis()
    used = mem.temp_size_in_bytes + mem.argument_size_in_bytes
    assert used < V5E_HBM_BYTES, (mem.temp_size_in_bytes,
                                  mem.argument_size_in_bytes)

"""repro.check: the walker's exactly-once guarantee, every rule's
deliberate-violation path, and the gate flipping nonzero on a seeded
mutation of a real surface.

The walker property: for ANY nesting of scan / cond / while / jit,
``iter_eqns`` yields every equation exactly once (no duplicates from a
sub-jaxpr reachable through two params paths, no misses from a container
shape it doesn't know).  The sin-count oracle is computed alongside the
random program construction: each ``cond`` doubles the live body (two
branch jaxprs), everything else keeps it — so the expected count is
2^(#conds above the leaf), independent of the walker under test.

The mutation tests are the gate's acceptance demo: swap the sharded
grid-count psum for an all_gather (the classic "accidentally replicate
the reduction" regression) or smuggle a ``jax.device_get`` into the
routed serve walk, and ``python -m repro.check`` must exit nonzero.
"""
import os
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.check import (BANNED_GATHER_PRIMS, COLLECTIVE_PRIMS,
                         CollectiveBudget, DonationCheck, DTypePolicy,
                         NoDynamicShapes, NoHostTransfer, ScratchBudget,
                         Surface, iter_eqns, prim_names)
from repro.check.walker import TRANSPARENT_PRIMS


# -- walker ----------------------------------------------------------------


def _build_nested(ops):
    """Wrap a sin leaf in combinators outward-in; returns (fn, expected
    number of sin equations in the full recursive trace)."""
    fn = jnp.sin
    n_sin = 1
    for op in ops:
        prev = fn
        if op == "scan":
            def fn(x, prev=prev):
                y, _ = jax.lax.scan(lambda c, _: (prev(c), None), x,
                                    None, length=2)
                return y
        elif op == "cond":
            n_sin *= 2          # both branches trace their own jaxpr
            # the false branch is a DISTINCT function: identical branch
            # callables share one traced jaxpr object, which would make
            # the id-based exactly-once assertion below vacuous
            def fn(x, prev=prev):
                return jax.lax.cond(x[0] > 0, prev,
                                    lambda v: prev(v) * 1.0, x)
        elif op == "while":
            def fn(x, prev=prev):
                return jax.lax.while_loop(lambda c: c[0] < 0, prev, x)
        else:                   # "jit"
            def fn(x, prev=prev):
                return jax.jit(prev)(x)
    return fn, n_sin


def _ref_eqn_count(jaxpr) -> int:
    """Independent oracle: jax's own non-recursive ``core.subjaxprs``
    (a different params traversal), recursed by the test itself."""
    import jax.core as jc
    return len(jaxpr.eqns) + sum(_ref_eqn_count(s)
                                 for s in jc.subjaxprs(jaxpr))


def _check_exactly_once(ops):
    fn, n_sin = _build_nested(ops)
    j = jax.make_jaxpr(fn)(jnp.ones((3,), jnp.float32))
    # exactly-once is per OCCURRENCE, not per object: jax caches traces,
    # so one jaxpr object can legitimately appear under several parents
    # (e.g. the same scan body reached through both cond branches)
    assert len(list(iter_eqns(j))) == _ref_eqn_count(j.jaxpr), ops
    names = prim_names(j)
    assert names.count("sin") == n_sin, (ops, names)
    assert not TRANSPARENT_PRIMS & set(names)       # dropped, bodies kept
    # transparent=() keeps the wrapper names in the sequence
    kept = prim_names(j, transparent=())
    assert kept.count("sin") == n_sin


@pytest.mark.parametrize("ops", [
    (), ("cond",), ("jit", "scan"), ("scan", "cond", "jit", "cond"),
    ("while", "cond", "scan"), ("jit", "jit", "while")])
def test_walker_exactly_once_seeded(ops):
    _check_exactly_once(ops)


def test_walker_exactly_once_property():
    pytest.importorskip("hypothesis")  # CI installs it; degrade locally
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=20, deadline=None)
    @given(st.lists(st.sampled_from(["scan", "cond", "while", "jit"]),
                    max_size=5))
    def check(ops):
        _check_exactly_once(tuple(ops))

    check()


def test_walker_accepts_open_and_closed_jaxprs():
    j = jax.make_jaxpr(jnp.sin)(1.0)
    assert prim_names(j) == prim_names(j.jaxpr) == ["sin"]
    with pytest.raises(TypeError, match="not a jaxpr"):
        list(iter_eqns("nope"))


def test_walker_pallas_boundary():
    """enter_pallas=False still yields the pallas_call equation (so
    ScratchBudget can see the kernel) but not its body (in-kernel ops are
    not XLA ops)."""
    pl = pytest.importorskip("jax.experimental.pallas")

    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    def f(x):
        return pl.pallas_call(
            kernel, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            interpret=True)(x)

    j = jax.make_jaxpr(f)(jnp.ones((8, 8), jnp.float32))
    inside = prim_names(j)
    outside = prim_names(j, enter_pallas=False)
    assert "pallas_call" in inside and "pallas_call" in outside
    assert "mul" in inside
    assert "mul" not in outside


# -- rules: one deliberate violation per rule ------------------------------


_MESH1 = None


def _mesh1():
    global _MESH1
    if _MESH1 is None:
        from jax.sharding import Mesh
        _MESH1 = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    return _MESH1


def _sharded_surface(body, x):
    from jax.sharding import PartitionSpec as P
    fn = jax.shard_map(body, mesh=_mesh1(), in_specs=P("data"),
                       out_specs=P(), check_vma=False)
    return Surface(jaxpr=jax.make_jaxpr(fn)(x), label="test")


def test_collective_budget_bans_gathers():
    s = _sharded_surface(lambda x: jax.lax.all_gather(x, "data").sum(),
                         jnp.ones((4,), jnp.float32))
    viol = CollectiveBudget().check(s)
    assert any("banned collective: all_gather" in str(v) for v in viol)
    assert BANNED_GATHER_PRIMS < COLLECTIVE_PRIMS


def test_collective_budget_unlisted_collective_fails():
    """Any collective outside ``allowed`` is a violation, banned or not."""
    s = _sharded_surface(lambda x: jax.lax.pmin(x.sum(), "data"),
                         jnp.ones((4,), jnp.float32))
    assert CollectiveBudget().check(s)
    assert not CollectiveBudget({"pmin": 1}).check(s)


def test_collective_budget_count_and_operand_specs():
    x = jnp.ones((4,), jnp.float32)
    twice = _sharded_surface(
        lambda x: jax.lax.psum(x.sum(), "data")
        + jax.lax.psum((x * 2).sum(), "data"), x)
    viol = CollectiveBudget({"psum": 1}).check(twice)
    assert any("appears 2x, budget 1" in str(v) for v in viol)
    assert not CollectiveBudget({"psum": 2}).check(twice)

    vec = _sharded_surface(lambda x: jax.lax.psum(x, "data").sum(), x)
    assert any("must be scalar" in str(v) for v in CollectiveBudget(
        {"psum": dict(max=1, scalar=True)}).check(vec))
    assert any("> max_rank 0" in str(v) for v in CollectiveBudget(
        {"psum": dict(max_rank=0)}).check(vec))
    assert any("contract says int32" in str(v) for v in CollectiveBudget(
        {"psum": dict(dtype="int32")}).check(vec))
    # bulk cap counts operands at/above bulk_rank across allowed prims
    assert any("bulk collectives" in str(v) for v in CollectiveBudget(
        {"psum": dict()}, max_bulk=0, bulk_rank=1).check(vec))
    assert not CollectiveBudget(
        {"psum": dict(max=1, max_rank=1)}, max_bulk=1,
        bulk_rank=1).check(vec)


def test_no_host_transfer_flags_callbacks():
    def f(x):
        return jax.pure_callback(
            lambda v: v, jax.ShapeDtypeStruct((), jnp.float32), x.sum())

    s = Surface(jaxpr=jax.make_jaxpr(f)(jnp.ones(3)), label="cb")
    viol = NoHostTransfer().check(s)
    assert any("pure_callback" in str(v) for v in viol)
    clean = Surface(jaxpr=jax.make_jaxpr(jnp.sin)(1.0))
    assert not NoHostTransfer().check(clean)


def test_dtype_policy_catches_banned_dtype():
    # f64 needs jax_enable_x64, so exercise the mechanism on int32
    s = Surface(jaxpr=jax.make_jaxpr(
        lambda: jnp.arange(4, dtype=jnp.int32).sum())())
    assert any("int32" in str(v)
               for v in DTypePolicy(banned=("int32",)).check(s))
    assert not DTypePolicy().check(s)       # default bans f64/complex only


def _fake_surface(shapes):
    """A hand-built object passing the walker's duck typing, carrying
    avals no real CPU trace can produce (symbolic/bool dims)."""
    var = lambda sh: SimpleNamespace(
        aval=SimpleNamespace(shape=sh, dtype=np.dtype("float32")))
    eqn = SimpleNamespace(primitive=SimpleNamespace(name="fake"),
                          params={}, invars=[var(s) for s in shapes],
                          outvars=[])
    jaxpr = type("Jaxpr", (), {})()
    jaxpr.eqns, jaxpr.invars, jaxpr.constvars = [eqn], [], []
    return Surface(jaxpr=jaxpr, label="fake")


def test_no_dynamic_shapes_flags_symbolic_dims():
    viol = NoDynamicShapes().check(_fake_surface([(4, None), (True, 2)]))
    assert len(viol) == 2
    assert any("non-static dim None" in str(v) for v in viol)
    assert not NoDynamicShapes().check(
        Surface(jaxpr=jax.make_jaxpr(jnp.sin)(jnp.ones((3, 2)))))


def test_donation_check_needs_lowering_and_donated_args():
    import warnings
    x = jax.ShapeDtypeStruct((4,), jnp.float32)
    bare = Surface(jaxpr=jax.make_jaxpr(jnp.sin)(jnp.ones(4)))
    assert any("no lowering" in str(v) for v in DonationCheck().check(bare))

    with warnings.catch_warnings():
        warnings.filterwarnings("ignore", message=".*[Dd]onat.*")
        undonated = jax.jit(lambda v: v + 1).lower(x)
        donated = jax.jit(lambda v: v + 1, donate_argnums=(0,)).lower(x)
    assert any("0 donated buffers" in str(v) for v in DonationCheck().check(
        Surface(jaxpr=jax.make_jaxpr(jnp.sin)(jnp.ones(4)),
                lowered=undonated)))
    assert not DonationCheck().check(
        Surface(jaxpr=jax.make_jaxpr(jnp.sin)(jnp.ones(4)),
                lowered=donated))


def test_scratch_budget_caps_kernel_blocks():
    pl = pytest.importorskip("jax.experimental.pallas")

    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    def f(x):
        return pl.pallas_call(
            kernel, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            interpret=True)(x)

    s = Surface(jaxpr=jax.make_jaxpr(f)(jnp.ones((8, 8), jnp.float32)))
    # 2 blocks x 8x8 f32 = 512 B resident: fits 1 KiB, busts 100 B
    assert not ScratchBudget(1024, require_pallas=True).check(s)
    viol = ScratchBudget(100).check(s)
    assert any("> cap 100 B" in str(v) for v in viol)
    plain = Surface(jaxpr=jax.make_jaxpr(jnp.sin)(1.0))
    assert any("no pallas_call" in str(v)
               for v in ScratchBudget(1024, require_pallas=True)
               .check(plain))
    assert not ScratchBudget(1024).check(plain)   # kernel optional


# -- CLI + gate flip on seeded mutations -----------------------------------


def test_cli_list_and_unmatched_only():
    from repro.check.cli import main
    assert main(["--list"]) == 0
    assert main(["--only", "no-such-contract-xyz"]) == 1


def _run_mutation(script):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    assert "MUTATION_FLIPPED" in r.stdout
    return r.stdout


MUTATE_PSUM = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
_orig_psum = jax.lax.psum
def evil_psum(x, axis_name, **kw):
    # the seeded regression: replicate-then-reduce instead of psum
    return jax.lax.all_gather(x, axis_name, **kw).sum(axis=0)
jax.lax.psum = evil_psum
from repro.check.cli import main
rc = main(["--only", "dist/grid-counts"])
assert rc == 1, rc
print("MUTATION_FLIPPED")
"""

MUTATE_HOST_PULL = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax
import repro.serve.registry as registry
_orig = registry.evaluate_predicate
def evil(xb, nn, op, tbin):
    np.asarray(xb)              # host materialization inside the hot walk
    return _orig(xb, nn, op, tbin)
registry.evaluate_predicate = evil
from repro.check.cli import main
rc = main(["--only", "serve/routed-walk"])
assert rc == 1, rc
print("MUTATION_FLIPPED")
"""


def test_gate_flips_on_psum_to_all_gather_mutation():
    """The acceptance demo: rerouting the sharded grid-count psum through
    all_gather makes `python -m repro.check` exit nonzero — the banned
    collective is caught statically, nothing runs."""
    out = _run_mutation(MUTATE_PSUM)
    assert "FAIL" in out


def test_gate_flips_on_host_pull_in_serve_walk():
    """Forcing a traced value to host (np.asarray / float()) never reaches
    the jaxpr — it raises at trace time, which the runner reports as a
    FAIL (trace error) and exits nonzero."""
    out = _run_mutation(MUTATE_HOST_PULL)
    assert "trace error" in out.lower()

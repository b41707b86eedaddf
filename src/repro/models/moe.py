"""Mixture-of-Experts with capacity-bounded gather dispatch (TPU-native).

Routing: top-k per token; each expert then takes its top-``capacity`` tokens
by router weight (GShard-style token dropping, dropped tokens fall through
on the residual path).  Dispatch is gather/scatter, NOT an [N, E, C] one-hot
einsum — at E=128 the one-hot dispatch tensor would be terabytes.

Sharding: expert weights live on the 'model' axis (expert parallelism); the
[E, C, D] dispatch buffer is constrained to the same axis so XLA inserts the
token all-to-all between the data-sharded token stream and the
expert-sharded FFN (visible as all-to-all / collective-permute in the
dry-run HLO — this is the MoE term of the roofline).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import numpy as np

from repro.models.layers import ffn_block, init_ffn, truncated_normal
from repro.models import sharding as SH
from repro.models.sharding import constrain_act

P = jax.sharding.PartitionSpec


def _route_and_gather(xf, router, e, k, cap):
    """Shared routing: top-k per token -> per-expert top-cap tokens.
    Returns (gw [E,cap] combine weights, gi [E,cap] token ids)."""
    n = xf.shape[0]
    logits = jnp.einsum("nd,de->ne", xf, router.astype(xf.dtype))
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_w, top_i = jax.lax.top_k(probs, k)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    smat = jnp.zeros((e, n), dtype=jnp.float32)
    smat = smat.at[top_i.T, jnp.arange(n)[None].repeat(k, 0)].set(top_w.T)
    return jax.lax.top_k(smat, cap)


def _expert_ffn(xe, w_gate, w_up, w_down, act):
    gate = jnp.einsum("ecd,edf->ecf", xe, w_gate)
    up = jnp.einsum("ecd,edf->ecf", xe, w_up)
    a = jax.nn.silu(gate) if act == "silu" else jax.nn.gelu(gate)
    return jnp.einsum("ecf,efd->ecd", a * up, w_down)


def _moe_a2a_experts(x, router, w_gate, w_up, w_down, *, cfg, model_axis):
    """shard_map body, all-to-all dispatch (GShard layout).

    Tokens are additionally SLICED over the model axis before routing: each
    model shard routes n/msize tokens, all_to_all ships each expert's
    tokens to its owner shard, the owner runs the FFN, a reverse all_to_all
    returns them, and the combined token slices are all-gathered.  Collective
    per layer ~ (2 * k * capacity_factor / msize + 1) * N * D bytes vs the
    psum variant's 2 * N * D — about 1.7x less at top-2/16-way, and the
    expert compute is load-balanced per (source shard, expert) capacity.
    """
    b, t, d = x.shape
    n = b * t
    e, k = cfg.n_experts, cfg.top_k
    msize = jax.lax.axis_size(model_axis)
    e_loc = e // msize
    my = jax.lax.axis_index(model_axis)
    n_loc = n // msize
    xf = x.reshape(n, d)
    xme = jax.lax.dynamic_slice_in_dim(xf, my * n_loc, n_loc, axis=0)

    cap = int(np.ceil(k * n_loc / e * cfg.moe_capacity_factor))
    cap = min(max(4, cap), n_loc)
    gw, gi = _route_and_gather(xme, router, e, k, cap)      # [E, cap]
    xe = jnp.take(xme, gi.reshape(-1), axis=0).reshape(e, cap, d)

    # dispatch: shard r sends expert block s to shard s
    xe = xe.reshape(msize, e_loc, cap, d)
    xe = jax.lax.all_to_all(xe, model_axis, split_axis=0, concat_axis=0)
    xe = xe.reshape(msize * e_loc, cap, d).reshape(e_loc, msize * cap, d,
                                                   order="F")         if False else xe.reshape(msize, e_loc, cap, d)
    # [source, E_loc, cap, D] -> [E_loc, source*cap, D]
    xe = xe.transpose(1, 0, 2, 3).reshape(e_loc, msize * cap, d)
    ye = _expert_ffn(xe, w_gate, w_up, w_down, cfg.act)
    ye = ye.reshape(e_loc, msize, cap, d).transpose(1, 0, 2, 3)
    ye = jax.lax.all_to_all(ye, model_axis, split_axis=0, concat_axis=0)
    ye = ye.reshape(e, cap, d)                              # my tokens back

    ye = ye * ((gw > 0) * gw)[..., None].astype(ye.dtype)
    out = jnp.zeros((n_loc, d), dtype=ye.dtype)
    out = out.at[gi.reshape(-1)].add(ye.reshape(-1, d))
    out = jax.lax.all_gather(out, model_axis, axis=0, tiled=True)  # [N, D]
    return out.reshape(b, t, d)


def _moe_local_experts(x, router, w_gate, w_up, w_down, *, cfg, model_axis):
    """shard_map body: tokens are data-sharded (model-replicated), expert
    weights are model-sharded.  Every model shard computes the (identical)
    routing, gathers tokens for ITS experts locally, runs the FFN, and the
    per-shard partial combines are one psum over the model axis — the same
    collective a Megatron row-parallel FFN pays.  No token tensor is ever
    replicated or all-gathered (the GSPMD gather path did exactly that,
    which is what made the MoE cells 100x memory-oversubscribed)."""
    b, t, d = x.shape
    n = b * t
    e, k = cfg.n_experts, cfg.top_k
    msize = jax.lax.axis_size(model_axis)
    e_loc = e // msize
    xf = x.reshape(n, d)
    cap = int(np.ceil(k * n / e * cfg.moe_capacity_factor))
    cap = min(max(8, cap), n)
    gw, gi = _route_and_gather(xf, router, e, k, cap)
    my = jax.lax.axis_index(model_axis)
    gw_l = jax.lax.dynamic_slice_in_dim(gw, my * e_loc, e_loc, axis=0)
    gi_l = jax.lax.dynamic_slice_in_dim(gi, my * e_loc, e_loc, axis=0)
    xe = jnp.take(xf, gi_l.reshape(-1), axis=0).reshape(e_loc, cap, d)
    ye = _expert_ffn(xe, w_gate, w_up, w_down, cfg.act)
    ye = ye * (gw_l > 0)[..., None].astype(ye.dtype)
    ye = ye * gw_l[..., None].astype(ye.dtype)
    out = jnp.zeros((n, d), dtype=ye.dtype)
    out = out.at[gi_l.reshape(-1)].add(ye.reshape(-1, d))
    out = jax.lax.psum(out, model_axis)
    return out.reshape(b, t, d)


def moe_block(p, x, cfg):
    """x: [B, T, D] -> [B, T, D].

    With a mesh installed (SH.MESH) and E divisible by the model axis, runs
    the shard_map local-expert path; otherwise the plain jnp path (CPU smoke
    tests, single device)."""
    axes = SH.ACT_AXES
    if (SH.MESH is not None and axes is not None
            and cfg.n_experts % axes.msize() == 0
            and x.shape[0] % axes.dsize() == 0):
        n_loc = (x.shape[0] // axes.dsize()) * x.shape[1]
        impl = (_moe_a2a_experts
                if n_loc % axes.msize() == 0 and n_loc // axes.msize() >= 64
                else _moe_local_experts)
        body = lambda xx, r, wg, wu, wd: impl(
            xx, r, wg, wu, wd, cfg=cfg, model_axis=axes.model)
        dspec = P(axes.data, None, None)
        espec = P(axes.model, None, None)
        out = jax.shard_map(
            body, mesh=SH.MESH,
            in_specs=(dspec, P(), espec, espec, espec),
            out_specs=dspec, check_vma=False,
        )(x, p["router"], p["w_gate"].astype(x.dtype),
          p["w_up"].astype(x.dtype), p["w_down"].astype(x.dtype))
        if cfg.moe_dense_residual:
            out = out + ffn_block({k_: p[f"res_{k_}"] for k_ in
                                   ("w_gate", "w_up", "w_down")}, x, cfg.act)
        return out
    return _moe_block_jnp(p, x, cfg)


def _moe_block_jnp(p, x, cfg):
    """Reference path (no mesh): capacity-bounded gather dispatch."""
    b, t, d = x.shape
    n = b * t
    e, k = cfg.n_experts, cfg.top_k
    xf = x.reshape(n, d)

    cap = int(np.ceil(k * n / e * cfg.moe_capacity_factor))
    cap = min(max(8, cap), n)
    gw, gi = _route_and_gather(xf, p["router"], e, k, cap)  # [E, cap]

    xe = jnp.take(xf, gi.reshape(-1), axis=0).reshape(e, cap, d)
    xe = constrain_act(xe, "ecd")                           # token all-to-all

    ye = _expert_ffn(xe, p["w_gate"].astype(x.dtype),
                     p["w_up"].astype(x.dtype),
                     p["w_down"].astype(x.dtype), cfg.act)
    ye = ye * (gw > 0)[..., None].astype(ye.dtype)
    ye = ye * gw[..., None].astype(ye.dtype)
    ye = constrain_act(ye, "ecd")

    out = jnp.zeros((n, d), dtype=ye.dtype)
    out = out.at[gi.reshape(-1)].add(ye.reshape(-1, d))     # combine (return a2a)
    out = constrain_act(out.reshape(b, t, d), "btd")

    if cfg.moe_dense_residual:
        out = out + ffn_block({k_: p[f"res_{k_}"] for k_ in
                               ("w_gate", "w_up", "w_down")}, x, cfg.act)
    return out


def init_moe(key, cfg, dtype):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 5)
    p = {
        "router": truncated_normal(ks[0], (d, e), jnp.float32, 1.0 / np.sqrt(d)),
        "w_gate": truncated_normal(ks[1], (e, d, f), dtype, 1.0 / np.sqrt(d)),
        "w_up": truncated_normal(ks[2], (e, d, f), dtype, 1.0 / np.sqrt(d)),
        "w_down": truncated_normal(ks[3], (e, f, d), dtype, 1.0 / np.sqrt(f)),
    }
    if cfg.moe_dense_residual:
        fr = cfg.moe_dense_ff or f
        res = init_ffn(ks[4], d, fr, dtype)
        p.update({f"res_{k}": v for k, v in res.items()})
    return p

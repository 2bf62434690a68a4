"""Mixture-of-Experts with two interchangeable implementations:

``moe_dense``  — every expert computed for every token, gated combine.  Used
                 for tiny CPU smoke tests (E≤8) and as the differentiable
                 reference oracle in property tests.
``moe_ep``     — expert parallelism via `shard_map`: experts sharded over the
                 'model' axis (weights additionally storage-sharded over
                 'data' and gathered at use), tokens dispatched with explicit
                 `lax.all_to_all`, capacity-bounded (token dropping) with
                 sorted-rank slotting.  This is the production path; the a2a
                 bytes are what the roofline's collective term sees.

Both paths share the router (softmax → top-k → renormalise).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.models.layers import silu

from jax.sharding import PartitionSpec as P


def _route(x2, router_w, top_k):
    """x2: (T, D) -> (topv, topi) each (T, k), renormalised."""
    logits = jnp.einsum("td,de->te", x2, router_w,
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, top_k)
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    return topv, topi


def _expert_ffn(tokens, wg, wu, wd):
    """tokens: (E, C, D); weights (E, D, F) / (E, F, D)."""
    g = jnp.einsum("ecd,edf->ecf", tokens, wg)
    u = jnp.einsum("ecd,edf->ecf", tokens, wu)
    return jnp.einsum("ecf,efd->ecd", silu(g) * u, wd)


def moe_dense(x, p, cfg):
    """x: (B,S,D).  All-experts reference path."""
    B, S, D = x.shape
    x2 = x.reshape(B * S, D)
    topv, topi = _route(x2, p["router"], cfg.top_k)
    g = jnp.einsum("td,edf->tef", x2, p["w_gate"])
    u = jnp.einsum("td,edf->tef", x2, p["w_up"])
    y_all = jnp.einsum("tef,efd->ted", silu(g) * u, p["w_down"])
    oh = jax.nn.one_hot(topi, cfg.num_experts, dtype=jnp.float32)  # (T,k,E)
    w = jnp.einsum("tk,tke->te", topv, oh)
    comb = jnp.einsum("ted,te->td", y_all.astype(jnp.float32), w)
    return comb.astype(x.dtype).reshape(B, S, D)


def _ep_local(x_local, router_w, wg, wu, wd, *, cfg, ep_axis, ep_size,
              gather_axis, gather_mode, fsdp_size):
    """Per-shard body of the EP shard_map.  x_local: (B_l, S_l, D).

    gather_mode:
      'weights' — train/prefill: expert weights storage-sharded on d_model
                  over the fsdp axis, all-gathered at use (amortised over
                  thousands of tokens per chip).
      'tokens'  — decode: weights stay RESIDENT with d_ff sharded over the
                  fsdp axis; the (tiny) token batch is all-gathered across
                  that axis and partial expert outputs are psum'd instead.
                  Removes the per-token weight gather that made MoE decode
                  collective-bound (EXPERIMENTS.md §Perf iteration D).
      'none'    — weights small enough to store unsharded on d.
    """
    m = ep_size
    E, k = cfg.num_experts, cfg.top_k
    E_l = E // m
    B_l, S_l, D = x_local.shape
    T_own = B_l * S_l

    if gather_mode == "weights":
        wg = jax.lax.all_gather(wg, gather_axis, axis=1, tiled=True)
        wu = jax.lax.all_gather(wu, gather_axis, axis=1, tiled=True)
        wd = jax.lax.all_gather(wd, gather_axis, axis=2, tiled=True)

    x2 = x_local.reshape(T_own, D)
    if gather_mode == "tokens":
        x2 = jax.lax.all_gather(x2, gather_axis, axis=0, tiled=True)
    T = x2.shape[0]
    C = max(1, math.ceil(T * k / E * cfg.capacity_factor))
    topv, topi = _route(x2, router_w, k)

    flat_e = topi.reshape(-1)                            # (T*k,)
    tok = jnp.arange(T * k) // k
    counts = jnp.zeros((E,), jnp.int32).at[flat_e].add(1)
    offs = jnp.cumsum(counts) - counts
    order = jnp.argsort(flat_e, stable=True)
    rank_sorted = jnp.arange(T * k, dtype=jnp.int32) - offs[flat_e[order]]
    rank = jnp.zeros((T * k,), jnp.int32).at[order].set(rank_sorted)
    keep = rank < C
    slot = jnp.clip(flat_e * C + rank, 0, E * C - 1)

    send = jnp.zeros((E * C, D), x2.dtype)
    send = send.at[slot].add(jnp.where(keep[:, None], x2[tok], 0))
    send = send.reshape(m, E_l * C, D)                   # owner-major
    recv = jax.lax.all_to_all(send, ep_axis, split_axis=0, concat_axis=0)
    # recv[j] = tokens sender j routed to my experts
    toks = recv.reshape(m, E_l, C, D).transpose(1, 0, 2, 3).reshape(E_l, m * C, D)
    y = _expert_ffn(toks, wg, wu, wd)                    # (E_l, m*C, D)
    if gather_mode == "tokens":
        # partial over the resident d_ff shard -> reduce across fsdp axis
        y = jax.lax.psum(y, gather_axis)
    back = y.reshape(E_l, m, C, D).transpose(1, 0, 2, 3).reshape(m, E_l * C, D)
    ret = jax.lax.all_to_all(back, ep_axis, split_axis=0, concat_axis=0)
    ret = ret.reshape(E * C, D)

    gathered = ret[slot] * (topv.reshape(-1)[:, None] *
                            keep[:, None]).astype(ret.dtype)
    out = gathered.reshape(T, k, D).sum(axis=1)
    if gather_mode == "tokens":
        # keep only this chip's original token segment of the gathered row
        idx = jax.lax.axis_index(gather_axis)
        out = jax.lax.dynamic_slice_in_dim(out, idx * T_own, T_own, axis=0)
    return out.reshape(B_l, S_l, D).astype(x_local.dtype)


def moe_ep(x, p, cfg, ctx):
    """Expert-parallel MoE.  x: (B,S,D) sharded per ctx (batch/seq)."""
    mesh = ctx.mesh
    xspec = _spec_for(ctx, x.shape)
    w_shape = p["w_gate"].shape                          # (E, D, F)
    fsdp = mesh.shape[ctx.fsdp_axis]
    if ctx.phase == "decode" and ctx.decode_tp and w_shape[2] % fsdp == 0:
        gather_mode = "tokens"
        wspec_in = P(ctx.ep_axis, None, ctx.fsdp_axis)
        wdspec_in = P(ctx.ep_axis, ctx.fsdp_axis, None)
    elif w_shape[1] % fsdp == 0:
        gather_mode = "weights"
        wspec_in = P(ctx.ep_axis, ctx.fsdp_axis, None)
        wdspec_in = P(ctx.ep_axis, None, ctx.fsdp_axis)
    else:
        gather_mode = "none"
        wspec_in = P(ctx.ep_axis, None, None)
        wdspec_in = P(ctx.ep_axis, None, None)

    fn = functools.partial(_ep_local, cfg=cfg, ep_axis=ctx.ep_axis,
                           ep_size=mesh.shape[ctx.ep_axis],
                           gather_axis=ctx.fsdp_axis,
                           gather_mode=gather_mode, fsdp_size=fsdp)
    sm = jax.shard_map(fn, mesh=mesh,
                       in_specs=(xspec, P(None, None), wspec_in, wspec_in,
                                 wdspec_in),
                       out_specs=xspec, check_vma=False)
    return sm(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])


def _spec_for(ctx, shape):
    """PartitionSpec for (B,S,D) hidden given ctx batch/seq axes (with the
    same divisibility fallback as AxisCtx.cs)."""
    from repro.models.partition import best_axes
    return P(best_axes(ctx.mesh, shape[0], ctx.batch),
             best_axes(ctx.mesh, shape[1], ctx.seq), None)


def moe_apply(x, p, cfg, ctx):
    """Full MoE block: routed experts (+ shared experts)."""
    if ctx.ep and ctx.mesh is not None and \
            cfg.num_experts % ctx.mesh.shape[ctx.ep_axis] == 0:
        y = moe_ep(x, p, cfg, ctx)
    else:
        y = moe_dense(x, p, cfg)
    if cfg.num_shared_experts:
        h = silu(x @ p["shared_gate"]) * (x @ p["shared_up"])
        y = y + h @ p["shared_down"]
    return y

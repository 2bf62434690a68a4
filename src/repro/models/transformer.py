"""Layer assembly: mixer+FFN blocks, prefix layers, and the scanned unit
stack.  Parameters of the scanned units carry a leading ``num_units`` dim so
the HLO contains a single unit regardless of depth."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.attention import (gqa_apply, gqa_decode_paged,
                                    gqa_prefill_paged, gqa_verify_paged,
                                    mla_apply, mla_decode_paged,
                                    mla_prefill_paged)
from repro.models.layers import mlp, rms_norm
from repro.models.mamba import mamba_apply
from repro.models.moe import moe_apply, moe_held, shared_experts
from repro.models.xlstm import mlstm_apply, slstm_apply

MIXERS = {
    "attn": gqa_apply,
    "mla": mla_apply,
    "mamba": mamba_apply,
    "mlstm": mlstm_apply,
    "slstm": slstm_apply,
}


def layer_apply(x, lp, mixer, ffn, cfg, ctx, mode, cache=None, index=None):
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    mix_out, new_cache = MIXERS[mixer](h, lp, cfg, ctx, mode,
                                       cache=cache, index=index)
    x = ctx.hidden(x + mix_out)
    if ffn != "none":
        h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
        y = mlp(h2, lp, ctx) if ffn == "mlp" else moe_apply(h2, lp, cfg, ctx)
        x = ctx.hidden(x + y)
    return x, new_cache


def unit_apply(x, unit_params, cfg, ctx, mode, unit_caches=None, index=None):
    new_caches = {}
    for i, (mixer, ffn) in enumerate(cfg.unit_pattern):
        key = f"l{i}"
        cache_i = unit_caches[key] if unit_caches is not None else None
        x, nc = layer_apply(x, unit_params[key], mixer, ffn, cfg, ctx, mode,
                            cache=cache_i, index=index)
        new_caches[key] = nc
    return x, new_caches


def stack_apply(x, params, cfg, ctx, mode, caches=None, index=None):
    """Returns (x, new_caches).  ``caches`` required for decode; produced by
    prefill; None (and returned None) for train."""
    new_prefix = []
    for i, (mixer, ffn) in enumerate(cfg.prefix_pattern):
        cache_i = caches["prefix"][i] if caches is not None else None
        x, nc = layer_apply(x, params["prefix"][f"l{i}"], mixer, ffn, cfg,
                            ctx, mode, cache=cache_i, index=index)
        new_prefix.append(nc)

    def body(carry, xs):
        h = carry
        if mode == "decode":
            up, ucache = xs
        else:
            up, ucache = xs, None
        h, ncache = unit_apply(h, up, cfg, ctx, mode, ucache, index)
        return h, ncache

    if cfg.remat and mode == "train":
        body = jax.checkpoint(body)

    xs = (params["units"], caches["units"]) if mode == "decode" \
        else params["units"]
    x, unit_caches = jax.lax.scan(body, x, xs)

    if mode == "train":
        return x, None
    return x, {"prefix": tuple(new_prefix), "units": unit_caches}


# ---------------------------------------------------------------------------
# Paged-KV serving path (DESIGN.md §3): same layer stack, but attention
# reads/writes a device-resident page pool addressed by block tables.
# ---------------------------------------------------------------------------
def layer_apply_paged(x, lp, mixer, ffn, cfg, ctx, mode, pages, tables, pos,
                      n=None, interpret=False, fused=False):
    """One layer over its page pool.  Returns (x, new pages, routed pairs
    per held expert (E,) int32 for an MoE layer, else None).  MoE layers
    take the held-expert path (``moe_held``) and route only real tokens:
    prefill rows before ``n``, decode lanes whose table does not start on
    the scrap page (the pool's last)."""
    if mixer not in ("attn", "mla"):
        raise ValueError(
            f"paged serving supports 'attn' and 'mla' mixers, got {mixer!r}")
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    if mixer == "mla":
        if mode == "prefill":
            mix_out, new_pages = mla_prefill_paged(h, lp, cfg, pages, tables,
                                                   pos, n)
        elif mode == "decode":
            mix_out, new_pages = mla_decode_paged(h, lp, cfg, pages, tables,
                                                  pos, interpret=interpret)
        else:
            raise ValueError(f"latent attention has no paged {mode!r} path")
    elif mode == "prefill":
        mix_out, new_pages = gqa_prefill_paged(h, lp, cfg, pages, tables,
                                               pos, n, ctx=ctx)
    elif mode == "verify":
        # speculative verification: ``pos`` is pos0 (B,), ``n`` the per-lane
        # window widths (B,) — see stack_apply_paged
        mix_out, new_pages = gqa_verify_paged(h, lp, cfg, pages, tables,
                                              pos, n, interpret=interpret,
                                              ctx=ctx)
    else:
        mix_out, new_pages = gqa_decode_paged(h, lp, cfg, pages, tables,
                                              pos, interpret=interpret,
                                              ctx=ctx, fused=fused)
    x = ctx.hidden(x + mix_out)
    counts = None
    if ffn == "moe":
        h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
        scrap = jax.tree.leaves(pages)[0].shape[0] - 1
        if mode == "prefill":
            valid = (jnp.arange(x.shape[1]) < n)[None, :]
        else:
            valid = (tables[:, 0] != scrap)[:, None]
        y, counts = moe_held(h2, lp, cfg, valid, interpret=interpret)
        if cfg.num_shared_experts:
            y = y + shared_experts(h2, lp, cfg)
        x = ctx.hidden(x + y)
    elif ffn != "none":
        h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
        x = ctx.hidden(x + mlp(h2, lp, ctx))
    return x, new_pages, counts


def stack_apply_paged(x, params, cfg, ctx, mode, pages, tables, pos, n=None,
                      interpret=False, fused=False):
    """Paged analogue of ``stack_apply``.  mode "prefill": ``tables`` is one
    sequence's (n_max,) block table, ``pos`` the chunk's start offset, ``n``
    the real chunk length (rows past it are padding).  mode "decode":
    ``tables`` is (B, n_max), ``pos`` the per-sequence write positions (B,).
    mode "verify" (speculative decoding, DESIGN.md §11): x is (B, W, D)
    window hidden states, ``pos`` the per-lane first-row positions (B,),
    ``n`` the per-lane live widths (B,).  Returns (x, new pages pytree,
    routed pairs (num_units, E) of the scanned units' MoE layers, summed
    over a unit's MoE layers, or None)."""
    new_prefix = []
    for i, (mixer, ffn) in enumerate(cfg.prefix_pattern):
        x, np_, _ = layer_apply_paged(x, params["prefix"][f"l{i}"], mixer,
                                      ffn, cfg, ctx, mode,
                                      pages["prefix"][i], tables, pos, n,
                                      interpret, fused)
        new_prefix.append(np_)

    def body(carry, xs):
        up, upages = xs
        h = carry
        new_u, cnt = {}, None
        for i, (mixer, ffn) in enumerate(cfg.unit_pattern):
            key = f"l{i}"
            h, nc, c = layer_apply_paged(h, up[key], mixer, ffn, cfg, ctx,
                                         mode, upages[key], tables, pos, n,
                                         interpret, fused)
            new_u[key] = nc
            if c is not None:
                cnt = c if cnt is None else cnt + c
        return h, (new_u, cnt)

    x, (unit_pages, counts) = jax.lax.scan(
        body, x, (params["units"], pages["units"]))
    return x, {"prefix": tuple(new_prefix), "units": unit_pages}, counts

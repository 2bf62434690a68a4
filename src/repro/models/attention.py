"""Attention: GQA and MLA (DeepSeek multi-head latent attention), with
memory-bounded online-softmax and two causal schedules.

Schedules (cf. DESIGN.md §4 and EXPERIMENTS.md §Perf):

``rect``     — every query shard scans the full (masked) key context with a
               `lax.scan` of online-softmax chunks.  Universally shardable
               (q sequence over 'model'); computes the full S×S rectangle, so
               HLO FLOPs carry ~2× the causal triangle.  This is the baseline.
``triangle`` — python-unrolled query blocks with *static* causal key slices
               `k[:, : (i+1)·blk]`: exact triangle FLOPs, still statically
               shaped, each block's rows resharded over 'model'.  This is the
               beyond-paper optimized schedule (hillclimbed in §Perf).

Decode is flash-decoding style: one query row against the cache; the cache
sequence dim is sharded over 'model' and XLA inserts the partial-softmax
all-reduces (max & sum).  ``decode_tp`` shards head_dim instead (weights stay
resident, scores are partially summed then all-reduced).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import apply_rope, rope_tables

NEG_INF = -1e30
# keys a block of the latent prefill's online softmax walks at a time
PREFILL_KEY_ROWS = 512


# ---------------------------------------------------------------------------
# Online-softmax core
# ---------------------------------------------------------------------------
def online_attention(q, k, v, q_pos, k_pos0, *, scale, kv_chunk, causal=True):
    """q: (B,Sq,KV,G,Dh), k/v: (B,Sk,KV,Dk/Dv); q_pos: (Sq,) absolute
    positions; k positions are k_pos0 + arange(Sk).  Returns (B,Sq,KV,G,Dv).
    """
    B, Sq, KV, G, _ = q.shape
    Sk = k.shape[1]
    Dv = v.shape[-1]
    kv_chunk = min(kv_chunk, Sk)
    if Sk % kv_chunk:
        kv_chunk = Sk  # fall back to a single chunk for odd sizes (tests)
    nc = Sk // kv_chunk

    qf = q.astype(jnp.float32)
    kc = k.reshape(B, nc, kv_chunk, KV, -1)
    vc = v.reshape(B, nc, kv_chunk, KV, Dv)
    kc = jnp.moveaxis(kc, 1, 0)
    vc = jnp.moveaxis(vc, 1, 0)
    cpos = k_pos0 + jnp.arange(nc) * kv_chunk

    def body(carry, xs):
        m, l, acc = carry
        k_i, v_i, p0 = xs
        s = jnp.einsum("bqkgd,bckd->bqkgc", qf, k_i.astype(jnp.float32)) * scale
        if causal:
            mask = q_pos[:, None] >= (p0 + jnp.arange(kv_chunk))[None, :]
            s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bqkgc,bckd->bqkgd", p, v_i.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, Sq, KV, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Sq, KV, G), jnp.float32)
    a0 = jnp.zeros((B, Sq, KV, G, Dv), jnp.float32)
    # flash-style: recompute chunk scores in the backward pass instead of
    # saving (nc, B, Sq, KV, G, chunk) f32 score tensors
    body = jax.checkpoint(body,
                          policy=jax.checkpoint_policies.nothing_saveable)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kc, vc, cpos))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out


def causal_attention(q, k, v, ctx, *, scale):
    """Dispatch on schedule.  q: (B,S,H,Dh) (full heads); k/v: (B,S,KV,·)."""
    B, S, H, Dh = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, S, KV, G, Dh)
    q_pos = jnp.arange(S)

    if ctx.attn_schedule == "triangle" and S % 16 == 0 and S >= 16:
        nblk = min(16, S // 16)
        blk = S // nblk
        outs = []
        for i in range(nblk):
            qi = qg[:, i * blk:(i + 1) * blk]
            qi = ctx.cs(qi, ctx.batch, ctx.seq, None, None, None)
            ctx_len = (i + 1) * blk
            ki, vi = k[:, :ctx_len], v[:, :ctx_len]
            oi = online_attention(qi, ki, vi, q_pos[i * blk:(i + 1) * blk], 0,
                                  scale=scale, kv_chunk=ctx.attn_chunk)
            outs.append(oi)
        out = jnp.concatenate(outs, axis=1)
    else:
        out = online_attention(qg, k, v, q_pos, 0,
                               scale=scale, kv_chunk=ctx.attn_chunk)
    return out.reshape(B, S, H, Dh if v.shape[-1] == Dh else v.shape[-1])


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------
def gqa_apply(x, p, cfg, ctx, mode, cache=None, index=None):
    """x: (B,S,D) normed.  Returns (out, new_cache|None)."""
    B, S, D = x.shape
    H, KV, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])

    if mode == "decode":
        pos = jnp.full((1,), index)
    else:
        pos = jnp.arange(S)
    if cfg.positional == "rope":
        cos, sin = rope_tables(pos, Dh, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)

    scale = Dh ** -0.5
    if mode in ("train", "prefill"):
        q = ctx.cs(q, ctx.batch, ctx.seq, None, None)
        k = ctx.cs(k, ctx.batch, None, None, None)   # gathered context
        v = ctx.cs(v, ctx.batch, None, None, None)
        o = causal_attention(q, k, v, ctx, scale=scale)
        new_cache = {"k": k, "v": v} if mode == "prefill" else None
    else:
        ck, cv = cache["k"], cache["v"]
        ck = jax.lax.dynamic_update_slice_in_dim(ck, k.astype(ck.dtype), index, 1)
        cv = jax.lax.dynamic_update_slice_in_dim(cv, v.astype(cv.dtype), index, 1)
        if ctx.decode_tp:
            dims = (ctx.batch, None, None, ("model",))
        else:
            dims = (ctx.batch, ("model",), None, None)
        ck = ctx.cs(ck, *dims)
        cv = ctx.cs(cv, *dims)
        Smax = ck.shape[1]
        qg = q.reshape(B, 1, KV, H // KV, Dh).astype(jnp.float32)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qg, ck.astype(jnp.float32)) * scale
        mask = jnp.arange(Smax) <= index
        s = jnp.where(mask[None, None, None, None, :], s, NEG_INF)
        w = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgqs,bskd->bqkgd", w, cv.astype(jnp.float32))
        o = o.reshape(B, 1, H, Dh)
        new_cache = {"k": ck, "v": cv}
    out = jnp.einsum("bshk,hkd->bsd", o.astype(x.dtype), p["wo"])
    return out, new_cache


# ---------------------------------------------------------------------------
# GQA over a paged KV cache (real serving path; DESIGN.md §3)
# ---------------------------------------------------------------------------
def gqa_prefill_paged(x, p, cfg, pages, block_table, start, n, ctx=None):
    """Chunked-prefill attention for ONE sequence against paged KV.

    Under serving TP (DESIGN.md §8) this body runs inside a shard_map:
    ``p`` holds the LOCAL head slice (wq/wk/wv sharded on the head dim, wo
    on its head rows), ``pages`` the local KV-head slice of the pool, and
    the wo projection's partial sum is all-reduced via ``ctx.psum_attn``.

    x: (1, C, D) chunk hidden states — rows at or past ``n`` are padding
    (chunks are padded to a few static shapes to bound recompiles); their
    KV is routed to the scrap page and their outputs are discarded by the
    caller.  ``block_table``: (n_max,) pages owned by the sequence; token i
    lives at pages[block_table[i // page], i % page].  ``start``: tokens
    already resident (earlier chunks).  Chunk KV is scattered FIRST, then
    queries attend over the gathered table under a causal position mask, so
    history and intra-chunk causality share one code path.
    Returns (out (1, C, D), new pages)."""
    from repro.kernels.paged_attention import paged_gather, paged_kv_append
    B, C, D = x.shape
    # head counts come from the (possibly head-sharded) weights, NOT cfg:
    # inside the TP shard_map each shard sees H/tp query heads and KV/tp
    # kv heads, with whole GQA groups kept together (G is shard-invariant)
    H, KV, Dh = p["wq"].shape[1], p["wk"].shape[1], p["wq"].shape[2]
    G = H // KV
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    pos = start + jnp.arange(C)
    if cfg.positional == "rope":
        cos, sin = rope_tables(pos, Dh, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    kp, vp = paged_kv_append(pages["k"], pages["v"], k[0], v[0],
                             block_table, start, n=n)
    keys = paged_gather(kp, block_table)                # (L, KV, Dh)
    vals = paged_gather(vp, block_table)
    L = keys.shape[0]
    qg = q.reshape(B, C, KV, G, Dh).astype(jnp.float32)
    s = jnp.einsum("bckgd,lkd->bckgl", qg,
                   keys.astype(jnp.float32)) * (Dh ** -0.5)
    live = jnp.arange(L)[None, :] <= pos[:, None]       # (C, L) causal
    s = jnp.where(live[None, :, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bckgl,lkd->bckgd", w, vals.astype(jnp.float32))
    o = o.reshape(B, C, H, Dh)
    out = jnp.einsum("bshk,hkd->bsd", o.astype(x.dtype), p["wo"])
    if ctx is not None:
        out = ctx.psum_attn(out)
    return out, {"k": kp, "v": vp}


def gqa_decode_paged(x, p, cfg, pages, block_tables, positions, *,
                     interpret=False, ctx=None, fused=False):
    """Batched one-token decode against paged KV via the Pallas kernel.

    x: (B, 1, D); block_tables: (B, n_max); positions: (B,) — the slot the
    new token's KV occupies (context length BEFORE this token).  Each
    sequence decodes at its own position; rope is applied per-sequence.
    Under serving TP the kernel runs per-shard on the local KV-head slice
    of the pool (per-head online softmax is shard-local — no cross-shard
    reduction until wo, whose partial sums ``ctx.psum_attn`` all-reduces).
    ``fused=True`` takes the single-dispatch append+attend kernel
    (``fused_decode_attention``); the default two-dispatch path is kept as
    the reference the fused kernel is parity-tested against.
    Returns (out (B, 1, D), new pages)."""
    from repro.kernels.paged_attention import (fused_decode_attention,
                                               paged_attention,
                                               paged_kv_append_batch)
    B, _, D = x.shape
    H, Dh = p["wq"].shape[1], p["wq"].shape[2]
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.positional == "rope":
        cos, sin = rope_tables(positions[:, None], Dh, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    if fused:
        o, kp, vp = fused_decode_attention(
            q[:, 0], k[:, 0], v[:, 0], pages["k"], pages["v"],
            block_tables, positions, scale=Dh ** -0.5, interpret=interpret)
    else:
        kp, vp = paged_kv_append_batch(pages["k"], pages["v"],
                                       k[:, 0], v[:, 0],
                                       block_tables, positions)
        o = paged_attention(q[:, 0], kp, vp, block_tables,
                            (positions + 1).astype(jnp.int32),
                            scale=Dh ** -0.5, interpret=interpret)
    out = jnp.einsum("bhk,hkd->bd", o.astype(x.dtype), p["wo"])[:, None, :]
    if ctx is not None:
        out = ctx.psum_attn(out)
    return out, {"k": kp, "v": vp}


def gqa_verify_paged(x, p, cfg, pages, block_tables, pos0, widths, *,
                     interpret=False, ctx=None):
    """Speculative verification attention: W window rows per lane in one
    dispatch (``fused_verify_attention``; DESIGN.md §11).

    x: (B, W, D) hidden states for the window tokens — row 0 the last
    accepted token, rows 1.. the drafted tokens, rows at or past
    ``widths[b]`` padding.  pos0: (B,) row 0's KV slot.  Rope positions are
    pos0+s per row; the projections are the same einsums as
    ``gqa_decode_paged`` batched over the row dim, so each row's q/k/v is
    bitwise what the sequential decode step would have computed.
    Returns (out (B, W, D), new pages)."""
    from repro.kernels.paged_attention import fused_verify_attention
    B, W, D = x.shape
    H, Dh = p["wq"].shape[1], p["wq"].shape[2]
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.positional == "rope":
        positions = pos0[:, None] + jnp.arange(W)[None, :]     # (B, W)
        cos, sin = rope_tables(positions, Dh, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    o, kp, vp = fused_verify_attention(
        q, k, v, pages["k"], pages["v"], block_tables, pos0, widths,
        scale=Dh ** -0.5, interpret=interpret)
    out = jnp.einsum("bshk,hkd->bsd", o.astype(x.dtype), p["wo"])
    if ctx is not None:
        out = ctx.psum_attn(out)
    return out, {"k": kp, "v": vp}


# ---------------------------------------------------------------------------
# MLA (DeepSeek-style multi-head latent attention)
# ---------------------------------------------------------------------------
def _mla_q(x, p, cfg):
    if cfg.q_lora_rank:
        from repro.models.layers import rms_norm
        cq = rms_norm(x @ p["w_dq"], p["q_ln"], cfg.norm_eps)
        q = jnp.einsum("bsr,rhk->bshk", cq, p["w_uq"])
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, p["w_q"])
    return q  # (B,S,H, nope+rope)


def _mla_rope(cfg, pos):
    """cos/sin tables of the rope part at ``pos`` (YaRN where set)."""
    return rope_tables(pos, cfg.qk_rope_dim, cfg.rope_theta, cfg)


def _mla_latents(x, p, cfg, cos, sin):
    """The per-token rows MLA caches: c = RMSNorm(x W_dkv) (B,S,r) and the
    roped shared key k_rope = RoPE(x W_kr) (B,S,rope)."""
    from repro.models.layers import rms_norm
    ckv = rms_norm(x @ p["w_dkv"], p["kv_ln"], cfg.norm_eps)
    k_rope = apply_rope((x @ p["w_kr"])[:, :, None, :], cos, sin,
                        cfg.rope_interleaved)[:, :, 0, :]
    return ckv, k_rope


def mla_apply(x, p, cfg, ctx, mode, cache=None, index=None):
    B, S, D = x.shape
    H = cfg.num_heads
    nope, rope_d, vd, r = (cfg.qk_nope_dim, cfg.qk_rope_dim,
                           cfg.v_head_dim, cfg.kv_lora_rank)
    scale = cfg.mla_softmax_scale

    q = _mla_q(x, p, cfg)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    pos = jnp.full((1,), index) if mode == "decode" else jnp.arange(S)
    cos, sin = _mla_rope(cfg, pos)
    q_rope = apply_rope(q_rope, cos, sin, cfg.rope_interleaved)
    ckv, k_rope = _mla_latents(x, p, cfg, cos, sin)
    k_rope = k_rope[:, :, None, :]                                # (B,S,1,rope)

    if mode in ("train", "prefill"):
        # Naive path: materialise per-head K/V (compute-friendly at long S).
        k_nope = jnp.einsum("bsr,rhk->bshk", ckv, p["w_uk"])
        v = jnp.einsum("bsr,rhv->bshv", ckv, p["w_uv"])
        k = jnp.concatenate([k_nope, jnp.broadcast_to(
            k_rope, (B, S, H, rope_d)).astype(k_nope.dtype)], axis=-1)
        qq = jnp.concatenate([q_nope, q_rope.astype(q_nope.dtype)], axis=-1)
        qq = ctx.cs(qq, ctx.batch, ctx.seq, None, None)
        k = ctx.cs(k, ctx.batch, None, None, None)
        v = ctx.cs(v, ctx.batch, None, None, None)
        o = causal_attention(qq, k, v, ctx, scale=scale)          # (B,S,H,vd)
        new_cache = ({"ckv": ckv, "kr": k_rope[:, :, 0, :]}
                     if mode == "prefill" else None)
    else:
        # Absorbed decode: attend in the compressed latent space; the cache
        # holds (ckv, k_rope) only — (r + rope_d) per token instead of
        # H·(nope+rope+vd).  TPU-native adaptation of MLA serving.
        cc, ckr = cache["ckv"], cache["kr"]
        cc = jax.lax.dynamic_update_slice_in_dim(
            cc, ckv.astype(cc.dtype), index, 1)
        ckr = jax.lax.dynamic_update_slice_in_dim(
            ckr, k_rope[:, :, 0, :].astype(ckr.dtype), index, 1)
        cc = ctx.cs(cc, ctx.batch, ("model",), None)
        ckr = ctx.cs(ckr, ctx.batch, ("model",), None)
        Smax = cc.shape[1]
        q_abs = jnp.einsum("bthn,rhn->bthr", q_nope.astype(jnp.float32),
                           p["w_uk"].astype(jnp.float32))         # (B,1,H,r)
        s = (jnp.einsum("bthr,bsr->bhts", q_abs, cc.astype(jnp.float32))
             + jnp.einsum("bthp,bsp->bhts", q_rope.astype(jnp.float32),
                          ckr.astype(jnp.float32))) * scale
        mask = jnp.arange(Smax) <= index
        s = jnp.where(mask[None, None, None, :], s, NEG_INF)
        w = jax.nn.softmax(s, axis=-1)
        o_lat = jnp.einsum("bhts,bsr->bthr", w, cc.astype(jnp.float32))
        o = jnp.einsum("bthr,rhv->bthv", o_lat,
                       p["w_uv"].astype(jnp.float32))             # (B,1,H,vd)
        new_cache = {"ckv": cc, "kr": ckr}
    out = jnp.einsum("bshv,hvd->bsd", o.astype(x.dtype), p["wo"])
    return out, new_cache


# ---------------------------------------------------------------------------
# MLA over a paged latent pool (DESIGN.md §3): one pool per layer,
# (pages, page, W) rows [c (r) | k_rope (rope) | 0] with W the row rounded
# up to whole 128-lane tiles
# ---------------------------------------------------------------------------
def mla_row_width(cfg) -> int:
    return -(-(cfg.kv_lora_rank + cfg.qk_rope_dim) // 128) * 128


def _mla_rows(ckv, k_rope, width, dtype):
    """(..., r) and (..., rope) -> (..., width) pool rows."""
    pad = width - ckv.shape[-1] - k_rope.shape[-1]
    zeros = jnp.zeros(ckv.shape[:-1] + (pad,), dtype)
    return jnp.concatenate([ckv.astype(dtype), k_rope.astype(dtype), zeros],
                           -1)


def mla_prefill_paged(x, p, cfg, pages, block_table, start, n):
    """Chunked prefill of ONE sequence into its latent pages, then causal
    attention of the chunk over its context with per-head K/V expanded
    from the latents (k_nope = c W_uk, v = c W_uv, the shared roped key on
    every head).  x: (1, C, D) with rows at or past ``n`` padding (their
    rows go to the scrap page).  The context is walked in blocks of about
    ``PREFILL_KEY_ROWS`` keys with an online softmax, and only the blocks
    up to the chunk's last real row: time and transient memory follow the
    context, not the block table's width.  Returns (out, pages)."""
    from repro.kernels.paged_attention import paged_row_append
    B, C, D = x.shape
    H, nope, r = cfg.num_heads, cfg.qk_nope_dim, cfg.kv_lora_rank
    rope_d = cfg.qk_rope_dim
    lat = pages["lat"]
    page = lat.shape[1]
    pos = start + jnp.arange(C)
    cos, sin = _mla_rope(cfg, pos)
    q = _mla_q(x, p, cfg)[0]                                   # (C, H, qk)
    q_nope = q[..., :nope].astype(jnp.float32)
    q_rope = apply_rope(q[None, ..., nope:], cos, sin,
                        cfg.rope_interleaved)[0].astype(jnp.float32)
    ckv, k_rope = _mla_latents(x, p, cfg, cos, sin)
    rows = _mla_rows(ckv[0], k_rope[0], lat.shape[-1], lat.dtype)
    lat = paged_row_append(lat, rows, block_table, start, n=n)

    kb = max(1, PREFILL_KEY_ROWS // page)                    # pages a key block
    Lk = kb * page
    last = (start + jnp.maximum(n, 1) - 1) // page   # last live table entry

    def block(j, carry):
        m, l, acc = carry
        # a block's entries past the last live one re-read it (masked)
        ids = block_table[jnp.minimum(j * kb + jnp.arange(kb), last)]
        ctx_rows = lat[ids].reshape(Lk, lat.shape[-1])
        c = ctx_rows[:, :r]
        kr = ctx_rows[:, r:r + rope_d].astype(jnp.float32)
        k_nope = jnp.einsum("lr,rhn->lhn", c, p["w_uk"]).astype(jnp.float32)
        v = jnp.einsum("lr,rhv->lhv", c, p["w_uv"]).astype(jnp.float32)
        s = (jnp.einsum("chn,lhn->hcl", q_nope, k_nope)
             + jnp.einsum("chp,lp->hcl", q_rope, kr)) * cfg.mla_softmax_scale
        kpos = j * Lk + jnp.arange(Lk)
        s = jnp.where((kpos[None, :] <= pos[:, None])[None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        w = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        return (m_new, l * corr + w.sum(-1),
                acc * corr[..., None] + jnp.einsum("hcl,lhv->hcv", w, v))

    m0 = jnp.full((H, C), NEG_INF, jnp.float32)
    l0 = jnp.zeros((H, C), jnp.float32)
    a0 = jnp.zeros((H, C, cfg.v_head_dim), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, last // kb + 1, block, (m0, l0, a0))
    o = jnp.transpose(acc / l[..., None], (1, 0, 2))[None]    # (1, C, H, v)
    out = jnp.einsum("bshv,hvd->bsd", o.astype(x.dtype), p["wo"])
    return out, {"lat": lat}


def mla_decode_paged(x, p, cfg, pages, block_tables, positions, *,
                     interpret=False):
    """Batched one-token absorbed MLA decode through the fused
    ``mla_decode_attention`` kernel: per head the query against the latent
    is q_nope W_uk (W_uk absorbed into the query), the latents' softmax
    average goes through W_uv after the kernel.  x: (B, 1, D); positions
    (B,) the slot of the new row.  Returns (out (B, 1, D), pages)."""
    from repro.kernels.paged_attention import mla_decode_attention
    nope, r = cfg.qk_nope_dim, cfg.kv_lora_rank
    lat = pages["lat"]
    W = lat.shape[-1]
    cos, sin = _mla_rope(cfg, positions[:, None])
    q = _mla_q(x, p, cfg)[:, 0]                                # (B, H, qk)
    q_rope = apply_rope(q[:, None, :, nope:], cos, sin,
                        cfg.rope_interleaved)[:, 0].astype(jnp.float32)
    q_abs = jnp.einsum("bhn,rhn->bhr", q[..., :nope].astype(jnp.float32),
                       p["w_uk"].astype(jnp.float32))          # (B, H, r)
    ckv, k_rope = _mla_latents(x, p, cfg, cos, sin)
    qcat = _mla_rows(q_abs, q_rope, W, jnp.float32)
    rows = _mla_rows(ckv, k_rope, W, lat.dtype)                # (B, 1, W)
    o_lat, lat = mla_decode_attention(
        qcat, rows, lat, block_tables, positions,
        scale=cfg.mla_softmax_scale, v_width=r, interpret=interpret)
    o = jnp.einsum("bhr,rhv->bhv", o_lat, p["w_uv"].astype(jnp.float32))
    out = jnp.einsum("bhv,hvd->bd", o.astype(x.dtype), p["wo"])[:, None, :]
    return out, {"lat": lat}

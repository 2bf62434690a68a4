"""Pallas TPU paged decode attention (serving hot spot).

One query token per sequence attends over a paged KV cache.  The per-sequence
block table and context lengths are SCALAR-PREFETCHED (pltpu
PrefetchScalarGridSpec): the kv-page BlockSpec's index_map reads the table to
pull exactly the pages this sequence owns from HBM into VMEM — the Pallas
equivalent of PagedAttention's gather, without materialising a contiguous KV.

Pages are 128 tokens (lane-aligned; the GPU artifact uses 16-token pages —
TPU adaptation recorded in DESIGN.md §3).  Grid: (batch, n_pages_max); VMEM
scratch carries online-softmax state across pages; tokens past the sequence's
context length are masked.  Working set per step: one page (128×KV×D) + q
(H×D) + acc (H×D) f32 ≈ 0.8 MB at KV=8, D=128 — comfortably inside VMEM.

Tensor parallelism (DESIGN.md §8): these kernels are shard-local.  Under
the serving shard_map each device calls them with its KV-head slice of
the page pool and the matching q-head slice (whole GQA groups per shard,
so G = H/KV is shard-invariant); the per-head online softmax needs no
cross-shard communication — the single all-reduce lives AFTER the wo
projection in models/attention.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

try:
    from jax.experimental.pallas import tpu as pltpu
    _HAS_TPU = True
except Exception:  # pragma: no cover
    pltpu = None
    _HAS_TPU = False

NEG_INF = -1e30


def _kernel(tables_ref, ctx_ref, q_ref, k_ref, v_ref, o_ref,
            m_scr, l_scr, acc_scr, *, scale: float, page: int, npages: int,
            G: int):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                   # (H, D)
    k = k_ref[0].astype(jnp.float32)                   # (page, KV, D)
    v = v_ref[0].astype(jnp.float32)
    H, D = q.shape
    KV = k.shape[1]
    qg = q.reshape(KV, G, D)

    s = jax.lax.dot_general(
        qg, k, (((2,), (2,)), ((0,), (1,))),
        preferred_element_type=jnp.float32) * scale     # (KV, G, page)
    pos = j * page + jax.lax.broadcasted_iota(jnp.int32, (KV, G, page), 2)
    live = pos < ctx_ref[b]
    s = jnp.where(live, s, NEG_INF)

    m_prev = m_scr[...]                                 # (KV, G)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=2))
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=2)
    pv = jax.lax.dot_general(
        p, v, (((2,), (0,)), ((0,), (1,))),
        preferred_element_type=jnp.float32)             # (KV, G, D)
    acc_scr[...] = acc_scr[...] * corr[..., None] + pv
    m_scr[...] = m_new

    @pl.when(j == npages - 1)
    def _finish():
        out = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)[..., None]
        o_ref[0] = out.reshape(H, D).astype(o_ref.dtype)


def _fused_kernel(tables_ref, ctx_ref, pos_ref, q_ref, kn_ref, vn_ref,
                  k_ref, v_ref, o_ref, ko_ref, vo_ref,
                  m_scr, l_scr, acc_scr, *, scale: float, page: int,
                  npages: int, G: int):
    """Append-then-attend in one grid pass (fused decode).

    Identical online-softmax body to ``_kernel``, except that when this
    grid cell holds the page the step's new token writes into
    (j == pos[b] // page), the new K/V row is spliced into the VMEM copy
    BEFORE attending, and the updated page is written back through the
    aliased page-pool output.  Cells that do not own the write route
    their (unchanged) page copy to the scrap page — see
    ``fused_decode_attention``."""
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    off = pos_ref[b] % page
    k = k_ref[0]                                       # (page, KV, D)
    v = v_ref[0]
    sel = (jax.lax.broadcasted_iota(jnp.int32, k.shape, 0) == off) \
        & (j == pos_ref[b] // page)
    k = jnp.where(sel, kn_ref[0][None].astype(k.dtype), k)
    v = jnp.where(sel, vn_ref[0][None].astype(v.dtype), v)
    ko_ref[0] = k
    vo_ref[0] = v

    q = q_ref[0].astype(jnp.float32)                   # (H, D)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    H, D = q.shape
    KV = kf.shape[1]
    qg = q.reshape(KV, G, D)

    s = jax.lax.dot_general(
        qg, kf, (((2,), (2,)), ((0,), (1,))),
        preferred_element_type=jnp.float32) * scale     # (KV, G, page)
    pos = j * page + jax.lax.broadcasted_iota(jnp.int32, (KV, G, page), 2)
    live = pos < ctx_ref[b]
    s = jnp.where(live, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=2))
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=2)
    pv = jax.lax.dot_general(
        p, vf, (((2,), (0,)), ((0,), (1,))),
        preferred_element_type=jnp.float32)
    acc_scr[...] = acc_scr[...] * corr[..., None] + pv
    m_scr[...] = m_new

    @pl.when(j == npages - 1)
    def _finish():
        out = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)[..., None]
        o_ref[0] = out.reshape(H, D).astype(o_ref.dtype)


def fused_decode_attention(q, k_new, v_new, k_pages, v_pages, block_tables,
                           positions, *, scale=None, interpret: bool = False):
    """Fused decode step: write each sequence's new KV entry into its page
    and attend over it in the same grid pass (one dispatch instead of the
    ``paged_kv_append_batch`` + ``paged_attention`` pair).

    q: (B, H, D); k_new/v_new: (B, KV, D) this step's entries; positions:
    (B,) the slot each entry occupies (context length BEFORE the token, so
    ctx = positions + 1 is attended).  The page pool is passed through as
    an aliased input/output: the kernel writes every visited page block
    back, but only the cell owning the write position routes to its real
    page — all other cells (and padded/finished lanes, whose tables are
    all-scrap already) land on the scrap page (pool index P-1), which by
    construction never appears in a live block table.  Returns
    (out (B, H, D), k_pages, v_pages)."""
    B, H, D = q.shape
    P, page, KV, _ = k_pages.shape
    n_max = block_tables.shape[1]
    G = H // KV
    scale = scale or D ** -0.5
    ctx_lens = (positions + 1).astype(jnp.int32)

    kernel = functools.partial(_fused_kernel, scale=scale, page=page,
                               npages=n_max, G=G)

    def kv_out_map(b, j, tab, ctx, pos):
        # the write-back page: real page at the write cell, scrap elsewhere
        return (jnp.where(j == pos[b] // page, tab[b, j], P - 1), 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, n_max),
        in_specs=[
            pl.BlockSpec((1, H, D),
                         lambda b, j, tab, ctx, pos: (b, 0, 0)),
            pl.BlockSpec((1, KV, D),
                         lambda b, j, tab, ctx, pos: (b, 0, 0)),
            pl.BlockSpec((1, KV, D),
                         lambda b, j, tab, ctx, pos: (b, 0, 0)),
            pl.BlockSpec((1, page, KV, D),
                         lambda b, j, tab, ctx, pos: (tab[b, j], 0, 0, 0)),
            pl.BlockSpec((1, page, KV, D),
                         lambda b, j, tab, ctx, pos: (tab[b, j], 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, H, D),
                         lambda b, j, tab, ctx, pos: (b, 0, 0)),
            pl.BlockSpec((1, page, KV, D), kv_out_map),
            pl.BlockSpec((1, page, KV, D), kv_out_map),
        ],
        scratch_shapes=[
            pltpu.VMEM((KV, G), jnp.float32),
            pltpu.VMEM((KV, G), jnp.float32),
            pltpu.VMEM((KV, G, D), jnp.float32),
        ],
    )
    # aliases index the flattened pallas_call operands INCLUDING the three
    # scalar-prefetch args: k_pages is operand 6, v_pages operand 7
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, H, D), q.dtype),
                   jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
                   jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype)],
        input_output_aliases={6: 1, 7: 2},
        interpret=interpret, name="fused_decode_attention",
    )(block_tables, ctx_lens, positions.astype(jnp.int32),
      q, k_new, v_new, k_pages, v_pages)


def _verify_kernel(tables_ref, pos0_ref, width_ref, q_ref, kn_ref, vn_ref,
                   k_ref, v_ref, o_ref, ko_ref, vo_ref,
                   m_scr, l_scr, acc_scr, *, scale: float, page: int,
                   npages: int, G: int, W: int):
    """Speculative verification: W query rows per lane in one grid pass.

    Window row s holds the lane's query at position pos0[b]+s (row 0 the
    last accepted token, rows 1.. the drafted tokens); rows at or past
    width[b] are padding.  All live rows' K/V entries are spliced into the
    VMEM page copy first (draft KV — rows beyond the eventually-accepted
    prefix become stale garbage the engine truncates / overwrites; they are
    never attended because of the per-row causal mask), then each row
    attends under its own context length pos0+s+1.

    The per-row online-softmax bodies are UNROLLED python loops so every
    row's dot_general shapes match ``_kernel`` exactly — that makes each
    verified position's attention output bitwise identical to the
    sequential single-token decode it replaces, which is what lets
    spec-on token streams be byte-equal to spec-off (DESIGN.md §11)."""
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    p0 = pos0_ref[b]
    width = width_ref[b]
    k = k_ref[0]                                       # (page, KV, D)
    v = v_ref[0]
    for s in range(W):
        ps = p0 + s
        sel = (jax.lax.broadcasted_iota(jnp.int32, k.shape, 0) == ps % page) \
            & (j == ps // page) & (s < width)
        k = jnp.where(sel, kn_ref[0, s][None].astype(k.dtype), k)
        v = jnp.where(sel, vn_ref[0, s][None].astype(v.dtype), v)
    ko_ref[0] = k
    vo_ref[0] = v

    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    KV = kf.shape[1]
    for s in range(W):
        q = q_ref[0, s].astype(jnp.float32)            # (H, D)
        qg = q.reshape(KV, G, q.shape[-1])
        sc = jax.lax.dot_general(
            qg, kf, (((2,), (2,)), ((0,), (1,))),
            preferred_element_type=jnp.float32) * scale  # (KV, G, page)
        pos = j * page + jax.lax.broadcasted_iota(
            jnp.int32, (KV, G, page), 2)
        live = pos < p0 + s + 1
        sc = jnp.where(live, sc, NEG_INF)

        m_prev = m_scr[s]                               # (KV, G)
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=2))
        p = jnp.exp(sc - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[s] = l_scr[s] * corr + jnp.sum(p, axis=2)
        pv = jax.lax.dot_general(
            p, vf, (((2,), (0,)), ((0,), (1,))),
            preferred_element_type=jnp.float32)
        acc_scr[s] = acc_scr[s] * corr[..., None] + pv
        m_scr[s] = m_new

    @pl.when(j == npages - 1)
    def _finish():
        H, D = o_ref.shape[2], o_ref.shape[3]
        for s in range(W):
            out = acc_scr[s] / jnp.maximum(l_scr[s], 1e-30)[..., None]
            o_ref[0, s] = out.reshape(H, D).astype(o_ref.dtype)


def fused_verify_attention(q, k_new, v_new, k_pages, v_pages, block_tables,
                           pos0, widths, *, scale=None,
                           interpret: bool = False):
    """Batched speculative verification: append + attend W window rows per
    lane in one device call (the multi-token generalization of
    ``fused_decode_attention``; W=1 degenerates to it exactly).

    q: (B, W, H, D); k_new/v_new: (B, W, KV, D) the window rows' entries;
    pos0: (B,) the slot of row 0 (= context length before the window);
    widths: (B,) live rows per lane, 1..W — rows past width are padding
    whose outputs the caller discards and whose KV is never spliced.
    Returns (out (B, W, H, D), k_pages, v_pages).

    Two lowerings, same contract:

    - real TPU: ``_verify_multirow``, a single grid pass scoring all W
      rows per lane against each page block while it is resident in VMEM
      (one pool read for the whole window).
    - interpret mode (CPU CI): W chained ``fused_decode_attention`` calls
      through the aliased page pool.  XLA's CPU fusion re-tiles the
      multi-row kernel's unrolled reductions into a different f32
      accumulation order than the single-row decode kernel (observed:
      1-ulp drift on one KV group once W >= 3), which would break the
      spec-on == spec-off stream byte-equality contract; reusing the
      EXACT single-row program row by row makes each verified position's
      math bitwise identical to the sequential decode it replaces —
      parity by program reuse, not by numerical accident (DESIGN.md §11).
    """
    if interpret:
        return _verify_unrolled(q, k_new, v_new, k_pages, v_pages,
                                block_tables, pos0, widths, scale=scale,
                                interpret=True)
    return _verify_multirow(q, k_new, v_new, k_pages, v_pages, block_tables,
                            pos0, widths, scale=scale)


def _verify_unrolled(q, k_new, v_new, k_pages, v_pages, block_tables,
                     pos0, widths, *, scale=None, interpret: bool = False):
    """Row-chained verification: the exact ``fused_decode_attention``
    program applied W times through the aliased pool.  Rows at or past a
    lane's width run with an all-scrap table (the same retired-lane
    masking ``_scan_decode`` uses), so their KV lands on the scrap page
    and their outputs are garbage the caller discards."""
    B, W, H, D = q.shape
    P = k_pages.shape[0]
    scale = scale or D ** -0.5
    scrap = jnp.full_like(block_tables, P - 1)
    outs = []
    kp, vp = k_pages, v_pages
    for s in range(W):
        tab_s = jnp.where(widths[:, None] > s, block_tables, scrap)
        o_s, kp, vp = fused_decode_attention(
            q[:, s], k_new[:, s], v_new[:, s], kp, vp, tab_s, pos0 + s,
            scale=scale, interpret=interpret)
        outs.append(o_s)
    return jnp.stack(outs, axis=1), kp, vp


def _verify_multirow(q, k_new, v_new, k_pages, v_pages, block_tables,
                     pos0, widths, *, scale=None, interpret: bool = False):
    """One-grid-pass verification kernel (real-TPU lowering of
    ``fused_verify_attention``).  Pages the window writes into
    (pos0//page .. (pos0+width-1)//page) are routed back to the pool;
    every other visited page lands on the scrap page (pool index P-1)."""
    B, W, H, D = q.shape
    P, page, KV, _ = k_pages.shape
    n_max = block_tables.shape[1]
    G = H // KV
    scale = scale or D ** -0.5

    kernel = functools.partial(_verify_kernel, scale=scale, page=page,
                               npages=n_max, G=G, W=W)

    def kv_out_map(b, j, tab, pos0, width):
        first = pos0[b] // page
        last = (pos0[b] + width[b] - 1) // page
        return (jnp.where((j >= first) & (j <= last), tab[b, j], P - 1),
                0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, n_max),
        in_specs=[
            pl.BlockSpec((1, W, H, D),
                         lambda b, j, tab, pos0, width: (b, 0, 0, 0)),
            pl.BlockSpec((1, W, KV, D),
                         lambda b, j, tab, pos0, width: (b, 0, 0, 0)),
            pl.BlockSpec((1, W, KV, D),
                         lambda b, j, tab, pos0, width: (b, 0, 0, 0)),
            pl.BlockSpec((1, page, KV, D),
                         lambda b, j, tab, pos0, width: (tab[b, j], 0, 0, 0)),
            pl.BlockSpec((1, page, KV, D),
                         lambda b, j, tab, pos0, width: (tab[b, j], 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, W, H, D),
                         lambda b, j, tab, pos0, width: (b, 0, 0, 0)),
            pl.BlockSpec((1, page, KV, D), kv_out_map),
            pl.BlockSpec((1, page, KV, D), kv_out_map),
        ],
        scratch_shapes=[
            pltpu.VMEM((W, KV, G), jnp.float32),
            pltpu.VMEM((W, KV, G), jnp.float32),
            pltpu.VMEM((W, KV, G, D), jnp.float32),
        ],
    )
    # aliases index the flattened operands INCLUDING the three
    # scalar-prefetch args: k_pages is operand 6, v_pages operand 7
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, W, H, D), q.dtype),
                   jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
                   jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype)],
        input_output_aliases={6: 1, 7: 2},
        interpret=interpret, name="fused_verify_attention",
    )(block_tables, pos0.astype(jnp.int32), widths.astype(jnp.int32),
      q, k_new, v_new, k_pages, v_pages)


def paged_kv_append(k_pages, v_pages, k_new, v_new, block_table, start,
                    n=None, scrap_page=None):
    """Chunked-prefill append: scatter a chunk of new KV entries into the
    paged cache (DESIGN.md §3).

    k_new/v_new: (C, KV, D) entries for token positions start..start+C-1 of
    ONE sequence whose pages are ``block_table`` ((n_max,) int32, token i
    lives in page block_table[i // page] slot i % page).  ``n`` (traced
    scalar) marks how many of the C rows are real — rows past ``n`` are
    routed to ``scrap_page`` so callers can pad chunks to a few static
    shapes without corrupting live pages.  Returns (k_pages, v_pages).
    """
    C = k_new.shape[0]
    page = k_pages.shape[1]
    idx = start + jnp.arange(C)
    page_ids = block_table[idx // page]
    offs = idx % page
    if n is not None:
        pad = jnp.arange(C) >= n
        fill = k_pages.shape[0] - 1 if scrap_page is None else scrap_page
        page_ids = jnp.where(pad, fill, page_ids)
        offs = jnp.where(pad, 0, offs)
    k_pages = k_pages.at[page_ids, offs].set(k_new.astype(k_pages.dtype))
    v_pages = v_pages.at[page_ids, offs].set(v_new.astype(v_pages.dtype))
    return k_pages, v_pages


def paged_kv_append_batch(k_pages, v_pages, k_new, v_new, block_tables,
                          positions):
    """Decode-step append: one new KV entry per sequence.

    k_new/v_new: (B, KV, D); block_tables: (B, n_max); positions: (B,) the
    slot each sequence's new token occupies.  Distinct sequences own
    disjoint pages, so the scatter never collides.  Returns updated pages.
    """
    B = k_new.shape[0]
    page = k_pages.shape[1]
    page_ids = block_tables[jnp.arange(B), positions // page]
    offs = positions % page
    k_pages = k_pages.at[page_ids, offs].set(k_new.astype(k_pages.dtype))
    v_pages = v_pages.at[page_ids, offs].set(v_new.astype(v_pages.dtype))
    return k_pages, v_pages


def paged_gather(pages, block_table):
    """Gather one sequence's pages into a contiguous (n_max*page, KV, D)
    view — the dense side of the append round-trip (chunked prefill attends
    over it; positions past the context length must be masked by the
    caller)."""
    P, page, KV, D = pages.shape
    return pages[block_table].reshape(block_table.shape[0] * page, KV, D)


def paged_attention(q, k_pages, v_pages, block_tables, ctx_lens, *,
                    scale=None, interpret: bool = False):
    """q: (B,H,D); k/v_pages: (P, page, KV, D); block_tables: (B, n_max)
    int32; ctx_lens: (B,) int32.  Returns (B,H,D)."""
    B, H, D = q.shape
    P, page, KV, _ = k_pages.shape
    n_max = block_tables.shape[1]
    G = H // KV
    scale = scale or D ** -0.5

    kernel = functools.partial(_kernel, scale=scale, page=page,
                               npages=n_max, G=G)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_max),
        in_specs=[
            pl.BlockSpec((1, H, D), lambda b, j, tab, ctx: (b, 0, 0)),
            pl.BlockSpec((1, page, KV, D),
                         lambda b, j, tab, ctx: (tab[b, j], 0, 0, 0)),
            pl.BlockSpec((1, page, KV, D),
                         lambda b, j, tab, ctx: (tab[b, j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, D), lambda b, j, tab, ctx: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KV, G), jnp.float32),
            pltpu.VMEM((KV, G), jnp.float32),
            pltpu.VMEM((KV, G, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        interpret=interpret, name="paged_attention",
    )(block_tables, ctx_lens, q, k_pages, v_pages)

"""Pallas TPU paged decode attention (serving hot spot).

One query token per sequence attends over a paged KV cache: a pool of
``(P, page, KV, D)`` pages, each sequence owning the pages its block table
lists (token i at ``pages[table[i // page], i % page]``; entries past the
context point at the scrap page P-1).  Tables and positions are
SCALAR-PREFETCHED (pltpu PrefetchScalarGridSpec), so the kernels pull
exactly the pages a sequence owns from HBM into VMEM — the Pallas
equivalent of PagedAttention's gather, without materialising a contiguous
KV.  The backend and the benchmark use 16-token pages (DESIGN.md §3).

- ``fused_decode_attention`` (the serving path): append the step's K/V row
  and attend, walking only each lane's live pages in blocks of 256 keys
  (``_pages_per_block``), DMA'd double-buffered from the HBM pool, one
  online-softmax update per block; the append writes one row.  (Heads
  narrower than 128 lanes take one page per grid step instead.)
- ``paged_attention`` (the ``fused=False`` reference) and
  ``_verify_multirow`` (speculative verification on the chip): grid
  ``(batch, n_pages_max)``, one page per step, VMEM scratch carrying the
  online-softmax state across pages, tokens past the context masked.

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


def _softmax_init(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def _block_update(q, k, v, t0, ctx, m_scr, l_scr, acc_scr, *,
                  scale: float):
    """Every kernel's online-softmax update over one block of keys (a
    page, or the fused decode walk's multi-page block): q (H, D) f32,
    k/v (L, KV, D) the keys and values at positions t0 .. t0+L-1, state
    m/l (H, 1) and acc (H, D) f32.  All H query rows score against all
    L*KV key rows in ONE dot, and p·V is one dot: a row keeps only its
    own KV head's keys (its GQA group: row // G == key % KV) before
    ``ctx``, the rest are masked to p = 0.  This reads the block in its
    pool layout; per-head dots would first relayout it head-major, which
    on a v5e cost 5x the whole fused decode kernel (Yi-34B widths, 8
    lanes of 1-4k tokens: 0.66 ms a call against 0.125 ms).  Masked
    rows' values still meet p = 0 in p·V, so they must be finite: rows
    of a live page, or of a buffer the kernel zeroed."""
    H, D = q.shape
    L, KV, _ = k.shape
    kf = k.astype(jnp.float32).reshape(L * KV, D)
    vf = v.astype(jnp.float32).reshape(L * KV, D)
    s = jax.lax.dot_general(
        q, kf, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale     # (H, L*KV)
    c = jax.lax.broadcasted_iota(jnp.int32, (1, L * KV), 1)
    r = jax.lax.broadcasted_iota(jnp.int32, (H, 1), 0)
    own = (c % KV == r // (H // KV)) & (t0 + c // KV < ctx)
    s = jnp.where(own, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    pv = jax.lax.dot_general(
        p, vf, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)             # (H, D)
    acc_scr[...] = acc_scr[...] * corr + pv
    m_scr[...] = m_new


def _softmax_out(l_scr, acc_scr):
    """The attention output (H, D) from the final softmax state."""
    return acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)


def _kernel(tables_ref, ctx_ref, q_ref, k_ref, v_ref, o_ref,
            m_scr, l_scr, acc_scr, *, scale: float, page: int, npages: int):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        _softmax_init(m_scr, l_scr, acc_scr)

    _block_update(q_ref[0].astype(jnp.float32), k_ref[0], v_ref[0],
                  j * page, ctx_ref[b], m_scr, l_scr, acc_scr, scale=scale)

    @pl.when(j == npages - 1)
    def _finish():
        o_ref[0] = _softmax_out(l_scr, acc_scr).astype(o_ref.dtype)


# Keys per block of the fused decode walk.  On a v5e at Yi-34B widths
# (8 lanes of 1-2.5k tokens) a call took 0.105 ms at 128 keys, 0.089 ms
# at 256 and 0.086 ms at 512: 256 keeps most of the gain while a
# lane's partial last block wastes at most 255 keys of compute.
_BLOCK_TOKENS = 256
_LANES = 128


def _pages_per_block(page: int, n_max: int) -> int:
    """Table entries per block of the fused decode walk (shapes alone)."""
    return min(n_max, max(1, _BLOCK_TOKENS // page))


def _fused_dma_kernel(tab_ref, pos_ref, q_ref, kn_ref, vn_ref, k_hbm, v_hbm,
                      o_ref, k_out, v_out, kbuf, vbuf, sems, row_sems,
                      slot_ref, m_scr, l_scr, acc_scr, *, scale: float,
                      page: int, ppb: int):
    """Append-then-attend for lane b = program_id(0) over its live pages.

    The pool stays in HBM.  Lane b's live table entries
    (0 .. pos[b] // page) are fetched ``ppb`` at a time by DMA into one
    of two VMEM slots, block i+1 (or the next lane's first block) while
    block i computes; each block is one online-softmax update over its
    ``ppb * page`` keys.  The new K/V row is spliced into the VMEM copy
    of the block that holds ``pos[b]`` before attending, and that one row
    is written to its page in HBM; no page is written back."""
    b = pl.program_id(0)
    lanes = pl.num_programs(0)
    bk = ppb * page
    pos = pos_ref[b]
    n_blocks = (pos // page) // ppb + 1

    def block_dma(lane, i, slot, op):
        """Start (or wait for) the page copies of lane's block i into
        ``slot``: only the block's entries before the lane's live-page
        count, so no entry past the context (which ends inside the
        table) is ever read."""
        n_live = pos_ref[lane] // page + 1

        def page_dma(j, carry):
            pid = tab_ref[lane, i * ppb + j]
            for kv, (hbm, buf) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf))):
                op(pltpu.make_async_copy(
                    hbm.at[pid], buf.at[slot, pl.ds(j * page, page)],
                    sems.at[kv, slot]))
            return carry

        jax.lax.fori_loop(0, jnp.minimum(ppb, n_live - i * ppb), page_dma, 0)

    def start(lane, i, slot):
        block_dma(lane, i, slot, lambda cp: cp.start())

    def wait(lane, i, slot):
        block_dma(lane, i, slot, lambda cp: cp.wait())

    @pl.when(b == 0)
    def _first():
        # a last block's entries past the live pages are never fetched:
        # their rows hold zeros or an earlier block's finite values
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)
        slot_ref[0] = 0
        start(0, 0, 0)

    # the append: this one row goes to its page; nothing else is written
    dst = tab_ref[b, pos // page]
    rows = [pltpu.make_async_copy(src.at[0], hbm.at[dst, pos % page],
                                  row_sems.at[kv])
            for kv, (src, hbm) in enumerate(((kn_ref, k_out),
                                             (vn_ref, v_out)))]
    for cp in rows:
        cp.start()

    _softmax_init(m_scr, l_scr, acc_scr)
    q = q_ref[0].astype(jnp.float32)

    def body(i, slot):
        nxt = 1 - slot

        @pl.when(i + 1 < n_blocks)
        def _prefetch_own():
            start(b, i + 1, nxt)

        @pl.when((i + 1 == n_blocks) & (b + 1 < lanes))
        def _prefetch_next_lane():
            start(b + 1, 0, nxt)

        wait(b, i, slot)

        @pl.when(i == n_blocks - 1)
        def _splice():
            kbuf[slot, pos - i * bk] = kn_ref[0]
            vbuf[slot, pos - i * bk] = vn_ref[0]

        _block_update(q, kbuf[slot], vbuf[slot], i * bk, pos + 1,
                      m_scr, l_scr, acc_scr, scale=scale)
        return nxt

    slot_ref[0] = jax.lax.fori_loop(0, n_blocks, body, slot_ref[0])
    o_ref[0] = _softmax_out(l_scr, acc_scr).astype(o_ref.dtype)
    for cp in rows:
        cp.wait()


def _fused_window_kernel(tab_ref, pos_ref, q_ref, kn_ref, vn_ref, k_ref,
                         v_ref, o_ref, ko_ref, vo_ref, m_scr, l_scr,
                         acc_scr, *, scale: float, page: int):
    """The same walk one page per grid step (j = program_id(1)), fetched
    by the grid's own page windows.  Steps past the lane's last live page
    keep its window (no fetch) and compute nothing; the appended row is
    the (1, 1, KV, D) output window at (table[b, pos // page], pos %
    page)."""
    b = pl.program_id(0)
    j = pl.program_id(1)
    pos = pos_ref[b]
    last = pos // page

    @pl.when(j == 0)
    def _init():
        _softmax_init(m_scr, l_scr, acc_scr)
        ko_ref[0, 0] = kn_ref[0]
        vo_ref[0, 0] = vn_ref[0]

    @pl.when(j <= last)
    def _attend():
        k, v = k_ref[0], v_ref[0]                         # (page, KV, D)
        sel = jax.lax.broadcasted_iota(jnp.int32, k.shape, 0) == pos - j * page
        k = jnp.where(sel, kn_ref[0][None], k)
        v = jnp.where(sel, vn_ref[0][None], v)
        _block_update(q_ref[0].astype(jnp.float32), k, v, j * page, pos + 1,
                      m_scr, l_scr, acc_scr, scale=scale)

    @pl.when(j == last)
    def _finish():
        o_ref[0] = _softmax_out(l_scr, acc_scr).astype(o_ref.dtype)


def fused_decode_attention(q, k_new, v_new, k_pages, v_pages, block_tables,
                           positions, *, scale=None, interpret: bool = False):
    """Fused decode step: write each sequence's new KV entry into its page
    and attend over its live pages in one call (one dispatch instead of
    the ``paged_kv_append_batch`` + ``paged_attention`` pair).

    q: (B, H, D); k_new/v_new: (B, KV, D) this step's entries; positions:
    (B,) the slot each entry occupies (context length BEFORE the token, so
    ctx = positions + 1 is attended).  The page pool is aliased input to
    output.  Each lane visits only its live table entries
    (``0 .. positions[b] // page``): no page past its context is read, and
    the append writes the one (KV, D) row at
    ``k_pages[table[b, pos // page], pos % page]`` (and the same for V),
    spliced into the attended copy first.  Padded and retired lanes carry
    all-scrap tables, so theirs lands on the scrap page (pool index P-1),
    which by construction never appears in a live block table.  KV heads
    come from the pool's shape, so a tensor-parallel shard runs it on its
    own head slice.  Returns (out (B, H, D), k_pages, v_pages).

    Two lowerings of the one walk, chosen by the head width:

    - D a multiple of 128 lanes: one grid step per lane; the pool stays in
      HBM and the kernel DMAs the live entries ``_pages_per_block(page,
      n_max)`` at a time (256 keys, or one page when pages are larger),
      double-buffered across blocks and lanes, one online-softmax update
      per block.
    - narrower heads: XLA pads the pool's minor dim to the 128-lane tile,
      and Mosaic cannot slice such an HBM ref for a DMA, so the grid's own
      windows fetch one page per step over a ``(B, n_max)`` grid; steps
      past a lane's live pages keep its last window and skip the math.
    """
    B, H, D = q.shape
    P, page, KV, _ = k_pages.shape
    n_max = block_tables.shape[1]
    scale = scale or D ** -0.5
    args = (block_tables, positions.astype(jnp.int32), q,
            k_new.astype(k_pages.dtype), v_new.astype(v_pages.dtype),
            k_pages, v_pages)
    out_shape = [jax.ShapeDtypeStruct((B, H, D), q.dtype),
                 jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
                 jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype)]
    softmax_state = [pltpu.VMEM((H, 1), jnp.float32),
                     pltpu.VMEM((H, 1), jnp.float32),
                     pltpu.VMEM((H, D), jnp.float32)]
    if D % _LANES == 0:
        ppb = _pages_per_block(page, n_max)
        kernel = functools.partial(_fused_dma_kernel, scale=scale, page=page,
                                   ppb=ppb)
        lane = lambda b, tab, pos: (b, 0, 0)
        hbm = pl.BlockSpec(memory_space=pl.ANY)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[pl.BlockSpec((1, H, D), lane),
                      pl.BlockSpec((1, KV, D), lane),
                      pl.BlockSpec((1, KV, D), lane), hbm, hbm],
            out_specs=[pl.BlockSpec((1, H, D), lane), hbm, hbm],
            scratch_shapes=[
                pltpu.VMEM((2, ppb * page, KV, D), k_pages.dtype),
                pltpu.VMEM((2, ppb * page, KV, D), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32)] + softmax_state,
        )
    else:
        kernel = functools.partial(_fused_window_kernel, scale=scale,
                                   page=page)
        lane = lambda b, j, tab, pos: (b, 0, 0)
        live_page = lambda b, j, tab, pos: (
            tab[b, jnp.minimum(j, pos[b] // page)], 0, 0, 0)
        row = lambda b, j, tab, pos: (
            tab[b, pos[b] // page], pos[b] % page, 0, 0)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, n_max),
            in_specs=[pl.BlockSpec((1, H, D), lane),
                      pl.BlockSpec((1, KV, D), lane),
                      pl.BlockSpec((1, KV, D), lane),
                      pl.BlockSpec((1, page, KV, D), live_page),
                      pl.BlockSpec((1, page, KV, D), live_page)],
            out_specs=[pl.BlockSpec((1, H, D), lane),
                       pl.BlockSpec((1, 1, KV, D), row),
                       pl.BlockSpec((1, 1, KV, D), row)],
            scratch_shapes=softmax_state,
        )
    # aliases index the flattened pallas_call operands INCLUDING the two
    # scalar-prefetch args: k_pages is operand 5, v_pages operand 6
    return pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=out_shape,
        input_output_aliases={5: 1, 6: 2},
        interpret=interpret, name="fused_decode_attention",
    )(*args)


def _verify_kernel(tables_ref, pos0_ref, width_ref, q_ref, kn_ref, vn_ref,
                   k_ref, v_ref, o_ref, ko_ref, vo_ref,
                   m_scr, l_scr, acc_scr, *, scale: float, page: int,
                   npages: int, W: int):
    """Speculative verification: W query rows per lane in one grid pass.

    Window row s holds the lane's query at position pos0[b]+s (row 0 the
    last accepted token, rows 1.. the drafted tokens); rows at or past
    width[b] are padding.  All live rows' K/V entries are spliced into the
    VMEM page copy first (draft KV — rows beyond the eventually-accepted
    prefix become stale garbage the engine truncates / overwrites; they are
    never attended because of the per-row causal mask), then each row
    attends under its own context length pos0+s+1.

    The per-row online-softmax bodies are UNROLLED python loops over
    ``_block_update`` on one page, which is also each step of the fused
    decode kernel's page-window lowering (heads under 128 lanes): there a
    verified position repeats the decode it replaces.  The DMA lowering
    (128-lane heads) takes the same update over 256-key blocks, so a
    verified position can differ in the last bits (``chip_smoke.py``
    measures both); interpret mode chains the decode kernel instead (see
    ``fused_verify_attention``)."""
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        _softmax_init(m_scr, l_scr, acc_scr)

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

    for s in range(W):
        _block_update(q_ref[0, s].astype(jnp.float32), k, v, j * page,
                      p0 + s + 1, m_scr.at[s], l_scr.at[s], acc_scr.at[s],
                      scale=scale)

    @pl.when(j == npages - 1)
    def _finish():
        for s in range(W):
            o_ref[0, s] = _softmax_out(l_scr.at[s], acc_scr.at[s]).astype(
                o_ref.dtype)


def fused_verify_attention(q, k_new, v_new, k_pages, v_pages, block_tables,
                           pos0, widths, *, scale=None,
                           interpret: bool = False):
    """Batched speculative verification: append + attend W window rows per
    lane in one device call (the multi-token generalization of
    ``fused_decode_attention``; W=1 attends as it does).

    q: (B, W, H, D); k_new/v_new: (B, W, KV, D) the window rows' entries;
    pos0: (B,) the slot of row 0 (= context length before the window);
    widths: (B,) live rows per lane, 1..W — rows past width are padding
    whose outputs the caller discards and whose KV is never spliced.
    Returns (out (B, W, H, D), k_pages, v_pages).

    Two lowerings, same contract:

    - real TPU: ``_verify_multirow``, a single grid pass scoring all W
      rows per lane against each page while it is resident in VMEM (one
      pool read for the whole window).
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
    scale = scale or D ** -0.5

    kernel = functools.partial(_verify_kernel, scale=scale, page=page,
                               npages=n_max, W=W)

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
            pltpu.VMEM((W, H, 1), jnp.float32),
            pltpu.VMEM((W, H, 1), jnp.float32),
            pltpu.VMEM((W, H, D), jnp.float32),
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
    scale = scale or D ** -0.5

    kernel = functools.partial(_kernel, scale=scale, page=page,
                               npages=n_max)
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
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        interpret=interpret, name="paged_attention",
    )(block_tables, ctx_lens, q, k_pages, v_pages)

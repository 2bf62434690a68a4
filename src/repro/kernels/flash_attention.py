"""Pallas TPU causal flash attention (prefill/training hot spot).

Grid: (batch, q_heads, q_blocks, kv_blocks); VMEM scratch carries the online
softmax state (m, l, acc) across the innermost kv dimension.  Block shapes
are MXU-aligned (q/kv blocks multiples of 128 where the problem allows) and
sized so the working set — q block (bq×D) + kv block (bk×D) ×2 + acc (bq×D)
f32 — stays well under the ~16 MB VMEM budget: bq=bk=512, D=128 uses
~1.4 MB.  GQA is handled by the kv index_map (q head h reads kv head h//G).

HBM traffic: q, k, v read once per needed tile, o written once — the whole
point vs. the XLA path that materialises (bq×S) score tensors (see
EXPERIMENTS.md §Perf).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, bq: int, bk: int, nk: int, causal: bool):
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, :, 0, :].astype(jnp.float32)          # (bq, D)
    k = k_ref[0, :, 0, :].astype(jnp.float32)          # (bk, D)
    v = v_ref[0, :, 0, :].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(qpos >= kpos, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1)
    acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(j == nk - 1)
    def _finish():
        o_ref[0, :, 0, :] = (
            acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)[:, None]
        ).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 512,
                    block_k: int = 512, scale=None,
                    interpret: bool = False):
    """q: (B,S,H,D); k/v: (B,S,KV,D), KV | H.  Returns (B,S,H,D)."""
    B, S, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    bq = min(block_q, S)
    bk = min(block_k, S)
    assert S % bq == 0 and S % bk == 0, (S, bq, bk)
    nq, nk = S // bq, S // bk
    scale = scale or D ** -0.5

    kernel = functools.partial(_kernel, scale=scale, bq=bq, bk=bk, nk=nk,
                               causal=causal)
    return pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, 1, D), lambda b, h, i, j: (b, i, h, 0)),
            pl.BlockSpec((1, bk, 1, D), lambda b, h, i, j: (b, j, h // G, 0)),
            pl.BlockSpec((1, bk, 1, D), lambda b, h, i, j: (b, j, h // G, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, 1, D), lambda b, h, i, j: (b, i, h, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            _vmem((bq,), jnp.float32),
            _vmem((bq,), jnp.float32),
            _vmem((bq, D), jnp.float32),
        ],
        interpret=interpret, name="flash_attention",
    )(q, k, v)


def _vmem(shape, dtype):
    try:
        from jax.experimental.pallas import tpu as pltpu
        return pltpu.VMEM(shape, dtype)
    except Exception:  # pragma: no cover - CPU-only fallback
        import jax.experimental.pallas as pl2
        return pl2.MemoryRef(shape, dtype)

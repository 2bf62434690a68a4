"""Shared building blocks: norms, rotary/sinusoidal positions, SwiGLU MLP,
LM loss.  All computations that affect numerics (norm variance, softmax,
logsumexp, recurrent states) run in float32 regardless of the activation
dtype."""

from __future__ import annotations

import math

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs.base import yarn_get_mscale


def rms_norm(x, w, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def silu(x):
    return x * jax.nn.sigmoid(x)


# ---------------------------------------------------------------------------
# Positions
# ---------------------------------------------------------------------------
def yarn_inv_freq(dim: int, theta: float, factor: float, orig: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN's inverse frequencies (HF ``DeepseekV2YarnRotaryEmbedding``):
    dimensions that turn more than ``beta_fast`` times over the original
    context keep theta's frequency, those turning fewer than ``beta_slow``
    times are divided by ``factor``, with a linear ramp between."""
    def corr_dim(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(corr_dim(beta_fast)), 0)
    high = min(math.ceil(corr_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / (theta ** (np.arange(0, dim, 2) / dim))
    inter = extra / factor
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    keep = 1.0 - ramp
    return inter * (1 - keep) + extra * keep


def rope_tables(positions, head_dim: int, theta: float, cfg=None):
    """positions: int array (...,) -> cos/sin tables (..., head_dim//2).
    A ``cfg`` with ``yarn_factor`` set takes YaRN's frequencies and scales
    the tables by mscale / mscale_all_dim."""
    half = head_dim // 2
    scale = 1.0
    if cfg is not None and cfg.yarn_factor:
        inv = yarn_inv_freq(head_dim, theta, cfg.yarn_factor,
                            cfg.yarn_original_max_pos, cfg.yarn_beta_fast,
                            cfg.yarn_beta_slow)
        scale = yarn_get_mscale(cfg.yarn_factor, cfg.yarn_mscale) \
            / yarn_get_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim)
    else:
        inv = 1.0 / (theta ** (np.arange(0, half) / half))
    ang = positions.astype(jnp.float32)[..., None] \
        * inv.astype(np.float32)                          # (..., half)
    if scale == 1.0:
        return jnp.cos(ang), jnp.sin(ang)
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale


def apply_rope(x, cos, sin, interleaved: bool = False):
    """x: (B, S, H, Dh); cos/sin: (S, Dh/2) or (B, S, Dh/2).  Half-split
    rotation; ``interleaved`` first gathers DeepSeek's pairs (x[2i],
    x[2i+1]) into the halves, as HF's DeepseekV2 ``apply_rotary_pos_emb``
    does (the output stays in the gathered order)."""
    half = x.shape[-1] // 2
    if interleaved:
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    if cos.ndim == 2:        # (S, half) -> (1, S, 1, half)
        cos = cos[None, :, None, :]
        sin = sin[None, :, None, :]
    else:                    # (B, S, half) -> (B, S, 1, half)
        cos = cos[:, :, None, :]
        sin = sin[:, :, None, :]
    xf = x.astype(jnp.float32)
    x1f, x2f = xf[..., :half], xf[..., half:]
    out = jnp.concatenate([x1f * cos - x2f * sin, x1f * sin + x2f * cos], -1)
    return out.astype(x.dtype)


def sinusoidal_embedding(positions, d_model: int):
    """positions: int array (S,) or (B, S) -> (..., d_model) f32 table."""
    half = d_model // 2
    freq = np.exp(-np.log(10000.0) * np.arange(half) / half)
    ang = positions.astype(jnp.float32)[..., None] * freq
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------
def mlp(x, p, ctx):
    h = silu(x @ p["w_gate"]) * (x @ p["w_up"])
    h = ctx.cs(h, ctx.batch, ctx.seq, None)
    # under serving TP w_gate/w_up are column- and w_down row-sharded on
    # d_ff; each shard's down-projection is a partial sum (no-op otherwise)
    return ctx.psum_mlp(h @ p["w_down"])


# ---------------------------------------------------------------------------
# LM loss (vocab possibly padded; computed in f32)
# ---------------------------------------------------------------------------
def lm_loss(h, w_head, labels, mask, vocab_size: int):
    """h: (B, S, D), w_head: (D, Vp), labels: (B, S) int, mask: (B, S).

    Returns mean NLL over masked-in tokens.  Padded vocab columns are
    excluded via a large negative bias.
    """
    logits = jnp.einsum("bsd,dv->bsv", h, w_head,
                        preferred_element_type=jnp.float32)
    vp = w_head.shape[-1]
    if vp > vocab_size:
        pad_bias = jnp.where(jnp.arange(vp) < vocab_size, 0.0, -1e9)
        logits = logits + pad_bias
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = (lse - ll) * mask
    return jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1.0)

"""Unified model API.

``build_model(cfg, ctx)`` returns a :class:`Model` with pure functions:

  init(key)                                   -> params
  loss(params, batch)                         -> scalar NLL
  prefill(params, batch)                      -> (logits, caches)
  decode_step(params, caches, tokens, index)  -> (logits, caches)
  cache_specs(B, S)                           -> ShapeDtypeStruct pytree
  input_specs(shape)                          -> batch ShapeDtypeStructs

Batch dict keys by frontend:
  none            : tokens (B,S) i32, labels (B,S) i32
  audio_frames    : frames (B,S,D) act-dtype, labels (B,S) i32
  vision_patches  : patches (B,P,D), tokens (B,S-P) i32, labels (B,S) i32
                    (loss masked to the text positions)
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.configs.shapes import Shape
from repro.models.layers import lm_loss, rms_norm, sinusoidal_embedding
from repro.models.partition import NULL_CTX, AxisCtx
from repro.models.transformer import stack_apply, stack_apply_paged


class _KeyGen:
    def __init__(self, key):
        self._key = key
        self._n = 0

    def __call__(self):
        self._n += 1
        return jax.random.fold_in(self._key, self._n)


def _dense(kg, shape, dtype, scale=0.02):
    return (jax.random.normal(kg(), shape, jnp.float32) * scale).astype(dtype)


def _init_layer(kg, mixer, ffn, cfg: ModelConfig, stack: int = 0):
    """Init one layer's params; if stack>0 every leaf gets a leading dim."""
    d, dt = cfg.d_model, jnp.dtype(cfg.dtype)

    def mk(*shape, scale=0.02, zeros=False, ones=False, f32=False):
        shape = ((stack,) + shape) if stack else shape
        dtype = jnp.float32 if f32 else dt
        if zeros:
            return jnp.zeros(shape, dtype)
        if ones:
            return jnp.ones(shape, dtype)
        return _dense(kg, shape, dtype, scale)

    p: Dict[str, Any] = {"ln1": mk(d, ones=True)}
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    if mixer == "attn":
        p.update(wq=mk(d, H, hd), wk=mk(d, KV, hd), wv=mk(d, KV, hd),
                 wo=mk(H, hd, d))
    elif mixer == "mla":
        qk = cfg.qk_head_dim
        if cfg.q_lora_rank:
            p.update(w_dq=mk(d, cfg.q_lora_rank),
                     q_ln=mk(cfg.q_lora_rank, ones=True),
                     w_uq=mk(cfg.q_lora_rank, H, qk))
        else:
            p.update(w_q=mk(d, H, qk))
        p.update(w_dkv=mk(d, cfg.kv_lora_rank),
                 kv_ln=mk(cfg.kv_lora_rank, ones=True),
                 w_kr=mk(d, cfg.qk_rope_dim),
                 w_uk=mk(cfg.kv_lora_rank, H, cfg.qk_nope_dim),
                 w_uv=mk(cfg.kv_lora_rank, H, cfg.v_head_dim),
                 wo=mk(H, cfg.v_head_dim, d))
    elif mixer == "mamba":
        di, ds, dc = cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_d_conv
        dtr = cfg.resolved_dt_rank
        alog = jnp.log(jnp.arange(1, ds + 1, dtype=jnp.float32))
        alog = jnp.broadcast_to(alog, (di, ds))
        if stack:
            alog = jnp.broadcast_to(alog, (stack, di, ds))
        p.update(w_in=mk(d, 2 * di), conv_w=mk(dc, di), conv_b=mk(di, zeros=True),
                 w_x=mk(di, dtr + 2 * ds), w_dt=mk(dtr, di, scale=0.1),
                 dt_bias=mk(di, zeros=True, f32=True),
                 A_log=alog, D=mk(di, ones=True, f32=True),
                 w_out=mk(di, d))
    elif mixer == "mlstm":
        Hx = cfg.xlstm_num_heads
        dh = d // Hx
        p.update(w_q=mk(d, Hx, dh), w_k=mk(d, Hx, dh), w_v=mk(d, Hx, dh),
                 w_i=mk(d, Hx), w_f=mk(d, Hx), w_og=mk(d, d), w_down=mk(d, d))
    elif mixer == "slstm":
        Hx = cfg.xlstm_num_heads
        dh = d // Hx
        p.update(w_z=mk(d, Hx, dh), w_i=mk(d, Hx, dh), w_f=mk(d, Hx, dh),
                 w_o=mk(d, Hx, dh),
                 r_z=mk(Hx, dh, dh), r_i=mk(Hx, dh, dh), r_f=mk(Hx, dh, dh),
                 r_o=mk(Hx, dh, dh))
    else:
        raise ValueError(mixer)

    if ffn != "none":
        p["ln2"] = mk(d, ones=True)
    if ffn == "mlp":
        p.update(w_gate=mk(d, cfg.d_ff), w_up=mk(d, cfg.d_ff),
                 w_down=mk(cfg.d_ff, d))
    elif ffn == "moe":
        # the router scores every expert; the weights are the held ones'
        E, fe = cfg.held_experts, cfg.d_ff_expert
        p.update(router=mk(d, cfg.num_experts),
                 w_gate=mk(E, d, fe), w_up=mk(E, d, fe), w_down=mk(E, fe, d))
        if cfg.num_shared_experts:
            fs = cfg.num_shared_experts * fe
            p.update(shared_gate=mk(d, fs), shared_up=mk(d, fs),
                     shared_down=mk(fs, d))
    return p


def _cache_for(mixer, cfg: ModelConfig, B: int, S: int, stack: int = 0):
    dt = jnp.dtype(cfg.dtype)
    d = cfg.d_model

    def sds(*shape, dtype=dt):
        shape = ((stack,) + shape) if stack else shape
        return jax.ShapeDtypeStruct(shape, dtype)

    if mixer == "attn":
        KV, hd = cfg.num_kv_heads, cfg.resolved_head_dim
        return {"k": sds(B, S, KV, hd), "v": sds(B, S, KV, hd)}
    if mixer == "mla":
        return {"ckv": sds(B, S, cfg.kv_lora_rank),
                "kr": sds(B, S, cfg.qk_rope_dim)}
    if mixer == "mamba":
        di = cfg.mamba_d_inner
        return {"conv": sds(B, cfg.mamba_d_conv - 1, di, dtype=jnp.float32),
                "ssm": sds(B, di, cfg.mamba_d_state, dtype=jnp.float32)}
    if mixer == "mlstm":
        Hx = cfg.xlstm_num_heads
        dh = d // Hx
        return {"C": sds(B, Hx, dh, dh, dtype=jnp.float32),
                "n": sds(B, Hx, dh, dtype=jnp.float32),
                "m": sds(B, Hx, dtype=jnp.float32)}
    if mixer == "slstm":
        Hx = cfg.xlstm_num_heads
        dh = d // Hx
        z = lambda: sds(B, Hx, dh, dtype=jnp.float32)
        return {"c": z(), "n": z(), "h": z(), "m": z()}
    raise ValueError(mixer)


@dataclasses.dataclass
class Model:
    cfg: ModelConfig
    ctx: AxisCtx = NULL_CTX

    # ------------------------------------------------------------------
    def init(self, key) -> Dict[str, Any]:
        cfg = self.cfg
        kg = _KeyGen(key)
        dt = jnp.dtype(cfg.dtype)
        params: Dict[str, Any] = {}
        if cfg.frontend != "audio_frames" or True:
            params["embed"] = _dense(kg, (cfg.vocab_size, cfg.d_model), dt)
        params["prefix"] = {
            f"l{i}": _init_layer(kg, m, f, cfg)
            for i, (m, f) in enumerate(cfg.prefix_pattern)}
        # scanned units: leading num_units dim on every leaf
        params["units"] = {
            f"l{i}": _init_layer(kg, m, f, cfg, stack=cfg.num_units)
            for i, (m, f) in enumerate(cfg.unit_pattern)}
        params["final_norm"] = jnp.ones((cfg.d_model,), dt)
        params["lm_head"] = _dense(kg, (cfg.d_model, cfg.vocab_padded), dt)
        return params

    # ------------------------------------------------------------------
    def _embed(self, params, batch, mode, index=None):
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        if mode == "decode":
            x = jnp.take(params["embed"], batch["tokens"], axis=0)
            pos = jnp.full((1,), index)
        elif cfg.frontend == "audio_frames":
            x = batch["frames"].astype(dt)
            pos = jnp.arange(x.shape[1])
        elif cfg.frontend == "vision_patches":
            te = jnp.take(params["embed"], batch["tokens"], axis=0)
            x = jnp.concatenate([batch["patches"].astype(dt), te], axis=1)
            pos = jnp.arange(x.shape[1])
        else:
            x = jnp.take(params["embed"], batch["tokens"], axis=0)
            pos = jnp.arange(x.shape[1])
        if cfg.positional == "sinusoidal":
            x = x + sinusoidal_embedding(pos, cfg.d_model)[None].astype(dt)
        return self.ctx.hidden(x)

    def _loss_mask(self, batch):
        cfg = self.cfg
        lab = batch["labels"]
        if cfg.frontend == "vision_patches":
            S = lab.shape[1]
            return (jnp.arange(S) >= cfg.num_patches)[None, :].astype(
                jnp.float32) * jnp.ones_like(lab, jnp.float32)
        return jnp.ones_like(lab, jnp.float32)

    def loss(self, params, batch):
        x = self._embed(params, batch, "train")
        x, _ = stack_apply(x, params, self.cfg, self.ctx, "train")
        x = rms_norm(x, params["final_norm"], self.cfg.norm_eps)
        return lm_loss(x, params["lm_head"], batch["labels"],
                       self._loss_mask(batch), self.cfg.vocab_size)

    def logits(self, params, batch):
        """Full-sequence logits — smoke tests / greedy eval."""
        x = self._embed(params, batch, "train")
        x, _ = stack_apply(x, params, self.cfg, self.ctx, "train")
        x = rms_norm(x, params["final_norm"], self.cfg.norm_eps)
        out = jnp.einsum("bsd,dv->bsv", x, params["lm_head"],
                         preferred_element_type=jnp.float32)
        return out[..., :self.cfg.vocab_size]

    def prefill(self, params, batch):
        x = self._embed(params, batch, "prefill")
        x, caches = stack_apply(x, params, self.cfg, self.ctx, "prefill")
        x = rms_norm(x, params["final_norm"], self.cfg.norm_eps)
        logits = jnp.einsum("bd,dv->bv", x[:, -1], params["lm_head"],
                            preferred_element_type=jnp.float32)
        return logits[..., :self.cfg.vocab_size], caches

    def decode_step(self, params, caches, tokens, index):
        """tokens: (B,1) int32; index: scalar int32 (next write position)."""
        x = self._embed(params, {"tokens": tokens}, "decode", index=index)
        x, caches = stack_apply(x, params, self.cfg, self.ctx, "decode",
                                caches=caches, index=index)
        x = rms_norm(x, params["final_norm"], self.cfg.norm_eps)
        logits = jnp.einsum("bd,dv->bv", x[:, 0], params["lm_head"],
                            preferred_element_type=jnp.float32)
        return logits[..., :self.cfg.vocab_size], caches

    # ------------------------------------------------------------------
    # Paged-KV serving entry points (DESIGN.md §3).  The KV cache is one
    # device-resident page pool per attention layer; sequences own pages
    # through block tables handed in by the serving engine's BlockManager.
    # ------------------------------------------------------------------
    def supports_paged(self) -> bool:
        """Paged serving covers attention stacks (GQA or latent attention,
        any FFN) with rope or no positional encoding — recurrent mixers
        have no paged state and sinusoidal embeds would need per-sequence
        position offsets."""
        cfg = self.cfg
        return (all(m in ("attn", "mla") for m, _ in
                    cfg.prefix_pattern + cfg.unit_pattern)
                and cfg.positional in ("rope", "none")
                and cfg.frontend == "none")

    def paged_cache_specs(self, num_pages: int, page: int):
        """Page pools per attention layer — GQA: k/v (num_pages, page, KV,
        Dh); MLA: one latent pool (num_pages, page, W) of rows [c | k_rope
        | 0], W = kv_lora_rank + qk_rope_dim rounded up to 128 lanes.
        Scanned units carry the leading num_units dim like cache_specs."""
        from repro.models.attention import mla_row_width
        cfg = self.cfg
        KV, hd = cfg.num_kv_heads, cfg.resolved_head_dim
        dt = jnp.dtype(cfg.dtype)

        def pool(mixer, stack=0):
            lead = (stack,) if stack else ()
            if mixer == "mla":
                return {"lat": jax.ShapeDtypeStruct(
                    lead + (num_pages, page, mla_row_width(cfg)), dt)}
            shape = lead + (num_pages, page, KV, hd)
            return {"k": jax.ShapeDtypeStruct(shape, dt),
                    "v": jax.ShapeDtypeStruct(shape, dt)}

        prefix = tuple(pool(m) for m, _ in cfg.prefix_pattern)
        units = {f"l{i}": pool(m, stack=cfg.num_units)
                 for i, (m, _) in enumerate(cfg.unit_pattern)}
        return {"prefix": prefix, "units": units}

    def init_paged_caches(self, num_pages: int, page: int):
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                            self.paged_cache_specs(num_pages, page))

    def kv_bytes_per_token(self) -> int:
        """Bytes a token's cache takes in the page pools, as stored (MLA
        rows with their lane padding)."""
        specs = self.paged_cache_specs(1, 1)
        return int(sum(np.prod(s.shape) * s.dtype.itemsize
                       for s in jax.tree.leaves(specs)))

    def prefill_paged(self, params, pages, tokens, start, block_table, n, *,
                      interpret: bool = False):
        """Append one prompt chunk's KV for a single sequence.

        tokens: (1, C) with rows past ``n`` as padding; start: tokens
        already resident.  No logits are produced — the first decode step
        re-runs the final prompt token (its KV write is idempotent), so
        every emitted token flows through decode_paged uniformly.
        Returns (new pages, routed (token, held expert) pairs per scanned
        unit and held expert (num_units, E), or None without MoE units).
        ``interpret`` runs the MoE kernel interpreted (off a TPU)."""
        x = self._embed(params, {"tokens": tokens}, "prefill")
        _, new_pages, counts = stack_apply_paged(
            x, params, self.cfg, self.ctx, "prefill", pages, block_table,
            start, n, interpret=interpret)
        return new_pages, counts

    def decode_paged(self, params, pages, tokens, positions, block_tables,
                     *, interpret: bool = False, fused: bool = False):
        """One batched decode step: tokens (B,1) i32 at per-sequence write
        positions (B,); block_tables (B, n_max).  Returns (logits (B, V),
        new pages, routed-pair counts or None as in ``prefill_paged``;
        lanes whose table starts on the scrap page, the pool's last, are
        padding and route nothing).  ``fused=True`` routes
        GQA attention through the single-dispatch append+attend kernel
        (``fused_decode_attention``); MLA always takes its fused kernel.
        Under serving TP (ctx.tp_vocab_axis set) lm_head is
        vocab-column-sharded; the local logit slices are all-gathered —
        a pure concatenation, every column computed exactly as on one
        device — before the vocab-size slice."""
        x = self._embed(params, {"tokens": tokens}, "decode", index=0)
        x, new_pages, counts = stack_apply_paged(
            x, params, self.cfg, self.ctx, "decode", pages, block_tables,
            positions, interpret=interpret, fused=fused)
        x = rms_norm(x, params["final_norm"], self.cfg.norm_eps)
        logits = jnp.einsum("bd,dv->bv", x[:, 0], params["lm_head"],
                            preferred_element_type=jnp.float32)
        logits = self.ctx.gather_vocab(logits)
        return logits[..., :self.cfg.vocab_size], new_pages, counts

    def verify_paged(self, params, pages, tokens, pos0, widths,
                     block_tables, *, interpret: bool = False):
        """Speculative verification forward (DESIGN.md §11): score all W
        window positions per lane in one pass.  tokens: (B, W) i32 — row 0
        the last accepted token, rows 1.. drafted tokens, rows at or past
        ``widths[b]`` padding; pos0: (B,) row 0's KV slot.  Returns
        (logits (B, W, V), new pages) — logits at EVERY window position, so
        the sampler can accept/reject each draft and emit the bonus token.
        Each row's logits are bitwise identical to the single-token decode
        at that position (per-row unrolled verification kernel + row-stable
        einsums), which is what makes spec-on streams byte-equal to
        spec-off.  GQA stacks only."""
        if any(m != "attn" for m, _ in
               self.cfg.prefix_pattern + self.cfg.unit_pattern):
            raise ValueError(f"{self.cfg.name}: speculative verification "
                             "covers GQA attention only")
        x = self._embed(params, {"tokens": tokens}, "decode", index=0)
        x, new_pages, _ = stack_apply_paged(x, params, self.cfg, self.ctx,
                                         "verify", pages, block_tables,
                                         pos0, n=widths, interpret=interpret)
        x = rms_norm(x, params["final_norm"], self.cfg.norm_eps)
        logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"],
                            preferred_element_type=jnp.float32)
        logits = self.ctx.gather_vocab(logits)
        return logits[..., :self.cfg.vocab_size], new_pages

    # ------------------------------------------------------------------
    def cache_specs(self, B: int, S: int):
        cfg = self.cfg
        prefix = tuple(_cache_for(m, cfg, B, S)
                       for m, _ in cfg.prefix_pattern)
        units = {f"l{i}": _cache_for(m, cfg, B, S, stack=cfg.num_units)
                 for i, (m, _) in enumerate(cfg.unit_pattern)}
        return {"prefix": prefix, "units": units}

    def init_caches(self, B: int, S: int):
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                            self.cache_specs(B, S))

    def input_specs(self, shape: Shape) -> Dict[str, Any]:
        """ShapeDtypeStruct stand-ins for every model input of this cell."""
        cfg = self.cfg
        B, S = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        dt = jnp.dtype(cfg.dtype)
        sds = jax.ShapeDtypeStruct
        if shape.kind == "train":
            if cfg.frontend == "audio_frames":
                batch = {"frames": sds((B, S, cfg.d_model), dt),
                         "labels": sds((B, S), i32)}
            elif cfg.frontend == "vision_patches":
                P = cfg.num_patches
                batch = {"patches": sds((B, P, cfg.d_model), dt),
                         "tokens": sds((B, S - P), i32),
                         "labels": sds((B, S), i32)}
            else:
                batch = {"tokens": sds((B, S), i32),
                         "labels": sds((B, S), i32)}
            return {"batch": batch}
        if shape.kind == "prefill":
            if cfg.frontend == "audio_frames":
                batch = {"frames": sds((B, S, cfg.d_model), dt)}
            elif cfg.frontend == "vision_patches":
                P = cfg.num_patches
                batch = {"patches": sds((B, P, cfg.d_model), dt),
                         "tokens": sds((B, S - P), i32)}
            else:
                batch = {"tokens": sds((B, S), i32)}
            return {"batch": batch}
        # decode: one token against a seq_len cache
        return {"caches": self.cache_specs(B, S),
                "tokens": sds((B, 1), i32),
                "index": sds((), i32)}


def build_model(cfg: ModelConfig, ctx: Optional[AxisCtx] = None) -> Model:
    return Model(cfg, ctx or NULL_CTX)

"""DeepSeek-V2-style latent attention and held-expert MoE on the paged path,
against the plain float32 reference (``mla_moe_reference.py``, a copy of
the benchmark's ``chipbench/references/mla_moe.py``) at a small size on the
CPU: 2 layers (one dense, one MoE), 8 routed experts of which 4 are held,
latent and rope widths scaled down.

Tolerances: the program runs in float32 here, as the reference does, so
the two differ only by the order of float32 sums (about 1e-6 of logits of
size 0.1-1); 1e-4 leaves that a hundredfold and is far below what the
reference computed in float8 moves (checked alongside), so a lower
precision than the configuration's would fail."""

import dataclasses
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mla_moe_reference as ref
from repro.configs.archs import reduced_config
from repro.configs.base import ModelConfig, get_config
from repro.kernels.paged_attention import (mla_decode_attention,
                                           paged_row_append)
from repro.models.layers import rope_tables
from repro.models.model import Model, build_model
from repro.models.moe import _route, moe_dense, moe_held, shared_experts

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARCH = "deepseek-v2-lite-16b"
TOL = 1e-4


def _cfg():
    return reduced_config(ARCH)


def hf_of(cfg: ModelConfig) -> dict:
    """The HF-style configuration the reference reads, for ``cfg``."""
    return dict(
        hidden_size=cfg.d_model, num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_kv_heads, head_dim=cfg.resolved_head_dim,
        intermediate_size=cfg.d_ff, num_hidden_layers=cfg.num_layers,
        vocab_size=cfg.vocab_size, rope_theta=cfg.rope_theta,
        rms_norm_eps=cfg.norm_eps, kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_dim, qk_rope_head_dim=cfg.qk_rope_dim,
        v_head_dim=cfg.v_head_dim, moe_intermediate_size=cfg.d_ff_expert,
        n_routed_experts=cfg.held_experts,
        published=dict(n_routed_experts=cfg.num_experts),
        experts_held_first=cfg.experts_first,
        num_experts_per_tok=cfg.top_k, n_shared_experts=cfg.num_shared_experts,
        first_k_dense_replace=len(cfg.prefix_pattern),
        norm_topk_prob=cfg.norm_topk_prob,
        routed_scaling_factor=cfg.routed_scaling_factor,
        rope_scaling=dict(
            type="yarn", factor=cfg.yarn_factor,
            original_max_position_embeddings=cfg.yarn_original_max_pos,
            beta_fast=cfg.yarn_beta_fast, beta_slow=cfg.yarn_beta_slow,
            mscale=cfg.yarn_mscale, mscale_all_dim=cfg.yarn_mscale_all_dim))


def _weights(cfg, seed=0):
    w = ref.make_weights(hf_of(cfg), jax.random.PRNGKey(seed))
    return jax.tree.map(lambda a: a.astype(jnp.float32), w)


def _ref_logits(cfg, w, toks, fp8=False):
    with jax.default_matmul_precision("highest"):
        h = ref.hidden(hf_of(cfg), w, toks, fp8=fp8)
        return np.asarray(h @ w["lm_head"][:, :cfg.vocab_size])


# ---------------------------------------------------------------------------
# the paged latent path against the reference
# ---------------------------------------------------------------------------
def test_chunked_prefill_then_decode_matches_reference():
    cfg = _cfg()
    w = _weights(cfg)
    m = build_model(cfg)
    page, n_max = 4, 16
    pages = m.init_paged_caches(2 * n_max + 1, page)
    table = np.arange(3, 3 + n_max, dtype=np.int32)     # pages 3..18
    toks = np.random.default_rng(1).integers(0, cfg.vocab_size, 30)
    prompt, C = 17, 8
    prefill = jax.jit(lambda pg, t, s, n: m.prefill_paged(
        w, pg, t, s, jnp.asarray(table), n, interpret=True)[0])
    for s in range(0, prompt, C):
        n = min(C, prompt - s)
        chunk = np.zeros((1, C), np.int32)
        chunk[0, :n] = toks[s:s + n]
        pages = prefill(pages, jnp.asarray(chunk), s, n)
    decode = jax.jit(lambda pg, t, p, tab: m.decode_paged(
        w, pg, t, p, tab, interpret=True))
    # lane 1 is padding: all-scrap table, as the backend pads batches
    tabs = np.stack([table, np.full(n_max, 2 * n_max, np.int32)])
    got = []
    for pos in range(prompt - 1, len(toks) - 1):
        logits, pages, _ = decode(pages, jnp.asarray([[toks[pos]], [0]]),
                               jnp.asarray([pos, 0], jnp.int32),
                               jnp.asarray(tabs))
        got.append(np.asarray(logits[0]))
    want = _ref_logits(cfg, w, toks[:-1])[prompt - 1:]
    err = np.max(np.abs(np.stack(got) - want))
    fp8 = np.max(np.abs(_ref_logits(cfg, w, toks[:-1], fp8=True)
                        [prompt - 1:] - want))
    assert err < TOL < fp8, (err, fp8)


@pytest.mark.parametrize("key_rows", [4, 8, 12])
def test_prefill_walks_only_live_key_blocks(key_rows, monkeypatch):
    """Prefill attends block by block over the live context only: pages
    past the chunk's last real row are NaN-poisoned and never read, and
    the real rows equal one block over the whole (unpoisoned) table.
    1e-5: float32 online softmax against a single block, on outputs of
    size ~0.1."""
    from repro.models import attention
    from repro.models.attention import mla_prefill_paged, mla_row_width
    cfg = _cfg()
    lp = _weights(cfg)["prefix"]["l0"]
    page, n_max, start, n, C = 4, 8, 10, 5, 8
    P = n_max + 2
    table = jnp.asarray(np.random.default_rng(2).permutation(n_max + 1)
                        [:n_max].astype(np.int32))
    rng = np.random.default_rng(3)
    lat = rng.standard_normal((P, page, mla_row_width(cfg))).astype(
        np.float32)
    dead = np.asarray(table)[-(-(start + n) // page):]
    poisoned = lat.copy()
    poisoned[dead] = np.nan
    x = jnp.asarray(rng.standard_normal((1, C, cfg.d_model)), jnp.float32)
    monkeypatch.setattr(attention, "PREFILL_KEY_ROWS", key_rows)
    got, pages = mla_prefill_paged(x, lp, cfg, {"lat": jnp.asarray(poisoned)},
                                   table, start, n)
    monkeypatch.setattr(attention, "PREFILL_KEY_ROWS", page * n_max)
    want, _ = mla_prefill_paged(x, lp, cfg, {"lat": jnp.asarray(lat)},
                                table, start, n)
    got, want = np.asarray(got)[0, :n], np.asarray(want)[0, :n]
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)
    assert np.isnan(np.asarray(pages["lat"])[dead]).all()


# ---------------------------------------------------------------------------
# the expert share
# ---------------------------------------------------------------------------
def test_expert_shares_add_up_to_the_uncut_layer():
    """Each device's held experts (the program's grouped kernel) give its
    part; with the shared experts counted once the parts add up to the
    uncut layer (every expert held, the reference oracle)."""
    cfg = _cfg()
    full = dataclasses.replace(cfg, experts_held=0)
    p = jax.tree.map(lambda a: a.astype(jnp.float32),
                     Model(full).init(jax.random.PRNGKey(4))["units"]["l0"])
    p = jax.tree.map(lambda a: a[0], p)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 9, cfg.d_model))
    want = moe_dense(x, p, full) + shared_experts(x, p, full)
    parts = shared_experts(x, p, full)
    E = cfg.held_experts
    for first in range(0, cfg.num_experts, E):
        share = dataclasses.replace(cfg, experts_first=first)
        ps = dict(p, **{n: p[n][first:first + E]
                        for n in ("w_gate", "w_up", "w_down")})
        y, counts = moe_held(x, ps, share, interpret=True)
        np.testing.assert_allclose(y, moe_dense(x, ps, share), atol=1e-5)
        parts = parts + y
    np.testing.assert_allclose(parts, want, atol=1e-5)


def test_held_kernel_with_empty_groups_equals_dense():
    cfg = _cfg()
    p = jax.tree.map(lambda a: a[0].astype(jnp.float32),
                     Model(cfg).init(jax.random.PRNGKey(6))["units"]["l0"])
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(7), (1, 12,
                                                           cfg.d_model)))
    # held experts 1 and 3 (and every expert past the share) score far
    # below the rest: no token routes to them
    r = jnp.abs(p["router"]).at[:, jnp.array([1, 3])].set(-1.0)
    p = dict(p, router=r.at[:, cfg.held_experts:].set(-1.0))
    y, counts = moe_held(x, p, cfg, interpret=True)
    counts = np.asarray(counts)
    assert counts[1] == counts[3] == 0 and counts.sum() == 12 * cfg.top_k
    np.testing.assert_allclose(y, moe_dense(x, p, cfg), atol=1e-5)


def test_router_does_not_renormalise():
    cfg = _cfg()
    assert not cfg.norm_topk_prob
    x = jax.random.normal(jax.random.PRNGKey(8), (5, cfg.d_model))
    w = 0.05 * jax.random.normal(jax.random.PRNGKey(9), (cfg.d_model,
                                                         cfg.num_experts))
    topv, topi = _route(x, w, cfg)
    probs = jax.nn.softmax(x @ w, -1)
    np.testing.assert_allclose(topv, jnp.take_along_axis(probs, topi, 1),
                               rtol=1e-6)
    assert float(jnp.max(topv.sum(-1))) < 1.0
    renorm, _ = _route(x, w, dataclasses.replace(cfg, norm_topk_prob=True))
    np.testing.assert_allclose(renorm.sum(-1), 1.0, rtol=1e-6)


# ---------------------------------------------------------------------------
# YaRN
# ---------------------------------------------------------------------------
def _hf_yarn(dim, base, factor, orig, beta_fast, beta_slow, mscale,
             mscale_all_dim, positions):
    """HF ``DeepseekV2YarnRotaryEmbedding._set_cos_sin_cache``, in numpy."""
    def find_dim(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))) \
            / (2 * math.log(base))

    low = max(math.floor(find_dim(beta_fast)), 0)
    high = min(math.ceil(find_dim(beta_slow)), dim - 1)

    def ramp(lo, hi, n):
        if lo == hi:
            hi += 0.001
        return np.clip((np.arange(n, dtype=np.float32) - lo) / (hi - lo),
                       0, 1)

    def get_mscale(scale, m):
        return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0

    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    inter = 1.0 / (factor * base ** (np.arange(0, dim, 2,
                                               dtype=np.float32) / dim))
    mask = 1.0 - ramp(low, high, dim // 2)
    inv = inter * (1 - mask) + extra * mask
    freqs = np.outer(np.asarray(positions, np.float32), inv)
    ms = get_mscale(factor, mscale) / get_mscale(factor, mscale_all_dim)
    return np.cos(freqs) * ms, np.sin(freqs) * ms


def test_yarn_frequencies_and_mscale_match_hf():
    cfg = get_config(ARCH)
    pos = np.array([0, 1, 7, 4095, 4096, 40000, 163839])
    cos, sin = rope_tables(jnp.asarray(pos), cfg.qk_rope_dim, cfg.rope_theta,
                           cfg)
    hc, hs = _hf_yarn(64, 10000.0, 40.0, 4096, 32.0, 1.0, 0.707, 0.707, pos)
    # float32 angles up to 1.6e5 rad: a few ulps of the angle
    np.testing.assert_allclose(cos, hc, atol=2e-3)
    np.testing.assert_allclose(sin, hs, atol=2e-3)
    np.testing.assert_allclose(cos[:3], hc[:3], atol=1e-6)
    mscale = 0.1 * 0.707 * math.log(40) + 1
    assert cfg.mla_softmax_scale == pytest.approx(192 ** -0.5 * mscale ** 2)
    # the reference computes them on its own
    k = ref.dims(hf_of(cfg) | dict(first_k_dense_replace=1))
    rc, rs = ref._rope_tables(int(pos.max()) + 1, k)
    np.testing.assert_allclose(np.asarray(rc)[pos], hc, atol=2e-3)
    assert ref._softmax_scale(k) == pytest.approx(cfg.mla_softmax_scale)


# ---------------------------------------------------------------------------
# the fused decode kernel
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("page", [16, 8])
def test_mla_decode_kernel_matches_absorbed_dense(page):
    """Contexts of 1, one page, a whole 256-key block, one past it and a
    partial last block, and a retired lane on the scrap page: outputs
    against the absorbed computation over the appended pool, and the
    pool written back equal to the unfused append."""
    rng = np.random.default_rng(page)
    H, W, r = 4, 128, 40
    pos = np.array([0, page - 1, 255, 256, 300, 0])
    B, n_max = len(pos), 320 // page
    P = B * n_max + 1
    pool = rng.normal(size=(P, page, W)).astype(np.float32)
    perm = rng.permutation(P - 1).reshape(B, n_max)
    tabs = np.where(np.arange(n_max)[None] <= (pos // page)[:, None], perm,
                    P - 1).astype(np.int32)
    tabs[-1] = P - 1                                      # retired lane
    q = rng.normal(size=(B, H, W)).astype(np.float32)
    rows = rng.normal(size=(B, 1, W)).astype(np.float32)
    out, got = mla_decode_attention(
        jnp.asarray(q), jnp.asarray(rows), jnp.asarray(pool),
        jnp.asarray(tabs), jnp.asarray(pos, jnp.int32), scale=0.3,
        v_width=r, interpret=True)
    want_pool = jnp.asarray(pool).at[tabs[np.arange(B), pos // page],
                                     pos % page].set(rows[:, 0])
    live = np.arange(B - 1)
    np.testing.assert_array_equal(np.asarray(got)[tabs[live].ravel()],
                                  np.asarray(want_pool)[tabs[live].ravel()])
    for b in live:
        ctx = np.asarray(want_pool)[tabs[b]].reshape(-1, W)[:pos[b] + 1]
        s = q[b] @ ctx.T * 0.3
        p = np.exp(s - s.max(-1, keepdims=True))
        want = (p / p.sum(-1, keepdims=True)) @ ctx[:, :r]
        np.testing.assert_allclose(np.asarray(out[b]), want, atol=1e-5)


def test_row_append_matches_unfused_pair_append():
    from repro.kernels.paged_attention import paged_kv_append
    rng = np.random.default_rng(0)
    k = jnp.asarray(rng.normal(size=(9, 4, 2, 8)), jnp.float32)
    new = jnp.asarray(rng.normal(size=(6, 2, 8)), jnp.float32)
    table = jnp.asarray([5, 2, 7], jnp.int32)
    a, _ = paged_kv_append(k, k, new, new, table, 3, n=4)
    b = paged_row_append(k, new, table, 3, n=4)
    np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# configuration
# ---------------------------------------------------------------------------
def test_serve_as_from_json_is_the_registered_model_with_its_share():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "deepseek-v2-lite-ep8.json")) as f:
        spec = json.load(f)
    cfg = ModelConfig(**spec["serve_as"])
    assert cfg.unit_pattern == (("mla", "moe"),)
    assert cfg.prefix_pattern == (("mla", "mlp"),)
    assert cfg == dataclasses.replace(
        get_config(ARCH), name="deepseek-v2-lite-ep8", head_dim=128,
        experts_held=spec["n_routed_experts"],
        experts_first=spec["experts_held_first"])
    assert cfg.num_experts == spec["published"]["n_routed_experts"]
    assert cfg.param_count() == pytest.approx(3.11e9, rel=0.01)


def test_reduced_config_keeps_dense_layer_share_and_yarn():
    full, cfg = get_config(ARCH), _cfg()
    assert cfg.prefix_pattern == (("mla", "mlp"),) and cfg.num_units == 1
    assert (cfg.num_experts, cfg.held_experts) == (8, 4)
    assert cfg.yarn_factor == full.yarn_factor and cfg.rope_interleaved
    assert not cfg.norm_topk_prob and cfg.norm_eps == 1e-6
    assert Model(cfg).supports_paged()
    # the latent pool: one row of [c | k_rope | 0] per token a layer
    specs = Model(cfg).paged_cache_specs(5, 4)
    assert specs["prefix"][0]["lat"].shape == (5, 4, 128)
    assert specs["units"]["l0"]["lat"].shape == (1, 5, 4, 128)
    assert Model(cfg).kv_bytes_per_token() == 2 * 128 * 4
    published = Model(dataclasses.replace(full, dtype="bfloat16"))
    assert published.kv_bytes_per_token() == 27 * 640 * 2


def test_mla_refuses_tp_and_speculation():
    from repro.serving.jax_backend import PagedJaxBackend
    with pytest.raises(ValueError, match="one device"):
        PagedJaxBackend(ARCH, tp=2)
    be = PagedJaxBackend(ARCH, num_blocks=8, page=4, max_len=32)
    assert not be.supports_spec_decode
    with pytest.raises(ValueError, match="GQA attention only"):
        be.model.verify_paged(be.params, be.pages,
                              jnp.zeros((1, 2), jnp.int32),
                              jnp.zeros(1, jnp.int32),
                              jnp.ones(1, jnp.int32),
                              jnp.zeros((1, 8), jnp.int32))


# ---------------------------------------------------------------------------
# the backend: page moves and counters
# ---------------------------------------------------------------------------
def _request(rid, prompt, out):
    from repro.serving.request import Request, SLOSpec
    return Request(rid=rid, app="chatbot", arrival=0.0, prompt_len=prompt,
                   true_output_len=out, slo=SLOSpec("none"))


def test_latent_pages_swap_out_and_in_exactly():
    from repro.serving.jax_backend import PagedJaxBackend
    be = PagedJaxBackend(ARCH, num_blocks=16, page=4, max_len=64)
    be.prefill_chunk(_request(1, 10, 4), 0, 10, [3, 5, 7])
    be.step_time(10, [])
    before = be._gather_pages(np.array([3, 5, 7]))
    assert all(np.abs(a).sum() > 0 for a in jax.tree.leaves(before))
    be.kv_swap_out(1, [3, 5, 7], 10)
    be.pages = jax.tree.map(jnp.zeros_like, be.pages)
    be.kv_swap_in(1, [9, 2, 11])
    after = be._gather_pages(np.array([9, 2, 11]))
    jax.tree.map(np.testing.assert_array_equal, before, after)
    be.kv_copy_page(9, 12)
    jax.tree.map(np.testing.assert_array_equal,
                 be._gather_pages(np.array([9])),
                 be._gather_pages(np.array([12])))
    be2 = PagedJaxBackend(ARCH, num_blocks=16, page=4, max_len=64)
    be2.kv_import_pages(1, be.kv_export_pages(1, [9, 2, 11]), [4, 6, 8])
    jax.tree.map(np.testing.assert_array_equal, before,
                 be2._gather_pages(np.array([4, 6, 8])))


def test_moe_counters_report_routed_pairs_and_tokens():
    """``jax_moe_routed_total`` counts (token, held expert) pairs over the
    MoE layers and ``jax_moe_tokens_total`` the tokens that passed them,
    padded lanes and chunk rows excluded."""
    from repro.core.baselines import make_scheduler
    from repro.obs import MetricsRegistry
    from repro.serving.engine import EngineConfig, ServeEngine
    from repro.serving.jax_backend import PagedJaxBackend
    be = PagedJaxBackend(ARCH, num_blocks=32, page=4, max_len=64)
    obs = MetricsRegistry()
    eng = ServeEngine(be, make_scheduler("vllm"),
                      EngineConfig(max_batch=4, prefill_budget=16), obs=obs)
    reqs = [_request(i + 1, 6 + 5 * i, 7) for i in range(3)]
    for r in reqs:
        eng.enqueue("r", r)
    eng.run()
    got = {(n, dict(lab)["phase"]): c.total
           for (n, lab), c in obs._metrics.items()
           if n.startswith("jax_moe_")}
    cfg = be.cfg
    assert got[("jax_moe_tokens_total", "decode")] == 3 * 7
    assert got[("jax_moe_tokens_total", "prefill")] == 6 + 11 + 16
    most = cfg.num_units * cfg.top_k
    for ph in ("prefill", "decode"):
        routed = got[("jax_moe_routed_total", ph)]
        assert 0 < routed <= most * got[("jax_moe_tokens_total", ph)]

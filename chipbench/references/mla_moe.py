"""Plain float32 reference of a DeepSeek-V2 decoder (latent attention and
fine-grained experts), held to one expert-parallel device's share, and its
seeded weights.

The model, from the published description (arXiv:2405.04434 and the HF
``modeling_deepseek.py`` of DeepSeek-V2-Lite): pre-norm RMSNorm; multi-head
latent attention without q LoRA — per head q = h W_q split into a nope and a
rope part, the latent c = RMSNorm(h W_dkv), per-head keys k_nope = c W_uk and
values v = c W_uv, and one rope key k_rope = RoPE(h W_kr) shared by all
heads; rope on DeepSeek's interleaved pairs with YaRN's frequencies and
cos/sin scale; softmax scaled by (nope + rope)^-1/2 times YaRN's mscale
squared.  The first ``first_k_dense_replace`` layers have a SwiGLU MLP; the
others a softmax router over all routed experts, greedy top-k, the raw
probabilities (renormalised only with ``norm_topk_prob``) times
``routed_scaling_factor``, plus the shared experts as one SwiGLU MLP.  A
final RMSNorm and an untied output head.  It imports nothing of the
serving program.

Departures from the published description, all of them the deployment's
cut: this device holds ``n_routed_experts`` of the
``published["n_routed_experts"]`` routed experts (from
``experts_held_first``); the router still scores all of them, and the
absent experts' contributions are left out, as the program leaves them
out.  The held experts are computed densely for every token and weighted
by the router (zero for tokens not routed to them).  Attention and the
output head are computed in blocks of queries and vocabulary columns.
The attention is the un-absorbed form of the paper (keys and values
expanded per head from the latent), not the program's absorbed decode.

``make_weights`` builds the served weights on the device in one jitted
call from a seed, in the program's parameter layout and in bfloat16.
``fp8=True`` is the control: every weight and every matmul input rounded
to float8 e4m3 (per output channel and per row scales), the nearest
precision below bfloat16.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512             # query rows per attention block
V_BLOCK = 25600           # vocabulary columns per output-head block
SEQ_BUCKET = 256          # sequences are padded to a multiple of this


def dims(hf: dict) -> dict:
    d = hf["hidden_size"]
    H = hf["num_attention_heads"]
    rs = hf["rope_scaling"] or {}
    return dict(
        d=d, H=H, KV=hf["num_key_value_heads"],
        hd=hf.get("head_dim") or d // H, ff=hf["intermediate_size"],
        L=hf["num_hidden_layers"], V=hf["vocab_size"],
        theta=float(hf["rope_theta"]), eps=float(hf["rms_norm_eps"]),
        r=hf["kv_lora_rank"], nope=hf["qk_nope_head_dim"],
        rope=hf["qk_rope_head_dim"], vd=hf["v_head_dim"],
        fe=hf["moe_intermediate_size"], E=hf["n_routed_experts"],
        E_all=hf.get("published", {}).get("n_routed_experts",
                                          hf["n_routed_experts"]),
        e0=hf.get("experts_held_first", 0), k=hf["num_experts_per_tok"],
        S=hf["n_shared_experts"], dense=hf["first_k_dense_replace"],
        norm_topk=bool(hf["norm_topk_prob"]),
        route_scale=float(hf["routed_scaling_factor"]),
        yarn=tuple(sorted(rs.items())) if rs.get("type") == "yarn" else ())


def _vocab_padded(v: int) -> int:
    return -(-v // 256) * 256


def _attn_shapes(k, lead=()):
    d, H, r, nope, rope, vd = (k[n] for n in ("d", "H", "r", "nope", "rope",
                                              "vd"))
    return dict(ln1=lead + (d,), w_q=lead + (d, H, nope + rope),
                w_dkv=lead + (d, r), kv_ln=lead + (r,),
                w_kr=lead + (d, rope), w_uk=lead + (r, H, nope),
                w_uv=lead + (r, H, vd), wo=lead + (H, vd, d),
                ln2=lead + (d,))


def weight_shapes(hf: dict) -> dict:
    k = dims(hf)
    d, ff, fe, E, S = k["d"], k["ff"], k["fe"], k["E"], k["S"]
    n_moe = k["L"] - k["dense"]
    lead = (n_moe,)
    dense = dict(_attn_shapes(k), w_gate=(d, ff), w_up=(d, ff),
                 w_down=(ff, d))
    moe = dict(_attn_shapes(k, lead), router=lead + (d, k["E_all"]),
               w_gate=lead + (E, d, fe), w_up=lead + (E, d, fe),
               w_down=lead + (E, fe, d), shared_gate=lead + (d, S * fe),
               shared_up=lead + (d, S * fe), shared_down=lead + (S * fe, d))
    return {"embed": (k["V"], d),
            "prefix": {f"l{i}": dense for i in range(k["dense"])},
            "units": {"l0": moe}, "final_norm": (d,),
            "lm_head": (d, _vocab_padded(k["V"]))}


def _is_shape(x):
    return isinstance(x, tuple) and all(isinstance(i, int) for i in x)


def make_weights(hf: dict, key):
    """bf16 weights from ``key`` in one jitted call: projections N(0, 0.02),
    norm gains 1 + N(0, 0.1)."""
    shapes = weight_shapes(hf)
    leaves, tree = jax.tree.flatten(shapes, is_leaf=_is_shape)
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(shapes,
                                                  is_leaf=_is_shape)[0]]

    def build(key):
        out = []
        for i, (shape, path) in enumerate(zip(leaves, paths)):
            z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.bfloat16)
            if "ln" in path or "norm" in path:
                out.append((1.0 + 0.1 * z).astype(jnp.bfloat16))
            else:
                out.append((0.02 * z).astype(jnp.bfloat16))
        return jax.tree.unflatten(tree, out)

    return jax.jit(build)(key)


# ---------------------------------------------------------------------------
# reference forward
# ---------------------------------------------------------------------------
def _q8(x, axis):
    """Round to float8 e4m3 with a scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, fp8, w_axes=0):
    """x (T, K) @ w (K, ...) in float32; ``fp8`` rounds both inputs."""
    if fp8:
        x = _q8(x, -1)
        w = _q8(w, w_axes)
    return jnp.tensordot(x, w, axes=1)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope_tables(T, k):
    """cos, sin (T, rope/2) of DeepSeek's YaRN rope."""
    dim, theta = k["rope"], k["theta"]
    inv = 1.0 / theta ** (np.arange(0, dim, 2) / dim)
    scale = 1.0
    y = dict(k["yarn"])
    if y:
        f, orig = y["factor"], y["original_max_position_embeddings"]

        def corr(rot):
            return dim * math.log(orig / (rot * 2 * math.pi)) \
                / (2 * math.log(theta))

        lo = max(math.floor(corr(y["beta_fast"])), 0)
        hi = min(math.ceil(corr(y["beta_slow"])), dim - 1)
        hi = hi + 0.001 if lo == hi else hi
        ramp = np.clip((np.arange(dim // 2) - lo) / (hi - lo), 0, 1)
        inv = inv / f * ramp + inv * (1 - ramp)

        def msc(m):
            return 0.1 * m * math.log(f) + 1.0 if f > 1 else 1.0

        scale = msc(y["mscale"]) / msc(y["mscale_all_dim"])
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv.astype(np.float32)
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale


def _rope(x, cos, sin):
    """Rotate pairs (x[2i], x[2i+1]) by angle i; the result is laid out as
    HF's DeepseekV2 ``apply_rotary_pos_emb`` leaves it (evens, then
    odds)."""
    x1, x2 = x[..., 0::2], x[..., 1::2]
    if x.ndim == 3:
        cos, sin = cos[:, None], sin[:, None]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _softmax_scale(k):
    s = (k["nope"] + k["rope"]) ** -0.5
    y = dict(k["yarn"])
    if y and y.get("mscale_all_dim") and y["factor"] > 1:
        m = 0.1 * y["mscale_all_dim"] * math.log(y["factor"]) + 1.0
        s = s * m * m
    return s


def _attention(x, p, k, fp8):
    T = x.shape[0]
    H, nope, eps = k["H"], k["nope"], k["eps"]
    h = _rms(x, p["ln1"], eps)
    q = _mm(h, p["w_q"], fp8)                               # (T, H, qk)
    c = _rms(_mm(h, p["w_dkv"], fp8), p["kv_ln"], eps)      # (T, r)
    cos, sin = _rope_tables(T, k)
    q_rope = _rope(q[..., nope:], cos, sin)
    k_rope = _rope(_mm(h, p["w_kr"], fp8), cos, sin)        # (T, rope)
    k_nope = _mm(c, p["w_uk"], fp8)                         # (T, H, nope)
    v = _mm(c, p["w_uv"], fp8)                              # (T, H, vd)
    scale = _softmax_scale(k)
    pos = jnp.arange(T)
    outs = []
    for q0 in range(0, T, Q_BLOCK):
        qn = q[q0:q0 + Q_BLOCK, :, :nope]
        s = (jnp.einsum("qhn,thn->hqt", qn, k_nope)
             + jnp.einsum("qhp,tp->hqt", q_rope[q0:q0 + Q_BLOCK], k_rope))
        causal = pos[None, :] <= (q0 + jnp.arange(qn.shape[0]))[:, None]
        s = jnp.where(causal[None], s * scale, -jnp.inf)
        outs.append(jnp.einsum("hqt,thv->qhv", jax.nn.softmax(s, -1), v))
    o = jnp.concatenate(outs, 0).reshape(T, -1)
    return x + _mm(o, p["wo"].reshape(o.shape[1], -1), fp8)


def _swiglu(h, wg, wu, wd, fp8):
    return _mm(jax.nn.silu(_mm(h, wg, fp8)) * _mm(h, wu, fp8), wd, fp8)


@functools.partial(jax.jit, static_argnames=("k", "fp8"))
def _dense_layer(x, p, *, k, fp8):
    k = dict(k)
    p = {n: a.astype(jnp.float32) for n, a in p.items()}
    x = _attention(x, p, k, fp8)
    h = _rms(x, p["ln2"], k["eps"])
    return x + _swiglu(h, p["w_gate"], p["w_up"], p["w_down"], fp8)


@functools.partial(jax.jit, static_argnames=("k", "fp8"))
def _moe_layer(x, units, i, *, k, fp8):
    k = dict(k)
    p = {n: a[i].astype(jnp.float32) for n, a in units["l0"].items()}
    x = _attention(x, p, k, fp8)
    h = _rms(x, p["ln2"], k["eps"])
    probs = jax.nn.softmax(_mm(h, p["router"], fp8), -1)    # (T, E_all)
    topv, topi = jax.lax.top_k(probs, k["k"])
    if k["norm_topk"]:
        topv = topv / jnp.sum(topv, -1, keepdims=True)
    topv = topv * k["route_scale"]
    y = _swiglu(h, p["shared_gate"], p["shared_up"], p["shared_down"], fp8)
    for e in range(k["E"]):
        gate = jnp.sum(jnp.where(topi == k["e0"] + e, topv, 0.0), -1)
        y = y + gate[:, None] * _swiglu(h, p["w_gate"][e], p["w_up"][e],
                                        p["w_down"][e], fp8)
    return x + y


@functools.partial(jax.jit, static_argnames=("eps",))
def _final(x, g, eps):
    return _rms(x, g.astype(jnp.float32), eps)


def hidden(hf: dict, w, tokens, fp8: bool = False):
    """Final normed hidden states (T, d) float32 for one token sequence."""
    k = dims(hf)
    T = len(tokens)
    Tp = -(-T // SEQ_BUCKET) * SEQ_BUCKET
    toks = np.zeros(Tp, np.int32)
    toks[:T] = tokens
    x = jnp.take(w["embed"], jnp.asarray(toks), axis=0).astype(jnp.float32)
    kk = tuple(sorted(k.items()))
    for i in range(k["dense"]):
        x = _dense_layer(x, w["prefix"][f"l{i}"], k=kk, fp8=fp8)
    for i in range(k["L"] - k["dense"]):
        x = _moe_layer(x, w["units"], jnp.int32(i), k=kk, fp8=fp8)
    return _final(x, w["final_norm"], k["eps"])[:T]


@functools.partial(jax.jit, static_argnames=("V", "fp8"))
def _head_block(h, head, v0, *, V, fp8):
    """Logits of columns [v0, v0 + V_BLOCK) (float32, -inf past V)."""
    w = jax.lax.dynamic_slice_in_dim(head, v0, V_BLOCK, 1).astype(jnp.float32)
    z = _mm(h, w, fp8)
    col = v0 + jnp.arange(V_BLOCK)
    return jnp.where(col[None, :] < V, z, -jnp.inf)


def head_stats(hf: dict, w, h, fp8: bool = False):
    """(max logit, argmax) per row of ``h`` over the whole vocabulary."""
    V = dims(hf)["V"]
    head = w["lm_head"]
    pad = (-head.shape[1]) % V_BLOCK
    if pad:
        head = jnp.pad(head, ((0, 0), (0, pad)))
    best = jnp.full(h.shape[0], -jnp.inf)
    arg = jnp.zeros(h.shape[0], jnp.int32)
    for v0 in range(0, head.shape[1], V_BLOCK):
        z = _head_block(h, head, jnp.int32(v0), V=V, fp8=fp8)
        m = jnp.max(z, -1)
        a = jnp.argmax(z, -1).astype(jnp.int32) + v0
        take = m > best
        arg = jnp.where(take, a, arg)
        best = jnp.where(take, m, best)
    return np.asarray(best), np.asarray(arg)


def logits_at(w, h, tokens):
    """Logit of ``tokens[i]`` at row i (float32)."""
    cols = jnp.take(w["lm_head"], jnp.asarray(tokens), axis=1)
    return np.asarray(jnp.sum(h * cols.T.astype(jnp.float32), -1))


def gaps(hf: dict, w, prompt, served, fp8_control: bool = False):
    """Per served token: how far its float32 reference logit lies below the
    reference's best at that position.  With ``fp8_control`` also the same
    gap for the token the fp8 forward puts first.  Returns (served_gaps,
    control_gaps or None)."""
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    seq = np.concatenate([prompt, served[:-1]])
    rows = slice(len(prompt) - 1, len(seq))
    with jax.default_matmul_precision("highest"):
        h = hidden(hf, w, seq)[rows]
        best, _ = head_stats(hf, w, h)
        got = logits_at(w, h, served)
        ctrl = None
        if fp8_control:
            h8 = hidden(hf, w, seq, fp8=True)[rows]
            _, arg8 = head_stats(hf, w, h8, fp8=True)
            ctrl = best - logits_at(w, h, arg8)
    return best - got, ctrl

"""Plain float32 reference of a dense decoder block, and its seeded weights.

The block, from the published descriptions (Llama-style, as Yi-34B):
pre-norm RMSNorm, grouped-query attention with rotary positions on the
whole head (half-split rotation, inverse frequency theta^(-2i/d)), softmax
scaled by head_dim^-1/2, a SwiGLU MLP, a final RMSNorm and an untied output
head.  It imports nothing of the serving program.

``make_weights`` builds the served weights on the device in one jitted call
from a seed, in the program's parameter layout and in bfloat16 (the dtype
the configuration states).  The reference reads those same arrays, casts
them to float32 layer by layer and computes at ``highest`` matmul precision.

``fp8=True`` is the control: the same forward with every weight and every
matmul input rounded to float8 e4m3 (per output channel and per row scales,
as an fp8 serving path would), the nearest precision below bfloat16.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512             # query rows per attention block
V_BLOCK = 32000           # vocabulary columns per output-head block
SEQ_BUCKET = 256          # sequences are padded to a multiple of this


def dims(hf: dict) -> dict:
    d = hf["hidden_size"]
    H = hf["num_attention_heads"]
    return dict(d=d, H=H, KV=hf["num_key_value_heads"],
                hd=hf.get("head_dim") or d // H, ff=hf["intermediate_size"],
                L=hf["num_hidden_layers"], V=hf["vocab_size"],
                theta=float(hf["rope_theta"]),
                eps=float(hf.get("rms_norm_eps", hf.get("norm_eps"))))


def _vocab_padded(v: int) -> int:
    return -(-v // 256) * 256


def weight_shapes(hf: dict) -> dict:
    k = dims(hf)
    d, H, KV, hd, ff, L, V = (k[n] for n in ("d", "H", "KV", "hd", "ff",
                                              "L", "V"))
    layer = dict(ln1=(L, d), wq=(L, d, H, hd), wk=(L, d, KV, hd),
                 wv=(L, d, KV, hd), wo=(L, H, hd, d), ln2=(L, d),
                 w_gate=(L, d, ff), w_up=(L, d, ff), w_down=(L, ff, d))
    return {"embed": (V, d), "prefix": {}, "units": {"l0": layer},
            "final_norm": (d,), "lm_head": (d, _vocab_padded(V))}


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
            k = jax.random.fold_in(key, i)
            z = jax.random.normal(k, shape, jnp.bfloat16)
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


def _mm(x, w, fp8, w_axes):
    """x (T, K) @ w (K, ...) in float32; ``fp8`` rounds both inputs."""
    if fp8:
        x = _q8(x, -1)
        w = _q8(w, w_axes)
    return jnp.tensordot(x, w, axes=1)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (np.arange(half) / half))
    ang = pos[:, None].astype(jnp.float32) * inv            # (T, half)
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


@functools.partial(jax.jit, static_argnames=("k", "fp8"))
def _layer(x, units, i, *, k, fp8):
    k = dict(k)
    f32 = lambda a: a[i].astype(jnp.float32)
    p = {n: f32(a) for n, a in units["l0"].items()}
    T = x.shape[0]
    H, KV, hd, eps = k["H"], k["KV"], k["hd"], k["eps"]
    G = H // KV
    pos = jnp.arange(T)
    h = _rms(x, p["ln1"], eps)
    q = _rope(_mm(h, p["wq"], fp8, 0), pos, k["theta"])     # (T, H, hd)
    kk = _rope(_mm(h, p["wk"], fp8, 0), pos, k["theta"])    # (T, KV, hd)
    v = _mm(h, p["wv"], fp8, 0)
    outs = []
    for q0 in range(0, T, Q_BLOCK):
        qb = q[q0:q0 + Q_BLOCK].reshape(-1, KV, G, hd)
        s = jnp.einsum("qkgd,tkd->kgqt", qb, kk) * hd ** -0.5
        causal = pos[None, :] <= (q0 + jnp.arange(qb.shape[0]))[:, None]
        s = jnp.where(causal, s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("kgqt,tkd->qkgd", w, v).reshape(-1, H, hd))
    o = jnp.concatenate(outs, 0).reshape(T, H * hd)
    x = x + _mm(o, p["wo"].reshape(H * hd, -1), fp8, 0)
    h = _rms(x, p["ln2"], eps)
    g = _mm(h, p["w_gate"], fp8, 0)
    u = _mm(h, p["w_up"], fp8, 0)
    return x + _mm(jax.nn.silu(g) * u, p["w_down"], fp8, 0)


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
    kk = tuple((n, k[n]) for n in ("H", "KV", "hd", "eps", "theta"))
    for i in range(k["L"]):
        x = _layer(x, w["units"], jnp.int32(i), k=kk, fp8=fp8)
    return _final(x, w["final_norm"], k["eps"])[:T]


@functools.partial(jax.jit, static_argnames=("V", "fp8"))
def _head_block(h, head, v0, *, V, fp8):
    """Logits of columns [v0, v0 + V_BLOCK) (float32, -inf past V)."""
    w = jax.lax.dynamic_slice_in_dim(head, v0, V_BLOCK, 1).astype(jnp.float32)
    z = _mm(h, w, fp8, 0)
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

"""Operations and bytes that a DeepSeek-V2 step needs on one
expert-parallel device, from the configuration.

These count what the algorithm must do, not what the compiled program does
(the latent pool's lane padding, padded lanes and row tiles are left out),
so no change to the program can move them.  ``k`` is
``references.mla_moe.dims`` of the configuration; weights and the latent
cache are bfloat16 (2 bytes).
"""

from __future__ import annotations

from typing import Iterable, Tuple

BYTES = 2


def mla_params(k: dict) -> int:
    """Projection weights of one latent-attention layer (no q LoRA)."""
    d, H, r, nope, rope, vd = (k[n] for n in ("d", "H", "r", "nope", "rope",
                                              "vd"))
    return d * H * (nope + rope) + d * r + d * rope + r * H * (nope + vd) \
        + H * vd * d


def expert_params(k: dict) -> int:
    """One routed expert's SwiGLU weights."""
    return 3 * k["d"] * k["fe"]


def routed_share(k: dict) -> float:
    """Held experts a token is routed to on average: top-k of all routed
    experts, of which this device holds E (0.75 for 6 of 64, 8 held)."""
    return k["k"] * k["E"] / k["E_all"]


def moe_layers(k: dict) -> int:
    return k["L"] - k["dense"]


def decode_mla(k: dict, decode_seqs: int, decode_ctx_total: int):
    """(flops, bytes) of absorbed MLA decode attention for one micro-step
    over all layers: each sequence appends its latent row and all H heads
    score its ctx + 1 positions against the (r + rope) row and average the
    r-wide latents; the rows are read once, plus the appended row, the
    absorbed query (H x (r + rope)) and the output (H x r)."""
    L, H, r, rope = k["L"], k["H"], k["r"], k["rope"]
    positions = decode_ctx_total + decode_seqs
    flops = 2 * H * ((r + rope) + r) * positions * L
    row = (r + rope) * BYTES
    byts = L * (row * positions
                + decode_seqs * (row + H * (r + rope) * BYTES
                                 + H * r * BYTES))
    return flops, byts


def decode_moe(k: dict, tokens: int):
    """(flops, bytes) of the held experts for one micro-step of ``tokens``
    tokens over all MoE layers: the uniform-router expectation (which
    random weights give) of E (1 - (1 - k/E_all)^T) experts touched, each
    read once, and routed_share x T rows through an expert (their input
    and output rows read and written once)."""
    if tokens <= 0:
        return 0, 0
    touched = k["E"] * (1.0 - (1.0 - k["k"] / k["E_all"]) ** tokens)
    rows = routed_share(k) * tokens
    flops = 2 * expert_params(k) * rows * moe_layers(k)
    byts = moe_layers(k) * (touched * expert_params(k) * BYTES
                            + 2 * rows * k["d"] * BYTES)
    return flops, byts


def token_flops(k: dict) -> float:
    """Matmul FLOPs of one token through every layer's weights on this
    device (attention projections, layer 0's MLP, the shared experts, the
    router and routed_share routed experts), without the output head."""
    d = k["d"]
    f = 2 * mla_params(k) * k["L"]
    f += 2 * 3 * d * k["ff"] * k["dense"]
    f += moe_layers(k) * 2 * (k["S"] * expert_params(k) + d * k["E_all"]
                              + routed_share(k) * expert_params(k))
    return f


def step_flops(k: dict, chunks: Iterable[Tuple[int, int]], decode_seqs: int,
               decode_ctx_total: int) -> float:
    """Model FLOPs of one engine step: its prefill chunks (causal attention
    of the paper's un-absorbed form: per query-key pair H (nope + rope)
    for the scores and H vd for the values, 2 FLOPs a multiply-add) and
    one decoded token per decoding sequence (absorbed attention over
    ctx + 1 positions, the absorption's q W_uk and o W_uv, and the output
    head)."""
    H, r, nope, rope, vd, L = (k[n] for n in ("H", "r", "nope", "rope", "vd",
                                              "L"))
    f = 0.0
    for start, n in chunks:
        pos_sum = n * start + n * (n + 1) // 2
        f += token_flops(k) * n + 2 * H * (nope + rope + vd) * pos_sum * L
    if decode_seqs:
        f += decode_seqs * (token_flops(k) + 2 * k["d"] * k["V"]
                            + 2 * H * r * (nope + vd) * L)
        f += decode_mla(k, decode_seqs, decode_ctx_total)[0]
    return f

"""Operations and bytes that the algorithm needs, from the configuration.

These count what a dense decoder step must do, not what the compiled
program does, so no change to the program can move them.  ``k`` is
``references.dense_gqa.dims`` of the configuration; weights and the KV
cache are bfloat16 (2 bytes).
"""

from __future__ import annotations

from typing import Iterable, Tuple

BYTES = 2


def matmul_params(k: dict) -> int:
    """Weights a token multiplies through per layer (no embedding)."""
    d, H, KV, hd, ff = k["d"], k["H"], k["KV"], k["hd"], k["ff"]
    return d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * ff


def attn_flops(k: dict, ctx: int) -> int:
    """Scores and weighted values of one query over ``ctx`` positions, all
    layers: 2 matmuls of 2 flops per multiply-add."""
    return 4 * k["H"] * k["hd"] * ctx * k["L"]


def prefill_chunk_flops(k: dict, start: int, n: int) -> int:
    """A prompt chunk of ``n`` tokens after ``start`` resident ones, with
    causal attention (token at position p sees p + 1 positions).  No output
    head: the prompt's last logits come from the first decode step."""
    pos_sum = n * start + n * (n + 1) // 2
    return 2 * matmul_params(k) * k["L"] * n + attn_flops(k, 1) * pos_sum


def step_flops(k: dict, chunks: Iterable[Tuple[int, int]], decode_seqs: int,
               decode_ctx_total: int) -> int:
    """Model FLOPs of one engine step: its prefill chunks and one decoded
    token per decoding sequence (contexts summed to ``decode_ctx_total``)."""
    f = sum(prefill_chunk_flops(k, s, n) for s, n in chunks)
    if decode_seqs:
        f += decode_seqs * (2 * matmul_params(k) * k["L"]
                            + 2 * k["d"] * k["V"])
        f += attn_flops(k, decode_ctx_total + decode_seqs)
    return f


def decode_attn(k: dict, decode_seqs: int, decode_ctx_total: int):
    """(flops, bytes) of paged decode attention for one micro-step over all
    layers: each sequence appends its new key and value and attends over
    ctx + 1 positions, reading their keys and values once, reading its
    query and writing its output."""
    L, H, KV, hd = k["L"], k["H"], k["KV"], k["hd"]
    positions = decode_ctx_total + decode_seqs
    flops = 4 * H * hd * positions * L
    kv = 2 * KV * hd * BYTES
    byts = L * (kv * positions + kv * decode_seqs
                + 2 * H * hd * BYTES * decode_seqs)
    return flops, byts


def least_time(flops: float, byts: float, peak: dict):
    """(seconds, bound) of the roofline: the larger of compute and memory
    time at the chip's peaks."""
    tc = flops / peak["bf16_flops"]
    tm = byts / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")

"""Model step, whole, for latent-attention MoE configurations: model FLOPs
of this device's share that the traced steps' prefill chunks and decoded
tokens need (``cost_mla_moe.step_flops``: attention over their contexts,
layer 0's MLP, the shared experts, 0.75 routed experts a token a MoE
layer, and the output head) over the traced window's wall time times the
chip's peak."""

from chipbench import cost_mla_moe


def read(ctx):
    a, b = ctx["traced"]
    k = ctx["k"]
    if "E_all" not in k:
        return None
    f = sum(cost_mla_moe.step_flops(k, s.chunks, s.decode_seqs, s.decode_ctx)
            for s in ctx["steps"] if a <= s.t0 and s.t1 <= b)
    win = ctx["trace"]["window_s"]
    return 100.0 * f / (win * ctx["peak"]["bf16_flops"]) if f else None

"""Model step, whole: model FLOPs that the traced steps' prefill chunks and
decoded tokens need (with attention over their contexts, counted from the
configuration) over the traced window's wall time times the chip's peak."""

from chipbench import cost


def read(ctx):
    a, b = ctx["traced"]
    k = ctx["k"]
    f = sum(cost.step_flops(k, s.chunks, s.decode_seqs, s.decode_ctx)
            for s in ctx["steps"] if a <= s.t0 and s.t1 <= b)
    win = ctx["trace"]["window_s"]
    return 100.0 * f / (win * ctx["peak"]["bf16_flops"]) if f else None

"""Kernel, absorbed MLA decode attention (``mla_decode_attention``): the
least time the traced decode steps' latent attention needs at the chip's
peaks (the larger of FLOPs over peak FLOP/s and bytes over HBM bandwidth,
from the contexts in the step log, ``cost_mla_moe.decode_mla``) over the
summed device time of the decode programs' ``mla_decode_attention``
operations.  A program without that kernel gives no number; decode
programs that did not run once per decode step stop the run here."""

import re

from chipbench import cost, cost_mla_moe
from chipbench.trace_reduce import check_runs

DECODE = r"decode|^jit__unknown$"
OP = re.compile(r"^jit_decode_scan/mla_decode_attention[.\w-]* \[pallas\]$")


def read(ctx):
    a, b = ctx["traced"]
    red = ctx["trace"]
    steps = [s for s in ctx["steps"]
             if a <= s.t0 and s.t1 <= b and s.decode_seqs]
    dev = sum(v for op, v in red["op_s"].items() if OP.match(op))
    if not steps or not dev:
        return None
    check_runs(red, DECODE, len(steps), "kernel.mla_decode_roofline")
    need = sum(cost.least_time(*cost_mla_moe.decode_mla(
        ctx["k"], s.decode_seqs, s.decode_ctx), ctx["peak"])[0]
        for s in steps)
    return 100.0 * need / dev

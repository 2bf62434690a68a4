"""Kernel, the held experts' grouped FFN (``moe_gmm``) in decode: the least
time the traced decode steps' held experts need at the chip's peaks
(``cost_mla_moe.decode_moe``: the weights of the experts that T tokens are
expected to touch, 8 (1 - (1 - 6/64)^T) for 8 of 64 held and top-6, and
0.75 routed rows a token; that is the uniform-router expectation, which
random weights give, and not a count of what the run routed) over the
summed device time of the decode programs' ``moe_gmm`` operations.  A
program without that kernel gives no number; decode programs that did not
run once per decode step stop the run here."""

import re

from chipbench import cost, cost_mla_moe
from chipbench.trace_reduce import check_runs

DECODE = r"decode|^jit__unknown$"
OP = re.compile(r"^jit_decode_scan/moe_gmm[.\w-]* \[pallas\]$")


def read(ctx):
    a, b = ctx["traced"]
    red = ctx["trace"]
    steps = [s for s in ctx["steps"]
             if a <= s.t0 and s.t1 <= b and s.decode_seqs]
    dev = sum(v for op, v in red["op_s"].items() if OP.match(op))
    if not steps or not dev:
        return None
    check_runs(red, DECODE, len(steps), "kernel.moe_gmm_roofline")
    need = sum(cost.least_time(*cost_mla_moe.decode_moe(
        ctx["k"], s.decode_seqs), ctx["peak"])[0] for s in steps)
    return 100.0 * need / dev

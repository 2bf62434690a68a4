"""Model step, experts: device time of the decode programs' ``moe_gmm``
operations (the held experts' grouped FFN) in the traced window over the
decode micro-steps, in milliseconds.  A program without that kernel gives
no number; decode programs that did not run once per decode step stop the
run here."""

import re

from chipbench.trace_reduce import check_runs

DECODE = r"decode|^jit__unknown$"
OP = re.compile(r"^jit_decode_scan/moe_gmm[.\w-]* \[pallas\]$")


def read(ctx):
    a, b = ctx["traced"]
    n = sum(1 for s in ctx["steps"] if a <= s.t0 and s.t1 <= b
            and s.decode_seqs)
    dev = sum(v for op, v in ctx["trace"]["op_s"].items() if OP.match(op))
    if not n or not dev:
        return None
    check_runs(ctx["trace"], DECODE, n, "step.moe_ms")
    return 1e3 * dev / n

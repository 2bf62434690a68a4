"""Model step, prefill: device time of the prefill programs in the traced
window over the prompt tokens the traced steps prefilled, in microseconds
(padding the chunks to their buckets is part of the cost).  A step runs
one prefill program per bucket its chunks fall in; unless the programs
matched ran that often, the run stops here."""

from chipbench.serve import _bucket
from chipbench.trace_reduce import check_runs, seconds_matching

PREFILL = r"prefill"


def read(ctx):
    a, b = ctx["traced"]
    steps = [s for s in ctx["steps"] if a <= s.t0 and s.t1 <= b]
    toks = sum(s.prefill_tokens for s in steps)
    if not toks:
        return None
    runs = sum(len({_bucket(n, 8) for _, n in s.chunks}) for s in steps)
    check_runs(ctx["trace"], PREFILL, runs, "step.prefill_us_per_tok")
    return 1e6 * seconds_matching(ctx["trace"], "program_s", PREFILL) / toks

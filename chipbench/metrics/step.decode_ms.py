"""Model step, decode: device time of the decode programs in the traced
window over the decode micro-steps they ran, in milliseconds.  The decode
program is the backend's jitted ``lax.scan`` over micro-steps, a
``functools.partial`` that JAX names ``jit__unknown``; a name with
"decode" in it is matched too.  Unless the programs matched ran once per
decode step that the harness saw, the run stops here: a renamed decode
program, or a second one of that name, would change what is measured."""

from chipbench.trace_reduce import check_runs, seconds_matching

DECODE = r"decode|^jit__unknown$"


def read(ctx):
    a, b = ctx["traced"]
    n = sum(1 for s in ctx["steps"] if a <= s.t0 and s.t1 <= b
            and s.decode_seqs)
    if not n:
        return None
    check_runs(ctx["trace"], DECODE, n, "step.decode_ms")
    return 1e3 * seconds_matching(ctx["trace"], "program_s", DECODE) / n

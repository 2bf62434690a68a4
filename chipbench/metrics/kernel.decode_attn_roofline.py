"""Kernel, fused paged decode attention (``fused_decode_attention``): the
least time the traced steps' decode attention needs at the chip's peaks
(the larger of FLOPs over peak FLOP/s and bytes over HBM bandwidth, from
the contexts in the step log; memory bounds it at every context these
cells reach) over the kernel's summed device time.  The kernel is the one
Pallas call (TPU custom call) in each compiled decode program; a decode
program with another count of Pallas calls, or decode programs that did
not run once per decode step, stop the run here."""

from chipbench import cost
from chipbench.trace_reduce import check_runs, program_name, \
    programs_matching

DECODE = r"decode|^jit__unknown$"


def read(ctx):
    a, b = ctx["traced"]
    k, peak, red = ctx["k"], ctx["peak"], ctx["trace"]
    steps = [s for s in ctx["steps"]
             if a <= s.t0 and s.t1 <= b and s.decode_seqs]
    if not steps:
        return None
    check_runs(red, DECODE, len(steps), "kernel.decode_attn_roofline")
    progs = programs_matching(red, DECODE)
    if any(len(ks) != 1 for ks in progs.values()):
        raise ValueError("kernel.decode_attn_roofline: decode programs with "
                         f"other than one Pallas call: {progs}")
    ops = {f"{program_name(m)}/{ks[0]}" for m, ks in progs.items()}
    dev = sum(red["op_s"].get(op, 0.0) for op in ops)
    need = 0.0
    for s in steps:
        f, by = cost.decode_attn(k, s.decode_seqs, s.decode_ctx)
        need += cost.least_time(f, by, peak)[0]
    return 100.0 * need / dev

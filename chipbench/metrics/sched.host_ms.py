"""Scheduler, host time: the program's ``sched.refine``, ``sched.group``
and ``sched.fill`` spans (estimate refinement, margin groups, greedy fill
up to the decision) inside the traced engine steps, summed, over the
steps, in milliseconds.  A program that marks no phase gives no number;
one that marks phases but none of these stops the run here."""

from chipbench.host_spans import per_step_ms

NAMES = ("sched.refine", "sched.group", "sched.fill")


def read(ctx):
    return per_step_ms(ctx, NAMES, "sched.host_ms")

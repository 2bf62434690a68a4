"""Model step, host side: the program's ``backend.stage`` (packing prefill
lanes and decode staging arrays), ``backend.launch`` (the jitted calls
until they return: argument transfer and enqueue) and ``backend.unpack``
(appending sampled tokens) spans inside the traced engine steps, summed,
over the steps, in milliseconds.  A program that marks no phase gives no
number; one that marks phases but none of these stops the run here."""

from chipbench.host_spans import per_step_ms

NAMES = ("backend.stage", "backend.launch", "backend.unpack")


def read(ctx):
    return per_step_ms(ctx, NAMES, "step.stage_ms")

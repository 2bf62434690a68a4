"""Engine, host time: mean over the traced engine steps (the harness's
``engine.step_once`` spans) of the step's duration less the program's
``backend.wait`` spans inside it (the host blocked on the device), in
milliseconds: scheduling, staging, launch and bookkeeping on the host.
A program that marks no phase gives no number; one that marks phases but
never ``backend.wait`` stops the run here."""

from chipbench.host_spans import host_ms


def read(ctx):
    return host_ms(ctx, "engine.host_ms")

"""Engine: wall time of the window over the engine steps started in it
(harness timer around ``step_once``), in milliseconds."""


def read(ctx):
    w0, w1 = ctx["window"]
    n = sum(1 for s in ctx["steps"] if w0 <= s.t0 < w1)
    return 1e3 * (w1 - w0) / n if n else None

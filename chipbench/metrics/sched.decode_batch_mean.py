"""Scheduler batch occupancy: mean number of sequences per decode
micro-step, over the window's steps that decoded (the engine's step log)."""


def read(ctx):
    w0, w1 = ctx["window"]
    n = [s.decode_seqs for s in ctx["steps"]
         if w0 <= s.t0 and s.t1 <= w1 and s.decode_seqs]
    return sum(n) / len(n) if n else None

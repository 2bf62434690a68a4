"""KV manager: pages in use over the pool (BlockManager, reclaimable cached
pages counted free), averaged over the window's steps."""


def read(ctx):
    w0, w1 = ctx["window"]
    u = [s.pool_used for s in ctx["steps"] if w0 <= s.t0 and s.t1 <= w1]
    return 100.0 * sum(u) / len(u) if u else None

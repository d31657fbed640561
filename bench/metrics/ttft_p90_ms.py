"""90th percentile over all requests of the window of the time to first
token, each timed from when it was due (open loop).  A request that never
produced a token counts as the window's whole length."""
import statistics


def read(ctx):
    if ctx.kind != "serve" or len(ctx.requests) < 10:
        return None
    v = []
    for r in ctx.requests:
        o = ctx.outputs.get(r.rid)
        v.append(o.t_first_token - r.arrival if o and o.tokens
                 else ctx.window_s)
    return statistics.quantiles(v, n=10)[8] * 1e3

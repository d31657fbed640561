"""90th percentile over the window's requests of each request's time per
output token after its first: (t_done - t_first_token) / (tokens - 1)."""
import statistics


def read(ctx):
    if ctx.kind != "serve":
        return None
    v = [o.tpot for o in ctx.outputs.values() if len(o.tokens) > 1]
    if len(v) < 10:
        return None
    return statistics.quantiles(v, n=10)[8] * 1e3

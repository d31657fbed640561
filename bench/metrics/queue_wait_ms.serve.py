"""Median time a request waited for a decode slot and its pages:
t_admitted - t_arrival (the scheduler's own timestamps)."""
import statistics


def read(ctx):
    if ctx.kind != "serve" or not ctx.outputs:
        return None
    return statistics.median(o.t_admitted - o.t_arrival
                             for o in ctx.outputs.values()) * 1e3

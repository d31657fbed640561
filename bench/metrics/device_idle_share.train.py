"""Share of the traced window in which no operation runs on chip 0:
100 x (1 - union of device-op intervals / window)."""
from harness import trace as tr


def read(ctx):
    v = ctx.trace
    if ctx.kind != "fl" or v is None or not v.planes:
        return None
    ops = tr.select(v.events, plane=v.planes[0], line=tr.OPS_LINE)
    if not ops:
        return None
    return 100.0 * (1.0 - tr.busy_ns(ops, v.t0, v.t1) / (v.t1 - v.t0))

"""Share of its roofline that the block top-k kernel (error feedback
fused) reaches in the traced window: every launch reads the delta and
the error feedback and writes the kept values and the new error feedback
(``harness.counts.topk_compress_bytes``, 2-byte values as the
configuration stores them), over HBM bandwidth, against the launches'
summed device time on chip 0.  Memory bounds it by construction.

A launch is a Pallas kernel whose result is [clients, ..., block]; its
elements are the coordinates it compresses."""
import math

from harness import trace as tr

ITEMSIZE = {"bfloat16": 2, "float32": 4}


def read(ctx):
    v, c, t = ctx.trace, ctx.config, ctx.traffic
    if ctx.kind != "fl" or v is None or not v.planes or ctx.peaks is None:
        return None
    clients = t["clusters"] * t["devices_per_cluster"] // ctx.chips
    nbytes = busy = 0.0
    for e in tr.select(v.events, plane=v.planes[0], line=tr.OPS_LINE):
        if not tr.is_kernel(e):
            continue
        dims = tr.result_dims(e.name)
        if not dims or dims[-1] != t["block_size"] or dims[0] != clients:
            continue
        d = tr.length(tr.clip([(e.start_ns, e.end_ns)], v.t0, v.t1))
        if not d:
            continue
        nbytes += ctx.counts.topk_compress_bytes(
            math.prod(dims), ITEMSIZE[c["param_dtype"]])
        busy += d
    if not busy:
        return None
    return 100.0 * nbytes / ctx.peaks["hbm_bytes_per_s"] / (busy * 1e-9)

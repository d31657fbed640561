"""Share of its roofline that the flash-attention forward kernel reaches
in the traced window: the least time the chip could take for the causal
attention FLOPs and bytes of every launch (``harness.counts``, from the
launch's shape and the configuration), over the launches' summed device
time on chip 0.  At seq 513, 14 heads of 64 and 2 KV heads the bytes
bound it (225 FLOP/byte, under v5e's 240).

A launch is a Pallas kernel whose result is [..., heads, seq, head_dim];
its leading dims count the sequences it covers.  The program runs its
forward over all seq_len + 1 tokens of a sequence.  The forward runs
twice a step under remat; each launch does the work it is counted for."""
import math

from harness import trace as tr


def read(ctx):
    v, c = ctx.trace, ctx.config
    if ctx.kind != "fl" or v is None or not v.planes or ctx.peaks is None:
        return None
    H = c["num_attention_heads"]
    Dh = c.get("head_dim") or c["hidden_size"] // H
    S = ctx.traffic["seq_len"] + 1
    flops = nbytes = busy = 0.0
    for e in tr.select(v.events, plane=v.planes[0], line=tr.OPS_LINE):
        if not tr.is_kernel(e):
            continue
        dims = tr.result_dims(e.name)
        if not dims or len(dims) < 4 or dims[-1] != Dh or dims[-3] != H:
            continue
        d = tr.length(tr.clip([(e.start_ns, e.end_ns)], v.t0, v.t1))
        if not d:
            continue
        n_seq = math.prod(dims[:-3])
        flops += n_seq * ctx.counts.attention_fwd_flops(c, S)
        nbytes += n_seq * ctx.counts.attention_fwd_bytes(c, S)
        busy += d
    if not busy:
        return None
    t_min, _ = ctx.counts.roofline_seconds(flops, nbytes, ctx.peaks)
    return 100.0 * t_min / (busy * 1e-9)

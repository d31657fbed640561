"""Share of its roofline that the expert layer's grouped matmuls reach in
the traced window: the least time the chips' bf16 peak and HBM bandwidth
allow the expert matmuls of the window's local steps, over the summed
device time on chip 0 of the Pallas kernel launches under
``lm.moe_experts`` (selected by scope, by ``harness.scopes.scope_ops``).

The work comes from the configuration and the traffic alone, by the
block's ``expert_gmm_flops`` (2 D F per matmul for every assignment of
the tokens the forward runs over: seq_len + 1 per sequence, K
assignments a token, every layer) and ``expert_gmm_bytes`` (each
product's operands and result once in bf16, the weights once per client
and local step), for each pass the block names: forward, the backward's
two products, and the remat re-run.  The least time is the larger of the
FLOPs over peak and the bytes over bandwidth."""
from harness import scopes, spec
from harness import trace as tr


def read(ctx):
    c, t = ctx.config, ctx.traffic
    if ctx.kind != "fl" or ctx.peaks is None or not ctx.rounds:
        return None
    block = spec.load_block(c["model_type"])
    flops_of = getattr(block, "expert_gmm_flops", None)
    bytes_of = getattr(block, "expert_gmm_bytes", None)
    ops = scopes.scope_ops(ctx, "lm.moe_experts")
    if flops_of is None or bytes_of is None or not ops:
        return None
    ns = sum(own for e, own, _ in ops if tr.is_kernel(e))
    if not ns:
        return None
    clients = t["clusters"] * t["devices_per_cluster"] // ctx.chips
    tokens = clients * t["tau"] * t["seqs_per_step"] * (t["seq_len"] + 1)
    flops = sum(flops_of(c, tokens).values()) * ctx.rounds
    nbytes = sum(bytes_of(c, tokens, clients * t["tau"]).values()) \
        * ctx.rounds
    least = max(flops / ctx.peaks["bf16_flops_per_s"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ns * 1e-9)

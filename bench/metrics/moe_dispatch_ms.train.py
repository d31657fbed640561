"""Device self time per round of the traced window on chip 0 of the
dropless expert layer's routing and combining, forward and backward: the
ops under ``lm.moe_route`` (router, top-k, gates, the sort of the
assignments by expert, its inverse and the gather of the tokens into
that order) and ``lm.moe_combine`` (the gather back to token order and
the gated sum over each token's experts), by ``harness.scopes.scope_ms``.
A program without these scopes reads nothing."""
from harness import scopes


def read(ctx):
    if ctx.kind != "fl":
        return None
    parts = [scopes.scope_ms(ctx, s)
             for s in ("lm.moe_route", "lm.moe_combine")]
    if all(p is None for p in parts):
        return None
    return sum(p["fwd"] + p["bwd"] for p in parts if p is not None)

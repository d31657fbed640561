"""Device self time per round of the traced window on chip 0 in the
``optimizer`` phase of ``harness.scopes``: ``hcef.sgd`` (the momentum SGD
update) and ``hcef.grad_stats`` (the per-step gradient norm, the rho bit
mask and the round's statistics)."""
from harness import scopes


def read(ctx):
    ms = scopes.phase_ms(ctx)
    return None if ms is None else ms["optimizer"]

"""Device self time per round of the traced window on chip 0 in the ``delta``
phase of ``harness.scopes``: ``hcef.delta``: the per-leaf update x_tau - x_0
of each client."""
from harness import scopes


def read(ctx):
    ms = scopes.phase_ms(ctx)
    return None if ms is None else ms["delta"]

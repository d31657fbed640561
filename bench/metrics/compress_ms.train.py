"""Device self time per round of the traced window on chip 0 in the
``compress`` phase of ``harness.scopes``: ``hcef.compress``: block top-k
with error feedback (the Pallas kernel and the copies around it)."""
from harness import scopes


def read(ctx):
    ms = scopes.phase_ms(ctx)
    return None if ms is None else ms["compress"]

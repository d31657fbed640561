"""Device self time per round of the traced window on chip 0 in the
``aggregate`` phase of ``harness.scopes``: ``hcef.aggregate`` and
``hcef.gossip``: the intra-cluster mean and the gossip mix."""
from harness import scopes


def read(ctx):
    ms = scopes.phase_ms(ctx)
    return None if ms is None else ms["aggregate"]

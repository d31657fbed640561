"""Device self time per round of the traced window on chip 0 in the ``vocab``
phase of ``harness.scopes``: ops whose innermost program scope is
``lm.embed`` or ``lm.head``: the embedding lookup, the final norm, the tied
output head and its cross-entropy, forward and backward."""
from harness import scopes


def read(ctx):
    ms = scopes.phase_ms(ctx)
    return None if ms is None else ms["vocab"]

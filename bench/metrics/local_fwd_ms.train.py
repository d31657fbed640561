"""Device self time per round of the traced window on chip 0 in the
``local_fwd`` phase of ``harness.scopes``: the other ops under
``hcef.local_step`` outside an autodiff ``transpose``: the layers' forward
(``lm.attn``, ``lm.mlp``, the norms and residuals)."""
from harness import scopes


def read(ctx):
    ms = scopes.phase_ms(ctx)
    return None if ms is None else ms["local_fwd"]

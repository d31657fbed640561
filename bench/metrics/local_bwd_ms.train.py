"""Device self time per round of the traced window on chip 0 in the
``local_bwd`` phase of ``harness.scopes``: the other ops under
``hcef.local_step`` inside an autodiff ``transpose``: the layers' backward,
with the remat re-run of their forward."""
from harness import scopes


def read(ctx):
    ms = scopes.phase_ms(ctx)
    return None if ms is None else ms["local_bwd"]

"""Tokens fed to the clients' local steps in the window, over the window's
whole time (gossip rounds and the host's work between rounds included)."""


def read(ctx):
    if ctx.kind != "fl" or not ctx.window_s:
        return None
    return ctx.tokens / ctx.window_s

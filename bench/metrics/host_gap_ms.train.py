"""Median host time per round between one round's state being ready and
the next round's dispatch: the loss read back, the controller's solve and
report sampling, and the feed.  The loop is the benchmark's own copy of
``repro.launch.train``'s (``kinds/fl.py``), so this reads the controller
and the program's dispatch, and the benchmark's feed besides."""
import statistics


def read(ctx):
    if ctx.kind != "fl" or not ctx.host_gaps_s:
        return None
    return statistics.median(ctx.host_gaps_s) * 1e3

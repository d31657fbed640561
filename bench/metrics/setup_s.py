"""Seconds from process start to the first timed step: start-up, weights,
compilation (or compile-cache reads) and warm-up."""


def read(ctx):
    return ctx.setup_s

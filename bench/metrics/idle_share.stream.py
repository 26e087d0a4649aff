"""Device idle share of a stream cell's window: one less the busy share,
averaged over the cell's devices (``trace.Reduced.busy_s``)."""


def read(ctx):
    r = ctx.reduced
    return 1.0 - r.busy_s / r.window_s

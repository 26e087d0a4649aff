"""Device idle share of a query cell's window: one less the busy share,
averaged over the cell's devices. The host's bisection between engine
calls shows here."""


def read(ctx):
    r = ctx.reduced
    return 1.0 - r.busy_s / r.window_s

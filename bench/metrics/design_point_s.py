"""Wall seconds per analysed design point: the whole window, spec to
report dict in host memory, over the design points it completed."""


def read(ctx):
    return ctx.window_s / ctx.units if ctx.units else None

"""Share of the traced window in which no operation ran on the device
(1 - busy union / window), in the design-point cells."""


def read(ctx):
    if ctx.busy_s is None:
        return None
    window = (ctx.trace_hi - ctx.trace_lo) / 1e9
    return 100.0 * (1.0 - ctx.busy_s / window)

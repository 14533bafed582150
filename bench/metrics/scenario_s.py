"""Seconds per design point in the traffic engine's passes: the program's
``traffic.scenario`` span (padding, uploads, the weighted ECMP device
loop, downloads and the per-matrix congestion metrics)."""


def read(ctx):
    if not ctx.span_attrs("traffic.scenario") or not ctx.units:
        return None
    return ctx.span_seconds("traffic.scenario") / ctx.units

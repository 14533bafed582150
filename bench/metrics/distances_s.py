"""Seconds per design point in the wavefront: the program's
``analysis.distances`` span (dist and multiplicities, device level loop,
download)."""


def read(ctx):
    if not ctx.span_attrs("analysis.distances") or not ctx.units:
        return None
    return ctx.span_seconds("analysis.distances") / ctx.units

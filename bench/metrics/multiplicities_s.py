"""Seconds per design point in the +1/+2 slack path counts: the program's
``analysis.multiplicities`` span (one counting product per walk and per
bounce level, each with a host round trip)."""


def read(ctx):
    if not ctx.span_attrs("analysis.multiplicities") or not ctx.units:
        return None
    return ctx.span_seconds("analysis.multiplicities") / ctx.units

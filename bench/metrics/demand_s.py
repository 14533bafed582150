"""Seconds per design point generating demand: the program's
``demand.host`` spans (the pattern's draws and the scatter of flows into
router demand matrices)."""


def read(ctx):
    if not ctx.span_attrs("demand.host") or not ctx.units:
        return None
    return ctx.span_seconds("demand.host") / ctx.units

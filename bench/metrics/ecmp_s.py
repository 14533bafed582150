"""Seconds per design point in the equal-cost comparison row: the
program's ``analysis.comparison`` span (all-pairs ECMP loads, cost and
power)."""


def read(ctx):
    if not ctx.span_attrs("analysis.comparison") or not ctx.units:
        return None
    return ctx.span_seconds("analysis.comparison") / ctx.units

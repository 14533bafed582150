"""Seconds per design point spent generating the fabric: the benchmark's
own ``bench.generate`` span around ``topology.make``."""


def read(ctx):
    if not ctx.span_attrs("bench.generate") or not ctx.units:
        return None
    return ctx.span_seconds("bench.generate") / ctx.units

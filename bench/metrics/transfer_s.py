"""Seconds per design point moving arrays between host and device: the
program's ``<stage>.h2d`` spans (each ends once the bytes are on the
device) and ``<stage>.d2h`` spans."""
from bench.parts import seconds


def read(ctx):
    return seconds(ctx, "h2d", "d2h")

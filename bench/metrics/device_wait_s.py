"""Seconds per design point the host waits on the device: the program's
``<stage>.wait`` spans, each a ``block_until_ready`` on what the next line
downloads. The most that faster kernels could take off a design point."""
from bench.parts import seconds


def read(ctx):
    return seconds(ctx, "wait")

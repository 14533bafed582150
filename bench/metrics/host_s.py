"""Seconds per design point of host compute: the program's ``<stage>.host``
spans (fabric generation, dense adjacency builds, padding, masks and
reductions over n^2 arrays, the sampled diversity loops)."""
from bench.parts import seconds


def read(ctx):
    return seconds(ctx, "host")

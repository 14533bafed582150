"""Gigabytes (1e9 bytes) per design point moved between host and device:
the ``h2d_bytes`` and ``d2h_bytes`` the program's transfer spans carry."""
from bench.parts import attribute


def read(ctx):
    value = attribute(ctx, "h2d_bytes", "d2h_bytes")
    return None if value is None else value / 1e9

"""Seconds per design point in JAX tracing, lowering and backend compiles
(persistent-cache loads included): the ``jit_s`` attributes the program's
spans carry, nested events counted once."""
from bench.parts import attribute


def read(ctx):
    return attribute(ctx, "jit_s")

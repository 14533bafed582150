"""Pallas TPU kernels for the EvalNet analysis hot-spots.

Layout: semiring.py owns the generic blocked matmul (grid/BlockSpec
scaffolding + `Semiring` algebra specs), <name>.py modules are thin
instantiations, ops.py is the jit'd wrapper layer with padding + kernel
dispatch (`repro.device` decides interpret vs compiled), ref.py holds the
pure-jnp oracles.
"""
from . import autotune, ops, ref, semiring  # noqa: F401

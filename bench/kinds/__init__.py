"""Workload kinds, one module each: ``bench/kinds/<kind>.py``, found by the
``kind`` a mix file names. A kind is the general generator of its cells:
it reads the mix's parameters and drives the program with them, so every
cell of a kind is data only.

A kind's module defines ``Workload(config, mix, seed, spans)`` with a
``unit`` (what a call completes), ``warmup()`` (the smallest call that
has the window's shapes), ``step(i) -> units`` (the window's i-th call),
``close()`` and ``check() -> {number: value}`` (the comparison with
`bench.reference`, once the window has closed; every number is held to
``bench/limits/<cell>.json``). Sub-seeds come from
``numpy.random.SeedSequence`` of the run seed and a call index, so any
run seed up to 2^63 is accepted and the same seed gives the same inputs.
This module holds what the kinds share.
"""
from __future__ import annotations

import importlib

import numpy as np

from bench.reference import fabrics


def load(kind: str):
    """The ``Workload`` class of a kind."""
    return importlib.import_module(f"{__name__}.{kind}").Workload


def sub_seed(seed: int, *path: int) -> int:
    """A 32-bit seed for one call, drawn from the run seed and a path."""
    seq = np.random.SeedSequence([int(seed) & ((1 << 64) - 1), *path])
    return int(seq.generate_state(1)[0])


def rel_err(got, want, floor: float = 1e-6) -> float:
    """Largest |got - want| / max(|want|, floor * max|want|, floor), and
    inf where shapes differ or a value is not finite on one side only."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        return float("inf")
    if got.size == 0:
        return 0.0
    if not np.array_equal(np.isfinite(got), np.isfinite(want)):
        return float("inf")
    fin = np.isfinite(want)
    g, w = got[fin], want[fin]
    if w.size == 0:
        return 0.0
    scale = np.maximum(np.abs(w), max(floor * float(np.abs(w).max()), floor))
    return float((np.abs(g - w) / scale).max())


def cells_off(got, want) -> int:
    """Cells that differ (+inf equals +inf); every cell on a shape clash."""
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape:
        return int(max(got.size, want.size))
    return int(np.count_nonzero(got != want))


def fabric_off(g, ref_adj: np.ndarray) -> int:
    """Adjacency cells in which the program's graph differs from the
    reference construction."""
    if g.n != ref_adj.shape[0]:
        return int(ref_adj.size)
    return cells_off(fabrics.adjacency_from_edges(g.n, g.edges), ref_adj)


class Spy:
    """Wraps a program function by attribute, handing each call's
    arguments and result to ``record``; `restore` puts the original back."""

    def __init__(self, module, name: str, record):
        self.module, self.name = module, name
        self.original = getattr(module, name)
        original = self.original

        def wrapper(*args, **kwargs):
            out = original(*args, **kwargs)
            record(args, kwargs, out)
            return out

        setattr(module, name, wrapper)

    def restore(self):
        setattr(self.module, self.name, self.original)

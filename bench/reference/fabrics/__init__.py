"""Router graphs built from their published constructions.

Each family is a module of its own, ``bench/reference/fabrics/<family>.py``,
found by the ``family`` a configuration file names; it defines
``build(params) -> (n, n) uint8 adjacency``. Vertex numbering follows the
program's convention (the comparison is cell by cell), but every edge is
derived from the construction itself.
"""
from __future__ import annotations

import importlib

import numpy as np


def family(name: str):
    """The reference module of a fabric family."""
    return importlib.import_module(f"{__name__}.{name}")


def build(config: dict) -> np.ndarray:
    """Adjacency of a configuration file's fabric."""
    return family(config["family"]).build(config["params"])


def adjacency_from_edges(n: int, edges: np.ndarray) -> np.ndarray:
    """Dense uint8 adjacency of an (E, 2) undirected edge list."""
    adj = np.zeros((n, n), np.uint8)
    e = np.asarray(edges, np.int64)
    adj[e[:, 0], e[:, 1]] = 1
    adj[e[:, 1], e[:, 0]] = 1
    return adj


def canonical_edges(adj: np.ndarray) -> np.ndarray:
    """(E, 2) edges u < v in lexicographic order."""
    u, v = np.nonzero(np.triu(adj, 1))
    return np.stack([u, v], axis=1).astype(np.int64)

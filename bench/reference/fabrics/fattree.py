"""Three-level k-ary fat tree (Al-Fares, Loukissas and Vahdat, SIGCOMM 2008)."""
from __future__ import annotations

import numpy as np


def fattree(k: int) -> np.ndarray:
    """Three-level k-ary fat tree: dense uint8 adjacency.

    Core c -> c; aggregation (pod, a) -> (k/2)^2 + pod*k/2 + a; edge
    (pod, e) -> (k/2)^2 + k*k/2 + pod*k/2 + e. Edge~aggregation is complete
    bipartite inside a pod; aggregation a of every pod reaches cores
    a*k/2 .. a*k/2 + k/2 - 1.
    """
    if k % 2:
        raise ValueError("k must be even")
    h = k // 2
    n_core = h * h
    n = n_core + 2 * k * h
    adj = np.zeros((n, n), np.uint8)
    for pod in range(k):
        agg = n_core + pod * h + np.arange(h)
        edge = n_core + k * h + pod * h + np.arange(h)
        adj[np.ix_(agg, edge)] = 1
        adj[np.ix_(edge, agg)] = 1
        for a in range(h):
            cores = a * h + np.arange(h)
            adj[agg[a], cores] = 1
            adj[cores, agg[a]] = 1
    return adj


def build(params: dict) -> np.ndarray:
    return fattree(int(params["k"]))

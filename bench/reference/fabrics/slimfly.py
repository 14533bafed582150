"""MMS Slim Fly (Besta and Hoefler, SC'14)."""
from __future__ import annotations

import numpy as np


def slimfly(q: int) -> np.ndarray:
    """MMS Slim Fly over GF(q), q prime with q = 1 mod 4: dense uint8 adjacency.

    Routers (0, x, y) -> x*q + y and (1, m, c) -> q^2 + m*q + c.
    (0,x,y)~(0,x,y') iff y - y' is a nonzero square mod q; (1,m,c)~(1,m,c')
    iff c - c' is a non-square; (0,x,y)~(1,m,c) iff y = m*x + c mod q.
    """
    if q % 4 != 1:
        raise ValueError(f"q={q} is not 1 mod 4")
    squares = {(i * i) % q for i in range(1, q)}
    non_squares = set(range(1, q)) - squares
    n = 2 * q * q
    adj = np.zeros((n, n), np.uint8)
    idx = np.arange(q)
    for half, diffs in ((0, squares), (1, non_squares)):
        for a in range(q):
            rows = half * q * q + a * q + idx
            for d in diffs:
                adj[rows, half * q * q + a * q + (idx + d) % q] = 1
    for x in range(q):
        for m in range(q):
            y = idx
            c = (y - m * x) % q
            u = x * q + y
            v = q * q + m * q + c
            adj[u, v] = 1
            adj[v, u] = 1
    return adj


def build(params: dict) -> np.ndarray:
    return slimfly(int(params["q"]))

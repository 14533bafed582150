"""Exact path counts and ECMP loads as plain `jax.numpy` products.

Walk counts are powers of the adjacency: W_L = A^L. A pair (i, j) is first
reached at L = d(i, j), and then W_L[i, j] is its number of shortest paths.
Walks of length d+1 are simple paths; walks of length d+2 are simple paths
plus the shortest paths with one bounce v->x->v inserted, which
T_L = T_(L-1) A + W_L D (D = diag(degree), T_0 = D) counts once per path
edge, so  plus2 = W_(d+2) - T_d + d * multiplicity  (checked against a
brute-force enumeration of simple paths in the benchmark's tests).

ECMP loads are the plain sum over demands of each pair's share of paths
through a link: a link u->v lies on a shortest s->t path iff
d(s,u) + 1 + d(v,t) = d(s,t), and carries sigma(s,u) sigma(v,t) / sigma(s,t)
of its demand, which is  sum_L sum_(a+b=L-1)  F_a^T W_L F_b  with
F_a = sigma masked to distance a and W_L = demand / sigma masked to
distance L.

Every product is a float32 dot at HIGHEST precision: integer counts are
exact below 2^24, which `path_counts` checks and refuses past.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

EXACT_LIMIT = float(2 ** 24)


def _dot(a, b):
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


@jax.jit
def _reach_step(walks, adj, dist, mult, level):
    walks = _dot(walks, adj)
    new = (walks > 0) & jnp.isinf(dist)
    dist = jnp.where(new, level.astype(jnp.float32), dist)
    mult = jnp.where(new, walks, mult)
    return walks, dist, mult, jnp.any(new), jnp.max(walks)


@jax.jit
def _slack_step(walks, bounce, adj, deg, dist, mult, corr, plus1, plus2,
                level):
    walks = _dot(walks, adj)
    bounce = _dot(bounce, adj) + walks * deg[None, :]
    lf = level.astype(jnp.float32)
    new = (walks > 0) & jnp.isinf(dist)
    dist = jnp.where(new, lf, dist)
    mult = jnp.where(new, walks, mult)
    corr = jnp.where(new, bounce, corr)
    plus1 = jnp.where(dist == lf - 1, walks, plus1)
    plus2 = jnp.where(dist == lf - 2, walks, plus2)
    return (walks, bounce, dist, mult, corr, plus1, plus2, jnp.any(new),
            jnp.maximum(jnp.max(walks), jnp.max(bounce)))


def path_counts(adj: np.ndarray, slack: bool) -> dict:
    """Distances (float32, +inf unreached), multiplicities and, with
    ``slack``, the +1/+2 simple-path counts of every router pair.

    Returns host arrays and ``walk_max``, the largest walk or bounce count
    met over levels 1 .. diameter + 2 (the program's exactness flag reads
    the same quantity).
    """
    n = adj.shape[0]
    a = jnp.asarray(adj, jnp.float32)
    eye = jnp.eye(n, dtype=jnp.float32)
    dist = jnp.where(eye > 0, 0.0, jnp.inf)
    mult = eye
    walks = eye
    walk_max = 0.0
    level = 0
    if not slack:
        more = True
        # stop once every pair is reached: longer walks feed no count
        while more and level < n and bool(jnp.isinf(dist).any()):
            level += 1
            walks, dist, mult, more, wmax = _reach_step(
                walks, a, dist, mult, jnp.int32(level))
            more = bool(more)
            walk_max = max(walk_max, float(wmax))
        out = {"dist": np.asarray(dist), "mult": np.asarray(mult)}
    else:
        deg = jnp.sum(a, axis=1)
        bounce = eye * deg[None, :]
        corr = bounce
        plus1 = jnp.zeros_like(a)
        plus2 = jnp.zeros_like(a)
        diam = None
        while diam is None or level < diam + 2:
            level += 1
            (walks, bounce, dist, mult, corr, plus1, plus2, more,
             wmax) = _slack_step(walks, bounce, a, deg, dist, mult, corr,
                                 plus1, plus2, jnp.int32(level))
            walk_max = max(walk_max, float(wmax))
            if diam is None and not bool(more):
                diam = level - 1
            if level > n + 2:
                raise RuntimeError("reference walk loop did not settle")
        d = np.asarray(dist)
        m = np.asarray(mult)
        finite = np.isfinite(d)
        d0 = np.where(finite, d, 0.0).astype(np.float32)
        p2 = np.asarray(plus2) - np.asarray(corr) + d0 * m
        out = {
            "dist": d,
            "mult": np.where(finite, m, 0.0).astype(np.float32),
            "plus1": np.where(finite, np.asarray(plus1), 0.0),
            "plus2": np.where(finite & (d > 0), p2, 0.0).astype(np.float32),
        }
    if walk_max > EXACT_LIMIT:
        raise RuntimeError(f"reference counts reach {walk_max:.0f} > 2^24: "
                           f"float32 products are not exact here")
    out["walk_max"] = walk_max
    return out


@jax.jit
def _bilinear(fa, w, fb):
    return _dot(_dot(fa.T, w), fb)


def ecmp_loads(adj: np.ndarray, dist: np.ndarray, mult: np.ndarray,
               demand: np.ndarray | None = None) -> np.ndarray:
    """Directed (n, n) ECMP link loads; ``demand=None`` puts 1 on every
    reachable ordered pair. Diagonal and unreachable demand is dropped."""
    finite = np.isfinite(dist)
    off = finite & (dist > 0)
    diam = int(dist[finite].max()) if finite.any() else 0
    if demand is None:
        share = np.where(off, 1.0 / np.where(off, mult, 1.0), 0.0)
    else:
        share = np.where(off, demand / np.where(off, mult, 1.0), 0.0)
    d = jnp.asarray(dist)
    m = jnp.asarray(mult, jnp.float32)
    s = jnp.asarray(share, jnp.float32)
    frontiers = [jnp.where(d == a, m, 0.0) for a in range(diam)]
    acc = jnp.zeros_like(m)
    for length in range(1, diam + 1):
        w = jnp.where(d == length, s, 0.0)
        for a in range(length):
            acc = acc + _bilinear(frontiers[a], w, frontiers[length - 1 - a])
    return np.asarray(jnp.asarray(adj, jnp.float32) * acc)

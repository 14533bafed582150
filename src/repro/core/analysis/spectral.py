"""Spectral analysis: algebraic connectivity (Fiedler value) and bounds.

Power iteration with deflation on B = c*I - L (L = unnormalized Laplacian),
run as dense blocked JAX matvecs — router counts are small enough that dense
blocks on the MXU beat sparse gathers (DESIGN.md §3). For very large graphs a
CSR numpy path is provided.

Bounds derived:
  * bisection width  >=  n/4 * lambda_2          (Fiedler)
  * edge expansion   >=  lambda_2 / 2            (Cheeger, d-regular normalized)
  * diameter         <=  ceil(cosh^{-1}(n-1) / cosh^{-1}((l_max+l_2)/(l_max-l_2)))
"""
from __future__ import annotations


import numpy as np
import jax
import jax.numpy as jnp

from ... import obs, transfers
from ..graph import Graph

__all__ = ["fiedler_value", "spectral_bounds"]


def _laplacian_dense(g: Graph) -> np.ndarray:
    a = g.adjacency_dense(np.float32)
    d = a.sum(axis=1)
    lap = np.diag(d) - a
    return lap


def _laplacian_device(g: Graph) -> jnp.ndarray:
    with obs.span("spectral.host"):
        lap = _laplacian_dense(g)
    return transfers.upload(lap, "spectral", "laplacian")


def fiedler_value(g: Graph, iters: int = 300, seed: int = 0,
                  return_vector: bool = False):
    """lambda_2 of the unnormalized Laplacian via shifted power iteration."""
    lap = _laplacian_device(g)
    n = g.n
    deg_max = float(jnp.max(jnp.diag(lap)))
    c = 2.0 * deg_max + 1.0
    b = c * jnp.eye(n, dtype=jnp.float32) - lap  # eigs: c - lambda_i

    ones = jnp.ones((n,), jnp.float32) / np.sqrt(n)
    key = jax.random.PRNGKey(seed)
    v = jax.random.normal(key, (n,), jnp.float32)

    def step(v, _):
        v = v - jnp.dot(ones, v) * ones  # deflate trivial eigenvector
        w = b @ v
        w = w - jnp.dot(ones, w) * ones
        w = w / (jnp.linalg.norm(w) + 1e-30)
        return w, None

    v, _ = jax.lax.scan(step, v, None, length=iters)
    mu = float(v @ (b @ v))  # Rayleigh quotient for B
    lam2 = c - mu
    lam2 = max(lam2, 0.0)
    if return_vector:
        return lam2, np.asarray(v)
    return lam2


def lambda_max(g: Graph, iters: int = 200, seed: int = 1) -> float:
    lap = _laplacian_device(g)
    v = jax.random.normal(jax.random.PRNGKey(seed), (g.n,), jnp.float32)

    def step(v, _):
        w = lap @ v
        w = w / (jnp.linalg.norm(w) + 1e-30)
        return w, None

    v, _ = jax.lax.scan(step, v, None, length=iters)
    return float(v @ (lap @ v))


def spectral_bounds(g: Graph, iters: int = 300) -> dict:
    lam2 = fiedler_value(g, iters=iters)
    lmax = lambda_max(g, iters=max(100, iters // 2))
    n = g.n
    d = g.degrees()
    davg = float(d.mean())
    out = {
        "fiedler_lambda2": lam2,
        "laplacian_lambda_max": lmax,
        "bisection_lower_bound": n / 4.0 * lam2,
        "edge_expansion_lower_bound": lam2 / 2.0,
        "full_bisection_edges": davg * n / 4.0,  # reference: ideal bisection
    }
    if lmax > lam2 > 0:
        x = (lmax + lam2) / (lmax - lam2)
        out["diameter_upper_bound"] = int(
            np.ceil(np.arccosh(max(n - 1, 2)) / np.arccosh(x))
        )
    return out

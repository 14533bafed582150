"""The report's reductions, spectral estimates and the cost model, written
plainly from their definitions.

Where the program samples (pairs for path diversity and interference, the
power iterations' start vectors), the draws here follow the same generator
calls in the same order, so a seed names the same sample on both sides.
"""
from __future__ import annotations

import math

import numpy as np

# -- distances, multiplicities, histogram ------------------------------------


def distance_summary(dist: np.ndarray) -> dict:
    n = dist.shape[0]
    finite = np.isfinite(dist)
    pairs = max(1, n * (n - 1))
    return {
        "diameter": int(dist[finite].max()),
        "avg_path_length": float(dist[finite].astype(np.float64).sum()) / pairs,
        "disconnected_pair_fraction": 1.0 - (int(finite.sum()) - n) / pairs,
        "exact": True,
    }


def multiplicity_summary(counts: dict) -> dict:
    dist = counts["dist"]
    off = np.isfinite(dist) & (dist > 0)
    mult = counts["mult"][off].astype(np.float64)
    return {
        "path_multiplicity_mean": float(mult.mean()),
        "path_multiplicity_min": int(mult.min()),
        "path_multiplicity_max": int(mult.max()),
        "nonminimal_plus1_mean": float(counts["plus1"][off].astype(np.float64).mean()),
        "nonminimal_plus2_mean": float(counts["plus2"][off].astype(np.float64).mean()),
        "path_counts_exact": counts["walk_max"] <= 2 ** 24,
    }


def histogram(dist: np.ndarray) -> list:
    """Counts of finite off-diagonal hop lengths 1.. (index 0 holds 0)."""
    d = dist[np.isfinite(dist)].astype(np.int64)
    counts = np.bincount(d).tolist()
    counts[0] = 0
    while len(counts) > 1 and counts[-1] == 0:
        counts.pop()
    return counts


# -- sampled diversity and interference --------------------------------------


def path_diversity_mean(adj: np.ndarray, dist: np.ndarray, seed: int,
                        pairs: int = 512) -> float:
    """Mean over sampled (s, t) of the neighbours w of s one hop closer to t."""
    rng = np.random.default_rng(seed)
    n = dist.shape[0]
    out = np.zeros(pairs)
    for i in range(pairs):
        s = int(rng.integers(n))
        t = int(rng.integers(n))
        while t == s:
            t = int(rng.integers(n))
        if not np.isfinite(dist[s, t]):
            continue
        nbrs = np.flatnonzero(adj[s])
        out[i] = np.count_nonzero(dist[nbrs, t] == dist[s, t] - 1)
    return float(out.mean())


def interference(edges: np.ndarray, dist: np.ndarray, seed: int,
                 pairs: int = 64) -> dict:
    """Jaccard overlap of the shortest-path link sets of sampled demands.

    Demands are distinct unordered reachable pairs s < t drawn by rejection;
    a link {u, v} supports (s, t) iff d(s,u) + 1 + d(v,t) = d(s,t) in one of
    its orientations.
    """
    rng = np.random.default_rng(seed)
    n = dist.shape[0]
    pairs -= pairs % 2
    seen = set()
    for _ in range(64 * pairs + 256):
        if len(seen) >= pairs:
            break
        s, t = int(rng.integers(n)), int(rng.integers(n))
        if s > t:
            s, t = t, s
        if s == t or (s, t) in seen or not np.isfinite(dist[s, t]):
            continue
        seen.add((s, t))
    else:
        raise RuntimeError("reference interference: too few reachable pairs")
    picks = np.array(sorted(seen))[:len(seen) - len(seen) % 2]
    u, v = edges[:, 0], edges[:, 1]
    s, t = picks[:, :1], picks[:, 1:]
    d_st = dist[picks[:, 0], picks[:, 1]][:, None]
    supports = ((dist[s, u] + 1 + dist[t, v] == d_st)
                | (dist[s, v] + 1 + dist[t, u] == d_st))
    idx = rng.permutation(len(supports))
    a, b = supports[idx[0::2]], supports[idx[1::2]]
    jac = (a & b).sum(axis=1) / np.maximum((a | b).sum(axis=1), 1)
    return {"edge_interference_mean": float(jac.mean()),
            "edge_interference_max": float(jac.max()),
            "support_links_mean": float(supports.sum(axis=1).mean())}


# -- spectral estimates --------------------------------------------------------


def spectral(adj: np.ndarray, iters: int = 300) -> dict:
    """The Laplacian's lambda_2 and lambda_max by the same power iterations
    (same start vectors, same counts), in float64 on the host."""
    import jax

    a = adj.astype(np.float64)
    n = a.shape[0]
    deg = a.sum(axis=1)
    lap = np.diag(deg) - a
    c = 2.0 * deg.max() + 1.0
    b = c * np.eye(n) - lap
    ones = np.ones(n) / np.sqrt(n)
    v = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (n,)), np.float64)
    u = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (n,)), np.float64)
    for _ in range(iters):
        v = v - (ones @ v) * ones
        w = b @ v
        w = w - (ones @ w) * ones
        v = w / (np.linalg.norm(w) + 1e-30)
    lam2 = max(c - float(v @ (b @ v)), 0.0)
    for _ in range(max(100, iters // 2)):
        w = lap @ u
        u = w / (np.linalg.norm(w) + 1e-30)
    lmax = float(u @ (lap @ u))
    out = {
        "fiedler_lambda2": lam2,
        "laplacian_lambda_max": lmax,
        "bisection_lower_bound": n / 4.0 * lam2,
        "edge_expansion_lower_bound": lam2 / 2.0,
        "full_bisection_edges": float(deg.mean()) * n / 4.0,
    }
    if lmax > lam2 > 0:
        x = (lmax + lam2) / (lmax - lam2)
        out["diameter_upper_bound"] = int(
            np.ceil(np.arccosh(max(n - 1, 2)) / np.arccosh(x)))
    return out


# -- construction cost and power --------------------------------------------------


def cost_and_power(config: dict) -> dict:
    """Construction cost and power of the configuration's inventory."""
    c = config["cost"]

    def cable(length, medium):
        if medium == "electrical":
            return (c["elec_per_m"] * length + c["elec_base"]) * c["link_gbps"]
        return (c["opt_per_m"] * length + c["opt_base"]) * c["link_gbps"]

    racks = max(1, math.ceil(config["routers"] / c["rack_routers"]))
    optical_m = (2.0 / 3.0) * math.sqrt(racks) * c["rack_pitch_m"] \
        + c["optical_overhead_m"]
    routers = sum(cnt * (c["router_base"] + c["router_per_port"] * r
                         + c["router_crossbar"] * r * r)
                  for r, cnt in c["routers_by_radix"])
    links = sum(lc["count"] * cable(c["electrical_length_m"]
                                    if lc["medium"] == "electrical"
                                    else optical_m, lc["medium"])
                for lc in c["links"])
    servers = config["servers"]
    endpoints = servers * (c["nic_cost"]
                           + cable(c["electrical_length_m"], "electrical"))
    power = sum(cnt * (c["router_idle_w"] + c["router_port_w"] * r)
                for r, cnt in c["routers_by_radix"]) + servers * c["nic_w"]
    return {"construction_cost": routers + links + endpoints,
            "power_w": power}

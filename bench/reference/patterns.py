"""Server-level random-permutation demand and the traffic scenario's
per-matrix metrics, written plainly from their definitions.

The demand follows the program's generator call for call (the same
seeding, the same draws in the same order), so a seed names the same
permutations on both sides: ``numpy.random.default_rng([seed, tag])``,
``tag`` the first 8 bytes of the pattern's name as a big-endian integer
(zero-padded) mod 2^31; then, per sample, ``permutation(N)`` over the N
servers, drawn again while any server maps to itself. Servers are numbered
router by router; server k sends ``rate`` to server ``perm[k]``.
"""
from __future__ import annotations

import numpy as np

NAME = "server_permutation"


def server_counts(config: dict) -> np.ndarray:
    """Servers per router of a configuration file's fabric: its
    ``concentration`` on every router, or, where that is 0, its
    ``edge_concentration`` on the edge switches, numbered last."""
    n = int(config["routers"])
    if config["concentration"]:
        return np.full(n, int(config["concentration"]), np.int64)
    counts = np.zeros(n, np.int64)
    per_edge = int(config["edge_concentration"])
    counts[n - int(config["servers"]) // per_edge:] = per_edge
    return counts


def _tag(name: str) -> int:
    return int.from_bytes(name.encode()[:8].ljust(8, b"\0"), "big") % (1 << 31)


def server_flows(counts: np.ndarray, seed: int, samples: int) -> list:
    """Per sample, the router pairs the permutation's flows join, as flat
    indices ``i * n + j`` (ascending) beside the number of flows on each."""
    n = len(counts)
    owner = np.concatenate([np.full(c, i, np.int64)
                            for i, c in enumerate(counts)])
    rng = np.random.default_rng([int(seed), _tag(NAME)])
    out = []
    for _ in range(samples):
        while True:
            perm = rng.permutation(len(owner))
            if not np.any(perm == np.arange(len(owner))):
                break
        out.append(np.unique(owner * n + owner[perm], return_counts=True))
    return out


def server_permutation(counts: np.ndarray, rate: float, seed: int,
                       samples: int) -> np.ndarray:
    """(samples, n, n) float64 router demand: entry (i, j) is ``rate`` times
    the number of servers of router i that send to a server of router j."""
    n = len(counts)
    out = np.zeros((samples, n * n), np.float64)
    for s, (pairs, flows) in enumerate(server_flows(counts, seed, samples)):
        out[s, pairs] = flows * rate
    return out.reshape(samples, n, n)


def _nearest_rank(ranked: np.ndarray, q: float) -> float:
    """The value at rank round(q (m - 1)) of m values sorted ascending
    (numpy's rounding: halves to even), 0.0 for none."""
    if ranked.size == 0:
        return 0.0
    return float(ranked[int(np.round(q * (ranked.size - 1)))])


def traffic_metrics(loads: np.ndarray, dist: np.ndarray, demand: np.ndarray,
                    links: int, capacity: float = 1.0) -> dict:
    """The scenario's metrics of one matrix from directed ``loads``: the
    peak, its throughput bound, the mean and ranks over used (positive)
    directed links, their share of ``links``, the routed volume's mean hop
    count, the offered volume and the share of it dropped (diagonal and
    unreachable pairs)."""
    loads = np.asarray(loads)
    demand = np.asarray(demand, np.float64)
    routable = np.isfinite(dist) & (dist > 0)
    total = float(demand.sum())
    routed = float(np.sum(demand, where=routable))
    hops = float(np.multiply(demand, dist, where=routable,
                             out=np.zeros(demand.shape)).sum())
    used = np.sort(loads[loads > 0].astype(np.float64))
    peak = float(used[-1]) if used.size else 0.0
    return {
        "max_link_load": peak,
        "tput_lb": capacity / peak if peak > 0 and routed > 0 else 0.0,
        "mean_link_load": float(used.mean()) if used.size else 0.0,
        "p50_link_load": _nearest_rank(used, 0.5),
        "p90_link_load": _nearest_rank(used, 0.9),
        "p99_link_load": _nearest_rank(used, 0.99),
        "links_used_frac": used.size / links,
        "avg_hops": hops / routed if routed else 0.0,
        "demand_total": total,
        "dropped_demand_frac": 1.0 - routed / total if total else 0.0,
    }

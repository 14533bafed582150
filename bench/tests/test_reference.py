"""The plain reference against definitions it does not share code with:
simple paths and shortest paths enumerated one by one, the published
sizes of each fabric."""
import itertools

import numpy as np
import pytest

from bench.reference import counts
from bench.reference.fabrics import fattree, slimfly


def enumerate_paths(adj: np.ndarray, max_slack: int = 2):
    """Per pair: hop distance and the simple paths of length d .. d+2,
    listed one by one (exponential; tiny graphs only)."""
    n = adj.shape[0]
    nbrs = [np.flatnonzero(adj[u]) for u in range(n)]
    dist = np.full((n, n), np.inf)
    for s in range(n):
        dist[s, s] = 0
        frontier, d = [s], 0
        while frontier:
            d += 1
            nxt = []
            for u in frontier:
                for w in nbrs[u]:
                    if np.isinf(dist[s, w]):
                        dist[s, w] = d
                        nxt.append(w)
            frontier = nxt
    paths = {}
    budget = int(dist[np.isfinite(dist)].max()) + max_slack

    def dfs(s, path):
        u = path[-1]
        paths.setdefault((s, u), []).append(tuple(path))
        if len(path) - 1 == budget:
            return
        for w in nbrs[u]:
            if w not in path:
                dfs(s, path + [int(w)])

    for s in range(n):
        dfs(s, [s])
    return dist, paths


def random_graph(n, p, seed):
    rng = np.random.default_rng(seed)
    a = np.triu(rng.random((n, n)) < p, 1)
    return (a | a.T).astype(np.uint8)


GRAPHS = {
    "slimfly_q5": lambda: slimfly.slimfly(5),
    "fattree_k4": lambda: fattree.fattree(4),
    "random_12": lambda: random_graph(12, 0.3, 3),
}


@pytest.mark.parametrize("name", sorted(GRAPHS))
def test_slack_counts_equal_enumeration(name):
    adj = GRAPHS[name]()
    if name == "slimfly_q5":   # enumeration is exponential: a 20-router cut
        adj = adj[:20, :20]
    dist, paths = enumerate_paths(adj)
    got = counts.path_counts(adj, slack=True)
    np.testing.assert_array_equal(got["dist"], dist)
    n = adj.shape[0]
    for s, t in itertools.product(range(n), range(n)):
        if np.isinf(dist[s, t]):
            assert got["mult"][s, t] == 0 and got["plus2"][s, t] == 0
            continue
        lens = [len(p) - 1 for p in paths.get((s, t), [])]
        d = int(dist[s, t])
        assert got["mult"][s, t] == lens.count(d)
        assert got["plus1"][s, t] == (lens.count(d + 1) if s != t else 0)
        assert got["plus2"][s, t] == (lens.count(d + 2) if s != t else 0)


@pytest.mark.parametrize("name", sorted(GRAPHS))
def test_ecmp_loads_equal_path_enumeration(name):
    adj = GRAPHS[name]()
    if name == "slimfly_q5":
        adj = adj[:20, :20]
    dist, paths = enumerate_paths(adj, max_slack=0)
    n = adj.shape[0]
    rng = np.random.default_rng(0)
    demand = rng.random((n, n))
    want = np.zeros((n, n))
    for (s, t), ps in paths.items():
        short = [p for p in ps if len(p) - 1 == dist[s, t] and s != t]
        for p in short:
            for u, v in zip(p, p[1:]):
                want[u, v] += demand[s, t] / len(short)
    got_counts = counts.path_counts(adj, slack=False)
    got = counts.ecmp_loads(adj, got_counts["dist"], got_counts["mult"],
                            demand)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("q", [5, 13, 41])
def test_slimfly_published_sizes(q):
    adj = slimfly.slimfly(q)
    k = (3 * q - 1) // 2
    assert adj.shape == (2 * q * q, 2 * q * q)
    assert (adj == adj.T).all() and not adj.diagonal().any()
    assert (adj.sum(axis=1) == k).all()


@pytest.mark.parametrize("k", [4, 8, 74])
def test_fattree_published_sizes(k):
    adj = fattree.fattree(k)
    h = k // 2
    assert adj.shape[0] == h * h + 2 * k * h
    assert (adj == adj.T).all()
    assert int(adj.sum()) // 2 == 2 * k * h * h
    deg = adj.sum(axis=1)
    assert (deg[:h * h + k * h] == k).all() and (deg[h * h + k * h:] == h).all()

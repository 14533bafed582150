"""Exact path-multiplicity engine — the paper's path-diversity tables.

EvalNet's headline analysis is fine-grained path diversity between *every*
router pair: the number of shortest paths (multiplicity), the number of
non-minimal simple paths at +1 and +2 length slack, and how much the
shortest-path sets of different demands interfere on links. All of it
reduces to semiring matmuls (`repro.kernels.semiring`):

* multiplicity: Brandes' frontier identity
  ``sigma(i,j) = sum_{u in N(j), d(i,u)=d(i,j)-1} sigma(i,u)`` — by default
  through the device-resident wavefront engine (`analysis.wavefront`: the
  whole level loop inside one jitted `lax.while_loop`), or as one masked
  counting matmul per BFS level when a distance matrix is already
  available. The retired fused tropical-count relaxation
  (:func:`tropical_count_relaxation`) stays as the kernel-path oracle.
* slack counts: walks of length d+1 are always simple paths (a revisit
  would shorten the walk below d); walks of length d+2 are simple paths
  plus exactly the "shortest path with one bounce v->x->v inserted" walks.
  Those bounce walks are counted by T_L = sum_{l<=L} A^l D A^(L-l)
  (D = diag(degree)), double-counting one walk per path edge, hence

      simple_paths(d+2) = A^(d+2) - T_d + d * multiplicity        (per pair)

  evaluated with counting matmuls via T_L = A T_(L-1) + D A^L. On the
  kernel path the whole level loop runs as one device program
  (`wavefront.slack_counts_device`).

Counts on the kernel path are f32 and exact while every intermediate walk
count stays below 2**24 (the numpy fallback accumulates in f64, exact to
2**53); `path_counts_with_slack` reports an ``exact`` flag and clamps the
plus2 subtraction at zero, since cancellation of two rounded large counts
is not merely saturating.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import numpy as np

from ... import obs
from ..graph import Graph

__all__ = [
    "shortest_path_multiplicity", "tropical_count_relaxation",
    "path_counts_with_slack", "pair_rows",
    "pair_edge_loads", "edge_interference", "brute_force_path_counts",
]


def pair_edge_loads(g: Graph, dist: np.ndarray, mult: np.ndarray,
                    s, t) -> np.ndarray:
    """Shortest-path count through each link for (s, t) demands.

    Link {u, v} (in `g.edges` order) carries ``mult[s,u] * mult[v,t]``
    shortest s->t paths in the u->v orientation iff
    ``dist(s,u) + 1 + dist(v,t) == dist(s,t)``, plus the symmetric v->u
    term (dist/mult are symmetric: the graph is undirected). Zero
    everywhere when s and t are disconnected.

    ``s``/``t`` may be ints (returns (E,)) or equal-length index arrays
    (returns (len(s), E), one row per demand).
    """
    u, v = g.edges[:, 0], g.edges[:, 1]
    scalar = np.ndim(s) == 0 and np.ndim(t) == 0
    s_arr, t_arr = np.atleast_1d(np.asarray(s)), np.atleast_1d(np.asarray(t))
    if s_arr.shape != t_arr.shape:
        raise ValueError(f"s and t must have matching shapes, "
                         f"got {s_arr.shape} vs {t_arr.shape}")
    d_st = dist[s_arr, t_arr][:, None]
    on_uv = dist[s_arr[:, None], u] + 1 + dist[t_arr[:, None], v] == d_st
    on_vu = dist[s_arr[:, None], v] + 1 + dist[t_arr[:, None], u] == d_st
    out = (np.where(on_uv, mult[s_arr[:, None], u] * mult[t_arr[:, None], v], 0.0)
           + np.where(on_vu, mult[s_arr[:, None], v] * mult[t_arr[:, None], u], 0.0))
    return out[0] if scalar else out


def _count_product(use_kernel: bool):
    # one canonical kernel/oracle dispatch, shared with the assignment engine
    from ..routing.assign import count_product

    return count_product(use_kernel)


def shortest_path_multiplicity(
        g: Graph, dist: Optional[np.ndarray] = None, use_kernel: bool = True,
        mesh=None, tile_rows: Optional[int] = None, packed: bool = False,
) -> Tuple[np.ndarray, np.ndarray]:
    """Exact (dist, multiplicity) matrices for all router pairs.

    With ``dist`` given (the shared APSP result), runs one masked counting
    matmul per BFS level (MXU path). Without it, the kernel path runs the
    device-resident wavefront engine (`wavefront.dist_mult_device`),
    producing both matrices from one jitted level loop — no per-level host
    round trips. ``use_kernel=False`` without ``dist`` computes distances by
    all-sources BFS and takes the masked branch — the jnp pair-product
    oracle would materialize an (n, n, n) broadcast per step. The retired
    fused tropical-count relaxation survives as
    :func:`tropical_count_relaxation`, the kernel-path oracle.

    Extreme-scale knobs (`analysis.distributed`, kernel path without
    ``dist`` only, resolved by `engine_select.resolve_engine` — see its
    matrix): ``mesh`` row-shards the wavefront over a device mesh
    (bit-equal); ``tile_rows`` streams source tiles out-of-core; both
    together compose (sharded adjacency x streamed tiles). ``packed=True``
    shrinks every cell — uint8 adjacency, int16 dist, uint32 mult
    saturating at 2**24 — and RETURNS (int16, uint32) matrices with the
    DIST_UNREACHED sentinel instead of +inf.

    Every count the kernel path keeps is a sum of nonnegative terms equal
    to some sigma(i, j), so results are exact iff the largest multiplicity
    fits f32's integer range; past that a RuntimeWarning is emitted (packed
    counts clamp at MULT_SAT and warn instead — never wrap).
    """
    if dist is None:
        from .engine_select import resolve_engine

        plan = resolve_engine(use_kernel=use_kernel, mesh=mesh,
                              tile_rows=tile_rows, packed=packed)
        if plan.engine in ("tiled", "composed"):
            from .distributed import tiled_dist_mult

            return tiled_dist_mult(g, tile_rows=plan.tile_rows or 512,
                                   mesh=plan.mesh, packed=plan.packed)
        if plan.engine == "wavefront" and plan.packed:
            from .wavefront import wavefront_dist_mult

            return wavefront_dist_mult(g.adjacency_dense(np.float32),
                                       packed=True)
        if plan.engine in ("wavefront", "sharded"):
            from .distributed import sharded_dist_mult

            # sharded/wavefront engines warn on f32-inexact counts
            # themselves; mesh=None is exactly the single-device wavefront
            return sharded_dist_mult(g.adjacency_dense(np.float32),
                                     mesh=plan.mesh)
    if dist is None:
        from .apsp import bfs_distances

        d = bfs_distances(g, np.arange(g.n)).astype(np.float32)
        dist = np.where(d < 0, np.float32(np.inf), d)
    product = _count_product(use_kernel)
    a = g.adjacency_dense(np.float32)
    mult = np.where(dist == 0, np.float32(1), np.float32(0))
    finite = dist[np.isfinite(dist)]
    diam = int(finite.max()) if finite.size else 0
    for level in range(1, diam + 1):
        frontier = np.where(dist == level - 1, mult, np.float32(0))
        mult = np.where(dist == level, product(frontier, a), mult)
    _warn_if_inexact(mult, use_kernel)
    return np.asarray(dist, np.float32), mult


def tropical_count_relaxation(g: Graph, use_kernel: bool = True
                              ) -> Tuple[np.ndarray, np.ndarray]:
    """Fused tropical-with-count relaxation — the wavefront engine's oracle.

    ``X <- X (x) B`` over (dist, count) pairs through the fused
    TROPICAL_COUNT kernel, diagonal re-pinned to (0, 1) each step; after k
    steps the pair matrix is exact for all pairs at distance <= k, so
    ``diameter`` steps converge. This was the default kernel path before the
    device-resident wavefront engine; it is kept verbatim — per-step
    ``np.array`` copies, host diagonal re-pinning, host convergence check —
    as an independent correctness anchor.
    """
    import jax.numpy as jnp
    from ... import kernels

    n = g.n
    # B: the edge-relaxation operand — (1, 1) on edges, (inf, 0) elsewhere
    # including the diagonal. Squaring with a (0, 1) diagonal would double
    # count settled pairs (stay-at-end vs last-edge decompositions); pure
    # edge relaxation with the diagonal re-pinned each step is exact.
    bd = g.distance_seed()
    np.fill_diagonal(bd, np.float32(np.inf))
    bc = np.where(np.isfinite(bd), np.float32(1), np.float32(0))
    d = g.distance_seed()
    c = np.where(d <= 1, np.float32(1), np.float32(0))
    diag = np.arange(n)

    bdj, bcj = jnp.asarray(bd), jnp.asarray(bc)  # constant operands: upload once

    if use_kernel:
        def step(xd, xc):
            return kernels.ops.minplus_count_matmul(
                jnp.asarray(xd), jnp.asarray(xc), bdj, bcj)
    else:
        def step(xd, xc):
            return kernels.ref.minplus_count_matmul_ref(
                jnp.asarray(xd), jnp.asarray(xc), bdj, bcj)

    for _ in range(max(1, n - 1)):
        nd, nc = (np.array(x) for x in step(d, c))  # copy: jax buffers are read-only
        nd[diag, diag] = 0.0
        nc[diag, diag] = 1.0
        if np.array_equal(nd, d, equal_nan=True):
            d, c = nd, nc
            break
        d, c = nd, nc
    # both step functions (kernel AND jnp ref oracle) accumulate counts in
    # f32, so the exact-integer limit is 2**24 on either path
    _warn_if_inexact(c, use_kernel=True)
    return d, c


def _warn_if_inexact(mult: np.ndarray, use_kernel: bool) -> None:
    limit = float(2 ** 24 if use_kernel else 2 ** 53)
    if mult.size and mult.max() > limit:
        import warnings

        warnings.warn(
            f"shortest-path multiplicities exceed the accumulator's exact "
            f"integer range ({limit:.0f}); counts are rounded",
            RuntimeWarning, stacklevel=3)


def path_counts_with_slack(
        g: Graph, dist: np.ndarray, use_kernel: bool = True,
) -> Dict[str, np.ndarray]:
    """Per-pair counts of simple paths at length d, d+1, d+2 (d = distance).

    Returns ``{"multiplicity": M, "plus1": P1, "plus2": P2, "exact": bool}``
    — the paper's path-diversity-with-slack matrices. Diagonal and
    unreachable pairs are 0 (multiplicity diagonal is 1: the trivial path).
    ``exact`` is False when any intermediate walk count exceeded the
    accumulator's exact-integer range (2**24 for the f32 kernel path, 2**53
    for the f64 numpy path): plus2 is a difference of large counts, so past
    that point it is clamped at zero but can still be off by the rounding.

    The kernel path runs the whole level loop as one device program
    (`wavefront.slack_counts_device`) and adds ``"pair_rows"``, the
    report's per-row reductions (:func:`pair_rows`) taken on the device.
    """
    if use_kernel:
        return _slack_counts_device(g, dist)
    product = _count_product(False)
    n = g.n
    a = g.adjacency_dense(np.float32)
    deg = g.degrees().astype(np.float32)
    finite = np.isfinite(dist)
    diam = int(dist[finite].max()) if finite.any() else 0

    walks = np.eye(n, dtype=np.float32)       # A^L
    bounce = np.diag(deg).astype(np.float32)  # T_L = sum_l A^l D A^(L-l)
    mult = np.where(dist == 0, np.float32(1), np.float32(0))
    plus1 = np.zeros((n, n), np.float32)
    plus2 = np.zeros((n, n), np.float32)
    correction = np.where(dist == 0, bounce, np.float32(0))  # T_d at d=0

    exact_limit = float(2 ** 53)
    exact = True
    for level in range(1, diam + 3):
        walks = product(walks, a)
        bounce_a = product(bounce, a)
        # T_L = T_(L-1) A + A^L D; the second term is a column scale
        bounce = bounce_a + walks * deg[None, :]
        exact = (exact and walks.max() <= exact_limit
                 and bounce.max() <= exact_limit)
        mult = np.where(dist == level, walks, mult)
        plus1 = np.where(dist == level - 1, walks, plus1)
        plus2 = np.where(dist == level - 2, walks, plus2)
        correction = np.where(dist == level, bounce, correction)

    d0 = np.where(finite, dist, 0.0).astype(np.float32)
    # difference of large counts: clamp the rounding's negative excursions
    plus2 = np.maximum(plus2 - correction + d0 * mult, 0.0)
    # unreachable pairs carry no paths at any slack
    mult = np.where(finite, mult, 0.0)
    plus1 = np.where(finite, plus1, 0.0)
    plus2 = np.where(finite & (dist > 0), plus2, 0.0)
    return {"multiplicity": mult, "plus1": plus1, "plus2": plus2,
            "exact": exact}


def _slack_counts_device(g: Graph, dist: np.ndarray) -> Dict[str, np.ndarray]:
    """The kernel path of :func:`path_counts_with_slack`: two uploads, one
    device program for levels 1 .. diameter + 2, and the downloads."""
    from ... import transfers
    from .wavefront import pad_block, pad_operand, slack_counts_device

    n = g.n
    with obs.span("slack.host"):
        finite = np.isfinite(dist)
        diam = int(np.max(dist, where=finite, initial=0))
        p, _ = pad_block(n)
        adj = pad_operand(g.adjacency_dense(np.float32), p, 0)
        dist_p = pad_operand(dist, p, np.inf)
    out = slack_counts_device(transfers.upload(adj, "slack", "slack_adjacency"),
                              transfers.upload(dist_p, "slack", "slack_dist"),
                              n, diam)
    levels = diam + 2
    mult, plus1, plus2, rows, exact = transfers.wait(
        out, "slack", levels=levels, products=2 * levels)
    return {"multiplicity": transfers.download(mult, "slack", "slack_mult"),
            "plus1": transfers.download(plus1, "slack", "slack_plus1"),
            "plus2": transfers.download(plus2, "slack", "slack_plus2"),
            "exact": bool(transfers.download(exact, "slack", "slack_exact")),
            "pair_rows": transfers.download(rows, "slack", "slack_rows")}


def pair_rows(xp, dist, mult, plus1, plus2):
    """Per-row reductions over the reachable off-diagonal pairs, the
    report's multiplicity summary in (6, n) rows: pair count, sums of
    ``mult``, ``plus1`` and ``plus2``, min and max of ``mult`` (+inf and
    -inf in a row without such pairs). ``xp`` is numpy or jax.numpy: the
    device program and the host take the same reductions."""
    off = xp.isfinite(dist) & (dist > 0)
    return xp.stack([
        off.sum(axis=1).astype(mult.dtype),
        xp.where(off, mult, 0).sum(axis=1),
        xp.where(off, plus1, 0).sum(axis=1),
        xp.where(off, plus2, 0).sum(axis=1),
        xp.where(off, mult, xp.inf).min(axis=1),
        xp.where(off, mult, -xp.inf).max(axis=1)])


def edge_interference(
        g: Graph, dist: np.ndarray, mult: np.ndarray,
        pairs: int = 64, seed: int = 0,
) -> Dict[str, float]:
    """Sampled interference between the shortest-path edge sets of demands.

    For each sampled (s, t), the *support* is the set of links lying on at
    least one shortest s->t path — link (u, v) qualifies iff
    ``d(s,u) + 1 + d(v,t) == d(s,t)`` in either orientation. Interference
    between two demands is the Jaccard overlap of their supports: the
    quantity adaptive-routing studies use to predict how demands collide.

    Returns mean/max Jaccard over sampled demand pairs plus the mean support
    size (links usable by at least one shortest path).
    """
    rng = np.random.default_rng(seed)
    n = g.n
    pairs -= pairs % 2  # interference is over demand *pairs*
    if pairs < 2:
        raise ValueError("need at least 2 sampled demands")
    # unordered demands (s < t), no repeats: supports are symmetric, so
    # comparing a demand against itself or its mirror would trivially
    # report Jaccard 1.0. Rejection-sample first (graphs are typically
    # connected, so O(pairs) draws suffice); enumerate the reachable pairs
    # — O(n^2) — only when rejections show reachability is actually sparse.
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
        reachable = np.isfinite(dist) & np.triu(np.ones((n, n), bool), k=1)
        candidates = np.argwhere(reachable)
        if len(candidates) < 2:  # fewer than two distinct demands exist
            return {"edge_interference_mean": 0.0,
                    "edge_interference_max": 0.0, "support_links_mean": 0.0}
        take = min(pairs, len(candidates) - len(candidates) % 2)
        seen = set(map(tuple, candidates[
            rng.choice(len(candidates), size=take, replace=False)]))
    picks = np.array(sorted(seen))[:len(seen) - len(seen) % 2]
    supports = pair_edge_loads(g, dist, mult, picks[:, 0], picks[:, 1]) > 0
    idx = rng.permutation(len(supports))
    a, b = supports[idx[0::2]], supports[idx[1::2]]
    inter = (a & b).sum(axis=1)
    union = (a | b).sum(axis=1)
    jac = inter / np.maximum(union, 1)
    return {
        "edge_interference_mean": float(jac.mean()),
        "edge_interference_max": float(jac.max()),
        "support_links_mean": float(supports.sum(axis=1).mean()),
    }


def brute_force_path_counts(g: Graph, max_slack: int = 2) -> Dict[str, np.ndarray]:
    """Oracle: DFS-enumerate simple paths of length d..d+max_slack per pair.

    Exponential — test-sized graphs only. Returns the same dict layout as
    :func:`path_counts_with_slack`.
    """
    from .apsp import bfs_distances

    n = g.n
    indptr, indices = g.csr()
    dist = bfs_distances(g, np.arange(n)).astype(np.float32)
    dist = np.where(dist < 0, np.inf, dist)
    counts = np.zeros((max_slack + 1, n, n), np.float32)
    for s in range(n):
        limit_row = dist[s]
        # budget: longest useful path from s is max over t of d(s,t)+slack
        finite = limit_row[np.isfinite(limit_row)]
        budget = int(finite.max()) + max_slack if finite.size else 0
        visited = np.zeros(n, bool)
        visited[s] = True

        def dfs(u: int, length: int):
            if length > 0 and np.isfinite(limit_row[u]):
                slack = length - int(limit_row[u])
                if 0 <= slack <= max_slack:
                    counts[slack, s, u] += 1
            if length == budget:
                return
            for w in indices[indptr[u]:indptr[u + 1]]:
                if not visited[w]:
                    visited[w] = True
                    dfs(int(w), length + 1)
                    visited[w] = False

        dfs(s, 0)
    mult = counts[0] + np.eye(n, dtype=np.float32)  # trivial path on diagonal
    out = {"multiplicity": mult}
    if max_slack >= 1:
        out["plus1"] = counts[1]
    if max_slack >= 2:
        out["plus2"] = counts[2]
    return out

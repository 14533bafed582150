"""Vectorized flow assignment: traffic matrix -> exact expected link loads.

This is the middle layer of the routing subsystem: a :class:`~..models`
routing model describes *where* flows may go (per-pair next-hop
probabilities); this module pushes a whole (n, n) demand matrix through that
description with dense counting-semiring matmuls (`repro.kernels.semiring`,
``COUNTING`` instantiation via `kernels.ops.count_matmul`) — no per-flow
Python loops anywhere.

The workhorse identity: under uniform-over-all-shortest-paths (exact ECMP)
routing, the expected flow of demand (s, t) across directed edge (u, v) is

    demand[s,t] * sigma(s,u) * sigma(v,t) / sigma(s,t)
        iff  d(s,u) + 1 + d(v,t) == d(s,t)

(sigma = shortest-path multiplicity from `analysis.paths`). Splitting by the
position ``a = d(s,u)`` of u on the path and the pair distance ``L``, the
whole (n, n) directed load matrix is a sum of bilinear forms

    load = A  *  sum_L sum_{a=0}^{L-1}  F_a^T @ W_L @ F_{L-1-a}

with ``F_a[s,u] = sigma(s,u) [d(s,u)=a]`` the level-a multiplicity frontier
and ``W_L = (demand / sigma) [dist=L]`` the normalized per-level demand —
O(diameter^2) dense matmuls total, each MXU-eligible. The same engine with
``F_a = A^a`` (walk counts instead of shortest-path frontiers) yields loads
for slack-limited non-minimal routing (`models.SlackRouting`).

Link-load reporting convention (the one place it is defined)
------------------------------------------------------------
Loads are reported *per undirected link* in ``g.edges`` order, summing both
orientations (full-duplex links, one shared counter). Summary statistics
(``link_load_stats``) are computed over the *used support* — links with
strictly positive load — so ``load_imbalance = max / mean`` compares the
most-loaded link against the average over links that carry any traffic.
Both the sampled and the expected reports in `workload.evaluate_workload`
use this helper, so their ``*_imbalance`` ratios are directly comparable.
"""
from __future__ import annotations

from typing import Callable, Dict, Iterable, Optional, Sequence, Tuple

import numpy as np

from ... import obs
from ..graph import Graph

__all__ = ["demand_matrix", "ecmp_link_loads", "ecmp_all_pairs_loads",
           "ecmp_demand_loads", "upload_graph", "walk_slack_link_loads",
           "directed_to_link_loads", "link_load_stats", "count_product",
           "padded_neighbors", "sample_columns", "mask_unreachable_demand"]


def count_product(use_kernel: bool) -> Callable[[np.ndarray, np.ndarray],
                                                np.ndarray]:
    """(+, x) matmul: Pallas COUNTING kernel, or f64 numpy oracle.

    The kernel path uploads both operands, waits for the product and
    downloads it, each through `repro.transfers`: spans ``count.h2d`` /
    ``.wait`` / ``.d2h``, byte counters ``h2d_bytes.count_lhs``,
    ``h2d_bytes.count_rhs`` and ``d2h_bytes.count_out``."""
    if use_kernel:
        import jax.numpy as jnp

        from ... import kernels, transfers

        def product(a, b):
            c = kernels.ops.count_matmul(
                transfers.upload(a, "count", "count_lhs", jnp.float32),
                transfers.upload(b, "count", "count_rhs", jnp.float32))
            return transfers.download(transfers.wait(c, "count"), "count",
                                      "count_out")

        return product
    return lambda a, b: np.asarray(a, np.float64) @ np.asarray(b, np.float64)


def padded_neighbors(g: Graph, with_edge_ids: bool = False):
    """CSR neighbour lists padded to (n, maxdeg) + validity mask.

    The shared representation behind every vectorized per-hop step (the
    workload sampler, the throughput successor chase): a hop's working set
    is (rows, maxdeg) gathers instead of dense (rows, n) rows.

    With ``with_edge_ids`` a third (n, maxdeg) array maps each slot to its
    *directed* edge index (0..2E-1: id < E is the u->v orientation of
    ``g.edges[id]``, id >= E the reverse of ``g.edges[id - E]``), so per-hop
    load accumulation can scatter into an O(E) vector instead of an (n, n)
    matrix.
    """
    indptr, indices = g.csr()
    deg = np.diff(indptr)
    maxdeg = int(deg.max(initial=1))
    valid = np.arange(maxdeg)[None, :] < deg[:, None]
    nbrs = np.zeros((g.n, maxdeg), np.int64)
    nbrs[valid] = indices
    if not with_edge_ids:
        return nbrs, valid
    # csr() sorts concat(u, v) stably: CSR slot p holds directed edge
    # order[p] of the concat([u->v], [v->u]) list — the same ordering the
    # throughput engine's capacity vectors use
    order = np.argsort(np.concatenate([g.edges[:, 0], g.edges[:, 1]]),
                       kind="stable")
    eids = np.zeros((g.n, maxdeg), np.int64)
    eids[valid] = order
    return nbrs, valid, eids


def sample_columns(weights: np.ndarray, mask: np.ndarray,
                   rng: np.random.Generator) -> np.ndarray:
    """Per row, draw one column index with probability ∝ ``weights``.

    ``mask`` marks the admissible columns (weights must be 0 outside it and
    every row must have at least one admissible column). Cumulative-sum
    inverse sampling; rows where float rounding pushes the draw to the
    total are repaired onto the first admissible column.
    """
    cums = np.cumsum(weights, axis=1)
    draw = rng.random(len(weights))[:, None] * cums[:, -1:]
    slot = (cums > draw).argmax(axis=1)
    bad = ~mask[np.arange(len(slot)), slot]
    if bad.any():
        slot[bad] = mask[bad].argmax(axis=1)
    return slot


def mask_unreachable_demand(demand: np.ndarray, dist: np.ndarray,
                            renormalize: bool = False
                            ) -> Tuple[np.ndarray, float]:
    """The partitioned-graph demand helper (contract: `traffic.spec`).

    Zeroes demand on diagonal and unreachable (``dist == inf``) pairs —
    what every engine in this module does implicitly — and returns the
    masked matrix together with the dropped *volume* fraction, so callers
    report disconnection instead of silently under-routing. With
    ``renormalize=True`` the surviving entries are rescaled to preserve
    the original total volume (the degradation curves' "demand
    renormalized over reachable pairs" convention). Accepts leading batch
    axes as long as demand/dist broadcast together. The full
    unreachable-demand contract is documented ONCE, in the
    `core.traffic.spec` module docstring.
    """
    demand = np.asarray(demand, np.float64)
    n = demand.shape[-1]
    off = ~np.eye(n, dtype=bool)
    total = float(np.where(off, demand, 0.0).sum())
    ok = off & np.isfinite(dist)
    masked = np.where(ok, demand, 0.0)
    kept = float(masked.sum())
    dropped_frac = 0.0 if total <= 0 else 1.0 - kept / total
    if renormalize and kept > 0:
        masked = masked * (total / kept)
    return masked, dropped_frac


def demand_matrix(g: Graph, pairs: np.ndarray,
                  volume: float = 1.0) -> np.ndarray:
    """(n, n) f64 demand from (F, 2) flow pairs: volume per flow, summed.

    .. deprecated:: PR 10
        Thin shim over `core.traffic.spec.pairs_to_matrix` (the one
        pairs -> matrix primitive of the unified `TrafficSpec` path).
    """
    import warnings

    from ..traffic.spec import pairs_to_matrix

    warnings.warn("routing.assign.demand_matrix is deprecated; use "
                  "core.traffic.TrafficSpec (or traffic.spec."
                  "pairs_to_matrix) instead", DeprecationWarning,
                  stacklevel=2)
    return pairs_to_matrix(g.n, pairs, volume)


def _bilinear_edge_loads(
        adj: np.ndarray,
        terms: Iterable[Tuple[np.ndarray, np.ndarray, np.ndarray]],
        product: Callable[[np.ndarray, np.ndarray], np.ndarray],
) -> np.ndarray:
    """``adj * sum_i  Fa_i^T @ W_i @ Fb_i`` — the shared assignment core."""
    acc: Optional[np.ndarray] = None
    for fa, w, fb in terms:
        if not w.any():
            continue
        term = product(product(fa.T, w), fb)
        acc = term if acc is None else acc + term
    if acc is None:
        return np.zeros_like(adj, dtype=np.float64)
    return adj * acc


def ecmp_link_loads(g: Graph, dist: np.ndarray, mult: np.ndarray,
                    demand: np.ndarray, use_kernel: bool = True,
                    directed: bool = False) -> np.ndarray:
    """Exact expected loads under uniform-over-all-shortest-paths routing.

    Returns (E,) undirected link loads in ``g.edges`` order (or the (n, n)
    directed load matrix with ``directed=True``). Demand on unreachable or
    diagonal pairs is ignored. The f32 kernel path is exact while every
    intermediate stays below 2**24; ``use_kernel=False`` accumulates in f64.
    """
    n = g.n
    finite = np.isfinite(dist)
    off = finite & (dist > 0) & (mult > 0)
    w_all = np.where(off, np.divide(demand, mult, where=off,
                                    out=np.zeros((n, n))), 0.0)
    diam = int(dist[finite].max()) if finite.any() else 0
    adj = g.adjacency_dense(np.float64)
    product = count_product(use_kernel)

    # level frontiers F_a; built once, reused across (L, a) terms
    frontiers = [np.where(dist == a, mult, 0.0).astype(np.float64)
                 for a in range(diam)]

    def terms():
        for level in range(1, diam + 1):
            w_l = np.where(dist == level, w_all, 0.0)
            if not w_l.any():
                continue
            for a in range(level):
                yield frontiers[a], w_l, frontiers[level - 1 - a]

    loads = _bilinear_edge_loads(adj, terms(), product)
    return loads if directed else directed_to_link_loads(g, loads)


def ecmp_all_pairs_loads(dist: np.ndarray, mult: np.ndarray, adj: np.ndarray,
                         product: Optional[Callable] = None,
                         use_kernel: bool = True, mesh=None) -> np.ndarray:
    """Directed ECMP link loads under *uniform all-pairs* demand, O(diameter).

    Partitioned graphs are first-class: "uniform all-pairs" means 1.0 on
    every *reachable* ordered pair — unreachable pairs (and dead routers'
    rows/columns) contribute nothing to any load, never inf/NaN, because
    every level mask below is gated on finite distance. The resilience
    engine's failure batches lean on this: ``1 / loads.max()`` stays the
    exact saturation-throughput bound for the surviving demand set.

    Specializing `ecmp_link_loads` to demand == 1 on every reachable pair
    admits Brandes-style backward dependency accumulation: with
    ``Z_a[s,w] = (1 + delta[s,w]) / sigma(s,w)`` on the level set
    ``d(s,w) = a`` (delta = the summed pair dependencies of w as an
    intermediate), the level recurrences

        delta_a = F_a * (Z_{a+1} @ A)        F_a[s,v] = sigma(s,v)[d(s,v)=a]
        load   += F_a^T @ Z_{a+1}

    cost 2 matmuls per BFS level — O(diameter) products instead of the
    general engine's O(diameter^2) — which is what makes the per-pair
    saturation-throughput column affordable inside the sweep driver.

    Arrays may carry leading batch dimensions (the sweep's stacked leading
    axis) as long as ``product`` handles the same stacking. The kernel-path
    default (``product=None, use_kernel=True``) runs the whole accumulation
    device-resident (`analysis.wavefront.ecmp_loads_device`: one jitted
    `lax.while_loop`, level masks never materialize on host); passing an
    explicit ``product`` (or ``use_kernel=False``) takes the host-looped
    reference path. Returns the directed (.., n, n) load matrix;
    ``1 / loads.max()`` is the exact ECMP lower bound on per-pair
    saturation throughput (capacity 1 per link direction). Tested equal to
    ``ecmp_link_loads(demand=all-ones)``.

    With ``mesh`` (a 1-D `analysis.distributed` row mesh) the kernel path
    accumulates the Brandes partials shard-locally — each device owns a
    block of source rows — and psums them once at the end; matches the
    single-device accumulation to f32 round-off.
    """
    if product is None and use_kernel:
        return _ecmp_device("ecmp", dist, mult, adj, mesh=mesh)
    if product is None:
        product = count_product(use_kernel)
    return _brandes_host(dist, mult, adj, 1.0, product)


def ecmp_demand_loads(dist, mult, adj, demand: np.ndarray,
                      use_kernel: bool = True, *, device: bool = False):
    """Directed ECMP link loads of *arbitrary* (stacked) demand, O(diameter).

    The demand-weighted generalization of :func:`ecmp_all_pairs_loads`:
    seeding the Brandes backward recurrence with the pair's demand instead
    of 1.0 (``Z_a[s,w] = (demand[s,w] + delta[s,w]) / sigma(s,w)`` on the
    level set ``d(s,w) = a``) yields the exact expected loads of
    :func:`ecmp_link_loads` in 2 counting products per BFS level instead
    of O(diameter^2) bilinear terms — the identity the batched traffic
    engine (`core.traffic.scenarios`) leans on to push thousands of demand
    matrices through one stacked pass.

    Demand on the diagonal and on unreachable pairs is dropped, never
    routed (contract: `core.traffic.spec`); the level masks are gated on
    finite distance, so partitioned graphs are first-class. The demand may
    be one (n, n) matrix or a (C, n, n) stack. The graph (dist, mult, adj)
    is either one shared (n, n) graph, which stays 2-D — on the kernel path
    it goes up once, or not at all when the caller passes the padded device
    operands of :func:`upload_graph`, and each call uploads only the demand
    — or per-sample (C, n, n) graphs (the traffic x failure grid), which
    are broadcast against the demand on the host and uploaded with it. The
    kernel default runs the whole accumulation device-resident
    (`analysis.wavefront.ecmp_loads_device` with its weighted variant);
    ``use_kernel=False`` is the f64 host oracle. Returns the directed
    ``(.., n, n)`` load matrix: host float64, or with ``device=True`` on
    the kernel path the device float32 loads, sliced on the device and
    never downloaded (the host oracle ignores ``device``).
    """
    demand = np.asarray(demand, np.float64)
    if 3 in (np.ndim(dist), np.ndim(mult), np.ndim(adj)):
        dist, mult, adj = (np.asarray(x) for x in (dist, mult, adj))
        shape = np.broadcast_shapes(dist.shape, mult.shape, adj.shape,
                                    demand.shape)
        with obs.span("traffic.host"):
            dist = np.ascontiguousarray(np.broadcast_to(dist, shape))
            mult = np.ascontiguousarray(np.broadcast_to(mult, shape))
            adj = np.ascontiguousarray(np.broadcast_to(adj, shape))
            demand = np.ascontiguousarray(np.broadcast_to(demand, shape))
    if use_kernel:
        return _ecmp_device("traffic", dist, mult, adj, demand, keep=device)
    dist, mult, adj = (np.asarray(x) for x in (dist, mult, adj))
    return _brandes_host(dist, mult, adj, demand, count_product(False))


def upload_graph(dist: np.ndarray, mult: np.ndarray, adj: np.ndarray):
    """One (n, n) graph as the device operands every stacked pass of
    :func:`ecmp_demand_loads` can share: dist (+inf), mult and adjacency
    (0) padded to the (p, p) of a batched problem, float32, uploaded once
    each (``h2d_bytes.traffic_dist`` / ``_mult`` / ``_adjacency``)."""
    from ..analysis.wavefront import pad_block

    p, _ = pad_block(np.shape(dist)[-1], batched=True)
    return _graph_operands("traffic", p, dist, mult, adj)


def _graph_operands(stage: str, p: int, dist, mult, adj):
    """dist, mult and adj padded to (.., p, p) float32 and uploaded in that
    order; an operand already on the device was padded by
    :func:`upload_graph` and is taken as it is. Traced, the innermost live
    span counts each upload of the graph in ``graph_uploads``."""
    import jax

    from ... import transfers
    from ..analysis.wavefront import pad_operand

    def operand(x, fill, what):
        if isinstance(x, jax.Array):
            return x
        with obs.span(f"{stage}.host"):
            x = pad_operand(x, p, fill)
        return transfers.upload(x, stage, f"{stage}_{what}")

    if not all(isinstance(x, jax.Array) for x in (dist, mult, adj)):
        obs.current().inc("graph_uploads", 1)
    return (operand(dist, np.inf, "dist"), operand(mult, 0.0, "mult"),
            operand(adj, 0.0, "adjacency"))


def _brandes_host(dist: np.ndarray, mult: np.ndarray, adj: np.ndarray,
                  demand, product: Callable) -> np.ndarray:
    """The host Brandes backward recurrence behind both ECMP entry points.

    ``Z_a = (demand + delta) / sigma`` on the level set ``d = a + 1``;
    ``demand`` is 1.0 for uniform all-pairs demand or a demand array that
    broadcasts against ``dist``. The level masks exclude the diagonal and
    unreachable pairs, so their demand never routes.
    """
    finite = np.isfinite(dist)
    diam = int(dist[finite].max()) if finite.any() else 0
    sigma_inv = np.where(finite & (mult > 0),
                         1.0 / np.where(mult > 0, mult, 1.0), 0.0)
    delta = np.zeros_like(sigma_inv)
    acc = np.zeros_like(sigma_inv)
    for a in range(diam - 1, -1, -1):
        z = np.where(dist == a + 1, (demand + delta) * sigma_inv, 0.0)
        f_a = np.where(dist == a, mult, 0.0)
        acc = acc + np.asarray(product(np.swapaxes(f_a, -1, -2), z))
        delta = np.where(dist == a, mult * np.asarray(product(z, adj)), delta)
    return adj * acc


def _ecmp_device(stage: str, dist, mult, adj,
                 demand: Optional[np.ndarray] = None,
                 mesh=None, keep: bool = False):
    """Pad -> device Brandes accumulation -> sliced loads: the one upload
    seam of both ECMP entry points.

    dist, mult and adj are host (n, n) or (C, n, n) arrays, padded and
    uploaded in turn so that one padded host copy lives at a time, or the
    padded (p, p) device operands of :func:`upload_graph`, used as they
    are. The (n, n) or (C, n, n) demand goes up as float32 at its own size
    and the program pads it; a 2-D graph under a stacked demand is
    broadcast inside the program. Spans ``<stage>.host`` / ``.h2d`` /
    ``.wait`` / ``.d2h`` and byte counters ``h2d_bytes.<stage>_<operand>``,
    ``d2h_bytes.<stage>_loads`` (`repro.transfers`). ``demand=None`` is
    uniform all-pairs demand. With a multi-device ``mesh`` the uniform
    accumulation runs shard-local over source rows
    (`distributed.ecmp_loads_sharded`); jit reshards the replicated uploads
    onto the mesh per the engine's in_specs. ``keep`` returns the sliced
    float32 loads on the device, never downloaded.
    """
    from ... import transfers
    from ..analysis.wavefront import ecmp_loads_device, pad_block

    n = np.shape(dist if demand is None else demand)[-1]
    batched = 3 in (np.ndim(dist), np.ndim(demand))
    sharded = mesh is not None and mesh.size > 1
    if sharded:
        from ..analysis.distributed import (ROW_AXIS, ecmp_loads_sharded,
                                            pad_block_sharded)

        p, _, block = pad_block_sharded(n, mesh.shape[ROW_AXIS],
                                        batched=batched)
    else:
        p, block = pad_block(n, batched=batched)

    operands = _graph_operands(stage, p, dist, mult, adj)
    if sharded:
        loads = ecmp_loads_sharded(*operands, mesh, block=block)
    else:
        if demand is not None:
            with obs.span(f"{stage}.host"):
                demand = np.asarray(demand, np.float32)
            demand = transfers.upload(demand, stage, f"{stage}_demand")
        loads = ecmp_loads_device(*operands, demand=demand, block=block)
    sl = (Ellipsis, slice(None, n), slice(None, n))
    if keep:
        return loads[sl]
    loads = transfers.download(transfers.wait(loads, stage), stage,
                               f"{stage}_loads")
    with obs.span(f"{stage}.host"):
        return loads[sl].astype(np.float64)


def walk_slack_link_loads(g: Graph, dist: np.ndarray, demand: np.ndarray,
                          slack: int, class_weights: Sequence[np.ndarray],
                          use_kernel: bool = True,
                          directed: bool = False) -> np.ndarray:
    """Expected loads when demand spreads uniformly over length-(d+j) walks.

    ``class_weights[j][s, t]`` is the probability mass pair (s, t) routes in
    slack class j (rows need not be normalized globally; each entry is the
    per-pair probability of class j, summing to 1 over j on routed pairs).
    Within class j the flow spreads uniformly over all walks of length
    ``d(s,t)+j``; for j <= 1 every such walk is a simple path (a revisit
    would shorten the walk below d), so classes 0 and 1 are exactly uniform
    over the paper's slack-path sets. Class 2 walks include one-bounce
    detours (see `analysis.paths`) — documented walk-model relaxation.
    """
    n = g.n
    adj_f = g.adjacency_dense(np.float64)
    product = count_product(use_kernel)
    finite = np.isfinite(dist)
    diam = int(dist[finite].max()) if finite.any() else 0
    max_len = diam + slack
    # walk-count powers A^0 .. A^(max_len - 1), plus totals up to max_len
    powers = [np.eye(n)]
    for _ in range(max_len):
        powers.append(product(powers[-1], adj_f))

    def terms():
        for j in range(slack + 1):
            cw = class_weights[j]
            for level in range(1 if j == 0 else 0, diam + 1):
                m = level + j
                if m == 0:
                    continue
                total = powers[m]
                sel = (dist == level) & (total > 0) & (cw > 0)
                if not sel.any():
                    continue
                w_lj = np.where(sel, demand * cw / np.where(sel, total, 1.0),
                                0.0)
                for a in range(m):
                    yield powers[a], w_lj, powers[m - 1 - a]

    loads = _bilinear_edge_loads(adj_f, terms(), product)
    return loads if directed else directed_to_link_loads(g, loads)


def directed_to_link_loads(g: Graph, directed: np.ndarray) -> np.ndarray:
    """Fold an (n, n) directed load matrix onto (E,) undirected link loads."""
    u, v = g.edges[:, 0], g.edges[:, 1]
    return directed[u, v] + directed[v, u]


def link_load_stats(loads: np.ndarray, total_links: int,
                    prefix: str = "") -> Dict[str, float]:
    """Summary stats over the used support (loads > 0); see module docstring.

    Keys: ``{prefix}max_link_load``, ``{prefix}mean_link_load``,
    ``{prefix}p99_link_load``, ``{prefix}load_imbalance``,
    ``{prefix}links_used`` (+ ``links_total`` when prefix is empty).
    """
    used = loads[loads > 0]
    out: Dict[str, float] = {}
    if not prefix:
        out["links_total"] = int(total_links)
    if used.size == 0:
        out.update({f"{prefix}max_link_load": 0.0,
                    f"{prefix}mean_link_load": 0.0,
                    f"{prefix}p99_link_load": 0.0,
                    f"{prefix}load_imbalance": 0.0,
                    f"{prefix}links_used": 0})
        return out
    out.update({
        f"{prefix}max_link_load": float(used.max()),
        f"{prefix}mean_link_load": float(used.mean()),
        f"{prefix}p99_link_load": float(np.percentile(used, 99)),
        f"{prefix}load_imbalance": float(used.max() / used.mean()),
        f"{prefix}links_used": int(used.size),
    })
    return out

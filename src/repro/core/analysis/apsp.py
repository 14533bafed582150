"""All-pairs shortest paths and batched BFS.

Two regimes, matching the hardware adaptation in DESIGN.md §3:

* dense device-resident analysis for router counts that fit a dense matrix —
  hop-distance APSP runs the wavefront engine (`wavefront.dist_mult_device`:
  one fused counting product per BFS level inside a jitted
  `jax.lax.while_loop`, nothing touches the host until the final matrices);
  min-plus squaring (D_{2l} = D_l ⊗ D_l) stays as the weighted-APSP path
  (`apsp_from_lengths`) and as the tropical oracle for the wavefront.
* frontier BFS over CSR (numpy, batched multi-source) from sampled sources
  for very large graphs — the classic toolchain path, used as oracle and
  for n > dense_limit.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

import jax.numpy as jnp

from ..graph import Graph

__all__ = ["apsp_dense", "apsp_from_lengths", "bfs_distances",
           "sampled_distances"]

_INF = np.float32(np.inf)

#: cap on the flattened CSR-span transient in the batched BFS (entries per
#: chunk; one chunk is never smaller than one full adjacency, 2E)
_SPAN_BUDGET = 1 << 22


def apsp_dense(g: Graph, use_kernel: bool = True,
               block: Optional[int] = None, max_squarings: int = 8,
               method: Optional[str] = None, mesh=None,
               tile_rows: Optional[int] = None) -> np.ndarray:
    """Dense APSP. Returns (n, n) float32 hop distances, inf = unreachable.

    ``method="wavefront"`` (the kernel-path default) runs the device-resident
    level loop — O(diameter) fused MXU counting products, one jitted call.
    ``method="squaring"`` is the tropical min-plus squaring oracle
    (ceil(log2(diameter)) products); it is also the ``use_kernel=False``
    default, running the jnp oracle product with a host-side loop.

    Extreme-scale knobs (`analysis.distributed`, resolved by
    `engine_select.resolve_engine` — see its matrix): ``mesh`` runs the
    wavefront row-sharded over a 1-D device mesh (bit-equal results);
    ``tile_rows`` runs the out-of-core tiled engine — source rows stream
    through the kernels tile by tile, adjacency panels are built from CSR,
    and no N x N device buffer ever exists (still assembles the full host
    result; stream tiles yourself via `distributed.tiled_dist_mult_tiles`
    to avoid that too). Both together compose: sharded adjacency panels x
    streamed source tiles (`distributed.composed_dist_mult_tiles`).
    """
    from .engine_select import resolve_engine

    plan = resolve_engine(use_kernel=use_kernel, method=method, mesh=mesh,
                          tile_rows=tile_rows)
    if plan.engine in ("tiled", "composed"):
        from .distributed import tiled_dist_mult

        dist, _ = tiled_dist_mult(g, tile_rows=plan.tile_rows or 512,
                                  block=block, mesh=plan.mesh)
        return dist
    if plan.engine in ("wavefront", "sharded"):
        from .distributed import sharded_dist_mult

        dist, _ = sharded_dist_mult(g.adjacency_dense(np.float32),
                                    mesh=plan.mesh, block=block)
        return dist
    return _apsp_squaring(g.distance_seed(), g.n, use_kernel,
                          block or 256, max_squarings)


def _apsp_squaring(d: np.ndarray, n: int, use_kernel: bool, block: int,
                   max_squarings: int) -> np.ndarray:
    """Host-looped min-plus squaring — the tropical oracle the wavefront
    engine is tested (and benchmarked) against."""
    from ... import kernels  # local import: keep core importable without kernels

    pad = (-n) % block
    if pad:
        d = np.pad(d, ((0, pad), (0, pad)), constant_values=_INF)
        # keep padded diagonal at 0 so padding never creates paths
        for i in range(n, n + pad):
            d[i, i] = 0.0
    dj = jnp.asarray(d)
    product = kernels.ops.minplus_matmul if use_kernel else _minplus_jnp
    for _ in range(max_squarings):
        nxt = product(dj, dj)
        if bool(jnp.all(nxt == dj)):
            dj = nxt
            break
        dj = nxt
    return np.asarray(dj)[:n, :n]


def apsp_from_lengths(lengths: np.ndarray, use_kernel: bool = True,
                      block: Optional[int] = None,
                      max_squarings: Optional[int] = None) -> np.ndarray:
    """APSP over an arbitrary nonnegative (n, n) edge-length matrix.

    ``lengths`` follows the `Graph.distance_seed` convention: 0 on the
    diagonal, the directed edge length at [u, v], +inf where there is no
    edge. Min-plus squaring through the tropical Pallas kernel, with the
    whole squaring loop — products AND the convergence flag — resident on
    device (`wavefront.squaring_apsp_device`); the ``use_kernel=False``
    oracle keeps the host loop over the jnp product. This is the
    weighted-shortest-path oracle the throughput engine calls once per
    multiplicative-weights round, batched over all router pairs at once.
    ``block`` defaults to the autotuner's min-plus entry for the padded
    size (128 blocks fit v5e's scoped VMEM; 256 with ``sub_k`` 8 does not).
    """
    lengths = np.asarray(lengths, np.float32)
    n = lengths.shape[0]
    if not use_kernel:
        if max_squarings is None:
            max_squarings = max(1, int(np.ceil(np.log2(max(2, n)))))
        return _apsp_squaring(lengths, n, use_kernel=False,
                              block=block or 128, max_squarings=max_squarings)
    from .wavefront import squaring_apsp_device

    pad = (-n) % max(block or 128, 128)
    d = lengths
    if pad:
        d = np.pad(d, ((0, pad), (0, pad)), constant_values=_INF)
        idx = np.arange(n, n + pad)
        d[idx, idx] = 0.0
    # max_squarings stays shape-derived by default (see squaring_apsp_device):
    # the device convergence flag stops early, and a shape-keyed cap means
    # one compile per padded shape instead of one per router count. The
    # padding honored `block`, so pass it through — the device engine's
    # table choice might not divide a non-128-multiple padded size.
    out = squaring_apsp_device(jnp.asarray(d), max_squarings=max_squarings,
                               block=block)
    return np.asarray(out)[:n, :n]


def _minplus_jnp(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    # oracle semantics; used when the kernel path is disabled
    return jnp.min(a[:, :, None] + b[None, :, :], axis=1)


def bfs_distances(g: Graph, sources: np.ndarray) -> np.ndarray:
    """Exact hop distances from each source via batched CSR frontier BFS.

    Returns (len(sources), n) int32 with -1 for unreachable. All sources
    sweep together: each level gathers every frontier vertex's CSR span
    with one `np.repeat`-flattened index expression and scatters the
    newly-visited mask — no per-source or per-vertex Python loops. This is
    the oracle for every invariant test and the > dense-limit path.
    """
    indptr, indices = g.csr()
    sources = np.asarray(sources, np.int64)
    ns, n = len(sources), g.n
    dist = np.full((ns, n), -1, dtype=np.int32)
    frontier = np.zeros((ns, n), dtype=bool)
    dist[np.arange(ns), sources] = 0
    frontier[np.arange(ns), sources] = True
    # bound the flattened-span transient (3 int64 arrays of this length) so
    # a wide multi-source frontier never costs sources x 2E peak memory
    span_budget = max(int(2 * g.num_edges), _SPAN_BUDGET)
    level = 0
    while frontier.any():
        level += 1
        rows, verts = np.nonzero(frontier)
        counts_all = indptr[verts + 1] - indptr[verts]
        bounds = np.searchsorted(np.cumsum(counts_all),
                                 np.arange(span_budget, counts_all.sum(),
                                           span_budget))
        new = np.zeros((ns, n), dtype=bool)
        for lo, hi in zip(np.concatenate(([0], bounds)),
                          np.concatenate((bounds, [len(verts)]))):
            if lo >= hi:
                continue
            starts, counts = indptr[verts[lo:hi]], counts_all[lo:hi]
            # flatten the chunk's CSR spans: starts[i] .. starts[i]+counts[i]
            flat = np.repeat(
                starts - np.concatenate(([0], np.cumsum(counts)[:-1])),
                counts) + np.arange(counts.sum())
            new[np.repeat(rows[lo:hi], counts), indices[flat]] = True
        new &= dist < 0
        dist[new] = level
        frontier = new
    return dist


def sampled_distances(g: Graph, n_sources: int = 64,
                      seed: int = 0) -> np.ndarray:
    """Distances from a uniform sample of sources (for huge graphs)."""
    rng = np.random.default_rng(seed)
    k = min(n_sources, g.n)
    sources = rng.choice(g.n, size=k, replace=False)
    return bfs_distances(g, sources)

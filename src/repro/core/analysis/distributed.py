"""Sharded and out-of-core wavefront analysis — the extreme-scale engines.

`analysis.wavefront` made the BFS level loop device-resident; this module
makes it *scale past one buffer*. Two regimes, same kernels, same numbers:

* **Row-sharded** (:func:`dist_mult_sharded` and friends): the dist / mult /
  frontier matrices are split row-wise over a 1-D device mesh
  (``axis="rows"``) with `shard_map` — each of the P devices owns an
  ``(N/P, N)`` block and runs the existing fused ``frontier_step`` Pallas
  primitive on its local block against the replicated adjacency. The whole
  level loop stays inside ONE jitted `jax.lax.while_loop`: the only
  cross-device traffic is a psum'd one-int convergence flag per level
  (plus a psum of the shard-local Brandes load partials at the end of
  :func:`ecmp_loads_sharded`). Demonstrable anywhere via
  ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

* **Tiled out-of-core** (:func:`tiled_dist_mult_tiles` and friends): when
  even one row-block times the dense adjacency exceeds memory, a host-side
  tile pump streams ``(panel_rows, N)`` adjacency panels (built on the fly
  from CSR — the dense N x N matrix never materializes anywhere) through
  the same counting kernels, accumulating each level's frontier product
  panel by panel into one reused pinned host staging buffer. Exact APSP +
  multiplicity at >= 16k routers on a laptop-class host; peak memory is
  O(tile_rows x N + panel_rows x N) instead of O(N^2).

* **Composed: sharding x streaming** (:func:`composed_dist_mult_tiles`, or
  just pass ``mesh=`` to the tiled entry points): each mesh shard owns the
  ``(N/P, N)`` row block of the adjacency — built shard-by-shard from CSR
  through the same reused staging pump, so neither the dense N x N matrix
  nor a replicated per-device copy ever exists — plus its row slice of the
  source tile's dist / mult / frontier. The level loop stays ONE jitted
  `lax.while_loop` with the psum convergence flag; inside it a
  `fori_loop` ring rotates the adjacency panels (`lax.ppermute`) so every
  shard sees every K-slab once per level. Per-device adjacency memory is
  N^2/P x cell bytes; source tiles stream through the mesh exactly like
  the single-device tiled engine.

All engines take ``packed=True`` to shrink the cell: int16 distances
(``DIST_UNREACHED`` = int16 max plays +inf), saturating uint32
multiplicities (clamped at ``MULT_SAT`` = 2**24, the f32 exact-integer
ceiling — never wrapped), uint8 {0,1} adjacency panels. Resident bytes and
streamed bytes drop 2-4x; results are bit-equal to f32 wherever values fit.

Bit-equality with the single-device wavefront is *by construction*, not by
luck: distances are small integers and multiplicities are integer counts,
so every partial sum an f32 row-shard or K-panel produces is exact while
counts stay below 2**24 — splitting the M rows over devices or the K
reduction over panels (or the composed engine's ring order) cannot change
a single bit. (ECMP loads divide by sigma, so the sharded accumulation
matches to f32 round-off, not bitwise.)

The module is import-light: jax device state is only touched when an engine
actually runs, so `XLA_FLAGS` recipes keep working.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, Iterator, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ...device import resolve_interpret
from .wavefront import pad_block, pad_operand

__all__ = [
    "ROW_AXIS", "device_mesh", "default_mesh", "best_shard_count",
    "pad_block_sharded",
    "dist_mult_sharded", "sharded_dist_mult", "ecmp_loads_sharded",
    "tiled_dist_mult", "tiled_dist_mult_tiles", "tiled_summary",
    "composed_dist_mult_tiles",
    "bfs_dist_sigma", "widest_divisor_block",
]

_INF = np.float32(np.inf)

#: the one mesh axis every sharded engine uses (1-D row mesh)
ROW_AXIS = "rows"

#: f32 row/col tile width every padded size is a multiple of
_TILE = 128

#: widest block edge of the streaming engines' kernels: a (512, 512) f32
#: output block with its double-buffered operands fits v5e's 16 MiB scoped
#: VMEM; the TPU compiler refuses a 2048-wide one
_MAX_BLOCK = 512

#: dense adjacency larger than this (bytes) makes the tiled engine stream
#: CSR-built panels instead of keeping the full matrix device-resident
_ADJ_BUDGET = 1 << 28


def _pad128(n: int) -> int:
    """Router count padded up to the f32 lane tile (the one padding rule
    shared by the shard sizer, the tile pump, and the CLI probe)."""
    return max(_TILE, n + ((-n) % _TILE))


# -- mesh plumbing -------------------------------------------------------------

def device_mesh(num_shards: Optional[int] = None):
    """A 1-D ``(rows,)`` mesh over the first ``num_shards`` local devices.

    Returns None when the mesh would be a single device — callers treat
    that as "use the unsharded wavefront engine".
    """
    from jax.sharding import Mesh

    if num_shards is None:
        num_shards = jax.device_count()
    if num_shards <= 1:
        return None
    devs = jax.devices()
    if num_shards > len(devs):
        raise ValueError(f"mesh wants {num_shards} devices, "
                         f"only {len(devs)} visible")
    return Mesh(np.array(devs[:num_shards]), (ROW_AXIS,))


def best_shard_count(n: int, max_shards: Optional[int] = None) -> int:
    """Largest useful shard count for an n-router problem.

    Each shard must own at least one full (128, N) f32 row tile of the
    padded problem, so P is capped at ``pad128(n) / 128`` (and at the
    visible device count).
    """
    if max_shards is None:
        max_shards = jax.device_count()
    return max(1, min(int(max_shards), _pad128(n) // _TILE))


def default_mesh(n: Optional[int] = None):
    """The mesh `AnalysisEngine` / `sweep` pick up automatically: all local
    devices when more than one is visible (capped so every shard keeps a
    whole row tile), else None."""
    if jax.device_count() <= 1:
        return None
    return device_mesh(best_shard_count(n) if n is not None
                       else jax.device_count())


def pad_block_sharded(n: int, num_shards: int, block: Optional[int] = None,
                      batched: bool = False) -> Tuple[int, int, int]:
    """(padded size, row block, col block) for an n-router problem split
    row-wise over ``num_shards`` devices.

    The padded size is a multiple of ``num_shards * 128`` so every shard
    owns whole f32 row tiles; extra padding rows are inert phantom routers
    exactly like the unsharded engine's. The col block matches the
    unsharded engine's tuned choice whenever it still divides, which keeps
    the K-reduction blocking — and therefore dist/mult bitwise — identical
    to the single-device path.
    """
    p, block = pad_block(n, block, batched=batched)
    p += (-p) % (num_shards * _TILE)
    col = block if p % block == 0 else _TILE
    rows = p // num_shards
    row = col if rows % col == 0 else _TILE
    return p, row, col


def _fit_sharded(p: int, num_shards: int, block: Optional[int],
                 batched: bool) -> Tuple[int, int]:
    """(row block, col block) that tile an already-padded size p split over
    ``num_shards`` — never re-pads, so pre-padded operands keep their shape."""
    if p % (num_shards * _TILE):
        raise ValueError(f"operand size {p} is not a multiple of "
                         f"{num_shards} shards x {_TILE} — pad with "
                         f"pad_block_sharded() first")
    if block is None:
        block = pad_block(p, batched=batched)[1]
    col = block if p % block == 0 else _TILE
    rows = p // num_shards
    row = col if rows % col == 0 else _TILE
    return row, col


# -- sharded wavefront: dist + mult --------------------------------------------

@functools.lru_cache(maxsize=None)
def _dist_mult_sharded_fn(mesh, batched: bool, bm: int, block: int,
                          interpret: bool, telemetry: bool = False):
    from jax.sharding import PartitionSpec as P

    from ... import kernels

    step = (kernels.semiring.frontier_step_batched_pallas if batched
            else kernels.semiring.frontier_step_pallas)
    num_shards = mesh.shape[ROW_AXIS]

    def local(adj: jnp.ndarray):
        # adj is the full replicated (.., p, p) adjacency; this shard owns
        # rows [r0, r0 + rows) of dist / mult / frontier
        p = adj.shape[-1]
        rows = p // num_shards
        r0 = jax.lax.axis_index(ROW_AXIS) * rows
        rr = jax.lax.broadcasted_iota(jnp.int32, (rows, p), 0) + r0
        cc = jax.lax.broadcasted_iota(jnp.int32, (rows, p), 1)
        eye = jnp.broadcast_to((rr == cc).astype(jnp.float32),
                               adj.shape[:-2] + (rows, p))
        dist0 = jnp.where(eye > 0, 0.0, _INF)

        if not telemetry:
            def cond(state):
                level, _, _, _, more = state
                return more & (level <= p)

            def body(state):
                level, dist, mult, frontier, _ = state
                x = step(frontier, adj, dist, bm=bm, bn=block, bk=block,
                         interpret=interpret)
                new = x > 0
                dist = jnp.where(new, level.astype(jnp.float32), dist)
                mult = mult + x
                # the ONE per-level collective: did any shard reach a new
                # pair?
                more = jax.lax.psum(new.any().astype(jnp.int32),
                                    ROW_AXIS) > 0
                return level + 1, dist, mult, x, more

            _, dist, mult, _, _ = jax.lax.while_loop(
                cond, body, (jnp.int32(1), dist0, eye, eye, jnp.bool_(True)))
            return dist, mult

        # telemetry variant: the per-level psum widens from the one-int
        # convergence flag to the global newly-reached counts (one extra
        # int per stacked problem per level — still collective-only, no
        # host callback); `telemetry` keys the lru cache so the plain
        # jaxpr stays byte-identical
        sizes0 = jnp.zeros((p + 1, adj.shape[0]) if batched else (p + 1,),
                           jnp.int32)

        def cond(state):
            level, _, _, _, more, _ = state
            return more & (level <= p)

        def body(state):
            level, dist, mult, frontier, _, sizes = state
            x = step(frontier, adj, dist, bm=bm, bn=block, bk=block,
                     interpret=interpret)
            new = x > 0
            dist = jnp.where(new, level.astype(jnp.float32), dist)
            mult = mult + x
            cnt = jax.lax.psum(
                jnp.sum(new, axis=(-2, -1), dtype=jnp.int32), ROW_AXIS)
            sizes = sizes.at[level].set(cnt)
            more = (cnt.sum() if batched else cnt) > 0
            return level + 1, dist, mult, x, more, sizes

        level, dist, mult, _, _, sizes = jax.lax.while_loop(
            cond, body,
            (jnp.int32(1), dist0, eye, eye, jnp.bool_(True), sizes0))
        return dist, mult, (level - 1, sizes)

    lead = (None,) * (1 if batched else 0)
    out_spec = P(*lead, ROW_AXIS, None)
    out_specs = ((out_spec, out_spec, (P(), P()))
                 if telemetry else (out_spec, out_spec))
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P(*lead, None, None),),
                       out_specs=out_specs, check_vma=False)
    return jax.jit(fn)


def dist_mult_sharded(adj: jnp.ndarray, mesh, bm: Optional[int] = None,
                      block: Optional[int] = None,
                      interpret: Optional[bool] = None,
                      telemetry: bool = False):
    """Row-sharded hop distances + multiplicities, fully on the mesh.

    ``adj`` is a (p, p) or stacked (B, p, p) {0,1} float adjacency whose
    size is a multiple of ``mesh_size * 128`` (see
    :func:`pad_block_sharded`; padding rows/cols must be zero). Returns
    row-sharded device arrays (dist, mult) bit-equal to
    `wavefront.dist_mult_device` on the same operand. One jitted call; the
    level loop never leaves the mesh.

    ``telemetry=True`` additionally returns the replicated aux pair
    ``(levels, sizes)`` — globally psum'd per-level newly-reached pair
    counts, same convention as `wavefront.dist_mult_device`.
    """
    interpret = resolve_interpret(interpret)
    p = adj.shape[-1]
    num_shards = mesh.shape[ROW_AXIS]
    batched = adj.ndim == 3
    row, col = _fit_sharded(p, num_shards, block, batched)
    if bm is not None and (p // num_shards) % bm == 0:
        row = bm
    return _dist_mult_sharded_fn(mesh, batched, row, col, interpret,
                                 telemetry)(adj)


def sharded_dist_mult(adj: np.ndarray, mesh=None,
                      block: Optional[int] = None
                      ) -> Tuple[np.ndarray, np.ndarray]:
    """Host convenience wrapper: pad -> sharded engine -> sliced np arrays.

    The sharded mirror of `wavefront.wavefront_dist_mult`; with
    ``mesh=None`` (or a would-be single-device mesh) it simply delegates
    there, so a P=1 "mesh" is the unsharded path by construction. Under an
    enabled `repro.obs` tracer the call is spanned and the mesh-wide
    telemetry (levels, frontier sizes) lands in the span attributes.
    """
    from ... import obs
    from .paths import _warn_if_inexact
    from .wavefront import telemetry_attrs, wavefront_dist_mult

    if mesh is None:
        return wavefront_dist_mult(adj, block=block)
    adj = np.asarray(adj, np.float32)
    n = adj.shape[-1]
    num_shards = mesh.shape[ROW_AXIS]
    if num_shards <= 1:
        return wavefront_dist_mult(adj, block=block)
    p, _, block = pad_block_sharded(n, num_shards, block,
                                    batched=adj.ndim == 3)
    tel = obs.enabled()
    with obs.span("wavefront.dist_mult_sharded", routers=n, padded=p,
                  block=block, shards=num_shards,
                  batched=adj.ndim == 3) as sp:
        padded = pad_operand(adj, p, 0.0)
        obs.record_h2d(padded.nbytes, "adjacency")
        out = dist_mult_sharded(jnp.asarray(padded), mesh, block=block,
                                telemetry=tel)
        if tel:
            dist, mult, aux = out
            sp.set(**telemetry_attrs(aux))
        else:
            dist, mult = out
        sl = (Ellipsis, slice(None, n), slice(None, n))
        mult = np.asarray(mult)[sl]
        dist = np.asarray(dist)[sl]
    _warn_if_inexact(mult, use_kernel=True)
    return dist, mult


# -- sharded Brandes ECMP loads ------------------------------------------------

@functools.lru_cache(maxsize=None)
def _ecmp_sharded_fn(mesh, batched: bool, bm: int, block: int,
                     interpret: bool):
    from jax.sharding import PartitionSpec as P

    from ...kernels.semiring import (COUNTING, semiring_matmul_batched_pallas,
                                     semiring_matmul_pallas)

    mm = semiring_matmul_batched_pallas if batched else semiring_matmul_pallas

    def count(a, b, bm_, bk_):
        (out,) = mm(COUNTING, (a,), (b,), bm=bm_, bn=block, bk=bk_,
                    interpret=interpret)
        return out

    def local(dist, mult, adj):
        # dist/mult are this shard's (.., rows, p) source blocks; adj is the
        # full replicated (.., p, p) adjacency. Each shard accumulates the
        # Brandes load partial over ITS sources; one psum at the end sums
        # the partials into the global directed load matrix.
        p = adj.shape[-1]
        finite = jnp.isfinite(dist)
        diam = jax.lax.pmax(
            jnp.max(jnp.where(finite, dist, 0.0)).astype(jnp.int32), ROW_AXIS)
        sigma_inv = jnp.where(finite & (mult > 0),
                              1.0 / jnp.where(mult > 0, mult, 1.0), 0.0)
        delta0 = jnp.zeros_like(dist)
        acc0 = jnp.zeros(adj.shape, jnp.float32)

        def cond(state):
            a, _, _ = state
            return a >= 0

        def body(state):
            a, delta, acc = state
            af = a.astype(jnp.float32)
            z = jnp.where(dist == af + 1.0, (1.0 + delta) * sigma_inv, 0.0)
            f_a = jnp.where(dist == af, mult, 0.0)
            # (p, rows) @ (rows, p): contracts this shard's source rows
            acc = acc + count(jnp.swapaxes(f_a, -1, -2), z, block, bm)
            delta = jnp.where(dist == af, mult * count(z, adj, bm, block),
                              delta)
            return a - 1, delta, acc

        _, _, acc = jax.lax.while_loop(cond, body, (diam - 1, delta0, acc0))
        return adj * jax.lax.psum(acc, ROW_AXIS)

    lead = (None,) * (1 if batched else 0)
    row_spec = P(*lead, ROW_AXIS, None)
    rep_spec = P(*lead, None, None)
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(row_spec, row_spec, rep_spec),
                       out_specs=rep_spec, check_vma=False)
    return jax.jit(fn)


def ecmp_loads_sharded(dist: jnp.ndarray, mult: jnp.ndarray,
                       adj: jnp.ndarray, mesh,
                       block: Optional[int] = None,
                       interpret: Optional[bool] = None) -> jnp.ndarray:
    """Directed ECMP loads under uniform all-pairs demand, shard-local
    Brandes accumulation + one psum.

    ``dist``/``mult`` are row-sharded (the arrays `dist_mult_sharded`
    returns — resharding replicated inputs is also fine), ``adj`` the
    replicated padded adjacency. Returns the replicated (.., p, p) load
    matrix; matches `wavefront.ecmp_loads_device` to f32 round-off (the
    per-source partials are summed in a different order).
    """
    interpret = resolve_interpret(interpret)
    p = adj.shape[-1]
    num_shards = mesh.shape[ROW_AXIS]
    batched = adj.ndim == 3
    row, col = _fit_sharded(p, num_shards, block, batched)
    return _ecmp_sharded_fn(mesh, batched, row, col, interpret)(
        dist, mult, adj)


# -- tiled out-of-core engine --------------------------------------------------

def _csr_panel(indptr: np.ndarray, indices: np.ndarray, n: int, k0: int,
               k1: int, out: np.ndarray) -> np.ndarray:
    """Fill ``out[:k1-k0, :n]`` with dense adjacency rows k0..k1 from CSR.

    ``out`` is the reused pinned staging buffer of the tile pump — one
    allocation for the whole run; rows past ``k1 - k0`` (panel padding) and
    columns past ``n`` stay zero."""
    out[...] = 0.0
    span = indptr[k0:k1 + 1]
    counts = np.diff(span)
    out[np.repeat(np.arange(k1 - k0), counts),
        indices[span[0]:span[-1]]] = 1.0
    return out


def _adjacency_source(source) -> Tuple[Callable[[int, int, np.ndarray],
                                                np.ndarray], int]:
    """(panel filler, n) from a Graph (CSR rows, nothing dense ever built)
    or a dense (n, n) array (sliced views)."""
    if hasattr(source, "csr"):
        indptr, indices = source.csr()
        n = source.n

        def fill(k0: int, k1: int, out: np.ndarray) -> np.ndarray:
            return _csr_panel(indptr, indices, n, k0, k1, out)

        return fill, n
    dense = np.asarray(source, np.float32)
    n = dense.shape[-1]

    def fill(k0: int, k1: int, out: np.ndarray) -> np.ndarray:
        out[...] = 0.0
        out[:k1 - k0, :n] = dense[k0:k1]
        return out

    return fill, n


def _router_count(source) -> int:
    return source.n if hasattr(source, "csr") else np.asarray(source).shape[-1]


def widest_divisor_block(size: int, cap: int) -> int:
    """Largest multiple of ``_TILE`` <= cap dividing ``size`` — ANY
    multiplier, not just powers of two (3456 = 128 x 27 takes 384, where
    a power-of-two search stops at 128). ``size`` must be a multiple of
    ``_TILE`` (padded extents always are).
    """
    q = size // _TILE
    qcap = max(1, cap // _TILE)
    best = 1
    d = 1
    while d * d <= q:
        if q % d == 0:
            if d <= qcap:
                best = max(best, d)
            if q // d <= qcap:
                best = max(best, q // d)
        d += 1
    return best * _TILE


def _stream_block(size: int, block: Optional[int]) -> int:
    """Kernel block edge for one padded extent of the streaming engines:
    the widest 128-multiple dividing ``size`` up to ``block`` (when given)
    and never past `_MAX_BLOCK`."""
    return widest_divisor_block(size, min(block or _MAX_BLOCK, _MAX_BLOCK))


def _record_panel(nbytes: int, dtype, h2d: bool = True) -> None:
    """Pump accounting shared by the tiled and composed engines: total bytes
    streamed through the staging buffer plus a per-dtype panel-size gauge
    (``h2d=False`` for host-side assembly passes that upload elsewhere)."""
    from ... import obs

    if h2d:
        obs.record_h2d(nbytes, "panel")
    obs.counter("pump.bytes_streamed").add(nbytes)
    obs.gauge(f"pump.panel_mb.{np.dtype(dtype).name}").set(
        round(nbytes / 2**20, 3))


@functools.lru_cache(maxsize=None)
def _panel_accumulate_fn(bm: int, bn: int, bk: int, interpret: bool):
    from ...kernels.semiring import COUNTING, semiring_matmul_pallas

    def run(x, frontier, panel, k0):
        kp = panel.shape[0]
        f_slab = jax.lax.dynamic_slice_in_dim(frontier, k0, kp, axis=1)
        # out_dtype pins the accumulator to f32: packed pumps dot a uint32
        # frontier against a uint8 panel (the kernel casts in-register) and
        # the cross-panel accumulator must stay the exact f32 partial sum
        (term,) = semiring_matmul_pallas(
            COUNTING, (f_slab,), (panel,), bm=bm, bn=bn,
            bk=min(bk, kp), interpret=interpret, out_dtype=jnp.float32)
        return x + term

    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _tile_level_fn(bm: int, bn: int, bk: int, interpret: bool,
                   packed: bool = False):
    from ...kernels.semiring import (frontier_step_packed_pallas,
                                     frontier_step_pallas)

    if packed:
        def run(frontier, adj, dist, mult, level):
            x = frontier_step_packed_pallas(frontier, adj, dist, bm=bm,
                                            bn=bn, bk=bk,
                                            interpret=interpret)
            new = x > 0
            dist = jnp.where(new, level.astype(jnp.int16), dist)
            return dist, mult + x, x, new.any()

        return jax.jit(run)

    def run(frontier, adj, dist, mult, level):
        x = frontier_step_pallas(frontier, adj, dist, bm=bm, bn=bn,
                                 bk=bk, interpret=interpret)
        new = x > 0
        dist = jnp.where(new, level.astype(jnp.float32), dist)
        return dist, mult + x, x, new.any()

    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _mask_update_fn(packed: bool = False):
    from ...kernels.semiring import DIST_UNREACHED, MULT_SAT

    if packed:
        def run(x, dist, mult, level):
            # x is the exact f32 panel-accumulated product; clamp at
            # MULT_SAT on the way into the uint32 cell — saturate, never
            # wrap (a stored MULT_SAT marks a lower bound)
            new = (x > 0) & (dist == DIST_UNREACHED)
            xs = jnp.where(new, jnp.minimum(x, float(MULT_SAT)),
                           0.0).astype(jnp.uint32)
            dist = jnp.where(new, level.astype(jnp.int16), dist)
            return dist, mult + xs, xs, new.any()

        return jax.jit(run)

    def run(x, dist, mult, level):
        new = (x > 0) & ~jnp.isfinite(dist)
        x = jnp.where(new, x, 0.0)
        dist = jnp.where(new, level.astype(jnp.float32), dist)
        return dist, mult + x, x, new.any()

    return jax.jit(run)


def _resolve_source_ids(n: int, sources, source_ids
                        ) -> Tuple[np.ndarray, Optional[int]]:
    """(ids array, base) for the tile pumps: ``base`` is the router id of
    the first row when the ids are a contiguous range (yields stay absolute
    row indices, the legacy contract) and None for an arbitrary id list
    (yields index the ids list)."""
    if source_ids is not None:
        if sources is not None:
            raise ValueError("pass sources=(lo, hi) or source_ids=, "
                             "not both")
        ids = np.asarray(source_ids, np.int64).ravel()
        if len(ids) == 0 or ids.min() < 0 or ids.max() >= n:
            raise ValueError(f"source_ids must be non-empty router ids "
                             f"in [0, {n})")
        return ids, None
    lo, hi = (0, n) if sources is None else sources
    if not (0 <= lo < hi <= n):
        raise ValueError(f"sources {sources!r} outside [0, {n})")
    return np.arange(lo, hi, dtype=np.int64), lo


def _seed_tile(ids: np.ndarray, tp: int, pc: int, packed: bool):
    """(frontier/mult seed, dist seed) host arrays for one source tile:
    rows 0..len(ids) seed router columns ``ids``; padding rows are inert
    phantom sources (frontier 0 everywhere, dist all-unreached)."""
    from ...kernels.semiring import DIST_UNREACHED

    t = len(ids)
    if packed:
        eye = np.zeros((tp, pc), np.uint32)
        seed = np.full((tp, pc), DIST_UNREACHED, np.int16)
        eye[np.arange(t), ids] = 1
        seed[np.arange(t), ids] = 0
    else:
        eye = np.zeros((tp, pc), np.float32)
        seed = np.full((tp, pc), np.inf, np.float32)
        eye[np.arange(t), ids] = 1.0
        seed[np.arange(t), ids] = 0.0
    return eye, seed


def _tile_shape(t: int, cap: int = 512) -> Tuple[int, int]:
    """(padded tile rows, row block) for a t-row source tile."""
    if t <= cap:
        tp = t + ((-t) % 8)       # f32 sublane tile; one row block
        return tp, tp
    # big tiles pad to the 128 lane tile (<= 127 phantom rows) so the row
    # block never degrades below 128
    tp = _pad128(t)
    return tp, widest_divisor_block(tp, cap)


def tiled_dist_mult_tiles(
        source, tile_rows: int = 512, panel_rows: Optional[int] = None,
        sources: Optional[Tuple[int, int]] = None,
        source_ids=None,
        adjacency_budget: int = _ADJ_BUDGET,
        block: Optional[int] = None, interpret: Optional[bool] = None,
        packed: bool = False, mesh=None,
) -> Iterator[Tuple[int, int, np.ndarray, np.ndarray]]:
    """Out-of-core exact dist+mult, one source tile at a time.

    Yields ``(r0, r1, dist_tile, mult_tile)`` with (r1 - r0, n) tiles for
    source rows [r0, r1) — bit-equal to the corresponding rows of
    `wavefront.wavefront_dist_mult` (integer-valued f32 partials are exact,
    so neither the row tiling nor the K-panel split changes a bit).

    ``source`` is a Graph (adjacency panels built from CSR on the fly; the
    dense N x N matrix never exists) or a dense (n, n) array. When the full
    padded adjacency fits ``adjacency_budget`` bytes it is uploaded once
    and each level runs the fused ``frontier_step`` kernel; past the budget
    the engine streams ``(panel_rows, n)`` panels through one reused host
    staging buffer and applies the first-reach mask on the accumulated
    (tile, n) product — peak memory O(tile_rows x n + panel_rows x n).

    ``sources=(lo, hi)`` restricts to a row range (tiles are independent,
    so out-of-core runs shard trivially across processes too);
    ``source_ids=`` takes an arbitrary id array instead (the sampled-
    sources estimator's path) — tiles then cover ``ids[r0:r1]`` and the
    yielded (r0, r1) index the ids list, not router rows. ``packed=True``
    shrinks every cell (uint8 panels, int16 dist, uint32 mult saturating at
    MULT_SAT — see the module docstring) and yields (int16, uint32) tiles.
    ``mesh=`` composes with a multi-device row mesh: the pump delegates to
    :func:`composed_dist_mult_tiles`, which shards the adjacency panels
    over the mesh instead of streaming them through one device.
    """
    if mesh is not None and mesh.shape[ROW_AXIS] > 1:
        yield from composed_dist_mult_tiles(
            source, mesh, tile_rows=tile_rows, panel_rows=panel_rows,
            sources=sources, source_ids=source_ids,
            adjacency_budget=adjacency_budget, block=block,
            interpret=interpret, packed=packed)
        return
    interpret = resolve_interpret(interpret)
    fill, n = _adjacency_source(source)
    pc = _pad128(n)                                # padded column count
    bn = bk = _stream_block(pc, block)
    ids_all, base = _resolve_source_ids(n, sources, source_ids)
    tile_rows = max(1, min(tile_rows, len(ids_all)))

    from ... import obs

    adtype = np.uint8 if packed else np.float32
    abytes = np.dtype(adtype).itemsize
    stream = pc * pc * abytes > adjacency_budget
    if panel_rows is None:
        panel_rows = min(pc, max(_TILE,
                                 adjacency_budget // (8 * pc * abytes)))
    # panels must tile the padded width exactly (uniform K-slabs, one jit):
    # round down to the largest 128-multiple that divides pc
    panel_rows = max(_TILE, min(pc, panel_rows) - (min(pc, panel_rows) % _TILE))
    while pc % panel_rows:
        panel_rows -= _TILE
    # the panel product's K dimension is panel_rows, not pc — its K block
    # must divide THAT (panel_rows | pc, so this also divides pc)
    bk_panel = widest_divisor_block(panel_rows, bk)
    panel_buf = np.zeros((panel_rows, pc), adtype)   # the pinned pump
    adj_dev = None
    if not stream:
        # whole padded adjacency fits: build it panel-wise into one device
        # upload, then every level is a single fused frontier_step
        adj_host = np.zeros((pc, pc), adtype)
        for k0 in range(0, n, panel_rows):
            k1 = min(n, k0 + panel_rows)
            adj_host[k0:k1] = fill(k0, k1, panel_buf)[:k1 - k0]
            _record_panel(panel_buf.nbytes, adtype, h2d=False)
        obs.record_h2d(adj_host.nbytes, "adjacency")
        adj_dev = jnp.asarray(adj_host)
        del adj_host
    # panels re-read per level in streaming mode; precompute the schedule
    # (panels fully inside the column padding are all-zero: skipped)
    panels = [(k0, min(n, k0 + panel_rows))
              for k0 in range(0, pc, panel_rows) if k0 < n]
    max_level = min(n, 32766) if packed else n

    for c0 in range(0, len(ids_all), tile_rows):
        ids = ids_all[c0:c0 + tile_rows]
        t = len(ids)
        r0 = c0 if base is None else base + c0
        r1 = r0 + t
        tp, bm = _tile_shape(t)
        with obs.span("tiled.tile", cat="tiled", r0=r0, r1=r1,
                      streamed=stream, packed=packed) as sp:
            eye, seed = _seed_tile(ids, tp, pc, packed)
            obs.record_h2d(eye.nbytes + seed.nbytes, "tile_seed")
            dist = jnp.asarray(seed)
            mult = jnp.asarray(eye)
            frontier = mult
            level_fused = _tile_level_fn(bm, bn, bk, interpret, packed)
            level_masked = _mask_update_fn(packed)
            panel_acc = _panel_accumulate_fn(bm, bn, bk_panel, interpret)

            pumped = 0
            level = 1
            while level <= max_level:
                lv = jnp.int32(level)
                if stream:
                    x = jnp.zeros((tp, pc), jnp.float32)
                    for k0, k1 in panels:
                        # upload a NUMPY copy: big host arrays go to the
                        # CPU "device" zero-copy (even under
                        # jnp.array(copy=True)), and the pump mutates the
                        # staging buffer for the next panel while this
                        # product is still in flight — only a host-side
                        # copy actually pins this panel's bytes
                        panel = jnp.asarray(fill(k0, k1, panel_buf).copy())
                        _record_panel(panel.nbytes, adtype)
                        x = panel_acc(x, frontier, panel, jnp.int32(k0))
                        pumped += 1
                    dist, mult, frontier, more = level_masked(x, dist, mult,
                                                              lv)
                else:
                    dist, mult, frontier, more = level_fused(
                        frontier, adj_dev, dist, mult, lv)
                if not bool(more):
                    break
                level += 1
            sp.set(levels=level, panels_pumped=pumped)
            yield r0, r1, np.asarray(dist)[:t, :n], np.asarray(mult)[:t, :n]


def tiled_dist_mult(source, tile_rows: int = 512,
                    panel_rows: Optional[int] = None,
                    out: Optional[Tuple[np.ndarray, np.ndarray]] = None,
                    **kw) -> Tuple[np.ndarray, np.ndarray]:
    """Assembled (n, n) dist+mult through the tiled engine.

    This materializes two full N x N host matrices — the point of the tiled
    engine is that the *device* never does; pass ``out=(dist, mult)``
    (e.g. np.memmap pair) to keep the host side out-of-core as well, or use
    :func:`tiled_dist_mult_tiles` / :func:`tiled_summary` to avoid the
    N x N buffers entirely.
    """
    from .paths import _warn_if_inexact

    n = _router_count(source)
    packed = bool(kw.get("packed"))
    if out is None:
        if packed:
            out = (np.empty((n, n), np.int16), np.empty((n, n), np.uint32))
        else:
            out = (np.empty((n, n), np.float32),
                   np.empty((n, n), np.float32))
    dist, mult = out
    for r0, r1, d, m in tiled_dist_mult_tiles(source, tile_rows, panel_rows,
                                              **kw):
        dist[r0:r1] = d
        mult[r0:r1] = m
    if not packed:
        # packed counts saturate (clamp + warn in the pump) instead of
        # losing f32 integer precision, so the 2**24 exactness check is an
        # f32-engine concern only
        _warn_if_inexact(mult, use_kernel=True)
    return dist, mult


def _peak_rss_mb() -> float:
    from ... import obs

    # the platform quirks (macOS bytes vs KiB) live in the obs samplers now
    return obs.peak_rss_mb()


def tiled_summary(source, tile_rows: int = 512,
                  panel_rows: Optional[int] = None,
                  sources: Optional[Tuple[int, int]] = None,
                  on_tile=None, checkpoint=None, **kw) -> Dict[str, object]:
    """Streaming aggregate of the tiled engine — no N x N buffer anywhere.

    Folds each (tile, n) dist/mult tile into diameter, reached-pair count,
    average shortest-path length and multiplicity stats, and reports the
    measured peak RSS next to what the single-buffer device engine would
    need (its while_loop carries adjacency + eye + dist + mult + two
    frontiers: 6 padded N^2 f32 buffers) — the memory-budget evidence for
    the extreme-scale claim, sampled through the structured `repro.obs`
    meters (``tiled.peak_rss_mb`` gauge, ``tiled.tiles`` counter) instead
    of ad-hoc prints.

    ``on_tile(r0, r1, dist, mult)``, when given, sees every tile before it
    is folded — callers spot-check rows without paying a second pass.

    ``checkpoint=`` (a path) makes long runs crash-safe: after every
    folded tile the partial aggregates are persisted atomically
    (`resilience.checkpoint.TileCheckpoint`, write-to-temp + rename), and
    a rerun with the same arguments resumes from the last completed tile —
    tiles are independent and the fold order is preserved, so a
    killed-and-resumed run returns aggregates BIT-identical to an
    uninterrupted one. The file binds to the run via a fingerprint
    (graph/array identity, tile_rows, packed, source selection); a
    mismatched checkpoint raises instead of seeding the wrong run, and a
    completed run removes its file. On resume ``on_tile`` only sees the
    recomputed tiles.
    """
    import time

    from ... import obs
    from ...kernels.semiring import DIST_UNREACHED, MULT_SAT

    n = _router_count(source)
    packed = bool(kw.get("packed"))
    t0 = time.perf_counter()
    diam = 0
    pairs = 0
    dist_sum = 0.0
    mult_sum = 0.0
    mult_min = np.inf
    mult_max = 0.0
    rows_done = 0
    tiles = 0

    source_ids = kw.pop("source_ids", None)
    ids_all, base = _resolve_source_ids(n, sources, source_ids)
    eff_tile = max(1, min(tile_rows, len(ids_all)))
    ckpt = fp = None
    if checkpoint is not None:
        from ..resilience.checkpoint import (TileCheckpoint,
                                             source_fingerprint)

        ckpt = TileCheckpoint(checkpoint)
        fp = source_fingerprint(source, tile_rows, packed, sources=sources,
                                source_ids=source_ids)
        state = ckpt.load(fp)
        if state is not None:
            diam = state["diameter"]
            pairs = state["reached_pairs"]
            dist_sum = state["dist_sum"]
            mult_sum = state["mult_sum"]
            mult_min = np.inf if state["mult_min"] is None else state["mult_min"]
            mult_max = state["mult_max"]
            rows_done = state["rows_done"]
            tiles = state["tiles"]
            obs.log("tiled.resume", checkpoint=str(checkpoint),
                    rows_done=rows_done, tiles=tiles)
    # the pump restarts at the first incomplete tile; rows_done is always
    # a whole number of tiles, so the remaining tile boundaries — and with
    # them every yielded tile — are identical to the uninterrupted run's
    if rows_done and base is not None:
        kw_sel = dict(sources=(base + rows_done, base + len(ids_all)))
    elif rows_done:
        kw_sel = dict(source_ids=ids_all[rows_done:])
    elif source_ids is not None:
        kw_sel = dict(source_ids=source_ids)
    else:
        kw_sel = dict(sources=sources)
    remaining = len(ids_all) - rows_done

    with obs.span("tiled.summary", cat="tiled", routers=n,
                  tile_rows=tile_rows, resumed_rows=rows_done) as sp:
        tile_iter = (tiled_dist_mult_tiles(source, eff_tile, panel_rows,
                                           **kw_sel, **kw)
                     if remaining > 0 else ())
        for r0, r1, d, m in tile_iter:
            if on_tile is not None:
                on_tile(r0, r1, d, m)
            # packed tiles carry the int16 DIST_UNREACHED sentinel instead
            # of +inf (int16 is always "finite")
            if packed:
                off = (d > 0) & (d != DIST_UNREACHED)
            else:
                off = np.isfinite(d) & (d > 0)
            if off.any():
                diam = max(diam, int(d[off].max()))
                pairs += int(off.sum())
                dist_sum += float(d[off].sum())
                mult_sum += float(m[off].sum())
                mult_min = min(mult_min, float(m[off].min()))
                mult_max = max(mult_max, float(m[off].max()))
            rows_done += r1 - r0
            tiles += 1
            obs.counter("tiled.tiles").add()
            obs.sample_process("tiled")
            if ckpt is not None:
                ckpt.save(fp, {
                    "diameter": diam, "reached_pairs": pairs,
                    "dist_sum": dist_sum, "mult_sum": mult_sum,
                    "mult_min": None if mult_min == np.inf else mult_min,
                    "mult_max": mult_max, "rows_done": rows_done,
                    "tiles": tiles,
                })
        sp.set(tiles=tiles, diameter=diam)
    if ckpt is not None:
        ckpt.remove()
    pc = _pad128(n)
    obs.gauge("tiled.peak_rss_mb").set(round(_peak_rss_mb(), 1))
    return {
        "routers": n,
        "rows_analyzed": rows_done,
        "tiles": tiles,
        "tile_rows": tile_rows,
        "diameter": diam,
        "reached_pairs": pairs,
        "avg_spl": dist_sum / pairs if pairs else 0.0,
        "mult_mean": mult_sum / pairs if pairs else 0.0,
        "mult_min": 0.0 if pairs == 0 else mult_min,
        "mult_max": mult_max,
        "elapsed_s": round(time.perf_counter() - t0, 2),
        "peak_rss_mb": round(_peak_rss_mb(), 1),
        "single_buffer_mb": round(6 * pc * pc * 4 / 2**20, 1),
        "packed": packed,
        "saturated": bool(packed and mult_max >= MULT_SAT),
    }


# -- composed engine: sharding x streaming -------------------------------------

@functools.lru_cache(maxsize=None)
def _dist_mult_composed_fn(mesh, bm: int, bn: int, bk_panel: int,
                           interpret: bool, packed: bool):
    """The composed level loop: row-sharded state x ring-rotated sharded
    adjacency panels, ONE jitted `lax.while_loop` with the psum convergence
    flag. Each shard holds its (kp, p) adjacency rows resident; per level a
    `fori_loop` ring (`lax.ppermute`) walks every K-slab past every shard,
    so the full product is accumulated without a dense or replicated
    adjacency ever existing."""
    from jax.sharding import PartitionSpec as P

    from ...kernels.semiring import (COUNTING, DIST_UNREACHED, MULT_SAT,
                                     semiring_matmul_pallas)

    num_shards = mesh.shape[ROW_AXIS]
    fwd = [(i, (i - 1) % num_shards) for i in range(num_shards)]

    def local(adj_panel, eye, dist0):
        # adj_panel: this shard's (kp, p) adjacency rows (kp = p / P);
        # eye / dist0: this shard's (rows, p) slice of the source tile
        kp, p = adj_panel.shape
        rows = eye.shape[0]
        me = jax.lax.axis_index(ROW_AXIS)
        cap = jnp.int32(min(p, 32766) if packed else p)

        def level_product(frontier):
            # ring step s: this shard holds the panel that started on shard
            # (me + s) % P — multiply the matching frontier K-slab against
            # it, then pass the panel one shard down the ring. After P
            # steps every K-slab has been contracted exactly once and the
            # panels are back home. The ring reorders the K partial sums
            # per shard, which cannot change a bit: the partials are
            # integer-valued f32 (exact below 2**24).
            def ring(s, carry):
                acc, panel = carry
                owner = (me + s) % num_shards
                f_slab = jax.lax.dynamic_slice_in_dim(
                    frontier, owner * kp, kp, axis=1)
                (term,) = semiring_matmul_pallas(
                    COUNTING, (f_slab,), (panel,), bm=bm, bn=bn,
                    bk=bk_panel, interpret=interpret,
                    out_dtype=jnp.float32)
                acc = acc + term
                panel = jax.lax.ppermute(panel, ROW_AXIS, fwd)
                return acc, panel

            acc, _ = jax.lax.fori_loop(
                0, num_shards, ring,
                (jnp.zeros((rows, p), jnp.float32), adj_panel))
            return acc

        if packed:
            def update(x, dist, mult, level):
                new = (x > 0) & (dist == DIST_UNREACHED)
                xs = jnp.where(new, jnp.minimum(x, float(MULT_SAT)),
                               0.0).astype(jnp.uint32)
                dist = jnp.where(new, level.astype(jnp.int16), dist)
                return dist, mult + xs, xs, new
        else:
            def update(x, dist, mult, level):
                new = (x > 0) & ~jnp.isfinite(dist)
                xf = jnp.where(new, x, 0.0)
                dist = jnp.where(new, level.astype(jnp.float32), dist)
                return dist, mult + xf, xf, new

        def cond(state):
            level, _, _, _, more, _ = state
            return more & (level <= cap)

        def body(state):
            level, dist, mult, frontier, _, sat = state
            x = level_product(frontier)
            dist, mult, frontier, new = update(x, dist, mult, level)
            if packed:
                sat = sat | jnp.any(frontier == MULT_SAT)
            # the ONE per-level collective beyond the ring: did any shard
            # reach a new pair?
            more = jax.lax.psum(new.any().astype(jnp.int32), ROW_AXIS) > 0
            return level + 1, dist, mult, frontier, more, sat

        _, dist, mult, _, _, sat = jax.lax.while_loop(
            cond, body,
            (jnp.int32(1), dist0, eye, eye, jnp.bool_(True),
             jnp.bool_(False)))
        sat = jax.lax.psum(sat.astype(jnp.int32), ROW_AXIS) > 0
        return dist, mult, sat

    row = P(ROW_AXIS, None)
    fn = jax.shard_map(local, mesh=mesh, in_specs=(row, row, row),
                       out_specs=(row, row, P()), check_vma=False)
    return jax.jit(fn)


def _build_sharded_adjacency(fill, n: int, p: int, mesh, panel_rows: int,
                             adtype):
    """Assemble the row-sharded (p, p) adjacency: each shard's (kp, p) row
    block is built from CSR through the reused staging buffer and placed on
    its device, then the blocks join into one sharded jax.Array. The dense
    p x p matrix never exists on the host (peak host transient: one shard
    block) and no device ever holds more than its own rows."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from ... import obs

    num_shards = mesh.shape[ROW_AXIS]
    kp = p // num_shards
    devices = list(mesh.devices.flat)
    panel_buf = np.zeros((panel_rows, p), adtype)     # the pinned pump
    shards = []
    for d, dev in enumerate(devices):
        # a fresh host block per shard: device_put on host-backed devices
        # can alias the numpy buffer, so the block must never be reused
        block_host = np.zeros((kp, p), adtype)
        g0 = d * kp
        for k0 in range(g0, min(n, g0 + kp), panel_rows):
            k1 = min(n, g0 + kp, k0 + panel_rows)
            block_host[k0 - g0:k1 - g0] = fill(k0, k1, panel_buf)[:k1 - k0]
            _record_panel(panel_buf.nbytes, adtype, h2d=False)
        obs.record_h2d(block_host.nbytes, "adjacency_shard")
        shards.append(jax.device_put(block_host, dev))
        del block_host
    obs.gauge("composed.shard_panel_mb").set(round(kp * p * np.dtype(
        adtype).itemsize / 2**20, 1))
    sharding = NamedSharding(mesh, P(ROW_AXIS, None))
    return jax.make_array_from_single_device_arrays((p, p), sharding, shards)


def composed_dist_mult_tiles(
        source, mesh, tile_rows: int = 512,
        panel_rows: Optional[int] = None,
        sources: Optional[Tuple[int, int]] = None,
        source_ids=None,
        adjacency_budget: int = _ADJ_BUDGET,
        block: Optional[int] = None, interpret: Optional[bool] = None,
        packed: bool = False,
) -> Iterator[Tuple[int, int, np.ndarray, np.ndarray]]:
    """Sharding x streaming composed dist+mult, one source tile at a time.

    The extreme-scale pump: the adjacency lives row-sharded on the mesh —
    shard d owns rows [d*N/P, (d+1)*N/P), built from CSR through the reused
    staging buffer, so neither the dense N x N matrix nor a replicated
    per-device copy ever exists — and source tiles stream through the mesh
    with each shard owning its (tile/P, N) dist/mult rows. The level loop
    is ONE jitted `shard_map` `lax.while_loop` with the psum convergence
    flag; a `lax.ppermute` ring rotates the adjacency panels inside it (see
    :func:`_dist_mult_composed_fn`). Yields the same
    ``(r0, r1, dist_tile, mult_tile)`` contract as
    :func:`tiled_dist_mult_tiles`, bit-equal to it and to the single-device
    wavefront where values fit.

    ``adjacency_budget`` bounds the PER-DEVICE resident panel
    (N^2/P x cell bytes). Past it, there is no in-loop streaming fallback —
    the single `while_loop` admits no host callbacks — so the call raises
    with the knobs that fit: more shards, ``packed=True`` (uint8 panels,
    4x), a larger budget, or the single-device streaming engine
    (``mesh=None``).
    """
    num_shards = mesh.shape[ROW_AXIS] if mesh is not None else 1
    if num_shards <= 1:
        yield from tiled_dist_mult_tiles(
            source, tile_rows=tile_rows, panel_rows=panel_rows,
            sources=sources, source_ids=source_ids,
            adjacency_budget=adjacency_budget, block=block,
            interpret=interpret, packed=packed)
        return
    interpret = resolve_interpret(interpret)
    fill, n = _adjacency_source(source)
    p = _pad128(n)
    p += (-p) % (num_shards * _TILE)
    kp = p // num_shards
    adtype = np.uint8 if packed else np.float32
    abytes = np.dtype(adtype).itemsize
    if kp * p * abytes > adjacency_budget:
        need = kp * p * abytes
        raise ValueError(
            f"composed engine: per-device adjacency panel needs {need} "
            f"bytes ({need / 2**20:.0f} MiB) > adjacency_budget "
            f"{adjacency_budget} — use more shards, packed=True (uint8 "
            f"panels), a larger budget, or the single-device streaming "
            f"engine (mesh=None)")
    bn = _stream_block(p, block)
    bk_panel = _stream_block(kp, block)
    if panel_rows is None:
        panel_rows = min(kp, max(_TILE, adjacency_budget // (8 * p * abytes)))
    panel_rows = max(_TILE,
                     min(kp, panel_rows) - (min(kp, panel_rows) % _TILE))
    while kp % panel_rows:
        panel_rows -= _TILE

    from ... import obs
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    ids_all, base = _resolve_source_ids(n, sources, source_ids)
    tile_rows = max(1, min(tile_rows, len(ids_all)))
    row_sharding = NamedSharding(mesh, P(ROW_AXIS, None))

    with obs.span("composed.build", cat="composed", routers=n, padded=p,
                  shards=num_shards, packed=packed):
        adj = _build_sharded_adjacency(fill, n, p, mesh, panel_rows, adtype)

    for c0 in range(0, len(ids_all), tile_rows):
        ids = ids_all[c0:c0 + tile_rows]
        t = len(ids)
        r0 = c0 if base is None else base + c0
        r1 = r0 + t
        # the tile splits row-wise over the mesh: every shard needs whole
        # 8-row sublane tiles, and the per-shard row block must divide the
        # local row count
        unit = 8 * num_shards
        if t <= 512 * num_shards:
            tp = t + ((-t) % unit)
            bm = tp // num_shards
        else:
            tp = t + ((-t) % (num_shards * _TILE))
            bm = widest_divisor_block(tp // num_shards, _MAX_BLOCK)
        with obs.span("composed.tile", cat="composed", r0=r0, r1=r1,
                      packed=packed) as sp:
            eye, seed = _seed_tile(ids, tp, p, packed)
            obs.record_h2d(eye.nbytes + seed.nbytes, "tile_seed")
            eye_dev = jax.device_put(eye, row_sharding)
            seed_dev = jax.device_put(seed, row_sharding)
            fn = _dist_mult_composed_fn(mesh, bm, bn, bk_panel, interpret,
                                        packed)
            dist, mult, sat = fn(adj, eye_dev, seed_dev)
            sp.set(saturated=bool(sat))
            if bool(sat):
                import warnings

                warnings.warn(
                    "composed engine: a multiplicity reached MULT_SAT "
                    "(2**24) and was clamped — saturated counts are lower "
                    "bounds", RuntimeWarning, stacklevel=2)
            try:
                obs.gauge("composed.device_peak_mb").set(
                    round(obs.device_memory_mb(), 1))
            except Exception:  # noqa: BLE001 - CPU backends lack the stat
                pass
            yield (r0, r1, np.asarray(dist)[:t, :n],
                   np.asarray(mult)[:t, :n])


# -- host oracle ---------------------------------------------------------------

def bfs_dist_sigma(g, s: int) -> Tuple[np.ndarray, np.ndarray]:
    """Single-source hop distances + shortest-path counts over CSR (host
    Brandes forward pass) — the O(E) per-source oracle the tiled and
    sharded engines are spot-checked against at sizes where dense N^2
    references are unaffordable. Returns (dist, sigma) length-n arrays,
    dist +inf where unreachable."""
    indptr, indices = g.csr()
    n = g.n
    dist = np.full(n, np.inf, np.float64)
    sigma = np.zeros(n, np.float64)
    dist[s] = 0.0
    sigma[s] = 1.0
    frontier = [s]
    level = 0
    while frontier:
        level += 1
        nxt: Dict[int, float] = {}
        for u in frontier:
            su = sigma[u]
            for v in indices[indptr[u]:indptr[u + 1]]:
                v = int(v)
                if dist[v] == np.inf or dist[v] == level:
                    dist[v] = level
                    nxt[v] = nxt.get(v, 0.0) + su
        for v, sv in nxt.items():
            sigma[v] += sv
        frontier = list(nxt)
    return dist, sigma


# -- CLI: the extreme-scale demo / memory-budget probe -------------------------

def main(argv=None) -> int:
    """``python -m repro.core.analysis.distributed`` — tiled 16k+ demo.

    Runs the out-of-core engine on a generated topology, spot-checks a few
    sources against the CSR Brandes oracle, and prints the summary JSON
    (incl. measured peak RSS vs the single-buffer requirement). The README
    Performance row and the slow-soak memory test both run through here so
    the published numbers stay reproducible by one command.
    """
    import argparse
    import json

    ap = argparse.ArgumentParser(description=main.__doc__.splitlines()[0])
    ap.add_argument("--family", default="jellyfish")
    ap.add_argument("--routers", type=int, default=16384)
    ap.add_argument("--degree", type=int, default=16)
    ap.add_argument("--tile-rows", type=int, default=256)
    ap.add_argument("--panel-rows", type=int, default=None)
    ap.add_argument("--sources", type=int, default=None,
                    help="analyze only the first K source rows "
                         "(tiles are independent; default: all)")
    ap.add_argument("--adjacency-budget", type=int, default=_ADJ_BUDGET,
                    help="device bytes before adjacency panels stream")
    ap.add_argument("--packed", action="store_true",
                    help="packed cells: uint8 panels, int16 dist, uint32 "
                         "mult saturating at 2**24 (4x less streamed/"
                         "resident memory; bit-exact where values fit)")
    ap.add_argument("--shards", type=int, default=None,
                    help="row-shard over this many devices (composed "
                         "engine); default: single-device streaming")
    ap.add_argument("--block", type=int, default=None,
                    help="widest kernel block edge to use (a 128-multiple; "
                         "the engines never go past 512, the widest that "
                         "fits v5e's scoped VMEM)")
    ap.add_argument("--check", type=int, default=2,
                    help="spot-check this many sources vs the CSR oracle")
    ap.add_argument("--checkpoint", default=None, metavar="FILE.json",
                    help="crash-safe mode: persist partial aggregates "
                         "after every tile (atomic write + rename) and "
                         "resume from the last completed tile on rerun")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="enable tracing and write a Chrome trace-event "
                         "file (load in https://ui.perfetto.dev)")
    args = ap.parse_args(argv)

    from ... import obs
    from .. import topology as topo

    if args.trace:
        obs.enable()

    if args.family == "jellyfish":
        g = topo.make("jellyfish", n=args.routers, r=args.degree, seed=0)
    else:
        g = topo.by_servers(args.family, args.routers)
    srcs = (0, min(args.sources, g.n)) if args.sources else None
    mesh = None
    if args.shards and args.shards > 1:
        mesh = device_mesh(args.shards)

    # the oracle spot-check rides the summary stream itself (on_tile sees
    # every tile before it is folded) — one pass, not two
    check_lo = srcs[0] if srcs else 0
    check_hi = min(check_lo + args.check, g.n) if args.check else check_lo
    checked = [0]

    def spot_check(r0, r1, d, m):
        from ...kernels.semiring import DIST_UNREACHED, MULT_SAT

        for i in range(max(check_lo, r0), min(check_hi, r1)):
            od, osig = bfs_dist_sigma(g, i)
            if args.packed:
                od = np.where(np.isfinite(od), od,
                              DIST_UNREACHED).astype(np.int16)
                osig = np.minimum(osig, MULT_SAT).astype(np.uint32)
            else:
                od = od.astype(np.float32)
                osig = osig.astype(np.float32)
            np.testing.assert_array_equal(d[i - r0], od)
            np.testing.assert_array_equal(m[i - r0], osig)
            checked[0] += 1

    summary = tiled_summary(g, tile_rows=args.tile_rows,
                            panel_rows=args.panel_rows, sources=srcs,
                            adjacency_budget=args.adjacency_budget,
                            packed=args.packed, mesh=mesh, block=args.block,
                            on_tile=spot_check if args.check else None,
                            checkpoint=args.checkpoint)
    if args.check and not args.checkpoint:
        # a checkpoint resume skips completed tiles, so the spot-check may
        # legitimately see fewer sources than requested
        assert checked[0] == check_hi - check_lo, (checked[0], check_lo,
                                                  check_hi)
        obs.log("distributed.check", status="oracle spot-check OK",
                sources=checked[0])
    summary["family"] = g.name
    summary["shards"] = mesh.shape[ROW_AXIS] if mesh is not None else 1
    summary["adjacency_streamed"] = bool(
        mesh is None and _pad128(g.n) ** 2 * (1 if args.packed else 4)
        > args.adjacency_budget)
    print(json.dumps(summary, indent=1))
    if args.trace:
        obs.export(args.trace)
        obs.log("distributed.trace", path=args.trace)
    return 0


if __name__ == "__main__":
    from ...device import use_compile_cache

    use_compile_cache()
    raise SystemExit(main())

"""Jit'd public wrappers for the Pallas kernels.

Every op auto-pads to block multiples, dispatches to the Pallas kernel
(interpreted on the CPU backend, compiled on a TPU — decided once, by
`repro.device.resolve_interpret`), and exposes a ``use_kernel=False``
escape hatch to the jnp oracle in `ref`.

Block shapes (and ``sub_k`` on the VPU path) default to the autotuner's
persisted tuning table (`autotune.resolve`); passing them explicitly always
wins — tests sweep fixed block shapes through the same entry points.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import autotune, ref
from .minplus import minplus_matmul_pallas
from .reachability import reachability_step_pallas
from .seghist import value_histogram_pallas
from .semiring import (BOOLEAN, COUNTING, DIST_UNREACHED, TROPICAL,
                       TROPICAL_COUNT, frontier_step_batched_pallas,
                       frontier_step_packed_batched_pallas,
                       frontier_step_packed_pallas, frontier_step_pallas,
                       semiring_matmul_batched_pallas, semiring_matmul_pallas)

__all__ = ["minplus_matmul", "reachability_step", "value_histogram",
           "count_matmul", "minplus_count_matmul", "frontier_step",
           "frontier_step_packed", "batched_minplus_matmul",
           "batched_count_matmul", "batched_frontier_step",
           "batched_frontier_step_packed"]


def _pad_to(x: jnp.ndarray, bm: int, bn: int, fill) -> jnp.ndarray:
    m, n = x.shape
    pm, pn = (-m) % bm, (-n) % bn
    if pm or pn:
        x = jnp.pad(x, ((0, pm), (0, pn)), constant_values=fill)
    return x


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "sub_k"))
def _minplus_jit(a: jnp.ndarray, b: jnp.ndarray, bm: int, bn: int, bk: int,
                 sub_k: int) -> jnp.ndarray:
    m, n = a.shape[0], b.shape[1]
    ap = _pad_to(a.astype(jnp.float32), bm, bk, TROPICAL.pad_a[0])
    bp = _pad_to(b.astype(jnp.float32), bk, bn, TROPICAL.pad_b[0])
    out = minplus_matmul_pallas(ap, bp, bm=bm, bn=bn, bk=bk, sub_k=sub_k)
    return out[:m, :n]


def minplus_matmul(a: jnp.ndarray, b: jnp.ndarray, bm: int = None,
                   bn: int = None, bk: int = None,
                   sub_k: int = None) -> jnp.ndarray:
    """Tropical (min, +) product with auto-padding (pad value +inf)."""
    cfg = autotune.resolve("minplus", a.shape[0], b.shape[1], a.shape[1],
                           bm=bm, bn=bn, bk=bk, sub_k=sub_k)
    return _minplus_jit(a, b, **cfg)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk"))
def _reachability_jit(a: jnp.ndarray, b: jnp.ndarray, bm: int, bn: int,
                      bk: int) -> jnp.ndarray:
    m, n = a.shape[0], b.shape[1]
    ap = _pad_to(a.astype(jnp.float32), bm, bk, BOOLEAN.pad_a[0])
    bp = _pad_to(b.astype(jnp.float32), bk, bn, BOOLEAN.pad_b[0])
    out = reachability_step_pallas(ap, bp, bm=bm, bn=bn, bk=bk)
    return out[:m, :n]


def reachability_step(a: jnp.ndarray, b: jnp.ndarray, bm: int = None,
                      bn: int = None, bk: int = None) -> jnp.ndarray:
    """Boolean-semiring product of {0,1} float masks, auto-padded with 0."""
    cfg = autotune.resolve("boolean", a.shape[0], b.shape[1], a.shape[1],
                           bm=bm, bn=bn, bk=bk)
    return _reachability_jit(a, b, **cfg)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk"))
def _count_jit(a: jnp.ndarray, b: jnp.ndarray, bm: int, bn: int,
               bk: int) -> jnp.ndarray:
    m, n = a.shape[0], b.shape[1]
    ap = _pad_to(a.astype(jnp.float32), bm, bk, COUNTING.pad_a[0])
    bp = _pad_to(b.astype(jnp.float32), bk, bn, COUNTING.pad_b[0])
    (out,) = semiring_matmul_pallas(COUNTING, (ap,), (bp,), bm=bm, bn=bn, bk=bk)
    return out[:m, :n]


def count_matmul(a: jnp.ndarray, b: jnp.ndarray, bm: int = None,
                 bn: int = None, bk: int = None) -> jnp.ndarray:
    """Counting semiring (+, x) product of f32 counts, auto-padded with 0.

    Runs the MXU path of the generic semiring kernel; exact while counts
    stay below 2**24.
    """
    cfg = autotune.resolve("count", a.shape[0], b.shape[1], a.shape[1],
                           bm=bm, bn=bn, bk=bk)
    return _count_jit(a, b, **cfg)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "sub_k"))
def _minplus_count_jit(da: jnp.ndarray, ca: jnp.ndarray, db: jnp.ndarray,
                       cb: jnp.ndarray, bm: int, bn: int, bk: int,
                       sub_k: int):
    m, n = da.shape[0], db.shape[1]
    dap = _pad_to(da.astype(jnp.float32), bm, bk, TROPICAL_COUNT.pad_a[0])
    cap = _pad_to(ca.astype(jnp.float32), bm, bk, TROPICAL_COUNT.pad_a[1])
    dbp = _pad_to(db.astype(jnp.float32), bk, bn, TROPICAL_COUNT.pad_b[0])
    cbp = _pad_to(cb.astype(jnp.float32), bk, bn, TROPICAL_COUNT.pad_b[1])
    d, c = semiring_matmul_pallas(TROPICAL_COUNT, (dap, cap), (dbp, cbp),
                                  bm=bm, bn=bn, bk=bk, sub_k=sub_k)
    return d[:m, :n], c[:m, :n]


def minplus_count_matmul(da: jnp.ndarray, ca: jnp.ndarray,
                         db: jnp.ndarray, cb: jnp.ndarray,
                         bm: int = None, bn: int = None, bk: int = None,
                         sub_k: int = None):
    """Fused tropical-with-count product over (dist, count) pairs.

    Distances pad with +inf, counts with 0 (so padding never wins a tie).
    Returns (dist, count) arrays.
    """
    cfg = autotune.resolve("minplus_count", da.shape[0], db.shape[1],
                           da.shape[1], bm=bm, bn=bn, bk=bk, sub_k=sub_k)
    return _minplus_count_jit(da, ca, db, cb, **cfg)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk"))
def _frontier_step_jit(f: jnp.ndarray, a: jnp.ndarray, d: jnp.ndarray,
                       bm: int, bn: int, bk: int) -> jnp.ndarray:
    m, n = f.shape[0], a.shape[1]
    fp = _pad_to(f.astype(jnp.float32), bm, bk, 0.0)
    ap = _pad_to(a.astype(jnp.float32), bk, bn, 0.0)
    # dist pads with +inf (unreached); padded counts are 0, so the
    # first-reach mask never fires in the padding
    dp = _pad_to(d.astype(jnp.float32), bm, bn, jnp.inf)
    out = frontier_step_pallas(fp, ap, dp, bm=bm, bn=bn, bk=bk)
    return out[:m, :n]


def frontier_step(f: jnp.ndarray, a: jnp.ndarray, d: jnp.ndarray,
                  bm: int = None, bn: int = None,
                  bk: int = None) -> jnp.ndarray:
    """Fused BFS wavefront step: ``where((F@A > 0) & (D == inf), F@A, 0)``.

    The counting product and the first-reach mask run in one kernel (mask in
    the MXU epilogue); this is the one product the device-resident wavefront
    engine (`core.analysis.wavefront`) issues per BFS level.
    """
    cfg = autotune.resolve("frontier_step", f.shape[0], a.shape[1],
                           f.shape[1], bm=bm, bn=bn, bk=bk)
    return _frontier_step_jit(f, a, d, **cfg)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk"))
def _frontier_step_packed_jit(f: jnp.ndarray, a: jnp.ndarray, d: jnp.ndarray,
                              bm: int, bn: int, bk: int) -> jnp.ndarray:
    m, n = f.shape[0], a.shape[1]
    fp = _pad_to(f.astype(jnp.uint32), bm, bk, 0)
    ap = _pad_to(a, bk, bn, 0)  # uint8 {0,1} panels (or f32 — kernel casts)
    # dist pads with the unreached sentinel; padded counts stay 0, so the
    # first-reach mask never fires in the padding
    dp = _pad_to(d.astype(jnp.int16), bm, bn, DIST_UNREACHED)
    out = frontier_step_packed_pallas(fp, ap, dp, bm=bm, bn=bn, bk=bk)
    return out[:m, :n]


def frontier_step_packed(f: jnp.ndarray, a: jnp.ndarray, d: jnp.ndarray,
                         bm: int = None, bn: int = None,
                         bk: int = None) -> jnp.ndarray:
    """Packed-cell fused wavefront step: uint32 frontier x uint8 adjacency
    with int16 distances; newly-reached counts saturate at MULT_SAT (never
    wrap). Bit-equal (as integers) to :func:`frontier_step` while counts
    stay below MULT_SAT. Block shapes resolve under the ``:packed`` dtype
    key, so narrow kernels tune independently of the f32 ones.
    """
    cfg = autotune.resolve("frontier_step", f.shape[0], a.shape[1],
                           f.shape[1], dtype="packed", bm=bm, bn=bn, bk=bk)
    return _frontier_step_packed_jit(f, a, d, **cfg)


def _pad_to_batched(x: jnp.ndarray, bm: int, bn: int, fill) -> jnp.ndarray:
    _, m, n = x.shape
    pm, pn = (-m) % bm, (-n) % bn
    if pm or pn:
        x = jnp.pad(x, ((0, 0), (0, pm), (0, pn)), constant_values=fill)
    return x


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "sub_k"))
def _batched_minplus_jit(a: jnp.ndarray, b: jnp.ndarray, bm: int, bn: int,
                         bk: int, sub_k: int) -> jnp.ndarray:
    m, n = a.shape[1], b.shape[2]
    ap = _pad_to_batched(a.astype(jnp.float32), bm, bk, TROPICAL.pad_a[0])
    bp = _pad_to_batched(b.astype(jnp.float32), bk, bn, TROPICAL.pad_b[0])
    (out,) = semiring_matmul_batched_pallas(TROPICAL, (ap,), (bp,), bm=bm,
                                            bn=bn, bk=bk, sub_k=sub_k)
    return out[:, :m, :n]


def batched_minplus_matmul(a: jnp.ndarray, b: jnp.ndarray, bm: int = None,
                           bn: int = None, bk: int = None,
                           sub_k: int = None) -> jnp.ndarray:
    """Tropical product over a stacked leading axis: (B, M, K) x (B, K, N).

    One kernel launch for the whole stack — the sweep driver's APSP path.
    """
    cfg = autotune.resolve("batched_minplus", a.shape[1], b.shape[2],
                           a.shape[2], bm=bm, bn=bn, bk=bk, sub_k=sub_k)
    return _batched_minplus_jit(a, b, **cfg)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk"))
def _batched_count_jit(a: jnp.ndarray, b: jnp.ndarray, bm: int, bn: int,
                       bk: int) -> jnp.ndarray:
    m, n = a.shape[1], b.shape[2]
    ap = _pad_to_batched(a.astype(jnp.float32), bm, bk, COUNTING.pad_a[0])
    bp = _pad_to_batched(b.astype(jnp.float32), bk, bn, COUNTING.pad_b[0])
    (out,) = semiring_matmul_batched_pallas(COUNTING, (ap,), (bp,), bm=bm,
                                            bn=bn, bk=bk)
    return out[:, :m, :n]


def batched_count_matmul(a: jnp.ndarray, b: jnp.ndarray, bm: int = None,
                         bn: int = None, bk: int = None) -> jnp.ndarray:
    """Counting product over a stacked leading axis (MXU path per block)."""
    cfg = autotune.resolve("batched_count", a.shape[1], b.shape[2],
                           a.shape[2], bm=bm, bn=bn, bk=bk)
    return _batched_count_jit(a, b, **cfg)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk"))
def _batched_frontier_step_jit(f: jnp.ndarray, a: jnp.ndarray,
                               d: jnp.ndarray, bm: int, bn: int,
                               bk: int) -> jnp.ndarray:
    m, n = f.shape[1], a.shape[2]
    fp = _pad_to_batched(f.astype(jnp.float32), bm, bk, 0.0)
    ap = _pad_to_batched(a.astype(jnp.float32), bk, bn, 0.0)
    dp = _pad_to_batched(d.astype(jnp.float32), bm, bn, jnp.inf)
    out = frontier_step_batched_pallas(fp, ap, dp, bm=bm, bn=bn, bk=bk)
    return out[:, :m, :n]


def batched_frontier_step(f: jnp.ndarray, a: jnp.ndarray, d: jnp.ndarray,
                          bm: int = None, bn: int = None,
                          bk: int = None) -> jnp.ndarray:
    """Stacked fused wavefront step over a leading batch axis."""
    cfg = autotune.resolve("batched_frontier_step", f.shape[1], a.shape[2],
                           f.shape[2], bm=bm, bn=bn, bk=bk)
    return _batched_frontier_step_jit(f, a, d, **cfg)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk"))
def _batched_frontier_step_packed_jit(f: jnp.ndarray, a: jnp.ndarray,
                                      d: jnp.ndarray, bm: int, bn: int,
                                      bk: int) -> jnp.ndarray:
    m, n = f.shape[1], a.shape[2]
    fp = _pad_to_batched(f.astype(jnp.uint32), bm, bk, 0)
    ap = _pad_to_batched(a, bk, bn, 0)
    dp = _pad_to_batched(d.astype(jnp.int16), bm, bn, DIST_UNREACHED)
    out = frontier_step_packed_batched_pallas(fp, ap, dp, bm=bm, bn=bn,
                                              bk=bk)
    return out[:, :m, :n]


def batched_frontier_step_packed(f: jnp.ndarray, a: jnp.ndarray,
                                 d: jnp.ndarray, bm: int = None,
                                 bn: int = None,
                                 bk: int = None) -> jnp.ndarray:
    """Stacked packed wavefront step over a leading batch axis."""
    cfg = autotune.resolve("batched_frontier_step", f.shape[1], a.shape[2],
                           f.shape[2], dtype="packed", bm=bm, bn=bn, bk=bk)
    return _batched_frontier_step_packed_jit(f, a, d, **cfg)


@functools.partial(jax.jit, static_argnames=("num_bins", "bm", "bn"))
def value_histogram(x: jnp.ndarray, num_bins: int,
                    bm: int = 256, bn: int = 256) -> jnp.ndarray:
    """Histogram of floor(x) over [0, num_bins); pads with -1 (dropped)."""
    xp = _pad_to(x.astype(jnp.float32), bm, bn, -1.0)
    return value_histogram_pallas(xp, num_bins, bm=bm, bn=bn)


# oracle aliases so callers can ask for the reference implementation
minplus_matmul_ref = ref.minplus_matmul_ref
reachability_step_ref = ref.reachability_step_ref
value_histogram_ref = ref.value_histogram_ref
count_matmul_ref = ref.count_matmul_ref
minplus_count_matmul_ref = ref.minplus_count_matmul_ref
frontier_step_ref = ref.frontier_step_ref
frontier_step_packed_ref = ref.frontier_step_packed_ref
batched_minplus_matmul_ref = ref.batched_minplus_matmul_ref
batched_count_matmul_ref = ref.batched_count_matmul_ref

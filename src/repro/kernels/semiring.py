"""Generic blocked Pallas semiring matmul — the one kernel behind APSP,
reachability, and path counting.

Every dense product this toolchain needs is a blocked matmul over some
semiring; only the scalar algebra differs. This module owns the shared
scaffolding exactly once — the (M/bm, N/bn, K/bk) grid with K innermost so
each (i, j) output block stays resident in VMEM across the K sweep
(revisiting semantics), and the BlockSpec index maps — and parameterizes the
algebra with a :class:`Semiring` spec. Two execution paths:

* **VPU path** (``mxu=False``): the semiring product is not an (+, x) dot, so
  it runs on the VPU as a broadcast combine + axis reduce. The inner K loop
  is unrolled in (sub_k, bn) slabs to keep the (bm, sub_k, bn) broadcast
  working set inside the vector registers. Elements may be *tuples* of
  arrays (``num_fields > 1``) — e.g. the fused (dist, count) product.
* **MXU path** (``mxu=True``): the semiring product is a plain f32 dot plus
  an elementwise epilogue (boolean threshold, or identity for counting), so
  it accumulates in a VMEM scratch block and only the epilogue result is
  written back to HBM.

Block shapes must be multiples of the (8, 128) float32 tile; defaults are
(128, 128, 128) giving a ~192 kB VMEM working set per field for f32.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..device import resolve_interpret

__all__ = [
    "Semiring", "semiring_matmul_pallas", "semiring_matmul_batched_pallas",
    "frontier_step_pallas", "frontier_step_batched_pallas",
    "frontier_step_packed_pallas", "frontier_step_packed_batched_pallas",
    "TROPICAL", "BOOLEAN", "COUNTING", "TROPICAL_COUNT",
    "DIST_DTYPE", "MULT_DTYPE", "DIST_UNREACHED", "MULT_SAT",
    "pack_dist", "unpack_dist",
]

Fields = Tuple[jnp.ndarray, ...]

# -- packed ("narrow cell") dtypes --------------------------------------------
#
# The extreme-scale engines store the per-pair state in narrow integer cells
# instead of f32: distances in int16 (hop counts; every family here has
# diameter << 32767) and multiplicities in a *saturating* uint32 counter.
# The MXU still accumulates products in f32, whose integer range is exact
# only below 2**24 — so the saturation point is 2**24, not 2**32: any count
# that reaches MULT_SAT is clamped there (never wrapped) and the engine
# raises a saturation flag. Where values fit (dist < 32767, mult < 2**24)
# the packed engines are bit-equal to the f32 engines.

#: packed distance dtype; DIST_UNREACHED (int16 max) plays the role of +inf.
DIST_DTYPE = jnp.int16
#: packed multiplicity dtype — stores exact counts up to MULT_SAT.
MULT_DTYPE = jnp.uint32
#: sentinel for "not yet reached" in packed distance cells.
DIST_UNREACHED = 32767
#: saturation point for packed counts: the f32 exact-integer ceiling.
MULT_SAT = 2 ** 24


def pack_dist(d: jnp.ndarray) -> jnp.ndarray:
    """f32 distances (+inf = unreached) -> int16 (DIST_UNREACHED sentinel)."""
    return jnp.where(jnp.isfinite(d), d, DIST_UNREACHED).astype(DIST_DTYPE)


def unpack_dist(d: jnp.ndarray) -> jnp.ndarray:
    """int16 packed distances -> f32 with +inf for unreached."""
    return jnp.where(d == DIST_UNREACHED, jnp.inf, d.astype(jnp.float32))


@dataclasses.dataclass(frozen=True)
class Semiring:
    """Spec of one blocked-matmul algebra.

    A semiring element is a tuple of ``num_fields`` scalars (one array per
    field at matrix level). ``pad_a``/``pad_b`` are the multiplicative
    annihilators used by `ops` to pad operands to block multiples — padding
    must never win a reduction. ``acc_init`` is the additive identity the
    accumulator starts from.

    VPU path callables (required when ``mxu=False``), all over field tuples:
      combine(a, b):    elementwise semiring multiply on a broadcast
                        (bm, sub_k, bn) slab; a is (bm, sub_k, 1)-shaped,
                        b is (1, sub_k, bn)-shaped.
      kreduce(f):       semiring-add reduce over axis 1 -> (bm, bn) fields.
      accumulate(x, y): binary semiring add of two (bm, bn) field tuples.

    MXU path (``mxu=True``, single field only): the product is the plain f32
    dot; ``epilogue`` maps the accumulated counts to the output block at the
    last K step.
    """

    name: str
    pad_a: Tuple[float, ...]
    pad_b: Tuple[float, ...]
    acc_init: Tuple[float, ...]
    num_fields: int = 1
    mxu: bool = False
    combine: Optional[Callable[[Fields, Fields], Fields]] = None
    kreduce: Optional[Callable[[Fields], Fields]] = None
    accumulate: Optional[Callable[[Fields, Fields], Fields]] = None
    epilogue: Optional[Callable[[jnp.ndarray], jnp.ndarray]] = None

    def __post_init__(self):
        if self.mxu:
            assert self.num_fields == 1, "MXU path is single-field"
            assert self.epilogue is not None
        else:
            assert self.combine and self.kreduce and self.accumulate


# -- kernel bodies ------------------------------------------------------------

def _as_f32(x):
    """Cast a block to f32 in-register. Mosaic has no unsigned -> float
    cast, so uint8 panels and uint32 frontiers go through int32 — exact,
    since {0,1} cells and counts clamped at MULT_SAT sit far below 2**31."""
    if jnp.issubdtype(x.dtype, jnp.unsignedinteger):
        x = x.astype(jnp.int32)
    return x.astype(jnp.float32)


def _from_f32(x, dtype):
    """Store-side inverse of :func:`_as_f32` (f32 -> uint goes via int32)."""
    if jnp.issubdtype(dtype, jnp.unsignedinteger):
        x = x.astype(jnp.int32)
    return x.astype(dtype)


def _unreached(d):
    """DIST_UNREACHED mask of an int16 dist block; v5e compares no 16-bit
    integers, so the block widens to int32 first."""
    return d.astype(jnp.int32) == DIST_UNREACHED


def _count_dot(a, b):
    """The one counting product of the MXU kernels: f32 accumulation at
    HIGHEST precision, so integer counts stay exact below 2**24 (a
    single-pass bf16 product would round every count above 256)."""
    return jax.lax.dot(_as_f32(a), _as_f32(b),
                       precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)


def _vpu_block(sr: Semiring, a, b, acc, sub_k: int):
    """Shared VPU math: semiring product-accumulate of one (bm, bk) x
    (bk, bn) block pair onto ``acc``, K unrolled in sub_k slabs."""
    bm, bk = a[0].shape
    bn = b[0].shape[1]
    # Unrolled K-blocking: process sub_k rows of b at a time so the
    # (bm, sub_k, bn) broadcast working set stays register/VMEM-friendly.
    for k0 in range(0, bk, sub_k):
        a_slab = tuple(
            jax.lax.slice(x, (0, k0), (bm, k0 + sub_k))[:, :, None] for x in a
        )
        b_slab = tuple(
            jax.lax.slice(x, (k0, 0), (k0 + sub_k, bn))[None, :, :] for x in b
        )
        term = sr.kreduce(sr.combine(a_slab, b_slab))
        acc = sr.accumulate(acc, term)
    return acc


def _vpu_kernel(*refs, sr: Semiring, sub_k: int):
    """Generic (bm, bk) x (bk, bn) -> (bm, bn) semiring product-accumulate."""
    nf = sr.num_fields
    a_refs, b_refs, o_refs = refs[:nf], refs[nf:2 * nf], refs[2 * nf:]

    @pl.when(pl.program_id(2) == 0)
    def _init():
        for o_ref, v in zip(o_refs, sr.acc_init):
            o_ref[...] = jnp.full_like(o_ref, v)

    a = [r[...] for r in a_refs]  # each (bm, bk)
    b = [r[...] for r in b_refs]  # each (bk, bn)
    acc = tuple(r[...] for r in o_refs)
    acc = _vpu_block(sr, a, b, acc, sub_k)
    for o_ref, v in zip(o_refs, acc):
        o_ref[...] = v


def _mxu_kernel(a_ref, b_ref, o_ref, acc_ref, *, sr: Semiring, k_blocks: int):
    """Fused dot-accumulate + epilogue; counts never leave VMEM.

    Operands are cast to f32 in-register before the dot (a no-op for the f32
    engines), so narrow packed inputs — uint8 adjacency panels, uint32
    frontiers — ride the same body and only pay f32 width inside VMEM.
    """

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _count_dot(a_ref[...], b_ref[...])

    @pl.when(pl.program_id(2) == k_blocks - 1)
    def _epilogue():
        o_ref[...] = _from_f32(sr.epilogue(acc_ref[...]), o_ref.dtype)


def _vpu_kernel_batched(*refs, sr: Semiring, sub_k: int):
    """Batched VPU body: blocks carry a leading size-1 batch dim.

    Grid is (B, M/bm, N/bn, K/bk) with K innermost, so each (b, i, j)
    output block stays resident across the K sweep exactly like the 2D
    kernel; the stacked leading axis only adds an outer grid dimension.
    """
    nf = sr.num_fields
    a_refs, b_refs, o_refs = refs[:nf], refs[nf:2 * nf], refs[2 * nf:]

    @pl.when(pl.program_id(3) == 0)
    def _init():
        for o_ref, v in zip(o_refs, sr.acc_init):
            o_ref[...] = jnp.full_like(o_ref, v)

    a = [r[0] for r in a_refs]  # (1, bm, bk) -> (bm, bk)
    b = [r[0] for r in b_refs]
    acc = tuple(r[0] for r in o_refs)
    acc = _vpu_block(sr, a, b, acc, sub_k)
    for o_ref, v in zip(o_refs, acc):
        o_ref[...] = v[None]


def _mxu_kernel_batched(a_ref, b_ref, o_ref, acc_ref, *, sr: Semiring,
                        k_blocks: int):
    @pl.when(pl.program_id(3) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _count_dot(a_ref[0], b_ref[0])

    @pl.when(pl.program_id(3) == k_blocks - 1)
    def _epilogue():
        o_ref[...] = _from_f32(sr.epilogue(acc_ref[...]), o_ref.dtype)[None]


def _frontier_kernel(f_ref, a_ref, d_ref, o_ref, acc_ref, *, k_blocks: int):
    """Fused BFS frontier step: counting product + first-reach mask.

    Accumulates ``F @ A`` in VMEM like the MXU counting kernel, then the
    epilogue keeps only entries that are newly reached — positive count AND
    still unreached (dist == +inf) — so the per-level ``dist == level``
    selects never materialize outside the kernel. The dist block is a plain
    input read once, at the last K step.
    """

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _count_dot(f_ref[...], a_ref[...])

    @pl.when(pl.program_id(2) == k_blocks - 1)
    def _epilogue():
        acc = acc_ref[...]
        new = (acc > 0.0) & (d_ref[...] == jnp.inf)
        o_ref[...] = _from_f32(jnp.where(new, acc, 0.0), o_ref.dtype)


def _frontier_kernel_batched(f_ref, a_ref, d_ref, o_ref, acc_ref, *,
                             k_blocks: int):
    @pl.when(pl.program_id(3) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _count_dot(f_ref[0], a_ref[0])

    @pl.when(pl.program_id(3) == k_blocks - 1)
    def _epilogue():
        acc = acc_ref[...]
        new = (acc > 0.0) & (d_ref[0] == jnp.inf)
        o_ref[...] = _from_f32(jnp.where(new, acc, 0.0), o_ref.dtype)[None]


def _frontier_kernel_packed(f_ref, a_ref, d_ref, o_ref, acc_ref, *,
                            k_blocks: int):
    """Packed-cell fused BFS frontier step with a saturating epilogue.

    Same dot-accumulate as :func:`_frontier_kernel` (the MXU accumulates in
    f32 regardless of storage dtype), but the state is narrow: the frontier
    is uint32 counts, the adjacency panel uint8, the dist block int16 with
    DIST_UNREACHED as the +inf sentinel. The epilogue clamps newly-reached
    counts at MULT_SAT — the f32 exact-integer ceiling — so an overflowing
    multiplicity saturates (detectably: the value *is* MULT_SAT) instead of
    wrapping.
    """

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _count_dot(f_ref[...], a_ref[...])

    @pl.when(pl.program_id(2) == k_blocks - 1)
    def _epilogue():
        acc = acc_ref[...]
        new = (acc > 0.0) & _unreached(d_ref[...])
        sat = jnp.minimum(acc, float(MULT_SAT))
        o_ref[...] = _from_f32(jnp.where(new, sat, 0.0), o_ref.dtype)


def _frontier_kernel_packed_batched(f_ref, a_ref, d_ref, o_ref, acc_ref, *,
                                    k_blocks: int):
    @pl.when(pl.program_id(3) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _count_dot(f_ref[0], a_ref[0])

    @pl.when(pl.program_id(3) == k_blocks - 1)
    def _epilogue():
        acc = acc_ref[...]
        new = (acc > 0.0) & _unreached(d_ref[0])
        sat = jnp.minimum(acc, float(MULT_SAT))
        o_ref[...] = _from_f32(jnp.where(new, sat, 0.0), o_ref.dtype)[None]


def frontier_step_pallas(f: jnp.ndarray, a: jnp.ndarray, d: jnp.ndarray, *,
                         bm: int = 128, bn: int = 128, bk: int = 128,
                         interpret: Optional[bool] = None) -> jnp.ndarray:
    """One fused wavefront step: ``where((F@A > 0) & (D == inf), F@A, 0)``.

    ``f`` is the (M, K) level-k multiplicity frontier, ``a`` the (K, N)
    adjacency, ``d`` the (M, N) running distance matrix (+inf = unreached).
    Returns the masked level-(k+1) frontier — exactly the newly-reached
    pairs with their shortest-path multiplicities. M, N, K must divide into
    blocks (the wavefront engine pre-pads once and reuses the buffers).
    """
    m, k = f.shape
    k2, n = a.shape
    assert k == k2 and d.shape == (m, n), (f.shape, a.shape, d.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, \
        (f.shape, a.shape, (bm, bn, bk))
    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        functools.partial(_frontier_kernel, k_blocks=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(f, a, d)


def frontier_step_batched_pallas(f: jnp.ndarray, a: jnp.ndarray,
                                 d: jnp.ndarray, *,
                                 bm: int = 128, bn: int = 128, bk: int = 128,
                                 interpret: Optional[bool] = None) -> jnp.ndarray:
    """Stacked fused wavefront step over a leading batch axis (B, M, N)."""
    nb, m, k = f.shape
    nb2, k2, n = a.shape
    assert nb == nb2 and k == k2 and d.shape == (nb, m, n), \
        (f.shape, a.shape, d.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, \
        (f.shape, a.shape, (bm, bn, bk))
    grid = (nb, m // bm, n // bn, k // bk)
    return pl.pallas_call(
        functools.partial(_frontier_kernel_batched, k_blocks=grid[3]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda bb, i, j, kk: (bb, i, kk)),
            pl.BlockSpec((1, bk, bn), lambda bb, i, j, kk: (bb, kk, j)),
            pl.BlockSpec((1, bm, bn), lambda bb, i, j, kk: (bb, i, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda bb, i, j, kk: (bb, i, j)),
        out_shape=jax.ShapeDtypeStruct((nb, m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(f, a, d)


def frontier_step_packed_pallas(f: jnp.ndarray, a: jnp.ndarray,
                                d: jnp.ndarray, *,
                                bm: int = 128, bn: int = 128, bk: int = 128,
                                interpret: Optional[bool] = None) -> jnp.ndarray:
    """Packed fused wavefront step over narrow cells.

    ``f`` is the (M, K) uint32 multiplicity frontier, ``a`` the (K, N)
    adjacency (uint8 {0,1} panels — or f32, the kernel casts), ``d`` the
    (M, N) int16 running distances (DIST_UNREACHED = unreached). Returns the
    uint32 masked next frontier with counts clamped at MULT_SAT; a returned
    cell equal to MULT_SAT means the true multiplicity may exceed it (the
    host wrappers surface this as a saturation flag). Below MULT_SAT the
    result is bit-equal (as integers) to :func:`frontier_step_pallas`.
    """
    m, k = f.shape
    k2, n = a.shape
    assert k == k2 and d.shape == (m, n), (f.shape, a.shape, d.shape)
    assert d.dtype == DIST_DTYPE, d.dtype
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, \
        (f.shape, a.shape, (bm, bn, bk))
    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        functools.partial(_frontier_kernel_packed, k_blocks=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), MULT_DTYPE),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(f, a, d)


def frontier_step_packed_batched_pallas(f: jnp.ndarray, a: jnp.ndarray,
                                        d: jnp.ndarray, *,
                                        bm: int = 128, bn: int = 128,
                                        bk: int = 128,
                                        interpret: Optional[bool] = None) -> jnp.ndarray:
    """Stacked packed wavefront step over a leading batch axis."""
    nb, m, k = f.shape
    nb2, k2, n = a.shape
    assert nb == nb2 and k == k2 and d.shape == (nb, m, n), \
        (f.shape, a.shape, d.shape)
    assert d.dtype == DIST_DTYPE, d.dtype
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, \
        (f.shape, a.shape, (bm, bn, bk))
    grid = (nb, m // bm, n // bn, k // bk)
    return pl.pallas_call(
        functools.partial(_frontier_kernel_packed_batched, k_blocks=grid[3]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda bb, i, j, kk: (bb, i, kk)),
            pl.BlockSpec((1, bk, bn), lambda bb, i, j, kk: (bb, kk, j)),
            pl.BlockSpec((1, bm, bn), lambda bb, i, j, kk: (bb, i, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda bb, i, j, kk: (bb, i, j)),
        out_shape=jax.ShapeDtypeStruct((nb, m, n), MULT_DTYPE),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(f, a, d)


# -- entry point --------------------------------------------------------------

def semiring_matmul_pallas(sr: Semiring, a: Fields, b: Fields, *,
                           bm: int = 128, bn: int = 128, bk: int = 128,
                           sub_k: int = 8, interpret: Optional[bool] = None,
                           out_dtype=None) -> Fields:
    """Blocked (M, K) x (K, N) product over ``sr``; returns one array per field.

    M, N, K must divide into blocks (use `ops` for auto-padding).
    ``interpret`` defaults to the backend's choice
    (`repro.device.resolve_interpret`). ``out_dtype`` (MXU path only) overrides the
    output dtype — the packed engines dot uint32 frontiers against uint8
    panels and take the f32 accumulator out directly.
    """
    nf = sr.num_fields
    assert len(a) == nf and len(b) == nf, (len(a), len(b), nf)
    assert out_dtype is None or sr.mxu, "out_dtype is an MXU-path control"
    m, k = a[0].shape
    k2, n = b[0].shape
    assert k == k2, (a[0].shape, b[0].shape)
    assert all(x.shape == (m, k) for x in a)
    assert all(x.shape == (k, n) for x in b)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, \
        (a[0].shape, b[0].shape, (bm, bn, bk))
    assert bk % sub_k == 0
    grid = (m // bm, n // bn, k // bk)
    a_spec = pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk))
    b_spec = pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j))
    o_spec = pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j))
    out_shape = [jax.ShapeDtypeStruct((m, n), out_dtype or x.dtype)
                 for x in a]

    if sr.mxu:
        kernel = functools.partial(_mxu_kernel, sr=sr, k_blocks=grid[2])
        scratch = [pltpu.VMEM((bm, bn), jnp.float32)]
    else:
        kernel = functools.partial(_vpu_kernel, sr=sr, sub_k=sub_k)
        scratch = []

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[a_spec] * nf + [b_spec] * nf,
        out_specs=o_spec if nf == 1 else [o_spec] * nf,
        out_shape=out_shape[0] if nf == 1 else out_shape,
        scratch_shapes=scratch,
        interpret=resolve_interpret(interpret),
    )(*a, *b)
    return (out,) if nf == 1 else tuple(out)


def semiring_matmul_batched_pallas(sr: Semiring, a: Fields, b: Fields, *,
                                   bm: int = 128, bn: int = 128, bk: int = 128,
                                   sub_k: int = 8, interpret: Optional[bool] = None,
                                   out_dtype=None) -> Fields:
    """Batched (B, M, K) x (B, K, N) product over ``sr`` — one kernel launch
    for a whole stack of independent problems (the equal-cost sweep driver's
    hot path: every topology's padded adjacency block rides the leading
    axis). Same blocking/revisiting scheme as the 2D kernel with the batch
    index as the outermost grid dimension."""
    nf = sr.num_fields
    assert len(a) == nf and len(b) == nf, (len(a), len(b), nf)
    assert out_dtype is None or sr.mxu, "out_dtype is an MXU-path control"
    nb, m, k = a[0].shape
    nb2, k2, n = b[0].shape
    assert nb == nb2 and k == k2, (a[0].shape, b[0].shape)
    assert all(x.shape == (nb, m, k) for x in a)
    assert all(x.shape == (nb, k, n) for x in b)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, \
        (a[0].shape, b[0].shape, (bm, bn, bk))
    assert bk % sub_k == 0
    grid = (nb, m // bm, n // bn, k // bk)
    a_spec = pl.BlockSpec((1, bm, bk), lambda bb, i, j, kk: (bb, i, kk))
    b_spec = pl.BlockSpec((1, bk, bn), lambda bb, i, j, kk: (bb, kk, j))
    o_spec = pl.BlockSpec((1, bm, bn), lambda bb, i, j, kk: (bb, i, j))
    out_shape = [jax.ShapeDtypeStruct((nb, m, n), out_dtype or x.dtype)
                 for x in a]

    if sr.mxu:
        kernel = functools.partial(_mxu_kernel_batched, sr=sr,
                                   k_blocks=grid[3])
        scratch = [pltpu.VMEM((bm, bn), jnp.float32)]
    else:
        kernel = functools.partial(_vpu_kernel_batched, sr=sr, sub_k=sub_k)
        scratch = []

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[a_spec] * nf + [b_spec] * nf,
        out_specs=o_spec if nf == 1 else [o_spec] * nf,
        out_shape=out_shape[0] if nf == 1 else out_shape,
        scratch_shapes=scratch,
        interpret=resolve_interpret(interpret),
    )(*a, *b)
    return (out,) if nf == 1 else tuple(out)


# -- the semirings this toolchain ships ---------------------------------------

_INF = float("inf")

#: (min, +) over distances — the APSP hot spot. Runs on the VPU: the MXU
#: only evaluates (+, x).
TROPICAL = Semiring(
    name="tropical",
    pad_a=(_INF,), pad_b=(_INF,), acc_init=(_INF,),
    combine=lambda a, b: (a[0] + b[0],),
    kreduce=lambda f: (jnp.min(f[0], axis=1),),
    accumulate=lambda x, y: (jnp.minimum(x[0], y[0]),),
)

#: (or, and) over {0,1} masks — reachability / BFS frontier expansion.
#: MXU-eligible: dot the masks as f32 counts, threshold in the epilogue.
BOOLEAN = Semiring(
    name="boolean",
    pad_a=(0.0,), pad_b=(0.0,), acc_init=(0.0,),
    mxu=True,
    epilogue=lambda acc: acc > 0.5,
)

#: (+, x) over nonneg counts — walk/path counting. The MXU's native algebra;
#: exact while counts stay below 2**24 (f32 integer range).
COUNTING = Semiring(
    name="counting",
    pad_a=(0.0,), pad_b=(0.0,), acc_init=(0.0,),
    mxu=True,
    epilogue=lambda acc: acc,
)


def _tc_combine(a: Fields, b: Fields) -> Fields:
    return (a[0] + b[0], a[1] * b[1])


def _tc_kreduce(f: Fields) -> Fields:
    d = jnp.min(f[0], axis=1)
    c = jnp.sum(jnp.where(f[0] == d[:, None, :], f[1], 0.0), axis=1)
    return (d, c)


def _tc_accumulate(x: Fields, y: Fields) -> Fields:
    d = jnp.minimum(x[0], y[0])
    c = (jnp.where(x[0] == d, x[1], 0.0) + jnp.where(y[0] == d, y[1], 0.0))
    return (d, c)


#: Fused (dist, count) pairs: lexicographic (min, +) on dist with count
#: summed over ties — one VPU pass yields shortest-path length AND its
#: multiplicity. Additive identity (inf, 0), multiplicative pad (inf, 0):
#: unreachable entries carry count 0, so inf==inf ties contribute nothing.
TROPICAL_COUNT = Semiring(
    name="tropical_count",
    num_fields=2,
    pad_a=(_INF, 0.0), pad_b=(_INF, 0.0), acc_init=(_INF, 0.0),
    combine=_tc_combine,
    kreduce=_tc_kreduce,
    accumulate=_tc_accumulate,
)

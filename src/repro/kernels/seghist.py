"""Blocked value histogram — path-length distribution hot-spot.

Histogramming an (n, n) distance matrix is memory-bound scatter work; the TPU
adaptation avoids scatters entirely: each grid step loads a (bm, bn) VMEM
block and evaluates, for every bin b, a vectorized popcount of
``floor(x) == b`` (a (num_bins, bm, bn) broadcast compare reduced on the
VPU), accumulating counts in an SMEM-resident (1, num_bins) block. Bins are
static so the compare-reduce unrolls into num_bins fused vector ops — no
data-dependent addressing anywhere.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..device import resolve_interpret

__all__ = ["value_histogram_pallas"]


def _hist_kernel(x_ref, o_ref, *, num_bins: int, grid_n: int):
    step = pl.program_id(0) * grid_n + pl.program_id(1)

    @pl.when(step == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...]
    valid = jnp.isfinite(x) & (x >= 0) & (x < num_bins)
    xi = jnp.where(valid, x, jnp.float32(num_bins)).astype(jnp.int32)
    # bins are static: unrolled compare+popcount per bin, no scatter
    counts = jnp.stack(
        [jnp.sum((xi == b).astype(jnp.int32)) for b in range(num_bins)]
    )
    o_ref[...] += counts[None, :]


def value_histogram_pallas(x: jnp.ndarray, num_bins: int, *,
                           bm: int = 256, bn: int = 256,
                           interpret: Optional[bool] = None) -> jnp.ndarray:
    """Histogram floor(x) into [0, num_bins) over a 2D float array."""
    m, n = x.shape
    assert m % bm == 0 and n % bn == 0, (x.shape, (bm, bn))
    grid = (m // bm, n // bn)
    out = pl.pallas_call(
        functools.partial(_hist_kernel, num_bins=num_bins, grid_n=grid[1]),
        grid=grid,
        in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((1, num_bins), lambda i, j: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, num_bins), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(x)
    return out[0]

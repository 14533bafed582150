"""Min-plus (tropical semiring) blocked matmul — the APSP hot-spot kernel.

TPU adaptation (DESIGN.md §3): the MXU only evaluates (+, x), so the tropical
product runs on the VPU broadcast-add path of the generic semiring matmul
(`semiring.py`), with the inner K loop unrolled in VREG-sized (sub_k, bn)
slabs. All grid/BlockSpec scaffolding lives in `semiring.py`; this module is
just the TROPICAL instantiation.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from .semiring import TROPICAL, semiring_matmul_pallas

__all__ = ["minplus_matmul_pallas"]


def minplus_matmul_pallas(a: jnp.ndarray, b: jnp.ndarray, *,
                          bm: int = 128, bn: int = 128, bk: int = 128,
                          sub_k: int = 8,
                          interpret: Optional[bool] = None) -> jnp.ndarray:
    """Tropical product of (M, K) and (K, N); M, N, K must divide into
    blocks. ``interpret`` defaults to the backend's choice."""
    (out,) = semiring_matmul_pallas(TROPICAL, (a,), (b,), bm=bm, bn=bn, bk=bk,
                                    sub_k=sub_k, interpret=interpret)
    return out

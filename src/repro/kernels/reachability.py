"""Fused boolean-semiring matmul (reachability / BFS frontier expansion).

Unlike min-plus, the boolean product CAN use the MXU: cast {0,1} masks to
f32, matmul (counts), then threshold. The generic semiring matmul
(`semiring.py`) fuses the threshold into the epilogue so the count matrix
never leaves VMEM; this module is just the BOOLEAN instantiation.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from .semiring import BOOLEAN, semiring_matmul_pallas

__all__ = ["reachability_step_pallas"]


def reachability_step_pallas(a: jnp.ndarray, b: jnp.ndarray, *,
                             bm: int = 128, bn: int = 128, bk: int = 128,
                             interpret: Optional[bool] = None
                             ) -> jnp.ndarray:
    """One reachability squaring step over {0,1} float masks."""
    (out,) = semiring_matmul_pallas(BOOLEAN, (a,), (b,), bm=bm, bn=bn, bk=bk,
                                    interpret=interpret)
    return out

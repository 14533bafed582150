"""Host<->device transfers at the analysis seams, each a span of its own.

The analysis stages move whole n x n matrices between the host and the
device. Their seams go through these helpers, so that with tracing on
(`repro.obs`) a design point splits by span name into host work (the
callers' ``<stage>.host`` spans), uploads (``<stage>.h2d``), waits on the
device (``<stage>.wait``) and downloads (``<stage>.d2h``), and every
transfer counts its bytes under ``h2d_bytes.<what>`` / ``d2h_bytes.<what>``
(`obs.record_h2d` / `obs.record_d2h`).

* :func:`upload`   — ``jnp.asarray``; traced, the span ends once the bytes
  are on the device;
* :func:`wait`     — traced, ``block_until_ready`` on what the next line
  downloads, a sync that download makes anyway; untraced, nothing;
* :func:`download` — ``np.asarray``.

With tracing off each helper is one boolean check in front of the plain
conversion the seam made before: no timestamp, no sync, no copy.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import obs

__all__ = ["upload", "wait", "download"]


def upload(x, stage: str, what: str, dtype=None) -> jax.Array:
    """``jnp.asarray(x, dtype)``, spanned and counted while tracing. An
    array already on the device moves nothing and is not counted."""
    if not obs.enabled() or isinstance(x, jax.Array):
        return jnp.asarray(x, dtype)
    with obs.span(f"{stage}.h2d", what=what):
        out = jnp.asarray(x, dtype).block_until_ready()
        obs.record_h2d(out.nbytes, what)
    return out


def wait(x, stage: str, **attrs):
    """While tracing, block on ``x`` (any pytree of device arrays) under a
    ``<stage>.wait`` span carrying ``attrs``: the time the host spends
    waiting for the device to finish the work it was given."""
    if obs.enabled():
        with obs.span(f"{stage}.wait", **attrs):
            jax.block_until_ready(x)
    return x


def download(x, stage: str, what: str) -> np.ndarray:
    """``np.asarray(x)``, spanned and counted while tracing."""
    if not obs.enabled():
        return np.asarray(x)
    with obs.span(f"{stage}.d2h", what=what):
        out = np.asarray(x)
        obs.record_d2h(out.nbytes, what)
    return out

"""GPipe-style pipeline parallelism over a mesh axis (shard_map + ppermute).

Intended placement: the `pod` axis — pipeline stages map onto pods so the
only cross-pod traffic is the (microbatch, d_model) activation handoff per
tick, the DCN-friendly alternative to cross-pod DP (gradient all-reduce) for
models whose per-pod state doesn't fit (DESIGN.md §6).

Schedule: classic GPipe fill-drain. With S stages and M microbatches the
loop runs M + S - 1 ticks; stage s computes microbatch m at tick t = m + s.
Bubble fraction = (S-1)/(M+S-1). The whole schedule is a lax.scan, so it
lowers to a single compact while loop, and it is differentiable (ppermute
transposes to the reverse permute), giving 1F1B-cost backward for free via
jax.grad.

`pipeline_apply(stage_fn, stage_params, x, ...)`:
  * stage_params: pytree whose leaves have leading dim = n_stages
    (stage-sharded over `axis` by the shard_map in_specs);
  * stage_fn(params_slice, x_mb) -> y_mb applies ONE stage to one microbatch;
  * x: (M, mb, ...) microbatched input (global); returns (M, mb, ...) outputs
    as produced by the LAST stage, replicated across the axis.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["pipeline_apply", "bubble_fraction"]


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)


def pipeline_apply(stage_fn: Callable, stage_params: Any, x: jnp.ndarray,
                   mesh: Mesh, axis: str = "pod") -> jnp.ndarray:
    """Run x through n_stages = mesh.shape[axis] pipeline stages.

    x: (M, mb, ...) — M microbatches. Stage s lives on rank s of `axis`.
    """
    s_count = mesh.shape[axis]
    m_count = x.shape[0]
    ticks = m_count + s_count - 1
    perm = [(i, i + 1) for i in range(s_count - 1)]  # stage s -> s+1

    def local(params_l, x_l):
        # params_l leaves: (1, ...) — this rank's stage slice
        params_me = jax.tree.map(lambda p: p[0], params_l)
        sid = jax.lax.axis_index(axis)
        out_buf = jnp.zeros_like(x_l)

        def tick(carry, t):
            recv, out_buf = carry
            # stage 0 feeds microbatch t (while t < M); others use recv
            m_idx = jnp.clip(t, 0, m_count - 1)
            x_mb = jax.lax.dynamic_index_in_dim(x_l, m_idx, 0, keepdims=False)
            inp = jnp.where(sid == 0, x_mb, recv)
            y = stage_fn(params_me, inp)
            # mask ticks where this stage has no live microbatch
            live = (t >= sid) & (t - sid < m_count)
            y = jnp.where(live, y, jnp.zeros_like(y))
            # last stage commits its finished microbatch t - (S-1)
            o_idx = jnp.clip(t - (s_count - 1), 0, m_count - 1)
            commit = (sid == s_count - 1) & (t >= s_count - 1)
            out_buf = jnp.where(
                commit,
                jax.lax.dynamic_update_index_in_dim(out_buf, y, o_idx, 0),
                out_buf)
            # hand off to the next stage
            recv = jax.lax.ppermute(y, axis, perm)
            return (recv, out_buf), None

        recv0 = jnp.zeros_like(x_l[0])
        (_, out_buf), _ = jax.lax.scan(
            tick, (recv0, out_buf), jnp.arange(ticks))
        # broadcast the last stage's buffer to every rank
        mask = (sid == s_count - 1).astype(out_buf.dtype)
        return jax.lax.psum(out_buf * mask, axis)

    n_axes = len(x.shape)
    p_specs = jax.tree.map(lambda _: P(axis), stage_params)
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(p_specs, P(*([None] * n_axes))),
        out_specs=P(*([None] * n_axes)),
        check_vma=False,
    )
    return fn(stage_params, x)

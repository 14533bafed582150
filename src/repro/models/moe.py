"""Token-choice top-k MoE with explicit expert-parallel all-to-all.

Two execution paths, selected by the active sharding plan:

* **EP/shard_map path** (distributed): the GShard pipeline made explicit —
  per-device local dispatch (sort-based queue positions, local scatter),
  `lax.all_to_all` over the EP (`model`) axis to exchange expert shards,
  local expert einsum, reverse all-to-all, local combine. Writing the
  exchange explicitly matters: under plain pjit the dispatch scatter/gather
  makes the SPMD partitioner replicate the full (B, S, D) stream on every
  device (observed +300 GB/device at Jamba/train_4k), while the explicit
  path moves exactly capacity x d_model bytes through the fabric — the
  all-to-all the EvalNet collective model prices per topology axis.

* **Local path** (single device / no plan): same math, no collectives —
  the oracle the EP path is tested against.

Experts that do not divide the EP axis (granite-3b: 40 on 16) are padded to
the next multiple with router-masked dummy experts (DESIGN.md §5); the
padding costs E_pad/E - 1 idle expert slots, never correctness.

Capacity is per local shard: C = round8(local_tokens * top_k * cf / E_pad).
Dropped tokens fall through the residual. Router runs in f32; Switch-style
aux loss (globally averaged on the EP path via pmean) is returned.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .common import Spec

__all__ = ["param_specs", "moe", "capacity"]


def param_specs(cfg) -> Dict[str, Spec]:
    d, e, f = cfg.d_model, cfg.n_experts, cfg.moe_d_ff or cfg.d_ff
    return {
        "router": Spec((d, e), ("embed", "experts"), scale=0.02),
        "wi": Spec((e, d, f), ("experts", "embed", "mlp")),
        "wg": Spec((e, d, f), ("experts", "embed", "mlp")),
        "wo": Spec((e, f, d), ("experts", "mlp", "embed")),
    }


def capacity(local_tokens: int, n_experts: int, cfg) -> int:
    c = int(local_tokens * cfg.top_k * cfg.capacity_factor / max(n_experts, 1))
    return max(8, ((c + 7) // 8) * 8)


def _route(xf, router, e_real: int, e_pad: int, k: int):
    """Router in f32. Returns gate (T,k), idx (T,k), probs_mean (E_pad,)."""
    logits = xf @ router                                   # (T, E_real)
    if e_pad > e_real:
        logits = jnp.pad(logits, ((0, 0), (0, e_pad - e_real)),
                         constant_values=-1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, k)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    return gate, idx, probs.mean(0)


def _positions(idx_f: jnp.ndarray, e_pad: int, cap: int):
    """Sort-based queue positions (no (T, E) one-hot cumsum).

    idx_f: (T, k) -> flat slot index (T*k,) into an (e_pad * cap) buffer,
    out-of-range for dropped slots; plus per-expert counts (e_pad,)."""
    t, k = idx_f.shape
    flat_e = idx_f.reshape(t * k)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(e_pad), side="left")
    ends = jnp.searchsorted(sorted_e, jnp.arange(e_pad), side="right")
    pos_sorted = jnp.arange(t * k, dtype=jnp.int32) - starts[sorted_e]
    pos = jnp.zeros((t * k,), jnp.int32).at[order].set(pos_sorted)
    keep = pos < cap
    flat = jnp.where(keep, flat_e * cap + pos, e_pad * cap)
    return flat, keep, (ends - starts).astype(jnp.float32)


def _expert_ffn(buf, wi, wg, wo):
    """buf: (E_loc, C', D); weights (E_loc, D, F) / (E_loc, F, D)."""
    h = jnp.einsum("ecd,edf->ecf", buf, wi)
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg))
    return jnp.einsum("ecf,efd->ecd", h * g, wo)


def _moe_local(x2, router, wi, wg, wo, cfg, e_pad: int):
    """Single-device oracle: x2 (T, D) -> (T, D), aux."""
    t, d = x2.shape
    e, k = cfg.n_experts, cfg.top_k
    gate, idx, p_mean = _route(x2.astype(jnp.float32),
                               router.astype(jnp.float32), e, e_pad, k)
    cap = capacity(t, e_pad, cfg)
    flat, keep, counts = _positions(idx, e_pad, cap)
    aux = e * jnp.sum((counts / (t * k)) * p_mean)

    buf = jnp.zeros((e_pad * cap, d), x2.dtype)
    xk = jnp.broadcast_to(x2[:, None], (t, k, d)).reshape(t * k, d)
    buf = buf.at[flat].add(xk, mode="drop")
    if e_pad > e:
        wi = jnp.pad(wi, ((0, e_pad - e), (0, 0), (0, 0)))
        wg = jnp.pad(wg, ((0, e_pad - e), (0, 0), (0, 0)))
        wo = jnp.pad(wo, ((0, e_pad - e), (0, 0), (0, 0)))
    y = _expert_ffn(buf.reshape(e_pad, cap, d), wi, wg, wo)
    y_flat = y.reshape(e_pad * cap, d)
    safe = jnp.minimum(flat, e_pad * cap - 1)
    yk = y_flat[safe] * (gate.reshape(t * k, 1) * keep[:, None]).astype(x2.dtype)
    out = yk.reshape(t, k, d).sum(1)
    return out, aux.astype(jnp.float32)


def _moe_ep(x, router, wi, wg, wo, cfg, plan):
    """shard_map EP path. x: (B, S, D) global."""
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    mesh = plan.mesh
    ep_axis = "model"
    ep = mesh.shape[ep_axis]
    e_pad = ((e + ep - 1) // ep) * ep
    e_loc = e_pad // ep
    dp = plan.batch_axes or None
    if dp is not None:
        dp_size = plan.axis_size(dp)
        if b % dp_size != 0 or b < dp_size:
            dp = None  # tiny batches (long_500k) stay replicated over data
    experts_sharded = (e % ep == 0) and plan.rules.get("experts") == ep_axis
    # FSDP-local expert compute: for few-token calls (decode), gathering the
    # FSDP-sharded expert weights costs ~GB/token; instead keep each weight's
    # d_model shard where it lives, contract the local slice, and psum the
    # (tiny) per-slot activations over the FSDP axis.
    fsdp_ax = plan.rules.get("embed")
    few_tokens = (b * s) <= 4096
    fsdp_local = bool(
        fsdp_ax and experts_sharded and few_tokens
        and d % plan.axis_size(fsdp_ax) == 0
    )
    if fsdp_local:
        # tokens MUST be replicated across the FSDP axis: each rank holds a
        # different d-slice of the weights, so it must see ALL tokens (the
        # weight-stationary decode regime). Batch-sharding over the same
        # axis would mix different batches' d-slices in the gather.
        dp = None

    seq_split = s % ep == 0 and s >= ep

    def local_fn(xl, router_l, wi_l, wg_l, wo_l):
        # xl: (b_loc, s_loc, D). With seq_split the sequence dim arrives
        # already split across the EP axis (in_spec carries it), so tokens
        # are disjoint per rank with NO full-sequence gather anywhere —
        # remat never has to save a gathered (B, S, D) per MoE layer.
        bl, s_loc = xl.shape[:2]
        t_loc = bl * s_loc
        x2 = xl.reshape(t_loc, d)
        midx = jax.lax.axis_index(ep_axis)
        gate, idx, p_mean = _route(x2.astype(jnp.float32),
                                   router_l.astype(jnp.float32), e, e_pad, k)
        cap = capacity(t_loc, e_pad, cfg)
        flat, keep, counts = _positions(idx, e_pad, cap)
        # global aux: average across every shard
        f_e = jax.lax.pmean(counts / (t_loc * k), ep_axis)
        f_e = jax.lax.pmean(f_e, dp) if dp else f_e
        p_m = jax.lax.pmean(p_mean, ep_axis)
        p_m = jax.lax.pmean(p_m, dp) if dp else p_m
        aux = e * jnp.sum(f_e * p_m)

        buf = jnp.zeros((e_pad * cap, d), xl.dtype)
        xk = jnp.broadcast_to(x2[:, None], (t_loc, k, d)).reshape(t_loc * k, d)
        buf = buf.at[flat].add(xk, mode="drop")
        buf = buf.reshape(ep, e_loc, cap, d)
        # EP exchange: every device receives the slots of ITS experts
        recv = jax.lax.all_to_all(buf, ep_axis, split_axis=0, concat_axis=2,
                                  tiled=True)                # (1*e_loc? ...)
        recv = recv.reshape(e_loc, ep * cap, d)
        if fsdp_local:
            # weights arrive (e_loc, d/|fsdp|, f) / (e_loc, f, d/|fsdp|):
            # contract the local d-slice, psum pre-activation, gather y's
            # d-slices back — wire is O(slots x d_ff), not O(weights).
            dsz = plan.axis_size(fsdp_ax)
            dl = d // dsz
            didx = jax.lax.axis_index(fsdp_ax)
            recv_l = jax.lax.dynamic_slice_in_dim(recv, didx * dl, dl, axis=2)
            h = jax.lax.psum(
                jnp.einsum("ecd,edf->ecf", recv_l, wi_l), fsdp_ax)
            gpre = jax.lax.psum(
                jnp.einsum("ecd,edf->ecf", recv_l, wg_l), fsdp_ax)
            y_l = jnp.einsum("ecf,efd->ecd", h * jax.nn.silu(gpre), wo_l)
            y = jax.lax.all_gather(y_l, fsdp_ax, axis=2, tiled=True)
            y = y.reshape(1, e_loc, ep * cap, d)
            back = jax.lax.all_to_all(y, ep_axis, split_axis=2, concat_axis=0,
                                      tiled=True)
            y_flat = back.reshape(e_pad * cap, d)
            safe = jnp.minimum(flat, e_pad * cap - 1)
            yk = y_flat[safe] * (gate.reshape(t_loc * k, 1)
                                 * keep[:, None]).astype(xl.dtype)
            out2 = yk.reshape(t_loc, k, d).sum(1)
            return out2.reshape(bl, s_loc, d), aux.astype(jnp.float32)
        if experts_sharded:
            wi_e, wg_e, wo_e = wi_l, wg_l, wo_l              # already local
        else:
            wi_e = jax.lax.dynamic_slice_in_dim(
                jnp.pad(wi_l, ((0, e_pad - e), (0, 0), (0, 0))),
                midx * e_loc, e_loc, 0)
            wg_e = jax.lax.dynamic_slice_in_dim(
                jnp.pad(wg_l, ((0, e_pad - e), (0, 0), (0, 0))),
                midx * e_loc, e_loc, 0)
            wo_e = jax.lax.dynamic_slice_in_dim(
                jnp.pad(wo_l, ((0, e_pad - e), (0, 0), (0, 0))),
                midx * e_loc, e_loc, 0)
        y = _expert_ffn(recv, wi_e, wg_e, wo_e)              # (e_loc, ep*C, D)
        y = y.reshape(1, e_loc, ep * cap, d)
        back = jax.lax.all_to_all(y, ep_axis, split_axis=2, concat_axis=0,
                                  tiled=True)                # (ep, e_loc, C, D)
        y_flat = back.reshape(e_pad * cap, d)
        safe = jnp.minimum(flat, e_pad * cap - 1)
        yk = y_flat[safe] * (gate.reshape(t_loc * k, 1)
                             * keep[:, None]).astype(xl.dtype)
        out2 = yk.reshape(t_loc, k, d).sum(1)
        return out2.reshape(bl, s_loc, d), aux.astype(jnp.float32)

    if fsdp_local:
        wi_spec = wg_spec = P(ep_axis, fsdp_ax, None)
        wo_spec = P(ep_axis, None, fsdp_ax)
    elif experts_sharded:
        wi_spec = wg_spec = wo_spec = P(ep_axis, None, None)
    else:
        wi_spec = wg_spec = wo_spec = P(None, None, None)
    x_spec = P(dp, ep_axis if seq_split else None, None)
    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(x_spec, P(None, None), wi_spec, wg_spec, wo_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )
    out, aux = fn(x, router, wi, wg, wo)
    return out, aux


def moe(p: Dict, x: jnp.ndarray, cfg) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, D) -> (out (B, S, D), aux_loss scalar)."""
    from ..sharding.partition import current_plan

    plan = current_plan()
    b, s, d = x.shape
    if plan is not None and plan.mesh.shape.get("model", 1) > 1:
        return _moe_ep(x, p["router"], p["wi"], p["wg"], p["wo"], cfg, plan)
    e = cfg.n_experts
    out2, aux = _moe_local(x.reshape(b * s, d), p["router"], p["wi"],
                           p["wg"], p["wo"], cfg, e)
    return out2.reshape(b, s, d), aux

"""Device-resident wavefront engine — the analysis stack's one level loop.

Level-synchronous BFS with Brandes' frontier identity gives hop distances
AND exact shortest-path multiplicities from one counting product per level
(``x_k = F_k @ A``; pairs first reached at level k+1 carry sigma = x). Before
this module, every caller ran that loop on the *host*: download the product,
`np.where`-mask it, re-upload, and check convergence in Python — one
device->host->device round trip per BFS level.

Here the **entire level loop runs inside one jitted `jax.lax.while_loop`**:
frontier expansion (the fused `frontier_step` Pallas primitive — counting
matmul with the first-reach mask folded into its epilogue), dist/mult
updates, and the convergence test all stay on device; only the final
matrices are transferred to host. The same holds for the three other
level loops in the stack:

* :func:`ecmp_loads_device` — the O(diameter) Brandes dependency
  accumulation behind the exact ECMP saturation-throughput bound;
* :func:`slack_counts_device` — the +1/+2 slack-count recurrence (walks
  and bounce walks, levels 1 .. diameter + 2) with its masks, clamp and
  the report's per-row reductions;
* :func:`squaring_apsp_device` — weighted min-plus squaring with the
  convergence flag computed on device (the throughput engine's per-round
  oracle, fed by an on-device scatter of edge lengths into a reused padded
  buffer).

Everything is shape-specialized and cached: one compiled executable per
(padded shape, block config), chosen through the kernel autotuner's
persisted table (`repro.kernels.autotune`). A regression test asserts the
loop lowers to a single compiled call with zero host transfers.

**In-loop telemetry, without callbacks.** With ``telemetry=True`` the
jitted engines carry a small auxiliary state through the `while_loop` —
levels executed, per-level newly-reached pair counts (the wavefront's
frontier sizes), squaring count — and return it as extra *device* outputs
next to the matrices; the host wrappers fold it into the active
observability span (`repro.obs`). No host callback, no transfer inside the
loop: the aux arrays ride the same single device `while` and come back
with the final download. ``telemetry`` is part of the lru-cache key, so
the ``telemetry=False`` jaxpr is byte-identical to the uninstrumented
engine — asserted in ``tests/test_wavefront.py``.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ... import obs, transfers
from ...device import resolve_interpret

__all__ = ["wavefront_dist_mult", "batched_dist_mult", "batched_count",
           "dist_mult_device", "ecmp_loads_device",
           "slack_counts_device", "squaring_apsp_device", "pad_block",
           "pad_operand", "telemetry_attrs"]

_INF = np.float32(np.inf)


def pad_block(n: int, block: Optional[int] = None,
              batched: bool = False) -> Tuple[int, int]:
    """(padded size, block) for an n-router problem: pad to the f32 tile
    (min 128) and size blocks from the autotune table when unspecified
    (the ``batched_frontier_step`` entry for stacked problems)."""
    from ...kernels import autotune

    p = max(128, n + ((-n) % 128))
    if block is None:
        op = "batched_frontier_step" if batched else "frontier_step"
        cfg = autotune.resolve(op, p, p, p)
        block = cfg["bm"]
    block = min(block, p)
    p += (-p) % block
    return p, block


def pad_operand(x: np.ndarray, p: int, fill: float,
                dtype=np.float32) -> np.ndarray:
    """Pad the trailing two dims of ``x`` to (p, p) — the one phantom-router
    padding helper every device-engine caller shares (fills:
    adjacency/multiplicity 0, distance +inf — or DIST_UNREACHED for the
    packed int16 cells, with ``dtype`` overriding the default f32)."""
    x = np.asarray(x, dtype)
    n = x.shape[-1]
    if n == p:
        return x
    w = [(0, 0)] * (x.ndim - 2) + [(0, p - n)] * 2
    return np.pad(x, w, constant_values=np.asarray(fill, dtype))


def _fit_block(p: int, block: Optional[int], batched: bool = False) -> int:
    """A block size that tiles an already-padded size p (p must be a
    multiple of the 128-wide f32 tile). Falls back from the tuned choice to
    128 when the tuned block does not divide p."""
    if p % 128:
        raise ValueError(f"operand size {p} is not a multiple of 128 — "
                         f"pad with pad_block() first")
    if block is None:
        _, block = pad_block(p, batched=batched)
    block = min(block, p)
    return block if p % block == 0 else 128


# -- the jitted engines (cached per padded shape / config) ---------------------

@functools.lru_cache(maxsize=None)
def _dist_mult_fn(batched: bool, block: int, interpret: bool,
                  telemetry: bool = False, packed: bool = False):
    from ... import kernels

    if packed:
        return _dist_mult_packed_fn(batched, block, interpret, telemetry)

    step = (kernels.semiring.frontier_step_batched_pallas if batched
            else kernels.semiring.frontier_step_pallas)

    def run(adj: jnp.ndarray):
        p = adj.shape[-1]
        eye = jnp.broadcast_to(jnp.eye(p, dtype=jnp.float32), adj.shape)
        dist0 = jnp.where(eye > 0, 0.0, _INF)

        if not telemetry:
            def cond(state):
                level, _, _, _, more = state
                return more & (level <= p)

            def body(state):
                level, dist, mult, frontier, _ = state
                x = step(frontier, adj, dist, bm=block, bn=block, bk=block,
                         interpret=interpret)
                new = x > 0
                dist = jnp.where(new, level.astype(jnp.float32), dist)
                # newly reached pairs carried 0 in mult, so += is the
                # masked set
                mult = mult + x
                return level + 1, dist, mult, x, new.any()

            _, dist, mult, _, _ = jax.lax.while_loop(
                cond, body, (jnp.int32(1), dist0, eye, eye, jnp.bool_(True)))
            return dist, mult

        # telemetry variant: the while state additionally carries the
        # per-level newly-reached pair counts (frontier sizes) — still one
        # device `while`, zero callbacks; only the RETURNED aux differs,
        # which is why `telemetry` keys the lru cache
        sizes0 = jnp.zeros((p + 1, adj.shape[0]) if batched else (p + 1,),
                           jnp.int32)

        def cond(state):
            level, _, _, _, more, _ = state
            return more & (level <= p)

        def body(state):
            level, dist, mult, frontier, _, sizes = state
            x = step(frontier, adj, dist, bm=block, bn=block, bk=block,
                     interpret=interpret)
            new = x > 0
            dist = jnp.where(new, level.astype(jnp.float32), dist)
            mult = mult + x
            cnt = jnp.sum(new, axis=(-2, -1), dtype=jnp.int32)
            sizes = sizes.at[level].set(cnt)
            return level + 1, dist, mult, x, new.any(), sizes

        level, dist, mult, _, _, sizes = jax.lax.while_loop(
            cond, body,
            (jnp.int32(1), dist0, eye, eye, jnp.bool_(True), sizes0))
        return dist, mult, (level - 1, sizes)

    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _dist_mult_packed_fn(batched: bool, block: int, interpret: bool,
                         telemetry: bool = False):
    """Packed-cell twin of :func:`_dist_mult_fn`: int16 dist, saturating
    uint32 mult, uint8 adjacency. Same single `lax.while_loop`; the extra
    return is a bool saturation flag (any multiplicity clamped at MULT_SAT).
    A separate cached factory so the f32 engine's jaxpr stays byte-identical
    to its pre-packed form (asserted in tests/test_wavefront.py)."""
    from ... import kernels
    from ...kernels.semiring import DIST_UNREACHED, MULT_DTYPE, MULT_SAT

    step = (kernels.semiring.frontier_step_packed_batched_pallas if batched
            else kernels.semiring.frontier_step_packed_pallas)

    def run(adj: jnp.ndarray):
        p = adj.shape[-1]
        eye = jnp.broadcast_to(jnp.eye(p, dtype=MULT_DTYPE), adj.shape)
        dist0 = jnp.where(eye > 0, 0, DIST_UNREACHED).astype(jnp.int16)
        # the int16 cell caps representable levels; every family here has
        # diameter << 32767, so the cap is a safety bound, not a limit hit
        cap = jnp.int32(min(p, DIST_UNREACHED - 1))
        sat0 = jnp.bool_(False)

        if not telemetry:
            def cond(state):
                level, _, _, _, more, _ = state
                return more & (level <= cap)

            def body(state):
                level, dist, mult, frontier, _, sat = state
                x = step(frontier, adj, dist, bm=block, bn=block, bk=block,
                         interpret=interpret)
                new = x > 0
                dist = jnp.where(new, level.astype(jnp.int16), dist)
                mult = mult + x
                sat = sat | jnp.any(x == MULT_SAT)
                return level + 1, dist, mult, x, new.any(), sat

            _, dist, mult, _, _, sat = jax.lax.while_loop(
                cond, body,
                (jnp.int32(1), dist0, eye, eye, jnp.bool_(True), sat0))
            return dist, mult, sat

        sizes0 = jnp.zeros((p + 1, adj.shape[0]) if batched else (p + 1,),
                           jnp.int32)

        def cond(state):
            level, _, _, _, more, _, _ = state
            return more & (level <= cap)

        def body(state):
            level, dist, mult, frontier, _, sat, sizes = state
            x = step(frontier, adj, dist, bm=block, bn=block, bk=block,
                     interpret=interpret)
            new = x > 0
            dist = jnp.where(new, level.astype(jnp.int16), dist)
            mult = mult + x
            sat = sat | jnp.any(x == MULT_SAT)
            cnt = jnp.sum(new, axis=(-2, -1), dtype=jnp.int32)
            sizes = sizes.at[level].set(cnt)
            return level + 1, dist, mult, x, new.any(), sat, sizes

        level, dist, mult, _, _, sat, sizes = jax.lax.while_loop(
            cond, body,
            (jnp.int32(1), dist0, eye, eye, jnp.bool_(True), sat0, sizes0))
        return dist, mult, sat, (level - 1, sizes)

    return jax.jit(run)


def dist_mult_device(adj: jnp.ndarray, block: Optional[int] = None,
                     interpret: Optional[bool] = None,
                     telemetry: bool = False, packed: bool = False):
    """Hop distances + shortest-path multiplicities, fully on device.

    ``adj`` is a (p, p) or stacked (B, p, p) {0,1} float adjacency whose
    size is already a multiple of the block (see :func:`pad_block`; padding
    rows/cols must be zero — isolated phantom routers). Returns device
    arrays (dist, mult): dist f32 with +inf for unreachable (phantom
    diagonals included at 0), mult f32 with 1 on the diagonal. One jitted
    call; the while_loop never leaves the device.

    ``telemetry=True`` returns ``(dist, mult, (levels, sizes))`` instead:
    ``levels`` the int32 count of level iterations executed and ``sizes``
    an int32 (p+1,) (or (p+1, B) stacked) array of newly-reached pair
    counts per level — device outputs carried through the same single
    `while`, no callbacks (see :func:`telemetry_attrs`).

    ``packed=True`` runs the narrow-cell engine: ``adj`` should be a uint8
    {0,1} adjacency, dist comes back int16 (DIST_UNREACHED = unreached),
    mult uint32 saturating at MULT_SAT, and a bool ``sat`` flag is appended
    to the return tuple — ``(dist, mult, sat)`` or
    ``(dist, mult, sat, aux)`` with telemetry. Bit-equal (as integers) to
    the f32 engine while diameters and counts fit.
    """
    interpret = resolve_interpret(interpret)
    p = adj.shape[-1]
    block = _fit_block(p, block, batched=adj.ndim == 3)
    return _dist_mult_fn(adj.ndim == 3, block, interpret, telemetry,
                         packed)(adj)


def telemetry_attrs(aux) -> Dict[str, object]:
    """Span attributes from a wavefront telemetry aux pair.

    ``levels`` counts executed level iterations (diameter + 1 confirmation
    sweep on connected graphs), ``converged_level`` the last level that
    reached a new pair (= max hop distance), ``frontier_sizes`` the
    newly-reached pair count per level 1..converged_level (summed over the
    stack when batched; ``frontier_sizes_per_graph`` keeps the per-graph
    split).
    """
    level, sizes = aux
    sizes = np.asarray(sizes)
    levels = int(level)
    per_graph = sizes if sizes.ndim == 1 else sizes.sum(axis=1)
    nz = np.flatnonzero(per_graph)
    last = int(nz.max()) if len(nz) else 0
    attrs = {
        "levels": levels,
        "converged_level": last,
        "frontier_sizes": per_graph[1:last + 1].tolist(),
    }
    if sizes.ndim == 2:
        attrs["frontier_sizes_per_graph"] = sizes[1:last + 1].T.tolist()
        attrs["levels_per_graph"] = [
            int(np.flatnonzero(col).max()) if col.any() else 0
            for col in sizes.T]
    return attrs


def wavefront_dist_mult(adj: np.ndarray, block: Optional[int] = None,
                        packed: bool = False
                        ) -> Tuple[np.ndarray, np.ndarray]:
    """Host convenience wrapper: pad -> device engine -> sliced np arrays.

    Warns (RuntimeWarning) when a multiplicity exceeds f32's exact-integer
    range — the engine's counts are f32 on device. Under an enabled
    `repro.obs` tracer the call is spanned and the device telemetry
    (levels, frontier sizes) lands in the span's attributes.

    ``packed=True`` runs the narrow-cell engine and returns
    ``(dist int16, mult uint32)`` — DIST_UNREACHED for unreached pairs
    (see ``kernels.semiring.unpack_dist``), counts saturating at MULT_SAT
    with a RuntimeWarning when any cell clamps. Adjacency uploads as uint8:
    a quarter of the f32 bytes.
    """
    from .paths import _warn_if_inexact

    n = np.asarray(adj).shape[-1]
    batched = np.asarray(adj).ndim == 3
    p, block = pad_block(n, block, batched=batched)
    tel = obs.enabled()
    with obs.span("wavefront.dist_mult", routers=n, padded=p, block=block,
                  batched=batched, packed=packed) as sp:
        dtype = np.uint8 if packed else np.float32
        with obs.span("wavefront.host"):
            padded = pad_operand(adj, p, 0, dtype=dtype)
        out = dist_mult_device(transfers.upload(padded, "wavefront",
                                                "adjacency"),
                               block=block, telemetry=tel, packed=packed)
        transfers.wait(out, "wavefront")
        if packed:
            dist, mult, sat = out[0], out[1], out[2]
            if tel:
                sp.set(**telemetry_attrs(out[3]))
            if bool(sat):
                import warnings

                warnings.warn(
                    "packed wavefront: a shortest-path multiplicity reached "
                    "MULT_SAT (2**24) and was clamped — saturated counts are "
                    "lower bounds, not exact", RuntimeWarning, stacklevel=2)
        elif tel:
            dist, mult, aux = out
            sp.set(**telemetry_attrs(aux))
        else:
            dist, mult = out
        sl = (Ellipsis, slice(None, n), slice(None, n))
        mult = transfers.download(mult, "wavefront", "wavefront_mult")[sl]
        dist = transfers.download(dist, "wavefront", "wavefront_dist")[sl]
        if not packed:
            with obs.span("wavefront.host"):
                _warn_if_inexact(mult, use_kernel=True)
    return dist, mult


def batched_count(use_kernel: bool):
    """Stacked (+, x) product over a leading axis: the batched Pallas
    COUNTING kernel, or the f64 numpy oracle."""
    if use_kernel:
        from ... import kernels

        return lambda a, b: np.asarray(
            kernels.ops.batched_count_matmul(jnp.asarray(a), jnp.asarray(b)))
    return lambda a, b: np.asarray(a, np.float64) @ np.asarray(b, np.float64)


def batched_dist_mult(adj: np.ndarray, use_kernel: bool = True
                      ) -> Tuple[np.ndarray, np.ndarray]:
    """Hop distances and float64 shortest-path multiplicities of one
    (n, n) or stacked (B, n, n) adjacency: the wavefront engine, or its f64
    host oracle.

    ``use_kernel`` runs :func:`wavefront_dist_mult`. Otherwise the same
    Brandes frontier identity runs as a host loop, one stacked f64 counting
    product per BFS level: ``x_k = F_k @ A`` extends the level-k
    multiplicity frontier by one hop, and any pair first reached at level
    k+1 has ``sigma = x_k`` there. The loop stops as soon as a level
    reaches no new pair. Padding rows are isolated phantoms: their frontier
    never grows.
    """
    if use_kernel:
        dist, mult = wavefront_dist_mult(adj)
        with obs.span("wavefront.host"):
            return dist, mult.astype(np.float64)
    stacked = adj if adj.ndim == 3 else adj[None]
    count = batched_count(False)
    nb, p, _ = stacked.shape
    dist = np.full((nb, p, p), _INF, np.float32)
    idx = np.arange(p)
    dist[:, idx, idx] = 0.0
    mult = np.where(dist == 0, 1.0, 0.0)
    frontier = mult.astype(stacked.dtype)
    for level in range(1, p + 1):
        x = count(frontier, stacked)
        new = (x > 0) & ~np.isfinite(dist)
        if not new.any():
            break
        dist[new] = level
        mult = np.where(new, x, mult)
        frontier = np.where(new, x, 0.0).astype(stacked.dtype)
    return (dist, mult) if adj.ndim == 3 else (dist[0], mult[0])


@functools.lru_cache(maxsize=None)
def _ecmp_fn(batched: bool, block: int, interpret: bool,
             weighted: bool = False, shared_graph: bool = False):
    from ... import kernels
    from ...kernels.semiring import (COUNTING, semiring_matmul_batched_pallas,
                                     semiring_matmul_pallas)

    mm = semiring_matmul_batched_pallas if batched else semiring_matmul_pallas

    def count(a, b):
        (out,) = mm(COUNTING, (a,), (b,), bm=block, bn=block, bk=block,
                    interpret=interpret)
        return out

    def accumulate(dist, mult, adj, w):
        finite = jnp.isfinite(dist)
        diam = jnp.max(jnp.where(finite, dist, 0.0)).astype(jnp.int32)
        sigma_inv = jnp.where(finite & (mult > 0),
                              1.0 / jnp.where(mult > 0, mult, 1.0), 0.0)
        zeros = jnp.zeros_like(dist)

        def cond(state):
            a, _, _ = state
            return a >= 0

        def body(state):
            a, delta, acc = state
            af = a.astype(jnp.float32)
            z = jnp.where(dist == af + 1.0, (w + delta) * sigma_inv, 0.0)
            f_a = jnp.where(dist == af, mult, 0.0)
            acc = acc + count(jnp.swapaxes(f_a, -1, -2), z)
            delta = jnp.where(dist == af, mult * count(z, adj), delta)
            return a - 1, delta, acc

        _, _, acc = jax.lax.while_loop(cond, body, (diam - 1, zeros, zeros))
        return adj * acc

    if weighted:
        def run_weighted(dist, mult, adj, demand):
            # demand goes up at its own size: its phantom cells are 0
            pad = dist.shape[-1] - demand.shape[-1]
            demand = jnp.pad(demand, [(0, 0)] * (demand.ndim - 2)
                             + [(0, pad)] * 2)
            if shared_graph:
                # one (p, p) graph under the (c, p, p) demand, broadcast
                # before the loop: each level's two products stay the
                # batched calls on the values a stacked graph gives
                dist, mult, adj = (jnp.broadcast_to(x, demand.shape)
                                   for x in (dist, mult, adj))
            return accumulate(dist, mult, adj, demand)

        return jax.jit(run_weighted)

    def run(dist, mult, adj):
        return accumulate(dist, mult, adj, 1.0)

    return jax.jit(run)


def ecmp_loads_device(dist: jnp.ndarray, mult: jnp.ndarray, adj: jnp.ndarray,
                      demand: Optional[jnp.ndarray] = None,
                      block: Optional[int] = None,
                      interpret: Optional[bool] = None) -> jnp.ndarray:
    """Directed ECMP loads, fully on device: uniform or weighted demand.

    The O(diameter) Brandes backward accumulation of
    `routing.assign.ecmp_all_pairs_loads` as one jitted `lax.while_loop` —
    2 counting products per level with the level masks evaluated on device.
    With ``demand=None`` every reachable pair carries 1.0 (the all-pairs
    case); a (.., p, p) ``demand`` operand seeds the recurrence with that
    pair's volume instead, which is the whole batched-traffic engine
    (`routing.assign.ecmp_demand_loads`): diagonal and unreachable demand
    never enters the level sets, so it is dropped, not routed. dist, mult
    and adj share a (.., p, p) block-multiple shape (phantom padding: dist
    +inf rows, mult/adj 0); a (c, .., ..) ``demand`` stack may sit on one
    shared (p, p) graph, which the program broadcasts, and may come
    unpadded, (.., n, n), which the program pads with 0. Returns the
    device (.., p, p) loads.
    """
    interpret = resolve_interpret(interpret)
    p = dist.shape[-1]
    batched = (dist if demand is None else demand).ndim == 3
    block = _fit_block(p, block, batched=batched)
    if demand is None:
        return _ecmp_fn(batched, block, interpret)(dist, mult, adj)
    return _ecmp_fn(batched, block, interpret, weighted=True,
                    shared_graph=batched and dist.ndim == 2)(
        dist, mult, adj, demand)


@functools.lru_cache(maxsize=None)
def _slack_fn(n: int, diameter: int, block: int, interpret: bool):
    from ...kernels.semiring import COUNTING, semiring_matmul_pallas
    from .paths import pair_rows

    def count(a, b):
        (out,) = semiring_matmul_pallas(COUNTING, (a,), (b,), bm=block,
                                        bn=block, bk=block,
                                        interpret=interpret)
        return out

    def run(adj, dist):
        deg = jnp.sum(adj, axis=0)
        walks = jnp.eye(adj.shape[-1], dtype=jnp.float32)   # A^L
        bounce = walks * deg[None, :]  # T_L = sum_l A^l D A^(L-l)
        zeros = jnp.zeros_like(adj)
        mult = jnp.where(dist == 0, 1.0, zeros)
        correction = jnp.where(dist == 0, bounce, zeros)    # T_d at d=0

        def level(lv, state):
            walks, bounce, mult, plus1, plus2, correction, peak = state
            lf = lv.astype(jnp.float32)
            walks = count(walks, adj)
            # T_L = T_(L-1) A + A^L D; the second term is a column scale
            bounce = count(bounce, adj) + walks * deg[None, :]
            peak = jnp.maximum(peak, jnp.maximum(walks.max(), bounce.max()))
            mult = jnp.where(dist == lf, walks, mult)
            plus1 = jnp.where(dist == lf - 1, walks, plus1)
            plus2 = jnp.where(dist == lf - 2, walks, plus2)
            correction = jnp.where(dist == lf, bounce, correction)
            return walks, bounce, mult, plus1, plus2, correction, peak

        _, _, mult, plus1, plus2, correction, peak = jax.lax.fori_loop(
            1, diameter + 3, level,
            (walks, bounce, mult, zeros, zeros, correction,
             jnp.float32(0)))
        finite = jnp.isfinite(dist)
        d0 = jnp.where(finite, dist, 0.0)
        # difference of large counts: clamp the rounding's negative excursions
        plus2 = jnp.maximum(plus2 - correction + d0 * mult, 0.0)
        # unreachable pairs carry no paths at any slack
        mult = jnp.where(finite, mult, 0.0)[:n, :n]
        plus1 = jnp.where(finite, plus1, 0.0)[:n, :n]
        plus2 = jnp.where(finite & (dist > 0), plus2, 0.0)[:n, :n]
        rows = pair_rows(jnp, dist[:n, :n], mult, plus1, plus2)
        return mult, plus1, plus2, rows, peak <= 2.0 ** 24

    return jax.jit(run)


def slack_counts_device(adj: jnp.ndarray, dist: jnp.ndarray, n: int,
                        diameter: int, interpret: Optional[bool] = None):
    """Simple-path counts at slack 0 / +1 / +2, fully on device.

    The recurrence of `paths.path_counts_with_slack` for levels 1 ..
    ``diameter`` + 2 as one jitted `lax.fori_loop`: two counting products
    per level (walks and bounces, the same float32 Pallas kernel at
    HIGHEST precision), the level masks, the final clamp and the report's
    per-row reductions (`paths.pair_rows`) all on the device. ``adj`` and
    ``dist`` are (p, p) padded operands (adjacency 0, distance +inf in the
    padding). Returns device arrays ``(mult, plus1, plus2, rows, exact)``:
    the (n, n) counts, the (6, n) reductions and whether every walk count
    stayed within float32's exact-integer range (2**24). One compiled
    program per (n, diameter).
    """
    from ...kernels import autotune

    interpret = resolve_interpret(interpret)
    p = adj.shape[-1]
    cfg = autotune.resolve("count", p, p, p)
    block = cfg["bm"] if p % cfg["bm"] == 0 else 128
    return _slack_fn(n, diameter, block, interpret)(adj, dist)


@functools.lru_cache(maxsize=None)
def _squaring_fn(block: int, sub_k: int, max_squarings: int, interpret: bool,
                 telemetry: bool = False):
    from ... import kernels

    def run(d: jnp.ndarray):
        def cond(state):
            i, _, done = state
            return (~done) & (i < max_squarings)

        def body(state):
            i, d, _ = state
            nxt = kernels.minplus.minplus_matmul_pallas(
                d, d, bm=block, bn=block, bk=block, sub_k=sub_k,
                interpret=interpret)
            return i + 1, nxt, jnp.all(nxt == d)

        i, d, _ = jax.lax.while_loop(
            cond, body, (jnp.int32(0), d, jnp.bool_(False)))
        # telemetry: the squaring count already rides the while state —
        # returning it is free and keys a separate cached jaxpr
        return (d, i) if telemetry else d

    return jax.jit(run)


def squaring_apsp_device(d: jnp.ndarray, max_squarings: Optional[int] = None,
                         block: Optional[int] = None,
                         interpret: Optional[bool] = None,
                         telemetry: bool = False):
    """Min-plus squaring to convergence with the convergence flag on device.

    For *weighted* length matrices (hop-distance problems should use
    :func:`dist_mult_device` — squaring costs O(log diam) VPU tropical
    products, the wavefront costs O(diam) MXU counting products). ``d`` is a
    (p, p) padded device seed (+inf off-graph, 0 diagonal everywhere
    including padding). One jitted call, no per-squaring host sync.

    ``max_squarings`` defaults to ceil(log2(p)) — always enough to converge
    — and is only a safety cap: the loop exits on the device-computed
    convergence flag, so callers should leave it shape-derived (one compile
    per padded shape) rather than n-derived.

    ``telemetry=True`` returns ``(dist, squarings)`` with the executed
    squaring count as an int32 device scalar (convergence step telemetry
    for the MWU oracle's spans).
    """
    from ...kernels import autotune

    interpret = resolve_interpret(interpret)
    p = d.shape[-1]
    if max_squarings is None:
        max_squarings = max(1, int(np.ceil(np.log2(p))))
    cfg = autotune.resolve("minplus", p, p, p,
                           bm=block, bn=block, bk=block)
    block = cfg["bm"] if p % cfg["bm"] == 0 else 128
    sub_k = min(cfg["sub_k"], block)
    return _squaring_fn(block, sub_k, max_squarings, interpret, telemetry)(d)

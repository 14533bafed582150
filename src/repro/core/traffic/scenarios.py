"""Batched traffic-scenario evaluation: one stacked pass per scenario.

:func:`evaluate_traffic_batch` pushes a whole ``(S, n, n)`` demand batch
(a :class:`~repro.core.traffic.spec.TrafficSpec`, a flag-grammar string,
or raw matrices) through ONE demand-weighted Brandes accumulation
(`routing.assign.ecmp_demand_loads`, the stacked device engine behind
`resilience.degradation`) and reduces per-matrix congestion metrics with
vectorized masked reductions over the fabric's directed-link cells — no
per-matrix Python loop anywhere on the device path (``mask_chunk`` only
splits oversized batches to bound device memory, reusing the resilience
chunk budget).

Per-matrix metrics (all defined on partitioned graphs; the
unreachable-demand contract lives in `traffic.spec`):

* ``max_link_load``        peak directed link load under exact ECMP.
* ``tput_lb``              saturation-throughput lower bound: the largest
  factor the whole matrix can be scaled by before the peak link hits
  ``capacity`` (``capacity / max_link_load``); 0.0 when nothing routes.
* ``mean_link_load`` / ``p50`` / ``p90`` / ``p99_link_load``  hot-link
  statistics over the *used* (positive-load) directed links.
* ``links_used_frac``      used directed links / 2|E|.
* ``avg_hops``             demand-weighted mean shortest-path length of
  the routed volume.
* ``demand_total`` / ``dropped_demand_frac``  offered volume and the
  fraction dropped (diagonal + unreachable pairs).

:func:`evaluate_traffic_failure_batch` is the traffic x failure engine:
the same metrics over a stacked *masked* adjacency batch
(`resilience.faults`), mask ``i`` paired with demand sample ``i`` (adds
``reachable_frac``). :func:`saturation_search` bisects the injection rate
until the peak load crosses capacity, evaluating each refinement round as
one batched pass across the whole rate grid x sample stack.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple, Union

import numpy as np

from ... import obs
from ..analysis.wavefront import batched_dist_mult
from ..graph import Graph
from .spec import TrafficSpec, as_spec

__all__ = ["TRAFFIC_METRICS", "demand_batch", "evaluate_traffic_batch",
           "evaluate_traffic_failure_batch", "saturation_search"]

#: metrics every scenario evaluation returns (the --check schema)
TRAFFIC_METRICS = ("max_link_load", "tput_lb", "mean_link_load",
                   "p50_link_load", "p90_link_load", "p99_link_load",
                   "links_used_frac", "avg_hops", "demand_total",
                   "dropped_demand_frac")

DemandLike = Union[str, TrafficSpec, np.ndarray]


def demand_batch(g: Graph, demand: DemandLike,
                 samples: Optional[int] = None) -> Tuple[np.ndarray, str]:
    """Normalize any demand form to ``((S, n, n) float64, label)``.

    Accepts a :class:`TrafficSpec`, a flag-grammar string, one ``(n, n)``
    matrix, or an already-stacked ``(S, n, n)`` batch — the normalization
    hook every engine entry point shares.
    """
    if isinstance(demand, (str, TrafficSpec)):
        spec = as_spec(demand)
        with obs.span("demand.host"):
            return spec.batch(g, samples=samples), spec.describe()
    d = np.asarray(demand, np.float64)
    if d.ndim == 2:
        d = d[None]
    if d.ndim != 3 or d.shape[-2:] != (g.n, g.n):
        raise ValueError(f"demand shape {d.shape} does not match "
                         f"(S, {g.n}, {g.n})")
    if samples is not None and len(d) not in (1, int(samples)):
        raise ValueError(f"demand batch has {len(d)} samples, wanted "
                         f"{samples}")
    return d, f"matrix[{len(d)}]"


def _demand_weights(dist: np.ndarray
                    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """``(off, dropped, hops)`` from (.., n, n) dist: the routable cells,
    and the float64 weights of the demand sums, 1.0 where demand is dropped
    (diagonal and unreachable) and the hop count where it routes."""
    off = np.isfinite(dist) & (dist > 0)           # self-demand never routes
    return (off, np.where(off, 0.0, 1.0),
            np.where(off, dist, 0.0).astype(np.float64))


def _per_sample_dot(demand: np.ndarray, w: np.ndarray) -> np.ndarray:
    """(S,) sums of each demand matrix against the (n, n) or (S, n, n)
    cell weights ``w``: a float64 mat-vec, no (S, n, n) temporary."""
    d = demand.reshape(len(demand), -1)
    if w.ndim == 2:
        return d @ w.reshape(-1)
    return np.einsum("sk,sk->s",
                     *np.broadcast_arrays(d, w.reshape(len(w), -1)))


def _traffic_metrics(link: np.ndarray, demand: np.ndarray,
                     weights: Tuple[np.ndarray, np.ndarray], n_links: int,
                     capacity: float) -> Dict[str, np.ndarray]:
    """Per-sample congestion metrics from (S, L) float64 loads on the
    fabric's directed-link cells (every other cell of ``adj * acc`` is 0)
    and the (S or 1, n, n) demand against ``weights`` (`_demand_weights`)."""
    from ..resilience.degradation import _masked_mean, _masked_percentiles

    s = len(link)
    demand = np.asarray(demand, np.float64)
    total = np.broadcast_to(demand.reshape(len(demand), -1).sum(1), (s,))
    # the dropped volume is summed itself, so it is exactly 0 where nothing
    # drops, and the routed volume is what is left of the offered
    lost = _per_sample_dot(demand, weights[0])
    routed_sum = total - lost
    dropped = np.where(total > 0, lost / np.maximum(total, 1e-300), 0.0)
    peak = link.max(1, initial=0.0)
    tput = np.where((routed_sum > 0) & (peak > 0),
                    capacity / np.maximum(peak, 1e-300), 0.0)
    pos = link > 0
    p50, p90, p99 = _masked_percentiles(link, pos, (0.5, 0.9, 0.99))
    hops = _per_sample_dot(demand, weights[1])
    return {
        "max_link_load": peak,
        "tput_lb": tput,
        "mean_link_load": _masked_mean(link, pos),
        "p50_link_load": p50,
        "p90_link_load": p90,
        "p99_link_load": p99,
        "links_used_frac": pos.sum(1) / max(n_links, 1),
        "avg_hops": np.where(routed_sum > 0,
                             hops / np.maximum(routed_sum, 1e-300), 0.0),
        "demand_total": np.array(total),
        "dropped_demand_frac": dropped,
    }


@functools.lru_cache(maxsize=None)
def _gather_fn():
    import jax

    def gather(loads, cells):
        # by row and column: a flat reshape of the stack would copy it into
        # another layout on a TPU first
        n = loads.shape[-1]
        return loads[:, cells // n, cells % n]

    return jax.jit(gather)


def _link_loads(loads, cells: np.ndarray, device_cells=None) -> np.ndarray:
    """(C, L) float64 values of (C, n, n) ``loads`` at the flat ``cells``.

    Device loads are gathered where they are, at the uploaded
    ``device_cells``, and only the (C, L) float32 link loads come down
    (``traffic.wait``, ``traffic.d2h``); host loads are indexed in place.
    """
    from ... import transfers

    if isinstance(loads, np.ndarray):
        with obs.span("traffic.host"):
            return loads.reshape(len(loads), -1)[:, cells].astype(
                np.float64, copy=False)
    link = _gather_fn()(loads, device_cells)
    link = transfers.download(transfers.wait(link, "traffic"), "traffic",
                              "traffic_link_loads")
    with obs.span("traffic.host"):
        return link.astype(np.float64)


def evaluate_traffic_batch(g: Graph, demand: DemandLike,
                           dist: Optional[np.ndarray] = None,
                           mult: Optional[np.ndarray] = None,
                           use_kernel: bool = True,
                           mask_chunk: Optional[int] = None,
                           capacity: float = 1.0) -> Dict[str, np.ndarray]:
    """Per-matrix congestion metrics over the *unfailed* graph.

    Returns ``{metric: (S,) array}`` for TRAFFIC_METRICS. The whole batch
    runs in stacked passes of at most ``mask_chunk`` matrices (auto-sized
    from the resilience working-set budget when None); the routing state
    (``dist``/``mult``) is computed once — pass precomputed ``(n, n)``
    arrays (e.g. a sweep's slices) to skip even that.

    Traced, demand generation is the ``demand.host`` span and the passes
    run inside ``traffic.scenario``, whose ``products`` attribute counts
    the weighted counting products (2 per BFS level per matrix) and
    ``link_cells`` the directed-link cells each matrix is reduced over.
    On the kernel path each pass's loads stay on the device and only
    their link cells come down.
    """
    from ... import transfers
    from ..resilience.degradation import _auto_chunk
    from ..routing.assign import ecmp_demand_loads

    batch, label = demand_batch(g, demand)
    s, n = len(batch), g.n
    with obs.span("traffic.host"):
        adj = g.adjacency_dense()
        # loads are adj * acc: only the nonzero adjacency cells carry any
        cells = np.flatnonzero(adj)
    if dist is None or mult is None:
        dist, mult = batched_dist_mult(adj, use_kernel)
    if mask_chunk is None:
        mask_chunk = _auto_chunk(n, s)
    parts = []
    with obs.span("traffic.scenario", cat="traffic", demand=label,
                  samples=s, routers=n, mask_chunk=mask_chunk,
                  link_cells=len(cells)) as sp:
        with obs.span("traffic.host"):
            weights = _demand_weights(dist)[1:]
        device_cells = transfers.upload(
            cells.astype(np.int32), "traffic",
            "traffic_link_cells") if use_kernel else None
        for lo in range(0, s, mask_chunk):
            d = batch[lo:lo + mask_chunk]
            loads = ecmp_demand_loads(dist, mult, adj, d,
                                      use_kernel=use_kernel, device=True)
            link = _link_loads(loads, cells, device_cells)
            with obs.span("traffic.host"):
                parts.append(_traffic_metrics(link, d, weights,
                                              2 * len(g.edges), capacity))
        with obs.span("traffic.host"):
            out = {k: np.concatenate([p[k] for p in parts])
                   for k in parts[0]}
            if sp:
                finite = np.isfinite(dist)
                diameter = int(dist[finite].max()) if finite.any() else 0
                sp.set(passes=len(parts), diameter=diameter,
                       products=2 * diameter * s,
                       max_link_load=float(out["max_link_load"].max()),
                       dropped=float(out["dropped_demand_frac"].mean()))
    return out


def evaluate_traffic_failure_batch(
        g: Graph, demand: DemandLike, adjacency: np.ndarray,
        dist: Optional[np.ndarray] = None, mult: Optional[np.ndarray] = None,
        use_kernel: bool = True, mask_chunk: Optional[int] = None,
        capacity: float = 1.0) -> Dict[str, np.ndarray]:
    """Traffic metrics over a stacked *masked* adjacency batch.

    The traffic x failure grid cell engine: ``adjacency`` is a
    ``(S, n, n)`` failure-masked stack (`resilience.faults.FailureBatch
    .adjacency`), demand sample ``i`` rides failure mask ``i`` (a single
    matrix broadcasts). Per chunk, the batched wavefront recomputes
    dist/mult on the masked graphs, then one demand-weighted Brandes pass
    produces the loads. Adds ``reachable_frac`` to TRAFFIC_METRICS.
    """
    from ..resilience.degradation import _auto_chunk

    adjacency = np.asarray(adjacency, np.float32)
    s, n = len(adjacency), g.n
    batch, label = demand_batch(g, demand)
    if len(batch) not in (1, s):
        raise ValueError(f"{len(batch)} demand samples cannot pair with "
                         f"{s} failure masks")
    if mask_chunk is None:
        mask_chunk = _auto_chunk(n, s)
    # the unfailed fabric's links: every masked graph's links are among them
    cells = np.flatnonzero(g.adjacency_dense())
    parts = []
    with obs.span("traffic.cell", cat="traffic", demand=label, samples=s,
                  routers=n, mask_chunk=mask_chunk) as sp:
        for lo in range(0, s, mask_chunk):
            a = adjacency[lo:lo + mask_chunk]
            d = batch if len(batch) == 1 else batch[lo:lo + mask_chunk]
            if dist is None or mult is None:
                cd, cm = batched_dist_mult(a, use_kernel)
            else:
                cd, cm = dist[lo:lo + mask_chunk], mult[lo:lo + mask_chunk]
            parts.append(_chunk_cell(g, a, d, cd, cm, cells, use_kernel,
                                     capacity))
        out = {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}
        sp.set(passes=len(parts),
               dropped=float(out["dropped_demand_frac"].mean()))
    return out


def _chunk_cell(g: Graph, adj: np.ndarray, demand: np.ndarray,
                dist: np.ndarray, mult: np.ndarray, cells: np.ndarray,
                use_kernel: bool, capacity: float) -> Dict[str, np.ndarray]:
    from ..routing.assign import ecmp_demand_loads

    loads = ecmp_demand_loads(dist, mult, adj.astype(np.float64), demand,
                              use_kernel=use_kernel)
    # a failed link carries 0, which the used-link statistics leave out
    off, *weights = _demand_weights(dist)
    out = _traffic_metrics(_link_loads(loads, cells), demand, weights,
                           2 * len(g.edges), capacity)
    c, n = len(adj), g.n
    out["reachable_frac"] = off.reshape(c, -1).sum(1) / max(n * (n - 1), 1)
    return out


def saturation_search(g: Graph, spec: Union[str, TrafficSpec],
                      capacity: float = 1.0, hi: Optional[float] = None,
                      rounds: int = 5, grid: int = 9,
                      samples: Optional[int] = None, use_kernel: bool = True,
                      mask_chunk: Optional[int] = None) -> Dict:
    """Max sustainable injection rate before the peak link saturates.

    Bisection on the per-router injection rate, batched across the rate
    grid: every refinement round stacks ``grid`` candidate rates x all
    demand samples into ONE batched load pass and contracts the bracket
    around the largest rate whose worst-sample peak load stays within
    ``capacity`` (the network_tester "max sustainable injection" sweep).

    Returns ``{"sat_rate", "ci95", "per_sample", "rounds", "probe_rate",
    "peak_at_probe"}`` — ``sat_rate`` is the bisected worst-sample rate;
    ``per_sample`` the exact per-sample crossings ``probe_rate * capacity
    / peak`` (load is homogeneous in rate for every registered pattern)
    with a bootstrap 95% CI. Demand that routes nothing anywhere raises.
    """
    from ..analysis.estimator import bootstrap_ci
    from ..routing.assign import ecmp_demand_loads
    from ..resilience.degradation import _auto_chunk

    spec = as_spec(spec)
    base, label = demand_batch(g, spec)
    s, n = len(base), g.n
    adj = g.adjacency_dense()
    dist, mult = batched_dist_mult(adj, use_kernel)
    if mask_chunk is None:
        mask_chunk = _auto_chunk(n, s * max(int(grid), 2))

    def peaks_for(stack: np.ndarray) -> np.ndarray:
        out = np.empty(len(stack))
        for lo in range(0, len(stack), mask_chunk):
            loads = ecmp_demand_loads(dist, mult, adj,
                                      stack[lo:lo + mask_chunk],
                                      use_kernel=use_kernel)
            out[lo:lo + mask_chunk] = loads.reshape(len(loads), -1).max(1)
        return out

    with obs.span("traffic.saturation", cat="traffic", demand=label,
                  samples=s, routers=n, rounds=rounds, grid=grid) as sp:
        probe = float(spec.rate) if spec.rate > 0 else 1.0
        peak0 = peaks_for(base * (probe / spec.rate if spec.rate > 0
                                  else 1.0))
        if not (peak0 > 0).any():
            raise ValueError(f"{label}: no demand routes on {g.name}; "
                             f"cannot saturate")
        per_sample = np.where(peak0 > 0,
                              probe * capacity / np.maximum(peak0, 1e-300),
                              np.inf)
        finite = per_sample[np.isfinite(per_sample)]
        lo_r, hi_r = 0.0, float(hi) if hi else 2.0 * float(finite.max())
        history = []
        unit = base / probe if spec.rate > 0 else base
        for _ in range(int(rounds)):
            rates = np.linspace(lo_r, hi_r, int(grid))
            stack = (rates[:, None, None, None] * unit[None]
                     ).reshape(-1, n, n)
            peaks = peaks_for(stack).reshape(len(rates), s)
            worst = peaks.max(axis=1)
            ok = worst <= capacity + 1e-12
            history.append({"lo": lo_r, "hi": hi_r,
                            "feasible": int(ok.sum())})
            if ok.all():
                lo_r = float(rates[-1])
                hi_r *= 2.0
                continue
            last = int(np.flatnonzero(ok)[-1]) if ok.any() else 0
            lo_r = float(rates[last])
            hi_r = float(rates[min(last + 1, len(rates) - 1)])
        point, ci_lo, ci_hi = bootstrap_ci(finite, seed=spec.seed)
        sp.set(sat_rate=lo_r)
        return {
            "demand": label,
            "capacity": float(capacity),
            "sat_rate": lo_r,
            "per_sample_mean": float(point),
            "ci95": [float(ci_lo), float(ci_hi)],
            "per_sample": [float(v) for v in per_sample],
            "probe_rate": probe,
            "peak_at_probe": [float(v) for v in peak0],
            "rounds": history,
        }

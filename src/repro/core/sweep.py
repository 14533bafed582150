"""Equal-cost topology comparison driver — the paper's headline table.

Instantiates many families at *matched construction cost* (``by_cost``
ladder solving over closed-form specs) and pushes the whole set through the
analysis stack **batched**: every topology's adjacency/distance block is
padded to one shared tile size and stacked along a leading axis, so the
semiring kernels (`repro.kernels.ops.batched_minplus_matmul` /
``batched_count_matmul``) run one launch per product for the entire sweep
instead of one per topology. Stages:

1. level-synchronous Brandes frontier expansion — ONE stacked counting
   product per BFS level yields hop distances AND exact shortest-path
   multiplicities together (``x_k = F_k @ A``; pairs first reached at
   level k+1 get dist = k+1 and sigma = x). Hop-distance BFS needs no
   tropical products at all, and the counting semiring rides the kernel's
   fast MXU path — this is where the sweep's speedup over looping
   ``analyze()`` per topology comes from (the loop's engine runs general
   min-plus squaring on the VPU path; benchmarked in
   ``benchmarks/bench_analysis.py`` and the ``--sweep`` example);
2. stacked Brandes accumulation (`routing.assign.ecmp_all_pairs_loads`,
   2 products per level) -> exact expected ECMP link loads under uniform
   all-pairs demand, whose max gives the per-pair saturation-throughput
   lower bound ``lambda >= 1 / max_load`` (capacity 1 per link direction);
3. `core.costmodel` over each spec -> construction cost and power columns.

Total: 3 x diameter stacked MXU-path products for the whole sweep. On the
kernel path both level loops run **device-resident** (`analysis.wavefront`):
the padded stack is uploaded once, the BFS wavefront and the Brandes
accumulation each execute as one jitted `jax.lax.while_loop` (fused
frontier-step kernel, on-device convergence tests), and only the final
dist/mult/loads matrices come back to host.

CLI::

  python -m repro.core.sweep [--families a,b,... ] [--ref-servers N]
                             [--budget C] [--max-routers N] [--out DIR]
  python -m repro.core.sweep --check    # CI gate: sizers + connectivity
"""
from __future__ import annotations

import json
import pathlib
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import obs
from . import costmodel
from . import topology as topo
from .graph import Graph
from .routing.assign import ecmp_all_pairs_loads

__all__ = ["equal_cost_graphs", "batched_apsp", "batched_dist_mult",
           "sweep", "format_table", "check_families",
           "sweep_extreme", "format_extreme_table"]

_INF = np.float32(np.inf)


# -- batched products ---------------------------------------------------------

def _batched_minplus(use_kernel: bool):
    if use_kernel:
        import jax.numpy as jnp

        from .. import kernels

        return lambda a, b: np.asarray(
            kernels.ops.batched_minplus_matmul(jnp.asarray(a), jnp.asarray(b)))

    def oracle(a: np.ndarray, b: np.ndarray, chunk: int = 64) -> np.ndarray:
        out = np.empty((a.shape[0], a.shape[1], b.shape[2]), np.float32)
        for i in range(a.shape[0]):  # row-chunked to bound the broadcast
            for lo in range(0, a.shape[1], chunk):
                hi = min(a.shape[1], lo + chunk)
                out[i, lo:hi] = np.min(
                    a[i, lo:hi, :, None] + b[i][None, :, :], axis=1)
        return out

    return oracle


def _batched_count(use_kernel: bool):
    if use_kernel:
        import jax.numpy as jnp

        from .. import kernels

        return lambda a, b: np.asarray(
            kernels.ops.batched_count_matmul(jnp.asarray(a), jnp.asarray(b)))
    return lambda a, b: np.asarray(a, np.float64) @ np.asarray(b, np.float64)


# -- equal-cost instantiation -------------------------------------------------

def equal_cost_graphs(
        families: Optional[Sequence[str]] = None,
        budget: Optional[float] = None,
        ref: Tuple[str, int] = ("slimfly", 2000),
        max_routers: int = 1024,
) -> Tuple[List[Graph], float]:
    """Build one graph per family at matched construction cost.

    ``budget`` defaults to the cost of ``ref`` = (family, n_servers) sized
    by :func:`topology.by_servers`. ``max_routers`` additionally caps every
    instance (keeps the sweep inside the dense-analysis regime; the cost
    column then reports what each family actually spends). Families whose
    smallest configuration exceeds the budget are skipped with a notice.
    """
    families = list(families) if families else topo.families()
    if budget is None:
        params = topo.solve(ref[0], lambda s: s.n_servers, ref[1], "closest")
        budget = costmodel.cost_report(topo.spec(ref[0], **params))["cost_total"]
    graphs: List[Graph] = []
    for fam in families:
        try:
            g = topo.by_cost(fam, budget, max_routers=max_routers)
        except ValueError as exc:
            obs.log("sweep.skip", family=fam, reason=str(exc))
            continue
        g.validate()
        graphs.append(g)
    return graphs, float(budget)


# -- batched analysis stages --------------------------------------------------

def _stack_adjacency(graphs: Sequence[Graph]) -> np.ndarray:
    """Stack adjacencies padded to the max router count; padding rows are
    isolated phantom routers (all-zero), inert under every product."""
    p = max(g.n for g in graphs)
    adj = np.zeros((len(graphs), p, p), np.float32)
    for i, g in enumerate(graphs):
        adj[i, :g.n, :g.n] = g.adjacency_dense(np.float32)
    return adj


def _stack_seeds(graphs: Sequence[Graph]) -> Tuple[np.ndarray, np.ndarray]:
    """Stack distance seeds and adjacencies, padded to the max router count.

    Padding rows/cols are +inf (no edges) with a 0 diagonal — isolated
    phantom routers that can never shorten a real path, so one stacked
    squaring loop serves every topology at once.
    """
    adj = _stack_adjacency(graphs)
    nb, p, _ = adj.shape
    dist = np.full((nb, p, p), _INF, np.float32)
    for i, g in enumerate(graphs):
        dist[i, :g.n, :g.n] = g.distance_seed()
    idx = np.arange(p)
    dist[:, idx, idx] = 0.0
    return dist, adj


def batched_apsp(graphs: Sequence[Graph], use_kernel: bool = True
                 ) -> np.ndarray:
    """All-pairs hop distances for a whole stack of topologies at once.

    Kernel path: the device-resident wavefront engine (one jitted level
    loop for the whole stack). Oracle path: host-looped stacked min-plus
    squaring (`_apsp_from_stack`).
    """
    if use_kernel:
        from .analysis.wavefront import wavefront_dist_mult

        dist, _ = wavefront_dist_mult(_stack_adjacency(graphs))
        return dist
    dist, _ = _stack_seeds(graphs)
    return _apsp_from_stack(dist, _batched_minplus(use_kernel))


def _apsp_from_stack(dist: np.ndarray, minplus) -> np.ndarray:
    max_squarings = max(1, int(np.ceil(np.log2(max(2, dist.shape[1])))))
    for _ in range(max_squarings):
        nxt = minplus(dist, dist)
        if np.array_equal(nxt, dist):
            return nxt
        dist = nxt
    return dist


def batched_dist_mult(adj: np.ndarray, count=None,
                      max_levels: Optional[int] = None):
    """Hop distances AND shortest-path multiplicities from one stacked
    counting product per BFS level (Brandes' frontier identity).

    ``x_k = F_k @ A`` extends the level-k multiplicity frontier by one hop;
    any pair first reached at level k+1 has ``sigma = x_k`` there. Distances
    fall out of *when* a pair is first reached, so hop-distance APSP needs
    no tropical (VPU-path) products at all — every product is a counting
    matmul on the kernel's fast MXU path. Stops as soon as a sweep makes no
    new pair reachable (= max diameter over the stack, +1 to confirm).
    Padding rows are isolated phantoms: their frontier never grows.

    With ``count=None`` the whole loop runs device-resident
    (`analysis.wavefront.dist_mult_device`) — one jitted `lax.while_loop`,
    no per-level host masking. Passing an explicit ``count`` product (or a
    ``max_levels`` cap, which the device engine does not expose) keeps the
    host-looped reference sweep below (the wavefront engine's batched
    oracle in the tests).
    """
    if count is None:
        if max_levels is None:
            from .analysis.wavefront import wavefront_dist_mult

            dist, mult = wavefront_dist_mult(adj)
            return dist, mult.astype(np.float64)
        count = _batched_count(True)  # capped sweep: host loop, kernel product
    nb, p, _ = adj.shape
    if max_levels is None:
        max_levels = p
    dist = np.full((nb, p, p), _INF, np.float32)
    idx = np.arange(p)
    dist[:, idx, idx] = 0.0
    mult = np.where(dist == 0, 1.0, 0.0).astype(np.float64)
    frontier = mult.astype(adj.dtype)
    for level in range(1, max_levels + 1):
        x = np.asarray(count(frontier, adj))
        new = (x > 0) & ~np.isfinite(dist)
        if not new.any():
            break
        dist[new] = level
        mult = np.where(new, x, mult)
        frontier = np.where(new, x, 0.0).astype(adj.dtype)
    return dist, mult


# -- the driver ---------------------------------------------------------------

def sweep(families: Optional[Sequence[str]] = None,
          budget: Optional[float] = None,
          ref: Tuple[str, int] = ("slimfly", 2000),
          max_routers: int = 1024,
          use_kernel: bool = True,
          throughput: bool = True,
          graphs: Optional[Sequence[Graph]] = None,
          mesh="auto", traffic=None) -> Dict:
    """Run the equal-cost comparison; returns ``{"rows": [...], ...}``.

    Pass ``graphs`` to analyze a pre-built list (the benchmarks reuse this
    to time the batched path against a per-topology ``analyze()`` loop on
    identical instances). With more than one jax device visible the stacked
    chain runs row-sharded over a 1-D mesh (`analysis.distributed`):
    ``mesh="auto"`` picks it up, an explicit Mesh pins it, None forces the
    single-device engines.

    ``traffic`` (a `core.traffic.TrafficSpec` or spec string, ``--traffic``
    on the CLI) additionally pushes that scenario's demand batch through
    each family, reusing the sweep's own dist/mult slices — adds
    ``traffic`` / ``traffic_max_load`` / ``traffic_tput_lb`` columns.
    """
    t0 = time.time()
    traffic_spec = None
    if traffic is not None:
        from .traffic.spec import as_spec

        traffic_spec = as_spec(traffic)
    with obs.span("sweep", cat="sweep", use_kernel=use_kernel) as root:
        if graphs is None:
            with obs.span("sweep.build", cat="sweep"):
                graphs, budget = equal_cost_graphs(families, budget, ref,
                                                   max_routers)
        if not graphs:
            raise ValueError("sweep has no topologies to compare")
        root.set(families=len(graphs), routers=max(g.n for g in graphs))

        with obs.span("sweep.stack", cat="sweep"):
            adj = _stack_adjacency(graphs)
        wf_levels = None
        if use_kernel:
            # device-resident chain: upload the padded stack once, run the
            # wavefront level loop AND the Brandes accumulation on device,
            # and transfer only the three final matrices back to host.
            # With a multi-device mesh each device owns a row block of
            # every stacked problem; only the convergence flag (and one
            # final psum of the Brandes partials) crosses devices.
            import jax.numpy as jnp

            from .analysis import distributed as DX
            from .analysis import wavefront as WF

            k = adj.shape[-1]
            if mesh == "auto":
                mesh = DX.default_mesh(k)
            tel = obs.enabled()
            sharded = mesh is not None and mesh.size > 1
            with obs.span("sweep.dist_mult", cat="sweep",
                          stacked=len(graphs), padded=k,
                          sharded=sharded) as sp:
                if sharded:
                    p, _, block = DX.pad_block_sharded(
                        k, mesh.shape[DX.ROW_AXIS], batched=True)
                else:
                    p, block = WF.pad_block(k, batched=True)
                padded = WF.pad_operand(adj, p, 0.0)
                adj_d = jnp.asarray(padded)
                obs.record_h2d(padded.nbytes, "sweep_stack")
                if sharded:
                    out = DX.dist_mult_sharded(adj_d, mesh, block=block,
                                               telemetry=tel)
                else:
                    out = WF.dist_mult_device(adj_d, block=block,
                                              telemetry=tel)
                if tel:
                    dist_d, mult_d, aux = out
                    attrs = WF.telemetry_attrs(aux)
                    wf_levels = attrs.get("levels_per_graph")
                    sp.set(**attrs)
                else:
                    dist_d, mult_d = out
            with obs.span("sweep.ecmp_loads", cat="sweep"):
                if not throughput:
                    loads_d = None
                elif sharded:
                    loads_d = DX.ecmp_loads_sharded(dist_d, mult_d, adj_d,
                                                    mesh, block=block)
                else:
                    loads_d = WF.ecmp_loads_device(dist_d, mult_d, adj_d,
                                                   block=block)
            with obs.span("sweep.download", cat="sweep"):
                dist = np.asarray(dist_d)[:, :k, :k]
                mult = np.asarray(mult_d)[:, :k, :k].astype(np.float64)
                loads = (np.asarray(loads_d)[:, :k, :k] if throughput
                         else None)
            from .analysis.paths import _warn_if_inexact

            _warn_if_inexact(mult, use_kernel=True)  # device counts are f32
        else:
            with obs.span("sweep.dist_mult", cat="sweep",
                          stacked=len(graphs), oracle=True):
                count = _batched_count(use_kernel)
                dist, mult = batched_dist_mult(adj, count)
            with obs.span("sweep.ecmp_loads", cat="sweep", oracle=True):
                loads = (ecmp_all_pairs_loads(dist, mult, adj, product=count)
                         if throughput else None)

        with obs.span("sweep.rows", cat="sweep"):
            rows = []
            for i, g in enumerate(graphs):
                n = g.n
                d = dist[i, :n, :n]
                m = mult[i, :n, :n]
                off = np.isfinite(d) & (d > 0)
                spec = g.meta.get("spec")
                cost = costmodel.cost_report(spec) if spec is not None else {}
                row = {
                    "family": g.meta["spec"].family if spec else g.name,
                    "params": spec.describe() if spec else g.name,
                    "routers": n,
                    "servers": g.num_servers,
                    "radix": spec.router_radix if spec else g.radix,
                    # partitioned-graph contract: every stat below covers
                    # the reachable pairs; this column says how many that is
                    "reachable_frac": (float(off.sum() / max(1, n * (n - 1)))
                                       if n > 1 else 1.0),
                    "diameter": int(d[off].max()) if off.any() else 0,
                    "avg_spl": float(d[off].mean()) if off.any() else 0.0,
                    "mult_mean": float(m[off].mean()) if off.any() else 0.0,
                    "mult_min": float(m[off].min()) if off.any() else 0.0,
                    "cost": cost.get("cost_total"),
                    "power_kw": (cost.get("power_total_w", 0.0) / 1e3
                                 if cost else None),
                    "cables_electrical": cost.get("cables_electrical"),
                    "cables_optical": cost.get("cables_optical"),
                }
                if loads is not None:
                    peak = float(loads[i, :n, :n].max())
                    row["tput_lb"] = 1.0 / peak if peak > 0 else 1.0
                if wf_levels is not None:
                    # device telemetry: BFS levels this family's wavefront
                    # actually ran (= its diameter on connected graphs)
                    row["wavefront_levels"] = int(wf_levels[i])
                if traffic_spec is not None:
                    from .traffic.scenarios import evaluate_traffic_batch

                    tv = evaluate_traffic_batch(g, traffic_spec, dist=d,
                                                mult=m,
                                                use_kernel=use_kernel)
                    row["traffic"] = traffic_spec.describe()
                    row["traffic_max_load"] = float(
                        tv["max_link_load"].mean())
                    row["traffic_tput_lb"] = float(tv["tput_lb"].mean())
                rows.append(row)
    return {
        "rows": rows,
        "budget": budget,
        "batched": True,
        "use_kernel": use_kernel,
        "traffic": traffic_spec.describe() if traffic_spec else None,
        "elapsed_s": round(time.time() - t0, 2),
    }


_COLS = [
    ("family", "<12s", "family"),
    ("routers", ">8d", "routers"),
    ("servers", ">9d", "servers"),
    ("radix", ">6d", "radix"),
    ("diam", ">5d", "diameter"),
    ("avg-spl", ">8.2f", "avg_spl"),
    ("mult", ">10.2f", "mult_mean"),
    ("tput-lb", ">8.4f", "tput_lb"),
    ("cost", ">11.3e", "cost"),
    ("power-kW", ">9.1f", "power_kw"),
]


#: extra columns when the sweep ran with a --traffic scenario
_TRAFFIC_COLS = [
    ("tr-load", ">9.3f", "traffic_max_load"),
    ("tr-tput", ">9.4f", "traffic_tput_lb"),
]


def format_table(result: Dict) -> str:
    """Paper-style fixed-width comparison table."""
    budget = result.get("budget")
    budget_s = f"budget={budget:.3e} " if budget else ""
    traffic = result.get("traffic")
    traffic_s = f" traffic={traffic}" if traffic else ""
    cols = _COLS + (_TRAFFIC_COLS if traffic else [])
    lines = [f"equal-cost sweep: {budget_s}"
             f"({len(result['rows'])} families, "
             f"{result['elapsed_s']}s batched analysis{traffic_s})"]
    hdr = "".join(f"{name:>{_w(fmt)}s}" for name, fmt, _ in cols)
    lines.append(hdr)
    lines.append("-" * len(hdr))
    for row in sorted(result["rows"], key=lambda r: r["family"]):
        cells = []
        for _, fmt, key in cols:
            v = row.get(key)
            cells.append(" " * _w(fmt) if v is None else f"{v:{fmt}}")
        lines.append("".join(cells))
    return "\n".join(lines)


def _w(fmt: str) -> int:
    digits = ""
    for ch in fmt[1:]:
        if ch.isdigit():
            digits += ch
        else:
            break
    return int(digits) if digits else 10


def sweep_extreme(families: Optional[Sequence[str]] = None,
                  target_routers: int = 100_000, k_sources: int = 32,
                  seed: int = 0, packed: bool = True, mesh=None,
                  tile_rows: Optional[int] = None,
                  adjacency_budget: Optional[int] = None,
                  throughput: bool = False) -> Dict:
    """Extreme-scale sweep: every family sized to ``target_routers``
    ROUTERS (not servers — at 100k the router count is the analysis cost),
    analyzed through the sampled-sources estimator.

    Each family runs `analysis.estimator.sampled_sources_summary`:
    ``k_sources`` exact source rows through the tiled/composed streaming
    engine (``packed=True`` by default — int16/uint32 cells, uint8
    adjacency panels, 4x less streamed than f32) and bootstrap 95% CIs on
    the aggregates. A family whose parameter ladder cannot reach the
    target records an ``error`` row instead of aborting the sweep — the
    table shows the gap. Returns ``{"rows": [...], ...}`` for
    :func:`format_extreme_table` / the BENCH_8 baseline row.
    """
    from .analysis.estimator import sampled_sources_summary

    families = list(families) if families else topo.families()
    t0 = time.time()
    rows: List[Dict] = []
    with obs.span("sweep.extreme", cat="sweep", target=target_routers,
                  k=k_sources, packed=packed) as root:
        for fam in families:
            try:
                params = topo.solve(fam, lambda s: s.n_routers,
                                    target_routers, "closest")
                with obs.span("sweep.extreme.build", cat="sweep",
                              family=fam):
                    g = topo.make(fam, **params)
            except (ValueError, KeyError) as exc:
                obs.log("sweep.extreme.skip", family=fam, error=str(exc))
                rows.append({"family": fam, "error": str(exc)})
                continue
            with obs.span("sweep.extreme.family", cat="sweep", family=fam,
                          routers=g.n):
                s = sampled_sources_summary(
                    g, k=k_sources, seed=seed, mesh=mesh,
                    tile_rows=tile_rows, packed=packed,
                    adjacency_budget=adjacency_budget,
                    throughput=throughput)
            spec = g.meta.get("spec")
            est = s["estimates"]
            row = {
                "family": g.name,
                "routers": g.n,
                "servers": spec.n_servers if spec is not None else None,
                "sampled_sources": s["sampled_sources"],
                "diameter_lb": s["diameter_lb"],
                "avg_spl": est["avg_spl"]["value"],
                "avg_spl_ci95": est["avg_spl"]["ci95"],
                "mult_mean": est["mult_mean"]["value"],
                "mult_mean_ci95": est["mult_mean"]["ci95"],
                "frac_multipath": est["frac_multipath"]["value"],
                "reached_frac": est["reached_frac"]["value"],
                "saturated": s["saturated"],
                "elapsed_s": s["elapsed_s"],
                "peak_rss_mb": s["peak_rss_mb"],
            }
            if "ecmp_saturation_throughput_lb" in est:
                row["ecmp_saturation_throughput_lb"] = (
                    est["ecmp_saturation_throughput_lb"]["value"])
                row["ecmp_saturation_throughput_lb_ci95"] = (
                    est["ecmp_saturation_throughput_lb"]["ci95"])
            rows.append(row)
        root.set(families=len(rows),
                 errors=sum("error" in r for r in rows))
    return {
        "target_routers": target_routers,
        "k_sources": k_sources,
        "seed": seed,
        "packed": packed,
        "rows": rows,
        "elapsed_s": round(time.time() - t0, 1),
        "peak_rss_mb": round(obs.peak_rss_mb(), 1),
    }


_XCOLS = (
    ("family", "<26s", "family"),
    ("routers", ">9d", "routers"),
    ("servers", ">10d", "servers"),
    ("k", ">5d", "sampled_sources"),
    ("diam>=", ">7d", "diameter_lb"),
    ("avg_spl", ">9.3f", "avg_spl"),
    ("+-ci", ">7.3f", "_spl_hw"),
    ("mult", ">13.2f", "mult_mean"),
    ("+-ci", ">10.2f", "_mult_hw"),
    ("multipath", ">10.3f", "frac_multipath"),
    ("sat", ">4s", "_sat"),
    ("s", ">8.1f", "elapsed_s"),
)


def format_extreme_table(result: Dict) -> str:
    """Fixed-width table for the sampled-sources extreme sweep (CIs shown
    as +- half-widths next to their estimates)."""
    lines = [f"extreme-scale sampled sweep: target={result['target_routers']}"
             f" routers, k={result['k_sources']} sources, "
             f"packed={result['packed']} "
             f"({result['elapsed_s']}s, peak rss "
             f"{result.get('peak_rss_mb', 0.0)} MB)"]
    hdr = "".join(f"{name:>{_w(fmt)}s}" if ">" in fmt else
                  f"{name:<{_w(fmt)}s}" for name, fmt, _ in _XCOLS)
    lines.append(hdr)
    lines.append("-" * len(hdr))
    for row in sorted(result["rows"], key=lambda r: r["family"]):
        if "error" in row:
            lines.append(f"{row['family']:<26s} SKIP: {row['error']}")
            continue
        r = dict(row)
        r["_spl_hw"] = (row["avg_spl_ci95"][1] - row["avg_spl_ci95"][0]) / 2
        r["_mult_hw"] = (row["mult_mean_ci95"][1]
                         - row["mult_mean_ci95"][0]) / 2
        r["_sat"] = "SAT" if row.get("saturated") else ""
        cells = []
        for _, fmt, key in _XCOLS:
            v = r.get(key)
            cells.append(" " * _w(fmt) if v is None else f"{v:{fmt}}")
        lines.append("".join(cells))
    return "\n".join(lines)


def check_families(n_servers: int = 300) -> List[str]:
    """CI gate: every registered family must have a working sizer (spec +
    ladder) and produce a connected graph. Returns failure messages."""
    failures = []
    for fam in topo.families():
        try:
            g = topo.by_servers(fam, n_servers)
            g.validate()
            if "spec" not in g.meta:
                failures.append(f"{fam}: generator attaches no TopologySpec")
        except Exception as exc:  # noqa: BLE001 - gate reports everything
            failures.append(f"{fam}: {type(exc).__name__}: {exc}")
    return failures


def main(argv: Optional[Sequence[str]] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--families", default=None,
                    help="comma-separated (default: all registered)")
    ap.add_argument("--budget", type=float, default=None)
    ap.add_argument("--ref-family", default="slimfly")
    ap.add_argument("--ref-servers", type=int, default=2000)
    ap.add_argument("--max-routers", type=int, default=512)
    ap.add_argument("--traffic", default=None,
                    help="TrafficSpec flag grammar (e.g. "
                         "'hotspot:zipf_a=1.4,samples=8'): add per-family "
                         "scenario load/throughput columns")
    ap.add_argument("--no-kernel", action="store_true",
                    help="numpy/jnp oracle products instead of Pallas")
    ap.add_argument("--out", default=None,
                    help="directory for comparison.{txt,json}")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="enable tracing and write a Chrome trace-event "
                         "file (load in https://ui.perfetto.dev or feed to "
                         "python -m repro.obs.report)")
    ap.add_argument("--check", action="store_true",
                    help="CI gate: verify sizers + connectivity, no sweep")
    ap.add_argument("--extreme", type=int, default=None, metavar="ROUTERS",
                    help="extreme-scale mode: size every family to this "
                         "many ROUTERS and run the sampled-sources "
                         "estimator instead of the equal-cost sweep")
    ap.add_argument("--sample-sources", type=int, default=32, metavar="K",
                    help="extreme mode: exact source rows to sample")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-packed", action="store_true",
                    help="extreme mode: f32 cells instead of int16/uint32")
    ap.add_argument("--shards", type=int, default=None,
                    help="extreme mode: row-shard over this many devices "
                         "(composed engine)")
    ap.add_argument("--tile-rows", type=int, default=None)
    ap.add_argument("--adjacency-budget", type=int, default=None,
                    help="device bytes before adjacency panels stream")
    ap.add_argument("--throughput", action="store_true",
                    help="extreme mode: add the sampled ECMP saturation-"
                         "throughput estimate (host Brandes, O(E)/source)")
    args = ap.parse_args(argv)

    if args.trace:
        obs.enable()

    if args.extreme:
        mesh = None
        if args.shards and args.shards > 1:
            from .analysis.distributed import device_mesh

            mesh = device_mesh(args.shards)
        fams = args.families.split(",") if args.families else None
        result = sweep_extreme(
            fams, target_routers=args.extreme,
            k_sources=args.sample_sources, seed=args.seed,
            packed=not args.no_packed, mesh=mesh,
            tile_rows=args.tile_rows,
            adjacency_budget=args.adjacency_budget,
            throughput=args.throughput)
        table = format_extreme_table(result)
        print(table)
        if args.out:
            out = pathlib.Path(args.out)
            out.mkdir(parents=True, exist_ok=True)
            (out / "extreme.txt").write_text(table + "\n")
            (out / "extreme.json").write_text(
                json.dumps(result, indent=1, default=str))
            obs.log("sweep.wrote", txt=str(out / "extreme.txt"),
                    json=str(out / "extreme.json"))
        if args.trace:
            obs.export(args.trace)
            obs.log("sweep.trace", path=args.trace)
        return 0

    if args.check:
        failures = check_families()
        for msg in failures:
            print(f"[sweep --check] FAIL {msg}")
        if not failures:
            print(f"[sweep --check] {len(topo.families())} families OK "
                  f"(sizer + spec + connected)")
        return 1 if failures else 0

    fams = args.families.split(",") if args.families else None
    result = sweep(fams, budget=args.budget,
                   ref=(args.ref_family, args.ref_servers),
                   max_routers=args.max_routers,
                   use_kernel=not args.no_kernel, traffic=args.traffic)
    table = format_table(result)
    print(table)
    if args.out:
        out = pathlib.Path(args.out)
        out.mkdir(parents=True, exist_ok=True)
        (out / "comparison.txt").write_text(table + "\n")
        (out / "comparison.json").write_text(
            json.dumps(result, indent=1, default=str))
        obs.log("sweep.wrote", txt=str(out / "comparison.txt"),
                json=str(out / "comparison.json"))
    if args.trace:
        obs.export(args.trace)
        obs.log("sweep.trace", path=args.trace)
    return 0


if __name__ == "__main__":
    from ..device import use_compile_cache

    use_compile_cache()
    raise SystemExit(main())

"""Smoke run of the analysis path on a TPU, through the library entry points.

    python chip_smoke.py             # one chip: every phase below
    python chip_smoke.py --chips 4   # the row-sharded and composed engines

One process, one chip (or one four-chip host). Each phase calls what a user
calls, checks the result against the repository's host reference and
raises on a mismatch; it prints its wall time for a first (compiling) call
and a second warm call, then its check, each on a line of its own. The last
line of standard output is one JSON object naming the device. Without a TPU
the script exits non-zero before any analysis runs, and no kernel runs
interpreted on the chip: `repro.device.resolve_interpret` compiles every
kernel there and refuses ``interpret=True``.

One chip:

* main analysis — ``AnalysisEngine(slimfly(q=41), mesh=None).report()``
  (3362 routers, ~100k servers); dist/mult bit-equal to the host Brandes
  BFS on 64 seeded sources, the path-length histogram equal to
  ``np.bincount`` of the distances;
* ECMP — ``ecmp_all_pairs_loads`` on the device within 1e-5 of the f64
  host engine;
* traffic — ``evaluate_traffic_batch`` of a Zipf hotspot batch within 1e-5
  of the f64 host engine;
* exactness — hypercube(dim=10), multiplicities up to 10!, bit-equal to the
  f64 host engine;
* packed/tiled — the packed tiled engine bit-equal to the f32 wavefront;
* throughput — certified max-concurrent-flow bounds, and one weighted APSP
  against its oracle.

Four chips: ``sharded_dist_mult`` / ``ecmp_loads_sharded`` on slimfly(q=41)
against the single-device engines, and the composed packed engine on
jellyfish(32768, r=16) against the host BFS on 64 seeded sources.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

SRC = pathlib.Path(__file__).resolve().parent / "src"
SEED = 0
SOURCES = 64
TOL = 1e-5


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def timed(name: str, fn):
    """Run ``fn`` twice (compile + warm), print both wall times, return the
    warm result."""
    t0 = time.perf_counter()
    fn()
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = fn()
    warm = time.perf_counter() - t0
    print(f"[time] {name}: first {first:.6f} s, warm {warm:.6f} s",
          flush=True)
    return out


def passed(name: str, what: str) -> None:
    print(f"[check] {name}: {what}", flush=True)


def max_rel_err(got, want) -> float:
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = np.maximum(np.abs(want), 1e-300)
    err = np.abs(got - want) / scale
    return float(err[np.abs(want) > 0].max(initial=0.0))


def seeded_sources(n: int):
    import numpy as np

    rng = np.random.default_rng(SEED)
    return np.sort(rng.choice(n, size=min(SOURCES, n), replace=False))


def bfs_rows_equal(g, dist, mult, sources, packed: bool = False) -> None:
    """Rows of (dist, mult) against the host Brandes BFS, bit for bit."""
    import numpy as np

    from repro.core.analysis.distributed import bfs_dist_sigma
    from repro.kernels.semiring import DIST_UNREACHED, MULT_SAT

    for i, s in enumerate(sources):
        od, osig = bfs_dist_sigma(g, int(s))
        if packed:
            od = np.where(np.isfinite(od), od, DIST_UNREACHED).astype(np.int16)
            osig = np.minimum(osig, MULT_SAT).astype(np.uint32)
        else:
            od, osig = od.astype(np.float32), osig.astype(np.float32)
        np.testing.assert_array_equal(dist[i], od, err_msg=f"dist row {s}")
        np.testing.assert_array_equal(mult[i], osig, err_msg=f"mult row {s}")


# -- one chip -----------------------------------------------------------------

def phase_main_analysis(g):
    import numpy as np

    from repro.core.analysis import AnalysisEngine

    def run():
        eng = AnalysisEngine(g, mesh=None)
        return eng, eng.report()

    eng, rep = timed("main analysis (report, default stages)", run)
    dist, mult = eng.distances(), eng.shortest_path_mult()
    src = seeded_sources(g.n)
    bfs_rows_equal(g, dist[src], mult[src], src)
    ref = np.bincount(dist[np.isfinite(dist)].astype(np.int64)).tolist()
    ref[0] = 0
    while len(ref) > 1 and ref[-1] == 0:
        ref.pop()
    check(rep["path_histogram"] == ref,
          f"histogram {rep['path_histogram']} != bincount {ref}")
    passed("main analysis",
           f"{g.name}: dist/mult bit-equal to bfs_dist_sigma on {len(src)} "
           f"seeded sources; histogram {ref} == np.bincount; diameter "
           f"{rep['diameter']}, max multiplicity {int(mult.max())}")
    return dist, mult


def phase_ecmp(g, dist, mult):
    import numpy as np

    from repro.core.routing.assign import ecmp_all_pairs_loads

    adj = g.adjacency_dense(np.float64)
    loads = timed("ECMP all-pairs loads (device)",
                  lambda: ecmp_all_pairs_loads(dist, mult, adj))
    host = ecmp_all_pairs_loads(dist, mult, adj, use_kernel=False)
    np.testing.assert_allclose(loads, host, rtol=TOL, atol=1e-9)
    passed("ECMP", f"device loads within {TOL} of the f64 host engine "
           f"(max rel err {max_rel_err(loads, host)}, peak load "
           f"{float(host.max())})")


def phase_traffic(g):
    import numpy as np

    from repro.core.traffic import evaluate_traffic_batch

    spec = "hotspot:zipf_a=1.4,samples=8,seed=0"
    got = timed("traffic batch (hotspot, 8 samples)",
                lambda: evaluate_traffic_batch(g, spec))
    want = evaluate_traffic_batch(g, spec, use_kernel=False)
    worst = 0.0
    for k, v in want.items():
        np.testing.assert_allclose(got[k], v, rtol=TOL, atol=1e-9,
                                   err_msg=k)
        worst = max(worst, max_rel_err(got[k], v))
    passed("traffic", f"{spec}: all {len(want)} metrics within {TOL} of the "
           f"f64 host engine (max rel err {worst})")


def phase_exactness():
    import numpy as np

    from repro.core import topology as T
    from repro.core.analysis import shortest_path_multiplicity

    g = T.make("hypercube", dim=10)
    dist, mult = timed("exactness probe (hypercube dim=10 wavefront)",
                       lambda: shortest_path_multiplicity(g))
    d_ref, m_ref = shortest_path_multiplicity(g, use_kernel=False)
    np.testing.assert_array_equal(dist, d_ref)
    np.testing.assert_array_equal(mult, m_ref)
    check(int(mult.max()) == 3628800, f"max multiplicity {mult.max()} != 10!")
    passed("exactness", f"{g.name}: dist/mult bit-equal to the f64 host "
           f"engine, max multiplicity {int(mult.max())} = 10! (bf16 holds "
           f"integers exactly only to 256)")


def phase_packed_tiled(g, dist, mult):
    import numpy as np

    from repro.core.analysis import shortest_path_multiplicity
    from repro.kernels.semiring import DIST_UNREACHED

    d, m = timed("packed tiled engine (tile_rows=512)",
                 lambda: shortest_path_multiplicity(g, tile_rows=512,
                                                    packed=True))
    check(d.dtype == np.int16 and m.dtype == np.uint32,
          f"packed engine returned {d.dtype}/{m.dtype}")
    np.testing.assert_array_equal(
        np.where(d == DIST_UNREACHED, np.inf, d.astype(np.float32)), dist)
    np.testing.assert_array_equal(m.astype(np.float32), mult)
    passed("packed/tiled", f"{g.name}: int16/uint32 cells bit-equal to the "
           f"f32 wavefront")


def phase_throughput():
    import numpy as np

    from repro.core import topology as T
    from repro.core.analysis import AnalysisEngine, apsp_from_lengths

    g = T.by_servers("slimfly", 2000)

    def run():
        return AnalysisEngine(g, throughput_demand="all-pairs",
                              throughput_eps=0.5,
                              throughput_rounds=8).throughput()

    res = timed("throughput (max concurrent flow, 8 rounds)", run)
    lo, hi = res["throughput"], res["upper_bound"]
    check(0.0 < lo <= hi, f"bounds not certified: lower {lo}, upper {hi}")
    rng = np.random.default_rng(SEED)
    lm = g.distance_seed()
    edge = np.isfinite(lm) & (lm > 0)
    lm[edge] = rng.uniform(0.25, 1.25, int(edge.sum())).astype(np.float32)
    got = timed("weighted APSP (min-plus squaring)",
                lambda: apsp_from_lengths(lm))
    want = apsp_from_lengths(lm, use_kernel=False)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    passed("throughput", f"{g.name}: certified lower {lo} <= upper {hi} "
           f"({res['rounds']} rounds); apsp_from_lengths within 1e-6 of its "
           f"oracle (max rel err {max_rel_err(got, want)})")


# -- four chips ---------------------------------------------------------------

def phase_sharded(g, mesh):
    import numpy as np

    from repro.core.analysis import sharded_dist_mult, wavefront_dist_mult
    from repro.core.routing.assign import ecmp_all_pairs_loads

    adj = g.adjacency_dense(np.float32)
    dist, mult = timed("row-sharded wavefront (4 chips)",
                       lambda: sharded_dist_mult(adj, mesh=mesh))
    d1, m1 = wavefront_dist_mult(adj)
    np.testing.assert_array_equal(dist, d1)
    np.testing.assert_array_equal(mult, m1)
    adj64 = adj.astype(np.float64)
    loads = timed("row-sharded ECMP loads (4 chips)",
                  lambda: ecmp_all_pairs_loads(dist, mult, adj64, mesh=mesh))
    l1 = ecmp_all_pairs_loads(d1, m1, adj64)
    np.testing.assert_allclose(loads, l1, rtol=TOL, atol=1e-9)
    passed("sharded", f"{g.name}: dist/mult bit-equal to the single-device "
           f"wavefront; ECMP within {TOL} (max rel err "
           f"{max_rel_err(loads, l1)})")


def phase_composed(mesh):
    import numpy as np

    from repro.core import topology as T
    from repro.core.analysis import composed_dist_mult_tiles

    g = T.make("jellyfish", n=32768, r=16, seed=SEED)
    src = seeded_sources(g.n)

    def run():
        tiles = list(composed_dist_mult_tiles(g, mesh, source_ids=src,
                                              packed=True))
        return (np.concatenate([t[2] for t in tiles]),
                np.concatenate([t[3] for t in tiles]))

    dist, mult = timed("composed packed engine (4 chips, 64 sources)", run)
    bfs_rows_equal(g, dist, mult, src, packed=True)
    passed("composed", f"{g.name}: int16/uint32 rows bit-equal to "
           f"bfs_dist_sigma on {len(src)} seeded sources")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        sys.exit(f"chip_smoke: no repro package at {SRC}")
    sys.path.insert(0, str(SRC))

    from repro.device import resolve_interpret, use_compile_cache

    cache = use_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"[device] jax {jax.__version__}, platform {dev.platform}, "
          f"device_kind {dev.device_kind}, count {len(devices)}; "
          f"compile cache {cache}", flush=True)
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, found {dev.platform}")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"devices, found {len(devices)}")
    check(resolve_interpret() is False, "kernels would run interpreted")
    print("[device] kernels run compiled (interpret=False; interpret=True "
          "raises on a TPU)", flush=True)

    from repro.core import topology as T

    g = T.by_servers("slimfly", 100_000)
    if args.chips == 4:
        from repro.core.analysis import device_mesh

        mesh = device_mesh(4)
        phase_sharded(g, mesh)
        phase_composed(mesh)
    else:
        dist, mult = phase_main_analysis(g)
        phase_ecmp(g, dist, mult)
        phase_traffic(g)
        phase_exactness()
        phase_packed_tiled(g, dist, mult)
        phase_throughput()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

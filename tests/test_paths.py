"""Path-multiplicity engine vs brute-force enumeration on known graphs."""
import numpy as np
import pytest

from repro.core import topology as T, workload as W
from repro.core.graph import Graph
from repro.core.analysis import (
    AnalysisEngine, analyze, apsp_dense, brute_force_path_counts,
    edge_interference, path_counts_with_slack, shortest_path_multiplicity,
)


def _ring(n):
    return Graph(n=n, edges=np.array([(i, (i + 1) % n) for i in range(n)]),
                 name=f"ring{n}")


def _complete(n):
    return Graph(n=n, edges=np.array(
        [(i, j) for i in range(n) for j in range(i + 1, n)]), name=f"K{n}")


def _two_paths():
    # disconnected: +inf distances between the halves
    return Graph(n=6, edges=np.array([(0, 1), (1, 2), (3, 4), (4, 5)]),
                 name="two-paths")


def _rings(copies, n):
    # disjoint n-rings: copies * n routers, +inf between the rings
    edges = [(c * n + i, c * n + (i + 1) % n)
             for c in range(copies) for i in range(n)]
    return Graph(n=copies * n, edges=np.array(edges),
                 name=f"{copies}xring{n}")


# 26xring5 has 130 routers and pads to 256: the device loops run over a
# second, mostly phantom tile
KNOWN = [_ring(8), _ring(5), _complete(5), T.make("torus", dims=(2, 3)),
         _two_paths(), _rings(26, 5)]


@pytest.mark.parametrize("g", KNOWN, ids=lambda g: g.name)
def test_multiplicity_matches_brute_force(g):
    bf = brute_force_path_counts(g)
    dist = apsp_dense(g, use_kernel=False)
    # counting-matmul path (dist supplied) and fused tropical-count path
    _, m_masked = shortest_path_multiplicity(g, dist, use_kernel=True)
    d_fused, m_fused = shortest_path_multiplicity(g, use_kernel=True)
    np.testing.assert_array_equal(m_masked, bf["multiplicity"])
    np.testing.assert_array_equal(m_fused, bf["multiplicity"])
    np.testing.assert_array_equal(d_fused, dist)


@pytest.mark.parametrize("g", KNOWN, ids=lambda g: g.name)
def test_slack_counts_match_brute_force(g):
    bf = brute_force_path_counts(g)
    dist = apsp_dense(g, use_kernel=False)
    pc = path_counts_with_slack(g, dist, use_kernel=True)
    oracle = path_counts_with_slack(g, dist, use_kernel=False)
    for key in ("multiplicity", "plus1", "plus2"):
        np.testing.assert_array_equal(pc[key], bf[key], err_msg=key)
        np.testing.assert_array_equal(pc[key], oracle[key], err_msg=key)
    assert pc["exact"] and oracle["exact"]


def test_slack_counts_flag_walks_past_f32():
    # K200: the +2 bounce walks reach ~4 * 199^3 > 2**24, so the float32
    # device path says its counts are not exact; the f64 oracle's are
    g = _complete(200)
    dist = apsp_dense(g, use_kernel=False)
    pc = path_counts_with_slack(g, dist, use_kernel=True)
    oracle = path_counts_with_slack(g, dist, use_kernel=False)
    assert not pc["exact"] and oracle["exact"]
    np.testing.assert_array_equal(pc["multiplicity"], oracle["multiplicity"])
    np.testing.assert_array_equal(pc["plus1"], oracle["plus1"])


def test_known_ring_counts():
    # C8: every pair < diameter has exactly 1 shortest path and 1 path of
    # slack +2 (the long way only once dist+2 >= n - dist); antipodal pairs
    # (dist 4) have 2 shortest paths.
    g = _ring(8)
    dist = apsp_dense(g, use_kernel=False)
    pc = path_counts_with_slack(g, dist)
    assert pc["multiplicity"][0, 4] == 2          # antipodal: both ways round
    assert pc["multiplicity"][0, 1] == 1
    assert pc["plus1"][0, 3] == 0                 # parity: no length-4 walk 0->3
    assert pc["plus2"][0, 3] == 1                 # the long way round (length 5)


def test_known_complete_graph_counts():
    # K5: adjacent pairs (d=1): 1 shortest, 3 two-hop, 6 three-hop simple paths
    g = _complete(5)
    dist = apsp_dense(g, use_kernel=False)
    pc = path_counts_with_slack(g, dist)
    off = ~np.eye(5, dtype=bool)
    assert (pc["multiplicity"][off] == 1).all()
    assert (pc["plus1"][off] == 3).all()
    assert (pc["plus2"][off] == 6).all()


def test_slimfly_multiplicity_exact():
    # acceptance case: Slim Fly instance, kernel path vs brute force
    g = T.make("slimfly", q=5)
    dist = apsp_dense(g)
    bf = brute_force_path_counts(g)
    pc = path_counts_with_slack(g, dist, use_kernel=True)
    np.testing.assert_array_equal(pc["multiplicity"], bf["multiplicity"])
    np.testing.assert_array_equal(pc["plus1"], bf["plus1"])
    np.testing.assert_array_equal(pc["plus2"], bf["plus2"])


def test_disconnected_pairs_count_zero():
    g = _two_paths()
    dist = apsp_dense(g, use_kernel=False)
    d, m = shortest_path_multiplicity(g, use_kernel=False)
    assert not np.isfinite(d[0, 3]) and m[0, 3] == 0
    pc = path_counts_with_slack(g, dist, use_kernel=False)
    assert pc["multiplicity"][0, 3] == 0
    assert pc["plus1"][0, 3] == 0 and pc["plus2"][0, 3] == 0


def test_edge_interference_bounds_and_determinism():
    g = T.make("slimfly", q=5)
    dist = apsp_dense(g)
    _, mult = shortest_path_multiplicity(g, dist)
    a = edge_interference(g, dist, mult, pairs=32, seed=3)
    b = edge_interference(g, dist, mult, pairs=32, seed=3)
    assert a == b
    assert 0.0 <= a["edge_interference_mean"] <= a["edge_interference_max"] <= 1.0
    assert a["support_links_mean"] >= 1.0
    # odd pair counts round down to demand pairs instead of crashing
    c = edge_interference(g, dist, mult, pairs=33, seed=3)
    assert 0.0 <= c["edge_interference_mean"] <= 1.0
    with pytest.raises(ValueError):
        edge_interference(g, dist, mult, pairs=1)


def test_analyze_edgeless_graph_degrades_gracefully():
    g = Graph(n=4, edges=np.empty((0, 2)), name="isolated")
    rep = analyze(g, spectral=False)
    assert rep["diameter"] == 0 and "path_multiplicity_mean" not in rep
    dist = apsp_dense(g, use_kernel=False)
    _, mult = shortest_path_multiplicity(g, dist, use_kernel=False)
    ei = edge_interference(g, dist, mult, pairs=8)  # must not hang
    assert ei["edge_interference_mean"] == 0.0


def test_engine_report_independent_of_cache_history():
    g = T.make("slimfly", q=5)
    fresh = AnalysisEngine(g).report(["distances", "diversity"])
    warm = AnalysisEngine(g)
    warm.multiplicities()  # populate the cache first
    assert fresh == warm.report(["distances", "diversity"])
    assert "edge_interference_mean" not in fresh


def test_analysis_engine_stages_share_apsp():
    g = T.make("slimfly", q=5)
    eng = AnalysisEngine(g)
    d1 = eng.distances()
    rep = eng.report()
    assert eng.distances() is d1  # cached, not recomputed
    for key in ("diameter", "path_multiplicity_mean", "nonminimal_plus1_mean",
                "nonminimal_plus2_mean", "edge_interference_mean",
                "path_diversity_mean", "path_histogram"):
        assert key in rep, key


def test_analyze_reports_multiplicity_metrics():
    g = T.make("slimfly", q=5)
    rep = analyze(g)
    bf = brute_force_path_counts(g)
    off = ~np.eye(g.n, dtype=bool)
    assert rep["path_multiplicity_mean"] == pytest.approx(
        bf["multiplicity"][off].mean())
    assert rep["nonminimal_plus1_mean"] == pytest.approx(bf["plus1"][off].mean())
    assert rep["nonminimal_plus2_mean"] == pytest.approx(bf["plus2"][off].mean())
    # legacy keys survive the engine refactor
    for key in ("diameter", "avg_path_length", "path_histogram", "exact",
                "path_diversity_mean"):
        assert key in rep


def test_analyze_engine_unknown_stage_raises():
    with pytest.raises(ValueError):
        AnalysisEngine(T.make("slimfly", q=5)).report(["nope"])


def test_expected_link_loads_conserve_hops():
    g = T.make("slimfly", q=5)
    dist = apsp_dense(g)
    _, mult = shortest_path_multiplicity(g, dist)
    wl = W.make_traffic(g, "uniform", flows=128, seed=4)
    loads = W.expected_link_loads(g, wl, dist, mult)
    # expected total link crossings == total shortest-path hops of the demand
    hops = sum(dist[int(s), int(t)] for s, t in wl.pairs)
    assert loads.sum() == pytest.approx(hops)
    rep = W.evaluate_workload(g, wl, dist=dist, mult=mult)
    assert "expected_load_imbalance" in rep and rep["max_expected_link_load"] > 0

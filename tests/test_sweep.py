"""Sweep driver tests: batched kernels vs 2D slices, stacked-stage results
vs the per-graph engine, ECMP all-pairs loads vs the general assignment
engine, and the equal-cost comparison table contract."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro import kernels
from repro.core import sweep as S
from repro.core import topology as T
from repro.core.analysis import AnalysisEngine, apsp_dense
from repro.core.analysis.paths import shortest_path_multiplicity
from repro.core.analysis.wavefront import batched_count, batched_dist_mult
from repro.core.routing import assign


# -- batched kernels ----------------------------------------------------------

def _rand_dist(rng, shape):
    x = rng.integers(0, 6, shape).astype(np.float32)
    x[rng.random(shape) < 0.25] = np.inf
    return x


def test_batched_minplus_matches_2d_slices():
    rng = np.random.default_rng(0)
    a = _rand_dist(rng, (3, 160, 160))
    b = _rand_dist(rng, (3, 160, 160))
    out = np.asarray(kernels.ops.batched_minplus_matmul(
        jnp.asarray(a), jnp.asarray(b), bm=128, bn=128, bk=128))
    for i in range(3):
        want = np.asarray(kernels.ops.minplus_matmul(
            jnp.asarray(a[i]), jnp.asarray(b[i])))
        np.testing.assert_array_equal(out[i], want)


def test_batched_count_matches_reference():
    rng = np.random.default_rng(1)
    a = rng.integers(0, 9, (2, 200, 200)).astype(np.float32)
    b = rng.integers(0, 9, (2, 200, 200)).astype(np.float32)
    out = np.asarray(kernels.ops.batched_count_matmul(
        jnp.asarray(a), jnp.asarray(b), bm=128, bn=128, bk=128))
    want = np.asarray(kernels.ops.batched_count_matmul_ref(
        jnp.asarray(a), jnp.asarray(b)))
    np.testing.assert_allclose(out, want, rtol=1e-6)


# -- stacked stages vs per-graph engine ---------------------------------------

def _small_mixed_graphs():
    return [T.make("slimfly", q=5), T.make("torus", dims=(4, 5)),
            T.make("polarfly", q=5), T.make("megafly", m=2)]


def test_batched_apsp_matches_dense_apsp():
    graphs = _small_mixed_graphs()
    dist = S.batched_apsp(graphs, use_kernel=False)
    for i, g in enumerate(graphs):
        want = apsp_dense(g, use_kernel=False)
        np.testing.assert_array_equal(dist[i, :g.n, :g.n], want)
        # padding stays inert: phantom routers never reach anyone
        assert np.isinf(dist[i, :g.n, g.n:]).all() or dist.shape[1] == g.n


def test_batched_dist_mult_matches_engine():
    graphs = _small_mixed_graphs()
    _, adj = S._stack_seeds(graphs)
    dist, mult = batched_dist_mult(adj, use_kernel=False)
    for i, g in enumerate(graphs):
        want_d, want_m = shortest_path_multiplicity(g, use_kernel=False)
        np.testing.assert_array_equal(dist[i, :g.n, :g.n], want_d)
        np.testing.assert_allclose(mult[i, :g.n, :g.n], want_m)
        # padding stays inert: phantom routers never get reached
        if dist.shape[1] > g.n:
            assert np.isinf(dist[i, :g.n, g.n:]).all()


def test_ecmp_all_pairs_matches_general_engine():
    for g in _small_mixed_graphs():
        dist, mult = shortest_path_multiplicity(g, use_kernel=False)
        adj = g.adjacency_dense(np.float64)
        demand = (np.isfinite(dist) & ~np.eye(g.n, dtype=bool)).astype(float)
        want = assign.ecmp_link_loads(g, dist, mult, demand,
                                      use_kernel=False, directed=True)
        got = assign.ecmp_all_pairs_loads(dist, mult, adj, use_kernel=False)
        np.testing.assert_allclose(got, want, rtol=1e-9, atol=1e-9)
        # flow conservation: total directed load == sum of pair distances
        np.testing.assert_allclose(got.sum(), dist[np.isfinite(dist)].sum())


def test_ecmp_all_pairs_batched_product():
    """The stacked path (batched product over the leading axis) must agree
    with running the accumulation per graph."""
    graphs = _small_mixed_graphs()
    _, adj = S._stack_seeds(graphs)
    dist, mult = batched_dist_mult(adj, use_kernel=False)
    loads = assign.ecmp_all_pairs_loads(dist, mult,
                                        adj.astype(np.float64),
                                        product=batched_count(False))
    for i, g in enumerate(graphs):
        want = assign.ecmp_all_pairs_loads(
            dist[i, :g.n, :g.n], mult[i, :g.n, :g.n],
            g.adjacency_dense(np.float64), use_kernel=False)
        np.testing.assert_allclose(loads[i, :g.n, :g.n], want, rtol=1e-9)


# -- the comparison driver ----------------------------------------------------

def test_sweep_rows_contract():
    graphs = _small_mixed_graphs()
    result = S.sweep(graphs=graphs, use_kernel=False, budget=0.0)
    rows = result["rows"]
    assert len(rows) == len(graphs)
    for row, g in zip(rows, graphs):
        assert row["routers"] == g.n
        for key in ("diameter", "avg_spl", "mult_mean", "tput_lb",
                    "cost", "power_kw"):
            assert row[key] is not None, (row["family"], key)
        assert 0 < row["tput_lb"] <= 1.0
        assert row["diameter"] >= 1 and row["avg_spl"] >= 1.0
        assert row["mult_mean"] >= 1.0
    # per-graph engine agrees with the batched rows
    eng = AnalysisEngine(graphs[0], use_kernel=False)
    cmp_row = eng.comparison()
    np.testing.assert_allclose(rows[0]["tput_lb"],
                               cmp_row["ecmp_saturation_throughput"])
    np.testing.assert_allclose(rows[0]["mult_mean"],
                               cmp_row["path_multiplicity_mean"])


def test_equal_cost_graphs_respects_budget_and_cap():
    graphs, budget = S.equal_cost_graphs(
        ["slimfly", "torus", "dragonfly"], ref=("slimfly", 500),
        max_routers=150)
    from repro.core import costmodel as C

    assert len(graphs) == 3
    for g in graphs:
        assert g.n <= 150
        assert C.cost_report(g.spec)["cost_total"] <= budget


def test_check_families_clean():
    assert S.check_families(n_servers=120) == []


def test_format_table_covers_all_rows():
    graphs = _small_mixed_graphs()
    result = S.sweep(graphs=graphs, use_kernel=False, budget=1.0)
    table = S.format_table(result)
    for g in graphs:
        assert g.spec.family in table
    assert "tput-lb" in table and "power-kW" in table


@pytest.mark.slow
def test_sweep_kernel_path_matches_oracle():
    graphs = _small_mixed_graphs()
    r_kernel = S.sweep(graphs=graphs, use_kernel=True, budget=0.0)
    r_oracle = S.sweep(graphs=graphs, use_kernel=False, budget=0.0)
    for a, b in zip(r_kernel["rows"], r_oracle["rows"]):
        for key in ("diameter", "avg_spl", "mult_mean", "tput_lb"):
            np.testing.assert_allclose(a[key], b[key], rtol=1e-5)

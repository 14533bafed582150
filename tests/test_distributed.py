"""Sharded + tiled extreme-scale engines vs the single-device wavefront.

The headline contract is *bit*-equality of dist/mult: distances and
multiplicities are integer-valued f32, so neither row-sharding the M
dimension over a mesh nor splitting the K reduction into panels may change
a single bit. Multi-device cases skip unless enough devices are visible —
the CI `sharded` job runs this file under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` to cover P in
{2, 8}; P=1 (mesh == None, 1-device mesh, tiled modes) runs everywhere.

The 16k out-of-core memory-budget test is `slow` (soak job): it drives the
module CLI in a subprocess so the measured peak RSS is the tiled engine's,
not the test session's.
"""
import json
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.extend.core as jex_core
import jax.numpy as jnp

from repro.core import topology as T
from repro.core import sweep as S
from repro.core.analysis import apsp_dense
from repro.core.analysis import distributed as D
from repro.core.analysis import wavefront as WF
from repro.core.analysis.paths import shortest_path_multiplicity
from repro.core.graph import Graph
from repro.core.routing.assign import ecmp_all_pairs_loads

needs2 = pytest.mark.skipif(jax.device_count() < 2,
                            reason="needs >= 2 devices (XLA_FLAGS fake-device "
                                   "recipe in the README)")
needs8 = pytest.mark.skipif(jax.device_count() < 8,
                            reason="needs >= 8 devices")


def _shard_counts():
    return [p for p in (2, 8) if p <= jax.device_count()]


# -- sharded engine: bit-equality across all 12 families -----------------------

@pytest.mark.parametrize("fam", T.families())
@needs2
def test_sharded_bit_equal_all_families(fam):
    g = T.by_servers(fam, 120)
    adj = g.adjacency_dense(np.float32)
    want_d, want_m = WF.wavefront_dist_mult(adj)
    for p in _shard_counts():
        got_d, got_m = D.sharded_dist_mult(adj, D.device_mesh(p))
        np.testing.assert_array_equal(want_d, got_d)
        np.testing.assert_array_equal(want_m, got_m)


def test_one_shard_mesh_is_the_unsharded_path():
    # a 1-device mesh through the real shard_map engine must equal the
    # unsharded device engine bitwise; the wrapper additionally just
    # delegates (device_mesh(1) is None by design)
    from jax.sharding import Mesh

    assert D.device_mesh(1) is None
    g = T.make("slimfly", q=5)
    adj = g.adjacency_dense(np.float32)
    want_d, want_m = WF.wavefront_dist_mult(adj)
    mesh1 = Mesh(np.array(jax.devices()[:1]), (D.ROW_AXIS,))
    p, _, block = D.pad_block_sharded(g.n, 1)
    dist, mult = D.dist_mult_sharded(
        jnp.asarray(WF.pad_operand(adj, p, 0.0)), mesh1, block=block)
    np.testing.assert_array_equal(want_d, np.asarray(dist)[:g.n, :g.n])
    np.testing.assert_array_equal(want_m, np.asarray(mult)[:g.n, :g.n])
    # and the host wrapper delegates for any single-shard mesh
    got_d, got_m = D.sharded_dist_mult(adj, mesh1)
    np.testing.assert_array_equal(want_d, got_d)
    np.testing.assert_array_equal(want_m, got_m)


@needs2
def test_sharded_indivisible_router_count():
    # N deliberately not divisible by any shard count (nor by 128)
    g = T.make("jellyfish", n=137, r=5, seed=3)
    adj = g.adjacency_dense(np.float32)
    want_d, want_m = WF.wavefront_dist_mult(adj)
    for p in _shard_counts():
        pp, _, block = D.pad_block_sharded(g.n, p)
        assert pp % (p * 128) == 0 and pp >= g.n
        got_d, got_m = D.sharded_dist_mult(adj, D.device_mesh(p))
        np.testing.assert_array_equal(want_d, got_d)
        np.testing.assert_array_equal(want_m, got_m)


@needs2
def test_sharded_disconnected_and_edgeless():
    g = Graph(n=6, edges=np.array([(0, 1), (1, 2), (3, 4), (4, 5)]))
    mesh = D.device_mesh(2)
    dist, mult = D.sharded_dist_mult(g.adjacency_dense(np.float32), mesh)
    assert np.isinf(dist[0, 3]) and mult[0, 3] == 0
    assert dist[0, 2] == 2 and mult[0, 2] == 1
    g2 = Graph(n=4, edges=np.empty((0, 2)))
    dist2, mult2 = D.sharded_dist_mult(g2.adjacency_dense(np.float32), mesh)
    off = ~np.eye(4, dtype=bool)
    assert np.isinf(dist2[off]).all() and (mult2[off] == 0).all()
    assert (np.diag(dist2) == 0).all() and (np.diag(mult2) == 1).all()


@needs2
def test_sharded_batched_stack_matches_per_graph():
    graphs = [T.make("slimfly", q=5), T.make("torus", dims=(4, 5)),
              T.make("hypercube", dim=5)]
    k = 128
    stack = np.zeros((len(graphs), k, k), np.float32)
    for i, g in enumerate(graphs):
        stack[i, :g.n, :g.n] = g.adjacency_dense(np.float32)
    want_d, want_m = WF.wavefront_dist_mult(stack)
    for p in _shard_counts():
        got_d, got_m = D.sharded_dist_mult(stack, D.device_mesh(p))
        np.testing.assert_array_equal(want_d, got_d)
        np.testing.assert_array_equal(want_m, got_m)


@needs2
def test_sharded_ecmp_loads_match_device_engine():
    g = T.make("jellyfish", n=96, r=6, seed=1)
    dist, mult = WF.wavefront_dist_mult(g.adjacency_dense(np.float32))
    adj = g.adjacency_dense(np.float64)
    want = ecmp_all_pairs_loads(dist, mult, adj)  # single-device engine
    for p in _shard_counts():
        got = ecmp_all_pairs_loads(dist, mult, adj, mesh=D.device_mesh(p))
        # shard-local partials sum in a different order: f32-close, not
        # bitwise — and the saturation bound (the consumed scalar) agrees
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        assert abs(got.max() - want.max()) <= 1e-5 * max(1.0, want.max())


@needs2
def test_sweep_sharded_matches_single_device_rows():
    graphs = [T.make("slimfly", q=5), T.make("jellyfish", n=60, r=4, seed=1)]
    auto = S.sweep(graphs=graphs, budget=0.0)           # picks up the mesh
    single = S.sweep(graphs=graphs, budget=0.0, mesh=None)
    for a, b in zip(auto["rows"], single["rows"]):
        assert a["diameter"] == b["diameter"]
        assert a["mult_mean"] == b["mult_mean"]  # bit-equal mult -> equal mean
        assert a["tput_lb"] == pytest.approx(b["tput_lb"], rel=1e-5)


@needs2
def test_engine_auto_mesh_matches_pinned_single_device():
    from repro.core.analysis.metrics import AnalysisEngine

    g = T.make("jellyfish", n=200, r=6, seed=0)
    e_auto = AnalysisEngine(g)            # mesh="auto" picks up the devices
    e_one = AnalysisEngine(g, mesh=None)  # pinned single-device engine
    assert D.default_mesh(g.n) is not None
    np.testing.assert_array_equal(e_auto.distances(), e_one.distances())
    np.testing.assert_array_equal(e_auto.shortest_path_mult(),
                                  e_one.shortest_path_mult())


@needs2
def test_sharded_level_loop_stays_device_resident():
    # the shard_map'd level loop lowers to a `while` with psum inside and
    # no host callbacks — the sharded mirror of the wavefront regression
    g = T.make("slimfly", q=5)
    mesh = D.device_mesh(2)
    p, row, col = D.pad_block_sharded(g.n, 2)
    padded = WF.pad_operand(g.adjacency_dense(np.float32), p, 0.0)
    fn = D._dist_mult_sharded_fn(mesh, False, row, col, True)
    jaxpr = jax.make_jaxpr(fn)(jnp.asarray(padded))
    prims = set()
    _collect(jaxpr.jaxpr, prims)
    assert "while" in prims, sorted(prims)
    leaks = [q for q in prims if "callback" in q or q == "infeed"]
    assert not leaks, leaks


def _collect(jaxpr, prims):
    for eqn in jaxpr.eqns:
        prims.add(eqn.primitive.name)
        for val in eqn.params.values():
            for sub in _subjaxprs(val):
                _collect(sub, prims)


def _subjaxprs(val):
    if isinstance(val, jex_core.ClosedJaxpr):
        yield val.jaxpr
    elif isinstance(val, jex_core.Jaxpr):
        yield val
    elif isinstance(val, (list, tuple)):
        for item in val:
            yield from _subjaxprs(item)


# -- tiled out-of-core engine --------------------------------------------------

def test_tiled_bit_equal_resident_and_streaming():
    g = T.make("jellyfish", n=100, r=5, seed=0)
    want_d, want_m = WF.wavefront_dist_mult(g.adjacency_dense(np.float32))
    # resident adjacency (fits the budget), tile_rows not dividing n
    d1, m1 = D.tiled_dist_mult(g, tile_rows=33)
    np.testing.assert_array_equal(want_d, d1)
    np.testing.assert_array_equal(want_m, m1)
    # streamed CSR panels (budget of 1 byte forces the pump)
    d2, m2 = D.tiled_dist_mult(g, tile_rows=48, adjacency_budget=1)
    np.testing.assert_array_equal(want_d, d2)
    np.testing.assert_array_equal(want_m, m2)
    # dense-array source goes through the same pump
    d3, m3 = D.tiled_dist_mult(g.adjacency_dense(np.float32), tile_rows=48,
                               adjacency_budget=1)
    np.testing.assert_array_equal(want_d, d3)


def test_tiled_multi_panel_streaming():
    # >= 3 K-panels per level (128-wide panels over 384 padded columns):
    # guards the staging-buffer reuse against the zero-copy upload hazard
    # (the pump must pin each panel's bytes before refilling the buffer)
    g = T.make("jellyfish", n=300, r=8, seed=0)
    want_d, want_m = WF.wavefront_dist_mult(g.adjacency_dense(np.float32))
    d, m = D.tiled_dist_mult(g, tile_rows=100, adjacency_budget=1,
                             panel_rows=128)
    np.testing.assert_array_equal(want_d, d)
    np.testing.assert_array_equal(want_m, m)


def test_tiled_non_power_of_two_panels_and_big_tile():
    # panel_rows that is a 128-multiple but not a power of two (640 | 1280):
    # the panel product's K block must divide panel_rows, not just the
    # padded width — and a >512-row tile must keep a >=128 row block
    g = T.make("jellyfish", n=1220, r=10, seed=0)
    want_d, want_m = WF.wavefront_dist_mult(g.adjacency_dense(np.float32))
    d, m = D.tiled_dist_mult(g, tile_rows=1220, adjacency_budget=1,
                             panel_rows=640)
    np.testing.assert_array_equal(want_d, d)
    np.testing.assert_array_equal(want_m, m)


def test_widest_divisor_block_any_multiplier():
    # 100k-class padded extents rarely have big power-of-two factors —
    # the sweep's block picker must use ANY 128-multiple divisor
    assert D.widest_divisor_block(104960, 16384) == 10496   # 128 x 82
    assert D.widest_divisor_block(131072, 16384) == 16384   # power of two
    assert D.widest_divisor_block(640, 16384) == 640
    assert D.widest_divisor_block(128, 16384) == 128
    assert D.widest_divisor_block(104960, 128) == 128
    for size in (640, 104960, 99840):
        b = D.widest_divisor_block(size, 16384)
        assert size % b == 0 and b % 128 == 0 and b <= 16384


def test_tiled_explicit_non_power_of_two_block():
    # explicit block= that is a 128-multiple but not a power of two
    # (384 = 3 x 128 divides pc = 768): bit-equal to the default grid
    g = T.make("jellyfish", n=700, r=10, seed=2)
    want_d, want_m = WF.wavefront_dist_mult(g.adjacency_dense(np.float32))
    from repro.kernels.semiring import DIST_UNREACHED

    for packed in (False, True):
        d, m = D.tiled_dist_mult(g, tile_rows=700, packed=packed, block=384)
        if packed:
            d = np.where(d == DIST_UNREACHED, np.inf, d.astype(np.float32))
            m = m.astype(np.float32)
        np.testing.assert_array_equal(want_d, d)
        np.testing.assert_array_equal(want_m, m)


def test_apsp_dense_rejects_conflicting_tiled_knobs():
    g = T.make("slimfly", q=5)
    with pytest.raises(ValueError, match="tile_rows"):
        apsp_dense(g, method="squaring", tile_rows=8)
    with pytest.raises(ValueError, match="tile_rows"):
        apsp_dense(g, use_kernel=False, tile_rows=8)


def test_tiled_tile_rows_smaller_than_one_block():
    # tile_rows far below the 128-row kernel block: rows pad to the f32
    # sublane tile and the row block shrinks to fit
    g = T.make("slimfly", q=5)
    want_d, want_m = WF.wavefront_dist_mult(g.adjacency_dense(np.float32))
    d, m = D.tiled_dist_mult(g, tile_rows=5, adjacency_budget=1)
    np.testing.assert_array_equal(want_d, d)
    np.testing.assert_array_equal(want_m, m)


def test_tiled_source_subrange_and_summary():
    g = T.make("jellyfish", n=96, r=6, seed=1)
    want_d, want_m = WF.wavefront_dist_mult(g.adjacency_dense(np.float32))
    tiles = list(D.tiled_dist_mult_tiles(g, tile_rows=16, sources=(32, 64)))
    assert [(r0, r1) for r0, r1, _, _ in tiles] == [(32, 48), (48, 64)]
    for r0, r1, d, m in tiles:
        np.testing.assert_array_equal(want_d[r0:r1], d)
        np.testing.assert_array_equal(want_m[r0:r1], m)
    s = D.tiled_summary(g, tile_rows=32)
    off = np.isfinite(want_d) & (want_d > 0)
    assert s["diameter"] == int(want_d[off].max())
    assert s["avg_spl"] == pytest.approx(float(want_d[off].mean()))
    assert s["mult_mean"] == pytest.approx(float(want_m[off].mean()))
    assert s["reached_pairs"] == int(off.sum())
    assert s["peak_rss_mb"] > 0 and s["single_buffer_mb"] > 0


def test_tiled_disconnected_graph():
    g = Graph(n=6, edges=np.array([(0, 1), (1, 2), (3, 4), (4, 5)]))
    want_d, want_m = WF.wavefront_dist_mult(g.adjacency_dense(np.float32))
    d, m = D.tiled_dist_mult(g, tile_rows=4, adjacency_budget=1)
    np.testing.assert_array_equal(want_d, d)
    np.testing.assert_array_equal(want_m, m)


def test_callsite_knobs_route_to_tiled_engine():
    g = T.make("slimfly", q=5)
    want_d, want_m = WF.wavefront_dist_mult(g.adjacency_dense(np.float32))
    np.testing.assert_array_equal(want_d, apsp_dense(g, tile_rows=16))
    d, m = shortest_path_multiplicity(g, tile_rows=16)
    np.testing.assert_array_equal(want_d, d)
    np.testing.assert_array_equal(want_m, m)


def test_bfs_sigma_oracle_matches_wavefront():
    g = T.make("torus", dims=(4, 5))
    want_d, want_m = WF.wavefront_dist_mult(g.adjacency_dense(np.float32))
    for s in (0, 7, g.n - 1):
        od, osig = D.bfs_dist_sigma(g, s)
        np.testing.assert_array_equal(want_d[s], od.astype(np.float32))
        np.testing.assert_array_equal(want_m[s], osig.astype(np.float32))


def test_shard_count_and_padding_helpers():
    assert D.best_shard_count(1000, max_shards=8) == 8
    assert D.best_shard_count(100, max_shards=8) == 1   # one 128-row tile
    assert D.best_shard_count(300, max_shards=8) == 3
    p, row, col = D.pad_block_sharded(1000, 8)
    assert p % (8 * 128) == 0 and p % col == 0 and (p // 8) % row == 0


# -- composed engine: sharded adjacency x streamed source tiles ----------------

def _assemble(tiles, k, n, ddt, mdt):
    d = np.empty((k, n), ddt)
    m = np.empty((k, n), mdt)
    at = 0
    for r0, r1, dt, mt in tiles:
        assert (r0, r1) == (at, at + dt.shape[0])
        d[r0:r1], m[r0:r1] = dt, mt
        at = r1
    assert at == k
    return d, m


@pytest.mark.parametrize("packed", [False, True])
@needs2
def test_composed_bit_equal_to_tiled(packed):
    g = T.make("jellyfish", n=137, r=5, seed=3)
    want = _assemble(D.tiled_dist_mult_tiles(g, tile_rows=48, packed=packed),
                     g.n, g.n, *(("int16", "uint32") if packed
                                 else ("float32", "float32")))
    for p in _shard_counts():
        got = _assemble(
            D.composed_dist_mult_tiles(g, D.device_mesh(p), tile_rows=48,
                                       packed=packed),
            g.n, g.n, want[0].dtype, want[1].dtype)
        np.testing.assert_array_equal(want[0], got[0])
        np.testing.assert_array_equal(want[1], got[1])


@needs2
def test_composed_source_ids_sampled_rows():
    g = T.make("slimfly", q=13)
    want_d, want_m = D.tiled_dist_mult(g)
    ids = [1, 9, 33, 34, 80]
    tiles = list(D.composed_dist_mult_tiles(g, D.device_mesh(2), tile_rows=3,
                                            source_ids=ids))
    assert [(r0, r1) for r0, r1, _, _ in tiles] == [(0, 3), (3, 5)]
    rows = np.concatenate([d for _, _, d, _ in tiles])
    mrows = np.concatenate([m for _, _, _, m in tiles])
    np.testing.assert_array_equal(want_d[ids], rows)
    np.testing.assert_array_equal(want_m[ids], mrows)


@needs2
def test_composed_budget_bounds_the_per_device_panel():
    g = T.make("slimfly", q=5)
    mesh = D.device_mesh(2)
    with pytest.raises(ValueError, match="per-device adjacency panel"):
        next(D.composed_dist_mult_tiles(g, mesh, adjacency_budget=1))
    # packed panels are 4x smaller: a budget that rejects f32 can admit
    # uint8 — the error message's own escape hatch
    kp_p = (D._pad128(g.n) + (-D._pad128(g.n)) % (2 * 128)) ** 2 // 2
    d, m = _assemble(
        D.composed_dist_mult_tiles(g, mesh, adjacency_budget=kp_p,
                                   packed=True),
        g.n, g.n, "int16", "uint32")
    want_d, want_m = D.tiled_dist_mult(g, packed=True)
    np.testing.assert_array_equal(want_d, d)
    np.testing.assert_array_equal(want_m, m)


def test_composed_single_shard_mesh_delegates_to_tiled():
    g = T.make("slimfly", q=5)
    want_d, want_m = D.tiled_dist_mult(g, tile_rows=16)
    d, m = _assemble(D.composed_dist_mult_tiles(g, None, tile_rows=16),
                     g.n, g.n, "float32", "float32")
    np.testing.assert_array_equal(want_d, d)
    np.testing.assert_array_equal(want_m, m)


@needs2
def test_mesh_and_tile_rows_compose_through_apsp_dense():
    g = T.make("jellyfish", n=100, r=5, seed=0)
    want = apsp_dense(g)
    got = apsp_dense(g, mesh=D.device_mesh(2), tile_rows=32)
    np.testing.assert_array_equal(want, got)
    d, m = shortest_path_multiplicity(g, mesh=D.device_mesh(2), tile_rows=32)
    want_d, want_m = shortest_path_multiplicity(g)
    np.testing.assert_array_equal(want_d, d)
    np.testing.assert_array_equal(want_m, m)


# -- the extreme-scale memory-budget gate (slow soak) --------------------------

@pytest.mark.slow
def test_16k_tiled_out_of_core_under_memory_budget():
    """Exact dist+mult rows at 16384 routers through the streaming pump, in
    a subprocess (so peak RSS is the engine's): the measured peak must stay
    under a budget the single-buffer device path cannot meet. Tiles are
    independent, so a row subrange certifies the whole run's peak."""
    budget_mb = 4096.0
    out = subprocess.run(
        [sys.executable, "-m", "repro.core.analysis.distributed",
         "--routers", "16384", "--degree", "16", "--tile-rows", "256",
         "--sources", "256", "--check", "2"],
        capture_output=True, text=True, timeout=3600, check=True,
        env={**__import__("os").environ, "PYTHONPATH": "src"},
        cwd=str(__import__("pathlib").Path(__file__).resolve().parents[1]))
    assert "oracle spot-check OK" in out.stdout, out.stdout + out.stderr
    summary = json.loads(out.stdout[out.stdout.index("{"):])
    assert summary["routers"] == 16384
    assert summary["rows_analyzed"] == 256
    assert summary["adjacency_streamed"] is True
    assert summary["diameter"] >= 3 and summary["mult_mean"] >= 1.0
    # the logged memory-budget evidence: tiled peak fits where the
    # single-buffer wavefront (6 padded N^2 f32 buffers) cannot
    assert summary["single_buffer_mb"] > budget_mb, summary
    assert summary["peak_rss_mb"] < budget_mb, summary
    print(f"[16k] peak_rss={summary['peak_rss_mb']}MB "
          f"single_buffer={summary['single_buffer_mb']}MB "
          f"elapsed={summary['elapsed_s']}s")


@pytest.mark.slow
def test_32k_composed_packed_under_memory_budget():
    """Composed engine soak: 32768 routers, 8 (fake) shards, packed cells.
    The f32 sharded adjacency alone would be 4 GiB; uint8 panels fit the
    whole run (panels + packed dist/mult tiles + host CSR + the 8 faked
    XLA host devices' overhead) under 6 GiB.
    The subprocess forces 8 host devices itself, so this runs in the soak
    job (which sets no XLA_FLAGS); the oracle spot-check transitively
    certifies equality with the tiled and wavefront engines."""
    budget_mb = 6144.0
    env = {**__import__("os").environ, "PYTHONPATH": "src",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8"}
    out = subprocess.run(
        [sys.executable, "-m", "repro.core.analysis.distributed",
         "--routers", "32768", "--degree", "16", "--tile-rows", "128",
         "--sources", "64", "--packed", "--shards", "8", "--check", "2"],
        capture_output=True, text=True, timeout=5400, check=True, env=env,
        cwd=str(__import__("pathlib").Path(__file__).resolve().parents[1]))
    assert "oracle spot-check OK" in out.stdout, out.stdout + out.stderr
    summary = json.loads(out.stdout[out.stdout.index("{"):])
    assert summary["routers"] == 32768
    assert summary["rows_analyzed"] == 64
    assert summary["shards"] == 8
    assert summary["packed"] is True
    # jellyfish r=16 keeps sigma well below 2**24 — clean, not clamped
    assert summary["saturated"] is False
    assert summary["diameter"] >= 3 and summary["mult_mean"] >= 1.0
    assert summary["single_buffer_mb"] > budget_mb, summary
    assert summary["peak_rss_mb"] < budget_mb, summary
    print(f"[32k composed] peak_rss={summary['peak_rss_mb']}MB "
          f"single_buffer={summary['single_buffer_mb']}MB "
          f"elapsed={summary['elapsed_s']}s")


# -- observability: sharded telemetry + jaxpr identity ------------------------

@needs2
def test_sharded_disabled_jaxpr_is_bit_identical():
    """Enabling the tracer must leave the telemetry=False shard_map jaxpr
    byte-equal, and the telemetry=True variant (psum'd per-level counts as
    replicated aux outputs) must still be one `while` with no callbacks."""
    from repro import obs

    g = T.make("slimfly", q=5)
    mesh = D.device_mesh(2)
    p, row, col = D.pad_block_sharded(g.n, 2)
    x = jnp.asarray(WF.pad_operand(g.adjacency_dense(np.float32), p, 0.0))
    base = str(jax.make_jaxpr(
        D._dist_mult_sharded_fn(mesh, False, row, col, True))(x))
    obs.enable()
    try:
        again = str(jax.make_jaxpr(
            D._dist_mult_sharded_fn(mesh, False, row, col, True))(x))
    finally:
        obs.disable()
        obs.reset()
    assert again == base
    jaxpr = jax.make_jaxpr(
        D._dist_mult_sharded_fn(mesh, False, row, col, True, True))(x)
    prims = set()
    _collect(jaxpr.jaxpr, prims)
    assert "while" in prims, sorted(prims)
    leaks = [q for q in prims if "callback" in q or q == "infeed"]
    assert not leaks, leaks


@needs2
def test_sharded_telemetry_matches_host_oracle():
    """The mesh-wide aux (globally psum'd per-level newly-reached counts)
    must agree with the host BFS truth and with the single-device engine's
    telemetry convention."""
    g = T.make("jellyfish", n=137, r=5, seed=3)
    adj = g.adjacency_dense(np.float32)
    for shards in _shard_counts():
        mesh = D.device_mesh(shards)
        p, _, block = D.pad_block_sharded(g.n, shards)
        x = jnp.asarray(WF.pad_operand(adj, p, 0.0))
        dist, mult, aux = D.dist_mult_sharded(x, mesh, block=block,
                                              telemetry=True)
        d = np.asarray(dist)[:g.n, :g.n]
        attrs = WF.telemetry_attrs(aux)
        diam = int(d[np.isfinite(d)].max())
        assert attrs["converged_level"] == diam
        assert attrs["levels"] == diam + 1
        assert attrs["frontier_sizes"] == [int((d == k).sum())
                                           for k in range(1, diam + 1)]


@needs2
def test_sharded_batched_telemetry_per_graph():
    graphs = [T.make("slimfly", q=5), T.make("hypercube", dim=5)]
    k = 128
    stack = np.zeros((len(graphs), k, k), np.float32)
    for i, g in enumerate(graphs):
        stack[i, :g.n, :g.n] = g.adjacency_dense(np.float32)
    mesh = D.device_mesh(2)
    p, _, block = D.pad_block_sharded(k, 2, batched=True)
    x = jnp.asarray(WF.pad_operand(stack, p, 0.0))
    dist, mult, aux = D.dist_mult_sharded(x, mesh, block=block,
                                          telemetry=True)
    attrs = WF.telemetry_attrs(aux)
    for i, g in enumerate(graphs):
        d = np.asarray(dist)[i, :g.n, :g.n]
        diam = int(d[np.isfinite(d)].max())
        assert attrs["levels_per_graph"][i] == diam
        sizes = attrs["frontier_sizes_per_graph"][i]
        assert sizes[:diam] == [int((d == k_).sum())
                                for k_ in range(1, diam + 1)]
        assert not any(sizes[diam:])

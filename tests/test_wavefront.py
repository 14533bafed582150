"""Device-resident wavefront engine vs every oracle, across all 12 families.

Correctness anchors: batched CSR BFS (`bfs_distances`), host-looped tropical
squaring (`apsp_dense(method="squaring")`), the retired fused tropical-count
relaxation (`tropical_count_relaxation`), and the host-looped Brandes
accumulation. Plus the no-host-transfer regression: the jitted level loop
must lower to ONE compiled call (a single `while` on device, no callbacks)
and execute under a disallow-transfer guard.
"""
import numpy as np
import pytest

import jax
import jax.extend.core as jex_core
import jax.numpy as jnp

from repro.core import topology as T
from repro.core.analysis import apsp_dense, bfs_distances
from repro.core.analysis import wavefront as WF
from repro.core.analysis.paths import (
    shortest_path_multiplicity, tropical_count_relaxation,
)
from repro.core.graph import Graph
from repro.core.routing.assign import ecmp_all_pairs_loads
from repro.kernels import autotune


def _bfs_dist(g) -> np.ndarray:
    b = bfs_distances(g, np.arange(g.n)).astype(np.float32)
    return np.where(b < 0, np.float32(np.inf), b)


# -- all 12 registered families against all oracles ---------------------------

@pytest.mark.parametrize("fam", T.families())
def test_wavefront_matches_oracles(fam):
    g = T.by_servers(fam, 120)
    dist, mult = WF.wavefront_dist_mult(g.adjacency_dense(np.float32))
    # BFS oracle and tropical-squaring oracle: dist bit-identical
    np.testing.assert_array_equal(dist, _bfs_dist(g))
    np.testing.assert_array_equal(dist, apsp_dense(g, method="squaring"))
    # masked-counting oracle over the BFS distances: mult bit-identical
    _, m_ref = shortest_path_multiplicity(g, _bfs_dist(g), use_kernel=False)
    np.testing.assert_array_equal(mult, m_ref)
    # ECMP loads: identical inputs through the device engine are
    # bit-identical, and match the f64 host-looped Brandes reference
    adj = g.adjacency_dense(np.float64)
    loads_dev = ecmp_all_pairs_loads(dist, mult, adj, use_kernel=True)
    loads_dev2 = ecmp_all_pairs_loads(dist, m_ref, adj, use_kernel=True)
    np.testing.assert_array_equal(loads_dev, loads_dev2)
    loads_host = ecmp_all_pairs_loads(dist, m_ref, adj, use_kernel=False)
    np.testing.assert_allclose(loads_dev, loads_host, rtol=1e-5, atol=1e-9)


def test_wavefront_matches_tropical_count_relaxation():
    g = T.make("slimfly", q=5)
    dist, mult = WF.wavefront_dist_mult(g.adjacency_dense(np.float32))
    d_ref, m_ref = tropical_count_relaxation(g)
    np.testing.assert_array_equal(dist, d_ref)
    np.testing.assert_array_equal(mult, m_ref)


def test_wavefront_batched_matches_per_graph():
    graphs = [T.make("slimfly", q=5), T.make("torus", dims=(4, 5)),
              T.make("hypercube", dim=5)]
    p = 128
    stack = np.zeros((len(graphs), p, p), np.float32)
    for i, g in enumerate(graphs):
        stack[i, :g.n, :g.n] = g.adjacency_dense(np.float32)
    dist, mult = WF.wavefront_dist_mult(stack)
    loads = ecmp_all_pairs_loads(dist, mult, stack.astype(np.float64),
                                 use_kernel=True)
    for i, g in enumerate(graphs):
        d1, m1 = WF.wavefront_dist_mult(g.adjacency_dense(np.float32))
        np.testing.assert_array_equal(dist[i, :g.n, :g.n], d1)
        np.testing.assert_array_equal(mult[i, :g.n, :g.n], m1)
        l1 = ecmp_all_pairs_loads(d1, m1, g.adjacency_dense(np.float64),
                                  use_kernel=True)
        np.testing.assert_allclose(loads[i, :g.n, :g.n], l1,
                                   rtol=1e-6, atol=1e-9)
        # phantom padding stays inert
        assert np.isinf(dist[i, :g.n, g.n:]).all()


def test_wavefront_disconnected_and_edgeless():
    g = Graph(n=6, edges=np.array([(0, 1), (1, 2), (3, 4), (4, 5)]))
    dist, mult = WF.wavefront_dist_mult(g.adjacency_dense(np.float32))
    assert np.isinf(dist[0, 3]) and mult[0, 3] == 0
    assert dist[0, 2] == 2 and mult[0, 2] == 1
    g2 = Graph(n=4, edges=np.empty((0, 2)))
    dist2, mult2 = WF.wavefront_dist_mult(g2.adjacency_dense(np.float32))
    off = ~np.eye(4, dtype=bool)
    assert np.isinf(dist2[off]).all() and (mult2[off] == 0).all()
    assert (np.diag(dist2) == 0).all() and (np.diag(mult2) == 1).all()


def test_bfs_span_chunking_is_exact(monkeypatch):
    """The memory-bounded chunked span gather must match the one-shot path."""
    from repro.core.analysis import apsp as A

    g = T.make("jellyfish", n=96, r=6, seed=1)
    want = bfs_distances(g, np.arange(g.n))
    monkeypatch.setattr(A, "_SPAN_BUDGET", 1)  # chunk = one adjacency (2E)
    got = bfs_distances(g, np.arange(g.n))
    np.testing.assert_array_equal(got, want)


def test_weighted_squaring_device_matches_oracle():
    rng = np.random.default_rng(3)
    n = 150
    lm = np.full((n, n), np.inf, np.float32)
    np.fill_diagonal(lm, 0.0)
    mask = rng.random((n, n)) < 0.05
    lm[mask] = (rng.random(mask.sum()) + 0.25).astype(np.float32)
    from repro.core.analysis import apsp_from_lengths

    got = apsp_from_lengths(lm, use_kernel=True)
    want = apsp_from_lengths(lm, use_kernel=False)
    np.testing.assert_array_equal(got, want)


# -- the no-host-transfer / single-compiled-call regression -------------------

def _collect_primitives(jaxpr, prims):
    for eqn in jaxpr.eqns:
        prims.add(eqn.primitive.name)
        for val in eqn.params.values():
            for sub in _subjaxprs(val):
                _collect_primitives(sub, prims)


def _subjaxprs(val):
    if isinstance(val, jex_core.ClosedJaxpr):
        yield val.jaxpr
    elif isinstance(val, jex_core.Jaxpr):
        yield val
    elif isinstance(val, (list, tuple)):
        for item in val:
            yield from _subjaxprs(item)


def test_level_loop_is_one_device_resident_call():
    g = T.make("slimfly", q=5)
    p, block = WF.pad_block(g.n)
    padded = np.zeros((p, p), np.float32)
    padded[:g.n, :g.n] = g.adjacency_dense(np.float32)

    fn = WF._dist_mult_fn(False, block, True)
    # lowering: the whole level loop is one jitted call around a single
    # device `while`; nothing calls back to the host mid-loop
    jaxpr = jax.make_jaxpr(fn)(jnp.asarray(padded))
    prims = set()
    _collect_primitives(jaxpr.jaxpr, prims)
    assert "while" in prims, sorted(prims)
    leaks = [p_ for p_ in prims if "callback" in p_ or p_ == "infeed"]
    assert not leaks, leaks

    # execution: zero host<->device transfers between the input upload and
    # the final matrices (the convergence test never syncs to host)
    adj_dev = jax.device_put(jnp.asarray(padded))
    fn(adj_dev)  # compile outside the guard
    with jax.transfer_guard("disallow"):
        dist, mult = fn(adj_dev)
        jax.block_until_ready((dist, mult))
    np.testing.assert_array_equal(
        np.asarray(dist)[:g.n, :g.n], apsp_dense(g, method="squaring"))


# -- autotuner ----------------------------------------------------------------

def test_autotune_resolve_precedence(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_TABLE", str(tmp_path / "table.json"))
    autotune.load_table(refresh=True)
    try:
        base = autotune.resolve("frontier_step", 4096, 4096, 4096)
        assert set(base) == {"bm", "bn", "bk"}
        autotune.save_entry("frontier_step", autotune.shape_key(4096, 4096, 4096),
                            {"bm": 256, "bn": 256, "bk": 256})
        tuned = autotune.resolve("frontier_step", 4096, 4096, 4096)
        assert tuned["bm"] == 256
        # explicit arguments always beat the table
        over = autotune.resolve("frontier_step", 4096, 4096, 4096, bm=128)
        assert over["bm"] == 128 and over["bk"] == 256
        # block shapes clamp to the bucketed problem size
        small = autotune.resolve("frontier_step", 40, 40, 40)
        assert small["bm"] == 128
    finally:
        monkeypatch.delenv("REPRO_TUNE_TABLE")
        autotune.load_table(refresh=True)


def test_autotune_shape_bucketing():
    assert autotune.shape_key(100, 128, 129) == "128x128x256"
    assert autotune.shape_key(1024, 1000, 513) == "1024x1024x1024"


# -- observability: jaxpr identity + in-loop device telemetry -----------------

def test_observability_disabled_jaxpr_is_bit_identical():
    """Enabling the tracer must not perturb the untelemetered engine: the
    telemetry=False jaxpr is byte-equal whether observability was ever on,
    and the telemetry=True jaxpr (extra aux outputs) is still one device
    `while` with no callbacks."""
    from repro import obs

    g = T.make("slimfly", q=5)
    p, block = WF.pad_block(g.n)
    x = jnp.asarray(WF.pad_operand(g.adjacency_dense(np.float32), p, 0.0))
    base = str(jax.make_jaxpr(WF._dist_mult_fn(False, block, True, False))(x))
    obs.enable()
    try:
        again = str(
            jax.make_jaxpr(WF._dist_mult_fn(False, block, True, False))(x))
        tele = str(
            jax.make_jaxpr(WF._dist_mult_fn(False, block, True, True))(x))
    finally:
        obs.disable()
        obs.reset()
    assert again == base
    assert tele != base
    jaxpr = jax.make_jaxpr(WF._dist_mult_fn(False, block, True, True))(x)
    prims = set()
    _collect_primitives(jaxpr.jaxpr, prims)
    assert "while" in prims, sorted(prims)
    leaks = [q for q in prims if "callback" in q or q == "infeed"]
    assert not leaks, leaks


@pytest.mark.parametrize("fam", ["slimfly", "fattree", "jellyfish",
                                 "hypercube", "dragonfly"])
def test_wavefront_telemetry_matches_host_bfs_oracle(fam):
    """The aux device outputs against the host BFS truth: levels executed =
    diameter + 1 confirmation sweep, frontier_sizes[k] = pairs first
    reached at hop k = count of dist == k."""
    g = T.by_servers(fam, 120)
    p, block = WF.pad_block(g.n)
    padded = WF.pad_operand(g.adjacency_dense(np.float32), p, 0.0)
    dist, mult, aux = WF.dist_mult_device(jnp.asarray(padded), block=block,
                                          telemetry=True)
    d = np.asarray(dist)[:g.n, :g.n]
    np.testing.assert_array_equal(d, _bfs_dist(g))
    attrs = WF.telemetry_attrs(aux)
    diam = int(d[np.isfinite(d)].max())
    assert attrs["converged_level"] == diam
    assert attrs["levels"] == diam + 1  # the convergence-confirming sweep
    assert attrs["frontier_sizes"] == [int((d == k).sum())
                                       for k in range(1, diam + 1)]


def test_wavefront_telemetry_batched_per_graph():
    graphs = [T.make("slimfly", q=5), T.make("torus", dims=(4, 5)),
              T.make("hypercube", dim=5)]
    p = 128
    stack = np.zeros((len(graphs), p, p), np.float32)
    for i, g in enumerate(graphs):
        stack[i, :g.n, :g.n] = g.adjacency_dense(np.float32)
    dist, mult, aux = WF.dist_mult_device(jnp.asarray(stack), telemetry=True)
    attrs = WF.telemetry_attrs(aux)
    deepest = attrs["converged_level"]
    for i, g in enumerate(graphs):
        d = np.asarray(dist)[i, :g.n, :g.n]
        diam = int(d[np.isfinite(d)].max())
        assert attrs["levels_per_graph"][i] == diam
        sizes = attrs["frontier_sizes_per_graph"][i]
        assert len(sizes) == deepest  # padded to the deepest graph
        assert sizes[:diam] == [int((d == k).sum())
                                for k in range(1, diam + 1)]
        assert not any(sizes[diam:])  # zero-filled past its own diameter
    assert deepest == max(attrs["levels_per_graph"])


def test_squaring_telemetry_reports_convergence_step():
    g = T.make("slimfly", q=5)
    p, _ = WF.pad_block(g.n)
    seed = np.full((p, p), np.float32(np.inf), np.float32)
    np.fill_diagonal(seed, 0.0)
    seed[:g.n, :g.n] = np.where(
        g.adjacency_dense(np.float32) > 0, np.float32(1), np.float32(np.inf))
    np.fill_diagonal(seed[:g.n, :g.n], 0.0)
    plain = WF.squaring_apsp_device(jnp.asarray(seed))
    dist, squarings = WF.squaring_apsp_device(jnp.asarray(seed),
                                              telemetry=True)
    np.testing.assert_array_equal(np.asarray(dist), np.asarray(plain))
    cap = max(1, int(np.ceil(np.log2(p))))
    assert 1 <= int(squarings) <= cap


def test_wavefront_host_wrapper_spans_under_tracing():
    """Under an enabled tracer the host wrapper spans the call, folds the
    device telemetry into the span attrs, and accounts the adjacency
    upload bytes — while returning exactly the usual (dist, mult)."""
    from repro import obs

    g = T.make("slimfly", q=5)
    adj = g.adjacency_dense(np.float32)
    want_d, want_m = WF.wavefront_dist_mult(adj)
    obs.disable()
    obs.reset()
    obs.meters.reset()
    obs.enable()
    try:
        dist, mult = WF.wavefront_dist_mult(adj)
        events = obs.events()
        h2d = obs.snapshot().get("h2d_bytes.adjacency", {})
    finally:
        obs.disable()
        obs.reset()
        obs.meters.reset()
    np.testing.assert_array_equal(dist, want_d)
    np.testing.assert_array_equal(mult, want_m)
    (span,) = [ev for ev in events if ev["name"] == "wavefront.dist_mult"]
    diam = int(want_d[np.isfinite(want_d)].max())
    assert span["args"]["converged_level"] == diam
    assert span["args"]["levels"] == diam + 1
    assert span["args"]["h2d_bytes"] > 0
    assert h2d.get("value", 0) == span["args"]["h2d_bytes"]

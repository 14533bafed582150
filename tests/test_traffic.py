"""Traffic engine: generator invariants, TrafficSpec round-trips and
deprecation shims, the demand-weighted ECMP engine vs its oracles,
saturation search vs closed forms, and the traffic x failure grid."""
import warnings

import numpy as np
import pytest

from repro.core import topology as topo
from repro.core import traffic, workload
from repro.core.analysis.wavefront import (batched_dist_mult,
                                          wavefront_dist_mult)
from repro.core.routing import assign
from repro.core.routing.throughput import max_concurrent_flow
from repro.core.traffic import (TrafficSpec, check_grid,
                                evaluate_traffic_batch,
                                evaluate_traffic_failure_batch,
                                format_grid_table, pairs_to_matrix,
                                pattern_names, saturation_search,
                                traffic_failure_grid)
from repro.core.traffic.spec import generate


def _ring(n=16):
    return topo.make("torus", dims=(n,))


def _dist_mult(g):
    adj = g.adjacency_dense()
    return (adj,) + wavefront_dist_mult(adj)


# ---------------------------------------------------------------- generators

ALL_PATTERNS = ("uniform", "permutation", "tornado", "shift", "bitcomp",
                "hotspot", "bursty")


def test_registry_covers_suite():
    assert set(ALL_PATTERNS) | {"server_permutation"} <= set(pattern_names())


@pytest.mark.parametrize("name", ALL_PATTERNS)
def test_generator_row_sums_and_contract(name):
    n, rate, s = 24, 2.5, 5
    out = generate(name, n, rate=rate, seed=3, samples=s)
    assert out.shape == (s, n, n)
    assert out.dtype == np.float64
    assert np.all(np.diagonal(out, axis1=1, axis2=2) == 0.0)
    assert np.all(out >= 0.0)
    rows = out.sum(axis=2)
    if name == "bursty":
        # on-rows inject exactly `rate`, off-rows nothing
        assert np.allclose(np.where(rows > 0, rows, rate), rate)
    else:
        assert np.allclose(rows, rate)


@pytest.mark.parametrize("name", ALL_PATTERNS)
def test_generator_deterministic(name):
    a = generate(name, 16, seed=9, samples=3)
    b = generate(name, 16, seed=9, samples=3)
    np.testing.assert_array_equal(a, b)
    c = generate(name, 16, seed=10, samples=3)
    if name not in ("uniform", "tornado", "shift", "bitcomp"):
        assert not np.array_equal(a, c)


def test_permutation_is_derangement():
    out = generate("permutation", 17, samples=8)
    for m in out:
        assert np.all(m.sum(axis=1) == 1.0)
        assert np.all(m.sum(axis=0) == 1.0)


def test_bitcomp_crosses_bisection():
    m = generate("bitcomp", 16)[0]
    src = np.arange(16)
    assert np.all(m[src, (16 - 1) ^ src] == 1.0)
    # odd-n mirror: the center row stays silent
    m9 = generate("bitcomp", 9)[0]
    assert m9[4].sum() == 0.0


def test_hotspot_skew_monotone_in_zipf_a():
    def skew(a):
        out = generate("hotspot", 32, seed=5, samples=4, zipf_a=a)
        # hottest destination's share of total volume, averaged over samples
        return (out.sum(axis=1).max(axis=1) / out.sum(axis=(1, 2))).mean()

    s = [skew(a) for a in (1.05, 1.3, 1.8, 2.5)]
    assert all(b > a for a, b in zip(s, s[1:]))


def test_bursty_duty_scales_time_average():
    lo = generate("bursty", 16, seed=0, samples=400, duty=0.2)
    hi = generate("bursty", 16, seed=0, samples=400, duty=0.8)
    assert hi.mean() > 2 * lo.mean()
    # sync=1: a phase is all-on or all-off
    on_rows = (lo.sum(axis=2) > 0).sum(axis=1)
    assert set(np.unique(on_rows)) <= {0, 16}


def test_shift_rejects_degenerate_k():
    with pytest.raises(ValueError):
        generate("shift", 8, shift=8)


# ------------------------------------------------------ server patterns

SERVER_GRAPHS = {
    "slimfly_q5": lambda: topo.make("slimfly", q=5),
    "fattree_k4": lambda: topo.make("fattree", k=4),
}


def test_server_counts_per_family():
    sf = SERVER_GRAPHS["slimfly_q5"]()
    assert np.all(sf.server_counts() == sf.concentration)
    ft = SERVER_GRAPHS["fattree_k4"]()
    counts = ft.server_counts()
    n_edge = ft.meta["n_edge"]
    assert np.all(counts[:-n_edge] == 0)
    assert np.all(counts[-n_edge:] == ft.meta["edge_concentration"])
    assert counts.sum() == ft.num_servers
    with pytest.raises(ValueError, match="not recorded"):
        topo.make("megafly", m=2).server_counts()


@pytest.mark.parametrize("graph", sorted(SERVER_GRAPHS))
def test_server_permutation_row_and_column_sums(graph):
    g = SERVER_GRAPHS[graph]()
    rate = 2.5
    out = TrafficSpec.parse(
        f"server_permutation:rate={rate},samples=8,seed=3").batch(g)
    assert out.shape == (8, g.n, g.n) and out.dtype == np.float64
    want = rate * g.server_counts()
    assert np.all(out.sum(axis=2) == want)
    assert np.all(out.sum(axis=1) == want)
    assert np.all(out >= 0.0)


@pytest.mark.parametrize("graph", sorted(SERVER_GRAPHS))
def test_server_permutation_seeded_and_round_trips(graph):
    g = SERVER_GRAPHS[graph]()
    spec = TrafficSpec.parse("server_permutation:samples=8,seed=5")
    assert TrafficSpec.parse(spec.describe()) == spec
    np.testing.assert_array_equal(spec.batch(g), spec.batch(g))
    assert not np.array_equal(spec.batch(g), spec.with_(seed=6).batch(g))


def test_server_permutation_sends_no_server_to_itself():
    # one server per router: a self-send would sit on the diagonal, where
    # an unconstrained permutation puts one per sample on average
    g = topo.make("torus", dims=(12,), concentration=1)
    out = TrafficSpec.parse("server_permutation:samples=64,seed=1").batch(g)
    assert np.all(np.diagonal(out, axis1=1, axis2=2) == 0.0)
    assert np.all(out.sum(axis=2) == 1.0) and np.all(out.sum(axis=1) == 1.0)


def test_server_permutation_fat_tree_core_and_aggregation_silent():
    g = SERVER_GRAPHS["fattree_k4"]()
    out = TrafficSpec.parse("server_permutation:samples=8,seed=2").batch(g)
    inner = g.n - g.meta["n_edge"]          # core and aggregation routers
    assert not out[:, :inner, :].any() and not out[:, :, :inner].any()
    res = evaluate_traffic_batch(g, out)
    # every flow leaves an edge switch: 4 hops between pods, 2 within one
    assert np.all((res["avg_hops"] >= 2.0) & (res["avg_hops"] <= 4.0))


def test_server_permutation_needs_servers():
    g = topo.make("torus", dims=(6,), concentration=0)
    with pytest.raises(ValueError, match="at least two servers"):
        TrafficSpec.parse("server_permutation").batch(g)
    with pytest.raises(ValueError, match="give it a graph"):
        TrafficSpec.parse("server_permutation").batch(6)
    with pytest.raises(ValueError, match="not a generator parameter"):
        TrafficSpec.parse("uniform:servers=3")


# ------------------------------------------------------------------- spec

def test_spec_parse_describe_round_trip():
    for text in ("uniform", "hotspot:zipf_a=1.4",
                 "permutation:flows=4096,seed=2",
                 "bursty:duty=0.25,rate=0.5,samples=16,sync=0",
                 "server_permutation:samples=8,seed=5"):
        spec = TrafficSpec.parse(text)
        again = TrafficSpec.parse(spec.describe())
        assert again == spec
        assert TrafficSpec.parse(spec) is spec


def test_spec_unknown_pattern_and_bad_items():
    with pytest.raises(KeyError):
        TrafficSpec.parse("wormhole")
    with pytest.raises(ValueError):
        TrafficSpec.parse("uniform:rate")
    with pytest.raises(ValueError):
        TrafficSpec(pattern="uniform", params={"seed": 1})


def test_spec_batch_matrix_pairs_consistent():
    g = _ring(12)
    spec = TrafficSpec.parse("hotspot:zipf_a=1.5,samples=3,seed=4")
    batch = spec.batch(g)
    assert batch.shape == (3, 12, 12)
    np.testing.assert_array_equal(spec.matrix(g), spec.batch(g, samples=1)[0])
    flows = spec.with_(flows=200)
    pairs = flows.pairs(g)
    assert pairs.shape == (200, 2)
    assert np.all(pairs[:, 0] != pairs[:, 1])
    np.testing.assert_array_equal(
        flows.batch(g, samples=1)[0] > 0,
        pairs_to_matrix(g.n, pairs) > 0)


def test_spec_scaled_is_linear():
    g = _ring(12)
    spec = TrafficSpec.parse("tornado")
    np.testing.assert_allclose(spec.scaled(0.5).matrix(g),
                               0.5 * spec.matrix(g))


# ------------------------------------------------------ deprecation shims

def test_make_traffic_exact_flows_and_warns():
    g = topo.make("jellyfish", n=30, r=6, seed=0)
    for pattern in ("permutation", "uniform", "skewed"):
        with pytest.deprecated_call():
            wl = workload.make_traffic(g, pattern, flows=777, seed=1)
        assert len(wl.pairs) == 777           # the historical contract bug
        assert np.all(wl.pairs[:, 0] != wl.pairs[:, 1])
    with pytest.raises(ValueError):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            workload.make_traffic(g, "nope")


def test_demand_matrix_shim_equivalence():
    g = _ring(10)
    pairs = np.array([(0, 3), (3, 0), (0, 3), (5, 5), (2, 9)])
    with pytest.deprecated_call():
        legacy = assign.demand_matrix(g, pairs, volume=2.0)
    np.testing.assert_array_equal(legacy, pairs_to_matrix(g.n, pairs, 2.0))
    assert legacy[0, 3] == 4.0                # summed, volume-weighted
    assert legacy[5, 5] == 0.0                # self-pairs zeroed
    wl = workload.Workload(pairs=pairs, volume=2.0)
    np.testing.assert_array_equal(wl.demand_matrix(g), legacy)


# -------------------------------------------------- demand-weighted engine

@pytest.mark.parametrize("use_kernel", [False, True])
def test_ecmp_demand_loads_matches_all_pairs_on_ones(use_kernel):
    g = topo.make("jellyfish", n=36, r=6, seed=2)
    adj, dist, mult = _dist_mult(g)
    ones = np.ones((g.n, g.n))
    np.fill_diagonal(ones, 0.0)
    ref = assign.ecmp_all_pairs_loads(dist, mult, adj, use_kernel=use_kernel)
    got = assign.ecmp_demand_loads(dist, mult, adj, ones,
                                   use_kernel=use_kernel)
    np.testing.assert_allclose(got, ref, rtol=1e-12)


def test_ecmp_demand_loads_matches_link_loads_oracle():
    g = topo.make("jellyfish", n=36, r=6, seed=2)
    adj, dist, mult = _dist_mult(g)
    batch = TrafficSpec.parse("hotspot:zipf_a=1.4,samples=3,seed=7").batch(g)
    ref = np.stack([assign.ecmp_link_loads(g, dist, mult, batch[i],
                                           use_kernel=False, directed=True)
                    for i in range(3)])
    got = assign.ecmp_demand_loads(dist, mult, adj, batch, use_kernel=False)
    np.testing.assert_allclose(got, ref, rtol=1e-7, atol=1e-10)


def test_ecmp_demand_loads_kernel_matches_host():
    g = topo.make("jellyfish", n=36, r=6, seed=2)
    adj, dist, mult = _dist_mult(g)
    batch = TrafficSpec.parse("bursty:samples=4,seed=1").batch(g)
    host = assign.ecmp_demand_loads(dist, mult, adj, batch, use_kernel=False)
    dev = assign.ecmp_demand_loads(dist, mult, adj, batch, use_kernel=True)
    np.testing.assert_allclose(dev, host, rtol=1e-5, atol=1e-6)


def test_tornado_and_shift_closed_forms_on_ring():
    g = _ring(16)
    adj, dist, mult = _dist_mult(g)
    torn = TrafficSpec.parse("tornado").batch(g)
    loads = assign.ecmp_demand_loads(dist, mult, adj, torn, use_kernel=False)
    assert loads.max() == pytest.approx(16 / 4)   # rate * n / 4
    for k in (1, 2, 3):
        sh = TrafficSpec.parse(f"shift:shift={k}").batch(g)
        lk = assign.ecmp_demand_loads(dist, mult, adj, sh, use_kernel=False)
        assert lk.max() == pytest.approx(float(k))  # rate * shift


def test_tornado_closed_form_on_torus():
    # torus(4,4): shift by n/2 = 8 is a per-column ring tornado -> rate*k/4
    g = topo.make("torus", dims=(4, 4))
    adj, dist, mult = _dist_mult(g)
    torn = TrafficSpec.parse("tornado").batch(g)
    loads = assign.ecmp_demand_loads(dist, mult, adj, torn, use_kernel=False)
    assert loads.max() == pytest.approx(4 / 4)


# ------------------------------------------------------------- scenarios

def test_evaluate_traffic_batch_metrics():
    g = _ring(16)
    out = evaluate_traffic_batch(g, "tornado:samples=3", use_kernel=False)
    for key in traffic.TRAFFIC_METRICS:
        assert out[key].shape == (3,)
    assert out["max_link_load"][0] == pytest.approx(4.0)
    assert out["tput_lb"][0] == pytest.approx(0.25)
    assert out["avg_hops"][0] == pytest.approx(8.0)
    assert out["dropped_demand_frac"][0] == 0.0
    assert out["demand_total"][0] == pytest.approx(16.0)


def _enumerated_loads(g, demand):
    """Directed ECMP loads by listing every shortest path of every pair and
    giving each an equal share of the pair's demand (tiny graphs only)."""
    adj = g.adjacency_dense(np.float64)
    nbrs = [np.flatnonzero(adj[u]) for u in range(g.n)]
    loads = np.zeros((g.n, g.n))
    for s in range(g.n):
        dist = np.full(g.n, -1)
        dist[s] = 0
        frontier = [s]
        while frontier:
            nxt = []
            for u in frontier:
                for w in nbrs[u]:
                    if dist[w] < 0:
                        dist[w] = dist[u] + 1
                        nxt.append(w)
            frontier = nxt

        def paths_to(t):
            if t == s:
                return [[s]]
            return [p + [t] for u in nbrs[t] if dist[u] == dist[t] - 1
                    for p in paths_to(u)]

        for t in np.flatnonzero(demand[s]):
            if t == s or dist[t] < 0:
                continue
            paths = paths_to(t)
            for p in paths:
                for u, v in zip(p, p[1:]):
                    loads[u, v] += demand[s, t] / len(paths)
    return loads


@pytest.mark.parametrize("graph", sorted(SERVER_GRAPHS))
def test_evaluate_traffic_batch_server_permutation(graph):
    g = SERVER_GRAPHS[graph]()
    spec = "server_permutation:samples=3,seed=11"
    kernel = evaluate_traffic_batch(g, spec)
    host = evaluate_traffic_batch(g, spec, use_kernel=False)
    demand = TrafficSpec.parse(spec).batch(g)
    dist = wavefront_dist_mult(g.adjacency_dense())[0]
    for i in range(3):
        loads = _enumerated_loads(g, demand[i])
        used = np.sort(loads[loads > 0])
        routed = np.where(np.isfinite(dist) & (dist > 0), demand[i], 0.0)
        ranks = [used[int(np.round(q * (len(used) - 1)))]
                 for q in (0.5, 0.9, 0.99)]
        want = {
            "max_link_load": used.max(), "tput_lb": 1.0 / used.max(),
            "mean_link_load": used.mean(), "p50_link_load": ranks[0],
            "p90_link_load": ranks[1], "p99_link_load": ranks[2],
            "links_used_frac": len(used) / (2 * g.num_edges),
            "avg_hops": (routed * np.where(routed > 0, dist, 0)).sum()
            / routed.sum(),
            "demand_total": float(g.num_servers),
            "dropped_demand_frac": np.trace(demand[i]) / g.num_servers,
        }
        for key in traffic.TRAFFIC_METRICS:
            assert host[key][i] == pytest.approx(want[key], rel=1e-12), key
            assert kernel[key][i] == pytest.approx(want[key], rel=1e-5), key


def test_evaluate_traffic_batch_drops_unroutable_demand():
    # two disconnected rings: cross-component demand is dropped, not routed
    a = _ring(8)
    edges = np.concatenate([a.edges, a.edges + 8])
    import repro.core.graph as G

    g = G.Graph(n=16, edges=edges, name="two-rings")
    demand = np.zeros((16, 16))
    demand[0, 12] = 1.0   # unreachable
    demand[0, 2] = 1.0    # reachable
    out = evaluate_traffic_batch(g, demand, use_kernel=False)
    assert out["dropped_demand_frac"][0] == pytest.approx(0.5)
    assert out["max_link_load"][0] > 0


def _metrics_case(case):
    """(graph, adjacency, demand) of one stack: one shared graph, per-sample
    failure-masked graphs, or a partitioned graph (two rings)."""
    import repro.core.graph as G
    from repro.core.resilience import failure_batch, failure_plan

    if case == "two_rings":
        a = _ring(8)
        g = G.Graph(n=16, edges=np.concatenate([a.edges, a.edges + 8]),
                    name="two-rings")
        spec = "uniform:samples=3,seed=5"
    else:
        g = topo.make("slimfly", q=5)
        spec = "server_permutation:samples=3,seed=4"
    adj = g.adjacency_dense()
    if case == "failure_masked":
        adj = failure_batch(failure_plan(g, samples=3, seed=1), 12).adjacency
    return g, adj, TrafficSpec.parse(spec).batch(g)


def _full_matrix_metrics(loads, dist, demand, n_links):
    """The metrics reduced over every cell of (S, n, n) float64 loads."""
    s = len(loads)
    offered = np.broadcast_to(demand, loads.shape)
    off = np.isfinite(dist) & (dist > 0)
    routed = np.where(off, offered, 0.0)
    total = offered.reshape(s, -1).sum(1)
    routed_sum = routed.reshape(s, -1).sum(1)
    peak = loads.reshape(s, -1).max(1)
    pos = loads > 0
    cnt = pos.reshape(s, -1).sum(1)
    ranked = np.sort(np.where(pos, loads, np.inf).reshape(s, -1), axis=1)
    rank = {q: np.where(cnt > 0, ranked[np.arange(s), np.round(
        q * np.maximum(cnt - 1, 0)).astype(int)], 0.0)
        for q in (0.5, 0.9, 0.99)}
    hops = np.where(off, routed * np.where(off, dist, 0.0), 0.0)
    return {
        "max_link_load": peak,
        "tput_lb": np.where((routed_sum > 0) & (peak > 0), 1.0 / peak, 0.0),
        "mean_link_load": np.where(pos, loads, 0.0).reshape(s, -1).sum(1)
        / np.maximum(cnt, 1),
        "p50_link_load": rank[0.5], "p90_link_load": rank[0.9],
        "p99_link_load": rank[0.99],
        "links_used_frac": cnt / n_links,
        "avg_hops": hops.reshape(s, -1).sum(1) / routed_sum,
        "demand_total": total,
        "dropped_demand_frac": 1.0 - routed_sum / total,
    }


@pytest.mark.parametrize("path", ["host", "device"])
@pytest.mark.parametrize("case", ["shared", "failure_masked", "two_rings"])
def test_link_cell_metrics_match_full_matrix_formula(case, path):
    import jax.numpy as jnp

    from repro.core.traffic import scenarios

    g, adj, demand = _metrics_case(case)
    dist, mult = batched_dist_mult(adj)
    cells = np.flatnonzero(g.adjacency_dense())
    if path == "host":
        loads = assign.ecmp_demand_loads(dist, mult, adj, demand,
                                         use_kernel=False)
        link = scenarios._link_loads(loads, cells)
    else:
        # the device loads are the default path's, bit for bit
        kept = assign.ecmp_demand_loads(dist, mult, adj, demand, device=True)
        loads = assign.ecmp_demand_loads(dist, mult, adj, demand)
        assert kept.shape == demand.shape and kept.dtype == np.float32
        np.testing.assert_array_equal(np.asarray(kept).astype(np.float64),
                                      loads)
        link = scenarios._link_loads(kept, cells,
                                     jnp.asarray(cells, np.int32))
    if case == "failure_masked":
        # every masked graph lost links, which now carry nothing
        assert np.all(np.count_nonzero(adj.reshape(len(adj), -1), 1)
                      < len(cells))
    n_links = 2 * g.num_edges
    got = scenarios._traffic_metrics(link, demand,
                                     scenarios._demand_weights(dist)[1:],
                                     n_links, 1.0)
    want = _full_matrix_metrics(loads, dist, demand, n_links)
    if case == "two_rings":
        assert np.all(want["dropped_demand_frac"] > 0.4)
    for key in ("max_link_load", "tput_lb", "p50_link_load",
                "p90_link_load", "p99_link_load", "links_used_frac"):
        np.testing.assert_array_equal(got[key], want[key], err_msg=key)
    for key in ("mean_link_load", "avg_hops", "demand_total",
                "dropped_demand_frac"):
        np.testing.assert_allclose(got[key], want[key], rtol=1e-12,
                                   atol=0, err_msg=key)


def test_traffic_failure_batch_unfailed_matches_unfailed_engine():
    g = topo.make("jellyfish", n=30, r=6, seed=1)
    dem = TrafficSpec.parse("hotspot:samples=4,seed=2").batch(g)
    stack = np.broadcast_to(g.adjacency_dense(np.float32),
                            (4, g.n, g.n)).copy()
    failed = evaluate_traffic_failure_batch(g, dem, stack, use_kernel=False)
    clean = evaluate_traffic_batch(g, dem, use_kernel=False)
    for key in traffic.TRAFFIC_METRICS:
        np.testing.assert_allclose(failed[key], clean[key], rtol=1e-7,
                                   err_msg=key)
    assert np.all(failed["reachable_frac"] == 1.0)


def test_resilience_demand_uniform_matches_legacy_tput():
    from repro.core.resilience import failure_batch, failure_plan
    from repro.core.resilience.degradation import evaluate_failure_batch

    g = topo.make("jellyfish", n=30, r=6, seed=1)
    plan = failure_plan(g, kind="link", samples=8, seed=0)
    batch = failure_batch(plan, 3)
    ones = np.ones((g.n, g.n))
    np.fill_diagonal(ones, 0.0)
    legacy = evaluate_failure_batch(g, batch, use_kernel=False)
    dem = evaluate_failure_batch(g, batch, use_kernel=False, demand=ones)
    np.testing.assert_allclose(dem["tput_lb"], legacy["tput_lb"], rtol=1e-7)
    assert "dropped_demand_frac" in dem


@pytest.mark.parametrize("check", ["loads", "chunks", "saturation"])
@pytest.mark.parametrize("graph", ["jellyfish", "slimfly"])
def test_shared_graph_matches_stacked_graph(graph, check, monkeypatch):
    """One 2-D graph under a stacked demand, uploaded once and broadcast
    inside the program, gives the loads of the same graph stacked on the
    host bit for bit, and so the same metrics however the batch is split."""
    g = (topo.make("jellyfish", n=36, r=6, seed=2) if graph == "jellyfish"
         else topo.make("slimfly", q=5))
    adj, dist, mult = _dist_mult(g)
    spec = "hotspot:zipf_a=1.4,samples=3,seed=7"
    if check == "loads":
        batch = TrafficSpec.parse(spec).batch(g)
        stacked = assign.ecmp_demand_loads(
            *(np.broadcast_to(x, batch.shape) for x in (dist, mult, adj)),
            batch)
        shared = assign.ecmp_demand_loads(
            *assign.upload_graph(dist, mult, adj), batch, device=True)
        np.testing.assert_array_equal(np.asarray(shared, np.float64),
                                      stacked)
        np.testing.assert_array_equal(
            assign.ecmp_demand_loads(dist, mult, adj, batch), stacked)
    elif check == "chunks":
        original, loads = assign.ecmp_demand_loads, []

        def spy(*args, **kw):
            out = original(*args, **kw)
            loads[-1].append(np.asarray(out))
            return out

        monkeypatch.setattr(assign, "ecmp_demand_loads", spy)
        runs = []
        for c in (1, 2, 3):
            loads.append([])
            runs.append(evaluate_traffic_batch(g, spec, mask_chunk=c))
        for run, passes in zip(runs[1:], loads[1:]):
            np.testing.assert_array_equal(np.concatenate(passes),
                                          np.concatenate(loads[0]))
            for k in traffic.TRAFFIC_METRICS:
                if k in ("avg_hops", "dropped_demand_frac"):
                    # the host's float64 mat-vec of the demand rounds by
                    # the number of rows it is given
                    np.testing.assert_allclose(run[k], runs[0][k],
                                               rtol=1e-15)
                else:
                    np.testing.assert_array_equal(run[k], runs[0][k])
    else:
        spec = "hotspot:zipf_a=1.4,samples=2,seed=7"
        got = saturation_search(g, spec, rounds=2, grid=3)
        original = assign.ecmp_demand_loads

        def stacked(dist, mult, adj, demand, **kw):
            # the graph stacked on the host against every pass's demand
            n = demand.shape[-1]
            return original(*(np.broadcast_to(np.asarray(x)[:n, :n],
                                              demand.shape)
                              for x in (dist, mult, adj)), demand, **kw)

        monkeypatch.setattr(assign, "ecmp_demand_loads", stacked)
        assert saturation_search(g, spec, rounds=2, grid=3) == got


# ------------------------------------------------------- saturation search

def test_saturation_search_ring_tornado_closed_form():
    g = _ring(16)
    sat = saturation_search(g, "tornado", use_kernel=False)
    # ring tornado saturates at rate = 4 / n = 0.25
    assert sat["per_sample_mean"] == pytest.approx(0.25)
    assert sat["sat_rate"] == pytest.approx(0.25, rel=0.02)
    assert sat["ci95"][0] <= sat["per_sample_mean"] <= sat["ci95"][1]


def test_saturation_search_rejects_unroutable():
    import repro.core.graph as G

    # tornado pairs on 4 routers: (0,2),(1,3),(2,0),(3,1) — none reachable
    # over the single 0-1 edge, so nothing routes and nothing can saturate
    g = G.Graph(n=4, edges=np.array([(0, 1)]), name="tiny")
    with pytest.raises(ValueError):
        saturation_search(g, "tornado", use_kernel=False)


def test_saturation_search_scales_with_capacity():
    g = _ring(16)
    s1 = saturation_search(g, "tornado", capacity=1.0, use_kernel=False)
    s2 = saturation_search(g, "tornado", capacity=2.0, use_kernel=False)
    assert s2["per_sample_mean"] == pytest.approx(2 * s1["per_sample_mean"])


# ------------------------------------------------------------------ grid

@pytest.fixture(scope="module")
def small_grid():
    return traffic_failure_grid(
        families=["jellyfish", "hypercube"], max_routers=40,
        scenarios=("uniform", "tornado"), rates=(0.0, 0.05),
        samples=8, seed=0, use_kernel=False, bootstrap=100)


def test_grid_schema_and_check(small_grid):
    assert check_grid(small_grid) == []
    table = format_grid_table(small_grid)
    assert "tornado" in table and "jellyfish" in table


def test_grid_rate0_bit_equal_to_unfailed_baseline(small_grid):
    from repro.core.sweep import equal_cost_graphs

    graphs, _ = equal_cost_graphs(["jellyfish", "hypercube"], None,
                                  ("slimfly", 2000), 40)
    by_name = {(g.meta["spec"].family if g.meta.get("spec") else g.name): g
               for g in graphs}
    for fam in small_grid["families"]:
        g = by_name[fam["family"]]
        for row in fam["scenarios"]:
            spec = TrafficSpec.parse(row["scenario"])
            base = evaluate_traffic_batch(
                g, spec.batch(g, samples=8)[:1], use_kernel=False)
            cell = row["cells"][0]
            assert cell["rate"] == 0.0
            for key in traffic.TRAFFIC_METRICS:
                # bit-equal: same single-matrix call as the baseline
                assert cell["metrics"][key]["value"] == float(base[key][0])
                assert fam["baseline"][row["scenario"]][key] == \
                    float(base[key][0])


def test_check_grid_catches_corruption(small_grid):
    import copy

    bad = copy.deepcopy(small_grid)
    bad["families"][0]["scenarios"][0]["cells"][0]["metrics"][
        "max_link_load"]["value"] = float("nan")
    assert any("not finite" in m for m in check_grid(bad))
    bad2 = copy.deepcopy(small_grid)
    bad2["families"][0]["baseline"][
        bad2["families"][0]["scenarios"][0]["scenario"]]["tput_lb"] = 99.0
    assert any("baseline" in m for m in check_grid(bad2))


# -------------------------------------------------- entry-point normalizers

def test_max_concurrent_flow_accepts_spec():
    g = _ring(12)
    r1 = max_concurrent_flow(g, "uniform", eps=0.3, max_rounds=20,
                             use_kernel=False)
    r2 = max_concurrent_flow(g, TrafficSpec.parse("uniform").matrix(g),
                             eps=0.3, max_rounds=20, use_kernel=False)
    assert r1["commodities"] == r2["commodities"]
    assert r1["throughput"] == pytest.approx(r2["throughput"])


def test_evaluate_workload_accepts_spec():
    g = topo.make("jellyfish", n=30, r=6, seed=0)
    rep = workload.evaluate_workload(g, "permutation:flows=128,seed=5")
    assert rep["flows"] == 128
    assert rep["workload"] == "permutation:flows=128,seed=5"


def test_sweep_traffic_column():
    from repro.core.sweep import format_table, sweep

    res = sweep(families=["jellyfish"], max_routers=40, use_kernel=False,
                traffic="tornado")
    row = res["rows"][0]
    assert row["traffic"] == "tornado"
    assert row["traffic_max_load"] > 0
    assert "tr-tput" in format_table(res)

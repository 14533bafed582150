"""The analysis seams under tracing: each stage of a design point splits
into leaf spans ``<stage>.host`` / ``.h2d`` / ``.wait`` / ``.d2h``, every
transfer counts its bytes, and tracing changes no result.

On Slim Fly q=5 (n = 50 routers, padded to p = 128 for the device loops)
the bytes follow from the operand shapes alone: the wavefront uploads the
padded float32 adjacency and downloads padded dist and mult; the slack
counts upload the padded adjacency and dist once, run levels 1 ..
diameter + 2 as one device program, and download mult, plus1 and plus2 at
n x n, the report's (6, n) row reductions and the one-byte exact flag; the
histogram
uploads dist and downloads 65 int32 bins; spectral uploads two
Laplacians; ECMP uploads padded dist, mult and adjacency and downloads the
padded loads. The traffic engine uploads, per pass of a stacked chunk,
padded dist, mult, adjacency and demand and downloads the padded loads.
"""
import numpy as np
import pytest

from repro import obs
from repro.core import topology as T
from repro.core.analysis import AnalysisEngine
from repro.core.analysis.wavefront import pad_block

F32 = 4
LEAVES = {
    None: {"topology.host", "distances.host", "wavefront.host",
           "wavefront.h2d", "wavefront.wait", "wavefront.d2h", "slack.host",
           "slack.h2d", "slack.wait", "slack.d2h", "diversity.host",
           "spectral.host", "spectral.h2d", "histograms.h2d",
           "histograms.wait", "histograms.d2h"},
    ("distances", "comparison"): {
        "topology.host", "distances.host", "wavefront.host", "wavefront.h2d",
        "wavefront.wait", "wavefront.d2h", "ecmp.host", "ecmp.h2d",
        "ecmp.wait", "ecmp.d2h"},
}


def _bytes(stages, n, p):
    """(h2d by name, d2h by name) the stages move at n routers."""
    nn, pp = n * n * F32, p * p * F32
    h2d = {"adjacency": pp}
    d2h = {"wavefront_dist": pp, "wavefront_mult": pp}
    if stages is None:
        h2d.update(slack_adjacency=pp, slack_dist=pp, histogram_dist=nn,
                   laplacian=2 * nn)
        d2h.update(slack_mult=nn, slack_plus1=nn, slack_plus2=nn,
                   slack_rows=6 * n * F32, slack_exact=1,
                   histogram_counts=65 * 4)
    else:
        h2d.update(ecmp_dist=pp, ecmp_mult=pp, ecmp_adjacency=pp)
        d2h.update(ecmp_loads=pp)
    return h2d, d2h


@pytest.fixture
def traced():
    obs.disable()
    obs.reset()
    obs.meters.reset()
    obs.enable()
    yield
    obs.disable()
    obs.reset()
    obs.meters.reset()


@pytest.mark.parametrize("stages", [None, ("distances", "comparison")])
def test_report_splits_into_leaf_spans_with_exact_bytes(stages, traced):
    g = T.make("slimfly", q=5)
    AnalysisEngine(g, mesh=None, seed=3).report(stages)
    events = [ev for ev in obs.events() if ev["ph"] == "X"]
    names = {ev["name"] for ev in events}
    leaves = {nm for nm in names
              if nm.rpartition(".")[2] in ("host", "h2d", "d2h", "wait")}
    assert leaves == LEAVES[stages]

    (report,) = [ev for ev in events if ev["name"] == "analysis.report"]
    assert report["args"]["seed"] == 3
    n, p = g.n, pad_block(g.n)[0]
    h2d, d2h = _bytes(stages, n, p)
    total = {k: sum(ev["args"].get(k, 0) for ev in events)
             for k in ("h2d_bytes", "d2h_bytes")}
    assert total == {"h2d_bytes": sum(h2d.values()),
                     "d2h_bytes": sum(d2h.values())}
    snap = obs.snapshot()
    assert {k: snap[f"h2d_bytes.{k}"]["value"] for k in h2d} == h2d
    assert {k: snap[f"d2h_bytes.{k}"]["value"] for k in d2h} == d2h
    if stages is None:
        # the slack counts' whole level loop is one device program
        diameter = 2
        (wait,) = [ev for ev in events if ev["name"] == "slack.wait"]
        assert wait["args"]["levels"] == diameter + 2
        assert wait["args"]["products"] == 2 * (diameter + 2)
    # bytes sit on the transfer spans alone
    for ev in events:
        part = ev["name"].rpartition(".")[2]
        assert ("h2d_bytes" in ev["args"]) == (part == "h2d")
        assert ("d2h_bytes" in ev["args"]) == (part == "d2h")


@pytest.mark.parametrize("stages", [None, ("distances", "comparison")])
def test_tracing_changes_no_result(stages):
    g = T.make("slimfly", q=5)
    plain = AnalysisEngine(g, mesh=None, seed=5).report(stages)
    obs.enable()
    try:
        seen = AnalysisEngine(g, mesh=None, seed=5).report(stages)
    finally:
        obs.disable()
        obs.reset()
        obs.meters.reset()
    assert seen == plain


def test_traffic_stage_splits_into_leaf_spans_with_exact_bytes(traced):
    from repro.core.traffic import evaluate_traffic_batch

    g = T.make("slimfly", q=5)
    samples, chunk, diameter = 3, 2, 2
    evaluate_traffic_batch(g, "server_permutation:samples=3,seed=2",
                           mask_chunk=chunk)
    events = [ev for ev in obs.events() if ev["ph"] == "X"]
    (scenario,) = [ev for ev in events if ev["name"] == "traffic.scenario"]
    lo, hi = scenario["ts"], scenario["ts"] + scenario["dur"]
    inside = [ev for ev in events if ev is not scenario
              and lo <= ev["ts"] and ev["ts"] + ev["dur"] <= hi]
    assert {ev["name"] for ev in inside} == {
        "traffic.host", "traffic.h2d", "traffic.wait", "traffic.d2h"}
    # every traffic transfer and wait nests under the scenario; demand
    # generation runs before it
    assert all(ev in inside for ev in events
               if ev["name"] in ("traffic.h2d", "traffic.wait",
                                 "traffic.d2h"))
    (demand,) = [ev for ev in events if ev["name"] == "demand.host"]
    assert demand["ts"] + demand["dur"] <= lo

    # once per call the int32 flat indices of the L directed-link cells up
    # and dist, mult and adjacency, each a (p, p) float32 matrix; per pass
    # only the (chunk, n, n) float32 demand up, and only the loads on the L
    # link cells down
    p = pad_block(g.n, batched=True)[0]
    links = np.count_nonzero(g.adjacency_dense())
    h2d = sum(ev["args"].get("h2d_bytes", 0) for ev in inside)
    d2h = sum(ev["args"].get("d2h_bytes", 0) for ev in inside)
    assert h2d == 3 * p * p * F32 + samples * g.n * g.n * F32 + links * 4
    assert d2h == samples * links * F32
    snap = obs.snapshot()
    assert snap["d2h_bytes.traffic_link_loads"]["value"] == d2h
    assert snap["h2d_bytes.traffic_link_cells"]["value"] == links * 4
    assert "d2h_bytes.traffic_loads" not in snap
    assert [ev["args"]["what"] for ev in inside
            if ev["name"] == "traffic.h2d"] == [
        "traffic_link_cells", "traffic_dist", "traffic_mult",
        "traffic_adjacency"] + ["traffic_demand"] * 2
    assert scenario["args"]["graph_uploads"] == 1
    assert [ev["args"]["what"] for ev in inside
            if ev["name"] == "traffic.d2h"] == ["traffic_link_loads"] * 2
    assert scenario["args"]["link_cells"] == links
    assert len([ev for ev in inside if ev["name"] == "traffic.wait"]) == 2
    assert scenario["args"]["diameter"] == diameter
    assert scenario["args"]["products"] == 2 * diameter * samples

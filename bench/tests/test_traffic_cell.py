"""The traffic cell on the CPU at a small Slim Fly (q=5, 50 routers, 200
servers) and fat tree (k=6, the control): a sound run is correct, its
reference draws the program's demand, and each planted fault, and the
lower-precision control, comes out not correct.

Faults:

* ``halved``      the ECMP pass returns half of every load;
* ``transposed``  the pattern hands back each demand matrix transposed
                  (row and column sums still agree);
* ``stale``       the engine returns its first result for every later
                  call.
"""
import importlib

import numpy as np
import pytest

from conftest import fattree_config, run_small, slimfly_config, small_cell

CELL = "slimfly_q41.permutation"


def _cell():
    return small_cell(CELL, slimfly_config(5))


def plant(fault: str, monkeypatch) -> None:
    from repro.core.routing import assign
    from repro.core.traffic import scenarios, spec

    if fault == "halved":
        original = assign.ecmp_demand_loads
        monkeypatch.setattr(assign, "ecmp_demand_loads",
                            lambda *a, **k: original(*a, **k) * 0.5)
    elif fault == "transposed":
        original = spec._REGISTRY["server_permutation"]
        monkeypatch.setitem(
            spec._REGISTRY, "server_permutation",
            lambda *a, **k: np.ascontiguousarray(
                original(*a, **k).transpose(0, 2, 1)))
    else:
        original = scenarios.evaluate_traffic_batch
        first = []

        def stale(*args, **kwargs):
            if not first:
                first.append(original(*args, **kwargs))
            return first[0]

        monkeypatch.setattr(scenarios, "evaluate_traffic_batch", stale)


def test_sound_run_is_correct(fresh_programs):
    cell = _cell()
    out = run_small(cell)
    assert out["correct"], out["compared"]
    assert set(out["compared"]) == set(cell.limits)
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"design_point_s", "setup_s"}


def test_traced_run_reports_the_traffic_spans(fresh_programs):
    out = run_small(_cell(), trace=True)
    assert out["correct"], out["compared"]
    assert {"generate_s", "demand_s", "scenario_s", "host_s", "transfer_s",
            "transfer_gb", "device_wait_s", "jit_s"} <= set(out["metrics"])


@pytest.mark.parametrize("config", [
    slimfly_config(5), dict(fattree_config(4), edge_concentration=2)])
def test_reference_draws_the_programs_demand(config):
    from bench.reference import patterns
    from repro.core import topology
    from repro.core.traffic import TrafficSpec

    g = topology.make(config["family"], **config["params"])
    counts = patterns.server_counts(config)
    np.testing.assert_array_equal(counts, g.server_counts())
    for seed in (0, 3_000_000_019):
        got = TrafficSpec.parse(
            f"server_permutation:rate=0.5,samples=4,seed={seed}").batch(g)
        want = patterns.server_permutation(counts, 0.5, seed, 4)
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("fault", ["halved", "transposed", "stale"])
def test_planted_fault_is_not_correct(fault, monkeypatch, fresh_programs):
    plant(fault, monkeypatch)
    out = run_small(_cell())
    assert not out["correct"], out["compared"]


def test_lower_precision_control_is_not_correct(fresh_programs):
    from bench import control

    # at q=5 every multiplicity and share is exact in one bf16 pass; the
    # k=6 fat tree splits pairs over 9 paths, and 1/9 is not
    control.lower_precision(1)
    try:
        out = run_small(small_cell(
            CELL, dict(fattree_config(6), edge_concentration=3)))
    finally:
        from repro.kernels import semiring
        importlib.reload(semiring)
    assert not out["correct"], out["compared"]
    assert out["compared"]["loads_rel_err"]["value"] > \
        out["compared"]["loads_rel_err"]["limit"]

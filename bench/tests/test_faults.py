"""Whole runs on the CPU at small sizes, past the harness's look for a
chip: a sound run comes out correct, and each fault a cell can have,
planted in the timed path, comes out not correct.

Faults (the cells run on one chip, so there is no exchange between chips
to leave out):

* ``altered``    one answer altered where it is produced: a multiplicity
                 of the wavefront;
* ``unchanged``  a step that returns its state unchanged: the counting
                 product hands back its left operand (slack counts), the
                 ECMP pass returns its zero start;
* ``half``       half of the batch left out and the rest reused: half the
                 source rows of the wavefront.
"""
import numpy as np
import pytest

from conftest import fattree_config, run_small, slimfly_config, small_cell

CELLS = {
    "report": ("slimfly_q41.report", lambda: slimfly_config(5)),
    "compare": ("fattree_k74.compare", lambda: fattree_config(4)),
}


def _wrap(monkeypatch, module, name, change):
    original = getattr(module, name)

    def faulty(*args, **kwargs):
        return change(original(*args, **kwargs), args)

    monkeypatch.setattr(module, name, faulty)


def plant(fault: str, cell: str, monkeypatch) -> None:
    from repro.core.analysis import wavefront
    from repro.core.routing import assign

    if fault == "altered":
        def bump(out, args):
            dist, mult = out
            mult = np.array(mult)
            mult[0, 1] += 1
            return dist, mult
        _wrap(monkeypatch, wavefront, "wavefront_dist_mult", bump)
    elif fault == "unchanged" and cell == "report":
        original = assign.count_product
        monkeypatch.setattr(assign, "count_product",
                            lambda use_kernel: lambda a, b: np.asarray(
                                original(use_kernel)(a, b)) * 0 + a)
    elif fault == "unchanged":
        _wrap(monkeypatch, wavefront, "ecmp_loads_device",
              lambda loads, args: loads * 0)
    else:
        def halve(out, args):
            dist, mult = (np.array(x) for x in out)
            h = len(dist) // 2
            dist[h:2 * h], mult[h:2 * h] = dist[:h], mult[:h]
            return dist, mult
        _wrap(monkeypatch, wavefront, "wavefront_dist_mult", halve)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_sound_run_is_correct(cell, fresh_programs):
    name, config = CELLS[cell]
    out = run_small(small_cell(name, config()))
    assert out["correct"], out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["device"]["platform"] == "cpu"
    assert set(out["metrics"]) == {
        m["name"] for m in small_cell(name, config()).end_to_end}


@pytest.mark.parametrize("fault", ["altered", "unchanged", "half"])
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_planted_fault_is_not_correct(cell, fault, monkeypatch,
                                      fresh_programs):
    name, config = CELLS[cell]
    plant(fault, cell, monkeypatch)
    out = run_small(small_cell(name, config()))
    assert not out["correct"], out["compared"]


@pytest.mark.parametrize("cell,config,passes", [
    ("slimfly_q41.report", lambda: fattree_config(10), 1),
    ("fattree_k74.compare", lambda: fattree_config(6), 3),
])
def test_lower_precision_control_is_not_correct(cell, config, passes,
                                                fresh_programs):
    from bench import control

    control.lower_precision(passes)
    try:
        out = run_small(small_cell(cell, config()))
    finally:
        from repro.kernels import semiring
        import importlib
        importlib.reload(semiring)
    assert not out["correct"], out["compared"]


def test_traced_run_reports_per_layer_metrics(fresh_programs):
    name, config = CELLS["report"]
    out = run_small(small_cell(name, config()), trace=True)
    assert out["correct"]
    assert {"generate_s", "distances_s", "multiplicities_s"} <= set(
        out["metrics"])
    assert out["device"]["window_s"] > 0
    assert "idle_gaps" in out["breakdown"]

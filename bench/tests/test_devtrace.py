"""Trace arithmetic and roofline readers on small event lists and on a
small trace recorded on a TPU v5e (``data/tiny_v5e.xplane.pb``: three
fused frontier steps and three XLA dots of 256 x 256, inside one
``bench.window`` annotation)."""
import pathlib

import pytest

from bench import devtrace, harness
from conftest import PEAKS

TINY = pathlib.Path(__file__).parent / "data" / "tiny_v5e.xplane.pb"
FRONTIER = ('%body.3 = f32[256,256]{1,0:T(8,128)} custom-call(f32[256,256]'
            '{1,0:T(8,128)} %f.1, f32[256,256]{1,0:T(8,128)} %a.1, '
            'f32[256,256]{1,0:T(8,128)} %d.1), '
            'custom_call_target="tpu_custom_call", operand_layout_constraints')
COUNTING = ('%_count_jit.1 = f32[256,256]{1,0:T(8,128)} custom-call(f32['
            '256,256]{1,0:T(8,128)S(1)} %pad.0, f32[256,256]{1,0:T(8,128)S(1)}'
            ' %pad.2), custom_call_target="tpu_custom_call", operand_layout')

EVENTS = [("a", 0, 10), ("b", 5, 10), ("a", 30, 5), ("c", 50, 20)]


def test_busy_is_the_union_of_op_intervals():
    assert devtrace.merged(EVENTS, 0, 100) == [(0, 15), (30, 35), (50, 70)]
    assert devtrace.busy_ns(EVENTS, 0, 100) == 15 + 5 + 20
    assert devtrace.busy_ns(EVENTS, 8, 60) == 7 + 5 + 10   # clipped


def test_time_by_name():
    assert devtrace.time_by_name(EVENTS) == {"a": 15, "b": 10, "c": 20}


def test_idle_gaps_go_to_the_innermost_host_span():
    host = [("bench.window", 0, 100), ("analysis.x", 20, 20)]
    gaps = devtrace.idle_gaps(EVENTS, host, 0, 100)
    # gaps: 15-30, 35-50, 70-100; analysis.x covers 20-40
    assert gaps == {"bench.window": 5 + 10 + 30, "analysis.x": 10 + 5}
    assert sum(gaps.values()) + devtrace.busy_ns(EVENTS, 0, 100) == 100


def _ctx(ops, spans, routers):
    return harness.Context(config={"routers": routers}, units=1,
                           window_s=1.0, setup_s=0.0, spans=spans,
                           peaks=PEAKS, device_ops=ops, trace_lo=0,
                           trace_hi=10 ** 12)


def test_frontier_roofline_counts_levels_and_bounds_by_peak():
    read = harness.metric_reader("frontier_step_roofline")
    n = 4096
    spans = [("wavefront.dist_mult", 0, 1, {"levels": 3})]
    # one second of kernel time for 3 products of 2 n^3 at the int8 peak
    ops = [(FRONTIER, 0, 10 ** 9), (COUNTING, 0, 10 ** 9)]
    want = 100 * max(3 * 2 * n ** 3 / PEAKS["int8_ops_per_s"],
                     3 * 16 * n * n / PEAKS["hbm_bytes_per_s"])
    assert read(_ctx(ops, spans, n)) == pytest.approx(want)
    assert read(_ctx([], spans, n)) is None       # nothing to read


def test_counting_roofline_products_follow_the_stages():
    from bench.harness import metric_reader

    read = metric_reader("counting_roofline")
    spans = [("wavefront.dist_mult", 0, 1, {"levels": 3}),
             ("analysis.multiplicities", 0, 1, {})]
    ops = [(COUNTING, 0, 10 ** 9), (FRONTIER, 0, 10 ** 9)]
    n = 1000
    products = 2 * (2 + 2)            # walks and bounces, levels 1 .. d+2
    want = 100 * max(products * 2 * n ** 3 / PEAKS["int8_ops_per_s"],
                     products * 12 * n * n / PEAKS["hbm_bytes_per_s"])
    assert read(_ctx(ops, spans, n)) == pytest.approx(want)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        harness.peaks_for("TPU v99")


def test_kernel_operands_of_hlo_event_names():
    assert devtrace.tpu_kernel_operands(FRONTIER) == 3
    assert devtrace.tpu_kernel_operands(COUNTING) == 2
    assert devtrace.tpu_kernel_operands("%fusion = f32[2] fusion(..)") is None


def test_recorded_trace():
    tr = devtrace.read_trace(TINY)
    assert list(tr["devices"]) == [0]
    (window,) = [h for h in tr["host"] if h[0] == "bench.window"]
    lo, hi = window[1], window[1] + window[2]
    ops = tr["devices"][0]
    busy = devtrace.busy_ns(ops, lo, hi)
    assert 0 < busy < hi - lo
    read = harness.metric_reader("frontier_step_roofline")
    ctx = harness.Context(
        config={"routers": 256}, units=3, window_s=(hi - lo) / 1e9,
        setup_s=0.0, spans=[("wavefront.dist_mult", 0, 1, {"levels": 1})] * 3,
        peaks=PEAKS, device_ops=ops, trace_lo=lo, trace_hi=hi)
    kernels = [e for e in ops if read.__globals__["is_kernel"](e[0])]
    assert len(kernels) == 3
    share = read(ctx)
    assert 0 < share <= 100
    gaps = devtrace.idle_gaps(ops, tr["host"], lo, hi)
    assert sum(gaps.values()) + busy == hi - lo

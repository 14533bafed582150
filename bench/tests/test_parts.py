"""The readers of the program's leaf spans (``<stage>.host``, ``.h2d``,
``.d2h``, ``.wait``) and of their ``h2d_bytes``, ``d2h_bytes`` and
``jit_s`` attributes, on small synthetic span lists."""
import pytest

from bench import harness

S = 10 ** 9
#: two design points' worth of spans, with the benchmark's own around them
SPANS = [
    ("bench.window", 0, 10 * S, {}),
    ("bench.generate", 0, S // 2, {}),
    ("analysis.report", 0, 4 * S, {"seed": 7}),
    ("topology.host", 0, S // 2, {}),
    ("slack.host", 0, S, {}),
    ("slack.h2d", 0, S // 5, {"h2d_bytes": 45_000_000}),
    ("slack.wait", 0, 3 * S // 10, {}),
    ("slack.d2h", 0, S // 10, {"d2h_bytes": 5_000_000}),
    ("ecmp.h2d", 0, S // 5, {"h2d_bytes": 150_000_000}),
    ("wavefront.dist_mult", 0, S, {"levels": 3, "jit_s": 0.05,
                                   "compiles": 1}),
    ("analysis.spectral", 0, S // 2, {"jit_s": 0.25, "compiles": 2}),
]
#: what a program without leaf spans leaves: stage spans only
STAGES_ONLY = [("bench.window", 0, 10 * S, {}),
               ("analysis.report", 0, 4 * S, {}),
               ("wavefront.dist_mult", 0, S, {"levels": 3,
                                              "h2d_bytes": 47_775_744})]


def _ctx(spans, units=2):
    return harness.Context(config={"routers": 10}, units=units, window_s=10.0,
                           setup_s=0.0, spans=spans)


@pytest.mark.parametrize("name, want", [
    ("host_s", (0.5 + 1.0) / 2),
    ("transfer_s", (0.2 + 0.1 + 0.2) / 2),
    ("transfer_gb", (45e6 + 5e6 + 150e6) / 1e9 / 2),
    ("device_wait_s", 0.3 / 2),
    ("jit_s", (0.05 + 0.25) / 2),
])
def test_reader_sums_its_part_per_design_point(name, want):
    read = harness.metric_reader(name)
    assert read(_ctx(SPANS)) == pytest.approx(want)
    assert read(_ctx(SPANS, units=0)) is None


@pytest.mark.parametrize("name", ["host_s", "transfer_s", "transfer_gb",
                                  "device_wait_s", "jit_s"])
def test_reader_without_leaf_spans_reads_nothing(name):
    read = harness.metric_reader(name)
    assert read(_ctx([])) is None
    assert read(_ctx(STAGES_ONLY)) is None

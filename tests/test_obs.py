"""The observability layer itself: tracing, meters and the report CLI.

The substrate contracts: disabled tracing returns the shared no-op span
and records nothing; enabled spans nest (per thread), export as Chrome
trace-event JSON with the meters snapshot in ``otherData``; ``record_h2d``
is inert when tracing is off. The jitted engines' disabled-path jaxpr
identity lives in ``test_wavefront.py`` / ``test_distributed.py``.
"""
import json
import os
import pathlib
import subprocess
import sys
import threading

import pytest

from repro import obs
from repro.obs import report as obs_report

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(autouse=True)
def _clean_tracer():
    """Every test starts and ends with a disabled, empty tracer/registry."""
    obs.disable()
    obs.reset()
    obs.meters.reset()
    yield
    obs.disable()
    obs.reset()
    obs.meters.reset()


# -- trace substrate ----------------------------------------------------------

def test_disabled_span_is_the_null_singleton_and_records_nothing():
    sp = obs.span("anything", attr=1)
    assert sp is obs.NULL_SPAN and not sp
    with sp as inner:
        inner.set(more=2).inc("h2d_bytes", 10)
    assert obs.current() is obs.NULL_SPAN
    assert obs.events() == []
    obs.instant("point")  # gated too
    obs.counter_sample("track", v=1)
    assert obs.events() == []


def test_span_nesting_attrs_and_export_structure(tmp_path):
    obs.enable()
    with obs.span("outer", cat="t", routers=64) as sp:
        assert obs.current() is sp
        with obs.span("inner", cat="t"):
            pass
        sp.set(levels=3)
        sp.inc("h2d_bytes", 100)
        sp.inc("h2d_bytes", 28)
    obs.instant("mark", round=1)
    events = obs.events()
    by_name = {ev["name"]: ev for ev in events}
    assert by_name["outer"]["ph"] == "X"
    assert by_name["outer"]["args"] == {"routers": 64, "levels": 3,
                                        "h2d_bytes": 128}
    # inner is contained in outer on the same thread track
    out, inn = by_name["outer"], by_name["inner"]
    assert out["tid"] == inn["tid"]
    assert out["ts"] <= inn["ts"]
    assert inn["ts"] + inn["dur"] <= out["ts"] + out["dur"]
    assert by_name["mark"]["ph"] == "i"

    path = tmp_path / "trace.json"
    doc = obs.export(str(path))
    loaded = json.loads(path.read_text())
    assert loaded["traceEvents"] == doc["traceEvents"]
    assert "meters" in loaded["otherData"]
    assert loaded["displayTimeUnit"] == "ms"


def test_span_records_error_attribute():
    obs.enable()
    with pytest.raises(ValueError):
        with obs.span("boom"):
            raise ValueError("x")
    (ev,) = obs.events()
    assert ev["args"]["error"] == "ValueError"


def test_span_summary_aggregates_by_name():
    obs.enable()
    for _ in range(3):
        with obs.span("stage.a"):
            pass
    with obs.span("stage.b"):
        pass
    summary = obs.span_summary()
    assert summary["stage.a"]["count"] == 3
    assert summary["stage.b"]["count"] == 1
    assert summary["stage.a"]["total_ms"] >= 0.0


def test_threaded_spans_land_on_their_own_tracks():
    obs.enable()
    barrier = threading.Barrier(4)  # all alive at once: distinct idents

    def work(i):
        with obs.span(f"thread.{i}"):
            with obs.span(f"thread.{i}.inner"):
                barrier.wait(timeout=30)

    threads = [threading.Thread(target=work, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    events = obs.events()
    assert len(events) == 8
    tids = {ev["tid"] for ev in events if not ev["name"].endswith("inner")}
    assert len(tids) == 4  # one Perfetto track per thread
    for i in range(4):
        pair = [ev for ev in events if ev["name"].startswith(f"thread.{i}")]
        assert pair[0]["tid"] == pair[1]["tid"]


def test_env_flag_enables_and_auto_exports(tmp_path):
    # REPRO_TRACE=<path> enables tracing and exports there at exit;
    # obs is stdlib-only so the subprocess is cheap
    out = tmp_path / "auto.json"
    code = ("from repro import obs\n"
            "assert obs.enabled()\n"
            "with obs.span('env.root'):\n"
            "    pass\n")
    subprocess.run(
        [sys.executable, "-c", code], check=True, timeout=60,
        env={**os.environ, "PYTHONPATH": str(REPO / "src"),
             "REPRO_TRACE": str(out)})
    doc = json.loads(out.read_text())
    assert [ev["name"] for ev in doc["traceEvents"]] == ["env.root"]
    # REPRO_TRACE=0 stays disabled
    res = subprocess.run(
        [sys.executable, "-c",
         "from repro import obs; print(obs.enabled())"],
        check=True, timeout=60, capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": str(REPO / "src"),
             "REPRO_TRACE": "0"})
    assert res.stdout.strip() == "False"


# -- meters -------------------------------------------------------------------

def test_meter_registry_and_types():
    obs.counter("c").add().add(2)
    obs.gauge("g").set(5.0)
    obs.gauge("g").set(2.0)
    snap = obs.snapshot()
    assert snap["c"] == {"type": "counter", "value": 3}
    assert snap["g"] == {"type": "gauge", "value": 2.0, "max": 5.0}
    with pytest.raises(TypeError):
        obs.gauge("c")  # name already registered as a counter


def test_rss_samplers_report_positive_numbers():
    assert obs.rss_mb() > 0
    assert obs.peak_rss_mb() > 0
    sampled = obs.sample_process("t")
    assert sampled["rss_mb"] > 0 and sampled["peak_rss_mb"] > 0
    assert obs.snapshot()["t.rss_mb"]["value"] == sampled["rss_mb"]


def test_record_h2d_gated_on_tracing():
    obs.record_h2d(4096, "upload")  # disabled: must not even register
    assert "h2d_bytes" not in obs.snapshot()
    obs.enable()
    with obs.span("stage") as sp:
        obs.record_h2d(4096, "upload")
        obs.record_h2d(1024)
    assert obs.snapshot()["h2d_bytes"]["value"] == 5120
    assert obs.snapshot()["h2d_bytes.upload"]["value"] == 4096
    assert sp.args["h2d_bytes"] == 5120
    # the Perfetto counter track got samples too
    assert any(ev["ph"] == "C" and ev["name"] == "h2d_bytes"
               for ev in obs.events())


def test_record_d2h_gated_on_tracing():
    obs.record_d2h(4096, "download")  # disabled: must not even register
    assert "d2h_bytes" not in obs.snapshot()
    obs.enable()
    with obs.span("stage") as sp:
        with obs.span("stage.d2h") as leaf:
            obs.record_d2h(4096, "download")
        obs.record_d2h(1024)
    snap = obs.snapshot()
    assert snap["d2h_bytes"]["value"] == 5120
    assert snap["d2h_bytes.download"]["value"] == 4096
    assert "h2d_bytes" not in snap
    # bytes land on the innermost live span only
    assert leaf.args["d2h_bytes"] == 4096 and sp.args["d2h_bytes"] == 1024
    assert any(ev["ph"] == "C" and ev["name"] == "d2h_bytes"
               for ev in obs.events())


# -- JAX hooks: JIT seconds and profiler annotations -----------------------------

def test_jit_listener_charges_the_innermost_span():
    import jax
    import jax.numpy as jnp

    x = jnp.arange(11.0)

    @jax.jit
    def fresh(v):  # a new function: its first call traces and compiles
        return jnp.sin(v) * 3.0 + 1.0

    obs.enable()
    with obs.span("outer") as outer:
        with obs.span("inner") as inner:
            fresh(x).block_until_ready()
        fresh(x).block_until_ready()  # cached: no JIT event
    assert inner.args["jit_s"] > 0 and inner.args["compiles"] >= 1
    assert "jit_s" not in outer.args and "compiles" not in outer.args
    assert inner.args["jit_s"] <= inner.dur / 1e9

    # off: a compile inside a span left open adds nothing to it
    with obs.span("open") as sp:
        obs.disable()
        jax.jit(lambda v: v * 5.0 - 2.0)(x).block_until_ready()
        obs.enable()
    assert "jit_s" not in sp.args and "compiles" not in sp.args


def test_nested_jit_events_count_once():
    # JAX reports a jit traced inside another's trace under its own event,
    # before the outer one; the outer event's seconds then hold the inner
    tr = obs.Tracer(enabled=True)
    assert tr._fresh_jit_seconds(1.2, 1.5) == pytest.approx(0.3)
    assert tr._fresh_jit_seconds(1.0, 3.0) == pytest.approx(1.7)
    assert tr._fresh_jit_seconds(3.5, 4.0) == pytest.approx(0.5)


def test_spans_land_in_the_profiler_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    obs.enable()
    with jax.profiler.trace(str(tmp_path)):
        with obs.span("probe.outer"):
            with obs.span("probe.host"):
                jnp.ones(5).block_until_ready()
    (path,) = tmp_path.rglob("*.xplane.pb")
    data = ProfileData.from_file(str(path))
    host = {ev.name: ev.duration_ns for plane in data.planes
            if not plane.name.startswith("/device:")
            for line in plane.lines for ev in line.events}
    assert {"probe.outer", "probe.host"} <= set(host)
    assert host["probe.outer"] >= host["probe.host"]


# -- report CLI ---------------------------------------------------------------

def _make_trace(tmp_path) -> str:
    obs.enable()
    with obs.span("root", cat="t"):
        with obs.span("child", cat="t") as sp:
            sp.inc("h2d_bytes", 2 << 20)
        with obs.span("child", cat="t"):
            pass
    path = tmp_path / "t.json"
    obs.export(str(path))
    return str(path)


def test_report_tree_aggregate_and_coverage(tmp_path):
    path = _make_trace(tmp_path)
    events, other = obs_report.load_events(path)
    roots = obs_report.build_tree(events)
    assert [n["event"]["name"] for n in roots] == ["root"]
    assert len(roots[0]["children"]) == 2
    rows = obs_report.aggregate(roots)
    child = next(r for r in rows if r["name"] == "child")
    assert child["count"] == 2 and child["depth"] == 1
    assert child["h2d_bytes"] == 2 << 20
    assert obs_report.coverage(events, roots) > 0.9
    text = obs_report.format_report(events, other)
    assert "root coverage" in text and "child" in text


def test_report_cli_exit_codes(tmp_path, capsys):
    path = _make_trace(tmp_path)
    assert obs_report.main([path]) == 0
    assert "root" in capsys.readouterr().out
    empty = tmp_path / "empty.json"
    empty.write_text(json.dumps({"traceEvents": []}))
    assert obs_report.main([str(empty)]) == 2
    assert obs_report.main([str(tmp_path / "missing.json")]) == 2
    garbage = tmp_path / "garbage.json"
    garbage.write_text("not json")
    assert obs_report.main([str(garbage)]) == 2

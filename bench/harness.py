"""Run one cell of ``BENCHMARK.json`` once: set-up, a measured window of
whole calls, the comparison with the plain reference, one result line.

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``,
its configuration at the ``file`` the configuration entry names, its mix at
``bench/mixes/<traffic>.json``, the mix's kind at ``bench/kinds/<kind>.py``,
the reference fabric at ``bench/reference/fabrics/<family>.py``, its limits
at ``bench/limits/<cell>.json`` and each metric's reader at
``bench/metrics/<metric>.py``. Adding a cell, a configuration, a mix, a
kind, a family or a metric adds files and entries and edits none.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import shutil
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"


class NoDevice(RuntimeError):
    """The run found no TPU, or fewer chips than the cell asks for."""


def load_json(path: pathlib.Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


def applies(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    limits: Dict[str, float]
    end_to_end: List[dict]
    per_layer: List[dict]


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    return Cell(
        name=name, chips=int(w["chips"]),
        config=load_json(root / configs[w["config"]]["file"]),
        mix=load_json(root / "bench" / "mixes" / f"{w['traffic']}.json"),
        limits=load_json(root / "bench" / "limits" / f"{name}.json"),
        end_to_end=[m for m in bench["end_to_end"] if applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if applies(m, name)])


def metric_reader(name: str) -> Callable:
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


# -- what the metric readers see ----------------------------------------------

@dataclasses.dataclass
class Context:
    """What a metric reader reads. Spans are (name, start ns, duration ns,
    attributes) on the host's perf_counter clock, inside the window; the
    trace's device ops are on the profiler's clock, with ``trace_lo`` and
    ``trace_hi`` bounding the traced window there."""

    config: dict
    units: int
    window_s: float
    setup_s: float
    spans: list
    peaks: Optional[dict] = None
    device_ops: Optional[list] = None
    trace_lo: int = 0
    trace_hi: int = 0
    busy_s: Optional[float] = None

    def span_seconds(self, name: str) -> float:
        return sum(d for n, _, d, _ in self.spans if n == name) / 1e9

    def span_attrs(self, name: str) -> List[dict]:
        return [a for n, _, _, a in self.spans if n == name]


class Spans:
    """The benchmark's own spans, kept in memory: a context manager per
    span that also marks it in the profiler's trace when one runs."""

    def __init__(self):
        self.events: list = []
        self.annotate = None

    def __call__(self, name: str):
        return _Span(self, name)


class _Span:
    def __init__(self, spans: Spans, name: str):
        self.spans, self.name = spans, name

    def __enter__(self):
        self.mark = (self.spans.annotate(self.name)
                     if self.spans.annotate else None)
        if self.mark:
            self.mark.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        if self.mark:
            self.mark.__exit__(*exc)
        self.spans.events.append((self.name, self.t0, t1 - self.t0, {}))
        return False


# -- set-up -----------------------------------------------------------------------

def device_info(chips: int, require_tpu: bool) -> dict:
    import jax

    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        raise NoDevice(f"no TPU: JAX found {dev.platform!r} devices")
    if len(devices) < chips:
        raise NoDevice(f"the cell asks for {chips} chips, JAX found "
                       f"{len(devices)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": chips}


def use_compile_cache() -> str:
    """The program's persistent compile cache (JAX_COMPILATION_CACHE_DIR
    when set, else a fixed directory of the checkout), holding every
    program however fast it compiled."""
    import jax

    from repro import device

    path = device.use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts compile requests and their seconds: JAX reports a program
    loaded from the persistent cache under the same event as one compiled,
    so a compiling run shows in the seconds, not in the count."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration


def peaks_for(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


# -- one run --------------------------------------------------------------------

def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, require_tpu: bool = True,
             trace_dir: Optional[str] = None,
             peaks: Optional[dict] = None) -> dict:
    """Set up, measure, compare; returns the result line's object.

    ``require_tpu=False`` and ``peaks`` (a row of ``bench/peaks.json``) let
    the benchmark's tests drive a whole run on the CPU."""
    import jax

    from bench import devtrace, kinds

    device = device_info(cell.chips, require_tpu)
    compiles = CompileCounter()
    from repro import obs

    if trace:
        obs.enable()
    spans = Spans()
    kind = kinds.load(cell.mix["kind"])
    t_device = time.perf_counter()
    work = kind(cell.config, cell.mix, seed, spans)
    t_built = time.perf_counter()
    work.warmup()
    setup_s = time.perf_counter() - t_start
    setup_parts = {"start_to_device": t_device - t_start,
                   "build": t_built - t_device,
                   "warmup": t_start + setup_s - t_built}
    compiles_setup, compile_s_setup = compiles.count, compiles.seconds

    # -- the window: whole calls until `seconds` have passed -------------
    log_dir = None
    if trace:
        log_dir = trace_dir or tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(log_dir)
        spans.annotate = jax.profiler.TraceAnnotation
    obs.reset()
    spans.events.clear()
    units = calls = failed = 0
    errors = []
    window = spans("bench.window").__enter__()
    window_ns = window.t0
    t0 = time.perf_counter()
    while True:
        try:
            units += work.step(calls)
        except Exception as exc:  # a failed call counts against the run
            failed += 1
            errors.append(f"call {calls}: {type(exc).__name__}: {exc}")
        calls += 1
        if time.perf_counter() - t0 >= seconds:
            break
    window_s = time.perf_counter() - t0
    window.__exit__(None, None, None)
    if trace:
        jax.profiler.stop_trace()
        spans.annotate = None
    compiles_window = compiles.count - compiles_setup
    compile_s_window = compiles.seconds - compile_s_setup
    work.close()

    stats = jax.devices()[0].memory_stats() or {}
    device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))

    # -- metrics ----------------------------------------------------------
    program = [(e["name"], int(e["ts"] * 1e3) + obs.get_tracer().epoch_ns,
                int(e["dur"] * 1e3), e.get("args", {}))
               for e in obs.events() if e.get("ph") == "X"]
    ctx = Context(config=cell.config, units=units, window_s=window_s,
                  setup_s=setup_s, spans=spans.events + program)
    breakdown = None
    if trace:
        obs.disable()
        tr = devtrace.read_trace(devtrace.find_xplane(log_dir))
        marks = [h for h in tr["host"] if h[0] == "bench.window"]
        if not marks:
            raise RuntimeError("the trace holds no bench.window span")
        lo = marks[-1][1]
        hi = lo + marks[-1][2]
        ops = [e for dev in sorted(tr["devices"]) for e in tr["devices"][dev]]
        per_dev = [devtrace.busy_ns(tr["devices"][d], lo, hi)
                   for d in sorted(tr["devices"])]
        busy = sum(per_dev) / max(len(per_dev), 1) / 1e9
        shift = lo - window_ns
        host = tr["host"] + [(n, s + shift, d) for n, s, d, _ in program]
        ctx.peaks = peaks or peaks_for(device["kind"])
        ctx.device_ops, ctx.trace_lo, ctx.trace_hi = ops, lo, hi
        ctx.busy_s = busy
        device["busy_s"] = busy
        device["window_s"] = (hi - lo) / 1e9
        top = sorted(devtrace.time_by_name(
            [e for e in ops if lo <= e[1] < hi]).items(),
            key=lambda kv: -kv[1])[:10]
        gaps = sorted(devtrace.idle_gaps(ops, host, lo, hi).items(),
                      key=lambda kv: -kv[1])[:10]
        breakdown = {"device_ops": [[k, v / 1e9] for k, v in top],
                     "idle_gaps": [[k, v / 1e9] for k, v in gaps]}
        if trace_dir is None:
            shutil.rmtree(log_dir, ignore_errors=True)

    chosen = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in chosen:
        value = metric_reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    # -- the comparison with the reference --------------------------------
    numbers = work.check()
    compared = {k: {"value": numbers[k], "limit": cell.limits[k]}
                for k in numbers}
    missing = sorted(set(cell.limits) - set(numbers))
    correct = (failed == 0 and units > 0 and not missing
               and all(v["value"] <= v["limit"] for v in compared.values()))
    # a call completes `units`; a failed call counts as one failed unit
    result = {"correct": correct, "attempted": units + failed,
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["info"] = {"calls": calls, "unit": kind.unit,
                      "setup_parts_s": setup_parts,
                      "compiles_in_setup": compiles_setup,
                      "compile_s_in_setup": compile_s_setup,
                      "compiles_in_window": compiles_window,
                      "compile_s_in_window": compile_s_window,
                      "errors": errors[:3], "missing_numbers": missing}
    result["compared"] = compared
    return result


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse

    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler trace in this directory")
    args = ap.parse_args(argv)

    cell = load_cell(args.workload)
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"bench: the program is missing ({src}/repro)", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    use_compile_cache()
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          t_start, trace_dir=args.trace_dir)
    except NoDevice as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 3
    for name, v in result["compared"].items():
        print(f"compared {name} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0

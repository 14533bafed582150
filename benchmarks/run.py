"""Benchmark harness — one bench per paper table/figure.

  python -m benchmarks.run [--quick] [--only generation,analysis,...]
  python -m benchmarks.run --baseline   # perf-trajectory -> BENCH_10.json
  python -m benchmarks.run --baseline --gate BENCH_5.json   # CI perf gate

  generation   Table-1 analogue: 10k/100k/1M-server generation scalability
  analysis     Table-2 analogue: per-metric analysis cost
  collectives  Fig-1 analogue: topology comparison under collective/traffic load
  kernels      Pallas kernel sweep + VMEM working sets
  roofline     the 40-cell dry-run roofline table (reads experiments/dryrun)
  resilience   batched failure-sweep severity pass vs the per-mask loop
  traffic      batched traffic-scenario pass vs the per-matrix loop

``--baseline`` runs the headline device-resident-vs-host-loop comparison
(`bench_analysis.baseline`) and writes the repo-root ``BENCH_10.json``
trajectory artifact (single-graph analyze, sweep chain, throughput rounds,
packed/estimator trajectory, batched failure-sweep severity pass, batched
traffic-scenario pass, with speedups over the host-looped reference) that
CI uploads per run, so future PRs have a fixed-size perf trajectory to
compare against.

``--gate REF.json`` is the perf-trajectory regression gate: every
``*speedup`` column present in BOTH the fresh baseline and the reference
artifact (the previous PR's committed BENCH_N.json) must hold at least
``(1 - tolerance)`` of the reference value — default tolerance 30% — or
the process exits nonzero and the CI job fails. Columns that exist on only
one side (new workloads, retired workloads) are reported but never gate.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

from . import (bench_analysis, bench_collectives, bench_generation,
               bench_kernels, bench_resilience, bench_roofline,
               bench_traffic)

BENCHES = {
    "generation": bench_generation,
    "analysis": bench_analysis,
    "collectives": bench_collectives,
    "kernels": bench_kernels,
    "roofline": bench_roofline,
    "resilience": bench_resilience,
    "traffic": bench_traffic,
}

OUT = pathlib.Path(__file__).resolve().parents[1] / "experiments" / "bench"

#: this PR sequence's baseline artifact (previous PRs' files stay committed
#: at the repo root, giving the trajectory its history)
BASELINE_NAME = "BENCH_10.json"

#: a shared speedup column may lose at most this fraction vs the reference
GATE_TOLERANCE = 0.30

#: artifact subtrees the gate never reads: run provenance and the span
#: summary vary per machine/run and must not produce speedup columns
GATE_IGNORED_KEYS = ("meta", "spans")


def run_metadata() -> dict:
    """Provenance stamp for a BENCH artifact: where and on what it ran.

    Every field degrades to ``None``/"unknown" rather than raising —
    stamping a benchmark must never fail it.
    """
    import datetime
    import platform
    import subprocess

    meta = {
        "timestamp_utc": datetime.datetime.now(
            datetime.timezone.utc).isoformat(timespec="seconds"),
        "platform": platform.platform(),
        "python": platform.python_version(),
        "git_sha": None,
        "jax": None,
        "jaxlib": None,
        "device_kind": None,
        "device_count": None,
    }
    try:
        meta["git_sha"] = subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            cwd=pathlib.Path(__file__).resolve().parents[1], timeout=10,
        ).stdout.strip() or None
    except (OSError, subprocess.SubprocessError):
        pass
    try:
        import jax
        import jaxlib

        meta["jax"] = jax.__version__
        meta["jaxlib"] = jaxlib.__version__
        devs = jax.local_devices()
        meta["device_kind"] = devs[0].device_kind if devs else "none"
        meta["device_count"] = len(devs)
    except Exception:  # noqa: BLE001 - stamp what we can
        pass
    return meta


def _speedup_columns(node, prefix: str = "") -> dict:
    """Flatten every numeric ``*speedup`` leaf to {"a.b.speedup": value}.

    The ``meta`` / ``spans`` subtrees (run provenance, span summaries) are
    skipped at every level: metadata never gates.
    """
    cols = {}
    if isinstance(node, dict):
        for key, val in sorted(node.items()):
            if key in GATE_IGNORED_KEYS:
                continue
            path = f"{prefix}.{key}" if prefix else key
            if isinstance(val, dict):
                cols.update(_speedup_columns(val, path))
            elif isinstance(val, (int, float)) and key.endswith("speedup"):
                cols[path] = float(val)
    return cols


def gate(current: dict, reference: dict,
         tolerance: float = GATE_TOLERANCE) -> int:
    """Compare shared speedup columns; return the number of regressions.

    Raises ValueError when the artifacts share no speedup column at all —
    that is a wrong-reference error, not a perf regression.
    """
    cur, ref = _speedup_columns(current), _speedup_columns(reference)
    shared = sorted(set(cur) & set(ref))
    regressions = 0
    for path in shared:
        floor = ref[path] * (1.0 - tolerance)
        ok = cur[path] >= floor
        regressions += 0 if ok else 1
        print(f"[gate] {'ok  ' if ok else 'FAIL'} {path}: "
              f"{cur[path]:.2f}x vs ref {ref[path]:.2f}x "
              f"(floor {floor:.2f}x)")
    for path in sorted(set(cur) - set(ref)):
        print(f"[gate] new  {path}: {cur[path]:.2f}x (not gated)")
    for path in sorted(set(ref) - set(cur)):
        print(f"[gate] gone {path}: ref {ref[path]:.2f}x (not gated)")
    if not shared:
        raise ValueError("no shared speedup columns between baseline and "
                         "reference — wrong --gate artifact?")
    return regressions


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated bench names")
    ap.add_argument("--baseline", action="store_true",
                    help=f"perf-trajectory summary -> repo-root "
                         f"{BASELINE_NAME}")
    ap.add_argument("--gate", default=None, metavar="REF_JSON",
                    help="fail if any shared speedup column of a FRESH "
                         f"baseline regresses > {GATE_TOLERANCE:.0%} vs "
                         "this reference artifact (always re-measures — a "
                         "committed BENCH file must never gate itself)")
    args = ap.parse_args()
    if args.baseline or args.gate:
        from repro import obs

        path = OUT.parents[1] / BASELINE_NAME
        # trace the baseline run: the artifact carries the per-span
        # time summary next to the numbers it explains
        obs.enable()
        summary = bench_analysis.baseline(quick=args.quick)
        summary["resilience"] = bench_resilience.baseline_section(
            quick=args.quick)
        summary["traffic"] = bench_traffic.baseline_section(
            quick=args.quick)
        summary["tier"] = "perf-trajectory"
        summary["meta"] = run_metadata()
        summary["spans"] = obs.span_summary()
        path.write_text(json.dumps(summary, indent=1) + "\n")
        print(json.dumps(summary, indent=1))
        print(f"[baseline] wrote {path}")
        if args.gate:
            reference = json.loads(pathlib.Path(args.gate).read_text())
            bad = gate(summary, reference)
            if bad:
                print(f"[gate] {bad} speedup column(s) regressed "
                      f"> {GATE_TOLERANCE:.0%} — failing")
                sys.exit(1)
            print("[gate] perf trajectory OK")
        return
    names = list(BENCHES) if not args.only else args.only.split(",")
    OUT.mkdir(parents=True, exist_ok=True)
    for name in names:
        mod = BENCHES[name]
        print(f"\n=== bench: {name} {'(quick)' if args.quick else ''} ===")
        t0 = time.time()
        rows = mod.main(quick=args.quick)
        dt = time.time() - t0
        (OUT / f"{name}.json").write_text(json.dumps(rows, indent=1, default=str))
        print(f"[{name}] {len(rows)} rows in {dt:.1f}s -> experiments/bench/{name}.json")


if __name__ == "__main__":
    from repro.device import use_compile_cache

    use_compile_cache()
    main()

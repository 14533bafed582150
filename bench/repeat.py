"""Run one cell several times, each run a process of its own, and collect
the result lines: the tool for measuring a cell's spread and its
correctness over many seeds.

    python3 bench/repeat.py --workload slimfly_q41.report --seeds 11 12 13 \
        --seconds 30 --trace 0 --out runs.jsonl [--script bench/control.py]

The parent never imports JAX, so each child has the chip to itself. Each
line of ``--out`` is the child's result (or its exit code and the end of
its error output) with its seed, trace flag and wall seconds; a summary of
each run goes to standard output.
"""
import argparse
import json
import pathlib
import subprocess
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def run_once(script: str, workload: str, seed: int, seconds: float,
             trace: int, extra) -> dict:
    cmd = [sys.executable, str(ROOT / script), "--workload", workload,
           "--seed", str(seed), "--seconds", str(seconds)]
    if script.endswith("run.py"):
        cmd += ["--trace", str(trace)]
    t0 = time.perf_counter()
    proc = subprocess.run(cmd + list(extra), cwd=ROOT, capture_output=True,
                          text=True)
    wall = time.perf_counter() - t0
    row = {"seed": seed, "trace": trace, "rc": proc.returncode,
           "wall_s": wall}
    lines = proc.stdout.strip().splitlines()
    if proc.returncode == 0 and lines:
        row["result"] = json.loads(lines[-1])
    else:
        row["stderr"] = proc.stderr[-4000:]
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", required=True)
    ap.add_argument("--script", default="bench/run.py")
    ap.add_argument("extra", nargs="*")
    args = ap.parse_args(argv)
    out = pathlib.Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    bad = 0
    with open(out, "a") as fh:
        for seed in args.seeds:
            row = run_once(args.script, args.workload, seed, args.seconds,
                           args.trace, args.extra)
            row["workload"] = args.workload
            fh.write(json.dumps(row) + "\n")
            fh.flush()
            res = row.get("result", {})
            bad += row["rc"] != 0
            summary = {k: v["value"] for k, v in res.get("metrics", {}).items()}
            compared = {k: v["value"] for k, v in res.get("compared", {}).items()}
            print(json.dumps({"seed": seed, "rc": row["rc"],
                              "wall_s": round(row["wall_s"], 3),
                              "correct": res.get("correct"),
                              "metrics": summary, "compared": compared,
                              "info": res.get("info"),
                              "device": res.get("device"),
                              "err": row.get("stderr", "")[-1500:]}),
                  flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())

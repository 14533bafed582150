"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload slimfly_q41.report --seed 7 \
        --seconds 30 --trace 0

Prints the cell's end-to-end metrics (``--trace 0``) or its per-layer
metrics from a profiler trace of the window (``--trace 1``) as the last
line of standard output, one JSON object; the numbers compared with the
plain reference, each beside its limit, are the last lines of standard
error. Exits non-zero, printing no result, without a TPU, with fewer chips
than the cell asks for, or without the program beside the benchmark.
"""
import time

T_START = time.perf_counter()

import pathlib  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))

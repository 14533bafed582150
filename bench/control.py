"""The lower-precision control: a cell run with the program's counting
products below the precision the configurations state.

    python3 bench/control.py --workload fattree_k74.compare --seed 5 \
        --seconds 10 --passes 3

The configurations state float32 counting products at HIGHEST precision
(six bf16 passes). The control swaps the one counting product of the
program's kernels (``repro.kernels.semiring._count_dot``) for a lower one,
written out so that it runs alike in the TPU's kernels and in the CPU's
interpreter: ``--passes 3`` is Precision.HIGH's three passes (each float32
operand split into a bf16 high and low part, the low-by-low product left
out), ``--passes 1`` is one bf16 pass. The rest of the run is the
benchmark's own; its comparison has to come out as not correct. The
benchmark's runs never load this file.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def bf16x3_dot(a, b):
    """Precision.HIGH's product of two float32 blocks: hi*hi + hi*lo + lo*hi
    in bf16 passes accumulated in float32 (exact for integer operands below
    2^16)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import semiring

    a, b = semiring._as_f32(a), semiring._as_f32(b)
    a_hi = a.astype(jnp.bfloat16)
    b_hi = b.astype(jnp.bfloat16)
    a_lo = (a - a_hi.astype(jnp.float32)).astype(jnp.bfloat16)
    b_lo = (b - b_hi.astype(jnp.float32)).astype(jnp.bfloat16)

    def dot(x, y):
        return jax.lax.dot(x, y, preferred_element_type=jnp.float32)

    return dot(a_hi, b_hi) + dot(a_hi, b_lo) + dot(a_lo, b_hi)


def bf16_dot(a, b):
    """One bf16 pass accumulated in float32 (exact for integers to 256)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import semiring

    return jax.lax.dot(semiring._as_f32(a).astype(jnp.bfloat16),
                       semiring._as_f32(b).astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)


PASSES = {3: bf16x3_dot, 1: bf16_dot}


def lower_precision(passes: int = 3) -> None:
    """Swap the kernels' counting product before anything is traced."""
    from repro.kernels import semiring

    semiring._count_dot = PASSES[passes]


def main(argv=None) -> int:
    from bench import harness

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--passes", type=int, choices=sorted(PASSES), default=3)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    sys.path.insert(0, str(ROOT / "src"))
    harness.use_compile_cache()
    lower_precision(args.passes)
    result = harness.run_cell(cell, args.seed, args.seconds, False, T_START)
    print(json.dumps({"control": f"bf16 x{args.passes}", "workload": args.workload,
                      "seed": args.seed, "correct": result["correct"],
                      "attempted": result["attempted"],
                      "compared": result["compared"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

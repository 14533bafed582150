"""Shared set-up of the benchmark's own tests (run on the CPU with
``JAX_PLATFORMS=cpu python -m pytest bench/tests -q``): the repository
root and ``src`` on the path, and small cells of the real mixes."""
import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

PEAKS = json.loads((ROOT / "bench" / "peaks.json").read_text())["devices"][
    "TPU v5 lite"]


def slimfly_config(q: int) -> dict:
    k = (3 * q - 1) // 2
    p = -(-k // 2)
    return {"name": f"slimfly_q{q}", "family": "slimfly", "params": {"q": q},
            "routers": 2 * q * q, "edges": q * q * k, "servers": 2 * q * q * p,
            "concentration": p, "network_radix": k, "diameter": 2}


def fattree_config(k: int) -> dict:
    h = k // 2
    n = h * h + 2 * k * h
    real = json.loads((ROOT / "bench" / "configs" / "fattree_k74.json")
                      .read_text())
    cost = dict(real["cost"],
                routers_by_radix=[[k, h * h], [k, k * h], [k, k * h]],
                links=[{"count": k * h * h, "medium": "electrical"},
                       {"count": k * h * h, "medium": "optical"}])
    return {"name": f"fattree_k{k}", "family": "fattree", "params": {"k": k},
            "routers": n, "edges": 2 * k * h * h, "servers": k * h * h,
            "concentration": 0, "network_radix": k, "diameter": 4,
            "cost": cost}


def small_cell(name: str, config: dict):
    """The real cell's mix, limits and metrics over a small fabric."""
    from bench import harness

    real = harness.load_cell(name)
    return harness.Cell(name=name, chips=1, config=config, mix=real.mix,
                        limits=real.limits, end_to_end=real.end_to_end,
                        per_layer=real.per_layer)


def run_small(cell, seed: int = 2 ** 31 + 7, seconds: float = 0.3,
              trace: bool = False) -> dict:
    import time

    from bench import harness

    return harness.run_cell(cell, seed, seconds, trace, time.perf_counter(),
                            require_tpu=False, peaks=PEAKS)


@pytest.fixture
def fresh_programs():
    """Forget every traced program, so a swapped function is traced anew
    (the wavefront and ECMP engines cache their jitted loops)."""
    import jax

    from repro.core.analysis import wavefront

    def clear():
        jax.clear_caches()
        for fn in (wavefront._dist_mult_fn, wavefront._dist_mult_packed_fn,
                   wavefront._ecmp_fn):
            fn.cache_clear()

    clear()
    yield
    clear()

"""Block-shape / sub_k autotuner for the Pallas semiring ops.

The semiring kernels expose (bm, bn, bk) block shapes (and ``sub_k`` slab
width on the VPU path) as free parameters; the best choice depends on the
problem size and the backend (the Pallas interpreter on the CPU, Mosaic on
a TPU, whose scoped VMEM bounds the block shapes that compile).
This module owns one small mechanism:

* a **persisted tuning table** — JSON mapping ``op -> shape bucket ->
  config``. The committed `tuning_table.json` is the table; only when
  ``$REPRO_TUNE_TABLE`` is set does that file overlay it. Block shapes
  change what is compiled, so nothing outside the checkout feeds them
  unless asked for.
* :func:`resolve` — the ops-layer hook: explicit keyword arguments always
  win, anything left unspecified comes from the table (falling back to the
  op's registered default config).
* :func:`autotune` — time a list of candidate configs on a given shape and
  persist the winner (into the overlay when ``$REPRO_TUNE_TABLE`` is set,
  else into the committed table). ``python -m repro.kernels.autotune
  --op frontier_step --size 1024`` from the CLI.

Shapes are bucketed to the next power of two (min 128) per dimension, so one
tuned entry covers the whole padded-size neighbourhood the wavefront engine
actually runs.
"""
from __future__ import annotations

import json
import os
import pathlib
import time
from typing import Dict, Iterable, List, Optional

__all__ = ["resolve", "autotune", "shape_key", "load_table", "save_entry",
           "DEFAULTS", "CANDIDATES"]

#: per-op fallback configs. The VPU (min-plus) ops stay at 128 blocks:
#: their (bm, sub_k, bn) broadcast slabs overflow v5e's 16 MiB scoped VMEM
#: at 256 blocks with sub_k 8 or 32 (tests/test_tpu_compile.py)
DEFAULTS: Dict[str, Dict[str, int]] = {
    "minplus": {"bm": 128, "bn": 128, "bk": 128, "sub_k": 8},
    "minplus_count": {"bm": 128, "bn": 128, "bk": 128, "sub_k": 8},
    "count": {"bm": 128, "bn": 128, "bk": 128},
    "boolean": {"bm": 128, "bn": 128, "bk": 128},
    "frontier_step": {"bm": 128, "bn": 128, "bk": 128},
    "batched_minplus": {"bm": 128, "bn": 128, "bk": 128, "sub_k": 8},
    "batched_count": {"bm": 256, "bn": 256, "bk": 256},
    "batched_frontier_step": {"bm": 256, "bn": 256, "bk": 256},
}

#: candidate grids the CLI sweeps (block shapes must tile (8, 128) f32)
CANDIDATES: Dict[str, List[Dict[str, int]]] = {
    "frontier_step": [
        {"bm": b, "bn": b, "bk": b} for b in (128, 256, 512)
    ],
    "batched_frontier_step": [
        {"bm": b, "bn": b, "bk": b} for b in (128, 256, 512)
    ],
    "count": [{"bm": b, "bn": b, "bk": b} for b in (128, 256, 512)],
    "boolean": [{"bm": b, "bn": b, "bk": b} for b in (128, 256, 512)],
    "minplus": [
        {"bm": b, "bn": b, "bk": b, "sub_k": s}
        for b in (128, 256) for s in (8, 16, 32)
    ],
    "minplus_count": [
        {"bm": b, "bn": b, "bk": b, "sub_k": s}
        for b in (128, 256) for s in (8, 16, 32)
    ],
}

_SHIPPED = pathlib.Path(__file__).with_name("tuning_table.json")


def _overlay_path() -> Optional[pathlib.Path]:
    env = os.environ.get("REPRO_TUNE_TABLE")
    return pathlib.Path(env) if env else None


def _read_json(path: pathlib.Path) -> Dict:
    try:
        return json.loads(path.read_text())
    except (OSError, ValueError):
        return {}


def shape_key(m: int, n: int, k: int, dtype: Optional[str] = None) -> str:
    """Bucket a problem shape: each dim rounds up to a power of two >= 128.

    A non-default ``dtype`` tag (e.g. ``"packed"`` for the int16/uint32
    narrow-cell kernels) is folded into the key as a ``:dtype`` suffix, so
    narrow kernels get their own tuned block shapes — their VMEM working set
    per block is smaller, which shifts the optimum. ``None``/``"f32"`` keep
    the legacy un-suffixed key, so shipped tables stay valid.
    """

    def bucket(x: int) -> int:
        b = 128
        while b < x:
            b *= 2
        return b

    key = f"{bucket(m)}x{bucket(n)}x{bucket(k)}"
    if dtype and dtype != "f32":
        key += f":{dtype}"
    return key


_TABLE_CACHE: Optional[Dict] = None


def load_table(refresh: bool = False) -> Dict[str, Dict[str, Dict[str, int]]]:
    """The committed table, overlaid by ``$REPRO_TUNE_TABLE`` when set
    (overlay entries win).

    Cached in-process (the ops layer consults it on every call); refreshed
    after :func:`save_entry` or on ``refresh=True``.
    """
    global _TABLE_CACHE
    if _TABLE_CACHE is None or refresh:
        table = _read_json(_SHIPPED)
        overlay = _overlay_path()
        if overlay is not None:
            for op, entries in _read_json(overlay).items():
                table.setdefault(op, {}).update(entries)
        _TABLE_CACHE = table
    return _TABLE_CACHE


def save_entry(op: str, key: str, config: Dict[str, int]) -> pathlib.Path:
    """Persist one tuned entry: into ``$REPRO_TUNE_TABLE`` when set, else
    into the committed table."""
    global _TABLE_CACHE
    path = _overlay_path() or _SHIPPED
    path.parent.mkdir(parents=True, exist_ok=True)
    table = _read_json(path)
    table.setdefault(op, {})[key] = dict(config)
    path.write_text(json.dumps(table, indent=1, sort_keys=True) + "\n")
    _TABLE_CACHE = None
    return path


_DECISIONS_SEEN: set = set()


def _note_decision(op: str, key: str, cfg: Dict[str, int],
                   tuned, overrides) -> None:
    """Under an enabled tracer, record which table entry won ``resolve``
    and why — once per (op, shape bucket) so the hot path stays hot."""
    from .. import obs

    if not obs.enabled() or (op, key) in _DECISIONS_SEEN:
        return
    _DECISIONS_SEEN.add((op, key))
    source = {kk: ("override" if kk in overrides
                   and overrides[kk] is not None
                   else "tuned" if tuned and kk in tuned else "default")
              for kk in cfg}
    obs.instant("autotune.resolve", cat="kernels", op=op, shape=key,
                config=dict(cfg), source=source,
                tuned_entry=dict(tuned) if tuned else None)


def resolve(op: str, m: int, n: int, k: int, dtype: Optional[str] = None,
            **overrides) -> Dict[str, int]:
    """Final config for one op call: overrides > tuned table > op default.

    Only keys the op's default config carries are returned, so VPU-only
    knobs (``sub_k``) never leak into MXU-path calls. Block shapes are
    clamped to the bucketed problem size (a 512-wide tile is useless on a
    256-wide padded matrix). ``dtype`` keys the lookup (see
    :func:`shape_key`): a ``"packed"`` entry never collides with the f32
    entry for the same shape bucket, and a packed lookup falls back to the
    op default — not the f32 tuned entry — when untuned. Under an enabled
    `repro.obs` tracer the decision (winning entry + per-knob source) is
    emitted as an ``autotune.resolve`` instant event, once per (op, key).
    """
    key = shape_key(m, n, k, dtype)
    cfg = dict(DEFAULTS[op])
    tuned = load_table().get(op, {}).get(key)
    if tuned:
        cfg.update({kk: vv for kk, vv in tuned.items() if kk in cfg})
    cfg.update({kk: vv for kk, vv in overrides.items()
                if kk in cfg and vv is not None})
    bucket = [int(s) for s in key.split(":")[0].split("x")]
    for dim, limit in zip(("bm", "bn", "bk"), bucket):
        cfg[dim] = min(cfg[dim], limit)
    if "sub_k" in cfg:
        cfg["sub_k"] = min(cfg["sub_k"], cfg["bk"])
    _note_decision(op, key, cfg, tuned, overrides)
    return cfg


def _bench_once(fn, *args) -> float:
    import jax

    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    out = fn(*args)
    jax.block_until_ready(out)
    return time.perf_counter() - t0


def autotune(op: str, size: int, batch: int = 0,
             candidates: Optional[Iterable[Dict[str, int]]] = None,
             persist: bool = True,
             dtype: Optional[str] = None) -> Dict[str, int]:
    """Time candidate configs for ``op`` at a square (size, size) problem
    (optionally stacked ``batch`` deep) and persist the winner.

    ``dtype="packed"`` tunes the narrow-cell (int16 dist / uint32 mult /
    uint8 adjacency) variant of the frontier-step ops under its own
    ``:packed`` table key."""
    import jax.numpy as jnp
    import numpy as np

    from . import ops
    from .semiring import DIST_UNREACHED

    rng = np.random.default_rng(0)
    shape = (batch, size, size) if batch else (size, size)
    mask = rng.random(shape) < 0.05
    a = jnp.asarray(mask.astype(np.float32))
    d = jnp.asarray(np.where(np.eye(size, dtype=bool), 0.0,
                             np.inf).astype(np.float32))
    if batch:
        d = jnp.broadcast_to(d, shape)

    if dtype == "packed":
        if op not in ("frontier_step", "batched_frontier_step"):
            raise ValueError(f"dtype='packed' tunes the frontier-step ops, "
                             f"not {op!r}")
        fq = jnp.asarray(mask.astype(np.uint32))
        aq = jnp.asarray(mask.astype(np.uint8))
        dq = jnp.where(d == jnp.inf, DIST_UNREACHED, d).astype(jnp.int16)
        runners = {
            "frontier_step":
                lambda cfg: ops.frontier_step_packed(fq, aq, dq, **cfg),
            "batched_frontier_step":
                lambda cfg: ops.batched_frontier_step_packed(fq, aq, dq,
                                                             **cfg),
        }
    else:
        runners = {
            "minplus": lambda cfg: ops.minplus_matmul(a, a, **cfg),
            "minplus_count": lambda cfg: ops.minplus_count_matmul(d, a, d, a,
                                                                  **cfg),
            "count": lambda cfg: ops.count_matmul(a, a, **cfg),
            "boolean": lambda cfg: ops.reachability_step(a, a, **cfg),
            "frontier_step": lambda cfg: ops.frontier_step(a, a, d, **cfg),
            "batched_minplus":
                lambda cfg: ops.batched_minplus_matmul(a, a, **cfg),
            "batched_count": lambda cfg: ops.batched_count_matmul(a, a, **cfg),
            "batched_frontier_step":
                lambda cfg: ops.batched_frontier_step(a, a, d, **cfg),
        }
    if op not in runners:
        raise ValueError(f"unknown autotune op {op!r}")
    cands = list(candidates if candidates is not None
                 else CANDIDATES.get(op, [DEFAULTS[op]]))
    best_cfg, best_t = None, float("inf")
    for cand in cands:
        cfg = {kk: vv for kk, vv in cand.items() if kk in DEFAULTS[op]}
        if any(cfg.get(dim, 128) > size for dim in ("bm", "bn", "bk")):
            continue
        try:
            dt = _bench_once(lambda c=cfg: runners[op](c))
        except Exception:  # noqa: BLE001 - an invalid tile is just skipped
            continue
        if dt < best_t:
            best_cfg, best_t = cfg, dt
    if best_cfg is None:
        raise RuntimeError(f"no candidate config ran for {op} at {size}")
    key = shape_key(shape[-2], shape[-1], shape[-1], dtype)
    if persist:
        save_entry(op, key, best_cfg)
    return dict(best_cfg, key=key, seconds=round(best_t, 4))


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--op", default="frontier_step",
                    help=f"one of {sorted(CANDIDATES)}")
    ap.add_argument("--size", type=int, default=1024)
    ap.add_argument("--batch", type=int, default=0)
    ap.add_argument("--dtype", default=None, choices=(None, "f32", "packed"),
                    help="cell dtype to tune (packed = int16/uint32 cells)")
    ap.add_argument("--no-persist", action="store_true")
    args = ap.parse_args(argv)
    res = autotune(args.op, args.size, batch=args.batch, dtype=args.dtype,
                   persist=not args.no_persist)
    print(f"[autotune] {args.op} @ {res.pop('key')}: best {res}")
    if not args.no_persist:
        print(f"[autotune] persisted to {_overlay_path() or _SHIPPED}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

"""Device policy: where the Pallas kernels run and where compiles are cached.

Both decisions are made on first use, never at import, so importing
`repro` initialises no JAX backend (a parent process that imports the
package can still hand the chip to a child).

* :func:`resolve_interpret` — the one place that decides interpret versus
  compiled kernels: the CPU backend runs them through the Pallas
  interpreter (the test suite), a TPU compiles them with Mosaic, and any
  other backend raises instead of silently falling back.
* :func:`use_compile_cache` — the entry points (`chip_smoke.py`, the
  benchmark runner, the module CLIs) call it before their first compile.
  ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
  stands; otherwise the cache goes to a fixed directory of the checkout.
"""
from __future__ import annotations

import os
import pathlib
from typing import Optional

__all__ = ["resolve_interpret", "use_compile_cache", "COMPILE_CACHE_DIR"]

#: the in-checkout compile cache used when JAX_COMPILATION_CACHE_DIR is unset
COMPILE_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Interpret (True) or compile (False) the Pallas kernels.

    ``None`` follows the default backend: CPU interprets, TPU compiles,
    anything else raises. An explicit ``False`` always compiles (the TPU
    compile tests lower for a described chip from a CPU process); an
    explicit ``True`` on a TPU raises, so no kernel runs interpreted where
    the device is present.
    """
    if interpret is False:
        return False
    import jax

    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(f"Pallas kernels run compiled on a TPU or "
                           f"interpreted on the CPU, not on {backend!r}")
    if interpret and backend == "tpu":
        raise RuntimeError("interpret=True on a TPU backend would hide the "
                           "device; the kernels run compiled there")
    return backend == "cpu"


def use_compile_cache() -> Optional[str]:
    """Point JAX's persistent compile cache at a fixed directory.

    Leaves JAX's own reading of ``JAX_COMPILATION_CACHE_DIR`` alone when
    the variable is set; otherwise sets ``jax_compilation_cache_dir`` to
    :data:`COMPILE_CACHE_DIR`. Returns the directory in use.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)

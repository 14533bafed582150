"""``python -m repro.core.resilience`` -> the degradation-curve CLI."""
from ...device import use_compile_cache
from .degradation import main

if __name__ == "__main__":
    use_compile_cache()
    raise SystemExit(main())

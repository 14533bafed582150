"""``python -m repro.core.traffic`` — the traffic x failure grid CLI."""
import sys

from ...device import use_compile_cache
from .grid import main

use_compile_cache()
sys.exit(main())

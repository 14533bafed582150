"""repro.obs — zero-dependency tracing + metrics for the analysis stack.

The instrumentation substrate every pipeline seam emits into:

* `trace` — hierarchical spans (context manager, thread-safe), exported
  as Chrome trace-event JSON viewable in Perfetto; global tracer with an
  env (``REPRO_TRACE``) / flag kill-switch. Disabled tracing costs one
  boolean check per seam and leaves the jitted engines' jaxprs
  bit-identical. Enabled in a process that has imported jax, each span is
  also a ``jax.profiler.TraceAnnotation`` and carries the JIT seconds
  (``jit_s``) and compiles (``compiles``) that ran inside it.
* `meters` — counters and gauges plus samplers for process RSS, jax
  device memory, and host<->device transfer bytes.
* `report` — ``python -m repro.obs.report trace.json`` prints the
  per-stage time / bytes / coverage table from a trace file.

Typical use::

    from repro import obs

    obs.enable()
    with obs.span("sweep", families=12) as sp:
        ...
        sp.set(levels=7)
    obs.export("trace.json")      # -> load in https://ui.perfetto.dev
"""
from . import meters, trace  # noqa: F401
from .meters import (  # noqa: F401
    counter, device_memory_mb, gauge, peak_rss_mb, record_d2h, record_h2d,
    rss_mb, sample_process, snapshot,
)
from .trace import (  # noqa: F401
    NULL_SPAN, Tracer, counter_sample, current, disable, enable, enabled,
    events, export, get_tracer, instant, log, reset, span, span_summary,
)

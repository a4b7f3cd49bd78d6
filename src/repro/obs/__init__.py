"""repro.obs — observability for the GP stack.

Three pieces (see docs/observability.md):

- `counters`: the on-device `[K, C]` telemetry counter stream contract
  that every evolution-block scan emits alongside best-fitness —
  telemetry rides the existing one-sync-per-block dispatch and is
  computed unconditionally, so enabling it never recompiles and never
  changes a trajectory.
- `trace`: host spans (`fit.*`, `serve.*`), each a
  `jax.profiler.TraceAnnotation` on the profiler's clock; a `Tracer`
  also writes them as Chrome-trace-event JSON (Perfetto-viewable), with
  the service's job lifetimes as async lanes. `NULL_TRACER`, the
  default, keeps the annotation alone.
- `metrics.Metrics`: counters/gauges/EMA summaries with a JSONL sink;
  `metrics.BlockMonitor` routes ALL block timing through one
  `runtime.fault.StepMonitor` wrapper. `python -m repro.obs.report`
  renders a run's JSONL (and optionally its trace) as a table.
"""
from repro.obs import counters  # noqa: F401
from repro.obs.metrics import BlockMonitor, Metrics  # noqa: F401
from repro.obs.trace import (  # noqa: F401
    NULL_TRACER,
    NullTracer,
    Tracer,
    validate_trace,
)

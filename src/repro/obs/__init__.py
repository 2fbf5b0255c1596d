"""`repro.obs`: observability for the serving stack.

Four parts, each usable on its own:

* :mod:`repro.obs.registry` — a typed counters/gauges/histograms registry
  (:class:`MetricsRegistry`) that owns every serving-stack counter, plus
  :class:`LiveMetrics`, a rolling window over the last N engine ticks
  (p95 TTFT/TPOT, SLO attainment, utilization) for live monitoring;
* :mod:`repro.obs.trace` — :class:`Tracer`, a structured event tracer on
  the deterministic virtual clock: per-request lifecycle spans
  (submit→admit→first-token→done, preempt/resume/shed) and per-tick
  engine events (decode chunk, prefill call + bucket, host sync,
  compile), exportable as Chrome ``trace_event`` JSON viewable in
  Perfetto — byte-identical across same-seed virtual-clock runs;
* :mod:`repro.obs.observe` — :func:`fit_profile`, which fits a
  :class:`repro.plan.WorkloadProfile` (arrival rate, prompt/decode
  length distributions, deadline slack) from a recorded trace, so
  :func:`repro.plan.planner.autotune` can replan from *observed*
  traffic instead of a synthetic probe
  (surfaced as ``WorkloadProfile.from_trace`` and
  ``planner.autotune_from_trace``);
* :mod:`repro.obs.spans` — :func:`span`, named host spans around each
  phase of the engine step and of one fused-RNN request
  (:data:`SPANS`), on the JAX profiler's clock: they appear in a
  ``jax.profiler`` trace beside the device's ops and cost about a
  microsecond when no profiler runs.

Clocks: the registry counts events and the live window rolls over engine
ticks; the tracer and the profile fit use the virtual tick clock, so
their output never depends on wall time; only the spans use the
profiler's (wall) clock.
"""

from repro.obs.registry import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    LiveMetrics,
    MetricsRegistry,
)
from repro.obs.trace import (  # noqa: F401
    TraceEvent,
    Tracer,
    check_trace,
    dumps_trace_doc,
    merge_traces,
)
from repro.obs.observe import fit_profile  # noqa: F401
from repro.obs.spans import SPANS, span  # noqa: F401

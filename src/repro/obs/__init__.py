"""Serving & training observability layer — see docs/observability.md.

Four pieces (ISSUE 9):

* :mod:`repro.obs.metrics` — counters / gauges / fixed-bucket histograms
  under a thread-safe registry, with Prometheus text exposition and a
  JSON dump. Off-by-default: the process default registry is a no-op
  unless ``REPRO_METRICS`` is truthy or an explicit registry is passed.
* :mod:`repro.obs.tracing` — per-request lifecycle span events (submit →
  queue → admit → prefill → first-token → decode → terminal status),
  JSONL on disk via ``REPRO_TRACE_FILE``, exportable to Chrome
  ``trace_event`` JSON for chrome://tracing / Perfetto.
* :mod:`repro.obs.log` — the one logger every banner routes through
  (``REPRO_LOG_LEVEL``; quiet by default under pytest).
* :mod:`repro.obs.profiling` — ``jax.profiler`` sessions
  (``REPRO_PROFILE_DIR``) and spans around prefill/decode waves and the
  trainer's batch read, batch placement, state copy and step, which
  record under any active profiler session.

The kernel tier (ISSUE 10) sits underneath:

* :mod:`repro.obs.cost` — analytic per-kernel FLOP/byte estimators keyed
  off the ski/tno plan objects, roofline math, and the
  ``cost_analysis()`` cross-check.
* :mod:`repro.obs.devstats` — kernel regions at the dispatch sites,
  profiler-trace aggregation / analytic attribution into
  ``repro_kernel_seconds_total{kernel}``, and HBM/live-buffer gauges.
* :mod:`repro.obs.compilewatch` — the compile/retrace watchdog
  (``repro_compiles_total{fn}`` + compile-seconds histogram + budget
  warnings) wrapping the memoised jit entry points.
"""
from repro.obs.metrics import (NULL_REGISTRY, MirroredCounts, NullRegistry,
                               Registry, default_registry, metrics_enabled,
                               set_default_registry)
from repro.obs.tracing import (Tracer, chrome_trace, default_tracer,
                               load_jsonl, set_default_tracer,
                               validate_spans, write_chrome)
from repro.obs.log import banner, get_logger, set_level
from repro.obs.profiling import annotation, profile_dir, session
from repro.obs.cost import (Cost, Peaks, achieved_fraction, cost_of_plan,
                            decode_step_cost, peaks, xla_cost)
from repro.obs.compilewatch import CompileWatch
from repro.obs.devstats import (aggregate_chrome, attribute_engine,
                                kernel_region, sample_memory)

__all__ = [
    "Registry", "NullRegistry", "NULL_REGISTRY", "MirroredCounts",
    "default_registry", "set_default_registry", "metrics_enabled",
    "Tracer", "default_tracer", "set_default_tracer", "load_jsonl",
    "chrome_trace", "write_chrome", "validate_spans",
    "get_logger", "set_level", "banner",
    "profile_dir", "session", "annotation",
    "Cost", "Peaks", "peaks", "cost_of_plan", "decode_step_cost",
    "achieved_fraction", "xla_cost",
    "CompileWatch",
    "kernel_region", "aggregate_chrome", "attribute_engine",
    "sample_memory",
]

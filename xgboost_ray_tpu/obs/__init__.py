"""Unified observability plane: metrics registry + span tracer.

Every subsystem used to invent its own telemetry (the ``AllreduceBytes``
number threaded through ``additional_results``, the hand-rolled
``robustness`` dict, ``bench.py``'s private phase timers, the serve layer's
lock-guarded metrics island). This package is the one plane they now share:

* :mod:`xgboost_ray_tpu.obs.metrics` — process-wide ``MetricsRegistry``
  with counters, gauges and the log-bucket ``LatencyHistogram`` (promoted
  out of ``serve/metrics.py``), plus Prometheus text exposition.
* :mod:`xgboost_ray_tpu.obs.trace` — span/event ``Tracer`` with a bounded
  ring buffer (dropped-record accounting, never silent), JSONL export and
  per-rank ``RXGB_TRACE_DIR`` streaming; ``validate_trace_records`` is the
  shared schema checker; ``recovery_time_s`` reconstructs
  failure→recovery timing from the event timeline. Every record carries
  ``t0_s`` (``time.perf_counter()`` at its start), the clock a harness
  window and a profiler session can be dated on; spans also enter a
  ``jax.profiler.TraceAnnotation`` once jax is imported. ``TRACE_NAMES``
  is the catalogue of host span / event names, ``DEVICE_SCOPES`` the
  ``jax.named_scope`` vocabulary of the compiled programs.
* :mod:`xgboost_ray_tpu.obs.compiles` — ``watch_compiles()``: one
  ``jax.monitoring`` listener per process that records every trace / lower
  / backend compile / cache load as a ``compile.*`` span under the span
  that caused it, and counts them in the registry.
* :mod:`xgboost_ray_tpu.obs.device` — ``scope_times(trace_dir)``: device
  seconds per ``DEVICE_SCOPES`` path from a profiler trace, and
  ``scope_times_by_device`` the same for each device of a mesh
  (``python -m xgboost_ray_tpu.obs.device <dir>``); ``idle_by_span(
  trace_dir, records)``: each device's idle seconds by the span path of
  the timeline record that covers them, the records put on the profiler's
  clock by their ``TraceAnnotation``; stdlib only.

``train()`` scopes a fresh tracer per run and returns its timeline under
``additional_results["obs"]``: one ``attempt`` span, under it ``data.load``,
``engine.init`` (``data.h2d``, ``data.sketch_bin``), one ``dispatch``
(``dispatch.enqueue`` › ``dispatch.args``, ``dispatch.wait``, ``round``
records, ``compile.*`` on a first call) per compiled dispatch, and
``driver.checkpoint`` (› ``checkpoint.readback``, ``checkpoint.serialise``)
/ ``driver.callbacks`` between dispatches. Environment knobs:
``RXGB_TRACE`` (0 disables), ``RXGB_TRACE_CAPACITY`` (ring size),
``RXGB_TRACE_DIR`` (per-rank JSONL streaming). Why the chip was idle::

    RXGB_PROFILE_DIR=/tmp/prof RXGB_TRACE_DIR=/tmp/tl python job.py
    python -m xgboost_ray_tpu.obs.device /tmp/prof --timeline /tmp/tl/trace-rank0.jsonl

Stdlib-only imports: safe to touch before jax comes up.
"""

from xgboost_ray_tpu.obs.metrics import (
    BUCKET_BOUNDS_MS,
    Counter,
    Gauge,
    LatencyHistogram,
    MetricsRegistry,
    get_registry,
)
from xgboost_ray_tpu.obs.compiles import watch_compiles
from xgboost_ray_tpu.obs.trace import (
    DEVICE_SCOPES,
    TRACE_NAMES,
    Tracer,
    get_tracer,
    recovery_time_s,
    set_default_tracer,
    use_tracer,
    validate_trace_records,
)

__all__ = [
    "BUCKET_BOUNDS_MS",
    "Counter",
    "DEVICE_SCOPES",
    "Gauge",
    "LatencyHistogram",
    "MetricsRegistry",
    "TRACE_NAMES",
    "Tracer",
    "get_registry",
    "get_tracer",
    "recovery_time_s",
    "set_default_tracer",
    "use_tracer",
    "validate_trace_records",
    "watch_compiles",
]

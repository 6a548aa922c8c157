"""Every compile of the process on the run's timeline.

JAX reports how long it traced, lowered and compiled (or loaded from the
persistent cache) through ``jax.monitoring`` as each stage ENDS, on the
thread that called the jitted function. :func:`watch_compiles` registers one
listener per process that turns those reports into ``compile.*`` spans on
the thread's current tracer: their parent is whatever span is open there
(``dispatch.enqueue``, ``data.sketch_bin``, ``driver.checkpoint``), so a
compile is named by what caused it, and a compile inside a steady window
cannot hide. The process-wide counters ``rxgb_compiles_total``,
``rxgb_compile_cache_hits_total`` and ``rxgb_compile_cache_misses_total``
sit beside the restart counters in :func:`obs.get_registry`.

``compile.backend`` is JAX's ``backend_compile_duration``, which also wraps a
persistent-cache hit: such a span carries ``cache_hit: true`` and holds the
``compile.cache_load`` recorded just before it.
"""

import sys
import threading
import time

from xgboost_ray_tpu.obs.metrics import get_registry
from xgboost_ray_tpu.obs.trace import get_tracer

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"
_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"

_lock = threading.Lock()
_watching = False
_tls = threading.local()


def _on_duration(event: str, duration_s: float, **_kw) -> None:
    if event not in (_TRACE, _LOWER, _BACKEND, _CACHE_LOAD):
        return
    tracer = get_tracer()
    ts, t0 = time.time() - duration_s, time.perf_counter() - duration_s
    if event == _TRACE:
        # a jitted function traced while another is being traced reports
        # too; its seconds are inside the outer trace's
        if sys.modules["jax"].core.trace_ctx.is_top_level():
            tracer.add_span("compile.trace", ts, t0, duration_s)
    elif event == _LOWER:
        tracer.add_span("compile.lower", ts, t0, duration_s)
    elif event == _CACHE_LOAD:
        _tls.cache_hit = True
        tracer.add_span("compile.cache_load", ts, t0, duration_s)
    else:
        get_registry().counter("rxgb_compiles_total").inc()
        hit = getattr(_tls, "cache_hit", False)
        _tls.cache_hit = False
        tracer.add_span("compile.backend", ts, t0, duration_s,
                        attrs={"cache_hit": hit})


def _on_event(event: str, **_kw) -> None:
    if event == _CACHE_HIT:
        get_registry().counter("rxgb_compile_cache_hits_total").inc()
    elif event == _CACHE_MISS:
        get_registry().counter("rxgb_compile_cache_misses_total").inc()


def watch_compiles() -> None:
    """Register the listeners, once per process (``jax.monitoring`` keeps
    them for the process's life)."""
    global _watching
    with _lock:
        if _watching:
            return
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        _watching = True

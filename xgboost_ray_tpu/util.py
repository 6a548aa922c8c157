"""Driver-side coordination primitives.

The reference uses actor-wrapped asyncio primitives for the driver↔actor
side-channel (``xgboost_ray/util.py:16-77``: Event actor, Queue actor,
MultiActorTask). In the TPU runtime the coordinator and workers share a
process (workers are mesh slots), so these become thin wrappers over
``threading``/``queue`` with the same interface — preserved so user-facing
semantics (stop events, callback queues) and the FT tests carry over.
"""

import os
import queue
import threading
from typing import Any, Callable, List, Optional


class Event:
    """Mirror of the reference's Event actor API (``util.py:16-47``)."""

    def __init__(self):
        self._event = threading.Event()

    def set(self):
        self._event.set()

    def is_set(self) -> bool:
        return self._event.is_set()

    def clear(self):
        self._event.clear()

    def shutdown(self):
        self._event.set()


class Queue:
    """Mirror of the Ray Queue actor the reference pins near the driver."""

    def __init__(self):
        self._q: "queue.Queue" = queue.Queue()

    def put(self, item: Any):
        self._q.put(item)

    def empty(self) -> bool:
        return self._q.empty()

    def get(self, block: bool = False, timeout: Optional[float] = None) -> Any:
        return self._q.get(block=block, timeout=timeout)

    def shutdown(self):
        while not self._q.empty():
            try:
                self._q.get_nowait()
            except queue.Empty:
                break


class MultiActorTask:
    """Readiness poll over a set of futures/callables (``util.py:52-77``)."""

    def __init__(self, checks: Optional[List[Callable[[], bool]]] = None):
        self._checks = checks or []

    def is_ready(self) -> bool:
        return all(check() for check in self._checks)


def restart_backoff_s(
    restart_index: int,
    base: Optional[float] = None,
    cap: Optional[float] = None,
    jitter: Optional[float] = None,
) -> float:
    """Delay before restart number ``restart_index`` (0-based, counted over
    CONSECUTIVE failures — callers reset their index once recovery makes
    real forward progress): full jitter on an exponential schedule,
    ``base * 2^i`` capped at ``cap``, scaled by ``1 + U(0, jitter)``.
    Shared by the driver retry loop and the launcher so a persistent fault
    cannot crash-loop storm. Env-tunable: ``RXGB_RESTART_BACKOFF_BASE_S``
    (default 0.5; 0 disables), ``RXGB_RESTART_BACKOFF_MAX_S`` (default 30),
    ``RXGB_RESTART_BACKOFF_JITTER`` (fraction, default 0.1)."""
    import random

    if base is None:
        base = float(os.environ.get("RXGB_RESTART_BACKOFF_BASE_S", "0.5"))
    if base <= 0:
        return 0.0
    if cap is None:
        cap = float(os.environ.get("RXGB_RESTART_BACKOFF_MAX_S", "30"))
    if jitter is None:
        jitter = float(os.environ.get("RXGB_RESTART_BACKOFF_JITTER", "0.1"))
    delay = min(cap, base * (2.0 ** max(0, int(restart_index))))
    if jitter > 0:
        # rxgblint: disable-next-line=DET001 - restart-schedule jitter only; never touches model state
        delay *= 1.0 + random.random() * jitter
    return delay


#: fixed in-checkout default of the persistent compile cache (git-ignored).
#: The directory is part of the cache key, so it is never a temp dir, a pid
#: or a time.
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def compile_cache_dir() -> str:
    """Where JAX's persistent compilation cache lives for this process:
    ``$JAX_COMPILATION_CACHE_DIR`` when the environment sets it, otherwise
    :data:`CHECKOUT_CACHE_DIR`."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or CHECKOUT_CACHE_DIR


def place_compile_cache() -> str:
    """Point JAX's persistent compilation cache at :func:`compile_cache_dir`
    and return that path — the one helper the entry-point scripts
    (``chip_smoke.py``, ``bench.py``, ``tools/chip_sweep.py``) call. JAX
    reads ``JAX_COMPILATION_CACHE_DIR`` itself, so when it is set nothing is
    configured in code; otherwise the default is installed through
    ``jax.config``. Call before the first compile."""
    path = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path


def device_record() -> dict:
    """Where this process runs, as JAX reports it — the three fields every
    benchmark and smoke result line carries. Initializes the backend."""
    import jax

    dev0 = jax.devices()[0]
    return {
        "platform": dev0.platform,
        "device_kind": dev0.device_kind,
        "device_count": len(jax.devices()),
    }

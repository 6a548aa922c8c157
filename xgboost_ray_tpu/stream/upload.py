"""Double-buffered host→device uploader for streamed ingestion.

One background worker drains a bounded queue of (key, host array, device)
transfers so chunk binning on the main thread overlaps the H2D copy of the
previous chunk. ``depth`` bounds the host copies alive at once: the chunk
being binned plus ``depth`` queued/in-flight uploads — depth=2 is classic
double buffering, and ``submit`` blocking on a full queue is the
backpressure that keeps peak host memory O(chunk).

Every transfer is recorded as a fenced ``data.h2d`` span on the tracer the
uploader was constructed with (captured on the TRAINING thread — the worker
must not fall back to the process-default tracer and lose the spans from
the run's timeline).

Concurrency: every shared attribute is guarded by ``self._cond``'s lock
(rxgblint LOCK001 enforces this statically; the rxgbrace
``stream_upload_double_buffer`` scenario explores the schedule space).
"""

import collections
import threading
import time
from typing import Any, Callable, Dict, Optional

from xgboost_ray_tpu import faults


def _device_transfer(array, device):
    """Default transfer: committed device_put, fenced so the recorded span
    covers the actual copy (module-level indirection so tests and the race
    scenario can stub the jax dependency)."""
    import jax

    out = array if device is None else jax.device_put(array, device)
    return getattr(out, "block_until_ready", lambda: out)()


class DoubleBufferedUploader:
    """Bounded-queue background H2D uploader (see module docstring)."""

    def __init__(
        self,
        depth: int = 2,
        transfer: Optional[Callable[[Any, Any], Any]] = None,
        tracer=None,
    ):
        if depth < 1:
            raise ValueError("depth must be >= 1")
        self.depth = int(depth)
        self._transfer = transfer or _device_transfer
        self._tracer = tracer
        self._cond = threading.Condition()
        self._pending: collections.deque = collections.deque()
        self._results: Dict[Any, Any] = {}
        self._inflight = 0
        self._submitted = 0
        self._error: Optional[BaseException] = None
        self._closed = False
        self._transfer_s = 0.0
        self._bytes = 0
        self._thread = threading.Thread(
            target=self._worker, name="rxgb-stream-h2d"
        )
        self._thread.start()

    # -- producer side -------------------------------------------------------

    def submit(self, key, array, device) -> None:
        """Queue one transfer; blocks while ``depth`` uploads are already
        queued or in flight (the double-buffer backpressure)."""
        # chaos site: fired on the SUBMITTING (training) thread, before the
        # hand-off (and before the lock — a plan-injected delay must model a
        # stalled H2D pipe, not wedge the worker out of the condition), so
        # an injected raise surfaces exactly where a real upload failure
        # does (drain() re-raises worker errors there too). The k-th
        # occurrence IS the k-th submitted transfer.
        faults.fire(
            "stream.h2d_upload",
            bytes=int(getattr(array, "nbytes", 0)),
        )
        with self._cond:
            while (
                len(self._pending) + self._inflight >= self.depth
                and self._error is None
                and not self._closed
            ):
                self._cond.wait()
            if self._error is not None:
                raise RuntimeError("uploader failed") from self._error
            if self._closed:
                raise RuntimeError("uploader is closed")
            self._pending.append((key, array, device))
            self._submitted += 1
            self._cond.notify_all()

    def drain(self) -> Dict[Any, Any]:
        """Wait for every queued transfer; returns {key: device array}.
        Re-raises the first worker error."""
        with self._cond:
            while (self._pending or self._inflight) and self._error is None:
                self._cond.wait()
            if self._error is not None:
                raise RuntimeError("uploader failed") from self._error
            return dict(self._results)

    def close(self) -> None:
        """Drain-free shutdown: stop the worker and join it. Safe to call
        multiple times; pending transfers are abandoned."""
        with self._cond:
            self._closed = True
            self._pending.clear()
            self._cond.notify_all()
        self._thread.join()

    def stats(self) -> Dict[str, float]:
        with self._cond:
            return {
                "transfers": len(self._results),
                "submitted": self._submitted,
                "transfer_s": self._transfer_s,
                "bytes": float(self._bytes),
            }

    # -- worker side ---------------------------------------------------------

    def _worker(self) -> None:
        while True:
            with self._cond:
                while not self._pending and not self._closed:
                    self._cond.wait()
                if self._closed:
                    return
                key, array, device = self._pending.popleft()
                self._inflight += 1
                self._cond.notify_all()
            ts = time.time()
            t0 = time.perf_counter()
            try:
                out = self._transfer(array, device)
                dur = time.perf_counter() - t0
                nbytes = int(getattr(array, "nbytes", 0))
                if self._tracer is not None:
                    self._tracer.add_span(
                        "data.h2d", ts, t0, dur,
                        attrs={"bytes": nbytes, "device": str(device)},
                    )
                with self._cond:
                    self._results[key] = out
                    self._transfer_s += dur
                    self._bytes += nbytes
                    self._inflight -= 1
                    self._cond.notify_all()
            except BaseException as exc:  # noqa: BLE001 - surfaced at drain()
                with self._cond:
                    self._error = exc
                    self._inflight -= 1
                    self._cond.notify_all()
                return

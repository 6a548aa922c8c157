"""Span tracer: bounded ring buffer, JSONL export, and timeline helpers.

One trace record per finished span or instantaneous event, as a plain JSON
dict (the schema the whole repo shares — drivers, tests, CI and ``bench.py``
all validate against :func:`validate_trace_records`):

``{"kind": "span" | "event", "name": str, "ts": float (epoch seconds),
"t0_s": float (``time.perf_counter()`` at the record's start),
"seq": int (monotonic per tracer), "dur_s": float (spans only),
"parent": int | None (enclosing span's seq, spans only),
"round": int (optional — global boosting round), "attrs": dict (optional)}``

``t0_s`` is the process's monotonic clock — the one a harness times its
window on and the one a ``jax.profiler`` session can be dated on (take
``perf_counter()`` inside a ``TraceAnnotation`` marker: a span's ``t0_s``
less the marker's is its place in the device trace). ``ts`` stays for JSONL
streams of several ranks, whose monotonic clocks share no origin.

Design points:

* **Bounded and never silent.** Records live in a fixed-capacity ring
  buffer (``RXGB_TRACE_CAPACITY``, default 8192); when a record would
  overflow, the OLDEST record is dropped and the tracer's ``dropped``
  counter advances — the count is exported in ``snapshot()`` and in
  ``additional_results["obs"]["dropped_spans"]``, so truncation is always
  accounted, never invisible.
* **Nesting via a thread-local stack.** ``span()`` records its enclosing
  span's ``seq`` as ``parent``; children finish (and are appended) before
  their parents, so the record list is end-time ordered while ``seq``
  preserves start order.
* **Streaming.** With ``RXGB_TRACE_DIR`` set (or ``trace_dir=`` passed),
  every record is also appended as one JSON line to
  ``<dir>/trace-rank<k>.jsonl`` (k = the JAX process index when available)
  at emission time — a crash loses at most the last unflushed line, and
  multi-host runs produce one stream per rank.
* **On the profiler's timeline.** When ``jax`` is already imported,
  ``span()`` also enters ``jax.profiler.TraceAnnotation(name)``: a profiler
  trace (``RXGB_PROFILE_DIR``, a harness's own session) then holds the
  program's spans on its host plane beside the device's operations. With no
  session running the annotation is a no-op.
* **Import-light.** Stdlib only: the launcher worker (and ``faults.py``)
  touch this module before any jax import.

This module is process-global-aware: :func:`get_tracer` returns the
thread's installed tracer (``use_tracer``) or a lazily-created process
default, so instrumentation sites never need plumbing.
"""

import collections
import contextlib
import json
import os
import sys
import threading
import time
from typing import Any, Dict, Iterable, List, Optional

__all__ = [
    "DEVICE_SCOPES",
    "TRACE_NAMES",
    "Tracer",
    "get_tracer",
    "set_default_tracer",
    "use_tracer",
    "validate_trace_records",
    "recovery_time_s",
]

_DEFAULT_CAPACITY = 8192

#: The declared catalog of every span/event name the runtime emits — the
#: single source of truth rxgblint's OBS001 checks emission sites against
#: (both directions: an uncatalogued emission and a never-emitted catalog
#: entry are each findings), and the optional ``known_names`` vocabulary
#: for :func:`validate_trace_records`. Grouped by emitting layer.
TRACE_NAMES = frozenset({
    # one compiled dispatch (engine.py step / step_many / step_dart /
    # step_vmapped): ``dispatch`` with its two halves as children —
    # ``dispatch.enqueue`` (argument assembly, trace / lower / compile or
    # cache load on a first call, launch: entry until the jitted call
    # returns) and ``dispatch.wait`` (the host blocked on the metric
    # readback) — and one ``round`` record per boosting round inside it;
    # ``dispatch.args`` (``program``) inside the enqueue is the argument
    # assembly up to the jitted call (PRNG key, eval sets, group rows,
    # bounds, a custom objective's gh upload, the scan's iterations)
    "dispatch", "dispatch.enqueue", "dispatch.wait", "round",
    "dispatch.args",
    # every compile of the process (obs/compiles.py, one jax.monitoring
    # listener), parented to whatever span is open on the compiling thread
    "compile.trace", "compile.lower", "compile.backend",
    "compile.cache_load",
    # one train() call's set-up (main.py, engine.py __init__): matrices ->
    # host shards, engine construction, and inside it the row uploads
    # (data.h2d, below) and the sketch + bin program
    "data.load", "engine.init", "data.sketch_bin",
    # host time between dispatches (main.py round loops); inside a save,
    # the booster's readback (``entries``, ``trees``, ``bytes`` moved in
    # ``transfers`` batched host reads: 1, or 0 with nothing pending) and
    # its serialisation (``bytes`` of the pickle, ``trees`` in the forest)
    "driver.checkpoint", "driver.callbacks",
    "checkpoint.readback", "checkpoint.serialise",
    # streamed ingestion (stream/ingest.py + stream/upload.py): one fenced
    # span per sketch/bin chunk and per H2D transfer, one per cuts merge —
    # a streamed load is reconstructible from the timeline alone
    "data.sketch_chunk", "data.bin_chunk", "data.h2d", "data.cuts_merge",
    # elastic continuation of a streamed world (stream/ingest.py): donor
    # binned-row reuse — one summary event per reuse pass plus one fenced
    # span per donor block fetch; a shrink that re-used every survivor
    # shard shows bin_reuse spans and NO sketch_chunk/bin_chunk after the
    # kill (the zero-re-stream contract, asserted from the timeline)
    "data.bin_reuse",
    # driver lifecycle (main.py)
    "attempt", "failure.detected", "recovered", "backoff",
    "world.shrink", "world.grow", "world.resume", "world.restart",
    "checkpoint.commit", "allreduce.bytes",
    # after training: the radix the dense build factored its bin index by
    # and the feature tiles a row chunk took at every width (2 * node slots)
    # its round programs traced, and a level-wise round's tile steps
    "hist.builds",
    # after training with grow_policy=lossguide: what the leaf-wise grower
    # counted on the device a round (full-row passes, nodes evaluated,
    # splits kept), the forest's deepest leaf, and the wanted nodes its
    # table had no room for
    "lossguide.grow",
    # failure domains (main.py): domain_down when a failure takes a whole
    # domain's last alive rank (one per lost domain, beside the single
    # coalesced world.shrink), deaths_coalesced when one shrink absorbed
    # multiple near-simultaneous deaths (ranks + how many were folded),
    # domain_up when an atomic domain grow-back makes the domain whole —
    # a host-loss incident reads domain_down -> deaths_coalesced ->
    # world.shrink -> elastic.ready -> world.grow -> domain_up
    "world.domain_down", "world.domain_up", "world.deaths_coalesced",
    # elastic scheduler (elastic.py)
    "elastic.reschedule", "elastic.ready",
    # launcher (launcher.py)
    "launcher.spawn", "launcher.hung", "launcher.attempt_failed",
    "checkpoint.load",
    # fault injection (faults.py)
    "fault.injected",
    # vectorized HPO (tuner.py): ASHA lane pruning inside a vmapped-K
    # program — one lane_prune event per pruned lane (original candidate
    # id + the rung metric that lost), one repack event per successive-
    # halving re-pack (k_before -> k_after)
    "hpo.lane_prune", "hpo.repack",
    # serving scale-out (serve/pool.py, serve/autoscale.py,
    # serve/canary.py): one route event per replica dispatch, one
    # replica_up/replica_down per pool membership change (reason:
    # scale_up | scale_down | rejoin | killed | shutdown), one scale
    # event per autoscaler decision (direction + from/to replica counts
    # + the p99/queue evidence), and the canary verdict pair — shadow
    # (candidate-vs-live divergence on mirrored traffic) then either
    # rollback (gate regression, old model keeps serving) or promote
    # (drain-then-flip committed). A scale-up -> scale-down cycle and a
    # replica-loss chaos run are each reconstructible from these alone
    # (asserted by tests/test_serve_pool.py).
    "serve.route", "serve.replica_up", "serve.replica_down", "serve.scale",
    "serve.shadow", "serve.rollback", "serve.promote",
})

#: The ``jax.named_scope`` vocabulary of the compiled programs (engine.py
#: ``_round_closures`` / ``_sketch_and_bin``, ops/grow.py, ops/
#: grow_lossguide.py): trace-time metadata of the HLO, so a profiler trace
#: names the device's operations by phase whatever XLA numbers its fusions.
#: A round nests ``tree`` > ``level{d}`` > {``hist``, ``allreduce``,
#: ``split``, ``partition``}; ``level`` stands for ``level0``, ``level1`` ...
#: The leaf-wise grower's levels are a loop: its root is ``level0``, every
#: later pass ``level`` (no number), and ``tree`` > ``select`` is the
#: best-first replay between levels.
#: :func:`xgboost_ray_tpu.obs.device.scope_times` reads them back.
DEVICE_SCOPES = frozenset({
    "objective", "quantize_gh", "sample", "tree", "level", "hist",
    "allreduce", "split", "partition", "select", "margin", "eval_walk",
    "metrics", "sketch", "bin",
})


def _process_rank() -> int:
    """This process's rank for trace-file naming; 0 when jax is absent or
    uninitialized (single-host)."""
    try:
        import jax

        return int(jax.process_index())
    except Exception:  # noqa: BLE001 - tracing must never fail the caller
        return 0


class Tracer:
    """Span/event recorder with a bounded ring buffer.

    ``enabled`` defaults from ``RXGB_TRACE`` (on unless ``"0"``);
    ``capacity`` from ``RXGB_TRACE_CAPACITY``; ``trace_dir`` from
    ``RXGB_TRACE_DIR`` (empty = no streaming). A disabled tracer's
    ``span()``/``event()`` are near-free no-ops.
    """

    def __init__(
        self,
        capacity: Optional[int] = None,
        enabled: Optional[bool] = None,
        trace_dir: Optional[str] = None,
        rank: Optional[int] = None,
    ):
        if enabled is None:
            enabled = os.environ.get("RXGB_TRACE", "1") != "0"
        if capacity is None:
            capacity = int(
                os.environ.get("RXGB_TRACE_CAPACITY", str(_DEFAULT_CAPACITY))
            )
        if trace_dir is None:
            trace_dir = os.environ.get("RXGB_TRACE_DIR", "")
        self.enabled = bool(enabled)
        self.capacity = max(1, int(capacity))
        self._buf: collections.deque = collections.deque(maxlen=self.capacity)
        self._lock = threading.Lock()
        self._tls = threading.local()
        self._seq = 0
        self._dropped = 0
        self._trace_dir = trace_dir or ""
        self._rank = rank
        self._stream_file = None
        self._stream_failed = False

    # -- recording ----------------------------------------------------------

    @property
    def dropped(self) -> int:
        with self._lock:
            return self._dropped

    def _next_seq_locked(self) -> int:
        # _locked suffix = caller holds self._lock (enforced by rxgblint
        # LOCK001 on both ends: this method may touch shared state bare,
        # and every call site must sit inside `with self._lock`)
        self._seq += 1
        return self._seq

    def _append(self, rec: Dict[str, Any]) -> None:
        with self._lock:
            if len(self._buf) == self.capacity:
                self._dropped += 1
            self._buf.append(rec)
            self._stream_locked(rec)

    def _stream_locked(self, rec: Dict[str, Any]) -> None:
        """Append one JSON line to the per-rank trace file (best-effort;
        caller holds the lock)."""
        if not self._trace_dir or self._stream_failed:
            return
        try:
            if self._stream_file is None:
                rank = self._rank if self._rank is not None else _process_rank()
                self._rank = rank
                os.makedirs(self._trace_dir, exist_ok=True)
                path = os.path.join(self._trace_dir, f"trace-rank{rank}.jsonl")
                self._stream_file = open(path, "a", buffering=1)
            # default=str: attrs are caller-supplied (span() hands out the
            # mutable dict) — a numpy scalar or exotic value must degrade to
            # its string form, never raise out of the instrumented code
            self._stream_file.write(json.dumps(rec, default=str) + "\n")
        except Exception:  # noqa: BLE001 - tracing must never fail the caller
            # a dead disk must not take training down; the in-memory ring
            # still has the records
            self._stream_failed = True

    @contextlib.contextmanager
    def span(self, name: str, round: Optional[int] = None, **attrs):
        """Context manager recording one fenced span; yields the (mutable)
        attrs dict so callers can attach results measured inside."""
        if not self.enabled:
            yield attrs
            return
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        with self._lock:
            seq = self._next_seq_locked()
        parent = stack[-1] if stack else None
        stack.append(seq)
        # on the profiler's host plane too, once jax is up (no-op with no
        # profiler session; this module itself never imports jax)
        jax = sys.modules.get("jax")
        note = (
            jax.profiler.TraceAnnotation(name) if jax is not None
            else contextlib.nullcontext()
        )
        ts = time.time()
        t0 = time.perf_counter()
        try:
            with note:
                yield attrs
        finally:
            dur = time.perf_counter() - t0
            stack.pop()
            self._finish_span(name, ts, t0, dur, seq, parent, round, attrs)

    def add_span(
        self,
        name: str,
        ts: float,
        t0_s: float,
        dur_s: float,
        round: Optional[int] = None,
        attrs: Optional[Dict[str, Any]] = None,
    ) -> None:
        """Record an externally-timed span: ``ts`` / ``t0_s`` are its start
        on the epoch and the ``perf_counter`` clock. Its parent is the span
        open on this thread, if any (no nesting bookkeeping of its own)."""
        if not self.enabled:
            return
        with self._lock:
            seq = self._next_seq_locked()
        stack = getattr(self._tls, "stack", None)
        parent = stack[-1] if stack else None
        self._finish_span(name, ts, t0_s, dur_s, seq, parent, round, attrs)

    def _finish_span(self, name, ts, t0_s, dur_s, seq, parent, round, attrs):
        rec: Dict[str, Any] = {
            "kind": "span",
            "name": name,
            "ts": ts,
            "t0_s": float(t0_s),
            "seq": seq,
            "dur_s": float(dur_s),
            "parent": parent,
        }
        if round is not None:
            rec["round"] = int(round)
        if attrs:
            rec["attrs"] = dict(attrs)
        self._append(rec)

    def event(
        self,
        name: str,
        round: Optional[int] = None,
        attrs: Optional[Dict[str, Any]] = None,
        **kw,
    ) -> None:
        """Record one instantaneous event; attributes may come as an
        ``attrs`` dict, keyword arguments, or both (merged, kwargs win)."""
        if not self.enabled:
            return
        merged = dict(attrs) if attrs else {}
        merged.update(kw)
        with self._lock:
            seq = self._next_seq_locked()
        rec: Dict[str, Any] = {
            "kind": "event",
            "name": name,
            "ts": time.time(),
            "t0_s": time.perf_counter(),
            "seq": seq,
        }
        if round is not None:
            rec["round"] = int(round)
        if merged:
            rec["attrs"] = merged
        self._append(rec)

    # -- reading / export ---------------------------------------------------

    def records(self) -> List[Dict[str, Any]]:
        """Snapshot of the ring buffer (oldest first)."""
        with self._lock:
            return list(self._buf)

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            return {
                "records": len(self._buf),
                "dropped_spans": self._dropped,
                "capacity": self.capacity,
            }

    def export_jsonl(self, path: str) -> int:
        """Write the buffered records as JSON lines; returns record count.
        Non-JSON-serializable attr values degrade to their string form."""
        recs = self.records()
        with open(path, "w") as f:
            for rec in recs:
                f.write(json.dumps(rec, default=str) + "\n")
        return len(recs)

    def close(self) -> None:
        with self._lock:
            if self._stream_file is not None:
                try:
                    self._stream_file.close()
                except OSError:  # pragma: no cover - close is best-effort
                    pass
                self._stream_file = None


# ---------------------------------------------------------------------------
# current-tracer plumbing: thread-local install (train() scopes a fresh
# tracer per run) over a lazily-created process default (launcher-level
# spans outside any train() land there).
# ---------------------------------------------------------------------------

_default_tracer: Optional[Tracer] = None
_default_lock = threading.Lock()
_tls = threading.local()


def get_tracer() -> Tracer:
    """The thread's installed tracer, else the process-default tracer."""
    current = getattr(_tls, "current", None)
    if current is not None:
        return current
    global _default_tracer
    if _default_tracer is None:
        with _default_lock:
            if _default_tracer is None:
                _default_tracer = Tracer()
    return _default_tracer


def set_default_tracer(tracer: Optional[Tracer]) -> None:
    """Replace the process-default tracer (None resets to lazy re-create)."""
    global _default_tracer
    _default_tracer = tracer


@contextlib.contextmanager
def use_tracer(tracer: Tracer):
    """Install ``tracer`` as this thread's current tracer for the scope."""
    prev = getattr(_tls, "current", None)
    _tls.current = tracer
    try:
        yield tracer
    finally:
        _tls.current = prev


# ---------------------------------------------------------------------------
# schema validation + timeline queries (shared by tests, CI and bench.py)
# ---------------------------------------------------------------------------

_ALLOWED_KEYS = {
    "kind", "name", "ts", "t0_s", "seq", "dur_s", "parent", "round", "attrs",
}


def validate_trace_records(
    records: Iterable[Dict[str, Any]],
    known_names: Optional[Iterable[str]] = None,
) -> List[str]:
    """Validate records against the trace schema; returns a list of problem
    strings (empty = valid). Exported at package top level so tests and the
    CI example (``examples/trace_run.py``) share one checker.

    ``known_names`` opts into vocabulary checking: pass :data:`TRACE_NAMES`
    (or any custom set) and a record whose ``name`` is outside it becomes a
    problem — the runtime counterpart of rxgblint's static OBS001 check.
    The default (``None``) keeps the historical schema-only behavior."""
    problems: List[str] = []
    seen_seq = set()
    name_vocab = None if known_names is None else set(known_names)
    for i, rec in enumerate(records):
        where = f"record {i}"
        if not isinstance(rec, dict):
            problems.append(f"{where}: not a dict")
            continue
        unknown = set(rec) - _ALLOWED_KEYS
        if unknown:
            problems.append(f"{where}: unknown keys {sorted(unknown)}")
        kind = rec.get("kind")
        if kind not in ("span", "event"):
            problems.append(f"{where}: bad kind {kind!r}")
        name = rec.get("name")
        if not isinstance(name, str) or not name:
            problems.append(f"{where}: bad name {name!r}")
        elif name_vocab is not None and name not in name_vocab:
            problems.append(f"{where}: unknown name {name!r}")
        for clock in ("ts", "t0_s"):
            if not isinstance(rec.get(clock), (int, float)):
                problems.append(f"{where}: bad {clock} {rec.get(clock)!r}")
        seq = rec.get("seq")
        if not isinstance(seq, int):
            problems.append(f"{where}: bad seq {seq!r}")
        elif seq in seen_seq:
            problems.append(f"{where}: duplicate seq {seq}")
        else:
            seen_seq.add(seq)
        if kind == "span":
            dur = rec.get("dur_s")
            if not isinstance(dur, (int, float)) or dur < 0:
                problems.append(f"{where}: bad dur_s {dur!r}")
            parent = rec.get("parent")
            if parent is not None and not isinstance(parent, int):
                problems.append(f"{where}: bad parent {parent!r}")
        if "round" in rec and not isinstance(rec["round"], int):
            problems.append(f"{where}: bad round {rec['round']!r}")
        if "attrs" in rec and not isinstance(rec["attrs"], dict):
            problems.append(f"{where}: bad attrs {rec['attrs']!r}")
        if kind == "event" and "dur_s" in rec:
            problems.append(f"{where}: event carries dur_s")
    return problems


def recovery_time_s(records: Iterable[Dict[str, Any]]) -> float:
    """Total failure→first-forward-progress time reconstructed from the
    timeline: each ``recovered`` event closes the clock opened by the most
    recent ``failure.detected`` event (matching the driver's
    ``time_to_recover_s`` accounting, which restarts the clock on repeated
    failures before progress)."""
    total = 0.0
    last_failure: Optional[float] = None
    for rec in records:
        if rec.get("kind") != "event":
            continue
        if rec.get("name") == "failure.detected":
            last_failure = float(rec["ts"])
        elif rec.get("name") == "recovered" and last_failure is not None:
            total += max(0.0, float(rec["ts"]) - last_failure)
            last_failure = None
    return total

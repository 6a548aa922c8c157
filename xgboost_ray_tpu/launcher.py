"""Driver-level multi-process launcher with automatic restart-from-checkpoint.

The SPMD failure model (SURVEY §5.8): when any process of a
``jax.distributed`` world dies, the coordination service TERMINATES the
survivors with a fatal diagnostic — there is no Python exception to catch
mid-collective, so recovery must live ABOVE the world, at the driver level.
The reference solves the same problem with its retry loop
(``xgboost_ray/main.py:1606-1713``): detect dead actors, re-create them, and
restart training from the last checkpoint. ``launch_distributed`` is that
loop for real process worlds: it spawns the per-process workers, watches for
any death, tears the attempt down, and respawns the whole world — the
workers resume from the newest checkpoint via ``load_round_checkpoint``.

In the single-host CPU-mesh rehearsal one launcher supervises the whole
world. On a multi-host pod, run one launcher per host with
``local_process_ids`` set to that host's process ids and a fixed
``coordinator_address``: a death anywhere kills every process (the
coordination service guarantees it), so every host's launcher observes its
local children die and independently respawns them — the world re-forms at
the same coordinator with the attempt counter advanced, and training resumes
from the shared checkpoint.

Worker functions must be module-level (pickled by reference into the spawned
interpreter) with signature ``fn(ctx, *args)``; see ``LaunchContext`` for
what they receive. The canonical training worker:

    def train_worker(ctx, data_path):
        booster, done = load_round_checkpoint(ctx.checkpoint_path)
        shards = ...  # THIS process's rows
        eng = TpuEngine(shards, params, num_actors=W, init_booster=booster)
        with AsyncCheckpointWriter() as ckpt:  # commits off the round loop
            for i in range(total_rounds - done):
                eng.step(i)
                ckpt.submit(eng.get_booster(), ctx.checkpoint_path, done + i)
        return eng.get_booster().save_raw()
"""

import dataclasses
import glob
import hashlib
import logging
import os
import pickle
import re
import shutil
import socket
import subprocess
import sys
import tempfile
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from xgboost_ray_tpu import faults, obs
from xgboost_ray_tpu.util import restart_backoff_s

logger = logging.getLogger(__name__)

__all__ = [
    "LaunchContext",
    "LaunchResult",
    "ProcessFailure",
    "LaunchFailedError",
    "launch_distributed",
    "save_round_checkpoint",
    "load_round_checkpoint",
    "AsyncCheckpointWriter",
]


@dataclasses.dataclass(frozen=True)
class LaunchContext:
    """What every worker process receives as its first argument."""

    process_id: int
    num_processes: int
    coordinator_address: str
    attempt: int  # 0 on the first try, +1 per world restart
    checkpoint_path: Optional[str]
    # per-process liveness file for the launcher's hang watchdog; workers
    # call ``ctx.heartbeat()`` each round (cheap mtime touch)
    heartbeat_path: Optional[str] = None

    def heartbeat(self) -> None:
        """Touch this process's heartbeat file (no-op when the launcher did
        not arm the watchdog). Must never fail the worker."""
        if not self.heartbeat_path:
            return
        try:
            with open(self.heartbeat_path, "w") as f:
                f.write(str(time.time()))
        except OSError:  # pragma: no cover - liveness is best-effort
            pass


@dataclasses.dataclass(frozen=True)
class ProcessFailure:
    attempt: int
    process_id: int
    returncode: int
    log_tail: str
    # True when the LAUNCHER force-killed this process during teardown;
    # False when it died on its own (the injected fault, the coordination
    # service's survivor termination, or a surfaced Python exception)
    forced: bool = False
    # why this process went down: "crashed" (nonzero exit on its own),
    # "hung" (killed because the world's heartbeats stalled past
    # hang_timeout_s — world-level: a wedged collective stalls every
    # member), "slow" (the whole-world timeout_s expired), or "torn_down"
    # (healthy peer killed while the launcher tore a crashed world down)
    reason: str = "crashed"
    # fault-domain attribution (RXGB_FAULT_DOMAINS logical partition of the
    # process space, same layout as the elastic plane's); None = no
    # partition configured
    domain: Optional[int] = None


@dataclasses.dataclass
class LaunchResult:
    results: List[Any]  # worker_fn return value per LOCAL process
    restarts: int  # world restarts that were needed
    failures: List[ProcessFailure]  # every observed process death


class LaunchFailedError(RuntimeError):
    def __init__(self, message: str, failures: List[ProcessFailure]):
        super().__init__(message)
        self.failures = failures


def _process_domain(process_id: int, num_processes: int) -> Optional[int]:
    """Fault-domain of a launcher process under the ``RXGB_FAULT_DOMAINS``
    logical partition (the same contiguous layout the elastic plane uses),
    or None when no partition is configured — correlates cross-process
    failures ("both deaths were domain 1") in ProcessFailure records and
    the ``launcher.attempt_failed`` timeline event."""
    from xgboost_ray_tpu.domains import logical_domain_of

    raw = os.environ.get("RXGB_FAULT_DOMAINS", "")
    try:
        h = int(raw) if raw else 0
    except ValueError:
        h = 0
    if h <= 0 or num_processes <= 0:
        return None
    return logical_domain_of(process_id, num_processes, h)


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _sha256_file(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


def _history_path(path: str, completed_round: int) -> str:
    return f"{path}.r{int(completed_round):06d}"


def _history_candidates(path: str) -> List[str]:
    """Retained history checkpoints for ``path``, newest round first."""
    pat = re.compile(re.escape(os.path.basename(path)) + r"\.r(\d{6})$")
    out = []
    for p in glob.glob(glob.escape(path) + ".r??????"):
        m = pat.match(os.path.basename(p))
        if m:
            out.append((int(m.group(1)), p))
    return [p for _, p in sorted(out, reverse=True)]


def _fsync_file(path: str) -> None:
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def _fsync_dir(path: str) -> None:
    """Durably commit a rename by fsyncing the containing directory (a
    crash after ``os.replace`` but before the directory entry hits disk can
    otherwise resurrect the OLD file — or nothing). Best-effort: some
    filesystems refuse directory fds."""
    try:
        fd = os.open(path or ".", os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:  # pragma: no cover - fs-dependent
        pass
    finally:
        os.close(fd)


def save_round_checkpoint(
    booster, path: str, completed_round: int, keep_last: Optional[int] = None,
    fsync: bool = True,
) -> None:
    """Atomically persist ``booster`` + the round it completed (the driver's
    rank-0 checkpoint role, reference ``main.py:612-626``). The MODEL rename
    is the single commit point — the ``.round`` marker is advisory (humans /
    monitoring) and never read back, so a death between the two renames
    cannot desynchronize resume arithmetic.

    Durability: the temp file is fsynced BEFORE the atomic rename (and the
    directory entry after), so a host crash cannot leave a zero-length or
    partially-written "newest" checkpoint behind the committed name —
    ``fsync=False`` opts out for tests/tmpfs.

    Integrity + retention (the hardened resume path): every commit also
    writes a ``.sha256`` sidecar and retains the last ``keep_last``
    checkpoints as independent ``{path}.rNNNNNN`` copies (default
    ``RXGB_CHECKPOINT_KEEP``, 2; 0 disables retention) — so a corrupt or
    truncated newest checkpoint makes ``load_round_checkpoint`` fall back
    to the previous good one instead of killing the resume path.

    This runs serialization + write + fsync on the CALLING thread; round
    loops should submit through :class:`AsyncCheckpointWriter` so the write
    overlaps the next rounds instead of stalling them."""
    if keep_last is None:
        keep_last = int(os.environ.get("RXGB_CHECKPOINT_KEEP", "2"))
    tmp = f"{path}.tmp"
    booster.save_model(tmp)
    digest = _sha256_file(tmp)
    if fsync:
        _fsync_file(tmp)
    os.replace(tmp, path)
    stmp = f"{path}.sha256.tmp"
    with open(stmp, "w") as f:
        f.write(digest)
    os.replace(stmp, f"{path}.sha256")
    rtmp = f"{path}.round.tmp"
    with open(rtmp, "w") as f:
        f.write(str(int(completed_round)))
    os.replace(rtmp, f"{path}.round")
    if keep_last > 0:
        # independent COPY (not a hardlink): single-inode corruption of the
        # live file must not take the retained fallback down with it
        hist = _history_path(path, completed_round)
        shutil.copyfile(path, f"{hist}.tmp")
        os.replace(f"{hist}.tmp", hist)
        with open(f"{hist}.sha256.tmp", "w") as f:
            f.write(digest)
        os.replace(f"{hist}.sha256.tmp", f"{hist}.sha256")
        for stale in _history_candidates(path)[keep_last:]:
            for victim in (stale, f"{stale}.sha256"):
                try:
                    os.remove(victim)
                except OSError:
                    pass
    if fsync:
        _fsync_dir(os.path.dirname(path))
    obs.get_tracer().event(
        "checkpoint.commit", round=int(completed_round),
        attrs={"path": path, "bytes": os.path.getsize(path)},
    )
    # chaos hook LAST: a corrupt/truncate rule damages the COMMITTED newest
    # checkpoint (post-write disk corruption), which load must survive
    faults.fire_file("checkpoint.save", path, round=int(completed_round))


class AsyncCheckpointWriter:
    """Background checkpoint writes for the round loop.

    ``save_round_checkpoint`` serializes, writes and fsyncs on the calling
    thread — at production model sizes that stalls the boosting loop for the
    full commit. ``submit()`` hands the (immutable) booster snapshot to a
    background thread instead; ``wait()`` joins the in-flight write and
    re-raises its failure, and is invoked automatically by the next
    ``submit()`` — so at most one write is ever in flight, checkpoints
    commit strictly in round order, and a write error surfaces at the next
    round boundary instead of being dropped. Use as a context manager so
    the final write is joined (and its errors surfaced) before the worker
    returns::

        with AsyncCheckpointWriter() as ckpt:
            for i in range(rounds):
                eng.step(i)
                ckpt.submit(eng.get_booster(), path, done + i)
    """

    def __init__(self):
        self._thread: Optional[threading.Thread] = None
        self._exc: Optional[BaseException] = None

    def submit(self, booster, path: str, completed_round: int,
               keep_last: Optional[int] = None, fsync: bool = True) -> None:
        """Queue one checkpoint commit; joins the previous one first."""
        self.wait()

        def _write():
            try:
                save_round_checkpoint(
                    booster, path, completed_round,
                    keep_last=keep_last, fsync=fsync,
                )
            except BaseException as exc:  # noqa: BLE001 - re-raised by wait()
                self._exc = exc

        self._thread = threading.Thread(
            target=_write, name="rxgb-ckpt-writer", daemon=True
        )
        self._thread.start()

    def wait(self, timeout: Optional[float] = None) -> bool:
        """Join the in-flight write (if any); re-raise its failure. Returns
        True when nothing is left in flight.

        With ``timeout`` the join is BOUNDED: if the write is still running
        after that many seconds (a hung disk, an injected ``checkpoint.save``
        hang), the writer thread is left behind (it is a daemon, so it can
        never wedge interpreter exit), a loud error is logged, and False is
        returned — the caller knows the newest checkpoint is unconfirmed.
        The thread handle is kept, so a later unbounded ``wait()`` can still
        collect a slow-but-alive write."""
        thread = self._thread
        if thread is not None:
            thread.join(timeout)
            if thread.is_alive():
                logger.error(
                    "[RayXGBoost] background checkpoint write still running "
                    "after %.1fs; abandoning the join (daemon thread '%s') — "
                    "the most recent checkpoint is NOT confirmed on disk.",
                    timeout if timeout is not None else -1.0, thread.name,
                )
                return False
            self._thread = None
        # read the outcome only once the thread is provably finished — a
        # timed-out join must not race the writer's error store
        exc, self._exc = self._exc, None
        if exc is not None:
            raise exc
        return True

    @staticmethod
    def _exit_join_timeout() -> Optional[float]:
        """Bounded-join budget for context-manager exit (driver shutdown):
        ``RXGB_CKPT_EXIT_JOIN_S`` seconds, default 60; <= 0 restores the
        unbounded pre-hardening join."""
        t = float(os.environ.get("RXGB_CKPT_EXIT_JOIN_S", "60"))
        return t if t > 0 else None

    def __enter__(self) -> "AsyncCheckpointWriter":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        # bounded on BOTH paths: a commit hung on dead storage must not
        # wedge driver exit (the write thread is a daemon; wait() already
        # logged loudly if it had to abandon the join)
        if exc_type is None:
            self.wait(timeout=self._exit_join_timeout())
        else:
            # don't mask the in-flight exception with a checkpoint error
            try:
                self.wait(timeout=self._exit_join_timeout())
            except BaseException as ckpt_exc:  # noqa: BLE001
                logger.warning(
                    "[RayXGBoost] background checkpoint write failed during "
                    "error teardown: %s", ckpt_exc,
                )
        return False


def _checkpoint_sha_ok(path: str) -> Optional[bool]:
    """True/False against the ``.sha256`` sidecar, None when there is no
    (readable) sidecar to check against."""
    sidecar = f"{path}.sha256"
    if not os.path.exists(sidecar):
        return None
    try:
        with open(sidecar) as f:
            expected = f.read().strip()
        if not expected:
            return None
        return _sha256_file(path) == expected
    except OSError:
        return None


def _parse_checkpoint(path: str) -> Optional[Any]:
    """Parse one checkpoint file; None when it is corrupt/truncated."""
    import json

    try:
        with open(path) as f:
            doc = json.load(f)
        # dispatch on the document's booster (gblinear checkpoints carry the
        # xgboost gblinear learner schema, trees our native format)
        name = doc.get("learner", {}).get("gradient_booster", {}).get("name")
        if name == "gblinear":
            from xgboost_ray_tpu.linear import RayLinearBooster

            return RayLinearBooster.import_xgboost_json(doc)
        from xgboost_ray_tpu.models.booster import RayXGBoostBooster

        return RayXGBoostBooster._from_dict(doc)
    except Exception as exc:  # noqa: BLE001 - any parse failure -> fallback
        logger.warning(
            "[RayXGBoost] checkpoint %s is unreadable (%s: %s); treating "
            "as corrupt.", path, type(exc).__name__, exc,
        )
        return None


def load_round_checkpoint(path: Optional[str]) -> Tuple[Optional[Any], int]:
    """(booster, completed_rounds) from the newest GOOD checkpoint, or
    (None, 0) when none exists yet. ``completed_rounds`` comes from the
    atomically committed model itself (``num_boosted_rounds``), never the
    advisory ``.round`` file — a kill between the checkpoint's two renames
    must not make the resumed world recount.

    A corrupt/truncated/sha-mismatched newest checkpoint falls back to the
    newest retained ``{path}.rNNNNNN`` copy that validates (replaying the
    rounds in between) instead of crashing the resume path; only when every
    candidate is bad does the world restart from scratch — loudly."""
    if not path:
        return None, 0
    faults.fire("checkpoint.load", path=path)
    candidates = [path] + _history_candidates(path)
    existing = [c for c in candidates if os.path.exists(c)]
    sha_mismatched: List[str] = []
    for cand in existing:
        if _checkpoint_sha_ok(cand) is False:
            logger.warning(
                "[RayXGBoost] checkpoint %s fails its sha256 sidecar; "
                "treating as corrupt.", cand,
            )
            sha_mismatched.append(cand)
            continue
        booster = _parse_checkpoint(cand)
        if booster is not None:
            if cand != path:
                logger.warning(
                    "[RayXGBoost] newest checkpoint %s is corrupt; resuming "
                    "from retained fallback %s (%d rounds).",
                    path, cand, booster.num_boosted_rounds(),
                )
            obs.get_tracer().event(
                "checkpoint.load",
                attrs={"rounds": booster.num_boosted_rounds(),
                       "fallback": cand != path},
            )
            return booster, booster.num_boosted_rounds()
    # no candidate passed integrity. A sha mismatch can also be a STALE
    # sidecar (a kill between the model rename and the sidecar rename), so
    # before abandoning the run to round 0, accept the newest mismatched
    # candidate that still parses — a valid checkpoint beats none.
    for cand in sha_mismatched:
        booster = _parse_checkpoint(cand)
        if booster is not None:
            logger.warning(
                "[RayXGBoost] no checkpoint for %s passes integrity; "
                "resuming from sha-mismatched but parseable %s (%d rounds) "
                "— likely a torn sidecar write.",
                path, cand, booster.num_boosted_rounds(),
            )
            return booster, booster.num_boosted_rounds()
    if existing:
        logger.error(
            "[RayXGBoost] every checkpoint candidate for %s is corrupt "
            "(%d tried); restarting training from round 0.",
            path, len(existing),
        )
    return None, 0


def _tail(path: str, limit: int = 4000) -> str:
    try:
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            size = f.tell()
            f.seek(max(0, size - limit))
            return f.read().decode("utf-8", errors="replace")
    except OSError:
        return ""


def launch_distributed(
    worker_fn: Callable,
    num_processes: int,
    *,
    args: tuple = (),
    checkpoint_path: Optional[str] = None,
    max_restarts: int = 2,
    local_process_ids: Optional[Sequence[int]] = None,
    coordinator_address: Optional[str] = None,
    env: Optional[Dict[str, str]] = None,
    timeout_s: float = 900.0,
    poll_interval: float = 0.25,
    survivor_grace_s: float = 150.0,
    hang_timeout_s: Optional[float] = None,
) -> LaunchResult:
    """Run ``worker_fn(ctx, *args)`` in a ``num_processes``-process
    ``jax.distributed`` world, restarting the WHOLE world from the latest
    checkpoint when any process dies (up to ``max_restarts`` times).

    ``worker_fn`` must be a module-level callable (pickled by reference).
    Each spawned process joins the world before the fn runs; the fn's return
    value is pickled back. ``env`` entries override the inherited
    environment (e.g. ``JAX_PLATFORMS=cpu`` plus
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` for the CPU-mesh
    rehearsal).

    This is a MULTI-HOST facility: one process per host, each owning all of
    its host's chips. On a pod, pass this host's ``local_process_ids`` and
    the shared ``coordinator_address``. The single-host default (all
    ``num_processes`` spawned locally with a fresh loopback coordinator per
    attempt) is a CPU rehearsal only — a chip belongs to one process, so on
    one TPU host every local child would try to take all the chips and all
    but the first fail or hang. Chips are not partitioned among local
    processes; on one host, one process drives every chip through the mesh.

    On a process death, survivors get ``survivor_grace_s`` to exit on their
    own (the coordination service terminates them — with default heartbeat
    settings detection takes up to ~100 s, so the grace must exceed it; a
    Python-level surfaced failure exits sooner) before being force-killed — so ``failures`` records
    whether each process surfaced the failure itself (``forced=False``) or
    had to be torn down (``forced=True``).

    ``hang_timeout_s`` arms the heartbeat watchdog: workers call
    ``ctx.heartbeat()`` each round, and a world whose heartbeats stall
    longer than this is flagged ``hung`` and restarted long before the
    global ``timeout_s`` — set it above the worst-case round (plus compile)
    time. ``failures[*].reason`` distinguishes ``hung`` / ``slow`` (global
    timeout) / ``crashed`` / ``torn_down``. Between attempts the launcher
    backs off exponentially with jitter (``RXGB_RESTART_BACKOFF_*``;
    base 0 disables) so a persistent fault cannot crash-loop storm.
    """
    if num_processes < 1:
        raise ValueError("num_processes must be >= 1")
    local_ids = (
        list(local_process_ids)
        if local_process_ids is not None
        else list(range(num_processes))
    )
    if any(i < 0 or i >= num_processes for i in local_ids):
        raise ValueError(
            f"local_process_ids {local_ids} out of range for "
            f"num_processes={num_processes}"
        )
    # pickle-by-reference sanity check up front (spawned interpreters import
    # the fn's module; a lambda/closure would die remotely with a worse error)
    try:
        payload_fn = pickle.dumps((worker_fn, tuple(args)))
    except Exception as exc:
        raise ValueError(
            f"worker_fn/args must be picklable module-level objects "
            f"(got {exc})"
        ) from exc

    scratch = tempfile.mkdtemp(prefix="rxgb_launch_")
    fn_mod_dir = None
    mod = sys.modules.get(getattr(worker_fn, "__module__", ""), None)
    mod_file = getattr(mod, "__file__", None)
    if mod_file:
        fn_mod_dir = os.path.dirname(os.path.abspath(mod_file))

    failures: List[ProcessFailure] = []
    try:
        return _run_attempts(
            payload_fn, num_processes, local_ids, checkpoint_path,
            coordinator_address, env, fn_mod_dir, scratch, timeout_s,
            poll_interval, survivor_grace_s, max_restarts, failures,
            hang_timeout_s,
        )
    finally:
        import shutil

        # failure log tails are already captured into the ProcessFailure
        # records (and into the raised error), so the scratch dir never
        # needs to outlive the call
        shutil.rmtree(scratch, ignore_errors=True)


def _run_attempts(
    payload_fn, num_processes, local_ids, checkpoint_path,
    coordinator_address, env, fn_mod_dir, scratch, timeout_s,
    poll_interval, survivor_grace_s, max_restarts, failures,
    hang_timeout_s=None,
) -> LaunchResult:
    restarts = 0
    attempt = 0
    consecutive_failures = 0
    # an attempt that ran at least this long before dying is an isolated
    # failure, not a crash loop — its restart rewinds the backoff escalation
    healthy_uptime_s = 2.0 * float(
        os.environ.get("RXGB_RESTART_BACKOFF_MAX_S", "30")
    )
    while True:
        coord = coordinator_address or f"127.0.0.1:{_free_port()}"
        procs: List[subprocess.Popen] = []
        paths = []
        spawned_at = time.time()
        attempt_started = time.monotonic()
        for pid_ in local_ids:
            heartbeat_path = os.path.join(scratch, f"a{attempt}_p{pid_}.hb")
            with open(heartbeat_path, "w") as f:
                # baseline: the hang clock starts at spawn, not first touch
                f.write(str(spawned_at))
            ctx = LaunchContext(
                process_id=pid_,
                num_processes=num_processes,
                coordinator_address=coord,
                attempt=attempt,
                checkpoint_path=checkpoint_path,
                heartbeat_path=heartbeat_path,
            )
            payload_path = os.path.join(scratch, f"a{attempt}_p{pid_}.pkl")
            result_path = os.path.join(scratch, f"a{attempt}_p{pid_}.result")
            log_path = os.path.join(scratch, f"a{attempt}_p{pid_}.log")
            with open(payload_path, "wb") as f:
                pickle.dump({"fn_args": payload_fn, "ctx": ctx}, f)
            child_env = dict(os.environ)
            if env:
                child_env.update(env)
            py_path = [p for p in (fn_mod_dir,) if p]
            py_path.append(
                os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
            )
            if child_env.get("PYTHONPATH"):
                py_path.append(child_env["PYTHONPATH"])
            child_env["PYTHONPATH"] = os.pathsep.join(py_path)
            child_env.pop("PYTEST_CURRENT_TEST", None)
            log_f = open(log_path, "w")
            procs.append(
                subprocess.Popen(
                    [
                        sys.executable,
                        "-u",
                        "-m",
                        "xgboost_ray_tpu._launcher_worker",
                        payload_path,
                        result_path,
                    ],
                    env=child_env,
                    stdout=log_f,
                    stderr=subprocess.STDOUT,
                )
            )
            log_f.close()
            paths.append((result_path, log_path, heartbeat_path, pid_))
        obs.get_tracer().event(
            "launcher.spawn",
            attrs={"attempt": attempt, "world": len(local_ids)},
        )

        deadline = time.monotonic() + timeout_s
        attempt_failed = False
        timed_out = False
        hung_ids = set()
        while True:
            codes = [p.poll() for p in procs]
            if any(c is not None and c != 0 for c in codes):
                attempt_failed = True
                break
            if all(c == 0 for c in codes):
                break
            if time.monotonic() > deadline:
                attempt_failed = True
                timed_out = True
                break
            if hang_timeout_s:
                now = time.time()
                for p, (_, _, hb_path, pid_) in zip(procs, paths):
                    if p.poll() is not None:
                        continue
                    try:
                        last = os.path.getmtime(hb_path)
                    except OSError:
                        last = spawned_at
                    if now - last > hang_timeout_s:
                        hung_ids.add(pid_)
                if hung_ids:
                    # a stalled world never trips the coordination service
                    # (nobody died) — flag it long before the global timeout
                    obs.get_tracer().event(
                        "launcher.hung",
                        attrs={
                            "attempt": attempt,
                            "ranks": sorted(hung_ids),
                            "heartbeat_age_s": round(
                                max(
                                    now - os.path.getmtime(hb)
                                    if os.path.exists(hb) else now - spawned_at
                                    for _, _, hb, _ in paths
                                ), 3,
                            ),
                        },
                    )
                    attempt_failed = True
                    break
            time.sleep(poll_interval)

        if attempt_failed:
            # give survivors the chance to exit on their own (coordination-
            # service termination / surfaced exception) so `forced` records
            # who actually surfaced the failure; hung/timed-out worlds skip
            # the grace (nobody is going to exit on their own)
            if not timed_out and not hung_ids and survivor_grace_s > 0:
                grace_end = time.monotonic() + survivor_grace_s
                while (any(p.poll() is None for p in procs)
                       and time.monotonic() < grace_end):
                    time.sleep(poll_interval)
            forced_ids = set()
            for p, (_, _, _, pid_) in zip(procs, paths):
                if p.poll() is None:
                    forced_ids.add(pid_)
                    p.kill()
            for p in procs:
                try:
                    p.wait(timeout=30)
                except subprocess.TimeoutExpired:  # pragma: no cover
                    pass
            for p, (_, log_path, _, pid_) in zip(procs, paths):
                rc = p.returncode if p.returncode is not None else -1
                if rc != 0:
                    # a heartbeat stall is detected at WORLD level (the
                    # first process to cross the threshold trips the
                    # teardown while its equally-stalled peers may be
                    # milliseconds short) — every process killed in a hang
                    # teardown was part of the stalled world
                    if hung_ids and pid_ in forced_ids:
                        reason = "hung"
                    elif timed_out:
                        reason = "slow"
                    elif pid_ in forced_ids:
                        reason = "torn_down"
                    else:
                        reason = "crashed"
                    failures.append(
                        ProcessFailure(
                            attempt, pid_, rc, _tail(log_path),
                            forced=pid_ in forced_ids,
                            reason=reason,
                            domain=_process_domain(pid_, num_processes),
                        )
                    )
            if hung_ids:
                why = f"heartbeats stalled > {hang_timeout_s}s"
            elif timed_out:
                why = "timed out"
            else:
                why = "process death"
            if restarts >= max_restarts:
                raise LaunchFailedError(
                    f"distributed world failed ({why}) on attempt {attempt} "
                    f"and the restart budget ({max_restarts}) is exhausted. "
                    f"Last failure logs:\n"
                    + "\n".join(
                        f"--- process {f_.process_id} (rc={f_.returncode})\n"
                        f"{f_.log_tail[-1500:]}"
                        for f_ in failures[-len(local_ids):]
                    ),
                    failures,
                )
            restarts += 1
            attempt += 1
            if time.monotonic() - attempt_started > healthy_uptime_s:
                consecutive_failures = 0
            consecutive_failures += 1
            backoff = restart_backoff_s(consecutive_failures - 1)
            logger.warning(
                "[RayXGBoost] distributed world died (%s, attempt %d); "
                "restarting from checkpoint %r (restart %d/%d, backoff "
                "%.2fs).",
                why, attempt - 1, checkpoint_path, restarts, max_restarts,
                backoff,
            )
            obs.get_tracer().event(
                "launcher.attempt_failed",
                attrs={"attempt": attempt - 1, "reason": why,
                       "restart": restarts, "backoff_s": round(backoff, 4),
                       "domains": sorted({
                           f_.domain for f_ in failures
                           if f_.attempt == attempt - 1
                           and f_.domain is not None
                       })},
            )
            if backoff > 0:
                time.sleep(backoff)
            continue

        results = []
        for result_path, log_path, _, pid_ in paths:
            try:
                with open(result_path, "rb") as f:
                    results.append(pickle.load(f))
            except (OSError, EOFError, pickle.UnpicklingError) as exc:
                # a zero-exit worker that left no (readable) result is a
                # broken contract, not a partial success — surface it with
                # the worker's log instead of silently returning None
                raise LaunchFailedError(
                    f"worker {pid_} exited 0 but its result file is "
                    f"missing/unreadable ({type(exc).__name__}: {exc}); "
                    f"refusing to return a partial world. Log tail:\n"
                    f"{_tail(log_path)}",
                    failures,
                )
        return LaunchResult(
            results=results, restarts=restarts, failures=failures
        )

"""Driver API: ``train()`` / ``predict()`` / ``RayParams`` — the coordinator.

API-compatible re-implementation of ``xgboost_ray/main.py`` (L3 of SURVEY §1)
for the TPU runtime. The architectural inversion (SURVEY §7.1): the
reference's N OS-process actors + Rabit tracker become virtual workers that
own data shards and a single jitted SPMD program over the device mesh
(``engine.TpuEngine``); the driver keeps the exact same responsibilities —
validation, checkpointing every k rounds, the retry loop with
restart-from-checkpoint arithmetic, elastic fault tolerance, the queue/event
side-channel, and result merging (evals_result / additional_results).

Fault model: TPU mesh failures surface as exceptions from the round step (or
from fault-injection callbacks in tests); the driver marks ranks dead and —
exactly like the reference (``main.py:1644-1713``) — either continues with
survivors (elastic) or recreates the failed workers, then resumes from the
last checkpoint with the world recompiled for the new mesh size.
"""

import dataclasses
import logging
import os
import pickle
import threading
import time
import warnings
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from xgboost_ray_tpu.callback import (
    DistributedCallback,
    DistributedCallbackContainer,
    TrainingCallback,
)
from xgboost_ray_tpu import faults, obs
from xgboost_ray_tpu.domains import DeathCoalescer, DomainMap, derive_domain_map
from xgboost_ray_tpu.engine import TpuEngine
from xgboost_ray_tpu.exceptions import (
    RayActorError,
    RayTaskError,
    RayXGBoostActorAvailable,
    RayXGBoostTrainingError,
    RayXGBoostTrainingStopped,
)
from xgboost_ray_tpu.matrix import (
    RayDMatrix,
    RayShardingMode,
    _get_sharding_indices,
    combine_data,
    translate_shard_categories,
)
from xgboost_ray_tpu.models.booster import RayXGBoostBooster
from xgboost_ray_tpu.ops import histogram as hist_ops
from xgboost_ray_tpu.params import parse_params
from xgboost_ray_tpu import session as session_mod
from xgboost_ray_tpu.util import Event, Queue, restart_backoff_s

logger = logging.getLogger(__name__)

LEGACY_MATRIX = False


# ---------------------------------------------------------------------------
# Env-var config system (mirror of ``xgboost_ray/main.py:110-162``): every
# field is overridable via RXGB_<NAME>, re-read live on each access.
# ---------------------------------------------------------------------------


def _get_environ(item: str, old_val: Any):
    env_var = f"RXGB_{item}"
    new_val = old_val
    if env_var in os.environ:
        raw = os.environ[env_var]
        if isinstance(old_val, bool):
            new_val = bool(int(raw))
        elif isinstance(old_val, int):
            new_val = int(raw)
        elif isinstance(old_val, float):
            new_val = float(raw)
        else:
            new_val = raw
    return new_val


@dataclass
class _XGBoostEnv:
    USE_SPREAD_STRATEGY: bool = True
    PLACEMENT_GROUP_TIMEOUT_S: int = 100
    STATUS_FREQUENCY_S: int = 30
    # when set, wrap each training attempt in a jax.profiler trace written
    # to this directory (xprof/tensorboard-compatible) — SURVEY §5.1 upgrade
    PROFILE_DIR: str = ""
    ELASTIC_RESTART_DISABLED: bool = False
    ELASTIC_RESTART_RESOURCE_CHECK_S: float = 30.0
    ELASTIC_RESTART_GRACE_PERIOD_S: float = 10.0
    # fault domains: 0 = derive from placement (process_index groups on a
    # real multi-host mesh, per-rank domains on one host); H > 0 = logical
    # H-way partition of the rank space so domain-granular failure behavior
    # is exercisable on the single-process CPU CI mesh
    FAULT_DOMAINS: int = 0
    # how long the in-flight recovery lingers to fold near-simultaneous
    # deaths (a whole domain dying at once) into ONE shrink; 0 still sweeps
    # once for already-dead ranks, it just doesn't wait for stragglers
    ELASTIC_DEATH_COALESCE_S: float = 0.0
    COMMUNICATION_SOFT_PLACEMENT: bool = True
    # upper bound on rounds fused into one compiled lax.scan program in the
    # batched fast path. Bounds compiled-program size and the stacked
    # per-round outputs held live at once (a pre-PR-1 HIGGS-11M run fused
    # all 100 rounds into a single program and crashed the TPU worker);
    # 10 divides the usual 100-round protocols so the driver compiles
    # exactly one scan program.
    SCAN_MAX_CHUNK: int = 10
    # SPMD prediction: shard predict rows over the device mesh and run the
    # gather walk as one compiled shard_map program instead of a host-side
    # per-actor loop. Set RXGB_SPMD_PREDICT=0 to force the host loop.
    SPMD_PREDICT: bool = True

    def __getattribute__(self, item):
        old_val = object.__getattribute__(self, item)
        if item.startswith("_"):
            return old_val
        return _get_environ(item, old_val)


ENV = _XGBoostEnv()


# ---------------------------------------------------------------------------
# RayParams
# ---------------------------------------------------------------------------


@dataclass
class RayParams:
    """Parameters to configure distributed-training behavior.

    API mirror of ``xgboost_ray/main.py:448-504`` with one TPU addition:
    ``tpus_per_actor`` (the number of mesh devices each logical actor may
    occupy; the total mesh size is min(num_actors, available devices)).
    """

    # Actor scheduling
    num_actors: int = 0
    cpus_per_actor: int = 0
    gpus_per_actor: int = -1
    tpus_per_actor: int = -1
    resources_per_actor: Optional[Dict] = None

    # Fault tolerance
    elastic_training: bool = False
    max_failed_actors: int = 0
    max_actor_restarts: int = 0
    checkpoint_frequency: int = 5

    # Distributed callbacks
    distributed_callbacks: Optional[List[DistributedCallback]] = None

    verbose: Optional[bool] = None
    placement_options: Optional[Dict[str, Any]] = None

    def get_tune_resources(self):
        """Resources for a Tune trial running this training."""
        from xgboost_ray_tpu.tune import _get_tune_resources

        if self.num_actors <= 0:
            raise ValueError("num_actors must be greater than 0.")
        return _get_tune_resources(
            num_actors=self.num_actors,
            cpus_per_actor=max(0, self.cpus_per_actor),
            gpus_per_actor=max(0, self.gpus_per_actor),
            tpus_per_actor=max(0, self.tpus_per_actor),
            resources_per_actor=self.resources_per_actor,
            placement_options=self.placement_options,
        )


def _validate_ray_params(ray_params: Union[None, RayParams, dict]) -> RayParams:
    if ray_params is None:
        ray_params = RayParams()
    elif isinstance(ray_params, dict):
        ray_params = RayParams(**ray_params)
    elif not isinstance(ray_params, RayParams):
        raise ValueError(
            f"`ray_params` must be a `RayParams` instance, a dict, or None, "
            f"but it was {type(ray_params)}."
        )
    if ray_params.num_actors <= 0:
        raise ValueError(
            "The `num_actors` parameter is set to 0. Please always specify "
            "the number of distributed workers you want to use "
            "(`RayParams(num_actors=X)`)."
        )
    elif ray_params.num_actors < 2:
        warnings.warn(
            f"`num_actors` in `ray_params` is smaller than 2 "
            f"({ray_params.num_actors}). Training will NOT be distributed!"
        )
    return ray_params


@dataclass
class _Checkpoint:
    iteration: int = 0
    value: Optional[bytes] = None


# ---------------------------------------------------------------------------
# Virtual worker ("actor"): owns a rank and its data shards. The compute
# itself runs in the shared mesh program; this object carries the lifecycle
# (load_data, liveness, callbacks) so the reference's scheduling/FT logic and
# tests have the same surface to hook into (``xgboost_ray/main.py:543-815``).
# ---------------------------------------------------------------------------


class RayXGBoostActor:
    def __init__(
        self,
        rank: int,
        num_actors: int,
        queue: Optional[Queue] = None,
        stop_event: Optional[Event] = None,
        distributed_callbacks: Optional[List[DistributedCallback]] = None,
    ):
        self.rank = rank
        self.num_actors = num_actors
        self.queue = queue
        self.stop_event = stop_event
        self.alive = True
        # death-coalescing mailbox (domains.DeathCoalescer) wired up by the
        # driver so an out-of-band kill() lands in the same shrink as its
        # domain siblings
        self._coalescer = None
        self._domain: Optional[int] = None
        self._data: Dict[RayDMatrix, Dict[str, Optional[np.ndarray]]] = {}
        self._local_n: Dict[RayDMatrix, int] = {}
        self._distributed_callbacks = DistributedCallbackContainer(
            distributed_callbacks
        )
        self._distributed_callbacks.on_init(self)

    def pid(self) -> int:
        if not self.alive:
            raise RayActorError(f"actor {self.rank} is dead", ranks=[self.rank])
        return os.getpid()

    def set_queue(self, queue: Queue):
        self.queue = queue

    def set_stop_event(self, stop_event: Event):
        self.stop_event = stop_event

    def load_data(self, data: RayDMatrix):
        if data in self._data:
            return
        faults.fire("actor.load_shard", rank=self.rank)
        self._distributed_callbacks.before_data_loading(self, data)
        shard = data.get_data(self.rank, self.num_actors)
        if shard.get("stream") is not None:
            n = shard["stream"].n_rows
        else:
            n = shard["data"].shape[0] if shard.get("data") is not None else 0
        self._local_n[data] = n
        self._data[data] = shard
        self._distributed_callbacks.after_data_loading(self, data)

    def get_shard(self, data: RayDMatrix) -> Dict[str, Optional[np.ndarray]]:
        return self._data[data]

    def local_n(self, data: RayDMatrix) -> int:
        return self._local_n.get(data, 0)

    def has_data(self, data: RayDMatrix) -> bool:
        return data in self._data

    def kill(self):
        """Mark this worker dead (fault injection / failure detection)."""
        self.alive = False
        coalescer = self._coalescer
        if coalescer is not None:
            coalescer.note(self.rank, self._domain)


# ---------------------------------------------------------------------------
# Training state shared across attempts (mirror of ``main.py:1038-1058``).
# ---------------------------------------------------------------------------


@dataclass
class _TrainingState:
    actors: List[Optional[RayXGBoostActor]]
    queue: Queue
    stop_event: Event
    checkpoint: _Checkpoint
    additional_results: Dict

    failed_actor_ranks: set

    # elastic: dead ranks awaiting background reintegration — NOT recreated
    # by the next attempt (mirror of clearing start ranks, main.py:1659)
    elastic_dead_ranks: set = dataclasses.field(default_factory=set)

    # elastic scheduling (mirror of elastic.py state)
    pending_actors: Optional[Dict[int, Any]] = None  # rank -> elastic.PendingActor
    restart_training_at: Optional[float] = None
    last_resource_check_at: float = 0.0

    # fault domains (ROADMAP item 4): the attempt's rank -> domain
    # assignment, the per-domain reintegration grace clocks, the domains
    # whose replacements are complete and past grace (set by the elastic
    # updater, consumed atomically by the round-boundary grow), and the
    # mailbox that folds near-simultaneous deaths into one shrink
    domain_map: Optional[DomainMap] = None
    domain_restart_at: Dict[int, float] = dataclasses.field(default_factory=dict)
    domains_due: List[int] = dataclasses.field(default_factory=list)
    death_coalescer: DeathCoalescer = dataclasses.field(
        default_factory=DeathCoalescer
    )

    # in-flight elastic continuation: live engines keyed by world signature
    # (tuple of alive ranks), so a shrink->grow cycle revives the cached
    # engine's compiled programs instead of retracing. Bounded to the two
    # most recent worlds (each entry pins device arrays).
    engine_cache: Dict[tuple, Any] = dataclasses.field(default_factory=dict)

    training_started_at: float = 0.0

    # robustness accounting: rounds completed inside the CURRENT attempt
    # (replay arithmetic), when the last failure was detected (so the next
    # attempt's first completed round closes the time-to-recover clock),
    # and failures since the last real forward progress (backoff index —
    # an isolated failure in a long job must not inherit an escalated wait)
    rounds_this_attempt: int = 0
    recover_started_at: Optional[float] = None
    consecutive_failures: int = 0


def _mark_recovered(state: "_TrainingState") -> None:
    """First forward progress after a restart: close the recovery clock and
    rewind the backoff escalation."""
    state.consecutive_failures = 0
    if state.recover_started_at is None:
        return
    delta = time.time() - state.recover_started_at
    rob = state.additional_results.get("robustness")
    if rob is not None:
        rob["time_to_recover_s"] = round(
            rob.get("time_to_recover_s", 0.0) + delta, 4,
        )
    state.recover_started_at = None
    # timeline closure of the clock the matching "failure.detected" opened:
    # bench --chaos reconstructs time-to-recover from these two timestamps
    obs.get_tracer().event(
        "recovered", attrs={"time_to_recover_s": round(delta, 4)}
    )


def _create_actor(
    rank: int,
    num_actors: int,
    queue: Queue,
    stop_event: Event,
    distributed_callbacks: Optional[List[DistributedCallback]],
) -> RayXGBoostActor:
    return RayXGBoostActor(
        rank,
        num_actors,
        queue=queue,
        stop_event=stop_event,
        distributed_callbacks=distributed_callbacks,
    )


def _get_placement_strategy(in_tune_session: bool) -> str:
    """SPREAD for standalone training (fault isolation), PACK inside tuning
    trials — the reference's strategy choice (``main.py:1581-1599``,
    ``tune.py:123``), gated on RXGB_USE_SPREAD_STRATEGY. Consumed by
    ``_select_mesh_devices`` (actual mesh placement) and re-exported through
    ``get_tune_resources()`` for schedulers above."""
    if in_tune_session:
        return "PACK"
    return "SPREAD" if ENV.USE_SPREAD_STRATEGY else "PACK"


def _select_mesh_devices(num: int, strategy: str, devices=None) -> list:
    """Choose which physical devices form the training mesh — the TPU analog
    of the reference's placement group (``main.py:958-1019``): there,
    SPREAD/PACK decides which *nodes* host the actors; here it decides which
    devices (and thereby hosts) host the mesh shards.

    PACK fills hosts/devices in order — fewest hosts touched, the locality
    choice for tune trials sharing one machine. SPREAD takes an equal share
    from every host and an even stride across each host's device ring —
    fault isolation across hosts and maximal spacing on the ICI ring, the
    reference's default for standalone training.

    The selection is returned in jax.devices() order (process-contiguous),
    which the engine's multi-host row layout requires.
    """
    import jax

    devices = list(devices) if devices is not None else list(jax.devices())
    if num >= len(devices) or num <= 0:
        return devices
    if strategy == "PACK":
        return devices[:num]
    by_proc: Dict[int, list] = {}
    for pos, d in enumerate(devices):
        by_proc.setdefault(getattr(d, "process_index", 0), []).append((pos, d))
    procs = sorted(by_proc)
    # Distribute quotas, redistributing any host's deficit (a host may hold
    # fewer devices than its even share) to hosts with spare devices so the
    # returned mesh always matches the requested actor count.
    quotas = {p: 0 for p in procs}
    remaining = num
    while remaining:
        active = [p for p in procs if quotas[p] < len(by_proc[p])]
        base, extra = divmod(remaining, len(active))
        for i, p in enumerate(active):
            k = min(base + (1 if i < extra else 0), len(by_proc[p]) - quotas[p])
            quotas[p] += k
            remaining -= k
    chosen = []
    for p in procs:
        group, k = by_proc[p], quotas[p]
        if k >= len(group):
            chosen.extend(group)
        else:
            # int(j * len / k) is strictly increasing when len > k
            chosen.extend(group[int(j * len(group) / k)] for j in range(k))
    chosen.sort(key=lambda t: t[0])
    assert len(chosen) == num
    return [d for _, d in chosen]


def _resolve_mesh_devices(num: int, ray_params: Optional["RayParams"]) -> list:
    """The one place that decides WHICH devices form a mesh of ``num`` slots:
    a concurrent tune trial's device slice wins; otherwise the user's
    ``placement_options`` strategy override, otherwise SPREAD/PACK by
    context. Shared by training and SPMD prediction so both place work on
    the same devices."""
    from xgboost_ray_tpu import tune as _tune_mod

    _sess = _tune_mod.get_session()
    trial_devices = getattr(_sess, "devices", None) if _sess else None
    if trial_devices is not None:
        return list(trial_devices)
    strategy = None
    if ray_params is not None and ray_params.placement_options:
        strategy = ray_params.placement_options.get("strategy")
    if strategy is None:
        strategy = _get_placement_strategy(in_tune_session=_sess is not None)
    return _select_mesh_devices(num, str(strategy).upper())


def _engine_can_reshard(engine) -> bool:
    """The ONE probe of an engine's zero-replay re-shard capability — every
    elastic decision point (caching a world, gating the in-flight recover,
    choosing boundary-grow vs the legacy ``RayXGBoostActorAvailable``
    restart) routes through here so the gate semantics cannot drift per
    call site. Every built-in engine (including ``LinearEngine``/gblinear)
    re-shards now; only a user-supplied engine without the method is
    restart-only."""
    probe = getattr(engine, "can_reshard", None)
    return bool(probe()) if probe is not None else False


def _handle_queue(queue: Queue, checkpoint: _Checkpoint, callback_returns: Dict):
    """Drain the callback queue (mirror of ``main.py:902-922``)."""
    while not queue.empty():
        rank, item = queue.get()
        if callable(item):
            item()
        elif isinstance(item, _Checkpoint):
            checkpoint.iteration = item.iteration
            checkpoint.value = item.value
            obs.get_tracer().event(
                "checkpoint.commit", round=item.iteration,
                attrs={"bytes": len(item.value or b"")},
            )
        else:
            callback_returns.setdefault(rank, []).append(item)


def _shard_attrs(dtrain) -> Dict:
    """``data.load``'s view of the training matrix: how many host shards it
    holds now and the bytes of each, by rank."""
    sizes = dtrain.get_shard_bytes()
    return {"shards": len(sizes),
            "shard_bytes": [sizes[r] for r in sorted(sizes)]}


def _record_engine_readouts(state, engine, booster) -> None:
    """Surface the engine's measured per-round collective payload bytes
    (the ``hist_quant`` traffic metric) and where the run ran in
    additional_results. Host reads, after training only — never on the
    per-round path. Nothing here is swallowed: on an accelerator an async
    device error surfaces at exactly such a first host read, and it must
    reach the caller instead of being dropped while the run exits 0."""
    placement = getattr(engine, "placement_record", None)
    if placement is not None:
        state.additional_results["device"] = placement()
    gh_getter = getattr(engine, "gh_plane_bytes_per_shard", None)
    if gh_getter is not None:
        # static layout arithmetic (no device read): the per-shard
        # gh-plane footprint the gh_precision mode shrinks — the
        # bench's memory metric, independent of the wire counter below
        state.additional_results["gh_plane_bytes_per_shard"] = int(
            gh_getter()
        )
    getter = getattr(engine, "hist_allreduce_bytes_per_round", None)
    if getter is None:
        return
    val = getter()
    if val is None:
        return
    # set by every tree round program (step, step_many, the K-lane step,
    # DART); the linear booster has no tree path and no getter
    mesh = engine.mesh_round_stats()
    state.additional_results["hist_allreduce_bytes_per_round"] = val
    state.additional_results.update(mesh)
    tracer = obs.get_tracer()
    tracer.event(
        "allreduce.bytes",
        attrs={
            "bytes_per_round": int(val),
            "collectives_per_round": mesh["collectives_per_round"],
            "mesh": {k: int(v) for k, v in engine.mesh.shape.items()},
        },
    )
    # what ops.histogram.onehot_radix and onehot_ftiles chose where this
    # run's round programs traced a dense build (empty under
    # hist_impl=scatter): the counters rxgb_hist_builds_total{radix=...} and
    # rxgb_hist_tile_steps_total hold how many builds took each radix and
    # the tile steps they traced
    builds = hist_ops.pop_traced_builds()
    if builds:
        attrs = {
            key: {str(width): v for width, v in builds[key].items()}
            for key in ("radix_by_width", "ftiles_by_width")
        }
        if engine.cfg.grow_policy != "lossguide":
            # a level-wise tree's builds are its levels', each traced once
            attrs["tile_steps_per_round"] = builds["tile_steps_per_tree"] * (
                engine.n_outputs * max(1, engine.params.num_parallel_tree))
        tracer.event("hist.builds", attrs=attrs)
    grown = engine.lossguide_round_stats()
    if grown is None:
        return
    # what the leaf-wise grower counted on the device: the host sees chunk
    # ends only, and a tree's passes are the tree's own
    rounds = max(1, grown["rounds"])
    attrs = {
        "passes_per_round": grown["lossguide_passes"] / rounds,
        "nodes_evaluated_per_round":
            grown["lossguide_nodes_evaluated"] / rounds,
        "splits_per_round": grown["lossguide_splits"] / rounds,
        "deepest_leaf": int(booster.node_depths()[
            np.asarray(booster.forest.is_leaf)].max()),
        "table_overflows": grown["lossguide_table_overflows"],
        "rounds": grown["rounds"],
    }
    tracer.event("lossguide.grow", attrs=attrs)
    registry = obs.get_registry()
    registry.counter("rxgb_lossguide_passes_total").inc(
        grown["lossguide_passes"])
    registry.counter("rxgb_lossguide_nodes_evaluated_total").inc(
        grown["lossguide_nodes_evaluated"])
    state.additional_results["lossguide_passes_per_round"] = (
        attrs["passes_per_round"])
    if grown["lossguide_table_overflows"]:
        warnings.warn(
            f"grow_policy='lossguide': {grown['lossguide_table_overflows']} "
            f"node(s) the best-first order wanted to split found no room in "
            f"the grower's table of evaluated nodes "
            f"(ops/grow_lossguide.TABLE_FACTOR per leaf) and stayed leaves: "
            f"those trees are not exactly best-first."
        )


def _stop_profile_if_running():
    if not ENV.PROFILE_DIR:
        return
    try:
        import jax

        jax.profiler.stop_trace()
    except Exception:  # noqa: BLE001 - no trace running
        pass


def _assemble_obs(tracer) -> Dict:
    """The ``additional_results["obs"]`` payload: full timeline plus the
    derived per-round and event views and the ring-buffer accounting
    (dropped records are surfaced, never silent)."""
    records = tracer.records()
    rounds = []
    for rec in records:
        if rec.get("kind") == "span" and rec.get("name") == "round":
            row = {"round": rec.get("round"), "dur_s": rec["dur_s"]}
            row.update(rec.get("attrs") or {})
            rounds.append(row)
    return {
        "timeline": records,
        "rounds": rounds,
        "events": [r for r in records if r.get("kind") == "event"],
        "dropped_spans": tracer.dropped,
        "capacity": tracer.capacity,
    }


class _FauxDMatrix:
    """Lightweight stand-in passed to custom objective/metric callables,
    exposing the xgboost DMatrix accessors they use."""

    def __init__(self, label, weight, group_ptr=None):
        self._label = label
        self._weight = weight
        self._group_ptr = group_ptr

    def get_label(self):
        return self._label

    def get_weight(self):
        return self._weight if self._weight is not None else np.array([])

    def get_group(self):
        return (
            np.diff(self._group_ptr) if self._group_ptr is not None else np.array([])
        )

    def num_row(self):
        return len(self._label)


class _EngineBoosterProxy:
    """Lazy booster view handed to per-iteration callbacks; materializes the
    current forest only when a callback actually touches the model."""

    def __init__(self, engine: TpuEngine):
        self._engine = engine
        self._cached: Optional[RayXGBoostBooster] = None
        self._cached_rounds = -1

    def _rebind(self, engine) -> None:
        """Point the proxy at a new engine (in-flight world shrink/grow)."""
        self._engine = engine
        self._cached = None
        self._cached_rounds = -1

    def _materialize(self) -> RayXGBoostBooster:
        n = self._engine.num_round_trees
        if self._cached is None or self._cached_rounds != n:
            self._cached = self._engine.get_booster()
            self._cached_rounds = n
        return self._cached

    def __getattr__(self, item):
        return getattr(self._materialize(), item)


def _serialize_booster(booster: RayXGBoostBooster) -> bytes:
    return pickle.dumps(booster)


def _deserialize_booster(raw: Optional[bytes]) -> Optional[RayXGBoostBooster]:
    return pickle.loads(raw) if raw else None


def _coerce_model(model) -> Optional[RayXGBoostBooster]:
    from xgboost_ray_tpu.linear import RayLinearBooster

    if model is None:
        return None
    if isinstance(model, (RayXGBoostBooster, RayLinearBooster)):
        return model
    if isinstance(model, bytes):
        return _deserialize_booster(model)
    if isinstance(model, str):
        # parse ONCE, dispatch on the document's own booster name (a
        # malformed tree file then fails with ITS parse error, not a
        # misleading gblinear one; no double I/O on big forests)
        import json as _json

        with open(model) as f:
            doc = _json.load(f)
        name = doc.get("learner", {}).get("gradient_booster", {}).get("name")
        if name == "gblinear":
            return RayLinearBooster.import_xgboost_json(doc)
        return RayXGBoostBooster._from_dict(doc)
    raise ValueError(f"Cannot interpret xgb_model of type {type(model)}")


_KNOWN_TRAIN_KWARGS = {
    "obj",
    "feval",
    "custom_metric",
    "callbacks",
    "early_stopping_rounds",
    "verbose_eval",
    "xgb_model",
    "maximize",
    "serve_registry",
}


def _validate_kwargs_for_func(kwargs: Dict, allowed: set, func_name: str):
    unknown = [k for k in kwargs if k not in allowed]
    if unknown:
        raise TypeError(
            f"{func_name}() got unexpected keyword argument(s): {unknown}. "
            f"Supported extra arguments: {sorted(allowed)}"
        )


# ---------------------------------------------------------------------------
# One training attempt (mirror of ``_train``, ``main.py:1061-1337``).
# ---------------------------------------------------------------------------


def _train(
    params: Dict,
    dtrain: RayDMatrix,
    boost_rounds_left: int,
    *,
    evals: Sequence[Tuple[RayDMatrix, str]],
    ray_params: RayParams,
    obj: Optional[Callable],
    feval: Optional[Callable],
    callbacks: Sequence[Any],
    early_stopping_rounds: Optional[int],
    maximize: Optional[bool],
    verbose_eval: Union[bool, int],
    _training_state: _TrainingState,
) -> Tuple[RayXGBoostBooster, Dict, Dict]:
    from xgboost_ray_tpu import elastic as elastic_mod

    state = _training_state
    num_actors = ray_params.num_actors
    tracer = obs.get_tracer()
    obs.watch_compiles()  # every compile of the process, on the timeline
    # the hist.builds event reports the builds this attempt's programs trace
    hist_ops.pop_traced_builds()

    # 1) create (or re-create) missing actors (mirror main.py:1129-1149)
    newly_created = 0
    for rank in list(state.failed_actor_ranks):
        if state.actors[rank] is not None:
            raise RuntimeError(
                f"Trying to create actor with rank {rank}, but it already exists."
            )
        actor = _create_actor(
            rank,
            num_actors,
            state.queue,
            state.stop_event,
            ray_params.distributed_callbacks,
        )
        state.actors[rank] = actor
        state.failed_actor_ranks.remove(rank)
        newly_created += 1
    alive_actors = sum(1 for a in state.actors if a is not None)
    if ray_params.verbose:
        from xgboost_ray_tpu import tune as tune_mod

        strategy = _get_placement_strategy(tune_mod.is_session_enabled())
        logger.info(
            f"[RayXGBoost] Created {newly_created} new actors "
            f"({alive_actors} total actors, {strategy} placement)."
        )

    # 2) locality / FIXED shard assignment (mirror main.py:1161-1165);
    # fail fast when a distributed matrix has fewer files/partitions than
    # actors (mirror matrix.py:900-901), covering FIXED mode too
    num_alive = sum(1 for a in state.actors if a is not None)
    for dm in [dtrain] + [e[0] for e in evals]:
        dm.assert_enough_shards_for_actors(num_alive)
    dtrain.assign_shards_to_actors(state.actors)
    for deval, _ in evals:
        deval.assign_shards_to_actors(state.actors)

    # 3) data loading on every alive actor (mirror _PrepareActorTask)
    load_errors = []
    with tracer.span("data.load", matrices=1 + len(evals)) as load_attrs:
        for actor in state.actors:
            if actor is None:
                continue
            try:
                actor.load_data(dtrain)
                for deval, _ in evals:
                    actor.load_data(deval)
            except (RayActorError, RayTaskError):
                raise
            except Exception as exc:  # noqa: BLE001 - surfaced as task error
                load_errors.append((actor.rank, exc))
        load_attrs.update(_shard_attrs(dtrain))
    if load_errors:
        err = RayTaskError(f"Data loading failed on ranks {load_errors}")
        err.ranks = [rank for rank, _ in load_errors]
        raise err
    if ray_params.verbose:
        logger.info("[RayXGBoost] Starting XGBoost training.")

    # 4) build the mesh engine over the alive actors' shards
    alive = [a for a in state.actors if a is not None]
    # RayDeviceQuantileDMatrix(max_bin=...) governs the binning of its data
    # (reference matrix.py:977-1033 honors it); an explicit conflicting
    # params['max_bin'] wins, with a warning. Injected before parse_params so
    # validation has a single source of truth.
    eff_params = dict(params or {})
    dm_max_bin = getattr(dtrain, "max_bin", None)
    if dm_max_bin:
        if "max_bin" in eff_params and int(eff_params["max_bin"]) != int(dm_max_bin):
            logger.warning(
                "params['max_bin']=%s overrides %s(max_bin=%s).",
                eff_params["max_bin"], type(dtrain).__name__, dm_max_bin,
            )
        else:
            eff_params["max_bin"] = int(dm_max_bin)
    parsed = parse_params(eff_params)
    if getattr(dtrain, "streamed", False):
        # fail the unsupported compositions (gblinear, ranking) BEFORE any
        # actor loads a chunk — the engine re-validates defensively
        from xgboost_ray_tpu.params import validate_streaming_params

        validate_streaming_params(parsed)
    train_cats = dtrain.resolved_categories

    def _build_world(world_actors, world_init, donor=None):
        """The one engine factory of this attempt: assemble the given
        actors' shards, translate eval-set categories, and build the engine
        — or revive a cached engine whose compiled programs cover exactly
        this world (shrink->grow cycles re-enter previously compiled world
        sizes; see ``_TrainingState.engine_cache``). ``donor`` is the
        engine being swapped out by an elastic shrink/grow: a STREAMED
        donor seeds the new world's binned matrix and frozen cuts in
        memory (no re-sketch, no re-stream of surviving shards — only a
        grow-back onto a brand-new replacement shard re-streams, and only
        that shard)."""
        from xgboost_ray_tpu.engine import shard_layout_fingerprint

        train_shards = [a.get_shard(dtrain) for a in world_actors]
        evals_in = []
        for deval, name in evals:
            if deval is dtrain:
                evals_in.append((train_shards, name))
            else:
                eshards = [a.get_shard(deval) for a in world_actors]
                ecats = deval.resolved_categories
                if ecats and not train_cats:
                    raise ValueError(
                        f"eval set {name!r} auto-encoded categorical columns, "
                        f"but the training matrix was built from integer "
                        f"codes — the mappings cannot be aligned. Encode the "
                        f"eval set with the same codes, or train from a "
                        f"DataFrame with enable_categorical=True."
                    )
                if train_cats and ecats != train_cats:
                    # align auto-encoded codes with the training mapping
                    eshards = [
                        translate_shard_categories(s, ecats, train_cats)
                        for s in eshards
                    ]
                evals_in.append((eshards, name))
        # 2D row x feature mesh: the engine needs R x C device slots (C=1
        # keeps the legacy R-slot request byte for byte)
        mesh_slots = len(world_actors) * max(1, parsed.feature_parallel)
        trial_devices = _resolve_mesh_devices(mesh_slots, ray_params)
        key = tuple(a.rank for a in world_actors)
        fp = shard_layout_fingerprint(train_shards)
        cached = state.engine_cache.pop(key, None)
        if cached is not None and getattr(cached, "_shard_fingerprint", None) == fp:
            try:
                cached.reset_from_booster(train_shards, evals_in, world_init)
                return cached
            except Exception as exc:  # noqa: BLE001 - cache is best-effort
                logger.warning(
                    "[RayXGBoost] cached engine for world %s unusable (%s); "
                    "rebuilding.", key, exc,
                )
        with tracer.span(
            "engine.init", booster=parsed.booster, world=len(world_actors)
        ) as init_attrs:
            if parsed.booster == "gblinear":
                from xgboost_ray_tpu.linear import LinearEngine

                eng = LinearEngine(
                    train_shards,
                    parsed,
                    num_actors=len(world_actors),
                    evals=evals_in,
                    devices=trial_devices,
                    init_booster=world_init,
                    feature_names=dtrain.resolved_feature_names,
                    feature_types=dtrain.resolved_feature_types,
                )
            else:
                eng = TpuEngine(
                    train_shards,
                    parsed,
                    num_actors=len(world_actors),
                    evals=evals_in,
                    devices=trial_devices,
                    init_booster=world_init,
                    feature_names=dtrain.resolved_feature_names,
                    total_rounds=boost_rounds_left,
                    feature_weights=dtrain.feature_weights,
                    feature_types=dtrain.resolved_feature_types,
                    categories=train_cats,
                    stream_donor=donor,
                )
                init_attrs["rows_per_device"] = eng.rows_per_device()
        eng._world_key = key
        eng._shard_fingerprint = fp
        return eng

    def _cache_world(eng):
        key = getattr(eng, "_world_key", None)
        if key is None or not _engine_can_reshard(eng):
            return
        state.engine_cache[key] = eng
        while len(state.engine_cache) > 2:
            state.engine_cache.pop(next(iter(state.engine_cache)))

    # fault domains for this attempt (ROADMAP item 4): the rank -> domain
    # assignment from RXGB_FAULT_DOMAINS or device placement. The faults
    # plane resolves `domain_kill` rules through it, actors carry their
    # domain into the death-coalescing mailbox, and the elastic updater
    # runs its grace clocks per domain.
    state.domain_map = derive_domain_map(
        num_actors,
        devices=_resolve_mesh_devices(
            num_actors * max(1, parsed.feature_parallel), ray_params
        ),
        logical_domains=int(ENV.FAULT_DOMAINS),
    )

    def _alive_domain_ranks(dom):
        if dom not in state.domain_map.domains():
            raise ValueError(
                f"domain_kill: unknown fault domain {dom!r}; this world has "
                f"domains {state.domain_map.domains()}"
            )
        return [
            r for r in state.domain_map.ranks_of(dom)
            if state.actors[r] is not None
        ]

    faults.set_domain_resolver(_alive_domain_ranks)
    _rewire_actors(state)  # actors pick up the coalescer + domain ids

    init_booster = _deserialize_booster(state.checkpoint.value)
    engine = _build_world(alive, init_booster)
    total_n = sum(a.local_n(dtrain) for a in alive)
    state.additional_results["total_n"] = total_n

    for actor in alive:
        actor._distributed_callbacks.before_train(actor)

    session_mod.init_session(rank=0, queue=state.queue)
    proxy = _EngineBoosterProxy(engine)
    evals_result: Dict[str, Dict[str, List[float]]] = {}
    callback_returns = state.additional_results.setdefault("callback_returns", {})

    # ------------------------------------------------------------------
    # In-flight elastic continuation (zero-replay shrink/grow). The global
    # round index of this attempt is ``attempt_offset0 + i``; after a world
    # swap the new engine's iteration_offset absorbs the rounds already
    # boosted, so ``engine_base`` tracks how many attempt rounds are folded
    # into it and the engine is stepped with the attempt round REBASED to
    # its own offset (keeping the per-round RNG stream world-schedule
    # independent: fold(seed, global_round)).
    # ------------------------------------------------------------------
    attempt_offset0 = engine.iteration_offset
    engine_base = 0
    rob = state.additional_results.get("robustness", {})

    def _fire_after_round(i_attempt, round_metrics, duration_s):
        """Fan the obs round record out to the distributed callbacks."""
        if not ray_params.distributed_callbacks:
            return
        record = {
            "round": attempt_offset0 + i_attempt,
            "iteration": i_attempt,
            "duration_s": duration_s,
            "world": sum(1 for a in state.actors if a is not None),
            "metrics": round_metrics,
        }
        for actor in state.actors:
            if actor is not None:
                actor._distributed_callbacks.after_round(actor, record)

    def _checkpoint_and_drain(iteration, save):
        """Drain the callback queue; on a checkpoint boundary (``save``)
        first read the booster back, serialise it and queue it. The
        ``driver.checkpoint`` span is the stall a save costs the loop, with
        ``checkpoint.readback`` and ``checkpoint.serialise`` inside it; its
        self time is the queue and the commit (the ``checkpoint.commit``
        event lands inside it)."""
        if not save:
            _handle_queue(state.queue, state.checkpoint, callback_returns)
            return
        with tracer.span("driver.checkpoint", round=iteration):
            with tracer.span("checkpoint.readback") as readback:
                booster = engine.get_booster(readback=readback)
            with tracer.span("checkpoint.serialise") as serialise:
                raw = _serialize_booster(booster)
                serialise.update(bytes=len(raw),
                                 trees=booster.num_boosted_rounds())
            state.queue.put((0, _Checkpoint(iteration, raw)))
            _handle_queue(state.queue, state.checkpoint, callback_returns)

    def _schedule_replacements(force=False):
        if ENV.ELASTIC_RESTART_DISABLED:
            return
        if force:
            state.last_resource_check_at = 0.0
        elastic_mod._maybe_schedule_new_actors(
            training_state=state,
            num_cpus_per_actor=ray_params.cpus_per_actor,
            num_gpus_per_actor=max(0, ray_params.gpus_per_actor),
            resources_per_actor=ray_params.resources_per_actor,
            ray_params=ray_params,
            load_data=[dtrain] + [e[0] for e in evals],
        )

    def _swap_engine(new_engine, kind, started):
        """Install ``new_engine`` as the attempt's engine; cache the old one
        for a later grow-back; update the robustness metrics and total_n.
        ``kind == "resume"`` (a blame-less transient failure continuing on
        the unchanged world) moves no capacity, so it counts as neither a
        shrink nor a grow."""
        nonlocal engine, engine_base, total_n
        if new_engine is not engine:
            _cache_world(engine)
            engine = new_engine
            proxy._rebind(engine)
        engine_base = engine.iteration_offset - attempt_offset0
        new_alive = [a for a in state.actors if a is not None]
        new_total = sum(a.local_n(dtrain) for a in new_alive)
        orphaned = max(0, total_n - new_total) if kind == "shrink" else 0
        if kind == "shrink":
            rob["shrinks"] = rob.get("shrinks", 0) + 1
            rob["orphaned_rows"] = rob.get("orphaned_rows", 0) + orphaned
        elif kind == "grow":
            rob["grows"] = rob.get("grows", 0) + 1
        recompile_s = round(time.time() - started, 4)
        rob["recompile_s"] = round(
            rob.get("recompile_s", 0.0) + recompile_s, 4
        )
        total_n = new_total
        state.additional_results["total_n"] = total_n
        # the machine-readable world-change record: the timeline entry every
        # chaos scenario reconstructs its shrink→grow sequence from. The
        # current global round is offset + trees boosted on this engine —
        # offset alone is stale when the immediate-reintegration fast path
        # reuses the attempt's compiled engine mid-flight.
        obs.get_tracer().event(
            # static literals (not f"world.{kind}") so the timeline event
            # vocabulary stays greppable and checkable against TRACE_NAMES
            "world.shrink" if kind == "shrink"
            else "world.grow" if kind == "grow" else "world.resume",
            round=engine.iteration_offset + engine.num_round_trees,
            attrs={
                "world": len(new_alive),
                "orphaned_rows": orphaned,
                "recompile_s": recompile_s,
            },
        )
        obs.get_registry().counter(f"rxgb_train_{kind}s_total").inc()

    def _coalesce_deaths():
        """Fold near-simultaneous deaths into the CURRENT failure: drain the
        death-coalescing mailbox and probe actor liveness, blaming every
        additional dead rank NOW so a whole lost domain costs one shrink and
        one retrace instead of N sequential shrink/recompile cycles. With
        ``RXGB_ELASTIC_DEATH_COALESCE_S > 0`` the sweep lingers until the
        window closes, catching stragglers of a correlated loss; at 0 it
        still folds everything already dead."""
        deadline = time.time() + max(
            0.0, float(ENV.ELASTIC_DEATH_COALESCE_S)
        )
        extra = []
        while True:
            noted = set(state.death_coalescer.drain())
            noted.update(
                rank for rank, a in enumerate(state.actors)
                if a is not None and not a.alive
            )
            for rank in sorted(noted):
                if state.actors[rank] is None:
                    continue  # already blamed (possibly by this sweep)
                state.actors[rank].kill()
                state.actors[rank] = None
                state.failed_actor_ranks.add(rank)
                extra.append(rank)
            now = time.time()
            if now >= deadline:
                return extra
            time.sleep(min(0.005, deadline - now))

    def _note_domains_lost(blamed):
        """Domain attribution of a failure: every domain whose LAST alive
        rank is among ``blamed`` is a lost domain — count it and put a
        ``world.domain_down`` record on the timeline."""
        dm = state.domain_map
        if dm is None or not blamed:
            return
        rnd = engine.iteration_offset + engine.num_round_trees
        for dom in dm.domains_of(blamed):
            ranks = dm.ranks_of(dom)
            if all(state.actors[r] is None for r in ranks):
                rob["domains_lost"] = rob.get("domains_lost", 0) + 1
                obs.get_tracer().event(
                    "world.domain_down", round=rnd,
                    attrs={"domain": dom, "ranks": list(ranks)},
                )

    def _note_domains_up(promoted):
        """Emit ``world.domain_up`` for every domain ``promoted`` made whole
        again — the timeline closure of its ``world.domain_down``."""
        dm = state.domain_map
        if dm is None or not promoted:
            return
        rnd = engine.iteration_offset + engine.num_round_trees
        for dom in dm.domains_of(promoted):
            if all(state.actors[r] is not None for r in dm.ranks_of(dom)):
                obs.get_tracer().event(
                    "world.domain_up", round=rnd,
                    attrs={
                        "domain": dom,
                        "ranks": [
                            r for r in promoted if dm.domain_of(r) == dom
                        ],
                    },
                )

    def _world_is_current(world_actors):
        """True when ``world_actors`` is exactly the world the CURRENT
        engine was built over (same ranks, same shard rows) — continuation
        then needs no rebuild at all: the device state is already live."""
        from xgboost_ray_tpu.engine import shard_layout_fingerprint

        if tuple(a.rank for a in world_actors) != getattr(
            engine, "_world_key", None
        ):
            return False
        return (
            shard_layout_fingerprint([a.get_shard(dtrain) for a in world_actors])
            == getattr(engine, "_shard_fingerprint", None)
        )

    def _grow_at_boundary():
        """Reintegrate the due COMPLETE domains at a round boundary by
        re-sharding the running world in place — the in-memory booster
        carries every boosted round, so reintegration replays NOTHING, and
        a domain re-admits as a unit (``state.domains_due`` holds only
        domains whose every dead rank is staged and past grace — a
        half-staged domain keeps waiting, it never half-grows). Falls back
        to the legacy restart-from-checkpoint reintegration
        (``RayXGBoostActorAvailable``) when the in-place grow fails."""
        started = time.time()
        try:
            booster_now = engine.get_booster()
        except Exception as exc:  # noqa: BLE001 - fall back to restart
            raise RayXGBoostActorAvailable(
                "A new worker is ready but the in-memory booster could not "
                "be snapshotted; restarting from the latest checkpoint."
            ) from exc
        due = list(state.domains_due or ())
        dm = state.domain_map
        if due and dm is not None:
            due_ranks = {r for dom in due for r in dm.ranks_of(dom)}
            promoted = [
                r for r, p in (state.pending_actors or {}).items()
                if p.ready and r in due_ranks
            ]
        else:
            promoted = [
                r for r, p in (state.pending_actors or {}).items() if p.ready
            ]
        state.domains_due = []
        _promote_pending_actors(state, ranks=promoted)
        _rewire_actors(state)
        target = [a for a in state.actors if a is not None]
        try:
            new_engine = _build_world(target, booster_now, donor=engine)
        except Exception as exc:  # noqa: BLE001 - fall back to restart
            raise RayXGBoostActorAvailable(
                f"In-place reintegration failed ({exc}); restarting from "
                f"the latest checkpoint with the restored world."
            ) from exc
        for r in promoted:
            if state.actors[r] is not None:
                state.actors[r]._distributed_callbacks.before_train(
                    state.actors[r]
                )
        _swap_engine(new_engine, "grow", started)
        _note_domains_up(promoted)
        logger.info(
            f"[RayXGBoost] Reintegrated ranks {promoted} in place at a round "
            f"boundary ({len(target)} workers, zero rounds replayed)."
        )

    def _inflight_recover(exc) -> bool:
        """Zero-replay elastic continuation for a mid-attempt failure:
        reintegrate immediately when every dead rank's replacement is
        already staged and no grace period applies (the world never
        actually shrinks — zero recompile, bitwise continuation), otherwise
        shrink to the survivors in place, recompiling once for the smaller
        mesh and continuing from the in-memory booster. Near-simultaneous
        deaths (a whole fault domain dying at once) are coalesced into ONE
        shrink before the target world is chosen. Returns False when the
        in-flight path is unavailable (non-elastic, an engine without
        ``can_reshard``, too many dead, rebuild failure, repeated failures
        without progress) — the caller re-raises into the
        restart-from-checkpoint policy."""
        if not ray_params.elastic_training:
            return False
        if not _engine_can_reshard(engine):
            return False
        if state.consecutive_failures >= 3:
            # repeated failures with no completed round in between: stop
            # absorbing them in-flight and let the retry loop's bounded
            # restart/backoff policy take over
            return False
        try:
            booster_now = engine.get_booster()
        except Exception as snap_exc:  # noqa: BLE001 - fall back to restart
            logger.warning(
                "[RayXGBoost] cannot snapshot the in-memory booster (%s); "
                "falling back to restart-from-checkpoint.", snap_exc,
            )
            return False
        alive_before = sum(1 for a in state.actors if a is not None)
        dead_before = {r for r, a in enumerate(state.actors) if a is None}
        _apply_failure(state, exc)
        # death coalescing: fold every near-simultaneous death (the rest of
        # a dying domain, out-of-band kills) into THIS failure so the world
        # shrinks once, retraces once, replays nothing
        _coalesce_deaths()
        alive_n = sum(1 for a in state.actors if a is not None)
        blamed = sorted(
            {r for r, a in enumerate(state.actors) if a is None} - dead_before
        )
        dead = ray_params.num_actors - alive_n
        if alive_n == 0 or dead > ray_params.max_failed_actors:
            return False
        for rank in list(state.failed_actor_ranks):
            state.elastic_dead_ranks.add(rank)
            state.failed_actor_ranks.discard(rank)
        state.recover_started_at = time.time()
        obs.get_tracer().event(
            "failure.detected", round=engine.iteration_offset
            + engine.num_round_trees,
            attrs={
                "ranks": sorted(state.elastic_dead_ranks),
                "in_flight": True,
            },
        )
        if len(blamed) > 1:
            rob["deaths_coalesced"] = (
                rob.get("deaths_coalesced", 0) + len(blamed) - 1
            )
            obs.get_tracer().event(
                "world.deaths_coalesced",
                round=engine.iteration_offset + engine.num_round_trees,
                attrs={"ranks": blamed, "extra": len(blamed) - 1},
            )
        _note_domains_lost(blamed)
        # stage replacements NOW: when every dead rank reloads within the
        # scheduler's fast path and no grace period applies, the world is
        # restored before the next round even starts
        _schedule_replacements(force=True)
        # a failure that blamed nobody (liveness probe found every actor
        # healthy) changes no capacity: continuing on the unchanged world
        # is a "resume", not a shrink — the robustness block is an
        # operator-facing contract and must not report phantom world loss
        kind = "shrink" if alive_n < alive_before else "resume"
        promoted = []
        target = [a for a in state.actors if a is not None]
        if (
            not ENV.ELASTIC_RESTART_DISABLED
            and float(ENV.ELASTIC_RESTART_GRACE_PERIOD_S) <= 0
            and state.elastic_dead_ranks
            and all(
                (state.pending_actors or {}).get(r) is not None
                and state.pending_actors[r].ready
                for r in state.elastic_dead_ranks
            )
        ):
            # immediate reintegration: build the grown world's engine from
            # the STAGED replacements first, promote only on success — a
            # rebuild failure must leave the replacements pending (for the
            # fallback restart to use), not get them killed as casualties
            # of the re-raised failure
            kind = "grow"
            promoted = sorted(state.elastic_dead_ranks)
            merged = list(state.actors)
            for r in promoted:
                merged[r] = state.pending_actors[r].actor
            target = [a for a in merged if a is not None]
        # recompile clock starts AFTER replacement staging: recompile_s is
        # the runbook's "rebuild/retrace cost" signal and must not absorb
        # the scheduler's (up to 1 s) data-load fast-path wait
        started = time.time()
        try:
            if _world_is_current(target):
                # the engine's device state already covers this exact world
                # (immediate reintegration, or a failure that blamed no
                # actor): pure resume — no rebuild, no recompile
                new_engine = engine
            else:
                new_engine = _build_world(target, booster_now, donor=engine)
        except Exception as build_exc:  # noqa: BLE001 - fall back to restart
            logger.warning(
                "[RayXGBoost] in-flight elastic %s failed (%s); falling "
                "back to restart-from-checkpoint.", kind, build_exc,
            )
            return False
        if kind == "grow":
            _promote_pending_actors(state)
            _rewire_actors(state)
            for r in promoted:
                if state.actors[r] is not None:
                    state.actors[r]._distributed_callbacks.before_train(
                        state.actors[r]
                    )
        # counted only when the in-flight path actually takes over (the
        # fallback return-False paths leave the increment to the outer
        # retry handler — one failure, one count)
        state.consecutive_failures += 1
        _swap_engine(new_engine, kind, started)
        if kind == "grow":
            _note_domains_up(promoted)
        if kind == "resume":
            logger.warning(
                f"[RayXGBoost] A transient failure blamed no worker. "
                f"Resuming in-flight with the unchanged {len(target)}-worker "
                f"world — zero rounds replayed."
            )
        else:
            logger.warning(
                f"[RayXGBoost] A worker died. Continuing in-flight ({kind}) "
                f"with {len(target)} workers — zero rounds replayed."
            )
        return True

    es_metric = None
    es_maximize = False
    es_best: Optional[float] = None
    es_best_iter = -1
    if early_stopping_rounds is not None and evals:
        from xgboost_ray_tpu.ops.metrics import is_maximize_metric

        es_set = evals[-1][1]
        es_metric = engine.metric_names[-1]
        es_maximize = maximize if maximize is not None else is_maximize_metric(es_metric)

    checkpoint_frequency = ray_params.checkpoint_frequency
    train_started = time.time()
    state.training_started_at = train_started
    profile_dir = ENV.PROFILE_DIR
    if profile_dir:
        import jax

        _stop_profile_if_running()  # clear any trace leaked by a prior abort
        jax.profiler.start_trace(profile_dir)
    round_times = state.additional_results.setdefault("round_times_s", [])
    # true per-dispatch wall times: one entry per compiled dispatch — a
    # fused scan chunk OR a single per-round step. round_times_s keeps its
    # historical shape (a fused chunk contributes its MEAN replicated per
    # round, which hides per-chunk variance); consumers that want the real
    # distribution read chunk_times_s (bench.py records both).
    chunk_times = state.additional_results.setdefault("chunk_times_s", [])
    stop_requested = False
    last_status = time.time()

    for model_cb in callbacks:
        if hasattr(model_cb, "before_training"):
            model_cb.before_training(proxy)

    # Fast path: no per-round host interaction needed -> fuse rounds into
    # compiled multi-round programs (lax.scan inside shard_map; see
    # engine.step_many). Scan length is bounded by ENV.SCAN_MAX_CHUNK and
    # clamped so no scan crosses a checkpoint boundary.
    state.rounds_this_attempt = 0
    use_batched = (
        not callbacks
        and obj is None
        and feval is None
        and early_stopping_rounds is None
        and engine.can_batch_rounds()
        and boost_rounds_left > 1
        # round-granular fault injection needs the per-round path so a
        # scheduled fault hits its exact round, not a fused-chunk boundary
        and not faults.plan_targets("actor.train_round")
    )
    if use_batched:
        # chunk size decoupled from checkpoint_frequency: scans never fuse
        # more than SCAN_MAX_CHUNK rounds into one program, but checkpoints
        # are still emitted exactly at checkpoint_frequency boundaries
        chunk = max(1, ENV.SCAN_MAX_CHUNK)
        completed = 0
        while completed < boost_rounds_left:
            if state.stop_event.is_set():
                raise RayXGBoostTrainingStopped("Training was aborted.")
            n = min(chunk, boost_rounds_left - completed)
            if checkpoint_frequency:
                # never scan across a checkpoint boundary
                to_boundary = checkpoint_frequency - (completed % checkpoint_frequency)
                n = min(n, to_boundary)
            chunk_started = time.time()
            try:
                chunk_results = engine.step_many(completed - engine_base, n)
            except (RayActorError, RayTaskError) as exc:
                if not _inflight_recover(exc):
                    raise
                completed = engine_base + engine.num_round_trees
                continue
            chunk_wall = time.time() - chunk_started
            chunk_times.append({"rounds": n, "seconds": round(chunk_wall, 6)})
            round_times.extend([chunk_wall / n] * n)
            state.rounds_this_attempt += n
            _mark_recovered(state)
            with tracer.span("driver.callbacks", rounds=n):
                for ri, round_metrics in enumerate(chunk_results):
                    for set_name, metrics in round_metrics.items():
                        for metric_name, value in metrics.items():
                            evals_result.setdefault(set_name, {}).setdefault(
                                metric_name, []
                            ).append(value)
                    # same per-round interval semantics as the per-round path
                    i = completed + ri
                    _fire_after_round(i, round_metrics, round_times[-1])
                    if verbose_eval and (
                        verbose_eval is True
                        or (i % max(int(verbose_eval), 1) == 0)
                    ):
                        flat = "\t".join(
                            f"{sn}-{mn}:{ms[mn]:.5f}"
                            for sn, ms in round_metrics.items()
                            for mn in ms
                        )
                        print(f"[{i}]\t{flat}")
            completed += n
            _checkpoint_and_drain(
                attempt_offset0 + completed - 1,
                save=checkpoint_frequency and (
                    completed % checkpoint_frequency == 0
                    or completed == boost_rounds_left
                ),
            )
            if ray_params.elastic_training and not ENV.ELASTIC_RESTART_DISABLED:
                _schedule_replacements()
                if elastic_mod._update_scheduled_actor_states(
                    state,
                    raise_on_ready=not _engine_can_reshard(engine),
                ):
                    _grow_at_boundary()
            if time.time() - last_status > ENV.STATUS_FREQUENCY_S:
                logger.info(
                    f"[RayXGBoost] Training in progress "
                    f"({time.time() - train_started:.0f}s, round {completed})."
                )
                last_status = time.time()

        booster = engine.get_booster()
        for actor in [a for a in state.actors if a is not None]:
            actor._distributed_callbacks.after_train(
                actor, {"evals_result": evals_result}
            )
        _handle_queue(state.queue, state.checkpoint, callback_returns)
        state.additional_results["callback_returns"] = callback_returns
        _record_engine_readouts(state, engine, booster)
        _stop_profile_if_running()
        train_time = time.time() - train_started
        return booster, evals_result, {
            "train_n": total_n,
            "training_time_s": train_time,
            "stopped_early": False,
            "completed_rounds": completed,
        }

    completed = 0
    i = 0
    while i < boost_rounds_left:
        if state.stop_event.is_set():
            raise RayXGBoostTrainingStopped("Training was aborted.")

        try:
            # driver.callbacks spans: user and framework code between
            # dispatches (``hook`` says which), so that a host gap in a
            # device trace has a name
            if callbacks:
                with tracer.span(
                    "driver.callbacks", round=attempt_offset0 + i,
                    hook="before_iteration",
                ):
                    for model_cb in callbacks:
                        if hasattr(model_cb, "before_iteration"):
                            model_cb.before_iteration(proxy, i, evals_result)

            faults.fire(
                "actor.train_round",
                round=attempt_offset0 + i,
                world=sum(1 for a in state.actors if a is not None),
            )

            round_started = time.time()
            gh_custom = None
            if obj is not None:
                # process-local rows (the reference computes the custom
                # objective per actor on its shard, ``main.py:745-752``);
                # label_np/weight_np hold exactly this process's rows.
                # Single-host: all rows.
                with tracer.span(
                    "driver.callbacks", round=attempt_offset0 + i, hook="obj"
                ):
                    margins = engine.get_margins_local()
                    preds = margins[:, 0] if engine.n_outputs == 1 else margins
                    faux = _FauxDMatrix(
                        engine.label_np, engine.weight_np, engine.group_ptr
                    )
                    g, h = obj(preds, faux)
                    gh_custom = (g, h)

            round_metrics = engine.step(i - engine_base, gh_custom=gh_custom)
            completed += 1
            state.rounds_this_attempt += 1
            _mark_recovered(state)
            round_wall = time.time() - round_started
            round_times.append(round_wall)
            chunk_times.append({"rounds": 1, "seconds": round(round_wall, 6)})

            with tracer.span(
                "driver.callbacks", round=attempt_offset0 + i,
                hook="after_round",
            ):
                # custom metric (feval) computed per process on its local rows,
                # then combined as a weighted mean across processes (the
                # reference's per-worker metric averaging). Single-host: one
                # call over all rows.
                if feval is not None:
                    for es in engine.evals:
                        margin = engine.get_margins_local(es)
                        preds = margin[:, 0] if engine.n_outputs == 1 else margin
                        faux = _FauxDMatrix(
                            es.label_np if es.label_np is not None else engine.label_np,
                            es.weight_np,
                            es.group_ptr,
                        )
                        name, value = feval(preds, faux)
                        round_metrics.setdefault(es.name, {})[name] = (
                            engine.combine_host_scalar(value, es, metric=name)
                        )

                for set_name, metrics in round_metrics.items():
                    for metric_name, value in metrics.items():
                        evals_result.setdefault(set_name, {}).setdefault(
                            metric_name, []
                        ).append(value)

                _fire_after_round(i, round_metrics, round_times[-1])

                if verbose_eval and (
                    verbose_eval is True or (i % max(int(verbose_eval), 1) == 0)
                ):
                    flat = "\t".join(
                        f"{sn}-{mn}:{v[-1]:.5f}"
                        for sn, ms in evals_result.items()
                        for mn, v in ms.items()
                    )
                    print(f"[{i}]\t{flat}")

            # driver-side checkpointing (mirror of the rank-0 checkpoint
            # callback, main.py:612-626): every k rounds + after the last
            is_last = i == boost_rounds_left - 1
            _checkpoint_and_drain(
                attempt_offset0 + i,
                save=checkpoint_frequency and (
                    (i + 1) % checkpoint_frequency == 0 or is_last
                ),
            )

            # elastic: reintegrate failed ranks at the round boundary —
            # in place (zero replay) for reshardable engines, via the
            # legacy RayXGBoostActorAvailable restart otherwise
            if ray_params.elastic_training and not ENV.ELASTIC_RESTART_DISABLED:
                _schedule_replacements()
                if elastic_mod._update_scheduled_actor_states(
                    state,
                    raise_on_ready=not _engine_can_reshard(engine),
                ):
                    _grow_at_boundary()

            stop = False
            with tracer.span(
                "driver.callbacks", round=attempt_offset0 + i,
                hook="after_iteration",
            ):
                for model_cb in callbacks:
                    if hasattr(model_cb, "after_iteration"):
                        stop = model_cb.after_iteration(proxy, i, evals_result) or stop

                if es_metric is not None:
                    try:
                        cur = evals_result[evals[-1][1]][es_metric][-1]
                    except KeyError:
                        cur = None
                    if cur is not None:
                        better = (
                            es_best is None
                            or (es_maximize and cur > es_best)
                            or (not es_maximize and cur < es_best)
                        )
                        if better:
                            es_best, es_best_iter = cur, i
                        elif i - es_best_iter >= early_stopping_rounds:
                            stop = True

            if time.time() - last_status > ENV.STATUS_FREQUENCY_S:
                logger.info(
                    f"[RayXGBoost] Training in progress "
                    f"({time.time() - train_started:.0f}s, round {i})."
                )
                last_status = time.time()

            if stop:
                stop_requested = True
                break
            i += 1
        except (RayActorError, RayTaskError) as exc:
            if not _inflight_recover(exc):
                raise
            # the in-memory booster is the single source of truth for how
            # many attempt rounds are complete (a failure before the step
            # re-runs round i; one after it does not)
            i = engine_base + engine.num_round_trees
            completed = i

    booster = engine.get_booster()
    if es_metric is not None and es_best_iter >= 0:
        # es_best_iter is attempt-local; xgboost reports the *global* boosting
        # round, so rebase by the continuation offset (xgb_model / restart).
        booster.best_iteration = attempt_offset0 + es_best_iter
        booster.best_score = es_best

    for model_cb in callbacks:
        if hasattr(model_cb, "after_training"):
            model_cb.after_training(proxy)

    for actor in [a for a in state.actors if a is not None]:
        actor._distributed_callbacks.after_train(actor, {"evals_result": evals_result})

    _handle_queue(state.queue, state.checkpoint, callback_returns)
    state.additional_results["callback_returns"] = callback_returns
    _record_engine_readouts(state, engine, booster)
    _stop_profile_if_running()

    train_time = time.time() - train_started
    return booster, evals_result, {
        "train_n": total_n,
        "training_time_s": train_time,
        "stopped_early": stop_requested,
        "completed_rounds": completed,
    }


# ---------------------------------------------------------------------------
# Remote-execution tier (mirror of the reference's Ray-client mode,
# ``main.py:1413-1452``, ``util.py:82-110``): there, a thin Ray client must
# not run the training loop locally, so train/predict re-run as a 0-CPU
# remote task pinned to the server node. The TPU analog of "thin client" is a
# driver process that does not own the accelerator: ``_remote=True`` ships
# the call to a freshly spawned server process that owns the devices and
# returns the results by pickle. Spawn (not fork) so the server starts with
# clean JAX/XLA state. A chip belongs to one process at a time, so this only
# works from a parent that has NOT initialized a JAX backend: a parent that
# already touched ``jax.devices()`` holds the chips and the server then
# fails or hangs at start-up.
# ---------------------------------------------------------------------------


def _remote_server_main(conn, mode: str, payload):
    """Entry point of the spawned server process (top level: spawn pickles
    it by reference). The spawned interpreter inherits the parent's
    environment, so ``JAX_PLATFORMS`` selects its backend as usual."""
    try:
        if mode == "train":
            params, dtrain, num_boost_round, evals, ray_params, kwargs = payload
            evals_result: Dict = {}
            additional_results: Dict = {}
            bst = train(
                params, dtrain, num_boost_round, evals=evals,
                evals_result=evals_result,
                additional_results=additional_results,
                ray_params=ray_params, _remote=False, **kwargs,
            )
            conn.send((True, (bst, evals_result, additional_results)))
        else:
            model, data, ray_params, kwargs = payload
            out = predict(model, data, ray_params=ray_params, _remote=False,
                          **kwargs)
            conn.send((True, out))
    except Exception as exc:  # noqa: BLE001 - marshal any failure back
        import traceback

        conn.send((False, f"{type(exc).__name__}: {exc}\n"
                          f"{traceback.format_exc()[-2000:]}"))
    finally:
        conn.close()


def _run_remote(mode: str, payload):
    """Run one train/predict call in a spawned server process and return its
    unpickled result. Raises RayXGBoostTrainingError on remote failure or
    server death. Payload objects (matrices, callbacks, custom objectives)
    must be picklable — the same constraint the reference's client mode puts
    on its remote task arguments. NOTE: standard multiprocessing spawn
    semantics apply — a script calling ``_remote=True`` at module top level
    must guard it under ``if __name__ == "__main__":`` or the spawned server
    re-executes the script."""
    import multiprocessing as mp

    ctx = mp.get_context("spawn")
    parent_conn, child_conn = ctx.Pipe()
    proc = ctx.Process(
        target=_remote_server_main, args=(child_conn, mode, payload),
        daemon=False,
    )
    proc.start()
    child_conn.close()
    try:
        ok, result = parent_conn.recv()
    except EOFError:
        proc.join()
        raise RayXGBoostTrainingError(
            f"the remote {mode} server process died (exit code "
            f"{proc.exitcode}) before returning a result."
        )
    finally:
        parent_conn.close()
    proc.join()
    if not ok:
        raise RayXGBoostTrainingError(
            f"remote {mode} failed on the server process:\n{result}"
        )
    return result


# ---------------------------------------------------------------------------
# Public train() (mirror of ``main.py:1341-1747``)
# ---------------------------------------------------------------------------


def train(
    params: Dict,
    dtrain: RayDMatrix,
    num_boost_round: int = 10,
    *args,
    evals: Union[List[Tuple[RayDMatrix, str]], Tuple] = (),
    evals_result: Optional[Dict] = None,
    additional_results: Optional[Dict] = None,
    ray_params: Union[None, RayParams, Dict] = None,
    _remote: Optional[bool] = None,
    **kwargs,
) -> RayXGBoostBooster:
    """Distributed GBDT training on the TPU mesh.

    Drop-in signature mirror of ``xgboost_ray.train`` (``main.py:1341``).
    Failure handling matches the reference's three-way policy (elastic
    continuation / recreate-from-checkpoint / abort), driven by
    ``ray_params``.

    Observability: every run is traced by a fresh run-scoped
    :class:`obs.Tracer` — spans at every layer boundary (``data.load``,
    ``engine.init``, one ``dispatch`` per compiled dispatch with its
    enqueue / wait halves, ``round`` records and ``compile.*`` children,
    ``driver.checkpoint`` / ``driver.callbacks`` between dispatches) and
    lifecycle events (failures, world shrink/grow, checkpoint commits,
    backoff) — and the timeline is returned under
    ``additional_results["obs"]``. ``RXGB_TRACE=0`` disables tracing,
    ``RXGB_TRACE_DIR`` streams per-rank JSONL.
    """
    tracer = obs.Tracer()
    with obs.use_tracer(tracer):
        return _train_impl(
            params,
            dtrain,
            num_boost_round,
            *args,
            evals=evals,
            evals_result=evals_result,
            additional_results=additional_results,
            ray_params=ray_params,
            _remote=_remote,
            _run_tracer=tracer,
            **kwargs,
        )


def _train_impl(
    params: Dict,
    dtrain: RayDMatrix,
    num_boost_round: int = 10,
    *args,
    evals: Union[List[Tuple[RayDMatrix, str]], Tuple] = (),
    evals_result: Optional[Dict] = None,
    additional_results: Optional[Dict] = None,
    ray_params: Union[None, RayParams, Dict] = None,
    _remote: Optional[bool] = None,
    _run_tracer=None,
    **kwargs,
) -> RayXGBoostBooster:
    """The driver body behind :func:`train` (which scopes the run tracer)."""
    start_time = time.time()
    if args:
        raise TypeError(
            "train() takes keyword arguments after num_boost_round; got "
            f"positional {args}"
        )
    _validate_kwargs_for_func(kwargs, _KNOWN_TRAIN_KWARGS, "train")
    ray_params = _validate_ray_params(ray_params)
    if isinstance(evals, tuple) and len(evals) == 2 and isinstance(evals[1], str):
        evals = [evals]  # single (dm, name) tuple — normalize BEFORE remote ship

    # online-serving handoff: when a serve.ModelRegistry is passed, the
    # trained booster is hot-swapped into it on completion (drain-then-flip,
    # see serve/registry.py) so a colocated endpoint picks up the retrain
    # without a restart. Popped before the remote ship: a registry holds
    # live locks/threads and cannot cross the process boundary.
    serve_registry = kwargs.pop("serve_registry", None)
    if serve_registry is not None and _remote:
        raise ValueError(
            "serve_registry cannot be combined with _remote=True: the "
            "registry lives in this process. Train remotely, then call "
            "registry.load(booster) on the result."
        )

    if _remote:
        bst, remote_evals, remote_extra = _run_remote(
            "train",
            (params, dtrain, num_boost_round, list(evals), ray_params, kwargs),
        )
        if evals_result is not None:
            evals_result.update(remote_evals)
        if additional_results is not None:
            additional_results.update(remote_extra)
        return bst

    if not isinstance(dtrain, RayDMatrix):
        raise ValueError(
            f"The `dtrain` argument passed to `train()` is not a RayDMatrix, "
            f"but of type {type(dtrain)}. FIX THIS by instantiating a "
            f"RayDMatrix first: `dtrain = RayDMatrix(data, labels)`."
        )
    for deval, name in evals:
        if not isinstance(deval, RayDMatrix):
            raise ValueError(
                f"Evaluation data must be a RayDMatrix, got {type(deval)} "
                f"for eval set {name!r}."
            )

    # Tune integration: auto-inject the report/checkpoint callback when
    # running inside a tuning session (mirror main.py:1477-1480)
    from xgboost_ray_tpu.compat import wrap_callbacks
    from xgboost_ray_tpu import tune as tune_mod

    kwargs_callbacks = wrap_callbacks(kwargs.get("callbacks"), num_boost_round)
    kwargs_callbacks = tune_mod._try_add_tune_callback(kwargs_callbacks)

    parsed = parse_params(params)  # early validation (tree_method etc.)
    if serve_registry is not None and parsed.booster == "gblinear":
        # fail BEFORE training, not after hours of boosting: the serve
        # layer compiles the padded forest walk, which linear models lack
        raise ValueError(
            "serve_registry is not supported with booster='gblinear' "
            "(the serving layer compiles tree-walk programs). Train "
            "without serve_registry and serve the model another way."
        )
    del parsed

    if ray_params.elastic_training and ray_params.max_failed_actors == 0:
        raise ValueError(
            "Elastic training enabled but the maximum number of failed "
            "actors is set to 0. FIX THIS by setting "
            "`RayParams(max_failed_actors=N)` to something > 0."
        )
    if ray_params.elastic_training and ray_params.max_actor_restarts == 0:
        raise ValueError(
            "Elastic training enabled but the maximum number of actor "
            "restarts is set to 0. FIX THIS by setting "
            "`RayParams(max_actor_restarts=N)` (-1 for unlimited)."
        )

    max_actor_restarts = (
        ray_params.max_actor_restarts
        if ray_params.max_actor_restarts >= 0
        else float("inf")
    )

    obj = kwargs.get("obj")
    feval = kwargs.get("feval") or kwargs.get("custom_metric")
    early_stopping_rounds = kwargs.get("early_stopping_rounds")
    maximize = kwargs.get("maximize")
    verbose_eval = kwargs.get("verbose_eval", False)
    xgb_model = _coerce_model(kwargs.get("xgb_model"))

    # eager central loading on the driver (mirror main.py:1555-1556)
    with obs.get_tracer().span(
        "data.load", matrices=1 + len(evals)
    ) as load_attrs:
        dtrain.load_data(ray_params.num_actors)
        for deval, _ in evals:
            deval.load_data(ray_params.num_actors)
        load_attrs.update(_shard_attrs(dtrain))

    state = _TrainingState(
        actors=[None] * ray_params.num_actors,
        queue=Queue(),
        stop_event=Event(),
        checkpoint=_Checkpoint(
            iteration=-1,
            value=_serialize_booster(xgb_model) if xgb_model else None,
        ),
        additional_results={},
        failed_actor_ranks=set(range(ray_params.num_actors)),
        pending_actors={},
    )

    boost_rounds_left = num_boost_round
    last_checkpoint_value = state.checkpoint.value
    tries = 0
    total_training_time = 0.0
    final_evals_result: Dict = {}
    booster: Optional[RayXGBoostBooster] = None

    # recovery observability: restarts taken, rounds replayed after each
    # restart-from-checkpoint, and failure->first-new-round latency. Present
    # (all zeros) even on clean runs so dashboards have a stable shape.
    robustness = state.additional_results.setdefault(
        "robustness",
        {
            "restarts": 0,
            "elastic_restarts": 0,
            "rounds_replayed": 0,
            "time_to_recover_s": 0.0,
            "backoff_s": 0.0,
            # in-flight elastic continuation (zero-replay shrink/grow)
            "shrinks": 0,
            "grows": 0,
            "orphaned_rows": 0,
            "recompile_s": 0.0,
            # failure-domain attribution: whole domains lost (every rank of
            # the domain dead in one failure) and deaths folded into an
            # already-detected failure's single shrink (a lost domain of K
            # ranks is 1 shrink + K-1 deaths_coalesced, never K shrinks)
            "domains_lost": 0,
            "deaths_coalesced": 0,
        },
    )

    def _xgb_base_rounds() -> int:
        return xgb_model.num_boosted_rounds() if xgb_model else 0

    def _account_failure(exc=None) -> None:
        """Called on every restart-causing exception: rounds progressed past
        the surviving checkpoint will be replayed by the next attempt."""
        progressed = (
            num_boost_round - boost_rounds_left
        ) + state.rounds_this_attempt
        if state.checkpoint.value:
            covered = (
                _deserialize_booster(state.checkpoint.value).num_boosted_rounds()
                - _xgb_base_rounds()
            )
        else:
            covered = 0
        replayed = max(0, progressed - covered)
        robustness["rounds_replayed"] += replayed
        state.rounds_this_attempt = 0
        state.recover_started_at = time.time()
        # opens the timeline clock "recovered" closes (matches the
        # robustness block's time_to_recover_s accounting)
        obs.get_tracer().event(
            "failure.detected",
            attrs={
                "ranks": sorted(getattr(exc, "ranks", None) or []),
                "rounds_replayed": replayed,
                "restart": True,
            },
        )

    attempt_no = -1
    run_tracer = obs.get_tracer()
    while tries <= max_actor_restarts:
        # restart-from-checkpoint round arithmetic (mirror main.py:1606-1612)
        if state.checkpoint.value and state.checkpoint.value != last_checkpoint_value:
            ckpt_booster = _deserialize_booster(state.checkpoint.value)
            done_rounds = ckpt_booster.num_boosted_rounds() - (
                xgb_model.num_boosted_rounds() if xgb_model else 0
            )
            boost_rounds_left = num_boost_round - done_rounds
            last_checkpoint_value = state.checkpoint.value
            if boost_rounds_left <= 0:
                # the checkpoint already covers every round: the restart IS
                # the recovery — close the clock before leaving the loop
                _mark_recovered(state)
                break

        attempt_no += 1
        try:
            # on the thread's span stack while it runs, so every span of the
            # attempt names it as parent; the outcome is set as it ends
            with run_tracer.span(
                "attempt", attempt=attempt_no, rounds_left=boost_rounds_left,
                outcome="error",
            ) as attempt_attrs:
                try:
                    booster, final_evals_result, stats = _train(
                        params,
                        dtrain,
                        boost_rounds_left,
                        evals=evals,
                        ray_params=ray_params,
                        obj=obj,
                        feval=feval,
                        callbacks=kwargs_callbacks,
                        early_stopping_rounds=early_stopping_rounds,
                        maximize=maximize,
                        verbose_eval=verbose_eval,
                        _training_state=state,
                    )
                    attempt_attrs["outcome"] = "ok"
                except RayXGBoostActorAvailable:
                    attempt_attrs["outcome"] = "elastic_restart"
                    raise
                except (RayActorError, RayTaskError):
                    attempt_attrs["outcome"] = "failed"
                    raise
            total_training_time += stats["training_time_s"]
            break
        except RayXGBoostActorAvailable as exc:
            _stop_profile_if_running()
            # elastic reintegration: free restart (mirror main.py:1661-1673)
            logger.info(f"[RayXGBoost] {exc} Restarting from checkpoint with "
                        f"reintegrated workers.")
            robustness["elastic_restarts"] += 1
            obs.get_registry().counter("rxgb_train_elastic_restarts_total").inc()
            _account_failure(exc)
            _promote_pending_actors(state)
            run_tracer.event(
                "world.restart",
                attrs={"elastic": True,
                       "world": sum(1 for a in state.actors if a is not None)},
            )
            state.queue = Queue()
            state.stop_event = Event()
            _rewire_actors(state)
            continue
        except (RayActorError, RayTaskError) as exc:
            _stop_profile_if_running()
            if state.training_started_at:
                total_training_time += time.time() - state.training_started_at
                state.training_started_at = 0.0
            robustness["restarts"] += 1
            obs.get_registry().counter("rxgb_train_restarts_total").inc()
            _account_failure(exc)
            # only REAL failures escalate the backoff exponent — the elastic
            # reintegration restart above replays rounds but is a planned
            # event, not a crash
            state.consecutive_failures += 1
            alive = _apply_failure(state, exc)
            if ray_params.elastic_training:
                dead = ray_params.num_actors - alive
                if dead > ray_params.max_failed_actors:
                    raise RayXGBoostTrainingError(
                        f"A worker died and too many workers are already dead "
                        f"({dead} > max_failed_actors="
                        f"{ray_params.max_failed_actors}). Aborting."
                    ) from exc
                logger.warning(
                    f"[RayXGBoost] A worker died. Continuing elastically with "
                    f"{alive} remaining workers."
                )
                # dead ranks are reintegrated in the background, not recreated
                # by the next attempt
                for rank in list(state.failed_actor_ranks):
                    state.elastic_dead_ranks.add(rank)
                    state.failed_actor_ranks.discard(rank)
            else:
                if tries + 1 > max_actor_restarts:
                    raise RayXGBoostTrainingError(
                        "A worker died during training and the maximum "
                        "number of retries is exhausted. Checkpoint the "
                        "model more frequently or raise "
                        "`RayParams(max_actor_restarts=N)`."
                    ) from exc
                logger.warning(
                    "[RayXGBoost] A worker died. Recreating it and restarting "
                    "from the latest checkpoint."
                )
            state.queue = Queue()
            state.stop_event = Event()
            _rewire_actors(state)
            # exponential backoff + jitter before the retry so a persistent
            # fault cannot crash-loop at full speed; indexed by CONSECUTIVE
            # failures (rewound on forward progress), so an isolated failure
            # hours into a job waits only the base delay
            # (RXGB_RESTART_BACKOFF_* to tune; base 0 disables)
            backoff = restart_backoff_s(state.consecutive_failures - 1)
            if backoff > 0:
                logger.warning(
                    f"[RayXGBoost] Backing off {backoff:.2f}s before "
                    f"restart {robustness['restarts']}."
                )
                robustness["backoff_s"] = round(
                    robustness["backoff_s"] + backoff, 4
                )
                run_tracer.event(
                    "backoff",
                    attrs={"seconds": round(backoff, 4),
                           "restart": robustness["restarts"]},
                )
                time.sleep(backoff)
            tries += 1
            continue
        except BaseException:
            # any other exit (user abort, unexpected error): don't leak a
            # running profiler trace into the next train() call
            _stop_profile_if_running()
            raise

    if booster is None:
        # all rounds were already covered by the checkpoint
        booster = _deserialize_booster(state.checkpoint.value)

    if evals_result is not None:
        evals_result.update(final_evals_result)

    total_time = time.time() - start_time
    state.additional_results["training_time_s"] = total_training_time
    state.additional_results["total_time_s"] = total_time
    if _run_tracer is not None and _run_tracer.enabled:
        # the queryable run timeline: spans at every layer boundary,
        # lifecycle events, ring-buffer truncation accounting
        state.additional_results["obs"] = _assemble_obs(_run_tracer)
    if additional_results is not None:
        additional_results.update(state.additional_results)

    if ray_params.verbose:
        logger.info(
            f"[RayXGBoost] Finished training after {total_time:.2f}s "
            f"({total_training_time:.2f}s pure training)."
        )
    if serve_registry is not None:
        state.additional_results["serve_model_version"] = serve_registry.load(
            booster
        )
        if additional_results is not None:
            additional_results["serve_model_version"] = state.additional_results[
                "serve_model_version"
            ]
    return booster


def _apply_failure(state: _TrainingState, exc) -> int:
    """Mark failed ranks dead; return number of alive actors.

    If the exception carries no rank information and liveness probing finds
    every actor healthy, no actor is blamed: the retry simply rebuilds the
    engine from the last checkpoint with the same world.
    """
    ranks = getattr(exc, "ranks", None) or []
    if not ranks:
        # unknown origin: probe liveness (mirror elastic.py:145-178)
        for rank, actor in enumerate(state.actors):
            if actor is not None and not actor.alive:
                ranks.append(rank)
    for rank in ranks:
        if state.actors[rank] is not None:
            state.actors[rank].kill()
            state.actors[rank] = None
            state.failed_actor_ranks.add(rank)
    return sum(1 for a in state.actors if a is not None)


def _rewire_actors(state: _TrainingState):
    for actor in state.actors:
        if actor is not None:
            actor.set_queue(state.queue)
            actor.set_stop_event(state.stop_event)
            actor._coalescer = state.death_coalescer
            if state.domain_map is not None:
                actor._domain = state.domain_map.domain_of(actor.rank)


def _promote_pending_actors(state: _TrainingState, ranks=None):
    """Install ready pending workers as live actors. ``ranks`` restricts the
    promotion (the round-boundary grow passes only the ranks of COMPLETE due
    domains — atomic domain grow-back); ``None`` promotes every ready worker
    (the legacy restart path, which rebuilds the whole world anyway)."""
    for rank, pending in list((state.pending_actors or {}).items()):
        if not pending.ready:
            continue  # still loading in the background; promote next time
        if ranks is not None and rank not in ranks:
            continue  # its domain is not complete yet: never half-grow
        state.actors[rank] = pending.actor
        state.failed_actor_ranks.discard(rank)
        state.elastic_dead_ranks.discard(rank)
        del state.pending_actors[rank]
    state.restart_training_at = None


# ---------------------------------------------------------------------------
# predict() (mirror of ``main.py:1750-1896``)
# ---------------------------------------------------------------------------


def _predict(
    model: RayXGBoostBooster,
    data: RayDMatrix,
    ray_params: RayParams,
    **kwargs,
):
    num_actors = ray_params.num_actors
    actors = [
        _create_actor(rank, num_actors, Queue(), Event(), ray_params.distributed_callbacks)
        for rank in range(num_actors)
    ]
    data.assign_shards_to_actors(actors)
    for actor in actors:
        actor.load_data(data)
        actor._distributed_callbacks.before_predict(actor)

    predict_kwargs = dict(kwargs)
    predict_kwargs.setdefault("validate_features", False)
    model_cats = getattr(model, "categories", None)
    if data.resolved_categories and not model_cats and model.cat_features:
        raise ValueError(
            "the prediction data auto-encoded categorical columns, but the "
            "model was trained on integer codes — the mappings cannot be "
            "aligned. Encode the data with the training codes instead."
        )
    shards = []
    for actor in actors:
        shard = actor.get_shard(data)
        if model_cats and data.resolved_categories != model_cats:
            # align this frame's auto-encoded codes with the model's mapping
            shard = translate_shard_categories(
                shard, data.resolved_categories, model_cats
            )
        shards.append(shard)

    # A user-passed base_margin addresses GLOBAL rows (original order); each
    # shard must receive its own rows' slice, not the array head.
    user_bm = predict_kwargs.pop("base_margin", None)
    if user_bm is not None and len(shards) > 1:
        user_bm = np.asarray(user_bm)
        if data.sharding == RayShardingMode.FIXED:
            sizes = [sh["data"].shape[0] for sh in shards]
            bm_shards = np.split(user_bm, np.cumsum(sizes)[:-1], axis=0)
        else:
            bm_shards = [
                user_bm[_get_sharding_indices(
                    data.sharding, r, len(shards), len(user_bm)
                )]
                for r in range(len(shards))
            ]
    elif user_bm is not None:
        bm_shards = [np.asarray(user_bm)]
    else:
        bm_shards = None

    results = _predict_shards_spmd(model, shards, predict_kwargs, bm_shards,
                                   ray_params=ray_params)
    if results is None:
        results = []
        for i, shard in enumerate(shards):
            if bm_shards is not None:
                bm = bm_shards[i]
            else:
                bm = shard.get("base_margin")
            if bm is not None:
                pred = model.predict(shard["data"], base_margin=bm, **predict_kwargs)
            else:
                pred = model.predict(shard["data"], **predict_kwargs)
            results.append(pred)
    for actor, pred in zip(actors, results):
        actor._distributed_callbacks.after_predict(actor, pred)

    if data.sharding == RayShardingMode.FIXED:
        return np.concatenate(results, axis=0)
    return combine_data(data.sharding, results)


def _predict_shards_spmd(model, shards, predict_kwargs, bm_shards=None,
                         ray_params=None):
    """SPMD fast path for distributed prediction: concatenate the actor
    shards (rank order), shard the rows over the training mesh's devices, and
    run the tree walk as one compiled shard_map program (the
    reference fans ``model.predict`` out to actors,
    ``xgboost_ray/main.py:1750-1896``; here the mesh IS the actor set).

    Returns per-actor prediction arrays (so callbacks and ``combine_data``
    see exactly what the host loop produces), or None when the request needs
    the host path (SHAP/leaf outputs, multi-process meshes, or
    RXGB_SPMD_PREDICT=0).
    """
    import jax

    if (
        not ENV.SPMD_PREDICT
        or not hasattr(model, "predict_margin_spmd")  # gblinear: host matmul
    ):
        return None
    special = None  # non-margin outputs ride their own SPMD kernels
    if predict_kwargs.get("pred_interactions"):
        special = "interactions"
        if predict_kwargs.get("approx_contribs"):
            import warnings

            # mirror the host path's signal that the flag is ignored
            warnings.warn(
                "approx_contribs=True is ignored with pred_interactions: "
                "only the exact interactions kernel is implemented."
            )
    elif predict_kwargs.get("pred_contribs"):
        special = ("contribs_approx" if predict_kwargs.get("approx_contribs")
                   else "contribs")
    elif predict_kwargs.get("pred_leaf"):
        special = "leaf"
    if jax.process_count() > 1:
        if special:
            return None  # host loop: special outputs are single-process SPMD
        # multi-process world: the full global mesh participates; this
        # process's shards are its local rows (same contract as training).
        devices = list(jax.devices())
        if len(devices) % jax.process_count():
            return None  # host loop fallback on ragged worlds
    else:
        devices = _resolve_mesh_devices(max(len(shards), 1), ray_params)
        if len(devices) > len(shards) > 0:
            devices = devices[: len(shards)]
        if len(devices) <= 1 and len(shards) <= 1:
            return None

    xs = [model._coerce_features(sh["data"]) for sh in shards]
    sizes = [xv.shape[0] for xv in xs]
    x_all = np.concatenate(xs, axis=0) if len(xs) > 1 else xs[0]

    base_margin = None
    if bm_shards is not None:
        base_margin = np.concatenate(
            [np.asarray(b, np.float32).reshape(sz, -1)
             for b, sz in zip(bm_shards, sizes)],
            axis=0,
        )
    elif any(sh.get("base_margin") is not None for sh in shards):
        base_margin = np.concatenate(
            [np.asarray(sh["base_margin"], np.float32).reshape(sz, -1)
             for sh, sz in zip(shards, sizes)],
            axis=0,
        )

    booster = model
    iteration_range = predict_kwargs.get("iteration_range")
    if iteration_range is not None and iteration_range != (0, 0):
        booster = model.slice_rounds(iteration_range[0], iteration_range[1])
    bounds = np.cumsum(sizes)[:-1]
    if special:
        res = booster.predict_special_spmd(
            x_all, devices, special,
            ntree_limit=int(predict_kwargs.get("ntree_limit", 0) or 0),
            base_margin=base_margin,
        )
        return np.split(res, bounds, axis=0)
    margin = booster.predict_margin_spmd(
        x_all, devices,
        ntree_limit=int(predict_kwargs.get("ntree_limit", 0) or 0),
        base_margin=base_margin,
    )
    pred = booster._margin_to_prediction(
        margin, bool(predict_kwargs.get("output_margin"))
    )
    return np.split(pred, bounds, axis=0)


def predict(
    model: RayXGBoostBooster,
    data: RayDMatrix,
    ray_params: Union[None, RayParams, Dict] = None,
    _remote: Optional[bool] = None,
    **kwargs,
) -> Optional[np.ndarray]:
    """Distributed prediction (signature mirror of ``main.py:1810``)."""
    ray_params = _validate_ray_params(ray_params)
    if _remote:
        return _run_remote("predict", (model, data, ray_params, kwargs))
    if not isinstance(data, RayDMatrix):
        raise ValueError(
            f"The `data` argument passed to `predict()` is not a RayDMatrix, "
            f"but of type {type(data)}. FIX THIS by instantiating a "
            f"RayDMatrix first: `data = RayDMatrix(data)`."
        )
    if getattr(data, "streamed", False):
        raise NotImplementedError(
            "predict() over a streamed matrix is not supported: the tree "
            "walk needs raw feature values (thresholds), which a streamed "
            "load never materializes. Streamed ingestion is a training-side "
            "memory optimization — predict from a materialized RayDMatrix "
            "(or the serve/ layer)."
        )
    model = _coerce_model(model)
    max_actor_restarts = (
        ray_params.max_actor_restarts
        if ray_params.max_actor_restarts >= 0
        else float("inf")
    )
    data.load_data(ray_params.num_actors)
    tries = 0
    while tries <= max_actor_restarts:
        try:
            return _predict(model, data, ray_params, **kwargs)
        except (RayActorError, RayTaskError):
            if tries + 1 <= max_actor_restarts:
                logger.warning(
                    "[RayXGBoost] A worker died during prediction. Trying "
                    "again with new workers."
                )
                tries += 1
            else:
                raise RayXGBoostTrainingError(
                    "A worker died during prediction and the maximum number "
                    "of retries is exhausted."
                )
    return None

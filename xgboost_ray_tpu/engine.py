"""The tpu_hist training engine: one JAX program over a device mesh.

This is the TPU-native inversion of the reference's architecture (SURVEY §7.1):
where xgboost_ray runs N OS-process actors each wrapping the xgboost C++ core
and glues them with a Rabit TCP allreduce (``xgboost_ray/main.py:543-815``,
``compat/tracker.py``), here the N "actors" are slots of a
``jax.sharding.Mesh`` axis (named by ``constants.AXIS_ACTORS``) and the
per-round histogram allreduce is ``lax.psum(hist, AXIS_ACTORS)`` inside a
shard_map-ed, jit-compiled round step.
There is no tracker, no rendezvous protocol, no sockets: XLA compiles the
collective onto ICI.

Responsibilities (mapping to reference components):
  * shard rows onto the mesh with padding + validity mask
                       <- per-actor shard dicts (``RayXGBoostActor.load_data``)
  * distributed quantile sketch + device binning (psum-merged)
                       <- xgboost C++ sketch inside ``xgb.DMatrix``
  * jitted round step: grad/hess -> K*T trees -> margin updates -> metrics
                       <- ``xgb.train`` hot loop + Rabit allreduce
  * warm start from a prior forest; forest export to RayXGBoostBooster
                       <- ``xgb_model`` kwarg / checkpoint resume

The driver retry/checkpoint/elastic loop lives in ``main.py`` — mirroring the
reference's split between actor hot loop and driver control flow.
"""

import contextlib
import dataclasses
import logging
import os
import time
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from xgboost_ray_tpu import obs
from xgboost_ray_tpu import progreg
from xgboost_ray_tpu.constants import (
    AXIS_ACTORS,
    AXIS_FEATURES,
    SHARD_COLUMN_FILLS,
)
from xgboost_ray_tpu.models.booster import RayXGBoostBooster, stack_trees
from xgboost_ray_tpu.ops import binning
from xgboost_ray_tpu.ops.histogram import (
    LOSSGUIDE_STATS,
    MESH_STATS,
    AllreduceBytes,
    counting_psum,
    default_hist_impl,
    quantized_hist_allreduce,
)
from xgboost_ray_tpu.ops.grow import (
    SALT_BYTREE,
    SALT_GOSS,
    SALT_SR,
    SALT_SUBSAMPLE,
    GrowConfig,
    Tree,
    build_tree,
    map_tree,
    predict_tree_binned,
    predict_tree_binned_fsharded,
    sample_feature_mask,
)
from xgboost_ray_tpu.ops.feature_shard import FeatureShard
from xgboost_ray_tpu.ops import sampling
from xgboost_ray_tpu.ops.metrics import (
    compute_metric,
    device_metric_contrib,
    is_device_metric,
    parse_metric_name,
)
from xgboost_ray_tpu.ops.objectives import (
    CustomObjective,
    get_objective,
    gh_plane_itemsize,
    quantize_gh,
)
from xgboost_ray_tpu.ops.ranking import RankingObjective, build_group_rows
from xgboost_ray_tpu.ops import predict as predict_ops
from xgboost_ray_tpu.ops.split import SplitParams
from xgboost_ray_tpu.params import LaneParams, TrainParams

logger = logging.getLogger(__name__)


def resolve_hist_precision(precision: str) -> str:
    """"auto": f32-exact sums on CPU (parity tests), single-pass bf16 on
    accelerators. Measured on TPU v5e (1M x 28 x 256, 16 rounds): "fast"
    shifts final logloss by ~1e-5 and saves 8-12% per histogram build
    (the builds are DMA/step-bound, not MXU-pass-bound, so the saving is
    modest — but never costs accuracy beyond bf16 rounding of gh)."""
    if precision != "auto":
        return precision
    return "highest" if jax.default_backend() == "cpu" else "fast"


@contextlib.contextmanager
def strict_transfer_guard(active: bool = True):
    """Runtime counterpart of rxgblint's SYNC001: under ``RXGB_STRICT=1``,
    steady-state round dispatch runs inside ``jax.transfer_guard("disallow")``
    so ANY hidden implicit host<->device sync (a stray ``.item()``/
    ``float()``/``np.asarray`` smuggled into a round closure) raises instead
    of silently serializing the pipeline.

    The documented host-sync boundaries stay out of scope by construction:
    the guard wraps ONLY the compiled-program dispatch, not the metric
    scalar reads / forest flushes that follow it, and callers pass
    ``active=False`` for a program's first (compiling) dispatch — trace-time
    closure-constant uploads are a legitimate one-off transfer.
    """
    if active and os.environ.get("RXGB_STRICT") == "1":
        with jax.transfer_guard("disallow"):
            yield
    else:
        yield


class _EvalSet:
    """Device-side state for one entry of ``evals`` (binned with train cuts)."""

    def __init__(self, name: str, n_rows: int, group_ptr: Optional[np.ndarray], is_train: bool):
        self.name = name
        self.n_rows = n_rows
        self.group_ptr = group_ptr
        self.is_train = is_train
        self.local_rows = n_rows  # multi-host: set to this process's rows
        self.lower_np = None
        self.upper_np = None
        self.margins_static = None
        # set by engine when not aliased to the train set:
        self.bins = None
        self.label = None
        self.weight = None
        self.valid = None
        self.margins = None
        self.label_np = None
        self.weight_np = None
        self.group_rows_dev = None  # sharded [NG, G] layout for device ndcg/map
        self.bounds_dev = None  # (lower, upper) device rows for device aft-nloglik


class _EvalArrs(NamedTuple):
    """Device arrays of one non-train eval set, as passed into the sharded
    step programs. Optional members hold scalar placeholders (P() specs) when
    absent so the pytree structure is static."""

    bins: Any
    label: Any
    weight: Any
    valid: Any
    margins: Any
    group_rows: Any  # [NG, G] or scalar placeholder
    margins_static: Any  # dart only; scalar placeholder otherwise
    bounds: Any  # (lower, upper) rows or scalar placeholder (survival only)


class TpuEngine:
    def __init__(
        self,
        shards: Sequence[Dict[str, Optional[np.ndarray]]],
        params: TrainParams,
        num_actors: int,
        evals: Sequence[Tuple[Sequence[Dict[str, Optional[np.ndarray]]], str]] = (),
        devices: Optional[Sequence[Any]] = None,
        init_booster: Optional[RayXGBoostBooster] = None,
        feature_names: Optional[List[str]] = None,
        total_rounds: Optional[int] = None,
        feature_weights: Optional[Any] = None,
        feature_types: Optional[List[str]] = None,
        categories: Optional[Dict[int, tuple]] = None,
        stream_donor: Optional["TpuEngine"] = None,
    ):
        # ``stream_donor``: a prior streamed engine of the SAME training run
        # (the elastic driver passes the engine being swapped out). When this
        # load's shard streams overlap the donor's, the new world is seeded
        # from the donor's retained binned rows and frozen cuts — zero
        # re-sketch, zero re-stream of surviving shards (stream/ingest.py's
        # reuse passes). Ignored for materialized loads and incompatible
        # donors (the full pipeline runs instead).
        self.params = params
        self.feature_names = feature_names
        # NOTE on placement: in this SPMD runtime the mesh IS the placement —
        # every actor rank is a physical device slot, so the reference's
        # PACK/SPREAD placement-group strategies reduce to rank NUMBERING.
        # The mesh must stay process-contiguous (the multi-host global row
        # layout and prediction reassembly assume it); real placement
        # decisions live where they have effect: tuner trials run on disjoint
        # contiguous device slices (tuner.py), and get_tune_resources()
        # exports the strategy hint for schedulers above.
        devices = list(devices if devices is not None else jax.devices())
        self.feature_parallel = int(getattr(params, "feature_parallel", 1))
        if self.feature_parallel > 1:
            # 2D row x feature mesh: rows shard over AXIS_ACTORS (R =
            # num_actors slots, the "world"), histogram feature columns over
            # AXIS_FEATURES (C = feature_parallel). C=1 keeps the 1D branch
            # below and traces the exact legacy program.
            if jax.process_count() > 1:
                raise NotImplementedError(
                    "feature_parallel > 1 is single-process only for now "
                    "(the multi-host global row layout assumes the 1D row "
                    "mesh)."
                )
            need = num_actors * self.feature_parallel
            if len(devices) < need:
                raise ValueError(
                    f"feature_parallel={self.feature_parallel} needs "
                    f"num_actors x C = {need} devices; only {len(devices)} "
                    f"available."
                )
            self.n_devices = max(1, num_actors)
            self.mesh = Mesh(
                np.array(devices[:need]).reshape(
                    self.n_devices, self.feature_parallel
                ),
                (AXIS_ACTORS, AXIS_FEATURES),
            )
        else:
            self.n_devices = max(1, min(num_actors, len(devices)))
            if self.n_devices < num_actors:
                logger.info(
                    "num_actors=%d > %d available devices; folding shards onto the mesh.",
                    num_actors,
                    len(devices),
                )
            self.mesh = Mesh(np.array(devices[: self.n_devices]), (AXIS_ACTORS,))
        self.num_actors = num_actors

        self.objective = (
            params.objective
            if isinstance(params.objective, (CustomObjective,))
            else get_objective(
                params.objective,
                params.num_class,
                params.scale_pos_weight,
                tweedie_variance_power=params.tweedie_variance_power,
                aft_loss_distribution=params.aft_loss_distribution,
                aft_loss_distribution_scale=params.aft_loss_distribution_scale,
                huber_slope=params.huber_slope,
                quantile_alpha=params.quantile_alpha,
            )
        )
        self.is_ranking = isinstance(self.objective, RankingObjective)
        from xgboost_ray_tpu.ops.survival import SurvivalObjective

        self.is_survival = isinstance(self.objective, SurvivalObjective)
        if (
            params.gh_precision != "float32"
            and isinstance(self.objective, CustomObjective)
        ):
            # the user's obj callback hands over f32 g/h it computed itself;
            # stochastic-rounding those behind its back would silently train
            # a different objective than the one supplied
            raise NotImplementedError(
                "gh_precision (quantized-gradient training) is not "
                "supported with a custom objective; set "
                "gh_precision='float32' or use a built-in objective."
            )
        self.n_outputs = self.objective.num_outputs
        base_score = (
            params.base_score
            if params.base_score is not None
            else self.objective.default_base_score
        )
        self.base_score = float(base_score)
        self.base_margin0 = float(self.objective.base_score_to_margin(self.base_score))

        # categorical features: bins are category codes, splits one-vs-rest
        from xgboost_ray_tpu.params import cat_feature_indices

        self.feature_types = feature_types
        self.categories = categories
        self._cat_features: tuple = cat_feature_indices(feature_types)

        self.cfg = GrowConfig(
            max_depth=params.max_depth,
            max_bin=params.max_bin,
            split=SplitParams(
                reg_lambda=params.reg_lambda,
                reg_alpha=params.reg_alpha,
                gamma=params.gamma,
                min_child_weight=params.min_child_weight,
                learning_rate=params.learning_rate,
                max_delta_step=params.max_delta_step,
            ),
            hist_impl=(
                default_hist_impl() if params.hist_impl == "auto"
                else params.hist_impl
            ),
            hist_precision=resolve_hist_precision(params.hist_precision),
            hist_quant=params.hist_quant,
            hist_quant_min_bytes=params.hist_quant_min_bytes,
            hist_quant_block=params.hist_quant_block,
            gh_precision=params.gh_precision,
            hist_chunk=params.hist_chunk,
            sibling_subtract=params.sibling_subtract,
            cat_features=self._cat_features,
            grow_policy=params.grow_policy,
            # leaf budget: under a depth bound 0 means "what the depth
            # allows" and a budget beyond 2^max_depth is unreachable, so cap
            # it (keeps the grower's tables minimal); max_depth=0 is no
            # depth bound and the budget stands as given (params.py
            # requires one)
            max_leaves=(
                0 if params.grow_policy != "lossguide"
                else params.max_leaves if params.max_depth == 0
                else min(params.max_leaves or (1 << params.max_depth),
                         1 << params.max_depth)
            ),
        )

        # metrics (device/host split happens after eval sets exist — ndcg/map
        # are device metrics only when every eval set has a group layout)
        names = list(params.eval_metric) or [self.objective.default_metric]
        self.metric_names = names

        # ---- streamed ingestion detection --------------------------------
        # A streamed shard carries {"stream": ShardStream} instead of a raw
        # array. Streams that fit in ONE chunk materialize here and take the
        # standard path below — the engine then traces the EXACT
        # pre-streaming programs, which is the bitwise-parity contract for
        # small streamed loads (the PR 4/PR 10 default-traces-the-old-
        # program discipline).
        from xgboost_ray_tpu.stream import reader as stream_reader

        streams = stream_reader.shard_streams(shards)
        if streams is not None and all(s.n_chunks <= 1 for s in streams):
            materialized = [
                stream_reader.materialize_shard(sh) for sh in shards
            ]
            # eval entries aliasing the train shard list must keep aliasing
            # the materialized one (the is-identity drives the train-set
            # eval fast path); single-chunk streamed eval sets degrade too
            evals = [
                (
                    materialized if eval_shards is shards
                    else self._materialize_if_single_chunk(eval_shards),
                    name,
                )
                for eval_shards, name in evals
            ]
            shards = materialized
            streams = None
        self._streamed = streams is not None
        self._stream_stats: Optional[Dict[str, Any]] = None
        self._stream_cuts_np: Optional[np.ndarray] = None
        if self._streamed:
            from xgboost_ray_tpu.params import validate_streaming_params

            validate_streaming_params(params)
            if jax.process_count() > 1:
                raise NotImplementedError(
                    "streamed ingestion is single-process only for now: the "
                    "multi-host global row layout needs per-process chunk "
                    "streams. Materialize the matrix on multi-host worlds."
                )

        # ---- host data assembly ------------------------------------------
        if self._streamed:
            from xgboost_ray_tpu.stream import ingest as stream_ingest

            # elastic continuation: when a donor engine already holds (a
            # superset of) these shards binned, skip the sketch pipeline
            # entirely — the donor's frozen cuts + binned rows seed this
            # world (shrink keeps every survivor shard; a grow-back onto a
            # NEW replacement actor re-streams only that one shard)
            self._stream_reuse_plan = stream_ingest.plan_stream_reuse(
                streams, stream_donor, max_bin=params.max_bin
            )
            # the FULL budget fail-fast before any byte streams: the
            # N-scaling block-buffer term needs only the declared row
            # counts, the mesh size, and the bin dtype — all known now
            # (the bin passes re-check with measured figures). The reuse
            # variant additionally guards the columns pass from reading a
            # byte of an over-budget re-streamed replacement shard.
            declared = sum(s.n_rows for s in streams)
            _, _, pre_pad_to = self._global_row_layout(declared)
            pre_block = pre_pad_to // self.n_devices
            pre_itemsize = np.dtype(binning.bin_dtype(params.max_bin)).itemsize
            if self._stream_reuse_plan is not None:
                stream_ingest.prevalidate_reuse_budget(
                    streams, self._stream_reuse_plan,
                    block_rows=pre_block,
                    bin_itemsize=pre_itemsize,
                )
                pass1 = stream_ingest.reuse_columns_pass(
                    streams, self._stream_reuse_plan, stream_donor,
                    params.max_bin, cat_features=self._cat_features,
                )
            else:
                stream_ingest.prevalidate_budget(
                    streams,
                    block_rows=pre_block,
                    bin_itemsize=pre_itemsize,
                    n_devices=self.n_devices,
                )
                pass1 = stream_ingest.sketch_pass(
                    streams, params.max_bin, cat_features=self._cat_features
                )
            x = None
            label = (
                pass1.label if pass1.label is not None
                else np.zeros(pass1.n_rows, np.float32)
            )
            weight, base_margin, qid = pass1.weight, pass1.base_margin, pass1.qid
            lo, hi = pass1.lower, pass1.upper
            self.n_rows = pass1.n_rows
            self.n_features = pass1.n_features
        else:
            x, label, weight, base_margin, qid, lo, hi = _concat_shards(shards)
            self.n_rows = x.shape[0]
            self.n_features = x.shape[1]
        if self.is_survival and lo is None and label is None:
            raise ValueError(
                "survival:aft requires label_lower_bound/label_upper_bound "
                "(or a plain label, interpreted as uncensored times)."
            )

        binning.validate_feature_types_count(self._cat_features, self.n_features)
        # streamed loads validate categorical codes per chunk in sketch_pass
        # via the same shared validator (the full column never materializes)
        if not self._streamed:
            binning.validate_categorical_codes(
                x, self._cat_features, params.max_bin
            )

        # monotone / interaction constraints: validated against the real
        # feature count, then attached to the (jit-static) grow config.
        # Reference surface: xgboost_ray/main.py:745-752 forwards both to
        # xgboost's hist updater untouched.
        if params.monotone_constraints or params.interaction_constraints:
            import dataclasses as _dc

            mono = tuple(int(c) for c in params.monotone_constraints)
            if len(mono) > self.n_features:
                raise ValueError(
                    f"monotone_constraints has {len(mono)} entries but the "
                    f"data has {self.n_features} features."
                )
            mono = mono + (0,) * (self.n_features - len(mono))
            for fi in self._cat_features:
                if mono and mono[fi] != 0:
                    raise ValueError(
                        f"monotone constraint on categorical feature {fi} is "
                        f"not supported (one-vs-rest category splits have no "
                        f"order to be monotone in)."
                    )
            ic = params.interaction_constraints
            bad = [i for grp in ic for i in grp if i >= self.n_features]
            if bad:
                raise ValueError(
                    f"interaction_constraints reference feature indices "
                    f"{sorted(set(bad))} but the data has "
                    f"{self.n_features} features."
                )
            self.cfg = _dc.replace(
                self.cfg,
                monotone_constraints=mono if any(mono) else (),
                interaction_constraints=ic,
            )

        # feature_weights bias the colsample_* draws (Gumbel-top-k weighted
        # sampling without replacement; xgboost set_info(feature_weights=...))
        self._log_fw = None
        if feature_weights is not None:
            fw = np.asarray(feature_weights, np.float32).ravel()
            if fw.shape[0] != self.n_features:
                raise ValueError(
                    f"feature_weights has {fw.shape[0]} entries but the data "
                    f"has {self.n_features} features."
                )
            if (fw < 0).any():
                raise ValueError("feature_weights must be non-negative.")
            if fw.sum() <= 0:
                raise ValueError("feature_weights must not be all zero.")
            with np.errstate(divide="ignore"):
                self._log_fw = jnp.asarray(np.log(fw))
        self.label_np = label if label is not None else lo
        self.weight_np = weight
        self.lower_np, self.upper_np = lo, hi
        self.group_ptr = (
            None if qid is None else build_group_rows(qid)[1]
        )
        if (
            getattr(self.objective, "name", "") == "reg:squaredlogerror"
            and label is not None
            and (np.asarray(label) <= -1).any()
        ):
            # xgboost rejects these at data load; clamping would silently
            # train on corrupted targets
            raise ValueError(
                "reg:squaredlogerror requires all labels > -1."
            )

        # Multi-host: `shards` holds only THIS process's ranks (in the order of
        # this process's devices within jax.devices()); row counts are
        # allgathered to agree on the global padded layout. Single-host this
        # degenerates to local == global.
        self._local_rows = self.n_rows
        self.n_rows, self._local_pad, pad_to = self._global_row_layout(
            self._local_rows
        )
        self._row_sharding = NamedSharding(self.mesh, P(AXIS_ACTORS))

        def put_rows(arr, dtype, fill=0):
            return self._upload_rows(arr, dtype, fill, self._local_pad)

        self._put_rows = put_rows
        self.pad_to = pad_to
        x_dev = None
        if not self._streamed:
            x_dev = put_rows(x, np.float32, fill=np.nan)
        self.valid = put_rows(np.ones(self._local_rows, bool), bool, fill=False)
        self.label_dev = put_rows(label, np.float32)
        self.weight_dev = put_rows(
            weight
            if weight is not None
            else np.ones(self._local_rows, np.float32),
            np.float32,
        )
        if self.is_survival:
            if lo is None:
                lo = label
            if hi is None:
                hi = lo
            self.lower_np, self.upper_np = lo, hi
            self.bounds_dev = (
                put_rows(lo, np.float32, fill=1.0),
                put_rows(hi, np.float32, fill=1.0),
            )
        else:
            self.bounds_dev = None

        # ---- distributed sketch + binning (device, psum-merged) ----------
        # Weight-aware: xgboost's quantile sketch weighs samples (hessian/user
        # weight), so cut points concentrate where the weighted mass is.
        # weight_dev is all-ones when the user passed no weights, which makes
        # the weighted sketch bit-identical to the unweighted one.
        self._stream_init_margins = None
        if self._streamed:
            if self._stream_reuse_plan is not None:
                # elastic continuation: FROZEN donor cuts (retained in
                # memory — bitwise the cuts every reused shard was binned
                # with, so the booster's split_bin routing stays valid) +
                # block assembly from the donor's device binned rows; only
                # a shard the donor never held re-streams, against these
                # same cuts. No sketch pass, no cuts merge.
                cuts_np = stream_donor._stream_cuts_np.copy()
                repl = NamedSharding(self.mesh, P())
                self.cuts = jax.device_put(cuts_np, repl)
                self._feat_has_missing = jax.device_put(
                    stream_donor._stream_fhm_np.copy(), repl
                )
                self._stream_cuts_np = cuts_np
                self.bins, up_stats = stream_ingest.reuse_bin_pass(
                    self, streams, self._stream_reuse_plan, stream_donor,
                    cuts_np,
                )
                self._stream_stats = {
                    "reused_from_donor": True,
                    "chunks": int(pass1.chunks),
                    "pass1_wall_s": round(pass1.wall_s, 4),
                }
            else:
                # streamed: two-pass host sketch -> device cuts merge (the
                # SAME pmin/pmax/psum collective schedule as the
                # materialized sketch program) -> chunked host binning with
                # double-buffered upload. Rows are born binned; the raw f32
                # matrix never exists.
                self.cuts, self._feat_has_missing, cuts_np, sk_err = (
                    stream_ingest.merged_cuts(self, pass1)
                )
                self._stream_cuts_np = cuts_np
                self.bins, up_stats = stream_ingest.bin_upload_pass(
                    self, streams, cuts_np,
                    sketch_bytes=sum(
                        sk.memory_bytes() for sk in pass1.sketches
                    ),
                )
                self._stream_stats = {
                    "chunks": int(pass1.chunks),
                    "sketch_s": round(pass1.sketch_s, 4),
                    "pass1_wall_s": round(pass1.wall_s, 4),
                    "rank_error_bound_max": float(sk_err.max(initial=0.0)),
                }
            for k, v in up_stats.items():
                self._stream_stats[k] = (
                    round(v, 4) if isinstance(v, float) else v
                )
            # elastic-continuation metadata: what a FUTURE shrink/grow needs
            # to seed its world from this engine (``plan_stream_reuse``) and
            # what ``reset_from_booster`` verifies stream identity against
            self._stream_fhm_np = np.asarray(self._feat_has_missing)
            self._stream_shard_fps = [s.fingerprint() for s in streams]
            self._stream_shard_rows = [s.n_rows for s in streams]
            self._stream_cols = {
                "label": pass1.label,
                "weight": pass1.weight,
                "base_margin": pass1.base_margin,
                "label_lower_bound": pass1.lower,
                "label_upper_bound": pass1.upper,
            }
            # warm start has no raw rows to walk: route the init forest over
            # the binned matrix on device, BEFORE any feature-axis sharding
            if init_booster is not None and init_booster.num_trees:
                self._stream_init_margins = self._init_margins_from_bins(
                    init_booster
                )
        else:
            # times trace / lower / compile (its compile.* children) and the
            # enqueue: nothing here waits for the program (``fenced: false``).
            # The first round program's lowering reads the cuts and so waits
            # for it; a profiler trace has its device seconds under the
            # ``sketch`` and ``bin`` scopes
            with obs.get_tracer().span("data.sketch_bin", fenced=False):
                self.bins, self.cuts, self._feat_has_missing = (
                    self._sketch_and_bin(x_dev, self.valid, self.weight_dev)
                )

        # ---- feature-axis sharding (feature_parallel > 1) ----------------
        # Sketch/binning ran at full F (one-off, row-parallel); the binned
        # matrix is then feature-padded to a C-multiple and laid out as
        # [N/R, F_pad/C] tiles. Pad columns bin entirely to the missing
        # bucket, so their split candidates score -inf and can never be
        # elected. cuts / feat_has_missing keep GLOBAL padded copies for the
        # growers (threshold recovery and routing use global feature ids).
        self._f_padded = self.n_features
        self._cuts_grow = self.cuts
        self._fhm_grow = self._feat_has_missing
        if self.feature_parallel > 1:
            c_shards = self.feature_parallel
            self._f_padded = -(-self.n_features // c_shards) * c_shards
            if self._f_padded * (self.params.max_bin - 1) >= (1 << 24):
                # the best-split election ships its flat candidate index as
                # f32 (exact integers below 2^24 only)
                raise NotImplementedError(
                    f"feature_parallel: padded F x (max_bin - 1) = "
                    f"{self._f_padded * (self.params.max_bin - 1)} exceeds "
                    f"the election record's exact-int f32 range (2^24); "
                    f"reduce max_bin or the feature count."
                )
            f_extra = self._f_padded - self.n_features
            if f_extra:
                self._cuts_grow = jnp.pad(self.cuts, ((0, f_extra), (0, 0)))
                # pad columns DO bin to the missing bucket; keeping the
                # flag True leaves their (all-missing) histogram honest
                self._fhm_grow = jnp.pad(
                    self._feat_has_missing, (0, f_extra),
                    constant_values=True,
                )
            if self.cfg.hist_quant != "none":
                # the quantize-vs-exact-f32 fallback (hist_quant_min_bytes)
                # must be decided on the GLOBAL payload, not the F/C local
                # tile — otherwise payloads in the window between the tile
                # size and the full-F size would quantize on (R, 1) but
                # fall back to exact f32 on (R, C), silently training a
                # different model per mesh shape. Scaling the threshold by
                # local/global keeps every decision site (the allreduce
                # fallback AND the growers' exact-node-totals mirrors,
                # which all compare LOCAL payload bytes against this cfg
                # field) exactly equivalent to the 1D decision.
                import dataclasses as _dc

                f_local = self._f_padded // c_shards
                self.cfg = _dc.replace(
                    self.cfg,
                    hist_quant_min_bytes=(
                        self.params.hist_quant_min_bytes
                        * f_local / max(self.n_features, 1)
                    ),
                )
            self.bins = self._feature_shard_bins(self.bins)

        # ---- ranking group structure (per device block) ------------------
        # built whenever qid exists (ranking gradients AND device ndcg/map
        # metrics use the same padded per-shard group layout)
        self.group_rows = (
            self._build_sharded_groups(qid) if qid is not None else None
        )
        if self.is_ranking and self.group_rows is None:
            raise ValueError(f"objective {self.objective.name!r} requires qid")

        # ---- margins ------------------------------------------------------
        margins_static = np.full(
            (self._local_rows, self.n_outputs), self.base_margin0, np.float32
        )
        if base_margin is not None:
            margins_static = margins_static + base_margin.reshape(
                self._local_rows, -1
            ).astype(np.float32)
        margins0 = margins_static
        self._init_trees: List[Tree] = []
        self._init_tree_weights: Optional[np.ndarray] = None
        # propagate the "was saved without per-node stats" marker through
        # continuation so pred_contribs keeps raising instead of silently
        # attributing zero to the init trees
        self._init_has_stats = (
            getattr(init_booster, "_has_node_stats", True)
            if init_booster is not None
            else True
        )
        if init_booster is not None and init_booster.num_trees:
            if not self._streamed:
                margins0 = margins0 + (
                    init_booster.predict_margin_np(x)
                    - init_booster.base_score_margin_np()
                )
            self._init_trees = [init_booster.forest]
            self._init_tree_weights = (
                init_booster.tree_weights
                if init_booster.tree_weights is not None
                else np.ones(init_booster.num_trees, np.float32)
            )
        self.margins = put_rows(margins0, np.float32)
        if self._stream_init_margins is not None:
            # streamed warm start: the device binned-walk contribution
            # (computed against this load's bins before feature sharding)
            self.margins = self.margins + self._stream_init_margins
            self._stream_init_margins = None
        self.dart = params.booster == "dart"
        if self.dart:
            self._margins_static_dev = put_rows(margins_static, np.float32)
            self._dart_total_rounds = int(total_rounds or 0)

        # ---- eval sets ----------------------------------------------------
        self.evals: List[_EvalSet] = []
        for eval_shards, name in evals:
            self._add_eval_set(eval_shards, name, x_id=id(shards), shards_obj=shards,
                               eval_obj=eval_shards, init_booster=init_booster)

        del x_dev  # raw features no longer needed on device

        has_groups = all(
            (self.group_rows is not None)
            if es.is_train
            else (es.group_rows_dev is not None)
            for es in self.evals
        )
        has_bounds = all(
            (self.bounds_dev is not None)
            if es.is_train
            else (es.bounds_dev is not None)
            for es in self.evals
        )
        self._device_metrics = [
            m for m in self.metric_names if is_device_metric(m, has_groups, has_bounds)
        ]
        self._host_metrics = [
            m
            for m in self.metric_names
            if not is_device_metric(m, has_groups, has_bounds)
        ]
        # Host metrics on multi-host meshes are computed per process on its
        # local rows and combined as a weight-/row-weighted mean across
        # processes — the reference's per-worker metric semantics (each actor
        # evaluates its shard, xgboost averages across workers). Exact for
        # per-row-mean metrics; an approximation for order-statistics like a
        # host-fallback AUC (use the device histogram-AUC for exactness).

        self.trees: List[Tree] = []  # host-side forest, one [K*T, heap] entry per round
        # per-round device forests pending host transfer: every host read
        # is a blocking device round trip, so the per-round step path defers
        # the (tiny) forest transfer and flushes in one batched stack per
        # checkpoint/get_booster instead of 9 reads per round
        self._trees_dev: List[Tuple[Tree, Optional[int]]] = []
        # incremental stacked-forest cache (amortized O(1) copies per tree;
        # re-stacking the whole forest per checkpoint interval was O(T^2))
        self._stack_entries = 0  # how many of (_init_trees + trees) are stacked
        self._stack_rows = 0  # filled tree rows in the buffers
        self._stack_buf: Optional[Tree] = None
        self._step_fn = None
        self._step_fn_custom = None
        self._scan_fn = None
        self._dart_fn = None
        # vmapped-K HPO state (enable_lanes): 0 means scalar mode — every
        # existing path traces the exact pre-lanes program
        self._vk = 0
        self._vk_spec_override = None
        # programs that have dispatched at least once: RXGB_STRICT's
        # transfer guard only arms for warm (non-compiling) dispatches
        self._warm_programs: set = set()
        # device-resident payload-byte counter of the latest round's tree
        # allreduces (materialized lazily — see hist_allreduce_bytes_per_round)
        self._ar_bytes_dev = None
        # the dispatches' mesh_stats outputs, summed on the device as they
        # come, and the rounds behind the sum; mesh_round_stats() reads it
        # once. None on a one-device world, whose programs have no such output
        self._mesh_stats_dev = None
        self._mesh_stats_rounds = 0
        self._mesh_stats_fold = None
        # static attributes attached to every "round" span: world size, row
        # counts, and (when sampling is on) the per-shard compacted budget —
        # the "sampling budgets become span attributes" half of the obs plane
        samp_spec = sampling.spec_from_params(params)
        self._obs_round_attrs = {
            "world": int(self.n_devices),
            "rows": int(self.n_rows),
        }
        if self.feature_parallel > 1:
            self._obs_round_attrs["feature_parallel"] = int(
                self.feature_parallel
            )
        if params.gh_precision != "float32":
            self._obs_round_attrs["gh_precision"] = params.gh_precision
        if self._streamed:
            self._obs_round_attrs["streamed"] = True
        if samp_spec is not None:
            self._obs_round_attrs["sample_rows_per_shard"] = int(
                sampling.row_budget(self.pad_to // self.n_devices, samp_spec)
            )
        if self.dart:
            self._init_dart_forest()
        self.iteration_offset = (
            init_booster.num_boosted_rounds() if init_booster is not None else 0
        )

    # ------------------------------------------------------------------
    def _upload_rows(self, arr, dtype, fill, local_pad: int):
        """Pad this process's local rows to ``local_pad`` and place them in
        the global row-sharded layout (multi-host: assembled without
        cross-host copies). The ``data.h2d`` span times the host's part —
        convert, pad, enqueue; the copy itself runs on (``fenced: false``)
        and is waited for by the first program that reads the rows."""
        from xgboost_ray_tpu.distributed import put_rows_global

        with obs.get_tracer().span("data.h2d", fenced=False) as span_attrs:
            arr = np.asarray(arr, dtype=dtype)
            if arr.shape[0] < local_pad:
                pad_width = [(0, local_pad - arr.shape[0])] + [(0, 0)] * (arr.ndim - 1)
                arr = np.pad(arr, pad_width, constant_values=fill)
            span_attrs["bytes"] = int(arr.nbytes)
            # this process's row blocks, one a mesh slot, all equal
            span_attrs["shards"] = self._local_row_blocks()
            span_attrs["shard_bytes"] = (
                int(arr.nbytes) // span_attrs["shards"]
            )
            return put_rows_global(arr, self._row_sharding)

    def _global_row_layout(self, local_n: int):
        """(global_n, local_pad, pad_to) for the row-sharded device layout.

        Multi-host, row counts are allgathered so every process agrees on the
        global padded extent; each process places exactly ``local_pad`` rows
        (its ranks' rows + tail padding) via put_rows_global.
        """
        pc = jax.process_count()
        if pc == 1:
            pad_to = -(-max(local_n, self.n_devices) // self.n_devices) * self.n_devices
            return local_n, pad_to, pad_to
        from jax.experimental import multihost_utils

        counts = np.asarray(
            multihost_utils.process_allgather(np.int64(local_n))
        ).ravel()
        global_n = int(counts.sum())
        if self.n_devices % pc:
            raise ValueError(
                f"{self.n_devices} mesh devices do not divide evenly over "
                f"{pc} processes."
            )
        per_proc_devices = self.n_devices // pc
        block = -(-max(global_n, self.n_devices) // self.n_devices)
        # every process must fit its rows in its devices' blocks
        block = max(block, int(-(-counts.max() // per_proc_devices)))
        pad_to = block * self.n_devices
        local_pad = block * per_proc_devices
        return global_n, local_pad, pad_to

    def _fetch_rows(self, arr, valid, n_real: int) -> np.ndarray:
        """Device row-sharded array -> host array of the real data rows.

        Single-host: plain transfer + tail-padding slice. Multi-host: the
        array spans non-addressable devices, so it is allgathered first and
        per-process tail padding dropped via the valid mask.
        """
        if getattr(arr, "is_fully_addressable", True):
            return np.asarray(arr)[:n_real]
        from jax.experimental import multihost_utils

        full = np.asarray(multihost_utils.process_allgather(arr, tiled=True))
        mask = np.asarray(
            multihost_utils.process_allgather(valid, tiled=True)
        ).astype(bool)
        return full[mask]

    # ------------------------------------------------------------------
    def _sketch_and_bin(self, x_dev, valid, weight_dev):
        max_bin = self.params.max_bin
        cat_features = self._cat_features

        def sketch(x, v, w):
            mn, mx = binning.feature_min_max(x, v)
            mn = jax.lax.pmin(mn, AXIS_ACTORS)
            mx = jax.lax.pmax(mx, AXIS_ACTORS)
            hist = binning.sketch_histogram(x, v, mn, mx, weight=w)
            hist = jax.lax.psum(hist, AXIS_ACTORS)
            cuts = binning.cuts_from_sketch(mn, mx, hist, max_bin)
            if cat_features:
                # categorical columns: cut k sits at k + 0.5, so the bin index
                # IS the category code and one-vs-rest split search applies
                from xgboost_ray_tpu.ops.grow import cat_mask_const

                cat_mask = cat_mask_const(cat_features, x.shape[1])
                code_cuts = jnp.arange(max_bin - 1, dtype=cuts.dtype) + 0.5
                cuts = jnp.where(cat_mask[:, None], code_cuts[None, :], cuts)
            return cuts

        def bin_rows(x, v, cuts):
            bins = binning.bin_matrix(x, cuts, max_bin)
            # global per-feature "has any missing value" mask (padding rows
            # are excluded — they bin to the missing bucket by construction):
            # lets the tree builder zero phantom missing mass that the
            # subtraction-reconstructed bucket picks up under fast precision
            miss_cnt = jnp.sum(
                ((bins == max_bin) & v[:, None]).astype(jnp.float32), axis=0
            )
            has_missing = jax.lax.psum(miss_cnt, AXIS_ACTORS) > 0
            return bins, cuts, has_missing

        def fn(x, v, w):
            with jax.named_scope("sketch"):
                cuts = sketch(x, v, w)
            with jax.named_scope("bin"):
                return bin_rows(x, v, cuts)

        mapped = jax.shard_map(
            fn,
            mesh=self.mesh,
            in_specs=(P(AXIS_ACTORS), P(AXIS_ACTORS), P(AXIS_ACTORS)),
            out_specs=(P(AXIS_ACTORS), P(), P()),
            check_vma=False,
        )
        jit_fn = progreg.register_jit(
            "engine.sketch_cuts",
            mapped,
            example_args=(x_dev, valid, weight_dev),
            meta=self._program_meta(),
        )
        bins, cuts, has_missing = jit_fn(x_dev, valid, weight_dev)
        return bins, cuts, has_missing

    @staticmethod
    def _materialize_if_single_chunk(shard_list):
        """Degrade a single-chunk streamed shard list to materialized
        fields (mirrors the train-set degrade); multi-chunk lists pass
        through untouched (and hit the streamed-eval gate downstream)."""
        from xgboost_ray_tpu.stream import reader as stream_reader

        st = stream_reader.shard_streams(shard_list)
        if st is not None and all(s.n_chunks <= 1 for s in st):
            return [stream_reader.materialize_shard(sh) for sh in shard_list]
        return shard_list

    def _init_margins_from_bins(
        self, init_booster, fsharded: bool = False
    ) -> jnp.ndarray:
        """Warm-start margin contribution of ``init_booster`` over a
        STREAMED load: walk the init forest against the binned device matrix
        (raw features never exist), routing on ``split_bin``.

        split_bin routing is only valid against the cuts the forest was
        grown with. Streamed cuts are deterministic in (data, chunking,
        world) — and FROZEN through elastic shrink/grow — so continuation
        and restart on retained cuts always match bitwise; any cut drift is
        gated loudly instead of silently mis-routing every split.

        ``fsharded=True`` walks ``self.bins`` in its 2D ``[N/R, F_pad/C]``
        tile layout (the ``reset_from_booster`` entry point, where the
        feature sharding already happened) via the fsharded walk's
        owner-broadcast bin columns; at ``__init__`` time the walk runs
        pre-sharding over the full-F row layout.
        """
        booster_cuts = np.asarray(init_booster.cuts, np.float32)
        my_cuts = self._stream_cuts_np
        if booster_cuts.shape != my_cuts.shape or not np.array_equal(
            booster_cuts, my_cuts
        ):
            raise NotImplementedError(
                "streamed warm start requires the checkpoint booster's "
                "sketch cuts to equal this load's (same data, same "
                "chunking, same world): re-binned rows cannot ride the "
                "forest's split_bin routing across cut drift. Materialize "
                "the matrix to warm start across worlds/cut changes."
            )
        forest = init_booster.forest
        weights = (
            init_booster.tree_weights
            if init_booster.tree_weights is not None
            else np.ones(forest.feature.shape[0], np.float32)
        )
        t_cap = forest.feature.shape[0]
        k_out = self.n_outputs
        tp = max(1, int(getattr(init_booster.params, "num_parallel_tree", 1)))
        depth = int(init_booster.max_depth)
        missing_bin = self.params.max_bin
        cats = self.cfg.cat_features
        forest_dev = map_tree(jnp.asarray, forest)
        w_dev = jnp.asarray(np.asarray(weights, np.float32))
        # round-major tree layout: tree t -> class (t // tp) % K (the
        # predict_ops.predict_margin mapping)
        cls_onehot = jax.nn.one_hot(
            (jnp.arange(t_cap) // tp) % k_out, k_out, dtype=jnp.float32
        )

        fshard = None
        if fsharded:
            fshard = FeatureShard(
                AXIS_FEATURES, self.feature_parallel, self._f_padded,
                self.n_features,
            )

        def fn(bins):
            def walk(tr):
                if fshard is None:
                    return predict_tree_binned(
                        tr, bins, depth, missing_bin, cat_features=cats
                    )
                return predict_tree_binned_fsharded(
                    tr, bins, depth, missing_bin, fshard, cat_features=cats
                )

            leaf = jax.vmap(walk)(forest_dev)  # [T, S]
            return jnp.einsum(
                "ts,tk->sk", leaf * w_dev[:, None], cls_onehot
            ) / tp

        mapped = jax.shard_map(
            fn,
            mesh=self.mesh,
            in_specs=(
                P(AXIS_ACTORS, AXIS_FEATURES) if fsharded else P(AXIS_ACTORS),
            ),
            out_specs=P(AXIS_ACTORS),
            check_vma=False,
        )
        jit_fn = progreg.register_jit(
            "stream.init_margins",
            mapped,
            example_args=(self.bins,),
            meta=self._program_meta(),
        )
        return jit_fn(self.bins)

    def _bin_with_cuts(self, x_dev):
        max_bin = self.params.max_bin
        jit_fn = progreg.register_jit(
            "engine.bin_matrix",
            lambda x, c: binning.bin_matrix(x, c, max_bin),
            example_args=(x_dev, self.cuts),
            meta=self._program_meta(),
        )
        return jit_fn(x_dev, self.cuts)

    def _feature_shard_bins(self, bins):
        """Feature-pad a [N, F] binned matrix to ``_f_padded`` columns
        (missing bucket) and lay it out over the 2D mesh as
        [N/R, F_pad/C] tiles."""
        f_extra = self._f_padded - bins.shape[1]
        if f_extra:
            bins = jnp.pad(
                bins, ((0, 0), (0, f_extra)),
                constant_values=np.asarray(
                    self.params.max_bin, bins.dtype
                ),
            )
        return jax.device_put(
            bins, NamedSharding(self.mesh, P(AXIS_ACTORS, AXIS_FEATURES))
        )

    def _bins_spec(self):
        """PartitionSpec of every binned matrix (train + eval sets)."""
        if self.feature_parallel > 1:
            return P(AXIS_ACTORS, AXIS_FEATURES)
        return P(AXIS_ACTORS)

    def _build_sharded_groups(self, qid, n_rows=None, pad_to=None):
        """Per-device-block padded group gather maps, stacked + sharded.

        Multi-host: ``qid`` holds only this process's rows, so each process
        builds the gather maps for its own devices' blocks; the padded
        (n_groups, group_size) extents are allgathered so every process
        materializes the same global array shape, then the per-process slabs
        are assembled without cross-host copies via ``put_rows_global``.
        """
        n_rows = self._local_rows if n_rows is None else n_rows
        pad_to = self.pad_to if pad_to is None else pad_to
        if qid is None:
            raise ValueError(f"objective {self.objective.name!r} requires qid")
        pc = jax.process_count()
        block = pad_to // self.n_devices
        local_devices = self.n_devices // pc
        per_dev = []
        for d in range(local_devices):
            lo, hi = d * block, min((d + 1) * block, n_rows)
            if hi <= lo:
                per_dev.append(None)
                continue
            rows, _ = build_group_rows(qid[lo:hi])
            per_dev.append(rows)
        ng = max([r.shape[0] for r in per_dev if r is not None] or [1])
        gsz = max([r.shape[1] for r in per_dev if r is not None] or [1])
        if pc > 1:
            from jax.experimental import multihost_utils

            dims = np.asarray(
                multihost_utils.process_allgather(
                    np.array([ng, gsz], np.int64)
                )
            ).reshape(-1, 2)
            ng, gsz = int(dims[:, 0].max()), int(dims[:, 1].max())
        stacked = np.full((local_devices, ng, gsz), block, np.int32)
        for d, rows in enumerate(per_dev):
            if rows is None:
                continue
            lo = d * block
            hi = min(lo + block, n_rows)
            # sentinel inside build_group_rows is the local segment length
            # (== hi-lo); remap it to `block`, the padded gather slot every
            # shard treats as invalid
            r = np.where(rows == hi - lo, block, rows)
            stacked[d, : rows.shape[0], : rows.shape[1]] = r
        flat = stacked.reshape(local_devices * ng, gsz)

        from xgboost_ray_tpu.distributed import put_rows_global

        return put_rows_global(flat, self._row_sharding)

    def _add_eval_set(self, eval_shards, name, x_id, shards_obj, eval_obj, init_booster):
        is_train = eval_obj is shards_obj
        if is_train:
            es = _EvalSet(name, self.n_rows, self.group_ptr, True)
            es.label_np = self.label_np
            es.weight_np = self.weight_np
            es.lower_np = getattr(self, "lower_np", None)
            es.upper_np = getattr(self, "upper_np", None)
            self.evals.append(es)
            return
        from xgboost_ray_tpu.stream.reader import is_streamed_shards

        # a single-chunk streamed eval set degrades to materialized fields
        # regardless of how the TRAIN set arrived (the same contract as the
        # train-side single-chunk degrade); only genuinely multi-chunk
        # streams hit the gate
        eval_shards = self._materialize_if_single_chunk(eval_shards)
        if is_streamed_shards(eval_shards):
            raise NotImplementedError(
                f"eval set {name!r} is a streamed matrix: streamed "
                f"ingestion is train-set only (eval margins need per-round "
                f"device residency anyway). Materialize eval sets, or "
                f"evaluate on the train set."
            )
        x, label, weight, base_margin, qid, lo, hi = _concat_shards(eval_shards)
        local_rows = x.shape[0]
        n_global, local_pad, pad_to = self._global_row_layout(local_rows)
        es = _EvalSet(
            name,
            n_global,
            None if qid is None else build_group_rows(qid)[1],
            False,
        )
        es.local_rows = local_rows

        def put_rows(arr, dtype, fill=0):
            return self._upload_rows(arr, dtype, fill, local_pad)

        x_dev = put_rows(x, np.float32, fill=np.nan)
        es.bins = self._bin_with_cuts(x_dev)
        if self.feature_parallel > 1:
            es.bins = self._feature_shard_bins(es.bins)
        if qid is not None:
            es.group_rows_dev = self._build_sharded_groups(
                qid, n_rows=x.shape[0], pad_to=pad_to
            )
        es.valid = put_rows(np.ones(x.shape[0], bool), bool, fill=False)
        es.label = put_rows(label, np.float32)
        es.weight = put_rows(
            weight if weight is not None else np.ones(x.shape[0], np.float32), np.float32
        )
        es.label_np = label if label is not None else lo
        es.weight_np = weight
        es.lower_np = lo if lo is not None else label
        es.upper_np = hi if hi is not None else es.lower_np
        if self.is_survival and es.lower_np is not None:
            es.bounds_dev = (
                put_rows(es.lower_np, np.float32, fill=1.0),
                put_rows(es.upper_np, np.float32, fill=1.0),
            )
        margins_static = np.full(
            (x.shape[0], self.n_outputs), self.base_margin0, np.float32
        )
        if base_margin is not None:
            margins_static = margins_static + base_margin.reshape(
                x.shape[0], -1
            ).astype(np.float32)
        margins0 = margins_static
        if init_booster is not None and init_booster.num_trees:
            margins0 = margins0 + (
                init_booster.predict_margin_np(x) - init_booster.base_score_margin_np()
            )
        es.margins = put_rows(margins0, np.float32)
        if getattr(self, "dart", False):
            es.margins_static = put_rows(margins_static, np.float32)
        del x_dev
        self.evals.append(es)

    # ------------------------------------------------------------------
    def _round_closures(self, update_evals: bool = True):
        """The shared traced round body used by the per-round step, the
        lax.scan multi-round path, and the dart step — one definition so
        sampling/tree semantics cannot diverge between compiled programs.
        ``update_evals=False`` skips incremental eval-margin updates (dart
        recomputes margins from tree weights instead)."""
        cfg = self.cfg
        params = self.params
        k_out = self.n_outputs
        t_par = params.num_parallel_tree
        obj = self.objective
        is_ranking = self.is_ranking
        missing_bin = params.max_bin
        dev_metrics = list(self._device_metrics)
        n_evals_dev = (
            sum(1 for e in self.evals if not e.is_train) if update_evals else 0
        )
        psum = lambda x: jax.lax.psum(x, AXIS_ACTORS)
        n_actors = self.n_devices

        is_survival = self.is_survival

        # feature-parallel context (trace-time constants; fp_c == 1 takes
        # every legacy branch below, tracing the exact 1D program)
        fp_c = self.feature_parallel
        n_feat_real = self.n_features
        f_padded = self._f_padded
        cuts_grow = self._cuts_grow
        fhm_grow = self._fhm_grow

        # row sampling (ops/sampling.py): None when off — the None path
        # traces the exact pre-sampling program, so default params stay
        # bit-identical to builds that predate the compaction machinery
        samp_spec = sampling.spec_from_params(params)
        if samp_spec is None and \
                getattr(self, "_vk_spec_override", None) is not None:
            # vmapped-K where max(lane subsample) == 1.0 but some lane
            # samples: the base params alone say "sampling off", yet the
            # lanes need the budget-mask machinery — trace the full-budget
            # uniform spec and let per-lane budgets cut it down
            samp_spec = self._vk_spec_override

        # quantize_gh's int32-overflow bound: the global padded row count
        # (trace-time constant; padding rows carry exactly-zero gh but the
        # bound stays safe either way)
        gh_max_rows = int(self.pad_to)

        def tree_round(bins, valid, label, weight, margins, group_rows, gh_in,
                       rng, bounds, eval_bins, eval_margins, lane=None):
            """One boosting round; gh_in is None unless a custom objective
            supplied precomputed gradients. Also returns the round's
            measured tree-path allreduce payload bytes (AllreduceBytes) and
            its ``mesh_stats`` (``None`` on a one-device world: no output).

            ``lane`` (vmapped-K only) is a dict of TRACED per-lane scalars:
            the lane-vectorizable split params, plus optionally
            ``depth_limit`` (level mask) and ``budget`` (sampling slot
            mask). ``None`` traces the exact scalar program."""
            # fresh per trace: counts the ring-model wire bytes of every
            # tree-path allreduce (histograms + small exact reductions)
            counter = AllreduceBytes(n_actors)
            cfg_t = cfg
            depth_limit = lane_budget = None
            if lane is not None:
                # the growers consume SplitParams arithmetically, so a
                # tracer-carrying replace works; max_delta_step stays the
                # static base value (leaf_weight branches on it in Python)
                cfg_t = dataclasses.replace(
                    cfg,
                    split=dataclasses.replace(
                        cfg.split,
                        learning_rate=lane["learning_rate"],
                        reg_lambda=lane["reg_lambda"],
                        reg_alpha=lane["reg_alpha"],
                        gamma=lane["gamma"],
                        min_child_weight=lane["min_child_weight"],
                    ),
                )
                depth_limit = lane.get("depth_limit")
                lane_budget = lane.get("budget")
            tree_psum = counting_psum(AXIS_ACTORS, counter)
            fshard = None
            counter_f = None
            if fp_c > 1:
                # the feature axis carries only the tiny election gather,
                # the node-total broadcast and the [N] bin-column psums —
                # counted with its own ring extent C
                counter_f = AllreduceBytes(fp_c)
                fshard = FeatureShard(
                    AXIS_FEATURES, fp_c, f_padded, n_feat_real,
                    counter=counter_f,
                )

            def walk(tree_, bins_):
                """Once-per-tree margin walk over a (possibly
                feature-sharded) binned matrix."""
                if fshard is None:
                    return predict_tree_binned(
                        tree_, bins_, cfg.max_depth, missing_bin,
                        cat_features=cfg.cat_features,
                    )
                return predict_tree_binned_fsharded(
                    tree_, bins_, cfg.max_depth, missing_bin, fshard,
                    cat_features=cfg.cat_features,
                )

            def hist_ar(h):
                return quantized_hist_allreduce(
                    h, AXIS_ACTORS, cfg.hist_quant, n_actors, counter,
                    min_bytes=cfg.hist_quant_min_bytes,
                    block=cfg.hist_quant_block,
                )

            # the jax.named_scope names below are obs.DEVICE_SCOPES: HLO
            # metadata a profiler trace names the device's operations by
            # (obs/device.py reads them back); nothing at run time
            with jax.named_scope("objective"):
                w_eff = weight * valid.astype(jnp.float32)
                if gh_in is not None:
                    g, h = gh_in
                elif is_ranking:
                    g, h = obj.grad_hess_ranked(
                        margins, label, w_eff, group_rows
                    )
                elif is_survival:
                    g, h = obj.grad_hess_bounds(
                        margins, bounds[0], bounds[1], w_eff
                    )
                else:
                    g, h = obj.grad_hess(margins, label, w_eff)
            new_margins = margins
            new_eval_margins = list(eval_margins)
            trees = []
            for k in range(k_out):
                for t in range(t_par):
                    key = jax.random.fold_in(rng, k * t_par + t)
                    with jax.named_scope("objective"):
                        ghk = jnp.stack([g[:, k], h[:, k]], axis=1)
                    ghk_scale = None
                    if cfg.gh_precision != "float32":
                        # quantize g/h AT THE SOURCE (per-tree pmax-shared
                        # scales, stochastic rounding): the narrow buffer is
                        # what compaction gathers and the histogram
                        # accumulates. The SR key folds SALT_SR per (seed,
                        # iteration, tree, actor) — deterministic reruns,
                        # and identical on every feature shard of a 2D mesh
                        # (rows replicate across AXIS_FEATURES).
                        srkey = jax.random.fold_in(
                            jax.random.fold_in(key, SALT_SR),
                            jax.lax.axis_index(AXIS_ACTORS),
                        )
                        with jax.named_scope("quantize_gh"):
                            ghk, ghk_scale = quantize_gh(
                                ghk, cfg.gh_precision, srkey,
                                axis_name=AXIS_ACTORS, counter=counter,
                                max_rows=gh_max_rows,
                            )
                    bins_t = bins
                    if samp_spec is not None:
                        # compact the round's rows to the fixed M-row budget
                        # so EVERY level's histogram build / partition update
                        # runs over M rows, not N (the tree walk below is
                        # then the only full-row work per tree). Per-actor
                        # key fold: same stream structure as the old
                        # Bernoulli mask, so selections are deterministic in
                        # (seed, iteration, actor) and replay identically
                        # after a checkpoint resume.
                        salt = (
                            SALT_GOSS
                            if samp_spec.policy == "gradient_based"
                            else SALT_SUBSAMPLE
                        )
                        skey = jax.random.fold_in(
                            jax.random.fold_in(key, salt),
                            jax.lax.axis_index(AXIS_ACTORS),
                        )
                        with jax.named_scope("sample"):
                            rows_sel, ghk = sampling.sample_rows(
                                ghk, valid, skey, samp_spec, scale=ghk_scale,
                                lane_budget=lane_budget,
                            )
                            bins_t = bins[rows_sel]
                    fmask = None
                    if params.colsample_bytree < 1.0:
                        fkey = jax.random.fold_in(key, SALT_BYTREE)
                        # drawn over the REAL global feature count (same
                        # stream/semantics on every mesh shape), padded out
                        # to the sharded layout's width when 2D
                        fmask = sample_feature_mask(
                            fkey, n_feat_real, params.colsample_bytree,
                            self._log_fw,
                        )
                        if fshard is not None and f_padded != n_feat_real:
                            fmask = jnp.pad(
                                fmask, (0, f_padded - n_feat_real)
                            )
                    need_level_rng = (
                        params.colsample_bylevel < 1.0
                        or params.colsample_bynode < 1.0
                    )
                    with jax.named_scope("tree"):
                        tree, row_value = build_tree(
                            bins_t,
                            ghk,
                            cuts_grow,
                            cfg_t,
                            depth_limit=depth_limit,
                            feature_mask=fmask,
                            level_rng=key if need_level_rng else None,
                            colsample_bylevel=params.colsample_bylevel,
                            colsample_bynode=params.colsample_bynode,
                            allreduce=tree_psum,
                            feature_log_weights=self._log_fw,
                            feat_has_missing=fhm_grow,
                            hist_allreduce=hist_ar,
                            ar_counter=counter,
                            fshard=fshard,
                            # GOSS compaction dequantizes its small [M, 2]
                            # buffer (amplification is real-valued); the
                            # grower then takes the f32 path over
                            # quantized-grid values
                            gh_scale=(
                                ghk_scale
                                if ghk_scale is not None
                                and jnp.issubdtype(ghk.dtype, jnp.integer)
                                else None
                            ),
                        )
                    trees.append(tree)
                    with jax.named_scope("margin"):
                        if samp_spec is not None:
                            # the compacted build only knows the sampled
                            # rows' leaf values; ALL rows need their margin
                            # update (the next round's gradients cover every
                            # row), so walk the finished tree over the full
                            # binned matrix — the same once-per-tree device
                            # walk eval sets use.
                            row_value = walk(tree, bins)
                        new_margins = new_margins.at[:, k].add(
                            row_value / t_par
                        )
                    with jax.named_scope("eval_walk"):
                        for e in range(n_evals_dev):
                            upd = walk(tree, eval_bins[e])
                            new_eval_margins[e] = (
                                new_eval_margins[e].at[:, k].add(upd / t_par)
                            )
            forest = jax.tree.map(lambda *xs: jnp.stack(xs), *trees)
            # total per-chip wire bytes of the round: actors-axis traffic
            # (histogram merges + exact reductions) plus, on a 2D mesh, the
            # feature-axis election/broadcast traffic
            counter.absorb(counter_f)
            return (new_margins, tuple(new_eval_margins), forest,
                    counter.as_scalar(), counter.mesh_stats())

        def metric_contribs(new_margins, new_eval_margins, label, w_eff,
                            train_group_rows, eval_data, bounds=None):
            """Post-update psum'd (num, den) pairs per eval set x metric."""
            with jax.named_scope("metrics"):
                contribs = []
                ei = 0
                for es in self.evals:
                    if es.is_train:
                        m, lab, w = new_margins, label, w_eff
                        gr, bnd = train_group_rows, bounds
                    else:
                        ed = eval_data[ei]
                        m, lab, w = (
                            new_eval_margins[ei],
                            ed.label,
                            ed.weight * ed.valid.astype(jnp.float32),
                        )
                        gr, bnd = ed.group_rows, ed.bounds
                        ei += 1
                    set_contribs = []
                    for name in dev_metrics:
                        set_contribs.append(
                            device_metric_contrib(
                                name, m, lab, w, gr, psum,
                                huber_slope=params.huber_slope,
                                quantile_alpha=tuple(
                                    params.quantile_alpha
                                    if isinstance(params.quantile_alpha, (list, tuple))
                                    else [params.quantile_alpha]
                                ),
                                bounds=bnd,
                                aft_distribution=params.aft_loss_distribution,
                                aft_sigma=params.aft_loss_distribution_scale,
                            )
                        )
                    contribs.append(tuple(set_contribs))
                return tuple(contribs)

        return tree_round, metric_contribs

    def _eval_arrs(self) -> tuple:
        """Non-train eval sets as _EvalArrs (scalar placeholders for absent
        members so the pytree structure is static across programs)."""
        out = []
        for es in self.evals:
            if es.is_train:
                continue
            out.append(_EvalArrs(
                es.bins, es.label, es.weight, es.valid, es.margins,
                es.group_rows_dev
                if es.group_rows_dev is not None
                else jnp.zeros((), jnp.int32),
                es.margins_static
                if es.margins_static is not None
                else jnp.zeros((), jnp.float32),
                es.bounds_dev
                if es.bounds_dev is not None
                else jnp.zeros((), jnp.float32),
            ))
        return tuple(out)

    def _eval_arr_specs(self) -> tuple:
        # vmapped-K: eval margins carry a leading (replicated) lane axis;
        # every other eval member is lane-shared
        m_spec = P(None, AXIS_ACTORS) if self._vk else P(AXIS_ACTORS)
        specs = []
        for es in self.evals:
            if es.is_train:
                continue
            specs.append(_EvalArrs(
                self._bins_spec(), P(AXIS_ACTORS), P(AXIS_ACTORS), P(AXIS_ACTORS), m_spec,
                P(AXIS_ACTORS) if es.group_rows_dev is not None else P(),
                P(AXIS_ACTORS) if es.margins_static is not None else P(),
                (P(AXIS_ACTORS), P(AXIS_ACTORS)) if es.bounds_dev is not None else P(),
            ))
        return tuple(specs)

    # ------------------------------------------------------------------
    # Program registry (tools/rxgbverify): abstract signatures of every
    # compiled program, so the verifier can re-trace them without running.
    # ------------------------------------------------------------------
    def _program_meta(self) -> Dict[str, Any]:
        """Config coordinates the jaxpr verifier groups programs by. The
        cross-world schedule-identity check compares records that agree on
        everything here except ``world``."""
        samp = sampling.spec_from_params(self.params)
        if samp is None and \
                getattr(self, "_vk_spec_override", None) is not None:
            samp = self._vk_spec_override
        # derived from params, not self.dart: the sketch program registers
        # during __init__ before the dart attribute exists
        is_dart = self.params.booster == "dart"
        meta = {
            "world": int(self.n_devices),
            "grower": "dart" if is_dart else self.params.grow_policy,
            "hist_quant": self.cfg.hist_quant,
            # block-scale wire granularity: a different block size traces a
            # different ring payload layout, so it is part of the identity
            "hist_quant_block": int(self.cfg.hist_quant_block),
            # on-chip gh precision: int8/int16 programs trace integer
            # accumulation + int32 (or quantized) histogram wires — a
            # legitimately different schedule from float32, so it is an
            # identity-group coordinate (and VER004's precision-flow key)
            "gh_precision": str(self.cfg.gh_precision),
            "sampling": samp.policy if samp is not None else "none",
            # feature-axis mesh extent: (R, C) programs are legitimately
            # different from (R, 1) ones and must not share a cross-world
            # identity group; 2D programs group with each other across R
            "feature_parallel": int(self.feature_parallel),
            "n_outputs": int(self.n_outputs),
            # program-shape coordinates: two engines differing here trace
            # legitimately different programs and must not share a
            # cross-world identity group
            "max_depth": int(self.cfg.max_depth),
            "max_leaves": int(self.cfg.max_leaves),
            # ingestion mode: like "world", a WITHIN-group variant axis —
            # rxgbverify's VER001 requires streamed and materialized
            # programs of one config to execute the identical collective
            # schedule (the streamed sketch merge must not change any
            # round-step program)
            "ingest": "streamed" if getattr(self, "_streamed", False)
            else "materialized",
        }
        if getattr(self, "_vk", 0):
            # candidate-lane extent: a K-lane program's collectives carry a
            # leading lane axis (rank grows by one, schedule identical), so
            # K is a program-shape coordinate — k=2 and k=4 must not share
            # a cross-world identity group
            meta["k"] = int(self._vk)
        return meta

    def _default_group_rows(self):
        """The ``group_rows`` dispatch argument (scalar sentinel when the
        data is ungrouped) — shared by the real dispatch sites and the
        ``*_example_args`` signature capture, so the registered abstract
        program cannot drift from the dispatched one."""
        if self.group_rows is not None:
            return self.group_rows
        return jnp.zeros((), jnp.int32)

    def _default_bounds(self):
        """The label-bounds dispatch argument (scalar sentinel when not
        survival training) — shared like :meth:`_default_group_rows`."""
        if self.bounds_dev is not None:
            return self.bounds_dev
        return jnp.zeros((), jnp.float32)

    def _step_example_args(self, custom: bool) -> tuple:
        """The ``step()`` call site's argument tuple, for signature capture.
        Must mirror :meth:`step` exactly — the registered abstract trace IS
        the program the verifier certifies."""
        group_rows = self._default_group_rows()
        gh_in = (
            (self.margins, self.margins) if custom
            else jnp.zeros((), jnp.float32)
        )
        bounds = self._default_bounds()
        rng = jax.random.PRNGKey(self.params.seed)
        return (self.bins, self.valid, self.label_dev, self.weight_dev,
                self.margins, group_rows, gh_in, rng, bounds,
                self._eval_arrs())

    def _scan_example_args(self) -> tuple:
        """``step_many``'s signature at a representative 2-round chunk (the
        collective schedule inside the scan body is chunk-length blind)."""
        group_rows = self._default_group_rows()
        bounds = self._default_bounds()
        return (self.bins, self.valid, self.label_dev, self.weight_dev,
                self.margins, group_rows, jnp.arange(2), bounds,
                self._eval_arrs())

    def _dart_example_args(self) -> tuple:
        group_rows = self._default_group_rows()
        bounds = self._default_bounds()
        return (self.bins, self.valid, self.label_dev, self.weight_dev,
                self._margins_static_dev, group_rows, bounds,
                self.dart_forest_dev, jnp.asarray(self.dart_weights),
                jnp.asarray(self.dart_weights), jnp.float32(1.0),
                jnp.int32(0), jax.random.PRNGKey(self.params.seed),
                self._eval_arrs())

    def build_programs(self) -> None:
        """Force-build every round program this engine configuration can
        dispatch (without compiling or executing any of them — ``jax.jit``
        is lazy). Under :func:`progreg.capture` this is how the verifier
        populates the registry for a config without running a round."""
        if self._vk:
            if self._vk not in self._vk_fns:
                self._vk_fns[self._vk] = self._make_vmapped_step(self._vk)
            return
        if self.dart:
            if self._dart_fn is None:
                self._dart_fn = self._make_dart_step()
            return
        if self._step_fn is None:
            self._step_fn = self._make_step(custom=False)
        if self._step_fn_custom is None:
            # the custom-objective variant dispatches the same collectives
            # from externally-supplied g/h; it must be certified too (a
            # user's obj callback can reach every grower/hist_quant config)
            self._step_fn_custom = self._make_step(custom=True)
        if self.can_batch_rounds() and self._scan_fn is None:
            self._scan_fn = self._make_scan_step()

    def _make_step(self, custom: bool):
        tree_round, metric_contribs = self._round_closures()

        def step(bins, valid, label, weight, margins, group_rows, gh_in, rng,
                 bounds, eval_data):
            eval_bins = tuple(d.bins for d in eval_data)
            eval_margins = tuple(d.margins for d in eval_data)
            new_margins, new_eval_margins, forest, ar_bytes, mesh_stats = tree_round(
                bins, valid, label, weight, margins, group_rows,
                gh_in if custom else None, rng, bounds, eval_bins, eval_margins,
            )
            contribs = metric_contribs(
                new_margins, new_eval_margins, label,
                weight * valid.astype(jnp.float32), group_rows, eval_data,
                bounds=bounds,
            )
            return (new_margins, new_eval_margins, forest, contribs, ar_bytes,
                    mesh_stats)

        eval_specs = self._eval_arr_specs()
        mapped = jax.shard_map(
            step,
            mesh=self.mesh,
            in_specs=(
                self._bins_spec(),  # bins
                P(AXIS_ACTORS),  # valid
                P(AXIS_ACTORS),  # label
                P(AXIS_ACTORS),  # weight
                P(AXIS_ACTORS),  # margins
                P(AXIS_ACTORS) if self.group_rows is not None else P(),
                (P(AXIS_ACTORS), P(AXIS_ACTORS)) if custom else P(),
                P(),  # rng
                (P(AXIS_ACTORS), P(AXIS_ACTORS)) if self.bounds_dev is not None else P(),
                eval_specs,
            ),
            out_specs=(
                P(AXIS_ACTORS),
                tuple(P(AXIS_ACTORS) for _ in eval_specs),
                P(),
                tuple(
                    tuple((P(), P()) for _ in self._device_metrics)
                    for _ in self.evals
                ),
                P(),  # allreduce payload bytes (identical on every shard)
                P(AXIS_ACTORS),  # mesh_stats, a [3] a shard (None: no output)
            ),
            check_vma=False,
        )
        return progreg.register_jit(
            "engine.step_custom" if custom else "engine.step",
            mapped,
            donate_argnums=(4,),
            example_args=lambda: self._step_example_args(custom),
            meta=self._program_meta(),
        )

    # ------------------------------------------------------------------
    def _make_scan_step(self):
        """Multi-round variant: lax.scan over the round body inside one
        shard_map program. Removes per-round host dispatch — the TPU analog
        of the reference keeping its hot loop inside ``xgb.train``
        (``xgboost_ray/main.py:745-752``) instead of stepping from Python.
        Only built when no per-round host interaction is needed (no custom
        objective, no host-side metrics)."""
        tree_round, metric_contribs = self._round_closures()
        seed_key = jax.random.PRNGKey(self.params.seed)

        def run(bins, valid, label, weight, margins, group_rows, iterations,
                bounds, eval_data):
            eval_bins = tuple(d.bins for d in eval_data)
            eval_margins0 = tuple(d.margins for d in eval_data)

            def scan_body(carry, iteration):
                margins_c, eval_margins_c = carry
                rng = jax.random.fold_in(seed_key, iteration)
                new_margins, new_eval_margins, forest, ar_bytes, mesh_stats = tree_round(
                    bins, valid, label, weight, margins_c, group_rows, None,
                    rng, bounds, eval_bins, eval_margins_c,
                )
                contribs = metric_contribs(
                    new_margins, new_eval_margins, label,
                    weight * valid.astype(jnp.float32), group_rows, eval_data,
                    bounds=bounds,
                )
                return (new_margins, new_eval_margins), (
                    forest, contribs, ar_bytes, mesh_stats)

            (margins_out, eval_margins_out), (
                forests, contribs, ar_bytes, mesh_stats) = (
                jax.lax.scan(scan_body, (margins, eval_margins0), iterations)
            )
            return (margins_out, eval_margins_out, forests, contribs, ar_bytes,
                    mesh_stats)

        eval_specs = self._eval_arr_specs()
        mapped = jax.shard_map(
            run,
            mesh=self.mesh,
            in_specs=(
                self._bins_spec(),
                P(AXIS_ACTORS),
                P(AXIS_ACTORS),
                P(AXIS_ACTORS),
                P(AXIS_ACTORS),
                P(AXIS_ACTORS) if self.group_rows is not None else P(),
                P(),  # iterations
                (P(AXIS_ACTORS), P(AXIS_ACTORS)) if self.bounds_dev is not None else P(),
                eval_specs,
            ),
            out_specs=(
                P(AXIS_ACTORS),
                tuple(P(AXIS_ACTORS) for _ in eval_specs),
                P(),
                tuple(tuple((P(), P()) for _ in self._device_metrics) for _ in self.evals),
                P(),  # per-round allreduce payload bytes [n_rounds]
                P(None, AXIS_ACTORS),  # mesh_stats [n_rounds, 3 a shard]
            ),
            check_vma=False,
        )
        return progreg.register_jit(
            "engine.step_many",
            mapped,
            donate_argnums=(4,),
            example_args=self._scan_example_args,
            meta=self._program_meta(),
        )

    def can_batch_rounds(self) -> bool:
        return not self._host_metrics and not self.dart

    def _adopt_eval_margins(self, new_eval_margins) -> None:
        """The dispatch's updated margins of the non-train eval sets."""
        for es, margins in zip(
            (es for es in self.evals if not es.is_train), new_eval_margins
        ):
            es.margins = margins

    def _read_metric_scalars(self, contribs, empty_shape) -> np.ndarray:
        """Every (num, den) scalar of the dispatch in ONE stacked transfer
        (rows: eval set x metric x (num, den)) instead of a blocking host
        read per scalar; this read is the sync ``dispatch.wait`` ends at."""
        flat = [
            c
            for si in range(len(self.evals))
            for mi in range(len(self._device_metrics))
            for c in contribs[si][mi]
        ]
        return np.asarray(jnp.stack(flat)) if flat else np.zeros(empty_shape)

    @staticmethod
    def _metric_value(name: str, num, den) -> float:
        val = float(num) / max(float(den), 1e-12)
        base, _ = parse_metric_name(name)
        return float(np.sqrt(val)) if base in ("rmse", "rmsle") else val

    def _emit_round_spans(self, ts, t0, round0: int, n_rounds: int = 1) -> None:
        """Record one ``round`` span per boosting round of the open
        ``dispatch`` span (their parent), fenced by the same host-side sync
        the step paths already perform (no extra device round trips). What
        happened is the dispatch; a fused-scan chunk's rounds are its
        duration split evenly, each marked ``fused_chunk`` so consumers
        (``after_round`` streaming, ``obs["rounds"]``) know the granularity."""
        tracer = obs.get_tracer()
        if not tracer.enabled:
            return
        dur = (time.perf_counter() - t0) / max(n_rounds, 1)
        attrs = self._obs_round_attrs
        if n_rounds > 1:
            attrs = dict(attrs, fused_chunk=n_rounds)
        for r in range(n_rounds):
            tracer.add_span(
                "round", ts + r * dur, t0 + r * dur, dur, round=round0 + r,
                attrs=attrs,
            )

    def step_many(self, iteration0: int, n_rounds: int) -> List[Dict[str, Dict[str, float]]]:
        """Run ``n_rounds`` boosting rounds in one compiled program.

        Returns the per-round metrics list (same schema as ``step``).
        Programs are cached per n_rounds; callers should use a fixed chunk
        size (the driver uses ENV.SCAN_MAX_CHUNK, clamped to checkpoint
        boundaries) to avoid recompiles.
        """
        if self._vk:
            raise RuntimeError(
                "engine is in vmapped-K mode; use step_vmapped()"
            )
        if not self.can_batch_rounds():
            raise RuntimeError("host-side metrics require per-round stepping")
        prog = ("scan", n_rounds)
        tracer = obs.get_tracer()
        with tracer.span(
            "dispatch", program="scan", rounds=n_rounds,
            first=prog not in self._warm_programs,
        ):
            span_ts, span_t0 = time.time(), time.perf_counter()
            with tracer.span("dispatch.enqueue"):
                if self._scan_fn is None:
                    self._scan_fn = self._make_scan_step()
                # placed from the host: jnp.arange(a, b) compiles an add for
                # the first a > 0, i.e. inside the second chunk's dispatch
                iterations = jax.device_put(
                    np.arange(
                        self.iteration_offset + iteration0,
                        self.iteration_offset + iteration0 + n_rounds,
                        dtype=np.int32,
                    ),
                    NamedSharding(self.mesh, P()),
                )
                eval_data = self._eval_arrs()
                group_rows = self._default_group_rows()
                bounds = self._default_bounds()
                # the scan program compiles once per distinct chunk length; the
                # strict guard arms only for chunk lengths already dispatched
                with strict_transfer_guard(active=prog in self._warm_programs):
                    new_margins, new_eval_margins, forests, contribs, ar_bytes, mesh_stats = self._scan_fn(
                        self.bins,
                        self.valid,
                        self.label_dev,
                        self.weight_dev,
                        self.margins,
                        group_rows,
                        iterations,
                        bounds,
                        eval_data,
                    )
            self._warm_programs.add(prog)
            # keep the device scalar; materialized lazily by the accessor so the
            # steady-state step path adds NO host reads (transfer-count contract)
            self._ar_bytes_dev = ar_bytes[0]
            self._keep_mesh_stats(mesh_stats)
            self.margins = new_margins
            self._adopt_eval_margins(new_eval_margins)
            # defer forest transfer: keep the whole stacked chunk on device
            # (order-safe alongside per-round step()s) and materialize it in ONE
            # batched read per Tree field at the next checkpoint/get_booster
            # instead of an eager 9-field read per chunk
            self._trees_dev.append((forests, n_rounds))

            # metrics: one stacked transfer for ALL (num, den) scalars of the
            # whole chunk instead of a device read per (eval, metric, row)
            with tracer.span("dispatch.wait"):
                flat_vals = self._read_metric_scalars(contribs, (0, n_rounds))
                if not flat_vals.size:
                    # with no eval sets, the metric read above is skipped and (with
                    # forest transfer deferred) nothing else syncs — block so that
                    # returning means "chunk computed", keeping round_times_s and
                    # the overhead ablation honest
                    new_margins.block_until_ready()
            self._emit_round_spans(
                span_ts, span_t0, self.iteration_offset + iteration0, n_rounds
            )
        results: List[Dict[str, Dict[str, float]]] = []
        for r in range(n_rounds):
            round_res: Dict[str, Dict[str, float]] = {}
            fi = 0
            for si, es in enumerate(self.evals):
                row: Dict[str, float] = {}
                for mi, name in enumerate(self._device_metrics):
                    row[name] = self._metric_value(
                        name, flat_vals[fi][r], flat_vals[fi + 1][r]
                    )
                    fi += 2
                round_res[es.name] = row
            results.append(round_res)
        return results

    def step(self, iteration: int, gh_custom=None) -> Dict[str, Dict[str, float]]:
        """Run one boosting round; returns {eval_name: {metric: value}}."""
        if self._vk:
            raise RuntimeError(
                "engine is in vmapped-K mode; use step_vmapped()"
            )
        if self.dart:
            if gh_custom is not None:
                raise ValueError("custom objectives are not supported with dart")
            return self.step_dart(iteration)
        custom = gh_custom is not None
        prog = "step_custom" if custom else "step"
        tracer = obs.get_tracer()
        with tracer.span(
            "dispatch", program=prog, rounds=1,
            first=prog not in self._warm_programs,
        ):
            span_ts, span_t0 = time.time(), time.perf_counter()
            with tracer.span("dispatch.enqueue"):
                if custom:
                    if self._step_fn_custom is None:
                        self._step_fn_custom = self._make_step(custom=True)
                    fn = self._step_fn_custom
                else:
                    if self._step_fn is None:
                        self._step_fn = self._make_step(custom=False)
                    fn = self._step_fn
                rng = jax.random.fold_in(
                    jax.random.PRNGKey(self.params.seed), self.iteration_offset + iteration
                )
                eval_data = self._eval_arrs()
                group_rows = self._default_group_rows()
                if custom:
                    # g/h hold THIS process's rows (the driver computes the custom
                    # objective from get_margins_local + process-local labels — the
                    # reference's per-actor local computation, ``main.py:745-752``);
                    # _put_rows assembles them into the global sharded layout.
                    g, h = gh_custom
                    gh_in = (
                        self._put_rows(
                            np.asarray(g, np.float32).reshape(self._local_rows, -1),
                            np.float32,
                        ),
                        self._put_rows(
                            np.asarray(h, np.float32).reshape(self._local_rows, -1),
                            np.float32,
                        ),
                    )
                else:
                    gh_in = jnp.zeros((), jnp.float32)
                bounds = self._default_bounds()
                with strict_transfer_guard(active=prog in self._warm_programs):
                    new_margins, new_eval_margins, forest, contribs, ar_bytes, mesh_stats = fn(
                        self.bins,
                        self.valid,
                        self.label_dev,
                        self.weight_dev,
                        self.margins,
                        group_rows,
                        gh_in,
                        rng,
                        bounds,
                        eval_data,
                    )
            self._warm_programs.add(prog)
            self._ar_bytes_dev = ar_bytes
            self._keep_mesh_stats(mesh_stats)
            self.margins = new_margins
            self._adopt_eval_margins(new_eval_margins)
            self._trees_dev.append((forest, None))

            # metrics: one stacked transfer for all (num, den) scalars instead of
            # a blocking host read per scalar
            with tracer.span("dispatch.wait"):
                flat_vals = self._read_metric_scalars(contribs, 0)
            results: Dict[str, Dict[str, float]] = {}
            fi = 0
            for si, es in enumerate(self.evals):
                row: Dict[str, float] = {}
                for mi, name in enumerate(self._device_metrics):
                    row[name] = self._metric_value(
                        name, flat_vals[fi], flat_vals[fi + 1]
                    )
                    fi += 2
                if self._host_metrics:
                    margin = self.get_margins_local(es)
                    for name in self._host_metrics:
                        row[name] = self.combine_host_scalar(
                            self._host_metric_value(name, margin, es), es,
                            metric=name,
                        )
                results[es.name] = row
            self._emit_round_spans(
                span_ts, span_t0, self.iteration_offset + iteration
            )
        return results

    def _host_metric_value(self, name: str, margin: np.ndarray, es) -> float:
        """One host-side metric value, including the aft-nloglik special case
        (which consumes label *bounds* rather than labels). Shared by the
        regular ``step()`` and the dart ``step_dart()`` results paths."""
        if name == "aft-nloglik":
            from xgboost_ray_tpu.ops import survival as survival_mod

            return survival_mod.aft_nloglik_np(
                margin,
                es.lower_np if es.lower_np is not None else self.lower_np,
                es.upper_np if es.upper_np is not None else self.upper_np,
                es.weight_np,
                distribution=self.params.aft_loss_distribution,
                sigma=self.params.aft_loss_distribution_scale,
            )
        return compute_metric(
            name,
            margin,
            es.label_np if es.label_np is not None else self.label_np,
            es.weight_np,
            group_ptr=es.group_ptr,
            huber_slope=self.params.huber_slope,
            quantile_alpha=self.params.quantile_alpha,
        )

    def get_margins(self, es: Optional[_EvalSet] = None) -> np.ndarray:
        """Gather (unpadded) margins for the train set or an eval set.

        Works on multi-host meshes: non-addressable sharded margins are
        allgathered before the padding rows are dropped.
        """
        if es is None or es.is_train:
            return self._fetch_rows(self.margins, self.valid, self.n_rows)
        return self._fetch_rows(es.margins, es.valid, es.n_rows)

    def get_margins_local(self, es: Optional[_EvalSet] = None) -> np.ndarray:
        """This process's rows' (unpadded) margins — the per-actor local view
        the reference computes custom obj/feval on (``main.py:745-752``).
        Pairs with the process-local ``label_np``/``weight_np`` arrays.
        Single-host this IS the global view."""
        if jax.process_count() == 1:
            return self.get_margins(es)
        if es is None or es.is_train:
            arr, local_n = self.margins, self._local_rows
        else:
            arr, local_n = es.margins, es.local_rows
        shards = sorted(
            arr.addressable_shards, key=lambda s: s.index[0].start or 0
        )
        slab = np.concatenate([np.asarray(s.data) for s in shards], axis=0)
        return slab[:local_n]

    def combine_host_scalar(
        self, value: float, es: Optional[_EvalSet] = None,
        metric: Optional[str] = None,
    ) -> float:
        """Combine a process-locally computed scalar metric into the global
        value: weighted mean across processes. The weight matches the
        metric's own averaging unit — GROUP count for per-group metrics
        (ndcg/map/pre are means over query groups), otherwise weight sum
        (weighted eval set) or row count. Identity on single-host meshes.
        Deterministic and identical on every process (allgather-based), so
        evals_result stays replica-consistent."""
        if jax.process_count() == 1:
            return float(value)
        from jax.experimental import multihost_utils

        base = parse_metric_name(metric)[0] if metric else None
        if base in ("ndcg", "map", "pre") and es is not None and es.group_ptr is not None:
            wt = float(len(es.group_ptr) - 1)
        elif es is not None and es.weight_np is not None:
            wt = float(np.sum(es.weight_np))
        elif es is not None and es.label_np is not None:
            wt = float(len(es.label_np))
        else:
            wt = float(self._local_rows)
        arr = np.asarray(
            multihost_utils.process_allgather(
                np.array([float(value) * wt, wt], np.float64)
            )
        ).reshape(-1, 2).sum(axis=0)
        return float(arr[0] / max(arr[1], 1e-12))

    def _stacked_forest(self) -> Tree:
        """Stacked [T, heap] forest with incremental appends: only rounds added
        since the last call are copied into capacity-doubling buffers, so T/k
        checkpoints over T rounds cost O(T) total tree copies, not O(T^2)."""
        self._flush_trees()
        all_trees = self._init_trees + self.trees
        if not all_trees:
            raise ValueError("empty forest")
        if self._stack_entries == len(all_trees):
            return map_tree(lambda f: f[: self._stack_rows], self._stack_buf)
        add = stack_trees(all_trees[self._stack_entries :])
        rows = add.feature.shape[0]
        need = self._stack_rows + rows
        if self._stack_buf is None or need > self._stack_buf.feature.shape[0]:
            cap = max(need, 2 * (self._stack_buf.feature.shape[0] if self._stack_buf is not None else 0))
            grown = []
            for i, f in enumerate(add):
                buf = np.empty((cap,) + f.shape[1:], f.dtype)
                if self._stack_rows:
                    buf[: self._stack_rows] = self._stack_buf[i][: self._stack_rows]
                grown.append(buf)
            self._stack_buf = type(add)(*grown)
        for i, f in enumerate(add):
            self._stack_buf[i][self._stack_rows : need] = f
        self._stack_rows = need
        self._stack_entries = len(all_trees)
        return map_tree(lambda f: f[: self._stack_rows], self._stack_buf)

    def _flush_trees(self) -> None:
        """Transfer pending device forests to host with batched reads.

        Entries are ``(tree, None)`` for one round (per-round step paths) or
        ``(stacked_tree, n_rounds)`` for a whole scan chunk. ALL pending
        entries are concatenated on device first (per-round trees expand to a
        length-1 leading axis; forest shapes are constant within a run), so a
        flush costs exactly one host read per Tree field no matter how many
        rounds or chunks are pending."""
        entries = self._trees_dev
        if not entries:
            return
        total = sum(1 if n is None else n for _, n in entries)
        if len(entries) == 1 and entries[0][1] is None:
            self.trees.append(jax.tree.map(np.asarray, entries[0][0]))
            self._trees_dev.clear()
            return
        expanded = [
            jax.tree.map(lambda a: a[None], t) if n is None else t
            for t, n in entries
        ]
        stacked = jax.tree.map(
            lambda *xs: np.asarray(jnp.concatenate(xs, axis=0)), *expanded
        )
        for r in range(total):
            self.trees.append(jax.tree.map(lambda a, _r=r: a[_r], stacked))
        self._trees_dev.clear()

    def hist_allreduce_bytes_per_round(self) -> Optional[int]:
        """Measured collective payload bytes of one boosting round's tree
        path (histogram merges + small exact reductions), from the
        device-side counter threaded through the compiled step. ``None``
        before the first round. Every round program sets it from its latest
        dispatch: ``step`` / ``step_custom``, the fused ``step_many`` (its
        first round), the K-lane ``step_vmapped`` (lane 0) and the DART
        step; 0 on a one-device world, where there is no wire. This is the
        ``hist_quant`` traffic metric:
        int8 cuts it ~4x vs the f32 psum. Reading it costs one device->host
        transfer, so callers (bench/driver) fetch it once after training,
        never per round."""
        if self._ar_bytes_dev is None:
            return None
        return int(np.asarray(self._ar_bytes_dev))

    def _keep_mesh_stats(self, mesh_stats) -> None:
        """Add a dispatch's ``mesh_stats`` output (``[..., 3 a shard]``:
        rounds or lanes by shards) to the running sum on the device: an
        enqueue, no host read on the round path. A one-device program has
        no such output."""
        if mesh_stats is None:
            return
        if self._mesh_stats_dev is None:
            # the sum starts as zeros in the layout the fold hands back, so
            # the first dispatch compiles the fold and no later one does
            per_shard = NamedSharding(self.mesh, P(AXIS_ACTORS))
            zeros = np.zeros(mesh_stats.shape[-1], np.int32)
            self._mesh_stats_dev = jax.make_array_from_callback(
                zeros.shape, per_shard, lambda idx: zeros[idx]
            )
            self._mesh_stats_fold = jax.jit(
                lambda acc, new: acc + new.reshape(-1, acc.shape[0]).sum(axis=0),
                out_shardings=per_shard,
            )
        self._mesh_stats_dev = self._mesh_stats_fold(
            self._mesh_stats_dev, mesh_stats
        )
        self._mesh_stats_rounds += int(np.prod(mesh_stats.shape[:-1]))

    def mesh_round_stats(self) -> Dict[str, int]:
        """What only a mesh has, from ``AllreduceBytes.mesh_stats`` as the
        round programs returned it: ``collectives_per_round`` (the tree
        path's collectives, counted where the bytes are), and over every
        round dispatched since the last reset and every shard,
        ``hist_sibling_builds`` (a shard's sibling-subtraction builds, one
        a level >= 1) and ``hist_skew_fallback_builds`` (those among them
        that did not hold the shard's rows of the chosen children in one
        pass: 0, since every build streams every row).
        All 0 on a one-device world, whose programs have no wire and whose
        shard cannot skew.
        Reads this process's shards of the one running sum (a small
        device->host read each, after training only) and, on a
        multi-process world, allgathers the processes' sums: every process
        must call it, and every process gets the world's numbers."""
        out = {"collectives_per_round": 0, "hist_skew_fallback_builds": 0,
               "hist_sibling_builds": 0}
        total = self._mesh_stats_total()
        if total is None:
            return out
        calls, fallback, sibling = (int(v) for v in total[:len(MESH_STATS)])
        out["collectives_per_round"] = calls // (
            self._mesh_stats_rounds * int(self.mesh.shape[AXIS_ACTORS]))
        out["hist_skew_fallback_builds"] = fallback
        out["hist_sibling_builds"] = sibling
        return out

    def lossguide_round_stats(self) -> Optional[Dict[str, int]]:
        """``LOSSGUIDE_STATS`` summed over every round dispatched since the
        last reset, with ``rounds``: what the leaf-wise grower counted on
        the device (full-row passes, nodes evaluated, splits kept, wanted
        nodes its table could not take). ``None`` for another grower or
        before the first round. A host read like ``mesh_round_stats``."""
        total = self._mesh_stats_total()
        if self.cfg.grow_policy != "lossguide" or total is None:
            return None
        # every row shard counts the same trees: one shard's share
        shards = int(self.mesh.shape[AXIS_ACTORS])
        out = {name: int(v) // shards
               for name, v in zip(LOSSGUIDE_STATS, total[len(MESH_STATS):])}
        out["rounds"] = self._mesh_stats_rounds
        return out

    def _mesh_stats_total(self):
        """The running sum of the dispatches' ``mesh_stats`` over every row
        shard (and process), or ``None`` where no program returned any."""
        if self._mesh_stats_dev is None:
            return None
        # one vector a row shard; a 2D mesh repeats it along the feature axis
        width = len(MESH_STATS) + (
            len(LOSSGUIDE_STATS) if self.cfg.grow_policy == "lossguide" else 0)
        total = np.zeros(width, np.int64)
        for s in self._mesh_stats_dev.addressable_shards:
            if s.replica_id == 0:
                total += np.asarray(s.data).reshape(-1, width).sum(axis=0)
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            total = np.asarray(
                multihost_utils.process_allgather(total)
            ).reshape(-1, width).sum(axis=0)
        return total

    def placement_record(self) -> Dict[str, Any]:
        """Where this engine runs and which chip-or-CPU defaults it
        resolved: platform, device_kind and device count as JAX reports
        them, the training mesh's shape and devices, the real (unpadded)
        rows each of this process's mesh devices holds (``rows_per_device``
        by device id; no device read), and the resolved ``hist_impl`` /
        ``hist_precision``. Recorded under ``additional_results["device"]``
        once per ``train()``."""
        from xgboost_ray_tpu.util import device_record

        blocks = self.rows_per_device()
        block = self._local_pad // len(blocks)
        shards = self.valid.addressable_shards
        first = min(s.index[0].start or 0 for s in shards)
        return {
            **device_record(),
            "mesh_shape": {k: int(v) for k, v in self.mesh.shape.items()},
            "mesh_device_ids": [int(d.id) for d in self.mesh.devices.flat],
            "rows_per_device": {
                str(s.device.id): blocks[
                    ((s.index[0].start or 0) - first) // block]
                for s in shards
            },
            "hist_impl": self.cfg.hist_impl,
            "hist_precision": self.cfg.hist_precision,
        }

    def _local_row_blocks(self) -> int:
        """Row blocks (``AXIS_ACTORS`` slots) this process holds."""
        return max(1, self.n_devices // jax.process_count())

    def rows_per_device(self) -> List[int]:
        """The real (unpadded) rows in each of this process's row blocks,
        in mesh order. ``valid`` is this process's rows followed by its
        padding, cut into equal blocks, so this is arithmetic: no device
        read. The one source of the number (``placement_record``, the
        ``engine.init`` span)."""
        n_local = self._local_row_blocks()
        block = self._local_pad // n_local
        return [
            max(0, min(block, self._local_rows - i * block))
            for i in range(n_local)
        ]

    def gh_plane_bytes_per_shard(self) -> int:
        """Static per-shard bytes of one tree's (grad, hess) plane — the
        buffer the objective kernel emits, compaction gathers from, and the
        histogram accumulates: ``rows_per_shard * 2 * itemsize`` of the
        ``gh_precision`` storage dtype. This is the memory metric the
        quantized-gradient mode is bought for (int8 = 4x smaller shards per
        chip); rxgbverify's VER004 pass certifies the traced programs really
        carry this dtype into the accumulation."""
        n_local = self.pad_to // self.n_devices
        return n_local * 2 * gh_plane_itemsize(self.cfg.gh_precision)

    @property
    def num_round_trees(self) -> int:
        """Rounds recorded so far (host-resident + pending device forests)."""
        return len(self.trees) + sum(
            1 if n is None else n for _, n in self._trees_dev
        )

    def get_booster(self) -> RayXGBoostBooster:
        if self._vk:
            raise RuntimeError(
                "engine is in vmapped-K mode; use get_booster_lane(lane)"
            )
        forest = self._stacked_forest()
        tree_weights = None
        if self.dart:
            tree_weights = self.dart_weights[: self.dart_t].copy()
        booster = RayXGBoostBooster(
            forest,
            np.asarray(self.cuts),
            self.params,
            self.base_score,
            feature_names=self.feature_names,
            feature_types=self.feature_types,
            tree_weights=tree_weights,
        )
        booster._has_node_stats = self._init_has_stats
        booster.categories = self.categories
        return booster

    # ------------------------------------------------------------------
    # Vmapped-K HPO: train K candidate boosters in ONE XLA program.
    #
    # ``enable_lanes`` switches a freshly-built engine into lane mode: the
    # whole boosting round (objective -> sampling -> histogram build ->
    # allreduce -> split election -> partition) is vmapped over a leading
    # candidate axis on the SAME binned data, with each lane's params
    # carried as traced scalars. Collectives batch under vmap — every
    # psum/pmax payload gains a leading K axis but the schedule (count,
    # order, reduction op) is identical to the scalar program, which is
    # exactly the property rxgbverify's VER001 certifies via the ``k``
    # program-meta coordinate. One compile covers all K candidates; ASHA
    # pruning re-packs survivors into a smaller K' program (one more
    # compile per distinct K', cached in ``_vk_fns``).
    # ------------------------------------------------------------------

    def enable_lanes(
        self, lane_params: LaneParams, *, force_masks: bool = False
    ) -> None:
        """Switch this engine into vmapped-K mode for ``lane_params.k``
        candidate lanes. The engine must have been constructed with
        ``lane_params.base`` (the trace-shape config: max depth, max
        subsample rate) and must be fresh — no rounds stepped yet.

        ``force_masks`` traces the per-lane depth and subsample planes even
        when this pack's lanes don't vary them — the sequential-HPO dedupe
        mode: a later ``reset_lanes`` pack may then vary depth/subsample
        (within the base caps) without retracing.

        Raises ``NotImplementedError`` for configurations whose round
        program cannot ride a lane axis; never silently degrades a lane.
        """
        if self._vk:
            raise RuntimeError("lanes already enabled on this engine")
        if self.trees or self._trees_dev or self.iteration_offset:
            raise RuntimeError(
                "enable_lanes requires a fresh engine (no boosted rounds)"
            )
        if jax.process_count() > 1:
            raise NotImplementedError(
                "vmapped-K HPO is single-process only (the multi-host "
                "dispatch path does not carry the lane axis)"
            )
        if self.dart:
            raise NotImplementedError(
                "booster='dart' is not supported on the vmapped-K path"
            )
        if self._streamed:
            raise NotImplementedError(
                "streamed ingestion is not supported on the vmapped-K "
                "path; materialize the matrix for vectorized HPO"
            )
        if self.feature_parallel > 1:
            raise NotImplementedError(
                "feature_parallel > 1 is not supported on the vmapped-K "
                "path (2D-mesh programs are per-trial compiles)"
            )
        if self._host_metrics:
            raise NotImplementedError(
                "host-side eval metrics "
                f"({', '.join(self._host_metrics)}) need per-round host "
                "margins and cannot ride the vmapped-K path; use device "
                "metrics (or sequential trials)"
            )
        if self._init_trees:
            raise NotImplementedError(
                "warm-starting from an init booster is not supported on "
                "the vmapped-K path (lanes share no forest)"
            )
        lanes = lane_params.lanes
        k = lane_params.k
        lane_depth_max = max(p.max_depth for p in lanes)
        if lane_depth_max > self.cfg.max_depth or (
            lane_depth_max != self.cfg.max_depth and not force_masks
        ):
            # without the depth plane the program's level count IS the lane
            # depth; with force_masks any depth <= the traced cap is fine
            raise ValueError(
                "engine was not built with lane_params.base: lane depths "
                f"{[p.max_depth for p in lanes]} vs cfg.max_depth="
                f"{self.cfg.max_depth}"
            )
        # per-lane param planes: f32 split params always; depth/budget
        # masks only when they actually vary (uniform lanes keep the
        # scalar program's exact arithmetic — the bitwise-parity contract)
        # or when force_masks pre-arms them for later reset_lanes packs
        planes = ["learning_rate", "reg_lambda", "reg_alpha", "gamma",
                  "min_child_weight"]
        if lane_params.depth_varied or force_masks:
            planes.append("depth_limit")
        if lane_params.subsample_varied or force_masks:
            planes.append("budget")
            if sampling.spec_from_params(self.params) is None:
                # base (max) rate is 1.0 yet some lane samples (or
                # force_masks pre-arms sampling): trace the full-budget
                # uniform machinery and let lane budgets mask
                self._vk_spec_override = sampling.SamplingSpec(
                    "uniform", rate=1.0
                )
        self._vk_plane_names = tuple(planes)
        arrs = self._vk_build_planes(lanes)
        # K-stack the margin state: [K, rows, n_outputs], lane axis
        # replicated across the mesh, row axis sharded as before. The
        # pristine pre-stack margins are stashed so reset_lanes can re-arm
        # the engine for a fresh pack without rebuilding.
        self._vk_sharding = NamedSharding(self.mesh, P(None, AXIS_ACTORS))
        self._vk_margins0 = np.asarray(self.margins)
        self._vk_eval_margins0 = [
            np.asarray(es.margins) for es in self.evals if not es.is_train
        ]
        self.margins = self._vk_stack(self._vk_margins0, k)
        ei = 0
        for es in self.evals:
            if not es.is_train:
                es.margins = self._vk_stack(self._vk_eval_margins0[ei], k)
                ei += 1
        self._vk = k
        self._vk_lane_params = list(lanes)
        self._vk_lane_ids = list(range(k))
        self._vk_seeds = [int(p.seed) for p in lanes]
        self._vk_lane_np = arrs
        self._vk_lane_arrays = {
            name: jnp.asarray(v) for name, v in arrs.items()
        }
        self._vk_fns: Dict[int, Any] = {}
        self._vk_trees: List[List[Tree]] = [[] for _ in range(k)]
        self._vk_trees_dev: List[Tree] = []
        self._obs_round_attrs = dict(self._obs_round_attrs, k=k)

    def _vk_stack(self, arr_np: np.ndarray, k: int):
        return jax.device_put(
            np.broadcast_to(arr_np, (k,) + arr_np.shape).copy(),
            self._vk_sharding,
        )

    def _vk_build_planes(self, lanes) -> Dict[str, np.ndarray]:
        """The per-lane param planes of ``self._vk_plane_names`` for a lane
        pack (shared by enable_lanes / reset_lanes / repack slicing)."""
        arrs: Dict[str, np.ndarray] = {
            "learning_rate": np.array(
                [p.learning_rate for p in lanes], np.float32
            ),
            "reg_lambda": np.array([p.reg_lambda for p in lanes], np.float32),
            "reg_alpha": np.array([p.reg_alpha for p in lanes], np.float32),
            "gamma": np.array([p.gamma for p in lanes], np.float32),
            "min_child_weight": np.array(
                [p.min_child_weight for p in lanes], np.float32
            ),
        }
        if "depth_limit" in self._vk_plane_names:
            arrs["depth_limit"] = np.array(
                [p.max_depth for p in lanes], np.int32
            )
        if "budget" in self._vk_plane_names:
            block = self.pad_to // self.n_devices
            arrs["budget"] = np.array(
                [
                    sampling.row_budget(
                        block,
                        sampling.SamplingSpec(
                            "uniform", rate=float(p.subsample)
                        ),
                    )
                    for p in lanes
                ],
                np.int32,
            )
        return arrs

    def reset_lanes(self, lane_params: LaneParams) -> None:
        """Re-arm a lane-enabled engine for a fresh candidate pack WITHOUT
        retracing: margin state rewinds to the pristine pre-training
        margins, per-lane planes and seeds are replaced, and the compiled
        K-lane programs in ``_vk_fns`` are reused when the pack's K was
        dispatched before (a new K compiles lazily).

        This is the sequential-HPO compile-dedupe primitive: the Tuner
        routes same-shaped trials through ONE engine, resetting between
        trials, so trials differing only in lane-vectorizable params share
        a single compile. The pack must be sliced from the SAME group pack
        the engine was built with (``lane_params.base == self.params``) so
        every static coordinate — padded shapes, max depth cap, max
        subsample budget — is already covered by the traced program.
        """
        if not self._vk:
            raise RuntimeError("enable_lanes() first")
        if lane_params.base != self.params:
            raise ValueError(
                "reset_lanes pack was built against different base params; "
                "slice the pack from the engine's own group LaneParams"
            )
        lanes = lane_params.lanes
        k = lane_params.k
        if "depth_limit" not in self._vk_plane_names and any(
            p.max_depth != self.cfg.max_depth for p in lanes
        ):
            raise NotImplementedError(
                "param 'max_depth' varies in this pack but the engine's "
                "lane programs traced no depth plane; enable_lanes with "
                "force_masks=True to pre-arm it"
            )
        if "budget" not in self._vk_plane_names and any(
            float(p.subsample) != float(self.params.subsample) for p in lanes
        ):
            raise NotImplementedError(
                "param 'subsample' varies in this pack but the engine's "
                "lane programs traced no budget plane; enable_lanes with "
                "force_masks=True to pre-arm it"
            )
        self._vk_trees_dev.clear()
        self.margins = self._vk_stack(self._vk_margins0, k)
        ei = 0
        for es in self.evals:
            if not es.is_train:
                es.margins = self._vk_stack(self._vk_eval_margins0[ei], k)
                ei += 1
        self._vk = k
        self._vk_lane_params = list(lanes)
        self._vk_lane_ids = list(range(k))
        self._vk_seeds = [int(p.seed) for p in lanes]
        self._vk_lane_np = self._vk_build_planes(lanes)
        self._vk_lane_arrays = {
            name: jnp.asarray(v) for name, v in self._vk_lane_np.items()
        }
        self._vk_trees = [[] for _ in range(k)]
        self._obs_round_attrs = dict(self._obs_round_attrs, k=k)

    def _make_vmapped_step(self, k: int):
        """The K-lane round program: ``jax.vmap`` of the shared round body
        over the lane axis, inside one shard_map. Per-round collectives
        stay per-lane-batched — payload rank grows by one, the collective
        schedule is identical to the scalar step."""
        tree_round, metric_contribs = self._round_closures()

        def step(bins, valid, label, weight, margins_k, group_rows,
                 lane_arrs, rngs, bounds, eval_data):
            eval_bins = tuple(d.bins for d in eval_data)
            eval_margins_k = tuple(d.margins for d in eval_data)

            def one_lane(margins, eval_margins, lane, rng):
                new_margins, new_eval_margins, forest, ar_bytes, mesh_stats = tree_round(
                    bins, valid, label, weight, margins, group_rows, None,
                    rng, bounds, eval_bins, eval_margins, lane=lane,
                )
                contribs = metric_contribs(
                    new_margins, new_eval_margins, label,
                    weight * valid.astype(jnp.float32), group_rows,
                    eval_data, bounds=bounds,
                )
                return (new_margins, new_eval_margins, forest, contribs,
                        ar_bytes, mesh_stats)

            return jax.vmap(one_lane, in_axes=(0, 0, 0, 0))(
                margins_k, eval_margins_k, lane_arrs, rngs
            )

        eval_specs = self._eval_arr_specs()
        mapped = jax.shard_map(
            step,
            mesh=self.mesh,
            in_specs=(
                self._bins_spec(),  # bins (lane-shared)
                P(AXIS_ACTORS),  # valid
                P(AXIS_ACTORS),  # label
                P(AXIS_ACTORS),  # weight
                P(None, AXIS_ACTORS),  # margins [K, rows, n_out]
                P(AXIS_ACTORS) if self.group_rows is not None else P(),
                {name: P() for name in self._vk_lane_arrays},  # lane planes
                P(),  # per-lane rng keys [K, 2]
                (P(AXIS_ACTORS), P(AXIS_ACTORS))
                if self.bounds_dev is not None else P(),
                eval_specs,
            ),
            out_specs=(
                P(None, AXIS_ACTORS),
                tuple(P(None, AXIS_ACTORS) for _ in eval_specs),
                P(),  # forests [K, T, heap]
                tuple(
                    tuple((P(), P()) for _ in self._device_metrics)
                    for _ in self.evals
                ),
                P(),  # allreduce payload bytes [K]
                P(None, AXIS_ACTORS),  # mesh_stats [K, 3 a shard]
            ),
            check_vma=False,
        )
        return progreg.register_jit(
            "engine.step_vmapped",
            mapped,
            donate_argnums=(4,),
            example_args=lambda: self._vmapped_example_args(),
            meta=self._program_meta(),
        )

    def _vmapped_example_args(self) -> tuple:
        group_rows = self._default_group_rows()
        bounds = self._default_bounds()
        return (self.bins, self.valid, self.label_dev, self.weight_dev,
                self.margins, group_rows, self._vk_lane_arrays,
                self._vk_rngs(0), bounds, self._eval_arrs())

    def _vk_rngs(self, iteration: int) -> jnp.ndarray:
        """[K, 2] per-lane round keys: each lane folds ITS OWN seed with
        the global round index, so a lane whose seed equals a sequential
        trial's seed replays that trial's exact PRNG stream."""
        it = self.iteration_offset + iteration
        return jnp.stack([
            jax.random.fold_in(jax.random.PRNGKey(lane_seed), it)
            for lane_seed in self._vk_seeds
        ])

    def step_vmapped(self, iteration: int) -> List[Dict[str, Dict[str, float]]]:
        """Run one boosting round for ALL live lanes; returns a per-lane
        list of ``{eval_name: {metric: value}}`` (index = live-lane slot;
        map through ``lane_ids()`` for original candidate identity)."""
        if not self._vk:
            raise RuntimeError("enable_lanes() first")
        prog = ("vmapped", self._vk)
        tracer = obs.get_tracer()
        with tracer.span(
            "dispatch", program="vmapped", rounds=1,
            first=prog not in self._warm_programs,
        ):
            span_ts, span_t0 = time.time(), time.perf_counter()
            with tracer.span("dispatch.enqueue"):
                k = self._vk
                fn = self._vk_fns.get(k)
                if fn is None:
                    fn = self._vk_fns[k] = self._make_vmapped_step(k)
                eval_data = self._eval_arrs()
                group_rows = self._default_group_rows()
                bounds = self._default_bounds()
                rngs = self._vk_rngs(iteration)
                with strict_transfer_guard(active=prog in self._warm_programs):
                    new_margins, new_eval_margins, forests, contribs, ar_bytes, mesh_stats = fn(
                        self.bins,
                        self.valid,
                        self.label_dev,
                        self.weight_dev,
                        self.margins,
                        group_rows,
                        self._vk_lane_arrays,
                        rngs,
                        bounds,
                        eval_data,
                    )
            self._warm_programs.add(prog)
            self._ar_bytes_dev = ar_bytes[0]
            self._keep_mesh_stats(mesh_stats)
            self.margins = new_margins
            self._adopt_eval_margins(new_eval_margins)
            # defer the [K, T, heap] forest transfer like the scalar path
            self._vk_trees_dev.append(forests)

            # metrics: one stacked [2*n_metrics*n_evals, K] transfer
            with tracer.span("dispatch.wait"):
                flat_vals = self._read_metric_scalars(contribs, (0, k))
            results: List[Dict[str, Dict[str, float]]] = []
            for j in range(k):
                lane_res: Dict[str, Dict[str, float]] = {}
                fi = 0
                for si, es in enumerate(self.evals):
                    row: Dict[str, float] = {}
                    for mi, name in enumerate(self._device_metrics):
                        row[name] = self._metric_value(
                            name, flat_vals[fi][j], flat_vals[fi + 1][j]
                        )
                        fi += 2
                    lane_res[es.name] = row
                results.append(lane_res)
            self._emit_round_spans(
                span_ts, span_t0, self.iteration_offset + iteration
            )
        return results

    def lane_ids(self) -> List[int]:
        """Original candidate index of each live lane slot."""
        return list(self._vk_lane_ids)

    def _vk_flush(self) -> None:
        """Transfer pending [K, T, heap] device forests to per-lane host
        tree lists. All pending entries share the CURRENT lane packing
        (``repack_lanes`` flushes before slicing)."""
        entries = self._vk_trees_dev
        if not entries:
            return
        for entry in entries:
            ent = jax.tree.map(np.asarray, entry)
            for j in range(len(self._vk_trees)):
                self._vk_trees[j].append(
                    jax.tree.map(lambda a, _j=j: a[_j], ent)
                )
        self._vk_trees_dev.clear()

    def repack_lanes(self, keep: Sequence[int]) -> None:
        """Drop pruned lanes and re-pack survivors into a K' = len(keep)
        program (ASHA's successive-halving primitive). Margin state and
        lane planes are sliced on host and re-placed; the K' round program
        compiles lazily at the next ``step_vmapped`` (cached per K', so a
        later group pruning to the same K' reuses it)."""
        keep = list(keep)
        if not keep:
            raise ValueError("repack_lanes needs at least one survivor")
        if sorted(set(keep)) != sorted(keep) or \
                not all(0 <= j < self._vk for j in keep):
            raise ValueError(f"invalid lane indices {keep!r}")
        self._vk_flush()
        idx = np.asarray(keep, np.int64)

        def take(arr):
            return jax.device_put(np.asarray(arr)[idx], self._vk_sharding)

        self.margins = take(self.margins)
        for es in self.evals:
            if not es.is_train:
                es.margins = take(es.margins)
        self._vk_lane_np = {
            name: v[idx] for name, v in self._vk_lane_np.items()
        }
        self._vk_lane_arrays = {
            name: jnp.asarray(v) for name, v in self._vk_lane_np.items()
        }
        self._vk_seeds = [self._vk_seeds[j] for j in keep]
        self._vk_lane_params = [self._vk_lane_params[j] for j in keep]
        self._vk_trees = [self._vk_trees[j] for j in keep]
        self._vk_lane_ids = [self._vk_lane_ids[j] for j in keep]
        self._vk = len(keep)
        self._obs_round_attrs = dict(self._obs_round_attrs, k=self._vk)

    def get_booster_lane(self, lane: int) -> RayXGBoostBooster:
        """The finished booster of live-lane slot ``lane``, carrying that
        lane's OWN parsed params (eta, lambda, depth, ...) — not the
        widened base config the program traced with."""
        if not self._vk:
            raise RuntimeError("enable_lanes() first")
        self._vk_flush()
        if not self._vk_trees[lane]:
            raise ValueError("empty forest")
        forest = stack_trees(self._vk_trees[lane])
        booster = RayXGBoostBooster(
            forest,
            np.asarray(self.cuts),
            self._vk_lane_params[lane],
            self.base_score,
            feature_names=self.feature_names,
            feature_types=self.feature_types,
        )
        booster._has_node_stats = self._init_has_stats
        booster.categories = self.categories
        return booster

    # ------------------------------------------------------------------
    # In-flight elastic continuation (zero-replay shrink/grow): the driver
    # swaps worlds mid-attempt without restarting from a checkpoint. A
    # cached engine for a previously-seen world signature is revived via
    # ``reset_from_booster`` — its compiled step programs, sketch cuts and
    # binned device matrix are reused, so growing back to a known world
    # costs one host forest walk instead of a retrace + re-sketch.
    # ------------------------------------------------------------------

    def can_reshard(self) -> bool:
        """Whether this engine supports the zero-replay re-shard path.

        True for EVERY gbtree configuration this engine can train: the 1D
        row mesh (PR 5), 2D row x feature meshes (a shrink rebuilds the
        mesh as ``(R', C)`` with feature tiles fixed; a grow-back into a
        previously-compiled ``(R, C)`` world hits the driver's engine
        cache), streamed matrices (survivor shards' binned blocks and
        frozen cuts are reused in memory — no re-stream, no re-sketch; see
        ``stream/ingest.py``'s reuse passes), and dart (the
        capacity-padded device forest and tree weights rebuild from the
        in-memory booster via ``reset_from_booster``; the per-round drop
        RNG is a pure function of (seed, global round), so it needs no
        carried state). gblinear is no longer the asterisk: ``LinearEngine``
        ships its own ``can_reshard``/``reset_from_booster`` (the weight
        vector re-derives from the in-memory booster on any survivor mesh),
        so every built-in booster continues in flight."""
        return True

    def reset_from_booster(self, shards, evals, init_booster) -> None:
        """Re-shard entry point: reuse this engine (compiled step programs,
        binned device matrix, sketch cuts, eval-set device state) for a
        continuation segment starting from ``init_booster``.

        The caller guarantees ``shards``/``evals`` hold the SAME rows this
        engine was built over (``shard_layout_fingerprint`` at the driver's
        world cache; shapes — or stream identities — re-checked here): the
        device-resident data never moves, only the margin state and forest
        bookkeeping are re-derived from the booster. Cost: one forest walk
        per data set — a host walk over raw rows for materialized loads, a
        compiled binned-matrix walk (``stream.init_margins``, fsharded on
        2D meshes) for streamed loads whose raw rows never existed. dart
        additionally rebuilds its capacity-padded device forest + weights
        from the booster inside the engine's compiled capacity. No round
        program retraces, no re-bin, no re-sketch.
        """
        base_margin = None
        x = None
        if self._streamed:
            # streamed: raw rows never existed — verify stream identity
            # (the same fingerprints the driver's cache matched on), then
            # re-derive margins from the retained binned matrix below
            from xgboost_ray_tpu.stream import reader as stream_reader

            streams = stream_reader.shard_streams(shards)
            if streams is None or [
                s.fingerprint() for s in streams
            ] != self._stream_shard_fps:
                raise ValueError(
                    "reshard: streamed shard identity changed; a fresh "
                    "engine build is required."
                )
            base_margin = self._stream_cols.get("base_margin")
        else:
            x, _label, _weight, base_margin, _qid, _lo, _hi = _concat_shards(
                shards
            )
            if x.shape[0] != self._local_rows or x.shape[1] != self.n_features:
                raise ValueError(
                    f"reshard: shard layout changed ({x.shape} vs "
                    f"({self._local_rows}, {self.n_features})); a fresh "
                    f"engine build is required."
                )
        self._init_has_stats = (
            getattr(init_booster, "_has_node_stats", True)
            if init_booster is not None
            else True
        )
        have_init = init_booster is not None and init_booster.num_trees

        def static_margins(n_rows, bm):
            ms = np.full((n_rows, self.n_outputs), self.base_margin0,
                         np.float32)
            if bm is not None:
                ms = ms + bm.reshape(n_rows, -1).astype(np.float32)
            return ms

        def margins_for(xv, bm):
            ms = static_margins(xv.shape[0], bm)
            if have_init:
                ms = ms + (
                    init_booster.predict_margin_np(xv)
                    - init_booster.base_score_margin_np()
                )
            return ms

        self._init_trees = []
        self._init_tree_weights = None
        if have_init:
            self._init_trees = [init_booster.forest]
            self._init_tree_weights = (
                init_booster.tree_weights
                if init_booster.tree_weights is not None
                else np.ones(init_booster.num_trees, np.float32)
            )
        if self.dart:
            # margins are recomputed from the device forest at every dart
            # step (static + weighted forest walk), so only the static part
            # is staged here; the forest/weights rebuild below is the state
            # the next step actually consumes
            self.margins = self._put_rows(
                static_margins(self._local_rows, base_margin), np.float32
            )
            self._reset_dart_state(init_booster)
        elif self._streamed:
            self.margins = self._put_rows(
                static_margins(self._local_rows, base_margin), np.float32
            )
            if have_init:
                # the PR 14 warm-start walk, gated on bitwise cut equality
                # — which holds trivially here: the cuts are retained in
                # memory and the booster was grown on this engine's cuts
                self.margins = self.margins + self._init_margins_from_bins(
                    init_booster, fsharded=self.feature_parallel > 1
                )
        else:
            self.margins = self._put_rows(
                margins_for(x, base_margin), np.float32
            )

        from xgboost_ray_tpu.distributed import put_rows_global

        if len(evals) != len(self.evals):
            raise ValueError("reshard: eval-set count changed")
        for (eval_shards, _name), es in zip(evals, self.evals):
            if es.is_train:
                continue
            # eval sets are materialized by construction (streamed evals
            # are gated at _add_eval_set), so the host walk always applies
            ex, _, _, ebm, _, _, _ = _concat_shards(eval_shards)
            if ex.shape[0] != es.local_rows:
                raise ValueError(
                    f"reshard: eval set {es.name!r} layout changed"
                )
            _, local_pad, _ = self._global_row_layout(ex.shape[0])
            # dart recomputes eval margins from the device forest per step
            # against margins_static, which is already device-resident
            arr = (
                static_margins(ex.shape[0], ebm) if self.dart
                else margins_for(ex, ebm)
            )
            if arr.shape[0] < local_pad:
                arr = np.pad(arr, [(0, local_pad - arr.shape[0]), (0, 0)])
            es.margins = put_rows_global(arr, self._row_sharding)

        # forest bookkeeping restarts at the booster's round count; the
        # compiled programs themselves carry no forest state (the margins
        # and per-round trees are program inputs/outputs)
        self.trees = []
        self._trees_dev = []
        self._stack_entries = 0
        self._stack_rows = 0
        self._stack_buf = None
        self._ar_bytes_dev = None
        self._mesh_stats_dev = None
        self._mesh_stats_rounds = 0
        self.iteration_offset = (
            init_booster.num_boosted_rounds() if init_booster is not None else 0
        )

    def _reset_dart_state(self, init_booster) -> None:
        """Rebuild dart's capacity-padded device forest, tree weights and
        slot cursor from ``init_booster`` WITHOUT changing ``_dart_t_cap``
        — the capacity is a static shape of the compiled dart step, so a
        reset that resized it would force a retrace (and the cached
        program would dispatch against stale shapes). The per-round drop
        RNG carries no state: ``_dart_sample_drops`` is a pure function of
        (seed, iteration_offset + round, weights), and both offset and
        weights are restored here."""
        n_init = (
            init_booster.num_trees
            if init_booster is not None and init_booster.num_trees
            else 0
        )
        if n_init > self._dart_t_cap:
            raise ValueError(
                f"reshard: booster carries {n_init} trees but this dart "
                f"engine's compiled forest capacity is {self._dart_t_cap}; "
                f"a fresh engine build is required."
            )
        self._init_dart_forest(t_cap=self._dart_t_cap)


    # ------------------------------------------------------------------
    # DART (dropout) booster: per-round dropout over the forest built so
    # far, with tree/forest normalization — the analog of xgboost's
    # ``booster="dart"`` which reference users pass straight through.
    # Margins are recomputed from the (capacity-padded, device-resident)
    # forest each round via a vmapped binned walk, so dropping trees is a
    # weight-vector edit, not a cache invalidation problem.
    # ------------------------------------------------------------------

    def _init_dart_forest(self, t_cap: Optional[int] = None):
        """Allocate (or, with an explicit ``t_cap``, re-fill at the pinned
        compiled capacity — the ``reset_from_booster`` path) the
        capacity-padded device forest from ``_init_trees``/weights."""
        k_out = self.n_outputs
        heap = self.cfg.heap_size
        n_init = self._init_trees[0].feature.shape[0] if self._init_trees else 0
        if t_cap is None:
            t_cap = n_init + max(1, self._dart_total_rounds) * k_out

        def empty(dtype, fill):
            return np.full((t_cap, heap), fill, dtype)

        fills = {"feature": (np.int32, -1), "split_bin": (np.int32, 0),
                 "threshold": (np.float32, 0.0), "default_left": (bool, False),
                 "is_leaf": (bool, False), "value": (np.float32, 0.0),
                 "gain": (np.float32, 0.0), "cover": (np.float32, 0.0),
                 "base_weight": (np.float32, 0.0)}
        bufs = {name: empty(dtype, fill) for name, (dtype, fill) in fills.items()}
        bufs["is_leaf"][:, 0] = True  # empty slots predict 0 from a root leaf
        if n_init:
            init = self._init_trees[0]
            for name in Tree._fields:
                bufs[name][:n_init] = getattr(init, name)
        # padded-heap trees only (params.py refuses dart with max_depth=0)
        self.dart_forest_dev = Tree(
            **{name: jnp.asarray(bufs[name]) for name in Tree._fields}
        )
        self.dart_weights = np.zeros(t_cap, np.float32)
        if n_init:
            self.dart_weights[:n_init] = self._init_tree_weights
        self.dart_t = n_init
        self._dart_t_cap = t_cap

    def _make_dart_step(self):
        tree_round, metric_contribs = self._round_closures(update_evals=False)
        cfg = self.cfg
        k_out = self.n_outputs
        missing_bin = self.params.max_bin
        t_cap = self._dart_t_cap
        cls_onehot = jax.nn.one_hot(
            jnp.arange(t_cap) % k_out, k_out, dtype=jnp.float32
        )  # [t_cap, K]

        def forest_margin(forest, bins_local, static, weights):
            leaf = jax.vmap(
                lambda tr: predict_tree_binned(
                    tr, bins_local, cfg.max_depth, missing_bin,
                    cat_features=cfg.cat_features,
                )
            )(forest)  # [t_cap, S]
            contrib = jnp.einsum(
                "ts,tk->sk", leaf * weights[:, None], cls_onehot,
                precision=jax.lax.Precision.HIGHEST,
            )
            return static + contrib

        def dart_step(bins, valid, label, weight, static_margins, group_rows,
                      bounds, forest, w_eff, w_post, new_w, slot, rng, eval_data):
            m_eff = forest_margin(forest, bins, static_margins, w_eff)
            eval_bins = tuple(d.bins for d in eval_data)
            new_margins, _, round_forest, ar_bytes, mesh_stats = tree_round(
                bins, valid, label, weight, m_eff, group_rows, None, rng,
                bounds, (), (),
            )
            del new_margins  # dart recomputes margins from weights instead
            # insert the K new trees at [slot, slot+K)
            forest = jax.tree.map(
                lambda fa, ta: jax.lax.dynamic_update_slice(
                    fa, ta.astype(fa.dtype), (slot,) + (0,) * (fa.ndim - 1)
                ),
                forest,
                round_forest,
            )
            # post-round weights: dropped rescaled + new trees at new_w
            slots = jnp.arange(t_cap)
            w_full = jnp.where(
                (slots >= slot) & (slots < slot + k_out), new_w, w_post
            )
            m_full = forest_margin(forest, bins, static_margins, w_full)
            new_eval_margins = []
            for e, d in enumerate(eval_data):
                m_e = forest_margin(forest, eval_bins[e], d.margins_static, w_full)
                new_eval_margins.append(m_e)
            contribs = metric_contribs(
                m_full, new_eval_margins, label,
                weight * valid.astype(jnp.float32), group_rows, eval_data,
                bounds=bounds,
            )
            return (m_full, tuple(new_eval_margins), forest, round_forest,
                    contribs, ar_bytes, mesh_stats)

        eval_specs = self._eval_arr_specs()
        mapped = jax.shard_map(
            dart_step,
            mesh=self.mesh,
            in_specs=(
                P(AXIS_ACTORS),  # bins
                P(AXIS_ACTORS),  # valid
                P(AXIS_ACTORS),  # label
                P(AXIS_ACTORS),  # weight
                P(AXIS_ACTORS),  # static margins
                P(AXIS_ACTORS) if self.group_rows is not None else P(),
                (P(AXIS_ACTORS), P(AXIS_ACTORS)) if self.bounds_dev is not None else P(),
                P(),  # forest (replicated)
                P(),  # w_eff
                P(),  # w_post
                P(),  # new_w
                P(),  # slot
                P(),  # rng
                eval_specs,
            ),
            out_specs=(
                P(AXIS_ACTORS),
                tuple(P(AXIS_ACTORS) for _ in eval_specs),
                P(),
                P(),
                tuple(
                    tuple((P(), P()) for _ in self._device_metrics)
                    for _ in self.evals
                ),
                P(),  # allreduce payload bytes
                P(AXIS_ACTORS),  # mesh_stats, a [3] a shard
            ),
            check_vma=False,
        )
        return progreg.register_jit(
            "engine.step_dart",
            mapped,
            donate_argnums=(7,),
            example_args=self._dart_example_args,
            meta=self._program_meta(),
        )

    def _dart_sample_drops(self, iteration: int):
        """Host-side dropout sampling; deterministic in (seed, iteration)."""
        params = self.params
        t = self.dart_t
        rng = np.random.RandomState(
            (params.seed * 1_000_003 + self.iteration_offset + iteration) % (2 ** 31)
        )
        drop = np.zeros(self._dart_t_cap, bool)
        if t == 0 or (params.skip_drop > 0 and rng.rand() < params.skip_drop):
            return drop
        weights = np.maximum(self.dart_weights[:t], 0.0)
        if params.sample_type == "weighted":
            probs = weights / max(weights.sum(), 1e-12)
            drop[:t] = rng.rand(t) < np.minimum(probs * t * params.rate_drop, 1.0)
        else:
            drop[:t] = rng.rand(t) < params.rate_drop
        if params.one_drop and not drop.any():
            if params.sample_type == "weighted" and weights.sum() > 0:
                idx = rng.choice(t, p=weights / weights.sum())
            else:
                idx = rng.randint(t)
            drop[idx] = True
        return drop

    def step_dart(self, iteration: int) -> Dict[str, Dict[str, float]]:
        params = self.params
        tracer = obs.get_tracer()
        with tracer.span(
            "dispatch", program="dart", rounds=1,
            first="dart" not in self._warm_programs,
        ):
            span_ts, span_t0 = time.time(), time.perf_counter()
            with tracer.span("dispatch.enqueue"):
                if self.dart_t + self.n_outputs > self._dart_t_cap:
                    # the in-program dynamic_update_slice CLAMPS an out-of-range
                    # slot, which would silently overwrite the newest trees —
                    # unreachable under the driver's round arithmetic (capacity
                    # covers init + total_rounds, resets keep the invariant), so
                    # tripping it means a bookkeeping bug, not a user error
                    raise RuntimeError(
                        f"dart forest capacity exhausted: slot {self.dart_t} + "
                        f"{self.n_outputs} trees > t_cap {self._dart_t_cap}"
                    )
                if self._dart_fn is None:
                    self._dart_fn = self._make_dart_step()
                lr = params.learning_rate
                drop = self._dart_sample_drops(iteration)
                k_dropped = int(drop.sum())
                if k_dropped:
                    if params.normalize_type == "forest":
                        new_w, drop_scale = 1.0 / (1.0 + lr), 1.0 / (1.0 + lr)
                    else:  # "tree"
                        new_w = 1.0 / (k_dropped + lr)
                        drop_scale = k_dropped / (k_dropped + lr)
                else:
                    new_w, drop_scale = 1.0, 1.0
                w_eff = self.dart_weights.copy()
                w_eff[drop] = 0.0
                w_post = self.dart_weights.copy()
                w_post[drop] *= drop_scale

                rng = jax.random.fold_in(
                    jax.random.PRNGKey(params.seed), self.iteration_offset + iteration
                )
                eval_data = self._eval_arrs()
                group_rows = self._default_group_rows()
                bounds = self._default_bounds()
                # the per-round drop weights / tree index are legitimate host
                # inputs of the dart program: place them explicitly (replicated)
                # BEFORE entering the strict guard, which rejects the implicit
                # upload-and-reshard the bare jnp conversions would trigger
                repl = NamedSharding(self.mesh, P())
                w_eff_dev = jax.device_put(np.asarray(w_eff), repl)
                w_post_dev = jax.device_put(np.asarray(w_post), repl)
                new_w_dev = jax.device_put(np.float32(new_w), repl)
                dart_t_dev = jax.device_put(np.int32(self.dart_t), repl)
                with strict_transfer_guard(active="dart" in self._warm_programs):
                    m_full, new_eval_margins, forest, round_forest, contribs, ar_bytes, mesh_stats = self._dart_fn(
                        self.bins,
                        self.valid,
                        self.label_dev,
                        self.weight_dev,
                        self._margins_static_dev,
                        group_rows,
                        bounds,
                        self.dart_forest_dev,
                        w_eff_dev,
                        w_post_dev,
                        new_w_dev,
                        dart_t_dev,
                        rng,
                        eval_data,
                    )
            self._warm_programs.add("dart")
            self.margins = m_full
            self._ar_bytes_dev = ar_bytes
            self._keep_mesh_stats(mesh_stats)
            self.dart_forest_dev = forest
            self._adopt_eval_margins(new_eval_margins)
            self._trees_dev.append((round_forest, None))
            w_new_vec = w_post
            w_new_vec[self.dart_t : self.dart_t + self.n_outputs] = new_w
            self.dart_weights = w_new_vec
            self.dart_t += self.n_outputs

            with tracer.span("dispatch.wait"):
                results: Dict[str, Dict[str, float]] = {}
                for si, es in enumerate(self.evals):
                    row: Dict[str, float] = {}
                    for mi, name in enumerate(self._device_metrics):
                        row[name] = self._metric_value(
                            name, *contribs[si][mi]
                        )
                    if self._host_metrics:
                        margin = self.get_margins_local(es)
                        for name in self._host_metrics:
                            row[name] = self.combine_host_scalar(
                                self._host_metric_value(name, margin, es), es,
                                metric=name,
                            )
                    results[es.name] = row
            self._emit_round_spans(
                span_ts, span_t0, self.iteration_offset + iteration
            )
        return results


def shard_layout_fingerprint(shards) -> tuple:
    """Cheap deterministic fingerprint of a shard list: per-shard shape plus
    strided value samples of data and label. The driver's world cache uses
    it to decide whether a cached engine's binned device data is still valid
    for the actors now holding these ranks — shard loads are deterministic
    in (rank, num_actors), so a matching fingerprint means matching rows
    without an O(N) comparison."""
    parts = []
    for sh in shards:
        stream = sh.get("stream")
        if stream is not None:
            # streamed shards: loaders are deterministic in (source, rank,
            # chunking), so the stream's declared identity stands in for
            # value samples (no rows exist to sample)
            parts.append(stream.fingerprint())
            continue
        d = np.asarray(sh["data"])
        flat = d.ravel()
        stride = max(1, flat.size // 256)
        dsum = float(np.nansum(flat[::stride].astype(np.float64)))
        lab = sh.get("label")
        lsum = 0.0
        if lab is not None:
            la = np.asarray(lab, np.float64).ravel()
            lsum = float(np.nansum(la[:: max(1, la.size // 64)]))
        parts.append((tuple(d.shape), dsum, lsum))
    return tuple(parts)


def _concat_shards(shards):
    """Merge per-actor shard dicts (rank order) into global host arrays.

    Absent-column fills come from ``constants.SHARD_COLUMN_FILLS`` — the
    same table the streamed ingest synthesizes from."""
    fills = SHARD_COLUMN_FILLS
    xs, ys, ws, bs, qs = [], [], [], [], []
    has_w = has_b = has_q = False
    for sh in shards:
        xs.append(np.asarray(sh["data"], np.float32))
        lab = sh.get("label")
        ys.append(
            np.asarray(lab, np.float32)
            if lab is not None
            else np.full(xs[-1].shape[0], fills["label"], np.float32)
        )
        w = sh.get("weight")
        if w is not None:
            has_w = True
        ws.append(
            np.asarray(w, np.float32) if w is not None
            else np.full(xs[-1].shape[0], fills["weight"], np.float32)
        )
        b = sh.get("base_margin")
        if b is not None:
            has_b = True
            bs.append(np.asarray(b, np.float32))
        else:
            bs.append(None)
        q = sh.get("qid")
        if q is not None:
            has_q = True
            qs.append(np.asarray(q))
        else:
            qs.append(None)
    lls, lus = [], []
    has_ll = has_lu = False
    for sh in shards:
        ll = sh.get("label_lower_bound")
        lu = sh.get("label_upper_bound")
        if ll is not None:
            has_ll = True
        if lu is not None:
            has_lu = True
        lls.append(None if ll is None else np.asarray(ll, np.float32).ravel())
        lus.append(None if lu is None else np.asarray(lu, np.float32).ravel())
    x = np.concatenate(xs, axis=0) if len(xs) > 1 else xs[0]
    y = np.concatenate(ys, axis=0) if len(ys) > 1 else ys[0]
    w = (np.concatenate(ws, axis=0) if len(ws) > 1 else ws[0]) if has_w else None
    if has_b:
        bs = [
            b if b is not None
            else np.full(xi.shape[0], fills["base_margin"], np.float32)
            for b, xi in zip(bs, xs)
        ]
        b = np.concatenate(bs, axis=0) if len(bs) > 1 else bs[0]
    else:
        b = None
    if has_q:
        qs = [
            q if q is not None else np.full(xi.shape[0], -1)
            for q, xi in zip(qs, xs)
        ]
        q = np.concatenate(qs, axis=0) if len(qs) > 1 else qs[0]
    else:
        q = None
    if has_ll:
        lls = [
            l if l is not None
            else np.full(xi.shape[0], fills["label_lower_bound"], np.float32)
            for l, xi in zip(lls, xs)
        ]
        ll = np.concatenate(lls, axis=0) if len(lls) > 1 else lls[0]
    else:
        ll = None
    if has_lu:
        lus = [
            l if l is not None
            else np.full(xi.shape[0], fills["label_upper_bound"], np.float32)
            for l, xi in zip(lus, xs)
        ]
        lu = np.concatenate(lus, axis=0) if len(lus) > 1 else lus[0]
    else:
        lu = None
    return x, y, w, b, q, ll, lu

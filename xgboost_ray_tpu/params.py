"""xgboost-style parameter dict parsing and validation.

The reference passes the user's ``params`` dict straight to ``xgb.train``
(``xgboost_ray/main.py:745-752``) after validating distributed-compatibility
(``main.py:1506-1524``: ``exact``/``grow_colmaker`` rejected, GPU hint
warnings). We mirror the same surface: same keys, same aliases, same
rejections — resolved into a typed config for the jitted tpu_hist engine.
"""

import dataclasses
import logging
from typing import Any, Dict, List, Optional, Sequence, Union

logger = logging.getLogger(__name__)

_ALIASES = {
    "eta": "learning_rate",
    "lambda": "reg_lambda",
    "alpha": "reg_alpha",
    "min_split_loss": "gamma",
}

# accepted-and-ignored keys (no TPU meaning, kept for drop-in compatibility)
_IGNORED = {
    "nthread",
    "n_jobs",
    "verbosity",
    "silent",
    "gpu_id",
    "predictor",
    "validate_parameters",
    "single_precision_histogram",
    "use_label_encoder",
    "enable_categorical",
    "disable_default_eval_metric",
    "num_pairsample",
    "device",
    "max_cat_to_onehot",
    "eval_at",
}


@dataclasses.dataclass
class TrainParams:
    objective: str = "reg:squarederror"
    num_class: int = 0
    learning_rate: float = 0.3
    max_depth: int = 6
    reg_lambda: float = 1.0
    reg_alpha: float = 0.0
    gamma: float = 0.0
    min_child_weight: float = 1.0
    max_delta_step: float = 0.0
    subsample: float = 1.0
    # row-sampling policy (ops/sampling.py): "uniform" (subsample-rate
    # without-replacement top-k) or "gradient_based" (GOSS: deterministic
    # top-|g|sqrt(h) fraction + amplified uniform remainder). Either policy
    # COMPACTS the round's rows to a fixed budget, so sampled rounds cost
    # O(M) histogram work, not O(N) with zeroed gradients.
    sampling_method: str = "uniform"
    # gradient_based fractions (LightGBM's GOSS names): keep the top
    # ``top_rate`` of rows by |g|*sqrt(h), sample ``other_rate`` of the
    # rest uniformly with unbiased weight amplification
    top_rate: float = 0.2
    other_rate: float = 0.1
    colsample_bytree: float = 1.0
    colsample_bylevel: float = 1.0
    colsample_bynode: float = 1.0
    max_bin: int = 256
    base_score: Optional[float] = None
    seed: int = 0
    num_parallel_tree: int = 1
    scale_pos_weight: float = 1.0
    tree_method: str = "tpu_hist"
    eval_metric: List[str] = dataclasses.field(default_factory=list)
    # booster selection: gbtree (default) or dart (dropout boosting)
    booster: str = "gbtree"
    rate_drop: float = 0.0
    one_drop: int = 0
    skip_drop: float = 0.0
    sample_type: str = "uniform"  # uniform | weighted
    normalize_type: str = "tree"  # tree | forest
    # survival:aft
    aft_loss_distribution: str = "normal"
    aft_loss_distribution_scale: float = 1.0
    # reg:tweedie
    tweedie_variance_power: float = 1.5
    # reg:pseudohubererror
    huber_slope: float = 1.0
    # reg:quantileerror target quantile(s): float or list of floats
    quantile_alpha: float = 0.5
    # tpu_hist internals
    hist_impl: str = "auto"  # auto | scatter | onehot
    # histogram MXU precision: auto (fast on accelerators, highest on CPU) |
    # highest (f32-exact) | fast (single bf16 pass, ~0.2% bin-sum rounding)
    hist_precision: str = "auto"
    # histogram ALLREDUCE wire format: none (f32 psum, default) | int16 |
    # int8 — quantized collective payloads (~4x fewer bytes for int8) with
    # deterministic rounding and int32 accumulation — | int16_block |
    # int8_block — block-scaled ppermute-ring merge with per-block scales
    # shipped in-band and NO global absmax pre-pass (fewer bytes AND one
    # fewer full-latency collective per merge); node totals / leaf weights
    # stay exact in all modes. Orthogonal to hist_precision (which governs
    # the on-chip BUILD, this governs the cross-chip MERGE).
    hist_quant: str = "none"
    # payloads under this many bytes psum in f32 even when hist_quant is on:
    # small collectives are latency-bound (no byte win) and staying exact
    # keeps small-problem tree structure invariant to the world size
    hist_quant_min_bytes: int = 32768
    # elements per in-band scale block of the flattened histogram for the
    # *_block wire modes (power of two; ignored by the row-scale modes).
    # 512 keeps the scale overhead under 1% while staying far finer than a
    # per-(node, feature) row at production bin counts.
    hist_quant_block: int = 512
    # on-chip gradient/hessian precision: float32 (default) | int16 | int8 —
    # g/h quantized AT THE OBJECTIVE KERNEL with per-tree pmax-shared scales
    # and stochastic rounding (deterministic per seed), then carried
    # low-precision through compaction and histogram accumulation
    # (int -> int32, exact); node totals and leaf weights stay exact f32 of
    # the quantized values. ~4x smaller per-shard gh plane at int8.
    # Orthogonal to (and composable with) hist_quant, which governs only
    # the cross-chip histogram WIRE format.
    gh_precision: str = "float32"
    hist_chunk: int = 8192
    # build only the smaller child's histogram per parent, derive the sibling
    # by subtraction (xgboost hist-core behavior); disable for A/B debugging
    sibling_subtract: bool = True
    # depthwise (level-wise) or lossguide (leaf-wise best-first growth)
    grow_policy: str = "depthwise"
    # lossguide leaf budget; 0 = bounded only by max_depth (2^max_depth).
    # With lossguide, max_depth=0 means no depth bound (xgboost's meaning):
    # the budget alone bounds the tree, which is then held in the linked
    # layout (ops/grow.py LinkedTree)
    max_leaves: int = 0
    # per-feature monotone constraints (-1/0/+1), padded with 0 to the
    # feature count at engine time; xgboost accepts "(1,-1)" strings too
    monotone_constraints: tuple = ()
    # interaction constraints: tuple of tuples of feature indices; a node may
    # only split on features sharing a constraint set with EVERY feature
    # already used on its root path (xgboost semantics)
    interaction_constraints: tuple = ()
    # feature-parallel mesh extent C: the engine's device mesh becomes the 2D
    # (num_actors, C) row x feature grid and each chip builds/allreduces only
    # its [N/R, F/C] histogram tile (psum over the actors axis only; a tiny
    # per-node best-split election rides the features axis). C=1 (default)
    # keeps the 1D row mesh and traces the exact legacy program.
    feature_parallel: int = 1


def validate_streaming_params(params: "TrainParams") -> None:
    """Composition gates for streamed (external-memory) ingestion.

    Streaming happens POST-sketch/PRE-histogram, so anything that only
    consumes the binned matrix composes: ``feature_parallel > 1`` (sharding
    happens post-bin), ``gh_precision`` (the gh plane is margin-derived),
    ``hist_quant``/``hist_impl``/``hist_precision``, row sampling (uniform
    and GOSS compact binned rows), depthwise and lossguide growers,
    monotone/interaction constraints, dart, custom objectives, survival
    bounds, and elastic training IN-FLIGHT (``TpuEngine.can_reshard`` is
    True for streamed loads: a shrink reuses the survivors' binned blocks
    and frozen cuts in memory — zero re-stream, zero re-sketch — and a
    grow-back onto a brand-new replacement actor re-streams only that one
    shard against the frozen cuts, budget-prevalidated; see
    ``stream/ingest.py``'s reuse passes. The warm-start cut-drift gate
    still guards CHECKPOINT resumes whose world or data changed — frozen
    in-memory cuts pass it trivially, re-sketched different ones raise
    instead of mis-routing split_bin).

    What does NOT compose is gated loudly here (the repo's
    no-silent-fallback invariant):

    * ``booster='gblinear'`` — the linear engine consumes raw feature
      values, which a streamed load never materializes;
    * ``rank:*`` objectives — query groups need a global qid-contiguity
      sort the chunk pipeline cannot perform (the qid column itself is also
      rejected at ingest).

    Multi-host worlds and streamed EVAL sets are gated at their own seams
    (engine init / ``_add_eval_set``).
    """
    if params.booster == "gblinear":
        raise NotImplementedError(
            "streamed ingestion is not supported with booster='gblinear': "
            "the linear engine trains on raw feature values, which a "
            "streamed load never materializes. Materialize the matrix or "
            "use a tree booster."
        )
    obj = params.objective
    if isinstance(obj, str) and obj.startswith("rank:"):
        raise NotImplementedError(
            f"streamed ingestion is not supported with objective={obj!r}: "
            f"ranking needs qid-contiguous query groups, which require a "
            f"global sort the chunk pipeline cannot do. Materialize the "
            f"matrix for ranking."
        )


def cat_feature_indices(feature_types: Optional[Sequence[Any]]) -> tuple:
    """Indices marked categorical ('c') in an xgboost feature_types list."""
    return tuple(
        i
        for i, t in enumerate(feature_types or [])
        if str(t).lower() in ("c", "categorical")
    )


def _parse_monotone_constraints(val: Any) -> tuple:
    """xgboost formats: "(1,-1,0)" string, or a sequence of -1/0/+1 ints.
    Length may be shorter than the feature count; the engine pads with 0
    (unconstrained), matching xgboost."""
    if isinstance(val, str):
        items = [s for s in val.strip().strip("()").split(",") if s.strip()]
    elif isinstance(val, dict):
        raise ValueError(
            "dict-form monotone_constraints (by feature name) are not "
            "supported; pass a tuple/list indexed by feature position."
        )
    else:
        items = list(val)
    try:
        out = tuple(int(v) for v in items)
    except (TypeError, ValueError):
        raise ValueError(f"could not parse monotone_constraints: {val!r}")
    if any(c not in (-1, 0, 1) for c in out):
        raise ValueError(
            f"monotone_constraints entries must be -1, 0, or +1; got {out}"
        )
    return out


def _parse_interaction_constraints(val: Any) -> tuple:
    """xgboost format: "[[0, 1], [2, 3, 4]]" string or a nested sequence of
    feature indices. Feature names are not supported (index positions only)."""
    if isinstance(val, str):
        import ast

        try:
            val = ast.literal_eval(val)
        except (SyntaxError, ValueError):
            raise ValueError(
                f"could not parse interaction_constraints string: {val!r}"
            )
    try:
        groups = tuple(
            tuple(sorted({int(i) for i in grp})) for grp in val
        )
    except (TypeError, ValueError):
        raise ValueError(
            f"interaction_constraints must be a sequence of index groups "
            f"(feature names are not supported); got {val!r}"
        )
    if any(i < 0 for grp in groups for i in grp):
        raise ValueError("interaction_constraints indices must be >= 0")
    return tuple(g for g in groups if g)


def parse_params(params: Optional[Dict[str, Any]]) -> TrainParams:
    params = dict(params or {})
    out = TrainParams()

    tree_method = str(params.pop("tree_method", "tpu_hist") or "tpu_hist")
    if tree_method in ("exact",):
        # parity with xgboost_ray/main.py:1509-1515 (exact unsupported distributed)
        raise ValueError(
            "`exact` tree_method doesn't support distributed training. Use "
            "`tree_method=\"tpu_hist\"` (or \"hist\"/\"approx\", which map to it)."
        )
    if tree_method in ("gpu_hist",):
        logger.warning(
            "tree_method='gpu_hist' has no meaning on TPU; using 'tpu_hist'."
        )
        tree_method = "tpu_hist"
    if tree_method in ("hist", "approx", "auto"):
        tree_method = "tpu_hist"
    if tree_method != "tpu_hist":
        raise ValueError(f"Unsupported tree_method: {tree_method!r}")
    out.tree_method = tree_method

    def _empty_constraint(val, empty_strs):
        # explicit checks — numpy arrays reject bool()/== against strings
        if val is None:
            return True
        if isinstance(val, str):
            return val.strip() in empty_strs
        try:
            return len(val) == 0
        except TypeError:
            return False

    mono = params.pop("monotone_constraints", None)
    if not _empty_constraint(mono, ("", "()")):
        out.monotone_constraints = _parse_monotone_constraints(mono)
    ic = params.pop("interaction_constraints", None)
    if not _empty_constraint(ic, ("", "()", "[]")):
        out.interaction_constraints = _parse_interaction_constraints(ic)

    updater = params.pop("updater", None)
    if updater and "grow_colmaker" in str(updater):
        # parity with xgboost_ray/main.py:1509-1515
        raise ValueError(
            "`grow_colmaker` updater doesn't support distributed training."
        )
    feature_selector = params.pop("feature_selector", None)
    # gblinear's LinearTrainParam defaults reg_lambda to 0 (the tree
    # booster's default is 1); remember whether the user set it explicitly
    had_lambda = any(k in params for k in ("lambda", "reg_lambda"))

    em = params.pop("eval_metric", None)
    if em is not None:
        out.eval_metric = [em] if isinstance(em, str) else list(em)

    for key, value in list(params.items()):
        name = _ALIASES.get(key, key)
        if name in _IGNORED:
            continue
        if name == "random_state":
            name = "seed"
        if not hasattr(out, name):
            logger.warning("Ignoring unknown xgboost parameter %r", key)
            continue
        field_type = type(getattr(TrainParams(), name))
        if value is not None:
            try:
                if name == "base_score":
                    value = float(value)
                elif field_type is bool:
                    value = (
                        value.strip().lower() in ("1", "true", "yes")
                        if isinstance(value, str)
                        else bool(value)
                    )
                elif field_type is float:
                    value = float(value)
                elif field_type is int:
                    value = int(value)
                elif field_type is str:
                    value = str(value)
            except (TypeError, ValueError):
                pass
        setattr(out, name, value)

    # function-level import: this module stays importable pre-jax
    from xgboost_ray_tpu.ops.histogram import HIST_IMPLS

    known_impls = ("auto",) + HIST_IMPLS
    if out.hist_impl not in known_impls:
        extra = ""
        if out.hist_impl in ("partition", "mixed", "pallas"):
            extra = (
                f" {out.hist_impl!r} built from node-sorted row blocks, lost "
                "every on-chip reading to the dense build and was removed: "
                "'onehot' (the chip's 'auto') takes its place at every depth."
            )
        raise ValueError(
            f"Unknown hist_impl {out.hist_impl!r}; use one of "
            f"{' | '.join(known_impls)}.{extra}"
        )

    if out.hist_quant not in (
        "none", "int16", "int8", "int16_block", "int8_block"
    ):
        raise ValueError(
            f"Unknown hist_quant {out.hist_quant!r}; use none | int16 | "
            f"int8 | int16_block | int8_block (quantized histogram "
            f"allreduce wire format)."
        )
    if out.hist_quant_block is None:
        out.hist_quant_block = 512
    out.hist_quant_block = int(out.hist_quant_block)
    if (
        out.hist_quant_block < 64
        or out.hist_quant_block > (1 << 20)
        or out.hist_quant_block & (out.hist_quant_block - 1)
    ):
        raise ValueError(
            f"hist_quant_block must be a power of two in [64, 2^20], got "
            f"{out.hist_quant_block!r} (elements per in-band scale block "
            f"of the *_block wire modes)."
        )

    if out.gh_precision is None:
        out.gh_precision = "float32"
    if out.gh_precision not in ("float32", "int16", "int8"):
        raise ValueError(
            f"Unknown gh_precision {out.gh_precision!r}; use float32 | "
            f"int16 | int8 (on-chip quantized-gradient training)."
        )
    if out.gh_precision != "float32" and out.booster == "gblinear":
        raise NotImplementedError(
            "gh_precision quantizes the per-tree gradient/hessian plane; "
            "booster='gblinear' has no gh histogram plane to quantize. "
            "Use gh_precision='float32' (silently ignoring the knob would "
            "misreport the training precision)."
        )

    if out.feature_parallel is None:
        out.feature_parallel = 1
    out.feature_parallel = int(out.feature_parallel)
    if out.feature_parallel < 1:
        raise ValueError(
            f"feature_parallel must be >= 1; got {out.feature_parallel}"
        )
    if out.feature_parallel > 1:
        # the 2D row x feature mesh supports the tree boosters' depthwise and
        # lossguide growers; combinations whose semantics would need global-F
        # state per node are gated loudly rather than silently degraded
        # (the repo's no-silent-fallback invariant)
        if out.booster in ("dart", "gblinear"):
            raise NotImplementedError(
                f"feature_parallel > 1 is not supported with "
                f"booster={out.booster!r} (dart recomputes margins from the "
                f"whole forest each round; gblinear has no histogram to "
                f"shard). Use booster='gbtree'."
            )
        for bad, name in (
            (out.colsample_bylevel < 1.0, "colsample_bylevel"),
            (out.colsample_bynode < 1.0, "colsample_bynode"),
            (bool(out.monotone_constraints)
             and any(out.monotone_constraints), "monotone_constraints"),
            (bool(out.interaction_constraints), "interaction_constraints"),
        ):
            if bad:
                raise NotImplementedError(
                    f"{name} is not supported with feature_parallel > 1 yet "
                    f"(per-level/per-node feature state is global-F); "
                    f"silently ignoring it would change model semantics."
                )

    # None means "unset" in every xgboost-adjacent API (the sklearn layer
    # filters None for exactly this reason) — normalize explicit Nones back
    # to the defaults BEFORE validating, so {'subsample': None} maps to 1.0
    # instead of crashing the range checks below
    if out.subsample is None:
        out.subsample = 1.0
    if out.sampling_method is None:
        out.sampling_method = "uniform"
    if out.top_rate is None:
        out.top_rate = 0.2
    if out.other_rate is None:
        out.other_rate = 0.1
    if not 0.0 < out.subsample <= 1.0:
        raise ValueError(
            f"subsample must be in (0, 1]; got {out.subsample}"
        )
    if out.sampling_method not in ("uniform", "gradient_based"):
        raise ValueError(
            f"Unknown sampling_method {out.sampling_method!r}; use uniform "
            f"(subsample-rate row sampling) | gradient_based (GOSS: "
            f"top_rate/other_rate)."
        )
    if not 0.0 <= out.top_rate <= 1.0 or not 0.0 <= out.other_rate <= 1.0:
        raise ValueError(
            f"top_rate/other_rate must be in [0, 1]; got "
            f"top_rate={out.top_rate} other_rate={out.other_rate}"
        )
    had_rates = (
        params.get("top_rate") is not None
        or params.get("other_rate") is not None
    )
    if out.sampling_method != "gradient_based" and had_rates:
        # explicit GOSS rates without the policy that reads them: surface
        # the misconfiguration (the block below raises/warns for every
        # neighboring combo; silence here would hide a forgotten
        # sampling_method='gradient_based')
        logger.warning(
            "top_rate/other_rate have no effect without "
            "sampling_method='gradient_based'; ignoring them."
        )
    if out.sampling_method == "gradient_based":
        # xgboost drives gradient_based sampling BY `subsample` (the
        # documented gpu_hist recipe carries no GOSS rate names), so
        # drop-in configs must keep xgboost semantics: subsample < 1 maps
        # onto the GOSS budget — half kept deterministically by
        # |g|sqrt(h), half sampled with amplification — and subsample ==
        # 1.0 without rates samples NOTHING (in xgboost that config is a
        # no-op). GOSS with this repo's explicit top_rate/other_rate
        # ALONGSIDE subsample < 1 is genuinely ambiguous and raises.
        if out.subsample < 1.0:
            if had_rates:
                raise ValueError(
                    "subsample < 1 is ambiguous with explicit "
                    "top_rate/other_rate under "
                    "sampling_method='gradient_based'; set either the "
                    "GOSS rates or subsample, not both."
                )
            out.top_rate = out.subsample / 2.0
            out.other_rate = out.subsample / 2.0
            out.subsample = 1.0
        elif not had_rates:
            logger.warning(
                "sampling_method='gradient_based' with subsample=1.0 and "
                "no top_rate/other_rate samples nothing (xgboost parity); "
                "set top_rate/other_rate (or subsample < 1) to enable "
                "GOSS."
            )
            out.sampling_method = "uniform"
    if out.sampling_method == "gradient_based":
        rate_sum = out.top_rate + out.other_rate
        if not 0.0 < rate_sum <= 1.0:
            raise ValueError(
                f"top_rate + other_rate must be in (0, 1] for "
                f"sampling_method='gradient_based'; got {rate_sum}"
            )
        if out.booster == "gblinear":
            raise NotImplementedError(
                "sampling_method='gradient_based' samples rows per TREE; "
                "it does not apply to booster='gblinear'."
            )

    if out.grow_policy not in ("depthwise", "lossguide"):
        raise ValueError(
            f"grow_policy must be 'depthwise' or 'lossguide'; got "
            f"{out.grow_policy!r}"
        )
    if out.max_leaves < 0:
        raise ValueError("max_leaves must be >= 0")
    if out.grow_policy == "depthwise" and out.max_leaves > 0:
        raise NotImplementedError(
            "max_leaves with grow_policy='depthwise' (leaf-budget pruning of "
            "level-wise growth) is not supported; use "
            "grow_policy='lossguide' for a leaf budget, or drop max_leaves. "
            "Silently ignoring it would change model semantics."
        )
    if out.grow_policy == "lossguide":
        for bad, name in (
            (out.colsample_bylevel < 1.0, "colsample_bylevel"),
            (out.colsample_bynode < 1.0, "colsample_bynode"),
            (bool(out.monotone_constraints)
             and any(out.monotone_constraints), "monotone_constraints"),
            (bool(out.interaction_constraints), "interaction_constraints"),
            # the lossguide grower's passes are always the dense one-hot
            # build; an explicit different impl must not be silently
            # dropped (the repo's no-silent-fallback invariant)
            (out.hist_impl not in ("auto", "onehot"),
             f"hist_impl={out.hist_impl!r}"),
        ):
            if bad:
                raise NotImplementedError(
                    f"{name} is not supported with grow_policy='lossguide' "
                    f"yet (level-wise only); silently ignoring it would "
                    f"change model semantics."
                )
    if out.max_depth == 0 and out.grow_policy == "lossguide":
        # xgboost's "no depth bound": the leaf budget alone bounds the tree,
        # which comes back as an ``ops.grow.LinkedTree``
        if out.max_leaves == 0:
            raise ValueError(
                "max_depth=0 (no depth bound) needs max_leaves > 0 with "
                "grow_policy='lossguide': nothing else bounds the tree."
            )
        if out.booster == "dart":
            raise NotImplementedError(
                "booster='dart' with max_depth=0: the DART forest buffer "
                "holds padded-heap trees only; give lossguide a max_depth."
            )
    elif out.max_depth < 1:
        raise ValueError(
            "max_depth must be >= 1 for tpu_hist (0, no depth bound, only "
            "with grow_policy='lossguide' and max_leaves > 0)"
        )
    if out.max_depth > 14:
        raise ValueError(
            f"max_depth={out.max_depth} too large for the padded-heap tpu_hist "
            "learner (limit 14)."
        )
    if not 1 < out.max_bin <= 1024:
        raise ValueError("max_bin must be in (1, 1024]")
    if out.objective.startswith("multi:") and out.num_class < 2:
        raise ValueError("multi:* objectives require num_class >= 2")
    if out.booster not in ("gbtree", "dart", "gblinear"):
        raise ValueError(
            f"Unsupported booster: {out.booster!r} (gbtree, dart, or "
            f"gblinear)."
        )
    if out.booster == "gblinear":
        if not had_lambda:
            out.reg_lambda = 0.0  # xgboost LinearTrainParam default
        if updater is not None and str(updater) not in ("shotgun",
                                                        "coord_descent"):
            raise ValueError(
                f"gblinear updater must be 'shotgun' or 'coord_descent'; "
                f"got {updater!r}"
            )
        if feature_selector is not None and str(feature_selector) != "cyclic":
            raise NotImplementedError(
                "gblinear feature_selector other than 'cyclic' is not "
                "supported (both updaters run the deterministic cyclic "
                "pass here)."
            )
        if out.grow_policy == "lossguide" or out.monotone_constraints or \
                out.interaction_constraints:
            raise NotImplementedError(
                "tree growth options (grow_policy/constraints) do not apply "
                "to booster='gblinear'."
            )
    if out.booster == "dart":
        if out.num_parallel_tree != 1:
            raise ValueError("dart does not support num_parallel_tree > 1")
        if out.normalize_type not in ("tree", "forest"):
            raise ValueError("normalize_type must be 'tree' or 'forest'")
        if out.sample_type not in ("uniform", "weighted"):
            raise ValueError("sample_type must be 'uniform' or 'weighted'")
    return out


# --- vmapped-K (vectorized HPO) lane parameters ------------------------------
# A vmapped-K engine traces ONE round program and runs K hyperparameter
# candidates ("lanes") through it under jax.vmap. A param can ride the lane
# axis only if the round body consumes it ARITHMETICALLY (a traced scalar
# works) — anything that changes trace-time structure (shapes, loop extents,
# histogram build, objective kernel) forces a separate compile and is NOT
# lane-vectorizable. The split is enforced loudly here (the repo's
# no-silent-fallback invariant: a lane must never silently train with a
# neighbor's params).

#: Params that may differ per lane inside one vmapped-K program.
#: ``max_depth`` rides as a traced level mask (the program traces
#: ``max(depths)`` levels); ``subsample`` as a traced slot budget over the
#: max-rate buffer; ``seed`` as a per-lane PRNG key fed in at dispatch.
LANE_VECTORIZABLE_KEYS = (
    "learning_rate",
    "reg_lambda",
    "reg_alpha",
    "gamma",
    "min_child_weight",
    "subsample",
    "max_depth",
    "seed",
)


@dataclasses.dataclass(frozen=True)
class LaneParams:
    """K parsed candidate configs packed for one vmapped-K program.

    ``base`` is the trace-time config: lane 0's params with the shape-
    determining fields widened to cover every lane (``max_depth`` = max,
    ``subsample`` = max rate). ``lanes`` keeps each candidate's own parsed
    params for per-lane PRNG seeds, depth/budget arrays, and the per-lane
    boosters' metadata.
    """

    base: TrainParams
    lanes: tuple  # Tuple[TrainParams, ...]

    @property
    def k(self) -> int:
        return len(self.lanes)

    def values(self, name: str) -> list:
        return [getattr(p, name) for p in self.lanes]

    @property
    def depth_varied(self) -> bool:
        return len({p.max_depth for p in self.lanes}) > 1

    @property
    def subsample_varied(self) -> bool:
        return len({float(p.subsample) for p in self.lanes}) > 1


def vectorize_params(configs: Sequence[Dict[str, Any]]) -> LaneParams:
    """Parse K candidate param dicts into a :class:`LaneParams`, or raise
    ``NotImplementedError`` NAMING the first param that cannot ride the
    lane axis (differs across lanes but is not in
    :data:`LANE_VECTORIZABLE_KEYS`)."""
    if not configs:
        raise ValueError("vectorize_params needs at least one config")
    parsed = [parse_params(c) for c in configs]
    base0 = parsed[0]
    for f in dataclasses.fields(TrainParams):
        if f.name in LANE_VECTORIZABLE_KEYS:
            continue
        reprs = {repr(getattr(p, f.name)) for p in parsed}
        if len(reprs) > 1:
            hint = ""
            if f.name in ("top_rate", "other_rate"):
                hint = (
                    " (GOSS budgets are trace-time row counts; under "
                    "sampling_method='gradient_based' every lane must use "
                    "the same rates)"
                )
            raise NotImplementedError(
                f"param {f.name!r} differs across vmapped-K lanes but is "
                f"not lane-vectorizable{hint}; lane-vectorizable params: "
                f"{', '.join(LANE_VECTORIZABLE_KEYS)}. Split these trials "
                f"into separate (sequential) programs instead."
            )
    if base0.booster != "gbtree":
        raise NotImplementedError(
            f"booster={base0.booster!r} is not supported on the vmapped-K "
            f"path (dart re-walks a lane-dependent forest per round; "
            f"gblinear has no round program to vmap). Use booster='gbtree' "
            f"or sequential trials."
        )
    if base0.grow_policy == "lossguide" and \
            len({p.max_depth for p in parsed}) > 1:
        raise NotImplementedError(
            "param 'max_depth' cannot vary across vmapped-K lanes with "
            "grow_policy='lossguide' (its loop of levels has no per-level "
            "structure to mask); use equal depths or sequential trials."
        )
    if base0.sampling_method == "gradient_based" and \
            len({float(p.subsample) for p in parsed}) > 1:
        raise NotImplementedError(
            "param 'subsample' cannot vary across vmapped-K lanes with "
            "sampling_method='gradient_based' (GOSS budgets are trace-time "
            "row counts); use equal rates or sequential trials."
        )
    base = dataclasses.replace(
        base0,
        max_depth=max(p.max_depth for p in parsed),
        subsample=max(float(p.subsample) for p in parsed),
        eval_metric=list(base0.eval_metric),
    )
    return LaneParams(base=base, lanes=tuple(parsed))

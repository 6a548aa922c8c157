"""gblinear: the linear booster, TPU-native.

xgboost's ``booster="gblinear"`` fits a (multi-output) linear model by
cyclic coordinate descent on the boosting gradients with elastic-net
regularization — the reference exposes it by params passthrough
(``xgboost_ray/main.py:745-752``; updaters ``shotgun``/``coord_descent``
in xgboost's ``src/linear``). TPU formulation: one jitted shard_map
program per round — margins and grad/hess from the row-sharded matrix,
then ONE ``lax.scan`` over features performing the cyclic pass, with the
per-coordinate sums ``psum``-merged across the mesh (the same allreduce
point the tree path uses for histograms). ``shotgun``'s hogwild
parallelism is nondeterministic by design; here both updater names run
the deterministic cyclic pass (what ``coord_descent`` means), which is
also the reproducible choice for SPMD.

Semantics matched to xgboost's ``CoordinateDelta``/``CoordinateDeltaBias``
(``src/linear/coordinate_common.h``): elastic-net soft threshold with the
penalties denormalized by the total instance weight, ``eta``-scaled
updates, and incremental gradient refresh ``g += h * x_j * dw`` within the
pass. Missing values are implicit zeros (xgboost's sparse convention).
"""

import dataclasses
import json
import pickle
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from xgboost_ray_tpu import progreg
from xgboost_ray_tpu.constants import AXIS_ACTORS
from xgboost_ray_tpu.engine import strict_transfer_guard
from xgboost_ray_tpu.ops.metrics import compute_metric, parse_metric_name
from xgboost_ray_tpu.ops.objectives import get_objective
from xgboost_ray_tpu.params import TrainParams


class RayLinearBooster:
    """A trained linear model: ``margin = x @ weights + bias + m0``.

    API mirror of the tree booster's surface where it makes sense
    (predict / save_model / load_model / save_raw / export_xgboost_json),
    so ``train(params={"booster": "gblinear"}, ...)`` drops into the same
    driver pipelines."""

    def __init__(self, weights: np.ndarray, bias: np.ndarray,
                 params: TrainParams, base_score: float,
                 feature_names: Optional[List[str]] = None,
                 rounds: int = 0):
        self.weights = np.asarray(weights, np.float32)  # [F, K]
        self.bias = np.asarray(bias, np.float32)  # [K]
        self.params = params
        self.base_score = float(base_score)
        self.feature_names = feature_names
        self.rounds = int(rounds)
        self._attrs: Dict[str, str] = {}
        self.best_iteration: Optional[int] = None

    # ---- introspection ---------------------------------------------------
    @property
    def num_features(self) -> int:
        return int(self.weights.shape[0])

    @property
    def num_outputs(self) -> int:
        return int(self.weights.shape[1])

    def num_boosted_rounds(self) -> int:
        return self.rounds

    def attributes(self) -> Dict[str, str]:
        return dict(self._attrs)

    def set_attr(self, **kwargs) -> None:
        for k, v in kwargs.items():
            if v is None:
                self._attrs.pop(k, None)
            else:
                self._attrs[k] = str(v)

    def attr(self, key: str) -> Optional[str]:
        return self._attrs.get(key)

    def _objective(self):
        return get_objective(
            self.params.objective, self.params.num_class,
            self.params.scale_pos_weight,
            tweedie_variance_power=self.params.tweedie_variance_power,
            huber_slope=self.params.huber_slope,
            quantile_alpha=self.params.quantile_alpha,
        )

    # ---- prediction ------------------------------------------------------
    def predict_margin_np(self, x: np.ndarray,
                          base_margin: Optional[np.ndarray] = None) -> np.ndarray:
        x = np.nan_to_num(np.asarray(x, np.float32), nan=0.0)
        obj = self._objective()
        m0 = float(obj.base_score_to_margin(self.base_score))
        margin = x @ self.weights + self.bias[None, :] + m0
        if base_margin is not None:
            margin = margin + np.asarray(
                base_margin, np.float32).reshape(x.shape[0], -1)
        return margin

    def predict(self, x, output_margin: bool = False,
                base_margin: Optional[np.ndarray] = None, **kwargs):
        unsupported = [
            k for k in ("pred_contribs", "pred_interactions", "pred_leaf")
            if kwargs.get(k)
        ]
        # normalize iteration_range first: [0, 0] lists and np-int (0, 0)
        # tuples all mean "the full model" (a no-op for a linear model) and
        # must not raise (ADVICE r5)
        it_range = kwargs.get("iteration_range")
        if it_range is not None:
            try:
                it_range = tuple(int(v) for v in it_range)
            except (TypeError, ValueError):
                it_range = kwargs.get("iteration_range")
        if kwargs.get("ntree_limit") or it_range not in (None, (0, 0)):
            unsupported.append("iteration_range/ntree_limit")
        if unsupported:
            raise NotImplementedError(
                f"gblinear predict does not support {unsupported} (a linear "
                f"model has no trees to slice or walk)."
            )
        x = np.asarray(x, np.float32)
        margin = self.predict_margin_np(x, base_margin=base_margin)
        if output_margin:
            return margin[:, 0] if self.num_outputs == 1 else margin
        obj = self._objective()
        return np.asarray(obj.transform(jnp.asarray(margin)))

    # ---- serialization ---------------------------------------------------
    def save_model(self, fname: str) -> None:
        self.export_xgboost_json(fname)

    @classmethod
    def load_model(cls, fname: str) -> "RayLinearBooster":
        with open(fname) as f:
            return cls.import_xgboost_json(f.read())

    def save_raw(self) -> bytes:
        return pickle.dumps(self)

    @classmethod
    def load_raw(cls, raw: bytes) -> "RayLinearBooster":
        return pickle.loads(raw)

    def export_xgboost_json(self, fname: Optional[str] = None) -> str:
        """The native xgboost gblinear JSON schema: flat ``weights`` of
        length ``(F+1)*K``, feature-major with the K bias entries last."""
        f, k = self.weights.shape
        flat = np.concatenate(
            [self.weights.reshape(f * k), self.bias]).astype(float)
        # per-objective param block shared with the tree exporter (a
        # hardcoded reg_loss_param misloads multiclass/poisson/tweedie
        # models in real xgboost — ADVICE r5)
        from xgboost_ray_tpu.models.xgb_export import objective_param_entry

        obj_name, pkey, pval = objective_param_entry(self.params)
        doc = {
            "learner": {
                "attributes": dict(self._attrs),
                "feature_names": list(self.feature_names or []),
                "feature_types": [],
                "gradient_booster": {
                    "name": "gblinear",
                    "model": {
                        "param": {"num_feature": str(f),
                                  "num_output_group": str(max(k, 1))},
                        "boosted_rounds": int(self.rounds),
                        "weights": [float(v) for v in flat],
                    },
                },
                "learner_model_param": {
                    "base_score": str(self.base_score),
                    "boost_from_average": "1",
                    "num_class": str(int(self.params.num_class or 0)),
                    "num_feature": str(f),
                    "num_target": "1",
                },
                "objective": {"name": obj_name, pkey: pval},
            },
            "version": [2, 0, 0],
        }
        out = json.dumps(doc)
        if fname:
            with open(fname, "w") as fh:
                fh.write(out)
        return out

    @classmethod
    def import_xgboost_json(cls, data) -> "RayLinearBooster":
        """Load from a parsed dict, a JSON string, or a file path.

        The three input forms are distinguished explicitly (dict type, then
        path existence) — not by sniffing a leading ``{``, which misreads
        brace-prefixed filenames and BOM-prefixed documents — and file
        reads close their handle (ADVICE r5)."""
        import os

        if isinstance(data, dict):
            doc = data
        else:
            text = os.fspath(data) if isinstance(data, os.PathLike) else data
            if isinstance(text, bytes):
                text = text.decode()
            if os.path.exists(text):
                with open(text) as fh:
                    doc = json.load(fh)
            else:
                doc = json.loads(text)
        learner = doc["learner"]
        gb = learner["gradient_booster"]
        if gb.get("name") != "gblinear":
            raise ValueError(
                f"not a gblinear model: {gb.get('name')!r} (tree models load "
                f"via RayXGBoostBooster.import_xgboost_json)"
            )
        model = gb["model"]
        f = int(model.get("param", {}).get(
            "num_feature", learner["learner_model_param"]["num_feature"]))
        k = max(1, int(model.get("param", {}).get("num_output_group", "1")))
        flat = np.asarray(model["weights"], np.float32)
        weights = flat[: f * k].reshape(f, k)
        bias = flat[f * k: (f + 1) * k]
        params = TrainParams()
        params.booster = "gblinear"
        params.objective = learner.get("objective", {}).get(
            "name", "reg:squarederror")
        params.num_class = int(
            learner["learner_model_param"].get("num_class", "0") or 0)
        out = cls(
            weights, bias, params,
            base_score=float(
                learner["learner_model_param"].get("base_score", "0.5")),
            feature_names=list(learner.get("feature_names") or []) or None,
            rounds=int(model.get("boosted_rounds", 0) or 0),
        )
        for key, val in (learner.get("attributes") or {}).items():
            out.set_attr(**{key: val})
        return out


@dataclasses.dataclass
class _LinEvalSet:
    name: str
    is_train: bool
    x: np.ndarray
    label_np: Optional[np.ndarray]
    weight_np: Optional[np.ndarray]
    base_margin: Optional[np.ndarray]
    group_ptr: Optional[np.ndarray] = None


class LinearEngine:
    """Drop-in engine for the driver loop when ``booster="gblinear"``.

    Implements the subset of ``TpuEngine``'s surface the per-round driver
    path uses (``step``/``get_booster``/``metric_names``/... —
    ``can_batch_rounds`` is False: linear rounds are a single tiny fused
    program, so per-round stepping costs one dispatch, not a tree build).
    """

    def __init__(self, shards, params: TrainParams, num_actors: int,
                 evals=None, devices=None, init_booster=None,
                 feature_names=None, feature_types=None, **_ignored):
        from xgboost_ray_tpu.params import cat_feature_indices

        if cat_feature_indices(feature_types):
            raise NotImplementedError(
                "categorical features with booster='gblinear' are not "
                "supported (a single linear coefficient on category CODES "
                "would silently misread them as ordinal); one-hot encode "
                "the columns or use a tree booster."
            )
        from xgboost_ray_tpu.engine import _concat_shards
        from xgboost_ray_tpu.ops.ranking import RankingObjective
        from xgboost_ray_tpu.ops.survival import SurvivalObjective

        self.params = params
        self.objective = get_objective(
            params.objective, params.num_class, params.scale_pos_weight,
            tweedie_variance_power=params.tweedie_variance_power,
            huber_slope=params.huber_slope,
            quantile_alpha=params.quantile_alpha,
        )
        if isinstance(self.objective, (RankingObjective, SurvivalObjective)):
            raise NotImplementedError(
                f"booster='gblinear' does not support objective "
                f"{params.objective!r} here (tree boosters do)."
            )
        self.n_outputs = self.objective.num_outputs
        self.base_score = float(
            params.base_score if params.base_score is not None
            else self.objective.default_base_score
        )
        self.base_margin0 = float(
            self.objective.base_score_to_margin(self.base_score))

        x, label, weight, base_margin, qid, lo, hi = _concat_shards(shards)
        if qid is not None:
            raise NotImplementedError("gblinear does not support qid groups.")
        self.n_rows = x.shape[0]
        self.n_features = x.shape[1]
        if label is None:
            raise ValueError("gblinear training requires labels.")
        if weight is None:
            weight = np.ones(self.n_rows, np.float32)
        self.label_np = label
        self.weight_np = weight
        self.group_ptr = None
        self.feature_names = feature_names

        devices = list(devices if devices is not None else jax.devices())
        self.n_devices = max(1, min(num_actors, len(devices)))
        self.mesh = Mesh(np.array(devices[: self.n_devices]), (AXIS_ACTORS,))
        self._rows_sharding = NamedSharding(self.mesh, P(AXIS_ACTORS))
        self._repl = NamedSharding(self.mesh, P())

        if jax.process_count() > 1:
            raise NotImplementedError(
                "gblinear multi-process training is not wired yet; train "
                "per-host or use the tree boosters."
            )
        pad_to = -(-max(self.n_rows, self.n_devices)
                   // self.n_devices) * self.n_devices
        self._pad_to = pad_to

        def put(arr, fill=0.0):
            arr = np.asarray(arr, np.float32)
            if arr.shape[0] < pad_to:
                pad = [(0, pad_to - arr.shape[0])] + [(0, 0)] * (arr.ndim - 1)
                arr = np.pad(arr, pad, constant_values=fill)
            return jax.device_put(arr, self._rows_sharding)

        # missing = implicit zero (xgboost's sparse gblinear convention)
        self._x = put(np.nan_to_num(x, nan=0.0))
        self._label = put(label)
        self._valid = put(np.ones(self.n_rows, np.float32))
        self._weight = put(weight)
        k = self.n_outputs
        bm = np.zeros((self.n_rows, k), np.float32)
        if base_margin is not None:
            bm += np.asarray(base_margin, np.float32).reshape(self.n_rows, -1)
        self._user_margin_np = bm
        self._user_margin = put(bm)

        if init_booster is not None:
            if not isinstance(init_booster, RayLinearBooster):
                raise ValueError(
                    "xgb_model for booster='gblinear' must be a gblinear "
                    "model (got a tree booster)."
                )
            self._w = jnp.asarray(init_booster.weights)
            self._b = jnp.asarray(init_booster.bias)
            self.iteration_offset = init_booster.num_boosted_rounds()
        else:
            self._w = jnp.zeros((self.n_features, k), jnp.float32)
            self._b = jnp.zeros((k,), jnp.float32)
            self.iteration_offset = 0
        self._rounds_done = self.iteration_offset

        self.metric_names = (
            list(params.eval_metric) or [self.objective.default_metric])
        self.evals: List[_LinEvalSet] = []
        for eshards, name in (evals or []):
            if eshards is shards:
                ex, el, ew, ebm = x, label, weight, base_margin
            else:
                ex, el, ew, ebm, eq, _, _ = _concat_shards(eshards)
            self.evals.append(_LinEvalSet(
                name=name, is_train=(eshards is shards),
                x=np.nan_to_num(np.asarray(ex, np.float32), nan=0.0),
                label_np=el,
                weight_np=(np.ones(len(ex), np.float32)
                           if ew is None else ew),
                base_margin=ebm,
            ))

        self._round_fn = None
        self._warm = False  # armed after the first (compiling) dispatch

    @property
    def num_round_trees(self) -> int:
        # no trees — but the driver's booster proxy invalidates its cache on
        # change, so this must advance every round; and like TpuEngine it
        # counts only rounds boosted on THIS engine (excluding the
        # init_booster's), which the driver's post-swap round arithmetic
        # (``engine_base + num_round_trees``) depends on
        return self._rounds_done - self.iteration_offset

    def can_batch_rounds(self) -> bool:
        return False

    # ------------------------------------------------------------------
    # Elastic re-shard: gblinear is the easy booster — the whole model is a
    # replicated [F, K] weight matrix + [K] bias with no carried histogram
    # or forest state, so continuing on a changed world is just rebuilding
    # the engine over the survivors' shards (the driver's `_build_world`
    # does that) and a cache revival is re-seeding w/b from the booster.
    # ------------------------------------------------------------------
    def can_reshard(self) -> bool:
        """Zero-replay elastic continuation: the driver may shrink/grow this
        engine's world in flight and continue from the in-memory booster."""
        return True

    def reset_from_booster(self, shards, evals, init_booster) -> None:
        """Revive this cached engine for its original world: verify the
        shard layout still matches the device-resident matrix, then re-seed
        weights/bias/round bookkeeping from ``init_booster``. The compiled
        coordinate-update program and the device-resident data are reused
        as-is — zero re-upload, zero retrace."""
        from xgboost_ray_tpu.engine import _concat_shards

        x, _, _, _, _, _, _ = _concat_shards(shards)
        if x.shape[0] != self.n_rows or x.shape[1] != self.n_features:
            raise ValueError(
                f"cached gblinear engine covers a [{self.n_rows}, "
                f"{self.n_features}] matrix; got [{x.shape[0]}, "
                f"{x.shape[1]}]"
            )
        if init_booster is not None:
            if not isinstance(init_booster, RayLinearBooster):
                raise ValueError(
                    "reset_from_booster for gblinear needs a gblinear model"
                )
            if init_booster.num_features != self.n_features:
                raise ValueError(
                    f"booster has {init_booster.num_features} features; "
                    f"engine has {self.n_features}"
                )
            # replicated placement (matches the round program's P() specs) —
            # jnp.asarray would land on the default device and trip the
            # strict transfer guard on the first warm dispatch
            self._w = jax.device_put(
                np.asarray(init_booster.weights, np.float32), self._repl
            )
            self._b = jax.device_put(
                np.asarray(init_booster.bias, np.float32), self._repl
            )
            self.iteration_offset = init_booster.num_boosted_rounds()
        else:
            k = self.n_outputs
            self._w = jax.device_put(
                np.zeros((self.n_features, k), np.float32), self._repl
            )
            self._b = jax.device_put(np.zeros((k,), np.float32), self._repl)
            self.iteration_offset = 0
        self._rounds_done = self.iteration_offset

    # ------------------------------------------------------------------
    def _build_round_fn(self):
        obj = self.objective
        eta = self.params.learning_rate
        n_feat = self.n_features
        sum_w = float(np.sum(self.weight_np))
        # penalties denormalized by total instance weight (xgboost
        # LinearTrainParam::DenormalizePenalties)
        lam = self.params.reg_lambda * sum_w
        alp = self.params.reg_alpha * sum_w
        psum = lambda v: jax.lax.psum(v, AXIS_ACTORS)

        def coordinate_delta(sg, sh, w):
            # xgboost coordinate_common.h CoordinateDelta (elastic net)
            sg_l2 = sg + lam * w
            sh_l2 = sh + lam
            tmp = w - sg_l2 / jnp.maximum(sh_l2, 1e-38)
            pos = jnp.maximum(-(sg_l2 + alp) / jnp.maximum(sh_l2, 1e-38), -w)
            neg = jnp.minimum(-(sg_l2 - alp) / jnp.maximum(sh_l2, 1e-38), -w)
            d = jnp.where(tmp >= 0, pos, neg)
            return jnp.where(sh < 1e-5, 0.0, d)

        def fn(x, label, valid, weight, user_margin, w, b):
            w_eff = weight * valid
            margins = x @ w + b[None, :] + user_margin + self.base_margin0
            g, h = obj.grad_hess(margins, label, w_eff)

            # bias first (CoordinateDeltaBias), per output
            sg = psum(jnp.sum(g, axis=0))
            sh = psum(jnp.sum(h, axis=0))
            db = eta * jnp.where(sh > 1e-5, -sg / jnp.maximum(sh, 1e-38), 0.0)
            b = b + db
            g = g + h * db[None, :]

            def step(carry, j):
                w, g = carry
                xj = jax.lax.dynamic_slice_in_dim(x, j, 1, axis=1)  # [n,1]
                sg = psum(jnp.sum(g * xj, axis=0))  # [K]
                sh = psum(jnp.sum(h * (xj * xj), axis=0))
                dw = eta * coordinate_delta(sg, sh, w[j])
                w = w.at[j].add(dw)
                g = g + h * xj * dw[None, :]
                return (w, g), None

            (w, g), _ = jax.lax.scan(step, (w, g), jnp.arange(n_feat))
            return w, b

        mapped = jax.shard_map(
            fn, mesh=self.mesh,
            in_specs=(P(AXIS_ACTORS), P(AXIS_ACTORS), P(AXIS_ACTORS), P(AXIS_ACTORS),
                      P(AXIS_ACTORS), P(), P()),
            out_specs=(P(), P()),
            check_vma=False,
        )
        return progreg.register_jit(
            "linear.update",
            mapped,
            example_args=lambda: (self._x, self._label, self._valid,
                                  self._weight, self._user_margin, self._w,
                                  self._b),
            meta={
                "world": int(self.n_devices),
                "grower": "gblinear",
                "hist_quant": "none",
                "sampling": "none",
                "n_outputs": int(self.n_outputs),
            },
        )

    def build_programs(self) -> None:
        """Force-build the coordinate-update program (jit is lazy — nothing
        compiles); under ``progreg.capture`` this registers it for the jaxpr
        verifier."""
        if self._round_fn is None:
            self._round_fn = self._build_round_fn()

    def step(self, i: int, gh_custom=None) -> Dict[str, Dict[str, float]]:
        if gh_custom is not None:
            raise NotImplementedError(
                "custom objectives with booster='gblinear' are not supported."
            )
        if self._round_fn is None:
            self._round_fn = self._build_round_fn()
        # RXGB_STRICT arms only after the first (compiling) dispatch, same
        # warm-path contract as TpuEngine's round steps
        with strict_transfer_guard(active=self._warm):
            self._w, self._b = self._round_fn(
                self._x, self._label, self._valid, self._weight,
                self._user_margin, self._w, self._b,
            )
        self._warm = True
        self._rounds_done += 1
        return self._eval_metrics()

    def _eval_metrics(self) -> Dict[str, Dict[str, float]]:
        w = np.asarray(self._w)
        b = np.asarray(self._b)
        out: Dict[str, Dict[str, float]] = {}
        for es in self.evals:
            margin = es.x @ w + b[None, :] + self.base_margin0
            if es.base_margin is not None:
                margin = margin + np.asarray(
                    es.base_margin, np.float32).reshape(len(es.x), -1)
            vals = {}
            for name in self.metric_names:
                vals[name] = compute_metric(
                    name, margin, es.label_np, es.weight_np,
                    huber_slope=self.params.huber_slope,
                    quantile_alpha=(
                        tuple(self.params.quantile_alpha)
                        if isinstance(self.params.quantile_alpha,
                                      (list, tuple))
                        else (self.params.quantile_alpha,)
                    ),
                )
            out[es.name] = vals
        return out

    # ------------------------------------------------------------------
    def get_margins_local(self, es=None) -> np.ndarray:
        w, b = np.asarray(self._w), np.asarray(self._b)
        if es is None or es.is_train:
            x = np.asarray(jax.device_get(self._x))[: self.n_rows]
            bm = self._user_margin_np  # training includes the user margin
        else:
            x, bm = es.x, es.base_margin
        margin = x @ w + b[None, :] + self.base_margin0
        if bm is not None:
            margin = margin + np.asarray(bm, np.float32).reshape(-1, margin.shape[1])
        return margin

    def combine_host_scalar(self, value, es=None, metric=None) -> float:
        return float(value)  # single-process (enforced in __init__)

    def get_booster(self, readback=None) -> RayLinearBooster:
        """``readback`` as ``TpuEngine.get_booster``'s: the weights' bytes,
        read in one batched transfer."""
        w, b = jax.device_get((self._w, self._b))
        if readback is not None:
            readback.update(bytes=w.nbytes + b.nbytes, transfers=1)
        return RayLinearBooster(
            w, b, self.params,
            self.base_score, feature_names=self.feature_names,
            rounds=self._rounds_done,
        )

"""Compiled-predictor cache: zero-recompile steady-state inference.

The batch path (``ops/predict.py``) jits one program per input shape; online
traffic has arbitrary batch sizes, so naively each new size would trigger a
fresh XLA compile — seconds of tail latency. This layer makes the shape
space finite: every batch is padded up to a power-of-two **bucket** (rounded
to a mesh multiple), and the compiled program for a given
``(model signature, bucket, output kind, mesh)`` key is built exactly once
and cached process-wide. The tree walk is row-independent, so the padding
rows change nothing about the real rows' routing.

Parity contract (pinned by ``tests/test_serve.py`` and
``tests/test_serve_pool.py``): the SAME compiled program on the same input
returns the same bits — every request served by one (model, bucket, kind,
layout, mesh) program is reproducible, and replicas sharing that program
agree bitwise. Two DIFFERENT programs over one forest — another bucket,
the node-array layout, another device count, the batch ``predict()`` path —
route every row to the same leaves (``leaf`` outputs are integers and agree
exactly) but may sum the trees in another order: the compiler picks the
reduction order per shape and sharding, on any backend. Their float outputs
agree to within :data:`PARITY_ULPS` float32 ulps (see :func:`parity_atol`).

Programs are keyed by the booster's *structural* signature
(``RayXGBoostBooster.signature()``), not its identity: hot-swapping to a
same-shaped model (the common retrain-and-swap loop) reuses every compiled
program, so a swap costs zero recompiles. The forest rides in as a plain
jit argument.

Compile tracking: each program body bumps a module counter at Python trace
time (the body only executes when jax traces, i.e. compiles) — the counter
the ``/metrics`` ``recompile_count`` field and the zero-recompile test read.
"""

import threading
from typing import Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from xgboost_ray_tpu import progreg
from xgboost_ray_tpu.constants import AXIS_ACTORS
from xgboost_ray_tpu.ops import node_array as node_array_ops
from xgboost_ray_tpu.ops import predict as predict_ops
from xgboost_ray_tpu.ops.grow import Tree, map_tree

#: output kinds this layer can serve (each has a batch-path ``predict()``
#: flag it is checked against, see the parity contract above)
KINDS = ("value", "margin", "leaf", "contribs")

#: forest layouts the predictor can walk: the padded heap (per-tree
#: depth-first walk, the batch path's layout) and the FIL-style breadth-
#: first node-array (level-synchronous gathers; see ops/node_array.py).
#: Both route rows identically; node_array targets lower p99.
LAYOUTS = ("heap", "node_array")

#: cross-program float parity bound, in float32 ulps at the magnitude of
#: the largest output (at least 1.0). Reordering a T-term float32 sum moves
#: it by at most ~T ulps of the summed magnitudes; 4 covers the forests of a
#: dozen trees the tests serve (largest seen: 2). Computing in a narrower
#: type than float32 would miss it by orders of magnitude.
PARITY_ULPS = 4


def parity_atol(ref) -> float:
    """Absolute tolerance of the cross-program parity contract for outputs
    compared against ``ref``."""
    scale = max(1.0, float(np.max(np.abs(ref))))
    return PARITY_ULPS * float(np.spacing(np.float32(scale)))

_lock = threading.Lock()
_COMPILE_COUNT = 0
# program cache: (signature, dev ids, kind) -> jitted callable; jax's own
# jit cache then holds one executable per bucket shape underneath it.
# Bounded FIFO like booster._SPMD_MARGIN_FNS; old models' programs age out.
_PROGRAMS: Dict[tuple, callable] = {}
_PROGRAMS_MAX = 128


def compile_count() -> int:
    """Total serve-program traces (== XLA compiles) in this process."""
    return _COMPILE_COUNT


def _count_trace() -> None:
    global _COMPILE_COUNT
    with _lock:
        _COMPILE_COUNT += 1


def bucket_rows(n: int, min_bucket: int, n_dev: int) -> int:
    """Smallest bucket >= max(n, min_bucket) from the ladder of powers of
    two rounded up to a multiple of ``n_dev`` (so the row shard divides
    evenly over the mesh). IDEMPOTENT — ``bucket_rows(bucket_rows(n)) ==
    bucket_rows(n)`` — which is what makes the warmup able to enumerate
    exactly the buckets live requests will hit on non-power-of-two device
    counts."""
    n_dev = max(int(n_dev), 1)
    rows = max(int(n), int(min_bucket), n_dev, 1)
    # start one power of two below rows: its n_dev-rounded value may
    # already cover rows (e.g. rows=17, n_dev=3 -> 16 rounds to 18)
    p = 1 << max((rows - 1).bit_length() - 1, 0)
    while True:
        b = -(-p // n_dev) * n_dev
        if b >= rows:
            return b
        p *= 2


def _cached_program(key, build):
    with _lock:
        fn = _PROGRAMS.get(key)
    if fn is not None:
        return fn
    fn = build()
    with _lock:
        if len(_PROGRAMS) >= _PROGRAMS_MAX:
            _PROGRAMS.pop(next(iter(_PROGRAMS)))
        _PROGRAMS[key] = fn
    return fn


class CompiledPredictor:
    """Padded-bucket inference facade over one booster + device set.

    Thin and stateless apart from device-resident model arrays: the
    program cache is module-level (shared across instances, so hot-swaps
    between same-shaped models hit warm programs), and every ``predict``
    call pads to a bucket, runs the cached program, and slices the real
    rows back out.
    """

    def __init__(self, booster, devices=None, min_bucket: int = 8,
                 layout: str = "heap"):
        sig = getattr(booster, "signature", None)
        if sig is None:
            raise TypeError(
                f"serving requires a tree booster (RayXGBoostBooster); got "
                f"{type(booster).__name__} — gblinear models have no padded "
                f"forest walk to compile."
            )
        if layout not in LAYOUTS:
            raise ValueError(
                f"unknown forest layout {layout!r}; one of {LAYOUTS}"
            )
        self.booster = booster
        self.devices = list(devices) if devices else [jax.devices()[0]]
        self.min_bucket = int(min_bucket)
        self.layout = layout
        self.signature = booster.signature()
        self._key_base = (
            self.signature,
            tuple(getattr(d, "id", i) for i, d in enumerate(self.devices)),
        )
        self.m0 = booster.base_score_margin_np()
        n_dev = len(self.devices)
        if n_dev > 1:
            from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

            self._mesh = Mesh(np.asarray(self.devices), (AXIS_ACTORS,))
            self._repl = NamedSharding(self._mesh, P())
            self._rows = NamedSharding(self._mesh, P(AXIS_ACTORS))
            put = lambda a: jax.device_put(a, self._repl)  # noqa: E731
        else:
            dev = self.devices[0]
            put = lambda a: jax.device_put(a, dev)  # noqa: E731
        self.forest_dev = map_tree(lambda f: put(np.asarray(f)), booster.forest)
        if layout == "node_array":
            # the level-major permutation of the same heap; forest_dev is
            # kept alongside because contribs stays on the heap program
            na_host = node_array_ops.forest_to_node_array(
                booster.forest, booster.max_depth
            )
            self.na_dev = node_array_ops.NodeForest(*[put(f) for f in na_host])
        else:
            self.na_dev = None
        self.has_tw = booster.tree_weights is not None
        self.tw_dev = put(
            np.asarray(booster.tree_weights, np.float32)
            if self.has_tw else np.zeros(0, np.float32)
        )

    # -- program builders --------------------------------------------------

    def _kernel_kwargs(self):
        b = self.booster
        return dict(
            max_depth=b.max_depth,
            num_outputs=b.num_outputs,
            num_parallel_tree=b.params.num_parallel_tree,
            ntree_limit=0,
            cat_features=b.cat_features,
        )

    def _uses_node_array(self, kind: str) -> bool:
        # contribs needs base_weight/cover path statistics the node array
        # does not carry — it routes to the (shared) heap program, so a
        # node-array predictor's contribs hit the same cache entry a heap
        # predictor's do: same program, same bits
        return self.layout == "node_array" and kind != "contribs"

    def _program(self, kind: str):
        # "value" and "margin" trace the identical program (they differ only
        # in host-side _finalize) — share one cache entry so warming either
        # warms both and neither ever compiles twice
        prog_kind = "margin" if kind == "value" else kind
        if self._uses_node_array(kind):
            key = self._key_base + (prog_kind, "node_array")
            return _cached_program(
                key, lambda: self._build_program_na(prog_kind)
            )
        key = self._key_base + (prog_kind,)
        return _cached_program(key, lambda: self._build_program(prog_kind))

    def _build_program(self, kind: str):
        kw = self._kernel_kwargs()
        has_tw = self.has_tw
        n_dev = len(self.devices)

        if kind in ("value", "margin"):
            def body(forest, tw, x, base):
                _count_trace()
                return predict_ops.predict_margin(
                    forest, x, base, tree_weights=tw if has_tw else None, **kw
                )

            if n_dev > 1:
                from jax.sharding import PartitionSpec as P

                return jax.jit(
                    jax.shard_map(
                        body, mesh=self._mesh,
                        in_specs=(P(), P(), P(AXIS_ACTORS), P(AXIS_ACTORS)),
                        out_specs=P(AXIS_ACTORS),
                        check_vma=False,
                    )
                )
            return jax.jit(body)

        if kind == "leaf":
            max_depth = kw["max_depth"]
            cat_features = kw["cat_features"]

            def body(forest, tw, x, base):
                _count_trace()
                return predict_ops.predict_leaf_index(
                    forest, x, max_depth, cat_features=cat_features
                )

            # row sharding propagates through the vmap'd walk (GSPMD); no
            # manual shard_map needed for an int gather with no collectives
            return jax.jit(body)

        if kind == "contribs":
            def body(forest, tw, x, base):
                _count_trace()
                return predict_ops.predict_contribs_exact(
                    forest, x, tree_weights=tw if has_tw else None, **kw
                )

            # like booster.predict_special_spmd: the scan-carrying SHAP
            # kernel parallelizes over rows via sharding propagation from
            # the device_put inputs, not an explicit shard_map
            return jax.jit(body)

        raise ValueError(f"unknown serve output kind {kind!r}; one of {KINDS}")

    def _build_program_na(self, kind: str):
        """Node-array twin of :meth:`_build_program`: same calling
        convention (model, tw, x, base) with the flat :class:`NodeForest`
        in the model slot, same sharding story as the heap programs."""
        kw = self._kernel_kwargs()
        has_tw = self.has_tw
        n_dev = len(self.devices)

        if kind == "margin":
            def body(na, tw, x, base):
                _count_trace()
                return node_array_ops.predict_margin_na(
                    na, x, base, tree_weights=tw if has_tw else None, **kw
                )

            if n_dev > 1:
                from jax.sharding import PartitionSpec as P

                return jax.jit(
                    jax.shard_map(
                        body, mesh=self._mesh,
                        in_specs=(P(), P(), P(AXIS_ACTORS), P(AXIS_ACTORS)),
                        out_specs=P(AXIS_ACTORS),
                        check_vma=False,
                    )
                )
            return jax.jit(body)

        if kind == "leaf":
            max_depth = kw["max_depth"]
            cat_features = kw["cat_features"]

            def body(na, tw, x, base):
                _count_trace()
                return node_array_ops.predict_leaf_index_na(
                    na, x, max_depth, cat_features=cat_features
                )

            return jax.jit(body)

        raise ValueError(
            f"no node-array program for kind {kind!r} (contribs routes to "
            f"the heap program)"
        )

    # -- execution ---------------------------------------------------------

    def predict(self, x: np.ndarray, kind: str = "value") -> np.ndarray:
        """Serve one already-coerced [N, F] float32 batch. Pads to the
        bucket, runs the cached program, slices the N real rows back out and
        applies the same host-side finalization as the batch path."""
        out, _ = self.predict_with_bucket(x, kind)
        return out

    def predict_with_bucket(
        self, x: np.ndarray, kind: str = "value"
    ) -> Tuple[np.ndarray, int]:
        if kind not in KINDS:
            raise ValueError(
                f"unknown serve output kind {kind!r}; one of {KINDS}"
            )
        b = self.booster
        if kind == "contribs":
            # same guard as the batch path: a pre-node-stats model would
            # serve all-zero SHAP values with a 200 instead of erroring
            b._assert_node_stats()
        n = int(x.shape[0])
        n_dev = len(self.devices)
        bucket = bucket_rows(n, self.min_bucket, n_dev)
        xb = np.zeros((bucket, b.num_features), np.float32)
        xb[:n] = x
        base = np.full((bucket, b.num_outputs), self.m0, np.float32)
        if n_dev > 1:
            xb_dev = jax.device_put(xb, self._rows)
            base_dev = jax.device_put(base, self._rows)
        else:
            xb_dev = jax.device_put(xb, self.devices[0])
            base_dev = jax.device_put(base, self.devices[0])
        prog = self._program(kind)
        self._note_program(kind, bucket, prog, (xb_dev, base_dev))
        model_dev = (
            self.na_dev if self._uses_node_array(kind) else self.forest_dev
        )
        res = prog(model_dev, self.tw_dev, xb_dev, base_dev)
        out = np.asarray(res)[:n]
        return self._finalize(out, kind), bucket

    def _note_program(self, kind: str, bucket: int, prog, row_args) -> None:
        """Register the bucket's program signature with the progreg registry
        (no-op unless capture is on — the serve hot path pays one early
        return). ``row_args`` are the (x, base) batch arrays; only shapes
        and dtypes are read, so host arrays work as well as device ones."""
        if not progreg.enabled():
            return
        prog_kind = "margin" if kind == "value" else kind
        meta = {
            "world": len(self.devices),
            "bucket": int(bucket),
            "grower": "serve",
            "hist_quant": "none",
            "sampling": "none",
        }
        if self._uses_node_array(kind):
            # own meta coordinate: node-array programs form their own
            # verify identity groups instead of colliding with the heap
            # walk's (same name, different jaxpr)
            meta["layout"] = "node_array"
            model_dev = self.na_dev
        else:
            model_dev = self.forest_dev
        progreg.note_jit_call(
            f"serve.predict_{prog_kind}",
            prog,
            (model_dev, self.tw_dev) + tuple(row_args),
            meta=meta,
        )

    def register_programs(self, kinds=KINDS, batch: int = 8) -> None:
        """Build + register the bucket programs for ``batch`` rows WITHOUT
        executing (jit stays lazy): the jaxpr verifier's entry point. Uses
        the exact argument assembly of :meth:`predict_with_bucket`."""
        b = self.booster
        n_dev = len(self.devices)
        bucket = bucket_rows(batch, self.min_bucket, n_dev)
        xb = np.zeros((bucket, b.num_features), np.float32)
        base = np.full((bucket, b.num_outputs), self.m0, np.float32)
        for kind in kinds:
            self._note_program(kind, bucket, self._program(kind), (xb, base))

    def _finalize(self, out: np.ndarray, kind: str) -> np.ndarray:
        b = self.booster
        if kind == "margin":
            return out[:, 0] if b.num_outputs == 1 else out
        if kind == "value":
            # the batch path transforms eagerly on host (outside the jitted
            # walk) — do exactly the same, so equal margins give equal values
            return b._margin_to_prediction(out, output_margin=False)
        if kind == "leaf":
            return out
        # contribs: bias column carries the base-score margin, class axis
        # squeezed for single-output models (shared batch-path helper, which
        # mutates in place — the device view is read-only, so copy)
        return b._finalize_contribs(np.array(out), "contribs", None)

    def warmup(self, kinds=("value",), max_batch: int = 256) -> int:
        """Compile every bucket in [min_bucket, bucket(max_batch)] for the
        given kinds; returns the number of programs compiled now. After
        warmup, requests up to ``max_batch`` rows never compile."""
        before = compile_count()
        n_dev = len(self.devices)
        top = bucket_rows(max_batch, self.min_bucket, n_dev)
        dummy_cols = self.booster.num_features
        n = 1
        while True:
            # enumerate successive distinct buckets: bucket_rows is an
            # idempotent monotone step function, so bucket+1 jumps to the
            # next rung of the ladder
            bucket = bucket_rows(n, self.min_bucket, n_dev)
            x = np.zeros((bucket, dummy_cols), np.float32)
            for kind in kinds:
                self.predict(x, kind)
            if bucket >= top:
                break
            n = bucket + 1
        return compile_count() - before

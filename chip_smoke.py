"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the main path once, through the entry points a user
calls — ``train()``, ``predict()``, ``serve.create_server()`` — at the full
width of the HIGGS-shaped model (``BASELINE.json`` config 2: binary:logistic,
28 features, ``max_bin=256``, ``max_depth=6``, ``tree_method="tpu_hist"``,
every ``auto`` option left at ``auto``); rows are 1,000,000 per local device
and rounds are cut to 20 + 5. Data and therefore the model come from a seed.

    python chip_smoke.py                          # needs a TPU, else exit 4
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse-cpu --rows 20000

Without ``--rehearse-cpu`` a platform other than ``tpu`` is a failure,
reported before anything compiles. The script sets no ``JAX_PLATFORMS`` or
``XLA_FLAGS`` itself: the rehearsal runs on whatever CPU devices the
environment gives JAX (add ``XLA_FLAGS=--xla_force_host_platform_device_count=4``
to rehearse the multi-device steps). A chip belongs to one process, so
nothing here spawns a process; the serve step's HTTP server is a thread.

Every step is fatal. The last line of stdout is one JSON object with exactly
the keys the chip check reads — ``{"ok": true, "device": {"platform": "tpu",
"kind": ..., "count": ...}}``, the device as JAX reports it. The line before
it, ``[smoke] summary {...}``, carries per-step pass/fail and seconds, peak
HBM and every observation. Every time in it is a smoke observation on the
named device, not a benchmark number.
"""

import argparse
import json
import os
import sys
import tempfile
import time
import traceback
import urllib.request

import numpy as np

PARAMS = {
    "objective": "binary:logistic",
    "eval_metric": ["logloss"],
    "max_depth": 6,
    "eta": 0.1,
    "max_bin": 256,
    "tree_method": "tpu_hist",
}
N_FEATURES = 28
ROWS_PER_DEVICE = 1_000_000
PREDICT_ROWS = 100_000
#: what hist_impl / hist_precision "auto" must resolve to, by platform
EXPECTED_AUTO = {"tpu": ("onehot", "fast"), "cpu": ("scatter", "highest")}

# Tolerances. Two differently-compiled programs over one forest may sum the
# trees in another order, so probabilities (in [0, 1]) are compared to an
# absolute 1e-5 — some 80 float32 ulps at 1.0, and three orders below what a
# bf16 pass anywhere in the walk would cost. SHAP contributions accumulate
# more terms per output: 1e-4.
VALUE_ATOL = 1e-5
CONTRIBS_ATOL = 1e-4
# bf16 keeps 8 significant bits, so rounding gh to bf16 moves each element by
# at most 2^-8 of its magnitude; a bucket's sum then moves by at most 2^-8
# of the bucket's sum of |gh| (reached by a bucket of one row; big buckets
# average far below it). 2^-7 leaves a factor two for f32 accumulation.
HIST_FAST_REL = 2.0 ** -7
# "highest" must be f32-exact up to accumulation order: 2^-16 of the
# bucket's sum of |gh| is 512x tighter than the bf16 bound above
HIST_HIGHEST_REL = 2.0 ** -16


def _check(cond, message):
    if not cond:
        raise AssertionError(message)


def _post(url, path, doc):
    req = urllib.request.Request(
        url + path, json.dumps(doc).encode("utf-8"),
        {"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=600.0) as r:
        return json.loads(r.read())


def _get(url, path):
    with urllib.request.urlopen(url + path, timeout=60.0) as r:
        return json.loads(r.read())


def _numpy_margin(bst, x):
    """Plain numpy walk of the padded-heap forest — the host reference the
    chip's predict programs are checked against on a small input."""
    forest = [np.asarray(f) for f in bst.forest]
    feature, _, threshold, default_left, is_leaf, value = forest[:6]
    rows = np.arange(x.shape[0])
    margin = np.full(x.shape[0], bst.base_score_margin_np(), np.float32)
    for t in range(feature.shape[0]):
        idx = np.zeros(x.shape[0], np.int64)
        for _ in range(bst.max_depth):
            xv = x[rows, np.clip(feature[t, idx], 0, x.shape[1] - 1)]
            right = np.where(
                np.isnan(xv), ~default_left[t, idx], xv >= threshold[t, idx]
            )
            idx = np.where(is_leaf[t, idx], idx, 2 * idx + 1 + right)
        margin += value[t, idx]
    return margin


class Smoke:
    def __init__(self, rows_per_device, rehearse_cpu):
        self.rows_per_device = rows_per_device
        self.rehearse_cpu = rehearse_cpu
        self.steps = {}
        self.obs = {}
        self.device = None

    # -- steps ------------------------------------------------------------

    def require_device(self):
        """Place the compile cache, then require a chip before any compile.
        Not a step: without the package (ImportError) or without a chip
        (exit 4) the script ends here and prints no result line."""
        from xgboost_ray_tpu.util import device_record, place_compile_cache

        cache_dir = place_compile_cache()
        rec = device_record()
        want = "cpu" if self.rehearse_cpu else "tpu"
        if rec["platform"] != want:
            print(
                f"chip_smoke: JAX found platform {rec['platform']!r} "
                f"({rec['device_kind']}), need {want!r}. Without "
                f"--rehearse-cpu this script only passes on a TPU; the "
                f"rehearsal wants JAX_PLATFORMS=cpu in the environment.",
                file=sys.stderr,
            )
            sys.exit(4)
        print(f"[smoke] platform={rec['platform']} "
              f"device_kind={rec['device_kind']} "
              f"device_count={rec['device_count']} compile_cache={cache_dir}",
              flush=True)
        # the result line's device object, in the chip check's key names
        self.device = {"platform": rec["platform"],
                       "kind": rec["device_kind"],
                       "count": rec["device_count"]}
        import jax

        self.n_dev = len(jax.local_devices())
        cache_entries = (
            len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
        )
        self.obs["device"] = {"compile_cache": cache_dir,
                              "compile_cache_entries_at_start": cache_entries}

    def step_data(self):
        from bench import make_higgs_like

        rows = self.rows_per_device * self.n_dev
        self.x, self.y = make_higgs_like(rows, N_FEATURES, seed=0)
        return {"rows": rows, "features": N_FEATURES}

    def _train(self, rounds, **kwargs):
        from xgboost_ray_tpu import RayDMatrix, RayParams, train

        if not hasattr(self, "dtrain"):
            self.dtrain = RayDMatrix(self.x, self.y)
        evals_result, extra = {}, {}
        t0 = time.perf_counter()
        bst = train(
            PARAMS, self.dtrain, rounds,
            evals=[(self.dtrain, "train")],
            evals_result=evals_result,
            additional_results=extra,
            ray_params=RayParams(num_actors=self.n_dev,
                                 checkpoint_frequency=0),
            **kwargs,
        )
        wall = time.perf_counter() - t0
        logloss = evals_result["train"]["logloss"]
        _check(bst.num_boosted_rounds() == rounds,
               f"{bst.num_boosted_rounds()} rounds boosted, wanted {rounds}")
        _check(np.all(np.isfinite(logloss)), f"non-finite logloss {logloss}")
        _check(logloss[-1] < logloss[0],
               f"logloss did not fall: {logloss[0]} -> {logloss[-1]}")
        return bst, logloss, extra, wall

    def step_train_batched(self):
        """20 rounds, no callbacks: the fused ``step_many`` path, two scan
        chunks of 10 — the second one compile-free."""
        bst, logloss, extra, wall = self._train(20)
        chunks = extra["chunk_times_s"]
        _check([c["rounds"] for c in chunks] == [10, 10],
               f"expected two fused chunks of 10 rounds, got {chunks}")
        _check(logloss[-1] < 0.67,
               f"final train logloss {logloss[-1]} not clearly under 0.693")
        rec = extra["device"]
        _check(rec["platform"] == self.device["platform"]
               and rec["device_kind"] == self.device["kind"]
               and rec["device_count"] == self.device["count"],
               f"train() record {rec} disagrees with {self.device}")
        want = EXPECTED_AUTO[self.device["platform"]]
        _check((rec["hist_impl"], rec["hist_precision"]) == want,
               f"auto resolved to {rec['hist_impl']}/{rec['hist_precision']},"
               f" this platform's defaults are {want}")
        self.bst, self.train_record = bst, extra
        chunk_s = [c["seconds"] for c in chunks]
        return {
            "hist_impl": rec["hist_impl"],
            "hist_precision": rec["hist_precision"],
            "logloss_first": round(logloss[0], 6),
            "logloss_last": round(logloss[-1], 6),
            "train_wall_s": round(wall, 3),
            # everything before the first round: ingest, sketch, bin, upload
            "setup_s": round(wall - sum(chunk_s), 3),
            "chunk1_s_with_compile": round(chunk_s[0], 3),
            "chunk2_s": round(chunk_s[1], 3),
        }

    def step_mesh(self):
        """The training mesh covers every local device, each holds rows, and
        with more than one device the histogram merge moved bytes."""
        rec = self.train_record["device"]
        rows = rec["rows_per_device"]
        n_mesh = int(np.prod(list(rec["mesh_shape"].values())))
        _check(n_mesh == self.n_dev == len(rows),
               f"mesh {rec['mesh_shape']} over {len(rows)} devices, "
               f"{self.n_dev} local devices")
        _check(all(v > 0 for v in rows.values()),
               f"a device holds no rows: {rows}")
        _check(sum(rows.values()) == self.x.shape[0],
               f"devices hold {sum(rows.values())} of {self.x.shape[0]} rows")
        ar_bytes = self.train_record.get("hist_allreduce_bytes_per_round")
        if self.n_dev > 1:
            _check(ar_bytes and ar_bytes > 0,
                   f"hist_allreduce_bytes_per_round={ar_bytes} on "
                   f"{self.n_dev} devices")
        return {"mesh_shape": rec["mesh_shape"], "rows_per_device": rows,
                "hist_allreduce_bytes_per_round": ar_bytes}

    def step_train_per_round(self):
        """5 rounds with early stopping armed: forces the per-round ``step``
        program instead of the fused scan."""
        _, logloss, extra, wall = self._train(5, early_stopping_rounds=3)
        chunks = extra["chunk_times_s"]
        _check([c["rounds"] for c in chunks] == [1] * 5,
               f"expected five per-round dispatches, got {chunks}")
        return {"logloss_last": round(logloss[-1], 6),
                "train_wall_s": round(wall, 3),
                "round_s": [round(c["seconds"], 3) for c in chunks]}

    def step_predict(self):
        """save/load, public ``predict()`` over a RayDMatrix on every
        device, against ``bst.predict`` and a numpy walk of the forest."""
        from xgboost_ray_tpu import (
            RayDMatrix, RayParams, RayXGBoostBooster, predict,
        )

        q = self.x[:PREDICT_ROWS]
        with tempfile.TemporaryDirectory() as tmp:
            path = os.path.join(tmp, "smoke_model.json")
            self.bst.save_model(path)
            loaded = RayXGBoostBooster.load_model(path)
        got = predict(loaded, RayDMatrix(q),
                      ray_params=RayParams(num_actors=self.n_dev))
        ref = self.bst.predict(q)
        _check(got.shape == ref.shape == (q.shape[0],),
               f"predict shapes {got.shape} vs {ref.shape}")
        _check(np.all(np.isfinite(got)), "non-finite predictions")
        diff = float(np.max(np.abs(got - ref)))
        _check(diff <= VALUE_ATOL,
               f"predict() vs bst.predict max abs diff {diff} > {VALUE_ATOL}")
        small = q[:1000]
        host = 1.0 / (1.0 + np.exp(-_numpy_margin(self.bst, small)))
        host_diff = float(np.max(np.abs(ref[:1000] - host)))
        _check(host_diff <= VALUE_ATOL,
               f"bst.predict vs numpy forest walk max abs diff {host_diff} "
               f"> {VALUE_ATOL}")
        return {"rows": int(q.shape[0]),
                "max_abs_diff_vs_bst_predict": diff,
                "max_abs_diff_vs_numpy_walk": host_diff}

    def step_serve(self):
        """``create_server``: /predict at three sizes + one contribs request
        against ``bst.predict``; the repeat must not recompile."""
        from xgboost_ray_tpu import serve

        sizes = (1, 37, 1000)
        handle = serve.create_server(self.bst)
        try:
            diffs = {}
            for attempt in range(2):
                for n in sizes:
                    q = self.x[:n]
                    doc = _post(handle.url, "/predict",
                                {"data": q.tolist(), "kind": "value"})
                    got = np.asarray(doc["predictions"], np.float32)
                    diff = float(np.max(np.abs(got - self.bst.predict(q))))
                    _check(got.shape == (n,) and diff <= VALUE_ATOL,
                           f"served value n={n}: shape {got.shape}, max "
                           f"abs diff {diff} > {VALUE_ATOL}")
                    diffs[f"value_{n}"] = diff
                if attempt == 0:
                    q = self.x[:37]
                    doc = _post(handle.url, "/predict",
                                {"data": q.tolist(), "kind": "contribs"})
                    got = np.asarray(doc["predictions"], np.float32)
                    ref = self.bst.predict(q, pred_contribs=True)
                    diff = float(np.max(np.abs(got - ref)))
                    _check(got.shape == ref.shape == (37, N_FEATURES + 1)
                           and diff <= CONTRIBS_ATOL,
                           f"served contribs: shape {got.shape}, max abs "
                           f"diff {diff} > {CONTRIBS_ATOL}")
                    diffs["contribs_37"] = diff
                    compiles = _get(handle.url, "/metrics")["recompile_count"]
            after = _get(handle.url, "/metrics")["recompile_count"]
            _check(after == compiles,
                   f"recompile_count moved {compiles} -> {after} on "
                   f"repeated request sizes")
        finally:
            handle.shutdown()
        return {"max_abs_diff": diffs, "recompile_count": after}

    def step_histogram(self):
        """One level's histogram by the build the engine resolved on a chip
        (``onehot``) at ``fast`` and ``highest``, against ``hist_scatter``
        in f32 on the same data, on this device."""
        import jax
        import jax.numpy as jnp

        from xgboost_ray_tpu.ops.histogram import build_histogram, hist_scatter

        n = self.rows_per_device
        nbt = PARAMS["max_bin"] + 1
        rng = np.random.RandomState(1)
        bins = jnp.asarray(
            rng.randint(0, PARAMS["max_bin"], size=(n, N_FEATURES),
                        dtype=np.uint8))
        gh_np = np.stack(
            [rng.standard_normal(n) * 0.5, rng.uniform(0.0, 0.25, n)], axis=1
        ).astype(np.float32)
        gh = jnp.asarray(gh_np)
        out = {}
        # a narrow level (4 matmul columns) and a wide one (2,048 columns:
        # the deepest level of max_depth 12 under sibling subtraction)
        for n_nodes in (2, 1024):
            pos = jnp.asarray(rng.randint(0, n_nodes, n).astype(np.int32))
            scatter = jax.jit(
                lambda b, g, p, nn=n_nodes: hist_scatter(b, g, p, nn, nbt))
            ref = np.asarray(scatter(bins, gh, pos))
            mass = np.array(scatter(bins, jnp.abs(gh), pos))
            # the missing bucket is rebuilt by subtraction from the node
            # total, so it carries the rounding of the whole (node, feature)
            mass[:, :, -1, :] = mass.sum(axis=2)
            for precision, rel in (("fast", HIST_FAST_REL),
                                   ("highest", HIST_HIGHEST_REL)):
                got = np.asarray(jax.jit(
                    lambda b, g, p, pr=precision, nn=n_nodes: build_histogram(
                        b, g, p, nn, nbt, impl="onehot", precision=pr)
                )(bins, gh, pos))
                _check(got.shape == ref.shape == (n_nodes, N_FEATURES, nbt, 2)
                       and np.all(np.isfinite(got)),
                       f"onehot/{precision} histogram shape {got.shape}")
                err = np.abs(got - ref)
                worst = float(np.max(err / np.maximum(mass, 1e-30)))
                _check(worst <= rel,
                       f"onehot/{precision} n_nodes={n_nodes}: error is "
                       f"{worst:.3e} of the bucket's |gh| mass, bound "
                       f"{rel:.3e}")
                out[f"n{n_nodes}_{precision}"] = {
                    "max_abs_err": float(err.max()),
                    "max_err_over_mass": worst,
                    "bound": rel,
                }
        return out

    # -- driver -----------------------------------------------------------

    STEPS = ("data", "train_batched", "mesh", "train_per_round", "predict",
             "serve", "histogram")

    def run(self) -> int:
        self.require_device()
        failed = None
        for name in self.STEPS:
            t0 = time.perf_counter()
            try:
                self.obs[name] = getattr(self, f"step_{name}")()
            except Exception:  # noqa: BLE001 - reported, then fatal
                traceback.print_exc()
                failed = name
            seconds = round(time.perf_counter() - t0, 3)
            self.steps[name] = {"ok": failed is None, "seconds": seconds}
            print(f"[smoke] {name}: {'ok' if failed is None else 'FAILED'} "
                  f"in {seconds}s {json.dumps(self.obs.get(name))}",
                  flush=True)
            if failed:
                break
        # the record for people and for CHANGES.md: per-step pass/fail and
        # seconds, peak HBM, every observation
        print("[smoke] summary " + json.dumps({
            "failed_step": failed,
            "steps": self.steps,
            "peak_hbm_bytes": self._peak_hbm(),
            "note": (f"times are smoke observations on "
                     f"{self.device['kind']}, "
                     f"not benchmark numbers"),
            "observations": self.obs,
        }), flush=True)
        # the chip check's result line: exactly these keys, and the last line
        print(json.dumps({"ok": failed is None, "device": self.device}),
              flush=True)
        return 0 if failed is None else 1

    def _peak_hbm(self):
        """Largest ``peak_bytes_in_use`` over the local devices, or None
        where the backend reports no memory statistics (CPU)."""
        import jax

        peaks = [
            (d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.local_devices()
        ]
        peaks = [p for p in peaks if p is not None]
        return max(peaks) if peaks else None


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--rehearse-cpu", action="store_true",
        help="run the same steps on the CPU backend (JAX_PLATFORMS=cpu in "
             "the environment); the result line then says platform cpu")
    parser.add_argument(
        "--rows", type=int, default=ROWS_PER_DEVICE,
        help="rows per local device (default 1,000,000)")
    args = parser.parse_args(argv)
    return Smoke(args.rows, args.rehearse_cpu).run()


if __name__ == "__main__":
    sys.exit(main())

"""Compile-and-step sweep of the non-default program families on the chip.

``chip_smoke.py`` proves the DEFAULT path; this records, for each option
that selects another compiled program, whether it compiles and steps on the
device JAX finds: two rounds each at full widths (28 features x 256 bins x
depth 6 unless the row says otherwise), 100,000 rows per device, one shared
compile cache. A failing row is a record (compiler's or runtime's own
message), not a gate — the exit code is 0 once every row has an outcome.

    python tools/chip_sweep.py                 # every row this host can run
    python tools/chip_sweep.py --rows lossguide,goss
    python tools/chip_sweep.py --north-star    # 11M x 28, 10 rounds, 1 chip

The parent process stays off JAX (a chip belongs to one process) and runs
the rows in ONE child; if the child dies (a compiler abort takes the process
with it) or stalls, the row in flight is recorded as crashed/hung and a new
child resumes with the next row. Results append to
``chiprun_out/chip_sweep.jsonl`` and print as a table; every record names
platform, device_kind and device count. Times are observations, not
benchmark numbers.
"""

import argparse
import json
import os
import subprocess
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)  # run as a script: sys.path[0] is tools/
from chip_smoke import PARAMS as BASE  # noqa: E402 - the smoke's config

_OUT = os.path.join(_ROOT, "chiprun_out", "chip_sweep.jsonl")
ROWS_PER_DEVICE = 100_000
ROUNDS = 2
#: a row that shows no outcome for this long is recorded as hung
ROW_TIMEOUT_S = 900.0

#: name -> (minimum device count, what the row changes)
ROWS = {
    "default": (1, "the chip_smoke configuration, for reference"),
    "lossguide": (1, "grow_policy=lossguide, max_leaves=255, max_depth=8"),
    "multiclass": (1, "multi:softprob, 7 classes x 54 features"),
    "rank_ndcg": (1, "rank:ndcg, 136 features, qid groups of ~120"),
    "gh_int8": (1, "gh_precision=int8"),
    "goss": (1, "sampling_method=gradient_based, top/other_rate=0.2"),
    "stream": (1, "RayDMatrix(stream=True, chunk_rows=25000)"),
    "lanes_k4": (1, "K=4 vmapped lanes (enable_lanes/step_vmapped)"),
    "hist_onehot": (1, "hist_impl=onehot"),
    "serve_node_array": (1, "serve layout=node_array, value+leaf+contribs"),
    "hist_quant_int8": (2, "hist_quant=int8, hist_quant_min_bytes=0"),
    "hist_quant_int8_block": (2, "hist_quant=int8_block, min_bytes=0"),
    "feature_parallel_2": (4, "feature_parallel=2 on a (devices/2, 2) mesh"),
}


# ---------------------------------------------------------------------------
# child: holds the chip, runs rows in order, one JSON record per row
# ---------------------------------------------------------------------------


def _train(params, x, y, n_dev, dm_kwargs=None, num_actors=None):
    from xgboost_ray_tpu import RayDMatrix, RayParams, train

    dtrain = RayDMatrix(x, y, **(dm_kwargs or {}))
    evals_result, extra = {}, {}
    bst = train(
        params, dtrain, ROUNDS, evals=[(dtrain, "train")],
        evals_result=evals_result, additional_results=extra,
        ray_params=RayParams(num_actors=num_actors or n_dev,
                             checkpoint_frequency=0),
    )
    metric = next(iter(evals_result["train"].values()))
    assert bst.num_boosted_rounds() == ROUNDS
    assert all(v == v and abs(v) != float("inf") for v in metric), metric
    dev = extra.get("device", {})
    return bst, {
        "metric": [round(float(v), 6) for v in metric],
        "hist_impl": dev.get("hist_impl"),
        "mesh_shape": dev.get("mesh_shape"),
        "allreduce_bytes": extra.get("hist_allreduce_bytes_per_round"),
    }


def _run_row(name, n_dev, rows_per_device):
    import numpy as np

    from bench import make_higgs_like

    n = rows_per_device * n_dev
    rng = np.random.RandomState(7)
    if name == "multiclass":
        x = rng.standard_normal((n, 54)).astype(np.float32)
        y = (np.digitize(x[:, 0] + 0.5 * x[:, 1], [-1.5, -0.8, -0.2, 0.3,
                                                   0.9, 1.6])
             ).astype(np.float32)
        bst, out = _train(
            dict(BASE, objective="multi:softprob", num_class=7,
                 eval_metric=["mlogloss"]), x, y, n_dev)
        # the multiclass margin is a [rows, trees] x [trees, classes]
        # matmul: check it against a per-class numpy sum of the leaf walk
        q = x[:2000]
        margin = bst.predict(q, output_margin=True)
        leaf = bst.predict(q, pred_leaf=True)
        value = np.asarray(bst.forest.value)
        ref = np.full_like(margin, bst.base_score_margin_np())
        for t in range(leaf.shape[1]):
            ref[:, t % 7] += value[t, leaf[:, t]]
        out["margin_max_abs_diff_vs_numpy"] = float(
            np.max(np.abs(margin - ref)))
        return out
    if name == "rank_ndcg":
        x = rng.standard_normal((n, 136)).astype(np.float32)
        rel = np.clip(np.round(x[:, 0] + 0.5 * x[:, 1] + 1.5), 0, 4)
        qid = np.arange(n) // 120
        _, out = _train(
            dict(BASE, objective="rank:ndcg", eval_metric=["ndcg@10"]),
            x, rel.astype(np.float32), n_dev, dm_kwargs={"qid": qid})
        return out
    x, y = make_higgs_like(n, 28, seed=0)
    if name == "default":
        return _train(BASE, x, y, n_dev)[1]
    if name == "lossguide":
        return _train(dict(BASE, grow_policy="lossguide", max_leaves=255,
                           max_depth=8), x, y, n_dev)[1]
    if name == "gh_int8":
        return _train(dict(BASE, gh_precision="int8"), x, y, n_dev)[1]
    if name == "goss":
        return _train(dict(BASE, sampling_method="gradient_based",
                           top_rate=0.2, other_rate=0.2), x, y, n_dev)[1]
    if name == "stream":
        return _train(BASE, x, y, n_dev,
                      dm_kwargs={"stream": True, "chunk_rows": 25_000})[1]
    if name == "hist_onehot":
        return _train(dict(BASE, hist_impl="onehot"), x, y, n_dev)[1]
    if name in ("hist_quant_int8", "hist_quant_int8_block"):
        return _train(dict(BASE, hist_quant=name[len("hist_quant_"):],
                           hist_quant_min_bytes=0), x, y, n_dev)[1]
    if name == "feature_parallel_2":
        return _train(dict(BASE, feature_parallel=2), x, y, n_dev,
                      num_actors=n_dev // 2)[1]
    if name == "lanes_k4":
        from xgboost_ray_tpu.engine import TpuEngine
        from xgboost_ray_tpu.params import vectorize_params

        lp = vectorize_params(
            [dict(BASE, eta=eta) for eta in (0.3, 0.1, 0.05, 0.02)])
        shards = [{"data": x[i::n_dev], "label": y[i::n_dev]}
                  for i in range(n_dev)]
        eng = TpuEngine(shards, lp.base, num_actors=n_dev,
                        evals=[(shards, "train")])
        eng.enable_lanes(lp)
        hist = [eng.step_vmapped(it) for it in range(ROUNDS)]
        last = [float(r["train"]["logloss"]) for r in hist[-1]]
        assert all(v == v for v in last), last
        return {"metric": [round(v, 6) for v in last],
                "hist_impl": eng.cfg.hist_impl}
    if name == "serve_node_array":
        from xgboost_ray_tpu import serve

        bst, out = _train(BASE, x, y, n_dev)
        q = x[:37]
        handle = serve.create_server(bst, layout="node_array")
        try:
            router = handle.batcher
            diffs = {}
            for kind, kw in (("value", {}), ("leaf", {"pred_leaf": True}),
                             ("contribs", {"pred_contribs": True})):
                got, _ = router.submit(q, kind)
                diffs[kind] = float(np.max(np.abs(
                    np.asarray(got, np.float64) - bst.predict(q, **kw))))
        finally:
            handle.shutdown()
        out["max_abs_diff_vs_bst_predict"] = diffs
        return out
    raise KeyError(name)


def _north_star():
    """ROADMAP P4: one attempt at 11M x 28, 10 rounds, on one chip."""
    import jax

    from bench import make_higgs_like

    t0 = time.perf_counter()
    x, y = make_higgs_like(11_000_000, 28, seed=0)
    gen_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    from xgboost_ray_tpu import RayDMatrix, RayParams, train

    extra, evals_result = {}, {}
    dtrain = RayDMatrix(x, y)
    train(BASE, dtrain, 10, evals=[(dtrain, "train")],
          evals_result=evals_result, additional_results=extra,
          ray_params=RayParams(num_actors=1, checkpoint_frequency=0))
    wall = time.perf_counter() - t0
    chunks = extra["chunk_times_s"]
    stats = jax.local_devices()[0].memory_stats() or {}
    return {
        "rows": 11_000_000, "rounds": 10,
        "logloss": [round(v, 6) for v in evals_result["train"]["logloss"]],
        "data_gen_s": round(gen_s, 2), "train_wall_s": round(wall, 2),
        "setup_s": round(wall - sum(c["seconds"] for c in chunks), 2),
        "chunk_s_with_compile": [c["seconds"] for c in chunks],
        "peak_hbm_bytes": stats.get("peak_bytes_in_use"),
    }


def child_main(names, rows_per_device):
    from xgboost_ray_tpu.util import device_record, place_compile_cache

    place_compile_cache()
    import jax

    device = device_record()
    n_dev = len(jax.local_devices())
    os.makedirs(os.path.dirname(_OUT), exist_ok=True)
    with open(_OUT, "a") as out:
        for name in names:
            rec = {"row": name, **device}
            out.write(json.dumps({"row": name, "status": "started"}) + "\n")
            out.flush()
            t0 = time.perf_counter()
            try:
                if name == "north_star":
                    rec.update(status="ran", **_north_star())
                elif n_dev < ROWS[name][0]:
                    rec.update(status="skipped",
                               message=f"needs {ROWS[name][0]} devices")
                else:
                    rec.update(status="ran",
                               **_run_row(name, n_dev, rows_per_device))
            except Exception as exc:  # noqa: BLE001 - the row's outcome
                rec.update(status="failed",
                           message=f"{type(exc).__name__}: {exc}"[:1500])
            rec["seconds"] = round(time.perf_counter() - t0, 2)
            out.write(json.dumps(rec) + "\n")
            out.flush()
    return 0


# ---------------------------------------------------------------------------
# parent: never imports jax; restarts the child past a crashed or hung row
# ---------------------------------------------------------------------------


def _read_records():
    if not os.path.exists(_OUT):
        return []
    with open(_OUT) as f:
        return [json.loads(line) for line in f if line.strip()]


def parent_main(names, rows_per_device):
    if os.path.exists(_OUT):
        os.remove(_OUT)
    os.makedirs(os.path.dirname(_OUT), exist_ok=True)
    todo = list(names)
    while todo:
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--child",
             "--rows", ",".join(todo),
             "--rows-per-device", str(rows_per_device)],
            cwd=_ROOT,
        )
        last_progress, seen = time.monotonic(), len(_read_records())
        hung = False
        while proc.poll() is None:
            time.sleep(2.0)
            n = len(_read_records())
            if n != seen:
                seen, last_progress = n, time.monotonic()
            elif time.monotonic() - last_progress > ROW_TIMEOUT_S:
                hung = True
                proc.kill()
                proc.wait()
        recs = _read_records()
        done = {r["row"] for r in recs if r.get("status") != "started"}
        todo = [n for n in todo if n not in done]
        if todo and (proc.returncode != 0 or hung):
            # the first unfinished row is the one that took the child down
            row = todo.pop(0)
            with open(_OUT, "a") as out:
                out.write(json.dumps({
                    "row": row,
                    "status": "hung" if hung else "crashed",
                    "message": (
                        f"no outcome within {ROW_TIMEOUT_S:.0f}s; child "
                        f"killed" if hung else
                        f"child process exited with code {proc.returncode} "
                        f"mid-row (see stderr above)"),
                }) + "\n")
        elif todo:
            break  # child exited cleanly yet rows remain: do not spin
    recs = [r for r in _read_records() if r.get("status") != "started"]
    print(f"{'row':24s} {'status':8s} {'seconds':>8s}  detail")
    for r in recs:
        detail = r.get("message") or json.dumps(
            {k: v for k, v in r.items()
             if k not in ("row", "status", "seconds", "platform",
                          "device_kind", "device_count")})
        print(f"{r['row']:24s} {r['status']:8s} "
              f"{r.get('seconds', 0):8.1f}  {detail[:400]}")
    if recs:
        print({k: recs[0].get(k)
               for k in ("platform", "device_kind", "device_count")})
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--rows", default=",".join(ROWS),
                        help="comma-separated row names (default: all)")
    parser.add_argument("--rows-per-device", type=int,
                        default=ROWS_PER_DEVICE,
                        help="shrink for a CPU rehearsal (default 100,000)")
    parser.add_argument("--north-star", action="store_true",
                        help="run only the 11M-row attempt")
    parser.add_argument("--child", action="store_true",
                        help=argparse.SUPPRESS)
    args = parser.parse_args(argv)
    names = ["north_star"] if args.north_star else args.rows.split(",")
    unknown = [n for n in names if n not in ROWS and n != "north_star"]
    if unknown:
        parser.error(f"unknown rows {unknown}; known: {list(ROWS)}")
    run = child_main if args.child else parent_main
    return run(names, args.rows_per_device)


if __name__ == "__main__":
    sys.exit(main())

"""Child process for the multi-host rehearsal test (see test_multihost.py).

Each invocation is one "host": it joins a 2-process jax.distributed world of
4 CPU devices each (8 global), feeds only its own ranks' shards into
TpuEngine, trains, and checks the result against the single-process
expectations the parent computed.

Usage: python _multihost_child.py <coordinator> <process_id> <expected.npz>
"""

import sys

import numpy as np


def main() -> int:
    coordinator, pid, expected_path = sys.argv[1], int(sys.argv[2]), sys.argv[3]

    import jax

    # the parent passes JAX_PLATFORMS=cpu in the environment (test_multihost)
    jax.distributed.initialize(
        coordinator_address=coordinator, num_processes=2, process_id=pid
    )
    assert jax.process_count() == 2, jax.process_count()
    assert len(jax.devices()) == 8, jax.devices()
    assert len(jax.local_devices()) == 4
    # the engine's row layout assumes process-contiguous device order
    procs = [d.process_index for d in jax.devices()]
    assert procs == sorted(procs), procs

    from xgboost_ray_tpu.distributed import put_rows_global
    from xgboost_ray_tpu.engine import TpuEngine
    from xgboost_ray_tpu.matrix import RayShardingMode, _get_sharding_indices
    from xgboost_ray_tpu.params import parse_params

    exp = np.load(expected_path)
    x, y = exp["x"], exp["y"]
    n = x.shape[0]
    num_actors = 8

    # --- put_rows_global over a 2-process mesh ------------------------------
    import jax.numpy as jnp
    from jax.experimental import multihost_utils
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = jax.sharding.Mesh(np.array(jax.devices()), ("actors",))
    sharding = NamedSharding(mesh, P("actors"))
    full = np.arange(64, dtype=np.float32).reshape(8, 8)
    local = full[pid * 4 : (pid + 1) * 4]
    arr = put_rows_global(local, sharding)
    assert not arr.is_fully_addressable
    gathered = np.asarray(multihost_utils.process_allgather(arr, tiled=True))
    np.testing.assert_array_equal(gathered, full)

    # --- short training with per-process rank shards ------------------------
    my_ranks = range(pid * 4, (pid + 1) * 4)
    shards = []
    for rank in my_ranks:
        idx = _get_sharding_indices(RayShardingMode.INTERLEAVED, rank, num_actors, n)
        shards.append({
            "data": x[idx], "label": y[idx], "weight": None,
            "base_margin": None, "label_lower_bound": None,
            "label_upper_bound": None, "qid": None,
        })
    params = parse_params({"objective": "binary:logistic",
                           "eval_metric": ["logloss", "auc"], "max_depth": 3})
    eng = TpuEngine(shards, params, num_actors=num_actors,
                    evals=[(shards, "train")])
    assert eng.n_rows == n, (eng.n_rows, n)
    results = [eng.step(i) for i in range(int(exp["rounds"]))]
    lls = [r["train"]["logloss"] for r in results]
    assert lls[-1] < lls[0], lls

    # metrics must match the single-process run (same mesh math, psum merged)
    np.testing.assert_allclose(lls, exp["logloss"], atol=1e-5)
    np.testing.assert_allclose(
        [r["train"]["auc"] for r in results], exp["auc"], atol=1e-5
    )

    # what only a mesh has is summed over BOTH processes' shards (an output
    # sharded over the actors is not addressable from one process), and is
    # what one process over the same eight shards reads
    stats = eng.mesh_round_stats()
    assert list(stats.values()) == list(exp["mesh_stats"]), (stats, exp["mesh_stats"])
    assert stats["collectives_per_round"] > 0, stats
    local_rows = sum(len(s["label"]) for s in shards)
    assert sum(eng.placement_record()["rows_per_device"].values()) == local_rows

    # margins gather across hosts (the VERDICT get_margins fix)
    margins = eng.get_margins()
    assert margins.shape[0] == n
    # rows are in rank-shard order: invert the interleave to compare
    order = np.concatenate([
        _get_sharding_indices(RayShardingMode.INTERLEAVED, r, num_actors, n)
        for r in range(num_actors)
    ])
    restored = np.empty_like(margins)
    restored[order] = margins
    np.testing.assert_allclose(restored[:, 0], exp["margins"], atol=1e-4)

    # the booster is replicated: predictions must match the expectation
    bst = eng.get_booster()
    np.testing.assert_allclose(
        bst.predict(x, output_margin=True), exp["margins"], atol=1e-4
    )

    # --- ranking: group layouts + device ndcg over the 2-host mesh ----------
    xr, yr, qid = exp["xr"], exp["yr"], exp["qid"]
    qn = xr.shape[0]
    rshards = []
    for rank in my_ranks:
        idx = _get_sharding_indices(RayShardingMode.BATCH, rank, num_actors, qn)
        rshards.append({
            "data": xr[idx], "label": yr[idx], "weight": None,
            "base_margin": None, "label_lower_bound": None,
            "label_upper_bound": None, "qid": qid[idx],
        })
    rparams = parse_params({"objective": "rank:pairwise",
                            "eval_metric": ["ndcg@4"], "max_depth": 3})
    reng = TpuEngine(rshards, rparams, num_actors=num_actors,
                     evals=[(rshards, "train")])
    rresults = [reng.step(i) for i in range(int(exp["rounds"]))]
    np.testing.assert_allclose(
        [r["train"]["ndcg@4"] for r in rresults], exp["rank_ndcg"], atol=1e-5
    )

    # --- survival: batched rounds + device aft-nloglik on the 2-host mesh ---
    sx, s_lo, s_hi = exp["sx"], exp["s_lo"], exp["s_hi"]
    qn = sx.shape[0]
    sshards = []
    for rank in my_ranks:
        idx = _get_sharding_indices(RayShardingMode.BATCH, rank, num_actors, qn)
        sshards.append({
            "data": sx[idx], "label": None, "weight": None,
            "base_margin": None, "label_lower_bound": s_lo[idx],
            "label_upper_bound": s_hi[idx], "qid": None,
        })
    sparams = parse_params({"objective": "survival:aft",
                            "eval_metric": ["aft-nloglik"], "max_depth": 3})
    seng = TpuEngine(sshards, sparams, num_actors=num_actors,
                     evals=[(sshards, "train")])
    assert seng.can_batch_rounds()
    sresults = seng.step_many(0, int(exp["rounds"]))
    np.testing.assert_allclose(
        [r["train"]["aft-nloglik"] for r in sresults], exp["aft_nll"], atol=1e-5
    )

    # --- custom objective + host feval over the 2-host mesh -----------------
    # Each process computes grad/hess and the host metric from ITS OWN rows
    # (get_margins_local + local label_np) — the reference's per-actor local
    # computation (``xgboost_ray/main.py:745-752``); combine_host_scalar
    # merges the per-process metric. Must match the single-process run
    # bit-for-bit (gradients are identical, placement is identical).
    ceng = TpuEngine(shards, params, num_actors=num_actors,
                     evals=[(shards, "train")])
    c_logloss, c_merror = [], []
    for i in range(int(exp["rounds"])):
        m = ceng.get_margins_local()[:, 0]
        assert m.shape[0] == ceng.label_np.shape[0] == n // 2
        p = 1.0 / (1.0 + np.exp(-m))
        g = (p - ceng.label_np).astype(np.float32)
        h = (p * (1.0 - p)).astype(np.float32)
        r = ceng.step(i, gh_custom=(g, h))
        c_logloss.append(r["train"]["logloss"])
        p2 = 1.0 / (1.0 + np.exp(-ceng.get_margins_local()[:, 0]))
        merr = float(((p2 > 0.5) != (ceng.label_np > 0.5)).mean())
        c_merror.append(ceng.combine_host_scalar(merr, ceng.evals[0]))
    np.testing.assert_allclose(c_logloss, exp["c_logloss"], atol=1e-5)
    np.testing.assert_allclose(c_merror, exp["c_merror"], atol=1e-6)
    np.testing.assert_allclose(
        ceng.get_booster().predict(x, output_margin=True),
        exp["c_margins"], atol=1e-4,
    )

    # --- multi-process SPMD predict (VERDICT r4 #4) -------------------------
    # Each process predicts its own (UNEVEN — exercises the allgathered
    # block layout + per-device padding) local rows through the public
    # predict() path; the global mesh walks all rows in one lockstep program.
    from xgboost_ray_tpu import RayDMatrix, RayParams
    from xgboost_ray_tpu import main as rxgb_main

    cut = 300
    local_x = x[:cut] if pid == 0 else x[cut:]
    expect = exp["margins"][:cut] if pid == 0 else exp["margins"][cut:]
    pm = rxgb_main.predict(
        bst, RayDMatrix(local_x),
        ray_params=RayParams(num_actors=2), output_margin=True,
    )
    np.testing.assert_allclose(np.asarray(pm).ravel(), expect, atol=1e-4)
    # booster-level entry with explicit devices agrees
    pm2 = bst.predict_margin_spmd(local_x, list(jax.devices()))[:, 0]
    np.testing.assert_allclose(pm2, expect, atol=1e-4)

    print(f"CHILD{pid} OK", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Serving scale-out (xgboost_ray_tpu/serve/{pool,autoscale,canary}.py and
the FIL-style node-array layout in ops/node_array.py).

Pins the subsystem's four acceptance invariants:

(a) the breadth-first node-array layout routes every row to the same leaves
    as the padded-heap walk (leaf outputs identical; float outputs within
    ``PARITY_ULPS`` — two programs may order the tree sum differently) for
    every output kind, across buckets, device counts, and NaN routing — and
    a replica spun up after warmup compiles nothing (the program cache is
    shared), so replicas of one program agree bitwise;
(b) a replica killed mid-load sheds capacity, never availability: every
    in-flight request completes, and the route → death → shed → rejoin
    story is reconstructible from the obs timeline alone;
(c) the autoscaler's scale-up → scale-down cycle is likewise
    timeline-reconstructible (every decision carries its evidence);
(d) a canary publish flips only on a metric pass: a regressing candidate
    rolls back automatically and the old version serves bit-identically
    throughout.

Everything runs on the hermetic 8-device CPU mesh from conftest.
"""

import threading
import time

import numpy as np
import pytest

import jax

from xgboost_ray_tpu import RayDMatrix, RayParams, obs, train
from xgboost_ray_tpu import serve

from _parity import assert_parity

RP = RayParams(num_actors=2)


def _train_binary(seed=0, eta=0.3, rounds=4):
    rng = np.random.RandomState(seed)
    x = rng.randn(300, 6).astype(np.float32)
    y = (x[:, 0] + 0.5 * x[:, 1] > 0).astype(np.float32)
    bst = train(
        {"objective": "binary:logistic", "max_depth": 3, "eta": eta,
         "seed": seed},
        RayDMatrix(x, y), rounds, ray_params=RP,
    )
    return bst, x, y


@pytest.fixture(scope="module")
def binary_model():
    return _train_binary(seed=0)


@pytest.fixture(scope="module")
def multiclass_model():
    rng = np.random.RandomState(3)
    x = rng.randn(240, 5).astype(np.float32)
    y = (np.abs(x[:, 0]) + x[:, 1] > 0.6).astype(np.float32) + (
        x[:, 2] > 0.8
    ).astype(np.float32)
    bst = train(
        {"objective": "multi:softprob", "num_class": 3, "max_depth": 3,
         "eta": 0.3, "seed": 0},
        RayDMatrix(x, y), 3, ray_params=RP,
    )
    return bst, x


@pytest.fixture()
def tracer():
    """Fresh ring-buffer tracer installed as the process default, so the
    serve plane's events land somewhere the test can read back."""
    tr = obs.Tracer(capacity=4096, enabled=True, trace_dir="", rank=0)
    old = obs.get_tracer()
    obs.set_default_tracer(tr)
    yield tr
    obs.set_default_tracer(old if old.enabled else None)


def _names(tracer):
    return [r["name"] for r in tracer.records()]


# ---------------------------------------------------------------------------
# (a) node-array layout: leaf-exact / PARITY_ULPS float parity vs the heap
# and batch programs + shared-cache zero compiles
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_dev", [1, 8])
def test_node_array_bitwise_parity_binary(binary_model, n_dev):
    bst, x, _ = binary_model
    devices = jax.devices()[:n_dev] if n_dev > 1 else None
    heap = serve.CompiledPredictor(bst, devices=devices)
    na = serve.CompiledPredictor(bst, devices=devices, layout="node_array")
    q = x[:37].copy()
    q[3, 0] = np.nan  # NaN routes via default_left in BOTH layouts
    q[11, 2] = np.nan
    for n in (1, 9, 37):  # several buckets of the padded ladder
        for kind in serve.KINDS:
            assert_parity(
                na.predict(q[:n], kind), heap.predict(q[:n], kind), (kind, n)
            )


def test_node_array_bitwise_parity_multiclass(multiclass_model):
    bst, x = multiclass_model
    heap = serve.CompiledPredictor(bst, devices=jax.devices())
    na = serve.CompiledPredictor(
        bst, devices=jax.devices(), layout="node_array"
    )
    q = x[:21]
    for kind in serve.KINDS:
        assert_parity(na.predict(q, kind), heap.predict(q, kind), kind)


def test_node_array_parity_vs_batch_predict(binary_model):
    """Node-array vs the reference batch path, under the same contract."""
    bst, x, _ = binary_model
    na = serve.CompiledPredictor(bst, layout="node_array")
    q = x[:16]
    assert_parity(na.predict(q, "value"), bst.predict(q))
    assert_parity(na.predict(q, "margin"), bst.predict(q, output_margin=True))
    assert_parity(na.predict(q, "leaf"), bst.predict(q, pred_leaf=True))
    assert_parity(
        na.predict(q, "contribs"), bst.predict(q, pred_contribs=True)
    )


def test_node_array_replica_spinup_zero_compiles(binary_model):
    bst, x, _ = binary_model
    first = serve.CompiledPredictor(
        bst, devices=jax.devices(), layout="node_array"
    )
    first.warmup(kinds=serve.KINDS, max_batch=64)
    c0 = serve.compile_count()
    # a second replica of the same model: programs come from the shared
    # module-level cache — zero compiles before its first request
    second = serve.CompiledPredictor(
        bst, devices=jax.devices(), layout="node_array"
    )
    for kind in serve.KINDS:
        second.predict(x[:13].astype(np.float32), kind)
    assert serve.compile_count() == c0


def test_invalid_layout_rejected(binary_model):
    bst, _, _ = binary_model
    with pytest.raises(ValueError, match="layout"):
        serve.CompiledPredictor(bst, layout="bfs")


# ---------------------------------------------------------------------------
# satellite 1: publish warms ALL four kinds
# ---------------------------------------------------------------------------


def test_publish_warms_all_four_kinds(binary_model):
    bst, x, _ = binary_model
    reg = serve.ModelRegistry(devices=jax.devices(), warm_max_batch=64)
    assert reg.warm_kinds == serve.KINDS  # the new default
    reg.load(bst)
    c0 = serve.compile_count()
    with reg.lease() as entry:
        for kind in serve.KINDS:
            # first request of EVERY kind after a publish: already warm
            entry.predictor.predict(x[:9].astype(np.float32), kind)
    assert serve.compile_count() == c0


def test_publish_warm_skips_contribs_without_node_stats(binary_model):
    import copy

    bst, x, _ = binary_model
    old = copy.deepcopy(bst)
    old._has_node_stats = False  # what _from_dict sets for pre-stats saves
    reg = serve.ModelRegistry(devices=jax.devices())
    reg.load(old)  # all-kinds warm must SKIP contribs, not raise
    with reg.lease() as entry:
        entry.predictor.predict(x[:4].astype(np.float32), "value")
        with pytest.raises(ValueError, match="contributions"):
            entry.predictor.predict(x[:4].astype(np.float32), "contribs")


# ---------------------------------------------------------------------------
# router: dispatch, admission control, replica-loss chaos
# ---------------------------------------------------------------------------


def _make_router(bst, n_replicas=2, layout="heap", **kw):
    metrics = serve.ServeMetrics(recompile_count_fn=serve.compile_count)
    reg = serve.ModelRegistry(
        devices=jax.devices(), layout=layout, warm_max_batch=64,
        metrics=metrics,
    )
    reg.load(bst)
    router = serve.Router(
        reg, n_replicas=n_replicas, metrics=metrics, max_batch=64,
        max_delay_ms=1.0, layout=layout, devices=jax.devices(), **kw
    )
    metrics.replica_count_fn = router.live_replicas
    return router, metrics


def test_router_serves_bit_identical_across_replicas(binary_model, tracer):
    bst, x, _ = binary_model
    router, metrics = _make_router(bst, n_replicas=2)
    try:
        ref = bst.predict(x[:8])
        outs = []
        for _ in range(6):
            out, version = router.submit(x[:8].astype(np.float32), "value")
            assert version == 1
            assert_parity(out, ref)
            outs.append(np.asarray(out))
        # the replicas share ONE compiled program: whichever replica served
        # a request, the bits are the same
        assert all(np.array_equal(o, outs[0]) for o in outs)
        assert metrics.snapshot()["replicas"] == 2
    finally:
        router.shutdown()
    routes = [r for r in tracer.records() if r["name"] == "serve.route"]
    assert len(routes) == 6
    assert {r["attrs"]["replica"] for r in routes} <= {0, 1}


def test_router_admission_control_rejects_and_counts(binary_model, tracer):
    bst, x, _ = binary_model
    router, metrics = _make_router(bst, n_replicas=2, max_queue_rows=4)
    try:
        with pytest.raises(serve.OverloadedError):
            router.submit(x[:8].astype(np.float32), "value")  # 8 > cap 4
        assert metrics.admission_rejects == 1
        assert metrics.snapshot()["admission_rejects"] == 1
        # under the cap still flows
        out, _ = router.submit(x[:2].astype(np.float32), "value")
        assert out.shape[0] == 2
    finally:
        router.shutdown()


def test_router_no_replicas_is_503_surface(binary_model):
    bst, x, _ = binary_model
    router, _ = _make_router(bst, n_replicas=1)
    try:
        router.kill(0)
        with pytest.raises(serve.NoReplicasError):
            router.submit(x[:2].astype(np.float32), "value")
        router.rejoin()
        out, _ = router.submit(x[:2].astype(np.float32), "value")
        assert out.shape[0] == 2
    finally:
        router.shutdown()


def test_replica_kill_mid_load_sheds_capacity_not_availability(
    binary_model, tracer
):
    """Satellite 2 chaos drill: kill a replica while clients hammer the
    router. ZERO requests may fail — shed requests re-dispatch to the
    survivor — and the timeline alone must tell the whole story."""
    bst, x, _ = binary_model
    router, _ = _make_router(bst, n_replicas=2)
    q = x[:4].astype(np.float32)
    ref = bst.predict(x[:4])
    errors, ok = [], []
    lock = threading.Lock()
    stop = threading.Event()

    def client():
        while not stop.is_set():
            try:
                out, version = router.submit(q, "value", timeout=30.0)
                with lock:
                    ok.append((version, np.asarray(out)))
            except Exception as exc:  # noqa: BLE001 - recorded as failure
                with lock:
                    errors.append(repr(exc))

    def wait_for(n, deadline_s=60.0):
        deadline = time.monotonic() + deadline_s
        while len(ok) < n:
            assert not errors, errors[:3]
            assert time.monotonic() < deadline, f"stalled at {len(ok)}/{n}"
            time.sleep(0.002)

    threads = [threading.Thread(target=client) for _ in range(4)]
    try:
        for t in threads:
            t.start()
        # let traffic build, then hard-kill a replica under load
        wait_for(20)
        victim = router.replica_slots()[0]
        router.kill(victim)
        wait_for(60)
        new_slot = router.rejoin()
        wait_for(90)
    finally:
        stop.set()
        for t in threads:
            t.join(30.0)
        router.shutdown()
    assert not errors, errors[:3]  # availability never degraded
    assert len(ok) >= 90
    for version, out in ok:
        assert version == 1
        assert np.array_equal(out, ref)
    # -- timeline reconstruction: route → death → shed → rejoin ----------
    recs = [
        (r["name"], r.get("attrs", {}))
        for r in tracer.records()
        if r["name"].startswith("serve.")
    ]
    kill_at = next(
        i for i, (n, a) in enumerate(recs)
        if n == "serve.replica_down" and a.get("reason") == "killed"
    )
    rejoin_at = next(
        i for i, (n, a) in enumerate(recs)
        if n == "serve.replica_up" and a.get("reason") == "rejoin"
    )
    assert kill_at < rejoin_at
    assert recs[kill_at][1]["replica"] == victim
    assert recs[kill_at][1]["live"] == 1
    assert recs[rejoin_at][1] == {"replica": new_slot, "reason": "rejoin",
                                  "live": 2}
    # routed to the victim before the kill, never after
    routed_before = {a["replica"] for n, a in recs[:kill_at]
                     if n == "serve.route"}
    routed_between = {a["replica"] for n, a in recs[kill_at:rejoin_at]
                      if n == "serve.route"}
    routed_after = {a["replica"] for n, a in recs[rejoin_at:]
                    if n == "serve.route"}
    assert victim in routed_before
    assert victim not in routed_between and victim not in routed_after
    assert routed_between  # the survivor carried the interregnum
    assert new_slot in routed_after  # the rejoined capacity took traffic


def test_scale_down_drains_before_stopping(binary_model):
    bst, x, _ = binary_model
    router, _ = _make_router(bst, n_replicas=3)
    try:
        assert router.live_replicas() == 3
        assert router.scale_to(1, reason="scale_down") == 1
        out, _ = router.submit(x[:4].astype(np.float32), "value")
        assert out.shape[0] == 4
    finally:
        router.shutdown()


# ---------------------------------------------------------------------------
# (c) autoscaler: hysteresis + timeline-reconstructible cycle
# ---------------------------------------------------------------------------


def test_autoscaler_cycle_reconstructible_from_timeline(binary_model, tracer):
    bst, _, _ = binary_model
    router, metrics = _make_router(bst, n_replicas=1)
    scaler = serve.AutoScaler(
        router, metrics, min_replicas=1, max_replicas=2,
        p99_high_ms=50.0, p99_low_ms=5.0, up_after=2, down_after=3,
    )
    try:
        # hot: synthetic 200 ms requests push p99 over the high bar
        for _ in range(10):
            metrics.observe_request(0.2, 1)
        assert scaler.tick() == 0  # hysteresis: one hot tick is not enough
        assert scaler.tick() == 1  # second consecutive hot tick scales up
        assert router.live_replicas() == 2
        assert scaler.tick() == 0  # still hot, but already at max_replicas

        # cold: a fresh window of sub-millisecond requests
        metrics.reset()
        for _ in range(10):
            metrics.observe_request(0.0005, 1)
        assert scaler.tick() == 0
        assert scaler.tick() == 0
        assert scaler.tick() == -1  # third consecutive cold tick scales down
        assert router.live_replicas() == 1
    finally:
        router.shutdown()

    # -- the cycle, from the timeline alone ------------------------------
    scale_events = [
        r["attrs"] for r in tracer.records() if r["name"] == "serve.scale"
    ]
    assert [e["direction"] for e in scale_events] == ["up", "down"]
    up, down = scale_events
    assert (up["from_replicas"], up["to_replicas"]) == (1, 2)
    assert up["reason"] == "p99_high" and up["p99_ms"] > 50.0
    assert (down["from_replicas"], down["to_replicas"]) == (2, 1)
    assert down["reason"] == "idle" and down["p99_ms"] < 5.0
    # membership events agree with the decisions: replay replica count
    # from zero (the router's startup replica is itself on the timeline)
    live = 0
    for r in tracer.records():
        if r["name"] == "serve.replica_up":
            live += 1
            assert r["attrs"]["live"] == live
        elif r["name"] == "serve.replica_down":
            live -= 1
            assert r["attrs"]["live"] == live
    assert live == 0  # shutdown returned the pool to zero, audited


def test_autoscaler_queue_depth_trigger(binary_model):
    bst, _, _ = binary_model
    router, metrics = _make_router(bst, n_replicas=1)
    scaler = serve.AutoScaler(
        router, metrics, max_replicas=2, queue_high=1, up_after=1,
        p99_high_ms=1e9,
    )
    try:
        router.queue_depth = lambda: 3  # instance shadow: a stuck backlog
        assert scaler.tick() == 1  # queue depth alone triggers the scale-up
        assert router.live_replicas() == 2
    finally:
        del router.queue_depth
        router.shutdown()


# ---------------------------------------------------------------------------
# (d) canary publish: rollback on regression, promote on pass
# ---------------------------------------------------------------------------


def test_canary_bad_candidate_rolls_back(binary_model, tracer):
    bst, x, y = binary_model
    metrics = serve.ServeMetrics()
    reg = serve.ModelRegistry(devices=jax.devices(), metrics=metrics)
    ctl = serve.CanaryController(reg, metrics=metrics)

    # cold start publishes unconditionally
    verdict = ctl.publish(bst, x[:100], y[:100])
    assert verdict == {"promoted": True, "version": 1, "reason": "cold_start"}

    # a deliberately bad candidate: trained on shuffled labels
    rng = np.random.RandomState(7)
    bad = train(
        {"objective": "binary:logistic", "max_depth": 3, "eta": 0.3,
         "seed": 7},
        RayDMatrix(x, rng.permutation(y)), 4, ray_params=RP,
    )
    ref = bst.predict(x[:9])
    verdict = ctl.publish(bad, x[:100], y[:100], shadow_x=x[:16])
    assert verdict["promoted"] is False
    assert verdict["reason"] == "metric_regression"
    assert verdict["version"] == 1  # the flip never happened
    assert verdict["candidate_metric"] > verdict["gate"]
    assert verdict["shadow_mean_abs_delta"] > 0
    assert reg.version == 1
    with reg.lease() as entry:  # old model still serving, bit-identically
        assert np.array_equal(
            entry.predictor.predict(x[:9].astype(np.float32), "value"), ref
        )
    assert metrics.canary_rollbacks == 1 and metrics.canary_promotions == 1
    names = _names(tracer)
    assert "serve.shadow" in names and "serve.rollback" in names
    assert names.index("serve.shadow") < names.index("serve.rollback")


def test_canary_good_candidate_promotes(binary_model, tracer):
    bst, x, y = binary_model
    metrics = serve.ServeMetrics()
    reg = serve.ModelRegistry(devices=jax.devices(), metrics=metrics)
    ctl = serve.CanaryController(reg, metrics=metrics)
    ctl.publish(bst, x[:100], y[:100])

    # the refresh helper: boost MORE rounds warm-started from the live
    # booster — strictly lower train-set logloss, so the gate passes
    refreshed = serve.refresh(
        bst, {"objective": "binary:logistic", "max_depth": 3, "eta": 0.3,
              "seed": 0},
        RayDMatrix(x, y), 2, ray_params=RP,
    )
    assert refreshed.num_trees > bst.num_trees
    verdict = ctl.publish(refreshed, x[:100], y[:100])
    assert verdict["promoted"] is True and verdict["reason"] == "gate_pass"
    assert verdict["candidate_metric"] <= verdict["gate"]
    assert verdict["version"] == reg.version == 2
    with reg.lease() as entry:
        assert np.array_equal(
            entry.predictor.predict(x[:9].astype(np.float32), "value"),
            refreshed.predict(x[:9]),
        )
    assert metrics.canary_promotions == 2 and metrics.canary_rollbacks == 0
    assert "serve.promote" in _names(tracer)

"""Online serving subsystem (xgboost_ray_tpu/serve/).

Pins the three serving invariants the subsystem is built around:

(a) served predictions match the batch ``predict()`` path for every output
    kind served (padding rows cannot leak into real rows): the same program
    is bitwise reproducible; across programs leaf indices are identical and
    float outputs agree to ``PARITY_ULPS`` (tests keep their historical
    ``bit_identical`` names);
(b) steady-state traffic causes ZERO recompiles: after warmup, 100+
    mixed-size requests never trace a new program (compile counter);
(c) hot-swap under concurrent load drains in-flight batches and drops or
    mixes no responses — every response is wholly from the model version
    it reports.

All HTTP tests run against a loopback ThreadingHTTPServer on an ephemeral
port; everything runs on the hermetic 8-device CPU mesh from conftest.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax

from xgboost_ray_tpu import RayDMatrix, RayParams, train
from xgboost_ray_tpu import serve
from xgboost_ray_tpu.serve.predictor import bucket_rows

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
    return bst, x


@pytest.fixture(scope="module")
def binary_model():
    return _train_binary(seed=0)


@pytest.fixture(scope="module")
def binary_model_b():
    # same shape (rounds/depth/features) as binary_model, different trees:
    # the retrain-and-swap shape, which must reuse every compiled program
    return _train_binary(seed=1, eta=0.05)


def _post(url, path, doc, timeout=30.0):
    req = urllib.request.Request(
        url + path, json.dumps(doc).encode("utf-8"),
        {"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def _get(url, path, timeout=30.0):
    with urllib.request.urlopen(url + path, timeout=timeout) as r:
        return r.status, json.loads(r.read())


# ---------------------------------------------------------------------------
# buckets
# ---------------------------------------------------------------------------


def test_bucket_rows_pow2_and_mesh_multiple():
    assert bucket_rows(1, 8, 1) == 8
    assert bucket_rows(8, 8, 1) == 8
    assert bucket_rows(9, 8, 1) == 16
    assert bucket_rows(100, 8, 1) == 128
    assert bucket_rows(100, 8, 8) == 128
    # non-power-of-two mesh: rounded up to a device multiple
    assert bucket_rows(5, 1, 3) % 3 == 0
    assert bucket_rows(0, 1, 1) == 1


def test_bucket_rows_idempotent_and_warmup_covers_live_buckets():
    """On non-power-of-two device counts the bucket ladder must be
    idempotent, else warmup compiles buckets live requests never hit and
    the first post-swap request pays a compile on the serving path."""
    for n_dev in (1, 2, 3, 5, 7, 8):
        live = {bucket_rows(n, 8, n_dev) for n in range(1, 257)}
        assert all(bucket_rows(b, 8, n_dev) == b for b in live), n_dev
        assert all(b % n_dev == 0 for b in live), n_dev
        # the warmup enumeration (bucket + 1 stepping) hits exactly `live`
        warm, n, top = set(), 1, bucket_rows(256, 8, n_dev)
        while True:
            b = bucket_rows(n, 8, n_dev)
            warm.add(b)
            if b >= top:
                break
            n = b + 1
        assert warm == live, (n_dev, warm ^ live)


# ---------------------------------------------------------------------------
# (a) parity vs the batch predict() path: another program over the same
# forest — same leaves, float sums within PARITY_ULPS (the restated
# contract, serve/predictor.py); the same program is bitwise reproducible
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_dev", [1, 8])
def test_served_bit_identical_to_batch_predict(binary_model, n_dev):
    bst, x = binary_model
    devices = jax.devices()[:n_dev] if n_dev > 1 else None
    pred = serve.CompiledPredictor(bst, devices=devices)
    q = x[:37]
    refs = {
        "value": bst.predict(q),
        "margin": bst.predict(q, output_margin=True),
        "leaf": bst.predict(q, pred_leaf=True),
        "contribs": bst.predict(q, pred_contribs=True),
    }
    for kind in serve.KINDS:
        got = pred.predict(q.astype(np.float32), kind)
        assert_parity(got, refs[kind], kind)
        # same program + same input => same bits, on any backend
        again = pred.predict(q.astype(np.float32), kind)
        assert np.array_equal(np.asarray(got), np.asarray(again)), kind


def test_parity_bound_rejects_a_narrower_dtype(binary_model):
    """The ulp bound is tight enough to mean float32: the same margins
    rounded through bfloat16 miss it by orders of magnitude."""
    bst, x = binary_model
    ref = bst.predict(x[:37], output_margin=True)
    import jax.numpy as jnp

    rounded = np.asarray(jnp.asarray(ref).astype(jnp.bfloat16).astype(
        jnp.float32))
    with pytest.raises(AssertionError):
        assert_parity(rounded, ref)


def test_served_bit_identical_multiclass():
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
    pred = serve.CompiledPredictor(bst, devices=jax.devices())
    q = x[:21].astype(np.float32)
    assert_parity(pred.predict(q, "value"), bst.predict(q))
    assert_parity(pred.predict(q, "margin"), bst.predict(q, output_margin=True))
    assert_parity(
        pred.predict(q, "contribs"), bst.predict(q, pred_contribs=True)
    )


def test_served_bit_identical_through_http(binary_model):
    bst, x = binary_model
    h = serve.create_server(bst, max_batch=64, max_delay_ms=1.0)
    try:
        for kind in serve.KINDS:
            status, r = _post(
                h.url, "/predict", {"data": x[:9].tolist(), "kind": kind}
            )
            assert status == 200
            ref = {
                "value": bst.predict(x[:9]),
                "margin": bst.predict(x[:9], output_margin=True),
                "leaf": bst.predict(x[:9], pred_leaf=True),
                "contribs": bst.predict(x[:9], pred_contribs=True),
            }[kind]
            got = np.asarray(r["predictions"], np.asarray(ref).dtype)
            assert_parity(got, ref, kind)
            assert r["model_version"] == 1
    finally:
        h.shutdown()


# ---------------------------------------------------------------------------
# (b) zero recompiles in steady state
# ---------------------------------------------------------------------------


def test_zero_recompiles_after_warmup(binary_model):
    bst, x = binary_model
    pred = serve.CompiledPredictor(bst, devices=jax.devices())
    warmed = pred.warmup(kinds=serve.KINDS, max_batch=64)
    assert warmed > 0  # fresh model: warmup really compiled something
    rng = np.random.RandomState(0)
    c0 = serve.compile_count()
    kinds = list(serve.KINDS)
    for i in range(120):  # >= 100 mixed-size requests across all kinds
        n = int(rng.randint(1, 65))
        pred.predict(x[:n].astype(np.float32), kinds[i % len(kinds)])
    assert serve.compile_count() == c0


def test_same_shape_hot_swap_reuses_programs(binary_model, binary_model_b):
    bst_a, x = binary_model
    bst_b, _ = binary_model_b
    assert bst_a.signature() == bst_b.signature()
    reg = serve.ModelRegistry(devices=jax.devices(), warm_kinds=("value",),
                              warm_max_batch=32)
    reg.load(bst_a)
    c0 = serve.compile_count()
    reg.load(bst_b)  # same signature: warmup must hit the cached programs
    assert serve.compile_count() == c0
    with reg.lease() as entry:
        got = entry.predictor.predict(x[:7].astype(np.float32), "value")
    assert np.array_equal(got, bst_b.predict(x[:7]))


# ---------------------------------------------------------------------------
# microbatching
# ---------------------------------------------------------------------------


def test_microbatcher_coalesces_concurrent_requests(binary_model):
    bst, x = binary_model
    metrics = serve.ServeMetrics()
    reg = serve.ModelRegistry(warm_kinds=("value",), warm_max_batch=64)
    reg.load(bst)
    batcher = serve.MicroBatcher(reg, max_batch=64, max_delay_ms=50.0,
                                 metrics=metrics)
    try:
        results = [None] * 8
        barrier = threading.Barrier(8)

        def client(i):
            barrier.wait()
            results[i] = batcher.submit(x[i * 3 : i * 3 + 3], "value")

        threads = [threading.Thread(target=client, args=(i,)) for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30.0)
        for i, (out, version) in enumerate(results):
            assert version == 1
            assert np.array_equal(out, bst.predict(x[i * 3 : i * 3 + 3]))
        snap = metrics.snapshot()
        assert snap["requests"] == 8
        # 8 near-simultaneous requests within one 50 ms window must coalesce
        assert snap["batches"] < 8
        assert snap["mean_batch_rows"] > 3
    finally:
        batcher.shutdown()


def test_oversized_request_flushes_alone(binary_model):
    bst, x = binary_model
    reg = serve.ModelRegistry(warm_kinds=())
    reg.load(bst, warm=False)
    batcher = serve.MicroBatcher(reg, max_batch=16, max_delay_ms=1.0)
    try:
        out, _ = batcher.submit(x[:100], "value")  # > max_batch rows
        assert np.array_equal(out, bst.predict(x[:100]))
    finally:
        batcher.shutdown()


def test_padding_waste_accounting(binary_model):
    bst, x = binary_model
    metrics = serve.ServeMetrics()
    reg = serve.ModelRegistry(warm_kinds=())
    reg.load(bst, warm=False)
    batcher = serve.MicroBatcher(reg, max_batch=64, max_delay_ms=1.0,
                                 metrics=metrics)
    try:
        batcher.submit(x[:5], "value")  # bucket 8 -> 3 padded rows
        snap = metrics.snapshot()
        assert snap["batches"] == 1
        assert snap["padding_waste"] == pytest.approx(3 / 8)
    finally:
        batcher.shutdown()


# ---------------------------------------------------------------------------
# (c) hot-swap under concurrent load
# ---------------------------------------------------------------------------


def test_hot_swap_under_load_no_dropped_or_mixed(binary_model, binary_model_b):
    bst_a, x = binary_model
    bst_b, _ = binary_model_b
    q = x[:4]
    ref = {1: bst_a.predict(q), 2: bst_b.predict(q)}
    h = serve.create_server(bst_a, max_batch=32, max_delay_ms=1.0)
    errors, responses = [], []
    resp_lock = threading.Lock()
    stop = threading.Event()

    def client():
        while not stop.is_set():
            try:
                status, r = _post(h.url, "/predict", {"data": q.tolist()})
                with resp_lock:
                    responses.append((status, r["model_version"],
                                      np.asarray(r["predictions"])))
            except Exception as exc:  # noqa: BLE001 - recorded as failure
                with resp_lock:
                    errors.append(repr(exc))

    threads = [threading.Thread(target=client) for _ in range(4)]
    try:
        for t in threads:
            t.start()
        time.sleep(0.3)
        v2 = h.registry.load(bst_b)  # drains in-flight, then flips
        assert v2 == 2
        time.sleep(0.3)
    finally:
        stop.set()
        for t in threads:
            t.join(30.0)
        h.shutdown()
    assert not errors, errors[:3]  # nothing dropped
    assert len(responses) > 10
    versions = {v for _, v, _ in responses}
    assert versions <= {1, 2} and 2 in versions
    for status, v, pred in responses:  # nothing mixed: bitwise per version
        assert status == 200
        assert np.array_equal(pred.astype(np.float32),
                              ref[v].astype(np.float32)), v


def test_canary_rollback_then_promote_under_load(binary_model):
    """Satellite acceptance (serving scale-out PR): the canary gate in the
    hot-swap-hammer loop. A regressing candidate publish rolls back
    automatically — the old version keeps serving BIT-IDENTICALLY for
    every concurrent request — then a passing warm-start refresh flips
    with zero dropped requests."""
    bst, x = binary_model
    y = (x[:, 0] + 0.5 * x[:, 1] > 0).astype(np.float32)
    rng = np.random.RandomState(7)
    bad = train(  # trained on shuffled labels: must fail the logloss gate
        {"objective": "binary:logistic", "max_depth": 3, "eta": 0.3,
         "seed": 7},
        RayDMatrix(x, rng.permutation(y)), 4, ray_params=RP,
    )
    good = serve.refresh(  # 2 more rounds warm-started from the live model
        bst, {"objective": "binary:logistic", "max_depth": 3, "eta": 0.3,
              "seed": 0},
        RayDMatrix(x, y), 2, ray_params=RP,
    )
    q = x[:4]
    ref = {1: bst.predict(q), 2: good.predict(q)}
    h = serve.create_server(bst, max_batch=32, max_delay_ms=1.0)
    ctl = serve.CanaryController(h.registry, metrics=h.metrics)
    errors, responses = [], []
    resp_lock = threading.Lock()
    stop = threading.Event()

    def client():
        while not stop.is_set():
            try:
                status, r = _post(h.url, "/predict", {"data": q.tolist()})
                with resp_lock:
                    responses.append((status, r["model_version"],
                                      np.asarray(r["predictions"])))
            except Exception as exc:  # noqa: BLE001 - recorded as failure
                with resp_lock:
                    errors.append(repr(exc))

    threads = [threading.Thread(target=client) for _ in range(4)]
    try:
        for t in threads:
            t.start()
        time.sleep(0.3)
        verdict = ctl.publish(bad, x[:100], y[:100], shadow_x=x[:16])
        assert verdict["promoted"] is False
        assert verdict["reason"] == "metric_regression"
        assert h.registry.version == 1  # rollback = the flip never happened
        time.sleep(0.3)
        with resp_lock:
            n_before_promote = len(responses)
        verdict = ctl.publish(good, x[:100], y[:100])
        assert verdict["promoted"] is True and verdict["version"] == 2
        time.sleep(0.3)
    finally:
        stop.set()
        for t in threads:
            t.join(30.0)
        h.shutdown()
    assert not errors, errors[:3]  # zero drops through both publishes
    assert len(responses) > n_before_promote > 10
    # every response between the rollback and the promote was version 1 —
    # the bad candidate never served a single request
    versions = [v for _, v, _ in responses]
    assert set(versions) <= {1, 2} and 2 in versions
    assert set(versions[:n_before_promote]) == {1}
    for status, v, pred in responses:  # bitwise per reported version
        assert status == 200
        assert np.array_equal(pred.astype(np.float32),
                              ref[v].astype(np.float32)), v
    snap = h.metrics.snapshot()
    assert snap["canary_rollbacks"] == 1 and snap["canary_promotions"] == 1


def test_http_handlers_concurrent_with_hot_swap(binary_model, binary_model_b):
    """Satellite acceptance (rxgbrace PR): /predict, /metrics and /healthz
    all running concurrently with registry hot-swaps — no request may ever
    observe a half-swapped model: every /predict response's predictions are
    bitwise those of the version it reports, /healthz always reports a
    committed version (never a mid-drain intermediate), and /metrics stays
    servable and internally consistent throughout."""
    bst_a, x = binary_model
    bst_b, _ = binary_model_b
    q = x[:3]
    ref = {}  # committed version -> expected predictions
    h = serve.create_server(bst_a, max_batch=32, max_delay_ms=1.0)
    ref[1] = bst_a.predict(q)
    errors, preds, healths, metrics = [], [], [], []
    lock = threading.Lock()
    stop = threading.Event()

    def predict_client():
        while not stop.is_set():
            try:
                status, r = _post(h.url, "/predict", {"data": q.tolist()})
                with lock:
                    preds.append((status, r["model_version"],
                                  np.asarray(r["predictions"])))
            except Exception as exc:  # noqa: BLE001 - recorded
                with lock:
                    errors.append(("predict", repr(exc)))

    def health_client():
        while not stop.is_set():
            try:
                status, r = _get(h.url, "/healthz")
                with lock:
                    healths.append((status, r))
            except Exception as exc:  # noqa: BLE001 - recorded
                with lock:
                    errors.append(("healthz", repr(exc)))

    def metrics_client():
        while not stop.is_set():
            try:
                status, r = _get(h.url, "/metrics")
                with lock:
                    metrics.append((status, r))
            except Exception as exc:  # noqa: BLE001 - recorded
                with lock:
                    errors.append(("metrics", repr(exc)))

    threads = [
        threading.Thread(target=predict_client),
        threading.Thread(target=predict_client),
        threading.Thread(target=health_client),
        threading.Thread(target=metrics_client),
    ]
    try:
        for t in threads:
            t.start()
        time.sleep(0.2)
        # two hot-swaps under sustained mixed traffic (A -> B -> A shape:
        # same buckets, different trees)
        assert h.registry.load(bst_b) == 2
        ref[2] = bst_b.predict(q)
        time.sleep(0.2)
        assert h.registry.load(bst_a) == 3
        ref[3] = ref[1]
        time.sleep(0.2)
    finally:
        stop.set()
        for t in threads:
            t.join(30.0)
        h.shutdown()
    assert not errors, errors[:3]
    assert len(preds) > 10 and len(healths) > 3 and len(metrics) > 3
    seen_versions = {v for _, v, _ in preds}
    assert seen_versions <= {1, 2, 3} and len(seen_versions) >= 2
    for status, version, got in preds:
        # the half-swap pin: the response is wholly from the version it
        # reports — bitwise equal to that committed model's predictions
        assert status == 200
        assert np.array_equal(
            got.astype(np.float32), ref[version].astype(np.float32)
        ), f"half-swapped response for v{version}"
    for status, doc in healths:
        assert status == 200, doc
        assert doc["status"] == "ok"
        assert doc["model_version"] in (1, 2, 3), (
            f"/healthz reported uncommitted version: {doc}"
        )
    swaps_seen = 0
    for status, doc in metrics:
        assert status == 200
        # every successful /predict records requests+=1 and rows+=3 under
        # ONE lock, and snapshot() cuts under the same lock: any mid-run
        # snapshot must see them exactly in lockstep
        assert doc["rows"] == doc["requests"] * 3, f"torn counters: {doc}"
        swaps_seen = max(swaps_seen, doc["model_swaps"])
    assert swaps_seen <= 2  # two live swaps (initial load is not a swap)


# ---------------------------------------------------------------------------
# registry loading surfaces
# ---------------------------------------------------------------------------


def test_registry_loads_checkpoint_path_and_xgb_json(binary_model, tmp_path):
    bst, x = binary_model
    native = tmp_path / "model.json"
    bst.save_model(str(native))
    xgb_json = bst.export_xgboost_json()

    reg = serve.ModelRegistry(warm_kinds=())
    v1 = reg.load(str(native), warm=False)  # native checkpoint path
    with reg.lease() as entry:
        got = entry.predictor.predict(x[:6].astype(np.float32), "value")
    assert np.allclose(got, bst.predict(x[:6]), atol=1e-6)

    v2 = reg.load(xgb_json, warm=False)  # xgboost JSON document string
    assert v2 == v1 + 1
    with reg.lease() as entry:
        got = entry.predictor.predict(x[:6].astype(np.float32), "margin")
    assert np.allclose(got, bst.predict(x[:6], output_margin=True), atol=1e-5)

    import pickle

    v3 = reg.load(pickle.dumps(bst), warm=False)  # checkpoint bytes
    assert v3 == v2 + 1


def test_serve_contribs_rejects_pre_stats_model(binary_model):
    """A model without per-node stats must error on served contribs (as
    the batch path does), never 200 with all-zero SHAP values."""
    import copy

    bst, x = binary_model
    old = copy.deepcopy(bst)
    old._has_node_stats = False  # what _from_dict sets for pre-stats saves
    pred = serve.CompiledPredictor(old)
    with pytest.raises(ValueError, match="contributions"):
        pred.predict(x[:4].astype(np.float32), "contribs")
    # other kinds still serve
    assert np.array_equal(pred.predict(x[:4].astype(np.float32), "value"),
                          old.predict(x[:4]))


def test_registry_rejects_gblinear():
    from xgboost_ray_tpu.linear import RayLinearBooster

    rng = np.random.RandomState(0)
    x = rng.randn(200, 4).astype(np.float32)
    y = x[:, 0].astype(np.float32)
    bst = train(
        {"objective": "reg:squarederror", "booster": "gblinear", "eta": 0.5},
        RayDMatrix(x, y), 3, ray_params=RP,
    )
    assert isinstance(bst, RayLinearBooster)
    reg = serve.ModelRegistry(warm_kinds=())
    with pytest.raises(TypeError, match="gblinear"):
        reg.load(bst, warm=False)


def test_batch_feature_mismatch_fails_only_bad_requests(binary_model):
    """A request whose width doesn't match the leased model (e.g. a
    hot-swap raced the HTTP-level check) fails alone; the rest of its
    batch still gets served."""
    bst, x = binary_model
    reg = serve.ModelRegistry(warm_kinds=())
    reg.load(bst, warm=False)
    batcher = serve.MicroBatcher(reg, max_batch=64, max_delay_ms=30.0)
    try:
        results = {}
        errors = {}
        barrier = threading.Barrier(3)

        def good(i):
            barrier.wait()
            results[i] = batcher.submit(x[i * 2 : i * 2 + 2], "value")

        def bad():
            barrier.wait()
            try:
                batcher.submit(x[:2, :4], "value")  # wrong feature count
            except ValueError as exc:
                errors["bad"] = str(exc)

        threads = [threading.Thread(target=good, args=(i,)) for i in range(2)]
        threads.append(threading.Thread(target=bad))
        for t in threads:
            t.start()
        for t in threads:
            t.join(30.0)
        assert "feature shape mismatch" in errors["bad"]
        for i in range(2):
            out, _ = results[i]
            assert np.array_equal(out, bst.predict(x[i * 2 : i * 2 + 2]))
    finally:
        batcher.shutdown()


def test_train_rejects_gblinear_serve_registry_before_training():
    """The unservable-booster check must fire BEFORE boosting, not after."""
    rng = np.random.RandomState(0)
    x = rng.randn(100, 4).astype(np.float32)
    y = x[:, 0].astype(np.float32)
    with pytest.raises(ValueError, match="gblinear"):
        train(
            {"objective": "reg:squarederror", "booster": "gblinear"},
            RayDMatrix(x, y), 2, ray_params=RP,
            serve_registry=serve.ModelRegistry(),
        )


def test_train_publishes_into_serve_registry():
    rng = np.random.RandomState(2)
    x = rng.randn(200, 4).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.float32)
    reg = serve.ModelRegistry(warm_kinds=())
    extra = {}
    bst = train(
        {"objective": "binary:logistic", "max_depth": 2, "eta": 0.3},
        RayDMatrix(x, y), 2, ray_params=RP, serve_registry=reg,
        additional_results=extra,
    )
    assert reg.version == 1
    assert extra["serve_model_version"] == 1
    with reg.lease() as entry:
        got = entry.predictor.predict(x[:5], "value")
    assert np.array_equal(got, bst.predict(x[:5]))


# ---------------------------------------------------------------------------
# HTTP surface: health, metrics, errors
# ---------------------------------------------------------------------------


def test_healthz_and_metrics_endpoints(binary_model):
    bst, x = binary_model
    h = serve.ServeHandle(max_batch=32, max_delay_ms=1.0).start()
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(h.url, "/healthz")
        assert ei.value.code == 503  # no model yet
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(h.url, "/predict", {"data": x[:2].tolist()})
        assert ei.value.code == 503

        h.registry.load(bst, warm=False)
        status, doc = _get(h.url, "/healthz")
        assert (status, doc["status"]) == (200, "ok")

        for _ in range(5):
            _post(h.url, "/predict", {"data": x[:4].tolist()})
        status, m = _get(h.url, "/metrics")
        assert status == 200
        for key in ("qps", "queue_depth", "latency_p50_ms", "latency_p95_ms",
                    "latency_p99_ms", "padding_waste", "recompile_count",
                    "requests", "batches", "model_swaps"):
            assert key in m, key
        assert m["requests"] == 5
        assert m["rows"] == 20
        assert 0.0 <= m["padding_waste"] < 1.0
        assert m["latency_p99_ms"] >= m["latency_p50_ms"] > 0.0
    finally:
        h.shutdown()


def test_http_error_codes(binary_model):
    bst, x = binary_model
    h = serve.create_server(bst, max_batch=32, max_delay_ms=1.0)
    try:
        for doc, frag in [
            ({"data": x[:2, :3].tolist()}, "shape mismatch"),
            ({"data": x[:2].tolist(), "kind": "nope"}, "output kind"),
            ({}, "missing 'data'"),
        ]:
            with pytest.raises(urllib.error.HTTPError) as ei:
                _post(h.url, "/predict", doc)
            assert ei.value.code == 400
            assert frag in json.loads(ei.value.read())["error"]
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(h.url, "/nope")
        assert ei.value.code == 404
    finally:
        h.shutdown()


def test_http_hot_swap_endpoint(binary_model, binary_model_b, tmp_path):
    bst_a, x = binary_model
    bst_b, _ = binary_model_b
    path = tmp_path / "next.json"
    bst_b.save_model(str(path))
    h = serve.create_server(bst_a, max_batch=32, max_delay_ms=1.0)
    try:
        status, r = _post(h.url, "/models", {"path": str(path)})
        assert (status, r["model_version"]) == (200, 2)
        status, r = _post(h.url, "/predict", {"data": x[:3].tolist()})
        assert r["model_version"] == 2
        assert np.array_equal(
            np.asarray(r["predictions"], np.float32), bst_b.predict(x[:3])
        )
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(h.url, "/models", {"path": str(tmp_path / "missing.json")})
        assert ei.value.code == 400
    finally:
        h.shutdown()

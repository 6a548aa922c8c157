"""booster="gblinear" (linear model, cyclic coordinate descent) tests.

The reference exposes gblinear by params passthrough to xgboost's linear
updaters (``xgboost_ray/main.py:745-752``); here it is one jitted
shard_map round with a lax.scan cyclic pass and psum-merged coordinate
sums (``linear.py``). Pinned: weight recovery, elastic-net sparsity,
multi-actor identity, classification quality, serialization/interop, and
the loud rejections for unsupported combinations.
"""

import json

import numpy as np
import pytest

from xgboost_ray_tpu import RayDMatrix, RayParams, predict, train
from xgboost_ray_tpu.linear import RayLinearBooster

RP1 = RayParams(num_actors=1)
RP2 = RayParams(num_actors=2)


def _lin_data(n=500, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, 6).astype(np.float32)
    w = np.array([1.5, -2.0, 0.5, 0.0, 0.0, 3.0], np.float32)
    y = (x @ w + 0.7 + 0.05 * rng.randn(n)).astype(np.float32)
    return x, y, w


def test_gblinear_recovers_weights_and_reduces_rmse():
    x, y, w_true = _lin_data()
    dm = RayDMatrix(x, y)
    res = {}
    bst = train({"objective": "reg:squarederror", "booster": "gblinear",
                 "eta": 0.6, "lambda": 0.01}, dm, 40, ray_params=RP2,
                evals=[(dm, "train")], evals_result=res)
    assert isinstance(bst, RayLinearBooster)
    assert bst.num_boosted_rounds() == 40
    assert res["train"]["rmse"][-1] < 0.2 * res["train"]["rmse"][0]
    np.testing.assert_allclose(bst.weights[:, 0], w_true, atol=0.1)
    # intercept: bias + base_score margin together model the 0.7 offset
    assert abs(bst.bias[0] + bst.base_score - 0.7) < 0.1


def test_gblinear_l1_drives_irrelevant_weights_to_zero():
    x, y, w_true = _lin_data(seed=1)
    bst = train({"objective": "reg:squarederror", "booster": "gblinear",
                 "eta": 0.5, "alpha": 0.05, "lambda": 0.0},
                RayDMatrix(x, y), 40, ray_params=RP2)
    w = bst.weights[:, 0]
    # effectively zero: the eta-scaled soft-threshold update (xgboost's
    # learning_rate * CoordinateDelta) decays sub-threshold weights
    # geometrically rather than snapping them
    assert abs(w[3]) < 1e-6 and abs(w[4]) < 1e-6, w
    assert abs(w[0]) > 1.0 and abs(w[5]) > 2.0


def test_gblinear_multi_actor_identity():
    x, y, _ = _lin_data(seed=2)
    kw = {"objective": "reg:squarederror", "booster": "gblinear",
          "eta": 0.4, "lambda": 0.1, "alpha": 0.01}
    a = train(kw, RayDMatrix(x, y), 12, ray_params=RP1)
    b = train(kw, RayDMatrix(x, y), 12, ray_params=RP2)
    np.testing.assert_allclose(a.weights, b.weights, atol=1e-5)
    np.testing.assert_allclose(a.bias, b.bias, atol=1e-5)


def test_gblinear_binary_logistic_and_distributed_predict():
    rng = np.random.RandomState(3)
    x = rng.randn(600, 4).astype(np.float32)
    y = (x[:, 0] - 0.8 * x[:, 1] > 0).astype(np.float32)
    bst = train({"objective": "binary:logistic", "booster": "gblinear",
                 "eta": 0.5}, RayDMatrix(x, y), 30, ray_params=RP2)
    p = bst.predict(x)
    assert ((p > 0.5) == y).mean() > 0.9
    assert p.min() >= 0 and p.max() <= 1
    pd = predict(bst, RayDMatrix(x), ray_params=RP2)
    np.testing.assert_allclose(pd, p, atol=1e-5)


def test_gblinear_multiclass_softprob():
    rng = np.random.RandomState(4)
    n = 450
    y = rng.randint(0, 3, n).astype(np.float32)
    x = (np.eye(3, dtype=np.float32)[y.astype(int)]
         + 0.3 * rng.randn(n, 3).astype(np.float32))
    bst = train({"objective": "multi:softprob", "num_class": 3,
                 "booster": "gblinear", "eta": 0.5}, RayDMatrix(x, y), 25,
                ray_params=RP2)
    p = bst.predict(x)
    assert p.shape == (n, 3)
    np.testing.assert_allclose(p.sum(axis=1), 1.0, atol=1e-5)
    assert (p.argmax(axis=1) == y).mean() > 0.85


def test_gblinear_missing_values_are_implicit_zeros():
    x, y, _ = _lin_data(seed=5)
    x_missing = x.copy()
    x_zero = x.copy()
    mask = np.random.RandomState(6).rand(*x.shape) < 0.2
    x_missing[mask] = np.nan
    x_zero[mask] = 0.0
    kw = {"objective": "reg:squarederror", "booster": "gblinear", "eta": 0.5}
    a = train(kw, RayDMatrix(x_missing, y), 8, ray_params=RP1)
    b = train(kw, RayDMatrix(x_zero, y), 8, ray_params=RP1)
    np.testing.assert_allclose(a.weights, b.weights, atol=1e-5)
    np.testing.assert_allclose(a.predict(x_missing), a.predict(x_zero),
                               atol=1e-5)


def test_gblinear_serialization_and_xgb_schema(tmp_path):
    x, y, _ = _lin_data(seed=7)
    bst = train({"objective": "reg:squarederror", "booster": "gblinear",
                 "eta": 0.5}, RayDMatrix(x, y), 10, ray_params=RP2)
    # native xgboost gblinear schema: flat (F+1)*K weights, bias last
    doc = json.loads(bst.export_xgboost_json())
    gb = doc["learner"]["gradient_booster"]
    assert gb["name"] == "gblinear"
    assert len(gb["model"]["weights"]) == 7  # 6 features + bias
    path = str(tmp_path / "lin.json")
    bst.save_model(path)
    back = RayLinearBooster.load_model(path)
    np.testing.assert_allclose(back.predict(x), bst.predict(x), atol=1e-6)
    raw = RayLinearBooster.load_raw(bst.save_raw())
    np.testing.assert_allclose(raw.weights, bst.weights)
    # warm start continues from the loaded model
    more = train({"objective": "reg:squarederror", "booster": "gblinear",
                  "eta": 0.5}, RayDMatrix(x, y), 5, ray_params=RP2,
                 xgb_model=back)
    assert more.num_boosted_rounds() == 15


def test_gblinear_validation_errors():
    x = np.random.RandomState(0).randn(60, 3).astype(np.float32)
    y = x[:, 0].astype(np.float32)
    with pytest.raises(NotImplementedError, match="feature_selector"):
        train({"objective": "reg:squarederror", "booster": "gblinear",
               "feature_selector": "greedy"}, RayDMatrix(x, y), 1,
              ray_params=RP1)
    with pytest.raises(ValueError, match="updater"):
        train({"objective": "reg:squarederror", "booster": "gblinear",
               "updater": "bogus"}, RayDMatrix(x, y), 1, ray_params=RP1)
    with pytest.raises(NotImplementedError, match="gblinear"):
        train({"objective": "rank:pairwise", "booster": "gblinear"},
              RayDMatrix(x, y, qid=np.zeros(60, np.int64)), 1,
              ray_params=RP1)
    with pytest.raises(NotImplementedError, match="tree growth"):
        train({"objective": "reg:squarederror", "booster": "gblinear",
               "monotone_constraints": "(1,0,0)"}, RayDMatrix(x, y), 1,
              ray_params=RP1)


def test_gblinear_launcher_checkpoint_roundtrip(tmp_path):
    """The launcher's canonical checkpoint/resume helpers must round-trip a
    gblinear model (they dispatch on the document's booster schema)."""
    from xgboost_ray_tpu.launcher import (
        load_round_checkpoint,
        save_round_checkpoint,
    )

    x, y, _ = _lin_data(seed=8)
    bst = train({"objective": "reg:squarederror", "booster": "gblinear",
                 "eta": 0.5}, RayDMatrix(x, y), 6, ray_params=RP1)
    path = str(tmp_path / "lin_ckpt.json")
    save_round_checkpoint(bst, path, 5)
    back, done = load_round_checkpoint(path)
    assert isinstance(back, RayLinearBooster)
    assert done == 6  # from the model itself (num_boosted_rounds)
    np.testing.assert_allclose(back.predict(x), bst.predict(x), atol=1e-6)


def test_gblinear_rejects_categorical_features():
    x = np.random.RandomState(0).randn(60, 3).astype(np.float32)
    x[:, 0] = np.random.RandomState(1).randint(0, 4, 60)
    y = x[:, 1].astype(np.float32)
    with pytest.raises(NotImplementedError, match="categorical"):
        train({"objective": "reg:squarederror", "booster": "gblinear"},
              RayDMatrix(x, y, feature_types=["c", "q", "q"]), 2,
              ray_params=RP1)


def test_gblinear_through_sklearn_with_coef():
    """The estimator facade works with booster='gblinear' and exposes the
    xgboost-sklearn coef_/intercept_ surface."""
    from xgboost_ray_tpu.sklearn import RayXGBRegressor

    x, y, w_true = _lin_data(seed=9)
    m = RayXGBRegressor(n_estimators=25, booster="gblinear", learning_rate=0.5,
                        ray_params=RP2)
    m.fit(x, y)
    p = m.predict(x)
    assert np.mean((p - y) ** 2) < 0.1
    np.testing.assert_allclose(m.coef_, w_true, atol=0.15)
    assert m.intercept_.shape == (1,)
    # tree estimators raise (coef_ is linear-only, xgboost convention)
    t = RayXGBRegressor(n_estimators=2, max_depth=2, ray_params=RP2)
    t.fit(x, y)
    with pytest.raises(AttributeError, match="gblinear"):
        _ = t.coef_


def test_gblinear_export_objective_param_keys():
    """ADVICE r5: the gblinear exporter must emit the per-objective param
    block real xgboost's loader expects (softmax_multiclass_param with
    num_class, poisson_regression_param, ...) — shared with the tree
    exporter's table, not a hardcoded reg_loss_param."""
    rng = np.random.RandomState(11)
    x = rng.randn(120, 3).astype(np.float32)
    x[np.arange(120), rng.randint(0, 3, 120)] += 2.0
    y = x.argmax(axis=1).astype(np.float32)
    bst = train({"objective": "multi:softprob", "num_class": 3,
                 "booster": "gblinear", "eta": 0.5},
                RayDMatrix(x, y), 5, ray_params=RP1)
    doc = json.loads(bst.export_xgboost_json())
    obj = doc["learner"]["objective"]
    assert obj["name"] == "multi:softprob"
    assert obj["softmax_multiclass_param"]["num_class"] == "3"
    assert "reg_loss_param" not in obj

    yp = np.maximum(x[:, 0] * 0.5 + 1.0 + 0.1 * rng.randn(120), 0.1).astype(
        np.float32)
    bstp = train({"objective": "count:poisson", "booster": "gblinear",
                  "eta": 0.3}, RayDMatrix(x, yp), 5, ray_params=RP1)
    objp = json.loads(bstp.export_xgboost_json())["learner"]["objective"]
    assert objp["name"] == "count:poisson"
    assert "poisson_regression_param" in objp


def test_gblinear_import_accepts_dict_json_and_path(tmp_path):
    """ADVICE r5: import distinguishes dict | JSON string | path explicitly
    (path-existence check, closed file handle) instead of sniffing a
    leading '{'."""
    x, y, _ = _lin_data(seed=13)
    bst = train({"objective": "reg:squarederror", "booster": "gblinear",
                 "eta": 0.5}, RayDMatrix(x, y), 5, ray_params=RP1)
    as_str = bst.export_xgboost_json()
    as_dict = json.loads(as_str)
    path = tmp_path / "lin.json"
    bst.export_xgboost_json(str(path))
    for src in (as_dict, as_str, str(path), path):
        back = RayLinearBooster.import_xgboost_json(src)
        np.testing.assert_allclose(back.predict(x), bst.predict(x), atol=1e-6)
    # a brace-prefixed FILENAME must load as a file, not parse as JSON
    brace_dir = tmp_path / "{odd}"
    brace_dir.mkdir()
    brace_path = brace_dir / "{m}.json"
    bst.export_xgboost_json(str(brace_path))
    back = RayLinearBooster.import_xgboost_json(str(brace_path))
    np.testing.assert_allclose(back.predict(x), bst.predict(x), atol=1e-6)


def test_gblinear_iteration_range_noop_forms_supported():
    """ADVICE r5: any (0, 0)-equivalent iteration_range (list, np ints) is
    the no-op full-model range and must not raise."""
    x, y, _ = _lin_data(seed=14)
    bst = train({"objective": "reg:squarederror", "booster": "gblinear",
                 "eta": 0.5}, RayDMatrix(x, y), 3, ray_params=RP1)
    want = bst.predict(x)
    for rng_form in (None, (0, 0), [0, 0],
                     (np.int64(0), np.int64(0)), np.array([0, 0])):
        got = bst.predict(x, iteration_range=rng_form)
        np.testing.assert_allclose(got, want, atol=0)
    with pytest.raises(NotImplementedError, match="iteration_range"):
        bst.predict(x, iteration_range=(0, 2))


def test_gblinear_save_reads_its_weights_back_in_one_transfer():
    """A save's ``checkpoint.readback`` brings the weights and the bias to
    the host in one batched read and names its bytes."""
    x, y, _ = _lin_data()
    res = {}
    train({"objective": "reg:squarederror", "booster": "gblinear"},
          RayDMatrix(x, y), 4, additional_results=res,
          ray_params=RayParams(num_actors=1, checkpoint_frequency=2))
    readbacks = [r for r in res["obs"]["timeline"]
                 if r["name"] == "checkpoint.readback"]
    assert len(readbacks) == 2
    for r in readbacks:
        assert r["attrs"] == {"bytes": (6 + 1) * 4, "transfers": 1}

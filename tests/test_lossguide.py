"""grow_policy=lossguide (leaf-wise best-first growth) tests.

The reference gets lossguide by forwarding params to xgboost's hist updater
(``xgboost_ray/main.py:745-752``); here it is a ``lax.scan`` best-first
grower (``ops/grow_lossguide.py``). Pinned semantics: the leaf budget is
respected, growth is depth-asymmetric (chases gain down one branch), a
budget of 2^max_depth reproduces depthwise behavior, and multi-actor model
identity holds (the per-step histograms psum-merge inside the scan).
"""

import numpy as np
import pytest

from xgboost_ray_tpu import RayDMatrix, RayParams, train

RP1 = RayParams(num_actors=1)
RP2 = RayParams(num_actors=2)


def _leaf_stats(bst):
    """(leaf_count, max_leaf_depth) per tree from the padded heap."""
    leaf = np.asarray(bst.forest.is_leaf)
    out = []
    for t in range(leaf.shape[0]):
        slots = np.nonzero(leaf[t])[0]
        depths = np.floor(np.log2(slots + 1)).astype(int)
        out.append((len(slots), int(depths.max()) if len(slots) else 0))
    return out


def _chain_data(n=600, seed=0):
    """One dominant feature with a staircase signal: the best-first grower
    keeps re-splitting along x0, producing a deep chain."""
    rng = np.random.RandomState(seed)
    x = rng.uniform(0, 1, size=(n, 4)).astype(np.float32)
    y = (np.floor(x[:, 0] * 16) + 0.01 * rng.randn(n)).astype(np.float32)
    return x, y


def test_leaf_budget_respected_and_filled():
    x, y = _chain_data()
    bst = train({"objective": "reg:squarederror", "grow_policy": "lossguide",
                 "max_leaves": 6, "max_depth": 6, "eta": 0.5, "seed": 0},
                RayDMatrix(x, y), 3, ray_params=RP2)
    for count, _ in _leaf_stats(bst):
        assert count == 6  # staircase data has gain everywhere -> budget hit


def test_lossguide_grows_asymmetric_deep_chains():
    # EXPONENTIAL staircase: variance is concentrated in the top step, so
    # best-first growth keeps re-splitting one branch (a chain) — the shape
    # depthwise growth cannot produce within the same leaf budget
    rng = np.random.RandomState(0)
    x = rng.uniform(0, 1, size=(800, 4)).astype(np.float32)
    # base-10 steps: each top step dominates ALL lower ones combined, so the
    # best split always isolates the current top step -> left-spine chain
    y = (10.0 ** np.floor(x[:, 0] * 6) + 0.01 * rng.randn(800)).astype(
        np.float32)
    bst = train({"objective": "reg:squarederror", "grow_policy": "lossguide",
                 "max_leaves": 5, "max_depth": 6, "eta": 0.5, "seed": 0},
                RayDMatrix(x, y), 2, ray_params=RP1)
    stats = _leaf_stats(bst)
    # 5 leaves balanced would sit at depth ceil(log2(5)) = 3; the chain
    # drives at least one leaf deeper
    assert any(depth > 3 for _, depth in stats), stats
    # and the model actually learns the staircase
    pred = bst.predict(x)
    base = np.full_like(y, y.mean())
    assert np.mean((pred - y) ** 2) < 0.2 * np.mean((base - y) ** 2)


def test_full_budget_matches_depthwise():
    """max_leaves = 2^max_depth removes the budget: per-node split decisions
    are policy-independent, so lossguide must reproduce the depthwise
    model."""
    rng = np.random.RandomState(1)
    x = rng.randn(500, 5).astype(np.float32)
    y = (x[:, 0] + 0.5 * x[:, 1] * x[:, 2] + 0.1 * rng.randn(500)).astype(
        np.float32)
    kw = {"objective": "reg:squarederror", "max_depth": 3, "eta": 0.4,
          "seed": 0}
    a = train(dict(kw, grow_policy="lossguide", max_leaves=8),
              RayDMatrix(x, y), 5, ray_params=RP2)
    b = train(dict(kw), RayDMatrix(x, y), 5, ray_params=RP2)
    np.testing.assert_allclose(a.predict(x), b.predict(x), atol=1e-4)
    assert [c for c, _ in _leaf_stats(a)] == [c for c, _ in _leaf_stats(b)]


def test_lossguide_multi_actor_identity():
    x, y = _chain_data(seed=2)
    kw = {"objective": "reg:squarederror", "grow_policy": "lossguide",
          "max_leaves": 7, "max_depth": 5, "eta": 0.3, "seed": 0}
    a = train(kw, RayDMatrix(x, y), 4, ray_params=RP1)
    b = train(kw, RayDMatrix(x, y), 4, ray_params=RP2)
    for field in ("feature", "split_bin", "is_leaf", "default_left"):
        np.testing.assert_array_equal(
            np.asarray(getattr(a.forest, field)),
            np.asarray(getattr(b.forest, field)), err_msg=field,
        )
    np.testing.assert_allclose(a.predict(x), b.predict(x), atol=1e-5)


def test_lossguide_binary_classification_quality():
    rng = np.random.RandomState(3)
    x = rng.randn(600, 6).astype(np.float32)
    y = ((x[:, 0] > 0) ^ (x[:, 1] > 0)).astype(np.float32)  # xor needs depth
    bst = train({"objective": "binary:logistic", "grow_policy": "lossguide",
                 "max_leaves": 16, "max_depth": 8, "eta": 0.4, "seed": 0},
                RayDMatrix(x, y), 10, ray_params=RP2)
    acc = ((bst.predict(x) > 0.5) == y).mean()
    assert acc > 0.95, acc


def test_grow_policy_validation():
    x = np.random.RandomState(0).randn(50, 3).astype(np.float32)
    y = x[:, 0].astype(np.float32)
    with pytest.raises(ValueError, match="grow_policy"):
        train({"objective": "reg:squarederror", "grow_policy": "bogus"},
              RayDMatrix(x, y), 1, ray_params=RP1)
    with pytest.raises(NotImplementedError, match="max_leaves"):
        train({"objective": "reg:squarederror", "max_leaves": 8},
              RayDMatrix(x, y), 1, ray_params=RP1)
    with pytest.raises(NotImplementedError, match="colsample_bylevel"):
        train({"objective": "reg:squarederror", "grow_policy": "lossguide",
               "colsample_bylevel": 0.5}, RayDMatrix(x, y), 1,
              ray_params=RP1)
    with pytest.raises(NotImplementedError, match="monotone"):
        train({"objective": "reg:squarederror", "grow_policy": "lossguide",
               "monotone_constraints": "(1,0,0)"}, RayDMatrix(x, y), 1,
              ray_params=RP1)
    # an explicit non-onehot hist impl must not be silently dropped
    with pytest.raises(NotImplementedError, match="hist_impl"):
        train({"objective": "reg:squarederror", "grow_policy": "lossguide",
               "hist_impl": "scatter"}, RayDMatrix(x, y), 1,
              ray_params=RP1)


def test_lossguide_with_missing_categorical_and_multiclass():
    """Feature-combination hardening: lossguide routing must honor the
    missing bucket's learned default and one-vs-rest categorical splits,
    and the engine's per-class tree loop composes with the scan grower."""
    rng = np.random.RandomState(8)
    n = 500
    y = rng.randint(0, 3, n).astype(np.float32)
    x = np.zeros((n, 3), np.float32)
    x[:, 0] = y + 0.3 * rng.randn(n)  # numeric, informative
    x[:, 1] = rng.randint(0, 4, n)  # categorical codes; partially informative
    x[y == 2, 1] = 3
    x[rng.rand(n) < 0.2, 0] = np.nan  # missing values
    bst = train({"objective": "multi:softprob", "num_class": 3,
                 "grow_policy": "lossguide", "max_leaves": 8,
                 "max_depth": 5, "eta": 0.4, "seed": 0},
                RayDMatrix(x, y, feature_types=["q", "c", "q"]), 8,
                ray_params=RP2)
    p = bst.predict(x)
    assert p.shape == (n, 3)
    assert (p.argmax(axis=1) == y).mean() > 0.8
    for count, _ in _leaf_stats(bst):
        assert count <= 8

"""The feature axis beyond 28 columns, at sizes a CPU test can hold: the dense
histogram build on every tiled path against the scatter-add, the split scan
at 300 features against a numpy argmax, and a small wide ``train()`` held to
the benchmark's plain reference by the numbers its ``compare`` gives (the
wide cell of the benchmark, ``epsilon-d8.default``, runs 2,000 features;
PERF.md section 4)."""

import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from xgboost_ray_tpu import RayDMatrix, RayParams, train
from xgboost_ray_tpu.ops import histogram as histogram_ops
from xgboost_ray_tpu.ops.histogram import (
    _hist_onehot,
    hist_onehot,
    hist_scatter,
    onehot_ftiles,
    onehot_radix,
)
from xgboost_ray_tpu.ops.split import SplitParams, find_splits

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")


def _rows(n, features, n_nodes, gh_dtype, seed=0):
    rng = np.random.RandomState(seed)
    bins = rng.randint(0, 257, size=(n, features)).astype(np.int16)
    pos = rng.randint(-1, n_nodes, size=n).astype(np.int32)
    if gh_dtype == jnp.int8:
        gh = rng.randint(-127, 128, size=(n, 2)).astype(np.int8)
    else:
        gh = rng.randn(n, 2).astype(np.float32)
    return jnp.asarray(bins), jnp.asarray(gh), jnp.asarray(pos)


# n_nodes 1 / 8 / 32 are 2 / 16 / 64 right-hand-side columns: radix 8, 4, 1
@pytest.mark.parametrize("gh_dtype", [jnp.float32, jnp.int8])
@pytest.mark.parametrize("n_nodes", [1, 8, 32])
@pytest.mark.parametrize("features", [70, 72, 300])
def test_the_tiled_dense_build_is_the_scatter_add(features, n_nodes,
                                                  gh_dtype):
    """More than one feature tile a row chunk, with padded feature columns
    (70; 300 at radix 1) and without (72; 300 at a radix over 1), at radix 1
    and over it, float and int8 gh: ``hist_onehot`` against ``hist_scatter``
    (row chunks of 1,024, the last one clamped)."""
    bins, gh, pos = _rows(2500, features, n_nodes, gh_dtype)
    radix = onehot_radix(n_nodes, 256)
    assert radix == {1: 8, 8: 4, 32: 1}[n_nodes]
    assert onehot_ftiles(features, radix) > 1
    live = jnp.where(pos >= 0, pos, 0)
    gh_live = gh * (pos >= 0)[:, None].astype(gh.dtype)
    want = np.asarray(hist_scatter(bins, gh_live, live, n_nodes, 257))
    got = np.asarray(hist_onehot(bins, gh, pos, n_nodes, 257, chunk=1024,
                                 precision="highest"))
    assert got.shape == want.shape == (n_nodes, features, 257, 2)
    if gh_dtype == jnp.int8:
        assert got.dtype == np.int32 and np.array_equal(got, want)
    else:
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("radix", [1, 2])
def test_one_tile_and_many_tiles_build_the_same_sums(radix, monkeypatch):
    """The tile count is a cost rule and no part of the result: a build
    forced to one feature a tile equals the rule's own."""
    bins, gh, pos = _rows(1500, 70, 4, jnp.float32, seed=3)
    own = np.asarray(_hist_onehot(bins, gh, pos, 4, 257, 512, "highest",
                                  radix))
    monkeypatch.setattr(histogram_ops, "onehot_ftiles",
                        lambda features, radix: features)
    single = np.asarray(_hist_onehot(bins, gh, pos, 4, 257, 512, "highest",
                                     radix))
    np.testing.assert_allclose(single, own, rtol=1e-6, atol=1e-6)


def _numpy_best(hist, node_gh, lam, mcw):
    """(gain [nodes, F, B-1], default_left) in float64, as find_splits
    defines them: candidate s sends bins <= s left, missing either way."""
    g, h = hist[..., 0].astype(np.float64), hist[..., 1].astype(np.float64)
    nb = hist.shape[2] - 1
    gl = np.cumsum(g[..., :nb], axis=-1)[..., :nb - 1]
    hl = np.cumsum(h[..., :nb], axis=-1)[..., :nb - 1]
    gp, hp = (node_gh[:, c].astype(np.float64)[:, None, None] for c in (0, 1))

    def gain(gl_, hl_):
        gr_, hr_ = gp - gl_, hp - hl_
        ok = (hl_ >= mcw) & (hr_ >= mcw)
        val = (gl_ ** 2 / (hl_ + lam) + gr_ ** 2 / (hr_ + lam)
               - gp ** 2 / (hp + lam))
        return np.where(ok, val, -np.inf)

    left = gain(gl + g[..., nb:], hl + h[..., nb:])
    right = gain(gl, hl)
    return np.maximum(left, right), left >= right


def test_the_split_scan_over_300_features_is_a_numpy_argmax():
    rng = np.random.RandomState(1)
    n_nodes, features, nbt = 8, 300, 65
    hist = np.stack([rng.randn(n_nodes, features, nbt),
                     rng.rand(n_nodes, features, nbt) + 0.05],
                    axis=-1).astype(np.float32)
    # every feature's buckets add up to the node's totals, as a real level's
    node_gh = hist[:, 0].sum(axis=1)
    hist[:, 1:, -1] += node_gh[:, None] - hist[:, 1:].sum(axis=2)
    sp = find_splits(jnp.asarray(hist), jnp.asarray(node_gh),
                     SplitParams(reg_lambda=1.0, min_child_weight=1.0))
    gain, default_left = _numpy_best(hist, node_gh, 1.0, 1.0)
    flat = gain.reshape(n_nodes, -1)
    best = flat.argmax(axis=1)
    runner_up = np.sort(flat, axis=1)[:, -2]
    assert np.all(flat.max(axis=1) - runner_up > 1e-3 * flat.max(axis=1))
    assert np.array_equal(np.asarray(sp.feature), best // (nbt - 2))
    assert np.array_equal(np.asarray(sp.split_bin), best % (nbt - 2))
    np.testing.assert_allclose(np.asarray(sp.gain), flat.max(axis=1),
                               rtol=1e-4)
    assert np.array_equal(
        np.asarray(sp.default_left),
        default_left.reshape(n_nodes, -1)[np.arange(n_nodes), best])
    assert np.asarray(sp.valid).all()


def test_a_small_wide_train_matches_the_plain_reference():
    """4,000 x 300 rows of the wide generator, depth 4, the dense build by
    name (the CPU's default is the scatter-add): the forest and the reported
    losses against ``benchmarks/reference.py``. At 4,000 rows a bin holds 16
    rows and the best split is sampling noise (the limits of the benchmark's
    own CPU rehearsals); values, covers and losses are exact arithmetic."""
    sys.path.insert(0, BENCH)
    try:
        import datagen_wide
        import reference
    finally:
        sys.path.remove(BENCH)
    x, y = datagen_wide.make(4000, 300, 2147483659, levels=257)
    params = {"objective": "binary:logistic", "tree_method": "tpu_hist",
              "eval_metric": ["logloss", "error"], "max_depth": 4,
              "eta": 0.1, "reg_lambda": 1, "min_child_weight": 1,
              "max_bin": 256, "hist_impl": "onehot"}
    dtrain = RayDMatrix(x, y)
    evals_result = {}
    bst = train(params, dtrain, 3, evals=[(dtrain, "train")],
                evals_result=evals_result,
                ray_params=RayParams(num_actors=1))
    forest = reference.forest_arrays(bst.forest)
    ref = reference.follow({"train": (x, y)}, forest, params,
                           split_trees=range(3))
    limits = {"loss": 1e-5, "leaf": 1e-3, "cover": 1e-3, "split": 0.15,
              "split_deep": 0.5}
    correct, compared = reference.compare(
        {"train": evals_result["train"]["logloss"]}, forest, ref, limits)
    assert correct, compared
    assert set(compared) == set(limits)
    # the label spreads over many columns: the trees use more than a few
    used = np.unique(forest["feature"][forest["feature"] >= 0])
    assert len(used) >= 12


def test_train_reports_the_tiles_of_every_width():
    """The ``hist.builds`` event of a wide ``train()``: the feature tiles a
    row chunk at every width, and the tile steps a round (a depth-3 tree
    builds 2, 2 and 4 columns at radix 8: three builds of one row chunk x
    three tiles of 24 features), which ``rxgb_hist_tile_steps_total`` counts
    as the builds are traced."""
    from xgboost_ray_tpu import obs

    rng = np.random.RandomState(0)
    x = rng.randn(600, 70).astype(np.float32)
    y = (x[:, 0] + x[:, 40] > 0).astype(np.float32)
    counter = obs.get_registry().counter("rxgb_hist_tile_steps_total")
    before = counter.value
    res = {}
    train({"objective": "binary:logistic", "max_depth": 3, "max_bin": 256,
           "hist_impl": "onehot"},
          RayDMatrix(x, y), num_boost_round=2, additional_results=res,
          ray_params=RayParams(num_actors=1))
    events = [r for r in res["obs"]["timeline"] if r["name"] == "hist.builds"]
    assert len(events) == 1
    assert events[0]["attrs"] == {
        "radix_by_width": {"2": 8, "4": 8},
        "ftiles_by_width": {"2": 3, "4": 3},
        "tile_steps_per_round": 9}
    programs = sum(1 for r in res["obs"]["timeline"]
                   if r["name"] == "dispatch" and r["attrs"]["first"])
    assert programs >= 1 and counter.value - before == 9 * programs
    assert obs.validate_trace_records(
        res["obs"]["timeline"], known_names=obs.TRACE_NAMES) == []

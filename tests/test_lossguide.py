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


# ---------------------------------------------------------------------------
# PR 35: the level-by-level speculative grower and the linked layout
# ---------------------------------------------------------------------------


def _plain_best_first(bins, g, h, leaves, nb, lam, mcw, max_depth=0):
    """Best-first growth as the textbook has it: a heap queue of gains over
    float64 histograms of the program's own bins; the t-th split's children
    are nodes 1 + 2t and 2 + 2t. Returns (nodes, splits in pop order, left)."""
    import heapq

    def best(rows):
        total_g, total_h = g[rows].sum(), h[rows].sum()
        out = (-np.inf, -1, -1)
        for f in range(bins.shape[1]):
            gl = np.cumsum(np.bincount(bins[rows, f], g[rows], nb))[:-1]
            hl = np.cumsum(np.bincount(bins[rows, f], h[rows], nb))[:-1]
            gr, hr = total_g - gl, total_h - hl
            gain = np.where(
                (hl >= mcw) & (hr >= mcw),
                gl ** 2 / (hl + lam) + gr ** 2 / (hr + lam)
                - total_g ** 2 / (total_h + lam), -np.inf)
            b = int(np.argmax(gain))
            if gain[b] > out[0]:
                out = (gain[b], f, b)
        return out

    nodes = [{"rows": np.arange(bins.shape[0]), "depth": 0}]
    nodes[0]["split"] = best(nodes[0]["rows"])
    queue = [(-nodes[0]["split"][0], 0)] if nodes[0]["split"][0] > 0 else []
    splits, left = [], {}
    while queue and len(splits) + 1 < leaves:
        _, i = heapq.heappop(queue)
        _, f, b = nodes[i]["split"]
        rows = nodes[i]["rows"]
        goes_left = bins[rows, f] <= b
        left[i] = len(nodes)
        for part in (rows[goes_left], rows[~goes_left]):
            node = {"rows": part, "depth": nodes[i]["depth"] + 1}
            node["split"] = best(part)
            nodes.append(node)
            if node["split"][0] > 0 and (
                    max_depth == 0 or node["depth"] < max_depth):
                heapq.heappush(queue, (-node["split"][0], len(nodes) - 1))
        splits.append((i, f, b))
    return nodes, splits, left


def _grower_case(seed, n=3000, features=5, nb=32):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, features)
    y = ((x[:, 0] + 0.5 * x[:, 1] * x[:, 2] + np.sin(3 * x[:, 3])
          + 0.3 * rng.randn(n)) > 0).astype(np.float64)
    bins = np.stack([
        np.clip(np.searchsorted(
            np.quantile(x[:, f], np.arange(1, nb) / nb), x[:, f]), 0, nb - 1)
        for f in range(features)], 1).astype(np.int32)
    # gradients of a non-zero margin: no two nodes tie in gain
    p = 1.0 / (1.0 + np.exp(-0.3 * rng.randn(n)))
    return bins, p - y, p * (1.0 - p)


@pytest.mark.parametrize("seed", range(5))
@pytest.mark.parametrize("leaves", [8, 31, 255])
def test_speculative_grower_is_plain_best_first(leaves, seed):
    """Same splits, same node numbering, same leaves as a heap-queue
    best-first over exact histograms, at ``hist_precision=highest``."""
    import jax
    import jax.numpy as jnp

    from xgboost_ray_tpu.ops.grow import GrowConfig, build_tree
    from xgboost_ray_tpu.ops.split import SplitParams

    nb, lam, mcw = 32, 1.0, 2.0
    bins, g, h = _grower_case(seed)
    cfg = GrowConfig(
        max_depth=0, max_bin=nb, grow_policy="lossguide", max_leaves=leaves,
        split=SplitParams(learning_rate=1.0, reg_lambda=lam,
                          min_child_weight=mcw),
        hist_impl="onehot", hist_precision="highest")
    cuts = jnp.zeros((bins.shape[1], nb - 1), jnp.float32)
    tree, row_value = jax.jit(lambda b, gh: build_tree(b, gh, cuts, cfg))(
        jnp.asarray(bins.astype(np.uint8)),
        jnp.asarray(np.stack([g, h], 1), jnp.float32))
    tree = jax.tree.map(np.asarray, tree)
    nodes, splits, left = _plain_best_first(bins, g, h, leaves, nb, lam, mcw)
    assert tree.left.shape == (2 * leaves - 1,)
    assert int(tree.is_leaf.sum()) == len(splits) + 1
    for i, f, b in splits:
        assert (tree.feature[i], tree.split_bin[i], tree.left[i]) == (
            f, b, left[i]), i
    want = np.zeros(bins.shape[0])
    split_nodes = {i for i, _, _ in splits}
    for i, node in enumerate(nodes):
        if i not in split_nodes:
            rows = node["rows"]
            want[rows] = -g[rows].sum() / (h[rows].sum() + lam)
            assert tree.is_leaf[i]
            np.testing.assert_allclose(tree.value[i], want[rows[0]],
                                       rtol=2e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(row_value), want, rtol=2e-4,
                               atol=1e-6)


def _deep_chain(n=4000, steps=24, seed=0):
    """A staircase whose top step outweighs all below it together: every
    best split peels the current top step off, a chain ``steps - 1`` deep."""
    rng = np.random.RandomState(seed)
    x = rng.uniform(0, 1, size=(n, 3)).astype(np.float32)
    # the steps are the feature's own values, so a bin never straddles two
    x[:, 0] = np.floor(x[:, 0] * steps)
    return x, (2.5 ** x[:, 0]).astype(np.float32)


_CHAIN_PARAMS = {"objective": "reg:squarederror", "grow_policy": "lossguide",
                 "max_depth": 0, "max_leaves": 22, "eta": 0.5, "max_bin": 64,
                 "min_child_weight": 1.0, "seed": 0}


@pytest.fixture(scope="module")
def chain_model():
    x, y = _deep_chain()
    res = {}
    bst = train(_CHAIN_PARAMS, RayDMatrix(x, y), 2, ray_params=RP1,
                additional_results=res)
    return x, y, bst, res


def _numpy_walk(forest, x):
    """Sum of the leaf values a plain walk reaches, tree by tree."""
    out = np.zeros(x.shape[0])
    for t in range(forest.feature.shape[0]):
        for r in range(x.shape[0]):
            i = 0
            while not forest.is_leaf[t, i]:
                right = x[r, forest.feature[t, i]] >= forest.threshold[t, i]
                i = int(forest.left[t, i]) + int(right)
            out[r] += forest.value[t, i]
    return out


def test_a_chain_deeper_than_any_heap_trains_and_predicts(chain_model):
    x, y, bst, res = chain_model
    assert bst.forest.left is not None and bst.forest.left.shape == (2, 43)
    depths = bst.node_depths()
    assert depths[np.asarray(bst.forest.is_leaf)].max() > 14
    assert bst.max_depth == depths.max() > 14
    margin = bst.predict(x[:200], output_margin=True)
    np.testing.assert_allclose(
        margin, bst.base_score + _numpy_walk(bst.forest, x[:200]),
        rtol=1e-5)
    # learns the staircase's top steps
    pred = bst.predict(x)
    assert np.mean((pred - y) ** 2) < 0.5 * np.mean((y.mean() - y) ** 2)
    # every row's leaf, by the walk that serves both layouts
    leaf = bst.predict(x[:50], pred_leaf=True)
    assert np.asarray(bst.forest.is_leaf)[np.arange(2)[None, :], leaf].all()


def test_a_linked_forest_saves_loads_and_round_trips_xgboost_json(
        chain_model, tmp_path):
    from xgboost_ray_tpu.models.booster import RayXGBoostBooster

    x, _, bst, _ = chain_model
    path = str(tmp_path / "m.json")
    bst.save_model(path)
    back = RayXGBoostBooster.load_model(path)
    np.testing.assert_array_equal(back.forest.left, bst.forest.left)
    np.testing.assert_array_equal(back.predict(x), bst.predict(x))
    assert back.save_raw() == bst.save_raw()
    # deeper than the heap importer's 16 levels: comes back linked
    again = RayXGBoostBooster.import_xgboost_json(bst.export_xgboost_json())
    assert again.forest.left is not None and again.max_depth == bst.max_depth
    assert (again.params.grow_policy, again.params.max_depth) == (
        "lossguide", 0)
    np.testing.assert_allclose(again.predict(x, output_margin=True),
                               bst.predict(x, output_margin=True), rtol=1e-6)
    dump = bst.get_dump(with_stats=True)
    assert dump[0].count("leaf=") == int(bst.forest.is_leaf[0].sum())
    assert len(bst.trees_to_dataframe()) == int(
        (bst.forest.is_leaf | (bst.forest.feature >= 0)).sum())


def test_each_walk_that_needs_a_heap_refuses_a_linked_forest(chain_model):
    from xgboost_ray_tpu import serve
    from xgboost_ray_tpu.ops.node_array import forest_to_node_array

    x, y, bst, _ = chain_model
    with pytest.raises(NotImplementedError, match="Exact TreeSHAP.*linked"):
        bst.predict(x[:4], pred_contribs=True)
    with pytest.raises(NotImplementedError,
                       match="SHAP interaction values.*linked"):
        bst.predict(x[:4], pred_interactions=True)
    with pytest.raises(NotImplementedError, match="node_array.*linked"):
        forest_to_node_array(bst.forest, bst.max_depth)
    with pytest.raises(NotImplementedError, match="node_array.*linked"):
        serve.create_server(bst, layout="node_array")
    # the walks that take it: Saabas contributions sum to the margin
    contribs = bst.predict(x[:20], pred_contribs=True, approx_contribs=True)
    # (float32 differences of node weights up to the top step's 1e9)
    np.testing.assert_allclose(
        contribs.sum(axis=1), bst.predict(x[:20], output_margin=True),
        rtol=1e-4, atol=1e-6 * float(y.max()))


def test_the_grow_event_and_counters_equal_a_hand_count():
    """3 leaves: the root's build, one pass for the root's children, one for
    the better child's; 1 + 2 + 2 nodes evaluated, 2 splits; 2 rounds."""
    from xgboost_ray_tpu import obs

    x, y = _chain_data(n=400)
    registry = obs.get_registry()
    before = [registry.counter(n).value for n in (
        "rxgb_lossguide_passes_total", "rxgb_lossguide_nodes_evaluated_total")]
    res = {}
    bst = train({"objective": "reg:squarederror", "grow_policy": "lossguide",
                 "max_leaves": 3, "max_depth": 0, "eta": 0.5, "seed": 0},
                RayDMatrix(x, y), 2, ray_params=RP2, additional_results=res)
    events = [r for r in res["obs"]["timeline"]
              if r["kind"] == "event" and r["name"] == "lossguide.grow"]
    assert len(events) == 1
    assert events[0]["attrs"] == {
        "passes_per_round": 3.0, "nodes_evaluated_per_round": 5.0,
        "splits_per_round": 2.0, "deepest_leaf": 2, "table_overflows": 0,
        "rounds": 2}
    assert res["lossguide_passes_per_round"] == 3.0
    after = [registry.counter(n).value for n in (
        "rxgb_lossguide_passes_total", "rxgb_lossguide_nodes_evaluated_total")]
    assert [a - b for a, b in zip(after, before)] == [6, 10]
    assert (bst.forest.is_leaf.sum(axis=1) == 3).all()
    # one histogram merge a pass on the mesh, counted where the bytes are
    assert res["collectives_per_round"] >= 3


def test_a_positive_max_depth_still_bounds_a_lossguide_tree():
    x, y = _deep_chain(n=1500)
    bst = train(dict(_CHAIN_PARAMS, max_depth=3, max_leaves=8),
                RayDMatrix(x, y), 2, ray_params=RP1)
    assert bst.forest.left is None  # a depth-bounded tree is a padded heap
    assert bst.forest.feature.shape[1] == 15
    stats = _leaf_stats(bst)
    assert all(depth <= 3 for _, depth in stats), stats
    assert all(2 <= count <= 8 for count, _ in stats), stats


def test_four_devices_grow_one_devices_unbounded_forest():
    x, y = _deep_chain(n=2000, steps=12, seed=3)
    kw = dict(_CHAIN_PARAMS, max_leaves=10)
    a = train(kw, RayDMatrix(x, y), 3, ray_params=RP1)
    b = train(kw, RayDMatrix(x, y), 3, ray_params=RayParams(num_actors=4))
    for field in ("feature", "split_bin", "is_leaf", "default_left", "left"):
        np.testing.assert_array_equal(
            np.asarray(getattr(a.forest, field)),
            np.asarray(getattr(b.forest, field)), err_msg=field)
    np.testing.assert_allclose(a.predict(x), b.predict(x), rtol=1e-5)


def test_max_depth_zero_needs_lossguide_and_a_leaf_budget():
    x = np.random.RandomState(0).randn(50, 3).astype(np.float32)
    y = x[:, 0].astype(np.float32)
    with pytest.raises(ValueError, match="max_depth must be >= 1"):
        train({"objective": "reg:squarederror", "max_depth": 0},
              RayDMatrix(x, y), 1, ray_params=RP1)
    with pytest.raises(ValueError, match="needs max_leaves > 0"):
        train({"objective": "reg:squarederror", "grow_policy": "lossguide",
               "max_depth": 0}, RayDMatrix(x, y), 1, ray_params=RP1)
    with pytest.raises(NotImplementedError, match="dart.*max_depth=0"):
        train({"objective": "reg:squarederror", "grow_policy": "lossguide",
               "max_depth": 0, "max_leaves": 4, "booster": "dart"},
              RayDMatrix(x, y), 1, ray_params=RP1)

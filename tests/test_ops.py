"""Unit tests for the core device ops: binning, histograms, splits, growth.

Models the reference's unit layer (``xgboost_ray/tests/test_matrix.py`` level
of granularity) but for the compute core our build owns.
"""

import numpy as np
import os
import pytest

import jax
import jax.numpy as jnp

from xgboost_ray_tpu.ops import binning
from xgboost_ray_tpu.ops.histogram import build_histogram, hist_onehot, hist_scatter, node_sums
from xgboost_ray_tpu.ops.split import SplitParams, find_splits, leaf_weight
from xgboost_ray_tpu.ops.grow import GrowConfig, build_tree, predict_tree_binned
from xgboost_ray_tpu.ops.objectives import get_objective
from xgboost_ray_tpu.ops.metrics import compute_metric


def test_binning_roundtrip_basic():
    rng = np.random.RandomState(0)
    x = rng.randn(500, 4).astype(np.float32)
    cuts = binning.sketch_cuts_np(x, max_bin=16)
    assert cuts.shape == (4, 15)
    assert np.all(np.diff(cuts, axis=1) >= 0)
    b = binning.bin_matrix_np(x, cuts, max_bin=16)
    assert b.dtype == np.uint8
    assert b.max() <= 15  # no missing values present
    # roughly equal occupancy per bin
    counts = np.bincount(b[:, 0], minlength=16)
    assert counts.min() > 0


def test_binning_missing_goes_to_reserved_bin():
    x = np.array([[1.0], [np.nan], [2.0], [3.0]], dtype=np.float32)
    cuts = binning.sketch_cuts_np(x, max_bin=4)
    b = binning.bin_matrix_np(x, cuts, max_bin=4)
    assert b[1, 0] == 4  # missing bucket
    assert b[0, 0] < 4


def test_binning_device_matches_host():
    rng = np.random.RandomState(1)
    x = rng.randn(200, 3).astype(np.float32)
    x[5, 1] = np.nan
    cuts = binning.sketch_cuts_np(x, max_bin=8)
    host = binning.bin_matrix_np(x, cuts, max_bin=8)
    dev = np.asarray(binning.bin_matrix(jnp.asarray(x), jnp.asarray(cuts), 8))
    np.testing.assert_array_equal(host, dev)


def _awkward_rows(max_bin, n=600):
    """Rows and cuts that sit on every edge of ``bin(x) = #cuts <= x``:
    column 0 continuous with NaN, +-inf, +-0.0, the first and last cut, a
    value below the first and one above the last; 1 few distinct values
    (the sketch repeats cuts); 2 constant; 3 categorical codes under code
    cuts ``k + 0.5``; 4 every one of its own cuts, once each; 5 all NaN."""
    rng = np.random.RandomState(max_bin)
    x = rng.randn(n, 6).astype(np.float32)
    x[:, 1] = np.round(x[:, 1])
    x[:, 2] = 3.0
    x[:, 3] = rng.randint(0, min(max_bin, 7), n)
    cuts = binning.sketch_cuts_np(x, max_bin=max_bin)
    cuts[3] = np.arange(max_bin - 1, dtype=np.float32) + 0.5
    x[rng.rand(n, 6) < 0.05] = np.nan
    first, last = cuts[0, 0], cuts[0, -1]
    x[:10, 0] = [np.inf, -np.inf, np.nan, -0.0, 0.0, first, last,
                 np.nextafter(first, -np.inf), np.nextafter(last, np.inf),
                 np.nextafter(last, -np.inf)]
    own = cuts[4][: n - 10]
    x[10:10 + own.size, 4] = own
    x[:, 5] = np.nan
    return x, cuts


@pytest.mark.parametrize("max_bin", [8, 255, 256, 512])
def test_device_binning_counts_cuts_like_the_host_loop(max_bin):
    """``bin_matrix`` against the per-feature ``np.searchsorted`` loop, bit
    for bit and in the same dtype (uint8 through ``max_bin`` 255, int16
    beyond), eagerly and under ``jit``."""
    x, cuts = _awkward_rows(max_bin)
    assert np.any(np.diff(cuts[1]) == 0), "column 1 should repeat cuts"
    want = binning._bin_matrix_np_loop(x, cuts, max_bin)
    assert want.dtype == (np.uint8 if max_bin <= 255 else np.int16)
    assert want[2, 0] == max_bin and np.all(want[:, 5] == max_bin)
    assert want[0, 0] == max_bin - 1 and want[1, 0] == 0  # +inf, -inf
    for fn in (binning.bin_matrix, jax.jit(binning.bin_matrix, static_argnums=2)):
        got = np.asarray(fn(jnp.asarray(x), jnp.asarray(cuts), max_bin))
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(got, want)


def test_device_binning_with_unordered_nan_cuts():
    """A feature holding -inf gets NaN cuts from the device sketch (``mn +
    edges * inf``) and one holding +inf gets +inf cuts: the count still
    equals the host's search, which sorts NaN last."""
    x = np.random.RandomState(5).randn(64, 2).astype(np.float32)
    x[0, 0], x[1, 1] = -np.inf, np.inf
    cuts = np.sort(np.random.RandomState(6).randn(2, 7).astype(np.float32), 1)
    cuts[0], cuts[1, -3:] = np.nan, np.inf
    got = np.asarray(binning.bin_matrix(jnp.asarray(x), jnp.asarray(cuts), 8))
    np.testing.assert_array_equal(got, binning._bin_matrix_np_loop(x, cuts, 8))


def _binning_programs(num_actors):
    """The lowered-from records of ``engine.sketch_cuts`` and
    ``engine.bin_matrix`` (an evaluation set's binning) of a small engine."""
    from xgboost_ray_tpu import progreg
    from xgboost_ray_tpu.engine import TpuEngine
    from xgboost_ray_tpu.params import parse_params

    rng = np.random.RandomState(7)
    x = rng.randn(256, 5).astype(np.float32)
    x[rng.rand(256, 5) < 0.05] = np.nan
    y = (x[:, 0] > 0).astype(np.float32)
    train_set = [{"data": x, "label": y}]
    valid_set = [{"data": x[:64], "label": y[:64]}]
    params = parse_params({"objective": "binary:logistic", "max_depth": 2,
                           "max_bin": 256})
    with progreg.capture():
        progreg.clear()
        TpuEngine(train_set, params, num_actors,
                  evals=[(train_set, "train"), (valid_set, "valid")])
        recs = {r.name: r for r in progreg.records()}
    progreg.clear()
    return recs["engine.sketch_cuts"], recs["engine.bin_matrix"]


def _primitives_under(jaxpr, scope, inside=False):
    """Primitive names of every equation whose name stack holds ``scope``
    (equations of a sub-jaxpr inherit their caller's stack)."""
    found = []
    for eqn in jaxpr.eqns:
        here = inside or scope in str(eqn.source_info.name_stack).split("/")
        if here:
            found.append(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _primitives_under(sub, scope, here)
    return found


@pytest.mark.parametrize("num_actors", [1, 4])
def test_the_binning_programs_search_nothing(num_actors):
    """No per-value search: what ``engine.sketch_cuts`` runs under its ``bin``
    scope, and the whole of ``engine.bin_matrix``, holds no gather, no loop
    and no sort -- on one device and as the 4-device mesh's program (a
    binary search is a ``while`` of gathers from the cut table, 25.8 of the
    26.6 s the chip took to bin 11M x 28 values; PERF.md section 6, PR 32)."""
    sketch_cuts, bin_alone = _binning_programs(num_actors)
    searching = {"gather", "while", "scan", "sort", "cond"}
    under_bin = _primitives_under(
        jax.make_jaxpr(sketch_cuts.fn)(*sketch_cuts.abstract_args).jaxpr, "bin")
    assert {"ge", "dot_general", "psum"} <= set(under_bin), under_bin
    assert not searching & set(under_bin), sorted(searching & set(under_bin))
    text = jax.jit(bin_alone.fn).lower(*bin_alone.abstract_args).as_text()
    assert "stablehlo.compare" in text and "stablehlo.dot_general" in text
    for op in ("gather", "while", "sort", "case"):
        assert "stablehlo." + op not in text, op


def test_device_sketch_close_to_exact_quantiles():
    rng = np.random.RandomState(2)
    x = rng.randn(20000, 2).astype(np.float32)
    valid = jnp.ones((x.shape[0],), bool)
    mn, mx = binning.feature_min_max(jnp.asarray(x), valid)
    hist = binning.sketch_histogram(jnp.asarray(x), valid, mn, mx)
    cuts = np.asarray(binning.cuts_from_sketch(mn, mx, hist, max_bin=16))
    exact = binning.sketch_cuts_np(x, max_bin=16)
    assert np.max(np.abs(cuts - exact)) < 0.05  # fine-histogram approximation


def test_histogram_impls_agree():
    rng = np.random.RandomState(3)
    n, f, nb = 300, 5, 8
    bins = rng.randint(0, nb + 1, size=(n, f)).astype(np.uint8)
    gh = rng.randn(n, 2).astype(np.float32)
    pos = rng.randint(0, 4, size=n).astype(np.int32)
    h1 = np.asarray(hist_scatter(jnp.asarray(bins), jnp.asarray(gh), jnp.asarray(pos), 4, nb + 1))
    h2 = np.asarray(
        hist_onehot(jnp.asarray(bins), jnp.asarray(gh), jnp.asarray(pos), 4, nb + 1, chunk=64)
    )
    np.testing.assert_allclose(h1, h2, atol=1e-4)
    # cross-check against numpy accumulation
    ref = np.zeros((4, f, nb + 1, 2), np.float32)
    for i in range(n):
        for j in range(f):
            ref[pos[i], j, bins[i, j]] += gh[i]
    np.testing.assert_allclose(h1, ref, atol=1e-4)


def test_node_sums():
    gh = jnp.array([[1.0, 2.0], [3.0, 4.0], [5.0, 6.0]])
    pos = jnp.array([0, 1, 0])
    s = np.asarray(node_sums(gh, pos, 2))
    np.testing.assert_allclose(s, [[6.0, 8.0], [3.0, 4.0]])


def test_find_splits_picks_obvious_split():
    # one node, one feature, 4 bins: grads +1 in low bins, -1 in high bins
    nbt = 5  # 4 bins + missing
    hist = np.zeros((1, 1, nbt, 2), np.float32)
    hist[0, 0, 0] = [10.0, 10.0]
    hist[0, 0, 1] = [10.0, 10.0]
    hist[0, 0, 2] = [-10.0, 10.0]
    hist[0, 0, 3] = [-10.0, 10.0]
    node_gh = jnp.asarray(hist[:, 0, :, :].sum(axis=1))
    sp = find_splits(jnp.asarray(hist), node_gh, SplitParams(min_child_weight=0.0))
    assert bool(sp.valid[0])
    assert int(sp.split_bin[0]) == 1  # bins {0,1} left, {2,3} right
    assert float(sp.gain[0]) > 0


def test_find_splits_respects_min_child_weight():
    nbt = 5
    hist = np.zeros((1, 1, nbt, 2), np.float32)
    hist[0, 0, 0] = [5.0, 0.5]
    hist[0, 0, 3] = [-5.0, 0.5]
    node_gh = jnp.asarray(hist[:, 0, :, :].sum(axis=1))
    sp = find_splits(jnp.asarray(hist), node_gh, SplitParams(min_child_weight=10.0))
    assert not bool(sp.valid[0])


def test_find_splits_learns_missing_direction():
    # missing rows have negative grads -> should go right with the negative bin
    nbt = 4  # 3 bins + missing
    hist = np.zeros((1, 1, nbt, 2), np.float32)
    hist[0, 0, 0] = [8.0, 8.0]
    hist[0, 0, 2] = [-8.0, 8.0]
    hist[0, 0, 3] = [-4.0, 4.0]  # missing bucket, negative grad
    node_gh = jnp.asarray(hist[:, 0, :, :].sum(axis=1))
    sp = find_splits(jnp.asarray(hist), node_gh, SplitParams(min_child_weight=0.0))
    assert bool(sp.valid[0])
    assert not bool(sp.default_left[0])  # missing joins the negative (right) side


def _fit_one_tree(x, g, h, max_depth=3, max_bin=8, **split_kw):
    cuts = binning.sketch_cuts_np(x, max_bin=max_bin)
    bins = binning.bin_matrix_np(x, cuts, max_bin=max_bin)
    gh = jnp.asarray(np.stack([g, h], axis=1).astype(np.float32))
    cfg = GrowConfig(
        max_depth=max_depth,
        max_bin=max_bin,
        split=SplitParams(learning_rate=1.0, reg_lambda=0.0, min_child_weight=0.0, **split_kw),
    )
    tree, row_value = build_tree(jnp.asarray(bins), gh, jnp.asarray(cuts), cfg)
    return tree, np.asarray(row_value), bins, cfg


def test_build_tree_fits_step_function():
    # discrete feature values so quantile cuts separate classes exactly;
    # y = 1 for x<0 else -1; squarederror from margin 0 -> g = -y, h = 1
    rng = np.random.RandomState(4)
    x = rng.choice([-0.75, -0.25, 0.25, 0.75], size=(400, 1)).astype(np.float32)
    y = np.where(x[:, 0] < 0, 1.0, -1.0).astype(np.float32)
    tree, row_value, bins, cfg = _fit_one_tree(x, -y, np.ones_like(y), max_depth=2)
    np.testing.assert_allclose(row_value, y, atol=1e-3)
    # binned walk agrees with row_value from training
    walked = np.asarray(
        predict_tree_binned(tree, jnp.asarray(bins), cfg.max_depth, cfg.max_bin)
    )
    np.testing.assert_allclose(walked, row_value, atol=1e-5)


def test_build_tree_row_values_match_leaf_math():
    rng = np.random.RandomState(5)
    x = rng.randn(200, 3).astype(np.float32)
    g = rng.randn(200).astype(np.float32)
    h = np.ones(200, np.float32)
    tree, row_value, bins, cfg = _fit_one_tree(x, g, h, max_depth=3)
    # each row's value must equal a leaf value of the tree
    leaf_vals = np.asarray(tree.value)[np.asarray(tree.is_leaf)]
    for v in row_value[:20]:
        assert np.min(np.abs(leaf_vals - v)) < 1e-5


def test_objectives_shapes_and_values():
    m = jnp.zeros((5, 1))
    y = jnp.array([0.0, 1.0, 1.0, 0.0, 1.0])
    w = jnp.ones((5,))
    obj = get_objective("binary:logistic")
    g, h = obj.grad_hess(m, y, w)
    np.testing.assert_allclose(np.asarray(g[:, 0]), [0.5, -0.5, -0.5, 0.5, -0.5])
    np.testing.assert_allclose(np.asarray(h[:, 0]), [0.25] * 5)
    obj2 = get_objective("reg:squarederror")
    g2, h2 = obj2.grad_hess(jnp.full((5, 1), 2.0), y, w)
    np.testing.assert_allclose(np.asarray(g2[:, 0]), np.asarray(2.0 - y))
    obj3 = get_objective("multi:softprob", num_class=3)
    g3, h3 = obj3.grad_hess(jnp.zeros((5, 3)), jnp.array([0.0, 1.0, 2.0, 0.0, 1.0]), w)
    assert g3.shape == (5, 3)
    np.testing.assert_allclose(np.asarray(g3).sum(axis=1), 0.0, atol=1e-6)


def test_metrics_basic():
    m = np.array([10.0, 10.0, -10.0, 10.0])
    y = np.array([0.0, 1.0, 0.0, 1.0])
    assert compute_metric("error", m, y) == pytest.approx(0.25)
    assert compute_metric("logloss", np.array([-10.0, 10.0, -10.0, 10.0]), y) < 0.2
    r = compute_metric("rmse", np.array([1.0, 2.0]), np.array([0.0, 4.0]))
    assert r == pytest.approx(np.sqrt((1 + 4) / 2))
    auc = compute_metric("auc", np.array([0.1, 0.9, 0.2, 0.8]), np.array([0, 1, 0, 1]))
    assert auc == pytest.approx(1.0)


def test_ndcg_metric_perfect_and_inverted():
    ptr = np.array([0, 3, 6])
    y = np.array([2.0, 1.0, 0.0, 0.0, 1.0, 2.0])
    perfect = np.array([3.0, 2.0, 1.0, 1.0, 2.0, 3.0])
    assert compute_metric("ndcg", perfect, y, group_ptr=ptr) == pytest.approx(1.0)
    inverted = -perfect
    assert compute_metric("ndcg", inverted, y, group_ptr=ptr) < 0.8


def test_ranking_gradients_point_the_right_way():
    from xgboost_ray_tpu.ops.ranking import build_group_rows, make_rank_grad_hess

    qid = np.array([0, 0, 0, 1, 1])
    rows, ptr = build_group_rows(qid)
    assert rows.shape == (2, 3)
    label = jnp.array([2.0, 1.0, 0.0, 1.0, 0.0])
    margin = jnp.zeros((5, 1))
    w = jnp.ones((5,))
    gh = make_rank_grad_hess("rank:pairwise")
    g, h = gh(margin, label, w, jnp.asarray(rows))
    g = np.asarray(g[:, 0])
    assert g[0] < g[1] < g[2]  # most relevant gets most negative grad (pushed up)
    assert g[3] < g[4]
    assert np.all(np.asarray(h) > 0)


@pytest.mark.parametrize("name,says", [
    ("partition", "takes its place"),
    ("mixed", "takes its place"),
    ("pallas", "takes its place"),
    ("bogus", "auto | scatter | onehot"),
])
def test_unknown_hist_impl_rejected(name, says):
    """The builds over node-sorted row blocks (``partition``, ``mixed``, the
    ``pallas`` kernel before them) lost on the chip and are gone (rationale
    in ops/grow.py's module docstring); asking for one, or for a name that
    never was, fails loudly at parse time with what to use, never silently
    runs another build."""
    from xgboost_ray_tpu.params import parse_params

    with pytest.raises(ValueError, match="Unknown hist_impl") as err:
        parse_params({"hist_impl": name})
    assert says in str(err.value)
    assert ("takes its place" in str(err.value)) == (name != "bogus")
    for ok in ("auto", "scatter", "onehot"):
        assert parse_params({"hist_impl": ok}).hist_impl == ok


def test_build_tree_impls_produce_identical_trees():
    """The scatter-add and the dense MXU build must grow the exact same
    tree."""
    rng = np.random.RandomState(12)
    x = rng.randn(800, 6).astype(np.float32)
    g = rng.randn(800).astype(np.float32)
    h = np.ones(800, np.float32)
    cuts = binning.sketch_cuts_np(x, max_bin=16)
    bins = binning.bin_matrix_np(x, cuts, max_bin=16)
    gh = jnp.asarray(np.stack([g, h], 1))
    outs = {}
    for impl in ("scatter", "onehot"):
        cfg = GrowConfig(max_depth=5, max_bin=16,
                         split=SplitParams(learning_rate=1.0), hist_impl=impl)
        tree, rv = build_tree(jnp.asarray(bins), gh, jnp.asarray(cuts), cfg)
        outs[impl] = (np.asarray(rv), np.asarray(tree.feature),
                      np.asarray(tree.value))
    np.testing.assert_allclose(outs["onehot"][0], outs["scatter"][0], atol=1e-4)
    np.testing.assert_array_equal(outs["onehot"][1], outs["scatter"][1])
    np.testing.assert_allclose(outs["onehot"][2], outs["scatter"][2], atol=1e-4)


def test_sibling_subtraction_matches_direct_build():
    """Deriving the larger child as parent - smaller child must grow the same
    tree as building both children directly (fp-subtraction noise aside)."""
    rng = np.random.RandomState(21)
    x = rng.randn(1000, 5).astype(np.float32)
    g = rng.randn(1000).astype(np.float32)
    h = np.abs(rng.randn(1000)).astype(np.float32) + 0.5
    cuts = binning.sketch_cuts_np(x, max_bin=32)
    bins = binning.bin_matrix_np(x, cuts, max_bin=32)
    gh = jnp.asarray(np.stack([g, h], 1))
    outs = {}
    impls = ("scatter", "onehot")
    for impl in impls:
        for sib in (True, False):
            cfg = GrowConfig(max_depth=6, max_bin=32,
                             split=SplitParams(learning_rate=1.0),
                             hist_impl=impl, sibling_subtract=sib)
            tree, rv = build_tree(jnp.asarray(bins), gh, jnp.asarray(cuts), cfg)
            outs[(impl, sib)] = (np.asarray(rv), np.asarray(tree.feature),
                                 np.asarray(tree.value))
    for impl in impls:
        np.testing.assert_array_equal(
            outs[(impl, True)][1], outs[(impl, False)][1]
        )
        np.testing.assert_allclose(
            outs[(impl, True)][0], outs[(impl, False)][0], atol=1e-3
        )
        np.testing.assert_allclose(
            outs[(impl, True)][2], outs[(impl, False)][2], atol=1e-3
        )


def test_new_objectives_train_and_improve():
    """binary:hinge / reg:squaredlogerror / reg:pseudohubererror train
    end-to-end and their default metrics improve."""
    from xgboost_ray_tpu import RayDMatrix, RayParams, train

    rng = np.random.RandomState(30)
    x = rng.randn(400, 4).astype(np.float32)
    yb = (x[:, 0] > 0).astype(np.float32)
    ypos = np.exp(x[:, 0] * 0.5 + 0.1 * rng.randn(400)).astype(np.float32)
    yreg = (2.0 * x[:, 0] + rng.randn(400) * 0.3).astype(np.float32)
    cases = [
        ("binary:hinge", yb, "error"),
        ("reg:squaredlogerror", ypos, "rmsle"),
        ("reg:pseudohubererror", yreg, "mphe"),
    ]
    for objective, y, metric in cases:
        er = {}
        bst = train({"objective": objective, "eval_metric": [metric]},
                    RayDMatrix(x, y), 10,
                    evals=[(RayDMatrix(x, y), "t")], evals_result=er,
                    ray_params=RayParams(num_actors=2))
        trace = er["t"][metric]
        assert trace[-1] <= trace[0], (objective, er)
        assert trace[-1] < 0.5, (objective, er)
        assert bst.num_boosted_rounds() == 10


def test_hinge_predicts_hard_labels():
    from xgboost_ray_tpu import RayDMatrix, RayParams, train

    rng = np.random.RandomState(31)
    x = rng.randn(300, 3).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.float32)
    bst = train({"objective": "binary:hinge"}, RayDMatrix(x, y), 8,
                ray_params=RayParams(num_actors=2))
    pred = bst.predict(x)
    assert set(np.unique(pred)) <= {0.0, 1.0}
    assert (pred == y).mean() > 0.9


def test_mape_rmsle_metrics_values():
    from xgboost_ray_tpu.ops.metrics import compute_metric

    pred = np.array([1.0, 2.0, 4.0], np.float32)
    y = np.array([1.0, 1.0, 2.0], np.float32)
    mape = compute_metric("mape", pred, y)
    assert abs(mape - np.mean([0.0, 1.0, 1.0])) < 1e-6
    rmsle = compute_metric("rmsle", pred, y)
    expect = np.sqrt(np.mean((np.log1p(pred) - np.log1p(y)) ** 2))
    assert abs(rmsle - expect) < 1e-6


def test_huber_slope_changes_model_and_sle_validates():
    from xgboost_ray_tpu import RayDMatrix, RayParams, train

    rng = np.random.RandomState(32)
    x = rng.randn(300, 3).astype(np.float32)
    y = (2 * x[:, 0] + rng.randn(300)).astype(np.float32)
    preds = {}
    for slope in (1.0, 5.0):
        bst = train({"objective": "reg:pseudohubererror", "huber_slope": slope},
                    RayDMatrix(x, y), 5, ray_params=RayParams(num_actors=2))
        preds[slope] = bst.predict(x)
    assert not np.allclose(preds[1.0], preds[5.0])

    with pytest.raises(ValueError, match="labels > -1"):
        train({"objective": "reg:squaredlogerror"},
              RayDMatrix(x, np.full(300, -2.0, np.float32)), 2,
              ray_params=RayParams(num_actors=2))


def test_hist_missing_bucket_reconstruction():
    """The dense build covers only the regular bins on the MXU and
    reconstructs the missing bucket as node_total - sum(regular); verify
    against scatter."""
    import numpy as np
    import jax.numpy as jnp
    from xgboost_ray_tpu.ops.histogram import hist_onehot, hist_scatter

    rng = np.random.RandomState(3)
    n, f, nbt = 5000, 5, 17  # max_bin=16, bucket 16 == missing
    bins = rng.randint(0, nbt, size=(n, f)).astype(np.int32)
    gh = rng.randn(n, 2).astype(np.float32)
    pos = rng.randint(0, 4, size=n).astype(np.int32)
    ref = np.asarray(hist_scatter(jnp.asarray(bins), jnp.asarray(gh),
                                  jnp.asarray(pos), 4, nbt))
    assert np.abs(ref[:, :, nbt - 1, :]).max() > 0  # missing bucket populated
    got = np.asarray(hist_onehot(jnp.asarray(bins), jnp.asarray(gh),
                                 jnp.asarray(pos), 4, nbt))
    np.testing.assert_allclose(got, ref, atol=2e-3)


def test_hist_precision_param_accepted_and_fast_close():
    """hist_precision plumbs through params; "fast" (bf16 one-hot + bf16 gh,
    ~0.2% bin-sum rounding) must not change model QUALITY — individual
    predictions may shift slightly where a split threshold moves by one bin."""
    import numpy as np
    from xgboost_ray_tpu import RayDMatrix, RayParams, train

    rng = np.random.RandomState(4)
    x = rng.randn(2000, 6).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.float32)
    preds = {}
    for prec in ("highest", "fast"):
        bst = train({"objective": "binary:logistic", "max_depth": 4,
                     "hist_precision": prec, "hist_impl": "onehot"},
                    RayDMatrix(x, y), 5,
                    ray_params=RayParams(num_actors=2))
        preds[prec] = bst.predict(x)
    # same hard labels, tiny mean probability shift
    assert ((preds["fast"] > 0.5) == (preds["highest"] > 0.5)).mean() > 0.995
    assert np.abs(preds["fast"] - preds["highest"]).mean() < 2e-3


@pytest.mark.parametrize("hist_impl", ["scatter", "onehot"])
def test_sibling_subtraction_holds_on_a_skewed_shard(hist_impl):
    """The smaller child is chosen from GLOBAL (allreduced) counts; on a
    skewed shard it can hold most of the shard's rows. Fake the count
    allreduce so the 'global' choice is the locally-BIGGER child: the build
    streams every row, so it grows exactly the tree the direct
    (no-subtraction) build grows."""
    import numpy as np
    import jax.numpy as jnp
    from xgboost_ray_tpu.ops import binning
    from xgboost_ray_tpu.ops.grow import GrowConfig, build_tree
    from xgboost_ray_tpu.ops.split import SplitParams

    rng = np.random.RandomState(22)
    x = rng.randn(1200, 5).astype(np.float32)
    g = rng.randn(1200).astype(np.float32)
    h = np.abs(rng.randn(1200)).astype(np.float32) + 0.5
    cuts = binning.sketch_cuts_np(x, max_bin=32)
    bins = binning.bin_matrix_np(x, cuts, max_bin=32)
    gh = jnp.asarray(np.stack([g, h], 1))

    def skew_allreduce(t):
        # pretend a peer shard holds 3x this shard's rows with left/right
        # swapped within every parent: the globally-smaller child becomes
        # this shard's locally-bigger one
        if t.ndim == 1 and t.shape[0] % 2 == 0:
            swapped = t.reshape(-1, 2)[:, ::-1].reshape(-1)
            return t + 3.0 * swapped
        return t

    outs = {}
    for sib in (True, False):
        cfg = GrowConfig(max_depth=5, max_bin=32,
                         split=SplitParams(learning_rate=1.0),
                         hist_impl=hist_impl, sibling_subtract=sib)
        tree, rv = build_tree(jnp.asarray(bins), gh, jnp.asarray(cuts), cfg,
                              allreduce=skew_allreduce)
        outs[sib] = (np.asarray(tree.feature), np.asarray(rv))
    np.testing.assert_array_equal(outs[True][0], outs[False][0])
    np.testing.assert_allclose(outs[True][1], outs[False][1], atol=1e-3)


def test_quantile_regression_single_and_multi():
    """reg:quantileerror (xgboost >= 2.0 pinball loss): empirical coverage of
    each predicted quantile matches its alpha, multi-alpha outputs are
    ordered, and the "quantile" eval metric decreases."""
    import numpy as np
    from xgboost_ray_tpu import RayDMatrix, RayParams, train

    rng = np.random.RandomState(5)
    n = 4000
    x = rng.randn(n, 3).astype(np.float32)
    y = (2.0 * x[:, 0] + rng.standard_normal(n)).astype(np.float32)

    res = {}
    bst = train({"objective": "reg:quantileerror",
                 "quantile_alpha": [0.1, 0.5, 0.9],
                 "eval_metric": ["quantile"], "max_depth": 4, "eta": 0.3},
                RayDMatrix(x, y), 30,
                evals=[(RayDMatrix(x, y), "train")], evals_result=res,
                ray_params=RayParams(num_actors=2))
    pin = res["train"]["quantile"]
    assert pin[-1] < pin[0]
    pred = bst.predict(x)
    assert pred.shape == (n, 3)
    for k, a in enumerate([0.1, 0.5, 0.9]):
        cov = float((y <= pred[:, k]).mean())
        assert abs(cov - a) < 0.08, (a, cov)
    # quantile crossing should be rare on train data
    assert float((pred[:, 0] <= pred[:, 2]).mean()) > 0.95

    bst1 = train({"objective": "reg:quantileerror", "quantile_alpha": 0.75,
                  "max_depth": 4, "eta": 0.3},
                 RayDMatrix(x, y), 25, ray_params=RayParams(num_actors=2))
    p1 = bst1.predict(x)
    assert p1.shape == (n,)
    assert abs(float((y <= p1).mean()) - 0.75) < 0.08


def test_quantile_save_load_and_sklearn():
    """quantile_alpha survives serialization (multi-output predict after
    load) and flows through the sklearn regressor params."""
    import numpy as np
    from xgboost_ray_tpu import RayDMatrix, RayParams, train
    from xgboost_ray_tpu.models.booster import Booster
    from xgboost_ray_tpu.sklearn import RayXGBRegressor

    rng = np.random.RandomState(6)
    x = rng.randn(600, 3).astype(np.float32)
    y = (x[:, 0] + 0.3 * rng.standard_normal(600)).astype(np.float32)
    bst = train({"objective": "reg:quantileerror",
                 "quantile_alpha": [0.25, 0.75], "max_depth": 3},
                RayDMatrix(x, y), 6, ray_params=RayParams(num_actors=2))
    import tempfile, os
    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "m.json")
        bst.save_model(p)
        loaded = Booster.load_model(p)
    assert loaded.num_outputs == 2
    np.testing.assert_allclose(loaded.predict(x), bst.predict(x), atol=1e-6)

    reg = RayXGBRegressor(objective="reg:quantileerror", quantile_alpha=0.5,
                          n_estimators=5, max_depth=3,
                          ray_params=RayParams(num_actors=2))
    reg.fit(x, y)
    p = reg.predict(x)
    assert p.shape == (600,)


def test_quantile_metric_alpha_threading_and_mismatch_guard():
    """compute_metric/elementwise_contrib take quantile_alpha (ADVICE r2:
    host-side evaluation silently scored with alpha=0.5); a margin/alpha
    count mismatch with >1 alphas raises instead of broadcasting."""
    import numpy as np
    import pytest
    from xgboost_ray_tpu.ops.metrics import compute_metric

    y = np.array([0.0, 1.0, 2.0, 4.0], np.float32)
    m = np.array([1.0, 1.0, 1.0, 1.0], np.float32)
    v10 = compute_metric("quantile", m, y, quantile_alpha=0.1)
    v90 = compute_metric("quantile", m, y, quantile_alpha=0.9)
    # pinball: alpha * max(y-m, 0) + (1-alpha) * max(m-y, 0)
    def pinball(a):
        d = y - m
        return float(np.mean(np.maximum(a * d, (a - 1) * d)))
    assert v10 == pytest.approx(pinball(0.1), rel=1e-5)
    assert v90 == pytest.approx(pinball(0.9), rel=1e-5)
    assert v10 != pytest.approx(v90)
    # one alpha broadcasts over multi-output margins; >1 mismatched raises
    m2 = np.stack([m, m], axis=1)
    compute_metric("quantile", m2, y, quantile_alpha=0.5)
    with pytest.raises(ValueError, match="must align"):
        compute_metric("quantile", m2, y, quantile_alpha=(0.1, 0.5, 0.9))


def test_mphe_metric_huber_slope_threading():
    import numpy as np
    import pytest
    from xgboost_ray_tpu.ops.metrics import compute_metric

    y = np.zeros(4, np.float32)
    m = np.array([1.0, -2.0, 3.0, 0.5], np.float32)
    v1 = compute_metric("mphe", m, y, huber_slope=1.0)
    v3 = compute_metric("mphe", m, y, huber_slope=3.0)
    def mphe(s):
        return float(np.mean(s * s * (np.sqrt(1 + (m / s) ** 2) - 1)))
    assert v1 == pytest.approx(mphe(1.0), rel=1e-5)
    assert v3 == pytest.approx(mphe(3.0), rel=1e-5)
    assert v1 != pytest.approx(v3)

"""The dense, order-free histogram build (``hist_onehot``: the node rides the
matmul's right-hand side) against ``hist_scatter``, the structural test that
keeps the presorted order, the compaction and the block copies out of a
``mixed`` tree of ``max_depth <= 11``, and ``mixed`` forests against
``scatter``'s on one device and on the 4-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from xgboost_ray_tpu import RayDMatrix, RayParams, obs, train
from xgboost_ray_tpu.ops import binning
from xgboost_ray_tpu.ops.grow import GrowConfig, build_tree
from xgboost_ray_tpu.ops.histogram import hist_onehot, hist_scatter
from xgboost_ray_tpu.ops.provider import (
    DENSE_MAX_COLUMNS,
    WIDEST_BUILD_NODES,
    resolve_hist_provider,
)
from xgboost_ray_tpu.ops.split import SplitParams

from chip_smoke import HIST_FAST_REL

N_BINS = 32
NBT = N_BINS + 1


def _rows(n_nodes, gh_dtype="float32", n=6000, features=7, seed=0):
    """Bins with missing values, gh, and a ``pos`` that leaves a fifth of the
    rows outside ``[0, n_nodes)`` (finished rows, the bigger sibling)."""
    rng = np.random.RandomState(seed + n_nodes)
    bins = rng.randint(0, N_BINS, size=(n, features)).astype(np.int16)
    bins[rng.rand(n, features) < 0.1] = N_BINS  # the missing bucket
    if gh_dtype == "float32":
        gh = np.stack([rng.standard_normal(n) * 0.5,
                       rng.uniform(0.0, 0.25, n)], axis=1).astype(np.float32)
    else:
        gh = rng.randint(-127, 128, size=(n, 2)).astype(gh_dtype)
    pos = rng.randint(0, n_nodes, size=n).astype(np.int32)
    out = rng.rand(n) < 0.2
    pos[out] = np.where(rng.rand(int(out.sum())) < 0.5, -1, n_nodes)
    # the reference sees the outside rows as rows of slot 0 with no gh
    gh_ref = np.where(out[:, None], 0, gh).astype(gh.dtype)
    pos_ref = np.where(out, 0, pos).astype(np.int32)
    return bins, gh, pos, gh_ref, pos_ref


@pytest.mark.parametrize("n_nodes", [1, 2, 16, 64, 256])
@pytest.mark.parametrize("precision", ["highest", "fast"])
def test_dense_build_matches_scatter(precision, n_nodes):
    bins, gh, pos, gh_ref, pos_ref = _rows(n_nodes)
    # three scan chunks and a ragged tail
    got = np.asarray(jax.jit(
        lambda b, g, p: hist_onehot(b, g, p, n_nodes, NBT, chunk=2048,
                                    precision=precision))(bins, gh, pos))
    scatter = jax.jit(lambda b, g, p: hist_scatter(b, g, p, n_nodes, NBT))
    want = np.asarray(scatter(bins, gh_ref, pos_ref))
    assert got.shape == want.shape == (n_nodes, bins.shape[1], NBT, 2)
    assert got.dtype == np.float32
    # a bucket's error against the |gh| that went into it; the missing
    # bucket is rebuilt by subtraction from the node total, so it carries
    # the rounding of its whole (node, feature)
    mass = np.array(scatter(bins, np.abs(gh_ref), pos_ref))
    mass[:, :, -1, :] = mass.sum(axis=2)
    worst = float(np.max(np.abs(got - want) / np.maximum(mass, 1e-30)))
    assert worst <= (1e-6 if precision == "highest" else HIST_FAST_REL), worst
    assert np.abs(want[:, :, -1, :]).max() > 0  # missing values were there


@pytest.mark.parametrize("n_nodes", [1, 2, 16, 64, 256])
def test_dense_build_is_exact_for_int8_gh(n_nodes):
    bins, gh, pos, gh_ref, pos_ref = _rows(n_nodes, gh_dtype="int8")
    got = jax.jit(lambda b, g, p: hist_onehot(b, g, p, n_nodes, NBT,
                                              chunk=2048))(bins, gh, pos)
    want = hist_scatter(jnp.asarray(bins), jnp.asarray(gh_ref),
                        jnp.asarray(pos_ref), n_nodes, NBT)
    assert got.dtype == want.dtype == jnp.int32
    np.testing.assert_array_equal(got, want)


def test_mixed_picks_the_build_from_the_static_shape():
    mixed = resolve_hist_provider("mixed")
    # a build: dense while its 2 columns a node slot stay under the crossover
    slots = DENSE_MAX_COLUMNS // 2
    assert [mixed.uses_order(nn) for nn in (1, 2, 64, slots, 2 * slots)] == [
        False, False, False, False, True]
    # could it ever (what the K-lane build has to know)
    assert mixed.uses_order(WIDEST_BUILD_NODES)
    assert resolve_hist_provider("partition").uses_order(1)
    for impl in ("scatter", "onehot"):
        assert not resolve_hist_provider(impl).uses_order(WIDEST_BUILD_NODES)


def _tree_inputs(n=4096, features=6, seed=3):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, features).astype(np.float32)
    x[rng.rand(n, features) < 0.05] = np.nan
    y = x[:, 0] + 0.5 * np.nan_to_num(x[:, 1] * x[:, 2]) > 0
    cuts = binning.sketch_cuts_np(x, max_bin=N_BINS)
    bins = binning.bin_matrix_np(x, cuts, max_bin=N_BINS)
    gh = np.stack([0.5 - y, np.full(n, 0.25)], axis=1).astype(np.float32)
    fhm = jnp.asarray((bins == N_BINS).any(axis=0))
    return jnp.asarray(bins), jnp.asarray(gh), jnp.asarray(cuts), fhm


def _row_extent_eqns(jaxpr, n_rows):
    """(primitive, innermost frame of this package) of every gather, scatter,
    sort or cumulative sum over an operand of at least ``n_rows // 2``
    entries: the data movement a maintained row order is made of."""
    from jax._src import source_info_util

    moving = ("gather", "sort", "cumsum", "cumlogsumexp", "cummax", "cummin")
    found = []
    for eqn in jaxpr.eqns:
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _row_extent_eqns(sub, n_rows)
        name = eqn.primitive.name
        if name not in moving and not name.startswith("scatter"):
            continue
        # the index operand of a gather / scatter, the operand otherwise
        aval = eqn.invars[1 if name == "gather" or name.startswith("scatter")
                          else 0].aval
        if not aval.shape or aval.shape[0] < n_rows // 2:
            continue
        frames = [f.function_name for f in
                  source_info_util.user_frames(eqn.source_info.traceback)
                  if "/xgboost_ray_tpu/" in f.file_name]
        found.append((name, frames[0] if frames else "?"))
    return found


def _trace_tree(depth, hist_impl="mixed", **cfg_kw):
    bins, gh, cuts, fhm = _tree_inputs()
    cfg = GrowConfig(max_depth=depth, max_bin=N_BINS, split=SplitParams(),
                     hist_impl=hist_impl, hist_precision="fast", **cfg_kw)
    reg = obs.get_registry()
    dense = reg.counter("rxgb_hist_dense_levels_total")
    presorted = reg.counter("rxgb_hist_presorted_levels_total")
    before = dense.value, presorted.value
    jaxpr = jax.make_jaxpr(
        lambda *a: build_tree(a[0], a[1], a[2], cfg, feat_has_missing=a[3])
    )(bins, gh, cuts, fhm)
    moved = _row_extent_eqns(jaxpr.jaxpr, bins.shape[0])
    return moved, dense.value - before[0], presorted.value - before[1]


#: the deepest ``mixed`` tree whose every level is dense: under sibling
#: subtraction its last level builds 2^(depth-2) node slots
DENSE_DEPTH = (DENSE_MAX_COLUMNS // 2).bit_length() + 1


@pytest.mark.parametrize("skew", [False, True])
@pytest.mark.parametrize("depth", [6, 8, DENSE_DEPTH])
def test_mixed_tree_under_the_crossover_moves_no_row(depth, skew):
    """No order, no compaction, no block copy, no window loop: a ``mixed``
    tree of depth 6, 8 or the deepest under the crossover holds no gather,
    scatter, sort or prefix sum of row extent, on one device and as a mesh's
    shard, and every level counts as a dense one."""
    moved, dense, presorted = _trace_tree(depth, shards_may_skew=skew)
    assert moved == []
    assert (dense, presorted) == (depth, 0)


def test_mixed_tree_past_the_crossover_keeps_its_presorted_levels():
    """Two levels deeper than the deepest all-dense tree: the levels up to
    ``DENSE_MAX_COLUMNS // 2`` node slots take the dense build, the last
    two the presorted blocks, from an order kept since level 0."""
    depth = DENSE_DEPTH + 2
    moved, dense, presorted = _trace_tree(depth)
    assert (dense, presorted) == (DENSE_DEPTH, 2)
    by_fn = {}
    for name, fn in moved:
        by_fn.setdefault(fn, []).append(name)
    assert {"update_partition_order", "select_small_child_rows",
            "presorted_block_layout"} <= set(by_fn)
    # the order is kept at every level (the last one's update is dead code
    # that XLA drops), the selection and the blocks are the two deep levels'
    assert len([n for n in by_fn["update_partition_order"]
                if n.startswith("scatter")]) == depth
    assert len([n for n in by_fn["presorted_block_layout"]
                if n.startswith("scatter")]) == 2


def test_partition_stays_presorted_at_every_fan_out():
    moved, dense, presorted = _trace_tree(4, hist_impl="partition")
    assert (dense, presorted) == (0, 4)
    assert {"update_partition_order", "select_small_child_rows",
            "presorted_block_layout"} <= {fn for _, fn in moved}


def _forest_fields(bst):
    return {f: np.asarray(getattr(bst.forest, f))
            for f in ("feature", "split_bin", "default_left", "is_leaf",
                      "value", "cover")}


def _higgs_like(n, seed):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, 12).astype(np.float32)
    x[rng.rand(n, 12) < 0.03] = np.nan
    logit = (x[:, 0] - 0.8 * np.nan_to_num(x[:, 1] * x[:, 2])
             + 0.5 * np.nan_to_num(x[:, 3]) ** 2)
    y = (logit + 0.5 * rng.randn(n) > 0.3).astype(np.float32)
    return x, y


@pytest.mark.parametrize("actors", [1, 4])
@pytest.mark.parametrize("depth", [6, 8])
def test_mixed_forest_has_scatters_splits(depth, actors):
    """Three rounds at ``highest``, on one device and over the 4-device mesh.
    The first tree's (g, h) are +-0.5 and 0.25, whose sums are exact in any
    order: its splits are ``scatter``'s at every node. Later trees sum other
    f32 values in another order, so a tie between two thresholds may break
    the other way: they are held by the loss they reach. On the mesh the
    noted sibling builds report no fallback and the wire is ``scatter``'s."""
    x, y = _higgs_like(6000, seed=depth)
    forests, extras, losses = {}, {}, {}
    for impl in ("scatter", "mixed"):
        extras[impl], losses[impl] = {}, {}
        dtrain = RayDMatrix(x, y)
        bst = train(
            {"objective": "binary:logistic", "max_depth": depth, "eta": 0.3,
             "max_bin": 64, "hist_impl": impl, "min_child_weight": 5.0,
             "eval_metric": ["logloss"]},
            dtrain, num_boost_round=3, evals=[(dtrain, "train")],
            evals_result=losses[impl], additional_results=extras[impl],
            ray_params=RayParams(num_actors=actors),
        )
        forests[impl] = _forest_fields(bst)
    got, want = forests["mixed"], forests["scatter"]
    for field in ("feature", "split_bin", "default_left", "is_leaf"):
        np.testing.assert_array_equal(got[field][0], want[field][0],
                                      err_msg=field)
    np.testing.assert_allclose(got["value"][0], want["value"][0],
                               rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(got["cover"][0], want["cover"][0], rtol=1e-6)
    assert int(want["is_leaf"][0].sum()) > 8
    np.testing.assert_allclose(losses["mixed"]["train"]["logloss"],
                               losses["scatter"]["train"]["logloss"],
                               rtol=1e-4)
    extra = extras["mixed"]
    if actors == 1:
        assert extra["hist_sibling_builds"] == 0
    else:
        assert extra["hist_sibling_builds"] == 3 * (depth - 1) * actors
        assert extra["collectives_per_round"] == 2 * depth
    assert extra["hist_skew_fallback_builds"] == 0
    assert (extra["hist_allreduce_bytes_per_round"]
            == extras["scatter"]["hist_allreduce_bytes_per_round"])

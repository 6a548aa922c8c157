"""The dense, order-free histogram build (``hist_onehot``: the node rides the
matmul's right-hand side) against ``hist_scatter`` at every fan-out a tree of
``max_depth <= 14`` asks for, the structural test that keeps a row order, a
compaction and block copies out of ``build_tree``, and ``onehot`` forests
against ``scatter``'s on one device and on the 4-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from xgboost_ray_tpu import RayDMatrix, RayParams, train
from xgboost_ray_tpu.ops import binning
from xgboost_ray_tpu.ops.grow import GrowConfig, build_tree
from xgboost_ray_tpu.ops.histogram import (
    AllreduceBytes,
    hist_onehot,
    hist_scatter,
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


#: node slots of a build: the root's, a shallow tree's, depth 6's and 8's
#: widest, and the widest of max_depth 12, 13 and 14 under sibling subtraction
FAN_OUTS = [1, 2, 16, 64, 256, 1024, 2048, 4096]


@pytest.mark.parametrize("n_nodes", FAN_OUTS)
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


@pytest.mark.parametrize("n_nodes", FAN_OUTS)
def test_dense_build_is_exact_for_int8_gh(n_nodes):
    bins, gh, pos, gh_ref, pos_ref = _rows(n_nodes, gh_dtype="int8")
    got = jax.jit(lambda b, g, p: hist_onehot(b, g, p, n_nodes, NBT,
                                              chunk=2048))(bins, gh, pos)
    want = hist_scatter(jnp.asarray(bins), jnp.asarray(gh_ref),
                        jnp.asarray(pos_ref), n_nodes, NBT)
    assert got.dtype == want.dtype == jnp.int32
    np.testing.assert_array_equal(got, want)


def _tree_inputs(n=40_000, features=6, seed=3):
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


#: (max_depth, sibling_subtract, shards of the mesh the tree is traced for)
TREES = [(d, True, shards) for d in (6, 8, 11) for shards in (1, 4)] + [
    (d, sib, 1) for d in (12, 14) for sib in (True, False)]


@pytest.mark.parametrize("depth,sibling_subtract,shards", TREES)
def test_a_tree_moves_no_row(depth, sibling_subtract, shards):
    """No order, no compaction, no block copy: the jaxpr of an ``onehot``
    tree holds no gather, scatter, sort or prefix sum of row extent, at the
    benchmark's depths and at the deepest ``params.py`` admits (whose widest
    tables, 2^14 node slots, stay under half the rows here), with and
    without sibling subtraction, on one device and as a mesh's shard."""
    bins, gh, cuts, fhm = _tree_inputs()
    cfg = GrowConfig(max_depth=depth, max_bin=N_BINS, split=SplitParams(),
                     hist_impl="onehot", hist_precision="fast",
                     sibling_subtract=sibling_subtract)
    counter = AllreduceBytes(shards)
    jaxpr = jax.make_jaxpr(
        lambda *a: build_tree(a[0], a[1], a[2], cfg, feat_has_missing=a[3],
                              ar_counter=counter)
    )(bins, gh, cuts, fhm)
    assert _row_extent_eqns(jaxpr.jaxpr, bins.shape[0]) == []
    # a mesh's shard notes one sibling build a level >= 1, a lone device none
    noted = depth - 1 if sibling_subtract and shards > 1 else 0
    assert counter.sibling_builds == noted


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
@pytest.mark.parametrize("depth", [6, 8, 12])
def test_dense_forest_has_scatters_splits(depth, actors):
    """Three rounds at ``highest``, on one device and over the 4-device mesh.
    The first tree's (g, h) are +-0.5 and 0.25, whose sums are exact in any
    order: its splits are ``scatter``'s at every node. Later trees sum other
    f32 values in another order, so a tie between two thresholds may break
    the other way: they are held by the loss they reach. On the mesh the
    noted sibling builds report no fallback and the wire is ``scatter``'s."""
    x, y = _higgs_like(6000, seed=depth)
    forests, extras, losses = {}, {}, {}
    for impl in ("scatter", "onehot"):
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
    got, want = forests["onehot"], forests["scatter"]
    for field in ("feature", "split_bin", "default_left", "is_leaf"):
        np.testing.assert_array_equal(got[field][0], want[field][0],
                                      err_msg=field)
    np.testing.assert_allclose(got["value"][0], want["value"][0],
                               rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(got["cover"][0], want["cover"][0], rtol=1e-6)
    assert int(want["is_leaf"][0].sum()) > 8
    np.testing.assert_allclose(losses["onehot"]["train"]["logloss"],
                               losses["scatter"]["train"]["logloss"],
                               rtol=1e-4)
    extra = extras["onehot"]
    if actors == 1:
        assert extra["hist_sibling_builds"] == 0
    else:
        assert extra["hist_sibling_builds"] == 3 * (depth - 1) * actors
        assert extra["collectives_per_round"] == 2 * depth
    assert extra["hist_skew_fallback_builds"] == 0
    assert (extra["hist_allreduce_bytes_per_round"]
            == extras["scatter"]["hist_allreduce_bytes_per_round"])

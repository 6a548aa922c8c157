"""The dense, order-free histogram build (``hist_onehot``: the node rides the
matmul's right-hand side, and under 64 columns the low bits of the bin index
beside it) against ``hist_scatter`` at every fan-out a tree of
``max_depth <= 14`` asks for and at every radix the rule can return, the rule
itself, the structural test that keeps a row order, a compaction and block
copies out of ``build_tree``, and ``onehot`` forests against ``scatter``'s on
one device and on the 4-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from xgboost_ray_tpu import RayDMatrix, RayParams, obs, train
from xgboost_ray_tpu.ops import binning
from xgboost_ray_tpu.ops import histogram as histogram_ops
from xgboost_ray_tpu.ops.grow import GrowConfig, build_tree
from xgboost_ray_tpu.ops.histogram import (
    ONEHOT_RADICES,
    AllreduceBytes,
    _hist_onehot,
    hist_onehot,
    hist_scatter,
    onehot_radix,
)
from xgboost_ray_tpu.ops.split import SplitParams

from chip_smoke import HIST_FAST_REL

N_BINS = 32
NBT = N_BINS + 1


def _rows(n_nodes, gh_dtype="float32", n=6000, features=7, seed=0,
          n_bins=N_BINS):
    """Bins with missing values, gh, and a ``pos`` that leaves a fifth of the
    rows outside ``[0, n_nodes)`` (finished rows, the bigger sibling)."""
    rng = np.random.RandomState(seed + n_nodes)
    bins = rng.randint(0, n_bins, size=(n, features)).astype(np.int16)
    bins[rng.rand(n, features) < 0.1] = n_bins  # the missing bucket
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


def _assert_matches_scatter(build, precision, n_nodes, n_bins=N_BINS,
                            features=7):
    """``build(bins, gh, pos)`` against the scatter-add's histogram of the
    same rows, a bucket's error measured against the |gh| that went into
    it."""
    nbt = n_bins + 1
    bins, gh, pos, gh_ref, pos_ref = _rows(n_nodes, n_bins=n_bins,
                                           features=features)
    got = np.asarray(jax.jit(build)(bins, gh, pos))
    scatter = jax.jit(lambda b, g, p: hist_scatter(b, g, p, n_nodes, nbt))
    want = np.asarray(scatter(bins, gh_ref, pos_ref))
    assert got.shape == want.shape == (n_nodes, bins.shape[1], nbt, 2)
    assert got.dtype == np.float32
    # a bucket's error against the |gh| that went into it; the missing
    # bucket is rebuilt by subtraction from the node total, so it carries
    # the rounding of its whole (node, feature)
    mass = np.array(scatter(bins, np.abs(gh_ref), pos_ref))
    mass[:, :, -1, :] = mass.sum(axis=2)
    worst = float(np.max(np.abs(got - want) / np.maximum(mass, 1e-30)))
    assert worst <= (1e-6 if precision == "highest" else HIST_FAST_REL), worst
    assert np.abs(want[:, :, -1, :]).max() > 0  # missing values were there


def _assert_exact_for_int8_gh(build, n_nodes, n_bins=N_BINS):
    bins, gh, pos, gh_ref, pos_ref = _rows(n_nodes, gh_dtype="int8",
                                           n_bins=n_bins)
    got = jax.jit(build)(bins, gh, pos)
    want = hist_scatter(jnp.asarray(bins), jnp.asarray(gh_ref),
                        jnp.asarray(pos_ref), n_nodes, n_bins + 1)
    assert got.dtype == want.dtype == jnp.int32
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("n_nodes", FAN_OUTS)
@pytest.mark.parametrize("precision", ["highest", "fast"])
def test_dense_build_matches_scatter(precision, n_nodes):
    # three scan chunks and a ragged tail
    _assert_matches_scatter(
        lambda b, g, p: hist_onehot(b, g, p, n_nodes, NBT, chunk=2048,
                                    precision=precision),
        precision, n_nodes)


@pytest.mark.parametrize("n_nodes", FAN_OUTS)
def test_dense_build_is_exact_for_int8_gh(n_nodes):
    _assert_exact_for_int8_gh(
        lambda b, g, p: hist_onehot(b, g, p, n_nodes, NBT, chunk=2048),
        n_nodes)


#: node slots under 64 matmul columns, where the rule may factor the bin
#: index, and the regular bins: a multiple of every radix and one of none
NARROW_FAN_OUTS = [1, 2, 4, 8, 16]
RADIX_BINS = [N_BINS, 37]


@pytest.mark.parametrize("n_bins", RADIX_BINS)
@pytest.mark.parametrize("n_nodes", NARROW_FAN_OUTS)
@pytest.mark.parametrize("radix", ONEHOT_RADICES)
@pytest.mark.parametrize("precision", ["highest", "fast"])
def test_dense_build_matches_scatter_at_every_radix(precision, radix, n_nodes,
                                                    n_bins):
    """The radix forced through the private build the rule calls: the
    missing bucket's ``hi`` lies outside the one-hot (32 bins) or in a padded
    ``lo`` slot that is cut off (37)."""
    _assert_matches_scatter(
        lambda b, g, p: _hist_onehot(b, g, p, n_nodes, n_bins + 1, 2048,
                                     precision, radix),
        precision, n_nodes, n_bins=n_bins)


@pytest.mark.parametrize("n_bins", RADIX_BINS)
@pytest.mark.parametrize("n_nodes", NARROW_FAN_OUTS)
@pytest.mark.parametrize("radix", ONEHOT_RADICES)
def test_dense_build_is_exact_for_int8_gh_at_every_radix(radix, n_nodes,
                                                         n_bins):
    _assert_exact_for_int8_gh(
        lambda b, g, p: _hist_onehot(b, g, p, n_nodes, n_bins + 1, 2048,
                                     "highest", radix),
        n_nodes, n_bins=n_bins)


@pytest.mark.parametrize("radix", [r for r in ONEHOT_RADICES if r > 1])
def test_factored_build_tiles_wide_data(radix):
    """More features than one batched step takes (no multiple of the tile):
    the inner loop over feature tiles and its padded features."""
    _assert_matches_scatter(
        lambda b, g, p: _hist_onehot(b, g, p, 2, NBT, 2048, "highest", radix),
        "highest", 2, features=histogram_ops._RADIX_FTILE_MAX + 5)


def test_radix_rule_is_a_function_of_the_builds_shape():
    """``onehot_radix`` reads (node slots, regular bins) and nothing else:
    a power of two the build knows, 1 from 64 matmul columns on and under 64
    bins, never past 64 columns with ``lo`` beside the node, 1 at the widths
    nobody measured (neither 2, 4 nor a multiple of 8), and never wider with
    more columns among the others."""
    for nb_reg in (16, 32, 37, 64, 100, 128, 255, 256, 512, 1024):
        last = max(ONEHOT_RADICES)
        for n_nodes in (1, 2, 3, 4, 8, 9, 12, 16, 31, 32, 64, 128, 4096):
            radix = onehot_radix(n_nodes, nb_reg)
            assert radix == onehot_radix(n_nodes, nb_reg)
            assert radix in ONEHOT_RADICES
            assert radix * 2 * n_nodes <= 64 or radix == 1
            if 2 * n_nodes >= 64 or nb_reg < 64:
                assert radix == 1
            if n_nodes > 2 and n_nodes % 4:
                assert radix == 1
                continue
            assert radix <= last
            last = radix
    # the benchmark's shape: 256 bins, a depth-6 tree's builds and wider
    assert [onehot_radix(n, 256) for n in (1, 2, 4, 8, 16, 32, 64)] == [
        8, 8, 8, 4, 2, 1, 1]


def _hist_onehot_pr35(bins, gh, pos, n_nodes, n_bins_total, chunk=8192,
                      precision="highest"):
    """``hist_onehot`` as it stood before the bin index was factored (PR 35),
    kept verbatim: what radix 1 has to lower to."""
    from xgboost_ray_tpu.ops.histogram import (
        _append_missing, _chunk_node_sums, _einsum_precision,
        _for_row_chunks)

    _ONEHOT_FTILE_MAX = 8
    n, num_features = bins.shape
    nb_reg = n_bins_total - 1
    width = 2 * n_nodes
    prec = _einsum_precision(precision)
    int_gh = jnp.issubdtype(gh.dtype, jnp.integer)
    acc_dt = jnp.int32 if int_gh else jnp.float32
    if int_gh:
        oh_dtype = gh.dtype
    else:
        oh_dtype = jnp.bfloat16 if precision == "fast" else jnp.float32
    n_ftiles = -(-num_features // _ONEHOT_FTILE_MAX)
    ftile = -(-num_features // n_ftiles)
    f_pad = n_ftiles * ftile - num_features
    bin_ids = jnp.arange(nb_reg, dtype=jnp.int32)
    node_of_col = jnp.arange(width, dtype=jnp.int32) // 2
    col_is_hess = (jnp.arange(width, dtype=jnp.int32) % 2).astype(bool)

    def chunk_step(carry, pk, bc, ghk):
        acc, tot = carry
        rows = pk.shape[0]
        bct = bc.T.astype(jnp.int32)
        if f_pad:
            bct = jnp.pad(bct, ((0, f_pad), (0, 0)), constant_values=nb_reg)
        ghc = ghk.astype(oh_dtype)
        of_col = jnp.where(col_is_hess[None, :], ghc[:, 1:2], ghc[:, 0:1])
        rhs = jnp.where(
            pk[:, None] == node_of_col[None, :], of_col, jnp.zeros((), oh_dtype)
        )

        def ftile_step(t, acc):
            cols = jax.lax.dynamic_slice_in_dim(bct, t * ftile, ftile, axis=0)
            oh = (cols[:, None, :] == bin_ids[None, :, None]).astype(oh_dtype)
            contrib = jax.lax.dot_general(
                oh.reshape(ftile * nb_reg, rows), rhs, (((1,), (0,)), ((), ())),
                precision=prec, preferred_element_type=acc_dt,
            )
            return jax.lax.dynamic_update_slice_in_dim(
                acc,
                jax.lax.dynamic_slice_in_dim(acc, t * ftile, ftile, axis=0)
                + contrib.reshape(ftile, nb_reg, width),
                t * ftile,
                axis=0,
            )

        acc = jax.lax.fori_loop(0, n_ftiles, ftile_step, acc)
        tot = tot + _chunk_node_sums(ghk, pk, n_nodes)
        return acc, tot

    acc0 = (
        jnp.zeros((n_ftiles * ftile, nb_reg, width), acc_dt),
        jnp.zeros((n_nodes, 2), acc_dt),
    )
    acc, node_tot = _for_row_chunks(chunk_step, acc0, chunk, pos, bins, gh)
    hist_reg = acc[:num_features].reshape(
        num_features, nb_reg, n_nodes, 2
    ).transpose(2, 0, 1, 3)
    return _append_missing(hist_reg, node_tot)


@pytest.mark.parametrize("gh_dtype,precision", [
    ("float32", "fast"), ("float32", "highest"), ("int8", "highest")])
def test_radix_one_lowers_to_the_unfactored_build(gh_dtype, precision):
    """A wide build (64 node slots: radix 1 by the rule) is the program it
    was: the same StableHLO text, through the public entry too."""
    n_nodes, nbt = 64, 257
    shapes = (jax.ShapeDtypeStruct((20_000, 28), jnp.int16),
              jax.ShapeDtypeStruct((20_000, 2), jnp.dtype(gh_dtype)),
              jax.ShapeDtypeStruct((20_000,), jnp.int32))
    assert onehot_radix(n_nodes, nbt - 1) == 1
    want = jax.jit(lambda b, g, p: _hist_onehot_pr35(
        b, g, p, n_nodes, nbt, precision=precision)).lower(*shapes).as_text()
    for build in (
        lambda b, g, p: hist_onehot(b, g, p, n_nodes, nbt,
                                    precision=precision),
        lambda b, g, p: _hist_onehot(b, g, p, n_nodes, nbt, 8192, precision,
                                     1),
    ):
        assert jax.jit(build).lower(*shapes).as_text() == want
    narrow = jax.jit(lambda b, g, p: hist_onehot(
        b, g, p, 4, nbt, precision=precision)).lower(*shapes).as_text()
    assert narrow != jax.jit(lambda b, g, p: _hist_onehot_pr35(
        b, g, p, 4, nbt, precision=precision)).lower(*shapes).as_text()


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


def _builds_by_radix():
    reg = obs.get_registry()
    return {r: reg.counter(f'rxgb_hist_builds_total{{radix="{r}"}}').value
            for r in ONEHOT_RADICES}


def test_a_traced_tree_counts_its_builds_by_radix():
    """A depth-6 ``build_tree`` at 256 bins builds 2, 2, 4, 8, 16 and 32
    columns (sibling subtraction halves levels 1-5): one count a level under
    ``rxgb_hist_builds_total`` by the radix the rule gave it, and the widths
    noted for the ``hist.builds`` event."""
    rng = np.random.RandomState(0)
    bins = jnp.asarray(rng.randint(0, 257, size=(512, 5)).astype(np.int16))
    gh = jnp.asarray(rng.randn(512, 2).astype(np.float32))
    cuts = jnp.zeros((5, 255), jnp.float32)
    cfg = GrowConfig(max_depth=6, max_bin=256, split=SplitParams(),
                     hist_impl="onehot", hist_precision="fast")
    histogram_ops.pop_traced_builds()
    before = _builds_by_radix()
    jax.make_jaxpr(lambda *a: build_tree(*a, cfg))(bins, gh, cuts)
    after = _builds_by_radix()
    assert {r: after[r] - before[r] for r in ONEHOT_RADICES} == {
        1: 0, 2: 1, 4: 1, 8: 4}
    # 512 rows are one row chunk and 5 features one tile: a step a build
    assert histogram_ops.pop_traced_builds() == {
        "radix_by_width": {2: 8, 4: 8, 8: 8, 16: 4, 32: 2},
        "ftiles_by_width": {2: 1, 4: 1, 8: 1, 16: 1, 32: 1},
        "tile_steps_per_tree": 6}
    assert histogram_ops.pop_traced_builds() == {}
    assert 'rxgb_hist_builds_total{radix="8"}' in (
        obs.get_registry().prometheus_text())


def test_train_reports_the_radix_of_every_width():
    """After ``train()`` the timeline's ``hist.builds`` event carries the
    radix of every width its round programs built; a deep tree's wide levels
    took radix 1."""
    rng = np.random.RandomState(0)
    x = rng.randn(800, 5).astype(np.float32)
    y = (x[:, 0] + x[:, 1] > 0).astype(np.float32)
    res = {}
    before = _builds_by_radix()
    train({"objective": "binary:logistic", "max_depth": 8, "max_bin": 256,
           "hist_impl": "onehot"},
          RayDMatrix(x, y), num_boost_round=2, additional_results=res,
          ray_params=RayParams(num_actors=1))
    events = [r for r in res["obs"]["timeline"] if r["name"] == "hist.builds"]
    assert len(events) == 1
    assert events[0]["attrs"]["radix_by_width"] == {
        "2": 8, "4": 8, "8": 8, "16": 4, "32": 2, "64": 1, "128": 1}
    after = _builds_by_radix()
    programs = sum(1 for r in res["obs"]["timeline"]
                   if r["name"] == "dispatch" and r["attrs"]["first"])
    assert programs >= 1
    assert [after[r] - before[r] for r in (1, 2, 4, 8)] == [
        2 * programs, programs, programs, 4 * programs]
    assert obs.validate_trace_records(
        res["obs"]["timeline"], known_names=obs.TRACE_NAMES) == []


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

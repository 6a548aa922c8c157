"""Row routing without per-row gathers: the dense node-table lookups, the
bin-of-feature reduce and the chunked node sums / counts of ``build_tree``'s
level loop against the gather / scatter forms they replaced
(``tests/_route_reference.py``), and the structural test that keeps the
gathers from coming back."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from xgboost_ray_tpu import obs
from xgboost_ray_tpu.ops import binning, histogram, sampling
from xgboost_ray_tpu.ops.grow import (
    GrowConfig,
    bin_of_feature,
    build_tree,
    lookup_by_node,
    predict_tree_binned,
)
from xgboost_ray_tpu.ops.histogram import node_counts_dense, node_sums_dense
from xgboost_ray_tpu.ops.objectives import quantize_gh
from xgboost_ray_tpu.ops.split import SplitParams

import _route_reference as ref

N_ROWS = 20_000


def _bits(a):
    a = np.asarray(a)
    return a.view(np.int32) if a.dtype == np.float32 else a


@pytest.mark.parametrize("n_nodes", [1, 2, 32, 128, 256])
@pytest.mark.parametrize("dtype", ["bool", "int32", "float32"])
def test_lookup_by_node_is_bitwise_the_gather(dtype, n_nodes):
    rng = np.random.RandomState(n_nodes)
    pos = jnp.asarray(rng.randint(0, n_nodes, size=3001).astype(np.int32))
    if dtype == "bool":
        table = rng.rand(n_nodes) < 0.5
    elif dtype == "int32":
        table = rng.randint(-(2**31), 2**31 - 1, size=n_nodes).astype(np.int32)
    else:
        table = rng.randn(n_nodes).astype(np.float32)
        # values a float add would not carry through: -0.0, inf, nan, denormal
        special = np.array([-0.0, np.inf, np.nan, 1e-42], np.float32)[:n_nodes]
        table[: len(special)] = special
    other = jnp.asarray(rng.randn(n_nodes).astype(np.float32))
    got, got_other = jax.jit(lookup_by_node)(pos, jnp.asarray(table), other)
    assert got.dtype == table.dtype
    np.testing.assert_array_equal(_bits(got), _bits(table[np.asarray(pos)]))
    np.testing.assert_array_equal(_bits(got_other), _bits(other[pos]))


@pytest.mark.parametrize("dtype,max_bin", [("uint8", 255), ("int16", 256)])
def test_bin_of_feature_matches_take_along_axis(dtype, max_bin):
    rng = np.random.RandomState(3)
    bins = rng.randint(0, max_bin + 1, size=(4099, 28)).astype(dtype)
    bins[::7, 5] = max_bin  # the missing bucket
    f = jnp.asarray(rng.randint(0, 28, size=4099).astype(np.int32))
    got = jax.jit(bin_of_feature)(jnp.asarray(bins), f)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(
        got, ref.bin_of_feature_gather(jnp.asarray(bins), f)
    )
    assert int(got.max()) == max_bin


@pytest.mark.parametrize("n_nodes", [1, 2, 32, 256])
@pytest.mark.parametrize("gh_dtype", ["float32", "int8", "int16"])
def test_node_sums_and_counts_match_the_scatter_add(gh_dtype, n_nodes, monkeypatch):
    # three scan chunks and a ragged tail at this row count
    monkeypatch.setattr(histogram, "_NODE_CHUNK", 1024)
    rng = np.random.RandomState(n_nodes)
    n = 2500
    pos = rng.randint(0, n_nodes, size=n).astype(np.int32)
    pos[rng.rand(n) < 0.2] = -1  # finished rows
    if gh_dtype == "float32":
        gh = rng.randn(n, 2).astype(np.float32)
    else:
        gh = rng.randint(-127, 128, size=(n, 2)).astype(gh_dtype)
    gh[-300:] = 0  # zero-gh padding rows: counted, add nothing
    pos[-300:] = 0
    pos, gh = jnp.asarray(pos), jnp.asarray(gh)
    sums = jax.jit(node_sums_dense, static_argnums=2)(gh, pos, n_nodes)
    counts = jax.jit(node_counts_dense, static_argnums=1)(pos, n_nodes)
    want = ref.node_sums_scatter(gh, pos, n_nodes)
    assert sums.dtype == want.dtype and counts.dtype == jnp.int32
    if gh_dtype == "float32":
        # reassociation only: a few ulp of the largest partial sum
        mag = np.abs(np.asarray(gh)).sum(axis=0)
        np.testing.assert_allclose(sums, want, rtol=0, atol=2e-6 * mag.max())
    else:
        np.testing.assert_array_equal(sums, want)
    np.testing.assert_array_equal(counts, ref.node_counts_scatter(pos, n_nodes))
    assert int(counts.sum()) == int((np.asarray(pos) >= 0).sum())


def _tree_data(categorical=False, missing=False, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(N_ROWS, 6).astype(np.float32)
    y = (x[:, 0] + 0.5 * x[:, 1] * x[:, 2] + 0.3 * rng.randn(N_ROWS) > 0)
    if missing:
        x[rng.rand(N_ROWS, 6) < 0.1] = np.nan
    cat = ()
    if categorical:
        x[:, 3] = rng.randint(0, 7, size=N_ROWS)
        y = y ^ (x[:, 3] == 2)
        cat = (3,)
    max_bin = 32
    cuts = binning.sketch_cuts_np(x, max_bin=max_bin)
    bins = binning.bin_matrix_np(x, cuts, max_bin=max_bin)
    if categorical:
        bins[:, 3] = x[:, 3].astype(bins.dtype)
    p = 0.5
    gh = np.stack([p - y, np.full(N_ROWS, p * (1 - p))], axis=1).astype(np.float32)
    fhm = jnp.asarray((bins == max_bin).any(axis=0))
    return jnp.asarray(bins), jnp.asarray(gh), jnp.asarray(cuts), fhm, cat, max_bin


def _assert_same_tree(got, want):
    tree, row_value = got
    tree_w, row_value_w = want
    for field in ("feature", "split_bin", "default_left", "is_leaf"):
        np.testing.assert_array_equal(
            getattr(tree, field), getattr(tree_w, field), err_msg=field
        )
    for field in ("value", "cover"):
        np.testing.assert_allclose(
            getattr(tree, field), getattr(tree_w, field), rtol=1e-6, atol=1e-6,
            err_msg=field,
        )
    np.testing.assert_allclose(row_value, row_value_w, rtol=1e-6, atol=1e-6)
    assert int(np.asarray(tree.is_leaf).sum()) > 2


def _both_forms(fn, *args, forms=("lookup_by_node", "node_sums_dense")):
    """``fn`` traced and run with the dense forms, then with the reference
    gathers. The forms are picked as the tree is traced and jax caches a
    trace by the function's identity, so each side gets a function of its
    own, and the reference side has to have used its ``forms``."""
    got = jax.jit(lambda *a: fn(*a))(*args)
    with ref.gather_form() as used:
        want = jax.jit(lambda *a: fn(*a))(*args)
    assert set(forms) <= used, used
    return got, want


TREE_CASES = {
    "depth3": dict(depth=3),
    "depth8": dict(depth=8),
    "depth3-onehot": dict(depth=3, hist_impl="onehot"),
    "depth8-onehot": dict(depth=8, hist_impl="onehot"),
    "categorical": dict(depth=3, categorical=True),
    "missing": dict(depth=3, missing=True),
    "missing-categorical-depth8": dict(depth=8, missing=True, categorical=True,
                                       hist_impl="onehot"),
    "subsample": dict(depth=3, subsample=0.5),
    "gh-int8": dict(depth=3, gh_precision="int8"),
    "gh-int8-quantized-wire": dict(depth=3, gh_precision="int8",
                                   hist_quant="int8"),
    "quantized-wire": dict(depth=3, hist_quant="int8"),
    "monotone": dict(depth=3, monotone=(1, 0, -1, 0, 0, 0)),
}


@pytest.mark.parametrize("case", list(TREE_CASES))
def test_build_tree_grows_the_same_tree_as_the_gather_form(case):
    kw = dict(TREE_CASES[case])
    bins, gh, cuts, fhm, cat, max_bin = _tree_data(
        categorical=kw.get("categorical", False), missing=kw.get("missing", False)
    )
    cfg = GrowConfig(
        max_depth=kw["depth"], max_bin=max_bin,
        split=SplitParams(learning_rate=0.3, min_child_weight=1.0),
        hist_impl=kw.get("hist_impl", "scatter"), cat_features=cat,
        gh_precision=kw.get("gh_precision", "float32"),
        hist_quant=kw.get("hist_quant", "none"), hist_quant_min_bytes=0,
        monotone_constraints=kw.get("monotone", ()),
    )
    gh_scale = None
    if "subsample" in kw:
        # the engine's sampled build: a compacted [M, F] row selection
        spec = sampling.SamplingSpec(policy="uniform", rate=kw["subsample"])
        rows, gh = sampling.sample_rows(
            gh, jnp.ones((N_ROWS,), bool), jax.random.PRNGKey(7), spec
        )
        bins = bins[rows]
        assert bins.shape[0] == N_ROWS // 2
    if cfg.gh_precision != "float32":
        gh, gh_scale = quantize_gh(gh, cfg.gh_precision, jax.random.PRNGKey(1))

    def grow_one(bins, gh, cuts, fhm):
        return build_tree(bins, gh, cuts, cfg, feat_has_missing=fhm,
                          gh_scale=gh_scale)

    got, want = _both_forms(grow_one, bins, gh, cuts, fhm)
    _assert_same_tree(got, want)
    if cfg.gh_precision != "float32":
        # integer sums are exact in both forms: the whole tree is bitwise
        np.testing.assert_array_equal(got[0].value, want[0].value)
        np.testing.assert_array_equal(got[1], want[1])


def test_vmapped_lanes_grow_the_same_trees_as_the_gather_form():
    """K lanes under ``jax.vmap`` with per-lane gh and depth limits, as the
    vectorized-HPO round builds them: the dense forms batch."""
    bins, gh, cuts, fhm, _, max_bin = _tree_data(missing=True)
    cfg = GrowConfig(max_depth=4, max_bin=max_bin,
                     split=SplitParams(learning_rate=0.3),
                     hist_impl="scatter")
    ghk = jnp.stack([gh, gh * jnp.asarray([1.0, 2.0]), -gh * jnp.asarray([1.0, -1.0])])
    limits = jnp.asarray([4, 2, 3], jnp.int32)

    def lanes(bins, ghk, cuts, fhm, limits):
        return jax.vmap(
            lambda g, d: build_tree(bins, g, cuts, cfg, feat_has_missing=fhm,
                                    depth_limit=d)
        )(ghk, limits)

    got, want = _both_forms(lanes, bins, ghk, cuts, fhm, limits)
    _assert_same_tree(got, want)
    depth_of = np.floor(np.log2(np.arange(cfg.heap_size) + 1))
    for lane, limit in enumerate([4, 2, 3]):
        assert depth_of[np.asarray(got[0].is_leaf[lane])].max() <= limit


# the one row-sized scatter that is real data movement (not a table lookup)
# and stays: the CPU's histogram build
_MAY_INDEX_BY_ROW = {"hist_scatter"}


def _row_indexed_eqns(jaxpr, n_rows):
    """(primitive, innermost frame of this package) of every gather / scatter
    equation whose index operand has a row-sized leading dimension."""
    from jax._src import source_info_util

    found = []
    for eqn in jaxpr.eqns:
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _row_indexed_eqns(sub, n_rows)
        name = eqn.primitive.name
        if name != "gather" and not name.startswith("scatter"):
            continue
        indices = eqn.invars[1].aval
        if not indices.shape or indices.shape[0] < n_rows // 2:
            continue
        frames = [
            f.function_name
            for f in source_info_util.user_frames(eqn.source_info.traceback)
            if "/xgboost_ray_tpu/" in f.file_name or "_route_reference" in f.file_name
        ]
        found.append((name, frames[0] if frames else "?"))
    return found


@pytest.mark.parametrize("hist_impl", ["scatter", "onehot"])
def test_no_row_keyed_gather_or_scatter_in_the_level_loop(hist_impl):
    """The routing block, the live-row count and the final node sums stream
    the rows: at depth 3 the only scatter with a row-sized index is
    ``scatter``'s own scatter-add, and there is none at all under ``onehot``,
    whose dense build streams the rows too."""
    n = 4096
    bins, gh, cuts, fhm, cat, max_bin = _tree_data(categorical=True, missing=True)
    bins, gh = bins[:n], gh[:n]
    cfg = GrowConfig(max_depth=3, max_bin=max_bin, split=SplitParams(),
                     hist_impl=hist_impl, cat_features=cat,
                     hist_quant="int8", hist_quant_min_bytes=0)

    def grow_one(bins, gh, cuts, fhm):
        return build_tree(bins, gh, cuts, cfg, feat_has_missing=fhm)

    dense = _row_indexed_eqns(
        jax.make_jaxpr(lambda *a: grow_one(*a))(bins, gh, cuts, fhm).jaxpr, n
    )
    assert {fn for _, fn in dense} <= _MAY_INDEX_BY_ROW, dense
    if hist_impl == "onehot":
        assert dense == []
    # the walk does see the old forms: traced with the reference gathers the
    # same tree shows them, issued from the level loop itself
    with ref.gather_form():
        gathered = _row_indexed_eqns(
            jax.make_jaxpr(lambda *a: grow_one(*a))(bins, gh, cuts, fhm).jaxpr, n
        )
    old = {fn for _, fn in gathered} - _MAY_INDEX_BY_ROW
    assert {"lookup_by_node_gather", "bin_of_feature_gather",
            "node_counts_scatter", "node_sums"} <= old, gathered


def test_train_counts_the_dense_levels_it_traces():
    """One ``train()`` of depth 6: every level of every program traced counts
    under ``rxgb_route_dense_levels_total``, none under the gather counter."""
    from xgboost_ray_tpu import RayDMatrix, RayParams, train

    reg = obs.get_registry()
    dense = reg.counter("rxgb_route_dense_levels_total")
    before = dense.value
    rng = np.random.RandomState(0)
    x = rng.randn(600, 5).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.float32)
    res = {}
    train({"objective": "binary:logistic", "max_depth": 6, "max_bin": 16},
          RayDMatrix(x, y), num_boost_round=2, additional_results=res,
          ray_params=RayParams(num_actors=2))
    programs = sum(
        1 for r in res["obs"]["timeline"]
        if r["name"] == "dispatch" and r["attrs"]["first"]
    )
    assert programs >= 1
    assert dense.value - before == 6 * programs
    assert reg.counter("rxgb_route_gather_levels_total").value == 0
    assert "rxgb_route_gather_levels_total 0" in reg.prometheus_text()


WALK_CASES = {
    "heap-early-leaves": dict(depth=5, min_child_weight=300.0),
    "missing": dict(depth=4, missing=True),
    "categorical": dict(depth=4, categorical=True, missing=True),
    "linked": dict(depth=0, leaves=11),
    "uint8-bins": dict(depth=3, bins_dtype="uint8"),
    "int16-bins": dict(depth=3, bins_dtype="int16"),
    "vmapped-forest": dict(depth=3, trees=3, missing=True),
}


def _walk_case(case):
    """(tree or stacked forest, bins, walk depth, missing bin, cat features)
    of one ``WALK_CASES`` entry, its trees grown by ``build_tree``."""
    kw = WALK_CASES[case]
    bins, gh, cuts, fhm, cat, max_bin = _tree_data(
        categorical=kw.get("categorical", False),
        missing=kw.get("missing", False), seed=len(case),
    )
    cfg = GrowConfig(
        max_depth=kw["depth"], max_bin=max_bin,
        split=SplitParams(learning_rate=0.3,
                          min_child_weight=kw.get("min_child_weight", 1.0)),
        hist_impl="onehot", cat_features=cat,
        **({"grow_policy": "lossguide", "max_leaves": kw["leaves"]}
           if "leaves" in kw else {}),
    )
    grow = jax.jit(lambda g: build_tree(bins, g, cuts, cfg,
                                        feat_has_missing=fhm)[0])
    scales = [1.0, -2.0, 0.5][: kw.get("trees", 1)]
    trees = [grow(gh * jnp.asarray([s, 1.0])) for s in scales]
    tree = (jax.tree.map(lambda *t: jnp.stack(t), *trees)
            if "trees" in kw else trees[0])
    if "bins_dtype" in kw:
        bins = bins.astype(kw["bins_dtype"])
    return tree, bins, cfg.max_depth, max_bin, cat


def _walk(tree, bins, depth, missing_bin, cat):
    walk = lambda tr: predict_tree_binned(tr, bins, depth, missing_bin, cat)
    return jax.vmap(walk)(tree) if tree.feature.ndim == 2 else walk(tree)


@pytest.mark.parametrize("case", list(WALK_CASES))
def test_predict_tree_binned_is_bitwise_the_gather_walk(case):
    """The eval-set / margin walk reads its node tables by ``lookup_by_node``
    and the split feature's bin by ``bin_of_feature``: every leaf value it
    returns is bitwise the one the per-row gathers read, over every layout
    and bin dtype it meets, and under ``jax.vmap`` over a forest."""
    tree, bins, depth, missing_bin, cat = _walk_case(case)
    layout = "heap" if tree.left is None else "linked"
    steps = obs.get_registry().counter(
        f'rxgb_walk_dense_steps_total{{layout="{layout}"}}')
    before = steps.value
    got, want = _both_forms(
        lambda tr, b: _walk(tr, b, depth, missing_bin, cat), tree, bins,
        forms=("lookup_by_node", "bin_of_feature"),
    )
    # a step per level as each side is traced, the while loop's body once
    assert steps.value - before == 2 * (depth or 1)
    np.testing.assert_array_equal(_bits(got), _bits(want))
    is_leaf = np.asarray(tree.is_leaf)
    assert np.isin(_bits(got), _bits(np.asarray(tree.value)[is_leaf])).all()
    if case == "heap-early-leaves":
        # a leaf above the last level: some rows stop before the last step
        assert is_leaf[: 2 ** depth - 1].any()
    if cat:
        assert (np.asarray(tree.feature)[~is_leaf] == cat[0]).any()
    if "vmapped" in case:
        assert got.shape == (3, N_ROWS)
        assert not np.array_equal(got[0], got[1])


@pytest.mark.parametrize("case", ["categorical", "linked"])
def test_no_row_keyed_gather_in_the_binned_walk(case):
    """No gather in ``predict_tree_binned``'s jaxpr has a row-sized index:
    node tables and bins are read densely, the leaf value too; traced with
    the reference gathers the same walk shows them."""
    tree, bins, depth, missing_bin, cat = _walk_case(case)

    def jaxpr():
        return jax.make_jaxpr(
            lambda tr, b: _walk(tr, b, depth, missing_bin, cat)
        )(tree, bins).jaxpr

    assert _row_indexed_eqns(jaxpr(), N_ROWS) == []
    with ref.gather_form():
        gathered = {fn for _, fn in _row_indexed_eqns(jaxpr(), N_ROWS)}
    assert {"lookup_by_node_gather", "bin_of_feature_gather"} <= gathered


@pytest.mark.parametrize("layout,evals", [
    ("heap", ("train", "valid")), ("linked", ("train", "valid")),
    ("heap", ("train",)),
])
def test_train_with_an_eval_set_counts_the_dense_walk(layout, evals):
    """``train()`` with a validation set walks every new tree over it in the
    dense form, counted by the tree's layout and by no other; with the
    training set alone (the benchmark's ``default`` traffic) the margin
    comes from the grower and no walk is traced."""
    from xgboost_ray_tpu import RayDMatrix, RayParams, train

    reg = obs.get_registry()
    counters = {lay: reg.counter(f'rxgb_walk_dense_steps_total{{layout="{lay}"}}')
                for lay in ("heap", "linked")}
    before = {lay: c.value for lay, c in counters.items()}
    rng = np.random.RandomState(1)
    x = rng.randn(800, 5).astype(np.float32)
    y = (x[:, 0] + 0.3 * rng.randn(800) > 0).astype(np.float32)
    params = {"objective": "binary:logistic", "max_depth": 4, "max_bin": 16}
    if layout == "linked":
        params.update(grow_policy="lossguide", max_depth=0, max_leaves=6)
    sets = {"train": RayDMatrix(x[:600], y[:600]),
            "valid": RayDMatrix(x[600:], y[600:])}
    res = {}
    train(params, sets["train"], num_boost_round=2, evals_result=res,
          evals=[(sets[name], name) for name in evals],
          ray_params=RayParams(num_actors=2))
    assert all(len(res[name]["logloss"]) == 2 for name in evals)
    counted = {lay: c.value - before[lay] for lay, c in counters.items()}
    if evals == ("train",):
        assert counted == {"heap": 0, "linked": 0}
        return
    other = "linked" if layout == "heap" else "heap"
    assert counted[layout] > 0 and counted[other] == 0, counted
    if layout == "heap":
        assert counted[layout] % 4 == 0

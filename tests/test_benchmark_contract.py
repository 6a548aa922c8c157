"""What tier-1 holds of the benchmark's yardstick (``benchmarks/``): the
shapes a cell's roofline is reckoned from, the per-layer readers on hand-made
inputs, and the way a generator's output reaches ``RayDMatrix``. Copies of
the hand-run ``benchmarks/tests/test_contract.py`` / ``test_mesh_cell.py``
cases that need no training run (PERF.md section 7 row 27 (a)), the
readers of the leaf-wise cell, and what the wide cell (``epsilon-d8``)
brought: its generator's seeding beside ``datagen``'s, its three readers, and
``reference_wide`` / ``controls_wide`` held to ``reference`` / ``controls``."""

import json
import os
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
FIXTURES = os.path.join(BENCH, "tests", "fixtures")
PEAK = json.load(open(os.path.join(BENCH, "peaks.json")))["TPU v5 lite"]
MANIFEST = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


@pytest.fixture(scope="module")
def bench():
    """``benchmarks/run.py`` and its neighbours, importable by their own
    names for the length of this module."""
    added = [p for p in (BENCH, FIXTURES) if p not in sys.path]
    sys.path[:0] = added
    import run as bench_run
    import shapes
    import trace_scopes

    yield types.SimpleNamespace(run=bench_run, shapes=shapes,
                                trace_scopes=trace_scopes)
    for p in added:
        sys.path.remove(p)


def _read(bench, name, ctx):
    return bench.run.load_metric_reader(name)(ctx)


@pytest.mark.parametrize("config, want", [
    ("higgs-d6", (11_000_000, 28, 6, 1)),
    ("higgs-d8", (11_000_000, 28, 8, 1)),
    ("higgs-d6-dp4", (44_000_000, 28, 6, 1)),
    # 255 leaves and no depth bound: no tree of 255 leaves has fewer levels
    ("higgs-l255", (11_000_000, 28, 8, 1)),
    ("epsilon-d8", (400_000, 2_000, 8, 1)),
])
def test_cell_shapes_of_the_committed_configurations(bench, config, want):
    s = bench.shapes.cell_shapes(
        json.load(open(os.path.join(BENCH, "configs", config + ".json"))))
    assert (s["rows"], s["features"], s["depth"], s["trees"]) == want


@pytest.mark.parametrize("params, depth", [
    ({"max_depth": 0, "max_leaves": 255}, 8),
    ({"max_depth": 0, "max_leaves": 31}, 5),
    ({"max_leaves": 257}, 9),
    ({"max_depth": 4, "max_leaves": 255}, 4),   # a positive depth is the bound
    ({"max_depth": 0}, 6), ({}, 6),             # xgboost's default
])
def test_a_tree_bounded_by_leaves_has_levels(bench, params, depth):
    config = {"rows": 1000, "features": 4, "params": params}
    assert bench.shapes.cell_shapes(config)["depth"] == depth


def test_every_cell_finds_its_files_and_readers(bench):
    for cell in MANIFEST["workloads"]:
        spec = bench.run.load_cell(cell["name"])
        for key in ("generator", "reference", "controls", "limits",
                    "nominal_round_s"):
            assert key in spec["config"], (cell["name"], key)
        for m in spec["per_layer"]:
            assert callable(bench.run.load_metric_reader(m["name"]))
    l255 = bench.run.load_cell("higgs-l255.default")
    assert l255["config"]["params"]["max_depth"] == 0
    assert {"grow.passes_per_round", "grow.evaluated_per_kept",
            "grow.select_pct", "hist_roofline", "round.mfu_pct"} <= {
        m["name"] for m in l255["per_layer"]}
    d6 = bench.run.load_cell("higgs-d6.default")
    assert not any(m["name"].startswith("grow.") for m in d6["per_layer"])
    assert set(l255["config"]["limits"]) == {
        "loss", "leaf", "cover", "split", "split_deep", "order"}


def _mfu_ctx(devices, **trace):
    return {"peak": PEAK,
            "shapes": {"rows": 44_000_000, "features": 28, "depth": 6,
                       "trees": 1},
            "trace": dict({"busy_s": 2.16, "window_s": 2.18, "rounds": 5,
                           "devices": devices}, **trace)}


def test_mfu_sets_one_devices_rows_against_one_chips_peak(bench):
    one, four = (_read(bench, "round.mfu_pct", _mfu_ctx(n)) for n in (1, 4))
    assert four == pytest.approx(one / 4)
    # 11M rows a device: 2.376 GB at 819 GB/s over 0.432 s of device time
    assert four == pytest.approx(100 * (2.376e9 / 819e9) / 0.432)


def test_hist_roofline_and_collective_time_on_hand_made_scopes(bench):
    level = {"tree/level0/hist": 0.30, "tree/level1/hist": 0.60,
             "tree/level1/split": 0.02, "margin": 0.01}
    ahead = dict(level, **{"tree/level0/allreduce": 0.05,
                           "tree/level1/allreduce": 0.015,
                           "tree/allreduce": 0.005})
    by_device = {"/device:TPU:0": ahead, "/device:TPU:1": level}
    ctx = _mfu_ctx(4, scopes_by_device=by_device)
    assert _read(bench, "hist_roofline", ctx) == pytest.approx(
        100 * (2.376e9 / 819e9) / (0.9 / 5))
    assert _read(bench, "collective.time_pct", ctx) == pytest.approx(7.0)
    for trace in (None, {"scopes_by_device": {}},
                  {"scopes_by_device": {"/device:TPU:0": {"(unscoped)": 2.}}}):
        none = (_mfu_ctx(1, **trace) if trace
                else dict(_mfu_ctx(1), trace=None))
        assert _read(bench, "hist_roofline", none) is None
        assert _read(bench, "collective.time_pct", none) is None


def _grow_ctx(*events):
    return {"additional_results": {"obs": {"timeline": [
        {"kind": "event", "name": name, "t0_s": 1.0, "attrs": attrs}
        for name, attrs in events]}}}


def test_the_leaf_wise_readers_on_hand_made_events(bench):
    ctx = _grow_ctx(
        ("allreduce.bytes", {"bytes_per_round": 0}),
        ("lossguide.grow", {"passes_per_round": 18.4,
                            "nodes_evaluated_per_round": 661.0,
                            "splits_per_round": 254.0, "deepest_leaf": 15}))
    assert _read(bench, "grow.passes_per_round", ctx) == 18.4
    # 661 nodes evaluated for the 509 a tree of 254 splits keeps
    assert _read(bench, "grow.evaluated_per_kept", ctx) == pytest.approx(
        661.0 / 509.0)
    # a pass of 9 leaves in which nothing was thrown away
    assert _read(bench, "grow.evaluated_per_kept", _grow_ctx(
        ("lossguide.grow", {"nodes_evaluated_per_round": 17.0,
                            "splits_per_round": 8.0}))) == 1.0
    # the parent's program, another grower, no timeline: nothing to read
    for nothing in (_grow_ctx(("allreduce.bytes", {"bytes_per_round": 0})),
                    _grow_ctx(), {"additional_results": None},
                    _grow_ctx(("lossguide.grow", {}))):
        assert _read(bench, "grow.passes_per_round", nothing) is None
        assert _read(bench, "grow.evaluated_per_kept", nothing) is None


def test_select_share_is_the_busiest_devices(bench):
    one = {"tree/level0/hist": 0.06, "tree/level/hist": 1.50,
           "tree/level/partition": 0.20, "tree/select": 0.10,
           "tree": 0.04, "margin": 0.10}
    ctx = {"trace": {"scopes_by_device": {
        "/device:TPU:0": one,
        "/device:TPU:1": dict(one, **{"tree/select": 0.05})}}}
    assert _read(bench, "grow.select_pct", ctx) == pytest.approx(5.0)
    for trace in (None, {"scopes_by_device": {}},
                  {"scopes_by_device": {"/device:TPU:0": {
                      "tree/level1/hist": 0.4, "(unscoped)": 0.1}}}):
        assert _read(bench, "grow.select_pct", {"trace": trace}) is None


class _Stop(Exception):
    pass


def _first_matrix_call(bench, argv):
    import xgboost_ray_tpu as real

    calls = []

    def matrix(*args, **kwargs):
        calls.append((len(args), sorted(kwargs)))
        raise _Stop

    program = types.SimpleNamespace(RayParams=real.RayParams, train=None,
                                    RayDMatrix=matrix)
    with pytest.raises(_Stop):
        bench.run.run(bench.run.parse(argv), program=program)
    return calls


def test_the_pair_form_reaches_the_matrix_as_two_arguments(bench):
    assert _first_matrix_call(bench, [
        "--workload", "higgs-l255.default", "--seed", "5", "--seconds", "1",
        "--trace", "0", "--rehearse-cpu"]) == [(2, [])]


def test_the_mapping_form_reaches_the_matrix_by_keyword(bench, monkeypatch):
    config = json.load(open(os.path.join(FIXTURES, "groups-l31.json")))
    monkeypatch.setattr(bench.run, "load_cell", lambda name: {
        "cell": {"name": name, "config": "groups-l31", "traffic": "default",
                 "chips": 1, "why": "test fixture"},
        "config": config,
        "traffic": json.load(open(os.path.join(BENCH, "traffic",
                                               "default.json"))),
        "end_to_end": MANIFEST["end_to_end"], "per_layer": []})
    assert _first_matrix_call(bench, [
        "--workload", "groups-l31.default", "--seed", "5", "--seconds", "1",
        "--trace", "0", "--rehearse-cpu"]) == [
        (0, ["data", "label", "qid", "weight"])]


@pytest.mark.parametrize("module, features", [("datagen", 28),
                                              ("datagen_wide", 210)])
def test_a_generator_is_seeded_by_seed_and_stream(bench, module, features):
    """The same seed gives the same rows, another stream or seed other
    rows; a seed past 32 signed bits is taken; on the grid every value is
    one of ``datagen.grid``'s."""
    import importlib

    import datagen

    make = importlib.import_module(module).make
    seed = 2**31 + 12
    x, y = make(300, features, seed, stream=0, levels=257)
    again = make(300, features, seed, stream=0, levels=257)
    assert x.shape == (300, features) and x.dtype == np.float32
    assert y.shape == (300,) and set(np.unique(y)) == {0.0, 1.0}
    assert np.array_equal(x, again[0]) and np.array_equal(y, again[1])
    assert np.isin(x, datagen.grid(257)).all()
    for other in (make(300, features, seed, stream=1, levels=257),
                  make(300, features, seed + 1, stream=0, levels=257),
                  make(300, features, seed % (2**31 - 1), stream=0,
                       levels=257)):
        assert not np.array_equal(x, other[0])
    # the first rows do not depend on how many are asked for
    assert np.array_equal(make(120, features, seed, levels=257)[0], x[:120])
    free = make(300, features, seed, stream=0, levels=None)[0]
    assert not np.isin(free, datagen.grid(257)).all()


def test_the_wide_label_spreads_over_its_columns(bench):
    import datagen_wide

    with pytest.raises(ValueError):
        datagen_wide.make(10, datagen_wide.MIN_FEATURES - 1, 1)
    x, y = datagen_wide.make(6000, datagen_wide.MIN_FEATURES, 7)
    w = datagen_wide.linear_weights()
    assert w.shape == (datagen_wide.LINEAR,) and w[0] > 0 > w[1]
    # no column carries the label: the heaviest explains a few percent
    corr = [abs(np.corrcoef(x[:, j], y)[0, 1]) for j in range(x.shape[1])]
    assert 0.05 < max(corr) < 0.3 and np.argmax(corr) < 4
    assert 0.4 < y.mean() < 0.6


def _scope_ctx(*devices):
    return {"trace": {"scopes_by_device": {
        f"/device:TPU:{i}": times for i, times in enumerate(devices)}}}


def test_the_wide_cells_readers_on_hand_made_inputs(bench):
    wide = bench.run.load_cell("epsilon-d8.default")
    assert {"hist.tile_steps_per_round", "split.time_pct",
            "partition.time_pct", "hist_roofline", "round.mfu_pct"} <= {
        m["name"] for m in wide["per_layer"]}
    d8 = bench.run.load_cell("higgs-d8.default")
    assert not {"hist.tile_steps_per_round", "split.time_pct",
                "partition.time_pct"} & {m["name"] for m in d8["per_layer"]}

    builds = ("hist.builds", {"radix_by_width": {"2": 8, "64": 1},
                              "ftiles_by_width": {"2": 63, "64": 250},
                              "tile_steps_per_round": 50470})
    assert _read(bench, "hist.tile_steps_per_round",
                 _grow_ctx(("allreduce.bytes", {}), builds)) == 50470
    # the parent's event has the radix alone; the CPU's build records none
    for nothing in (_grow_ctx(("hist.builds", {"radix_by_width": {"2": 8}})),
                    _grow_ctx(), {"additional_results": None}):
        assert _read(bench, "hist.tile_steps_per_round", nothing) is None

    one = {"tree/level0/hist": 0.50, "tree/level0/split": 0.02,
           "tree/level7/split": 0.10, "tree/level7/split/hist": 0.30,
           "tree/level0/partition": 0.03, "tree/level7/partition": 0.03,
           "tree": 0.01, "margin": 0.01}
    other = dict(one, **{"tree/level7/split": 0.05,
                         "tree/level7/partition": 0.08})
    ctx = _scope_ctx(one, other)
    # device 0: 0.12 of 1.00 s under split; device 1: 0.11 of 1.00 under
    # partition: each reader gives the device where its share is largest
    assert _read(bench, "split.time_pct", ctx) == pytest.approx(12.0)
    assert _read(bench, "partition.time_pct", ctx) == pytest.approx(11.0)
    for trace in (None, {"scopes_by_device": {}},
                  {"scopes_by_device": {"/device:TPU:0": {
                      "tree/level1/hist": 0.4, "(unscoped)": 0.1}}}):
        assert _read(bench, "split.time_pct", {"trace": trace}) is None
        assert _read(bench, "partition.time_pct", {"trace": trace}) is None


def test_the_eval_walk_reader_on_hand_made_inputs(bench):
    """``eval_walk.time_pct``: the per-round job's walk of each new tree over
    its validation rows, read in that cell alone."""
    assert "eval_walk.time_pct" in {
        m["name"] for m in bench.run.load_cell("higgs-d6.earlystop")["per_layer"]}
    assert "eval_walk.time_pct" not in {
        m["name"] for m in bench.run.load_cell("higgs-d6.default")["per_layer"]}
    one = {"tree/level0/hist": 0.40, "tree/level5/partition": 0.05,
           "margin": 0.01, "eval_walk": 0.30, "metrics": 0.04,
           "(unscoped)": 0.20}
    other = dict(one, eval_walk=0.10, **{"(unscoped)": 0.40})
    # device 0: 0.30 of 1.00 s; device 1: 0.10 of 1.00 s
    assert _read(bench, "eval_walk.time_pct",
                 _scope_ctx(one, other)) == pytest.approx(30.0)
    for trace in (None, {"scopes_by_device": {}},
                  {"scopes_by_device": {"/device:TPU:0": {
                      "tree/level1/hist": 0.4, "margin": 0.02}}}):
        assert _read(bench, "eval_walk.time_pct", {"trace": trace}) is None


def _small_wide_forest(rng, x, trees, depth):
    """A forest over ``x``'s columns with thresholds on its values: full
    but for one early leaf in tree 1."""
    heap = 2 ** (depth + 1) - 1
    inner = 2 ** depth - 1
    forest = {k: np.zeros((trees, heap), dt) for k, dt in (
        ("feature", np.int32), ("threshold", np.float32),
        ("default_left", bool), ("is_leaf", bool), ("value", np.float32),
        ("cover", np.float32))}
    forest["feature"][:, :inner] = rng.integers(0, x.shape[1],
                                                (trees, inner))
    forest["threshold"][:, :inner] = np.quantile(
        x[:, 0], rng.uniform(0.3, 0.7, (trees, inner)))
    forest["feature"][:, inner:] = -1
    forest["is_leaf"][:, inner:] = True
    forest["feature"][1, 4], forest["is_leaf"][1, 4] = -1, True
    forest["feature"][1, [9, 10]] = -1
    forest["is_leaf"][1, 19:23] = False
    forest["value"] = np.where(forest["is_leaf"],
                               rng.normal(0, 0.1, (trees, heap)),
                               0).astype(np.float32)
    return forest


def test_the_wide_reference_is_the_plain_reference(bench):
    """``reference_wide.follow`` (bins through a table, a level's gains at
    once, feature blocks in threads) against ``reference.follow`` on 5,000
    rows x 210 columns, some of them off the grid: values, covers and losses
    to the bit, split gaps to 1e-9 (the gain's parent term is subtracted
    from the sum, not inside it); ``controls_wide`` likewise."""
    import controls
    import controls_wide
    import datagen_wide
    import reference
    import reference_wide

    rng = np.random.default_rng(0)
    x, y = datagen_wide.make(5000, 210, 11, levels=257)
    x[:60, :7] = rng.normal(size=(60, 7))
    sets = {"train": (x, y), "valid": (x[:900] + 0, y[:900])}
    params = {"max_depth": 4, "eta": 0.1, "min_child_weight": 1}
    forest = _small_wide_forest(rng, x, 3, 4)
    for kwargs in ({"split_trees": range(3)},
                   {"split_trees": [1], "row_share": 0.5,
                    "own_values": True},
                   {"real": np.float32, "own_values": True}):
        want = reference.follow(sets, forest, params, **kwargs)
        got = reference_wide.follow(sets, forest, params, **kwargs)
        assert np.array_equal(got["value"], want["value"])
        assert np.array_equal(got["cover"], want["cover"])
        assert got["loss"] == want["loss"]
        assert got["split_gap"].keys() == want["split_gap"].keys()
        for node, gap in want["split_gap"].items():
            assert got["split_gap"][node] == pytest.approx(gap, rel=1e-9)
    assert len(want["split_gap"]) == 0 and len(got["loss"]["valid"]) == 3

    limits = {"loss": 1e-4, "leaf": 8e-3, "cover": 4.5e-3, "split": 1e-3,
              "split_deep": 8e-3}
    said = {k: list(v) for k, v in
            reference.follow(sets, forest, params)["loss"].items()}
    want = controls.readings(sets, forest, said, params, limits)
    got = controls_wide.readings(sets, forest, said, params, limits)
    assert got.keys() == want.keys() == {
        "lowprec", "half_batch", "state_unchanged", "answer_altered"}
    for case, numbers in want.items():
        for name, c in numbers.items():
            assert got[case][name]["value"] == pytest.approx(
                c["value"], rel=1e-9), (case, name)
    every = controls_wide.every_tree_splits(sets, forest, params)
    assert every["numbers"].keys() == controls.every_tree_splits(
        sets, forest, params)["numbers"].keys() == {0, 1, 2}

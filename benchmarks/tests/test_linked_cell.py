"""The leaf-wise cell (``higgs-l255.default``), run by hand like the rest of
this directory (not part of tier-1):

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_linked_cell.py -q

Its CPU rehearsal, and ``reference_linked`` / ``controls_linked`` at a size
a test can hold: the program's forest passes all six numbers; the control
fails ``leaf``; a level-wise tree handed in for the best-first one and the
best-first tree stopped at a depth both fail ``order`` and nothing else.
"""

import contextlib
import io
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import controls_linked  # noqa: E402
import datagen  # noqa: E402
import reference_linked  # noqa: E402
import run as bench_run  # noqa: E402

CELL = "higgs-l255.default"
# at 20,000 rows a 256-bin histogram holds 78 rows a bin, so the best split
# is a matter of sampling noise; the configuration's limits are set at 11M
TEST_LIMITS = {"loss": 1e-5, "leaf": 1e-3, "cover": 1e-3, "split": 0.15,
               "split_deep": 0.5, "order": 1e-3}
# the cell's parameters with a budget that binds at 20,000 rows: 100 of
# hessian a child allows some 50 leaves there, so 24 make best-first choose
PARAMS = dict(json.load(open(os.path.join(
    BENCH, "configs", "higgs-l255.json")))["params"],
    max_leaves=24, min_child_weight=20)
CAP = 3


def test_rehearsal_of_the_cell():
    argv = ["--workload", CELL, "--seed", "2147483777", "--seconds", "1",
            "--trace", "1", "--rehearse-cpu"]
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = bench_run.run(bench_run.parse(argv), limits=TEST_LIMITS)
    assert rc == 0
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert line["correct"], line["compared"]
    assert set(line["compared"]) == set(TEST_LIMITS)
    assert line["attempted"] == 10 and line["failed"] == 0
    got = line["rehearsal"]
    assert got["grow.passes_per_round"]["value"] >= 3
    assert got["grow.evaluated_per_kept"]["value"] >= 1.0
    # a CPU trace has no device plane: no share of the device's seconds
    assert "grow.select_pct" not in got and "hist_roofline" not in got


@pytest.fixture(scope="module")
def grown():
    """Four rounds of the program at 20,000 rows: the sets, its forest and
    the losses it reported."""
    import xgboost_ray_tpu as program

    sets = {"train": datagen.make(20_000, 28, 2147483999, stream=0,
                                  levels=257)}
    result = {}
    bst = program.train(
        PARAMS, program.RayDMatrix(*sets["train"]), 4,
        evals=[(program.RayDMatrix(*sets["train"]), "train")],
        evals_result=result, ray_params=program.RayParams(num_actors=1))
    return (sets, reference_linked.forest_arrays(bst.forest),
            {"train": list(result["train"]["logloss"])})


def test_the_programs_forest_is_the_best_first_forest(grown):
    sets, forest, reported = grown
    assert forest["left"].shape == (4, 2 * PARAMS["max_leaves"] - 1)
    assert (forest["is_leaf"].sum(axis=1) == PARAMS["max_leaves"]).all()
    ref = reference_linked.follow(sets, forest, PARAMS, split_trees=range(4))
    correct, compared = reference_linked.compare(reported, forest, ref,
                                                 TEST_LIMITS)
    assert correct, compared
    assert set(compared) == set(TEST_LIMITS)
    # exact sums on the CPU: best-first leaves nothing over its allowance
    # but what the reference's thresholds hold and the program's do not
    assert compared["order"]["value"] < 1e-4
    deepest = max(int(d.max()) for d in ref["depth"].values())
    assert deepest > np.log2(PARAMS["max_leaves"])  # no balanced tree


def test_the_control_and_the_faults(grown):
    sets, forest, reported = grown
    got = controls_linked.readings(sets, forest, reported, PARAMS,
                                   TEST_LIMITS, cap_depth=CAP)
    failed = {name: sorted(k for k, c in numbers.items()
                           if c["value"] > c["limit"])
              for name, numbers in got.items()}
    assert "leaf" in failed["lowprec"]
    assert {"leaf", "cover"} <= set(failed["half_batch"])
    assert "loss" in failed["state_unchanged"]
    assert {"loss", "leaf"} <= set(failed["answer_altered"])
    # the growth order is all that is wrong with these two
    assert failed["levelwise_forest"] == ["order"], got["levelwise_forest"]
    assert failed["depth_capped"] == ["order"], got["depth_capped"]
    for name in ("levelwise_forest", "depth_capped"):
        assert got[name]["order"]["value"] > 10 * TEST_LIMITS["order"]


def test_a_heap_forest_reads_as_its_own_left_pointers():
    """A depth-bounded forest holds no ``left``: ``forest_arrays`` makes the
    heap's, and the walk, the sums and the depths are the heap's."""
    class Heap:
        left = None
        feature = np.array([[0, 1, -1, -1, -1, -1, -1]])
        threshold = np.array([[0.0, 0.5, 0, 0, 0, 0, 0]], np.float32)
        default_left = np.zeros((1, 7), bool)
        is_leaf = np.array([[0, 0, 1, 1, 1, 0, 0]], bool)
        value = np.array([[0, 0, .3, .1, .2, 0, 0]], np.float32)
        cover = np.zeros((1, 7), np.float32)

    forest = reference_linked.forest_arrays(Heap)
    tree = {k: v[0] for k, v in forest.items()}
    assert list(tree["left"]) == [1, 3, 5, 7, 9, 11, 13]
    assert list(reference_linked.internal_nodes(tree)) == [0, 1]
    assert list(reference_linked.node_depths(tree)) == [0, 1, 1, 2, 2, -1, -1]
    x = np.array([[-1.0, 0.0], [-1.0, 1.0], [1.0, 0.0]], np.float32)
    block = reference_linked._Block(x, np.zeros(3), True,
                                    reference_linked._Rounder(np.float64))
    block.walk(tree)
    assert list(block.leaf) == [3, 4, 2]

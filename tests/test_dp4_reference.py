"""The four-actor data-parallel job (``higgs-d6-dp4``'s deployment) at a
small size: 40,000 x 28, depth 6, 256 bins, 5 rounds, seeded, over 4 of
conftest's 8 host devices, with the chip's ``hist_impl`` (``onehot``: the
dense build, which streams every row, compacts nothing and so has nothing a
skewed shard could overflow).

Two ways of cutting the same rows into shards: (a) i.i.d. shards (the rows
as generated, in contiguous blocks) and (b) the rows sorted by feature 0
first (case ``sorted``), so that every shard holds one quarter of feature
0's range and a split on it sends whole shards to one side: the same checks
hold.

The 4-device forest is held to the benchmark's plain reference
(``benchmarks/reference.py``: ``follow`` / ``compare``, which imports nothing
of the program) and to the 1-device forest on the same rows; every actor
ends with the same model; and the counters the mesh programs thread out
(``collectives_per_round``, ``hist_allreduce_bytes_per_round``) are held to
the shapes' own arithmetic.
"""

import os
import sys

import numpy as np
import pytest

from xgboost_ray_tpu import RayDMatrix, RayParams, train
from xgboost_ray_tpu.matrix import RayShardingMode

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
sys.path.insert(0, BENCH)

import datagen  # noqa: E402
import reference  # noqa: E402

ROWS, FEATURES, ROUNDS, ACTORS, SEED = 40_000, 28, 5, 4, 2_900_000_029
PARAMS = {
    "objective": "binary:logistic", "tree_method": "tpu_hist",
    "eval_metric": ["logloss", "error"], "max_depth": 6, "eta": 0.3,
    "max_bin": 256, "hist_impl": "onehot",
}
# Limits at this size, on the CPU (float32 sums, ``hist_precision`` highest).
# loss / leaf / cover: the program sums 40,000 float32 (g, h) in another
# order than the float64 reference; readings are 1e-7 to 1e-6, a bfloat16
# pass anywhere would read 1e-3 and more. split / split_deep: at 40,000 rows
# a 256-bin histogram holds some 150 rows a bin, so the best threshold is a
# matter of sampling noise between the program's cuts and the reference's
# quantiles (benchmarks/tests' rehearsal takes the same two at 20,000 rows).
LIMITS = {"loss": 1e-5, "leaf": 1e-4, "cover": 1e-4, "split": 0.15,
          "split_deep": 0.5}
NBT = 257  # 256 bins and the missing bucket


CASES = ("iid", "sorted")


def _rows(case):
    x, y = datagen.make(ROWS, FEATURES, SEED, levels=257)
    if case == "sorted":
        order = np.argsort(x[:, 0], kind="stable")
        x, y = x[order], y[order]
    return x, y


def _train(x, y, actors):
    evals_result, extra = {}, {}
    dtrain = RayDMatrix(x, y, sharding=RayShardingMode.BATCH)
    bst = train(PARAMS, dtrain, ROUNDS,
                evals=[(dtrain, "train")],
                evals_result=evals_result, additional_results=extra,
                ray_params=RayParams(num_actors=actors, max_actor_restarts=1))
    return bst, evals_result, extra


@pytest.fixture(scope="module", params=CASES)
def job(request):
    x, y = _rows(request.param)
    bst4, evals4, extra4 = _train(x, y, ACTORS)
    bst1, _, extra1 = _train(x, y, 1)
    return {
        "case": request.param, "x": x, "y": y,
        "forest4": reference.forest_arrays(bst4.forest),
        "forest1": reference.forest_arrays(bst1.forest),
        "loss4": {"train": list(evals4["train"]["logloss"])},
        "extra4": extra4, "extra1": extra1,
    }


def test_forest_of_four_actors_matches_the_plain_reference(job):
    ref = reference.follow({"train": (job["x"], job["y"])}, job["forest4"],
                           PARAMS, split_trees=range(ROUNDS))
    correct, compared = reference.compare(job["loss4"], job["forest4"], ref,
                                          LIMITS)
    assert correct, compared
    assert set(compared) == set(LIMITS)
    # the guarantee the configuration states: the root's cover is the
    # hessian sum over EVERY shard's rows (0.25 a row in the first round)
    assert job["forest4"]["cover"][0][0] == pytest.approx(0.25 * ROWS,
                                                          rel=1e-6)


def test_forest_of_four_actors_is_the_one_device_forest(job):
    f4, f1 = job["forest4"], job["forest1"]
    # the same splits ...
    np.testing.assert_array_equal(f4["feature"], f1["feature"])
    np.testing.assert_array_equal(f4["is_leaf"], f1["is_leaf"])
    np.testing.assert_array_equal(f4["default_left"], f1["default_left"])
    np.testing.assert_array_equal(f4["threshold"], f1["threshold"])
    # ... and leaf values and covers to float32 reassociation: four partial
    # sums merged by a psum against one sum over all rows (read 4.4e-6 and
    # 3.7e-7 at worst; a value is G / (H + lambda) of two such sums)
    np.testing.assert_allclose(f4["value"], f1["value"], rtol=5e-5, atol=1e-7)
    np.testing.assert_allclose(f4["cover"], f1["cover"], rtol=5e-6, atol=1e-6)


def test_one_device_world_counts_nothing(job):
    extra = job["extra1"]
    assert extra["hist_allreduce_bytes_per_round"] == 0
    assert extra["collectives_per_round"] == 0
    assert extra["device"]["mesh_shape"] == {"actors": 1}


def test_wire_counters_are_the_shapes_own_arithmetic(job):
    extra = job["extra4"]
    depth = PARAMS["max_depth"]
    # a level merges one histogram a built node: the root, then one a parent
    # (sibling subtraction); levels >= 1 first count their live rows; the
    # final node sums close the tree
    per_psum = ([(1 if d == 0 else 1 << (d - 1)) * FEATURES * NBT * 2 * 4
                 for d in range(depth)]
                + [(1 << d) * 4 for d in range(1, depth)]
                + [(1 << depth) * 2 * 4])
    # ring model, per psum: 2 (n - 1) / n of the operand, truncated
    want = sum(int(2 * (ACTORS - 1) * b / ACTORS) for b in per_psum)
    assert extra["hist_allreduce_bytes_per_round"] == want == 2_764_404
    assert extra["collectives_per_round"] == len(per_psum) == 2 * depth


def test_every_actor_ends_with_the_same_model():
    import jax

    from xgboost_ray_tpu.engine import TpuEngine
    from xgboost_ray_tpu.params import parse_params

    x, y = _rows("iid")
    shards = [{"data": x[idx], "label": y[idx]}
              for idx in np.array_split(np.arange(ROWS), ACTORS)]
    eng = TpuEngine(shards, parse_params(PARAMS), ACTORS,
                    evals=[(shards, "train")], devices=jax.devices()[:ACTORS])
    eng.step_many(0, 2)
    forests, _ = eng._trees_dev[0]
    for field in forests:
        copies = [np.asarray(s.data) for s in field.addressable_shards]
        assert len(copies) == ACTORS
        for other in copies[1:]:
            np.testing.assert_array_equal(copies[0], other)
    stats = eng.mesh_round_stats()
    # the running sum: every shard's collectives of both rounds
    summed = int(eng._mesh_stats_total()[0])
    assert summed == stats["collectives_per_round"] * 2 * ACTORS

    # the counts are one running sum on the device, and only the first
    # dispatch compiles its fold: a later chunk compiles nothing
    from xgboost_ray_tpu import obs
    from xgboost_ray_tpu.obs.compiles import watch_compiles

    watch_compiles()
    compiles = obs.get_registry().counter("rxgb_compiles_total")
    before = compiles.value
    eng.step_many(2, 2)
    assert compiles.value == before
    again = eng.mesh_round_stats()
    assert int(eng._mesh_stats_total()[0]) == 2 * summed
    assert again["collectives_per_round"] == stats["collectives_per_round"] == 12


@pytest.mark.parametrize("rows", [ROWS, ROWS - 3, 5])
def test_rows_per_device_is_the_valid_masks_own_count(rows):
    """``rows_per_device`` is arithmetic (no device read): hold it to the
    mask it describes, padded last blocks included."""
    import jax

    from xgboost_ray_tpu.engine import TpuEngine
    from xgboost_ray_tpu.params import parse_params

    x, y = _rows("iid")
    x, y = x[:rows], y[:rows]
    shards = [{"data": x[idx], "label": y[idx]}
              for idx in np.array_split(np.arange(rows), ACTORS)]
    eng = TpuEngine(shards, parse_params(PARAMS), ACTORS,
                    devices=jax.devices()[:ACTORS])
    by_device = {str(s.device.id): int(np.asarray(s.data).sum())
                 for s in eng.valid.addressable_shards}
    assert eng.placement_record()["rows_per_device"] == by_device
    assert eng.rows_per_device() == list(by_device.values())
    assert sum(eng.rows_per_device()) == rows


def test_events_and_spans_carry_what_the_mesh_adds(job):
    recs = job["extra4"]["obs"]["timeline"]
    by_name = {}
    for r in recs:
        by_name.setdefault(r["name"], []).append(r)
    wire = by_name["allreduce.bytes"][-1]["attrs"]
    assert wire == {"bytes_per_round": 2_764_404,
                    "collectives_per_round": 12, "mesh": {"actors": ACTORS}}
    load = by_name["data.load"][0]["attrs"]
    assert load["shards"] == ACTORS
    # 28 float32 features and a float32 label a row
    assert load["shard_bytes"] == [ROWS // ACTORS * (FEATURES + 1) * 4] * ACTORS
    rows_h2d = by_name["data.h2d"][0]["attrs"]
    assert rows_h2d["shards"] == ACTORS
    assert rows_h2d["shard_bytes"] * ACTORS == rows_h2d["bytes"]
    init = by_name["engine.init"][0]["attrs"]
    assert init["rows_per_device"] == [ROWS // ACTORS] * ACTORS
    assert sum(init["rows_per_device"]) == sum(
        job["extra4"]["device"]["rows_per_device"].values())


def _walk(jaxpr, scope=""):
    """(primitive, named scope, operand shapes) of every equation, loop and
    shard_map bodies included; a body's scopes continue its equation's."""
    import jax

    for eqn in jaxpr.eqns:
        here = scope + str(eqn.source_info.name_stack)
        yield (eqn.primitive.name, here,
               [getattr(v.aval, "shape", ()) for v in eqn.invars])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk(sub, here + "/")


@pytest.mark.parametrize("actors", [1, ACTORS])
@pytest.mark.parametrize("depth", [6, 8])
def test_mesh_tree_holds_one_sibling_build_a_level(depth, actors):
    """The fused round program of the benchmark's depths under the chip's
    build (``onehot``, ``fast``), on one device and as the 4-device mesh's:
    a level's histogram is ONE dense build. Its only loops are that build's
    own (row chunks, and feature tiles inside them) and, from level 1 on,
    the live-row count's: no loop of traced length (the window loop a
    compacted build needed on a skewed shard), no ``cond`` between two
    builds (at 11M rows a device a second build a level made the 4-device
    program 1.15 GB of code, which the chip's host could not compile;
    PERF.md section 6, PR 29), no sort, and no gather or scatter keyed by
    the row. The mesh's tree has the one-device tree's control flow."""
    import re

    import jax

    from xgboost_ray_tpu.engine import TpuEngine
    from xgboost_ray_tpu.params import parse_params

    rows = 8192
    x, y = _rows("iid")
    shards = [{"data": x[idx], "label": y[idx]}
              for idx in np.array_split(np.arange(rows), actors)]
    params = dict(PARAMS, max_depth=depth, max_bin=32, hist_impl="onehot",
                  hist_precision="fast")
    eng = TpuEngine(shards, parse_params(params), actors,
                    evals=[(shards, "train")], devices=jax.devices()[:actors])
    eng.build_programs()
    eqns = list(_walk(
        jax.make_jaxpr(eng._scan_fn)(*eng._scan_example_args()).jaxpr))
    names = {name for name, _, _ in eqns}
    assert "dot_general" in names and "scan" in names
    assert not names & {"while", "cond", "sort"}, names
    per_shard = rows // actors
    row_keyed = [
        (name, scope, shapes) for name, scope, shapes in eqns
        if (name == "gather" or name.startswith("scatter"))
        and shapes[1] and shapes[1][0] >= per_shard // 2
    ]
    assert row_keyed == []
    loops = {}
    for name, scope, _ in eqns:
        level = re.search(r"tree/level(\d+)/hist", scope)
        if name == "scan" and level:
            loops[int(level.group(1))] = loops.get(int(level.group(1)), 0) + 1
    assert loops == {d: 2 if d == 0 else 3 for d in range(depth)}

"""The wide cell (``epsilon-d8.default``), run by hand like the rest of this
directory (not part of tier-1):

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_wide_cell.py -q

Its CPU rehearsal (20,000 x 2,000 rows: two minutes), and one planted fault
for each reader the cell brought: a build that takes one feature a tile, a
split scan that takes four times its seconds, a routing whose seconds the
trace files under another scope. Each fault has to move its reader's number
and no other's.
"""

import contextlib
import io
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import run as bench_run  # noqa: E402

CELL = "epsilon-d8.default"
# at 20,000 rows a 256-bin histogram holds 78 rows a bin, so the best split
# is a matter of sampling noise; the configuration's limits are set at 400,000
TEST_LIMITS = {"loss": 1e-5, "leaf": 1e-3, "cover": 1e-3, "split": 0.15,
               "split_deep": 0.5}
NEW = ("hist.tile_steps_per_round", "split.time_pct", "partition.time_pct")


def _read(name, ctx):
    return bench_run.load_metric_reader(name)(ctx)


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
    assert line["device"]["platform"] == "cpu" and line["metrics"] == {}
    # the CPU's build is the scatter-add and its trace has no device plane:
    # none of the cell's three readers finds anything to read
    assert not set(NEW) & set(line["rehearsal"])
    assert "ingest.load_s" in line["rehearsal"]


def _ctx(tile_steps=50470, split_s=0.10, partition_as="partition"):
    scopes = {"tree/level0/hist": 0.16, "tree/level7/hist": 1.96,
              "tree/level7/split": split_s,
              f"tree/level7/{partition_as}": 0.09, "tree": 0.01,
              "(unscoped)": 0.10}
    attrs = {"radix_by_width": {"2": 8, "128": 1},
             "ftiles_by_width": {"2": 63, "128": 250}}
    if tile_steps is not None:
        attrs["tile_steps_per_round"] = tile_steps
    return {"trace": {"scopes_by_device": {"/device:TPU:0": scopes}},
            "additional_results": {"obs": {"timeline": [
                {"kind": "event", "name": "hist.builds", "t0_s": 1.0,
                 "attrs": attrs}]}}}


def _numbers(ctx):
    return {name: _read(name, ctx) for name in NEW}


def test_a_planted_fault_moves_its_reader_and_no_other():
    sound = _numbers(_ctx())
    assert sound == {"hist.tile_steps_per_round": 50470,
                     "split.time_pct": pytest.approx(100 * 0.10 / 2.42),
                     "partition.time_pct": pytest.approx(100 * 0.09 / 2.42)}
    # one feature a tile: 2,000 tiles a row chunk where the rule takes 63-250
    one_a_tile = _numbers(_ctx(tile_steps=49 * 8 * 2000))
    assert one_a_tile["hist.tile_steps_per_round"] > 15 * 50470
    assert {k: v for k, v in one_a_tile.items() if k != NEW[0]} == {
        k: pytest.approx(v) for k, v in sound.items() if k != NEW[0]}
    # a split scan of four times the seconds
    slow_scan = _numbers(_ctx(split_s=0.40))
    assert slow_scan["split.time_pct"] == pytest.approx(100 * 0.40 / 2.72)
    assert slow_scan["split.time_pct"] > 3.5 * sound["split.time_pct"]
    assert slow_scan["hist.tile_steps_per_round"] == 50470
    # the routing's seconds under a scope the reader does not know: the
    # metric falls silent, it does not read another scope's seconds
    misfiled = _numbers(_ctx(partition_as="route"))
    assert misfiled["partition.time_pct"] is None
    assert misfiled["split.time_pct"] == pytest.approx(
        sound["split.time_pct"])
    # a program from before the attribute
    assert _numbers(_ctx(tile_steps=None))[NEW[0]] is None

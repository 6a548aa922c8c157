"""What the four-chip cell adds to the yardstick, run by hand like the
rest of this directory (``conftest.py`` gives the child processes and this
one four CPU devices):

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_mesh_cell.py -q

The two readers of the mesh's counters on hand-made timelines and the one of
its ``allreduce`` scopes on hand-made per-device seconds, the traced
rehearsal of the cell, and the planted fault a one-chip cell cannot have: the
exchange between the chips left out.
"""

import contextlib
import io
import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import run as bench_run  # noqa: E402

CELL = "higgs-d6-dp4.default"
READERS = ["collective.bytes_per_round", "collective.count_per_round"]
MESH_ONLY = READERS + ["collective.time_pct"]
# as test_rehearsal.py's: the configuration's limits are set at 44M rows
TEST_LIMITS = {"loss": 1e-5, "leaf": 1e-3, "cover": 1e-3, "split": 0.15,
               "split_deep": 0.5}


def _event(name, **attrs):
    rec = {"kind": "event", "name": name, "ts": 0.0, "t0_s": 1.0, "seq": 1}
    if attrs:
        rec["attrs"] = attrs
    return rec


def _ctx(*records):
    return {"additional_results": {"obs": {"timeline": list(records)}}}


def _read(name, ctx):
    return bench_run.load_metric_reader(name)(ctx)


def test_the_cell_lists_the_mesh_readers():
    spec = bench_run.load_cell(CELL)
    assert spec["cell"]["chips"] == 4
    assert set(MESH_ONLY) <= {m["name"] for m in spec["per_layer"]}
    one_chip = bench_run.load_cell("higgs-d6.default")
    assert not set(MESH_ONLY) & {m["name"] for m in one_chip["per_layer"]}


def test_readers_on_a_mesh_programs_events():
    ctx = _ctx(
        _event("allreduce.bytes", bytes_per_round=1, collectives_per_round=1),
        {"kind": "span", "name": "allreduce.bytes", "t0_s": 0.0, "dur_s": 1.0,
         "attrs": {"bytes_per_round": 7}},  # a span of that name is no event
        _event("allreduce.bytes", bytes_per_round=2_764_404,
               collectives_per_round=12, mesh={"actors": 4}),
    )
    assert _read("collective.bytes_per_round", ctx) == 2_764_404
    assert _read("collective.count_per_round", ctx) == 12


@pytest.mark.parametrize("ctx, want", [
    # a program that counts the bytes only
    (_ctx(_event("allreduce.bytes", bytes_per_round=2_764_404)),
     [2_764_404, None]),
    # a one-device world
    (_ctx(_event("allreduce.bytes", bytes_per_round=0,
                 collectives_per_round=0)), [0, 0]),
    (_ctx(), [None, None]),
    ({"additional_results": None}, [None, None]),
])
def test_readers_return_none_where_the_program_gives_nothing(ctx, want):
    assert [_read(name, ctx) for name in READERS] == want


def test_collective_time_is_the_share_of_the_device_that_waits_longest():
    level = {"tree/level0/hist": 0.30, "tree/level1/hist": 0.60,
             "tree/level1/split": 0.02, "margin": 0.01}
    ahead = dict(level, **{"tree/level0/allreduce": 0.05,
                           "tree/level1/allreduce": 0.015,
                           "tree/allreduce": 0.005})
    others = dict(level, **{"tree/level0/allreduce": 0.001,
                            "tree/level1/allreduce": 0.001})
    by_device = {"/device:TPU:0": ahead, "/device:TPU:1": others,
                 "/device:TPU:2": others, "/device:TPU:3": others}
    ctx = {"trace": {"scopes_by_device": by_device, "devices": 4}}
    # device 0 waited 0.07 s of its 1.0 s busy; the sum over devices would
    # read 0.076 of 3.796: 2.0%
    assert _read("collective.time_pct", ctx) == pytest.approx(7.0)
    # one device, a CPU trace, a cache older than the scopes: nothing to read
    one = {"trace": {"scopes_by_device": {"/device:TPU:0": level},
                     "devices": 1}}
    assert _read("collective.time_pct", one) is None
    assert _read("collective.time_pct", {"trace": {
        "scopes_by_device": {}, "scopes": {}}}) is None
    assert _read("collective.time_pct", {"trace": None}) is None


def _rehearse(trace, program=None):
    argv = ["--workload", CELL, "--seed", "2147483777", "--seconds", "1",
            "--trace", str(trace), "--rehearse-cpu"]
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = bench_run.run(bench_run.parse(argv), program=program,
                           limits=TEST_LIMITS)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_traced_rehearsal_reports_the_mesh_counters():
    line = _rehearse(1)
    assert line["correct"], line["compared"]
    assert line["device"]["count"] >= 4 and line["metrics"] == {}
    got = {k: v["value"] for k, v in line["rehearsal"].items()}
    # 28 features x 257 buckets x (g, h) float32 for 32 built node
    # histograms, five live-count vectors and the final node sums, each
    # times 2 (n - 1) / n of the ring model
    assert got["collective.bytes_per_round"] == 2_764_404
    assert got["collective.count_per_round"] == 12
    # a CPU trace has no device plane: no scope to read, and no error
    assert "collective.time_pct" not in got and "hist_roofline" not in got
    assert "driver.checkpoint_ms" in got and "ingest.load_s" in got


def test_exchange_between_chips_left_out_is_not_correct():
    """Each actor's statistics from its own rows alone: the model rank 0
    would end with had the per-level psum been an identity."""
    import xgboost_ray_tpu as real

    def first_shard(x, y):
        return real.RayDMatrix(x[: len(x) // 4], y[: len(y) // 4])

    line = _rehearse(0, program=types.SimpleNamespace(
        train=real.train, RayParams=real.RayParams, RayDMatrix=first_shard))
    assert line["correct"] is False, line["compared"]
    cover = line["compared"]["cover"]
    assert cover["value"] > 0.5 > cover["limit"]

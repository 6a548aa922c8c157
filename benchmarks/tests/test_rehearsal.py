"""CPU rehearsal of the benchmark, run by hand (not part of tier-1):

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

It drives every cell end to end at 20,000 rows, holds the control and each
planted fault to ``correct: false``, and checks the pieces of the yardstick
(names, shapes, the trace reduction) against hand-worked values. A CPU run
says nothing about speed: the result line of a rehearsal carries no metric.
"""

import contextlib
import io
import json
import os
import re
import subprocess
import sys
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import reference  # noqa: E402
import run as bench_run  # noqa: E402
import shapes  # noqa: E402
import trace_reduce  # noqa: E402

MANIFEST = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in MANIFEST["workloads"]]
# at 20,000 rows a 256-bin histogram holds 78 rows a bin, so the best split
# is a matter of sampling noise; the configurations' limits are set at 11M
TEST_LIMITS = {"loss": 1e-5, "leaf": 1e-3, "cover": 1e-3, "split": 0.15,
               "split_deep": 0.5}

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_manifest_names_and_units():
    metrics = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
    names = ([m["name"] for m in metrics] + CELLS
             + [c["name"] for c in MANIFEST["configs"]]
             + [w["traffic"] for w in MANIFEST["workloads"]]
             + [k for c in MANIFEST["configs"] for k in c["reduced"]]
             + [m["layer"] for m in MANIFEST["per_layer"]])
    assert all(NAME.match(n) for n in names), names
    assert all(UNIT.match(m["unit"]) for m in metrics)
    assert len(set(m["name"] for m in metrics)) == len(metrics)
    e2e = {m["name"] for m in MANIFEST["end_to_end"]}
    assert all(m["moves"] in e2e for m in MANIFEST["per_layer"])
    for m in MANIFEST["per_layer"]:
        assert os.path.exists(
            os.path.join(BENCH, "metrics", m["name"] + ".py")), m["name"]
    for w in MANIFEST["workloads"]:
        assert os.path.exists(
            os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)


def test_round_work_by_hand():
    # higgs-d6: six levels, every row's 28 one-byte bins and 8 B of (g, h)
    nbytes, ops = shapes.round_work(11_000_000, 28, 6)
    assert nbytes == 6 * 11_000_000 * 36 == 2_376_000_000
    assert ops == 6 * 11_000_000 * 28 * 2
    peak = json.load(open(os.path.join(BENCH, "peaks.json")))["TPU v5 lite"]
    least, bound = shapes.roofline_seconds(nbytes, ops, peak)
    assert bound == "bytes" and abs(least - 2.376e9 / 819e9) < 1e-12


def test_trace_reduction_by_hand():
    rows = [
        ["/host:CPU", "python", "bench.window_open", 0.0, 5.0],
        ["/host:CPU", "python", "bench.trace_stop", 1000.0, 5.0],
        ["/device:TPU:0", "XLA Ops", "while", 100.0, 500.0],
        ["/device:TPU:0", "XLA Ops", "fusion.1", 150.0, 100.0],
        ["/device:TPU:0", "XLA Ops", "fusion.1", 300.0, 100.0],
        ["/device:TPU:0", "XLA Ops", "copy", 700.0, 100.0],
        ["/device:TPU:0", "Steps", "0", 0.0, 1000.0],
    ]
    out = trace_reduce.reduce_trace(rows, [(0.0, 1e-7)])
    assert out["window_s"] == pytest.approx(1e-6)
    assert out["busy_s"] == pytest.approx(6e-7)
    assert dict(out["device_ops"]) == pytest.approx(
        {"while": 3e-7, "fusion.1": 2e-7, "copy": 1e-7})
    assert dict(out["idle_gaps"]) == pytest.approx(
        {"in_dispatch.0": 2e-7, "in_dispatch.1": 1e-7, "host.0": 1e-7})
    assert trace_reduce.reduce_trace(rows[:2]) is None


def test_trace_reduction_on_recorded_trace():
    path = os.path.join(HERE, "fixtures", "trace_rows.json")
    doc = json.load(open(path))
    out = trace_reduce.reduce_trace(doc["rows"], doc["host_spans_s"])
    for key, want in doc["expected"].items():
        assert out[key] == pytest.approx(want, rel=1e-9), key
    assert 0 < out["busy_s"] <= out["window_s"]
    assert out["device_ops"][0] == pytest.approx(doc["expected_top_op"])
    assert out["idle_gaps"][:3] == [
        [k, pytest.approx(v)] for k, v in doc["expected_gaps"]]
    # nothing nested is counted twice: self times add up to the busy time
    assert sum(s for _, s in trace_reduce.reduce_trace(
        [r for r in doc["rows"] if r[1] != "XLA Modules"],
    )["device_ops"]) <= out["busy_s"] * (1 + 1e-9)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_end_to_end(cell):
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", "2147483659", "--seconds", "1", "--trace", "0",
         "--rehearse-cpu"],
        capture_output=True, text=True, env=dict(os.environ,
                                                 JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert list(line)[-1] == "compared"
    assert line["device"]["platform"] == "cpu" and line["metrics"] == {}
    assert set(line["rehearsal"]) == {"round_ms", "setup_s"}
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["compared"]) == {"loss", "leaf", "cover", "split",
                                     "split_deep"}


def test_no_chip_no_result():
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=dict(os.environ,
                                                 JAX_PLATFORMS="cpu"))
    assert p.returncode == bench_run.EXIT_NO_DEVICE
    assert p.stdout.strip() == ""


# -- the comparison has to fail what is broken ------------------------------


def _broken_program(fault):
    """``xgboost_ray_tpu`` with the timed path broken underneath."""
    import xgboost_ray_tpu as real

    def train(params, dtrain, rounds, *, evals_result, **kwargs):
        bst = real.train(params, dtrain, rounds, evals_result=evals_result,
                         **kwargs)
        forest = {k: np.array(getattr(bst.forest, k))
                  for k in bst.forest._fields}
        if fault == "state_unchanged":
            # round 2 was handed round 1's margins back: the same tree
            # again, the same loss reported
            for k in forest:
                forest[k][1] = forest[k][0]
            for curve in evals_result.values():
                for series in curve.values():
                    series[1] = series[0]
        elif fault == "answer_altered":
            leaves = np.flatnonzero(forest["is_leaf"][0])
            big = leaves[np.argmax(forest["cover"][0][leaves])]
            forest["value"][0, big] = forest["value"][
                0, big + 1 if big % 2 else big - 1]
        bst.forest = type(bst.forest)(**forest)
        return bst

    def half_matrix(x, y):
        # half of the batch left out, the statistics taken over the rest
        return real.RayDMatrix(x[: len(x) // 2], y[: len(y) // 2])

    return types.SimpleNamespace(
        train=train, RayParams=real.RayParams,
        RayDMatrix=half_matrix if fault == "half_batch" else real.RayDMatrix)


def _rehearse(cell, program=None):
    argv = ["--workload", cell, "--seed", "2147483777", "--seconds", "1",
            "--trace", "0", "--rehearse-cpu"]
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = bench_run.run(bench_run.parse(argv), program=program,
                           limits=TEST_LIMITS)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.fixture(scope="module")
def sound():
    line = _rehearse(CELLS[0])
    assert line["correct"], line["compared"]
    return line


@pytest.mark.parametrize(
    "fault", ["state_unchanged", "half_batch", "answer_altered"])
def test_fault_in_timed_path_is_not_correct(sound, fault):
    # the fourth fault, the exchange between chips left out, needs a cell on
    # more than one chip; the benchmark has none yet
    line = _rehearse(CELLS[0], program=_broken_program(fault))
    assert line["correct"] is False, line["compared"]
    over = [k for k, c in line["compared"].items()
            if c["value"] > c["limit"]]
    assert over, line["compared"]


def test_lower_precision_control_is_not_correct(sound):
    """The reference put in the program's place one step under the stated
    precision fails the same comparison, and so does every planted fault,
    here over a hand-made forest at test size."""
    import controls
    import datagen

    x, y = datagen.make(20_000, 28, 7, levels=257)
    sets = {"train": (x, y)}
    params = {"max_depth": 3, "eta": 0.3}
    # a forest to stand on: splits from the reference's own candidates
    forest = _stump_forest(x, n_trees=3, depth=3)
    made, loss = controls._in_place_of_program(sets, forest, params)
    ref = reference.follow(sets, made, params, split_trees=range(3))
    ok, compared = reference.compare(loss, made, ref, TEST_LIMITS)
    assert ok or compared["split"]["value"] > TEST_LIMITS["split"]
    assert max(compared[k]["value"] for k in ("loss", "leaf", "cover")) < 1e-6
    out = controls.readings(sets, made, loss, params, TEST_LIMITS)
    for name in ("lowprec", "half_batch", "state_unchanged",
                 "answer_altered"):
        assert any(c["value"] > c["limit"] for c in out[name].values()), (
            name, out[name])


def test_wrong_election_deep_in_a_late_tree_is_not_correct():
    """A split chosen badly below the top levels of a tree of the window
    leaves leaf values, covers and losses self-consistent: only
    ``split_deep`` can see it, and only if that tree is judged."""
    import controls
    import datagen
    import xgboost_ray_tpu as program

    x, y = datagen.make(20_000, 28, 11)
    sets = {"train": (x, y)}
    params = {"max_depth": 4, "eta": 0.3}
    bst = program.train(
        dict(params, objective="binary:logistic", tree_method="tpu_hist"),
        program.RayDMatrix(x, y), 6,
        ray_params=program.RayParams(num_actors=1))
    forest = {k: v.copy() for k, v in
              reference.forest_arrays(bst.forest).items()}
    judged = reference.split_trees_of(11, 5, 6)
    assert judged == [0, 1, 2, 5]

    def numbers(forest):
        made, loss = controls._in_place_of_program(sets, forest, params)
        ref = reference.follow(sets, made, params, split_trees=judged)
        return {k: c["value"] for k, c in reference.compare(
            loss, made, ref, TEST_LIMITS)[1].items()}

    sound = numbers(forest)
    forest["feature"][5, 7:15] = 27  # level 3 of the window's tree: noise
    forest["threshold"][5, 7:15] = np.median(x[:, 27])
    broken = numbers(forest)
    assert max(broken[k] for k in ("loss", "leaf", "cover")) < 1e-6
    assert broken["split"] == pytest.approx(sound["split"])
    assert broken["split_deep"] > 0.9 > 0.3 > sound["split_deep"]


def test_mfu_is_device_time_per_traced_round():
    peak = json.load(open(os.path.join(BENCH, "peaks.json")))["TPU v5 lite"]
    read = bench_run.load_metric_reader("round.mfu_pct")
    ctx = {"peak": peak, "window_s": 1e9, "window_rounds": 1,
           "shapes": {"rows": 11_000_000, "features": 28, "depth": 6,
                      "trees": 1},
           "trace": {"busy_s": 24.0, "window_s": 30.0, "rounds": 5,
                     "devices": 1}}
    # 2.376 GB at 819 GB/s over 4.8 s of device time a round; host time
    # (the window's 30 s, the run's 1e9) moves nothing
    assert read(ctx) == pytest.approx(100 * (2.376e9 / 819e9) / 4.8)
    assert read(dict(ctx, trace=None)) is None
    assert read(dict(ctx, trace=dict(ctx["trace"], rounds=0))) is None


def _stump_forest(x, n_trees, depth):
    """Complete trees that split on the median of features 0, 1, 2..."""
    heap = (1 << (depth + 1)) - 1
    forest = {
        "feature": np.full((n_trees, heap), -1, np.int32),
        "threshold": np.zeros((n_trees, heap), np.float32),
        "default_left": np.zeros((n_trees, heap), bool),
        "is_leaf": np.zeros((n_trees, heap), bool),
        "value": np.zeros((n_trees, heap), np.float32),
        "cover": np.zeros((n_trees, heap), np.float32),
    }
    first_leaf = (1 << depth) - 1
    for t in range(n_trees):
        for node in range(first_leaf):
            f = (t + int(np.log2(node + 1))) % 5
            forest["feature"][t, node] = f
            forest["threshold"][t, node] = np.median(x[:, f])
        forest["is_leaf"][t, first_leaf:] = True
    return forest


def _checkout_with(tmp_path, configs=(), mixes=(), cells=(), metrics=()):
    """A copy of the benchmark with new files and manifest entries laid
    beside the committed ones, none of which is edited."""
    import shutil

    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "xgboost_ray_tpu"),
               root / "xgboost_ray_tpu")
    manifest = json.loads(json.dumps(MANIFEST))
    for name, body in configs:
        (root / f"benchmarks/configs/{name}.json").write_text(
            json.dumps(body))
        manifest["configs"].append({
            "name": name, "source": "test", "reduced": [], "why": "test",
            "file": f"benchmarks/configs/{name}.json"})
    for name, body in mixes:
        (root / f"benchmarks/traffic/{name}.json").write_text(
            json.dumps(body))
    for config, mix in cells:
        manifest["workloads"].append({
            "name": f"{config}.{mix}", "config": config, "traffic": mix,
            "chips": 1, "why": "test"})
    manifest["per_layer"] += list(metrics)
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return root


def _run_in(root, cache, *argv):
    return subprocess.run(
        [sys.executable, str(root / "benchmarks/run.py"), *argv,
         "--rehearse-cpu"], capture_output=True, text=True, cwd=root,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 JAX_COMPILATION_CACHE_DIR=str(cache)))


@pytest.mark.xfail(strict=False, reason=(
    "program fault, PERF.md section 7 row 0: the round programs close over "
    "the sketch's cut points, so a new data set is a new program to the "
    "compile cache; passes once the cuts are an argument (P1)"))
def test_a_new_data_set_of_the_same_shape_compiles_nothing_new(tmp_path):
    """What the cells cannot show while they stand on the grid: with the
    source's continuous features, a second data set of the same shape has to
    find every round program in the cache the first one left."""
    config = json.load(open(os.path.join(BENCH, "configs", "higgs-d6.json")))
    config["data"] = {"levels": None}
    root = _checkout_with(tmp_path, configs=[("higgs-d6-cont", config)],
                          cells=[("higgs-d6-cont", "default")])
    cache = tmp_path / "cache"
    entries = []
    for seed in ("5", "6"):
        p = _run_in(root, cache, "--workload", "higgs-d6-cont.default",
                    "--seed", seed, "--seconds", "1", "--trace", "0")
        assert p.returncode == 0, p.stderr[-2000:]
        entries.append(set(os.listdir(cache)))
    assert entries[0], "the first run left nothing in the cache"
    assert entries[1] == entries[0], sorted(entries[1] - entries[0])


def test_new_cell_config_mix_and_metric_are_only_new_files(tmp_path):
    """README's recipe: a configuration with a generator of its own, a mix,
    a cell and a per-layer metric added as new files and new manifest
    entries, with no file edited."""
    config = json.load(open(os.path.join(BENCH, "configs", "higgs-d6.json")))
    config["params"]["max_depth"] = 4
    config["generator"] = "datagen_flipped"
    mix = json.load(open(os.path.join(BENCH, "traffic", "default.json")))
    mix["ray_params"]["checkpoint_frequency"] = 0
    mix["chunk_rounds"] = 10
    root = _checkout_with(
        tmp_path, configs=[("higgs-d4", config)], mixes=[("nosave", mix)],
        cells=[("higgs-d4", "nosave")],
        metrics=[{
            "name": "driver.rounds_per_dispatch", "unit": "rounds",
            "better": "higher", "source": "program_span", "layer": "driver",
            "moves": "round_ms", "workloads": ["higgs-d4.nosave"]}])
    (root / "benchmarks/metrics/driver.rounds_per_dispatch.py").write_text(
        "def read(ctx):\n"
        "    return ctx['window_rounds'] / len(ctx['in_window'])\n")
    (root / "benchmarks/datagen_flipped.py").write_text(
        "import datagen\n\n\n"
        "def make(rows, features, seed, stream=0, **data):\n"
        "    x, y = datagen.make(rows, features, seed, stream, **data)\n"
        "    return x, 1.0 - y\n")
    p = _run_in(root, tmp_path / "cache", "--workload", "higgs-d4.nosave",
                "--seed", "11", "--seconds", "1", "--trace", "1")
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["rehearsal"]["driver.rounds_per_dispatch"]["value"] == 10
    assert line["attempted"] == 20

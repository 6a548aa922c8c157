"""What a new deployment meets of the harness, run by hand like the rest of
this directory (not part of tier-1):

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_contract.py -q

Data: whatever a generator returns, the pair ``(x, y)`` or a mapping of
``RayDMatrix``'s keyword names, reaches ``RayDMatrix``, the reference and the
controls. Shapes: a tree bounded by its leaves has levels, so a roofline.
Trace: readers get the device's seconds by scope and by device, from the
benchmark's own reading of the trace file. The fixture configuration
(``fixtures/groups-l31.json``: query groups, a weight a row, ``max_depth`` 0
with 31 leaves) is no cell: ``load_cell`` is patched to find it.
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
ROOT = os.path.dirname(BENCH)
FIXTURES = os.path.join(HERE, "fixtures")
sys.path.insert(0, BENCH)
sys.path.insert(0, FIXTURES)

import run as bench_run  # noqa: E402
import shapes  # noqa: E402
import trace_scopes  # noqa: E402

PEAK = json.load(open(os.path.join(BENCH, "peaks.json")))["TPU v5 lite"]
MANIFEST = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


def _read(name, ctx):
    return bench_run.load_metric_reader(name)(ctx)


# -- shapes -----------------------------------------------------------------


@pytest.mark.parametrize("config, want", [
    ("higgs-d6", (11_000_000, 28, 6, 1)),
    ("higgs-d8", (11_000_000, 28, 8, 1)),
    ("higgs-d6-dp4", (44_000_000, 28, 6, 1)),
])
def test_cell_shapes_of_the_committed_configurations(config, want):
    s = shapes.cell_shapes(
        json.load(open(os.path.join(BENCH, "configs", config + ".json"))))
    assert (s["rows"], s["features"], s["depth"], s["trees"]) == want


@pytest.mark.parametrize("params, depth", [
    ({"max_depth": 0, "max_leaves": 255}, 8),   # LightGBM's Higgs experiment
    ({"max_depth": 0, "max_leaves": 31}, 5),
    ({"max_leaves": 256}, 8),
    ({"max_leaves": 257}, 9),
    ({"max_depth": 4, "max_leaves": 255}, 4),   # a positive depth is the bound
    ({"max_depth": 0}, 6), ({}, 6),             # xgboost's default
])
def test_a_tree_bounded_by_leaves_has_levels(params, depth):
    config = {"rows": 1000, "features": 4, "params": params}
    assert shapes.cell_shapes(config)["depth"] == depth


def _mfu_ctx(devices, **trace):
    return {"peak": PEAK,
            "shapes": {"rows": 44_000_000, "features": 28, "depth": 6,
                       "trees": 1},
            "trace": dict({"busy_s": 2.16, "window_s": 2.18, "rounds": 5,
                           "devices": devices}, **trace)}


def test_mfu_sets_one_devices_rows_against_one_chips_peak():
    one, four = (_read("round.mfu_pct", _mfu_ctx(n)) for n in (1, 4))
    assert four == pytest.approx(one / 4)
    # 11M rows a device: 2.376 GB at 819 GB/s over 0.432 s of device time
    assert four == pytest.approx(100 * (2.376e9 / 819e9) / 0.432)


def test_a_leaf_bounded_configuration_has_a_roofline_that_is_not_zero():
    config = {"rows": 11_000_000, "features": 28,
              "params": {"max_depth": 0, "max_leaves": 255}}
    ctx = dict(_mfu_ctx(1), shapes=shapes.cell_shapes(config))
    assert _read("round.mfu_pct", ctx) == pytest.approx(
        100 * (8 * 11e6 * 36 / 819e9) / 0.432)


# -- the readers of the scopes ----------------------------------------------


def _levels(hist):
    return {f"tree/level{d}/hist": s for d, s in enumerate(hist)} | {
        "tree/level0/split": 0.004, "tree/level0/partition": 0.01,
        "tree": 0.02, "margin": 0.002, "(unscoped)": 0.01}


def test_hist_roofline_is_the_slowest_devices_hist_seconds():
    fast, slow = _levels([0.33, 0.34, 0.35]), _levels([0.33, 0.34, 0.38])
    by_device = {f"/device:TPU:{i}": fast for i in range(3)}
    by_device["/device:TPU:3"] = slow
    ctx = _mfu_ctx(4, scopes_by_device=by_device,
                   scopes=trace_scopes.summed(by_device))
    # a device's 11M rows, six levels, over 1.05 s of hist in five rounds
    assert _read("hist_roofline", ctx) == pytest.approx(
        100 * (2.376e9 / 819e9) / (1.05 / 5))
    assert _read("hist_roofline", ctx) > _read("round.mfu_pct", ctx)
    # a leaf-wise grower names its builds ``tree/hist``
    one = _mfu_ctx(1, scopes_by_device={"/device:TPU:0": {
        "tree/hist": 2.1, "tree/split": 0.1}})
    assert _read("hist_roofline", one) == pytest.approx(
        100 * (4 * 2.376e9 / 819e9) / (2.1 / 5))


@pytest.mark.parametrize("trace", [
    None,
    {"scopes_by_device": {}, "scopes": {}},
    # an executable from a cache written before the program named its scopes
    {"scopes_by_device": {"/device:TPU:0": {"(unscoped)": 2.1}},
     "scopes": {"(unscoped)": 2.1}},
])
def test_new_readers_return_none_where_no_scope_is_named(trace):
    ctx = _mfu_ctx(1, **trace) if trace else dict(_mfu_ctx(1), trace=None)
    assert _read("hist_roofline", ctx) is None
    assert _read("collective.time_pct", ctx) is None
    no_peak = dict(_mfu_ctx(1, scopes_by_device={"/device:TPU:0": _levels(
        [0.3])}), peak=None)
    assert _read("hist_roofline", no_peak) is None


# -- the scopes' way from a trace file into ctx["trace"] --------------------


def _varint(v):
    out = bytearray()
    while True:
        out.append((v & 0x7F) | (0x80 if v > 0x7F else 0))
        v >>= 7
        if not v:
            return bytes(out)


def _field(num, val):
    if isinstance(val, int):
        return _varint(num << 3) + _varint(val)
    return _varint(num << 3 | 2) + _varint(len(val)) + bytes(val)


def _entry(key, msg):
    return _field(1, key) + _field(2, msg)


def _event(md, offset_ps, dur_ps, extra=b""):
    return _field(1, md) + _field(2, offset_ps) + _field(3, dur_ps) + extra


def _device_plane(name, events):
    """An XPlane as the chip's profiler writes one: ``tf_op`` paths in the
    event metadata, the operations on the line ``XLA Ops`` (picoseconds
    after the line's start), a summary line beside it."""
    def op(md_id, op_name, tf_op):
        stat = _field(1, 7) + _field(5, tf_op.encode())
        return _field(4, _entry(md_id, _field(1, md_id) + _field(
            2, op_name.encode()) + _field(5, stat)))

    body = "jit(run)/while/body/closed_call/"
    ops = (op(1, "%while.1", "jit(run)/while")
           + op(2, "%fusion.2", body + "tree/level0/hist/dot_general:")
           + op(3, "%all-reduce.3", body + "tree/level0/allreduce/psum:")
           # a cond branch repeats the scopes it sits in
           + op(4, "%fusion.4", body + "tree/level1/hist/cond/branch_1_fun/"
                "tree/level1/hist/add:")
           + op(5, "%copy.5", body + "margin/jit(_where)/select_n:")
           + op(300, "%fusion.300", body + "tree/level1/split/reduce:"))
    stat_md = _field(5, _entry(7, _field(1, 7) + _field(2, b"tf_op")))
    line = (_field(1, 2) + _field(2, b"XLA Ops") + _field(3, 1_000)
            + b"".join(_field(4, e) for e in events))
    modules = _field(2, b"XLA Modules") + _field(4, _event(1, 0, 10**9))
    return (_field(2, name.encode()) + stat_md + ops + _field(3, line)
            + _field(3, modules))


def _write_trace(trace_dir):
    us = 10**6  # picoseconds
    per_event_stat = _field(4, _field(1, 9) + _field(3, 77))
    dev0 = _device_plane("/device:TPU:0", [
        _event(1, 0, 1000 * us), _event(2, 100 * us, 300 * us, per_event_stat),
        _event(3, 400 * us, 200 * us), _event(4, 600 * us, 100 * us),
        _event(300, 700 * us, 50 * us), _event(5, 1500 * us, 500 * us)])
    dev1 = _device_plane("/device:TPU:1", [
        _event(1, 0, 1000 * us), _event(2, 100 * us, 480 * us),
        _event(3, 580 * us, 20 * us), _event(4, 600 * us, 100 * us),
        _event(5, 1500 * us, 500 * us)])
    idle = _field(2, b"/device:TPU:2")  # a chip the job does not use
    host = _field(2, b"/host:CPU") + _field(3, _field(2, b"XLA Ops") + _field(
        4, _event(1, 0, 9000 * us)))
    run_dir = os.path.join(trace_dir, "plugins", "profile", "run")
    os.makedirs(run_dir)
    with open(os.path.join(run_dir, "host.xplane.pb"), "wb") as f:
        f.write(b"".join(_field(1, p) for p in (dev0, dev1, idle, host)))


WANT_BY_DEVICE = {
    "/device:TPU:0": {
        "(unscoped)": 350e-6,           # the while, less its body
        "tree/level0/hist": 300e-6, "tree/level0/allreduce": 200e-6,
        "tree/level1/hist": 100e-6, "tree/level1/split": 50e-6,
        "margin": 500e-6},
    "/device:TPU:1": {
        "(unscoped)": 400e-6, "tree/level0/hist": 480e-6,
        "tree/level0/allreduce": 20e-6, "tree/level1/hist": 100e-6,
        "margin": 500e-6},
}


def test_scope_seconds_by_device_from_a_hand_written_trace(tmp_path):
    from xgboost_ray_tpu.obs import DEVICE_SCOPES, device

    _write_trace(str(tmp_path))
    got = trace_scopes.scope_times_by_device(str(tmp_path), DEVICE_SCOPES)
    assert set(got) == set(WANT_BY_DEVICE)
    for plane, want in WANT_BY_DEVICE.items():
        assert got[plane] == pytest.approx(want), plane
        assert sum(got[plane].values()) == pytest.approx(1500e-6)  # busy
    # the benchmark's copy reads what the program's own reader reads
    theirs = device.scope_times_by_device(str(tmp_path))
    assert {p: t for p, t in theirs.items() if t} == {
        p: pytest.approx(t) for p, t in got.items()}
    assert trace_scopes.scope_times_by_device(
        str(tmp_path / "nothing_here"), DEVICE_SCOPES) == {}


def test_reduce_run_trace_hands_readers_the_scopes_then_deletes(tmp_path,
                                                                monkeypatch):
    """``reduce_run_trace`` over a trace directory: ``scopes_by_device``,
    ``scopes`` and ``devices`` in what a reader gets as ``ctx["trace"]``,
    and the directory gone after the reading, not before."""
    from xgboost_ray_tpu.obs import DEVICE_SCOPES

    import trace_reduce

    trace_dir = tmp_path / "bench_trace"
    _write_trace(str(trace_dir))
    seen = []

    def rows_of_the_same_file(path):
        seen.append(os.path.isdir(path))
        us = 1e3  # ProfileData gives nanoseconds
        rows = [["/host:CPU", "python", "bench.window_open", 0.0, 1.0],
                ["/host:CPU", "python", "bench.trace_stop", 2100 * us, 1.0]]
        for plane, hist0 in (("/device:TPU:0", 300), ("/device:TPU:1", 480)):
            rows += [[plane, "XLA Ops", "%while.1", 0.0, 1000 * us],
                     [plane, "XLA Ops", "%fusion.2", 100 * us, hist0 * us],
                     [plane, "XLA Ops", "%copy.5", 1500 * us, 500 * us]]
        return rows

    # jax.profiler.ProfileData needs a trace the profiler wrote itself
    monkeypatch.setattr(trace_reduce, "read_events", rows_of_the_same_file)
    clock = types.SimpleNamespace(trace_dir=str(trace_dir), trace_open=10.0,
                                  trace_close=10.0021)
    timeline = [{"rounds": 5, "seconds": 0.002, "start": 10.00001,
                 "end": 10.00201}]
    reduced = bench_run.reduce_run_trace(clock, timeline,
                                         scope_names=DEVICE_SCOPES)
    assert seen == [True] and not trace_dir.exists()
    assert reduced["devices"] == 2 and reduced["rounds"] == 5
    assert reduced["busy_s"] == pytest.approx(1500e-6)
    assert reduced["scopes_by_device"] == {
        p: pytest.approx(t) for p, t in WANT_BY_DEVICE.items()}
    assert reduced["scopes"] == pytest.approx({
        "(unscoped)": 750e-6, "tree/level0/hist": 780e-6,
        "tree/level0/allreduce": 220e-6, "tree/level1/hist": 200e-6,
        "tree/level1/split": 50e-6, "margin": 1000e-6})
    # and the readers on it: device 1 spent most under hist, device 0 waited
    ctx = {"peak": PEAK, "trace": reduced,
           "shapes": {"rows": 2000, "features": 28, "depth": 2, "trees": 1}}
    least = 2 * 1000 * 36 / 819e9
    assert _read("hist_roofline", ctx) == pytest.approx(
        100 * least / (580e-6 / 5))
    assert _read("round.mfu_pct", ctx) == pytest.approx(
        100 * least / (1500e-6 / 5))
    assert _read("collective.time_pct", ctx) == pytest.approx(
        100 * 200 / 1500)


# -- the mapping form, end to end -------------------------------------------


def _fixture_cell(mix):
    config = json.load(open(os.path.join(FIXTURES, "groups-l31.json")))

    def load_cell(name):
        return {
            "cell": {"name": name, "config": "groups-l31", "traffic": mix,
                     "chips": 1, "why": "test fixture"},
            "config": config,
            "traffic": json.load(open(os.path.join(
                BENCH, "traffic", mix + ".json"))),
            "end_to_end": MANIFEST["end_to_end"],
            "per_layer": [m for m in MANIFEST["per_layer"]
                          if "workloads" not in m],
        }
    return load_cell


def _rehearse(monkeypatch, mix, trace, program, controls=1):
    monkeypatch.setattr(bench_run, "load_cell", _fixture_cell(mix))
    argv = ["--workload", "groups-l31." + mix, "--seed", "2147483777",
            "--seconds", "1", "--trace", str(trace), "--rehearse-cpu",
            "--controls", str(controls)]
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = bench_run.run(bench_run.parse(argv), program=program)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def _recording(monkeypatch):
    """The program with a ``RayDMatrix`` that records what it is given, and
    the fixture's generator, reference and controls recording what they make
    and are handed. Two things the program refuses today are taken up by
    this stand-in, since the fixture proves the harness and not the program:
    ``weight`` beside ``qid`` (``matrix.py``: per-group weight is not
    implemented) is recorded and not passed on, and ``max_depth`` 0 (``params.py``:
    must be >= 1) is trained as depth 8, which holds 31 leaves."""
    import groups_controls
    import groups_gen
    import groups_ref
    import xgboost_ray_tpu as real

    log = {"made": [], "matrix": [], "follow": [], "readings": [],
           "splits": []}

    def matrix(*args, **kwargs):
        log["matrix"].append((args, kwargs))
        return real.RayDMatrix(*args, **{k: v for k, v in kwargs.items()
                                         if k != "weight"})

    def recorder(fn, key):
        def wrapped(sets, *args, **kwargs):
            log[key].append(sets)
            return fn(sets, *args, **kwargs)
        return wrapped

    make = groups_gen.make

    def made(*args, **kwargs):
        log["made"].append(make(*args, **kwargs))
        return log["made"][-1]

    monkeypatch.setattr(groups_gen, "make", made)
    monkeypatch.setattr(groups_ref, "follow",
                        recorder(groups_ref.follow, "follow"))
    monkeypatch.setattr(groups_controls, "readings",
                        recorder(groups_controls.readings, "readings"))
    monkeypatch.setattr(groups_controls, "every_tree_splits",
                        recorder(groups_controls.every_tree_splits, "splits"))
    program = types.SimpleNamespace(
        RayParams=real.RayParams, RayDMatrix=matrix,
        train=lambda params, *a, **kw: real.train(
            dict(params, max_depth=8), *a, **kw))
    return program, log


def test_a_mapping_reaches_the_matrix_the_reference_and_the_controls(
        monkeypatch):
    """The per-round mix with a validation set: both sets in the mapping
    form, the validation rows cut with the training rows."""
    program, log = _recording(monkeypatch)
    line = _rehearse(monkeypatch, "earlystop", 0, program)
    assert line["correct"], line["compared"]
    assert set(line["compared"]) == {"handed", "reported"}
    train, valid = log["made"]
    # 20,000 rehearsal rows of the configuration's 40,000: half its 4,000
    assert len(train["qid"]) == 20_000 and len(valid["qid"]) == 2_000
    assert [(args, sorted(kwargs)) for args, kwargs in log["matrix"]] == [
        ((), ["data", "label", "qid", "weight"])] * 2
    for made, (_, kwargs) in zip((train, valid), log["matrix"]):
        assert all(kwargs[k] is made[k] for k in made)
    for key in ("follow", "readings", "splits"):
        (sets,) = log[key]
        assert sets["train"] is train and sets["valid"] is valid, key


def test_traced_rehearsal_of_a_leaf_bounded_tree(monkeypatch):
    """The fused mix, traced: a CPU trace has no device plane, so the
    reduced trace is ``None`` and the readers of the scopes find nothing,
    without an error; the levels come from ``max_leaves``."""
    program, log = _recording(monkeypatch)
    contexts = []
    reader = bench_run.load_metric_reader

    def spying(name):
        read = reader(name)

        def spy(ctx):
            contexts.append(ctx)
            return read(ctx)
        return spy

    monkeypatch.setattr(bench_run, "load_metric_reader", spying)
    line = _rehearse(monkeypatch, "default", 1, program, controls=0)
    assert line["correct"] and line["attempted"] == 10
    assert log["follow"][0]["train"] is log["made"][0]
    got = line["rehearsal"]
    assert "hist_roofline" not in got and "round.mfu_pct" not in got
    assert "driver.checkpoint_ms" in got and "ingest.load_s" in got
    ctx = contexts[0]
    assert ctx["trace"] is None
    assert ctx["shapes"] == {"rows": 20_000, "features": 8, "depth": 5,
                             "trees": 1}


def test_the_pair_form_reaches_the_matrix_as_two_arguments(monkeypatch):
    """A committed configuration's generator returns ``(x, y)``: the call is
    the parent's ``RayDMatrix(x, y)``."""
    import xgboost_ray_tpu as real

    calls = []

    def matrix(*args, **kwargs):
        calls.append((len(args), sorted(kwargs)))
        raise _Stop

    program = types.SimpleNamespace(RayParams=real.RayParams, train=None,
                                    RayDMatrix=matrix)
    argv = ["--workload", "higgs-d6.default", "--seed", "5", "--seconds",
            "1", "--trace", "0", "--rehearse-cpu"]
    with pytest.raises(_Stop):
        bench_run.run(bench_run.parse(argv), program=program)
    assert calls == [(2, [])]


def test_what_the_matrix_refuses_the_harness_does_not_hide(monkeypatch):
    """``run.py`` names no field: a key ``RayDMatrix`` does not take, or a
    pair of keys it refuses, is its own to refuse."""
    import xgboost_ray_tpu as real

    monkeypatch.setattr(bench_run, "load_cell", _fixture_cell("default"))
    argv = ["--workload", "groups-l31.default", "--seed", "5", "--seconds",
            "1", "--trace", "0", "--rehearse-cpu"]
    with pytest.raises(NotImplementedError, match="per-group weight"):
        bench_run.run(bench_run.parse(argv), program=real)


class _Stop(Exception):
    pass

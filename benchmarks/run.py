"""One run of one benchmark cell.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell, configuration, traffic mix or per-layer
metric is data found by the names in ``BENCHMARK.json`` (see README.md); this
file names none of them. One process, one ``xgboost_ray_tpu.train()`` call:
set-up is everything from process start until the window opens, the window
is timed from the program's public callbacks, and the comparison with the
plain reference runs once the window has closed and the peak is read.
"""

import time

_PROCESS_START = time.time()

import argparse  # noqa: E402
import collections.abc  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)

EXIT_NO_DEVICE = 3
REHEARSAL_ROWS = 20_000
DUMP_TRACE_ROWS = 200_000


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name):
    """The cell's entry, configuration, traffic mix and metric entries."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; it has "
                         f"{sorted(cells)}")
    cell = cells[name]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(ROOT, entry["file"])
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")

    def mine(metric):
        return name in metric.get("workloads", [name])

    return {
        "cell": cell, "config": config, "traffic": traffic,
        "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
        "per_layer": [m for m in bench["per_layer"] if mine(m)],
    }


def load_metric_reader(name):
    """``benchmarks/metrics/<name>.py``'s ``read(ctx)``."""
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


class Clock:
    """What the harness sees of a run through the program's public hooks.

    As a ``DistributedCallback`` it dates every round record (on the fused
    path they arrive in a burst after each chunk); as a ``TrainingCallback``
    (per-round mixes only: it switches the fused path off) it dates every
    iteration's end and asks for the stop past the deadline. It opens the
    window after the warm-up, and in a traced run starts the profiler there
    and stops it ``trace_rounds`` rounds later."""

    def __init__(self, warmup_rounds, seconds, trace_rounds, per_round):
        self.warmup_rounds = warmup_rounds
        self.seconds = seconds
        self.trace_rounds = trace_rounds
        self.per_round = per_round
        self.round_ends = []  # host time of each round's record
        self.iteration_ends = []
        self.window_open = None
        self.window_open_wall = None
        self.window_close = None
        self.trace_dir = None
        self.trace_open = None
        self.trace_close = None
        self.trace_stop_s = 0.0

    # -- DistributedCallback ------------------------------------------------
    def after_round(self, actor, record, *args, **kwargs):
        now = time.perf_counter()
        if actor.rank != 0:
            return
        self.round_ends.append(now)
        if not self.per_round:
            self._tick(len(self.round_ends), now)

    # -- TrainingCallback ---------------------------------------------------
    def after_iteration(self, model, epoch, evals_log):
        now = time.perf_counter()
        self.iteration_ends.append(now)
        return self._tick(epoch + 1, now)

    def _tick(self, rounds_done, now):
        if rounds_done == self.warmup_rounds:
            self._start_trace()
            self.window_open = time.perf_counter()
            self.window_open_wall = time.time()
            return False
        if self.window_open is None:
            return False
        self.window_close = now
        if (self.trace_open is not None and self.trace_close is None
                and rounds_done >= self.warmup_rounds + self.trace_rounds):
            self._stop_trace()
        return self.per_round and now - self.window_open >= self.seconds

    def _start_trace(self):
        if not self.trace_rounds:
            return
        import jax

        self.trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.window_open"):
            self.trace_open = time.perf_counter()

    def _stop_trace(self):
        import jax

        with jax.profiler.TraceAnnotation("bench.trace_stop"):
            self.trace_close = time.perf_counter()
        jax.profiler.stop_trace()
        self.trace_stop_s = time.perf_counter() - self.trace_close

    # the rest of both callback protocols
    def _nothing(self, *args, **kwargs):
        return None

    on_init = before_data_loading = after_data_loading = _nothing
    before_train = after_train = before_predict = after_predict = _nothing

    def before_training(self, model):
        return model

    def after_training(self, model):
        return model

    def before_iteration(self, model, epoch, evals_log):
        return False


def plan_rounds(traffic, seconds, nominal_round_s):
    """(rounds to ask for, warm-up rounds, rounds traced) for this run."""
    if traffic["mode"] == "fused":
        chunk = int(traffic["chunk_rounds"])
        warm = chunk * int(traffic["warmup_chunks"])
        k = max(1, math.floor(seconds / (chunk * nominal_round_s)))
        return warm + chunk * k, warm, chunk
    warm = int(traffic["warmup_rounds"])
    # the deadline stops the run; the cap only has to lie beyond it
    cap = warm + int(4 * seconds / nominal_round_s) + 10
    return cap, warm, int(traffic["trace_rounds"])


def dispatch_timeline(chunk_times, clock):
    """Each dispatch as ``{"rounds", "seconds", "start", "end"}`` on the
    harness clock: its end is the first round record it produced, its start
    lies ``seconds`` (the program's own reading) before that."""
    out, done = [], 0
    for c in chunk_times:
        if done >= len(clock.round_ends):
            break
        end = clock.round_ends[done]
        out.append({"rounds": c["rounds"], "seconds": c["seconds"],
                    "start": end - c["seconds"], "end": end})
        done += c["rounds"]
    return out


def reduce_run_trace(clock, timeline, dump_path="", scope_names=()):
    """The run's trace, reduced; the host's spans between dispatches come
    from the timeline. ``scope_names`` are the names the program gives its
    device scopes: the reduced trace holds the device's seconds under each
    scope path, per device (``scopes_by_device``) and over all of them
    (``scopes``). The trace's files are deleted once both are read."""
    import trace_reduce
    import trace_scopes

    rows_ev, by_device, scopes_read_s = [], {}, 0.0
    if clock.trace_dir:
        rows_ev = trace_reduce.read_events(clock.trace_dir)
        t_scopes = time.perf_counter()
        by_device = trace_scopes.scope_times_by_device(clock.trace_dir,
                                                       scope_names)
        scopes_read_s = time.perf_counter() - t_scopes
        shutil.rmtree(clock.trace_dir, ignore_errors=True)
    traced = [d for d in timeline if d["end"] > clock.trace_open
              and d["start"] < clock.trace_close]
    edges = [clock.trace_open] + [t for d in traced for t in (
        max(d["start"], clock.trace_open),
        min(d["end"], clock.trace_close))] + [clock.trace_close]
    host_spans = [(edges[i] - clock.trace_open, edges[i + 1] - clock.trace_open)
                  for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    if dump_path and rows_ev:
        os.makedirs(os.path.dirname(dump_path) or ".", exist_ok=True)
        with open(dump_path, "w") as f:
            # markers first, so that a cut list keeps them
            rows_ev.sort(key=lambda r: not r[2].startswith("bench."))
            json.dump(rows_ev[:DUMP_TRACE_ROWS], f)
    reduced = trace_reduce.reduce_trace(rows_ev, host_spans)
    if reduced:
        reduced["scopes_by_device"] = by_device
        reduced["scopes"] = trace_scopes.summed(by_device)
        # what the second pass over the trace's file cost the run
        reduced["scopes_read_s"] = scopes_read_s
        # the rounds whose dispatches the traced window holds whole
        reduced["rounds"] = sum(
            d["rounds"] for d in traced
            if d["start"] >= clock.trace_open - 1e-3
            and d["end"] <= clock.trace_close + 1e-3)
    return reduced


def run(args, program=None, limits=None):
    """One run. ``program`` (tests only) stands in for the
    ``xgboost_ray_tpu`` module: whatever has its ``train``, ``RayDMatrix``
    and ``RayParams``; ``limits`` (tests only) replaces the
    configuration's, which are set at the cell's size."""
    spec = load_cell(args.workload)
    cell, config, traffic = spec["cell"], spec["config"], spec["traffic"]

    try:
        import jax
        import xgboost_ray_tpu
        from xgboost_ray_tpu.util import device_record, place_compile_cache
    except ImportError as exc:
        print(f"benchmarks/run.py: the program is not here: {exc}",
              file=sys.stderr)
        return 1
    program = program or xgboost_ray_tpu
    RayDMatrix, RayParams, train = (program.RayDMatrix, program.RayParams,
                                    program.train)
    limits = limits or config["limits"]
    place_compile_cache()
    rec = device_record()
    device = {"platform": rec["platform"], "kind": rec["device_kind"],
              "count": rec["device_count"]}
    want = "cpu" if args.rehearse_cpu else "tpu"
    if device["platform"] != want or device["count"] < cell["chips"]:
        print(f"benchmarks/run.py: JAX found {device}; cell "
              f"{cell['name']!r} needs {cell['chips']} {want} device(s)",
              file=sys.stderr)
        return EXIT_NO_DEVICE
    peaks = load_json(HERE, "peaks.json")
    if not args.rehearse_cpu and device["kind"] not in peaks:
        print(f"benchmarks/run.py: no peaks for device_kind "
              f"{device['kind']!r} in peaks.json", file=sys.stderr)
        return 1

    import shapes

    # the configuration names its generator, its plain reference and its
    # controls: modules of this directory with ``make``; with
    # ``forest_arrays``, ``split_trees_of``, ``follow`` and ``compare``;
    # with ``readings`` and ``every_tree_splits``
    generator = importlib.import_module(config["generator"])
    reference = importlib.import_module(config["reference"])
    rows = REHEARSAL_ROWS if args.rehearse_cpu else config["rows"]
    # a cut run keeps the published ratio of validation to training rows
    valid_rows = max(1, config["valid_rows"] * rows // config["rows"])
    config = dict(config, rows=rows)
    t_data = time.perf_counter()
    sets = {"train": generator.make(rows, config["features"], args.seed,
                                    stream=0, **config["data"])}
    eval_names = [name for _, name in traffic["evals"]]
    if any(s == "valid" for s, _ in traffic["evals"]):
        sets["valid"] = generator.make(valid_rows, config["features"],
                                       args.seed, stream=1, **config["data"])
    data_s = time.perf_counter() - t_data

    rounds, warmup, trace_rounds = plan_rounds(
        traffic, args.seconds, config["nominal_round_s"])
    per_round = traffic["mode"] == "per_round"
    clock = Clock(warmup, args.seconds, trace_rounds if args.trace else 0,
                  per_round)
    # a generator returns the pair (x, y) or a mapping of RayDMatrix's own
    # keyword names; the reference and the controls get ``sets`` as it is
    matrices = {name: (RayDMatrix(**s)
                       if isinstance(s, collections.abc.Mapping)
                       else RayDMatrix(*s)) for name, s in sets.items()}
    ray_params = RayParams(num_actors=cell["chips"],
                           distributed_callbacks=[clock],
                           **traffic["ray_params"])
    kwargs = {}
    if traffic.get("early_stopping_rounds") is not None:
        kwargs["early_stopping_rounds"] = traffic["early_stopping_rounds"]
    if per_round:
        kwargs["callbacks"] = [clock]
    params = config["params"]
    evals_result, extra = {}, {}
    t_train = time.perf_counter()
    bst = train(params, matrices["train"], rounds,
                evals=[(matrices[s], name) for s, name in traffic["evals"]],
                evals_result=evals_result, additional_results=extra,
                ray_params=ray_params, **kwargs)
    train_s = time.perf_counter() - t_train
    if clock.trace_open is not None and clock.trace_close is None:
        clock._stop_trace()

    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    peak_bytes = max(s.get("peak_bytes_in_use") or 0 for s in stats)
    limit_bytes = max(s.get("bytes_limit") or 0 for s in stats)
    forest = reference.forest_arrays(bst.forest)
    n_trees = int(bst.num_boosted_rounds())
    reported = {name: list(evals_result[name][config["loss_metric"]])
                for name in eval_names}
    del bst, matrices
    gc.collect()

    if clock.window_open is None or clock.window_close is None:
        print("benchmarks/run.py: the window never opened", file=sys.stderr)
        return 1
    done = len(clock.iteration_ends) if per_round else len(clock.round_ends)
    window_rounds = done - warmup
    window_s = clock.window_close - clock.window_open
    # a traced run loses the seconds stop_trace takes, where rounds follow it
    stopped_inside = (clock.trace_close is not None
                      and clock.window_close > clock.trace_close)
    steady_s = window_s - (clock.trace_stop_s if stopped_inside else 0.0)
    setup_s = clock.window_open_wall - _PROCESS_START
    timeline = dispatch_timeline(extra["chunk_times_s"], clock)
    in_window = [d for d in timeline if d["start"] >= clock.window_open - 1e-3
                 and d["end"] <= clock.window_close + 1e-3]

    values = {"setup_s": setup_s,
              "round_ms": steady_s * 1000.0 / window_rounds}
    summary = {
        "workload": cell["name"], "seed": args.seed, "rows": rows,
        "rounds": done, "window_rounds": window_rounds,
        "window_s": window_s, "setup_s": setup_s, "data_s": data_s,
        "train_call_s": train_s,
        "ingest_s": timeline[0]["start"] - t_train,
        "first_dispatch_s": timeline[0]["seconds"],
        "steady_dispatch_s": (statistics.median(
            d["seconds"] for d in in_window) if in_window else None),
        "memory_peak_bytes": peak_bytes, "bytes_limit": limit_bytes,
        "memory_peak_share": (peak_bytes / limit_bytes if limit_bytes
                              else None),
        "program": extra.get("device"),
    }

    line = {"attempted": done, "failed": max(0, done - n_trees)}
    if args.trace:
        reduced = reduce_run_trace(clock, timeline, args.dump_trace,
                                   xgboost_ray_tpu.obs.DEVICE_SCOPES)
        ctx = {
            "clock": clock, "timeline": timeline, "in_window": in_window,
            "window_s": steady_s, "window_rounds": window_rounds,
            "train_call": t_train, "trace": reduced,
            "shapes": shapes.cell_shapes(config),
            "peak": None if args.rehearse_cpu else peaks[device["kind"]],
            "additional_results": extra, "config": config,
        }
        values = {}
        for m in spec["per_layer"]:
            v = load_metric_reader(m["name"])(ctx)
            if v is not None:
                values[m["name"]] = v
        if reduced:
            summary["traced_rounds"] = reduced["rounds"]
            summary["scopes_read_s"] = reduced["scopes_read_s"]
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            line["breakdown"] = {"device_ops": reduced["device_ops"],
                                 "idle_gaps": reduced["idle_gaps"]}
    device["memory_peak_bytes"] = peak_bytes

    t_ref = time.perf_counter()
    judged = reference.split_trees_of(args.seed, warmup, n_trees)
    ref = reference.follow(sets, forest, params, split_trees=judged)
    correct, compared = reference.compare(reported, forest, ref, limits)
    if line["failed"]:
        correct = False
    summary["reference_s"] = time.perf_counter() - t_ref
    summary["split_trees"] = judged
    if args.controls:
        controls = importlib.import_module(config["controls"])
        summary["controls"] = controls.readings(sets, forest, reported,
                                                params, limits)
        summary["split_every_tree"] = controls.every_tree_splits(
            sets, forest, params)

    units = {m["name"]: m["unit"]
             for m in spec["end_to_end"] + spec["per_layer"]}
    wanted = spec["per_layer"] if args.trace else spec["end_to_end"]
    metrics = {m["name"]: {"value": values[m["name"]], "unit": units[
        m["name"]]} for m in wanted if m["name"] in values}
    print("[bench] summary " + json.dumps(summary), flush=True)
    for name, c in compared.items():
        print(f"[bench] compared {name}: {c['value']:.6g} limit "
              f"{c['limit']:.6g}", file=sys.stderr)
    print(f"[bench] correct: {correct}", file=sys.stderr, flush=True)
    line = {"correct": correct, **line}
    if args.rehearse_cpu:
        # a CPU timing never goes under a device metric's name
        line["metrics"], line["rehearsal"] = {}, metrics
    else:
        line["metrics"] = metrics
    line["device"] = device
    line["compared"] = compared
    print(json.dumps(line), flush=True)
    return 0


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="the same flow at 20,000 rows on the CPU; prints "
                         "no device metric")
    ap.add_argument("--controls", type=int, default=0,
                    help="also read the control and the planted faults "
                         "(benchmark PRs, to set limits; never the driver)")
    ap.add_argument("--dump-trace", default="",
                    help="write the trace's rows here (to record a fixture)")
    return ap.parse_args(argv)


if __name__ == "__main__":
    sys.exit(run(parse()))

"""Unified observability plane (xgboost_ray_tpu/obs/) tests.

Covers the plane's own guarantees (span nesting, ring-buffer truncation
accounting, histogram edge cases, Prometheus exposition stability, the
shared trace-schema validator) and the instrumentation contract: a traced
``train()`` returns a queryable timeline under
``additional_results["obs"]``, the ``after_round`` callback streams round
records live, and a chaos run's shrink→grow story is reconstructible from
the timeline alone — no driver-log reading, no counter re-derivation.
"""

import json
import os
import threading

import numpy as np
import pytest

from xgboost_ray_tpu import (
    DistributedCallback,
    RayDMatrix,
    RayParams,
    faults,
    obs,
    train,
    validate_trace_records,
)
from xgboost_ray_tpu.obs.metrics import (
    BUCKET_BOUNDS_MS,
    LatencyHistogram,
    MetricsRegistry,
)
from xgboost_ray_tpu.obs.trace import Tracer, recovery_time_s, use_tracer

_PARAMS = {"objective": "binary:logistic", "eval_metric": ["logloss"],
           "max_depth": 3}


def _data(n=256, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, 4).astype(np.float32)
    y = (x[:, 0] + x[:, 1] > 0).astype(np.float32)
    return x, y


# ---------------------------------------------------------------------------
# tracer: spans, events, ring buffer
# ---------------------------------------------------------------------------


def test_span_nesting_records_parent_and_orders_by_end_time():
    t = Tracer(enabled=True, trace_dir="")
    with t.span("outer"):
        with t.span("inner") as attrs:
            attrs["k"] = 1
        t.event("mark", round=2, flag=True)
    recs = t.records()
    assert [r["name"] for r in recs] == ["inner", "mark", "outer"]
    inner, mark, outer = recs
    # seq preserves START order: outer started first
    assert outer["seq"] < inner["seq"] < mark["seq"]
    assert inner["parent"] == outer["seq"]
    assert outer["parent"] is None
    assert inner["attrs"] == {"k": 1}
    assert mark["kind"] == "event" and mark["round"] == 2
    assert mark["attrs"] == {"flag": True}
    assert outer["dur_s"] >= inner["dur_s"] >= 0.0
    assert validate_trace_records(recs) == []


def test_span_yields_mutable_attrs_measured_inside():
    t = Tracer(enabled=True, trace_dir="")
    with t.span("work", round=7) as attrs:
        attrs["bytes"] = 1024
    (rec,) = t.records()
    assert rec["round"] == 7
    assert rec["attrs"]["bytes"] == 1024


def test_ring_buffer_truncation_is_accounted_never_silent():
    t = Tracer(capacity=8, enabled=True, trace_dir="")
    for i in range(20):
        t.event(f"e{i}")
    recs = t.records()
    assert len(recs) == 8
    # oldest dropped, newest kept
    assert [r["name"] for r in recs] == [f"e{i}" for i in range(12, 20)]
    assert t.dropped == 12
    snap = t.snapshot()
    assert snap == {"records": 8, "dropped_spans": 12, "capacity": 8}


def test_disabled_tracer_records_nothing():
    t = Tracer(enabled=False, trace_dir="")
    with t.span("outer"):
        t.event("e")
    assert t.records() == []
    assert t.snapshot()["records"] == 0


def test_rxgb_trace_env_disables(monkeypatch):
    monkeypatch.setenv("RXGB_TRACE", "0")
    assert Tracer().enabled is False
    monkeypatch.setenv("RXGB_TRACE", "1")
    assert Tracer().enabled is True


def test_trace_dir_streams_jsonl_matching_ring(tmp_path):
    t = Tracer(enabled=True, trace_dir=str(tmp_path), rank=3)
    t.event("a", x=1)
    with t.span("b"):
        pass
    t.close()
    path = tmp_path / "trace-rank3.jsonl"
    assert path.exists()
    streamed = [json.loads(line) for line in path.read_text().splitlines()]
    assert streamed == t.records()
    assert validate_trace_records(streamed) == []


def test_export_jsonl_roundtrip(tmp_path):
    t = Tracer(enabled=True, trace_dir="")
    t.event("a")
    t.event("b")
    out = tmp_path / "trace.jsonl"
    assert t.export_jsonl(str(out)) == 2
    assert [json.loads(line)["name"]
            for line in out.read_text().splitlines()] == ["a", "b"]


def test_use_tracer_scopes_current_thread():
    scoped = Tracer(enabled=True, trace_dir="")
    with use_tracer(scoped):
        obs.get_tracer().event("inside")
    assert obs.get_tracer() is not scoped
    assert [r["name"] for r in scoped.records()] == ["inside"]


# ---------------------------------------------------------------------------
# schema validator + timeline queries
# ---------------------------------------------------------------------------


def test_validate_trace_records_flags_malformed():
    bad = [
        {"kind": "span", "name": "a", "ts": 0.0, "t0_s": 0.0, "seq": 1,
         "dur_s": 0.1, "parent": None, "extra": 1},       # unknown key
        {"kind": "event", "name": "b", "ts": 0.0, "t0_s": 0.0,
         "seq": 1},                                       # dup seq
        {"kind": "event", "name": "c", "ts": 0.0, "t0_s": 0.0, "seq": 2,
         "dur_s": 0.5},
        {"kind": "nope", "name": "d", "ts": 0.0, "t0_s": 0.0,
         "seq": 3},                                       # bad kind
        {"kind": "span", "name": "", "ts": "x", "seq": 4, "dur_s": -1,
         "parent": "p"},                                  # no t0_s either
    ]
    problems = validate_trace_records(bad)
    text = "\n".join(problems)
    assert "unknown keys" in text
    assert "duplicate seq" in text
    assert "event carries dur_s" in text
    assert "bad kind 'nope'" in text
    assert "bad name" in text and "bad ts" in text and "bad t0_s" in text
    assert "bad dur_s" in text and "bad parent" in text


def test_recovery_time_s_pairs_failures_with_recoveries():
    def ev(name, ts):
        return {"kind": "event", "name": name, "ts": ts, "seq": int(ts * 10)}

    records = [
        ev("failure.detected", 10.0),
        ev("recovered", 12.0),          # 2 s
        ev("failure.detected", 20.0),   # clock restarted by the next one:
        ev("failure.detected", 23.0),   # repeated failure before progress
        ev("recovered", 24.0),          # 1 s (from the LATEST failure)
        ev("recovered", 30.0),          # unmatched: no open clock, ignored
    ]
    assert recovery_time_s(records) == pytest.approx(3.0)
    assert recovery_time_s([]) == 0.0


# ---------------------------------------------------------------------------
# metrics: histogram edge cases, registry, Prometheus exposition
# ---------------------------------------------------------------------------


def test_histogram_percentile_interpolates_at_bucket_boundaries():
    h = LatencyHistogram("h")
    # one sample: p100 walks to the sample's bucket upper bound; p50 lands
    # mid-bucket by linear interpolation
    h.record(1.0)
    idx = next(
        i for i, b in enumerate(BUCKET_BOUNDS_MS) if 1.0 <= b
    )
    lo = BUCKET_BOUNDS_MS[idx - 1]
    hi = BUCKET_BOUNDS_MS[idx]
    assert h.percentile(1.0) == pytest.approx(hi)
    assert h.percentile(0.5) == pytest.approx(lo + 0.5 * (hi - lo))
    # a sample at/below the smallest bound interpolates from 0
    h2 = LatencyHistogram("h2")
    h2.record(0.0)
    assert 0.0 <= h2.percentile(0.5) <= BUCKET_BOUNDS_MS[0]
    # overflow bucket: beyond the largest bound, extrapolated one factor up
    h3 = LatencyHistogram("h3")
    h3.record(1e9)
    assert h3.percentile(1.0) == pytest.approx(BUCKET_BOUNDS_MS[-1] * 1.26)
    # empty histogram: 0.0, not NaN
    assert LatencyHistogram("h4").percentile(0.99) == 0.0


def test_histogram_rejects_nonfinite_and_clamps_negative():
    h = LatencyHistogram("h")
    for bad in (float("nan"), float("inf"), float("-inf")):
        h.record(bad)
    assert h.total == 0
    assert h.sum_ms == 0.0
    assert h.invalid == 3
    h.record(-5.0)  # clamps to 0: bucket 0, no sum poisoning
    assert h.total == 1
    assert h.sum_ms == 0.0
    assert h.counts[0] == 1
    snap = h.snapshot()
    assert snap["invalid"] == 3 and snap["total"] == 1
    assert np.isfinite(snap["mean_ms"])


def test_histogram_snapshot_is_consistent_under_concurrent_record():
    h = LatencyHistogram("h")
    stop = threading.Event()

    def hammer():
        while not stop.is_set():
            h.record(1.0)

    t = threading.Thread(target=hammer, daemon=True)
    t.start()
    try:
        for _ in range(200):
            snap = h.snapshot()
            # every recorded sample is exactly 1.0 ms: a torn read shows up
            # as counts/total/sum disagreeing with each other
            assert sum(snap["counts"]) == snap["total"]
            assert snap["sum_ms"] == pytest.approx(float(snap["total"]))
    finally:
        stop.set()
        t.join(5.0)


def test_registry_get_or_create_and_type_conflicts():
    reg = MetricsRegistry()
    c = reg.counter("rxgb_test_total")
    assert reg.counter("rxgb_test_total") is c
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("rxgb_test_total")
    with pytest.raises(ValueError, match="invalid metric name"):
        reg.counter("bad name!")


def test_prometheus_exposition_golden():
    """The exposition is byte-stable for a given registry state: metrics
    sorted by name, histogram buckets ascending and cumulative, counts as
    bare ints — the contract a scrape config and this golden pin rely on."""
    reg = MetricsRegistry()
    reg.counter("rxgb_b_total", "b help").inc(3)
    reg.gauge("rxgb_a").set(2.5)
    h = reg.histogram("rxgb_lat_ms")
    h.record(0.04)   # bucket 0 (le 0.05)
    h.record(0.06)   # bucket 1 (le 0.063)
    h.record(1e9)    # overflow (+Inf only)
    text = reg.prometheus_text()
    lines = text.splitlines()
    # deterministic name ordering: a, b, lat
    assert lines[0] == "# TYPE rxgb_a gauge"
    assert lines[1] == "rxgb_a 2.5"
    assert lines[2] == "# HELP rxgb_b_total b help"
    assert lines[3] == "# TYPE rxgb_b_total counter"
    assert lines[4] == "rxgb_b_total 3"
    assert lines[5] == "# TYPE rxgb_lat_ms histogram"
    assert lines[6] == 'rxgb_lat_ms_bucket{le="0.05"} 1'
    assert lines[7] == 'rxgb_lat_ms_bucket{le="0.063"} 2'
    # cumulative counts: every later bucket carries the running total
    assert 'rxgb_lat_ms_bucket{le="+Inf"} 3' in lines
    assert lines[-2] == "rxgb_lat_ms_sum 1000000000.1"
    assert lines[-1] == "rxgb_lat_ms_count 3"
    # bucket lines are sorted ascending by le
    les = [
        float(line.split('le="')[1].split('"')[0])
        for line in lines
        if 'le="' in line and "+Inf" not in line
    ]
    assert les == sorted(les)
    # a second render of the same state is byte-identical
    assert reg.prometheus_text() == text


def test_registry_snapshot_flattens_and_live_gauge():
    reg = MetricsRegistry()
    reg.counter("rxgb_c_total").inc(2)
    reg.gauge("rxgb_live", fn=lambda: 7)
    reg.histogram("rxgb_h_ms").record(3.0)
    snap = reg.snapshot()
    assert snap["rxgb_c_total"] == 2
    assert snap["rxgb_live"] == 7
    assert "counts" not in snap["rxgb_h_ms"]
    assert snap["rxgb_h_ms"]["total"] == 1
    # a dead live-gauge probe must not kill the export
    reg.gauge("rxgb_dead", fn=lambda: 1 / 0)
    assert np.isnan(reg.snapshot()["rxgb_dead"])
    assert "rxgb_dead NaN" in reg.prometheus_text()


# ---------------------------------------------------------------------------
# instrumentation contract: train() timeline, after_round, chaos story
# ---------------------------------------------------------------------------


@pytest.fixture(autouse=True)
def _fast_restarts(monkeypatch):
    monkeypatch.setenv("RXGB_RESTART_BACKOFF_BASE_S", "0")
    yield
    faults.clear_plan()


def test_train_returns_queryable_timeline():
    x, y = _data()
    res = {}
    train(_PARAMS, RayDMatrix(x, y), 3, additional_results=res,
          ray_params=RayParams(num_actors=2, checkpoint_frequency=2))
    o = res["obs"]
    assert validate_trace_records(o["timeline"]) == []
    assert o["dropped_spans"] == 0
    # one round record per boosting round, attributed with world/rows
    assert [r["round"] for r in o["rounds"]] == [0, 1, 2]
    assert all(r["world"] == 2 and r["rows"] == len(x) for r in o["rounds"])
    assert all(r["dur_s"] >= 0 for r in o["rounds"])
    # lifecycle events: checkpoint commits carry their round index
    ck = [e for e in o["events"] if e["name"] == "checkpoint.commit"]
    assert [e["round"] for e in ck] == [1, 2]
    # the attempt span closes over the whole run
    attempts = [r for r in o["timeline"]
                if r["kind"] == "span" and r["name"] == "attempt"]
    assert len(attempts) == 1
    assert attempts[0]["attrs"]["outcome"] == "ok"


def test_train_trace_disabled_omits_obs(monkeypatch):
    monkeypatch.setenv("RXGB_TRACE", "0")
    x, y = _data()
    res = {}
    train(_PARAMS, RayDMatrix(x, y), 2, additional_results=res,
          ray_params=RayParams(num_actors=2, checkpoint_frequency=0))
    assert "obs" not in res


def test_train_streams_per_rank_jsonl(monkeypatch, tmp_path):
    monkeypatch.setenv("RXGB_TRACE_DIR", str(tmp_path))
    x, y = _data()
    res = {}
    train(_PARAMS, RayDMatrix(x, y), 2, additional_results=res,
          ray_params=RayParams(num_actors=2, checkpoint_frequency=0))
    files = sorted(os.listdir(tmp_path))
    assert files == ["trace-rank0.jsonl"]
    streamed = [
        json.loads(line)
        for line in (tmp_path / "trace-rank0.jsonl").read_text().splitlines()
    ]
    assert validate_trace_records(streamed) == []
    names = {r["name"] for r in streamed}
    assert "round" in names


def test_after_round_callback_streams_round_records():
    class Collect(DistributedCallback):
        def __init__(self):
            self.records = []

        def after_round(self, actor, record, *args, **kwargs):
            self.records.append((actor.rank, record))

    cb = Collect()
    x, y = _data()
    dtrain = RayDMatrix(x, y)
    train(_PARAMS, dtrain, 3, evals=[(dtrain, "train")],
          ray_params=RayParams(num_actors=2, checkpoint_frequency=0,
                               distributed_callbacks=[cb]))
    # fan-out: one record per (round, actor)
    assert len(cb.records) == 3 * 2
    rounds_seen = sorted({rec["round"] for _, rec in cb.records})
    assert rounds_seen == [0, 1, 2]
    for rank, rec in cb.records:
        assert rec["world"] == 2
        assert rec["duration_s"] >= 0
        assert "logloss" in rec["metrics"]["train"]


def test_pre_obs_callbacks_without_after_round_still_work():
    """Duck-typed callbacks written against the original (pre-obs) hook
    surface — no after_round at all — must keep working through the
    container fan-out."""

    class Legacy:  # deliberately NOT a DistributedCallback subclass
        hooks = []

        def on_init(self, actor, *args, **kwargs):
            self.hooks.append("on_init")

        def before_data_loading(self, actor, data, *args, **kwargs):
            pass

        def after_data_loading(self, actor, data, *args, **kwargs):
            pass

        def before_train(self, actor, *args, **kwargs):
            pass

        def after_train(self, actor, result_dict, *args, **kwargs):
            self.hooks.append("after_train")

        def before_predict(self, actor, *args, **kwargs):
            pass

        def after_predict(self, actor, predictions, *args, **kwargs):
            pass

    x, y = _data()
    train(_PARAMS, RayDMatrix(x, y), 2,
          ray_params=RayParams(num_actors=2, checkpoint_frequency=0,
                               distributed_callbacks=[Legacy()]))
    assert "after_train" in Legacy.hooks


def test_chaos_shrink_grow_sequence_reconstructible_from_timeline(monkeypatch):
    """The acceptance scenario: kill → shrink → boundary grow leaves a
    machine-readable timeline — fault.injected, failure.detected,
    world.shrink and world.grow events in order with correct round
    indices — so the chaos story no longer needs driver logs or counter
    re-derivation."""
    monkeypatch.setenv("RXGB_ELASTIC_RESTART_RESOURCE_CHECK_S", "0")
    monkeypatch.setenv("RXGB_ELASTIC_RESTART_GRACE_PERIOD_S", "0")
    x, y = _data(512)
    kill_round = 3
    plan = faults.FaultPlan(rules=[
        {"site": "actor.train_round", "action": "raise", "ranks": [1],
         "match": {"round": kill_round}},
        # hold rank 1's reload past the scheduler's fast path so the world
        # actually shrinks, then grows back at a later round boundary
        {"site": "actor.load_shard", "action": "delay", "delay_s": 2.0,
         "match": {"rank": 1}, "at": 2},
    ])
    res = {}
    with faults.active_plan(plan):
        bst = train(_PARAMS, RayDMatrix(x, y), 16, additional_results=res,
                    ray_params=RayParams(num_actors=2, elastic_training=True,
                                         max_failed_actors=1,
                                         max_actor_restarts=2,
                                         checkpoint_frequency=4))
    assert bst.num_boosted_rounds() == 16
    o = res["obs"]
    assert validate_trace_records(o["timeline"]) == []

    by_name = {}
    for e in o["events"]:
        by_name.setdefault(e["name"], []).append(e)
    assert len(by_name["fault.injected"]) >= 1
    assert by_name["fault.injected"][0]["attrs"]["site"] == \
        "actor.train_round"
    (shrink,) = by_name["world.shrink"]
    (grow,) = by_name["world.grow"]
    # rounds 0..kill_round-1 boosted before the kill: the shrunk world takes
    # over AT the kill round; the grow lands at a later round boundary
    assert shrink["round"] == kill_round
    assert shrink["attrs"]["world"] == 1
    assert shrink["attrs"]["orphaned_rows"] == len(x) // 2
    assert grow["round"] > kill_round
    assert grow["attrs"]["world"] == 2
    # ordering: injection → detection → shrink → grow, by seq
    seqs = [
        by_name["fault.injected"][0]["seq"],
        by_name["failure.detected"][0]["seq"],
        shrink["seq"],
        grow["seq"],
    ]
    assert seqs == sorted(seqs)
    # per-round spans attribute the world size through the change: full
    # world before the kill, survivor world at the kill round, full world
    # again from the grow boundary on
    worlds = {r["round"]: r["world"] for r in o["rounds"]}
    assert worlds[kill_round - 1] == 2
    assert worlds[kill_round] == 1
    if grow["round"] < 16:
        assert worlds[grow["round"]] == 2
    # the timeline's failure→recovery clock matches the robustness dict's
    ttr = recovery_time_s(o["timeline"])
    assert ttr == pytest.approx(
        res["robustness"]["time_to_recover_s"], abs=0.05
    )


# ---------------------------------------------------------------------------
# one timeline from inside train(): the monotonic clock, spans at the layer
# boundaries, compiles under their cause, named scopes on the device phases
# (structural checks only: nothing here asserts a duration)
# ---------------------------------------------------------------------------


def _traced(rounds=4, per_round=False, **ray):
    """One small traced train(); ``per_round`` forces the per-round loop
    (a TrainingCallback switches the fused scan off)."""

    class Noop:
        def after_iteration(self, model, epoch, evals_log):
            return False

    x, y = _data()
    dtrain = RayDMatrix(x, y)
    res = {}
    train(_PARAMS, dtrain, rounds, evals=[(dtrain, "train")],
          additional_results=res, callbacks=[Noop()] if per_round else None,
          ray_params=RayParams(num_actors=2, checkpoint_frequency=2, **ray))
    return res["obs"]["timeline"]


def _spans(timeline, name):
    return [r for r in timeline if r["kind"] == "span" and r["name"] == name]


def _below(timeline, seq):
    """Every record under span ``seq`` (children, grandchildren, ...)."""
    parent = {r["seq"]: r.get("parent") for r in timeline}
    out = []
    for r in timeline:
        p = r.get("parent")
        while p is not None and p != seq:
            p = parent.get(p)
        if p == seq:
            out.append(r)
    return out


@pytest.mark.parametrize("per_round", [False, True], ids=["fused", "per_round"])
def test_every_record_is_on_the_monotonic_clock_inside_its_parent(per_round):
    timeline = _traced(per_round=per_round)
    assert validate_trace_records(timeline, known_names=obs.TRACE_NAMES) == []
    assert all(isinstance(r["t0_s"], float) for r in timeline)
    by_seq = {r["seq"]: r for r in timeline}
    nested = [r for r in timeline if r.get("parent") is not None]
    assert len(nested) > 10
    slack = 0.05  # compile spans are dated back from jax's own stopwatch
    for r in nested:
        p = by_seq[r["parent"]]
        assert p["t0_s"] - slack <= r["t0_s"], (r, p)
        assert (r["t0_s"] + r["dur_s"]
                <= p["t0_s"] + p["dur_s"] + slack), (r, p)
    # events share the clock: a commit lies inside the save that made it
    (attempt,) = _spans(timeline, "attempt")
    for name in ("data.load", "engine.init", "dispatch", "driver.callbacks",
                 "driver.checkpoint"):
        assert any(r["parent"] == attempt["seq"]
                   for r in _spans(timeline, name)), name
    init = _spans(timeline, "engine.init")[0]
    under_init = {r["name"] for r in _below(timeline, init["seq"])}
    assert {"data.h2d", "data.sketch_bin", "compile.backend"} <= under_init
    assert all(r["attrs"]["bytes"] > 0 for r in _spans(timeline, "data.h2d"))


def test_fused_run_has_one_dispatch_per_chunk():
    timeline = _traced(rounds=4)
    dispatches = _spans(timeline, "dispatch")
    assert [d["attrs"]["rounds"] for d in dispatches] == [2, 2]
    assert all(d["attrs"]["program"] == "scan" for d in dispatches)
    seen_rounds = []
    for d in dispatches:
        kids = [r for r in timeline if r.get("parent") == d["seq"]]
        names = [r["name"] for r in kids if not r["name"].startswith("compile.")]
        assert sorted(names) == ["dispatch.enqueue", "dispatch.wait",
                                 "round", "round"]
        rounds = [r for r in kids if r["name"] == "round"]
        assert all(r["attrs"]["fused_chunk"] == 2 for r in rounds)
        seen_rounds += [r["round"] for r in rounds]
    assert seen_rounds == [0, 1, 2, 3]
    # every round record names a real dispatch as its parent
    ids = {d["seq"] for d in dispatches}
    assert all(r["parent"] in ids for r in _spans(timeline, "round"))


@pytest.mark.parametrize("per_round", [False, True], ids=["fused", "per_round"])
def test_first_dispatch_compiles_and_the_next_does_not(per_round):
    timeline = _traced(per_round=per_round)
    dispatches = _spans(timeline, "dispatch")
    assert len(dispatches) == (4 if per_round else 2)
    assert [d["attrs"]["first"] for d in dispatches] == (
        [True] + [False] * (len(dispatches) - 1))
    assert {d["attrs"]["program"] for d in dispatches} == {
        "step" if per_round else "scan"}
    first = _below(timeline, dispatches[0]["seq"])
    enqueue = next(r for r in first if r["name"] == "dispatch.enqueue")
    compiled = {r["name"] for r in first if r["parent"] == enqueue["seq"]}
    assert {"compile.trace", "compile.lower", "compile.backend"} <= compiled
    for d in dispatches[1:]:
        assert not [r for r in _below(timeline, d["seq"])
                    if r["name"].startswith("compile.")], d


@pytest.mark.parametrize("per_round", [False, True], ids=["fused", "per_round"])
def test_every_save_is_one_checkpoint_span_around_its_commit(per_round):
    timeline = _traced(rounds=5, per_round=per_round)
    saves = _spans(timeline, "driver.checkpoint")
    commits = [r for r in timeline if r["name"] == "checkpoint.commit"]
    assert len(saves) == len(commits) == 3  # rounds 2, 4 and the last
    for save, commit in zip(saves, commits):
        assert save["round"] == commit["round"]
        assert (save["t0_s"] <= commit["t0_s"]
                <= save["t0_s"] + save["dur_s"])
    hooks = [r["attrs"].get("hook") for r in _spans(timeline,
                                                    "driver.callbacks")]
    if per_round:
        assert hooks.count("after_round") == hooks.count("after_iteration") == 5
    else:
        assert hooks == [None] * 3  # one fan-out a chunk


def test_compiles_land_under_the_span_that_caused_them():
    import jax
    import jax.numpy as jnp

    obs.watch_compiles()
    obs.watch_compiles()  # once per process, however often it is asked
    t = Tracer(enabled=True, trace_dir="")
    ones = jnp.ones(7)
    before = obs.get_registry().counter("rxgb_compiles_total").value
    with use_tracer(t):
        with t.span("dispatch.enqueue"):
            jax.jit(lambda v: jnp.sin(v) * 3 + 1)(ones)
    recs = t.records()
    outer = next(r for r in recs if r["name"] == "dispatch.enqueue")
    kids = [r["name"] for r in recs if r.get("parent") == outer["seq"]]
    # one of each: a function traced inside another's trace is not a span
    assert kids == ["compile.trace", "compile.lower", "compile.backend"]
    assert obs.get_registry().counter("rxgb_compiles_total").value == before + 1
    assert validate_trace_records(recs, known_names=obs.TRACE_NAMES) == []


def _engine(params, n_evals=0, num_actors=2):
    from xgboost_ray_tpu.engine import TpuEngine
    from xgboost_ray_tpu.params import parse_params

    x, y = _data(128)
    shards = [{"data": x, "label": y}]
    evals = [(shards, "train")] + [
        ([{"data": x[:64], "label": y[:64]}], f"v{i}") for i in range(n_evals)]
    return TpuEngine(shards, parse_params(params), num_actors, evals=evals)


_SCOPE_CASES = {
    "default": (dict(_PARAMS, max_depth=3), 0, set()),
    "eval_set": (dict(_PARAMS, max_depth=2), 1, {"eval_walk"}),
    "subsample": (dict(_PARAMS, max_depth=2, subsample=0.5), 0, {"sample"}),
    "int8_gh": (dict(_PARAMS, max_depth=2, gh_precision="int8"), 0,
                {"quantize_gh"}),
    "lossguide": (dict(_PARAMS, max_depth=3, grow_policy="lossguide",
                       max_leaves=4), 0, {"select", "level", "level0"}),
}


@pytest.mark.parametrize("case", sorted(_SCOPE_CASES))
def test_lowered_round_programs_name_their_phases(case):
    """The scope vocabulary is in the HLO's metadata, so a device trace of
    any later program names its phases the same way."""
    import re

    params, n_evals, extra = _SCOPE_CASES[case]
    eng = _engine(params, n_evals)
    eng.build_programs()
    want = {"objective", "tree", "hist", "allreduce", "split", "partition",
            "margin", "metrics"} | extra
    if case != "lossguide":
        want |= {f"level{d}" for d in range(params["max_depth"])}
    assert {w.rstrip("0123456789") for w in want} <= obs.DEVICE_SCOPES
    texts = {
        "step": eng._step_fn.lower(
            *eng._step_example_args(False)).as_text(debug_info=True),
        "step_many": eng._scan_fn.lower(
            *eng._scan_example_args()).as_text(debug_info=True),
    }
    for prog, text in texts.items():
        have = set(re.findall(r"[\w]+", " ".join(
            re.findall(r'loc\("([^"]+)"', text))))
        assert want <= have, (prog, sorted(want - have))
        # phases nest: a level's histogram is tree/level{d}/hist
        if case != "lossguide":
            assert "tree/level1/hist/" in text and "tree/level1/split/" in text
        else:
            # the leaf-wise grower's levels are a loop: the root is level0,
            # every later pass ``level``, the best-first replay ``select``
            assert "tree/level0/hist/" in text and "tree/select/" in text
            assert "tree/while/body/level/while/body/hist/" in text
            assert "tree/while/body/select/" in text


def test_the_binning_program_names_sketch_and_bin():
    from xgboost_ray_tpu import progreg
    import jax

    with progreg.capture():
        progreg.clear()
        _engine(_PARAMS)
        (rec,) = [r for r in progreg.records()
                  if r.name == "engine.sketch_cuts"]
    progreg.clear()
    text = jax.jit(rec.fn).lower(*rec.abstract_args).as_text(debug_info=True)
    assert 'loc("sketch/' in text and 'loc("bin/' in text


def test_scope_times_on_a_recorded_trace(tmp_path):
    """``obs.device.scope_times`` over a hand-written XSpace: self times by
    scope path, nested operations not counted twice, other lines and host
    planes left out."""
    from xgboost_ray_tpu.obs import device

    def varint(v):
        out = bytearray()
        while True:
            out.append((v & 0x7F) | (0x80 if v > 0x7F else 0))
            v >>= 7
            if not v:
                return bytes(out)

    def field(num, val):
        if isinstance(val, int):
            return varint(num << 3) + varint(val)
        return varint(num << 3 | 2) + varint(len(val)) + bytes(val)

    def entry(key, msg):
        return field(1, key) + field(2, msg)

    def event(md, offset_ps, dur_ps):
        return field(1, md) + field(2, offset_ps) + field(3, dur_ps)

    def op(md_id, name, tf_op):
        stat = field(1, 7) + field(5, tf_op.encode())
        return field(4, entry(md_id, field(1, md_id) + field(2, name.encode())
                              + field(5, stat)))

    body = "jit(run)/while/body/closed_call/"
    ops = (op(1, "%while.1", "jit(run)/while")
           + op(2, "%fusion.2", body + "tree/level0/hist/dot_general:")
           + op(3, "%fusion.3", body + "tree/level0/partition/gather:")
           # a cond branch repeats the scopes it sits in
           + op(4, "%fusion.4", body + "tree/level1/hist/cond/branch_1_fun/"
                "tree/level1/hist/add:")
           + op(5, "%copy.5", body + "margin/jit(_where)/select_n:"))
    stat_md = field(5, entry(7, field(1, 7) + field(2, b"tf_op")))
    xla_ops = field(2, b"XLA Ops") + b"".join(field(4, e) for e in (
        event(1, 0, 1000), event(2, 100, 300), event(3, 400, 200),
        event(4, 600, 100), event(5, 1500, 500)))
    modules = field(2, b"XLA Modules") + field(4, event(1, 0, 2000))
    plane = (field(2, b"/device:TPU:0") + stat_md + ops
             + field(3, xla_ops) + field(3, modules))
    host = field(2, b"/host:CPU") + field(3, field(2, b"XLA Ops")
                                          + field(4, event(1, 0, 9000)))
    run_dir = tmp_path / "plugins" / "profile" / "run"
    run_dir.mkdir(parents=True)
    (run_dir / "host.xplane.pb").write_bytes(field(1, plane) + field(1, host))

    got = device.scope_times(str(tmp_path))
    ps = 1e-12
    assert got == pytest.approx({
        "(unscoped)": 400 * ps,            # the while, less its body
        "tree/level0/hist": 300 * ps,
        "tree/level0/partition": 200 * ps,
        "tree/level1/hist": 100 * ps,
        "margin": 500 * ps,
    })
    assert sum(got.values()) == pytest.approx(1500 * ps)  # the busy time
    assert device.scope_times(str(tmp_path / "nothing_here")) == {}

    # a mesh: a second device whose level 1 waits in the merge's psum
    merge = op(6, "%all-reduce.6", body + "tree/level1/allreduce/psum:")
    ops1 = field(2, b"XLA Ops") + field(4, event(6, 0, 700))
    plane1 = field(2, b"/device:TPU:1") + stat_md + merge + field(3, ops1)
    (run_dir / "host.xplane.pb").write_bytes(
        field(1, plane) + field(1, plane1) + field(1, host))
    by_device = device.scope_times_by_device(str(tmp_path))
    assert by_device["/device:TPU:0"] == pytest.approx(got)
    assert by_device["/device:TPU:1"] == pytest.approx(
        {"tree/level1/allreduce": 700 * ps})
    both = device.scope_times(str(tmp_path))
    assert sum(both.values()) == pytest.approx(2200 * ps)
    assert both["tree/level1/allreduce"] == pytest.approx(700 * ps)

"""Fault-injection layer + hardened-recovery tests.

Every chaos scenario here is driven by a deterministic ``FaultPlan`` (no
sleep-and-kill races): scheduled rank kills and stragglers through the
driver retry loop, corrupt/truncated checkpoints through the retention
fallback, serve overload through the shedding cap, and the launcher's
heartbeat watchdog (slow tier). The plan-driven runs must be reproducible:
the recovered model matches the uninterrupted run and
``additional_results["robustness"]`` reports the expected restart
arithmetic.
"""

import json
import os
import threading
import time

import numpy as np
import pytest

from xgboost_ray_tpu import RayDMatrix, RayParams, faults, train
from xgboost_ray_tpu import serve
from xgboost_ray_tpu.exceptions import RayActorError
from xgboost_ray_tpu.launcher import (
    load_round_checkpoint,
    save_round_checkpoint,
)

_PARAMS = {"objective": "binary:logistic", "eval_metric": ["logloss"],
           "max_depth": 3}


def _data(n=256, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, 4).astype(np.float32)
    y = (x[:, 0] + x[:, 1] > 0).astype(np.float32)
    return x, y


@pytest.fixture(autouse=True)
def _fast_restarts(monkeypatch):
    """Chaos tests assert deterministic timelines: no backoff sleeps."""
    monkeypatch.setenv("RXGB_RESTART_BACKOFF_BASE_S", "0")
    yield
    faults.clear_plan()


def _noop_plan():
    """Targets actor.train_round without ever firing — forces the per-round
    path so bit-identity never compares a fused-scan forest to a per-round
    one."""
    return faults.FaultPlan(rules=[{
        "site": "actor.train_round", "action": "raise",
        "match": {"round": -1},
    }])


# ---------------------------------------------------------------------------
# streaming-plane fault sites (stream.read_chunk / stream.h2d_upload)
# ---------------------------------------------------------------------------


def test_stream_read_chunk_fault_site():
    """A scheduled raise at the k-th chunk read surfaces from the ingest
    pipeline at exactly that chunk (the streaming plane's analog of
    actor.load_shard), and is reproducible: the counter advances per
    chunks() iteration, so the same plan fails at the same chunk."""
    from xgboost_ray_tpu.engine import TpuEngine
    from xgboost_ray_tpu.params import parse_params
    from xgboost_ray_tpu.stream.reader import array_shard_stream

    x, y = _data(n=1200)
    p = parse_params(_PARAMS)
    plan = faults.FaultPlan(rules=[{
        "site": "stream.read_chunk", "action": "raise", "at": 3,
        "message": "chaos: chunk source died",
    }])
    with faults.active_plan(plan):
        with pytest.raises(RuntimeError, match="chunk source died"):
            TpuEngine([array_shard_stream(x, label=y, chunk_rows=300)], p,
                      num_actors=2)
    # match-filtered by chunk index: only the matching chunk advances it
    plan2 = faults.FaultPlan(rules=[{
        "site": "stream.read_chunk", "action": "raise",
        "match": {"chunk": 2}, "message": "chaos: third chunk",
    }])
    with faults.active_plan(plan2):
        with pytest.raises(RuntimeError, match="third chunk"):
            TpuEngine([array_shard_stream(x, label=y, chunk_rows=300)], p,
                      num_actors=2)


def test_stream_h2d_upload_fault_site():
    """A scheduled raise at the k-th H2D submit surfaces on the TRAINING
    thread (where drain() would surface a real transfer failure), and a
    delay models a stalled upload pipe without wedging the worker."""
    from xgboost_ray_tpu.engine import TpuEngine
    from xgboost_ray_tpu.params import parse_params
    from xgboost_ray_tpu.stream.reader import array_shard_stream

    x, y = _data(n=1200)
    p = parse_params(_PARAMS)
    plan = faults.FaultPlan(rules=[{
        "site": "stream.h2d_upload", "action": "raise",
        "message": "chaos: upload failed",
    }])
    with faults.active_plan(plan):
        with pytest.raises(RuntimeError, match="upload failed"):
            TpuEngine([array_shard_stream(x, label=y, chunk_rows=300)], p,
                      num_actors=2)
    # a delayed upload only slows ingest; training still completes and the
    # injected fault lands on the timeline
    from xgboost_ray_tpu import obs

    tracer = obs.Tracer(enabled=True)
    plan2 = faults.FaultPlan(rules=[{
        "site": "stream.h2d_upload", "action": "delay", "delay_s": 0.05,
    }])
    with obs.use_tracer(tracer):
        with faults.active_plan(plan2):
            eng = TpuEngine(
                [array_shard_stream(x, label=y, chunk_rows=300)], p,
                num_actors=2,
            )
            eng.step(0)
    injected = [r for r in tracer.records() if r["name"] == "fault.injected"]
    assert any(r["attrs"]["site"] == "stream.h2d_upload" for r in injected)


def test_streamed_ingest_fault_is_deterministic():
    """Chaos-vs-chaos over the streaming plane: two runs of the same
    read-chunk straggler plan train bitwise-identical forests (the delay
    perturbs wall time only, never data order)."""
    from xgboost_ray_tpu.engine import TpuEngine
    from xgboost_ray_tpu.params import parse_params
    from xgboost_ray_tpu.stream.reader import array_shard_stream

    x, y = _data(n=1200)
    p = parse_params(_PARAMS)
    outs = []
    for _ in range(2):
        plan = faults.FaultPlan(rules=[{
            "site": "stream.read_chunk", "action": "delay",
            "delay_s": 0.05, "at": 2,
        }])
        with faults.active_plan(plan):
            eng = TpuEngine(
                [array_shard_stream(x, label=y, chunk_rows=300)], p,
                num_actors=2,
            )
            for i in range(3):
                eng.step(i)
        outs.append([np.asarray(f) for f in eng.get_booster().forest])
    for f1, f2 in zip(*outs):
        assert np.array_equal(f1, f2)


# ---------------------------------------------------------------------------
# FaultPlan unit semantics (pure, no training)
# ---------------------------------------------------------------------------


def test_rule_counting_at_times_and_match():
    plan = faults.FaultPlan(rules=[
        {"site": "serve.predict", "action": "raise", "at": 2, "times": 2,
         "match": {"kind": "value"}},
    ])
    # occurrence 1 passes; 2 and 3 fire; 4 passes again; non-matching ctx
    # never advances the counter
    plan.fire("serve.predict", kind="margin")
    plan.fire("serve.predict", kind="value")
    for _ in range(2):
        with pytest.raises(RuntimeError, match="injected fault"):
            plan.fire("serve.predict", kind="value")
    plan.fire("serve.predict", kind="value")
    plan.reset()
    plan.fire("serve.predict", kind="value")  # counter rewound


def test_times_zero_fires_forever():
    plan = faults.FaultPlan(rules=[
        {"site": "registry.swap", "action": "raise", "at": 1, "times": 0},
    ])
    for _ in range(3):
        with pytest.raises(RuntimeError):
            plan.fire("registry.swap")


def test_unknown_site_and_action_rejected():
    with pytest.raises(ValueError, match="unknown fault site"):
        faults.FaultRule(site="nope", action="raise")
    with pytest.raises(ValueError, match="unknown fault action"):
        faults.FaultRule(site="serve.predict", action="explode")


def test_plan_json_roundtrip_and_env_install(monkeypatch):
    plan = faults.FaultPlan(rules=[
        {"site": "actor.load_shard", "action": "raise", "ranks": [1],
         "match": {"rank": 1}},
    ], seed=5)
    clone = faults.FaultPlan.from_json(plan.to_json())
    assert clone.seed == 5 and clone.rules[0].ranks == [1]
    monkeypatch.setenv("RXGB_FAULT_PLAN", plan.to_json())
    with pytest.raises(RayActorError) as ei:
        faults.fire("actor.load_shard", rank=1)
    assert ei.value.ranks == [1]
    faults.fire("actor.load_shard", rank=0)  # non-matching rank passes


def test_corrupt_is_seed_deterministic(tmp_path):
    payload = bytes(range(256)) * 8
    damaged = []
    for run in range(2):
        p = tmp_path / f"f{run}.bin"
        p.write_bytes(payload)
        plan = faults.FaultPlan(rules=[
            {"site": "checkpoint.save", "action": "corrupt", "nbytes": 8},
        ], seed=42)
        plan.fire_file("checkpoint.save", str(p))
        damaged.append(p.read_bytes())
    assert damaged[0] == damaged[1] != payload


def test_truncate_keeps_prefix(tmp_path):
    p = tmp_path / "t.bin"
    p.write_bytes(b"x" * 100)
    plan = faults.FaultPlan(rules=[
        {"site": "checkpoint.save", "action": "truncate", "nbytes": 10},
    ])
    plan.fire_file("checkpoint.save", str(p))
    assert p.read_bytes() == b"x" * 10


def test_restart_backoff_schedule(monkeypatch):
    from xgboost_ray_tpu.util import restart_backoff_s

    monkeypatch.setenv("RXGB_RESTART_BACKOFF_BASE_S", "0.5")
    monkeypatch.setenv("RXGB_RESTART_BACKOFF_MAX_S", "4")
    monkeypatch.setenv("RXGB_RESTART_BACKOFF_JITTER", "0")
    assert [restart_backoff_s(i) for i in range(5)] == [
        0.5, 1.0, 2.0, 4.0, 4.0]
    monkeypatch.setenv("RXGB_RESTART_BACKOFF_JITTER", "0.5")
    d = restart_backoff_s(0)
    assert 0.5 <= d <= 0.75
    monkeypatch.setenv("RXGB_RESTART_BACKOFF_BASE_S", "0")
    assert restart_backoff_s(3) == 0.0


# ---------------------------------------------------------------------------
# Driver-level chaos: kills + stragglers through the retry loop
# ---------------------------------------------------------------------------


def test_kill_and_straggler_recovered_model_matches():
    """The acceptance scenario: a FaultPlan injecting a rank kill plus a
    straggler delay is fully deterministic — the recovered model matches
    the uninterrupted run to 1e-5 (the restart recomputes resume margins
    from the checkpoint forest, a different f32 summation order than the
    uninterrupted run's incremental accumulation, so last-ulp wiggle is
    expected; structural divergence is not) and the robustness block
    reports the exact restart arithmetic."""
    x, y = _data()
    with faults.active_plan(_noop_plan()):
        ref = train(_PARAMS, RayDMatrix(x, y), 10,
                    ray_params=RayParams(num_actors=2,
                                         checkpoint_frequency=2))
    ref_margin = ref.predict(x, output_margin=True)

    plan = faults.FaultPlan(rules=[
        {"site": "actor.train_round", "action": "raise", "ranks": [1],
         "match": {"round": 5}},
        {"site": "actor.train_round", "action": "delay", "delay_s": 0.05,
         "match": {"round": 7}},
    ])
    res = {}
    with faults.active_plan(plan):
        bst = train(_PARAMS, RayDMatrix(x, y), 10,
                    additional_results=res,
                    ray_params=RayParams(num_actors=2, max_actor_restarts=1,
                                         checkpoint_frequency=2))
    assert bst.num_boosted_rounds() == 10
    np.testing.assert_allclose(
        bst.predict(x, output_margin=True), ref_margin, atol=1e-5
    )
    rob = res["robustness"]
    # kill at round 5 with checkpoints every 2: ckpt covers rounds 0..3,
    # rounds 4 had completed -> exactly 1 round is replayed by 1 restart
    assert rob["restarts"] == 1
    assert rob["rounds_replayed"] == 1
    assert rob["elastic_restarts"] == 0
    assert rob["time_to_recover_s"] > 0
    assert rob["backoff_s"] == 0


def test_clean_run_reports_zero_robustness():
    x, y = _data(64)
    res = {}
    train(_PARAMS, RayDMatrix(x, y), 3, additional_results=res,
          ray_params=RayParams(num_actors=2))
    assert res["robustness"] == {
        "restarts": 0, "elastic_restarts": 0, "rounds_replayed": 0,
        "time_to_recover_s": 0.0, "backoff_s": 0.0,
        "shrinks": 0, "grows": 0, "orphaned_rows": 0, "recompile_s": 0.0,
        "domains_lost": 0, "deaths_coalesced": 0,
    }


def test_multi_kill_same_rank_across_rounds_elastic(monkeypatch):
    """Kill the SAME rank twice at different rounds with elastic training
    on and immediate reintegration (check + grace at zero): each kill is
    absorbed IN-FLIGHT — the staged replacement is promoted before the next
    round starts, so no attempt restarts, nothing is replayed, and the
    model is bitwise identical to an uninterrupted run."""
    monkeypatch.setenv("RXGB_ELASTIC_RESTART_RESOURCE_CHECK_S", "0")
    monkeypatch.setenv("RXGB_ELASTIC_RESTART_GRACE_PERIOD_S", "0")
    x, y = _data()
    with faults.active_plan(_noop_plan()):
        ref = train(_PARAMS, RayDMatrix(x, y), 12,
                    ray_params=RayParams(num_actors=2,
                                         checkpoint_frequency=2))
    plan = faults.FaultPlan(rules=[
        {"site": "actor.train_round", "action": "raise", "ranks": [0],
         "match": {"round": 3}},
        {"site": "actor.train_round", "action": "raise", "ranks": [0],
         "match": {"round": 7}},
    ])
    res = {}
    with faults.active_plan(plan):
        bst = train(_PARAMS, RayDMatrix(x, y), 12,
                    additional_results=res,
                    ray_params=RayParams(num_actors=2, elastic_training=True,
                                         max_failed_actors=1,
                                         max_actor_restarts=4,
                                         checkpoint_frequency=2))
    assert bst.num_boosted_rounds() == 12
    rob = res["robustness"]
    assert rob["restarts"] == 0  # absorbed in-flight, no attempt restart
    assert rob["elastic_restarts"] == 0
    assert rob["rounds_replayed"] == 0
    assert rob["grows"] == 2  # one immediate reintegration per kill
    assert rob["shrinks"] == 0
    assert rob["elastic_reschedules"] >= 2
    assert np.array_equal(
        bst.predict(x, output_margin=True),
        ref.predict(x, output_margin=True),
    )


def test_load_shard_fault_recovers():
    x, y = _data(64)
    plan = faults.FaultPlan(rules=[
        {"site": "actor.load_shard", "action": "raise", "ranks": [1],
         "match": {"rank": 1}},
    ])
    with faults.active_plan(plan):
        bst = train(_PARAMS, RayDMatrix(x, y), 3,
                    ray_params=RayParams(num_actors=2, max_actor_restarts=1))
    assert bst.num_boosted_rounds() == 3


# ---------------------------------------------------------------------------
# Checkpoint integrity + retention fallback
# ---------------------------------------------------------------------------


def _flip_bytes(path, offset=50, n=20):
    with open(path, "rb+") as f:
        f.seek(offset)
        raw = f.read(n)
        f.seek(offset)
        f.write(bytes(b ^ 0xFF for b in raw))


def test_save_writes_sha_sidecar_and_retention(tmp_path):
    x, y = _data(64)
    bst = train(_PARAMS, RayDMatrix(x, y), 4,
                ray_params=RayParams(num_actors=2))
    ckpt = str(tmp_path / "ckpt.json")
    for r in range(4):
        save_round_checkpoint(bst.slice_rounds(0, r + 1), ckpt, r,
                              keep_last=2)
    assert os.path.exists(ckpt + ".sha256")
    # keep_last=2: only the two newest history copies survive pruning
    hist = sorted(p for p in os.listdir(tmp_path)
                  if p.startswith("ckpt.json.r0"))
    assert hist == ["ckpt.json.r000002", "ckpt.json.r000002.sha256",
                    "ckpt.json.r000003", "ckpt.json.r000003.sha256"]
    loaded, rounds = load_round_checkpoint(ckpt)
    assert rounds == 4


def test_corrupt_newest_checkpoint_falls_back_and_resumes(tmp_path):
    """Satellite acceptance: a corrupt/truncated newest checkpoint falls
    back to the previous GOOD retained checkpoint, and resuming from it
    reproduces the uninterrupted model — instead of json.load killing the
    whole retry loop."""
    x, y = _data()
    ref = train(_PARAMS, RayDMatrix(x, y), 6,
                ray_params=RayParams(num_actors=2))
    ckpt = str(tmp_path / "ckpt.json")
    save_round_checkpoint(ref.slice_rounds(0, 4), ckpt, 3)
    save_round_checkpoint(ref.slice_rounds(0, 5), ckpt, 4)
    # a torn newest save: both the live file and its retained copy are bad
    _flip_bytes(ckpt)
    _flip_bytes(ckpt + ".r000004")
    fb, fb_rounds = load_round_checkpoint(ckpt)
    assert fb is not None and fb_rounds == 4  # fell back to .r000003
    resumed = train(_PARAMS, RayDMatrix(x, y), 6 - fb_rounds, xgb_model=fb,
                    ray_params=RayParams(num_actors=2))
    np.testing.assert_allclose(
        resumed.predict(x, output_margin=True),
        ref.predict(x, output_margin=True),
        atol=1e-4,
    )


def test_truncated_checkpoint_via_fault_plan_falls_back(tmp_path):
    x, y = _data(64)
    bst = train(_PARAMS, RayDMatrix(x, y), 3,
                ray_params=RayParams(num_actors=2))
    ckpt = str(tmp_path / "ckpt.json")
    plan = faults.FaultPlan(rules=[
        {"site": "checkpoint.save", "action": "truncate", "at": 2,
         "nbytes": 40},
    ])
    with faults.active_plan(plan):
        save_round_checkpoint(bst.slice_rounds(0, 2), ckpt, 1)
        save_round_checkpoint(bst, ckpt, 2)  # committed file truncated
    fb, fb_rounds = load_round_checkpoint(ckpt)
    # live file is torn; the newest retained copy (made pre-damage) is good
    assert fb is not None and fb_rounds == 3


def test_torn_sidecar_still_resumes(tmp_path):
    """A kill between the model rename and the sidecar rename leaves a VALID
    newest checkpoint with a stale sidecar: when nothing passes integrity,
    the loader must accept the parseable mismatched file rather than
    abandoning the run to round 0."""
    x, y = _data(64)
    bst = train(_PARAMS, RayDMatrix(x, y), 3,
                ray_params=RayParams(num_actors=2))
    ckpt = str(tmp_path / "ckpt.json")
    save_round_checkpoint(bst, ckpt, 2, keep_last=0)  # no retained copies
    with open(ckpt + ".sha256", "w") as f:
        f.write("0" * 64)  # stale/foreign digest, model itself is fine
    fb, fb_rounds = load_round_checkpoint(ckpt)
    assert fb is not None and fb_rounds == 3


def test_all_candidates_corrupt_restarts_from_scratch(tmp_path):
    x, y = _data(64)
    bst = train(_PARAMS, RayDMatrix(x, y), 2,
                ray_params=RayParams(num_actors=2))
    ckpt = str(tmp_path / "ckpt.json")
    save_round_checkpoint(bst, ckpt, 1, keep_last=1)
    _flip_bytes(ckpt)
    _flip_bytes(ckpt + ".r000001")
    assert load_round_checkpoint(ckpt) == (None, 0)


def test_async_checkpoint_writer_commits_in_order(tmp_path):
    """Satellite acceptance: the background writer commits the same files
    (newest + sha sidecars + retained history) as the synchronous path,
    strictly in submit order, and leaves no torn temp file behind."""
    from xgboost_ray_tpu.launcher import AsyncCheckpointWriter

    x, y = _data(64)
    bst = train(_PARAMS, RayDMatrix(x, y), 4,
                ray_params=RayParams(num_actors=2))
    ckpt = str(tmp_path / "ckpt.json")
    with AsyncCheckpointWriter() as w:
        for r in range(4):
            w.submit(bst.slice_rounds(0, r + 1), ckpt, r, keep_last=2)
    loaded, rounds = load_round_checkpoint(ckpt)
    assert loaded is not None and rounds == 4
    hist = sorted(p for p in os.listdir(tmp_path)
                  if p.startswith("ckpt.json.r0"))
    assert hist == ["ckpt.json.r000002", "ckpt.json.r000002.sha256",
                    "ckpt.json.r000003", "ckpt.json.r000003.sha256"]
    assert not os.path.exists(ckpt + ".tmp")


def test_async_checkpoint_writer_surfaces_write_errors(tmp_path):
    """A failed background write must re-raise at the next boundary (the
    following submit/wait), not vanish — a silently unwritten checkpoint
    is replay debt discovered only at the next crash."""
    from xgboost_ray_tpu.launcher import AsyncCheckpointWriter

    x, y = _data(64)
    bst = train(_PARAMS, RayDMatrix(x, y), 2,
                ray_params=RayParams(num_actors=2))
    w = AsyncCheckpointWriter()
    w.submit(bst, str(tmp_path / "no_such_dir" / "ckpt.json"), 0)
    with pytest.raises(OSError):
        w.wait()
    # the writer is reusable after the failure surfaced
    ok_path = str(tmp_path / "ckpt.json")
    w.submit(bst, ok_path, 1)
    w.wait()
    assert load_round_checkpoint(ok_path)[1] == 2


def test_async_checkpoint_writer_bounded_exit_join(tmp_path, monkeypatch, caplog):
    """Satellite acceptance: a hung commit can no longer wedge driver exit.
    Under a forced-slow ``checkpoint.save`` fault (injected delay AFTER the
    commit), the context-manager exit joins for at most
    ``RXGB_CKPT_EXIT_JOIN_S`` seconds, logs loudly, and abandons the daemon
    writer instead of blocking forever."""
    import logging
    import time as _time

    from xgboost_ray_tpu.launcher import AsyncCheckpointWriter

    x, y = _data(64)
    bst = train(_PARAMS, RayDMatrix(x, y), 2,
                ray_params=RayParams(num_actors=2))
    ckpt = str(tmp_path / "ckpt.json")
    monkeypatch.setenv("RXGB_CKPT_EXIT_JOIN_S", "0.2")
    plan = faults.FaultPlan(rules=[
        {"site": "checkpoint.save", "action": "delay", "delay_s": 0.9},
    ])
    w = AsyncCheckpointWriter()
    with faults.active_plan(plan):
        t0 = _time.monotonic()
        with caplog.at_level(logging.ERROR, logger="xgboost_ray_tpu.launcher"):
            with w:
                w.submit(bst, ckpt, 1)
        exit_s = _time.monotonic() - t0
    assert exit_s < 0.8, f"exit blocked {exit_s:.2f}s despite the bounded join"
    assert any("NOT confirmed" in r.message for r in caplog.records), (
        "abandoning the join must be LOUD"
    )
    # the injected delay fires AFTER the atomic rename: once the abandoned
    # writer finishes, the checkpoint is intact on disk and a later
    # unbounded wait() can still collect the thread
    assert w.wait() is True
    assert load_round_checkpoint(ckpt)[1] == 2


def test_checkpoint_load_fault_site(tmp_path):
    plan = faults.FaultPlan(rules=[
        {"site": "checkpoint.load", "action": "raise", "exc": "OSError"},
    ])
    with faults.active_plan(plan):
        with pytest.raises(OSError):
            load_round_checkpoint(str(tmp_path / "ckpt.json"))


# ---------------------------------------------------------------------------
# Serve: shedding (429), degradation breaker, prompt shutdown
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def serve_model():
    x, y = _data(64, seed=3)
    bst = train({"objective": "binary:logistic", "max_depth": 2},
                RayDMatrix(x, y), 2, ray_params=RayParams(num_actors=1))
    return bst, x


def _wait_for(cond, timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.002)
    return False


def test_serve_429_shedding_under_plugged_predictor(serve_model):
    """Satellite acceptance: with the predictor plugged (deterministic
    delay on serve.predict), the max_queue_rows cap rejects the overflow
    request with OverloadedError (HTTP 429) and counts the shed."""
    bst, x = serve_model
    metrics = serve.ServeMetrics()
    reg = serve.ModelRegistry(warm_max_batch=8)
    reg.load(bst)
    b = serve.MicroBatcher(reg, max_batch=8, max_delay_ms=1.0,
                           metrics=metrics, max_queue_rows=4)
    plan = faults.FaultPlan(rules=[
        {"site": "serve.predict", "action": "delay", "delay_s": 0.4,
         "times": 0},
    ])
    oks = []
    try:
        with faults.active_plan(plan):
            t1 = threading.Thread(
                target=lambda: oks.append(b.submit(x[:4])), daemon=True)
            t1.start()
            assert _wait_for(lambda: b.executing_batches() == 1)
            t2 = threading.Thread(
                target=lambda: oks.append(b.submit(x[:4])), daemon=True)
            t2.start()
            assert _wait_for(lambda: b.queued_rows() == 4)
            with pytest.raises(serve.OverloadedError):
                b.submit(x[:1])
            assert metrics.shed == 1
            assert metrics.snapshot()["shed"] == 1
            t1.join(5)
            t2.join(5)
        assert len(oks) == 2  # the queued (non-shed) requests all served
    finally:
        b.shutdown()


def test_serve_shutdown_fails_queued_promptly(serve_model):
    """Regression for the shutdown race: a request queued behind a busy
    flusher must be failed promptly by shutdown() (ShuttingDownError), not
    sit out its full client timeout."""
    bst, x = serve_model
    reg = serve.ModelRegistry(warm_max_batch=8)
    reg.load(bst)
    b = serve.MicroBatcher(reg, max_batch=4, max_delay_ms=1.0)
    plan = faults.FaultPlan(rules=[
        {"site": "serve.predict", "action": "delay", "delay_s": 0.5,
         "times": 0},
    ])
    outcome = []
    with faults.active_plan(plan):
        t1 = threading.Thread(target=lambda: b.submit(x[:2]), daemon=True)
        t1.start()
        assert _wait_for(lambda: b.executing_batches() == 1)

        def queued_submit():
            t0 = time.monotonic()
            try:
                b.submit(x[:2], timeout=10.0)
                outcome.append(("ok", time.monotonic() - t0))
            except BaseException as exc:  # noqa: BLE001
                outcome.append((exc, time.monotonic() - t0))

        t2 = threading.Thread(target=queued_submit, daemon=True)
        t2.start()
        assert _wait_for(lambda: b.queue_depth() == 1)
        b.shutdown()
        t2.join(5)
        t1.join(5)
    assert outcome, "queued submit never returned"
    exc, waited = outcome[0]
    assert isinstance(exc, serve.ShuttingDownError), exc
    assert waited < 3.0, f"queued request waited {waited:.1f}s of a 10s timeout"
    with pytest.raises(serve.ShuttingDownError):
        b.submit(x[:1])


def test_serve_breaker_degraded_and_http_status_mapping(serve_model):
    """Consecutive predictor failures flip /healthz to degraded (503) and
    show in /metrics; a success closes the breaker again. Handler errors map
    to distinct statuses: 500 internal, 429 shed, 400 bad payload."""
    import urllib.error
    import urllib.request

    bst, x = serve_model

    def _call(url, path, body=None):
        req = urllib.request.Request(
            url + path,
            json.dumps(body).encode() if body is not None else None,
            {"Content-Type": "application/json"},
        )
        try:
            with urllib.request.urlopen(req, timeout=10) as r:
                return r.status, json.loads(r.read())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read())

    h = serve.create_server(bst, max_batch=8, breaker_threshold=2)
    try:
        plan = faults.FaultPlan(rules=[
            {"site": "serve.predict", "action": "raise", "times": 0,
             "message": "plugged predictor"},
        ])
        with faults.active_plan(plan):
            for _ in range(2):
                status, doc = _call(h.url, "/predict",
                                    {"data": x[:2].tolist()})
                assert status == 500, doc
            status, doc = _call(h.url, "/healthz")
            assert (status, doc["status"]) == (503, "degraded")
            assert doc["consecutive_predictor_failures"] == 2
            status, m = _call(h.url, "/metrics")
            assert m["breaker_open"] == 1
        # plan cleared: one success closes the breaker
        status, doc = _call(h.url, "/predict", {"data": x[:2].tolist()})
        assert status == 200
        status, doc = _call(h.url, "/healthz")
        assert (status, doc["status"]) == (200, "ok")
        status, m = _call(h.url, "/metrics")
        assert m["breaker_open"] == 0
        # malformed payloads stay 400, never 503
        status, doc = _call(h.url, "/predict", {"data": x[:2].tolist(),
                                                "kind": "nope"})
        assert status == 400
        status, doc = _call(h.url, "/predict", {})
        assert status == 400
        # draining: new predicts are refused with 503 before the drain
        h._draining = True
        status, doc = _call(h.url, "/predict", {"data": x[:2].tolist()})
        assert status == 503
        status, doc = _call(h.url, "/healthz")
        assert (status, doc["status"]) == (503, "draining")
        h._draining = False
    finally:
        h.shutdown()


# ---------------------------------------------------------------------------
# Launcher: heartbeat watchdog + result-contract enforcement (real
# processes -> slow tier, see tests/slow_tests.txt)
# ---------------------------------------------------------------------------


_LAUNCH_ENV = {
    "JAX_PLATFORMS": "cpu",
    "RXGB_RESTART_BACKOFF_BASE_S": "0",
}


def test_launcher_hang_watchdog_flags_and_restarts():
    """A worker hung via the fault plan never trips the coordination service
    (nobody died) — the heartbeat watchdog must flag the stalled world as
    ``hung`` and restart it long before the global timeout."""
    from xgboost_ray_tpu.launcher import launch_distributed

    from _launcher_ft_fn import quick_worker

    plan = faults.FaultPlan(rules=[
        {"site": "launcher.worker", "action": "hang", "delay_s": 120,
         "match": {"process_id": 1, "attempt": 0}},
    ])
    t0 = time.monotonic()
    res = launch_distributed(
        quick_worker, 2,
        # budget 2: a loaded machine can stretch a healthy attempt's
        # jax-import gap past the hang timeout and burn a spurious restart
        max_restarts=2,
        timeout_s=300.0,
        # > worst-case jax import + distributed-init gap between heartbeats
        hang_timeout_s=15.0,
        env=dict(_LAUNCH_ENV, RXGB_FAULT_PLAN=plan.to_json()),
    )
    elapsed = time.monotonic() - t0
    assert res.restarts >= 1
    assert sorted(res.results) == [0, 1]
    hung = [f for f in res.failures if f.reason == "hung"]
    assert any(f.process_id == 1 and f.attempt == 0 for f in hung), \
        res.failures
    assert all(f.reason in ("hung", "torn_down", "crashed")
               for f in res.failures)
    # the watchdog, not the 300s global timeout, did the flagging
    assert elapsed < 200, f"watchdog never fired ({elapsed:.0f}s)"


def test_launcher_missing_result_file_raises():
    """Satellite acceptance: a zero-exit worker whose result file is missing
    raises LaunchFailedError with the worker's log tail instead of silently
    returning a partial world of Nones."""
    from xgboost_ray_tpu.launcher import LaunchFailedError, launch_distributed

    from _launcher_ft_fn import exit_zero_without_result

    with pytest.raises(LaunchFailedError, match="exited 0"):
        launch_distributed(
            exit_zero_without_result, 1,
            max_restarts=0,
            timeout_s=120.0,
            env=dict(_LAUNCH_ENV),
        )


def test_registry_swap_fault_site(serve_model):
    bst, _ = serve_model
    reg = serve.ModelRegistry(warm_max_batch=8)
    plan = faults.FaultPlan(rules=[
        {"site": "registry.swap", "action": "raise", "exc": "ValueError"},
    ])
    with faults.active_plan(plan):
        with pytest.raises(ValueError):
            reg.load(bst)
        assert reg.load(bst) == 1  # rule exhausted; swap proceeds


# ---------------------------------------------------------------------------
# correlated failure: the domain_kill action
# ---------------------------------------------------------------------------


@pytest.fixture()
def _clear_resolver():
    yield
    faults.set_domain_resolver(None)


def test_domain_kill_requires_domain():
    with pytest.raises(ValueError, match="domain"):
        faults.FaultRule(site="actor.train_round", action="domain_kill")


def test_domain_kill_json_roundtrip():
    plan = faults.FaultPlan(rules=[{
        "site": "actor.train_round", "action": "domain_kill", "domain": 1,
        "ranks": [2], "match": {"round": 3}}])
    clone = faults.FaultPlan.from_json(plan.to_json())
    rule = clone.rules[0]
    assert rule.action == "domain_kill" and rule.domain == 1
    assert rule.ranks == [2] and rule.match == {"round": 3}


def test_domain_kill_resolver_blames_whole_domain(_clear_resolver):
    """With the driver's resolver installed, one rule occurrence raises a
    single RayActorError blaming EVERY alive rank of the domain — that is
    what lets the recovery coalesce a host loss into one shrink."""
    faults.set_domain_resolver(lambda d: (3, 2) if d == 1 else ())
    plan = faults.FaultPlan(rules=[{
        "site": "actor.train_round", "action": "domain_kill", "domain": 1,
        "ranks": [2]}])
    with pytest.raises(RayActorError) as ei:
        plan.fire("actor.train_round", rank=2, round=0)
    assert ei.value.ranks == [2, 3]  # sorted, both ranks in ONE exception


def test_domain_kill_dead_domain_is_noop(_clear_resolver):
    """A domain whose ranks are all gone resolves to no targets: the rule
    passes instead of raising (nothing left to kill)."""
    faults.set_domain_resolver(lambda d: ())
    plan = faults.FaultPlan(rules=[{
        "site": "actor.train_round", "action": "domain_kill", "domain": 0,
        "times": 0}])
    plan.fire("actor.train_round", rank=0, round=0)  # does not raise


def test_domain_kill_fallback_ranks_without_resolver(_clear_resolver):
    """Outside a training run (no resolver) the rule's explicit `ranks`
    list is the target set; with neither, the misconfiguration is loud."""
    faults.set_domain_resolver(None)
    plan = faults.FaultPlan(rules=[{
        "site": "actor.train_round", "action": "domain_kill", "domain": 5,
        "ranks": [4, 1]}])
    with pytest.raises(RayActorError) as ei:
        plan.fire("actor.train_round", rank=1)
    assert ei.value.ranks == [1, 4]

    bare = faults.FaultPlan(rules=[{
        "site": "actor.train_round", "action": "domain_kill", "domain": 5}])
    with pytest.raises(RuntimeError, match="no domain resolver"):
        bare.fire("actor.train_round", rank=0)

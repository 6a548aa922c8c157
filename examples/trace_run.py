"""Traced-training smoke: the obs plane's queryable run timeline.

Trains 5 rounds with tracing on (the default) plus per-rank JSONL
streaming (``RXGB_TRACE_DIR``) and a profiler trace of the round loop
(``RXGB_PROFILE_DIR``), then:

* validates BOTH the in-memory timeline (``additional_results["obs"]``)
  and the streamed JSONL file against the shared trace schema
  (``xgboost_ray_tpu.validate_trace_records`` — the same checker the
  tests use, so the CI example and the suite cannot drift apart),
* prints where one ``train()`` call spent its host time, by span name
  (``data.load`` / ``engine.init`` / ``dispatch.enqueue`` / ``dispatch.wait``
  / ``compile.*`` / ``driver.checkpoint`` / ``driver.callbacks``), and
* prints the device seconds per named scope (``tree/level3/hist`` ...) that
  ``python -m xgboost_ray_tpu.obs.device <dir>`` reads from the profiler
  trace. A CPU trace has no device plane, so the table is empty here; on a
  TPU it is the in-program phase breakdown.

Run directly: python examples/trace_run.py
"""

import json
import os
import tempfile

import numpy as np

from xgboost_ray_tpu import RayDMatrix, RayParams, train, validate_trace_records
from xgboost_ray_tpu.obs import TRACE_NAMES, device


def main():
    rounds = 5
    rng = np.random.RandomState(0)
    x = rng.randn(4096, 12).astype(np.float32)
    y = (x[:, 0] + 0.5 * x[:, 1] > 0).astype(np.float32)

    with tempfile.TemporaryDirectory() as trace_dir, \
            tempfile.TemporaryDirectory() as profile_dir:
        os.environ["RXGB_TRACE_DIR"] = trace_dir
        os.environ["RXGB_PROFILE_DIR"] = profile_dir
        try:
            res = {}
            bst = train(
                {"objective": "binary:logistic", "eval_metric": ["logloss"],
                 "max_depth": 4},
                RayDMatrix(x, y),
                rounds,
                additional_results=res,
                ray_params=RayParams(num_actors=2, checkpoint_frequency=2),
            )
        finally:
            os.environ.pop("RXGB_TRACE_DIR", None)
            os.environ.pop("RXGB_PROFILE_DIR", None)

        assert bst.num_boosted_rounds() == rounds
        obs = res["obs"]

        # schema validation: in-memory timeline AND the streamed JSONL
        problems = validate_trace_records(obs["timeline"],
                                          known_names=TRACE_NAMES)
        assert not problems, problems
        stream_path = os.path.join(trace_dir, "trace-rank0.jsonl")
        with open(stream_path) as f:
            streamed = [json.loads(line) for line in f]
        problems = validate_trace_records(streamed)
        assert not problems, problems
        print(f"trace schema OK: {len(obs['timeline'])} buffered records, "
              f"{len(streamed)} streamed lines, "
              f"{obs['dropped_spans']} dropped")
        # what `python -m xgboost_ray_tpu.obs.device <dir>` prints
        scopes = device.scope_times(profile_dir)

    # the queryable views: one span per round, lifecycle events
    assert [r["round"] for r in obs["rounds"]] == list(range(rounds))
    print("\nround  dur_s     world  rows")
    for r in obs["rounds"]:
        print(f"{r['round']:>5}  {r['dur_s']:<8.4f}  {r['world']:>5}  "
              f"{r['rows']}")
    events = [(e["name"], e.get("round")) for e in obs["events"]]
    print(f"events: {events}")
    assert any(name == "checkpoint.commit" for name, _ in events)

    # one train() call by layer: seconds by span name (a parent holds its
    # children's seconds too: dispatch > dispatch.enqueue > compile.*)
    by_name = {}
    for rec in obs["timeline"]:
        if rec["kind"] == "span":
            n, sec = by_name.get(rec["name"], (0, 0.0))
            by_name[rec["name"]] = (n + 1, sec + rec["dur_s"])
    for name in ("attempt", "data.load", "engine.init", "data.h2d",
                 "data.sketch_bin", "dispatch", "dispatch.enqueue",
                 "dispatch.wait", "round", "compile.backend",
                 "driver.checkpoint", "driver.callbacks"):
        assert name in by_name, name
    print(f"\n{'span':<20} {'count':>6} {'seconds':>10}")
    for name, (n, sec) in sorted(by_name.items(), key=lambda kv: -kv[1][1]):
        print(f"{name:<20} {n:>6} {sec:>10.4f}")
    first, second = [r for r in obs["timeline"] if r["name"] == "dispatch"][:2]
    assert first["attrs"]["first"] and not second["attrs"]["first"]

    print(f"\ndevice seconds by named scope ({len(scopes)} scope paths):")
    for name, sec in sorted(scopes.items(), key=lambda kv: -kv[1]):
        print(f"{sec:12.6f}  {name}")
    if not scopes:
        print("  (none: this backend's trace has no device plane)")
    print("\ntraced run OK")


if __name__ == "__main__":
    main()

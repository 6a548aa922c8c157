"""The per-layer metrics that read the program's own spans, run by hand like
``test_rehearsal.py`` (not part of tier-1):

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_span_metrics.py -q

A traced CPU rehearsal of one fused and of the per-round cell has to report
all ten, and the three ``driver.*`` together have to be what the harness
reads from outside as the window less its dispatches.
"""

import json
import os
import subprocess
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import run as bench_run  # noqa: E402
import spans  # noqa: E402

SPAN_METRICS = [
    "driver.checkpoint_ms", "driver.callbacks_ms", "driver.loop_self_ms",
    "round.enqueue_ms", "round.warm_dispatch_pct", "round.compile_s",
    "round.cache_load_s", "ingest.load_s", "ingest.h2d_s",
    "ingest.sketch_bin_s",
]


def _span(name, t0, dur, seq, parent=None, **attrs):
    return {"kind": "span", "name": name, "ts": 0.0, "t0_s": t0,
            "dur_s": dur, "seq": seq, "parent": parent, "attrs": attrs}


def _ctx(records, **clock):
    marks = dict(window_open=10.0, window_close=20.0, trace_close=None,
                 trace_stop_s=0.0)
    marks.update(clock)
    return {"clock": types.SimpleNamespace(**marks), "window_rounds": 5,
            "additional_results": {"obs": {"timeline": records}}}


def test_spans_are_clipped_to_the_window_by_hand():
    recs = [
        _span("driver.callbacks", 9.5, 1.0, 1),    # half of it inside
        _span("driver.checkpoint", 12.0, 0.25, 2),
        _span("driver.checkpoint", 19.9, 0.5, 3),  # 0.1 s inside
        _span("dispatch", 10.5, 9.0, 4),
        {"kind": "event", "name": "checkpoint.commit", "ts": 0.0,
         "t0_s": 12.1, "seq": 5},
    ]
    ctx = _ctx(recs)
    assert spans.in_window_ms_per_round(
        ctx, "driver.callbacks") == pytest.approx(100.0)
    assert spans.in_window_ms_per_round(
        ctx, "driver.checkpoint") == pytest.approx(70.0)
    assert spans.before_window_s(
        ctx, "driver.callbacks") == pytest.approx(0.5)
    # the seconds stop_trace took leave the window, as they leave round_ms
    cut = _ctx(recs, trace_close=12.1, trace_stop_s=2.0)
    assert spans.window_parts(cut["clock"]) == [[10.0, 12.1], [14.1, 20.0]]
    assert spans.in_window_ms_per_round(
        cut, "driver.checkpoint") == pytest.approx(40.0)
    assert spans.in_window_ms_per_round(
        cut, "dispatch") == pytest.approx(1400.0)
    # a program whose records carry no t0_s has no timeline to read
    old = [{k: v for k, v in r.items() if k != "t0_s"} for r in recs]
    assert spans.timeline(_ctx(old)) is None
    assert spans.in_window_ms_per_round(_ctx(old), "dispatch") is None
    assert spans.before_window_s(_ctx([]), "data.load") is None


def test_a_compile_under_a_window_dispatch_is_not_warm():
    recs = [
        _span("dispatch", 5.0, 4.0, 1, first=True),       # before the window
        _span("dispatch.enqueue", 5.0, 3.0, 2, parent=1),
        _span("compile.backend", 5.5, 2.0, 3, parent=2, cache_hit=True),
        _span("compile.cache_load", 5.5, 1.9, 4, parent=2),
        _span("compile.trace", 5.1, 0.3, 5, parent=2),
        _span("dispatch", 11.0, 4.0, 6),
        _span("dispatch", 15.5, 4.0, 7),
        _span("dispatch.enqueue", 15.5, 0.5, 8, parent=7),
        _span("compile.backend", 15.6, 0.25, 9, parent=8, cache_hit=False),
        # a compile in the window under another parent is not a dispatch's
        _span("driver.checkpoint", 15.0, 0.5, 10),
        _span("compile.backend", 15.1, 0.125, 11, parent=10, cache_hit=False),
    ]
    ctx = _ctx(recs)
    assert bench_run.load_metric_reader("round.warm_dispatch_pct")(ctx) == pytest.approx(50.0)
    assert bench_run.load_metric_reader("round.cache_load_s")(ctx) == pytest.approx(1.9)
    # the backend span that was a cache hit is the load's, not a compile
    assert bench_run.load_metric_reader("round.compile_s")(ctx) == pytest.approx(0.3)
    assert bench_run.load_metric_reader("round.enqueue_ms")(ctx) == pytest.approx(100.0)
    assert bench_run.load_metric_reader("driver.loop_self_ms")(ctx) == pytest.approx(
        (10.0 - 8.0 - 0.5) * 1000 / 5)


@pytest.mark.parametrize("cell", ["higgs-d6.default", "higgs-d6.earlystop"])
def test_traced_rehearsal_reports_the_span_metrics(cell):
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", "2147483693", "--seconds", "1", "--trace", "1",
         "--rehearse-cpu"],
        capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    summary = json.loads(next(
        ln for ln in lines if ln.startswith("[bench] summary ")
    )[len("[bench] summary "):])
    assert line["metrics"] == {}  # a CPU timing is under no device name
    got = {k: v["value"] for k, v in line["rehearsal"].items()}
    assert set(SPAN_METRICS) <= set(got)
    assert got["round.warm_dispatch_pct"] == 100
    assert got["round.compile_s"] > 0 and got["ingest.load_s"] > 0
    # the window less its dispatches, read from inside and from outside: the
    # two agree to 1 % of the window (a dispatch span starts a few
    # microseconds inside the step call that chunk_times_s times)
    inside = (got["driver.checkpoint_ms"] + got["driver.callbacks_ms"]
              + got["driver.loop_self_ms"])
    round_ms = summary["window_s"] * 1000.0 / summary["window_rounds"]
    assert inside == pytest.approx(got["driver.between_dispatch_ms"],
                                   abs=0.01 * round_ms)
    assert all(got[k] >= 0 for k in SPAN_METRICS)

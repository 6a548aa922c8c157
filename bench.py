"""Headline benchmark: HIGGS-protocol training wall-clock (BASELINE.md).

Reproduces the reference's benchmark protocol
(``xgboost_ray/tests/release/benchmark_cpu_gpu.py:22-106``: N workers, 100
boosting rounds, ``TRAIN TIME TAKEN``) on TPU. The real HIGGS csv (11M x 28)
is not downloadable in this zero-egress image, so the dataset is a
synthetic HIGGS-shaped binary-classification problem of the same size and
dtype; wall-clock is shape-bound (histograms over 11M x 28 x 256 bins), not
data-content-bound, so timings are protocol-comparable.

vs_baseline: BASELINE.json publishes no reference number (the reference
writes res.csv at runtime only), so we normalize against the BASELINE.md
north-star target of 120 s for `gpu_hist` on HIGGS-11M/100 rounds.
vs_baseline > 1.0 means faster than that target.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}; every
result line names the device it ran on (``platform``, ``device_kind``,
``device_count`` as JAX reports them).

Structure: one process. ``python bench.py`` runs the measurement on the
backend JAX finds and exits non-zero, with no result line, when that is not
a TPU — there is no fallback, no retry ladder and no child process (a chip
belongs to one process at a time). ``python bench.py --cpu-counts`` is the
explicit counts-and-correctness mode on the 8-device virtual CPU mesh: it
runs the paired sections (bytes, compiles, logloss gates) and prints a line
with no ``higgs11m_*`` metric — a CPU timing is not a speed number.
"""

import contextlib
import glob
import json
import os
import re
import sys
import time

import numpy as np

BASELINE_GPU_HIST_S = 120.0

# ---------------------------------------------------------------------------
# REGRESSION NOTE (r4 -> r5 "52% CPU-mesh slowdown", investigated r6): the
# recorded BENCH_r04 (0.76 s/round) vs BENCH_r05 (1.44 s/round) delta is NOT
# a code regression. Re-running both snapshots' bench on one machine under
# identical conditions gives r4-end 4.17 s/round vs r5-end 4.11 s/round
# (within 1.5%) — the recorded gap was environmental (different machine
# load/hardware during the driver's capture runs). Two confounds make the
# recorded numbers fragile: (a) with 10 rounds fused into one scan chunk,
# round_times_s is (compile + run)/10, so compile-time variance lands in the
# "per-round" figure; (b) absolute CPU-mesh throughput varies ~5x across
# capture environments. The tripwire below exists so the next such delta is
# flagged AT CAPTURE TIME instead of a round later; cross-machine noise can
# still trip it — treat a firing as "investigate", not "revert".
#
# r6 closes the item with in-process data: every CPU-mesh capture now also
# emits an ``r4_regression_recheck`` section (see ``r4_paired_recheck``)
# pairing two same-process re-measurements of the protocol config; the
# pair ratio bounds same-environment variance, and the recorded 1.89x
# r4->r5 delta sits far outside it => environmental, recorded in the
# BENCH_r06 snapshot itself.
# ---------------------------------------------------------------------------

# tripwire: warn when the steady per-round time regresses more than this
# factor vs the newest recorded BENCH_*.json of the same backend
TRIPWIRE_RATIO = 1.2

# serving p99 latency gets a looser band: tail latency on a shared CPU mesh
# is noisier than steady per-round medians (scheduler jitter lands directly
# in the p99), so 1.2x would fire on environmental noise alone
SERVE_TRIPWIRE_RATIO = 1.5

# paired heap-vs-node-array serving arms run back-to-back in ONE process
# under an identical closed-loop config, so same-environment variance is
# bounded and the band can be the tight 20%: fire when the node-array
# arm's p99 exceeds 1.2x the heap arm's (the FIL-style layout's p99 cut
# regressed)
SERVE_LAYOUT_TRIPWIRE_RATIO = 1.2

# chaos recovery: flag >20% time-to-recover regressions across snapshots
CHAOS_TRIPWIRE_RATIO = 1.2

# restart-vs-continue: flag >20% regressions of the elastic continuation's
# recovery advantage (continue_ttr / restart_ttr) across snapshots — the
# guard that keeps "zero-replay continuation is actually faster than
# restart-from-checkpoint" from silently rotting
ELASTIC_TRIPWIRE_RATIO = 1.2

# sampled-config round time: flag >20% regressions of the subsample=0.5
# ablation arm across snapshots — the guard that keeps "subsample is
# actually cheaper" from silently rotting back into zeroed-gh full-row cost
SAMPLING_TRIPWIRE_RATIO = 1.2

# instrumentation overhead: the obs plane's per-round spans ride the round
# loop of EVERY traced run, so their cost budget is absolute — tracing on
# may cost at most 2% of steady round time over tracing off. Unlike the
# other tripwires this one fires on the current run's own paired
# measurement (the budget), not only on cross-snapshot drift; the section
# still lands in every BENCH_*.json so history stays queryable.
OBS_OVERHEAD_RATIO = 1.02

# wide-feature 2D mesh: flag >20% regressions of the (4,2) row x feature
# arm's per-round time across snapshots — the guard that keeps "feature
# sharding is actually cheaper on wide data" from silently rotting. The
# byte cut itself is trace-deterministic and carries its own >=1.5x floor
# inside the section (byte_cut_ok).
WIDE_FEATURE_TRIPWIRE_RATIO = 1.2
WIDE_FEATURE_BYTE_CUT_MIN = 1.5

# low-precision gh plane: flag >20% regressions of the gh_precision='int8'
# ablation arm's steady per-round time across snapshots — the guard that
# keeps "int8 gradients are at worst round-time-neutral" from silently
# rotting into a slow path. The gh-plane byte cut itself is static layout
# arithmetic certified by rxgbverify (the traced programs really carry the
# narrow dtype), and carries its own >=3.5x floor inside the section.
LOW_PRECISION_TRIPWIRE_RATIO = 1.2
LOW_PRECISION_GH_CUT_MIN = 3.5
# accuracy gate: quantized-gradient arms must land within this of the f32
# arm's final logloss (the PR 4 sampling discipline, applied to precision)
LOW_PRECISION_LOGLOSS_TOL = 5e-4
# steady-round budget: int8 gh may cost at most this factor of f32 per round
LOW_PRECISION_ROUND_TIME_MAX = 1.05

# vectorized HPO: one vmapped-K=4 program vs 4 sequential trials of the same
# configs. cost_ratio = vmapped total wall / sequential total wall — the
# gate is the shipping contract (the lane axis exists to amortize compile
# and per-round dispatch across candidates, so the packed program must cost
# well under the sum of its lanes), and the >20% tripwire guards
# cross-snapshot drift of the ratio itself.
HPO_COST_RATIO_GATE = 0.6
HPO_TRIPWIRE_RATIO = 1.2


def _load_latest_bench_record(bench_dir):
    """Newest BENCH_*.json result dict (by round number, then mtime).

    The driver writes ``{"n": ..., "parsed": {...}}`` wrappers; accept both
    that shape and a bare result dict."""
    paths = glob.glob(os.path.join(bench_dir, "BENCH_*.json"))

    def key(p):
        m = re.search(r"BENCH_r?0*(\d+)", os.path.basename(p))
        return (int(m.group(1)) if m else -1, os.path.getmtime(p))

    for p in sorted(paths, key=key, reverse=True):
        try:
            with open(p) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        rec = doc.get("parsed", doc) if isinstance(doc, dict) else None
        if isinstance(rec, dict) and "metric" in rec:
            return rec, os.path.basename(p)
    return None, None


def _steady_per_round(round_times, chunk, total_s, rounds):
    """The one steady-state per-round estimator every ablation arm uses:
    median of the rounds after the compile-carrying first chunk, mean of
    the recorded times when there is no post-chunk sample, whole-train
    average as the last resort. Shared so the chunk-exclusion protocol
    cannot drift between call sites."""
    rt = round_times or []
    if len(rt) > chunk:
        return float(np.median(rt[chunk:]))
    if rt:
        return float(np.mean(rt))
    return float(total_s) / max(rounds, 1)


def _per_round_seconds(rec):
    """Best available per-round figure from a bench record, with its basis.

    Returns ``(seconds, basis)``: basis "steady" (compile excluded) or
    "compile_inclusive" (first-chunk mean / whole-train average)."""
    if not isinstance(rec, dict):
        return None, None
    if rec.get("steady_median_s"):
        return float(rec["steady_median_s"]), "steady"
    if rec.get("first_chunk_mean_s"):
        return float(rec["first_chunk_mean_s"]), "compile_inclusive"
    if rec.get("train_time_s") and rec.get("rounds"):
        return (
            float(rec["train_time_s"]) / float(rec["rounds"]),
            "compile_inclusive",
        )
    return None, None


def round_time_tripwire(current_s, prev_rec, prev_name=None, backend=None,
                        threshold=TRIPWIRE_RATIO,
                        current_basis="compile_inclusive"):
    """Compare the current per-round time against the newest recorded bench.

    Returns a dict ``{prev_per_round_s, prev_record, basis, ratio, fired}``
    or ``None`` when no comparable record exists (different backend,
    missing timing). Only fires when both figures share the same basis —
    a compile-inclusive first-chunk mean against a prior run's steady
    median would measure XLA compile time, not a regression; a
    basis-mismatched comparison is still reported, with ``fired`` False
    and the mismatch named. Fires (warns on stderr) when ``current >
    threshold * prev`` — the guard the r4->r5 CPU-mesh "regression"
    (environmental, see the note above) slipped past uninspected."""
    if not current_s or not isinstance(prev_rec, dict):
        return None
    if backend and prev_rec.get("backend") and prev_rec["backend"] != backend:
        return None
    prev, prev_basis = _per_round_seconds(prev_rec)
    if not prev:
        return None
    ratio = float(current_s) / prev
    out = {
        "prev_per_round_s": round(prev, 4),
        "prev_record": prev_name,
        "basis": current_basis,
        "ratio": round(ratio, 3),
        "fired": False,
    }
    if prev_basis != current_basis:
        out["basis_mismatch"] = f"prev={prev_basis}"
        return out
    if ratio > threshold:
        out["fired"] = True
        print(
            f"[bench] TRIPWIRE: per-round time {current_s:.4f}s is "
            f"{ratio:.2f}x the newest recorded run "
            f"({prev:.4f}s in {prev_name or 'BENCH_*.json'}, "
            f"basis={current_basis}) — >{(threshold - 1) * 100:.0f}% "
            f"regression. Investigate before trusting this build's round "
            f"times.",
            file=sys.stderr,
        )
    return out


def serve_latency_tripwire(current_serve, prev_rec, prev_name=None,
                           backend=None, threshold=SERVE_TRIPWIRE_RATIO,
                           section="serve"):
    """Compare this run's serve p99 against the newest recorded bench.

    The serving analog of ``round_time_tripwire``: returns
    ``{prev_p99_ms, prev_record, ratio, fired}`` or None when no comparable
    record exists (different backend, no recorded ``section`` — "serve" by
    default, "serve_node_array" for the paired layout arm). Only fires
    like-for-like — when the recorded run used a different closed-loop
    config (clients / max_batch / deadline / request profile), the
    comparison is still reported with ``config_mismatch`` set and ``fired``
    False, since a p99 under different load is not a regression signal."""
    if not isinstance(current_serve, dict):
        return None
    cur = current_serve.get("latency_p99_ms")
    if not cur or not isinstance(prev_rec, dict):
        return None
    if backend and prev_rec.get("backend") and prev_rec["backend"] != backend:
        return None
    prev_serve = prev_rec.get(section)
    if not isinstance(prev_serve, dict):
        return None
    prev = prev_serve.get("latency_p99_ms")
    if not prev:
        return None
    ratio = float(cur) / float(prev)
    out = {
        "prev_p99_ms": round(float(prev), 4),
        "prev_record": prev_name,
        "ratio": round(ratio, 3),
        "fired": False,
    }
    if prev_serve.get("config") != current_serve.get("config"):
        out["config_mismatch"] = True
        return out
    if ratio > threshold:
        out["fired"] = True
        print(
            f"[bench] SERVE TRIPWIRE: p99 latency {cur:.2f}ms is "
            f"{ratio:.2f}x the newest recorded run ({prev:.2f}ms in "
            f"{prev_name or 'BENCH_*.json'}) — >{(threshold - 1) * 100:.0f}% "
            f"regression. Investigate before trusting this build's serving "
            f"tail.",
            file=sys.stderr,
        )
    return out


def serve_layout_tripwire(heap_serve, na_serve,
                          threshold=SERVE_LAYOUT_TRIPWIRE_RATIO):
    """Paired-arm tripwire: heap vs node-array p99 from the SAME process.

    Both arms serve the same model under the identical closed-loop config,
    back to back, so this is the low-variance comparison: returns
    ``{heap_p99_ms, node_array_p99_ms, ratio, fired}`` (ratio =
    node_array / heap) or None when either arm is missing its p99. Fires
    when the node-array arm's p99 exceeds ``threshold``x the heap arm's —
    the FIL-style layout's measured tail-latency cut has regressed >20%.
    A config difference between the arms (everything but the ``layout``
    key) is reported with ``config_mismatch`` and never fires."""
    if not isinstance(heap_serve, dict) or not isinstance(na_serve, dict):
        return None
    heap_p99 = heap_serve.get("latency_p99_ms")
    na_p99 = na_serve.get("latency_p99_ms")
    if not heap_p99 or not na_p99:
        return None
    ratio = float(na_p99) / float(heap_p99)
    out = {
        "heap_p99_ms": round(float(heap_p99), 4),
        "node_array_p99_ms": round(float(na_p99), 4),
        "ratio": round(ratio, 3),
        "fired": False,
    }

    def _cfg(section):
        cfg = section.get("config")
        if not isinstance(cfg, dict):
            return None
        return {k: v for k, v in cfg.items() if k != "layout"}

    if _cfg(heap_serve) != _cfg(na_serve):
        out["config_mismatch"] = True
        return out
    if ratio > threshold:
        out["fired"] = True
        print(
            f"[bench] SERVE LAYOUT TRIPWIRE: node-array p99 "
            f"{float(na_p99):.2f}ms is {ratio:.2f}x the paired heap arm's "
            f"({float(heap_p99):.2f}ms) — the FIL-style layout's p99 cut "
            f"regressed >{(threshold - 1) * 100:.0f}%. Investigate before "
            f"trusting this build's node-array serving path.",
            file=sys.stderr,
        )
    return out


def chaos_recovery_tripwire(current_chaos, prev_rec, prev_name=None,
                            backend=None, threshold=CHAOS_TRIPWIRE_RATIO):
    """Compare this run's time-to-recover against the newest recorded bench.

    The recovery analog of ``round_time_tripwire``: returns
    ``{prev_time_to_recover_s, prev_record, ratio, fired}`` or None when no
    comparable record exists (different backend, no recorded ``chaos``
    section). Like-for-like only: a different chaos config (rows / rounds /
    actors / fault schedule) is reported with ``config_mismatch`` set and
    never fires."""
    if not isinstance(current_chaos, dict):
        return None
    cur = current_chaos.get("time_to_recover_s")
    if not cur or not isinstance(prev_rec, dict):
        return None
    if backend and prev_rec.get("backend") and prev_rec["backend"] != backend:
        return None
    prev_chaos = prev_rec.get("chaos")
    if not isinstance(prev_chaos, dict):
        return None
    prev = prev_chaos.get("time_to_recover_s")
    if not prev:
        return None
    ratio = float(cur) / float(prev)
    out = {
        "prev_time_to_recover_s": round(float(prev), 4),
        "prev_record": prev_name,
        "ratio": round(ratio, 3),
        "fired": False,
    }
    if prev_chaos.get("config") != current_chaos.get("config"):
        out["config_mismatch"] = True
        return out
    if ratio > threshold:
        out["fired"] = True
        print(
            f"[bench] CHAOS TRIPWIRE: time-to-recover {cur:.2f}s is "
            f"{ratio:.2f}x the newest recorded run ({prev:.2f}s in "
            f"{prev_name or 'BENCH_*.json'}) — >{(threshold - 1) * 100:.0f}% "
            f"regression. Investigate the recovery path before trusting "
            f"this build's fault tolerance.",
            file=sys.stderr,
        )
    return out


def elastic_recovery_tripwire(current_chaos, prev_rec, prev_name=None,
                              backend=None, threshold=ELASTIC_TRIPWIRE_RATIO):
    """Compare this run's continue-vs-restart recovery ratio against the
    newest recorded bench.

    The elastic-continuation analog of ``chaos_recovery_tripwire``: the
    tracked figure is ``continue_vs_restart.ratio`` (elastic in-flight
    recovery time over restart-from-checkpoint recovery time — smaller is
    better, < 1 means continuation keeps its edge), compared for the base
    pairing AND the per-config pairings (``elastic_2d`` /
    ``elastic_streamed`` — the 2D-mesh and streamed arms that used to be
    fallback cases — and ``elastic_domain``, the correlated host-loss
    arm). Returns ``{prev_ratio, prev_record, ratio, fired[,
    arms]}`` or None when no comparable record exists (different backend,
    no recorded base pairing); ``fired`` is True when ANY arm regresses
    past the threshold. Like-for-like only: a different chaos config is
    reported with ``config_mismatch`` set and never fires (per arm for the
    per-config pairings)."""
    if not isinstance(current_chaos, dict):
        return None
    cur = (current_chaos.get("continue_vs_restart") or {}).get("ratio")
    if not cur or not isinstance(prev_rec, dict):
        return None
    if backend and prev_rec.get("backend") and prev_rec["backend"] != backend:
        return None
    prev_chaos = prev_rec.get("chaos")
    if not isinstance(prev_chaos, dict):
        return None
    prev = (prev_chaos.get("continue_vs_restart") or {}).get("ratio")
    if not prev:
        return None
    ratio = float(cur) / float(prev)
    out = {
        "prev_ratio": round(float(prev), 4),
        "prev_record": prev_name,
        "ratio": round(ratio, 3),
        "fired": False,
    }
    base_config_matches = (
        prev_chaos.get("config") == current_chaos.get("config")
    )
    if not base_config_matches:
        # the base pairing is reported-but-never-fired on a config change;
        # the per-config arms below still compare (each against its OWN
        # config), so a soak-config change cannot mask an arm regression
        out["config_mismatch"] = True

    def _fire(label, c, p, r):
        out["fired"] = True
        print(
            f"[bench] ELASTIC TRIPWIRE [{label}]: continue-vs-restart "
            f"recovery ratio {c:.3f} is {r:.2f}x the newest recorded run "
            f"({p:.3f} in {prev_name or 'BENCH_*.json'}) — "
            f">{(threshold - 1) * 100:.0f}% regression of the zero-replay "
            f"continuation's advantage. Investigate the in-flight recovery "
            f"path before trusting this build's elastic training.",
            file=sys.stderr,
        )

    if base_config_matches and ratio > threshold:
        _fire("base", float(cur), float(prev), ratio)
    arms = {}
    for key in ("elastic_2d", "elastic_streamed", "elastic_domain"):
        cur_arm = current_chaos.get(key) or {}
        prev_arm = prev_chaos.get(key) or {}
        c = (cur_arm.get("continue_vs_restart") or {}).get("ratio")
        p = (prev_arm.get("continue_vs_restart") or {}).get("ratio")
        if not c or not p:
            continue  # arm absent on one side (older record) — not comparable
        a_ratio = float(c) / float(p)
        arm_out = {
            "prev_ratio": round(float(p), 4),
            "ratio": round(a_ratio, 3),
            "fired": False,
        }
        if prev_arm.get("config") != cur_arm.get("config"):
            arm_out["config_mismatch"] = True
        elif a_ratio > threshold:
            arm_out["fired"] = True
            _fire(key, float(c), float(p), a_ratio)
        arms[key] = arm_out
    if arms:
        out["arms"] = arms
    return out


def sampling_round_time_tripwire(current_sampling, prev_rec, prev_name=None,
                                 backend=None,
                                 threshold=SAMPLING_TRIPWIRE_RATIO):
    """Compare this run's sampled-config (subsample=0.5 arm) steady
    per-round time against the newest recorded bench.

    The sampling analog of ``round_time_tripwire``: returns
    ``{prev_per_round_s, prev_record, ratio, fired}`` or None when no
    comparable record exists (different backend, no recorded ``sampling``
    section). Like-for-like only: a different ablation config (rows /
    rounds / actors / rates) is reported with ``config_mismatch`` set and
    never fires."""
    if not isinstance(current_sampling, dict):
        return None
    cur = (current_sampling.get("subsample") or {}).get("per_round_s")
    if not cur or not isinstance(prev_rec, dict):
        return None
    if backend and prev_rec.get("backend") and prev_rec["backend"] != backend:
        return None
    prev_samp = prev_rec.get("sampling")
    if not isinstance(prev_samp, dict):
        return None
    prev = (prev_samp.get("subsample") or {}).get("per_round_s")
    if not prev:
        return None
    ratio = float(cur) / float(prev)
    out = {
        "prev_per_round_s": round(float(prev), 4),
        "prev_record": prev_name,
        "ratio": round(ratio, 3),
        "fired": False,
    }
    if prev_samp.get("config") != current_sampling.get("config"):
        out["config_mismatch"] = True
        return out
    if ratio > threshold:
        out["fired"] = True
        print(
            f"[bench] SAMPLING TRIPWIRE: sampled per-round time {cur:.4f}s "
            f"is {ratio:.2f}x the newest recorded run ({prev:.4f}s in "
            f"{prev_name or 'BENCH_*.json'}) — >{(threshold - 1) * 100:.0f}% "
            f"regression. The compacted-build win is eroding; investigate "
            f"before trusting this build's sampled rounds.",
            file=sys.stderr,
        )
    return out


def obs_overhead_tripwire(current_obs, prev_rec=None, prev_name=None,
                          backend=None, threshold=OBS_OVERHEAD_RATIO):
    """Check the tracing-on/tracing-off paired measurement against the
    ≤2% instrumentation budget.

    The obs analog of ``round_time_tripwire``, with one deliberate
    difference: the tracked figure (``overhead_ratio`` = tracing-on steady
    per-round time over tracing-off) is a within-run pairing, so the
    tripwire fires on the CURRENT run's own budget violation — no prior
    snapshot needed. When the newest recorded bench carries a comparable
    ``obs_overhead`` section (same backend, same config), its ratio is
    reported alongside so cross-snapshot drift of the overhead itself stays
    visible. Returns ``{overhead_ratio, budget, fired, ...}`` or ``None``
    when the current section has no ratio (an arm failed to measure)."""
    if not isinstance(current_obs, dict):
        return None
    cur = current_obs.get("overhead_ratio")
    if not cur:
        return None
    out = {
        "overhead_ratio": round(float(cur), 4),
        "budget": threshold,
        "fired": False,
    }
    prev_obs = (prev_rec or {}).get("obs_overhead") \
        if isinstance(prev_rec, dict) else None
    if isinstance(prev_obs, dict) and prev_obs.get("overhead_ratio"):
        if backend and prev_rec.get("backend") \
                and prev_rec["backend"] != backend:
            prev_obs = None
        elif prev_obs.get("config") != current_obs.get("config"):
            out["config_mismatch"] = True
            prev_obs = None
    if isinstance(prev_obs, dict) and prev_obs.get("overhead_ratio"):
        out["prev_overhead_ratio"] = round(
            float(prev_obs["overhead_ratio"]), 4
        )
        out["prev_record"] = prev_name
    if float(cur) > threshold:
        out["fired"] = True
        print(
            f"[bench] OBS OVERHEAD TRIPWIRE: tracing-on steady round time "
            f"is {float(cur):.4f}x tracing-off — over the "
            f"{(threshold - 1) * 100:.0f}% instrumentation budget. The "
            f"span emission path has grown a hot-loop cost; profile "
            f"obs.trace before trusting traced-run timings.",
            file=sys.stderr,
        )
    return out


def run_obs_overhead(x=None, y=None, base_params=None, actors=None):
    """Paired tracing-on vs tracing-off steady-round measurement.

    Two fresh back-to-back trainings of the identical config — one with
    ``RXGB_TRACE=0`` (the tracer's ``span()``/``event()`` become near-free
    no-ops), one with tracing on (the default every production run gets) —
    each 2 scan chunks so the steady median excludes the compile-carrying
    first chunk. The ratio is the price of the obs plane itself, which the
    ≤2% budget (``OBS_OVERHEAD_RATIO``) keeps honest: instrumentation that
    costs real round time is a perf regression like any other. Returns the
    ``obs_overhead`` section for the BENCH record."""
    import jax

    from xgboost_ray_tpu import RayDMatrix, RayParams, train

    chunk = max(1, int(os.environ.get("RXGB_SCAN_MAX_CHUNK", "10")))
    rounds = int(os.environ.get("BENCH_OBS_OVERHEAD_ROUNDS", 2 * chunk))
    if x is None or y is None:
        n_rows = int(os.environ.get("BENCH_OBS_OVERHEAD_ROWS", 25_000))
        x, y = make_higgs_like(n_rows, 28, seed=5)
    if actors is None:
        actors = int(os.environ.get(
            "BENCH_ACTORS", max(1, len(jax.devices()))
        ))
    params = {
        "objective": "binary:logistic", "max_depth": 6, "eta": 0.1,
        "max_bin": 256, "tree_method": "tpu_hist",
    }
    if base_params:
        params.update(base_params)

    out = {"rounds": rounds}
    saved = os.environ.get("RXGB_TRACE")
    try:
        for arm, flag in (("tracing_off", "0"), ("tracing_on", "1")):
            os.environ["RXGB_TRACE"] = flag
            res = {}
            t0 = time.time()
            train(
                params, RayDMatrix(x, y), num_boost_round=rounds,
                additional_results=res,
                ray_params=RayParams(num_actors=actors,
                                     checkpoint_frequency=0),
            )
            arm_time = time.time() - t0
            out[arm] = {
                "per_round_s": round(_steady_per_round(
                    res.get("round_times_s"), chunk, arm_time, rounds
                ), 4),
                "train_time_s": round(arm_time, 2),
            }
            if flag == "1":
                obs_res = res.get("obs") or {}
                out[arm]["records"] = len(obs_res.get("timeline") or [])
                out[arm]["dropped_spans"] = obs_res.get("dropped_spans", 0)
    finally:
        if saved is None:
            os.environ.pop("RXGB_TRACE", None)
        else:
            os.environ["RXGB_TRACE"] = saved
    off_s = out["tracing_off"]["per_round_s"]
    if off_s:
        out["overhead_ratio"] = round(
            out["tracing_on"]["per_round_s"] / off_s, 4
        )
        out["within_budget"] = out["overhead_ratio"] <= OBS_OVERHEAD_RATIO
    out["config"] = {
        "rows": int(x.shape[0]), "features": int(x.shape[1]),
        "rounds": rounds, "actors": actors,
        "max_depth": int(params.get("max_depth", 6)),
    }
    print(f"[bench] obs overhead: {out}", file=sys.stderr)
    return out


def run_sampling_ablation(x, y, base_params, actors):
    """Paired full/sampled training ablation on the ambient mesh.

    Three arms, fresh and back-to-back (identical environment): full rows,
    ``subsample=0.5``, and GOSS (``sampling_method='gradient_based'``,
    a=0.1 / b=0.1). Each runs 2 scan chunks so the steady per-round median
    excludes the compile-carrying first chunk, and each records its final
    train logloss — the win must show up in wall clock WITHOUT the metric
    drifting outside the documented tolerance. Arms train with NO eval
    sets (logloss is computed post-hoc from the predicted margins) so the
    "full" arm is config-identical to the protocol run and the hist_quant
    ablation's "none" arm — ``r4_paired_recheck`` depends on that
    like-for-like pairing. Returns the ``sampling`` section with per-arm
    timings and sampled/full ratios."""
    from xgboost_ray_tpu import RayDMatrix, RayParams, train

    chunk = max(1, int(os.environ.get("RXGB_SCAN_MAX_CHUNK", "10")))
    abl_rounds = int(
        os.environ.get("BENCH_SAMPLING_ABLATION_ROUNDS", 2 * chunk)
    )
    arms = {
        "full": {},
        "subsample": {"subsample": 0.5},
        "goss": {"sampling_method": "gradient_based", "top_rate": 0.1,
                 "other_rate": 0.1},
    }

    def binary_logloss(margin):
        p = 1.0 / (1.0 + np.exp(-np.asarray(margin, np.float64).ravel()))
        p = np.clip(p, 1e-15, 1 - 1e-15)
        return float(-np.mean(y * np.log(p) + (1 - y) * np.log1p(-p)))

    out = {"rounds": abl_rounds}
    for name, extra in arms.items():
        p = dict(base_params)
        p.update(extra)
        res = {}
        t0 = time.time()
        bst = train(
            p,
            RayDMatrix(x, y),
            num_boost_round=abl_rounds,
            additional_results=res,
            ray_params=RayParams(num_actors=actors, checkpoint_frequency=0),
        )
        arm_time = time.time() - t0
        per_round = _steady_per_round(
            res.get("round_times_s"), chunk, arm_time, abl_rounds
        )
        out[name] = {
            "per_round_s": round(per_round, 4),
            "train_time_s": round(arm_time, 2),
            "final_logloss": round(
                binary_logloss(bst.predict(x, output_margin=True)), 5
            ),
        }
    full_s = out["full"]["per_round_s"]
    if full_s:
        out["subsample_per_round_vs_full"] = round(
            out["subsample"]["per_round_s"] / full_s, 3
        )
        out["goss_per_round_vs_full"] = round(
            out["goss"]["per_round_s"] / full_s, 3
        )
    full_ll = out["full"]["final_logloss"]
    out["subsample_logloss_delta"] = round(
        out["subsample"]["final_logloss"] - full_ll, 5
    )
    out["goss_logloss_delta"] = round(
        out["goss"]["final_logloss"] - full_ll, 5
    )
    out["config"] = {
        "rows": int(x.shape[0]), "features": int(x.shape[1]),
        "rounds": abl_rounds, "actors": actors,
        "max_depth": int(base_params.get("max_depth", 6)),
        # derived from the arms dict so the recorded config (the tripwire's
        # like-for-like key) cannot drift from what actually ran
        "subsample_rate": arms["subsample"]["subsample"],
        "goss_top_rate": arms["goss"]["top_rate"],
        "goss_other_rate": arms["goss"]["other_rate"],
    }
    print(f"[bench] sampling ablation: {out}", file=sys.stderr)
    return out


def low_precision_tripwire(current_lp, prev_rec, prev_name=None,
                           backend=None,
                           threshold=LOW_PRECISION_TRIPWIRE_RATIO):
    """Compare this run's gh_precision='int8' arm steady per-round time
    against the newest recorded bench's ``low_precision`` section, and —
    when both records carry it — the composed ``int8_block_wire`` arm too
    (records predating the block wire simply lack the arm; the watch is
    skipped, never fired, so old snapshots stay comparable).

    The quantized-gradient analog of ``sampling_round_time_tripwire``:
    returns ``{prev_per_round_s, prev_record, ratio, fired}`` or None when
    no comparable record exists. Like-for-like only (config key)."""
    if not isinstance(current_lp, dict):
        return None
    cur = (current_lp.get("int8") or {}).get("per_round_s")
    if not cur or not isinstance(prev_rec, dict):
        return None
    if backend and prev_rec.get("backend") and prev_rec["backend"] != backend:
        return None
    prev_lp = prev_rec.get("low_precision")
    if not isinstance(prev_lp, dict):
        return None
    prev = (prev_lp.get("int8") or {}).get("per_round_s")
    if not prev:
        return None
    ratio = float(cur) / float(prev)
    out = {
        "prev_per_round_s": round(float(prev), 4),
        "prev_record": prev_name,
        "ratio": round(ratio, 3),
        "fired": False,
    }
    if prev_lp.get("config") != current_lp.get("config"):
        out["config_mismatch"] = True
        return out
    if ratio > threshold:
        out["fired"] = True
        print(
            f"[bench] LOW-PRECISION TRIPWIRE: int8-gh per-round time "
            f"{cur:.4f}s is {ratio:.2f}x the newest recorded run "
            f"({prev:.4f}s in {prev_name or 'BENCH_*.json'}) — "
            f">{(threshold - 1) * 100:.0f}% regression. The quantized-"
            f"gradient mode is rotting into a slow path; investigate "
            f"before trusting this build's low-precision numbers.",
            file=sys.stderr,
        )
    cur_b = (current_lp.get("int8_block_wire") or {}).get("per_round_s")
    prev_b = (prev_lp.get("int8_block_wire") or {}).get("per_round_s")
    if cur_b and prev_b:
        bratio = float(cur_b) / float(prev_b)
        out["block_wire_ratio"] = round(bratio, 3)
        out["prev_block_wire_per_round_s"] = round(float(prev_b), 4)
        if bratio > threshold:
            out["fired"] = True
            print(
                f"[bench] LOW-PRECISION TRIPWIRE: int8_block_wire per-round "
                f"time {cur_b:.4f}s is {bratio:.2f}x the newest recorded "
                f"run ({prev_b:.4f}s in {prev_name or 'BENCH_*.json'}) — "
                f">{(threshold - 1) * 100:.0f}% regression. The block-"
                f"scaled ring is rotting into a slow path; investigate "
                f"before trusting this build's wire numbers.",
                file=sys.stderr,
            )
    return out


def run_low_precision_ablation(x, y, base_params, actors):
    """Paired gh-precision ablation on the ambient mesh: f32 vs int16 vs
    int8 quantized gradients (ROADMAP item 3's measured contract).

    Six arms, fresh and back-to-back (identical environment), each
    config-identical to the protocol run except the precision knobs — and
    the f32 reference runs TWICE, bracketing the quantized arms
    (f32, int16, int8, int8_row_wire, int8_block_wire, f32_recheck): the
    two wire arms compose int8 gradients with the quantized actors-axis
    histogram wire (row scales vs block scales) and carry the block
    format's measured byte-cut and block-vs-row logloss-parity gates.
    Same-process round time drifts a few
    percent over a multi-minute capture (the r4_paired_recheck lesson), so
    comparing the last arm against the first conflates that drift with the
    mode under test. Ratios are judged against the bracket MEAN, and the
    recheck/first ratio is recorded as ``f32_drift_ratio`` so every capture
    carries its own noise bound. Per arm: steady per-round time (min over
    the post-compile chunks' true wall times), the static per-shard
    gh-plane bytes (the
    memory metric the mode is bought for — int8 must cut
    >= LOW_PRECISION_GH_CUT_MIN x; rxgbverify certifies the traced
    programs really carry the narrow dtype), and the final train logloss.
    The section asserts the shipping contract: both quantized arms within
    LOW_PRECISION_LOGLOSS_TOL of f32 (judged on UNROUNDED loglosses), and
    int8 steady-round time <= LOW_PRECISION_ROUND_TIME_MAX x the f32
    bracket mean, with the budget widened by the capture's own measured
    f32-vs-f32 drift (a gate tighter than the reference's same-config
    noise would fire on machine weather, not on the mode)."""
    from xgboost_ray_tpu import RayDMatrix, RayParams, train

    chunk = max(1, int(os.environ.get("RXGB_SCAN_MAX_CHUNK", "10")))
    # three chunks per arm (one compile-carrying + two steady) so the
    # steady figure can be the MIN over steady chunks: shared-box
    # contention only ever inflates a chunk, so the minimum is the
    # statistic least polluted by co-scheduling hiccups (the timeit
    # discipline) — medians over a single steady chunk inherit whichever
    # weather that chunk ran under
    abl_rounds = int(
        os.environ.get("BENCH_LOW_PRECISION_ROUNDS", 3 * chunk)
    )
    arms = {
        "f32": {},
        "int16": {"gh_precision": "int16"},
        "int8": {"gh_precision": "int8"},
        # composed wire arms (PR 19): int8 gradients x quantized actors-axis
        # histogram wire, row scales vs block scales — the paired comparison
        # the block format is bought for. min_bytes=0 so every level really
        # takes the quantized wire at ablation scale.
        "int8_row_wire": {"gh_precision": "int8", "hist_quant": "int8",
                          "hist_quant_min_bytes": 0},
        "int8_block_wire": {"gh_precision": "int8",
                            "hist_quant": "int8_block",
                            "hist_quant_min_bytes": 0},
        "f32_recheck": {},
    }

    def steady(res, arm_time):
        """Min steady per-round over the post-compile chunks from the TRUE
        per-dispatch chunk wall times; falls back to the shared estimator
        when chunk times are absent (per-round stepping paths)."""
        chunks = [
            c["seconds"] / max(1, c["rounds"])
            for c in (res.get("chunk_times_s") or [])[1:]
            if isinstance(c, dict) and c.get("rounds")
        ]
        if chunks:
            return min(chunks)
        return _steady_per_round(
            res.get("round_times_s"), chunk, arm_time, abl_rounds
        )

    def binary_logloss(margin):
        p = 1.0 / (1.0 + np.exp(-np.asarray(margin, np.float64).ravel()))
        p = np.clip(p, 1e-15, 1 - 1e-15)
        return float(-np.mean(y * np.log(p) + (1 - y) * np.log1p(-p)))

    out = {"rounds": abl_rounds}
    ll_exact = {}  # unrounded per-arm loglosses: the tolerance gate's inputs
    pr_exact = {}  # unrounded per-arm steady times: the round-time gate's
    #   inputs (stored per_round_s is display — the same discipline as the
    #   gh-bytes and logloss gates)
    for name, extra in arms.items():
        p = dict(base_params)
        p.update(extra)
        res = {}
        t0 = time.time()
        bst = train(
            p,
            RayDMatrix(x, y),
            num_boost_round=abl_rounds,
            additional_results=res,
            ray_params=RayParams(num_actors=actors, checkpoint_frequency=0),
        )
        arm_time = time.time() - t0
        pr_exact[name] = steady(res, arm_time)
        ll_exact[name] = binary_logloss(bst.predict(x, output_margin=True))
        arm = {
            "per_round_s": round(pr_exact[name], 4),
            "train_time_s": round(arm_time, 2),
            "final_logloss": round(ll_exact[name], 6),
        }
        gh_bytes = res.get("gh_plane_bytes_per_shard")
        if gh_bytes is not None:
            arm["gh_plane_bytes_per_shard"] = int(gh_bytes)
        wire_bytes = res.get("hist_allreduce_bytes_per_round")
        if wire_bytes is not None:
            arm["hist_allreduce_bytes_per_round"] = int(wire_bytes)
        out[name] = arm
    # drift-resistant f32 reference: the mean of the two bracket arms (the
    # int arms ran between them), plus the recheck/first drift bound
    f32_s = 0.5 * (pr_exact["f32"] + pr_exact["f32_recheck"])
    drift = 1.0
    if pr_exact["f32"]:
        drift = pr_exact["f32_recheck"] / pr_exact["f32"]
        out["f32_drift_ratio"] = round(drift, 3)
    if f32_s:
        out["int16_per_round_vs_f32"] = round(pr_exact["int16"] / f32_s, 3)
        out["int8_per_round_vs_f32"] = round(pr_exact["int8"] / f32_s, 3)
        # the budget is widened by the capture's OWN measured same-config
        # noise (the two f32 arms trained the identical program): a gate
        # tighter than the drift the reference itself exhibits would fire
        # on machine weather, not on the mode under test — the
        # r4_paired_recheck "pair ratio bounds same-env variance" logic
        budget = LOW_PRECISION_ROUND_TIME_MAX * max(1.0, drift)
        out["round_time_budget"] = round(budget, 3)
        out["round_time_ok"] = pr_exact["int8"] / f32_s <= budget
        if not out["round_time_ok"]:
            print(
                f"[bench] LOW-PRECISION ROUND TIME over budget: int8-gh "
                f"steady round is {out['int8_per_round_vs_f32']}x the f32 "
                f"bracket mean (budget {LOW_PRECISION_ROUND_TIME_MAX}x "
                f"widened to {out['round_time_budget']}x by the capture's "
                f"own f32 drift).",
                file=sys.stderr,
            )
    b_f32 = out["f32"].get("gh_plane_bytes_per_shard")
    b_int8 = out["int8"].get("gh_plane_bytes_per_shard")
    if b_f32 and b_int8:
        # the gate reads the unrounded ratio; the stored value is display
        out["gh_bytes_cut"] = round(b_f32 / b_int8, 2)
        out["gh_bytes_cut_ok"] = (b_f32 / b_int8) >= LOW_PRECISION_GH_CUT_MIN
        if not out["gh_bytes_cut_ok"]:
            print(
                f"[bench] LOW-PRECISION GH-PLANE CUT below floor: int8 "
                f"stores only {out['gh_bytes_cut']}x fewer gh bytes/shard "
                f"than f32 (floor {LOW_PRECISION_GH_CUT_MIN}x).",
                file=sys.stderr,
            )
    # parity judged on the UNROUNDED per-arm loglosses (the wide_feature
    # discipline: rounding first can slip a near-miss under the gate)
    for name in ("int16", "int8"):
        delta = ll_exact[name] - ll_exact["f32"]
        out[f"{name}_logloss_delta"] = round(delta, 6)
        out[f"{name}_logloss_ok"] = abs(delta) <= LOW_PRECISION_LOGLOSS_TOL
        if not out[f"{name}_logloss_ok"]:
            print(
                f"[bench] LOW-PRECISION LOGLOSS drift: {name}-gh final "
                f"logloss differs from f32 by {out[f'{name}_logloss_delta']} "
                f"(> {LOW_PRECISION_LOGLOSS_TOL}). Quantized-gradient "
                f"accuracy is drifting; fall back to gh_precision='float32' "
                f"until understood.",
                file=sys.stderr,
            )
    # composed wire arms: the block format's measured contract is (a) the
    # ppermute ring moves strictly fewer bytes than the row-scale wire at
    # the same payload and (b) the two int8-granularity wires agree in
    # final logloss (block-vs-row parity; both sit ~1e-3 absolute from f32
    # at this protocol — row and block alike — so the 5e-4 ABSOLUTE gate
    # stays on the gh arms where it physically holds, and the per-arm f32
    # deltas are recorded unGated for the drift history)
    wb_row = out["int8_row_wire"].get("hist_allreduce_bytes_per_round")
    wb_block = out["int8_block_wire"].get("hist_allreduce_bytes_per_round")
    if wb_row and wb_block:
        out["block_wire_bytes_cut"] = round(wb_row / wb_block, 4)
        out["block_wire_bytes_ok"] = wb_block < wb_row
        if not out["block_wire_bytes_ok"]:
            print(
                f"[bench] BLOCK WIRE BYTES not below row wire: int8_block "
                f"moved {wb_block} B/round vs int8 row {wb_row} B/round — "
                f"the in-band-scale ring lost its byte cut; see the "
                f"low-precision runbook in README.",
                file=sys.stderr,
            )
    for name in ("int8_row_wire", "int8_block_wire"):
        out[f"{name}_logloss_delta"] = round(
            ll_exact[name] - ll_exact["f32"], 6
        )
    wire_delta = ll_exact["int8_block_wire"] - ll_exact["int8_row_wire"]
    out["block_vs_row_logloss_delta"] = round(wire_delta, 6)
    # two-tier accuracy contract for the block wire (mirrors
    # tests/test_hist_quant.py): ALWAYS gate "block no worse than the row
    # wire vs f32" — the scale-robust check that catches block-format
    # accuracy rot — and gate the strict 5e-4 block-vs-row parity only at
    # protocol scale (>=100k rows; at smoke shapes the two wires path-
    # diverge by ~1e-3 from sheer sample noise, which says nothing about
    # the wire format)
    d_row = abs(ll_exact["int8_row_wire"] - ll_exact["f32"])
    d_block = abs(ll_exact["int8_block_wire"] - ll_exact["f32"])
    out["block_no_worse_than_row_ok"] = (
        d_block <= d_row + LOW_PRECISION_LOGLOSS_TOL
    )
    if not out["block_no_worse_than_row_ok"]:
        print(
            f"[bench] BLOCK WIRE LOGLOSS drift: int8_block sits "
            f"{d_block:.6f} from f32 vs the row wire's {d_row:.6f} "
            f"(margin {LOW_PRECISION_LOGLOSS_TOL}). The block-scale "
            f"rounding is drifting from the row-scale reference; fall "
            f"back to hist_quant='int8' until understood (README "
            f"runbook).",
            file=sys.stderr,
        )
    if x.shape[0] >= 100_000:
        out["block_vs_row_logloss_ok"] = (
            abs(wire_delta) <= LOW_PRECISION_LOGLOSS_TOL
        )
        if not out["block_vs_row_logloss_ok"]:
            print(
                f"[bench] BLOCK WIRE PARITY: block-vs-row logloss delta "
                f"{out['block_vs_row_logloss_delta']} exceeds "
                f"{LOW_PRECISION_LOGLOSS_TOL} at protocol scale — the two "
                f"int8 wires no longer track each other (measured 6e-5 at "
                f"200k when healthy); see README runbook.",
                file=sys.stderr,
            )
    out["config"] = {
        "rows": int(x.shape[0]), "features": int(x.shape[1]),
        "rounds": abl_rounds, "actors": actors,
        "max_depth": int(base_params.get("max_depth", 6)),
        # derived from the arms dict so the recorded config (the tripwire's
        # like-for-like key) cannot drift from what actually ran; the
        # bracket design (two f32 arms) is part of the protocol identity
        # lists, not tuples: the prev record round-trips through JSON and
        # the tripwire's like-for-like comparison is plain ==
        "arm_modes": [
            [k, v.get("gh_precision", "float32"),
             v.get("hist_quant", "none")] for k, v in arms.items()
        ],
    }
    print(f"[bench] low-precision ablation: {out}", file=sys.stderr)
    return out


#: streamed-ingest throughput guard: prev/current rows-per-second beyond
#: this fires (a >20% ingest slowdown — the streaming hot path is host
#: binning + H2D, both easy to silently regress)
STREAMING_TRIPWIRE_RATIO = 1.25

#: the streamed-vs-materialized accuracy contract at bench scale (same
#: bound the acceptance criterion and tests/test_streaming.py pin)
STREAMING_LOGLOSS_TOL = 5e-4


def streaming_ingest_tripwire(current_streaming, prev_rec, prev_name=None,
                              backend=None,
                              threshold=STREAMING_TRIPWIRE_RATIO):
    """Compare this run's streamed ingest throughput (rows/s) against the
    newest recorded bench's ``streaming`` section.

    Returns ``{prev_rows_per_s, prev_record, ratio, fired}`` or None when
    no comparable record exists; like-for-like only (config key), cross-
    backend records skipped. ``ratio`` is prev/current, so >threshold
    means ingest got >(threshold-1)x slower."""
    if not isinstance(current_streaming, dict):
        return None
    cur = (current_streaming.get("streamed") or {}).get("rows_per_s")
    if not cur or not isinstance(prev_rec, dict):
        return None
    if backend and prev_rec.get("backend") and prev_rec["backend"] != backend:
        return None
    prev_sec = prev_rec.get("streaming")
    if not isinstance(prev_sec, dict):
        return None
    prev = (prev_sec.get("streamed") or {}).get("rows_per_s")
    if not prev:
        return None
    ratio = float(prev) / float(cur)
    out = {
        "prev_rows_per_s": round(float(prev), 1),
        "prev_record": prev_name,
        "ratio": round(ratio, 3),
        "fired": False,
    }
    if prev_sec.get("config") != current_streaming.get("config"):
        out["config_mismatch"] = True
        return out
    if ratio > threshold:
        out["fired"] = True
        print(
            f"[bench] STREAMING TRIPWIRE: streamed ingest throughput "
            f"{cur:.0f} rows/s is {ratio:.2f}x slower than the newest "
            f"recorded run ({prev:.0f} rows/s in "
            f"{prev_name or 'BENCH_*.json'}) — "
            f">{(threshold - 1) * 100:.0f}% ingest regression. The "
            f"sketch/bin/H2D pipeline is rotting; investigate before "
            f"trusting this build's out-of-core numbers.",
            file=sys.stderr,
        )
    return out


class _RssPeakSampler:
    """Peak process RSS over the sampled window (background thread, 5 ms).

    psutil when present, /proc/self/statm otherwise — psutil is not in
    setup.py's install_requires, and the streaming ablation is default-on
    for CPU bench runs, so a bare install must still be able to sample.
    """

    def __init__(self):
        self._read_rss = self._pick_reader()
        self.baseline = 0
        self.peak = 0

    @staticmethod
    def _pick_reader():
        try:
            import psutil

            proc = psutil.Process()
            return lambda: proc.memory_info().rss
        except ImportError:
            pass
        try:
            page = os.sysconf("SC_PAGE_SIZE")

            def read_statm():
                with open("/proc/self/statm") as fh:
                    return int(fh.read().split()[1]) * page

            read_statm()  # probe: /proc is Linux-only
            return read_statm
        except (OSError, ValueError):
            pass
        # last resort (macOS/BSD without psutil): lifetime peak RSS via
        # getrusage — monotone, so window deltas under-count only when an
        # earlier phase peaked higher
        import resource

        scale = 1 if sys.platform == "darwin" else 1024  # bytes vs KiB
        return lambda: resource.getrusage(
            resource.RUSAGE_SELF
        ).ru_maxrss * scale

    def __enter__(self):
        import threading

        self._stop = threading.Event()
        self.baseline = self._read_rss()
        self.peak = self.baseline

        def run():
            while not self._stop.is_set():
                self.peak = max(self.peak, self._read_rss())
                time.sleep(0.005)

        self._thread = threading.Thread(target=run, daemon=True)
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join()
        self.peak = max(self.peak, self._read_rss())

    @property
    def delta_mb(self):
        return (self.peak - self.baseline) / 2**20


def run_streaming_ablation(x, y, base_params, actors):
    """Materialized-vs-streamed ingestion ablation on the ambient mesh
    (ROADMAP item 1's measured contract).

    Two arms over the SAME data, fresh and back-to-back: the materialized
    engine (raw f32 shard device-put + on-device sketch) and the streamed
    engine (chunked two-pass sketch→bin with double-buffered upload). Per
    arm: peak host RSS delta while the engine builds + trains (streamed
    must drop — the raw f32 copies never exist), ingest wall time, and the
    final train logloss; the streamed arm additionally records ingest
    throughput (the tripwire metric), the sketch/bin/H2D phase split from
    the engine's stream stats, and the overlap efficiency — the fraction
    of the smaller of (bin, H2D) hidden behind the other by the double
    buffer. The accuracy contract (|streamed - materialized| final logloss
    <= STREAMING_LOGLOSS_TOL) is recorded as ``logloss_delta_ok`` and a
    violation prints a LOUD stderr line — tests/test_streaming.py pins the
    bound itself; the bench records it at scale.
    """
    import gc

    from xgboost_ray_tpu.engine import TpuEngine
    from xgboost_ray_tpu.params import parse_params
    from xgboost_ray_tpu.stream.reader import array_shard_stream

    rounds = int(os.environ.get("BENCH_STREAM_ROUNDS", "8"))
    chunk_rows = int(os.environ.get(
        "BENCH_STREAM_CHUNK", str(max(4096, x.shape[0] // 16))
    ))
    parsed = parse_params({
        k: v for k, v in base_params.items() if k != "tree_method"
    })

    def binary_logloss(margin):
        p = 1.0 / (1.0 + np.exp(-np.asarray(margin, np.float64).ravel()))
        p = np.clip(p, 1e-15, 1 - 1e-15)
        return float(-np.mean(y * np.log(p) + (1 - y) * np.log1p(-p)))

    out = {"rounds": rounds}
    logloss = {}
    for arm in ("materialized", "streamed"):
        gc.collect()
        with _RssPeakSampler() as rss:
            t0 = time.time()
            if arm == "streamed":
                shards = [array_shard_stream(x, label=y,
                                             chunk_rows=chunk_rows)]
            else:
                shards = [{"data": x, "label": y}]
            eng = TpuEngine(shards, parsed, num_actors=actors)
            ingest_s = time.time() - t0
            for i in range(rounds):
                eng.step(i)
        margin = eng._fetch_rows(eng.margins, eng.valid, x.shape[0])
        logloss[arm] = binary_logloss(margin)
        arm_out = {
            "rss_peak_delta_mb": round(rss.delta_mb, 1),
            "ingest_s": round(ingest_s, 3),
            "final_logloss": round(logloss[arm], 6),
        }
        if arm == "streamed":
            stats = eng._stream_stats or {}
            arm_out["rows_per_s"] = round(x.shape[0] / max(ingest_s, 1e-9), 1)
            for k in ("chunks", "sketch_s", "bin_s", "transfer_s",
                      "pass2_wall_s", "rank_error_bound_max"):
                if k in stats:
                    arm_out[k] = stats[k]
            bin_s = float(stats.get("bin_s") or 0.0)
            h2d_s = float(stats.get("transfer_s") or 0.0)
            wall2 = float(stats.get("pass2_wall_s") or 0.0)
            hidden = max(0.0, bin_s + h2d_s - wall2)
            denom = max(min(bin_s, h2d_s), 1e-9)
            arm_out["overlap_efficiency"] = round(
                min(1.0, hidden / denom), 3
            )
        out[arm] = arm_out
        del eng
    out["logloss_delta"] = round(
        abs(logloss["streamed"] - logloss["materialized"]), 6
    )
    out["logloss_delta_ok"] = out["logloss_delta"] <= STREAMING_LOGLOSS_TOL
    if not out["logloss_delta_ok"]:
        print(
            f"[bench] STREAMING ACCURACY: streamed final logloss drifted "
            f"{out['logloss_delta']} from materialized "
            f"(tolerance {STREAMING_LOGLOSS_TOL}) — the sketch path's cuts "
            f"moved; see the streaming runbook in README.",
            file=sys.stderr,
        )
    out["rss_drop_ok"] = (
        out["streamed"]["rss_peak_delta_mb"]
        < out["materialized"]["rss_peak_delta_mb"]
    )
    out["config"] = {
        "rows": int(x.shape[0]),
        "features": int(x.shape[1]),
        "rounds": rounds,
        "chunk_rows": chunk_rows,
        "actors": actors,
        "max_depth": int(parsed.max_depth),
    }
    return out


#: --large drift guard: >20% steady per-round regression of the composed
#: (streamed x int8-gh x int8_block-wire) arm across snapshots
LARGE_TRIPWIRE_RATIO = 1.2
#: --large accuracy envelope, RELATIVE to the f32 reference logloss: the
#: composed arm carries int8-granularity wire rounding, which sits ~2e-3
#: relative from f32 at the 200k/10-round protocol (row and block scales
#: alike — the 5e-4 ABSOLUTE bound is pinned where it physically holds:
#: int16_block vs f32 and block-vs-row, tests/test_hist_quant.py). The
#: relative gate catches the failure mode that matters at scale: the
#: composed pipeline drifting from "tracks f32" to "trains a different
#: model".
LARGE_LOGLOSS_REL_TOL = 5e-3


def _meminfo_available_mb():
    """MemAvailable from /proc/meminfo in MB, or None off-Linux."""
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemAvailable:"):
                    return int(line.split()[1]) // 1024
    except OSError:
        pass
    return None


def _synthetic_higgs_stream(n_rows, n_feat, seed=0, chunk_rows=None):
    """A fully synthetic generator-backed ShardStream: ``chunk_fn``
    SYNTHESIZES HIGGS-shaped rows (the make_higgs_like recipe) for
    [lo, hi) on demand, so the full matrix never exists on the host —
    peak host memory is O(chunk), which is what lets --large reach rows
    that a materialized ``make_higgs_like`` array could not.

    Rows are generated in fixed 65536-row blocks each seeded by
    (seed, block index), so the dataset is a pure function of
    (n_rows, n_feat, seed) — independent of chunk boundaries, identical
    across the two-pass read and across arms."""
    from xgboost_ray_tpu.stream.reader import ShardStream, StreamConfig

    block = 65536

    def _block(bi):
        rng = np.random.RandomState((int(seed) * 1000003 + bi) % (2 ** 31))
        lo = bi * block
        rows = min(block, n_rows - lo)
        bx = rng.standard_normal(size=(rows, n_feat)).astype(np.float32)
        logits = (0.8 * bx[:, 0] - 0.6 * bx[:, 1]
                  + 0.4 * bx[:, 2] * bx[:, 3] + 0.3 * bx[:, 4])
        by = (logits + rng.standard_normal(rows).astype(np.float32)
              > 0).astype(np.float32)
        return bx, by

    def chunk_fn(lo, hi):
        xs, ys = [], []
        for bi in range(lo // block, (hi - 1) // block + 1):
            bx, by = _block(bi)
            s = slice(max(0, lo - bi * block), min(block, hi - bi * block))
            xs.append(bx[s])
            ys.append(by[s])
        return {"data": np.concatenate(xs), "label": np.concatenate(ys)}

    stream = ShardStream(
        n_rows, n_feat, chunk_fn,
        config=StreamConfig(chunk_rows=chunk_rows),
        source_token=("synthetic_higgs", int(n_rows), int(n_feat),
                      int(seed)),
    )
    return {"stream": stream}, chunk_fn


def run_large_measurement():
    """``--large``: the composed-headline run, measured at the size it
    reports. Streams a HIGGS-shaped dataset (11M rows when the host
    allows; auto-scaled DOWN and recorded/printed otherwise, never
    silently) through the full low-precision pipeline — streamed binned
    ingest x gh_precision=int8 x hist_quant=int8_block — against a
    config-identical f32 reference arm on the same synthetic stream.

    Per arm: peak host RSS delta over build+train, per-device peak memory
    when the backend reports it (recorded as unavailable otherwise),
    steady per-round time (min over post-compile rounds), measured wire
    bytes per round, and the final train logloss via chunked predict over
    the regenerated stream (the matrix is never materialized). Gates:
    peak host RSS within the memory budget (2x the binned matrix + 768 MB
    slack by default, BENCH_LARGE_MEM_BUDGET_MB overrides), composed
    logloss within LARGE_LOGLOSS_REL_TOL relative of f32, and the
    composed arm moving strictly fewer wire bytes than the f32 psum."""
    import gc

    import jax

    from xgboost_ray_tpu.engine import TpuEngine
    from xgboost_ray_tpu.params import parse_params

    n_feat = int(os.environ.get("BENCH_FEATURES", 28))
    rounds = int(os.environ.get("BENCH_LARGE_ROUNDS", 20))
    depth = int(os.environ.get("BENCH_DEPTH", 6))
    actors = int(os.environ.get("BENCH_ACTORS",
                                max(1, len(jax.devices()))))
    requested = int(os.environ.get("BENCH_LARGE_ROWS", 11_000_000))

    # auto-scale rows to the host: the streamed pipeline's resident set is
    # ~(1 binned byte per feature + bookkeeping) per row; cap the run so
    # the estimate stays under 40% of MemAvailable. NEVER silent: the
    # requested and actual row counts are both recorded and printed.
    avail_mb = _meminfo_available_mb()
    est_bytes_per_row = n_feat + 64
    rows = requested
    if avail_mb is not None:
        cap = int(avail_mb * 0.4 * 2 ** 20 / est_bytes_per_row)
        rows = min(requested, cap)
    if rows < requested:
        print(
            f"[bench] --large AUTO-SCALED: host MemAvailable "
            f"{avail_mb} MB supports ~{rows} rows at "
            f"{est_bytes_per_row} B/row estimated; requested {requested}. "
            f"Running the MEASURED smaller shape — figures below are real "
            f"measurements at rows={rows}, not the requested scale.",
            file=sys.stderr,
        )
    chunk_rows = int(os.environ.get(
        "BENCH_LARGE_CHUNK", str(max(65536, rows // 64))
    ))
    binned_mb = rows * n_feat / 2 ** 20
    budget_mb = float(os.environ.get(
        "BENCH_LARGE_MEM_BUDGET_MB", str(2.0 * binned_mb + 768.0)
    ))

    base = {
        "objective": "binary:logistic",
        "eval_metric": ["logloss"],
        "max_depth": depth,
        "eta": 0.1,
        "max_bin": 256,
    }
    arms = {
        "f32": {},
        "composed": {"gh_precision": "int8", "hist_quant": "int8_block",
                     "hist_quant_min_bytes": 0},
    }
    out = {
        "rows_requested": requested,
        "rows": rows,
        "auto_scaled": rows < requested,
        "features": n_feat,
        "rounds": rounds,
        "actors": actors,
        "chunk_rows": chunk_rows,
        "host_mem_available_mb": avail_mb,
        "mem_budget_mb": round(budget_mb, 1),
    }

    def _device_peak_mb():
        peaks = []
        for d in jax.devices():
            try:
                stats = d.memory_stats()
            except Exception:
                stats = None
            if stats and stats.get("peak_bytes_in_use"):
                peaks.append(stats["peak_bytes_in_use"])
        if peaks:
            return round(sum(peaks) / 2 ** 20, 1)
        return None

    ll_exact = {}
    for name, extra in arms.items():
        gc.collect()
        shard, chunk_fn = _synthetic_higgs_stream(
            rows, n_feat, seed=0, chunk_rows=chunk_rows
        )
        parsed = parse_params(dict(base, **extra))
        with _RssPeakSampler() as rss:
            t0 = time.time()
            eng = TpuEngine([shard], parsed, num_actors=actors)
            ingest_s = time.time() - t0
            round_s = []
            for i in range(rounds):
                r0 = time.time()
                eng.step(i)
                round_s.append(time.time() - r0)
        train_s = sum(round_s)
        # chunked logloss over the regenerated stream: predict per chunk,
        # accumulate the sum — the matrix is never materialized
        bst = eng.get_booster()
        n_seen, ll_sum = 0, 0.0
        for lo in range(0, rows, chunk_rows):
            hi = min(lo + chunk_rows, rows)
            fields = chunk_fn(lo, hi)
            margin = np.asarray(
                bst.predict(fields["data"], output_margin=True), np.float64
            ).ravel()
            p = np.clip(1.0 / (1.0 + np.exp(-margin)), 1e-15, 1 - 1e-15)
            cy = fields["label"].astype(np.float64)
            ll_sum += float(-np.sum(cy * np.log(p)
                                    + (1 - cy) * np.log1p(-p)))
            n_seen += hi - lo
        ll_exact[name] = ll_sum / max(1, n_seen)
        arm_out = {
            "ingest_s": round(ingest_s, 3),
            "train_s": round(train_s, 2),
            "steady_per_round_s": round(min(round_s[1:]) if len(round_s) > 1
                                        else round_s[0], 4),
            "rss_peak_delta_mb": round(rss.delta_mb, 1),
            "final_logloss": round(ll_exact[name], 6),
        }
        dev_mb = _device_peak_mb()
        arm_out["device_peak_mb"] = (
            dev_mb if dev_mb is not None else "unavailable"
        )
        wire = eng.hist_allreduce_bytes_per_round()
        if wire is not None:
            arm_out["hist_allreduce_bytes_per_round"] = int(wire)
        gh = getattr(eng, "gh_plane_bytes_per_shard", None)
        if callable(gh):
            arm_out["gh_plane_bytes_per_shard"] = int(gh())
        out[name] = arm_out
        del eng
    # gates — all three recorded, all three loud on failure
    peak = max(out["f32"]["rss_peak_delta_mb"],
               out["composed"]["rss_peak_delta_mb"])
    out["mem_budget_ok"] = peak <= budget_mb
    if not out["mem_budget_ok"]:
        print(
            f"[bench] LARGE MEMORY over budget: peak host RSS delta "
            f"{peak} MB exceeds the {budget_mb:.0f} MB budget "
            f"(2x binned + slack) — a full-f32 materialization has crept "
            f"into the streamed path.",
            file=sys.stderr,
        )
    delta = ll_exact["composed"] - ll_exact["f32"]
    out["logloss_delta"] = round(delta, 6)
    rel = abs(delta) / max(abs(ll_exact["f32"]), 1e-9)
    out["logloss_rel_delta"] = round(rel, 6)
    out["logloss_ok"] = rel <= LARGE_LOGLOSS_REL_TOL
    if not out["logloss_ok"]:
        print(
            f"[bench] LARGE LOGLOSS drift: composed arm differs from f32 "
            f"by {rel:.2%} relative (> {LARGE_LOGLOSS_REL_TOL:.1%}) — the "
            f"low-precision composition is no longer tracking the "
            f"reference; fall back per the README runbook.",
            file=sys.stderr,
        )
    wb_f32 = out["f32"].get("hist_allreduce_bytes_per_round")
    wb_comp = out["composed"].get("hist_allreduce_bytes_per_round")
    if wb_f32 and wb_comp:
        out["wire_bytes_cut"] = round(wb_f32 / wb_comp, 2)
        out["wire_bytes_ok"] = wb_comp < wb_f32
        if not out["wire_bytes_ok"]:
            print(
                f"[bench] LARGE WIRE BYTES: composed arm moved {wb_comp} "
                f"B/round vs the f32 psum's {wb_f32} — the quantized ring "
                f"lost its cut.",
                file=sys.stderr,
            )
    out["config"] = {
        "rows": rows, "features": n_feat, "rounds": rounds,
        "actors": actors, "max_depth": depth, "chunk_rows": chunk_rows,
        "arm_modes": [
            [k, v.get("gh_precision", "float32"),
             v.get("hist_quant", "none")] for k, v in arms.items()
        ],
    }
    print(f"[bench] large measurement: {out}", file=sys.stderr)
    return out


def large_tripwire(current_large, prev_rec, prev_name=None, backend=None,
                   threshold=LARGE_TRIPWIRE_RATIO):
    """Compare this run's composed-arm steady per-round time against the
    newest recorded bench's ``large`` section. Same shape as the other
    tripwires: None when no comparable record exists (records predating
    --large simply lack the section), like-for-like config only."""
    if not isinstance(current_large, dict):
        return None
    cur = (current_large.get("composed") or {}).get("steady_per_round_s")
    if not cur or not isinstance(prev_rec, dict):
        return None
    if backend and prev_rec.get("backend") and prev_rec["backend"] != backend:
        return None
    prev_sec = prev_rec.get("large")
    if not isinstance(prev_sec, dict):
        return None
    prev = (prev_sec.get("composed") or {}).get("steady_per_round_s")
    if not prev:
        return None
    ratio = float(cur) / float(prev)
    out = {
        "prev_per_round_s": round(float(prev), 4),
        "prev_record": prev_name,
        "ratio": round(ratio, 3),
        "fired": False,
    }
    if prev_sec.get("config") != current_large.get("config"):
        out["config_mismatch"] = True
        return out
    if ratio > threshold:
        out["fired"] = True
        print(
            f"[bench] LARGE TRIPWIRE: composed-arm steady per-round time "
            f"{cur:.4f}s is {ratio:.2f}x the newest recorded run "
            f"({prev:.4f}s in {prev_name or 'BENCH_*.json'}) — "
            f">{(threshold - 1) * 100:.0f}% regression at the headline "
            f"scale; investigate before trusting this build's large-run "
            f"numbers.",
            file=sys.stderr,
        )
    return out


def wide_feature_round_time_tripwire(current_wide, prev_rec, prev_name=None,
                                     backend=None,
                                     threshold=WIDE_FEATURE_TRIPWIRE_RATIO):
    """Compare this run's (4,2) 2D-mesh arm steady per-round time against
    the newest recorded bench's ``wide_feature`` section.

    The feature-parallel analog of ``sampling_round_time_tripwire``:
    returns ``{prev_per_round_s, prev_record, ratio, fired}`` or None when
    no comparable record exists. Like-for-like only (config key)."""
    if not isinstance(current_wide, dict):
        return None
    cur = (current_wide.get("2d") or {}).get("per_round_s")
    if not cur or not isinstance(prev_rec, dict):
        return None
    if backend and prev_rec.get("backend") and prev_rec["backend"] != backend:
        return None
    prev_wide = prev_rec.get("wide_feature")
    if not isinstance(prev_wide, dict):
        return None
    prev = (prev_wide.get("2d") or {}).get("per_round_s")
    if not prev:
        return None
    ratio = float(cur) / float(prev)
    out = {
        "prev_per_round_s": round(float(prev), 4),
        "prev_record": prev_name,
        "ratio": round(ratio, 3),
        "fired": False,
    }
    if prev_wide.get("config") != current_wide.get("config"):
        out["config_mismatch"] = True
        return out
    if ratio > threshold:
        out["fired"] = True
        print(
            f"[bench] WIDE-FEATURE TRIPWIRE: 2D-mesh per-round time "
            f"{cur:.4f}s is {ratio:.2f}x the newest recorded run "
            f"({prev:.4f}s in {prev_name or 'BENCH_*.json'}) — "
            f">{(threshold - 1) * 100:.0f}% regression. The feature-"
            f"parallel win is eroding; investigate before trusting this "
            f"build on wide data.",
            file=sys.stderr,
        )
    return out


def run_wide_feature_ablation(actors=8):
    """Synthetic wide-feature (ads/CTR-shaped) 1D-vs-2D mesh ablation.

    Requires an even ``actors >= 4`` (returns None otherwise): the 2D arm
    runs on ``(actors // 2, 2)``, and with fewer/odd actors the comparison
    degenerates — a (1, 2) mesh has NO actors-axis histogram traffic (ring
    terms are zero on one actor) so the byte-cut gate would pass
    vacuously, and odd counts would compare meshes of different total
    device counts.

    F=2048 sparse-ish columns, the regime ROADMAP item 2 targets: on the
    8-device mesh the same data/params train as (8, 1) pure row sharding
    and as the (4, 2) row x feature mesh (``feature_parallel=2``). Each arm
    records true per-chunk wall times, the steady per-round figure, the
    measured per-chip AllreduceBytes (ring model, from the compiled
    program), and the final train logloss. The section asserts the two
    contracts the 2D mesh ships under: per-round collective bytes cut
    >= WIDE_FEATURE_BYTE_CUT_MIN (the F/C histogram payload win must beat
    the election/broadcast overhead it buys), and logloss parity <= 1e-5
    (feature sharding must not change the model beyond reduction-order
    noise)."""
    from xgboost_ray_tpu import RayDMatrix, RayParams, train

    if actors < 4 or actors % 2:
        print(
            f"[bench] wide-feature ablation skipped: needs an even "
            f"actors >= 4 for a like-for-like (R,1)-vs-(R/2,2) pairing "
            f"(got {actors}).",
            file=sys.stderr,
        )
        return None
    chunk = max(1, int(os.environ.get("RXGB_SCAN_MAX_CHUNK", "10")))
    abl_rounds = int(os.environ.get("BENCH_WIDE_ROUNDS", 2 * chunk))
    n_rows = int(os.environ.get("BENCH_WIDE_ROWS", 4096))
    n_feat = int(os.environ.get("BENCH_WIDE_FEATURES", 2048))
    depth = int(os.environ.get("BENCH_WIDE_DEPTH", 4))
    max_bin = int(os.environ.get("BENCH_WIDE_MAX_BIN", 32))

    rng = np.random.RandomState(11)
    # CTR-shaped: mostly-zero wide columns, a sparse true weight vector
    x = (rng.rand(n_rows, n_feat) < 0.1).astype(np.float32)
    x *= rng.rand(n_rows, n_feat).astype(np.float32)
    w_true = rng.randn(n_feat).astype(np.float32) * (rng.rand(n_feat) < 0.05)
    y = ((x @ w_true + 0.2 * rng.randn(n_rows)) > 0).astype(np.float32)

    base = {
        "objective": "binary:logistic",
        "max_depth": depth,
        "max_bin": max_bin,
        "eta": 0.1,
        "tree_method": "tpu_hist",
    }
    arms = {
        "1d": (dict(base), actors),                          # (8, 1)
        "2d": ({**base, "feature_parallel": 2}, actors // 2),  # (4, 2)
    }

    def binary_logloss(margin):
        p = 1.0 / (1.0 + np.exp(-np.asarray(margin, np.float64).ravel()))
        p = np.clip(p, 1e-15, 1 - 1e-15)
        return float(-np.mean(y * np.log(p) + (1 - y) * np.log1p(-p)))

    out = {"rounds": abl_rounds}
    ll_exact = {}  # unrounded per-arm loglosses: the parity gate's inputs
    for name, (p, arm_actors) in arms.items():
        res = {}
        t0 = time.time()
        bst = train(
            p,
            RayDMatrix(x, y),
            num_boost_round=abl_rounds,
            additional_results=res,
            ray_params=RayParams(
                num_actors=arm_actors, checkpoint_frequency=0
            ),
        )
        arm_time = time.time() - t0
        per_round = _steady_per_round(
            res.get("round_times_s"), chunk, arm_time, abl_rounds
        )
        ll_exact[name] = binary_logloss(bst.predict(x, output_margin=True))
        arm = {
            "mesh": [arm_actors, p.get("feature_parallel", 1)],
            "per_round_s": round(per_round, 4),
            "train_time_s": round(arm_time, 2),
            # true per-dispatch wall times, NOT the replicated chunk mean
            "chunk_times_s": res.get("chunk_times_s"),
            "final_logloss": round(ll_exact[name], 6),
        }
        ar_bytes = res.get("hist_allreduce_bytes_per_round")
        if ar_bytes is not None:
            arm["allreduce_bytes_per_round"] = int(ar_bytes)
        out[name] = arm
    b1 = out["1d"].get("allreduce_bytes_per_round")
    b2 = out["2d"].get("allreduce_bytes_per_round")
    if b1 and b2:
        # the gate reads the UNROUNDED ratio; the stored value is display
        out["allreduce_bytes_cut"] = round(b1 / b2, 2)
        out["byte_cut_ok"] = (b1 / b2) >= WIDE_FEATURE_BYTE_CUT_MIN
        if not out["byte_cut_ok"]:
            print(
                f"[bench] WIDE-FEATURE BYTE CUT below floor: (4,2) moves "
                f"only {out['allreduce_bytes_cut']}x fewer bytes than "
                f"(8,1) (floor {WIDE_FEATURE_BYTE_CUT_MIN}x).",
                file=sys.stderr,
            )
    if out["1d"]["per_round_s"]:
        out["2d_per_round_vs_1d"] = round(
            out["2d"]["per_round_s"] / out["1d"]["per_round_s"], 3
        )
    # parity judged on the UNROUNDED per-arm loglosses (rounding the arms
    # first would let a ~1.05e-5 miss slip under the 1e-5 gate); the stored
    # delta is rounded for display only
    ll_delta = ll_exact["2d"] - ll_exact["1d"]
    out["logloss_delta"] = round(ll_delta, 6)
    out["logloss_parity_ok"] = abs(ll_delta) <= 1e-5
    if not out["logloss_parity_ok"]:
        print(
            f"[bench] WIDE-FEATURE LOGLOSS PARITY broken: (4,2) final "
            f"logloss differs from (8,1) by {out['logloss_delta']} "
            f"(> 1e-5).",
            file=sys.stderr,
        )
    out["config"] = {
        "rows": n_rows, "features": n_feat, "rounds": abl_rounds,
        "max_depth": depth, "max_bin": max_bin, "actors": actors,
        "mesh_1d": out["1d"]["mesh"], "mesh_2d": out["2d"]["mesh"],
    }
    print(f"[bench] wide-feature ablation: {out}", file=sys.stderr)
    return out


def hpo_cost_ratio_tripwire(current_hpo, prev_rec=None, prev_name=None,
                            backend=None, gate=HPO_COST_RATIO_GATE,
                            threshold=HPO_TRIPWIRE_RATIO):
    """Check the vmapped-K-vs-sequential HPO pairing against its gate.

    Like ``obs_overhead_tripwire``, the tracked figure (``cost_ratio`` =
    vmapped-K=4 total wall over 4 sequential trials) is a within-run
    pairing, so the tripwire fires on the CURRENT run's own gate violation
    (cost_ratio >= HPO_COST_RATIO_GATE) — no prior snapshot needed. When
    the newest recorded bench carries a comparable ``hpo`` section (same
    backend, same config), the >20% cross-snapshot drift check applies on
    top. Returns ``{cost_ratio, gate, fired, ...}`` or ``None`` when the
    current section has no ratio (an arm failed to measure)."""
    if not isinstance(current_hpo, dict):
        return None
    cur = current_hpo.get("cost_ratio")
    if not cur:
        return None
    out = {
        "cost_ratio": round(float(cur), 4),
        "gate": gate,
        "fired": False,
    }
    prev_hpo = prev_rec.get("hpo") if isinstance(prev_rec, dict) else None
    if isinstance(prev_hpo, dict) and prev_hpo.get("cost_ratio"):
        if backend and prev_rec.get("backend") \
                and prev_rec["backend"] != backend:
            prev_hpo = None
        elif prev_hpo.get("config") != current_hpo.get("config"):
            out["config_mismatch"] = True
            prev_hpo = None
    if isinstance(prev_hpo, dict) and prev_hpo.get("cost_ratio"):
        out["prev_cost_ratio"] = round(float(prev_hpo["cost_ratio"]), 4)
        out["prev_record"] = prev_name
        ratio = float(cur) / float(prev_hpo["cost_ratio"])
        out["ratio"] = round(ratio, 3)
        if ratio > threshold:
            out["fired"] = True
            print(
                f"[bench] HPO TRIPWIRE: vmapped-K cost ratio {cur:.3f} is "
                f"{ratio:.2f}x the newest recorded run "
                f"({float(prev_hpo['cost_ratio']):.3f} in "
                f"{prev_name or 'BENCH_*.json'}) — "
                f">{(threshold - 1) * 100:.0f}% regression of the packed-"
                f"program win.",
                file=sys.stderr,
            )
    if float(cur) >= gate:
        out["fired"] = True
        print(
            f"[bench] HPO GATE: vmapped-K=4 total wall is {float(cur):.3f}x "
            f"the 4 sequential trials — over the {gate}x gate. The packed "
            f"program is no longer amortizing compile/dispatch across "
            f"lanes; investigate before trusting vectorized sweeps.",
            file=sys.stderr,
        )
    return out


def run_hpo_ablation(x, y, base_params, actors):
    """Paired HPO measurement: 4 sequential trials vs one vmapped-K=4 run.

    Both arms train the SAME four candidate configs (the protocol params
    with eta swept over 4 values) on the same data. The sequential arm is
    the status-quo sweep — one engine per trial, each paying its own
    compile and dispatching its own per-round program. The vmapped arm
    packs all four candidates as lanes of ONE ``engine.step_vmapped``
    program (``enable_lanes`` on a ``vectorize_params`` pack): one compile,
    one dispatch per round, collectives per-lane-batched. Headline figures:
    trials-per-hour for each arm and ``cost_ratio`` (vmapped wall over
    sequential wall), gated at HPO_COST_RATIO_GATE. The section also
    asserts lane parity: each lane's final train logloss must match its
    sequential twin to 1e-5 (same data, same per-lane params, masks not
    engaged — the lanes ARE the sequential runs, batched)."""
    from xgboost_ray_tpu.engine import TpuEngine
    from xgboost_ray_tpu.params import parse_params, vectorize_params

    k = 4
    rounds = int(os.environ.get("BENCH_HPO_ROUNDS", "8"))
    rows = min(int(x.shape[0]), int(os.environ.get("BENCH_HPO_ROWS", "50000")))
    hx, hy = x[:rows], y[:rows]
    shards = [{"data": hx, "label": hy}]
    evals = [(shards, "train")]
    etas = (0.3, 0.2, 0.1, 0.05)
    configs = []
    for eta in etas:
        cfg = dict(base_params)
        cfg["learning_rate"] = eta
        cfg.pop("eta", None)
        configs.append(cfg)

    def _final_logloss(res):
        return float(res["train"]["logloss"])

    seq_start = time.time()
    seq_ll = []
    for cfg in configs:
        eng = TpuEngine(shards, parse_params(cfg), num_actors=actors,
                        evals=evals)
        for it in range(rounds):
            res = eng.step(it)
        seq_ll.append(_final_logloss(res))
        del eng
    seq_time = time.time() - seq_start

    vm_start = time.time()
    lp = vectorize_params(configs)
    veng = TpuEngine(shards, lp.base, num_actors=actors, evals=evals)
    veng.enable_lanes(lp)
    for it in range(rounds):
        vres = veng.step_vmapped(it)
    vm_ll = [_final_logloss(r) for r in vres]
    vm_time = time.time() - vm_start

    ll_delta = max(abs(a - b) for a, b in zip(seq_ll, vm_ll))
    cost_ratio = vm_time / seq_time if seq_time else None
    out = {
        "k": k,
        "rounds": rounds,
        "sequential": {
            "total_s": round(seq_time, 2),
            "trials_per_hour": round(k / (seq_time / 3600.0), 1),
            "compiles": k,
        },
        "vmapped": {
            "total_s": round(vm_time, 2),
            "trials_per_hour": round(k / (vm_time / 3600.0), 1),
            "compiles": 1,
        },
        "cost_ratio": round(cost_ratio, 4) if cost_ratio else None,
        "gate": HPO_COST_RATIO_GATE,
        "gate_ok": bool(cost_ratio is not None
                        and cost_ratio < HPO_COST_RATIO_GATE),
        # parity judged on the unrounded values (see wide-feature ablation)
        "logloss_max_delta": round(ll_delta, 7),
        "logloss_parity_ok": ll_delta <= 1e-5,
        "config": {
            "rows": rows, "features": int(x.shape[1]), "rounds": rounds,
            "actors": actors, "k": k, "etas": list(etas),
            "max_depth": int(base_params.get("max_depth", 6)),
        },
    }
    if not out["logloss_parity_ok"]:
        print(
            f"[bench] HPO LANE PARITY broken: max per-lane final-logloss "
            f"delta vmapped-vs-sequential is {out['logloss_max_delta']} "
            f"(> 1e-5).",
            file=sys.stderr,
        )
    print(f"[bench] hpo ablation: {out}", file=sys.stderr)
    return out


def r4_paired_recheck(detail):
    """Close the r4->r5 "52% CPU-bench regression" open item with DATA.

    The recorded BENCH_r04 -> BENCH_r05 delta (0.76 -> 1.44 s/round, 1.89x)
    came from captures on different machines/load; the r6 bisect re-ran
    both snapshots on one machine and saw parity (see the REGRESSION NOTE
    above). This section adds the in-process control: the hist_quant
    ablation's "none" arm and the sampling ablation's "full" arm are the
    SAME protocol config measured minutes apart in the SAME process — their
    pair ratio bounds same-environment run-to-run variance. A recorded
    1.89x delta far outside that band is environmental capture noise, not
    code; the verdict lands in the BENCH snapshot for the open item."""
    quant = detail.get("hist_quant_ablation") or {}
    samp = detail.get("sampling") or {}
    a = (quant.get("none") or {}).get("per_round_s")
    b = (samp.get("full") or {}).get("per_round_s")
    if not a or not b:
        return None
    pair_ratio = max(a, b) / min(a, b)
    recorded = 1.89  # BENCH_r04 0.7628 -> BENCH_r05 1.4421 s/round
    out = {
        "pair_a_per_round_s": round(float(a), 4),
        "pair_b_per_round_s": round(float(b), 4),
        "pair_ratio": round(pair_ratio, 3),
        "recorded_r4_r5_ratio": recorded,
        "verdict": (
            "environmental"
            if recorded > pair_ratio * TRIPWIRE_RATIO
            else "inconclusive"
        ),
        "note": (
            "pair = same protocol config re-measured minutes apart in one "
            "process (quant-ablation none arm vs sampling-ablation full "
            "arm); recorded r4->r5 delta far outside the pair band => "
            "capture-environment noise, closing VERDICT r5 open item"
        ),
    }
    print(f"[bench] r4 regression recheck: {out}", file=sys.stderr)
    return out


def _timeline_recovery_s(timeline):
    """Failure→recovery seconds reconstructed from a run's trace timeline
    (``obs.recovery_time_s``), or None when the run produced no timeline
    (tracing disabled) so callers can fall back to the robustness dict."""
    if not timeline:
        return None
    from xgboost_ray_tpu import obs

    return round(obs.recovery_time_s(timeline), 4)


def _timeline_fault_events(timeline):
    """The chaos story as the timeline tells it: the ordered
    ``fault.injected`` / ``failure.detected`` / ``world.shrink`` /
    ``world.grow`` / ``world.restart`` / ``recovered`` events with their
    round indices — the machine-readable sequence the BENCH snapshot
    records instead of a prose description of what the soak did."""
    names = {
        "fault.injected", "failure.detected", "world.shrink", "world.grow",
        "world.restart", "recovered", "backoff", "world.domain_down",
        "world.domain_up", "world.deaths_coalesced",
    }
    out = []
    for rec in timeline or []:
        if rec.get("kind") != "event" or rec.get("name") not in names:
            continue
        row = {"event": rec["name"]}
        if "round" in rec:
            row["round"] = rec["round"]
        attrs = rec.get("attrs") or {}
        for k in ("world", "ranks", "site", "action", "orphaned_rows",
                  "domain", "extra"):
            if k in attrs:
                row[k] = attrs[k]
        out.append(row)
    return out


@contextlib.contextmanager
def _immediate_reintegration_env():
    """Zero the elastic scheduler's resource-check/grace knobs for the
    scope (the immediate-reintegration posture every continue arm runs
    under), restoring the ambient values after — shared by the base
    restart-vs-continue pairing and the per-config arms so the two cannot
    drift on which knobs define 'continue'."""
    saved = {}
    for k in ("RXGB_ELASTIC_RESTART_RESOURCE_CHECK_S",
              "RXGB_ELASTIC_RESTART_GRACE_PERIOD_S"):
        saved[k] = os.environ.get(k)
        os.environ[k] = "0"
    try:
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _continue_vs_restart_block(restart_ttr, cont_ttr, label):
    """The tripwire-tracked pairing dict (or None when either recovery is
    unmeasured), with the shared not-faster warning — ONE definition of
    the ratio semantics for the base pairing and every per-config arm."""
    if not restart_ttr or not cont_ttr:
        return None
    ratio = round(cont_ttr / restart_ttr, 4)
    if ratio >= 1.0:
        print(
            f"[bench] WARNING: {label} elastic continuation recovered in "
            f"{cont_ttr:.2f}s, NOT faster than the restart-from-checkpoint "
            f"policy ({restart_ttr:.2f}s) — the zero-replay path has lost "
            f"its edge.",
            file=sys.stderr,
        )
    return {
        "restart_time_to_recover_s": restart_ttr,
        "continue_time_to_recover_s": cont_ttr,
        "ratio": ratio,
        "continue_faster": ratio < 1.0,
    }


def run_chaos_measurement():
    """Deterministic chaos soak on the ambient mesh: one training run with a
    mid-run rank kill plus a straggler delay (driven by a ``FaultPlan``, no
    sleep-and-kill races), checked bit-identical against the uninterrupted
    run; then a corrupt-newest-checkpoint resume through the retention
    fallback. Returns the ``chaos`` section: time-to-recover, rounds
    replayed, restart count, and the two identity verdicts."""
    import tempfile

    import jax

    from xgboost_ray_tpu import RayDMatrix, RayParams, faults, train
    from xgboost_ray_tpu.launcher import (
        load_round_checkpoint,
        save_round_checkpoint,
    )

    n_rows = int(os.environ.get("BENCH_CHAOS_ROWS", 20_000))
    rounds = int(os.environ.get("BENCH_CHAOS_ROUNDS", 12))
    actors = int(os.environ.get("BENCH_CHAOS_ACTORS",
                                max(1, len(jax.devices()))))
    straggle_s = float(os.environ.get("BENCH_CHAOS_STRAGGLE_S", 0.25))
    # kill on an ODD round: with checkpoint_frequency=2 the newest
    # checkpoint then trails the kill by one round, so the soak measurably
    # replays work (rounds_replayed >= 1) instead of resuming for free
    kill_round = max(1, rounds // 3) | 1
    straggle_round = max(kill_round + 1, (2 * rounds) // 3)
    # short, bounded backoff: the soak measures recovery, not the storm guard
    os.environ.setdefault("RXGB_RESTART_BACKOFF_BASE_S", "0.05")

    x, y = make_higgs_like(n_rows, 28, seed=2)
    params = {
        "objective": "binary:logistic", "eval_metric": ["logloss"],
        "max_depth": 6, "eta": 0.1, "max_bin": 256,
        "tree_method": "tpu_hist",
    }
    print(
        f"[bench] chaos soak: rows={n_rows} rounds={rounds} actors={actors} "
        f"kill@r{kill_round} straggle@r{straggle_round} (+{straggle_s}s)",
        file=sys.stderr,
    )

    # uninterrupted reference — run it under a never-firing plan targeting
    # the same site so BOTH runs take the per-round path (bit-identity must
    # not compare a fused-scan forest against a per-round one)
    noop_plan = faults.FaultPlan(rules=[{
        "site": "actor.train_round", "action": "raise",
        "match": {"round": -1},
    }])
    with faults.active_plan(noop_plan):
        ref = train(
            params, RayDMatrix(x, y), rounds,
            ray_params=RayParams(num_actors=actors, checkpoint_frequency=2),
        )
    ref_margin = ref.predict(x, output_margin=True)

    plan = faults.FaultPlan(rules=[
        {"site": "actor.train_round", "action": "raise",
         "match": {"round": kill_round}, "ranks": [actors - 1],
         "message": "chaos: scheduled rank kill"},
        {"site": "actor.train_round", "action": "delay",
         "match": {"round": straggle_round}, "delay_s": straggle_s},
    ])
    res = {}
    soak_started = time.time()
    with faults.active_plan(plan):
        bst = train(
            params, RayDMatrix(x, y), rounds,
            additional_results=res,
            ray_params=RayParams(num_actors=actors, checkpoint_frequency=2,
                                 max_actor_restarts=2),
        )
    soak_s = time.time() - soak_started
    rob = res.get("robustness", {})
    # recovery numbers come from the RUN TIMELINE, not the robustness dict:
    # each "recovered" event closes the clock its "failure.detected" opened
    # (obs.recovery_time_s mirrors the driver's accounting — the dict value
    # is kept alongside as a cross-check; the two must agree)
    soak_timeline = (res.get("obs") or {}).get("timeline") or []
    ttr_timeline = _timeline_recovery_s(soak_timeline)
    # the restart recomputes resume margins from the checkpoint forest — a
    # different f32 summation order than the uninterrupted run's incremental
    # accumulation — so the match is pinned at atol=1e-5 (NOT bitwise), with
    # the observed max divergence reported alongside (structural drift shows
    # up as >> 1e-5). Chaos-vs-chaos reruns of the same plan ARE bitwise
    # identical (pinned by tests/test_faults.py).
    chaos_margin = bst.predict(x, output_margin=True)
    model_max_abs_diff = float(np.max(np.abs(chaos_margin - ref_margin)))
    model_matches = bool(np.allclose(chaos_margin, ref_margin, atol=1e-5))

    # corrupt-newest-checkpoint resume: bank two retained checkpoints from
    # the reference forest, corrupt the newest via the checkpoint.save fault
    # site, and resume through the retention fallback to the full model
    with tempfile.TemporaryDirectory() as td:
        ckpt = os.path.join(td, "ckpt.json")
        k = rounds - 2
        corrupt_plan = faults.FaultPlan(rules=[{
            "site": "checkpoint.save", "action": "corrupt", "at": 2,
            "nbytes": 64,
        }], seed=13)
        with faults.active_plan(corrupt_plan):
            save_round_checkpoint(ref.slice_rounds(0, k - 1), ckpt, k - 2)
            save_round_checkpoint(ref.slice_rounds(0, k), ckpt, k - 1)
        fb, fb_rounds = load_round_checkpoint(ckpt)
        resume_matches = False
        if fb is not None:
            noop_plan.reset()
            with faults.active_plan(noop_plan):  # per-round path, as above
                resumed = train(
                    params, RayDMatrix(x, y), rounds - fb_rounds,
                    xgb_model=fb,
                    ray_params=RayParams(num_actors=actors,
                                         checkpoint_frequency=0),
                )
            # the on-disk JSON roundtrip is not bit-exact (float reprs), so
            # the file-resume check uses the same tolerance as the
            # launcher resume test (1e-4, vs the soak's in-memory 1e-5)
            resume_matches = bool(np.allclose(
                resumed.predict(x, output_margin=True), ref_margin,
                atol=1e-4,
            ))

    section = {
        "restarts": rob.get("restarts", 0),
        "rounds_replayed": rob.get("rounds_replayed", 0),
        "time_to_recover_s": (
            ttr_timeline if ttr_timeline is not None
            else rob.get("time_to_recover_s", 0.0)
        ),
        "recovery_source": (
            "timeline" if ttr_timeline is not None else "robustness_dict"
        ),
        "time_to_recover_robustness_s": rob.get("time_to_recover_s", 0.0),
        "fault_events": _timeline_fault_events(soak_timeline),
        "backoff_s": rob.get("backoff_s", 0.0),
        "soak_train_time_s": round(soak_s, 2),
        "model_matches": model_matches,  # vs uninterrupted, atol=1e-5
        "model_max_abs_diff": model_max_abs_diff,
        "ckpt_fallback_rounds": fb_rounds,
        "ckpt_resume_matches": resume_matches,  # vs uninterrupted, atol=1e-4
        "config": {
            "rows": n_rows, "rounds": rounds, "actors": actors,
            "kill_round": kill_round, "straggle_round": straggle_round,
            "straggle_s": straggle_s, "max_depth": 6,
        },
    }

    # paired restart-vs-continue: the SAME kill schedule once more, now with
    # elastic in-flight continuation (immediate reintegration: resource
    # check + grace period zeroed) — recovery must be strictly faster than
    # the restart-from-checkpoint policy measured above, with ZERO rounds
    # replayed; the final model stays within the soak tolerance of the
    # uninterrupted run (the kill fires before the round's step, so no
    # survivor-world round is ever boosted).
    if actors >= 2:
        cont_plan = faults.FaultPlan(rules=[
            {"site": "actor.train_round", "action": "raise",
             "match": {"round": kill_round}, "ranks": [actors - 1],
             "message": "chaos: scheduled rank kill"},
            {"site": "actor.train_round", "action": "delay",
             "match": {"round": straggle_round}, "delay_s": straggle_s},
        ])
        res_cont = {}
        with _immediate_reintegration_env():
            with faults.active_plan(cont_plan):
                bst_cont = train(
                    params, RayDMatrix(x, y), rounds,
                    additional_results=res_cont,
                    ray_params=RayParams(
                        num_actors=actors, checkpoint_frequency=2,
                        elastic_training=True,
                        max_failed_actors=actors - 1,
                        max_actor_restarts=2,
                    ),
                )
        rob_c = res_cont.get("robustness", {})
        cont_timeline = (res_cont.get("obs") or {}).get("timeline") or []
        cont_ttr_timeline = _timeline_recovery_s(cont_timeline)
        cont_ttr = (
            cont_ttr_timeline if cont_ttr_timeline is not None
            else rob_c.get("time_to_recover_s", 0.0)
        )
        restart_ttr = section["time_to_recover_s"]
        cont_matches = bool(np.allclose(
            bst_cont.predict(x, output_margin=True), ref_margin, atol=1e-5
        ))
        section["elastic"] = {
            "time_to_recover_s": cont_ttr,
            "recovery_source": (
                "timeline" if cont_ttr_timeline is not None
                else "robustness_dict"
            ),
            "time_to_recover_robustness_s": rob_c.get(
                "time_to_recover_s", 0.0
            ),
            "rounds_replayed": rob_c.get("rounds_replayed", 0),
            "restarts": rob_c.get("restarts", 0),
            "shrinks": rob_c.get("shrinks", 0),
            "grows": rob_c.get("grows", 0),
            "orphaned_rows": rob_c.get("orphaned_rows", 0),
            "recompile_s": rob_c.get("recompile_s", 0.0),
            "model_matches": cont_matches,  # vs uninterrupted, atol=1e-5
            # the kill→shrink→grow (or immediate-reintegration) sequence as
            # the timeline recorded it, round indices included
            "fault_events": _timeline_fault_events(cont_timeline),
        }
        cvr = _continue_vs_restart_block(restart_ttr, cont_ttr, "base")
        if cvr is not None:
            section["continue_vs_restart"] = cvr
    # per-config pairings: the SAME restart-vs-continue experiment over the
    # configurations that used to be fallback cases — the 2D row x feature
    # mesh and the streamed (out-of-core) matrix. Each arm runs its own
    # uninterrupted reference, a kill under the restart-from-checkpoint
    # policy, and the same kill under elastic in-flight continuation; the
    # continue_vs_restart ratios feed elastic_recovery_tripwire alongside
    # the base pairing.
    if actors >= 2:
        arm_rows = int(os.environ.get("BENCH_CHAOS_ARM_ROWS",
                                      min(n_rows, 8_000)))
        arm_rounds = int(os.environ.get("BENCH_CHAOS_ARM_ROUNDS", rounds))
        arm_kill = max(1, arm_rounds // 3) | 1
        ax, ay = make_higgs_like(arm_rows, 28, seed=3)
        actors_2d = max(2, actors // 2)
        if actors_2d * 2 <= len(jax.devices()):
            section["elastic_2d"] = _paired_continue_vs_restart(
                label="2d",
                params={**params, "feature_parallel": 2},
                make_dmatrix=lambda: RayDMatrix(ax, ay),
                x=ax,
                rounds=arm_rounds, actors=actors_2d, kill_round=arm_kill,
                config={"rows": arm_rows, "rounds": arm_rounds,
                        "actors": actors_2d, "feature_parallel": 2,
                        "kill_round": arm_kill, "max_depth": 6},
            )
        chunk_rows = max(256, arm_rows // 8)
        section["elastic_streamed"] = _paired_continue_vs_restart(
            label="streamed",
            params=params,
            make_dmatrix=lambda: RayDMatrix(
                ax, ay, stream=True, chunk_rows=chunk_rows
            ),
            x=ax,
            rounds=arm_rounds, actors=actors, kill_round=arm_kill,
            config={"rows": arm_rows, "rounds": arm_rounds,
                    "actors": actors, "streamed": True,
                    "chunk_rows": chunk_rows, "kill_round": arm_kill,
                    "max_depth": 6},
        )
        # correlated host loss: a whole fault domain (2 of 4 ranks under
        # RXGB_FAULT_DOMAINS=2) dies at once — the continue arm must fold
        # both deaths into ONE shrink (or one immediate reintegration),
        # never two sequential recompile cycles
        actors_dom = 4
        if actors_dom <= len(jax.devices()):
            section["elastic_domain"] = _paired_continue_vs_restart(
                label="domain",
                params=params,
                make_dmatrix=lambda: RayDMatrix(ax, ay),
                x=ax,
                rounds=arm_rounds, actors=actors_dom, kill_round=arm_kill,
                config={"rows": arm_rows, "rounds": arm_rounds,
                        "actors": actors_dom, "fault_domains": 2,
                        "kill_round": arm_kill, "max_depth": 6},
                kill_rule={"site": "actor.train_round",
                           "action": "domain_kill", "domain": 1,
                           "ranks": [actors_dom - 1],
                           "match": {"round": arm_kill},
                           "message": "chaos: correlated domain kill"},
                extra_env={"RXGB_FAULT_DOMAINS": "2"},
            )
    print(f"[bench] chaos section: {section}", file=sys.stderr)
    return section


def _paired_continue_vs_restart(label, params, make_dmatrix, x, rounds,
                                actors, kill_round, config,
                                kill_rule=None, extra_env=None):
    """One restart-vs-continue pairing for a specific training config: the
    same deterministic kill, once under the restart-from-checkpoint policy
    and once under elastic in-flight continuation (immediate
    reintegration). Returns the arm dict with both recoveries, the
    continue arm's zero-replay/identity verdicts, and the
    ``continue_vs_restart`` ratio the elastic tripwire tracks.

    ``kill_rule`` overrides the default single-rank kill (the
    ``elastic_domain`` arm injects a correlated ``domain_kill`` instead);
    ``extra_env`` sets env vars for BOTH chaos runs (e.g.
    ``RXGB_FAULT_DOMAINS``) so the pairing stays like-for-like."""
    from xgboost_ray_tpu import RayParams, faults, train

    @contextlib.contextmanager
    def _arm_env():
        saved = {}
        for k, v in (extra_env or {}).items():
            saved[k] = os.environ.get(k)
            os.environ[k] = v
        try:
            yield
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    noop = faults.FaultPlan(rules=[{
        "site": "actor.train_round", "action": "raise",
        "match": {"round": -1},
    }])
    with faults.active_plan(noop):
        ref = train(params, make_dmatrix(), rounds,
                    ray_params=RayParams(num_actors=actors,
                                         checkpoint_frequency=2))
    ref_margin = ref.predict(x, output_margin=True)

    def kill_plan():
        return faults.FaultPlan(rules=[dict(kill_rule) if kill_rule else {
            "site": "actor.train_round", "action": "raise",
            "match": {"round": kill_round}, "ranks": [actors - 1],
            "message": f"chaos: scheduled rank kill ({label})",
        }])

    # restart-from-checkpoint policy
    res_r = {}
    with _arm_env(), faults.active_plan(kill_plan()):
        bst_r = train(params, make_dmatrix(), rounds, additional_results=res_r,
                      ray_params=RayParams(num_actors=actors,
                                           checkpoint_frequency=2,
                                           max_actor_restarts=2))
    rob_r = res_r.get("robustness", {})
    tl_r = (res_r.get("obs") or {}).get("timeline") or []
    restart_ttr = _timeline_recovery_s(tl_r) or rob_r.get(
        "time_to_recover_s", 0.0
    )

    # elastic in-flight continuation, immediate reintegration
    res_c = {}
    with _arm_env(), _immediate_reintegration_env():
        with faults.active_plan(kill_plan()):
            bst_c = train(params, make_dmatrix(), rounds,
                          additional_results=res_c,
                          ray_params=RayParams(num_actors=actors,
                                               checkpoint_frequency=2,
                                               elastic_training=True,
                                               max_failed_actors=actors - 1,
                                               max_actor_restarts=2))
    rob_c = res_c.get("robustness", {})
    tl_c = (res_c.get("obs") or {}).get("timeline") or []
    cont_ttr = _timeline_recovery_s(tl_c) or rob_c.get(
        "time_to_recover_s", 0.0
    )
    arm = {
        "restart": {
            "time_to_recover_s": restart_ttr,
            "restarts": rob_r.get("restarts", 0),
            "rounds_replayed": rob_r.get("rounds_replayed", 0),
            "model_matches": bool(np.allclose(
                bst_r.predict(x, output_margin=True), ref_margin, atol=1e-5
            )),
        },
        "elastic": {
            "time_to_recover_s": cont_ttr,
            "restarts": rob_c.get("restarts", 0),
            "rounds_replayed": rob_c.get("rounds_replayed", 0),
            "shrinks": rob_c.get("shrinks", 0),
            "grows": rob_c.get("grows", 0),
            "domains_lost": rob_c.get("domains_lost", 0),
            "deaths_coalesced": rob_c.get("deaths_coalesced", 0),
            "model_matches": bool(np.allclose(
                bst_c.predict(x, output_margin=True), ref_margin, atol=1e-5
            )),
            "fault_events": _timeline_fault_events(tl_c),
        },
        "config": config,
    }
    cvr = _continue_vs_restart_block(restart_ttr, cont_ttr, label)
    if cvr is not None:
        arm["continue_vs_restart"] = cvr
    print(f"[bench] chaos {label} pairing: {arm}", file=sys.stderr)
    return arm


def _train_serve_model():
    """Train the small served model once; shared by the paired heap and
    node-array serving arms so both serve the IDENTICAL forest."""
    import jax

    from xgboost_ray_tpu import RayDMatrix, RayParams, train

    n_rows = int(os.environ.get("BENCH_SERVE_TRAIN_ROWS", 20_000))
    rounds = int(os.environ.get("BENCH_SERVE_TRAIN_ROUNDS", 5))
    n_feat = 28
    x, y = make_higgs_like(n_rows, n_feat, seed=1)
    bst = train(
        {"objective": "binary:logistic", "max_depth": 6, "eta": 0.1,
         "max_bin": 256, "tree_method": "tpu_hist"},
        RayDMatrix(x, y), num_boost_round=rounds,
        ray_params=RayParams(num_actors=max(1, len(jax.devices())),
                             checkpoint_frequency=0),
    )
    return bst, x


def run_serve_measurement(layout="heap", trained=None):
    """Closed-loop serving benchmark: train a small model (or reuse
    ``trained`` — the ``_train_serve_model()`` result — for a paired arm),
    serve it over loopback HTTP on the ambient mesh with the requested
    forest ``layout``, drive it with concurrent clients, and return the
    endpoint's /metrics snapshot (plus the loop config) as the ``serve`` /
    ``serve_node_array`` section of the bench record."""
    import json as json_mod
    import threading
    import urllib.request

    import jax

    from xgboost_ray_tpu import serve as serve_mod

    n_rows = int(os.environ.get("BENCH_SERVE_TRAIN_ROWS", 20_000))
    rounds = int(os.environ.get("BENCH_SERVE_TRAIN_ROUNDS", 5))
    clients = int(os.environ.get("BENCH_SERVE_CLIENTS", 16))
    max_batch = int(os.environ.get("BENCH_SERVE_MAX_BATCH", 256))
    max_delay_ms = float(os.environ.get("BENCH_SERVE_MAX_DELAY_MS", 2.0))
    req_rows_max = int(os.environ.get("BENCH_SERVE_REQ_ROWS", 32))
    duration_s = float(os.environ.get("BENCH_SERVE_SECONDS", 6.0))
    warm_s = float(os.environ.get("BENCH_SERVE_WARM_SECONDS", 1.5))

    if trained is None:
        trained = _train_serve_model()
    bst, x = trained
    handle = serve_mod.create_server(
        bst, devices=jax.devices(), max_batch=max_batch,
        max_delay_ms=max_delay_ms, layout=layout,
    )
    print(f"[bench] serve endpoint up at {handle.url} "
          f"(devices={len(jax.devices())} max_batch={max_batch} "
          f"max_delay_ms={max_delay_ms} clients={clients} "
          f"layout={layout})", file=sys.stderr)

    stop = threading.Event()
    errors = []

    def client(seed):
        rng = np.random.RandomState(seed)
        while not stop.is_set():
            n = int(rng.randint(1, req_rows_max + 1))
            lo = int(rng.randint(0, n_rows - n))
            body = json_mod.dumps(
                {"data": x[lo : lo + n].tolist()}
            ).encode("utf-8")
            req = urllib.request.Request(
                handle.url + "/predict", body,
                {"Content-Type": "application/json"},
            )
            try:
                with urllib.request.urlopen(req, timeout=30.0) as r:
                    r.read()
            except Exception as exc:  # noqa: BLE001 - counted, loop on
                if not stop.is_set():
                    errors.append(repr(exc))

    threads = [
        threading.Thread(target=client, args=(i,), daemon=True)
        for i in range(clients)
    ]
    try:
        for t in threads:
            t.start()
        time.sleep(warm_s)  # steady-state only: warmup traffic excluded
        handle.metrics.reset()  # also re-baselines the recompile counter
        del errors[:]  # client_errors must describe the measured window too
        time.sleep(duration_s)
        # recompile_count is since-reset, i.e. inside the measured window
        # (the steady-state claim: this should be 0)
        snap = handle.metrics.snapshot()
    finally:
        stop.set()
        for t in threads:
            t.join(10.0)
        handle.shutdown()
    section = {
        k: snap[k]
        for k in (
            "requests", "rows", "errors", "qps", "rows_per_s", "batches",
            "mean_batch_rows", "padding_waste", "latency_p50_ms",
            "latency_p95_ms", "latency_p99_ms", "latency_mean_ms",
            "recompile_count",
        )
    }
    section["client_errors"] = len(errors)
    section["config"] = {
        "clients": clients,
        "max_batch": max_batch,
        "max_delay_ms": max_delay_ms,
        "req_rows_max": req_rows_max,
        "duration_s": duration_s,
        "devices": len(jax.devices()),
        # served-model size changes per-batch predict cost: part of
        # like-for-like, so a different model never compares as "same run"
        "train_rows": n_rows,
        "train_rounds": rounds,
        "max_depth": 6,
        "layout": layout,
    }
    print(f"[bench] serve closed-loop: {section}", file=sys.stderr)
    return section


def make_higgs_like(n_rows: int, n_features: int, seed: int = 0):
    rng = np.random.RandomState(seed)
    x = rng.standard_normal(size=(n_rows, n_features)).astype(np.float32)
    # learnable structure: a few informative features + mild nonlinearity
    logits = 0.8 * x[:, 0] - 0.6 * x[:, 1] + 0.4 * x[:, 2] * x[:, 3] + 0.3 * x[:, 4]
    y = (logits + rng.standard_normal(n_rows).astype(np.float32) > 0).astype(np.float32)
    return x, y


def _force_cpu_mesh():
    """Point this process at the 8-device virtual CPU mesh. Must run before
    the first jax import: JAX reads both variables at backend start-up."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")


def run_measurement(cpu_counts: bool = False):
    """Run the protocol once in this process and print the JSON line.

    Without ``cpu_counts`` the backend must be a TPU; anything else exits
    non-zero before any work, naming the platform found."""
    import jax

    from xgboost_ray_tpu.util import device_record

    device = device_record()
    backend = jax.default_backend()
    on_tpu = device["platform"] == "tpu"
    if not on_tpu and not cpu_counts:
        print(
            f"[bench] no TPU: JAX found platform {device['platform']!r} "
            f"({device['device_kind']}). The measurement does not fall "
            f"back; `python bench.py --cpu-counts` runs the "
            f"counts-and-correctness mode on the virtual CPU mesh.",
            file=sys.stderr,
        )
        sys.exit(1)

    n_rows = int(os.environ.get("BENCH_ROWS", 11_000_000 if on_tpu else 200_000))
    n_feat = int(os.environ.get("BENCH_FEATURES", 28))
    rounds = int(os.environ.get("BENCH_ROUNDS", 100 if on_tpu else 10))
    depth = int(os.environ.get("BENCH_DEPTH", 6))
    actors = int(os.environ.get("BENCH_ACTORS", max(1, len(jax.devices()))))
    hist_impl = os.environ.get("BENCH_HIST_IMPL", "auto")
    hist_quant = os.environ.get("BENCH_HIST_QUANT", "none")

    print(
        f"[bench] platform={device['platform']} "
        f"device_kind={device['device_kind']} "
        f"device_count={device['device_count']} rows={n_rows} "
        f"features={n_feat} "
        f"rounds={rounds} depth={depth} actors={actors} hist_impl={hist_impl} "
        f"hist_quant={hist_quant} "
        f"scan_chunk={os.environ.get('RXGB_SCAN_MAX_CHUNK', 'default')}",
        file=sys.stderr,
    )

    t0 = time.time()
    x, y = make_higgs_like(n_rows, n_feat)
    print(f"[bench] data generated in {time.time() - t0:.1f}s", file=sys.stderr)

    from xgboost_ray_tpu import RayDMatrix, RayParams, train

    dtrain = RayDMatrix(x, y)
    params = {
        "objective": "binary:logistic",
        "eval_metric": ["logloss"],
        "max_depth": depth,
        "eta": 0.1,
        "max_bin": 256,
        "tree_method": "tpu_hist",
        "hist_impl": hist_impl,
        "hist_quant": hist_quant,
    }

    from xgboost_ray_tpu import progreg

    train_start = time.time()
    additional_results = {}
    # capture the protocol run's compiled-program signatures so the snapshot
    # carries their jaxpr fingerprints (tools/rxgbverify) — a PR that
    # silently changes a compiled program shows up as a fingerprint diff
    # across BENCH_*.json files. Capture costs one early-returning branch
    # per registration site; the abstract re-trace below runs post-timing.
    with progreg.capture():
        progreg.clear()
        bst = train(
            params,
            dtrain,
            num_boost_round=rounds,
            additional_results=additional_results,
            ray_params=RayParams(num_actors=actors, checkpoint_frequency=0),
        )
        train_time = time.time() - train_start
        print(f"[bench] TRAIN TIME TAKEN: {train_time:.2f}s", file=sys.stderr)
        assert bst.num_boosted_rounds() == rounds
        from tools.rxgbverify import fingerprint_registry

        program_fingerprints = fingerprint_registry()
    progreg.clear()  # drop the engine references the records keep alive

    # per-round time series. First chunk carries the compile; the median of
    # the rest is the steady-state marginal.
    rt = additional_results.get("round_times_s") or []
    detail = {}
    if rt:
        chunk = max(1, int(os.environ.get("RXGB_SCAN_MAX_CHUNK", "10")))
        detail = {
            "round_times_s": [round(v, 4) for v in rt],
            "first_chunk_mean_s": round(float(np.mean(rt[:chunk])), 4),
        }
        # true per-dispatch wall times: round_times_s above replicates each
        # fused chunk's MEAN across its rounds (per-round variance inside a
        # chunk is invisible by construction), so the real distribution is
        # recorded separately as [{rounds, seconds}] per compiled dispatch
        chunk_times = additional_results.get("chunk_times_s")
        if chunk_times:
            detail["chunk_times_s"] = chunk_times
        if len(rt) > chunk:
            # steady-state excludes the compile-carrying first chunk; with
            # fewer rounds than one chunk there IS no steady sample — omit
            # rather than mislabel compile time
            steady = rt[chunk:]
            detail["steady_median_s"] = round(float(np.median(steady)), 4)
            detail["steady_p90_s"] = round(float(np.percentile(steady, 90)), 4)
        print(f"[bench] round-time detail: {detail}", file=sys.stderr)

    if program_fingerprints:
        detail["program_fingerprints"] = program_fingerprints
        print(f"[bench] {len(program_fingerprints)} program fingerprints "
              f"recorded", file=sys.stderr)

    # measured collective wire bytes per round (the hist_quant metric; see
    # ops/histogram.py AllreduceBytes for the ring-model accounting)
    ar_bytes = additional_results.get("hist_allreduce_bytes_per_round")
    if ar_bytes is not None:
        detail["hist_allreduce_bytes_per_round"] = int(ar_bytes)

    # regression tripwire vs the newest recorded BENCH_*.json (like-for-like
    # bases only: steady-vs-steady or compile-inclusive-vs-same)
    if detail.get("steady_median_s"):
        current_s, current_basis = detail["steady_median_s"], "steady"
    elif detail.get("first_chunk_mean_s"):
        current_s, current_basis = (
            detail["first_chunk_mean_s"], "compile_inclusive"
        )
    else:
        current_s, current_basis = (
            train_time / max(rounds, 1), "compile_inclusive"
        )
    prev_rec, prev_name = _load_latest_bench_record(
        os.path.dirname(os.path.abspath(__file__))
    )
    trip = round_time_tripwire(current_s, prev_rec, prev_name,
                               backend=backend, current_basis=current_basis)
    if trip is not None:
        detail["regression_tripwire"] = trip

    # hist_quant ablation: paired none-vs-int8 runs measuring wire bytes AND
    # compile-free steady per-round wall clock. Both arms run fresh,
    # back-to-back, for 2 scan chunks so the steady median excludes the
    # compile-carrying first chunk (the protocol run's 10-rounds-in-1-chunk
    # figure conflates compile and steady and would unfairly penalize the
    # bigger int8 program). Default on for the CPU mesh; opt-in on TPU via
    # BENCH_QUANT_ABLATION=1 (it adds two short extra trainings).
    abl_env = os.environ.get("BENCH_QUANT_ABLATION")
    run_ablation = hist_quant == "none" and (
        abl_env == "1" or (abl_env is None and not on_tpu)
    )
    if run_ablation:
        chunk = max(1, int(os.environ.get("RXGB_SCAN_MAX_CHUNK", "10")))
        abl_rounds = int(os.environ.get("BENCH_QUANT_ABLATION_ROUNDS", 2 * chunk))
        arms = {}
        for hq in ("none", "int8"):
            abl_params = dict(params)
            abl_params["hist_quant"] = hq
            abl_results = {}
            abl_start = time.time()
            train(
                abl_params,
                RayDMatrix(x, y),
                num_boost_round=abl_rounds,
                additional_results=abl_results,
                ray_params=RayParams(num_actors=actors, checkpoint_frequency=0),
            )
            abl_time = time.time() - abl_start
            per_round = _steady_per_round(
                abl_results.get("round_times_s"), chunk, abl_time, abl_rounds
            )
            arms[hq] = {
                "per_round_s": round(per_round, 4),
                "train_time_s": round(abl_time, 2),
            }
            abl_bytes = abl_results.get("hist_allreduce_bytes_per_round")
            if abl_bytes is not None:
                arms[hq]["hist_allreduce_bytes_per_round"] = int(abl_bytes)
        abl = {"rounds": abl_rounds, **{k: v for k, v in arms.items()}}
        b_none = arms["none"].get("hist_allreduce_bytes_per_round")
        b_int8 = arms["int8"].get("hist_allreduce_bytes_per_round")
        if b_none and b_int8:
            abl["allreduce_bytes_reduction"] = round(b_none / b_int8, 2)
        if arms["none"]["per_round_s"]:
            abl["int8_per_round_vs_none"] = round(
                arms["int8"]["per_round_s"] / arms["none"]["per_round_s"], 3
            )
        detail["hist_quant_ablation"] = abl
        print(f"[bench] hist_quant ablation: {abl}", file=sys.stderr)

    # full/sampled training ablation (the row-sampling counterpart of the
    # hist_quant ablation: hist_quant cut the wire bytes, the compacted
    # sampled build cuts the per-round FLOPs/HBM feeding them). Default on
    # for the CPU mesh; opt-in on TPU via BENCH_SAMPLING_ABLATION=1.
    samp_env = os.environ.get("BENCH_SAMPLING_ABLATION")
    if samp_env == "1" or (samp_env is None and not on_tpu):
        samp_section = run_sampling_ablation(x, y, params, actors)
        strip = sampling_round_time_tripwire(
            samp_section, prev_rec, prev_name, backend=backend
        )
        if strip is not None:
            samp_section["regression_tripwire"] = strip
        detail["sampling"] = samp_section
        recheck = r4_paired_recheck(detail)
        if recheck is not None:
            detail["r4_regression_recheck"] = recheck

    # low-precision (gh_precision) ablation: f32 vs int16 vs int8 quantized
    # gradients on the protocol data — per-round time, the static gh-plane
    # bytes/shard, and final-logloss deltas with their gates. Default on
    # for the CPU mesh; opt-in on TPU via BENCH_LOW_PRECISION=1.
    lp_env = os.environ.get("BENCH_LOW_PRECISION")
    if lp_env == "1" or (lp_env is None and not on_tpu):
        lp_section = run_low_precision_ablation(x, y, params, actors)
        ltrip = low_precision_tripwire(
            lp_section, prev_rec, prev_name, backend=backend
        )
        if ltrip is not None:
            lp_section["regression_tripwire"] = ltrip
        detail["low_precision"] = lp_section

    # streamed-vs-materialized ingestion ablation (ROADMAP item 1): peak
    # host RSS, ingest wall time, overlap efficiency, and the 5e-4 final-
    # logloss contract, with the >20% ingest-throughput tripwire. Default
    # on for the CPU mesh; opt-in on TPU via BENCH_STREAMING=1.
    stream_env = os.environ.get("BENCH_STREAMING")
    if stream_env == "1" or (stream_env is None and not on_tpu):
        stream_section = run_streaming_ablation(x, y, params, actors)
        strip2 = streaming_ingest_tripwire(
            stream_section, prev_rec, prev_name, backend=backend
        )
        if strip2 is not None:
            stream_section["regression_tripwire"] = strip2
        detail["streaming"] = stream_section
        print(f"[bench] streaming ablation: {stream_section}", file=sys.stderr)

    # wide-feature (F=2048, CTR-shaped) 1D-vs-2D mesh ablation: (8,1) row
    # sharding vs the (4,2) row x feature mesh, recording per-round time,
    # AllreduceBytes, and logloss parity. Default on for the 8-dev CPU
    # mesh; opt-in on TPU via BENCH_WIDE_FEATURE=1.
    wide_env = os.environ.get("BENCH_WIDE_FEATURE")
    if (wide_env == "1" or (wide_env is None and not on_tpu)) and \
            actors >= 4 and actors % 2 == 0:
        wide_section = run_wide_feature_ablation(actors=actors)
        if wide_section is not None:
            wtrip = wide_feature_round_time_tripwire(
                wide_section, prev_rec, prev_name, backend=backend
            )
            if wtrip is not None:
                wide_section["regression_tripwire"] = wtrip
            detail["wide_feature"] = wide_section

    # vectorized-HPO pairing: 4 sequential trials vs one vmapped-K=4
    # program (engine.step_vmapped) on the same data — trials-per-hour for
    # each arm, the cost_ratio gate, and the >20% drift tripwire. Default
    # on for the CPU mesh; opt-in on TPU via BENCH_HPO=1.
    hpo_env = os.environ.get("BENCH_HPO")
    if hpo_env == "1" or (hpo_env is None and not on_tpu):
        hpo_section = run_hpo_ablation(x, y, params, actors)
        htrip = hpo_cost_ratio_tripwire(
            hpo_section, prev_rec, prev_name, backend=backend
        )
        if htrip is not None:
            hpo_section["regression_tripwire"] = htrip
        detail["hpo"] = hpo_section

    # the protocol run's own obs snapshot: per-round span stats, ring-buffer
    # truncation accounting, wire bytes
    obs_res = additional_results.get("obs") or {}
    if obs_res:
        round_durs = [
            r["dur_s"] for r in obs_res.get("rounds") or []
            if r.get("dur_s") is not None
        ]
        obs_section = {
            "rounds_traced": len(round_durs),
            "events": len(obs_res.get("events") or []),
            "dropped_spans": obs_res.get("dropped_spans", 0),
            "capacity": obs_res.get("capacity"),
        }
        if round_durs:
            obs_section["round_dur_mean_s"] = round(
                float(np.mean(round_durs)), 4
            )
            obs_section["round_dur_median_s"] = round(
                float(np.median(round_durs)), 4
            )
        if ar_bytes is not None:
            obs_section["allreduce_bytes_per_round"] = int(ar_bytes)
        detail["obs"] = obs_section
        print(f"[bench] obs snapshot: {obs_section}", file=sys.stderr)

    # instrumentation-overhead pairing (tracing on vs off) with the ≤2%
    # budget tripwire. Default on for the CPU mesh; opt-in on TPU via
    # BENCH_OBS_OVERHEAD=1 (two short extra trainings).
    obs_env = os.environ.get("BENCH_OBS_OVERHEAD")
    if obs_env == "1" or (obs_env is None and not on_tpu):
        obs_overhead = run_obs_overhead(x, y, params, actors)
        otrip = obs_overhead_tripwire(
            obs_overhead, prev_rec, prev_name, backend=backend
        )
        if otrip is not None:
            obs_overhead["regression_tripwire"] = otrip
        detail["obs_overhead"] = obs_overhead

    # closed-loop serving benchmark (the online-inference counterpart of the
    # training protocol). Default on for the CPU mesh; opt-in on TPU via
    # BENCH_SERVE=1 (it adds a short extra training + a few seconds of
    # serving traffic).
    serve_env = os.environ.get("BENCH_SERVE")
    if serve_env == "1" or (serve_env is None and not on_tpu):
        serve_trained = _train_serve_model()
        serve_section = run_serve_measurement(trained=serve_trained)
        strip = serve_latency_tripwire(
            serve_section, prev_rec, prev_name, backend=backend
        )
        if strip is not None:
            serve_section["regression_tripwire"] = strip
        detail["serve"] = serve_section
        # paired arm: the identical model + closed loop on the FIL-style
        # node-array layout; its p99 is gated against BOTH the recorded
        # history and (tightly) the in-process heap arm
        na_section = run_serve_measurement(
            layout="node_array", trained=serve_trained
        )
        natrip = serve_latency_tripwire(
            na_section, prev_rec, prev_name, backend=backend,
            section="serve_node_array",
        )
        if natrip is not None:
            na_section["regression_tripwire"] = natrip
        ltrip = serve_layout_tripwire(serve_section, na_section)
        if ltrip is not None:
            na_section["layout_tripwire"] = ltrip
            na_section["p99_speedup_vs_heap"] = round(
                1.0 / ltrip["ratio"], 3
            ) if ltrip["ratio"] else None
        detail["serve_node_array"] = na_section

    # deterministic chaos soak (the recovery counterpart of the protocol
    # run). Default on for the CPU mesh so every recorded BENCH_*.json
    # snapshot carries a `chaos` section for the time-to-recover tripwire
    # to compare against; opt-in on TPU via BENCH_CHAOS=1.
    chaos_env = os.environ.get("BENCH_CHAOS")
    if chaos_env == "1" or (chaos_env is None and not on_tpu):
        chaos_section = run_chaos_measurement()
        ctrip = chaos_recovery_tripwire(
            chaos_section, prev_rec, prev_name, backend=backend
        )
        if ctrip is not None:
            chaos_section["regression_tripwire"] = ctrip
        etrip = elastic_recovery_tripwire(
            chaos_section, prev_rec, prev_name, backend=backend
        )
        if etrip is not None:
            chaos_section["elastic_regression_tripwire"] = etrip
        detail["chaos"] = chaos_section

    # the headline is what was measured, on the device that measured it: a
    # TPU run reports its own train wall clock (under the protocol's name
    # only at the protocol's size — nothing is scaled to a size that did
    # not run); the CPU counts mode reports no speed metric at all
    result = {
        **device,
        "backend": backend,
        "rows": n_rows,
        "rounds": rounds,
        "actors": actors,
        "train_time_s": round(train_time, 2),
        **detail,
    }
    if on_tpu:
        full_protocol = n_rows == 11_000_000 and rounds == 100
        headline = {
            "metric": (
                "higgs11m_100r_train_wall_clock" if full_protocol
                else "higgs_shape_train_wall_clock"
            ),
            "value": round(train_time, 2),
            "unit": "s",
        }
        if full_protocol:
            headline["vs_baseline"] = round(
                BASELINE_GPU_HIST_S / train_time, 3
            )
    else:
        headline = {"metric": None, "mode": "cpu_counts"}
    print(json.dumps({**headline, **result}))


def main(cpu_counts: bool = False):
    """``python bench.py [--cpu-counts]``: one process, one measurement."""
    if cpu_counts:
        _force_cpu_mesh()
    from xgboost_ray_tpu.util import place_compile_cache

    cache_dir = place_compile_cache()
    print(f"[bench] compile cache: {cache_dir}", file=sys.stderr)
    run_measurement(cpu_counts)


def chaos_only_main():
    """``--chaos``: run ONLY the chaos soak and print one JSON line headlined
    by its time-to-recover, with the full ``chaos`` section and the >20%
    recovery-regression tripwire vs the newest BENCH_*.json. Runs on the
    8-device virtual CPU mesh unless BENCH_CHAOS_ON_ACCEL=1 keeps the
    ambient accelerator backend."""
    if os.environ.get("BENCH_CHAOS_ON_ACCEL") != "1":
        _force_cpu_mesh()
    import jax

    from xgboost_ray_tpu.util import device_record

    backend = jax.default_backend()
    section = run_chaos_measurement()
    prev_rec, prev_name = _load_latest_bench_record(
        os.path.dirname(os.path.abspath(__file__))
    )
    trip = chaos_recovery_tripwire(section, prev_rec, prev_name,
                                   backend=backend)
    if trip is not None:
        section["regression_tripwire"] = trip
    etrip = elastic_recovery_tripwire(section, prev_rec, prev_name,
                                      backend=backend)
    if etrip is not None:
        section["elastic_regression_tripwire"] = etrip
    ok = section["model_matches"] and section["ckpt_resume_matches"]
    elastic_sec = section.get("elastic")
    if elastic_sec is not None:
        # the elastic continuation must replay nothing, reproduce the
        # uninterrupted model, and recover strictly faster than the
        # restart-from-checkpoint policy
        ok = ok and elastic_sec["model_matches"]
        ok = ok and elastic_sec["rounds_replayed"] == 0
        cvr = section.get("continue_vs_restart")
        if cvr is not None:
            ok = ok and cvr["continue_faster"]
    # the per-config pairings carry the same contract: zero replay,
    # uninterrupted-model identity, continuation strictly faster
    for key in ("elastic_2d", "elastic_streamed", "elastic_domain"):
        arm = section.get(key)
        if arm is None:
            continue
        ok = ok and arm["elastic"]["rounds_replayed"] == 0
        ok = ok and arm["elastic"]["model_matches"]
        cvr = arm.get("continue_vs_restart")
        if cvr is not None:
            ok = ok and cvr["continue_faster"]
    print(
        json.dumps(
            {
                "metric": "chaos_time_to_recover_s",
                "value": section["time_to_recover_s"],
                "unit": "s",
                **device_record(),
                "backend": backend,
                "chaos": section,
            }
        )
    )
    if not ok:
        # a chaos soak whose recovered model DIFFERS from the uninterrupted
        # run is a correctness failure, not a slow recovery — fail the run
        print("[bench] chaos soak FAILED identity checks", file=sys.stderr)
        sys.exit(1)


def serve_only_main():
    """``--serve``: run ONLY the closed-loop serving benchmark and print one
    JSON line headlined by its QPS, with the full ``serve`` section. Runs on
    the 8-device virtual CPU mesh unless BENCH_SERVE_ON_ACCEL=1 keeps the
    ambient accelerator backend."""
    if os.environ.get("BENCH_SERVE_ON_ACCEL") != "1":
        _force_cpu_mesh()
    import jax

    from xgboost_ray_tpu.util import device_record

    backend = jax.default_backend()
    trained = _train_serve_model()
    section = run_serve_measurement(trained=trained)
    na_section = run_serve_measurement(layout="node_array", trained=trained)
    prev_rec, prev_name = _load_latest_bench_record(
        os.path.dirname(os.path.abspath(__file__))
    )
    trip = serve_latency_tripwire(section, prev_rec, prev_name,
                                  backend=backend)
    if trip is not None:
        section["regression_tripwire"] = trip
    natrip = serve_latency_tripwire(na_section, prev_rec, prev_name,
                                    backend=backend,
                                    section="serve_node_array")
    if natrip is not None:
        na_section["regression_tripwire"] = natrip
    ltrip = serve_layout_tripwire(section, na_section)
    if ltrip is not None:
        na_section["layout_tripwire"] = ltrip
        na_section["p99_speedup_vs_heap"] = round(
            1.0 / ltrip["ratio"], 3
        ) if ltrip["ratio"] else None
    print(
        json.dumps(
            {
                "metric": "serve_closed_loop_qps",
                "value": section["qps"],
                "unit": "req/s",
                **device_record(),
                "backend": backend,
                "serve": section,
                "serve_node_array": na_section,
            }
        )
    )


def large_only_main():
    """``--large``: run ONLY the composed-headline large measurement and
    print one JSON line headlined by the composed arm's steady per-round
    time, with the full ``large`` section and the >20% drift tripwire vs
    the newest BENCH_*.json. Runs on the 8-device virtual CPU mesh unless
    BENCH_LARGE_ON_ACCEL=1 keeps the ambient accelerator backend. Exits
    nonzero when any of the section's contracts (memory budget, relative
    logloss envelope, wire byte cut) fails."""
    if os.environ.get("BENCH_LARGE_ON_ACCEL") != "1":
        _force_cpu_mesh()
    import jax

    from xgboost_ray_tpu.util import device_record

    backend = jax.default_backend()
    section = run_large_measurement()
    prev_rec, prev_name = _load_latest_bench_record(
        os.path.dirname(os.path.abspath(__file__))
    )
    trip = large_tripwire(section, prev_rec, prev_name, backend=backend)
    if trip is not None:
        section["regression_tripwire"] = trip
    print(
        json.dumps(
            {
                "metric": "large_composed_steady_per_round_s",
                "value": section["composed"]["steady_per_round_s"],
                "unit": "s",
                **device_record(),
                "backend": backend,
                "large": section,
            }
        )
    )
    ok = section["mem_budget_ok"] and section["logloss_ok"]
    ok = ok and section.get("wire_bytes_ok", True)
    if not ok:
        print("[bench] large measurement FAILED its contracts",
              file=sys.stderr)
        sys.exit(1)


def lowprec_only_main():
    """``--lowprec``: run ONLY the low-precision ablation (gh arms + the
    composed row/block wire arms) on protocol-shaped data and print one
    JSON line headlined by the block wire's measured byte cut vs the row
    wire, with the full ``low_precision`` section and its tripwire. Runs
    on the 8-device virtual CPU mesh unless BENCH_LOW_PRECISION_ON_ACCEL=1
    keeps the ambient backend. Exits nonzero when a section gate fails."""
    if os.environ.get("BENCH_LOW_PRECISION_ON_ACCEL") != "1":
        _force_cpu_mesh()
    import jax

    from xgboost_ray_tpu.util import device_record

    backend = jax.default_backend()
    rows = int(os.environ.get("BENCH_LOW_PRECISION_ROWS", 200_000))
    n_feat = int(os.environ.get("BENCH_FEATURES", 28))
    actors = int(os.environ.get("BENCH_ACTORS",
                                max(1, len(jax.devices()))))
    x, y = make_higgs_like(rows, n_feat)
    params = {
        "objective": "binary:logistic",
        "eval_metric": ["logloss"],
        "max_depth": int(os.environ.get("BENCH_DEPTH", 6)),
        "eta": 0.1,
        "max_bin": 256,
        "tree_method": "tpu_hist",
    }
    section = run_low_precision_ablation(x, y, params, actors)
    prev_rec, prev_name = _load_latest_bench_record(
        os.path.dirname(os.path.abspath(__file__))
    )
    trip = low_precision_tripwire(section, prev_rec, prev_name,
                                  backend=backend)
    if trip is not None:
        section["regression_tripwire"] = trip
    print(
        json.dumps(
            {
                "metric": "low_precision_block_wire_bytes_cut",
                "value": section.get("block_wire_bytes_cut"),
                "unit": "x",
                **device_record(),
                "backend": backend,
                "low_precision": section,
            }
        )
    )
    ok = True
    for gate in ("int16_logloss_ok", "int8_logloss_ok", "round_time_ok",
                 "gh_bytes_cut_ok", "block_wire_bytes_ok",
                 "block_no_worse_than_row_ok", "block_vs_row_logloss_ok"):
        ok = ok and section.get(gate, True)
    if not ok:
        print("[bench] low-precision ablation FAILED its contracts",
              file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    if "--serve" in sys.argv:
        serve_only_main()
    elif "--chaos" in sys.argv:
        chaos_only_main()
    elif "--large" in sys.argv:
        large_only_main()
    elif "--lowprec" in sys.argv:
        lowprec_only_main()
    else:
        main(cpu_counts="--cpu-counts" in sys.argv)

"""Streamed ingestion (stream/): parity, sketch guarantees, memory budget.

Pins the PR's non-negotiable contracts:

* a single-chunk streamed load is BITWISE-identical (bins, cuts, trained
  forest) to the in-memory path;
* merged sketches are invariant to chunking (same rows, any chunk size ->
  bitwise-same summary) and deterministic;
* the sketch's runtime rank-error certificate really bounds the observed
  error against exact quantiles;
* NaN/missing and weighted rows are handled;
* a dataset whose raw f32 form exceeds ``RXGB_STREAM_BUDGET_MB`` trains
  with measured peak RSS under the budget;
* gh_precision=int8 composes; warm start rides the binned forest walk
  (with the cut-drift gate pinned);
* the vectorized host sketch/bin are bitwise-equal to the loop oracles;
* a streamed load is reconstructible from the obs timeline.
"""

import gc
import os
import sys
import threading
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from xgboost_ray_tpu import obs  # noqa: E402
from xgboost_ray_tpu.engine import TpuEngine  # noqa: E402
from xgboost_ray_tpu.ops import binning  # noqa: E402
from xgboost_ray_tpu.params import parse_params, validate_streaming_params  # noqa: E402
from xgboost_ray_tpu.stream.reader import (  # noqa: E402
    StreamConfig,
    array_shard_stream,
    npy_shard_stream,
)
from xgboost_ray_tpu.stream.sketch import StreamSketch  # noqa: E402

_PARAMS = {
    "objective": "binary:logistic",
    "max_depth": 3,
    "eval_metric": ["logloss"],
}


def _data(n=4000, f=6, seed=7, nan_frac=0.05):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, f).astype(np.float32)
    if nan_frac:
        x[rng.rand(n, f) < nan_frac] = np.nan
    y = (np.nan_to_num(x[:, 0]) + rng.randn(n) * 0.5 > 0).astype(np.float32)
    return x, y


def _forest_fields(eng):
    booster = eng.get_booster()
    return [np.asarray(f) for f in booster.forest]


# ---------------------------------------------------------------------------
# parity contracts
# ---------------------------------------------------------------------------


def test_single_chunk_stream_is_bitwise_identical():
    """One-chunk streams degrade to the materialized path: cuts, bins and
    the trained forest must be BITWISE equal, not merely close."""
    x, y = _data()
    p = parse_params(_PARAMS)
    eng_m = TpuEngine([{"data": x, "label": y}], p, num_actors=4)
    eng_s = TpuEngine(
        [array_shard_stream(x, label=y, chunk_rows=x.shape[0])], p,
        num_actors=4,
    )
    assert not eng_s._streamed  # the degrade path IS the materialized path
    assert np.array_equal(np.asarray(eng_m.cuts), np.asarray(eng_s.cuts))
    assert np.array_equal(np.asarray(eng_m.bins), np.asarray(eng_s.bins))
    for i in range(3):
        eng_m.step(i)
        eng_s.step(i)
    for fm, fs in zip(_forest_fields(eng_m), _forest_fields(eng_s)):
        assert np.array_equal(fm, fs)


def test_single_chunk_stream_with_train_eval_alias():
    """The degrade path must preserve eval-set aliasing: an eval entry that
    IS the train shard list keeps the train-set eval fast path after
    materialization (regression pin for the rebind bug)."""
    x, y = _data(n=2000, f=4, seed=16)
    shards = [array_shard_stream(x, label=y, chunk_rows=x.shape[0])]
    eng = TpuEngine(shards, parse_params(_PARAMS), num_actors=2,
                    evals=[(shards, "train")])
    assert not eng._streamed
    assert eng.evals and eng.evals[0].is_train
    res = eng.step(0)
    assert np.isfinite(res["train"]["logloss"])


def test_single_chunk_streamed_eval_degrades_with_materialized_train():
    """A single-chunk streamed eval set degrades to materialized fields no
    matter how the TRAIN set arrived (the same contract as the train-side
    degrade); only genuinely multi-chunk eval streams hit the gate."""
    x, y = _data(n=3000, f=4, seed=21)
    xe, ye = _data(n=1000, f=4, seed=22)
    p = parse_params(_PARAMS)
    eng = TpuEngine(
        [{"data": x, "label": y}], p, num_actors=2,
        evals=[([array_shard_stream(xe, label=ye, chunk_rows=xe.shape[0])],
                "ev")],
    )
    res = eng.step(0)
    assert np.isfinite(res["ev"]["logloss"])
    with pytest.raises(NotImplementedError, match="streamed"):
        TpuEngine(
            [{"data": x, "label": y}], p, num_actors=2,
            evals=[([array_shard_stream(xe, label=ye, chunk_rows=100)],
                    "ev")],
        )


def test_multi_chunk_assembled_bins_match_host_binning():
    """The double-buffered upload + on-device assembly must reproduce
    exactly bin_matrix_np(x, streamed_cuts) in row order, with the padding
    tail in the missing bucket."""
    x, y = _data(n=3001, f=5)
    p = parse_params(_PARAMS)
    eng = TpuEngine(
        [array_shard_stream(x, label=y, chunk_rows=257)], p, num_actors=4
    )
    assert eng._streamed
    got = np.asarray(eng.bins)
    ref = binning.bin_matrix_np(x, eng._stream_cuts_np, p.max_bin)
    assert np.array_equal(got[: x.shape[0]], ref)
    assert (got[x.shape[0]:] == p.max_bin).all()


def test_multi_chunk_stream_trains_close_to_materialized():
    """The sketch path's cuts differ from the materialized sketch only
    within the rank-error certificate; final logloss must land within 5e-4
    (the bench `streaming` section pins the same bound at 200k scale)."""
    x, y = _data(n=20000, f=8, seed=1)
    p = parse_params(_PARAMS)
    eng_m = TpuEngine([{"data": x, "label": y}], p, num_actors=4,
                      evals=[([{"data": x, "label": y}], "train")])
    eng_s = TpuEngine(
        [array_shard_stream(x, label=y, chunk_rows=3000)], p, num_actors=4,
        evals=[([{"data": x, "label": y}], "train")],
    )
    assert eng_s._streamed
    for i in range(8):
        m = eng_m.step(i)
        s = eng_s.step(i)
    delta = abs(m["train"]["logloss"] - s["train"]["logloss"])
    assert delta <= 5e-4, f"final logloss drifted {delta}"


def test_streamed_composes_with_gh_precision_int8():
    x, y = _data(n=6000, f=6, seed=2)
    p = parse_params({**_PARAMS, "gh_precision": "int8"})
    eng_s = TpuEngine(
        [array_shard_stream(x, label=y, chunk_rows=1000)], p, num_actors=4,
        evals=[([{"data": x, "label": y}], "train")],
    )
    assert eng_s._streamed
    eng_m = TpuEngine([{"data": x, "label": y}], p, num_actors=4,
                      evals=[([{"data": x, "label": y}], "train")])
    for i in range(5):
        s = eng_s.step(i)
        m = eng_m.step(i)
    assert np.isfinite(s["train"]["logloss"])
    assert abs(s["train"]["logloss"] - m["train"]["logloss"]) <= 5e-4


def test_streamed_composes_with_feature_parallel():
    """2D row x feature sharding happens post-bin, so it composes: the
    streamed (R, C) engine must train, and match the streamed (R, 1) run
    bitwise (the PR 10 mesh-parity contract on streamed bins)."""
    x, y = _data(n=2000, f=6, seed=4)
    p1 = parse_params(_PARAMS)
    p2 = parse_params({**_PARAMS, "feature_parallel": 2})
    shards = lambda: [array_shard_stream(x, label=y, chunk_rows=333)]  # noqa: E731
    e1 = TpuEngine(shards(), p1, num_actors=4)
    e2 = TpuEngine(shards(), p2, num_actors=4)
    assert e1._streamed and e2._streamed
    for i in range(3):
        e1.step(i)
        e2.step(i)
    for f1, f2 in zip(_forest_fields(e1), _forest_fields(e2)):
        assert np.array_equal(f1, f2)


# ---------------------------------------------------------------------------
# sketch guarantees
# ---------------------------------------------------------------------------


def test_sketch_chunking_invariance_bitwise():
    """Same rows, ANY chunking -> bitwise-identical exported summary (the
    state is a function of the row prefix only)."""
    x, _ = _data(n=5000, f=4, seed=3)
    w = np.abs(np.random.RandomState(5).randn(5000)).astype(np.float32)
    for weights in (None, w):
        exports = []
        for chunk in (1, 7, 64, 977, 5000):
            sk = StreamSketch(4, capacity=256)
            for lo in range(0, 5000, chunk):
                wc = None if weights is None else weights[lo : lo + chunk]
                sk.update(x[lo : lo + chunk], weight=wc)
            exports.append(sk.export(1024))
        ref_vals, ref_wts, ref_err = exports[0]
        for vals, wts, err in exports[1:]:
            assert np.array_equal(vals, ref_vals)
            assert np.array_equal(wts, ref_wts)
            assert np.array_equal(err, ref_err)


def test_sketch_rank_error_bound_vs_exact_quantiles():
    """The runtime certificate really bounds the observed rank error of
    sketch quantiles against exact quantiles."""
    rng = np.random.RandomState(11)
    n, f = 30000, 3
    x = np.stack([
        rng.randn(n), rng.lognormal(size=n), rng.randint(0, 50, n).astype(float)
    ], axis=1).astype(np.float32)
    sk = StreamSketch(f, capacity=512)
    for lo in range(0, n, 1000):
        sk.update(x[lo : lo + 1000])
    qs = np.arange(1, 32) / 32.0
    est = sk.quantiles(qs)
    bound = sk.rank_error_bound()
    assert (bound < 0.05 * n).all(), "certificate uselessly loose"
    for fi in range(f):
        col = np.sort(x[:, fi])
        for qi, q in enumerate(qs):
            # observed rank of the estimate vs the target rank: the
            # certificate must cover it (ties give a rank interval)
            v = est[fi, qi]
            rank_lo = np.searchsorted(col, v, side="left")
            rank_hi = np.searchsorted(col, v, side="right")
            target = q * n
            err = max(0.0, max(rank_lo - target, target - rank_hi))
            assert err <= bound[fi] + 1e-6, (
                f"feature {fi} q={q}: err {err} > certified {bound[fi]}"
            )


def test_sketch_merge_and_missing_handling():
    """Actor-merge equals a single sketch over the union (within the summed
    certificate); NaN rows never contribute mass but are tracked."""
    x, _ = _data(n=8000, f=5, seed=6, nan_frac=0.2)
    x[:, 3] = np.nan  # all-missing feature
    parts = np.array_split(x, 3)
    sks = []
    for part in parts:
        sk = StreamSketch(5, capacity=256)
        sk.update(part)
        sks.append(sk)
    merged = sks[0].merge(sks[1]).merge(sks[2])
    n_missing = np.isnan(x).sum(axis=0)
    assert np.allclose(merged.missing_weight, n_missing)
    assert np.allclose(
        merged.total_weight, x.shape[0] - n_missing
    )
    assert merged.n_rows == x.shape[0]
    # quantiles over non-missing values stay within the certificate
    qs = np.array([0.25, 0.5, 0.75])
    est = merged.quantiles(qs)
    bound = merged.rank_error_bound()
    for fi in (0, 1, 2, 4):
        col = np.sort(x[:, fi][~np.isnan(x[:, fi])])
        w_total = col.size
        for qi, q in enumerate(qs):
            v = est[fi, qi]
            rank_lo = np.searchsorted(col, v, side="left")
            rank_hi = np.searchsorted(col, v, side="right")
            target = q * w_total
            err = max(0.0, max(rank_lo - target, target - rank_hi))
            assert err <= bound[fi] + 1e-6
    # the all-missing feature yields zero mass and a zero placeholder
    assert merged.total_weight[3] == 0.0
    assert (est[3] == 0.0).all()


def test_weighted_sketch_matches_replicated_rows():
    """Integer weights must act like row replication (the xgboost weighted
    quantile semantics), within the certificate."""
    rng = np.random.RandomState(9)
    n = 4000
    x = rng.randn(n, 2).astype(np.float32)
    w = rng.randint(1, 4, n).astype(np.float32)
    sk = StreamSketch(2, capacity=512)
    sk.update(x, weight=w)
    qs = np.array([0.1, 0.5, 0.9])
    est = sk.quantiles(qs)
    bound = sk.rank_error_bound()
    for fi in range(2):
        rep = np.sort(np.repeat(x[:, fi], w.astype(int)))
        w_total = rep.size
        for qi, q in enumerate(qs):
            v = est[fi, qi]
            rank_lo = np.searchsorted(rep, v, side="left")
            rank_hi = np.searchsorted(rep, v, side="right")
            target = q * w_total
            err = max(0.0, max(rank_lo - target, target - rank_hi))
            assert err <= bound[fi] + 1e-6


def test_streamed_engine_weighted_rows_reach_the_sketch():
    """Row weights must shift streamed cuts (weight-aware sketch), mirroring
    the materialized weighted sketch behavior."""
    rng = np.random.RandomState(13)
    n = 6000
    x = rng.randn(n, 3).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.float32)
    w = np.where(x[:, 0] > 1.0, 50.0, 1.0).astype(np.float32)
    p = parse_params(_PARAMS)
    eng_u = TpuEngine([array_shard_stream(x, label=y, chunk_rows=1000)],
                      p, num_actors=2)
    eng_w = TpuEngine(
        [array_shard_stream(x, label=y, weight=w, chunk_rows=1000)],
        p, num_actors=2,
    )
    assert eng_u._streamed and eng_w._streamed
    cu, cw = eng_u._stream_cuts_np, eng_w._stream_cuts_np
    # upweighting the right tail must drag median-region cuts right
    mid = cu.shape[1] // 2
    assert cw[0, mid] > cu[0, mid]


# ---------------------------------------------------------------------------
# vectorized host binning == loop oracles (satellite: binning on the
# streaming hot path)
# ---------------------------------------------------------------------------


def test_vectorized_host_sketch_and_bin_bitwise_equal_loop():
    rng = np.random.RandomState(21)
    for n, f, b in ((1000, 7, 256), (513, 3, 16), (64, 2, 4), (200, 33, 64)):
        x = rng.randn(n, f).astype(np.float32)
        x[rng.rand(n, f) < 0.15] = np.nan
        x[rng.rand(n, f) < 0.2] = np.float32(0.5)  # ties
        x[rng.rand(n, f) < 0.05] = np.float32(-0.0)  # signed-zero boundary
        if f > 2:
            x[:, 1] = np.nan  # all-missing feature
        assert np.array_equal(
            binning.sketch_cuts_np(x, b),
            binning._sketch_cuts_np_loop(x, b),
        )
        w = rng.rand(n).astype(np.float32)
        w[rng.rand(n) < 0.1] = 0.0
        assert np.array_equal(
            binning.sketch_cuts_np(x, b, sample_weight=w),
            binning._sketch_cuts_np_loop(x, b, sample_weight=w),
        )
        cuts = binning._sketch_cuts_np_loop(x, b)
        assert np.array_equal(
            binning.bin_matrix_np(x, cuts, b),
            binning._bin_matrix_np_loop(x, cuts, b),
        )


# ---------------------------------------------------------------------------
# warm start / elastic-restart resume
# ---------------------------------------------------------------------------


def test_streamed_warm_start_resumes_via_binned_walk():
    """Restart-from-checkpoint over an unchanged streamed world: the init
    forest walks the binned matrix (no raw rows exist) and training must
    continue exactly like an unbroken run (same cuts -> same split_bin
    routing -> bitwise margins)."""
    x, y = _data(n=6000, f=6, seed=8)
    p = parse_params(_PARAMS)
    mk = lambda **kw: TpuEngine(  # noqa: E731
        [array_shard_stream(x, label=y, chunk_rows=1000)], p, num_actors=4,
        evals=[([{"data": x, "label": y}], "train")], **kw,
    )
    full = mk()
    assert full._streamed
    for i in range(4):
        unbroken = full.step(i)
    seg1 = mk()
    for i in range(2):
        seg1.step(i)
    ckpt = seg1.get_booster()
    seg2 = mk(init_booster=ckpt)
    assert seg2.iteration_offset == 2
    for i in range(2):
        resumed = seg2.step(i)
    assert resumed["train"]["logloss"] == unbroken["train"]["logloss"]


def test_streamed_warm_start_gates_on_cut_drift():
    """A checkpoint grown against different cuts cannot ride split_bin
    routing over re-binned rows: pinned loud gate, not silent corruption."""
    x, y = _data(n=6000, f=6, seed=8)
    p = parse_params(_PARAMS)
    other_x = x + np.float32(1.7)  # different data -> different cuts
    donor = TpuEngine(
        [array_shard_stream(other_x, label=y, chunk_rows=1000)], p,
        num_actors=4,
    )
    donor.step(0)
    ckpt = donor.get_booster()
    with pytest.raises(NotImplementedError, match="cuts"):
        TpuEngine(
            [array_shard_stream(x, label=y, chunk_rows=1000)], p,
            num_actors=4, init_booster=ckpt,
        )


def test_streamed_engine_reshards_in_flight():
    """Streamed engines re-shard now: ``can_reshard()`` is True, a reset
    against the SAME shard streams rebuilds margins via the binned forest
    walk (retained cuts — no re-stream, no re-sketch), and a reset against
    different streams (or materialized shards) is loudly rejected."""
    x, y = _data(n=3000, f=4, seed=10)
    p = parse_params(_PARAMS)
    mk = lambda: [array_shard_stream(x, label=y, chunk_rows=500)]  # noqa: E731
    shards = mk()
    eng = TpuEngine(shards, p, num_actors=2)
    assert eng._streamed
    assert eng.can_reshard()
    for i in range(3):
        eng.step(i)
    bst = eng.get_booster()
    step_fn = eng._step_fn
    eng.reset_from_booster(shards, [], bst)
    assert eng._step_fn is step_fn  # compiled round program retained
    assert eng.iteration_offset == 3
    eng.step(0)
    # materialized shards / different streams cannot ride the reset
    with pytest.raises(ValueError, match="streamed shard identity"):
        eng.reset_from_booster([{"data": x, "label": y}], [], bst)
    with pytest.raises(ValueError, match="streamed shard identity"):
        eng.reset_from_booster(
            [array_shard_stream(x, label=y, chunk_rows=250)], [], bst
        )


def test_streamed_shrink_reuses_donor_bins_zero_resketch():
    """The PR's streamed keystone at engine level: a shrunken world built
    with ``stream_donor=`` reuses the survivors' binned blocks and FROZEN
    cuts — the timeline after the donor build shows bin-reuse spans and
    ZERO sketch/bin-chunk work, and the shrunken engine's cuts are bitwise
    the donor's."""
    from xgboost_ray_tpu.stream.reader import StreamConfig, fields_shard_stream

    x, y = _data(n=3000, f=5, seed=11)
    cfg = lambda: StreamConfig(chunk_rows=400)  # noqa: E731

    def shard(lo, hi, rank):
        return {"stream": fields_shard_stream(
            {"data": x[lo:hi], "label": y[lo:hi]}, config=cfg(),
            source_token=("central", "uid", rank),
        )}

    p = parse_params(_PARAMS)
    donor = TpuEngine([shard(0, 1500, 0), shard(1500, 3000, 1)], p,
                      num_actors=2)
    for i in range(3):
        donor.step(i)
    bst = donor.get_booster()

    tracer = obs.Tracer(enabled=True)
    with obs.use_tracer(tracer):
        surv = TpuEngine([shard(0, 1500, 0)], p, num_actors=1,
                         init_booster=bst, stream_donor=donor)
    names = [r["name"] for r in tracer.records()]
    assert "data.sketch_chunk" not in names
    assert "data.bin_chunk" not in names
    assert "data.cuts_merge" not in names
    assert "data.bin_reuse" in names
    assert surv._stream_stats["reused_from_donor"] is True
    assert np.array_equal(surv._stream_cuts_np, donor._stream_cuts_np)
    assert surv.iteration_offset == 3
    surv.step(0)

    # the shrunken world's binned rows are bitwise the donor's survivor rows
    assert np.array_equal(
        np.asarray(surv.bins)[:1500], np.asarray(donor.bins)[:1500]
    )


def test_streamed_growback_restreams_only_the_new_shard():
    """Grow-back onto a brand-new replacement shard (engine-cache miss):
    the donor seeds every surviving shard from memory and only the ONE new
    shard re-streams — binned against the donor's frozen cuts, with
    bin-chunk spans for that shard alone and still zero sketch work."""
    from xgboost_ray_tpu.stream.reader import StreamConfig, fields_shard_stream

    x, y = _data(n=3000, f=5, seed=12)

    def shard(lo, hi, rank, uid="uid"):
        return {"stream": fields_shard_stream(
            {"data": x[lo:hi], "label": y[lo:hi]},
            config=StreamConfig(chunk_rows=400),
            source_token=("central", uid, rank),
        )}

    p = parse_params(_PARAMS)
    donor = TpuEngine([shard(0, 1500, 0)], p, num_actors=1)
    for i in range(2):
        donor.step(i)
    bst = donor.get_booster()

    tracer = obs.Tracer(enabled=True)
    with obs.use_tracer(tracer):
        grown = TpuEngine(
            [shard(0, 1500, 0), shard(1500, 3000, 1, uid="uid2")], p,
            num_actors=2, init_booster=bst, stream_donor=donor,
        )
    names = [r["name"] for r in tracer.records()]
    assert "data.sketch_chunk" not in names
    assert "data.cuts_merge" not in names
    assert "data.bin_chunk" in names  # the one re-streamed shard
    st = grown._stream_stats
    assert st["reused_shards"] == 1 and st["restreamed_shards"] == 1
    assert st["restreamed_rows"] == 1500
    assert np.array_equal(grown._stream_cuts_np, donor._stream_cuts_np)
    grown.step(0)
    # the re-streamed shard binned against the frozen cuts lands bitwise
    # where a direct host binning of its raw rows would
    expect = binning.bin_matrix_np(
        x[1500:3000], donor._stream_cuts_np, p.max_bin
    )
    assert np.array_equal(np.asarray(grown.bins)[1500:3000], expect)


def test_streamed_growback_restream_is_budget_prevalidated():
    """A grow-back re-stream that cannot fit the host budget must fail
    BEFORE the new shard's first byte streams (the reuse pass runs the
    same validate_budget model as the original ingest)."""
    from xgboost_ray_tpu.stream.reader import (
        ShardStream, StreamConfig, fields_shard_stream,
    )

    x, y = _data(n=3000, f=5, seed=13)

    def shard(lo, hi, rank):
        return {"stream": fields_shard_stream(
            {"data": x[lo:hi], "label": y[lo:hi]},
            config=StreamConfig(chunk_rows=400),
            source_token=("central", "uid", rank),
        )}

    p = parse_params(_PARAMS)
    donor = TpuEngine([shard(0, 1500, 0)], p, num_actors=1)
    donor.step(0)
    bst = donor.get_booster()

    reads = {"n": 0}

    def bomb_chunk_fn(lo, hi):
        reads["n"] += 1
        return {"data": x[1500 + lo:1500 + hi], "label": y[1500 + lo:1500 + hi]}

    bomb = {"stream": ShardStream(
        1500, 5, bomb_chunk_fn,
        config=StreamConfig(chunk_rows=400, budget_mb=0.001),
        source_token=("central", "uid2", 1),
    )}
    with pytest.raises(ValueError, match="cannot hold"):
        TpuEngine([shard(0, 1500, 0), bomb], p, num_actors=2,
                  init_booster=bst, stream_donor=donor)
    assert reads["n"] == 0, "budget must reject before any byte streams"


# ---------------------------------------------------------------------------
# composition gates
# ---------------------------------------------------------------------------


def test_streaming_composition_gates():
    validate_streaming_params(parse_params(_PARAMS))  # tree boosters pass
    validate_streaming_params(parse_params({**_PARAMS, "booster": "dart"}))
    with pytest.raises(NotImplementedError, match="gblinear"):
        validate_streaming_params(
            parse_params({"objective": "reg:squarederror",
                          "booster": "gblinear"})
        )
    with pytest.raises(NotImplementedError, match="rank"):
        validate_streaming_params(
            parse_params({"objective": "rank:pairwise"})
        )


def test_streamed_eval_set_is_gated():
    x, y = _data(n=2000, f=4, seed=12)
    p = parse_params(_PARAMS)
    with pytest.raises(NotImplementedError, match="eval"):
        TpuEngine(
            [array_shard_stream(x, label=y, chunk_rows=400)], p,
            num_actors=2,
            evals=[([array_shard_stream(x, label=y, chunk_rows=400)], "ev")],
        )


def test_streamed_qid_is_gated():
    x, _ = _data(n=1000, f=3, seed=14)
    qid = np.repeat(np.arange(100), 10).astype(np.float32)
    shard = array_shard_stream(x, label=None, chunk_rows=100)
    inner = shard["stream"]._chunk_fn

    def with_qid(lo, hi):
        out = inner(lo, hi)
        out["qid"] = qid[lo:hi]
        return out

    shard["stream"]._chunk_fn = with_qid
    with pytest.raises(NotImplementedError, match="qid"):
        TpuEngine([shard], parse_params(_PARAMS), num_actors=2)


# ---------------------------------------------------------------------------
# obs timeline: a streamed load is reconstructible from spans alone
# ---------------------------------------------------------------------------


def test_streamed_load_emits_catalogued_ingest_spans():
    for name in ("data.sketch_chunk", "data.bin_chunk", "data.h2d",
                 "data.cuts_merge"):
        assert name in obs.TRACE_NAMES
    x, y = _data(n=3000, f=4, seed=15)
    tracer = obs.Tracer(capacity=4096, enabled=True, trace_dir="")
    with obs.use_tracer(tracer):
        eng = TpuEngine(
            [array_shard_stream(x, label=y, chunk_rows=500)],
            parse_params(_PARAMS), num_actors=4,
        )
    assert eng._streamed
    recs = tracer.records()
    assert obs.validate_trace_records(recs, known_names=obs.TRACE_NAMES) == []
    by_name = {}
    for r in recs:
        by_name.setdefault(r["name"], []).append(r)
    n_chunks = eng._stream_stats["chunks"]
    assert len(by_name["data.sketch_chunk"]) == n_chunks
    assert len(by_name["data.bin_chunk"]) == n_chunks
    assert len(by_name["data.cuts_merge"]) == 1
    # every uploaded block is fenced, with byte accounting (the engine's own
    # row uploads — valid, label, weight, margins — are data.h2d spans too,
    # unfenced: they time the enqueue)
    h2d = [r for r in by_name["data.h2d"] if r["attrs"].get("fenced", True)]
    assert len(by_name["data.h2d"]) == len(h2d) + 4
    assert len(h2d) == eng._stream_stats["transfers"]
    assert sum(r["attrs"]["bytes"] for r in h2d) == eng._stream_stats["bytes"]


# ---------------------------------------------------------------------------
# beyond-budget training with RSS under the budget
# ---------------------------------------------------------------------------


def _write_big_npy(path, n, f, seed=0, block=50000):
    """Stream a synthetic [n, f] float32 .npy to disk without ever holding
    it in memory (the test process's RSS baseline must stay small)."""
    header = {"descr": "<f4", "fortran_order": False, "shape": (n, f)}
    rng = np.random.RandomState(seed)
    with open(path, "wb") as fh:
        np.lib.format.write_array_header_2_0(fh, header)
        for lo in range(0, n, block):
            hi = min(lo + block, n)
            fh.write(rng.randn(hi - lo, f).astype(np.float32).tobytes())


def test_csv_stream_counts_rows_without_trailing_newline(tmp_path):
    """Raw newline counting would drop the last row of a file without a
    trailing newline; the counting parse must see every row."""
    import pandas as pd

    from xgboost_ray_tpu.stream.reader import file_shard_stream

    path = str(tmp_path / "part.csv")
    with open(path, "w") as fh:
        fh.write("f0,f1,label\n1.0,2.0,0\n3.0,4.0,1")  # no trailing newline

    def split_fn(df):
        y = df["label"].to_numpy(np.float32)
        return {"data": df[["f0", "f1"]].to_numpy(np.float32), "label": y}

    s = file_shard_stream([path], split_fn, "csv",
                          config=StreamConfig(chunk_rows=1))
    assert s.n_rows == 2
    rows = [c for c in s.chunks()]
    assert len(rows) == 2
    assert np.array_equal(rows[1]["data"], [[3.0, 4.0]])


def test_npy_stream_gates_unsupported_side_fields(tmp_path):
    """base_margin/bounds/qid/missing/ignore cannot ride the .npy reader —
    they must fail loudly, never be silently dropped (a `missing` sentinel
    would be sketched and binned as real feature values)."""
    from xgboost_ray_tpu import RayShardingMode, RayStreamingDMatrix

    xp, yp = str(tmp_path / "x.npy"), str(tmp_path / "y.npy")
    np.save(xp, np.zeros((64, 3), np.float32))
    np.save(yp, np.zeros(64, np.float32))
    with pytest.raises(NotImplementedError, match="base_margin"):
        RayStreamingDMatrix(
            xp, label=yp, base_margin=np.zeros(64, np.float32),
            chunk_rows=16, sharding=RayShardingMode.BATCH, num_actors=2,
        )
    with pytest.raises(NotImplementedError, match="missing"):
        RayStreamingDMatrix(
            xp, label=yp, missing=-999.0,
            chunk_rows=16, sharding=RayShardingMode.BATCH, num_actors=2,
        )
    with pytest.raises(NotImplementedError, match="ignore"):
        RayStreamingDMatrix(
            xp, label=yp, ignore=["f0"],
            chunk_rows=16, sharding=RayShardingMode.BATCH, num_actors=2,
        )
    # missing=NaN is the default sentinel — equivalent to None, stays legal
    dm = RayStreamingDMatrix(
        xp, label=yp, missing=np.nan,
        chunk_rows=16, sharding=RayShardingMode.BATCH, num_actors=2,
    )
    assert dm.streamed


def test_stream_config_prefetch_respected():
    """prefetch=1 must reach the uploader (memory-minimizing configs) and
    RXGB_STREAM_PREFETCH=0 must raise like an explicit 0 does."""
    assert StreamConfig(prefetch=1).prefetch == 1
    with pytest.raises(ValueError, match="prefetch"):
        StreamConfig(prefetch=0)
    os.environ["RXGB_STREAM_PREFETCH"] = "0"
    try:
        with pytest.raises(ValueError, match="prefetch"):
            StreamConfig()
    finally:
        del os.environ["RXGB_STREAM_PREFETCH"]


def test_streamed_oversized_feature_types_error_is_loud():
    x, y = _data(n=500, f=3, seed=17, nan_frac=0.0)
    with pytest.raises(ValueError, match="more entries than features"):
        TpuEngine(
            [array_shard_stream(x, label=y, chunk_rows=100)],
            parse_params(_PARAMS), num_actors=2,
            feature_types=["q", "q", "q", "c", "c"],
        )


def test_budget_validation_rejects_oversized_chunking():
    """RXGB_STREAM_BUDGET_MB is enforced up front: a chunk/sketch config
    that cannot fit the budget fails loudly before any byte streams."""
    cfg = StreamConfig(chunk_rows=1_000_000, budget_mb=8.0)
    with pytest.raises(ValueError, match="BUDGET"):
        cfg.validate_budget(
            n_rows=2_000_000, n_features=96, chunk_rows=1_000_000,
            sketch_bytes=1 << 20,
        )


def test_bin_matrix_np_rejects_nan_cuts():
    """NaN cuts (a feature whose quantiles mix -inf and +inf) break the
    flat key array's sortedness and would bin silently differently from
    the per-feature oracle — must fail loudly instead."""
    x = np.array([[0.0], [1.0]], np.float32)
    cuts = np.array([[0.5, np.nan]], np.float32)
    with pytest.raises(ValueError, match="NaN"):
        binning.bin_matrix_np(x, cuts, max_bin=4)


def test_npy_stream_rejects_wide_side_files(tmp_path):
    """A [N, k>1] label/weight side file must be rejected at header read —
    ravel()ed it would flow downstream as a k*N column and die far from
    the cause (or silently misalign)."""
    from xgboost_ray_tpu.stream.reader import npy_shard_stream

    xp, yp = str(tmp_path / "x.npy"), str(tmp_path / "y2.npy")
    np.save(xp, np.zeros((32, 3), np.float32))
    np.save(yp, np.zeros((32, 2), np.float32))  # accidentally one-hot
    with pytest.raises(ValueError, match="1-D"):
        npy_shard_stream(xp, label_path=yp)


def test_explicit_sketch_capacity_is_validated_not_rewritten():
    """An explicit (user/env) sketch_capacity that StreamSketch itself
    would reject must raise, not be silently rounded to a capacity the
    user never configured."""
    x = np.zeros((16, 2), np.float32)
    with pytest.raises(ValueError, match="capacity"):
        array_shard_stream(x, config=StreamConfig(sketch_capacity=6))
    with pytest.raises(ValueError, match="capacity"):
        array_shard_stream(x, config=StreamConfig(sketch_capacity=9))


def test_block_budget_term_fails_before_any_byte_streams(monkeypatch):
    """The N-scaling block-buffer budget term is checkable from declared
    row counts alone, so a violating config must be rejected BEFORE pass 1
    streams the dataset (not after hours of I/O, in pass 2)."""
    from xgboost_ray_tpu.stream.reader import ShardStream

    x, y = _data(n=200_000, f=64, seed=23, nan_frac=0.0)
    # budget fits chunk+sketch (small chunks, tiny cap) but NOT the
    # per-actor block buffers of a 200k-row world on few actors
    cfg = StreamConfig(chunk_rows=512, budget_mb=8.0, sketch_capacity=64)
    shards = [array_shard_stream(x, label=y, config=cfg)]

    def bomb(self):
        raise AssertionError("a chunk streamed before the budget check")

    monkeypatch.setattr(ShardStream, "chunks", bomb)
    with pytest.raises(ValueError, match="block buffers"):
        TpuEngine(shards, parse_params(_PARAMS), num_actors=2)


def test_budget_counts_cuts_merge_summaries():
    """The cuts merge stacks [n_devices, F, export_cap] f32 vals+wts
    summaries — at wide F that term alone can dwarf the chunk/sketch
    terms, so the up-front fail-fast must charge it."""
    from xgboost_ray_tpu.stream import ingest

    x = np.zeros((512, 2000), np.float32)
    cfg = StreamConfig(chunk_rows=64, budget_mb=32.0, sketch_capacity=64)
    s = array_shard_stream(x, config=cfg)["stream"]
    with pytest.raises(ValueError, match="cuts-merge"):
        ingest.prevalidate_budget(
            [s], block_rows=64, bin_itemsize=1, n_devices=8
        )
    cfg2 = StreamConfig(chunk_rows=64, budget_mb=256.0, sketch_capacity=64)
    s2 = array_shard_stream(x, config=cfg2)["stream"]
    ingest.prevalidate_budget(
        [s2], block_rows=64, bin_itemsize=1, n_devices=8
    )


def test_budget_derived_chunk_fits_its_own_budget():
    """The budget-derived chunk size must never be a config
    validate_budget then rejects (the old 1024-row efficiency floor could
    inflate a tiny budget's derived chunk past the budget itself)."""
    cfg = StreamConfig(budget_mb=4.0)
    rows = cfg.resolve_chunk_rows(n_rows=1_000_000, n_features=1000)
    assert 1 <= rows < 1024  # the floor must not win over the budget
    cfg.validate_budget(1_000_000, 1000, rows, sketch_bytes=0)


def test_budget_validation_sums_sketches_across_shards():
    """The driver holds EVERY shard's sketch concurrently through pass 1,
    so the fail-fast must reject a budget that each shard's own sketch
    would fit but the sum does not — before any byte streams."""
    from xgboost_ray_tpu.stream import ingest

    cfg = StreamConfig(chunk_rows=500, budget_mb=16.0, sketch_capacity=1024)
    rng = np.random.RandomState(3)
    streams = []
    for _ in range(8):
        x = rng.randn(2000, 256).astype(np.float32)
        streams.append(array_shard_stream(x, config=cfg)["stream"])
    one = ingest.sketch_pass(streams[:1], max_bin=256)  # alone: fits
    assert one.n_rows == 2000
    with pytest.raises(ValueError, match="BUDGET"):
        ingest.sketch_pass(streams, max_bin=256)


def test_beyond_budget_training_respects_rss_budget(tmp_path, monkeypatch):
    """A dataset whose raw f32 form exceeds the enforced
    RXGB_STREAM_BUDGET_MB ingests with measured peak RSS delta under the
    budget, then trains successfully (the streaming data plane's acceptance
    criterion).

    The budget governs the INGEST host plane (chunk + sketch + per-actor
    bin blocks + upload); the round step's histogram scratch afterwards
    lives in HBM on real accelerators — on this CPU test backend it shares
    process RSS, so the budget window closes at the end of ingestion and
    training is asserted for completion only. The materialized path would
    blow the window by construction: raw host concat + raw device copy are
    each bigger than the whole budget.
    """
    n, f = 375_000, 256
    raw_mb = n * f * 4 / 2**20  # ~366 MB raw f32
    budget_mb = 320.0
    assert raw_mb > budget_mb
    xp = str(tmp_path / "x.npy")
    yp = str(tmp_path / "y.npy")
    _write_big_npy(xp, n, f, seed=31)
    rng = np.random.RandomState(32)
    np.save(yp, (rng.rand(n) > 0.5).astype(np.float32))
    monkeypatch.setenv("RXGB_STREAM_BUDGET_MB", str(budget_mb))
    monkeypatch.setenv("RXGB_STREAM_CHUNK_ROWS", "16384")
    monkeypatch.setenv("RXGB_STREAM_SKETCH_CAP", "512")
    p = parse_params({**_PARAMS, "max_depth": 3, "max_bin": 64})
    cfg = StreamConfig()  # everything from the enforced env knobs
    assert cfg.budget_mb == budget_mb
    # warm the runtime before opening the budget window: XLA's compile
    # arena and the backend allocator's pools grow once per process and are
    # one-time runtime overhead, not data-plane memory the budget governs
    warm_x, warm_y = _data(n=4096, f=f, seed=33, nan_frac=0.0)
    warm = TpuEngine(
        [array_shard_stream(warm_x, label=warm_y, chunk_rows=1024)],
        p, num_actors=8,
    )
    assert warm._streamed
    del warm, warm_x, warm_y
    import bench

    gc.collect()
    with bench._RssPeakSampler() as rss:  # the bench section's sampler
        shards = [{"stream": npy_shard_stream(
            xp, label_path=yp, config=cfg,
            row_range=(0, n),
        )}]
        eng = TpuEngine(shards, p, num_actors=8)
    assert eng._streamed
    ingest_peak_mb = rss.delta_mb
    assert ingest_peak_mb < budget_mb, (
        f"ingest peak RSS delta {ingest_peak_mb:.1f} MB >= budget "
        f"{budget_mb} MB"
    )
    for i in range(2):
        eng.step(i)
    assert eng.n_rows == n

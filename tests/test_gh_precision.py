"""End-to-end int8/int16 quantized-gradient training (``gh_precision``).

The on-chip half of the low-precision story (ROADMAP item 3): g/h are
quantized AT THE OBJECTIVE KERNEL with per-tree pmax-shared scales and
SALT_SR-folded stochastic rounding, carried low-precision through
GOSS/uniform compaction and histogram accumulation (int -> int32, exact),
and dequantized ONCE at the split-search/leaf-weight boundary. Covers the
acceptance contract: stochastic-rounding unbiasedness + on-grid exactness,
bitwise same-seed reruns, the float32 default deduping onto the exact
pre-PR program, accuracy within the documented tolerance of f32,
composition with sampling / hist_quant / lossguide / the 2D mesh, elastic
shrink->grow continuation, and the rxgbverify precision-flow extension.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from xgboost_ray_tpu import progreg
from xgboost_ray_tpu.engine import TpuEngine
from xgboost_ray_tpu.ops import sampling
from xgboost_ray_tpu.ops.histogram import build_histogram, node_sums
from xgboost_ray_tpu.ops.objectives import (
    CustomObjective,
    dequantize_gh_sums,
    get_objective,
    quantize_gh,
)
from xgboost_ray_tpu.params import parse_params


def _data(n=512, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, 6).astype(np.float32)
    y = (x[:, 0] * 2 + np.sin(x[:, 1]) + 0.1 * rng.randn(n) > 0).astype(
        np.float32
    )
    return x, y


_BASE = {"objective": "binary:logistic", "max_depth": 4, "eta": 0.3,
         "eval_metric": ["logloss"]}


def _train(shards, num_actors, rounds=10, params=None, **kw):
    eng = TpuEngine(shards, parse_params(params or _BASE), num_actors, **kw)
    last = None
    for i in range(rounds):
        last = eng.step(i)
    return eng, last


def _forest_arrays(booster):
    f = booster.forest
    return tuple(
        np.asarray(getattr(f, n))
        for n in ("feature", "split_bin", "threshold", "default_left",
                  "value", "gain", "cover")
    )


# ---------------------------------------------------------------------------
# op level: the stochastic-rounding quantizer
# ---------------------------------------------------------------------------


def test_sr_on_grid_values_round_deterministically():
    """Values exactly on the quantization grid (x = k * scale) must map to
    k under EVERY rounding key: floor(k + u) == k for all u < 1. Zero
    gradients — padding rows — therefore stay exactly zero."""
    qmax = 127
    ks = np.array([-qmax, -3, 0, 1, 64, qmax], np.float32)
    amax = float(np.abs(ks).max())
    scale = amax / qmax
    gh = np.stack([ks * scale, np.abs(ks) * scale], axis=1).astype(np.float32)
    outs = set()
    for seed in range(50):
        q, s = jax.jit(lambda g, k: quantize_gh(g, "int8", k))(
            jnp.asarray(gh), jax.random.PRNGKey(seed)
        )
        outs.add(np.asarray(q).tobytes())
        np.testing.assert_allclose(
            np.asarray(dequantize_gh_sums(q, s)), gh, rtol=1e-6, atol=1e-7
        )
    assert len(outs) == 1  # on-grid: key-independent


def test_sr_unbiased_mean_error_vanishes():
    """E[q * scale] == x: the mean dequantized value over many independent
    rounding keys converges to the f32 input at the 1/sqrt(K) rate — the
    property (arxiv 2207.09682) that keeps quantized-gradient training
    accuracy at f32 level where deterministic rounding biases it."""
    rng = np.random.RandomState(3)
    gh = np.stack(
        [rng.randn(256), np.abs(rng.randn(256))], axis=1
    ).astype(np.float32)
    n_keys = 2048
    keys = jax.random.split(jax.random.PRNGKey(0), n_keys)

    @jax.jit
    def deq_one(key):
        q, s = quantize_gh(jnp.asarray(gh), "int8", key)
        return dequantize_gh_sums(q, s)

    mean = np.asarray(jnp.mean(jax.vmap(deq_one)(keys), axis=0))
    scale = np.abs(gh).max(axis=0) / 127.0
    # per-element SR variance <= scale^2/4 -> mean std = scale/(2*sqrt(K));
    # 6 sigma over 512 samples keeps the flake rate negligible
    tol = 6.0 * scale / (2.0 * np.sqrt(n_keys))
    assert np.abs(mean - gh).max(axis=0)[0] < tol[0]
    assert np.abs(mean - gh).max(axis=0)[1] < tol[1]


def test_quantize_max_rows_caps_grid_against_int32_overflow():
    """The exact-accumulation theorem: with ``max_rows`` given, the grid is
    capped so qmax * rows < 2^31 — at 200k rows int16's effective qmax
    drops to 10737 while int8's 127 is untouched. Without the cap, a
    logistic root (every row's h ~ absmax) silently wraps int32."""
    rng = np.random.RandomState(0)
    # the real failure shape: every value at absmax (root-hessian-like)
    gh = np.full((64, 2), 0.25, np.float32)
    q16, s16 = quantize_gh(jnp.asarray(gh), "int16", jax.random.PRNGKey(0),
                           max_rows=200_000)
    cap = (2**31 - 1) // 200_000
    assert int(np.abs(np.asarray(q16)).max()) <= cap
    assert 200_000 * int(np.abs(np.asarray(q16)).max()) < 2**31
    # values still dequantize to ~the input at the coarser grid
    np.testing.assert_allclose(
        np.asarray(dequantize_gh_sums(q16, s16)), gh, rtol=2e-4
    )
    q8, _ = quantize_gh(jnp.asarray(gh), "int8", jax.random.PRNGKey(0),
                        max_rows=200_000)
    assert int(np.abs(np.asarray(q8)).max()) == 127  # int8 unaffected


def test_int16_large_row_count_trains():
    """Regression pin for the int32-overflow bug the 200k-row bench caught:
    80k rows x qmax 32767 would exceed 2^31 in the root hessian sum and
    train garbage (logloss stuck at log 2); the max_rows grid cap keeps
    the accumulation exact and the model learning."""
    rng = np.random.RandomState(0)
    n = 80_000
    x = rng.randn(n, 4).astype(np.float32)
    y = (x[:, 0] + 0.5 * x[:, 1] > 0).astype(np.float32)
    shards = [{"data": x, "label": y}]
    p = {"objective": "binary:logistic", "max_depth": 3, "eta": 0.3,
         "eval_metric": ["logloss"], "gh_precision": "int16"}
    _, m = _train(shards, 1, rounds=3, params=p, evals=[(shards, "train")])
    assert m["train"]["logloss"] < 0.45  # log(2) = 0.693 when wrapped


def test_quantize_zero_channel_and_clip_range():
    gh = np.zeros((16, 2), np.float32)
    q, s = quantize_gh(jnp.asarray(gh), "int16", jax.random.PRNGKey(0))
    assert q.dtype == jnp.int16
    np.testing.assert_array_equal(np.asarray(q), 0)
    np.testing.assert_array_equal(np.asarray(s), 1.0)  # amax=0 guard
    rng = np.random.RandomState(0)
    gh = rng.randn(1000, 2).astype(np.float32) * 100
    q, s = quantize_gh(jnp.asarray(gh), "int8", jax.random.PRNGKey(1))
    assert int(np.abs(np.asarray(q)).max()) <= 127


def test_int_histogram_builders_match_f32_of_quantized_values():
    """Either build accumulates the int buffer EXACTLY: the int32 histogram
    equals the f32 build of the same integer values (cast)."""
    rng = np.random.RandomState(1)
    n, F, nbt, nn = 257, 3, 9, 4
    bins = jnp.asarray(rng.randint(0, nbt, size=(n, F)), jnp.uint8)
    q = rng.randint(-127, 128, size=(n, 2))
    pos = jnp.asarray(rng.randint(0, nn, size=(n,)), jnp.int32)
    gh_i = jnp.asarray(q, jnp.int8)
    gh_f = jnp.asarray(q, jnp.float32)
    for impl in ("scatter", "onehot"):
        hi = build_histogram(bins, gh_i, pos, nn, nbt, impl=impl, chunk=64)
        hf = build_histogram(bins, gh_f, pos, nn, nbt, impl=impl, chunk=64)
        assert jnp.issubdtype(hi.dtype, jnp.integer), impl
        np.testing.assert_array_equal(
            np.asarray(hi, np.float32), np.asarray(hf), err_msg=impl
        )
    ns_i = node_sums(gh_i, pos, nn)
    assert ns_i.dtype == jnp.int32
    np.testing.assert_array_equal(
        np.asarray(ns_i, np.float32), np.asarray(node_sums(gh_f, pos, nn))
    )


def test_uniform_sampling_gathers_int_buffer_goss_dequantizes():
    rng = np.random.RandomState(2)
    n = 64
    gh_i = jnp.asarray(rng.randint(-127, 128, size=(n, 2)), jnp.int8)
    scale = jnp.asarray([0.5, 0.25], jnp.float32)
    valid = jnp.ones((n,), bool)
    key = jax.random.PRNGKey(0)
    rows, sel = sampling.sample_rows(
        gh_i, valid, key, sampling.SamplingSpec("uniform", rate=0.5)
    )
    assert sel.dtype == jnp.int8  # the int buffer rides compaction directly
    np.testing.assert_array_equal(np.asarray(sel), np.asarray(gh_i)[rows])
    spec = sampling.SamplingSpec("gradient_based", top_rate=0.25,
                                 other_rate=0.25)
    rows_g, sel_g = sampling.sample_rows(gh_i, valid, key, spec, scale=scale)
    assert sel_g.dtype == jnp.float32  # amplified compaction dequantizes
    top_n, _ = sampling.goss_counts(n, spec)
    # the deterministic top segment holds exactly the dequantized values
    np.testing.assert_allclose(
        np.asarray(sel_g)[:top_n],
        np.asarray(gh_i)[np.asarray(rows_g)[:top_n]].astype(np.float32)
        * np.asarray(scale),
        rtol=1e-6,
    )
    with pytest.raises(ValueError, match="scale"):
        sampling.sample_rows(gh_i, valid, key, spec)


# ---------------------------------------------------------------------------
# engine level — the acceptance contract
# ---------------------------------------------------------------------------


def test_int8_gh_accuracy_tracks_f32():
    """Final train logloss under int8 gh lands within the documented 5e-4
    of the f32 run on a real binary task (the bench gate's unit-level
    mirror), and int16 even closer."""
    x, y = _data()
    shards = [{"data": x[i::2], "label": y[i::2]} for i in range(2)]
    finals = {}
    for ghp in ("float32", "int8", "int16"):
        p = dict(_BASE, gh_precision=ghp)
        _, m = _train(shards, 2, rounds=10, params=p,
                      evals=[(shards, "train")])
        finals[ghp] = m["train"]["logloss"]
    assert abs(finals["int8"] - finals["float32"]) <= 5e-4
    assert abs(finals["int16"] - finals["float32"]) <= 1e-4


def test_same_seed_rerun_is_bitwise_identical():
    """Stochastic rounding included, the whole int8 forest and its
    predictions replay bit-identically for the same (seed, config)."""
    x, y = _data()
    shards = [{"data": x[i::2], "label": y[i::2]} for i in range(2)]

    def run():
        eng, _ = _train(shards, 2, rounds=6,
                        params=dict(_BASE, gh_precision="int8"))
        b = eng.get_booster()
        return _forest_arrays(b), b.predict(x, output_margin=True)

    (f1, m1), (f2, m2) = run(), run()
    for a, b in zip(f1, f2):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(m1, m2)


def test_different_seed_changes_rounding():
    """The SR stream really is live: a different params.seed draws
    different roundings (guards against the quantizer silently degrading
    to deterministic rounding)."""
    x, y = _data(256, seed=5)
    shards = [{"data": x, "label": y}]
    margins = []
    for seed in (0, 1):
        eng, _ = _train(shards, 1, rounds=3,
                        params=dict(_BASE, gh_precision="int8", seed=seed))
        margins.append(eng.get_booster().predict(x, output_margin=True))
    assert not np.array_equal(margins[0], margins[1])


def test_float32_default_dedupes_onto_default_program():
    """``gh_precision='float32'`` written out explicitly registers onto the
    SAME registry record as the default config with the IDENTICAL jaxpr
    fingerprint — the PR 10 explicit-C=1 discipline applied to the new
    knob. (The byte-exact collective-schedule golden for the default rows
    lives in test_feature_parallel.py.)"""
    from tools.rxgbverify import walker

    x, y = _data(64)
    shards = [{"data": x, "label": y}]
    with progreg.capture():
        progreg.clear()
        eng = TpuEngine(shards, parse_params(_BASE), num_actors=2)
        eng.build_programs()
        recs = [r for r in progreg.records() if r.name == "engine.step"]
        assert len(recs) == 1
        fp_default = walker.trace_record(recs[0]).fingerprint
        assert fp_default and not fp_default.startswith("trace-error")

        eng2 = TpuEngine(
            shards, parse_params(dict(_BASE, gh_precision="float32")),
            num_actors=2,
        )
        eng2.build_programs()
        recs2 = [r for r in progreg.records() if r.name == "engine.step"]
        assert len(recs2) == 1 and recs2[0].registrations >= 2
        assert walker.trace_record(recs2[0]).fingerprint == fp_default
    progreg.clear()


@pytest.mark.parametrize("extra", [
    {"subsample": 0.5},
    {"sampling_method": "gradient_based", "top_rate": 0.2,
     "other_rate": 0.2},
    {"grow_policy": "lossguide", "max_leaves": 8},
    {"hist_quant": "int8", "hist_quant_min_bytes": 0},
], ids=["subsample", "goss", "lossguide", "int8wire"])
def test_int8_gh_composes(extra):
    """int8 gh through each composition leg: trains to a sane metric and
    reruns bitwise."""
    x, y = _data()
    shards = [{"data": x[i::2], "label": y[i::2]} for i in range(2)]
    p = dict(_BASE, gh_precision="int8", **extra)
    margins = []
    for _ in range(2):
        eng, m = _train(shards, 2, rounds=6, params=p,
                        evals=[(shards, "train")])
        margins.append(eng.get_booster().predict(x, output_margin=True))
        assert m["train"]["logloss"] < 0.4, extra
    np.testing.assert_array_equal(margins[0], margins[1])


def test_int8_gh_2d_mesh_bitwise_parity():
    """(R, 1) <-> (R, C) forest parity stays BITWISE under int8 gh: the SR
    key and pmax scales are feature-shard-invariant (rows replicate across
    the feature axis), and integer histogram sums have no reduction-order
    rounding at all."""
    x, y = _data()
    shards = [{"data": x[i::2], "label": y[i::2]} for i in range(2)]
    e1, _ = _train(shards, 2, rounds=6,
                   params=dict(_BASE, gh_precision="int8"))
    e2, _ = _train(shards, 2, rounds=6,
                   params=dict(_BASE, gh_precision="int8",
                               feature_parallel=2))
    for a, b in zip(_forest_arrays(e1.get_booster()),
                    _forest_arrays(e2.get_booster())):
        np.testing.assert_array_equal(a, b)


def test_gh_plane_bytes_shrink_4x():
    x, y = _data(256)
    shards = [{"data": x, "label": y}]
    sizes = {}
    for ghp in ("float32", "int16", "int8"):
        eng = TpuEngine(shards, parse_params(dict(_BASE, gh_precision=ghp)),
                        num_actors=2)
        sizes[ghp] = eng.gh_plane_bytes_per_shard()
    assert sizes["float32"] == 4 * sizes["int8"]
    assert sizes["float32"] == 2 * sizes["int16"]


def test_gh_precision_param_validation():
    assert parse_params({}).gh_precision == "float32"
    assert parse_params({"gh_precision": "int8"}).gh_precision == "int8"
    assert parse_params({"gh_precision": None}).gh_precision == "float32"
    with pytest.raises(ValueError, match="gh_precision"):
        parse_params({"gh_precision": "fp8"})
    with pytest.raises(NotImplementedError, match="gblinear"):
        parse_params({"gh_precision": "int8", "booster": "gblinear"})
    # composition with hist_quant parses (wire and plane are orthogonal)
    out = parse_params({"gh_precision": "int8", "hist_quant": "int8"})
    assert out.gh_precision == "int8" and out.hist_quant == "int8"


def test_custom_objective_gated():
    x, y = _data(64)
    shards = [{"data": x, "label": y}]
    p = parse_params(dict(_BASE, gh_precision="int8"))
    p.objective = CustomObjective(
        fn=lambda preds, d: (preds, np.ones_like(preds)),
        base=get_objective("binary:logistic"),
    )
    with pytest.raises(NotImplementedError, match="custom objective"):
        TpuEngine(shards, p, num_actors=2)


def test_elastic_shrink_growback_parity_under_int8_gh(monkeypatch):
    """Elastic shrink -> boundary grow-back continuation under int8 gh:
    zero replay, the world restored, and the whole chaotic run (stochastic
    rounding included) bitwise reproducible chaos-vs-chaos."""
    from xgboost_ray_tpu import RayDMatrix, RayParams, faults, train

    monkeypatch.setenv("RXGB_RESTART_BACKOFF_BASE_S", "0")
    monkeypatch.setenv("RXGB_ELASTIC_RESTART_RESOURCE_CHECK_S", "0")
    monkeypatch.setenv("RXGB_ELASTIC_RESTART_GRACE_PERIOD_S", "0")
    x, y = _data(512, seed=7)
    params = dict(_BASE, gh_precision="int8", max_depth=3)

    def run():
        plan = faults.FaultPlan(rules=[
            {"site": "actor.train_round", "action": "raise", "ranks": [1],
             "match": {"round": 3}},
            # hold rank 1's reload past the scheduler's 1 s fast path so
            # the world really shrinks, then grows back at a boundary
            {"site": "actor.load_shard", "action": "delay", "delay_s": 2.0,
             "match": {"rank": 1}, "at": 2},
        ])
        res = {}
        with faults.active_plan(plan):
            bst = train(params, RayDMatrix(x, y), 12,
                        additional_results=res,
                        ray_params=RayParams(num_actors=2,
                                             elastic_training=True,
                                             max_failed_actors=1,
                                             max_actor_restarts=2,
                                             checkpoint_frequency=4))
        return bst.predict(x, output_margin=True), res
    m1, res1 = run()
    m2, res2 = run()
    rob = res1["robustness"]
    assert rob["rounds_replayed"] == 0
    assert rob["restarts"] == 0
    assert rob["shrinks"] == 1 and rob["grows"] == 1
    assert res1["total_n"] == 512  # the boundary grow restored the world
    np.testing.assert_array_equal(m1, m2)
    assert ({k: v for k, v in rob.items() if not k.endswith("_s")}
            == {k: v for k, v in res2["robustness"].items()
                if not k.endswith("_s")})


# ---------------------------------------------------------------------------
# rxgbverify: the VER004 gh-precision extension
# ---------------------------------------------------------------------------


def test_ver004_flags_f32_program_claiming_int8_gh():
    """True positive: an engine.step whose meta claims gh_precision=int8
    but whose jaxpr carries no int8 aval (and psums the histogram in f32)
    must be flagged — the 'hidden upcast at the source' failure mode."""
    from tools.rxgbverify import checks, walker

    x, y = _data(64)
    shards = [{"data": x, "label": y}]
    with progreg.capture():
        progreg.clear()
        eng = TpuEngine(shards, parse_params(_BASE), num_actors=2)
        eng.build_programs()
        rec = [r for r in progreg.records() if r.name == "engine.step"][0]
        rec.meta = dict(rec.meta, gh_precision="int8")  # the planted lie
        t = walker.trace_record(rec)
    progreg.clear()
    findings = checks.check_precision_flow([t])
    assert any(f.rule == "VER004" and "no int8 aval" in f.message
               for f in findings)
    assert any(f.rule == "VER004" and "upcast before accumulation"
               in f.message for f in findings)


def test_gh_matrix_rows_trace_clean_and_nonvacuous():
    """The new gh_precision matrix rows re-trace clean through every VER*
    check, and really carry what VER004 certifies: int8 avals, exact int32
    histogram psums (unquantized wire), the int8 all_to_all composition,
    and the GOSS exemption (its dequantized compaction must NOT flag)."""
    from tools.rxgblint import catalog
    from tools.rxgbverify import checks
    from tools.rxgbverify.matrix import FULL_MATRIX, trace_matrix

    entries = [e for e in FULL_MATRIX if "gh" in e.label]
    assert len(entries) >= 5  # int8/int16/wire-composition/goss/2d rows
    traced = trace_matrix(entries=entries)
    assert traced and all(t.ok for t in traced), [
        t.error for t in traced if not t.ok
    ]
    findings = checks.run_checks(traced, catalog.mesh_axes(),
                                 root=catalog.REPO_ROOT)
    assert findings == [], [f.render() for f in findings]
    steps = [t for t in traced if t.record.name == "engine.step"]
    plain = [t for t in steps
             if t.record.meta.get("gh_precision") == "int8"
             and t.record.meta.get("hist_quant") == "none"
             and t.record.meta.get("sampling") != "gradient_based"]
    assert plain
    for t in plain:
        assert "int8" in t.analysis.dtypes
        assert any(c.prim == "psum" and c.dtype == "int32"
                   and len(c.shape) >= 4 for c in t.analysis.collectives)
    composed = [t for t in steps
                if t.record.meta.get("gh_precision") == "int8"
                and t.record.meta.get("hist_quant") == "int8"]
    assert composed
    for t in composed:
        assert any(c.prim == "all_to_all" and c.dtype == "int8"
                   for c in t.analysis.collectives)
        # composition never round-trips the payload through a f32 psum
        assert not any(c.prim == "psum" and c.dtype == "float32"
                       and len(c.shape) >= 4
                       for c in t.analysis.collectives)
    goss = [t for t in steps
            if t.record.meta.get("gh_precision") == "int8"
            and t.record.meta.get("sampling") == "gradient_based"]
    assert goss  # present AND clean (the carve-out works)

"""Quantized histogram allreduce (``hist_quant``) — the per-round psum hot
path with an int8/int16 wire format (ops/histogram.py).

Covers the acceptance contract: keystone half/joint accuracy under int8,
1-actor vs 2-actor structural identity, deterministic (bit-identical across
shards) merging, and the measured allreduce payload-byte reduction.

Size threshold: payloads under ``hist_quant_min_bytes`` (default 32 KiB)
keep the exact f32 psum — small collectives are latency-bound, and exactness
below the threshold keeps small-problem tree structure invariant to the
world size. Tests that exercise the quantized wire itself therefore pass
``hist_quant_min_bytes=0`` (quantize everything), while the structural-
identity test pins the DEFAULT contract.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from xgboost_ray_tpu.engine import TpuEngine
from xgboost_ray_tpu.ops.histogram import (
    AllreduceBytes,
    quantized_hist_allreduce,
)
from xgboost_ray_tpu.params import parse_params

shard_map = functools.partial(jax.shard_map, check_vma=False)


def _one_hot_fixture():
    eye = np.eye(4, dtype=np.float32)
    x = np.concatenate([np.tile(eye[[0, 1]], (8, 1)), np.tile(eye[[2, 3]], (8, 1))])
    y = np.concatenate(
        [np.tile([1.0, 0.0], 8), np.tile([1.0, 0.0], 8)]
    ).astype(np.float32)
    return x, y, eye


_KEYSTONE = {
    "objective": "binary:logistic",
    "max_depth": 3,
    "eta": 0.5,
    "eval_metric": ["logloss", "error"],
    "reg_lambda": 0.0,
    "min_child_weight": 0.0,
}


def _train(shards, num_actors, rounds=10, params=None, **kw):
    eng = TpuEngine(shards, parse_params(params or _KEYSTONE), num_actors, **kw)
    last = None
    for i in range(rounds):
        last = eng.step(i)
    return eng, last


# ---------------------------------------------------------------------------
# op level
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode,rel_tol", [("int8", 0.05), ("int16", 2e-4)])
def test_quantized_allreduce_matches_psum(mode, rel_tol):
    """The quantized merge approximates the f32 psum within the mode's
    granularity, and every shard sees a BIT-IDENTICAL merged histogram
    (deterministic rounding, shared scales)."""
    n_dev = len(jax.devices())
    mesh = Mesh(np.array(jax.devices()), ("actors",))
    rng = np.random.RandomState(0)
    nn, F, nbt = 4, 3, 17  # rows (nn*F) NOT divisible by 8: exercises padding
    # per-(node, feature) magnitudes spanning 4 orders: per-row scales must
    # hold relative accuracy where a global scale could not
    mags = 10.0 ** rng.uniform(-2, 2, size=(nn, F, 1, 1)).astype(np.float32)
    local = (rng.randn(n_dev, nn, F, nbt, 2).astype(np.float32) * mags)

    def f(h):
        out = quantized_hist_allreduce(
            h[0], "actors", mode, n_dev, None, min_bytes=0
        )
        return out[None]

    mapped = jax.jit(
        shard_map(f, mesh=mesh, in_specs=P("actors"), out_specs=P("actors"))
    )
    # out_specs P("actors") keeps every shard's copy visible for the
    # bit-identity check
    out = np.asarray(mapped(jnp.asarray(local)))
    for i in range(1, n_dev):
        np.testing.assert_array_equal(out[i], out[0])
    ref = local.sum(axis=0)
    # error bound: two roundings at 1/qmax of the per-(node, feature) absmax
    amax = np.abs(ref).max(axis=(2, 3), keepdims=True)
    err = np.abs(out[0] - ref) / np.maximum(amax, 1e-12)
    assert err.max() < rel_tol, err.max()


def test_quantized_allreduce_none_and_subthreshold_are_exact_psum():
    n_dev = len(jax.devices())
    mesh = Mesh(np.array(jax.devices()), ("actors",))
    local = np.random.RandomState(1).randn(n_dev, 2, 3, 9, 2).astype(np.float32)
    ref = local.sum(axis=0)

    for mode, min_bytes in (("none", 0), ("int8", 1 << 20)):
        def f(h):
            return quantized_hist_allreduce(
                h[0], "actors", mode, n_dev, None, min_bytes=min_bytes
            )[None]

        out = np.asarray(
            jax.jit(
                shard_map(f, mesh=mesh, in_specs=P("actors"),
                          out_specs=P("actors"))
            )(jnp.asarray(local))
        )
        # sub-threshold int8 payloads take the identical exact-psum path
        np.testing.assert_allclose(out[0], ref, rtol=1e-6, atol=1e-6)


def test_quantized_allreduce_zero_histogram():
    """All-zero histograms (empty nodes) must survive the scale guard."""
    n_dev = len(jax.devices())
    mesh = Mesh(np.array(jax.devices()), ("actors",))
    local = np.zeros((n_dev, 2, 2, 9, 2), np.float32)

    for mode in ("int8", "int8_block"):
        def f(h):
            return quantized_hist_allreduce(
                h[0], "actors", mode, n_dev, None, min_bytes=0, block=64
            )[None]

        out = np.asarray(
            jax.jit(shard_map(f, mesh=mesh, in_specs=P("actors"), out_specs=P("actors")))(
                jnp.asarray(local)
            )
        )
        np.testing.assert_array_equal(out[0], 0.0)


# ---------------------------------------------------------------------------
# block-scaled (ring) wire modes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode,qmax", [("int8_block", 127),
                                       ("int16_block", 32767)])
def test_block_allreduce_matches_psum_within_ring_bound(mode, qmax):
    """The block-scaled ring merge approximates the f32 psum within the
    provable per-hop bound, and every shard sees a BIT-IDENTICAL merged
    histogram (each chunk's final value is computed by exactly one actor
    along its ring path, then gathered)."""
    n_dev = len(jax.devices())
    mesh = Mesh(np.array(jax.devices()), ("actors",))
    rng = np.random.RandomState(0)
    nn, F, nbt = 4, 3, 17  # flat size 408 not divisible by 8*block
    mags = 10.0 ** rng.uniform(-2, 2, size=(nn, F, 1, 1)).astype(np.float32)
    local = rng.randn(n_dev, nn, F, nbt, 2).astype(np.float32) * mags
    B = 64  # small block so the grid has several blocks per chunk

    def f(h):
        return quantized_hist_allreduce(
            h[0], "actors", mode, n_dev, None, min_bytes=0, block=B
        )[None]

    out = np.asarray(
        jax.jit(shard_map(f, mesh=mesh, in_specs=P("actors"),
                          out_specs=P("actors")))(jnp.asarray(local))
    )
    for i in range(1, n_dev):
        np.testing.assert_array_equal(out[i], out[0])
    ref = local.sum(axis=0)
    # rigorous bound: n_dev roundings (n-1 hops + publish), each at most
    # running_absmax/qmax of its block; the running absmax is bounded by
    # the per-block max of sum(|local|) over actors. Replicate the ring's
    # flattened chunk/block grid to evaluate it per element.
    S = nn * F * nbt * 2
    pad = (-S) % n_dev
    chunk = (S + pad) // n_dev
    bpc = -(-chunk // B)
    cum = np.pad(np.abs(local).sum(axis=0).reshape(-1), (0, pad))
    cum = np.pad(cum.reshape(n_dev, chunk), ((0, 0), (0, bpc * B - chunk)))
    blk_amax = cum.reshape(n_dev, bpc, B).max(axis=2)  # [n, bpc]
    bound = np.repeat(blk_amax, B, axis=1)[:, :chunk].reshape(-1)
    bound = bound * (n_dev + 1) / qmax + 1e-6
    err = np.pad(np.abs(out[0] - ref).reshape(-1), (0, pad))
    assert (err <= bound).all(), (err.max(), mode)


def test_block_single_actor_two_roundings_bitwise():
    """The n_actors == 1 no-wire branch must apply exactly the two
    deterministic block-granular roundings of the multi-actor path (one at
    the first ring send, one at the publish requantize) — pinned bitwise
    against a numpy replica, so 1-actor and n-actor models stay on the same
    quantization contract."""
    rng = np.random.RandomState(4)
    nn, F, nbt, B = 3, 5, 17, 64
    h = (rng.randn(nn, F, nbt, 2) * 50).astype(np.float32)
    out = np.asarray(quantized_hist_allreduce(
        jnp.asarray(h), "actors", "int8_block", 1, None, min_bytes=0,
        block=B,
    ))

    def round_trip(flat):
        S = flat.size
        bpc = -(-S // B)
        vb = np.pad(flat, (0, bpc * B - S)).reshape(bpc, B)
        amax = np.abs(vb).max(axis=1)
        scale = np.where(amax > 0, amax / np.float32(127), np.float32(1.0))
        scale = scale.astype(np.float32)
        q = np.clip(np.round(vb / scale[:, None]), -127, 127).astype(np.int8)
        deq = (q.astype(np.float32) * scale[:, None]).reshape(-1)[:S]
        return deq.astype(np.float32)

    expect = round_trip(round_trip(h.reshape(-1))).reshape(h.shape)
    np.testing.assert_array_equal(out, expect)


def test_block_allreduce_bytes_match_ring_formula():
    """``AllreduceBytes.add_ppermute`` accounting: block-mode counted bytes
    equal the hand-derived ring formula 2(n-1) * (chunk + scale_words) at
    the HIGGS-shaped bench payload, and sit strictly below BOTH the
    mode="none" f32 psum bytes and the row-scale int8 bytes — the tentpole
    byte cut, measured from the traced program's own counter."""
    n_dev = len(jax.devices())
    mesh = Mesh(np.array(jax.devices()), ("actors",))
    nn, F, nbt = 16, 28, 257  # one deep level of the bench payload
    local = np.zeros((n_dev, nn, F, nbt, 2), np.float32)
    counters = {}
    for mode in ("none", "int8", "int8_block"):
        counter = AllreduceBytes(n_dev)

        def f(h):
            return quantized_hist_allreduce(
                h[0], "actors", mode, n_dev, counter, min_bytes=0
            )[None]

        jax.jit(shard_map(f, mesh=mesh, in_specs=P("actors"),
                          out_specs=P("actors")))(jnp.asarray(local))
        counters[mode] = counter.total

    S = nn * F * nbt * 2
    pad = (-S) % n_dev
    chunk = (S + pad) // n_dev
    bpc = -(-chunk // 512)  # default hist_quant_block
    payload = chunk * 1 + bpc * 4  # int8 data + bitcast f32 scales
    assert counters["int8_block"] == 2 * (n_dev - 1) * payload
    assert counters["int8_block"] < counters["int8"]
    assert counters["int8_block"] < counters["none"]


def test_add_ppermute_hops_and_a_loop_of_traced_length():
    """Unit contract of the counter's ring term: nbytes * hops; and of a
    loop whose trips the device counts (the leaf-wise grower's passes): what
    one trip records, once however often the body is traced, times the
    trips, joins the scalar as a traced term."""
    c = AllreduceBytes(8)
    arr = np.zeros((100,), np.int8)
    c.add_ppermute(arr)
    assert c.total == 100
    c.add_ppermute(arr, hops=7)
    assert c.total == 800
    mark = c.mark()
    for _ in range(2):  # a body traced twice
        c.rewind(mark)
        c.add_ppermute(arr, hops=2)
    one_trip = c.since(mark)
    c.rewind(mark)
    c.add_trips(one_trip, jnp.int32(3))
    assert one_trip == (200, 2) and (c.total, c.calls) == (800, 8)
    assert int(c.as_scalar()) == 800 + 600
    assert int(c.mesh_stats()[0]) == 8 + 3 * 2
    # no loop, no traced term: the scalar is the static total's constant
    assert int(AllreduceBytes(8).as_scalar()) == 0


# ---------------------------------------------------------------------------
# engine level — the acceptance contract
# ---------------------------------------------------------------------------


def test_int8_keystone_joint_matches_f32():
    """Keystone half/joint end-to-end under hist_quant='int8' with the wire
    quantized at EVERY level (min_bytes=0, strictly harder than the default
    threshold): joint 2-actor training still recovers 100% accuracy and the
    final train metric is within 1e-3 relative of the f32 run."""
    x, y, eye = _one_hot_fixture()
    shards = [
        {"data": x[:16], "label": y[:16]},
        {"data": x[16:], "label": y[16:]},
    ]
    finals = {}
    for hq in ("none", "int8"):
        p = dict(_KEYSTONE)
        p.update(hist_quant=hq, hist_quant_min_bytes=0)
        eng, metrics = _train(shards, 2, params=p, evals=[(shards, "train")])
        finals[hq] = metrics["train"]
        pred = eng.get_booster().predict(eye)
        assert pred[0] > 0.9 and pred[2] > 0.9
        assert pred[1] < 0.1 and pred[3] < 0.1
    assert finals["int8"]["error"] == 0.0
    a, b = finals["none"]["logloss"], finals["int8"]["logloss"]
    assert abs(a - b) / max(abs(a), 1e-12) < 1e-3


def _forest_structure(forest):
    return (
        np.asarray(forest.feature),
        np.asarray(forest.split_bin),
        np.asarray(forest.threshold),
    )


def test_int8_keystone_structural_noop_per_world_size():
    """On the keystone fixture every level payload sits under the default
    size threshold, so hist_quant='int8' must be a BIT-EXACT no-op: for each
    world size, the int8 forest is structurally identical to the f32 forest
    (same split features/bins/thresholds).

    Why per world size and not 1-actor-vs-2-actor directly: the keystone's
    symmetric patterns produce exactly tied gains, and even pure-f32
    training breaks those ties differently under different shardings (psum
    reassociation) — pinned by test_f32_keystone_tie_breaking_baseline
    below. Quantization must not make that any worse, which the no-op
    property guarantees."""
    x, y, _ = _one_hot_fixture()
    for shards in (
        [{"data": x, "label": y}],
        [{"data": x[:16], "label": y[:16]}, {"data": x[16:], "label": y[16:]}],
    ):
        structures = {}
        for hq in ("none", "int8"):
            p = dict(_KEYSTONE)
            p["hist_quant"] = hq
            eng, _ = _train(shards, len(shards), params=p)
            structures[hq] = _forest_structure(eng.get_booster().forest)
        for a, b in zip(structures["none"], structures["int8"]):
            np.testing.assert_array_equal(a, b)


def test_int8_world_size_structural_identity_where_f32_has_it():
    """On a tie-free fixture whose payloads stay sub-threshold, 1-actor and
    2-actor training produce structurally identical trees under f32 — and
    hist_quant='int8' preserves that property exactly. (In the quantized
    regime a lossy wire cannot guarantee near-ties break identically under
    different shardings — the same class of effect f32 psum reassociation
    already exhibits on exactly tied gains.)"""
    rng = np.random.RandomState(7)
    x = rng.randn(400, 5).astype(np.float32)
    y = (x[:, 0] * 2 + np.sin(x[:, 1]) + 0.1 * rng.randn(400)).astype(np.float32)
    for hq in ("none", "int8"):
        p = {"objective": "reg:squarederror", "max_depth": 3, "eta": 0.3,
             "hist_quant": hq}
        structures = []
        for n in (1, 2):
            shards = [{"data": x[i::n], "label": y[i::n]} for i in range(n)]
            eng, _ = _train(shards, n, rounds=5, params=p)
            structures.append(_forest_structure(eng.get_booster().forest))
        for a, b in zip(*structures):
            np.testing.assert_array_equal(a, b)


def test_f32_keystone_tie_breaking_baseline():
    """Pin the PRE-EXISTING baseline behavior the structural contract is
    defined against: pure-f32 keystone training already breaks its
    symmetric gain ties differently for 1 vs 2 actors (psum
    reassociation). If this ever starts passing, the no-op framing above
    can be upgraded to direct world-size structural identity."""
    x, y, _ = _one_hot_fixture()
    structures = []
    for shards in (
        [{"data": x, "label": y}],
        [{"data": x[:16], "label": y[:16]}, {"data": x[16:], "label": y[16:]}],
    ):
        eng, _ = _train(shards, len(shards))
        structures.append(_forest_structure(eng.get_booster().forest))
    assert not np.array_equal(structures[0][0], structures[1][0])


def test_int16_tracks_f32_closely():
    """int16 granularity (1/32767) should land within regular numeric noise
    of the f32 model on a real regression task, with every level
    quantized."""
    rng = np.random.RandomState(3)
    x = rng.randn(512, 6).astype(np.float32)
    y = (x[:, 0] * 2 + np.sin(x[:, 1]) + 0.1 * rng.randn(512)).astype(np.float32)
    shards = [{"data": x, "label": y}]
    preds = {}
    for hq in ("none", "int16"):
        p = {"objective": "reg:squarederror", "max_depth": 4, "eta": 0.3,
             "eval_metric": ["rmse"], "hist_quant": hq,
             "hist_quant_min_bytes": 0}
        eng, metrics = _train(shards, 4, rounds=15, params=p,
                              evals=[(shards, "train")])
        preds[hq] = metrics["train"]["rmse"]
    assert preds["int16"] < 0.35
    assert abs(preds["none"] - preds["int16"]) / preds["none"] < 0.02


def test_allreduce_bytes_counter_measures_reduction():
    """The device-side byte counter reports the real wire-format saving:
    >= 3.5x for int8 vs the f32 psum on the 8-way mesh at a HIGGS-shaped
    feature count (every level payload clears the default size threshold;
    4x is the dtype ratio, the gap is scales + the small exact node-total
    psums that ride along in every mode)."""
    rng = np.random.RandomState(0)
    x = rng.randn(512, 28).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.float32)
    shards = [{"data": x[i::8], "label": y[i::8]} for i in range(8)]
    bytes_per = {}
    for hq in ("none", "int8", "int16", "int8_block"):
        p = {"objective": "binary:logistic", "max_depth": 4, "hist_quant": hq}
        eng, _ = _train(shards, 8, rounds=1, params=p)
        bytes_per[hq] = eng.hist_allreduce_bytes_per_round()
        assert bytes_per[hq] is not None and bytes_per[hq] > 0
    assert bytes_per["none"] / bytes_per["int8"] >= 3.5
    assert bytes_per["none"] / bytes_per["int16"] >= 1.7
    # the tentpole cut: the block ring (no pre-pass, in-band block scales)
    # moves strictly fewer bytes than the row-scale int8 schedule at the
    # same payload — at every level, so the per-round total is also below
    assert bytes_per["int8_block"] < bytes_per["int8"]


def test_scan_path_matches_per_round_under_int8():
    """The fused lax.scan path and per-round stepping share one traced round
    body; under quantization they must still produce identical forests."""
    rng = np.random.RandomState(11)
    x = rng.randn(300, 5).astype(np.float32)
    y = (x[:, 0] + 0.3 * x[:, 1] > 0).astype(np.float32)
    p = parse_params({"objective": "binary:logistic", "max_depth": 3,
                      "eta": 0.4, "hist_quant": "int8",
                      "hist_quant_min_bytes": 0})
    shards = [{"data": x, "label": y}]

    eng_scan = TpuEngine(shards, p, num_actors=2)
    assert eng_scan.can_batch_rounds()
    eng_scan.step_many(0, 4)
    assert eng_scan.hist_allreduce_bytes_per_round() > 0
    eng_step = TpuEngine(shards, p, num_actors=2)
    for i in range(4):
        eng_step.step(i)
    np.testing.assert_allclose(
        eng_scan.get_booster().predict(x, output_margin=True),
        eng_step.get_booster().predict(x, output_margin=True),
        atol=1e-5,
    )


def test_hist_quant_lossguide_and_dense_impl():
    """The quantized wire plugs into both growers and the dense build."""
    rng = np.random.RandomState(5)
    x = rng.randn(500, 8).astype(np.float32)
    y = (x[:, 2] > 0).astype(np.float32)
    shards = [{"data": x, "label": y}]
    for extra in (
        {"grow_policy": "lossguide", "max_leaves": 8},
        {"hist_impl": "onehot"},
    ):
        p = dict(_KEYSTONE)
        p.update(extra)
        p.update(hist_quant="int8", hist_quant_min_bytes=0)
        eng, metrics = _train(shards, 2, rounds=10, params=p,
                              evals=[(shards, "train")])
        assert metrics["train"]["error"] < 0.05, extra


def test_block_wire_logloss_tracks_f32_and_row():
    """Fast sanity tier of the wire-accuracy contract: int16_block lands
    within 5e-4 ABSOLUTE of the f32 reference even on a small fixture,
    and the int8-granularity wires (row and block) stay within 1e-2.

    The tight int8-class contract (block-vs-row ≤ 5e-4, block no worse
    than row vs f32) lives in
    test_block_wire_logloss_bench_shape_contract at the 200k bench
    shape — at 4k rows the two int8 wires path-diverge by ~1e-3, which
    says nothing about the wire format."""
    rng = np.random.RandomState(9)
    x = rng.randn(4000, 28).astype(np.float32)
    y = (x[:, 0] + 0.6 * x[:, 1] - 0.4 * x[:, 2]
         + 0.3 * rng.randn(4000) > 0).astype(np.float32)
    shards = [{"data": x[i::8], "label": y[i::8]} for i in range(8)]
    ll = {}
    for hq in ("none", "int8", "int8_block", "int16_block"):
        p = {"objective": "binary:logistic", "max_depth": 5, "eta": 0.3,
             "eval_metric": ["logloss"], "hist_quant": hq,
             "hist_quant_min_bytes": 0}
        eng, metrics = _train(shards, 8, rounds=12, params=p,
                              evals=[(shards, "train")])
        ll[hq] = metrics["train"]["logloss"]
    assert abs(ll["int16_block"] - ll["none"]) <= 5e-4, ll
    for hq in ("int8", "int8_block"):
        assert abs(ll[hq] - ll["none"]) <= 1e-2, ll


def test_block_wire_logloss_bench_shape_contract():
    """Block-wire logloss contract at the EXACT bench protocol
    (make_higgs_like 200k x 28 seed 0, eta 0.1, depth 6, max_bin 256,
    10 rounds, 8 actors, default min_bytes — every level quantized):

    - int16_block lands within 5e-4 ABSOLUTE of the f32 reference
      (measured 7.1e-5); this arm carries the paper's 5e-4 bound.
    - int8_block agrees with the established int8 ROW wire to within
      5e-4 (measured 6.1e-5): same int8 granularity, finer scales.
    - int8_block is no further from f32 than the row mode it replaces
      (both measured ~1.1e-3 absolute; int8-granularity wires cannot
      hold 5e-4 vs f32 on this task, so the absolute gate is pinned
      only where it physically holds)."""
    from bench import make_higgs_like
    from xgboost_ray_tpu import RayDMatrix, RayParams, train

    x, y = make_higgs_like(200_000, 28, seed=0)

    def logloss(bst):
        m = np.asarray(bst.predict(x, output_margin=True),
                       np.float64).ravel()
        p = np.clip(1.0 / (1.0 + np.exp(-m)), 1e-15, 1 - 1e-15)
        return float(-np.mean(y * np.log(p) + (1 - y) * np.log1p(-p)))

    ll = {}
    for hq in ("none", "int8", "int8_block", "int16_block"):
        p = {"objective": "binary:logistic", "eval_metric": ["logloss"],
             "max_depth": 6, "eta": 0.1, "max_bin": 256,
             "tree_method": "tpu_hist", "hist_quant": hq}
        bst = train(p, RayDMatrix(x, y), num_boost_round=10,
                    ray_params=RayParams(num_actors=8,
                                         checkpoint_frequency=0))
        ll[hq] = logloss(bst)
    assert abs(ll["int16_block"] - ll["none"]) <= 5e-4, ll
    assert abs(ll["int8_block"] - ll["int8"]) <= 5e-4, ll
    assert (abs(ll["int8_block"] - ll["none"])
            <= abs(ll["int8"] - ll["none"]) + 5e-4), ll


def test_block_wire_same_seed_bitwise_rerun():
    """Same seed, same params, same sharding: two block-wire runs produce
    BITWISE-identical forests and margins (deterministic rounding, a single
    computation path per ring chunk)."""
    rng = np.random.RandomState(12)
    x = rng.randn(600, 8).astype(np.float32)
    y = (x[:, 0] + 0.3 * x[:, 1] > 0).astype(np.float32)
    shards = [{"data": x[i::4], "label": y[i::4]} for i in range(4)]
    p = {"objective": "binary:logistic", "max_depth": 4, "eta": 0.4,
         "seed": 7, "hist_quant": "int8_block", "hist_quant_min_bytes": 0}
    margins = []
    for _ in range(2):
        eng, _ = _train(shards, 4, rounds=6, params=p)
        margins.append(
            np.asarray(eng.get_booster().predict(x, output_margin=True))
        )
    np.testing.assert_array_equal(margins[0], margins[1])


def test_block_structural_noop_sub_threshold():
    """Under the DEFAULT min_bytes threshold the keystone payloads all take
    the exact f32 psum, so hist_quant='int8_block' must be a bit-exact
    structural no-op — same contract the row modes pin."""
    x, y, _ = _one_hot_fixture()
    shards = [
        {"data": x[:16], "label": y[:16]},
        {"data": x[16:], "label": y[16:]},
    ]
    structures = {}
    for hq in ("none", "int8_block"):
        p = dict(_KEYSTONE)
        p["hist_quant"] = hq
        eng, _ = _train(shards, 2, params=p)
        structures[hq] = _forest_structure(eng.get_booster().forest)
    for a, b in zip(structures["none"], structures["int8_block"]):
        np.testing.assert_array_equal(a, b)


def test_hist_quant_param_validation():
    assert parse_params({"hist_quant": "int8"}).hist_quant == "int8"
    out = parse_params({})
    assert out.hist_quant == "none"
    assert out.hist_quant_min_bytes == 32768
    assert parse_params({"hist_quant_min_bytes": 0}).hist_quant_min_bytes == 0
    with pytest.raises(ValueError, match="hist_quant"):
        parse_params({"hist_quant": "fp4"})
    assert parse_params({"hist_quant": "int8_block"}).hist_quant == "int8_block"
    assert parse_params({"hist_quant": "int16_block"}).hist_quant_block == 512
    assert parse_params({"hist_quant_block": 1024}).hist_quant_block == 1024
    for bad in (0, 63, 100, 1 << 21, -512):
        with pytest.raises(ValueError, match="hist_quant_block"):
            parse_params({"hist_quant_block": bad})

"""Quantile sketch and feature binning.

TPU-native replacement for the quantile sketch + CSR binning that the
reference delegates to the xgboost C++ core (DMatrix construction at
``xgboost_ray/main.py:379-445``, iterator feed at
``xgboost_ray/matrix.py:127-196``).

Design
------
Instead of the GK-style weighted quantile sketch, we use a *histogram CDF*
sketch that is (a) fully vectorized, (b) exactly mergeable across shards via a
single ``psum`` — so the distributed sketch is one collective, not a
tree-merge protocol:

1. per-feature global ``min``/``max`` (ignoring NaN)         -> psum-min/max
2. fine-grained weighted histogram (``SKETCH_BINS`` buckets) -> psum
3. cut points read off the merged CDF at equi-weight quantiles

Bin encoding: present values map to ``0 .. max_bin-1``; missing (NaN) maps to
the reserved bin ``max_bin``.  A split at bin ``s`` sends ``bin <= s`` left,
which corresponds to the raw-value rule ``x < cuts[f, s]``.

Everything here is shape-static and jittable; the distributed variants live in
``xgboost_ray_tpu/parallel``.
"""

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Number of fine histogram buckets used by the sketch. Must be >= max_bin;
# larger values give a more faithful quantile approximation.
SKETCH_BINS = 2048


def bin_dtype(max_bin: int):
    """Smallest integer dtype that can hold bins 0..max_bin (missing == max_bin)."""
    return np.uint8 if max_bin + 1 <= 256 else np.int16


# ---------------------------------------------------------------------------
# Host-side (numpy) sketch: used by the central data loader, where the driver
# sees the full dataset. Exact quantiles over the observed values.
# ---------------------------------------------------------------------------


def _sketch_cuts_np_loop(
    x: np.ndarray, max_bin: int, sample_weight: Optional[np.ndarray] = None
) -> np.ndarray:
    """Reference per-feature-loop implementation of :func:`sketch_cuts_np`.

    Kept (non-exported) as the bitwise oracle the vectorized version is
    pinned against in ``tests/test_streaming.py`` — host sketching sits on
    the streaming ingest hot path now, so the vectorized form is the one
    that ships.
    """
    x = np.asarray(x, dtype=np.float32)
    if x.ndim != 2:
        raise ValueError(f"expected 2-D feature matrix, got shape {x.shape}")
    n, num_features = x.shape
    qs = np.arange(1, max_bin, dtype=np.float64) / max_bin
    cuts = np.empty((num_features, max_bin - 1), dtype=np.float32)
    for f in range(num_features):
        col = x[:, f]
        mask = ~np.isnan(col)
        vals = col[mask]
        if vals.size == 0:
            cuts[f] = 0.0
            continue
        if sample_weight is not None:
            w = np.asarray(sample_weight, dtype=np.float64)[mask]
            order = np.argsort(vals, kind="stable")
            sv, sw = vals[order], w[order]
            cw = np.cumsum(sw)
            total = cw[-1]
            if total <= 0:
                cuts[f] = np.quantile(vals, qs).astype(np.float32)
                continue
            idx = np.searchsorted(cw / total, qs, side="left")
            idx = np.clip(idx, 0, sv.size - 1)
            cuts[f] = sv[idx].astype(np.float32)
        else:
            cuts[f] = np.quantile(vals, qs).astype(np.float32)
    return cuts


def sketch_cuts_np(
    x: np.ndarray, max_bin: int, sample_weight: Optional[np.ndarray] = None
) -> np.ndarray:
    """Compute per-feature cut points on the host. Returns [F, max_bin-1].

    Cut points are the (i+1)/max_bin weighted quantiles of each feature's
    non-missing values. Duplicate cuts are allowed (they produce empty bins,
    which split finding simply never selects).

    Vectorized across the feature axis (bitwise-equal to
    :func:`_sketch_cuts_np_loop`): the unweighted path is one
    ``nanquantile`` over axis 0; the weighted path sorts every column at
    once (stable, NaN last, NaN weights zeroed so the tail is inert) and
    reads the weighted CDF per feature with the loop's exact
    ``searchsorted(..., side='left')``, with no float-key arithmetic that
    could flip boundary cases.
    """
    x = np.asarray(x, dtype=np.float32)
    if x.ndim != 2:
        raise ValueError(f"expected 2-D feature matrix, got shape {x.shape}")
    n, num_features = x.shape
    qs = np.arange(1, max_bin, dtype=np.float64) / max_bin
    nan = np.isnan(x)
    all_nan = nan.all(axis=0)

    def unweighted_cuts(cols: np.ndarray, cols_all_nan: np.ndarray):
        with np.errstate(invalid="ignore"), \
                np.testing.suppress_warnings() as sup:
            sup.filter(RuntimeWarning)
            out = (
                np.nanquantile(cols, qs, axis=0).T.astype(np.float32)
                if n else np.zeros((cols.shape[1], max_bin - 1), np.float32)
            )
        out[cols_all_nan] = 0.0
        return out

    if sample_weight is None or n == 0:
        return unweighted_cuts(x, all_nan)

    w = np.asarray(sample_weight, dtype=np.float64).reshape(n, 1)
    w_eff = np.where(nan, 0.0, w)  # [n, F]
    order = np.argsort(x, axis=0, kind="stable")  # NaN sorts last
    sv = np.take_along_axis(x, order, axis=0)
    sw = np.take_along_axis(w_eff, order, axis=0)
    cw = np.cumsum(sw, axis=0)
    total = cw[-1] if n else np.zeros(num_features)
    weighted_ok = total > 0
    z = cw / np.where(weighted_ok, total, 1.0)[None, :]
    # per-feature searchsorted('left') on the sorted CDF == count of
    # z < q, the loop oracle's exact semantics (a flat float-offset key
    # could collapse z-vs-q boundary cases; per-quantile full-matrix
    # comparison counts would be O(max_bin·N·F)). The zero-weight NaN
    # tail holds z == 1.0 exactly, never counted for q < 1.
    zt = np.ascontiguousarray(z.T)
    idx = np.empty((num_features, max_bin - 1), np.int64)
    for f in range(num_features):
        idx[f] = np.searchsorted(zt[f], qs, side="left")
    finite_n = n - nan.sum(axis=0)
    idx = np.clip(idx, 0, np.maximum(finite_n, 1)[:, None] - 1)
    cuts = np.take_along_axis(sv, idx.T, axis=0).T.astype(np.float32)
    if weighted_ok.all():
        return cuts
    # unweighted fallback only for the zero-total-weight columns (the loop
    # oracle's np.quantile arm) — not a full second quantile pass
    bad = ~weighted_ok
    cuts[bad] = unweighted_cuts(x[:, bad], all_nan[bad])
    return cuts


def validate_feature_types_count(cat_features, n_features: int) -> None:
    """Every categorical feature index must name a real column."""
    if any(i >= n_features for i in cat_features):
        raise ValueError("feature_types has more entries than features.")


def validate_categorical_codes(
    x: np.ndarray, cat_features, max_bin: int
) -> None:
    """Categorical columns must hold integer codes in [0, max_bin-2]
    (NaN = missing is fine). The ONE validator shared by the engine's
    materialized load and the streamed per-chunk mirror, so the two paths
    structurally cannot accept different data."""
    validate_feature_types_count(cat_features, x.shape[1])
    for fi in cat_features:
        col = x[:, fi]
        vals = col[~np.isnan(col)]
        if vals.size and (
            (vals < 0).any()
            or (vals != np.round(vals)).any()
            or vals.max() > max_bin - 2
        ):
            raise ValueError(
                f"categorical feature {fi} must hold integer codes in "
                f"[0, {max_bin - 2}] (max_bin={max_bin}); raise max_bin or "
                f"re-encode the column."
            )


def _f32_order_keys(a: np.ndarray) -> np.ndarray:
    """Strictly order-preserving uint64 keys of float32 values: the
    sign-flipped bit pattern, with -0.0 normalized to +0.0 first so float
    equality survives the transform. NaN keys are unspecified (mask them)."""
    a = np.asarray(a, np.float32) + np.float32(0.0)  # -0.0 -> +0.0
    u = a.view(np.uint32)
    keys = np.where(u >> 31 == 1, ~u, u | np.uint32(0x80000000))
    return keys.astype(np.uint64)


def _bin_matrix_np_loop(x: np.ndarray, cuts: np.ndarray, max_bin: int) -> np.ndarray:
    """Reference per-feature-loop implementation of :func:`bin_matrix_np`
    (the bitwise oracle for the flat-searchsorted version)."""
    x = np.asarray(x, dtype=np.float32)
    n, num_features = x.shape
    out = np.empty((n, num_features), dtype=bin_dtype(max_bin))
    for f in range(num_features):
        col = x[:, f]
        b = np.searchsorted(cuts[f], col, side="right")
        b = np.where(np.isnan(col), max_bin, b)
        out[:, f] = b.astype(out.dtype)
    return out


#: row-block size bounding bin_matrix_np's transient uint64 key buffers
#: (~4 x F x 8 bytes per row in flight; 8192 rows x F=2048 ≈ 0.5 GB would
#: be the 65536 figure — the streaming budget wants these transients small)
_BIN_BLOCK_ROWS = 8192


def bin_matrix_np(x: np.ndarray, cuts: np.ndarray, max_bin: int) -> np.ndarray:
    """Bin a raw feature matrix on the host. Returns [N, F] ints in 0..max_bin.

    bin(x) = #cuts <= x  (``searchsorted(..., side='right')``), NaN -> max_bin.

    One flat ``searchsorted`` over the whole feature axis instead of a
    per-column Python loop (this is the streaming ingest hot path; at
    F=2048 the per-column loop is real time): values and cuts map through
    the order-preserving float32 bit-pattern keys, offset per feature by
    ``f << 32`` so feature blocks can never interleave — bitwise-equal to
    :func:`_bin_matrix_np_loop` by strict monotonicity of the key map.
    """
    x = np.asarray(x, dtype=np.float32)
    cuts = np.asarray(cuts, np.float32)
    if np.isnan(cuts).any():
        # NaN keys are unspecified under _f32_order_keys, so NaN cuts (a
        # feature whose quantiles mix -inf and +inf) would break the flat
        # key array's sortedness and bin silently differently from the
        # per-feature oracle — fail loudly instead
        raise ValueError(
            "cut points contain NaN (a feature holding both -inf and "
            "+inf?); clean non-finite values out of the feature matrix."
        )
    n, num_features = x.shape
    n_cuts = cuts.shape[1]
    feat_off = (np.arange(num_features, dtype=np.uint64) << np.uint64(32))
    flat_cuts = (_f32_order_keys(cuts) + feat_off[:, None]).ravel()
    out = np.empty((n, num_features), dtype=bin_dtype(max_bin))
    for lo in range(0, n, _BIN_BLOCK_ROWS):
        hi = min(lo + _BIN_BLOCK_ROWS, n)
        block = x[lo:hi]
        keys = _f32_order_keys(block) + feat_off[None, :]
        b = np.searchsorted(flat_cuts, keys.ravel(), side="right").reshape(
            hi - lo, num_features
        )
        b = b - np.arange(num_features, dtype=np.int64)[None, :] * n_cuts
        out[lo:hi] = np.where(np.isnan(block), max_bin, b).astype(out.dtype)
    return out


# ---------------------------------------------------------------------------
# Device-side (jax) sketch: building blocks for the distributed path. The
# min/max and fine histogram are per-shard quantities that the caller merges
# with psum before calling cuts_from_sketch.
# ---------------------------------------------------------------------------


def feature_min_max(x: jnp.ndarray, valid: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-feature (min, max) over valid, non-NaN entries. x: [N, F], valid: [N]."""
    mask = valid[:, None] & ~jnp.isnan(x)
    big = jnp.float32(np.finfo(np.float32).max)
    mn = jnp.min(jnp.where(mask, x, big), axis=0)
    mx = jnp.max(jnp.where(mask, x, -big), axis=0)
    return mn, mx


def sketch_histogram(
    x: jnp.ndarray,
    valid: jnp.ndarray,
    mn: jnp.ndarray,
    mx: jnp.ndarray,
    weight: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Fine weighted histogram per feature over [mn, mx]. Returns [F, SKETCH_BINS].

    Mergeable across shards by summation (psum).
    """
    n, num_features = x.shape
    scale = jnp.where(mx > mn, (mx - mn), 1.0)
    t = (x - mn[None, :]) / scale[None, :]
    idx = jnp.clip((t * SKETCH_BINS).astype(jnp.int32), 0, SKETCH_BINS - 1)
    mask = valid[:, None] & ~jnp.isnan(x)
    w = jnp.ones((n,), jnp.float32) if weight is None else weight.astype(jnp.float32)
    wv = jnp.where(mask, w[:, None], 0.0)
    # One scatter-add per feature via segment offsets into a flat histogram.
    flat_idx = idx + (jnp.arange(num_features, dtype=jnp.int32) * SKETCH_BINS)[None, :]
    hist = jnp.zeros((num_features * SKETCH_BINS,), jnp.float32)
    hist = hist.at[flat_idx.reshape(-1)].add(wv.reshape(-1))
    return hist.reshape(num_features, SKETCH_BINS)


def sketch_histogram_items(
    vals: jnp.ndarray, wts: jnp.ndarray, mn: jnp.ndarray, mx: jnp.ndarray
) -> jnp.ndarray:
    """Rasterize per-feature summary items onto the fine sketch grid.

    The streamed-ingest analog of :func:`sketch_histogram`: instead of raw
    rows, the input is one actor's exported quantile-sketch summary —
    ``vals``/``wts`` [F, C] (inert slots hold (+inf, 0)). The bucket-index
    formula is identical, so the merged histogram feeds the SAME
    :func:`cuts_from_sketch` readout and the psum merge shape matches the
    materialized sketch program collective for collective.
    """
    num_features, _cap = vals.shape
    scale = jnp.where(mx > mn, (mx - mn), 1.0)
    t = (vals - mn[:, None]) / scale[:, None]
    idx = jnp.clip((t * SKETCH_BINS).astype(jnp.int32), 0, SKETCH_BINS - 1)
    mask = jnp.isfinite(vals) & (wts > 0)
    wv = jnp.where(mask, wts.astype(jnp.float32), 0.0)
    flat_idx = idx + (
        jnp.arange(num_features, dtype=jnp.int32) * SKETCH_BINS
    )[:, None]
    hist = jnp.zeros((num_features * SKETCH_BINS,), jnp.float32)
    hist = hist.at[flat_idx.reshape(-1)].add(wv.reshape(-1))
    return hist.reshape(num_features, SKETCH_BINS)


def cuts_from_sketch(
    mn: jnp.ndarray, mx: jnp.ndarray, hist: jnp.ndarray, max_bin: int
) -> jnp.ndarray:
    """Turn a merged fine histogram into cut points [F, max_bin-1].

    Reads the CDF at equi-weight quantiles; cut value is the upper edge of the
    bucket where the quantile falls, mapped back to feature scale.
    """
    num_features = hist.shape[0]
    cdf = jnp.cumsum(hist, axis=1)
    total = jnp.maximum(cdf[:, -1:], 1e-12)
    cdf = cdf / total
    qs = jnp.arange(1, max_bin, dtype=jnp.float32) / max_bin  # [B-1]
    # For each quantile, the first bucket whose cdf >= q.
    # cdf: [F, S], qs: [B-1] -> idx [F, B-1]
    idx = jax.vmap(lambda c: jnp.searchsorted(c, qs, side="left"))(cdf)
    idx = jnp.clip(idx, 0, SKETCH_BINS - 1)
    scale = jnp.where(mx > mn, (mx - mn), 1.0)
    edges = (idx.astype(jnp.float32) + 1.0) / SKETCH_BINS  # upper edge in [0,1]
    return mn[:, None] + edges * scale[:, None]


def bin_matrix(x: jnp.ndarray, cuts: jnp.ndarray, max_bin: int) -> jnp.ndarray:
    """Device-side binning. x: [N, F] float, cuts: [F, max_bin-1] -> [N, F] ints.

    ``bin(x) = #cuts <= x``: ``searchsorted(side="right")`` on sorted cuts,
    exactly -- duplicated cuts, infinities and NaN cuts (which no value
    reaches) included --, NaN values -> ``max_bin``; in :func:`bin_dtype`
    (uint8 where ``max_bin + 1 <= 256``, else int16), which the count is
    also kept in.

    The count is one compare of every value against every cut of its feature,
    contracted over the cuts axis with a vector of ones: no per-value search.
    ``jnp.searchsorted`` is a ``while`` of ``log2(max_bin)`` dependent steps
    with a ``gather`` from the cut table in each, and on a v5e those 8 x 28
    gathers over 11M rows were 25.8 of the 26.6 s that binning 11M x 28
    values took (0.26 s in this form; PERF.md section 6, PR 32) -- seconds
    the first round program's lowering waited for. Written as a contraction
    and not as ``sum(x >= cuts)`` because the CPU backend fuses the compare
    into a dot but materialises it in front of a reduce (2.9 GB at 200,000 x
    28 x 255); the chip's compiler makes the same reduce fusion of both and
    never holds the ``[N, F, max_bin - 1]`` predicate. The cost is linear in
    ``max_bin`` where the search's was logarithmic: 1,024 bins are 4x this
    count, 2,000 features 70x, each still far under one gather pass.
    """
    out_dtype = bin_dtype(max_bin)
    below = (x[:, :, None] >= cuts[None, :, :]).astype(out_dtype)
    bins = jnp.einsum(
        "nfc,c->nf", below, jnp.ones((cuts.shape[1],), out_dtype),
        preferred_element_type=out_dtype,
    )
    return jnp.where(jnp.isnan(x), jnp.asarray(max_bin, out_dtype), bins)

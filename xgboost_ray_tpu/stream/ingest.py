"""Two-pass streamed ingestion pipeline (the engine's streamed data plane).

Pass 1 (``sketch_pass``): each shard's chunk stream runs through a
deterministic :class:`~xgboost_ray_tpu.stream.sketch.StreamSketch` on the
host while the small per-row columns (label/weight/base_margin/bounds)
accumulate — the raw [N, F] float32 matrix never exists; peak memory is
O(chunk + sketch).

Cuts merge (``merged_cuts``): per-device merged summaries ride a shard_map
program with the SAME collective shape as the materialized sketch
(``pmin(min) → pmax(max) → psum(fine histogram) → psum(missing mass)``,
reusing ``ops/binning.py``'s grid and CDF readout) — registered under the
same ``engine.sketch_cuts`` program name so rxgbverify's schedule-identity
pass certifies streamed and materialized worlds execute identical
collective sequences.

Pass 2 (``bin_upload_pass``): chunks re-stream, bin on the host with the
vectorized ``bin_matrix_np`` straight into ``bin_dtype`` blocks, and a
:class:`~xgboost_ray_tpu.stream.upload.DoubleBufferedUploader` overlaps the
H2D transfer of each block part with the binning of the next chunk. Each
phase emits fenced spans (``data.sketch_chunk`` / ``data.cuts_merge`` /
``data.bin_chunk`` / ``data.h2d``), so a streamed load is reconstructible
from the timeline alone.
"""

import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from xgboost_ray_tpu import obs, progreg
from xgboost_ray_tpu.constants import AXIS_ACTORS, SHARD_COLUMN_FILLS
from xgboost_ray_tpu.ops import binning
from xgboost_ray_tpu.stream.reader import ShardStream
from xgboost_ray_tpu.stream.sketch import DEFAULT_EXPORT_CAPACITY, StreamSketch
from xgboost_ray_tpu.stream.upload import DoubleBufferedUploader


class PassOneResult:
    """Sketches + small columns of one streamed load's first pass."""

    def __init__(self):
        self.sketches: List[StreamSketch] = []
        self.shard_rows: List[int] = []
        self.label: Optional[np.ndarray] = None
        self.weight: Optional[np.ndarray] = None
        self.base_margin: Optional[np.ndarray] = None
        self.qid: Optional[np.ndarray] = None
        self.lower: Optional[np.ndarray] = None
        self.upper: Optional[np.ndarray] = None
        self.n_rows = 0
        self.n_features = 0
        self.sketch_s = 0.0
        self.wall_s = 0.0
        self.chunks = 0


def _concat_optional(parts: List[List[Optional[np.ndarray]]],
                     shard_rows: List[int],
                     fill: Optional[float]) -> Optional[np.ndarray]:
    """Concatenate a per-shard list of per-chunk optional columns with
    ``_concat_shards`` semantics: absent everywhere -> None; absent on some
    shards -> synthesized fill for those shards (None fill: zeros)."""
    present = [any(p is not None for p in shard) for shard in parts]
    if not any(present):
        return None
    out = []
    for shard, rows, has in zip(parts, shard_rows, present):
        if has:
            if any(p is None for p in shard):
                raise ValueError(
                    "a streamed column is present in some chunks of a shard "
                    "but not others"
                )
            out.append(np.concatenate([np.asarray(p, np.float32).ravel()
                                       for p in shard]))
        else:
            val = 0.0 if fill is None else fill
            out.append(np.full(rows, val, np.float32))
    return np.concatenate(out) if len(out) > 1 else out[0]




def apriori_sketch_bytes(
    streams: Sequence[ShardStream], n_features: int, cap: int
) -> int:
    """Summed a-priori sketch estimate across shards, per stream at the
    level count it will actually reach (levels ~ log2(rows/capacity),
    ceiling MAX_LEVELS) — a fixed small multiplier would let long streams
    outgrow the budget mid-pass with the fail-fast already passed. Summed
    because the driver holds EVERY shard's sketch concurrently through
    pass 1. Closed form: never allocates sketch-sized arrays itself."""
    from xgboost_ray_tpu.stream.sketch import MAX_LEVELS

    base_bytes = StreamSketch.level_nbytes(n_features, cap)
    return sum(
        base_bytes * min(
            MAX_LEVELS,
            max(1, (max(s.n_rows, 1) // max(cap, 1)).bit_length()) + 1,
        )
        for s in streams
    )


def export_summary_ceiling(n_features: int) -> int:
    """Ceiling on the per-device export-summary item count the cuts merge
    will use (the F-scaled cap in :func:`merged_cuts`) — shared with the
    budget model so the merge's stacked summaries are a charged term."""
    return (
        DEFAULT_EXPORT_CAPACITY if n_features <= 128
        else 2048 if n_features <= 1024 else 512
    )


def prevalidate_budget(
    streams: Sequence[ShardStream],
    block_rows: int,
    bin_itemsize: int,
    n_devices: int,
) -> None:
    """The FULL streaming-budget fail-fast, callable BEFORE any byte
    streams: every input — each shard's declared rows, the mesh block
    size, the bin dtype, the merge's summary ceiling — is known up front,
    so the N-scaling block-buffer and cuts-merge terms must not wait for
    the end of pass 1 (hours of I/O on a beyond-RAM load) to reject the
    config."""
    if not streams:
        return
    n_features = streams[0].n_features
    est = apriori_sketch_bytes(
        streams, n_features, streams[0].sketch_capacity
    )
    # stacked [n_devices, F, export_cap] f32 vals + wts summaries the cuts
    # merge holds on host before device_put
    merge_bytes = (
        n_devices * n_features * export_summary_ceiling(n_features) * 4 * 2
    )
    for s in streams:
        s.config.validate_budget(
            s.n_rows, s.n_features, s.chunk_rows, est,
            block_rows=block_rows, bin_itemsize=bin_itemsize,
            merge_bytes=merge_bytes,
        )


def sketch_pass(
    streams: Sequence[ShardStream],
    max_bin: int,
    cat_features: Sequence[int] = (),
) -> PassOneResult:
    """Pass 1: stream every shard once, building per-shard sketches and the
    small per-row columns."""
    tracer = obs.get_tracer()
    res = PassOneResult()
    res.n_features = streams[0].n_features
    # before any chunk validation indexes columns — the engine's shared
    # loud error, not a fork of it
    binning.validate_feature_types_count(cat_features, res.n_features)
    cap = streams[0].sketch_capacity
    for s in streams:
        if s.n_features != res.n_features:
            raise ValueError(
                f"streamed shards disagree on feature count "
                f"({s.n_features} vs {res.n_features})"
            )
        if s.sketch_capacity != cap:
            raise ValueError("streamed shards disagree on sketch capacity")
    wall0 = time.perf_counter()
    # "qid" is deliberately absent: the per-chunk gate below rejects it on
    # first sight, so collecting it would be dead plumbing
    cols: Dict[str, List[List[Optional[np.ndarray]]]] = {
        k: [] for k in ("label", "weight", "base_margin",
                        "label_lower_bound", "label_upper_bound")
    }
    est_sketch_total = apriori_sketch_bytes(streams, res.n_features, cap)
    for s in streams:
        s.config.validate_budget(
            s.n_rows, s.n_features, s.chunk_rows, est_sketch_total
        )
    for s in streams:
        sketch = StreamSketch(res.n_features, capacity=cap)
        shard_cols = {k: [] for k in cols}
        rows = 0
        for chunk in s.chunks():
            if chunk.get("qid") is not None:
                # gate on the FIRST qid-carrying chunk — a beyond-RAM load
                # must not stream to completion before learning its query
                # groups cannot be honored
                raise NotImplementedError(
                    "streamed ingestion does not support qid/ranking data "
                    "yet (query groups need a global contiguity sort the "
                    "chunk pipeline cannot do); materialize the matrix for "
                    "ranking."
                )
            x = np.asarray(chunk["data"], np.float32)
            binning.validate_categorical_codes(x, cat_features, max_bin)
            t0 = time.perf_counter()
            with tracer.span(
                "data.sketch_chunk", rows=int(x.shape[0]),
                shard=len(res.sketches),
            ):
                sketch.update(x, weight=chunk.get("weight"))
            res.sketch_s += time.perf_counter() - t0
            for k in shard_cols:
                shard_cols[k].append(chunk.get(k))
            rows += x.shape[0]
            res.chunks += 1
        if rows != s.n_rows:
            raise ValueError(
                f"stream produced {rows} rows but declared {s.n_rows}"
            )
        res.sketches.append(sketch)
        res.shard_rows.append(rows)
        for k in cols:
            cols[k].append(shard_cols[k])
    res.n_rows = sum(res.shard_rows)
    fills = SHARD_COLUMN_FILLS  # _concat_shards parity, one table
    res.label = _concat_optional(
        cols["label"], res.shard_rows, fill=fills["label"]
    )
    res.weight = _concat_optional(
        cols["weight"], res.shard_rows, fill=fills["weight"]
    )
    res.base_margin = _concat_optional(
        cols["base_margin"], res.shard_rows, fill=fills["base_margin"]
    )
    res.lower = _concat_optional(
        cols["label_lower_bound"], res.shard_rows,
        fill=fills["label_lower_bound"],
    )
    res.upper = _concat_optional(
        cols["label_upper_bound"], res.shard_rows,
        fill=fills["label_upper_bound"],
    )
    res.wall_s = time.perf_counter() - wall0
    return res


# ---------------------------------------------------------------------------
# cuts merge (device, same collective shape as the materialized sketch)
# ---------------------------------------------------------------------------


def merged_cuts(
    engine,
    pass1: PassOneResult,
) -> Tuple[jnp.ndarray, jnp.ndarray, np.ndarray, np.ndarray]:
    """Merge per-shard sketches into global cuts on the mesh.

    Shard sketches fold deterministically (rank order, round-robin over the
    ``n_devices`` mesh slots), export to fixed-shape summaries, and merge on
    device through pmin/pmax + histogram/missing psums — the materialized
    sketch program's exact collective schedule. Returns (cuts_dev [F, B-1],
    has_missing_dev [F] bool, cuts_np, rank_error_bound [F]).
    """
    tracer = obs.get_tracer()
    max_bin = engine.params.max_bin
    cat_features = engine._cat_features
    n_dev = engine.n_devices
    num_features = pass1.n_features
    with tracer.span("data.cuts_merge", world=n_dev) as span_attrs:
        groups: List[Optional[StreamSketch]] = [None] * n_dev
        for i, sk in enumerate(pass1.sketches):
            d = i % n_dev
            groups[d] = sk if groups[d] is None else groups[d].merge(sk)
        # export shape: tight power-of-two over the fullest group's live
        # items, capped by an F-scaled ceiling — the stacked [D, F, export]
        # summaries are the merge program's memory, so shipping mostly-inert
        # padding (or summaries far finer than the SKETCH_BINS grid they
        # rasterize onto) costs real RSS at wide F for no cut accuracy
        items_max = max(
            (g.item_count() for g in groups if g is not None), default=1
        )
        export_cap = min(
            export_summary_ceiling(num_features),
            max(256, 1 << (items_max - 1).bit_length()),
        )
        mns, mxs, valss, wtss, missws = [], [], [], [], []
        err = np.zeros(num_features, np.float64)
        for g in groups:
            if g is None:
                # inert empty summary, bitwise what an empty sketch exports
                # — without allocating its full [F, cap] level buffers
                mns.append(np.full(num_features, np.inf, np.float32))
                mxs.append(np.full(num_features, -np.inf, np.float32))
                valss.append(
                    np.full((num_features, export_cap), np.inf, np.float32)
                )
                wtss.append(
                    np.zeros((num_features, export_cap), np.float32)
                )
                missws.append(np.zeros(num_features, np.float32))
                continue
            vals, wts, g_err = g.export(export_cap)
            err += g_err
            mns.append(g.min)
            mxs.append(g.max)
            valss.append(vals)
            wtss.append(wts)
            missws.append(g.missing_weight.astype(np.float32))
        rows = NamedSharding(engine.mesh, P(AXIS_ACTORS))
        mn_dev = jax.device_put(np.stack(mns), rows)
        mx_dev = jax.device_put(np.stack(mxs), rows)
        vals_dev = jax.device_put(np.stack(valss), rows)
        wts_dev = jax.device_put(np.stack(wtss), rows)
        miss_dev = jax.device_put(np.stack(missws), rows)

        def fn(mn, mx, vals, wts, missw):
            mn = jax.lax.pmin(mn[0], AXIS_ACTORS)
            mx = jax.lax.pmax(mx[0], AXIS_ACTORS)
            hist = binning.sketch_histogram_items(vals[0], wts[0], mn, mx)
            hist = jax.lax.psum(hist, AXIS_ACTORS)
            cuts = binning.cuts_from_sketch(mn, mx, hist, max_bin)
            if cat_features:
                from xgboost_ray_tpu.ops.grow import cat_mask_const

                cat_mask = cat_mask_const(cat_features, num_features)
                code_cuts = jnp.arange(max_bin - 1, dtype=cuts.dtype) + 0.5
                cuts = jnp.where(cat_mask[:, None], code_cuts[None, :], cuts)
            miss = jax.lax.psum(missw[0], AXIS_ACTORS)
            return cuts, miss > 0

        mapped = jax.shard_map(
            fn,
            mesh=engine.mesh,
            in_specs=(
                P(AXIS_ACTORS), P(AXIS_ACTORS), P(AXIS_ACTORS),
                P(AXIS_ACTORS), P(AXIS_ACTORS),
            ),
            out_specs=(P(), P()),
            check_vma=False,
        )
        jit_fn = progreg.register_jit(
            "engine.sketch_cuts",
            mapped,
            example_args=(mn_dev, mx_dev, vals_dev, wts_dev, miss_dev),
            meta=engine._program_meta(),
        )
        cuts_dev, has_missing = jit_fn(
            mn_dev, mx_dev, vals_dev, wts_dev, miss_dev
        )
        # the pipeline's ONE documented device->host read: pass 2 bins on
        # the host against these cuts
        cuts_np = np.asarray(cuts_dev)
        span_attrs["rank_error_bound_max"] = float(err.max(initial=0.0))
    return cuts_dev, has_missing, cuts_np, err


# ---------------------------------------------------------------------------
# pass 2: bin on host, double-buffered upload, on-device assembly
# ---------------------------------------------------------------------------


def _mesh_block_devices(engine) -> List[Tuple[Any, List[Any]]]:
    """Per row-block (primary device, replica devices): 1D meshes have no
    replicas; a 2D row x feature mesh replicates each row block over the
    feature axis."""
    dev = np.asarray(engine.mesh.devices)
    if dev.ndim == 1:
        return [(d, []) for d in dev.tolist()]
    return [(row[0], list(row[1:])) for row in dev.tolist()]


def _upload_blocks(
    engine,
    rows_iter,
    num_features: int,
    prefetch: int,
) -> Tuple[jnp.ndarray, Dict[str, float]]:
    """Shared block assembly of the streamed data plane: consume binned
    ``[k, F]`` row batches arriving in GLOBAL row order, fill the per-actor
    ``bin_dtype`` block buffers, upload completed blocks double-buffered,
    and assemble the [pad_to, F] row-sharded device matrix.

    Rows arrive in global row order, so exactly ONE per-actor block buffer
    is being filled at any time; a completed block hands off to the
    background uploader (one H2D transfer per device block — the device
    holds exactly the final binned bytes, no concat/update churn) while the
    next batch is produced on the main thread. Peak host memory:
    O(batch + prefetch·block_bytes). Tail padding rows bin to the missing
    bucket — exactly where the materialized path's NaN-padded rows land, so
    a streamed matrix is indistinguishable downstream.

    Consumed by :func:`bin_upload_pass` (batches = freshly binned chunks)
    and :func:`reuse_bin_pass` (batches = donor fetches + re-binned chunks
    of the one replacement shard).
    """
    tracer = obs.get_tracer()
    max_bin = engine.params.max_bin
    dtype = binning.bin_dtype(max_bin)
    pad_to = engine.pad_to
    block = pad_to // engine.n_devices
    block_devices = _mesh_block_devices(engine)
    uploader = DoubleBufferedUploader(depth=prefetch, tracer=tracer)
    cursor = 0
    buf: Optional[np.ndarray] = None  # the block being filled

    def submit_rows(rows: np.ndarray) -> None:
        nonlocal cursor, buf
        pos = 0
        while pos < rows.shape[0]:
            b = cursor // block
            off = cursor - b * block
            if buf is None:
                buf = np.full((block, num_features), max_bin, dtype)
            take = min(block - off, rows.shape[0] - pos)
            buf[off : off + take] = rows[pos : pos + take]
            pos += take
            cursor += take
            if off + take == block:
                primary, replicas = block_devices[b]
                uploader.submit((b, 0), buf, primary)
                for ci, rdev in enumerate(replicas):
                    uploader.submit((b, ci + 1), buf, rdev)
                buf = None

    try:
        for rows in rows_iter:
            submit_rows(np.asarray(rows, dtype))
        if cursor < pad_to:
            # padding tail: the partially-filled block buffer already holds
            # the missing bucket in its unwritten rows; flush block by block
            while cursor < pad_to:
                b = cursor // block
                take = block * (b + 1) - cursor
                if buf is None:
                    buf = np.full((block, num_features), max_bin, dtype)
                cursor += take
                primary, replicas = block_devices[b]
                uploader.submit((b, 0), buf, primary)
                for ci, rdev in enumerate(replicas):
                    uploader.submit((b, ci + 1), buf, rdev)
                buf = None
        results = uploader.drain()
    finally:
        uploader.close()

    sharding = engine._row_sharding
    shape = (pad_to, num_features)
    per_device = {}
    for b, (primary, replicas) in enumerate(block_devices):
        for ci, dev in enumerate([primary] + replicas):
            per_device[dev] = results[(b, ci)]
    arrays = [
        per_device[d]
        for d, _idx in sharding.addressable_devices_indices_map(shape).items()
    ]
    bins_global = jax.make_array_from_single_device_arrays(
        shape, sharding, arrays
    )
    return bins_global, dict(uploader.stats())


def bin_upload_pass(
    engine,
    streams: Sequence[ShardStream],
    cuts_np: np.ndarray,
    sketch_bytes: int = 0,
) -> Tuple[jnp.ndarray, Dict[str, float]]:
    """Pass 2: re-stream chunks, bin each on the host straight into the
    current device block's ``bin_dtype`` buffer, and assemble the device
    matrix through :func:`_upload_blocks` (one block buffer filling while
    the previous block's H2D transfer is in flight).

    Returns (bins_global, stats).
    """
    tracer = obs.get_tracer()
    max_bin = engine.params.max_bin
    dtype = binning.bin_dtype(max_bin)
    num_features = cuts_np.shape[0]
    block = engine.pad_to // engine.n_devices
    prefetch = streams[0].config.prefetch
    # the full budget check: now that the mesh layout is known, the
    # N-scaling term (per-actor block buffers alive at once) is included
    streams[0].config.validate_budget(
        sum(s.n_rows for s in streams), num_features,
        max(s.chunk_rows for s in streams), sketch_bytes,
        block_rows=block, bin_itemsize=np.dtype(dtype).itemsize,
    )
    wall0 = time.perf_counter()
    bin_state = {"bin_s": 0.0}

    def binned_chunks():
        for si, s in enumerate(streams):
            for chunk in s.chunks():
                x = np.asarray(chunk["data"], np.float32)
                t0 = time.perf_counter()
                with tracer.span(
                    "data.bin_chunk", rows=int(x.shape[0]), shard=si
                ):
                    bins_chunk = binning.bin_matrix_np(x, cuts_np, max_bin)
                bin_state["bin_s"] += time.perf_counter() - t0
                yield bins_chunk

    bins_global, stats = _upload_blocks(
        engine, binned_chunks(), num_features, prefetch
    )
    stats.update({
        "bin_s": bin_state["bin_s"],
        "pass2_wall_s": time.perf_counter() - wall0,
    })
    return bins_global, stats


# ---------------------------------------------------------------------------
# elastic continuation: seed a new world's binned matrix from a donor engine
# (zero re-sketch, zero re-stream of surviving shards)
# ---------------------------------------------------------------------------


def plan_stream_reuse(
    streams: Sequence[ShardStream], donor, max_bin: Optional[int] = None
) -> Optional[List[Tuple]]:
    """Map each of this load's shard streams onto ``donor``'s retained
    binned rows (an elastic shrink/grow of a streamed world).

    Returns a per-shard plan — ``("donor", lo, hi)`` for a shard whose
    binned rows (and small columns) live in the donor engine at donor-global
    rows [lo, hi), ``("stream", shard_stream)`` for a shard the donor never
    streamed (a grow-back onto a NEW replacement actor: that one shard
    re-streams and bins against the donor's FROZEN cuts) — or ``None`` when
    the donor cannot seed this load at all (not streamed, different
    feature count / binning, or no shard overlap), in which case the
    caller falls through to the full sketch+bin pipeline.

    Shard identity is the stream fingerprint (deterministic in source,
    rank window, and chunking — the same identity the driver's engine
    cache keys on), so a matching shard's binned rows are bitwise the rows
    a re-stream would produce under the donor's cuts.
    """
    if donor is None or not getattr(donor, "_streamed", False):
        return None
    fps = getattr(donor, "_stream_shard_fps", None)
    shard_rows = getattr(donor, "_stream_shard_rows", None)
    cuts_np = getattr(donor, "_stream_cuts_np", None)
    if not fps or not shard_rows or cuts_np is None:
        return None
    if any(s.n_features != donor.n_features for s in streams):
        return None
    if max_bin is not None and int(donor.params.max_bin) != int(max_bin):
        # frozen cuts are only valid at the binning they were sketched for
        # (unreachable from the elastic driver — params are fixed within a
        # run — but a direct TpuEngine(stream_donor=) caller could differ)
        return None
    offsets = np.concatenate([[0], np.cumsum(shard_rows)])
    by_fp = {fp: i for i, fp in enumerate(fps)}
    plan: List[Tuple] = []
    reused = 0
    for s in streams:
        i = by_fp.get(s.fingerprint())
        if i is None:
            plan.append(("stream", s))
        else:
            plan.append(("donor", int(offsets[i]), int(offsets[i + 1])))
            reused += 1
    if reused == 0:
        return None
    return plan


def prevalidate_reuse_budget(
    streams: Sequence[ShardStream],
    plan: Sequence[Tuple],
    block_rows: int,
    bin_itemsize: int,
) -> None:
    """Budget fail-fast for the reuse path, callable BEFORE any byte of a
    re-streamed replacement shard moves: the re-stream charges the same
    chunk+binned+block model as the original ingest, with the donor-fetch
    slice (one block of already-binned rows) standing in for the sketch
    term. Zero-restream plans (a pure shrink) still validate the block
    buffers — the uploader keeps them alive either way."""
    if not streams:
        return
    n_features = streams[0].n_features
    fetch_bytes = block_rows * n_features * bin_itemsize
    n_rows = sum(s.n_rows for s in streams)
    restreamed = [s for s, e in zip(streams, plan) if e[0] == "stream"]
    for s in streams:
        chunk = s.chunk_rows if s in restreamed else min(
            s.chunk_rows, block_rows
        )
        s.config.validate_budget(
            n_rows, n_features, chunk, fetch_bytes,
            block_rows=block_rows, bin_itemsize=bin_itemsize,
        )


def reuse_columns_pass(
    streams: Sequence[ShardStream],
    plan: Sequence[Tuple],
    donor,
    max_bin: int,
    cat_features: Sequence[int] = (),
) -> PassOneResult:
    """The reuse path's stand-in for :func:`sketch_pass`: small per-row
    columns come from donor slices for reused shards, and from ONE chunk
    iteration for re-streamed shards (no sketch is built — cuts are the
    donor's frozen ones, which is the whole point). The re-streamed
    shards' data chunks are read again by :func:`reuse_bin_pass` — a
    deliberate tradeoff: binning here would have to buffer the whole
    shard's binned rows on the host until the mesh layout exists (the
    columns feed the engine's row layout BEFORE the bin assembly runs),
    breaking the O(chunk + block) memory contract, so the one replacement
    shard pays the same two-read cost the original ingest pays per shard
    and host memory stays bounded."""
    tracer = obs.get_tracer()
    res = PassOneResult()
    res.n_features = streams[0].n_features
    binning.validate_feature_types_count(cat_features, res.n_features)
    wall0 = time.perf_counter()
    col_keys = ("label", "weight", "base_margin",
                "label_lower_bound", "label_upper_bound")
    donor_cols = getattr(donor, "_stream_cols", None) or {}
    # per-column, per-shard chunk lists in _concat_optional's shape: a
    # donor-sourced shard contributes its slice as one "chunk", so the
    # merge below rides the SAME fill/concat contract sketch_pass uses
    cols: Dict[str, List[List[Optional[np.ndarray]]]] = {
        k: [] for k in col_keys
    }
    for s, entry in zip(streams, plan):
        if entry[0] == "donor":
            _, lo, hi = entry
            for k in col_keys:
                col = donor_cols.get(k)
                cols[k].append([None if col is None else col[lo:hi]])
            res.shard_rows.append(hi - lo)
            continue
        shard_cols: Dict[str, List[Optional[np.ndarray]]] = {
            k: [] for k in col_keys
        }
        rows = 0
        for chunk in s.chunks():
            if chunk.get("qid") is not None:
                raise NotImplementedError(
                    "streamed ingestion does not support qid/ranking data"
                )
            x = np.asarray(chunk["data"], np.float32)
            binning.validate_categorical_codes(x, cat_features, max_bin)
            for k in col_keys:
                shard_cols[k].append(chunk.get(k))
            rows += x.shape[0]
            res.chunks += 1
        if rows != s.n_rows:
            raise ValueError(
                f"stream produced {rows} rows but declared {s.n_rows}"
            )
        res.shard_rows.append(rows)
        for k in col_keys:
            cols[k].append(shard_cols[k])
    res.n_rows = sum(res.shard_rows)
    fills = SHARD_COLUMN_FILLS
    res.label = _concat_optional(
        cols["label"], res.shard_rows, fill=fills["label"]
    )
    res.weight = _concat_optional(
        cols["weight"], res.shard_rows, fill=fills["weight"]
    )
    res.base_margin = _concat_optional(
        cols["base_margin"], res.shard_rows, fill=fills["base_margin"]
    )
    res.lower = _concat_optional(
        cols["label_lower_bound"], res.shard_rows,
        fill=fills["label_lower_bound"],
    )
    res.upper = _concat_optional(
        cols["label_upper_bound"], res.shard_rows,
        fill=fills["label_upper_bound"],
    )
    res.wall_s = time.perf_counter() - wall0
    tracer.event(
        "data.bin_reuse",
        attrs={
            "rows": int(res.n_rows),
            "reused_shards": sum(1 for e in plan if e[0] == "donor"),
            "restreamed_shards": sum(1 for e in plan if e[0] == "stream"),
        },
    )
    return res


def reuse_bin_pass(
    engine,
    streams: Sequence[ShardStream],
    plan: Sequence[Tuple],
    donor,
    cuts_np: np.ndarray,
) -> Tuple[jnp.ndarray, Dict[str, float]]:
    """Assemble the new world's [pad_to, F] binned device matrix without
    re-sketching and without re-streaming surviving shards.

    Donor-resident shards are fetched from the donor's DEVICE binned
    matrix in block-sized slices (already-binned bytes — no raw f32 ever
    exists, and peak host stays O(block)); a shard the donor never held
    (grow-back onto a new replacement actor) re-streams and bins against
    the donor's frozen cuts, prevalidated against the budget model before
    its first byte streams. Everything rides the same double-buffered
    uploader as the original ingest."""
    tracer = obs.get_tracer()
    max_bin = engine.params.max_bin
    dtype = binning.bin_dtype(max_bin)
    num_features = int(cuts_np.shape[0])
    block = engine.pad_to // engine.n_devices
    prefetch = streams[0].config.prefetch
    itemsize = np.dtype(dtype).itemsize
    # defensive re-check of the engine's up-front reuse prevalidation (the
    # mesh layout is authoritative here)
    prevalidate_reuse_budget(
        streams, plan, block_rows=block, bin_itemsize=itemsize
    )
    wall0 = time.perf_counter()
    state = {"bin_s": 0.0, "reused_rows": 0, "restreamed_rows": 0}
    donor_bins = donor.bins
    donor_f_real = donor.n_features  # donor tiles may be feature-padded

    def batches():
        for si, (s, entry) in enumerate(zip(streams, plan)):
            if entry[0] == "donor":
                _, lo, hi = entry
                for a in range(lo, hi, block):
                    b = min(a + block, hi)
                    with tracer.span(
                        "data.bin_reuse", rows=int(b - a), shard=si
                    ):
                        # device gather + one host read of binned bytes;
                        # slice away feature padding when the donor ran a
                        # 2D (feature-sharded) mesh
                        rows = np.asarray(donor_bins[a:b])[:, :donor_f_real]
                    state["reused_rows"] += b - a
                    yield rows
                continue
            for chunk in s.chunks():
                x = np.asarray(chunk["data"], np.float32)
                t0 = time.perf_counter()
                with tracer.span(
                    "data.bin_chunk", rows=int(x.shape[0]), shard=si
                ):
                    bins_chunk = binning.bin_matrix_np(x, cuts_np, max_bin)
                state["bin_s"] += time.perf_counter() - t0
                state["restreamed_rows"] += x.shape[0]
                yield bins_chunk

    bins_global, stats = _upload_blocks(
        engine, batches(), num_features, prefetch
    )
    stats.update({
        "bin_s": state["bin_s"],
        "pass2_wall_s": time.perf_counter() - wall0,
        "reused_rows": state["reused_rows"],
        "restreamed_rows": state["restreamed_rows"],
        "reused_shards": sum(1 for e in plan if e[0] == "donor"),
        "restreamed_shards": sum(1 for e in plan if e[0] == "stream"),
    })
    return bins_global, stats

"""Gradient/hessian histogram construction.

TPU-native replacement for xgboost's C++ ``hist`` / CUDA ``gpu_hist``
histogram builders (selected by the user's ``params["tree_method"]``,
validated at ``xgboost_ray/main.py:1506-1524``). This is the hot op of GBDT
training: per boosting level we accumulate (grad, hess) sums into
``[n_nodes, n_features, n_bins+1, 2]`` buckets keyed by (row's node, feature,
feature bin). The merged-across-shards histogram is obtained by ``psum`` in
the shard_map round step (replacing the Rabit allreduce, SURVEY §5.8).

Two implementations, chosen from the platform (``default_hist_impl``) or by
name (params ``hist_impl``) in ``build_histogram``:

* ``hist_scatter`` — one flat XLA scatter-add. Correct everywhere; the CPU
  default, and what the tests hold the dense build to.
* ``hist_onehot`` — the dense MXU build: row-chunked ``onehot(bins)ᵀ @
  (gh ⊗ onehot(node))`` matmuls, one pass over all rows at every fan-out, no
  row order; the loop over row chunks and feature tiles bounds the one-hot
  transient. The bin index is factored, ``b = hi * L + lo``: the one-hot
  is of ``hi`` alone (``n_bins / L`` wide) and ``lo`` rides the right-hand
  side beside the node, so a narrow level makes 48-192 elements a (row,
  feature) where it made 256; ``L`` comes from the build's shape
  (``onehot_radix``): over 1 under 64 columns, and from 64 on only where a
  probe read it faster, with ``lo`` minor in a feature's columns. The
  default on an accelerator.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from xgboost_ray_tpu.obs import get_registry

# ---------------------------------------------------------------------------
# Quantized histogram allreduce (``hist_quant`` in params).
#
# The per-round hot path psums a full [n_nodes, F, n_bins+1, 2] float32
# histogram at every tree level; on a multi-chip mesh those collective bytes
# ARE the scaling cost (VERDICT r5: only the 8-chip projection beats the
# gpu_hist target). "Quantized Training of GBDTs" (arxiv 2207.09682) shows
# gradient histograms tolerate low-bit quantization, and EQuARX
# (arxiv 2506.17615) shows quantized allreduce recovers near-linear
# collective bandwidth. Two wire formats:
#
# Row scales ("int8" / "int16"):
#
#   1. per-(node, feature) symmetric scales from a pmax-merged absmax
#      (one tiny f32 pre-reduce — every actor agrees on the scales);
#   2. deterministic round-to-nearest quantization (NO stochastic rounding,
#      so every actor computes bit-identical payloads and the merged
#      histogram is bit-identical on every shard);
#   3. reduce-scatter as an int8/int16 all_to_all, with the accumulation
#      WIDENED to int32 on the receiving actor — actor counts cannot
#      overflow the narrow payload dtype;
#   4. the reduced rows are re-quantized against their own merged absmax
#      (same per-(node, feature) granularity) and all_gathered as
#      int8/int16 + one f32 scale per row.
#
# Block scales ("int8_block" / "int16_block") — the EQuARX schedule:
#
#   1. NO absmax pre-pass. Scales are per contiguous block of the FLATTENED
#      histogram (``hist_quant_block`` elements, default 512), computed from
#      whatever each actor holds locally at the moment it sends — the
#      full-extent pmax pre-reduce (a full-latency collective per merge) is
#      deleted from the schedule entirely;
#   2. the merge is a ppermute ring reduce-scatter: at each of the n-1 hops
#      an actor quantizes its running partial sum against its own running
#      block absmax, ships int8/int16 data + bitcast f32 block scales as ONE
#      in-band payload, and the receiver dequant-accumulates in f32 — the
#      wire is narrow on every hop;
#   3. after the ring each actor owns one fully-reduced chunk, built by a
#      single computation path — so the final requantize + tiled all_gather
#      (scales again in-band) publishes bit-identical results everywhere.
#
# Row-scale wire per element ~ 1 + 1/n bytes (int8) vs 4 for f32 psum, plus
# the pmax pre-pass. Block-scale wire = 2(n-1) * (S/n + 4*ceil(S/(n*B)))
# bytes for S elements at block B: fewer bytes AND one fewer full-latency
# collective per merge. Accuracy: row modes round twice at 1/127 (int8)
# per (node, feature); block modes round once per hop against the running
# block absmax (n_hops + 1 roundings at 1/127 per block of 512 elements —
# finer granularity, more roundings; 2207.09682 bounds both regimes).
# ---------------------------------------------------------------------------

HIST_QUANT_MODES = ("none", "int16", "int8", "int16_block", "int8_block")
_QMAX = {"int16": 32767, "int8": 127}
_QDTYPE = {"int16": jnp.int16, "int8": jnp.int8}
#: block-scaled wire modes -> the narrow dtype key their payloads use
HIST_QUANT_BLOCK_MODES = {"int16_block": "int16", "int8_block": "int8"}
#: default elements per in-band scale block (``hist_quant_block`` param)
HIST_QUANT_DEFAULT_BLOCK = 512

# Payloads below this ship as plain f32 psum even when a quantized mode is
# on: small collectives are latency-bound (quantizing them saves nothing and
# costs two extra dispatches), and keeping small histograms exact preserves
# world-size-invariant tree structure on small problems — sub-threshold
# levels see identical bin sums no matter how rows are sharded. 32 KiB is
# well under one HIGGS-shaped level payload (28 x 257 x 2 x 4 B ~ 57 KiB per
# node row), so production-scale meshes quantize every level.
HIST_QUANT_MIN_BYTES = 32768


#: what ``AllreduceBytes.mesh_stats`` holds, in order
MESH_STATS = ("collectives",)
#: what follows them where the round grew leaf-wise trees
#: (``note_lossguide``): full-row passes, nodes whose split was evaluated,
#: splits kept, and wanted nodes the node table had no room to expand (0
#: unless a tree evaluates more than ``ops.grow_lossguide.TABLE_FACTOR``
#: nodes a leaf; such a tree is no longer exactly best-first)
LOSSGUIDE_STATS = ("lossguide_passes", "lossguide_nodes_evaluated",
                   "lossguide_splits", "lossguide_table_overflows")


class AllreduceBytes:
    """Per-actor wire-byte counter for one traced round, under the standard
    ring-collective cost model.

    Every collective call site records the bytes an actor moves over the
    wire for that op — the quantity ICI/DCN actually carries, which is what
    the quantized modes are built to cut:

    * allreduce (psum/pmax) = reduce-scatter + all-gather:
      ``2 * (n-1)/n * bytes(operand)``
    * all_to_all: ``(n-1)/n * bytes(operand)``
    * all_gather: ``(n-1) * bytes(local chunk)`` (each actor receives every
      other actor's chunk)

    Operand shapes are jit-static, so trace-time accumulation counts
    exactly the traffic of the compiled collectives; the total is emitted
    as a device scalar next to the metrics, so the reduction of a quantized
    mode is *measured from the program that ran*, not asserted. On a
    1-device mesh every term is zero — there is no wire.

    Beside the bytes it counts what else only a mesh has, at the same call
    sites: ``calls``, the collectives a round (one per recorded op, a
    multi-hop ring once per hop), 0 on a 1-device axis, where there is no
    wire. They leave the round program through ``mesh_stats``, which is
    ``None`` -- no output at all -- where the round traced none, so a
    one-device program is the program it was.

    A loop of traced length (the leaf-wise grower's passes) counts through
    ``mark`` / ``rewind`` / ``since`` / ``add_trips``: what one trip
    records, times the trips the program counted, joins the totals as a
    traced term."""

    def __init__(self, n_actors: int):
        self.n = max(1, int(n_actors))
        self.total = 0  # python int: operand shapes are trace-time constants
        self.calls = 0
        # traced int32 terms of loops whose trip count the device decides
        self.dyn_total = 0
        self.dyn_calls = 0
        self.lossguide = None  # traced int32 [len(LOSSGUIDE_STATS)]

    @staticmethod
    def _nbytes(arr) -> int:
        return int(arr.size) * arr.dtype.itemsize

    def _add(self, nbytes: float, calls: int = 1) -> None:
        self.total += int(nbytes)
        if self.n > 1:
            self.calls += calls

    def add_allreduce(self, arr) -> None:
        self._add(2 * (self.n - 1) * self._nbytes(arr) / self.n)

    def add_all_to_all(self, arr) -> None:
        self._add((self.n - 1) * self._nbytes(arr) / self.n)

    def add_all_gather(self, chunk) -> None:
        self._add((self.n - 1) * self._nbytes(chunk))

    def add_ppermute(self, arr, hops: int = 1) -> None:
        """One ``ppermute`` ring hop: every actor ships the full operand to
        exactly one peer, so the per-actor wire cost is the operand itself
        (``hops`` times for a multi-hop ring recorded at one call site).
        Without this the counter would have no model for the block-scale
        ring and would silently charge it as an allreduce."""
        self._add(self._nbytes(arr) * int(hops), calls=int(hops))

    def mark(self):
        """The static totals now, for ``rewind`` and ``since``."""
        return self.total, self.calls

    def rewind(self, mark) -> None:
        """Forget what was recorded since ``mark``: the first line of a loop
        body, so that a body traced twice still counts one trip."""
        self.total, self.calls = mark

    def since(self, mark):
        """``(bytes, calls)`` recorded since ``mark``: one trip's worth where
        the loop's body began with ``rewind(mark)``."""
        return self.total - mark[0], self.calls - mark[1]

    def add_trips(self, one_trip, trips) -> None:
        """What ``one_trip`` (``since``) recorded, ``trips`` (traced) times."""
        per_total, per_calls = one_trip
        if per_total:
            self.dyn_total = self.dyn_total + trips * jnp.int32(per_total)
        if per_calls:
            self.dyn_calls = self.dyn_calls + trips * jnp.int32(per_calls)

    def note_lossguide(self, *stats) -> None:
        """One leaf-wise tree's ``LOSSGUIDE_STATS`` (traced int32 each, in
        that order), added to the round's."""
        stats = jnp.stack([jnp.asarray(v, jnp.int32) for v in stats])
        self.lossguide = stats if self.lossguide is None else (
            self.lossguide + stats)

    def absorb(self, other: Optional["AllreduceBytes"]) -> None:
        """Fold another counter's total into this one (e.g. the feature
        axis's own-ring-extent counter on a 2D mesh) so ``as_scalar`` stays
        the single emission point. ``None`` is a no-op."""
        if other is not None:
            self.total += int(other.total)
            self.calls += int(other.calls)
            self.dyn_total = self.dyn_total + other.dyn_total
            self.dyn_calls = self.dyn_calls + other.dyn_calls

    def as_scalar(self) -> jnp.ndarray:
        """The total as a device int32 (clamped; ~2 GB/round is beyond any
        real per-round payload)."""
        return _plus(jnp.int32(min(self.total, 2**31 - 1)), self.dyn_total)

    def mesh_stats(self) -> Optional[jnp.ndarray]:
        """``MESH_STATS`` of this shard's round as int32 ``[1]``, followed by
        its ``LOSSGUIDE_STATS`` where it grew leaf-wise trees, or ``None``
        where there is nothing to say (see the class's text)."""
        if not self.calls and self.lossguide is None:
            return None
        stats = jnp.stack([_plus(jnp.int32(self.calls), self.dyn_calls)])
        if self.lossguide is None:
            return stats
        return jnp.concatenate([stats, self.lossguide])


def _plus(static, traced):
    """``static + traced``, and ``static`` itself where no loop of traced
    length added a term (a program without one lowers as it did)."""
    return static if isinstance(traced, int) and traced == 0 else (
        static + traced)


def counting_psum(axis_name: str, counter: Optional[AllreduceBytes]):
    """A ``lax.psum`` wrapper that records its ring-model wire bytes."""

    def psum(x):
        if counter is not None:
            counter.add_allreduce(x)
        return jax.lax.psum(x, axis_name)

    return psum


def quantized_hist_allreduce(
    h: jnp.ndarray,  # [n_nodes, F, n_bins_total, 2] float32 local histogram
    axis_name: str,
    mode: str,
    n_actors: int,
    counter: Optional[AllreduceBytes] = None,
    min_bytes: int = HIST_QUANT_MIN_BYTES,
    block: int = HIST_QUANT_DEFAULT_BLOCK,
) -> jnp.ndarray:
    """Allreduce a histogram across ``axis_name`` with an optionally
    quantized wire format (see module comment). ``mode`` is one of
    ``HIST_QUANT_MODES``; ``"none"`` is the plain f32 psum, and payloads
    under ``min_bytes`` fall back to it (shape-static decision). ``block``
    is the scale granularity of the block-scaled modes (ignored by the row
    modes). The result is bit-identical on every shard in all modes.

    ``h`` may be an INT32 quantized-domain histogram (``gh_precision``
    int8/int16 gradients accumulate integer-exact): the fallback psum stays
    in int32 — an exact integer wire at the same 4 bytes/element — and the
    quantized wire stages read the f32 view of the integer sums (exact below
    2^24; the wire rounding is far coarser beyond)."""
    if mode == "none" or h.size * 4 < min_bytes:
        if counter is not None:
            counter.add_allreduce(h)
        return jax.lax.psum(h, axis_name)
    if mode in HIST_QUANT_BLOCK_MODES:
        return _block_scaled_allreduce(
            h, axis_name, HIST_QUANT_BLOCK_MODES[mode], n_actors, counter,
            int(block),
        )
    if mode not in _QMAX:
        raise ValueError(f"unknown hist_quant mode {mode!r}")
    qmax = _QMAX[mode]
    qdt = _QDTYPE[mode]
    nn, num_features, nbt, two = h.shape
    rows = nn * num_features
    cols = nbt * two
    hr = h.reshape(rows, cols)
    if hr.dtype != jnp.float32:
        hr = hr.astype(jnp.float32)

    # stage 1: shared per-(node, feature) scales from the global absmax of
    # the LOCAL histograms (pmax bounds every actor's values, so the
    # quantized payload always fits +-qmax)
    amax_local = jnp.max(jnp.abs(hr), axis=1)  # [rows] f32
    if counter is not None:
        counter.add_allreduce(amax_local)
    amax = jax.lax.pmax(amax_local, axis_name)
    scale = jnp.where(amax > 0, amax / qmax, 1.0)
    q = jnp.clip(jnp.round(hr / scale[:, None]), -qmax, qmax).astype(qdt)

    if n_actors == 1:
        # no wire (the counter's ring terms are all zero on 1 device): the
        # same two deterministic roundings as the multi-actor path, so
        # 1-actor and n-actor models see the same quantization contract
        merged = q.astype(jnp.int32).astype(jnp.float32) * scale[:, None]
        amax2 = jnp.max(jnp.abs(merged), axis=1)
        scale2 = jnp.where(amax2 > 0, amax2 / qmax, 1.0)
        q2 = jnp.clip(jnp.round(merged / scale2[:, None]), -qmax, qmax)
        return (q2 * scale2[:, None]).reshape(nn, num_features, nbt, two)

    # stage 2: reduce-scatter the narrow payload (all_to_all), accumulate
    # WIDENED to int32 — up to 2^23 actors cannot overflow an int8 payload
    pad = (-rows) % n_actors
    scale_p = jnp.pad(scale, (0, pad), constant_values=1.0) if pad else scale
    qp = jnp.pad(q, ((0, pad), (0, 0))) if pad else q
    chunk = (rows + pad) // n_actors
    if counter is not None:
        counter.add_all_to_all(qp)
    recv = jax.lax.all_to_all(
        qp.reshape(n_actors, chunk, cols), axis_name, 0, 0
    )  # [n_actors, chunk, cols] narrow ints
    acc = jnp.sum(recv.astype(jnp.int32), axis=0)  # widened accumulation

    # stage 3: requantize the merged rows this actor owns against their own
    # merged absmax (same per-(node, feature) granularity as stage 1) and
    # gather narrow ints + one f32 scale per row. The scale's raw bytes ride
    # INSIDE the same payload (bitcast to the narrow dtype, appended as
    # trailing columns) so the gather is ONE collective, not two — collective
    # dispatch count, not only bytes, is a real cost on small meshes.
    idx = jax.lax.axis_index(axis_name)
    scale_own = jax.lax.dynamic_slice_in_dim(scale_p, idx * chunk, chunk)
    merged_rows = acc.astype(jnp.float32) * scale_own[:, None]
    amax2 = jnp.max(jnp.abs(merged_rows), axis=1)
    scale2 = jnp.where(amax2 > 0, amax2 / qmax, 1.0)
    q2 = jnp.clip(
        jnp.round(merged_rows / scale2[:, None]), -qmax, qmax
    ).astype(qdt)
    scale_cols = jax.lax.bitcast_convert_type(scale2, qdt)  # [chunk, 4 // iw]
    payload = jnp.concatenate([q2, scale_cols], axis=1)
    if counter is not None:
        counter.add_all_gather(payload)
    full = jax.lax.all_gather(payload, axis_name, tiled=True)
    full_s = jax.lax.bitcast_convert_type(full[:, cols:], jnp.float32)
    merged = full[:, :cols].astype(jnp.float32) * full_s.reshape(-1, 1)
    return merged[:rows].reshape(nn, num_features, nbt, two)


def _block_scaled_allreduce(
    h: jnp.ndarray,
    axis_name: str,
    base: str,  # "int8" | "int16" — the narrow payload dtype
    n_actors: int,
    counter: Optional[AllreduceBytes],
    block: int,
) -> jnp.ndarray:
    """Block-scaled ring allreduce (``hist_quant="int8_block"/"int16_block"``,
    see module comment). No absmax pre-pass: each send quantizes against the
    LOCAL running block absmax, and the schedule is n-1 narrow ppermute hops
    (ring reduce-scatter with f32 dequant-accumulate per hop) + one narrow
    tiled all_gather with the f32 block scales bitcast in-band. Each chunk's
    final value is computed by exactly one actor along its ring path, so the
    gathered result is bit-identical on every shard."""
    qmax = _QMAX[base]
    qdt = _QDTYPE[base]
    nn, num_features, nbt, two = h.shape
    size = nn * num_features * nbt * two
    flat = h.reshape(-1)
    if flat.dtype != jnp.float32:
        # int32 gh_precision domain: exact below 2^24, coarser-than-wire
        # rounding beyond — and NEVER a full-rank f32 psum (VER004)
        flat = flat.astype(jnp.float32)
    n = max(1, int(n_actors))
    pad = (-size) % n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    chunk = (size + pad) // n
    bpc = -(-chunk // block)  # scale blocks per chunk (last may be ragged)
    bpad = bpc * block - chunk
    sw = 4 // jnp.dtype(qdt).itemsize  # narrow words per f32 scale

    def quantize(v):  # [chunk] f32 -> ([chunk] narrow, [bpc] f32 scales)
        vb = (jnp.pad(v, (0, bpad)) if bpad else v).reshape(bpc, block)
        amax = jnp.max(jnp.abs(vb), axis=1)
        scale = jnp.where(amax > 0, amax / qmax, 1.0)
        q = jnp.clip(jnp.round(vb / scale[:, None]), -qmax, qmax).astype(qdt)
        return q.reshape(-1)[:chunk], scale

    def dequantize(q, scale):  # ([chunk] narrow, [bpc] f32) -> [chunk] f32
        qb = (jnp.pad(q, (0, bpad)) if bpad else q).reshape(bpc, block)
        v = qb.astype(jnp.int32).astype(jnp.float32) * scale[:, None]
        return v.reshape(-1)[:chunk]

    def pack(q, scale):  # ragged 1-D wire: data then bitcast scale words
        return jnp.concatenate(
            [q, jax.lax.bitcast_convert_type(scale, qdt).reshape(-1)]
        )

    def unpack(payload):
        scale = jax.lax.bitcast_convert_type(
            payload[chunk:].reshape(bpc, sw), jnp.float32
        )
        return payload[:chunk], scale

    if n == 1:
        # no wire: the same two deterministic block-granular roundings as
        # the multi-actor path (one at the first ring send, one at the
        # publish requantize), so 1-actor and n-actor models see the same
        # quantization contract
        q, scale = quantize(flat)
        q2, scale2 = quantize(dequantize(q, scale))
        return dequantize(q2, scale2)[:size].reshape(
            nn, num_features, nbt, two
        )

    chunks = flat.reshape(n, chunk)
    p = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    # ring reduce-scatter: at step s actor p ships the running sum of chunk
    # (p - 1 - s) % n to p + 1, quantized against its running block absmax;
    # the receiver dequant-accumulates its own local copy in f32. After the
    # n - 1 hops actor p owns the fully reduced chunk p.
    cur = jnp.take(chunks, (p - 1) % n, axis=0)
    for s in range(n - 1):
        payload = pack(*quantize(cur))
        if counter is not None:
            counter.add_ppermute(payload)
        recv = jax.lax.ppermute(payload, axis_name, perm)
        rq, rscale = unpack(recv)
        cur = dequantize(rq, rscale) + jnp.take(chunks, (p - 2 - s) % n, axis=0)
    # publish: requantize the owned chunk against its merged block absmax
    # and all_gather with the scales riding in-band — one collective
    payload = pack(*quantize(cur))
    if counter is not None:
        counter.add_all_gather(payload)
    full = jax.lax.all_gather(payload, axis_name, tiled=True)
    per = full.reshape(n, chunk + bpc * sw)
    scales = jax.lax.bitcast_convert_type(
        per[:, chunk:].reshape(n, bpc, sw), jnp.float32
    )
    qs = per[:, :chunk]
    qb = jnp.pad(qs, ((0, 0), (0, bpad))) if bpad else qs
    vals = (
        qb.reshape(n, bpc, block).astype(jnp.int32).astype(jnp.float32)
        * scales[:, :, None]
    )
    merged = vals.reshape(n, bpc * block)[:, :chunk].reshape(-1)
    return merged[:size].reshape(nn, num_features, nbt, two)


def _einsum_precision(precision: str):
    """Histogram accumulation precision: "highest" (f32-exact bf16x3 passes)
    or "fast" (single bf16 pass; ~0.2% relative rounding on gh entering the
    MXU, 2-3x fewer MXU passes). Accumulation itself is always f32."""
    return (
        jax.lax.Precision.DEFAULT
        if precision == "fast"
        else jax.lax.Precision.HIGHEST
    )


def _append_missing(hist_reg: jnp.ndarray, node_tot: jnp.ndarray) -> jnp.ndarray:
    """Reconstruct the missing-value bucket by subtraction.

    ``hist_reg`` is [n_nodes, F, n_bins, 2] over the regular (non-missing)
    bins; a row's gh lands in NO regular bin exactly when its value is
    missing, so per (node, feature): missing = node_total - sum(regular).
    Keeping the built histogram at n_bins (a 128-lane multiple for the
    default max_bin=256) instead of n_bins+1 avoids a whole extra MXU tile
    per pass (257 -> 3x128 tiles, 256 -> 2)."""
    miss = node_tot[:, None, :] - hist_reg.sum(axis=2)  # [n_nodes, F, 2]
    return jnp.concatenate([hist_reg, miss[:, :, None, :]], axis=2)


def _acc_dtype(gh) -> jnp.dtype:
    """Histogram accumulation dtype for a gh buffer: int32 for quantized
    (``gh_precision``) integer gradients — sums of narrow ints are EXACT in
    int32 up to ~2^31/qmax rows per (shard, bin) — float32 otherwise."""
    return (
        jnp.int32 if jnp.issubdtype(gh.dtype, jnp.integer) else jnp.float32
    )


def _chunk_node_sums(ghk: jnp.ndarray, pk: jnp.ndarray, n_nodes: int):
    """[n_nodes, 2] (grad, hess) totals of one row chunk as one-hot(node)ᵀ @ gh
    on the MXU (a [N]-row scatter here measured ~20 ms/1M rows on TPU): exact
    int32 for quantized integer gh, f32 products at ``HIGHEST`` otherwise.
    Rows whose ``pk`` lies outside ``[0, n_nodes)`` hit no slot."""
    if jnp.issubdtype(ghk.dtype, jnp.integer):
        oh_node = jax.nn.one_hot(pk, n_nodes, dtype=ghk.dtype)
        return jax.lax.dot_general(
            oh_node, ghk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
    oh_node = jax.nn.one_hot(pk, n_nodes, dtype=jnp.float32)
    return jnp.matmul(oh_node.T, ghk, precision=jax.lax.Precision.HIGHEST)


def _for_row_chunks(step, init, chunk: int, pos, *rows):
    """``init`` carried through ``step(carry, pos_window, *row_windows)`` over
    ``chunk``-row windows of ``pos`` and the row-aligned arrays ``rows``.
    Each window is read in place by a dynamic slice: no padded copy and no
    ``[n_chunks, chunk, ...]`` reshape of a row-extent array, which under the
    chip's tiled layouts is no bitcast -- an 11M-row build's 1.3 GB of
    transients and all but two of the 213 s it took to compile went into
    that reshape (PERF.md §6, PR 30). The last window is clamped onto the
    arrays' end; the rows it shares with the window before come with
    ``pos`` -1, the callers' "in no node"."""
    n = pos.shape[0]
    if n == 0:
        return init
    chunk = min(chunk, n)

    def body(i, carry):
        start = jnp.minimum(i * chunk, n - chunk)
        pk, *windows = (
            jax.lax.dynamic_slice_in_dim(r, start, chunk, axis=0)
            for r in (pos, *rows)
        )
        fresh = start + jnp.arange(chunk, dtype=jnp.int32) >= i * chunk
        return step(carry, jnp.where(fresh, pk, -1), *windows)

    return jax.lax.fori_loop(0, -(-n // chunk), body, init)


def hist_scatter(
    bins: jnp.ndarray,  # [N, F] integer bins in 0..n_bins (n_bins == missing)
    gh: jnp.ndarray,  # [N, 2] float32 (grad, hess); padding rows must be 0
    pos: jnp.ndarray,  # [N] int32 node position within level, 0..n_nodes-1
    n_nodes: int,
    n_bins_total: int,  # n_bins + 1 (missing bucket included)
) -> jnp.ndarray:
    """Returns [n_nodes, F, n_bins_total, 2] float32 (int32 exact sums when
    ``gh`` is a quantized integer buffer)."""
    n, num_features = bins.shape
    b = bins.astype(jnp.int32)
    # flat bucket id per (row, feature)
    flat = (pos[:, None] * num_features + jnp.arange(num_features, dtype=jnp.int32)[None, :]) * n_bins_total + b
    acc = _acc_dtype(gh)
    if acc == jnp.int32:
        gh = gh.astype(jnp.int32)  # widen the [N, 2] source, not the fan-out
    out = jnp.zeros((n_nodes * num_features * n_bins_total, 2), acc)
    ghb = jnp.broadcast_to(gh[:, None, :], (n, num_features, 2))
    out = out.at[flat.reshape(-1)].add(ghb.reshape(-1, 2))
    return out.reshape(n_nodes, num_features, n_bins_total, 2)


# most feature columns per sequential matmul step of ``hist_onehot`` at
# radix 1: a [7 x 256 bins, 8192 rows] one-hot is the tile a v5e builds
# fastest there (PERF.md §6, PR 30); tiles are sized evenly so that 28
# features make four of 7
_ONEHOT_FTILE_MAX = 8
# the same at a radix L > 1, where a step is one matmul batched over its
# features, [256 / L, 8192] x [8192, L * C] each: all 28 features in one step
# ran 7-13% faster than tiles of 14 and 13-23% faster than tiles of 7 at the
# widths the rule takes, and no tile up to 44M elements fell off a cliff
# (PERF.md §6, PR 36)
_RADIX_FTILE_MAX = 32
# a build's columns (C = 2 * n_nodes, before ``lo`` widens them L-fold) from
# which a factored build lays a feature's columns out with ``lo`` minor: the
# form that kept 128 right-hand-side columns off the cliff ``lo`` major fell
# from; narrower builds keep ``lo`` major (``_hist_onehot``)
_LO_MINOR_COLS = 64
# most feature columns per step of a build with ``lo`` minor: in steps of 7
# features of 8,192 rows 28 / 2,000 features' 64-column build read 69.3 /
# 197.7 ms against 81.9 / 229.9 at radix 1, in one step of 28 / 32 features
# 189.3 / 497.0 (PERF.md §6)
_LO_MINOR_FTILE_MAX = 7
#: the radices ``onehot_radix`` can return
ONEHOT_RADICES = (1, 2, 4, 8)
# (columns, regular bins) -> radix from ``_LO_MINOR_COLS`` columns on, where
# the probe read it faster than radix 1 at both shapes (``onehot_radix``)
_WIDE_RADIX = {(64, 256): 2}

# ``builds``: width (2 * n_nodes) -> (radix, feature tiles a row chunk) of
# every ``hist_onehot`` build traced since the last ``pop_traced_builds``;
# ``tree_steps``: the tile steps (row chunks x feature tiles) of the builds
# traced since a grower last began a tree. Trace-time bookkeeping for the
# ``hist.builds`` event, not state any program reads. One record a process:
# two ``train()`` calls running in threads at once report each other's widths
_traced = {"builds": {}, "tree_steps": 0}


def onehot_radix(n_nodes: int, nb_reg: int) -> int:
    """The radix L ``hist_onehot`` factors the bin index by (``b = hi * L +
    lo``) at a build of ``n_nodes`` node slots over ``nb_reg`` regular bins,
    from the build's shape alone. Under 64 columns (C = 2 * n_nodes): the L
    of 1, 2, 4, 8 with at most 64 right-hand-side columns (``L * C``) that
    makes the fewest elements a (row, feature), ``nb_reg / L`` one-hot rows
    and ``L * C`` columns at 0.8 of a row's cost each; 1 under 64 bins and at
    widths other than 2, 4 and the multiples of 8 (a leaf-wise pass of
    ``max_leaves - 1`` slots): none was measured, and the forms whose column
    blocks were no whole sublane tiles were the slow ones. From 64 columns
    on, with ``lo`` minor (``_hist_onehot``): the radix ``_WIDE_RADIX``
    holds for the (C, ``nb_reg``) where a probe read it at least 5% faster
    than radix 1 at both 11M x 28 and 400,000 x 2,000, and 1 everywhere
    else, past the widest width probed (256) too.

    Measured on a v5e at 11M x 28 x 256 bins, bf16, 8192-row chunks, ms a
    build by L (rows) and C (``tools/bench_hist.py --radix-sweep --stages``,
    PERF.md §6, PR 36; the rule's choice starred):

        L / C      2      4      8     16     32     64    128
        1       67.5   67.6   67.6   69.1   73.2   81.8 *143.1
        2       34.4   33.9   35.7   38.2  *42.4  189.6
        4       21.4   21.9   24.1  *35.7  180.8  184.6
        8      *15.8  *20.5  *22.5  181.0  184.0  189.0

    which is about 4 + 0.25 ms a one-hot row + 0.15-0.27 ms a column: the
    VPU makes ``nb_reg / L + L * C`` elements a (row, feature) where radix 1
    makes ``nb_reg``. Past 64 right-hand-side columns this form (``lo``
    major) falls off a cliff (181-190 ms at 128). From 64 columns on, ms a
    build at 11M x 28 / 400,000 x 2,000 (the same probe, ``--widths 64 96
    128 256 --radices 1 2 4 --masks key --stages forms``, and the package's
    build; PERF.md §6), the best form of each row:

        L / C               64            96            128            256
        1              81.9/229.7  *99.1/*282.8  *142.7/*387.8  *260.1/*769.3
        2, lo major   195.8/506.5   106.8/333.2   211.2/607.4    271.8/818.5
        2, lo minor  *69.3/*197.7    96.2/275.4   191.4/556.3    257.3/771.3
        4, either     184.5/498.9   190.9/535.3   191.4/571.4    263.8/924.1

    Only 64 columns gain at least 5% at both shapes (15% / 14%), in steps of
    ``_LO_MINOR_FTILE_MAX`` features whose products are turned to ``lo``
    major before they join the accumulator (``_hist_onehot``): 2 x 128
    elements a (row, feature) where radix 1 makes 256, and a 128-column
    matmul output where radix 1's half-fills the MXU. 96 columns gain under
    3%, 128 and 256 none. Under 64 bins nothing was measured, and from 64
    columns on only 256 bins. With an earlier form of the right-hand side (a
    product with the 0/1 ``lo`` compare) 64 / 1,024 bins read 12.8 / 39.9 ms
    at 2 columns and L = 8 against 20.3 / 250.5 at L = 1.
    """
    width = 2 * n_nodes
    if width >= _LO_MINOR_COLS:
        return _WIDE_RADIX.get((width, nb_reg), 1)
    best, least = 1, float(nb_reg)
    if nb_reg < 64 or not (width in (2, 4) or width % 8 == 0):
        return best
    for radix in ONEHOT_RADICES[1:]:
        made = nb_reg / radix + 0.8 * radix * width
        if radix * width <= 64 and made < least:
            best, least = radix, made
    return best


def onehot_ftiles(num_features: int, radix: int, n_nodes: int = 1) -> int:
    """Feature tiles a row chunk of ``hist_onehot`` takes at a radix and a
    build's node slots: the fewest of at most ``_ONEHOT_FTILE_MAX`` (radix
    1), ``_LO_MINOR_FTILE_MAX`` (a radix over 1 with ``lo`` minor, from
    ``_LO_MINOR_COLS`` columns on) or ``_RADIX_FTILE_MAX`` features, sized
    evenly. 28 features make four tiles of 7 at radix 1, one of 28 over it
    and four of 7 with ``lo`` minor; 2,000 make 250 of 8, 63 of 32 and 286
    of 7 (the last padded by 2), and a v5e builds
    them in the time 28 features take, feature for feature (31-143 ms a
    level at 400,000 x 2,000 against 14-43 at 11M x 28 through 32 columns,
    231 / 392 against 82 / 144 at 64 / 128: 0.75-1.28 of the same work;
    PERF.md section 6, PR 38), so the maxima stand as read at 28."""
    if radix == 1:
        most = _ONEHOT_FTILE_MAX
    elif 2 * n_nodes >= _LO_MINOR_COLS:
        most = _LO_MINOR_FTILE_MAX
    else:
        most = _RADIX_FTILE_MAX
    return -(-num_features // most)


def begin_traced_tree() -> None:
    """A grower starts tracing a tree: the tile steps counted from here on
    are that tree's (``pop_traced_builds``)."""
    _traced["tree_steps"] = 0


def pop_traced_builds() -> dict:
    """What the dense builds traced since the last call were, for the
    ``hist.builds`` event (``main.py`` empties it when an attempt starts and
    reports it after training): ``radix_by_width`` and ``ftiles_by_width``
    (``{width: ...}``), and ``tile_steps_per_tree``, the row chunks x
    feature tiles of every build of the tree traced last (one device's; a
    level-wise tree's builds are its levels', a leaf-wise tree's passes are
    a loop of traced length, whose body counts once). ``{}`` where no build
    was traced."""
    if not _traced["builds"]:
        return {}
    traced = dict(sorted(_traced["builds"].items()))
    _traced["builds"].clear()
    return {
        "radix_by_width": {w: b[0] for w, b in traced.items()},
        "ftiles_by_width": {w: b[1] for w, b in traced.items()},
        "tile_steps_per_tree": _traced["tree_steps"],
    }


def hist_onehot(
    bins: jnp.ndarray,
    gh: jnp.ndarray,
    pos: jnp.ndarray,
    n_nodes: int,
    n_bins_total: int,
    chunk: int = 8192,
    precision: str = "highest",
) -> jnp.ndarray:
    """Dense, order-free MXU histogram: one pass over the rows whatever the
    fan-out, ``hist[f, b, (node, c)] = onehot(bins[:, f])ᵀ @ (gh ⊗ onehot(pos))``,
    with the bin index factored by the radix ``onehot_radix`` gives the
    build's shape (``_hist_onehot``). Counts the build, by radix, under
    ``rxgb_hist_builds_total`` and its tile steps (row chunks x feature
    tiles) under ``rxgb_hist_tile_steps_total`` as it is traced."""
    radix = onehot_radix(n_nodes, n_bins_total - 1)
    n, num_features = bins.shape
    ftiles = onehot_ftiles(num_features, radix, n_nodes)
    steps = ftiles * -(-n // max(1, min(chunk, n)))
    get_registry().counter(
        f'rxgb_hist_builds_total{{radix="{radix}"}}',
        "dense histogram builds traced, by the radix of the bin index",
    ).inc()
    get_registry().counter(
        "rxgb_hist_tile_steps_total",
        "tile steps (row chunks x feature tiles) of the dense histogram "
        "builds traced",
    ).inc(steps)
    _traced["builds"][2 * n_nodes] = (radix, ftiles)
    _traced["tree_steps"] += steps
    return _hist_onehot(bins, gh, pos, n_nodes, n_bins_total, chunk,
                        precision, radix)


def _hist_onehot(bins, gh, pos, n_nodes, n_bins_total, chunk, precision,
                 radix):
    """``hist_onehot`` at a given radix L (a power of two).

    Loops over row chunks (outer, ``_for_row_chunks``) and feature tiles
    (inner). A chunk's ``[chunk, 2 * n_nodes]`` right-hand side holds a row's
    (grad, hess) in its node's two columns: the node costs matmul columns,
    not one-hot width, so compares and one-hot traffic do not grow with
    ``n_nodes``. The one-hots have the bins leading and the rows along the
    minor axis: the chip keeps ``bins`` feature-major, so a chunk's
    ``[F, chunk]`` view is free, the compare broadcasts along the major axis,
    and the contraction is the matmul's natural ``(M, K) @ (K, N)`` (3x
    faster on a v5e than the ``[chunk, ftile * n_bins]`` orientation,
    PERF.md §6, PR 30).

    **L = 1**: each inner step builds the
    ``[ftile * n_bins, chunk]`` one-hot of the REGULAR bins -- the same at
    every level -- and contracts it over the rows against the right-hand
    side. **L > 1**: with ``b = hi * L + lo`` the low bits ride the
    right-hand side beside the node,

        hist[f, hi, lo, (node, c)] = Σ_r [hi(r, f) = hi] · [lo(r, f) = lo] · [pos(r) = node] · gh(r, c)

    so a step is one matmul batched over its features: the one-hot of ``hi``
    alone, ``[ftile, ceil(n_bins / L), chunk]``, against each feature's own
    ``[chunk, L * C]`` right-hand side, where column ``lo * C + 2 * node + c``
    holds a row's g or h if the row's key ``lo(r, f) * n_nodes + pos(r)`` is
    the column's and 0 otherwise: one compare and one select an element, no
    product of two broadcasts (which the compiler materialised at 16 columns
    and more, 10 ms a level; PERF.md §6, PR 36). Every element is gh or 0 as
    before, every sum over the same rows in the same dtype. The VPU then
    makes ``n_bins / L + L * C`` elements a (row, feature) for ``n_bins``,
    and the MXU takes L times fewer one-hot tiles.

    That order (``lo`` major) is kept under ``_LO_MINOR_COLS`` columns; there
    128 right-hand-side columns fell off a cliff (181-190 ms a build where
    radix 1 took 82). From ``_LO_MINOR_COLS`` on, a feature's column
    ``(2 * node + c) * L + lo`` holds what column ``lo * C + 2 * node + c``
    held (``lo`` minor), a step takes at most ``_LO_MINOR_FTILE_MAX``
    features, and each step's product is turned to ``lo`` major before it
    joins the accumulator: 69 ms. Kept ``lo`` minor through the row loop and
    turned once after it, the same build fell off the cliff again (208 ms;
    PERF.md §6).

    Missing rows (bin ``n_bins``) match no regular bin -- their ``hi`` lies
    outside the one-hot, or, where L does not divide ``n_bins``, in the last
    ``hi``'s padded ``lo`` slots, which are cut off -- and are reconstructed
    by subtraction, see ``_append_missing``. Rows whose ``pos`` lies outside
    ``[0, n_nodes)`` and rows whose gh the caller zeroed (padding, the bigger
    sibling) add nothing.
    """
    n, num_features = bins.shape
    nb_reg = n_bins_total - 1  # regular bins; bucket nb_reg == missing
    width = 2 * n_nodes
    prec = _einsum_precision(precision)

    # quantized gradients (gh_precision): both one-hots and gh ride the matmul
    # in the narrow integer dtype accumulating int32 — exact, and the
    # int8 x int8 -> int32 MXU path on modern hardware. The bf16 "fast" knob
    # is meaningless here (integer accumulation is already the cheap mode).
    int_gh = jnp.issubdtype(gh.dtype, jnp.integer)
    acc_dt = jnp.int32 if int_gh else jnp.float32
    # fast mode: the one-hot (the big operand) in bf16 — exact for 0/1
    # values, halves the traffic; gh rounds to bf16 (~0.2%) once, and its
    # product with the node's 0/1 is exact
    if int_gh:
        oh_dtype = gh.dtype
    else:
        oh_dtype = jnp.bfloat16 if precision == "fast" else jnp.float32

    shift = radix.bit_length() - 1
    n_hi = -(-nb_reg // radix)  # one-hot rows a feature; nb_reg at radix 1
    n_ftiles = onehot_ftiles(num_features, radix, n_nodes)
    ftile = -(-num_features // n_ftiles)
    f_pad = n_ftiles * ftile - num_features
    lo_minor = radix > 1 and width >= _LO_MINOR_COLS
    bin_ids = jnp.arange(n_hi, dtype=jnp.int32)
    node_of_col = jnp.arange(width, dtype=jnp.int32) // 2
    if lo_minor:
        # column (2 * node + c) * L + lo of a feature's right-hand side
        col = jnp.arange(radix * width, dtype=jnp.int32)
        col_is_hess = (col // radix % 2).astype(bool)
        key_of_col = col % radix * n_nodes + col // (2 * radix)
    else:
        col_is_hess = (
            jnp.arange(radix * width, dtype=jnp.int32) % 2).astype(bool)
        if radix > 1:
            # column lo * C + 2 * node + c of a feature's right-hand side
            key_of_col = (
                jnp.arange(radix, dtype=jnp.int32)[:, None] * n_nodes
                + node_of_col[None, :]
            ).reshape(-1)

    def chunk_step(carry, pk, bc, ghk):
        acc, tot = carry  # pk [rows], bc [rows, F] in the storage dtype, ghk [rows, 2]
        rows = pk.shape[0]
        bct = bc.T.astype(jnp.int32)  # [F, rows]: per-chunk transient upcast
        if f_pad:
            # pad with missing-valued features -> all-zero one-hot rows
            bct = jnp.pad(bct, ((0, f_pad), (0, 0)), constant_values=nb_reg)
        # the node rides the right-hand side: column 2 * node + c holds a
        # row's g (c = 0) or h (c = 1) where the row sits in that node
        ghc = ghk.astype(oh_dtype)
        of_col = jnp.where(col_is_hess[None, :], ghc[:, 1:2], ghc[:, 0:1])
        if radix == 1:
            rhs = jnp.where(
                pk[:, None] == node_of_col[None, :], of_col,
                jnp.zeros((), oh_dtype),
            )
        else:
            # a row in no node slot gets a key no column has
            node_key = jnp.where(
                (pk >= 0) & (pk < n_nodes), pk, -radix * n_nodes)

        def ftile_step(t, acc):
            cols = jax.lax.dynamic_slice_in_dim(bct, t * ftile, ftile, axis=0)
            # bins == nb_reg (missing) match no regular bin -> zero one-hot
            oh = (cols[:, None, :] == bin_ids[None, :, None]).astype(oh_dtype)
            contrib = jax.lax.dot_general(
                oh.reshape(ftile * nb_reg, rows), rhs, (((1,), (0,)), ((), ())),
                precision=prec, preferred_element_type=acc_dt,
            )  # [ftile*nb_reg, 2*n_nodes] (MXU, f32 — or exact int32 — accumulate)
            return jax.lax.dynamic_update_slice_in_dim(
                acc,
                jax.lax.dynamic_slice_in_dim(acc, t * ftile, ftile, axis=0)
                + contrib.reshape(ftile, nb_reg, width),
                t * ftile,
                axis=0,
            )

        def radix_step(t, acc):
            cols = bct if n_ftiles == 1 else jax.lax.dynamic_slice_in_dim(
                bct, t * ftile, ftile, axis=0)
            oh = ((cols >> shift)[:, None, :] == bin_ids[None, :, None]
                  ).astype(oh_dtype)  # [ftile, n_hi, rows]
            # a feature's right-hand side: the column of key_of_col's (lo,
            # node) holds a row's g or h where the row's own are the column's
            key = (cols & (radix - 1)) * n_nodes + node_key[None, :]
            of_f = jnp.where(
                key[:, :, None] == key_of_col[None, None, :], of_col[None],
                jnp.zeros((), oh_dtype),
            )  # [ftile, rows, L * C]
            contrib = jax.lax.dot_general(
                oh, of_f, (((2,), (1,)), ((0,), (0,))),
                precision=prec, preferred_element_type=acc_dt,
            )  # [ftile, n_hi, L * C]
            if lo_minor:  # [.., col * L + lo] -> [.., lo * C + col]
                contrib = contrib.reshape(ftile, n_hi, width, radix).transpose(
                    0, 1, 3, 2).reshape(ftile, n_hi, radix * width)
            if n_ftiles == 1:  # all features in one step: no slice of acc
                return acc + contrib
            return jax.lax.dynamic_update_slice_in_dim(
                acc,
                jax.lax.dynamic_slice_in_dim(acc, t * ftile, ftile, axis=0)
                + contrib,
                t * ftile,
                axis=0,
            )

        if radix > 1 and n_ftiles == 1:
            acc = radix_step(0, acc)
        else:
            acc = jax.lax.fori_loop(
                0, n_ftiles, ftile_step if radix == 1 else radix_step, acc)
        # node totals ride the loop as one extra tiny matmul per chunk
        tot = tot + _chunk_node_sums(ghk, pk, n_nodes)
        return acc, tot

    acc0 = (
        jnp.zeros((n_ftiles * ftile, n_hi, radix * width), acc_dt),
        jnp.zeros((n_nodes, 2), acc_dt),
    )
    # bins stay in the storage dtype (uint8/int16) until a chunk is read
    acc, node_tot = _for_row_chunks(chunk_step, acc0, chunk, pos, bins, gh)
    acc = acc[:num_features]
    if radix > 1:  # [F, hi, lo * C + col] -> [F, hi * L + lo, col]
        acc = acc.reshape(num_features, n_hi * radix, width)[:, :nb_reg]
    # [F, nb_reg, n_nodes * 2] -> [n_nodes, F, nb_reg, 2]
    hist_reg = acc.reshape(
        num_features, nb_reg, n_nodes, 2
    ).transpose(2, 0, 1, 3)
    return _append_missing(hist_reg, node_tot)


def node_sums(gh: jnp.ndarray, pos: jnp.ndarray, n_nodes: int) -> jnp.ndarray:
    """Per-node (grad, hess) totals: [n_nodes, 2] via segment-sum (exact
    int32 sums for quantized integer gh)."""
    acc = _acc_dtype(gh)
    out = jnp.zeros((n_nodes, 2), acc)
    return out.at[pos].add(gh if gh.dtype == acc else gh.astype(acc))


# rows per step of the dense node reductions: large enough that the ~340
# steps of an 11M-row shard cost under 3 ms of step overhead on a v5e, small
# enough that a step's [n_nodes, chunk] one-hot stays a transient
_NODE_CHUNK = 32768


def node_sums_dense(gh: jnp.ndarray, pos: jnp.ndarray, n_nodes: int) -> jnp.ndarray:
    """``node_sums`` without the per-row scatter-add: the rows stream once
    through ``hist_onehot``'s node-total matmul, chunk by chunk. Quantized
    integer ``gh`` sums stay exact int32; f32 sums agree with ``node_sums``
    to reassociation. Rows with ``pos`` outside ``[0, n_nodes)`` (the
    callers' -1 for finished rows) add to no node."""
    return _for_row_chunks(
        lambda tot, pk, ghk: tot + _chunk_node_sums(ghk, pk, n_nodes),
        jnp.zeros((n_nodes, 2), _acc_dtype(gh)), _NODE_CHUNK, pos, gh,
    )


def node_counts_dense(pos: jnp.ndarray, n_nodes: int) -> jnp.ndarray:
    """Rows per node slot, exact int32 [n_nodes], by the same chunked
    one-hot in place of ``zeros.at[pos].add(1)``."""
    slots = jnp.arange(n_nodes, dtype=pos.dtype)[:, None]
    return _for_row_chunks(
        lambda cnt, pk: cnt + jnp.sum(slots == pk[None, :], axis=1, dtype=jnp.int32),
        jnp.zeros((n_nodes,), jnp.int32), _NODE_CHUNK, pos,
    )


def zero_phantom_missing(h: jnp.ndarray, feat_has_missing) -> jnp.ndarray:
    """h: [nn, F, nbt, 2]; zero the (subtraction-reconstructed) missing
    bucket where the feature provably has NO missing values — under
    hist_precision="fast" the bf16 rounding residue of the regular bins
    lands in that bucket, and phantom missing mass must not steer the
    learned default direction. Shared by both growers (depthwise build_tree
    and the lossguide scan)."""
    if feat_has_missing is None:
        return h
    keep = feat_has_missing[None, :, None].astype(h.dtype)
    return h.at[:, :, -1, :].multiply(keep)


#: the builds ``hist_impl`` can name (beside "auto": ``default_hist_impl``)
HIST_IMPLS = ("scatter", "onehot")


def default_hist_impl() -> str:
    """What ``hist_impl="auto"`` means here: the scatter-add on the CPU
    backend (the only build that runs in reasonable time there), the dense
    MXU build elsewhere."""
    return "scatter" if jax.default_backend() == "cpu" else "onehot"


def build_histogram(
    bins: jnp.ndarray,
    gh: jnp.ndarray,
    pos: jnp.ndarray,
    n_nodes: int,
    n_bins_total: int,
    impl: str = "scatter",
    chunk: int = 8192,
    precision: str = "highest",
) -> jnp.ndarray:
    """One level's ``[n_nodes, F, n_bins_total, 2]`` histogram by the build
    ``impl`` names: the one place a name becomes a build, for both growers.
    ``chunk`` and ``precision`` are the dense build's; the scatter-add has
    neither."""
    if impl == "scatter":
        return hist_scatter(bins, gh, pos, n_nodes, n_bins_total)
    if impl == "onehot":
        return hist_onehot(bins, gh, pos, n_nodes, n_bins_total,
                           chunk=chunk, precision=precision)
    raise ValueError(
        f"unknown histogram build {impl!r}; use one of {' | '.join(HIST_IMPLS)}"
    )

"""Row sampling with compacted histogram builds.

Before this module, ``subsample < 1`` merely ZEROED the dropped rows'
grad/hess (the old branch in ``engine.py``'s round closure), so every
histogram scatter / one-hot matmul, partition update, and node-id gather
still ran over all N rows — a sampled round cost exactly as much as a full
one. The per-round histogram build is the hot op of GBDT training (SURVEY
§5.8) and its cost scales with the number of live rows per level, so
sampling must shrink the ROW BUFFER, not just the values in it.

The shape-static formulation: per tree, select a FIXED budget of
``M = ceil(rate * N_local)`` row slots (XLA needs static shapes, so the
budget is a trace-time constant derived from the shard's padded block
size), then gather ``gh`` and the binned rows down to the M-row buffer.
``build_tree`` / ``build_tree_lossguide`` are row-count-blind — they derive
N from ``bins.shape`` — so the whole level loop (histogram builds, row
routing, sibling subtraction) runs over M rows with no grower changes. Full-row work remains only in the once-per-tree leaf-value margin
update, which reuses the eval-set tree walk (``predict_tree_binned``).

Two policies (``sampling_method`` in params):

* ``"uniform"`` — ``subsample``-rate sampling WITHOUT replacement via
  top-k over per-row uniform keys (the fixed-budget analog of the
  reference's Bernoulli row mask; "XGBoost: Scalable GPU Accelerated
  Learning", arxiv 1806.11248 §5). No weight amplification — leaf values
  come from the sampled statistics, matching xgboost's ``subsample``.
* ``"gradient_based"`` — GOSS/MVS-style (LightGBM's Gradient-based
  One-Side Sampling; MVS, arxiv 1910.13204): keep the deterministic top
  ``top_rate`` fraction by ``|g| * sqrt(h)`` (the rows that dominate the
  split-gain signal), sample ``other_rate`` of the remainder uniformly,
  and amplify the sampled remainder's gh by ``pool / rand_n`` so the
  histogram sums stay unbiased estimates of the full-data sums.

Selection is per-actor (the PRNG key is folded with the mesh axis index by
the engine, mirroring the old subsample fold), so re-sharding the same
rows onto a different world size changes which rows are drawn — the same
world-size determinism caveat the Bernoulli mask had. ``subsample=1.0``
with the default policy produces NO spec (``spec_from_params`` returns
None) and the engine's round closure traces the exact pre-sampling
program — compaction is a provable no-op when sampling is off.
"""

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class SamplingSpec:
    """Jit-static row-sampling policy (hashable; closed over at trace time).

    ``policy`` is "uniform" (rate = ``subsample``) or "gradient_based"
    (GOSS: ``top_rate`` kept deterministically, ``other_rate`` sampled with
    amplification). Budgets are derived per shard from the traced row-block
    shape via ``row_budget`` so every shard's compacted buffer is static.
    """

    policy: str
    rate: float = 1.0
    top_rate: float = 0.2
    other_rate: float = 0.1


def spec_from_params(params) -> Optional[SamplingSpec]:
    """Resolve TrainParams into a SamplingSpec, or None when sampling is
    off (the None path must stay bit-identical to pre-sampling training)."""
    if params.sampling_method == "gradient_based":
        return SamplingSpec(
            "gradient_based",
            top_rate=float(params.top_rate),
            other_rate=float(params.other_rate),
        )
    if params.subsample < 1.0:
        return SamplingSpec("uniform", rate=float(params.subsample))
    return None


def _ceil_frac(rate: float, n: int) -> int:
    # ceil(rate * n) without float-dust surprises at exact multiples
    return int(math.ceil(round(rate * n, 9)))


def goss_counts(n: int, spec: SamplingSpec) -> Tuple[int, int]:
    """Static (top_n, rand_n) for a gradient_based spec over ``n`` rows."""
    top_n = min(n, _ceil_frac(spec.top_rate, n))
    rand_n = min(n - top_n, _ceil_frac(spec.other_rate, n))
    if top_n + rand_n == 0:
        rand_n = 1  # validation forbids this, but never emit an empty buffer
    return top_n, rand_n


def row_budget(n: int, spec: SamplingSpec) -> int:
    """Compacted buffer size M for an ``n``-row shard (trace-time constant)."""
    if spec.policy == "uniform":
        return max(1, min(n, _ceil_frac(spec.rate, n)))
    top_n, rand_n = goss_counts(n, spec)
    return top_n + rand_n


def sample_rows(
    gh: jnp.ndarray,  # [N, 2] grad/hess (0 for padding rows): float32, or a
    #   quantized int8/int16 buffer (gh_precision) with ``scale`` supplied
    valid: jnp.ndarray,  # [N] bool — real data rows (padding excluded)
    key: jnp.ndarray,  # PRNG key, already folded per (tree, actor)
    spec: SamplingSpec,
    scale: Optional[jnp.ndarray] = None,  # [2] f32 dequantization scales of
    #   a quantized gh buffer (required for gradient_based over int gh)
    lane_budget: Optional[jnp.ndarray] = None,  # traced int32 scalar: keep
    #   only the first ``lane_budget`` of the M selected slots (vmapped-K
    #   HPO's per-lane subsample rate; uniform policy only)
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Select the round's row budget. Returns ``(rows, gh_sel)``:

    * ``rows`` [M] int32 — indices into the shard's row block. Slots are
      distinct within each selection stage; slots landing on ineligible
      rows (padding under the uniform draw, or GOSS budget exceeding the
      eligible pool) have their ``gh_sel`` entry zeroed, so they
      contribute nothing downstream.
    * ``gh_sel`` [M, 2] — the selected rows' grad/hess, with GOSS's
      remainder amplification (``pool / rand_n``, the unbiased inflation
      of the sampled non-top mass) already applied.

    Deterministic in ``key`` — identical (seed, iteration, actor) always
    draws the same rows, so checkpoint-resumed rounds replay bit-identically.

    Quantized gh (``gh_precision``): the uniform policy gathers the narrow
    INTEGER buffer straight through (the zero-mask is exact in any int
    dtype), so the compacted build stays on the int -> int32 fast path. The
    gradient_based policy scores in f32 FROM the quantized values and
    gathers from the int buffer, but its compacted [M, 2] result is
    dequantized f32: GOSS's remainder amplification is a real-valued per-row
    multiplier that cannot ride an int8 grid without either overflowing it
    or clipping the amplified mass. M is small (top_rate + other_rate of N),
    so the full-N gh plane keeps the 4x cut and the model still trains on
    quantized-grid gradients.
    """
    n = gh.shape[0]
    int_gh = jnp.issubdtype(gh.dtype, jnp.integer)
    if int_gh and scale is None and spec.policy == "gradient_based":
        raise ValueError(
            "gradient_based sampling over a quantized gh buffer needs the "
            "dequantization scale (quantize_gh's [2] scales)"
        )
    if spec.policy == "uniform":
        # top-k over UNMASKED uniform keys: every row slot — valid or
        # padding — competes equally, so each valid row is kept with
        # probability ~ m/n == rate no matter how much of the shard is
        # padding. Preferring valid rows here would silently keep ALL of a
        # heavily-padded shard's rows (budget derives from the padded block
        # size), overweighting that shard's data vs the Bernoulli semantics
        # this replaces; selected padding slots instead just waste budget,
        # contributing nothing (their gh is zeroed below).
        m = row_budget(n, spec)
        u = jax.random.uniform(key, (n,))
        _, rows = jax.lax.top_k(u, m)
        ok = valid[rows][:, None].astype(gh.dtype)
        if lane_budget is not None:
            # top_k sorts descending, so slots [0, lane_budget) ARE the
            # lane's own exact top-k selection; the surplus slots keep
            # their row ids (shape stays the vmapped program's shared M)
            # but contribute zero gh downstream
            ok = ok * (jnp.arange(m) < lane_budget)[:, None].astype(gh.dtype)
        return rows.astype(jnp.int32), gh[rows] * ok
    if lane_budget is not None:
        raise NotImplementedError(
            "per-lane budgets (vmapped-K subsample) are only supported for "
            "the 'uniform' sampling policy"
        )
    if spec.policy != "gradient_based":
        raise ValueError(f"unknown sampling policy {spec.policy!r}")

    top_n, rand_n = goss_counts(n, spec)

    def take(rows):
        # gather from the (possibly int) buffer; the compacted result is
        # f32 quantized-grid values when gh is quantized (see docstring)
        sel = gh[rows]
        return sel.astype(jnp.float32) * scale if int_gh else sel

    if int_gh:
        g_f = gh[:, 0].astype(jnp.float32) * scale[0]
        h_f = gh[:, 1].astype(jnp.float32) * scale[1]
    else:
        g_f, h_f = gh[:, 0], gh[:, 1]
    # |g| * sqrt(h): the gradient magnitude weighted by curvature — rows
    # with large values dominate split gains g^2/(h+lambda), so keeping
    # them deterministically preserves the gain landscape (GOSS keeps
    # top-|g|; the sqrt(h) factor is the MVS-style curvature correction).
    score = jnp.abs(g_f) * jnp.sqrt(jnp.maximum(h_f, 0.0))
    score = jnp.where(valid, score, -jnp.inf)
    rows_parts = []
    gh_parts = []
    eligible = valid
    if top_n:
        tvals, rows_top = jax.lax.top_k(score, top_n)
        ok_top = jnp.isfinite(tvals)[:, None].astype(jnp.float32)
        rows_parts.append(rows_top)
        gh_parts.append(take(rows_top) * ok_top)
        eligible = eligible & (
            jnp.ones((n,), bool).at[rows_top].set(False)
        )
    if rand_n:
        u = jax.random.uniform(key, (n,))
        rscore = jnp.where(eligible, u, -1.0)
        rvals, rows_rand = jax.lax.top_k(rscore, rand_n)
        # unbiased amplification: the sampled rows stand in for the whole
        # eligible pool, so their mass is inflated by pool/rand_n (the
        # per-shard exact form of GOSS's (1-a)/b — exact even on padded
        # shards where the nominal fractions overcount dead rows). When
        # the pool is smaller than the budget every pool row is selected
        # (the surplus slots are zeroed), so the factor collapses to 1 —
        # the selection IS the pool and must not be shrunk.
        pool = jnp.sum(eligible.astype(jnp.float32))
        amp = jnp.where(
            pool > 0, pool / jnp.minimum(pool, float(rand_n)), 0.0
        )
        ok = (rvals >= 0.0)[:, None].astype(jnp.float32)
        rows_parts.append(rows_rand)
        gh_parts.append(take(rows_rand) * amp * ok)
    rows = jnp.concatenate(rows_parts).astype(jnp.int32)
    gh_sel = jnp.concatenate(gh_parts, axis=0)
    return rows, gh_sel

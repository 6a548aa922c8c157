"""Level-wise (depth-wise) tree growth under static shapes.

TPU-native replacement for xgboost's C++ ``hist``/``gpu_hist`` tree updaters
(the compute core behind ``xgb.train`` in the reference's actor hot loop,
``xgboost_ray/main.py:745-752``).

XLA wants static shapes, so the dynamic frontier of xgboost's tree growth
becomes a *padded heap*: a tree of max_depth D occupies ``2^(D+1)-1`` node
slots (root 0, children of i at 2i+1 / 2i+2). At level d all ``2^d`` node
positions are processed at once; nodes that stopped splitting are masked.
Rows carry an int32 position vector (their node at the current level). Each
level reads its per-node state back per row through dense one-hot forms
(``lookup_by_node``, ``bin_of_feature``, the chunked node sums) that stream
the rows once — no per-row gather or scatter keyed by the position, no host
round-trips, no sorting.

The histogram allreduce point is the ``allreduce`` callable: identity on a
single device, ``lax.psum(..., "actors")`` inside the shard_map round step —
this is the exact spot where the reference relied on Rabit (SURVEY §5.8).

A level's histogram comes from ``ops.histogram.build_histogram``: ``scatter``
(one scatter-add; the CPU's) or ``onehot`` (the dense one-hot matmul on the
MXU at every fan-out; the chip's). Either streams every row once: the tree
keeps no row order, compacts no smaller child and copies no row into blocks
(on a v5e at 11M rows a dense level costs 66-73 ms up to 32 columns and
144 ms at 128; PERF.md §5, §6).

A hand-written Pallas kernel over node-contiguous row blocks (r2-r4) and
the XLA build over the same blocks (until PR 31) both lost to the dense
build on the chip: the blocked layout itself was the cost (the row copies
and the order update were 62% of a round, PR 28). XLA's dense build still
runs some 500x over its bytes floor; whether a kernel that keeps the one-hot
in VMEM beats it is ROADMAP P2's open question.
"""

import dataclasses
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from xgboost_ray_tpu.obs import get_registry
from xgboost_ray_tpu.ops.histogram import (
    begin_traced_tree,
    build_histogram,
    node_counts_dense,
    node_sums_dense,
    zero_phantom_missing,
)
from xgboost_ray_tpu.ops.split import (
    SplitParams,
    bounded_weight,
    elect_across_feature_shards,
    find_splits,
    leaf_weight,
)

# Disjoint fold_in domains for the per-tree sampling mechanisms, so row
# subsampling and the three column-sampling masks never draw from overlapping
# PRNG streams (a bare fold_in(key, d) for bylevel would collide with
# fold_in(key, 0) for bytree and fold_in(key, rank+1) for subsample).
SALT_SUBSAMPLE = 0x51D1
SALT_BYTREE = 0x51D2
SALT_BYLEVEL = 0x51D3
SALT_BYNODE = 0x51D4
SALT_GOSS = 0x51D5  # gradient_based row sampling (ops/sampling.py)
SALT_SR = 0x51D6  # stochastic gh rounding (gh_precision, ops/objectives.py)


def route_right_binned(bin_vals, split_bin, default_left, is_cat, missing_bin):
    """The one binned routing rule (build_tree, lossguide, binned predict):
    numeric = bin > split_bin goes right, categorical one-vs-rest = the
    candidate category (bin == split_bin) goes left, missing bucket follows
    the learned default. All args broadcast elementwise; ``is_cat`` may be
    None when the tree has no categorical features. predict.py's raw-x
    walk mirrors this rule in value space (``_step_right``)."""
    present_right = bin_vals > split_bin
    if is_cat is not None:
        present_right = jnp.where(is_cat, bin_vals != split_bin, present_right)
    return jnp.where(bin_vals == missing_bin, ~default_left, present_right)


def lookup_by_node(pos: jnp.ndarray, *tables: jnp.ndarray, keys=None) -> list:
    """``[t[pos] for t in tables]`` for node-sized tables (each ``[n_nodes]``:
    bool, int32 or float32) without a per-row gather: ``pos`` is compared with
    the node slots once, each table's words are selected under the hit and one
    variadic reduce over the slots yields every table's row vector, so the
    ``[n_nodes, N]`` hit mask lives only inside the fusion. With ``keys``
    (``[n_nodes]`` distinct int32) slot ``j`` is the one whose key equals
    ``pos``, not ``j`` itself (the leaf-wise grower's node ids of one pass). Values travel as
    int32 bit patterns and exactly one slot hits a row, so the result is
    bitwise ``t[pos]`` (no float add touches an f32 value). XLA's TPU gather
    moves one element at a time; at 11M rows on a v5e the five lookups of a
    level took 2.4 ms at one slot, 22 ms at 128 and 110 ms at 2,048 this way
    against 170-560 ms as gathers, so every fan-out takes this form. A
    ``pos`` outside ``[0, n_nodes)`` reads 0 / False."""
    n_nodes = tables[0].shape[0]
    if keys is None:
        keys = jnp.arange(n_nodes, dtype=pos.dtype)
    hit = keys[:, None] == pos[None, :]
    words = tuple(
        jnp.where(hit, _as_word(t)[:, None], jnp.int32(0)) for t in tables
    )
    picked = jax.lax.reduce(
        words, (jnp.int32(0),) * len(words),
        lambda a, b: tuple(x + y for x, y in zip(a, b)), (0,),
    )
    return [_from_word(w, t.dtype) for w, t in zip(picked, tables)]


def _as_word(table: jnp.ndarray) -> jnp.ndarray:
    if table.dtype == jnp.float32:
        return jax.lax.bitcast_convert_type(table, jnp.int32)
    if table.dtype == jnp.bool_ or table.dtype == jnp.int32:
        return table.astype(jnp.int32)
    raise TypeError(f"lookup_by_node: unsupported table dtype {table.dtype}")


def _from_word(word: jnp.ndarray, dtype) -> jnp.ndarray:
    if dtype == jnp.float32:
        return jax.lax.bitcast_convert_type(word, jnp.float32)
    return word != 0 if dtype == jnp.bool_ else word


def bin_of_feature(bins: jnp.ndarray, f_of_row: jnp.ndarray) -> jnp.ndarray:
    """``bins[i, f_of_row[i]]`` per row as int32: a masked reduce over the
    feature axis in the storage dtype (uint8 / int16), in place of
    ``take_along_axis`` on an int32 copy of the whole ``[N, F]`` matrix (an
    N-element gather: 200 ms against 2 ms at 11M x 28 on a v5e)."""
    num_features = bins.shape[1]
    hit = jnp.arange(num_features, dtype=f_of_row.dtype) == f_of_row[..., None]
    picked = jnp.where(hit, bins, jnp.zeros((), bins.dtype))
    return picked.sum(axis=-1, dtype=bins.dtype).astype(jnp.int32)


def cat_mask_const(cat_features: tuple, num_features: int):
    """[F] bool compile-time constant marking categorical features (None when
    there are none) — single source for every walk/build/sketch site."""
    if not cat_features:
        return None
    return (
        jnp.zeros((num_features,), bool)
        .at[jnp.asarray(cat_features, jnp.int32)]
        .set(True)
    )


def fshard_local_views(fshard, cat_features, num_features, feat_has_missing,
                       feature_mask):
    """Global-vs-local per-feature state for one feature shard — the ONE
    derivation both growers share.

    Returns ``(cat_mask_global, cat_mask_local, fhm_local, fmask_local,
    f_global_max)``: the GLOBAL (padded) categorical mask for row routing,
    its local slice plus the local feat-has-missing / feature-mask slices
    for the split search, and the max valid global feature index.

    Padded columns (global index >= ``fshard.f_real``) are masked OUT of
    the local split search explicitly: they bin entirely to the missing
    bucket, which scores -inf for any ``min_child_weight > 0``, but at
    ``min_child_weight=0`` an empty child passes the hessian gate and the
    pad column's gain is f32 rounding noise around 0 — electable, which
    would break (R,1)<->(R,C) parity and emit a split on a nonexistent
    feature. The mask closes that hole for every SplitParams setting.
    """
    cat_mask = cat_mask_const(cat_features, fshard.f_padded)
    cat_mask_local = (
        None if cat_mask is None
        else fshard.slice_cols(cat_mask, num_features)
    )
    fhm_local = (
        None if feat_has_missing is None
        else fshard.slice_cols(feat_has_missing, num_features)
    )
    fmask_local = (
        None if feature_mask is None
        else fshard.slice_cols(feature_mask, num_features)
    )
    if fshard.f_padded != fshard.f_real:
        real_cols = (
            fshard.offset(num_features)
            + jnp.arange(num_features, dtype=jnp.int32)
        ) < fshard.f_real
        fmask_local = (
            real_cols if fmask_local is None else (fmask_local & real_cols)
        )
    return cat_mask, cat_mask_local, fhm_local, fmask_local, fshard.f_padded - 1


def sample_feature_mask(
    key: jnp.ndarray,
    n_features: int,
    rate: float,
    log_fw: Optional[jnp.ndarray] = None,
    batch: Optional[int] = None,
) -> jnp.ndarray:
    """Draw a boolean feature-sampling mask ([F], or [batch, F]).

    Without feature weights: independent Bernoulli(rate) per feature (with a
    never-empty guard) — the historical behavior. With ``log_fw`` (log of the
    user's per-feature weights, -inf for weight 0): weighted sampling WITHOUT
    replacement of k = max(1, round(rate * F)) features via Gumbel-top-k, the
    semantics of xgboost's ``feature_weights`` (zero-weight features are never
    drawn; reference surface: xgboost_ray/matrix.py:283-358 + its
    tests/test_end_to_end.py:429-468 demo).
    """
    shape = (n_features,) if batch is None else (batch, n_features)
    if log_fw is None:
        mask = jax.random.uniform(key, shape) < rate
        # never mask out every feature (of a node)
        guard = jnp.arange(n_features) == jnp.argmax(mask, axis=-1, keepdims=batch is not None)
        return mask | guard
    k = max(1, int(round(rate * n_features)))
    scores = log_fw + jax.random.gumbel(key, shape)
    kth = jax.lax.top_k(scores, k)[0][..., -1:]
    mask = (scores >= kth) & jnp.isfinite(log_fw)
    guard = jnp.arange(n_features) == jnp.argmax(scores, axis=-1, keepdims=batch is not None)
    return mask | guard


@dataclasses.dataclass(frozen=True)
class GrowConfig:
    max_depth: int = 6
    max_bin: int = 256
    split: SplitParams = dataclasses.field(default_factory=SplitParams)
    hist_impl: str = "scatter"
    hist_chunk: int = 8192
    # "highest": f32-exact histogram sums (bf16x3 MXU passes); "fast": one
    # rounded bf16 pass (~0.2% relative error on bin sums, 2-3x fewer passes)
    hist_precision: str = "highest"
    # Build only the globally-smaller child's histogram per parent and derive
    # the sibling as parent - child (xgboost hist/gpu_hist's core trick):
    # halves the built/allreduced histogram tensor at every level >= 1, and
    # halves the one-hot matmul FLOPs for the onehot path.
    sibling_subtract: bool = True
    # indices of categorical features (bins are category codes; splits are
    # one-vs-rest partitions routed by equality). Static tuple so it can ride
    # inside this hashable jit-static config.
    cat_features: tuple = ()
    # per-feature monotone constraints (len == F, values -1/0/+1) or () —
    # xgboost's monotone_constraints via per-node weight-bound propagation
    # (reference passthrough surface: xgboost_ray/main.py:745-752)
    monotone_constraints: tuple = ()
    # tuple of feature-index groups; a node may only split on features that
    # share a constraint set with every feature used on its root path
    # (xgboost's interaction_constraints semantics)
    interaction_constraints: tuple = ()
    # "depthwise" (level-wise, this module) or "lossguide" (best-first,
    # ops/grow_lossguide.py — the LightGBM growth strategy)
    grow_policy: str = "depthwise"
    # leaf budget for lossguide (resolved by the engine: 0 -> 2^max_depth)
    max_leaves: int = 0
    # wire format of the per-level histogram allreduce: "none" (f32 psum) |
    # "int16" | "int8" (row-scale quantized collective) | "int16_block" |
    # "int8_block" (block-scale ppermute ring, no absmax pre-pass;
    # ops/histogram.py). The engine resolves this into the
    # ``hist_allreduce`` callable; carried here so the jit-static config
    # names the full histogram contract. The exact-totals side-psum and 2D
    # min_bytes rescale decisions key on != "none", so the block modes
    # compose through both growers with no further plumbing.
    hist_quant: str = "none"
    # sub-threshold payloads keep the exact f32 psum (latency-bound regime)
    hist_quant_min_bytes: int = 32768
    # elements per in-band scale block (``*_block`` wire modes only)
    hist_quant_block: int = 512
    # on-chip gh storage/accumulation precision: "float32" (default, exact
    # pre-PR program) | "int16" | "int8" — g/h quantized at the objective
    # kernel (stochastic rounding, per-tree pmax scales; ops/objectives.py)
    # and accumulated int -> int32 through the histogram build. The growers
    # key off the traced gh buffer (``gh_scale`` arg); this field names the
    # contract in the jit-static config and the progreg meta.
    gh_precision: str = "float32"

    @property
    def heap_size(self) -> int:
        return (1 << (self.max_depth + 1)) - 1


class Tree(NamedTuple):
    """One decision tree in padded-heap layout; all arrays ``[heap_size]``
    (``2^(max_depth+1) - 1`` slots, the children of ``i`` at ``2i + 1`` /
    ``2i + 2``): every depth-bounded tree. ``LinkedTree`` is the layout of a
    tree no depth bounds; every walk takes both (``walk_to_leaf``)."""

    feature: jnp.ndarray  # int32, -1 if leaf/unused
    split_bin: jnp.ndarray  # int32, rows with bin <= split_bin go left
    threshold: jnp.ndarray  # float32 raw-value threshold (go left iff x < threshold)
    default_left: jnp.ndarray  # bool, where missing goes
    is_leaf: jnp.ndarray  # bool
    value: jnp.ndarray  # float32 leaf value (already scaled by learning_rate)
    gain: jnp.ndarray  # float32 split gain at internal nodes (importances)
    cover: jnp.ndarray  # float32 hessian sum reaching each node (xgb 'cover')
    base_weight: jnp.ndarray  # float32 lr-scaled leaf_weight of EVERY node
    #   (internal nodes included) — the E[f(x)|node] estimate Saabas/SHAP
    #   path attribution needs; equals `value` at real leaves

    @property
    def left(self):
        """A heap's children need no pointer (``LinkedTree.left``)."""
        return None


class LinkedTree(NamedTuple):
    """One decision tree in linked layout: ``Tree``'s fields over ``2 *
    max_leaves - 1`` slots and ``left``, the slot of a node's left child, its
    right child the next one, a node always after its parent. What a
    leaf-wise tree that no depth bounds comes back in
    (``grow_policy=lossguide, max_depth=0``), its slots in the order
    best-first growth made them: the ``t``-th split's children are ``1 + 2t``
    and ``2 + 2t``, as xgboost numbers them."""

    feature: jnp.ndarray
    split_bin: jnp.ndarray
    threshold: jnp.ndarray
    default_left: jnp.ndarray
    is_leaf: jnp.ndarray
    value: jnp.ndarray
    gain: jnp.ndarray
    cover: jnp.ndarray
    base_weight: jnp.ndarray
    left: jnp.ndarray  # int32 slot of the left child (0 at a leaf)


def map_tree(fn, tree):
    """``fn`` over the arrays of a tree or forest, in its own layout."""
    return type(tree)(*map(fn, tree))


def child_index(tree: Tree, idx, go_right):
    """Slot of the child a row at slot ``idx`` steps to: the one walk rule of
    both layouts."""
    first = 2 * idx + 1 if tree.left is None else tree.left[idx]
    return first + go_right.astype(jnp.int32)


def gather_rows(idx, *tables):
    """``[t[idx] for t in tables]``: each node table read at the rows' slots
    by a per-row gather (``walk_to_leaf``'s read by default)."""
    return [t[idx] for t in tables]


def slots_reached(tree: Tree, steps: int):
    """Slots a row can sit in after ``steps`` steps from the root: a heap
    tree's first ``2^(steps+1) - 1``; every slot (``None``) of a linked tree
    or where the steps are not counted (``None``)."""
    if tree.left is not None or steps is None:
        return None
    return 2 ** (steps + 1) - 1


def walk_to_leaf(tree: Tree, idx, max_depth: int, go_right_at,
                 read=gather_rows, route_tables=()):
    """Leaf slot of every row from its slot ``idx`` (the root's zeros).
    Each step reads ``is_leaf`` (a linked tree's ``left`` too) and
    ``route_tables`` at the rows' slots in one ``read(idx, *tables)``, the
    tables cut to ``slots_reached``, and ``go_right_at(idx, *route)`` routes
    the rows from what it read of ``route_tables``. ``max_depth`` steps
    where the depth is known (any heap tree; a linked forest whose depth the
    caller measured), else (0) steps until every row sits in a leaf."""
    tables = (tree.is_leaf,) + (() if tree.left is None else (tree.left,))

    def step(idx, n_slots):
        leaf, *read_at = read(
            idx, *(t[:n_slots] for t in tables + tuple(route_tables))
        )
        if tree.left is None:
            first = 2 * idx + 1
        else:
            first, *read_at = read_at
        nxt = first + go_right_at(idx, *read_at).astype(jnp.int32)
        return jnp.where(leaf, idx, nxt)

    if max_depth > 0:
        for s in range(max_depth):
            idx = step(idx, slots_reached(tree, s))
        return idx
    return jax.lax.while_loop(
        lambda i: ~jnp.all(read(i, tree.is_leaf)[0]),
        lambda i: step(i, None), idx,
    )


def empty_tree(heap_size: int) -> Tree:
    return Tree(
        feature=jnp.full((heap_size,), -1, jnp.int32),
        split_bin=jnp.zeros((heap_size,), jnp.int32),
        threshold=jnp.zeros((heap_size,), jnp.float32),
        default_left=jnp.zeros((heap_size,), bool),
        is_leaf=jnp.zeros((heap_size,), bool),
        value=jnp.zeros((heap_size,), jnp.float32),
        gain=jnp.zeros((heap_size,), jnp.float32),
        cover=jnp.zeros((heap_size,), jnp.float32),
        base_weight=jnp.zeros((heap_size,), jnp.float32),
    )


def _in_scope(name: str, fn):
    """``fn`` with its operations under ``jax.named_scope(name)`` (one of
    ``obs.DEVICE_SCOPES``: trace-time metadata, nothing at run time)."""

    def scoped(x):
        with jax.named_scope(name):
            return fn(x)

    return scoped


def build_tree(
    bins: jnp.ndarray,  # [N, F] int bins (max_bin == missing bucket); may be
    #   a COMPACTED [M, F] row selection (ops/sampling.py) — every shape in
    #   the level loop derives from bins.shape, so the grower is
    #   row-count-blind and sampled builds cost O(M), not O(N_full)
    gh: jnp.ndarray,  # [N, 2] float32 grad/hess (0 for padding rows;
    #   GOSS-amplified for sampled-remainder rows)
    cuts: jnp.ndarray,  # [F, max_bin-1] raw cut values for threshold recovery
    cfg: GrowConfig,
    feature_mask: Optional[jnp.ndarray] = None,  # [F] bool (colsample_bytree)
    level_rng: Optional[jnp.ndarray] = None,  # PRNG key for level/node sampling
    colsample_bylevel: float = 1.0,
    colsample_bynode: float = 1.0,
    allreduce: Callable[[jnp.ndarray], jnp.ndarray] = lambda x: x,
    feature_log_weights: Optional[jnp.ndarray] = None,  # [F] log(fw), -inf at 0
    feat_has_missing: Optional[jnp.ndarray] = None,  # [F] bool, global
    hist_allreduce: Optional[Callable[[jnp.ndarray], jnp.ndarray]] = None,
    ar_counter=None,  # AllreduceBytes: scan-scoped byte accounting, and the
    #   count of a mesh shard's sibling builds
    fshard=None,  # ops.feature_shard.FeatureShard on a 2D row x feature mesh
    gh_scale: Optional[jnp.ndarray] = None,  # [2] f32 per-channel scales of a
    #   quantized integer gh buffer (gh_precision; None = f32 legacy path)
    depth_limit: Optional[jnp.ndarray] = None,  # traced int32 scalar: levels
    #   >= depth_limit force still-active nodes to leaves (vmapped-K HPO's
    #   per-lane max_depth; the program still traces cfg.max_depth levels)
):
    """Grow one tree. Returns (Tree, row_value[N]) — row_value is the leaf
    value each row receives (learning-rate scaled), used to update margins
    without re-walking the tree.

    With ``gh_scale`` (``gh_precision`` int8/int16), ``gh`` is the quantized
    INTEGER buffer from ``ops.objectives.quantize_gh``: histogram bins and
    node totals accumulate integer-exact (int -> int32), the histogram
    allreduce rides int32 (exact) or the quantized wire, and the sums are
    dequantized ONCE per level at the split-search/leaf-weight boundary —
    node totals and leaf weights are exact f32 of the quantized values.

    ``hist_allreduce`` merges the per-level [n_nodes, F, nbt, 2] histogram
    across shards (the hot collective; may be quantized per
    ``cfg.hist_quant``). The small exact reductions — per-child row counts
    and final-level node sums — always go through ``allreduce``, so leaf
    weights and the sibling-subtraction child choice never carry
    quantization error. Defaults to ``allreduce`` when not given.

    With ``fshard`` (``feature_parallel`` > 1), ``bins`` is this chip's
    [N_shard, F_pad/C] feature tile and ``cuts``/``feat_has_missing``/
    ``feature_mask`` are GLOBAL (feature-padded) arrays: histograms and the
    split search run over the local tile (the psums above still ride the
    actors axis only), the per-node winner is elected across the feature
    axis (``elect_across_feature_shards``), and the winning feature's bin
    column is owner-broadcast so row routing stays O(rows)."""
    begin_traced_tree()
    hist_ar = hist_allreduce if hist_allreduce is not None else allreduce
    allreduce = _in_scope("allreduce", allreduce)
    hist_ar = _in_scope("allreduce", hist_ar)
    if cfg.grow_policy == "lossguide":
        if depth_limit is not None:
            # lossguide's levels are a loop of traced length with no
            # per-level structure to mask; vmapped-K lanes must share
            # max_depth under lossguide (the engine/params validation names
            # the key before tracing)
            raise NotImplementedError(
                "depth_limit (per-lane max_depth) is not supported with "
                "grow_policy='lossguide'"
            )
        from xgboost_ray_tpu.ops.grow_lossguide import build_tree_lossguide

        # engine validation guarantees the unsupported-combination params
        # (bylevel/bynode sampling, constraints) never reach this point
        return build_tree_lossguide(
            bins, gh, cuts, cfg,
            feature_mask=feature_mask,
            allreduce=allreduce,
            feat_has_missing=feat_has_missing,
            hist_allreduce=hist_ar,
            ar_counter=ar_counter,
            fshard=fshard,
            gh_scale=gh_scale,
        )
    n, num_features = bins.shape
    nbt = cfg.max_bin + 1
    lr = cfg.split.learning_rate
    missing_bin = cfg.max_bin

    # quantized-gh mode: sums stay in the exact integer domain until this
    # one dequantization point (gh_scale is None on the f32 legacy path,
    # where deq is the identity and every branch below traces the exact
    # pre-quantization program)
    quant = gh_scale is not None
    if quant:
        from xgboost_ray_tpu.ops.objectives import dequantize_gh_sums

        deq = lambda s: dequantize_gh_sums(s, gh_scale)  # noqa: E731
    else:
        deq = lambda s: s  # noqa: E731

    if fshard is None:
        cat_mask = cat_mask_const(cfg.cat_features, num_features)
        cat_mask_local = cat_mask
        fhm_local = feat_has_missing
        fmask_tree = feature_mask
        f_global_max = num_features - 1
    else:
        # params.py gates the combinations whose per-level state is
        # global-F; enforce here too for direct build_tree callers
        if (colsample_bylevel < 1.0 or colsample_bynode < 1.0
                or any(cfg.monotone_constraints)
                or cfg.interaction_constraints):
            raise NotImplementedError(
                "per-level/per-node column sampling and constraints are "
                "not supported with feature_parallel > 1"
            )
        # global routing view vs local split-search view of per-feature
        # state (shared derivation incl. the pad-column mask)
        (cat_mask, cat_mask_local, fhm_local, fmask_tree,
         f_global_max) = fshard_local_views(
            fshard, cfg.cat_features, num_features, feat_has_missing,
            feature_mask,
        )

    tree = empty_tree(cfg.heap_size)
    pos = jnp.zeros((n,), jnp.int32)
    done = jnp.zeros((n,), bool)
    row_value = jnp.zeros((n,), jnp.float32)
    active = jnp.ones((1,), bool)

    # monotone constraints: per-node feasible weight interval, narrowed at
    # every constrained-feature split by the children's weight midpoint
    # (xgboost hist's MonotonicConstraint propagation)
    mono_on = any(int(c) != 0 for c in cfg.monotone_constraints)
    mono_arr = lower = upper = None
    if mono_on:
        # the engine validates + zero-pads to exactly num_features
        # (engine.py constraint block); keep one normalization layer
        if len(cfg.monotone_constraints) != num_features:
            raise ValueError(
                f"monotone_constraints length "
                f"{len(cfg.monotone_constraints)} != {num_features} features"
                f" (pad with 0 for unconstrained columns)."
            )
        mono_arr = jnp.asarray(cfg.monotone_constraints, jnp.float32)
        lower = jnp.full((1,), -jnp.inf, jnp.float32)
        upper = jnp.full((1,), jnp.inf, jnp.float32)

    # interaction constraints: per-node set of still-active constraint
    # groups (those containing every feature used on the root path); the
    # allowed features are their union plus the path's own features
    ic_on = len(cfg.interaction_constraints) > 0
    if ic_on:
        import numpy as _np

        n_sets = len(cfg.interaction_constraints)
        mem_np = _np.zeros((n_sets, num_features), bool)
        for s, grp in enumerate(cfg.interaction_constraints):
            for fi in grp:
                if fi < num_features:
                    mem_np[s, fi] = True
        ic_membership = jnp.asarray(mem_np)  # [S, F]
        ic_active = jnp.ones((1, n_sets), bool)
        ic_used = jnp.zeros((1, num_features), bool)
        ic_has_used = jnp.zeros((1,), bool)

    prev_hist = None
    for d in range(cfg.max_depth):
        with jax.named_scope(f"level{d}"):
            n_nodes = 1 << d
            base = n_nodes - 1

            # Does THIS level's histogram cross the quantization size threshold?
            # (Mirrors quantized_hist_allreduce's static decision on the built
            # tensor; != "none" covers the row AND block wire modes.) Sub-
            # threshold levels take the exact f32 psum, and then node totals
            # also come from the histogram readout — bit-identical to
            # hist_quant="none", so small problems are a provable no-op.
            # node slots of this level's build: the smaller children only
            # (one a parent) under sibling subtraction
            build_nodes = (
                n_nodes // 2 if cfg.sibling_subtract and d > 0 else n_nodes
            )
            exact_totals = (
                cfg.hist_quant != "none"
                and build_nodes * num_features * nbt * 2 * 4
                >= cfg.hist_quant_min_bytes
            )

            node_gh_exact = counts_live = None
            if exact_totals:
                # quantized histogram wire: node totals must stay full-precision
                # (they become leaf weights -g/(h+lambda)), and the sibling-
                # subtraction child choice needs exact live-row counts. ONE
                # packed [n_nodes, 3] psum carries both — a single extra small
                # collective per level regardless of mode. Under quantized gh
                # the whole packed payload rides int32 (sums AND counts), so the
                # side-psum is an exact integer reduction dequantized once
                # (deq is the identity on the f32 path).
                live_pos = jnp.where(done, -1, pos)
                sums_live = node_sums_dense(gh, live_pos, n_nodes)
                packed = allreduce(
                    jnp.concatenate(
                        [
                            sums_live,
                            node_counts_dense(live_pos, n_nodes)[:, None]
                            .astype(sums_live.dtype),
                        ],
                        axis=1,
                    )
                )
                node_gh_exact = deq(packed[:, :2])
                counts_live = packed[:, 2]

            def _build(gh_b, pos_b, nn):
                """One histogram build over nn node slots.

                The missing bucket is reconstructed by subtraction (node_total -
                sum of regular bins), so with hist_precision="fast" the bf16
                rounding residue of the regular bins lands there; for features
                with NO missing values (known globally from the binned matrix)
                the bucket is exactly zero, so it is zeroed to keep phantom
                missing mass from steering the learned default direction.
                """
                with jax.named_scope("hist"):
                    return zero_phantom_missing(
                        build_histogram(
                            bins, gh_b, pos_b, nn, nbt, impl=cfg.hist_impl,
                            chunk=cfg.hist_chunk, precision=cfg.hist_precision,
                        ),
                        fhm_local,
                    )

            if cfg.sibling_subtract and d > 0 and prev_hist is not None:
                # Sibling subtraction: per parent, build only the globally-smaller
                # child's histogram (indexed by parent -> half the tensor and half
                # the one-hot width) and derive the sibling as parent - child.
                # The choice must be identical on every shard, so it is made from
                # allreduced per-child row counts.
                n_par = n_nodes // 2
                if counts_live is not None:
                    child_counts = counts_live
                else:
                    with jax.named_scope("hist"):
                        live_rows = node_counts_dense(
                            jnp.where(done, -1, pos), n_nodes
                        ).astype(jnp.float32)
                    child_counts = allreduce(live_rows)
                # [n_par] True when the right child is the (weakly) smaller one
                small_is_right = child_counts[1::2] <= child_counts[0::2]
                # every row streams through the build once, the bigger child's
                # with zeroed gh: nothing is compacted, so no shard's rows of
                # the chosen children can overflow a buffer
                parent_pos = pos >> 1
                is_right = (pos & 1).astype(bool)
                sel = (
                    is_right == lookup_by_node(parent_pos, small_is_right)[0]
                ) & ~done
                gh_sel = gh * sel[:, None].astype(gh.dtype)
                hist_small = hist_ar(_build(gh_sel, parent_pos, n_par))
                with jax.named_scope("hist"):
                    hist_big = prev_hist - hist_small
                    sir = small_is_right[:, None, None, None]
                    left = jnp.where(sir, hist_big, hist_small)
                    right = jnp.where(sir, hist_small, hist_big)
                    hist = jnp.stack([left, right], axis=1).reshape(
                        (n_nodes,) + hist_small.shape[1:]
                    )
            else:
                hist = hist_ar(_build(gh, pos, n_nodes))
            prev_hist = hist
            # [n_nodes, 2]: feature 0's buckets cover every row. Under
            # hist_precision="fast" these totals carry the regular bins' bf16
            # rounding (when feature 0 has no missing values its zeroed missing
            # bucket no longer re-balances the sum) — accepted as part of the
            # fast-precision accuracy/speed contract; use the default precision
            # when exact node totals matter.
            # under a quantized wire the histogram's feature-0 totals carry the
            # quantization rounding, which would land straight in the leaf
            # weights -g/(h+lambda); the packed exact psum above keeps node
            # totals full-precision while only the split *search* sees
            # quantized bin sums
            if exact_totals:
                node_gh = node_gh_exact
            else:
                node_gh = hist[:, 0, :, :].sum(axis=1)
                if fshard is not None:
                    # each shard's column-0 readout sums a DIFFERENT feature's
                    # buckets (same value up to f32 rounding); leaf weights must
                    # be identical on every chip, so global feature 0's owner —
                    # the column the (R, 1) program reads — wins
                    node_gh = fshard.bcast_from_shard0(node_gh)
                # quantized gh + exact int32 wire: the readout sums are exact
                # integer node totals — dequantize at the same boundary the
                # packed side-psum uses, so both totals paths agree bitwise
                node_gh = deq(node_gh)
            with jax.named_scope("split"):
                # the split search consumes real-valued bin sums: dequantize the
                # merged histogram ONCE per level (identity on the f32 path);
                # prev_hist stays in the quantized domain for sibling subtraction
                hist_sv = deq(hist)

                fmask = fmask_tree
                if colsample_bylevel < 1.0 and level_rng is not None:
                    k = jax.random.fold_in(jax.random.fold_in(level_rng, SALT_BYLEVEL), d)
                    lmask = sample_feature_mask(
                        k, num_features, colsample_bylevel, feature_log_weights
                    )
                    fmask = lmask if fmask is None else (fmask & lmask)
                if colsample_bynode < 1.0 and level_rng is not None:
                    k = jax.random.fold_in(jax.random.fold_in(level_rng, SALT_BYNODE), d)
                    nmask = sample_feature_mask(
                        k, num_features, colsample_bynode, feature_log_weights,
                        batch=n_nodes,
                    )
                    fmask = nmask if fmask is None else (nmask & fmask[None, :])

                if ic_on:
                    # allowed = union of still-active groups + the path's features;
                    # a node that has not split yet (root) may use any feature
                    union_active = jnp.any(
                        ic_active[:, :, None] & ic_membership[None, :, :], axis=1
                    )  # [n_nodes, F]
                    allowed = jnp.where(
                        ic_has_used[:, None], union_active | ic_used, True
                    )
                    if fmask is None:
                        fmask = allowed
                    else:
                        fmask = (fmask[None, :] if fmask.ndim == 1 else fmask) & allowed

                sp = find_splits(hist_sv, node_gh, cfg.split, feature_mask=fmask,
                                 cat_mask=cat_mask_local, monotone=mono_arr,
                                 node_lower=lower, node_upper=upper)
                if fshard is not None:
                    # the per-shard winner covers only this chip's feature slice;
                    # one tiny per-node record gather over the feature axis elects
                    # the global split (first-max tie-break — bitwise the (R, 1)
                    # argmax)
                    sp = elect_across_feature_shards(
                        sp, fshard.offset(num_features), cfg.max_bin, cfg.split,
                        fshard.axis, counter=fshard.counter,
                    )
                valid_split = sp.valid & active
                if depth_limit is not None:
                    # per-lane depth ceiling: a lane whose limit is this level keeps
                    # its active nodes but may not split them — they fall through to
                    # is_new_leaf below with node values from the histogram readout
                    # (vs the final-level exact psum, so a depth-masked lane matches
                    # its sequential twin to f32 rounding, bitwise only when its
                    # limit equals cfg.max_depth and this mask is never engaged)
                    valid_split = valid_split & (d < depth_limit)
                if mono_on:
                    node_value = lr * bounded_weight(
                        node_gh[:, 0], node_gh[:, 1], cfg.split, lower, upper
                    )
                else:
                    node_value = lr * leaf_weight(
                        node_gh[:, 0], node_gh[:, 1], cfg.split
                    )
                is_new_leaf = active & ~valid_split

                fsafe = jnp.clip(sp.feature, 0, f_global_max)
                thr = cuts[fsafe, jnp.clip(sp.split_bin, 0, cfg.max_bin - 2)]
                sl = slice(base, base + n_nodes)
                tree = tree._replace(
                    feature=tree.feature.at[sl].set(jnp.where(valid_split, sp.feature, -1)),
                    split_bin=tree.split_bin.at[sl].set(jnp.where(valid_split, sp.split_bin, 0)),
                    threshold=tree.threshold.at[sl].set(jnp.where(valid_split, thr, 0.0)),
                    default_left=tree.default_left.at[sl].set(sp.default_left & valid_split),
                    is_leaf=tree.is_leaf.at[sl].set(is_new_leaf),
                    value=tree.value.at[sl].set(jnp.where(is_new_leaf, node_value, 0.0)),
                    gain=tree.gain.at[sl].set(jnp.where(valid_split, sp.gain, 0.0)),
                    cover=tree.cover.at[sl].set(jnp.where(active, node_gh[:, 1], 0.0)),
                    base_weight=tree.base_weight.at[sl].set(
                        jnp.where(active, node_value, 0.0)
                    ),
                )

            with jax.named_scope("partition"):
                # counted as the level is traced: every fan-out takes the
                # dense form (lookup_by_node's measurements), so no level
                # counts under rxgb_route_gather_levels_total
                get_registry().counter("rxgb_route_dense_levels_total").inc()
                get_registry().counter("rxgb_route_gather_levels_total")
                is_cat_node = () if cat_mask is None else (cat_mask[fsafe],)
                (leaf_of_row, value_of_row, f_of_row, split_bin_of_row,
                 default_left_of_row, *is_cat_of_row) = lookup_by_node(
                    pos, is_new_leaf, node_value, fsafe, sp.split_bin,
                    sp.default_left, *is_cat_node,
                )
                newly_leafed = leaf_of_row & ~done
                row_value = jnp.where(newly_leafed, value_of_row, row_value)
                done = done | newly_leafed

                if fshard is None:
                    b = bin_of_feature(bins, f_of_row)
                else:
                    # winning feature's bin column, owner-broadcast over the
                    # feature axis: one [N] collective — O(rows), not O(rows x F)
                    b = fshard.bin_column(bins, f_of_row)
                go_right = route_right_binned(
                    b, split_bin_of_row, default_left_of_row,
                    is_cat_of_row[0] if is_cat_of_row else None, missing_bin,
                )
                effective_right = jnp.where(done, False, go_right)
                pos = pos * 2 + effective_right.astype(jnp.int32)
                active = jnp.repeat(valid_split, 2)

            if mono_on:
                # Recompute the CHOSEN split's child weights (same clamped
                # formula find_splits scored with) to narrow the children's
                # feasible interval at the midpoint — xgboost's monotone bound
                # propagation. O(n_nodes * bins), negligible next to the build.
                hist_f = jnp.take_along_axis(
                    hist_sv, fsafe[:, None, None, None], axis=1
                )[:, 0]  # [n_nodes, nbt, 2]
                gf, hf = hist_f[..., 0], hist_f[..., 1]
                sbin_c = jnp.clip(sp.split_bin, 0, cfg.max_bin - 2)[:, None]
                gl_c = jnp.take_along_axis(
                    jnp.cumsum(gf[:, : cfg.max_bin], axis=-1), sbin_c, axis=1
                )[:, 0]
                hl_c = jnp.take_along_axis(
                    jnp.cumsum(hf[:, : cfg.max_bin], axis=-1), sbin_c, axis=1
                )[:, 0]
                if cat_mask is not None:
                    is_cat = cat_mask[fsafe]
                    gl_c = jnp.where(
                        is_cat, jnp.take_along_axis(gf, sbin_c, axis=1)[:, 0], gl_c
                    )
                    hl_c = jnp.where(
                        is_cat, jnp.take_along_axis(hf, sbin_c, axis=1)[:, 0], hl_c
                    )
                gl_c = jnp.where(sp.default_left, gl_c + gf[:, cfg.max_bin], gl_c)
                hl_c = jnp.where(sp.default_left, hl_c + hf[:, cfg.max_bin], hl_c)
                wl = bounded_weight(gl_c, hl_c, cfg.split, lower, upper)
                wr = bounded_weight(
                    node_gh[:, 0] - gl_c, node_gh[:, 1] - hl_c, cfg.split,
                    lower, upper,
                )
                mid = 0.5 * (wl + wr)
                c = jnp.where(valid_split, mono_arr[fsafe], 0.0)
                lower_l = jnp.where(c < 0, jnp.maximum(lower, mid), lower)
                upper_l = jnp.where(c > 0, jnp.minimum(upper, mid), upper)
                lower_r = jnp.where(c > 0, jnp.maximum(lower, mid), lower)
                upper_r = jnp.where(c < 0, jnp.minimum(upper, mid), upper)
                lower = jnp.stack([lower_l, lower_r], axis=1).reshape(-1)
                upper = jnp.stack([upper_l, upper_r], axis=1).reshape(-1)

            if ic_on:
                contains_f = ic_membership.T[fsafe]  # [n_nodes, S]
                ic_active = jnp.where(
                    valid_split[:, None], ic_active & contains_f, ic_active
                )
                f_onehot = jnp.arange(num_features)[None, :] == fsafe[:, None]
                ic_used = ic_used | (valid_split[:, None] & f_onehot)
                ic_has_used = ic_has_used | valid_split
                ic_active = jnp.repeat(ic_active, 2, axis=0)
                ic_used = jnp.repeat(ic_used, 2, axis=0)
                ic_has_used = jnp.repeat(ic_has_used, 2)

    # Final level: every still-active node is a leaf.
    n_nodes = 1 << cfg.max_depth
    base = n_nodes - 1
    node_gh = deq(
        allreduce(node_sums_dense(gh, jnp.where(done, -1, pos), n_nodes))
    )
    if mono_on:
        node_value = lr * bounded_weight(
            node_gh[:, 0], node_gh[:, 1], cfg.split, lower, upper
        )
    else:
        node_value = lr * leaf_weight(node_gh[:, 0], node_gh[:, 1], cfg.split)
    sl = slice(base, base + n_nodes)
    tree = tree._replace(
        is_leaf=tree.is_leaf.at[sl].set(active),
        value=tree.value.at[sl].set(jnp.where(active, node_value, 0.0)),
        cover=tree.cover.at[sl].set(jnp.where(active, node_gh[:, 1], 0.0)),
        base_weight=tree.base_weight.at[sl].set(
            jnp.where(active, node_value, 0.0)
        ),
    )
    row_value = jnp.where(done, row_value, lookup_by_node(pos, node_value)[0])
    return tree, row_value


def predict_tree_binned(
    tree: Tree, bins: jnp.ndarray, max_depth: int, missing_bin: int,
    cat_features: tuple = (),
) -> jnp.ndarray:
    """Walk one tree over pre-binned rows; returns leaf value per row [N].

    Used during training to update eval-set margins with each new tree
    without leaving the device. ``max_depth`` 0 (a linked tree no depth
    bounds) walks until every row sits in a leaf.
    """
    return _walk_binned(
        tree, bins, max_depth, missing_bin, bins.shape[1], cat_features,
        bin_of_feature,
    )


def predict_tree_binned_fsharded(
    tree: Tree, bins: jnp.ndarray, max_depth: int, missing_bin: int,
    fshard, cat_features: tuple = (),
) -> jnp.ndarray:
    """``predict_tree_binned`` over a feature-sharded [N, F_pad/C] tile.

    The tree's split features are global indices, so each depth step
    owner-broadcasts the needed bin column across the feature axis (one
    [N] collective per step — the O(rows x depth) cost the 2D mesh pays
    for eval-set / sampled-build margin walks instead of replicating F).
    Routing state (idx) stays identical on every feature shard.
    """
    return _walk_binned(
        tree, bins, max_depth, missing_bin, fshard.f_padded, cat_features,
        fshard.bin_column,
    )


def _walk_binned(tree, bins, max_depth, missing_bin, num_features,
                 cat_features, column):
    """The binned walk of both bin layouts: the node tables a step needs
    read in one ``lookup_by_node`` (no per-row gather; heap steps compare
    only the slots ``slots_reached``), the split feature's bin by
    ``column(bins, f_of_row)``, the leaf value by the same lookup. Counts
    each step under ``rxgb_walk_dense_steps_total`` as it is traced."""
    layout = "heap" if tree.left is None else "linked"
    cat_mask = cat_mask_const(cat_features, num_features)
    f = jnp.clip(tree.feature, 0, num_features - 1)
    route = (f, tree.split_bin, tree.default_left) + (
        () if cat_mask is None else (cat_mask[f],)
    )

    read = lookup_by_node

    def go_right_at(idx, f_of_row, split_bin, default_left, *is_cat):
        get_registry().counter(
            f'rxgb_walk_dense_steps_total{{layout="{layout}"}}',
            "steps of the binned tree walk traced, by the tree's layout",
        ).inc()
        return route_right_binned(
            column(bins, f_of_row), split_bin, default_left,
            is_cat[0] if is_cat else None, missing_bin,
        )

    root = jnp.zeros((bins.shape[0],), jnp.int32)
    leaf = walk_to_leaf(tree, root, max_depth, go_right_at, read, route)
    return read(leaf, tree.value[:slots_reached(tree, max_depth or None)])[0]

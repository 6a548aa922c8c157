"""Leaf-wise (``grow_policy=lossguide``) tree growth under static shapes.

xgboost's lossguide policy repeatedly splits the FRONTIER LEAF WITH THE
HIGHEST GAIN until ``max_leaves`` is reached — depth-asymmetric trees that
chase the best objective reduction first (the LightGBM growth strategy;
reference surface: the params dict forwarded untouched at
``xgboost_ray/main.py:745-752``).

TPU-native formulation: the dynamic best-first loop becomes ONE
``lax.scan`` of ``max_leaves - 1`` identical steps over a static frontier
table of ``2*max_leaves - 1`` entries (every node the tree can ever
create). Each step: argmax over frontier gains -> split that leaf (dynamic
heap slot, pure scatters) -> route only its rows -> build the two
children's histograms (one-hot MXU pass over all rows, psum-merged at the
reference's Rabit point) -> score their best splits into the two
append-slots ``1+2t, 2+2t``. Append-only indexing keeps every shape static
and the whole tree build a single compiled program.

Cost note: each step's histogram pass is O(N) regardless of the split
leaf's row count (rows outside the leaf are masked, not skipped), so a
full lossguide tree costs O(N * max_leaves) histogram work vs depthwise's
O(N * max_depth). That is the static-shape price; the constant is one
bf16/f32 one-hot matmul per step, which the MXU absorbs.
"""

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from xgboost_ray_tpu.ops.grow import (
    GrowConfig,
    Tree,
    cat_mask_const,
    empty_tree,
    fshard_local_views,
    route_right_binned,
)
from xgboost_ray_tpu.ops.histogram import (
    build_histogram,
    node_sums,
    zero_phantom_missing,
)
from xgboost_ray_tpu.ops.split import (
    elect_across_feature_shards,
    find_splits,
    leaf_weight,
)


def build_tree_lossguide(
    bins: jnp.ndarray,  # [N, F] int bins (max_bin == missing bucket); may be
    #   a compacted [M, F] row selection (ops/sampling.py) — each step's
    #   O(N) one-hot pass then costs O(M)
    gh: jnp.ndarray,  # [N, 2] grad/hess (0 for padding rows; GOSS-amplified
    #   for sampled-remainder rows)
    cuts: jnp.ndarray,  # [F, max_bin-1] raw cut values
    cfg: GrowConfig,
    feature_mask: Optional[jnp.ndarray] = None,  # [F] bool (colsample_bytree)
    allreduce: Callable[[jnp.ndarray], jnp.ndarray] = lambda x: x,
    feat_has_missing: Optional[jnp.ndarray] = None,
    hist_allreduce: Optional[Callable[[jnp.ndarray], jnp.ndarray]] = None,
    ar_counter=None,  # AllreduceBytes: the scan body traces once, runs
    #   leaves-1 times — the repeated() scope keeps byte accounting exact
    fshard=None,  # ops.feature_shard.FeatureShard on a 2D row x feature mesh
    gh_scale: Optional[jnp.ndarray] = None,  # [2] f32 scales of a quantized
    #   integer gh buffer (gh_precision); None = the f32 legacy path
):
    """Grow one leaf-wise tree. Returns (Tree, row_value[N]) — the same
    contract as ``build_tree`` so the engine's round step is policy-blind.

    With ``gh_scale`` the per-step 2-node histogram accumulates the integer
    gh buffer exactly (int -> int32) and bin sums / node totals are
    dequantized once at the split-search boundary, mirroring ``build_tree``'s
    quantized-gh contract.

    ``hist_allreduce`` merges the per-step 2-node histogram (may be
    quantized per ``cfg.hist_quant``); exact node totals ride ``allreduce``
    when quantization is on, mirroring the depthwise grower. With
    ``fshard`` the per-step histogram/split search covers this chip's
    feature tile and the step's winner is elected over the feature axis,
    mirroring ``build_tree``'s 2D contract (bins local, cuts/
    feat_has_missing/feature_mask global feature-padded)."""
    hist_ar = hist_allreduce if hist_allreduce is not None else allreduce
    quant = gh_scale is not None
    if quant:
        from xgboost_ray_tpu.ops.objectives import dequantize_gh_sums

        deq = lambda s: dequantize_gh_sums(s, gh_scale)  # noqa: E731
    else:
        deq = lambda s: s  # noqa: E731
    n, num_features = bins.shape
    nbt = cfg.max_bin + 1
    missing_bin = cfg.max_bin
    lr = cfg.split.learning_rate
    heap = cfg.heap_size
    leaves = max(1, int(cfg.max_leaves))
    n_ent = 2 * leaves - 1
    if fshard is None:
        cat_mask = cat_mask_const(cfg.cat_features, num_features)
        cat_mask_local = cat_mask
        fhm_local = feat_has_missing
        fmask_local = feature_mask
        f_global_max = num_features - 1
    else:
        # shared global-vs-local derivation (incl. the pad-column mask)
        (cat_mask, cat_mask_local, fhm_local, fmask_local,
         f_global_max) = fshard_local_views(
            fshard, cfg.cat_features, num_features, feat_has_missing,
            feature_mask,
        )

    def _hist(gh_b, pos_b, nn):
        # node totals downstream are read from the zeroed histogram's
        # feature-0 row, so under hist_precision="fast" they carry the
        # regular bins' bf16 rounding — the SAME accepted contract as the
        # depthwise grower's node_gh (see ops/grow.py's node_gh comment).
        # Always the one-hot MXU pass, on the CPU too (params.py pins
        # hist_impl to auto|onehot for lossguide).
        with jax.named_scope("hist"):
            h = build_histogram(
                bins, gh_b, pos_b, nn, nbt, impl="onehot",
                chunk=cfg.hist_chunk, precision=cfg.hist_precision,
            )
        return zero_phantom_missing(hist_ar(h), fhm_local)

    def _node_gh(hist, gh_b, pos_b, nn):
        # [nn, 2] totals: exact psum when the histogram wire is quantized
        # (leaf weights must not carry quantization rounding), feature-0
        # readout otherwise (free). Mirrors quantized_hist_allreduce's
        # static size-threshold decision — != "none" covers row and block
        # wire modes alike — so sub-threshold trees stay bit-identical to
        # hist_quant="none".
        quantized = (
            cfg.hist_quant != "none"
            and nn * num_features * nbt * 2 * 4 >= cfg.hist_quant_min_bytes
        )
        if quantized:
            # under quantized gh the side-psum rides int32 (exact) and is
            # dequantized here — the one boundary both totals paths share
            return deq(allreduce(node_sums(gh_b, pos_b, nn)))
        totals = hist[:, 0, :, :].sum(axis=1)
        if fshard is not None:
            # column-0 readout differs per feature shard in f32 rounding;
            # global feature 0's owner wins (see build_tree's node_gh)
            totals = fshard.bcast_from_shard0(totals)
        return deq(totals)

    tree = empty_tree(heap)
    pos = jnp.zeros((n,), jnp.int32)

    # --- root: evaluate its best split, seed the frontier -------------------
    root_hist = _hist(gh, pos, 1)  # [1, F_local, nbt, 2]
    root_gh = _node_gh(root_hist, gh, pos, 1)  # [1, 2]
    with jax.named_scope("split"):
        sp0 = find_splits(deq(root_hist), root_gh, cfg.split,
                          feature_mask=fmask_local, cat_mask=cat_mask_local)
        if fshard is not None:
            sp0 = elect_across_feature_shards(
                sp0, fshard.offset(num_features), cfg.max_bin, cfg.split,
                fshard.axis, counter=fshard.counter,
            )
    root_value = lr * leaf_weight(root_gh[:, 0], root_gh[:, 1], cfg.split)[0]
    tree = tree._replace(
        is_leaf=tree.is_leaf.at[0].set(True),
        value=tree.value.at[0].set(root_value),
        cover=tree.cover.at[0].set(root_gh[0, 1]),
        base_weight=tree.base_weight.at[0].set(root_value),
    )

    # frontier entry table (append-only; entry 0 = root)
    ent_pos = jnp.full((n_ent,), -1, jnp.int32).at[0].set(0)
    ent_active = jnp.zeros((n_ent,), bool).at[0].set(True)
    can_root = heap > 1  # max_depth >= 1
    ent_gain = jnp.full((n_ent,), -jnp.inf).at[0].set(
        jnp.where(sp0.valid[0] & can_root, sp0.gain[0], -jnp.inf)
    )
    ent_feat = jnp.zeros((n_ent,), jnp.int32).at[0].set(sp0.feature[0])
    ent_bin = jnp.zeros((n_ent,), jnp.int32).at[0].set(sp0.split_bin[0])
    ent_dl = jnp.zeros((n_ent,), bool).at[0].set(sp0.default_left[0])

    b32 = bins.astype(jnp.int32)

    def body(carry, t):
        tree, pos, ent_pos, ent_active, ent_gain, ent_feat, ent_bin, ent_dl = carry

        scores = jnp.where(ent_active, ent_gain, -jnp.inf)
        i = jnp.argmax(scores)
        do_split = jnp.isfinite(scores[i])

        slot = ent_pos[i]
        feat = jnp.clip(ent_feat[i], 0, f_global_max)
        sbin = ent_bin[i]
        dl = ent_dl[i]
        thr = cuts[feat, jnp.clip(sbin, 0, cfg.max_bin - 2)]
        slot_c = jnp.maximum(slot, 0)

        # parent leaf -> internal node (scatters guarded by do_split)
        def setw(arr, idx, new):
            return arr.at[idx].set(jnp.where(do_split, new, arr[idx]))

        tree = tree._replace(
            feature=setw(tree.feature, slot_c, feat),
            split_bin=setw(tree.split_bin, slot_c, sbin),
            threshold=setw(tree.threshold, slot_c, thr),
            default_left=setw(tree.default_left, slot_c, dl),
            is_leaf=setw(tree.is_leaf, slot_c, False),
            value=setw(tree.value, slot_c, 0.0),
            gain=setw(tree.gain, slot_c, ent_gain[i]),
        )

        # route ONLY this leaf's rows
        with jax.named_scope("partition"):
            sel = (pos == slot) & do_split
            if fshard is None:
                bv = jnp.take_along_axis(
                    b32, jnp.full((n, 1), feat), axis=1
                )[:, 0]
            else:
                # split feature is a global index; owner-broadcast its column
                bv = fshard.bin_column(bins, jnp.full((n,), feat))
            go_right = route_right_binned(
                bv, sbin, dl,
                None if cat_mask is None else cat_mask[feat], missing_bin,
            )
            l_slot, r_slot = 2 * slot_c + 1, 2 * slot_c + 2
            pos = jnp.where(sel, jnp.where(go_right, r_slot, l_slot), pos)

        # the two children's histograms + best splits
        gh_sel = gh * sel[:, None].astype(gh.dtype)
        pos2 = go_right.astype(jnp.int32)
        hist2 = _hist(gh_sel, pos2, 2)  # [2, F_local, nbt, 2]
        child_gh = _node_gh(hist2, gh_sel, pos2, 2)  # [2, 2]
        with jax.named_scope("split"):
            sp2 = find_splits(deq(hist2), child_gh, cfg.split,
                              feature_mask=fmask_local,
                              cat_mask=cat_mask_local)
            if fshard is not None:
                sp2 = elect_across_feature_shards(
                    sp2, fshard.offset(num_features), cfg.max_bin, cfg.split,
                    fshard.axis, counter=fshard.counter,
                )
        child_slots = jnp.stack([l_slot, r_slot])
        # children may split further only while their own children fit the
        # depth-bounded heap
        can_deepen = 2 * child_slots + 2 < heap
        child_gain = jnp.where(
            sp2.valid & can_deepen & do_split, sp2.gain, -jnp.inf
        )
        child_value = lr * leaf_weight(child_gh[:, 0], child_gh[:, 1],
                                       cfg.split)

        def set2(arr, new):
            upd = jnp.where(do_split, new, arr[child_slots])
            return arr.at[child_slots].set(upd)

        tree = tree._replace(
            is_leaf=set2(tree.is_leaf, jnp.array([True, True])),
            value=set2(tree.value, child_value),
            cover=set2(tree.cover, child_gh[:, 1]),
            base_weight=set2(tree.base_weight, child_value),
        )

        # frontier bookkeeping: retire entry i, append children at 1+2t, 2+2t
        ent_active = ent_active.at[i].set(
            jnp.where(do_split, False, ent_active[i])
        )
        k = 1 + 2 * t
        ks = jnp.stack([k, k + 1])

        def app(arr, new, fill):
            upd = jnp.where(do_split, new, jnp.asarray(fill, arr.dtype))
            return arr.at[ks].set(upd)

        ent_pos = app(ent_pos, child_slots, -1)
        ent_active = app(ent_active, jnp.array([True, True]), False)
        ent_gain = app(ent_gain, child_gain, -jnp.inf)
        ent_feat = app(ent_feat, sp2.feature, 0)
        ent_bin = app(ent_bin, sp2.split_bin, 0)
        ent_dl = app(ent_dl, sp2.default_left, False)

        return (tree, pos, ent_pos, ent_active, ent_gain, ent_feat, ent_bin,
                ent_dl), None

    if leaves > 1:
        import contextlib

        carry = (tree, pos, ent_pos, ent_active, ent_gain, ent_feat, ent_bin,
                 ent_dl)
        scope = (
            ar_counter.repeated(leaves - 1)
            if ar_counter is not None
            else contextlib.nullcontext()
        )
        # the feature-axis counter (election gather + bin-column psum in
        # the scan body) multiplies by the step count too
        fscope = (
            fshard.counter.repeated(leaves - 1)
            if fshard is not None and fshard.counter is not None
            else contextlib.nullcontext()
        )
        with scope, fscope:
            carry, _ = jax.lax.scan(body, carry, jnp.arange(leaves - 1))
        tree, pos = carry[0], carry[1]

    row_value = tree.value[pos]
    return tree, row_value

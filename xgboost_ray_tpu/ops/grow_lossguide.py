"""Leaf-wise (``grow_policy=lossguide``) tree growth under static shapes.

xgboost's lossguide policy repeatedly splits the FRONTIER LEAF WITH THE
HIGHEST GAIN until ``max_leaves`` is reached — depth-asymmetric trees that
chase the best objective reduction first (the LightGBM growth strategy;
reference surface: the params dict forwarded untouched at
``xgboost_ray/main.py:745-752``).

TPU-native formulation: **the tree is grown level by level, speculatively
and exactly.** Which nodes best-first growth splits is a function of the
gains alone: it pops the frontier's largest gain first, and that order
restricted to any ancestor-closed set of nodes is the same order (a node's
rank can only fall as more nodes become known). So at level ``d``:

1. *select* — replay best-first (at most ``max_leaves - 1`` pops over a
   table of a few hundred gains, no rows) over the nodes evaluated so far;
   the popped nodes of depth ``d - 1`` whose children are not evaluated yet
   are *wanted*;
2. *pass* — expand the wanted nodes in one pass over all rows (of the
   smallest of ``PASS_WIDTHS`` node slots that holds them; more passes only
   where a level wants more than the widest):
   route their rows (dense lookups keyed by node id, no gather), build the
   smaller child's histogram of each in ONE dense build with the node on the
   matmul's right-hand side, derive the sibling by subtraction from the
   parent's stored histogram, score both children;

and growth stops when the replay wants nothing new: the popped set then is
exactly the set best-first growth splits. Nodes evaluated and not kept are
the price of speculation (``lossguide_nodes_evaluated`` against
``lossguide_splits``).

Cost: a tree costs its LEVELS, not its leaves — a pass a level, each about
one dense level of the depth-wise grower at that fan-out (PERF.md §5). A
255-leaf tree at 11M rows takes 11-17 passes where the former 254-step scan
took 254.

The result is a padded heap where ``max_depth`` bounds the depth, and else
(``max_depth=0``) ``ops.grow.LinkedTree``: ``2 * max_leaves
- 1`` slots in the order best-first growth made them.
"""

import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from xgboost_ray_tpu.ops.grow import (
    GrowConfig,
    LinkedTree,
    bin_of_feature,
    cat_mask_const,
    empty_tree,
    fshard_local_views,
    lookup_by_node,
    route_right_binned,
)
from xgboost_ray_tpu.ops.histogram import (
    _acc_dtype,
    build_histogram,
    node_counts_dense,
    node_sums_dense,
    zero_phantom_missing,
)
from xgboost_ray_tpu.ops.split import (
    elect_across_feature_shards,
    find_splits,
    leaf_weight,
)

#: node slots a pass over the rows may have. The dense build pays for its
#: one-hot once a pass whatever the slots (66-73 ms at 11M rows through 16
#: slots) and then about 2 ms a slot (82 ms at 32, 144 at 64, 262 at 128;
#: PERF.md §5), so a level takes ONE pass of the smallest width that holds
#: its wanted nodes, and only a level of more than the widest takes
#: another. Steps of 16: a coarser menu (16 / 32 / 64 / 128) made a level's
#: cost jump by 118 ms where it wants 65 nodes and not 64, and a run's
#: `round_ms` swing with it (PERF.md §6)
PASS_WIDTHS = tuple(range(16, 129, 16))
#: nodes the table of evaluated nodes holds, per leaf of the budget. A tree
#: that evaluates more stops expanding the nodes that no longer fit
#: (counted in ``lossguide_table_overflows``, warned about after training)
TABLE_FACTOR = 8


def table_size(max_leaves: int) -> int:
    """Slots of the table of evaluated nodes (odd: the root and pairs)."""
    return 2 * (TABLE_FACTOR * max(1, int(max_leaves)) // 2) + 1


def build_tree_lossguide(
    bins: jnp.ndarray,  # [N, F] int bins (max_bin == missing bucket); may be
    #   a compacted [M, F] row selection (ops/sampling.py)
    gh: jnp.ndarray,  # [N, 2] grad/hess (0 for padding rows; GOSS-amplified
    #   for sampled-remainder rows)
    cuts: jnp.ndarray,  # [F, max_bin-1] raw cut values
    cfg: GrowConfig,
    feature_mask: Optional[jnp.ndarray] = None,  # [F] bool (colsample_bytree)
    allreduce: Callable[[jnp.ndarray], jnp.ndarray] = lambda x: x,
    feat_has_missing: Optional[jnp.ndarray] = None,
    hist_allreduce: Optional[Callable[[jnp.ndarray], jnp.ndarray]] = None,
    ar_counter=None,  # AllreduceBytes: a pass's collectives are recorded
    #   once and scaled by the passes the program counts; also takes the
    #   tree's LOSSGUIDE_STATS
    fshard=None,  # ops.feature_shard.FeatureShard on a 2D row x feature mesh
    gh_scale: Optional[jnp.ndarray] = None,  # [2] f32 scales of a quantized
    #   integer gh buffer (gh_precision); None = the f32 legacy path
):
    """Grow one leaf-wise tree. Returns (Tree, row_value[N]) — the same
    contract as ``build_tree`` so the engine's round step is policy-blind.

    With ``gh_scale`` every histogram accumulates the integer gh buffer
    exactly (int -> int32), stored parents and sibling subtraction stay in
    that domain, and bin sums / node totals are dequantized once at the
    split-search boundary, mirroring ``build_tree``'s quantized-gh contract.

    ``hist_allreduce`` merges a pass's histogram (one collective a pass; may
    be quantized per ``cfg.hist_quant``); the live-row counts of sibling
    subtraction and, when quantization is on, exact node totals ride
    ``allreduce``, mirroring the depthwise grower. With ``fshard`` the
    histograms and the split search cover this chip's feature tile and each
    node's winner is elected over the feature axis, mirroring
    ``build_tree``'s 2D contract (bins local, cuts/feat_has_missing/
    feature_mask global feature-padded)."""
    hist_ar = hist_allreduce if hist_allreduce is not None else allreduce
    if gh_scale is not None:
        from xgboost_ray_tpu.ops.objectives import dequantize_gh_sums

        deq = lambda s: dequantize_gh_sums(s, gh_scale)  # noqa: E731
    else:
        deq = lambda s: s  # noqa: E731
    n, num_features = bins.shape
    nbt = cfg.max_bin + 1
    missing_bin = cfg.max_bin
    lr = cfg.split.learning_rate
    leaves = max(1, int(cfg.max_leaves))
    bounded = cfg.max_depth > 0  # else: no depth bound, linked layout
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
    counters = [c for c in (
        ar_counter, None if fshard is None else fshard.counter
    ) if c is not None]

    def _build(bins_b, gh_b, slot_b, nn):
        # always the dense one-hot build, on the CPU too (params.py pins
        # hist_impl to auto|onehot for lossguide)
        with jax.named_scope("hist"):
            return zero_phantom_missing(
                build_histogram(
                    bins_b, gh_b, slot_b, nn, nbt, impl="onehot",
                    chunk=cfg.hist_chunk, precision=cfg.hist_precision,
                ),
                fhm_local,
            )

    def _exact_totals(nn):
        # mirrors quantized_hist_allreduce's static size-threshold decision
        # (!= "none" covers row and block wire modes alike) so sub-threshold
        # builds stay bit-identical to hist_quant="none"
        return (cfg.hist_quant != "none"
                and nn * num_features * nbt * 2 * 4 >= cfg.hist_quant_min_bytes)

    def _readout(hist):
        # [nn, 2] node totals from feature 0's buckets: under
        # hist_precision="fast" they carry the regular bins' bf16 rounding —
        # the SAME accepted contract as the depthwise grower's node_gh
        totals = hist[:, 0, :, :].sum(axis=1)
        if fshard is not None:
            # global feature 0's owner wins (see build_tree's node_gh)
            totals = fshard.bcast_from_shard0(totals)
        return deq(totals)

    def _splits(hist, node_gh):
        with jax.named_scope("split"):
            sp = find_splits(deq(hist), node_gh, cfg.split,
                             feature_mask=fmask_local,
                             cat_mask=cat_mask_local)
            if fshard is not None:
                sp = elect_across_feature_shards(
                    sp, fshard.offset(num_features), cfg.max_bin, cfg.split,
                    fshard.axis, counter=fshard.counter,
                )
        return sp

    # --- root ---------------------------------------------------------------
    with jax.named_scope("level0"):
        pos0 = jnp.zeros((n,), jnp.int32)
        root_hist = hist_ar(_build(bins, gh, pos0, 1))  # [1, F_local, nbt, 2]
        if _exact_totals(1):
            root_gh = deq(allreduce(node_sums_dense(gh, pos0, 1)))
        else:
            root_gh = _readout(root_hist)
        sp0 = _splits(root_hist, root_gh)

    most = max(1, leaves - 1)  # nodes a level can want
    widths = sorted({min(w, most) for w in PASS_WIDTHS})
    # a level's wanted nodes, padded so that the widest pass may start at
    # the last of them
    wanted_cap = most + widths[-1]
    k_tab = table_size(leaves)
    flat = num_features * nbt * 2
    slots = jnp.arange(k_tab, dtype=jnp.int32)

    table = {
        "gain": jnp.full((k_tab,), -jnp.inf).at[0].set(
            jnp.where(sp0.valid[0], sp0.gain[0], -jnp.inf)),
        "feat": jnp.zeros((k_tab,), jnp.int32).at[0].set(sp0.feature[0]),
        "bin": jnp.zeros((k_tab,), jnp.int32).at[0].set(sp0.split_bin[0]),
        "dl": jnp.zeros((k_tab,), bool).at[0].set(sp0.default_left[0]),
        "g": jnp.zeros((k_tab,), jnp.float32).at[0].set(root_gh[0, 0]),
        "h": jnp.zeros((k_tab,), jnp.float32).at[0].set(root_gh[0, 1]),
        "parent": jnp.full((k_tab,), -1, jnp.int32),
        "child": jnp.full((k_tab,), -1, jnp.int32),  # left child's id
        "depth": jnp.zeros((k_tab,), jnp.int32),
        "hslot": jnp.zeros((k_tab,), jnp.int32),  # heap slot (bounded)
    }

    def select(table, n_nodes, overflows):
        """Best-first over the evaluated nodes: ``order`` (the popped node
        ids, -1 padded), and the popped nodes not expanded yet, as many as
        the table has room for, in pop order."""
        with jax.named_scope("select"):
            gain, child = table["gain"], table["child"]

            def pop(state):
                # the frontier's largest gain; among equal gains the node
                # made first (the smaller id in best-first's own numbering:
                # xgboost's tie-break)
                t, frontier, made, order, _ = state
                top = jnp.max(jnp.where(frontier, gain, -jnp.inf))
                ok = top > -jnp.inf
                i = jnp.argmin(jnp.where(
                    frontier & (gain == top), made, jnp.iinfo(jnp.int32).max
                )).astype(jnp.int32)
                c = child[i]
                opened = jnp.where(ok & (c >= 0), c, k_tab)
                frontier = (
                    frontier.at[jnp.where(ok, i, k_tab)].set(False, mode="drop")
                    .at[opened].set(True, mode="drop")
                    .at[opened + 1].set(True, mode="drop")
                )
                made = (made.at[opened].set(1 + 2 * t, mode="drop")
                        .at[opened + 1].set(2 + 2 * t, mode="drop"))
                order = order.at[jnp.where(ok, t, leaves)].set(i, mode="drop")
                return t + ok.astype(jnp.int32), frontier, made, order, ok

            _, _, _, order, _ = jax.lax.while_loop(
                lambda s: (s[0] < leaves - 1) & s[4], pop,
                (jnp.int32(0), slots == 0, jnp.zeros((k_tab,), jnp.int32),
                 jnp.full((leaves - 1,), -1, jnp.int32), jnp.bool_(True)),
            )
            is_wanted = (order >= 0) & (child[jnp.maximum(order, 0)] < 0)
            rank = jnp.cumsum(is_wanted.astype(jnp.int32)) - 1
            wanted = jnp.full((wanted_cap,), -1, jnp.int32).at[
                jnp.where(is_wanted, rank, wanted_cap)
            ].set(order, mode="drop")
            n_wanted = jnp.sum(is_wanted, dtype=jnp.int32)
            keep = jnp.minimum(n_wanted, (k_tab - n_nodes) // 2)
            fits = jnp.arange(wanted_cap, dtype=jnp.int32) < keep
            # a wanted node the table cannot take is never split
            gain = gain.at[
                jnp.where(fits | (wanted < 0), k_tab, wanted)
            ].set(-jnp.inf, mode="drop")
            return (dict(table, gain=gain), order,
                    jnp.where(fits, wanted, -1), keep,
                    overflows + n_wanted - keep)

    acc = _acc_dtype(gh)
    marks = [c.mark() for c in counters]

    per_pass = {}  # width -> what one pass of it records in each counter

    def one_pass(carry, level, width):
        """Expand the next ``width`` wanted nodes of ``level``."""
        for c, m in zip(counters, marks):
            c.rewind(m)
        done, node_id, table, hist_cur, passes = carry
        ids = jax.lax.dynamic_slice_in_dim(level["wanted"], done, width)
        live = ids >= 0
        idc = jnp.maximum(ids, 0)
        feat = jnp.clip(table["feat"][idc], 0, f_global_max)
        with jax.named_scope("partition"):
            is_cat = () if cat_mask is None else (cat_mask[feat],)
            (rel, in_pass, f_of_row, bin_of_row, dl_of_row,
             *cat_of_row) = lookup_by_node(
                node_id, jnp.arange(width, dtype=jnp.int32),
                jnp.ones((width,), bool), feat, table["bin"][idc],
                table["dl"][idc], *is_cat, keys=ids,
            )
            if fshard is None:
                bv = bin_of_feature(bins, f_of_row)
            else:
                # split feature is a global index; owner-broadcast its column
                bv = fshard.bin_column(bins, f_of_row)
            go_right = route_right_binned(
                bv, bin_of_row, dl_of_row,
                cat_of_row[0] if cat_of_row else None, missing_bin,
            )
            side = go_right.astype(jnp.int32)
            child_slot = jnp.where(in_pass, 2 * rel + side, -1)
            slot = jnp.where(in_pass, rel, 0)
        exact = _exact_totals(width if cfg.sibling_subtract else 2 * width)
        child_gh = None
        if cfg.sibling_subtract:
            # per parent, build only the globally-smaller child and derive
            # its sibling as parent - child; the choice is made from
            # allreduced live-row counts so every shard makes the same one
            with jax.named_scope("hist"):
                counts = node_counts_dense(child_slot, 2 * width).astype(
                    jnp.float32)
            if exact:
                # one packed psum: exact totals beside the counts (int32
                # under quantized gh), as in build_tree
                sums = node_sums_dense(gh, child_slot, 2 * width)
                packed = allreduce(jnp.concatenate(
                    [sums, counts[:, None].astype(sums.dtype)], axis=1))
                child_gh, counts = deq(packed[:, :2]), packed[:, 2]
            else:
                counts = allreduce(counts)
            small_is_right = counts[1::2] <= counts[0::2]
            with jax.named_scope("partition"):
                sel = in_pass & (
                    go_right == lookup_by_node(slot, small_is_right)[0])
            hist_small = hist_ar(_build(
                bins, gh * sel[:, None].astype(gh.dtype), slot, width))
            with jax.named_scope("hist"):
                src = jnp.clip(idc - level["base_prev"], 0,
                               2 * wanted_cap - 1)
                hist_big = (level["hist_prev"][src].reshape(hist_small.shape)
                            - hist_small)
                sir = small_is_right[:, None, None, None]
                hist2 = jnp.stack(
                    [jnp.where(sir, hist_big, hist_small),
                     jnp.where(sir, hist_small, hist_big)], axis=1,
                ).reshape((2 * width,) + hist_small.shape[1:])
        else:
            if exact:
                child_gh = deq(allreduce(
                    node_sums_dense(gh, child_slot, 2 * width)))
            hist2 = hist_ar(_build(
                bins, gh * in_pass[:, None].astype(gh.dtype),
                jnp.maximum(child_slot, 0), 2 * width))
        if child_gh is None:
            child_gh = _readout(hist2)
        sp = _splits(hist2, child_gh)
        with jax.named_scope("split"):
            first = level["base_new"] + 2 * done
            child_ids = first + jnp.arange(2 * width, dtype=jnp.int32)
            live2 = jnp.repeat(live, 2)
            parent2 = jnp.repeat(idc, 2)
            depth2 = table["depth"][parent2] + 1
            may_split = sp.valid & live2
            if bounded:
                may_split = may_split & (depth2 < cfg.max_depth)
            dst = jnp.where(live2, child_ids, k_tab)

            def put(name, values):
                return table[name].at[dst].set(values, mode="drop")

            table = dict(
                table,
                gain=put("gain", jnp.where(may_split, sp.gain, -jnp.inf)),
                feat=put("feat", sp.feature),
                bin=put("bin", sp.split_bin),
                dl=put("dl", sp.default_left),
                g=put("g", child_gh[:, 0]),
                h=put("h", child_gh[:, 1]),
                parent=put("parent", parent2),
                depth=put("depth", depth2),
                hslot=put("hslot", 2 * table["hslot"][parent2] + 1
                          + (jnp.arange(2 * width, dtype=jnp.int32) & 1)),
                child=table["child"].at[jnp.where(live, idc, k_tab)].set(
                    child_ids[0::2], mode="drop"),
            )
            hist_cur = jax.lax.dynamic_update_slice_in_dim(
                hist_cur, hist2.reshape(2 * width, flat), 2 * done, axis=0)
        with jax.named_scope("partition"):
            node_id = jnp.where(in_pass, first + child_slot, node_id)
        per_pass[width] = [c.since(m) for c, m in zip(counters, marks)]
        return (done + width, node_id, table, hist_cur,
                passes.at[widths.index(width)].add(1))

    def one_level(state):
        """The passes of one level (one, of the smallest width that holds
        what the level wants, unless it wants more than the widest), then
        the replay that says what the next level wants."""
        n_wanted = state["n_wanted"]
        level = {"wanted": state["wanted"], "base_prev": state["base_prev"],
                 "base_new": state["n_nodes"], "hist_prev": state["hist_prev"]}

        def next_pass(carry):
            left = n_wanted - carry[0]
            return jax.lax.switch(
                sum((left > w).astype(jnp.int32) for w in widths[:-1]),
                [lambda c, w=w: one_pass(c, level, w) for w in widths], carry)

        with jax.named_scope("level"):
            _, node_id, table, hist_cur, passes = jax.lax.while_loop(
                lambda c: c[0] < n_wanted, next_pass,
                (jnp.int32(0), state["node_id"], state["table"],
                 state["hist_cur"], state["passes"]))
        grown = state["n_nodes"] + 2 * n_wanted
        table, order, wanted, n_wanted_next, overflows = select(
            table, grown, state["overflows"])
        return dict(
            state, node_id=node_id, table=table, order=order, wanted=wanted,
            n_wanted=n_wanted_next, overflows=overflows, passes=passes,
            hist_prev=hist_cur, hist_cur=state["hist_prev"],
            base_prev=state["n_nodes"], n_nodes=grown,
            evaluated=state["evaluated"] + 2 * n_wanted,
        )

    state = {
        "node_id": jnp.zeros((n,), jnp.int32), "table": table,
        "order": jnp.full((most,), -1, jnp.int32),
        "passes": jnp.zeros((len(widths),), jnp.int32),  # by width
        "evaluated": jnp.int32(1), "overflows": jnp.int32(0),
    }
    if leaves > 1:
        table, order, wanted, n_wanted, overflows = select(
            table, jnp.int32(1), state["overflows"])
        hist_prev = jnp.zeros((2 * wanted_cap, flat), acc).at[0].set(
            root_hist.reshape(flat))
        state = jax.lax.while_loop(
            lambda s: s["n_wanted"] > 0, one_level,
            dict(state, table=table, order=order, wanted=wanted,
                 n_wanted=n_wanted, overflows=overflows, hist_prev=hist_prev,
                 hist_cur=jnp.zeros_like(hist_prev), base_prev=jnp.int32(0),
                 n_nodes=jnp.int32(1)))
        for c, m in zip(counters, marks):
            c.rewind(m)
        for i, w in enumerate(widths):
            for c, one in zip(counters, per_pass[w]):
                c.add_trips(one, state["passes"][i])
        if ar_counter is not None and cfg.sibling_subtract:
            ar_counter.note_sibling_build(True, times=jnp.sum(state["passes"]))
    passes = 1 + jnp.sum(state["passes"])  # the root's build is one
    node_id, table, order = state["node_id"], state["table"], state["order"]

    # --- the tree: the popped nodes are internal, their children the rest ---
    order_c = jnp.maximum(order, 0)
    child_of = table["child"][order_c]
    split_t = (order >= 0) & (child_of >= 0)  # the t-th pop, where it split
    # slot of each evaluated node in the tree (-1: not in it): the t-th
    # split's children are slots 1 + 2t and 2 + 2t
    t_ids = jnp.arange(order.shape[0], dtype=jnp.int32)
    opened = jnp.where(split_t, child_of, k_tab)
    final = (
        jnp.full((k_tab,), -1, jnp.int32).at[0].set(0)
        .at[opened].set(1 + 2 * t_ids, mode="drop")
        .at[opened + 1].set(2 + 2 * t_ids, mode="drop")
    )
    internal = jnp.zeros((k_tab,), bool).at[
        jnp.where(split_t, order_c, k_tab)].set(True, mode="drop")
    in_tree = final >= 0
    feat = jnp.clip(table["feat"], 0, f_global_max)
    thr = cuts[feat, jnp.clip(table["bin"], 0, cfg.max_bin - 2)]
    weight = lr * leaf_weight(table["g"], table["h"], cfg.split)
    if bounded:
        tree = empty_tree(cfg.heap_size)
        dst = jnp.where(in_tree, table["hslot"], cfg.heap_size)
    else:
        n_slots = 2 * leaves - 1
        dst = jnp.where(in_tree, final, n_slots)
        tree = LinkedTree(
            *empty_tree(n_slots),
            left=jnp.zeros((n_slots,), jnp.int32).at[dst].set(
                jnp.where(internal, final[jnp.maximum(table["child"], 0)], 0),
                mode="drop"))

    def put(name, values):
        return getattr(tree, name).at[dst].set(values, mode="drop")

    is_leaf = in_tree & ~internal
    tree = tree._replace(
        feature=put("feature", jnp.where(internal, feat, -1)),
        split_bin=put("split_bin", jnp.where(internal, table["bin"], 0)),
        threshold=put("threshold", jnp.where(internal, thr, 0.0)),
        default_left=put("default_left", table["dl"] & internal),
        is_leaf=put("is_leaf", is_leaf),
        value=put("value", jnp.where(is_leaf, weight, 0.0)),
        gain=put("gain", jnp.where(internal, table["gain"], 0.0)),
        cover=put("cover", table["h"]),
        base_weight=put("base_weight", weight),
    )

    # a row sits in the deepest node evaluated on its path; its leaf is that
    # node's nearest ancestor-or-self in the tree
    parent_c = jnp.maximum(table["parent"], 0)
    jump = jnp.where(in_tree, slots, parent_c)
    for _ in range(max(1, math.ceil(math.log2(k_tab)))):
        jump = jump[jump]
    row_value = lookup_by_node(node_id, weight[jump])[0]
    if ar_counter is not None:
        ar_counter.note_lossguide(
            passes, state["evaluated"], jnp.sum(split_t, dtype=jnp.int32),
            state["overflows"])
    return tree, row_value

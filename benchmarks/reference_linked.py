"""The plain reference for a forest of leaf-wise trees no depth bounds, and
the comparison with it.

Numpy only; imports nothing of the program (``reference.py``'s arithmetic is
this directory's own) and is given nothing the program made except its
answers: the forest (per slot: split feature, threshold, leaf value, cover,
and ``left``, the slot of a node's left child, its right child the next one;
a forest without ``left`` is a padded heap, ``left = 2 i + 1``) and the
losses it reported.

Teacher-forced like ``reference.py``: for every tree it takes the splits the
program chose, routes every raw row through them, and works out in float64
what the rest of that round has to be (``g``, ``h``, every leaf's value,
every node's cover, the logloss of every round and set). For the judged trees
(``split_trees``) it builds the exact (g, h) histogram of every node the tree
*has* (per leaf, summed up the tree; never a slot per heap position) over its
own quantile thresholds, and reads:

* ``split`` / ``split_deep``: how far a chosen split's exact gain lies under
  the best of the reference's thresholds, by the worst node of the top
  ``TOP_LEVELS`` levels and by the worst level below them (its nodes'
  shortfalls together over their best gains together), as ``reference.py``;
* ``order``: how much gain the tree's growth order left on the table against
  best-first. The slots say in which order a tree was grown (the ``t``-th
  split's children are slots ``1 + 2 t`` and ``2 + 2 t``: xgboost's numbering
  and the program's; any layout by the rank of ``left``). Best-first splits
  the frontier's largest gain first, so from the split of a leaf's parent to
  the end of growth no split may gain less than the leaf's best admissible
  one would (by the reference's thresholds, children under
  ``min_child_weight`` barred): that split should have gone to the leaf.
  What a leaf's gain lies over the smallest gain taken in that stretch, all
  leaves together, as a share of the gains the tree took together; in a tree
  of fewer than ``max_leaves`` leaves the leaves with the most to gain count
  in full, as many as the budget had room for. By gain, not by node: a leaf
  with next to nothing to gain reads any share alone (PERF.md section 2). Two
  nodes that both split may have split in either order, the tree is the
  same: under bfloat16 sums neighbours in the order swap all the time. And a
  leaf whose parent split last is held to nothing: the budget ends where it
  ends, and the generator's interaction term leaves such leaves with the
  largest gains of the tree.
"""

from concurrent.futures import ThreadPoolExecutor

import numpy as np

import reference
from reference import (BLOCK_ROWS, N_CUTS, TOP_LEVELS, _Rounder, _gain,
                       _threads, quantile_cuts, split_trees_of)  # noqa: F401

NAMES = ("feature", "threshold", "default_left", "is_leaf", "value", "cover")
WIDTH = N_CUTS + 1  # bins a feature


def forest_arrays(forest):
    """The program's answer as plain arrays, by the names this file uses;
    ``left`` made for a padded heap, which holds none."""
    out = {n: np.asarray(getattr(forest, n)) for n in NAMES}
    left = getattr(forest, "left", None)
    out["left"] = (np.asarray(left) if left is not None else
                   np.broadcast_to(2 * np.arange(out["feature"].shape[1]) + 1,
                                   out["feature"].shape).copy())
    return out


def internal_nodes(tree):
    """Slots of the nodes that split (children inside the arrays)."""
    n = tree["feature"].shape[0]
    return np.flatnonzero(~tree["is_leaf"].astype(bool)
                          & (tree["feature"] >= 0) & (tree["left"] + 1 < n))


def node_depths(tree):
    """Depth of every node the tree has (-1: a slot it does not use). A
    node's slot lies after its parent's in both layouts."""
    depth = np.full(tree["feature"].shape[0], -1, np.int64)
    depth[0] = 0
    for i in internal_nodes(tree):
        if depth[i] >= 0:
            depth[tree["left"][i]] = depth[tree["left"][i] + 1] = depth[i] + 1
    return depth


class _Block(reference._Block):
    """One block of rows; the walk follows ``left``."""

    def walk(self, tree):
        feature, threshold = tree["feature"], tree["threshold"]
        default_left, left = tree["default_left"], tree["left"]
        stops = tree["is_leaf"].astype(bool) | (feature < 0)
        pos = np.zeros(self.x.shape[0], np.int64)
        moving = self.rows[:0] if stops[0] else self.rows
        while moving.size:
            at = pos[moving]
            xv = self.x[moving, np.maximum(feature[at], 0)]
            right = np.where(np.isnan(xv), ~default_left[at],
                             xv >= threshold[at])
            pos[moving] = left[at] + right
            moving = moving[~stops[pos[moving]]]
        self.leaf = pos


def _tree_step(block, tree, with_hist, rnd):
    """Route one block through one tree; its sums of g and h per leaf and,
    where asked, per (feature, leaf, bin)."""
    block.walk(tree)
    if not block.is_train:
        return None
    n = tree["feature"].shape[0]
    keep = block.keep
    g, h = (a[:keep] for a in block.grad_hess(rnd))
    leaf = block.leaf[:keep]
    out = {"g": np.bincount(leaf, g, n), "h": np.bincount(leaf, h, n)}
    if with_hist:
        hist = np.empty((len(block.bins), 2, n * WIDTH))
        base = leaf * WIDTH
        for f, b in enumerate(block.bins):
            hist[f, 0] = np.bincount(base + b[:keep], g, n * WIDTH)
            hist[f, 1] = np.bincount(base + b[:keep], h, n * WIDTH)
        out["hist"] = hist
    return out


def _sum_up(at_leaves, tree):
    """Every node's sum from the sums at the leaves (axis 0 the slots)."""
    total = at_leaves.copy()
    for i in internal_nodes(tree)[::-1]:
        first = tree["left"][i]
        total[i] = total[first] + total[first + 1]
    return total


def _best_gains(hist, lam, mcw):
    """Best admissible gain of one node over the reference's thresholds
    (``hist`` [F, 2, bins]), less the lowest and the highest: a 256-bin
    sketch of other make may hold neither (``reference._split_gaps``)."""
    left = np.cumsum(hist, axis=2)[:, :, :-1]
    tot = hist.sum(axis=2, keepdims=True)
    gl, hl = left[:, 0], left[:, 1]
    gr, hr = tot[:, 0] - gl, tot[:, 1] - hl
    cand = np.where((hl >= mcw) & (hr >= mcw), _gain(gl, hl, gr, hr, lam),
                    -np.inf)
    return float(cand[:, 1:-1].max())


def order_regret(tree, gain_of, max_leaves):
    """``(regret, taken)``: the gain ``tree``'s growth order left against
    best-first (the module's text), and the gain its splits took together.
    ``gain_of`` [slots]: a splitting node's exact gain, a leaf's best
    admissible one (0 where it has none)."""
    inner = internal_nodes(tree)
    leaves = np.flatnonzero(tree["is_leaf"].astype(bool))
    inner = inner[np.argsort(tree["left"][inner], kind="stable")]  # by time
    born = np.full(tree["feature"].shape[0], -1, np.int64)  # parent's time
    for t, i in enumerate(inner):
        born[tree["left"][i]] = born[tree["left"][i] + 1] = t
    taken = gain_of[inner]
    # the smallest gain taken after each time (inf after the last)
    later = np.append(np.minimum.accumulate(taken[::-1])[::-1], np.inf)
    over = np.where(born[leaves] >= 0,
                    np.maximum(0.0, gain_of[leaves] - later[born[leaves] + 1]),
                    0.0)
    room = max_leaves - len(leaves) if max_leaves > 0 else 0
    if room > 0:  # a budget not spent: the best leaves count in full
        best = np.argsort(-gain_of[leaves])[:room]
        over[best] = np.maximum(over[best], gain_of[leaves][best])
    return float(over.sum()), float(taken.sum())


def judge_tree(hist, tree, node_g, node_h, lam, mcw, max_leaves):
    """``(split_gap, order)`` of one tree from its leaves' histograms
    (``hist`` [F, 2, slots * bins]): ``{node: (best gain, shortfall)}`` for
    the nodes that split, and ``order_regret``'s pair."""
    n = tree["feature"].shape[0]
    hist = _sum_up(
        np.moveaxis(hist.reshape(hist.shape[0], 2, n, WIDTH), 2, 0), tree)
    gaps = {}
    gain_of = np.zeros(n)
    for i in internal_nodes(tree):
        first = tree["left"][i]
        gain_of[i] = _gain(node_g[first], node_h[first], node_g[first + 1],
                           node_h[first + 1], lam)
        best = _best_gains(hist[i], lam, mcw)
        if best > 0:
            gaps[int(i)] = (best, max(0.0, best - gain_of[i]))
    leaves = np.flatnonzero(tree["is_leaf"].astype(bool))
    for i in leaves:
        gain_of[i] = max(0.0, _best_gains(hist[i], lam, mcw))
    return gaps, order_regret(tree, gain_of, max_leaves)


def follow(sets, forest, params, *, real=np.float64, gh_real=None,
           own_values=False, split_trees=(), row_share=1.0):
    """Follow ``forest`` over ``sets`` as ``reference.follow`` does, over
    trees held by ``left``. Returns its keys (``loss``, ``value``, ``cover``,
    ``is_leaf``, ``split_gap``) and ``order``: ``{tree: (left over,
    taken)}``, ``depth``: ``{tree: [slots]}`` of the judged trees."""
    eta = float(params.get("eta", params.get("learning_rate", 0.3)))
    lam = float(params.get("lambda", params.get("reg_lambda", 1.0)))
    mcw = float(params.get("min_child_weight", 1.0))
    max_leaves = int(params.get("max_leaves") or 0)
    n_trees, n_slots = forest["feature"].shape
    rnd = _Rounder(real, gh_real)
    split_trees = {t for t in split_trees if t < n_trees}

    blocks = []
    for name, (x, y) in sets.items():
        for lo in range(0, x.shape[0], BLOCK_ROWS):
            blocks.append((name, _Block(x[lo:lo + BLOCK_ROWS],
                                        y[lo:lo + BLOCK_ROWS],
                                        name == "train", rnd, row_share)))
    train_blocks = [b for _, b in blocks if b.is_train]
    counts = {name: sum(b.x.shape[0] for n, b in blocks if n == name)
              for name in sets}
    out = {"loss": {name: [] for name in sets},
           "value": np.zeros((n_trees, n_slots)),
           "cover": np.zeros((n_trees, n_slots)),
           "is_leaf": forest["is_leaf"].astype(bool),
           "split_gap": {}, "order": {}, "depth": {}}

    with ThreadPoolExecutor(_threads()) as pool:
        if split_trees:
            cuts = quantile_cuts(sets["train"][0])
            list(pool.map(lambda b: b.bin_rows(cuts), train_blocks))
        for t in range(n_trees):
            tree = {k: v[t] for k, v in forest.items()}
            judged = t in split_trees
            # (a judged tree's histograms are slots x bins wide, 58 MB a
            # block at 509 slots: the 22 blocks of 11M rows hold 1.3 GB)
            parts = list(pool.map(
                lambda nb: _tree_step(nb[1], tree, judged, rnd), blocks))
            parts = [p for p in parts if p is not None]
            is_leaf = tree["is_leaf"].astype(bool)
            node_g = _sum_up(np.where(is_leaf, sum(p["g"] for p in parts), 0.0),
                             tree)
            node_h = _sum_up(np.where(is_leaf, sum(p["h"] for p in parts), 0.0),
                             tree)
            value = rnd(np.where(is_leaf, -eta * node_g / (node_h + lam),
                                 0.0))
            out["value"][t] = value
            out["cover"][t] = node_h
            if judged:
                hist = parts[0]["hist"]
                for p in parts[1:]:
                    hist += p["hist"]
                gaps, order = judge_tree(hist, tree, node_g, node_h, lam,
                                         mcw, max_leaves)
                out["split_gap"].update(
                    {(t, node): gap for node, gap in gaps.items()})
                out["order"][t] = order
                out["depth"][t] = node_depths(tree)
            step = value if own_values else tree["value"]

            def advance(nb, step=step):
                b = nb[1]
                b.margin = rnd(b.margin + step[b.leaf])
                return nb[0], b.loss_sum()

            sums = {name: 0.0 for name in sets}
            for name, s in pool.map(advance, blocks):
                sums[name] += s
            for name in sets:
                out["loss"][name].append(sums[name] / counts[name])
    return out


def split_numbers(ref):
    """``{tree: (top, deep)}`` as ``reference.split_numbers``, a node's level
    read from ``ref["depth"]``."""
    top, lost, best = {}, {}, {}
    for (t, node), (b, short) in ref["split_gap"].items():
        level = int(ref["depth"][t][node])
        if level < TOP_LEVELS:
            top[t] = max(top.get(t, 0.0), short / b)
        else:
            lost[t, level] = lost.get((t, level), 0.0) + short
            best[t, level] = best.get((t, level), 0.0) + b
    out = {t: [top.get(t, 0.0), 0.0] for t, _ in ref["split_gap"]}
    for (t, level), b in best.items():
        out[t][1] = max(out[t][1], lost[t, level] / b)
    return {t: tuple(v) for t, v in sorted(out.items())}


def order_numbers(ref):
    """``{tree: the gain its growth order left against best-first, as a
    share of the gain the tree took}`` of the judged trees."""
    return {t: (left / taken if taken > 0 else (np.inf if left > 0 else 0.0))
            for t, (left, taken) in sorted(ref["order"].items())}


def compare(reported_loss, forest, ref, limits):
    """The numbers compared, each beside its limit: ``loss``, ``leaf``,
    ``cover`` as ``reference.compare`` reads them; ``split``, ``split_deep``
    and ``order`` over the judged trees (the module's text)."""
    _, compared = reference.compare(
        reported_loss, forest, dict(ref, split_gap={}),
        {k: limits[k] for k in ("loss", "leaf", "cover") if k in limits})
    numbers = {}
    per_tree = split_numbers(ref)
    if per_tree:
        numbers["split"] = max(top for top, _ in per_tree.values())
        if any(ref["depth"][t][node] >= TOP_LEVELS
               for t, node in ref["split_gap"]):
            numbers["split_deep"] = max(d for _, d in per_tree.values())
    if ref["order"]:
        numbers["order"] = max(order_numbers(ref).values())
    compared.update({k: {"value": v, "limit": limits[k]}
                     for k, v in numbers.items() if k in limits})
    correct = bool(compared) and all(
        c["value"] <= c["limit"] for c in compared.values())
    return correct, compared

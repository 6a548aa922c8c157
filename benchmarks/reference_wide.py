"""``reference.py`` for a table thousands of features wide.

The same plain reference (numpy, float64, nothing of the program's; the same
teacher-forced arithmetic, the same numbers out of ``follow``, and
``reference.py``'s own ``compare``), laid out for the feature axis. At 28
features ``reference.py`` spends its time on the rows, which it cuts into
blocks and hands to threads. At 2,000 features and 400,000 rows there is one
block, and what it pays for is per feature: 2,000 binary searches over the
rows to bin them (70 s), a python loop over 255 nodes of ``[2000, 2, 256]``
arrays for every judged tree (13 s a tree), and twice that again for every
control. Here

* a set is one block, walked whole (``reference._Block``'s own walk,
  gradients and loss);
* the thresholds are cut and the rows binned once per feature matrix,
  feature blocks in threads, the bins through a table of 4,096 equal cells over a feature's cuts: a value's cell
  gives the cuts below the cell and the first cut inside it, the bin
  follows from one compare, every bin is then checked against its two
  neighbouring cuts, and whatever fails the check (a cell that holds two
  different cuts, a NaN) goes through ``searchsorted`` as before: the bins
  are ``searchsorted``'s, exactly. ``bin_features`` returns them as
  ``[features, rows]`` uint8 and ``follow`` takes them back (``binned=``),
  so that a ``--controls`` run bins once and not once per control;
* a judged tree's best gains are worked out a level at a time over all its
  nodes, feature blocks in threads, not node by node.

``tests/test_benchmark_contract.py`` holds ``follow`` here to
``reference.follow`` on a small wide set.
"""

from concurrent.futures import ThreadPoolExecutor

import numpy as np

import reference
from reference import (N_CUTS, compare, forest_arrays,  # noqa: F401
                       quantile_cuts, split_numbers, split_trees_of,
                       widest_node_shares)

BIN_BLOCK = 50  # features a thread cuts and bins at a time
GAIN_BLOCK = 8  # features a thread scans at a time: temporaries of 4 MB
CELLS = 4096


def _bin_column(col, cuts):
    """``searchsorted(cuts, col, side="right")`` as uint8 (``cuts``
    ascending, at most 255 of them)."""
    lo, hi = float(cuts[0]), float(cuts[-1])
    width = (hi - lo) / CELLS
    if not width > 0:
        return np.searchsorted(cuts, col, side="right").astype(np.uint8)
    edges = lo + width * np.arange(CELLS + 1)
    # cuts below a cell's left edge; cuts inside it; the first of those
    below = np.searchsorted(cuts, edges, side="left")
    inside = np.diff(below)
    cut_in = np.where(inside > 0, cuts[np.minimum(below[:-1], len(cuts) - 1)],
                      np.inf).astype(cuts.dtype)
    with np.errstate(invalid="ignore"):
        cell = np.clip(((col - lo) / width).astype(np.int64), 0, CELLS - 1)
    b = below[cell] + inside[cell] * (col >= cut_in[cell])
    # b cuts lie at or under a value exactly when it sits between the b-th
    # cut and the next: what a cell with two cuts, a value on a cell's
    # rounded edge or a NaN gets wrong fails here and is searched
    padded = np.concatenate([[-np.inf], cuts, [np.inf]]).astype(cuts.dtype)
    redo = ~((padded[b] <= col) & (col < padded[b + 1]))
    if redo.any():
        b[redo] = np.searchsorted(cuts, col[redo], side="right")
    return b.astype(np.uint8)


def bin_features(x):
    """The reference's candidate thresholds ``[F, 255]`` and every row's bin
    under them, ``[F, rows]`` uint8."""
    cuts = np.empty((x.shape[1], N_CUTS), np.float32)
    bins = np.empty((x.shape[1], x.shape[0]), np.uint8)

    def block(f0):
        part = x[:, f0:f0 + BIN_BLOCK]
        cuts[f0:f0 + BIN_BLOCK] = quantile_cuts(part)
        for i, col in enumerate(np.ascontiguousarray(part.T)):
            bins[f0 + i] = _bin_column(col, cuts[f0 + i])

    with ThreadPoolExecutor(reference._threads()) as pool:
        list(pool.map(block, range(0, x.shape[1], BIN_BLOCK)))
    return cuts, bins


def _level_hist(bins, slot, g, h, depth):
    """Sums of g and h per (feature, slot at level ``depth - 1``, bin):
    ``[F, 2, slots, bins]``."""
    n_slots = 1 << (depth - 1)
    width = n_slots * (N_CUTS + 1)
    base = (slot - (n_slots - 1)) * (N_CUTS + 1)
    hist = np.empty((bins.shape[0], 2, width))
    for f, b in enumerate(bins):
        idx = base + b
        hist[f, 0] = np.bincount(idx, g, width)
        hist[f, 1] = np.bincount(idx, h, width)
    return hist.reshape(bins.shape[0], 2, n_slots, N_CUTS + 1)


def _split_gaps(hist, tree, node_g, node_h, depth, lam, mcw, pool):
    """``reference._split_gaps``: for each internal node ``(best,
    shortfall)``, a level's nodes at once."""
    gaps = {}
    for d in range(depth - 1, -1, -1):
        first = (1 << d) - 1

        def best_of(f0, hist=hist):
            # reference._gain over [f, slots, thresholds], less the lowest
            # and the highest threshold as there, in place: nine passes over
            # arrays of a few MB where the plain form makes twenty
            sums = np.cumsum(hist[f0:f0 + GAIN_BLOCK], axis=3)
            tot_g, tot_h = sums[:, 0, :, -1:], sums[:, 1, :, -1:]
            gl = np.ascontiguousarray(sums[:, 0, :, 1:-2])
            hl = np.ascontiguousarray(sums[:, 1, :, 1:-2])
            gr, hr = tot_g - gl, tot_h - hl
            barred = (hl < mcw) | (hr < mcw)
            np.multiply(gl, gl, out=gl)
            np.multiply(gr, gr, out=gr)
            hl += lam
            hr += lam
            np.divide(gl, hl, out=gl)
            np.divide(gr, hr, out=gr)
            gl += gr
            gl -= tot_g * tot_g / (tot_h + lam)
            gl[barred] = -np.inf
            return gl.max(axis=(0, 2))

        best = np.max(list(pool.map(
            best_of, range(0, hist.shape[0], GAIN_BLOCK))), axis=0)
        for j in range(1 << d):
            node = first + j
            if tree["is_leaf"][node] or tree["feature"][node] < 0:
                continue
            lc, rc = 2 * node + 1, 2 * node + 2
            mine = float(reference._gain(node_g[lc], node_h[lc], node_g[rc],
                                         node_h[rc], lam))
            if best[j] > 0:
                gaps[node] = (float(best[j]), max(0.0, float(best[j]) - mine))
        if d:
            hist = hist.reshape(hist.shape[0], 2, 1 << (d - 1), 2,
                                N_CUTS + 1).sum(axis=3)
    return gaps


def follow(sets, forest, params, *, real=np.float64, gh_real=None,
           own_values=False, split_trees=(), row_share=1.0, binned=None):
    """``reference.follow`` (its arguments, its result). ``binned`` is what
    ``bin_features`` gave for ``sets["train"]``'s features, where the caller
    has it already."""
    eta = float(params.get("eta", params.get("learning_rate", 0.3)))
    lam = float(params.get("lambda", params.get("reg_lambda", 1.0)))
    mcw = float(params.get("min_child_weight", 1.0))
    depth = int(params["max_depth"])
    n_trees, heap = forest["feature"].shape
    rnd = reference._Rounder(real, gh_real)
    split_trees = {t for t in split_trees if t < n_trees}

    blocks = {name: reference._Block(x, y, name == "train", rnd, row_share)
              for name, (x, y) in sets.items()}
    train = blocks["train"]
    out = {"loss": {name: [] for name in sets},
           "value": np.zeros((n_trees, heap)),
           "cover": np.zeros((n_trees, heap)),
           "is_leaf": forest["is_leaf"].astype(bool),
           "split_gap": {}}

    if split_trees:
        _, bins = binned or bin_features(sets["train"][0])
    with ThreadPoolExecutor(reference._threads()) as pool:
        for t in range(n_trees):
            tree = {k: v[t] for k, v in forest.items()}
            judged = t in split_trees
            slots = {name: b.walk(tree, depth, judged and b.is_train)
                     for name, b in blocks.items()}
            keep = train.keep
            g, h = (a[:keep] for a in train.grad_hess(rnd))
            leaf = train.leaf[:keep]
            leaf_g = np.bincount(leaf, g, heap)
            leaf_h = np.bincount(leaf, h, heap)
            is_leaf = tree["is_leaf"].astype(bool)
            node_g = reference._node_sums(leaf_g, is_leaf, heap)
            node_h = reference._node_sums(leaf_h, is_leaf, heap)
            value = rnd(np.where(is_leaf, -eta * node_g / (node_h + lam),
                                 0.0))
            out["value"][t] = value
            out["cover"][t] = node_h
            if judged:
                hist = _level_hist(bins[:, :keep], slots["train"][:keep],
                                   g, h, depth)
                gaps = _split_gaps(hist, tree, node_g, node_h, depth, lam,
                                   mcw, pool)
                out["split_gap"].update(
                    {(t, node): gap for node, gap in gaps.items()})
            step = value if own_values else tree["value"]
            for name, b in blocks.items():
                b.margin = rnd(b.margin + step[b.leaf])
                out["loss"][name].append(b.loss_sum() / b.x.shape[0])
    return out

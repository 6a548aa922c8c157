"""The control and the planted faults for ``correct`` of a leaf-wise forest,
read with ``reference_linked``'s comparison.

``controls.py``'s four (``lowprec``, ``half_batch``, ``state_unchanged``,
``answer_altered``: see its text) over the first ``TREES`` trees, and two that
only a leaf-wise forest has, each a first tree grown otherwise from the same
rows and then given the reference's own values, covers and loss, so that the
growth order is all that is wrong with it:

* ``levelwise_forest``: the depth-``CAP_DEPTH`` level-wise tree of the same
  rows (every node split at its best threshold, level by level) handed in
  where the best-first tree of as many leaves was asked for;
* ``depth_capped``: the program's own first tree stopped at depth
  ``CAP_DEPTH``: every deeper split taken back.
"""

from concurrent.futures import ThreadPoolExecutor

import ml_dtypes
import numpy as np

import reference_linked as reference
from reference_linked import WIDTH

TREES = 3
CAP_DEPTH = 8


def _head(forest, n):
    return {k: v[:n].copy() for k, v in forest.items()}


def _in_place_of_program(sets, forest, params, **kwargs):
    """What ``follow`` computes itself, as a program's answer."""
    own = reference.follow(sets, forest, params, own_values=True, **kwargs)
    made = dict(forest, value=own["value"].astype(np.float32),
                cover=own["cover"].astype(np.float32))
    return made, own["loss"]


def capped(tree, depth_cap):
    """``tree`` with every node at ``depth_cap`` a leaf."""
    out = {k: v.copy() for k, v in tree.items()}
    cut = (reference.node_depths(tree) == depth_cap) & ~tree["is_leaf"].astype(bool)
    out["is_leaf"] = tree["is_leaf"].astype(bool) | cut
    out["feature"] = np.where(cut, -1, tree["feature"])
    return out


def levelwise_tree(sets, params, depth, n_slots):
    """The level-wise tree of ``depth`` levels over the training rows at
    margin 0 (the first tree's gradients), every node split at the best of
    the reference's thresholds, as slots in breadth-first order."""
    lam = float(params.get("lambda", params.get("reg_lambda", 1.0)))
    mcw = float(params.get("min_child_weight", 1.0))
    x, y = sets["train"]
    cuts = reference.quantile_cuts(x)
    n_slots = max(n_slots, (1 << (depth + 1)) - 1)
    tree = {"feature": np.full(n_slots, -1, np.int64),
            "threshold": np.zeros(n_slots, np.float32),
            "default_left": np.zeros(n_slots, bool),
            "is_leaf": np.zeros(n_slots, bool),
            "value": np.zeros(n_slots, np.float32),
            "cover": np.zeros(n_slots, np.float32),
            "left": np.zeros(n_slots, np.int64)}
    tree["is_leaf"][0] = True
    rnd = reference._Rounder(np.float64)
    blocks = [reference._Block(x[lo:lo + reference.BLOCK_ROWS],
                               y[lo:lo + reference.BLOCK_ROWS], True, rnd)
              for lo in range(0, x.shape[0], reference.BLOCK_ROWS)]
    handed = 1
    with ThreadPoolExecutor(reference._threads()) as pool:
        list(pool.map(lambda b: b.bin_rows(cuts), blocks))
        for _ in range(depth):
            parts = list(pool.map(
                lambda b: reference._tree_step(b, tree, True, rnd), blocks))
            hist = parts[0]["hist"]
            for p in parts[1:]:
                hist += p["hist"]
            hist = np.moveaxis(hist.reshape(hist.shape[0], 2, n_slots, WIDTH),
                               2, 0)
            for i in np.flatnonzero(tree["is_leaf"]):
                h = hist[i]
                left = np.cumsum(h, axis=2)[:, :, :-1]
                tot = h.sum(axis=2, keepdims=True)
                gl, hl = left[:, 0], left[:, 1]
                gr, hr = tot[:, 0] - gl, tot[:, 1] - hl
                gain = np.where((hl >= mcw) & (hr >= mcw),
                                reference._gain(gl, hl, gr, hr, lam), -np.inf)
                gain[:, 0] = gain[:, -1] = -np.inf  # as the judge reads them
                f, b = np.unravel_index(np.argmax(gain), gain.shape)
                if not gain[f, b] > 0:
                    continue
                tree["is_leaf"][i] = False
                tree["feature"][i], tree["threshold"][i] = f, cuts[f, b]
                tree["left"][i] = handed
                tree["is_leaf"][handed:handed + 2] = True
                handed += 2
    return tree


def _as_first_tree(forest, tree):
    """A forest of one tree, its arrays as wide as ``tree``'s."""
    return {k: np.asarray(tree[k], forest[k].dtype)[None] for k in forest}


def readings(sets, forest, reported, params, limits, cap_depth=CAP_DEPTH):
    """``{control: {number: {"value", "limit"}}}`` over the first trees
    (``cap_depth``: tests, whose trees are shallower than the cell's)."""
    n = min(TREES, forest["feature"].shape[0])
    head = _head(forest, n)
    said = {k: list(v[:n]) for k, v in reported.items()}
    cases = {}

    cases["lowprec"] = _in_place_of_program(
        sets, head, params, real=ml_dtypes.bfloat16,
        gh_real=ml_dtypes.float8_e4m3fn)
    cases["half_batch"] = _in_place_of_program(sets, head, params,
                                               row_share=0.5)
    if n >= 2:
        same = _head(head, n)
        for k in same:
            same[k][1] = same[k][0]
        cases["state_unchanged"] = (
            same, {k: [v[0], v[0]] + v[2:] for k, v in said.items()})
    off = _head(head, n)
    leaves = np.flatnonzero(off["is_leaf"][0])
    big = leaves[np.argmax(off["cover"][0][leaves])]
    # siblings are adjacent, the left one at an odd slot, in both layouts
    off["value"][0, big] = off["value"][0, big + 1 if big % 2 else big - 1]
    cases["answer_altered"] = (off, said)

    first = {k: v[0] for k, v in forest.items()}
    cases["levelwise_forest"] = _in_place_of_program(
        sets, _as_first_tree(forest, levelwise_tree(
            sets, params, cap_depth, first["feature"].shape[0])), params)
    cases["depth_capped"] = _in_place_of_program(
        sets, _as_first_tree(forest, capped(first, cap_depth)), params)

    out = {}
    for name, (made, loss) in cases.items():
        trees = range(made["feature"].shape[0])
        ref = reference.follow(sets, made, params, split_trees=trees)
        out[name] = reference.compare(loss, made, ref, limits)[1]
    return out


def every_tree_splits(sets, forest, params):
    """The split and order numbers of every tree, not of the run's draw:
    what their limits have to clear whichever tree a seed draws; beside them
    each tree's deepest leaf."""
    every = reference.follow(sets, forest, params,
                             split_trees=range(forest["feature"].shape[0]))
    return {"numbers": reference.split_numbers(every),
            "order": reference.order_numbers(every),
            "deepest_leaf": {t: int(d.max())
                             for t, d in every["depth"].items()}}

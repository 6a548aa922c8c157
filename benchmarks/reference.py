"""The plain reference for a boosted forest, and the comparison with it.

Numpy only; imports nothing of the program and is given nothing the program
made except its answers: the forest the driver read back (split feature,
threshold, leaf value, cover per heap node) and the losses it reported. The
inputs are the rows the benchmark generated.

The reference is teacher-forced, like a served model's: it cannot rebuild the
forest bit for bit (the program's quantile sketch picks the candidate
thresholds), so for every tree it takes the splits the program chose, routes
every raw row through them (``x < threshold`` goes left), and works out in
float64 from the published xgboost equations what the rest of that round has
to be:

* ``g = p - y``, ``h = max(p (1 - p), 1e-16)`` at the margin of the trees so
  far, ``p = sigmoid(margin)``; a leaf's value is ``-eta G / (H + lambda)``
  over its rows, a node's cover is its ``H``;
* the logloss after each round, on every evaluation set;
* for every internal node of the trees named in ``split_trees`` (the first
  trees and one of the window's, drawn from the seed), the gain of the split
  the program chose, from the exact child sums, against the best gain over
  the reference's own 255 quantile thresholds per feature, less the lowest
  and the highest (``GL^2/(HL+lambda) + GR^2/(HR+lambda) - G^2/(H+lambda)``,
  children under ``min_child_weight`` barred). The top ``TOP_LEVELS`` levels are one number
  (``split``), every level under them another (``split_deep``).

``follow(..., own_values=True, real=<dtype>)`` is the same arithmetic put in
the program's place: it keeps its own margins and returns its own leaf
values, covers and losses, in ``real`` precision (g and h in ``gh_real``).
The control runs it one step under what the configuration states.
"""

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCK_ROWS = 500_000
HESS_FLOOR = 1e-16
N_CUTS = 255
CUT_SAMPLE_ROWS = 200_000
TOP_LEVELS = 3
FIRST_SPLIT_TREES = 3


def _threads():
    return max(1, min(12, (os.cpu_count() or 2) - 1))


def forest_arrays(forest):
    """The program's answer as plain arrays, by the names this file uses."""
    names = ("feature", "threshold", "default_left", "is_leaf", "value",
             "cover")
    return {n: np.asarray(getattr(forest, n)) for n in names}


def quantile_cuts(x):
    """The reference's own candidate thresholds: 255 quantiles per feature
    over the first ``CUT_SAMPLE_ROWS`` rows (rows are iid draws)."""
    sample = x[:CUT_SAMPLE_ROWS]
    qs = np.arange(1, N_CUTS + 1) / (N_CUTS + 1)
    return np.ascontiguousarray(
        np.quantile(sample, qs, axis=0).T.astype(np.float32))


class _Rounder:
    """Arithmetic in ``real``: float64 as is; a narrower type computes in
    float32 and rounds every result to it. ``gh`` rounds g and h, which a
    configuration may hold in a narrower type than the rest."""

    def __init__(self, real, gh_real=None):
        self.real = np.dtype(real)
        self.gh_real = np.dtype(gh_real or real)
        self.exact = self.real == np.float64

    def __call__(self, a):
        if self.exact:
            return a
        return a.astype(self.real).astype(np.float32)

    def gh(self, a):
        if self.gh_real == np.float64:
            return a
        return a.astype(self.gh_real).astype(np.float32)

    def zeros(self, n):
        return np.zeros(n, np.float64 if self.exact else np.float32)


class _Block:
    """One block of rows of one set, with its running margin."""

    def __init__(self, x, y, is_train, rnd, stat_share=1.0):
        self.x, self.y, self.is_train = x, y, is_train
        # tree statistics come from these leading rows (None: all of them);
        # margins and losses always advance over every row
        self.keep = (None if stat_share >= 1.0
                     else int(x.shape[0] * stat_share))
        self.rows = np.arange(x.shape[0])
        self.margin = rnd.zeros(x.shape[0])
        self.bins = None
        self.leaf = None

    def bin_rows(self, cuts):
        self.bins = [
            np.searchsorted(cuts[f], self.x[:, f], side="right").astype(
                np.int64)
            for f in range(self.x.shape[1])
        ]

    def walk(self, tree, depth, with_slots):
        """Leaf id of every row and, where asked, its slot at level
        ``depth - 1`` (a row that stopped in a shallower leaf is carried
        down its left edge, so a node's rows are the union of its slots)."""
        feature, threshold = tree["feature"], tree["threshold"]
        default_left, is_leaf = tree["default_left"], tree["is_leaf"]
        pos = np.zeros(self.x.shape[0], np.int64)
        slot = None
        for d in range(depth):
            stop = is_leaf[pos] | (feature[pos] < 0)
            xv = self.x[self.rows, np.maximum(feature[pos], 0)]
            right = np.where(np.isnan(xv), ~default_left[pos],
                             xv >= threshold[pos])
            if with_slots and d < depth - 1:
                base = pos if slot is None else slot
                slot = 2 * base + 1 + (right & ~stop)
            pos = np.where(stop, pos, 2 * pos + 1 + right)
        self.leaf = pos
        if with_slots and depth == 1:
            slot = np.zeros_like(pos)
        return slot

    def grad_hess(self, rnd):
        p = rnd(1.0 / (1.0 + np.exp(-self.margin)))
        g = rnd.gh(p - self.y)
        h = rnd.gh(np.maximum(p * (1.0 - p), HESS_FLOOR))
        return g, h

    def loss_sum(self):
        m = self.margin.astype(np.float64)
        return float(np.sum(np.where(self.y > 0.5, np.logaddexp(0.0, -m),
                                     np.logaddexp(0.0, m))))


def _tree_step(block, tree, depth, heap, with_hist, rnd):
    """Route one block through one tree; its sums of g and h per leaf and,
    where asked, per (feature, slot at level ``depth - 1``, bin)."""
    slot = block.walk(tree, depth, with_hist and block.is_train)
    if not block.is_train:
        return None
    keep = block.keep
    g, h = (a[:keep] for a in block.grad_hess(rnd))
    leaf = block.leaf[:keep]
    out = {"g": np.bincount(leaf, g, heap), "h": np.bincount(leaf, h, heap)}
    if with_hist:
        n_slots = 1 << (depth - 1)
        first = n_slots - 1
        width = n_slots * (N_CUTS + 1)
        hist = np.empty((len(block.bins), 2, width))
        base = (slot[:keep] - first) * (N_CUTS + 1)
        for f, b in enumerate(block.bins):
            hist[f, 0] = np.bincount(base + b[:keep], g, width)
            hist[f, 1] = np.bincount(base + b[:keep], h, width)
        out["hist"] = hist
    return out


def _node_sums(leaf_sums, is_leaf, heap):
    """Sums of every heap node from the sums at the leaves."""
    total = np.where(is_leaf, leaf_sums, 0.0)
    for i in range((heap - 3) // 2, -1, -1):
        if not is_leaf[i]:
            total[i] = total[2 * i + 1] + total[2 * i + 2]
    return total


def _gain(gl, hl, gr, hr, lam):
    g, h = gl + gr, hl + hr
    return gl * gl / (hl + lam) + gr * gr / (hr + lam) - g * g / (h + lam)


def _split_gaps(hist, tree, node_g, node_h, depth, lam, mcw):
    """For each internal node ``(best, shortfall)``: the reference's best
    candidate's gain, and how far the exact gain of the program's split lies
    under it.
    ``hist`` is [F, 2, slots at level ``depth - 1``, bins]; a level's
    histograms are its children's, added in pairs."""
    hist = hist.reshape(hist.shape[0], 2, 1 << (depth - 1), N_CUTS + 1)
    gaps = {}
    for d in range(depth - 1, -1, -1):
        for j in range(1 << d):
            node = (1 << d) - 1 + j
            if tree["is_leaf"][node] or tree["feature"][node] < 0:
                continue
            h_node = hist[:, :, j]
            left = np.cumsum(h_node, axis=2)[:, :, :-1]  # [F, 2, N_CUTS]
            tot = h_node.sum(axis=2, keepdims=True)
            gl, hl = left[:, 0], left[:, 1]
            gr, hr = tot[:, 0] - gl, tot[:, 1] - hl
            cand = np.where((hl >= mcw) & (hr >= mcw),
                            _gain(gl, hl, gr, hr, lam), -np.inf)
            # a 256-bin sketch of other make may hold neither the lowest nor
            # the highest of these thresholds (it merges an end pair of
            # values this one separates): judge by those every sketch holds
            best = float(cand[:, 1:-1].max())
            lc, rc = 2 * node + 1, 2 * node + 2
            mine = float(_gain(node_g[lc], node_h[lc], node_g[rc],
                               node_h[rc], lam))
            if best > 0:
                gaps[node] = (best, max(0.0, best - mine))
        if d:
            hist = hist.reshape(hist.shape[0], 2, 1 << (d - 1), 2,
                                N_CUTS + 1).sum(axis=3)
    return gaps


def split_trees_of(seed, warmup_rounds, n_trees):
    """The trees whose splits are judged: the first ones and one of the
    window's, drawn from the seed."""
    first = list(range(min(FIRST_SPLIT_TREES, n_trees)))
    if n_trees <= max(warmup_rounds, FIRST_SPLIT_TREES):
        return first
    lo = max(warmup_rounds, FIRST_SPLIT_TREES)
    drawn = lo + int(np.random.default_rng(seed).integers(n_trees - lo))
    return first + [drawn]


def follow(sets, forest, params, *, real=np.float64, gh_real=None,
           own_values=False, split_trees=(), row_share=1.0):
    """Follow ``forest`` over ``sets`` (``{"train": (x, y), ...}``; the tree
    statistics come from ``"train"``).

    Returns ``{"loss": {set: [T]}, "value": [T, heap], "cover": [T, heap],
    "is_leaf": [T, heap], "split_gap": {(tree, node): (best gain,
    shortfall)}}``; the splits of
    the trees in ``split_trees`` are judged. With ``own_values`` the margins
    advance by the values computed here, not by the forest's. ``row_share``
    < 1 takes the tree statistics from that leading share of each block only
    (the planted fault "half the batch")."""
    eta = float(params.get("eta", params.get("learning_rate", 0.3)))
    lam = float(params.get("lambda", params.get("reg_lambda", 1.0)))
    mcw = float(params.get("min_child_weight", 1.0))
    depth = int(params["max_depth"])
    n_trees, heap = forest["feature"].shape
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
           "value": np.zeros((n_trees, heap)),
           "cover": np.zeros((n_trees, heap)),
           "is_leaf": forest["is_leaf"].astype(bool),
           "split_gap": {}}

    with ThreadPoolExecutor(_threads()) as pool:
        if split_trees:
            cuts = quantile_cuts(sets["train"][0])
            list(pool.map(lambda b: b.bin_rows(cuts), train_blocks))
        for t in range(n_trees):
            tree = {k: v[t] for k, v in forest.items()}
            judged = t in split_trees
            parts = list(pool.map(
                lambda nb: _tree_step(nb[1], tree, depth, heap, judged, rnd),
                blocks))
            parts = [p for p in parts if p is not None]
            leaf_g = sum(p["g"] for p in parts)
            leaf_h = sum(p["h"] for p in parts)
            is_leaf = tree["is_leaf"].astype(bool)
            node_g = _node_sums(leaf_g, is_leaf, heap)
            node_h = _node_sums(leaf_h, is_leaf, heap)
            value = rnd(np.where(is_leaf, -eta * node_g / (node_h + lam),
                                 0.0))
            out["value"][t] = value
            out["cover"][t] = node_h
            if judged:
                gaps = _split_gaps(sum(p["hist"] for p in parts), tree,
                                   node_g, node_h, depth, lam, mcw)
                out["split_gap"].update(
                    {(t, node): gap for node, gap in gaps.items()})
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


def _level(node):
    return int(node + 1).bit_length() - 1


def split_numbers(ref):
    """``{tree: (top, deep)}``. ``top``: the widest shortfall of one node of
    the top ``TOP_LEVELS`` levels, as a share of that node's best gain.
    ``deep``: the widest shortfall of one level below them, all its nodes
    together, as a share of their best gains together. Down there a node
    with next to no gain to find reads any share at all under the
    configuration's own precision, and would set a widest single share."""
    top, lost, best = {}, {}, {}
    for (t, node), (b, short) in ref["split_gap"].items():
        level = _level(node)
        if level < TOP_LEVELS:
            top[t] = max(top.get(t, 0.0), short / b)
        else:
            lost[t, level] = lost.get((t, level), 0.0) + short
            best[t, level] = best.get((t, level), 0.0) + b
    out = {t: [top.get(t, 0.0), 0.0] for t, _ in ref["split_gap"]}
    for (t, level), b in best.items():
        out[t][1] = max(out[t][1], lost[t, level] / b)
    return {t: tuple(v) for t, v in sorted(out.items())}


def widest_node_shares(ref):
    """``{tree: widest single node's share below the top levels}``: what
    ``split_deep`` is not, kept for the summary line of a ``--controls``
    run."""
    out = {}
    for (t, node), (b, short) in ref["split_gap"].items():
        if _level(node) >= TOP_LEVELS:
            out[t] = max(out.get(t, 0.0), short / b)
    return out


def compare(reported_loss, forest, ref, limits):
    """The numbers compared, each beside its limit.

    ``loss``: the widest gap between a reported logloss and the reference's,
    over every round and set, as a share of the reference's.
    ``leaf``: the worst leaf's gap between the forest's value and the
    reference's, against the reference's value of that leaf or of the tree's
    median leaf, whichever is larger.
    ``cover``: the same for every node's cover against the reference's ``H``.
    ``split``, ``split_deep``: see ``split_numbers``, over the judged trees.
    """
    numbers = {}
    loss_gap = 0.0
    for name, ref_curve in ref["loss"].items():
        got = np.asarray(reported_loss[name], np.float64)
        want = np.asarray(ref_curve, np.float64)
        if got.shape != want.shape or not np.all(np.isfinite(got)):
            loss_gap = float("inf")
            continue
        loss_gap = max(loss_gap, float(np.max(np.abs(got - want) / want)))
    numbers["loss"] = loss_gap

    leaf_gap = cover_gap = 0.0
    for t in range(ref["value"].shape[0]):
        leaves = ref["is_leaf"][t]
        want = ref["value"][t][leaves]
        scale = np.maximum(np.abs(want), np.median(np.abs(want)))
        got = forest["value"][t][leaves].astype(np.float64)
        leaf_gap = max(leaf_gap, float(np.max(np.abs(got - want) / scale)))
        used = leaves | (forest["feature"][t] >= 0)
        want_c = ref["cover"][t][used]
        scale_c = np.maximum(want_c, np.median(ref["cover"][t][leaves]))
        got_c = forest["cover"][t][used].astype(np.float64)
        cover_gap = max(cover_gap,
                        float(np.max(np.abs(got_c - want_c) / scale_c)))
    numbers["leaf"] = leaf_gap
    numbers["cover"] = cover_gap
    per_tree = split_numbers(ref)
    if per_tree:
        numbers["split"] = max(top for top, _ in per_tree.values())
        if any(_level(node) >= TOP_LEVELS for _, node in ref["split_gap"]):
            numbers["split_deep"] = max(d for _, d in per_tree.values())
    compared = {k: {"value": v, "limit": limits[k]}
                for k, v in numbers.items() if k in limits}
    correct = bool(compared) and all(
        c["value"] <= c["limit"] for c in compared.values())
    return correct, compared

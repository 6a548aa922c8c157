"""A boosting round's necessary work, from shapes only.

What any histogram GBDT has to do for one round of a depth-``D`` tree over
``N`` rows, ``F`` features and ``K`` trees per round, whatever implements it:
at each level, read every row's ``F`` one-byte bins and its 8 bytes of
(g, h) once, and make two accumulations (g and h) per (row, feature). Split
search, partition and the margin update are lower order in ``N * F`` and
are left out, so the roofline time is a floor and a share of it cannot pass
100%.

A tree bounded by its leaves (``max_depth`` 0 or absent, ``max_leaves`` = L)
has ``ceil(log2(L))`` levels here: no tree of L leaves has fewer, and every
row is read once at each level of its path, so the balanced tree is the
least work whatever implements the growth (255 leaves: 8). The figure stays a
floor; a growth that reads all rows for every leaf does many times that.
"""

import math

BIN_BYTES = 1
GH_BYTES = 8
ACCUMULATIONS_PER_CELL = 2
DEFAULT_MAX_DEPTH = 6  # xgboost's, where a configuration sets no bound


def level_work(rows, features, trees=1):
    """(bytes, ops) of one level's histogram build."""
    return (trees * rows * (features * BIN_BYTES + GH_BYTES),
            trees * rows * features * ACCUMULATIONS_PER_CELL)


def round_work(rows, features, depth, trees=1):
    """(bytes, ops) of one boosting round: ``depth`` levels."""
    b, o = level_work(rows, features, trees)
    return depth * b, depth * o


def roofline_seconds(nbytes, ops, peak):
    """The least time the chip could take, and which bound binds.

    ``peak`` is one entry of ``peaks.json``; accumulations are counted
    against the bf16 rate (one multiply-add of a one-hot product is two
    FLOPs, so an accumulation is 2 FLOPs)."""
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    t_ops = 2 * ops / peak["bf16_flops_per_s"]
    return max(t_bytes, t_ops), ("bytes" if t_bytes >= t_ops else "ops")


def device_round_work(shape, devices=1):
    """(bytes, ops) of one boosting round on one of ``devices`` devices that
    share the rows of ``shape`` (``cell_shapes``) evenly: what a share of one
    chip's peak, or of one device's seconds, has to be set against."""
    b, o = round_work(shape["rows"], shape["features"], shape["depth"],
                      shape["trees"])
    return b / devices, o / devices


def tree_levels(params):
    """The levels a round's tree has to make: ``max_depth`` where it is
    positive; else the least a tree of ``max_leaves`` leaves can have; else
    xgboost's default depth."""
    depth = int(params.get("max_depth") or 0)
    if depth > 0:
        return depth
    leaves = int(params.get("max_leaves") or 0)
    if leaves > 0:
        return max(1, math.ceil(math.log2(leaves)))
    return DEFAULT_MAX_DEPTH


def cell_shapes(config):
    """rows, features, depth and trees per round of a configuration file."""
    p = config["params"]
    return {"rows": int(config["rows"]), "features": int(config["features"]),
            "depth": tree_levels(p),
            "trees": int(p.get("num_class", 1)) or 1}

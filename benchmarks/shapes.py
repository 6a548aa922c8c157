"""A boosting round's necessary work, from shapes only.

What any histogram GBDT has to do for one round of a depth-``D`` tree over
``N`` rows, ``F`` features and ``K`` trees per round, whatever implements it:
at each level, read every row's ``F`` one-byte bins and its 8 bytes of
(g, h) once, and make two accumulations (g and h) per (row, feature). Split
search, partition and the margin update are lower order in ``N * F`` and
are left out, so the roofline time is a floor and a share of it cannot pass
100%.
"""

BIN_BYTES = 1
GH_BYTES = 8
ACCUMULATIONS_PER_CELL = 2


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


def cell_shapes(config):
    """rows, features, depth and trees per round of a configuration file."""
    p = config["params"]
    return {"rows": int(config["rows"]), "features": int(config["features"]),
            "depth": int(p["max_depth"]),
            "trees": int(p.get("num_class", 1)) or 1}

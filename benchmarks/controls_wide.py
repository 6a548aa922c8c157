"""``controls.py`` over ``reference_wide.py``: the same control
(``lowprec``) and the same planted faults (``half_batch``,
``state_unchanged``, ``answer_altered``), made and read the same way, with
the rows binned once for all of them (``reference_wide.bin_features``; at
2,000 features the binning is most of a ``follow``)."""

import ml_dtypes
import numpy as np

import reference_wide
from controls import TREES, _head


def _in_place_of_program(sets, forest, params, **kwargs):
    """What ``follow`` computes itself, as a program's answer."""
    own = reference_wide.follow(sets, forest, params, own_values=True,
                                **kwargs)
    made = dict(forest, value=own["value"].astype(np.float32),
                cover=own["cover"].astype(np.float32))
    return made, own["loss"]


def readings(sets, forest, reported, params, limits):
    """``{control: {number: {"value", "limit"}}}`` over the first trees."""
    n = min(TREES, forest["feature"].shape[0])
    head = _head(forest, n)
    said = {k: list(v[:n]) for k, v in reported.items()}
    binned = reference_wide.bin_features(sets["train"][0])
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
    off["value"][0, big] = off["value"][0, big + 1 if big % 2 else big - 1]
    cases["answer_altered"] = (off, said)

    out = {}
    for name, (made, loss) in cases.items():
        ref = reference_wide.follow(sets, made, params, split_trees=range(n),
                                    binned=binned)
        out[name] = reference_wide.compare(loss, made, ref, limits)[1]
    return out


def every_tree_splits(sets, forest, params):
    """``controls.every_tree_splits``."""
    every = reference_wide.follow(
        sets, forest, params, split_trees=range(forest["feature"].shape[0]),
        binned=reference_wide.bin_features(sets["train"][0]))
    return {"numbers": reference_wide.split_numbers(every),
            "widest_node_share": reference_wide.widest_node_shares(every)}

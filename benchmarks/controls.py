"""The control and the planted faults for ``correct``, read with the same
comparison as a run's.

Each stands in the program's place over the first ``TREES`` trees of a
forest the program made (its splits), produces what the program would have
produced with that fault, and is compared with the plain reference exactly
as a run is. None runs in a benchmark run: ``run.py --controls 1`` reads
them on the chip at the cell's size when a benchmark PR sets limits, and
``tests/`` keeps them at a size a test can hold.

* ``lowprec``: the reference's own arithmetic one step under what the
  configuration states: margins, p and leaf values in bfloat16 where it
  states float32, g and h in float8 (e4m3) where it holds them in bfloat16
  for the histogram build.
* ``half_batch``: the tree statistics from half of the rows.
* ``state_unchanged``: round 2 repeats round 1 (the step handed back the
  margins it was given): the same tree again, the same loss reported.
* ``answer_altered``: the leaf with most rows answers with its sibling's
  value.
"""

import ml_dtypes
import numpy as np

import reference

TREES = 3


def _head(forest, n):
    return {k: v[:n].copy() for k, v in forest.items()}


def _in_place_of_program(sets, forest, params, **kwargs):
    """What ``follow`` computes itself, as a program's answer."""
    own = reference.follow(sets, forest, params, own_values=True, **kwargs)
    made = dict(forest, value=own["value"].astype(np.float32),
                cover=own["cover"].astype(np.float32))
    return made, own["loss"]


def readings(sets, forest, reported, params, limits):
    """``{control: {number: {"value", "limit"}}}`` over the first trees."""
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
    off["value"][0, big] = off["value"][0, big + 1 if big % 2 else big - 1]
    cases["answer_altered"] = (off, said)

    out = {}
    for name, (made, loss) in cases.items():
        ref = reference.follow(sets, made, params, split_trees=range(n))
        out[name] = reference.compare(loss, made, ref, limits)[1]
    return out


def every_tree_splits(sets, forest, params):
    """The split numbers of every tree, not of the run's draw: what their
    limits have to clear whichever tree a seed draws. Beside them each
    tree's widest single node below the top levels, which ``split_deep`` is
    not (PERF.md section 2)."""
    every = reference.follow(sets, forest, params,
                             split_trees=range(forest["feature"].shape[0]))
    return {"numbers": reference.split_numbers(every),
            "widest_node_share": reference.widest_node_shares(every)}

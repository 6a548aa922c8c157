"""A reference stub for ``test_contract.py``: the four functions ``run.py``
calls, judging only that it was handed what ``groups_gen`` makes and that
the program reported a metric for every round. No plain reference of a
ranking objective: the fixture proves the harness's contract, not a cell."""

import numpy as np


def forest_arrays(forest):
    return {"trees": int(np.asarray(forest.is_leaf).shape[0])}


def split_trees_of(seed, warmup_rounds, n_trees):
    return []


def follow(sets, forest, params, *, split_trees):
    missing = [f"{name}.{key}" for name, s in sets.items()
               for key in ("data", "label", "qid", "weight")
               if not hasattr(s, "keys") or key not in s]
    unsorted = [name for name, s in sets.items() if not missing
                and np.any(np.diff(s["qid"]) < 0)]
    return {"faults": missing + unsorted, "trees": forest["trees"]}


def compare(reported_loss, forest, ref, limits):
    short = sum(len(series) != ref["trees"]
                for series in reported_loss.values())
    compared = {"handed": {"value": float(len(ref["faults"])),
                           "limit": limits["handed"]},
                "reported": {"value": float(short),
                             "limit": limits["reported"]}}
    return all(c["value"] <= c["limit"] for c in compared.values()), compared

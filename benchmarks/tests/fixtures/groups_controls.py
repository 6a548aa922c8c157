"""The controls' stub of the same fixture: what ``--controls 1`` calls."""


def readings(sets, forest, reported, params, limits):
    return {"handed": sorted(sets)}


def every_tree_splits(sets, forest, params):
    return []

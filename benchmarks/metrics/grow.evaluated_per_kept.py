"""Nodes whose split the leaf-wise grower evaluated for every node the
round's tree kept (a tree of s splits keeps 2 s + 1 nodes): 1.0 where no
speculative node was thrown away. From the program's ``lossguide.grow``
event; ``None`` where the program records no such event."""

import events


def read(ctx):
    grown = events.last_attrs(ctx, "lossguide.grow")
    evaluated = grown.get("nodes_evaluated_per_round")
    splits = grown.get("splits_per_round")
    if evaluated is None or splits is None:
        return None
    return evaluated / (2.0 * splits + 1.0)

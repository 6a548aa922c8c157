"""Share of a device's busy seconds under the program's ``split`` scopes
(``tree/level*/split``: the scan of a level's ``[nodes, features, bins, 2]``
histogram for every node's best threshold) in the traced window, on the
device where it is largest. ``None`` where the trace names no such scope."""

import scope_share


def read(ctx):
    return scope_share.worst_device_pct(ctx, "split")

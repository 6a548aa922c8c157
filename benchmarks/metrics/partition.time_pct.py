"""Share of a device's busy seconds under the program's ``partition``
scopes (``tree/level*/partition``: a level's row routing, the node tables
looked up per row and the split feature's bin picked out of the row's
``features`` bins) in the traced window, on the device where it is largest.
``None`` where the trace names no such scope."""

import scope_share


def read(ctx):
    return scope_share.worst_device_pct(ctx, "partition")

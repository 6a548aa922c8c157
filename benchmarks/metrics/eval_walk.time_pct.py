"""Share of a device's busy seconds under the program's ``eval_walk`` scope
(each new tree walked over every eval set's binned rows to update its
margins) in the traced window, on the device where it is largest. ``None``
where the trace names no such scope (no eval set beside the training one)."""

import scope_share


def read(ctx):
    return scope_share.worst_device_pct(ctx, "eval_walk")

"""Share of a device's busy seconds under the program's ``select`` scope
(the best-first replay between a leaf-wise tree's levels: a sequential loop
over a table of gains, no rows) in the traced window, on the device that
spent most there. ``None`` where the trace names no such scope (another
grower, a CPU trace, or an executable compiled before the program named
it)."""

import trace_scopes


def read(ctx):
    t = ctx["trace"]
    shares = []
    for times in ((t or {}).get("scopes_by_device") or {}).values():
        spent, busy = (trace_scopes.seconds_under(times, "select"),
                       sum(times.values()))
        if spent is not None and busy > 0:
            shares.append(100.0 * spent / busy)
    return max(shares) if shares else None

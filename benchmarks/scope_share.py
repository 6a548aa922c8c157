"""A named scope's share of a device's busy seconds in the traced window."""

import trace_scopes


def worst_device_pct(ctx, leaf):
    """Seconds under the program's scopes whose path ends in ``leaf`` as a
    share (%) of the same device's busy seconds, on the device where that
    share is largest; ``None`` where the trace names no such scope (a CPU
    trace, or an executable compiled before the program named its
    scopes)."""
    t = ctx["trace"]
    shares = []
    for times in ((t or {}).get("scopes_by_device") or {}).values():
        spent, busy = (trace_scopes.seconds_under(times, leaf),
                       sum(times.values()))
        if spent is not None and busy > 0:
            shares.append(100.0 * spent / busy)
    return max(shares) if shares else None

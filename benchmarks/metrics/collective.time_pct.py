"""Share of its busy seconds that the device which waits longest spends
under the program's ``allreduce`` scopes (the merge itself and the wait for
the slowest shard at each level's barrier) in the traced window. The worst
device, not the sum: a sum over devices hides who waited. ``None`` where the
trace names no such scope (one device, a CPU trace, or an executable compiled
before the program named its scopes)."""

import trace_scopes


def read(ctx):
    t = ctx["trace"]
    shares = []
    for times in ((t or {}).get("scopes_by_device") or {}).values():
        waited, busy = (trace_scopes.seconds_under(times, "allreduce"),
                        sum(times.values()))
        if waited is not None and busy > 0:
            shares.append(100.0 * waited / busy)
    return max(shares) if shares else None

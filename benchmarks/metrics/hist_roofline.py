"""The histogram build's share of the chip's roofline: the least time one
chip could take for its device's part of a round's builds
(``shapes.device_round_work``: every row's bins and (g, h) once a level, two
accumulations per row and feature) over the seconds the device spent under
the program's ``hist`` scopes per round of the traced window, on the device
that spent most. ``None`` where the trace names no such scope (a CPU trace, or
an executable compiled before the program named its scopes)."""

import shapes
import trace_scopes


def read(ctx):
    t = ctx["trace"]
    if ctx["peak"] is None or not t or not t.get("rounds"):
        return None
    by_device = t.get("scopes_by_device") or {}
    found = [s for s in (trace_scopes.seconds_under(times, "hist")
                         for times in by_device.values()) if s]
    if not found:
        return None
    nbytes, ops = shapes.device_round_work(ctx["shapes"], t["devices"])
    least, _ = shapes.roofline_seconds(nbytes, ops, ctx["peak"])
    return 100.0 * least / (max(found) / t["rounds"])

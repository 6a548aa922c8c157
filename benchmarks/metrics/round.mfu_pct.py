"""The whole round's share of the chip's roofline: the least time the chip
could take for one round's necessary work (``shapes.round_work``, from
shapes only) over the seconds the device was busy per round of the traced
window. Device time only: what the host does between dispatches is
``driver.between_dispatch_ms``'s and ``device.idle_pct``'s to show."""

import shapes


def read(ctx):
    t = ctx["trace"]
    if ctx["peak"] is None or not t or not t.get("rounds") or t["busy_s"] <= 0:
        return None
    s = ctx["shapes"]
    nbytes, ops = shapes.round_work(s["rows"], s["features"], s["depth"],
                                    s["trees"])
    least, _ = shapes.roofline_seconds(nbytes, ops, ctx["peak"])
    return 100.0 * least / (t["busy_s"] / t["rounds"])

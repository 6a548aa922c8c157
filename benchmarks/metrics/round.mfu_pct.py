"""The whole round's share of the chip's roofline: the least time one chip
could take for its device's part of one round's necessary work
(``shapes.device_round_work``: from shapes only, the job's rows split evenly
over the traced devices) over the seconds a device was busy per round of the
traced window. Device time only: what the host does between dispatches is
``driver.between_dispatch_ms``'s and ``device.idle_pct``'s to show."""

import shapes


def read(ctx):
    t = ctx["trace"]
    if ctx["peak"] is None or not t or not t.get("rounds") or t["busy_s"] <= 0:
        return None
    nbytes, ops = shapes.device_round_work(ctx["shapes"], t["devices"])
    least, _ = shapes.roofline_seconds(nbytes, ops, ctx["peak"])
    return 100.0 * least / (t["busy_s"] / t["rounds"])

"""A save's booster readback, per round of the window: the
``checkpoint.readback`` spans (``engine.get_booster()``: the pending trees'
flush, the stacked forest, the cut points) inside the window. ``None`` where
the program has no such span."""

import spans


def read(ctx):
    recs = spans.timeline(ctx)
    if recs is None or not spans.named(recs, "checkpoint.readback"):
        return None
    return spans.in_window_ms_per_round(ctx, "checkpoint.readback")

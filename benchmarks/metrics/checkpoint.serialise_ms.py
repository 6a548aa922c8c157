"""A save's serialisation, per round of the window: the
``checkpoint.serialise`` spans (the whole forest's pickle) inside the
window. ``None`` where the program has no such span."""

import spans


def read(ctx):
    recs = spans.timeline(ctx)
    if recs is None or not spans.named(recs, "checkpoint.serialise"):
        return None
    return spans.in_window_ms_per_round(ctx, "checkpoint.serialise")

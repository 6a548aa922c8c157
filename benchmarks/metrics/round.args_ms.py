"""A dispatch's argument assembly, per round of the window: the
``dispatch.args`` spans (PRNG key, eval sets, group rows, bounds, uploads up
to the jitted call) inside the window; the rest of ``round.enqueue_ms`` is
the launch. ``None`` where the program has no such span."""

import spans


def read(ctx):
    recs = spans.timeline(ctx)
    if recs is None or not spans.named(recs, "dispatch.args"):
        return None
    return spans.in_window_ms_per_round(ctx, "dispatch.args")

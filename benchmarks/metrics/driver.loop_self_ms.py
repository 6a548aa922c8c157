"""The round loop's self time, per round of the window: the window less
every ``dispatch``, ``driver.checkpoint`` and ``driver.callbacks`` span in
it — host time that no span names."""

import spans


def read(ctx):
    recs = spans.timeline(ctx)
    if recs is None:
        return None
    parts = spans.window_parts(ctx["clock"])
    spanned = spans.seconds_in(
        spans.named(recs, "dispatch", "driver.checkpoint",
                    "driver.callbacks"), parts)
    window = sum(hi - lo for lo, hi in parts)
    return (window - spanned) * 1000.0 / ctx["window_rounds"]

"""The stall a save costs the round loop, per round of the window: the
program's ``driver.checkpoint`` spans (booster readback, serialise, queue,
commit) inside the window."""

import spans


def read(ctx):
    return spans.in_window_ms_per_round(ctx, "driver.checkpoint")

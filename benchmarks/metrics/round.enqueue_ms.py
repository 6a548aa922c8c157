"""Host time before the device has the program, per round of the window:
the ``dispatch.enqueue`` spans (argument assembly and launch; on a first
call also trace, lower, compile or cache load)."""

import spans


def read(ctx):
    return spans.in_window_ms_per_round(ctx, "dispatch.enqueue")

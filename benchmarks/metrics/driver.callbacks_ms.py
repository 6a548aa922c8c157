"""User and framework code between dispatches, per round of the window: the
program's ``driver.callbacks`` spans (``after_round`` fan-out,
``TrainingCallback`` hooks, ``feval``, early-stopping bookkeeping)."""

import spans


def read(ctx):
    return spans.in_window_ms_per_round(ctx, "driver.callbacks")

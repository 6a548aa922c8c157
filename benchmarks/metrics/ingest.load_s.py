"""Matrices to host shards: the ``data.load`` spans of ``train()`` and of
the attempt, train and eval sets."""

import spans


def read(ctx):
    return spans.before_window_s(ctx, "data.load")

"""Seconds of set-up spent loading executables from the persistent compile
cache: ``compile.cache_load`` spans before the window."""

import spans


def read(ctx):
    return spans.before_window_s(ctx, "compile.cache_load")

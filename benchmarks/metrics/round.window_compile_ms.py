"""Compiles inside the window, per round of it: ``compile.trace`` +
``compile.lower`` + ``compile.backend`` spans in the window, whatever span
caused them (a dispatch, a save, a callback). ``compile.cache_load`` lies
inside a ``compile.backend`` with ``cache_hit`` and is not added again."""

import spans


def read(ctx):
    return spans.in_window_ms_per_round(
        ctx, "compile.trace", "compile.lower", "compile.backend")

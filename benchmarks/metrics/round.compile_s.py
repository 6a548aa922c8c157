"""Seconds of set-up spent compiling: ``compile.trace`` + ``compile.lower``
+ ``compile.backend`` spans before the window. A ``compile.backend`` that
was a persistent-cache hit (``cache_hit``) is ``round.cache_load_s``'s."""

import spans


def read(ctx):
    return spans.before_window_s(
        ctx, "compile.trace", "compile.lower", "compile.backend",
        keep=lambda r: not (r.get("attrs") or {}).get("cache_hit"))

"""The sketch + bin program as the host sees it: the ``data.sketch_bin``
span (trace, lower, compile or cache load, enqueue). Its device seconds run
on behind it, under the ``sketch`` and ``bin`` scopes of a device trace."""

import spans


def read(ctx):
    return spans.before_window_s(ctx, "data.sketch_bin")

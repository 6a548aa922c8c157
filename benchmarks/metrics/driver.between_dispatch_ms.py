"""Host time between dispatches, per round of the window: what ``main.py``
does outside ``step``/``step_many`` (callbacks, checkpoint readback and
serialise, evaluation bookkeeping). Window wall time less the program's own
``chunk_times_s`` readings of the dispatches inside it."""


def read(ctx):
    if not ctx["in_window"]:
        return None
    inside = sum(d["seconds"] for d in ctx["in_window"])
    return (ctx["window_s"] - inside) * 1000.0 / ctx["window_rounds"]

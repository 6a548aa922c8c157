"""Share of the window's dispatches that compiled and loaded nothing: those
with no ``compile.backend`` or ``compile.cache_load`` span below them. 100,
or the warm-up missed a shape."""

import spans


def read(ctx):
    recs = spans.timeline(ctx)
    if recs is None:
        return None
    clock = ctx["clock"]
    parent = {r["seq"]: r.get("parent") for r in recs}
    inside = {r["seq"] for r in spans.named(recs, "dispatch")
              if clock.window_open <= r["t0_s"] < clock.window_close}
    if not inside:
        return None
    cold = set()
    for r in spans.named(recs, "compile.backend", "compile.cache_load"):
        seq = r.get("parent")
        while seq is not None and seq not in inside:
            seq = parent.get(seq)
        if seq is not None:
            cold.add(seq)
    return 100.0 * (len(inside) - len(cold)) / len(inside)

"""The first dispatch's seconds over a steady dispatch of the same length:
trace, lower, compile or cache load, and first execution."""

import statistics


def read(ctx):
    first = ctx["timeline"][0]
    steady = [d["seconds"] for d in ctx["in_window"]
              if d["rounds"] == first["rounds"]]
    if not steady:
        return None
    return first["seconds"] - statistics.median(steady)

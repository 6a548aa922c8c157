"""The program's own spans on the harness's clock.

``xgboost_ray_tpu.obs`` dates every record of a ``train()`` call by
``t0_s`` (``time.perf_counter()`` at its start), the clock ``run.py``'s
``Clock`` keeps ``window_open`` and ``window_close`` on. A reader sums the
spans of one name, each clipped to the window or to the time before it.
A program whose records carry no ``t0_s`` has no such timeline: every
reader then returns ``None`` and the line leaves the metric out.
"""


def timeline(ctx):
    """The run's span records that carry ``t0_s``, or None."""
    obs = (ctx.get("additional_results") or {}).get("obs") or {}
    recs = [r for r in obs.get("timeline") or ()
            if r.get("kind") == "span" and "t0_s" in r]
    return recs or None


def named(recs, *names):
    return [r for r in recs if r["name"] in names]


def window_parts(clock):
    """The measured window as ``[lo, hi]`` intervals: open to close, less
    the seconds ``stop_trace`` took where rounds followed it (``run.py``
    takes them out of ``round_ms`` the same way)."""
    lo, hi = clock.window_open, clock.window_close
    cut = clock.trace_close
    if cut is None or not lo < cut < hi or clock.trace_stop_s <= 0:
        return [[lo, hi]]
    return [[lo, cut], [min(cut + clock.trace_stop_s, hi), hi]]


def seconds_in(recs, parts):
    """Seconds of ``recs`` that lie inside ``parts``."""
    return sum(max(0.0, min(r["t0_s"] + r["dur_s"], hi) - max(r["t0_s"], lo))
               for r in recs for lo, hi in parts)


def in_window_ms_per_round(ctx, *names):
    """ms a round that the spans called ``names`` take of the window."""
    recs = timeline(ctx)
    if recs is None:
        return None
    inside = seconds_in(named(recs, *names), window_parts(ctx["clock"]))
    return inside * 1000.0 / ctx["window_rounds"]


def before_window_s(ctx, *names, keep=lambda r: True):
    """Seconds of the spans called ``names`` before the window opened."""
    recs = timeline(ctx)
    if recs is None:
        return None
    picked = [r for r in named(recs, *names) if keep(r)]
    return seconds_in(picked, [[float("-inf"), ctx["clock"].window_open]])

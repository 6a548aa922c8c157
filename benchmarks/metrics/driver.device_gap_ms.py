"""Time with nothing of the program enqueued, per round of the window: for
each two consecutive ``dispatch`` spans, from the end of the first's
``dispatch.wait`` to the end of the next one's ``dispatch.enqueue``, inside
the window. The inside counterpart of ``driver.between_dispatch_ms``: it
also holds the enqueue and what ``step`` does after its wait."""

import spans


def read(ctx):
    recs = spans.timeline(ctx)
    if recs is None:
        return None
    ends = {(r["parent"], r["name"]): r["t0_s"] + r["dur_s"]
            for r in spans.named(recs, "dispatch.wait", "dispatch.enqueue")}
    dispatches = [
        (ends[(d["seq"], "dispatch.wait")], ends[(d["seq"], "dispatch.enqueue")])
        for d in sorted(spans.named(recs, "dispatch"), key=lambda r: r["t0_s"])
        if (d["seq"], "dispatch.wait") in ends
        and (d["seq"], "dispatch.enqueue") in ends]
    gaps = [{"t0_s": waited, "dur_s": max(0.0, enqueued - waited)}
            for (waited, _), (_, enqueued) in zip(dispatches, dispatches[1:])]
    if not gaps:
        return None
    inside = spans.seconds_in(gaps, spans.window_parts(ctx["clock"]))
    return inside * 1000.0 / ctx["window_rounds"]

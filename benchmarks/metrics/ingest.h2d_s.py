"""Row uploads before the first dispatch: the ``data.h2d`` spans (rows,
labels, weights, margins, eval sets). On the materialised path a span times
the host's part (convert, pad, enqueue), not the copy behind it."""

import spans


def read(ctx):
    recs = spans.timeline(ctx)
    if recs is None:
        return None
    first = min((r["t0_s"] for r in spans.named(recs, "dispatch")),
                default=float("inf"))
    return spans.seconds_in(spans.named(recs, "data.h2d"),
                            [[float("-inf"), first]])

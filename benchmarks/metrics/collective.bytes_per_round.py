"""Bytes one actor moves over the wire in one boosting round's tree path
(the histogram merge of every level and the small exact reductions), under
the ring model, as the round program itself counted them: the
``allreduce.bytes`` event's ``bytes_per_round``."""

import events


def read(ctx):
    return events.last_attrs(ctx, "allreduce.bytes").get("bytes_per_round")

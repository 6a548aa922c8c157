"""Collectives in one boosting round's tree path, counted where the bytes
are: the ``allreduce.bytes`` event's ``collectives_per_round``. With
``collective.bytes_per_round`` it tells a latency-bound merge (many small
collectives) from a bandwidth-bound one."""

import events


def read(ctx):
    return events.last_attrs(ctx, "allreduce.bytes").get(
        "collectives_per_round")

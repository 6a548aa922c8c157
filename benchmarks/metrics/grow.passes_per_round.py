"""Full-row passes the leaf-wise grower made a round (the root's build and
one pass per 32 wanted nodes of a level), from the sums its round programs
counted on the device (the program's ``lossguide.grow`` event, recorded once
training has ended). ``None`` where the program records no such event."""

import events


def read(ctx):
    return events.last_attrs(ctx, "lossguide.grow").get("passes_per_round")

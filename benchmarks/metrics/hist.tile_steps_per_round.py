"""Feature-tile steps the dense histogram build makes a round: one step is
one matmul of a tile of features over one chunk of rows (a ``dynamic_slice``
of the chunk's bins and, with more than one tile, a ``dynamic_update_slice``
into the build's accumulator), so a level's build makes row chunks x
feature tiles of them. From the program's ``hist.builds`` event, which it
works out from the shapes of the builds it traced. ``None`` where the
program records no such event or not that attribute (a program from before
the attribute, or a run whose builds are the scatter-add's: the CPU's)."""

import events


def read(ctx):
    return events.last_attrs(ctx, "hist.builds").get("tile_steps_per_round")

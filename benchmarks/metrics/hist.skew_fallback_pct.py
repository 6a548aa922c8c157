"""Share of the (shard, level >= 1) sibling builds of the run that fell back
to a further pass because the shard's rows of the globally smaller children
overflowed its half-size buffer: the ``hist.skew_builds`` event's
``fallback_builds`` over its ``sibling_builds``. A shard in the fallback
does up to twice the rows, and the others wait for it at the level's psum."""

import events


def read(ctx):
    attrs = events.last_attrs(ctx, "hist.skew_builds")
    if not attrs.get("sibling_builds"):
        return None
    return 100.0 * attrs["fallback_builds"] / attrs["sibling_builds"]

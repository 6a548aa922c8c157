"""The program's own events, read from a run's timeline.

``xgboost_ray_tpu.obs`` records, once training has ended, what the round
programs counted on the device (``allreduce.bytes``):
a reader takes the attributes of the last event of a name. A program that
records no such event, or not that attribute, gives ``None``, and the line
leaves the metric out.
"""


def last_attrs(ctx, name):
    """Attributes of the run's last event called ``name`` (``{}`` if none)."""
    obs = (ctx.get("additional_results") or {}).get("obs") or {}
    found = [r for r in obs.get("timeline") or ()
             if r.get("kind") == "event" and r.get("name") == name]
    return (found[-1].get("attrs") or {}) if found else {}

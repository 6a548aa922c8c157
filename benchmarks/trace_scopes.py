"""Device seconds per named scope and per device, from a profiler trace.

The program names the phases of its compiled programs with
``jax.named_scope``; those names are all this module takes from it
(``scope_names``: ``xgboost_ray_tpu.obs.DEVICE_SCOPES``). A TPU trace keeps
each operation's scope path in its event *metadata* (the ``tf_op`` stat:
``jit(run)/while/body/closed_call/tree/level3/hist/dot_general:``), which
``jax.profiler.ProfileData`` does not show, so the ``.xplane.pb`` wire
format is read here (stdlib only). An operation counts under the scope names
of its path (``tree/level3/hist``) with its self time: what the operations
nested inside it (a ``while``'s body) do not cover. A device's scopes
therefore add up to its busy seconds.

This is the benchmark's own copy of the arithmetic of
``xgboost_ray_tpu/obs/device.py`` (``tests/test_contract.py`` holds the
two equal on a recorded file), kept here so that no PR that claims a gain can
change how a scope's seconds are read. The events of a line are decoded in
one loop without a message object each: a four-device window holds over a
million of them.
"""

import glob
import os
import re

DEVICE_PLANE_PREFIX = "/device:"
OP_LINE = b"XLA Ops"
UNSCOPED = "(unscoped)"
_LEVEL = re.compile(r"level\d+$")


def _varint(buf, i):
    val = shift = 0
    while True:
        byte = buf[i]
        i += 1
        val |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return val, i


def _fields(buf):
    """(field number, value) pairs of one protobuf message: ints for varints,
    memoryviews for length-delimited fields; fixed-width fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
            yield key >> 3, val
        elif wire == 2:
            size, i = _varint(buf, i)
            yield key >> 3, buf[i:i + size]
            i += size
        else:
            i += 8 if wire == 1 else 4


def scope_of(tf_op, scope_names):
    """``tree/level3/hist`` of ``jit(run)/while/body/tree/level3/hist/dot:``
    (a ``cond`` branch repeats its enclosing scopes: each name counts once)."""
    parts = [p for p in tf_op.split("/")
             if p in scope_names or _LEVEL.match(p)]
    return "/".join(dict.fromkeys(parts)) or UNSCOPED


def _line_events(line):
    """``(offset_ps, -duration_ps, index, metadata_id)`` of every XEvent of an
    XLine (events = 4; XEvent: metadata_id = 1, offset_ps = 2,
    duration_ps = 3), ready to sort: by start, the longer first, equal ones
    in the file's order."""
    buf = bytes(line)
    out = []
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            _, i = _varint(buf, i)
            continue
        if wire != 2:
            i += 8 if wire == 1 else 4
            continue
        size, i = _varint(buf, i)
        end = i + size
        if key >> 3 != 4:
            i = end
            continue
        md = off = dur = 0
        while i < end:
            k = buf[i]
            i += 1
            if k & 0x80:  # a field number over 15: none of the three
                k, i = _varint(buf, i - 1)
            wire = k & 7
            if wire == 0:
                val = buf[i]
                i += 1
                if val & 0x80:
                    val, i = _varint(buf, i - 1)
                if k == 8:
                    md = val
                elif k == 16:
                    off = val
                elif k == 24:
                    dur = val
            elif wire == 2:
                size, i = _varint(buf, i)
                i += size
            else:
                i += 8 if wire == 1 else 4
        out.append((off, -dur, len(out), md))
    return out


def _plane_times(plane, scope_names, out):
    """Add the self seconds of one XPlane's ``XLA Ops`` to ``out`` by scope."""
    fields = list(_fields(plane))

    def map_values(num):  # map<int64, Message> entries: key = 1, value = 2
        return [dict(_fields(v))[2] for n, v in fields if n == num]

    stat_names = {}  # XStatMetadata: id = 1, name = 2
    for md in map(dict, map(_fields, map_values(5))):
        stat_names[md.get(1, 0)] = bytes(md.get(2, b"")).decode()
    scope = {}  # XEventMetadata: id = 1, stats = 5; XStat: metadata_id = 1,
    for md in map(list, map(_fields, map_values(4))):  # str = 5, ref = 7
        for stat in (dict(_fields(v)) for k, v in md if k == 5):
            if stat_names.get(stat.get(1)) == "tf_op":
                op = stat.get(5) or stat_names.get(stat.get(7), "").encode()
                scope[dict(md).get(1, 0)] = scope_of(bytes(op).decode(),
                                                     scope_names)
    for line in (v for n, v in fields if n == 3):
        name = next((v for n, v in _fields(line) if n == 2), b"")
        if bytes(name) != OP_LINE:  # XLine.name = 2
            continue
        stack = []  # [end, scope, self_ps] of the operations still open
        for start, neg_dur, _, md in sorted(_line_events(line)):
            while stack and stack[-1][0] <= start:
                _, key, self_ps = stack.pop()
                out[key] = out.get(key, 0.0) + max(self_ps, 0) / 1e12
            if stack:
                stack[-1][2] += neg_dur
            stack.append([start - neg_dur, scope.get(md, UNSCOPED), -neg_dur])
        for _, key, self_ps in stack:
            out[key] = out.get(key, 0.0) + max(self_ps, 0) / 1e12


def scope_times_by_device(trace_dir, scope_names):
    """``{device plane: {scope path: device seconds}}`` over the ``XLA Ops``
    of every device plane of the newest ``.xplane.pb`` under ``trace_dir``
    (``{}`` where there is no file or no device plane, as in a CPU
    rehearsal). On a mesh the scope ``allreduce`` holds each device's wait
    at the level's barrier too."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = {}
    if not paths:
        return out
    with open(max(paths, key=os.path.getmtime), "rb") as fh:
        space = memoryview(fh.read())
    for n, plane in _fields(space):  # repeated XPlane planes = 1; name = 2
        if n != 1:
            continue
        name = next((bytes(v).decode() for k, v in _fields(plane) if k == 2),
                    "")
        if name.startswith(DEVICE_PLANE_PREFIX):
            _plane_times(plane, scope_names, out.setdefault(name, {}))
    # a device plane that ran no operation (a chip the job does not use)
    return {name: times for name, times in out.items() if times}


def summed(by_device):
    """``{scope path: seconds}`` over all devices together."""
    out = {}
    for times in by_device.values():
        for name, sec in times.items():
            out[name] = out.get(name, 0.0) + sec
    return out


def seconds_under(times, leaf):
    """Seconds of the scopes of one device whose path ends in ``leaf``
    (``hist`` of ``tree/level3/hist``), or None where it has none."""
    found = [sec for name, sec in times.items()
             if name.rsplit("/", 1)[-1] == leaf]
    return sum(found) if found else None

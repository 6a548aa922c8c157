"""Device seconds per named scope, from a profiler trace.

    python -m xgboost_ray_tpu.obs.device <trace dir>

The compiled programs name their phases with ``jax.named_scope``
(``obs.DEVICE_SCOPES``). A TPU trace keeps each operation's scope path in its
event metadata (the ``tf_op`` stat: ``jit(run)/while/body/closed_call/tree/
level3/hist/...``), which ``jax.profiler.ProfileData`` does not show, so this
reads the ``.xplane.pb`` wire format itself (stdlib only). An operation counts
under the ``DEVICE_SCOPES`` names of its path (``tree/level3/hist``), with its
self time: what operations nested inside it (a ``while``'s body) do not cover.
"""

import glob
import os
import re
import sys

from xgboost_ray_tpu.obs.trace import DEVICE_SCOPES

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


def _scope_of(tf_op: str) -> str:
    """``tree/level3/hist`` of ``jit(run)/while/body/tree/level3/hist/dot:``
    (a ``cond`` branch repeats its enclosing scopes: each name counts once)."""
    parts = [p for p in tf_op.split("/")
             if p in DEVICE_SCOPES or _LEVEL.match(p)]
    return "/".join(dict.fromkeys(parts)) or "(unscoped)"


def _plane_times(plane, out):
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
                scope[dict(md).get(1, 0)] = _scope_of(bytes(op).decode())
    for line in (list(_fields(v)) for n, v in fields if n == 3):
        if bytes(dict(line).get(2, b"")) != b"XLA Ops":  # XLine.name = 2
            continue
        # XEvent: metadata_id = 1, offset_ps = 2, duration_ps = 3
        events = [dict(_fields(v)) for k, v in line if k == 4]
        stack = []  # [end, scope, self_ps] of the operations still open

        def close(until):
            while stack and stack[-1][0] <= until:
                _, name, self_ps = stack.pop()
                out[name] = out.get(name, 0.0) + max(self_ps, 0) / 1e12

        for ev in sorted(events, key=lambda e: (e.get(2, 0), -e.get(3, 0))):
            start, dur = ev.get(2, 0), ev.get(3, 0)
            close(start)
            if stack:
                stack[-1][2] -= dur
            stack.append([start + dur, scope.get(ev.get(1), "(unscoped)"), dur])
        close(float("inf"))


def scope_times_by_device(trace_dir: str) -> dict:
    """``{device plane: {scope path: device seconds}}`` over the ``XLA Ops``
    of every device plane of the newest ``.xplane.pb`` under ``trace_dir``.
    A device's values add up to its busy seconds; on a mesh the scope
    ``allreduce`` holds each device's wait at the level's barrier too."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    out = {}
    if paths:
        with open(max(paths, key=os.path.getmtime), "rb") as fh:
            space = memoryview(fh.read())
        for n, plane in _fields(space):  # repeated XPlane planes = 1; name = 2
            name = bytes(dict(_fields(plane)).get(2, b"")).decode() if n == 1 else ""
            if name.startswith("/device:"):
                _plane_times(plane, out.setdefault(name, {}))
    return out


def _summed(by_device: dict) -> dict:
    out = {}
    for times in by_device.values():
        for name, sec in times.items():
            out[name] = out.get(name, 0.0) + sec
    return out


def scope_times(trace_dir: str) -> dict:
    """``{scope path: device seconds}`` summed over the devices of
    :func:`scope_times_by_device` (the values add up to the devices' busy
    seconds together)."""
    return _summed(scope_times_by_device(trace_dir))


def _print_table(times):
    total = sum(times.values()) or 1.0
    for name, sec in sorted(times.items(), key=lambda kv: -kv[1]):
        print(f"{sec:12.6f} s  {100 * sec / total:6.2f} %  {name}")
    print(f"{sum(times.values()):12.6f} s  busy, all scopes")


if __name__ == "__main__":
    by_device = scope_times_by_device(sys.argv[1])
    if len(by_device) > 1:
        for device, times in sorted(by_device.items()):
            print(device)
            _print_table(times)
        print("all devices")
    _print_table(_summed(by_device))

"""Device seconds per named scope, and the device's idle seconds per span,
from a profiler trace.

    python -m xgboost_ray_tpu.obs.device <trace dir>
    python -m xgboost_ray_tpu.obs.device <trace dir> --timeline <trace-rank0.jsonl>

The compiled programs name their phases with ``jax.named_scope``
(``obs.DEVICE_SCOPES``). A TPU trace keeps each operation's scope path in its
event metadata (the ``tf_op`` stat: ``jit(run)/while/body/closed_call/tree/
level3/hist/...``), which ``jax.profiler.ProfileData`` does not show, so this
reads the ``.xplane.pb`` wire format itself (stdlib only). An operation counts
under the ``DEVICE_SCOPES`` names of its path (``tree/level3/hist``), with its
self time: what operations nested inside it (a ``while``'s body) do not cover.

With ``--timeline`` (the JSONL ``RXGB_TRACE_DIR`` streams) it prints instead
what the host was doing while each device was idle: :func:`idle_by_span`.
"""

import argparse
import bisect
import glob
import json
import os
import re
import statistics

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


def _planes(trace_dir):
    """``(name, XPlane)`` of every plane of the newest ``.xplane.pb`` under
    ``trace_dir`` (XSpace: repeated XPlane planes = 1; XPlane.name = 2)."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        return []
    with open(max(paths, key=os.path.getmtime), "rb") as fh:
        space = memoryview(fh.read())
    return [(bytes(dict(_fields(plane)).get(2, b"")).decode(), plane)
            for n, plane in _fields(space) if n == 1]


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
    out = {}
    for name, plane in _planes(trace_dir):
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


def _timed_events(plane):
    """``(line name, event name, start ps, duration ps)`` of every event of
    one XPlane, its start on the trace's clock: the line's ``timestamp_ns``
    plus the event's ``offset_ps`` (XLine: name = 2, timestamp_ns = 3,
    events = 4; XEventMetadata: id = 1, name = 2)."""
    fields = list(_fields(plane))
    names = {}
    for n, v in fields:
        if n == 4:
            md = dict(_fields(dict(_fields(v))[2]))
            names[md.get(1, 0)] = bytes(md.get(2, b"")).decode()
    out = []
    for line in (list(_fields(v)) for n, v in fields if n == 3):
        head = dict(line)
        line_name = bytes(head.get(2, b"")).decode()
        base_ps = head.get(3, 0) * 1000
        for k, v in line:
            if k == 4:
                ev = dict(_fields(v))
                out.append((line_name, names.get(ev.get(1), ""),
                            base_ps + ev.get(2, 0), ev.get(3, 0)))
    return out


def _union(intervals):
    merged = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return merged


#: how far a record's start and duration may lie from its annotation's: the
#: two clocks are read a few microseconds apart at either end of a span
_MATCH_S = 5e-5


def _clock_offset(spans, marks):
    """Seconds to add to a record's ``t0_s`` to put it on the trace's clock,
    from the records that have their ``TraceAnnotation`` among ``marks``
    (``(name, start s, duration s)``), or ``None`` where none pairs.

    Each of the longest marks proposes an offset for every record of its
    name and about its duration; an offset is worth the marks that then
    find a record of their name within ``_MATCH_S`` in start and in
    duration, and the least error among equals (a job's rounds repeat, so a
    shift by whole rounds can pair as many marks, never as closely). The
    offset is the median of the matched pairs'."""
    by_name = {}
    for r in spans:
        by_name.setdefault(r["name"], []).append(r)
    for recs in by_name.values():
        recs.sort(key=lambda r: r["t0_s"])
    starts = {name: [r["t0_s"] for r in recs] for name, recs in by_name.items()}

    def fit(offset):
        pairs = []
        for name, start, dur in marks:
            recs = by_name.get(name, ())
            i = bisect.bisect_left(starts.get(name, ()), start - offset)
            err, r = min(((abs(r["t0_s"] + offset - start)
                           + abs(r["dur_s"] - dur), r)
                          for r in recs[max(0, i - 1):i + 1]),
                         default=(None, None), key=lambda er: er[0])
            if r is not None and err <= _MATCH_S:
                pairs.append((err, start - r["t0_s"]))
        return (len(pairs), -sum(e for e, _ in pairs)), pairs

    best = None
    for name, start, dur in sorted(marks, key=lambda m: -m[2])[:8]:
        for r in by_name.get(name, ()):
            if abs(r["dur_s"] - dur) <= _MATCH_S:
                key, pairs = fit(start - r["t0_s"])
                if best is None or key > best[0]:
                    best = (key, pairs)
    if best is None or not best[1]:
        return None
    return statistics.median(d for _, d in best[1])


def _span_paths(spans):
    """``{id(record): (depth, path)}``: a record's path is its ancestors'
    names and its own, joined by ``/``. Parents are found by ``seq``; where
    a stream holds several tracers' records (several ``seq`` 1), the
    parent is the one of that ``seq`` whose time covers the child."""
    by_seq = {}
    for r in spans:
        by_seq.setdefault(r["seq"], []).append(r)

    def parent_of(r):
        cands = by_seq.get(r.get("parent"), ())
        return min(cands, default=None, key=lambda p: (
            not p["t0_s"] - 1e-3 <= r["t0_s"] <= p["t0_s"] + p["dur_s"] + 1e-3,
            p["dur_s"]))

    out = {}
    for r in spans:
        names, p = [r["name"]], parent_of(r)
        while p is not None and len(names) < 64:
            names.append(p["name"])
            p = parent_of(p)
        out[id(r)] = (len(names), "/".join(reversed(names)))
    return out


def _innermost(spans, offset):
    """Sorted ``[start, end, path]`` pieces of the trace's clock, each
    labelled by the deepest record (the latest to start among equals) that
    covers it; ``None`` where none does."""
    paths = _span_paths(spans)
    edges = []
    for r in spans:
        lo = r["t0_s"] + offset
        edges.append((lo, 1, r))
        edges.append((lo + r["dur_s"], 0, r))
    edges.sort(key=lambda e: (e[0], e[1]))
    pieces, open_, t = [], [], float("-inf")
    for when, starts, r in edges:
        if when > t:
            best = max(open_, default=None, key=lambda o: (
                paths[id(o)][0], o["t0_s"]))
            pieces.append([t, when, best and paths[id(best)][1]])
            t = when
        if starts:
            open_.append(r)
        else:
            open_.remove(r)
    pieces.append([t, float("inf"), None])
    return pieces


def idle_by_span(trace_dir, records, bounds=None):
    """``{device plane: {span path: idle seconds}}``: why each device of the
    newest trace under ``trace_dir`` was idle, by what the host was doing.

    A device's idle is the time between its ``XLA Ops`` inside ``bounds``
    (``(lo, hi)`` on the timeline's ``perf_counter`` clock) or, without
    them, between its first and last operation. Each idle slice goes to the
    innermost timeline record that covers it, named by its span path
    (``attempt/driver.checkpoint/checkpoint.readback/compile.backend``;
    ``round`` records restate their dispatch and are left out), and to
    ``(no span)`` where no record covers it. ``records`` (a ``train()``
    timeline, or the JSONL ``RXGB_TRACE_DIR`` writes) are put on the
    trace's clock by the offset at which ``span()`` records best meet their
    ``jax.profiler.TraceAnnotation`` on the host plane (:func:`_clock_offset`),
    so records with no annotation of their own (``compile.*``) land too. Where
    no record pairs with an annotation, neither records nor ``bounds`` can
    be placed: every idle second from the first operation to the last is
    ``(no span)``. A CPU trace has no device plane: ``{}``."""
    planes = _planes(trace_dir)
    events = [(name, _timed_events(plane)) for name, plane in planes]
    stamps = [ev[2] for _, evs in events for ev in evs]
    if not stamps:
        return {}
    base = min(stamps)  # seconds from here keep the picoseconds' precision
    spans = [r for r in records if r.get("kind") == "span"
             and r.get("name") != "round" and "t0_s" in r and "dur_s" in r]
    names = {r["name"] for r in spans}
    marks = [(ev[1], (ev[2] - base) / 1e12, ev[3] / 1e12)
             for name, evs in events if name.startswith("/host:")
             for ev in evs if ev[1] in names]
    offset = _clock_offset(spans, marks)
    pieces = (_innermost(spans, offset) if offset is not None
              else [[float("-inf"), float("inf"), None]])
    out = {}
    for name, evs in events:
        ops = [((s - base) / 1e12, (s + d - base) / 1e12)
               for line, _, s, d in evs if line == "XLA Ops" and d > 0]
        if not name.startswith("/device:") or not ops:
            continue
        busy = _union(ops)
        if bounds is not None and offset is not None:
            lo, hi = bounds[0] + offset, bounds[1] + offset
        else:
            lo, hi = busy[0][0], busy[-1][1]
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        gaps = [(max(a, lo), min(b, hi)) for a, b in zip(edges[::2],
                                                         edges[1::2])]
        table = out.setdefault(name, {})
        j = 0
        for a, b in gaps:
            if b <= a:
                continue
            while pieces[j][1] <= a:
                j += 1
            k = j
            while k < len(pieces) and pieces[k][0] < b:
                p_lo, p_hi, path = pieces[k]
                sec = min(b, p_hi) - max(a, p_lo)
                key = path or "(no span)"
                table[key] = table.get(key, 0.0) + sec
                k += 1
    return out


def _print_table(times, what="busy, all scopes"):
    total = sum(times.values()) or 1.0
    for name, sec in sorted(times.items(), key=lambda kv: -kv[1]):
        print(f"{sec:12.6f} s  {100 * sec / total:6.2f} %  {name}")
    print(f"{sum(times.values()):12.6f} s  {what}")


def _read_jsonl(path):
    with open(path) as fh:
        return [json.loads(line) for line in fh if line.strip()]


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="python -m xgboost_ray_tpu.obs.device",
        description="Device seconds per named scope from a profiler trace; "
                    "with --timeline, each device's idle seconds by span.")
    ap.add_argument("trace_dir")
    ap.add_argument("--timeline", help="a trace-rank<k>.jsonl of the run "
                    "(RXGB_TRACE_DIR): print the idle seconds by span")
    args = ap.parse_args(argv)
    if args.timeline:
        by_device = idle_by_span(args.trace_dir, _read_jsonl(args.timeline))
        for device, times in sorted(by_device.items()):
            print(device)
            _print_table(times, "idle")
        return
    by_device = scope_times_by_device(args.trace_dir)
    if len(by_device) > 1:
        for device, times in sorted(by_device.items()):
            print(device)
            _print_table(times)
        print("all devices")
    _print_table(_summed(by_device))


if __name__ == "__main__":
    main()

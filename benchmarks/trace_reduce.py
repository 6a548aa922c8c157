"""From a profiler trace to busy intervals, operations by name and idle gaps.

``read_events`` turns the profiler's ``.xplane.pb`` into plain rows
``[plane, line, name, start_ns, duration_ns]``; everything after that works
on the rows, so the reduction is checked on a small recorded list
(``tests/fixtures``) and no PR that claims a gain can change how a number is
read.

The harness writes two host markers into the trace
(``jax.profiler.TraceAnnotation``), which land on the same clock as the
device's events: ``bench.window_open`` and ``bench.trace_stop`` bound the
traced window. The spans in which the harness knows the host was outside a
dispatch (callbacks, checkpoint, metric read: a dispatch's end as the
callback saw it, to the next one's start as ``chunk_times_s`` dates it) come
in seconds after ``bench.window_open``.
"""

import glob
import os

DEVICE_PLANE_PREFIX = "/device:"
OP_LINE = "XLA Ops"
# lines of a device plane that hold summaries, not operations
SUMMARY_LINES = ("Steps", "XLA Modules", "Framework Name Scope",
                 "Framework Ops", "Source code")
MARK_OPEN = "bench.window_open"
MARK_STOP = "bench.trace_stop"


def read_events(trace_dir):
    """Rows of every device event and every ``bench.*`` host marker."""
    import jax

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        return []
    data = jax.profiler.ProfileData.from_file(sorted(paths)[-1])
    rows = []
    for plane in data.planes:
        device = plane.name.startswith(DEVICE_PLANE_PREFIX)
        for line in plane.lines:
            for ev in line.events:
                if device or ev.name.startswith("bench."):
                    rows.append([plane.name, line.name, ev.name,
                                 float(ev.start_ns), float(ev.duration_ns)])
    return rows


def short_name(name):
    """``%fusion.12 s32[11000000]`` from the HLO line the chip's trace gives
    as an operation's name; any other name as it is."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:120]
    return (head + " " + rest.split("{", 1)[0].split(" ", 1)[0])[:120]


def _union(intervals):
    """Merged, sorted ``[start, end]`` intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _self_times(events):
    """Seconds by name, each event less the events nested inside it."""
    totals = {}
    stack = []  # [end, name, self_ns]

    def close(until):
        while stack and stack[-1][0] <= until:
            _, name, self_ns = stack.pop()
            totals[name] = totals.get(name, 0.0) + max(self_ns, 0.0) / 1e9

    for start, dur, name in sorted(events, key=lambda e: (e[0], -e[1])):
        close(start)
        if stack:
            stack[-1][2] -= dur
        stack.append([start + dur, name, dur])
    close(float("inf"))
    return totals


def reduce_trace(rows, host_spans_s=()):
    """``{"window_s", "busy_s", "device_ops", "idle_gaps", "devices"}`` or
    None where the trace holds no device operation.

    ``busy_s`` is the union of the intervals in which an operation ran,
    averaged over the device planes that ran any; ``device_ops`` the ten
    names with most self time (summed over devices); ``idle_gaps`` the ten
    longest gaps of the busiest-gapped device, labelled ``host`` where one
    of ``host_spans_s`` overlaps them and ``in_dispatch`` where none does."""
    marks = {}
    by_plane = {}
    for plane, line, name, start, dur in rows:
        if name in (MARK_OPEN, MARK_STOP):
            marks[name] = start
        elif plane.startswith(DEVICE_PLANE_PREFIX):
            by_plane.setdefault(plane, {}).setdefault(line, []).append(
                (start, dur, name))
    op_events = {}
    for plane, lines in by_plane.items():
        if OP_LINE in lines:
            picked = lines[OP_LINE]
        else:
            picked = [e for ln, evs in lines.items()
                      if ln not in SUMMARY_LINES for e in evs]
        picked = [e for e in picked if e[1] > 0]
        if picked:
            op_events[plane] = picked
    if not op_events:
        return None
    lo = marks.get(MARK_OPEN, min(e[0] for evs in op_events.values()
                                  for e in evs))
    hi = marks.get(MARK_STOP, max(e[0] + e[1] for evs in op_events.values()
                                  for e in evs))
    host_spans = [[lo + s * 1e9, lo + e * 1e9] for s, e in host_spans_s]
    busy, ops, worst_gaps = [], {}, []
    for plane, evs in sorted(op_events.items()):
        clipped = [[max(s, lo), min(s + d, hi)] for s, d, _ in evs
                   if s + d > lo and s < hi]
        merged = _union(clipped)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        for name, sec in _self_times(evs).items():
            name = short_name(name)
            ops[name] = ops.get(name, 0.0) + sec
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        gaps = [[edges[i], edges[i + 1]] for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        if sum(e - s for s, e in gaps) >= sum(e - s for s, e in worst_gaps):
            worst_gaps = gaps
    labelled = {}
    for s, e in sorted(worst_gaps, key=lambda g: g[0] - g[1])[:10]:
        on_host = any(hs < e and he > s for hs, he in host_spans)
        key = "host" if on_host else "in_dispatch"
        labelled.setdefault(key, []).append((e - s) / 1e9)
    idle_gaps = sorted(
        ([f"{k}.{i}", sec] for k, secs in labelled.items()
         for i, sec in enumerate(secs)),
        key=lambda kv: -kv[1])[:10]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / len(busy),
        "devices": len(busy),
        "device_ops": [[n, s] for n, s in sorted(
            ops.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": idle_gaps,
    }

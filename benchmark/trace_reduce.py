"""``.xplane.pb`` → device busy time inside the traced window, the longest
operations, the longest gaps.

Reads the profiler's trace with nothing but JAX (``ProfileData``). Only device
planes count (``/device:TPU:<n>``): busy is the union of the intervals in which
an operation ran on that device, so nested and overlapping events are counted
once. The profiler session writes its own collection span into the trace
(plane ``Task Environment``: ``profile_start_time`` and ``profile_stop_time``,
Unix nanoseconds), and a device plane's timestamps are nanoseconds since that
start: that places a window given in Unix time on the clock of the device's
operations. Every operation is clipped to the window before anything is
summed, so 0 <= ``busy_s`` <= ``window_s`` by arithmetic (whole picoseconds).

The window is not the collection span itself: on the v5e the span opens some
40 ms before ``start_trace`` returns and closes some 270 ms after
``stop_trace`` is called, and the device's tracer records nothing in either
margin (PERF.md, PR 34). It is what the caller marks inside the span.
Checked by ``tests/test_trace_reduce.py`` on ``trace_small.txt``,
``trace_span.txt`` and ``trace_recorded.txt``.
"""

import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"          # one event per HLO operation as it ran
SESSION_PLANE = "Task Environment"   # written by the profiler session itself
SPAN_STATS = ("profile_start_time", "profile_stop_time")
TOP = 10
MIN_GAP_S = 1e-4   # shorter gaps are the device sequencing its own operations
PS = 1e12


def load(path: str):
    from jax.profiler import ProfileData
    if path.endswith(".txt"):
        with open(path) as f:
            return ProfileData.from_serialized_xspace(
                ProfileData.text_proto_to_serialized_xspace(f.read()))
    return ProfileData.from_file(path)


def collection_span(profile):
    """(start, stop) of the profiler's collection in Unix nanoseconds, as the
    session wrote them into the trace; None where it wrote none."""
    for plane in profile.planes:
        if plane.name == SESSION_PLANE:
            stats = dict(plane.stats)
            if all(k in stats for k in SPAN_STATS):
                start, stop = (int(stats[k]) for k in SPAN_STATS)
                if stop > start:
                    return start, stop
    return None


def plane_events(plane) -> list:
    """(name, start_ps, end_ps) of the plane's operation events, in whole
    picoseconds (the trace's own resolution). A device plane that has lines
    and none of operations is an error: another line's events are another
    quantity."""
    lines = {ln.name: ln for ln in plane.lines}
    if OPS_LINE not in lines:
        if lines:
            raise ValueError(f"{plane.name}: no {OPS_LINE!r} line among "
                             f"{sorted(lines)}")
        return []
    return [(e.name, round(e.start_ns * 1000),
             round((e.start_ns + e.duration_ns) * 1000))
            for e in lines[OPS_LINE].events]


def short(name: str) -> str:
    """'%fusion.1 = s32[4096]{...} fusion(...)' → 'fusion.1': the trace
    names an operation by its whole HLO line."""
    return name.split(" = ", 1)[0].lstrip("%")[:80]


def union(intervals: list) -> list:
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(spans: list, lo: int, hi: int) -> list:
    """The part inside [lo, hi] of every (..., start, end); one that ended
    before the window opened or began after it closed is gone."""
    return [(*x[:-2], max(x[-2], lo), min(x[-1], hi)) for x in spans
            if x[-1] > lo and x[-2] < hi]


def place(span, marks):
    """``marks`` (Unix nanoseconds; None for the whole span) on the device
    planes' clock, whose zero is the span's start."""
    if span is None:
        raise ValueError(
            f"the trace has no plane {SESSION_PLANE!r} with the stats "
            f"{SPAN_STATS[0]} and {SPAN_STATS[1]}: no collection span to "
            f"place the window in")
    lo, hi = marks or span
    if not span[0] <= lo < hi <= span[1]:
        raise ValueError(
            f"the marks {lo}..{hi} are no window inside the trace's "
            f"collection span {span[0]}..{span[1]}: the clock they were read "
            f"from is not the profiler's")
    return lo - span[0], hi - span[0]


def reduce_profile(profile, interval=None, marks=None,
                   min_gap_s: float = MIN_GAP_S) -> dict:
    """The traced window is ``marks``, (lo, hi) in Unix nanoseconds as the
    caller read them around the slice it means (``time.time_ns()``, inside
    the profiler's collection span), placed on the device planes' clock by
    the span's start; left out, the span itself. A trace without the span
    raises ValueError, as do marks not inside it: nothing then says where the
    window lies among the device's operations. ``interval``, (lo, hi) in
    nanoseconds on the device planes' own clock, is for a recorded text trace
    that has no session plane. ``window_s`` is the window's length. Every
    operation is clipped to it first: busy_s (averaged over the device planes
    found), ``op_s`` and ``gap_s`` (every operation's and every gap's
    seconds), ``device_ops`` and ``idle_gaps`` (the ten longest of each) are
    all of the clipped events. ``planes[name]`` says what the clipping took:
    ``clipped_s``, the busy seconds that lay outside the window, and
    ``outside``, the events wholly outside it; ``first_s`` and ``last_s`` are
    the first operation's start and the last one's end in seconds from the
    window's opening. Clocks that do not line up show there, as seconds
    clipped. ``span_unix_s`` is the collection span in Unix seconds."""
    span = collection_span(profile)
    if interval is None:
        interval = place(span, marks)
    lo, hi = (round(t * 1000) for t in interval)
    planes, op_s, gaps, busy_ps = {}, {}, [], 0
    for plane in profile.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        found = plane_events(plane)
        events = clip(found, lo, hi)
        whole = union([(s, e) for _, s, e in found])
        merged = clip(whole, lo, hi)   # the clipped events' union
        busy = sum(e - s for s, e in merged)
        busy_ps += busy
        planes[plane.name] = {
            "events": len(found), "busy_s": busy / PS,
            "clipped_s": (sum(e - s for s, e in whole) - busy) / PS,
            "outside": len(found) - len(events),
            "first_s": (merged[0][0] - lo) / PS if merged else None,
            "last_s": (merged[-1][1] - lo) / PS if merged else None}
        for name, s, e in events:
            name = short(name)
            op_s[name] = op_s.get(name, 0.0) + (e - s) / PS
        # a gap is named by the operations on either side of it: the trace
        # holds no host span yet that says what the host was doing
        by_end = {e: short(n) for n, _, e in events}
        by_start = {s: short(n) for n, s, _ in reversed(events)}
        for (_, e0), (s1, _) in zip(merged, merged[1:]):
            if (s1 - e0) / PS < min_gap_s:
                continue
            gaps.append((f"{by_end.get(e0, '?')}..{by_start.get(s1, '?')}",
                         (s1 - e0) / PS))
    # whole picoseconds: the planes' sum is at most their number of windows,
    # and neither division can turn that round
    busy_s = busy_ps / len(planes) / PS if planes else 0.0
    window_s = (hi - lo) / PS
    gap_s = {}
    for name, s in gaps:
        gap_s[name] = gap_s.get(name, 0.0) + s
    top = lambda d: [[k, v] for k, v in
                     sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]
    return {"planes": planes, "busy_s": busy_s, "window_s": window_s,
            "span_unix_s": span and (span[0] / 1e9, span[1] / 1e9),
            "idle_share": (1.0 - busy_s / window_s) if window_s > 0 else None,
            "op_s": op_s, "gap_s": gap_s,
            "device_ops": top(op_s), "idle_gaps": top(gap_s)}


def reduce_file(path: str, interval=None, marks=None, **kw) -> dict:
    return reduce_profile(load(path), interval, marks, **kw)

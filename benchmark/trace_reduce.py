"""``.xplane.pb`` → device busy time, the longest operations, the longest gaps.

Reads the profiler's trace with nothing but JAX (``ProfileData``). Only device
planes count (``/device:TPU:<n>``): busy is the union of the intervals in which
an operation ran on that device, so nested and overlapping events are counted
once. Checked by ``tests/test_trace_reduce.py`` on ``trace_small.txt``.
"""

import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"          # one event per HLO operation as it ran
TOP = 10
MIN_GAP_S = 1e-4   # shorter gaps are the device sequencing its own operations


def load(path: str):
    from jax.profiler import ProfileData
    if path.endswith(".txt"):
        with open(path) as f:
            return ProfileData.from_serialized_xspace(
                ProfileData.text_proto_to_serialized_xspace(f.read()))
    return ProfileData.from_file(path)


def plane_events(plane) -> list:
    """(name, start_ns, end_ns) of the plane's operation events. A device
    plane that has lines and none of operations is an error: another line's
    events are another quantity."""
    lines = {ln.name: ln for ln in plane.lines}
    if OPS_LINE not in lines:
        if lines:
            raise ValueError(f"{plane.name}: no {OPS_LINE!r} line among "
                             f"{sorted(lines)}")
        return []
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
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


def reduce_profile(profile, window_s: float,
                   min_gap_s: float = MIN_GAP_S) -> dict:
    """``window_s`` is the length of the traced window by the host's clock.
    busy_s is averaged over the device planes found; an idle share is only
    given where there is a window to take it of. ``op_s`` and ``gap_s`` hold
    every operation's and every gap's seconds, ``device_ops`` and
    ``idle_gaps`` the ten longest of each."""
    planes, op_s, gaps = {}, {}, []
    for plane in profile.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        events = plane_events(plane)
        merged = union([(s, e) for _, s, e in events])
        planes[plane.name] = {
            "events": len(events),
            "busy_s": sum(e - s for s, e in merged) / 1e9}
        for name, s, e in events:
            name = short(name)
            op_s[name] = op_s.get(name, 0.0) + (e - s) / 1e9
        # a gap is named by the operations on either side of it: the trace
        # holds no host span yet that says what the host was doing
        by_end = {e: short(n) for n, _, e in events}
        by_start = {s: short(n) for n, s, _ in reversed(events)}
        for (_, e0), (s1, _) in zip(merged, merged[1:]):
            if (s1 - e0) / 1e9 < min_gap_s:
                continue
            gaps.append((f"{by_end.get(e0, '?')}..{by_start.get(s1, '?')}",
                         (s1 - e0) / 1e9))
    busy = [p["busy_s"] for p in planes.values()]
    busy_s = sum(busy) / len(busy) if busy else 0.0
    gap_s = {}
    for name, s in gaps:
        gap_s[name] = gap_s.get(name, 0.0) + s
    top = lambda d: [[k, v] for k, v in
                     sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]
    return {"planes": planes, "busy_s": busy_s, "window_s": window_s,
            "idle_share": (1.0 - busy_s / window_s) if window_s > 0 else None,
            "op_s": op_s, "gap_s": gap_s,
            "device_ops": top(op_s), "idle_gaps": top(gap_s)}


def reduce_file(path: str, window_s: float, **kw) -> dict:
    return reduce_profile(load(path), window_s, **kw)

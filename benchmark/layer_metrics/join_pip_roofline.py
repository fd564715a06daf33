"""The grouped point-in-polygon kernel's share of the HBM roofline, in
percent.

layer: join kernel (index/scan.py) · source: device_trace · moves: qps
Bytes the window's joins had to read: the rows of the tiles they gathered
(counter ``join.points_scanned``) times the bytes a row's predicate needs
(``predicate_plane_bytes`` of the configuration: x, y, the time, the two
summed attributes), plus the segments their pairs read from the polygons'
pool (counter ``join.segments_read``: a polygon's own segments, once a pair)
times the bytes a segment's test needs (``refine_plane_bytes``); never what
the kernel happens to read. Least time a second of the window = bytes /
window seconds / peak HBM bytes/s; share = that / the traced slice's busy
share, as ``intersects_pool_roofline`` reckons. HBM-bound by this count: the
point × edge compares run on the VPU, for which no peak is published, so the
share reads low, the lower the more edges a pair has. The busy share is of
every operation of the launches, so the share cannot pass 100. A program
without the counters reads None."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _window  # noqa: E402


def bytes_needed(points: int, segments: int, point_bytes: dict,
                 segment_bytes: dict) -> int:
    return points * sum(point_bytes.values()) \
        + segments * sum(segment_bytes.values())


def read(ctx: dict):
    trace, peaks, cfg = ctx.get("trace"), ctx.get("peaks"), ctx["config"]
    points = _window.counter_delta(ctx, "join.points_scanned")
    segments = _window.counter_delta(ctx, "join.segments_read")
    if not trace or trace["busy_s"] <= 0 or not peaks or not points \
            or segments is None or "predicate_plane_bytes" not in cfg \
            or "refine_plane_bytes" not in cfg:
        return None
    need = bytes_needed(points, segments, cfg["predicate_plane_bytes"],
                        cfg["refine_plane_bytes"])
    least_share = need / ctx["seconds"] / peaks["hbm_bytes_per_s"]
    return 100.0 * least_share / (trace["busy_s"] / trace["window_s"])

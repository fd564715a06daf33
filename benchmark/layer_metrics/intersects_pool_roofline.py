"""The pool kernel's share of the HBM roofline, in percent.

layer: staged kernels (index/scan.py) · source: device_trace · moves: qps
Bytes the window's polygon counts had to read from the segment pool: the
segments in the spans of their candidate blocks (counter
``refine.segments_tested``, ``before`` → ``after``) times the bytes a
segment's test needs, as the configuration lists them (``refine_plane_bytes``:
two endpoints as four f32), never what the kernel happens to read. Least
time a second of the window = bytes / window seconds / peak HBM bytes/s;
share = that / the traced slice's busy share. HBM-bound by this count: the
segment × edge compares run on the VPU, for which no peak is published, so
the share reads low for many-edged polygons. The busy share holds the
envelope scan of the same launches and the program's gathers too, so the
share cannot pass 100. The counter is of the whole window and the busy share
of its 3 s slice: the load is the same closed loop throughout. A program
without the counter reads None."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _window  # noqa: E402


def bytes_needed(segments: int, plane_bytes: dict) -> int:
    return segments * sum(plane_bytes.values())


def read(ctx: dict):
    trace, peaks = ctx.get("trace"), ctx.get("peaks")
    planes = ctx["config"].get("refine_plane_bytes")
    segments = _window.counter_delta(ctx, "refine.segments_tested")
    if not trace or trace["busy_s"] <= 0 or not peaks or not planes \
            or not segments:
        return None
    need = bytes_needed(segments, planes)
    least_share = need / ctx["seconds"] / peaks["hbm_bytes_per_s"]
    return 100.0 * least_share / (trace["busy_s"] / trace["window_s"])

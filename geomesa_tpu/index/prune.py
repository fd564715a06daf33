"""Range-pruned scan execution (host planning side).

≙ the reference's core query model: decompose the query region into at most
``geomesa.scan.ranges.target`` (2000) key ranges and scan only those
(Z3IndexKeySpace.getRanges, /root/reference/geomesa-index-api/src/main/scala/
org/locationtech/geomesa/index/index/z3/Z3IndexKeySpace.scala:162-189;
QueryProperties.scala:22). Here the "tablet ranges" become row intervals of
the index's sorted order, found by binary search over the host-resident
sorted key arrays, then converted to fixed-size *blocks* — small int32 ids
the device turns back into row indices with an iota, so a pruned scan ships
a few hundred ints instead of millions of row positions. The device kernel
gathers candidate blocks and re-applies the full exact mask, so the cover
only ever needs to be a superset (block granularity and cover slop are
harmless).

One cover per scan: the range budget is for the whole decomposition, however
many boxes it unions, as upstream's ``geomesa.scan.ranges.target`` is per
scan. A plan's boxes are one scan (``candidate_blocks``); so are all the
boxes of a scheduler dispatch, which the collector covers together once
(``BaseSpatialIndex.cover_blocks``), not request by request.

The pruned path is preferred when the candidate fraction of the scan (for a
dispatch, of the union of its boxes) is small (``PRUNE_MAX_FRACTION``);
above that a full-table fused mask scan is faster than gathering (sequential
HBM beats scattered gathers once most blocks are touched anyway).
"""

from __future__ import annotations

import sys
from typing import List, Optional, Sequence, Tuple

import numpy as np

from geomesa_tpu import config
from geomesa_tpu.curves.ranges import IndexRange

# MAX_RANGES / BLOCK_SIZE / PRUNE_MAX_FRACTION resolve through the config
# registry on EVERY access (PEP 562 module __getattr__ below), so env/set()
# overrides take effect at runtime; tests may still monkeypatch the module
# attribute directly (a real attribute shadows __getattr__).
#   MAX_RANGES         ≙ geomesa.scan.ranges.target (QueryProperties.scala:22)
#   BLOCK_SIZE         rows per gather block (coalesced HBM reads vs slop)
#   PRUNE_MAX_FRACTION above this candidate fraction a full scan wins
_CONFIG_ATTRS = {
    "MAX_RANGES": "SCAN_RANGES_TARGET",
    "BLOCK_SIZE": "PRUNE_BLOCK",
    "PRUNE_MAX_FRACTION": "PRUNE_MAX_FRACTION",
}


def __getattr__(name: str):
    prop = _CONFIG_ATTRS.get(name)
    if prop is None:
        raise AttributeError(name)
    return getattr(config, prop).get()
# cap on per-query interval decomposition (bins), mirroring the reference's
# per-epoch range decomposition limits
MAX_BINS = 512


def ranges_to_slices(sorted_keys: np.ndarray,
                     ranges,
                     base: int = 0,
                     lo: int = 0,
                     hi: Optional[int] = None) -> np.ndarray:
    """Inclusive key ranges → [lo, hi) row slices via binary search over one
    contiguous segment of a sorted key array. Returns (S, 2) int64.

    ``ranges``: a Sequence[IndexRange], or the array form — a (lo, hi, ...)
    tuple of int64 arrays (the hot path: sfc.ranges_arrays feeds this with
    no per-range Python objects)."""
    if hi is None:
        hi = len(sorted_keys)
    if (isinstance(ranges, tuple) and len(ranges) >= 2
            and isinstance(ranges[0], np.ndarray)):
        # the array form; a tuple OF IndexRange objects (legal under the
        # Sequence contract) falls through to the object branch below
        lowers, uppers = ranges[0], ranges[1]
    elif ranges:
        lowers = np.fromiter((r.lower for r in ranges), np.int64, len(ranges))
        uppers = np.fromiter((r.upper for r in ranges), np.int64, len(ranges))
    else:
        lowers = uppers = np.empty(0, np.int64)
    if len(lowers) == 0 or lo >= hi:
        return np.empty((0, 2), dtype=np.int64)
    seg = sorted_keys[lo:hi]
    starts = np.searchsorted(seg, lowers, side="left") + lo + base
    stops = np.searchsorted(seg, uppers, side="right") + lo + base
    keep = stops > starts
    return np.stack([starts[keep], stops[keep]], axis=1)


def slices_to_blocks(slices: np.ndarray, n_rows: int,
                     block_size: Optional[int] = None) -> Optional[np.ndarray]:
    """Row slices → sorted unique block ids (int32). None when the expansion
    would be degenerate (no slices). ``block_size`` defaults to the *current*
    module BLOCK_SIZE (late-bound so runtime/test overrides take effect)."""
    if block_size is None:
        block_size = sys.modules[__name__].BLOCK_SIZE
    if len(slices) == 0:
        return None
    last = max(0, (n_rows - 1) // block_size)
    lo_b = np.minimum(slices[:, 0] // block_size, last)
    hi_b = np.minimum((slices[:, 1] - 1) // block_size, last)
    counts = (hi_b - lo_b + 1)
    total = int(counts.sum())
    # expand each [lo_b, hi_b] run with a ragged iota
    offsets = np.repeat(np.cumsum(counts) - counts, counts)
    ids = np.repeat(lo_b, counts) + (np.arange(total) - offsets)
    return np.unique(ids).astype(np.int32)


def candidate_stats(slices: np.ndarray, blocks: Optional[np.ndarray],
                    n_rows: int, block_size: Optional[int] = None) -> dict:
    """Explain payload for a pruned plan."""
    if block_size is None:
        block_size = sys.modules[__name__].BLOCK_SIZE
    rows = int((slices[:, 1] - slices[:, 0]).sum()) if len(slices) else 0
    nb = 0 if blocks is None else len(blocks)
    return {
        "candidate_rows": rows,
        "candidate_blocks": nb,
        "scanned_rows": nb * block_size,
        "scanned_fraction": round(nb * block_size / max(1, n_rows), 5),
    }


def bin_windows(intervals, period) -> Optional[List[Tuple[int, Tuple[int, int]]]]:
    """Decompose time intervals into per-bin in-bin offset windows:
    [(bin, (t_lo, t_hi))...], t in period offset units, inclusive.

    ≙ Z3IndexKeySpace.getIndexValues' per-epoch time decomposition
    (Z3IndexKeySpace.scala:98-160). None when the decomposition explodes
    (> MAX_BINS bins) — callers fall back to the unpruned scan.
    """
    from geomesa_tpu.curves.binnedtime import max_offset, time_to_binned_time

    out: List[Tuple[int, Tuple[int, int]]] = []
    mo = max_offset(period) - 1
    for lo, hi in intervals:
        blo, olo = time_to_binned_time(int(lo), period)
        bhi, ohi = time_to_binned_time(int(hi), period)
        blo, olo, bhi, ohi = int(blo), int(olo), int(bhi), int(ohi)
        if bhi - blo + 1 > MAX_BINS or len(out) + (bhi - blo + 1) > MAX_BINS:
            return None
        for b in range(blo, bhi + 1):
            t0 = olo if b == blo else 0
            t1 = ohi if b == bhi else mo
            out.append((b, (t0, min(t1, mo))))
    return out


class BinSegments:
    """Per-bin contiguous row segments of an epoch-major sorted index
    (lazy; one linear pass over the sorted bins array, cached)."""

    def __init__(self, sorted_bins: np.ndarray):
        bins = np.asarray(sorted_bins)
        if len(bins) == 0:
            self.bins = np.empty(0, np.int64)
            self.starts = np.zeros(1, np.int64)
            return
        change = np.flatnonzero(np.diff(bins)) + 1
        self.bins = np.concatenate([[bins[0]], bins[change]]).astype(np.int64)
        self.starts = np.concatenate(
            [[0], change, [len(bins)]]).astype(np.int64)

    def segment(self, b: int) -> Tuple[int, int]:
        """[lo, hi) rows of bin ``b`` (empty slice when absent)."""
        i = int(np.searchsorted(self.bins, b))
        if i == len(self.bins) or self.bins[i] != b:
            return 0, 0
        return int(self.starts[i]), int(self.starts[i + 1])

    def all_bins(self) -> np.ndarray:
        return self.bins


# -- the join's gate ----------------------------------------------------------

# slack of the gate's envelope tests in degrees: a tile's envelope is of the
# f32 planes, within 2.5e-5 of the f64 coordinates (scan._IN_DELTA), and a
# segment's y-tie band is 3e-5 (scan._DY_BAND)
GATE_SLACK = 1e-3


def gate_blocks(env: dict, blocks: Optional[np.ndarray],
                windows: Optional[np.ndarray], boxes) -> np.ndarray:
    """Sorted ids of the gather blocks that may hold a row of a plan: those
    of the cover's ``blocks`` (None: the whole table) whose time span meets
    one of the plan's ``windows`` ((T, 4) int32 bin/offset bounds, pads with
    bin_lo > bin_hi) and whose envelope meets one of its ``boxes``
    (user-space (xmin, ymin, xmax, ymax)). ``env``: the index's
    ``join_envelopes``. A superset: the kernel applies the exact mask to
    what it gathers."""
    if blocks is None:
        blocks = np.arange(len(env["xmin"]))
    blocks = np.asarray(blocks, dtype=np.int64)
    if windows is not None and "tmin" in env:
        w = windows[windows[:, 0] <= windows[:, 2]].astype(np.int64)
        lo, hi = (w[:, 0] << 32) + w[:, 1], (w[:, 2] << 32) + w[:, 3]
        blocks = blocks[np.any((env["tmax"][blocks, None] >= lo)
                               & (env["tmin"][blocks, None] <= hi), axis=1)]
    if boxes:
        b = np.asarray(boxes, dtype=np.float64)
        x0, x1 = env["xmin"][blocks].min(1), env["xmax"][blocks].max(1)
        y0, y1 = env["ymin"][blocks].min(1), env["ymax"][blocks].max(1)
        blocks = blocks[np.any(
            (x1[:, None] + GATE_SLACK >= b[:, 0])
            & (x0[:, None] - GATE_SLACK <= b[:, 2])
            & (y1[:, None] + GATE_SLACK >= b[:, 1])
            & (y0[:, None] - GATE_SLACK <= b[:, 3]), axis=1)]
    return blocks


def gate_slabs(env: dict, blocks: np.ndarray, poly_env: np.ndarray,
               ykey: np.ndarray, rise: np.ndarray, step: int) -> np.ndarray:
    """The join's pairs, (k, 5) int64 [tile, first chunk, first segment in
    it, past the last, polygon]: every tile of the candidate ``blocks`` (a block's rows in
    ascending y, ``env``'s tiles; tile t of ``blocks[j]`` is j * tiles a
    block + t) against every polygon whose envelope (``poly_env``: (P, 4)
    f64) its own meets, with the span of that polygon's y-sorted segments
    (``ykey``, ``rise``: geom_batch.slab_order) that can reach into the
    tile's y-range, as the kernel reads it: from the chunk that starts at
    the last multiple of ``step`` at or before the span's first segment
    (``device.segments_by_y``). In two steps, so that P polygons cost a tile x P test
    only where the tile's block met the polygon."""
    from geomesa_tpu.filter.geom_batch import KEY_ROW
    if len(blocks) == 0 or len(poly_env) == 0:
        return np.empty((0, 5), dtype=np.int64)
    x0, x1 = env["xmin"][blocks] - GATE_SLACK, env["xmax"][blocks] + GATE_SLACK
    y0, y1 = env["ymin"][blocks] - GATE_SLACK, env["ymax"][blocks] + GATE_SLACK
    per = x0.shape[1]
    px0, py0, px1, py1 = poly_env.T
    j, p = np.nonzero((x1.max(1)[:, None] >= px0) & (x0.min(1)[:, None] <= px1)
                      & (y1.max(1)[:, None] >= py0)
                      & (y0.min(1)[:, None] <= py1))
    tile = (j[:, None] * per + np.arange(per)).reshape(-1)
    p = np.repeat(p, per)
    x0, x1, y0, y1 = (a.reshape(-1) for a in (x0, x1, y0, y1))
    keep = ((x1[tile] >= px0[p]) & (x0[tile] <= px1[p])
            & (y1[tile] >= py0[p]) & (y0[tile] <= py1[p]))
    tile, p = tile[keep], p[keep]
    at = p * KEY_ROW + 90.0
    lo = np.searchsorted(ykey, at + np.maximum(y0[tile] - rise[p], -90.0))
    hi = np.searchsorted(ykey, at + np.minimum(y1[tile], 90.0), side="right")
    keep = hi > lo
    first = lo // step
    return np.stack([tile, first, lo - first * step, hi - first * step, p],
                    axis=1)[keep]

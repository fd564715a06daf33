"""Curve-layer tests: golden values + invariants, in the spirit of the
reference's Z3SFCTest / XZ2SFCTest (SURVEY.md §4: index/invert round-trips,
range covers contain indexed points)."""

from collections import deque

import numpy as np
import pytest

from geomesa_tpu.curves import (
    BitNormalizedDimension,
    TimePeriod,
    XZ2SFC,
    XZ3SFC,
    Z2SFC,
    Z3SFC,
    max_offset,
    merge_ranges,
    time_to_binned_time,
    binned_time_to_millis,
)
from geomesa_tpu.curves.ranges import IndexRange
from geomesa_tpu.curves import zorder
from geomesa_tpu.metrics import REGISTRY

RNG = np.random.default_rng(42)


class TestZOrder:
    def test_z2_roundtrip(self):
        x = RNG.integers(0, 1 << 31, 1000)
        y = RNG.integers(0, 1 << 31, 1000)
        z = zorder.z2_encode(x, y)
        xd, yd = zorder.z2_decode(z)
        np.testing.assert_array_equal(xd, x)
        np.testing.assert_array_equal(yd, y)

    def test_z2_golden(self):
        # interleave with x in even bits: (x=1,y=0) -> 1, (x=0,y=1) -> 2
        assert int(zorder.z2_encode(1, 0)) == 1
        assert int(zorder.z2_encode(0, 1)) == 2
        assert int(zorder.z2_encode(3, 3)) == 15
        assert int(zorder.z2_encode(2**31 - 1, 2**31 - 1)) == 2**62 - 1

    def test_z3_roundtrip(self):
        x = RNG.integers(0, 1 << 21, 1000)
        y = RNG.integers(0, 1 << 21, 1000)
        t = RNG.integers(0, 1 << 21, 1000)
        z = zorder.z3_encode(x, y, t)
        xd, yd, td = zorder.z3_decode(z)
        np.testing.assert_array_equal(xd, x)
        np.testing.assert_array_equal(yd, y)
        np.testing.assert_array_equal(td, t)

    def test_z3_golden(self):
        assert int(zorder.z3_encode(1, 0, 0)) == 1
        assert int(zorder.z3_encode(0, 1, 0)) == 2
        assert int(zorder.z3_encode(0, 0, 1)) == 4
        assert int(zorder.z3_encode(2**21 - 1, 2**21 - 1, 2**21 - 1)) == 2**63 - 1

    def test_z2_order_locality(self):
        # monotone along each dim when the other is fixed
        z = zorder.z2_encode(np.arange(100), np.zeros(100, dtype=np.int64))
        assert np.all(np.diff(z) > 0)


class TestNormalize:
    def test_golden_lon(self):
        # floor-normalize semantics (NormalizedDimension.scala:67-68)
        lon = BitNormalizedDimension(-180.0, 180.0, 21)
        assert int(lon.normalize(-180.0)) == 0
        assert int(lon.normalize(180.0)) == 2**21 - 1  # x >= max -> maxIndex
        assert int(lon.normalize(0.0)) == 2**20
        cell = 360.0 / 2**21
        assert int(lon.normalize(-180.0 + 1.5 * cell)) == 1

    def test_denormalize_centers(self):
        # +0.5 bin centers (NormalizedDimension.scala:70-71)
        lat = BitNormalizedDimension(-90.0, 90.0, 21)
        cell = 180.0 / 2**21
        assert float(lat.denormalize(0)) == pytest.approx(-90.0 + 0.5 * cell)
        assert float(lat.denormalize(2**21 - 1)) == pytest.approx(90.0 - 0.5 * cell)

    def test_roundtrip_within_cell(self):
        lon = BitNormalizedDimension(-180.0, 180.0, 21)
        x = RNG.uniform(-180, 180, 1000)
        back = lon.denormalize(lon.normalize(x))
        assert np.max(np.abs(back - x)) <= 360.0 / 2**21


class TestBinnedTime:
    def test_max_offsets(self):
        # BinnedTime.scala:148-156
        assert max_offset(TimePeriod.DAY) == 86_400_000
        assert max_offset(TimePeriod.WEEK) == 604_800
        assert max_offset(TimePeriod.MONTH) == 2_678_400
        assert max_offset(TimePeriod.YEAR) == 527_050

    def test_day_golden(self):
        # 2020-01-01T12:00:00Z = 18262 days, 12h into the day
        ms = np.datetime64("2020-01-01T12:00:00", "ms").astype(np.int64)
        b, o = time_to_binned_time(ms, TimePeriod.DAY)
        assert int(b) == 18262
        assert int(o) == 12 * 3600 * 1000

    def test_week_golden(self):
        # epoch was a Thursday; 1970-01-08T00:00 = exactly 1 week
        ms = np.datetime64("1970-01-08T00:00:00", "ms").astype(np.int64)
        b, o = time_to_binned_time(ms, TimePeriod.WEEK)
        assert (int(b), int(o)) == (1, 0)

    def test_month_year_golden(self):
        ms = np.datetime64("2020-03-01T00:00:30", "ms").astype(np.int64)
        b, o = time_to_binned_time(ms, TimePeriod.MONTH)
        assert int(b) == (2020 - 1970) * 12 + 2
        assert int(o) == 30
        b, o = time_to_binned_time(ms, TimePeriod.YEAR)
        assert int(b) == 50

    @pytest.mark.parametrize("period", list(TimePeriod))
    def test_roundtrip(self, period):
        unit_ms = {"day": 1, "week": 1000, "month": 1000, "year": 60_000}[period.value]
        ms = RNG.integers(0, np.datetime64("2038-01-01").astype("datetime64[ms]").astype(np.int64), 500)
        ms = (ms // unit_ms) * unit_ms  # truncate to offset resolution
        b, o = time_to_binned_time(ms, period)
        back = binned_time_to_millis(b, o, period)
        np.testing.assert_array_equal(back, ms)
        assert np.all(o >= 0) and np.all(o < max_offset(period) * (1000 if period is TimePeriod.DAY else 1))


class TestZ2SFC:
    def test_roundtrip(self):
        sfc = Z2SFC()
        x = RNG.uniform(-180, 180, 500)
        y = RNG.uniform(-90, 90, 500)
        xb, yb = sfc.invert(sfc.index(x, y))
        assert np.max(np.abs(xb - x)) <= 360.0 / 2**31
        assert np.max(np.abs(yb - y)) <= 180.0 / 2**31

    def test_strict_bounds(self):
        sfc = Z2SFC()
        with pytest.raises(ValueError):
            sfc.index(181.0, 0.0)
        # lenient clamps (Z2SFC.scala:37-41)
        assert int(sfc.index(181.0, 0.0, lenient=True)) == int(sfc.index(180.0, 0.0))

    def test_ranges_cover_points(self):
        sfc = Z2SFC()
        box = (-10.0, -10.0, 10.0, 10.0)
        ranges = sfc.ranges([box], max_ranges=2000)
        assert 0 < len(ranges) <= 2000
        x = RNG.uniform(-10, 10, 300)
        y = RNG.uniform(-10, 10, 300)
        zs = sfc.index(x, y)
        lowers = np.array([r.lower for r in ranges])
        uppers = np.array([r.upper for r in ranges])
        for z in zs:
            i = np.searchsorted(lowers, z, side="right") - 1
            assert i >= 0 and z <= uppers[i], f"z {z} not covered"

    def test_contained_ranges_are_tight(self):
        sfc = Z2SFC()
        box = (-10.0, -10.0, 10.0, 10.0)
        xlo, ylo = sfc.normalize(box[0], box[1])
        xhi, yhi = sfc.normalize(box[2], box[3])
        for r in sfc.ranges([box], max_ranges=500):
            if not r.contained:
                continue
            for z in (r.lower, r.upper, (r.lower + r.upper) // 2):
                xd, yd = zorder.z2_decode(z)
                assert xlo <= xd <= xhi and ylo <= yd <= yhi


class TestZ3SFC:
    def test_roundtrip(self):
        sfc = Z3SFC.apply(TimePeriod.WEEK)
        x = RNG.uniform(-180, 180, 500)
        y = RNG.uniform(-90, 90, 500)
        t = RNG.integers(0, max_offset(TimePeriod.WEEK), 500)
        xb, yb, tb = sfc.invert(sfc.index(x, y, t))
        assert np.max(np.abs(xb - x)) <= 360.0 / 2**21
        assert np.max(np.abs(yb - y)) <= 180.0 / 2**21
        assert np.max(np.abs(tb - t)) <= max_offset(TimePeriod.WEEK) / 2**21 + 1

    def test_ranges_cover(self):
        sfc = Z3SFC.apply(TimePeriod.WEEK)
        ranges = sfc.ranges([(-10.0, -10.0, 10.0, 10.0)], [(0, 100_000)], max_ranges=2000)
        assert ranges
        x = RNG.uniform(-10, 10, 200)
        y = RNG.uniform(-10, 10, 200)
        t = RNG.integers(0, 100_000, 200)
        zs = sfc.index(x, y, t)
        lowers = np.array([r.lower for r in ranges])
        uppers = np.array([r.upper for r in ranges])
        for z in zs:
            i = np.searchsorted(lowers, z, side="right") - 1
            assert i >= 0 and z <= uppers[i]


class TestMergeRanges:
    def test_merge(self):
        rs = [IndexRange(5, 10), IndexRange(0, 4), IndexRange(11, 12), IndexRange(20, 30)]
        merged = merge_ranges(rs)
        assert [(r.lower, r.upper) for r in merged] == [(0, 12), (20, 30)]


class TestXZ2SFC:
    def test_point_index_is_max_length(self):
        sfc = XZ2SFC.apply(12)
        # a degenerate bbox (a point) always gets the max sequence length
        code = sfc.index_bbox(1.0, 1.0, 1.0, 1.0)
        assert code.shape == (1,)
        assert int(code[0]) > 0

    def test_query_finds_intersecting_bboxes(self):
        # core XZ guarantee: any stored bbox intersecting the query window has
        # its code covered by the query ranges
        sfc = XZ2SFC.apply(12)
        n = 300
        cx = RNG.uniform(-170, 170, n)
        cy = RNG.uniform(-80, 80, n)
        w = RNG.uniform(0, 5, n)
        h = RNG.uniform(0, 5, n)
        codes = sfc.index_bbox(cx - w, cy - h, cx + w, cy + h)
        window = (-20.0, -20.0, 20.0, 20.0)
        ranges = sfc.ranges_bbox([window])
        lowers = np.array([r.lower for r in ranges])
        uppers = np.array([r.upper for r in ranges])
        intersects = (cx - w <= 20) & (cx + w >= -20) & (cy - h <= 20) & (cy + h >= -20)
        for i in range(n):
            if not intersects[i]:
                continue
            z = codes[i]
            j = np.searchsorted(lowers, z, side="right") - 1
            assert j >= 0 and z <= uppers[j], f"bbox {i} missed"

    def test_vectorized_matches_scalar(self):
        sfc = XZ2SFC.apply(12)
        boxes = [(-50.0, -50.0, -49.0, -49.5), (0.0, 0.0, 10.0, 10.0), (179.0, 89.0, 180.0, 90.0)]
        batch = sfc.index_bbox(
            np.array([b[0] for b in boxes]), np.array([b[1] for b in boxes]),
            np.array([b[2] for b in boxes]), np.array([b[3] for b in boxes]))
        for i, b in enumerate(boxes):
            single = sfc.index_bbox(*b)
            assert int(single[0]) == int(batch[i])


class TestManyBoxCovers:
    """A cover of many boxes normalises them as arrays and tests a cell
    against all of them at once; the per-corner scalar form is the
    reference."""

    BOXES = [(-10.0, -5.0, 10.0, 5.0), (20.5, 20.25, 30.0, 31.0),
             (-180.0, -90.0, -179.0, -89.0), (179.0, 89.0, 180.0, 90.0),
             (12.345678, -45.6789, 12.345679, -45.6788)]

    def test_z3_corners_match_scalar_normalize(self):
        from geomesa_tpu.curves.ranges import zranges_3d_arrays
        sfc = Z3SFC.apply(TimePeriod.WEEK)
        windows = [(0, 1000), (5000, 604799)]
        rows = []
        for xmin, ymin, xmax, ymax in self.BOXES:
            for t0, t1 in windows:
                rows.append((int(sfc.lon.normalize(xmin)),
                             int(sfc.lat.normalize(ymin)),
                             int(sfc.time.normalize(t0)),
                             int(sfc.lon.normalize(xmax)),
                             int(sfc.lat.normalize(ymax)),
                             int(sfc.time.normalize(t1))))
        want = zranges_3d_arrays(rows, sfc.precision, 2000, 64)
        got = sfc.ranges_arrays(self.BOXES, windows, max_ranges=2000)
        for a, b in zip(got, want):
            np.testing.assert_array_equal(a, b)

    def test_z2_corners_match_scalar_normalize(self):
        from geomesa_tpu.curves.ranges import zranges_2d_arrays
        sfc = Z2SFC()
        rows = []
        for xmin, ymin, xmax, ymax in self.BOXES:
            xlo, ylo = sfc.normalize(xmin, ymin)
            xhi, yhi = sfc.normalize(xmax, ymax)
            rows.append((int(xlo), int(ylo), int(xhi), int(yhi)))
        want = zranges_2d_arrays(rows, sfc.precision, 2000, 64)
        got = sfc.ranges_arrays(self.BOXES, max_ranges=2000)
        for a, b in zip(got, want):
            np.testing.assert_array_equal(a, b)
        with pytest.raises(ValueError):
            sfc.ranges_arrays([(-190.0, 0.0, 0.0, 1.0)])

    def test_xz2_union_cover_is_the_union_of_the_covers(self):
        """With no range budget in the way, the cover of many boxes holds
        exactly the codes of the boxes' own covers (a cell is tested against
        all windows in one pass)."""
        sfc = XZ2SFC(g=6)

        def codes(ranges):
            return {c for r in ranges for c in range(r.lower, r.upper + 1)}

        boxes = self.BOXES[:4] + [(-60.0, -30.0, 45.0, 50.0)]
        want = set()
        for box in boxes:
            want |= codes(sfc.ranges_bbox([box]))
        assert codes(sfc.ranges_bbox(boxes)) == want and want


def _xz_ranges_walk(sfc, queries, max_ranges=None):
    """The reference's XZ query decomposition, cell by cell (XZ2SFC.scala
    ``ranges``: a breadth-first queue, one tree cell an iteration, the
    sequence code walked from the root for every cell). It served
    ``XZSFC.ranges`` until the by-level numpy form replaced it, and stays
    here as that form's oracle. Returns (merged ranges, cells visited)."""
    max_ranges = max_ranges or (1 << 62)
    q = np.asarray(queries, dtype=np.float64).reshape(-1, 2 * sfc.dims)
    wmins, wmaxs = sfc._normalize(q[:, : sfc.dims], q[:, sfc.dims:],
                                  lenient=False)   # (W, D) each

    out = []

    def seq_code(point, length):
        cs = 0
        lo = np.zeros(sfc.dims)
        hi = np.ones(sfc.dims)
        for i in range(length):
            center = (lo + hi) / 2.0
            quadrant = 0
            for d in range(sfc.dims):
                if point[d] >= center[d]:
                    quadrant |= 1 << d
                    lo[d] = center[d]
                else:
                    hi[d] = center[d]
            cs += 1 + quadrant * sfc._seq_term(i)
        return cs

    def emit(cell_lo, level, contained):
        lo_code = seq_code(cell_lo, level)
        if contained:
            # lemma 3, with the reference's over-inclusive upper code
            hi_code = lo_code + sfc._seq_term(level - 1)
        else:
            hi_code = lo_code
        out.append(IndexRange(lo_code, hi_code, contained))

    queue = deque(
        (np.array([(c >> d) & 1 for d in range(sfc.dims)]) * 0.5, 1)
        for c in range(sfc.fan))
    cells = 0
    while queue:
        cell_lo, level = queue.popleft()
        cells += 1
        side = 0.5 ** level
        ext_hi = cell_lo + 2 * side  # enlarged element upper corner
        if ((wmins <= cell_lo) & (wmaxs >= ext_hi)).all(axis=1).any():
            emit(cell_lo, level, True)
        elif ((wmaxs >= cell_lo) & (wmins <= ext_hi)).all(axis=1).any():
            emit(cell_lo, level, False)
            if level < sfc.g and len(out) < max_ranges:
                half = side / 2.0
                for c in range(sfc.fan):
                    child = cell_lo + np.array(
                        [((c >> d) & 1) * half for d in range(sfc.dims)])
                    queue.append((child, level + 1))
            elif level < sfc.g:
                # budget exhausted: cover the whole subtree coarsely
                lo_code = seq_code(cell_lo, level)
                out.append(IndexRange(
                    lo_code, lo_code + sfc._seq_term(level - 1), False))
    return merge_ranges(out), cells


def _xz_cover_cells() -> int:
    """The counter ``xz.cover.cells``: cells the XZ covers so far tested."""
    return REGISTRY.snapshot()["counters"].get("xz.cover.cells", 0)


_WEEK_S = float(max_offset(TimePeriod.WEEK))
_XZ_CURVES = {"xz2-g12": lambda: XZ2SFC(g=12),
              "xz2-g6": lambda: XZ2SFC(g=6),
              "xz3-g12": lambda: XZ3SFC(g=12, period=TimePeriod.WEEK)}


def _xz_windows(name: str, dims: int):
    """The window sets of the parity cases as (xmin, ymin, xmax, ymax) boxes
    (XZ3 adds a time window to each): ``cell-N`` are N windows of the OSM
    cell's sizes (0.1-1 degree) around one urban cluster."""
    rng = np.random.default_rng(sum(map(ord, name)))
    if name.startswith("cell-"):
        n = int(name.split("-")[1])
        cx = 13.4 + rng.uniform(-2, 2, n)
        cy = 52.5 + rng.uniform(-2, 2, n)
        w = rng.uniform(0.1, 1.0, (2, n))
        boxes = np.stack([cx, cy, cx + w[0], cy + w[1]], axis=1)
    else:
        boxes = np.array({
            "point": [(-73.9857, 40.7484, -73.9857, 40.7484)],
            "bounds": [(179.0, 89.0, 180.0, 90.0),
                       (-180.0, -90.0, -179.0, -89.0)],
            "world": [(-180.0, -90.0, 180.0, 90.0)],
        }[name])
    if dims == 2:
        return boxes
    if name == "world":
        t0, t1 = np.zeros(1), np.full(1, _WEEK_S)
    elif name == "point":
        t0 = t1 = np.full(1, 86400.0)
    else:   # a quarter of an hour to an hour, somewhere in the week
        t0 = rng.uniform(0, _WEEK_S - 3600, len(boxes))
        t1 = t0 + rng.uniform(900, 3600, len(boxes))
    return np.column_stack([boxes[:, :2], t0, boxes[:, 2:], t1])


class TestXZRangesParity:
    """``XZSFC.ranges_arrays`` takes a tree level a step; the cell-by-cell
    walk it replaced is the oracle, and the merged ranges are equal, the
    range budget's coarse covers included."""

    @pytest.mark.parametrize("max_ranges", [1, 7, 64, 2000, None])
    @pytest.mark.parametrize(
        "windows", ["cell-1", "cell-2", "cell-5", "cell-16", "point",
                    "bounds", "world"])
    @pytest.mark.parametrize("curve", list(_XZ_CURVES))
    def test_ranges_equal_the_walk(self, curve, windows, max_ranges):
        sfc = _XZ_CURVES[curve]()
        if windows == "world" and max_ranges is None and sfc.g > 6:
            # the world's edge cells overlap at every level: unbudgeted, the
            # walk visits 4^g (8^g) of them. 20,000 spends as 2,000 does
            max_ranges = 20_000
        qs = _xz_windows(windows, sfc.dims)
        want, cells = _xz_ranges_walk(sfc, qs, max_ranges)
        before = _xz_cover_cells()
        lo, hi, cont = sfc.ranges_arrays(qs, max_ranges)
        assert _xz_cover_cells() - before == cells
        assert lo.dtype == hi.dtype == np.int64 and cont.dtype == bool
        assert want and len(lo) == len(want)
        np.testing.assert_array_equal(lo, [r.lower for r in want])
        np.testing.assert_array_equal(hi, [r.upper for r in want])
        np.testing.assert_array_equal(cont, [r.contained for r in want])
        assert sfc.ranges(qs, max_ranges) == want

    @pytest.mark.parametrize("curve", list(_XZ_CURVES))
    def test_window_out_of_bounds_raises(self, curve):
        sfc = _XZ_CURVES[curve]()
        qs = _xz_windows("cell-2", sfc.dims)
        qs[1, 0], qs[1, sfc.dims] = -181.0, -179.5
        with pytest.raises(ValueError):
            sfc.ranges_arrays(qs)
        with pytest.raises(ValueError):
            sfc.ranges(qs, 64)

    def test_ranges_bbox_is_the_object_form(self):
        sfc = XZ2SFC.apply(12)
        boxes = [tuple(b) for b in _xz_windows("cell-5", 2)]
        want, _ = _xz_ranges_walk(sfc, boxes, 2000)
        assert sfc.ranges_bbox(boxes, max_ranges=2000) == want
        assert all(isinstance(r, IndexRange) for r in want)

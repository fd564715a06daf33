"""One range decomposition per dispatch (serve/scheduler.py ``_cover_group``
over index/spatial.py ``cover_blocks``): the cover of a fused group is
computed once, for the union of its members' boxes. Every index class, each
answer against a numpy reference over the raw columns."""

import time

import numpy as np
import pytest

from geomesa_tpu import config
from geomesa_tpu.curves import XZ2SFC, XZ3SFC
from geomesa_tpu.datastore import TpuDataStore
from geomesa_tpu.features.geometry import LINESTRING, GeometryArray
from geomesa_tpu.features.table import FeatureTable
from geomesa_tpu.index import prune
from geomesa_tpu.obs.flight import RECORDER
from geomesa_tpu.serve.scheduler import QueryScheduler, StoreBinding

from test_curves import _xz_cover_cells, _xz_ranges_walk

N = 200_000
DURING = "dtg DURING 2020-01-05T00:00:00Z/2020-01-12T00:00:00Z"
T_LO = np.datetime64("2020-01-05", "ms").astype(np.int64)
T_HI = np.datetime64("2020-01-12", "ms").astype(np.int64)
KINDS = {"z3": ("v:Int,dtg:Date,*geom:Point;geomesa.z3.interval=week", 6.0),
         "z2": ("v:Int,*geom:Point", 6.0),
         "xz3": ("v:Int,dtg:Date,*geom:LineString;geomesa.z3.interval=week",
                 1.0),
         "xz2": ("v:Int,*geom:LineString", 1.0)}
WINDOW_S = 0.3


class _Setup:
    """One store of ``N`` rows served by the index ``kind``, its scheduler
    (a fixed window, so what is submitted together is one batch) and the raw
    columns for the numpy reference."""

    def __init__(self, kind: str):
        spec, self.side = KINDS[kind]
        rng = np.random.default_rng(sum(map(ord, kind)))
        self.kind = kind
        self.points = kind.startswith("z")
        self.temporal = kind.endswith("3")
        self.x = rng.uniform(-60, 59, N)
        self.y = rng.uniform(-40, 39, N)
        self.v = rng.integers(0, 100, N).astype(np.int32)
        base = np.datetime64("2020-01-01T00:00:00", "ms").astype(np.int64)
        self.dtg = base + rng.integers(0, 30 * 86400000, N)
        cols = {"v": self.v}
        if self.temporal:
            cols["dtg"] = self.dtg
        if self.points:
            cols["geom"] = (self.x, self.y)
        else:   # extents: short diagonals, envelope (x, y, x + .5, y + .4)
            cols["geom"] = GeometryArray.from_shapes(
                [(LINESTRING, [[self.x[i], self.y[i]],
                               [self.x[i] + 0.5, self.y[i] + 0.4]])
                 for i in range(N)])
        self.ds = TpuDataStore()
        self.ds.create_schema("t", spec)
        self.ds.load("t", FeatureTable.build(self.ds.get_schema("t"), cols))
        self.planner = self.ds.planner("t")
        self.index = next(i for i in self.planner.indexes if i.name == kind)
        self.sched = QueryScheduler(
            StoreBinding(self.ds), flush_size=64, window_us=WINDOW_S * 1e6,
            min_window_us=WINDOW_S * 1e6)

    def box(self, i: int, side=None):
        """The i-th of a run of distinct boxes across the data."""
        side = self.side if side is None else side
        x0, y0 = -58 + 1.7 * (i % 64), -38 + 1.1 * (i % 64) + 0.01 * i
        return (x0, y0, x0 + side, y0 + side)

    def query(self, box) -> str:
        q = "BBOX(geom, {}, {}, {}, {})".format(*box)
        if self.temporal:
            q += " AND " + DURING
        return q + " AND v > 5"

    def mask(self, box) -> np.ndarray:
        """The exact predicate of ``query(box)`` over the raw columns."""
        xmin, ymin, xmax, ymax = box
        if self.points:
            m = (self.x >= xmin) & (self.x <= xmax) \
                & (self.y >= ymin) & (self.y <= ymax)
        else:   # envelope overlap
            m = (self.x <= xmax) & (self.x + 0.5 >= xmin) \
                & (self.y <= ymax) & (self.y + 0.4 >= ymin)
        if self.temporal:
            m &= (self.dtg > T_LO) & (self.dtg < T_HI)
        return m & (self.v > 5)

    def batch_events(self, since_ms: float, size: int) -> list:
        """The batch events since ``since_ms``, once they hold ``size``
        requests (an event is recorded as its dispatch resolves)."""
        deadline = time.time() + 10
        while True:
            events = RECORDER.recent(kind="batch", since_ms=since_ms,
                                     limit=1000)
            if sum(e["batch_size"] for e in events) >= size \
                    or time.time() > deadline:
                return events
            time.sleep(0.01)


@pytest.fixture(scope="module", params=list(KINDS))
def setup(request):
    # small blocks: at 200k rows a block of 4,096 is a fiftieth of the
    # table, and no union of 32 boxes stays under PRUNE_MAX_FRACTION
    config.PRUNE_BLOCK.set(256)
    s = _Setup(request.param)
    yield s
    s.sched.shutdown()
    if s.ds._scheduler is not None:
        s.ds._scheduler.shutdown()
    config.PRUNE_BLOCK.unset()


def test_union_cover_holds_every_matching_row(setup):
    """Every row that matches any member's exact predicate lies in a block
    of the one cover made for all the members' boxes."""
    s = setup
    boxes = [s.box(i) for i in range(32)]
    lead = s.planner.plan(s.query(boxes[0]))
    assert lead.index is s.index
    blocks, stats = s.index.cover_blocks(boxes, s.index.cover_intervals(lead))
    assert blocks is not None and len(blocks) > 0, stats
    assert blocks.dtype == np.int32 and np.all(np.diff(blocks) > 0)
    assert stats["cover_boxes"] == 32
    # one scan's range budget for the whole union (two week bins for z3/xz3)
    assert 0 < stats["cover_ranges"] <= 4 * config.SCAN_RANGES_TARGET.get()
    assert len(blocks) * prune.BLOCK_SIZE <= prune.PRUNE_MAX_FRACTION * N
    matched = np.zeros(N, bool)
    for b in boxes:
        matched |= s.mask(b)
    assert matched.sum() > 0
    positions = np.flatnonzero(matched[s.index.perm])   # in sorted order
    assert np.isin(positions // prune.BLOCK_SIZE, blocks).all()


def test_group_of_one_is_the_plans_own_cover(setup):
    s = setup
    q = s.query(s.box(70))
    fresh = s.planner.plan(q)
    want = s.index.candidate_blocks(fresh)
    assert want is not None and len(want) > 0
    r = s.sched.submit("t", q)
    assert r.result(timeout=60) == int(s.mask(s.box(70)).sum())
    assert r.batch_size == 1
    np.testing.assert_array_equal(r.plan.blocks, want)
    # and the body gives the plan-level entry's blocks for the plan's boxes
    got, _ = s.index.cover_blocks(list(fresh.explain["boxes"]),
                                  s.index.cover_intervals(fresh))
    np.testing.assert_array_equal(got, want)


def test_xz_cover_is_the_cell_walks_cover(setup):
    """An extent index hands ``XZSFC.ranges_arrays`` straight to the slice
    search: the blocks and ``cover_ranges`` are those the reference's
    cell-by-cell walk gives through the same search, and ``xz.cover.cells``
    rises by the cells that walk visits. A point index never enters the XZ
    decomposition."""
    s = setup
    boxes = [s.box(3 * i) for i in range(5)]
    intervals = s.index.cover_intervals(s.planner.plan(s.query(boxes[0])))
    c0 = _xz_cover_cells()
    blocks, stats = s.index.cover_blocks(boxes, intervals)
    rose = _xz_cover_cells() - c0
    assert blocks is not None and len(blocks) > 0
    if s.points:
        assert rose == 0
        return
    g = s.index.sft.xz_precision
    sfc = XZ3SFC.apply(g, s.index.period) if s.temporal else XZ2SFC.apply(g)
    keys = s.index.sorted_xz
    if s.temporal:
        segs, covers, slices = s.index._bin_segments(), {}, []
        for b, w in prune.bin_windows(intervals, s.index.period):
            lo, hi = segs.segment(b)
            if lo >= hi:
                continue
            if w not in covers:
                covers[w] = _xz_ranges_walk(
                    sfc, [(x0, y0, float(w[0]), x1, y1, float(w[1]))
                          for x0, y0, x1, y1 in boxes], prune.MAX_RANGES)
            slices.append(prune.ranges_to_slices(keys, covers[w][0],
                                                 lo=lo, hi=hi))
        walked, slices = list(covers.values()), np.concatenate(slices)
        assert len(walked) == 2   # DURING's week spans two bins
    else:
        walked = [_xz_ranges_walk(sfc, boxes, prune.MAX_RANGES)]
        slices = prune.ranges_to_slices(keys, walked[0][0])
    np.testing.assert_array_equal(blocks, prune.slices_to_blocks(slices, N))
    assert stats["cover_ranges"] == sum(len(r) for r, _ in walked)
    assert rose == sum(n for _, n in walked) > 0


def test_mixed_group_past_the_fraction_scans_unpruned_and_exact(setup):
    """Small boxes with one that alone passes PRUNE_MAX_FRACTION: the choice
    is made on the union's rows, so the whole group scans the table in one
    dispatch, and every member counts exactly."""
    s = setup
    boxes = [s.box(100 + i) for i in range(8)] + [(-50.0, -35.0, 40.0, 30.0)]
    big = s.planner.plan(s.query(boxes[-1]))
    assert s.index.candidate_blocks(big) is None            # alone: declined
    assert s.index.candidate_blocks(
        s.planner.plan(s.query(boxes[0]))) is not None      # alone: pruned
    t0 = time.time() * 1000
    reqs = [s.sched.submit("t", s.query(b)) for b in boxes]
    got = [r.result(timeout=60) for r in reqs]
    assert got == [int(s.mask(b).sum()) for b in boxes]
    assert len({r.batch_id for r in reqs}) == 1 and reqs[0].batch_size == 9
    assert {r.rows_scanned for r in reqs} == {N}
    (ev,) = s.batch_events(t0, 9)
    assert ev["kernel"] == f"count_multi.{big.primary_kind}"
    assert ev["cover_boxes"] == 9 and ev["cover_ranges"] > 0
    assert ev["union_tier"] == 0 and ev["rows_scanned"] == N


@pytest.mark.parametrize("native", ["native", "numpy"])
def test_wave_of_64_distinct_boxes_counts_exactly(setup, native):
    """64 distinct boxes in one wave: one dispatch over one cover, each
    answer the numpy reference's (and the library's), with the native range
    decomposition and with the numpy one."""
    import geomesa_tpu.native as nat
    s = setup
    off = 200 if native == "native" else 300
    boxes = [s.box(off + i, side=s.side / 2) for i in range(64)]
    if native == "numpy":
        config.NO_NATIVE.set(True)
        nat._lib, nat._load_failed = None, False
    try:
        st0, t0 = s.sched.stats(), time.time() * 1000
        reqs = [s.sched.submit("t", s.query(b)) for b in boxes]
        got = [r.result(timeout=120) for r in reqs]
        events = s.batch_events(t0, 64)
    finally:
        if native == "numpy":
            config.NO_NATIVE.unset()
            nat._lib, nat._load_failed = None, False
    assert got == [int(s.mask(b).sum()) for b in boxes]
    for i in (0, 21, 63):
        assert got[i] == s.planner.count(s.query(boxes[i]))
    st1 = s.sched.stats()
    assert st1["group_covers"] - st0["group_covers"] == len(events) <= 2
    assert sum(e["cover_boxes"] for e in events) == 64
    assert all(e["kernel"].startswith("count_multi_blocks.") for e in events)
    # the kernel read the cover's blocks, and what it read is what the
    # roofline counts
    for e in events:
        assert e["rows_scanned"] % prune.BLOCK_SIZE == 0
        assert 0 < e["rows_scanned"] <= prune.PRUNE_MAX_FRACTION * N


def test_empty_union_answers_zero_plus_delta_rows(setup):
    """Boxes far from every indexed row: the union's cover is empty, nothing
    is dispatched, and each member answers 0 plus its own delta rows."""
    s = setup
    far = [(100.0 + i, 50.0, 100.5 + i, 50.5) for i in range(6)]
    attrs = {"v": 50}
    if s.temporal:
        attrs["dtg"] = int(T_LO + 86400000)
    with s.ds.get_writer("t") as w:
        for i in (1, 4):   # inside far[1] and far[4], still in the delta
            x0 = far[i][0]
            w.write(geom=f"POINT({x0 + 0.2} 50.2)" if s.points else
                    f"LINESTRING({x0 + 0.1} 50.1, {x0 + 0.2} 50.2)", **attrs)
    st0, t0 = s.sched.stats(), time.time() * 1000
    reqs = [s.sched.submit("t", s.query(b)) for b in far]
    assert [r.result(timeout=60) for r in reqs] == [0, 1, 0, 0, 1, 0]
    st1 = s.sched.stats()
    assert st1["group_covers"] - st0["group_covers"] == 1
    assert all(r.batch_id is None and not r.batched for r in reqs)
    time.sleep(0.05)
    assert not RECORDER.recent(kind="batch", since_ms=t0, limit=10)

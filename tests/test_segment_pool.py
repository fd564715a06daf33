"""The segment pool of an extent index: every feature's segments on the
device in the index's row order (columns ``__seg__`` and ``__way__``), and the
host's cut of candidate blocks into tiles and launches."""

import numpy as np
import pytest

from geomesa_tpu.features import geometry as geo
from geomesa_tpu.features.sft import SimpleFeatureType
from geomesa_tpu.features.table import FeatureTable
from geomesa_tpu.index import scan
from geomesa_tpu.index.device import SEG, WAY
from geomesa_tpu.index.spatial import (FullScanIndex, XZ2Index, XZ3Index,
                                       Z2Index)


def _index(garr, spec="*geom:Geometry", cls=XZ2Index, extra=None):
    sft = SimpleFeatureType.from_spec("l", spec)
    table = FeatureTable.build(sft, {"geom": garr, **(extra or {})})
    return cls(sft, table)


def _mixed(n=3000, seed=11):
    """LineStrings of 2 to 2,000 vertices, closed 5-vertex Polygons, one way
    longer than a pool tile, and a few of the other types."""
    rng = np.random.default_rng(seed)
    shapes = []
    for i in range(n):
        c = rng.uniform(-40, 40, 2)
        if i % 3 == 0:
            w, h = rng.uniform(0.001, 0.01, 2)
            ring = [c, c + [w, 0], c + [w, h], c + [0, h], c]
            shapes.append((geo.POLYGON, [[list(p) for p in ring]]))
        else:
            k = int(min(2 + rng.lognormal(1.4, 1.0), 2000))
            k = {1: 2000, 2: 2, 4: scan.POOL_TILE + 1500}.get(i, k)
            pts = c + np.cumsum(rng.normal(0, 0.01, (k, 2)), axis=0)
            shapes.append((geo.LINESTRING, pts.tolist()))
    shapes += [
        (geo.POLYGON, [[[0, 0], [4, 0], [4, 4], [0, 4], [0, 0]],
                       [[1, 1], [2, 1], [2, 2], [1, 1]]]),       # a hole
        (geo.MULTILINESTRING, [[[5, 5], [6, 6], [7, 5]], [[9, 9], [9, 8]]]),
        (geo.MULTIPOLYGON, [[[[10, 10], [11, 10], [11, 11], [10, 10]]],
                            [[[20, 20], [21, 20], [21, 21], [20, 20]]]]),
        (geo.MULTIPOINT, [[30, 30], [31, 31]]),
        (geo.POINT, [33, 33]),
    ]
    return geo.GeometryArray.from_shapes(shapes)


def _host_segments(garr, row):
    """(k, 4) segments of one feature by its rings: vertex i to i + 1, a
    lone vertex to itself, nothing from ring to ring."""
    out = []
    for p in range(garr.geom_offsets[row], garr.geom_offsets[row + 1]):
        for r in range(garr.part_offsets[p], garr.part_offsets[p + 1]):
            xy = garr.coords[garr.ring_offsets[r]: garr.ring_offsets[r + 1]]
            if len(xy) == 1:
                out.append(np.concatenate([xy[0], xy[0]])[None])
            else:
                out.append(np.concatenate([xy[:-1], xy[1:]], axis=1))
    return np.concatenate(out)


def test_pool_layout_matches_the_host_geometry():
    garr = _mixed()
    idx = _index(garr)
    seg = np.asarray(idx.device.columns[SEG]).T
    way = np.asarray(idx.device.columns[WAY])
    assert way.shape == (3, len(garr)) and seg.shape[1] == 4
    assert np.array_equal(way[0], idx.seg_off[:-1])
    assert np.array_equal(way[1], np.diff(idx.seg_off))
    # a tile read from any offset stays inside the planes
    assert len(seg) == idx.seg_off[-1] + scan.POOL_TILE
    kinds = {geo.POLYGON: 1, geo.MULTIPOLYGON: 3, geo.MULTILINESTRING: 2,
             geo.MULTIPOINT: 2}
    for pos, row in enumerate(idx.perm):
        want = _host_segments(garr, int(row)).astype(np.float32)
        got = seg[idx.seg_off[pos]: idx.seg_off[pos + 1]]
        assert np.array_equal(got, want), (pos, row)
        assert way[2][pos] == kinds.get(int(garr.type_codes[row]), 0)
    nodes = np.diff(garr.ring_offsets)
    assert nodes.min() == 1 and nodes.max() > scan.POOL_TILE


def test_one_segment_a_way_is_the_endpoints_in_index_order():
    """What ``ensure_segment_columns`` uploaded as sx1/sy1/sx2/sy2 for a
    single-segment layer is the pool with one segment a way."""
    rng = np.random.default_rng(2)
    coords = rng.uniform(-60, 60, (2 * 5000, 2))
    garr = geo.GeometryArray.linestrings(coords)
    idx = _index(garr, "*geom:LineString")
    seg = np.asarray(idx.device.columns[SEG])[:, :len(garr)]
    want = coords.reshape(len(garr), 4)[idx.perm].astype(np.float32)
    assert np.array_equal(seg.T, want)
    assert np.array_equal(idx.seg_off, np.arange(len(garr) + 1))
    assert not np.asarray(idx.device.columns[WAY])[2].any()


def test_only_extent_indexes_keep_a_pool():
    garr = _mixed(300)
    rng = np.random.default_rng(3)
    dtg = np.datetime64("2020-01-01", "ms").astype(np.int64) \
        + rng.integers(0, 20 * 86_400_000, len(garr))
    xz3 = _index(garr, "dtg:Date,*geom:Geometry", XZ3Index, {"dtg": dtg})
    assert SEG in xz3.device.columns and xz3.seg_off is not None
    full = _index(garr, cls=FullScanIndex)
    assert SEG not in full.device.columns and full.seg_off is None
    pts = geo.GeometryArray.points(rng.uniform(-10, 10, 500),
                                   rng.uniform(-10, 10, 500))
    z2 = _index(pts, "*geom:Point", Z2Index)
    assert SEG not in z2.device.columns and z2.seg_off is None


def test_merge_rebuilds_the_pool_as_a_full_build_lays_it():
    garr = _mixed(600)
    sft = SimpleFeatureType.from_spec("l", "*geom:Geometry")
    table = FeatureTable.build(sft, {"geom": garr})
    n_old = 450
    old = XZ2Index(sft, table.take(np.arange(n_old)))
    merged = XZ2Index.merge_from(old, table, n_old)
    fresh = XZ2Index(sft, table)
    assert np.array_equal(merged.seg_off, fresh.seg_off)
    for name in (SEG, WAY):
        assert np.array_equal(np.asarray(merged.device.columns[name]),
                              np.asarray(fresh.device.columns[name]))


def test_band_launches_form_few_programs(monkeypatch):
    """Past the smallest block tier a launch's tile tier follows from its
    block tier, whatever the spans: no rare pair is left to be compiled in
    the middle of a load. Every block goes out once and its tiles fit."""
    monkeypatch.setattr(scan, "BAND_MAX_BLOCKS", 32)
    rng = np.random.default_rng(3)
    n, bsz = 400_000, 4096
    seg_n = np.minimum(1 + np.floor(rng.lognormal(1.4, 1.0, n)), 1999)
    seg_n[rng.integers(0, n, 40)] = 1999       # blocks far over the mean
    seg_off = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(seg_n, out=seg_off[1:])
    n_blocks = -(-n // bsz)
    per = 1 << int(np.ceil(np.log2(seg_off[-1] / n_blocks / scan.POOL_TILE)))
    seen = set()
    for k in (1, 3, 9, 20, 50, n_blocks):
        for _ in range(20):
            blocks = np.sort(rng.choice(n_blocks, size=k, replace=False)
                             ).astype(np.int32)
            if rng.random() < 0.5:             # one run of neighbours
                blocks = (np.arange(k) + rng.integers(0, n_blocks - k + 1)
                          ).astype(np.int32)
            out = list(scan.band_launches(blocks, bsz, seg_off, n))
            assert np.array_equal(np.concatenate([c[0] for c in out]), blocks)
            for chunk, starts, delta, n_seg, (bt, tt) in out:
                assert len(chunk) <= bt <= scan.BAND_MAX_BLOCKS
                assert len(starts) <= tt
                assert tt == bt * per or bt == 8 or len(chunk) == 1
                seen.add((bt, tt))
    tiers = int(np.log2(scan.BAND_MAX_BLOCKS // 8))
    lone = {p for p in seen if p[1] > p[0] * per}
    assert len(seen - lone) <= tiers + 1 + int(np.log2(per))


@pytest.mark.parametrize("blocks", [[0], [0, 1, 2], [1, 5, 6, 40], [97]],
                         ids=str)
def test_pool_tiles_hold_every_block_span(blocks):
    rng = np.random.default_rng(8)
    n, bsz = 25_000, 256
    seg_off = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(rng.integers(1, 60, n), out=seg_off[1:])
    blocks = np.asarray(blocks, dtype=np.int32)
    starts, delta, n_seg = scan.pool_tiles(blocks, bsz, seg_off, n)
    lo = seg_off[np.minimum(blocks * bsz, n)]
    hi = seg_off[np.minimum((blocks + 1) * bsz, n)]
    assert n_seg == int((hi - lo).sum())
    flat = (starts[:, None] + np.arange(scan.POOL_TILE)).reshape(-1)
    for b, d, a, z in zip(blocks, delta, lo, hi):
        # the block's span, shifted, reads its own pool segments
        assert np.array_equal(flat[a + d: z + d], np.arange(a, z))

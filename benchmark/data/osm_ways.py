"""OSM ways in the `osm-ways` converter's example SimpleFeatureType: corpus,
loader, residency check and plain reference.

The record (``user:String, tags, dtg:Date, *geom:Geometry``) is the GeoMesa
user guide's example for ``geomesa-convert-osm``: a way becomes a LineString,
a closed building way a Polygon. A way has 2 to 2,000 nodes (OSM API 0.6,
``waynodes maximum``) and OSM stores a coordinate as an integer of 1e-7
degrees. Both are documented; the values between are made here, since no
extract may be fetched in a run, and how each is drawn is the configuration's
``assumed``. A corpus holds every vertex on that integer grid (``xi``,
``yi``), and the loader hands the store ``xi / 1e7``: the double nearest the
decimal a parser would read from the OSM file.

The reference is numpy over those integers and imports nothing of
geomesa_tpu: a way intersects a polygon when one of its vertices lies inside
(crossing parity), one of its segments crosses or touches an edge
(orientation signs; a zero with the point inside the other segment's envelope
is a touch), or, for a building, the polygon lies inside it. On the grid
every difference is under 4e9 units and every product of two differences of
one query's neighbourhood under 4e14, so int64 is exact.

Where the program may differ from it. The program refines its uncertain ways
in f64 degrees. A grid coordinate is a double to within 1.4e-14 degrees, a
cross product of differences of 0.1 to 1 degrees therefore to within 3e-15
to 5e-14 degrees^2, and one unit^2 is 1e-14: an f64 sign can be wrong only
where the integer cross product is within ~5 units^2 of 0, in effect a vertex
exactly on an edge's line or an edge's end exactly on a segment's. The
program calls a point "on" a line where the f64 cross product is inside its
own rounding (``geom_numpy._CROSS_ROUNDING``, ~1e-2 units^2 here).
"""

import ctypes
import glob
import json
import os
import sys

import numpy as np

CENTRES_SEED = 1234       # the GDELT corpus's: the deployment's geography
CLUSTERS = 64
CLUSTER_SIGMA_DEG = 0.25  # an urban area: two thirds of its ways within 40 km
GRID = 1e7                # OSM's storage: integers of 1e-7 degrees
MAX_NODES = 2000          # OSM API 0.6 waynodes maximum
BUILDING_NODES = 5        # four corners and the closing node
USERS, TAG_SETS = 16384, 4096
HIGHWAYS = ("residential", "service", "footway", "track", "path",
            "unclassified", "tertiary", "secondary")
CHUNK_WAYS = 1 << 13      # a query's candidates go chunk by chunk
PROBE_WAYS = 1024
HERE = os.path.dirname(os.path.abspath(__file__))


def _keep_freed_memory() -> None:
    """Tell glibc's allocator to keep what numpy frees. The reference makes
    some hundred temporaries a chunk; where the process's map and trim
    thresholds have stayed at their 128 KiB, each temporary is mapped,
    faulted in and unmapped again: 100 answers took 19.6 s before this call
    and 3.8 s after it, in one process on the chip's host (PR 27's builder).
    Runs when a reference is made, after the window is closed; elsewhere than
    glibc it does nothing."""
    try:
        mallopt = ctypes.CDLL("libc.so.6").mallopt
    except (OSError, AttributeError):
        return
    for param, value in ((-3, 32 << 20),     # M_MMAP_THRESHOLD
                         (-1, 512 << 20),    # M_TRIM_THRESHOLD
                         (-2, 64 << 20)):    # M_TOP_PAD
        mallopt(param, value)


def centres() -> np.ndarray:
    """(CLUSTERS, 2) lon/lat of the urban clusters, hottest first."""
    return np.random.default_rng(CENTRES_SEED).uniform(
        [-120, -40], [140, 60], size=(CLUSTERS, 2))


def zipf(rng, n: int, rows: int) -> np.ndarray:
    """Codes 0..n-1 with p ~ 1/(1+rank)."""
    cdf = np.cumsum(1.0 / (1.0 + np.arange(n)))
    return np.searchsorted(cdf, rng.random(rows, dtype=np.float32)
                           * cdf[-1]).astype(np.int32).clip(0, n - 1)


def _per_way_cumsum(v: np.ndarray, off: np.ndarray) -> np.ndarray:
    """Running sum of ``v`` (float64, changed in place) that starts anew at
    every way: each way's first value gives back what the way before it
    summed to, so that one running sum over all of them does it."""
    sums = np.add.reduceat(v, off[:-1])
    v[off[1:-1]] -= sums[:-1]
    return np.cumsum(v, out=v)


def make_corpus(rows: int, seed: int) -> dict:
    """Ways as walks from ``cluster centre + N(0, 0.25 deg)``, every vertex
    rounded to the 1e-7 degree grid. A third are buildings: closed rings of
    five nodes, rectangles with sides log-normal around 15 m at any angle.
    The others are open ways of 2 + LogNormal(1.9, 1.0) nodes (capped at
    2,000), steps log-normal around 50 m, turns N(0, 0.5 rad)."""
    cs = centres()
    rng = np.random.default_rng([seed, 2])
    closed = rng.random(rows, dtype=np.float32) < np.float32(1 / 3)
    nodes = np.minimum(2 + np.floor(rng.lognormal(1.9, 1.0, rows)),
                       MAX_NODES).astype(np.int64)
    nodes[closed] = BUILDING_NODES
    off = np.zeros(rows + 1, dtype=np.int64)
    np.cumsum(nodes, out=off[1:])
    total = int(off[-1])
    first = off[:-1]
    start = cs[rng.integers(0, CLUSTERS, rows)] \
        + rng.normal(0, CLUSTER_SIGMA_DEG, (rows, 2))
    turn = rng.standard_normal(total, dtype=np.float32).astype(np.float64)
    turn *= 0.5
    step = rng.standard_normal(total, dtype=np.float32)
    step = np.exp(step * np.float32(0.7) + np.float32(np.log(4.5e-4)))
    # a building: straight on to the second corner, then three right angles
    b = first[closed]
    side = np.exp(rng.normal(np.log(1.35e-4), 0.5, (len(b), 2))
                  ).astype(np.float32)
    turn[b + 1] = 0.0
    for k in (2, 3, 4):
        turn[b + k] = np.pi / 2
    for k in (1, 2, 3, 4):
        step[b + k] = side[:, (k + 1) % 2]
    turn[first] = rng.uniform(0, 2 * np.pi, rows)
    heading = _per_way_cumsum(turn, off).astype(np.float32)
    del turn
    out = {}
    for name, axis, trig, bound in (("xi", 0, np.cos, 180),
                                    ("yi", 1, np.sin, 90)):
        d = (step * trig(heading)).astype(np.float64)
        d[first] = start[:, axis]
        pos = _per_way_cumsum(d, off)
        np.clip(pos, -bound, bound, out=pos)
        pos *= GRID
        grid = np.rint(pos, out=pos).astype(np.int32)
        grid[b + BUILDING_NODES - 1] = grid[b]    # the ring closes exactly
        out[name] = grid
    base = np.datetime64("2012-01-01T00:00:00", "ms").astype(np.int64)
    span = np.datetime64("2024-01-01T00:00:00", "ms").astype(np.int64) - base
    out.update(
        off=off, closed=closed, centers=cs,
        dtg=base + rng.integers(0, span, rows),
        user=(zipf(rng, USERS, rows),
              [f"mapper{i:05d}" for i in range(USERS)]),
        # JSON text, as convert/formats.py:read_osm emits a way's tags
        tags=(zipf(rng, TAG_SETS, rows),
              [json.dumps({"highway": HIGHWAYS[i % len(HIGHWAYS)],
                           "name": f"Street {i:04d}"}, sort_keys=True)
               for i in range(TAG_SETS)]))
    return out


def head(corpus: dict, ways: int) -> dict:
    """The corpus's first ``ways`` ways as a corpus of their own."""
    ways = min(ways, len(corpus["off"]) - 1)
    end = int(corpus["off"][ways])
    out = dict(corpus, off=corpus["off"][: ways + 1],
               xi=corpus["xi"][:end], yi=corpus["yi"][:end])
    for name in ("closed", "dtg"):
        out[name] = corpus[name][:ways]
    for name in ("user", "tags"):
        out[name] = (corpus[name][0][:ways], corpus[name][1])
    return out


def _table(sft, corpus: dict):
    from geomesa_tpu.features.geometry import (LINESTRING, POLYGON,
                                               GeometryArray)
    from geomesa_tpu.features.table import FeatureTable, StringColumn

    coords = np.empty((len(corpus["xi"]), 2))
    np.divide(corpus["xi"], GRID, out=coords[:, 0])
    np.divide(corpus["yi"], GRID, out=coords[:, 1])
    level = np.arange(len(corpus["off"]), dtype=np.int64)
    geom = GeometryArray(
        np.where(corpus["closed"], POLYGON, LINESTRING).astype(np.int8),
        level, level, corpus["off"], coords)
    return FeatureTable.build(sft, {
        "user": StringColumn(*corpus["user"]),
        "tags": StringColumn(*corpus["tags"]),
        "dtg": corpus["dtg"], "geom": geom})


def device_planes(type_name: str) -> list:
    """``device_planes`` of the configurations this module makes the data
    of: the planes their queries need resident on the device."""
    planes = []
    for path in sorted(glob.glob(os.path.join(HERE, "..", "configs",
                                              "*.json"))):
        with open(path) as f:
            cfg = json.load(f)
        if cfg.get("data") == "osm_ways" and cfg["type_name"] == type_name:
            planes += [p for p in cfg["device_planes"] if p not in planes]
    return planes


def check_resident(ds, type_name: str, planes: list) -> int:
    """Every plane the configuration needs is a device column of the index
    the polygon counts read, on the default device: or the run ends. The
    cell measures ways refined on the chip; a store that keeps no vertices
    there refines on the host, and that is another deployment. Returns the
    planes' bytes."""
    import jax

    idx = next((i for i in ds.planners[type_name].indexes
                if i.name == "xz2"), None)
    cols = idx.device.columns if idx is not None else {}
    missing = [p for p in planes if p not in cols]
    if missing:
        print(f"osm_ways: type {type_name!r}: planes {missing} are not among "
              f"the xz2 index's device columns {sorted(cols)}: this store "
              f"keeps no segments on the device", file=sys.stderr, flush=True)
        raise SystemExit(1)
    where = {d.platform for p in planes for d in cols[p].devices()}
    if where != {jax.default_backend()}:
        print(f"osm_ways: type {type_name!r}: planes on {sorted(where)}, not "
              f"on the {jax.default_backend()}", file=sys.stderr, flush=True)
        raise SystemExit(1)
    return sum(int(cols[p].nbytes) for p in planes)


def load(ds, corpus: dict, type_name: str, spec: str) -> None:
    """create_schema → FeatureTable.build → ds.load: the normal path, first
    for a probe of 1,024 ways under a type name of its own, so that a store
    without the planes ends the run before the full load, then for the
    corpus, which is checked the same way."""
    planes = device_planes(type_name)
    probe = type_name + "_probe"
    ds.load(probe, _table(ds.create_schema(probe, spec),
                          head(corpus, PROBE_WAYS)))
    check_resident(ds, probe, planes)
    ds.remove_schema(probe)
    ds.load(type_name, _table(ds.create_schema(type_name, spec), corpus))
    print(f"device planes {planes}: {check_resident(ds, type_name, planes)} "
          f"bytes resident", flush=True)


class Reference:
    """Exact on the grid with ``dtype`` int64. float32 exists for the control
    only: the same tests on float32 degrees, the nearest precision below the
    f64 the program refines in."""

    def __init__(self, corpus: dict, dtype=np.int64):
        _keep_freed_memory()
        self.corpus, self.dtype = corpus, dtype
        self.exact = np.issubdtype(dtype, np.integer)
        off = corpus["off"][:-1]
        # envelopes, sorted by their west edge so a polygon reads a slice
        boxes = [f.reduceat(corpus[k], off) for k, f in (
            ("xi", np.minimum), ("xi", np.maximum),
            ("yi", np.minimum), ("yi", np.maximum))]
        self.order = np.argsort(boxes[0], kind="stable")
        self.xmin, self.xmax, self.ymin, self.ymax = (
            b[self.order] for b in boxes)
        self.widest = int((boxes[1].astype(np.int64) - boxes[0]).max())

    def _values(self, ints):
        """Grid integers as the numbers the tests run on."""
        ints = np.asarray(ints)
        if self.exact:
            return ints.astype(np.int64)
        return (ints / GRID).astype(self.dtype)

    def _candidates(self, ring: np.ndarray):
        """Ways whose envelope meets the ring's (no other can intersect it)
        and those envelopes. The control's are a little wide, so that its
        own rounding decides, not the prefilter."""
        pad = 0 if self.exact else 1000
        x0, x1 = ring[:, 0].min() - pad, ring[:, 0].max() + pad
        y0, y1 = ring[:, 1].min() - pad, ring[:, 1].max() + pad
        # keys of the plane's own type: another type would have numpy
        # convert the whole plane at every search
        key = lambda v: self.xmin.dtype.type(np.clip(v, -2**31, 2**31 - 1))
        lo = np.searchsorted(self.xmin, key(x0 - self.widest), side="left")
        hi = np.searchsorted(self.xmin, key(x1), side="right")
        keep = np.flatnonzero((self.xmax[lo:hi] >= x0)
                              & (self.ymax[lo:hi] >= y0)
                              & (self.ymin[lo:hi] <= y1)) + lo
        return self.order[keep], [self._values(b[keep]) for b in (
            self.xmin, self.ymin, self.xmax, self.ymax)]

    def count_intersects(self, ring) -> int:
        """Ways that intersect the polygon whose ring (grid integers, open:
        the last vertex joins the first) is ``ring``, CHUNK_WAYS candidates
        at a time, so that every temporary stays in cache."""
        ring = np.asarray(ring, dtype=np.int64)
        r = self._values(ring)
        edges = [(*r[i], *r[(i + 1) % len(r)]) for i in range(len(r))]
        ways, box = self._candidates(ring)
        return sum(self._count(edges, ways[lo: lo + CHUNK_WAYS],
                               [b[lo: lo + CHUNK_WAYS] for b in box])
                   for lo in range(0, len(ways), CHUNK_WAYS))

    def _count(self, edges, ways, box) -> int:
        """A way whose envelope the polygon's boundary does not meet lies
        wholly inside or wholly outside, as its envelope's corner does; the
        others are tested vertex by vertex and segment by segment, and a
        building that none of that settles may still hold the polygon."""
        met, inside = _boundary_meets(edges, *box)
        total = int(np.count_nonzero(inside & ~met))
        ways = ways[met]
        if len(ways) == 0:
            return total
        off = self.corpus["off"]
        first, nodes = off[ways], off[ways + 1] - off[ways]
        way = np.repeat(np.arange(len(first)), nodes)
        v = np.repeat(first - (np.cumsum(nodes) - nodes), nodes) \
            + np.arange(int(nodes.sum()))
        x, y = self._values(self.corpus["xi"][v]), \
            self._values(self.corpus["yi"][v])
        hit = np.bincount(way[_inside(x, y, edges)],
                          minlength=len(first)) > 0
        # segments of the ways no vertex settled: vertex i to i + 1 (a
        # building's ring is stored closed, so that is all its sides)
        open_ = ~hit[way]
        open_[np.cumsum(nodes) - 1] = False
        a = np.flatnonzero(open_)
        touch = _touches(x[a], y[a], x[a + 1], y[a + 1], edges)
        hit |= np.bincount(way[a[touch]], minlength=len(first)) > 0
        # the polygon inside a building (nothing of the two boundaries met,
        # and its first vertex is inside): only one whose envelope holds
        # that vertex can
        px, py = edges[0][:2]
        x0, y0, x1, y1 = (b[met] for b in box)
        ends = np.cumsum(nodes)
        for k in np.flatnonzero(~hit & self.corpus["closed"][ways]
                                & (x0 <= px) & (px <= x1)
                                & (y0 <= py) & (py <= y1)):
            lo, hi = ends[k] - nodes[k], ends[k]
            sides = list(zip(x[lo:hi - 1], y[lo:hi - 1],
                             x[lo + 1:hi], y[lo + 1:hi]))
            hit[k] = bool(_inside(np.array([px]), np.array([py]), sides)[0])
        return total + int(hit.sum())


def _cross(ox, oy, px, py, qx, qy):
    return (px - ox) * (qy - oy) - (py - oy) * (qx - ox)


def _boundary_meets(edges, x0, y0, x1, y1):
    """(some edge meets the closed rectangle, its corner (x0, y0) is inside
    by crossing parity). An edge misses a rectangle when it lies beyond one
    of its sides or the four corners lie strictly on one side of its line
    (the separating axes of a segment and a box)."""
    met = np.zeros(len(x0), dtype=bool)
    inside = np.zeros(len(x0), dtype=bool)
    for cx, cy, dx, dy in edges:
        c = [_cross(cx, cy, dx, dy, px, py)
             for px, py in ((x0, y0), (x1, y0), (x1, y1), (x0, y1))]
        beyond = ((max(cx, dx) < x0) | (min(cx, dx) > x1)
                  | (max(cy, dy) < y0) | (min(cy, dy) > y1))
        one_side = ((c[0] > 0) & (c[1] > 0) & (c[2] > 0) & (c[3] > 0)) \
            | ((c[0] < 0) & (c[1] < 0) & (c[2] < 0) & (c[3] < 0))
        met |= ~(beyond | one_side)
        inside ^= ((cy > y0) != (dy > y0)) \
            & ((c[0] > 0) if dy > cy else (c[0] < 0))
    return met, inside


def _inside(x, y, edges) -> np.ndarray:
    """Crossing parity of the +x ray, half-open in y."""
    inside = np.zeros(len(x), dtype=bool)
    for x1, y1, x2, y2 in edges:
        straddles = (y1 > y) != (y2 > y)
        c = _cross(x1, y1, x2, y2, x, y)
        inside ^= straddles & ((c > 0) if y2 > y1 else (c < 0))
    return inside


def _touches(ax, ay, bx, by, edges) -> np.ndarray:
    """Segment (a, b) crosses or touches some edge (c, d). Only segments
    whose envelope meets the edge's can, and only they are tested."""
    out = np.zeros(len(ax), dtype=bool)
    x_lo, x_hi = np.minimum(ax, bx), np.maximum(ax, bx)
    y_lo, y_hi = np.minimum(ay, by), np.maximum(ay, by)
    for cx, cy, dx, dy in edges:
        near = np.flatnonzero(
            (x_hi >= min(cx, dx)) & (x_lo <= max(cx, dx))
            & (y_hi >= min(cy, dy)) & (y_lo <= max(cy, dy)))
        px, py, qx, qy = ax[near], ay[near], bx[near], by[near]
        d1 = _cross(px, py, qx, qy, cx, cy)
        d2 = _cross(px, py, qx, qy, dx, dy)
        d3 = _cross(cx, cy, dx, dy, px, py)
        d4 = _cross(cx, cy, dx, dy, qx, qy)
        # proper crossing, or a zero with the point on the other segment
        # (it is on its line, so inside its envelope is on it)
        hit = ((((d1 > 0) & (d2 < 0)) | ((d1 < 0) & (d2 > 0)))
               & (((d3 > 0) & (d4 < 0)) | ((d3 < 0) & (d4 > 0)))) \
            | ((d1 == 0) & _within(px, py, qx, qy, cx, cy)) \
            | ((d2 == 0) & _within(px, py, qx, qy, dx, dy)) \
            | ((d3 == 0) & _within(cx, cy, dx, dy, px, py)) \
            | ((d4 == 0) & _within(cx, cy, dx, dy, qx, qy))
        out[near[hit]] = True
    return out


def _within(ox, oy, qx, qy, px, py):
    """p inside the envelope of segment (o, q)."""
    return ((np.minimum(ox, qx) <= px) & (px <= np.maximum(ox, qx))
            & (np.minimum(oy, qy) <= py) & (py <= np.maximum(oy, qy)))


def controls(corpus: dict) -> dict:
    """What is put in the program's place to show that ``correct`` can read
    false: the reference one precision below the f64 the program refines
    in."""
    return {"float32": Reference(corpus, dtype=np.float32)}

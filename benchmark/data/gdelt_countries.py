"""GDELT events × country polygons, two types in one store: corpus, loader,
residency check and plain reference of the join.

The deployment is upstream's documented spatial join: the geomesa-tutorials
Spark example ``ShallowJoin`` (GDELT events joined to a small covering set of
country polygons that is broadcast, aggregates per country) and the same join
as Spark SQL, ``gdelt JOIN countries ON st_intersects``, planned by
geomesa-spark-sql's ``SpatialJoinStrategy``. The point side is the quick
start's GDELT record, made by this module's neighbour ``gdelt_events``. The
polygon side is a second feature type, ``name:String, *geom:Polygon``. No
country file may be fetched in a run, so the polygons are made here, from a
constant: they are the deployment's, not the seed's, as the corpus's cluster
centres are. How they are drawn is the configuration's ``assumed``.

The reference is numpy in f64 and int64 and imports nothing of geomesa_tpu:
for a polygon and a window, the events that pass the filter and lie in the
polygon's envelope, then the half-open crossing rule edge by edge over the
events whose y lies in the edge's y-range (found in a sort by y), a point of
the boundary by an exact on-segment test.
"""

import glob
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import gdelt_events  # noqa: E402

POLYGONS_SEED = 32          # the deployment's geography, like CENTRES_SEED
PER_CENTRE = 4
CENTRE_SIGMA_DEG = (6.0, 4.0)
RADIUS_DEG = (1.0, 8.0)     # mean radius, log-uniform
VERTICES = (200, 2000)      # log-uniform: Natural Earth 1:110m-1:50m borders
HURST, NOISE_SIGMA, HARMONICS = 0.7, 0.25, 64
STATS = ("NumMentions", "NumArticles")


def _config() -> dict:
    """The configuration this module makes the data of."""
    for path in sorted(glob.glob(os.path.join(HERE, "..", "configs",
                                              "*.json"))):
        with open(path) as f:
            cfg = json.load(f)
        if cfg.get("data") == "gdelt_countries":
            return cfg
    raise SystemExit("gdelt_countries: no configuration names this module")


def polygons(count: int) -> dict:
    """The first ``count`` country polygons: ``off`` (count + 1 offsets into
    ``xy``), ``xy`` (f64 vertices, every ring closed: its first vertex again
    at its end) and ``names``. A ring is star-shaped around its centre:
    vertices at evenly spaced angles, each jittered within its own sector, at
    radius R * exp(noise(angle)), the noise a sum of ``HARMONICS`` cosines
    whose amplitudes fall as k^-(H + 1/2): fractional Brownian in the angle,
    normalised to ``NOISE_SIGMA``. Polygon i does not depend on ``count``."""
    centres = np.random.default_rng(gdelt_events.CENTRES_SEED).uniform(
        [-120, -40], [140, 60], size=(gdelt_events.CLUSTERS, 2))
    off, rings = [0], []
    for i in range(count):
        rng = np.random.default_rng([POLYGONS_SEED, i])
        c = centres[i // PER_CENTRE] + rng.normal(0, CENTRE_SIGMA_DEG)
        radius = np.exp(rng.uniform(*np.log(RADIUS_DEG)))
        k = int(np.exp(rng.uniform(*np.log(VERTICES))))
        angle = (np.arange(k) + rng.uniform(0.1, 0.9, k)) * (2 * np.pi / k)
        h = np.arange(1, HARMONICS + 1)
        amp = h ** -(HURST + 0.5)
        noise = (amp[:, None] * np.cos(
            h[:, None] * angle + rng.uniform(0, 2 * np.pi, HARMONICS)[:, None]
        )).sum(axis=0) * (NOISE_SIGMA / np.sqrt((amp ** 2).sum() / 2))
        r = radius * np.exp(noise)
        ring = np.stack([np.clip(c[0] + r * np.cos(angle), -180, 180),
                         np.clip(c[1] + r * np.sin(angle), -90, 90)], axis=1)
        rings.append(np.vstack([ring, ring[:1]]))
        off.append(off[-1] + k + 1)
    return {"off": np.asarray(off, dtype=np.int64),
            "xy": np.concatenate(rings) if rings else np.zeros((0, 2)),
            "names": [f"country{i:03d}" for i in range(count)]}


def make_corpus(rows: int, seed: int) -> dict:
    """``gdelt_events``'s corpus and the polygons beside it. A store without
    the join's entry point ends the run here, before a row is made: the cell
    measures the served join, and a program from before it has none."""
    from geomesa_tpu.datastore import TpuDataStore
    if not callable(getattr(TpuDataStore, "join", None)):
        print("gdelt_countries: this store has no join entry point "
              "(TpuDataStore.join)", file=sys.stderr, flush=True)
        raise SystemExit(1)
    cfg = _config()
    full = cfg["join"]["polygons"]
    corpus = gdelt_events.make_corpus(rows, seed)
    # a rehearsal at fewer rows joins as many fewer polygons (`assumed`)
    corpus["polygons"] = polygons(min(full, max(8, full * rows // cfg["rows"])))
    return corpus


def check_resident(ds, type_name: str, planes: list) -> int:
    """The polygons' segment pool and envelopes are device columns of the
    polygon type's xz2 index, on the default backend: or the run ends. The
    cell measures a join that reads the polygons on the chip. Returns the
    planes' bytes."""
    import jax

    idx = next((i for i in ds.planners[type_name].indexes
                if i.name == "xz2"), None)
    cols = idx.device.columns if idx is not None else {}
    missing = [p for p in planes if p not in cols]
    if missing:
        print(f"gdelt_countries: type {type_name!r}: planes {missing} are "
              f"not among the xz2 index's device columns {sorted(cols)}",
              file=sys.stderr, flush=True)
        raise SystemExit(1)
    where = {d.platform for p in planes for d in cols[p].devices()}
    if where != {jax.default_backend()}:
        print(f"gdelt_countries: type {type_name!r}: planes on "
              f"{sorted(where)}, not on the {jax.default_backend()}",
              file=sys.stderr, flush=True)
        raise SystemExit(1)
    return sum(int(cols[p].nbytes) for p in planes)


def load(ds, corpus: dict, type_name: str, spec: str) -> None:
    """create_schema → FeatureTable.build → ds.load, the normal path, for the
    polygons first: a store that keeps no pool of them on the device ends the
    run before the events' long load."""
    from geomesa_tpu.features.geometry import POLYGON, GeometryArray
    from geomesa_tpu.features.table import FeatureTable, StringColumn

    join = _config()["join"]
    pol = corpus["polygons"]
    level = np.arange(len(pol["off"]), dtype=np.int64)
    geom = GeometryArray(
        np.full(len(pol["names"]), POLYGON, dtype=np.int8),
        level, level, pol["off"], pol["xy"])
    sft = ds.create_schema(join["type_name"], join["schema"])
    ds.load(join["type_name"], FeatureTable.build(sft, {
        "name": StringColumn.encode(pol["names"]), "geom": geom},
        fids=pol["names"]))
    got = sorted(i.name for i in ds.planners[join["type_name"]].indexes)
    if got != sorted(join["index"]):
        print(f"gdelt_countries: indexes {got} of {join['type_name']!r}, "
              f"configuration says {join['index']}", file=sys.stderr,
              flush=True)
        raise SystemExit(1)
    resident = check_resident(ds, join["type_name"], join["device_planes"])
    print(f"device planes {join['device_planes']} of {join['type_name']!r}: "
          f"{resident} bytes resident, {len(pol['names'])} polygons, "
          f"{len(pol['xy']) - len(pol['names'])} segments", flush=True)
    gdelt_events.load(ds, corpus, type_name, spec)


class Reference:
    """f64 compares on lon/lat and vertices, int64 on epoch millis and sums.
    ``dtype`` exists for the control only: float32 is the nearest precision
    below the f64 both types are held in."""

    def __init__(self, corpus: dict, dtype=np.float64):
        self.corpus, self.dtype = corpus, dtype
        pol = corpus["polygons"]
        self.off, self.names = pol["off"], pol["names"]
        self.xy = pol["xy"].astype(dtype)
        self._rows = {}

    def _window(self, t_lo: int, t_hi: int):
        """x, y (in ``dtype``) and the summed attributes of the events of a
        window; DURING is exclusive at both ends."""
        key = (t_lo, t_hi)
        if key not in self._rows:
            c = self.corpus
            rows = np.flatnonzero((c["dtg"] > t_lo) & (c["dtg"] < t_hi))
            self._rows = {key: (c["x"][rows].astype(self.dtype),
                                c["y"][rows].astype(self.dtype),
                                [c[a][rows].astype(np.int64)
                                 for a in STATS])}
        return self._rows[key]

    def join(self, t_lo: int, t_hi: int, polygon: int,
             boundary: bool = True) -> tuple:
        """(count, sum of each of ``STATS``) of the window's events in polygon
        ``polygon``; an event on its boundary counts where ``boundary``."""
        x, y, vals = self._window(t_lo, t_hi)
        ring = self.xy[self.off[polygon]: self.off[polygon + 1]]
        near = np.flatnonzero((x >= ring[:, 0].min()) & (x <= ring[:, 0].max())
                              & (y >= ring[:, 1].min())
                              & (y <= ring[:, 1].max()))
        near = near[np.argsort(y[near], kind="stable")]
        x, y = x[near], y[near]
        inside = np.zeros(len(near), dtype=bool)
        on = np.zeros(len(near), dtype=bool)
        for (x1, y1), (x2, y2) in zip(ring[:-1], ring[1:]):
            lo = np.searchsorted(y, min(y1, y2), side="left")
            hi = np.searchsorted(y, max(y1, y2), side="right")
            if lo == hi:
                continue
            px, py = x[lo:hi], y[lo:hi]
            c = (x2 - x1) * (py - y1) - (y2 - y1) * (px - x1)
            inside[lo:hi] ^= ((y1 > py) != (y2 > py)) \
                & ((c > 0) if y2 > y1 else (c < 0))
            on[lo:hi] |= (c == 0) & (min(x1, x2) <= px) & (px <= max(x1, x2))
        hit = near[(inside | on) if boundary else (inside & ~on)]
        return (len(hit),) + tuple(int(v[hit].sum()) for v in vals)


def controls(corpus: dict) -> dict:
    """What is put in the program's place to show that ``correct`` can read
    false: the reference with events and vertices rounded to float32."""
    return {"float32": Reference(corpus, dtype=np.float32)}

"""GDELT events in the GeoMesa quick start's SimpleFeatureType: corpus, loader
and plain reference.

The fourteen attributes are those of geomesa-tutorials' ``GDELTData`` (the
configuration's ``schema``). The values are made here, since no GDELT file may
be fetched: how each is drawn is the configuration's ``assumed``. The corpus's
shape (Gaussian clusters over 30 days) and the reference are copied from
``chip_smoke.py`` (``make_corpus``, ``Reference``) so that the yardstick does
not move when that script does. The reference is numpy over the raw f64/int64
host columns and imports nothing of geomesa_tpu.
"""

import numpy as np

CENTRES_SEED = 1234   # chip_smoke.py's default seed: the layout PR 21 ran
CLUSTERS = 64
# (attribute, distinct values, width of a value): Zipf(1) over the vocabulary
ZIPF_STRINGS = (("Actor1Name", 8192, "ACTOR1 {:05d}"),
                ("Actor1CountryCode", 224, "A{:03d}"),
                ("Actor2Name", 8192, "ACTOR2 {:05d}"),
                ("Actor2CountryCode", 224, "B{:03d}"),
                ("EventCode", 256, "{:04d}"))
PLACES_PER_CLUSTER = 256


def _zipf(rng, n: int, rows: int) -> np.ndarray:
    """Codes 0..n-1 with p ~ 1/(1+rank), by one search of the cumulated p."""
    cdf = np.cumsum(1.0 / (1.0 + np.arange(n)))
    return np.searchsorted(cdf, rng.random(rows, dtype=np.float32)
                           * cdf[-1]).astype(np.int32).clip(0, n - 1)


def make_corpus(rows: int, seed: int) -> dict:
    """Raw host columns. Strings are dictionary codes into sorted
    vocabularies (the form ``FeatureTable`` keeps them in).

    The cluster centres are the deployment's geography and do not move with
    the seed (they are chip_smoke's at its default seed); every row is drawn
    from ``seed``. A seed that moved the centres would change how far the
    clusters overlap, and with that the work of a query."""
    centers = np.random.default_rng(CENTRES_SEED).uniform(
        [-120, -40], [140, 60], size=(CLUSTERS, 2))
    rng = np.random.default_rng([seed, 1])
    which = rng.integers(0, CLUSTERS, rows, dtype=np.int32)
    x = np.clip(centers[which, 0] + rng.normal(0, 8, rows), -180, 180)
    y = np.clip(centers[which, 1] + rng.normal(0, 6, rows), -90, 90)
    base = np.datetime64("2020-01-01T00:00:00", "ms").astype(np.int64)
    dtg = base + rng.integers(0, 30 * 86_400_000, rows)
    mentions = rng.geometric(0.18, rows).astype(np.int32)   # median 4
    corpus = {
        "x": x, "y": y, "dtg": dtg, "centers": centers,
        # event ids count up as GDELT's do; nine digits, so sorted as text
        "GLOBALEVENTID": (np.arange(rows, dtype=np.int32), None),
        "NumMentions": mentions,
        "NumSources": rng.geometric(0.6, rows).astype(np.int32),
        "NumArticles": mentions + rng.integers(0, 3, rows, dtype=np.int32),
        "ActionGeo_Type": rng.integers(1, 6, rows, dtype=np.int32),
        # a place belongs to its cluster, a country to every fourth cluster
        "ActionGeo_FullName": (
            which * PLACES_PER_CLUSTER
            + _zipf(rng, PLACES_PER_CLUSTER, rows),
            [f"PLACE {i:05d}" for i in range(CLUSTERS * PLACES_PER_CLUSTER)]),
        "ActionGeo_CountryCode": (
            which // 4, [f"C{i:02d}" for i in range(CLUSTERS // 4)]),
    }
    for name, n, fmt in ZIPF_STRINGS:
        corpus[name] = (_zipf(rng, n, rows), [fmt.format(i) for i in range(n)])
    return corpus


def load(ds, corpus: dict, type_name: str, spec: str) -> None:
    """create_schema → FeatureTable.build → ds.load: the normal path."""
    from geomesa_tpu.features.table import FeatureTable, StringColumn

    sft = ds.create_schema(type_name, spec)
    columns = {"dtg": corpus["dtg"], "geom": (corpus["x"], corpus["y"])}
    for attr in sft.attributes:
        col = corpus.get(attr.name)
        if isinstance(col, tuple):
            codes, vocab = col
            if vocab is None:
                vocab = [f"{900_000_000 + i}" for i in range(len(codes))]
            col = StringColumn(codes, vocab)
        if col is not None:
            columns[attr.name] = col
    ds.load(type_name, FeatureTable.build(sft, columns))


class Reference:
    """f64 compares on lon/lat, int64 compares on epoch millis. ``dtype``
    exists for the control only: float32 is the nearest precision below the
    f64 the configuration states."""

    def __init__(self, corpus: dict, dtype=np.float64):
        self.corpus, self.dtype = corpus, dtype
        self._cands = {}

    def _candidates(self, t_lo: int, t_hi: int, attr: str, gt: int):
        """x (ascending) and y of the rows passing time ∧ attr > gt; DURING
        is exclusive at both ends. Sorted once so a box reads only its
        slice."""
        key = (t_lo, t_hi, attr, gt)
        if key not in self._cands:
            c = self.corpus
            rows = np.flatnonzero((c["dtg"] > t_lo) & (c["dtg"] < t_hi)
                                  & (c[attr] > gt))
            x = c["x"][rows].astype(self.dtype)
            order = np.argsort(x, kind="stable")
            self._cands[key] = (x[order],
                                c["y"][rows][order].astype(self.dtype))
        return self._cands[key]

    def count_box_during_gt(self, box, t_lo: int, t_hi: int, attr: str,
                            gt: int) -> int:
        xs, ys = self._candidates(t_lo, t_hi, attr, gt)
        x0, y0, x1, y1 = (self.dtype(v) for v in box)
        lo = np.searchsorted(xs, x0, side="left")     # x >= x0
        hi = np.searchsorted(xs, x1, side="right")    # x <= x1
        y = ys[lo:hi]
        return int(np.count_nonzero((y >= y0) & (y <= y1)))


def controls(corpus: dict) -> dict:
    """What is put in the program's place to show that ``correct`` can read
    false: the reference one precision below the f64 the data is held in."""
    return {"float32": Reference(corpus, dtype=np.float32)}

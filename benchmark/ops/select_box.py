"""GET /types/<type>/features?cql=BBOX ∧ dtg DURING <week>&limit=<n>.

Box centre as ``count_box`` draws it: one of the corpus's cluster centres,
drawn Zipf(s) over the centres' fixed order, plus N(0, jitter); a fixed
half-width on both axes. Floats drawn afresh, so every box is distinct. The
answer is the GeoJSON FeatureCollection of the matching events with every
attribute of the record; what is compared is which events came back, where
and when each is and how often it was mentioned.

A collection as long as the limit may have been cut there, and a cut set has
no reference: such an answer counts as failed, and no box is shrunk to avoid
it. At 10M rows a 1 x 1 degree box at a cluster's centre holds ~120 of the
week's events, and where clusters overlap a few times that: the limit of
50,000 is never near."""

from urllib.parse import quote

import numpy as np

CHUNK = 256
ID_BASE = 900_000_000   # data/gdelt_events.py's load(): nine digits, counting


def _ms(iso: str) -> int:
    return int(np.datetime64(iso, "ms").astype(np.int64))


def requests(params: dict, config: dict, corpus: dict, seed: int,
             client: int, clients: int):
    """Endless (path, args) for one client; its stream depends on the seed
    and the client's number alone."""
    centers = corpus["centers"]
    p = 1.0 / (1.0 + np.arange(len(centers))) ** params["zipf_s"]
    p /= p.sum()
    rng = np.random.default_rng([seed, 3, client])
    w = params["half_width_deg"]
    t0, t1 = params["during"]
    tail = f" AND dtg DURING {t0}Z/{t1}Z"
    base = (f"/types/{config['type_name']}/features?limit={params['limit']}"
            f"&cql=")
    while True:
        which = rng.choice(len(centers), size=CHUNK, p=p)
        c = centers[which] + rng.normal(0, params["centre_jitter_deg"],
                                        (CHUNK, 2))
        for cx, cy in c.tolist():
            box = (max(-180.0, cx - w), max(-90.0, cy - w),
                   min(180.0, cx + w), min(90.0, cy + w))
            cql = (f"BBOX(geom, {box[0]!r}, {box[1]!r}, {box[2]!r}, "
                   f"{box[3]!r})" + tail)
            yield base + quote(cql), (box, params["limit"])


def answer(body: dict):
    """The sorted tuple of (GLOBALEVENTID, lon, lat, dtg, NumMentions) of the
    collection's features, or None where the body is flagged approximate, is
    no FeatureCollection, or lacks any of those on a feature. (Whether it was
    cut at the limit only ``expected`` can say: the body does not carry its
    request's limit.)"""
    feats = body.get("features")
    if body.get("type") != "FeatureCollection" or body.get("approximate") \
            or not isinstance(feats, list):
        return None
    try:
        return tuple(sorted(
            (str(f["properties"]["GLOBALEVENTID"]),
             float(f["geometry"]["coordinates"][0]),
             float(f["geometry"]["coordinates"][1]),
             str(f["properties"]["dtg"]),
             int(f["properties"]["NumMentions"])) for f in feats))
    except (KeyError, TypeError, IndexError, ValueError):
        return None


_WEEKS = {}   # (id of the reference, week) -> its rows, sorted by x


def _week(ref, t0: int, t1: int):
    """Rows inside the week (DURING is exclusive at both ends) with their x
    (ascending) and y in the reference's precision, so a box reads a slice.
    The reference is held too, so that its id stays its own."""
    key = (id(ref), t0, t1)
    if key not in _WEEKS:
        c = ref.corpus
        rows = np.flatnonzero((c["dtg"] > t0) & (c["dtg"] < t1))
        x = c["x"][rows].astype(ref.dtype)
        order = np.argsort(x, kind="stable")
        _WEEKS[key] = (ref, rows[order], x[order],
                       c["y"][rows][order].astype(ref.dtype))
    return _WEEKS[key][1:]


def expected(ref, params: dict, args):
    """The same from the raw columns, with the box compares in the
    reference's precision and the values as the corpus holds them. None for
    a box that holds as many rows as the limit or more: the server's answer
    was cut, no reference stands for a cut set, and None equals no answer
    that ``answer`` gives, so the request counts as wrong."""
    box, limit = args
    rows, xs, ys = _week(ref, *(_ms(t) for t in params["during"]))
    x0, y0, x1, y1 = (ref.dtype(v) for v in box)
    lo = np.searchsorted(xs, x0, side="left")     # x >= x0
    hi = np.searchsorted(xs, x1, side="right")    # x <= x1
    hit = rows[lo:hi][(ys[lo:hi] >= y0) & (ys[lo:hi] <= y1)]
    if len(hit) >= limit:
        return None
    c = ref.corpus
    when = np.datetime_as_string(c["dtg"][hit].astype("datetime64[ms]"),
                                 unit="ms")
    return tuple(sorted(
        (f"{ID_BASE + int(i)}", float(x), float(y), str(t) + "Z", int(m))
        for i, x, y, t, m in zip(hit.tolist(), c["x"][hit].tolist(),
                                 c["y"][hit].tolist(), when.tolist(),
                                 c["NumMentions"][hit].tolist())))

"""GET /types/<type>/count?cql=INTERSECTS(geom, POLYGON((...))).

The polygon is an irregular ring around a centre: one of the corpus's cluster
centres, drawn Zipf(s) over the centres' fixed order, plus N(0, jitter). Its
5 to 12 vertices stand at evenly spaced angles, each jittered within its own
sector (so the ring never crosses itself), at a share of a circumradius drawn
uniform; every vertex is rounded to OSM's 1e-7 degree grid and written with
seven decimals, so the server parses exactly the grid point. Not a BBOX: no
envelope stands for it, so the refine does the work. Floats drawn afresh, so
every polygon is distinct and the result cache never hits. Which clusters are
hot is the deployment's, not the seed's."""

from urllib.parse import quote

import numpy as np

CHUNK = 256
GRID = 1e7


def rings(params: dict, centers: np.ndarray, rng, n: int) -> list:
    """``n`` rings as tuples of (x, y) grid integers, open (the last vertex
    joins the first)."""
    p = 1.0 / (1.0 + np.arange(len(centers))) ** params["zipf_s"]
    p /= p.sum()
    c = centers[rng.choice(len(centers), size=n, p=p)] \
        + rng.normal(0, params["centre_jitter_deg"], (n, 2))
    radius = rng.uniform(*params["circumradius_deg"], n)
    k_lo, k_hi = params["vertices"]
    ks = rng.integers(k_lo, k_hi + 1, n)
    out = []
    for (cx, cy), r, k in zip(c.tolist(), radius.tolist(), ks.tolist()):
        angle = (np.arange(k) + rng.uniform(0.1, 0.9, k)) * (2 * np.pi / k)
        rr = r * rng.uniform(*params["radius_share"], k)
        xy = np.stack([np.clip(cx + rr * np.cos(angle), -180, 180),
                       np.clip(cy + rr * np.sin(angle), -90, 90)], axis=1)
        out.append(tuple(map(tuple, np.rint(xy * GRID).astype(np.int64)
                             .tolist())))
    return out


def requests(params: dict, config: dict, corpus: dict, seed: int,
             client: int, clients: int):
    """Endless (path, args) for one client; its stream depends on the seed
    and the client's number alone. args: the ring as grid integers."""
    rng = np.random.default_rng([seed, 4, client])
    base = f"/types/{config['type_name']}/count?cql="
    while True:
        for ring in rings(params, corpus["centers"], rng, CHUNK):
            wkt = ", ".join(f"{x / GRID:.7f} {y / GRID:.7f}"
                            for x, y in ring + ring[:1])
            yield (base + quote(f"INTERSECTS(geom, POLYGON(({wkt})))"),
                   ring)


def answer(body: dict):
    """The value to compare, or None where the answer may not stand for an
    exact count (missing, or flagged approximate)."""
    if body.get("approximate") or not isinstance(body.get("count"), int):
        return None
    return body["count"]


def expected(ref, params: dict, ring) -> int:
    return ref.count_intersects(ring)

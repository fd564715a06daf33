"""GET /types/<type>/join?with=<polygon type>&op=st_intersects
&cql=dtg DURING <t0>/<t1>&stats=count,sum(NumMentions),sum(NumArticles).

Every request joins all the polygons with the events of a window of its own:
``t0`` uniform over ``start`` at millisecond resolution, its length uniform
over ``length_days``, so no two requests are alike and neither the result
cache nor the plan cache hits.

An answer holds a row a polygon, and the plain reference on the host costs
seconds for all of them, so a checked answer is held to the reference on a
sample: ``check_polygons`` polygons drawn from the request's own window plus
the polygon of most vertices. run.py compares ``answer(body) !=
expected(...)`` and ``answer`` sees the body alone, so ``expected`` returns a
``Sampled``: equal to a whole answer when the sample's rows agree, count and
sums, equal to another ``Sampled`` (the control's) when their rows do."""

from urllib.parse import quote

import numpy as np

STATS = ("NumMentions", "NumArticles")
DAY_MS = 86_400_000


def _ms(iso: str) -> int:
    return int(np.datetime64(iso, "ms").astype(np.int64))


def _iso(ms: int) -> str:
    return str(np.datetime64(int(ms), "ms")) + "Z"


def requests(params: dict, config: dict, corpus: dict, seed: int,
             client: int, clients: int):
    """Endless (path, args) for one client; its stream depends on the seed
    and the client's number alone. args: the window's ends in epoch ms."""
    rng = np.random.default_rng([seed, 7, client])
    lo, hi = (_ms(t) for t in params["start"])
    d_lo, d_hi = (int(d * DAY_MS) for d in params["length_days"])
    base = (f"/types/{config['type_name']}/join?with="
            f"{config['join']['type_name']}&op={params['op']}&stats="
            + quote(",".join(["count"] + [f"sum({a})" for a in STATS]))
            + "&cql=")
    while True:
        t0 = int(rng.integers(lo, hi + 1))
        t1 = t0 + int(rng.integers(d_lo, d_hi + 1))
        yield (base + quote(f"dtg DURING {_iso(t0)}/{_iso(t1)}"), (t0, t1))


def answer(body: dict):
    """Every row of the answer as (fid, count, sums...), or None where it may
    not stand for an exact answer (malformed, or flagged approximate)."""
    rows = body.get("rows")
    if body.get("approximate") or not isinstance(rows, list) \
            or body.get("polygons") != len(rows):
        return None
    try:
        return tuple((r["fid"], r["count"]) + tuple(r["sum"][a] for a in STATS)
                     for r in rows)
    except (KeyError, TypeError):
        return None


def sample(params: dict, off: np.ndarray, window) -> list:
    """The polygons an answer to ``window`` is checked on, from the window
    alone, so that the program's answer and a control's meet the same ones."""
    n = len(off) - 1
    rng = np.random.default_rng([window[0] & 0xFFFFFFFF, window[0] >> 32,
                                 window[1] & 0xFFFFFFFF, window[1] >> 32])
    picks = rng.choice(n, size=min(n, params["check_polygons"]),
                       replace=False).tolist()
    return sorted(set(picks) | {int(np.argmax(np.diff(off)))})


class Sampled:
    """The reference's rows of a sample of an answer's polygons."""

    def __init__(self, polygons: int, rows: dict):
        self.polygons, self.rows = polygons, rows

    def __eq__(self, other):
        if isinstance(other, Sampled):
            return (self.polygons, self.rows) == (other.polygons, other.rows)
        return isinstance(other, tuple) and len(other) == self.polygons \
            and all(other[i] == row for i, row in self.rows.items())

    def __ne__(self, other):
        return not self == other

    __hash__ = None

    def __repr__(self):
        return f"Sampled({self.polygons}, {self.rows})"


def expected(ref, params: dict, window) -> Sampled:
    t0, t1 = window
    return Sampled(len(ref.names), {
        i: (ref.names[i],) + ref.join(t0, t1, i,
                                      params["op"] == "st_intersects")
        for i in sample(params, ref.off, window)})

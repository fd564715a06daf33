"""GET /types/<type>/count?cql=BBOX ∧ dtg DURING <week> ∧ <attr> > <n>.

Box centre: one of the corpus's cluster centres, drawn Zipf(s) over the
centres' fixed order, plus N(0, jitter); half-widths uniform. Which clusters
are hot is the deployment's, not the seed's: a seed that moved the hot
clusters would change the work of a run. Floats drawn afresh, so every box is
distinct and the result cache never hits."""

from urllib.parse import quote

import numpy as np

CHUNK = 256


def _ms(iso: str) -> int:
    return int(np.datetime64(iso, "ms").astype(np.int64))


def requests(params: dict, config: dict, corpus: dict, seed: int,
             client: int, clients: int):
    """Endless (path, args) for one client; its stream depends on the seed
    and the client's number alone. Some thousands of boxes make a window, so
    sizes drawn afresh average out: two sets of runs on the same six seeds
    showed no seed in the numbers (PERF.md, PR 24)."""
    centers = corpus["centers"]
    p = 1.0 / (1.0 + np.arange(len(centers))) ** params["zipf_s"]
    p /= p.sum()
    rng = np.random.default_rng([seed, 1, client])
    lo, hi = params["half_width_deg"]
    t0, t1 = params["during"]
    tail = (f" AND dtg DURING {t0}Z/{t1}Z AND {params['residual_attr']} > "
            f"{params['residual_gt']}")
    base = f"/types/{config['type_name']}/count?cql="
    while True:
        which = rng.choice(len(centers), size=CHUNK, p=p)
        c = centers[which] + rng.normal(0, params["centre_jitter_deg"],
                                        (CHUNK, 2))
        w = rng.uniform(lo, hi, (CHUNK, 2))
        for (cx, cy), (wx, wy) in zip(c.tolist(), w.tolist()):
            box = (max(-180.0, cx - wx), max(-90.0, cy - wy),
                   min(180.0, cx + wx), min(90.0, cy + wy))
            cql = (f"BBOX(geom, {box[0]!r}, {box[1]!r}, {box[2]!r}, "
                   f"{box[3]!r})" + tail)
            yield base + quote(cql), box


def answer(body: dict):
    """The value to compare, or None where the answer may not stand for an
    exact count (missing, or flagged approximate)."""
    if body.get("approximate") or not isinstance(body.get("count"), int):
        return None
    return body["count"]


def expected(ref, params: dict, box) -> int:
    t0, t1 = params["during"]
    return ref.count_box_during_gt(box, _ms(t0), _ms(t1),
                                   params["residual_attr"],
                                   params["residual_gt"])

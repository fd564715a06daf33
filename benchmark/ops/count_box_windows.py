"""GET /types/<type>/count?cql=BBOX ∧ dtg DURING <one of the weeks> ∧
<attr> > <one of the thresholds>.

``count_box``'s request (its centres, widths and reference) with the week
and the threshold drawn a request, uniformly, from the mix's lists: a
dashboard's panels each with a week and a threshold of their own. The
scheduler keys a group of counts by the bytes of its windows and of its
residual's parameters, so a cycle's requests fall apart into up to
``len(during) * len(residual_gt)`` groups, each with a cover and a dispatch
of its own. Floats drawn afresh, so every box is distinct and the result
cache never hits."""

from urllib.parse import quote

import numpy as np

CHUNK = 256


def _ms(iso: str) -> int:
    return int(np.datetime64(iso, "ms").astype(np.int64))


def requests(params: dict, config: dict, corpus: dict, seed: int,
             client: int, clients: int):
    """Endless (path, args) for one client; its stream depends on the seed
    and the client's number alone. args: (box, week's index, threshold)."""
    centers = corpus["centers"]
    p = 1.0 / (1.0 + np.arange(len(centers))) ** params["zipf_s"]
    p /= p.sum()
    rng = np.random.default_rng([seed, 1, client])
    lo, hi = params["half_width_deg"]
    weeks, gts = params["during"], params["residual_gt"]
    base = f"/types/{config['type_name']}/count?cql="
    while True:
        which = rng.choice(len(centers), size=CHUNK, p=p)
        c = centers[which] + rng.normal(0, params["centre_jitter_deg"],
                                        (CHUNK, 2))
        w = rng.uniform(lo, hi, (CHUNK, 2))
        week = rng.integers(0, len(weeks), CHUNK).tolist()
        gt = rng.integers(0, len(gts), CHUNK).tolist()
        for (cx, cy), (wx, wy), k, g in zip(c.tolist(), w.tolist(), week, gt):
            box = (max(-180.0, cx - wx), max(-90.0, cy - wy),
                   min(180.0, cx + wx), min(90.0, cy + wy))
            t0, t1 = weeks[k]
            cql = (f"BBOX(geom, {box[0]!r}, {box[1]!r}, {box[2]!r}, "
                   f"{box[3]!r}) AND dtg DURING {t0}Z/{t1}Z AND "
                   f"{params['residual_attr']} > {gts[g]}")
            yield base + quote(cql), (box, k, gts[g])


def answer(body: dict):
    """The value to compare, or None where the answer may not stand for an
    exact count (missing, or flagged approximate)."""
    if body.get("approximate") or not isinstance(body.get("count"), int):
        return None
    return body["count"]


def expected(ref, params: dict, args) -> int:
    box, k, gt = args
    t0, t1 = params["during"][k]
    return ref.count_box_during_gt(box, _ms(t0), _ms(t1),
                                   params["residual_attr"], gt)

"""The control of ``correct`` (``controls()`` of the data module) has to read
wrong answers where the f64 reference reads none: the reference in float32.
Kept at a size a test run holds (2M rows, up to 20,000 requests, no server:
a control is done at its first wrong answer, since the limit is 0); the join
is shown on made events beside a polygon's edges instead: its reference
costs a sixth of a second an answer, and 600 answers at 2M rows met no
boundary (PR 34). The readings at the cell's own size on the chip host are in
PERF.md, and ``test_rehearsal.py`` puts the control in the program's place in
a whole run.
"""

import itertools
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
import run  # noqa: E402

BENCH = run.load_json(os.path.dirname(HERE), "BENCHMARK.json")
JOIN = "gdelt-countries-10m.join-c4"


@pytest.mark.parametrize("cell", [c for c in BENCH["workloads"]
                                  if c["name"] != JOIN],
                         ids=lambda c: c["name"])
def test_control_reads_wrong_answers(cell, rows=2_000_000, requests=20_000):
    _, cfg, traffic = run.find_cell(BENCH, cell["name"])
    data = run.load_module("data", cfg["data"])
    op = run.load_module("ops", traffic["operation"])
    corpus = data.make_corpus(rows, seed=2147483659)
    ref = data.Reference(corpus)
    for control, low in data.controls(corpus).items():
        stream = op.requests(traffic["params"], cfg, corpus, 2147483659, 0, 1)
        first = next((i for i, (_, a) in enumerate(
            itertools.islice(stream, requests), 1)
            if op.expected(low, traffic["params"], a)
            != op.expected(ref, traffic["params"], a)), None)
        print(f"{cell['name']} {control}: first wrong answer at request "
              f"{first} of up to {requests}")
        assert first is not None      # the limit on wrong_answers is 0


def test_join_control_reads_a_wrong_answer_on_made_events():
    """An event a tenth of a micro-degree outside the middle of each edge of
    the polygon every checked answer holds (the one of most vertices):
    float32 spaces lon and lat by 2-8e-6 degrees there, so rounding events
    and vertices carries some of them over their edge, and the control's
    count of that polygon is not the reference's."""
    _, cfg, traffic = run.find_cell(BENCH, JOIN)
    data = run.load_module("data", cfg["data"])
    op = run.load_module("ops", traffic["operation"])
    corpus = data.make_corpus(4000, seed=2147483659)
    pol = corpus["polygons"]
    most = int(np.argmax(np.diff(pol["off"])))
    ring = pol["xy"][pol["off"][most]: pol["off"][most + 1]]
    a, b = ring[:-1], ring[1:]
    along = (b - a) / np.linalg.norm(b - a, axis=1, keepdims=True)
    # the ring runs counter-clockwise: its outside is to the right of an edge
    made = (a + b) / 2 + 1e-7 * np.stack([along[:, 1], -along[:, 0]], axis=1)
    window = tuple(int(np.datetime64(t, "ms").astype(np.int64))
                   for t in ("2020-01-05T00:00:00", "2020-01-10T00:00:00"))
    n = len(made)
    assert n <= len(corpus["x"])
    corpus["x"][:n], corpus["y"][:n] = made[:, 0], made[:, 1]
    corpus["dtg"][:n] = window[0] + 1000
    ref, low = data.Reference(corpus), data.controls(corpus)["float32"]
    exact = op.expected(ref, traffic["params"], window)
    lower = op.expected(low, traffic["params"], window)
    assert most in exact.rows and exact != lower
    assert exact.rows[most] != lower.rows[most]
    # none of the made events is inside for the f64 reference
    corpus["dtg"][:n] = window[1] + 1000
    without = op.expected(data.Reference(corpus), traffic["params"], window)
    assert without.rows[most] == exact.rows[most]

"""The control of ``correct`` (``controls()`` of the data module) has to read
wrong answers where the f64 reference reads none: the reference in float32.
Kept at a size a test run holds (2M rows, 20,000 requests, no server); the
readings at the cell's own size on the chip host are in PERF.md, and
``test_rehearsal.py`` puts the control in the program's place in a whole run.
"""

import itertools
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
import run  # noqa: E402

BENCH = run.load_json(os.path.dirname(HERE), "BENCHMARK.json")


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_control_reads_wrong_answers(cell, rows=2_000_000, requests=20_000):
    _, cfg, traffic = run.find_cell(BENCH, cell["name"])
    data = run.load_module("data", cfg["data"])
    op = run.load_module("ops", traffic["operation"])
    corpus = data.make_corpus(rows, seed=2147483659)
    ref = data.Reference(corpus)
    for control, low in data.controls(corpus).items():
        stream = op.requests(traffic["params"], cfg, corpus, 2147483659, 0, 1)
        wrong = sum(op.expected(low, traffic["params"], a)
                    != op.expected(ref, traffic["params"], a)
                    for _, a in itertools.islice(stream, requests))
        print(f"{cell['name']} {control}: {wrong} of {requests} wrong")
        assert wrong > 0          # the limit on wrong_answers is 0

"""``gdelt-z3-10m.select-c8``: the program still emits what the benchmark in
force reads of the fused select and the GeoJSON route
(tests/yardstick.py says how and why)."""

import pytest

import yardstick

CELL = "gdelt-z3-10m.select-c8"


@pytest.fixture(scope="module")
def line():
    return yardstick.rehearse(CELL)


@pytest.mark.parametrize("name", yardstick.entries(CELL))
def test_reader_finds_what_it_reads(line, name):
    yardstick.check_entry(line, name)


def test_rehearsal_is_correct(line):
    yardstick.check_correct(line)

"""``gdelt-countries-10m.join-c4``: the program still emits what the benchmark
in force reads of the served spatial join (tests/yardstick.py says how and
why). At the rehearsal's 50,000 rows the cell joins 8 polygons."""

import pytest

import yardstick

CELL = "gdelt-countries-10m.join-c4"


@pytest.fixture(scope="module")
def line():
    return yardstick.rehearse(CELL)


@pytest.mark.parametrize("name", yardstick.entries(CELL))
def test_reader_finds_what_it_reads(line, name):
    yardstick.check_entry(line, name)


def test_rehearsal_is_correct(line):
    yardstick.check_correct(line)

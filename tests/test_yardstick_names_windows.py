"""``gdelt-z3-10m.count-windows-c64``: the program still emits the dispatch
and cycle timers the benchmark in force reads there (tests/yardstick.py says
how and why)."""

import pytest

import yardstick

CELL = "gdelt-z3-10m.count-windows-c64"


@pytest.fixture(scope="module")
def line():
    return yardstick.rehearse(CELL)


@pytest.mark.parametrize("name", yardstick.entries(CELL))
def test_reader_finds_what_it_reads(line, name):
    yardstick.check_entry(line, name)


def test_rehearsal_is_correct(line):
    yardstick.check_correct(line)

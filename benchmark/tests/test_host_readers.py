"""The REST readers of a request's time from accept and of the process's CPU
by thread role — ``rest.accept_ms_per_query``, ``rest.offcpu_ms_per_query``,
``rest.outside_ms_per_query``, ``host.foreign_cpu_pct`` — on hand-made pages,
and on a parent's pages (``parent_pages.json``: a program from before
``http.accept``, ``http.offcpu_us`` and ``host.cpu_us.*``), where each reads
None. A second on the CPU; by hand:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_host_readers.py -q
"""

import copy
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
import run  # noqa: E402

NAMES = ("rest.accept_ms_per_query", "rest.offcpu_ms_per_query",
         "rest.outside_ms_per_query", "host.foreign_cpu_pct")
CELLS = ["gdelt-z3-10m.count-c64", "osm-xz2-10m.intersects-c8",
         "gdelt-z3-10m.select-c8", "gdelt-countries-10m.join-c4",
         "gdelt-z3-10m.count-windows-c64"]

with open(os.path.join(HERE, "tests", "parent_pages.json")) as f:
    PARENT = json.load(f)


def _timer(count, total_s):
    return {"count": count, "total_s": total_s}


def make_ctx():
    """A window of 1,000 counts and 2 page reads: 1,002 accepts of 3 ms each
    on the server, counts of 80 ms from parsed request to flush, 50 ms off a
    CPU each; clients that waited 200 ms for each answer (and one failure
    that took 5 s); the CPU of the program's threads 30 s, of the others
    10 s."""
    before = {"timers": {"http.accept": _timer(10, 0.030),
                         "http.request.count": _timer(10, 0.800),
                         "http.request.metrics": _timer(1, 0.002)},
              "counters": {"http.offcpu_us": 500_000,
                           "host.cpu_us.http": 1_000_000,
                           "host.cpu_us.sched.collector": 500_000,
                           "host.cpu_us.foreign": 9_000_000,
                           "host.cpu_us.process": 99_000_000}}
    after = {"timers": {"http.accept": _timer(1012, 3.036),
                        "http.request.count": _timer(1010, 80.800),
                        "http.request.metrics": _timer(3, 0.010)},
             "counters": {"http.offcpu_us": 50_600_000,
                          "host.cpu_us.http": 21_000_000,
                          "host.cpu_us.http.listener": 2_000_000,
                          "host.cpu_us.sched.collector": 8_500_000,
                          "host.cpu_us.foreign": 19_000_000,
                          "host.cpu_us.process": 199_000_000}}
    requests = [(0.01 * i, 0.01 * i + 0.2, True) for i in range(1000)]
    requests.append((1.0, 6.0, False))
    return {"seconds": 50.0, "before": {"/metrics": before},
            "after": {"/metrics": after}, "requests": requests}


def read(name, ctx):
    return run.load_module("layer_metrics", name).read(ctx)


def test_accept_ms_is_the_timer_mean_over_the_window():
    assert read("rest.accept_ms_per_query", make_ctx()) \
        == pytest.approx(3.0, rel=1e-9)


def test_offcpu_ms_is_the_counter_over_the_accepts():
    assert read("rest.offcpu_ms_per_query", make_ctx()) \
        == pytest.approx(50_100_000 / 1002 / 1000, rel=1e-12)


def test_outside_ms_is_the_clients_mean_less_the_servers():
    # 200 ms a good request; the server: 3 ms of accept + 80 ms of the
    # route with the most observations (count, not the pages)
    assert read("rest.outside_ms_per_query", make_ctx()) \
        == pytest.approx(200.0 - 3.0 - 80.0, rel=1e-9)


def test_foreign_pct_is_the_foreign_share_of_the_python_threads():
    # foreign 10 s; program 20 + 2 + 8 s; the process's total is left out
    assert read("host.foreign_cpu_pct", make_ctx()) \
        == pytest.approx(100.0 * 10 / 40, rel=1e-12)


@pytest.mark.parametrize("name", NAMES)
def test_a_window_without_requests_reads_none(name):
    ctx = make_ctx()
    ctx["after"] = copy.deepcopy(ctx["before"])
    ctx["requests"] = []
    assert read(name, ctx) is None


@pytest.mark.parametrize("cell", sorted(PARENT))
@pytest.mark.parametrize("name", NAMES)
def test_a_parents_pages_read_none(name, cell):
    pages = PARENT[cell]
    ctx = {"seconds": 50.0, "before": pages["before"],
           "after": pages["after"],
           "requests": [(0.0, 0.1, True), (0.1, 0.25, True)]}
    assert read(name, ctx) is None


@pytest.mark.parametrize("name", NAMES)
def test_benchmark_json_lists_the_reader_in_every_cell(name):
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    entry = {m["name"]: m for m in bench["per_layer"]}[name]
    assert entry["workloads"] == CELLS
    assert entry["workloads"] == [w["name"] for w in bench["workloads"]]
    assert (entry["source"], entry["layer"], entry["better"]) \
        == ("program_counter", "REST", "lower")
    assert entry["moves"] == ("qps" if name.startswith("host.") else "p50_ms")
    assert entry["unit"] == ("%" if name.endswith("_pct") else "ms")
    assert os.path.exists(os.path.join(HERE, "layer_metrics", name + ".py"))

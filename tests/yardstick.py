"""One traced rehearsal of a cell of BENCHMARK.json through benchmark/run.py's
own CPU path, for tests/test_yardstick_names_*.py (a cell a file, so that
``--dist loadfile`` gives each rehearsal a worker of its own).

A per-layer metric whose timer or counter the program no longer emits reads
``null``, and the ledger's rules then refuse every ``benchmark`` PR until it
is repaired. run.py calls the per-layer readers only in a traced run, so the
rehearsal is traced; on a CPU the trace holds no device operation, which a
rehearsal tolerates, and the ``device_trace`` entries read nothing. This
module imports benchmark/run.py and reads BENCHMARK.json; it edits neither.
A CPU run proves that a name is read, never what a device metric is.
"""

import contextlib
import gc
import importlib.util
import io
import json
import math
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)

# what start_jax() of run.py sets for its own process; put back afterwards,
# or every program the worker's later tests compile would be written to disk
_RUN_SETS = ("jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")


def _load_run():
    spec = importlib.util.spec_from_file_location(
        "benchmark_run", os.path.join(ROOT, "benchmark", "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def entries(cell: str) -> list:
    """The per-layer entries a CPU run can read: ``program_counter`` ones."""
    return [m["name"] for m in BENCH["per_layer"]
            if m["source"] == "program_counter" and cell in m["workloads"]]


def rehearse(cell: str) -> dict:
    """The last line run.py prints for a 2 s traced rehearsal at 50,000 rows."""
    import jax
    run = _load_run()
    keep = {k: getattr(jax.config, k) for k in _RUN_SETS}
    out = io.StringIO()
    with pytest.MonkeyPatch.context() as mp, contextlib.redirect_stdout(out):
        # with the variable set run.py gives the compile cache no home of its
        # own (config.enable_compile_cache): tier-1 writes none in the checkout
        mp.setenv("JAX_COMPILATION_CACHE_DIR", os.environ.get(
            "JAX_COMPILATION_CACHE_DIR") or "unset-by-tests")
        try:
            rc = run.main(["--workload", cell, "--seed", "2147483659",
                           "--seconds", "2", "--trace", "1",
                           "--rehearse-rows", "50000"])
        finally:
            for k, v in keep.items():
                jax.config.update(k, v)
            if run._gc_watch in gc.callbacks:
                gc.callbacks.remove(run._gc_watch)
    assert rc == 0, out.getvalue()[-4000:]
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    # a rehearsal prints no number under a metric's name
    assert line["metrics"] == {}
    return line


def check_correct(line: dict) -> None:
    assert line["checked"] > 0 and line["attempted"] > 0
    assert line["failed"] == 0
    assert line["compared"]["wrong_answers"] == {"value": 0, "limit": 0}
    assert line["correct"] is True


def check_entry(line: dict, name: str) -> None:
    """The reader found every timer and counter it reads: a finite number.
    A reader answers None when one of its names is missing."""
    got = line["rehearsal"].get(name)
    if name == "device.hbm_peak_gb":
        import jax
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in jax.local_devices())
        if not peak:
            # the one entry that reads None here by design: the CPU backend
            # reports no memory statistics, run.py then hands the reader a
            # peak of 0, and the reader answers None for "no peak reported"
            assert got is None
            return
    assert got is not None, (
        f"{name}: its reader in benchmark/layer_metrics/ read None: a timer, "
        f"counter or page field it reads is no longer emitted under that name")
    assert math.isfinite(got["value"]), (name, got)

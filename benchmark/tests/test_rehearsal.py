"""The CPU rehearsal: every cell of BENCHMARK.json at 200k rows through the
whole of run.py but its look for a chip. A sound run is correct; a wrong reference, an answer
altered where the server produces it, and an answer flagged approximate each
make ``correct`` false. Run by hand (about two minutes):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
import run  # noqa: E402

CELLS = [c["name"] for c in run.load_json(
    os.path.dirname(HERE), "BENCHMARK.json")["workloads"]]


def rehearse(capsys, cell, seed=2147483659, trace=0, more=(), seconds=3,
             rows=200000):
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", str(trace), "--rehearse-rows",
                   str(rows), *more])
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert rc == 0
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert list(line)[-1] == "compared"
    assert err.strip().splitlines()[-1].startswith("compared: ")
    assert line["checked"] > 0
    # a rehearsal prints no number under a metric's name
    assert line["metrics"] == {}
    return line


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(capsys, cell):
    line = rehearse(capsys, cell)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["compared"]["wrong_answers"] == {"value": 0, "limit": 0}
    assert {"setup_s", "qps", "p50_ms", "p95_ms"} == set(line["rehearsal"])


def test_traced_rehearsal_reads_the_counters(capsys):
    line = rehearse(capsys, CELLS[0], trace=1)
    assert line["correct"] is True
    # no TPU plane on the CPU: the trace's readers return nothing, and the
    # counters' readers still read
    assert line["rehearsal"]["sched.batch_mean"]["value"] >= 1.0
    assert "device.idle_pct" not in line["rehearsal"]


@pytest.mark.parametrize("cell", CELLS)
def test_wrong_reference_is_seen(capsys, monkeypatch, cell):
    real = run.load_module

    def load(kind, name):
        mod = real(kind, name)
        if kind == "ops":
            exp = mod.expected
            mod.expected = lambda ref, params, a: exp(ref, params, a) + 1
        return mod

    monkeypatch.setattr(run, "load_module", load)
    line = rehearse(capsys, cell)
    assert line["correct"] is False
    assert line["compared"]["wrong_answers"]["value"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_answer_altered_where_it_is_produced_is_seen(capsys, monkeypatch,
                                                     cell):
    """Every 7th count the store hands the REST layer is one too many."""
    from geomesa_tpu.datastore import TpuDataStore
    real, calls = TpuDataStore.count_coalesced, [0]

    def altered(self, *a, **kw):
        n = real(self, *a, **kw)
        calls[0] += 1
        return n + 1 if calls[0] % 7 == 0 else n

    monkeypatch.setattr(TpuDataStore, "count_coalesced", altered)
    line = rehearse(capsys, cell)
    assert line["correct"] is False
    assert line["compared"]["wrong_answers"]["value"] > 0


def test_answer_flagged_approximate_is_a_failure(capsys, monkeypatch):
    real = run.load_module

    def load(kind, name):
        mod = real(kind, name)
        if kind == "ops":
            ans, calls = mod.answer, [0]

            def answer(body):
                calls[0] += 1
                if calls[0] % 50 == 0:
                    body = dict(body, approximate=True)
                return ans(body)
            mod.answer = answer
        return mod

    monkeypatch.setattr(run, "load_module", load)
    line = rehearse(capsys, CELLS[0])
    assert line["correct"] is False and line["failed"] > 0


def test_window_counts_answers_by_when_they_came(capsys):
    """`qps` is of the answers that came inside the window, also those sent
    before it opened; latency is of the requests sent inside it."""
    line = rehearse(capsys, CELLS[0])
    qps = line["rehearsal"]["qps"]["value"]
    clients = 64
    assert line["attempted"] >= qps * 3          # both kinds are attempted
    assert line["attempted"] - qps * 3 <= 2 * clients   # at most a wave more


def test_control_in_the_programs_place_reads_not_correct(capsys):
    """The float32 reference answers the window's own requests in the
    program's place: some counts differ and `correct` is false. 2M rows and
    some thousands of answers, so that a boundary is met (about two
    minutes)."""
    line = rehearse(capsys, CELLS[0], more=("--control", "float32"),
                    seconds=30, rows=2_000_000)
    assert line["control"] == "float32"
    assert line["correct"] is False and line["failed"] == 0
    assert line["compared"]["wrong_answers"]["value"] > 0


def test_refuses_without_a_tpu(capsys):
    import jax
    if jax.default_backend() == "tpu":
        pytest.skip("a TPU is attached")
    with pytest.raises(SystemExit) as stopped:
        run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                  "--trace", "0"])
    out, err = capsys.readouterr()
    assert stopped.value.code == 2 and "refusing to run" in err
    assert '"correct"' not in out

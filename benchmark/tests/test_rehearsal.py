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


@pytest.mark.parametrize("busy_s, rc", [(3.000001, 1), (3.0, 0)])
def test_no_line_says_busier_than_its_window(capsys, monkeypatch, busy_s, rc):
    """The driver refuses a traced line whose device.busy_s passes its
    window_s: run.py holds that itself and prints no result (exit 1, one
    line on stderr); busy for the whole of the window is a line."""
    trace = {"busy_s": busy_s, "window_s": 3.0, "idle_share": 1 - busy_s / 3,
             "planes": {}, "op_s": {}, "gap_s": {}, "device_ops": [],
             "idle_gaps": []}

    def load_json(*parts):
        # a trace that shows the device at work wants the device's peaks
        got = real_json(*parts)
        return dict(got, cpu=got["TPU v5 lite"]) \
            if parts[-1] == "peaks.json" else got

    real_json = run.load_json
    monkeypatch.setattr(run, "load_json", load_json)
    monkeypatch.setattr(run, "reduce_trace", lambda *a: dict(trace))
    got = run.main(["--workload", CELLS[0], "--seed", "2147483659",
                    "--seconds", "3", "--trace", "1", "--rehearse-rows",
                    "50000"])
    out, err = capsys.readouterr()
    assert got == rc
    last = out.strip().splitlines()[-1]
    if rc:
        assert not last.startswith("{")
        assert [ln for ln in err.splitlines() if ln.startswith("run.py:")] \
            == ["run.py: device.busy_s 3.000001 is not above 0 and at most "
                "device.window_s 3.0"]
    else:
        device = json.loads(last)["device"]
        assert device["busy_s"] == device["window_s"] == 3.0


@pytest.mark.parametrize("cell", CELLS)
def test_wrong_reference_is_seen(capsys, monkeypatch, cell):
    real = run.load_module

    class Another:
        """What a wrong reference expects: never what was answered, be the
        answer a count, a set of features or a join's rows."""
        __hash__ = None

        def __eq__(self, other):
            return False

        def __ne__(self, other):
            return True

    def load(kind, name):
        mod = real(kind, name)
        if kind == "ops":
            mod.expected = lambda ref, params, a: Another()
        return mod

    monkeypatch.setattr(run, "load_module", load)
    line = rehearse(capsys, cell)
    assert line["correct"] is False
    assert line["compared"]["wrong_answers"]["value"] > 0


def _one_more(n):
    return n + 1


def _first_row_one_more(body):
    rows = [dict(r) for r in body["rows"]]
    rows[0]["count"] += 1
    return dict(body, rows=rows)


def _first_feature_gone(text):
    body = json.loads(text)
    return json.dumps(dict(body, features=body["features"][1:]))


# where the program produces a cell's answer, by the kind of its operation:
# the store's entry point the route calls, or what serialises its result
PRODUCED = {"count": ("geomesa_tpu.datastore.TpuDataStore.count_coalesced",
                      _one_more),
            "join": ("geomesa_tpu.datastore.TpuDataStore.join",
                     _first_row_one_more),
            "select": ("geomesa_tpu.io.export.export", _first_feature_gone)}


@pytest.mark.parametrize("cell", CELLS)
def test_answer_altered_where_it_is_produced_is_seen(capsys, monkeypatch,
                                                     cell):
    """Every 7th answer the program produces is altered before the REST
    layer sends it: a count one too many, a join's first row one too many, a
    feature collection without its first feature."""
    import importlib
    _, _, traffic = run.find_cell(run.load_json(
        os.path.dirname(HERE), "BENCHMARK.json"), cell)
    target, alter = PRODUCED[traffic["operation"].split("_")[0]]
    owner, name = target.rsplit(".", 1)
    try:
        owner = importlib.import_module(owner)
    except ModuleNotFoundError:
        module, cls = owner.rsplit(".", 1)
        owner = getattr(importlib.import_module(module), cls)
    real, calls = getattr(owner, name), [0]

    def altered(*a, **kw):
        out = real(*a, **kw)
        calls[0] += 1
        return alter(out) if calls[0] % 7 == 0 else out

    monkeypatch.setattr(owner, name, altered)
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

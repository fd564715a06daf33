"""Shard balance observatory (obs/shardwatch.py, ISSUE 16).

Split-point projection properties (boundaries inside the victim's key
range, load partition within cell granularity), the fractional
hot-cell -> shard join, guaranteed-vs-estimated imbalance scoring (sketch
error can never fake an imbalance), the doctor's shard_imbalance /
collective_straggler rules over injected collaborators, collective-op
telemetry + straggler attribution, state merge / federation, the
empirical cell map vs the sketch's cell keys, flight shard-dim
conformance through the JSONL sink and the federated scrape, and the
web + CLI balance surfaces.
"""

import json
import urllib.request

import numpy as np
import pytest

from geomesa_tpu import config
from geomesa_tpu.metrics import REGISTRY, MetricsRegistry
from geomesa_tpu.obs import shardwatch as sw
from geomesa_tpu.obs import workload as wl
from geomesa_tpu.obs.shardwatch import (WATCH, ShardWatch,
                                        fleet_balance_report,
                                        merge_states, project_splits)
from geomesa_tpu.obs.sketches import cell_key
from geomesa_tpu.obs.workload import WorkloadAnalytics

_KNOBS = (config.SHARDWATCH_ENABLED, config.SHARDWATCH_TOP_CELLS,
          config.SHARDWATCH_SPLIT_PARTS, config.SHARDWATCH_CELL_STATS,
          config.DOCTOR_IMBALANCE_RATIO, config.DOCTOR_IMBALANCE_MIN,
          config.DOCTOR_STRAGGLER_MS, config.DOCTOR_STRAGGLER_ROUNDS,
          config.DOCTOR_CLEAR_TICKS, config.WORKLOAD_ENABLED)


@pytest.fixture(autouse=True)
def _clean_ledger():
    WATCH.clear()
    yield
    for p in _KNOBS:
        p.unset()
    WATCH.clear()


def _wl_cells(events, capacity=64):
    """A private workload plane fed cell-carrying events (no metering,
    no process globals)."""
    w = WorkloadAnalytics(spans=(600.0,), keep=2,
                          sketch_capacity=capacity, meter=False)
    for i, cell in enumerate(events):
        w.offer({"kind": "count.scheduled", "type": "pts",
                 "plan_hash": f"p{i % 7}", "priority": "interactive",
                 "tenant": "t", "ts_ms": 1_000_000_000.0 + i,
                 "duration_ms": 1.0, "cell": cell})
    w.drain()
    return w


# -- split-point projection ---------------------------------------------------


def test_project_splits_basic_two_way():
    cells = [
        {"cell": "a", "load": 10.0, "key_lo": 0, "key_hi": 9},
        {"cell": "b", "load": 10.0, "key_lo": 10, "key_hi": 19},
        {"cell": "c", "load": 10.0, "key_lo": 20, "key_hi": 29},
        {"cell": "d", "load": 10.0, "key_lo": 30, "key_hi": 39},
    ]
    out = project_splits(cells, (0, 39), parts=2)
    assert len(out) == 1
    b = out[0]
    # rows with key < 20 go left: exactly half the observed load
    assert b["key"] == 20 and b["left_fraction"] == 0.5
    assert b["cells_left"] == 2 and b["cell"] == "b"


def test_project_splits_degenerate_inputs():
    assert project_splits([], (0, 10)) == []
    assert project_splits(
        [{"cell": "a", "load": 0.0, "key_lo": 1, "key_hi": 2}],
        (0, 10)) == []
    assert project_splits(
        [{"cell": "a", "load": 5.0, "key_lo": 1, "key_hi": 2}],
        (7, 7)) == []          # hi <= lo: nothing to split


def test_project_splits_property_randomized():
    """ISSUE 16 satellite: over randomized cell layouts every projected
    boundary (1) falls strictly inside the victim's key range and (2)
    partitions the observed load within the largest single-cell share of
    the target — cells are atomic, so no boundary can cut finer."""
    rng = np.random.default_rng(16)
    for trial in range(250):
        n_cells = int(rng.integers(1, 24))
        parts = int(rng.integers(2, 5))
        lo = int(rng.integers(-1000, 1000))
        hi = lo + int(rng.integers(1, 10_000))
        # random, possibly overlapping key spans inside [lo, hi]
        cells = []
        for i in range(n_cells):
            a = int(rng.integers(lo, hi + 1))
            b = int(rng.integers(a, hi + 1))
            cells.append({"cell": f"c{i:02d}",
                          "load": float(rng.uniform(0.0, 50.0)),
                          "key_lo": a, "key_hi": b})
        usable = [c for c in cells if c["load"] > 0.0]
        total = sum(c["load"] for c in usable)
        out = project_splits(cells, (lo, hi), parts=parts)
        if not usable or total <= 0.0:
            assert out == []
            continue
        max_share = max(c["load"] for c in usable) / total
        assert len(out) <= parts - 1
        for b in out:
            assert lo < b["key"] <= hi, (trial, b, lo, hi)
            # the boundary lands at-or-past its target, overshooting by
            # at most the crossing cell's own share
            assert b["left_fraction"] >= b["target"] - 1e-9
            assert b["left_fraction"] - b["target"] <= max_share + 1e-9, \
                (trial, b, max_share)


# -- the join -----------------------------------------------------------------


def _two_shard_map():
    return {
        "cells": {
            "cA": {"0": {"rows": 50, "key_lo": 0, "key_hi": 9}},
            "cB": {"1": {"rows": 50, "key_lo": 100, "key_hi": 109}},
            # straddles the boundary 3:1 in favor of shard 0
            "cC": {"0": {"rows": 30, "key_lo": 90, "key_hi": 99},
                   "1": {"rows": 10, "key_lo": 100, "key_hi": 104}},
        },
        "key_ranges": {"0": [0, 99], "1": [100, 199]},
        "shard_rows": {"0": 80, "1": 60},
    }


def test_fractional_join_attributes_straddling_cells_by_row_share():
    m = _two_shard_map()
    events = ["cA"] * 100 + ["cB"] * 40 + ["cC"] * 40
    watch = ShardWatch(workload=_wl_cells(events))
    watch.set_shard_map("pts", m["cells"], m["key_ranges"],
                        m["shard_rows"])
    for c in events:
        watch.fold_event({"cell": c, "rows_scanned": 10,
                          "device_ms": 0.5})
    rep = watch.balance()
    assert rep["active"]
    t = rep["types"]["pts"]
    s0, s1 = t["shards"]["0"], t["shards"]["1"]
    # 3 distinct cells < sketch capacity -> zero error, exact counts;
    # cC's 40 events split 30:10 by row share
    assert s0["load"] == pytest.approx(100 + 40 * 0.75)
    assert s1["load"] == pytest.approx(40 + 40 * 0.25)
    assert s0["at_least"] == s0["load"]  # guaranteed == estimate here
    assert s0["load_share"] == pytest.approx(130 / 180, abs=1e-3)
    # drain-hook stats split by the same fractions
    assert s0["events"] == pytest.approx(130)
    assert s0["rows_scanned"] == pytest.approx(1300)
    assert s1["device_ms"] == pytest.approx(25.0)
    assert s0["qps"] > 0        # elapsed clock started at first fold
    sc = t["score"]
    assert sc["hot_shard"] == "0"
    assert sc["max_over_mean"] == pytest.approx(130 / 90, abs=1e-3)
    assert t["unmapped"] == {"cells": 0, "load": 0}


def test_unmapped_cells_are_reported_not_silently_dropped():
    m = _two_shard_map()
    watch = ShardWatch(workload=_wl_cells(["zz"] * 50 + ["cA"] * 10))
    watch.set_shard_map("pts", m["cells"], m["key_ranges"])
    t = watch.balance()["types"]["pts"]
    assert t["unmapped"]["cells"] == 1
    assert t["unmapped"]["load"] == 50


def test_imbalance_flags_only_on_guaranteed_load():
    """Sketch error can never fake an imbalance: the over_bar verdict
    uses at_least-based loads, so a huge estimated skew whose error
    bound swallows it stays quiet; the same skew with tight bounds
    fires."""
    config.DOCTOR_IMBALANCE_MIN.set(100)

    class _Stub:
        def __init__(self, err):
            self.err = err

        def hot_set(self, k=None):
            c = 1000
            return {"total": c, "plans": [], "cells": [
                {"key": "cB", "count": c, "error": self.err,
                 "at_least": c - self.err, "fraction": 1.0}]}

    m = _two_shard_map()
    loose = ShardWatch(workload=_Stub(err=950))
    loose.set_shard_map("pts", m["cells"], m["key_ranges"])
    sc = loose.balance()["types"]["pts"]["score"]
    # estimated ratio is maximal but only 50 events are guaranteed
    assert sc["max_over_mean_est"] == pytest.approx(2.0)
    assert not sc["over_bar"]
    tight = ShardWatch(workload=_Stub(err=0))
    tight.set_shard_map("pts", m["cells"], m["key_ranges"])
    sc = tight.balance()["types"]["pts"]["score"]
    assert sc["over_bar"] and sc["hot_shard"] == "1"


def test_min_load_floor_keeps_cold_clusters_quiet():
    config.DOCTOR_IMBALANCE_MIN.set(200)
    m = _two_shard_map()
    watch = ShardWatch(workload=_wl_cells(["cB"] * 100))  # skewed but cold
    watch.set_shard_map("pts", m["cells"], m["key_ranges"])
    sc = watch.balance()["types"]["pts"]["score"]
    assert sc["max_over_mean"] == pytest.approx(2.0)
    assert not sc["over_bar"]


def test_balance_inactive_paths_and_disable_knob():
    watch = ShardWatch(workload=_wl_cells([]))
    rep = watch.balance()
    assert rep == {"active": False, "reason": "no shard map registered",
                   "hot_cells": 0}
    config.SHARDWATCH_ENABLED.set(False)
    assert watch.balance()["reason"] == "shardwatch disabled"
    # folds are gated too: nothing accumulates while disabled
    watch.fold_event({"cell": "cA", "rows_scanned": 1})
    config.SHARDWATCH_ENABLED.unset()
    assert watch.export_state()["cells"] == {}


def test_cell_stats_cap_counts_drops():
    config.SHARDWATCH_CELL_STATS.set(2)
    m = _two_shard_map()
    watch = ShardWatch(workload=_wl_cells(["cA", "cB", "cC"]))
    watch.set_shard_map("pts", m["cells"], m["key_ranges"])
    for c in ("cA", "cB", "cC", "cC"):
        watch.fold_event({"cell": c})
    rep = watch.balance()
    assert rep["cell_stats"]["tracked"] == 2
    assert rep["cell_stats"]["dropped"] == 2


def test_workload_fold_hook_feeds_the_ledger():
    """The production wiring: events offered to a METERED workload plane
    reach registered fold hooks at drain time; read-only from_state
    views never re-fire them."""
    seen = []
    wl.add_fold_hook(seen.append)
    wl.add_fold_hook(seen.append)        # idempotent registration
    try:
        w = WorkloadAnalytics(spans=(600.0,), keep=2,
                              sketch_capacity=8, meter=True)
        for i in range(5):
            w.offer({"kind": "count.scheduled", "type": "pts",
                     "plan_hash": "p", "tenant": "t",
                     "ts_ms": 1_000_000_000.0 + i, "duration_ms": 1.0,
                     "cell": "cA"})
        w.drain()
        assert len(seen) == 5
        WorkloadAnalytics.from_state(w.export_state()).hot_set(k=1)
        assert len(seen) == 5            # view rebuild is silent
    finally:
        wl._FOLD_HOOKS.remove(seen.append)


# -- state merge / federation -------------------------------------------------


def test_export_load_roundtrip_and_merge_sums():
    m = _two_shard_map()
    a = ShardWatch(workload=_wl_cells([]))
    a.set_shard_map("pts", m["cells"], m["key_ranges"])
    for _ in range(3):
        a.fold_event({"cell": "cA", "rows_scanned": 10, "device_ms": 1.0})
    b = ShardWatch(workload=_wl_cells([]))
    b.set_shard_map("pts", m["cells"], m["key_ranges"])
    b.fold_event({"cell": "cA", "rows_scanned": 5, "device_ms": 0.5})
    b.fold_event({"cell": "cB", "rows_scanned": 1, "device_ms": 0.1})
    merged = merge_states([a.export_state(), b.export_state(), {}])
    assert merged["cells"]["cA"] == [4, 35, 3.5]
    assert merged["cells"]["cB"] == [1, 1, 0.1]
    assert "pts" in merged["maps"]
    # round-trip through load_state preserves the join inputs
    c = ShardWatch(workload=_wl_cells(["cA"] * 10)).load_state(merged)
    rep = c.balance()
    assert rep["active"] and rep["cell_stats"]["tracked"] == 2


def test_fleet_balance_report_matches_single_process_oracle():
    """Split one event stream across two per-node planes + ledgers; the
    federated report's score equals the one-process oracle's."""
    m = _two_shard_map()
    events = ["cA"] * 60 + ["cB"] * 200 + ["cC"] * 40
    half1, half2 = events[::2], events[1::2]
    wl_states, sw_states = [], []
    for half in (half1, half2):
        w = _wl_cells(half)
        watch = ShardWatch(workload=w)
        watch.set_shard_map("pts", m["cells"], m["key_ranges"])
        for c in half:
            watch.fold_event({"cell": c, "rows_scanned": 2})
        wl_states.append(w.export_state())
        sw_states.append(watch.export_state())
    fleet = fleet_balance_report(wl.merge_states(wl_states), sw_states)
    oracle_w = _wl_cells(events)
    oracle = ShardWatch(workload=oracle_w)
    oracle.set_shard_map("pts", m["cells"], m["key_ranges"])
    for c in events:
        oracle.fold_event({"cell": c, "rows_scanned": 2})
    assert fleet["active"]
    fs = fleet["types"]["pts"]
    os_ = oracle.balance()["types"]["pts"]
    assert fs["score"]["max_over_mean"] == os_["score"]["max_over_mean"]
    assert fs["shards"]["1"]["load"] == os_["shards"]["1"]["load"]
    assert fs["shards"]["1"]["rows_scanned"] \
        == os_["shards"]["1"]["rows_scanned"]


# -- doctor rules -------------------------------------------------------------


class _FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, s):
        self.t += s


class _NoWorkload:
    def hot_set(self, k=None):
        return {"total": 0, "plans": [], "cells": []}

    def top_tenants(self, k=10):
        return []


def _mk_doctor(reg, clock, shardwatch=None):
    from geomesa_tpu.obs.doctor import DoctorEngine
    from geomesa_tpu.obs.incidents import IncidentStore
    from geomesa_tpu.obs.slo import SloEngine
    return DoctorEngine(
        registry=reg, clock=clock,
        slo_engine=SloEngine(registry=reg, clock=clock),
        federator=False, workload=_NoWorkload(),
        store=IncidentStore(journal_path="", registry=reg),
        shardwatch=shardwatch)


class _BalanceStub:
    def __init__(self):
        self.over = True

    def balance(self, k=None, parts=None):
        if not self.over:
            sc = {"max_over_mean": 1.01, "max_over_mean_est": 1.01,
                  "top_cell_fraction": 0.1, "imbalance": 1.11,
                  "hot_shard": "1", "guaranteed_total": 500.0,
                  "bar": 1.5, "min_load": 200, "over_bar": False}
        else:
            sc = {"max_over_mean": 1.9, "max_over_mean_est": 1.95,
                  "top_cell_fraction": 0.4, "imbalance": 2.3,
                  "hot_shard": "1", "guaranteed_total": 570.0,
                  "bar": 1.5, "min_load": 200, "over_bar": True}
        return {"active": True, "types": {"pts": {
            "score": sc,
            "shards": {"1": {"load_share": 0.95,
                             "key_range": [100, 199]}},
            "splits": {"shard": "1", "parts": 2,
                       "boundaries": [{"key": 150}]},
        }}}


def test_doctor_shard_imbalance_opens_attributes_and_resolves():
    reg = MetricsRegistry()
    clock = _FakeClock()
    stub = _BalanceStub()
    doc = _mk_doctor(reg, clock, shardwatch=stub)
    res = doc.evaluate()
    alerts = [a for a in res["alerts"] if a["rule"] == "shard_imbalance"]
    assert len(alerts) == 1
    a = alerts[0]
    assert a["cause"] == "shard:pts:1"
    assert a["suspect"] == {"type": "pts", "shard": "1",
                            "load_share": 0.95, "key_range": [100, 199]}
    assert a["detail"]["split_keys"] == [150]
    open_inc = [i for i in res["incidents"]
                if i["rule"] == "shard_imbalance"
                and i["status"] == "open"]
    assert len(open_inc) == 1
    # rebalanced: the verdict clears after DOCTOR_CLEAR_TICKS quiet evals
    stub.over = False
    for _ in range(int(config.DOCTOR_CLEAR_TICKS.get())):
        clock.advance(30)
        res = doc.evaluate()
    assert not [i for i in res["incidents"]
                if i["rule"] == "shard_imbalance"
                and i["status"] == "open"]


def test_doctor_shard_imbalance_quiet_when_ledger_inactive():
    class _Inactive:
        def balance(self, k=None, parts=None):
            return {"active": False, "reason": "no shard map registered"}

    reg = MetricsRegistry()
    doc = _mk_doctor(reg, _FakeClock(), shardwatch=_Inactive())
    assert not [a for a in doc.evaluate()["alerts"]
                if a["rule"] == "shard_imbalance"]


def test_doctor_collective_straggler_names_the_rank():
    config.DOCTOR_STRAGGLER_ROUNDS.set(5)
    reg = MetricsRegistry()
    clock = _FakeClock()
    doc = _mk_doctor(reg, clock, shardwatch=_BalanceStub())
    reg.inc("cluster.collective.rounds", 10)
    reg.inc("cluster.collective.straggler.rank1", 1)
    doc.evaluate()                       # first sighting: baseline only
    clock.advance(30)
    reg.inc("cluster.collective.rounds", 20)
    reg.inc("cluster.collective.straggler.rank1", 6)
    reg.inc("cluster.collective.straggler.rank0", 2)  # under the bar
    res = doc.evaluate()
    alerts = [a for a in res["alerts"]
              if a["rule"] == "collective_straggler"]
    assert len(alerts) == 1
    assert alerts[0]["cause"] == "collective:rank1"
    assert alerts[0]["suspect"] == {"rank": 1}
    assert alerts[0]["match"] == {"kind": "collective"}
    assert alerts[0]["detail"]["over_bar_rounds_in_window"] == 6


# -- collective telemetry (cluster/runtime.py) --------------------------------


def test_note_collective_counts_bytes_and_straggler_attribution():
    import importlib

    from geomesa_tpu.obs.flight import RECORDER
    crt = importlib.import_module("geomesa_tpu.cluster.runtime")

    before = REGISTRY.snapshot_prefixed("cluster.collective.")
    crt.note_collective("psum", 0.012, payload_bytes=256)
    after = REGISTRY.snapshot_prefixed("cluster.collective.")
    got = (after["counters"].get("cluster.collective.psum.bytes", 0)
           - before["counters"].get("cluster.collective.psum.bytes", 0))
    assert got == 256

    crt._reset_for_tests()
    try:
        forced = crt.ClusterRuntime(num_processes=2, process_id=0,
                                    initialized=True)
        crt._RUNTIME = forced
        config.DOCTOR_STRAGGLER_MS.set(50.0)
        b4 = REGISTRY.snapshot_prefixed("cluster.collective.")
        # the LAST arriver made everyone wait, so it measured the
        # SHORTEST round: slowest rank = argmin
        forced._note_straggler("allgather", [120.0, 4.0])
        aft = REGISTRY.snapshot_prefixed("cluster.collective.")
        key = "cluster.collective.straggler.rank1"
        assert (aft["counters"].get(key, 0)
                - b4["counters"].get(key, 0)) == 1
        evs = RECORDER.recent(kind="collective")
        assert evs and evs[0]["slowest_rank"] == 1
        assert evs[0]["process"] == 0 and evs[0]["shard"] == "0/2"
        # a tight round records nothing
        forced._note_straggler("allgather", [10.0, 11.0])
        aft2 = REGISTRY.snapshot_prefixed("cluster.collective.")
        assert aft2["counters"].get(key, 0) == aft["counters"].get(key, 0)
    finally:
        crt._reset_for_tests()
        RECORDER.clear()


# -- the empirical cell map (cluster/table.py) --------------------------------


def test_shard_cell_map_agrees_with_sketch_cell_keys():
    from geomesa_tpu.cluster.dryrun import inactive_runtime
    from geomesa_tpu.cluster.table import shard_cell_map

    rng = np.random.default_rng(5)
    n = 800
    xs = rng.uniform(-180, 180, n)
    ys = rng.uniform(-90, 90, n)
    keys = np.sort(rng.integers(0, 1 << 40, n).astype(np.int64))
    cells, key_ranges, shard_rows = shard_cell_map(
        inactive_runtime(), xs, ys, keys)
    assert list(key_ranges) == ["0"]
    assert key_ranges["0"] == [int(keys.min()), int(keys.max())]
    assert shard_rows["0"] == n
    assert sum(o["rows"] for owners in cells.values()
               for o in owners.values()) == n
    bits = int(config.WORKLOAD_CELL_BITS.get())
    for x, y, k in zip(xs[:100], ys[:100], keys[:100]):
        cell = cell_key(x, y, x, y, bits=bits)
        assert cell in cells, (x, y, cell)
        o = cells[cell]["0"]
        assert o["key_lo"] <= int(k) <= o["key_hi"]  # span covers member
        assert o["key_lo"] >= int(keys.min())
        assert o["key_hi"] <= int(keys.max())


# -- flight shard-dim conformance (ISSUE 16 satellite) ------------------------


def test_flight_shard_dims_survive_jsonl_roundtrip(tmp_path):
    """``process``/``shard`` dims stamped on flight events in a cluster
    survive the JSONL sink round-trip bit-exact (the replay surface the
    runbooks lean on)."""
    import importlib

    from geomesa_tpu.obs.flight import FlightRecorder
    crt = importlib.import_module("geomesa_tpu.cluster.runtime")

    crt._reset_for_tests()
    try:
        crt._RUNTIME = crt.ClusterRuntime(num_processes=2, process_id=1,
                                          initialized=True)
        dims = crt.event_dims()
        assert dims == {"process": 1, "shard": "1/2"}
        path = str(tmp_path / "events.jsonl")
        rec = FlightRecorder(keep=16, jsonl_path=path)
        rec.record({"ts_ms": 1.0, "kind": "query", "type": "pts",
                    "plan_hash": "p", "cell": "b6:abc",
                    "duration_ms": 1.0, **dims})
        got = rec.recent(kind="query")[0]
        rec.close()                      # flush the buffered sink
        with open(path) as fh:
            lines = [json.loads(ln) for ln in fh if ln.strip()]
        assert lines[-1]["process"] == 1 and lines[-1]["shard"] == "1/2"
        assert got["process"] == 1 and got["shard"] == "1/2"
    finally:
        crt._reset_for_tests()


def _get(url):
    with urllib.request.urlopen(url) as r:
        return r.status, json.loads(r.read())


def test_shard_dims_and_ledger_appear_in_federated_scrape():
    """A cluster-stamped event reaches the web surfaces intact: /events
    carries the process/shard dims, /metrics?format=state federates the
    shardwatch ledger state, and /cluster/balance serves the join."""
    import importlib

    from geomesa_tpu.datastore import TpuDataStore
    from geomesa_tpu.obs.flight import RECORDER
    from geomesa_tpu.web import serve
    crt = importlib.import_module("geomesa_tpu.cluster.runtime")

    crt._reset_for_tests()
    httpd = None
    try:
        crt._RUNTIME = crt.ClusterRuntime(num_processes=2, process_id=0,
                                          initialized=True)
        m = _two_shard_map()
        WATCH.set_shard_map("pts", m["cells"], m["key_ranges"])
        WATCH.fold_event({"cell": "cA", "rows_scanned": 7,
                          "device_ms": 0.2})
        RECORDER.record({"ts_ms": 1.0, "kind": "query", "type": "pts",
                         "plan_hash": "p", "cell": "cA",
                         "duration_ms": 1.0, **crt.event_dims()})
        ds = TpuDataStore()
        httpd = serve(ds, port=0, background=True)
        base = f"http://127.0.0.1:{httpd.server_address[1]}"
        status, body = _get(f"{base}/events?kind=query")
        ev = next(e for e in body["events"] if e.get("cell") == "cA")
        assert ev["process"] == 0 and ev["shard"] == "0/2"
        status, body = _get(f"{base}/metrics?format=state")
        swst = body["state"]["shardwatch"]
        assert "pts" in swst["maps"] and swst["cells"]["cA"][0] >= 1
        status, body = _get(f"{base}/cluster/balance")
        assert status == 200 and body["active"]
        assert "pts" in body["types"]
    finally:
        if httpd is not None:
            httpd.shutdown()
        crt._reset_for_tests()
        RECORDER.clear()


# -- CLI ----------------------------------------------------------------------


def test_cli_debug_balance_local_ledger(capsys):
    from geomesa_tpu.tools.cli import main

    m = _two_shard_map()
    WATCH.set_shard_map("pts", m["cells"], m["key_ranges"])
    main(["debug", "balance"])
    out = json.loads(capsys.readouterr().out)
    assert out["active"] and "pts" in out["types"]


# -- the real thing (slow: two worker processes a half) ------------------------


def _rank0_drill(report):
    r0 = next(r for r in report["ranks"] if r and r["process_id"] == 0)
    drill = r0["drill"]
    return drill, drill["balance"]["types"]["pts"]


@pytest.mark.slow
def test_two_process_balance_drill_verdict():
    """The two-sided verdict on a real 2-process fleet: a Zipf storm that
    rank 0 fires at the other rank's key range is flagged, opens exactly one
    shard_imbalance incident that names the victim shard, and projects split
    keys inside the victim's key range; the same number of events spread
    evenly reads balanced and opens none. Both halves still equal the
    single-process oracle, and the fleet verdict comes from both nodes."""
    from geomesa_tpu.cluster.dryrun import run_dryrun
    skew = run_dryrun(num_processes=2, n=8000, drill="skew", timeout_s=360)
    ctrl = run_dryrun(num_processes=2, n=8000, drill="uniform",
                      timeout_s=360)
    assert skew["ok"], json.dumps(skew["checks"], indent=1)
    assert ctrl["ok"], json.dumps(ctrl["checks"], indent=1)

    drill, pts = _rank0_drill(skew)
    victim = drill["victim"]
    assert pts["score"]["over_bar"]
    incidents = drill["imbalance_incidents"]
    assert len(incidents) == 1, incidents
    assert incidents[0]["suspect"]["shard"] == victim
    lo, hi = pts["shards"][victim]["key_range"]
    splits = pts["splits"]["boundaries"]
    assert splits and all(lo < b["key"] <= hi + 1 for b in splits), splits
    fleet = drill["fleet_balance"]
    assert len(fleet["nodes"]) == 2 and not fleet.get("partial")

    drill, pts = _rank0_drill(ctrl)
    assert drill["imbalance_incidents"] == []
    assert pts["score"]["max_over_mean"] <= 1.35

"""Fleet soak scoreboard (obs/soakfleet.py).

Tier-1 covers the pure scoring/summarising helpers deterministically —
bucket-delta percentiles, last-known-position backlog, precision/recall
against a fault schedule, the cfg11 metric flattening, and the
/fleet/soak web surface. The slow test runs the real thing: a
multi-process fleet soak (both halves) in-process.
"""

import json

import pytest

from geomesa_tpu.metrics import BUCKET_BOUNDS
from geomesa_tpu.obs import soakfleet


# -- pure helpers -------------------------------------------------------------


def test_hist_delta_percentile_scores_only_the_window():
    b0 = [0] * len(BUCKET_BOUNDS)
    b1 = list(b0)
    # 90 observations in bucket 3, 10 in bucket 7 — p50 reads bucket 3's
    # bound, p99 reads bucket 7's, both in ms
    b1[3] += 90
    b1[7] += 10
    assert soakfleet.hist_delta_percentile(b0, b1, 0.50) == \
        BUCKET_BOUNDS[3] * 1000.0
    assert soakfleet.hist_delta_percentile(b0, b1, 0.99) == \
        BUCKET_BOUNDS[7] * 1000.0
    # identical snapshots → no traffic in the window → 0.0, not a crash
    assert soakfleet.hist_delta_percentile(b1, b1, 0.99) == 0.0
    # a merged-histogram reset (counter went DOWN) clamps, never negative
    assert soakfleet.hist_delta_percentile(b1, b0, 0.99) == 0.0


def test_fleet_backlog_from_last_known_positions():
    seqs = {"p0": {"wal": 120}, "r1": {"applied": 120},
            "r2": {"applied": 95}}
    assert soakfleet.fleet_backlog(seqs, "p0", ["r1", "r2"]) == 25
    # a dead follower's applied_seq freezes while the head advances:
    # the backlog keeps growing even though the node can't report
    seqs["p0"]["wal"] = 200
    assert soakfleet.fleet_backlog(seqs, "p0", ["r1", "r2"]) == 105
    # no known head (primary never scraped) → no signal, not a spike
    assert soakfleet.fleet_backlog({}, "p0", ["r1"]) == 0
    assert soakfleet.fleet_backlog({"p0": {}}, "p0", ["r1"]) == 0


def _phase(name, expected=None, incidents=(), ok=None):
    p = {"name": name, "expected_rule": expected,
         "new_incidents": [{"rule": r, "status": "resolved"}
                           for r in incidents],
         "fleet_p50_ms": 1.0, "fleet_p99_ms": 5.0, "burn": 0.0,
         "requests": 10, "duration_s": 1.0}
    if expected is None:
        p["ok"] = not p["new_incidents"]
    else:
        p["ok"] = ok if ok is not None else (
            len(incidents) == 1 and incidents[0] == expected)
    return p


def test_score_phases_perfect_run():
    phases = [
        _phase("steady"),
        _phase("rolling_restart", "replication_lag", ["replication_lag"]),
        _phase("reindex_churn", "reindex_churn", ["reindex_churn"]),
        _phase("recovery"),
    ]
    s = soakfleet.score_phases(phases)
    assert s["precision"] == 1.0 and s["recall"] == 1.0
    assert s["fault_phases"] == 2 and s["detected"] == 2
    assert s["incidents_total"] == 2 and s["false_positives"] == 0


def test_score_phases_false_positive_breaks_precision_not_recall():
    # an incident during steady is a false positive BY CONSTRUCTION —
    # there is no fault scheduled there
    phases = [
        _phase("steady", incidents=["slo_burn"]),
        _phase("lag_spike", "replication_lag", ["replication_lag"]),
    ]
    s = soakfleet.score_phases(phases)
    assert s["recall"] == 1.0
    assert s["precision"] == 0.5
    assert s["false_positives"] == 1


def test_score_phases_missed_fault_breaks_recall():
    phases = [
        _phase("lag_spike", "replication_lag", []),   # slept through it
        _phase("reindex_churn", "reindex_churn", ["reindex_churn"]),
    ]
    s = soakfleet.score_phases(phases)
    assert s["recall"] == 0.5
    assert s["precision"] == 1.0


def test_score_phases_wrong_rule_counts_against_both():
    phases = [
        _phase("lag_spike", "replication_lag", ["shed_storm"], ok=False),
    ]
    s = soakfleet.score_phases(phases)
    assert s["recall"] == 0.0
    assert s["precision"] == 0.0


def test_percentile_ms_edges():
    assert soakfleet.percentile_ms([], 0.99) == 0.0
    assert soakfleet.percentile_ms([3.0], 0.99) == 3.0
    vals = [float(i) for i in range(1, 101)]
    assert soakfleet.percentile_ms(vals, 0.50) == 50.0
    assert soakfleet.percentile_ms(vals, 0.99) == 99.0


# -- scoreboard flattening ----------------------------------------------------


def _board():
    chaos = {
        "mode": "chaos", "ok": True, "duration_s": 60.0,
        "phases": [
            dict(_phase("steady"), fleet_p50_ms=0.4, fleet_p99_ms=8.0),
            _phase("lag_spike", "replication_lag", ["replication_lag"]),
        ],
        "doctor": {"precision": 1.0, "recall": 1.0, "fault_phases": 1,
                   "detected": 1, "incidents_total": 1, "correct": 1,
                   "false_positives": 0},
        "slo": {"worst_fault_phase_burn": 0.0, "overall_worst_burn": 0.0,
                "partial_outside_fault_windows": 0,
                "pages_while_partial": 0},
        "failover": {"old_primary": "p0", "promoted": "r2",
                     "duration_ms": 21.5, "budget_ms": 5000.0,
                     "within_budget": True, "count_at_promote": 840,
                     "expected": 840, "no_acked_loss": True},
        "catchup_s": 2.3,
        "honesty": {"node": "r2", "forced_refreshes": 4,
                    "scrape_errors_delta": 4, "scrape_errors_exact": True,
                    "partial_during_kill": True, "missing_exact": True,
                    "clean_after_respawn": True, "partial_cleared": True},
        "cache": {"hit_rate": 0.66, "hits": 660, "misses": 340,
                  "victim_tenant": "tenant7", "victim_samples": 50,
                  "victim_p99_ms": 15.0},
        "conservation": {"expected_rows": 1000, "final_count": 1000,
                         "loss": 0, "fingerprints": {},
                         "fingerprints_matched": True},
        "traffic": {"requests": 4000, "errors": 0}, "notes": [],
    }
    clean = {
        "mode": "clean", "ok": True, "duration_s": 45.0,
        "phases": [dict(_phase("steady"),
                        fleet_p50_ms=0.3, fleet_p99_ms=7.0)],
        "doctor": {"precision": 1.0, "recall": 1.0, "fault_phases": 0,
                   "detected": 0, "incidents_total": 0, "correct": 0,
                   "false_positives": 0},
        "slo": {"worst_fault_phase_burn": 0.0, "overall_worst_burn": 0.0,
                "partial_outside_fault_windows": 0,
                "pages_while_partial": 0},
        "failover": None, "catchup_s": None, "honesty": None,
        "cache": {"hit_rate": 0.67, "hits": 670, "misses": 330,
                  "victim_tenant": "tenant7", "victim_samples": 50,
                  "victim_p99_ms": 12.0},
        "conservation": {"expected_rows": 300, "final_count": 300,
                         "loss": 0, "fingerprints": {},
                         "fingerprints_matched": True},
        "traffic": {"requests": 2500, "errors": 0}, "notes": [],
    }
    return {"ok": True, "mini": True,
            "halves": {"chaos": chaos, "clean": clean}}


def test_scoreboard_metrics_flatten_and_types():
    m = soakfleet.scoreboard_metrics(_board())
    assert m["cfg11_doctor_precision"] == 1.0
    assert m["cfg11_doctor_recall"] == 1.0
    assert m["cfg11_acked_write_loss"] == 0
    assert m["cfg11_clean_incidents"] == 0
    assert m["cfg11_failover_ms"] == 21.5
    assert m["cfg11_catchup_s"] == 2.3
    assert m["cfg11_steady_fleet_p50_ms"] == 0.4
    assert m["cfg11_storm_cache_hit_rate"] == 0.66
    # the fingerprint check flattens to an int (a scoreboard metric is a
    # number), and it ANDs both halves
    assert m["cfg11_fingerprints_matched"] == 1
    assert not isinstance(m["cfg11_fingerprints_matched"], bool)
    b = _board()
    b["halves"]["clean"]["conservation"]["fingerprints_matched"] = False
    assert soakfleet.scoreboard_metrics(b)["cfg11_fingerprints_matched"] == 0


def test_render_scoreboard_carries_the_story():
    board = _board()
    board["metrics"] = soakfleet.scoreboard_metrics(board)
    text = soakfleet.render_scoreboard(board)
    assert "# Fleet soak scoreboard" in text
    for needle in ("chaos half", "clean half", "precision", "recall",
                   "failover", "conservation", "cfg11_failover_ms",
                   "cfg11_doctor_precision"):
        assert needle in text, needle


def test_last_run_file_fallback(tmp_path, monkeypatch):
    monkeypatch.setattr(soakfleet, "LAST", None)
    path = tmp_path / "board.json"
    monkeypatch.setenv("GEOMESA_TPU_SOAK_SCOREBOARD", str(path))
    assert soakfleet.last_run() is None          # no file yet
    path.write_text(json.dumps(_board()))
    board = soakfleet.last_run()
    assert board and board["ok"] is True
    # an in-process run wins over the file
    monkeypatch.setattr(soakfleet, "LAST", {"ok": False, "marker": 1})
    assert soakfleet.last_run()["marker"] == 1


# -- web surface --------------------------------------------------------------


def test_fleet_soak_route(monkeypatch):
    from geomesa_tpu.web.server import GeoJsonApi
    api = GeoJsonApi(object())       # the route never touches the store
    monkeypatch.setattr(soakfleet, "LAST", None)
    monkeypatch.setenv("GEOMESA_TPU_SOAK_SCOREBOARD",
                       "/nonexistent/never.json")
    status, body = api.handle("GET", "/fleet/soak", {})
    assert status == 404
    monkeypatch.setattr(soakfleet, "LAST", _board())
    status, body = api.handle("GET", "/fleet/soak", {})
    assert status == 200 and body["ok"] is True
    assert body["halves"]["chaos"]["doctor"]["precision"] == 1.0


def test_flush_route_forces_delta_merge(tmp_path):
    from geomesa_tpu.datastore import TpuDataStore
    from geomesa_tpu.replication import drills
    from geomesa_tpu.web.server import GeoJsonApi
    store = TpuDataStore.open(str(tmp_path / "s"),
                              {"wal.fsync": "off", "scheduler": False})
    try:
        sft = store.create_schema("t", drills.SPEC)
        store.load("t", drills.make_batch(sft, 0, n=8))
        api = GeoJsonApi(store)
        status, body = api.handle("POST", "/types/t/flush", {})
        assert status == 200 and body["flushed"] == "t"
        # the delta tier merged into main — a second flush is a no-op
        # but still well-formed
        status, _ = api.handle("POST", "/types/t/flush", {})
        assert status == 200
        assert store.count("t") == 8
    finally:
        store.close()


# -- the real thing (slow: multi-process fleet) -------------------------------


@pytest.mark.slow
def test_mini_soak_both_halves(tmp_path):
    """The acceptance drill: a real fleet (primary + 2 followers +
    router as subprocesses), chaos half AND clean control half, scored
    two-sided."""
    board = soakfleet.run(mini=True,
                          scoreboard_path=str(tmp_path / "board.json"),
                          base_dir=str(tmp_path / "fleet"))
    assert board["ok"], json.dumps(board, indent=1, default=str)[:4000]
    ch = board["halves"]["chaos"]
    cl = board["halves"]["clean"]

    # chaos side: every injected fault → exactly one correctly-attributed
    # incident, none anywhere else
    assert ch["doctor"]["precision"] == 1.0
    assert ch["doctor"]["recall"] == 1.0
    assert ch["doctor"]["false_positives"] == 0
    assert ch["failover"]["within_budget"]
    assert ch["failover"]["no_acked_loss"]
    # federation honesty while a node was dead: partial flagged, the
    # dead node listed, per-node scrape_errors exact, paging suppressed
    h = ch["honesty"]
    assert h["scrape_errors_exact"] and h["partial_during_kill"]
    assert h["missing_exact"] and h["clean_after_respawn"]
    assert ch["slo"]["pages_while_partial"] == 0
    assert ch["slo"]["partial_outside_fault_windows"] == 0
    # conservation: no acked write lost, surviving stores byte-identical
    assert ch["conservation"]["loss"] == 0
    assert ch["conservation"]["fingerprints_matched"]
    assert ch["traffic"]["errors"] == 0

    # clean side: the control — zero incidents, nothing partial
    assert cl["doctor"]["incidents_total"] == 0
    assert cl["slo"]["partial_outside_fault_windows"] == 0
    assert cl["conservation"]["loss"] == 0
    assert cl["conservation"]["fingerprints_matched"]

    # artifacts: scoreboard JSON + markdown twin
    assert (tmp_path / "board.json").exists()
    assert (tmp_path / "board.md").exists()
    assert "cfg11_doctor_precision" in (tmp_path / "board.md").read_text()

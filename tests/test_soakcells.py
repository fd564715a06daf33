"""Cluster cell chaos soak (obs/soakcells.py): the pure scoring /
flattening / rendering helpers run tier-1; the real two-half soak
(multi-process fleet, SIGKILL drills) is slow-marked for the CI
``cluster-v2`` job.
"""

import json

import pytest

from geomesa_tpu.obs import soakcells


def _fake_half(faulted=True, loss=0, fp=True, refusals=2,
               detected=True, partial=True, names_range=True,
               incidents=0):
    phases = [{"name": "steady", "expected_rule": None,
               "duration_s": 5.0, "p50_ms": 3.0, "p99_ms": 9.0,
               "requests": 100, "new_incidents": [], "ok": True}]
    if faulted:
        phases.append({"name": "shard_dark",
                       "expected_rule": "shard_dark",
                       "duration_s": 6.0, "p50_ms": 4.0,
                       "p99_ms": 12.0, "requests": 80,
                       "new_incidents": [{"rule": "shard_dark"}],
                       "ok": True})
    return {
        "mode": "chaos" if faulted else "clean",
        "ok": True,
        "duration_s": 11.0,
        "rows": 200,
        "acked": 200,
        "phases": phases,
        "doctor": {"precision": 1.0, "recall": 1.0,
                   "fault_phases": 1 if faulted else 0,
                   "detected": 1 if faulted else 0,
                   "incidents_total": incidents, "correct": incidents,
                   "false_positives": 0},
        "failover": ({"shard": "s0", "old_primary": "s0p",
                      "promoted": "s0r", "duration_ms": 25.0,
                      "budget_ms": 5000.0, "within_budget": True,
                      "epoch": 2} if faulted else None),
        "handoff": ({"shard": "s1", "old_owner": "s1p",
                     "new_owner": "s1r", "caught_up": True,
                     "head_seq": 3, "epoch": 2, "duration_ms": 14.0}
                    if faulted else None),
        "split_brain": {"refusals": refusals if faulted else 0,
                        "attempts": ([{"node": "s0p", "refused": True},
                                      {"node": "s1p", "refused": True}]
                                     if faulted else [])},
        "dark": {"detected": detected if faulted else False,
                 "resolved": True},
        "partial_envelope": ({"partial": partial,
                              "missing_shards": [],
                              "names_range": names_range}
                             if faulted else None),
        "conservation": {"expected_rows": 200, "acked_ingests": 200,
                         "final_count": 200 - loss, "loss": loss,
                         "final_partial": False,
                         "fingerprints_matched": fp},
        "checks": {"zero_loss": loss == 0},
        "counts": [],
        "notes": [],
    }


def _fake_board(**kw):
    return {"schema": 1, "mini": True, "ok": True,
            "halves": {"chaos": _fake_half(True, **kw),
                       "clean": _fake_half(False)}}


class TestScoreboardMetrics:
    def test_exact_axes_flattened(self):
        m = soakcells.scoreboard_metrics(_fake_board())
        assert m["cfg16_failover_within_budget"] == 1.0
        assert m["cfg16_acked_write_loss"] == 0.0
        assert m["cfg16_split_brain_refused"] == 2.0
        assert m["cfg16_doctor_precision"] == 1.0
        assert m["cfg16_doctor_recall"] == 1.0
        assert m["cfg16_clean_incidents"] == 0.0
        assert m["cfg16_shard_dark_fired"] == 1.0
        assert m["cfg16_partial_envelope_seen"] == 1.0
        assert m["cfg16_fingerprints_matched"] == 1.0

    def test_statistical_axes_flattened(self):
        m = soakcells.scoreboard_metrics(_fake_board())
        assert m["cfg16_steady_p50_ms"] == 3.0
        assert m["cfg16_steady_p99_ms"] == 9.0
        assert m["cfg16_failover_ms"] == 25.0
        assert m["cfg16_handoff_ms"] == 14.0

    def test_loss_sums_both_halves(self):
        board = _fake_board()
        board["halves"]["clean"]["conservation"]["loss"] = 3
        m = soakcells.scoreboard_metrics(board)
        assert m["cfg16_acked_write_loss"] == 3.0

    def test_fingerprint_mismatch_in_either_half_fails_the_axis(self):
        board = _fake_board()
        board["halves"]["clean"]["conservation"][
            "fingerprints_matched"] = False
        m = soakcells.scoreboard_metrics(board)
        assert m["cfg16_fingerprints_matched"] == 0.0

    def test_partial_envelope_must_name_the_range(self):
        # an envelope that says partial but not WHICH key range is
        # absent does not satisfy the contract
        m = soakcells.scoreboard_metrics(_fake_board(names_range=False))
        assert m["cfg16_partial_envelope_seen"] == 0.0

    def test_chaos_only_board(self):
        board = _fake_board()
        del board["halves"]["clean"]
        m = soakcells.scoreboard_metrics(board)
        assert "cfg16_clean_incidents" not in m
        assert m["cfg16_acked_write_loss"] == 0.0


class TestRenderScoreboard:
    def test_render_names_the_drills(self):
        board = _fake_board()
        board["metrics"] = soakcells.scoreboard_metrics(board)
        md = soakcells.render_scoreboard(board)
        assert "# Cluster cell soak scoreboard" in md
        assert "## chaos half (PASS" in md
        assert "## clean half (PASS" in md
        assert "s0p → s0r in 25.0ms" in md
        assert "s1p → s1r in 14.0ms" in md
        assert "2/2 fenced losers refused" in md
        assert "cfg16_split_brain_refused" in md
        assert "fingerprints_matched=True" in md

    def test_render_flags_failed_checks(self):
        board = _fake_board()
        board["halves"]["chaos"]["ok"] = False
        board["halves"]["chaos"]["checks"]["zero_loss"] = False
        md = soakcells.render_scoreboard(board)
        assert "## chaos half (FAIL" in md
        assert "FAILED checks: zero_loss" in md

    def test_render_is_json_free_roundtrip(self):
        board = _fake_board()
        json.dumps(board)  # the scoreboard itself must be serializable
        md = soakcells.render_scoreboard(board)
        assert md.endswith("\n")


@pytest.mark.slow
def test_cell_soak_two_halves_end_to_end(tmp_path):
    """The real thing: chaos half (failover, handoff, split-brain,
    dark shard) + clean control, scored two-sided."""
    board = soakcells.run(mini=True,
                          scoreboard_path=str(tmp_path / "board.json"))
    assert board["ok"], json.dumps(
        {h: half["checks"] for h, half in board["halves"].items()},
        default=str)
    m = board["metrics"]
    assert m["cfg16_acked_write_loss"] == 0.0
    assert m["cfg16_split_brain_refused"] == 2.0
    assert m["cfg16_doctor_precision"] == 1.0
    assert m["cfg16_doctor_recall"] == 1.0
    assert m["cfg16_clean_incidents"] == 0.0
    assert m["cfg16_failover_within_budget"] == 1.0
    assert m["cfg16_fingerprints_matched"] == 1.0
    assert m["cfg16_shard_dark_fired"] == 1.0
    assert m["cfg16_partial_envelope_seen"] == 1.0
    assert (tmp_path / "board.json").exists()
    assert (tmp_path / "board.md").exists()

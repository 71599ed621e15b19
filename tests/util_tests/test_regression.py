"""Perf regression sentinel (ISSUE 15): noise-aware verdict math over
bench history, workload matching, and the ``--check`` wiring
through ``_bench_common.run_child_with_retries`` — fresh records are
scored BEFORE they join the history, verdicts ride the one JSON line,
and the exit code goes red only on a regression."""

import json
import os
import sys

import pytest

from chainermn_tpu.utils import regression

_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", ".."))


class TestVerdictMath:
    def test_no_history_is_evidence_not_a_verdict(self):
        out = regression.check_value(5.0, [], min_history=2)
        assert out["verdict"] == "no_history" and out["n_history"] == 0
        out = regression.check_value(5.0, [5.0], min_history=2)
        assert out["verdict"] == "no_history"

    def test_pass_within_slack_floor(self):
        # perfectly repeatable history: sigma 0, the 5% floor rules
        hist = [100.0, 100.0, 100.0]
        assert regression.check_value(96.0, hist)["verdict"] == "pass"
        assert regression.check_value(104.9, hist)["verdict"] == "pass"
        out = regression.check_value(94.9, hist)
        assert out["verdict"] == "regression"
        assert out["lower_bound"] == pytest.approx(95.0)
        assert regression.check_value(105.1, hist)["verdict"] \
            == "improved"

    def test_noise_widens_the_bound(self):
        # noisy history: 3 × (1.4826 × MAD) beats the 5% floor
        hist = [100.0, 90.0, 110.0, 95.0, 105.0]
        b = regression.noise_bounds(hist)
        assert b["median"] == 100.0
        assert b["slack"] == pytest.approx(3 * 1.4826 * 5.0)
        out = regression.check_value(85.0, hist)
        assert out["verdict"] == "pass"      # inside the noise band
        assert regression.check_value(70.0, hist)["verdict"] \
            == "regression"

    def test_direction_lower_is_better(self):
        hist = [10.0, 10.0, 10.0]
        assert regression.check_value(
            11.0, hist, direction="lower")["verdict"] == "regression"
        assert regression.check_value(
            9.0, hist, direction="lower")["verdict"] == "improved"
        with pytest.raises(ValueError):
            regression.check_value(1.0, hist, direction="sideways")

    def test_median_robust_to_one_outlier(self):
        hist = [100.0, 101.0, 99.0, 100.0, 5.0]    # one burst-hit run
        out = regression.check_value(97.0, hist)
        assert out["baseline_median"] == 100.0
        assert out["verdict"] == "pass"


class TestHistoryFiltering:
    RUNS = [
        {"metric": "m", "value": 100.0, "batch": 256},
        {"metric": "m", "value": 101.0, "batch": 256},
        {"metric": "m", "value": 50.0, "batch": 4},      # toy debug run
        {"metric": "m", "value": None, "batch": 256},    # failed run
        {"metric": "m", "value": 60.0, "batch": 256,
         "check_verdict": "regression"},  # sentinel-flagged regression
        {"metric": "other", "value": 7.0},
        {"metric": "m", "value": 102.0},                 # legacy, no batch
    ]

    def test_workload_match_and_exclusions(self):
        vals = regression.history_values(self.RUNS, "m",
                                         match={"batch": 256})
        # the toy run is excluded; the null and
        # regression-flagged rows are excluded (a flagged regression
        # must not re-anchor the baseline); the legacy batch-less row
        # passes (leniency that retires itself)
        assert vals == [100.0, 101.0, 102.0]
        assert regression.history_values(self.RUNS, "other") == [7.0]

    def test_check_record(self, tmp_path):
        path = tmp_path / "hist.json"
        path.write_text(json.dumps({"runs": self.RUNS}))
        hist = regression.load_history(str(path))
        out = regression.check_record(
            {"metric": "m", "value": 98.0}, hist,
            match={"batch": 256})
        assert out["verdict"] == "pass" and out["n_history"] == 3
        out = regression.check_record(
            {"metric": "m", "value": None}, hist)
        assert out["verdict"] == "no_result"

    def test_stale_history_never_anchors(self):
        """Timestamped runs past the age cutoff are excluded: a verdict
        against a weeks-old baseline is not a verdict about this
        tree.  Legacy un-timestamped entries pass."""
        import datetime

        fresh = datetime.datetime.now(
            datetime.timezone.utc).isoformat(timespec="seconds")
        runs = [
            {"metric": "m", "value": 100.0,
             "timestamp": "2020-01-01T00:00:00+00:00"},
            {"metric": "m", "value": 50.0, "timestamp": fresh},
            {"metric": "m", "value": 51.0},     # legacy, no timestamp
        ]
        assert regression.history_values(runs, "m") == [50.0, 51.0]
        assert regression.history_values(
            runs, "m", max_age_days=None) == [100.0, 50.0, 51.0]

    def test_load_history_degrades(self, tmp_path):
        assert regression.load_history(
            str(tmp_path / "missing.json")) == []
        bad = tmp_path / "bad.json"
        bad.write_text("{not json")
        assert regression.load_history(str(bad)) == []


class TestBenchCheckWiring:
    """--check through run_child_with_retries against a scratch
    history: scored before recording, verdict on the line, exit code
    red only on regression (the test_bench_contract driving style)."""

    @pytest.fixture()
    def bc(self, tmp_path, monkeypatch):
        sys.path.insert(0, _ROOT)
        try:
            import _bench_common as bc
        finally:
            sys.path.pop(0)
        monkeypatch.setattr(bc, "HISTORY_PATH",
                            str(tmp_path / "history.json"))
        return bc

    @staticmethod
    def _ok_cmd(value, **extra):
        rec = {"metric": "m", "value": value, "unit": "u",
               "vs_baseline": 1.0, **extra}
        return [sys.executable, "-c",
                f"print('BENCH_RESULT ' + {json.dumps(json.dumps(rec))})"]

    def test_first_runs_are_no_history_then_pass(self, bc, tmp_path,
                                                 capsys):
        # run 1: nothing to compare against — green, not a failure
        assert bc.run_child_with_retries(
            self._ok_cmd(100.0), str(tmp_path), [30], "m", "u",
            check=True) == 0
        rec = json.loads(capsys.readouterr().out.strip())
        assert rec["check"]["verdict"] == "no_history"
        assert rec["check"]["n_history"] == 0   # scored BEFORE append
        # run 2: one prior — still below min_history
        assert bc.run_child_with_retries(
            self._ok_cmd(100.0), str(tmp_path), [30], "m", "u",
            check=True) == 0
        rec = json.loads(capsys.readouterr().out.strip())
        assert rec["check"]["verdict"] == "no_history"
        # run 3: two matching priors — a real verdict
        assert bc.run_child_with_retries(
            self._ok_cmd(99.0), str(tmp_path), [30], "m", "u",
            check=True) == 0
        rec = json.loads(capsys.readouterr().out.strip())
        assert rec["check"]["verdict"] == "pass"
        # the verdict never pollutes the history entries
        cache = json.load(open(bc.HISTORY_PATH))
        assert all("check" not in r for r in cache["runs"])

    def test_regression_goes_red(self, bc, tmp_path, capsys):
        for v in (100.0, 100.0, 100.0):
            assert bc.run_child_with_retries(
                self._ok_cmd(v), str(tmp_path), [30], "m", "u") == 0
            capsys.readouterr()
        assert bc.run_child_with_retries(
            self._ok_cmd(80.0), str(tmp_path), [30], "m", "u",
            check=True) == 1
        rec = json.loads(capsys.readouterr().out.strip())
        assert rec["check"]["verdict"] == "regression"
        assert rec["check"]["baseline_median"] == 100.0
        # the regressed record is stamped in the history, so CI
        # re-running the regressed tree CANNOT pull the baseline
        # down until the gate self-normalizes: every re-run keeps
        # scoring against the clean 100.0 history and stays red
        cache = json.load(open(bc.HISTORY_PATH))
        assert cache["runs"][-1]["check_verdict"] == "regression"
        for _ in range(3):
            assert bc.run_child_with_retries(
                self._ok_cmd(80.0), str(tmp_path), [30], "m", "u",
                check=True) == 1
            rec = json.loads(capsys.readouterr().out.strip())
            assert rec["check"]["baseline_median"] == 100.0
        # without --check the same run stays contract-green
        assert bc.run_child_with_retries(
            self._ok_cmd(80.0), str(tmp_path), [30], "m", "u") == 0

    def test_smoke_runs_are_never_gated(self, bc, tmp_path, capsys):
        """A platform-pinned smoke run (record=False) under --check
        gets the non-gating "smoke" verdict: its records are excluded
        from the hardware history, so scoring it against that history
        would gate a toy CPU number on a foreign-device baseline."""
        for v in (100.0, 100.0, 100.0):     # hardware history
            assert bc.run_child_with_retries(
                self._ok_cmd(v), str(tmp_path), [30], "m", "u") == 0
            capsys.readouterr()
        assert bc.run_child_with_retries(
            self._ok_cmd(2.0), str(tmp_path), [30], "m", "u",
            record=False, check=True) == 0
        rec = json.loads(capsys.readouterr().out.strip())
        assert rec["check"]["verdict"] == "smoke"
        # and the smoke run left no history entry behind
        assert all(r["value"] != 2.0
                   for r in json.load(open(bc.HISTORY_PATH))["runs"])

    def test_device_kind_joins_the_match(self, bc, tmp_path, capsys):
        """A fresh record carrying device_kind is only scored against
        history of the SAME device kind — a first TPU run after an
        all-CPU history is no_history, not a meaningless verdict."""
        for v in (100.0, 101.0, 99.0):
            assert bc.run_child_with_retries(
                self._ok_cmd(v, device_kind="cpu"), str(tmp_path),
                [30], "m", "u") == 0
            capsys.readouterr()
        assert bc.run_child_with_retries(
            self._ok_cmd(3000.0, device_kind="TPU v5 lite"),
            str(tmp_path), [30], "m", "u", check=True) == 0
        rec = json.loads(capsys.readouterr().out.strip())
        assert rec["check"]["verdict"] == "no_history"
        # same-kind scoring still works
        assert bc.run_child_with_retries(
            self._ok_cmd(100.0, device_kind="cpu"), str(tmp_path),
            [30], "m", "u", check=True) == 0
        rec = json.loads(capsys.readouterr().out.strip())
        assert rec["check"]["verdict"] == "pass"

    def test_total_failure_under_check_is_red(self, bc, tmp_path,
                                              capsys):
        bad = [sys.executable, "-c", "raise SystemExit(3)"]
        assert bc.run_child_with_retries(
            bad, str(tmp_path), [30], "m", "u", check=True) == 1
        rec = json.loads(capsys.readouterr().out.strip())
        assert rec["value"] is None
        assert rec["check"]["verdict"] == "no_result"

    def test_history_is_never_served_for_a_live_failure(
            self, bc, tmp_path, capsys):
        for v in (100.0, 101.0):
            assert bc.run_child_with_retries(
                self._ok_cmd(v), str(tmp_path), [30], "m", "u") == 0
            capsys.readouterr()
        bad = [sys.executable, "-c", "raise SystemExit(3)"]
        # live failure + a fresh history: the failure is reported as
        # what it is — null, the diagnosis, a red exit code with or
        # without --check — never an earlier run's value
        for check in (True, False):
            assert bc.run_child_with_retries(
                bad, str(tmp_path), [30], "m", "u", check=check) == 1
            rec = json.loads(capsys.readouterr().out.strip())
            assert rec["value"] is None and "rc=3" in rec["error"]
            assert "cached" not in rec


def test_bench_scripts_wire_the_check_flag():
    """``bench_programs.py --check`` reaches
    ``run_child_with_retries(check=...)`` — the one-line wiring that
    makes any bench script self-verify.  Source-level pin (the check
    semantics are unit-tested above through the same
    run_child_with_retries entrypoint the scripts call)."""
    src = open(os.path.join(_ROOT, "bench_programs.py")).read()
    assert '"--check"' in src
    assert "check=args.check" in src

"""Bench-regression sentry white-box tests (tools/perf_sentry.py): the
BENCH_r* trajectory as a machine-checked ledger — synthetic regressions
flagged, the real committed trajectory inside its noise bands."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools import perf_sentry  # noqa: E402


def _releases(stat, values, start=1):
    return [
        {"release": f"r{n:02d}", "n": n, "stats": {stat: v}}
        for n, v in enumerate(values, start=start)
    ]


def test_injected_regression_flagged_lower_is_better():
    releases = _releases("etl_query_s", [0.070, 0.072, 0.069, 0.071, 0.070])
    baseline = perf_sentry.derive_baselines(releases)
    # 2x slower is far outside any noise band the stable series produced
    failures = perf_sentry.check_release({"etl_query_s": 0.145}, baseline)
    assert failures and "etl_query_s" in failures[0]
    # within-band drift passes
    assert perf_sentry.check_release({"etl_query_s": 0.078}, baseline) == []


def test_injected_regression_flagged_higher_is_better():
    releases = _releases("e2e_sps", [300e3, 310e3, 295e3, 305e3])
    baseline = perf_sentry.derive_baselines(releases)
    failures = perf_sentry.check_release({"e2e_sps": 150e3}, baseline)
    assert failures and "e2e_sps" in failures[0]
    assert perf_sentry.check_release({"e2e_sps": 290e3}, baseline) == []


def test_noise_band_floor_and_clamp():
    # the r06 lesson: no band tighter than ±25% box noise...
    assert perf_sentry.noise_band([1.0, 1.001, 1.002, 1.0]) == (
        perf_sentry.MIN_BAND
    )
    # ...and one wild historical swing doesn't make a stat ungateable
    assert perf_sentry.noise_band([1.0, 5.0, 1.0, 5.0]) == (
        perf_sentry.MAX_BAND
    )
    # too few points = a sample, not a distribution
    assert perf_sentry.noise_band([1.0, 2.0]) == perf_sentry.MAX_BAND


def test_stats_a_release_does_not_report_are_skipped():
    releases = _releases("etl_query_s", [0.07, 0.07, 0.07])
    baseline = perf_sentry.derive_baselines(releases)
    # a release reporting an untracked/new stat fails nothing
    assert perf_sentry.check_release({"brand_new_stat": 1.0}, baseline) == []


def test_ledger_schema_validation():
    good = perf_sentry.build_ledger()
    perf_sentry.validate_ledger(good)  # committed repo state validates
    with pytest.raises(ValueError):
        perf_sentry.validate_ledger({"format": "wrong"})
    bad = json.loads(json.dumps(good))
    bad["releases"][0]["stats"]["e2e_sps"] = "fast"
    with pytest.raises(ValueError):
        perf_sentry.validate_ledger(bad)
    unordered = json.loads(json.dumps(good))
    unordered["releases"] = unordered["releases"][::-1]
    with pytest.raises(ValueError):
        perf_sentry.validate_ledger(unordered)


def test_real_trajectory_passes_committed_baseline():
    """Acceptance: --check semantics pass on the full committed BENCH_r05→
    r18 trajectory against the committed BENCH_BASELINE.json."""
    ledger = perf_sentry.build_ledger()
    assert len(ledger["releases"]) >= 10  # r05..r18 minus gaps: exactly 10
    committed = perf_sentry.load_baseline()
    assert committed, "BENCH_BASELINE.json missing or invalid"
    newest = ledger["releases"][-1]
    failures = perf_sentry.check_release(newest["stats"], committed)
    assert failures == [], failures


def test_truncated_tail_snapshot_still_parses():
    """r05's stdout tail is front-truncated (no parseable JSON line) — the
    per-stat regex fallback must still extract its stats."""
    release, stats = perf_sentry._parse_snapshot(
        os.path.join(REPO, "BENCH_r05.json")
    )
    assert release == 5
    assert stats.get("etl_query_s") == pytest.approx(0.296)


def test_cli_check_passes(capsys):
    assert perf_sentry.main(["--check"]) == 0
    assert "PERF-SENTRY OK" in capsys.readouterr().out


def test_perf_smoke_reads_sentry_thresholds():
    """Satellite: perf_smoke's thresholds come from the committed ledger
    (the hardcoded r08 fallback remains for checkouts without it)."""
    from tools import perf_smoke

    baseline = perf_smoke._sentry_baseline()
    assert baseline, "perf_smoke did not load the sentry ledger"
    assert "etl_query_s" in baseline and baseline["etl_query_s"]["value"] > 0
    # the legacy snapshot path still answers (the fallback stays alive)
    assert perf_smoke.snapshot_etl_query_s() is not None

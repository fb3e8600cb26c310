"""Shared diag formatter/parser (harness/diagfmt.py) and the
perf-regression report (tools/perf_report.py).

Covers the contracts the telemetry pipeline rests on:

- the ``diag:`` line round-trips through the ONE writer
  (``diagfmt.format_*``) and the ONE parser (``diagfmt.parse_diag``);
- the parser still reads the legacy hand-rolled format frozen into the
  committed ``BENCH_r03..r05`` tails;
- the e2e segment is rendered from the metrics-registry histogram's own
  accessors, so ``diag:`` and ``/metrics`` cannot disagree;
- a synthetic bench history with a deliberate out-of-band regression
  AND a within-noise wobble flags exactly the regression, with phase
  attribution from the row's telemetry;
- every committed ``BENCH_r*.json`` in the repo parses under the driver
  schema (tier-1 smoke: a malformed round fails CI, not a human);
- the headline row's ``telemetry`` sub-object survives into the
  driver-captured stdout tail (the same trap the REST row hit pre-PR 5:
  a row that prints too early falls off the tail).
"""

from __future__ import annotations

import json
import os

import pytest

from kubernetes_tpu.harness import diagfmt
from tools.perf_report import (
    _rows_from_tail,
    build_series,
    detect_regressions,
    load_round,
    load_rounds,
    noise_band,
    render,
    summarize_telemetry,
)

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_HEADLINE = ("pods_scheduled_per_sec[SchedulingBasic 5000nodes/"
             "30000pods, TPU batch path]")

# a verbatim line from the committed BENCH_r05.json tail — the legacy
# hand-rolled format the parser must keep reading forever
_LEGACY_DIAG = (
    "    diag: commit=4.32s/8 device=1.34s/14 encode=2.37s/14 "
    "session[hits=7 rebuilds=7 state_only=7] chunk=4096 "
    "max_cycle=1.03s pad_warms=0 "
    "e2e_buckets[<=0.2:84 <=0.5:12441 <=1.0:17127 <=2.0:348]")


# ---------------------------------------------------------------------------
# diagfmt: one writer, one parser


class TestDiagFmtRoundTrip:
    def test_current_format_round_trips(self):
        segs = diagfmt.format_phases({
            "solve.commit": {"total_s": 4.32, "count": 8,
                             "p50_s": 0.4, "p99_s": 0.54},
            "solve.device": {"total_s": 1.34, "count": 14,
                             "p50_s": 0.05, "p99_s": 0.2},
        })
        sess = diagfmt.format_session(
            type("S", (), {"incremental_hits": 7, "rebuilds": 1,
                           "state_only_rebuilds": 1})(),
            chunk=4096, max_cycle_s=0.88, pad_warms=2)
        dev = diagfmt.format_devprof({
            "cycles": 8, "compiles": 1, "unexpected_compiles": 0,
            "warm_compiles": 1, "device_wait_share": 0.61,
            "pad_waste_pct": 12.5, "h2d_bytes": 52_400_000,
            "d2h_bytes": 960_000, "compile_detector": "listener",
            "max_cycle": {"cycle": 3, "block_s": 0.4, "dispatch_s": 0.01,
                          "encode_s": 0.05, "compiles": 0},
        })
        line = diagfmt.format_diag(segs + [sess, dev])
        parsed = diagfmt.parse_diag(line)
        assert parsed["phases"]["solve.commit"] == {
            "total_s": 4.32, "count": 8, "p99_ms": 540.0}
        assert parsed["session"]["hits"] == 7
        assert parsed["chunk"] == 4096
        assert parsed["max_cycle_s"] == pytest.approx(0.88)
        assert parsed["pad_warms"] == 2
        assert parsed["devprof"]["cycles"] == 8
        assert parsed["devprof"]["wait_share"] == pytest.approx(0.61)
        assert parsed["devprof"]["max_cycle_phase"] == "block"
        assert parsed["devprof"]["detector"] == "listener"

    def test_legacy_committed_format_parses(self):
        parsed = diagfmt.parse_diag(_LEGACY_DIAG)
        assert parsed["phases"]["commit"] == {"total_s": 4.32, "count": 8}
        assert parsed["phases"]["device"]["total_s"] == pytest.approx(1.34)
        assert parsed["session"] == {
            "hits": 7, "rebuilds": 7, "state_only": 7}
        assert parsed["chunk"] == 4096
        assert parsed["max_cycle_s"] == pytest.approx(1.03)
        assert parsed["pad_warms"] == 0
        assert parsed["e2e_buckets"] == {
            "0.2": 84, "0.5": 12441, "1.0": 17127, "2.0": 348}

    def test_non_diag_lines_return_none(self):
        assert diagfmt.parse_diag("[headline] batch run 1/3: ...") is None
        assert diagfmt.parse_diag('{"metric": "x"}') is None

    def test_e2e_segment_rendered_from_registry_histogram(self):
        """The e2e text and /metrics share one series: counts in the
        rendered bucket segment must equal the histogram's own
        bucket_counts, and the p99 must be the histogram's interpolated
        quantile — byte-for-byte the same numbers a scrape would see."""
        from kubernetes_tpu.metrics.registry import Histogram

        hist = Histogram("e2e_scheduling_duration_seconds", "t",
                         ("result",))
        for v in (0.1, 0.3, 0.3, 0.7, 0.9, 1.5):
            hist.observe(v, "scheduled")
        segs = diagfmt.format_e2e(hist)
        parsed = diagfmt.parse_diag(diagfmt.format_diag(segs))
        counts = hist.bucket_counts("scheduled")
        edges = list(hist.buckets) + ["inf"]
        expect = {str(edges[i]): c for i, c in enumerate(counts) if c}
        assert parsed["e2e_buckets"] == expect
        assert parsed["e2e_p99_ms"] == pytest.approx(
            hist.quantile(0.99, "scheduled") * 1000, abs=0.5)

    def test_e2e_empty_histogram_renders_nothing(self):
        from kubernetes_tpu.metrics.registry import Histogram

        hist = Histogram("e2e", "t", ("result",))
        assert diagfmt.format_e2e(hist) == []

    def test_pipeline_segment_round_trips(self):
        """The streaming-scheduler segment (ISSUE 14 satellite):
        depth + overlap share through the one writer / one parser."""
        seg = diagfmt.format_pipeline(
            {"depth": 3, "overlap": 0.437, "cycles": 12})
        parsed = diagfmt.parse_diag(diagfmt.format_diag([seg]))
        assert parsed["pipeline"]["depth"] == 3
        assert parsed["pipeline"]["overlap"] == pytest.approx(0.44)
        assert parsed["pipeline"]["cycles"] == 12
        # quiet conventions: no info (pipeline off) renders nothing
        assert diagfmt.format_pipeline(None) == ""
        assert diagfmt.format_pipeline({}) == ""

    def test_mirror_segment_round_trips(self):
        """The device-mirror segment (ISSUE 20 satellite): scatter
        counters + encode share through the one writer / one parser
        (the generic bracket grammar — no parser change)."""
        seg = diagfmt.format_mirror(
            {"events": 42, "scatter_mb": 1.2345, "reseeds": 1,
             "encode_share": 0.0037})
        parsed = diagfmt.parse_diag(diagfmt.format_diag([seg]))
        assert parsed["mirror"]["events"] == 42
        assert parsed["mirror"]["scatter_mb"] == pytest.approx(1.234,
                                                               abs=1e-3)
        assert parsed["mirror"]["encode_share"] == pytest.approx(0.0037)
        assert parsed["mirror"]["reseeds"] == 1
        # quiet conventions: mirror off (None info) renders nothing,
        # and a row without encode_share omits the key
        assert diagfmt.format_mirror(None) == ""
        assert diagfmt.format_mirror({}) == ""
        seg = diagfmt.format_mirror({"events": 1, "scatter_mb": 0.0,
                                     "reseeds": 0})
        parsed = diagfmt.parse_diag(diagfmt.format_diag([seg]))
        assert "encode_share" not in parsed["mirror"]


# ---------------------------------------------------------------------------
# synthetic trajectory: the flagging semantics


def _artifact(dirpath, n: int, value: float, runs=None, telemetry=None,
              diag: str = _LEGACY_DIAG, metric: str = _HEADLINE,
              extra: dict = None) -> None:
    row = {"metric": metric, "value": value, "unit": "pods/s",
           "p99_latency_ms": 994}
    if runs:
        row["runs"] = runs
    if telemetry:
        row["telemetry"] = telemetry
    if extra:
        row.update(extra)
    tail = "\n".join([
        "SchedulingBasic/batch: 30000 pods created",
        diag,
        f"[headline] batch run 1/1: {value} pods/s",
        json.dumps(row),
    ])
    doc = {"n": n, "cmd": "timeout 3600 python bench.py", "rc": 0,
           "tail": tail}
    with open(os.path.join(dirpath, f"BENCH_r{n:02d}.json"), "w") as f:
        json.dump(doc, f)


class TestSyntheticTrajectory:
    def test_flags_regression_not_wobble(self, tmp_path):
        """r3 wobbles -7% (inside the ±30% noise band: NOT flagged —
        the r3→r4 false alarm this tool exists to prevent) while r4
        drops -54% (flagged, attributed to its telemetry)."""
        _artifact(tmp_path, 1, 7000.0, runs=[6800.0, 7000.0, 7200.0])
        _artifact(tmp_path, 2, 7150.0, runs=[7000.0, 7150.0, 7300.0])
        _artifact(tmp_path, 3, 6500.0, runs=[6400.0, 6500.0, 6700.0])
        _artifact(tmp_path, 4, 3200.0, runs=[3100.0, 3200.0, 3400.0],
                  telemetry={
                      "cycles": 8, "compiles": 2, "unexpected_compiles": 2,
                      "device_wait_share": 0.82, "pad_waste_pct": 4.0,
                      "max_cycle": {"cycle": 5, "rebuild": "full",
                                    "compiles": 2, "block_s": 2.0},
                  })
        series = build_series(load_rounds(str(tmp_path)))
        assert len(series[_HEADLINE]) == 4
        flags = detect_regressions(series)
        assert len(flags) == 1
        (flag,) = flags
        assert flag["round"] == 4
        assert flag["delta_pct"] < -30.0
        # attribution names the compile-inside-measured-cycle and the
        # wait share — the "what regressed" ships with the flag
        assert "compile" in flag["attribution"]
        assert "device-wait share 82%" in flag["attribution"]
        # and the human rendering marks exactly that row
        text = render(series, flags)
        assert text.count("REGRESSION") == 1

    def test_legacy_rounds_attribute_from_diag_phases(self, tmp_path):
        """Pre-telemetry rounds attribute a flagged drop by comparing
        parsed diag phase totals against the previous round's."""
        fast = ("    diag: commit=1.30s/8 device=0.21s/8 encode=0.28s/8 "
                "session[hits=7 rebuilds=1 state_only=1] chunk=4096 "
                "max_cycle=0.88s pad_warms=0 e2e_buckets[<=1.0:30000]")
        _artifact(tmp_path, 1, 7000.0, diag=fast)
        _artifact(tmp_path, 2, 3000.0, diag=_LEGACY_DIAG)  # commit grew
        flags = detect_regressions(build_series(load_rounds(str(tmp_path))))
        (flag,) = flags
        assert "commit" in flag["attribution"]

    def test_regression_cannot_widen_its_own_band(self, tmp_path):
        """The judging band comes from the PRIOR rounds only: a round
        that regresses AND blows up its own run-to-run spread (the
        classic recompile-in-some-runs signature) is still flagged."""
        _artifact(tmp_path, 1, 4000.0, runs=[3900.0, 4000.0, 4100.0])
        _artifact(tmp_path, 2, 4050.0, runs=[3950.0, 4050.0, 4150.0])
        _artifact(tmp_path, 3, 2800.0, runs=[2000.0, 2800.0, 4100.0])
        flags = detect_regressions(
            build_series(load_rounds(str(tmp_path))))
        (flag,) = flags
        assert flag["round"] == 3
        assert flag["band_pct"] == pytest.approx(30.0)  # prior floor

    def test_persistent_regression_stays_flagged(self, tmp_path):
        """The r5 GangScheduling shape: a drop with NO later recovery
        round stays an open flag and still gates --strict."""
        from tools.perf_report import main, open_regressions

        gang = ("pods_scheduled_per_sec[GangScheduling 5000nodes/"
                "30000pods, TPU batch path]")
        _artifact(tmp_path, 1, 4400.0, metric=gang)
        _artifact(tmp_path, 2, 4390.0, metric=gang)
        _artifact(tmp_path, 3, 2846.0, metric=gang)
        flags = detect_regressions(
            build_series(load_rounds(str(tmp_path))))
        assert len(flags) == 1
        assert "recovered_round" not in flags[0]
        assert open_regressions(flags) == flags
        assert main(["--dir", str(tmp_path), "--strict"]) == 1

    def test_recovered_regression_stops_gating(self, tmp_path):
        """ISSUE 14 satellite: once a later round lands back inside
        the band the drop was judged against, the old flag retires —
        it no longer gates --strict, but stays reported as recovered
        provenance. (The GangScheduling acceptance: the pipeline row
        landing in-band must silence the r5 flag without rewriting
        committed artifacts.)"""
        from tools.perf_report import main, open_regressions

        gang = ("pods_scheduled_per_sec[GangScheduling 5000nodes/"
                "30000pods, TPU batch path]")
        _artifact(tmp_path, 1, 4400.0, metric=gang)
        _artifact(tmp_path, 2, 4390.0, metric=gang)
        _artifact(tmp_path, 3, 2846.0, metric=gang)
        _artifact(tmp_path, 4, 4300.0, metric=gang)   # back in band
        flags = detect_regressions(
            build_series(load_rounds(str(tmp_path))))
        assert len(flags) == 1
        assert flags[0]["recovered_round"] == 4
        assert open_regressions(flags) == []
        assert main(["--dir", str(tmp_path), "--strict"]) == 0
        # the human report still names the recovery
        text = render(build_series(load_rounds(str(tmp_path))), flags)
        assert "recovered" in text
        assert "REGRESSION" not in text

    def test_stray_bench_named_files_are_ignored(self, tmp_path):
        _artifact(tmp_path, 1, 7000.0)
        # matches the glob, not the round-name contract — must be
        # skipped, not crash the loader (and so the tier-1 smoke)
        (tmp_path / "BENCH_rest.json").write_text("not even json")
        rounds = load_rounds(str(tmp_path))
        assert [r["round"] for r in rounds] == [1]

    def test_noise_band_from_repeat_runs(self):
        points = [{"value": 1000.0, "runs": [600.0, 1000.0, 1400.0]}]
        assert noise_band(points) == pytest.approx(0.8)   # spread wins
        assert noise_band([{"value": 1000.0, "runs": None}]) == \
            pytest.approx(0.30)                           # floor

    def test_schema_drift_raises(self, tmp_path):
        p = tmp_path / "BENCH_r01.json"
        p.write_text(json.dumps({"n": 1, "cmd": "x", "rc": 0}))  # no tail
        with pytest.raises(ValueError, match="tail"):
            load_round(str(p))


# ---------------------------------------------------------------------------
# the sustained-arrival family gate (ISSUE 14 satellite)


class TestSustainedFlags:
    _METRIC = ("sustained_arrival[open-loop 5000/s 240nodes/30000pods "
               "seed=14, store-direct replay engine]")

    def _row(self, tmp_path, n, **extra):
        base = {"p99_arrival_to_bind_ms": 180, "lost_pods": 0,
                "rate_normalized_throughput": 0.99,
                "telemetry": {"overlap_share": 0.6,
                              "overlapped_cycles": 40},
                "freshness": {"slo": {"snapshot_staleness": "ok",
                                      "schedule_latency": "ok"}}}
        base.update(extra)
        _artifact(tmp_path, n, 4900.0, metric=self._METRIC, extra=base)

    def test_green_row_passes(self, tmp_path):
        from tools.perf_report import main, sustained_flags

        self._row(tmp_path, 1)
        assert sustained_flags(load_rounds(str(tmp_path))) == []
        assert main(["--dir", str(tmp_path), "--strict"]) == 0

    def test_p99_over_budget_gates_strict(self, tmp_path):
        from tools.perf_report import main, sustained_flags

        self._row(tmp_path, 1, p99_arrival_to_bind_ms=812)
        (flag,) = sustained_flags(load_rounds(str(tmp_path)))
        assert "812ms > 500ms" in flag["problems"][0]
        assert main(["--dir", str(tmp_path), "--strict"]) == 1

    def test_lost_pods_and_red_staleness_flagged(self, tmp_path):
        from tools.perf_report import sustained_flags

        self._row(tmp_path, 1, lost_pods=3,
                  freshness={"slo": {"snapshot_staleness": "violated"}})
        (flag,) = sustained_flags(load_rounds(str(tmp_path)))
        probs = " ".join(flag["problems"])
        assert "lost_pods=3" in probs
        assert "snapshot_staleness SLO violated" in probs

    def test_zero_overlap_flagged(self, tmp_path):
        from tools.perf_report import sustained_flags

        self._row(tmp_path, 1,
                  telemetry={"overlap_share": 0.0,
                             "overlapped_cycles": 0})
        (flag,) = sustained_flags(load_rounds(str(tmp_path)))
        assert "degenerated" in flag["problems"][0]


class TestUpgradeFlags:
    _METRIC = ("upgrade_roll[open-loop 5000/s 3part+2sched rolling "
               "restart, 30000pods seed=16, REST fabric]")

    def _row(self, tmp_path, n, **extra):
        base = {"p99_arrival_to_bind_ms": 120, "lost_pods": 0,
                "lost_watch_events": 0, "duplicated_events": 0,
                "unmoved_relists": 0, "frozen_ms_max": 330.0,
                "freeze_budget_ms": 2000.0, "codec_failures": 0,
                "codec_renegotiations": 8,
                "rolled_exactly_once": True, "invariants_ok": True,
                "slo_verdicts_ok": True}
        base.update(extra)
        _artifact(tmp_path, n, 4100.0, metric=self._METRIC,
                  extra=base)

    def test_green_roll_passes(self, tmp_path):
        from tools.perf_report import main, upgrade_flags

        self._row(tmp_path, 1)
        assert upgrade_flags(load_rounds(str(tmp_path))) == []
        assert main(["--dir", str(tmp_path), "--strict"]) == 0

    def test_lost_pod_gates_strict(self, tmp_path):
        from tools.perf_report import main, upgrade_flags

        self._row(tmp_path, 1, lost_pods=2)
        (flag,) = upgrade_flags(load_rounds(str(tmp_path)))
        assert "lost_pods=2" in flag["problems"][0]
        assert main(["--dir", str(tmp_path), "--strict"]) == 1

    def test_lost_and_duplicated_events_flagged(self, tmp_path):
        from tools.perf_report import upgrade_flags

        self._row(tmp_path, 1, lost_watch_events=1,
                  duplicated_events=3)
        (flag,) = upgrade_flags(load_rounds(str(tmp_path)))
        probs = " ".join(flag["problems"])
        assert "lost_watch_events=1" in probs
        assert "duplicated_events=3" in probs

    def test_freeze_budget_overrun_flagged(self, tmp_path):
        from tools.perf_report import upgrade_flags

        self._row(tmp_path, 1, frozen_ms_max=2750.0)
        (flag,) = upgrade_flags(load_rounds(str(tmp_path)))
        assert "frozen_ms_max 2750.0 > budget 2000ms" \
            in flag["problems"][0]

    def test_red_slo_and_p99_flagged(self, tmp_path):
        from tools.perf_report import main, upgrade_flags

        self._row(tmp_path, 1, slo_verdicts_ok=False,
                  p99_arrival_to_bind_ms=812)
        (flag,) = upgrade_flags(load_rounds(str(tmp_path)))
        probs = " ".join(flag["problems"])
        assert "812ms > 500ms" in probs
        assert "SLO went red" in probs
        assert main(["--dir", str(tmp_path), "--strict"]) == 1

    def test_codec_failure_and_double_roll_flagged(self, tmp_path):
        from tools.perf_report import upgrade_flags

        self._row(tmp_path, 1, codec_failures=1,
                  rolled_exactly_once=False, unmoved_relists=2)
        (flag,) = upgrade_flags(load_rounds(str(tmp_path)))
        probs = " ".join(flag["problems"])
        assert "codec_failures=1" in probs
        assert "not exactly-once" in probs
        assert "unmoved_relists=2" in probs

    def test_flags_survive_json_mode(self, tmp_path, capsys):
        from tools.perf_report import main

        self._row(tmp_path, 1, lost_pods=1)
        main(["--dir", str(tmp_path), "--json"])
        doc = json.loads(capsys.readouterr().out)
        assert len(doc["upgrade_flags"]) == 1


class TestFederationFlags:
    _SPILL = ("federation_spill[open-loop 300/s 3clusters saturation "
              "spillover, 900pods seed=18, REST fabric]")
    _LOSS = ("federation_loss[open-loop 300/s 3clusters cluster-loss "
             "SIGKILL, 900pods seed=18, REST fabric]")

    def _row(self, tmp_path, n, metric=None, **extra):
        base = {"lost_pods": 0, "gang_splits": 0,
                "survivor_relists": 0, "per_cluster_slo_ok": True,
                "spilled": 31, "failovers": 0, "recovery_ratio": 1.0,
                "slo_verdicts_ok": True, "invariants_ok": True}
        base.update(extra)
        _artifact(tmp_path, n, 280.0,
                  metric=metric or self._SPILL, extra=base)

    def test_green_row_passes(self, tmp_path):
        from tools.perf_report import federation_flags, main

        self._row(tmp_path, 1)
        assert federation_flags(load_rounds(str(tmp_path))) == []
        assert main(["--dir", str(tmp_path), "--strict"]) == 0

    def test_lost_pod_gates_strict(self, tmp_path):
        from tools.perf_report import federation_flags, main

        self._row(tmp_path, 1, lost_pods=2)
        (flag,) = federation_flags(load_rounds(str(tmp_path)))
        assert "lost_pods=2" in flag["problems"][0]
        assert main(["--dir", str(tmp_path), "--strict"]) == 1

    def test_gang_split_and_survivor_relist_flagged(self, tmp_path):
        from tools.perf_report import federation_flags

        self._row(tmp_path, 1, gang_splits=1, survivor_relists=2)
        (flag,) = federation_flags(load_rounds(str(tmp_path)))
        probs = " ".join(flag["problems"])
        assert "gang_splits=1" in probs
        assert "survivor_relists=2" in probs

    def test_red_per_cluster_slo_gates_strict(self, tmp_path):
        from tools.perf_report import federation_flags, main

        self._row(tmp_path, 1, per_cluster_slo_ok=False)
        (flag,) = federation_flags(load_rounds(str(tmp_path)))
        assert "per-cluster SLO went red" in flag["problems"][0]
        assert main(["--dir", str(tmp_path), "--strict"]) == 1

    def test_low_recovery_ratio_needs_a_failover(self, tmp_path):
        from tools.perf_report import federation_flags

        # no failover happened: a low ratio is vacuous, not a flag
        self._row(tmp_path, 1, recovery_ratio=0.0, failovers=0)
        assert federation_flags(load_rounds(str(tmp_path))) == []
        self._row(tmp_path, 2, metric=self._LOSS,
                  recovery_ratio=0.5, failovers=1)
        (flag,) = federation_flags(load_rounds(str(tmp_path)))
        assert "recovery_ratio 0.50 < 0.8" in flag["problems"][0]

    def test_dry_spill_row_flagged(self, tmp_path):
        from tools.perf_report import federation_flags

        self._row(tmp_path, 1, spilled=0)
        (flag,) = federation_flags(load_rounds(str(tmp_path)))
        assert "spilled=0" in flag["problems"][0]
        # a LOSS row with spilled=0 is fine — spill is not its job
        self._row(tmp_path, 2, metric=self._LOSS, spilled=0,
                  failovers=1)
        flags = federation_flags(load_rounds(str(tmp_path)))
        assert [f["round"] for f in flags] == [1]

    def test_invariant_failure_carries_reason(self, tmp_path):
        from tools.perf_report import federation_flags

        self._row(tmp_path, 1, invariants_ok=False,
                  invariants={"failed": "gang fg-3 split 2 clusters"},
                  slo_verdicts_ok=False)
        (flag,) = federation_flags(load_rounds(str(tmp_path)))
        probs = " ".join(flag["problems"])
        assert "gang fg-3 split 2 clusters" in probs
        assert "fleet freshness SLO went red" in probs

    def test_flags_survive_json_mode(self, tmp_path, capsys):
        from tools.perf_report import main

        self._row(tmp_path, 1, lost_pods=1)
        main(["--dir", str(tmp_path), "--json"])
        doc = json.loads(capsys.readouterr().out)
        assert len(doc["federation_flags"]) == 1


class TestReadtierFlags:
    _ARM = ("watchherd[320 informers R=4, 289 events open-loop 12/s "
            "seed=16, REST fabric]")
    _SCALING = ("watchherd_scaling[R=4 vs R=0, 320 informers seed=16, "
                "per owner-cpu-second]")
    _CELL = "watchherd_cell[replica_kill seed=16]"

    def _arm(self, tmp_path, n, **extra):
        base = {"replicas": 4, "lost_events": 0,
                "unconverged_informers": 0, "dup_suppressed": 0,
                "relists": 0, "replica_reads": 12,
                "replication_lag_p99_ms": 80.0, "lag_budget_ms": 500.0,
                "invariants_ok": True,
                "freshness": {"slo": {"replication_lag": "ok"}}}
        base.update(extra)
        _artifact(tmp_path, n, 5000.0, metric=self._ARM, extra=base)

    def _scaling(self, tmp_path, n, **extra):
        base = {"read_scaling_x": 10.4, "read_scaling_floor_x": 1.5,
                "write_flat_ok": True, "write_ratio": 1.0,
                "differential_match": True, "invariants_ok": True}
        base.update(extra)
        _artifact(tmp_path, n, 10.4, metric=self._SCALING, extra=base)

    def _cell(self, tmp_path, n, **extra):
        base = {"ok": True, "lost_events": 0,
                "relists_beyond_faulted": 0}
        base.update(extra)
        _artifact(tmp_path, n, 1.0, metric=self._CELL, extra=base)

    def test_green_rows_pass(self, tmp_path):
        from tools.perf_report import main, readtier_flags

        self._arm(tmp_path, 1)
        self._scaling(tmp_path, 2)
        self._cell(tmp_path, 3)
        assert readtier_flags(load_rounds(str(tmp_path))) == []
        assert main(["--dir", str(tmp_path), "--strict"]) == 0

    def test_lost_events_gate_strict(self, tmp_path):
        from tools.perf_report import main, readtier_flags

        self._arm(tmp_path, 1, lost_events=3, unconverged_informers=3)
        (flag,) = readtier_flags(load_rounds(str(tmp_path)))
        probs = " ".join(flag["problems"])
        assert "lost_events=3" in probs
        assert "unconverged_informers=3" in probs
        assert main(["--dir", str(tmp_path), "--strict"]) == 1

    def test_dups_and_relists_flagged(self, tmp_path):
        from tools.perf_report import readtier_flags

        self._arm(tmp_path, 1, dup_suppressed=2, relists=5)
        (flag,) = readtier_flags(load_rounds(str(tmp_path)))
        probs = " ".join(flag["problems"])
        assert "dup_suppressed=2" in probs
        assert "relists=5" in probs

    def test_unused_replicas_flagged(self, tmp_path):
        from tools.perf_report import readtier_flags

        # reads never touched a replica while replicas were advertised
        self._arm(tmp_path, 1, replica_reads=0)
        (flag,) = readtier_flags(load_rounds(str(tmp_path)))
        assert "replica_reads=0" in flag["problems"][0]
        # vacuous on the replicas-off differential arm
        self._arm(tmp_path, 2, replicas=0, replica_reads=0)
        flags = readtier_flags(load_rounds(str(tmp_path)))
        assert [f["round"] for f in flags] == [1]

    def test_lag_over_budget_and_red_slo_gate_strict(self, tmp_path):
        from tools.perf_report import main, readtier_flags

        self._arm(tmp_path, 1, replication_lag_p99_ms=740.0,
                  freshness={"slo": {"replication_lag": "violated"}})
        (flag,) = readtier_flags(load_rounds(str(tmp_path)))
        probs = " ".join(flag["problems"])
        assert "lag p99 740.0ms over the 500ms budget" in probs
        assert "freshness SLO red: replication_lag" in probs
        assert main(["--dir", str(tmp_path), "--strict"]) == 1

    def test_scaling_below_floor_gates_strict(self, tmp_path):
        from tools.perf_report import main, readtier_flags

        self._scaling(tmp_path, 1, read_scaling_x=1.2,
                      invariants_ok=False)
        (flag,) = readtier_flags(load_rounds(str(tmp_path)))
        assert "read scaling 1.20x < 1.5x floor" in flag["problems"][0]
        assert main(["--dir", str(tmp_path), "--strict"]) == 1

    def test_write_regression_and_differential_flagged(self, tmp_path):
        from tools.perf_report import readtier_flags

        self._scaling(tmp_path, 1, write_flat_ok=False,
                      write_ratio=0.7, differential_match=False,
                      invariants_ok=False)
        (flag,) = readtier_flags(load_rounds(str(tmp_path)))
        probs = " ".join(flag["problems"])
        assert "write throughput regressed" in probs
        assert "differential arms disagree" in probs

    def test_failed_cell_gates_strict(self, tmp_path):
        from tools.perf_report import main, readtier_flags

        self._cell(tmp_path, 1, ok=False,
                   failure="2 relists beyond the killed replica",
                   lost_events=1, relists_beyond_faulted=2)
        (flag,) = readtier_flags(load_rounds(str(tmp_path)))
        probs = " ".join(flag["problems"])
        assert "2 relists beyond the killed replica" in probs
        assert "lost_events=1" in probs
        assert "relists_beyond_faulted=2" in probs
        assert main(["--dir", str(tmp_path), "--strict"]) == 1

    def test_flags_survive_json_mode(self, tmp_path, capsys):
        from tools.perf_report import main

        self._arm(tmp_path, 1, lost_events=1)
        main(["--dir", str(tmp_path), "--json"])
        doc = json.loads(capsys.readouterr().out)
        assert len(doc["readtier_flags"]) == 1

    def test_committed_watchherd_log_is_strict_clean(self):
        from tools.perf_report import readtier_flags

        path = os.path.join(_REPO_ROOT, "watchherd_rows.log")
        with open(path) as f:
            rows = _rows_from_tail(f.read())
        assert any(r["metric"].startswith("watchherd[") for r in rows)
        assert any(r["metric"].startswith("watchherd_scaling[")
                   for r in rows)
        assert any(r["metric"].startswith("watchherd_cell[")
                   for r in rows)
        fake_round = [{"round": 0, "rows": rows}]
        assert readtier_flags(fake_round) == []
        # the committed scaling row proves the headline claims
        (srow,) = [r for r in rows
                   if r["metric"].startswith("watchherd_scaling[")]
        assert srow["read_scaling_x"] >= srow["read_scaling_floor_x"]
        assert srow["write_flat_ok"] and srow["differential_match"]


# ---------------------------------------------------------------------------
# committed artifacts: the tier-1 smoke over the real trajectory


class TestCommittedArtifacts:
    def test_every_committed_round_parses(self):
        rounds = load_rounds(_REPO_ROOT)
        assert len(rounds) >= 5, "committed BENCH_r*.json went missing"
        for rnd in rounds:
            assert rnd["rows"], f"round {rnd['round']} yielded no rows"

    def test_headline_family_spans_all_rounds(self):
        rounds = load_rounds(_REPO_ROOT)
        series = build_series(rounds)
        points = series.get(_HEADLINE, [])
        assert len(points) == len(rounds), \
            "headline row missing from a committed round"
        assert all(p["value"] > 0 for p in points)

    def test_report_renders_and_cli_exits_zero(self, capsys):
        from tools.perf_report import main

        assert main(["--dir", _REPO_ROOT]) == 0
        out = capsys.readouterr().out
        assert "SchedulingBasic" in out
        assert "noise band" in out

    def test_json_mode_is_machine_readable(self, capsys):
        from tools.perf_report import main

        assert main(["--dir", _REPO_ROOT, "--json"]) == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc["rounds"] == sorted(doc["rounds"])
        assert _HEADLINE in doc["series"]


# ---------------------------------------------------------------------------
# telemetry stream cross-check


class TestTelemetryStream:
    def test_jsonl_summary_matches_profiler_summary(self, tmp_path):
        """A bench row's committed telemetry sub-object can be
        cross-checked against the raw KTPU_TELEMETRY stream: the same
        cycles aggregate to the same compile count, wait share, pad
        waste and transfer volume through both paths."""
        from kubernetes_tpu.observability.devprof import DevProfiler

        p = DevProfiler(enabled=True, use_listener=False,
                        telemetry_dir=str(tmp_path))
        rec = p.begin_cycle(cycle=-1, pad=256, real=8, warming=True)
        p.phase("block", 1.0)
        p.end_cycle(rec)
        for i in range(4):
            rec = p.begin_cycle(cycle=i, pad=256, real=192)
            p.phase("encode", 0.02)
            p.phase("dispatch", 0.01)
            p.phase("block", 0.10)
            p.add_bytes("h2d", 1_000_000)
            p.add_bytes("d2h", 2_048)
            p.end_cycle(rec)
        p.close()
        live = p.summary()
        stream = summarize_telemetry(str(tmp_path))
        assert stream["files"] == 1
        assert stream["cycles"] == live["cycles"] == 4
        assert stream["warming_cycles"] == 1
        assert stream["h2d_bytes"] == live["h2d_bytes"]
        assert stream["device_wait_share"] == pytest.approx(
            live["device_wait_share"], abs=0.01)
        assert stream["pad_waste_pct"] == pytest.approx(
            live["pad_waste_pct"], abs=0.01)


# ---------------------------------------------------------------------------
# tier-1 guard: telemetry survives the driver tail capture


class TestBenchTailGuard:
    def test_run_one_attaches_devprof_summary(self, monkeypatch):
        """bench.run_one carries the median run's devprof summary into
        the row JSON as ``telemetry`` — the attach point the acceptance
        criterion rests on."""
        import bench
        from kubernetes_tpu.harness.perf import BenchmarkResult

        tel = {"cycles": 8, "compiles": 1, "unexpected_compiles": 0,
               "device_wait_share": 0.4, "pad_waste_pct": 7.5}

        def fake_run_workload(name, ops, **kw):
            return BenchmarkResult(
                name=name, total_pods=1000, measured_pods=1000,
                duration_seconds=1.0, pods_per_second=5000.0,
                throughput={}, metrics={"Perc99": 900.0}, telemetry=tel)

        monkeypatch.setattr(bench, "make_workload", lambda *a, **k: [])
        monkeypatch.setattr(bench, "run_workload", fake_run_workload)
        row = bench.run_one("headline", "SchedulingBasic", 200, 0, 1000,
                            serial_rate=100.0, repeat=1)
        assert row["telemetry"] == tel

    def test_headline_telemetry_survives_tail_capture(self, capsys,
                                                      monkeypatch):
        """The driver captures the LAST bytes of stdout: the headline
        row must print last (so its telemetry cannot fall off the tail
        — the trap the REST row hit pre-PR 5) and the committed-artifact
        parser must recover the sub-object from that tail."""
        import bench

        tel = {"cycles": 8, "compiles": 0, "unexpected_compiles": 0,
               "device_wait_share": 0.35, "pad_waste_pct": 3.1,
               "max_cycle": {"cycle": 6, "rebuild": "none",
                             "compiles": 0, "block_s": 0.2}}

        def fake_run_one(key, name, nodes, init_pods, measure_pods,
                         serial_rate, repeat=1):
            row = {"metric": f"pods_scheduled_per_sec[{name} {key}]",
                   "value": 7000.0, "unit": "pods/s",
                   "vs_baseline": 10.0}
            if key == "headline":
                row["telemetry"] = tel
            return row

        def fake_run_rest_one(nodes, measure_pods, serial_rate, qps,
                              repeat=1):
            return {"metric":
                    "pods_scheduled_per_sec[SchedulingBasic REST fabric]",
                    "value": 4500.0, "unit": "pods/s",
                    "vs_baseline": 70.0,
                    "store_direct_pods_per_sec": 7500.0,
                    "fabric_overhead_ratio": 0.6}

        def fake_run_qos_one(nodes, measure_pods, serial_rate, qps,
                             tenants=3, solo_baseline=None):
            return {"metric": "noisy_tenant_qos[SchedulingBasic]",
                    "value": 3000.0, "unit": "pods/s",
                    "vs_baseline": 48.0, "p99_ratio_vs_solo": 1.3,
                    "qos_ok": True}

        def fake_run_scale10x_one(serial_rate, qps, quick=False):
            return {"metric": "pods_scheduled_per_sec[Scale10x "
                              "50000nodes/500000pods, partitioned "
                              "fabric 4p x 2r]",
                    "value": 4200.0, "unit": "pods/s",
                    "vs_baseline": 68.0,
                    "ab": {"sharding_pays": True},
                    "invariants": {"lost_pods": 0, "double_binds": 0}}

        monkeypatch.setattr(bench, "run_one", fake_run_one)
        monkeypatch.setattr(bench, "run_rest_one", fake_run_rest_one)
        monkeypatch.setattr(bench, "run_qos_one", fake_run_qos_one)
        monkeypatch.setattr(bench, "run_scale10x_one",
                            fake_run_scale10x_one)
        monkeypatch.setattr(bench.sys, "argv",
                            ["bench.py", "--skip-serial"])
        bench.main()
        # simulate the driver's tail capture: keep only the last 2KB
        tail = capsys.readouterr().out[-2048:]
        rows = _rows_from_tail(tail)
        assert rows, "tail capture lost every row"
        headline = rows[-1]
        assert "headline" in headline["metric"]
        assert headline["telemetry"] == tel


# ---------------------------------------------------------------------------
# device-mirror flags (ISSUE 20)


class TestMirrorFlags:
    _ON = ("mirror_sustained[arm=on, open-loop 5000/s 240nodes/"
           "30000pods seed=14, store-direct replay engine]")
    _AB = ("mirror_ab[sustained 30000pods @ 5000/s on/off + seeded "
           "node_kill differential]")

    def _on_row(self, tmp_path, n, **extra):
        base = {"mirror_arm": "on", "encode_share": 0.004,
                "encode_share_budget": 0.05,
                "mirror": {"events": 12, "catch_ups": 9,
                           "scatter_mb": 0.4, "reseeds": 0},
                "reseeds_allowed": 0,
                "h2d_per_cycle_bytes": 106_500,
                "h2d_per_cycle_budget_bytes": 618_497,
                "p99_arrival_to_bind_ms": 180, "p99_budget_ms": 500,
                "lost_pods": 0, "invariants_ok": True}
        base.update(extra)
        _artifact(tmp_path, n, 4900.0, metric=self._ON, extra=base)

    def _ab_row(self, tmp_path, n, **extra):
        base = {"mirror_on_pods_per_sec": 4900.0,
                "mirror_off_pods_per_sec": 4880.0,
                "h2d_per_cycle_on_bytes": 106_500,
                "h2d_per_cycle_off_bytes": 108_000,
                "differential_match": True, "invariants_ok": True}
        base.update(extra)
        _artifact(tmp_path, n, 0.4, metric=self._AB, extra=base)

    def test_green_rows_pass(self, tmp_path):
        from tools.perf_report import main, mirror_flags

        self._on_row(tmp_path, 1)
        self._ab_row(tmp_path, 2)
        assert mirror_flags(load_rounds(str(tmp_path))) == []
        assert main(["--dir", str(tmp_path), "--strict"]) == 0

    def test_encode_share_over_budget_gates_strict(self, tmp_path):
        from tools.perf_report import main, mirror_flags

        self._on_row(tmp_path, 1, encode_share=0.31)
        (flag,) = mirror_flags(load_rounds(str(tmp_path)))
        assert "encode share 0.3100 >= 0.05" in flag["problems"][0]
        assert main(["--dir", str(tmp_path), "--strict"]) == 1

    def test_unexpected_reseed_flagged(self, tmp_path):
        from tools.perf_report import mirror_flags

        self._on_row(tmp_path, 1,
                     mirror={"events": 12, "catch_ups": 9,
                             "scatter_mb": 0.4, "reseeds": 3})
        (flag,) = mirror_flags(load_rounds(str(tmp_path)))
        assert "reseeds=3 > 0 allowed" in flag["problems"][0]

    def test_h2d_over_committed_budget_gates_strict(self, tmp_path):
        from tools.perf_report import main, mirror_flags

        self._on_row(tmp_path, 1, h2d_per_cycle_bytes=700_000)
        (flag,) = mirror_flags(load_rounds(str(tmp_path)))
        assert "700,000B >= the committed donation-row budget " \
               "618,497B" in flag["problems"][0]
        assert main(["--dir", str(tmp_path), "--strict"]) == 1

    def test_off_arm_not_held_to_mirror_budgets(self, tmp_path):
        """The reference arm re-encodes node columns by design: its
        encode share and reseeds are not defects."""
        from tools.perf_report import mirror_flags

        self._on_row(tmp_path, 1, mirror_arm="off", mirror={},
                     encode_share=0.4)
        assert mirror_flags(load_rounds(str(tmp_path))) == []

    def test_lost_pods_and_p99_flag_either_arm(self, tmp_path):
        from tools.perf_report import mirror_flags

        self._on_row(tmp_path, 1, mirror_arm="off", mirror={},
                     lost_pods=2, p99_arrival_to_bind_ms=812)
        (flag,) = mirror_flags(load_rounds(str(tmp_path)))
        probs = " ".join(flag["problems"])
        assert "lost_pods=2" in probs
        assert "812ms over the 500ms SLO" in probs

    def test_differential_mismatch_gates_strict(self, tmp_path):
        from tools.perf_report import main, mirror_flags

        self._ab_row(tmp_path, 1, differential_match=False,
                     invariants_ok=False)
        (flag,) = mirror_flags(load_rounds(str(tmp_path)))
        probs = " ".join(flag["problems"])
        assert "differential arms disagree" in probs
        assert main(["--dir", str(tmp_path), "--strict"]) == 1

    def test_scatter_h2d_regression_flagged_with_headroom(self,
                                                          tmp_path):
        from tools.perf_report import mirror_flags

        # within the 10% jitter band: clean
        self._ab_row(tmp_path, 1, h2d_per_cycle_on_bytes=115_000)
        assert mirror_flags(load_rounds(str(tmp_path))) == []
        # past it: the scatter triples cost more than the encode
        self._ab_row(tmp_path, 1, h2d_per_cycle_on_bytes=160_000)
        (flag,) = mirror_flags(load_rounds(str(tmp_path)))
        assert "above the off arm's" in flag["problems"][0]

    def test_chaos_cell_row_flagged(self, tmp_path):
        from tools.perf_report import mirror_flags

        _artifact(tmp_path, 1, 0.0,
                  metric="mirror_cell[node_kill seed=11]",
                  extra={"ok": False, "differential_match": False,
                         "lost_pods": 1,
                         "failure": "differential mismatch"})
        (flag,) = mirror_flags(load_rounds(str(tmp_path)))
        probs = " ".join(flag["problems"])
        assert "cell failed" in probs
        assert "lost_pods=1" in probs

    def test_flags_survive_json_mode(self, tmp_path, capsys):
        from tools.perf_report import main

        self._on_row(tmp_path, 1, encode_share=0.2)
        main(["--dir", str(tmp_path), "--json"])
        doc = json.loads(capsys.readouterr().out)
        assert len(doc["mirror_flags"]) == 1

    def test_committed_mirror_rows_strict_clean(self, tmp_path):
        """The committed artifact IS the acceptance criterion: the
        checked-in mirror_rows.log rows must hold every mirror_flags
        budget (encode share, per-cycle h2d vs the donation row, zero
        lost, differential match) — a regression that sneaks into the
        committed row fails tier-1, not just --strict CI."""
        path = os.path.join(_REPO_ROOT, "mirror_rows.log")
        assert os.path.exists(path), "mirror_rows.log not committed"
        with open(path) as f:
            tail = f.read()
        doc = {"n": 1, "cmd": "python bench.py --config mirrorab",
               "rc": 0, "tail": tail}
        with open(os.path.join(tmp_path, "BENCH_r01.json"), "w") as fh:
            json.dump(doc, fh)
        from tools.perf_report import mirror_flags

        rounds = load_rounds(str(tmp_path))
        rows = _rows_from_tail(tail)
        kinds = {str(r.get("metric", "")).split("[", 1)[0]
                 for r in rows}
        assert "mirror_sustained" in kinds and "mirror_ab" in kinds
        on_rows = [r for r in rows if r.get("mirror_arm") == "on"]
        assert on_rows and all(
            float(r["encode_share"]) < 0.05 for r in on_rows)
        assert mirror_flags(rounds) == []

"""Perf-regression report over the committed bench trajectory.

Reads the driver-captured ``BENCH_r*.json`` artifacts (each holds the
stdout/stderr tail of one round's ``python bench.py`` run: per-run
``diag:`` lines interleaved with the per-family JSON rows), rebuilds
the per-family throughput/p99 trend, and judges every round-to-round
move against a measured NOISE BAND instead of eyeballing: the r3→r4
headline "regression" that turned out to be run-to-run variance is
the motivating case — a drop is only flagged when it falls OUTSIDE the
band the row's own repeat-runs establish.

When a drop IS flagged, the report attributes it to a phase: the row's
``telemetry`` sub-object (devprof: compile count, device-wait share,
pad waste, slowest-cycle phase) when present, else the round's parsed
``diag:`` phases compared against the previous round's — so the answer
to "what regressed" ships with the flag, not as a follow-up profiling
request.

Usage::

    python tools/perf_report.py                  # report over ./BENCH_r*.json
    python tools/perf_report.py --dir path/      # artifacts elsewhere
    python tools/perf_report.py --telemetry dir/ # + KTPU_TELEMETRY JSONL summary
    python tools/perf_report.py --strict         # exit 1 on any flagged regression
    python tools/perf_report.py --json           # machine-readable output

Runs as a tier-1 smoke over the committed artifacts
(tests/test_perf_report.py), so a malformed BENCH round or a schema
drift in the row JSON fails CI, not a human reading the trend table.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import Dict, List, Optional

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO_ROOT)

from kubernetes_tpu.harness.diagfmt import parse_diag  # noqa: E402

# relative spread floor: single-run rounds carry no within-row spread,
# and back-to-back runs of the committed rounds swung by ±30% — a band
# narrower than that flags weather as regression (the r3→r4 case)
DEFAULT_NOISE_BAND = 0.30

_ROUND_RE = re.compile(r"BENCH_r(\d+)\.json$")


# ---------------------------------------------------------------------------
# artifact loading


def _rows_from_tail(tail: str) -> List[dict]:
    """Per-family JSON rows in a driver tail, each annotated with the
    ``diag:`` lines of ITS runs (the diag lines print per run, the row
    JSON after the repeats — so the diags pending when a row line
    appears belong to that row)."""
    rows: List[dict] = []
    pending_diags: List[dict] = []
    for line in tail.splitlines():
        parsed = parse_diag(line)
        if parsed is not None:
            pending_diags.append(parsed)
            continue
        stripped = line.strip()
        if not stripped.startswith("{"):
            continue
        try:
            doc = json.loads(stripped)
        except ValueError:
            continue
        if not isinstance(doc, dict) or "metric" not in doc:
            continue
        doc["_diags"] = pending_diags
        pending_diags = []
        rows.append(doc)
    return rows


def load_round(path: str) -> dict:
    """One BENCH_r*.json artifact under the driver schema (``n``,
    ``cmd``, ``rc``, ``tail``, optional ``parsed``). Raises ValueError
    on schema drift — the tier-1 smoke turns that into a test failure."""
    with open(path) as f:
        doc = json.load(f)
    for key in ("n", "cmd", "rc", "tail"):
        if key not in doc:
            raise ValueError(f"{path}: missing driver key {key!r}")
    if not isinstance(doc["tail"], str):
        raise ValueError(f"{path}: tail is not a string")
    rows = _rows_from_tail(doc["tail"])
    if "parsed" in doc and isinstance(doc["parsed"], dict) \
            and doc["parsed"].get("metric"):
        metrics = {r["metric"] for r in rows}
        if doc["parsed"]["metric"] not in metrics:
            rows.append(dict(doc["parsed"], _diags=[]))
    return {"round": int(doc["n"]), "path": path, "rc": doc["rc"],
            "rows": rows}


def load_rounds(bench_dir: str) -> List[dict]:
    # the glob is wider than the round-name contract (BENCH_rest.json
    # would match it): only files the round regex accepts are rounds
    paths = [p for p in glob.glob(os.path.join(bench_dir,
                                               "BENCH_r*.json"))
             if _ROUND_RE.search(p)]
    paths.sort(key=lambda p: int(_ROUND_RE.search(p).group(1)))
    return [load_round(p) for p in paths]


def build_series(rounds: List[dict]) -> Dict[str, List[dict]]:
    """metric string → [{round, value, p99, runs, telemetry, diags}],
    round-ordered. The metric string IS the family key: it pins the
    workload, scale and path, so renamed scales never splice."""
    series: Dict[str, List[dict]] = {}
    for rnd in rounds:
        for row in rnd["rows"]:
            if row.get("unit") != "pods/s" or "error" in row:
                continue
            point = {
                "round": rnd["round"],
                "value": float(row.get("value", 0.0)),
                "p99_ms": row.get("p99_latency_ms",
                                  row.get("p99_arrival_to_bind_ms")),
                "runs": row.get("runs"),
                "telemetry": row.get("telemetry"),
                "diags": row.get("_diags", []),
            }
            if row.get("rate_normalized_throughput") is not None:
                # replay rows are OPEN-LOOP: raw pods/s tracks the
                # trace's offered rate, not the scheduler — the trend
                # (and regression detection) must compare bound-rate ÷
                # offered-rate, or a re-paced trace masquerades as a
                # perf move. Raw value kept for the table.
                point["raw_value"] = point["value"]
                point["value"] = float(
                    row["rate_normalized_throughput"])
            series.setdefault(row["metric"], []).append(point)
    for points in series.values():
        points.sort(key=lambda p: p["round"])
    return series


# ---------------------------------------------------------------------------
# noise band + regression detection


def noise_band(points: List[dict],
               floor: float = DEFAULT_NOISE_BAND) -> float:
    """Relative band from the rows' own repeat-runs (each ``runs``
    array is back-to-back samples of one round: its spread IS the
    run-to-run noise at that scale), floored at ``floor`` for rounds
    that ran single-shot."""
    band = 0.0
    for p in points:
        runs = p.get("runs")
        if runs and len(runs) >= 2 and p["value"] > 0:
            band = max(band, (max(runs) - min(runs)) / p["value"])
    return max(band, floor)


def _attribute(point: dict, prev: Optional[dict]) -> str:
    """Phase attribution for a flagged drop: devprof telemetry first
    (it names the slowest cycle's phase and the compile ledger), parsed
    diag phase totals vs the previous round second."""
    tel = point.get("telemetry")
    if tel:
        bits = []
        if tel.get("unexpected_compiles"):
            bits.append(
                f"{tel['unexpected_compiles']} compile(s) inside "
                f"measured cycles")
        mc = tel.get("max_cycle") or {}
        if mc.get("rebuild") not in (None, "none"):
            bits.append(f"max cycle did a {mc['rebuild']} rebuild")
        bits.append(
            f"device-wait share {tel.get('device_wait_share', 0.0):.0%}")
        if tel.get("pad_waste_pct", 0) > 25:
            bits.append(f"pad waste {tel['pad_waste_pct']:.0f}%")
        return "; ".join(bits)
    # legacy rounds: compare this row's diag phase totals against the
    # previous round's — the phase that grew the most is the suspect
    cur = _phase_totals(point)
    old = _phase_totals(prev) if prev else {}
    if not cur:
        return "no telemetry/diag in artifact"
    if not old:
        top = max(cur, key=cur.get)
        return f"dominant phase {top}={cur[top]:.2f}s (no prior round)"
    growth = {
        name: cur[name] - old.get(name, 0.0) for name in cur
    }
    top = max(growth, key=growth.get)
    return (f"phase {top} grew {old.get(top, 0.0):.2f}s -> "
            f"{cur[top]:.2f}s")


def _phase_totals(point: Optional[dict]) -> Dict[str, float]:
    if not point:
        return {}
    totals: Dict[str, float] = {}
    for diag in point.get("diags", []):
        for name, stats in (diag.get("phases") or {}).items():
            totals[name] = totals.get(name, 0.0) + stats["total_s"]
    return totals


def detect_regressions(series: Dict[str, List[dict]],
                       band_floor: float = DEFAULT_NOISE_BAND,
                       ) -> List[dict]:
    """Out-of-band drops, newest rounds judged against the median of
    the prior rounds (a single hot round must not become a baseline
    every later round 'regresses' from).

    Recovery (ISSUE 14): a flagged drop whose family LATER landed back
    inside the band it was judged against is history, not an open
    regression — the r5 GangScheduling flag must retire the round a
    fixed row is committed, without rewriting old artifacts. Such
    flags stay in the list carrying ``recovered_round`` (provenance
    for the report) but no longer gate ``--strict``
    (``open_regressions`` filters them)."""
    flags: List[dict] = []
    for metric, points in series.items():
        if len(points) < 2:
            continue
        for i in range(1, len(points)):
            # band from the PRIOR rounds only: a regression that also
            # blows up its own run-to-run variance (e.g. a recompile
            # landing in some runs) must not widen the band it is
            # judged against
            band = noise_band(points[:i], floor=band_floor)
            prior = sorted(p["value"] for p in points[:i])
            baseline = prior[len(prior) // 2]
            if baseline <= 0:
                continue
            delta = (points[i]["value"] - baseline) / baseline
            if delta < -band:
                flag = {
                    "metric": metric,
                    "round": points[i]["round"],
                    "value": points[i]["value"],
                    "baseline": baseline,
                    "delta_pct": round(100.0 * delta, 1),
                    "band_pct": round(100.0 * band, 1),
                    "attribution": _attribute(points[i], points[i - 1]),
                }
                floor_v = baseline * (1.0 - band)
                recovered = next(
                    (p["round"] for p in points[i + 1:]
                     if p["value"] >= floor_v), None)
                if recovered is not None:
                    flag["recovered_round"] = recovered
                flags.append(flag)
    return flags


def open_regressions(flags: List[dict]) -> List[dict]:
    """The flags that still gate ``--strict``: drops no later round
    has recovered from."""
    return [f for f in flags if "recovered_round" not in f]


# ---------------------------------------------------------------------------
# telemetry JSONL (KTPU_TELEMETRY) summary


def summarize_telemetry(telemetry_dir: str) -> dict:
    """Aggregate per-cycle JSONL records (one file per process) into
    the same shape as ``DevProfiler.summary()`` — so a bench row's
    committed sub-object can be cross-checked against the raw stream."""
    out = {"cycles": 0, "warming_cycles": 0, "compiles": 0,
           "unexpected_compiles": 0, "block_s": 0.0, "dispatch_s": 0.0,
           "encode_s": 0.0, "h2d_bytes": 0, "d2h_bytes": 0,
           "donated_bytes": 0, "real_rows": 0, "padded_rows": 0,
           "overlap_s": 0.0, "overlap_block_s": 0.0,
           "overlapped_cycles": 0, "files": 0}
    for path in sorted(glob.glob(
            os.path.join(telemetry_dir, "solvercycles-*.jsonl"))):
        out["files"] += 1
        with open(path) as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if rec.get("warming"):
                    out["warming_cycles"] += 1
                    continue
                out["cycles"] += 1
                out["compiles"] += rec.get("compiles", 0)
                if rec.get("compiles") and not rec.get("warming"):
                    out["unexpected_compiles"] += rec["compiles"]
                out["block_s"] += rec.get("block_s", 0.0)
                out["dispatch_s"] += rec.get("dispatch_s", 0.0)
                out["encode_s"] += rec.get("encode_s", 0.0) \
                    + rec.get("pack_s", 0.0)
                out["h2d_bytes"] += rec.get("h2d_bytes", 0)
                out["d2h_bytes"] += rec.get("d2h_bytes", 0)
                out["donated_bytes"] += rec.get("donated_bytes", 0)
                out["real_rows"] += rec.get("real", 0)
                out["padded_rows"] += rec.get("pad", 0) or rec.get(
                    "real", 0)
                if rec.get("overlap_s") is not None:
                    # pipeline overlap: lazy cycles only (mirrors
                    # DevProfiler.summary's overlap_share definition)
                    out["overlap_s"] += rec["overlap_s"]
                    out["overlap_block_s"] += rec.get("block_s", 0.0)
                    out["overlapped_cycles"] += 1
    phase_total = out["block_s"] + out["dispatch_s"] + out["encode_s"]
    out["device_wait_share"] = round(
        out["block_s"] / phase_total, 4) if phase_total > 0 else 0.0
    ov_window = out["overlap_s"] + out["overlap_block_s"]
    out["overlap_share"] = round(
        out["overlap_s"] / ov_window, 4) if ov_window > 0 else 0.0
    out["pad_waste_pct"] = round(
        100.0 * (1.0 - out["real_rows"] / out["padded_rows"]), 2) \
        if out["padded_rows"] else 0.0
    return out


# ---------------------------------------------------------------------------
# rendering


def scale_ab_flags(rounds: List[dict]) -> List[dict]:
    """The scale10x row family's own checks — throughput trend alone
    can't judge it. Each committed Scale10x row carries a same-scale
    partitioned-vs-single A/B, hard invariants, and the conflict
    chaos cell's verdict; flag the round when any of them fails:

    - ``ab.sharding_pays`` false (partitioned arm slower than the
      single-partition arm: the sharded fabric stopped paying for
      itself — a partition-layer regression even if the headline value
      still looks fine);
    - nonzero ``invariants`` (lost pods / double-binds);
    - a conflict cell that either broke an invariant or never
      conflicted (``ok`` false — a cell with zero conflicts proved
      nothing about the resolution path)."""
    flags: List[dict] = []
    for rnd in rounds:
        for row in rnd["rows"]:
            if "Scale10x" not in str(row.get("metric", "")) \
                    or "error" in row:
                continue
            problems = []
            ab = row.get("ab") or {}
            if ab and not ab.get("sharding_pays", True):
                problems.append(
                    f"partitioned {ab.get('partitioned_pods_per_sec')} "
                    f"< single-partition "
                    f"{ab.get('single_partition_pods_per_sec')} pods/s")
            inv = row.get("invariants") or {}
            for key in ("lost_pods", "double_binds"):
                if inv.get(key):
                    problems.append(f"{key}={inv[key]}")
            cell = row.get("conflict_cell") or {}
            if cell and not cell.get("ok", True):
                problems.append(
                    f"conflict cell failed (conflicts="
                    f"{cell.get('conflicts_total')}, lost="
                    f"{cell.get('lost_pods')}, double="
                    f"{cell.get('double_binds')})")
            if problems:
                flags.append({
                    "metric": row["metric"],
                    "round": rnd["round"],
                    "value": float(row.get("value", 0.0)),
                    "problems": problems,
                })
    return flags


def devscale_flags(rounds: List[dict]) -> List[dict]:
    """The devscale row family's own checks — a devices×throughput row
    can't be judged by its throughput trend alone. Flag the round when:

    - the solve fails its scaling bar: speedup at 4 devices < 1.5× vs
      the 1-device arm (the row's acceptance criterion), or — on REAL
      hardware rows only (``virtual_devices`` false/absent) — scaling
      efficiency (speedup ÷ devices) at 4 devices below 0.6: the mesh
      is mostly burning collective latency, a sharding-layer regression
      even when absolute throughput still looks fine. Virtual-device
      rows are exempt from the efficiency gate by construction: the
      forced host devices share silicon AND the 1-device baseline is
      intra-op multithreaded, so their efficiency understates any real
      mesh;
    - the donation A/B stopped paying: per-cycle h2d bytes or
      device-wait share NOT strictly lower with donation on — either
      the donated buffers regressed to real uploads or the transfer
      accounting started counting resident planes again (the metric-
      lies case the donated-bytes ledger exists to prevent)."""
    flags: List[dict] = []
    for rnd in rounds:
        for row in rnd["rows"]:
            if "devscale" not in str(row.get("metric", "")) \
                    or "error" in row:
                continue
            problems = []
            speedup = (row.get("solve_speedup_vs_1dev") or {}).get("4")
            if speedup is not None and speedup < 1.5:
                problems.append(
                    f"4-device solve speedup {speedup} < 1.5x")
            eff = row.get("scaling_efficiency_4dev")
            if eff is not None and eff < 0.6 \
                    and not row.get("virtual_devices"):
                problems.append(
                    f"scaling efficiency {eff} < 0.6 at 4 devices")
            ab = row.get("donation_ab") or {}
            if ab and not ab.get("donation_pays", True):
                on = ab.get("on") or {}
                off = ab.get("off") or {}
                problems.append(
                    "donation A/B not paying (h2d/cycle "
                    f"on={on.get('h2d_bytes_per_cycle')} "
                    f"off={off.get('h2d_bytes_per_cycle')}, d2h/cycle "
                    f"on={on.get('d2h_bytes_per_cycle')} "
                    f"off={off.get('d2h_bytes_per_cycle')}, wait share "
                    f"on={on.get('device_wait_share')} "
                    f"off={off.get('device_wait_share')})")
            if problems:
                flags.append({
                    "metric": row["metric"],
                    "round": rnd["round"],
                    "value": float(row.get("value", 0.0)),
                    "problems": problems,
                })
    return flags


def replay_flags(rounds: List[dict]) -> List[dict]:
    """The ``replay_*`` family's own checks (ISSUE 13 satellite):
    throughput trend alone cannot judge an open-loop trace-replay row.
    Flag the round when:

    - the row LOST pods (``lost_pods`` > 0, or short-injected — the
      zero-lost invariant is the suite's hardest bar);
    - any family invariant failed (``invariants_ok`` false: gang
      atomicity, priority inversion at quiesce, serve-latency budget);
    - the row's gated SLO verdicts went red (``slo_verdicts_ok``
      false — the family-exempt SLOs are already excluded row-side);
    - the gang family's adjacency A/B stopped paying
      (``adjacency_ab.scored_beats_blind`` false: MeshLocality scoring
      no longer beats the adjacency-blind arm).

    All gate ``--strict``."""
    flags: List[dict] = []
    for rnd in rounds:
        for row in rnd["rows"]:
            if not str(row.get("metric", "")).startswith("replay_") \
                    or "error" in row:
                continue
            problems = []
            if row.get("lost_pods"):
                problems.append(f"lost_pods={row['lost_pods']}")
            if row.get("invariants_ok") is False:
                bad = [k for k, v in
                       (row.get("invariants") or {}).items() if not v]
                problems.append(
                    "invariants failed: " + (", ".join(bad) or "?"))
            if row.get("slo_verdicts_ok") is False:
                slo = (row.get("freshness") or {}).get("slo") or {}
                bad = [n for n, v in slo.items() if v != "ok"
                       and n in (row.get("slo_gated") or slo)]
                problems.append(
                    "slo violated: " + (", ".join(sorted(bad)) or "?"))
            ab = row.get("adjacency_ab") or {}
            if ab and not ab.get("scored_beats_blind", True):
                problems.append(
                    f"adjacency A/B not paying (scored="
                    f"{ab.get('scored_mean_gang_adjacency')} vs blind="
                    f"{ab.get('blind_mean_gang_adjacency')})")
            if problems:
                flags.append({
                    "metric": row["metric"],
                    "round": rnd["round"],
                    "value": float(row.get("value", 0.0)),
                    "problems": problems,
                })
    return flags


def sustained_flags(rounds: List[dict]) -> List[dict]:
    """The ``sustained_arrival`` family's own checks (ISSUE 14
    satellite): the streaming scheduler's open-loop row cannot be
    judged by throughput — the offered rate pins it. Flag the round
    when:

    - p99 arrival→bind exceeds the 500 ms budget (the pipeline's
      latency acceptance bar — the barrier quantized p99 at
      whole-cycle time, and this is the number that proves it's gone);
    - the row LOST pods (``lost_pods`` > 0 or short-injected — the
      replay engine's hardest invariant);
    - the snapshot-staleness SLO verdict went red (a deeper in-flight
      window must never mean solving stale truth);
    - the pipeline stopped overlapping (``telemetry.overlap_share``
      == 0 on a row whose telemetry is present: the streaming loop
      silently degenerated back to the barrier).

    All gate ``--strict``."""
    flags: List[dict] = []
    for rnd in rounds:
        for row in rnd["rows"]:
            if not str(row.get("metric", "")).startswith(
                    "sustained_arrival") or "error" in row:
                continue
            problems = []
            p99 = row.get("p99_arrival_to_bind_ms")
            if p99 is not None and p99 > 500:
                problems.append(
                    f"p99 arrival→bind {p99}ms > 500ms budget")
            if row.get("lost_pods"):
                problems.append(f"lost_pods={row['lost_pods']}")
            if row.get("invariants_ok") is False:
                bad = [k for k, v in
                       (row.get("invariants") or {}).items() if not v]
                problems.append(
                    "invariants failed: " + (", ".join(bad) or "?"))
            slo = (row.get("freshness") or {}).get("slo") or {}
            verdict = slo.get("snapshot_staleness")
            if verdict is not None and verdict != "ok":
                problems.append(
                    f"snapshot_staleness SLO {verdict}")
            tel = row.get("telemetry") or {}
            if tel and "overlap_share" in tel \
                    and not tel.get("overlap_share"):
                problems.append(
                    "overlap_share=0 (pipeline degenerated to the "
                    "barrier)")
            if problems:
                flags.append({
                    "metric": row["metric"],
                    "round": rnd["round"],
                    "value": float(row.get("value", 0.0)),
                    "problems": problems,
                })
    return flags


def hotspot_flags(rounds: List[dict]) -> List[dict]:
    """The ``hotspot_recovery`` family's own checks (ISSUE 15
    satellite): the elastic-control-plane row is a RATIO row — its
    trend says nothing unless the migrations underneath were clean.
    Flag the round when:

    - any watch event was lost across the migrations
      (``lost_watches`` > 0 — the cursor-preserving handoff's hardest
      bar: an informer's final state diverged from server truth);
    - any hard invariant failed (``invariants_ok`` false: lost or
      duplicated pods, relists of unmoved slices, RV regressions, or
      the rebalancer never acting at all);
    - the recovery ratio fell below 0.8 (the rebalanced arm failed to
      claw back ≥80% of the balanced fleet's throughput — the row's
      acceptance bar).

    All gate ``--strict``."""
    flags: List[dict] = []
    for rnd in rounds:
        for row in rnd["rows"]:
            if not str(row.get("metric", "")).startswith(
                    "hotspot_recovery") or "error" in row:
                continue
            problems = []
            if row.get("lost_watches"):
                problems.append(
                    f"lost_watches={row['lost_watches']} (handoff "
                    f"dropped or duplicated events)")
            if row.get("invariants_ok") is False:
                # count-valued invariants are bad when NONZERO;
                # rebalancer_acted is the one boolean (bad when False)
                bad = [k for k, v in
                       (row.get("invariants") or {}).items()
                       if (not v if k == "rebalancer_acted"
                           else bool(v))]
                problems.append(
                    "invariants failed: " + (", ".join(bad) or "?"))
            ratio = row.get("recovery_ratio", row.get("value"))
            if ratio is not None and float(ratio) < 0.8:
                problems.append(
                    f"recovery_ratio {float(ratio):.3f} < 0.8 "
                    f"(rebalancer failed to recover balanced "
                    f"throughput)")
            if problems:
                flags.append({
                    "metric": row["metric"],
                    "round": rnd["round"],
                    "value": float(row.get("value", 0.0)),
                    "problems": problems,
                })
    return flags


def upgrade_flags(rounds: List[dict]) -> List[dict]:
    """The ``upgrade_roll`` family's own checks (ISSUE 16 satellite):
    the rolling-upgrade row is a THROUGHPUT-UNDER-SURGERY row — its
    trend says nothing unless the fleet actually kept serving while
    every process restarted. Flag the round when:

    - any pod was lost across the roll (``lost_pods`` > 0 — injected,
      acked, then absent from both server truth and the bind stream);
    - any watch event was lost or duplicated (``lost_watch_events`` /
      ``duplicated_events`` > 0 — a CompositeCursor failed to carry a
      client across a restart seam exactly-once);
    - any slice whose partition did NOT move was relisted
      (``unmoved_relists`` > 0 — the seam leaked beyond the restarted
      process);
    - a partition's write-freeze window blew its drain budget
      (``frozen_ms_max`` > ``freeze_budget_ms`` — the roll should have
      aborted and rolled back instead);
    - p99 arrival→bind exceeded 500 ms during the roll (the row's
      latency acceptance bar under open-loop load);
    - any freshness SLO went red during the roll
      (``slo_verdicts_ok`` false);
    - the mixed-version wire guard broke (``codec_failures`` > 0 — a
      client's pinned codec version was refused or mis-negotiated
      across a seam);
    - the roll was not exactly-once (``rolled_exactly_once`` false:
      a process restarted twice or never) or any other hard invariant
      failed (``invariants_ok`` false).

    All gate ``--strict``."""
    flags: List[dict] = []
    for rnd in rounds:
        for row in rnd["rows"]:
            if not str(row.get("metric", "")).startswith(
                    "upgrade_roll") or "error" in row:
                continue
            problems = []
            if row.get("lost_pods"):
                problems.append(
                    f"lost_pods={row['lost_pods']} (injected pods "
                    f"vanished across the roll)")
            if row.get("lost_watch_events"):
                problems.append(
                    f"lost_watch_events={row['lost_watch_events']} "
                    f"(informer diverged from server truth)")
            if row.get("duplicated_events"):
                problems.append(
                    f"duplicated_events={row['duplicated_events']} "
                    f"(a seam replayed events already delivered)")
            if row.get("unmoved_relists"):
                problems.append(
                    f"unmoved_relists={row['unmoved_relists']} "
                    f"(restart seam relisted a slice that never "
                    f"moved)")
            frozen = row.get("frozen_ms_max")
            budget = row.get("freeze_budget_ms")
            if (frozen is not None and budget is not None
                    and float(frozen) > float(budget)):
                problems.append(
                    f"frozen_ms_max {float(frozen):.1f} > budget "
                    f"{float(budget):.0f}ms (drain overran; should "
                    f"have aborted and rolled back)")
            p99 = row.get("p99_arrival_to_bind_ms")
            if p99 is not None and float(p99) > 500.0:
                problems.append(
                    f"p99_arrival_to_bind {float(p99):.0f}ms > 500ms "
                    f"under open-loop load during the roll")
            if row.get("slo_verdicts_ok") is False:
                problems.append(
                    "freshness SLO went red during the roll")
            if row.get("codec_failures"):
                problems.append(
                    f"codec_failures={row['codec_failures']} "
                    f"(mixed-version wire guard refused a client)")
            if row.get("rolled_exactly_once") is False:
                problems.append(
                    "roll not exactly-once (a process restarted "
                    "twice or never)")
            if row.get("invariants_ok") is False:
                why = (row.get("invariants") or {}).get("failed", "?")
                problems.append(f"invariants failed: {why}")
            if problems:
                flags.append({
                    "metric": row["metric"],
                    "round": rnd["round"],
                    "value": float(row.get("value", 0.0)),
                    "problems": problems,
                })
    return flags


def critpath_flags(rounds: List[dict]) -> List[dict]:
    """The fleet-tracing family's own checks (ISSUE 17 satellite): a
    bench row that carries a ``critical_path`` sub-object claims its
    latency is ATTRIBUTED — phase shares over the sampled pods'
    stitched cross-process span trees. Flag the round when:

    - ``unattributed_share`` > 0.05 (more than 5% of the summed
      in-flight windows has no covering phase span — the trace has a
      hole, so the phase shares cannot be trusted);
    - ``fully_attributed`` < 0.95 (fewer than 95% of sampled pods are
      individually ≤5% unattributed — the aggregate hides broken pods);
    - ``max_skew_ms`` exceeds ``skew_bound_ms`` (a scrape's half-RTT
      clock-offset bound was worse than the merge contract allows —
      cross-process orderings in the trace are not trustworthy);
    - a row that should carry a fleet trace lacks one: within rounds
      where at least one row DOES carry ``critical_path`` (tracing-era
      rounds — earlier committed artifacts predate the layer and stay
      green), a headline row measured with the tracer on
      (``trace_sample_rate`` > 0) or an ``upgrade_roll`` row without
      the sub-object means the collection silently broke.

    All gate ``--strict``."""
    flags: List[dict] = []
    for rnd in rounds:
        if not any("critical_path" in row for row in rnd["rows"]):
            continue
        for row in rnd["rows"]:
            if "error" in row:
                continue
            metric = str(row.get("metric", ""))
            cp = row.get("critical_path")
            problems = []
            if cp is None:
                should_carry = (
                    float(row.get("trace_sample_rate", 0.0) or 0.0) > 0
                    or metric.startswith("upgrade_roll"))
                if should_carry:
                    problems.append(
                        "row ran with tracing on but carries no "
                        "critical_path (fleet-trace collection "
                        "silently broke)")
            else:
                unatt = float(cp.get("unattributed_share", 0.0))
                if unatt > 0.05:
                    problems.append(
                        f"unattributed_share {unatt:.3f} > 0.05 "
                        f"(trace hole — phase shares untrustworthy)")
                fully = cp.get("fully_attributed")
                if fully is not None and float(fully) < 0.95:
                    problems.append(
                        f"fully_attributed {float(fully):.3f} < 0.95 "
                        f"(aggregate hides per-pod trace holes)")
                skew = float(cp.get("max_skew_ms", 0.0))
                bound = float(cp.get("skew_bound_ms", 50.0))
                if skew > bound:
                    problems.append(
                        f"max_skew_ms {skew:.3f} > bound {bound:.1f} "
                        f"(cross-process ordering not trustworthy)")
            if problems:
                flags.append({
                    "metric": metric,
                    "round": rnd["round"],
                    "value": float(row.get("value", 0.0) or 0.0),
                    "problems": problems,
                })
    return flags


def federation_flags(rounds: List[dict]) -> List[dict]:
    """The ``federation_*`` family's own checks (ISSUE 18 satellite):
    the federation rows are ROBUSTNESS-UNDER-PARTITION rows — a
    cross-cluster placement tier only earns its keep if losing a whole
    cluster loses zero pods and saturating one cluster stays invisible
    to its tenants. Flag the round when:

    - any pod was lost fleet-wide (``lost_pods`` > 0 — injected, acked
      by some cell, then absent from every survivor's truth);
    - a gang was split across clusters (``gang_splits`` > 0 — gangs
      place atomically or not at all; a cross-cluster split deadlocks
      the workload);
    - a SURVIVOR cell relisted (``survivor_relists`` > 0 — the
      cluster-loss seam leaked beyond the dead cell);
    - any per-cluster freshness/latency SLO went red
      (``per_cluster_slo_ok`` false — spillover must keep the
      saturated cell's own tenants green);
    - a cluster was failed over but fewer than 80% of its orphaned
      pods re-bound within the recovery budget (``recovery_ratio``
      < 0.8 with ``failovers`` > 0);
    - a spillover row spilled nothing (``spilled`` == 0 on a
      ``federation_spill`` row — the saturation penalty never fired,
      so the row measured a plain single-cluster run);
    - any fleet freshness SLO went red (``slo_verdicts_ok`` false) or
      any other hard invariant failed (``invariants_ok`` false).

    All gate ``--strict``."""
    flags: List[dict] = []
    for rnd in rounds:
        for row in rnd["rows"]:
            metric = str(row.get("metric", ""))
            if not metric.startswith("federation_") or "error" in row:
                continue
            problems = []
            if row.get("lost_pods"):
                problems.append(
                    f"lost_pods={row['lost_pods']} (pods vanished "
                    f"fleet-wide across the cluster loss)")
            if row.get("gang_splits"):
                problems.append(
                    f"gang_splits={row['gang_splits']} (a gang was "
                    f"split across clusters — placement must be "
                    f"atomic)")
            if row.get("survivor_relists"):
                problems.append(
                    f"survivor_relists={row['survivor_relists']} "
                    f"(cluster-loss seam leaked a relist into a "
                    f"surviving cell)")
            if row.get("per_cluster_slo_ok") is False:
                problems.append(
                    "a per-cluster SLO went red (spillover leaked "
                    "onto the saturated cell's own tenants)")
            ratio = row.get("recovery_ratio")
            if (row.get("failovers") and ratio is not None
                    and float(ratio) < 0.8):
                problems.append(
                    f"recovery_ratio {float(ratio):.2f} < 0.8 "
                    f"(failover re-placed too few orphans within "
                    f"the recovery budget)")
            if (metric.startswith("federation_spill")
                    and row.get("spilled") == 0):
                problems.append(
                    "spilled=0 on a spillover row (saturation "
                    "penalty never fired — row measured nothing)")
            if row.get("slo_verdicts_ok") is False:
                problems.append(
                    "fleet freshness SLO went red during the storm")
            if row.get("invariants_ok") is False:
                why = (row.get("invariants") or {}).get("failed", "?")
                problems.append(f"invariants failed: {why}")
            if problems:
                flags.append({
                    "metric": metric,
                    "round": rnd["round"],
                    "value": float(row.get("value", 0.0)),
                    "problems": problems,
                })
    return flags


def readtier_flags(rounds: List[dict]) -> List[dict]:
    """The ``watchherd*`` family's own checks (ISSUE 19 satellite):
    read-tier rows are LOSS-AND-STALENESS rows — replica-served
    watches only earn their keep if every informer converges to the
    owner's truth with zero lost or duplicated events, replicas stay
    inside their lag budget, and the replicated fan-out actually
    scales. Flag the round when:

    - an arm row (``watchherd[...]``) lost events (``lost_events`` or
      ``unconverged_informers`` > 0 — an informer's steady state
      diverged from the owner's truth at quiesce), re-applied a
      duplicate (``dup_suppressed`` > 0 on the happy path), relisted
      (``relists`` > 0 — a healthy tier never breaks a watch), never
      routed a single read through a replica while replicas were
      advertised (``replica_reads`` < 1 with ``replicas`` > 0),
      blew the replication-lag budget
      (``replication_lag_p99_ms`` > ``lag_budget_ms``), went red on
      the freshness SLO, or failed any hard invariant;
    - the scaling row (``watchherd_scaling[...]``) shows fan-out per
      owner CPU-second below the committed floor (``read_scaling_x``
      < ``read_scaling_floor_x``), the write path regressing against
      the replicas-off arm (``write_flat_ok`` false), or the
      differential arms disagreeing on final state
      (``differential_match`` false — replicas changed WHAT was
      stored, not just who served it);
    - a chaos cell row (``watchherd_cell[...]``) failed its scenario
      judgement (``ok``/``invariants_ok`` false), lost events, or
      leaked relists beyond the faulted replica
      (``relists_beyond_faulted`` > 0).

    All gate ``--strict``."""
    flags: List[dict] = []
    for rnd in rounds:
        for row in rnd["rows"]:
            metric = str(row.get("metric", ""))
            if not metric.startswith(("watchherd[", "watchherd_scaling[",
                                      "watchherd_cell[")) \
                    or "error" in row:
                continue
            problems = []
            if metric.startswith("watchherd["):
                if row.get("lost_events"):
                    problems.append(
                        f"lost_events={row['lost_events']} (informer "
                        f"steady state diverged from owner truth)")
                if row.get("unconverged_informers"):
                    problems.append(
                        f"unconverged_informers="
                        f"{row['unconverged_informers']} (herd never "
                        f"reached the owner's state hash)")
                if row.get("dup_suppressed"):
                    problems.append(
                        f"dup_suppressed={row['dup_suppressed']} "
                        f"(duplicate frames on the happy path)")
                if row.get("relists"):
                    problems.append(
                        f"relists={row['relists']} (a healthy read "
                        f"tier never breaks a watch)")
                if (row.get("replicas") and
                        not row.get("replica_reads")):
                    problems.append(
                        "replica_reads=0 with replicas advertised "
                        "(reads never routed through the read tier)")
                lag = row.get("replication_lag_p99_ms")
                budget = row.get("lag_budget_ms")
                if (lag is not None and budget
                        and float(lag) > float(budget)):
                    problems.append(
                        f"replication lag p99 {float(lag):.1f}ms over "
                        f"the {float(budget):.0f}ms budget")
                slo = (row.get("freshness") or {}).get("slo") or {}
                if any(v == "violated" for v in slo.values()):
                    red = [k for k, v in slo.items() if v == "violated"]
                    problems.append(
                        f"freshness SLO red: {', '.join(red)}")
            elif metric.startswith("watchherd_scaling["):
                floor = float(row.get("read_scaling_floor_x") or 1.5)
                sx = row.get("read_scaling_x")
                if sx is not None and float(sx) < floor:
                    problems.append(
                        f"read scaling {float(sx):.2f}x < {floor:.1f}x "
                        f"floor (fan-out per owner CPU-second)")
                if row.get("write_flat_ok") is False:
                    problems.append(
                        f"write throughput regressed vs the "
                        f"replicas-off arm "
                        f"(ratio {row.get('write_ratio')})")
                if row.get("differential_match") is False:
                    problems.append(
                        "differential arms disagree on final state "
                        "(replicas changed what was stored)")
            else:  # watchherd_cell[...]
                if row.get("ok") is False:
                    problems.append(
                        f"cell failed: {row.get('failure') or '?'}")
                if row.get("lost_events"):
                    problems.append(
                        f"lost_events={row['lost_events']} across the "
                        f"fault")
                if row.get("relists_beyond_faulted"):
                    problems.append(
                        f"relists_beyond_faulted="
                        f"{row['relists_beyond_faulted']} (fault seam "
                        f"leaked relists past the faulted replica)")
            if row.get("invariants_ok") is False:
                why = row.get("invariants") or row.get("failure") or "?"
                problems.append(f"invariants failed: {why}")
            if problems:
                flags.append({
                    "metric": metric,
                    "round": rnd["round"],
                    "value": float(row.get("value", 0.0)),
                    "problems": problems,
                })
    return flags


def mirror_flags(rounds: List[dict]) -> List[dict]:
    """Device-mirror rows (``bench.py --config mirrorab`` and the
    chaos-matrix mirror suite): the mirror-on sustained arm must keep
    its encode share near zero (the stage the mirror exists to kill),
    never reseed unexpectedly, and stay strictly below the committed
    donation-row per-cycle h2d; the A/B row must show the on arm at or
    below the off arm's per-cycle h2d with bit-identical placements;
    chaos cells must hold the differential across faults."""
    flags = []
    for rnd in rounds:
        for row in rnd["rows"]:
            metric = str(row.get("metric", ""))
            if not metric.startswith(("mirror_sustained[", "mirror_ab[",
                                      "mirror_cell[")) \
                    or "error" in row:
                continue
            problems = []
            if metric.startswith("mirror_sustained["):
                mirror = row.get("mirror") or {}
                on_arm = row.get("mirror_arm") == "on"
                if on_arm:
                    share = row.get("encode_share")
                    budget = float(
                        row.get("encode_share_budget") or 0.05)
                    if share is not None and float(share) >= budget:
                        problems.append(
                            f"encode share {float(share):.4f} >= "
                            f"{budget:g} budget on a mirror-on row "
                            f"(the resident planes should have killed "
                            f"the encode stage)")
                    allowed = int(row.get("reseeds_allowed") or 0)
                    if int(mirror.get("reseeds") or 0) > allowed:
                        problems.append(
                            f"reseeds={mirror['reseeds']} > "
                            f"{allowed} allowed (journal gaps or "
                            f"inexpressible deltas forced full host "
                            f"encodes mid-run)")
                    h2d = row.get("h2d_per_cycle_bytes")
                    h2d_budget = row.get("h2d_per_cycle_budget_bytes")
                    if (h2d is not None and h2d_budget
                            and float(h2d) >= float(h2d_budget)):
                        problems.append(
                            f"per-cycle h2d {float(h2d):,.0f}B >= the "
                            f"committed donation-row budget "
                            f"{float(h2d_budget):,.0f}B")
                if row.get("lost_pods"):
                    problems.append(
                        f"lost_pods={row['lost_pods']} (arrivals "
                        f"never bound)")
                p99 = row.get("p99_arrival_to_bind_ms")
                p99_budget = row.get("p99_budget_ms")
                if (p99 is not None and p99_budget
                        and float(p99) > float(p99_budget)):
                    problems.append(
                        f"arrival→bind p99 {float(p99):.0f}ms over "
                        f"the {float(p99_budget):.0f}ms SLO")
            elif metric.startswith("mirror_ab["):
                if row.get("differential_match") is False:
                    problems.append(
                        "differential arms disagree on final "
                        "placements (the mirror changed what was "
                        "bound)")
                on_h2d = row.get("h2d_per_cycle_on_bytes")
                off_h2d = row.get("h2d_per_cycle_off_bytes")
                # 10% headroom: per-cycle h2d jitters with batch
                # splits even over identical traces — the flag is for
                # scatter triples costing MORE than the encode they
                # replaced, not for cycle-count noise
                if (on_h2d is not None and off_h2d is not None
                        and float(on_h2d) > 1.10 * float(off_h2d)):
                    problems.append(
                        f"mirror-on per-cycle h2d "
                        f"{float(on_h2d):,.0f}B above the off arm's "
                        f"{float(off_h2d):,.0f}B (scatter triples "
                        f"cost more than the encode they replaced)")
            else:  # mirror_cell[...]
                if row.get("ok") is False:
                    problems.append(
                        f"cell failed: {row.get('failure') or '?'}")
                if row.get("differential_match") is False:
                    problems.append(
                        "differential arms disagree across the fault")
                if row.get("lost_pods"):
                    problems.append(
                        f"lost_pods={row['lost_pods']} across the "
                        f"fault")
            if row.get("invariants_ok") is False:
                why = row.get("invariants") or row.get("failure") or "?"
                problems.append(f"invariants failed: {why}")
            if problems:
                flags.append({
                    "metric": metric,
                    "round": rnd["round"],
                    "value": float(row.get("value", 0.0)),
                    "problems": problems,
                })
    return flags


def _short_metric(metric: str) -> str:
    m = re.match(r"(\w+)\[([^\]]*)\]", metric)
    return m.group(2) if m else metric


def render(series: Dict[str, List[dict]], flags: List[dict],
           band_floor: float = DEFAULT_NOISE_BAND) -> str:
    lines: List[str] = []
    open_flags = open_regressions(flags)
    recovered = [f for f in flags if "recovered_round" in f]
    flagged = {(f["metric"], f["round"]) for f in open_flags}
    for metric in sorted(series):
        points = series[metric]
        band = noise_band(points, floor=band_floor)
        lines.append(f"{_short_metric(metric)}  "
                     f"(noise band ±{band * 100:.0f}%)")
        lines.append(f"  {'round':>5} {'pods/s':>10} {'p99 ms':>8} "
                     f"{'Δ vs prior':>10}  flag")
        prev = None
        for p in points:
            delta = ""
            if prev and prev > 0:
                delta = f"{100.0 * (p['value'] - prev) / prev:+.1f}%"
            mark = "REGRESSION" if (metric, p["round"]) in flagged else ""
            p99 = f"{p['p99_ms']:.0f}" if p.get("p99_ms") is not None \
                else "-"
            lines.append(f"  r{p['round']:>4} {p['value']:>10.1f} "
                         f"{p99:>8} {delta:>10}  {mark}")
            prev = p["value"]
        lines.append("")
    if open_flags:
        lines.append("flagged regressions:")
        for f in open_flags:
            lines.append(
                f"  r{f['round']} {_short_metric(f['metric'])}: "
                f"{f['value']:.1f} vs baseline {f['baseline']:.1f} "
                f"({f['delta_pct']}%, band ±{f['band_pct']}%) — "
                f"{f['attribution']}")
    else:
        lines.append("no out-of-band regressions "
                     f"(band floor ±{band_floor * 100:.0f}%)")
    if recovered:
        lines.append("recovered regressions (back inside the band, "
                     "no longer gating):")
        for f in recovered:
            lines.append(
                f"  r{f['round']} {_short_metric(f['metric'])}: "
                f"{f['value']:.1f} ({f['delta_pct']}%) — recovered "
                f"in r{f['recovered_round']}")
    return "\n".join(lines)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--dir", default=_REPO_ROOT,
                    help="directory holding BENCH_r*.json")
    ap.add_argument("--band", type=float, default=DEFAULT_NOISE_BAND,
                    help="noise-band floor as a fraction (default 0.30)")
    ap.add_argument("--telemetry", default=None,
                    help="KTPU_TELEMETRY dir of per-cycle JSONL to "
                         "summarize alongside the trend")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable output")
    ap.add_argument("--strict", action="store_true",
                    help="exit 1 when any regression is flagged")
    args = ap.parse_args(argv)

    rounds = load_rounds(args.dir)
    if not rounds:
        print(f"no BENCH_r*.json under {args.dir}", file=sys.stderr)
        return 2
    series = build_series(rounds)
    flags = detect_regressions(series, band_floor=args.band)
    open_flags = open_regressions(flags)
    scale_flags = scale_ab_flags(rounds)
    dev_flags = devscale_flags(rounds)
    rep_flags = replay_flags(rounds)
    sus_flags = sustained_flags(rounds)
    hot_flags = hotspot_flags(rounds)
    upg_flags = upgrade_flags(rounds)
    fed_flags = federation_flags(rounds)
    crit_flags = critpath_flags(rounds)
    rt_flags = readtier_flags(rounds)
    mir_flags = mirror_flags(rounds)
    telemetry = summarize_telemetry(args.telemetry) \
        if args.telemetry else None
    if args.json:
        print(json.dumps({
            "rounds": [r["round"] for r in rounds],
            "series": {
                m: [{k: v for k, v in p.items() if k != "diags"}
                    for p in pts]
                for m, pts in series.items()
            },
            "regressions": open_flags,
            "recovered": [f for f in flags
                          if "recovered_round" in f],
            "scale_flags": scale_flags,
            "devscale_flags": dev_flags,
            "replay_flags": rep_flags,
            "sustained_flags": sus_flags,
            "hotspot_flags": hot_flags,
            "upgrade_flags": upg_flags,
            "federation_flags": fed_flags,
            "critpath_flags": crit_flags,
            "readtier_flags": rt_flags,
            "mirror_flags": mir_flags,
            "telemetry": telemetry,
        }, indent=1))
    else:
        print(render(series, flags, band_floor=args.band))
        if scale_flags:
            print("\nscale10x A/B / invariant flags:")
            for f in scale_flags:
                print(f"  r{f['round']} {_short_metric(f['metric'])}: "
                      + "; ".join(f["problems"]))
        if dev_flags:
            print("\ndevscale scaling / donation flags:")
            for f in dev_flags:
                print(f"  r{f['round']} {_short_metric(f['metric'])}: "
                      + "; ".join(f["problems"]))
        if rep_flags:
            print("\nreplay SLO / invariant flags:")
            for f in rep_flags:
                print(f"  r{f['round']} {_short_metric(f['metric'])}: "
                      + "; ".join(f["problems"]))
        if sus_flags:
            print("\nsustained-arrival latency / pipeline flags:")
            for f in sus_flags:
                print(f"  r{f['round']} {_short_metric(f['metric'])}: "
                      + "; ".join(f["problems"]))
        if hot_flags:
            print("\nhotspot recovery / handoff flags:")
            for f in hot_flags:
                print(f"  r{f['round']} {_short_metric(f['metric'])}: "
                      + "; ".join(f["problems"]))
        if upg_flags:
            print("\nrolling-upgrade / version-skew flags:")
            for f in upg_flags:
                print(f"  r{f['round']} {_short_metric(f['metric'])}: "
                      + "; ".join(f["problems"]))
        if fed_flags:
            print("\nfederation placement / cluster-loss flags:")
            for f in fed_flags:
                print(f"  r{f['round']} {_short_metric(f['metric'])}: "
                      + "; ".join(f["problems"]))
        if crit_flags:
            print("\nfleet-trace critical-path flags:")
            for f in crit_flags:
                print(f"  r{f['round']} {_short_metric(f['metric'])}: "
                      + "; ".join(f["problems"]))
        if rt_flags:
            print("\nread-tier watch-herd flags:")
            for f in rt_flags:
                print(f"  r{f['round']} {_short_metric(f['metric'])}: "
                      + "; ".join(f["problems"]))
        if mir_flags:
            print("\ndevice-mirror flags:")
            for f in mir_flags:
                print(f"  r{f['round']} {_short_metric(f['metric'])}: "
                      + "; ".join(f["problems"]))
        if telemetry:
            print(f"\ntelemetry stream ({args.telemetry}): "
                  f"{telemetry['cycles']} cycles "
                  f"({telemetry['warming_cycles']} warming), "
                  f"{telemetry['compiles']} compiles, "
                  f"device-wait share {telemetry['device_wait_share']:.0%}, "
                  f"overlap share {telemetry['overlap_share']:.0%}, "
                  f"pad waste {telemetry['pad_waste_pct']:.1f}%")
    return 1 if (args.strict
                 and (open_flags or scale_flags or dev_flags
                      or rep_flags or sus_flags or hot_flags
                      or upg_flags or fed_flags
                      or crit_flags or rt_flags
                      or mir_flags)) else 0


if __name__ == "__main__":
    raise SystemExit(main())

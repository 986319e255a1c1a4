#!/usr/bin/env python3
"""Stage-share regression gate over BENCH JSON `stage_breakdown` objects.

bench.py attributes its end-to-end PUT/GET wall clock to pipeline stages
via the always-on perf ledger (control/perf.py). This gate compares the
latest BENCH line's breakdown against the previous one and flags any stage
whose SHARE of total latency grew by more than a threshold -- a share
shift localizes a regression to a stage even when absolute times moved
with the machine (shares are scale-free; GiB/s is not).

A stage is flagged when BOTH hold:
  * its share grew by more than `threshold` (absolute, e.g. 0.10 = ten
    percentage points), and
  * its absolute time grew too -- a share can grow because OTHER stages
    got faster, which is an improvement, not a regression.

Codec-floor mode (automatic in stage mode): when the new BENCH line claims
`device: true`, its headline encode number -- and the fused encode + hash
number, when measured -- must beat the same line's recorded CPU floor
(`cpu_avx2_gibs`). A "device" round that encodes slower than the host AVX2
path means the device codec regressed into net-negative territory; the
seed shipped exactly that (a device encode number of 0.0) for five rounds
without any gate noticing. bench.py exits non-zero without an accelerator;
older lines that say `device: false` are never floor-gated.

SLO mode (`--slo`) gates loadgen reports (tools/loadgen.py) instead:
per-op p99 regressions between two same-scenario reports, plus absolute
SLO violations (budget burn > 1, declared p99 target missed) in the new
report. A p99 is flagged only when it grew by BOTH a relative tolerance
and an absolute floor -- bucket-scheme quantiles are coarse, and a
1 ms -> 2 ms "doubling" is measurement noise, not a regression.

Usage:
    python tools/perf_gate.py OLD.json NEW.json [--threshold 0.10]
    python tools/perf_gate.py --slo OLD.json NEW.json \\
        [--p99-tol=0.25] [--min-ms=5]

Exit 0 = no stage regressed, 1 = regression(s) flagged, 2 = unusable
input (missing/unparseable breakdowns -- the gate cannot vouch either
way, callers decide whether that blocks).
"""

from __future__ import annotations

import json
import sys

DEFAULT_THRESHOLD = 0.10  # share points a stage may grow before flagging
DEFAULT_P99_TOL = 0.25    # relative p99 growth tolerated between reports
DEFAULT_MIN_MS = 5.0      # ...and the absolute floor under which it's noise


def _breakdowns(bench: dict) -> dict:
    """Phase -> breakdown from one BENCH JSON object (tolerates absence)."""
    sb = bench.get("stage_breakdown")
    return sb if isinstance(sb, dict) else {}


def compare(old: dict, new: dict, threshold: float = DEFAULT_THRESHOLD) -> list[dict]:
    """Regressed stages between two BENCH JSON objects.

    Returns one record per flagged stage: phase, stage, old/new share,
    old/new total_ms. Stages present on only one side are skipped (no
    basis for a delta); phases compare independently.
    """
    flagged: list[dict] = []
    old_sb, new_sb = _breakdowns(old), _breakdowns(new)
    for phase, new_phase in new_sb.items():
        old_phase = old_sb.get(phase)
        if not isinstance(old_phase, dict):
            continue
        old_stages = old_phase.get("stages", {})
        for stage, new_row in new_phase.get("stages", {}).items():
            old_row = old_stages.get(stage)
            if not isinstance(old_row, dict) or not isinstance(new_row, dict):
                continue
            d_share = float(new_row.get("share", 0.0)) - float(old_row.get("share", 0.0))
            d_ms = float(new_row.get("total_ms", 0.0)) - float(old_row.get("total_ms", 0.0))
            if d_share > threshold and d_ms > 0:
                flagged.append(
                    {
                        "phase": phase,
                        "stage": stage,
                        "old_share": old_row.get("share", 0.0),
                        "new_share": new_row.get("share", 0.0),
                        "old_total_ms": old_row.get("total_ms", 0.0),
                        "new_total_ms": new_row.get("total_ms", 0.0),
                    }
                )
    return flagged


def codec_floor_findings(new: dict) -> list[dict]:
    """Device-codec floor violations in one BENCH line (empty when the line
    makes no device claim or carries no codec keys).

    Gated metrics: the headline `value` (device encode GiB/s) always; the
    fused encode + hash number only when it was actually measured (non-zero, no
    recorded error) -- a skipped secondary metric is absence of evidence,
    not a regression.
    """
    if new.get("device") is not True:
        return []
    try:
        floor = float(new.get("cpu_avx2_gibs", 0.0))
    except (TypeError, ValueError):
        return []
    if floor <= 0:
        return []
    findings: list[dict] = []
    for key, err_key in (("value", None), ("fused_encode_hash_gibs", "fused_encode_hash_error")):
        if key not in new:
            continue
        if err_key and new.get(err_key):
            continue
        try:
            v = float(new[key])
        except (TypeError, ValueError):
            continue
        if key != "value" and v == 0.0:
            continue
        if v <= floor:
            findings.append(
                {"kind": "codec-floor", "metric": key,
                 "gibs": v, "cpu_floor_gibs": floor}
            )
    return findings


def compare_slo(
    old: dict,
    new: dict,
    p99_tol: float = DEFAULT_P99_TOL,
    min_ms: float = DEFAULT_MIN_MS,
) -> list[dict]:
    """SLO findings between two loadgen reports (tolerates partial shapes).

    Five finding kinds:
      * p99-regression: an op's p99 grew past old * (1 + p99_tol) AND by
        more than min_ms (both sides must report the op);
      * burn-violation: the new report burned more than its whole error
        budget (burn > 1.0) -- absolute, old report not required;
      * p99-violation: the new report misses its own declared p99 target;
      * compare-violation: a compare block in the new report (dict, or one
        entry of a sweep list like put_scaling's) missed its min_ratio;
      * cache-violation: the report's cache_slo block (hot-read memcache
        hit-ratio promise) judged itself not ok.
    """
    findings: list[dict] = []
    old_ops = old.get("ops") if isinstance(old.get("ops"), dict) else {}
    new_ops = new.get("ops") if isinstance(new.get("ops"), dict) else {}
    for op, new_row in sorted(new_ops.items()):
        old_row = old_ops.get(op)
        if not isinstance(new_row, dict) or not isinstance(old_row, dict):
            continue
        try:
            old_p99 = float(old_row.get("p99_ms", 0.0))
            new_p99 = float(new_row.get("p99_ms", 0.0))
        except (TypeError, ValueError):
            continue
        if old_p99 > 0 and new_p99 > old_p99 * (1.0 + p99_tol) and new_p99 - old_p99 > min_ms:
            findings.append(
                {"kind": "p99-regression", "op": op,
                 "old_p99_ms": old_p99, "new_p99_ms": new_p99}
            )
    slo = new.get("slo") if isinstance(new.get("slo"), dict) else {}
    for op, row in sorted(slo.items()):
        if not isinstance(row, dict):
            continue
        try:
            burn = float(row.get("budget_burn", 0.0))
        except (TypeError, ValueError):
            burn = 0.0
        if burn > 1.0:
            findings.append(
                {"kind": "burn-violation", "op": op, "budget_burn": burn,
                 "error_budget": row.get("error_budget")}
            )
        if row.get("p99_ok") is False:
            findings.append(
                {"kind": "p99-violation", "op": op,
                 "p99_ms": row.get("p99_ms"),
                 "target_p99_ms": row.get("target_p99_ms")}
            )
    cmp = new.get("compare")
    blocks = cmp if isinstance(cmp, list) else [cmp] if isinstance(cmp, dict) else []
    for entry in blocks:
        if isinstance(entry, dict) and entry.get("reproduced") is False:
            findings.append(
                {"kind": "compare-violation",
                 "a": entry.get("a"), "b": entry.get("b"),
                 "op": entry.get("op"), "metric": entry.get("metric"),
                 "ratio": entry.get("ratio"),
                 "min_ratio": entry.get("min_ratio")}
            )
    cache_slo = new.get("cache_slo")
    if isinstance(cache_slo, dict) and cache_slo.get("ok") is False:
        findings.append(
            {"kind": "cache-violation",
             "phase": cache_slo.get("phase", ""),
             "hit_ratio": cache_slo.get("hit_ratio"),
             "min_hit_ratio": cache_slo.get("min_hit_ratio"),
             "error": cache_slo.get("error", "")}
        )
    return findings


def _load(path: str) -> dict | None:
    """Last parseable JSON object line of a file (BENCH logs are JSONL;
    the final line is the bench's one-object contract)."""
    try:
        with open(path) as f:
            lines = [ln.strip() for ln in f if ln.strip()]
    except OSError as e:
        print(f"perf_gate: {path}: {e}", file=sys.stderr)
        return None
    for ln in reversed(lines):
        try:
            doc = json.loads(ln)
        except json.JSONDecodeError:
            continue
        if isinstance(doc, dict):
            return doc
    print(f"perf_gate: {path}: no JSON object line", file=sys.stderr)
    return None


def main(argv: list[str]) -> int:
    args = [a for a in argv if not a.startswith("--")]
    threshold = DEFAULT_THRESHOLD
    p99_tol, min_ms = DEFAULT_P99_TOL, DEFAULT_MIN_MS
    slo_mode = "--slo" in argv
    for a in argv:
        if a.startswith("--threshold="):
            threshold = float(a.split("=", 1)[1])
        elif a.startswith("--p99-tol="):
            p99_tol = float(a.split("=", 1)[1])
        elif a.startswith("--min-ms="):
            min_ms = float(a.split("=", 1)[1])
    if len(args) != 2:
        print(__doc__, file=sys.stderr)
        return 2
    old, new = _load(args[0]), _load(args[1])
    if old is None or new is None:
        return 2
    if slo_mode:
        if not new.get("ops") and not new.get("slo"):
            print("perf_gate: new report has no ops/slo sections; nothing to gate",
                  file=sys.stderr)
            return 2
        findings = compare_slo(old, new, p99_tol, min_ms)
        for f in findings:
            if f["kind"] == "p99-regression":
                print(f"REGRESSED p99 {f['op']}: "
                      f"{f['old_p99_ms']:.1f} ms -> {f['new_p99_ms']:.1f} ms")
            elif f["kind"] == "burn-violation":
                print(f"SLO BURN {f['op']}: {f['budget_burn']:.2f}x the error budget")
            elif f["kind"] == "compare-violation":
                print(f"COMPARE MISS {f['a']}/{f['b']} {f['op']} {f['metric']}: "
                      f"ratio {f['ratio']} < {f['min_ratio']}")
            elif f["kind"] == "cache-violation":
                where = f" ({f['phase']})" if f.get("phase") else ""
                why = f": {f['error']}" if f.get("error") else (
                    f": hit ratio {f['hit_ratio']} < {f['min_hit_ratio']}")
                print(f"CACHE MISS{where}{why}")
            else:
                print(f"SLO MISS {f['op']}: p99 {f['p99_ms']} ms "
                      f"over target {f['target_p99_ms']} ms")
        if not findings:
            print("perf_gate: slo ok")
        return 1 if findings else 0
    floor = codec_floor_findings(new)
    for f in floor:
        print(
            f"CODEC FLOOR {f['metric']}: {f['gibs']:.2f} GiB/s on-device "
            f"<= CPU floor {f['cpu_floor_gibs']:.2f} GiB/s"
        )
    if not _breakdowns(old) or not _breakdowns(new):
        print("perf_gate: no stage_breakdown on one side; nothing to compare",
              file=sys.stderr)
        return 1 if floor else 2
    flagged = compare(old, new, threshold)
    for f in flagged:
        print(
            f"REGRESSED {f['phase']}/{f['stage']}: share "
            f"{f['old_share']:.3f} -> {f['new_share']:.3f}, "
            f"{f['old_total_ms']:.1f} ms -> {f['new_total_ms']:.1f} ms"
        )
    if not flagged and not floor:
        print("perf_gate: ok")
    return 1 if (flagged or floor) else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

"""Compare two bench-round JSON records and flag perf regressions.

    python -m tools.bench_diff A.json B.json
    python -m tools.bench_diff A.json B.json --threshold 0.10 --json

Input handling (pure stdlib, no framework import):

- Both the raw bench summary (what ``bench.py`` prints) and the
  driver's wrapper shape ``{"n", "cmd", "rc", "tail", "parsed"}`` are
  accepted — the wrapper is unwrapped to its ``parsed`` dict.
- Every numeric key present in both rounds is compared. Direction is
  inferred from the key name (throughput-like keys are
  higher-is-better, latency/size-like keys lower-is-better; unknown
  keys are reported as neutral and never flagged).
- **Honesty about broken rounds**: a round with ``rc != 0``, a
  ``status`` of ``partial``/``failed``/``recovered``, an ``error``
  field, or a zeroed ``vs_baseline`` did not produce trustworthy
  numbers. The diff still prints, but every flag is downgraded to
  *advisory* and the exit code stays 0 — a dead-device round must not
  read as a 100% regression.

Exit code: 1 only when both rounds are clean AND at least one metric
regressed past ``--threshold`` (default 5%).
"""
from __future__ import annotations

import argparse
import json
import re
import sys
from typing import Dict, List, Optional, Tuple

__all__ = ["load_round", "classify", "diff_rounds", "main"]

# key-name → direction rules; first match wins, unknown keys neutral
_HIGHER = re.compile(
    r"(per_sec|_rps$|vs_baseline|speedup|goodput|accept|hit_rate|"
    r"fraction_of_synthetic|ratio$|_mfu|tokens_total|improvement|"
    r"bitwise_ok|reroles|balance)")
_LOWER = re.compile(
    r"(_seconds|_ms$|_s$|_p50|_p90|_p95|_p99|_bytes|bubble|pad_waste|"
    r"exposed|latency|restarts|_errors|dropped|redispatch|"
    r"parity_vs_oracle)")

_BAD_STATUS = ("partial", "failed", "recovered")


def load_round(path: str) -> Tuple[Dict, List[str]]:
    """(metrics dict, caveats) for one round file; unwraps the driver
    wrapper and collects the reasons this round is untrustworthy."""
    with open(path) as f:
        doc = json.load(f)
    caveats: List[str] = []
    if isinstance(doc, dict) and "parsed" in doc and "cmd" in doc:
        if int(doc.get("rc", 0) or 0) != 0:
            caveats.append(f"rc={doc['rc']}")
        doc = doc.get("parsed") or {}
    if not isinstance(doc, dict):
        return {}, caveats + ["not a JSON object"]
    status = doc.get("status")
    if status in _BAD_STATUS:
        caveats.append(f"status={status}")
    if doc.get("error"):
        caveats.append(f"error: {str(doc['error'])[:120]}")
    if not doc:
        caveats.append("no parsed metrics")
    elif float(doc.get("vs_baseline") or 0.0) == 0.0 \
            and "vs_baseline" in doc:
        caveats.append("vs_baseline=0 (flagship did not run)")
    return doc, caveats


def classify(key: str) -> str:
    """'higher' | 'lower' | 'neutral' — which direction is better."""
    if _HIGHER.search(key):
        return "higher"
    if _LOWER.search(key):
        return "lower"
    return "neutral"


def diff_rounds(a: Dict, b: Dict, threshold: float) -> List[Dict]:
    """Per-key comparison rows for numeric keys present in both."""
    rows: List[Dict] = []
    for key in sorted(set(a) & set(b)):
        va, vb = a[key], b[key]
        if isinstance(va, bool) or isinstance(vb, bool):
            continue
        if not isinstance(va, (int, float)) \
                or not isinstance(vb, (int, float)):
            continue
        direction = classify(key)
        change = (vb - va) / abs(va) if va else None
        flag = ""
        if change is not None and direction != "neutral":
            worse = -change if direction == "higher" else change
            better = -worse
            if worse > threshold:
                flag = "REGRESSION"
            elif better > threshold:
                flag = "improved"
        rows.append({"key": key, "a": va, "b": vb, "change": change,
                     "direction": direction, "flag": flag})
    return rows


def _fmt_change(c: Optional[float]) -> str:
    return "n/a" if c is None else f"{c * 100:+.1f}%"


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m tools.bench_diff",
        description="Diff two BENCH_*.json rounds, flag regressions")
    p.add_argument("round_a")
    p.add_argument("round_b")
    p.add_argument("--threshold", type=float, default=0.05,
                   help="relative change to flag (default 0.05 = 5%%)")
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="machine-readable output")
    args = p.parse_args(argv)

    a, caveats_a = load_round(args.round_a)
    b, caveats_b = load_round(args.round_b)
    rows = diff_rounds(a, b, args.threshold)
    regressions = [r for r in rows if r["flag"] == "REGRESSION"]
    advisory = bool(caveats_a or caveats_b)

    doc = {
        "round_a": args.round_a, "round_b": args.round_b,
        "threshold": args.threshold,
        "caveats_a": caveats_a, "caveats_b": caveats_b,
        "advisory": advisory,
        "compared": len(rows),
        "regressions": [r["key"] for r in regressions],
        "rows": rows,
    }
    if args.as_json:
        print(json.dumps(doc, indent=1))
    else:
        print(f"bench_diff: {args.round_a} -> {args.round_b} "
              f"(threshold {args.threshold * 100:g}%)")
        for side, caveats in (("A", caveats_a), ("B", caveats_b)):
            for c in caveats:
                print(f"  caveat [{side}]: {c}")
        if not rows:
            print("  no comparable numeric keys")
        w = max((len(r["key"]) for r in rows), default=3)
        for r in rows:
            print(f"  {r['key']:<{w}}  {r['a']:>12}  ->  {r['b']:>12}  "
                  f"{_fmt_change(r['change']):>8}  {r['flag']}")
        if regressions:
            kind = "ADVISORY (broken round)" if advisory else "FAIL"
            print(f"  {len(regressions)} regression(s) past threshold "
                  f"— {kind}")
        else:
            print("  no regressions past threshold")
    return 1 if regressions and not advisory else 0


if __name__ == "__main__":  # pragma: no cover - CLI
    sys.exit(main())

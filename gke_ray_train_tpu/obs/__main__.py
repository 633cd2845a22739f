"""CLI: ``python -m gke_ray_train_tpu.obs <verb>``.

Verbs:

- ``report <run_dir>`` — merge the run's events/spans/metrics/ledger/
  bench records into ``<obs_dir>/report.json``, print ONE JSON summary
  line on stdout (the contract of a script that collects it; ``--text``
  additionally renders the per-attempt timeline + critical-path flame
  summary on stderr).
- ``diff <A> <B>`` — the cross-run regression gate (obs/diff.py):
  compare two reports' goodput terms, goodput_frac, serve p50/p99 and
  critical-path composition under two-sided tolerances; each operand
  is a run dir, a ``report.json``, or a checked-in regression ledger
  (``tests/regressions/*.json``). ``--update`` (or
  ``REGRESSION_UPDATE=1``) re-records B from A instead of comparing.
- ``schema`` — validate the shipped event + metric + trace schema
  files against the code's pinned vocabularies (the CI lint step).

Exit codes (pinned by tests/test_obs.py + tests/test_trace.py):
  0 ok · 1 run dir unreadable / no telemetry / schema drift ·
  2 usage (argparse) · 3 ledger/span reconciliation failure ·
  4 ``diff`` tripped a regression tolerance.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="python -m gke_ray_train_tpu.obs")
    sub = p.add_subparsers(dest="verb", required=True)
    rp = sub.add_parser("report", help="one report per run dir")
    rp.add_argument("run_dir")
    rp.add_argument("--out", default=None,
                    help="report.json path (default: <obs_dir>/report.json)")
    rp.add_argument("--text", action="store_true",
                    help="also render the human timeline (stderr)")
    dp = sub.add_parser("diff", help="cross-run regression gate")
    dp.add_argument("a", help="fresh side: run dir / report.json / "
                              "regression ledger")
    dp.add_argument("b", help="recorded side (same forms; usually "
                              "tests/regressions/<name>.json)")
    dp.add_argument("--update", action="store_true",
                    help="re-record B from A instead of comparing "
                         "(also: REGRESSION_UPDATE=1)")
    sub.add_parser("schema", help="validate shipped schema files")
    args = p.parse_args(argv)

    if args.verb == "schema":
        from gke_ray_train_tpu.obs import events, metrics, trace
        findings = (events.check_schema() + metrics.check_schema()
                    + trace.check_schema())
        for f in findings:
            print(f"SCHEMA: {f}", file=sys.stderr)
        print(json.dumps({"verb": "schema",
                          "findings": len(findings),
                          "ok": not findings}))
        return 1 if findings else 0

    if args.verb == "diff":
        return _diff(args)

    from gke_ray_train_tpu.obs.report import (
        ReportError, render_text, write_report)
    try:
        report = write_report(args.run_dir, args.out)
    except ReportError as e:
        print(f"obs report: {e}", file=sys.stderr)
        return 1
    if args.text:
        print(render_text(report), file=sys.stderr)
    summary = {
        "metric": f"obs report {report['run_id']}",
        "value": report["n_attempts"], "unit": "attempts",
        "reconciled": report["reconciled"],
        "critical_path_ok": report.get("critical_path_ok", True),
        "spans": (report.get("trace") or {}).get("span_count", 0),
        "anomalies": len(report["anomalies"]),
        "captures": len(report["captures"]),
        "reshards": sum(len(a.get("reshard", []))
                        for a in report["attempts"]),
        "events": report["event_count"],
        "goodput_frac": round((report.get("goodput") or {}).get(
            "goodput_frac", 0.0), 4),
        "report": report["report_path"],
    }
    print(json.dumps(summary))
    if not report["reconciled"]:
        print("obs report: ledger terms do NOT reconcile to attempt "
              "wall-clock — telemetry bug", file=sys.stderr)
        return 3
    if not report.get("critical_path_ok", True):
        print("obs report: span-derived critical-path terms do NOT "
              "match the goodput ledger — telemetry bug (see each "
              "attempt's critical_path.reconciliation)", file=sys.stderr)
        return 3
    return 0


def _diff(args) -> int:
    from gke_ray_train_tpu.obs.diff import (
        diff_flat, load_side, write_regression)
    from gke_ray_train_tpu.obs.report import ReportError
    try:
        flat_a, label_a = load_side(args.a)
    except (ReportError, OSError, ValueError) as e:
        print(f"obs diff: cannot read A ({args.a}): {e}",
              file=sys.stderr)
        return 1
    update = args.update or os.environ.get(
        "REGRESSION_UPDATE", "").strip().lower() in ("1", "true", "yes")
    if update:
        try:
            old_tol = None
            if os.path.exists(args.b):
                with open(args.b, encoding="utf-8") as f:
                    old = json.load(f)
                old_tol = old.get("tolerances") \
                    if isinstance(old.get("tolerances"), dict) else None
            doc = write_regression(flat_a, args.b, source=label_a,
                                   tolerances=old_tol)
        except (OSError, ValueError) as e:
            print(f"obs diff: cannot record {args.b}: {e}",
                  file=sys.stderr)
            return 1
        print(json.dumps({"metric": f"obs diff record {args.b}",
                          "value": len([k for k in doc
                                        if not k.startswith("_")]),
                          "unit": "fields", "recorded": args.b}))
        return 0
    try:
        flat_b, label_b = load_side(args.b)
    except (ReportError, OSError, ValueError) as e:
        print(f"obs diff: cannot read B ({args.b}): {e}",
              file=sys.stderr)
        return 1
    viols = diff_flat(flat_a, flat_b)
    for v in viols:
        print(f"DIFF {v}", file=sys.stderr)
    print(json.dumps({
        "metric": f"obs diff {label_a} vs {label_b}",
        "value": len(viols), "unit": "violations",
        "ok": not viols,
        "goodput_frac": [flat_a.get("goodput_frac"),
                         flat_b.get("goodput_frac")],
    }))
    if viols:
        print("obs diff: regression tolerances tripped — if the "
              "change is INTENTIONAL, re-record: REGRESSION_UPDATE=1 "
              "python -m gke_ray_train_tpu.obs diff A B",
              file=sys.stderr)
        return 4
    return 0


if __name__ == "__main__":
    sys.exit(main())

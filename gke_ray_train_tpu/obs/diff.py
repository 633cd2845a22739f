"""``obs diff`` — the cross-run regression gate over telemetry reports
(ISSUE 14 tentpole, part 3).

PR 4/9/12 made compile-time cost numbers pinnable: checked-in JSONs +
a two-sided comparator, re-recorded only on intentional change, CI
enforcing the rest. This module gives RUNTIME telemetry the same
ratchet: a report (``obs/report.py``) flattens to a small dict of
robust scalars — goodput fraction, the ledger terms as fractions of
wall, attempt/preemption/reshard counts, serve p50/p99, and the
critical-path composition — and two such dicts are compared by the
SAME comparator core the budget files use (``perf/compare.py``:
two-sided relative tolerances, per-field overrides recorded in the
checked-in JSON, the offending-term delta printed on a trip).

The checked-in side lives in ``tests/regressions/*.json`` — one file
per recorded drill (``elastic_cpu8.json``, an 8→4→8 shrink and grow, is
the flagship). Re-record after an INTENTIONAL change with
``REGRESSION_UPDATE=1`` (or ``obs diff <run> <ledger> --update``) and
review the JSON diff like code — that diff IS the goodput review.

Why fractions, not seconds: wall-clock varies machine to machine; the
COMPOSITION of an attempt (what share of wall went to restore vs step)
is the stable, reviewable signal — exactly the quantity the goodput
ledger was built to expose. Fields where both sides sit under
:data:`NOISE_FLOOR` are skipped (a 0.4%→0.9% compile share is timing
noise, not a regression; relative tolerances explode near zero).

Stdlib-only, like everything report-side.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional, Tuple

from gke_ray_train_tpu.obs.report import LEDGER_TERMS
from gke_ray_train_tpu.perf.compare import compare_dicts

# two-sided relative tolerances per flattened field. Counts are exact
# (a drill that suddenly takes 4 attempts instead of 3 IS the
# regression); composition fractions get wide bands (CPU-mesh timing
# jitter); latencies are loosest (absolute seconds on shared runners).
# A regression ledger can tighten/loosen any of these via its own
# "tolerances" key — recorded beside the numbers, reviewed like code.
DIFF_TOLERANCES: Dict[str, float] = {
    "goodput_frac": 0.35,
    **{f"frac_{t}": 0.60 for t in LEDGER_TERMS},
    "n_attempts": 0.0,
    "preemptions": 0.0,
    "reshards": 0.0,
    # "anomalies" is flattened for the record but NOT gated by default:
    # spike/stall detection is machine-speed dependent — a ledger that
    # wants to pin it adds its own tolerance entry
    "serve_p50_token_latency_s": 2.0,
    "serve_p99_token_latency_s": 2.0,
    **{f"cp_frac_{t}": 0.60 for t in LEDGER_TERMS},
    # autotune calibration drift (autotune/registry.py ingest): the
    # drift-event count is exact — a calibrated cost model tripping
    # the band where the recorded run had zero drift IS the
    # regression; the worst relative error gets a wide band (it only
    # exists when drift fired, and its magnitude is machine-sensitive)
    "autotune_drift_events": 0.0,
    "autotune_drift_stale": 0.0,
    "autotune_drift_max_rel_err": 1.0,
}
# composition fields where both sides below this share are noise
NOISE_FLOOR = 0.02


def flatten_report(report: Dict[str, Any]) -> Dict[str, float]:
    """The comparable scalar surface of one report — every field here
    must be meaningful to compare across machines/runs of the same
    drill (compositions and counts, not absolute seconds)."""
    flat: Dict[str, float] = {}
    g = report.get("goodput") or {}
    wall = float(g.get("wall_s") or 0.0)
    if wall > 0:
        flat["goodput_frac"] = float(
            g.get("goodput_frac", g.get("step_s", 0.0) / wall))
        for t in LEDGER_TERMS:
            flat[f"frac_{t}"] = float(g.get(t, 0.0)) / wall
    flat["n_attempts"] = float(report.get("n_attempts", 0))
    if report.get("preemptions") is not None:
        flat["preemptions"] = float(report["preemptions"])
    flat["reshards"] = float(sum(
        len(a.get("reshard", [])) for a in report.get("attempts", [])))
    flat["anomalies"] = float(len(report.get("anomalies", [])))
    # serving latency: the max across rank exports (a replica's p99 is
    # the fleet's p99)
    for key in ("serve_p50_token_latency_s", "serve_p99_token_latency_s"):
        vals = [doc.get(key) for doc in
                (report.get("metrics") or {}).values()
                if isinstance(doc.get(key), (int, float))]
        if vals:
            flat[key] = float(max(vals))
    # critical-path composition (obs/critical.py): the SPAN-attributed
    # share of total wall per term, summed across attempts — where the
    # attempt spent its gating rank's time, not just that it spent it
    cp_sum: Dict[str, float] = {}
    cp_wall = 0.0
    for a in report.get("attempts", []):
        cp = a.get("critical_path")
        if not cp or not cp.get("wall_s"):
            continue
        cp_wall += float(cp["wall_s"])
        for t, v in (cp.get("span_terms") or {}).items():
            cp_sum[t] = cp_sum.get(t, 0.0) + float(v)
    if cp_wall > 0:
        for t in LEDGER_TERMS:
            if t in cp_sum:
                flat[f"cp_frac_{t}"] = cp_sum[t] / cp_wall
    # autotune feedback scalars (report "autotune" section): the drift
    # counts pin the calibration loop — a model that starts
    # mispredicting real runs shows up as a count where the recorded
    # drill had none
    at = report.get("autotune") or {}
    if at:
        flat["autotune_drift_events"] = float(at.get("drift_events", 0))
        flat["autotune_drift_stale"] = float(at.get("drift_stale", 0))
        if at.get("drift_max_rel_err") is not None:
            flat["autotune_drift_max_rel_err"] = \
                float(at["drift_max_rel_err"])
    return {k: round(v, 6) for k, v in flat.items()}


def _drop_noise(a: Dict[str, float], b: Dict[str, float]
                ) -> Tuple[Dict[str, float], Dict[str, float]]:
    """Remove composition fields where BOTH sides sit under the noise
    floor — relative tolerances are meaningless at ~0, and a 0.004 vs
    0.011 compile share is scheduler jitter, not a regression."""
    def keep(k: str) -> bool:
        if not (k.startswith("frac_") or k.startswith("cp_frac_")):
            return True
        return abs(a.get(k, 0.0)) >= NOISE_FLOOR \
            or abs(b.get(k, 0.0)) >= NOISE_FLOOR
    kept = [k for k in set(a) | set(b) if keep(k)]
    return ({k: v for k, v in a.items() if k in kept},
            {k: v for k, v in b.items() if k in kept})


def diff_flat(flat_a: Dict[str, Any], flat_b: Dict[str, Any],
              tolerances: Optional[Dict[str, float]] = None
              ) -> List[str]:
    """Violation strings comparing A (the fresh run) against B (the
    recorded side) — the ``perf/budget.py`` comparator shape, reused
    not forked. Empty = within tolerances."""
    a, b = _drop_noise(
        {k: v for k, v in flat_a.items()
         if isinstance(v, (int, float)) and not k.startswith("_")},
        {k: v for k, v in flat_b.items()
         if isinstance(v, (int, float)) and not k.startswith("_")})
    # the recorded side may carry its own per-field overrides, exactly
    # like a budget JSON's "tolerances" key
    budget = dict(b)
    if isinstance(flat_b.get("tolerances"), dict):
        budget["tolerances"] = flat_b["tolerances"]
    viols = compare_dicts(a, budget, tolerances,
                          default_tolerances=DIFF_TOLERANCES)
    # the comparator skips fields absent from either side — safe for
    # budget files (their field set is structural), WRONG for
    # telemetry, where fields are emergent from the run: a recorded
    # cp_frac_* vanishing from the fresh report usually means tracing
    # silently broke (TRACE off, a span-stream bug) — exactly the
    # regression class this gate exists to catch. Noise-floored
    # fields were already dropped from BOTH dicts above, so anything
    # still recorded-but-missing is a real signal.
    gated = dict(DIFF_TOLERANCES)
    gated.update(budget.get("tolerances", {}))
    gated.update(tolerances or {})
    for k in sorted(set(b) - set(a)):
        if k in gated and not k.startswith("_") and k != "tolerances":
            viols.append(
                f"{k}: recorded {b[k]:.4g} but MISSING from the fresh "
                "report — the telemetry that produced it broke or was "
                "turned off")
    return viols


def load_side(path: str) -> Tuple[Dict[str, Any], str]:
    """Resolve one CLI operand into a flat dict: a regression-ledger
    JSON (already flat), a ``report.json``, an obs dir, or a run dir
    (report built on the fly). Returns (flat, label)."""
    from gke_ray_train_tpu.obs.report import build_report
    if os.path.isdir(path):
        return flatten_report(build_report(path)), f"report({path})"
    with open(path, encoding="utf-8") as f:
        doc = json.load(f)
    if "attempts" in doc:          # a written report.json
        return flatten_report(doc), f"report({path})"
    return doc, path               # an already-flat regression ledger


def write_regression(flat: Dict[str, Any], path: str, *,
                     source: str = "",
                     tolerances: Optional[Dict[str, float]] = None
                     ) -> Dict[str, Any]:
    """Record one flattened report as a checked-in regression ledger
    (the ``write_budget`` shape: provenance + re-record note + the
    numbers, reviewed like code)."""
    doc: Dict[str, Any] = {
        "_source": source,
        "_note": ("re-record after an INTENTIONAL change: "
                  "REGRESSION_UPDATE=1 (or `obs diff <run> <ledger> "
                  "--update`) and review this diff like code"),
        **({"tolerances": dict(tolerances)} if tolerances else {}),
        # "tolerances" excluded from the spread: when the A side is
        # itself a flat ledger its own overrides ride in ``flat`` and
        # would silently clobber the reviewed B-side ones the caller
        # explicitly passed to preserve
        **{k: flat[k] for k in sorted(flat)
           if not k.startswith("_") and k != "tolerances"},
    }
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    return doc

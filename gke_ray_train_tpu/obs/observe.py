"""Deterministic ``ObservedRun`` extraction — the obs→autotune bridge
(ISSUE 16 tentpole, part 1).

PR 14's autotune ranks plans by a *static* cost model; the obs stream
(PR 11/13) already records what those plans actually did. This module
closes the gap: :func:`observed_runs` flattens one run dir's artifacts
— the span-derived step windows, the goodput ledger, the serve drain
stats, and any bench records — into small, deterministic rows keyed by
``(plan_fingerprint, surface, topology, chip family, backend)`` that
``autotune ingest`` (autotune/registry.py) can match against registry
entries and ``autotune calibrate`` (autotune/calibrate.py) can fit
correction factors over.

Measurement discipline:

- the measured TRAIN step time is a robust weighted MEDIAN over the
  ``step_window`` spans' per-step compute time ``(dur_s −
  data_stall_s) / steps`` (each window weighted by its step count) —
  one slow window (a GC pause, a noisy neighbour) must not drag the
  number the calibration fits against;
- the measured SERVE number is the drained engine's per-token p50
  (p99 rides along as provenance) — the same quantity the scorer's
  ``modeled_per_token_s`` predicts;
- ``backend`` comes from the run's own record (the ``first_step``
  event / the bench record's ``backend`` tag), NEVER inferred — a
  CPU measurement must be refusable at ingest so it can never
  calibrate a TPU ChipSpec;
- every float is rounded once, here, so re-extracting the same
  artifacts is bitwise-identical (the ingest idempotency contract).

Stdlib-only, like everything report-side (the extraction must run on a
laptop pointed at a GCS-FUSE mount, with no jax).
"""

from __future__ import annotations

import json
import logging
import os
from typing import Any, Dict, List, Optional, Tuple

from gke_ray_train_tpu.obs.events import iter_events
from gke_ray_train_tpu.obs.trace import iter_spans

logger = logging.getLogger(__name__)

# float precision of every measured value (µs on seconds-scale
# numbers): rounding happens ONCE, at extraction, so re-ingesting the
# same artifacts appends nothing and rewrites nothing
ROUND_DIGITS = 6


def _round(v: Optional[float]) -> Optional[float]:
    return None if v is None else round(float(v), ROUND_DIGITS)


def weighted_median(pairs: List[Tuple[float, float]]) -> Optional[float]:
    """Median of ``(value, weight)`` pairs: the smallest value at which
    the cumulative weight reaches half the total. Deterministic (sorted
    by value, ties kept in sort order); None on empty/zero weight."""
    pairs = [(float(v), float(w)) for v, w in pairs if w > 0]
    if not pairs:
        return None
    pairs.sort(key=lambda p: (p[0], p[1]))
    total = sum(w for _, w in pairs)
    acc = 0.0
    for v, w in pairs:
        acc += w
        if acc >= total / 2:
            return v
    return pairs[-1][0]          # pragma: no cover - float-sum guard


def chip_family(topology: Optional[str]) -> Optional[str]:
    """The ChipSpec family the topology scores against — the same
    ``split("-", 1)[0]`` rule as ``autotune.score.chip_for_plan`` (kept
    string-level here: this module must import without jax)."""
    if not topology:
        return None
    return str(topology).split("-", 1)[0]


def _bench_rows(obs_dir: str) -> List[Dict[str, Any]]:
    """Observed rows from ``bench_records.jsonl``: the autotune A/B
    record measures BOTH arms (``measured_step_s_default`` /
    ``_tuned`` against their plan fingerprints); any other record with
    a plan fingerprint + a measured step time contributes one row."""
    out: List[Dict[str, Any]] = []
    path = os.path.join(obs_dir, "bench_records.jsonl")
    if not os.path.exists(path):
        return out
    with open(path, encoding="utf-8") as f:
        for i, line in enumerate(f):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                logger.warning("%s:%d: skipping corrupt bench record",
                               path, i + 1)
                continue
            backend = rec.get("backend")
            topology = rec.get("topology")
            steps = rec.get("steps")
            for arm in ("default", "tuned"):
                fp = rec.get(f"plan_fingerprint_{arm}")
                step_s = rec.get(f"measured_step_s_{arm}")
                if not fp or not isinstance(step_s, (int, float)):
                    continue
                out.append({
                    "source": "bench",
                    "run_id": rec.get("run_id"),
                    "attempt": 0,
                    "arm_hint": "base" if arm == "default" else "tuned",
                    "plan_fingerprint": fp,
                    "surface": "train",
                    "topology": topology,
                    "chip_family": chip_family(topology),
                    "backend": backend,
                    "steps": int(steps) if steps else None,
                    "measured_step_s": _round(step_s),
                })
    return out


def observed_runs(obs_dir: str) -> List[Dict[str, Any]]:
    """Every deterministic observed row a run dir supports (possibly
    several runs/attempts — event files append). Rows missing the
    identity the registry keys on (a plan fingerprint and a measured
    value) are dropped, not guessed at; ``backend`` may be None here —
    ingest REFUSES such rows rather than this module inventing one."""
    events = list(iter_events(obs_dir))
    spans = list(iter_spans(obs_dir, names=("step_window",)))

    # -- per-(run_id, attempt) event context ---------------------------
    keys: List[Tuple[Optional[str], int]] = []
    ctx: Dict[Tuple[Optional[str], int], Dict[str, Any]] = {}

    def _ctx(rec) -> Dict[str, Any]:
        key = (rec.get("run_id"), int(rec.get("attempt") or 0))
        if key not in ctx:
            keys.append(key)
            ctx[key] = {"run_id": key[0], "attempt": key[1]}
        return ctx[key]

    for e in events:
        c = _ctx(e)
        if c.get("plan_fingerprint") is None \
                and e.get("plan_fingerprint"):
            c["plan_fingerprint"] = e["plan_fingerprint"]
        kind = e.get("kind")
        if kind == "attempt_start" and e.get("topology"):
            c.setdefault("topology", e["topology"])
        elif kind == "first_step" and e.get("backend"):
            c.setdefault("backend", e["backend"])
        elif kind == "attempt_end" and isinstance(e.get("goodput"), dict):
            c["goodput"] = e["goodput"]      # driver side: authoritative
        elif kind == "worker_exit" and isinstance(e.get("goodput"), dict):
            c.setdefault("goodput", e["goodput"])
        elif kind == "serve_drained" and isinstance(e.get("stats"), dict):
            c.setdefault("serve", e["stats"])

    # -- span-derived step windows, weighted by step count -------------
    windows: Dict[Tuple[Optional[str], int], List[Tuple[float, float]]] = {}
    steps_total: Dict[Tuple[Optional[str], int], int] = {}
    for s in spans:
        key = (s.get("run_id"), int(s.get("attempt") or 0))
        n = int(s.get("steps") or 0)
        if n <= 0:
            continue
        per_step = (float(s.get("dur_s") or 0.0)
                    - float(s.get("data_stall_s") or 0.0)) / n
        windows.setdefault(key, []).append((per_step, float(n)))
        steps_total[key] = steps_total.get(key, 0) + n

    rows: List[Dict[str, Any]] = []
    for key in keys:
        c = ctx[key]
        fp = c.get("plan_fingerprint")
        if not fp:
            continue
        g = c.get("goodput") or {}
        wall = float(g.get("wall_s") or 0.0)
        common = {
            "source": "obs",
            "run_id": c["run_id"],
            "attempt": c["attempt"],
            "plan_fingerprint": fp,
            "topology": c.get("topology"),
            "chip_family": chip_family(c.get("topology")),
            "backend": c.get("backend"),
            "goodput_frac": _round(
                float(g.get("step_s", 0.0)) / wall if wall > 0 else None),
            "data_stall_frac": _round(
                float(g.get("data_stall_s", 0.0)) / wall
                if wall > 0 else None),
        }
        med = weighted_median(windows.get(key, []))
        if med is not None:
            rows.append({**common, "surface": "train",
                         "steps": steps_total.get(key, 0),
                         "measured_step_s": _round(med)})
        sv = c.get("serve") or {}
        p50 = sv.get("p50_token_latency_s")
        if isinstance(p50, (int, float)) and p50 > 0:
            rows.append({
                **common, "surface": "serve",
                "steps": int(sv.get("iterations") or 0),
                "measured_per_token_s": _round(p50),
                "serve_p50_token_latency_s": _round(p50),
                "serve_p99_token_latency_s": _round(
                    sv.get("p99_token_latency_s")
                    if isinstance(sv.get("p99_token_latency_s"),
                                  (int, float)) else None),
            })

    rows.extend(_bench_rows(obs_dir))
    rows.sort(key=lambda r: (r["source"], str(r.get("run_id")),
                             r.get("attempt") or 0, r["surface"],
                             r["plan_fingerprint"]))
    return rows


def row_measure(row: Dict[str, Any]) -> Optional[float]:
    """The one measured number a row contributes to calibration/drift:
    step seconds on the train surface, per-token seconds on serve —
    mirroring ``autotune.score.rank_metric``."""
    if row.get("surface") == "serve":
        return row.get("measured_per_token_s")
    return row.get("measured_step_s")

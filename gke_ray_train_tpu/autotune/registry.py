"""Tuned-plan registry: persist winners, overlay them at run time.

A search result is persisted as ONE JSON entry keyed by
``(model-config digest, topology, surface)`` — the identity triple
under which the score is meaningful — with the full scored-candidate
table beside it (``<key>.candidates.json``) so the verdict stays
auditable. ``AUTOTUNE=1`` (plan field) lets ``_run_worker`` and both
ray-jobs entries overlay a registry hit onto the resolved plan:

- the overlay writes ONLY the surface's tunable fields
  (:data:`~gke_ray_train_tpu.autotune.space.TUNABLE_FIELDS`) — it can
  never touch operational identity (obs dirs, cache policy, guards);
- application is LOUD (a warning-level line naming both fingerprints)
  and REFUSED — run continues untuned, also loudly — when the tuned
  plan no longer validates (plancheck/kernelcheck findings against the
  current model) or the entry's fingerprint inputs drifted (model
  digest, scorer version, chip spec);
- an elastic reshard drops the overlay (``plan.replan``) and the next
  attempt's ``maybe_apply`` re-keys against the survivors' topology —
  a plan tuned for 8 devices can never silently ride a 4-device
  attempt.

Since ISSUE 16 the registry also LEARNS: entries carry *observed*
columns beside the modeled ones. :func:`ingest_observed` matches a run
dir's :func:`gke_ray_train_tpu.obs.observe.observed_runs` rows against
entries by plan fingerprint (base arm / tuned arm), refusing rows the
same way ``apply`` refuses entries — fingerprint drift, version drift,
and the backend gate (a CPU measurement can NEVER calibrate a
non-CPU ChipSpec). ``autotune/calibrate.py`` fits
per-chip-spec correction factors over those rows, and when a
calibration exists ingest grows teeth: an arm whose corrected
prediction misses the measured value by more than
``AUTOTUNE_DRIFT_BAND`` marks the entry STALE, fires a schema'd
``autotune_drift`` event into the run dir, and ``validate_entry``
refuses the overlay until a re-tune (or healthier measurements on a
re-ingest) clears it — the self-correcting part of the loop.

The registry directory defaults to ``<repo>/tuned_plans`` and is
overridable via ``AUTOTUNE_DIR`` (config key wins over env, like every
knob).
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import logging
import os
import statistics
from typing import Any, Dict, List, Mapping, Optional, Tuple

from gke_ray_train_tpu.autotune.space import TUNABLE_FIELDS
from gke_ray_train_tpu.autotune.score import SCORER_VERSION, chip_for_plan
from gke_ray_train_tpu.autotune import calibrate as _calibrate

logger = logging.getLogger(__name__)

REGISTRY_VERSION = 1

# |corrected_modeled − measured| / measured beyond this fraction marks
# an entry stale (config key wins over env, like every knob)
DRIFT_BAND_DEFAULT = 0.25

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_REPO_ROOT, "tuned_plans")


def registry_dir(config: Optional[Mapping[str, Any]] = None) -> str:
    if config is not None and dict(config).get("AUTOTUNE_DIR"):
        return str(dict(config)["AUTOTUNE_DIR"])
    return os.environ.get("AUTOTUNE_DIR") or DEFAULT_DIR


def model_digest(model_cfg) -> str:
    """Stable 16-hex identity of the model the plan was tuned FOR — the
    first key component. A tuned mesh/batch split is meaningless on a
    different architecture; digest drift refuses the overlay."""
    payload = json.dumps(model_cfg.to_dict(), sort_keys=True, default=str)
    return hashlib.sha256(payload.encode()).hexdigest()[:16]


def chip_digest(chip) -> str:
    payload = json.dumps(dataclasses.asdict(chip), sort_keys=True)
    return hashlib.sha256(payload.encode()).hexdigest()[:12]


def entry_key(digest: str, topology: str, surface: str) -> str:
    return f"{surface}-{topology}-{digest}"


def entry_path(key: str, directory: Optional[str] = None) -> str:
    return os.path.join(directory or registry_dir(), f"{key}.json")


def save_entry(result: Dict[str, Any], *, base_plan, model_cfg,
               directory: Optional[str] = None) -> str:
    """Persist a search result as a registry entry + its candidate
    table; returns the entry path."""
    import jax

    directory = directory or registry_dir()
    digest = model_digest(model_cfg)
    surface = result["surface"]
    key = entry_key(digest, base_plan.topology, surface)
    chip = chip_for_plan(base_plan)
    doc = {
        "_version": REGISTRY_VERSION,
        "key": key,
        "surface": surface,
        "topology": base_plan.topology,
        "model_digest": digest,
        "model": model_cfg.to_dict(),
        "fingerprint_inputs": {
            "model_digest": digest,
            "scorer_version": result.get("scorer_version",
                                         SCORER_VERSION),
            "chip": chip.name,
            "chip_digest": chip_digest(chip),
            "calibration_version": _calibrate.CALIBRATION_VERSION,
        },
        "base_fingerprint": result["base"]["plan_fingerprint"],
        "winner_fingerprint": result["winner"]["plan_fingerprint"],
        "tuned": {f: result["winner_tuned_fields"][f]
                  for f in TUNABLE_FIELDS[surface]},
        "env": result.get("winner_env") or {},
        "score": result["winner"]["score"],
        "base_score": result["base"]["score"],
        "improvement": result["improvement"],
        "space": result["space"],
        "candidates_file": f"{key}.candidates.json",
        "_recorded_with": {"jax": jax.__version__},
    }
    # a re-record keeps the prior entry's observed rows that still
    # describe one of the NEW arms (same plan fingerprint), re-stamped
    # against the new scores; stale/drift verdicts do NOT carry — the
    # model just changed, the next ingest re-judges
    doc["observed"] = _carry_observed(load_entry(key, directory), doc)
    os.makedirs(directory, exist_ok=True)
    path = entry_path(key, directory)
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    with open(os.path.join(directory, doc["candidates_file"]), "w") as f:
        json.dump({"key": key, "candidates": result["candidates"],
                   "pruned": result["pruned"]}, f, indent=1,
                  sort_keys=True)
        f.write("\n")
    logger.info("autotune: recorded tuned plan %s -> %s (%.3fx)",
                key, path, result["improvement"])
    return path


def load_entry(key: str, directory: Optional[str] = None
               ) -> Optional[Dict[str, Any]]:
    path = entry_path(key, directory)
    if not os.path.exists(path):
        return None
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        logger.warning("autotune: registry entry %s unreadable (%s)",
                       path, e)
        return None


def validate_entry(entry: Dict[str, Any], plan, model_cfg
                   ) -> List[str]:
    """Why this entry must NOT overlay this run (empty = applicable):
    fingerprint-input drift, a tuned plan that no longer validates, or
    static findings against the current model."""
    out: List[str] = []
    if entry.get("_version") != REGISTRY_VERSION:
        out.append(f"registry version {entry.get('_version')} != "
                   f"{REGISTRY_VERSION}")
    fi = entry.get("fingerprint_inputs") or {}
    if model_cfg is not None:
        digest = model_digest(model_cfg)
        if fi.get("model_digest") != digest:
            out.append(f"model digest drifted: tuned for "
                       f"{fi.get('model_digest')}, run resolves "
                       f"{digest}")
    if fi.get("scorer_version") != SCORER_VERSION:
        out.append(f"scorer version drifted: entry "
                   f"{fi.get('scorer_version')} vs current "
                   f"{SCORER_VERSION} — re-tune")
    if fi.get("calibration_version") != _calibrate.CALIBRATION_VERSION:
        out.append(f"calibration version drifted: entry "
                   f"{fi.get('calibration_version')} vs current "
                   f"{_calibrate.CALIBRATION_VERSION} — re-tune")
    if entry.get("stale"):
        d = entry.get("drift") or {}
        out.append(
            "entry is STALE — observed drift: corrected model "
            f"{d.get('corrected_modeled_step_s')}s vs measured "
            f"{d.get('measured_step_s')}s (rel_err "
            f"{d.get('rel_err')} > band {d.get('band')}); re-tune or "
            "re-ingest healthier measurements")
    chip = chip_for_plan(plan)
    if fi.get("chip_digest") != chip_digest(chip):
        out.append(f"chip spec drifted for family {chip.name!r} — the "
                   "scores no longer describe this hardware; re-tune")
    if entry.get("topology") != plan.topology:
        out.append(f"topology mismatch: tuned for "
                   f"{entry.get('topology')}, plan declares "
                   f"{plan.topology}")
    if out:
        return out
    # the tuned plan itself must still validate end to end — through
    # the SAME surface-aware gauntlet the enumerator pruned with
    # (space.static_findings skips the mesh arithmetic on the serve
    # surface: a serving replica's decode is mesh-local by design)
    from gke_ray_train_tpu.autotune.space import static_findings
    from gke_ray_train_tpu.plan import PlanError
    try:
        tuned = _overlay(plan, entry)
    except PlanError as e:
        return [f"tuned plan no longer validates: {e}"]
    if entry.get("surface", "train") == "train":
        # the search preserves ITS base's global batch by construction
        # (space.py); the overlay must preserve THIS run's too. With
        # data x fsdp fixed by the chip count, that reduces to the
        # (per_device_batch x grad_accum) product — an entry searched
        # against a different configured batch must not silently move
        # the run's optimization trajectory.
        t = entry.get("tuned") or {}
        entry_rows = (int(t.get("per_device_batch",
                                plan.per_device_batch))
                      * int(t.get("grad_accum", plan.grad_accum)))
        run_rows = plan.per_device_batch * plan.grad_accum
        if entry_rows != run_rows:
            out.append(
                f"tuned batch split (per_device_batch x grad_accum = "
                f"{entry_rows}) does not preserve this run's "
                f"configured product ({run_rows}) — the entry was "
                "searched against a different base batch; re-tune")
    stray_env = sorted(set(entry.get("env") or {})
                       - set(_env_override_keys()))
    if stray_env:
        out.append(
            f"entry carries undeclared env overrides {stray_env} "
            f"(allowed: {list(_env_override_keys())}) — refusing "
            "to export them into the worker")
    if out:
        return out
    return static_findings(tuned, model_cfg,
                           surface=entry.get("surface", "train"))


def _env_override_keys() -> Tuple[str, ...]:
    from gke_ray_train_tpu.autotune.space import ENV_OVERRIDE_KEYS
    return ENV_OVERRIDE_KEYS


def _overlay(plan, entry: Dict[str, Any]):
    surface = entry.get("surface", "train")
    fields = {f: v for f, v in (entry.get("tuned") or {}).items()
              if f in TUNABLE_FIELDS.get(surface, ())}
    return dataclasses.replace(plan, **fields)


def apply_entry(plan, entry: Dict[str, Any]):
    """The validated overlay: tunable fields written onto the runtime
    plan, the pre-overlay plan stashed so ``plan.replan`` can drop the
    tune on a reshard (the re-key contract)."""
    tuned = _overlay(plan, entry)
    object.__setattr__(tuned, "_tuned_base", plan)
    object.__setattr__(tuned, "_tuned_key", entry.get("key"))
    return tuned


def maybe_apply(plan, *, config: Optional[Mapping[str, Any]] = None,
                model_cfg=None, surface: str = "train",
                log: Optional[logging.Logger] = None
                ) -> Tuple[Any, bool]:
    """(plan, applied) — the runtime hook ``_run_worker`` and both
    entry points call after plan resolution (and after any elastic
    replan, so the lookup keys on the topology the attempt actually
    runs). No-op unless the plan opted in via ``AUTOTUNE=1``."""
    log = log or logger
    if not getattr(plan, "autotune", False):
        return plan, False
    if model_cfg is None:
        try:
            from gke_ray_train_tpu.analysis.plancheck import (
                model_config_for)
            model_cfg = model_config_for(dict(config or {}), plan)
        except Exception as e:  # noqa: BLE001 - static derivation only
            log.warning("autotune: model config underivable (%s); "
                        "running untuned", e)
            return plan, False
    if model_cfg is None:
        log.warning(
            "autotune: AUTOTUNE=1 but no statically-derivable model "
            "config (no MODEL_ID/SMOKE_TEST) — registry keys on the "
            "model digest; running untuned")
        return plan, False
    directory = registry_dir(config)
    key = entry_key(model_digest(model_cfg), plan.topology, surface)
    entry = load_entry(key, directory)
    if entry is None:
        log.warning("autotune: no tuned plan for %s under %s; running "
                    "untuned (record one: python -m "
                    "gke_ray_train_tpu.autotune search)", key, directory)
        return plan, False
    findings = validate_entry(entry, plan, model_cfg)
    if findings:
        log.warning(
            "autotune: REFUSING tuned plan %s — %s; running untuned "
            "(re-tune or remove the stale entry)", key,
            "; ".join(findings[:3]))
        return plan, False
    tuned = apply_entry(plan, entry)
    # export the entry's env-dialect knobs (validated above against
    # ENV_OVERRIDE_KEYS). Attempt-scoped: _run_worker restores these
    # keys in its finally, so a dropped overlay's flash blocks never
    # leak into a later in-process attempt that runs untuned.
    for k, v in (entry.get("env") or {}).items():
        os.environ[k] = str(v)
    log.warning(
        "autotune: OVERLAY applied from %s — plan %s -> %s (tuned %s, "
        "modeled %.3es vs default %.3es, %.3fx)", key,
        plan.fingerprint(), tuned.fingerprint(),
        {f: v for f, v in (entry.get("tuned") or {}).items()
         if getattr(plan, f, None) != v} or "no field changes",
        entry.get("score", {}).get("modeled_step_s", float("nan")),
        entry.get("base_score", {}).get("modeled_step_s", float("nan")),
        entry.get("improvement", float("nan")))
    return tuned, True


# ---------------------------------------------------------------------------
# observed columns: ingest + drift teeth (ISSUE 16 tentpole, part 2)
# ---------------------------------------------------------------------------

# the observed-row identity inside an entry — re-ingesting the same run
# dir appends nothing (the bitwise-idempotency contract)
_ROW_KEY = ("run_id", "attempt", "arm", "plan_fingerprint", "source")

def drift_band(config: Optional[Mapping[str, Any]] = None) -> float:
    """``AUTOTUNE_DRIFT_BAND`` (config key wins over env, like every
    knob); unparsable values fall back to the default rather than
    silently disabling the teeth."""
    cfg = dict(config or {})
    v = cfg.get("AUTOTUNE_DRIFT_BAND",
                os.environ.get("AUTOTUNE_DRIFT_BAND"))
    if v in (None, ""):
        return DRIFT_BAND_DEFAULT
    try:
        band = float(v)
    except (TypeError, ValueError):
        logger.warning("autotune: AUTOTUNE_DRIFT_BAND=%r unparsable; "
                       "using %.2f", v, DRIFT_BAND_DEFAULT)
        return DRIFT_BAND_DEFAULT
    return band if band > 0 else DRIFT_BAND_DEFAULT


def list_entries(directory: Optional[str] = None
                 ) -> List[Tuple[str, Dict[str, Any]]]:
    """Every registry entry under ``directory`` as sorted
    ``(path, entry)`` pairs (candidate tables and the calibration file
    are not entries)."""
    directory = directory or registry_dir()
    out: List[Tuple[str, Dict[str, Any]]] = []
    try:
        names = sorted(os.listdir(directory))
    except OSError:
        return out
    for name in names:
        if (not name.endswith(".json")
                or name.endswith(".candidates.json")
                or name == _calibrate.CAL_FILENAME):
            continue
        path = os.path.join(directory, name)
        try:
            with open(path, encoding="utf-8") as f:
                entry = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            logger.warning("autotune: skipping unreadable entry %s (%s)",
                           path, e)
            continue
        if isinstance(entry, dict) and entry.get("key"):
            out.append((path, entry))
    return out


def _row_id(row: Mapping[str, Any]) -> Tuple:
    return tuple(row.get(k) for k in _ROW_KEY)


def _arm_score(entry: Dict[str, Any], arm: str
               ) -> Optional[Dict[str, Any]]:
    return entry.get("base_score") if arm == "base" else entry.get("score")


def _stored_row(row: Mapping[str, Any], arm: str,
                entry: Dict[str, Any]) -> Dict[str, Any]:
    """The column an observed row becomes inside the entry: measurement
    + identity + the RAW model prediction it is evidence against (the
    pair calibrate.py fits over)."""
    from gke_ray_train_tpu.obs.observe import row_measure
    surface = entry.get("surface", "train")
    score = _arm_score(entry, arm) or {}
    stored = {
        "run_id": row.get("run_id"),
        "attempt": row.get("attempt"),
        "arm": arm,
        "source": row.get("source"),
        "plan_fingerprint": row.get("plan_fingerprint"),
        "surface": surface,
        "topology": row.get("topology"),
        "backend": row.get("backend"),
        "measured": row_measure(dict(row)),
        "steps": row.get("steps"),
        "raw_modeled": _calibrate.raw_prediction(score, surface),
        "binding": _calibrate.raw_binding(score),
    }
    for k in ("goodput_frac", "data_stall_frac",
              "serve_p50_token_latency_s", "serve_p99_token_latency_s"):
        if row.get(k) is not None:
            stored[k] = row[k]
    return stored


def _entry_refusal(entry: Dict[str, Any]) -> Optional[str]:
    """Version gates an entry must pass before ANY row lands in it —
    the ingest half of ``validate_entry``'s drift discipline."""
    if entry.get("_version") != REGISTRY_VERSION:
        return (f"registry version {entry.get('_version')} != "
                f"{REGISTRY_VERSION}")
    fi = entry.get("fingerprint_inputs") or {}
    if fi.get("scorer_version") != SCORER_VERSION:
        return (f"scorer version drifted ({fi.get('scorer_version')} vs "
                f"{SCORER_VERSION}) — observed rows would describe a "
                "different model; re-tune first")
    if fi.get("calibration_version") != _calibrate.CALIBRATION_VERSION:
        return (f"calibration version drifted "
                f"({fi.get('calibration_version')} vs "
                f"{_calibrate.CALIBRATION_VERSION}) — re-tune first")
    return None


def _row_refusal(row: Mapping[str, Any],
                 entry: Dict[str, Any]) -> Optional[str]:
    """Why a fingerprint-matched row must NOT become an observed column
    of this entry (None = ingest it). The backend gate is the critical
    one: measurements are only evidence against the ChipSpec they ran
    on — a CPU step time must never calibrate a TPU."""
    from gke_ray_train_tpu.perf.costs import CHIP_SPECS
    fi = entry.get("fingerprint_inputs") or {}
    chip = fi.get("chip")
    if row.get("surface", "train") != entry.get("surface", "train"):
        return (f"surface mismatch: row {row.get('surface')!r} vs entry "
                f"{entry.get('surface')!r}")
    if row.get("topology") and entry.get("topology") \
            and row["topology"] != entry["topology"]:
        return (f"topology drift: row measured {row['topology']!r}, "
                f"entry tuned {entry.get('topology')!r}")
    fam = row.get("chip_family")
    if fam is not None and chip:
        expected = fam if fam in CHIP_SPECS else "cpu"
        if expected != chip:
            return (f"chip family drift: row is {expected!r} evidence, "
                    f"entry scores against {chip!r}")
    backend = row.get("backend")
    if not backend:
        return ("row carries no backend stamp — refusing an "
                "unattributable measurement")
    if backend == "cpu" and chip != "cpu":
        return (f"backend {backend!r} measurement can NEVER calibrate "
                f"ChipSpec {chip!r} — CPU numbers describe the "
                "host, not the declared hardware")
    if backend != "cpu" and chip == "cpu":
        return (f"backend {backend!r} measurement does not describe the "
                "CPU ChipSpec this entry scores against")
    return None


def evaluate_drift(entry: Dict[str, Any],
                   cal: Optional[Dict[str, Any]],
                   band: float) -> Optional[Dict[str, Any]]:
    """The worst-arm drift verdict for one entry, or None when it
    cannot be judged (no calibration for this chip yet — calibrate
    first, THEN watch — or no observed rows). ``stale`` inside the
    returned dict is the verdict; the caller writes it onto the entry,
    so a healthier re-ingest can also clear it."""
    fi = entry.get("fingerprint_inputs") or {}
    digest = fi.get("chip_digest")
    if not digest or not _calibrate.factors_for(cal, digest):
        return None
    surface = entry.get("surface", "train")
    worst: Optional[Dict[str, Any]] = None
    for arm in ("base", "tuned"):
        score = _arm_score(entry, arm)
        if not score:
            continue
        vals = sorted(
            float(r["measured"]) for r in entry.get("observed") or []
            if r.get("arm") == arm
            and isinstance(r.get("measured"), (int, float))
            and r["measured"] > 0)
        if not vals:
            continue
        measured = statistics.median(vals)
        corrected = _calibrate.corrected_prediction(
            score, cal, chip_digest=digest, surface=surface)
        if corrected is None or measured <= 0:
            continue
        rel = abs(corrected - measured) / measured
        d = {
            "arm": arm,
            "measured_step_s": round(measured, 9),
            "raw_modeled_step_s": _calibrate.raw_prediction(score,
                                                            surface),
            "corrected_modeled_step_s": round(corrected, 9),
            "rel_err": round(rel, 6),
            "band": band,
            "stale": rel > band,
        }
        if worst is None or d["rel_err"] > worst["rel_err"]:
            worst = d
    return worst


def _emit_drift(obs_dir: str, entry: Dict[str, Any],
                drift: Dict[str, Any]) -> None:
    """Fire the schema'd ``autotune_drift`` event — through the active
    obs session when one exists (the attempt-end hook path), else
    appended directly into the run dir the evidence came from (the
    offline CLI path). Best-effort: a failed emit never blocks ingest."""
    payload = {"key": entry.get("key"), **drift}
    try:
        from gke_ray_train_tpu.obs import runtime as obs_runtime
        run = obs_runtime.active()
        if run is not None:
            run.emit("autotune_drift", **payload)
            return
        from gke_ray_train_tpu.obs.events import EventLog, events_path
        rows = [r for r in entry.get("observed") or []
                if r.get("arm") == drift.get("arm")]
        elog = EventLog(
            events_path(obs_dir, "cal"),
            run_id=str((rows or [{}])[0].get("run_id") or "ingest"),
            attempt=int((rows or [{}])[0].get("attempt") or 0),
            rank="cal",
            plan_fingerprint=entry.get("winner_fingerprint"))
        try:
            elog.emit("autotune_drift", **payload)
        finally:
            elog.close()
    except Exception:  # noqa: BLE001 - never fatal on the ingest path
        logger.warning("autotune: drift event emit failed for %s",
                       entry.get("key"), exc_info=True)


def _carry_observed(prior: Optional[Dict[str, Any]],
                    doc: Dict[str, Any]) -> List[Dict[str, Any]]:
    """On a re-record, keep prior observed rows that still describe one
    of the new arms (same plan fingerprint), re-stamped against the new
    scores; everything else is evidence about plans this entry no
    longer proposes."""
    if not prior:
        return []
    arms = {doc.get("base_fingerprint"): "base",
            doc.get("winner_fingerprint"): "tuned"}
    kept: List[Dict[str, Any]] = []
    seen = set()
    for row in prior.get("observed") or []:
        arm = arms.get(row.get("plan_fingerprint"))
        if arm is None:
            continue
        stored = _stored_row(
            {**row, "measured_step_s": row.get("measured")
             if row.get("surface", "train") != "serve" else None,
             "measured_per_token_s": row.get("measured")
             if row.get("surface", "train") == "serve" else None},
            arm, doc)
        if _row_id(stored) in seen:
            continue
        seen.add(_row_id(stored))
        kept.append(stored)
    kept.sort(key=_row_id)
    return kept


def ingest_observed(obs_dir: str, *,
                    directory: Optional[str] = None,
                    config: Optional[Mapping[str, Any]] = None,
                    band: Optional[float] = None,
                    runtime_arms: Optional[Mapping[str, Tuple[str, str]]]
                    = None,
                    log: Optional[logging.Logger] = None
                    ) -> Dict[str, Any]:
    """Match one run dir's observed rows into the registry's observed
    columns and re-judge drift — the write half of the feedback loop.

    ``runtime_arms`` maps a RUNTIME plan fingerprint to ``(entry_key,
    arm)`` — the attempt-end hook passes it because the live plan's
    operational fields (``autotune=True`` itself, obs knobs) make its
    fingerprint differ from the search-time base/winner fingerprints
    the entry recorded.

    Deterministic and idempotent: rows dedupe on :data:`_ROW_KEY`,
    columns stay sorted, and entries are rewritten ONLY when their
    bytes would change — re-ingesting the same run dir twice is a
    no-op. Returns a summary dict; the CLI maps it to the rc contract
    (0 ok / 3 nothing matched / 4 all refused / 5 drift tripped).
    """
    log = log or logger
    directory = directory or registry_dir(config)
    band = drift_band(config) if band is None else float(band)
    from gke_ray_train_tpu.obs.observe import observed_runs
    rows = observed_runs(obs_dir)
    cal = _calibrate.load_calibration(directory)
    summary: Dict[str, Any] = {
        "obs_dir": obs_dir, "directory": directory, "band": band,
        "calibrated": bool(cal), "rows": len(rows), "matched": 0,
        "refusals": [], "entries": {}, "updated": [], "drift": [],
    }
    for path, entry in list_entries(directory):
        key = entry["key"]
        gate = _entry_refusal(entry)
        if gate is not None:
            summary["refusals"].append(f"{key}: {gate}")
            continue
        arms = {entry.get("base_fingerprint"): "base",
                entry.get("winner_fingerprint"): "tuned"}
        for fp, (k, arm) in dict(runtime_arms or {}).items():
            if k == key:
                arms[fp] = arm
        before = json.dumps(entry, indent=1, sort_keys=True) + "\n"
        observed = {_row_id(r): r for r in entry.get("observed") or []}
        matched_here = 0
        for row in rows:
            arm = arms.get(row.get("plan_fingerprint"))
            if arm is None:
                continue
            why = _row_refusal(row, entry)
            if why is not None:
                summary["refusals"].append(f"{key}: {why}")
                continue
            stored = _stored_row(row, arm, entry)
            if stored.get("measured") is None:
                continue
            observed.setdefault(_row_id(stored), stored)
            matched_here += 1
        summary["matched"] += matched_here
        entry["observed"] = [observed[k2] for k2 in sorted(
            observed, key=lambda t: tuple(str(x) for x in t))]
        verdict = evaluate_drift(entry, cal, band)
        if verdict is not None:
            entry["drift"] = verdict
            if verdict["stale"]:
                entry["stale"] = True
                summary["drift"].append({"key": key, **verdict})
                log.warning(
                    "autotune: DRIFT on %s (%s arm): corrected model "
                    "%.3es vs measured %.3es — rel_err %.3f > band "
                    "%.3f; entry marked STALE (overlay will refuse "
                    "until re-tune)", key, verdict["arm"],
                    verdict["corrected_modeled_step_s"],
                    verdict["measured_step_s"], verdict["rel_err"],
                    band)
                _emit_drift(obs_dir, entry, verdict)
            else:
                entry.pop("stale", None)
        after = json.dumps(entry, indent=1, sort_keys=True) + "\n"
        if after != before:
            with open(path, "w", encoding="utf-8") as f:
                f.write(after)
            summary["updated"].append(key)
        if matched_here:
            summary["entries"][key] = len(entry["observed"])
    return summary


def fit_and_save_calibration(directory: Optional[str] = None, *,
                             config: Optional[Mapping[str, Any]] = None
                             ) -> Dict[str, Any]:
    """``autotune calibrate``: fit factors over every entry's observed
    columns and persist ``calibration.json``. Returns the calibration
    doc with the written path under ``"_path"`` (not persisted)."""
    directory = directory or registry_dir(config)
    entries = [e for _, e in list_entries(directory)]
    samples = _calibrate.samples_from_entries(entries)
    cal = _calibrate.fit_calibration(samples)
    path = _calibrate.save_calibration(cal, directory)
    logger.info("autotune: calibration fitted over %d samples from %d "
                "entries -> %s", len(samples), len(entries), path)
    return {**cal, "_path": path, "_samples": len(samples)}

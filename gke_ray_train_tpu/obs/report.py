"""One report per run (ISSUE 11 tentpole, part 4).

``build_report(run_dir)`` merges everything a run left behind — the
per-rank event streams, the metric exports, the supervisor heartbeat
export, the capture artifacts, and any bench records — into one JSON
document with a per-attempt timeline: compile / restore / fast-forward
/ stall / step / lost decomposition (the goodput ledger), reshards,
anomalies, and their capture artifacts.

The reconciliation invariant is re-VERIFIED here, not trusted: every
attempt's ledger terms must sum to its wall-clock (the identity
``finish_ledger`` constructs; ``rayint/trainer.py`` computes ``lost_s``
as the attempt-wall residual). A report whose ledgers do not reconcile
is a telemetry bug — the CLI exits 3 so CI catches it.

Stdlib-only (the report runs on machines with no jax — a laptop
pointed at a GCS-FUSE mount).
"""

from __future__ import annotations

import glob
import json
import logging
import os
from typing import Any, Dict, List, Optional

from gke_ray_train_tpu.obs import critical as critical_mod
from gke_ray_train_tpu.obs.events import iter_events
from gke_ray_train_tpu.obs.trace import iter_spans

logger = logging.getLogger(__name__)

# terms must match train/metrics.py LEDGER_TERMS; duplicated as a
# STRING list on purpose — the report must run without jax, and the
# schema contract test pins the two against each other
LEDGER_TERMS = ["compile_s", "restore_s", "fast_forward_s",
                "data_stall_s", "eval_ckpt_stall_s", "ckpt_async_s",
                "peer_restore_s", "step_s", "lost_s"]
RECONCILE_TOL = 1e-6


class ReportError(RuntimeError):
    """The run dir is unreadable or holds no telemetry."""


def find_obs_dir(run_dir: str) -> str:
    """Accept the obs dir itself OR its parent run dir."""
    for cand in (run_dir, os.path.join(run_dir, "obs")):
        if glob.glob(os.path.join(cand, "events-*.jsonl")):
            return cand
    raise ReportError(
        f"no obs telemetry under {run_dir!r} (no events-*.jsonl in it "
        "or its obs/ subdir) — was the run started with OBS enabled?")


def _reconcile(goodput: Optional[dict]) -> Optional[Dict[str, Any]]:
    if not goodput or "wall_s" not in goodput:
        return None
    total = sum(float(goodput.get(t, 0.0)) for t in LEDGER_TERMS)
    wall = float(goodput["wall_s"])
    return {"terms_sum_s": total, "wall_s": wall,
            "residual_s": total - wall,
            "ok": abs(total - wall) <= RECONCILE_TOL * max(1.0, wall)}


def _captures_on_disk(obs_dir: str) -> List[Dict[str, Any]]:
    out = []
    for marker in sorted(glob.glob(
            os.path.join(obs_dir, "captures", "*", "capture.json"))):
        try:
            with open(marker, encoding="utf-8") as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        doc["artifact"] = os.path.dirname(marker)
        out.append(doc)
    return out


def _load_json(path: str) -> Optional[dict]:
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def _bench_records(obs_dir: str) -> List[dict]:
    out = []
    path = os.path.join(obs_dir, "bench_records.jsonl")
    if os.path.exists(path):
        with open(path, encoding="utf-8") as f:
            for line in f:
                line = line.strip()
                if line:
                    try:
                        out.append(json.loads(line))
                    except ValueError:
                        pass
    return out


def build_report(run_dir: str) -> Dict[str, Any]:
    obs_dir = find_obs_dir(run_dir)
    events = list(iter_events(obs_dir))
    if not events:
        raise ReportError(f"no events under {obs_dir!r}")
    run_ids = sorted({e.get("run_id") for e in events if e.get("run_id")})

    # -- attempts: driver attempt_end is authoritative (it carries the
    # FINISHED ledger, lost_s reconciled to the attempt wall); a bare
    # run_training session has only worker_exit streams --------------
    att_events: Dict[int, List[dict]] = {}
    for e in events:
        att_events.setdefault(int(e.get("attempt") or 0), []).append(e)
    ends = [e for e in events if e["kind"] == "attempt_end"]
    if not ends:
        # driverless session: ONE entry per attempt, not per rank — a
        # multi-process run writes a worker_exit per rank, all stamped
        # with the same attempt; summing them would multiply the
        # goodput totals by the world size
        picked: Dict[int, dict] = {}
        for e in events:
            if e["kind"] == "worker_exit":
                picked.setdefault(int(e.get("attempt") or 0), e)
        ends = list(picked.values())
    attempts: List[Dict[str, Any]] = []
    for i, end in enumerate(sorted(ends, key=lambda e: e["ts"]), 1):
        n = int(end.get("attempt") or i)
        end_run_id = end.get("run_id")
        evs = att_events.get(n, [])
        t0 = min((e["ts"] for e in evs), default=end["ts"])
        goodput = end.get("goodput")
        att: Dict[str, Any] = {
            "attempt": n,
            "run_id": end_run_id,
            "status": end.get("status"),
            "plan_fingerprint": end.get("plan_fingerprint"),
            "resumed_step": end.get("resumed_step"),
            "goodput": goodput,
            "reconciliation": _reconcile(goodput),
            "timeline": [
                {"t": round(e["ts"] - t0, 3), "rank": e.get("rank"),
                 "step": e.get("step"), "kind": e["kind"],
                 **{k: v for k, v in e.items()
                    if k not in ("ts", "run_id", "attempt", "rank",
                                 "slice", "step", "plan_fingerprint",
                                 "kind")}}
                for e in evs if e["kind"] not in ("step",)],
            "steps_logged": sum(1 for e in evs if e["kind"] == "step"),
            # the backend the attempt ACTUALLY ran on (first_step
            # stamps it): `autotune ingest` filters on this so a CPU
            # measurement can never calibrate a TPU ChipSpec — the
            # report carries it through
            "backend": next((e.get("backend") for e in evs
                             if e["kind"] == "first_step"
                             and e.get("backend")), None),
        }
        if end.get("event"):
            att["event"] = end["event"]          # shrink | grow
            att["pool"] = end.get("pool")
        # one entry per actual mesh transition: the plan re-formation
        # (rayint/elastic.py) and the resharded restore
        # (ckpt/manager.py) both witness the same from->to pair —
        # merge them, keeping the richest fields
        reshards: Dict[tuple, dict] = {}
        for e in evs:
            if e["kind"] != "reshard":
                continue
            key = (e.get("from_devices"), e.get("to_devices"))
            merged = reshards.setdefault(key, {})
            for k in ("from_devices", "to_devices", "to_fingerprint",
                      "mesh", "per_device_batch"):
                if e.get(k) is not None:
                    merged[k] = e[k]
        if reshards:
            att["reshard"] = list(reshards.values())
        attempts.append(att)

    # a local-path heartbeat stall is witnessed TWICE — the watchdog's
    # worker-stream anomaly (which may carry the capture) and the
    # driver's note_stall anomaly; merge per (attempt, class,
    # trigger_step) like the reshard twins, preferring the worker's
    # (rank-stamped) record
    seen_anoms: Dict[tuple, dict] = {}
    for e in events:
        if e["kind"] != "anomaly":
            continue
        key = (int(e.get("attempt") or 0), e.get("class"),
               e.get("trigger_step"))
        prev = seen_anoms.get(key)
        if prev is None or prev.get("rank") == "driver":
            seen_anoms[key] = e
    anomalies = list(seen_anoms.values())
    capture_events = [e for e in events if e["kind"] == "capture"]
    captures = _captures_on_disk(obs_dir)

    # anomaly -> capture cross-reference: fire-once means each
    # (attempt, class) pair with an anomaly has AT MOST one capture;
    # count how many anomalies got their artifact
    cap_keys = {(int(e.get("attempt") or 0), e.get("class"))
                for e in capture_events}
    for a in anomalies:
        a_key = (int(a.get("attempt") or 0), a.get("class"))
        a["captured"] = a_key in cap_keys

    # -- metrics: latest export per rank ------------------------------
    metrics = {}
    for path in sorted(glob.glob(os.path.join(obs_dir,
                                              "metrics-r*.json"))):
        rank = os.path.basename(path)[len("metrics-r"):-len(".json")]
        doc = _load_json(path)
        if doc is not None:
            metrics[rank] = doc

    # -- causal spans (obs/trace.py) -> per-attempt critical path ------
    # grouped by (run_id, attempt), NOT attempt alone: span/event files
    # open in append mode, so a reused obs dir (the run-stable default
    # <output>/obs) holds several runs' streams — merging run A's and
    # run B's attempt-1 spans would double-count terms against one
    # ledger and flip the rc=3 gate on perfectly healthy telemetry
    spans = list(iter_spans(obs_dir))
    spans_by_attempt: Dict[tuple, List[dict]] = {}
    for s in spans:
        spans_by_attempt.setdefault(
            (s.get("run_id"), int(s.get("attempt") or 0)), []).append(s)
    # per-rank worker ledgers: the span/ledger reconciliation runs
    # against the CRITICAL rank's own books (worker_exit carries one
    # per rank), not rank 0's
    rank_ledgers: Dict[tuple, Dict[Any, dict]] = {}
    for e in events:
        if e["kind"] == "worker_exit" and isinstance(e.get("goodput"),
                                                     dict):
            rank_ledgers.setdefault(
                (e.get("run_id"), int(e.get("attempt") or 0)),
                {})[e.get("rank")] = e["goodput"]
    critical_ok = True
    for att in attempts:
        key = (att.get("run_id"), att["attempt"])
        sp = spans_by_attempt.get(key)
        if not sp:
            continue
        cp = critical_mod.critical_path(
            sp, att.get("goodput"), rank_ledgers.get(key))
        if cp is not None:
            att["critical_path"] = cp
            critical_ok = critical_ok and cp["reconciliation"]["ok"]
    trace_section = None
    if spans:
        # same reused-dir discipline as the critical path above: the
        # headline trace section describes ONE run — the newest by
        # span end time — never a cross-run mixture (the serve
        # "slowest request" of run A must not label run B's report)
        newest_run = max(spans, key=lambda s: s.get("t1", 0.0)) \
            .get("run_id")
        tr_spans = [s for s in spans if s.get("run_id") == newest_run]
        trace_section = {
            "trace_id": tr_spans[0].get("trace_id"),
            "span_count": len(tr_spans),
            "runs_in_dir": len({s.get("run_id") for s in spans}),
            "serve": critical_mod.serve_summary(tr_spans),
        }

    reconciled = all(a["reconciliation"]["ok"] for a in attempts
                     if a["reconciliation"] is not None)
    totals: Dict[str, float] = {}
    for a in attempts:
        for k, v in (a.get("goodput") or {}).items():
            if isinstance(v, (int, float)):
                totals[k] = totals.get(k, 0.0) + float(v)
    if totals.get("wall_s"):
        totals["goodput_frac"] = totals.get("step_s", 0.0) / \
            totals["wall_s"]

    # network traffic of the compiled step (grt_ici_bytes /
    # grt_dcn_bytes, noted at AOT build from the StepCostReport): one
    # per-run summary — every rank compiles the same SPMD program, so
    # the max across ranks IS the program's number
    network = {}
    for key in ("ici_bytes", "dcn_bytes"):
        vals = [doc.get(key) for doc in metrics.values()
                if isinstance(doc.get(key), (int, float))]
        if vals:
            network[key] = max(vals)

    # -- autotune feedback loop (autotune/registry.py ingest): an
    # autotune_drift event in the stream means a calibrated cost model
    # mispredicted a real run — counted and the worst relative error
    # surfaced as report scalars, so an `obs diff` baseline pins a
    # silently-degrading model (a drift event appearing where the
    # recorded run had none trips the gate)
    drift_events = [e for e in events if e["kind"] == "autotune_drift"]
    n_candidates = sum(1 for e in events
                       if e["kind"] == "autotune_candidate")
    n_results = sum(1 for e in events if e["kind"] == "autotune_result")
    autotune_section = None
    if drift_events or n_candidates or n_results:
        autotune_section = {
            "candidates": n_candidates,
            "results": n_results,
            "drift_events": len(drift_events),
            "drift_stale": sum(1 for e in drift_events
                               if e.get("stale")),
        }
        if drift_events:
            worst = max(drift_events,
                        key=lambda e: float(e.get("rel_err") or 0.0))
            autotune_section["drift_max_rel_err"] = worst.get("rel_err")
            autotune_section["drift_band"] = worst.get("band")
            autotune_section["drift_keys"] = sorted(
                {e.get("key") for e in drift_events if e.get("key")})

    backends = sorted({a["backend"] for a in attempts if a.get("backend")})

    run_end = next((e for e in reversed(events)
                    if e["kind"] == "run_end"), None)
    report = {
        "run_id": run_ids[0] if len(run_ids) == 1 else run_ids,
        "obs_dir": obs_dir,
        "status": run_end.get("status") if run_end else None,
        "attempts": attempts,
        "n_attempts": len(attempts),
        "preemptions": run_end.get("preemptions") if run_end else None,
        "goodput": totals or None,
        "network": network or None,
        "backend": (backends[0] if len(backends) == 1
                    else (backends or None)),
        "autotune": autotune_section,
        "reconciled": reconciled,
        # span/ledger cross-stream verification (obs/critical.py):
        # True when no attempt has spans, or every attempt's span-
        # derived terms match its rank's ledger — the CLI exits 3 on
        # False, the same teeth as the ledger identity above
        "critical_path_ok": critical_ok,
        "trace": trace_section,
        "anomalies": [{k: a.get(k) for k in
                       ("attempt", "rank", "class", "trigger_step",
                        "detail", "captured")} for a in anomalies],
        "captures": captures,
        "metrics": metrics,
        "supervisor": _load_json(os.path.join(obs_dir,
                                              "supervisor.json")),
        "bench_records": _bench_records(obs_dir),
        "event_count": len(events),
    }
    return report


def render_text(report: Dict[str, Any]) -> str:
    """Human-readable per-attempt timeline."""
    L: List[str] = []
    L.append(f"obs report — run {report['run_id']} "
             f"({report['n_attempts']} attempt(s), "
             f"{report['event_count']} events, "
             f"{'reconciled' if report['reconciled'] else 'NOT RECONCILED'})")
    g = report.get("goodput") or {}
    if g.get("wall_s"):
        L.append("  goodput: {:.1%} of {:.1f}s wall".format(
            g.get("goodput_frac", 0.0), g["wall_s"]))
    net = report.get("network") or {}
    if net:
        L.append("  network: ici {:,}B dcn {:,}B per step".format(
            int(net.get("ici_bytes", 0)), int(net.get("dcn_bytes", 0))))
    if report.get("backend"):
        L.append(f"  backend: {report['backend']}")
    at = report.get("autotune")
    if at:
        line = (f"  autotune: {at['candidates']} candidate(s), "
                f"{at['results']} result(s), {at['drift_events']} "
                f"drift event(s)")
        if at["drift_events"]:
            line += (f" — {at['drift_stale']} STALE, worst rel err "
                     f"{at.get('drift_max_rel_err')} vs band "
                     f"{at.get('drift_band')} ({at.get('drift_keys')})")
        L.append(line)
    for a in report["attempts"]:
        head = f"attempt {a['attempt']}: {a['status']}"
        if a.get("event"):
            head += f" [{a['event']} -> pool {a.get('pool')}]"
        if a.get("resumed_step") is not None:
            head += f" (resumed @ step {a['resumed_step']})"
        L.append(head)
        gp = a.get("goodput") or {}
        if gp:
            wall = gp.get("wall_s", 0.0) or 1.0
            bar = "  ledger: " + " ".join(
                f"{t[:-2]}={gp.get(t, 0.0):.2f}s"
                f"({gp.get(t, 0.0) / wall:.0%})"
                for t in LEDGER_TERMS if gp.get(t, 0.0) > 0.0005)
            L.append(bar + f"  wall={wall:.2f}s")
            rec = a.get("reconciliation")
            if rec is not None and not rec["ok"]:
                L.append(f"  !! ledger does NOT reconcile: terms sum "
                         f"{rec['terms_sum_s']:.4f}s vs wall "
                         f"{rec['wall_s']:.4f}s")
        cp = a.get("critical_path")
        if cp:
            terms = cp.get("terms") or {}
            cw = cp.get("wall_s") or terms.get("wall_s") or 1.0
            flame = " | ".join(
                f"{t[:-2]} {terms.get(t, 0.0):.2f}s"
                f"({terms.get(t, 0.0) / cw:.0%})"
                for t in LEDGER_TERMS
                if terms.get(t, 0.0) > max(0.005 * cw, 0.0005))
            crec = cp.get("reconciliation") or {}
            L.append(f"  critical path r{cp['rank']}: {flame}"
                     + ("" if crec.get("ok")
                        else "  !! SPANS DO NOT MATCH LEDGER "
                             f"(deltas {crec.get('deltas')})"))
        for e in a["timeline"]:
            extras = {k: v for k, v in e.items()
                      if k not in ("t", "rank", "step", "kind")
                      and v is not None}
            detail = (" " + json.dumps(extras, sort_keys=True,
                                       default=str)[:160]
                      if extras else "")
            L.append(f"  +{e['t']:>8.3f}s r{e['rank']} "
                     f"step {e['step'] if e['step'] is not None else '-':>5}"
                     f"  {e['kind']}{detail}")
    if report["anomalies"]:
        L.append("anomalies:")
        for a in report["anomalies"]:
            L.append(f"  attempt {a['attempt']} {a['class']} @ step "
                     f"{a['trigger_step']} captured={a['captured']}")
    if report["captures"]:
        L.append("captures:")
        for c in report["captures"]:
            L.append(f"  {c['class']} @ step {c['trigger_step']}: "
                     f"{c['artifact']}")
    tr = report.get("trace")
    if tr:
        L.append(f"trace {tr['trace_id']}: {tr['span_count']} spans")
        sv = tr.get("serve")
        if sv:
            ex = sv.get("slowest") or {}
            L.append(
                f"  serve: {sv['requests']} request(s), slowest "
                f"{ex.get('rid')} = {ex.get('total_s', 0.0):.3f}s "
                f"(enqueue {ex.get('enqueue_s', 0.0):.3f}s, prefill "
                f"{ex.get('prefill_s', 0.0):.3f}s, decode "
                f"{ex.get('decode_s', 0.0):.3f}s / "
                f"{ex.get('iterations')} iter)")
    sup = report.get("supervisor")
    if sup and sup.get("stalled"):
        L.append(f"supervisor: stalled ranks {sup['stalled']}")
    for b in report.get("bench_records", []):
        L.append(f"bench: {b.get('metric', '?')[:80]} = "
                 f"{b.get('value')} {b.get('unit')}")
    return "\n".join(L)


def write_report(run_dir: str,
                 out_path: Optional[str] = None) -> Dict[str, Any]:
    """build + persist ``report.json`` beside the events; returns the
    report dict (the CLI layers the rc contract on top)."""
    report = build_report(run_dir)
    path = out_path or os.path.join(report["obs_dir"], "report.json")
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(report, f, indent=1, sort_keys=True, default=str)
    os.replace(tmp, path)
    report["report_path"] = path
    return report

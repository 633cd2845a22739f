"""Critical-path attribution over the merged span DAG (ISSUE 14
tentpole, part 2).

The report's goodput ledger says an attempt spent 41% of wall in
``restore_s``; this module says *which rank's* restore gated the
attempt and what the gating chain looked like. Per attempt:

- worker spans (``obs/trace.py``) are grouped by rank; the **critical
  rank** is the one whose attempt span ran longest — on an SPMD job
  every rank exits the attempt together, so the rank with the longest
  own-work chain is the one the others waited on;
- the **path** is that rank's causally-ordered leaf spans (a rank's
  loop is sequential, so temporal order on one rank IS causal order;
  cross-rank edges come from the driver-attempt parent links);
- the **terms** are the attempt's finished goodput ledger — the
  identity that already sums to attempt wall EXACTLY (``finish_ledger``
  constructs it; ``report.py`` re-verifies it);
- the **reconciliation** is this module's own teeth: the span-derived
  duration of every directly-traced term (restore / compile /
  fast-forward / eval+ckpt stalls / data stalls) must match the same
  rank's goodput ledger to within :data:`RECONCILE_TOL` — the
  instrumented sites emit the EXACT floats the ledger booked, so a
  drift between the two streams is an instrumentation bug, not noise —
  and the spans must never claim more time than the attempt wall.
  ``obs report`` exits 3 on a failure, the same discipline as the
  ledger identity itself.

Stdlib-only (runs wherever the report runs — no jax).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

# span name -> the goodput ledger term it measures (train/metrics.py
# LEDGER_TERMS; duplicated as strings on purpose — report-side code
# must run without jax, and test_trace pins the mapping against the
# ledger). step_window spans split between step_s (their duration
# minus the stall attr) and data_stall_s; serve/reshard/attempt spans
# map to no term (reshard time is inside restore; serve runs post-loop),
# and neither do the build's (step_build and its children, state_build)
# nor train_loop, which holds the whole loop: its children book it.
SPAN_TERM = {
    "restore": "restore_s",
    "peer_restore": "peer_restore_s",
    "compile": "compile_s",
    "fast_forward": "fast_forward_s",
    "eval": "eval_ckpt_stall_s",
    "ckpt_save": "eval_ckpt_stall_s",
    "ckpt_snapshot": "ckpt_async_s",
    "preempt_save": "eval_ckpt_stall_s",
}
# the terms whose span measurement must agree with the ledger exactly
# (they are emitted from the identical floats); step_s is NOT here —
# step windows legitimately undercover the loop's residual (the ledger
# books step_s as wall minus everything else).
RECONCILED_TERMS = ("restore_s", "compile_s", "fast_forward_s",
                    "eval_ckpt_stall_s", "data_stall_s",
                    "ckpt_async_s", "peer_restore_s")
RECONCILE_TOL = 1e-6
MAX_PATH = 64


def _is_worker(span: Dict[str, Any]) -> bool:
    return str(span.get("rank")) != "driver"


def span_terms(leaves: List[Dict[str, Any]]) -> Dict[str, float]:
    """Ledger-term sums as the SPANS measured them, for one rank's
    leaf spans of one attempt."""
    out: Dict[str, float] = {}
    for s in leaves:
        name = s.get("name")
        dur = float(s.get("dur_s", 0.0))
        if name == "step_window":
            stall = float(s.get("data_stall_s", 0.0) or 0.0)
            out["step_s"] = out.get("step_s", 0.0) + max(dur - stall, 0.0)
            out["data_stall_s"] = out.get("data_stall_s", 0.0) + stall
        elif name in SPAN_TERM:
            term = SPAN_TERM[name]
            out[term] = out.get(term, 0.0) + dur
    return out


def critical_path(spans: List[Dict[str, Any]],
                  goodput: Optional[Dict[str, Any]],
                  worker_ledgers: Optional[Dict[Any, dict]] = None,
                  max_path: int = MAX_PATH) -> Optional[Dict[str, Any]]:
    """The critical-path section for ONE attempt.

    ``spans``: every span of the attempt (all ranks incl. driver).
    ``goodput``: the driver's finished ledger (terms + ``wall_s``).
    ``worker_ledgers``: rank -> that rank's own ``worker_exit`` ledger
    (the per-rank stream carries one each); the span/ledger
    reconciliation runs against the CRITICAL rank's own ledger, not
    rank 0's — on a multi-rank job the gating rank's spans must match
    the gating rank's books.
    """
    by_rank: Dict[Any, List[Dict[str, Any]]] = {}
    for s in spans:
        if _is_worker(s):
            by_rank.setdefault(s.get("rank"), []).append(s)
    if not by_rank:
        return None

    def rank_weight(rank) -> float:
        att = [s for s in by_rank[rank] if s.get("name") == "attempt"]
        if att:
            return float(att[-1].get("dur_s", 0.0))
        return sum(float(s.get("dur_s", 0.0)) for s in by_rank[rank])

    crit = max(sorted(by_rank, key=str), key=rank_weight)
    mine = sorted(by_rank[crit], key=lambda s: (s.get("t0", 0.0),
                                                str(s.get("span_id"))))
    att_spans = [s for s in mine if s.get("name") == "attempt"]
    t_base = (att_spans[-1].get("t0") if att_spans
              else (mine[0].get("t0") if mine else 0.0)) or 0.0
    # the path: causally-ordered leaf spans (serve children excluded —
    # their parent request span already covers them — and train_loop,
    # which holds every span of the loop)
    child_parents = {s.get("span_id") for s in mine
                     if s.get("name") == "serve_request"}
    leaves = [s for s in mine
              if s.get("name") not in ("attempt", "train_loop")
              and s.get("parent_id") not in child_parents]
    if not any(s.get("name") in SPAN_TERM or s.get("name") ==
               "step_window" for s in leaves):
        # no ledger-mapped spans at all: the session never ran the
        # instrumented loop (a serve-only drain, a script emitting bare
        # events, an attempt killed before restore) — there is no path
        # to attribute and nothing to reconcile
        return None
    path = [{
        "name": s.get("name"),
        "t": round(float(s.get("t0", 0.0)) - float(t_base), 3),
        "dur_s": float(s.get("dur_s", 0.0)),
        "step": s.get("step"),
        **({"steps": s.get("steps")}
           if s.get("name") == "step_window" else {}),
    } for s in leaves]
    dropped = max(len(path) - max_path, 0)
    path = path[:max_path]

    sterms = span_terms(leaves)
    wall = float((goodput or {}).get("wall_s", 0.0) or 0.0)
    tol = RECONCILE_TOL * max(1.0, wall)
    ledger = (worker_ledgers or {}).get(crit) \
        or (worker_ledgers or {}).get(str(crit)) or goodput or {}
    deltas: Dict[str, float] = {}
    ok = True
    for term in RECONCILED_TERMS:
        if term not in ledger:
            continue
        d = sterms.get(term, 0.0) - float(ledger.get(term, 0.0))
        deltas[term] = d
        if abs(d) > tol:
            ok = False
    covered = sum(sterms.values())
    over = covered - wall if wall else 0.0
    if wall and over > tol:
        # spans claiming more time than the attempt wall is the same
        # class of telemetry bug as a non-summing ledger
        ok = False
    out: Dict[str, Any] = {
        "rank": crit,
        "wall_s": wall or None,
        # the attempt's reconciled identity: these sum to wall exactly
        # (report.py re-verifies); the spans ATTRIBUTE them
        "terms": {k: float(v) for k, v in (goodput or {}).items()
                  if isinstance(v, (int, float))} or None,
        "span_terms": {k: round(v, 6) for k, v in sorted(sterms.items())},
        "path": path,
        "reconciliation": {
            "ok": ok,
            "deltas": {k: round(v, 9) for k, v in deltas.items()},
            "span_covered_s": round(covered, 6),
            "overcoverage_s": round(max(over, 0.0), 6),
        },
    }
    if dropped:
        out["path_truncated"] = dropped
    return out


def serve_summary(spans: List[Dict[str, Any]]) -> Optional[Dict[str, Any]]:
    """End-to-end decomposition of the traced serve requests: per-phase
    mean durations plus one fully-decomposed example request (the
    "where did my p99 go" witness the report surfaces)."""
    reqs = [s for s in spans if s.get("name") == "serve_request"]
    if not reqs:
        return None
    children: Dict[str, List[Dict[str, Any]]] = {}
    for s in spans:
        if s.get("name") in ("serve_enqueue", "serve_prefill",
                             "serve_decode"):
            children.setdefault(s.get("parent_id"), []).append(s)

    def mean(vals: List[float]) -> float:
        return round(sum(vals) / len(vals), 6) if vals else 0.0

    phases: Dict[str, List[float]] = {}
    iters: List[int] = []
    for r in reqs:
        for c in children.get(r.get("span_id"), []):
            phases.setdefault(c["name"], []).append(
                float(c.get("dur_s", 0.0)))
            if c["name"] == "serve_decode" and c.get("iterations") \
                    is not None:
                iters.append(int(c["iterations"]))
    first = max(reqs, key=lambda r: float(r.get("dur_s", 0.0)))
    example: Dict[str, Any] = {
        "rid": first.get("rid"), "bucket": first.get("bucket"),
        "total_s": round(float(first.get("dur_s", 0.0)), 6),
        "finish_reason": first.get("finish_reason"),
        "generated": first.get("generated"),
    }
    for c in children.get(first.get("span_id"), []):
        example[c["name"].replace("serve_", "") + "_s"] = round(
            float(c.get("dur_s", 0.0)), 6)
        if c["name"] == "serve_decode":
            example["iterations"] = c.get("iterations")
    return {
        "requests": len(reqs),
        "mean_total_s": mean([float(r.get("dur_s", 0.0)) for r in reqs]),
        "mean_enqueue_s": mean(phases.get("serve_enqueue", [])),
        "mean_prefill_s": mean(phases.get("serve_prefill", [])),
        "mean_decode_s": mean(phases.get("serve_decode", [])),
        "mean_iterations": (round(sum(iters) / len(iters), 2)
                            if iters else None),
        "slowest": example,
    }

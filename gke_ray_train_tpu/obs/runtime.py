"""The per-process obs session — one configure point, no plumbing.

``rayint/trainer.py::_run_worker`` starts an attempt-scoped session
(:func:`start_attempt`) and the driver a run-scoped one
(:func:`start_driver`); everything else — the train loop, the preempt
exit, the elastic replan, the serve engine, the entries — just calls
:func:`emit` / :func:`registry` / :func:`active`, which no-op when
nothing is configured (bare ``run_training`` in tests and benches pays
one ``is None`` check).

Resolution (:func:`resolve_obs_dir`): an explicit ``OBS_DIR`` (plan
field ``obs_dir``) wins; otherwise the run's output dir is used
(``OUTPUT_DIR_BASE`` for the fine-tune entry, ``storage_path`` +
``run_name`` for the pre-train entry) with an ``obs/`` suffix; with
neither resolvable — or ``OBS=0`` — the session stays off. Identity
rides the env: the trainer mints ``OBS_RUN_ID`` once per ``fit()`` and
stamps ``OBS_ATTEMPT`` per attempt, so every rank of every attempt
writes into one correlated stream.

Stdlib-only at import (driver side has no jax); capture and the
jax.monitoring listener import lazily inside the session.
"""

from __future__ import annotations

import logging
import os
import time
import uuid
from typing import Any, Dict, Optional, Union

from gke_ray_train_tpu.obs import events as events_mod
from gke_ray_train_tpu.obs import metrics as metrics_mod
from gke_ray_train_tpu.obs.events import EventLog, events_path
from gke_ray_train_tpu.obs.metrics import (
    MetricsRegistry, export_serve_stats, pull_jax_counters)
from gke_ray_train_tpu.obs import trace as trace_mod
from gke_ray_train_tpu.obs.trace import SpanLog, new_span_id, spans_path

logger = logging.getLogger(__name__)

RUN_ID_ENV = "OBS_RUN_ID"
ATTEMPT_ENV = "OBS_ATTEMPT"
# the causal parent of this process's attempt span (obs/trace.py): the
# driver mints one span id per attempt and forwards it to every worker
# through the same env path as the run/attempt identity, so worker
# attempt spans parent under the driver's and the merged cross-rank
# span DAG is connected
PARENT_SPAN_ENV = "OBS_PARENT_SPAN"

_active: Optional["ObsRun"] = None


def new_run_id() -> str:
    return uuid.uuid4().hex[:10]


def _knob(name: str, config: Optional[dict], default: str) -> str:
    """config key > env > default (every knob's precedence)."""
    if config is not None and name in config:
        return str(config[name])
    return os.environ.get(name, default)


def _truthy(raw) -> bool:
    """The one falsey-spelling set for every default-on obs knob
    (OBS / OBS_CAPTURE / TRACE) — four call sites, one dialect."""
    return str(raw).strip().lower() not in ("0", "false", "no", "off")


def resolve_obs_dir(plan=None, config: Optional[dict] = None
                    ) -> Optional[str]:
    """The obs dir for this run, or None (= obs off). Precedence:
    plan.obs_dir / OBS_DIR > OUTPUT_DIR_BASE/obs > storage_path[/run_
    name]/obs. ``plan.obs=False`` / OBS=0 disables regardless."""
    config = config or {}
    enabled = True
    explicit = None
    if plan is not None:
        enabled = bool(getattr(plan, "obs", True))
        explicit = getattr(plan, "obs_dir", None)
    else:
        enabled = _truthy(config.get("OBS", os.environ.get("OBS", "1")))
        explicit = config.get("OBS_DIR", os.environ.get("OBS_DIR"))
    if not enabled:
        return None
    if explicit:
        return str(explicit)
    base = config.get("OUTPUT_DIR_BASE")
    if base:
        return os.path.join(str(base), "obs")
    storage = config.get("storage_path")
    if storage:
        return os.path.join(str(storage),
                            str(config.get("run_name", "")), "obs")
    return None


def current_backend() -> Optional[str]:
    """The backend tag observed-row producers stamp (ISSUE 16): the
    live jax backend name, the honest answer to "what hardware produced
    this measurement" — lazy-imported so the stdlib-only driver side
    can call this and get None rather than an import error. The point
    of the stamp: a CPU number must be REFUSABLE at autotune ingest, so
    it can never calibrate a TPU ChipSpec."""
    try:
        import jax
    except ImportError:
        return None
    return str(jax.default_backend())


class ObsRun:
    """One configured obs session: an event log, the process metrics
    registry, and (worker side) the anomaly capture manager."""

    def __init__(self, obs_dir: str, *, run_id: str, attempt: int,
                 rank: Union[int, str], slice_index: Optional[int],
                 plan_fingerprint: Optional[str],
                 capture=None, trace: bool = True):
        self.obs_dir = obs_dir
        self.rank = rank
        self.events = EventLog(events_path(obs_dir, rank),
                               run_id=run_id, attempt=attempt, rank=rank,
                               slice_index=slice_index,
                               plan_fingerprint=plan_fingerprint)
        self.registry = MetricsRegistry(labels={
            "run_id": run_id, "attempt": str(attempt), "rank": str(rank),
            **({"slice": str(slice_index)}
               if slice_index is not None else {})})
        self.capture = capture
        # causal span stream (obs/trace.py): one attempt span per
        # session, parented under the driver's (OBS_PARENT_SPAN) when
        # one exists; leaf spans default-parent under the attempt span.
        # The span is OPENED here and written at finish() — a killed
        # attempt simply never lands it, which is itself the signal.
        self.spans: Optional[SpanLog] = None
        self.attempt_span_id: Optional[str] = None
        self._attempt_parent = os.environ.get(PARENT_SPAN_ENV) or None
        self._attempt_t0 = time.time()
        if trace:
            self.spans = SpanLog(spans_path(obs_dir, rank),
                                 run_id=run_id, attempt=attempt,
                                 rank=rank, slice_index=slice_index)
            self.attempt_span_id = new_span_id()
        self._closed = False

    # -- loop hooks (hot-path budget: host floats only) ----------------

    def note_step(self, step: int, iter_s: float, wait_s: float) -> None:
        self.events.set_step(step)
        if self.capture is not None:
            self.capture.note_step(step, iter_s, wait_s)
        else:
            # captures off = detection off, but the per-step timing
            # metrics must not go blind with them
            self.registry.counter("steps_total").inc()
            self.registry.histogram("step_time_s").observe(iter_s)
            if wait_s > 0:
                self.registry.histogram("data_wait_s").observe(wait_s)

    def log_metrics(self, step: int, metrics: Dict[str, Any],
                    epoch: Optional[int] = None) -> None:
        """Log-cadence sink: gauges from the already-fetched host
        metrics dict, one ``step`` event, and a file export — all at
        ``log_every`` rate, never per step."""
        self.registry.set_many(metrics)
        pull_jax_counters(self.registry)
        payload = {k: metrics[k] for k in (
            "loss", "learning_rate", "grad_norm",
            "tokens_per_sec_per_chip", "mfu", "data_stall_frac")
            if k in metrics}
        self.emit("step", step=step, epoch=epoch, **payload)
        self.export()

    def note_cost_report(self, report) -> None:
        """Network gauges from a StepCostReport something else already
        computed (the AOT build in perf/cache.py, the serve engine's
        executable_info) — never a second compile-time analysis. One
        call per attempt; exported with the next registry flush."""
        self.registry.set_many({"ici_bytes": getattr(report, "ici_bytes", 0),
                                "dcn_bytes": getattr(report, "dcn_bytes", 0)})

    def note_serve(self, stats: Dict[str, Any],
                   replica: Optional[int] = None) -> None:
        export_serve_stats(self.registry, stats)
        self.emit("serve_drained", replica=replica, stats={
            k: stats.get(k) for k in (
                "iterations", "refills", "completed", "batch_occupancy",
                "p50_token_latency_s", "p99_token_latency_s")})
        self.export()

    def finish(self, status: str, ledger: Optional[dict] = None) -> None:
        """Attempt exit (every path): ledger terms into the registry,
        a ``worker_exit`` event, final export, close."""
        if self._closed:
            return
        if self.capture is not None:
            self.capture.close()
        if ledger:
            from gke_ray_train_tpu.train.metrics import ledger_metrics
            self.registry.set_many(ledger_metrics(ledger))
        pull_jax_counters(self.registry)
        self.emit("worker_exit", status=status, goodput=ledger)
        self.export()
        if self.spans is not None:
            now = time.time()
            try:
                self.spans.emit("attempt", now - self._attempt_t0,
                                t1=now, span_id=self.attempt_span_id,
                                parent_id=self._attempt_parent,
                                status=status)
            except Exception as e:  # noqa: BLE001 - IO best-effort
                logger.warning("obs attempt span dropped: %s", e)
            self.spans.close()
        self.events.close()
        self._closed = True

    # -- primitives ----------------------------------------------------

    def emit(self, kind: str, step: Optional[int] = None,
             **payload: Any) -> None:
        try:
            self.events.emit(kind, step=step, **payload)
        except events_mod.EventError:
            raise            # schema violations are bugs, not telemetry
        except Exception as e:  # noqa: BLE001 - IO must not kill a run
            logger.warning("obs event %s dropped: %s", kind, e)

    def span_add(self, name: str, dur_s: float, *,
                 t1: Optional[float] = None,
                 step: Optional[int] = None,
                 parent_id: Optional[str] = None,
                 span_id: Optional[str] = None,
                 **attrs: Any) -> Optional[str]:
        """Record one finished leaf span (obs/trace.py), parented under
        this attempt's span unless told otherwise. ``dur_s`` is the
        caller's own measurement — instrumented sites pass the exact
        float the goodput ledger booked, which is what lets
        ``obs/critical.py`` reconcile the two streams exactly. Returns
        the span id (for child spans), or None when tracing is off."""
        if self.spans is None:
            return None
        try:
            rec = self.spans.emit(
                name, dur_s, t1=t1, step=step, span_id=span_id,
                parent_id=(parent_id if parent_id is not None
                           else self.attempt_span_id),
                **attrs)
            return rec["span_id"]
        except trace_mod.SpanError:
            raise            # schema violations are bugs, not telemetry
        except Exception as e:  # noqa: BLE001 - IO must not kill a run
            logger.warning("obs span %s dropped: %s", name, e)
            return None

    def export(self) -> None:
        try:
            self.registry.export(self.obs_dir, self.rank)
        except Exception as e:  # noqa: BLE001
            logger.warning("obs metrics export failed: %s", e)


# ---------------------------------------------------------------------------
# module-level session
# ---------------------------------------------------------------------------

def active() -> Optional[ObsRun]:
    return _active


def emit(kind: str, step: Optional[int] = None, **payload: Any) -> None:
    """Emit through the active session; a no-op when none is
    configured — the one line every instrumented module calls."""
    if _active is not None:
        _active.emit(kind, step=step, **payload)


def registry() -> Optional[MetricsRegistry]:
    return _active.registry if _active is not None else None


def note_cost_report(report) -> None:
    """Module-level twin of :meth:`ObsRun.note_cost_report` — no-op
    unconfigured, like :func:`emit`."""
    if _active is not None:
        _active.note_cost_report(report)


def span_add(name: str, dur_s: float, **kw: Any) -> Optional[str]:
    """Module-level twin of :meth:`ObsRun.span_add` — the one line
    every instrumented module calls; no-op (None) when no session is
    configured or tracing is off."""
    if _active is not None:
        return _active.span_add(name, dur_s, **kw)
    return None


def tracing() -> bool:
    """True when the active session records spans — lets hot-ish call
    sites skip building attr dicts for nothing."""
    return _active is not None and _active.spans is not None


def start_attempt(plan=None, config: Optional[dict] = None, *,
                  rank: Optional[int] = None,
                  slice_index: Optional[int] = None,
                  obs_dir: Optional[str] = None) -> Optional[ObsRun]:
    """Worker-side session for one attempt (called by ``_run_worker``
    and usable directly by tests/benches). Returns None when obs is
    off or no dir resolves. Also prefixes the stdlib text logs with
    the same correlation fields (``logging_utils``)."""
    global _active
    end_attempt("replaced")      # a retry must not inherit the old log
    obs_dir = obs_dir or resolve_obs_dir(plan, config)
    run_id = os.environ.get(RUN_ID_ENV) or new_run_id()
    attempt = int(os.environ.get(ATTEMPT_ENV, "1") or 1)
    rank = int(os.environ.get("PROCESS_ID", "0")) if rank is None \
        else int(rank)
    if obs_dir is None:
        return None
    # the log prefix exists to JOIN text logs with the event stream —
    # installed only when a stream exists (and cleared by end_attempt)
    from gke_ray_train_tpu.logging_utils import configure_run_logging
    configure_run_logging(run_id, attempt, rank)
    if slice_index is None:
        slice_index = _rank_slice(rank, config)
    fp = None
    if plan is not None:
        try:
            fp = plan.fingerprint()
        except Exception:  # noqa: BLE001 - provenance is best-effort
            pass
    capture = None
    if plan is not None:        # validated fields
        cap_on = bool(getattr(plan, "obs_capture", True))
        budget = int(getattr(plan, "obs_capture_budget", 4))
        trace_on = bool(getattr(plan, "trace", True))
    else:
        # config key wins over env, and a malformed value DEGRADES
        # with a warning — telemetry knobs must never kill an attempt
        # (the ELASTIC_N_DEVICES convention)
        cap_on = _truthy(_knob("OBS_CAPTURE", config, "1"))
        raw = _knob("OBS_CAPTURE_BUDGET", config, "4")
        try:
            budget = int(raw)
        except (TypeError, ValueError):
            logger.warning("OBS_CAPTURE_BUDGET=%r is not an int; "
                           "using 4", raw)
            budget = 4
        trace_on = _truthy(_knob("TRACE", config, "1"))
    run = ObsRun(obs_dir, run_id=run_id, attempt=attempt, rank=rank,
                 slice_index=slice_index, plan_fingerprint=fp,
                 trace=trace_on)
    if cap_on:
        from gke_ray_train_tpu.obs.capture import CaptureManager
        capture = CaptureManager(obs_dir, emit_fn=run.emit,
                                 registry=run.registry, budget=budget)
        run.capture = capture
    _active = run
    logger.info("obs: events -> %s (run %s attempt %d rank %s%s)",
                run.events.path, run_id, attempt, rank,
                f" slice {slice_index}" if slice_index is not None
                else "")
    return run


def end_attempt(status: str = "ok") -> None:
    """Seal the active worker session (idempotent) and drop the log
    prefix — outside an attempt there is no run context to stamp."""
    global _active
    from gke_ray_train_tpu.logging_utils import clear_run_logging
    clear_run_logging()
    if _active is not None:
        run, _active = _active, None
        try:
            from gke_ray_train_tpu.rayint.context import get_context
            ledger = get_context().goodput
        except Exception:  # noqa: BLE001
            ledger = None
        run.finish(status, ledger)


def _rank_slice(rank: int, config: Optional[dict]) -> Optional[int]:
    """Rank -> slice index through the one contract function
    (parallel/mesh.py). None when no slice identity exists (single
    slice, or a non-tiling layout)."""
    try:
        num_slices = int((config or {}).get(
            "NUM_SLICES", os.environ.get("NUM_SLICES", "1")))
        n = int(os.environ.get("NUM_PROCESSES", "1"))
        if num_slices <= 1 or n <= 1:
            return None
        from gke_ray_train_tpu.parallel.mesh import slice_assignments
        assign = slice_assignments(list(range(n)), num_slices)
        return assign[rank] if len(set(assign)) > 1 else None
    except Exception:  # noqa: BLE001 - identity is best-effort
        return None


# ---------------------------------------------------------------------------
# driver side (rayint/trainer.py fit loop)
# ---------------------------------------------------------------------------

class DriverObs:
    """Run-scoped driver session: the ``attempt_end`` / ``run_end``
    reconciliation stream plus the supervisor heartbeat export — and,
    with tracing on, the span skeleton the worker spans hang off: one
    ``run`` root span and one ``attempt`` span per attempt, whose id
    is forwarded to the workers as ``OBS_PARENT_SPAN``."""

    def __init__(self, obs_dir: str, run_id: str, trace: bool = True):
        self.obs_dir = obs_dir
        self.run_id = run_id
        self.events = EventLog(events_path(obs_dir, "driver"),
                               run_id=run_id, attempt=0, rank="driver")
        self.spans: Optional[SpanLog] = None
        self.run_span_id: Optional[str] = None
        self.attempt_span_id: Optional[str] = None
        self._run_t0 = time.time()
        self._attempt_t0: Optional[float] = None
        self._run_status: Optional[str] = None
        if trace:
            self.spans = SpanLog(spans_path(obs_dir, "driver"),
                                 run_id=run_id, attempt=0, rank="driver")
            self.run_span_id = new_span_id()

    def begin_attempt(self, attempt: int) -> Optional[str]:
        """Mint (and remember) the span id for the attempt ABOUT TO
        run — the trainer stamps it into every worker's env before the
        workers launch; the span itself lands at ``note_attempt``."""
        if self.spans is None:
            return None
        self.attempt_span_id = new_span_id()
        self._attempt_t0 = time.time()
        self.spans.attempt = int(attempt)
        return self.attempt_span_id

    def note_attempt(self, attempt: int, entry: Dict[str, Any],
                     plan_fingerprint: Optional[str] = None) -> None:
        self.events.attempt = int(attempt)
        self.events.plan_fingerprint = (
            entry.get("plan_fingerprint") or plan_fingerprint)
        self.events.emit(
            "attempt_end", step=entry.get("step"),
            status=entry.get("status"), goodput=entry.get("goodput"),
            event=entry.get("event"), pool=entry.get("pool"),
            error=entry.get("error"),
            resumed_step=entry.get("resumed_step"),
            ckpt_save_s=entry.get("ckpt_save_s"))
        if self.spans is not None and self.attempt_span_id is not None:
            now = time.time()
            t0 = self._attempt_t0 if self._attempt_t0 is not None else now
            self.spans.emit("attempt", now - t0, t1=now,
                            span_id=self.attempt_span_id,
                            parent_id=self.run_span_id,
                            status=entry.get("status"))
            self.attempt_span_id = None
            self._attempt_t0 = None

    def note_run_end(self, result) -> None:
        self._run_status = result.status
        self.events.emit("run_end", status=result.status,
                         attempts=result.attempts,
                         preemptions=result.preemptions,
                         goodput=result.goodput)

    def note_stall(self, stalled, timeout_s: float,
                   attempt: Optional[int] = None) -> None:
        if attempt is not None:
            # stamp the attempt that stalled — note_attempt for it has
            # not run yet, so the log still carries the previous one
            self.events.attempt = int(attempt)
        self.events.emit("stall", stalled=[list(s) for s in stalled],
                         timeout_s=timeout_s)
        self.events.emit("anomaly", **{"class": "stalled_rank"},
                         detail={"stalled": [list(s) for s in stalled]},
                         trigger_step=max((s[1] for s in stalled),
                                          default=-1))

    def export_supervisor(self, view: Dict[str, Any]) -> None:
        """HeartbeatBoard.metrics_view() -> <obs_dir>/supervisor.json
        (atomic) — the per-rank last-beat-age/slice/step export both
        the scraper and ``obs report`` consume."""
        import json
        try:
            os.makedirs(self.obs_dir, exist_ok=True)
            path = os.path.join(self.obs_dir, "supervisor.json")
            tmp = path + ".tmp"
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump({"ts": time.time(), "run_id": self.run_id,
                           **view}, f, indent=1, sort_keys=True)
            os.replace(tmp, path)
        except Exception as e:  # noqa: BLE001
            logger.warning("supervisor export failed: %s", e)

    def close(self) -> None:
        if self.spans is not None:
            now = time.time()
            try:
                self.spans.attempt = 0
                self.spans.emit("run", now - self._run_t0, t1=now,
                                span_id=self.run_span_id,
                                status=self._run_status)
            except Exception as e:  # noqa: BLE001 - IO best-effort
                logger.warning("obs run span dropped: %s", e)
            self.spans.close()
        self.events.close()


_minted_ids: set = set()


def start_driver(config: Optional[dict] = None,
                 obs_dir: Optional[str] = None) -> Optional[DriverObs]:
    """Driver session for one ``fit()``; mints and exports the shared
    run id so every worker stamps the same one. An id minted by a
    PREVIOUS fit in this process is stale — each fit is its own run —
    but an externally supplied OBS_RUN_ID (a job-level env) is kept."""
    run_id = os.environ.get(RUN_ID_ENV)
    if not run_id or run_id in _minted_ids:
        run_id = new_run_id()
        _minted_ids.add(run_id)
        os.environ[RUN_ID_ENV] = run_id
    obs_dir = obs_dir or resolve_obs_dir(None, config)
    if obs_dir is None:
        return None
    return DriverObs(obs_dir, run_id,
                     trace=_truthy(_knob("TRACE", config, "1")))

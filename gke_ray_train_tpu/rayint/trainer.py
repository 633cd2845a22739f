"""JaxTrainer — Ray Train style orchestration for JAX-on-TPU workers.

API parity with the reference's driver blocks
(ray-jobs/fine_tune_llama_ray.py:445-457, pytorch_llm_ray.py:346-376):
``JaxTrainer(train_loop_per_worker, train_loop_config, scaling_config,
run_config).fit() → Result(metrics)``. Differences, by design
(SURVEY.md row D1):

- One worker per TPU *host* (``resources_per_worker={"TPU": chips}``),
  not per accelerator: a single JAX process drives all local chips.
- Instead of MASTER_ADDR/PORT + NCCL process groups, the trainer elects
  worker 0's node as the JAX coordinator and injects
  COORDINATOR_ADDRESS/NUM_PROCESSES/PROCESS_ID; workers then call
  ``parallel.mesh.distributed_init`` (SURVEY.md row D2/§5.8).
- ``FailureConfig(max_failures=N)`` is actually wired (the reference
  never configures it, §5.3); retried workers resume from the latest
  orbax checkpoint because every entry script restores-if-present.

Fault-tolerance model (one PR, three failure classes):

- **Genuine failures** (crash, InjectedKill, heartbeat/worker timeout)
  consume the ``max_failures`` budget and retry with exponential
  backoff + jitter.
- **Preemptions** (SIGTERM → ``train/preempt.py`` → the loop
  checkpoints and raises ``Preempted``) do NOT consume ``max_failures``
  — a spot eviction is not the job's fault — and are bounded by
  ``FailureConfig.max_preemptions`` instead.
- **Non-retryable errors** (KeyError/ValueError/TypeError/... — a
  config typo fails identically every attempt) fail fast on the first
  attempt with the original traceback in the log.

Liveness is supervised at step granularity when
``RunConfig.heartbeat_timeout_s`` is set (``rayint/supervisor.py``):
workers report per-step heartbeats, and a rank with no step progress
for that long is killed BY NAME — versus ``worker_timeout_s``, which
only bounds the whole attempt's wall clock.

Ray is optional at import time: with no Ray installed (or
``use_ray=False``) the trainer degrades to a single in-process worker —
that is also the unit-test path.
"""

from __future__ import annotations

import dataclasses
import logging
import os
import random
import time
from typing import Any, Callable, Dict, Optional

logger = logging.getLogger(__name__)

try:  # pragma: no cover - exercised only on clusters with Ray installed
    import ray
    _HAS_RAY = True
except ImportError:
    ray = None
    _HAS_RAY = False

DEFAULT_COORDINATOR_PORT = 8476  # fallback when port discovery fails

# deterministic errors: retrying replays the identical failure N times
# and buries the real traceback under repetition. Matched by type AND
# name (Ray's serialized task errors may rebuild exception instances).
NONRETRYABLE_TYPES = (KeyError, ValueError, TypeError, AttributeError,
                      ImportError, NotImplementedError)
_NONRETRYABLE_NAMES = frozenset(t.__name__ for t in NONRETRYABLE_TYPES) | {
    "ModuleNotFoundError",
    # shardlint runtime-guard violations (analysis/guards.py) are
    # deterministic by construction — divergent traces and shape-churn
    # recompiles replay identically every attempt, and the guards'
    # contract is FAIL FAST with the diagnosis on top, not buried
    # under max_failures retries
    "GuardViolation", "HloDivergenceError", "RecompileLimitExceeded"}
# explicitly-retryable markers override the type match: a collective
# checkpoint-restore failure is often a ValueError underneath
# (orbax/tensorstore), but a fresh attempt re-reads storage
_RETRYABLE_NAMES = frozenset({"CheckpointRestoreError"})


@dataclasses.dataclass
class ScalingConfig:
    """ScalingConfig parity (fine_tune_llama_ray.py:445-449) with TPU
    resources instead of {"GPU": 1}."""
    num_workers: int = 1
    resources_per_worker: Dict[str, float] = dataclasses.field(
        default_factory=lambda: {"TPU": 4})
    placement_strategy: str = "SPREAD"

    @staticmethod
    def from_env() -> "ScalingConfig":
        """World shape from env — NUM_HOSTS/CHIPS_PER_HOST, the TPU
        analogues of NUM_NODES/NUM_GPUS_PER_NODE
        (fine_tune_llama_ray.py:439-441, SURVEY.md §5.6)."""
        hosts = int(os.environ.get("NUM_HOSTS",
                                   os.environ.get("NUM_NODES", "1")))
        chips = int(os.environ.get("CHIPS_PER_HOST",
                                   os.environ.get("NUM_GPUS_PER_NODE", "4")))
        return ScalingConfig(num_workers=hosts,
                             resources_per_worker={"TPU": chips})


@dataclasses.dataclass
class FailureConfig:
    # genuine-failure retry budget (crashes, hangs, timeouts)
    max_failures: int = 0
    # separate budget for spot/preemptible evictions: a preemption
    # checkpoints within its grace window and resumes, so it must not
    # burn a max_failures slot — but unbounded preemption churn on a
    # doomed node pool still needs a stop
    max_preemptions: int = 8


@dataclasses.dataclass
class RunConfig:
    name: str = "jax-train"
    storage_path: Optional[str] = None
    failure_config: FailureConfig = dataclasses.field(
        default_factory=FailureConfig)
    # elastic mesh re-formation (rayint/elastic.py): when a preemption
    # or failure's post-mortem shows the device pool changed (slice
    # eviction, spot shrink, node return), the next attempt re-resolves
    # the ExecutionPlan on the survivors (plan.replan), re-forms the
    # mesh, and restores resharded — instead of burning the retry
    # budget waiting for the old topology. None = $ELASTIC (default
    # off: a non-elastic job keeps the wait-for-identical behavior).
    elastic: Optional[bool] = None
    # the smallest pool worth re-forming on; below it the run fails
    # with a clear error instead of limping. None = $MIN_DEVICES or 1.
    min_devices: Optional[int] = None
    # Hang detection (SURVEY.md §5.3): with no bound, one wedged worker
    # (deadlocked collective, dead TPU host) blocks ray.get forever and
    # FailureConfig never gets its chance. When set, an attempt that
    # exceeds this wall-clock kills every worker and counts as a
    # failure, so retry-with-resume proceeds. None = wait forever (the
    # default: legitimate training runs have no universal time bound).
    worker_timeout_s: Optional[float] = None
    # Step-granular liveness (rayint/supervisor.py): kill the attempt —
    # naming the stalled rank — when a worker reports no step progress
    # for this long. Orthogonal to worker_timeout_s: this bounds the
    # gap BETWEEN steps, not the run. None = no heartbeat supervision.
    heartbeat_timeout_s: Optional[float] = None
    # base of the exponential backoff between genuine-failure retries
    # (delay = base * 2^(failures-1), capped at 60s, x jitter in
    # [0.5, 1.5)). None = $RETRY_BACKOFF_S or 1.0. Preemptions resume
    # immediately — their checkpoint is already durable.
    retry_backoff_s: Optional[float] = None


@dataclasses.dataclass
class Result:
    metrics: Dict[str, Any]
    error: Optional[str] = None
    # per-worker metrics (worker 0 first); `metrics` is worker 0's view,
    # matching Ray Train's rank-0 convention, but nothing is dropped
    worker_metrics: Optional[list] = None
    # attempt metadata: "ok" | "failed" | "preempted" (budget exhausted)
    status: str = "ok"
    attempts: int = 1
    preemptions: int = 0
    # one dict per attempt: {"status", "error"?, "step"?, "resumed_step"?,
    # "ckpt_save_s"?, "nonretryable"?, "goodput" (the per-attempt
    # ledger, train/metrics.py LEDGER_TERMS + wall_s), "event"?
    # ("shrink"|"grow" on elastic pool changes), "pool"? (surviving
    # device count), "plan_fingerprint"?}
    attempt_log: list = dataclasses.field(default_factory=list)
    # summed goodput ledger across every attempt: LEDGER_TERMS +
    # "wall_s" + the headline "goodput_frac" (= step_s / wall_s) —
    # terms reconcile to wall-clock by construction (tests assert it)
    goodput: dict = dataclasses.field(default_factory=dict)


def _cause_chain(e: BaseException):
    """Walk explicit causes only (ray's .cause / raise-from __cause__) —
    __context__ drags in unrelated already-handled exceptions."""
    seen = set()
    while e is not None and id(e) not in seen:
        seen.add(id(e))
        yield e
        e = getattr(e, "cause", None) or e.__cause__


def _find_preempted(e: BaseException):
    from gke_ray_train_tpu.train.preempt import Preempted
    for x in _cause_chain(e):
        if isinstance(x, Preempted) or type(x).__name__ == "Preempted":
            return x
    return None


def _is_nonretryable(e: BaseException) -> bool:
    for x in _cause_chain(e):
        # walked outermost-in: a retryable wrapper vouches for whatever
        # deterministic-looking cause sits beneath it
        if type(x).__name__ in _RETRYABLE_NAMES:
            return False
        if isinstance(x, NONRETRYABLE_TYPES) \
                or type(x).__name__ in _NONRETRYABLE_NAMES:
            return True
    return False


def _maybe_ingest_observed(obs, plan, config: dict) -> None:
    """Attempt-end feedback hook (autotune/registry.py): rank 0 of an
    AUTOTUNE=1 + obs-active attempt ingests its own observed rows back
    into the tuned-plan registry, so calibration data and drift alarms
    accumulate from real runs without separate tooling. Never fatal —
    a broken registry must not turn a finished attempt into a failure
    — and each row is refused on fingerprint/chip/backend drift
    exactly like ``apply``. AUTOTUNE_INGEST=0 opts out."""
    if obs is None or plan is None:
        return
    if not (getattr(plan, "autotune", False)
            and getattr(plan, "autotune_ingest", True)):
        return
    if str(getattr(obs, "rank", None)) != "0":
        return                     # one writer per attempt, like apply
    try:
        from gke_ray_train_tpu.autotune.registry import (
            entry_key, ingest_observed, model_digest, registry_dir)
        # map THIS attempt's runtime fingerprint onto its registry arm:
        # the runtime plan fingerprint covers operational fields the
        # search-time base/winner fingerprints don't, so the entry's
        # own arm map would never match it
        arms = {}
        key = getattr(plan, "_tuned_key", None)
        arm = "tuned"
        if key is None:
            arm = "base"
            from gke_ray_train_tpu.analysis.plancheck import (
                model_config_for)
            model_cfg = model_config_for(dict(config or {}), plan)
            if model_cfg is not None:
                key = entry_key(model_digest(model_cfg), plan.topology,
                                "train")
        if key is not None:
            arms[plan.fingerprint()] = (key, arm)
        summary = ingest_observed(
            obs.obs_dir, directory=registry_dir(config), config=config,
            runtime_arms=arms, log=logger)
        if summary["matched"] or summary["refusals"] or summary["drift"]:
            logger.info(
                "autotune ingest: %d observed row(s) matched, "
                "%d refusal(s), %d drift verdict(s) under %s",
                summary["matched"], len(summary["refusals"]),
                len(summary["drift"]), summary["directory"])
    except Exception as e:  # noqa: BLE001 - feedback must never be fatal
        logger.warning("autotune ingest hook skipped: %s", e)


def _run_worker(fn: Callable, config: dict, env: Dict[str, str],
                beat_fn: Optional[Callable] = None) -> dict:
    """Returns {"metrics", "resumed_step", "goodput",
    "plan_fingerprint"} — attempt metadata rides the payload because on
    the Ray path the worker context lives in another process and the
    driver could not read it otherwise."""
    os.environ.update(env)
    from gke_ray_train_tpu.analysis.guards import (
        install_recompile_limit, uninstall_recompile_limit)
    from gke_ray_train_tpu.obs import runtime as obs_runtime
    from gke_ray_train_tpu.perf.cache import (
        enable_persistent_cache, log_cache_summary)
    from gke_ray_train_tpu.plan import ExecutionPlan, PlanError
    from gke_ray_train_tpu.rayint.context import get_context
    from gke_ray_train_tpu.train import preempt
    # the worker's declarative ExecutionPlan (plan.py): resolved from
    # the same config+env the loop fn will read, logged up front so
    # every attempt states the plan identity it runs under. Purely
    # static — no backend is touched before distributed_init. Under an
    # elastic pool override the plan is re-resolved on the survivors
    # (the entry/worker fn does the same via rayint/elastic.py), so the
    # logged identity matches what the attempt actually compiles.
    # snapshot the tuned-overlay env keys BEFORE maybe_apply below can
    # export an entry's flash blocks — the finally must restore the
    # PRE-attempt values, or a dropped overlay's env leaks into a later
    # in-process attempt that runs untuned (attempt-scoped, like the
    # KERNELCHECK export further down)
    from gke_ray_train_tpu.autotune.space import ENV_OVERRIDE_KEYS
    prev_overrides = {k: os.environ.get(k) for k in ENV_OVERRIDE_KEYS}
    plan = None
    try:
        plan = ExecutionPlan.resolve(config)
        pool = os.environ.get("ELASTIC_N_DEVICES")
        try:
            pool_n = int(pool) if pool else None
        except ValueError:
            # same degrade as elastic_devices(): a malformed override
            # must not kill the attempt (and burn a failure slot)
            logger.warning("ELASTIC_N_DEVICES=%r is not an int; "
                           "ignoring the pool override", pool)
            pool_n = None
        if pool_n and pool_n != plan.chips:
            from gke_ray_train_tpu.plan import replan
            plan = replan(plan, pool_n)
        # tuned-plan overlay (autotune/registry.py): AFTER the replan,
        # so the registry lookup keys on the topology this attempt
        # actually runs — a reshard re-keys (usually a miss) instead of
        # a stale 8-device tune riding a 4-device attempt. Loud apply,
        # loud refusal.
        if plan.autotune:
            from gke_ray_train_tpu.autotune.registry import maybe_apply
            plan, _ = maybe_apply(plan, config=config, log=logger)
        logger.info("execution plan %s (topology %s)",
                    plan.fingerprint(), plan.topology)
    except PlanError as e:
        # a config in a non-flat dialect (the pretrain driver refines
        # its plan in the entry) must not kill the attempt here
        logger.warning("worker-level plan resolution failed (%s); the "
                       "entry's own plan still applies", e)
    # attempt-scoped obs session (obs/runtime.py): per-rank event
    # stream + metrics registry + anomaly captures into the run's obs
    # dir, and the run_id/attempt/rank prefix on every text log line.
    # No-op (None) when obs is off or no dir resolves — the bare test
    # path stays telemetry-free.
    obs = obs_runtime.start_attempt(plan=plan, config=config)
    if obs is not None:
        obs.emit("attempt_start",
                 topology=plan.topology if plan is not None else None,
                 n_devices=plan.chips if plan is not None else None,
                 pool=os.environ.get("ELASTIC_N_DEVICES"))
    # compile-once across restarts: every attempt (and every retry of a
    # preempted worker) reuses the persistent XLA cache instead of
    # paying a full recompile. Config-only — the backend must not
    # initialize before distributed_init.
    enable_persistent_cache(plan=plan)
    # KERNELCHECK (config key wins over env, like every knob): export
    # the resolved value so run_training's attempt-start probe sees it
    # — the probe itself runs THERE, after distributed_init, because
    # verifying a kernel computes and the backend must not initialize
    # here in a multi-host worker. Scoped to the attempt (restored in
    # the finally below): in-process fits must not inherit a previous
    # config's setting through the process env.
    prev_kernelcheck = os.environ.get("KERNELCHECK")
    if "KERNELCHECK" in config:
        os.environ["KERNELCHECK"] = str(config["KERNELCHECK"])
    ctx = get_context()
    ctx.resumed_step = None      # fresh attempt, fresh metadata
    ctx.goodput = None
    ctx.plan_fingerprint = plan.fingerprint() if plan is not None else None
    ctx.set_heartbeat_sink(beat_fn)
    preempt.reset()              # a retry must not inherit the previous
    preempt.install()            # attempt's preemption flag
    try:
        # RECOMPILE_LIMIT teeth (analysis/guards.py): armed per attempt
        # so the count starts fresh on every retry — shape/dtype/
        # sharding churn past the limit raises from the compile path,
        # naming the function and the signature diff. Armed INSIDE the
        # try: the finally below must disarm it on every failure path,
        # or a raising log handler outlives the attempt
        install_recompile_limit(config=config)
        ret = fn(config)
        reported = ctx.last_reported
        return {"metrics": ret if ret is not None else (reported or {}),
                "resumed_step": ctx.resumed_step,
                "goodput": ctx.goodput,
                "plan_fingerprint": ctx.plan_fingerprint}
    finally:
        # seal the attempt's obs session on every path: worker_exit
        # event (with the ledger the loop parked on the context), final
        # metric export, stream closed — BEFORE the mesh teardown below
        import sys as _sys
        _exc = _sys.exc_info()[1]
        obs_runtime.end_attempt(
            "ok" if _exc is None else
            ("preempted" if _find_preempted(_exc) is not None
             else "failed"))
        # the sealed obs dir now holds this attempt's measured rows —
        # feed them back to the registry (rank 0, AUTOTUNE=1, never
        # fatal; AUTOTUNE_INGEST=0 opts out)
        _maybe_ingest_observed(obs, plan, config)
        # one line of compile-cache health per attempt: a warm restart
        # should show hits ≈ compile count and seconds saved
        log_cache_summary(logger)
        # a finished (or failed — its error surfaces via the future)
        # worker must never be reported as stalled
        ctx.heartbeat_done()
        uninstall_recompile_limit()
        # mesh teardown: the attempt's mesh dies with the attempt, and
        # the replicated-generate cache is the one thing that would
        # keep its device buffers alive across retries. sys.modules
        # guard, NOT an import: the cache can only be non-empty if
        # inference was already imported, and a fresh import inside
        # this finally could raise over the attempt's REAL error
        import sys
        inf_mod = sys.modules.get("gke_ray_train_tpu.inference")
        if inf_mod is not None:
            inf_mod.clear_generate_cache()
        # restore the default SIGTERM disposition: outside an attempt
        # nothing reads the preemption flag, and a long-lived driver
        # process must not silently swallow termination
        preempt.uninstall()
        # the attempt-scoped KERNELCHECK export (above) must not leak
        # into a later in-process fit whose config omits the key
        if "KERNELCHECK" in config:
            if prev_kernelcheck is None:
                os.environ.pop("KERNELCHECK", None)
            else:
                os.environ["KERNELCHECK"] = prev_kernelcheck
        for k, prev in prev_overrides.items():
            if prev is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = prev


class JaxTrainer:
    def __init__(self, train_loop_per_worker: Callable, *,
                 train_loop_config: Optional[dict] = None,
                 scaling_config: Optional[ScalingConfig] = None,
                 run_config: Optional[RunConfig] = None,
                 use_ray: Optional[bool] = None):
        self.fn = train_loop_per_worker
        # copied: env-derived injections below must not leak into the
        # caller's dict (it may be reused or serialized as a job spec)
        self.config = dict(train_loop_config or {})
        # input-pipeline knob threaded through config so `ray job submit
        # --env PREFETCH_BATCHES=N` tunes the async prefetch depth
        # (data/prefetch.py) without editing the job JSON; an explicit
        # config value always wins over the driver env
        if "PREFETCH_BATCHES" in os.environ and \
                "PREFETCH_BATCHES" not in self.config:
            self.config["PREFETCH_BATCHES"] = \
                int(os.environ["PREFETCH_BATCHES"])
        self.scaling = scaling_config or ScalingConfig()
        self.run_config = run_config or RunConfig()
        self.use_ray = (_HAS_RAY and self.scaling.num_workers >= 1
                        if use_ray is None else use_ray)
        # surviving device count of the last elastic pool change; when
        # set, every subsequent attempt's workers see it as
        # ELASTIC_N_DEVICES and re-form their mesh on it
        self._pool_override: Optional[int] = None
        # obs identity: fit() mints one OBS_RUN_ID per run and stamps
        # OBS_ATTEMPT per attempt into every worker's env, so all
        # ranks of all attempts correlate into one stream
        self._attempt = 0
        self._obs = None

    # -- elastic knobs -------------------------------------------------
    def _elastic(self) -> bool:
        if self.run_config.elastic is not None:
            return bool(self.run_config.elastic)
        from gke_ray_train_tpu.rayint.elastic import elastic_enabled
        return elastic_enabled(self.config)

    def _min_devices(self) -> int:
        if self.run_config.min_devices is not None:
            return max(int(self.run_config.min_devices), 1)
        from gke_ray_train_tpu.rayint.elastic import min_devices
        return min_devices(self.config)

    def _pool_env(self) -> Dict[str, str]:
        """Per-attempt worker env: the elastic pool override plus the
        obs run/attempt identity stamps."""
        env: Dict[str, str] = {}
        if self._obs is not None:
            # both worker paths route through _run_worker, whose first
            # action is os.environ.update(env) — one write site
            env["OBS_RUN_ID"] = self._obs.run_id
            env["OBS_ATTEMPT"] = str(self._attempt or 1)
            if self._obs.attempt_span_id is not None:
                # trace context (obs/trace.py): the driver's attempt
                # span is the causal parent of every worker attempt
                # span this attempt spawns
                from gke_ray_train_tpu.obs.runtime import PARENT_SPAN_ENV
                env[PARENT_SPAN_ENV] = self._obs.attempt_span_id
        if self._obs is None or self._obs.attempt_span_id is None:
            # local path shares os.environ across fits — a stale parent
            # from a previous traced fit must not adopt this attempt
            from gke_ray_train_tpu.obs.runtime import PARENT_SPAN_ENV
            os.environ.pop(PARENT_SPAN_ENV, None)
        # a RunConfig(elastic=True) opt-in must reach the worker-side
        # gate too (rayint/elastic.py reads config/env only) — else the
        # driver arms the override and the workers refuse to replan
        if self.run_config.elastic:
            env["ELASTIC"] = "1"
        if self._pool_override is not None:
            env["ELASTIC_N_DEVICES"] = str(self._pool_override)
            return env
        # local path shares os.environ across attempts — a cleared
        # override must not leave a stale pool behind
        os.environ.pop("ELASTIC_N_DEVICES", None)
        return env

    def _probe_pool(self) -> Optional[int]:
        """Post-mortem device-pool probe for failures whose exception
        carried no pool notice: the fault registry's emulated pool
        in-process (the CPU drill), best-effort and None elsewhere —
        a graceful pool change always arrives on Preempted.pool."""
        try:
            from gke_ray_train_tpu.testing.faults import current_pool
            return current_pool()
        except Exception:  # noqa: BLE001 - probe is best-effort
            return None

    # -- local ---------------------------------------------------------
    def _fit_local(self) -> tuple:
        from gke_ray_train_tpu.rayint.context import get_context
        from gke_ray_train_tpu.rayint.supervisor import (
            HeartbeatBoard, HeartbeatTimeout, Watchdog)
        env = {"NUM_PROCESSES": "1", "PROCESS_ID": "0",
               **self._pool_env()}
        hb = self.run_config.heartbeat_timeout_s
        board = HeartbeatBoard() if hb else None

        def _stall_capture(stalled):
            # obs stalled-rank anomaly (obs/capture.py): a best-effort
            # trace of whatever the device is doing RIGHT NOW, taken on
            # the watchdog thread before the wedged main thread is
            # interrupted — the only moment that trace can exist
            from gke_ray_train_tpu.obs import runtime as obs_runtime
            run = obs_runtime.active()
            if run is not None and run.capture is not None:
                run.capture.note_stalled_rank(
                    {"stalled": [list(s) for s in stalled],
                     "step": max((s[1] for s in stalled), default=-1)})

        wd = Watchdog(board, hb,
                      pre_interrupt=_stall_capture).start() if hb else None
        # the outer try also covers the cleanup and the return: a
        # watchdog SIGINT raised while the finally runs (worker finished
        # in the detection race window) must still be translated, not
        # escape fit() as a raw KeyboardInterrupt
        try:
            try:
                out = _run_worker(self.fn, self.config, env,
                                  beat_fn=board.beat if board else None)
            finally:
                if wd is not None:
                    wd.stop()
                if board is not None and self._obs is not None:
                    self._obs.export_supervisor(board.metrics_view(hb))
                get_context().set_heartbeat_sink(None)
            return Result(metrics=out["metrics"]), out
        except KeyboardInterrupt:
            # the watchdog interrupts the main thread on stall (the only
            # way to pry a single process out of a wedged collective);
            # translate it — a real Ctrl-C (no stall recorded) re-raises
            if wd is not None and wd.stalled_info:
                raise HeartbeatTimeout(wd.stalled_info, hb) from None
            raise

    # -- ray ----------------------------------------------------------
    @staticmethod
    def _kill_workers(workers) -> None:
        for w in workers:
            try:
                ray.kill(w)
            except Exception:  # noqa: BLE001
                pass

    @staticmethod
    def _get_result(future, rank: int, ips: list):
        """ray.get with per-rank error attribution: a worker exception
        re-raises naming the failing rank and its node IP ("a worker
        died" is undebuggable on a slice); Preempted passes through
        untouched for fit()'s classification."""
        try:
            return ray.get(future)
        except Exception as e:  # noqa: BLE001
            if _find_preempted(e) is not None:
                raise
            cause = getattr(e, "cause", None) or e.__cause__ or e
            raise RuntimeError(
                f"worker rank {rank} (node {ips[rank]}) failed: "
                f"{type(cause).__name__}: {cause}") from e

    def _fit_ray(self) -> tuple:
        if not ray.is_initialized():
            ray.init(address=os.environ.get("RAY_ADDRESS", "auto"))
        n = self.scaling.num_workers
        resources = dict(self.scaling.resources_per_worker)
        num_cpus = resources.pop("CPU", 1)

        @ray.remote(max_restarts=0)
        class Worker:
            def node_ip(self):
                return ray.util.get_node_ip_address()

            def free_port(self):
                # a port that is free NOW on the coordinator node; the
                # coordinator binds it moments later (standard
                # bind-0-release discovery, replaces the fixed 8476 that
                # collides on shared nodes)
                import socket
                s = socket.socket()
                s.bind(("", 0))
                port = s.getsockname()[1]
                s.close()
                return port

            def run(self, fn, config, env, supervisor=None):
                beat = None
                if supervisor is not None:
                    def beat(rank, step, done):
                        # fire-and-forget: the worker never blocks on
                        # its own liveness report
                        supervisor.beat.remote(rank, step, done)
                return _run_worker(fn, config, env, beat_fn=beat)

        hb_timeout = self.run_config.heartbeat_timeout_s
        # slice identity (rank → slice, the slice_index contract): with
        # NUM_SLICES declared, contiguous worker blocks form slices —
        # the same layout parallel/mesh.py emulates — so a stall/loss
        # confined to one slice is reported (and classified) as a
        # slice-scoped event, not an anonymous whole-job failure
        try:
            num_slices = int(self.config.get(
                "NUM_SLICES", os.environ.get("NUM_SLICES", "1")))
        except (TypeError, ValueError):
            num_slices = 1
        # rank → slice through the ONE contract function (its non-
        # tiling fallback collapses to a single domain, which carries
        # no slice-scoping information — treat it as no slice identity)
        from gke_ray_train_tpu.parallel.mesh import slice_assignments
        assign = slice_assignments(list(range(n)), num_slices)
        slice_map = (dict(enumerate(assign))
                     if len(set(assign)) > 1 else None)
        supervisor = None
        if hb_timeout:
            from gke_ray_train_tpu.rayint.supervisor import (
                HeartbeatTimeout, Supervisor)
            # tiny bookkeeping actor; released with its handle at return
            supervisor = ray.remote(Supervisor).options(num_cpus=0).remote()
            if slice_map:
                supervisor.set_slices.remote(slice_map)

        # honor placement_strategy: one bundle per worker, SPREAD puts
        # each TPU worker on its own host (the declared-but-unused
        # strategy from round 1)
        pg = ray.util.placement_group(
            [dict(resources, CPU=num_cpus) for _ in range(n)],
            strategy=self.scaling.placement_strategy)
        try:
            ray.get(pg.ready())
            try:
                from ray.util.scheduling_strategies import (
                    PlacementGroupSchedulingStrategy)

                def sched(i):
                    return PlacementGroupSchedulingStrategy(
                        placement_group=pg, placement_group_bundle_index=i)
            except ImportError:  # very old ray: best-effort scheduling
                def sched(i):
                    return None

            workers = [
                Worker.options(resources=resources, num_cpus=num_cpus,
                               scheduling_strategy=sched(i)).remote()
                for i in range(n)]
            # all node IPs up front: worker 0's elects the coordinator,
            # the rest name the failing host in errors
            ips = ray.get([w.node_ip.remote() for w in workers])
            coord_ip = ips[0]
            coord_port = None
            for port_try in range(3):
                try:
                    coord_port = int(ray.get(workers[0].free_port.remote()))
                    break
                except Exception as e:  # noqa: BLE001 - transient RPC/bind
                    logger.warning(
                        "coordinator port discovery attempt %d/3 failed "
                        "(%s: %s); retrying", port_try + 1,
                        type(e).__name__, e)
                    time.sleep(0.2 * (2 ** port_try))
            if coord_port is None:
                coord_port = DEFAULT_COORDINATOR_PORT
                logger.error(
                    "coordinator port discovery failed after 3 attempts; "
                    "FALLING BACK to fixed port %d — this COLLIDES when "
                    "another job's coordinator shares the node",
                    DEFAULT_COORDINATOR_PORT)
            env_base = {
                "COORDINATOR_ADDRESS": f"{coord_ip}:{coord_port}",
                "NUM_PROCESSES": str(n),
            }
            # plan-scoped knobs ride to the workers explicitly — a
            # driver-side `env COMPILE_CACHE_DIR=...` or `env
            # TRANSFER_GUARD=disallow` must shape the workers even
            # without a Ray runtime-env entry. The key list is DERIVED
            # from the ExecutionPlan's config-key mapping (plan.py), so
            # a renamed knob cannot silently stop being forwarded.
            from gke_ray_train_tpu.plan import ENV_FORWARD_KEYS
            env_base.update({k: os.environ[k] for k in ENV_FORWARD_KEYS
                             if k in os.environ})
            # elastic + autotune-registry knobs + the per-attempt pool
            # override ride to the workers the same way (AUTOTUNE
            # itself is plan-scoped and already in ENV_FORWARD_KEYS;
            # the registry DIR is operational like KERNELCHECK)
            env_base.update({k: os.environ[k]
                             for k in ("ELASTIC", "MIN_DEVICES",
                                       "NUM_SLICES", "KERNELCHECK",
                                       "AUTOTUNE_DIR",
                                       "AUTOTUNE_DRIFT_BAND",
                                       "ASYNC_CKPT", "PEER_REPLICATION",
                                       "CKPT_COMMIT_TIMEOUT_S",
                                       "CKPT_STORAGE_DELAY_S")
                             if k in os.environ})
            env_base.update(self._pool_env())
            futures = [
                w.run.remote(self.fn, self.config,
                             {**env_base, "PROCESS_ID": str(i)}, supervisor)
                for i, w in enumerate(workers)]
            timeout = self.run_config.worker_timeout_s
            if timeout is not None or supervisor is not None:
                # supervised wait: poll for completion while checking
                # (a) step-granular heartbeat stalls — a wedged
                # collective or dead host is caught HEARTBEAT_TIMEOUT_S
                # after its last step, named by rank — and (b) the
                # whole-attempt wall-clock bound. Either kills every
                # worker and raises into the retry loop (workers resume
                # from the latest checkpoint).
                deadline = (time.monotonic() + timeout
                            if timeout is not None else None)
                # timeout=0 means "expire immediately", not "no bound" —
                # it must not reach min() as an empty candidate set
                slices = [t / 4.0 for t in (timeout, hb_timeout)
                          if t is not None and t > 0]
                poll_s = max(0.005, min(min(slices, default=5.0), 5.0))
                while True:
                    done, pending = ray.wait(futures,
                                             num_returns=len(futures),
                                             timeout=poll_s)
                    if not pending:
                        break
                    # a crashed rank completes-with-error while its
                    # collective partners wedge (and, pre-first-step,
                    # never even arm supervision) — the crash is the
                    # ROOT CAUSE and must surface NOW, on every poll,
                    # or a heartbeat-only config hangs forever hiding
                    # it. Preempted completions are NOT raised here:
                    # the other ranks are mid-grace-window-save and
                    # must be allowed to finish before collection.
                    for i, f in enumerate(futures):
                        if f not in done:
                            continue
                        try:
                            ray.get(f)
                        except Exception as e:  # noqa: BLE001
                            if _find_preempted(e) is not None:
                                continue
                            self._kill_workers(workers)
                            self._get_result(f, i, ips)  # raises wrapped
                    if supervisor is not None:
                        stalled = ray.get(
                            supervisor.stalled.remote(hb_timeout))
                        if stalled:
                            self._kill_workers(workers)
                            raise HeartbeatTimeout(stalled, hb_timeout,
                                                   slice_map=slice_map)
                    if deadline is not None and \
                            time.monotonic() >= deadline:
                        stalled_idx = sorted(
                            i for i, f in enumerate(futures)
                            if f in pending)
                        self._kill_workers(workers)
                        raise TimeoutError(
                            f"worker(s) {stalled_idx} still running after "
                            f"{timeout}s (others done: {len(done)}/{n}); "
                            "killed all workers for retry-with-resume")
            results = [self._get_result(f, i, ips)
                       for i, f in enumerate(futures)]
        finally:
            # obs supervisor export (driver side, best-effort): the
            # per-rank last-beat view — on a stall it NAMES the dead
            # rank in <obs_dir>/supervisor.json for `obs report`
            if supervisor is not None and self._obs is not None:
                try:
                    self._obs.export_supervisor(ray.get(
                        supervisor.metrics_view.remote(hb_timeout)))
                except Exception:  # noqa: BLE001 - telemetry only
                    pass
            # PGs outlive their Python handles; without removal a retry
            # attempt would create a second PG against resources the
            # first still reserves and deadlock in pg.ready()
            try:
                ray.util.remove_placement_group(pg)
            except Exception:  # noqa: BLE001 - cleanup is best-effort
                pass
        return Result(
            metrics=results[0]["metrics"] if results else {},
            worker_metrics=[r["metrics"] for r in results]), \
            (results[0] if results else {})

    def _local_attempt_note(self, p) -> tuple:
        """(ledger, plan_fingerprint) of a failed/preempted attempt:
        Preempted carries its ledger across process boundaries; on the
        local path the loop's finally parked both on the context even
        when the attempt crashed."""
        led = getattr(p, "ledger", None) if p is not None else None
        fp = None
        if not self.use_ray:
            try:
                from gke_ray_train_tpu.rayint.context import get_context
                ctx = get_context()
                led = led if led is not None else ctx.goodput
                fp = ctx.plan_fingerprint
            except Exception:  # noqa: BLE001 - metadata is best-effort
                pass
        return led, fp

    def fit(self) -> Result:
        from gke_ray_train_tpu.obs import runtime as obs_runtime
        from gke_ray_train_tpu.train.metrics import (
            finish_ledger, sum_ledgers)
        fc = self.run_config.failure_config
        backoff_base = self.run_config.retry_backoff_s
        if backoff_base is None:
            backoff_base = float(os.environ.get("RETRY_BACKOFF_S", "1.0"))
        elastic = self._elastic()
        min_dev = self._min_devices()
        failures = 0
        preemptions = 0
        attempt = 0
        attempt_log: list = []
        # driver-side obs stream (obs/runtime.py): mints the shared
        # OBS_RUN_ID, then records one `attempt_end` per attempt — the
        # FINISHED ledger (lost_s = attempt-wall residual, so terms sum
        # to wall exactly) that `obs report` reconciles against — plus
        # the final `run_end`. None when obs is off / no dir resolves.
        self._obs = obs_runtime.start_driver(config=self.config)

        def finalize(result: Result) -> Result:
            result.attempts = attempt
            result.preemptions = preemptions
            result.attempt_log = attempt_log
            result.goodput = sum_ledgers(
                [e["goodput"] for e in attempt_log if "goodput" in e])
            if self._obs is not None:
                self._obs.note_run_end(result)
                self._obs.close()
                self._obs = None
            return result

        def note_attempt(entry: dict) -> None:
            if self._obs is not None:
                self._obs.note_attempt(attempt, entry)

        def classify_pool(p, entry, exc=None) -> Optional[Result]:
            """Elastic post-mortem: did the device pool change? Reads
            the pool off the preemption notice, the fault registry's
            emulated pool, or — for a heartbeat stall whose stalled
            ranks all sit on ONE slice — the slice-loss arithmetic.
            Records the shrink/grow event on the attempt entry, arms
            the override for the next attempt's workers, and returns a
            terminal Result when the survivors are below MIN_DEVICES."""
            pool = getattr(p, "pool", None) if p is not None else None
            if pool is None:
                pool = self._probe_pool()
            if pool is None and exc is not None:
                from gke_ray_train_tpu.rayint.supervisor import (
                    HeartbeatTimeout, slice_shrink_pool)
                for x in _cause_chain(exc):
                    if isinstance(x, HeartbeatTimeout) \
                            and x.uniform_slice is not None:
                        entry["slice"] = x.uniform_slice
                        per = float(self.scaling.resources_per_worker
                                    .get("TPU", 0))
                        if per > 0:
                            pool = slice_shrink_pool(
                                x.uniform_slice, x.slice_map, per)
                        break
            if pool is None or pool == self._pool_override or not elastic:
                return None
            prev = self._pool_override
            event = "shrink" if prev is None or pool < prev else "grow"
            entry["event"] = event
            entry["pool"] = int(pool)
            if pool < min_dev:
                msg = (f"device pool shrank to {pool} (< MIN_DEVICES="
                       f"{min_dev}); refusing to re-form — raise the "
                       "floor knowingly or wait for capacity")
                logger.error("%s", msg)
                entry["status"] = "failed"
                entry["error"] = msg
                # the terminal attempt must be noted BEFORE finalize
                # emits run_end and closes the driver stream — the
                # caller's note_attempt would hit a closed session
                note_attempt(entry)
                return finalize(Result(metrics={}, error=msg,
                                       status="failed"))
            self._pool_override = int(pool)
            logger.warning(
                "elastic %s event: next attempt re-forms the mesh on "
                "%d devices (restore reshards from the logical spec)",
                event, pool)
            return None

        while True:
            attempt += 1
            self._attempt = attempt       # stamped into worker env
            if self._obs is not None:
                # mint the attempt span id BEFORE the workers launch —
                # _pool_env forwards it as their causal parent; the
                # span itself lands at note_attempt with the verdict
                self._obs.begin_attempt(attempt)
            t_attempt = time.perf_counter()
            try:
                result, out = self._fit_ray() if self.use_ray \
                    else self._fit_local()
                entry = {
                    "status": "ok",
                    "resumed_step": out.get("resumed_step"),
                    "goodput": finish_ledger(
                        out.get("goodput"),
                        time.perf_counter() - t_attempt)}
                if out.get("plan_fingerprint"):
                    entry["plan_fingerprint"] = out["plan_fingerprint"]
                if self._pool_override is not None:
                    entry["pool"] = self._pool_override
                attempt_log.append(entry)
                note_attempt(entry)
                return finalize(result)
            except Exception as e:  # noqa: BLE001 - classified below
                wall = time.perf_counter() - t_attempt
                p = _find_preempted(e)
                led, fp = self._local_attempt_note(p)
                goodput = finish_ledger(led, wall)
                if self._obs is not None:
                    from gke_ray_train_tpu.rayint.supervisor import (
                        HeartbeatTimeout)
                    for x in _cause_chain(e):
                        if isinstance(x, HeartbeatTimeout):
                            self._obs.note_stall(x.stalled, x.timeout_s,
                                                 attempt=attempt)
                            break
                if p is not None:
                    # preempted: checkpointed within the grace window and
                    # exited cleanly — not a failure, does NOT consume
                    # max_failures; bounded by its own budget
                    preemptions += 1
                    entry = {
                        "status": "preempted",
                        "step": getattr(p, "step", None),
                        "resumed_step": getattr(p, "resumed_step", None),
                        "ckpt_save_s": getattr(p, "save_s", None),
                        "goodput": goodput}
                    if fp:
                        entry["plan_fingerprint"] = fp
                    attempt_log.append(entry)
                    stop = classify_pool(p, entry)
                    if stop is not None:
                        return stop      # classify noted the attempt
                    note_attempt(entry)
                    if preemptions > fc.max_preemptions:
                        logger.error(
                            "preemption budget exhausted "
                            "(max_preemptions=%d): %s",
                            fc.max_preemptions, e)
                        return finalize(Result(metrics={}, error=str(e),
                                               status="preempted"))
                    logger.warning(
                        "attempt %d preempted (%s); resuming from the "
                        "saved checkpoint (preemption %d/%d; max_failures "
                        "budget untouched)", attempt, e, preemptions,
                        fc.max_preemptions)
                    continue  # immediate: the checkpoint is durable
                if _is_nonretryable(e):
                    logger.exception(
                        "attempt %d failed with non-retryable %s; NOT "
                        "retrying (a deterministic error fails "
                        "identically every attempt)", attempt,
                        type(e).__name__)
                    entry = {"status": "failed", "error": str(e),
                             "nonretryable": True, "goodput": goodput}
                    if fp:
                        entry["plan_fingerprint"] = fp
                    attempt_log.append(entry)
                    note_attempt(entry)
                    return finalize(Result(metrics={}, error=str(e),
                                           status="failed"))
                # a failure whose post-mortem shows the pool changed
                # (slice eviction without grace, heartbeat stall with
                # the slice-loss signature) is a SHRINK event, not a
                # max_failures burn — the hardware leaving is not the
                # job's fault any more than a polite SIGTERM is
                entry = {"status": "failed", "error": str(e),
                         "goodput": goodput}
                if fp:
                    entry["plan_fingerprint"] = fp
                attempt_log.append(entry)
                stop = classify_pool(None, entry, exc=e)
                if stop is not None:
                    return stop          # classify noted the attempt
                if entry.get("event"):
                    entry["status"] = "preempted"
                    preemptions += 1
                    note_attempt(entry)
                    if preemptions > fc.max_preemptions:
                        logger.error(
                            "preemption budget exhausted "
                            "(max_preemptions=%d): %s",
                            fc.max_preemptions, e)
                        return finalize(Result(metrics={}, error=str(e),
                                               status="preempted"))
                    logger.warning(
                        "attempt %d lost to a pool change (%s); "
                        "re-forming on %d devices (preemption %d/%d; "
                        "max_failures budget untouched)", attempt, e,
                        entry["pool"], preemptions, fc.max_preemptions)
                    continue
                note_attempt(entry)
                failures += 1
                logger.exception(
                    "training attempt %d failed (failure %d/%d)",
                    attempt, failures, fc.max_failures)
                if failures > fc.max_failures:
                    return finalize(Result(metrics={}, error=str(e),
                                           status="failed"))
                # exponential backoff + jitter: a mass restart (whole
                # slice lost) must not thundering-herd the coordinator
                delay = min(backoff_base * (2 ** (failures - 1)), 60.0)
                delay *= 0.5 + random.random()
                if delay > 0:
                    logger.info("retrying in %.1fs (backoff + jitter)",
                                delay)
                    time.sleep(delay)

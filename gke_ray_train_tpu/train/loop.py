"""Host-side training loop.

The behavioral spec is the reference's visible loop
(ray-jobs/pytorch_llm_ray.py:263-310): per-epoch batch iteration with
epoch reshuffle, rank-0 logging every ``log_every`` batches (loss + LR,
:283-284), end-of-epoch checkpoint + metrics report through the trainer
context (:296-310). Differences by design:

- metrics include tokens/sec/chip and MFU (ThroughputMeter) — the
  BASELINE.json north-star metrics the reference never logs.
- checkpointing is collective (orbax) with keep-best retention and the
  resume-on-start the reference lacks.
- every host runs the loop in lockstep (SPMD); `is_host0` only gates
  *printing*, never collectives (the reference's filesystem-flag barrier
  antipattern, SURVEY.md §5.2, does not exist here).
"""

from __future__ import annotations

import contextlib
import functools
import logging
import os
import time
from typing import Callable, Iterable, Optional

import jax
import numpy as np

from gke_ray_train_tpu.analysis.guards import RuntimeGuards, allow_transfers
from gke_ray_train_tpu.data.prefetch import make_batch_source
from gke_ray_train_tpu.obs import runtime as obs_runtime
from gke_ray_train_tpu.obs import trace
from gke_ray_train_tpu.train import preempt
from gke_ray_train_tpu.train.metrics import (
    GoodputLedger, ThroughputMeter, paused)
from gke_ray_train_tpu.train.step import TrainState

logger = logging.getLogger(__name__)


def _fetch_metrics(m: dict) -> dict:
    """ONE batched host sync for the whole metrics tree.

    The pre-shardlint form — ``float(jax.device_get(v))`` per key —
    paid one device round-trip per metric every log step (TPU001);
    ``jax.device_get`` on the dict transfers every leaf in a single
    fetch, inside the transfer guard's explicit allow-list. It is also
    where a job that logs every step waits for the device, hence the
    region."""
    with trace.region("metrics_fetch"), allow_transfers():
        return {k: float(v) for k, v in jax.device_get(m).items()}


_END = object()


def _iterations(source):
    """``(batch, region)`` for each batch of ``source``, yielded inside
    an open ``step_iter`` region (obs/trace.py) whose first child,
    ``data_wait``, is the hand-over that ``source.consume_wait`` times.
    The region closes when the loop asks for the next batch, or when
    the generator is closed; the wait that finds the stream exhausted
    belongs to no iteration and is dropped."""
    it = iter(source)
    while True:
        with trace.region("step_iter") as span:
            with trace.region("data_wait") as wait:
                batch = next(it, _END)
                wait.drop = span.drop = batch is _END
            if batch is _END:
                return
            yield batch, span


def _in_train_loop_region(fn):
    """``fn`` inside one ``train_loop`` region (obs/trace.py): the
    whole call, entry to return, on every way out; the parent of what
    the loop opens. The call gets the open region as ``loop_span``."""
    @functools.wraps(fn)
    def in_region(*args, **kw):
        with trace.region("train_loop") as span:
            return fn(*args, loop_span=span, **kw)
    return in_region


@_in_train_loop_region
def run_training(state: TrainState,
                 train_step: Callable,
                 epoch_batches: Callable[[int], Iterable],
                 *,
                 epochs: int = 1,
                 steps_per_epoch: Optional[int] = None,
                 log_every: int = 20,
                 meter: Optional[ThroughputMeter] = None,
                 ckpt_manager=None,
                 report_fn: Optional[Callable] = None,
                 eval_fn: Optional[Callable] = None,
                 eval_every: Optional[int] = None,
                 eval_at_epoch_end: bool = False,
                 ckpt_every: Optional[int] = None,
                 place_batch: Optional[Callable] = None,
                 prefetch: int = 0,
                 ckpt_view: Optional[tuple] = None,
                 profiler=None,
                 tb_writer=None,
                 heartbeat_fn: Optional[Callable] = None,
                 fault_injector=None,
                 guards: Optional[RuntimeGuards] = None,
                 is_host0: bool = True,
                 loop_span=None) -> tuple:
    """Returns (final_state, last_metrics).

    last_metrics carries two compile-level timings alongside the step
    metrics: ``compile_s`` (wall time of the first step call incl. its
    trace+compile — near zero under a warm persistent compile cache or
    a deserialized AOT executable, perf/cache.py) and
    ``restart_to_first_step_s`` (run_training entry → first completed
    step: restore + fast-forward + compile; the recovery-path metric).

    epoch_batches(epoch) → iterable of host-local numpy batch dicts.
    place_batch(batch) → device arrays (sharded form-up); default asis.
    prefetch: queue depth of the asynchronous input pipeline
    (data/prefetch.py) — a background thread runs the epoch iterator AND
    ``place_batch`` ahead of the step, overlapping tokenize/pack and the
    host→device transfer with device compute. 0 = synchronous (identical
    batch stream either way; resume fast-forward never transfers skipped
    batches on either path). When a meter is attached, the fraction of
    the train window spent blocked on the pipeline is surfaced as
    ``data_stall_frac`` in the periodic log line and TB scalars.
    report_fn(metrics_dict) → trainer-context report (Ray or local).
    ckpt_view: optional (save_view, load_view) pair mapping the state to
    the subset the checkpoint persists — LoRA mode saves only adapters +
    optimizer state (the frozen/quantized base is rebuilt from the
    pretrained weights on resume, and its NF4 codes are ``uint4``, which
    orbax's tensorstore refuses: "Unsupported data type", orbax 0.11.32).
    heartbeat_fn(step, done=False) → per-step liveness report
    (rayint/supervisor.py; entry scripts wire ctx.heartbeat). Called
    after every completed step — supervision arms at the first beat,
    so first-step compile and resume fast-forward are not stalls; the
    done=True call at loop exit exempts this rank from stall detection
    (post-loop export work is unsupervised by design). Size
    HEARTBEAT_TIMEOUT_S above the longest eval/checkpoint pause: the
    clock only refreshes on step ADVANCE.
    fault_injector: deterministic fault hook fired once per completed
    step (testing/faults.py). None = built from $FAULT_SPEC, which is
    unset in production — the env read is the only overhead.
    guards: runtime enforcement of the shardlint properties
    (analysis/guards.py). None = resolved from env: TRANSFER_GUARD
    wraps the hot loop in jax's device→host transfer guard (the
    batched metrics fetch, eval, and checkpoint saves are the
    explicit allow-list); DIVERGENCE_GUARD allgathers a fingerprint
    of each host's lowered step-fn HLO before the first step and
    fails fast — with the per-host diff — when hosts traced
    different programs (otherwise that bug presents as a collective
    deadlock the watchdog can only name).

    Preemption (train/preempt.py): when the SIGTERM flag is up at a
    step boundary the loop force-saves a checkpoint, waits until it is
    durable, and raises Preempted — the trainer retries WITHOUT
    consuming the max_failures budget.
    """
    # time-to-first-step accounting: the clock starts BEFORE the
    # checkpoint restore below — at 8B scale restore is the other
    # dominant term besides compile, and
    # restart_to_first_step_s must cover restore + fast-forward +
    # compile (compile_s isolates the first step call, ≈0 when the step
    # is a deserialized AOT executable; perf/cache.py)
    t_loop0 = time.perf_counter()
    loop_timing: dict = {}
    # per-attempt goodput ledger (train/metrics.py): every second of
    # this call decomposes into LEDGER_TERMS; the trainer reads it off
    # the context (or the Preempted exception) into Result.goodput
    ledger = GoodputLedger()
    # unified telemetry (obs/): the attempt-scoped session the trainer
    # (or a test) configured; None = one is-None check per step.
    # Per-step feed = host floats the loop already measures (iteration
    # wall minus data wait minus eval/ckpt pauses) — no device sync,
    # no event emission off the log cadence, so the A/B stream with
    # obs on is bitwise-identical to obs off (tests pin that).
    obs = obs_runtime.active()
    if obs is not None and obs.capture is not None and profiler is not None:
        # jax.profiler is process-global: the anomaly capture must not
        # collide with the config-gated trace window
        obs.capture._conflict = lambda: bool(
            getattr(profiler, "active", False))
    if profiler is not None:
        # a profiler object on the loop is what turns the in-memory
        # span record on (with an obs session, which does so itself):
        # what lands in its window can then be read back by name
        trace.RECORD.attach()
    _obs_prev = [t_loop0, 0.0]   # [last note time, last eval/ckpt total]
    # step-window span accumulator (obs/trace.py): [step_s,
    # data_stall_s, steps] since the last flush. Spans aggregate at the
    # log cadence (plus one tail flush at loop exit), never per step —
    # the same hot-path contract the event stream keeps. data_stall_s
    # accumulates the identical wait floats the ledger books, so the
    # span-derived stall total reconciles with the ledger exactly.
    _win = [0.0, 0.0, 0]

    def _flush_window(step):
        if obs is not None and _win[2]:
            obs.span_add("step_window", _win[0] + _win[1], step=step,
                         steps=_win[2], data_stall_s=_win[1])
        _win[:] = [0.0, 0.0, 0]

    @contextlib.contextmanager
    def _ledgered(name, term, **kw):
        """A region whose JSONL duration is what the ledger booked to
        ``term`` while it was open (the delta, not a re-measurement:
        obs/critical.py reconciles the two streams) — on the exception
        path too, because paused() books on __exit__ regardless."""
        before = getattr(ledger, term)
        with trace.region(name, **kw) as r:
            try:
                yield r
            finally:
                r.dur_s = getattr(ledger, term) - before

    def _snapshot_save(step, state, m_host) -> float:
        """The async-commit save's blocking part (snapshot + enqueue),
        booked as ckpt_async_s — on the exception path too. The span
        carries the same float."""
        with trace.region("ckpt_snapshot", step=step,
                          forced=False) as s_span:
            t_save0 = time.perf_counter()
            try:
                with allow_transfers():
                    ckpt_manager.save(step, save_view(state),
                                      metrics=m_host)
            finally:
                s_span.dur_s = snap_dt = time.perf_counter() - t_save0
                ledger.note("ckpt_async_s", snap_dt)
        return snap_dt
    if guards is None:
        guards = RuntimeGuards.from_config()
    # KERNELCHECK=1 (analysis/kernelcheck.py): before anything trains,
    # run every registered kernel's cheapest case against its oracle,
    # gated by the pinned tolerance ledger. Sits HERE — after
    # distributed_init, before restore — so a kernel/oracle
    # disagreement fails the attempt loudly instead of corrupting a
    # run; KernelCheckError is an AssertionError, which the trainer
    # classifies as non-retryable.
    if os.environ.get("KERNELCHECK", "0").lower() not in (
            "", "0", "false", "no", "off"):
        from gke_ray_train_tpu.analysis.kernelcheck import quick_verify
        quick_verify(log=logger.info)
    save_view = (ckpt_view[0] if ckpt_view else (lambda st: st))
    load_view = (ckpt_view[1] if ckpt_view else (lambda st, v: v))
    if fault_injector is None:
        from gke_ray_train_tpu.testing.faults import FaultInjector
        fault_injector = FaultInjector.from_env(ckpt_manager=ckpt_manager)
    elif ckpt_manager is not None:
        fault_injector.bind_ckpt(ckpt_manager)
    resumed_step = None
    if ckpt_manager is not None:
        with trace.region("restore") as r_span:
            t_restore0 = time.perf_counter()
            try:
                view, resumed = ckpt_manager.restore_if_available(
                    save_view(state))
                if resumed is not None:
                    state = load_view(state, view)
            except Exception as e:  # noqa: BLE001 - layout mismatch
                if ckpt_view is None:
                    raise
                # a checkpoint written before the view existed stores
                # the FULL state (ADVICE r1: pre-view LoRA checkpoints
                # must stay restorable) — retry against the full-state
                # template
                logger.warning(
                    "ckpt_view restore failed (%s: %s); retrying as a "
                    "full-state checkpoint (pre-view layout)",
                    type(e).__name__, e)
                full, resumed = ckpt_manager.restore_if_available(state)
                if resumed is not None:
                    state = full
            restore_dt = time.perf_counter() - t_restore0
            # a resume served from the peer slice's hot state
            # (ckpt/peer.py) books peer_restore_s, not restore_s — the
            # ledger says which recovery path paid for the attempt's start
            peer_served = getattr(ckpt_manager, "last_restore_source",
                                  None) == "peer"
            ledger.note("peer_restore_s" if peer_served else "restore_s",
                        restore_dt)
            if resumed is not None and is_host0:
                logger.info("resumed at step %d", resumed)
            resumed_step = resumed
            # span duration is the EXACT float the ledger booked — the
            # critical-path reconciliation (obs/critical.py) depends on
            # the two streams agreeing bitwise, not approximately. The
            # profiler's plane shows `grt:restore` for both sources.
            r_span.dur_s, r_span.step = restore_dt, resumed
            r_span.attrs["resumed_step"] = resumed
            if peer_served:
                r_span.name = "peer_restore"
        if obs is not None and peer_served:
            _pmeta = getattr(ckpt_manager, "last_peer_restore",
                             None) or {}
            obs.emit("peer_restore", step=resumed,
                     restore_s=restore_dt,
                     bytes=_pmeta.get("bytes"),
                     from_slice=_pmeta.get("from_slice"))
        if obs is not None and resumed is not None:
            obs.emit("resume", step=resumed, resumed_step=resumed)
        # attempt metadata for Result.attempt_log (rayint/trainer.py);
        # context is stdlib-only, so this costs nothing standalone
        from gke_ray_train_tpu.rayint.context import get_context
        get_context().note_resume(resumed)

    last_metrics = {}
    global_step = first_step = int(jax.device_get(state.step))

    n_procs = max(jax.process_count(), 1)
    # multi-host flag agreement runs only every K-th boundary: blocking
    # on a cross-host collective EVERY step would serialize the async
    # dispatch overlap the input pipeline exists for. K is uniform
    # across hosts, so ranks still agree on the exit step; worst-case
    # exit delay is K steps against the ~25s grace window.
    preempt_sync_every = max(
        1, int(os.environ.get("PREEMPT_SYNC_EVERY", "4")))
    _boundary = [0]

    def _preempt_requested() -> bool:
        """Collective preemption verdict. SIGTERM lands on every host of
        an evicted slice, but async dispatch skews the hosts' Python
        loops by a step or two — a host exiting at ITS flag-observation
        step would enter a forced save its peers never join and wedge
        the slice inside the grace window. The allgather (a tiny host
        collective, multi-host only) makes every rank exit at the SAME
        boundary: any host's flag preempts all."""
        local = preempt.requested()
        if n_procs <= 1:
            return local
        _boundary[0] += 1
        if _boundary[0] % preempt_sync_every:
            # off-cycle boundaries never exit, even with the local flag
            # up — exits happen only where every rank runs the collective
            return False
        from jax.experimental import multihost_utils
        with allow_transfers():
            # the flag allgather is a sanctioned host collective —
            # its fetch must pass the transfer guard
            flags = multihost_utils.process_allgather(
                np.asarray(1 if local else 0, np.int32))
        return bool(np.max(flags))

    def _preempt_exit(state, m, step):
        """Grace-window exit: force-save, wait until durable, raise the
        distinct 'preempted' status (train/preempt.py)."""
        # flush TB FIRST: the grace window may end in SIGKILL mid-save,
        # and a killed attempt's last scalars must already be on disk
        # (the close() in the finally never runs under SIGKILL)
        if tb_writer is not None:
            tb_writer.flush()
        save_s = None
        if ckpt_manager is not None:
            with trace.region("preempt_save", step=step) as p_span:
                t0 = time.perf_counter()
                with allow_transfers():
                    if m is not None and \
                            ckpt_manager.latest_step() != step:
                        ckpt_manager.save(step, save_view(state),
                                          metrics=_fetch_metrics(m),
                                          force=True)
                    ckpt_manager.wait()
                p_span.dur_s = save_s = time.perf_counter() - t0
            ledger.note("eval_ckpt_stall_s", save_s)
            kept = ckpt_manager.latest_step()
            if kept != step:
                # best-by-score retention can delete a forced save whose
                # metric is not among the best — the resume then loses
                # every step since the surviving checkpoint. Training
                # managers should use recency retention (the entry
                # scripts do); shout, because this is silent data loss.
                logger.error(
                    "preemption save at step %d was DROPPED by "
                    "retention (surviving latest: %s) — the retry "
                    "resumes from there; use score_attribute=None on "
                    "resume managers", step, kept)
            elif is_host0:
                logger.warning(
                    "preemption: checkpoint at step %d durable in %.2fs "
                    "(grace remaining: %s s)", step, save_s,
                    preempt.remaining_grace_s())
        # close the ledger NOW so it rides the exception (the finally
        # below re-closes idempotently) — a preempted attempt's ledger
        # must survive process boundaries on the Ray path, and a pool-
        # change notice carries the surviving device count for the
        # trainer's elastic re-form
        ledger.close(time.perf_counter() - t_loop0)
        if obs is not None:
            obs.emit("preempt_exit", step=step, save_s=save_s,
                     grace_remaining_s=preempt.remaining_grace_s(),
                     pool=preempt.pool_target())
        raise preempt.Preempted(step=step, resumed_step=resumed_step,
                                save_s=save_s, grace_s=preempt.grace_s(),
                                pool=preempt.pool_target(),
                                ledger=ledger.as_dict())
    # resume fast-forward (HF Trainer resume_from_checkpoint semantics):
    # batches the restored step counter already consumed are SKIPPED, not
    # retrained — the epoch iterators are seeded by epoch index, so
    # replaying them positions the data stream exactly where the
    # checkpoint left off; a fully-trained checkpoint yields no new steps
    to_skip = global_step
    # NOTE: supervision arms at the FIRST step-completion beat, not
    # here — first-step compile and the resume fast-forward can
    # legitimately dwarf HEARTBEAT_TIMEOUT_S (worker_timeout_s bounds
    # that phase when needed)
    #
    # TRANSFER_GUARD teeth: the steady-state region below runs under
    # jax's device→host transfer guard (thread-local, so the prefetch
    # thread's h2d placement is untouched); every sanctioned fetch
    # site inside wraps itself in allow_transfers()
    _guard_region = contextlib.ExitStack()
    _guard_region.enter_context(guards.transfer_ctx())
    try:
      for epoch in range(epochs):
        if _preempt_requested():
            _preempt_exit(state, None, global_step)
        if meter is not None:
            meter.reset()
        m = None
        trained_this_epoch = 0
        # one iteration shape for both pipelines: the source pulls from
        # the epoch iterator, applies the resume fast-forward skip
        # (skipped batches are consumed but NEVER placed/transferred),
        # and runs place_batch — inline when prefetch=0, on a background
        # thread with a depth-`prefetch` device-resident queue otherwise
        source = make_batch_source(epoch_batches(epoch),
                                   place_fn=place_batch,
                                   depth=prefetch, skip=to_skip)
        iterations = _iterations(source)
        try:
          for batch, it_span in iterations:
            if _preempt_requested():
                _preempt_exit(state, m, global_step)
            wait_s = source.consume_wait()
            if trained_this_epoch == 0:
                # fast-forwarding consumed batches costs wall clock
                # (tokenize/pack) that must not deflate the tokens/sec
                # window of the steps actually trained — the reset also
                # drops the first batch's pipeline-warmup wait (the
                # ledger books that span as fast_forward, below)
                if meter is not None:
                    meter.reset()
            else:
                if meter is not None:
                    meter.data_wait(wait_s)
                ledger.data_wait(wait_s)
                # the span-side twin is accumulated HERE, at the same
                # point the ledger books — a crash later in the
                # iteration must leave both streams agreeing, or the
                # report's span/ledger reconciliation (rc=3) fires on
                # a healthy trace over a non-telemetry failure
                if obs is not None:
                    _win[1] += max(float(wait_s), 0.0)
            trained_this_epoch += 1
            if not loop_timing:
                # DIVERGENCE_GUARD (multi-host, opt-in): every host
                # must have lowered the SAME step program before the
                # first collective dispatch wedges on a mismatch
                guards.check_divergence(train_step, state, batch)
                with trace.region("compile",
                                  step=global_step + 1) as c_span:
                    t_step0 = time.perf_counter()
                    with trace.region("step_dispatch"):
                        state, m = train_step(state, batch)
                    # block: the first call's wall time must cover the
                    # compile it triggered, not just the async dispatch
                    jax.block_until_ready(m["loss"])
                    now = time.perf_counter()
                    loop_timing = {
                        "compile_s": now - t_step0,
                        "restart_to_first_step_s": now - t_loop0,
                    }
                    c_span.dur_s = loop_timing["compile_s"]
                # ledger decomposition of the restart window: restore
                # was timed directly; the first step call is compile;
                # on a RESUMED attempt everything else between entry
                # and the first completed step IS the fast-forward
                # (iterator replay, guard checks, pipeline warmup). A
                # fresh start fast-forwarded nothing — its warmup stays
                # in step_s rather than fabricating resume time.
                ledger.note("compile_s", loop_timing["compile_s"])
                if resumed_step is not None:
                    ff_dt = (loop_timing["restart_to_first_step_s"]
                             - loop_timing["compile_s"]
                             - ledger.restore_s)
                    ledger.note("fast_forward_s", ff_dt)
                    if obs is not None:
                        # ledger.note clamps negatives to 0; mirror it
                        # so span and ledger stay bitwise-equal
                        obs.span_add("fast_forward", max(ff_dt, 0.0),
                                     step=global_step + 1)
                if obs is not None:
                    from gke_ray_train_tpu.obs import (
                        runtime as _obs_runtime)
                    obs.emit("first_step", step=global_step + 1,
                             compile_s=loop_timing["compile_s"],
                             restart_to_first_step_s=loop_timing[
                                 "restart_to_first_step_s"],
                             restore_s=ledger.restore_s,
                             fast_forward_s=ledger.fast_forward_s,
                             backend=_obs_runtime.current_backend())
            else:
                with trace.region("step_dispatch"):
                    state, m = train_step(state, batch)
            global_step += 1
            it_span.step = global_step
            if heartbeat_fn is not None:
                # step-granular liveness: the metric the supervisor
                # watches is "this rank completed another step"
                heartbeat_fn(global_step)
            if profiler is not None:
                profiler.step(global_step)
            if obs is not None:
                # anomaly detection feed (obs/capture.py): host
                # iteration wall minus this batch's data wait and minus
                # every ledgered non-step term booked since the last
                # note (eval/ckpt pauses, and on the first step the
                # restore/compile/fast-forward window) — under async
                # dispatch, backpressure makes what remains track
                # device step time. Pure host floats, no sync.
                _now = time.perf_counter()
                _booked = (ledger.eval_ckpt_stall_s + ledger.compile_s
                           + ledger.restore_s + ledger.fast_forward_s
                           + ledger.ckpt_async_s
                           + ledger.peer_restore_s)
                _iter_v = max(_now - _obs_prev[0] - wait_s
                              - (_booked - _obs_prev[1]), 0.0)
                obs.note_step(global_step, _iter_v, wait_s)
                _obs_prev[0] = _now
                _obs_prev[1] = _booked
                # step-window span feed (the stall half was booked at
                # the ledger's own site above)
                _win[0] += _iter_v
                _win[2] += 1
            if meter is not None:
                # tokens metric is device-resident; fetching it each step
                # would sync — use the (static) batch token count instead
                meter.update(int(np.prod(batch["inputs"].shape)))
            if log_every and global_step % log_every == 0:
                m_host = _fetch_metrics(m)
                with trace.region("log_emit"):
                    last_metrics = {"epoch": epoch, "step": global_step,
                                    **loop_timing, **m_host}
                    if meter is not None:
                        last_metrics.update(meter.snapshot())
                    if tb_writer is not None:
                        tb_writer.log(global_step, last_metrics)
                    if obs is not None:
                        # log-cadence telemetry sink: gauges + ONE
                        # `step` event + file export, from the host
                        # dict already fetched above — obs adds no
                        # device traffic
                        obs.log_metrics(global_step, last_metrics,
                                        epoch=epoch)
                        _flush_window(global_step)
                    if is_host0:
                        logger.info(
                            "epoch %d step %d loss %.4f lr %.3g%s",
                            epoch, global_step,
                            m_host.get("loss", float("nan")),
                            m_host.get("learning_rate", float("nan")),
                            (f" tok/s/chip {last_metrics['tokens_per_sec_per_chip']:.0f}"
                             f" mfu {last_metrics['mfu']:.1%}"
                             f" stall {last_metrics['data_stall_frac']:.1%}"
                             if meter is not None else ""))
            if eval_fn is not None and eval_every and \
                    global_step % eval_every == 0:
                # eval/ckpt stalls are excluded from the meter's
                # steady-state window; the *_incl_stalls metrics keep
                # the cumulative view (VERDICT r4 weak #8). Sync on the
                # async-dispatched train step FIRST so its in-flight
                # compute is booked as training, not stall
                if meter is not None:
                    jax.block_until_ready(m)
                with _ledgered("eval", "eval_ckpt_stall_s",
                               step=global_step), \
                        paused(meter), paused(ledger), allow_transfers():
                    eval_metrics = eval_fn(state)
                last_metrics.update(eval_metrics)
                if tb_writer is not None:
                    tb_writer.log(global_step, eval_metrics)
                if obs is not None:
                    obs.emit("eval", step=global_step,
                             metrics=eval_metrics)
                if is_host0:
                    logger.info("eval @ %d: %s", global_step, eval_metrics)
            # SAVE_STRATEGY="steps": mid-epoch checkpoints (HF save_steps
            # semantics, reference fine_tune_config.json:22-23)
            if ckpt_manager is not None and ckpt_every and \
                    global_step % ckpt_every == 0:
                m_host = _fetch_metrics(m)
                if getattr(ckpt_manager, "async_commit", False):
                    # async-commit save (ISSUE 18): the loop blocks only
                    # for the device→host snapshot + enqueue — booked as
                    # ckpt_async_s, the residual blocking cost of async
                    # checkpointing. The storage serialize runs on the
                    # committer thread behind the write-ahead marker and
                    # lands as a ckpt_commit EVENT, never loop time.
                    with paused(meter):
                        snap_dt = _snapshot_save(global_step, state,
                                                 m_host)
                    if obs is not None:
                        obs.emit("ckpt_snapshot", step=global_step,
                                 snapshot_s=snap_dt, forced=False)
                else:
                    t_save0 = time.perf_counter()
                    with _ledgered("ckpt_save", "eval_ckpt_stall_s",
                                   step=global_step, forced=False), \
                            paused(meter), paused(ledger), \
                            allow_transfers():
                        ckpt_manager.save(global_step, save_view(state),
                                          metrics=m_host)
                    if obs is not None:
                        obs.emit("ckpt_save", step=global_step,
                                 save_s=time.perf_counter() - t_save0,
                                 forced=False)
            if fault_injector is not None:
                # after the step's bookkeeping AND its scheduled save, so
                # kind=ckpt_truncate at step k tears the step-k save
                fault_injector.on_step(global_step)
        finally:
            # closes the iteration a failing step left open, then the
            # source: normal exhaustion already joined the workers; this
            # reclaims them on the exception path (a failing step must
            # not leak prefetch threads parked on backpressure)
            iterations.close()
            source.close()
        yielded = source.yielded
        to_skip -= source.skipped

        # end of epoch: checkpoint + report (collective; all hosts enter)
        if m is None:
            if yielded > 0:
                # every batch of this epoch was consumed before the
                # restore point — nothing to retrain, nothing to re-save
                if is_host0:
                    logger.info("epoch %d already completed before "
                                "resume point (step %d); skipping",
                                epoch, global_step)
                continue
            # an iterator that yielded NOTHING is a data/config error on
            # fresh AND resumed runs alike — never mask it as "resumed"
            raise ValueError(
                f"epoch {epoch} produced 0 batches — the dataset is "
                "smaller than one global batch (shrink GLOBAL_BATCH / "
                "PER_DEVICE_TRAIN_BATCH_SIZE or grow the dataset)")
        m_host = _fetch_metrics(m)
        epoch_metrics = {"epoch": epoch, "step": global_step,
                         **loop_timing, **m_host}
        if meter is not None:
            epoch_metrics.update(meter.snapshot())
        if eval_fn is not None and eval_at_epoch_end:
            with _ledgered("eval", "eval_ckpt_stall_s",
                           step=global_step), \
                    paused(ledger), allow_transfers():
                epoch_metrics.update(eval_fn(state))
        if tb_writer is not None:
            tb_writer.log(global_step, epoch_metrics)
            tb_writer.flush()
        if obs is not None:
            obs.emit("epoch_end", step=global_step, epoch=epoch)
        last_metrics = epoch_metrics
        if ckpt_manager is not None:
            if getattr(ckpt_manager, "async_commit", False):
                _snapshot_save(global_step, state, m_host)
            else:
                with _ledgered("ckpt_save", "eval_ckpt_stall_s",
                               step=global_step, forced=False), \
                        paused(ledger), allow_transfers():
                    ckpt_manager.save(global_step, save_view(state),
                                      metrics=m_host)
        if report_fn is not None:
            report_fn(epoch_metrics)
    finally:
        # what this call trained, on its `train_loop` region
        loop_span.attrs.update(
            steps=global_step - first_step,
            to_first_step_s=loop_timing.get("restart_to_first_step_s"))
        # seal the attempt's goodput ledger on EVERY exit path (normal,
        # Preempted — already closed there, idempotent — and crash) and
        # park it on the context for Result.attempt_log / Result.goodput
        ledger.close(time.perf_counter() - t_loop0)
        from gke_ray_train_tpu.rayint.context import get_context
        get_context().note_goodput(ledger.as_dict())
        if obs is not None:
            # tail step-window span: the steps since the last log
            # boundary must not fall off the trace — critical-path
            # coverage is checked against the ledger
            _flush_window(global_step)
            # ledger terms -> the obs registry, and the registry -> TB
            # (train/tb.py log_registry): the dashboard, the Prometheus
            # textfile and `obs report` all read the SAME decomposition
            from gke_ray_train_tpu.train.metrics import ledger_metrics
            obs.registry.set_many(ledger_metrics(ledger.as_dict()))
            if tb_writer is not None:
                tb_writer.log_registry(global_step, obs.registry)
            obs.export()
        # leave the transfer-guard region before the post-loop export/
        # merge work — only the hot loop is guarded
        _guard_region.close()
        # a failing step must still flush an in-flight trace — the
        # profile matters most in exactly that case
        if profiler is not None:
            profiler.close()
            # after the last step and the window's end, never between
            # two timed steps: the executable's {instruction: op_name}
            # table, so that the window's device events can be joined
            # to the program's scopes (obs/trace.py). An AOT-built step
            # keeps its executable; a plain jitted one has none to read
            note_table = getattr(train_step, "note_scope_table", None)
            if note_table is not None:
                note_table()
            trace.RECORD.detach()
        if tb_writer is not None:
            tb_writer.close()

    if ckpt_manager is not None:
        ckpt_manager.wait()
    if heartbeat_fn is not None:
        # supervised region ends here: post-loop export/merge work can
        # legitimately exceed the heartbeat timeout
        heartbeat_fn(global_step, done=True)
    return state, last_metrics

"""Tracing/profiling hooks (SURVEY.md §5.1).

The reference has no profiler anywhere (no torch profiler, no NVTX —
§5.1); its only perf observability is loss curves. Hitting the ≥40% MFU
north star needs step-level traces, so this wires ``jax.profiler``
(XProf/TensorBoard format) into the training loop as a first-class,
config-gated subsystem: trace a window of steps mid-run (after compile +
warmup noise) and write to the shared storage mount where TensorBoard
reads it. The window holds the program's own names (obs/trace.py):
``grt:<region>`` spans of the loop, the input pipeline and the step's
build on the host plane, and on the device ops the ``jax.named_scope``
path (``.../transpose(jvp())/.../attn/qkv/base/dot_general``) and the
Pallas kernels' names (``flash_fwd``, ...).

Config surface (fine_tune_config.json / pre-train config):
  "PROFILE": true | "gs-mounted/dir"   — enable (default dir under the
                                         run's output dir)
  "PROFILE_START_STEP": 10             — steps to run after (re)start
                                         before tracing begins (skips
                                         compile + warmup, also after a
                                         checkpoint resume)
  "PROFILE_NUM_STEPS": 5               — traced window length
Debug-NaNs smoke switch (§5.2): "DEBUG_NANS": true.
"""

from __future__ import annotations

import logging
from typing import Optional

import jax

logger = logging.getLogger(__name__)


class TraceProfiler:
    """Start/stop a jax.profiler trace around a step window.

    Host-side and idempotent; every host traces its own process (device
    traces land per-host, the standard multi-host XProf layout).
    """

    def __init__(self, logdir: str, start_step: int = 10,
                 num_steps: int = 5):
        self.logdir = logdir
        self.start_step = start_step
        self.num_steps = num_steps
        self._first = None           # first global step seen this run
        self._stop_at = None
        self._active = False
        self._done = False

    @property
    def active(self) -> bool:
        """Whether a trace is in flight RIGHT NOW. jax.profiler is
        process-global, so the anomaly-capture scheduler
        (obs/capture.py) checks this before arming its own one-shot
        trace — two concurrent start_trace calls would fail both."""
        return self._active

    @property
    def stop_step(self) -> int:
        """Exclusive end of the trace window relative to this run's
        first step: traced steps are [start_step, stop_step)."""
        return self.start_step + self.num_steps

    def step(self, global_step: int) -> None:
        """Call once per train step, AFTER the step ran (post-increment
        index). The window is relative to the first step this process
        runs — a checkpoint resume at step 1000 still skips its own
        compile/warmup steps before tracing."""
        if self._done:
            return
        if self._first is None:
            self._first = global_step
        # start_trace after `start_step` steps have completed, so the
        # first *traced* step is first + start_step
        if not self._active and \
                global_step >= self._first + self.start_step - 1:
            try:
                jax.profiler.start_trace(self.logdir)
                self._active = True
                self._stop_at = global_step + self.num_steps
                logger.info("profiler: tracing steps %d-%d to %s",
                            global_step + 1, self._stop_at, self.logdir)
            except Exception as e:  # noqa: BLE001 - profiling never fatal
                logger.warning("profiler start failed: %s", e)
                self._done = True
        elif self._active and global_step >= self._stop_at:
            self.close()

    def close(self) -> None:
        if self._active:
            try:
                jax.profiler.stop_trace()
                logger.info("profiler: trace written to %s", self.logdir)
            except Exception as e:  # noqa: BLE001
                logger.warning("profiler stop failed: %s", e)
            self._active = False
        self._done = True


def profiler_from_config(config: dict, default_dir: str) -> Optional[
        TraceProfiler]:
    """Build a TraceProfiler from reference-style flat config keys, or
    None when profiling is off."""
    prof = config.get("PROFILE", False)
    if not prof:
        return None
    logdir = prof if isinstance(prof, str) else default_dir
    return TraceProfiler(
        logdir,
        start_step=int(config.get("PROFILE_START_STEP", 10)),
        num_steps=int(config.get("PROFILE_NUM_STEPS", 5)))


def apply_debug_flags(config: dict) -> None:
    """§5.2 smoke-mode checks: jax_debug_nans turns silent NaN training
    into an immediate, located failure."""
    if bool(config.get("DEBUG_NANS", False)):
        jax.config.update("jax_debug_nans", True)
        logger.info("jax_debug_nans enabled")

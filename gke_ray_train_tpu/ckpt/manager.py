"""Sharded checkpointing with retention + resume (SURVEY.md §5.4).

Reference behavior replaced:
- Rank-0 torch.save of model/optimizer/scheduler state_dicts + Ray
  ``Checkpoint.from_directory`` (ray-jobs/pytorch_llm_ray.py:296-310) with
  ``CheckpointConfig(num_to_keep=1, checkpoint_score_attribute="loss",
  order="min")`` retention (:355-359).
- **Resume is never implemented in the reference** (no
  ``train.get_checkpoint()`` anywhere); ``restore_if_available`` fixes
  that gap (§5.3).

TPU redesign: once params are GSPMD-sharded, rank-0-only save is invalid —
orbax writes the distributed pytree collectively (every host participates)
and restores it into the same shardings.

Write-ahead commit (ISSUE 18 tentpole a): every save is bracketed by
durable markers beside the step directories —

    COMMITTING.<step>   (fsync'd BEFORE any step data is serialized)
    COMMITTED.<step>    (fsync'd only after the step data is durable)

A ``COMMITTING`` marker without its ``COMMITTED`` twin is the on-disk
signature of a mid-commit death. In **async-commit** mode
(``async_commit=True`` / ``ASYNC_CKPT=1``) the caller-facing ``save``
does ONE device→host snapshot and returns; a background committer
thread serializes to storage behind the marker pair, and the restore
path treats the mid-commit signature as "this step never existed" —
quarantined without a restore attempt, falling back to the previous
committed step. In the default synchronous mode the markers are
advisory: ``latest_step()`` never offers a marker-suspect step (the
satellite-1 contract — a quarantined step directory that reappears
after a second crash at the same step), but ``restore_if_available``
still verifies suspects by restoring (the save may well be durable —
only the lazy marker flush was lost with the process) and promotes the
marker on success. Marker-less step directories — every checkpoint
written before this protocol existed — stay trusted.

Peer-slice hot state (ISSUE 18 tentpole b): when a
``ckpt.peer.PeerReplicator`` is bound (``PEER_REPLICATION=1``), every
snapshot also streams to the peer slice's hot store, and
``restore_if_available`` serves the resume from the living peer —
no storage read — whenever the peer's step is at least as new as the
latest committed one (``last_restore_source``/``last_peer_restore``
tell the loop which ledger term to book).
"""

from __future__ import annotations

import glob
import json
import logging
import os
import threading
import time
from typing import Any, Optional, Tuple

import jax
import orbax.checkpoint as ocp

logger = logging.getLogger(__name__)

TOPOLOGY_NOTE = "topology.json"

# write-ahead marker names; <name>.<step> files beside the step dirs
_WAL_OPEN = "COMMITTING"
_WAL_DONE = "COMMITTED"


def _env_flag(name: str, default: bool = False) -> bool:
    v = os.environ.get(name)
    if v is None:
        return default
    return v.strip().lower() not in ("", "0", "false", "no")


def _tree_n_devices(tree: Any) -> Optional[int]:
    """Device count of the mesh a concrete pytree lives on (None when
    no leaf carries a sharding — e.g. an abstract template)."""
    for leaf in jax.tree.leaves(tree):
        sharding = getattr(leaf, "sharding", None)
        devs = getattr(sharding, "device_set", None)
        if devs:
            return len(devs)
    return None


class CheckpointRestoreError(RuntimeError):
    """A collectively-agreed restore failure. The trainer's retry
    classifier treats this as RETRYABLE even though the underlying
    orbax/tensorstore cause is often a ValueError (which would
    otherwise fail fast as 'deterministic') — a fresh attempt re-reads
    storage and can succeed where a flake failed."""


class CheckpointCommitError(RuntimeError):
    """The background committer thread failed or did not drain in time
    (``CKPT_COMMIT_TIMEOUT_S``) — surfaced from ``wait()`` so the
    attempt fails loudly instead of exiting with a silently-lost
    checkpoint."""


class CheckpointManager:
    """Thin orbax wrapper carrying the reference's retention contract."""

    def __init__(self, directory: str, *, max_to_keep: int = 1,
                 score_attribute: str = "loss", score_mode: str = "min",
                 save_interval_steps: int = 1, async_save: bool = True,
                 async_commit: Optional[bool] = None,
                 commit_timeout_s: Optional[float] = None,
                 storage_delay_s: Optional[float] = None,
                 peer: Any = None):
        if async_commit is None:
            async_commit = _env_flag("ASYNC_CKPT")
        self.async_commit = bool(async_commit)
        if commit_timeout_s is None:
            commit_timeout_s = float(
                os.environ.get("CKPT_COMMIT_TIMEOUT_S", "120"))
        self.commit_timeout_s = float(commit_timeout_s)
        if storage_delay_s is None:
            storage_delay_s = float(
                os.environ.get("CKPT_STORAGE_DELAY_S", "0"))
        # emulated storage latency per commit (the GCS round-trip the
        # chaos drill hides behind the committer thread; the sync
        # baseline arm eats it on the loop's wall-clock)
        self.storage_delay_s = max(float(storage_delay_s), 0.0)
        self._options = ocp.CheckpointManagerOptions(
            max_to_keep=max_to_keep,
            best_fn=(lambda m: m[score_attribute]) if score_attribute else None,
            best_mode=score_mode,
            save_interval_steps=save_interval_steps,
            # the committer thread owns durability in async-commit mode:
            # its orbax save is synchronous so COMMITTED means durable
            enable_async_checkpointing=(async_save and
                                        not self.async_commit),
        )
        self._mgr = ocp.CheckpointManager(directory, options=self._options)
        self.directory = directory
        if peer is False:  # explicit opt-out beats the env knob
            peer = None
        elif peer is None and _env_flag("PEER_REPLICATION"):
            from gke_ray_train_tpu.ckpt.peer import PeerReplicator
            peer = PeerReplicator.from_env()
        self.peer = peer
        # (saved_n_devices, restored_n_devices) of the last restore
        # that crossed topologies — the elastic-resume witness the
        # trainer/tests read; None = same-topology (or unknown) restore
        self.last_restore_resharded: Optional[Tuple[int, int]] = None
        # "peer" | "storage" | None — which path served the last
        # restore_if_available (the loop books peer_restore_s vs
        # restore_s off this)
        self.last_restore_source: Optional[str] = None
        # {"step","bytes","from_slice"} of the last peer-served restore
        self.last_peer_restore: Optional[dict] = None
        # orbax-async saves whose COMMITTED marker is still pending
        # (flushed lazily once wait_until_finished proves durability)
        self._pending_marks: set = set()
        # async-commit machinery (committer thread started lazily)
        self._commit_lock = threading.Condition()
        self._commit_queue: list = []
        self._committing_now: Optional[int] = None
        self._abort_step: Optional[int] = None
        self._commit_error: Optional[BaseException] = None
        self._committer: Optional[threading.Thread] = None
        self._stop = False
        self.commits_done = 0
        self.last_torn_step: Optional[int] = None
        # steps already snapshot by THIS manager: the async path must
        # dedupe itself (the sync path gets this from orbax should_save
        # — e.g. the end-of-epoch save re-offering the cadence save's
        # step would otherwise enqueue a second commit that dies on
        # StepAlreadyExists)
        self._snapshotted: set = set()

    # ------------------------------------------------------------------
    # write-ahead markers

    def _is_host0(self) -> bool:
        try:
            return jax.process_index() == 0
        except Exception:  # noqa: BLE001 - backend-free callers
            return True

    def _marker_path(self, kind: str, step: int) -> str:
        return os.path.join(str(self.directory), f"{kind}.{int(step)}")

    def _fsync_dir(self) -> None:
        try:
            dfd = os.open(str(self.directory), os.O_RDONLY)
        except OSError:  # pragma: no cover - directory raced away
            return
        try:
            os.fsync(dfd)
        except OSError:  # pragma: no cover - fs without dir fsync
            pass
        finally:
            os.close(dfd)

    def _write_marker(self, kind: str, step: int) -> None:
        """COMMITTING/COMMITTED marker, fsync'd (file then directory) so
        the ordering the recovery rule relies on survives a crash."""
        if not self._is_host0():
            return
        os.makedirs(str(self.directory), exist_ok=True)
        fd = os.open(self._marker_path(kind, step),
                     os.O_CREAT | os.O_WRONLY | os.O_TRUNC, 0o644)
        try:
            os.write(fd, f"{kind} step={int(step)}\n".encode())
            os.fsync(fd)
        finally:
            os.close(fd)
        self._fsync_dir()

    def _remove_marker(self, kind: str, step: int) -> None:
        if not self._is_host0():
            return
        try:
            os.remove(self._marker_path(kind, step))
        except FileNotFoundError:
            pass
        except OSError as e:  # pragma: no cover - removal best-effort
            logger.debug("could not remove %s.%d marker: %s", kind, step, e)

    def _mark_committed(self, step: int) -> None:
        self._write_marker(_WAL_DONE, step)
        self._remove_marker(_WAL_OPEN, step)

    def _flush_marks(self) -> None:
        """Promote the write-ahead markers of orbax-async saves that are
        now durable. Lazy on purpose: called where durability is about
        to be asserted anyway (next save / wait / latest_step / restore),
        so the loop's save window never eats a wait_until_finished."""
        if not self._pending_marks:
            return
        self._mgr.wait_until_finished()
        for step in sorted(self._pending_marks):
            self._mark_committed(step)
        self._pending_marks.clear()

    def _step_eligible(self, step: int) -> bool:
        """The recovery rule: a step is offered iff it is NOT in the
        mid-commit state. COMMITTED wins; a bare COMMITTING marker means
        the writer died between the write-ahead record and the durable
        one; no markers at all (pre-protocol checkpoints) stay trusted."""
        step = int(step)
        if step in self._pending_marks:
            return True
        if os.path.exists(self._marker_path(_WAL_DONE, step)):
            return True
        return not os.path.exists(self._marker_path(_WAL_OPEN, step))

    def _purge_uncommitted(self) -> None:
        """Async-commit recovery sweep: every COMMITTING-without-
        COMMITTED step on disk 'never existed' — quarantine it (or just
        drop the orphan marker when the death landed before any step
        data) so the restore walk only ever sees committed steps."""
        pattern = os.path.join(str(self.directory), _WAL_OPEN + ".*")
        for path in sorted(glob.glob(pattern)):
            try:
                step = int(os.path.basename(path).split(".", 1)[1])
            except (IndexError, ValueError):
                continue
            if step in self._pending_marks:
                continue
            if os.path.exists(self._marker_path(_WAL_DONE, step)):
                # death landed between COMMITTED and the marker cleanup:
                # the step is durable, only the stale twin lingers
                self._remove_marker(_WAL_OPEN, step)
                continue
            logger.warning(
                "checkpoint step %d has a write-ahead marker but no "
                "commit record — the previous attempt died mid-commit; "
                "treating the step as never saved and falling back to "
                "the last committed one", step)
            if os.path.exists(os.path.join(str(self.directory),
                                           str(step))):
                self._quarantine(step)
            else:
                self._remove_marker(_WAL_OPEN, step)

    # ------------------------------------------------------------------
    # topology note

    def _write_topology_note(self, step: int, n: Optional[int]) -> None:
        if n is None:
            return
        if not self._is_host0():
            return
        try:
            with open(os.path.join(str(self.directory),
                                   TOPOLOGY_NOTE), "w") as f:
                json.dump({"step": int(step), "n_devices": int(n)}, f)
        except OSError as e:  # pragma: no cover - note is best-effort
            logger.debug("could not write topology note: %s", e)

    def _note_topology(self, step: int, state: Any) -> None:
        """Record the saving mesh's device count beside the checkpoints
        (best-effort, host 0) so a later restore can SAY it resharded —
        the save-time topology is not recoverable from orbax metadata."""
        self._write_topology_note(step, _tree_n_devices(state))

    def saved_topology(self) -> Optional[dict]:
        try:
            with open(os.path.join(str(self.directory),
                                   TOPOLOGY_NOTE)) as f:
                return json.load(f)
        except (OSError, ValueError):
            return None

    # ------------------------------------------------------------------
    # peer replication

    def _replicate(self, step: int, host_state: Any) -> None:
        t0 = time.perf_counter()
        try:
            meta = self.peer.replicate(str(self.directory), int(step),
                                       host_state)
        except Exception as e:  # noqa: BLE001 - replication best-effort
            logger.warning("peer replication of step %d failed "
                           "(%s: %s); storage path unaffected",
                           step, type(e).__name__, e)
            return
        from gke_ray_train_tpu.obs import runtime as obs_runtime
        obs_runtime.emit("peer_replicate", step=int(step),
                         bytes=int(meta.get("bytes", 0)),
                         to_slice=int(meta.get("to_slice", 0)),
                         replicate_s=time.perf_counter() - t0)

    # ------------------------------------------------------------------
    # save

    def save(self, step: int, state: Any, metrics: Optional[dict] = None,
             force: bool = False) -> bool:
        metrics = {k: float(v) for k, v in (metrics or {}).items()}
        if self.async_commit:
            return self._save_async(step, state, metrics, force)
        self._flush_marks()
        self._write_marker(_WAL_OPEN, step)
        try:
            saved = self._mgr.save(step, args=ocp.args.StandardSave(state),
                                   metrics=metrics, force=force)
        except BaseException:
            self._remove_marker(_WAL_OPEN, step)
            raise
        if saved:
            if self.storage_delay_s:
                # the emulated storage round-trip: the sync path blocks
                # the loop on it, which is exactly what the goodput
                # drill's baseline arm measures
                time.sleep(self.storage_delay_s)
            self._pending_marks.add(int(step))
            if self.peer is not None:
                self._replicate(step, jax.device_get(state))
            self._note_topology(step, state)
            logger.info("checkpoint saved at step %d (metrics=%s)",
                        step, metrics)
        else:
            self._remove_marker(_WAL_OPEN, step)
        return saved

    def _save_async(self, step: int, state: Any, metrics: dict,
                    force: bool) -> bool:
        """The caller-facing half of an async-commit save: ONE
        device→host snapshot, replicate to the peer slice, enqueue for
        the committer — the loop blocks only for the snapshot."""
        if self._stop:
            # a torn manager (kill_during_commit) is 'dead': the real
            # process would never reach another save
            return False
        if self._commit_error is not None:
            self.wait()  # re-raise the committer's failure loudly
        if int(step) in self._snapshotted:
            # already snapshot (queued, in-flight or committed): the
            # durability the caller wants is one wait() away
            return False
        if not force and not self._mgr.should_save(step):
            return False
        host_state = jax.device_get(state)
        n_devices = _tree_n_devices(state)
        if self.peer is not None:
            self._replicate(step, host_state)
        with self._commit_lock:
            self._ensure_committer()
            self._commit_queue.append(
                (int(step), host_state, metrics, n_devices))
            self._snapshotted.add(int(step))
            self._commit_lock.notify_all()
        logger.info("checkpoint snapshot taken at step %d "
                    "(commit queued; metrics=%s)", step, metrics)
        return True

    def _ensure_committer(self) -> None:
        if self._committer is None or not self._committer.is_alive():
            self._committer = threading.Thread(
                target=self._commit_loop, name="ckpt-committer",
                daemon=True)
            self._committer.start()

    def _commit_loop(self) -> None:
        while True:
            with self._commit_lock:
                while not self._commit_queue and not self._stop:
                    self._commit_lock.wait()
                if not self._commit_queue and self._stop:
                    return
                step, host_state, metrics, n_devices = \
                    self._commit_queue.pop(0)
                self._committing_now = step
            try:
                self._commit_one(step, host_state, metrics, n_devices)
            except BaseException as e:  # noqa: BLE001 - surfaced in wait()
                logger.exception("background commit of step %d failed",
                                 step)
                with self._commit_lock:
                    if self._commit_error is None:
                        self._commit_error = e
                    self._committing_now = None
                    self._commit_lock.notify_all()
                continue
            with self._commit_lock:
                self._committing_now = None
                self.commits_done += 1
                self._commit_lock.notify_all()

    def _commit_one(self, step: int, host_state: Any, metrics: dict,
                    n_devices: Optional[int]) -> None:
        """One write-ahead commit: COMMITTING → serialize → COMMITTED.
        A death anywhere inside leaves the COMMITTING signature and the
        step is recovered as never-saved."""
        t0 = time.perf_counter()
        self._write_marker(_WAL_OPEN, step)
        if self.storage_delay_s:
            time.sleep(self.storage_delay_s)
        with self._commit_lock:
            aborted = self._abort_step == step
        if not aborted:
            # force=True: the should_save/retention gate already ran on
            # the caller thread at snapshot time
            self._mgr.save(step, args=ocp.args.StandardSave(host_state),
                           metrics=metrics, force=True)
            self._mgr.wait_until_finished()
            with self._commit_lock:
                aborted = self._abort_step == step
        if aborted:
            # drill cooperation (tear_mid_commit): emulate the SIGKILL
            # landing before COMMITTED — the marker pair stays torn
            logger.warning("commit of step %d torn mid-flight "
                           "(kill_during_commit drill)", step)
            self._emit_commit_event(step, time.perf_counter() - t0,
                                    "torn")
            return
        self._mark_committed(step)
        self._write_topology_note(step, n_devices)
        self._emit_commit_event(step, time.perf_counter() - t0, "ok")
        logger.info("checkpoint committed at step %d (metrics=%s)",
                    step, metrics)

    @staticmethod
    def _emit_commit_event(step: int, commit_s: float,
                           status: str) -> None:
        try:
            from gke_ray_train_tpu.obs import runtime as obs_runtime
            obs_runtime.emit("ckpt_commit", step=int(step),
                             commit_s=float(commit_s), status=status)
        except Exception:  # noqa: BLE001 - telemetry never kills commits
            logger.debug("ckpt_commit event emission failed",
                         exc_info=True)

    def tear_mid_commit(self) -> int:
        """Drill hook for ``kill_during_commit``: freeze the in-flight
        commit in its mid-commit state (COMMITTING on disk, no
        COMMITTED), purge anything else queued — exactly the on-disk +
        in-memory state a SIGKILL during the commit leaves behind —
        and report the torn step. The manager is 'dead' afterwards
        (further saves no-op), like the process it stands in for."""
        if not self.async_commit:
            raise RuntimeError(
                "tear_mid_commit requires an async-commit manager "
                "(ASYNC_CKPT=1); the sync save path has no commit "
                "window to kill inside")
        with self._commit_lock:
            if self._commit_queue:
                step = int(self._commit_queue[-1][0])
                self._commit_queue.clear()
                self._stop = True
                self._commit_lock.notify_all()
                in_flight = False
            elif self._committing_now is not None:
                step = int(self._committing_now)
                self._abort_step = step
                deadline = time.monotonic() + self.commit_timeout_s
                while self._committing_now is not None:
                    if time.monotonic() > deadline:  # pragma: no cover
                        raise CheckpointCommitError(
                            f"committer did not tear step {step} within "
                            f"{self.commit_timeout_s}s")
                    self._commit_lock.wait(timeout=0.05)
                self._stop = True
                self._commit_lock.notify_all()
                in_flight = True
            else:
                raise RuntimeError(
                    "kill_during_commit fired with no in-flight commit; "
                    "schedule it on a step the checkpoint cadence "
                    "actually saves")
        if not in_flight:
            # the kill landed between the write-ahead record and the
            # serialize: marker + a torn partial step directory
            self._write_marker(_WAL_OPEN, step)
            d = os.path.join(str(self.directory), str(step))
            os.makedirs(d, exist_ok=True)
            with open(os.path.join(d, "_PARTIAL"), "wb") as f:
                f.write(b"\x00" * 64)
        self.last_torn_step = step
        logger.warning("checkpoint commit of step %d torn by "
                       "kill_during_commit drill", step)
        return step

    # ------------------------------------------------------------------
    # queries

    def latest_step(self) -> Optional[int]:
        self._flush_marks()
        steps = [int(s) for s in self._mgr.all_steps()
                 if self._step_eligible(int(s))]
        return max(steps) if steps else None

    def best_step(self) -> Optional[int]:
        return self._mgr.best_step()

    # ------------------------------------------------------------------
    # restore

    def restore(self, state_like: Any, step: Optional[int] = None) -> Any:
        """Restore into the shardings/dtypes of ``state_like`` (an abstract
        or concrete pytree — shardings are honored, so a checkpoint saved
        on one mesh restores resharded onto another)."""
        step = step if step is not None else self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoint in {self.directory}")
        abstract = jax.tree.map(ocp.utils.to_shape_dtype_struct, state_like)
        return self._mgr.restore(step,
                                 args=ocp.args.StandardRestore(abstract))

    def restore_resharded(self, state_like: Any, mesh, spec_tree: Any,
                          step: Optional[int] = None) -> Any:
        """Cross-topology restore: re-derive shardings from the LOGICAL
        PartitionSpec tree on a NEW mesh instead of reusing the saved
        layout — the reshard-on-restore path of elastic resume (ROADMAP
        #1: save on a 16-chip mesh, restore on 8). ``spec_tree`` must be
        structurally isomorphic to ``state_like`` (params:
        ``models.transformer.param_specs``; optimizer state:
        ``train.step.opt_state_specs``; an ExecutionPlan supplies the
        batch/mesh side). ``analysis plancheck`` (PLAN003) statically
        proves every (save, restore) topology pair this path will be
        asked to handle is well-formed — same logical shapes, valid
        shardings on the restore mesh."""
        from jax.sharding import NamedSharding

        abstract = jax.tree.map(
            lambda leaf, spec: jax.ShapeDtypeStruct(
                leaf.shape, leaf.dtype,
                sharding=NamedSharding(mesh, spec)),
            jax.tree.map(ocp.utils.to_shape_dtype_struct, state_like),
            spec_tree)
        return self.restore(abstract, step=step)

    def restore_raw(self, step: Optional[int] = None) -> Any:
        """Topology-free restore: structure/shapes come from checkpoint
        metadata, everything lands on this host's first device — the
        offline-converter path, where the save-time mesh (TPU pod) does
        not exist on the converting machine."""
        step = step if step is not None else self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoint in {self.directory}")
        meta = self._mgr.item_metadata(step)
        sh = jax.sharding.SingleDeviceSharding(jax.devices()[0])
        abstract = jax.tree.map(
            lambda m: jax.ShapeDtypeStruct(m.shape, m.dtype, sharding=sh),
            meta, is_leaf=lambda x: hasattr(x, "shape"))
        return self._mgr.restore(step, args=ocp.args.StandardRestore(abstract))

    def item_metadata(self, step: Optional[int] = None) -> Any:
        """Shape/dtype metadata tree of a saved checkpoint (no data
        read) — drives the converter's leaf-by-leaf walk.

        Read via a standalone PyTreeCheckpointer on the step's item dir:
        the manager's own ``item_metadata`` returns an EMPTY tree in any
        process that has not yet registered a 'default' handler (i.e.
        every fresh converter process) and only warns about it."""
        step = step if step is not None else self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoint in {self.directory}")
        ckptr = ocp.PyTreeCheckpointer()
        meta = ckptr.metadata(
            os.path.join(str(self.directory), str(step), "default"))
        return meta.item_metadata.tree

    def restore_partial(self, abstract: Any,
                        step: Optional[int] = None) -> Any:
        """Restore only a subset of the saved tree — the offline
        converter reads one leaf at a time this way, so a 70B conversion
        needs O(one leaf) RAM instead of the whole tree (VERDICT r3 weak
        #4b).

        ``abstract`` is the full structure with every unwanted leaf
        replaced by ``ocp.PLACEHOLDER``."""
        step = step if step is not None else self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoint in {self.directory}")
        return self._mgr.restore(
            step, args=ocp.args.PyTreeRestore(item=abstract))

    @staticmethod
    def _any_host_failed(local_failed: bool) -> bool:
        """Collective agreement on a restore outcome: every host enters,
        every host leaves with the same verdict — the prerequisite for
        a fallback/quarantine that cannot diverge the slice."""
        if jax.process_count() <= 1:
            return local_failed
        from jax.experimental import multihost_utils
        import numpy as np
        flags = multihost_utils.process_allgather(
            np.asarray(1 if local_failed else 0, np.int32))
        return bool(np.max(flags))

    def _quarantine(self, step: int) -> str:
        """Move an unrestorable step directory aside (``<step>.corrupt``)
        so it never shadows a good checkpoint again, drop its write-ahead
        markers (the marker state must always describe the CURRENT save
        of a step — a later re-save of the same step writes fresh ones),
        and refresh the manager's step cache. All hosts enter (the
        verdict was collective); host 0 renames, everyone syncs before
        reloading."""
        import shutil

        src = os.path.join(str(self.directory), str(step))
        dst = src + ".corrupt"
        n = 0
        while os.path.exists(dst):
            n += 1
            dst = f"{src}.corrupt{n}"
        multi = jax.process_count() > 1
        if not multi or jax.process_index() == 0:
            if os.path.isdir(src):
                shutil.move(src, dst)
        self._remove_marker(_WAL_OPEN, step)
        self._remove_marker(_WAL_DONE, step)
        self._pending_marks.discard(int(step))
        if multi:
            from jax.experimental import multihost_utils
            multihost_utils.sync_global_devices(f"ckpt_quarantine_{step}")
        if hasattr(self._mgr, "reload"):
            self._mgr.reload()
        else:  # pragma: no cover - pre-reload orbax
            self._mgr = ocp.CheckpointManager(self.directory,
                                              options=self._options)
        return dst

    def restore_if_available(self, state_like: Any):
        """(state, resumed_step) — the resume-on-retry behavior the
        reference lacks. Returns (state_like, None) on a fresh start.

        Recovery order: (1) the write-ahead sweep — in async-commit mode
        every mid-commit step (COMMITTING without COMMITTED) 'never
        existed' and is quarantined WITHOUT a restore attempt; (2) the
        peer slice's hot state, when a replicator is bound and its step
        is at least as new as the latest committed one — no storage
        read at all; (3) the verify-by-restoring walk below.

        Integrity fallback: the latest step is VERIFIED by restoring it;
        when that fails (an interrupted async save left a committed but
        torn tail — without this, every subsequent resume crashes on the
        same bad step) the newest earlier restorable step is used and
        each newer unrestorable step is quarantined as ``<step>.corrupt``.
        When EVERY step fails the first error re-raises and nothing is
        quarantined: that signature is a template/layout mismatch (the
        caller's pytree, not the data, is wrong — see the ckpt_view
        fallback in train/loop.py), and quarantining healthy checkpoints
        on a caller bug would destroy the run's only resume points.

        Each step gets one bounded retry before being declared
        unrestorable — a transient storage flake must not cost the
        newest checkpoint. On multi-host runs every verdict is
        COLLECTIVE (``_any_host_failed``): a step counts as failed when
        ANY host failed it, all hosts retry/fall back/quarantine in
        lockstep, and a host whose local restore succeeded discards the
        result rather than diverge — per-host divergence here would
        wedge the slice in its next collective."""
        self.last_restore_source = None
        self.last_peer_restore = None
        self._flush_marks()
        if self.async_commit:
            self._purge_uncommitted()
        steps = sorted(int(s) for s in self._mgr.all_steps())
        steps.reverse()
        # sync mode: marker-suspect steps (lazy flush lost with the
        # process) are still verified below — promoted on success
        suspects = {s for s in steps if not self._step_eligible(s)}
        if self.peer is not None:
            latest_ok = max((s for s in steps if s not in suspects),
                            default=None)
            peer_step = self.peer.peek(str(self.directory))
            if peer_step is not None and (latest_ok is None or
                                          int(peer_step) >= latest_ok):
                try:
                    out, meta = self.peer.restore(str(self.directory),
                                                  state_like)
                except Exception as e:  # noqa: BLE001 - fall to storage
                    logger.warning(
                        "peer hot-state restore failed (%s: %s); "
                        "falling back to storage",
                        type(e).__name__, e)
                else:
                    self.last_restore_resharded = None
                    self.last_restore_source = "peer"
                    self.last_peer_restore = dict(meta)
                    logger.info(
                        "resuming from PEER slice %s hot state at step "
                        "%d (no storage read)",
                        meta.get("from_slice"), int(peer_step))
                    return out, int(peer_step)
        if not steps:
            return state_like, None
        first_err: Optional[Exception] = None
        failed: list = []
        for step in steps:
            out = err = None
            restored = False
            t_restore0 = time.perf_counter()
            for restore_try in range(2):
                try:
                    out = self.restore(state_like, step)
                except Exception as e:  # noqa: BLE001 - classified below
                    err = e
                if not self._any_host_failed(err is not None):
                    restored = True
                    break
                if err is None:
                    # this host restored fine but another did not — the
                    # verdict is collective, so align with the failure
                    err = CheckpointRestoreError(
                        f"step {step} failed to restore on another host")
                out = None
                if restore_try == 0:
                    logger.warning(
                        "restore of step %d failed (%s: %s); retrying "
                        "once before treating it as corrupt", step,
                        type(err).__name__, err)
                    err = None
            if not restored:
                first_err = first_err if first_err is not None else err
                failed.append((step, err))
                continue
            for bad, bad_err in failed:
                logger.warning(
                    "checkpoint step %d is unrestorable (%s: %s); "
                    "quarantining it and resuming from step %d",
                    bad, type(bad_err).__name__, bad_err, step)
                self._quarantine(bad)
            if step in suspects:
                # the save was durable after all — only the marker
                # flush died with the process; heal the record
                self._mark_committed(step)
            # elastic-resume witness: a restore onto a different device
            # count than the save is a reshard (shardings re-derived
            # from the template) — say so, and leave the evidence for
            # the trainer's attempt log
            self.last_restore_resharded = None
            self.last_restore_source = "storage"
            note = self.saved_topology()
            cur_n = _tree_n_devices(state_like)
            if note and cur_n and int(note.get("n_devices", 0)) and \
                    int(note["n_devices"]) != cur_n:
                self.last_restore_resharded = (int(note["n_devices"]),
                                               cur_n)
                logger.warning(
                    "elastic resume: checkpoint step %d was saved on %d "
                    "devices; restored RESHARDED onto %d (shardings "
                    "re-derived from the restore template)",
                    step, int(note["n_devices"]), cur_n)
                # obs: the resharded restore IS the reshard witness —
                # one event per actual mesh transition (8->4 AND 4->8
                # in the elastic drill), rendered on the attempt
                # timeline by `obs report` (no-op when obs is off)
                from gke_ray_train_tpu.obs import runtime as obs_runtime
                obs_runtime.emit("reshard", step=step,
                                 from_devices=int(note["n_devices"]),
                                 to_devices=cur_n)
                # the restore-level half of the reshard span twin pair
                # (rayint/elastic.py spans the plan re-formation): how
                # long the RESHARDED restore itself took
                obs_runtime.span_add(
                    "reshard", time.perf_counter() - t_restore0,
                    step=step, from_devices=int(note["n_devices"]),
                    to_devices=cur_n, where="restore")
            logger.info("resuming from checkpoint step %d in %s", step,
                        self.directory)
            return out, step
        raise first_err

    # ------------------------------------------------------------------
    # lifecycle

    def wait(self, timeout: Optional[float] = None) -> None:
        """Block until every save is durable AND committed (call before
        process exit). In async-commit mode this drains the committer
        queue — bounded by ``CKPT_COMMIT_TIMEOUT_S`` — and re-raises any
        background commit failure so it cannot be silently lost."""
        if self.async_commit:
            budget = self.commit_timeout_s if timeout is None \
                else float(timeout)
            deadline = time.monotonic() + budget
            with self._commit_lock:
                while (self._commit_queue
                       or self._committing_now is not None):
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        raise CheckpointCommitError(
                            f"checkpoint commit queue did not drain "
                            f"within {budget}s "
                            f"(CKPT_COMMIT_TIMEOUT_S)")
                    self._commit_lock.wait(timeout=min(remaining, 0.1))
                if self._commit_error is not None:
                    err = self._commit_error
                    self._commit_error = None
                    raise CheckpointCommitError(
                        "background checkpoint commit failed") from err
        self._mgr.wait_until_finished()
        self._flush_marks()

    def close(self) -> None:
        if self._committer is not None and self._committer.is_alive():
            with self._commit_lock:
                self._stop = True
                self._commit_lock.notify_all()
            self._committer.join(timeout=self.commit_timeout_s)
        try:
            self._flush_marks()
        except Exception:  # noqa: BLE001 - close is best-effort
            logger.debug("marker flush on close failed", exc_info=True)
        self._mgr.close()

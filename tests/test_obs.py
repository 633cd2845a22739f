"""obs/ — unified run telemetry (ISSUE 11).

Contract tests: the event/metric schema is PINNED (shipped schema files == code vocabularies), the
anomaly-capture drill proves fire-once semantics on the CPU mesh with
injected faults, `obs report` over the elastic 8->4->8 drill shows both
reshards with every attempt's ledger reconciling to its wall-clock
exactly, and the hot-path guarantee is asserted the strong way: the
loss stream with obs enabled is BITWISE-identical to obs off.
"""

import json
import logging
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gke_ray_train_tpu.obs import events as obs_events
from gke_ray_train_tpu.obs import metrics as obs_metrics
from gke_ray_train_tpu.obs import runtime as obs_runtime

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_session(monkeypatch):
    """Every test starts with no active obs session and fresh identity
    env (the suite-wide OBS=0 from conftest stays in force unless a
    test opts in explicitly)."""
    obs_runtime.end_attempt("test-cleanup")
    monkeypatch.delenv("OBS_RUN_ID", raising=False)
    monkeypatch.delenv("OBS_ATTEMPT", raising=False)
    monkeypatch.delenv("OBS_DIR", raising=False)
    monkeypatch.delenv("OBS_PARENT_SPAN", raising=False)
    yield
    obs_runtime.end_attempt("test-cleanup")


# ---------------------------------------------------------------------------
# schema contracts
# ---------------------------------------------------------------------------

def test_event_schema_pinned():
    # shipped file == code vocabulary, both directions
    assert obs_events.check_schema() == []
    # the stamp is the cross-artifact correlation contract
    assert obs_events.STAMP_FIELDS == (
        "ts", "run_id", "attempt", "rank", "slice", "step",
        "plan_fingerprint", "kind")
    # closed vocabulary: unknown kinds and stray payload fields raise
    with pytest.raises(obs_events.EventError):
        obs_events.validate_event("made_up_kind", {})
    with pytest.raises(obs_events.EventError):
        obs_events.validate_event("resume", {"stray_field": 1})
    obs_events.validate_event("resume", {"resumed_step": 4})


def test_metric_schema_pinned():
    assert obs_metrics.check_schema() == []
    reg = obs_metrics.MetricsRegistry()
    with pytest.raises(obs_metrics.MetricError):
        reg.counter("made_up_metric")
    with pytest.raises(obs_metrics.MetricError):
        reg.counter("loss")        # declared a gauge
    # goodput_* mirror the ledger terms exactly (one source)
    from gke_ray_train_tpu.train.metrics import LEDGER_TERMS
    assert {f"goodput_{t}" for t in LEDGER_TERMS} | \
        {"goodput_wall_s", "goodput_frac"} == \
        {k for k in obs_metrics.METRIC_NAMES if k.startswith("goodput_")}
    # report's jax-free term list cannot drift from the ledger either
    from gke_ray_train_tpu.obs.report import LEDGER_TERMS as REPORT_TERMS
    assert tuple(REPORT_TERMS) == LEDGER_TERMS


def test_registry_exports(tmp_path):
    reg = obs_metrics.MetricsRegistry(labels={"run_id": "r1", "rank": "0"})
    reg.counter("steps_total").inc(3)
    reg.gauge("loss").set(1.25)
    for v in (0.01, 0.02, 0.5):
        reg.histogram("step_time_s").observe(v)
    reg.set_many({"mfu": 0.4, "not_a_metric": 9.9, "loss": float("nan")})
    snap = reg.snapshot()
    assert snap["steps_total"] == 3 and snap["mfu"] == 0.4
    assert "not_a_metric" not in snap
    assert snap["loss"] == 1.25          # NaN set_many is dropped
    assert snap["step_time_s"]["count"] == 3
    assert snap["step_time_s"]["p99"] == 0.5
    paths = reg.export(str(tmp_path), 0)
    doc = json.load(open(paths[".json"]))
    assert set(doc) - {"labels"} <= set(obs_metrics.METRIC_NAMES)
    prom = open(paths[".prom"]).read()
    assert '# TYPE grt_loss gauge' in prom
    assert 'grt_loss{rank="0",run_id="r1"} 1.25' in prom
    assert 'grt_steps_total{rank="0",run_id="r1"} 3' in prom
    assert 'quantile="0.99"' in prom


def test_configure_run_logging_prefix(capsys):
    from gke_ray_train_tpu.logging_utils import configure_run_logging
    root = logging.getLogger()
    h = logging.Handler()
    records = []
    h.emit = lambda rec: records.append(rec.getMessage())
    root.addHandler(h)
    try:
        configure_run_logging("abc123", 2, 1)
        logging.getLogger("some.module").warning("hello %d", 7)
        # re-arm with a new attempt: the old filter is REPLACED
        configure_run_logging("abc123", 3, 1)
        logging.getLogger("some.module").warning("again")
    finally:
        root.removeHandler(h)
    assert records[0] == "[run=abc123 a2 r1] hello 7"
    assert records[1] == "[run=abc123 a3 r1] again"


# ---------------------------------------------------------------------------
# loop integration: bitwise A/B + anomaly-capture drill
# ---------------------------------------------------------------------------

def _batches(steps, B=2, S=16, vocab=128, hook=None):
    def gen(epoch):
        for i in range(steps):
            if hook is not None:
                hook(i)
            k = jax.random.key(i)
            yield {"inputs": jax.random.randint(k, (B, S), 0, vocab),
                   "targets": jax.random.randint(k, (B, S), 0, vocab),
                   "weights": jnp.ones((B, S), jnp.float32)}
    return gen


def test_obs_off_hot_path_bitwise(tmp_path, tiny_train_setup):
    """The acceptance gate: the loss stream with obs fully enabled —
    including causal span tracing, which defaults on (TRACE=1) — is
    BITWISE-identical to obs off: telemetry adds no device traffic
    and perturbs no numerics. Both arms start from the SAME shared
    step-0 state, which is the A/B discipline anyway."""
    from gke_ray_train_tpu.train.loop import run_training

    def run(with_obs):
        _, _, state, step = tiny_train_setup
        if with_obs:
            obs_runtime.start_attempt(
                obs_dir=str(tmp_path / "obs_on"))
        try:
            final, m = run_training(state, step, _batches(8), epochs=1,
                                    log_every=2)
        finally:
            obs_runtime.end_attempt("ok")
        return float(m["loss"]), jax.device_get(final.params)

    loss_off, params_off = run(False)
    loss_on, params_on = run(True)
    assert loss_on == loss_off          # bitwise, not approx
    flat_off = jax.tree_util.tree_leaves(params_off)
    flat_on = jax.tree_util.tree_leaves(params_on)
    assert all(np.array_equal(a, b) for a, b in zip(flat_on, flat_off))
    # and the enabled run actually produced telemetry — events AND
    # spans (tracing was on, so the bitwise claim covers TRACE=1)
    evs = [json.loads(line) for line in
           open(tmp_path / "obs_on" / "events-r0.jsonl")]
    assert {"step", "worker_exit"} <= {e["kind"] for e in evs}
    sps = [json.loads(line) for line in
           open(tmp_path / "obs_on" / "spans-r0.jsonl")]
    assert {"compile", "step_window", "attempt"} <= \
        {s["name"] for s in sps}


def test_anomaly_capture_fire_once(tmp_path, tiny_train_setup):
    """The drill the ISSUE names: injected data stall + injected
    mid-run recompile on the CPU mesh; each anomaly class fires
    exactly ONE capture with a real artifact, and a second stall does
    not re-fire."""
    from gke_ray_train_tpu.train.loop import run_training
    _, _, state, step = tiny_train_setup
    steps = 26
    STALLS, COMPILE_AT = (12, 18), 22

    def hook(i):
        if i in STALLS:
            time.sleep(0.35)                      # input-pipeline stall
        if i == COMPILE_AT:
            jax.jit(lambda x: x * 3)(jnp.ones(()))  # mid-run compile

    run = obs_runtime.start_attempt(obs_dir=str(tmp_path))
    assert run is not None and run.capture is not None
    try:
        run_training(state, step, _batches(steps, hook=hook), epochs=1,
                     log_every=5)
    finally:
        obs_runtime.end_attempt("ok")

    evs = [json.loads(line) for line in open(tmp_path / "events-r0.jsonl")]
    anomalies = [e for e in evs if e["kind"] == "anomaly"]
    captures = [e for e in evs if e["kind"] == "capture"]
    by_class = {}
    for a in anomalies:
        by_class.setdefault(a["class"], []).append(a)
    # fire-once: ONE anomaly per class despite two injected stalls
    assert len(by_class.get("data_stall", [])) == 1
    assert len(by_class.get("recompile", [])) == 1
    cap_classes = sorted(c["class"] for c in captures)
    assert cap_classes.count("data_stall") == 1
    assert cap_classes.count("recompile") == 1
    for c in captures:
        assert not c["failed"]
        marker = os.path.join(c["artifact"], "capture.json")
        assert os.path.exists(marker), c
        doc = json.load(open(marker))
        assert doc["class"] == c["class"]
    # counters agree with the event stream
    mx = json.load(open(tmp_path / "metrics-r0.json"))
    assert mx["anomalies_total"] == len(anomalies)
    assert mx["captures_total"] == len(captures)
    assert mx["steps_total"] == steps
    assert mx["backend_compiles_total"] > 0


def test_capture_budget_and_trace_conflict(tmp_path):
    """Budget 0 = detection without captures; an external in-flight
    trace defers arming (jax.profiler is process-global)."""
    from gke_ray_train_tpu.obs.capture import CaptureManager
    emitted = []
    cm = CaptureManager(str(tmp_path), emit_fn=lambda k, **kw:
                        emitted.append((k, kw)), budget=0,
                        warmup_steps=2)
    for i in range(3):
        cm.note_step(i, 0.001, 0.0)
    cm.note_step(3, 0.001, 5.0)      # stall, but budget is 0
    for i in range(4, 8):
        cm.note_step(i, 0.001, 0.0)
    kinds = [k for k, _ in emitted]
    assert kinds.count("anomaly") == 1 and "capture" not in kinds

    cm2 = CaptureManager(str(tmp_path / "c2"), emit_fn=lambda k, **kw:
                         emitted.append((k, kw)), budget=2,
                         warmup_steps=2,
                         trace_conflict=lambda: True)
    for i in range(3):
        cm2.note_step(i, 0.001, 0.0)
    cm2.note_step(3, 0.001, 5.0)
    for i in range(4, 10):
        cm2.note_step(i, 0.001, 0.0)
    # anomaly recorded, but the conflicting trace kept the capture
    # pending the whole run — nothing started
    assert cm2._active is None and not cm2.captured


# ---------------------------------------------------------------------------
# supervisor satellite
# ---------------------------------------------------------------------------

def test_supervisor_metrics_view_names_stalled_rank(tmp_path):
    from gke_ray_train_tpu.rayint.supervisor import HeartbeatBoard
    board = HeartbeatBoard()
    board.set_slices({0: 0, 1: 1})
    board.beat(0, 5)
    board.beat(1, 5)
    board.beat(0, 6)                 # rank 1 stops progressing
    time.sleep(0.05)
    board.beat(0, 7)                 # rank 0 keeps beating
    view = board.metrics_view(timeout_s=0.02)
    assert set(view["ranks"]) == {"0", "1"}
    assert view["ranks"]["1"]["slice"] == 1
    stalled_ranks = [s["rank"] for s in view["stalled"]]
    assert stalled_ranks == [1]      # rank 1 named, rank 0 fresh... ish
    # the driver-side exporter writes it where `obs report` reads it
    drv = obs_runtime.DriverObs(str(tmp_path), "runX")
    drv.export_supervisor(view)
    drv.close()
    doc = json.load(open(tmp_path / "supervisor.json"))
    assert doc["stalled"][0]["rank"] == 1
    assert doc["ranks"]["1"]["step"] == 5


def test_watchdog_pre_interrupt_hook_fires():
    from gke_ray_train_tpu.rayint.supervisor import HeartbeatBoard, Watchdog
    board = HeartbeatBoard()
    board.beat(0, 1)
    seen = []
    wd = Watchdog(board, timeout_s=0.05, poll_s=0.02,
                  on_stall=lambda stalled: seen.append(("kill", stalled)),
                  pre_interrupt=lambda stalled: seen.append(("pre", stalled)))
    wd.start()
    time.sleep(0.4)
    wd.stop()
    assert [tag for tag, _ in seen] == ["pre", "kill"]


# ---------------------------------------------------------------------------
# tb satellite
# ---------------------------------------------------------------------------

class _StubWriter:
    """Duck-typed tb writer recording calls (no TB backend needed)."""

    def __init__(self):
        self.scalars = {}
        self.flushes = 0
        self.closed = False
        self._w = True       # satisfies TensorBoardWriter.log_registry

    def log(self, step, metrics):
        for k, v in metrics.items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                self.scalars[k] = float(v)

    def log_registry(self, step, registry):
        from gke_ray_train_tpu.train.tb import TensorBoardWriter
        TensorBoardWriter.log_registry(self, step, registry)

    def flush(self):
        self.flushes += 1

    def close(self):
        self.closed = True


def test_tb_flush_on_preempt_and_ledger_scalars(tmp_path,
                                                tiny_train_setup):
    """The satellite fix: a preempted attempt flushes its scalars
    BEFORE the grace-window save (SIGKILL-proof), and the goodput
    ledger reaches TB from the obs registry — no second computation."""
    from gke_ray_train_tpu.ckpt import CheckpointManager
    from gke_ray_train_tpu.testing.faults import (
        FaultInjector, parse_fault_spec, reset_fired)
    from gke_ray_train_tpu.train import preempt
    from gke_ray_train_tpu.train.loop import run_training
    from gke_ray_train_tpu.train.preempt import Preempted
    _, _, state, step = tiny_train_setup
    reset_fired()
    preempt.reset()
    w = _StubWriter()
    mgr = CheckpointManager(str(tmp_path / "ckpt"), max_to_keep=2,
                            score_attribute=None, async_save=False)
    inj = FaultInjector(parse_fault_spec("rank=0:kind=sigterm:step=4"),
                        rank=0, ckpt_manager=mgr)
    obs_runtime.start_attempt(obs_dir=str(tmp_path / "obs"))
    try:
        with pytest.raises(Preempted):
            run_training(state, step, _batches(8), epochs=1,
                         ckpt_manager=mgr, fault_injector=inj,
                         tb_writer=w, log_every=2)
    finally:
        mgr.close()
        preempt.reset()
        preempt.uninstall()
        obs_runtime.end_attempt("preempted")
    assert w.flushes >= 1            # flushed at the preempt boundary
    assert w.closed                  # and still closed by the finally
    # ledger terms arrived as obs/goodput_* scalars via log_registry
    assert "obs/goodput_step_s" in w.scalars
    assert "obs/goodput_compile_s" in w.scalars
    assert w.scalars["obs/steps_total"] == 4


# ---------------------------------------------------------------------------
# plan knobs
# ---------------------------------------------------------------------------

def test_obs_plan_knobs_three_dialects():
    from gke_ray_train_tpu.plan import ExecutionPlan
    via_json = ExecutionPlan.from_config(
        {"OBS": False, "OBS_DIR": "/x/obs", "OBS_CAPTURE": 0,
         "OBS_CAPTURE_BUDGET": 7})
    via_env = ExecutionPlan.from_env(
        {"OBS": "false", "OBS_DIR": "/x/obs", "OBS_CAPTURE": "off",
         "OBS_CAPTURE_BUDGET": "7"})
    via_kw = ExecutionPlan.from_kwargs(obs=False, obs_dir="/x/obs",
                                       obs_capture=False,
                                       obs_capture_budget=7)
    assert via_json == via_env == via_kw
    assert via_json.fingerprint() == via_kw.fingerprint()
    # telemetry knobs are OPERATIONAL: they must never stale a compiled
    # artifact on either surface
    base = ExecutionPlan()
    toggled = ExecutionPlan.from_kwargs(obs=False, obs_capture_budget=9)
    for surface in ("train", "serve", "all"):
        assert base.compile_fingerprint(surface) == \
            toggled.compile_fingerprint(surface)
    # obs_dir is RUN-scoped (a drill points it at a mktemp dir):
    # two runs of the byte-identical plan must share a fingerprint
    assert ExecutionPlan.from_kwargs(obs_dir="/tmp/a").fingerprint() \
        == ExecutionPlan.from_kwargs(obs_dir="/tmp/b").fingerprint() \
        == base.fingerprint()
    with pytest.raises(Exception):
        ExecutionPlan.from_kwargs(obs_capture_budget=-1)


def test_resolve_obs_dir_precedence(monkeypatch):
    from gke_ray_train_tpu.obs.runtime import resolve_obs_dir
    monkeypatch.setenv("OBS", "1")
    assert resolve_obs_dir(None, {"OBS_DIR": "/d"}) == "/d"
    assert resolve_obs_dir(None, {"OUTPUT_DIR_BASE": "/o"}) == "/o/obs"
    assert resolve_obs_dir(
        None, {"storage_path": "/s", "run_name": "r"}) == "/s/r/obs"
    assert resolve_obs_dir(None, {}) is None
    assert resolve_obs_dir(None, {"OBS": "0", "OBS_DIR": "/d"}) is None


# ---------------------------------------------------------------------------
# the elastic drill: events + report + reconciliation + CLI
# ---------------------------------------------------------------------------

def _elastic_drill(work):
    """The elastic drill (8->4->8 injected pool change
    through the real trainer) with obs enabled — shared by the report
    tests below."""
    from gke_ray_train_tpu.ckpt import CheckpointManager
    from gke_ray_train_tpu.models import tiny
    from gke_ray_train_tpu.parallel.placement import make_place_batch
    from gke_ray_train_tpu.plan import ExecutionPlan
    from gke_ray_train_tpu.rayint import (
        FailureConfig, JaxTrainer, RunConfig)
    from gke_ray_train_tpu.rayint.elastic import maybe_replan
    from gke_ray_train_tpu.testing.faults import (
        FaultInjector, parse_fault_spec, reset_fired, reset_pool)
    from gke_ray_train_tpu.train import (
        make_optimizer, make_train_state, make_train_step)
    from gke_ray_train_tpu.train.loop import run_training

    cfg = tiny(vocab_size=128, d_model=32, n_layers=1, n_heads=2,
               n_kv_heads=2, d_ff=64, dtype="float32",
               param_dtype="float32")
    opt = make_optimizer(1e-3)
    steps, shrink, grow, ck = 10, 4, 7, 2
    B, S = 8, 16
    obs_dir = os.path.join(work, "obs")
    config = {"MESH_DATA": 1, "MESH_FSDP": -1,
              "PER_DEVICE_TRAIN_BATCH_SIZE": 1, "MAX_SEQ_LENGTH": S,
              "TOPOLOGY": "cpu-8", "ELASTIC": "1",
              "OBS": "1", "OBS_DIR": obs_dir, "OBS_CAPTURE": "0"}

    def batches(epoch):
        for i in range(steps):
            rng = np.random.default_rng(epoch * 1000 + i)
            yield {"inputs": rng.integers(0, 128, (B, S)).astype(np.int32),
                   "targets": rng.integers(0, 128, (B, S)).astype(np.int32),
                   "weights": np.ones((B, S), np.float32)}

    def worker(c):
        plan, devs = maybe_replan(ExecutionPlan.resolve(c), config=c)
        mesh = plan.build_mesh(devs)
        state = make_train_state(cfg, opt, jax.random.key(0), mesh=mesh)
        step_fn = make_train_step(cfg, opt, mesh=mesh, donate=False)
        mgr = CheckpointManager(os.path.join(work, "ckpt"),
                                max_to_keep=2, score_attribute=None,
                                async_save=False)
        inj = FaultInjector(parse_fault_spec(
            f"rank=0:kind=pool_shrink:to=4:step={shrink};"
            f"rank=0:kind=pool_shrink:to=8:step={grow}"),
            rank=0, ckpt_manager=mgr)
        try:
            final, _m = run_training(
                state, step_fn, batches, epochs=1, ckpt_manager=mgr,
                ckpt_every=ck, log_every=2,
                place_batch=make_place_batch(mesh), fault_injector=inj)
        finally:
            mgr.close()
        # serve ONE request on the trained weights inside the same
        # attempt (the SERVE_AFTER_TRAIN shape, engine-direct): the
        # trace must decompose a request end-to-end — enqueue /
        # prefill / decode — beside the training spans (ISSUE 14)
        from gke_ray_train_tpu.serve.engine import BatchEngine, Request
        host_params = jax.device_get(final.params)
        engine = BatchEngine(
            host_params, cfg, eos_ids=(),
            plan=ExecutionPlan.from_kwargs(
                max_batch=2, decode_buckets="16", aot_train_step=False,
                compile_cache=False))
        comps = engine.run_until_drained([Request(
            rid="drill0", token_ids=np.arange(3, 9, dtype=np.int32),
            max_new_tokens=4)])
        return {"final_step": int(jax.device_get(final.step)),
                "served": len(comps)}

    reset_fired()
    reset_pool()
    try:
        res = JaxTrainer(
            worker, train_loop_config=config, use_ray=False,
            run_config=RunConfig(
                failure_config=FailureConfig(max_failures=0,
                                             max_preemptions=4),
                retry_backoff_s=0.0)).fit()
    finally:
        reset_pool()
    assert res.error is None and res.metrics["final_step"] == steps, res
    return obs_dir, res


@pytest.fixture(scope="module")
def elastic_drill(tmp_path_factory):
    """ONE traced 8->4->8 drill with serve-after-train, shared by the
    report AND trace/diff acceptance tests — the drill is the
    expensive part (five compiles across two mesh shapes) and both
    consumers only READ its artifacts (ISSUE 16 wall satellite)."""
    work = str(tmp_path_factory.mktemp("obs_elastic_drill"))
    obs_dir, res = _elastic_drill(work)
    return work, obs_dir, res


def test_obs_report_elastic_drill(elastic_drill):
    """The acceptance drill: a CPU-mesh run with injected pool_shrink
    events produces ONE report in which (a) every attempt's ledger
    terms sum to its wall-clock exactly, (b) both reshards (8->4 and
    4->8) appear on the attempt timelines, and (c) the per-attempt
    events classify shrink/grow as preemptions."""
    from gke_ray_train_tpu.obs.report import build_report
    work, obs_dir, res = elastic_drill
    rep = build_report(work)                # parent dir also accepted
    assert rep["n_attempts"] == res.attempts == 3
    assert rep["reconciled"] is True
    for a in rep["attempts"]:
        rec = a["reconciliation"]
        assert rec is not None and rec["ok"], a
        # exact identity, not approximate: lost_s was constructed as
        # the attempt-wall residual
        assert abs(rec["residual_s"]) <= 1e-6 * max(1.0, rec["wall_s"])
    assert [a.get("event") for a in rep["attempts"]] == \
        ["shrink", "grow", None]
    pairs = [(r["from_devices"], r["to_devices"])
             for a in rep["attempts"] for r in a.get("reshard", [])]
    assert (8, 4) in pairs and (4, 8) in pairs     # BOTH reshards
    # every record of every stream carries the same run id
    run_ids = {e.get("run_id")
               for e in obs_events.iter_events(obs_dir)}
    assert len(run_ids) == 1
    # the driver's summed ledger matches the trainer's Result
    assert abs(rep["goodput"]["wall_s"] - res.goodput["wall_s"]) < 1e-6


def test_terminal_pool_failure_attempt_still_reported(tmp_path,
                                                     tiny_train_setup):
    """A shrink below MIN_DEVICES ends the run from inside
    classify_pool — the terminal attempt must still get its
    attempt_end BEFORE run_end closes the driver stream, so the
    report shows the refusing-to-re-form attempt."""
    from gke_ray_train_tpu.ckpt import CheckpointManager
    from gke_ray_train_tpu.obs.report import build_report
    from gke_ray_train_tpu.rayint import (
        FailureConfig, JaxTrainer, RunConfig)
    from gke_ray_train_tpu.testing.faults import (
        FaultInjector, parse_fault_spec, reset_fired, reset_pool)
    from gke_ray_train_tpu.train.loop import run_training
    _, _, state, step = tiny_train_setup
    obs_dir = str(tmp_path / "obs")
    config = {"ELASTIC": "1", "MIN_DEVICES": "6",
              "OBS": "1", "OBS_DIR": obs_dir, "OBS_CAPTURE": "0"}

    def worker(c):
        mgr = CheckpointManager(str(tmp_path / "ckpt"), max_to_keep=2,
                                score_attribute=None, async_save=False)
        inj = FaultInjector(
            parse_fault_spec("rank=0:kind=pool_shrink:to=4:step=3"),
            rank=0, ckpt_manager=mgr)
        try:
            run_training(state, step, _batches(6), epochs=1,
                         ckpt_manager=mgr, ckpt_every=2,
                         fault_injector=inj)
        finally:
            mgr.close()
        return {}

    from gke_ray_train_tpu.train import preempt
    reset_fired()
    reset_pool()
    try:
        res = JaxTrainer(
            worker, train_loop_config=config, use_ray=False,
            run_config=RunConfig(
                failure_config=FailureConfig(max_failures=0,
                                             max_preemptions=4),
                retry_backoff_s=0.0)).fit()
    finally:
        reset_pool()
        # the run ENDS preempted-with-flag-up (no further attempt
        # resets it) — clear it or later tests in this process
        # preempt-exit at step 0
        preempt.reset()
        preempt.uninstall()
    assert res.status == "failed" and "MIN_DEVICES" in res.error
    rep = build_report(obs_dir)
    assert rep["n_attempts"] == len(res.attempt_log) == 1
    assert rep["attempts"][0]["status"] == "failed"
    assert rep["reconciled"] is True
    # run_end is the LAST driver record, after the terminal attempt_end
    kinds = [e["kind"] for e in obs_events.iter_events(obs_dir)
             if e.get("rank") == "driver"]
    assert kinds[-1] == "run_end" and "attempt_end" in kinds


def test_obs_report_cli_contract(tmp_path):
    """rc contract (pinned like the analysis CLIs): 0 = report written
    + ONE JSON summary line on stdout; 1 = no telemetry; 2 = usage;
    plus the schema verb."""
    env = dict(os.environ, PYTHONPATH=REPO)
    r = subprocess.run([sys.executable, "-m", "gke_ray_train_tpu.obs"],
                       capture_output=True, text=True, env=env)
    assert r.returncode == 2
    r = subprocess.run([sys.executable, "-m", "gke_ray_train_tpu.obs",
                        "report", str(tmp_path / "nothing_here")],
                       capture_output=True, text=True, env=env)
    assert r.returncode == 1, r.stderr
    r = subprocess.run([sys.executable, "-m", "gke_ray_train_tpu.obs",
                        "schema"], capture_output=True, text=True,
                       env=env)
    assert r.returncode == 0, r.stderr
    assert json.loads(r.stdout.strip())["ok"] is True

    # a real (tiny, no-trainer) run dir: one summary line, rc 0
    run = obs_runtime.start_attempt(obs_dir=str(tmp_path / "obs"))
    run.emit("attempt_start", topology="cpu-8", n_devices=8)
    run.note_step(1, 0.001, 0.0)
    obs_runtime.end_attempt("ok")
    r = subprocess.run([sys.executable, "-m", "gke_ray_train_tpu.obs",
                        "report", str(tmp_path / "obs"), "--text"],
                       capture_output=True, text=True, env=env)
    assert r.returncode == 0, r.stderr
    lines = [ln for ln in r.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1
    summary = json.loads(lines[0])
    assert summary["unit"] == "attempts" and summary["reconciled"]
    assert os.path.exists(summary["report"])
    assert "obs report" in r.stderr      # --text timeline on stderr


def test_report_driverless_multirank_one_attempt(tmp_path):
    """A driverless multi-process session writes one worker_exit per
    RANK; the report must still count one attempt (not world-size) and
    must not multiply the goodput totals."""
    from gke_ray_train_tpu.obs.events import EventLog, events_path
    from gke_ray_train_tpu.obs.report import build_report
    led = {"compile_s": 1.0, "step_s": 3.0, "wall_s": 4.0}
    for rank in (0, 1, 2):
        log = EventLog(events_path(str(tmp_path), rank), run_id="r",
                       attempt=1, rank=rank)
        log.emit("worker_exit", status="ok", goodput=led)
        log.close()
    rep = build_report(str(tmp_path))
    assert rep["n_attempts"] == 1
    assert rep["goodput"]["wall_s"] == 4.0          # not 12.0


def test_capture_start_failure_reported_failed(tmp_path, monkeypatch):
    """A capture whose start_trace failed must be emitted with
    failed=True — an operator must never be pointed at an empty
    artifact as good evidence."""
    import jax

    from gke_ray_train_tpu.obs.capture import CaptureManager

    def boom(*a, **k):
        raise RuntimeError("profiler busy")

    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    emitted = []
    cm = CaptureManager(str(tmp_path), emit_fn=lambda k, **kw:
                        emitted.append((k, kw)), budget=2,
                        warmup_steps=2)
    for i in range(3):
        cm.note_step(i, 0.001, 0.0)
    cm.note_step(3, 0.001, 5.0)          # stall -> arm capture
    for i in range(4, 10):
        cm.note_step(i, 0.001, 0.0)
    cm.close()
    caps = [kw for k, kw in emitted if k == "capture"]
    assert caps and caps[0]["failed"] is True


def test_report_rejects_unreconciled(tmp_path):
    """A doctored ledger (terms != wall) must flip the report to
    un-reconciled and the CLI to rc 3 — the invariant has teeth."""
    drv = obs_runtime.DriverObs(str(tmp_path), "runY")
    bad = {t: 0.0 for t in
           ("compile_s", "restore_s", "fast_forward_s", "data_stall_s",
            "eval_ckpt_stall_s", "step_s", "lost_s")}
    bad.update(step_s=1.0, wall_s=9.0)      # terms sum 1.0 != wall 9.0
    drv.note_attempt(1, {"status": "ok", "goodput": bad})
    drv.close()
    from gke_ray_train_tpu.obs.report import build_report
    rep = build_report(str(tmp_path))
    assert rep["reconciled"] is False
    env = dict(os.environ, PYTHONPATH=REPO)
    r = subprocess.run([sys.executable, "-m", "gke_ray_train_tpu.obs",
                        "report", str(tmp_path)],
                       capture_output=True, text=True, env=env)
    assert r.returncode == 3


def test_crashed_attempt_trace_still_reconciles(tmp_path,
                                                 tiny_train_setup):
    """Span/ledger coherence on the EXCEPTION path: a step that dies
    right after the ledger booked a data wait (and an eval that dies
    inside its paused() region) must not leave the span stream short
    of the ledger — a crashed run's report is exactly when the
    critical path matters, and rc=3 there would cry 'telemetry bug'
    over a training failure."""
    from gke_ray_train_tpu.obs.report import build_report
    from gke_ray_train_tpu.train.loop import run_training
    _, _, state, step = tiny_train_setup
    calls = {"n": 0}

    def crashing_step(st, batch):
        calls["n"] += 1
        if calls["n"] == 4:
            raise RuntimeError("boom mid-iteration")
        return step(st, batch)

    def hook(i):
        if i == 3:                 # the doomed call's batch: its wait
            time.sleep(0.06)       # is ledger-booked BEFORE the step

    obs_runtime.start_attempt(obs_dir=str(tmp_path / "a"))
    try:
        with pytest.raises(RuntimeError, match="boom"):
            run_training(state, crashing_step, _batches(8, hook=hook),
                         epochs=1, log_every=2)
    finally:
        obs_runtime.end_attempt("failed")
    rep = build_report(str(tmp_path / "a"))
    assert rep["critical_path_ok"] is True, \
        rep["attempts"][0].get("critical_path")

    # and the eval twin: paused(ledger) books on __exit__ even when
    # eval raises — the span must be emitted on that path too
    _, _, state2, step2 = tiny_train_setup

    def bad_eval(st):
        time.sleep(0.03)
        raise RuntimeError("eval boom")

    obs_runtime.start_attempt(obs_dir=str(tmp_path / "b"))
    try:
        with pytest.raises(RuntimeError, match="eval boom"):
            run_training(state2, step2, _batches(8), epochs=1,
                         log_every=2, eval_fn=bad_eval, eval_every=3)
    finally:
        obs_runtime.end_attempt("failed")
    rep = build_report(str(tmp_path / "b"))
    assert rep["critical_path_ok"] is True, \
        rep["attempts"][0].get("critical_path")
    spans = [json.loads(line) for line in
             open(tmp_path / "b" / "spans-r0.jsonl")]
    assert any(s["name"] == "eval" for s in spans)


def test_trace_critical_path_and_diff_on_elastic_drill(elastic_drill):
    """ISSUE 14 acceptance on the existing drill path: the 8->4->8 run
    produces ONE merged trace whose per-attempt critical path
    reconciles exactly with the goodput ledger (CLI rc=0), shows both
    reshard spans, and decomposes a serve request end-to-end; `obs
    diff` passes self-vs-self and trips with a named term delta on a
    doctored goodput_frac."""
    from gke_ray_train_tpu.obs import trace as obs_trace
    from gke_ray_train_tpu.obs.diff import diff_flat, flatten_report
    from gke_ray_train_tpu.obs.report import build_report
    _, obs_dir, res = elastic_drill
    assert res.metrics.get("served") == 1

    spans = list(obs_trace.iter_spans(obs_dir))
    assert spans, "the traced drill must leave a span stream"
    # ONE merged trace: every span of every rank + the driver agrees
    assert len({s["trace_id"] for s in spans}) == 1
    # worker attempt spans parent under the driver's attempt spans
    drv = {s["span_id"]: s for s in spans
           if s["rank"] == "driver" and s["name"] == "attempt"}
    wrk = [s for s in spans if s["rank"] != "driver"
           and s["name"] == "attempt"]
    assert len(drv) == 3 and len(wrk) == 3
    assert all(s["parent_id"] in drv for s in wrk)
    # both reshard transitions appear as spans (replan and/or the
    # resharded restore — the 8->4 AND the 4->8)
    reshard_pairs = {(s.get("from_devices"), s.get("to_devices"))
                     for s in spans if s["name"] == "reshard"}
    assert (8, 4) in reshard_pairs and (4, 8) in reshard_pairs
    # restore-level reshard witness fired on a resumed attempt
    assert any(s["name"] == "reshard" and s.get("where") == "restore"
               for s in spans)

    rep = build_report(obs_dir)
    assert rep["critical_path_ok"] is True
    for a in rep["attempts"]:
        cp = a.get("critical_path")
        assert cp is not None and cp["reconciliation"]["ok"], a
        # the exact contract: span-derived terms == the rank's ledger
        for term, d in cp["reconciliation"]["deltas"].items():
            assert abs(d) <= 1e-6 * max(1.0, cp["wall_s"]), (term, cp)
    # the serve request decomposes end-to-end in the trace section
    sv = rep["trace"]["serve"]
    assert sv["requests"] == 1
    ex = sv["slowest"]
    assert ex["rid"] == "drill0" and ex["generated"] == 4
    for phase in ("enqueue_s", "prefill_s", "decode_s"):
        assert phase in ex and ex[phase] >= 0
    assert ex["iterations"] >= 1

    # CLI rc=0 with the critical path present (rc=3 has teeth: a
    # doctored span stream must trip it — drilled in test_trace.py)
    env = dict(os.environ, PYTHONPATH=REPO)
    r = subprocess.run([sys.executable, "-m", "gke_ray_train_tpu.obs",
                        "report", obs_dir, "--text"],
                       capture_output=True, text=True, env=env)
    assert r.returncode == 0, r.stderr
    summary = json.loads(r.stdout.strip())
    assert summary["critical_path_ok"] and summary["spans"] > 0
    assert "critical path" in r.stderr     # the --text flame summary

    # obs diff: self-vs-self is clean; a doctored goodput regression
    # trips with the offending term named
    flat = flatten_report(rep)
    assert flat["n_attempts"] == 3 and flat["reshards"] == 2
    assert "cp_frac_step_s" in flat or "cp_frac_compile_s" in flat
    assert diff_flat(flat, flat) == []
    doctored = dict(flat)
    doctored["goodput_frac"] = flat["goodput_frac"] * 0.3
    viols = diff_flat(doctored, flat)
    assert viols and any("goodput_frac" in v for v in viols)

    report_path = os.path.join(obs_dir, "report.json")
    import json as _json
    with open(report_path, "w") as f:
        _json.dump(rep, f, default=str)
    r = subprocess.run([sys.executable, "-m", "gke_ray_train_tpu.obs",
                        "diff", report_path, report_path],
                       capture_output=True, text=True, env=env)
    assert r.returncode == 0, r.stderr


# ---------------------------------------------------------------------------
# serve engine integration
# ---------------------------------------------------------------------------

def test_serve_engine_exports_obs(tmp_path):
    """run_until_drained lands serve_start/serve_drained on the event
    stream and the p50/p99/occupancy numbers in the metric export —
    the engine's own stats() dict."""
    import dataclasses

    from gke_ray_train_tpu.models import init_params, llama3_8b
    from gke_ray_train_tpu.plan import ExecutionPlan
    from gke_ray_train_tpu.serve.engine import BatchEngine, Request
    cfg = dataclasses.replace(
        llama3_8b(), name="obs-serve-test", d_model=64, n_layers=1,
        n_heads=2, n_kv_heads=2, d_ff=128, vocab_size=128,
        max_seq_len=64, dtype="float32", param_dtype="float32",
        remat=False)
    plan = ExecutionPlan.from_kwargs(max_batch=2, decode_buckets="64",
                                     aot_train_step=False)
    params = init_params(cfg, jax.random.key(0))
    obs_runtime.start_attempt(obs_dir=str(tmp_path))
    try:
        engine = BatchEngine(params, cfg, plan=plan, eos_ids=())
        comps = engine.run_until_drained([
            Request(rid=f"r{i}",
                    token_ids=np.arange(3, 9, dtype=np.int32),
                    max_new_tokens=4) for i in range(3)])
    finally:
        obs_runtime.end_attempt("ok")
    assert len(comps) == 3
    evs = [json.loads(line) for line in open(tmp_path / "events-r0.jsonl")]
    drained = [e for e in evs if e["kind"] == "serve_drained"]
    assert drained and drained[-1]["stats"]["completed"] == 3
    mx = json.load(open(tmp_path / "metrics-r0.json"))
    assert mx["serve_completed_total"] == 3
    assert mx["serve_batch_occupancy"] > 0
    assert mx["serve_p50_token_latency_s"] >= 0
    # the workload-shape histogram (ISSUE 16 satellite): one
    # observation per ADMITTED request — prompt tokens plus the decode
    # budget, the number capacity planning actually sizes against
    rl = mx["request_len"]
    assert rl["count"] == 3
    assert rl["p50"] == 6 + 4      # len(token_ids) + max_new_tokens
    assert rl["sum"] == 3 * 10


def test_serve_multitenant_counters_export():
    """ISSUE 17: the feature-gated stats() keys (adapter pool, prefix
    cache, speculation) map onto the six serve_* counters — and a
    plain engine's stats, which LACK those keys, must leave the
    counters unregistered rather than exporting misleading zeros for
    features that are off."""
    from gke_ray_train_tpu.obs.metrics import (
        MetricsRegistry, export_serve_stats)
    base = {"iterations": 4, "refills": 0, "completed": 2,
            "batch_occupancy": 0.5, "p50_token_latency_s": 0.001,
            "p99_token_latency_s": 0.002}
    reg = MetricsRegistry()
    export_serve_stats(reg, dict(base))
    snap = reg.snapshot()
    for name in ("serve_adapter_hits_total", "serve_prefix_hits_total",
                 "serve_spec_proposed_total"):
        assert name not in snap, name
    export_serve_stats(reg, dict(
        base, adapter_hits=3, adapter_misses=2, adapter_evictions=1,
        prefix_hits=2, spec_proposed=12, spec_accepted=7))
    snap = reg.snapshot()
    assert snap["serve_adapter_hits_total"] == 3
    assert snap["serve_adapter_misses_total"] == 2
    assert snap["serve_adapter_evictions_total"] == 1
    assert snap["serve_prefix_hits_total"] == 2
    assert snap["serve_spec_proposed_total"] == 12
    assert snap["serve_spec_accepted_total"] == 7


# ---------------------------------------------------------------------------
# observed-run extraction (ISSUE 16: the obs -> autotune bridge)
# ---------------------------------------------------------------------------

def test_weighted_median_and_chip_family():
    from gke_ray_train_tpu.obs.observe import chip_family, weighted_median
    assert weighted_median([]) is None
    assert weighted_median([(0.5, 3.0)]) == 0.5
    # weights count: the heavy window wins even when outnumbered
    assert weighted_median([(1.0, 1.0), (2.0, 1.0), (3.0, 10.0)]) == 3.0
    # deterministic crossing: smallest value where cumulative weight
    # reaches half the total
    assert weighted_median([(1.0, 1.0), (2.0, 1.0)]) == 1.0
    assert chip_family("v5e-256") == "v5e"
    assert chip_family("cpu-8") == "cpu"
    assert chip_family(None) is None


def _synthetic_session(obs_dir, *, backend="cpu", fp="f" * 16):
    """Hand-written event/span streams shaped like one train attempt
    that also drained a serve engine — the driverless idiom of the
    report tests above, pointed at the extraction instead."""
    from gke_ray_train_tpu.obs.events import EventLog, events_path
    from gke_ray_train_tpu.obs.trace import SpanLog, spans_path
    log = EventLog(events_path(obs_dir, 0), run_id="obsrun", attempt=1,
                   rank=0, plan_fingerprint=fp)
    log.emit("attempt_start", topology="cpu-8", n_devices=8)
    if backend:
        log.emit("first_step", compile_s=1.0, backend=backend)
    log.emit("serve_drained", replica=0, stats={
        "completed": 3, "iterations": 12,
        "p50_token_latency_s": 0.002, "p99_token_latency_s": 0.004})
    log.emit("worker_exit", status="ok", goodput={
        "step_s": 6.0, "data_stall_s": 1.0, "wall_s": 10.0})
    log.close()
    sp = SpanLog(spans_path(obs_dir, 0), run_id="obsrun", attempt=1,
                 rank=0)
    # three windows; the weighted median must shrug off the slow one
    sp.emit("step_window", 1.0, steps=10, data_stall_s=0.0)  # 0.10/step
    sp.emit("step_window", 1.2, steps=10, data_stall_s=0.2)  # 0.10/step
    sp.emit("step_window", 2.0, steps=2, data_stall_s=0.0)   # 1.00/step
    sp.close()


def test_observed_runs_extraction_and_determinism(tmp_path):
    from gke_ray_train_tpu.obs.observe import observed_runs, row_measure
    _synthetic_session(str(tmp_path))
    rows = observed_runs(str(tmp_path))
    assert [r["surface"] for r in rows] == ["serve", "train"]
    serve, train = rows
    assert train["plan_fingerprint"] == "f" * 16
    assert train["topology"] == "cpu-8" and train["chip_family"] == "cpu"
    assert train["backend"] == "cpu"
    # (dur - data_stall) / steps, step-count-weighted median: the
    # 1.0s/step outlier window (2 steps) must not drag the number
    assert train["measured_step_s"] == 0.1
    assert train["steps"] == 22
    assert train["goodput_frac"] == 0.6
    assert train["data_stall_frac"] == 0.1
    assert serve["measured_per_token_s"] == 0.002
    assert serve["serve_p99_token_latency_s"] == 0.004
    assert row_measure(train) == 0.1 and row_measure(serve) == 0.002
    # re-extraction is bitwise-identical — the base of the ingest
    # idempotency contract (autotune/registry.py)
    assert json.dumps(rows, sort_keys=True) == \
        json.dumps(observed_runs(str(tmp_path)), sort_keys=True)


def test_observed_backend_never_inferred(tmp_path):
    """No first_step backend stamp -> backend stays None. The
    extraction NEVER guesses: ingest refuses None-backend rows, which
    is the first half of the CPU-rows-never-calibrate-a-TPU
    guarantee (the other half is the registry's backend gate)."""
    from gke_ray_train_tpu.obs.observe import observed_runs
    _synthetic_session(str(tmp_path), backend=None)
    rows = observed_runs(str(tmp_path))
    assert rows and all(r["backend"] is None for r in rows)


def test_report_backend_and_autotune_drift_section(tmp_path):
    """first_step's backend stamp and any autotune_drift events ride
    the report, render in the text view, and flatten into `obs diff`
    scalars with teeth (a drift event appearing — or the recorded
    drift fields VANISHING — trips the gate)."""
    from gke_ray_train_tpu.obs.diff import diff_flat, flatten_report
    from gke_ray_train_tpu.obs.events import EventLog, events_path
    from gke_ray_train_tpu.obs.report import build_report, render_text
    log = EventLog(events_path(str(tmp_path), 0), run_id="r",
                   attempt=1, rank=0)
    log.emit("first_step", compile_s=1.0, backend="cpu")
    log.emit("worker_exit", status="ok",
             goodput={"compile_s": 1.0, "step_s": 3.0, "wall_s": 4.0})
    log.emit("autotune_drift", key="train-cpu-8-abc", arm="tuned",
             measured_step_s=0.19, raw_modeled_step_s=0.019,
             corrected_modeled_step_s=0.038, rel_err=0.8, band=0.25,
             stale=True)
    log.close()
    rep = build_report(str(tmp_path))
    assert rep["backend"] == "cpu"
    at = rep["autotune"]
    assert at["drift_events"] == 1 and at["drift_stale"] == 1
    assert at["drift_max_rel_err"] == 0.8 and at["drift_band"] == 0.25
    assert at["drift_keys"] == ["train-cpu-8-abc"]
    txt = render_text(rep)
    assert "backend: cpu" in txt
    assert "1 STALE" in txt
    flat = flatten_report(rep)
    assert flat["autotune_drift_events"] == 1.0
    assert flat["autotune_drift_stale"] == 1.0
    assert flat["autotune_drift_max_rel_err"] == 0.8
    assert diff_flat(flat, flat) == []
    # a NEW drift event where the baseline recorded one is exact-gated
    viols = diff_flat({**flat, "autotune_drift_events": 2.0}, flat)
    assert any("autotune_drift_events" in v for v in viols)
    # recorded drift scalars missing from the fresh side = the
    # telemetry that produced them broke — also a trip
    clean = {k: v for k, v in flat.items()
             if not k.startswith("autotune_drift")}
    viols = diff_flat(clean, flat)
    assert any("autotune_drift" in v and "MISSING" in v for v in viols)

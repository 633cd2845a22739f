"""Causal span tracing + critical path + `obs diff` (ISSUE 14).

Unit-level contracts (the drill-level acceptance lives in
tests/test_obs.py::test_trace_critical_path_and_diff_on_elastic_drill):
the trace schema is pinned both directions, trace context propagates
across the trainer's worker-spawn env forwarding, driverless
multi-rank sessions merge to ONE trace, the critical-path
reconciliation has teeth (a doctored span stream exits 3), and the
`obs diff` regression gate holds its rc contract on the checked-in
fixture ledgers.
"""

import json
import os
import subprocess
import sys
import time

import pytest

from gke_ray_train_tpu.obs import runtime as obs_runtime
from gke_ray_train_tpu.obs import trace as obs_trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_session(monkeypatch):
    obs_runtime.end_attempt("test-cleanup")
    for k in ("OBS_RUN_ID", "OBS_ATTEMPT", "OBS_DIR", "OBS_PARENT_SPAN",
              "TRACE"):
        monkeypatch.delenv(k, raising=False)
    yield
    obs_runtime.end_attempt("test-cleanup")


# ---------------------------------------------------------------------------
# schema + span log contracts
# ---------------------------------------------------------------------------

def test_trace_schema_pinned_both_directions():
    assert obs_trace.check_schema() == []
    assert obs_trace.SPAN_STAMP == (
        "trace_id", "span_id", "parent_id", "name", "run_id",
        "attempt", "rank", "slice", "step", "t0", "t1", "dur_s")
    with pytest.raises(obs_trace.SpanError):
        obs_trace.validate_span("made_up_span", {})
    with pytest.raises(obs_trace.SpanError):
        obs_trace.validate_span("compile", {"stray": 1})
    obs_trace.validate_span("serve_decode", {"rid": "r", "iterations": 3})
    # the schema FILE must drift when the code does (both directions)
    doc = obs_trace.load_schema()
    assert set(doc["names"]) == set(obs_trace.SPAN_NAMES)


def test_span_term_mapping_pins_ledger_terms():
    """critical.py's span->term mapping is a jax-free string copy of
    the ledger vocabulary — pin it against the real LEDGER_TERMS."""
    from gke_ray_train_tpu.obs import critical
    from gke_ray_train_tpu.train.metrics import LEDGER_TERMS
    assert set(critical.SPAN_TERM.values()) <= set(LEDGER_TERMS)
    assert set(critical.RECONCILED_TERMS) <= set(LEDGER_TERMS)
    # every term-mapped span name is in the pinned schema vocabulary
    assert set(critical.SPAN_TERM) <= set(obs_trace.SPAN_NAMES)


def test_span_log_roundtrip_and_deterministic_trace_id(tmp_path):
    a = obs_trace.SpanLog(obs_trace.spans_path(str(tmp_path), 0),
                          run_id="runA", attempt=1, rank=0)
    rec = a.emit("compile", 1.5, step=3)
    child = a.emit("serve_prefill", 0.2, parent_id=rec["span_id"],
                   rid="r0")
    a.close()
    # a second process that only knows the run id joins the same trace
    assert obs_trace.trace_id_for_run("runA") == rec["trace_id"]
    spans = list(obs_trace.iter_spans(str(tmp_path)))
    assert [s["name"] for s in spans] in (
        [rec["name"], "serve_prefill"], ["serve_prefill", rec["name"]])
    got = {s["span_id"]: s for s in spans}
    assert got[child["span_id"]]["parent_id"] == rec["span_id"]
    assert got[rec["span_id"]]["dur_s"] == 1.5
    assert got[rec["span_id"]]["t1"] - got[rec["span_id"]]["t0"] == \
        pytest.approx(1.5, abs=2e-6)
    # corrupt lines are skipped, never fatal (SIGKILL mid-write)
    with open(obs_trace.spans_path(str(tmp_path), 0), "a") as f:
        f.write('{"torn...\n')
    assert len(list(obs_trace.iter_spans(str(tmp_path)))) == 2


def test_emit_site_schema_teeth_through_runtime(tmp_path):
    run = obs_runtime.start_attempt(obs_dir=str(tmp_path))
    try:
        with pytest.raises(obs_trace.SpanError):
            run.span_add("not_a_span", 0.1)
        with pytest.raises(obs_trace.SpanError):
            run.span_add("eval", 0.1, undeclared_attr=1)
    finally:
        obs_runtime.end_attempt("ok")


# ---------------------------------------------------------------------------
# trace-context propagation (the satellite drill)
# ---------------------------------------------------------------------------

def test_parent_span_survives_worker_env_forwarding(tmp_path):
    """The trainer's fake-ray worker spawn path: the driver mints an
    attempt span id, _pool_env forwards it as OBS_PARENT_SPAN through
    _run_worker's os.environ.update, and the worker's attempt span
    parents under it — the merged DAG is connected across the spawn
    boundary."""
    from gke_ray_train_tpu.rayint import JaxTrainer
    obs_dir = str(tmp_path / "obs")
    seen = {}

    def worker(config):
        seen["parent_env"] = os.environ.get("OBS_PARENT_SPAN")
        run = obs_runtime.active()
        assert run is not None and run.spans is not None
        run.span_add("compile", 0.01)
        return {"ok": 1}

    res = JaxTrainer(worker, use_ray=False,
                     train_loop_config={"OBS": "1", "OBS_DIR": obs_dir,
                                        "OBS_CAPTURE": "0"}).fit()
    assert res.error is None
    spans = list(obs_trace.iter_spans(obs_dir))
    drv_att = [s for s in spans if s["rank"] == "driver"
               and s["name"] == "attempt"]
    wrk_att = [s for s in spans if s["rank"] == 0
               and s["name"] == "attempt"]
    run_span = [s for s in spans if s["name"] == "run"]
    assert len(drv_att) == len(wrk_att) == len(run_span) == 1
    # the env actually carried the driver's minted id
    assert seen["parent_env"] == drv_att[0]["span_id"]
    assert wrk_att[0]["parent_id"] == drv_att[0]["span_id"]
    assert drv_att[0]["parent_id"] == run_span[0]["span_id"]
    # one trace across driver + worker
    assert len({s["trace_id"] for s in spans}) == 1
    # leaf spans parent under the worker's attempt span
    leaf = [s for s in spans if s["name"] == "compile"][0]
    assert leaf["parent_id"] == wrk_att[0]["span_id"]


def test_driverless_multirank_merges_to_one_trace(tmp_path, monkeypatch):
    """No driver at all: ranks that share OBS_RUN_ID derive the SAME
    trace id (it is a hash of the run id, not minted state), so the
    merged stream is one trace with one attempt span per rank."""
    monkeypatch.setenv("OBS_RUN_ID", "sharedrun")
    for rank in (0, 1, 2):
        obs_runtime.start_attempt(obs_dir=str(tmp_path), rank=rank)
        obs_runtime.span_add("compile", 0.01 * (rank + 1))
        obs_runtime.end_attempt("ok")
    spans = list(obs_trace.iter_spans(str(tmp_path)))
    assert {s["trace_id"] for s in spans} == \
        {obs_trace.trace_id_for_run("sharedrun")}
    atts = [s for s in spans if s["name"] == "attempt"]
    assert sorted(s["rank"] for s in atts) == [0, 1, 2]
    # driverless = no parent to adopt
    assert all(s["parent_id"] is None for s in atts)


def test_trace_off_keeps_events_on(tmp_path, monkeypatch):
    monkeypatch.setenv("TRACE", "0")
    run = obs_runtime.start_attempt(obs_dir=str(tmp_path))
    assert run.spans is None
    assert run.span_add("compile", 0.1) is None     # silent no-op
    run.emit("attempt_start", topology="cpu-8")
    obs_runtime.end_attempt("ok")
    assert os.path.exists(tmp_path / "events-r0.jsonl")
    assert not os.path.exists(tmp_path / "spans-r0.jsonl")
    assert list(obs_trace.iter_spans(str(tmp_path))) == []


def test_trace_plan_knob_three_dialects():
    from gke_ray_train_tpu.plan import ExecutionPlan
    via_json = ExecutionPlan.from_config({"TRACE": False})
    via_env = ExecutionPlan.from_env({"TRACE": "off"})
    via_kw = ExecutionPlan.from_kwargs(trace=False)
    assert via_json == via_env == via_kw
    assert via_json.fingerprint() == via_kw.fingerprint()
    assert ExecutionPlan().trace is True
    # operational like every obs knob: toggling tracing must never
    # stale a compiled artifact on either surface
    base = ExecutionPlan()
    for surface in ("train", "serve", "all"):
        assert base.compile_fingerprint(surface) == \
            via_kw.compile_fingerprint(surface)


# ---------------------------------------------------------------------------
# critical path: teeth
# ---------------------------------------------------------------------------

def _fake_attempt(tmp_path, *, compile_span_s, ledger, run_id="runZ"):
    """One driver attempt_end + one worker stream whose spans claim
    ``compile_span_s`` for compile against ``ledger``."""
    from gke_ray_train_tpu.obs.events import EventLog, events_path
    drv = obs_runtime.DriverObs(str(tmp_path), run_id)
    drv.begin_attempt(1)
    wrk_events = EventLog(events_path(str(tmp_path), 0), run_id=run_id,
                          attempt=1, rank=0)
    wrk_events.emit("worker_exit", status="ok",
                    goodput={k: v for k, v in ledger.items()
                             if k != "wall_s"})
    wrk_events.close()
    spans = obs_trace.SpanLog(obs_trace.spans_path(str(tmp_path), 0),
                              run_id=run_id, attempt=1, rank=0)
    att = spans.emit("attempt", ledger["wall_s"])
    spans.emit("compile", compile_span_s, parent_id=att["span_id"])
    spans.emit("step_window", ledger["step_s"], steps=4,
               data_stall_s=0.0, parent_id=att["span_id"])
    spans.close()
    drv.note_attempt(1, {"status": "ok", "goodput": ledger})
    drv.close()


LEDGER = {"compile_s": 1.0, "restore_s": 0.0, "fast_forward_s": 0.0,
          "data_stall_s": 0.0, "eval_ckpt_stall_s": 0.0, "step_s": 2.0,
          "lost_s": 1.0, "wall_s": 4.0}


def test_critical_path_reconciles_and_doctored_trips(tmp_path):
    from gke_ray_train_tpu.obs.report import build_report
    ok_dir = tmp_path / "ok"
    ok_dir.mkdir()
    _fake_attempt(ok_dir, compile_span_s=1.0, ledger=LEDGER)
    rep = build_report(str(ok_dir))
    cp = rep["attempts"][0]["critical_path"]
    assert rep["critical_path_ok"] and cp["reconciliation"]["ok"]
    assert cp["span_terms"]["compile_s"] == 1.0
    # the terms ARE the reconciled ledger identity: they sum to wall
    terms = cp["terms"]
    assert sum(terms[t] for t in
               ("compile_s", "restore_s", "fast_forward_s",
                "data_stall_s", "eval_ckpt_stall_s", "step_s",
                "lost_s")) == pytest.approx(terms["wall_s"])

    bad_dir = tmp_path / "bad"
    bad_dir.mkdir()
    _fake_attempt(bad_dir, compile_span_s=1.7, ledger=LEDGER)
    rep = build_report(str(bad_dir))
    cp = rep["attempts"][0]["critical_path"]
    assert rep["critical_path_ok"] is False
    assert not cp["reconciliation"]["ok"]
    assert cp["reconciliation"]["deltas"]["compile_s"] == \
        pytest.approx(0.7)
    # ...and the CLI turns that into rc 3 (report.py's discipline)
    env = dict(os.environ, PYTHONPATH=REPO)
    r = subprocess.run([sys.executable, "-m", "gke_ray_train_tpu.obs",
                        "report", str(bad_dir)],
                       capture_output=True, text=True, env=env)
    assert r.returncode == 3
    assert "critical-path" in r.stderr


def test_critical_rank_is_the_straggler(tmp_path):
    """Multi-rank: the critical path belongs to the rank whose attempt
    span ran longest, and reconciliation uses THAT rank's own ledger."""
    from gke_ray_train_tpu.obs.critical import critical_path
    spans = []
    for rank, wall, comp in ((0, 2.0, 0.5), (1, 3.0, 1.5)):
        log = obs_trace.SpanLog(
            obs_trace.spans_path(str(tmp_path), rank),
            run_id="r", attempt=1, rank=rank)
        att = log.emit("attempt", wall)
        spans.append(att)
        spans.append(log.emit("compile", comp,
                              parent_id=att["span_id"]))
        log.close()
    ledgers = {0: {"compile_s": 0.5}, 1: {"compile_s": 1.5}}
    cp = critical_path(spans, {"wall_s": 3.5, "compile_s": 0.5},
                       ledgers)
    assert cp["rank"] == 1
    assert cp["span_terms"]["compile_s"] == 1.5
    assert cp["reconciliation"]["ok"]       # vs rank 1's OWN ledger


# ---------------------------------------------------------------------------
# obs diff: rc contract on the checked-in fixtures
# ---------------------------------------------------------------------------

def test_reused_obs_dir_two_runs_stay_reconciled(tmp_path):
    """Span/event files open in append mode and the default obs dir is
    run-stable: a SECOND run into the same dir must not merge its
    attempt-1 spans with the first run's (grouping is per run_id) —
    the reconciliation gate must stay green on healthy telemetry."""
    from gke_ray_train_tpu.obs.report import build_report
    for run_id in ("runFirst", "runSecond"):
        _fake_attempt(tmp_path, compile_span_s=1.0, ledger=LEDGER,
                      run_id=run_id)
    rep = build_report(str(tmp_path))
    assert rep["critical_path_ok"] is True
    for a in rep["attempts"]:
        cp = a.get("critical_path")
        assert cp is not None and cp["reconciliation"]["ok"], a
        # one run's spans only: compile counted once, not twice
        assert cp["span_terms"]["compile_s"] == 1.0


def test_diff_trips_on_recorded_field_missing_from_fresh():
    """A recorded field vanishing from the fresh report (tracing
    silently off, serving gone) is a VIOLATION, not a silent skip —
    the exact regression class the gate exists for."""
    from gke_ray_train_tpu.obs.diff import diff_flat
    recorded = {"goodput_frac": 0.5, "n_attempts": 1.0,
                "cp_frac_compile_s": 0.4}
    fresh = {"goodput_frac": 0.5, "n_attempts": 1.0}   # no cp_* at all
    viols = diff_flat(fresh, recorded)
    assert viols and "cp_frac_compile_s" in viols[0]
    assert "MISSING" in viols[0]
    # a noise-floored recorded field missing from fresh is NOT a trip
    recorded_small = {"goodput_frac": 0.5, "n_attempts": 1.0,
                      "cp_frac_restore_s": 0.003}
    assert diff_flat(fresh, recorded_small) == []
    # ungated extras (e.g. `anomalies`) stay informational
    assert diff_flat(fresh, {**fresh, "anomalies": 2.0}) == []


def test_diff_fixture_rc_contract():
    """The exact commands CI runs: identical recorded reports diff to
    rc 0; the doctored goodput regression exits nonzero with the
    offending term named."""
    env = dict(os.environ, PYTHONPATH=REPO)
    fix = os.path.join(REPO, "tests", "regressions", "elastic_cpu8.json")
    doctored = os.path.join(REPO, "tests", "regressions",
                            "elastic_cpu8_doctored.json")
    assert os.path.exists(fix) and os.path.exists(doctored)
    r = subprocess.run([sys.executable, "-m", "gke_ray_train_tpu.obs",
                        "diff", fix, fix],
                       capture_output=True, text=True, env=env)
    assert r.returncode == 0, r.stderr
    assert json.loads(r.stdout.strip())["ok"] is True
    r = subprocess.run([sys.executable, "-m", "gke_ray_train_tpu.obs",
                        "diff", doctored, fix],
                       capture_output=True, text=True, env=env)
    assert r.returncode == 4, (r.stdout, r.stderr)
    assert "goodput_frac" in r.stderr       # offending term named
    # unreadable operand = rc 1, never a crash
    r = subprocess.run([sys.executable, "-m", "gke_ray_train_tpu.obs",
                        "diff", "/nonexistent", fix],
                       capture_output=True, text=True, env=env)
    assert r.returncode == 1


def test_diff_update_records_ledger(tmp_path):
    """REGRESSION_UPDATE / --update re-records the B side from A and
    preserves any tolerance overrides the old ledger carried."""
    from gke_ray_train_tpu.obs.diff import diff_flat
    env = dict(os.environ, PYTHONPATH=REPO)
    ledger_path = str(tmp_path / "ledger.json")
    with open(ledger_path, "w") as f:
        json.dump({"goodput_frac": 0.9, "n_attempts": 1.0,
                   "tolerances": {"goodput_frac": 0.01}}, f)
    flat_path = str(tmp_path / "fresh.json")
    with open(flat_path, "w") as f:
        # the A side carries its OWN tolerances key: the re-record must
        # keep B's reviewed overrides, not silently adopt A's
        json.dump({"goodput_frac": 0.5, "n_attempts": 2.0,
                   "tolerances": {"goodput_frac": 0.9}}, f)
    # tightened tolerance applies before the re-record (2.2% drift
    # against the ledger's own 1% override)
    with open(ledger_path) as f:
        assert diff_flat({"goodput_frac": 0.88}, json.load(f))
    r = subprocess.run([sys.executable, "-m", "gke_ray_train_tpu.obs",
                        "diff", flat_path, ledger_path, "--update"],
                       capture_output=True, text=True, env=env)
    assert r.returncode == 0, r.stderr
    doc = json.load(open(ledger_path))
    assert doc["goodput_frac"] == 0.5 and doc["n_attempts"] == 2.0
    assert doc["tolerances"] == {"goodput_frac": 0.01}  # preserved
    assert "_note" in doc
    # the env spelling drives the same path
    r = subprocess.run([sys.executable, "-m", "gke_ray_train_tpu.obs",
                        "diff", flat_path, ledger_path],
                       capture_output=True, text=True,
                       env={**env, "REGRESSION_UPDATE": "1"})
    assert r.returncode == 0, r.stderr


def test_diff_noise_floor_and_named_terms():
    from gke_ray_train_tpu.obs.diff import diff_flat
    # both sides under the floor: composition jitter is not a finding
    a = {"frac_compile_s": 0.004, "n_attempts": 1.0}
    b = {"frac_compile_s": 0.015, "n_attempts": 1.0}
    assert diff_flat(a, b) == []
    # above the floor the two-sided comparator has teeth, named
    a = {"frac_compile_s": 0.60, "n_attempts": 1.0}
    b = {"frac_compile_s": 0.25, "n_attempts": 1.0}
    viols = diff_flat(a, b)
    assert viols and "frac_compile_s" in viols[0]
    # counts are exact in BOTH directions
    assert diff_flat({"n_attempts": 2.0}, {"n_attempts": 3.0})
    assert diff_flat({"n_attempts": 3.0}, {"n_attempts": 2.0})


# ---------------------------------------------------------------------------
# satellite: histogram reservoir
# ---------------------------------------------------------------------------

def test_histogram_reservoir_spans_whole_run():
    """The satellite fix: past the cap the sample is a uniform
    reservoir over the WHOLE run — a long run's p50/p99 must reflect
    both its early and late regimes (the old scheme forgot one side).
    Deterministic: the replacement stream is a fixed-seed LCG."""
    from gke_ray_train_tpu.obs.metrics import Histogram
    h = Histogram("step_time_s", max_samples=256)
    for _ in range(5000):
        h.observe(0.001)
    for _ in range(5000):
        h.observe(1.0)
    snap = h.snapshot()
    assert snap["count"] == 10000
    assert snap["sum"] == pytest.approx(5000 * 1.001)
    fast = sum(1 for v in h._samples if v < 0.5)
    # a uniform reservoir holds ~50% early samples (binomial, n=256);
    # the old rotating window held 0% and the pre-fix frozen sample
    # held 100% — both far outside this band
    assert 0.25 * len(h._samples) < fast < 0.75 * len(h._samples)
    # and the export still carries _count/_sum so scrapers can rate()
    from gke_ray_train_tpu.obs.metrics import MetricsRegistry
    reg = MetricsRegistry()
    for v in (0.1, 0.2):
        reg.histogram("step_time_s").observe(v)
    prom = reg.to_prometheus()
    assert "grt_step_time_s_count 2" in prom
    assert "grt_step_time_s_sum 0.3" in prom
    # determinism: same observations -> bitwise-same reservoir
    h2 = Histogram("step_time_s", max_samples=256)
    for _ in range(5000):
        h2.observe(0.001)
    for _ in range(5000):
        h2.observe(1.0)
    assert h2._samples == h._samples


# ---------------------------------------------------------------------------
# the program's own names in the profile (ISSUE 24): scopes on the
# step's device ops, names on the kernels, loop phases as regions
# ---------------------------------------------------------------------------

class _NoProfiler:
    """Stands where a profiler object would: the loop only asks for
    ``step`` and ``close`` (and the capture manager for ``active``)."""
    active = False

    def __init__(self):
        self.steps, self.closed = 0, False

    def step(self, global_step):
        self.steps += 1

    def close(self):
        self.closed = True


@pytest.fixture
def record():
    obs_trace.RECORD.clear()
    yield obs_trace.RECORD
    obs_trace.RECORD.clear()


@pytest.fixture(scope="module", params=[True, False],
                ids=["remat", "no_remat"])
def lora_step_table(request):
    """(remat?, optimised HLO text, scope table) of a tiny scanned LoRA
    train step with two micro-batches, compiled on the CPU."""
    import jax
    import jax.numpy as jnp

    from gke_ray_train_tpu.models import tiny
    from gke_ray_train_tpu.train import (
        LoraConfig, make_optimizer, make_train_state, make_train_step)
    cfg = tiny(vocab_size=64, d_model=32, n_layers=2, n_heads=2,
               n_kv_heads=2, d_ff=64, dtype="float32",
               param_dtype="float32", remat=request.param)
    opt = make_optimizer(1e-3)
    lora = LoraConfig(r=4, alpha=8, targets=(
        "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"))
    state = make_train_state(cfg, opt, jax.random.key(0), lora_cfg=lora)
    step = make_train_step(cfg, opt, lora_cfg=lora, grad_accum=2,
                           donate=False)
    batch = {"inputs": jnp.zeros((4, 16), jnp.int32),
             "targets": jnp.zeros((4, 16), jnp.int32),
             "weights": jnp.ones((4, 16), jnp.float32)}
    text = step.lower(state, batch).compile().as_text()
    return request.param, text, obs_trace.scope_table(text)


def test_scope_table_names_every_matmul(lora_step_table):
    import re
    _, text, table = lora_step_table
    matmuls = re.findall(
        r"^\s+(?:ROOT )?%?([\w.\-]+) = \S+ (?:dot|convolution)\(", text,
        re.M)
    assert len(matmuls) > 20
    # XLA:CPU leaves a dot unfused, so each is an entry of its own
    unnamed = [m for m in matmuls if m in table
               and obs_trace.scope_path(table[m]) is None]
    assert unnamed == []
    assert sum(m in table for m in matmuls) > 20


def test_scope_table_shows_the_phase(lora_step_table):
    remat, _, table = lora_step_table
    base = [op for op in table.values()
            if (obs_trace.scope_path(op) or "").endswith("/base")]
    recomputed = [op for op in base if "rematted_computation" in op]
    backward = [op for op in base if "transpose(" in op
                and "rematted_computation" not in op]
    forward = [op for op in base if "jvp(" in op and "transpose(" not in op]
    assert forward and backward
    assert bool(recomputed) == remat
    if not remat:
        assert not any("rematted_computation" in op
                       for op in table.values())
    else:
        # full remat runs the frozen projections of the forward again,
        # but for the block's last (w_down): nothing downstream in the
        # block needs its output, so XLA drops the recomputation
        assert len(recomputed) == len(forward) - 1
        assert not any("mlp/down" in op for op in recomputed)


def test_scope_table_separates_base_from_lora(lora_step_table):
    _, _, table = lora_step_table
    paths = {obs_trace.scope_path(op) for op in table.values()} - {None}
    for module in ("attn/qkv", "attn/out", "mlp/gate_up", "mlp/down"):
        assert {module + "/base", module + "/lora"} <= paths
    assert not any(p.endswith("base/lora") or p.endswith("lora/base")
                   for p in paths)
    # the step's own scopes, and the ones jax wraps into a transform
    # (`transpose(jvp(loss))`) because they were open where grad ran
    assert {"embed", "attn_norm", "attn/rope", "attn/core", "mlp_norm",
            "final_norm", "unembed", "loss", "optimizer",
            "optimizer/clip"} <= paths


def test_scope_table_prefers_a_fusion_s_matmul():
    text = """HloModule m
%fused_computation.1 (p: f32[4,4]) -> f32[4,4] {
  %p = f32[4,4]{1,0} parameter(0)
  %c = f32[4,4]{1,0} convolution(%p, %p), dim_labels=bf_io->bf, metadata={op_name="jit(f)/jvp()/attn/qkv/base/dot_general"}
  ROOT %a = f32[4,4]{1,0} add(%c, %p), metadata={op_name="jit(f)/jvp()/attn/qkv/lora/add"}
}
ENTRY %main (x: f32[4,4]) -> f32[4,4] {
  %x = f32[4,4]{1,0} parameter(0)
  %w = f32[4,4]{1,0} multiply(%x, %x), metadata={op_name="jit(f)/jvp(embed)/mul"}
  ROOT %fusion.7 = f32[4,4]{1,0} fusion(%w), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(f)/jvp()/attn/qkv/lora/add"}
}
"""
    table = obs_trace.scope_table(text)
    assert set(table) == {"x", "w", "fusion.7"}     # no fused member
    assert obs_trace.scope_path(table["fusion.7"]) == "attn/qkv/base"
    assert obs_trace.scope_path(table["w"]) == "embed"
    assert obs_trace.scope_path("jit(f)/while/body/add") is None


@pytest.mark.parametrize("key,code", [
    ("scopes", obs_trace.SCOPE_NAMES), ("kernels", obs_trace.KERNEL_NAMES),
    ("per_step", sorted(obs_trace.PER_STEP_SPANS))])
def test_name_vocabularies_match_the_schema(key, code):
    assert obs_trace.load_schema()[key] == list(code)
    assert obs_trace.PER_STEP_SPANS | obs_trace.ALWAYS_RECORDED <= \
        set(obs_trace.SPAN_NAMES)
    with pytest.raises(obs_trace.SpanError):
        obs_trace.scope("made_up_scope")
    with pytest.raises(obs_trace.SpanError):
        with obs_trace.region("made_up_span"):
            pass
    with pytest.raises(obs_trace.SpanError):
        with obs_trace.region("step_build", stray=1):
            pass


def test_every_pallas_call_is_named():
    import glob
    import re
    found = []
    for path in glob.glob(os.path.join(REPO, "gke_ray_train_tpu", "ops",
                                       "*.py")):
        # each call, up to the `)(` that applies it to its operands
        calls = re.findall(r"pl\.pallas_call\((.*?)\n\s*\)\(",
                           open(path).read(), re.S)
        names = [re.findall(r'^\s+name="(\w+)",$', c, re.M)
                 for c in calls]
        assert all(len(n) == 1 for n in names), path
        found += [n[0] for n in names]
    assert sorted(found + list(obs_trace.LIBRARY_KERNEL_NAMES)) \
        == sorted(obs_trace.KERNEL_NAMES)


def test_names_salt_rides_the_aot_compile_s_cache_key(monkeypatch):
    from jax._src import cache_key
    from gke_ray_train_tpu.perf import cache as perf_cache
    salt = perf_cache.names_salt()
    assert salt.startswith("grt-names:") and salt == perf_cache.names_salt()
    unsalted = cache_key.custom_hook()
    with perf_cache.salted_cache_key():
        assert cache_key.custom_hook() == unsalted + salt
        # a scope that moves bumps the version, and with it the key
        monkeypatch.setattr(obs_trace, "SCOPE_VERSION",
                            obs_trace.SCOPE_VERSION + 1)
        assert cache_key.custom_hook() != unsalted + salt
    assert cache_key.custom_hook() == unsalted

    seen = []

    class Lowered:
        def compile(self):
            seen.append(cache_key.custom_hook())
            return object()

    class Jitted:
        def lower(self, *args):
            return Lowered()
    perf_cache.build_or_load_step(Jitted(), label="fake step")
    assert seen == [unsalted + perf_cache.names_salt()]
    obs_trace.RECORD.clear()


@pytest.mark.parametrize("prefetch", [0, 2], ids=["inline", "prefetch"])
def test_step_iter_children_nest_and_pipeline_spans_land(
        record, tiny_train_setup, prefetch):
    from gke_ray_train_tpu.train.loop import run_training
    from tests.test_obs import _batches
    _, _, state, step = tiny_train_setup
    prof = _NoProfiler()
    run_training(state, step, _batches(6), epochs=1, log_every=2,
                 prefetch=prefetch, place_batch=lambda b: b,
                 profiler=prof)
    assert prof.closed and prof.steps == 6 and not record.attached
    spans = list(record.spans)
    by_id = {s["id"]: s for s in spans}
    iters = [s for s in spans if s["name"] == "step_iter"]
    assert [s["step"] for s in iters] == [1, 2, 3, 4, 5, 6]
    for name, count in (("data_wait", 6), ("step_dispatch", 6),
                        ("metrics_fetch", 3), ("log_emit", 3)):
        mine = [s for s in spans if s["name"] == name
                and s["parent"] in {i["id"] for i in iters}
                or (name == "step_dispatch" and s["name"] == name)]
        assert len(mine) == count, name
    for s in spans:
        parent = by_id.get(s["parent"])
        if parent is not None:
            assert parent["t0"] <= s["t0"] <= s["t1"] <= parent["t1"]
    # the first dispatch sits under `compile`, which sits under step 1
    first = next(s for s in spans if s["name"] == "compile")
    assert by_id[first["parent"]]["name"] == "step_iter"
    # the pipeline's two stages: on the prefetch thread they have no
    # parent (a thread's regions nest among themselves); inline they
    # are the hand-over the loop waits for
    for name in ("batch_next", "batch_place"):
        mine = [s for s in spans if s["name"] == name]
        assert len(mine) >= 6
        # (the wait that found the stream exhausted is dropped, so the
        # last `next` under it names a parent that was never recorded)
        parents = {by_id[s["parent"]]["name"] if s["parent"] else None
                   for s in mine if s["parent"] is None
                   or s["parent"] in by_id}
        assert parents == ({None} if prefetch else {"data_wait"})
    # a plain jitted step keeps no executable to read a table from
    assert record.scope_tables == {}


def test_no_profiler_no_session_records_no_per_step_name(
        record, tiny_train_setup):
    from gke_ray_train_tpu.train.loop import run_training
    from tests.test_obs import _batches
    _, _, state, step = tiny_train_setup
    run_training(state, step, _batches(4), epochs=1, log_every=2,
                 prefetch=2)
    # what ends once a call is kept whoever listens (ISSUE 34), what
    # ends once a step is not
    assert sorted(s["name"] for s in record.spans) == [
        "compile", "train_loop"]
    assert record.scope_tables == {}


def test_session_keeps_per_step_spans_out_of_the_stream(
        record, tmp_path, tiny_train_setup):
    from gke_ray_train_tpu.train.loop import run_training
    from tests.test_obs import _batches
    _, _, state, step = tiny_train_setup
    obs_runtime.start_attempt(obs_dir=str(tmp_path))
    try:
        run_training(state, step, _batches(4), epochs=1, log_every=2)
    finally:
        obs_runtime.end_attempt("ok")
    written = {json.loads(line)["name"]
               for line in open(tmp_path / "spans-r0.jsonl")}
    assert {"compile", "step_window", "attempt"} <= written
    assert not written & obs_trace.PER_STEP_SPANS
    # while the in-memory record, on with a session, holds them
    assert {"step_iter", "step_dispatch", "compile"} <= \
        {s["name"] for s in record.spans}


@pytest.mark.parametrize("attached", [True, False],
                         ids=["profiler", "no_profiler"])
def test_aot_step_build_spans_and_scope_table(record, attached):
    import jax
    import jax.numpy as jnp

    from gke_ray_train_tpu.perf.cache import build_or_load_step
    from gke_ray_train_tpu.train.loop import run_training
    from tests.test_obs import _batches
    from gke_ray_train_tpu.models import tiny
    from gke_ray_train_tpu.train import (
        make_optimizer, make_train_state, make_train_step)
    cfg = tiny(vocab_size=128, d_model=32, n_layers=1, n_heads=2,
               n_kv_heads=2, d_ff=64, dtype="float32",
               param_dtype="float32")
    opt = make_optimizer(1e-3)
    state = make_train_state(cfg, opt, jax.random.key(0))
    batch = jax.tree.map(jnp.asarray, next(iter(_batches(1)(0))))
    step = build_or_load_step(
        make_train_step(cfg, opt, donate=False), state, batch,
        label="tiny train_step")
    # the build's spans are recorded whoever listens: it is over before
    # a profiler could be attached
    built = {s["name"]: s for s in record.spans}
    assert set(built) == {"state_build", "step_build", "step_lower",
                          "step_compile"}
    assert built["step_build"]["source"] == "compiled"
    assert built["step_lower"]["parent"] == built["step_build"]["id"]
    assert step.info["build_s"] == pytest.approx(
        built["step_build"]["t1"] - built["step_build"]["t0"])
    assert step.info["build_s"] >= (
        built["step_compile"]["t1"] - built["step_lower"]["t0"])
    run_training(state, step, _batches(3), epochs=1, log_every=1,
                 profiler=_NoProfiler() if attached else None)
    if attached:
        table = record.scope_tables["tiny train_step"]
        assert any(obs_trace.scope_path(op) == "mlp/gate_up/base"
                   for op in table.values())
        assert record.scope_table_s["tiny train_step"] > 0
    else:
        assert record.scope_tables == {}
        assert not {s["name"] for s in record.spans} \
            & obs_trace.PER_STEP_SPANS


# ---------------------------------------------------------------------------
# set-up on the record (ISSUE 34): what ends once a call is kept always
# ---------------------------------------------------------------------------

SETUP_NAMES = {"state_build": ("args_bytes",),
               "train_loop": ("steps", "to_first_step_s"),
               "step_lower": ("trace_s", "to_mlir_s"),
               "step_compile": ("cache", "retrieval_s",
                                "backend_compile_s")}


@pytest.mark.parametrize("name", sorted(SETUP_NAMES))
def test_setup_names_and_attributes_in_code_and_schema(name):
    attrs = SETUP_NAMES[name]
    assert obs_trace.SPAN_NAMES[name] == attrs
    assert tuple(obs_trace.load_schema()["names"][name]) == attrs
    assert obs_trace.check_schema() == []
    obs_trace.validate_span(name, dict.fromkeys(attrs, 1))
    with pytest.raises(obs_trace.SpanError):
        obs_trace.validate_span(name, {"stray": 1})


@pytest.mark.parametrize("attr", ["xla_memory", "remat_estimate_bytes"])
def test_step_build_declares_the_memory_attributes(attr):
    assert attr in obs_trace.SPAN_NAMES["step_build"]
    assert attr in obs_trace.load_schema()["names"]["step_build"]


@pytest.mark.parametrize("name", ["compile", "state_build", "train_loop"])
def test_once_a_call_names_are_always_recorded(name):
    assert name in obs_trace.ALWAYS_RECORDED
    assert name not in obs_trace.PER_STEP_SPANS
    # the vocabulary that rides the compile cache's key is not touched
    assert obs_trace.SCOPE_VERSION == 4


def test_train_loop_is_the_parent_of_compile_and_counts_its_steps(
        record, tiny_train_setup):
    from gke_ray_train_tpu.train.loop import run_training
    from tests.test_obs import _batches
    _, _, state, step = tiny_train_setup
    _, last = run_training(state, step, _batches(3), epochs=1,
                           log_every=1)
    spans = {s["name"]: s for s in record.spans}
    loop, first = spans["train_loop"], spans["compile"]
    # no profiler, no session: the iteration's region between the two
    # is not kept, so `compile` names the nearest region that is
    assert first["parent"] == loop["id"] and loop["parent"] is None
    assert loop["t0"] <= first["t0"] <= first["t1"] <= loop["t1"]
    assert loop["steps"] == 3 and first["step"] == 1
    assert loop["to_first_step_s"] == pytest.approx(
        last["restart_to_first_step_s"])
    assert loop["to_first_step_s"] <= loop["t1"] - loop["t0"]


def test_train_loop_region_closes_on_a_failing_step(record,
                                                    tiny_train_setup):
    from gke_ray_train_tpu.train.loop import run_training
    from tests.test_obs import _batches
    _, _, state, step = tiny_train_setup

    def failing(st, batch):
        raise RuntimeError("step failed")
    with pytest.raises(RuntimeError, match="step failed"):
        run_training(state, failing, _batches(2), epochs=1)
    loop = next(s for s in record.spans if s["name"] == "train_loop")
    assert loop["steps"] == 0 and loop["to_first_step_s"] is None
    # the thread's stack of open regions is empty again
    with obs_trace.region("state_build") as r:
        assert r.parent is None


def test_state_build_is_recorded_with_one_device_s_share(record):
    import jax

    from gke_ray_train_tpu.models import tiny
    from gke_ray_train_tpu.train import make_optimizer, make_train_state
    from gke_ray_train_tpu.train.remat import shard_bytes
    cfg = tiny(vocab_size=64, d_model=32, n_layers=1, n_heads=2,
               n_kv_heads=2, d_ff=64, dtype="float32",
               param_dtype="float32")
    state = make_train_state(cfg, make_optimizer(1e-3), jax.random.key(0))
    (span,) = [s for s in record.spans if s["name"] == "state_build"]
    assert span["parent"] is None
    assert span["args_bytes"] == shard_bytes(state) > 0


@pytest.mark.parametrize("name", ["train_loop", "state_build"])
def test_critical_path_books_no_term_for_the_setup_spans(tmp_path, name):
    """The reconciliation of the fixture of
    test_critical_path_reconciles_and_doctored_trips is unchanged by a
    span of the whole loop, or of the state's build, beside it."""
    from gke_ray_train_tpu.obs import critical
    from gke_ray_train_tpu.obs.report import build_report
    assert name not in critical.SPAN_TERM
    _fake_attempt(tmp_path, compile_span_s=1.0, ledger=LEDGER)
    before = build_report(str(tmp_path))["attempts"][0]["critical_path"]
    spans = obs_trace.SpanLog(obs_trace.spans_path(str(tmp_path), 0),
                              run_id="runZ", attempt=1, rank=0)
    spans.emit(name, LEDGER["wall_s"])
    spans.close()
    rep = build_report(str(tmp_path))
    after = rep["attempts"][0]["critical_path"]
    assert rep["critical_path_ok"] and after["reconciliation"]["ok"]
    assert after["span_terms"] == before["span_terms"]
    assert after["reconciliation"] == before["reconciliation"]
    # the loop's own span holds every other: it is no step of the path
    on_path = [p["name"] for p in after["path"]]
    assert (name in on_path) == (name == "state_build")

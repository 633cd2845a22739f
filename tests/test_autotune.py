"""autotune/ — cost-model-driven plan search + tuned-plan registry.

Contracts drilled here:

- the AUTOTUNE plan knob: 3-dialect coercion, compile-fingerprint
  invariance (consulting the registry must not stale a sidecar), env
  forwarding;
- property-style enumerator coverage: EVERY candidate space.py yields
  passes ExecutionPlan validation, plancheck feasibility and
  kernelcheck statics with NO compile, preserves the global batch, and
  never reflows a structural axis;
- determinism: two enumerations are identical; two full searches over
  the same space produce a bitwise-identical winner + candidate table;
- the registry: save → load → validate → overlay roundtrip, loud
  refusal on fingerprint-input drift or a tuned plan that no longer
  validates, AUTOTUNE=1 runtime application via maybe_apply;
- replan × tuning: an elastic reshard drops the overlay and re-keys
  the lookup (the 8-device-tune-on-4-devices trap), regression-tested
  from the plan side here and from the elastic side in test_elastic.py;
- the tuned plan runs: a real step stream under the tuned plan compiles
  exactly once (RECOMPILE_LIMIT=1 armed — zero recompiles beyond the
  tuned plan's own compile).
"""

import dataclasses
import json
import os

import pytest

from gke_ray_train_tpu.autotune.space import (
    TUNABLE_FIELDS, enumerate_space)
from gke_ray_train_tpu.perf.budget import (
    plan_for_preset, preset_model_cfg)
from gke_ray_train_tpu.plan import ExecutionPlan, replan


# ---------------------------------------------------------------------------
# the AUTOTUNE plan knob
# ---------------------------------------------------------------------------

def test_autotune_knob_three_dialects_and_fingerprints():
    from_json = ExecutionPlan.from_config({"AUTOTUNE": True})
    from_env = ExecutionPlan.from_env({"AUTOTUNE": "1"})
    from_kwargs = ExecutionPlan.from_kwargs(autotune=True)
    assert from_json.autotune and from_env.autotune and from_kwargs.autotune
    assert from_json.fingerprint() == from_env.fingerprint() \
        == from_kwargs.fingerprint()
    base = ExecutionPlan()
    # operational: the flag changes the plan identity but NEVER the
    # compiled-program identity on either surface
    assert from_json.fingerprint() != base.fingerprint()
    for surface in ("train", "serve", "all"):
        assert from_json.compile_fingerprint(surface) \
            == base.compile_fingerprint(surface)


def test_autotune_env_forwarded_to_workers():
    from gke_ray_train_tpu.plan import ENV_FORWARD_KEYS
    assert "AUTOTUNE" in ENV_FORWARD_KEYS


# ---------------------------------------------------------------------------
# property-style enumerator coverage (no compile anywhere)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("preset", ["tiny_fsdp8", "tiny_dp8",
                                    "tiny_hybrid_2x4_hier"])
def test_every_train_candidate_statically_valid(preset):
    from gke_ray_train_tpu.analysis.kernelcheck import (
        kernel_constraint_findings)
    base = plan_for_preset(preset)
    cfg = preset_model_cfg(preset)
    space = enumerate_space(base, cfg)
    assert len(space) > 1
    sizes0 = base.resolved_sizes()
    for cand in space.candidates:
        plan = cand.plan
        # PLAN000 held by construction; PLAN001/002 clean:
        assert plan.feasibility(cfg) == [], cand
        # KER001-003 clean:
        assert kernel_constraint_findings(plan, cfg) == [], cand
        # global batch preserved, structural axes never reflowed
        assert plan.global_batch() == base.global_batch(), cand
        sizes = plan.resolved_sizes()
        for axis in ("model", "context", "pipe"):
            assert sizes[axis] == sizes0[axis], cand
        if base.num_slices > 1:
            assert sizes["data"] % base.num_slices == 0, cand


def test_every_serve_candidate_statically_valid():
    base = plan_for_preset("serve_tiny8")
    cfg = preset_model_cfg("serve_tiny8")
    space = enumerate_space(base, cfg, surface="serve")
    assert len(space) > 1
    # the ONLY acceptable prune on this base is the spec_k ledger note:
    # speculation is off, so every spec_k arm would compile the
    # identical program — enumerating them would be wasted compiles
    assert [p for p in space.pruned if "spec_k" not in p] == []
    assert space.pruned and "SPEC_DRAFT=none" in space.pruned[0]
    assert set(space.dims) == {"max_batch", "buckets", "adapters",
                               "spec_k"}
    assert space.dims["adapters"] >= 3 and space.dims["spec_k"] == 1
    for cand in space.candidates:
        assert cand.plan.bucket_list()           # validates
        assert cand.plan.max_batch >= 1
        # the train surface's fields are untouched on serve candidates
        for f in TUNABLE_FIELDS["train"]:
            assert getattr(cand.plan, f) == getattr(base, f), cand


def test_serve_space_spec_k_arms_gated_on_draft():
    """spec_k arms enumerate ONLY when the base plan speculates: with
    SPEC_DRAFT=self the arms appear (and the ledger note disappears);
    adapter-count arms are always on for the serve surface."""
    import dataclasses as dc
    base = dc.replace(plan_for_preset("serve_tiny8"),
                      spec_draft="self", spec_k=4)
    cfg = preset_model_cfg("serve_tiny8")
    space = enumerate_space(base, cfg, surface="serve")
    assert not space.pruned
    assert space.dims["spec_k"] >= 3
    sks = {c.plan.spec_k for c in space.candidates}
    assert {2, 4, 8} <= sks
    ads = {c.plan.max_adapters for c in space.candidates}
    assert {4, 8, 16} <= ads


def test_decode_buckets_fitted_from_observed_histogram(tmp_path):
    """The obs -> autotune satellite: a served run's request_len
    histogram (p50/p99 of prompt + decode budget) yields bucket arms
    rounded UP to the 128-token grid and capped at max_seq_len — the
    widths that pad the median and tail request least."""
    import dataclasses as dc
    import json as _json
    from gke_ray_train_tpu.autotune.space import _bucket_options
    (tmp_path / "metrics-r0.json").write_text(_json.dumps({
        "labels": {},
        "request_len": {"count": 40, "sum": 8000.0,
                        "p50": 180.0, "p99": 430.0}}))
    base = dc.replace(plan_for_preset("serve_tiny8"),
                      obs_dir=str(tmp_path), max_seq_len=512)
    opts = _bucket_options(base)
    # 180 -> 256, 430 -> 512 (capped at max_seq_len=512), plus the
    # fitted two-bucket list covering median AND tail
    assert "256" in opts and "512" in opts and "256,512" in opts
    cfg = preset_model_cfg("serve_tiny8")
    space = enumerate_space(base, cfg, surface="serve")
    fitted = [c for c in space.candidates
              if c.plan.decode_buckets == "256,512"]
    assert fitted, [c.plan.decode_buckets for c in space.candidates]
    # no telemetry -> no fitted arms, silently (the dims count shrinks)
    bare = dc.replace(base, obs_dir=None)
    assert "256,512" not in _bucket_options(bare)


def test_enumeration_deterministic_and_deduped():
    base = plan_for_preset("tiny_fsdp8")
    cfg = preset_model_cfg("tiny_fsdp8")
    a = [c.fingerprint() for c in enumerate_space(base, cfg).candidates]
    b = [c.fingerprint() for c in enumerate_space(base, cfg).candidates]
    assert a == b
    assert len(a) == len(set(a))
    # base plan is always candidate 0
    assert a[0] == base.fingerprint()


def test_dims_filter_and_unknown_dim():
    base = plan_for_preset("tiny_fsdp8")
    cfg = preset_model_cfg("tiny_fsdp8")
    full = enumerate_space(base, cfg)
    mesh_only = enumerate_space(base, cfg, dims=["mesh"])
    assert 1 < len(mesh_only) < len(full)
    for cand in mesh_only.candidates:
        assert cand.plan.overlap == base.overlap
        assert cand.plan.fused_ops == base.fused_ops
    with pytest.raises(ValueError, match="unknown autotune dims"):
        enumerate_space(base, cfg, dims=["warp-drive"])


# ---------------------------------------------------------------------------
# search: bitwise determinism + the winner contract (compiles a small
# mesh-only space on the fake-8 mesh)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def search_result():
    from gke_ray_train_tpu.autotune.search import search
    base = plan_for_preset("tiny_fsdp8")
    cfg = preset_model_cfg("tiny_fsdp8")
    return search(base, cfg, dims=["mesh"])


@pytest.mark.slow
def test_search_winner_never_loses_to_base(search_result):
    r = search_result
    assert r["winner"]["score"]["modeled_step_s"] \
        <= r["base"]["score"]["modeled_step_s"]
    assert r["improvement"] >= 1.0
    # full per-ceiling breakdown retained as provenance on every row
    for row in r["candidates"]:
        for key in ("t_compute_s", "t_hbm_s", "t_ici_s", "t_dcn_s",
                    "exposed_penalty_s", "binding", "modeled_step_s",
                    "mfu_ceiling", "chip"):
            assert key in row["score"], (row["fingerprint"], key)
    # the table is sorted best-first and contains the base row
    steps = [row["score"]["modeled_step_s"] for row in r["candidates"]]
    assert steps == sorted(steps)
    assert any(row["fingerprint"] == r["base"]["fingerprint"]
               for row in r["candidates"])


@pytest.mark.slow
def test_search_bitwise_deterministic(search_result):
    from gke_ray_train_tpu.autotune.search import search
    again = search(plan_for_preset("tiny_fsdp8"),
                   preset_model_cfg("tiny_fsdp8"), dims=["mesh"])
    assert json.dumps(again, sort_keys=True) \
        == json.dumps(search_result, sort_keys=True)


@pytest.mark.slow
def test_search_emits_schema_valid_obs_events(monkeypatch):
    from gke_ray_train_tpu.autotune.search import search
    from gke_ray_train_tpu.obs import runtime as obs_runtime
    from gke_ray_train_tpu.obs.events import validate_event
    emitted = []

    def fake_emit(kind, step=None, **payload):
        validate_event(kind, payload)      # schema teeth at the source
        emitted.append((kind, payload))

    monkeypatch.setattr(obs_runtime, "emit", fake_emit)
    # prefetch-only space: >1 candidates, ONE compile (memoized — the
    # depths share a compile fingerprint), so the event contract is
    # drilled without paying another mesh sweep
    result = search(plan_for_preset("tiny_fsdp8"),
                    preset_model_cfg("tiny_fsdp8"), dims=["prefetch"])
    kinds = [k for k, _ in emitted]
    assert kinds.count("autotune_result") == 1
    assert kinds.count("autotune_candidate") == result["space"]["scored"]


# ---------------------------------------------------------------------------
# registry: roundtrip, refusal, runtime overlay
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_registry_roundtrip_and_maybe_apply(search_result, tmp_path,
                                            monkeypatch):
    from gke_ray_train_tpu.autotune import registry
    base = plan_for_preset("tiny_fsdp8")
    cfg = preset_model_cfg("tiny_fsdp8")
    path = registry.save_entry(search_result, base_plan=base,
                               model_cfg=cfg, directory=str(tmp_path))
    assert os.path.exists(path)
    key = registry.entry_key(registry.model_digest(cfg), base.topology,
                             "train")
    entry = registry.load_entry(key, str(tmp_path))
    assert entry is not None
    assert registry.validate_entry(entry, base, cfg) == []
    # the candidate table is persisted beside the entry
    with open(os.path.join(str(tmp_path), entry["candidates_file"])) as f:
        table = json.load(f)["candidates"]
    assert len(table) == search_result["space"]["scored"]

    # runtime overlay: AUTOTUNE=1 + AUTOTUNE_DIR → applied loudly
    monkeypatch.setenv("AUTOTUNE_DIR", str(tmp_path))
    armed = dataclasses.replace(base, autotune=True)
    tuned, applied = registry.maybe_apply(armed, model_cfg=cfg)
    assert applied
    for f in TUNABLE_FIELDS["train"]:
        assert getattr(tuned, f) == search_result["winner_tuned_fields"][f]
    assert tuned.autotune
    assert getattr(tuned, "_tuned_base") is armed
    assert getattr(tuned, "_tuned_key") == key
    # the winner's compiled program is what the run will fingerprint
    assert tuned.compile_fingerprint("train") \
        == search_result["winner"]["compile_fingerprint"]
    # opt-out plans are untouched
    same, applied = registry.maybe_apply(base, model_cfg=cfg)
    assert same is base and not applied


@pytest.mark.slow
def test_registry_refuses_on_drift(search_result, tmp_path):
    from gke_ray_train_tpu.autotune import registry
    base = plan_for_preset("tiny_fsdp8")
    cfg = preset_model_cfg("tiny_fsdp8")
    registry.save_entry(search_result, base_plan=base, model_cfg=cfg,
                        directory=str(tmp_path))
    key = registry.entry_key(registry.model_digest(cfg), base.topology,
                             "train")
    entry = registry.load_entry(key, str(tmp_path))

    # model drift: the digest no longer matches the run's model
    other = dataclasses.replace(cfg, d_ff=cfg.d_ff * 2)
    assert any("model digest" in m
               for m in registry.validate_entry(entry, base, other))
    # scorer drift
    doctored = dict(entry, fingerprint_inputs=dict(
        entry["fingerprint_inputs"], scorer_version=-1))
    assert any("scorer version" in m
               for m in registry.validate_entry(doctored, base, cfg))
    # topology drift
    moved = dataclasses.replace(base, topology="cpu-4", fsdp=4)
    assert any("topology" in m
               for m in registry.validate_entry(entry, moved, cfg))
    # a tuned plan that no longer validates (data=3 cannot tile 8)
    broken = dict(entry, tuned=dict(entry["tuned"], data=3, fsdp=2))
    assert registry.validate_entry(broken, base, cfg) != []
    # a run whose configured batch differs from the entry's base: the
    # overlay would silently move the global batch — refused
    bigger = dataclasses.replace(base, per_device_batch=4)
    assert any("does not preserve this run's configured product" in m
               for m in registry.validate_entry(entry, bigger, cfg))

    # and maybe_apply REFUSES (continues untuned) instead of crashing
    armed = dataclasses.replace(base, autotune=True)
    plan, applied = registry.maybe_apply(
        armed, model_cfg=other, config={"AUTOTUNE_DIR": str(tmp_path)})
    assert plan is armed and not applied


def test_maybe_apply_miss_and_underivable_model(tmp_path):
    from gke_ray_train_tpu.autotune import registry
    armed = dataclasses.replace(plan_for_preset("tiny_fsdp8"),
                                autotune=True)
    # empty registry → loud miss, untuned
    plan, applied = registry.maybe_apply(
        armed, model_cfg=preset_model_cfg("tiny_fsdp8"),
        config={"AUTOTUNE_DIR": str(tmp_path)})
    assert plan is armed and not applied
    # no statically-derivable model (no MODEL_ID / SMOKE_TEST) → untuned
    plan, applied = registry.maybe_apply(
        armed, config={"AUTOTUNE_DIR": str(tmp_path)})
    assert plan is armed and not applied


@pytest.mark.slow
def test_maybe_apply_derives_model_from_smoke_config(tmp_path):
    """The _run_worker path end to end: the search runs on the model a
    SMOKE_TEST config statically resolves to, the entry is keyed by
    that model's digest, and a worker whose config says AUTOTUNE=1
    derives the same digest and overlays — with no model object passed
    in anywhere."""
    from gke_ray_train_tpu.analysis.plancheck import model_config_for
    from gke_ray_train_tpu.autotune import registry
    from gke_ray_train_tpu.autotune.search import search
    base = plan_for_preset("tiny_fsdp8")
    config = {**{k: v for k, v in base.to_config().items()
                 if v is not None},
              "SMOKE_TEST": 1, "AUTOTUNE": 1,
              "AUTOTUNE_DIR": str(tmp_path)}
    plan = ExecutionPlan.from_config(config)
    smoke_cfg = model_config_for(config, plan)
    result = search(plan, smoke_cfg, dims=["mesh"])
    registry.save_entry(result, base_plan=plan, model_cfg=smoke_cfg,
                        directory=str(tmp_path))
    tuned, applied = registry.maybe_apply(plan, config=config)
    assert applied
    assert tuned.data == result["winner_tuned_fields"]["data"]
    assert tuned.fsdp == result["winner_tuned_fields"]["fsdp"]


# ---------------------------------------------------------------------------
# replan x tuning: the reshard drops the overlay and re-keys
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_replan_drops_tuned_overlay(search_result, tmp_path):
    from gke_ray_train_tpu.autotune import registry
    base = dataclasses.replace(plan_for_preset("tiny_fsdp8"),
                               autotune=True)
    cfg = preset_model_cfg("tiny_fsdp8")
    registry.save_entry(search_result, base_plan=base, model_cfg=cfg,
                        directory=str(tmp_path))
    tuned, applied = registry.maybe_apply(
        base, model_cfg=cfg, config={"AUTOTUNE_DIR": str(tmp_path)})
    assert applied
    # reshard to 4 devices: the overlay is DROPPED — the result is
    # exactly what replanning the never-tuned plan gives, and carries
    # no overlay marker for a later attempt to trip over
    shrunk = replan(tuned, 4, model_cfg=cfg)
    assert shrunk.fingerprint() == replan(base, 4,
                                          model_cfg=cfg).fingerprint()
    assert getattr(shrunk, "_tuned_base", None) is None
    # ...and the re-keyed lookup on the survivors' topology misses (no
    # cpu-4 entry recorded), so the attempt runs untuned — loudly
    plan, applied = registry.maybe_apply(
        shrunk, model_cfg=cfg, config={"AUTOTUNE_DIR": str(tmp_path)})
    assert plan is shrunk and not applied
    # identity replan (pool unchanged) keeps the overlay
    assert replan(tuned, tuned.chips) is tuned


# ---------------------------------------------------------------------------
# the tuned plan actually runs: one compile, zero recompiles
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_tuned_plan_trains_with_zero_recompiles(search_result, devices):
    import jax
    import jax.numpy as jnp

    from gke_ray_train_tpu.analysis.guards import (
        install_recompile_limit, uninstall_recompile_limit)
    from gke_ray_train_tpu.train import (
        make_optimizer, make_train_state, make_train_step)
    base = plan_for_preset("tiny_fsdp8")
    cfg = preset_model_cfg("tiny_fsdp8")
    tuned = dataclasses.replace(base, **{
        f: search_result["winner_tuned_fields"][f]
        for f in TUNABLE_FIELDS["train"]})
    mesh = tuned.build_mesh(devices)
    opt = make_optimizer(1e-3)
    state = make_train_state(cfg, opt, jax.random.key(0), mesh=mesh)
    step = make_train_step(cfg, opt, mesh=mesh, plan=tuned)
    rows, seq = tuned.global_batch(), tuned.max_seq_len
    batch = jax.device_put(
        {"inputs": jnp.zeros((rows, seq), jnp.int32),
         "targets": jnp.zeros((rows, seq), jnp.int32),
         "weights": jnp.ones((rows, seq), jnp.float32)},
        tuned.batch_shardings(mesh))
    assert install_recompile_limit(limit=1)
    try:
        losses = []
        for _ in range(3):
            state, m = step(state, batch)
            losses.append(float(m["loss"]))
    finally:
        uninstall_recompile_limit()
    assert all(v == v for v in losses)       # finite stream, one compile


# ---------------------------------------------------------------------------
# CLI contracts (in-process; apply/explain are static)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_serve_entry_roundtrips_and_applies(tmp_path):
    """The serve half of the registry is actually applicable: a
    freshly-recorded serve entry validates clean (the mesh arithmetic
    a mesh-local decode plan can never satisfy is skipped on the serve
    surface, exactly as the enumerator skips it) and overlays."""
    from gke_ray_train_tpu.autotune import registry
    from gke_ray_train_tpu.autotune.search import search
    base = plan_for_preset("serve_tiny8")
    cfg = preset_model_cfg("serve_tiny8")
    result = search(base, cfg, surface="serve")
    registry.save_entry(result, base_plan=base, model_cfg=cfg,
                        directory=str(tmp_path))
    key = registry.entry_key(registry.model_digest(cfg), base.topology,
                             "serve")
    entry = registry.load_entry(key, str(tmp_path))
    assert registry.validate_entry(entry, base, cfg) == []
    armed = dataclasses.replace(base, autotune=True)
    tuned, applied = registry.maybe_apply(
        armed, model_cfg=cfg, surface="serve",
        config={"AUTOTUNE_DIR": str(tmp_path)})
    assert applied
    for f in TUNABLE_FIELDS["serve"]:
        assert getattr(tuned, f) == result["winner_tuned_fields"][f]


@pytest.mark.slow
def test_entry_with_stray_env_refused(search_result, tmp_path):
    """A corrupt/doctored entry cannot export arbitrary env into a
    worker: only ENV_OVERRIDE_KEYS pass validation."""
    from gke_ray_train_tpu.autotune import registry
    base = plan_for_preset("tiny_fsdp8")
    cfg = preset_model_cfg("tiny_fsdp8")
    registry.save_entry(search_result, base_plan=base, model_cfg=cfg,
                        directory=str(tmp_path))
    key = registry.entry_key(registry.model_digest(cfg), base.topology,
                             "train")
    entry = registry.load_entry(key, str(tmp_path))
    doctored = dict(entry, env={"LD_PRELOAD": "/tmp/evil.so"})
    assert any("undeclared env overrides" in m
               for m in registry.validate_entry(doctored, base, cfg))


def test_cli_refuses_big_models():
    from gke_ray_train_tpu.autotune.__main__ import _guard_model_size
    from gke_ray_train_tpu.models import llama3_8b
    with pytest.raises(SystemExit, match="refusing to compile-score"):
        _guard_model_size(ExecutionPlan.from_kwargs(topology="v5e-16",
                                                    data=1, fsdp=16),
                          llama3_8b())


def test_cli_explain_rc_contract(tmp_path):
    from gke_ray_train_tpu.autotune.__main__ import main
    assert main(["explain", "--dir", str(tmp_path)]) == 3
    assert main(["apply", "--dir", str(tmp_path)]) == 3


@pytest.mark.slow
def test_cli_apply_and_explain_after_search(search_result, tmp_path,
                                            capsys):
    from gke_ray_train_tpu.autotune import registry
    from gke_ray_train_tpu.autotune.__main__ import main
    registry.save_entry(search_result,
                        base_plan=plan_for_preset("tiny_fsdp8"),
                        model_cfg=preset_model_cfg("tiny_fsdp8"),
                        directory=str(tmp_path))
    assert main(["apply", "--dir", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "applied train-cpu-8-" in out
    assert main(["explain", "--dir", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "candidate table" in out and "fingerprint inputs" in out


def test_budget_cli_all_excludes_names():
    from gke_ray_train_tpu.perf.budget import main
    with pytest.raises(SystemExit) as e:
        main(["check", "tiny_fsdp8", "--all"])
    assert e.value.code == 2


# ---------------------------------------------------------------------------
# observed columns -> calibration -> drift (ISSUE 16: the feedback loop)
# ---------------------------------------------------------------------------

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
OBS_GOOD = os.path.join(FIXTURES, "autotune_obs")
OBS_DOCTORED = os.path.join(FIXTURES, "autotune_obs_doctored")


@pytest.fixture
def fixture_registry(tmp_path):
    """A scratch COPY of the checked-in fixture registry — ingest
    mutates entries in place, and drift emits events into the obs dir,
    so the checked-in fixtures must never be pointed at directly for
    anything that writes (scripts/make_autotune_fixture.py regenerates
    them)."""
    import shutil
    dst = str(tmp_path / "registry")
    shutil.copytree(os.path.join(FIXTURES, "autotune_registry"), dst)
    return dst


def _one_entry(directory):
    from gke_ray_train_tpu.autotune import registry
    [(path, entry)] = registry.list_entries(directory)
    return path, entry


def _rewrite_entry(path, entry):
    with open(path, "w") as f:
        json.dump(entry, f, indent=1, sort_keys=True)
        f.write("\n")


def test_ingest_then_calibrate_corrects_toward_measured(fixture_registry):
    """The acceptance loop: measured rows land as observed columns,
    the fit recovers the fixture's engineered 2.0x compute factor
    EXACTLY (least-squares over measured = 2 * modeled), and the
    corrected prediction is closer to the measured value than the raw
    one — on BOTH arms."""
    from gke_ray_train_tpu.autotune import calibrate, registry
    s = registry.ingest_observed(OBS_GOOD, directory=fixture_registry)
    assert s["rows"] == 2 and s["matched"] == 2 and not s["refusals"]
    assert not s["calibrated"]        # no factors existed yet
    cal_doc = registry.fit_and_save_calibration(fixture_registry)
    assert cal_doc["_samples"] == 2
    cal = calibrate.load_calibration(fixture_registry)
    _, entry = _one_entry(fixture_registry)
    digest = entry["fingerprint_inputs"]["chip_digest"]
    assert cal["chips"][digest]["factors"]["compute"]["factor"] == 2.0
    assert cal["chips"][digest]["factors"]["compute"]["clamped"] is False
    for arm, score in (("base", entry["base_score"]),
                       ("tuned", entry["score"])):
        rows = [r for r in entry["observed"] if r["arm"] == arm]
        assert len(rows) == 1
        assert rows[0]["backend"] == "cpu"      # stamped, not inferred
        assert rows[0]["raw_modeled"] == score["modeled_step_s"]
        measured = rows[0]["measured"]
        raw = calibrate.raw_prediction(score, "train")
        corrected = calibrate.corrected_prediction(
            score, cal, chip_digest=digest, surface="train")
        assert abs(corrected - measured) < abs(raw - measured), arm


def test_apply_to_score_idempotent_with_provenance():
    """Calibration rewrites the prediction, never the terms: raw
    prediction + raw binding survive as provenance, re-applying
    replaces instead of compounding, and an unknown chip digest is a
    no-op copy."""
    from gke_ray_train_tpu.autotune import calibrate
    score = {"chip": "cpu", "t_compute_s": 0.02, "t_hbm_s": 0.01,
             "t_ici_s": 0.003, "t_dcn_s": 0.0,
             "exposed_penalty_s": 0.003, "binding": "compute",
             "mfu_ceiling": 0.5, "modeled_step_s": 0.023}
    cal = calibrate.fit_calibration([
        {"chip_digest": "d", "chip": "cpu", "binding": "compute",
         "raw": 0.023, "measured": 0.046}])
    once = calibrate.apply_to_score(score, cal, chip_digest="d")
    assert calibrate.apply_to_score(once, cal, chip_digest="d") == once
    assert once["raw_modeled_step_s"] == 0.023
    assert once["calibration"]["raw_binding"] == "compute"
    assert once["calibration"]["factors"]["compute"] == 2.0
    # corrected = max(2*.02, 1*.01, 1*.003) + 1*.003
    assert once["modeled_step_s"] == pytest.approx(0.043)
    assert once["t_compute_s"] == 0.02          # terms stay raw
    assert score["modeled_step_s"] == 0.023     # input not mutated
    same = calibrate.apply_to_score(score, cal, chip_digest="other")
    assert same == score and same is not score


def test_reingest_and_refit_bitwise_idempotent(fixture_registry):
    """Re-ingesting the same run dir and re-fitting the same registry
    state are BYTE-level no-ops — rows dedupe on their identity key,
    floats were rounded once at extraction, and the fit sums in sorted
    order."""
    from gke_ray_train_tpu.autotune import calibrate, registry
    registry.ingest_observed(OBS_GOOD, directory=fixture_registry)
    registry.fit_and_save_calibration(fixture_registry)
    # second ingest re-judges drift (in band) and writes the verdict
    s = registry.ingest_observed(OBS_GOOD, directory=fixture_registry)
    assert s["calibrated"] and not s["drift"]
    path, entry = _one_entry(fixture_registry)
    assert entry["drift"]["stale"] is False
    assert entry["drift"]["rel_err"] <= entry["drift"]["band"]
    with open(path, "rb") as f:
        entry_bytes = f.read()
    with open(calibrate.cal_path(fixture_registry), "rb") as f:
        cal_bytes = f.read()
    registry.ingest_observed(OBS_GOOD, directory=fixture_registry)
    registry.fit_and_save_calibration(fixture_registry)
    with open(path, "rb") as f:
        assert f.read() == entry_bytes
    with open(calibrate.cal_path(fixture_registry), "rb") as f:
        assert f.read() == cal_bytes


def test_drift_trips_stale_event_and_overlay_refusal(fixture_registry,
                                                     tmp_path, caplog):
    """The teeth, end to end: the doctored run (10x the model) trips
    the band -> rc 5, the entry goes STALE, a schema-valid
    autotune_drift event lands in the run dir, validate_entry names
    the drift, and maybe_apply REFUSES while the run continues
    untuned. A healthier re-judge under a wider band then CLEARS the
    flag — self-correcting, not a one-way latch."""
    import shutil
    from gke_ray_train_tpu.autotune import registry
    from gke_ray_train_tpu.autotune.__main__ import main
    from gke_ray_train_tpu.obs.events import (
        STAMP_FIELDS, iter_events, validate_event)
    obs_doc = str(tmp_path / "obs_doctored")
    shutil.copytree(OBS_DOCTORED, obs_doc)
    assert main(["ingest", OBS_GOOD, "--dir", fixture_registry]) == 0
    assert main(["calibrate", "--dir", fixture_registry]) == 0
    assert main(["ingest", obs_doc, "--dir", fixture_registry]) == 5
    _, entry = _one_entry(fixture_registry)
    assert entry["stale"] is True
    assert entry["drift"]["rel_err"] > entry["drift"]["band"]
    # the drift event is real telemetry: schema-valid, in the run dir
    evs = list(iter_events(obs_doc, kinds=("autotune_drift",)))
    assert len(evs) == 1
    payload = {k: v for k, v in evs[0].items()
               if k not in STAMP_FIELDS}
    validate_event("autotune_drift", payload)
    assert payload["stale"] is True and payload["key"] == entry["key"]
    # overlay refusal: loud, named, and the plan keeps running untuned
    base = plan_for_preset("tiny_fsdp8")
    cfg = preset_model_cfg("tiny_fsdp8")
    findings = registry.validate_entry(entry, base, cfg)
    assert any("STALE" in f for f in findings)
    armed = dataclasses.replace(base, autotune=True)
    with caplog.at_level("WARNING"):
        plan, applied = registry.maybe_apply(
            armed, model_cfg=cfg,
            config={"AUTOTUNE_DIR": fixture_registry})
    assert plan is armed and not applied
    assert any("REFUSING" in r.getMessage() for r in caplog.records)
    # explain surfaces the verdict without crashing on a stale entry
    assert main(["explain", "--dir", fixture_registry]) == 0
    # the same evidence re-judged under a wider band clears the flag
    s = registry.ingest_observed(obs_doc, directory=fixture_registry,
                                 band=10.0)
    assert not s["drift"]
    _, entry = _one_entry(fixture_registry)
    assert "stale" not in entry and entry["drift"]["stale"] is False


def test_ingest_refusal_matrix(fixture_registry):
    """Row gates in refusal order (surface, topology, chip family,
    backend missing, backend-vs-chip both directions) plus the
    entry-level version gates that refuse BEFORE any row lands."""
    from gke_ray_train_tpu.autotune import registry
    from gke_ray_train_tpu.autotune.__main__ import main
    path, entry = _one_entry(fixture_registry)
    row = {"surface": "train", "topology": "cpu-8",
           "chip_family": "cpu", "backend": "cpu"}
    assert registry._row_refusal(row, entry) is None
    assert "surface mismatch" in registry._row_refusal(
        {**row, "surface": "serve"}, entry)
    assert "topology drift" in registry._row_refusal(
        {**row, "topology": "cpu-4"}, entry)
    assert "no backend stamp" in registry._row_refusal(
        {**row, "backend": None}, entry)
    # a real-backend number is not evidence about the CPU spec
    assert "does not describe" in registry._row_refusal(
        {**row, "backend": "tpu"}, entry)
    # THE gate, inverted: host numbers can never calibrate a TPU entry
    v5e = json.loads(json.dumps(entry))
    v5e["topology"] = "v5e-8"
    v5e["fingerprint_inputs"]["chip"] = "v5e"
    tpu_row = {"surface": "train", "topology": "v5e-8",
               "chip_family": "v5e", "backend": "cpu"}
    assert "can NEVER calibrate" in registry._row_refusal(tpu_row, v5e)
    # an unknown chip family is host evidence (scored as cpu), so it
    # is chip-family-refused against the v5e entry too
    assert "chip family drift" in registry._row_refusal(
        {**tpu_row, "chip_family": "weird"}, v5e)

    # entry-level version gates: fingerprint-matched rows exist but
    # every entry refuses -> rc 4, and nothing is written
    for field, bogus in (("scorer_version", -1),
                         ("calibration_version", -1)):
        doctored = json.loads(json.dumps(entry))
        doctored["fingerprint_inputs"][field] = bogus
        _rewrite_entry(path, doctored)
        assert main(["ingest", OBS_GOOD, "--dir",
                     fixture_registry]) == 4
        _, now = _one_entry(fixture_registry)
        assert not now.get("observed")
    # restore -> nothing-matched contract on an EMPTY obs dir is rc 3
    _rewrite_entry(path, entry)
    empty = os.path.join(fixture_registry, "empty_obs")
    os.makedirs(empty)
    assert main(["ingest", empty, "--dir", fixture_registry]) == 3
    # calibrate with no observed rows anywhere: rc 3 too
    assert main(["calibrate", "--dir", fixture_registry]) == 3


def test_cpu_rows_never_calibrate_tpu_entry(fixture_registry,
                                            tmp_path, capsys):
    """The satellite-3 regression, full-ingest path: re-key the
    fixture entry as a v5e tune, measure the SAME fingerprints on a
    CPU host — ingest must refuse every row (rc 4) and the entry must
    gain zero observed columns."""
    from gke_ray_train_tpu.autotune.__main__ import main
    path, entry = _one_entry(fixture_registry)
    entry["topology"] = "v5e-8"
    entry["key"] = entry["key"].replace("cpu-8", "v5e-8")
    entry["fingerprint_inputs"]["chip"] = "v5e"
    os.remove(path)
    _rewrite_entry(path.replace("cpu-8", "v5e-8"), entry)
    with open(os.path.join(OBS_GOOD, "bench_records.jsonl")) as f:
        rec = json.loads(f.readline())
    rec["backend"] = "cpu"
    rec["topology"] = "v5e-8"
    obs = tmp_path / "obs_cpu"
    obs.mkdir()
    (obs / "bench_records.jsonl").write_text(json.dumps(rec) + "\n")
    assert main(["ingest", str(obs), "--dir", fixture_registry]) == 4
    assert "can NEVER calibrate" in capsys.readouterr().out
    _, now = _one_entry(fixture_registry)
    assert not now.get("observed")


def test_observed_columns_survive_entry_rerecord(fixture_registry):
    """A re-tune whose arms keep their plan fingerprints carries the
    observed evidence forward (re-stamped against the new scores);
    rows about plans the entry no longer proposes — and any stale /
    drift verdict — are dropped for the next ingest to re-judge."""
    from gke_ray_train_tpu.autotune import registry
    registry.ingest_observed(OBS_GOOD, directory=fixture_registry)
    path, entry = _one_entry(fixture_registry)
    assert {r["arm"] for r in entry["observed"]} == {"base", "tuned"}
    result = {
        "surface": "train",
        "scorer_version": entry["fingerprint_inputs"]["scorer_version"],
        "base": {"plan_fingerprint": entry["base_fingerprint"],
                 "score": entry["base_score"]},
        "winner": {"plan_fingerprint": entry["winner_fingerprint"],
                   "score": entry["score"]},
        "winner_tuned_fields": entry["tuned"],
        "winner_env": {},
        "improvement": entry["improvement"],
        "candidates": [], "pruned": [],
        "space": entry["space"],
    }
    base = plan_for_preset("tiny_fsdp8")
    cfg = preset_model_cfg("tiny_fsdp8")
    registry.save_entry(result, base_plan=base, model_cfg=cfg,
                        directory=fixture_registry)
    _, fresh = _one_entry(fixture_registry)
    assert len(fresh["observed"]) == 2
    assert {r["arm"] for r in fresh["observed"]} == {"base", "tuned"}
    assert "stale" not in fresh and "drift" not in fresh
    # a re-tune with a DIFFERENT winner drops the old tuned evidence
    moved = dict(result,
                 winner={"plan_fingerprint": "0" * 16,
                         "score": entry["score"]})
    registry.save_entry(moved, base_plan=base, model_cfg=cfg,
                        directory=fixture_registry)
    _, fresh = _one_entry(fixture_registry)
    assert {r["arm"] for r in fresh["observed"]} == {"base"}


def test_drift_band_knob(monkeypatch):
    """AUTOTUNE_DRIFT_BAND: config wins over env wins over the
    default; malformed values degrade to the default, loudly enough
    to live with."""
    from gke_ray_train_tpu.autotune.registry import (
        DRIFT_BAND_DEFAULT, drift_band)
    monkeypatch.delenv("AUTOTUNE_DRIFT_BAND", raising=False)
    assert drift_band() == DRIFT_BAND_DEFAULT
    monkeypatch.setenv("AUTOTUNE_DRIFT_BAND", "0.5")
    assert drift_band() == 0.5
    assert drift_band({"AUTOTUNE_DRIFT_BAND": "0.1"}) == 0.1
    monkeypatch.setenv("AUTOTUNE_DRIFT_BAND", "bogus")
    assert drift_band() == DRIFT_BAND_DEFAULT
    assert drift_band({"AUTOTUNE_DRIFT_BAND": -1}) == DRIFT_BAND_DEFAULT


def test_ingest_hook_gating(fixture_registry, tmp_path):
    """_run_worker's attempt-end hook: rank-0 only, AUTOTUNE_INGEST=0
    opts out, and NOTHING on this path is ever fatal — a broken
    registry dir degrades to a logged warning."""
    from gke_ray_train_tpu.rayint.trainer import _maybe_ingest_observed

    class Obs:
        rank = 0
        obs_dir = OBS_GOOD

    plan = dataclasses.replace(plan_for_preset("tiny_fsdp8"),
                               autotune=True)
    config = {"AUTOTUNE_DIR": fixture_registry}
    # opt-out plan / non-zero rank / no obs session: nothing written
    _maybe_ingest_observed(None, plan, config)
    off = dataclasses.replace(plan, autotune_ingest=False)
    _maybe_ingest_observed(Obs(), off, config)
    r1 = Obs()
    r1.rank = 1
    _maybe_ingest_observed(r1, plan, config)
    _, entry = _one_entry(fixture_registry)
    assert not entry.get("observed")
    # rank 0 + armed plan: the bench rows (search-time fingerprints)
    # match without any runtime_arms mapping
    _maybe_ingest_observed(Obs(), plan, config)
    _, entry = _one_entry(fixture_registry)
    assert len(entry["observed"]) == 2
    # never fatal: an unreadable registry path degrades to a warning
    _maybe_ingest_observed(Obs(), plan,
                           {"AUTOTUNE_DIR": str(tmp_path) + "\x00bad"})


def test_stale_entry_worker_attempt_completes_untuned(fixture_registry,
                                                      tmp_path, caplog):
    """Drift teeth never turn into a crash: a worker whose config says
    AUTOTUNE=1 against a drift-tripped entry logs the refusal and the
    attempt runs — and COMPLETES — on the untuned plan."""
    import shutil
    from gke_ray_train_tpu.analysis.plancheck import model_config_for
    from gke_ray_train_tpu.autotune import registry
    from gke_ray_train_tpu.rayint.trainer import _run_worker
    obs_doc = str(tmp_path / "obs_doctored")
    shutil.copytree(OBS_DOCTORED, obs_doc)
    registry.ingest_observed(OBS_GOOD, directory=fixture_registry)
    registry.fit_and_save_calibration(fixture_registry)
    s = registry.ingest_observed(obs_doc, directory=fixture_registry)
    assert s["drift"]
    # re-key the stale entry onto the model a SMOKE_TEST config
    # derives, so the worker's digest lookup HITS it (and then refuses
    # on staleness, not on a miss)
    base = plan_for_preset("tiny_fsdp8")
    config = {**{k: v for k, v in base.to_config().items()
                 if v is not None},
              "SMOKE_TEST": 1, "AUTOTUNE": 1,
              "AUTOTUNE_DIR": fixture_registry}
    smoke_cfg = model_config_for(config, ExecutionPlan.resolve(config))
    digest = registry.model_digest(smoke_cfg)
    path, entry = _one_entry(fixture_registry)
    key = registry.entry_key(digest, entry["topology"],
                             entry["surface"])
    entry["key"] = key
    entry["model_digest"] = digest
    entry["model"] = smoke_cfg.to_dict()
    entry["fingerprint_inputs"]["model_digest"] = digest
    entry["candidates_file"] = f"{key}.candidates.json"
    os.remove(path)
    _rewrite_entry(registry.entry_path(key, fixture_registry), entry)

    def fn(cfg_in):
        return {"ok": 1.0}

    with caplog.at_level("WARNING"):
        out = _run_worker(fn, config, {})
    assert out["metrics"] == {"ok": 1.0}
    assert out["plan_fingerprint"] == \
        ExecutionPlan.resolve(config).fingerprint()   # untuned plan
    msgs = [r.getMessage() for r in caplog.records]
    assert any("REFUSING" in m and "STALE" in m for m in msgs)

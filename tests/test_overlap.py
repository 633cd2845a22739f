"""Overlap execution path (plan knob OVERLAP) + fused Pallas kernels.

The contract under test (ISSUE 12 / ROADMAP #3):

- the three overlap modes produce BITWISE-identical loss streams on the
  canonical CPU mesh (off = GSPMD scan; xla = same program + TPU-only
  scheduler flags, inert here; manual = the shard_map microbatch
  pipeline of train/overlap.py);
- the re-recorded tiny_fsdp8 budget pins ``overlap_frac > 0`` with
  strictly fewer exposed collective bytes than the PR-9 baseline, and a
  de-overlapped program (the plain GSPMD schedule) TRIPS it with the
  exposure-region delta named;
- the fused kernels (ops/fused_norm_rope.py, ops/fused_ce.py) pass the
  differential registry sweep against their oracles under the
  checked-in tolerance pins, and a seeded precision regression is
  caught (KER101);
- the manual path dispatches recompile-free and preserves state
  donation (alias bytes >= 80%).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gke_ray_train_tpu.models import tiny
from gke_ray_train_tpu.perf.budget import (
    PRESETS, budget_path, load_budget, plan_for_preset)
from gke_ray_train_tpu.plan import ExecutionPlan, PlanError
from gke_ray_train_tpu.train import (
    make_optimizer, make_train_state, make_train_step)

# the PR-9 pre-overlap baseline: tiny_fsdp8 with every collective byte
# exposed (overlap_frac 0.0). The re-recorded budget must beat it —
# this literal is the regression floor the ISSUE names.
_PR9_FSDP8_EXPOSED_BYTES = 870224


def _drill_cfg(**kw):
    base = dict(d_model=64, n_layers=2, n_heads=2, n_kv_heads=2,
                d_ff=128, vocab_size=256, max_seq_len=64, remat=True)
    base.update(kw)
    return tiny(**base)


def _drill_plan(overlap, **kw):
    base = dict(data=2, fsdp=4, per_device_batch=1, max_seq_len=64,
                overlap=overlap, donate_state=False, donate_batch=False,
                compile_cache=False, aot_train_step=False, obs=False,
                topology="cpu-8")
    base.update(kw)
    return ExecutionPlan.from_kwargs(**base)


def _run_drill(overlap, cfg, *, steps=5, grad_accum=1, fused_ops=False,
               seed=0):
    plan = _drill_plan(overlap, grad_accum=grad_accum,
                       max_seq_len=cfg.max_seq_len, fused_ops=fused_ops)
    mesh = plan.build_mesh(jax.devices())
    opt = make_optimizer(1e-3)
    state = make_train_state(cfg, opt, jax.random.key(seed), mesh=mesh)
    step = make_train_step(cfg, opt, mesh=mesh, plan=plan)
    B = 8 * grad_accum
    losses = []
    for i in range(steps):
        k = jax.random.key(100 + i)
        batch = {
            "inputs": jax.random.randint(
                k, (B, cfg.max_seq_len), 0, cfg.vocab_size, jnp.int32),
            "targets": jax.random.randint(
                jax.random.fold_in(k, 1), (B, cfg.max_seq_len), 0,
                cfg.vocab_size, jnp.int32),
            "weights": jnp.ones((B, cfg.max_seq_len), jnp.float32),
        }
        batch = jax.device_put(batch, plan.batch_shardings(mesh))
        state, m = step(state, batch)
        losses.append(m["loss"])
    return [float(v) for v in jax.device_get(losses)], state


# ---------------------------------------------------------------------------
# bitwise equivalence
# ---------------------------------------------------------------------------

def _assert_same_to_the_last_ulp(off, man):
    """off against manual are two step programs: how XLA:CPU fuses the
    loss's final reduction in each decides its last float32 ulp (jax
    0.9.0: one loss of five differs by one), not the program. The
    gradients stay exact: test_bitwise_equivalence_gqa_deeper."""
    np.testing.assert_array_max_ulp(np.float32(off), np.float32(man),
                                    maxulp=2)


def test_bitwise_loss_equivalence_off_xla_manual():
    """The 5-step tiny_fsdp8 drill: all three modes, one loss stream."""
    cfg = _drill_cfg()
    off, _ = _run_drill("off", cfg)
    xla, _ = _run_drill("xla", cfg)
    man, _ = _run_drill("manual", cfg)
    # xla is off's program (the scheduler flags are inert off the TPU):
    # a fact of the program, so exact
    assert off == xla, (off, xla)
    _assert_same_to_the_last_ulp(off, man)


def test_bitwise_equivalence_with_grad_accum():
    """The microbatch pipeline: accum scan over shard_map'd micros."""
    cfg = _drill_cfg()
    off, s0 = _run_drill("off", cfg, steps=3, grad_accum=2)
    man, s1 = _run_drill("manual", cfg, steps=3, grad_accum=2)
    _assert_same_to_the_last_ulp(off, man)
    # The raw loss-grads are bitwise (the drills above pin that); the
    # full STATE is compared at tight tolerance instead of bitwise:
    # XLA fuses the adamw g**2 second-moment update into different
    # clusters in the two step programs, and the reassociated product
    # can differ in the last ulp — which round-trips into a param ulp
    # a few steps later without ever moving the loss stream past its
    # last ulp at drill length. Where a gradient element is itself near
    # zero, adam's m/sqrt(v) turns that ulp into a visible share of one
    # update (lr 1e-3): jax 0.9.0 reads 1.2e-7 in one element of w_up,
    # hence an atol of a thousandth of a step.
    assert jax.tree.structure(s0) == jax.tree.structure(s1)
    for a, b in zip(jax.tree.leaves(s0), jax.tree.leaves(s1)):
        assert jnp.allclose(a.astype(jnp.float32), b.astype(jnp.float32),
                            rtol=1e-4, atol=1e-6)


def test_bitwise_equivalence_gqa_deeper():
    """GQA heads + 4 layers + a 1k vocab — every grad-reduction class
    (gathered stacked leaves, embed, lm_head, replicated norms)."""
    cfg = _drill_cfg(n_layers=4, n_heads=4, n_kv_heads=2, d_ff=256,
                     vocab_size=1024, max_seq_len=128)
    off, _ = _run_drill("off", cfg, steps=3)
    man, _ = _run_drill("manual", cfg, steps=3)
    assert off == man


# ---------------------------------------------------------------------------
# plan validation / scope refusals
# ---------------------------------------------------------------------------

def test_manual_refuses_structural_axes():
    with pytest.raises(PlanError, match="manual"):
        ExecutionPlan.from_kwargs(model=2, fsdp=4, overlap="manual")
    with pytest.raises(PlanError, match="overlap"):
        ExecutionPlan.from_kwargs(overlap="bogus")


def test_manual_refuses_lora_and_moe():
    from gke_ray_train_tpu.train.overlap import (
        ManualOverlapUnsupported, check_manual_support)
    plan = _drill_plan("manual")
    mesh = plan.build_mesh(jax.devices())
    with pytest.raises(ManualOverlapUnsupported, match="LoRA"):
        check_manual_support(_drill_cfg(), mesh, lora=True)
    moe_cfg = tiny(d_model=64, n_layers=2, n_heads=2, n_kv_heads=2,
                   d_ff=128, vocab_size=256, max_seq_len=64,
                   n_experts=4, expert_top_k=2)
    with pytest.raises(ManualOverlapUnsupported, match="MoE"):
        check_manual_support(moe_cfg, mesh)


def test_overlap_env_dialect_off_spellings():
    assert ExecutionPlan.from_config({"OVERLAP": ""}).overlap == "off"
    assert ExecutionPlan.from_config({"OVERLAP": "0"}).overlap == "off"
    assert ExecutionPlan.from_config({"OVERLAP": "MANUAL"}
                                     ).overlap == "manual"


# ---------------------------------------------------------------------------
# budgets: the overlap claim is a checked-in number
# ---------------------------------------------------------------------------

def test_checked_in_fsdp8_budget_beats_pr9_baseline():
    doc = load_budget(budget_path("tiny_fsdp8"))
    assert doc["overlap_frac"] > 0.0
    assert doc["exposed_collective_bytes"] < _PR9_FSDP8_EXPOSED_BYTES
    assert doc["exposed_collective_bytes"] < doc["collective_bytes"]
    # the attribution lines carry the double-buffered classification
    assert any("double-buffered" in ln or "ahead of its first consumer"
               in ln for ln in doc["exposure_lines"])


def test_budget_trips_on_deoverlap():
    """Reintroduce the synchronous schedule (the plain GSPMD scan) and
    the comparator must name the exposure delta — a de-overlap cannot
    land silently."""
    from gke_ray_train_tpu.perf.budget import (
        BudgetViolation, assert_within_budget)
    from gke_ray_train_tpu.perf.costs import step_cost_report
    from gke_ray_train_tpu.train.step import batch_shardings

    plan = dataclasses.replace(plan_for_preset("tiny_fsdp8"),
                               overlap="off")
    mesh = plan.build_mesh(jax.devices())
    p = PRESETS["tiny_fsdp8"]
    cfg = _drill_cfg(max_seq_len=p.seq, remat=p.remat)
    opt = make_optimizer(1e-3)
    state = make_train_state(cfg, opt, jax.random.key(0), mesh=mesh)
    step = make_train_step(cfg, opt, mesh=mesh, plan=plan)
    batch = jax.device_put(
        {"inputs": jnp.zeros((p.batch, p.seq), jnp.int32),
         "targets": jnp.zeros((p.batch, p.seq), jnp.int32),
         "weights": jnp.ones((p.batch, p.seq), jnp.float32)},
        batch_shardings(mesh))
    report = step_cost_report(step.lower(state, batch).compile(),
                              tokens_per_step=p.batch * p.seq)
    with pytest.raises(BudgetViolation) as ei:
        assert_within_budget(report, budget_path("tiny_fsdp8"),
                             plan=plan)
    msg = str(ei.value)
    assert "overlap_frac" in msg or "exposed_collective_bytes" in msg
    assert "HLO" in msg   # the exposure-region delta is printed


def test_checked_in_budgets_pass():
    """The shipped budgets match the shipped code (the tier-1 gate the
    CI lint job also runs)."""
    from gke_ray_train_tpu.perf.budget import (
        assert_within_budget, build_preset_report)
    for name in ("tiny_fsdp8", "tiny_dp8"):
        report = build_preset_report(name)
        assert_within_budget(report, budget_path(name),
                             plan=plan_for_preset(name))


# ---------------------------------------------------------------------------
# fused kernels
# ---------------------------------------------------------------------------

@pytest.mark.slow  # ~25s all-preset sweep; the fused-kernel precision
# contract stays in tier-1 via test_seeded_precision_regression_caught
def test_fused_kernels_within_pinned_ledger():
    from gke_ray_train_tpu.analysis.kernelcheck import (
        ledger_findings, sweep)
    results = sweep(["fused_norm_rope", "fused_cross_entropy"])
    assert len(results) >= 9
    findings = ledger_findings(results)
    assert findings == [], [str(f) for f in findings]


def test_seeded_precision_regression_caught(monkeypatch):
    """Corrupt the fused norm kernel's variance term and the pinned
    f32 ledger must flag KER101 through the REAL sweep path."""
    from gke_ray_train_tpu.analysis.kernelcheck import (
        ledger_findings, run_case)
    from gke_ray_train_tpu.ops import fused_norm_rope, registry

    real = fused_norm_rope._norm_block

    def corrupt(x32, scale32, *, eps, scale_plus_one):
        return real(x32, scale32, eps=eps + 3e-2,
                    scale_plus_one=scale_plus_one)

    monkeypatch.setattr(fused_norm_rope, "_norm_block", corrupt)
    spec = registry.get("fused_norm_rope")
    case = next(c for c in spec.cases if c.name == "norm_f32")
    findings = ledger_findings([run_case(spec, case)])
    assert any(f.rule == "KER101" for f in findings), \
        [str(f) for f in findings]


def test_fused_train_step_close_to_unfused():
    """FUSED_OPS through make_train_step: same model, same batches —
    losses agree to fp tolerance (NOT bitwise: blockwise logsumexp
    accumulates in a different order; that is why the knob is
    compile-relevant and budgets are recorded with it off)."""
    cfg = _drill_cfg(max_seq_len=128)
    plain, _ = _run_drill("off", cfg, steps=3)
    fused, _ = _run_drill("off", cfg, steps=3, fused_ops=True)
    assert plain != [] and len(plain) == len(fused)
    for a, b in zip(plain, fused):
        assert abs(a - b) / abs(a) < 1e-4, (plain, fused)


def test_fused_manual_compose():
    """The manual pipeline with fused kernels on: runs, and stays close
    to the plain path (the composition the plan can declare)."""
    cfg = _drill_cfg(max_seq_len=128)
    plain, _ = _run_drill("off", cfg, steps=2)
    both, _ = _run_drill("manual", cfg, steps=2, fused_ops=True)
    for a, b in zip(plain, both):
        assert abs(a - b) / abs(a) < 1e-4


def test_fused_ce_trains_the_unembedding():
    """Regression (code review): the fused-CE head must come from the
    DIFFERENTIATED arg in full fine-tuning — taking it from the frozen
    alias silently zeroed the lm_head gradient."""
    cfg = _drill_cfg(max_seq_len=128)
    updates = {}
    for fused in (False, True):
        plan = _drill_plan("off", max_seq_len=128, fused_ops=fused)
        mesh = plan.build_mesh(jax.devices())
        opt = make_optimizer(1e-3)
        state = make_train_state(cfg, opt, jax.random.key(0), mesh=mesh)
        step = make_train_step(cfg, opt, mesh=mesh, plan=plan)
        batch = jax.device_put(
            {"inputs": jax.random.randint(
                jax.random.key(2), (8, 128), 0, 256, jnp.int32),
             "targets": jax.random.randint(
                 jax.random.key(3), (8, 128), 0, 256, jnp.int32),
             "weights": jnp.ones((8, 128), jnp.float32)},
            plan.batch_shardings(mesh))
        s1, _ = step(state, batch)
        updates[fused] = float(jnp.max(jnp.abs(
            s1.params["lm_head"] - state.params["lm_head"])))
    # same order of magnitude — the head actually trains on both arms
    assert updates[True] > 0.3 * updates[False], updates


def test_fused_kernel_knobs_audited():
    from gke_ray_train_tpu.config import KNOWN_KEYS, PLAN_SCOPED_KEYS
    from gke_ray_train_tpu.plan import CONFIG_KEYS, ENV_FORWARD_KEYS
    for key in ("OVERLAP", "FUSED_OPS"):
        assert key in KNOWN_KEYS
        assert key in PLAN_SCOPED_KEYS
        assert key in CONFIG_KEYS.values()
        assert key in ENV_FORWARD_KEYS


def test_overlap_fused_are_train_compile_relevant():
    """Both knobs must stale TRAIN sidecars (they change the compiled
    step) and must NOT touch the serve surface — the OBS-exclusion
    twin, pinned from the other side."""
    base = _drill_plan("off")
    man = dataclasses.replace(base, overlap="manual")
    fused = dataclasses.replace(base, fused_ops=True)
    assert man.compile_fingerprint("train") != \
        base.compile_fingerprint("train")
    assert fused.compile_fingerprint("train") != \
        base.compile_fingerprint("train")
    assert man.compile_fingerprint("serve") == \
        base.compile_fingerprint("serve")
    assert fused.compile_fingerprint("serve") == \
        base.compile_fingerprint("serve")


def test_overlap_three_dialects_agree():
    kw = ExecutionPlan.from_kwargs(overlap="manual", fused_ops=True)
    cfgd = ExecutionPlan.from_config({"OVERLAP": "manual",
                                      "FUSED_OPS": "1"})
    envd = ExecutionPlan.from_env({"OVERLAP": "manual",
                                   "FUSED_OPS": "true"})
    assert kw.fingerprint() == cfgd.fingerprint() == envd.fingerprint()


# ---------------------------------------------------------------------------
# recompile-free dispatch + donation
# ---------------------------------------------------------------------------

def test_manual_path_recompile_free():
    from gke_ray_train_tpu.analysis.jaxprcheck import RecompileDetector
    cfg = _drill_cfg()
    plan = _drill_plan("manual")
    mesh = plan.build_mesh(jax.devices())
    opt = make_optimizer(1e-3)
    state = make_train_state(cfg, opt, jax.random.key(0), mesh=mesh)
    step = make_train_step(cfg, opt, mesh=mesh, plan=plan)

    def batch(i):
        return jax.device_put(
            {"inputs": jax.random.randint(
                jax.random.key(i), (8, 64), 0, 256, jnp.int32),
             "targets": jax.random.randint(
                 jax.random.key(i + 50), (8, 64), 0, 256, jnp.int32),
             "weights": jnp.ones((8, 64), jnp.float32)},
            plan.batch_shardings(mesh))

    state, m = step(state, batch(0))       # trace + compile once
    jax.block_until_ready(m["loss"])
    with RecompileDetector() as det:
        for i in range(1, 4):
            state, m = step(state, batch(i))
            jax.block_until_ready(m["loss"])
    assert det.recompiled() == {}, det.recompiled()


def test_manual_path_donation_held():
    from gke_ray_train_tpu.perf.costs import assert_state_donation
    cfg = _drill_cfg()
    plan = dataclasses.replace(_drill_plan("manual"), donate_state=True)
    mesh = plan.build_mesh(jax.devices())
    opt = make_optimizer(1e-3)
    state = make_train_state(cfg, opt, jax.random.key(0), mesh=mesh)
    step = make_train_step(cfg, opt, mesh=mesh, plan=plan)
    batch = jax.device_put(
        {"inputs": jnp.zeros((8, 64), jnp.int32),
         "targets": jnp.zeros((8, 64), jnp.int32),
         "weights": jnp.ones((8, 64), jnp.float32)},
        plan.batch_shardings(mesh))
    compiled = step.lower(state, batch).compile()
    alias = assert_state_donation(compiled, state, min_frac=0.8)
    assert alias != 0


# ---------------------------------------------------------------------------
# overlap_stats v2: bytes-weighted + carried classification
# ---------------------------------------------------------------------------

_CARRIED_HLO = """\
HloModule m

%body (arg: (f32[64,64], f32[16,64])) -> (f32[64,64], f32[16,64]) {
  %arg = (f32[64,64]{1,0}, f32[16,64]{1,0}) parameter(0)
  %gte0 = f32[64,64]{1,0} get-tuple-element((f32[64,64]{1,0}, f32[16,64]{1,0}) %arg), index=0
  %gte1 = f32[16,64]{1,0} get-tuple-element((f32[64,64]{1,0}, f32[16,64]{1,0}) %arg), index=1
  %all-gather = f32[64,64]{1,0} all-gather(f32[16,64]{1,0} %gte1), dimensions={0}
  %copy = f32[64,64]{1,0} copy(f32[64,64]{1,0} %all-gather)
  %dot = f32[64,64]{1,0} dot(f32[64,64]{1,0} %gte0, f32[64,64]{1,0} %gte0)
  %fusion = f32[64,64]{1,0} fusion(f32[64,64]{1,0} %dot)
  %slice-f = f32[16,64]{1,0} fusion(f32[64,64]{1,0} %fusion)
  ROOT %tuple = (f32[64,64]{1,0}, f32[16,64]{1,0}) tuple(%copy, %slice-f)
}
"""


def test_overlap_stats_carried_collective_hidden():
    """A gather whose result is consumed only by the next loop
    iteration (flows to the body root through a copy) is the
    double-buffered prefetch shape — hidden, with the body's
    independent compute attributed."""
    from gke_ray_train_tpu.perf.costs import overlap_stats
    exposed, frac, lines = overlap_stats(_CARRIED_HLO)
    assert exposed == 0 and frac == 1.0
    assert len(lines) == 1 and "double-buffered" in lines[0]


def test_overlap_stats_carried_needs_bytes():
    """Bytes-weighted: the same carried shape with only a thin fusion
    in the body cannot hide a bigger collective."""
    from gke_ray_train_tpu.perf.costs import overlap_stats
    hlo = _CARRIED_HLO.replace(
        "  %dot = f32[64,64]{1,0} dot(f32[64,64]{1,0} %gte0, "
        "f32[64,64]{1,0} %gte0)\n", "").replace(
        "%fusion = f32[64,64]{1,0} fusion(f32[64,64]{1,0} %dot)",
        "%fusion = f32[2,2]{1,0} fusion(f32[64,64]{1,0} %gte0)")
    exposed, frac, lines = overlap_stats(hlo)
    assert exposed == 64 * 64 * 4 and frac == 0.0
    assert "EXPOSED" in lines[0]


def test_overlap_stats_async_thin_window_exposed():
    """An async pair whose window holds less independent compute than
    the collective's own bytes is EXPOSED (the satellite: a 1-op
    window cannot mask a multi-MB all-gather)."""
    from gke_ray_train_tpu.perf.costs import overlap_stats
    hlo = """\
HloModule m

ENTRY %main (p: f32[512,512]) -> f32[512,512] {
  %p = f32[512,512]{1,0} parameter(0)
  %ar-start = f32[512,512]{1,0} all-reduce-start(f32[512,512]{1,0} %p)
  %dot = f32[2,2]{1,0} dot(f32[2,2]{1,0} %p, f32[2,2]{1,0} %p)
  %ar-done = f32[512,512]{1,0} all-reduce-done(f32[512,512]{1,0} %ar-start)
  ROOT %add = f32[512,512]{1,0} add(f32[512,512]{1,0} %ar-done, f32[2,2]{1,0} %dot)
}
"""
    exposed, frac, lines = overlap_stats(hlo)
    assert exposed == 512 * 512 * 4 and frac == 0.0
    assert "thin window" in lines[0]


def test_overlap_stats_survives_tpu_tile_annotations():
    """Regression (code review): TPU-dumped HLO carries tile-layout
    annotations like ``{1,0:T(8,128)}`` whose ``T(`` must not shadow
    the opcode — the carried gather stays hidden with them present."""
    from gke_ray_train_tpu.perf.costs import overlap_stats
    hlo = _CARRIED_HLO.replace("{1,0}", "{1,0:T(8,128)}")
    assert "T(8,128)" in hlo
    exposed, frac, lines = overlap_stats(hlo)
    assert exposed == 0 and frac == 1.0
    assert "double-buffered" in lines[0]


def test_overlap_stats_entry_output_collective_exposed():
    """Regression (code review): a collective feeding only the ENTRY
    output tuple has no consumer to overlap with — it stalls the step
    before returning and must stay EXPOSED even with trailing
    independent compute in the schedule."""
    from gke_ray_train_tpu.perf.costs import overlap_stats
    hlo = """\
HloModule m

ENTRY %main (p: f32[64,64]) -> (f32[64,64], f32[64,64]) {
  %p = f32[64,64]{1,0} parameter(0)
  %ar = f32[64,64]{1,0} all-reduce(f32[64,64]{1,0} %p)
  %d1 = f32[64,64]{1,0} dot(f32[64,64]{1,0} %p, f32[64,64]{1,0} %p)
  %d2 = f32[64,64]{1,0} dot(f32[64,64]{1,0} %d1, f32[64,64]{1,0} %d1)
  ROOT %tuple = (f32[64,64]{1,0}, f32[64,64]{1,0}) tuple(%d2, %ar)
}
"""
    exposed, frac, lines = overlap_stats(hlo)
    assert exposed == 64 * 64 * 4 and frac == 0.0
    assert "EXPOSED" in lines[0]


def test_manual_accepts_fill_axes_that_resolve_to_one():
    """Regression (code review): model=-1 that fills to 1 on the
    declared topology IS a data/fsdp mesh — the manual path must not
    refuse it on the raw field value."""
    plan = ExecutionPlan.from_kwargs(data=2, fsdp=4, model=-1,
                                     overlap="manual", topology="cpu-8")
    assert plan.resolved_sizes()["model"] == 1
    with pytest.raises(PlanError, match="manual"):
        # and a fill that resolves to >1 is still refused
        ExecutionPlan.from_kwargs(data=2, fsdp=2, model=-1,
                                  overlap="manual", topology="cpu-8")


def test_xla_overlap_options_parse_as_bools():
    """Regression (code review): jaxlib rejects lowercase \"true\"
    strings for bool compiler options — the dict must hold values the
    option parser accepts (verified against a real bool option here,
    since the TPU-only flag names don't exist on the CPU backend)."""
    from gke_ray_train_tpu.plan import XLA_OVERLAP_OPTIONS, XLA_TPU_OPTIONS
    assert all(isinstance(v, bool) for v in XLA_OVERLAP_OPTIONS.values())
    assert all(isinstance(v, bool) for v in XLA_TPU_OPTIONS.values())
    import jax
    f = jax.jit(lambda x: x + 1,
                compiler_options={"xla_cpu_enable_fast_math": False})
    assert float(f(jnp.zeros(()))) == 1.0


def test_manual_step_hlo_shows_hidden_gathers():
    """The compiled manual step's own scheduled HLO classifies gathers
    as hidden — the live program, not a fixture."""
    from gke_ray_train_tpu.perf.budget import build_preset_step
    from gke_ray_train_tpu.perf.costs import step_cost_report
    compiled, _, _ = build_preset_step("tiny_fsdp8")
    report = step_cost_report(compiled)
    assert report.overlap_frac > 0.0
    assert report.exposed_collective_bytes < report.collective_bytes

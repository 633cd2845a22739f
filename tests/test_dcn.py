"""DCN-aware hierarchical gradient sync (ISSUE 13 / ROADMAP #4).

The contract under test:

- on the emulated 2-slice hybrid mesh, ``DCN_SYNC=flat`` and ``=hier``
  produce BITWISE-identical loss streams through the real
  ``make_train_step`` (the shared slice-staged accumulation grouping),
  including under grad accumulation, while hier sends ``1/ici_size``
  of flat's bytes across the slice boundary — pinned by the checked-in
  ``tiny_hybrid_2x4_{flat,hier}`` budget pair;
- ``DCN_COMPRESS=bf16`` casts only the DCN hop (error feedback across
  the accum scan) — close, NOT bitwise, tolerance-pinned in the
  ``hier_psum`` kernelcheck ledger, and a seeded precision regression
  is caught (KER101);
- ``perf/costs.py`` attributes every collective's bytes to the fabric
  its replica groups span (ICI vs DCN) and multiplies while-body
  collectives by their statically-known trip count;
- a reshard that fattens the cross-slice hop trips both the budget
  comparator (with the per-op DCN delta named) and the one-sided
  ``analysis check`` rule;
- the plan knobs audit end-to-end (3-dialect coercion, equal
  fingerprints, loud no-op downgrade on single-slice, refusals, train
  surface only).
"""

from __future__ import annotations

import json
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gke_ray_train_tpu.models import tiny
from gke_ray_train_tpu.perf.budget import budget_path, load_budget
from gke_ray_train_tpu.plan import ExecutionPlan, PlanError
from gke_ray_train_tpu.train import (
    make_optimizer, make_train_state, make_train_step)


def _drill_cfg(**kw):
    base = dict(d_model=64, n_layers=2, n_heads=2, n_kv_heads=2,
                d_ff=128, vocab_size=256, max_seq_len=64, remat=True)
    base.update(kw)
    return tiny(**base)


def _drill_plan(dcn_sync, *, dcn_compress="none", grad_accum=1, **kw):
    base = dict(data=2, fsdp=4, num_slices=2, per_device_batch=1,
                grad_accum=grad_accum, max_seq_len=64,
                overlap="manual", dcn_sync=dcn_sync,
                dcn_compress=dcn_compress,
                donate_state=False, donate_batch=False,
                compile_cache=False, aot_train_step=False, obs=False,
                topology="cpu-8")
    base.update(kw)
    return ExecutionPlan.from_kwargs(**base)


# the session-scoped 2-slice mesh (tests/conftest.py::hybrid_mesh),
# bound once per module by the autouse fixture below: every drill arm
# uses the SAME mesh object (the arms differ in sync/compress/accum,
# never in topology), instead of rebuilding it per call
_MESH: list = []


@pytest.fixture(autouse=True)
def _bind_hybrid_mesh(hybrid_mesh):
    _MESH[:] = [hybrid_mesh]


def _drill_mesh(plan):
    return _MESH[0] if _MESH else plan.build_mesh(jax.devices())


def _run_drill(dcn_sync, *, dcn_compress="none", grad_accum=1, steps=4,
               with_report=False, cfg=None):
    cfg = cfg or _drill_cfg()
    plan = _drill_plan(dcn_sync, dcn_compress=dcn_compress,
                       grad_accum=grad_accum)
    mesh = _drill_mesh(plan)
    opt = make_optimizer(1e-3)
    state = make_train_state(cfg, opt, jax.random.key(0), mesh=mesh)
    step = make_train_step(cfg, opt, mesh=mesh, plan=plan)
    rng = np.random.default_rng(7)
    B = 8 * grad_accum
    losses = []
    report = None
    for i in range(steps):
        batch = jax.device_put(
            {"inputs": jnp.asarray(rng.integers(0, 256, (B, 64)),
                                   jnp.int32),
             "targets": jnp.asarray(rng.integers(0, 256, (B, 64)),
                                    jnp.int32),
             "weights": jnp.ones((B, 64), jnp.float32)},
            plan.batch_shardings(mesh))
        if i == 0 and with_report:
            from gke_ray_train_tpu.perf.costs import step_cost_report
            compiled = step.lower(state, batch).compile()
            report = step_cost_report(compiled, num_slices=2)
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    return (losses, report) if with_report else losses


# ---------------------------------------------------------------------------
# the bitwise flat-vs-hier drill (+ the manual-overlap compose)
# ---------------------------------------------------------------------------

@pytest.mark.slow  # ~12s double elastic drill; the flat-vs-hier bitwise
# contract stays in tier-1 via test_flat_vs_hier_bitwise_under_grad_accum
def test_flat_vs_hier_bitwise_with_live_dcn_shrink():
    """One drill, three claims: bitwise loss streams, the live compiled
    programs' DCN bytes shrink by ~1/ici_size, and the hier program
    still double-buffers its gathers (the manual-overlap compose —
    hiding collectives and shrinking the DCN hop are not either/or)."""
    loss_flat, rep_flat = _run_drill("flat", with_report=True)
    loss_hier, rep_hier = _run_drill("hier", with_report=True)
    assert loss_flat == loss_hier          # bitwise, not allclose
    assert rep_hier.dcn_bytes < rep_flat.dcn_bytes
    # ici_size = fsdp(4) x data_intra(1); scalars + indivisible leaves
    # are the epsilon
    assert rep_hier.dcn_bytes <= (1 / 4 + 0.01) * rep_flat.dcn_bytes
    assert rep_hier.overlap_frac > 0.0
    assert rep_hier.ici_bytes + rep_hier.dcn_bytes \
        == rep_hier.collective_bytes


def test_flat_vs_hier_bitwise_under_grad_accum():
    loss_flat = _run_drill("flat", grad_accum=2, steps=3)
    loss_hier = _run_drill("hier", grad_accum=2, steps=3)
    assert loss_flat == loss_hier


def test_compressed_arm_close_not_bitwise():
    """DCN_COMPRESS=bf16: the hop is cast, so the stream tracks the
    f32 arms closely but must NOT be bitwise-identical (a compressed
    arm that matches bitwise means the cast silently did not happen)."""
    loss_hier = _run_drill("hier", grad_accum=2, steps=3)
    loss_comp = _run_drill("hier", dcn_compress="bf16", grad_accum=2,
                           steps=3)
    assert loss_comp != loss_hier
    assert np.allclose(loss_comp, loss_hier, rtol=2e-2)


def test_hier_psum_vjp_identity():
    """The custom VJP passes the cotangent through unchanged — AD can
    never transpose the scatter/gather chain into a differently-grouped
    reduction (which would cost the bitwise contract)."""
    from jax.sharding import PartitionSpec as P

    from jax import shard_map
    from gke_ray_train_tpu.parallel.hierarchical import (
        SliceTopology, hier_psum)

    mesh = _drill_mesh(_drill_plan("flat"))
    topo = SliceTopology(num_slices=2, data=2, fsdp=4)
    x = jnp.arange(8 * 16, dtype=jnp.float32).reshape(8, 16)

    def local(v):
        return jax.grad(
            lambda u: jnp.sum(hier_psum(u, topo, mode="hier") * 3.0))(v)

    g = shard_map(local, mesh=mesh, in_specs=P(("data", "fsdp"), None),
                  out_specs=P(("data", "fsdp"), None),
                  check_vma=False)(x)
    assert np.all(np.asarray(g) == 3.0)


def test_slice_topology_contract():
    from gke_ray_train_tpu.parallel.hierarchical import (
        HierSyncUnsupported, SliceTopology, slice_topology)

    mesh = _drill_mesh(_drill_plan("flat"))
    topo = slice_topology(mesh, 2)
    assert topo.ici_size == 4 and topo.data_intra == 1
    assert topo.intra_groups == ((0,), (1,))
    assert topo.cross_groups == ((0, 1),)
    assert slice_topology(mesh, 1) is None
    t42 = SliceTopology(num_slices=2, data=4, fsdp=2)
    assert t42.intra_groups == ((0, 1), (2, 3))
    assert t42.cross_groups == ((0, 2), (1, 3))
    with pytest.raises(HierSyncUnsupported, match="divisible"):
        slice_topology(mesh, 3)


# ---------------------------------------------------------------------------
# per-axis byte attribution + while-trip accounting (perf/costs.py)
# ---------------------------------------------------------------------------

_SLICE_MAP = [0, 0, 0, 0, 1, 1, 1, 1]


def test_axis_attribution_unit_hlos():
    from gke_ray_train_tpu.perf.costs import collective_axis_stats

    flat = ("%ar = f32[64]{0} all-reduce(f32[64]{0} %x), "
            "replica_groups={{0,1,2,3,4,5,6,7}}")
    local = ("%ag = f32[64]{0} all-gather(f32[16]{0} %x), "
             "replica_groups={{0,1,2,3},{4,5,6,7}}, dimensions={0}")
    iota_local = ("%rs = f32[16]{0} reduce-scatter(f32[64]{0} %x), "
                  "replica_groups=[2,4]<=[8], dimensions={0}")
    iota_cross = ("%ar2 = f32[16]{0} all-reduce(f32[16]{0} %x), "
                  "replica_groups=[4,2]<=[2,4]T(1,0)")
    permute = ("%cp = f32[8]{0} collective-permute(f32[8]{0} %x), "
               "source_target_pairs={{0,4},{4,0}}")
    ici, dcn, lines = collective_axis_stats(
        "\n".join([flat, local, iota_local, iota_cross, permute]),
        _SLICE_MAP)
    # flat {0..7} -> DCN; {0,1,2,3},{4,5,6,7} and [2,4]<=[8] are
    # slice-local -> ICI; the transposed iota pairs {0,4}.. cross, and
    # so does the 0<->4 permute
    assert dcn == 64 * 4 + 16 * 4 + 8 * 4
    assert ici == 64 * 4 + 16 * 4
    assert any("all-reduce" in ln and "crosses" in ln for ln in lines)

    # a single-slice map attributes EVERYTHING to ICI
    ici1, dcn1, _ = collective_axis_stats(
        "\n".join([flat, local]), [0] * 8)
    assert dcn1 == 0 and ici1 == 64 * 4 + 64 * 4


def test_while_trip_count_multiplies_bytes_not_counts():
    from gke_ray_train_tpu.perf.costs import (
        collective_axis_stats, collective_stats, overlap_stats)

    hlo = """
%body (p: (s32[], f32[64])) -> (s32[], f32[64]) {
  %ar = f32[64]{0} all-reduce(f32[64]{0} %g), replica_groups={{0,1,2,3,4,5,6,7}}
  ROOT %t = (s32[], f32[64]) tuple(%iv, %ar)
}
ENTRY %main (p0: f32[64]) -> f32[64] {
  %w = (s32[], f32[64]) while((s32[], f32[64]) %init), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"3"}}
  %ar2 = f32[64]{0} all-reduce(f32[64]{0} %p0), replica_groups={{0,1,2,3,4,5,6,7}}
  ROOT %r = f32[64]{0} copy(%w)
}
"""
    counts, nbytes, lines = collective_stats(hlo)
    assert counts["all-reduce"] == 2          # static op count
    assert nbytes == 64 * 4 * 3 + 64 * 4      # body x3 + entry x1
    assert any("x3 while-trip" in ln for ln in lines)
    ici, dcn, _ = collective_axis_stats(hlo, _SLICE_MAP)
    assert dcn == nbytes and ici == 0
    exposed, frac, _ = overlap_stats(hlo)
    assert exposed == nbytes                   # both scale together


def test_while_trip_count_nested_and_fallback():
    from gke_ray_train_tpu.perf.costs import _while_trip_counts

    hlo = """
%inner_cond (p: (s32[])) -> pred[] {
  %c = s32[] constant(5)
  %gte = s32[] get-tuple-element((s32[]) %p), index=0
  ROOT %cmp = pred[] compare(s32[] %gte, s32[] %c), direction=LT
}
%inner_body (p: (s32[])) -> (s32[]) {
  ROOT %t = (s32[]) tuple(%iv)
}
%outer_body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %w2 = (s32[]) while((s32[]) %i), condition=%inner_cond, body=%inner_body
  ROOT %t2 = (s32[], f32[8]) tuple(%iv, %x)
}
ENTRY %main (p0: f32[8]) -> f32[8] {
  %w = (s32[], f32[8]) while((s32[], f32[8]) %init), condition=%oc, body=%outer_body, backend_config={"known_trip_count":{"n":"2"}}
  ROOT %r = f32[8]{0} copy(%w)
}
"""
    trips = _while_trip_counts(hlo)
    assert trips["outer_body"] == 2
    # inner: 5 (condition-parse fallback) x 2 (outer container)
    assert trips["inner_body"] == 10


def test_root_while_trip_count_seen():
    """A while op printed as the computation ROOT (a step whose entry
    returns only the scan carry) must not lose its trip count."""
    from gke_ray_train_tpu.perf.costs import (
        _while_trip_counts, collective_stats)

    hlo = """
%body (p: (s32[], f32[64])) -> (s32[], f32[64]) {
  %ar = f32[64]{0} all-reduce(f32[64]{0} %g), replica_groups={}
  ROOT %t = (s32[], f32[64]) tuple(%iv, %ar)
}
ENTRY %main (p0: f32[64]) -> (s32[], f32[64]) {
  ROOT %w = (s32[], f32[64]) while((s32[], f32[64]) %init), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"4"}}
}
"""
    assert _while_trip_counts(hlo) == {"body": 4}
    _, nbytes, _ = collective_stats(hlo)
    assert nbytes == 64 * 4 * 4


def test_unknown_trip_counts_once():
    from gke_ray_train_tpu.perf.costs import collective_stats

    hlo = """
%body (p: (s32[], f32[64])) -> (s32[], f32[64]) {
  %ar = f32[64]{0} all-reduce(f32[64]{0} %g), replica_groups={}
  ROOT %t = (s32[], f32[64]) tuple(%iv, %ar)
}
ENTRY %main (p0: f32[64]) -> f32[64] {
  %w = (s32[], f32[64]) while((s32[], f32[64]) %init), condition=%cond, body=%body
  ROOT %r = f32[64]{0} copy(%w)
}
"""
    _, nbytes, lines = collective_stats(hlo)
    assert nbytes == 64 * 4                 # conservative: counted once
    assert not any("while-trip" in ln for ln in lines)


# ---------------------------------------------------------------------------
# budgets: the DCN claim is a checked-in number
# ---------------------------------------------------------------------------

def test_hybrid_budget_pair_pins_dcn_shrink():
    """The acceptance criterion, asserted from the checked-in JSONs:
    dcn_bytes(hier) <= (1/ici_size + eps) x dcn_bytes(flat), on the
    emulated 2-slice mesh whose ici_size is 4."""
    flat = load_budget(budget_path("tiny_hybrid_2x4_flat"))
    hier = load_budget(budget_path("tiny_hybrid_2x4_hier"))
    assert flat["dcn_bytes"] > 0
    assert hier["dcn_bytes"] <= (1 / 4 + 0.01) * flat["dcn_bytes"]
    # flat's DCN load is the full gradient payload; hier's ICI load
    # grows a little (the scatter/gather staging) — that trade is the
    # whole point and both sides are pinned
    assert hier["collective_bytes"] < flat["collective_bytes"]
    assert any("crosses the slice boundary" in ln
               for ln in flat["dcn_lines"])


def test_single_slice_budgets_pin_zero_dcn():
    for name in ("tiny_fsdp8", "tiny_dp8", "serve_tiny8"):
        doc = load_budget(budget_path(name))
        assert doc["dcn_bytes"] == 0
        assert doc["ici_bytes"] == doc["collective_bytes"]


def test_budget_trips_on_dcn_fattening_with_named_delta():
    """A reshard that fattens the cross-slice hop is a budget event
    carrying the per-op slice-crossing delta."""
    from gke_ray_train_tpu.perf.budget import (
        BudgetViolation, assert_within_budget)

    budget = load_budget(budget_path("tiny_hybrid_2x4_hier"))
    doctored = dict(budget)
    doctored["dcn_bytes"] = int(budget["dcn_bytes"] * 1.5)
    doctored["dcn_lines"] = budget["dcn_lines"] + [
        "all-reduce 77777B crosses the slice boundary (replica groups "
        "span 2 slices): %all-reduce.999 = f32[19444]{0} all-reduce("]
    with pytest.raises(BudgetViolation) as ei:
        assert_within_budget(doctored,
                             budget_path("tiny_hybrid_2x4_hier"))
    msg = str(ei.value)
    assert "dcn_bytes" in msg
    assert "HLO + " in msg          # the fattened hop is NAMED
    assert "77777B" in msg


def test_analysis_dcn_rule_is_one_sided():
    from gke_ray_train_tpu.analysis.jaxprcheck import unbudgeted_dcn_bytes

    budget = {"dcn_bytes": 1000, "dcn_lines": []}
    fat = {"dcn_bytes": 1200, "dcn_lines": ["all-reduce 1200B crosses"]}
    thin = {"dcn_bytes": 200, "dcn_lines": []}
    findings = unbudgeted_dcn_bytes(fat, budget)
    assert len(findings) == 1 and "fattening" in findings[0]
    assert unbudgeted_dcn_bytes(thin, budget) == []
    # pre-DCN budgets (no dcn_bytes key) gate nothing
    assert unbudgeted_dcn_bytes(fat, {}) == []


# ---------------------------------------------------------------------------
# kernelcheck: the compressed arm's tolerance ledger
# ---------------------------------------------------------------------------

def test_hier_psum_registry_within_pinned_ledger():
    from gke_ray_train_tpu.analysis.kernelcheck import (
        ledger_findings, sweep)

    results = sweep(["hier_psum"])
    assert len(results) == 4
    findings = ledger_findings(results)
    assert findings == [], [str(f) for f in findings]
    by_case = {r.case: r for r in results}
    # f32 arms agree with the mesh-ignorant sum to reassociation
    # noise; the bf16 hop sits at cast scale — orders apart
    assert by_case["hier_f32"].value_err < 1e-5
    assert by_case["compressed_bf16_hop"].value_err > 1e-4


def test_seeded_dcn_compression_regression_caught(monkeypatch):
    """Corrupt the compressed hop (cast to fp8 instead of bf16) and
    the pinned ledger must catch it as KER101 through the REAL
    registry path."""
    import ml_dtypes

    from gke_ray_train_tpu.analysis.kernelcheck import (
        ledger_findings, sweep)
    from gke_ray_train_tpu.parallel import hierarchical as hier_mod

    real = hier_mod.compressed_cross_psum

    def corrupted(p, residual, topo, compress="bf16"):
        p8 = p.astype(jnp.dtype(ml_dtypes.float8_e4m3fn)).astype(
            jnp.float32)
        return real(p8, residual, topo, compress)

    monkeypatch.setattr(hier_mod, "compressed_cross_psum", corrupted)
    results = sweep(["hier_psum"])
    findings = ledger_findings(results)
    assert any(f.rule == "KER101" and "compressed_bf16_hop" in str(f)
               for f in findings), [str(f) for f in findings]


# ---------------------------------------------------------------------------
# plan validation + knob audit
# ---------------------------------------------------------------------------

def test_hier_on_single_slice_is_loud_noop_downgrade(caplog):
    with caplog.at_level(logging.WARNING):
        p = ExecutionPlan.from_kwargs(dcn_sync="hier",
                                      dcn_compress="bf16")
    assert p.dcn_sync == "flat" and p.dcn_compress == "none"
    assert any("no-op" in r.message for r in caplog.records)
    # the no-op must not churn ANY fingerprint vs plain flat
    q = ExecutionPlan.from_kwargs()
    assert p.fingerprint() == q.fingerprint()
    assert p.compile_fingerprint("train") == q.compile_fingerprint("train")


def test_plan_refusals():
    # hier needs the hand-placed pipeline
    with pytest.raises(PlanError, match="overlap='manual'"):
        ExecutionPlan.from_kwargs(num_slices=2, data=2, fsdp=4,
                                  dcn_sync="hier")
    # compression compresses the hier hop only
    with pytest.raises(PlanError, match="DCN_SYNC=hier"):
        ExecutionPlan.from_kwargs(num_slices=2, data=2, fsdp=4,
                                  overlap="manual", dcn_compress="bf16")
    # structural axes stay untouched (the manual refusal fires first)
    with pytest.raises(PlanError, match="data/fsdp"):
        ExecutionPlan.from_kwargs(num_slices=2, data=2, fsdp=2, model=2,
                                  overlap="manual", dcn_sync="hier")
    with pytest.raises(PlanError, match="dcn_sync"):
        ExecutionPlan.from_kwargs(dcn_sync="bogus")
    with pytest.raises(PlanError, match="dcn_compress"):
        ExecutionPlan.from_kwargs(dcn_compress="fp4")


def test_knob_audit_three_dialects_and_surfaces():
    from gke_ray_train_tpu.config import KNOWN_KEYS, PLAN_SCOPED_KEYS
    from gke_ray_train_tpu.plan import (
        CONFIG_KEYS, COMPILE_SURFACES, ENV_FORWARD_KEYS)

    assert CONFIG_KEYS["dcn_sync"] == "DCN_SYNC"
    assert CONFIG_KEYS["dcn_compress"] == "DCN_COMPRESS"
    assert {"DCN_SYNC", "DCN_COMPRESS"} <= PLAN_SCOPED_KEYS <= KNOWN_KEYS
    assert {"DCN_SYNC", "DCN_COMPRESS"} <= set(ENV_FORWARD_KEYS)
    # train-surface compile-relevant; the serve surface never sees them
    assert {"dcn_sync", "dcn_compress"} <= set(COMPILE_SURFACES["train"])
    assert not {"dcn_sync", "dcn_compress"} & set(COMPILE_SURFACES["serve"])

    kw = dict(num_slices=2, data=2, fsdp=4, overlap="manual",
              dcn_sync="hier", dcn_compress="bf16")
    a = ExecutionPlan.from_kwargs(**kw)
    b = ExecutionPlan.from_config({
        "NUM_SLICES": "2", "MESH_DATA": "2", "MESH_FSDP": "4",
        "OVERLAP": "manual", "DCN_SYNC": "HIER",
        "DCN_COMPRESS": "BF16"})
    c = ExecutionPlan.from_env({
        "NUM_SLICES": "2", "MESH_DATA": "2", "MESH_FSDP": "4",
        "OVERLAP": "manual", "DCN_SYNC": "hier",
        "DCN_COMPRESS": "bf16"})
    assert a.fingerprint() == b.fingerprint() == c.fingerprint()
    # retuning the gradient sync must not stale SERVE sidecars (the
    # OBS-exclusion twin): the serve fingerprint is untouched
    base = ExecutionPlan.from_kwargs(num_slices=2, data=2, fsdp=4)
    assert a.compile_fingerprint("serve") == \
        base.compile_fingerprint("serve")
    assert a.compile_fingerprint("train") != \
        base.compile_fingerprint("train")
    # disabling spellings coerce to the defaults in every dialect
    assert ExecutionPlan.from_config({"DCN_SYNC": ""}).dcn_sync == "flat"
    assert ExecutionPlan.from_config({"DCN_SYNC": "0"}).dcn_sync == "flat"
    assert ExecutionPlan.from_config(
        {"DCN_COMPRESS": "off"}).dcn_compress == "none"


def test_plan005_clean():
    """plan.CONFIG_KEYS <-> config.PLAN_SCOPED_KEYS drift check still
    passes with the new keys (the real PLAN005 rule, not a re-pin)."""
    from gke_ray_train_tpu.analysis.plancheck import drift_findings
    assert drift_findings() == []


# ---------------------------------------------------------------------------
# obs: the network gauges
# ---------------------------------------------------------------------------

def test_obs_network_gauges_and_report_surface(tmp_path):
    from gke_ray_train_tpu.obs import metrics as obs_metrics
    from gke_ray_train_tpu.obs import runtime as obs_runtime
    from gke_ray_train_tpu.obs.report import build_report, render_text

    assert obs_metrics.METRIC_NAMES["dcn_bytes"] == "gauge"
    assert obs_metrics.METRIC_NAMES["ici_bytes"] == "gauge"
    assert obs_metrics.check_schema() == []

    run = obs_runtime.start_attempt(obs_dir=str(tmp_path))
    try:
        class FakeReport:
            ici_bytes = 1312080
            dcn_bytes = 155976

        obs_runtime.note_cost_report(FakeReport())
        run.emit("attempt_start", n_devices=8)
        run.export()
    finally:
        obs_runtime.end_attempt("ok")
    doc = json.load(open(tmp_path / "metrics-r0.json"))
    assert doc["dcn_bytes"] == 155976 and doc["ici_bytes"] == 1312080
    prom = open(tmp_path / "metrics-r0.prom").read()
    assert "grt_dcn_bytes" in prom and "grt_ici_bytes" in prom
    report = build_report(str(tmp_path))
    assert report["network"] == {"ici_bytes": 1312080,
                                 "dcn_bytes": 155976}
    assert "dcn" in render_text(report)


def test_obs_note_cost_report_noop_unconfigured():
    from gke_ray_train_tpu.obs import runtime as obs_runtime
    assert obs_runtime.active() is None
    obs_runtime.note_cost_report(object())    # must not raise

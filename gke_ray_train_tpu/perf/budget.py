"""Per-preset compile-cost budgets — regression gates, not benchmarks.

A budget JSON (checked in under ``tests/budgets/``) pins the
:class:`~gke_ray_train_tpu.perf.costs.StepCostReport` of a named preset
(model + mesh + batch shape) as recorded on the 8-fake-device CPU mesh —
the same mesh tier-1 CI runs on, so the comparator needs no hardware.
The comparator flags, with tolerances:

- **flops / bytes drift** (two-sided: a remat policy silently turning
  OFF *drops* flops while blowing up peak memory);
- **peak temp-memory growth** (the remat / activation-liveness signal);
- **any change in collective count by kind** — an extra all-reduce in
  the grad path is exactly the class of silent perf bug GSPMD can
  introduce; the violation message prints the offending HLO lines
  (the delta against the lines recorded in the budget).

Re-baselining after an INTENTIONAL change:
``python -m gke_ray_train_tpu.perf.budget record`` rewrites the files
(it re-execs itself onto the canonical CPU mesh), or run the tier-1
budget test with ``BUDGET_UPDATE=1``. Review the JSON diff like code —
that diff *is* the perf review.
"""

from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
from typing import Any, Dict, List, Optional, Union

from gke_ray_train_tpu.perf.compare import compare_dicts, hlo_delta
from gke_ray_train_tpu.perf.costs import (
    COLLECTIVE_KINDS, StepCostReport, step_cost_report)

# two-sided relative tolerances; collective COUNTS are exact by design.
# exposed_collective_bytes / overlap_frac are the overlap-analysis
# fields (perf.costs.overlap_stats): a pinned 0 stays exactly 0 under
# relative tolerances, so the first collective a schedule EXPOSES (or
# the first one it newly hides) is a budget event, not drift noise —
# the asserted metric the ROADMAP #3 overlap work moves.
DEFAULT_TOLERANCES = {
    "flops": 0.05,
    "bytes_accessed": 0.25,
    "temp_bytes": 0.25,
    "argument_bytes": 0.05,
    "output_bytes": 0.05,
    "collective_bytes": 0.25,
    "exposed_collective_bytes": 0.25,
    "overlap_frac": 0.05,
    # network attribution (perf.costs.collective_axis_stats): dcn_bytes
    # is deliberately TIGHT — the cross-slice hop is the number the
    # hierarchical sync exists to shrink, and a reshard that silently
    # fattens it by 10% is exactly the regression the hybrid budgets
    # gate (a pinned 0 stays exactly 0 on single-slice presets)
    "ici_bytes": 0.25,
    "dcn_bytes": 0.10,
    # peer hot-state replication (ckpt/peer.py): bytes ONE snapshot's
    # replication round streams across DCN on a hybrid preset. EXACT —
    # the number is a pure function of the train-state tree (shapes x
    # dtypes x num_slices, via jax.eval_shape), so any drift means the
    # replicated tree itself changed and the pin must be re-reviewed
    "peer_dcn_bytes": 0.0,
}

BUDGET_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "tests", "budgets")


class BudgetViolation(AssertionError):
    """A compiled step broke its checked-in cost/memory budget."""


def load_budget(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def write_budget(report: Union[StepCostReport, Dict[str, Any]], path: str,
                 *, preset: str = "", note: str = "",
                 plan=None) -> Dict[str, Any]:
    if isinstance(report, StepCostReport):
        report = report.to_dict()
    import jax
    if plan is None and (preset in PRESETS or preset in SERVE_PRESETS):
        plan = plan_for_preset(preset)
    doc = {
        "_preset": preset,
        "_note": note or ("re-baseline with: python -m "
                          "gke_ray_train_tpu.perf.budget record"),
        # the ExecutionPlan identity this budget was recorded under
        # (plan.py): plancheck PLAN004 fails the build when the preset
        # plan no longer resolves to this fingerprint (stale budget)
        "_plan_fingerprint": plan.fingerprint() if plan is not None
        else None,
        "_recorded_with": {"jax": jax.__version__,
                           "platform": jax.devices()[0].platform,
                           "n_devices": len(jax.devices())},
        **{k: v for k, v in report.items() if not k.startswith("_")},
    }
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    return doc


def compare_to_budget(report: Union[StepCostReport, Dict[str, Any]],
                      budget: Dict[str, Any],
                      tolerances: Optional[Dict[str, float]] = None
                      ) -> List[str]:
    """Violation strings (empty = within budget) — the stdlib-only
    comparator core (``perf/compare.py``; ``obs diff`` reuses it over
    telemetry reports) bound to this module's cost-report defaults:
    :data:`DEFAULT_TOLERANCES` and exact per-kind collective counts."""
    if isinstance(report, StepCostReport):
        report = report.to_dict()
    return compare_dicts(report, budget, tolerances,
                         default_tolerances=DEFAULT_TOLERANCES,
                         collective_kinds=COLLECTIVE_KINDS)


# jaxprcheck (and older call sites) import the delta printer from here
_hlo_delta = hlo_delta


def assert_within_budget(report: Union[StepCostReport, Dict[str, Any]],
                         budget_path: str, *, plan=None, **kw) -> None:
    """Raise :class:`BudgetViolation` on any comparator finding. The
    failure names the preset AND the plan fingerprint the budget was
    recorded under (plus the current plan's, when given) — a mismatched
    budget used to print only HLO deltas, leaving WHICH declared plan
    drifted to archaeology."""
    budget = load_budget(budget_path)
    viols = compare_to_budget(report, budget, **kw)
    if viols:
        preset = budget.get("_preset") or os.path.splitext(
            os.path.basename(budget_path))[0]
        recorded_fp = budget.get("_plan_fingerprint") or "<unrecorded>"
        ident = f"preset {preset!r} (recorded under plan {recorded_fp}"
        if plan is not None:
            ident += f"; current plan {plan.fingerprint()}"
        ident += ")"
        raise BudgetViolation(
            f"compiled step broke the budget {budget_path} — {ident}:"
            "\n  " + "\n  ".join(viols)
            + "\nIf the change is INTENTIONAL, re-baseline: python -m "
              "gke_ray_train_tpu.perf.budget record")


# ---------------------------------------------------------------------------
# Presets — the shapes whose budgets are checked in under tests/budgets/
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Preset:
    name: str
    mesh: Dict[str, int]
    batch: int = 8
    seq: int = 64
    remat: bool = True
    # the overlap mode the budget measures (plan.OVERLAP_MODES): train
    # presets pin the manual shard_map pipeline — the overlap_frac /
    # exposed_collective_bytes numbers ROADMAP #3 moves live here
    overlap: str = "manual"
    # DCN topology + cross-slice sync arm (parallel/hierarchical.py):
    # hybrid presets emulate num_slices>1 on the fake-8 mesh and pin
    # ici_bytes/dcn_bytes per DCN_SYNC arm — the budgeted claim that
    # hier sends 1/ici_size of flat's bytes over the slow link
    num_slices: int = 1
    dcn_sync: str = "flat"


PRESETS = {
    # fsdp grad path: the per-layer weight all-gathers are the overlap
    # target — double-buffered behind compute by the manual pipeline
    "tiny_fsdp8": Preset("tiny_fsdp8", {"data": 2, "fsdp": 4}),
    # pure data-parallel grad path: the classic gradient all-reduce
    # (no param gathers to hide — the manual path pins the same
    # program shape so the two presets stay comparable)
    "tiny_dp8": Preset("tiny_dp8", {"data": 8, "fsdp": 1}),
    # emulated 2-slice hybrid mesh (2 data x 4 fsdp, data spans the
    # slices — the PR-5 contract, fake-8 emulation pinned in
    # test_mesh.py): the flat arm's budget pins the full gradient
    # payload crossing DCN, the hier arm pins the 1/ici_size scattered
    # hop — the pair IS the recorded evidence for the DCN_SYNC claim,
    # and test_dcn.py asserts the ratio between the two JSONs
    "tiny_hybrid_2x4_flat": Preset(
        "tiny_hybrid_2x4_flat", {"data": 2, "fsdp": 4},
        num_slices=2, dcn_sync="flat"),
    "tiny_hybrid_2x4_hier": Preset(
        "tiny_hybrid_2x4_hier", {"data": 2, "fsdp": 4},
        num_slices=2, dcn_sync="hier"),
}


@dataclasses.dataclass(frozen=True)
class ServePreset:
    """A serving-decode budget shape: the ``[max_batch, 1]`` continuous-
    batching decode step of ``serve/engine.py`` at one bucket width.
    Mesh-local by design (a serving replica's decode carries no
    collectives — an all-gather showing up here IS the regression the
    exact-count check exists to catch)."""
    name: str
    max_batch: int = 8
    bucket: int = 128
    quant: str = "none"
    # multi-tenant shape (ISSUE 17): n_adapters > 0 budgets the POOLED
    # decode — the batched-LoRA gather+BGMV path over an AdapterPool of
    # n_adapters tenant slots (+ the reserved zero slot), the one
    # executable every mixed-tenant batch shares
    n_adapters: int = 0
    lora_r: int = 4


SERVE_PRESETS = {
    "serve_tiny8": ServePreset("serve_tiny8"),
    # the multi-tenant arm: same model/bucket as serve_tiny8, decode
    # compiled WITH the stacked adapter pool — the flops/bytes delta
    # between the two JSONs is the recorded cost of multi-LoRA, and the
    # zero-collective pin still holds (the gather is mesh-local)
    "serve_multilora8": ServePreset("serve_multilora8", n_adapters=8),
}


def all_preset_names() -> List[str]:
    """Every budget-bearing preset (train + serve) — the CLI default
    and the repo-level PLAN004 sweep iterate exactly this list."""
    return sorted(PRESETS) + sorted(SERVE_PRESETS)


def _serve_model_cfg(p: ServePreset):
    """The deterministic tiny model a serve preset decodes (same dims
    the train presets use, max_seq_len = the bucket width)."""
    from gke_ray_train_tpu.models import tiny
    return tiny(d_model=64, n_layers=2, n_heads=2, n_kv_heads=2,
                d_ff=128, vocab_size=256, max_seq_len=p.bucket,
                remat=False)


def plan_for_serve_preset(preset: Union[str, ServePreset]):
    """The serving ExecutionPlan a serve budget measures under — one
    plan fingerprint shared by the budget JSON, plancheck PLAN004 and
    ``analysis check`` (mirror of :func:`plan_for_preset`)."""
    from gke_ray_train_tpu.plan import ExecutionPlan
    p = SERVE_PRESETS[preset] if isinstance(preset, str) else preset
    return ExecutionPlan.from_kwargs(
        data=1, fsdp=1, max_seq_len=p.bucket,
        max_batch=p.max_batch, decode_buckets=str(p.bucket),
        serve_quant=p.quant, max_adapters=(p.n_adapters or 8),
        donate_state=False, donate_batch=False, prefetch=0,
        compile_cache=False, aot_train_step=False,
        topology="cpu-8", budget_preset=p.name)


def build_serve_preset_step(preset: Union[str, ServePreset], *,
                            with_jitted: bool = False):
    """(compiled_decode, params, serve_state) for a serve preset — the
    deterministic decode compile whose StepCostReport the budget pins.
    ``with_jitted`` additionally returns the jitted (un-AOT) decode fn
    and the lora argument it was lowered with (the stacked pool blocks
    on a multi-adapter preset, else None) for the analysis
    compile-once probe."""
    import jax

    from gke_ray_train_tpu.models import init_params
    from gke_ray_train_tpu.ops.quant import quantize_for_serving
    from gke_ray_train_tpu.serve.engine import (
        init_serve_state, make_decode_fn)

    p = SERVE_PRESETS[preset] if isinstance(preset, str) else preset
    cfg = _serve_model_cfg(p)
    params = quantize_for_serving(init_params(cfg, jax.random.key(0)),
                                  p.quant)
    if p.n_adapters:
        # pooled decode: the state carries per-slot adapter indices and
        # the lora argument is the stacked pool — the ONE executable a
        # mixed-tenant batch runs regardless of which tenants are in it
        from gke_ray_train_tpu.serve.adapters import AdapterPool
        from gke_ray_train_tpu.train.lora import LoraConfig, init_lora
        template = init_lora(cfg, LoraConfig(r=p.lora_r),
                             jax.random.key(1))
        pool = AdapterPool(template, max_adapters=p.n_adapters)
        state = init_serve_state(cfg, p.max_batch, p.bucket,
                                 multi_lora=True)
        jitted = jax.jit(make_decode_fn(cfg, eos_ids=(), pool=True),
                         donate_argnums=(1,))
        compiled = jitted.lower(params, state, pool.blocks).compile()
        if with_jitted:
            return compiled, params, state, jitted, pool.blocks
        return compiled, params, state
    state = init_serve_state(cfg, p.max_batch, p.bucket)
    jitted = jax.jit(make_decode_fn(cfg, eos_ids=()), donate_argnums=(1,))
    compiled = jitted.lower(params, state, None).compile()
    if with_jitted:
        return compiled, params, state, jitted, None
    return compiled, params, state


def build_budget_doc(preset: Union[str, Preset, ServePreset],
                     *, remat=None) -> Dict[str, Any]:
    """The full dict a budget records/checks: the StepCostReport plus,
    on hybrid presets, ``peer_dcn_bytes`` — the one builder the CLI and
    the tier-1 budget tests share, so the recorded and the checked
    documents can never diverge in shape. Counts only (flops, bytes,
    collectives): a compile on XLA:CPU gives no time and no rate."""
    doc = build_preset_report(preset, remat=remat).to_dict()
    name = preset if isinstance(preset, str) else preset.name
    if not isinstance(preset, ServePreset) and name not in SERVE_PRESETS:
        p = PRESETS[name] if isinstance(preset, str) else preset
        if p.num_slices > 1:
            doc["peer_dcn_bytes"] = peer_replication_bytes(p)
    return doc


def peer_replication_bytes(preset: Union[str, Preset]) -> int:
    """DCN bytes ONE peer hot-state replication round moves on a hybrid
    preset (``ckpt/peer.py``: every slice streams its full state replica
    to its ring neighbor). Computed from the ABSTRACT train-state tree —
    ``jax.eval_shape`` over the same model/optimizer the preset budgets,
    no arrays materialized — so recording it costs no device memory and
    the live replicator counter can be pinned against it exactly."""
    import jax

    from gke_ray_train_tpu.ckpt.peer import round_dcn_bytes
    from gke_ray_train_tpu.train import make_optimizer, make_train_state

    p = PRESETS[preset] if isinstance(preset, str) else preset
    cfg = preset_model_cfg(p)
    opt = make_optimizer(1e-3)
    abstract = jax.eval_shape(
        lambda key: make_train_state(cfg, opt, key), jax.random.key(0))
    return round_dcn_bytes(abstract, p.num_slices)


def preset_model_cfg(preset: Union[str, Preset, ServePreset]):
    """The deterministic tiny ModelConfig a preset measures — the ONE
    model shared by the budget compile, ``analysis check`` and the
    autotune search (whose registry entries are keyed by this model's
    digest, so a tuned plan provably describes the budget model)."""
    from gke_ray_train_tpu.models import tiny
    if isinstance(preset, ServePreset) or (
            isinstance(preset, str) and preset in SERVE_PRESETS):
        p = SERVE_PRESETS[preset] if isinstance(preset, str) else preset
        return _serve_model_cfg(p)
    p = PRESETS[preset] if isinstance(preset, str) else preset
    return tiny(d_model=64, n_layers=2, n_heads=2, n_kv_heads=2,
                d_ff=128, vocab_size=256, max_seq_len=p.seq,
                remat=p.remat)


def plan_for_preset(preset: Union[str, "Preset"]):
    """The ExecutionPlan a budget preset measures under — the SAME plan
    object ``analysis check`` and the budget CLI consume, so one
    fingerprint identifies the preset across budget JSONs, plancheck,
    and the comparator's failure output.

    Measurement policy is part of the identity: budgets are recorded
    donate=False (backend-independent numbers) with no input pipeline
    or guards, on the canonical 8-fake-device CPU mesh. Serve presets
    (``SERVE_PRESETS``) route to :func:`plan_for_serve_preset`."""
    from gke_ray_train_tpu.plan import ExecutionPlan
    if isinstance(preset, ServePreset) or (
            isinstance(preset, str) and preset in SERVE_PRESETS):
        return plan_for_serve_preset(preset)
    p = PRESETS[preset] if isinstance(preset, str) else preset
    mesh = {axis: p.mesh.get(axis, 1)
            for axis in ("data", "fsdp", "model", "context", "pipe")}
    dp = mesh["data"] * mesh["fsdp"]
    return ExecutionPlan.from_kwargs(
        **mesh,
        num_slices=p.num_slices, dcn_sync=p.dcn_sync,
        per_device_batch=max(p.batch // max(dp, 1), 1),
        grad_accum=1, max_seq_len=p.seq, packing=False,
        donate_state=False, donate_batch=False,
        prefetch=0, compile_cache=False, aot_train_step=False,
        # the overlap path IS the measured program (ROADMAP #3): the
        # manual shard_map pipeline's double-buffered fsdp gathers are
        # what moves overlap_frac/exposed_collective_bytes off the
        # PR-9 zero baseline — and the budget comparator is what keeps
        # a de-overlap regression (a gather resharded back next to its
        # consumer) from landing silently. Losses are bitwise-equal to
        # overlap="off" by construction (tests/test_overlap.py).
        overlap=p.overlap,
        topology="cpu-8", budget_preset=p.name)


def build_preset_step(preset: Union[str, Preset], *, remat=None,
                      wrap=None, donate: bool = False,
                      with_jitted: bool = False):
    """(compiled, state, batch) for a preset on the current devices —
    the deterministic compile whose report the budget pins.

    ``wrap(unjitted_step) -> fn``: transform the step before jit — the
    regression tests use it to deliberately smuggle an extra collective
    into the grad path and prove the comparator catches it.
    ``donate``: budgets stay donate=False (backend-independent); the
    analysis CLI's donation check builds the donated twin.
    ``with_jitted``: return (compiled, state, batch, jitted_step) — the
    analysis compile-once check dispatches the JITTED fn (the compiled
    executable can trivially never recompile)."""
    import dataclasses as _dc

    import jax
    import jax.numpy as jnp

    from gke_ray_train_tpu.train import (
        make_optimizer, make_train_state, make_train_step)

    p = PRESETS[preset] if isinstance(preset, str) else preset
    # ONE ExecutionPlan drives mesh, batch shardings and donation — the
    # same plan object whose fingerprint the budget JSON records
    plan = _dc.replace(plan_for_preset(p), donate_state=donate)
    mesh = plan.build_mesh(jax.devices())
    cfg = preset_model_cfg(p)
    if remat is not None:
        cfg = _dc.replace(cfg, remat=remat)
    opt = make_optimizer(1e-3)
    state = make_train_state(cfg, opt, jax.random.key(0), mesh=mesh)
    # donate_state=False default: budgets must not vary with backend
    # donation support (the analysis donation check opts in explicitly)
    step = make_train_step(cfg, opt, mesh=mesh, plan=plan)
    if wrap is not None:
        step = jax.jit(wrap(step.__wrapped__))
    batch = jax.device_put(
        {"inputs": jnp.zeros((p.batch, p.seq), jnp.int32),
         "targets": jnp.zeros((p.batch, p.seq), jnp.int32),
         "weights": jnp.ones((p.batch, p.seq), jnp.float32)},
        plan.batch_shardings(mesh))
    compiled = step.lower(state, batch).compile()
    if with_jitted:
        return compiled, state, batch, step
    return compiled, state, batch


def build_preset_report(preset: Union[str, Preset, ServePreset],
                        *, remat=None) -> StepCostReport:
    if isinstance(preset, ServePreset) or (
            isinstance(preset, str) and preset in SERVE_PRESETS):
        p = SERVE_PRESETS[preset] if isinstance(preset, str) else preset
        compiled, _, _ = build_serve_preset_step(p)
        # one decode iteration emits one token per slot
        return step_cost_report(compiled, tokens_per_step=p.max_batch)
    p = PRESETS[preset] if isinstance(preset, str) else preset
    compiled, _, _ = build_preset_step(p, remat=remat)
    # the DCN byte attribution runs against the preset's DECLARED slice
    # layout (the fake-8 devices carry no slice_index; num_slices is
    # what maps replica-group positions onto slices)
    return step_cost_report(compiled, tokens_per_step=p.batch * p.seq,
                            num_slices=p.num_slices)


def budget_path(name: str, budget_dir: Optional[str] = None) -> str:
    return os.path.join(budget_dir or BUDGET_DIR, f"{name}.json")


# ---------------------------------------------------------------------------
# CLI: record / check on the canonical 8-fake-device CPU mesh
# ---------------------------------------------------------------------------

def _reexec_on_cpu_mesh(argv) -> int:
    """Budgets are only comparable on the canonical mesh; re-exec this
    CLI in a child whose backend is forced to 8 CPU devices."""
    from gke_ray_train_tpu.perf.cache import cpu_mesh_env
    return subprocess.run(
        [sys.executable, "-m", "gke_ray_train_tpu.perf.budget"] + argv,
        env=cpu_mesh_env(_BUDGET_CLI_NATIVE="1")).returncode


def main(argv: Optional[List[str]] = None) -> int:
    import argparse
    parser = argparse.ArgumentParser(
        prog="python -m gke_ray_train_tpu.perf.budget",
        description="record/check compile-cost budgets on the canonical "
                    "8-fake-device CPU mesh")
    parser.add_argument("command", choices=("record", "check"))
    parser.add_argument("names", nargs="*",
                        help=f"presets (default: all of "
                             f"{all_preset_names()})")
    parser.add_argument("--all", action="store_true", dest="sweep_all",
                        help="sweep EVERY checked-in preset (train + "
                             "hybrid + serve) in one invocation — the "
                             "explicit spelling the CI budget step "
                             "uses, so the gate can never silently "
                             "narrow to a hand-kept preset list")
    parser.add_argument("--dir", default=BUDGET_DIR,
                        help="budget directory (default tests/budgets)")
    args = parser.parse_args(argv)
    if args.sweep_all and args.names:
        parser.error("--all and explicit preset names are mutually "
                     "exclusive")
    if os.environ.get("_BUDGET_CLI_NATIVE") != "1":
        return _reexec_on_cpu_mesh(
            [args.command] + args.names
            + (["--all"] if args.sweep_all else [])
            + ["--dir", args.dir])

    import jax
    assert jax.devices()[0].platform == "cpu" and len(jax.devices()) == 8, \
        "budget CLI must run on the 8-fake-device CPU mesh"
    names = args.names or all_preset_names()
    rc = 0
    for name in names:
        plan = plan_for_preset(name)
        report = build_budget_doc(name)
        path = budget_path(name, args.dir)
        if args.command == "record":
            write_budget(report, path, preset=name, plan=plan)
            print(f"recorded {path} (plan {plan.fingerprint()})")
        else:
            try:
                assert_within_budget(report, path, plan=plan)
                print(f"{name}: within budget "
                      f"(plan {plan.fingerprint()})")
            except BudgetViolation as e:
                print(e)
                rc = 1
    return rc


if __name__ == "__main__":
    sys.exit(main())

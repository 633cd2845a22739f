"""The autotune CLI — ``python -m gke_ray_train_tpu.autotune``.

``search``   enumerate + statically prune + compile-score the space
             around a base plan on the canonical fake-device CPU mesh
             SIZED TO THE BASE PLAN'S CHIP COUNT (unconditional re-exec
             like ``perf.budget`` — the parent never initializes a
             backend, so a dead accelerator cannot hang the CLI), print
             the winner's per-ceiling breakdown, and persist the
             tuned-plan registry entry + candidate table. rc 0 on
             success. Refuses models past ~0.5B params (train-state
             materialization would exhaust a CPU host).
``score``    score the BASE plan only — one compile, full breakdown
             printed. rc 0.
``apply``    overlay the recorded entry onto the base plan, re-validate
             (plancheck feasibility + kernelcheck statics) and print
             the tuned plan's flat-config dialect + fingerprints.
             rc 0 applied · 3 no entry · 4 refused (stale/invalid).
``explain``  print a recorded entry's provenance: key, fingerprint
             inputs, score breakdown (raw AND calibration-corrected
             when a calibration exists), observed columns, the drift
             verdict, improvement, top of the candidate table.
             rc 0 found · 3 no entry.
``ingest``   match an obs run dir's observed rows (measured step time /
             serve per-token latency, backend-stamped) into the
             registry's observed columns and re-judge drift:
             ``ingest <obs_dir>``. rc 0 ingested · 3 nothing matched ·
             4 every match refused (backend/version/fingerprint gates)
             · 5 drift band tripped (entry marked stale, schema'd
             ``autotune_drift`` event fired).
``calibrate`` fit per-chip-spec, per-ceiling correction factors over
             every entry's observed columns and write
             ``calibration.json`` beside the entries (bitwise-
             deterministic re-fit). rc 0 fitted · 3 no observed
             samples.

Base-plan selection (all verbs): ``--preset <budget preset>`` (default
``tiny_fsdp8``; serve presets imply ``--surface serve``) or ``--config
<fine-tune JSON>`` (the plan + model resolve exactly as plancheck
resolves them). ``--dir`` overrides the registry directory
(``AUTOTUNE_DIR`` env otherwise), ``--dims`` restricts the searched
dimensions, ``--budget`` caps full compiles (``AUTOTUNE_BUDGET`` env
otherwise).

``apply``/``explain``/``ingest``/``calibrate`` are static (no compile)
and force ``JAX_PLATFORMS=cpu`` like plancheck instead of re-exec'ing.
"""

from __future__ import annotations

import json
import logging
import os
import subprocess
import sys
from typing import List, Optional

logging.basicConfig(level=logging.INFO,
                    format="%(levelname)s %(name)s: %(message)s")


def _base_from_args(args):
    """(base_plan, model_cfg, surface, label) for the chosen base."""
    from gke_ray_train_tpu.perf.budget import (
        SERVE_PRESETS, plan_for_preset, preset_model_cfg)
    if args.config:
        from gke_ray_train_tpu.analysis.plancheck import model_config_for
        from gke_ray_train_tpu.plan import ExecutionPlan
        with open(args.config) as f:
            config = json.load(f)
        plan = ExecutionPlan.from_config(config)
        model_cfg = model_config_for(config, plan)
        if model_cfg is None:
            raise SystemExit(
                f"{args.config} names no model (MODEL_ID/SMOKE_TEST) — "
                "the registry keys on the model digest")
        return plan, model_cfg, args.surface, args.config, config
    surface = "serve" if args.preset in SERVE_PRESETS else args.surface
    return (plan_for_preset(args.preset), preset_model_cfg(args.preset),
            surface, f"preset {args.preset}", {})


def _print_score(label: str, score: dict) -> None:
    cal = score.get("calibration")
    corrected = (" (calibration-corrected; raw "
                 f"{score.get('raw_modeled_step_s', float('nan')):.4e}s,"
                 f" raw binding {cal.get('raw_binding')})"
                 if cal else "")
    print(f"{label}: modeled {score['modeled_step_s']:.4e}s "
          f"({score['binding']}-bound on {score['chip']}){corrected}")
    print(f"  t_compute {score['t_compute_s']:.4e}s | "
          f"t_hbm {score['t_hbm_s']:.4e}s | "
          f"t_ici {score['t_ici_s']:.4e}s | "
          f"t_dcn {score['t_dcn_s']:.4e}s | "
          f"exposed penalty {score['exposed_penalty_s']:.4e}s | "
          f"mfu ceiling {score['mfu_ceiling']:.3f}")


def _cmd_search(args, base) -> int:
    from gke_ray_train_tpu.autotune.registry import save_entry
    from gke_ray_train_tpu.autotune.search import search
    plan, model_cfg, surface, label, config = base
    result = search(plan, model_cfg, surface=surface, dims=args.dims,
                    budget=args.budget, config=config,
                    directory=args.dir)
    print(f"searched {label} ({surface} surface): "
          f"{result['space']['scored']} scored / "
          f"{result['space']['compiled']} compiled / "
          f"{result['space']['statically_pruned']} statically pruned / "
          f"{result['space']['coarse_skipped']} coarse-skipped")
    _print_score("base   ", result["base"]["score"])
    _print_score("winner ", result["winner"]["score"])
    if result["winner"]["diff"]:
        print(f"winner diff vs base: {result['winner']['diff']}"
              + (f" env {result['winner']['env']}"
                 if result["winner"]["env"] else ""))
        print(f"improvement: {result['improvement']:.3f}x modeled "
              + ("per-token time" if surface == "serve"
                 else "step time"))
    else:
        print("the hand-written default stands (no candidate beat it)")
    if not args.no_save:
        path = save_entry(result, base_plan=plan, model_cfg=model_cfg,
                          directory=args.dir)
        print(f"recorded {path}")
    return 0


def _cmd_score(args, base) -> int:
    from gke_ray_train_tpu.autotune import calibrate
    from gke_ray_train_tpu.autotune.registry import (
        chip_digest, registry_dir)
    from gke_ray_train_tpu.autotune.score import (
        chip_for_plan, score_candidate)
    from gke_ray_train_tpu.autotune.space import Candidate
    plan, model_cfg, surface, label, _ = base
    score, report = score_candidate(Candidate(plan=plan), model_cfg,
                                    surface=surface)
    cal = calibrate.load_calibration(args.dir or registry_dir())
    score = calibrate.apply_to_score(
        score, cal, chip_digest=chip_digest(chip_for_plan(plan)))
    _print_score(label, score)
    print(json.dumps(report.summary(), indent=1, sort_keys=True))
    return 0


def _load_entry_for(args):
    from gke_ray_train_tpu.autotune.registry import (
        entry_key, entry_path, load_entry, model_digest)
    plan, model_cfg, surface, label, _ = _base_from_args(args)
    key = entry_key(model_digest(model_cfg), plan.topology, surface)
    return (plan, model_cfg, key, load_entry(key, args.dir),
            entry_path(key, args.dir))


def _cmd_apply(args) -> int:
    from gke_ray_train_tpu.autotune.registry import (
        apply_entry, validate_entry)
    plan, model_cfg, key, entry, path = _load_entry_for(args)
    if entry is None:
        print(f"no tuned plan recorded at {path}")
        return 3
    findings = validate_entry(entry, plan, model_cfg)
    if findings:
        print(f"REFUSED tuned plan {key}:")
        for m in findings:
            print(f"  {m}")
        return 4
    tuned = apply_entry(plan, entry)
    print(f"applied {key}: plan {plan.fingerprint()} -> "
          f"{tuned.fingerprint()}")
    print(json.dumps(tuned.to_config(), indent=1, sort_keys=True))
    if entry.get("env"):
        print(f"env overrides: {entry['env']}")
    return 0


def _cmd_explain(args) -> int:
    plan, model_cfg, key, entry, path = _load_entry_for(args)
    if entry is None:
        print(f"no tuned plan recorded at {path}")
        return 3
    print(f"tuned plan {key} ({path})")
    print(f"  recorded with: {entry.get('_recorded_with')}")
    print(f"  fingerprint inputs: {entry.get('fingerprint_inputs')}")
    print(f"  base plan {entry.get('base_fingerprint')} -> winner "
          f"{entry.get('winner_fingerprint')} "
          f"({entry.get('improvement', float('nan')):.3f}x modeled)")
    _print_score("  base  ", entry["base_score"])
    _print_score("  winner", entry["score"])
    observed = entry.get("observed") or []
    if observed:
        by_arm: dict = {}
        for r in observed:
            by_arm.setdefault(r.get("arm"), []).append(r)
        print(f"  observed columns: {len(observed)} row(s) — "
              + ", ".join(f"{arm}: {len(rs)} (backends "
                          f"{sorted({r.get('backend') for r in rs})})"
                          for arm, rs in sorted(by_arm.items())))
    drift = entry.get("drift")
    if drift:
        verdict = "STALE (overlay will refuse)" if entry.get("stale") \
            else "within band"
        print(f"  drift verdict: {verdict} — {drift.get('arm')} arm "
              f"corrected {drift.get('corrected_modeled_step_s')}s vs "
              f"measured {drift.get('measured_step_s')}s "
              f"(rel_err {drift.get('rel_err')}, band "
              f"{drift.get('band')})")
    elif observed:
        print("  drift verdict: not judged (no calibration for this "
              "chip yet — run `autotune calibrate`)")
    print(f"  tuned fields: {entry.get('tuned')}")
    if entry.get("env"):
        print(f"  env: {entry['env']}")
    print(f"  space: {entry.get('space')}")
    cand_path = os.path.join(os.path.dirname(path),
                             entry.get("candidates_file", ""))
    if os.path.exists(cand_path):
        with open(cand_path) as f:
            table = json.load(f).get("candidates", [])
        print(f"  candidate table ({len(table)} scored, best first):")
        for row in table[:8]:
            print(f"    {row.get('fingerprint', row.get('plan_fingerprint'))} "
                  f"{row['score']['modeled_step_s']:.4e}s "
                  f"{row.get('diff') or '[base]'}"
                  + (f" env {row['env']}" if row.get("env") else ""))
    return 0


def _cmd_ingest(args) -> int:
    from gke_ray_train_tpu.autotune.registry import (
        ingest_observed, registry_dir)
    if not args.obs_dir:
        raise SystemExit("ingest needs an obs dir: "
                         "python -m gke_ray_train_tpu.autotune ingest "
                         "<obs_dir>")
    summary = ingest_observed(args.obs_dir,
                              directory=args.dir or registry_dir())
    print(f"ingested {args.obs_dir} -> {summary['directory']}: "
          f"{summary['rows']} observed row(s), {summary['matched']} "
          f"matched, {len(summary['refusals'])} refused, entries "
          f"updated: {summary['updated'] or 'none'}")
    for r in summary["refusals"]:
        print(f"  REFUSED {r}")
    for d in summary["drift"]:
        print(f"  DRIFT {d['key']} ({d['arm']} arm): corrected "
              f"{d['corrected_modeled_step_s']}s vs measured "
              f"{d['measured_step_s']}s — rel_err {d['rel_err']} > "
              f"band {d['band']}; entry marked STALE")
    if summary["drift"]:
        return 5
    if summary["matched"] == 0:
        return 4 if summary["refusals"] else 3
    return 0


def _cmd_calibrate(args) -> int:
    from gke_ray_train_tpu.autotune.registry import (
        fit_and_save_calibration, registry_dir)
    cal = fit_and_save_calibration(args.dir or registry_dir())
    if not cal.get("_samples"):
        print(f"no observed samples under "
              f"{args.dir or registry_dir()} — ingest a run first "
              "(wrote an empty calibration)")
        return 3
    print(f"calibration fitted over {cal['_samples']} sample(s) -> "
          f"{cal['_path']}")
    for digest, chip in sorted(cal.get("chips", {}).items()):
        for ceiling, f in sorted((chip.get("factors") or {}).items()):
            print(f"  {chip.get('chip')}/{digest} {ceiling}: "
                  f"x{f['factor']:.4g} (n={f['n']}"
                  + (", clamped" if f.get("clamped") else "") + ")")
    return 0


def _base_chips(args) -> int:
    """The base plan's chip count, derived WITHOUT touching a jax
    backend (plan arithmetic only) — the parent process must never
    probe a possibly-dead accelerator before the re-exec (the same
    discipline as perf.budget's unconditional re-exec)."""
    if args.config:
        from gke_ray_train_tpu.plan import ExecutionPlan
        with open(args.config) as f:
            return ExecutionPlan.from_config(json.load(f)).chips
    from gke_ray_train_tpu.perf.budget import plan_for_preset
    return plan_for_preset(args.preset).chips


# compile-scoring materializes the model's train state on the fake
# mesh; past this many parameters that is an OOM/hour-scale stall on a
# CPU host, not a search — refuse with guidance instead
_MAX_SCORING_PARAMS = 5e8


def _guard_model_size(plan, model_cfg) -> None:
    import jax

    from gke_ray_train_tpu.autotune.space import numel
    shapes = plan.abstract_params(model_cfg)
    elems = sum(numel(x) for x in jax.tree.leaves(shapes))
    if elems > _MAX_SCORING_PARAMS:
        raise SystemExit(
            f"refusing to compile-score a {elems / 1e9:.1f}B-parameter "
            "model on the fake-device CPU mesh (train-state "
            "materialization would exhaust host memory). Search with a "
            "SMOKE_TEST config or a budget preset here; re-tune the "
            "full model when accelerator hardware is attached.")


def main(argv: Optional[List[str]] = None) -> int:
    import argparse
    parser = argparse.ArgumentParser(
        prog="python -m gke_ray_train_tpu.autotune",
        description="cost-model-driven ExecutionPlan search + tuned-plan "
                    "registry (CPU-mesh compiles, no accelerator needed)")
    parser.add_argument("command",
                        choices=("search", "score", "apply", "explain",
                                 "ingest", "calibrate"))
    parser.add_argument("obs_dir", nargs="?", default=None,
                        help="obs run dir (ingest only): the dir whose "
                             "observed rows feed the registry")
    parser.add_argument("--preset", default="tiny_fsdp8",
                        help="budget preset naming the base plan + model "
                             "(default tiny_fsdp8; serve presets imply "
                             "--surface serve)")
    parser.add_argument("--config", default=None,
                        help="fine-tune config JSON as the base instead "
                             "of a preset")
    parser.add_argument("--surface", default="train",
                        choices=("train", "serve"))
    parser.add_argument("--dir", default=None,
                        help="registry directory (default AUTOTUNE_DIR "
                             "env or <repo>/tuned_plans)")
    parser.add_argument("--dims", nargs="*", default=None,
                        help="restrict searched dimensions (mesh batch "
                             "sync fused flash prefetch | max_batch "
                             "buckets)")
    parser.add_argument("--budget", type=int, default=None,
                        help="max full compiles (default AUTOTUNE_BUDGET "
                             "env or 64); larger spaces run successive "
                             "halving")
    parser.add_argument("--no-save", action="store_true",
                        help="search only — do not write the registry")
    args = parser.parse_args(argv)

    if args.command in ("apply", "explain", "ingest", "calibrate"):
        # static: plan arithmetic + JSON only — never probe a possibly
        # dead accelerator (same discipline as plancheck)
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        return {"apply": _cmd_apply, "explain": _cmd_explain,
                "ingest": _cmd_ingest,
                "calibrate": _cmd_calibrate}[args.command](args)

    if os.environ.get("_AUTOTUNE_CLI_NATIVE") != "1":
        # scoring compiles are only comparable on the canonical
        # fake-device mesh SIZED TO THE BASE PLAN (a v5e-16 config
        # compiles its real 16-chip mesh arithmetic on fake-16).
        # Unconditional re-exec, like perf.budget — the parent never
        # initializes a backend, so a dead accelerator cannot hang the
        # CLI before the child forces CPU.
        from gke_ray_train_tpu.perf.cache import cpu_mesh_env
        argv_out = [args.command, "--preset", args.preset,
                    "--surface", args.surface]
        if args.config:
            argv_out += ["--config", args.config]
        if args.dir:
            argv_out += ["--dir", args.dir]
        if args.dims is not None:
            argv_out += ["--dims"] + list(args.dims)
        if args.budget is not None:
            argv_out += ["--budget", str(args.budget)]
        if args.no_save:
            argv_out += ["--no-save"]
        return subprocess.run(
            [sys.executable, "-m", "gke_ray_train_tpu.autotune"]
            + argv_out,
            env=cpu_mesh_env(n_devices=_base_chips(args),
                             _AUTOTUNE_CLI_NATIVE="1")).returncode

    # scoring compiles hit the persistent compile cache so re-tunes over
    # a mostly-unchanged space are warm (COMPILE_CACHE=0 still disables)
    from gke_ray_train_tpu.perf.cache import enable_persistent_cache
    enable_persistent_cache()
    base = _base_from_args(args)
    _guard_model_size(base[0], base[1])
    return (_cmd_search if args.command == "search"
            else _cmd_score)(args, base)


if __name__ == "__main__":
    sys.exit(main())

"""The shardlint CLI — ``python -m gke_ray_train_tpu.analysis``.

``lint``      AST pass (level 1) over the repo source; exit 1 on findings.
``trace``     print the level-2 compile ledger per preset (informational).
``check``     level-2 assertions per preset (unbudgeted collectives,
              dropped donation, recompiles); exit 1 on findings.
``plancheck`` level-4 static ExecutionPlan verification (plancheck.py):
              topology feasibility, model-dim divisibility, the
              checkpoint-portability matrix, budget/fingerprint
              consistency, KNOWN_KEYS drift; exit 1 on findings.
``kernelcheck`` level-5 kernel verification (kernelcheck.py): static
              grid/VMEM/mesh-contract rules + the jaxpr numerics lint
              (KER001-006), then registry-driven differential sweeps
              of every accelerated op against its oracle vs the pinned
              tolerance ledger (KER100-102); exit 1 on findings.
              ``--record`` / ``TOLERANCE_UPDATE=1`` re-records the
              ledger, ``--static-only`` skips the sweeps.

``trace``/``check`` need the canonical 8-fake-device CPU mesh, so —
like ``perf.budget`` — they re-exec themselves into a child with the
forced-CPU env when not already on it; ``kernelcheck``'s differential
sweeps do the same (its static half runs anywhere). ``lint`` is pure
AST and runs anywhere; ``plancheck`` is pure shape arithmetic +
``jax.eval_shape`` (no backend, no devices — it never probes the
possibly-dead accelerator), so both run on the CI lint runner.
"""

from __future__ import annotations

import os
import subprocess
import sys
from typing import List, Optional

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# the repo's runtime surface; tests/ are deliberately excluded (their
# fixtures CONTAIN the bad snippets the rules must keep catching)
DEFAULT_LINT_PATHS = ("gke_ray_train_tpu", "ray-jobs",
                      "__graft_entry__.py")


def _lint(paths: List[str]) -> int:
    from gke_ray_train_tpu.analysis.astlint import lint_paths
    paths = paths or [os.path.join(REPO_ROOT, p)
                      for p in DEFAULT_LINT_PATHS
                      if os.path.exists(os.path.join(REPO_ROOT, p))]
    findings = lint_paths(paths)
    for f in findings:
        path = os.path.relpath(f.path, REPO_ROOT) \
            if os.path.isabs(f.path) else f.path
        print(f"{path}:{f.line}:{f.col}: {f.code} {f.message}")
    n = len(findings)
    print(f"shardlint: {n} finding(s)" if n else "shardlint: clean")
    # findings always fail the lint verb; the --fail-on-findings flag
    # exists so the CI step states its contract explicitly
    return 1 if findings else 0


def _preset_names(names: List[str]) -> List[str]:
    from gke_ray_train_tpu.perf.budget import all_preset_names
    return names or all_preset_names()


def _plancheck(paths: List[str], budget_dir: str = None) -> int:
    # plancheck is static: make sure abstract tracing can never probe a
    # (possibly dead) accelerator backend, exactly like the tier-1 env
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from gke_ray_train_tpu.analysis.plancheck import (
        check_paths, default_config_paths)
    paths = paths or default_config_paths(REPO_ROOT)
    findings = check_paths(paths, budget_dir=budget_dir)
    for f in findings:
        print(f"FINDING {f}")
    if findings:
        print(f"plancheck: {len(findings)} finding(s) over "
              f"{len(paths)} config(s)")
        return 1
    import json as _json

    from gke_ray_train_tpu.plan import ExecutionPlan
    for p in paths:
        with open(p) as fh:
            plan = ExecutionPlan.from_config(_json.load(fh))
        print(f"{os.path.relpath(p, REPO_ROOT)}: plan "
              f"{plan.fingerprint()} feasible on {plan.topology}; "
              "portability + budget + KNOWN_KEYS consistent")
    print(f"plancheck: clean ({len(paths)} config(s))")
    return 0


def _reexec_on_cpu_mesh(argv: List[str]) -> int:
    from gke_ray_train_tpu.perf.cache import cpu_mesh_env
    return subprocess.run(
        [sys.executable, "-m", "gke_ray_train_tpu.analysis"] + argv,
        env=cpu_mesh_env(_ANALYSIS_CLI_NATIVE="1"), cwd=REPO_ROOT
    ).returncode


def _kernelcheck(args) -> int:
    from gke_ray_train_tpu.analysis.kernelcheck import main_check
    return main_check(
        args.names or None, static_only=args.static_only,
        diff_only=args.diff_only, record=args.record,
        ledger_dir=args.ledger_dir,
        config_paths=args.configs or None)


def _on_canonical_mesh() -> bool:
    import jax
    return jax.devices()[0].platform == "cpu" and len(jax.devices()) == 8


def _trace(names: List[str]) -> int:
    from gke_ray_train_tpu.analysis.jaxprcheck import trace_preset
    for name in _preset_names(names):
        print(trace_preset(name))
    return 0


def _check(names: List[str]) -> int:
    from gke_ray_train_tpu.analysis.jaxprcheck import check_preset
    from gke_ray_train_tpu.perf.budget import plan_for_preset
    rc = 0
    for name in _preset_names(names):
        findings = check_preset(name)
        for f in findings:
            print(f"FINDING {f}")
        if findings:
            rc = 1
        else:
            # the fingerprint printed here is the SAME ExecutionPlan
            # identity the budget CLI and the budget JSON carry — one
            # plan across trainer, budget check and analysis check
            print(f"{name}: clean (collectives within budget, donation "
                  "held, one compile per fn; plan "
                  f"{plan_for_preset(name).fingerprint()})")
    return rc


def main(argv: Optional[List[str]] = None) -> int:
    import argparse
    parser = argparse.ArgumentParser(
        prog="python -m gke_ray_train_tpu.analysis",
        description="shardlint: sharding & host-sync static analysis "
                    "(AST lint / trace-level analyzers, CPU-only)")
    sub = parser.add_subparsers(dest="command", required=True)
    p_lint = sub.add_parser("lint", help="AST rules TPU001-TPU005")
    p_lint.add_argument("paths", nargs="*",
                        help="files/dirs (default: the repo's runtime "
                             "surface, tests excluded)")
    p_lint.add_argument("--fail-on-findings", action="store_true",
                        help="exit 1 on any finding (also the default)")
    p_trace = sub.add_parser(
        "trace", help="print the compile-level ledger per preset")
    p_trace.add_argument("names", nargs="*")
    p_check = sub.add_parser(
        "check", help="assert collectives/donation/compile-once per preset")
    p_check.add_argument("names", nargs="*")
    p_plan = sub.add_parser(
        "plancheck",
        help="statically verify ExecutionPlans: feasibility, "
             "portability matrix, budget/fingerprint + KNOWN_KEYS "
             "consistency (no backend needed)")
    p_plan.add_argument("configs", nargs="*",
                        help="config JSONs (default: the shipped "
                             "ray-jobs/fine_tune_config*.json presets)")
    p_plan.add_argument("--budget-dir", default=None,
                        help="budget directory (default tests/budgets)")
    p_ker = sub.add_parser(
        "kernelcheck",
        help="level 5: static kernel rules (KER001-006) + differential "
             "kernel-vs-oracle sweeps against the tolerance ledger "
             "(KER100-102)")
    p_ker.add_argument("names", nargs="*",
                       help="registered kernels (default: all)")
    p_ker.add_argument("--static-only", action="store_true",
                       help="KER001-006 only (no devices needed)")
    p_ker.add_argument("--diff-only", action="store_true",
                       help="differential sweeps only")
    p_ker.add_argument("--record", action="store_true",
                       help="re-record tests/tolerances/*.json "
                            "(same as TOLERANCE_UPDATE=1)")
    p_ker.add_argument("--ledger-dir", default=None,
                       help="tolerance directory (default "
                            "tests/tolerances)")
    p_ker.add_argument("--configs", nargs="*", default=None,
                       help="config JSONs for the static rules "
                            "(default: the shipped presets)")
    args = parser.parse_args(argv)

    if args.command == "lint":
        return _lint(args.paths)
    if args.command == "plancheck":
        return _plancheck(args.configs, args.budget_dir)
    if args.command == "kernelcheck" and args.static_only:
        return _kernelcheck(args)   # pure arithmetic + jaxpr tracing
    if os.environ.get("_ANALYSIS_CLI_NATIVE") != "1" \
            and not _on_canonical_mesh():
        argv_out = [args.command] + args.names
        if args.command == "kernelcheck":
            argv_out += (["--diff-only"] if args.diff_only else []) \
                + (["--record"] if args.record else []) \
                + (["--ledger-dir", args.ledger_dir]
                   if args.ledger_dir else []) \
                + (["--configs"] + args.configs if args.configs else [])
        return _reexec_on_cpu_mesh(argv_out)
    if args.command == "kernelcheck":
        return _kernelcheck(args)
    return _trace(args.names) if args.command == "trace" \
        else _check(args.names)


if __name__ == "__main__":
    sys.exit(main())

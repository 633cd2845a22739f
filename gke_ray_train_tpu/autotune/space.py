"""Candidate-plan enumeration around a base ExecutionPlan (autotune).

The search space is every performance lever the plan already exposes,
varied AROUND a declared base plan — never beyond what the repo's
static checkers can prove runnable:

- **mesh**: every (data, fsdp) factorization of the declared topology's
  chip count with the *structural* axes (model, context, pipe) kept
  exactly as declared — the same never-reflow rule ``plan.replan`` and
  plancheck's portability matrix enforce. On a multi-slice plan only
  factorizations whose data axis tiles the slice count survive (the
  hybrid-mesh contract: data — and only data — spans slices).
- **batch**: every (per_device_batch, grad_accum) factorization of
  their base product — the global batch is preserved by construction,
  so the optimization trajectory is comparable across candidates.
- **sync**: the overlap/DCN arms (``OVERLAP``, ``DCN_SYNC``,
  ``DCN_COMPRESS``) that are legal for the mesh: ``manual`` only on
  data/fsdp-only meshes, ``xla`` only on TPU families (the flags are
  inert on the CPU mesh — an arm that compiles the identical program
  is a wasted compile), ``hier``/``bf16`` only on multi-slice plans.
- **fused**: the FUSED_OPS epilogue-kernel toggle.
- **flash**: FLASH_BLOCK_Q/KV pairs (env-dialect knobs — they ride the
  candidate as env overrides, not plan fields), only when the plan's
  resolved attention impl actually runs a Pallas kernel.
- **prefetch**: input-pipeline depths. Operational — the cost model is
  indifferent, and the distance-from-base tie-break keeps the declared
  depth unless something else differentiates.
- serve surface: **max_batch** slot counts, **buckets** request
  length-bucket lists (declared arms plus widths fitted to the
  observed ``request_len`` histogram when the plan has an obs dir),
  **adapters** pool capacities and **spec_k** speculative draft
  lengths (only when the base plan speculates) instead of the train
  dims.

Every candidate is pruned STATICALLY before any compile, reusing the
checkers the budget suite already trusts: ``ExecutionPlan`` validation
(PLAN000), ``plan.feasibility`` (plancheck PLAN001/002 arithmetic) and
``kernelcheck.kernel_constraint_findings`` (KER001-003 grid/VMEM/mesh
rules); flash-block env arms go through the same ``pick_block`` /
``estimate_vmem_bytes`` arithmetic KER001/KER002 are built on.

Enumeration is DETERMINISTIC: candidates are deduplicated by
fingerprint and ordered by (distance from base, fingerprint) — two
enumerations of the same space are identical lists, which is the first
half of the search's bitwise-reproducibility contract.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
from typing import Any, Dict, List, Mapping, Optional, Tuple

from gke_ray_train_tpu.plan import ExecutionPlan, PlanError

# the plan fields a tuned overlay may change, by surface — the ONLY
# fields ``registry.apply_entry`` writes onto a runtime plan (an
# overlay must never touch operational identity: obs dirs, cache
# policy, guards, the AUTOTUNE flag itself)
TUNABLE_FIELDS: Dict[str, Tuple[str, ...]] = {
    "train": ("data", "fsdp", "per_device_batch", "grad_accum",
              "overlap", "dcn_sync", "dcn_compress", "fused_ops",
              "prefetch"),
    "serve": ("max_batch", "decode_buckets", "max_adapters", "spec_k"),
}

# dimension vocabulary per surface (the --dims CLI filter)
TRAIN_DIMS: Tuple[str, ...] = ("mesh", "batch", "sync", "fused",
                               "flash", "prefetch")
SERVE_DIMS: Tuple[str, ...] = ("max_batch", "buckets", "adapters",
                               "spec_k")

# the flash-block sweep grid (on the chip the kernels alone are swept by
# scripts/flash_block_sweep.py)
FLASH_BLOCK_GRID: Tuple[Tuple[int, int], ...] = tuple(
    (q, kv) for q in (128, 256, 512) for kv in (512, 1024, 2048))

PREFETCH_DEPTHS: Tuple[int, ...] = (0, 2, 4)
MAX_BATCH_ARMS: Tuple[int, ...] = (4, 8, 16)
# multi-tenant serving arms (ISSUE 17): adapter-pool capacities and
# speculative draft lengths. spec_k arms only enumerate when the base
# plan actually speculates (SPEC_DRAFT != none) — with speculation off
# spec_k never enters a compiled program and every arm is a duplicate
MAX_ADAPTERS_ARMS: Tuple[int, ...] = (4, 8, 16)
SPEC_K_ARMS: Tuple[int, ...] = (2, 4, 8)


def numel(shape_struct) -> int:
    """Element count of one ShapeDtypeStruct-like leaf (shared by the
    coarse scorer and the CLI's model-size guard)."""
    out = 1
    for d in getattr(shape_struct, "shape", ()):
        out *= int(d)
    return out


@dataclasses.dataclass(frozen=True)
class Candidate:
    """One point of the search space: a validated plan plus the
    env-dialect knobs (flash blocks) that ride along with it."""
    plan: ExecutionPlan
    env: Tuple[Tuple[str, str], ...] = ()

    def fingerprint(self) -> str:
        if not self.env:
            return self.plan.fingerprint()
        payload = json.dumps([self.plan.fingerprint(), list(self.env)],
                             sort_keys=True)
        return hashlib.sha256(payload.encode()).hexdigest()[:16]

    def env_dict(self) -> Dict[str, str]:
        return dict(self.env)


@dataclasses.dataclass
class Space:
    """The enumerated space plus its pruning ledger — no silent caps:
    everything skipped is named, so "searched the space" never silently
    means "searched the feasible corner of it"."""
    base: Candidate
    candidates: List[Candidate]
    pruned: List[str] = dataclasses.field(default_factory=list)
    dims: Dict[str, int] = dataclasses.field(default_factory=dict)

    def __len__(self) -> int:
        return len(self.candidates)


def distance(plan: ExecutionPlan, base: ExecutionPlan,
             surface: str = "train") -> int:
    """How many tunable fields a candidate changed — the deterministic
    tie-break (equal scores prefer the plan closest to what the
    operator declared)."""
    return sum(1 for f in TUNABLE_FIELDS[surface]
               if getattr(plan, f) != getattr(base, f))


def candidate_sort_key(cand: Candidate, base: ExecutionPlan,
                       surface: str):
    return (distance(cand.plan, base, surface) + (1 if cand.env else 0),
            cand.fingerprint())


# ---------------------------------------------------------------------------
# per-dimension option lists
# ---------------------------------------------------------------------------

def _mesh_options(base: ExecutionPlan) -> List[Tuple[int, int]]:
    sizes = base.resolved_sizes()
    structural = sizes["model"] * sizes["context"] * sizes["pipe"]
    n = base.chips // structural
    opts = []
    for data in range(1, n + 1):
        if n % data:
            continue
        if base.num_slices > 1 and data % base.num_slices:
            # hybrid contract: the data axis — and only data — spans
            # slices, so it must tile the slice count
            continue
        opts.append((data, n // data))
    return opts


def _batch_options(base: ExecutionPlan) -> List[Tuple[int, int]]:
    product = base.per_device_batch * base.grad_accum
    return [(product // a, a) for a in range(1, product + 1)
            if product % a == 0]


def _sync_options(base: ExecutionPlan) -> List[Tuple[str, str, str]]:
    """(overlap, dcn_sync, dcn_compress) arms legal for the base mesh.
    Structural axes never vary across the space, so manual-legality is
    a property of the base plan."""
    sizes = base.resolved_sizes()
    manual_ok = all(sizes[a] == 1 for a in ("model", "context", "pipe"))
    family = base.topology.split("-", 1)[0]
    arms = [(base.overlap, base.dcn_sync, base.dcn_compress),
            ("off", "flat", "none")]
    if manual_ok:
        arms.append(("manual", "flat", "none"))
        if base.num_slices > 1:
            arms.append(("manual", "hier", "none"))
            arms.append(("manual", "hier", "bf16"))
    if family != "cpu":
        # the latency-hiding-scheduler flags are TPU-only; on the CPU
        # mesh the xla arm compiles the bitwise-identical program to
        # "off" (plan.overlap_compiler_options gates on the backend) —
        # a duplicate compile, not a candidate
        arms.append(("xla", "flat", "none"))
    seen = set()
    return [a for a in arms if not (a in seen or seen.add(a))]


def _flash_envs(base: ExecutionPlan, model_cfg) -> List[Tuple]:
    """FLASH_BLOCK_Q/KV env arms, pruned by the KER001/KER002
    arithmetic (pick_block divisibility + VMEM estimate vs the declared
    chip's budget). Empty when the plan's resolved attention impl runs
    no Pallas attention kernel (the XLA oracle has no grid to tune)."""
    if model_cfg is None:
        return [()]
    from gke_ray_train_tpu.analysis.kernelcheck import resolve_attn_impl
    from gke_ray_train_tpu.ops.flash_attention import (
        estimate_vmem_bytes, pick_block)
    from gke_ray_train_tpu.perf.costs import CHIP_SPECS

    impl = resolve_attn_impl(model_cfg, base)
    if impl not in ("flash", "ring", "a2a"):
        return [()]
    sizes = base.resolved_sizes()
    ctx = sizes["context"]
    seq = base.max_seq_len
    s_local = seq // ctx if ctx > 1 and seq % ctx == 0 else seq
    dtype = str(model_cfg.dtype)
    dbytes = 2 if dtype in ("bfloat16", "float16") else 4
    head_dim = model_cfg.resolved_head_dim
    family = base.topology.split("-", 1)[0]
    chip = CHIP_SPECS.get(family, CHIP_SPECS["cpu"])
    out: List[Tuple] = [()]
    for q, kv in FLASH_BLOCK_GRID:
        try:
            bq = pick_block(q, s_local)
            bkv = pick_block(kv, s_local)
        except ValueError:
            continue            # KER001: the pair cannot tile s_local
        if estimate_vmem_bytes(bq, bkv, head_dim, dbytes) \
                > chip.vmem_bytes:
            continue            # KER002: blows the per-core VMEM budget
        out.append((("FLASH_BLOCK_Q", str(q)),
                    ("FLASH_BLOCK_KV", str(kv))))
    return out


def _observed_len_buckets(base: ExecutionPlan) -> List[int]:
    """Bucket widths fitted to OBSERVED traffic: the request_len
    histogram (prompt + budgeted new tokens, the number the engine's
    ``pick_bucket`` routes on) exported to ``metrics-r*.json`` under
    the plan's obs dir. Its p50/p99 rounded up to the 128-token grid
    are exactly the widths that make the median and the tail request
    pad least — the histogram closes the loop from a served run back
    into the search space. Silent when the plan has no obs dir or the
    dir has no serving telemetry."""
    import glob
    import os
    if not base.obs_dir or not os.path.isdir(base.obs_dir):
        return []
    quantiles: List[float] = []
    for path in sorted(glob.glob(
            os.path.join(base.obs_dir, "metrics-r*.json"))):
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        h = doc.get("request_len")
        if isinstance(h, dict) and h.get("count"):
            quantiles += [float(h.get("p50", 0)), float(h.get("p99", 0))]
    out = []
    for q in quantiles:
        if q <= 0:
            continue
        width = min(max(128, -(-int(q) // 128) * 128), base.max_seq_len)
        if width not in out:
            out.append(width)
    return sorted(out)


def _bucket_options(base: ExecutionPlan) -> List[str]:
    """Serve bucket-list arms: the declared list plus each single
    bucket (coarser lists = fewer executables, finer = tighter pads),
    plus the histogram-fit widths from the plan's obs dir — each as a
    single-bucket arm and, when more than one, the fitted list (p50
    bucket for the median, p99 bucket for the tail)."""
    buckets = base.bucket_list()
    opts = [",".join(str(b) for b in buckets)]
    opts.extend(str(b) for b in buckets)
    fitted = _observed_len_buckets(base)
    opts.extend(str(b) for b in fitted)
    if len(fitted) > 1:
        opts.append(",".join(str(b) for b in fitted))
    seen = set()
    return [o for o in opts if not (o in seen or seen.add(o))]


# ---------------------------------------------------------------------------
# enumeration + static pruning
# ---------------------------------------------------------------------------

# the ONLY env-dialect knobs a candidate (and therefore a registry
# entry) may carry — maybe_apply refuses anything else, so a corrupt
# or hand-doctored entry can never export arbitrary env into a worker
ENV_OVERRIDE_KEYS: Tuple[str, ...] = ("FLASH_BLOCK_Q", "FLASH_BLOCK_KV")


def static_findings(plan: ExecutionPlan, model_cfg,
                    config: Mapping[str, Any] = (),
                    surface: str = "train") -> List[str]:
    """The pre-compile gauntlet: plancheck PLAN001/002 feasibility plus
    kernelcheck KER001-003 — the same rules CI lints shipped configs
    with, applied to a machine-proposed one. The serve surface skips
    the mesh arithmetic: a serving replica's decode is mesh-local by
    design (the budget serve presets declare data=1 x fsdp=1 on an
    8-chip topology precisely because the engine replicates), so only
    plan validation + the kernel rules apply there."""
    findings: List[str] = []
    if surface != "serve":
        findings = [str(m) for m in plan.feasibility(model_cfg)]
    if findings or model_cfg is None:
        return findings
    from gke_ray_train_tpu.analysis.kernelcheck import (
        kernel_constraint_findings)
    findings.extend(str(f) for f in kernel_constraint_findings(
        plan, model_cfg, config=config))
    return findings


def enumerate_space(base_plan: ExecutionPlan, model_cfg=None, *,
                    surface: str = "train",
                    dims: Optional[List[str]] = None,
                    config: Mapping[str, Any] = ()) -> Space:
    """The full, statically-pruned candidate space around ``base_plan``.

    ``dims`` restricts which dimensions vary (names from
    :data:`TRAIN_DIMS` / :data:`SERVE_DIMS`); unknown names raise.
    The base plan itself is always candidate 0 — a search can never
    "lose" to an unsearched default.
    """
    all_dims = TRAIN_DIMS if surface == "train" else SERVE_DIMS
    use = tuple(all_dims) if dims is None else tuple(dims)
    unknown = sorted(set(use) - set(all_dims))
    if unknown:
        raise ValueError(f"unknown autotune dims {unknown} for surface "
                         f"{surface!r}; valid: {list(all_dims)}")

    base_cand = Candidate(plan=base_plan)
    pruned: List[str] = []
    dim_counts: Dict[str, int] = {}

    if surface == "serve":
        mb_opts = sorted({base_plan.max_batch, *MAX_BATCH_ARMS}) \
            if "max_batch" in use else [base_plan.max_batch]
        bucket_opts = _bucket_options(base_plan) \
            if "buckets" in use else [base_plan.decode_buckets]
        ad_opts = sorted({base_plan.max_adapters, *MAX_ADAPTERS_ARMS}) \
            if "adapters" in use else [base_plan.max_adapters]
        if "spec_k" in use and base_plan.spec_draft != "none":
            sk_opts = sorted({base_plan.spec_k, *SPEC_K_ARMS})
        else:
            sk_opts = [base_plan.spec_k]
            if "spec_k" in use:
                pruned.append(
                    "spec_k arms: skipped — base SPEC_DRAFT=none "
                    "(speculation off; every arm would compile the "
                    "identical program)")
        dim_counts = {"max_batch": len(mb_opts),
                      "buckets": len(bucket_opts),
                      "adapters": len(ad_opts),
                      "spec_k": len(sk_opts)}
        combos: List[Dict[str, Any]] = [
            {"max_batch": mb, "decode_buckets": bl,
             "max_adapters": na, "spec_k": sk}
            for mb in mb_opts for bl in bucket_opts
            for na in ad_opts for sk in sk_opts]
        env_opts: List[Tuple] = [()]
    else:
        mesh_opts = _mesh_options(base_plan) if "mesh" in use \
            else [(base_plan.resolved_sizes()["data"],
                   base_plan.resolved_sizes()["fsdp"])]
        batch_opts = _batch_options(base_plan) if "batch" in use \
            else [(base_plan.per_device_batch, base_plan.grad_accum)]
        sync_opts = _sync_options(base_plan) if "sync" in use \
            else [(base_plan.overlap, base_plan.dcn_sync,
                   base_plan.dcn_compress)]
        fused_opts = [False, True] if "fused" in use \
            else [base_plan.fused_ops]
        prefetch_opts = sorted({base_plan.prefetch, *PREFETCH_DEPTHS}) \
            if "prefetch" in use else [base_plan.prefetch]
        env_opts = _flash_envs(base_plan, model_cfg) \
            if "flash" in use else [()]
        dim_counts = {"mesh": len(mesh_opts), "batch": len(batch_opts),
                      "sync": len(sync_opts), "fused": len(fused_opts),
                      "flash": len(env_opts),
                      "prefetch": len(prefetch_opts)}
        combos = [
            {"data": d, "fsdp": f, "per_device_batch": pdb,
             "grad_accum": ga, "overlap": ov, "dcn_sync": ds,
             "dcn_compress": dc, "fused_ops": fu, "prefetch": pf}
            for d, f in mesh_opts
            for pdb, ga in batch_opts
            for ov, ds, dc in sync_opts
            for fu in fused_opts
            for pf in prefetch_opts]

    seen = {base_cand.fingerprint()}
    out = [base_cand]
    for fields in combos:
        try:
            plan = dataclasses.replace(base_plan, **fields)
        except PlanError as e:
            pruned.append(f"{fields}: PLAN000 {e}")
            continue
        findings = static_findings(plan, model_cfg, config, surface)
        if findings:
            pruned.append(f"{fields}: {findings[0]}")
            continue
        for env in env_opts:
            cand = Candidate(plan=plan, env=env)
            fp = cand.fingerprint()
            if fp in seen:
                continue
            seen.add(fp)
            out.append(cand)
    # deterministic order: base first, then by (distance, fingerprint)
    rest = sorted(out[1:],
                  key=lambda c: candidate_sort_key(c, base_plan, surface))
    return Space(base=base_cand, candidates=[base_cand] + rest,
                 pruned=pruned, dims=dim_counts)

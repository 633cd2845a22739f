"""shardlint level 5 — kernelcheck: differential kernel verification,
numerics lint, and static kernel/mesh constraints.

The accelerated ops (Pallas flash attention, ring/a2a context
parallelism, NF4/int8 quantization, MoE dispatch, RoPE, the KV-cache
admit path) were each verified by hand-rolled per-test oracles; nothing
statically related a kernel's grid/BlockSpec tiling to the dims an
:class:`~gke_ray_train_tpu.plan.ExecutionPlan` actually declares, and a
kernel claim ("fp32 online softmax", "bf16 matmuls accumulate in f32")
was a docstring, not a checkable property. kernelcheck makes all three
checkable, accelerator-free:

========  ==========================================================
rule      what it catches
========  ==========================================================
KER001    grid/BlockSpec infeasibility: the flash/ring block sizes
          cannot tile the per-shard sequence length the plan implies
          (seq len after context sharding has no legal Pallas block),
          or head_dim breaks the TPU sublane tile for the compute
          dtype (f32: 8, bf16: 16, int8: 32 — lane is always 128)
KER002    estimated VMEM footprint of one ``pallas_call`` grid step
          (double-buffered I/O blocks + scratch) exceeds the per-core
          VMEM budget of the declared topology's chip
KER003    kernel/mesh contract violation: ``attn_impl="flash"`` with
          a context-sharded plan (the runtime ValueError in
          ``ops/dispatch.py``, hoisted into lint)
KER004    non-finite hazard in traced step code: ``exp``/``log``/
          ``rsqrt`` with no guard (max-subtraction, eps-add, clamp,
          select) anywhere in its bounded ancestry — softmax without
          max-subtraction is the canonical instance
KER005    fp32-accumulation policy: a low-precision ``dot_general``
          without ``preferred_element_type=float32``, or a variance /
          second-moment reduction accumulated below fp32
KER006    an accelerated op required to be registered is missing from
          the kernel registry (``ops/registry.py``) — unregistered
          kernels are unverifiable by construction
KER100    a registered kernel case has no pinned tolerance in the
          ledger (``tests/tolerances/*.json``) — record it
KER101    differential value/grad error beyond the pinned tolerance
          band (precision regression vs the oracle)
KER102    the pinned tolerance is far looser than the observed error
          (silent over-loosening — the two-sided half, à la
          ``perf/budget.py``)
========  ==========================================================

KER001-003 are pure arithmetic per plan (no backend, like plancheck);
KER004-005 walk jaxprs — including the jaxprs *inside* ``pallas_call``
eqns — via ``jax.make_jaxpr`` over abstract args (no devices); the
KER10x differential sweeps run every registered kernel against its
oracle (values AND grads, per dtype, sharded cases via the kernel's own
``shard_map`` wrapper on the canonical fake-8 CPU mesh, Pallas in
interpret mode). ``TOLERANCE_UPDATE=1`` (or ``--record``) re-records
the ledger; review the JSON diff like code — that diff IS the numerics
review.
"""

from __future__ import annotations

import dataclasses
import json
import os
import zlib
from typing import Any, Dict, List, Mapping, Optional, Tuple

RULES = {
    "KER001": "grid/BlockSpec cannot tile the plan's kernel shapes",
    "KER002": "pallas_call VMEM footprint exceeds the per-core budget",
    "KER003": "kernel/mesh contract violation",
    "KER004": "non-finite hazard in traced step code",
    "KER005": "accumulation below fp32",
    "KER006": "accelerated op missing from the kernel registry",
    "KER100": "kernel case unrecorded in the tolerance ledger",
    "KER101": "differential error beyond the pinned tolerance",
    "KER102": "pinned tolerance over-loose vs observed error",
}

TOLERANCE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "tests", "tolerances")

# two-sided band à la perf/budget: observed error may drift at most
# SLACK x past the pin (regression), and the pin may sit at most
# SLACK x above the observed error (over-loosened pin). FLOOR absorbs
# exact-zero cases and denormal noise.
LEDGER_SLACK = 4.0
LEDGER_FLOOR = 1e-9

# registry names that MUST exist — deleting a registration (or breaking
# ops/registry.py import order) fails lint instead of silently
# unverifying the kernel (KER006)
REQUIRED_KERNELS = frozenset({
    "flash_attention", "ring_attention", "a2a_attention",
    "quant_matmul", "moe_dispatch", "rope", "kvcache_insert",
    "fused_norm_rope", "fused_cross_entropy", "hier_psum", "nf4_matmul"})

# TPU tiling: lane is always 128; sublane depends on dtype
SUBLANE = {"float32": 8, "bfloat16": 16, "float16": 16,
           "int8": 32, "fp8": 32}


@dataclasses.dataclass(frozen=True)
class KernelFinding:
    rule: str
    subject: str           # kernel / case / config field / traced label
    message: str
    config: str = ""       # config path or label, when plan-scoped

    def __str__(self) -> str:
        where = f"{self.config}: " if self.config else ""
        return f"{where}{self.rule} [{self.subject}] {self.message}"


# ---------------------------------------------------------------------------
# static layer: KER001-003 (pure arithmetic per plan) + KER006
# ---------------------------------------------------------------------------

def resolve_attn_impl(model_cfg, plan, config: Mapping[str, Any] = ()
                      ) -> str:
    """The attention impl the DECLARED topology would run: the config's
    ATTN_IMPL overrides the model preset; ``auto`` resolves to the
    Pallas kernel on TPU families and the XLA oracle on cpu-N — the
    same policy the runtime applies, evaluated against the plan's
    topology instead of the (possibly dead) attached backend."""
    impl = str(dict(config).get("ATTN_IMPL", model_cfg.attn_impl)).lower()
    if impl == "auto":
        family = plan.topology.split("-", 1)[0]
        return "xla" if family == "cpu" else "flash"
    return impl


def kernel_constraint_findings(plan, model_cfg, label: str = "",
                               config: Mapping[str, Any] = ()
                               ) -> List[KernelFinding]:
    """KER001 + KER002 + KER003 for one plan/model pair."""
    from gke_ray_train_tpu.ops.flash_attention import (
        estimate_vmem_bytes, pick_block, window_blocks)
    from gke_ray_train_tpu.perf.costs import CHIP_SPECS

    out: List[KernelFinding] = []
    if model_cfg is None:
        return out
    try:
        sizes = plan.resolved_sizes()
    except ValueError:
        return out          # untileable mesh is PLAN001's finding
    impl = resolve_attn_impl(model_cfg, plan, config)
    ctx = sizes["context"]

    # KER003: the ops/dispatch.py runtime contract, hoisted into lint
    if impl == "flash" and ctx > 1:
        out.append(KernelFinding(
            "KER003", "ATTN_IMPL",
            f"attn_impl='flash' with a context-sharded plan (context="
            f"{ctx}) would silently drop cross-shard attention — the "
            "dispatcher refuses it at runtime; declare attn_impl='ring' "
            "(or 'a2a') for context parallelism", label))

    seq = plan.max_seq_len
    s_local = seq // ctx if ctx > 1 and seq % ctx == 0 else seq
    dtype = str(model_cfg.dtype)
    dbytes = 2 if dtype in ("bfloat16", "float16") else 4
    head_dim = model_cfg.resolved_head_dim

    if impl not in ("flash", "ring", "a2a"):
        # the XLA attention oracle has no grid to tile — but the
        # FUSED_OPS epilogue kernels (norm/rope, cross-entropy) run
        # regardless of the attention impl
        family = plan.topology.split("-", 1)[0]
        out.extend(_fused_kernel_findings(
            plan, model_cfg, CHIP_SPECS.get(family, CHIP_SPECS["cpu"]),
            s_local, dbytes, label))
        return out

    # KER001a: block divisibility against the post-context-sharding
    # sequence — the Pallas grid covers s_local // block blocks, and a
    # non-divisor block silently leaves tail rows unwritten, which is
    # why pick_block hard-fails; lint moves that failure to CI
    # (the blocks a full causal row asks for at this head size: the
    # largest kv block any kind of layer takes)
    blocks: Dict[str, int] = {}
    for name, requested in zip(("block_q", "block_kv"),
                               window_blocks(
                                   s_local, None, head_dim,
                                   model_cfg.n_heads
                                   // model_cfg.n_kv_heads)):
        try:
            blocks[name] = pick_block(requested, s_local)
        except ValueError as e:
            out.append(KernelFinding(
                "KER001", name,
                f"{impl} kernel {name}={requested} cannot tile the "
                f"per-shard sequence {s_local} (= {seq} / context "
                f"{ctx}): {e}", label))

    # KER001b: head_dim vs the dtype's sublane tile (lane = 128)
    sublane = SUBLANE.get(dtype, 8)
    if head_dim % sublane:
        out.append(KernelFinding(
            "KER001", "head_dim",
            f"head_dim={head_dim} is not a multiple of the {dtype} "
            f"sublane tile ({sublane}) — Mosaic cannot tile the "
            "kernel's [block, head_dim] VMEM blocks", label))

    # KER002: VMEM footprint of one grid step vs the chip budget
    family = plan.topology.split("-", 1)[0]
    chip = CHIP_SPECS.get(family, CHIP_SPECS["cpu"])
    if len(blocks) == 2:
        est = estimate_vmem_bytes(blocks["block_q"], blocks["block_kv"],
                                  head_dim, dbytes)
        if est > chip.vmem_bytes:
            out.append(KernelFinding(
                "KER002", "FLASH_BLOCK_*",
                f"estimated VMEM for one {impl} grid step is "
                f"{est / 2**20:.1f} MiB (block_q={blocks['block_q']}, "
                f"block_kv={blocks['block_kv']}, head_dim={head_dim}, "
                f"{dtype}) vs the {chip.name} per-core budget "
                f"{chip.vmem_bytes / 2**20:.0f} MiB — shrink "
                "FLASH_BLOCK_Q/FLASH_BLOCK_KV", label))
    out.extend(_fused_kernel_findings(plan, model_cfg, chip, s_local,
                                      dbytes, label))
    return out


def _fused_kernel_findings(plan, model_cfg, chip, s_local: int,
                           dbytes: int, label: str
                           ) -> List[KernelFinding]:
    """KER001/KER002 for the FUSED_OPS kernels — their tiling routes
    through the SAME pick_block/estimate helpers flash uses
    (ops/fused_norm_rope.py, ops/fused_ce.py), so lint sees the same
    numbers the kernels will actually pick; a plan with fused_ops off
    has no fused grid to lint."""
    if not getattr(plan, "fused_ops", False):
        return []
    from gke_ray_train_tpu.ops import fused_ce, fused_norm_rope
    from gke_ray_train_tpu.ops.flash_attention import pick_block

    out: List[KernelFinding] = []
    d_model = model_cfg.d_model
    vocab = model_cfg.vocab_size
    sizes = plan.resolved_sizes()
    v_local = vocab // sizes["model"] if vocab % sizes["model"] == 0 \
        else vocab
    rows = plan.per_device_batch * s_local

    # fused_norm_rope: rows blocked over the per-shard sequence
    try:
        bs = pick_block(fused_norm_rope.DEFAULT_BLOCK_S, s_local)
    except ValueError as e:
        out.append(KernelFinding(
            "KER001", "FUSED_BLOCK_S",
            f"fused_norm_rope block_s="
            f"{fused_norm_rope.DEFAULT_BLOCK_S} cannot tile the "
            f"per-shard sequence {s_local}: {e}", label))
        bs = None
    if bs is not None:
        est = fused_norm_rope.estimate_vmem_bytes(bs, d_model, dbytes)
        if est > chip.vmem_bytes:
            out.append(KernelFinding(
                "KER002", "FUSED_BLOCK_S",
                f"estimated VMEM for one fused_norm_rope grid step is "
                f"{est / 2**20:.1f} MiB (block_s={bs}, "
                f"d_model={d_model}) vs the {chip.name} per-core "
                f"budget {chip.vmem_bytes / 2**20:.0f} MiB — shrink "
                "FUSED_BLOCK_S", label))

    # fused_cross_entropy: rows = local batch x seq, vocab tiled
    br = bv = None
    try:
        br = pick_block(fused_ce.DEFAULT_BLOCK_R, rows)
    except ValueError as e:
        out.append(KernelFinding(
            "KER001", "FUSED_CE_BLOCK_R",
            f"fused_cross_entropy block_r={fused_ce.DEFAULT_BLOCK_R} "
            f"cannot tile the local row count {rows} "
            f"(= per_device_batch {plan.per_device_batch} x per-shard "
            f"seq {s_local}): {e}", label))
    try:
        bv = pick_block(fused_ce.DEFAULT_BLOCK_V, v_local)
    except ValueError as e:
        out.append(KernelFinding(
            "KER001", "FUSED_CE_BLOCK_V",
            f"fused_cross_entropy block_v={fused_ce.DEFAULT_BLOCK_V} "
            f"cannot tile the per-shard vocab {v_local}: {e}", label))
    if br is not None and bv is not None:
        est = fused_ce.estimate_vmem_bytes(br, bv, d_model, dbytes)
        if est > chip.vmem_bytes:
            out.append(KernelFinding(
                "KER002", "FUSED_CE_BLOCK_*",
                f"estimated VMEM for one fused_cross_entropy grid step "
                f"is {est / 2**20:.1f} MiB (block_r={br}, block_v={bv}, "
                f"d_model={d_model}) vs the {chip.name} per-core "
                f"budget {chip.vmem_bytes / 2**20:.0f} MiB — shrink "
                "FUSED_CE_BLOCK_R/FUSED_CE_BLOCK_V", label))
    return out


def registration_findings() -> List[KernelFinding]:
    """KER006: every required accelerated op is registered."""
    from gke_ray_train_tpu.ops import registry
    have = {s.name for s in registry.all_kernels()}
    return [KernelFinding(
        "KER006", name,
        "required kernel has no entry in ops/registry.py — an "
        "unregistered kernel has no oracle, no domain, and no pinned "
        "tolerance, so nothing can verify it")
        for name in sorted(REQUIRED_KERNELS - have)]


# ---------------------------------------------------------------------------
# numerics lint: KER004/KER005 over jaxprs (no devices)
# ---------------------------------------------------------------------------

_EXP_GUARDS = frozenset({"sub", "min", "minimum", "clamp", "select_n"})
_LOG_GUARDS = frozenset({"add", "max", "maximum", "clamp", "select_n",
                         "exp", "log1p"})
_RSQRT_GUARDS = frozenset({"add", "max", "maximum", "clamp", "select_n"})
_ANCESTRY_DEPTH = 10


def _low_precision(dtype) -> bool:
    return str(dtype) in ("bfloat16", "float16")


def _sub_jaxprs(params: Mapping[str, Any]):
    from jax.extend import core as jex_core
    for v in params.values():
        vals = v if isinstance(v, (list, tuple)) else (v,)
        for item in vals:
            if isinstance(item, jex_core.ClosedJaxpr):
                yield item.jaxpr
            elif hasattr(item, "eqns") and hasattr(item, "invars"):
                yield item           # raw Jaxpr (pallas_call)


def _eqn_where(eqn) -> str:
    try:
        frame = eqn.source_info.traceback.frames[0]
        return f" ({os.path.basename(frame.file_name)}:"\
               f"{frame.start_line})"
    except Exception:  # noqa: BLE001 - source info is best-effort
        return ""


def _walk_jaxpr(jaxpr, label: str, top: bool,
                findings: List[KernelFinding]) -> None:
    from jax.extend import core as jex_core

    producers = {}
    for eqn in jaxpr.eqns:
        for ov in eqn.outvars:
            producers[ov] = eqn

    def guarded(var, guards) -> bool:
        """True when the bounded producer ancestry of ``var`` contains a
        guarding primitive. Free vars (jaxpr inputs) are benign at
        sub-jaxpr depth — the guard may live in the caller (a Pallas
        backward kernel receives the already-max-subtracted lse via a
        ref) — but raw inputs of the TOP-LEVEL traced body are exactly
        the unguarded case the rule exists for."""
        stack = [(var, 0)]
        seen = set()
        while stack:
            v, d = stack.pop()
            if isinstance(v, jex_core.Literal):
                continue     # a literal operand is a constant, not data
            if id(v) in seen or d > _ANCESTRY_DEPTH:
                continue
            seen.add(id(v))
            eqn = producers.get(v)
            if eqn is None:
                if not top:
                    return True
                continue
            if eqn.primitive.name in guards:
                return True
            stack.extend((iv, d + 1) for iv in eqn.invars)
        return False

    def low_prec_square(var) -> bool:
        """A square (x*x / x**2) in the bounded ancestry whose RESULT
        is low-precision — the second moment rounds to bf16 before it
        is ever accumulated (rms_norm's discipline: cast to f32 FIRST,
        then square, then reduce)."""
        stack = [(var, 0)]
        seen = set()
        while stack:
            v, d = stack.pop()
            if isinstance(v, jex_core.Literal) or id(v) in seen \
                    or d > _ANCESTRY_DEPTH:
                continue
            seen.add(id(v))
            eqn = producers.get(v)
            if eqn is None:
                continue
            name = eqn.primitive.name
            is_square = (
                name == "square"
                or (name == "integer_pow" and eqn.params.get("y") == 2)
                or (name == "mul" and len(eqn.invars) == 2
                    and eqn.invars[0] is eqn.invars[1]))
            if is_square and _low_precision(eqn.outvars[0].aval.dtype):
                return True
            stack.extend((iv, d + 1) for iv in eqn.invars)
        return False

    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in ("exp", "exp2") and not guarded(eqn.invars[0],
                                                   _EXP_GUARDS):
            findings.append(KernelFinding(
                "KER004", label,
                f"exp with no max-subtraction/clamp in its ancestry"
                f"{_eqn_where(eqn)} — overflows to inf for large "
                "logits; subtract the row max first (online-softmax "
                "discipline)"))
        elif name == "log" and not guarded(eqn.invars[0], _LOG_GUARDS):
            findings.append(KernelFinding(
                "KER004", label,
                f"log with no eps/max/clamp guard in its ancestry"
                f"{_eqn_where(eqn)} — NaN/-inf at zero; add an eps or "
                "clamp the operand"))
        elif name == "rsqrt" and not guarded(eqn.invars[0],
                                             _RSQRT_GUARDS):
            findings.append(KernelFinding(
                "KER004", label,
                f"rsqrt with no eps-add in its ancestry{_eqn_where(eqn)}"
                " — inf at zero variance; use rsqrt(x + eps)"))
        elif name == "dot_general":
            pref = eqn.params.get("preferred_element_type")
            if _low_precision(eqn.invars[0].aval.dtype) and (
                    pref is None or _low_precision(pref)):
                findings.append(KernelFinding(
                    "KER005", label,
                    "low-precision dot_general without "
                    f"preferred_element_type=float32{_eqn_where(eqn)} — "
                    "the contraction accumulates (and rounds) in "
                    f"{eqn.invars[0].aval.dtype}; declare fp32 "
                    "accumulation and cast the result"))
        elif name == "reduce_sum" and low_prec_square(eqn.invars[0]):
            findings.append(KernelFinding(
                "KER005", label,
                "variance/second-moment computed below fp32"
                f"{_eqn_where(eqn)} — the squares round to bf16/f16 "
                "before accumulation; cast to float32 FIRST, then "
                "square and reduce (rms_norm's discipline)"))
        for sub in _sub_jaxprs(eqn.params):
            _walk_jaxpr(sub, label, False, findings)


def numerics_findings() -> List[KernelFinding]:
    """KER004/KER005 over every registered kernel's traced bodies plus
    the standalone step-code targets (loss, norms, dense attention)."""
    import jax

    from gke_ray_train_tpu.ops import registry

    targets: List[tuple] = []
    for spec in registry.all_kernels():
        if spec.numerics_targets is not None:
            targets.extend(spec.numerics_targets())
    targets.extend(registry.standalone_numerics_targets())

    findings: List[KernelFinding] = []
    for label, fn, abstract_args in targets:
        jaxpr = jax.make_jaxpr(fn)(*abstract_args)
        _walk_jaxpr(jaxpr.jaxpr, label, True, findings)
    return findings


def lint_traced_fn(fn, *abstract_args, label: str = "<fn>"
                   ) -> List[KernelFinding]:
    """KER004/KER005 over one traced body — the test-fixture entry."""
    import jax
    findings: List[KernelFinding] = []
    jaxpr = jax.make_jaxpr(fn)(*abstract_args)
    _walk_jaxpr(jaxpr.jaxpr, label, True, findings)
    return findings


# ---------------------------------------------------------------------------
# differential layer: registry sweeps vs the tolerance ledger
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class CaseResult:
    kernel: str
    case: str
    value_err: float
    grad_err: Optional[float] = None
    exact: bool = False

    def metrics(self) -> Dict[str, float]:
        out = {"value": self.value_err}
        if self.grad_err is not None:
            out["grad"] = self.grad_err
        return out


def _case_key(spec_name: str, case_name: str):
    import jax
    return jax.random.key(zlib.crc32(f"{spec_name}/{case_name}".encode()))


def _rel_err(a, b) -> float:
    import numpy as np
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    denom = max(float(np.max(np.abs(b))), 1e-30)
    return float(np.max(np.abs(a - b))) / denom


def _matched_leaves(got, want):
    """Leaf pairs, with the tree structures asserted equal FIRST — a
    kernel/oracle structure mismatch must be a loud error, never a
    zip-truncated partial comparison that reports 'clean' on leaves it
    silently skipped."""
    import jax
    got_s = jax.tree.structure(got)
    want_s = jax.tree.structure(want)
    if got_s != want_s:
        raise KernelCheckError(
            f"kernel and oracle outputs have different tree structures "
            f"({got_s} vs {want_s}) — the differential claim is "
            "ill-formed; fix the registration")
    return list(zip(jax.tree.leaves(got), jax.tree.leaves(want)))


def _tree_err(got, want) -> float:
    return max(_rel_err(g, w) for g, w in _matched_leaves(got, want))


def _tree_exact(got, want) -> bool:
    import numpy as np
    return all(np.array_equal(np.asarray(g), np.asarray(w))
               for g, w in _matched_leaves(got, want))


def _case_mesh(case):
    import jax

    from gke_ray_train_tpu.parallel.mesh import MESH_AXES, MeshConfig, \
        build_mesh
    if case.mesh_axes is None:
        return None
    sizes = {a: 1 for a in MESH_AXES}
    sizes.update(case.mesh_axes)
    n = 1
    for v in sizes.values():
        n *= v
    if n != len(jax.devices()):
        raise RuntimeError(
            f"case {case.name!r} wants a {n}-device mesh but "
            f"{len(jax.devices())} devices are attached — run on the "
            "canonical fake-8 CPU mesh (the CLI re-execs itself there)")
    return build_mesh(MeshConfig(**sizes), jax.devices())


def _probe(tree):
    """Deterministic cotangent for the grad check (cos ramp per leaf)."""
    import jax
    import jax.numpy as jnp

    def one(x):
        flat = jnp.cos(jnp.arange(x.size, dtype=jnp.float32) * 0.7)
        return flat.reshape(x.shape).astype(x.dtype)

    return jax.tree.map(one, tree)


def run_case(spec, case) -> CaseResult:
    """One differential point: values (and grads) of kernel vs oracle."""
    import jax
    import jax.numpy as jnp

    args, diff_argnums = spec.build(case, _case_key(spec.name, case.name))
    mesh = _case_mesh(case)

    out_k = spec.kernel(case, mesh, *args)
    out_o = spec.oracle(case, mesh, *args)
    if case.exact:
        return CaseResult(spec.name, case.name,
                          0.0 if _tree_exact(out_k, out_o) else
                          _tree_err(out_k, out_o), exact=True)
    value_err = _tree_err(out_k, out_o)

    grad_err = None
    if case.grads and diff_argnums:
        probe = _probe(out_k)

        def loss(run):
            def fn(*dargs):
                full = list(args)
                for i, a in zip(diff_argnums, dargs):
                    full[i] = a
                out = run(case, mesh, *full)
                return sum(
                    jnp.sum(o.astype(jnp.float32)
                            * p.astype(jnp.float32))
                    for o, p in zip(jax.tree.leaves(out),
                                    jax.tree.leaves(probe)))
            return fn

        dargs = tuple(args[i] for i in diff_argnums)
        g_k = jax.grad(loss(spec.kernel),
                       argnums=tuple(range(len(dargs))))(*dargs)
        g_o = jax.grad(loss(spec.oracle),
                       argnums=tuple(range(len(dargs))))(*dargs)
        grad_err = _tree_err(g_k, g_o)
    return CaseResult(spec.name, case.name, value_err, grad_err)


def sweep(names: Optional[List[str]] = None) -> List[CaseResult]:
    """Run every registered kernel's full case sweep (or a subset)."""
    from gke_ray_train_tpu.ops import registry
    specs = registry.all_kernels()
    if names:
        unknown = set(names) - {s.name for s in specs}
        if unknown:
            # a typo'd name must not shrink the sweep to nothing and
            # report 'clean' — the gate would pass having verified zero
            raise KernelCheckError(
                f"unknown kernel(s) {sorted(unknown)}; registered: "
                f"{[s.name for s in specs]}")
        specs = [s for s in specs if s.name in set(names)]
    results: List[CaseResult] = []
    for spec in specs:
        for case in spec.cases:
            results.append(run_case(spec, case))
    return results


# -- tolerance ledger --------------------------------------------------------

def ledger_path(kernel: str, ledger_dir: Optional[str] = None) -> str:
    return os.path.join(ledger_dir or TOLERANCE_DIR, f"{kernel}.json")


def load_ledger(kernel: str, ledger_dir: Optional[str] = None
                ) -> Optional[Dict[str, Any]]:
    path = ledger_path(kernel, ledger_dir)
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def record_ledger(results: List[CaseResult],
                  ledger_dir: Optional[str] = None) -> List[str]:
    """Write one ledger JSON per kernel, pinning the observed errors.
    Values are rounded to 3 significant digits so a bitwise-stable
    re-record survives last-ulp drift in the error measurement."""
    by_kernel: Dict[str, Dict[str, Dict[str, float]]] = {}
    for r in results:
        by_kernel.setdefault(r.kernel, {})[r.case] = {
            k: float(f"{v:.3g}") for k, v in r.metrics().items()}
    written = []
    for kernel in sorted(by_kernel):
        path = ledger_path(kernel, ledger_dir)
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        doc = {
            "_kernel": kernel,
            "_note": "observed kernel-vs-oracle error per case, pinned "
                     "two-sided; re-record with TOLERANCE_UPDATE=1 (or "
                     "python -m gke_ray_train_tpu.analysis kernelcheck "
                     "--record) and review the diff like code",
            "cases": by_kernel[kernel],
        }
        with open(path, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
            f.write("\n")
        written.append(path)
    return written


def ledger_findings(results: List[CaseResult],
                    ledger_dir: Optional[str] = None
                    ) -> List[KernelFinding]:
    """KER100/101/102: the two-sided comparator. A regression (observed
    error above the pinned band) and an over-loosened pin (pinned error
    far above observed — e.g. a hand-edited ledger hiding a regression
    behind slack) both fail."""
    out: List[KernelFinding] = []
    ledgers: Dict[str, Optional[Dict[str, Any]]] = {}
    for r in results:
        if r.kernel not in ledgers:
            ledgers[r.kernel] = load_ledger(r.kernel, ledger_dir)
        doc = ledgers[r.kernel]
        pinned = (doc or {}).get("cases", {}).get(r.case)
        subject = f"{r.kernel}/{r.case}"
        if pinned is None:
            out.append(KernelFinding(
                "KER100", subject,
                "no pinned tolerance for this case — record the ledger "
                "(TOLERANCE_UPDATE=1) and review the new pin"))
            continue
        for metric, observed in r.metrics().items():
            pin = pinned.get(metric)
            if pin is None:
                out.append(KernelFinding(
                    "KER100", f"{subject}:{metric}",
                    "metric unpinned in the ledger — re-record"))
                continue
            if observed > max(pin * LEDGER_SLACK, LEDGER_FLOOR):
                out.append(KernelFinding(
                    "KER101", f"{subject}:{metric}",
                    f"observed error {observed:.3g} vs pinned "
                    f"{pin:.3g} (> {LEDGER_SLACK:g}x band) — precision "
                    "regression against the oracle; if the change is "
                    "INTENTIONAL, re-record with TOLERANCE_UPDATE=1"))
            elif pin > max(observed * LEDGER_SLACK, LEDGER_FLOOR):
                out.append(KernelFinding(
                    "KER102", f"{subject}:{metric}",
                    f"pinned tolerance {pin:.3g} is > {LEDGER_SLACK:g}x "
                    f"looser than the observed error {observed:.3g} — "
                    "an over-loose pin would hide the next regression; "
                    "re-record to tighten"))
    return out


def quick_verify(log=None) -> List[CaseResult]:
    """The KERNELCHECK=1 worker-startup probe: first (cheapest) case of
    every registered kernel, value-only, against the shipped ledger.
    Raises on any finding — a worker whose kernels disagree with their
    oracles must not train."""
    import jax

    from gke_ray_train_tpu.ops import registry

    def mesh_fits(case) -> bool:
        if case.mesh_axes is None:
            return True
        n = 1
        for v in case.mesh_axes.values():
            n *= v
        return n == len(jax.devices())

    results = []
    for spec in registry.all_kernels():
        # cheapest case whose mesh (if any) the attached pool can form
        # — a worker on a 16-chip pool must not die because a case was
        # written for the canonical fake-8 mesh; mesh-free cases cover
        # the kernel math either way
        case = next((c for c in spec.cases if mesh_fits(c)), None)
        if case is None:
            continue
        results.append(run_case(spec, dataclasses.replace(case,
                                                          grads=False)))
    findings = [f for f in ledger_findings(results)
                if f.rule != "KER102"]   # startup gate: regressions only
    if findings:
        raise KernelCheckError(
            "KERNELCHECK startup verification failed:\n  "
            + "\n  ".join(str(f) for f in findings))
    if log is not None and results:
        log("KERNELCHECK: %d kernel(s) verified against their oracles "
            "(worst value error %.3g)", len(results),
            max(r.value_err for r in results))
    return results


class KernelCheckError(AssertionError):
    """A kernel disagreed with its oracle beyond the pinned tolerance."""


# ---------------------------------------------------------------------------
# CLI body (the `kernelcheck` verb of python -m gke_ray_train_tpu.analysis)
# ---------------------------------------------------------------------------

def static_findings(config_paths: Optional[List[str]] = None
                    ) -> List[KernelFinding]:
    """KER001-006 over the shipped configs (same default set plancheck
    gates) — no backend, no devices."""
    from gke_ray_train_tpu.analysis.plancheck import (
        default_config_paths, model_config_for)
    from gke_ray_train_tpu.plan import ExecutionPlan, PlanError

    repo_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    paths = config_paths or default_config_paths(repo_root)
    findings: List[KernelFinding] = []
    for p in paths:
        label = os.path.relpath(p, repo_root) if os.path.isabs(p) else p
        try:
            with open(p) as fh:
                config = json.load(fh)
            plan = ExecutionPlan.from_config(config)
            model_cfg = model_config_for(config, plan)
        except (OSError, json.JSONDecodeError, PlanError, ValueError):
            continue         # plancheck PLAN000 owns unparseable configs
        findings.extend(kernel_constraint_findings(
            plan, model_cfg, label=label, config=config))
    findings.extend(registration_findings())
    findings.extend(numerics_findings())
    return findings


def main_check(names: Optional[List[str]] = None, *,
               static_only: bool = False, diff_only: bool = False,
               record: bool = False,
               ledger_dir: Optional[str] = None,
               config_paths: Optional[List[str]] = None) -> int:
    findings: List[KernelFinding] = []
    if not diff_only:
        findings.extend(static_findings(config_paths))
    results: List[CaseResult] = []
    if not static_only:
        results = sweep(names)
        if record or os.environ.get("TOLERANCE_UPDATE") == "1":
            for path in record_ledger(results, ledger_dir):
                print(f"recorded {path}")
        else:
            findings.extend(ledger_findings(results, ledger_dir))
    for f in findings:
        print(f"FINDING {f}")
    if findings:
        print(f"kernelcheck: {len(findings)} finding(s)")
        return 1
    parts = []
    if not diff_only:
        parts.append("static rules KER001-006 clean")
    if results:
        worst = max(r.value_err for r in results)
        parts.append(f"{len(results)} differential case(s) within the "
                     f"pinned ledger, worst value error {worst:.3g}")
    print("kernelcheck: clean (" + "; ".join(parts) + ")")
    return 0

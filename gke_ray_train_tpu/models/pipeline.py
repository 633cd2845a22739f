"""GSPMD pipeline parallelism over the ``pipe`` mesh axis.

The reference stack reaches pipeline parallelism through DeepSpeed/
Megatron-style stage processes; SURVEY.md §2c records PP as optional on
TPU ("prefer TP+FSDP"). This module closes that row anyway, the TPU way:
no stage processes, no send/recv framework — the pipeline is ordinary
jit-traced array code whose *shardings* make XLA emit the stage-to-stage
transfer as a one-hop ``collective-permute`` on ICI.

Design (the "shift buffer" formulation, cf. the public scaling-book
pipelining recipe):

- The stacked block params ``[R, ...]`` are viewed as ``[R/P, P, ...]``
  with the stage dim sharded over ``pipe`` — each device owns the
  weights of its ``R/P`` contiguous repeats (param memory scales 1/P,
  same as the reference's stage partitioning).
- Activations live in a stage buffer ``[P, Bm, S, D]`` (microbatch size
  ``Bm = B/M``). Each tick: ``jnp.roll`` the buffer by one stage (XLA:
  collective-permute), feed microbatch ``t`` into stage 0, apply every
  stage's local repeats in parallel (stage-batched einsums — block-
  diagonal matmuls, one per device), and harvest stage ``P-1``'s output.
- ``M + P - 1`` ticks drain ``M`` microbatches; the bubble fraction is
  ``(P-1)/(M+P-1)`` — raise ``pipe_microbatches`` to amortize it.
- The whole loop is a ``lax.scan``; autodiff transposes the rolls into
  reverse permutes, so the backward pass is the mirrored pipeline with
  no hand-written schedule.

Circular / interleaved schedule (``cfg.pipe_virtual = v > 1``): each
device owns ``v`` NON-contiguous layer groups of ``R/(P·v)`` repeats
(device p owns groups ``{j·P+p}``); the stage buffer generalizes to
``[v, P, ...]`` and a microbatch loops the device ring ``v`` times.
``v = 1`` IS the plain shift schedule (one code path).

Honest bubble accounting for this homogeneous-scan formulation — every
tick costs the same R/P repeats per device whether a slot holds real
data or garbage, so "bubble" here means garbage-slot compute:

| schedule      | ticks        | garbage fraction    |
|---------------|--------------|---------------------|
| shift (v=1)   | M + P - 1    | (P-1)/(M+P-1)       |
| circular (v)  | M + vP - 1   | (vP-1)/(M+vP-1)     |

i.e. circular does NOT cut the scan-form bubble — the Megatron-style
``(P-1)/(Mv+P-1)`` figure requires a heterogeneous 1F1B schedule that a
single jitted scan (and its autodiff transpose) cannot express. What
circular buys here is finer-grained stages (first-token latency R/(vP)
per hop, relevant for inference pipelining) at the cost of one
gather-style param regroup per forward (non-contiguous ownership vs the
contiguous ``pipe``-sharded storage). The REAL bubble lever on TPU is
``M``: fold grad-accum microbatches into ``pipe_microbatches`` (set
``GRADIENT_ACCUMULATION_STEPS=1`` and ``PIPE_MICROBATCHES=G·M``) so one
pipeline pass amortizes its P-1 warmup over the whole accumulation
window — the loss is a per-token sum either way, so the math is
identical. Measured tick counts are pinned by tests/test_pipeline.py.

Composability: the batch dim stays sharded over ``(data, fsdp)``,
head/ffn dims over ``model``, and the sequence dim over ``context``
*inside* the pipeline (the stage dim is just one more array axis to
GSPMD), so PP composes with DP/FSDP/TP/CP — ring/a2a attention take the
stage-folded ``(pipe, data, fsdp)`` batch spec through the dispatch's
``batch_axes`` hook, and EP rides in via the MoE expert sharding.

Correctness notes:
- Warmup ticks process zero buffers and drain ticks replay the last
  microbatch; microbatch m surfaces from the last slot at tick
  m + depth - 1 (depth = v·P hops), so the harvest is simply the last M
  scan outputs (``ys[depth-1:]``) — garbage emissions fall outside the
  window and get zero cotangent in the backward pass. The one thing
  that DOES need masking is the MoE router aux, which would otherwise
  count the garbage passes (see the validity mask in the tick body).
- LoRA adapters ride along as stage-batched einsums (QLoRA bases
  dequantize per stage-slice); LoRA *dropout* is not supported on a
  pipelined mesh — the per-repeat rng fold-in would need a per-stage
  tick-varying key schedule for exactness.
- MoE MLPs route per stage via a vmapped moe_mlp; dispatch capacity is
  per sequence row, so pipelined logits are exact vs the plain path.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from gke_ray_train_tpu.models.config import ModelConfig
from gke_ray_train_tpu.models.remat import checkpoint_block
from gke_ray_train_tpu.ops.attention import (
    dot_product_attention, make_attention_mask)
from gke_ray_train_tpu.ops.norms import rms_norm
from gke_ray_train_tpu.ops.rope import apply_rope
from gke_ray_train_tpu.parallel.mesh import (
    AXIS_CONTEXT, AXIS_PIPE, BATCH_AXES)

# the folded (stage * microbatch) leading dim of attention inputs
STAGE_BATCH_AXES = (AXIS_PIPE,) + BATCH_AXES

def _warn_shallow_microbatches(M: int, V: int, Pn: int) -> None:
    """Trace-time (once per shape) warning: fewer microbatches than
    pipeline hops means the garbage fraction exceeds 50%."""
    import logging

    from gke_ray_train_tpu.logging_utils import warn_once
    depth = V * Pn
    warn_once(
        logging.getLogger(__name__), ("shallow_microbatches", M, V, Pn),
        "pipeline has %d microbatches for depth %d (pipe=%d x virtual=%d):"
        " garbage fraction is %d/%d — raise PIPE_MICROBATCHES to amortize",
        M, depth, Pn, V, depth - 1, M + depth - 1)


def _constrain(x, mesh: Optional[Mesh], *spec):
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))


def _proj_p(x, w, lora_p, lora_scale, dtype, bias=None):
    """Stage-batched projection: x [P, Bm, S, d_in] @ w [P, d_in, d_out]
    (+ optional per-stage bias [P, d_out] — Qwen-2 q/k/v).

    One matmul per stage (block-diagonal to XLA — each device sees only
    its own stage's operand, so locally this is a plain matmul on the
    MXU). ``w`` may be a quantized QTensor slice (QLoRA base)."""
    from gke_ray_train_tpu.ops.quant import maybe_dequantize
    y = jnp.einsum("pbsd,pdh->pbsh", x, maybe_dequantize(w, dtype))
    if lora_p is not None:
        xa = jnp.einsum("pbsd,pdr->pbsr", x, lora_p["a"].astype(dtype))
        y = y + jnp.einsum("pbsr,prh->pbsh", xa,
                           lora_p["b"].astype(dtype)) \
            * jnp.asarray(lora_scale, dtype)
    if bias is not None:
        y = y + bias[:, None, None, :].astype(dtype)
    return y


def _norm_p(x, scale, eps, sp1):
    """rms_norm with a per-stage scale [P, D] against x [P, Bm, S, D]."""
    return rms_norm(x, scale[:, None, None, :], eps=eps, scale_plus_one=sp1)


def _lora_entry(lora_p, name):
    return None if lora_p is None or name not in lora_p else lora_p[name]


def _attn_p(x, lp, cfg: ModelConfig, impl, dtype, rope, posf, segf, mask,
            window, mesh, lora_p, lora_scale, seq_ax=None):
    """posf/segf: stage-folded [Pn*Bm, S]; mask: prebuilt dense mask for
    this block kind (xla impl) or None (kernel impls build blockwise)."""
    Pn, Bm, S, D = x.shape
    hd = cfg.resolved_head_dim
    H, K = cfg.n_heads, cfg.n_kv_heads

    def lr(name):
        return _lora_entry(lora_p, name)
    q = _proj_p(x, lp["wq"], lr("wq"), lora_scale, dtype,
                bias=lp.get("bq"))
    k = _proj_p(x, lp["wk"], lr("wk"), lora_scale, dtype,
                bias=lp.get("bk"))
    v = _proj_p(x, lp["wv"], lr("wv"), lora_scale, dtype,
                bias=lp.get("bv"))
    # fold the stage dim into batch: attention is weightless, so every
    # stage runs the identical kernel on its own microbatch
    q = q.reshape(Pn * Bm, S, H, hd)
    k = k.reshape(Pn * Bm, S, K, hd)
    v = v.reshape(Pn * Bm, S, K, hd)
    q = _constrain(q, mesh, STAGE_BATCH_AXES, seq_ax, "model", None)
    k = _constrain(k, mesh, STAGE_BATCH_AXES, seq_ax, "model", None)
    if rope is not None:
        q = apply_rope(q, posf, rope)
        k = apply_rope(k, posf, rope)
    if impl == "xla":
        out = dot_product_attention(q, k, v, mask, scale=cfg.attn_scale,
                                    logit_softcap=cfg.attn_softcap)
    else:
        from gke_ray_train_tpu.ops.dispatch import attention_dispatch
        out = attention_dispatch(
            impl, q, k, v, q_positions=posf, kv_positions=posf,
            q_segment_ids=segf, kv_segment_ids=segf, causal=True,
            sliding_window=window, scale=cfg.attn_scale,
            logit_softcap=cfg.attn_softcap, mesh=mesh,
            batch_axes=STAGE_BATCH_AXES)
    out = out.reshape(Pn, Bm, S, H * hd)
    return _proj_p(out, lp["wo"], lr("wo"), lora_scale, dtype)


def _moe_p(x, lp, cfg: ModelConfig, dtype, w):
    """Stage-batched MoE MLP: vmap the plain moe_mlp over the stage dim
    (each stage owns different expert weights). Returns (y [P,Bm,S,D],
    per-stage aux [P]). Dispatch capacity is per sequence row, so the
    routing inside one microbatch is IDENTICAL to the unpipelined layer;
    only the aux statistic becomes a mean over (stage, microbatch)
    submeans instead of one joint batch mean. ``w`` [P,Bm,S] are the
    token weights riding the stage buffers — all-zero on WARMUP slots
    (zero-initialized buffer), but drain slots replay the last
    microbatch's real weights: the tick's ``(mb>=0)&(mb<M)`` mask is
    what actually excludes garbage passes from the aux."""
    from gke_ray_train_tpu.ops.moe import moe_mlp

    def one_stage(xs, router, w_gate, w_up, w_down, ws):
        return moe_mlp(xs, router, w_gate, w_up, w_down, cfg, dtype,
                       weights=ws)

    return jax.vmap(one_stage)(x, lp["router"], lp["w_gate"],
                               lp["w_up"], lp["w_down"], w)


def _mlp_p(x, lp, cfg: ModelConfig, dtype, lora_p, lora_scale):
    def lr(name):
        return _lora_entry(lora_p, name)
    gate = _proj_p(x, lp["w_gate"], lr("w_gate"), lora_scale, dtype)
    up = _proj_p(x, lp["w_up"], lr("w_up"), lora_scale, dtype)
    if cfg.activation == "silu":
        act = jax.nn.silu(gate)
    elif cfg.activation == "gelu_tanh":
        act = jax.nn.gelu(gate, approximate=True)
    else:
        raise ValueError(f"unknown activation {cfg.activation}")
    return _proj_p(act * up, lp["w_down"], lr("w_down"), lora_scale, dtype)


def _stage_repeats(x, pos, seg, w, blocks_r, lora_r, cfg: ModelConfig,
                   impl, dtype, rope, mesh, lora_scale, seq_ax=None):
    """Apply each stage's R/P local repeats to its buffer slot.

    Mirrors transformer.repeat_body, stage-batched; scanned over the
    per-stage repeat dim so depth compiles once. Dense masks (xla impl)
    are built ONCE per tick per block kind — pos/seg are constant across
    the repeat scan (same 'build once' rule as transformer.forward)."""
    eps, sp1 = cfg.norm_eps, cfg.norm_scale_plus_one
    Pn, Bm, S = pos.shape
    posf = pos.reshape(Pn * Bm, S)
    segf = seg.reshape(Pn * Bm, S)
    masks = {kind: None for kind in set(cfg.block_pattern)}
    if impl == "xla":
        for kind in masks:
            masks[kind] = make_attention_mask(
                posf, posf, segf, segf, causal=True,
                sliding_window=(cfg.sliding_window if kind == "sliding"
                                else None))

    moe = cfg.n_experts > 0

    def body(carry, xs_slice):
        x, aux = carry
        layer_slice = xs_slice[0]
        lora_slice = xs_slice[1] if lora_r is not None else None
        for p_i, kind in enumerate(cfg.block_pattern):
            lp = layer_slice[p_i]
            lo = lora_slice[p_i] if lora_slice is not None else None
            window = cfg.sliding_window if kind == "sliding" else None
            h = _norm_p(x, lp["attn_norm"], eps, sp1)
            h = _attn_p(h, lp, cfg, impl, dtype, rope, posf, segf,
                        masks[kind], window, mesh, lo, lora_scale,
                        seq_ax)
            if cfg.post_block_norm:
                h = _norm_p(h, lp["attn_post_norm"], eps, sp1)
            x = x + h
            x = _constrain(x, mesh, AXIS_PIPE, BATCH_AXES, seq_ax, None)
            h = _norm_p(x, lp["mlp_norm"], eps, sp1)
            if moe:
                h, a = _moe_p(h, lp, cfg, dtype, w)
                aux = aux + a
            else:
                h = _mlp_p(h, lp, cfg, dtype, lo, lora_scale)
            if cfg.post_block_norm:
                h = _norm_p(h, lp["mlp_post_norm"], eps, sp1)
            x = x + h
            x = _constrain(x, mesh, AXIS_PIPE, BATCH_AXES, seq_ax, None)
        return (x, aux), None

    # nothing kept beside the block inputs: a schedule's ticks in flight
    # multiply what a block keeps, and no chooser sizes that yet
    body = checkpoint_block(body, cfg)
    xs = [blocks_r]
    if lora_r is not None:
        xs.append(lora_r)
    (x, aux), _ = jax.lax.scan(
        body, (x, jnp.zeros((Pn,), jnp.float32)), tuple(xs))
    return x, aux


def _virtual_repeats(buf, pbuf, sbuf, wbuf, blocks_r, lora_r,
                     cfg: ModelConfig, impl, dtype, rope, mesh,
                     lora_scale, seq_ax):
    """Apply every (virtual-group, device-stage) slot's local repeats.

    buf [V, Pn, Bm, S, D]; blocks_r/lora_r leaves [Rg, V, Pn, ...].
    V=1 (the default shift schedule) calls _stage_repeats directly —
    byte-identical program to the pre-virtual implementation; V>1 vmaps
    it over the virtual-group dim (device p's V groups are processed
    within one tick, keeping per-tick cost at R/P repeats per device).
    Returns (buf [V, Pn, ...], aux [V, Pn])."""
    V = buf.shape[0]
    if V == 1:
        blocks1 = jax.tree.map(lambda l: l[:, 0], blocks_r)
        lora1 = (jax.tree.map(lambda l: l[:, 0], lora_r)
                 if lora_r is not None else None)
        x, aux = _stage_repeats(buf[0], pbuf[0], sbuf[0], wbuf[0],
                                blocks1, lora1, cfg, impl, dtype, rope,
                                mesh, lora_scale, seq_ax)
        return x[None], aux[None]

    def one_group(x, p, s, w, b, lo):
        return _stage_repeats(x, p, s, w, b, lo, cfg, impl, dtype, rope,
                              mesh, lora_scale, seq_ax)

    if lora_r is None:
        return jax.vmap(
            lambda x, p, s, w, b: one_group(x, p, s, w, b, None),
            in_axes=(0, 0, 0, 0, 1))(buf, pbuf, sbuf, wbuf, blocks_r)
    return jax.vmap(one_group, in_axes=(0, 0, 0, 0, 1, 1))(
        buf, pbuf, sbuf, wbuf, blocks_r, lora_r)


def pipeline_blocks(x, params_blocks, cfg: ModelConfig, mesh: Mesh, *,
                    impl: str, dtype, rope, positions, segment_ids,
                    lora_blocks=None, lora_scale: float = 1.0,
                    n_microbatches: Optional[int] = None,
                    token_weights=None):
    """Run the stacked decoder blocks pipelined over the ``pipe`` axis.

    x: embedded activations [B, S, D] (batch sharded over (data, fsdp),
    replicated over pipe). Returns ``(y, aux)``: the block-stack output
    [B, S, D] with the same layout (final norm/unembed run replicated,
    outside) and the summed-over-layers MoE router aux (0.0 for dense).
    """
    Pn = int(mesh.shape[AXIS_PIPE])
    V = int(cfg.pipe_virtual)  # >= 1 by ModelConfig validation
    R = cfg.n_repeats
    if R % (Pn * V) != 0:
        raise ValueError(
            f"n_repeats={R} must be divisible by pipe axis x virtual "
            f"stages ({Pn} x {V})")
    if impl not in ("xla", "flash", "ring", "a2a"):
        raise ValueError(f"unknown attn impl {impl!r}")
    # context-parallel attention composes: ring/a2a take the stage-folded
    # batch spec (ops/dispatch.py batch_axes) and the seq dims of every
    # buffer shard over `context`
    seq_ax = AXIS_CONTEXT if mesh.shape[AXIS_CONTEXT] > 1 else None
    Rg = R // (Pn * V)
    B, S, D = x.shape
    # default M: one microbatch per HOP (depth = V*Pn) so the circular
    # schedule is not born with a majority-garbage tick budget; an
    # explicit n_microbatches below the depth still runs but is warned
    # about once (garbage fraction (depth-1)/(M+depth-1) per the table)
    M = int(n_microbatches) if n_microbatches else V * Pn
    if M < Pn:
        raise ValueError(
            f"pipeline microbatches ({M}) must be >= pipe stages ({Pn})")
    if M < V * Pn:
        _warn_shallow_microbatches(M, V, Pn)
    if B % M != 0:
        raise ValueError(
            f"batch {B} not divisible by {M} pipeline microbatches")
    batch_par = math.prod(mesh.shape[a] for a in BATCH_AXES)
    Bm = B // M
    if Bm % batch_par != 0:
        raise ValueError(
            f"pipeline microbatch size {Bm} (= batch {B} / {M}) must stay "
            f"divisible by the batch-parallel extent {batch_par}; lower "
            f"pipe_microbatches or raise the batch")

    # [R, ...] -> [Rg, V, Pn, ...]: group g = j*Pn + p (hop order ==
    # layer order) owns repeats [g*Rg, (g+1)*Rg). For V=1 the split
    # boundary coincides with the pipe shard boundary so no data moves;
    # for V>1 ownership is non-contiguous and GSPMD regroups the params
    # once per forward (outside the tick scan).
    def to_stages(leaf):
        return jnp.moveaxis(
            leaf.reshape((V, Pn, Rg) + leaf.shape[1:]), 2, 0)

    blocks_r = jax.tree.map(to_stages, params_blocks)
    lora_r = (jax.tree.map(to_stages, lora_blocks)
              if lora_blocks is not None else None)

    if positions is None:
        positions = jnp.broadcast_to(
            jnp.arange(S, dtype=jnp.int32), (B, S))
    if segment_ids is None:
        segment_ids = jnp.ones((B, S), jnp.int32)
    if token_weights is None:
        # all-ones = unweighted router aux (weighted mean == plain mean)
        token_weights = jnp.ones((B, S), jnp.float32)

    # microbatch streams ride the tick scan as xs (static per-iteration
    # slices — a traced dynamic_index over the microbatch dim forces the
    # SPMD partitioner into full rematerialization on reshard); drain
    # ticks replay the last microbatch into slot (0,0) and their outputs
    # are dropped by the static ys window below. Pipeline depth in hops
    # is V*Pn (a microbatch loops the device ring V times).
    depth = V * Pn
    T = M + depth - 1

    def pad_drain(a):
        return jnp.concatenate(
            [a, jnp.broadcast_to(a[-1:], (depth - 1,) + a.shape[1:])])

    xm = _constrain(pad_drain(x.reshape(M, Bm, S, D)), mesh,
                    None, BATCH_AXES, seq_ax, None)
    pm = pad_drain(positions.reshape(M, Bm, S))
    sm = pad_drain(segment_ids.reshape(M, Bm, S))
    wm = pad_drain(token_weights.astype(jnp.float32).reshape(M, Bm, S))

    buf = _constrain(jnp.zeros((V, Pn, Bm, S, D), x.dtype), mesh,
                     None, AXIS_PIPE, BATCH_AXES, seq_ax, None)
    pbuf = jnp.zeros((V, Pn, Bm, S), pm.dtype)
    sbuf = jnp.ones((V, Pn, Bm, S), sm.dtype)
    # weight buffer starts all-zero, nulling WARMUP-slot aux; drain
    # ticks replay real weights (pad_drain), so the tick mask below is
    # load-bearing for them — do not remove it as redundant
    wbuf = jnp.zeros((V, Pn, Bm, S), jnp.float32)

    def shift(b, inj):
        """Advance the (V, Pn) ring one hop: slot (j,p) <- (j,p-1); the
        wrap (j-1, Pn-1) -> (j, 0) re-enters the device ring (device-
        local move: both slots live on device 0's column after the
        roll); slot (0,0) takes the injected microbatch."""
        r = jnp.roll(b, 1, axis=1)         # one-hop collective-permute
        c0 = jnp.roll(r[:, 0], 1, axis=0).at[0].set(inj)
        return r.at[:, 0].set(c0)

    def tick(carry, xs_t):
        buf, pbuf, sbuf, wbuf, aux = carry
        x_in, p_in, s_in, w_in, t = xs_t
        buf = shift(buf, x_in)
        pbuf = shift(pbuf, p_in)
        sbuf = shift(sbuf, s_in)
        wbuf = shift(wbuf, w_in)
        buf = _constrain(buf, mesh, None, AXIS_PIPE, BATCH_AXES, seq_ax,
                         None)
        buf, aux_vec = _virtual_repeats(buf, pbuf, sbuf, wbuf, blocks_r,
                                        lora_r, cfg, impl, dtype, rope,
                                        mesh, lora_scale, seq_ax)
        # MoE router aux: slot (j,p) holds microbatch t - (j*Pn + p) —
        # warmup/drain passes over garbage slots must not contribute.
        # This mask is the sole guard for DRAIN slots (their wbuf holds
        # the replayed last microbatch's real weights)
        mb = t - (jnp.arange(V)[:, None] * Pn + jnp.arange(Pn)[None, :])
        aux = aux + jnp.sum(aux_vec * ((mb >= 0) & (mb < M)))
        # emit the last slot; microbatch m surfaces from (V-1, Pn-1) at
        # tick m + depth-1, so ys[depth-1:] is exactly [0..M) in order
        return (buf, pbuf, sbuf, wbuf, aux), buf[V - 1, Pn - 1]

    (_, _, _, _, aux), ys = jax.lax.scan(
        tick, (buf, pbuf, sbuf, wbuf, jnp.zeros((), jnp.float32)),
        (xm, pm, sm, wm, jnp.arange(T)))
    out = ys[depth - 1:]
    # aux summed over (every layer) x (every microbatch): /M leaves the
    # same sum-over-layers scale the plain path returns (forward then
    # divides by n_layers)
    return out.reshape(B, S, D), aux / M

"""Fused cross-entropy over the (optionally sharded) vocab — Pallas.

The unfused loss path materializes [B, S, V] fp32 logits in HBM (1 GB+
at 8B dims / 128k vocab) just to reduce them to one scalar: unembed
matmul, then ``token_nll``'s logsumexp + target gather. This kernel
never materializes them: the vocab is tiled, each [rows, block_v]
logits tile lives only in VMEM, and the row statistics are carried
online — blockwise max / logsumexp with the label gather INSIDE the
kernel (a tile contributes the target logit iff the label falls in its
column range). Value AND grad: the backward recomputes the logits tile
by tile and accumulates ``dx`` / ``dhead`` without the [B, S, V]
intermediate either (two more kernels, the flash dq/dkv split).

Sharded vocab: under a mesh the wrapper runs per device on the local
vocab shard and combines the per-shard row statistics with one
``pmax``/``psum`` pair (exact online-logsumexp merge; the target logit
lives in exactly one shard, the rest contribute zero).

Block sizes route through ``flash_attention.pick_block`` and the VMEM
footprint through :func:`estimate_vmem_bytes` (kernelcheck
KER001/KER002 — same helpers as flash, no hard-coded tiles).

Numerics: blockwise logsumexp accumulates in a different order than the
full-row ``jax.scipy.special.logsumexp``, so the fused loss is
oracle-pinned in ``tests/tolerances/fused_cross_entropy.json``, NOT
bitwise vs ``token_nll`` — which is why ``FUSED_OPS`` is its own plan
knob and the overlap A/B runs with it fixed on both arms.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from gke_ray_train_tpu.ops.attention import NEG_INF
from gke_ray_train_tpu.ops.flash_attention import (
    FUSED_VMEM_LIMIT_BYTES, _block_env, interpret_default, pick_block)
from gke_ray_train_tpu.parallel.mesh import AXIS_CONTEXT, BATCH_AXES


DEFAULT_BLOCK_R = _block_env("FUSED_CE_BLOCK_R", 256)    # rows per step
DEFAULT_BLOCK_V = _block_env("FUSED_CE_BLOCK_V", 2048)   # vocab per step


def estimate_vmem_bytes(block_r: int, block_v: int, d_model: int,
                        dtype_bytes: int) -> int:
    """Static VMEM footprint of one fused-CE grid step (KER002):
    double-buffered I/O blocks (x rows, head tile, the int32 labels and
    fp32 row outputs) plus the fp32 logits tile + row statistics."""
    io = (block_r * d_model * dtype_bytes        # x rows
          + d_model * block_v * dtype_bytes      # head tile
          + block_r * 4                          # targets (int32)
          + 2 * block_r * 4)                     # lse + tgt rows (fp32)
    scratch = (block_r * block_v * 4             # logits tile (fp32)
               + 3 * block_r * 128 * 4)          # m / l / t accumulators
    return 2 * io + scratch


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _fwd_kernel(t_ref, x_ref, h_ref, lse_ref, tgt_ref, m_s, l_s, t_s, *,
                block_v, n_v):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        t_s[:] = jnp.zeros_like(t_s)

    logits = jax.lax.dot_general(
        x_ref[0].astype(jnp.float32), h_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)          # [br, bv]
    cols = j * block_v + jax.lax.broadcasted_iota(
        jnp.int32, logits.shape, 1)
    tgt = t_ref[0]

    m_prev = m_s[:, 0]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1))
    p = jnp.exp(logits - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_s[:, 0] * alpha + jnp.sum(p, axis=-1)
    # label gather: the target column lands in exactly one vocab tile
    t_new = t_s[:, 0] + jnp.sum(
        jnp.where(cols == tgt[:, None], logits, 0.0), axis=-1)
    m_s[:] = jnp.broadcast_to(m_new[:, None], m_s.shape)
    l_s[:] = jnp.broadcast_to(l_new[:, None], l_s.shape)
    t_s[:] = jnp.broadcast_to(t_new[:, None], t_s.shape)

    @pl.when(j == n_v - 1)
    def _():
        lse_ref[0] = m_s[:, 0] + jnp.log(l_s[:, 0])
        tgt_ref[0] = t_s[:, 0]


def _dx_kernel(t_ref, wg_ref, lse_ref, x_ref, h_ref, dx_ref, dx_acc, *,
               block_v, n_v):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        dx_acc[:] = jnp.zeros_like(dx_acc)

    h = h_ref[...]
    logits = jax.lax.dot_general(
        x_ref[0].astype(jnp.float32), h.astype(jnp.float32),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    cols = j * block_v + jax.lax.broadcasted_iota(
        jnp.int32, logits.shape, 1)
    p = jnp.exp(logits - lse_ref[0][:, None])
    dl = (p - (cols == t_ref[0][:, None]).astype(jnp.float32)) \
        * wg_ref[0][:, None]
    dx_acc[:] += jax.lax.dot_general(
        dl, h.astype(jnp.float32), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(j == n_v - 1)
    def _():
        dx_ref[0] = dx_acc[:].astype(dx_ref.dtype)


def _dhead_kernel(t_ref, wg_ref, lse_ref, x_ref, h_ref, dh_ref, dh_acc, *,
                  block_v, n_r):
    i = pl.program_id(2)
    j = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        dh_acc[:] = jnp.zeros_like(dh_acc)

    x = x_ref[0].astype(jnp.float32)
    logits = jax.lax.dot_general(
        x, h_ref[...].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    cols = j * block_v + jax.lax.broadcasted_iota(
        jnp.int32, logits.shape, 1)
    p = jnp.exp(logits - lse_ref[0][:, None])
    dl = (p - (cols == t_ref[0][:, None]).astype(jnp.float32)) \
        * wg_ref[0][:, None]
    dh_acc[:] += jax.lax.dot_general(
        x, dl, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(i == n_r - 1)
    def _():
        dh_ref[...] = dh_acc[:].astype(dh_ref.dtype)


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

def _row_stats(x, head, targets, *, block_r, block_v, interpret):
    """Per-row (lse, target-logit) without materializing logits.
    x: [N, D]; head: [D, V]; targets: [N]."""
    N, D = x.shape
    V = head.shape[1]
    br = pick_block(block_r, N)
    bv = pick_block(block_v, V)
    n_v = V // bv
    grid = (1, N // br, n_v)
    kernel = functools.partial(_fwd_kernel, block_v=bv, n_v=n_v)
    lse, tgt = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, br), lambda b, i, j: (0, i)),
            pl.BlockSpec((1, br, D), lambda b, i, j: (0, i, 0)),
            pl.BlockSpec((D, bv), lambda b, i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, br), lambda b, i, j: (0, i)),
            pl.BlockSpec((1, br), lambda b, i, j: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, N), jnp.float32),
            jax.ShapeDtypeStruct((1, N), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((br, 128), jnp.float32),
            pltpu.VMEM((br, 128), jnp.float32),
            pltpu.VMEM((br, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=FUSED_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="fused_ce_fwd",
    )(targets.astype(jnp.int32)[None, :], x[None], head)
    return lse[0], tgt[0]


def _grads(x, head, targets, wg, lse, *, block_r, block_v, interpret):
    """(dx, dhead) tile by tile. wg: per-row weight x upstream cotangent."""
    N, D = x.shape
    V = head.shape[1]
    br = pick_block(block_r, N)
    bv = pick_block(block_v, V)
    n_v, n_r = V // bv, N // br
    t2 = targets.astype(jnp.int32)[None, :]
    wg2 = wg.astype(jnp.float32)[None, :]
    lse2 = lse[None, :]

    dx = pl.pallas_call(
        functools.partial(_dx_kernel, block_v=bv, n_v=n_v),
        grid=(1, n_r, n_v),
        in_specs=[
            pl.BlockSpec((1, br), lambda b, i, j: (0, i)),
            pl.BlockSpec((1, br), lambda b, i, j: (0, i)),
            pl.BlockSpec((1, br), lambda b, i, j: (0, i)),
            pl.BlockSpec((1, br, D), lambda b, i, j: (0, i, 0)),
            pl.BlockSpec((D, bv), lambda b, i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, br, D), lambda b, i, j: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((1, N, D), x.dtype),
        scratch_shapes=[pltpu.VMEM((br, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=FUSED_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="fused_ce_dx",
    )(t2, wg2, lse2, x[None], head)[0]

    dhead = pl.pallas_call(
        functools.partial(_dhead_kernel, block_v=bv, n_r=n_r),
        grid=(n_v, 1, n_r),
        in_specs=[
            pl.BlockSpec((1, br), lambda j, b, i: (0, i)),
            pl.BlockSpec((1, br), lambda j, b, i: (0, i)),
            pl.BlockSpec((1, br), lambda j, b, i: (0, i)),
            pl.BlockSpec((1, br, D), lambda j, b, i: (0, i, 0)),
            pl.BlockSpec((D, bv), lambda j, b, i: (0, j)),
        ],
        out_specs=pl.BlockSpec((D, bv), lambda j, b, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((D, V), head.dtype),
        scratch_shapes=[pltpu.VMEM((D, bv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=FUSED_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="fused_ce_dhead",
    )(t2, wg2, lse2, x[None], head)
    return dx, dhead


def fused_cross_entropy(x: jnp.ndarray, head: jnp.ndarray,
                        targets: jnp.ndarray, weights: jnp.ndarray, *,
                        block_r: int = DEFAULT_BLOCK_R,
                        block_v: int = DEFAULT_BLOCK_V,
                        interpret: Optional[bool] = None,
                        vocab_axis: Optional[str] = None,
                        mesh=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(weighted nll sum, weight sum) — ``token_nll`` semantics, logits
    never materialized. x: [B, S, D] final-normed hidden; head: [D, V];
    targets/weights: [B, S].

    ``vocab_axis``: mesh axis name sharding V when called INSIDE a
    manual/shard_map region — the per-shard row stats are merged with
    one exact online-logsumexp pmax/psum pair. ``mesh``: wrap in
    shard_map here (the GSPMD call site), sharding rows over the batch
    axes and V over ``model``."""
    interpret = interpret_default(interpret)
    kw = dict(block_r=block_r, block_v=block_v, interpret=interpret)

    def local(x, head, targets, weights, axis):
        B, S, D = x.shape
        xf = x.reshape(B * S, D)
        tf = targets.reshape(-1)
        if axis is not None:
            # targets are GLOBAL vocab ids; the kernel's column iota is
            # local to this shard's head slice — shift the labels into
            # local coordinates (off-shard labels land out of range and
            # match no tile, which is exactly the "contributes 0" the
            # psum merge relies on)
            tf = tf - jax.lax.axis_index(axis) * head.shape[1]
        wf = weights.reshape(-1).astype(jnp.float32)

        def merge(lse, tgt):
            if axis is None:
                return lse, tgt
            # exact online merge across vocab shards: the target logit
            # lives in exactly one shard (the rest contribute 0)
            m = jax.lax.pmax(lse, axis)
            lse = m + jnp.log(jax.lax.psum(jnp.exp(lse - m), axis))
            return lse, jax.lax.psum(tgt, axis)

        # tf/wf ride as custom_vjp ARGS (None cotangents), never
        # closures — closing over tracers would leak them across the
        # fwd/bwd trace boundary under remat (the flash kernel's rule)
        @jax.custom_vjp
        def ce(xf, head, tf, wf):
            lse, tgt = _row_stats(xf, head, tf, **kw)
            lse, tgt = merge(lse, tgt)
            return jnp.sum((lse - tgt) * wf), jnp.sum(wf)

        def fwd(xf, head, tf, wf):
            lse, tgt = _row_stats(xf, head, tf, **kw)
            lse, tgt = merge(lse, tgt)
            out = (jnp.sum((lse - tgt) * wf), jnp.sum(wf))
            return out, (xf, head, tf, wf, lse)

        def bwd(res, ct):
            xf, head, tf, wf, lse = res
            dx, dhead = _grads(xf, head, tf, wf * ct[0], lse, **kw)
            if axis is not None:
                # dx contracts over the vocab dim — partial per shard
                dx = jax.lax.psum(dx, axis)
            return (dx.reshape(B * S, D).astype(xf.dtype),
                    dhead.astype(head.dtype), None, None)

        ce.defvjp(fwd, bwd)
        return ce(xf, head, tf, wf)

    if mesh is None:
        return local(x, head, targets, weights, vocab_axis)

    # Mesh path: the custom_vjp sits OUTSIDE the shard_map and both
    # passes are explicit primal shard_maps — relying on shard_map's
    # AD transpose for replicated operands (the head is replicated
    # over data/fsdp) under check_vma=False mis-scales the cotangent.
    v_axis = "model" if int(mesh.shape.get("model", 1)) > 1 else None
    sum_axes = tuple(a for a in (*BATCH_AXES, AXIS_CONTEXT)
                     if int(mesh.shape.get(a, 1)) > 1)
    row_spec = P(BATCH_AXES, AXIS_CONTEXT)
    x_spec = P(BATCH_AXES, AXIS_CONTEXT, None)
    head_spec = P(None, "model")

    def shift(targets, head):
        tf = targets.reshape(-1)
        if v_axis is not None:
            tf = tf - jax.lax.axis_index(v_axis) * head.shape[1]
        return tf

    def merge(lse, tgt):
        if v_axis is None:
            return lse, tgt
        m = jax.lax.pmax(lse, v_axis)
        lse = m + jnp.log(jax.lax.psum(jnp.exp(lse - m), v_axis))
        return lse, jax.lax.psum(tgt, v_axis)

    def fwd_local(x, head, targets, weights):
        Bl, Sl, D = x.shape
        xf = x.reshape(Bl * Sl, D)
        wf = weights.reshape(-1).astype(jnp.float32)
        lse, tgt = _row_stats(xf, head, shift(targets, head),
                              block_r=block_r, block_v=block_v,
                              interpret=interpret)
        lse, tgt = merge(lse, tgt)
        nll = jnp.sum((lse - tgt) * wf)
        w = jnp.sum(wf)
        if sum_axes:
            nll = jax.lax.psum(nll, sum_axes)
            w = jax.lax.psum(w, sum_axes)
        return nll, w, lse.reshape(Bl, Sl)

    def bwd_local(x, head, targets, wg, lse):
        Bl, Sl, D = x.shape
        dx, dh = _grads(x.reshape(Bl * Sl, D), head,
                        shift(targets, head), wg.reshape(-1),
                        lse.reshape(-1), block_r=block_r,
                        block_v=block_v, interpret=interpret)
        if v_axis is not None:
            dx = jax.lax.psum(dx, v_axis)     # contracts over vocab
        if sum_axes:
            dh = jax.lax.psum(dh, sum_axes)   # sums over batch rows
        return dx.reshape(Bl, Sl, D), dh

    smapped_fwd = shard_map(
        fwd_local, mesh=mesh,
        in_specs=(x_spec, head_spec, row_spec, row_spec),
        out_specs=(P(), P(), row_spec), check_vma=False)
    smapped_bwd = shard_map(
        bwd_local, mesh=mesh,
        in_specs=(x_spec, head_spec, row_spec, row_spec, row_spec),
        out_specs=(x_spec, head_spec), check_vma=False)

    @jax.custom_vjp
    def ce(x, head, targets, weights):
        nll, w, _ = smapped_fwd(x, head, targets, weights)
        return nll, w

    def fwd(x, head, targets, weights):
        nll, w, lse = smapped_fwd(x, head, targets, weights)
        return (nll, w), (x, head, targets, weights, lse)

    def bwd(res, ct):
        x, head, targets, weights, lse = res
        dx, dh = smapped_bwd(x, head, targets,
                             weights.astype(jnp.float32) * ct[0], lse)
        return dx.astype(x.dtype), dh.astype(head.dtype), None, None

    ce.defvjp(fwd, bwd)
    return ce(x, head, targets, weights)

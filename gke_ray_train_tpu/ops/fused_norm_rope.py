"""Fused RMSNorm / RoPE Pallas kernels — the pre-attention epilogue.

The per-layer epilogue around the QKV projection is memory-bound: an
rms_norm dispatch on the [B, S, D] hidden state, then two separate
rope dispatches on the projected q and k. Each costs a full HBM
round-trip for arrays that never feed the MXU between loads. These
kernels collapse them (plan knob ``FUSED_OPS``):

- :func:`fused_rmsnorm` — rms_norm in one ``pallas_call``: x is read
  once per block, the fp32 variance + scale apply happen in VMEM, the
  result is written once;
- :func:`fused_rope_qk` — q AND k rotated in ONE kernel launch (the
  cos/sin tables are computed once per block and shared by both heads'
  rotations, replacing the two ``ops/rope.py`` dispatches);
- :func:`fused_rmsnorm_rope` — the fully fused composition (norm over
  head_dim, then rotate) in a single VMEM round-trip — the qk-norm
  epilogue shape (Gemma-3/Qwen-3 style); registered as the composed
  differential case even though the shipped model families norm the
  hidden state, not the heads.

Block sizes route through ``flash_attention.pick_block`` and the VMEM
footprint through :func:`estimate_vmem_bytes`, so kernelcheck's
KER001/KER002 lint the tiling the same way it lints flash — no
hard-coded tiles.

Numerics: the kernels execute the same fp32 op sequence as the XLA
references (``ops/norms.py`` / ``ops/rope.py``); the differential
contract (value + grad vs those oracles) is pinned in
``tests/tolerances/fused_norm_rope.json``. Backward: rope's VJP is the
same kernel with negated frequencies (a rotation's transpose is the
inverse rotation); rms_norm's VJP is the closed-form jnp expression —
the memory-bound win this module targets is the forward epilogue, and
XLA already fuses the backward chain into the surrounding elementwise
graph.
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

from gke_ray_train_tpu.ops.flash_attention import (
    FUSED_VMEM_LIMIT_BYTES, _block_env, interpret_default, pick_block)
from gke_ray_train_tpu.parallel.mesh import AXIS_CONTEXT, BATCH_AXES


# rows (sequence positions) per grid step; env override mirrors
# FLASH_BLOCK_* (re-validated by pick_block at every call site)
DEFAULT_BLOCK_S = _block_env("FUSED_BLOCK_S", 256)


def estimate_vmem_bytes(block_s: int, width: int, dtype_bytes: int) -> int:
    """Static VMEM footprint of one fused-epilogue grid step — the
    KER002 number. Counts the double-buffered I/O blocks (input + output
    rows of ``width`` elements, the int32 position row, the fp32
    frequency row) plus the fp32 compute scratch."""
    io = (2 * block_s * width * dtype_bytes     # x in, y out
          + block_s * 4                          # positions (int32)
          + width * 4)                           # freqs / scale (fp32)
    scratch = block_s * width * 4                # fp32 working copy
    return 2 * io + scratch


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _norm_block(x32, scale32, *, eps, scale_plus_one):
    """The exact op sequence of ops/norms.py::rms_norm, fp32 in VMEM."""
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    s = scale32
    if scale_plus_one:
        s = 1.0 + s
    return y * s


def _rot_block(x32, pos, freqs):
    """The exact op sequence of ops/rope.py::apply_rope, fp32 in VMEM.
    x32: [bs, H, dh]; pos: [bs]; freqs: [dh // 2]."""
    angles = pos[:, None].astype(jnp.float32) * freqs    # [bs, dh/2]
    cos = jnp.cos(angles)[:, None, :]
    sin = jnp.sin(angles)[:, None, :]
    half = x32.shape[-1] // 2
    x1, x2 = x32[..., :half], x32[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _rmsnorm_kernel(x_ref, s_ref, o_ref, *, eps, scale_plus_one):
    x32 = x_ref[0].astype(jnp.float32)
    y = _norm_block(x32, s_ref[0].astype(jnp.float32),
                    eps=eps, scale_plus_one=scale_plus_one)
    o_ref[0] = y.astype(o_ref.dtype)


def _rope_qk_kernel(pos_ref, f_ref, q_ref, k_ref, oq_ref, ok_ref):
    pos = pos_ref[0, 0]
    freqs = f_ref[0]
    oq_ref[0] = _rot_block(q_ref[0].astype(jnp.float32), pos, freqs
                           ).astype(oq_ref.dtype)
    ok_ref[0] = _rot_block(k_ref[0].astype(jnp.float32), pos, freqs
                           ).astype(ok_ref.dtype)


def _rmsnorm_rope_kernel(pos_ref, f_ref, s_ref, x_ref, o_ref, *,
                         eps, scale_plus_one):
    x32 = x_ref[0].astype(jnp.float32)
    y = _norm_block(x32, s_ref[0].astype(jnp.float32),
                    eps=eps, scale_plus_one=scale_plus_one)
    o_ref[0] = _rot_block(y, pos_ref[0, 0], f_ref[0]).astype(o_ref.dtype)


# ---------------------------------------------------------------------------
# public entries
# ---------------------------------------------------------------------------

def _row_grid(B: int, S: int, block_s: int) -> Tuple[Tuple[int, int], int]:
    bs = pick_block(block_s, S)
    return (B, S // bs), bs


def fused_rmsnorm(x: jnp.ndarray, scale: jnp.ndarray, *,
                  eps: float = 1e-5, scale_plus_one: bool = False,
                  block_s: int = DEFAULT_BLOCK_S,
                  interpret: Optional[bool] = None,
                  mesh=None) -> jnp.ndarray:
    """rms_norm(x, scale) in one Pallas pass. x: [B, S, D]; scale: [D].
    Under a mesh the kernel runs per device on the local batch/sequence
    rows via shard_map (D is never sharded for activations)."""
    interpret = interpret_default(interpret)

    def local(x, scale):
        B, S, D = x.shape
        grid, bs = _row_grid(B, S, block_s)
        kernel = functools.partial(_rmsnorm_kernel, eps=eps,
                                   scale_plus_one=scale_plus_one)

        @jax.custom_vjp
        def norm(x, scale):
            return pl.pallas_call(
                kernel,
                grid=grid,
                in_specs=[
                    pl.BlockSpec((1, bs, D), lambda b, i: (b, i, 0)),
                    pl.BlockSpec((1, D), lambda b, i: (0, 0)),
                ],
                out_specs=pl.BlockSpec((1, bs, D), lambda b, i: (b, i, 0)),
                out_shape=jax.ShapeDtypeStruct((B, S, D), x.dtype),
                compiler_params=pltpu.CompilerParams(
                    vmem_limit_bytes=FUSED_VMEM_LIMIT_BYTES),
                interpret=interpret,
                name="fused_rmsnorm",
            )(x, scale[None, :])

        def fwd(x, scale):
            return norm(x, scale), (x, scale)

        def bwd(res, g):
            x, scale = res
            x32 = x.astype(jnp.float32)
            g32 = g.astype(jnp.float32)
            s = scale.astype(jnp.float32)
            if scale_plus_one:
                s = 1.0 + s
            var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
            r = jax.lax.rsqrt(var + eps)
            y = x32 * r
            gy = g32 * s
            # d rms_norm: r * (gy - y * mean(gy * y))
            dx = r * (gy - y * jnp.mean(gy * y, axis=-1, keepdims=True))
            dscale = jnp.sum(g32 * y, axis=tuple(range(x.ndim - 1)))
            return dx.astype(x.dtype), dscale.astype(scale.dtype)

        norm.defvjp(fwd, bwd)
        return norm(x, scale)

    if mesh is None:
        return local(x, scale)
    return shard_map(local, mesh=mesh,
                     in_specs=(P(BATCH_AXES, AXIS_CONTEXT, None), P(None)),
                     out_specs=P(BATCH_AXES, AXIS_CONTEXT, None),
                     check_vma=False)(x, scale)


def fused_rope_qk(q: jnp.ndarray, k: jnp.ndarray, positions: jnp.ndarray,
                  inv_freqs: jnp.ndarray, *,
                  block_s: int = DEFAULT_BLOCK_S,
                  interpret: Optional[bool] = None,
                  mesh=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """RoPE on q [B, S, H, dh] AND k [B, S, K, dh] in one kernel launch
    (one cos/sin table per block, shared by both rotations). The VJP is
    the same kernel with negated frequencies — the rotation transpose."""
    interpret = interpret_default(interpret)

    def local(q, k, positions, inv_freqs):
        B, S, H, dh = q.shape
        K = k.shape[2]
        grid, bs = _row_grid(B, S, block_s)

        def call(q, k, positions, freqs):
            return pl.pallas_call(
                _rope_qk_kernel,
                grid=grid,
                in_specs=[
                    # [B, 1, S] positions: a (1, 1, bs) block is
                    # Mosaic-legal where (1, bs) of [B, S] is not (the
                    # flash kernel's layout, for the same reason)
                    pl.BlockSpec((1, 1, bs), lambda b, i: (b, 0, i)),
                    pl.BlockSpec((1, dh // 2), lambda b, i: (0, 0)),
                    pl.BlockSpec((1, bs, H, dh), lambda b, i: (b, i, 0, 0)),
                    pl.BlockSpec((1, bs, K, dh), lambda b, i: (b, i, 0, 0)),
                ],
                out_specs=[
                    pl.BlockSpec((1, bs, H, dh), lambda b, i: (b, i, 0, 0)),
                    pl.BlockSpec((1, bs, K, dh), lambda b, i: (b, i, 0, 0)),
                ],
                out_shape=[
                    jax.ShapeDtypeStruct((B, S, H, dh), q.dtype),
                    jax.ShapeDtypeStruct((B, S, K, dh), k.dtype),
                ],
                compiler_params=pltpu.CompilerParams(
                    vmem_limit_bytes=FUSED_VMEM_LIMIT_BYTES),
                interpret=interpret,
                name="fused_rope_qk",
            )(positions.astype(jnp.int32)[:, None, :], freqs[None, :],
              q, k)

        # positions/freqs ride as custom_vjp ARGS (None cotangents) —
        # closing over tracers would leak them across the fwd/bwd
        # trace boundary under the scan+remat the block stack runs in
        @jax.custom_vjp
        def rot(q, k, positions, inv_freqs):
            return tuple(call(q, k, positions, inv_freqs))

        def fwd(q, k, positions, inv_freqs):
            return rot(q, k, positions, inv_freqs), (positions, inv_freqs)

        def bwd(res, ct):
            positions, inv_freqs = res
            gq, gk = ct
            # the rotation transpose is the inverse rotation
            dq, dk = call(gq, gk, positions, -inv_freqs)
            return dq, dk, None, None

        rot.defvjp(fwd, bwd)
        return rot(q, k, positions, inv_freqs)

    if mesh is None:
        return local(q, k, positions, inv_freqs)
    head_spec = P(BATCH_AXES, AXIS_CONTEXT, "model", None)
    return shard_map(
        local, mesh=mesh,
        in_specs=(head_spec, head_spec, P(BATCH_AXES, AXIS_CONTEXT),
                  P(None)),
        out_specs=(head_spec, head_spec), check_vma=False,
    )(q, k, positions, inv_freqs)


def fused_rmsnorm_rope(x: jnp.ndarray, scale: jnp.ndarray,
                       positions: jnp.ndarray, inv_freqs: jnp.ndarray, *,
                       eps: float = 1e-5, scale_plus_one: bool = False,
                       block_s: int = DEFAULT_BLOCK_S,
                       interpret: Optional[bool] = None) -> jnp.ndarray:
    """The fully fused composition: per-head rms_norm (over head_dim)
    then RoPE, one VMEM round-trip. x: [B, S, H, dh]; scale: [dh].
    The qk-norm epilogue shape; the registry's composed differential
    case. VJP: closed-form norm backward after the inverse rotation."""
    interpret = interpret_default(interpret)
    B, S, H, dh = x.shape
    grid, bs = _row_grid(B, S, block_s)
    kernel = functools.partial(_rmsnorm_rope_kernel, eps=eps,
                               scale_plus_one=scale_plus_one)

    def call(x, scale, positions, inv_freqs):
        return pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, bs), lambda b, i: (b, 0, i)),
                pl.BlockSpec((1, dh // 2), lambda b, i: (0, 0)),
                pl.BlockSpec((1, dh), lambda b, i: (0, 0)),
                pl.BlockSpec((1, bs, H, dh), lambda b, i: (b, i, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, bs, H, dh),
                                   lambda b, i: (b, i, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((B, S, H, dh), x.dtype),
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=FUSED_VMEM_LIMIT_BYTES),
            interpret=interpret,
            name="fused_rmsnorm_rope",
        )(positions.astype(jnp.int32)[:, None, :], inv_freqs[None, :],
          scale[None, :], x)

    @jax.custom_vjp
    def nr(x, scale, positions, inv_freqs):
        return call(x, scale, positions, inv_freqs)

    def fwd(x, scale, positions, inv_freqs):
        return (nr(x, scale, positions, inv_freqs),
                (x, scale, positions, inv_freqs))

    def bwd(res, g):
        x, scale, positions, inv_freqs = res
        # un-rotate the cotangent (rotation transpose = inverse
        # rotation), then the closed-form rms_norm backward
        angles = positions[..., :, None].astype(jnp.float32) * inv_freqs
        cos = jnp.cos(angles)[..., None, :]
        sin = jnp.sin(angles)[..., None, :]
        g32 = g.astype(jnp.float32)
        half = dh // 2
        g1, g2 = g32[..., :half], g32[..., half:]
        gy = jnp.concatenate([g1 * cos + g2 * sin,
                              g2 * cos - g1 * sin], axis=-1)
        x32 = x.astype(jnp.float32)
        s = scale.astype(jnp.float32)
        if scale_plus_one:
            s = 1.0 + s
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        r = jax.lax.rsqrt(var + eps)
        y = x32 * r
        gys = gy * s
        dx = r * (gys - y * jnp.mean(gys * y, axis=-1, keepdims=True))
        dscale = jnp.sum(gy * y, axis=tuple(range(x.ndim - 1)))
        return dx.astype(x.dtype), dscale.astype(scale.dtype), None, None

    nr.defvjp(fwd, bwd)
    return nr(x, scale, positions, inv_freqs)

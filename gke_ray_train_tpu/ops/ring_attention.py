"""Ring attention — context-parallel flash attention over the ``context``
mesh axis (SURVEY.md §5.7, §2c row SP/CP).

The reference has no long-context story (max seq 1024, dense O(L²) masks
— ray-jobs/pytorch_llm_ray.py:91-99, fine_tune_config.json:27). This is
the TPU-native subsystem that replaces it: queries stay put, K/V shards
rotate around the ring of context-axis devices via ``lax.ppermute``
(XLA collective-permute rides ICI neighbor links), and each device
merges per-shard flash-attention partials with an online logsumexp — so
attention memory stays O(S·S/C) per device and sequence length scales
with the mesh.

Structure: one ``shard_map`` over the mesh; inside, a single custom_vjp
wraps the whole ring —
- forward: C steps of the Pallas flash kernel (ops/flash_attention._fwd)
  on the local queries vs the visiting K/V shard, merged via logaddexp;
- backward: a second ring reusing the flash backward kernels
  (ops/flash_attention._bwd) with the *final* lse: per-shard dq
  accumulates locally, dk/dv accumulate on the rotating buffers and land
  back on their owner after the full circle. Positions + segment IDs
  travel with the K/V shards, so causal/packed masking across shard
  boundaries is exact.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from gke_ray_train_tpu.ops import flash_attention as fa
from gke_ray_train_tpu.parallel.mesh import (
    AXIS_CONTEXT, AXIS_MODEL, BATCH_AXES)


def _rotate(x, axis_name, size):
    """Shift a buffer one hop around the ring (device i → i+1)."""
    perm = [(i, (i + 1) % size) for i in range(size)]
    return jax.lax.ppermute(x, axis_name, perm)


def _merge(o_acc, lse_acc, o_i, lse_i):
    """Online logsumexp merge of two normalized partials.

    lse shapes [b, h, 1, s]; o shapes [b, h, s, dh]. Fully-masked rows
    carry lse == NEG_INF (finite), so the exp() weights stay 0/1-ish and
    never NaN.
    """
    lse_new = jnp.logaddexp(lse_acc, lse_i)
    w_acc = jnp.exp(lse_acc - lse_new).swapaxes(-1, -2)
    w_i = jnp.exp(lse_i - lse_new).swapaxes(-1, -2)
    return o_acc * w_acc + o_i * w_i, lse_new


def _local_ring(qt, kt, vt, qp, kp, qs, ks, *, axis_name, size, kw):
    """Per-device ring attention on transposed [b, h, s, dh] shards.

    qp/kp/qs/ks are [b, 1, s] (the layout flash's kernels take).
    """

    @jax.custom_vjp
    def ring(qt, kt, vt, qp, kp, qs, ks):
        out, _ = _ring_fwd_loop(qt, kt, vt, qp, kp, qs, ks)
        return out

    def _ring_fwd_loop(qt, kt, vt, qp, kp, qs, ks):
        # step 0: the local shard, no communication
        o_i, lse = fa._fwd(qt, kt, vt, qp, kp, qs, ks, **kw)
        o = o_i.astype(jnp.float32)

        # steps 1..C-1: rotate first, then attend the visiting shard —
        # exactly C-1 ppermutes (no wasted final hop)
        def body(carry, _):
            o_acc, lse_acc, k_c, v_c, kp_c, ks_c = carry
            k_c, v_c, kp_c, ks_c = (
                _rotate(x, axis_name, size) for x in (k_c, v_c, kp_c, ks_c))
            o_i, lse_i = fa._fwd(qt, k_c, v_c, qp, kp_c, qs, ks_c, **kw)
            o_acc, lse_acc = _merge(o_acc, lse_acc,
                                    o_i.astype(jnp.float32), lse_i)
            return (o_acc, lse_acc, k_c, v_c, kp_c, ks_c), None

        (o, lse, *_), _ = jax.lax.scan(
            body, (o, lse, kt, vt, kp, ks), None, length=size - 1)
        return o.astype(qt.dtype), lse

    def ring_fwd(qt, kt, vt, qp, kp, qs, ks):
        out, lse = _ring_fwd_loop(qt, kt, vt, qp, kp, qs, ks)
        return out, (qt, kt, vt, out, lse, qp, kp, qs, ks)

    def ring_bwd(res, g):
        qt, kt, vt, out, lse, qp, kp, qs, ks = res
        # D_i = rowsum(do * o) is shard-invariant — compute once, not per
        # ring step
        dvec = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                       axis=-1)[:, :, None, :]

        # flash backward vs a visiting shard, with the FINAL lse:
        # p_i = exp(s_i - lse) is exactly that shard's slice of the
        # global softmax, so per-shard grads sum to the exact total.
        def shard_grads(k_c, v_c, kp_c, ks_c):
            return fa._bwd((qt, k_c, v_c, out, lse, qp, kp_c, qs, ks_c),
                           g, dvec=dvec, **kw)

        # step 0: local shard
        dq_i, dk_i, dv_i = shard_grads(kt, vt, kp, ks)
        dq = dq_i.astype(jnp.float32)
        dk = dk_i.astype(jnp.float32)
        dv = dv_i.astype(jnp.float32)

        # steps 1..C-1: rotate the kv shard AND its grad accumulators
        # together, then accumulate the visiting shard's grads
        def body(carry, _):
            dq_acc, k_c, v_c, kp_c, ks_c, dk_c, dv_c = carry
            k_c, v_c, kp_c, ks_c, dk_c, dv_c = (
                _rotate(x, axis_name, size)
                for x in (k_c, v_c, kp_c, ks_c, dk_c, dv_c))
            dq_i, dk_i, dv_i = shard_grads(k_c, v_c, kp_c, ks_c)
            dq_acc = dq_acc + dq_i.astype(jnp.float32)
            dk_c = dk_c + dk_i.astype(jnp.float32)
            dv_c = dv_c + dv_i.astype(jnp.float32)
            return (dq_acc, k_c, v_c, kp_c, ks_c, dk_c, dv_c), None

        (dq, _, _, _, _, dk, dv), _ = jax.lax.scan(
            body, (dq, kt, vt, kp, ks, dk, dv), None, length=size - 1)
        # dk/dv have rotated C-1 hops from their owner — one final hop
        # completes the circle home
        if size > 1:
            dk = _rotate(dk, axis_name, size)
            dv = _rotate(dv, axis_name, size)
        return (dq.astype(qt.dtype), dk.astype(kt.dtype),
                dv.astype(vt.dtype), None, None, None, None)

    ring.defvjp(ring_fwd, ring_bwd)
    return ring(qt, kt, vt, qp, kp, qs, ks)


def ring_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                   mesh, q_positions=None, kv_positions=None,
                   q_segment_ids=None, kv_segment_ids=None,
                   causal: bool = True,
                   sliding_window: Optional[int] = None,
                   scale: Optional[float] = None,
                   logit_softcap: Optional[float] = None,
                   block_q: int = fa.DEFAULT_BLOCK_Q,
                   block_kv: int = fa.DEFAULT_BLOCK_KV,
                   interpret: Optional[bool] = None,
                   batch_axes=BATCH_AXES) -> jnp.ndarray:
    """Context-parallel attention; q [B, S, H, dh], k/v [B, S, K, dh]
    sharded over (batch: data x fsdp, seq: context, heads: model).

    S here is the GLOBAL sequence length; each device sees S/C locally.
    Positions default to arange(S) (sharded alongside), so causality and
    packing masks are exact across shard boundaries.
    """
    if mesh is None:
        raise ValueError("ring attention needs a mesh with a context axis")
    B, S, H, dh = q.shape
    interpret = fa.interpret_default(interpret)

    if q_positions is None:
        q_positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32),
                                       (B, S))
    if kv_positions is None:
        kv_positions = q_positions
    if q_segment_ids is None:
        q_segment_ids = jnp.ones((B, S), jnp.int32)
    if kv_segment_ids is None:
        kv_segment_ids = q_segment_ids

    size = mesh.shape[AXIS_CONTEXT]
    C = size
    if S % C:
        raise ValueError(f"global seq len {S} not divisible by context "
                         f"axis size {C}")
    S_local = S // C
    # divisor-safe blocks: a non-divisor block would leave tail query
    # rows unwritten by the Pallas grid (silent garbage)
    block_q = fa.pick_block(block_q, S_local)
    block_kv = fa.pick_block(block_kv, S_local)
    kw = dict(scale=dh ** -0.5 if scale is None else scale, causal=causal,
              window=sliding_window, softcap=logit_softcap,
              block_q=block_q, block_kv=block_kv, interpret=interpret)

    def local(q, k, v, qp, kp, qs, ks):
        qt = q.transpose(0, 2, 1, 3)
        kt = k.transpose(0, 2, 1, 3)
        vt = v.transpose(0, 2, 1, 3)
        out = _local_ring(
            qt, kt, vt,
            qp.astype(jnp.int32)[:, None, :],
            kp.astype(jnp.int32)[:, None, :],
            qs.astype(jnp.int32)[:, None, :],
            ks.astype(jnp.int32)[:, None, :],
            axis_name=AXIS_CONTEXT, size=C, kw=kw)
        return out.transpose(0, 2, 1, 3)

    # batch_axes: (data, fsdp) normally; (pipe, data, fsdp) for the
    # pipeline path's stage-folded batch (models/pipeline.py)
    qkv_spec = P(batch_axes, AXIS_CONTEXT, AXIS_MODEL, None)
    vec_spec = P(batch_axes, AXIS_CONTEXT)
    return shard_map(
        local, mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec,
                  vec_spec, vec_spec, vec_spec, vec_spec),
        out_specs=qkv_spec, check_vma=False,
    )(q, k, v, q_positions, kv_positions, q_segment_ids, kv_segment_ids)

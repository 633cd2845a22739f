"""Attention implementation dispatch (cfg.attn_impl).

"xla" is handled inline in the transformer (dense mask oracle); this
module routes the accelerated paths — "flash" (Pallas kernel), "ring"
(context-parallel flash, K/V rotation) and "a2a" (Ulysses-style
all-to-all context parallelism) — so the model code never imports
kernels directly. All take mask *inputs* (positions, segment ids,
causality, window) rather than a materialized [S, T] mask: never
building that mask in HBM is the point of the kernels.

Sharding: a ``pallas_call`` is a custom call GSPMD cannot partition, so
under a mesh the flash kernel is wrapped in ``shard_map`` — each device
runs the kernel on its local (batch x head) shard. That is correct only
while the sequence axis is unsharded; a context-sharded mesh must use a
sequence-parallel strategy — "ring" (K/V blocks rotate around the
context axis) or "a2a" (all-to-all head/sequence redistribution, which
falls back to ring when the context axis cannot divide the local head
counts).
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from gke_ray_train_tpu.parallel.mesh import (
    AXIS_CONTEXT, AXIS_MODEL, BATCH_AXES)


def _flash_sharded(q, k, v, q_positions, kv_positions, q_segment_ids,
                   kv_segment_ids, *, mesh, causal, sliding_window, scale,
                   logit_softcap, interpret, rows_ordered,
                   batch_axes=BATCH_AXES):
    from gke_ray_train_tpu.ops.flash_attention import flash_attention

    def local(q, k, v, qp, kp, qs, ks):
        return flash_attention(
            q, k, v, q_positions=qp, kv_positions=kp, q_segment_ids=qs,
            kv_segment_ids=ks, causal=causal,
            sliding_window=sliding_window, scale=scale,
            logit_softcap=logit_softcap, rows_ordered=rows_ordered,
            interpret=interpret)

    if mesh is None:
        return local(q, k, v, q_positions, kv_positions, q_segment_ids,
                     kv_segment_ids)

    if mesh.shape[AXIS_CONTEXT] > 1:
        raise ValueError(
            "attn_impl='flash' with a context-sharded mesh would silently "
            "drop cross-shard attention; use attn_impl='ring'")

    qkv_spec = P(batch_axes, None, AXIS_MODEL, None)
    vec_spec = P(batch_axes, None)
    return shard_map(
        local, mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec,
                  vec_spec, vec_spec, vec_spec, vec_spec),
        out_specs=qkv_spec, check_vma=False,
    )(q, k, v, q_positions, kv_positions, q_segment_ids, kv_segment_ids)


def attention_dispatch(impl: str, q, k, v, *,
                       q_positions=None, kv_positions=None,
                       q_segment_ids=None, kv_segment_ids=None,
                       causal: bool = True,
                       sliding_window: Optional[int] = None,
                       scale=None, logit_softcap=None, mesh=None,
                       interpret: Optional[bool] = None,
                       rows_ordered: bool = False,
                       batch_axes=BATCH_AXES) -> jnp.ndarray:
    """``batch_axes``: mesh axes sharding dim 0 of q/k/v — the default is
    the (data, fsdp) batch; the pipeline path passes (pipe, data, fsdp)
    for its stage-folded batch (models/pipeline.py).

    ``rows_ordered``: the caller's statement that q and kv are the same
    rows with positions rising inside each segment; the flash kernels
    then walk only the causal / window band of blocks
    (ops/flash_attention.py::kernel_bands). Ring and all-to-all
    attention see shifted or regrouped kv and keep the full grid."""
    B, S = q.shape[:2]
    T = k.shape[1]
    if q_positions is None:
        q_positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32),
                                       (B, S))
    if kv_positions is None:
        kv_positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32),
                                        (B, T))
    if q_segment_ids is None:
        q_segment_ids = jnp.ones((B, S), jnp.int32)
    if kv_segment_ids is None:
        kv_segment_ids = jnp.ones((B, T), jnp.int32)

    if impl == "flash":
        return _flash_sharded(
            q, k, v, q_positions, kv_positions, q_segment_ids,
            kv_segment_ids, mesh=mesh, causal=causal,
            sliding_window=sliding_window, scale=scale,
            logit_softcap=logit_softcap, interpret=interpret,
            rows_ordered=rows_ordered, batch_axes=batch_axes)
    if impl == "ring":
        try:
            from gke_ray_train_tpu.ops.ring_attention import ring_attention
        except ImportError as e:
            raise NotImplementedError(
                "attn_impl='ring' requires ops/ring_attention.py, not yet "
                "in this build") from e
        return ring_attention(
            q, k, v, mesh=mesh, q_positions=q_positions,
            kv_positions=kv_positions, q_segment_ids=q_segment_ids,
            kv_segment_ids=kv_segment_ids, causal=causal,
            sliding_window=sliding_window, scale=scale,
            logit_softcap=logit_softcap, interpret=interpret,
            batch_axes=batch_axes)
    if impl == "a2a":
        from gke_ray_train_tpu.ops.a2a_attention import (
            a2a_attention, a2a_supported)
        if mesh is None or mesh.shape[AXIS_CONTEXT] == 1:
            # no context sharding to redistribute — plain flash is the
            # same computation
            return _flash_sharded(
                q, k, v, q_positions, kv_positions, q_segment_ids,
                kv_segment_ids, mesh=mesh, causal=causal,
                sliding_window=sliding_window, scale=scale,
                logit_softcap=logit_softcap, interpret=interpret,
                rows_ordered=rows_ordered, batch_axes=batch_axes)
        if not a2a_supported(mesh, q.shape[2], k.shape[2]):
            # context axis does not divide the local head counts — ring
            # computes the identical function without that constraint
            return attention_dispatch(
                "ring", q, k, v, q_positions=q_positions,
                kv_positions=kv_positions, q_segment_ids=q_segment_ids,
                kv_segment_ids=kv_segment_ids, causal=causal,
                sliding_window=sliding_window, scale=scale,
                logit_softcap=logit_softcap, mesh=mesh,
                interpret=interpret, batch_axes=batch_axes)
        return a2a_attention(
            q, k, v, mesh=mesh, q_positions=q_positions,
            kv_positions=kv_positions, q_segment_ids=q_segment_ids,
            kv_segment_ids=kv_segment_ids, causal=causal,
            sliding_window=sliding_window, scale=scale,
            logit_softcap=logit_softcap, interpret=interpret,
            batch_axes=batch_axes)
    raise ValueError(f"unknown attn_impl {impl!r}")

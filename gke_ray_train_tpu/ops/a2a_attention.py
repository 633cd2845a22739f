"""All-to-all (Ulysses-style) sequence parallelism — the second
context-parallel attention strategy next to ring attention
(SURVEY.md §5.7; the reference has no long-context story at all).

Where ring attention keeps queries resident and rotates K/V shards C-1
hops around the context axis, the all-to-all form redistributes ONCE:
``lax.all_to_all`` swaps the sequence sharding for a head sharding
(each device ends up with the FULL sequence for H/C of its heads), the
unmodified Pallas flash kernel runs locally — plain causal/packed
masking, no cross-shard bookkeeping — and a second all-to-all restores
the sequence sharding. Two collectives total instead of C-1 ppermute
rounds, which wins whenever heads are plentiful relative to the context
axis; ring remains the fallback when C does not divide the local head
counts (the dispatcher enforces this).

Differentiability is free: ``all_to_all``/``all_gather`` have transpose
rules and the flash kernel carries its own custom VJP, so no bespoke
backward ring is needed.
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


def a2a_supported(mesh, n_heads: int, n_kv_heads: int) -> bool:
    """True when the context axis divides the model-sharded head counts
    — the GQA group structure then nests inside the head chunks, so the
    chunk-c queries attend exactly the chunk-c K/V heads."""
    if mesh is None:
        return False
    C = mesh.shape[AXIS_CONTEXT]
    M = mesh.shape[AXIS_MODEL]
    h_loc, k_loc = n_heads // M, n_kv_heads // M
    return h_loc % C == 0 and k_loc % C == 0


def a2a_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                  mesh, q_positions=None, kv_positions=None,
                  q_segment_ids=None, kv_segment_ids=None,
                  causal: bool = True,
                  sliding_window: Optional[int] = None,
                  scale: Optional[float] = None,
                  logit_softcap: Optional[float] = None,
                  interpret: Optional[bool] = None,
                  batch_axes=BATCH_AXES) -> jnp.ndarray:
    """Context-parallel attention; q [B, S, H, dh], k/v [B, S, K, dh]
    sharded over (batch: data x fsdp, seq: context, heads: model) — the
    same contract as ring_attention. S is the GLOBAL sequence length.
    """
    if mesh is None:
        raise ValueError("a2a attention needs a mesh with a context axis")
    B, S, H, dh = q.shape
    K = k.shape[2]
    C = mesh.shape[AXIS_CONTEXT]
    if not a2a_supported(mesh, H, K):
        raise ValueError(
            f"context axis {C} does not divide the model-sharded head "
            f"counts (H={H}, K={K}, model={mesh.shape[AXIS_MODEL]}); "
            "use attn_impl='ring'")
    if S % C:
        raise ValueError(f"global seq len {S} not divisible by context "
                         f"axis size {C}")
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

    def heads_to_seq(x):
        # [B, S/C, h, dh] -> [B, S, h/C, dh]: head chunk c stays here,
        # sequence chunks arrive from every ring member in index order
        return jax.lax.all_to_all(x, AXIS_CONTEXT, split_axis=2,
                                  concat_axis=1, tiled=True)

    def gather_seq(x):
        return jax.lax.all_gather(x, AXIS_CONTEXT, axis=1, tiled=True)

    def local(q, k, v, qp, kp, qs, ks):
        out = fa.flash_attention(
            heads_to_seq(q), heads_to_seq(k), heads_to_seq(v),
            q_positions=gather_seq(qp), kv_positions=gather_seq(kp),
            q_segment_ids=gather_seq(qs), kv_segment_ids=gather_seq(ks),
            causal=causal, sliding_window=sliding_window, scale=scale,
            logit_softcap=logit_softcap, interpret=interpret)
        # inverse redistribution: sequence chunks scatter home, head
        # chunks concatenate back
        return jax.lax.all_to_all(out, AXIS_CONTEXT, split_axis=1,
                                  concat_axis=2, tiled=True)

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

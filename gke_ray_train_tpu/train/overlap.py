"""Manual communication/compute overlap — the ``OVERLAP=manual`` path.

The GSPMD scan step (``train/step.py``) leaves every FSDP collective
where GSPMD put it: the per-layer weight all-gather lands immediately
before the dot that consumes it, so the step stalls for the full fabric
latency of every gather — ``tests/budgets/tiny_fsdp8.json`` pinned that
as ``overlap_frac 0.0`` with 100% of collective bytes exposed (PR 9).
This module rewrites the grad path as a ``shard_map`` microbatch
pipeline, the way Megatron-LM-style stacks hide their collectives:

- every fsdp-sharded leaf is gathered through an explicit collective
  the *program* places, not GSPMD;
- the layer loop double-buffers the gather: layer *k+1*'s params are
  prefetched (and pinned before this layer's compute with an
  ``optimization_barrier``) while layer *k* computes, so the gathered
  result is consumed only by the NEXT loop iteration — the carried
  shape ``perf/costs.py::overlap_stats`` classifies as hidden, which is
  what moves the budget's ``overlap_frac``/``exposed_collective_bytes``;
- the grad reduction mimics GSPMD's exact accumulation structure (one
  all-reduce over the consecutive {data x fsdp} group, then the local
  fsdp slice), which is what makes the manual path's losses AND grads
  **bitwise-identical** to the GSPMD scan on the CPU mesh — the
  equivalence `tests/test_overlap.py` drills (the gradients exactly;
  the loss, whose last reduction XLA:CPU fuses per program, to its
  last ulp).

On a **multi-slice hybrid mesh** (``num_slices > 1`` — the data axis
spans slices, PR 5's contract) the reduction is additionally
DCN-aware (``parallel/hierarchical.py``): both ``DCN_SYNC`` arms stage
the accumulation fold at the slice boundary (intra-slice partials
first, the cross-slice combine second — the shared grouping that keeps
flat-vs-hier **bitwise-identical**), and the arm picks the cross-slice
payload: ``flat`` sends the full leaf over DCN (GSPMD's
all-reduce-then-slice traffic shape), ``hier`` reduce-scatters over
the intra-slice axes first so only ``1/ici_size`` of the bytes cross —
the budgeted number ``tests/budgets/tiny_hybrid_2x4_*.json`` pins.
``DCN_COMPRESS=bf16`` casts only the hier DCN hop, with error feedback
carried across the grad-accumulation scan (not bitwise;
tolerance-pinned in ``tests/tolerances/hier_psum.json``).

Scope: data/fsdp meshes, dense blocks, full fine-tuning. The plan
validator refuses ``overlap='manual'`` on structural-axis topologies
(model/context/pipe > 1), and :func:`check_manual_support` refuses
LoRA and MoE configs loudly — those paths would need their own manual
collectives (TP reduces, ring permutes, expert all-to-alls) that this
pipeline does not emit.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import jax.tree_util as jtu
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from gke_ray_train_tpu.models.config import ModelConfig
from gke_ray_train_tpu.models.remat import checkpoint_block
from gke_ray_train_tpu.models.transformer import (
    Params, param_specs, pre_unembed, resolve_seq_impl, run_block_stack,
    unembed_head, _unembed, make_attention_mask)
from gke_ray_train_tpu.ops.rope import rope_frequencies, sinusoidal_positions

_DP_AXES = ("data", "fsdp")


class ManualOverlapUnsupported(ValueError):
    """The model/mesh combination has no manual-overlap path."""


def check_manual_support(cfg: ModelConfig, mesh: Optional[Mesh], *,
                         lora: bool = False) -> None:
    if mesh is None:
        raise ManualOverlapUnsupported(
            "overlap='manual' needs a mesh — the whole point is placing "
            "the mesh collectives by hand")
    for axis in ("model", "context", "pipe"):
        if int(mesh.shape.get(axis, 1)) != 1:
            raise ManualOverlapUnsupported(
                f"overlap='manual' supports data/fsdp meshes only "
                f"(mesh has {axis}={mesh.shape[axis]}); use "
                "overlap='xla' there")
    if lora:
        raise ManualOverlapUnsupported(
            "overlap='manual' does not support LoRA (the adapter grads "
            "flow outside the fsdp gather structure); set OVERLAP=off "
            "or =xla for adapter runs")
    if cfg.n_experts > 0:
        raise ManualOverlapUnsupported(
            "overlap='manual' does not support MoE blocks (expert "
            "dispatch needs its own manual all-to-alls); set "
            "OVERLAP=off or =xla")


# ---------------------------------------------------------------------------
# building blocks
# ---------------------------------------------------------------------------

@jax.custom_vjp
def _pin(args):
    """``optimization_barrier`` with a trivial VJP. Forward pins the
    schedule (the prefetched gather is issued before the compute that
    the barrier releases); the cotangent passes through untouched, with
    no barrier in the backward. jax 0.9 has its own AD rule for the
    primitive, but that rule barriers the cotangents too — a different
    backward program from the one the bitwise off/manual tests pin."""
    return jax.lax.optimization_barrier(args)


def _pin_fwd(args):
    return _pin(args), None


def _pin_bwd(_, ct):
    return (ct,)


_pin.defvjp(_pin_fwd, _pin_bwd)


def _leaf_fsdp_dims(spec, mesh: Mesh) -> Tuple[int, ...]:
    """Dims of a leaf sharded over a >1 mesh axis. Manual overlap runs
    on data/fsdp meshes, so any such axis is ``fsdp``."""
    out = []
    for dim, entry in enumerate(spec):
        names = (entry if isinstance(entry, tuple)
                 else ((entry,) if entry else ()))
        for ax in names:
            if ax and int(mesh.shape.get(ax, 1)) > 1:
                out.append(dim)
    return tuple(out)


def _fsdp_gather(x, dim: int, shard_reduce=None):
    """All-gather one leaf over ``fsdp`` along ``dim`` — with a backward
    that reproduces GSPMD's accumulation structure EXACTLY. Single
    slice (``shard_reduce=None``): one all-reduce over the consecutive
    {data x fsdp} device group (the ``[1,8]<=[8]`` form the GSPMD grad
    path emits), then the local fsdp shard sliced out — the default AD
    transpose (``psum_scatter`` over fsdp + a second psum over data)
    sums the same partials in a different grouping, which costs the
    last ulp and the bitwise off/manual loss equivalence with it.
    Multi-slice: the slice-staged ``DCN_SYNC`` arm the caller passes as
    ``shard_reduce(ct, dim) -> local shard``
    (``parallel/hierarchical.py``)."""
    shard = x.shape[dim]

    @jax.custom_vjp
    def gather(x):
        return jax.lax.all_gather(x, "fsdp", axis=dim, tiled=True)

    def fwd(x):
        return gather(x), None

    def bwd(_, ct):
        if shard_reduce is not None:
            return (shard_reduce(ct, dim),)
        full = jax.lax.psum(ct, _DP_AXES)
        idx = jax.lax.axis_index("fsdp") * shard
        return (jax.lax.dynamic_slice_in_dim(full, idx, shard, axis=dim),)

    gather.defvjp(fwd, bwd)
    return gather(x)


def _gather_full(tree, spec_tree, mesh: Mesh, shard_reduce=None):
    """Gather every sharded dim of every leaf (the non-block params:
    embed / lm_head / final norm)."""
    def one(x, spec):
        for dim in _leaf_fsdp_dims(spec, mesh):
            x = _fsdp_gather(x, dim, shard_reduce)
        return x
    return jtu.tree_map(one, tree, spec_tree,
                        is_leaf=lambda s: isinstance(s, P))


def _gather_layer(blocks, block_specs, mesh: Mesh, i, shard_reduce=None):
    """Gather ONE layer of the stacked block leaves: dynamic-slice the
    repeat dim at (traced) index ``i``, then gather the fsdp dims. The
    leading stacked dim is the ``pipe`` axis (size 1 on these meshes)
    and is never gathered."""
    def one(x, spec):
        sl = jax.lax.dynamic_slice_in_dim(x, i, 1, axis=0)
        for dim in _leaf_fsdp_dims(spec, mesh):
            if dim == 0:
                continue
            sl = _fsdp_gather(sl, dim, shard_reduce)
        return sl
    return jtu.tree_map(one, blocks, block_specs,
                        is_leaf=lambda s: isinstance(s, P))


# ---------------------------------------------------------------------------
# the pipelined local step (runs per device inside shard_map)
# ---------------------------------------------------------------------------

def _pipelined_hidden(full_nonblock: Params, blocks_local, cfg: ModelConfig,
                      mesh: Mesh, tokens, positions, segment_ids,
                      fused_ops: bool, shard_reduce=None, remat_keep=()):
    """tokens -> final hidden state, with the per-layer double-buffered
    fsdp gather. Per-layer math is :func:`run_block_stack` — the same
    function ``forward``'s scan body calls, so the two paths cannot
    fork. ``mesh=None`` inside: each device computes the dense program
    on its local batch rows with the gathered full weights — exactly
    the per-device program GSPMD compiles for these meshes, which is
    why the values match bitwise."""
    import math

    dtype = jnp.dtype(cfg.dtype)
    B, S = tokens.shape
    specs = param_specs(cfg)
    block_specs = specs["blocks"]

    x = full_nonblock["embed"].astype(dtype)[tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), dtype)
    if cfg.positional == "sinusoidal":
        table = jnp.asarray(sinusoidal_positions(cfg.max_seq_len,
                                                 cfg.d_model))
        x = x + table.astype(dtype)[positions]
        rope = None
    else:
        rope = jnp.asarray(rope_frequencies(
            cfg.rope_dim, theta=cfg.rope_theta,
            llama3_scaling=cfg.rope_scaling))

    impl = resolve_seq_impl(cfg, None, S)
    masks = {kind: None for kind in set(cfg.block_pattern)}
    if impl == "xla":
        for kind in masks:
            masks[kind] = make_attention_mask(
                positions, positions, segment_ids, segment_ids,
                causal=True,
                sliding_window=(cfg.sliding_window if kind == "sliding"
                                else None))

    R = cfg.n_repeats
    cur0 = _gather_layer(blocks_local, block_specs, mesh, 0, shard_reduce)

    def body(carry, i):
        x, aux, cur = carry
        # prefetch layer i+1 while layer i computes; the barrier pins
        # the issue order (the gather must complete before x is
        # released to this layer's compute — the double-buffer
        # discipline). The wrap-around gather of layer 0 on the last
        # iteration is carried out unused; its cotangent is zero.
        nxt = _gather_layer(blocks_local, block_specs, mesh, (i + 1) % R,
                            shard_reduce)
        nxt, x = _pin((nxt, x))
        layer_slice = jtu.tree_map(lambda v: v[0], cur)
        x, aux = run_block_stack(
            x, aux, layer_slice, cfg, impl, dtype, rope, positions,
            masks, segment_ids, None, fused_ops=fused_ops)
        return (x, aux, nxt), None

    (x, _, _), _ = jax.lax.scan(
        checkpoint_block(body, cfg, remat_keep),
        (x, jnp.zeros((), jnp.float32), cur0), jnp.arange(R))
    return x


def make_manual_grad_fn(cfg: ModelConfig, mesh: Mesh, *,
                        batch_keys: Tuple[str, ...] =
                        ("inputs", "targets", "weights"),
                        fused_ops: bool = False,
                        use_fused_ce: bool = False,
                        num_slices: int = 1,
                        dcn_sync: str = "flat",
                        dcn_compress: str = "none",
                        remat_keep: Tuple[str, ...] = ()):
    """Build ``(params, micro) -> ((nll_sum, w_sum), grads)`` — the
    drop-in replacement for the GSPMD path's
    ``value_and_grad(micro_loss)`` that the accum scan consumes. The
    returned function is a ``shard_map`` over the whole mesh: inputs
    arrive as the local param shards / local batch rows, the fsdp
    gathers and grad reductions are placed explicitly, and the outputs
    come back sharded exactly like the GSPMD grads (params-like tree +
    replicated scalars).

    ``num_slices``/``dcn_sync``/``dcn_compress``: the DCN-aware
    reduction knobs (module docstring). With compression on, the
    signature grows an error-feedback residual:
    ``(params, micro, residual) -> ((nll, w), grads, new_residual)`` —
    the residual tree is params-shaped (zeros at step start; the accum
    scan in ``train/step.py`` carries it across microbatches) and the
    returned fn carries ``grad_fn.compressed = True``.

    ``remat_keep``: what each checkpointed layer keeps beside its input
    (models/remat.py)."""
    from gke_ray_train_tpu.parallel.hierarchical import (
        compressed_cross_psum, flat_reduce_shard, hier_reduce_full,
        hier_reduce_shard, intra_reduce_shard, slice_topology,
        staged_psum)

    check_manual_support(cfg, mesh)
    specs = param_specs(cfg)
    topo = slice_topology(mesh, num_slices)
    compressed = dcn_compress != "none" and topo is not None
    if dcn_sync == "hier" and topo is None:
        # a loud no-op: single-slice pools have no DCN hop to shrink
        # (plan validation already downgraded a declared NUM_SLICES=1
        # hier; this catches direct callers)
        dcn_sync = "flat"

    # the sharded-leaf reduction _fsdp_gather's backward applies:
    #   single slice      — None (the joint psum + slice, unchanged)
    #   flat  multi-slice — staged full payload over DCN
    #   hier  multi-slice — scattered shard over DCN (1/ici_size)
    #   compressed        — intra-slice half only; the DCN hop runs
    #                       after value_and_grad, with the residual
    if topo is None:
        shard_reduce = None
    elif compressed:
        shard_reduce = lambda ct, dim: intra_reduce_shard(ct, topo, dim)  # noqa: E731
    elif dcn_sync == "hier":
        shard_reduce = lambda ct, dim: hier_reduce_shard(ct, topo, dim)  # noqa: E731
    else:
        shard_reduce = lambda ct, dim: flat_reduce_shard(ct, topo, dim)  # noqa: E731

    def _scalar_sum(x):
        return jax.lax.psum(x, _DP_AXES) if topo is None \
            else staged_psum(x, topo)

    def local_grad(params_local, micro_local, resid_local=None):
        B_loc, S = micro_local["inputs"].shape
        positions = micro_local.get("positions")
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(S, dtype=jnp.int32), (B_loc, S))
        segment_ids = micro_local.get("segment_ids")

        def loss_fn(p):
            nonblock = {k: v for k, v in p.items() if k != "blocks"}
            nb_specs = {k: v for k, v in specs.items() if k != "blocks"}
            full_nb = _gather_full(nonblock, nb_specs, mesh, shard_reduce)
            x = _pipelined_hidden(full_nb, p["blocks"], cfg, mesh,
                                  micro_local["inputs"], positions,
                                  segment_ids, fused_ops, shard_reduce,
                                  remat_keep)
            dtype = jnp.dtype(cfg.dtype)
            if use_fused_ce and cfg.logit_softcap is None:
                from gke_ray_train_tpu.ops.fused_ce import \
                    fused_cross_entropy
                xn = pre_unembed(x, full_nb, cfg, None)
                nll, w = fused_cross_entropy(
                    xn.astype(dtype),
                    unembed_head(full_nb, cfg).astype(dtype),
                    micro_local["targets"], micro_local["weights"])
            else:
                from gke_ray_train_tpu.train.step import token_nll
                logits = _unembed(x, full_nb, cfg, dtype, None)
                nll, w = token_nll(logits, micro_local["targets"],
                                   micro_local["weights"])
            return nll, w

        (nll, w), g = jax.value_and_grad(loss_fn, has_aux=True)(
            params_local)

        if compressed:
            # sharded leaves arrive as intra-slice partials; the DCN
            # hop runs here, bf16 with the error-feedback residual.
            # Replicated leaves (norms — a rounding error of bytes)
            # ride the uncompressed hier hop; their residual stays 0.
            def hop(gl, rl, spec):
                if _leaf_fsdp_dims(spec, mesh):
                    return compressed_cross_psum(gl, rl, topo,
                                                 dcn_compress)
                return hier_reduce_full(gl, topo), rl

            paired = jtu.tree_map(hop, g, resid_local, specs,
                                  is_leaf=lambda s: isinstance(s, P))
            is_pair = lambda x: isinstance(x, tuple)  # noqa: E731
            g = jtu.tree_map(lambda p: p[0], paired, is_leaf=is_pair)
            new_resid = jtu.tree_map(lambda p: p[1], paired,
                                     is_leaf=is_pair)
            return (g, _scalar_sum(nll), _scalar_sum(w), new_resid)

        def reduce_leaf(gl, spec):
            # gathered leaves were already reduced over BOTH axes by
            # _fsdp_gather's backward; replicated leaves (norms,
            # biases) still need the cross-device sum
            if _leaf_fsdp_dims(spec, mesh):
                return gl
            if topo is None:
                return jax.lax.psum(gl, _DP_AXES)
            return (hier_reduce_full(gl, topo) if dcn_sync == "hier"
                    else staged_psum(gl, topo))

        g = jtu.tree_map(reduce_leaf, g, specs,
                         is_leaf=lambda s: isinstance(s, P))
        return g, _scalar_sum(nll), _scalar_sum(w)

    batch_specs = {k: P(_DP_AXES, None) for k in batch_keys}
    if compressed:
        mapped = shard_map(local_grad, mesh=mesh,
                           in_specs=(specs, batch_specs, specs),
                           out_specs=(specs, P(), P(), specs),
                           check_vma=False)

        @functools.wraps(local_grad)
        def grad_fn(params: Params, micro: Dict[str, Any], residual):
            g, nll, w, new_resid = mapped(params, micro, residual)
            return (nll, w), g, new_resid
    else:
        mapped = shard_map(local_grad, mesh=mesh,
                           in_specs=(specs, batch_specs),
                           out_specs=(specs, P(), P()),
                           check_vma=False)

        @functools.wraps(local_grad)
        def grad_fn(params: Params, micro: Dict[str, Any]):
            g, nll, w = mapped(params, micro)
            return (nll, w), g

    grad_fn.compressed = compressed
    return grad_fn

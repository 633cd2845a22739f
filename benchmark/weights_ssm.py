"""Weights from ``--seed`` for the hybrid state-space / attention routed
decoder (``granitemoehybrid``: Granite-4.0-H-Small): the leaves
``benchmark/weights.py`` and ``benchmark/weights_moe.py`` know are drawn
by them, under their ids; the mixer's own (its two projections, the
conv's taps and bias, the step bias, the decays, the skip, the gate
norm) and the shared expert at its own width are drawn here the same
way. A leaf is a pure function of (seed, leaf name, layer[, expert]), so
the driver builds the tree in the program's layout in one jitted call and
the plain reference makes the same layer again, alone. Nothing here
imports the program.
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from benchmark import weights as wts
from benchmark import weights_moe as wm

ATTENTION = wm.ATTENTION
# the two matrices of a state-space layer's mixer, in the order the
# layer uses them, and the rest of it
MIXER = ("in_proj", "out_proj")
MIXER_REST = ("conv_w", "conv_b", "dt_bias", "a_log", "d_skip", "ssm_norm")
SHARED, EXPERT = wm.SHARED, wm.EXPERT
# fixed numbers beside weights.LEAF_ID's 1-12, weights_moe's 13-22 and
# weights_mla's 23-28 (the shared expert keeps weights_moe's ids: the
# draw differs in its shape alone)
LEAF_ID = {"in_proj": 29, "out_proj": 30, "conv_w": 31, "conv_b": 32,
           "dt_bias": 33, "a_log": 34, "d_skip": 35, "ssm_norm": 36,
           **{n: wm.LEAF_ID[n] for n in wm.SHARED}}
RESIDUAL_WRITERS = ("out_proj", "shared_down")
# which of a state-space layer's matrices stand where a job's target
# list names an attention projection
TARGETS_OF = {"wq": ("in_proj",), "wk": ("in_proj",), "wv": ("in_proj",),
              "wo": ("out_proj",)}


def dims_from_config(config: dict) -> Dict[str, int]:
    """The sizes the decoder needs, from the published key names
    (``num_local_experts`` counts the experts held here: the
    configuration file's ``reduced``; ``router_outputs`` is the
    published count). ``intermediate_size`` is read as one routed
    expert's width (the configuration file's ``assumed``),
    ``shared_intermediate_size`` is the shared expert's.
    ``layers_published`` scales the residual writers, so that a cut in
    depth leaves every layer as the whole model has it."""
    dims = wts.dims_from_config(config)
    held = config.get("experts_held") or [0, int(config["num_local_experts"])]
    # mamba_expand x hidden_size at the published sizes; a shrunk
    # rehearsal (rehearse/tiny.py knows a dense decoder's keys only)
    # keeps the mixer's heads at a smaller hidden size
    heads, head = int(config["mamba_n_heads"]), int(config["mamba_d_head"])
    groups, state = int(config["mamba_n_groups"]), int(config["mamba_d_state"])
    dims.update(
        expert_ff=int(config["intermediate_size"]),
        shared_ff=int(config["shared_intermediate_size"]), shared=1,
        experts=int(config.get("router_outputs",
                               config["num_local_experts"])),
        held_lo=int(held[0]), held=int(held[1]) - int(held[0]),
        top_k=int(config["num_experts_per_tok"]), dense_layers=0,
        layers_published=int(config.get("num_hidden_layers_published",
                                        config["num_hidden_layers"])),
        ssm_heads=heads, ssm_head_dim=head, ssm_state=state,
        ssm_groups=groups, ssm_conv=int(config["mamba_d_conv"]),
        ssm_chunk=int(config["mamba_chunk_size"]),
        ssm_inner=heads * head,
        ssm_conv_dim=heads * head + 2 * groups * state)
    return dims


def layer_kinds(config: dict, layer: int) -> Tuple[str, str]:
    """("mamba" | "full", "sparse") of one layer: every layer's MLP is
    the routed one."""
    kind = config["layer_types"][layer]
    return ("mamba" if kind == "mamba" else "full"), "sparse"


def leaf_shape(dims: Dict[str, int], name: str) -> Tuple[int, ...]:
    d, inner, conv = dims["hidden"], dims["ssm_inner"], dims["ssm_conv_dim"]
    fs = dims["shared_ff"]
    own = {"in_proj": (d, inner + conv + dims["ssm_heads"]),  # z|x|B|C|dt
           "out_proj": (inner, d),
           "conv_w": (conv, dims["ssm_conv"]), "conv_b": (conv,),
           "dt_bias": (dims["ssm_heads"],), "a_log": (dims["ssm_heads"],),
           "d_skip": (dims["ssm_heads"],), "ssm_norm": (inner,),
           "shared_gate": (d, fs), "shared_up": (d, fs),
           "shared_down": (fs, d)}
    return own[name] if name in own else wm.leaf_shape(dims, name)


def master(dims: Dict[str, int], key: jax.Array, name: str, layer,
           expert=0) -> jnp.ndarray:
    """One leaf in float32; ``layer`` and ``expert`` may be traced. The
    mixer's own as Mamba-2 initialises them, so that the decays are real
    ones: ``a_log`` the log of uniform [1, 16], ``dt_bias`` the inverse
    softplus of a log-uniform step in [0.001, 0.1], ``d_skip`` 1, the
    conv's taps uniform +-1/2 (a fan-in of 4), its bias 0.02 x normal."""
    if name not in LEAF_ID:
        return wm.master(dims, key, name, layer, expert)
    k = jax.random.fold_in(jax.random.fold_in(key, LEAF_ID[name]), layer)
    shape = leaf_shape(dims, name)
    if name == "ssm_norm":
        return 1.0 + 0.05 * jax.random.normal(k, shape, jnp.float32)
    if name == "a_log":
        return jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
    if name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    if name == "d_skip":
        return jnp.ones(shape, jnp.float32)
    if name == "conv_w":
        bound = 1.0 / math.sqrt(dims["ssm_conv"])
        return jax.random.uniform(k, shape, jnp.float32, -bound, bound)
    std = 0.02
    if name in RESIDUAL_WRITERS:
        std /= math.sqrt(2 * dims["layers_published"])
    return jax.random.normal(k, shape, jnp.float32) * std


def stored(dims, key, name: str, layer, dtype, expert=0) -> jnp.ndarray:
    return master(dims, key, name, layer, expert).astype(jnp.dtype(dtype))


def lora_targets(targets, kind: str, dims) -> Tuple[str, ...]:
    """The leaves of one layer that take adapters, from the job's target
    list: in a state-space layer (``kind == "mamba"``) the mixer's two
    projections in the place of the attention's four, in an attention
    layer those four; the shared expert in the MLP's place; routed
    experts and router are frozen."""
    out = []
    for t in targets:
        names = TARGETS_OF.get(t, ()) if kind == "mamba" \
            else ((t,) if t in ATTENTION else ())
        out += [n for n in names if n not in out]
    return tuple(out) + tuple(
        s for t, s in zip(wm.DENSE_MLP, SHARED) if t in targets)


def lora_a(dims, key, target: str, layer, rank: int) -> jnp.ndarray:
    """LoRA's A ~ N(0, 1/r) in float32; B starts at zero."""
    if target not in LEAF_ID:
        return wm.lora_a(dims, key, target, layer, rank)
    k = jax.random.fold_in(jax.random.fold_in(
        key, wts.LORA_A_ID + LEAF_ID[target]), layer)
    return jax.random.normal(k, (leaf_shape(dims, target)[0], rank),
                             jnp.float32) / math.sqrt(rank)


def lora_b_shape(dims, target: str, rank: int) -> Tuple[int, int]:
    return (rank, leaf_shape(dims, target)[1])

"""Weights from ``--seed`` for the latent-attention routed decoder
(``glm4_moe_lite``: GLM-4.7-Flash): the leaves ``benchmark/weights.py``
and ``benchmark/weights_moe.py`` know are drawn by them, under their ids;
the latent layer's own (the four matrices that stand in the place of
wq / wk / wv, and the two latents' norm scales) are drawn here the same
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

# in the order the layer uses them
ATTENTION = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo")
LATENT_NORMS = ("q_latent_norm", "kv_latent_norm")
DENSE_MLP, SHARED, EXPERT = wm.DENSE_MLP, wm.SHARED, wm.EXPERT
# fixed numbers beside weights.LEAF_ID's 1-12 and weights_moe's 13-22
LEAF_ID = {"wq_a": 23, "wq_b": 24, "wkv_a": 25, "wkv_b": 26,
           "q_latent_norm": 27, "kv_latent_norm": 28}
# which latent matrices make the projection a job's target list names
TARGETS_OF = {"wq": ("wq_a", "wq_b"), "wk": ("wkv_a", "wkv_b"),
              "wv": ("wkv_a", "wkv_b"), "wo": ("wo",)}


def dims_from_config(config: dict) -> Dict[str, int]:
    """The sizes the decoder needs, from the published key names
    (``n_routed_experts`` counts the experts held here: the
    configuration file's ``reduced``; ``router_outputs`` is the
    published count). ``head_dim`` is the one head size the attention
    sees: a head's q and k are ``qk_nope_head_dim`` values without
    position and ``qk_rope_head_dim`` rotated ones, and ``v_head_dim``
    has to be their sum. ``kv_heads`` is ``heads``: the up-projection
    of the shared latent gives every query head its own keys and values,
    whatever ``num_key_value_heads`` says (the source repeats the head
    count there; nothing in the layer reads it)."""
    nope, rot = int(config["qk_nope_head_dim"]), int(config["qk_rope_head_dim"])
    if int(config["v_head_dim"]) != nope + rot:
        raise ValueError("value heads differ in size from q / k heads")
    held = config.get("experts_held") or [0, int(config["n_routed_experts"])]
    return {
        "vocab": int(config["vocab_size"]),
        "hidden": int(config["hidden_size"]),
        "layers": int(config["num_hidden_layers"]),
        "heads": int(config["num_attention_heads"]),
        "kv_heads": int(config["num_attention_heads"]),
        "head_dim": nope + rot,
        "ff": int(config["intermediate_size"]),
        "q_rank": int(config["q_lora_rank"]),
        "kv_rank": int(config["kv_lora_rank"]),
        "nope": nope, "rope": rot,
        "expert_ff": int(config["moe_intermediate_size"]),
        "experts": int(config.get("router_outputs",
                                  config["n_routed_experts"])),
        "held_lo": int(held[0]), "held": int(held[1]) - int(held[0]),
        "top_k": int(config["num_experts_per_tok"]),
        "shared": int(config.get("n_shared_experts", 0)),
        "dense_layers": int(config.get("first_k_dense_replace", 0)),
        "layers_published": int(config.get("num_hidden_layers_published",
                                           config["num_hidden_layers"])),
    }


def layer_kinds(config: dict, layer: int) -> Tuple[str, str]:
    """("latent", "dense" | "sparse") of one layer."""
    return "latent", ("dense" if layer < int(config.get(
        "first_k_dense_replace", 0)) else "sparse")


def leaf_shape(dims: Dict[str, int], name: str) -> Tuple[int, ...]:
    d, h = dims["hidden"], dims["heads"]
    own = {"wq_a": (d, dims["q_rank"]),
           "wq_b": (dims["q_rank"], h * dims["head_dim"]),
           "wkv_a": (d, dims["kv_rank"] + dims["rope"]),
           # a head's keys without position, then its values
           "wkv_b": (dims["kv_rank"], h * (dims["nope"] + dims["head_dim"])),
           "q_latent_norm": (dims["q_rank"],),
           "kv_latent_norm": (dims["kv_rank"],)}
    return own[name] if name in own else wm.leaf_shape(dims, name)


def master(dims: Dict[str, int], key: jax.Array, name: str, layer,
           expert=0) -> jnp.ndarray:
    """One leaf in float32; ``layer`` and ``expert`` may be traced."""
    if name not in LEAF_ID:
        return wm.master(dims, key, name, layer, expert)
    k = jax.random.fold_in(jax.random.fold_in(key, LEAF_ID[name]), layer)
    shape = leaf_shape(dims, name)
    if name in LATENT_NORMS:
        return 1.0 + 0.05 * jax.random.normal(k, shape, jnp.float32)
    return jax.random.normal(k, shape, jnp.float32) * 0.02


def stored(dims, key, name: str, layer, dtype, expert=0) -> jnp.ndarray:
    return master(dims, key, name, layer, expert).astype(jnp.dtype(dtype))


def lora_targets(targets, mlp: str, dims) -> Tuple[str, ...]:
    """The leaves of one layer that take adapters, from the job's target
    list: the latent matrices in the place of the projection they make
    (``TARGETS_OF``), the MLP in a dense layer, the shared expert (in
    the MLP's place) in a sparse one; routed experts, router and bias
    are frozen."""
    out = []
    for t in targets:
        out += [n for n in TARGETS_OF.get(t, ()) if n not in out]
    return tuple(out) + tuple(
        t for t in wm.lora_targets(targets, mlp, dims)
        if t not in wm.ATTENTION)


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

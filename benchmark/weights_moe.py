"""Weights from ``--seed`` for the routed decoder (``exaone_moe``): the
leaves ``benchmark/weights.py`` knows are drawn by it, under its ids; the
leaves it does not know (q/k norm scales, router and its selection bias,
the shared expert, the experts one by one) are drawn here the same way.
A leaf is a pure function of (seed, leaf name, layer[, expert]), so the
driver builds the tree in the program's layout in one jitted call and the
plain reference makes the same layer again, alone. Nothing here imports
the program.
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from benchmark import weights as wts

ATTENTION = ("wq", "wk", "wv", "wo")
DENSE_MLP = ("w_gate", "w_up", "w_down")
SHARED = ("shared_gate", "shared_up", "shared_down")
EXPERT = ("expert_gate", "expert_up", "expert_down")
# fixed numbers beside weights.LEAF_ID's 1-12
LEAF_ID = {"q_norm": 13, "k_norm": 14, "router": 15, "router_bias": 16,
           "shared_gate": 17, "shared_up": 18, "shared_down": 19,
           "expert_gate": 20, "expert_up": 21, "expert_down": 22}
NORMS = ("q_norm", "k_norm")
RESIDUAL_WRITERS = ("shared_down", "expert_down")
ROUTER_BIAS_STD = 0.02


def dims_from_config(config: dict) -> Dict[str, int]:
    """The sizes the routed decoder needs, from the published key names
    (``num_experts`` counts the experts held here: the configuration
    file's ``reduced``; ``router_outputs`` is the published count).
    ``layers_published`` scales the residual writers, so that a cut in
    depth leaves every layer as the whole model has it."""
    dims = wts.dims_from_config(config)
    held = config.get("experts_held") or [0, int(config["num_experts"])]
    dims.update(
        expert_ff=int(config["moe_intermediate_size"]),
        experts=int(config.get("router_outputs", config["num_experts"])),
        held_lo=int(held[0]), held=int(held[1]) - int(held[0]),
        top_k=int(config["num_experts_per_tok"]),
        shared=int(config.get("num_shared_experts", 0)),
        dense_layers=int(config.get("first_k_dense_replace", 0)),
        layers_published=int(config.get("num_hidden_layers_published",
                                        config["num_hidden_layers"])))
    return dims


def layer_kinds(config: dict, layer: int) -> Tuple[str, str]:
    """("sliding" | "full", "dense" | "sparse") of one layer."""
    attn = config["layer_types"][layer].split("_")[0]
    mlp = (config["mlp_layer_types"][layer] if "mlp_layer_types" in config
           else "dense" if layer < int(config.get(
               "first_k_dense_replace", 0)) else "sparse")
    return attn, mlp


def leaf_shape(dims: Dict[str, int], name: str) -> Tuple[int, ...]:
    d, fe = dims["hidden"], dims["expert_ff"]
    fs = dims["shared"] * fe
    own = {"q_norm": (dims["head_dim"],), "k_norm": (dims["head_dim"],),
           "router": (d, dims["experts"]),
           "router_bias": (dims["experts"],),
           "shared_gate": (d, fs), "shared_up": (d, fs),
           "shared_down": (fs, d),
           "expert_gate": (d, fe), "expert_up": (d, fe),
           "expert_down": (fe, d)}
    return own[name] if name in own else wts.leaf_shape(dims, name)


def _depth(dims):
    return dict(dims, layers=dims["layers_published"])


def master(dims: Dict[str, int], key: jax.Array, name: str, layer,
           expert=0) -> jnp.ndarray:
    """One leaf in float32; ``layer`` and ``expert`` may be traced."""
    if name not in LEAF_ID:
        return wts.master(_depth(dims), key, name, layer)
    k = jax.random.fold_in(jax.random.fold_in(
        jax.random.fold_in(key, LEAF_ID[name]), layer), expert)
    shape = leaf_shape(dims, name)
    if name in NORMS:
        return 1.0 + 0.05 * jax.random.normal(k, shape, jnp.float32)
    std = ROUTER_BIAS_STD if name == "router_bias" else 0.02
    if name in RESIDUAL_WRITERS:
        std /= math.sqrt(2 * dims["layers_published"])
    return jax.random.normal(k, shape, jnp.float32) * std


def stored(dims, key, name: str, layer, dtype, expert=0) -> jnp.ndarray:
    return master(dims, key, name, layer, expert).astype(jnp.dtype(dtype))


def lora_targets(targets, mlp: str, dims) -> Tuple[str, ...]:
    """The leaves of one layer that take adapters, from the job's target
    list: attention everywhere, the MLP in a dense layer, the shared
    expert (in the MLP's place) in a sparse one; routed experts, router
    and bias are frozen."""
    out = [t for t in targets if t in ATTENTION]
    if mlp == "dense":
        out += [t for t in targets if t in DENSE_MLP]
    elif dims["shared"]:
        out += [s for t, s in zip(DENSE_MLP, SHARED) if t in targets]
    return tuple(out)


def lora_a(dims, key, target: str, layer, rank: int) -> jnp.ndarray:
    """LoRA's A ~ N(0, 1/r) in float32; B starts at zero."""
    if target not in LEAF_ID:
        return wts.lora_a(dims, key, target, layer, rank)
    k = jax.random.fold_in(jax.random.fold_in(
        key, wts.LORA_A_ID + LEAF_ID[target]), layer)
    return jax.random.normal(k, (leaf_shape(dims, target)[0], rank),
                             jnp.float32) / math.sqrt(rank)


def lora_b_shape(dims, target: str, rank: int) -> Tuple[int, int]:
    return (rank, leaf_shape(dims, target)[1])

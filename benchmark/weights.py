"""Weights from ``--seed``: the benchmark's own, not the program's.

One leaf of one layer is a pure function of (seed, leaf name, layer), so
the driver can build the whole tree in the program's layout in one jitted
call and the plain reference can make the same layer again, alone, after
the window has closed. Nothing here imports the program.
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

PROJECTIONS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
NORMS = ("attn_norm", "mlp_norm", "final_norm")
RESIDUAL_WRITERS = ("wo", "w_down")
# fixed numbers, so that a leaf's draw never depends on what else exists
LEAF_ID = {"embed": 1, "lm_head": 2, "final_norm": 3, "attn_norm": 4,
           "mlp_norm": 5, "wq": 6, "wk": 7, "wv": 8, "wo": 9,
           "w_gate": 10, "w_up": 11, "w_down": 12}
LORA_A_ID = 100


def dims_from_config(config: dict) -> Dict[str, int]:
    """The sizes a dense decoder needs, from the published key names."""
    heads = int(config["num_attention_heads"])
    hidden = int(config["hidden_size"])
    return {
        "vocab": int(config["vocab_size"]),
        "hidden": hidden,
        "layers": int(config["num_hidden_layers"]),
        "heads": heads,
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config.get("head_dim") or hidden // heads),
        "ff": int(config["intermediate_size"]),
    }


def leaf_shape(dims: Dict[str, int], name: str) -> Tuple[int, ...]:
    d, f = dims["hidden"], dims["ff"]
    q = dims["heads"] * dims["head_dim"]
    kv = dims["kv_heads"] * dims["head_dim"]
    return {
        "embed": (dims["vocab"], d), "lm_head": (d, dims["vocab"]),
        "final_norm": (d,), "attn_norm": (d,), "mlp_norm": (d,),
        "wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
        "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d),
    }[name]


def seed_key(seed: int) -> jax.Array:
    """A key for any whole number: the driver's seeds pass 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed % (2 ** 31)),
                              seed // (2 ** 31))


def master(dims: Dict[str, int], key: jax.Array, name: str,
           layer) -> jnp.ndarray:
    """One leaf in float32. ``layer`` may be traced (0 for the leaves
    outside the layers)."""
    k = jax.random.fold_in(jax.random.fold_in(key, LEAF_ID[name]), layer)
    shape = leaf_shape(dims, name)
    if name in NORMS:
        return 1.0 + 0.05 * jax.random.normal(k, shape, jnp.float32)
    std = 0.02
    if name in RESIDUAL_WRITERS:
        std /= math.sqrt(2 * dims["layers"])
    return jax.random.normal(k, shape, jnp.float32) * std


def stored(dims, key, name: str, layer, dtype) -> jnp.ndarray:
    """The leaf as the configuration stores it: rounded to ``dtype``."""
    return master(dims, key, name, layer).astype(jnp.dtype(dtype))


def lora_a(dims, key, target: str, layer, rank: int) -> jnp.ndarray:
    """LoRA's A ~ N(0, 1/r) in float32; B starts at zero (Hu et al.)."""
    k = jax.random.fold_in(
        jax.random.fold_in(key, LORA_A_ID + LEAF_ID[target]), layer)
    d_in = leaf_shape(dims, target)[0]
    return jax.random.normal(k, (d_in, rank), jnp.float32) / math.sqrt(rank)


def lora_b_shape(dims, target: str, rank: int) -> Tuple[int, int]:
    return (rank, leaf_shape(dims, target)[1])

"""The yardstick's arithmetic for the latent-attention routed decoder
(``glm4_moe_lite``): ``flops_moe``'s rules with the five matrices of a
latent layer in the place of wq / wk / wv / wo. Operations that the
mathematics needs, from shapes, the real documents and the program's
count of held pairs: recomputation is never counted, padding never
billed, an expert is billed for the pairs it was given, and attention
for what the layer as published multiplies: 20 heads of 256 over every
in-document causal pair (the rotated 64 of a key are billed a head,
though all heads share them: no kernel here reads them once).
"""

from __future__ import annotations

from typing import Dict, Sequence

from benchmark import flops_moe
from benchmark import weights_mla as wl

expert_params = flops_moe.expert_params
attention_pairs = flops_moe.attention_pairs
flash_call = flops_moe.flash_call


def _size(dims, name) -> int:
    a, b = wl.leaf_shape(dims, name)
    return a * b


def frozen_params(dims: Dict[str, int], layer_kinds) -> int:
    """Frozen weights that every real token is multiplied with: each
    layer's five attention matrices, the dense layers' MLP, a sparse
    layer's shared expert and router, and the head slice (not the
    embedding rows it merely reads, not the routed experts: those are
    billed by pair)."""
    total = dims["hidden"] * dims["vocab"]
    for _, mlp in layer_kinds:
        total += sum(_size(dims, n) for n in wl.ATTENTION)
        if mlp == "dense":
            total += sum(_size(dims, n) for n in wl.DENSE_MLP)
        else:
            total += _size(dims, "router")
            if dims["shared"]:
                total += sum(_size(dims, n) for n in wl.SHARED)
    return total


def base_params(dims: Dict[str, int], layer_kinds) -> int:
    """Weights that the program holds as NF4 ``base`` leaves of
    ``_proj``: each layer's five attention matrices, the dense layers'
    MLP, a sparse layer's shared expert (the routed experts are a bank
    under ``moe/experts``; router and head are not quantised)."""
    total = 0
    for _, mlp in layer_kinds:
        total += sum(_size(dims, n) for n in wl.ATTENTION)
        if mlp == "dense":
            total += sum(_size(dims, n) for n in wl.DENSE_MLP)
        elif dims["shared"]:
            total += sum(_size(dims, n) for n in wl.SHARED)
    return total


def lora_params(dims: Dict[str, int], layer_kinds, rank: int,
                targets: Sequence[str]) -> int:
    total = 0
    for _, mlp in layer_kinds:
        for t in wl.lora_targets(targets, mlp, dims):
            a, b = wl.leaf_shape(dims, t)
            total += rank * (a + b)
    return total


def train_flops(dims: Dict[str, int], layer_kinds, doc_lengths, *,
                held_pairs: float, lora_rank: int, lora_targets) -> float:
    """Forward + backward of a LoRA step over the documents given (real
    tokens only): 4 per frozen weight a token meets (no weight gradient)
    and per expert weight a held pair meets, 6 per adapter weight;
    attention by in-document causal pairs: QK^T and PV, forward
    (2 x 2 x d) and backward (twice that) per pair, head and layer."""
    tokens = sum(int(n) for n in doc_lengths)
    dense = (4.0 * frozen_params(dims, layer_kinds)
             + 6.0 * lora_params(dims, layer_kinds, lora_rank,
                                 lora_targets)) * tokens
    routed = 4.0 * expert_params(dims) * float(held_pairs)
    d_attn = dims["heads"] * dims["head_dim"]
    pairs = attention_pairs(doc_lengths) * len(layer_kinds)
    return dense + routed + 12.0 * d_attn * pairs

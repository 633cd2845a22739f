"""The yardstick's arithmetic for the hybrid state-space / attention
routed decoder (``granitemoehybrid``): ``flops_mla``'s conventions with
a state-space layer's mixer where such a layer stands. Operations that
the mathematics needs, from shapes, the real documents and the program's
count of held pairs: recomputation is never counted, padding never
billed, an expert is billed for the pairs it was given, attention for
the in-document causal pairs of the attention layers, and the scan for
the work of its chunked form at the PUBLISHED chunk, whatever chunk or
kernel implements it.
"""

from __future__ import annotations

from typing import Dict, Sequence

from benchmark import flops_moe
from benchmark import weights_ssm as ws

expert_params = flops_moe.expert_params
attention_pairs = flops_moe.attention_pairs
flash_call = flops_moe.flash_call


def _size(dims, name) -> int:
    a, b = ws.leaf_shape(dims, name)
    return a * b


def _mixer(kind: str):
    return ws.MIXER if kind == "mamba" else ws.ATTENTION


def base_params(dims: Dict[str, int], layer_kinds) -> int:
    """Weights that the program holds as NF4 ``base`` leaves of
    ``_proj``: a state-space layer's two projections, an attention
    layer's four, every layer's shared expert (the routed experts are a
    bank under ``moe/experts``; router and head are not quantised)."""
    return sum(sum(_size(dims, n) for n in _mixer(kind) + ws.SHARED)
               for kind, _ in layer_kinds)


def frozen_params(dims: Dict[str, int], layer_kinds) -> int:
    """Frozen weights that every real token is multiplied with: the
    ``base`` leaves, every layer's router and the head slice (not the
    embedding rows it merely reads, not the routed experts: those are
    billed by pair)."""
    return (dims["hidden"] * dims["vocab"] + base_params(dims, layer_kinds)
            + len(layer_kinds) * _size(dims, "router"))


def lora_params(dims: Dict[str, int], layer_kinds, rank: int,
                targets: Sequence[str]) -> int:
    total = 0
    for kind, _ in layer_kinds:
        for t in ws.lora_targets(targets, kind, dims):
            a, b = ws.leaf_shape(dims, t)
            total += rank * (a + b)
    return total


def scan_position(dims: Dict[str, int]) -> Dict[str, float]:
    """What the scan of one state-space layer needs for one position,
    forward: the chunked form at the published chunk Q: a head's
    products inside the chunk (2 Q P), into and out of the chunk's state
    (4 N P), and a group's scores (2 Q N); it reads x, B, C and dt and
    writes y, 2 bytes a value."""
    H, P = dims["ssm_heads"], dims["ssm_head_dim"]
    N, G, Q = dims["ssm_state"], dims["ssm_groups"], dims["ssm_chunk"]
    return {"flops": float(H * (2 * Q * P + 4 * N * P) + 2 * Q * N * G),
            "bytes": 2.0 * (2 * H * P + 2 * G * N + H)}


def train_flops(dims: Dict[str, int], layer_kinds, doc_lengths, *,
                held_pairs: float, lora_rank: int, lora_targets) -> float:
    """Forward + backward of a LoRA step over the documents given (real
    tokens only): 4 per frozen weight a token meets (no weight gradient)
    and per expert weight a held pair meets, 6 per adapter weight;
    attention by in-document causal pairs in the attention layers: QK^T
    and PV, forward (2 x 2 x d) and backward (twice that) per pair and
    head; the scan 3 x its forward count a token and state-space
    layer."""
    tokens = sum(int(n) for n in doc_lengths)
    dense = (4.0 * frozen_params(dims, layer_kinds)
             + 6.0 * lora_params(dims, layer_kinds, lora_rank,
                                 lora_targets)) * tokens
    routed = 4.0 * expert_params(dims) * float(held_pairs)
    n_ssm = sum(kind == "mamba" for kind, _ in layer_kinds)
    d_attn = dims["heads"] * dims["head_dim"]
    pairs = attention_pairs(doc_lengths) * (len(layer_kinds) - n_ssm)
    scan = 3.0 * scan_position(dims)["flops"] * tokens * n_ssm
    return dense + routed + 12.0 * d_attn * pairs + scan

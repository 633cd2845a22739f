"""The yardstick's arithmetic for the routed decoder (``exaone_moe``):
operations and bytes that the mathematics needs, from shapes, the real
documents and the program's count of held pairs. Recomputation is never
counted, padding never billed, and an expert is billed for the pairs it
was given, not for its bank.
"""

from __future__ import annotations

from typing import Dict, Iterable, Sequence

from benchmark import weights_moe as wm


def _size(dims, name) -> int:
    a, b = wm.leaf_shape(dims, name)
    return a * b


def frozen_params(dims: Dict[str, int], layer_kinds) -> int:
    """Frozen weights that every real token is multiplied with: each
    layer's attention, the dense layers' MLP, a sparse layer's shared
    expert and router, and the head slice (not the embedding rows it
    merely reads, not the routed experts: those are billed by pair)."""
    total = dims["hidden"] * dims["vocab"]
    for _, mlp in layer_kinds:
        total += sum(_size(dims, n) for n in wm.ATTENTION)
        if mlp == "dense":
            total += sum(_size(dims, n) for n in wm.DENSE_MLP)
        else:
            total += _size(dims, "router")
            if dims["shared"]:
                total += sum(_size(dims, n) for n in wm.SHARED)
    return total


def expert_params(dims: Dict[str, int]) -> int:
    """Weights of one routed expert: what one (token, expert) pair meets."""
    return sum(_size(dims, n) for n in wm.EXPERT)


def lora_params(dims: Dict[str, int], layer_kinds, rank: int,
                targets: Sequence[str]) -> int:
    total = 0
    for _, mlp in layer_kinds:
        for t in wm.lora_targets(targets, mlp, dims):
            a, b = wm.leaf_shape(dims, t)
            total += rank * (a + b)
    return total


def attention_pairs(lengths: Iterable[int], window=None) -> int:
    """(query, key) pairs within each document: causal, and within
    ``window`` positions where one is given."""
    total = 0
    for n in lengths:
        n = int(n)
        if window is None or n <= window:
            total += n * (n + 1) // 2
        else:
            total += window * (window + 1) // 2 + (n - window) * window
    return total


def train_flops(dims: Dict[str, int], layer_kinds, doc_lengths, *,
                held_pairs: float, lora_rank: int, lora_targets,
                window: int) -> float:
    """Forward + backward of a LoRA step over the documents given (real
    tokens only): 4 per frozen weight a token meets (no weight gradient)
    and per expert weight a held pair meets, 6 per adapter weight;
    attention by in-document, in-window pairs: QK^T and PV, forward
    (2 x 2 x d) and backward (twice that) per pair and layer."""
    tokens = sum(int(n) for n in doc_lengths)
    dense = (4.0 * frozen_params(dims, layer_kinds)
             + 6.0 * lora_params(dims, layer_kinds, lora_rank,
                                 lora_targets)) * tokens
    routed = 4.0 * expert_params(dims) * float(held_pairs)
    d_attn = dims["heads"] * dims["head_dim"]
    pairs = sum(attention_pairs(doc_lengths,
                                window if attn == "sliding" else None)
                for attn, _ in layer_kinds)
    return dense + routed + 12.0 * d_attn * pairs


def flash_call(dims: Dict[str, int], rows: int, seq: int, pairs: float,
               act_bytes: int = 2) -> Dict[str, Dict[str, float]]:
    """What one call of each flash kernel needs at [rows, seq] when its
    rows hold ``pairs`` (query, key) pairs a head in all. fwd: QK^T, PV.
    dq: QK^T again, dP, dQ. dkv: QK^T again, dV, dP, dK. Bytes as
    ``flops.flash_call`` has them: every kernel reads q, k, v whole."""
    dh = dims["head_dim"]
    per_head = dims["heads"] * float(pairs)
    q = rows * seq * dims["heads"] * dh * act_bytes
    kv = rows * seq * dims["kv_heads"] * dh * act_bytes
    return {
        "flash_fwd": {"flops": 4.0 * dh * per_head, "bytes": 2 * q + 2 * kv},
        "flash_dq": {"flops": 6.0 * dh * per_head, "bytes": 3 * q + 2 * kv},
        "flash_dkv": {"flops": 8.0 * dh * per_head, "bytes": 2 * q + 4 * kv},
    }

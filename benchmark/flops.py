"""The yardstick's arithmetic: operations and bytes that the mathematics
needs, from shapes alone. Recomputation is never counted, padding never
billed. Copied in spirit from ``train/metrics.py::train_flops_per_token``
(4N for LoRA, causal half) with two corrections: the embedding table is a
gather and does no matmul, and the attention term follows the real
document lengths and not the padded row.
"""

from __future__ import annotations

from typing import Dict, Iterable, Sequence

from benchmark.weights import PROJECTIONS, leaf_shape


def layer_matmul_params(dims: Dict[str, int]) -> int:
    return sum(a * b for a, b in (leaf_shape(dims, n) for n in PROJECTIONS))


def matmul_params(dims: Dict[str, int]) -> int:
    """Weights that a token is multiplied with: the layers' projections
    and the output head (not the embedding rows it merely reads)."""
    return (dims["layers"] * layer_matmul_params(dims)
            + dims["hidden"] * dims["vocab"])


def lora_params(dims: Dict[str, int], rank: int,
                targets: Sequence[str] = PROJECTIONS) -> int:
    return dims["layers"] * sum(
        rank * (leaf_shape(dims, t)[0] + leaf_shape(dims, t)[1])
        for t in targets)


def attention_pairs(lengths: Iterable[int]) -> int:
    """(query, key) pairs of causal attention within each document."""
    return sum(int(n) * (int(n) + 1) // 2 for n in lengths)


def train_flops(dims: Dict[str, int], doc_lengths: Sequence[int], *,
                trainable: str, lora_rank: int = 0) -> float:
    """Forward + backward of the documents given (real tokens only).

    full: 6 per weight and token. lora: 4 per frozen weight (no weight
    gradient) and 6 per adapter weight. Attention: QK^T and PV, forward
    (2 x 2 x d) and backward (twice that) per pair and layer."""
    tokens = sum(int(n) for n in doc_lengths)
    n = matmul_params(dims)
    if trainable == "lora":
        dense = (4.0 * n + 6.0 * lora_params(dims, lora_rank)) * tokens
    elif trainable == "full":
        dense = 6.0 * n * tokens
    else:
        raise ValueError(f"trainable={trainable!r}")
    d_attn = dims["heads"] * dims["head_dim"]
    attn = 12.0 * dims["layers"] * d_attn * attention_pairs(doc_lengths)
    return dense + attn


def flash_call(dims: Dict[str, int], rows: int, seq: int,
               act_bytes: int = 2) -> Dict[str, Dict[str, float]]:
    """What one call of each flash kernel needs at [rows, seq]: causal
    pairs only. fwd: QK^T, PV. dq: QK^T again, dP, dQ. dkv: QK^T again,
    dV, dP, dK (the backward is two kernels and each recomputes)."""
    pairs = rows * dims["heads"] * attention_pairs([seq])
    dh = dims["head_dim"]
    q = rows * seq * dims["heads"] * dh * act_bytes
    kv = rows * seq * dims["kv_heads"] * dh * act_bytes
    return {
        "fwd": {"flops": 4.0 * dh * pairs, "bytes": 2 * q + 2 * kv},
        "dq": {"flops": 6.0 * dh * pairs, "bytes": 3 * q + 2 * kv},
        "dkv": {"flops": 8.0 * dh * pairs, "bytes": 2 * q + 4 * kv},
    }

"""Blockwise weight quantization — the TPU-native bitsandbytes (D5).

The reference gets 4-bit NF4 base weights + LoRA from CUDA kernels
(``BitsAndBytesConfig(load_in_4bit=True, bnb_4bit_quant_type="nf4")``,
ray-jobs/fine_tune_llama_ray.py:216-227). Here quantization is a pytree
transform: each targeted weight leaf becomes a ``QTensor`` (codes +
per-group scales, group along the input dim), dequantized on the fly
inside the jitted forward — XLA fuses the dequant into the consuming
matmul's prologue, and the frozen base stays 4-bit/8-bit in HBM, which
is what makes 8B QLoRA fit a single 16 GB v5e chip.

- "nf4": 4-bit NormalFloat codebook (the QLoRA data type), absmax-scaled
  per group. The codes are ``jnp.uint4`` of the weight's own shape, on
  every backend: the device packs two a byte (XLA bills an executable
  half a byte a code, and the v5e takes them as arguments of the AOT
  step, stacked by ``lax.map`` and sliced inside the fusion that holds
  the dequant: PERF.md, PR 29), while ``itemsize`` / ``nbytes`` and a
  host copy still say a byte a code (``train/remat.py::shard_bytes``
  bills bits). There is no other storage and no key.
- "int8": symmetric per-group int8 (the load_in_8bit analogue).

Scales keep the rank of the weight (input dim / group), so one
PartitionSpec serves both the codes and the scales — quantized trees
shard with the same spec tree as fp32 ones.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

# NF4 codebook (QLoRA appendix E; public constant) — the 16 values are
# quantiles of N(0,1) normalized to [-1, 1].
NF4_CODEBOOK = np.array([
    -1.0, -0.6961928009986877, -0.5250730514526367, -0.39491748809814453,
    -0.28444138169288635, -0.18477343022823334, -0.09105003625154495, 0.0,
    0.07958029955625534, 0.16093020141124725, 0.24611230194568634,
    0.33791524171829224, 0.44070982933044434, 0.5626170039176941,
    0.7229568362236023, 1.0], dtype=np.float32)

DEFAULT_GROUP = 64
# weights the fine-tune quantizes — same set LoRA adapts (the reference's
# bnb pass covers LLAMA_TARGET_MODULES, fine_tune_config.json:30-33);
# the shared canonical tuple lives in models.config (leaf module) so
# quantize→merge→export stay structurally in sync without a train↔ops cycle
from gke_ray_train_tpu.models.config import (
    LATENT_TARGETS, PROJ_TARGETS, SHARED_TARGETS)

QUANT_TARGETS = PROJ_TARGETS + SHARED_TARGETS + LATENT_TARGETS

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QTensor:
    """codes [..., D, F] (``uint4`` for "nf4": half a byte a weight on
    the device; ``int8`` for "int8") + scales [..., D/group, F] fp32."""
    codes: jnp.ndarray
    scales: jnp.ndarray
    kind: str = "nf4"
    group: int = DEFAULT_GROUP

    def tree_flatten(self):
        return (self.codes, self.scales), (self.kind, self.group)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], *aux)

    @property
    def shape(self):
        return self.codes.shape

    @property
    def dtype(self):  # the *logical* dtype consumers see post-dequant
        return jnp.float32


def is_qtensor(x: Any) -> bool:
    return isinstance(x, QTensor)


def stored_bits(dtype) -> int:
    """Bits an element of ``dtype`` takes on the device: ``itemsize``
    says a byte for the sub-byte integers that XLA packs."""
    dtype = jnp.dtype(dtype)
    if jnp.issubdtype(dtype, jnp.integer):
        return jnp.iinfo(dtype).bits
    return dtype.itemsize * 8


@partial(jax.jit, static_argnames=("kind", "group"))
def quantize_tensor(w: jnp.ndarray, kind: str = "nf4",
                    group: int = DEFAULT_GROUP) -> QTensor:
    """Quantize along the input dim (axis -2) in groups of ``group``."""
    *lead, D, F = w.shape
    if D % group:
        # largest divisor of D <= group (tiny/smoke models have odd dims)
        group = next(g for g in range(min(group, D), 0, -1) if D % g == 0)
    wg = w.astype(jnp.float32).reshape(*lead, D // group, group, F)
    absmax = jnp.max(jnp.abs(wg), axis=-2, keepdims=True)  # [..., G, 1, F]
    if kind == "nf4":
        scales = absmax
        normed = wg / jnp.where(scales > 0, scales, 1.0)
        book = jnp.asarray(NF4_CODEBOOK)
        codes = jnp.argmin(
            jnp.abs(normed[..., None] - book),
            axis=-1).astype(jnp.uint4)
    elif kind == "int8":
        scales = absmax / 127.0
        codes = jnp.round(
            wg / jnp.where(scales > 0, scales, 1.0)
        ).clip(-127, 127).astype(jnp.int8)
    else:
        raise ValueError(f"unknown quant kind {kind!r}")
    return QTensor(codes.reshape(*lead, D, F),
                   scales[..., 0, :].astype(jnp.float32),
                   kind, group)


def _nf4_lookup(codes: jnp.ndarray) -> jnp.ndarray:
    """Codebook lookup. On TPU: a flat select chain — a per-element
    gather from a 16-entry table lowers to a catastrophically slow TPU
    gather (measured 23x step slowdown); 15 VPU selects are ~free. On
    CPU (the host-merge export path): the select chain is the slow one
    (15 full passes over an 8B-element tensor), a table take is one."""
    c = codes.astype(jnp.int32)
    on_cpu_eager = (not isinstance(codes, jax.core.Tracer)
                    and all(d.platform == "cpu"
                            for d in codes.devices()))
    if on_cpu_eager:
        return jnp.asarray(NF4_CODEBOOK, jnp.float32)[c]
    out = jnp.full(c.shape, NF4_CODEBOOK[0], jnp.float32)
    for i in range(1, 16):
        out = jnp.where(c == i, NF4_CODEBOOK[i], out)
    return out


def dequantize(qt: QTensor, dtype=jnp.bfloat16) -> jnp.ndarray:
    *lead, D, F = qt.codes.shape
    g = qt.group
    codes = qt.codes.reshape(*lead, D // g, g, F)
    scales = qt.scales[..., :, None, :]
    if qt.kind == "nf4":
        vals = _nf4_lookup(codes)
    else:
        vals = codes.astype(jnp.float32)
    return (vals * scales).reshape(*lead, D, F).astype(dtype)


def maybe_dequantize(w: Any, dtype) -> jnp.ndarray:
    """Transparent hook for the model forward: fp weights pass through."""
    if is_qtensor(w):
        return dequantize(w, dtype)
    return w.astype(dtype)


def quantize_params(params: Any, kind: str = "nf4",
                    group: int = DEFAULT_GROUP,
                    targets=QUANT_TARGETS) -> Any:
    """Quantize the targeted matmul weights of a param tree in place
    (returns a new tree; norms/embed/lm_head stay full precision, like
    the reference's bnb pass which only rewrites the proj modules)."""
    def rec(node):
        if isinstance(node, dict):
            return {k: (quantize_tensor(v, kind, group)
                        if k in targets and not is_qtensor(v)
                        else rec(v))
                    for k, v in node.items()}
        if isinstance(node, list):
            return [rec(c) for c in node]
        return node

    return rec(params)


SERVE_QUANT_KINDS = ("none", "int8", "nf4")


def quantize_for_serving(params: Any, kind: str,
                         group: int = DEFAULT_GROUP) -> Any:
    """The serving engine's weight-encoding hook (serve/engine.py):
    ``"none"`` passes the tree through untouched (serve whatever dtype
    the checkpoint holds); ``"int8"``/``"nf4"`` quantize the projection
    targets in place — already-quantized leaves (a QLoRA base) are left
    as they are, so a quantized training artifact round-trips."""
    kind = (kind or "none").strip().lower()
    if kind == "none":
        return params
    if kind not in SERVE_QUANT_KINDS:
        raise ValueError(f"serve quant kind {kind!r}; use "
                         f"{'|'.join(SERVE_QUANT_KINDS)}")
    return quantize_params(params, kind=kind, group=group)


def quant_specs(specs: Any, params: Any, mesh=None) -> Any:
    """Spec tree matching a quantized param tree: QTensor codes reuse the
    weight's spec; scales reuse it too except on dims too small to shard
    (the group dim is D/group long — with few groups it must replicate)."""
    from jax.sharding import PartitionSpec

    def axis_size(ax):
        if ax is None or mesh is None:
            return 1
        names = ax if isinstance(ax, tuple) else (ax,)
        size = 1
        for n in names:
            size *= mesh.shape[n]
        return size

    def fit(spec, shape):
        if mesh is None:
            return spec
        dims = list(spec) + [None] * (len(shape) - len(spec))
        return PartitionSpec(*[
            ax if shape[d] % max(axis_size(ax), 1) == 0 else None
            for d, ax in enumerate(dims)])

    def rec(spec_node, p_node):
        if is_qtensor(p_node):
            return QTensor(fit(spec_node, p_node.codes.shape),
                           fit(spec_node, p_node.scales.shape),
                           p_node.kind, p_node.group)
        if isinstance(p_node, dict):
            return {k: rec(spec_node[k], v) for k, v in p_node.items()}
        if isinstance(p_node, list):
            return [rec(s, c) for s, c in zip(spec_node, p_node)]
        return spec_node

    return rec(specs, params)

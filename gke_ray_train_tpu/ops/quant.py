"""Blockwise weight quantization — the TPU-native bitsandbytes (D5).

The reference gets 4-bit NF4 base weights + LoRA from CUDA kernels
(``BitsAndBytesConfig(load_in_4bit=True, bnb_4bit_quant_type="nf4")``,
ray-jobs/fine_tune_llama_ray.py:216-227). Here quantization is a pytree
transform: each targeted weight leaf becomes a ``QTensor`` (codes +
per-group scales, group along the input dim), dequantized on the fly
inside the jitted forward, and the frozen base stays 4-bit/8-bit in
HBM, which is what makes 8B QLoRA fit a single 16 GB v5e chip. On the
v5e a decode is one fusion of its own (half a byte a weight in, the
compute dtype out, 4.5-4.9 ps a weight) and the product after it reads
the decoded weight; ``dequantize`` says why it is not the product's
prologue, and what that cost until PR 31 (PERF.md section 6).

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
    LATENT_TARGETS, PROJ_TARGETS, SHARED_TARGETS, SSM_TARGETS)

QUANT_TARGETS = PROJ_TARGETS + SHARED_TARGETS + LATENT_TARGETS \
    + SSM_TARGETS

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
    """``NF4_CODEBOOK[code]`` as float32, bit for bit. Traced (and on
    any device but the CPU): a select tree on the code's four bits, 8
    selects between pairs of constants by bit 0, then 4, 2, 1: 15
    selects and 7 mask operations a weight, where
    the chain of ``where(c == i, book[i], out)`` that stood here until
    PR 31 took 30. A per-element gather from the 16-entry table lowers
    to a catastrophically slow TPU gather (measured 23x step slowdown).
    What the v5e measured (PERF.md section 6, PR 31): in a decode that
    runs as one fusion the tree costs 0 to 0.6 ps a weight over no
    lookup at all and the chain 1.1 to 1.8; ``select_n`` reads as the
    chain, a 4 x 4 tree as this one. Eager on the CPU
    (the host-merge export path) selects are the slow way (a full pass
    over an 8B-element tensor each) and a table take is one pass."""
    on_cpu_eager = (not isinstance(codes, jax.core.Tracer)
                    and all(d.platform == "cpu"
                            for d in codes.devices()))
    c = codes.astype(jnp.int32)
    if on_cpu_eager:
        return jnp.asarray(NF4_CODEBOOK, jnp.float32)[c]
    level = [jnp.float32(v) for v in NF4_CODEBOOK]
    for mask in [(c & (1 << b)) != 0 for b in range(3)] + [c >= 8]:
        level = [jnp.where(mask, hi, lo)
                 for lo, hi in zip(level[::2], level[1::2])]
    return level[0]


def dequantize(qt: QTensor, dtype=jnp.bfloat16) -> jnp.ndarray:
    """``value(code) * scale`` in float32, cast to ``dtype``.

    The barrier is what PR 31's sweep found the decode's time to be.
    Without it XLA's TPU pipeline sinks the group reshape through the
    elementwise chain onto the scales' broadcast, cannot fuse a
    broadcast through the bitcast that leaves, and writes the broadcast
    to HBM: float32 ``[D, F]``, 4 bytes a weight written and read back
    on every decode, sixteen times the codes' own traffic. That was 12
    of the 16.5 ps a weight of a bank decoded alone and the 31% over the
    roofline that ``nf4_matmul_roofline.train`` read from PR 25 to PR
    30; the lookup was 0.4 ps of it. Behind the barrier the codes arrive
    in the grouped shape as an operand, there is no reshape to sink, and
    the decode is one fusion over half a byte in and two out (4.5-4.9 ps
    a weight alone); the product that consumes it reads the decoded
    weight from HBM as it would a bf16 one. The values are the same
    float32 products, bit for bit. Eager calls have no fusion to keep.

    What the barrier costs: it takes a whole operand, so inside a scan
    over stacked layers the slice of one layer's codes out of the stack
    is a copy of its own before each decode (``dynamic-slice`` fusion,
    half a byte a weight in and out, 1.1 ps a weight on the v5e, 2.7%
    of the dense cell's step) and carries the scan's name, not the
    caller's scope: a profile reads it as unscoped time, and a roofline
    of the caller's scope has to add it back (PERF.md section 6, PR
    31). Storing the codes grouped does not spare it: compiled for a
    described v5e, the slice's own reshape sinks onto the broadcast
    just the same without the barrier, and with it the copy stays
    (ROADMAP S3). ``tests/test_quant.py`` compiles this function for a
    described v5e and fails if the broadcast comes back."""
    *lead, D, F = qt.codes.shape
    g = qt.group
    codes = qt.codes.reshape(*lead, D // g, g, F)
    if isinstance(codes, jax.core.Tracer):
        codes = jax.lax.optimization_barrier(codes)
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

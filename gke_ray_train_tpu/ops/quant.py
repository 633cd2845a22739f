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
Where a projection's operands are whole on one device and its shapes
tile, the decode is inside the product instead (:func:`frozen_matmul`:
the kernel pair ``nf4_matmul`` / ``nf4_matmul_dx``).

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
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

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


# ---------------------------------------------------------------------------
# x @ dequantize(qt) with the decode inside the product: codes and scales
# read a tile at a time, decoded in VMEM and fed to the MXU, so that the
# decoded weight never reaches HBM
# ---------------------------------------------------------------------------

# read off scripts/nf4_matmul_sweep.py on the v5e (its table is in
# PERF.md section 6): the rows a call where the kernel beat the decode
# and the product in turn (32 and 2048; one packed row of 8192 lost in
# dx, where a weight is decoded once a row tile), and the tiles a grid
# step at most: forward (rows, columns, contraction), dx (columns,
# contraction)
NF4_MIN_ROWS = 32
NF4_MAX_ROWS = 2048
NF4_TILE = (2048, 2048, 512)
NF4_DX_TILE = (1024, 1024)


class Nf4Plan(NamedTuple):
    """How :func:`frozen_matmul` runs one product ``[rows, depth] x
    [depth, cols]``: ``impl`` ``pallas`` (the kernels ``nf4_matmul``
    forward, a weight tile of ``[depth, cols]`` and ``rows`` rows a grid
    step, and ``nf4_matmul_dx`` backward, a weight tile of ``[dx_depth,
    dx_cols]``) or ``xla`` (:func:`dequantize`, then the product); tiles
    0 under ``xla``."""
    impl: str
    rows: int = 0
    cols: int = 0
    depth: int = 0
    dx_cols: int = 0
    dx_depth: int = 0


def _largest_tile(size: int, step: int, most: int) -> int:
    """The largest multiple of ``step`` up to ``most`` that divides
    ``size``; 0 where none does."""
    return next((t for t in range(most - most % step, 0, -step)
                 if size % t == 0), 0)


def nf4_matmul_plan(rows: int, depth: int, cols: int, kind: str,
                    group: int = DEFAULT_GROUP) -> Nf4Plan:
    """The one rule that picks the form of a frozen product, from shapes
    alone: the kernel pair for an NF4 leaf whose rows a call lie in
    [:data:`NF4_MIN_ROWS`, :data:`NF4_MAX_ROWS`] and whose weight tiles
    whole: the contraction in steps of eight groups (a tile of scales is
    then whole sublanes), the columns in steps of 128 lanes, the rows in
    steps of 16 (bf16 sublanes), each the largest that divides, up to
    :data:`NF4_TILE` forward and :data:`NF4_DX_TILE` for dx. Everything
    else (int8 leaves, widths that do not tile, one packed row of 8192)
    takes ``dequantize`` + einsum."""
    if kind != "nf4" or not NF4_MIN_ROWS <= rows <= NF4_MAX_ROWS:
        return Nf4Plan("xla")
    tiles = (_largest_tile(rows, 16, NF4_TILE[0]),
             _largest_tile(cols, 128, NF4_TILE[1]),
             _largest_tile(depth, 8 * group, NF4_TILE[2]),
             _largest_tile(cols, 128, NF4_DX_TILE[0]),
             _largest_tile(depth, 8 * group, NF4_DX_TILE[1]))
    if not all(tiles):
        return Nf4Plan("xla")
    return Nf4Plan("pallas", *tiles)


def frozen_matmul(x: jnp.ndarray, w: Any, dtype, *,
                  whole: bool = False) -> jnp.ndarray:
    """``x [B, S, depth] @ w [depth, cols]`` in ``dtype``: the frozen
    base of a projection (``models/transformer.py::_proj``). ``w`` a
    QTensor or a float weight. ``whole``: the operands are whole on one
    device (the caller's mesh has one), so that a kernel may take them;
    a Mosaic kernel cannot be partitioned. Where that holds, ``x`` is
    traced in ``dtype`` and :func:`nf4_matmul_plan` picks the kernel,
    :func:`nf4_matmul`; else ``dequantize`` + einsum."""
    if whole and is_qtensor(w) and isinstance(x, jax.core.Tracer) \
            and x.dtype == jnp.dtype(dtype):
        *lead, depth = x.shape
        rows = int(np.prod(lead))
        plan = nf4_matmul_plan(rows, depth, w.shape[-1], w.kind, w.group)
        if plan.impl == "pallas":
            return nf4_matmul(x.reshape(rows, depth), w, plan=plan
                              ).reshape(*lead, w.shape[-1])
    return jnp.einsum("bsd,dh->bsh", x, maybe_dequantize(w, dtype))


def nf4_geometry(params: Any, rows: int, whole: bool) -> dict:
    """The frozen products of a step for ``rows`` a micro-batch (the
    ``step_build`` span's ``nf4_matmul``): by weight shape
    ``"depth x cols"``, the form :func:`nf4_matmul_plan` picks, its
    tiles (rows, columns, contraction; ``dx_tiles`` the same for dx) and
    the calls a micro-pass (layers stacked on the leaf); then the calls
    a micro-pass of each form. Stacked projections only (a bank of
    experts is rank 4 and decoded whole, ``ops/moe.py``); ``{}`` without
    a quantized one."""
    shapes: dict = {}

    def visit(node):
        if is_qtensor(node) and node.codes.ndim == 3:
            layers, depth, cols = node.codes.shape
            plan = (nf4_matmul_plan(rows, depth, cols, node.kind, node.group)
                    if whole else Nf4Plan("xla"))
            key = f"{depth}x{cols}"
            entry = shapes.setdefault(key, {
                "impl": plan.impl, "kind": node.kind,
                "tiles": [plan.rows, plan.cols, plan.depth],
                "dx_tiles": [plan.rows, plan.dx_cols, plan.dx_depth],
                "calls": 0})
            entry["calls"] += layers
        elif isinstance(node, dict):
            for v in node.values():
                visit(v)
        elif isinstance(node, (list, tuple)):
            for v in node:
                visit(v)
    visit(params)
    if not shapes:
        return {}
    calls = {impl: sum(s["calls"] for s in shapes.values()
                       if s["impl"] == impl) for impl in ("pallas", "xla")}
    return {"rows": rows, "shapes": shapes, **calls}


def nf4_matmul(x: jnp.ndarray, qt: QTensor, *,
               plan: Optional[Nf4Plan] = None,
               interpret: Optional[bool] = None) -> jnp.ndarray:
    """``x [rows, depth] @ dequantize(qt) [depth, cols] -> [rows, cols]``
    in x's dtype, the weights decoded a tile at a time inside the
    kernel: the same values as ``dequantize``'s, bit for bit (the select
    tree, ``value * scale`` in float32, one rounding), products summed
    in float32 and rounded once on the way out. Differentiable in ``x``
    (the kernel ``nf4_matmul_dx``: ``dy @ dequantize(qt)^T``, the same
    tiles decoded again); the leaf is frozen and gets no cotangent, and
    the leaf is all the backward keeps. ``plan`` overrides
    :func:`nf4_matmul_plan`'s (tests, the sweep); off the chip the
    kernels run interpreted."""
    from gke_ray_train_tpu.ops.flash_attention import interpret_default
    rows, depth = x.shape
    plan = plan or nf4_matmul_plan(rows, depth, qt.shape[-1], qt.kind,
                                   qt.group)
    if plan.impl != "pallas":
        raise ValueError(f"no kernel tiles {x.shape} x {qt.shape} "
                         f"({qt.kind}); use frozen_matmul")
    return _nf4_product(x, qt, plan, interpret_default(interpret))


def _decode_tile(codes: jnp.ndarray, scales: jnp.ndarray, group: int,
                 dtype) -> jnp.ndarray:
    """codes [rows, cols] uint4, scales [rows / group, cols] -> [rows,
    cols] in ``dtype``: ``dequantize``'s arithmetic on one tile."""
    tk, tn = codes.shape
    vals = _nf4_lookup(codes).reshape(tk // group, group, tn)
    return (vals * scales[:, None, :]).reshape(tk, tn).astype(dtype)


def _nf4_fwd_kernel(x_ref, codes_ref, scales_ref, out_ref, acc_ref, *,
                    group: int):
    """Grid (rows, columns, contraction): decode a weight tile, multiply,
    add into the float32 accumulator; the last contraction step writes."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = _decode_tile(codes_ref[...], scales_ref[...], group, x_ref.dtype)
    acc_ref[...] += jnp.dot(x_ref[...], w,
                            preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _nf4_dx_kernel(dy_ref, codes_ref, scales_ref, out_ref, acc_ref, *,
                   group: int):
    """Grid (rows, contraction tiles of the forward, its columns): the
    same weight tile decoded, ``dy [tm, tn] . w [tk, tn]`` over tn."""
    n = pl.program_id(2)

    @pl.when(n == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = _decode_tile(codes_ref[...], scales_ref[...], group, dy_ref.dtype)
    acc_ref[...] += jax.lax.dot_general(
        dy_ref[...], w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(n == pl.num_programs(2) - 1)
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _nf4_specs(a, codes, scales, plan: Nf4Plan, *, dx: bool) -> dict:
    """One direction's grid, blocks and VMEM: ``a`` is x [rows, depth]
    forward and dy [rows, cols] for dx; the weight tile is [plan.depth,
    plan.cols] forward and [plan.dx_depth, plan.dx_cols] for dx."""
    (rows, _), (depth, cols) = a.shape, codes.shape
    tm = plan.rows
    tn, tk = (plan.dx_cols, plan.dx_depth) if dx else (plan.cols,
                                                       plan.depth)
    group = depth // scales.shape[0]
    if dx:     # grid: rows, the forward's contraction tiles, its columns
        grid = (rows // tm, depth // tk, cols // tn)
        a_block, out = (tm, tn), (tm, tk, depth)
        a_at = lambda i, j, n: (i, n)   # noqa: E731
        w_at = lambda i, j, n: (j, n)   # noqa: E731
    else:      # grid: rows, columns, contraction
        grid = (rows // tm, cols // tn, depth // tk)
        a_block, out = (tm, tk), (tm, tn, cols)
        a_at = lambda i, j, k: (i, k)   # noqa: E731
        w_at = lambda i, j, k: (k, j)   # noqa: E731
    # two buffers of each operand and of the result, the accumulator,
    # and the decode's float32 temporaries
    vmem = (2 * (a_block[0] * a_block[1] + out[0] * out[1]) * a.dtype.itemsize
            + 4 * out[0] * out[1] + (tk * tn // 2 + tk // group * tn * 4) * 2
            + 4 * 4 * tk * tn)
    return dict(
        grid=grid,
        in_specs=[pl.BlockSpec(a_block, a_at), pl.BlockSpec((tk, tn), w_at),
                  pl.BlockSpec((tk // group, tn), w_at)],
        out_specs=pl.BlockSpec(out[:2], lambda i, j, k: (i, j)),
        scratch_shapes=[pltpu.VMEM(out[:2], jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((rows, out[2]), a.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem + 16 * 2**20))


@partial(jax.jit, static_argnames=("plan", "interpret"))
def _nf4_fwd_pallas(x, codes, scales, *, plan: Nf4Plan, interpret: bool):
    """Jitted, each direction: the layers of a step share one trace and
    one lowering of each shape."""
    return pl.pallas_call(
        partial(_nf4_fwd_kernel, group=codes.shape[0] // scales.shape[0]),
        **_nf4_specs(x, codes, scales, plan, dx=False),
        interpret=interpret,
        name="nf4_matmul",
    )(x, codes, scales)


@partial(jax.jit, static_argnames=("plan", "interpret"))
def _nf4_dx_pallas(dy, codes, scales, *, plan: Nf4Plan, interpret: bool):
    return pl.pallas_call(
        partial(_nf4_dx_kernel, group=codes.shape[0] // scales.shape[0]),
        **_nf4_specs(dy, codes, scales, plan, dx=True),
        interpret=interpret,
        name="nf4_matmul_dx",
    )(dy, codes, scales)


@partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _nf4_product(x, qt, plan, interpret):
    return _nf4_fwd_pallas(x, qt.codes, qt.scales, plan=plan,
                           interpret=interpret)


def _nf4_product_fwd(x, qt, plan, interpret):
    return _nf4_product(x, qt, plan, interpret), qt


def _nf4_product_bwd(plan, interpret, qt, dy):
    return _nf4_dx_pallas(dy, qt.codes, qt.scales, plan=plan,
                          interpret=interpret), None


_nf4_product.defvjp(_nf4_product_fwd, _nf4_product_bwd)


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

"""Mixture-of-Experts MLP with GSPMD expert parallelism (SURVEY.md §2c
row EP — out of scope for the reference, built here for completeness).

TPU-first design (GShard/Switch lineage): routing is expressed as three
einsums against a static-capacity dispatch tensor, NOT per-token gather/
scatter — every op keeps static shapes, the expert FFN is one batched
matmul over the expert dim (MXU-friendly), and *expert parallelism is a
sharding spec*: the expert dim of the weight bank shards over the
``model`` mesh axis, so GSPMD inserts the token all-to-alls that
dedicated MoE frameworks hand-write (the same way DP gradient psums are
implied by batch sharding).

Capacity semantics: each expert accepts at most
``C = capacity_factor * top_k * S / E`` tokens per batch row (dispatch
is per-row, so the tensor stays O(S²) not O((B·S)²)). Overflow tokens
contribute nothing from the dropped expert slot — their MLP output is
just the remaining slots' weighted sum (possibly zero → pure residual
passthrough), matching Switch/GShard drop behavior.

Router numerics are fp32 end-to-end (softmax over experts is
precision-critical at E=8..64); expert matmuls run in the model compute
dtype.

That is the ``router="softmax"`` layer (Mixtral), and it stays as it is:
its capacity einsums are what GSPMD partitions into the expert exchange.
``router="sigmoid"`` (DeepSeek-V3's layer, K-EXAONE's) and
``router="topk_softmax"`` (Granite's: the largest logits selected, their
softmax the weights) are :func:`routed_experts` below: no capacity and
no drop. The (token, held
expert) pairs are sorted by expert into a buffer sized for the worst
case the held share allows (every one of a token's picks held), and the
three products run over the real group sizes (:func:`grouped_dot`: a
kernel that visits only the tiles the groups cover), so imbalance costs
time and never a token. The combine's forward and the dispatch's
backward sum a token's K rows of the buffer (:func:`gather_sum`): where
rows are wide, a kernel that copies them into VMEM and sums them there,
so that no ``[T, K, D]`` tensor reaches HBM. The
layer is told which experts it holds (``cfg.experts_held``); it scores
all ``n_experts``, normalises a token's weights over all it selected,
and computes the pairs that land in its range, which is what one
expert-parallel rank computes between the exchanges. On one chip there
is no exchange, and nothing here stands in for one.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from gke_ray_train_tpu.models.config import ModelConfig
from gke_ray_train_tpu.obs.trace import scope
from gke_ray_train_tpu.ops.flash_attention import interpret_default

# per-step counters of the dropless layer, in the train step's metrics:
# pairs dispatched to held experts (summed over layers and
# micro-batches), the fullest held expert's pairs over the mean (the
# largest of any layer), and picks of held experts that found no room
# in the buffer (0 by construction: the buffer is the worst case)
COUNTERS = ("moe_pairs", "moe_max_load", "moe_pairs_dropped")


def stats_init(cfg: ModelConfig) -> dict:
    """What a layer loop carries beside the activations: the softmax
    router's aux loss, or the sigmoid layer's counters."""
    names = ("router_aux",) if cfg.router == "softmax" else COUNTERS
    return {n: jnp.zeros((), jnp.float32) for n in names}


def stats_merge(a: dict, b: dict) -> dict:
    return {n: jnp.maximum(a[n], b[n]) if n == "moe_max_load"
            else a[n] + b[n] for n in a}


def expert_capacity(cfg: ModelConfig, seq_len: int) -> int:
    """Static per-row expert capacity, padded to a multiple of 8 lanes."""
    c = int(cfg.capacity_factor * cfg.expert_top_k * seq_len
            / cfg.n_experts)
    return max(8 * ((c + 7) // 8), 8)


def moe_mlp(x: jnp.ndarray, router_w: jnp.ndarray, w_gate: jnp.ndarray,
            w_up: jnp.ndarray, w_down: jnp.ndarray, cfg: ModelConfig,
            dtype, weights: jnp.ndarray = None) -> tuple:
    """x [B, S, D] → (y [B, S, D], aux_loss scalar fp32).

    router_w [D, E]; w_gate/w_up [E, D, F]; w_down [E, F, D].
    aux_loss is the Switch load-balance term E * Σ_e f_e · p_e (=1 when
    perfectly balanced); the train step adds cfg.router_aux_coef of it.

    ``weights`` (optional [B, S], e.g. the loss weights): f_e/p_e become
    weighted means, so on padded (non-packed) batches the router is
    pressured to balance REAL tokens, not padding (ADVICE r4). All-zero
    weights (pipeline garbage ticks) yield aux = 0.

    Memory: the two [B, S, E, C] tensors (combine/dispatch) are built in
    the compute ``dtype`` — at Mixtral seq-4096 shapes the old fp32
    combine alone was ~256 MB per batch row saved for backward (VERDICT
    r4 weak #4). Router numerics (softmax, top-k, gate renorm, aux) stay
    fp32; only the per-slot gate value rounds once to ``dtype``.
    """
    with scope("moe/route"):
        combine, aux = _route(x, router_w, cfg, dtype, weights)
    with scope("moe/experts"):
        return _experts(x, combine, w_gate, w_up, w_down, cfg,
                        dtype), aux


def _route(x, router_w, cfg: ModelConfig, dtype, weights):
    """Router softmax, top-k, aux loss and the static-capacity combine
    tensor [B, S, E, C] (gate value at each token's expert slot)."""
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.expert_top_k
    C = expert_capacity(cfg, S)

    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)            # [B, S, E] fp32
    gate_k, idx_k = jax.lax.top_k(probs, K)            # [B, S, K]
    # Mixtral-style renormalization over the selected experts
    gate_k = gate_k / jnp.sum(gate_k, axis=-1, keepdims=True)

    # Switch aux loss: fraction routed (first-choice counts per expert)
    # x mean router prob, scaled by E — (weighted) means over tokens
    first = jax.nn.one_hot(idx_k[..., 0], E, dtype=jnp.float32)
    if weights is None:
        f_e = jnp.mean(first, axis=(0, 1))
        p_e = jnp.mean(probs, axis=(0, 1))
    else:
        w = weights.astype(jnp.float32)[..., None]     # [B, S, 1]
        wsum = jnp.maximum(jnp.sum(w), 1e-9)
        f_e = jnp.sum(first * w, axis=(0, 1)) / wsum
        p_e = jnp.sum(probs * w, axis=(0, 1)) / wsum
    aux = E * jnp.sum(f_e * p_e)

    # Static-capacity dispatch: slot k assignments take positions after
    # all slot-(k-1) assignments (priority to higher-gate choices),
    # positions count per (row, expert) via cumsum along the sequence.
    combine = jnp.zeros((B, S, E, C), dtype)
    base = jnp.zeros((B, 1, E), jnp.float32)
    for k in range(K):
        oh = jax.nn.one_hot(idx_k[..., k], E, dtype=jnp.float32)  # [B,S,E]
        pos = jnp.cumsum(oh, axis=1) - 1.0 + base                 # [B,S,E]
        base = base + jnp.sum(oh, axis=1, keepdims=True)
        keep = oh * (pos < C).astype(jnp.float32)
        slot = jax.nn.one_hot(pos.astype(jnp.int32).clip(0, C - 1), C,
                              dtype=dtype)                        # [B,S,E,C]
        combine = combine \
            + slot * (keep * gate_k[..., k:k + 1]).astype(dtype)[..., None]
    return combine, aux


def _experts(x, combine, w_gate, w_up, w_down, cfg: ModelConfig, dtype):
    """Dispatch, the batched expert FFN and the weighted combine."""
    # deferred import (ops.quant registers a pytree class; only needed
    # when the expert bank is a quantized QLoRA base)
    from gke_ray_train_tpu.ops.quant import maybe_dequantize

    # every dispatch/expert einsum declares fp32 accumulation and
    # rounds ONCE on the way out (kernelcheck KER005: a bf16
    # dot_general without preferred_element_type accumulates — and
    # rounds — the whole contraction in bf16). The big [B, S, E, C]
    # combine/dispatch tensors stay in the compute dtype (the VERDICT
    # r4 memory fix); only the transient einsum results ride fp32.
    f32 = jnp.float32
    dispatch = (combine > 0).astype(dtype)             # [B, S, E, C]
    xin = jnp.einsum("bsec,bsd->ebcd", dispatch, x.astype(dtype),
                     preferred_element_type=f32).astype(dtype)
    gate = jnp.einsum("ebcd,edf->ebcf", xin,
                      maybe_dequantize(w_gate, dtype),
                      preferred_element_type=f32).astype(dtype)
    up = jnp.einsum("ebcd,edf->ebcf", xin, maybe_dequantize(w_up, dtype),
                    preferred_element_type=f32).astype(dtype)
    if cfg.activation == "silu":
        act = jax.nn.silu(gate)
    elif cfg.activation == "gelu_tanh":
        act = jax.nn.gelu(gate, approximate=True)
    else:
        raise ValueError(f"unknown activation {cfg.activation}")
    h = jnp.einsum("ebcf,efd->ebcd", act * up,
                   maybe_dequantize(w_down, dtype),
                   preferred_element_type=f32).astype(dtype)
    y = jnp.einsum("bsec,ebcd->bsd", combine, h,
                   preferred_element_type=f32)
    return y.astype(dtype)


# ---------------------------------------------------------------------------
# the sigmoid router's layer: no drops, grouped products over held experts
# ---------------------------------------------------------------------------

# (rows, contraction, columns) of a tile of the grouped product on the
# chip, at most. At the K-EXAONE share's shapes (65536 buffer rows of
# which 8192 live, 16 groups, 6144 x 2048) this tiling reads 2.0 ms a
# product on a v5e against 2.3-2.9 for four others and 2.8 for XLA's own
# lowering of `jax.lax.ragged_dot` (my chip run, PR 26)
GMM_TILING = (256, 1024, 2048)


def gmm_tiling(m: int, k: int, n: int) -> tuple:
    """The tile of one grouped product ``[m, k] x [groups, k, n]`` from
    its own shapes: in the contraction and in the columns the largest
    multiple of 128 that divides the dimension, up to
    :data:`GMM_TILING`'s (the dimension itself where none does). The
    kernel's backward hands this very function the shapes of the dx
    product (the same kernel over the transposed bank, contraction and
    columns exchanged), so each direction gets a tile that divides its
    own: an expert of 2048 x 1536 takes (256, 1024, 1536) forward and
    (256, 768, 2048) backward, where one clamped tuple left a third of
    a contraction tile and a quarter of a column tile empty. At 6144 x
    2048 both directions read (256, 1024, 2048), as before the rule."""
    def tile(size, most):
        fits = [t for t in range(128, min(most, size) + 1, 128)
                if size % t == 0]
        return fits[-1] if fits else size
    return (min(GMM_TILING[0], m), tile(k, GMM_TILING[1]),
            tile(n, GMM_TILING[2]))


def grouped_dot(x, w, sizes):
    """x [P, K] rows sorted by group, w [G, K, N], sizes [G] int32 ->
    [P, N]: row p times the matrix of its group. Rows past
    ``sum(sizes)`` are NOT defined (the kernel never visits them).

    On the chip: the megablox grouped-matmul kernel (its own
    ``custom_vjp``: dx is the same kernel over the transposed bank, and
    the bank's gradient, where nothing asks for it, is dead code), at
    the tile :func:`gmm_tiling` reads off each product's shapes. It
    keeps the scope it runs under in the profile, which XLA's lowering
    of ``jax.lax.ragged_dot`` to its own kernel does not (the op_name
    becomes ``ragged-dot-none``). Elsewhere ``ragged_dot`` itself."""
    from gke_ray_train_tpu.parallel.mesh import on_tpu
    if not on_tpu():
        return jax.lax.ragged_dot(x, w, sizes)
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    return gmm(x, w, sizes, preferred_element_type=x.dtype,
               tiling=gmm_tiling)


def pair_buffer_rows(cfg: ModelConfig, tokens: int) -> int:
    """Rows of the sorted pair buffer: every pick of every token held."""
    return tokens * min(cfg.expert_top_k, cfg.n_experts_held)


def gather_geometry(cfg: ModelConfig, tokens: int) -> dict:
    """The routed layer's gather-and-sum (the combine's forward and the
    dispatch's backward) for ``tokens`` a micro-batch: the form
    :func:`gather_plan` picks, its token tile, the pair buffer's rows,
    the bytes of a row and the picks a token (the ``step_build`` span's
    ``moe_gather``); ``{}`` without such a layer."""
    if not cfg.dropless_router or cfg.n_layers <= cfg.n_dense_layers:
        return {}
    rows = pair_buffer_rows(cfg, tokens)
    dtype = jnp.dtype(cfg.dtype)
    plan = gather_plan(rows, cfg.d_model, cfg.expert_top_k, dtype, tokens)
    return {"impl": plan.impl, "token_tile": plan.token_tile, "rows": rows,
            "row_bytes": cfg.d_model * dtype.itemsize,
            "picks": cfg.expert_top_k}


def select_experts(x, router_w, router_bias, cfg: ModelConfig):
    """x [T, D] -> (idx [T, K] int32, weights [T, K] float32).

    "sigmoid": scores ``sigmoid(x R)`` in float32; the K experts with
    the largest ``score + bias`` are selected; the weights come from the
    scores alone, renormalised over the K selected and scaled.
    "topk_softmax": the K largest logits ``x R`` are selected and the
    weights are the softmax over those K logits."""
    logits = jnp.einsum(
        "td,de->te", x.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST)
    if cfg.router == "topk_softmax":
        top, idx = jax.lax.top_k(logits, cfg.expert_top_k)
        return idx, jax.nn.softmax(top, axis=-1) * cfg.router_scale
    s = jax.nn.sigmoid(logits)
    biased = s if router_bias is None \
        else s + router_bias.astype(jnp.float32)
    _, idx = jax.lax.top_k(biased, cfg.expert_top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if cfg.router_renorm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, w * cfg.router_scale


# ---------------------------------------------------------------------------
# y[t] = sum_k w[t, k] buf[row[t, k]]: the combine's forward and the
# dispatch's backward, a token's K rows summed in VMEM
# ---------------------------------------------------------------------------

# read off scripts/moe_gather_sweep.py on the v5e (PERF.md section 6,
# PR 35): the kernel issues a row's copy in ~21 ns whatever its size, so
# it wins where a row is 8 KB or more (a hidden of 4096 in bf16) and
# XLA's form wins at 4 KB; tokens a grid step (16 to 256 read within 1%
# but at 12 KB rows, where 32 led by 5%); VMEM for the two slots
GATHER_MIN_ROW_BYTES = 8192
GATHER_TILE = 32
GATHER_SLOTS_BYTES = 16 * 2**20


class GatherPlan(NamedTuple):
    """How :func:`gather_sum` runs one shape: ``impl`` ``pallas`` (the
    kernel ``moe_gather_sum``) or ``xla`` (the sum unrolled over K in
    ``jax.numpy``); ``token_tile`` the tokens of a grid step (0 under
    ``xla``)."""
    impl: str
    token_tile: int


def gather_plan(rows: int, width: int, picks: int, dtype,
                tokens: Optional[int] = None) -> GatherPlan:
    """The one rule that picks the form, from shapes alone: the kernel
    where a row holds :data:`GATHER_MIN_ROW_BYTES` or more and is whole
    tiles (``width / 128`` a multiple of the sublanes of one: 8, or 16
    for a type packed two a word), with the largest multiple of 8 tokens
    up to :data:`GATHER_TILE` that divides ``tokens`` (``rows // picks``,
    the worst-case buffer's, by default) and keeps both slots within
    :data:`GATHER_SLOTS_BYTES`. Else the ``jax.numpy`` form."""
    tokens = rows // picks if tokens is None else tokens
    item = jnp.dtype(dtype).itemsize
    sublanes = 8 * 4 // item
    most = min(GATHER_TILE, tokens,
               GATHER_SLOTS_BYTES // (2 * picks * width * item))
    if width * item >= GATHER_MIN_ROW_BYTES \
            and width % (128 * sublanes) == 0:
        for tile in range(most - most % 8, 0, -8):
            if tokens % tile == 0:
                return GatherPlan("pallas", tile)
    return GatherPlan("xla", 0)


def gather_sum(buf: jnp.ndarray, row: jnp.ndarray, w: jnp.ndarray, *,
               plan: Optional[GatherPlan] = None,
               interpret: Optional[bool] = None) -> jnp.ndarray:
    """buf [P, D], row [T, K] int32, w [T, K] -> [T, D] in buf's dtype:
    ``y[t] = sum_k w[t, k] buf[row[t, k]]``, each product and the sum in
    float32 and one rounding on the way out. Every pick's row is read;
    one whose weight is 0 adds 0 whatever its row holds (a buffer row
    past the live ones may hold anything). ``plan`` overrides
    :func:`gather_plan`'s (tests, the sweep); off the chip the kernel
    runs interpreted."""
    T, K = row.shape
    P, D = buf.shape
    plan = plan or gather_plan(P, D, K, buf.dtype, T)
    w = w.astype(jnp.float32)
    if plan.impl == "pallas":
        out = _gather_sum_pallas(buf.reshape(P, D // 128, 128), row, w,
                                 tile=plan.token_tile,
                                 interpret=interpret_default(interpret))
        return out.reshape(T, D)
    return _gather_sum_xla(buf, row, w)


def _gather_sum_xla(buf, row, w):
    acc = None
    for k in range(row.shape[1]):
        wk = w[:, k:k + 1]
        term = jnp.where(wk != 0, buf[row[:, k]].astype(jnp.float32) * wk,
                         0.0)
        acc = term if acc is None else acc + term
    return acc.astype(buf.dtype)


def _gather_sum_kernel(row_ref, w_ref, buf_ref, out_ref, rows, sem, *,
                       tile: int, picks: int):
    """A grid step: the K rows of each of ``tile`` tokens, copied from
    HBM into one slot and summed there a token at a time. A row is
    ``[D / 128, 128]``, whole tiles, so that each copy is one aligned
    block; the next step's copies start before this step's wait, into
    the other slot."""
    i = pl.program_id(0)
    slot = i % 2

    def fetch(step, into):
        first = step * tile * picks

        def one_token(t, carry):
            for k in range(picks):
                pltpu.make_async_copy(
                    buf_ref.at[row_ref[first + t * picks + k]],
                    rows.at[into, k, t], sem.at[into]).start()
            return carry
        jax.lax.fori_loop(0, tile, one_token, 0)

    @pl.when(i == 0)
    def _():
        fetch(0, 0)

    @pl.when(i + 1 < pl.num_programs(0))
    def _():
        fetch(i + 1, 1 - slot)

    # one wait for the whole slot: the semaphore counts what has landed
    pltpu.make_async_copy(rows.at[slot], rows.at[slot], sem.at[slot]).wait()

    def one_sum(t, carry):
        acc = jnp.zeros(out_ref.shape[1:], jnp.float32)
        for k in range(picks):
            wk = w_ref[t, k]
            acc = acc + jnp.where(
                wk != 0, rows[slot, k, t].astype(jnp.float32) * wk, 0.0)
        out_ref[t] = acc.astype(out_ref.dtype)
        return carry
    jax.lax.fori_loop(0, tile, one_sum, 0)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _gather_sum_pallas(buf, row, w, *, tile: int, interpret: bool):
    """buf [P, D / 128, 128] (a row is whole tiles, so that each copy is
    one aligned block), ``row`` [T, K] scalar-prefetched flat (a step
    starts the next one's copies), ``w`` [T, K] a tile at a time in
    SMEM -> [T, D / 128, 128]. Jitted: the routed layers of a step share
    one trace and one lowering a use."""
    (_, lanes, _), (T, K) = buf.shape, row.shape
    slots = 2 * K * tile * lanes * 128 * buf.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_gather_sum_kernel, tile=tile, picks=K),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(T // tile,),
            in_specs=[pl.BlockSpec((tile, K), lambda i, row: (i, 0),
                                   memory_space=pltpu.SMEM),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tile, lanes, 128),
                                   lambda i, row: (i, 0, 0)),
            scratch_shapes=[pltpu.VMEM((2, K, tile, lanes, 128), buf.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((T, lanes, 128), buf.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=slots + 8 * 2**20),
        interpret=interpret,
        name="moe_gather_sum",
    )(row.reshape(T * K), w, buf)


@jax.custom_vjp
def _dispatch(x, tok, row, held):
    """x [T, D] -> x[tok] [P, D]. ``row`` [T, K]: the buffer row of each
    pair, read only where ``held``. The transpose of a gather is a
    scatter-add, which the chip serialises (21 ms for 65536 rows of
    6144 against 7 for the gather below: PERF.md, PR 26); the sort has
    both directions of the permutation, so dx is a gather too."""
    return x[tok]


def _dispatch_fwd(x, tok, row, held):
    return x[tok], (row, held)


def _dispatch_bwd(res, g):
    row, held = res
    return gather_sum(g, row, held), None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(out, w, tok, row, pair):
    """y[t] = sum_k w[t, k] out[row[t, k]] -> [T, D]; ``w`` is 0 where
    the pair is not held. ``tok`` [P] / ``pair`` [P]: the token and the
    flat (t, k) of each buffer row, for the way back."""
    return _weighted_rows(out, w, row)


def _weighted_rows(out, w, row):
    # the weights rounded to the compute dtype, as the einsum this
    # replaced rounded them
    return gather_sum(out, row, w.astype(out.dtype))


def _combine_fwd(out, w, tok, row, pair):
    return _weighted_rows(out, w, row), (out, w, tok, row, pair)


def _combine_bwd(res, dy):
    out, w, tok, row, pair = res
    dy_rows = dy[tok]                                   # [P, D]
    w_rows = w.reshape(-1)[pair]                        # [P]
    d_out = dy_rows * w_rows[:, None].astype(dy.dtype)
    d_w_rows = jnp.sum(out.astype(jnp.float32)
                       * dy_rows.astype(jnp.float32), axis=-1)
    # a row past the live ones belongs to a pair that is not held: its
    # weight is 0 and nothing reads its d_w
    d_w = jnp.where(w != 0, d_w_rows[row], 0.0).astype(w.dtype)
    return d_out, d_w, None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def routed_experts(x: jnp.ndarray, lp: dict, cfg: ModelConfig, dtype,
                   valid: jnp.ndarray = None,
                   buffer_rows: int = None) -> tuple:
    """The routed part of the dropless layer for the experts held here:
    x [B, S, D] -> (y [B, S, D], counters).

    ``lp``: ``router`` [D, E], optional ``router_bias`` [E], the held
    bank ``w_gate`` / ``w_up`` [G, D, F] and ``w_down`` [G, F, D]
    (QTensors are dequantised here). ``valid`` [B, S] bool: positions
    that are tokens (padding is not routed). ``buffer_rows``: rows of
    the pair buffer when not the worst case (tests plant a capacity to
    see ``moe_pairs_dropped`` count)."""
    from gke_ray_train_tpu.ops.quant import maybe_dequantize
    B, S, D = x.shape
    T, K = B * S, cfg.expert_top_k
    lo, hi = cfg.held_range
    G = hi - lo
    P = pair_buffer_rows(cfg, T) if buffer_rows is None else buffer_rows
    xf = x.reshape(T, D)
    with scope("moe/route"):
        idx, w = select_experts(xf, lp["router"], lp.get("router_bias"),
                                cfg)
    with scope("moe/dispatch"):
        held = (idx >= lo) & (idx < hi)
        if valid is not None:
            held &= valid.reshape(T, 1)
        # sorted by expert, the pairs not held (sentinel G) last
        e = jnp.where(held, idx - lo, G).reshape(T * K)
        order = jnp.argsort(e, stable=True)[:P].astype(jnp.int32)
        sizes = jnp.bincount(e, length=G + 1)[:G].astype(jnp.int32)
        wanted = jnp.sum(sizes)
        # a planted capacity cuts the last groups short
        sizes = jnp.diff(jnp.minimum(jnp.cumsum(sizes), P), prepend=0)
        pairs = jnp.sum(sizes)
        tok = order // K
        row = jnp.zeros((T * K,), jnp.int32).at[order].set(
            jnp.arange(P, dtype=jnp.int32), unique_indices=True
        ).reshape(T, K)
        in_buffer = jnp.zeros((T * K,), bool).at[order].set(
            jnp.arange(P) < pairs, unique_indices=True).reshape(T, K)
        held &= in_buffer
        xs = _dispatch(xf.astype(dtype), tok, row, held)
    with scope("moe/experts"):
        dot = grouped_dot
        gate = dot(xs, maybe_dequantize(lp["w_gate"], dtype), sizes)
        up = dot(xs, maybe_dequantize(lp["w_up"], dtype), sizes)
        gate, up = (checkpoint_name(t, "moe/experts") for t in (gate, up))
        if cfg.activation == "silu":
            act = jax.nn.silu(gate)
        elif cfg.activation == "gelu_tanh":
            act = jax.nn.gelu(gate, approximate=True)
        else:
            raise ValueError(f"unknown activation {cfg.activation}")
        # rows past the live ones hold whatever the kernel left there:
        # their weights are 0, and gather_sum selects, never multiplies
        out = dot(act * up, maybe_dequantize(lp["w_down"], dtype), sizes)
    with scope("moe/combine"):
        y = _combine(out, jnp.where(held, w, 0.0), tok, row, order)
    mean = jnp.maximum(pairs, 1).astype(jnp.float32) / G
    counters = {
        "moe_pairs": pairs.astype(jnp.float32),
        "moe_max_load": jnp.max(sizes).astype(jnp.float32) / mean,
        "moe_pairs_dropped": (wanted - pairs).astype(jnp.float32)}
    return y.reshape(B, S, D), counters

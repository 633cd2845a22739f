"""Flash attention — Pallas TPU kernel (forward + backward).

The hot op of every model family. The reference delegates attention to
dense-mask ``nn.TransformerEncoder`` math (ray-jobs/pytorch_llm_ray.py:91-99)
and whatever HF dispatches for Llama (SURVEY.md row D8: "custom Pallas
kernels only where XLA underperforms"). This kernel is the TPU-native
replacement: blockwise online-softmax attention that never materializes
the [S, T] logits or mask in HBM, with

- GQA folded into the index map (a KV block is DMA'd once per query-head
  group — no repeated K/V in HBM);
- masking computed in-kernel from *positions + segment IDs* (packing,
  SURVEY.md §5.7), plus causality, optional sliding window (Gemma-2) and
  logit softcap;
- fp32 online softmax, bf16 MXU matmuls;
- a custom VJP whose backward is two more Pallas kernels (dq and dk/dv)
  that recompute probabilities from the saved logsumexp — flash memory
  behavior in the backward too;
- a grid that walks only the band of blocks causality and the window
  leave alive, where the call site says its rows allow it
  (``rows_ordered``: self-attention, positions rising inside a
  segment). The kv axis of ``flash_fwd`` / ``flash_dq`` (the q axis of
  ``flash_dkv``) then has the band's extent, its index maps start at
  the band's first block for the outer block (integer arithmetic on
  ``program_id``s: no scalar prefetch, no further operand), and at
  blocks sized from the window (``window_blocks``). Without a window
  the extent stays rectangular and the index is clamped to the
  diagonal: a step past it names the block already there, so nothing is
  copied, and its body is predicated off. Inside the band
  ``_block_live`` still skips, in the kernel, the blocks that documents
  make dead (those are fetched); ``_block_mask`` stays exact on
  positions and segments either way. Other callers (ring attention's
  shifted kv slices, a prefill against a cache) keep the full grid, on
  which every dead block is fetched and only its matmuls are skipped.

Semantics oracle: ops/attention.py::dot_product_attention — the tests
check both values and grads against it, in interpret mode on CPU.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from gke_ray_train_tpu.ops.attention import NEG_INF

# tuned on v5e (8x2048x16h/8kv/128dh bf16 fwd+bwd sweep: 13.1 ms vs
# 18.6 @ 256/512, 32.4 for the XLA dense-mask path); env overrides for
# per-topology A/B without code edits (numeric values re-validated by
# pick_block at every call site; empty = unset, junk fails by name)
import os as _os


def _block_env(name: str, default: int) -> int:
    """Env-overridable block size (shared with the fused epilogue
    kernels — ops/fused_norm_rope.py / ops/fused_ce.py import it)."""
    raw = _os.environ.get(name, "").strip()
    if not raw:
        return default
    try:
        return int(raw)
    except ValueError:
        raise ValueError(f"{name}={raw!r} is not an integer") from None


def interpret_default(interpret: "Optional[bool]") -> bool:
    """Resolve the Pallas interpret default: off-TPU (CPU smoke/tests)
    the Mosaic kernels can't compile, so the same kernel runs under the
    interpreter. One rule for every kernel module."""
    if interpret is None:
        from gke_ray_train_tpu.parallel.mesh import on_tpu
        return not on_tpu()
    return interpret


DEFAULT_BLOCK_Q = _block_env("FLASH_BLOCK_Q", 256)
DEFAULT_BLOCK_KV = _block_env("FLASH_BLOCK_KV", 1024)


def _block_mask(q_pos, kv_pos, q_seg, kv_seg, causal, window):
    """[bq, bkv] bool mask from per-block position/segment vectors."""
    qp = q_pos[:, None]
    kp = kv_pos[None, :]
    mask = q_seg[:, None] == kv_seg[None, :]
    mask &= kv_seg[None, :] != 0
    if causal:
        mask &= kp <= qp
    if window is not None:
        mask &= kp > qp - window
    return mask


def _softcap_fwd(s, cap):
    return jnp.tanh(s / cap) * cap if cap is not None else s


def _block_live(q_pos, kv_pos, q_seg, kv_seg, causal, window):
    """Block-level skip predicate shared by fwd/dq/dkv kernels.

    Dead block ⇔ no (q, kv) pair can be unmasked:
    - causal future: every kv newer than every q;
    - window-expired past: every kv at or older than every q - window
      (mask keeps ``kv > q - window``, so max(kv) <= min(q) - window is
      provably all-masked — conservative under packed/per-segment
      positions, since any in-window pair violates it);
    - segment-disjoint: the mask keeps only q_seg == kv_seg != 0, so
      non-overlapping [min, max] segment-id ranges can contain no equal
      pair (if max(q_seg) < min(kv_seg) or vice versa, every pair
      differs). Packed rows number documents 1..N along the sequence,
      making attention block-diagonal — with the causal skip this cuts
      the scanned area from O(S²/2) toward O(Σ len(doc)²/2). An
      all-padding (segment-0) block is disjoint from every real one and
      skips too.
    A block this predicate turns off was still fetched: only the
    matmuls are skipped. The causal and window clauses bite on the full
    grid alone (on long sliding-window sequences the window clause cuts
    the scanned KV area from O(S²/2) to O(S·window)); under
    ``rows_ordered`` the grid does not visit those blocks in the first
    place (``kernel_bands``), and what is left to this predicate is the
    documents' part."""
    live = (not causal) or (jnp.max(q_pos) >= jnp.min(kv_pos))
    if window is not None:
        live = live & (jnp.max(kv_pos) > jnp.min(q_pos) - window)
    live = live & (jnp.min(q_seg) <= jnp.max(kv_seg)) \
        & (jnp.min(kv_seg) <= jnp.max(q_seg))
    return live


FULL_BLOCK_LIMIT = 2048  # max seq to load as one VMEM block

# Mosaic's default scoped-VMEM limit is 16 MiB of a v5e core's 128. At
# Llama-3.1-8B widths the fused epilogue kernels (ops/fused_norm_rope.py,
# ops/fused_ce.py) need more for their default blocks — 22 MiB for rope
# over 32+8 heads of 128, 39 MiB for the cross-entropy dx kernel at
# d_model 4096 — so they ask for this much instead.
FUSED_VMEM_LIMIT_BYTES = 48 * 2**20


def estimate_vmem_bytes(block_q: int, block_kv: int, head_dim: int,
                        dtype_bytes: int) -> int:
    """Static VMEM footprint of one fwd-kernel grid step — the number
    kernelcheck's KER002 compares against the chip's per-core budget.

    Counts the I/O blocks the BlockSpecs DMA (q, k, v, o, lse, plus the
    int32 position/segment vectors) double-buffered — Pallas pipelines
    the next grid step's DMA against this step's compute — and the fp32
    scratch (acc + the [block_q, 128] m/l accumulators). An estimate,
    not Mosaic's allocator: it exists to catch order-of-magnitude
    misconfiguration (FLASH_BLOCK_KV=32768) in lint, not to pack VMEM.
    """
    io = (block_q * head_dim * dtype_bytes            # q block
          + 2 * block_kv * head_dim * dtype_bytes     # k, v blocks
          + block_q * head_dim * dtype_bytes          # o block
          + block_q * 4                               # lse row (fp32)
          + 2 * (block_q + block_kv) * 4)             # pos/seg (int32)
    scratch = (block_q * head_dim * 4                 # acc (fp32)
               + 2 * block_q * 128 * 4)               # m, l (fp32)
    return 2 * io + scratch


def pick_block(requested: int, n: int) -> int:
    """A block size that tiles n exactly and satisfies Mosaic tiling.

    The Pallas grid covers n // block blocks — a non-divisor block would
    silently leave the tail rows unwritten, so this guard is mandatory
    for every caller of the kernels (flash_attention and ring_attention).
    Preference: largest 128-multiple divisor of n that is <= requested;
    otherwise the full length (a block equal to the array dim is always
    tiling-legal), capped by VMEM sanity."""
    best = None
    for b in range(128, min(requested, n) + 1, 128):
        if n % b == 0:
            best = b
    if best is None:
        if n <= FULL_BLOCK_LIMIT:
            best = n
        else:
            raise ValueError(
                f"sequence length {n} has no 128-multiple block divisor "
                f"<= {requested} and is too long for a single block; pad "
                f"to a multiple of 128 and mask via segment_ids")
    return best


# ---------------------------------------------------------------------------
# the band: which blocks a kernel's inner grid axis visits
# ---------------------------------------------------------------------------

def _band_blocks(i, own: int, other: int, n_other: int, back, ahead,
                 mx=max, mn=min):
    """First and last block (of ``other`` indices each) that holds an
    index of ``[i*own - back, (i+1)*own - 1 + ahead]``: what block ``i``
    (``own`` indices) of a kernel's outer axis can meet on its inner
    axis. ``back`` / ``ahead`` None = no bound on that side. Integer
    arithmetic only, so that it runs on Python ints (the extent, at
    trace time) and, with ``mx`` / ``mn`` from jnp, on ``program_id``s
    inside an index map or a kernel."""
    first = 0 if back is None else mx(i * own - back, 0) // other
    last = n_other - 1 if ahead is None else mn(
        ((i + 1) * own - 1 + ahead) // other, n_other - 1)
    return first, last


@dataclasses.dataclass(frozen=True)
class Band:
    """The inner grid axis of one kernel: ``steps`` grid steps that
    start at block ``first(i)`` of the inner operand, for block ``i`` of
    the outer one. ``geom`` is None where that is every block in order
    (the full grid: index ``j``, nothing predicated)."""

    steps: int
    geom: Optional[tuple]     # _band_blocks' (own, other, n_other, back, ahead)
    visited: int              # steps inside the band, over all outer blocks
    rectangular: int          # n_outer x n_inner

    def index(self, i, j):
        """Block of the inner operand at grid step ``(i, j)``. Past the
        band's end the index stays on its last block: a step whose block
        index did not change issues no copy."""
        if self.geom is None:
            return j
        first, last = _band_blocks(i, *self.geom, mx=jnp.maximum,
                                   mn=jnp.minimum)
        return jnp.minimum(first + j, last)

    def inside(self, i, j):
        """Whether grid step ``(i, j)`` is one of the band's (True, a
        Python bool, on the full grid). A step past the end sits on a
        block the kernel has already run: its body must not run again."""
        if self.geom is None:
            return True
        first, last = _band_blocks(i, *self.geom, mx=jnp.maximum,
                                   mn=jnp.minimum)
        return first + j <= last


def band_of(n_outer: int, n_inner: int, own: int, other: int, *,
            back, ahead) -> Band:
    """The inner axis for ``n_outer`` blocks of ``own`` indices against
    ``n_inner`` blocks of ``other``, each meeting what lies within
    ``back`` before and ``ahead`` after it (None: no bound). Where that
    is every block anyway (no bound on either side; one inner block; a
    window wider than the row), the full grid, by the same code as
    before there was a band."""
    geom = (own, other, n_inner, back, ahead)
    spans = [_band_blocks(i, *geom) for i in range(n_outer)]
    widths = [last - first + 1 for first, last in spans]
    if all(span == (0, n_inner - 1) for span in spans):
        geom = None
    return Band(max(widths), geom, sum(widths), n_outer * n_inner)


def kernel_bands(S: int, T: int, block_q: int, block_kv: int, *,
                 causal: bool, window: Optional[int],
                 rows_ordered: bool) -> Dict[str, Band]:
    """The inner axes of the three kernels: ``fwd`` and ``dq`` walk kv
    blocks for a query block (a query row meets keys no later than
    itself and, under a window, at most ``window - 1`` earlier), ``dkv``
    walks query blocks for a kv block (the transpose).

    Valid under the contract ``rows_ordered`` states: q and kv are the
    same rows and positions rise with the index inside a segment. Then
    ``kv_pos <= q_pos`` iff ``kv_idx <= q_idx`` there, and a pair is
    never further apart in index than in position, so every unmasked
    pair lies inside the band whatever the documents are."""
    n_q, n_kv = S // block_q, T // block_kv
    # nothing stated, or not causal self-attention: no bound either way
    past, future = None, None
    if rows_ordered and causal and S == T:
        past, future = (None if window is None else window - 1), 0
    kv_axis = band_of(n_q, n_kv, block_q, block_kv, back=past,
                      ahead=future)
    q_axis = band_of(n_kv, n_q, block_kv, block_q, back=future,
                     ahead=past)
    return {"fwd": kv_axis, "dq": kv_axis, "dkv": q_axis}


def _when(cond, body):
    """``pl.when`` that takes a Python True for "always"."""
    if cond is True:
        body()
    else:
        pl.when(cond)(body)


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(qp_ref, kp_ref, qs_ref, ks_ref, q_ref, k_ref, v_ref,
                o_ref, lse_ref, acc, m_s, l_s, *,
                scale, causal, window, softcap, band):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _():
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc[:] = jnp.zeros_like(acc)

    def visit():
        q_pos = qp_ref[0, 0]
        kv_pos = kp_ref[0, 0]
        # block-level skip (causal future + window-expired past +
        # segment-disjoint): see _block_live. The block was fetched,
        # the compute is skipped.
        run = _block_live(q_pos, kv_pos, qs_ref[0, 0], ks_ref[0, 0],
                          causal, window)

        @pl.when(run)
        def _():
            q = q_ref[0, 0]
            k = k_ref[0, 0]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            s = _softcap_fwd(s, softcap)
            mask = _block_mask(q_pos, kv_pos, qs_ref[0, 0], ks_ref[0, 0],
                               causal, window)
            s = jnp.where(mask, s, NEG_INF)

            m_prev = m_s[:, 0]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
            # masked entries sit at NEG_INF; with a fully-masked row
            # m_new is also NEG_INF and exp(s - m_new) would be
            # exp(0)=1 — re-zero via the mask so such rows keep l == 0
            # (and o == 0 downstream).
            p = jnp.exp(s - m_new[:, None]) * mask
            alpha = jnp.exp(m_prev - m_new)
            l_new = l_s[:, 0] * alpha + jnp.sum(p, axis=-1)
            acc[:] = acc[:] * alpha[:, None] + jax.lax.dot_general(
                p.astype(v_ref.dtype), v_ref[0, 0],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_s[:] = jnp.broadcast_to(m_new[:, None], m_s.shape)
            l_s[:] = jnp.broadcast_to(l_new[:, None], l_s.shape)

    _when(band.inside(i, j), visit)

    @pl.when(j == band.steps - 1)
    def _():
        l = l_s[:, 0]
        safe_l = jnp.where(l > 0, l, 1.0)
        o_ref[0, 0] = (acc[:] / safe_l[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0, 0] = jnp.where(
            l > 0, m_s[:, 0] + jnp.log(safe_l), NEG_INF)


def _fwd(q, k, v, q_pos, kv_pos, q_seg, kv_seg, *, scale, causal, window,
         softcap, block_q, block_kv, interpret, rows_ordered=False):
    B, H, S, dh = q.shape
    K = k.shape[1]
    T = k.shape[2]
    G = H // K
    band = kernel_bands(S, T, block_q, block_kv, causal=causal,
                        window=window, rows_ordered=rows_ordered)["fwd"]
    kv = band.index

    grid = (B, H, S // block_q, band.steps)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, band=band)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q), lambda b, h, i, j: (b, 0, i)),
            pl.BlockSpec((1, 1, block_kv),
                         lambda b, h, i, j: (b, 0, kv(i, j))),
            pl.BlockSpec((1, 1, block_q), lambda b, h, i, j: (b, 0, i)),
            pl.BlockSpec((1, 1, block_kv),
                         lambda b, h, i, j: (b, 0, kv(i, j))),
            pl.BlockSpec((1, 1, block_q, dh),
                         lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_kv, dh),
                         lambda b, h, i, j: (b, h // G, kv(i, j), 0)),
            pl.BlockSpec((1, 1, block_kv, dh),
                         lambda b, h, i, j: (b, h // G, kv(i, j), 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, dh),
                         lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, 1, block_q),
                         lambda b, h, i, j: (b, h, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, dh), q.dtype),
            jax.ShapeDtypeStruct((B, H, 1, S), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, dh), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_fwd",
    )(q_pos, kv_pos, q_seg, kv_seg, q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------

def _recompute_p(q, k, lse_row, q_pos, kv_pos, q_seg, ks_seg, *,
                 scale, causal, window, softcap):
    """Recompute probabilities + raw logits for one (q,kv) block pair."""
    s_raw = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    s = _softcap_fwd(s_raw, softcap)
    mask = _block_mask(q_pos, kv_pos, q_seg, ks_seg, causal, window)
    s = jnp.where(mask, s, NEG_INF)
    p = jnp.exp(s - lse_row[:, None]) * mask
    return p, s, mask


def _softcap_bwd_factor(s, softcap):
    """d(softcap*tanh(s/softcap))/ds given the *capped* logits s̃."""
    if softcap is None:
        return 1.0
    return 1.0 - (s / softcap) ** 2


def _dq_kernel(qp_ref, kp_ref, qs_ref, ks_ref, q_ref, k_ref, v_ref,
               do_ref, lse_ref, dvec_ref, dq_ref, dq_acc, *,
               scale, causal, window, softcap, band):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def visit():
        q_pos = qp_ref[0, 0]
        kv_pos = kp_ref[0, 0]
        run = _block_live(q_pos, kv_pos, qs_ref[0, 0], ks_ref[0, 0],
                          causal, window)

        @pl.when(run)
        def _():
            q = q_ref[0, 0]
            k = k_ref[0, 0]
            p, s, _ = _recompute_p(
                q, k, lse_ref[0, 0, 0], q_pos, kv_pos, qs_ref[0, 0],
                ks_ref[0, 0], scale=scale, causal=causal, window=window,
                softcap=softcap)
            do = do_ref[0, 0]
            dp = jax.lax.dot_general(
                do, v_ref[0, 0], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = p * (dp - dvec_ref[0, 0, 0][:, None])
            ds = ds * _softcap_bwd_factor(jnp.where(p > 0, s, 0.0), softcap)
            dq_acc[:] += jax.lax.dot_general(
                ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * scale

    _when(band.inside(i, j), visit)

    @pl.when(j == band.steps - 1)
    def _():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _dkv_kernel(qp_ref, kp_ref, qs_ref, ks_ref, q_ref, k_ref, v_ref,
                do_ref, lse_ref, dvec_ref, dk_ref, dv_ref, dk_acc, dv_acc,
                *, scale, causal, window, softcap, band):
    j, i = pl.program_id(2), pl.program_id(3)

    @pl.when(i == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def visit():
        q_pos = qp_ref[0, 0]
        kv_pos = kp_ref[0, 0]
        run = _block_live(q_pos, kv_pos, qs_ref[0, 0], ks_ref[0, 0],
                          causal, window)

        @pl.when(run)
        def _():
            q = q_ref[0, 0]
            k = k_ref[0, 0]
            p, s, _ = _recompute_p(
                q, k, lse_ref[0, 0, 0], q_pos, kv_pos, qs_ref[0, 0],
                ks_ref[0, 0], scale=scale, causal=causal, window=window,
                softcap=softcap)
            do = do_ref[0, 0]
            pt = p.astype(do.dtype)
            dv_acc[:] += jax.lax.dot_general(
                pt, do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(
                do, v_ref[0, 0], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = p * (dp - dvec_ref[0, 0, 0][:, None])
            ds = ds * _softcap_bwd_factor(jnp.where(p > 0, s, 0.0), softcap)
            dk_acc[:] += jax.lax.dot_general(
                ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * scale

    _when(band.inside(j, i), visit)

    @pl.when(i == band.steps - 1)
    def _():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd(res, g, *, scale, causal, window, softcap, block_q, block_kv,
         interpret, rows_ordered=False, dvec=None):
    q, k, v, out, lse, q_pos, kv_pos, q_seg, kv_seg = res
    B, H, S, dh = q.shape
    K, T = k.shape[1], k.shape[2]
    G = H // K
    n_q = S // block_q
    n_kv = T // block_kv
    bands = kernel_bands(S, T, block_q, block_kv, causal=causal,
                         window=window, rows_ordered=rows_ordered)

    # D_i = sum_d do_id * o_id, one scalar per query row (fp32) — tiny,
    # XLA fuses it; not worth a kernel. Ring attention precomputes it
    # once outside its per-shard loop and passes it in.
    if dvec is None:
        dvec = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                       axis=-1)[:, :, None, :]

    def in_specs(qi, ki):
        """The ten operands' specs from the grid's (q block, kv block):
        ``qi`` / ``ki`` take the two inner grid indices."""
        vec_q = pl.BlockSpec((1, 1, block_q),
                             lambda b, h, x, y: (b, 0, qi(x, y)))
        vec_kv = pl.BlockSpec((1, 1, block_kv),
                              lambda b, h, x, y: (b, 0, ki(x, y)))
        rows_q = pl.BlockSpec((1, 1, block_q, dh),
                              lambda b, h, x, y: (b, h, qi(x, y), 0))
        rows_kv = pl.BlockSpec((1, 1, block_kv, dh),
                               lambda b, h, x, y: (b, h // G, ki(x, y), 0))
        row_q = pl.BlockSpec((1, 1, 1, block_q),
                             lambda b, h, x, y: (b, h, 0, qi(x, y)))
        return [vec_q, vec_kv, vec_q, vec_kv, rows_q, rows_kv, rows_kv,
                rows_q, row_q, row_q]

    args = (q_pos, kv_pos, q_seg, kv_seg, q, k, v, g, lse, dvec)

    band = bands["dq"]
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          window=window, softcap=softcap, band=band),
        grid=(B, H, n_q, band.steps),
        in_specs=in_specs(lambda i, j: i, band.index),
        out_specs=pl.BlockSpec((1, 1, block_q, dh),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, dh), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, dh), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_dq",
    )(*args)

    # dk/dv are computed per *query* head ([B, H, T, dh]) so grid programs
    # never write the same block; the GQA group-sum down to K kv heads
    # happens outside, where XLA turns it into a cheap reduce.
    band = bands["dkv"]
    dk_per_h, dv_per_h = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          window=window, softcap=softcap, band=band),
        grid=(B, H, n_kv, band.steps),
        in_specs=in_specs(band.index, lambda j, i: j),
        out_specs=[
            pl.BlockSpec((1, 1, block_kv, dh),
                         lambda b, h, j, i: (b, h, j, 0)),
            pl.BlockSpec((1, 1, block_kv, dh),
                         lambda b, h, j, i: (b, h, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, dh), k.dtype),
            jax.ShapeDtypeStruct((B, H, T, dh), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_kv, dh), jnp.float32),
            pltpu.VMEM((block_kv, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_dkv",
    )(*args)

    dk = dk_per_h.reshape(B, K, G, T, dh).sum(axis=2).astype(k.dtype)
    dv = dv_per_h.reshape(B, K, G, T, dh).sum(axis=2).astype(v.dtype)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

def window_blocks(T: int, window: Optional[int], head_dim: int = 128,
                  q_per_kv: int = 1) -> "tuple[int, int]":
    """The (block_q, block_kv) to ask ``pick_block`` for where the grid
    walks a band over a row of ``T`` (causal self-attention over
    ordered rows): under a window twice the default query block, and
    half the default kv block where the window fits in it; without one
    the defaults, and twice the default query block at ungrouped heads
    of 256 and wider (grouped ones were not swept: they keep the defaults).

    Read off two sweeps on the v5e (scripts/flash_block_sweep.py;
    PERF.md section 6). Heads of 128 (PR 27): a band step costs 1.5-2 us
    before its first product, so at window 128 blocks of 256 x 128 or
    256 x 256 (little dead arithmetic, many steps) lose to 512 x 512
    (two steps a query block) in all three kernels; a wider query block
    adds no step to a band; and past half the default a kv block of the
    default's size wins again. Without a window, or with one that
    reaches across the row, the defaults (tuned for full rows) stand
    there. Heads of 256 (PR 30: 20 ungrouped heads, full causal): a kv
    block is twice the bytes to fetch for the same products a query
    row, and a query block twice as tall halves how often it is
    fetched: 512 x 1024 reads 16.0 ms for the three kernels against
    19.5 at 256 x 1024, 16.3-16.5 at 1024-tall or 512-wide blocks and
    21.9 at 256 x 2048 (512 x 2048 does not fit ``flash_dkv``'s
    VMEM)."""
    if window is None or window >= T:
        tall = 2 if head_dim >= 256 and q_per_kv == 1 else 1
        return tall * DEFAULT_BLOCK_Q, DEFAULT_BLOCK_KV
    half = DEFAULT_BLOCK_KV // 2
    return (2 * DEFAULT_BLOCK_Q,
            half if window <= half else DEFAULT_BLOCK_KV)


def call_plan(S: int, T: int, *, causal: bool, window: Optional[int],
              rows_ordered: bool, head_dim: int = 128, q_per_kv: int = 1,
              block_q: Optional[int] = None,
              block_kv: Optional[int] = None):
    """``(block_q, block_kv, kernel_bands)`` of one ``flash_attention``
    call: what the call does with its grid, from static facts alone, so
    that the step's builder can report it (``flash_grid``:
    models/transformer.py::flash_grids) without tracing anything."""
    # the band needs causal self-attention (kernel_bands); anything
    # else keeps the full grid and the blocks tuned for it
    rows_ordered = rows_ordered and causal and S == T
    want_q, want_kv = window_blocks(T, window, head_dim, q_per_kv) \
        if rows_ordered else (DEFAULT_BLOCK_Q, DEFAULT_BLOCK_KV)
    block_q = pick_block(want_q if block_q is None else block_q, S)
    block_kv = pick_block(want_kv if block_kv is None else block_kv, T)
    return block_q, block_kv, kernel_bands(
        S, T, block_q, block_kv, causal=causal, window=window,
        rows_ordered=rows_ordered)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    q_positions: Optional[jnp.ndarray] = None,
                    kv_positions: Optional[jnp.ndarray] = None,
                    q_segment_ids: Optional[jnp.ndarray] = None,
                    kv_segment_ids: Optional[jnp.ndarray] = None,
                    causal: bool = True,
                    sliding_window: Optional[int] = None,
                    scale: Optional[float] = None,
                    logit_softcap: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_kv: Optional[int] = None,
                    rows_ordered: bool = False,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """Blockwise flash attention.

    q: [B, S, H, dh]; k, v: [B, T, K, dh] with H % K == 0 (GQA).
    positions: [B, len] absolute token positions (default arange — ring
    attention passes shifted slices). segment_ids: [B, len]; 0 = padding
    (never attended). Returns [B, S, H, dh] in q.dtype.

    ``rows_ordered``: the call site's statement that q and kv are the
    same rows and that positions rise with the index inside a segment
    (``data/packing.py::pack_examples`` and the default arange both do).
    With ``causal`` the kernels then walk only the band of blocks that
    causality and the window leave alive (:func:`kernel_bands`), at
    blocks sized from the window (:func:`window_blocks`) unless
    ``block_q`` / ``block_kv`` are given. False (the default): the full
    grid, right for any positions.
    """
    B, S, H, dh = q.shape
    T = k.shape[1]
    if H % k.shape[2]:
        raise ValueError(f"H={H} not a multiple of KV heads {k.shape[2]}")
    interpret = interpret_default(interpret)
    scale = dh ** -0.5 if scale is None else scale
    block_q, block_kv, _ = call_plan(
        S, T, causal=causal, window=sliding_window,
        rows_ordered=rows_ordered, head_dim=dh, q_per_kv=H // k.shape[2],
        block_q=block_q, block_kv=block_kv)

    if q_positions is None:
        q_positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32),
                                       (B, S))
    if kv_positions is None:
        kv_positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32),
                                        (B, T))
    # uniform mask logic in-kernel: absent segment ids = all ones
    if q_segment_ids is None:
        q_segment_ids = jnp.ones((B, S), jnp.int32)
    if kv_segment_ids is None:
        kv_segment_ids = jnp.ones((B, T), jnp.int32)
    # [B, len] → [B, 1, len]: Mosaic requires the last two block dims be
    # (8k, 128k)-divisible or full — a (1, 1, block) slice of [B, 1, len]
    # satisfies that where a (1, block) slice of [B, len] cannot.
    q_positions = q_positions.astype(jnp.int32)[:, None, :]
    kv_positions = kv_positions.astype(jnp.int32)[:, None, :]
    q_segment_ids = q_segment_ids.astype(jnp.int32)[:, None, :]
    kv_segment_ids = kv_segment_ids.astype(jnp.int32)[:, None, :]

    # [B, S, H, dh] → [B, H, S, dh]: head-major blocks so one (head, q
    # block) is a contiguous VMEM tile
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    # config (python scalars only — closing over *tracers* here would
    # leak them across the custom_vjp fwd/bwd trace boundary under remat)
    kw = dict(scale=scale, causal=causal, window=sliding_window,
              softcap=logit_softcap, block_q=block_q, block_kv=block_kv,
              interpret=interpret, rows_ordered=rows_ordered)

    @jax.custom_vjp
    def fa(qt, kt, vt, qp, kp, qs, ks):
        out, _ = _fwd(qt, kt, vt, qp, kp, qs, ks, **kw)
        return out

    def fa_fwd(qt, kt, vt, qp, kp, qs, ks):
        out, lse = _fwd(qt, kt, vt, qp, kp, qs, ks, **kw)
        # the two residuals only this kernel can make, named here in the
        # forward rule so that a block checkpoint can keep both
        # (models/remat.py): with `out` alone kept, `lse` would still
        # force the kernel to run again
        out = checkpoint_name(out, "attn/core")
        lse = checkpoint_name(lse, "attn/core")
        return out, (qt, kt, vt, out, lse, qp, kp, qs, ks)

    def fa_bwd(res, g):
        dq, dk, dv = _bwd(res, g, **kw)
        return dq, dk, dv, None, None, None, None

    fa.defvjp(fa_fwd, fa_bwd)
    out = fa(qt, kt, vt, q_positions, kv_positions, q_segment_ids,
             kv_segment_ids)
    return out.transpose(0, 2, 1, 3)

"""The sequence part of a state-space layer's mixer (Mamba-2; Dao & Gu
2024): a causal depthwise conv and the selective scan in its chunked
(state-space-duality) form, over packed rows.

The recurrence, a head ``h`` of ``P`` values with a state ``S`` of
``[P, N]``::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t
    y_t = S_t C_t + D x_t

and ``S = 0`` before a document's first position. The chunked form
computes the same numbers with products the MXU runs: inside a chunk of
``Q`` positions the scores ``C B^T`` ([Q, Q], one a group), masked by
the cumulated decay ``exp(sum of dt A over (s, t])`` of each head, times
``dt x``; one state a chunk, carried across chunks by the decays of the
whole chunks between.

Packed rows (``data/packing.py::pack_examples``): a document's
positions are contiguous and carry one id in ``segment_ids``, so "no
boundary between s and t" is ``segment[s] == segment[t]``. That one
comparison cuts the decay mask inside a chunk, a chunk's outgoing
state (only the positions of the document that the chunk ends in feed
it), the state a chunk receives (only positions of the document the
previous chunk ended in read it) and the conv's taps. A boundary may
fall anywhere inside a chunk.

The decays are cumulated in float32. The masked decay of all heads of a
layer is ``[chunks, heads, Q, Q]`` float32 (1.07 GB at 32 x 128 x 256 x
256), several times over in a backward pass; :func:`ssd_scan` therefore
runs the quadratic part over blocks of heads under a checkpoint of its
own, so that one block's is alive at a time, forward and backward.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

# heads of one block of the quadratic part: 16 heads x 32 chunks of 256
# hold 134 MB of masked decay in float32
HEAD_BLOCK = 16


def causal_conv(x: jnp.ndarray, w: jnp.ndarray, b: Optional[jnp.ndarray],
                segment_ids: Optional[jnp.ndarray]) -> jnp.ndarray:
    """Depthwise causal conv along the sequence: x [B, S, C], w [C, K]
    (tap ``K - 1`` is the position itself), b [C] or None ->
    ``b + sum_j w[:, j] x[t - (K - 1) + j]`` in x's dtype, summed in
    float32. A tap that lies before the row's start, or in another
    document than ``t``, reads 0."""
    B, S, C = x.shape
    K = w.shape[-1]
    acc = x.astype(jnp.float32) * w[:, K - 1].astype(jnp.float32)
    for shift in range(1, K):
        tap = jnp.pad(x, ((0, 0), (shift, 0), (0, 0)))[:, :S]
        if segment_ids is not None:
            before = jnp.pad(segment_ids, ((0, 0), (shift, 0)),
                             constant_values=-1)[:, :S]
            tap = jnp.where((before == segment_ids)[..., None], tap, 0)
        acc = acc + tap.astype(jnp.float32) \
            * w[:, K - 1 - shift].astype(jnp.float32)
    if b is not None:
        acc = acc + b.astype(jnp.float32)
    return acc.astype(x.dtype)


def scan_geometry(seq: int, chunk: int) -> tuple:
    """(chunk used, chunks a row): the configured chunk where it
    divides the row, else the largest divisor of the row under it (a
    row that is no multiple pays smaller chunks, never padding)."""
    q = min(chunk, seq)
    while seq % q:
        q -= 1
    return q, seq // q


def ssd_scan(x: jnp.ndarray, dt: jnp.ndarray, a: jnp.ndarray,
             b_mat: jnp.ndarray, c_mat: jnp.ndarray, d_skip: jnp.ndarray,
             segment_ids: Optional[jnp.ndarray], *, chunk: int,
             head_block: int = HEAD_BLOCK) -> jnp.ndarray:
    """x [B, S, H, P]; dt [B, S, H] float32 (after softplus); a [H]
    float32 (negative); b_mat, c_mat [B, S, G, N]; d_skip [H];
    segment_ids [B, S] or None (one document a row) -> y [B, S, H, P] in
    x's dtype."""
    B, S, H, P = x.shape
    G, N = b_mat.shape[2:]
    Q, nc = scan_geometry(S, chunk)
    dtype = x.dtype
    f32 = jnp.float32
    seg = jnp.zeros((B, S), jnp.int32) if segment_ids is None \
        else segment_ids.astype(jnp.int32)
    seg = seg.reshape(B, nc, Q)
    per_group = H // G

    def head_major(t):
        """[B, S, H, ...] -> [B, nc, G, H/G, Q, ...]: the chunk's
        positions (and what follows) are the minor dimensions of every
        product below."""
        t = t.reshape((B, nc, Q, G, per_group) + t.shape[3:])
        return jnp.moveaxis(t, 2, 4)

    # cumulated log-decay inside each chunk, float32: [B, nc, G, k, Q]
    cs = jnp.cumsum(head_major(dt.astype(f32) * a.astype(f32)), axis=-1)
    xh = head_major(x)                                  # [B,nc,G,k,Q,P]
    dtx = (xh.astype(f32) * head_major(dt.astype(f32))[..., None]
           ).astype(dtype)
    bc = jnp.moveaxis(b_mat.reshape(B, nc, Q, G, N), 2, 3)  # [B,nc,G,Q,N]
    cc = jnp.moveaxis(c_mat.reshape(B, nc, Q, G, N), 2, 3)
    seg_q = seg[:, :, None, None, :]                    # over G and k

    # ---- a chunk's own state at its end: only the positions of the
    # document the chunk ends in feed it ------------------------------
    last_seg = seg[:, :, -1]                                  # [B, nc]
    feeds = seg_q == last_seg[:, :, None, None, None]
    to_end = jnp.where(feeds, jnp.exp(cs[..., -1:] - cs), 0.0)
    own = jnp.einsum("bcgqn,bcgkqp->bcgkpn", bc,
                     dtx * to_end[..., None].astype(dtype),
                     preferred_element_type=f32)        # [B,nc,G,k,P,N]
    # ---- the state a chunk receives: the earlier chunks' own states,
    # decayed by the whole chunks between, where no boundary lies
    # between (chunk z receives from chunk c < z) ----------------------
    total = cs[..., -1]                                       # [B,nc,G,k]
    upto = jnp.cumsum(total, axis=1)
    # sum of the totals of chunks c+1 .. z-1: [B, z, c, G, k]
    between = (upto - total)[:, :, None] - upto[:, None, :]
    z, c = jnp.arange(nc)[:, None], jnp.arange(nc)[None, :]
    prev_seg = jnp.concatenate(
        [jnp.full((B, 1), -1, jnp.int32), last_seg[:, :-1]], axis=1)
    passes = ((c < z)[None] & (prev_seg[:, :, None] == last_seg[:, None, :])
              )[..., None, None]
    carry = jnp.where(passes, jnp.exp(jnp.where(passes, between, 0.0)), 0.0)
    received = jnp.einsum("bzcgk,bcgkpn->bzgkpn", carry, own,
                          preferred_element_type=f32)
    # read by the positions of the document the previous chunk ended in
    reads = seg_q == prev_seg[:, :, None, None, None]
    from_start = jnp.where(reads, jnp.exp(cs), 0.0)           # [B,nc,G,k,Q]
    y = jnp.einsum("bcgqn,bcgkpn->bcgkqp", cc, received.astype(dtype),
                   preferred_element_type=f32) * from_start[..., None]

    # ---- inside a chunk, a block of heads at a time -------------------
    scores = jnp.einsum("bcgqn,bcgsn->bcgqs", cc, bc,
                        preferred_element_type=f32)           # [B,nc,G,Q,Q]
    q_idx = jnp.arange(Q)
    visible = ((q_idx[:, None] >= q_idx[None, :])[None, None]
               & (seg[:, :, :, None] == seg[:, :, None, :])
               )[:, :, None, None]                        # [B,nc,1,1,Q,Q]

    hb = math.gcd(min(head_block, per_group), per_group)
    blocks = per_group // hb

    @jax.checkpoint
    def inside(args):
        cs_b, dtx_b = args            # [B,nc,G,hb,Q], [B,nc,G,hb,Q,P]
        diff = cs_b[..., :, None] - cs_b[..., None, :]
        decay = jnp.exp(jnp.where(visible, diff, -jnp.inf))
        m = (scores[:, :, :, None] * decay).astype(dtype)
        return jnp.einsum("bcgkqs,bcgksp->bcgkqp", m, dtx_b,
                          preferred_element_type=f32)

    def split(t):        # [B,nc,G,k,...] -> [blocks, B,nc,G,hb,...]
        t = t.reshape(t.shape[:3] + (blocks, hb) + t.shape[4:])
        return jnp.moveaxis(t, 3, 0)
    intra = jax.lax.map(inside, (split(cs), split(dtx)))
    intra = jnp.moveaxis(intra, 0, 3).reshape(y.shape)
    y = y + intra + xh.astype(f32) * d_skip.astype(f32).reshape(
        G, per_group, 1, 1)
    # [B, nc, G, k, Q, P] -> [B, S, H, P]
    return jnp.moveaxis(y.astype(dtype), 4, 2).reshape(B, S, H, P)

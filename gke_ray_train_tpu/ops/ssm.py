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
256), several times over in a backward pass. Where the sizes tile
(:func:`scan_plan`: the one place that reads them) everything but the
cumulated sum of ``dt A`` runs as Pallas kernels: ``ssd_fwd`` forward,
``ssd_states`` and ``ssd_bwd`` backward. A grid step holds one chunk and
a block of heads; it forms the group's scores, each head's masked decay
and the products in VMEM, so that no ``[Q, Q]`` tensor reaches HBM. The
grid walks the chunks of a row in turn and the state between chunks
stays in VMEM as well: a chunk's outgoing state, the carry and the state
a chunk receives never reach HBM in the forward. The residuals are the
forward's inputs: the backward first walks the chunks again and writes
out the state each received (``ssd_states``), then walks them from the
last to the first with the state's cotangent in VMEM and makes every
decay again (``ssd_bwd``). Elsewhere (chunks of 5 or 48, a row that is
no multiple of the chunk) the ``jax.numpy`` form runs the quadratic part
over blocks of heads under a checkpoint of its own, so that one block's
decay is alive at a time.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from gke_ray_train_tpu.ops.flash_attention import interpret_default

# the jax.numpy form: heads of one block of the quadratic part (16 heads
# x 32 chunks of 256 hold 134 MB of masked decay in float32)
HEAD_BLOCK = 16
# the kernels: heads a grid step, and the side of the square sub-tiles
# of a chunk (the tiles above the diagonal are never formed); read off
# scripts/ssd_scan_sweep.py on the v5e (PERF.md section 6, PR 33)
HEADS_A_STEP = 16
SUB_TILE = 128


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


class ScanPlan(NamedTuple):
    """How :func:`ssd_scan` runs rows of one geometry: ``impl`` is
    ``pallas`` (the kernel pair) or ``xla`` (the ``jax.numpy`` form);
    ``head_block`` the heads of a grid step, or of a block of the
    ``jax.numpy`` form's quadratic part."""
    impl: str
    chunk: int
    chunks: int
    head_block: int

    def grid_steps(self, heads: int) -> int:
        """Grid steps of one kernel call a row (0: no kernel)."""
        return self.chunks * (heads // self.head_block) \
            if self.impl == "pallas" else 0


def _lane_tile(head_dim: int) -> tuple:
    """(lanes of a tile, heads in it): a tile is whole lane tiles of
    128, so heads of 64 go two a tile; (0, 0) where neither divides the
    other."""
    if head_dim % 128 == 0:
        return head_dim, 1
    if 128 % head_dim == 0:
        return 128, 128 // head_dim
    return 0, 0


def scan_plan(seq: int, chunk: int, heads: int, head_dim: int, state: int,
              groups: int, head_block: Optional[int] = None) -> ScanPlan:
    """The one rule that picks the form, from shapes alone: the kernels
    where the chunk is whole sub-tiles of 128 positions, the state whole
    lane tiles, heads fill lane tiles and some block of a group's heads
    (at most ``head_block``, by default HEADS_A_STEP) is whole sublane
    tiles of 8 or all the heads; else the ``jax.numpy`` form."""
    q, nc = scan_geometry(seq, chunk)
    per_group = heads // groups
    _, in_tile = _lane_tile(head_dim)
    if q % SUB_TILE == 0 and state % 128 == 0 and in_tile:
        most = HEADS_A_STEP if head_block is None else head_block
        for hb in range(min(most, per_group), 0, -1):
            if per_group % hb == 0 and hb % in_tile == 0 \
                    and (hb % 8 == 0 or hb == heads):
                return ScanPlan("pallas", q, nc, hb)
    most = HEAD_BLOCK if head_block is None else head_block
    return ScanPlan("xla", q, nc,
                    math.gcd(min(most, per_group), per_group))


def ssd_scan(x: jnp.ndarray, dt: jnp.ndarray, a: jnp.ndarray,
             b_mat: jnp.ndarray, c_mat: jnp.ndarray, d_skip: jnp.ndarray,
             segment_ids: Optional[jnp.ndarray], *, chunk: int,
             head_block: Optional[int] = None,
             interpret: Optional[bool] = None) -> jnp.ndarray:
    """x [B, S, H, P]; dt [B, S, H] float32 (after softplus); a [H]
    float32 (negative); b_mat, c_mat [B, S, G, N]; d_skip [H];
    segment_ids [B, S] or None (one document a row) -> y [B, S, H, P] in
    x's dtype. ``head_block`` overrides the plan's (tests, the sweep);
    off the chip the kernels run interpreted."""
    B, S, H, P = x.shape
    G, N = b_mat.shape[2:]
    plan = scan_plan(S, chunk, H, P, N, G, head_block)
    seg = jnp.zeros((B, S), jnp.int32) if segment_ids is None \
        else segment_ids.astype(jnp.int32)
    if plan.impl == "pallas":
        return _scan_pallas(x, dt, a, b_mat, c_mat, d_skip, seg, plan,
                            interpret_default(interpret))
    return _scan_xla(x, dt, a, b_mat, c_mat, d_skip, seg, plan)


# ---------------------------------------------------------------------------
# the kernels: one chunk and a block of heads a grid step, the chunks of
# a row in turn (the state between chunks stays in VMEM)
# ---------------------------------------------------------------------------

class _Sizes(NamedTuple):
    Q: int          # positions of a chunk
    hb: int         # heads a grid step
    P: int          # values of a head
    N: int          # state
    per_group: int  # heads that share one B and C
    tq: int         # side of a sub-tile of the chunk


_NT = (((1,), (1,)), ((), ()))    # a b^T
_TN = (((0,), (0,)), ((), ()))    # a^T b
# columns of ``cols`` (a number a position and head): dt, the cumulated
# log-decay, the decay from the chunk's start where the received state
# is read, the decay to the chunk's end where the outgoing state is fed
_DT, _CS, _FROM_START, _TO_END = range(4)
# rows of ``rows`` (a number a chunk and lane): the decay of the state
# over the whole chunk (0 where a document ends inside it), the skip
_GATE, _SKIP = range(2)


def _lane_heads(rows: int, sz: _Sizes):
    """[rows, W] int32: which head of its lane tile a lane belongs to."""
    W, _ = _lane_tile(sz.P)
    return jax.lax.broadcasted_iota(jnp.int32, (rows, W), 1) // sz.P


def _widen(cols_ref, k: int, t: int, sz: _Sizes):
    """[Q, W] float32: column ``k`` of the heads of lane tile ``t``, each
    over its head's lanes ([Q, 1], to be broadcast, where one head fills
    the tile)."""
    _, r = _lane_tile(sz.P)
    at = k * sz.hb + t * r
    out = cols_ref[0, 0, :, at:at + 1]
    if r == 1:
        return out
    heads = _lane_heads(sz.Q, sz)
    out = jnp.where(heads == 0, out, 0.0)
    for i in range(1, r):
        out = jnp.where(heads == i, cols_ref[0, 0, :, at + i:at + i + 1],
                        out)
    return out


def _visible(segc_ref, segr_ref, Q: int):
    """[Q, Q] bool: s is no later than q and in q's document."""
    q = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    s = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    return (q >= s) & (segc_ref[0] == segr_ref[0])


def _masked_scores(segc_ref, segr_ref, bt_ref, c_ref, sm_ref, Q: int):
    """The group's scores ``C B^T`` of the chunk, 0 where q may not read
    s, float32, into VMEM."""
    scores = jnp.dot(c_ref[0], bt_ref[0], preferred_element_type=jnp.float32)
    sm_ref[...] = jnp.where(_visible(segc_ref, segr_ref, Q), scores, 0.0)


def _decay(cols_ref, csr_ref, h: int, q0: int, sz: _Sizes):
    """exp(cs[q] - cs[s]) of head ``h`` for the sub-tiles of rows ``q0``
    up to the diagonal, [tq, q0 + tq] float32; above the diagonal (where
    the difference is positive and the scores are 0) it reads 1."""
    at = _CS * sz.hb + h
    col = cols_ref[0, 0, q0:q0 + sz.tq, at:at + 1]
    row = csr_ref[0, 0, h:h + 1, :q0 + sz.tq]
    return jnp.exp(jnp.minimum(col - row, 0.0))


def _fed(xt, cols_ref, t: int, sz: _Sizes, dtype):
    """(dt x, dt x decayed to the chunk's end where it feeds the
    outgoing state) of lane tile ``t``, in the compute dtype."""
    dtx = (xt * _widen(cols_ref, _DT, t, sz)).astype(dtype)
    fed = (dtx.astype(jnp.float32) * _widen(cols_ref, _TO_END, t, sz)
           ).astype(dtype)
    return dtx, fed


def _fwd_kernel(segc_ref, segr_ref, x_ref, cols_ref, csr_ref, bt_ref, c_ref,
                rows_ref, y_ref, state_ref, sm_ref, *, sz: _Sizes):
    """y of one chunk and block of heads, a lane tile (two heads of 64)
    at a time; ``state_ref`` holds the state the chunk receives, ``[N,
    heads x P]`` float32, and is handed on to the row's next chunk."""
    f32 = jnp.float32
    dtype = x_ref.dtype
    W, r = _lane_tile(sz.P)

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)
    _masked_scores(segc_ref, segr_ref, bt_ref, c_ref, sm_ref, sz.Q)
    heads = _lane_heads(sz.tq, sz)
    for t in range(sz.hb // r):
        lanes = slice(t * W, (t + 1) * W)
        xt = x_ref[0, :, lanes].astype(f32)
        dtx, fed = _fed(xt, cols_ref, t, sz, dtype)
        state = state_ref[:, lanes]
        # the received state read by the positions it reaches, and the skip
        base = jnp.dot(c_ref[0], state.astype(dtype),
                       preferred_element_type=f32) \
            * _widen(cols_ref, _FROM_START, t, sz) \
            + xt * rows_ref[0, 0, _SKIP:_SKIP + 1, lanes]
        state_ref[:, lanes] = state * rows_ref[0, 0, _GATE:_GATE + 1, lanes] \
            + jnp.dot(bt_ref[0], fed, preferred_element_type=f32)
        for q0 in range(0, sz.Q, sz.tq):
            K = q0 + sz.tq
            out = base[q0:K]
            for i in range(r):
                m = (sm_ref[q0:K, :K]
                     * _decay(cols_ref, csr_ref, t * r + i, q0, sz)
                     ).astype(dtype)
                y = out + jnp.dot(m, dtx[:K], preferred_element_type=f32)
                out = y if r == 1 else jnp.where(heads == i, y, out)
            y_ref[0, q0:K, lanes] = out.astype(dtype)


def _states_kernel(x_ref, cols_ref, bt_ref, rows_ref, states_ref, state_ref,
                   *, sz: _Sizes):
    """The state every chunk receives, float32, for the backward."""
    W, r = _lane_tile(sz.P)

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)
    for t in range(sz.hb // r):
        lanes = slice(t * W, (t + 1) * W)
        _, fed = _fed(x_ref[0, :, lanes].astype(jnp.float32), cols_ref, t,
                      sz, x_ref.dtype)
        state = state_ref[:, lanes]
        states_ref[0, 0, :, lanes] = state
        state_ref[:, lanes] = state * rows_ref[0, 0, _GATE:_GATE + 1, lanes] \
            + jnp.dot(bt_ref[0], fed, preferred_element_type=jnp.float32)


def _bwd_kernel(segc_ref, segr_ref, x_ref, cols_ref, csr_ref, bt_ref, c_ref,
                rows_ref, b_ref, ct_ref, states_ref, dy_ref,
                dx_ref, dcols_ref, db_ref, dc_ref, drows_ref,
                dstate_ref, sm_ref, dsm_ref, dxt_ref, yns_ref, *,
                sz: _Sizes):
    """The chunks of a row from the last to the first: ``dstate_ref``
    holds the cotangent of the state a chunk hands on."""
    f32 = jnp.float32
    dtype = x_ref.dtype
    W, r = _lane_tile(sz.P)
    hb = sz.hb

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate_ref[...] = jnp.zeros_like(dstate_ref)
    _masked_scores(segc_ref, segr_ref, bt_ref, c_ref, sm_ref, sz.Q)
    dsm_ref[...] = jnp.zeros_like(dsm_ref)
    heads = _lane_heads(sz.Q, sz)
    heads_t = _lane_heads(sz.tq, sz)
    cmat = c_ref[0]
    db = jnp.zeros((sz.Q, sz.N), f32)
    dc = jnp.zeros((sz.Q, sz.N), f32)

    def narrow(k, t, v):
        """Column ``k`` of the heads of lane tile ``t``: each head's sum
        of ``v`` over its own lanes."""
        for i in range(r):
            at = k * hb + t * r + i
            dcols_ref[0, 0, :, at:at + 1] = jnp.sum(
                v if r == 1 else jnp.where(heads == i, v, 0.0), axis=1,
                keepdims=True)

    for t in range(hb // r):
        lanes = slice(t * W, (t + 1) * W)
        xt = x_ref[0, :, lanes].astype(f32)
        dyf = dy_ref[0, :, lanes].astype(f32)
        dtx, fed = _fed(xt, cols_ref, t, sz, dtype)
        gate = rows_ref[0, 0, _GATE:_GATE + 1, lanes]
        state = states_ref[0, 0, :, lanes]
        dstate = dstate_ref[:, lanes]
        # through the state the chunk hands on: own = B^T fed
        dfed = jnp.dot(b_ref[0], dstate.astype(dtype),
                       preferred_element_type=f32) \
            * _widen(cols_ref, _TO_END, t, sz)
        db += jax.lax.dot_general(fed, dstate.astype(dtype), _NT,
                                  preferred_element_type=f32)
        # through the state the chunk received
        from_start = _widen(cols_ref, _FROM_START, t, sz)
        dye = (dyf * from_start).astype(dtype)
        dc += jax.lax.dot_general(dye, state.astype(dtype), _NT,
                                  preferred_element_type=f32)
        dstate_ref[:, lanes] = dstate * gate + jnp.dot(
            ct_ref[0], dye, preferred_element_type=f32)
        # inside the chunk; y less the skip is made again on the way:
        # d cs = sum over a head's lanes of dy y - d(dt x) (dt x)
        yns_ref[...] = jnp.dot(cmat, state.astype(dtype),
                               preferred_element_type=f32) * from_start
        dxt_ref[...] = jnp.zeros_like(dxt_ref)
        for i in range(r):
            h = t * r + i
            dy_h = dy_ref[0, :, lanes] if r == 1 else jnp.where(
                heads == i, dyf, 0.0).astype(dtype)
            for q0 in range(0, sz.Q, sz.tq):
                K = q0 + sz.tq
                decay = _decay(cols_ref, csr_ref, h, q0, sz)
                m = sm_ref[q0:K, :K] * decay
                dsm_ref[q0:K, :K] += decay * jax.lax.dot_general(
                    dy_h[q0:K], dtx[:K], _NT, preferred_element_type=f32)
                m = m.astype(dtype)
                y = yns_ref[q0:K] + jnp.dot(m, dtx[:K],
                                            preferred_element_type=f32)
                yns_ref[q0:K] = y if r == 1 else jnp.where(
                    heads_t == i, y, yns_ref[q0:K])
                # (M^T dy)^T: the transposed operand is the smaller one
                dxt_ref[:, :K] += jax.lax.dot_general(
                    dy_h[q0:K], m, _TN, preferred_element_type=f32)
        dxt = dxt_ref[...].T + dfed
        dtxf = dtx.astype(f32)
        narrow(0, t, dxt * xt)
        narrow(1, t, dyf * yns_ref[...] - dxt * dtxf)
        dx_ref[0, :, lanes] = (
            dxt * _widen(cols_ref, _DT, t, sz)
            + dyf * rows_ref[0, 0, _SKIP:_SKIP + 1, lanes]
        ).astype(dx_ref.dtype)
        # by lane: the skip's, and what reaches the chunk's last cs (the
        # gate over the whole chunk and every position's decay to the end)
        drows_ref[0, 0, 0:1, lanes] = jnp.sum(dyf * xt, axis=0,
                                              keepdims=True)
        drows_ref[0, 0, 1:2, lanes] = gate * jnp.sum(
            dstate * state, axis=0, keepdims=True) + jnp.sum(
            dfed * dtxf, axis=0, keepdims=True)
    # d scores, summed over the step's heads, through C B^T
    dsm = jnp.where(_visible(segc_ref, segr_ref, sz.Q), dsm_ref[...],
                    0.0).astype(dtype)
    db_ref[0, 0] = db + jax.lax.dot_general(dsm, cmat, _TN,
                                            preferred_element_type=f32)
    dc_ref[0, 0] = dc + jnp.dot(dsm, b_ref[0], preferred_element_type=f32)


def _prepare(x2, dt, cs, b2, c2, d_skip, seg, sz: _Sizes):
    """The kernels' operands (the forward's, in its order) from the
    scan's: a position and head's four numbers as columns ``[B, H/hb, S,
    4 hb]`` (a head's is one lane, broadcast along a sub-tile's lanes),
    the cumulated decay again as rows ``[B, nc, H, Q]`` (broadcast along
    its sublanes), a chunk's gate and the skip over their heads' lanes,
    B also transposed, the segments both ways."""
    B, S, H = dt.shape
    Q, hb, P = sz.Q, sz.hb, sz.P
    nc = S // Q
    f32 = jnp.float32
    segc = seg.reshape(B, nc, Q)
    last_seg = segc[:, :, -1]                                 # [B, nc]
    prev_seg = jnp.concatenate(
        [jnp.full((B, 1), -1, jnp.int32), last_seg[:, :-1]], axis=1)
    cs4 = cs.reshape(B, nc, Q, H)
    # read by the positions of the document the previous chunk ended in
    reads = (segc == prev_seg[:, :, None])[..., None]
    from_start = jnp.where(reads, jnp.exp(cs4), 0.0)
    # only the positions of the document the chunk ends in feed its state
    feeds = (segc == last_seg[:, :, None])[..., None]
    to_end = jnp.where(feeds, jnp.exp(cs4[:, :, -1:] - cs4), 0.0)
    # the state passes a chunk that lies inside one document
    gate = jnp.where((prev_seg == last_seg)[..., None],
                     jnp.exp(cs4[:, :, -1]), 0.0)             # [B, nc, H]

    def cols(t):
        return jnp.moveaxis(t.reshape(B, S, H // hb, hb), 2, 1)
    packed = jnp.concatenate(
        [cols(dt), cols(cs), cols(from_start), cols(to_end)], axis=-1)
    rows = jnp.stack(
        [jnp.repeat(gate, P, axis=-1),
         jnp.broadcast_to(jnp.repeat(d_skip.astype(f32), P), (B, nc, H * P))],
        axis=2)                                           # [B, nc, 2, H P]
    return (seg[:, :, None], seg[:, None, :], x2, packed,
            jnp.swapaxes(cs4, 2, 3), jnp.swapaxes(b2, 1, 2), c2, rows)


def _specs(B: int, nc: int, H: int, sz: _Sizes, back: bool = False):
    """(grid, the forward's in_specs, {name: spec}); ``back`` walks the
    chunks from the last to the first."""
    Q, hb, P, N = sz.Q, sz.hb, sz.P, sz.N
    steps = sz.per_group // hb

    def at(c):
        return nc - 1 - c if back else c
    wide = pl.BlockSpec((1, Q, hb * P), lambda b, j, c: (b, at(c), j))
    cols = pl.BlockSpec((1, 1, Q, 4 * hb), lambda b, j, c: (b, j, at(c), 0))
    bt = pl.BlockSpec((1, N, Q), lambda b, j, c: (b, j // steps, at(c)))
    rows = pl.BlockSpec((1, 1, 2, hb * P), lambda b, j, c: (b, at(c), 0, j))
    group = pl.BlockSpec((1, Q, N), lambda b, j, c: (b, at(c), j // steps))
    specs = [
        pl.BlockSpec((1, Q, 1), lambda b, j, c: (b, at(c), 0)),
        pl.BlockSpec((1, 1, Q), lambda b, j, c: (b, 0, at(c))),
        wide, cols,
        pl.BlockSpec((1, 1, hb, Q), lambda b, j, c: (b, at(c), j, 0)),
        bt, group, rows,
    ]
    named = dict(
        wide=wide, cols=cols, bt=bt, rows=rows, group=group,
        state=pl.BlockSpec((1, 1, N, hb * P),
                           lambda b, j, c: (b, at(c), 0, j)),
        part=pl.BlockSpec((1, 1, Q, N), lambda b, j, c: (b, j, at(c), 0)))
    return (B, H // hb, nc), specs, named


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


@functools.partial(jax.jit, static_argnames=("sz", "interpret"))
def _chunks_fwd(x2, dt, cs, b2, c2, d_skip, seg, *, sz: _Sizes,
                interpret: bool):
    """The scan but for the cumulated sum of dt A -> y [B, S, H P].
    Jitted: the mixers of a step that share their shapes share one
    trace and one lowering of the kernel."""
    ops = _prepare(x2, dt, cs, b2, c2, d_skip, seg, sz)
    B, S, H = dt.shape
    grid, specs, named = _specs(B, S // sz.Q, H, sz)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, sz=sz),
        grid=grid, in_specs=specs, out_specs=named["wide"],
        out_shape=jax.ShapeDtypeStruct(x2.shape, x2.dtype),
        scratch_shapes=[pltpu.VMEM((sz.N, sz.hb * sz.P), jnp.float32),
                        pltpu.VMEM((sz.Q, sz.Q), jnp.float32)],
        compiler_params=_params(), interpret=interpret,
        name="ssd_fwd",
    )(*ops)


@functools.partial(jax.jit, static_argnames=("sz", "interpret"))
def _chunks_bwd(x2, dt, cs, b2, c2, d_skip, seg, dy, *, sz: _Sizes,
                interpret: bool):
    """(dx, ddt, dcs, db, dc, dd) from the forward's inputs and dy: a
    pass over the chunks that writes out the state each received
    (``ssd_states``), then ``ssd_bwd`` from the last chunk to the
    first. Jitted as the forward is."""
    ops = _prepare(x2, dt, cs, b2, c2, d_skip, seg, sz)
    B, S, HP = x2.shape
    hb, Q, N = sz.hb, sz.Q, sz.N
    H = dt.shape[-1]
    nc, nj = S // Q, H // hb
    f32 = jnp.float32
    W, _ = _lane_tile(sz.P)
    grid, _, named = _specs(B, nc, H, sz)
    states = pl.pallas_call(
        functools.partial(_states_kernel, sz=sz),
        grid=grid,
        in_specs=[named["wide"], named["cols"], named["bt"], named["rows"]],
        out_specs=named["state"],
        out_shape=jax.ShapeDtypeStruct((B, nc, N, HP), f32),
        scratch_shapes=[pltpu.VMEM((N, hb * sz.P), f32)],
        compiler_params=_params(), interpret=interpret,
        name="ssd_states",
    )(x2, ops[3], ops[5], ops[7])
    grid, specs, named = _specs(B, nc, H, sz, back=True)
    dx, dcols, db, dc, drows = pl.pallas_call(
        functools.partial(_bwd_kernel, sz=sz),
        grid=grid,
        in_specs=specs + [named["group"], named["bt"], named["state"],
                          named["wide"]],
        out_specs=[named["wide"],
                   pl.BlockSpec((1, 1, Q, 2 * hb),
                                lambda b, j, c: (b, j, nc - 1 - c, 0)),
                   named["part"], named["part"], named["rows"]],
        out_shape=[
            jax.ShapeDtypeStruct(x2.shape, x2.dtype),
            jax.ShapeDtypeStruct((B, nj, S, 2 * hb), f32),
            jax.ShapeDtypeStruct((B, nj, S, N), f32),
            jax.ShapeDtypeStruct((B, nj, S, N), f32),
            jax.ShapeDtypeStruct((B, nc, 2, HP), f32),
        ],
        scratch_shapes=[pltpu.VMEM((N, hb * sz.P), f32),
                        pltpu.VMEM((Q, Q), f32), pltpu.VMEM((Q, Q), f32),
                        pltpu.VMEM((W, Q), f32), pltpu.VMEM((Q, W), f32)],
        compiler_params=_params(), interpret=interpret,
        name="ssd_bwd",
    )(*ops, b2, jnp.swapaxes(c2, 1, 2), states, dy)
    ddt, dcs = (
        jnp.moveaxis(dcols[..., k * hb:(k + 1) * hb], 1, 2).reshape(B, S, H)
        for k in range(2))
    by_head = drows.reshape(B, nc, 2, H, sz.P).sum(-1)
    dcs = dcs.reshape(B, nc, Q, H).at[:, :, -1].add(by_head[:, :, 1])
    steps = sz.per_group // hb          # the steps of one group's heads

    def groups(t):      # [B, nj, S, N] -> [B, S, G N], summed over steps
        t = t.reshape(B, nj // steps, steps, S, N).sum(2)
        return jnp.moveaxis(t, 1, 2).reshape(B, S, -1)
    return (dx, ddt, dcs.reshape(B, S, H), groups(db).astype(b2.dtype),
            groups(dc).astype(c2.dtype),
            by_head[:, :, 0].sum((0, 1)).astype(d_skip.dtype))


def _chunk_scan(sz: _Sizes, interpret: bool):
    """(x2 [B, S, H P], dt, cs [B, S, H] float32, b2, c2 [B, S, G N],
    d_skip [H], seg [B, S]) -> y [B, S, H P]: the scan but for the
    cumulated sum of dt A. The residuals are the inputs: with y kept by
    a block checkpoint, nothing of the forward kernel is needed
    again."""
    kw = dict(sz=sz, interpret=interpret)

    @jax.custom_vjp
    def scan(*args):
        return _chunks_fwd(*args, **kw)

    def fwd(*args):
        return scan(*args), args

    def bwd(args, dy):
        return _chunks_bwd(*args, dy, **kw) + (None,)

    scan.defvjp(fwd, bwd)
    return scan


def _scan_pallas(x, dt, a, b_mat, c_mat, d_skip, seg, plan: ScanPlan,
                 interpret: bool):
    B, S, H, P = x.shape
    G, N = b_mat.shape[2:]
    f32 = jnp.float32
    dt = dt.astype(f32)
    # cumulated log-decay inside each chunk, float32
    cs = jnp.cumsum((dt * a.astype(f32)).reshape(B, plan.chunks, plan.chunk,
                                                 H), axis=2)
    sz = _Sizes(Q=plan.chunk, hb=plan.head_block, P=P, N=N, per_group=H // G,
                tq=SUB_TILE)
    y = _chunk_scan(sz, interpret)(
        x.reshape(B, S, H * P), dt, cs.reshape(B, S, H),
        b_mat.reshape(B, S, G * N), c_mat.reshape(B, S, G * N), d_skip, seg)
    return y.reshape(B, S, H, P)



# ---------------------------------------------------------------------------
# the jax.numpy form
# ---------------------------------------------------------------------------

def _scan_xla(x, dt, a, b_mat, c_mat, d_skip, seg, plan: ScanPlan):
    B, S, H, P = x.shape
    G, N = b_mat.shape[2:]
    Q, nc = plan.chunk, plan.chunks
    dtype = x.dtype
    f32 = jnp.float32
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

    hb = plan.head_block
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

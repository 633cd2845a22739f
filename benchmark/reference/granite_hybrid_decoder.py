"""Plain reference of the ``granitemoehybrid`` decoder
(Granite-4.0-H-Small): Mamba-2 mixers beside grouped-query attention
without positions, every layer's MLP 72 softmax-routed experts (the 10
largest logits a token) beside a shared expert, Granite's four
multipliers; with NF4 / int8 weight quantisation, LoRA, cross-entropy
and AdamW.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul
precision, layer by layer. It imports nothing of the program; the
quantiser, the controls' rounding, RMSNorm, the loss, AdamW and the
trainer's loop over layers come from ``dense_decoder``, the block-wise
attention, the SwiGLU and the trainer that counts held pairs from
``exaone_moe_decoder``, the first gradient kept for the driver from
``glm_mla_moe_decoder``.

The model (``config.json`` keys in brackets): ``x = embedding_multiplier
* E[ids]``; the layers; ``logits = (RMSNorm(x) E^T) / logits_scaling``
(``tie_word_embeddings``). Layer ``l``, RMSNorm eps ``rms_norm_eps``::

    h = RMSNorm(x); h = Mamba(h) if layer_types[l] == "mamba" else Attn(h)
    x = x + residual_multiplier * h
    h = RMSNorm(x); x = x + residual_multiplier * (MoE(h) + Shared(h))

``Attn``: ``num_attention_heads`` / ``num_key_value_heads`` heads of
``hidden_size / num_attention_heads``, no bias, no positions
(``position_embedding_type: nope``), softmax scale
``attention_multiplier``, causal within a document.

``Mamba`` (H ``mamba_n_heads`` heads of P ``mamba_d_head``, state N
``mamba_d_state``, G ``mamba_n_groups``, conv of ``mamba_d_conv`` taps)::

    [z | xBC | dt] = h W_in                  # H P | H P + 2 G N | H
    xBC_t = silu(b_c + sum_j w_c[:, j] xBC_{t-3+j})    # taps before the
                                             # document's start read 0
    [x | B | C] = xBC
    dt_t = softplus(dt_t + dt_bias);  A = -exp(A_log)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t   # S = 0 before a
    y_t = S_t C_t + D x_t                              # document starts
    out = (RMSNorm(y * silu(z)) * g) W_out   # one norm over all H P

The scan is that recurrence, position by position (``lax.scan`` over
the row, the state set to 0 at a document's first position), in blocks
of :data:`SCAN_BLOCK` positions each under a checkpoint: 8192 states of
128 x 64 x 128 float32 would be 34 GB if a backward pass kept them.

``MoE``: ``l = float32(h) R``; the ``num_experts_per_tok`` largest
logits are selected; weights = softmax over those logits; ``sum over
the selected experts HELD HERE of w_e FFN_e(h)``: the softmax runs over
all selected, held or not (one expert-parallel rank's share; what the
absent experts would add is left out, here as in the program). No token
is dropped, no auxiliary loss. Padding positions (segment 0) are not
routed.

Departures from the published description, each the configuration
file's ``assumed``: the expert width is read from ``intermediate_size``;
``in_proj``'s columns are ordered ``[z | x | B | C | dt]``; the gate is
applied before the norm and the norm has one group; ``time_step_limit``
is (0, inf), so ``dt`` is not clamped.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

from benchmark import weights as wts
from benchmark import weights_ssm as ws
from benchmark.reference import dense_decoder as dd
from benchmark.reference import exaone_moe_decoder as em
from benchmark.reference import glm_mla_moe_decoder as gl

HI = dd.HI
# positions of one checkpointed block of the recurrence, and how many
# of them one iteration of the compiled loop holds (the same steps in
# the same order; XLA fuses a few of them, so that the state goes to HBM
# once an iteration and not once an operation)
SCAN_BLOCK = 128
SCAN_UNROLL = 1


def causal_conv(x, w, b, segments):
    """x [b, S, C], w [C, K], b [C]: tap j of position t reads position
    ``t - (K - 1) + j`` where that lies in t's document, else 0."""
    S, K = x.shape[1], w.shape[1]
    out = jnp.zeros_like(x) + b
    for j in range(K):
        back = K - 1 - j
        tap = jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :S]
        same = jnp.pad(segments, ((0, 0), (back, 0)),
                       constant_values=-1)[:, :S] == segments
        out = out + jnp.where(same[..., None], tap, 0.0) * w[:, j]
    return out


def selective_scan(x, dt, a, b_mat, c_mat, d_skip, segments):
    """x [b, S, H, P], dt [b, S, H], a [H], b_mat / c_mat [b, S, G, N],
    d_skip [H], segments [b, S] -> y [b, S, H, P]: the recurrence, one
    position a step."""
    bsz, S, H, P = x.shape
    G, N = b_mat.shape[2:]
    first = jnp.concatenate([jnp.ones((bsz, 1), bool),
                             segments[:, 1:] != segments[:, :-1]], axis=1)

    def step(state, t):
        x_t, dt_t, b_t, c_t, first_t = t
        state = jnp.where(first_t[:, None, None, None], 0.0, state)
        b_h = jnp.repeat(b_t, H // G, axis=1)                 # [b, H, N]
        c_h = jnp.repeat(c_t, H // G, axis=1)
        state = jnp.exp(dt_t * a)[..., None, None] * state \
            + (dt_t[..., None] * x_t)[..., None] * b_h[:, :, None, :]
        y_t = jnp.sum(state * c_h[:, :, None, :], axis=-1) \
            + d_skip[:, None] * x_t
        return state, y_t

    block = SCAN_BLOCK if S % SCAN_BLOCK == 0 else S

    @jax.checkpoint
    def run_block(state, ts):
        return jax.lax.scan(step, state, ts, unroll=SCAN_UNROLL)

    def blocks(t):                       # [b, S, ...] -> [S/block, block, b, ...]
        t = jnp.moveaxis(t, 1, 0)
        return t.reshape((S // block, block) + t.shape[1:])
    _, ys = jax.lax.scan(
        run_block, jnp.zeros((bsz, H, P, N), jnp.float32),
        tuple(blocks(t) for t in (x, dt, b_mat, c_mat, first)))
    return jnp.moveaxis(ys.reshape((S,) + ys.shape[2:]), 0, 1)


def mamba(h, W, lora, hp, segments, mode):
    """The mixer of one state-space layer, h [b, S, D] -> [b, S, D]."""
    b, S, _ = h.shape
    H, P, N, G = hp["ssm_heads"], hp["ssm_head_dim"], hp["ssm_state"], \
        hp["ssm_groups"]
    inner, sc, lo = H * P, hp["lora_scale"], lora.get
    zxbcdt = dd._proj(h, W["in_proj"], lo("in_proj"), sc, mode)
    z = zxbcdt[..., :inner]
    xbc = zxbcdt[..., inner:2 * inner + 2 * G * N]
    dt = zxbcdt[..., 2 * inner + 2 * G * N:]
    xbc = jax.nn.silu(causal_conv(xbc, W["conv_w"], W["conv_b"], segments))
    y = selective_scan(
        xbc[..., :inner].reshape(b, S, H, P),
        jax.nn.softplus(dt + W["dt_bias"]), -jnp.exp(W["a_log"]),
        xbc[..., inner:inner + G * N].reshape(b, S, G, N),
        xbc[..., inner + G * N:].reshape(b, S, G, N),
        W["d_skip"], segments)
    y = dd.rms_norm(y.reshape(b, S, inner) * jax.nn.silu(z),
                    W["ssm_norm"], hp["eps"])
    return dd._proj(y, W["out_proj"], lo("out_proj"), sc, mode)


def attn(h, W, lora, hp, positions, segments, mode):
    b, S, _ = h.shape
    H, K, dh = hp["heads"], hp["kv_heads"], hp["head_dim"]
    sc, lo = hp["lora_scale"], lora.get
    q = dd._proj(h, W["wq"], lo("wq"), sc, mode).reshape(b, S, H, dh)
    k = dd._proj(h, W["wk"], lo("wk"), sc, mode).reshape(b, S, K, dh)
    v = dd._proj(h, W["wv"], lo("wv"), sc, mode).reshape(b, S, K, dh)
    # ``exaone_moe_decoder.attention`` divides the scores by sqrt(dh);
    # this model's scale is ``attention_multiplier``
    q = q * (hp["attn_scale"] * math.sqrt(dh))
    o = em.attention(q, k, v, positions, segments, em.NO_WINDOW)
    return dd._proj(o.reshape(b, S, H * dh), W["wo"], lo("wo"), sc, mode)


def route(h, router, hp):
    """h [..., D] -> (selected [..., E] bool, weights [..., E] float32:
    the softmax over the selected logits, 0 where not selected)."""
    logits = jnp.matmul(h.astype(jnp.float32), router, precision=HI)
    _, idx = jax.lax.top_k(logits, hp["top_k"])
    sel = jnp.sum(jax.nn.one_hot(idx, logits.shape[-1], dtype=jnp.float32),
                  axis=-2) > 0
    return sel, jax.nn.softmax(jnp.where(sel, logits, -jnp.inf), axis=-1)


def routed(h, W, hp, valid, mode):
    """sum over held experts of w_e FFN_e(h), and the count of
    (position, held expert) pairs."""
    sel, w = route(h, W["router"], hp)
    sel &= valid[..., None]
    y = jnp.zeros_like(h)
    pairs = jnp.zeros((), jnp.int32)
    for j in range(hp["held"]):
        e = hp["held_lo"] + j
        out = em.swiglu(h, W["expert_gate"][j], W["expert_up"][j],
                        W["expert_down"][j], None, None, None, 0.0, mode)
        y = y + jnp.where(sel[..., e], w[..., e], 0.0)[..., None] * out
        pairs = pairs + jnp.sum(sel[..., e])
    return y, pairs


def layer_fwd(x, W, lora, hp, positions, segments, mode):
    """One layer -> (x, held pairs). The mixer's kind is which leaves
    ``W`` has."""
    b, S, _ = x.shape
    sc, lo, rm = hp["lora_scale"], lora.get, hp["residual"]
    if segments is None:
        segments = jnp.ones((b, S), jnp.int32)
    h = dd.rms_norm(x, W["attn_norm"], hp["eps"])
    if "in_proj" in W:
        h = mamba(h, W, lora, hp, segments, mode)
    else:
        h = attn(h, W, lora, hp, positions, segments, mode)
    x = x + rm * h
    h = dd.rms_norm(x, W["mlp_norm"], hp["eps"])
    y, pairs = routed(h, W, hp, segments != 0, mode)
    y = y + em.swiglu(h, W["shared_gate"], W["shared_up"], W["shared_down"],
                      lo("shared_gate"), lo("shared_up"), lo("shared_down"),
                      sc, mode)
    return x + rm * y, pairs


class Model:
    """Seed -> layers of one configuration, as it stores them."""

    def __init__(self, config: dict, seed: int, *, store_dtype: str,
                 quant_kind: Optional[str], quant_group: int = 64,
                 lora_rank: int = 0, lora_alpha: float = 0.0,
                 lora_targets: Sequence[str] = wts.PROJECTIONS):
        if config.get("hidden_act", "silu") != "silu" \
                or config.get("position_embedding_type") != "nope" \
                or config.get("normalization_function",
                              "rmsnorm") != "rmsnorm" \
                or config.get("attention_bias") \
                or config.get("mamba_proj_bias") \
                or not config.get("mamba_conv_bias", True) \
                or not config.get("tie_word_embeddings"):
            raise ValueError(
                "this reference has SwiGLU experts, attention without "
                "positions or bias, RMSNorm, a conv with bias, mixer "
                "projections without, and a tied head")
        self.config = config
        self.sizes = ws.dims_from_config(config)
        self.key = wts.seed_key(seed)
        self.store_dtype = store_dtype
        self.quant = (quant_kind, quant_group)
        self.rank = int(lora_rank)
        self.targets = tuple(lora_targets) if self.rank else ()
        d = self.sizes
        self.hp = {
            "heads": d["heads"], "kv_heads": d["kv_heads"],
            "head_dim": d["head_dim"],
            "eps": float(config["rms_norm_eps"]),
            "attn_scale": float(config["attention_multiplier"]),
            "residual": float(config["residual_multiplier"]),
            "top_k": d["top_k"], "held": d["held"], "held_lo": d["held_lo"],
            "ssm_heads": d["ssm_heads"], "ssm_head_dim": d["ssm_head_dim"],
            "ssm_state": d["ssm_state"], "ssm_groups": d["ssm_groups"],
            "lora_scale": (lora_alpha / lora_rank) if lora_rank else 0.0,
        }
        self.embed_mult = float(config["embedding_multiplier"])
        self.logits_div = float(config["logits_scaling"])
        self.held_pairs: List[int] = []
        self.first_gradient: Optional[List[dict]] = None
        # the key is an argument of every compiled program: closed over,
        # it would be a constant, and each seed would compile its own
        self._layer = jax.jit(self._make_layer, static_argnums=2)
        self._outer = jax.jit(self._leaf, static_argnums=1)

    @property
    def dims(self) -> Dict[str, object]:
        """The sizes, and what the driver reads after the steps: the
        held pairs counted so far, one number a followed step, and the
        first gradient."""
        return dict(self.sizes, held_pairs=list(self.held_pairs),
                    first_gradient=self.first_gradient)

    def kinds(self, i: int):
        return ws.layer_kinds(self.config, i)

    def _leaf(self, key, name, layer, dtype=None, expert=0):
        return ws.stored(self.sizes, key, name, layer,
                         dtype or self.store_dtype,
                         expert).astype(jnp.float32)

    def _make_layer(self, key, layer, kind):
        q_kind, group = self.quant
        W = {n: self._leaf(key, n, layer)
             for n in ("attn_norm", "mlp_norm", "router")}
        # a quantiser is handed the weight in bfloat16, as checkpoints
        # of these models hold it
        dt = None if q_kind in (None, "none") else "bfloat16"

        def q(name, expert=0):
            return dd.quant_dequant(self._leaf(key, name, layer, dt, expert),
                                    q_kind, group)
        if kind == "mamba":
            for n in ws.MIXER_REST:
                W[n] = self._leaf(key, n, layer)
        for n in (ws.MIXER if kind == "mamba" else ws.ATTENTION) + ws.SHARED:
            W[n] = q(n)
        experts = self.sizes["held_lo"] + jnp.arange(self.sizes["held"])
        for n in ws.EXPERT:
            W[n] = jax.lax.map(lambda e, n=n: q(n, e), experts)
        return W

    def layer(self, i: int) -> Dict[str, jnp.ndarray]:
        return self._layer(self.key, jnp.asarray(i, jnp.int32),
                           self.kinds(i)[0])

    def outer(self, name: str) -> jnp.ndarray:
        """The leaves outside the layers as ``dense_decoder``'s trainer
        uses them: the embedding with its multiplier, the tied head
        (the embedding transposed) with the logits' divisor."""
        if name == "embed":
            return self._outer(self.key, "embed", 0) * self.embed_mult
        if name == "lm_head":
            return self._outer(self.key, "embed", 0).T / self.logits_div
        return self._outer(self.key, name, 0)

    def init_lora(self) -> List[Dict[str, Dict[str, jnp.ndarray]]]:
        def make(key, i, targets):
            return {t: {"a": ws.lora_a(self.sizes, key, t, i, self.rank),
                        "b": jnp.zeros(ws.lora_b_shape(self.sizes, t,
                                                       self.rank),
                                       jnp.float32)}
                    for t in targets}
        make = jax.jit(make, static_argnums=2)
        return [make(self.key, jnp.asarray(i, jnp.int32),
                     ws.lora_targets(self.targets, self.kinds(i)[0],
                                     self.sizes))
                for i in range(self.sizes["layers"])]


class LoraTrainer(gl.LoraTrainer):
    """``glm_mla_moe_decoder``'s trainer (``dense_decoder``'s, counting
    the held pairs of every forward pass and keeping the first gradient)
    over this module's layer."""

    def __init__(self, model: Model, opt: dict, *, mode: str = "f32",
                 rows_per_block: int = 1, keep_rows=None):
        super().__init__(model, opt, mode=mode,
                         rows_per_block=rows_per_block, keep_rows=keep_rows)
        hp = model.hp

        def fwd(x, W, lo, positions, segments):
            return layer_fwd(x, W, lo, hp, positions, segments, mode)

        def bwd(x, W, lo, positions, segments, g):
            _, vjp = jax.vjp(
                lambda x_, lo_: layer_fwd(x_, W, lo_, hp, positions,
                                          segments, mode)[0], x, lo)
            return vjp(g)

        counted = jax.jit(fwd)

        def fwd_and_count(*args):
            x, pairs = counted(*args)
            self._pairs += int(pairs)
            return x
        self._fwd, self._bwd = fwd_and_count, jax.jit(bwd)


def trainer(config: dict, seed: int, *, store_dtype: str,
            quant_kind: Optional[str], lora: Optional[dict],
            optimizer: dict, mode: str = "f32", keep_rows=None):
    """(model, trainer) for the first steps of the job given: the
    interface ``drivers/train.py::reference_readings`` calls."""
    if lora is None:
        raise ValueError("this reference follows LoRA fine-tunes only")
    model = Model(config, seed, store_dtype=store_dtype,
                  quant_kind=quant_kind, lora_rank=lora["rank"],
                  lora_alpha=lora["alpha"], lora_targets=lora["targets"])
    return model, LoraTrainer(model, optimizer, mode=mode,
                              keep_rows=keep_rows)

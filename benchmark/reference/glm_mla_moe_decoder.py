"""Plain reference of the ``glm4_moe_lite`` decoder (GLM-4.7-Flash):
latent attention (the query through a latent of ``q_lora_rank``, keys and
values through one latent of ``kv_lora_rank``, a rotary slice that all
heads' keys share), a leading dense SwiGLU layer, then layers of
sigmoid-routed experts beside a shared expert; with NF4 / int8 weight
quantisation, LoRA, cross-entropy and AdamW.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul
precision, layer by layer. It imports nothing of the program; the
quantiser, the controls' rounding, RMSNorm, rotary, the loss, AdamW and
the trainer's loop over layers it takes from ``dense_decoder``, the
router, the SwiGLU, the plain loop over held experts and the attention
in blocks of queries from ``exaone_moe_decoder``.

The layer, for the normed input ``x [S, D]`` (``config.json`` keys in
brackets; no bias anywhere; RMSNorm eps ``rms_norm_eps``):

1. ``c_q = RMSNorm(x W_qa)`` [``q_lora_rank``]; ``q = c_q W_qb``,
   a head's ``qk_nope_head_dim`` values without position, then its
   ``qk_rope_head_dim`` rotated ones.
2. ``a = x W_kva`` = ``[c_kv (kv_lora_rank) | k_rope (qk_rope_head_dim)]``:
   ``k_rope`` is one vector a position. ``RMSNorm(c_kv) W_kvb`` gives a
   head's keys without position [``qk_nope_head_dim``], then its values
   [``v_head_dim``].
3. Rotary (``rope_theta``, all ``qk_rope_head_dim`` dims, the document's
   positions) on ``q_rope`` and ``k_rope``; ``q = [q_nope | q_rope]``,
   ``k = [k_nope | k_rope, the same for every head]``.
4. ``softmax(q k^T / sqrt(qk_nope_head_dim + qk_rope_head_dim))``,
   causal within the document, times ``v``; the heads' outputs through
   ``W_o``.
5. ``h = RMSNorm(x)``; layer ``l < first_k_dense_replace`` adds
   ``SwiGLU(h)`` of width ``intermediate_size``. A sparse layer:
   ``s = sigmoid(float32(h) R)``; the ``num_experts_per_tok`` experts
   with the largest ``s + b`` are selected (``topk_method: noaux_tc``;
   ``n_group = topk_group = 1``: no group step); weights
   ``routed_scaling_factor * s_e / (sum of the selected s + 1e-20)``;
   ``x += sum over the selected experts HELD HERE of w_e FFN_e(h) +
   FFN_shared(h)``. The denominator runs over all selected, held or not:
   this is one expert-parallel rank's share of the layer, and what the
   absent experts would add is left out, here as in the program.

What ``config.json`` does not say (the configuration file's
``assumed``): which pairs the rotary turns (split halves, as the
program's; with seeded weights the interleaved convention is a
permutation of ``W_qb``'s and ``W_kva``'s columns), the selection bias's
values, the weights. The multi-token-prediction layer is left out
(``num_nextn_predict_layers`` in ``reduced``). Padding positions
(segment 0) are not routed.

A held expert runs over every position and is weighted by the position's
routing weight for it, which is 0 where it was not selected
(``exaone_moe_decoder.routed``: no sort, no buffer, nothing to drop). An
expert over the rows routed to it alone, gathered into slots a row, was
tried on the chip and lost (``reference_s`` 274 s, my chip run, PR 30):
random weights route unevenly (a deep layer sends one expert 6.5 x the
mean load), so the slots have to be found by running a layer again.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

from benchmark import weights as wts
from benchmark import weights_mla as wl
from benchmark.reference import dense_decoder as dd
from benchmark.reference import exaone_moe_decoder as em

NO_WINDOW = em.NO_WINDOW
def latent_qkv(h, W, lora, hp, positions, mode):
    """q, k, v [b, S, H, nope + rope] of one latent-attention layer."""
    b, S, _ = h.shape
    H, nope, rot = hp["heads"], hp["nope"], hp["rope"]
    sc, lo = hp["lora_scale"], lora.get
    c_q = dd.rms_norm(dd._proj(h, W["wq_a"], lo("wq_a"), sc, mode),
                      W["q_latent_norm"], hp["eps"])
    q = dd._proj(c_q, W["wq_b"], lo("wq_b"), sc, mode).reshape(
        b, S, H, nope + rot)
    a = dd._proj(h, W["wkv_a"], lo("wkv_a"), sc, mode)
    c_kv, k_rope = a[..., :hp["kv_rank"]], a[..., hp["kv_rank"]:]
    c_kv = dd.rms_norm(c_kv, W["kv_latent_norm"], hp["eps"])
    kv = dd._proj(c_kv, W["wkv_b"], lo("wkv_b"), sc, mode).reshape(
        b, S, H, nope + hp["head_dim"])
    q_rope = dd.rope(q[..., nope:], positions, hp["theta"])
    k_rope = dd.rope(k_rope[:, :, None, :], positions, hp["theta"])
    q = jnp.concatenate([q[..., :nope], q_rope], -1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (b, S, H, rot))], -1)
    return q, k, kv[..., nope:]


def layer_fwd(x, W, lora, hp, positions, segments, mode):
    """One layer -> (x, held pairs). The MLP's kind is which leaves
    ``W`` has."""
    b, S, _ = x.shape
    sc, lo = hp["lora_scale"], lora.get
    h = dd.rms_norm(x, W["attn_norm"], hp["eps"])
    q, k, v = latent_qkv(h, W, lora, hp, positions, mode)
    o = em.attention(q, k, v, positions, segments, NO_WINDOW)
    x = x + dd._proj(o.reshape(b, S, -1), W["wo"], lo("wo"), sc, mode)
    h = dd.rms_norm(x, W["mlp_norm"], hp["eps"])
    if "router" not in W:
        return x + em.swiglu(h, W["w_gate"], W["w_up"], W["w_down"],
                             lo("w_gate"), lo("w_up"), lo("w_down"), sc,
                             mode), jnp.zeros((), jnp.int32)
    valid = jnp.ones((b, S), bool) if segments is None else segments != 0
    y, pairs = em.routed(h, W, hp, valid, mode)
    if "shared_gate" in W:
        y = y + em.swiglu(h, W["shared_gate"], W["shared_up"],
                          W["shared_down"], lo("shared_gate"),
                          lo("shared_up"), lo("shared_down"), sc, mode)
    return x + y, pairs


class Model:
    """Seed -> layers of one configuration, as it stores them."""

    def __init__(self, config: dict, seed: int, *, store_dtype: str,
                 quant_kind: Optional[str], quant_group: int = 64,
                 lora_rank: int = 0, lora_alpha: float = 0.0,
                 lora_targets: Sequence[str] = wts.PROJECTIONS):
        if config.get("hidden_act", "silu") != "silu" \
                or config.get("topk_method", "noaux_tc") != "noaux_tc" \
                or int(config.get("n_group", 1)) != 1 \
                or int(config.get("topk_group", 1)) != 1:
            raise ValueError("this reference has SwiGLU experts under a "
                             "sigmoid router with one group only")
        if not config.get("norm_topk_prob", True):
            raise ValueError("weights are renormalised over the selected")
        if config.get("rope_scaling") or \
                float(config.get("partial_rotary_factor", 1)) != 1:
            raise ValueError("the rotary slice is turned whole, unscaled")
        if config.get("attention_bias"):
            raise ValueError("this reference has no bias")
        self.config = config
        self.sizes = wl.dims_from_config(config)
        self.key = wts.seed_key(seed)
        self.store_dtype = store_dtype
        self.quant = (quant_kind, quant_group)
        self.rank = int(lora_rank)
        self.targets = tuple(lora_targets) if self.rank else ()
        d = self.sizes
        self.hp = {
            "heads": d["heads"], "head_dim": d["head_dim"],
            "nope": d["nope"], "rope": d["rope"], "kv_rank": d["kv_rank"],
            "eps": float(config["rms_norm_eps"]),
            "theta": float(config["rope_theta"]),
            "top_k": d["top_k"], "held": d["held"],
            "held_lo": d["held_lo"],
            "routed_scale": float(config.get("routed_scaling_factor", 1.0)),
            "lora_scale": (lora_alpha / lora_rank) if lora_rank else 0.0,
        }
        self.held_pairs: List[int] = []
        # the first step's gradient as AdamW gets it, a dict a layer
        # (the trainer leaves it here after its first step)
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
        return wl.layer_kinds(self.config, i)

    def _leaf(self, key, name, layer, dtype=None, expert=0):
        return wl.stored(self.sizes, key, name, layer,
                         dtype or self.store_dtype,
                         expert).astype(jnp.float32)

    def _make_layer(self, key, layer, mlp):
        kind, group = self.quant
        W = {n: self._leaf(key, n, layer)
             for n in ("attn_norm", "mlp_norm") + wl.LATENT_NORMS}
        # a quantiser is handed the weight in bfloat16, as checkpoints
        # of these models hold it
        dt = None if kind in (None, "none") else "bfloat16"

        def q(name, expert=0):
            return dd.quant_dequant(self._leaf(key, name, layer, dt, expert),
                                    kind, group)
        for n in wl.ATTENTION:
            W[n] = q(n)
        if mlp == "dense":
            for n in wl.DENSE_MLP:
                W[n] = q(n)
            return W
        W["router"] = self._leaf(key, "router", layer)
        W["router_bias"] = self._leaf(key, "router_bias", layer)
        if self.sizes["shared"]:
            for n in wl.SHARED:
                W[n] = q(n)
        experts = self.sizes["held_lo"] + jnp.arange(self.sizes["held"])
        for n in wl.EXPERT:
            W[n] = jax.lax.map(lambda e, n=n: q(n, e), experts)
        return W

    def layer(self, i: int) -> Dict[str, jnp.ndarray]:
        return self._layer(self.key, jnp.asarray(i, jnp.int32),
                           self.kinds(i)[1])

    def outer(self, name: str) -> jnp.ndarray:
        return self._outer(self.key, name, 0)

    def init_lora(self) -> List[Dict[str, Dict[str, jnp.ndarray]]]:
        def make(key, i, targets):
            return {t: {"a": wl.lora_a(self.sizes, key, t, i, self.rank),
                        "b": jnp.zeros(wl.lora_b_shape(self.sizes, t,
                                                       self.rank),
                                       jnp.float32)}
                    for t in targets}
        make = jax.jit(make, static_argnums=2)
        return [make(self.key, jnp.asarray(i, jnp.int32),
                     wl.lora_targets(self.targets, self.kinds(i)[1],
                                     self.sizes))
                for i in range(self.sizes["layers"])]


class LoraTrainer(em.LoraTrainer):
    """``exaone_moe_decoder``'s trainer (``dense_decoder``'s, counting
    the held pairs of every forward pass) over this module's layer."""

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
        self._first = None

    def gradients(self, batch):
        loss, grads = super().gradients(batch)
        if self._first is None:
            self._first = grads
        return loss, grads

    def step(self, batch) -> dict:
        out = super().step(batch)
        if self.model.first_gradient is None:
            # clipped as the update took it: the factor is the clipped
            # norms' share of the norms
            sq = sum(dd.leaf_sq_norms(self._first).values())
            clip = math.sqrt(sum(v * v for v in out["grad_norm"].values())
                             / sq) if sq else 1.0
            # on the host: the steps that follow need the device's room
            self.model.first_gradient = [
                jax.device_get(jax.tree.map(lambda g: g * clip, layer))
                for layer in self._first]
            self._first = ()
        return out


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

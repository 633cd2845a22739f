"""Plain reference of the ``exaone_moe`` decoder (K-EXAONE-236B-A23B):
per-kind attention (window-128 layers with rotary, full layers without;
RMSNorm on q and k per head), a leading dense SwiGLU layer, then layers
of sigmoid-routed experts beside a shared expert; with NF4 / int8 weight
quantisation, LoRA, cross-entropy and AdamW.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul
precision, layer by layer, a Python loop over the experts held (each
expert's FFN runs over every position and is weighted by the position's
routing weight for it, which is 0 where it was not selected: no sort, no
buffer, nothing to drop). It imports nothing of the program; what it
shares with ``dense_decoder`` (the quantiser, the controls' rounding,
RMSNorm, rotary, the attention softmax, the loss, AdamW and the trainer's
loop over layers) it takes from there.

The layer, for input ``x [T, D]`` (``config.json`` keys in brackets):

1. ``h = RMSNorm(x)``; q, k, v projections without bias.
2. ``q``, ``k``: RMSNorm over each head's ``head_dim``, one scale vector
   a layer each.
3. ``layer_types[l] == "sliding_attention"``: rotary on q and k
   (``rope_parameters.rope_theta``, split-halves layout) and keys within
   ``sliding_window`` positions of the query, same document;
   ``"full_attention"``: no rotary, causal within the document.
4. ``h = RMSNorm(x)``; a dense layer (``mlp_layer_types[l] == "dense"``)
   adds ``SwiGLU(h)`` of width ``intermediate_size``. A sparse layer:
   ``s = sigmoid(float32(h) R)``; the ``num_experts_per_tok`` experts
   with the largest ``s + b`` are selected (``n_group = topk_group = 1``:
   the group mask keeps everything); weights ``routed_scaling_factor *
   s_e / (sum of the selected s + 1e-20)``; ``x += sum over the selected
   experts HELD HERE of w_e FFN_e(h) + FFN_shared(h)``. The denominator
   runs over all selected, held or not: this is one expert-parallel
   rank's share of the layer, and what the absent experts would add is
   left out, here as in the program.

Where it follows the family and not a key (the configuration file's
``assumed``): the norms sit before each sublayer (the DeepSeek-V3 block
whose keys name the routed layer; EXAONE 4.0's own block norms the
sublayer's output instead); q/k norm in every layer and rotary in the
sliding layers only (``transformers`` 4.57 ``models/exaone4/
modeling_exaone4.py``: the norm is unconditional, the rotary is applied
``if self.sliding_window is None or self.is_sliding``); the selection
bias ``b`` exists and is used for selection only (``models/deepseek_v3/
modeling_deepseek_v3.py``'s ``e_score_correction_bias``). The
multi-token-prediction layer is left out (``num_nextn_predict_layers``
in ``reduced``). Padding positions (segment 0) are not routed.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

from benchmark import weights as wts
from benchmark import weights_moe as wm
from benchmark.reference import dense_decoder as dd

HI = dd.HI
NO_WINDOW = 2 ** 30
# queries a block of the attention: at 8192 positions the scores of one
# row's 64 heads are 17 GB in float32, so the reference computes them
# 512 queries at a time (each block recomputed in the backward pass)
QUERY_BLOCK = 512


def attention(q, k, v, positions, segments, window):
    """``dense_decoder.attention`` in blocks of queries: q [b,S,H,dh],
    k/v [b,S,K,dh] -> [b,S,H,dh]; causal, within ``window`` positions
    and within a segment (0 = padding)."""
    b, S, H, dh = q.shape
    if S <= QUERY_BLOCK or S % QUERY_BLOCK:
        return dd.attention(q, k, v, positions, segments, window)
    K, n = k.shape[2], S // QUERY_BLOCK
    if segments is None:
        segments = jnp.ones((b, S), jnp.int32)

    @jax.checkpoint
    def block(args):
        qb, qp, qs = args
        qg = qb.reshape(b, QUERY_BLOCK, K, H // K, dh)
        s = jnp.einsum("bskgd,btkd->bkgst", qg, k,
                       precision=HI) / math.sqrt(dh)
        kp = positions[:, None, :]
        mask = (kp <= qp[:, :, None]) & (kp > qp[:, :, None] - window)
        mask &= qs[:, :, None] == segments[:, None, :]
        mask &= segments[:, None, :] != 0
        p = jax.nn.softmax(jnp.where(mask[:, None, None], s, -1e30), -1)
        o = jnp.einsum("bkgst,btkd->bskgd", p, v, precision=HI)
        return o.reshape(b, QUERY_BLOCK, H, dh)

    def split(x):
        return jnp.moveaxis(
            x.reshape((b, n, QUERY_BLOCK) + x.shape[2:]), 1, 0)
    out = jax.lax.map(block, (split(q), split(positions), split(segments)))
    return jnp.moveaxis(out, 0, 1).reshape(b, S, H, dh)


def route(h, router, bias, hp):
    """h [..., D] -> (selected [..., E] bool, weights [..., E] float32:
    0 where not selected)."""
    s = jax.nn.sigmoid(jnp.matmul(h.astype(jnp.float32), router,
                                  precision=HI))
    _, idx = jax.lax.top_k(s + bias, hp["top_k"])
    sel = jnp.sum(jax.nn.one_hot(idx, s.shape[-1], dtype=jnp.float32),
                  axis=-2) > 0
    picked = jnp.where(sel, s, 0.0)
    w = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    return sel, w * hp["routed_scale"]


def swiglu(h, gate, up, down, lo_gate, lo_up, lo_down, scale, mode):
    g = dd._proj(h, gate, lo_gate, scale, mode)
    u = dd._proj(h, up, lo_up, scale, mode)
    return dd._proj(jax.nn.silu(g) * u, down, lo_down, scale, mode)


def routed(h, W, hp, valid, mode, capacity: Optional[int] = None):
    """sum over held experts of w_e FFN_e(h), and the count of (position,
    held expert) pairs. ``capacity`` plants the fault of a layer that
    drops: only an expert's first ``capacity`` positions of a row count
    (tests)."""
    sel, w = route(h, W["router"], W["router_bias"], hp)
    sel &= valid[..., None]
    y = jnp.zeros_like(h)
    pairs = jnp.zeros((), jnp.int32)
    for j in range(hp["held"]):
        e = hp["held_lo"] + j
        mine = sel[..., e]
        if capacity is not None:
            mine &= jnp.cumsum(mine, axis=-1) <= capacity
        out = swiglu(h, W["expert_gate"][j], W["expert_up"][j],
                     W["expert_down"][j], None, None, None, 0.0, mode)
        y = y + jnp.where(mine, w[..., e], 0.0)[..., None] * out
        pairs = pairs + jnp.sum(mine)
    return y, pairs


def layer_fwd(x, W, lora, hp, positions, segments, mode):
    """One layer -> (x, held pairs). ``W["window"]`` / ``W["rotary"]``
    carry the layer's attention kind (a full layer's window is wider
    than any row); the MLP's kind is which leaves ``W`` has."""
    b, S, _ = x.shape
    H, K, dh = hp["heads"], hp["kv_heads"], hp["head_dim"]
    sc, lo = hp["lora_scale"], lora.get
    h = dd.rms_norm(x, W["attn_norm"], hp["eps"])
    q = dd._proj(h, W["wq"], lo("wq"), sc, mode).reshape(b, S, H, dh)
    k = dd._proj(h, W["wk"], lo("wk"), sc, mode).reshape(b, S, K, dh)
    v = dd._proj(h, W["wv"], lo("wv"), sc, mode).reshape(b, S, K, dh)
    q = dd.rms_norm(q, W["q_norm"], hp["eps"])
    k = dd.rms_norm(k, W["k_norm"], hp["eps"])
    q = jnp.where(W["rotary"], dd.rope(q, positions, hp["theta"]), q)
    k = jnp.where(W["rotary"], dd.rope(k, positions, hp["theta"]), k)
    o = attention(q, k, v, positions, segments, W["window"])
    x = x + dd._proj(o.reshape(b, S, H * dh), W["wo"], lo("wo"), sc, mode)
    h = dd.rms_norm(x, W["mlp_norm"], hp["eps"])
    if "router" not in W:
        return x + swiglu(h, W["w_gate"], W["w_up"], W["w_down"],
                          lo("w_gate"), lo("w_up"), lo("w_down"), sc,
                          mode), jnp.zeros((), jnp.int32)
    valid = jnp.ones((b, S), bool) if segments is None else segments != 0
    y, pairs = routed(h, W, hp, valid, mode, hp.get("capacity"))
    if "shared_gate" in W:
        y = y + swiglu(h, W["shared_gate"], W["shared_up"],
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
                or config.get("scoring_func", "sigmoid") != "sigmoid" \
                or int(config.get("n_group", 1)) != 1:
            raise ValueError("this reference has SwiGLU experts under a "
                             "sigmoid router with one group only")
        self.config = config
        self.sizes = wm.dims_from_config(config)
        self.key = wts.seed_key(seed)
        self.store_dtype = store_dtype
        self.quant = (quant_kind, quant_group)
        self.rank = int(lora_rank)
        self.targets = tuple(lora_targets) if self.rank else ()
        d = self.sizes
        rope = config.get("rope_parameters") or {}
        self.hp = {
            "heads": d["heads"], "kv_heads": d["kv_heads"],
            "head_dim": d["head_dim"],
            "eps": float(config["rms_norm_eps"]),
            "theta": float(rope.get("rope_theta",
                                    config.get("rope_theta", 1e4))),
            "top_k": d["top_k"], "held": d["held"],
            "held_lo": d["held_lo"],
            "routed_scale": float(config.get("routed_scaling_factor", 1.0)),
            "lora_scale": (lora_alpha / lora_rank) if lora_rank else 0.0,
        }
        if not config.get("norm_topk_prob", True):
            raise ValueError("weights are renormalised over the selected")
        self.held_pairs: List[int] = []
        # the key is an argument of every compiled program: closed over,
        # it would be a constant, and each seed would compile its own
        self._layer = jax.jit(self._make_layer, static_argnums=2)
        self._outer = jax.jit(self._leaf, static_argnums=1)

    @property
    def dims(self) -> Dict[str, object]:
        """The sizes, and the held pairs counted so far, one number a
        followed step (the driver reads them after the steps)."""
        return dict(self.sizes, held_pairs=list(self.held_pairs))

    def kinds(self, i: int):
        return wm.layer_kinds(self.config, i)

    def _leaf(self, key, name, layer, dtype=None, expert=0):
        return wm.stored(self.sizes, key, name, layer,
                         dtype or self.store_dtype,
                         expert).astype(jnp.float32)

    def _make_layer(self, key, layer, mlp):
        kind, group = self.quant
        W = {n: self._leaf(key, n, layer)
             for n in ("attn_norm", "mlp_norm", "q_norm", "k_norm")}
        # a quantiser is handed the weight in bfloat16, as checkpoints
        # of these models hold it
        dt = None if kind in (None, "none") else "bfloat16"

        def q(name, expert=0):
            return dd.quant_dequant(self._leaf(key, name, layer, dt, expert),
                                    kind, group)
        for n in wm.ATTENTION:
            W[n] = q(n)
        if mlp == "dense":
            for n in wm.DENSE_MLP:
                W[n] = q(n)
            return W
        W["router"] = self._leaf(key, "router", layer)
        W["router_bias"] = self._leaf(key, "router_bias", layer)
        if self.sizes["shared"]:
            for n in wm.SHARED:
                W[n] = q(n)
        experts = self.sizes["held_lo"] + jnp.arange(self.sizes["held"])
        for n in wm.EXPERT:
            W[n] = jax.lax.map(lambda e, n=n: q(n, e), experts)
        return W

    def layer(self, i: int) -> Dict[str, jnp.ndarray]:
        attn, mlp = self.kinds(i)
        W = self._layer(self.key, jnp.asarray(i, jnp.int32), mlp)
        sliding = attn == "sliding"
        return dict(W, rotary=jnp.asarray(sliding), window=jnp.asarray(
            int(self.config["sliding_window"]) if sliding else NO_WINDOW,
            jnp.int32))

    def outer(self, name: str) -> jnp.ndarray:
        return self._outer(self.key, name, 0)

    def init_lora(self) -> List[Dict[str, Dict[str, jnp.ndarray]]]:
        def make(key, i, targets):
            return {t: {"a": wm.lora_a(self.sizes, key, t, i, self.rank),
                        "b": jnp.zeros(wm.lora_b_shape(self.sizes, t,
                                                       self.rank),
                                       jnp.float32)}
                    for t in targets}
        make = jax.jit(make, static_argnums=2)
        return [make(self.key, jnp.asarray(i, jnp.int32),
                     wm.lora_targets(self.targets, self.kinds(i)[1],
                                     self.sizes))
                for i in range(self.sizes["layers"])]


class LoraTrainer(dd.LoraTrainer):
    """``dense_decoder``'s trainer over this module's layer; the held
    pairs of every forward pass are counted on the way."""

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
        self._pairs = 0

        def fwd_and_count(*args):
            x, pairs = counted(*args)
            self._pairs += int(pairs)
            return x
        self._fwd, self._bwd = fwd_and_count, jax.jit(bwd)

    def step(self, batch) -> dict:
        self._pairs = 0
        out = super().step(batch)
        self.model.held_pairs.append(self._pairs)
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

"""Plain reference of a dense decoder-only transformer (the Llama/Mistral
layer: RMSNorm, rotary GQA attention with a sliding window, SwiGLU),
with NF4 / int8 weight quantisation, LoRA, cross-entropy and AdamW.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul
precision: no kernels, no cache, no batching tricks. It imports nothing
of the program and takes nothing the program made: weights come from
``benchmark/weights.py`` (seed -> leaf), and it quantises them itself.

It works layer by layer, so that a 7 B model fits one chip once the
program's state is freed: one layer's weights are made, used for every
block of rows, and dropped.

``mode="int8"`` and ``mode="fp8"`` are the controls of "How `correct` is
decided": the same mathematics with the operands of every projection
matmul rounded to int8 or to float8 e4m3 (activations per token, weights
per output channel, absmax scaled), the precisions next below the
bfloat16 the configurations compute in; int8 is the one a v5e has units
for. Departures from the published description: none known; the rotary
embedding uses the split-halves layout of the HF checkpoints.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights as wts

HI = jax.lax.Precision.HIGHEST

# NF4 code book: QLoRA (Dettmers et al. 2023), appendix E
NF4 = np.array([
    -1.0, -0.6961928009986877, -0.5250730514526367, -0.39491748809814453,
    -0.28444138169288635, -0.18477343022823334, -0.09105003625154495, 0.0,
    0.07958029955625534, 0.16093020141124725, 0.24611230194568634,
    0.33791524171829224, 0.44070982933044434, 0.5626170039176941,
    0.7229568362236023, 1.0], dtype=np.float32)


# ---------------------------------------------------------------------------
# quantisation, as published: absmax blocks along the input dimension
# ---------------------------------------------------------------------------

def quant_dequant(w: jnp.ndarray, kind: Optional[str], group: int):
    """w [D, F] float32 -> what the quantised weight stands for."""
    if kind in (None, "none"):
        return w
    D, F = w.shape
    if D % group:
        raise ValueError(f"input dim {D} is no multiple of group {group}")
    wg = w.reshape(D // group, group, F)
    absmax = jnp.max(jnp.abs(wg), axis=1, keepdims=True)
    if kind == "nf4":
        normed = wg / jnp.where(absmax > 0, absmax, 1.0)
        # nearest code: the last midpoint below decides (ties go to the
        # lower code). A chain of selects, not a table look-up: a gather
        # of 2e8 elements takes the chip a second and a half a layer
        mids = (NF4[1:] + NF4[:-1]) / 2
        code = jnp.full(normed.shape, NF4[0], jnp.float32)
        for m, value in zip(mids, NF4[1:]):
            code = jnp.where(normed > m, value, code)
        out = code * absmax
    elif kind == "int8":
        scale = absmax / 127.0
        codes = jnp.clip(jnp.round(wg / jnp.where(scale > 0, scale, 1.0)),
                         -127.0, 127.0)
        out = codes * scale
    else:
        raise ValueError(f"unknown quantisation {kind!r}")
    return out.reshape(D, F)


def _int8_ste(x: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Round to int8 along ``axis`` (absmax), straight-through gradient."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s > 0, s, 1.0)
    q = jnp.clip(jnp.round(x / s), -127, 127) * s
    return x + jax.lax.stop_gradient(q - x)


def _fp8_ste(x: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Round to float8 e4m3 along ``axis`` (absmax scaled to 448)."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)


def mm(x: jnp.ndarray, w: jnp.ndarray, mode: str) -> jnp.ndarray:
    """x [..., D] @ w [D, F]."""
    if mode == "int8":
        x, w = _int8_ste(x, -1), _int8_ste(w, 0)
    elif mode == "fp8":
        x, w = _fp8_ste(x, -1), _fp8_ste(w, 0)
    elif mode != "f32":
        raise ValueError(f"unknown mode {mode!r}")
    return jnp.matmul(x, w, precision=HI)


# ---------------------------------------------------------------------------
# the layer
# ---------------------------------------------------------------------------

def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, positions, theta):
    """x [b, S, h, dh], positions [b, S]; halves layout."""
    dh = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, dh, 2, dtype=np.float64) / dh))
    ang = positions[..., None].astype(jnp.float32) * jnp.asarray(
        inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, positions, segments, window):
    """q [b,S,H,dh], k/v [b,S,K,dh] -> [b,S,H,dh]; causal, windowed, and
    within a segment where segments are given (0 = padding)."""
    b, S, H, dh = q.shape
    K = k.shape[2]
    qg = q.reshape(b, S, K, H // K, dh)
    s = jnp.einsum("bskgd,btkd->bkgst", qg, k, precision=HI) / math.sqrt(dh)
    qp, kp = positions[:, :, None], positions[:, None, :]
    mask = kp <= qp
    if window is not None:
        mask &= kp > qp - window
    if segments is not None:
        mask &= segments[:, :, None] == segments[:, None, :]
        mask &= segments[:, None, :] != 0
    s = jnp.where(mask[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgst,btkd->bskgd", p, v, precision=HI)
    return o.reshape(b, S, H, dh)


def _proj(x, w, lo, scale, mode):
    y = mm(x, w, mode)
    if lo is not None:
        y = y + scale * jnp.matmul(jnp.matmul(x, lo["a"], precision=HI),
                                   lo["b"], precision=HI)
    return y


def layer_fwd(x, W, lora, hp, positions, segments, mode):
    """One layer. x [b,S,D] float32; W the layer's leaves; lora
    {target: {a, b}} or {}."""
    b, S, _ = x.shape
    H, K, dh = hp["heads"], hp["kv_heads"], hp["head_dim"]
    sc = hp["lora_scale"]
    lo = lora.get
    h = rms_norm(x, W["attn_norm"], hp["eps"])
    q = _proj(h, W["wq"], lo("wq"), sc, mode).reshape(b, S, H, dh)
    k = _proj(h, W["wk"], lo("wk"), sc, mode).reshape(b, S, K, dh)
    v = _proj(h, W["wv"], lo("wv"), sc, mode).reshape(b, S, K, dh)
    q, k = rope(q, positions, hp["theta"]), rope(k, positions, hp["theta"])
    o = attention(q, k, v, positions, segments, hp["window"])
    x = x + _proj(o.reshape(b, S, H * dh), W["wo"], lo("wo"), sc, mode)
    h = rms_norm(x, W["mlp_norm"], hp["eps"])
    gate = _proj(h, W["w_gate"], lo("w_gate"), sc, mode)
    up = _proj(h, W["w_up"], lo("w_up"), sc, mode)
    return x + _proj(jax.nn.silu(gate) * up, W["w_down"], lo("w_down"),
                     sc, mode)


def tail_nll(x, final_norm, lm_head, targets, weights, eps, mode):
    """Weighted sum of token cross-entropies, and the sum of weights."""
    logits = mm(rms_norm(x, final_norm, eps), lm_head, mode)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return jnp.sum((lse - tgt) * weights), jnp.sum(weights)


class Model:
    """Seed -> layers of one configuration, as it stores them."""

    def __init__(self, config: dict, seed: int, *, store_dtype: str,
                 quant_kind: Optional[str], quant_group: int = 64,
                 lora_rank: int = 0, lora_alpha: float = 0.0,
                 lora_targets: Sequence[str] = wts.PROJECTIONS):
        if config.get("hidden_act", "silu") != "silu":
            raise ValueError("this reference has the SwiGLU layer only")
        self.dims = wts.dims_from_config(config)
        self.key = wts.seed_key(seed)
        self.store_dtype = store_dtype
        self.quant = (quant_kind, quant_group)
        self.rank = int(lora_rank)
        self.targets = tuple(lora_targets) if self.rank else ()
        self.hp = {
            "heads": self.dims["heads"], "kv_heads": self.dims["kv_heads"],
            "head_dim": self.dims["head_dim"],
            "eps": float(config["rms_norm_eps"]),
            "theta": float(config["rope_theta"]),
            "window": config.get("sliding_window"),
            "lora_scale": (lora_alpha / lora_rank) if lora_rank else 0.0,
        }
        # the key is an argument of every compiled program: closed over,
        # it would be a constant, and each seed would compile its own
        self._layer = jax.jit(self._make_layer)
        self._outer = jax.jit(self._leaf, static_argnums=1)

    def _leaf(self, key, name, layer, dtype=None):
        return wts.stored(self.dims, key, name, layer,
                          dtype or self.store_dtype).astype(jnp.float32)

    def _make_layer(self, key, layer):
        kind, group = self.quant
        W = {n: self._leaf(key, n, layer)
             for n in ("attn_norm", "mlp_norm")}
        # a quantiser is handed the weight in bfloat16, as checkpoints
        # of these models hold it
        dt = None if kind in (None, "none") else "bfloat16"
        for n in wts.PROJECTIONS:
            W[n] = quant_dequant(self._leaf(key, n, layer, dt), kind, group)
        return W

    def layer(self, i: int) -> Dict[str, jnp.ndarray]:
        return self._layer(self.key, jnp.asarray(i, jnp.int32))

    def outer(self, name: str) -> jnp.ndarray:
        return self._outer(self.key, name, 0)

    def init_lora(self) -> List[Dict[str, Dict[str, jnp.ndarray]]]:
        make = jax.jit(lambda key, i: {
            t: {"a": wts.lora_a(self.dims, key, t, i, self.rank),
                "b": jnp.zeros(wts.lora_b_shape(self.dims, t, self.rank),
                               jnp.float32)}
            for t in self.targets})
        return [make(self.key, jnp.asarray(i, jnp.int32))
                for i in range(self.dims["layers"])]


# ---------------------------------------------------------------------------
# training: LoRA over a frozen (quantised) base, AdamW, clipping
# ---------------------------------------------------------------------------

def lr_at(count: int, opt: dict) -> float:
    """Linear warm-up from 0, then cosine to 1% of the peak."""
    peak, total = opt["lr"], opt["total_steps"]
    warm = max(1, int(total * opt["warmup_ratio"]))
    if count < warm:
        return peak * count / warm
    decay = max(total, warm + 1) - warm
    frac = min(max((count - warm) / decay, 0.0), 1.0)
    end = peak * 0.01
    return end + (peak - end) * 0.5 * (1.0 + math.cos(math.pi * frac))


class LoraTrainer:
    """The first steps of a LoRA fine-tune, in float32."""

    def __init__(self, model: Model, opt: dict, *, mode: str = "f32",
                 rows_per_block: int = 2, keep_rows: Optional[slice] = None):
        self.model, self.opt, self.mode = model, opt, mode
        self.block = rows_per_block
        # a planted fault (tests, readings): train on part of the batch
        self.keep_rows = keep_rows
        self.lora = model.init_lora()
        self.lora0 = self.lora
        zeros = [jax.tree.map(jnp.zeros_like, lo) for lo in self.lora]
        self.m, self.v = zeros, zeros
        self.count = 0
        hp = model.hp

        def fwd(x, W, lo, positions, segments):
            return layer_fwd(x, W, lo, hp, positions, segments, mode)

        def bwd(x, W, lo, positions, segments, g):
            _, vjp = jax.vjp(
                lambda x_, lo_: layer_fwd(x_, W, lo_, hp, positions,
                                          segments, mode), x, lo)
            return vjp(g)

        def tail(x, fn, head, targets, weights):
            (nll, w), gx = jax.value_and_grad(
                lambda x_: tail_nll(x_, fn, head, targets, weights,
                                    hp["eps"], mode), has_aux=True)(x)
            return nll, w, gx

        b1, b2, eps, wd = (opt["b1"], opt["b2"], opt["eps"],
                           opt["weight_decay"])

        def update(p, g, m, v, clip, lr, c1, c2):
            g = g * clip
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            upd = (m / c1) / (jnp.sqrt(v / c2) + eps) + wd * p
            return p - lr * upd, m, v

        self._fwd, self._bwd, self._tail, self._update = (
            jax.jit(fwd), jax.jit(bwd), jax.jit(tail), jax.jit(update))

    def gradients(self, batch: Dict[str, np.ndarray]):
        """Loss and the gradient of the mean token loss w.r.t. LoRA."""
        model, L = self.model, self.model.dims["layers"]
        rows = slice(None) if self.keep_rows is None else self.keep_rows
        inputs = np.asarray(batch["inputs"])[rows]
        n, S = inputs.shape
        cuts = [slice(i, min(i + self.block, n))
                for i in range(0, n, self.block)]

        def part(key, c, dtype):
            if key not in batch:
                return None
            return jnp.asarray(np.asarray(batch[key])[rows][c], dtype)

        pos = [part("positions", c, jnp.int32) if "positions" in batch
               else jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32),
                                     (c.stop - c.start, S)) for c in cuts]
        seg = [part("segment_ids", c, jnp.int32) for c in cuts]
        embed = model.outer("embed")
        xs = [[embed[jnp.asarray(inputs[c])] for c in cuts]]
        del embed
        for i in range(L):
            W = model.layer(i)
            xs.append([self._fwd(x, W, self.lora[i], p, s)
                       for x, p, s in zip(xs[-1], pos, seg)])
        fn, head = model.outer("final_norm"), model.outer("lm_head")
        nll_sum, w_sum, gx = 0.0, 0.0, []
        for x, c in zip(xs.pop(), cuts):
            nll, w, g = self._tail(x, fn, head,
                                   part("targets", c, jnp.int32),
                                   part("weights", c, jnp.float32))
            nll_sum, w_sum = nll_sum + nll, w_sum + w
            gx.append(g)
        del fn, head
        grads: List = [None] * L
        for i in reversed(range(L)):
            W = model.layer(i)
            acc = None
            for j, (x, p, s) in enumerate(zip(xs.pop(), pos, seg)):
                gx[j], gl = self._bwd(x, W, self.lora[i], p, s, gx[j])
                acc = gl if acc is None else jax.tree.map(jnp.add, acc, gl)
            grads[i] = acc
        inv = 1.0 / w_sum
        grads = [jax.tree.map(lambda g: g * inv, g) for g in grads]
        return float(nll_sum * inv), grads

    def step(self, batch) -> dict:
        """One optimizer step. Returns the loss and, leaf by leaf (over
        all layers), the norm of the gradient as AdamW gets it."""
        o = self.opt
        loss, grads = self.gradients(batch)
        sq = leaf_sq_norms(grads)
        gnorm = math.sqrt(sum(sq.values()))
        clip = o["clip"] / max(gnorm, o["clip"])
        lr = lr_at(self.count, o)
        self.count += 1
        t = self.count
        b1, b2 = o["b1"], o["b2"]

        scal = [jnp.asarray(x, jnp.float32) for x in
                (clip, lr, 1 - b1 ** t, 1 - b2 ** t)]
        new = jax.tree.map(lambda p, g, m, v: self._update(p, g, m, v, *scal),
                           self.lora, grads, self.m, self.v)
        self.lora, self.m, self.v = (
            jax.tree.map(lambda out: out[i], new,
                         is_leaf=lambda x: isinstance(x, tuple))
            for i in range(3))
        return {"loss": loss,
                "grad_norm": {k: math.sqrt(s) * clip for k, s in sq.items()}}

    def change_norms(self) -> Dict[str, float]:
        diff = [jax.tree.map(jnp.subtract, a, b)
                for a, b in zip(self.lora, self.lora0)]
        return {k: math.sqrt(s) for k, s in leaf_sq_norms(diff).items()}


def trainer(config: dict, seed: int, *, store_dtype: str,
            quant_kind: Optional[str], lora: Optional[dict],
            optimizer: dict, mode: str = "f32", keep_rows=None):
    """(model, trainer) for the first steps of the job given. ``lora``
    is {"rank", "alpha", "targets"}, or None for a full fine-tune, which
    this reference does not follow yet."""
    if lora is None:
        raise ValueError("this reference follows LoRA fine-tunes only; a "
                         "full fine-tune needs a reference of its own")
    model = Model(config, seed, store_dtype=store_dtype,
                  quant_kind=quant_kind, lora_rank=lora["rank"],
                  lora_alpha=lora["alpha"], lora_targets=lora["targets"])
    return model, LoraTrainer(model, optimizer, mode=mode,
                              keep_rows=keep_rows)


def leaf_sq_norms(per_layer: Sequence[dict]) -> Dict[str, float]:
    """{"wq.a": sum of squares over all layers, ...}."""
    @jax.jit
    def sq(tree):
        return jax.tree.map(lambda x: jnp.sum(x.astype(jnp.float32) ** 2),
                            tree)
    out: Dict[str, float] = {}
    for layer in per_layer:
        for t, ab in jax.device_get(sq(layer)).items():
            for k, s in ab.items():
                out[f"{t}.{k}"] = out.get(f"{t}.{k}", 0.0) + float(s)
    return out

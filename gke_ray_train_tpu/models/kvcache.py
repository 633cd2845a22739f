"""KV-cache decode (VERDICT r1 missing #3).

The reference's inference rides HF ``model.generate`` with its built-in
KV cache (/root/reference/ray-jobs/fine_tune_llama_ray.py:138-146). The
round-1 decode loop (models/decode.py) recomputes the full O(L²) forward
per generated token — correct (it is the oracle this module is tested
against) but unusable at 8B/300-token scale.

TPU design:
- The cache is a pytree shaped like the scanned block stack
  ([n_repeats, B, max_len, n_kv_heads, head_dim] per pattern position),
  so the same ``lax.scan`` that runs training blocks runs decode blocks.
- One function, ``forward_step``, serves prefill (T = prompt length)
  and decode (T = 1): new tokens sit at per-row positions
  ``lens + arange(T)``, their K/V are scattered into the cache, and
  attention masks by absolute position (kv_pos <= q_pos) — ragged
  prompts need no compaction, garbage slots from right-padding are
  overwritten before they ever become visible.
- Static shapes everywhere: the decode loop is a ``lax.while_loop``
  over a fixed buffer, one compile per (B, L, max_new) bucket.
- Prefill runs through the flash kernel when the prompt and cache
  widths tile by 128 (the dense path materializes [B, H, T, max_len]
  logits — the O(S²) memory wall at long prompts); T = 1 decode steps
  and ``attn_impl="xla"`` keep the dense mask.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from gke_ray_train_tpu.models.config import ModelConfig
from gke_ray_train_tpu.models.transformer import (
    Params, _lora_entry, _mlp, _proj)
from gke_ray_train_tpu.ops.attention import (
    dot_product_attention, make_attention_mask)
from gke_ray_train_tpu.ops.norms import rms_norm
from gke_ray_train_tpu.ops.rope import (
    apply_rope, rope_frequencies, sinusoidal_positions)

Cache = Dict[str, Any]


def require_decodable(cfg: ModelConfig) -> None:
    """The cached forward is a second copy of the block
    (:func:`forward_step`), and it has not caught up with every model
    the trainer runs: refuse those by name instead of computing
    something else."""
    missing = []
    if cfg.prologue_layers:
        missing.append("a cache for the leading dense layers outside "
                       "the scan")
    if cfg.qk_norm:
        missing.append("q/k norm in the cached block")
    if set(cfg.rope_kinds) != {"global", "sliding"}:
        missing.append("a cache per attention kind (rotated keys in a "
                       "window-sized ring for sliding layers, unrotated "
                       "keys at full length for the others)")
    if cfg.latent_attention:
        missing.append("a latent cache (the normed key/value latent and "
                       "the shared rotated key a position, with the "
                       "up-projections absorbed at decode)")
    if "ssm" in cfg.block_pattern:
        missing.append("a recurrent state beside keys and values (a "
                       "state-space layer's conv taps and scan state a "
                       "slot, in the cache manager that pages the "
                       "attention layers' keys and values)")
    if cfg.n_experts and cfg.router != "softmax":
        missing.append("the sigmoid router's layer at decode shapes")
    if cfg.n_mtp_layers:
        missing.append("the multi-token-prediction head (self-drafting)")
    if missing:
        raise NotImplementedError(
            f"{cfg.name} trains here but cannot be served yet "
            f"(inference.py, serve/, models/kvcache.py): missing "
            + "; ".join(missing))


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype: Optional[str] = None) -> Cache:
    """Zeroed cache pytree: blocks[i] = {"k","v"} of
    [n_repeats, batch, max_len, n_kv_heads, head_dim]."""
    require_decodable(cfg)
    dt = jnp.dtype(dtype or cfg.dtype)
    hd = cfg.resolved_head_dim
    shape = (cfg.n_repeats, batch, max_len, cfg.n_kv_heads, hd)
    return {"blocks": [{"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}
                       for _ in cfg.block_pattern]}


def insert_cache_slot(pool: Cache, slot: jnp.ndarray, row: Cache) -> Cache:
    """Write a batch=1 cache ``row`` into batch index ``slot`` of a
    pooled cache — the continuous-batching admit path (serve/engine.py):
    a freshly prefilled request takes over a finished sequence's slot
    without touching any other slot's K/V bytes (pure
    ``dynamic_update_slice`` along the batch axis, so the surviving
    sequences' attention inputs are bit-identical before and after).

    ``slot`` may be a traced scalar — one compiled insert serves every
    slot index."""
    def upd(p, r):
        return jax.lax.dynamic_update_slice_in_dim(
            p, r.astype(p.dtype), slot, axis=1)
    return jax.tree.map(upd, pool, row)


def _scatter_rows(cache_kv: jnp.ndarray, new_kv: jnp.ndarray,
                  lens: jnp.ndarray) -> jnp.ndarray:
    """Write new_kv [B, T, K, hd] into cache_kv [B, max_len, K, hd] at
    per-row offsets lens[b] — a vmapped dynamic_update_slice: O(T·K·hd)
    copy per row, no materialized [T, max_len] one-hot.

    dynamic_update_slice clamps out-of-range starts, so a done row whose
    lens reached max_len re-writes the last slot instead of dropping the
    write — harmless, nothing is read for done rows."""
    def upd(c, n, start):
        return jax.lax.dynamic_update_slice(c, n, (start, 0, 0))
    return jax.vmap(upd)(cache_kv, new_kv.astype(cache_kv.dtype), lens)


def _warn_dense_prefill(T: int, max_len: int) -> None:
    import logging

    from gke_ray_train_tpu.logging_utils import warn_once
    warn_once(logging.getLogger(__name__), ("dense_prefill", T, max_len),
              "prefill width %d / cache %d do not tile by 128 — falling "
              "back to dense-mask attention (O(T*max_len) logits in "
              "memory); pad the prompt buffer to 128-multiples to use "
              "the flash kernel", T, max_len)


def forward_step(params: Params, tokens: jnp.ndarray, cfg: ModelConfig,
                 cache: Cache, lens: jnp.ndarray, *,
                 lora: Optional[Params] = None,
                 lora_scale: float = 1.0,
                 mesh=None) -> Tuple[jnp.ndarray, Cache]:
    """tokens [B, T] at per-row absolute positions lens + arange(T) →
    (logits [B, T, vocab] fp32, updated cache).

    Same math as transformer.forward restricted to the new tokens, with
    K/V read from + written to the cache. Supports every family the
    trainer supports (GQA, RoPE/sinusoidal, sliding-window patterns,
    softcaps, QTensor bases, LoRA adapters).

    ``mesh``: the mesh the params live on when they span several
    devices. The cache and the tokens stay replicated; only the flash
    prefill needs it, because a compiled Mosaic kernel cannot be
    partitioned by GSPMD and must sit inside a ``shard_map``.
    """
    # multi-tenant serving: ``lora`` is {"aslot": [B] int32, "blocks":
    # stacked pool with adapter axis 1}. Each row's adapter is gathered
    # per repeat inside the scan (ops/lora_batched.py says why), where
    # ``_proj`` then sees per-row [B, d_in, r] entries
    aslot = lora.get("aslot") if lora is not None else None

    require_decodable(cfg)
    B, T = tokens.shape
    dtype = jnp.dtype(cfg.dtype)
    eps, sp1 = cfg.norm_eps, cfg.norm_scale_plus_one
    hd = cfg.resolved_head_dim
    H, K = cfg.n_heads, cfg.n_kv_heads
    max_len = cache["blocks"][0]["k"].shape[2]

    positions = lens[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]

    x = params["embed"].astype(dtype)[tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, dtype)
    if cfg.positional == "sinusoidal":
        table = jnp.asarray(sinusoidal_positions(cfg.max_seq_len,
                                                 cfg.d_model))
        x = x + table.astype(dtype)[jnp.clip(positions, 0,
                                             cfg.max_seq_len - 1)]
        rope = None
    else:
        rope = jnp.asarray(rope_frequencies(
            hd, theta=cfg.rope_theta, llama3_scaling=cfg.rope_scaling))

    kv_positions = jnp.broadcast_to(
        jnp.arange(max_len, dtype=jnp.int32)[None, :], (B, max_len))
    # prefill goes through the flash kernel when shapes tile (the dense
    # path materializes [B, H, T, max_len] logits — the O(S²) memory
    # wall at long prompts); single-token decode steps (T=1) and odd
    # widths keep the cheap dense mask, and attn_impl="xla" forces it.
    # ring/a2a are training-time context-parallel strategies — decode
    # never shards the sequence, so they resolve to plain flash here.
    use_flash = (cfg.resolved_attn_impl != "xla" and T > 1
                 and T % 128 == 0 and max_len % 128 == 0)
    if not use_flash and cfg.resolved_attn_impl != "xla" and T > 1:
        # loud fallback, same policy as transformer._warn_flash_fallback:
        # a non-tiling long prefill silently eating O(T·max_len) logits
        # memory is easy to miss (pad the prompt buffer to 128s instead)
        _warn_dense_prefill(T, max_len)
    masks = {}
    if not use_flash:
        for kind in set(cfg.block_pattern):
            masks[kind] = make_attention_mask(
                positions, kv_positions, causal=True,
                sliding_window=(cfg.sliding_window if kind == "sliding"
                                else None))

    def repeat_body(x, xs_slice):
        layer_slice = xs_slice[0]
        cache_slice = xs_slice[1]
        lora_slice = xs_slice[2] if lora is not None else None
        if aslot is not None:
            from gke_ray_train_tpu.ops.lora_batched import gather_pool
            lora_slice = gather_pool(lora_slice, aslot)
        new_cache = []
        for p, kind in enumerate(cfg.block_pattern):
            lp = layer_slice[p]
            ck = cache_slice[p]
            lo = lora_slice[p] if lora_slice is not None else None

            def lr(name):
                return _lora_entry(lo, name)

            h = rms_norm(x, lp["attn_norm"], eps=eps, scale_plus_one=sp1)
            q = _proj(h, lp["wq"], lr("wq"), lora_scale, dtype,
                      bias=lp.get("bq"))
            k = _proj(h, lp["wk"], lr("wk"), lora_scale, dtype,
                      bias=lp.get("bk"))
            v = _proj(h, lp["wv"], lr("wv"), lora_scale, dtype,
                      bias=lp.get("bv"))
            q = q.reshape(B, T, H, hd)
            k = k.reshape(B, T, K, hd)
            v = v.reshape(B, T, K, hd)
            if rope is not None:
                q = apply_rope(q, positions, rope)
                k = apply_rope(k, positions, rope)
            k_cache = _scatter_rows(ck["k"], k.astype(ck["k"].dtype), lens)
            v_cache = _scatter_rows(ck["v"], v.astype(ck["v"].dtype), lens)
            window = cfg.sliding_window if kind == "sliding" else None
            if use_flash:
                # single kernel entry point for the whole repo
                # (ops/dispatch.py); batch_axes=None — the request
                # batch is replicated, whatever shards the weights
                from gke_ray_train_tpu.ops.dispatch import (
                    attention_dispatch)
                out = attention_dispatch(
                    "flash", q, k_cache.astype(dtype),
                    v_cache.astype(dtype),
                    q_positions=positions, kv_positions=kv_positions,
                    causal=True, sliding_window=window,
                    scale=cfg.attn_scale, logit_softcap=cfg.attn_softcap,
                    mesh=mesh, batch_axes=None)
            else:
                out = dot_product_attention(
                    q, k_cache.astype(dtype), v_cache.astype(dtype),
                    masks[kind], scale=cfg.attn_scale,
                    logit_softcap=cfg.attn_softcap)
            h = _proj(out.reshape(B, T, H * hd), lp["wo"], lr("wo"),
                      lora_scale, dtype)
            if cfg.post_block_norm:
                h = rms_norm(h, lp["attn_post_norm"], eps=eps,
                             scale_plus_one=sp1)
            x = x + h
            h = rms_norm(x, lp["mlp_norm"], eps=eps, scale_plus_one=sp1)
            if cfg.n_experts > 0:
                # routed expert MLP; the load-balance aux is a training
                # loss term and is discarded at inference
                from gke_ray_train_tpu.ops.moe import moe_mlp
                h, _ = moe_mlp(h, lp["router"], lp["w_gate"], lp["w_up"],
                               lp["w_down"], cfg, dtype)
            else:
                h = _mlp(h, lp, cfg, dtype, lora_p=lo,
                         lora_scale=lora_scale)
            if cfg.post_block_norm:
                h = rms_norm(h, lp["mlp_post_norm"], eps=eps,
                             scale_plus_one=sp1)
            x = x + h
            new_cache.append({"k": k_cache, "v": v_cache})
        return x, new_cache

    xs = [params["blocks"], cache["blocks"]]
    if lora is not None:
        xs.append(lora["blocks"])
    x, new_blocks = jax.lax.scan(repeat_body, x, tuple(xs))

    x = rms_norm(x, params["final_norm"], eps=eps, scale_plus_one=sp1)
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = jnp.einsum("btd,dv->btv", x, head.astype(dtype),
                        preferred_element_type=jnp.float32)
    if cfg.logit_softcap is not None:
        logits = jnp.tanh(logits / cfg.logit_softcap) * cfg.logit_softcap
    return logits, {"blocks": new_blocks}


@partial(jax.jit, static_argnames=("cfg", "max_new_tokens", "eos_ids",
                                   "lora_scale"))
def greedy_generate_cached(params: Params, prompt: jnp.ndarray,
                           prompt_len: jnp.ndarray, cfg: ModelConfig, *,
                           max_new_tokens: int = 64,
                           eos_ids: Sequence[int] = (),
                           lora: Optional[Params] = None,
                           lora_scale: float = 1.0) -> jnp.ndarray:
    """Drop-in replacement for decode.greedy_generate (same signature,
    same outputs) running prefill + cached single-token steps.

    prompt: [B, L] right-padded buffer with L >= prompt_len + max_new;
    the prompt region (L - max_new_tokens) is prefilled in one pass.

    The prefill width is rounded UP to a 128 multiple (capped at L) so
    the flash-prefill gate engages for any max_new_tokens. Safe by the
    same invariant right-padding already relies on: garbage K/V written
    past prompt_len sit at positions strictly above every query's until
    the decode loop overwrites them (one slot per step, always writing
    slot ``lens`` before attending), so they are never unmasked.
    """
    B, L = prompt.shape
    Lp = max(L - max_new_tokens, 1)
    if L % 128 == 0 and Lp > 1:
        # only when the flash gate can actually engage (max_len = L must
        # tile too) — otherwise rounding just widens the dense prefill
        Lp = min(L, ((Lp + 127) // 128) * 128)
    eos = jnp.asarray(list(eos_ids) or [-1], jnp.int32)

    cache = init_cache(cfg, B, L)
    logits, cache = forward_step(
        params, prompt[:, :Lp], cfg, cache,
        jnp.zeros((B,), jnp.int32), lora=lora, lora_scale=lora_scale)
    idx = jnp.clip(prompt_len - 1, 0, Lp - 1)
    cur_tok = jnp.argmax(
        jnp.take_along_axis(logits, idx[:, None, None], axis=1)[:, 0, :],
        axis=-1).astype(jnp.int32)

    def cond(state):
        buf, lens, done, cache, cur_tok, step = state
        return (step < max_new_tokens) & ~jnp.all(done)

    def body(state):
        buf, lens, done, cache, cur_tok, step = state
        write_pos = jnp.clip(lens, 0, L - 1)
        buf = jnp.where(
            (~done)[:, None] & (jnp.arange(L)[None, :] ==
                                write_pos[:, None]),
            cur_tok[:, None], buf)
        logits, cache = forward_step(
            params, cur_tok[:, None], cfg, cache, lens,
            lora=lora, lora_scale=lora_scale)
        next_tok = jnp.argmax(logits[:, 0, :], axis=-1).astype(jnp.int32)
        now_eos = jnp.any(cur_tok[:, None] == eos[None, :], axis=-1)
        new_lens = jnp.where(done | (lens >= L), lens, lens + 1)
        new_done = done | now_eos | (new_lens >= L)
        return buf, new_lens, new_done, cache, next_tok, step + 1

    buf, _, _, _, _, _ = jax.lax.while_loop(
        cond, body, (prompt, prompt_len, jnp.zeros((B,), bool), cache,
                     cur_tok, jnp.asarray(0)))
    return buf

"""Continuous-batching inference engine (ROADMAP #2, Orca-style).

Iteration-level scheduling over bucketed, jitted executables:

- Every request is assigned the smallest declared length bucket that
  fits ``prompt_len + max_new_tokens`` (serve/bucketing.py). Per bucket
  the engine compiles exactly THREE executables — ``prefill_step``
  (``[1, L]``), ``decode_step`` (``[max_batch, 1]``) and
  ``insert_slot`` — so XLA compiles once per bucket, never per request.
- Admission is slot-level: a finished sequence's slot is refilled at
  the next decode iteration (prefill the newcomer at batch 1, then
  ``dynamic_update_slice`` its KV rows into the pooled cache) without
  flushing the batch — the surviving sequences' K/V bytes are
  untouched, which is what makes continuous-batched output bitwise
  identical to sequential ``greedy_generate_cached``.
- The KV pool is ``models/kvcache.py::init_cache`` at
  ``[max_batch, bucket]`` per bucket — the static-shape stand-in for
  vLLM's dynamic pages (XLA cannot page, it CAN bucket).
- Weights optionally serve quantized (``ops/quant.py``: int8/nf4).
- Cold start: executables build through ``compile_step_with_plan``
  (plan.py), so the persistent compile cache applies and — when a
  ``sidecar_dir`` is given — each executable AOT-serializes through
  ``perf/cache.py``; a fresh replica deserializes all three per bucket
  and reaches its first decoded token with zero new compilations.

Sequential-equivalence contract (drilled in tests/test_serve.py): the
per-slot update rule is exactly ``greedy_generate_cached``'s loop body,
attention masking contributes *exact zeros* for other slots' garbage
(ops/attention.py NEG_INF underflows), and prefill runs the full bucket
width — which equals the oracle's internal prefill width whenever the
bucket is a 128-multiple and ``max_new_tokens < 128``.
"""

from __future__ import annotations

import dataclasses
import logging
import os
import time
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from gke_ray_train_tpu.models.config import ModelConfig
from gke_ray_train_tpu.models.kvcache import (
    forward_step, init_cache, insert_cache_slot)
from gke_ray_train_tpu.ops.quant import quantize_for_serving
from gke_ray_train_tpu.plan import ExecutionPlan, compile_step_with_plan
from gke_ray_train_tpu.serve.bucketing import (
    form_prompt_buffer, pick_bucket, truncate_prompt)

logger = logging.getLogger(__name__)


def serve_plan(**overrides: Any) -> ExecutionPlan:
    """The serving ExecutionPlan: env/config resolved like the trainer's
    (MAX_BATCH / DECODE_BUCKETS / SERVE_QUANT et al.), with kwarg
    overrides — so the engine shares the plan fingerprint/budget/
    plancheck machinery instead of growing a fifth knob dialect."""
    return ExecutionPlan.resolve(**overrides)


@dataclasses.dataclass
class Request:
    """One generation request. ``token_ids`` is the already-tokenized
    prompt (the engine is tokenizer-agnostic; rayint/serving.py holds
    the tokenizer)."""
    rid: str
    token_ids: np.ndarray
    max_new_tokens: int = 32
    # multi-tenant serving: which LoRA tenant decodes this request
    # (None = the base model / reserved zero adapter). Requires the
    # engine to carry an AdapterPool (serve/adapters.py).
    adapter_id: Optional[str] = None


@dataclasses.dataclass
class Completion:
    rid: str
    tokens: np.ndarray          # full row buffer [bucket] incl. prompt
    prompt_len: int
    length: int                 # prompt_len + generated count
    bucket: int
    finish_reason: str          # "eos" | "length"
    submit_s: float = 0.0
    first_token_s: float = 0.0  # submit -> first decoded token
    done_s: float = 0.0         # submit -> completion
    adapter_id: Optional[str] = None

    @property
    def generated(self) -> np.ndarray:
        """The generated region (includes the EOS token when one was
        produced, mirroring ``greedy_generate_cached``'s buffer)."""
        return self.tokens[self.prompt_len:self.length]


# ---------------------------------------------------------------------------
# the pure step bodies (named so shardlint treats them as traced code)
# ---------------------------------------------------------------------------

def init_serve_state(cfg: ModelConfig, batch: int, width: int, *,
                     multi_lora: bool = False,
                     draft_cfg: Optional[ModelConfig] = None
                     ) -> Dict[str, Any]:
    """Zeroed per-bucket batch state: token buffer, per-slot cursors and
    the pooled KV cache. ``active`` starts all-False — empty slots run
    the decode step as masked no-ops until admission fills them.

    ``multi_lora`` adds the per-slot adapter index ``aslot`` [B] (slot 0
    = the reserved zero adapter); ``draft_cfg`` adds the draft model's
    own KV pool ``dcache`` for speculative decoding."""
    state = {
        "buf": jnp.zeros((batch, width), jnp.int32),
        "lens": jnp.zeros((batch,), jnp.int32),
        "stop": jnp.zeros((batch,), jnp.int32),
        "active": jnp.zeros((batch,), bool),
        "cur": jnp.zeros((batch,), jnp.int32),
        "cache": init_cache(cfg, batch, width),
    }
    if multi_lora:
        state["aslot"] = jnp.zeros((batch,), jnp.int32)
    if draft_cfg is not None:
        state["dcache"] = init_cache(draft_cfg, batch, width)
    return state


def _resolve_lora(state: Dict[str, Any], lora: Any, pool: bool) -> Any:
    """In pool mode the compiled step's ``lora`` argument is the stacked
    pool blocks; pair them with the state's per-slot adapter indices
    into the {"aslot", "blocks"} dict kvcache.forward_step gathers."""
    if not pool or lora is None:
        return lora
    return {"aslot": state["aslot"], "blocks": lora}


def params_mesh(params: Any):
    """The mesh ``params`` are sharded over, or None when every leaf
    sits on one device — read off the arrays, so an engine handed
    mesh-placed weights (the post-train smoke on a multi-chip host)
    needs no extra argument."""
    from jax.sharding import NamedSharding
    for leaf in jax.tree.leaves(params):
        sharding = getattr(leaf, "sharding", None)
        if isinstance(sharding, NamedSharding) and sharding.mesh.size > 1:
            return sharding.mesh
    return None


def make_prefill_fn(cfg: ModelConfig, *, lora_scale: float = 1.0,
                    draft_cfg: Optional[ModelConfig] = None,
                    mesh=None) -> Callable:
    """``prefill_step(params, prompt[1, L], prompt_len[1], lora) ->
    (first_tok[1], cache_row)`` — full-bucket-width prefill with lens=0:
    garbage K/V past the prompt sit at positions strictly above every
    query's until decode overwrites them (the kvcache.py invariant).

    With ``draft_cfg`` (speculative decoding) the signature grows a
    draft-params arg and the draft model's cache row rides along:
    ``spec_prefill(params, draft_params, prompt, prompt_len, lora) ->
    (first_tok, cache_row, dcache_row)`` — still ONE executable."""
    def _target_prefill(params, prompt, prompt_len, lora):
        B, L = prompt.shape
        cache = init_cache(cfg, B, L)
        logits, cache = forward_step(
            params, prompt, cfg, cache, jnp.zeros((B,), jnp.int32),
            lora=lora, lora_scale=lora_scale, mesh=mesh)
        idx = jnp.clip(prompt_len - 1, 0, L - 1)
        first = jnp.argmax(
            jnp.take_along_axis(logits, idx[:, None, None],
                                axis=1)[:, 0, :],
            axis=-1).astype(jnp.int32)
        return first, cache

    if draft_cfg is None:
        def prefill_step(params, prompt, prompt_len, lora):
            return _target_prefill(params, prompt, prompt_len, lora)
        return prefill_step

    def spec_prefill_step(params, draft_params, prompt, prompt_len, lora):
        first, cache = _target_prefill(params, prompt, prompt_len, lora)
        B, L = prompt.shape
        dcache = init_cache(draft_cfg, B, L)
        # the draft never carries adapters — it proposes, the (LoRA'd)
        # target disposes; only its K/V matter here
        _, dcache = forward_step(
            draft_params, prompt, draft_cfg, dcache,
            jnp.zeros((B,), jnp.int32), mesh=mesh)
        return first, cache, dcache
    return spec_prefill_step


def make_decode_fn(cfg: ModelConfig, eos_ids: Sequence[int], *,
                   lora_scale: float = 1.0, pool: bool = False
                   ) -> Callable:
    """``decode_step(params, state, lora) -> state`` — one iteration for
    the whole slot batch. The per-slot update rule is EXACTLY
    ``greedy_generate_cached``'s loop body (write the pending token,
    forward one position, argmax, advance), with the loop-count bound
    expressed as the per-slot absolute ``stop`` position — so a slot's
    token stream is bit-identical to a batch-1 greedy decode.

    ``pool=True`` (multi-tenant): ``lora`` is the stacked adapter-pool
    blocks and the state carries per-slot ``aslot`` indices — one shared
    executable decodes a mixed-tenant batch (ops/lora_batched.py)."""
    eos_host = np.asarray(list(eos_ids) or [-1], np.int32)

    def decode_step(params, state, lora):
        buf, lens, stop = state["buf"], state["lens"], state["stop"]
        active, cur, cache = state["active"], state["cur"], state["cache"]
        L = buf.shape[1]
        eos = jnp.asarray(eos_host)
        write_pos = jnp.clip(lens, 0, L - 1)
        buf = jnp.where(
            active[:, None] & (jnp.arange(L)[None, :] ==
                               write_pos[:, None]),
            cur[:, None], buf)
        logits, cache = forward_step(
            params, cur[:, None], cfg, cache, lens,
            lora=_resolve_lora(state, lora, pool),
            lora_scale=lora_scale)
        next_tok = jnp.argmax(logits[:, 0, :], axis=-1).astype(jnp.int32)
        now_eos = jnp.any(cur[:, None] == eos[None, :], axis=-1)
        new_lens = jnp.where(~active | (lens >= L), lens, lens + 1)
        new_active = active & ~now_eos & (new_lens < stop)
        out = {"buf": buf, "lens": new_lens, "stop": stop,
               "active": new_active, "cur": next_tok, "cache": cache}
        if pool:
            out["aslot"] = state["aslot"]
        return out
    return decode_step


def make_spec_decode_fn(cfg: ModelConfig, draft_cfg: ModelConfig,
                        eos_ids: Sequence[int], spec_k: int, *,
                        lora_scale: float = 1.0, pool: bool = False
                        ) -> Callable:
    """ONE fused speculative iteration (``spec_decode(params,
    draft_params, state, lora) -> state``): the draft proposes
    ``spec_k`` tokens via a scanned T=1 loop on its own cache, the
    target verifies all ``spec_k + 1`` positions in a single batched
    forward, and a vectorized acceptance rule commits the longest
    draft prefix the target agrees with (plus the target's one bonus
    token) — per slot, per iteration.

    Greedy-acceptance equivalence (drilled bitwise in tests): the
    committed stream is EXACTLY what the T=1 rule above would have
    produced, because a draft token is only consumed when it equals the
    target argmax given the identical committed prefix; the first
    disagreement is replaced by the target's own argmax and everything
    after it is discarded (the cache rows it wrote are overwritten by
    the next iteration's ``spec_k + 1``-wide scatter before any query
    can attend to them). The draft model only steers HOW MANY tokens
    commit per iteration — never WHICH.

    Bucket headroom contract: the engine routes speculative requests
    with ``max_new_tokens + spec_k`` (submit()), so every active slot
    satisfies ``stop + spec_k <= width`` and the verify window never
    clamps into committed history."""
    eos_host = np.asarray(list(eos_ids) or [-1], np.int32)
    K = int(spec_k)

    def spec_decode_step(params, draft_params, state, lora):
        buf, lens, stop = state["buf"], state["lens"], state["stop"]
        active, cur = state["active"], state["cur"]
        cache, dcache = state["cache"], state["dcache"]
        B, L = buf.shape
        eos = jnp.asarray(eos_host)

        # -- draft phase: K sequential single-token proposals ----------
        def draft_body(carry, _):
            dc, tok, pos = carry
            lg, dc = forward_step(draft_params, tok[:, None], draft_cfg,
                                  dc, pos)
            nxt = jnp.argmax(lg[:, 0, :], axis=-1).astype(jnp.int32)
            return (dc, nxt, pos + 1), tok
        (dcache, last, _), toks = jax.lax.scan(
            draft_body, (dcache, cur, lens), None, length=K)
        # tokens_in[:, 0] = the committed pending token; 1..K = drafts
        tokens_in = jnp.concatenate(
            [jnp.moveaxis(toks, 0, 1), last[:, None]], axis=1)  # [B, K+1]

        # -- verify: one batched target forward over all K+1 ----------
        logits, cache = forward_step(
            params, tokens_in, cfg, cache, lens,
            lora=_resolve_lora(state, lora, pool), lora_scale=lora_scale)
        tgt = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, K+1]

        # -- vectorized greedy acceptance ------------------------------
        match = (tokens_in[:, 1:] == tgt[:, :-1]).astype(jnp.int32)
        accepted = jnp.cumprod(match, axis=1).sum(axis=1)   # leading run
        consumed = accepted + 1                   # + the pending token
        # the sequential rule deactivates ON the first consumed eos —
        # nothing after it may commit
        is_eos = jnp.any(tokens_in[:, :, None] == eos[None, None, :],
                         axis=-1)
        has_eos = jnp.any(is_eos, axis=1)
        eos_cut = jnp.where(has_eos, jnp.argmax(is_eos, axis=1) + 1,
                            K + 1)
        m = jnp.minimum(jnp.minimum(consumed, eos_cut), stop - lens)
        m = jnp.where(active, jnp.maximum(m, 0), 0)         # [B]

        rel = jnp.arange(L, dtype=jnp.int32)[None, :] - lens[:, None]
        write = (rel >= 0) & (rel < m[:, None]) & active[:, None]
        vals = jnp.take_along_axis(tokens_in, jnp.clip(rel, 0, K), axis=1)
        buf = jnp.where(write, vals, buf)
        new_lens = lens + m
        consumed_eos = has_eos & (jnp.argmax(is_eos, axis=1) < m)
        new_active = active & ~consumed_eos & (new_lens < stop)
        # next pending token = the target's argmax after the last
        # committed token (the "bonus" token on full acceptance)
        nxt = jnp.take_along_axis(
            tgt, jnp.clip(m - 1, 0, K)[:, None], axis=1)[:, 0]
        new_cur = jnp.where(active & (m > 0), nxt, cur)
        out = {"buf": buf, "lens": new_lens, "stop": stop,
               "active": new_active, "cur": new_cur, "cache": cache,
               "dcache": dcache}
        if pool:
            out["aslot"] = state["aslot"]
        return out
    return spec_decode_step


def make_insert_fn(*, multi_lora: bool = False, spec: bool = False
                   ) -> Callable:
    """``insert_slot(state, slot, cache_row, prompt_row, prompt_len,
    stop, first_tok[, dcache_row][, aslot]) -> state`` — admit one
    prefilled request into slot ``slot`` (a traced scalar: one compile
    serves every slot). ``spec`` adds the draft cache row; ``multi_lora``
    adds the request's adapter slot index (both trailing, in that
    order)."""
    def insert_slot(state, slot, cache_row, prompt_row, prompt_len,
                    stop, first_tok, *extra):
        new_state = dict(state)
        new_state["cache"] = insert_cache_slot(state["cache"], slot,
                                               cache_row)
        new_state["buf"] = jax.lax.dynamic_update_slice_in_dim(
            state["buf"], prompt_row, slot, axis=0)
        new_state["lens"] = state["lens"].at[slot].set(prompt_len[0])
        new_state["stop"] = state["stop"].at[slot].set(stop[0])
        new_state["active"] = state["active"].at[slot].set(True)
        new_state["cur"] = state["cur"].at[slot].set(first_tok[0])
        i = 0
        if spec:
            new_state["dcache"] = insert_cache_slot(state["dcache"],
                                                    slot, extra[i])
            i += 1
        if multi_lora:
            new_state["aslot"] = state["aslot"].at[slot].set(extra[i][0])
        return new_state
    return insert_slot


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _Slot:
    rid: str
    prompt_len: int
    submit_t: float
    first_token_t: float
    # span bookkeeping (obs/trace.py): host perf_counter stamps the
    # admit path already took — the request's lifecycle spans are
    # emitted retroactively at retirement from these, so tracing adds
    # zero work to the decode loop
    prefill_t0: float = 0.0
    decodes0: int = 0
    adapter_id: Optional[str] = None


class _BucketRuntime:
    """Per-bucket state + slot bookkeeping (the host-side half; every
    device-side transition happens in the three compiled steps)."""

    def __init__(self, width: int, max_batch: int):
        self.width = width
        self.max_batch = max_batch
        self.state: Optional[Dict[str, Any]] = None   # device pytree
        self.slots: List[Optional[_Slot]] = [None] * max_batch
        self.host_active = np.zeros((max_batch,), bool)
        self.decodes = 0            # decode iterations run so far
        # last fetched per-slot lens — the speculative acceptance
        # ledger is pure host arithmetic on the control leaves the
        # step loop already fetches (no extra device traffic)
        self.prev_lens = np.zeros((max_batch,), np.int64)

    def free_slots(self) -> List[int]:
        return [i for i, s in enumerate(self.slots) if s is None]

    def occupied(self) -> int:
        return sum(s is not None for s in self.slots)


class BatchEngine:
    """The in-process continuous-batching engine (the CPU-mesh tests
    drive this directly; rayint/serving.py wraps it in a Ray actor).

    ``params`` may be a plain or quantized tree, optionally mesh-placed;
    ``plan.serve_quant`` quantizes at construction when asked. All
    executables build eagerly on first use of a bucket through
    ``compile_step_with_plan`` — with ``sidecar_dir`` set they
    AOT-serialize there, and a fresh engine pointed at the same dir
    deserializes instead of compiling (cold-start-in-seconds path).
    """

    def __init__(self, params: Any, cfg: ModelConfig, *,
                 plan: Optional[ExecutionPlan] = None,
                 eos_ids: Sequence[int] = (),
                 lora: Optional[Any] = None, lora_scale: float = 1.0,
                 adapters: Optional[Any] = None,
                 draft: Optional[Tuple[Any, ModelConfig]] = None,
                 sidecar_dir: Optional[str] = None,
                 heartbeat_fn: Optional[Callable[[int], None]] = None):
        from gke_ray_train_tpu.models.kvcache import require_decodable
        require_decodable(cfg)
        self.plan = plan if plan is not None else serve_plan()
        self.cfg = cfg
        self.params = quantize_for_serving(params, self.plan.serve_quant)
        if adapters is not None and lora is not None:
            raise ValueError(
                "pass either a single lora= adapter or a multi-tenant "
                "adapters= pool, not both")
        self.lora = lora
        self.pool = adapters
        self._pool_mode = adapters is not None
        self.eos_ids = tuple(int(e) for e in eos_ids)
        self.max_batch = self.plan.max_batch
        # speculative decoding: "self" drafts with the target's own
        # (already quantized) weights — the zero-infrastructure arm
        # whose accept-all behavior witnesses verify/decode equality;
        # "distilled" takes a caller-provided small model
        if self.plan.spec_draft == "self":
            self._draft: Optional[Tuple[Any, ModelConfig]] = (
                self.params, cfg)
        elif self.plan.spec_draft == "distilled":
            if draft is None:
                raise ValueError(
                    "SPEC_DRAFT=distilled needs draft=(draft_params, "
                    "draft_cfg) — train side produces small configs")
            dparams, dcfg = draft
            if dcfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    f"draft vocab {dcfg.vocab_size} != target vocab "
                    f"{cfg.vocab_size} — proposals must be target tokens")
            self._draft = (quantize_for_serving(
                dparams, self.plan.serve_quant), dcfg)
        else:
            self._draft = None
        self.spec_k = int(self.plan.spec_k) if self._draft else 0
        self.buckets = [b for b in self.plan.bucket_list()
                        if b <= cfg.max_seq_len
                        and (self._draft is None
                             or b <= self._draft[1].max_seq_len)]
        if not self.buckets:
            raise ValueError(
                f"no declared bucket {self.plan.bucket_list()} fits "
                f"max_seq_len={cfg.max_seq_len}")
        self.sidecar_dir = sidecar_dir
        self._heartbeat = heartbeat_fn
        dcfg = self._draft[1] if self._draft else None
        self._prefill_fn = make_prefill_fn(cfg, lora_scale=lora_scale,
                                           draft_cfg=dcfg,
                                           mesh=params_mesh(self.params))
        if self._draft is not None:
            self._decode_fn = make_spec_decode_fn(
                cfg, dcfg, self.eos_ids, self.plan.spec_k,
                lora_scale=lora_scale, pool=self._pool_mode)
        else:
            self._decode_fn = make_decode_fn(
                cfg, self.eos_ids, lora_scale=lora_scale,
                pool=self._pool_mode)
        self._insert_fn = make_insert_fn(multi_lora=self._pool_mode,
                                         spec=self._draft is not None)
        # host-side whole-prompt prefix/KV reuse (plan.prefix_cache):
        # (bucket, adapter_id, prompt-token hash) -> the prefill outputs
        # (first token + cache row(s)); bounded LRU. Insert does NOT
        # donate the row, so a memoized row serves any number of slots.
        from collections import OrderedDict
        self._prefix_memo: Any = OrderedDict()
        self.prefix_hits = 0
        self.spec_proposed = 0      # draft tokens offered to the target
        self.spec_accepted = 0      # draft tokens the target agreed with
        self._compiled: Dict[Tuple[str, int], Callable] = {}
        self._runtimes: Dict[int, _BucketRuntime] = {}
        self._pending: List[Request] = []
        self._pending_bucket: Dict[str, int] = {}
        self._completions: Dict[str, Completion] = {}
        self._submit_t: Dict[str, float] = {}
        self.iterations = 0
        self.refills = 0            # admissions into a non-fresh batch
        self.completed_total = 0    # process-lifetime completion count
        # rolling windows (one entry per decode iteration): a replica
        # serving for hours must not grow per iteration; p50/p99 and
        # occupancy reflect the most recent traffic
        from collections import deque
        self._token_latencies: Any = deque(maxlen=10_000)
        self._occupancy: Any = deque(maxlen=10_000)

    # -- executables ---------------------------------------------------

    def _sidecar(self, kind: str, width: int) -> Optional[str]:
        if not self.sidecar_dir:
            return None
        return os.path.join(self.sidecar_dir,
                            f"serve_{kind}_b{width}.bin")

    def _abstract_lora(self):
        """Abstract shape of the decode/prefill ``lora`` argument: the
        single adapter tree, the stacked pool blocks (multi-tenant), or
        None."""
        from gke_ray_train_tpu.perf.cache import abstractify
        if self._pool_mode:
            return abstractify(self.pool.blocks)
        return abstractify(self.lora) if self.lora is not None else None

    def _decode_lora_arg(self):
        """The concrete ``lora`` argument every decode call passes —
        re-read from the pool each call so admission-time tenant churn
        (register/evict) is visible without recompiling."""
        return self.pool.blocks if self._pool_mode else self.lora

    def _get(self, kind: str, width: int) -> Callable:
        key = (kind, width)
        fn = self._compiled.get(key)
        if fn is not None:
            return fn
        from gke_ray_train_tpu.perf.cache import abstractify
        aparams = abstractify(self.params)
        alora = self._abstract_lora()
        spec = self._draft is not None
        adraft = abstractify(self._draft[0]) if spec else None
        dcfg = self._draft[1] if spec else None
        astate = jax.eval_shape(
            partial(init_serve_state, self.cfg, self.max_batch, width,
                    multi_lora=self._pool_mode, draft_cfg=dcfg))
        if kind == "decode":
            args = (aparams, adraft, astate, alora) if spec else \
                (aparams, astate, alora)
            fn = compile_step_with_plan(
                self.plan, None, self._decode_fn, *args,
                donate_argnums=(2,) if spec else (1,),
                sidecar=self._sidecar(kind, width),
                label=f"serve_decode_b{width}",
                surface="serve")
        elif kind == "prefill":
            aprompt = jax.ShapeDtypeStruct((1, width), jnp.int32)
            alen = jax.ShapeDtypeStruct((1,), jnp.int32)
            aplora = alora
            if self._pool_mode:
                # prefill runs at batch 1: its aslot is a length-1 vec
                aplora = {"aslot": jax.ShapeDtypeStruct((1,), jnp.int32),
                          "blocks": alora}
            args = (aparams, adraft, aprompt, alen, aplora) if spec \
                else (aparams, aprompt, alen, aplora)
            fn = compile_step_with_plan(
                self.plan, None, self._prefill_fn, *args,
                donate_argnums=(), sidecar=self._sidecar(kind, width),
                label=f"serve_prefill_b{width}",
                surface="serve")
        else:  # insert
            row_cache = jax.eval_shape(
                partial(init_cache, self.cfg, 1, width))
            scalars = jax.ShapeDtypeStruct((1,), jnp.int32)
            extra = []
            if spec:
                extra.append(jax.eval_shape(
                    partial(init_cache, dcfg, 1, width)))
            if self._pool_mode:
                extra.append(scalars)
            fn = compile_step_with_plan(
                self.plan, None, self._insert_fn,
                astate, jax.ShapeDtypeStruct((), jnp.int32), row_cache,
                jax.ShapeDtypeStruct((1, width), jnp.int32),
                scalars, scalars, scalars, *extra,
                # the batch-1 cache row is NOT donated: its [1, L] rows
                # cannot alias into the pooled [B, L] buffer, and jax
                # warns on every unusable donation (this is also what
                # lets the prefix cache reuse a memoized row)
                donate_argnums=(0,), sidecar=self._sidecar(kind, width),
                label=f"serve_insert_b{width}",
                surface="serve")
        self._compiled[key] = fn
        return fn

    def set_heartbeat(self, fn: Optional[Callable[[int], None]]) -> None:
        """(Re)wire the per-iteration liveness beat — the deployment
        (rayint/serving.py) points this at a Supervisor actor after the
        engine is built, so a replica wedged mid-decode is detected by
        the same board shape that watches training ranks."""
        self._heartbeat = fn

    def executable_info(self) -> Dict[str, Dict[str, Any]]:
        """Build provenance per compiled executable ("deserialized" |
        "compiled" | absent for plain-jit) — the warm-start tests'
        witness that a fresh replica recompiled nothing."""
        return {f"{k}_b{w}": dict(getattr(fn, "info", {}))
                for (k, w), fn in self._compiled.items()}

    def decode_cost_report(self, width: Optional[int] = None):
        """StepCostReport of the decode executable (perf/costs.py) —
        None when the executable cannot be introspected (plain jit or a
        deserialized blob without analyses)."""
        from gke_ray_train_tpu.perf.costs import step_cost_report
        width = width or self.buckets[0]
        fn = self._get("decode", width)
        compiled = getattr(fn, "_compiled", None)
        if compiled is None:
            return None
        try:
            return step_cost_report(compiled,
                                    tokens_per_step=self.max_batch)
        except Exception as e:  # noqa: BLE001 - introspection best-effort
            logger.debug("decode cost report unavailable: %s", e)
            return None

    def warm_up(self, widths: Optional[Sequence[int]] = None) -> None:
        """Build (or deserialize) every executable for the given buckets
        up front — the replica cold-start path, so the first request
        pays dispatch latency, not compile latency."""
        for w in widths or self.buckets:
            for kind in ("prefill", "decode", "insert"):
                self._get(kind, w)
        # obs: record the cold-start provenance (deserialized vs
        # compiled, per executable) on the run's event stream
        from gke_ray_train_tpu.obs import runtime as obs_runtime
        obs_runtime.emit("serve_start",
                         executables=self.executable_info())

    # -- request intake ------------------------------------------------

    def submit(self, request: Request) -> int:
        """Queue a request; returns the bucket it will run in. Raises
        ValueError when no declared bucket fits (reject up front — a
        fixed-shape executable must never truncate silently)."""
        # every per-request structure is keyed by rid alone: a
        # duplicate (e.g. a client retry racing its original) would
        # overwrite the first request's routing and double-pop its
        # completion — reject it while the first is still in flight
        # (_pending_bucket spans submit→retire) or unretrieved
        if request.rid in self._pending_bucket \
                or request.rid in self._completions:
            raise ValueError(f"request {request.rid}: rid already in "
                             "flight or unretrieved — rids must be "
                             "unique per engine")
        ids = np.asarray(request.token_ids, np.int32).reshape(-1)
        if ids.size == 0:
            raise ValueError(f"request {request.rid}: empty prompt")
        if request.max_new_tokens < 1:
            raise ValueError(f"request {request.rid}: max_new_tokens="
                             f"{request.max_new_tokens} must be >= 1")
        if request.adapter_id is not None and not self._pool_mode:
            raise ValueError(
                f"request {request.rid}: adapter_id="
                f"{request.adapter_id!r} but the engine has no adapter "
                "pool — construct BatchEngine(adapters=AdapterPool(...))")
        # speculative headroom: the verify window writes spec_k + 1
        # cache positions from ``lens``, so the bucket must hold
        # stop + spec_k — route (and reject/truncate) as if the request
        # asked for max_new_tokens + spec_k
        budget = request.max_new_tokens + self.spec_k
        # reject BEFORE truncating: even a 1-token prompt cannot fit —
        # truncate_prompt would log a misleading head-DROPPED warning
        # for a request that is rejected anyway
        if budget + 1 > self.buckets[-1]:
            raise ValueError(
                f"request {request.rid}: max_new_tokens="
                f"{request.max_new_tokens}"
                + (f" + spec_k={self.spec_k}" if self.spec_k else "")
                + f" + a 1-token prompt needs {budget + 1} slots but "
                f"the largest usable bucket is {self.buckets[-1]} — "
                "lower max_new_tokens or declare a larger bucket")
        max_prompt = max(self.buckets[-1] - budget, 1)
        ids = truncate_prompt(ids, max_prompt,
                              label=f"request {request.rid} prompt")
        bucket = pick_bucket(len(ids), budget,
                             self.buckets, self.cfg.max_seq_len)
        # obs: the admitted request's total length (post-truncation
        # prompt + decode budget) into the shared metrics registry —
        # the workload-shape histogram bucket declarations are tuned
        # against. No-op when obs is off.
        from gke_ray_train_tpu.obs import runtime as obs_runtime
        if obs_runtime.active() is not None:
            try:
                obs_runtime.registry().histogram("request_len").observe(
                    float(len(ids) + request.max_new_tokens))
            except Exception:  # noqa: BLE001 - telemetry must not reject
                pass
        request = dataclasses.replace(request, token_ids=ids)
        self._pending.append(request)
        self._pending_bucket[request.rid] = bucket
        self._submit_t[request.rid] = time.perf_counter()
        return bucket

    # -- the iteration loop --------------------------------------------

    def _admit(self) -> None:
        """Slot-level admission: fill every free slot whose bucket has a
        pending request — prefill at batch 1, insert into the pool."""
        still_pending: List[Request] = []
        for req in self._pending:
            width = self._pending_bucket[req.rid]
            rt = self._runtimes.get(width)
            if rt is None:
                rt = self._runtimes[width] = _BucketRuntime(
                    width, self.max_batch)
            free = rt.free_slots()
            if not free:
                still_pending.append(req)
                continue
            slot = free[0]
            if self._pool_mode:
                # resolve (and pin) the tenant BEFORE any state work —
                # a pool with every slot pinned is a transient
                # condition (requests retire), not an error: keep the
                # request queued and retry next iteration
                from gke_ray_train_tpu.serve.adapters import (
                    AdapterPoolPinned)
                try:
                    aslot_idx = self.pool.acquire(req.adapter_id)
                except AdapterPoolPinned:
                    still_pending.append(req)
                    continue
            if rt.state is None:
                rt.state = init_serve_state(
                    self.cfg, self.max_batch, width,
                    multi_lora=self._pool_mode,
                    draft_cfg=self._draft[1] if self._draft else None)
            elif rt.occupied() > 0 and rt.decodes > 0:
                # a TRUE mid-batch refill: decode already ran for this
                # batch and other sequences are live (the initial
                # fill-up wave before the first decode is not a refill)
                self.refills += 1
            buf, plen = form_prompt_buffer(req.token_ids, width)
            stop = min(plen + req.max_new_tokens, width)
            t_prefill0 = time.perf_counter()
            out = self._prefill_outputs(req, width, buf, plen)
            first = out[0]
            # the first decoded token exists only once prefill
            # materializes — on an async backend stamping at dispatch
            # would measure enqueue latency, not time-to-first-token
            jax.block_until_ready(first)
            extra = list(out[2:])   # dcache_row when speculative
            if self._pool_mode:
                extra.append(jnp.asarray([aslot_idx], jnp.int32))
            rt.state = self._get("insert", width)(
                rt.state, jnp.asarray(slot, jnp.int32), out[1],
                jnp.asarray(buf), jnp.asarray([plen], jnp.int32),
                jnp.asarray([stop], jnp.int32), first, *extra)
            now = time.perf_counter()
            rt.slots[slot] = _Slot(req.rid, plen,
                                   self._submit_t[req.rid], now,
                                   prefill_t0=t_prefill0,
                                   decodes0=rt.decodes,
                                   adapter_id=req.adapter_id)
            rt.host_active[slot] = True
            rt.prev_lens[slot] = plen
        self._pending = still_pending

    _PREFIX_MEMO_MAX = 64

    def _prefill_outputs(self, req: Request, width: int,
                         buf: np.ndarray, plen: int) -> tuple:
        """Run (or reuse) the batch-1 prefill for one admission:
        ``(first_tok, cache_row[, dcache_row])``.

        Prefix/KV reuse (plan.prefix_cache) memoizes WHOLE post-
        truncation prompts by token hash, per (bucket, tenant): the
        common shared-system-prompt traffic pattern re-admits the same
        prefix verbatim, and replaying the memoized cache row through
        the (non-donating) insert executable is bitwise the cold
        prefill by construction — the same buffers go in. Partial-
        prefix splicing is deliberately out of scope: reusing a strict
        prefix would change the prefill width and break the bitwise
        contract."""
        key = None
        if self.plan.prefix_cache:
            import hashlib
            digest = hashlib.sha1(
                np.ascontiguousarray(buf).tobytes()).hexdigest()
            # plen rides in the key: a prompt that genuinely ends in
            # token id 0 pads to the same buffer as a shorter one
            key = (width, req.adapter_id or "", int(plen), digest)
            hit = self._prefix_memo.get(key)
            if hit is not None:
                self._prefix_memo.move_to_end(key)
                self.prefix_hits += 1
                return hit
        lora_arg = self.lora
        if self._pool_mode:
            lora_arg = {
                "aslot": jnp.asarray(
                    [self.pool.slot_of(req.adapter_id)], jnp.int32),
                "blocks": self.pool.blocks}
        args = (self.params, jnp.asarray(buf),
                jnp.asarray([plen], jnp.int32), lora_arg)
        if self._draft is not None:
            args = (args[0], self._draft[0]) + args[1:]
        out = self._get("prefill", width)(*args)
        if key is not None:
            self._prefix_memo[key] = out
            while len(self._prefix_memo) > self._PREFIX_MEMO_MAX:
                self._prefix_memo.popitem(last=False)
        return out

    def _collect(self, rt: _BucketRuntime, active: np.ndarray,
                 lens: np.ndarray, buf: Optional[np.ndarray]) -> None:
        """Retire slots that went inactive this iteration."""
        now = time.perf_counter()
        for i, slot in enumerate(rt.slots):
            if slot is None or active[i]:
                continue
            # np.array COPIES: device_get can return a zero-copy view of
            # the device buffer (CPU backend), and the state is DONATED —
            # without the copy, a later admit/decode reuses that buffer
            # and the retired completion's tokens mutate under it
            row = np.array(buf[i])
            length = int(lens[i])
            gen = row[slot.prompt_len:length]
            reason = ("eos" if self.eos_ids and len(gen)
                      and int(gen[-1]) in self.eos_ids else "length")
            self._completions[slot.rid] = Completion(
                rid=slot.rid, tokens=row, prompt_len=slot.prompt_len,
                length=length, bucket=rt.width, finish_reason=reason,
                submit_s=slot.submit_t,
                first_token_s=slot.first_token_t - slot.submit_t,
                done_s=now - slot.submit_t,
                adapter_id=slot.adapter_id)
            self._trace_request(rt, slot, now, length, reason)
            if self._pool_mode:
                # unpin the tenant — its slot becomes evictable once no
                # in-flight request decodes against it
                self.pool.release(slot.adapter_id)
            rt.slots[i] = None
            rt.host_active[i] = False
            self.completed_total += 1
            # pre-completion bookkeeping dies with the request — a
            # long-lived replica must not grow per served request
            self._submit_t.pop(slot.rid, None)
            self._pending_bucket.pop(slot.rid, None)

    def _trace_request(self, rt: _BucketRuntime, slot: _Slot,
                       now: float, length: int, reason: str) -> None:
        """Emit the request's lifecycle spans (obs/trace.py) at
        retirement — the "where did my p99 go" decomposition: enqueue
        (submit → prefill dispatch), prefill (dispatch → first token
        materialized), decode (admission → retire, with the iteration
        count it shared with the continuous batch). Everything here is
        host floats the engine already stamped; emission is once per
        COMPLETED request, never per decode iteration, so the one-
        ``device_get``-per-iteration hot-path contract holds. No-op
        when obs/tracing is off."""
        from gke_ray_train_tpu.obs import runtime as obs_runtime
        if not obs_runtime.tracing():
            return
        anchor = time.time()      # map perf_counter diffs to wall ts

        def t1_of(pc: float) -> float:
            return anchor - (now - pc)

        req_id = obs_runtime.span_add(
            "serve_request", now - slot.submit_t, t1=anchor,
            rid=slot.rid, bucket=rt.width, prompt_len=slot.prompt_len,
            generated=int(length - slot.prompt_len),
            finish_reason=reason)
        if req_id is None:
            # the parent write failed (IO): children with parent=None
            # would re-parent under the attempt span and read as
            # attempt-level path leaves — a lossy trace must stay
            # consistent, so drop the orphans with their parent
            return
        obs_runtime.span_add(
            "serve_enqueue", slot.prefill_t0 - slot.submit_t,
            t1=t1_of(slot.prefill_t0), parent_id=req_id, rid=slot.rid)
        obs_runtime.span_add(
            "serve_prefill", slot.first_token_t - slot.prefill_t0,
            t1=t1_of(slot.first_token_t), parent_id=req_id,
            rid=slot.rid)
        obs_runtime.span_add(
            "serve_decode", now - slot.first_token_t, t1=anchor,
            parent_id=req_id, rid=slot.rid,
            iterations=int(rt.decodes - slot.decodes0))

    def step(self) -> int:
        """One engine iteration: admit into free slots, then run ONE
        decode step per live bucket. Returns the number of slots still
        active across buckets (0 = drained)."""
        self._admit()
        total_active = 0
        for rt in self._runtimes.values():
            if rt.occupied() == 0:
                continue
            t0 = time.perf_counter()
            fn = self._get("decode", rt.width)
            if self._draft is not None:
                rt.state = fn(self.params, self._draft[0], rt.state,
                              self._decode_lora_arg())
            else:
                rt.state = fn(self.params, rt.state,
                              self._decode_lora_arg())
            rt.decodes += 1
            # ONE batched fetch of the small control leaves per
            # iteration (shardlint TPU001: never per-slot round-trips);
            # buf rides along only when a slot may have finished
            active, lens = jax.device_get(
                (rt.state["active"], rt.state["lens"]))
            dt = time.perf_counter() - t0
            n_act = int(np.sum(rt.host_active))
            if self.spec_k:
                # acceptance ledger from the lens deltas the fetch
                # above already paid for: each previously-active slot
                # was offered spec_k drafts and committed (delta - 1)
                # of them (the +1 being the target's own bonus token)
                was = rt.host_active
                deltas = np.asarray(lens, np.int64)[was] \
                    - rt.prev_lens[was]
                self.spec_proposed += self.spec_k * n_act
                self.spec_accepted += int(
                    np.clip(deltas - 1, 0, self.spec_k).sum())
            rt.prev_lens = np.asarray(lens, np.int64).copy()
            self._token_latencies.append(dt)
            self._occupancy.append(n_act / self.max_batch)
            total_active += int(np.sum(active))
            if bool(np.any(rt.host_active & ~active)):
                buf = jax.device_get(rt.state["buf"])
                self._collect(rt, active, lens, buf)
        self.iterations += 1
        if self._heartbeat is not None:
            try:
                self._heartbeat(self.iterations)
            except Exception as e:  # noqa: BLE001 - liveness best-effort
                logger.debug("serve heartbeat dropped: %s", e)
        return total_active + len(self._pending)

    def run_until_drained(self, requests: Sequence[Request] = ()
                          ) -> List[Completion]:
        """Submit ``requests`` and iterate until every queued request
        completed; returns completions in submit order. Returned
        completions are RELEASED from the engine (a long-lived replica
        calls this per request batch and must not accumulate every
        buffer it ever served) — use :meth:`completion` + manual
        :meth:`step` when you need them retained."""
        for r in requests:
            self.submit(r)
        want = [r.rid for r in requests]
        while self.step() > 0:
            pass
        # obs: serving latency/occupancy into the shared metrics
        # registry + one `serve_drained` event (off the decode loop —
        # once per drain, never per iteration; no-op when obs is off)
        from gke_ray_train_tpu.obs import runtime as obs_runtime
        if obs_runtime.active() is not None:
            obs_runtime.active().note_serve(self.stats())
        if want:
            return [self._completions.pop(rid) for rid in want]
        out = list(self._completions.values())
        self._completions.clear()
        return out

    def completion(self, rid: str) -> Optional[Completion]:
        return self._completions.get(rid)

    # -- reporting -----------------------------------------------------

    def stats(self) -> Dict[str, Any]:
        """Serving statistics: iteration count, batch occupancy and the
        per-token latency distribution (one decode iteration produces
        one token per active slot, so the iteration latency IS the
        per-token latency)."""
        lat = sorted(self._token_latencies)

        def pct(p):
            if not lat:
                return 0.0
            return lat[min(int(p / 100.0 * len(lat)), len(lat) - 1)]

        out = {
            "iterations": self.iterations,
            "refills": self.refills,
            "completed": self.completed_total,
            "pending": len(self._pending),
            "batch_occupancy": (float(np.mean(self._occupancy))
                                if self._occupancy else 0.0),
            "p50_token_latency_s": pct(50),
            "p99_token_latency_s": pct(99),
            "plan_fingerprint": self.plan.fingerprint(),
        }
        # multi-tenant / reuse / speculation telemetry, present exactly
        # when the feature is on (obs export_serve_stats maps what it
        # finds; absent keys stay out of the metrics registry)
        if self.plan.prefix_cache:
            out["prefix_hits"] = self.prefix_hits
        if self.spec_k:
            out["spec_proposed"] = self.spec_proposed
            out["spec_accepted"] = self.spec_accepted
        if self._pool_mode:
            out.update(self.pool.stats())
        return out


def post_train_smoke(params: Any, cfg: ModelConfig,
                     plan: ExecutionPlan,
                     prompt_ids: Sequence[np.ndarray], *,
                     eos_ids: Sequence[int] = (),
                     lora: Optional[Any] = None, lora_scale: float = 1.0,
                     adapter_ids: Optional[Sequence[Optional[str]]] = None,
                     max_new_tokens: int = 32
                     ) -> Optional[Tuple[List[Completion], Dict[str, Any]]]:
    """The ``SERVE_AFTER_TRAIN`` hook both ray-jobs entries call after
    training: run the given already-tokenized prompts through a fresh
    continuous-batching engine on the just-trained weights (train →
    serve on the same process, ROADMAP #2's loop closed end to end).
    Returns (completions, stats), or None — with a loud warning — when
    no declared bucket fits the model or no prompt is usable. A failure
    inside the engine raises: a job whose serving path is broken must
    not exit 0."""
    usable = [b for b in plan.bucket_list() if b <= cfg.max_seq_len]
    if not usable:
        logger.warning(
            "SERVE_AFTER_TRAIN skipped: no declared bucket %s fits "
            "max_seq_len=%d (set DECODE_BUCKETS)", plan.bucket_list(),
            cfg.max_seq_len)
        return None
    prompts = [np.asarray(p, np.int32).reshape(-1) for p in prompt_ids]
    prompts = [p for p in prompts if p.size]
    if not prompts:
        logger.warning("SERVE_AFTER_TRAIN skipped: no non-empty prompts")
        return None
    # one-shot in-process smoke: AOT off — the executables would build
    # from abstract UNSHARDED args while the just-trained params are
    # mesh-placed, so every AOT call would be rejected into the jit
    # fallback anyway (a wasted build + a noisy guard log per bucket)
    plan = dataclasses.replace(plan, aot_train_step=False)
    # the budget must fit the declared buckets (a tight DECODE_BUCKETS
    # would otherwise reject every request at submit) — a smoke clamps
    # rather than crash
    max_new_tokens = min(max_new_tokens, max(usable[-1] - 1, 1))
    # adapter_id-tagged smoke (ISSUE 17): when the run trained LoRA and
    # the caller tags requests, serve through a real AdapterPool so the
    # batched multi-tenant path is what the smoke exercises end to end
    # — every unique id maps to the just-trained adapter tree
    tags: List[Optional[str]] = list(adapter_ids or [])
    tags += [None] * (len(prompts) - len(tags))
    lora_kw: Dict[str, Any] = {"lora": lora, "lora_scale": lora_scale}
    if lora is not None and any(t is not None for t in tags):
        from gke_ray_train_tpu.serve.adapters import AdapterPool
        tenants = sorted({t for t in tags if t})
        # sized to the tenants this smoke registers, not to
        # plan.max_adapters: the trained state is still resident, and
        # 8 spare slots of an r=64 Llama-3.1-8B adapter are 5 GB
        pool = AdapterPool.from_template(lora, max_adapters=len(tenants))
        for aid in tenants:
            pool.register(aid, lora)
        lora_kw = {"adapters": pool, "lora_scale": lora_scale}
    elif lora is None:
        tags = [None] * len(prompts)
    t0 = time.perf_counter()
    engine = BatchEngine(params, cfg, plan=plan, eos_ids=eos_ids,
                         **lora_kw)
    comps = engine.run_until_drained([
        Request(rid=f"smoke{i}", token_ids=p,
                max_new_tokens=max_new_tokens, adapter_id=tags[i])
        for i, p in enumerate(prompts)])
    stats = engine.stats()
    stats["wall_s"] = round(time.perf_counter() - t0, 3)
    stats["generated_tokens"] = int(
        sum(c.length - c.prompt_len for c in comps))
    stats["adapter_requests"] = sum(1 for t in tags if t is not None)
    logger.info(
        "SERVE_AFTER_TRAIN: %d request(s) -> %d tokens in %.2fs "
        "(occupancy %.2f, p50 %.1fms/token, plan %s)",
        len(comps), stats["generated_tokens"], stats["wall_s"],
        stats["batch_occupancy"], stats["p50_token_latency_s"] * 1e3,
        stats["plan_fingerprint"])
    return comps, stats

"""Side-by-side base-vs-tuned inference comparison (SURVEY.md §3.4).

Capability parity with run_inference_comparison
(ray-jobs/fine_tune_llama_ray.py:22-194): post-training; filter test
rows, greedy-generate from both the original and the fine-tuned weights
with a shared prompt template, print and accumulate side-by-side
results, JSON-dump to shared storage. TPU redesign: both models generate
through one jitted KV-cached prefill+step loop (models/kvcache.py;
models/decode.py is the full-forward oracle it is tested against),
prompts bucketed to 128-multiples so similar lengths share a compile; no
device cache juggling (the reference's del model +
torch.cuda.empty_cache() dance at :191-194 has no XLA equivalent — arrays
free when references drop).

Multi-host semantics (the one place this deliberately diverges from the
reference's rank-0-only harness, :22-194): the reference can generate on
rank 0 alone because DDP replicates weights; here the weights are
mesh-sharded global arrays, so EVERY host must enter the generate —
running it on host 0 only would diverge the SPMD program and deadlock.
``is_host0`` therefore gates only printing and file IO, exactly like
train/loop.py. Pass ``mesh`` whenever params are sharded over one: the
prompt buffers are formed up as globally-replicated arrays (every host
feeds identical bytes — callers must pass identical ``test_rows``, which
holds for the seeded downsample/synthetic paths) and the generated
buffer is read back from an addressable replica shard.
"""

from __future__ import annotations

import json
import logging
import os
from collections import OrderedDict
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from gke_ray_train_tpu.data.sft import format_gretel_sql_example, render_chat
from gke_ray_train_tpu.models.config import ModelConfig
from gke_ray_train_tpu.models.kvcache import (
    greedy_generate_cached, require_decodable)
from gke_ray_train_tpu.models.transformer import Params
from gke_ray_train_tpu.serve.bucketing import (
    form_prompt_buffer, prompt_bucket, truncate_prompt)

logger = logging.getLogger(__name__)

# jitted replicated-generate executables keyed on (mesh identity, cfg,
# decode shape). NOT an lru_cache: every entry closes over a
# NamedSharding that pins its Mesh — and through it the device buffers
# of every array the jit ever touched — so an unbounded/function-scoped
# cache kept torn-down meshes alive for the life of the process. The
# id(mesh) key is stable exactly because the entry pins the mesh (no id
# reuse while the entry lives); eviction and clear_generate_cache()
# are what release it.
_GENERATE_CACHE: "OrderedDict[Tuple, Callable]" = OrderedDict()
_GENERATE_CACHE_MAX = 32


def clear_generate_cache() -> int:
    """Drop every cached replicated-generate executable — call on mesh
    teardown (``rayint/trainer.py`` does, after every worker attempt):
    the cache is the only thing keeping a dead mesh's device buffers
    live. Returns the number of entries dropped."""
    n = len(_GENERATE_CACHE)
    _GENERATE_CACHE.clear()
    return n


def _replicated_generate(mesh: Mesh, cfg: ModelConfig,
                         max_new_tokens: int, eos_ids: Tuple[int, ...],
                         lora_scale: float):
    """One jitted generate per (mesh, cfg, decode-shape) with the output
    pinned to a replicated sharding, so every host can read its full
    value from any addressable shard. The inner call traces through the
    already-jitted greedy_generate_cached."""
    key = (id(mesh), cfg, max_new_tokens, eos_ids, lora_scale)
    fn = _GENERATE_CACHE.get(key)
    if fn is not None:
        _GENERATE_CACHE.move_to_end(key)
        return fn
    out_sharding = NamedSharding(mesh, P())

    def f(params, prompt, prompt_len, lora):
        return greedy_generate_cached(
            params, prompt, prompt_len, cfg,
            max_new_tokens=max_new_tokens, eos_ids=eos_ids,
            lora=lora, lora_scale=lora_scale)
    fn = jax.jit(f, out_shardings=out_sharding)
    _GENERATE_CACHE[key] = fn
    while len(_GENERATE_CACHE) > _GENERATE_CACHE_MAX:
        _GENERATE_CACHE.popitem(last=False)
    return fn


def _place_replicated(mesh: Mesh, arr: np.ndarray) -> jax.Array:
    """Host-local numpy (identical on every host) → globally-replicated
    jax.Array over the mesh (the form-up greedy decode needs once params
    are sharded; single-host this is a plain device_put)."""
    return jax.make_array_from_process_local_data(
        NamedSharding(mesh, P()), arr, arr.shape)


def generate_answer(params: Params, cfg: ModelConfig, tokenizer,
                    prompt_text: str, *, max_new_tokens: int = 300,
                    lora: Optional[Params] = None,
                    lora_scale: float = 1.0,
                    mesh: Optional[Mesh] = None) -> str:
    require_decodable(cfg)
    ids = np.asarray(
        tokenizer(prompt_text, add_special_tokens=False)["input_ids"],
        np.int32)
    # bucketed fixed-size buffer: prompt region rounded up to a 128
    # multiple + generation room — compiles once per bucket, not per
    # prompt length. Bucketing/truncation/form-up are shared with the
    # serving engine (serve/bucketing.py) so the two paths cannot drift;
    # an over-long prompt is truncated LOUDLY (the head is dropped).
    max_prompt = max(cfg.max_seq_len - max_new_tokens, 1)
    ids = truncate_prompt(ids, max_prompt, label="generate_answer prompt")
    # buffer width rounded to a 128 multiple: the KV-cache flash prefill
    # gates on the CACHE width tiling too (models/kvcache.py) — an
    # unaligned width would silently fall back to the dense
    # O(T*max_len) prefill at exactly the long-prompt sizes where it
    # hurts. One bucket call keeps compile-sharing per length class.
    L = min(prompt_bucket(len(ids) + max_new_tokens), cfg.max_seq_len)
    buf, _ = form_prompt_buffer(ids, L)
    eos_ids = []
    if getattr(tokenizer, "eos_token_id", None) is not None:
        eos_ids.append(int(tokenizer.eos_token_id))
    if mesh is not None:
        gen_fn = _replicated_generate(mesh, cfg, max_new_tokens,
                                      tuple(eos_ids), lora_scale)
        out = gen_fn(params, _place_replicated(mesh, buf),
                     _place_replicated(
                         mesh, np.asarray([len(ids)], np.int32)),
                     lora)
        # replicated sharding: any addressable shard IS the full array
        # (np.asarray on the global array would require every device to
        # be addressable, which fails under multi-process)
        out = np.asarray(out.addressable_data(0)[0])
    else:
        out = greedy_generate_cached(
            params, jnp.asarray(buf), jnp.asarray([len(ids)], jnp.int32),
            cfg, max_new_tokens=max_new_tokens, eos_ids=tuple(eos_ids),
            lora=lora, lora_scale=lora_scale)
        out = np.asarray(out[0])
    gen = out[len(ids):]
    # trim at the first EOS; otherwise strip only TRAILING zeros (the
    # unwritten buffer tail). Filtering every zero would also delete a
    # legitimately generated token id 0 (e.g. "!" in Llama-3's vocab)
    # from the middle of the answer.
    stops = np.where(np.isin(gen, eos_ids))[0] if eos_ids else []
    if len(stops):
        gen = gen[: stops[0]]
    else:
        nz = np.nonzero(gen)[0]
        gen = gen[: nz[-1] + 1] if len(nz) else gen[:0]
    return tokenizer.decode(gen)


def run_inference_comparison(
        base_params: Params, tuned_params: Params, cfg: ModelConfig,
        tokenizer, test_rows: List[Dict], *,
        num_samples: int = 2, max_new_tokens: int = 300,
        output_path: Optional[str] = None,
        row_filter: Optional[Callable[[Dict], bool]] = None,
        format_example: Callable = format_gretel_sql_example,
        mesh: Optional[Mesh] = None,
        is_host0: bool = True,
        tuned_lora: Optional[Params] = None,
        lora_scale: float = 1.0) -> List[Dict]:
    """Returns the accumulated comparison records; writes JSON when
    ``output_path`` is given (reference behavior: filter on
    sql_complexity == 'window functions', :87-96; JSON dump :182-187).

    COLLECTIVE once ``mesh`` is given and params are sharded: every host
    must call this with identical ``test_rows`` (see module docstring);
    ``is_host0`` gates only the log lines and the JSON write.

    ``tuned_lora``: when given, the tuned model is ``tuned_params`` +
    adapters applied at decode time — (Q)LoRA runs never materialize a
    merged tree on device (an 8B NF4 base dequantized to a merged copy
    does not fit one 16 GB chip).
    """
    if row_filter is not None:
        test_rows = [r for r in test_rows if row_filter(r)]
    test_rows = test_rows[:num_samples]
    results = []
    for i, row in enumerate(test_rows):
        msgs = format_example(row)
        prompt = render_chat(tokenizer, msgs, add_generation_prompt=True)
        record = {
            "index": i,
            "question": msgs["user"],
            "reference_answer": msgs["assistant"],
            "base_model_answer": generate_answer(
                base_params, cfg, tokenizer, prompt,
                max_new_tokens=max_new_tokens, mesh=mesh),
            "finetuned_model_answer": generate_answer(
                tuned_params, cfg, tokenizer, prompt,
                max_new_tokens=max_new_tokens, mesh=mesh,
                lora=tuned_lora, lora_scale=lora_scale),
        }
        if is_host0:
            logger.info("sample %d\n  Q: %s\n  base: %s\n  tuned: %s", i,
                        record["question"], record["base_model_answer"],
                        record["finetuned_model_answer"])
        results.append(record)
    if output_path and is_host0:
        os.makedirs(os.path.dirname(output_path) or ".", exist_ok=True)
        with open(output_path, "w") as f:
            json.dump(results, f, indent=2)
        logger.info("wrote %d comparison records to %s", len(results),
                    output_path)
    return results

"""HF-format weight interop (safetensors, torch-free).

Replaces ``AutoModelForCausalLM.from_pretrained``
(ray-jobs/fine_tune_llama_ray.py:240) for loading pretrained Llama /
Mistral / Gemma-2 weights into the sharded pytree, and ``save_pretrained``
(:354-355, :373-374) for exporting — final artifacts stay in HF
safetensors layout for ecosystem parity (SURVEY.md §5.4).

Implementation notes:
- ``safetensors.safe_open`` streams one tensor at a time (never the whole
  model) and each tensor is ``device_put`` straight into its target
  sharding — hosts keep at most one full tensor in RAM (SURVEY.md §7
  "hard parts" #1).
- torch Linear stores W as [out, in]; our layout is [in, out] → transpose
  on both directions. Embeddings and norm scales copy as-is. HF Gemma-2
  RMSNorm uses the same (1 + w) convention as ``norm_scale_plus_one``.
"""

from __future__ import annotations

import json
import os
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from gke_ray_train_tpu.models.config import ModelConfig
from gke_ray_train_tpu.models.transformer import (
    Params, init_params, param_specs)
from gke_ray_train_tpu.parallel.sharding import tree_shardings


def require_name_map(cfg: ModelConfig) -> None:
    """The tensor names below are those of the families whose
    checkpoints' index files they were read from. A model with
    state-space layers (``granitemoehybrid``) has none here yet: its
    mixer's tensors (``mamba.in_proj``, ``conv1d``, ``A_log`` ...) and
    its experts' fused layout want a checkpoint's index file to be
    written from, not a guess; refuse it by name instead."""
    if "ssm" in cfg.block_pattern:
        raise NotImplementedError(
            f"{cfg.name}: no name map for a model with state-space "
            "layers (ckpt/hf_io.py, ckpt/convert.py): its checkpoint's "
            "tensor names are not in this repository; train it from "
            "seeded weights, or add the map from the checkpoint's "
            "model.safetensors.index.json")


def _hf_layer_names(cfg: ModelConfig, i: int) -> Dict[str, str]:
    """our-key → HF tensor name for decoder layer i (per-layer tensors;
    MoE expert banks are per-(layer, expert), see _hf_expert_names)."""
    base = f"model.layers.{i}"
    names = {
        "wq": f"{base}.self_attn.q_proj.weight",
        "wk": f"{base}.self_attn.k_proj.weight",
        "wv": f"{base}.self_attn.v_proj.weight",
        "wo": f"{base}.self_attn.o_proj.weight",
        "attn_norm": f"{base}.input_layernorm.weight",
    }
    if cfg.attn_qkv_bias:  # Qwen-2 layout
        names["bq"] = f"{base}.self_attn.q_proj.bias"
        names["bk"] = f"{base}.self_attn.k_proj.bias"
        names["bv"] = f"{base}.self_attn.v_proj.bias"
    if cfg.n_experts > 0:  # Mixtral layout
        names["router"] = f"{base}.block_sparse_moe.gate.weight"
    else:
        names["w_gate"] = f"{base}.mlp.gate_proj.weight"
        names["w_up"] = f"{base}.mlp.up_proj.weight"
        names["w_down"] = f"{base}.mlp.down_proj.weight"
    if cfg.post_block_norm:  # Gemma-2 has four norms per block
        names["attn_post_norm"] = f"{base}.post_attention_layernorm.weight"
        names["mlp_norm"] = f"{base}.pre_feedforward_layernorm.weight"
        names["mlp_post_norm"] = f"{base}.post_feedforward_layernorm.weight"
    else:
        names["mlp_norm"] = f"{base}.post_attention_layernorm.weight"
    return names


# Mixtral expert naming: w1 = gate, w2 = down, w3 = up
_EXPERT_HF = {"w_gate": "w1", "w_up": "w3", "w_down": "w2"}
_EXPERT_KEYS = ("w_gate", "w_up", "w_down")


def _hf_expert_names(i: int, e: int) -> Dict[str, str]:
    base = f"model.layers.{i}.block_sparse_moe.experts.{e}"
    return {k: f"{base}.{v}.weight" for k, v in _EXPERT_HF.items()}


# HF stores every projection (and the Mixtral router) as [out, in];
# this pytree keeps [in, out] so matmuls read x @ w
_TRANSPOSED = {"wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
               "router"}


def _open_shards(model_dir: str):
    """Yield (name → (file, tensorname)) index over all safetensors shards."""
    from safetensors import safe_open

    index_path = os.path.join(model_dir, "model.safetensors.index.json")
    files: Dict[str, str] = {}
    if os.path.exists(index_path):
        with open(index_path) as f:
            weight_map = json.load(f)["weight_map"]
        for tname, fname in weight_map.items():
            files[tname] = os.path.join(model_dir, fname)
    else:
        single = os.path.join(model_dir, "model.safetensors")
        if not os.path.exists(single):
            raise FileNotFoundError(
                f"no model.safetensors[.index.json] in {model_dir}")
        with safe_open(single, framework="numpy") as f:
            for tname in f.keys():
                files[tname] = single
    return files


def load_hf_checkpoint(model_dir: str, cfg: ModelConfig, *,
                       mesh=None,
                       quantize: Optional[str] = None) -> Params:
    """Stream HF safetensors into the (optionally mesh-sharded) pytree.

    ``quantize``: "nf4" | "int8" — quantize the projection matrices
    *during* the stream (one layer-slice at a time, quantized result
    held in host RAM), so an 8B QLoRA base loads onto a single 16 GB
    chip without the full-precision tree ever existing on device. The
    equivalent of the reference loading with BitsAndBytesConfig
    (fine_tune_llama_ray.py:216-227,240). Norms/embed/lm_head stay full
    precision, like bnb.
    """
    from safetensors import safe_open

    require_name_map(cfg)
    specs = param_specs(cfg)
    shardings = (tree_shardings(mesh, specs) if mesh is not None else None)
    pdt = jnp.dtype(cfg.param_dtype)
    P_ = len(cfg.block_pattern)
    R = cfg.n_repeats
    handles: Dict[str, object] = {}

    def read(tname: str) -> np.ndarray:
        path = files[tname]
        if path not in handles:
            handles[path] = safe_open(path, framework="numpy")
        # bf16 tensors come back as ml_dtypes.bfloat16, which jnp converts
        return np.asarray(handles[path].get_tensor(tname))

    files = _open_shards(model_dir)

    def place(arr: np.ndarray, spec_path) -> jax.Array:
        arr = jnp.asarray(arr, pdt)
        if shardings is None:
            return arr
        return jax.device_put(arr, spec_path)

    def _accumulate(shape, dtype, sharding, slices):
        """Stream per-slice [1, ..] (or [1, 1, ..] for expert banks)
        device arrays into a stacked leaf living at its final (sharded)
        home: zeros-allocate once, then one donated dynamic_update_slice
        per slice. Host RAM peak stays one tensor (VERDICT r3 weak #4a:
        np.stack of all R slices held ~37 GB host RAM for a single 70B
        leaf). ``slices`` yields (lead-index tuple, array)."""
        kw = {} if sharding is None else {"out_shardings": sharding}
        out = jax.jit(lambda: jnp.zeros(shape, dtype), **kw)()
        upd = jax.jit(
            lambda o, a, idx: jax.lax.dynamic_update_slice(
                o, a.astype(dtype),
                tuple(idx) + (0,) * (len(shape) - len(idx))),
            donate_argnums=(0,))
        for idx, a in slices:
            out = upd(out, a, idx)
        return out

    def _indices_and_names(p: int, key: str, experts: bool):
        """(lead index tuples, idx→tensor-name) for a stacked leaf:
        [R] per-layer tensors, or [R, E] per-(layer, expert) for MoE
        banks (Mixtral layout)."""
        if experts:
            idxs = [(r, e) for r in range(R)
                    for e in range(cfg.n_experts)]
            return idxs, (lambda idx: _hf_expert_names(
                idx[0] * P_ + p, idx[1])[key])
        return [(r,) for r in range(R)], (lambda idx: _hf_layer_names(
            cfg, idx[0] * P_ + p)[key])

    def _slice_sharding(spec, n_lead: int):
        """Sharding for ONE streamed slice: the full leaf's spec with
        its lead (stack) dims replaced by None — a [1, 1, D, F] expert
        slice cannot be partitioned along its size-1 expert dim even
        though the assembled [R, E, D, F] leaf is."""
        from jax.sharding import NamedSharding, PartitionSpec
        return NamedSharding(mesh, PartitionSpec(
            *([None] * n_lead + list(spec)[n_lead:])))

    def load_stacked(p: int, key: str, *, experts: bool = False):
        idxs, name = _indices_and_names(p, key, experts)
        n_lead = len(idxs[0])
        tgt = shardings["blocks"][p][key] if shardings is not None else None
        # idxs[0] reuses the shape-probe read (one disk read per tensor)
        first = _maybe_t(read(name(idxs[0])), key)
        slice_tgt = (None if tgt is None else
                     _slice_sharding(specs["blocks"][p][key], n_lead))

        def slices():
            for idx in idxs:
                w = first if idx == idxs[0] else _maybe_t(
                    read(name(idx)), key)
                a = w[(None,) * n_lead]
                yield idx, (a if slice_tgt is None
                            else jax.device_put(a, slice_tgt))

        lead = tuple(d + 1 for d in idxs[-1])
        return _accumulate(lead + first.shape, pdt, tgt, slices())

    def load_quantized(p: int, key: str, *, experts: bool = False):
        """Per-slice quantize: device sees one layer (or one (layer,
        expert)) slice at a time; codes/scales stream straight into
        their device-resident (sharded) homes — neither the bf16 tree
        nor the stacked codes ever exist in host RAM (VERDICT r3 weak
        #4a)."""
        from jax.sharding import NamedSharding
        from gke_ray_train_tpu.ops.quant import (
            QTensor, quant_specs, quantize_tensor)
        idxs, name = _indices_and_names(p, key, experts)
        n_lead = len(idxs[0])

        def qt_for(idx):
            w = _maybe_t(read(name(idx)), key)
            return quantize_tensor(
                jnp.asarray(w, jnp.bfloat16)[(None,) * n_lead], quantize)

        first = qt_for(idxs[0])
        kind, group = first.kind, first.group
        lead = tuple(d + 1 for d in idxs[-1])
        c_shape = lead + first.codes.shape[n_lead:]
        s_shape = lead + first.scales.shape[n_lead:]
        c_shard = s_shard = None
        if mesh is not None:
            q_spec = quant_specs(specs["blocks"][p][key], QTensor(
                jax.ShapeDtypeStruct(c_shape, first.codes.dtype),
                jax.ShapeDtypeStruct(s_shape, first.scales.dtype),
                kind, group), mesh)
            c_shard = NamedSharding(mesh, q_spec.codes)
            s_shard = NamedSharding(mesh, q_spec.scales)

        # one read+quantize pass per tensor, feeding BOTH accumulators
        kwc = {} if c_shard is None else {"out_shardings": c_shard}
        kws = {} if s_shard is None else {"out_shardings": s_shard}
        codes = jax.jit(lambda: jnp.zeros(c_shape, first.codes.dtype),
                        **kwc)()
        scales = jax.jit(lambda: jnp.zeros(s_shape, first.scales.dtype),
                         **kws)()
        upd = jax.jit(
            lambda o, a, idx: jax.lax.dynamic_update_slice(
                o, a, tuple(idx) + (0,) * (len(o.shape) - n_lead)),
            donate_argnums=(0,))
        for idx in idxs:
            qt = first if idx == idxs[0] else qt_for(idx)
            codes = upd(codes, qt.codes, idx)
            scales = upd(scales, qt.scales, idx)
        return QTensor(codes, scales, kind, group)

    # per-(pattern-position, key): stream the R per-layer tensors
    from gke_ray_train_tpu.models.config import PROJ_TARGETS as _PROJ_KEYS
    blocks = []
    for p in range(P_):
        blk: Dict[str, jax.Array] = {}
        keys = _hf_layer_names(cfg, 0).keys()
        for key in keys:
            if quantize and key in _PROJ_KEYS:
                blk[key] = load_quantized(p, key)
                continue
            blk[key] = load_stacked(p, key)
        for key in (_EXPERT_KEYS if cfg.n_experts > 0 else ()):
            blk[key] = (load_quantized(p, key, experts=True) if quantize
                        else load_stacked(p, key, experts=True))
        blocks.append(blk)

    params: Params = {
        "embed": place(read("model.embed_tokens.weight"),
                       shardings["embed"] if shardings else None),
        "blocks": blocks,
        "final_norm": place(read("model.norm.weight"),
                            shardings["final_norm"] if shardings else None),
    }
    if not cfg.tie_embeddings:
        name = ("lm_head.weight" if "lm_head.weight" in files
                else "model.embed_tokens.weight")  # some exports tie anyway
        params["lm_head"] = place(read(name).T,
                                  shardings["lm_head"] if shardings else None)
    for h in handles.values():
        del h
    return params


def _maybe_t(arr: np.ndarray, key: str) -> np.ndarray:
    return arr.T if key in _TRANSPOSED else arr


class ShardedSafetensorsWriter:
    """Incremental HF-layout safetensors writer with bounded host RAM.

    Tensors accumulate into an in-memory shard until ``max_shard_bytes``,
    then flush to ``model-XXXXX-of-YYYYY.safetensors``; ``finish()``
    renames the shards with the final count and writes
    ``model.safetensors.index.json`` (the layout ``_open_shards``
    reads back). A model that fits one shard is written as plain
    ``model.safetensors`` with no index — identical to the old
    single-file export. Peak host RAM = max_shard_bytes + one tensor."""

    def __init__(self, out_dir: str, *, max_shard_bytes: int = 4 << 30):
        os.makedirs(out_dir, exist_ok=True)
        self.out_dir = out_dir
        self.max_shard_bytes = max_shard_bytes
        self._cur: Dict[str, np.ndarray] = {}
        self._cur_bytes = 0
        self._shards = []          # temp file paths, in order
        self._weight_maps = []     # [names] per shard

    def add(self, name: str, arr: np.ndarray) -> None:
        if self._cur and self._cur_bytes + arr.nbytes > self.max_shard_bytes:
            self._flush()
        self._cur[name] = arr
        self._cur_bytes += arr.nbytes

    def _flush(self) -> None:
        from safetensors.numpy import save_file
        path = os.path.join(self.out_dir,
                            f"model-tmp-{len(self._shards):05d}.safetensors")
        save_file(self._cur, path)
        self._shards.append(path)
        self._weight_maps.append(list(self._cur))
        self._cur = {}
        self._cur_bytes = 0

    def abort(self) -> None:
        """Remove tmp shards after a mid-stream failure so a retry does
        not inherit stale model-tmp-* files."""
        for tmp in self._shards:
            try:
                os.remove(tmp)
            except OSError:
                pass
        self._shards = []
        self._weight_maps = []
        self._cur = {}
        self._cur_bytes = 0

    def finish(self) -> None:
        if self._cur or not self._shards:
            self._flush()
        n = len(self._shards)
        if n == 1:
            os.replace(self._shards[0],
                       os.path.join(self.out_dir, "model.safetensors"))
            return
        weight_map = {}
        for i, (tmp, names) in enumerate(zip(self._shards,
                                             self._weight_maps)):
            fname = f"model-{i + 1:05d}-of-{n:05d}.safetensors"
            os.replace(tmp, os.path.join(self.out_dir, fname))
            for t in names:
                weight_map[t] = fname
        with open(os.path.join(self.out_dir,
                               "model.safetensors.index.json"), "w") as f:
            json.dump({"metadata": {}, "weight_map": weight_map}, f)


def hf_dtype_np(arr, dtype: str) -> np.ndarray:
    arr = np.asarray(jax.device_get(arr))
    if dtype == "bfloat16":
        import ml_dtypes
        arr = arr.astype(ml_dtypes.bfloat16)
    else:
        arr = arr.astype(np.dtype(dtype))
    # astype(order='K') keeps F-order on transposed views and
    # safetensors serializes the raw buffer ignoring strides — force C
    return np.ascontiguousarray(arr)


def save_hf_checkpoint(params: Params, cfg: ModelConfig, out_dir: str,
                       *, dtype: str = "bfloat16",
                       max_shard_bytes: int = 4 << 30) -> None:
    """Export the pytree to HF safetensors (sharded above
    ``max_shard_bytes``) + minimal config.json (save_pretrained parity).
    Tensors are pulled off device one LAYER at a time and flushed
    incrementally — host RAM stays O(max_shard_bytes), not O(model)
    (VERDICT r3 weak #4: the 70B export must not buffer every tensor)."""
    require_name_map(cfg)
    P_ = len(cfg.block_pattern)
    w = ShardedSafetensorsWriter(out_dir, max_shard_bytes=max_shard_bytes)

    def to_np(x) -> np.ndarray:
        return hf_dtype_np(x, dtype)

    w.add("model.embed_tokens.weight", to_np(params["embed"]))
    w.add("model.norm.weight", to_np(params["final_norm"]))
    if not cfg.tie_embeddings:
        w.add("lm_head.weight", to_np(params["lm_head"].T))
    for p, blk in enumerate(params["blocks"]):
        for r in range(cfg.n_repeats):
            names = _hf_layer_names(cfg, r * P_ + p)
            for key, tname in names.items():
                arr = jax.device_get(blk[key][r])
                w.add(tname, to_np(_maybe_t(np.asarray(arr), key)))
            for key in (_EXPERT_KEYS if cfg.n_experts > 0 else ()):
                for e in range(cfg.n_experts):
                    arr = jax.device_get(blk[key][r, e])
                    w.add(_hf_expert_names(r * P_ + p, e)[key],
                          to_np(_maybe_t(np.asarray(arr), key)))
    w.finish()
    write_hf_config(cfg, out_dir, dtype)


# cfg.name prefix → (HF architectures entry, model_type). Known
# families export a REAL HF config so `AutoConfig`/`AutoModelForCausalLM
# .from_pretrained(out_dir)` work with stock transformers — the same
# directly-loadable artifact the reference's save_pretrained produces
# (/root/reference/ray-jobs/fine_tune_llama_ray.py:354-355). Unknown
# (from-scratch) families keep the custom tag.
_HF_ARCH = (
    ("llama", ("LlamaForCausalLM", "llama")),
    ("mixtral", ("MixtralForCausalLM", "mixtral")),
    ("mistral", ("MistralForCausalLM", "mistral")),
    ("gemma2", ("Gemma2ForCausalLM", "gemma2")),
    ("qwen2", ("Qwen2ForCausalLM", "qwen2")),
)


def write_hf_config(cfg: ModelConfig, out_dir: str,
                    dtype: str = "bfloat16") -> None:
    arch, model_type = next(
        (v for pfx, v in _HF_ARCH if cfg.name.startswith(pfx)),
        ("GkeRayTrainTpuForCausalLM", None))
    out = {
        "architectures": [arch],
        "model_family": cfg.name,
        "vocab_size": cfg.vocab_size,
        "hidden_size": cfg.d_model,
        "num_hidden_layers": cfg.n_layers,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "intermediate_size": cfg.d_ff,
        "head_dim": cfg.resolved_head_dim,
        "rope_theta": cfg.rope_theta,
        "rms_norm_eps": cfg.norm_eps,
        "tie_word_embeddings": cfg.tie_embeddings,
        "torch_dtype": dtype,
        "max_position_embeddings": cfg.max_seq_len,
        "hidden_act": ("gelu_pytorch_tanh"
                       if cfg.activation == "gelu_tanh" else "silu"),
        **({"num_local_experts": cfg.n_experts,
            "num_experts_per_tok": cfg.expert_top_k}
           if cfg.n_experts else {}),
    }
    if model_type is not None:
        out["model_type"] = model_type
    if cfg.sliding_window is not None:
        out["sliding_window"] = cfg.sliding_window
    if cfg.attn_qkv_bias:
        out["attention_bias"] = True
    if cfg.rope_scaling:
        rs = dict(cfg.rope_scaling)
        # Exported bit-identical to training: every rope_scaling field
        # (factor, band factors, original_max_position_embeddings) FEEDS
        # HF's _compute_llama3_parameters, so clamping any of them would
        # silently change the loaded model's rotary frequencies.
        # max_position_embeddings stays the context the model was
        # actually built/trained with (cfg.max_seq_len) — the old
        # original*factor inflation (65536 for the Llama-3.1 preset)
        # advertised a context matching neither this model nor the stock
        # HF checkpoint (ADVICE r5 #2). When max_seq_len <= original,
        # HF's llama3 validation logs a warning (original must be <
        # max_position_embeddings) but loads fine — frequencies depend
        # only on rope_scaling, never on max_position_embeddings.
        out["rope_scaling"] = {"rope_type": "llama3", **rs}
    if model_type == "gemma2":
        if cfg.attn_softcap is not None:
            out["attn_logit_softcapping"] = cfg.attn_softcap
        if cfg.logit_softcap is not None:
            out["final_logit_softcapping"] = cfg.logit_softcap
        if cfg.attn_scale is not None:
            out["query_pre_attn_scalar"] = round(cfg.attn_scale ** -2)
    with open(os.path.join(out_dir, "config.json"), "w") as f:
        json.dump(out, f, indent=2)

"""LoRA as a low-rank param pytree (SURVEY.md row D6).

The reference delegates adapters to peft (``LoraConfig`` targeting all
projection matrices, ray-jobs/fine_tune_llama_ray.py:245-252; merge via
``merge_and_unload`` at :349-353). Here an adapter is a second pytree with
the same block structure as the model params; only it is passed to the
optimizer in LoRA mode, and merging is one einsum per target at save time:
``W += (alpha/r) * A @ B``.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from gke_ray_train_tpu.models.config import (
    LATENT_TARGETS, ModelConfig, PROJ_TARGETS, SHARED_TARGETS, SSM_TARGETS)
from gke_ray_train_tpu.models.transformer import (
    Params, block_layout, block_leaves)

# Default targets = every projection matrix, matching the reference config
# LORA_TARGET_MODULES (fine_tune_config.json:33: all q/k/v/o/gate/up/down).
ALL_TARGETS = PROJ_TARGETS


@dataclasses.dataclass(frozen=True)
class LoraConfig:
    r: int = 64
    alpha: int = 16
    targets: Tuple[str, ...] = ALL_TARGETS
    # dropout on the adapter-branch input (reference LORA_DROPOUT,
    # fine_tune_config.json:32). The train step applies it with a
    # per-(step, microbatch) rng; inference/merge ignore it.
    dropout: float = 0.0

    @property
    def scale(self) -> float:
        return self.alpha / self.r

    @staticmethod
    def from_dict(cfg: dict) -> "LoraConfig":
        """From reference-style flat config keys (fine_tune_config.json:30-33)."""
        return LoraConfig(
            r=int(cfg.get("LORA_R", 64)),
            alpha=int(cfg.get("LORA_ALPHA", 16)),
            dropout=float(cfg.get("LORA_DROPOUT", 0.0)),
        )


ATTN_TARGETS = ("wq", "wk", "wv", "wo")
# a routed layer's shared expert takes the adapters its dense MLP would
_SHARED_OF = dict(zip(("w_gate", "w_up", "w_down"), SHARED_TARGETS))
# a latent-attention layer has no wq / wk / wv: whoever asks for one
# gets the two matrices (down and up) that make it there
_LATENT_OF = {"wq": ("wq_a", "wq_b"), "wk": ("wkv_a", "wkv_b"),
              "wv": ("wkv_a", "wkv_b")}
# nor has a state-space layer: its mixer's first projection stands
# where q, k and v would, its second where the output projection would
_SSM_OF = {"wq": ("in_proj",), "wk": ("in_proj",), "wv": ("in_proj",),
           "wo": ("out_proj",)}


def _effective_targets(cfg: ModelConfig, lora_cfg: LoraConfig,
                       mlp_kind: str = None, kind: str = "global"):
    """The leaves of one block that get an (A, B) pair, from the job's
    target list. Attention: the names as given, or, in a
    latent-attention layer (``cfg.latent_attention``), the layer's own
    matrices in their place (so the default list adapts all five); in a
    state-space layer (``kind == "ssm"``) the mixer's two projections
    in the place of the attention's four. MLP:
    a routed expert bank has no single delta-W a pair could target (peft
    does the same for Mixtral by default), so a routed layer adapts,
    where it has one, its shared expert (under the shared leaves' own
    names); the router stays frozen."""
    if mlp_kind is None:
        mlp_kind = cfg.scan_mlp_kind
    out = []
    for t in lora_cfg.targets:
        if kind == "ssm" and (t in ATTN_TARGETS or t in SSM_TARGETS):
            names = _SSM_OF.get(t, (t,))
        elif t in SSM_TARGETS:
            names = ()
        elif t in ATTN_TARGETS or t in LATENT_TARGETS:
            names = _LATENT_OF.get(t, (t,)) if cfg.latent_attention \
                else (t,)
        elif mlp_kind != "moe":
            names = (t,)
        else:
            names = (_SHARED_OF[t],) if cfg.n_shared_experts \
                and t in _SHARED_OF else ()
        out += [n for n in names if n not in out]
    return tuple(out)


def init_lora(cfg: ModelConfig, lora_cfg: LoraConfig, key: jax.Array) -> Params:
    """A ~ N(0, 1/r) (kaiming-ish), B = 0 — adapters start as identity.

    Adapters (and therefore their Adam moments) are ALWAYS fp32: they
    are the only trained parameters, and bf16 masters silently drop
    updates below ~value/256 (peft's prepare_model_for_kbit_training
    keeps trainables fp32 for the same reason). The forward casts them
    to the compute dtype at use (_proj)."""
    pdt = jnp.dtype(jnp.float32)
    n_scan = len(cfg.block_pattern)
    # as many keys a scanned position as its kind with most targets has
    # (models of one kind: that kind's)
    scan_targets = max(len(_effective_targets(cfg, lora_cfg, kind=kind))
                       for kind in cfg.block_pattern)
    keys = iter(jax.random.split(key, n_scan * scan_targets + 1))
    # seven a leading layer, as when seven projections were all a layer
    # had (a latent-attention layer with a dense MLP has eight)
    a_layer = max([7] + [len(_effective_targets(cfg, lora_cfg,
                                                cfg.mlp_kind(i),
                                                cfg.block_kind(i)))
                         for i in range(cfg.prologue_layers)])
    pkeys = iter(jax.random.split(jax.random.fold_in(key, 7),
                                  a_layer * max(cfg.prologue_layers, 1)))

    def block(R, mlp_kind, kind, keys):
        shapes = block_leaves(cfg, R, mlp_kind, kind)
        out = {}
        for t in _effective_targets(cfg, lora_cfg, mlp_kind, kind):
            _, d_in, d_out = shapes[t][0]
            out[t] = {
                "a": (jax.random.normal(next(keys), (R, d_in, lora_cfg.r),
                                        jnp.float32)
                      / jnp.sqrt(lora_cfg.r)).astype(pdt),
                "b": jnp.zeros((R, lora_cfg.r, d_out), pdt),
            }
        return out

    tree: Params = {}
    for where, _, first, R, _, mlp_kind in block_layout(cfg):
        tree.setdefault(where, []).append(
            block(R, mlp_kind, cfg.block_kind(first),
                  keys if where == "blocks" else pkeys))
    return tree


def lora_specs(cfg: ModelConfig, lora_cfg: LoraConfig) -> Params:
    """Adapters are small: keep the rank dim replicated, shard the long dim
    the same way the base matrix shards (fsdp on d_model-ish inputs,
    model on head/ffn outputs)."""
    in_spec = {"wq": "fsdp", "wk": "fsdp", "wv": "fsdp", "wo": "model",
               "w_gate": "fsdp", "w_up": "fsdp", "w_down": "model",
               "shared_gate": "fsdp", "shared_up": "fsdp",
               "shared_down": "model",
               "wq_a": "fsdp", "wq_b": "fsdp", "wkv_a": "fsdp",
               "wkv_b": "fsdp", "in_proj": "fsdp", "out_proj": None}
    out_spec = {"wq": "model", "wk": "model", "wv": "model", "wo": "fsdp",
                "w_gate": "model", "w_up": "model", "w_down": "fsdp",
                "shared_gate": "model", "shared_up": "model",
                "shared_down": "fsdp",
                "wq_a": None, "wq_b": "model", "wkv_a": None,
                "wkv_b": "model", "in_proj": None, "out_proj": "fsdp"}

    tree: Params = {}
    for where, _, first, _, _, mlp_kind in block_layout(cfg):
        # leading repeat dim follows the base weights onto `pipe`
        # (no-op while the pipe axis is size 1)
        tree.setdefault(where, []).append(
            {t: {"a": P("pipe", in_spec[t], None),
                 "b": P("pipe", None, out_spec[t])}
             for t in _effective_targets(cfg, lora_cfg, mlp_kind,
                                         cfg.block_kind(first))})
    return tree


def merge_lora(params: Params, lora: Params, lora_cfg: LoraConfig, *,
               on_host: bool = False) -> Params:
    """W + (alpha/r) A@B for every adapted matrix — the equivalent of
    peft's merge_and_unload (reference fine_tune_llama_ray.py:349-353),
    but a pure function on pytrees (jit/shard friendly).

    ``on_host``: run the merge on the CPU backend (leaves moved off the
    accelerator first). Dequantizing an 8B NF4 base into a merged fp32
    tree needs ~32 GB — far over one chip's HBM but trivial in host RAM;
    the single-host export path uses this (the multi-host path keeps the
    merge on device, where each host holds only its shard)."""
    # deferred import keeps ops.quant (and its pytree registration) out
    # of LoRA-only runs; the old train↔ops cycle is gone (PROJ_TARGETS
    # now lives in models.config)
    from gke_ray_train_tpu.ops.quant import (
        QTensor, dequantize, is_qtensor, maybe_dequantize)

    import contextlib

    cpu = jax.devices("cpu")[0] if on_host else None
    # jitted helpers (dequantize's NF4 lookup) dispatch to the DEFAULT
    # device no matter where their operands live — without this the
    # "host" merge math would still run (and OOM) on the accelerator
    dev_ctx = (jax.default_device(cpu) if cpu is not None
               else contextlib.nullcontext())

    def pull(x):
        if cpu is None:
            return x
        if is_qtensor(x):
            return QTensor(jax.device_put(x.codes, cpu),
                           jax.device_put(x.scales, cpu), x.kind, x.group)
        return jax.device_put(x, cpu)

    merged = jax.tree.map(lambda x: x, params)  # shallow-ish copy
    with dev_ctx:
        for p_blk, l_blk in zip(
                merged["blocks"] + merged.get("prologue", []),
                lora["blocks"] + lora.get("prologue", [])):
            for t, ab in l_blk.items():
                delta = jnp.einsum("lir,lro->lio",
                                   pull(ab["a"]).astype(jnp.float32),
                                   pull(ab["b"]).astype(jnp.float32)) \
                    * lora_cfg.scale
                # QLoRA bases dequantize on merge — peft's
                # merge_and_unload does the same before folding in
                base = maybe_dequantize(pull(p_blk[t]), jnp.float32)
                out_dtype = (jnp.float32 if is_qtensor(p_blk[t])
                             else p_blk[t].dtype)
                p_blk[t] = (base + delta).astype(out_dtype)
            # quantized weights WITHOUT adapters (e.g. q/v-only LoRA)
            # must still come back to full precision — the HF export
            # consumes plain arrays only
            for t, w in p_blk.items():
                if is_qtensor(w):
                    p_blk[t] = dequantize(pull(w), jnp.float32)
    if cpu is not None:
        # non-target leaves (embed/norms/lm_head) follow so the export
        # reads a uniformly host-resident tree
        merged = jax.tree.map(
            lambda x: jax.device_put(x, cpu)
            if not isinstance(x, (int, float)) else x, merged)
    return merged

"""The routed decoder's family (``exaone_moe``) for
``drivers/train.py::run``: its model configuration from the published
keys (q/k norm, the sigmoid router with its bias and scale, a shared
expert and a dense prologue), the benchmark's seeded tree in the
program's layout (every bank quantised expert by expert as it is drawn,
one jitted call), adapters, and each layer's kind. The tree and the
adapters are built for every routed family here, from the weights module
each names (``w``).
"""

from __future__ import annotations

import functools
import sys
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from benchmark import weights as wts
from benchmark import weights_moe as wm
from benchmark.drivers import common, train

# program leaf -> the benchmark's leaf, where the names differ: the
# program stores a sparse layer's bank under the dense MLP's names
BANK = dict(zip(wm.DENSE_MLP, wm.EXPERT))


# ---------------------------------------------------------------------------
# the seam to the program
# ---------------------------------------------------------------------------

def attention_period(config: dict):
    """The shortest period of ``layer_types`` over the layers run, in
    the program's words."""
    n = int(config["num_hidden_layers"])
    kinds = ["sliding" if wm.layer_kinds(config, i)[0] == "sliding"
             else "global" for i in range(n)]
    for p in range(1, n + 1):
        if n % p == 0 and all(kinds[i] == kinds[i % p] for i in range(n)):
            return tuple(kinds[:p])


def model_config(config: dict, *, dtype: str, param_dtype: str,
                 attn_impl: str = "auto", remat_policy: str = "full",
                 max_seq_len: int):
    from gke_ray_train_tpu.models.config import ModelConfig
    dims = wm.dims_from_config(config)
    rope = config.get("rope_parameters") or {}
    return ModelConfig(
        name=str(config.get("model_type", "model")),
        vocab_size=dims["vocab"], d_model=dims["hidden"],
        n_layers=dims["layers"], n_heads=dims["heads"],
        n_kv_heads=dims["kv_heads"], head_dim=dims["head_dim"],
        d_ff=dims["ff"], max_seq_len=int(max_seq_len),
        norm_eps=float(config["rms_norm_eps"]),
        rope_theta=float(rope.get("rope_theta",
                                  config.get("rope_theta", 1e4))),
        block_pattern=attention_period(config),
        sliding_window=int(config["sliding_window"]),
        rope_kinds=("sliding",), qk_norm=True,
        n_experts=dims["experts"], expert_top_k=dims["top_k"],
        expert_d_ff=dims["expert_ff"], n_shared_experts=dims["shared"],
        n_dense_layers=dims["dense_layers"], router="sigmoid",
        router_bias=True,
        router_renorm=bool(config.get("norm_topk_prob", True)),
        router_scale=float(config.get("routed_scaling_factor", 1.0)),
        experts_held=(dims["held_lo"], dims["held_lo"] + dims["held"]),
        tie_embeddings=bool(config.get("tie_word_embeddings", False)),
        dtype=dtype, param_dtype=param_dtype, attn_impl=attn_impl,
        remat_policy=remat_policy)


def params_maker(cfg, config: dict, *, quant_kind: Optional[str],
                 quant_group: int = 64, w=wm):
    """``make(key) -> tree`` in the program's layout and the types it is
    run in: projections, mixers and experts quantised slice by slice as
    they are drawn (never a full-precision tree first), the rest in
    ``cfg.param_dtype``. ``w`` is the routed family's weights module
    (``weights_moe``, ``weights_mla``, ``weights_ssm``): its sizes, names
    and draws."""
    from gke_ray_train_tpu.models.transformer import (
        block_layout, block_leaves)
    from gke_ray_train_tpu.ops.quant import QTensor, quantize_tensor

    dims = w.dims_from_config(config)
    pdt = jnp.dtype(cfg.param_dtype)
    quant = quant_kind not in (None, "none")
    frozen = w.ATTENTION + getattr(w, "MIXER", ()) + tuple(BANK) + w.SHARED

    def leaf(key, name, layer, kind, expert=None):
        """One layer's (one expert's) leaf as the program stores it."""
        bench_name = BANK[name] if kind == "moe" and name in BANK else name
        args = () if expert is None else (expert,)
        if not (quant and name in frozen):
            return w.stored(dims, key, bench_name, layer, pdt, *args)
        x = w.stored(dims, key, bench_name, layer, jnp.bfloat16, *args)
        qt = quantize_tensor(x[None], quant_kind, quant_group)
        return qt.codes[0], qt.scales[0]

    def stack(key, name, first, count, stride, kind):
        bank = kind == "moe" and name in BANK
        experts = dims["held_lo"] + jnp.arange(dims["held"], dtype=jnp.int32)

        def one(r):
            layer = first + r * stride
            if bank:
                return jax.lax.map(
                    lambda e: leaf(key, name, layer, kind, e), experts)
            return leaf(key, name, layer, kind)
        out = jax.lax.map(one, jnp.arange(count, dtype=jnp.int32))
        if isinstance(out, tuple):
            return QTensor(out[0], out[1], quant_kind,
                           out[0].shape[-2] // out[1].shape[-2])
        return out

    def make(key):
        tree = {"embed": wts.stored(dims, key, "embed", 0, pdt),
                "final_norm": wts.stored(dims, key, "final_norm", 0, pdt)}
        if not cfg.tie_embeddings:
            tree["lm_head"] = wts.stored(dims, key, "lm_head", 0, pdt)
        for where, _, first, count, stride, kind in block_layout(cfg):
            tree.setdefault(where, []).append(
                {name: stack(key, name, first, count, stride, kind)
                 for name in block_leaves(cfg, count, kind,
                                          cfg.block_kind(first))})
        return tree
    return make


def build_params(cfg, config: dict, seed: int, mesh, *,
                 quant_kind: Optional[str], quant_group: int = 64, w=wm):
    """The whole tree in one jitted call from the seed."""
    make = params_maker(cfg, config, quant_kind=quant_kind,
                        quant_group=quant_group, w=w)
    key = wts.seed_key(seed)
    shardings = common.param_shardings(cfg, jax.eval_shape(make, key), mesh)
    return jax.jit(make, out_shardings=shardings)(key)


def build_lora(cfg, config: dict, seed: int, mesh, lora_cfg, w=wm):
    """LoRA adapters in the program's layout: A from the seed, B zero."""
    from gke_ray_train_tpu.models.transformer import block_layout
    from gke_ray_train_tpu.parallel.sharding import tree_shardings
    from gke_ray_train_tpu.train.lora import lora_specs

    dims = w.dims_from_config(config)
    specs = lora_specs(cfg, lora_cfg)

    def make(key):
        tree: Dict[str, list] = {}
        for where, i, first, count, stride, _ in block_layout(cfg):
            layers = first + stride * jnp.arange(count, dtype=jnp.int32)
            tree.setdefault(where, []).append({
                t: {"a": jax.lax.map(
                        lambda l, t=t: w.lora_a(dims, key, t, l,
                                                lora_cfg.r), layers),
                    "b": jnp.zeros((count,) + w.lora_b_shape(
                        dims, t, lora_cfg.r), jnp.float32)}
                for t in specs[where][i]})
        return tree
    return jax.jit(make, out_shardings=tree_shardings(mesh, specs))(
        wts.seed_key(seed))


# ``drivers/train.py::run`` with this module as the family
layer_kinds = wm.layer_kinds
run = functools.partial(train.run, family=sys.modules[__name__])

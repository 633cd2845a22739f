"""The latent-attention routed decoder's family (``glm4_moe_lite``:
GLM-4.7-Flash) for ``drivers/train.py::run``: its model configuration
from the published keys (the latent ranks and the three head sizes), the
benchmark's seeded tree in the program's layout (the five attention
matrices, every bank quantised expert by expert as it is drawn, one
jitted call), adapters, each layer's kind, and two numbers for
``correct`` beside the norms' gaps: the first gradient set tensor
against tensor with the reference's (``grad_dir_gap``,
``attn_dir_gap``), which tell bfloat16 from the precision below where
the norms' gap does not.
"""

from __future__ import annotations

import functools
import sys
from typing import Dict

from benchmark import weights_mla as wl
from benchmark.drivers import train, train_moe
# the tensor-against-tensor arithmetic is common to every family that
# reads it; it is named here too, where the tests of this family look
from benchmark.drivers.common import (  # noqa: F401
    direction_gap, gradient_by_layer, gradient_table)


# ---------------------------------------------------------------------------
# the seam to the program
# ---------------------------------------------------------------------------

def model_config(config: dict, *, dtype: str, param_dtype: str,
                 attn_impl: str = "auto", remat_policy: str = "full",
                 max_seq_len: int):
    from gke_ray_train_tpu.models.config import ModelConfig
    dims = wl.dims_from_config(config)
    return ModelConfig(
        name=str(config.get("model_type", "model")),
        vocab_size=dims["vocab"], d_model=dims["hidden"],
        n_layers=dims["layers"], n_heads=dims["heads"],
        n_kv_heads=dims["kv_heads"], d_ff=dims["ff"],
        max_seq_len=int(max_seq_len),
        norm_eps=float(config["rms_norm_eps"]),
        rope_theta=float(config["rope_theta"]),
        q_lora_rank=dims["q_rank"], kv_lora_rank=dims["kv_rank"],
        qk_nope_head_dim=dims["nope"], qk_rope_head_dim=dims["rope"],
        v_head_dim=int(config["v_head_dim"]),
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


# the tree and the adapters as every routed family builds them
# (``train_moe``), from this family's weights module
params_maker = functools.partial(train_moe.params_maker, w=wl)
build_params = functools.partial(train_moe.build_params, w=wl)
build_lora = functools.partial(train_moe.build_lora, w=wl)


def gradient_readings(table) -> Dict[str, float]:
    """``grad_dir_gap`` over every adapter leaf; ``attn_dir_gap`` over
    the latent layer's five matrices alone, whose gradients rounding
    moves least and a fault in the attention moves most."""
    return {"grad_dir_gap": direction_gap(table),
            "attn_dir_gap": direction_gap(table, wl.ATTENTION)}


# ``drivers/train.py::run`` with this module as the family
layer_kinds = wl.layer_kinds
run = functools.partial(train.run, family=sys.modules[__name__])

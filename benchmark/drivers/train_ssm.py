"""The hybrid state-space / attention routed decoder's family
(``granitemoehybrid``: Granite-4.0-H-Small) for ``drivers/train.py::run``:
its model configuration from the published keys (the layer pattern, the
mixer's sizes, the four multipliers, the softmax-of-the-selected
router), the benchmark's seeded tree in the program's layout (a
state-space layer's leaves where such a layer stands, no head: it is
tied), adapters, each layer's kind, and ``grad_dir_gap`` as the latent
family reads it with ``ssm_dir_gap`` beside it: the first gradient
tensor against tensor over the mixers' two projections alone.
"""

from __future__ import annotations

import functools
import sys
from typing import Dict

from benchmark import harness as hs
from benchmark import weights_ssm as ws
from benchmark.drivers import train, train_moe
# the tensor-against-tensor arithmetic is common to every family that
# reads it; it is named here too, where the tests of this family look
from benchmark.drivers.common import (  # noqa: F401
    direction_gap, gradient_by_layer, gradient_table)


# ---------------------------------------------------------------------------
# the seam to the program
# ---------------------------------------------------------------------------

def layer_period(config: dict):
    """The shortest period of ``layer_types`` over the layers run, in
    the program's words ("ssm" | "global")."""
    n = int(config["num_hidden_layers"])
    kinds = ["ssm" if ws.layer_kinds(config, i)[0] == "mamba" else "global"
             for i in range(n)]
    for p in range(1, n + 1):
        if n % p == 0 and all(kinds[i] == kinds[i % p] for i in range(n)):
            return tuple(kinds[:p])


def model_config(config: dict, *, dtype: str, param_dtype: str,
                 attn_impl: str = "auto", remat_policy: str = "full",
                 max_seq_len: int):
    from gke_ray_train_tpu.models.config import ModelConfig
    dims = ws.dims_from_config(config)
    if config["mamba_proj_bias"]:
        raise hs.BenchFailure("the program's mixer projects without bias")
    return ModelConfig(
        name=str(config.get("model_type", "model")),
        vocab_size=dims["vocab"], d_model=dims["hidden"],
        n_layers=dims["layers"], n_heads=dims["heads"],
        n_kv_heads=dims["kv_heads"], d_ff=dims["ff"],
        max_seq_len=int(max_seq_len),
        norm_eps=float(config["rms_norm_eps"]),
        block_pattern=layer_period(config), rope_kinds=(),
        attn_scale=float(config["attention_multiplier"]),
        ssm_heads=dims["ssm_heads"], ssm_head_dim=dims["ssm_head_dim"],
        ssm_state=dims["ssm_state"], ssm_groups=dims["ssm_groups"],
        ssm_conv=dims["ssm_conv"], ssm_chunk=dims["ssm_chunk"],
        ssm_conv_bias=bool(config["mamba_conv_bias"]),
        n_experts=dims["experts"], expert_top_k=dims["top_k"],
        expert_d_ff=dims["expert_ff"], n_shared_experts=dims["shared"],
        shared_d_ff=dims["shared_ff"], router="topk_softmax",
        experts_held=(dims["held_lo"], dims["held_lo"] + dims["held"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        embed_multiplier=float(config["embedding_multiplier"]),
        residual_multiplier=float(config["residual_multiplier"]),
        logits_scaling=float(config["logits_scaling"]),
        dtype=dtype, param_dtype=param_dtype, attn_impl=attn_impl,
        remat_policy=remat_policy)


# the tree and the adapters as every routed family builds them
# (``train_moe``), from this family's weights module
params_maker = functools.partial(train_moe.params_maker, w=ws)
build_params = functools.partial(train_moe.build_params, w=ws)
build_lora = functools.partial(train_moe.build_lora, w=ws)


def gradient_readings(table) -> Dict[str, float]:
    """``grad_dir_gap`` over every adapter leaf, as the latent family
    reads it; ``ssm_dir_gap`` over the mixers' two projections alone,
    which a fault in the scan or the conv moves most: everything between
    ``in_proj``'s output and ``out_proj``'s input is theirs."""
    return {"grad_dir_gap": direction_gap(table),
            "ssm_dir_gap": direction_gap(table, ws.MIXER)}


def _carry_across_documents() -> None:
    from gke_ray_train_tpu.ops import ssm
    scan = ssm.ssd_scan
    ssm.ssd_scan = lambda x, dt, a, b, c, d, seg, **kw: scan(
        x, dt, a, b, c, d, None, **kw)


def _conv_across_documents() -> None:
    from gke_ray_train_tpu.ops import ssm
    conv = ssm.causal_conv
    ssm.causal_conv = lambda x, w, b, seg: conv(x, w, b, None)


# faults of the mechanism that ``tools/gradient_readings.py --fault``
# plants in the program, to read what the cell's numbers make of them at
# its own size: the scan, or the conv, told nothing of the documents
FAULTS = {"carry": _carry_across_documents, "conv": _conv_across_documents}

# ``drivers/train.py::run`` with this module as the family
layer_kinds = ws.layer_kinds
run = functools.partial(train.run, family=sys.modules[__name__])

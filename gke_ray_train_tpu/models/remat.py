"""What the per-block ``jax.checkpoint`` keeps.

Every block of the layer scan is checkpointed (``cfg.remat``): its input
is saved and its forward runs a second time in the backward pass. This
module holds what a model's shapes say about saving more: activations
tagged with ``jax.ad_checkpoint.checkpoint_name`` under the scope names
the program already has (``obs/trace.py::SCOPE_NAMES``).

- :func:`checkpoint_block` wraps a scan body; the three layer loops
  (``models/transformer.py``, ``train/overlap.py``,
  ``models/pipeline.py``) all call it. ``keep=()`` is ``policy=None``.
- :func:`keep_candidates` / :func:`choose_keep`: per-device bytes of each
  named tensor over all layers, first-fit into a byte budget.
- :func:`working_set_bytes`: what a step with nothing kept holds beside
  its arguments.

The budget itself (the device's limit less the step's arguments) is the
train step's to know: ``train/remat.py``.
"""

from __future__ import annotations

import math
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from gke_ray_train_tpu.models.config import ModelConfig

# the tensors a block can keep, first-fit in this order. `mlp/gate_up`
# leads because it removes more recomputation than the other three
# together (two d_model x d_ff matmuls a position against the q/k/v and
# output projections and one attention forward); the rest follow by
# device seconds saved per byte kept (the second flash forward, the
# q/k/v projections with rope, the output projection: PERF.md, PR 25)
KEEP_ORDER: Tuple[str, ...] = ("mlp/gate_up", "attn/core", "attn/qkv",
                               "attn/out", "attn/latent", "ssm/scan",
                               "ssm/out", "moe/shared", "moe/experts",
                               "ssm/in_proj")
# `attn/latent` (a latent-attention layer's two down-projections'
# outputs) follows `attn/out`: a position's 1,344 values spare 2.75 M
# weights of products where the output projection's 2,048 spare 10.5 M
# (GLM-4.7-Flash's sizes); where `attn/qkv` fits as well, the latents
# spare nothing more and cost a twelfth of it
# the routed layer's two names come last: `moe/shared` (the shared
# expert's gate and up) saves what `mlp/gate_up` saves at a ninth of the
# width, and `moe/experts` (gate and up of every row of the pair buffer)
# pays for the buffer's worst case, eight times the rows that are real
# at one rank of eight (PERF.md, PR 26)
# a state-space layer's three names (models/transformer.py::_ssm):
# `ssm/scan` (the scan's output, 2 bytes a value of the mixer's heads)
# spares the conv and the scan their second run, the slowest work a
# byte in such a layer as XLA writes the scan (PERF.md, PR 32), so it
# leads the routed layer's names; `ssm/out` is `attn/out`'s like;
# `ssm/in_proj` (the first projection's output, twice the heads' values
# and more) is last: where it fits, everything does

# XLA's peak grows by less than a kept tensor's stacked bytes: the layer
# in flight was among the block's temporaries already, and the backward
# no longer holds a recomputed copy beside the cotangents. Over the same
# compiles as `working_set_bytes`: 0.90-0.92 of the bytes for
# `mlp/gate_up`, 0.94 for the three attention tensors (1.07 at one row
# of 2048, which the margin of `working_set_bytes` covers half of)
KEPT_PEAK_SHARE = 0.92

Candidates = Tuple[Tuple[str, int], ...]


def checkpoint_block(body: Callable, cfg: ModelConfig,
                     keep: Sequence[str] = (), *,
                     in_scan: bool = True) -> Callable:
    """``body`` under the block checkpoint ``cfg`` asks for.

    ``remat_policy == "full"``: the block's input is saved, plus the
    tensors named in ``keep``. ``"dots"`` saves every matmul output and
    ignores ``keep``. ``in_scan``: ``body`` is a whole scan body, whose
    loop already keeps the recomputation where it belongs; a layer that
    is one of several in a body (or in no loop at all) is tied to its
    cotangent by jax's own barriers instead (``prevent_cse``), or XLA
    recomputes every such layer ahead of the backward pass and holds all
    their residuals at once."""
    if not cfg.remat:
        return body
    policy = None
    if cfg.remat_policy == "dots":
        # save matmul outputs, recompute only elementwise
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    elif keep:
        policy = jax.checkpoint_policies.save_only_these_names(*keep)
    return jax.checkpoint(body, prevent_cse=not in_scan, policy=policy)


def keep_candidates(cfg: ModelConfig, rows: int, seq: int, *,
                    model: int = 1, flash: bool = True) -> Candidates:
    """``((name, bytes), ...)`` in :data:`KEEP_ORDER`: what keeping each
    named tensor costs one device over all layers, for a micro-batch of
    ``rows`` x ``seq`` positions on that device. ``model``: size of the
    mesh's tensor-parallel axis (heads and d_ff are divided over it).
    ``flash``: ``attn/core`` names the flash kernel's residuals (``o``,
    ``lse``); the dense path has nothing under that name. A name costs
    the layers that have it: ``mlp/gate_up`` the dense-MLP layers, the
    ``moe/`` names the sigmoid router's layers (the softmax layer names
    nothing)."""
    item = jnp.dtype(cfg.dtype).itemsize
    hd = cfg.resolved_head_dim
    heads = math.ceil(cfg.n_heads / model)
    kv_heads = math.ceil(cfg.n_kv_heads / model)
    n_moe = sum(cfg.mlp_kind(i) == "moe" for i in range(cfg.n_layers))
    routed = n_moe if cfg.dropless_router else 0
    d_fe = math.ceil(cfg.resolved_expert_d_ff / model)
    n_ssm = cfg.n_ssm_layers
    n_attn = cfg.n_layers - n_ssm
    # (bytes a position, layers that have the name)
    per_position = {
        "attn/core": (heads * (hd * item + 4),          # o + float32 lse
                      n_attn if flash else 0),
        "mlp/gate_up": (2 * math.ceil(cfg.d_ff / model) * item,
                        cfg.n_layers - n_moe),
        "attn/qkv": ((heads + 2 * kv_heads) * hd * item, n_attn),
        "attn/out": (cfg.d_model * item, n_attn),
        # every device has the latents whole (models/transformer.py)
        "attn/latent": (_latent_width(cfg) * item,
                        n_attn if cfg.latent_attention else 0),
        "moe/shared": (2 * _shared_width(cfg, model) * item,
                       routed if cfg.n_shared_experts else 0),
        "moe/experts": (2 * d_fe * item
                        * min(cfg.expert_top_k, max(cfg.n_experts_held, 1)),
                        routed),
        # the mixer is not divided over `model`
        "ssm/scan": (cfg.ssm_inner * item, n_ssm),
        "ssm/out": (cfg.d_model * item, n_ssm),
        "ssm/in_proj": (_ssm_in_width(cfg) * item, n_ssm),
    }
    return tuple((n, rows * seq * layers * nbytes)
                 for n, (nbytes, layers) in
                 ((n, per_position[n]) for n in KEEP_ORDER) if layers)


def _latent_width(cfg: ModelConfig) -> int:
    """Values a position under ``attn/latent``: what the two
    down-projections of a latent-attention layer give (0 without)."""
    if not cfg.latent_attention:
        return 0
    return cfg.q_lora_rank + cfg.kv_lora_rank + cfg.qk_rope_head_dim


def _shared_width(cfg: ModelConfig, model: int) -> int:
    """Hidden values of the shared expert on one device."""
    if cfg.shared_d_ff:
        return math.ceil(cfg.shared_d_ff / model)
    return cfg.n_shared_experts * math.ceil(
        cfg.resolved_expert_d_ff / model)


def _ssm_in_width(cfg: ModelConfig) -> int:
    """Columns of a state-space mixer's first projection: the gate, the
    conv's columns and a step a head (0 without such layers)."""
    return cfg.ssm_inner + cfg.ssm_conv_dim + (cfg.ssm_heads or 0)


def _ssm_io_width(cfg: ModelConfig) -> int:
    """Values a position that the backward of a state-space mixer holds
    where an attention layer holds q, k, v and o: the first
    projection's output and twice the heads' values (the scan's output
    and the gated, normed input of the second projection), fitted to
    XLA's peak for the Granite-4.0-H-Small share (PERF.md, PR 32)."""
    return _ssm_in_width(cfg) + 2 * cfg.ssm_inner


def _latent_up_width(cfg: ModelConfig, model: int) -> int:
    """Values a position that the two up-projections of a
    latent-attention layer give one device (0 without): a head's q
    before its rotary slice is turned, and its keys without position
    beside its values."""
    if not cfg.latent_attention:
        return 0
    return math.ceil(cfg.n_heads / model) * (
        2 * cfg.qk_nope_head_dim + cfg.qk_rope_head_dim + cfg.v_head_dim)


def choose_keep(candidates: Candidates, budget_bytes: Optional[int], *,
                peak_share: float = 1.0) -> Tuple[str, ...]:
    """First-fit in the candidates' order, each charged ``peak_share``
    of its bytes. ``None`` (the device reports no limit) keeps
    nothing."""
    if budget_bytes is None:
        return ()
    kept, left = [], budget_bytes
    for name, nbytes in candidates:
        charged = math.ceil(nbytes * peak_share)
        if charged <= left:
            kept.append(name)
            left -= charged
    return tuple(kept)


def working_set_bytes(cfg: ModelConfig, rows: int, seq: int, *,
                      model: int, trainable_bytes: int,
                      trainable_full_bytes: int,
                      cast_bytes: int = 0) -> int:
    """What the step with nothing kept holds beside its arguments at
    its fullest moment, on one device: inside the backward pass of one
    micro-batch, or at its loss. ``trainable_bytes``: one device's share
    of the trainable tree; ``trainable_full_bytes``: the whole tree, of
    which one layer's gradient exists unsharded before it is reduced;
    ``cast_bytes``: the compute-dtype copy of trainable leaves that XLA
    makes once for all layers (it does so for adapters).

    Meant to err high. Set against ``compiled.memory_analysis()`` of
    the 7B QLoRA step on one described v5e chip over ranks, vocabularies,
    rows, sequence lengths and depths, it reads 0.04-0.40 GB above
    XLA's, and 0.04-0.72 GB above for a full fine-tune across four
    (PERF.md, PR 25). The sigmoid router's block is sized by
    :func:`_routed_block_bytes` (PERF.md, PR 26). With latent attention
    (the GLM-4.7-Flash share on one described v5e chip at 47, 24 and 12
    layers, 8192 and 4096 positions) it reads 0.00-0.32 GB above XLA's
    ``peak_memory_in_bytes`` (PERF.md, PR 30). A state-space layer is
    sized as the block it stands in with the mixer's weights and
    tensors in the attention's place (:func:`_ssm_io_width`; PERF.md,
    PR 32)."""
    item = jnp.dtype(cfg.dtype).itemsize
    positions = rows * seq
    d_ff = math.ceil(cfg.d_ff / model)
    hd = cfg.resolved_head_dim
    qkv = math.ceil((cfg.n_heads + 2 * cfg.n_kv_heads) / model) * hd
    # a latent layer's q, k and v are assembled from the up-projections'
    # outputs, which live beside them (with the latents they come from)
    attn_io = qkv + cfg.d_model + _latent_width(cfg) + _latent_up_width(
        cfg, model)
    attn_weights = cfg.d_model * (qkv + math.ceil(cfg.n_heads / model) * hd)
    if cfg.latent_attention:
        # the five matrices, the up- and output projections' heads divided
        attn_weights = sum(
            a * b // (model if name in ("wq_b", "wkv_b", "wo") else 1)
            for name, (a, b) in cfg.attn_leaf_shapes().items())
    routed = cfg.dropless_router
    block_weights = attn_weights + (
        1 if routed else max(cfg.n_experts, 1)) * 3 * cfg.d_model * d_ff
    # float32 logits and their compute-dtype cotangent; this micro-batch
    # has no gradients yet
    at_loss = positions * math.ceil(cfg.vocab_size / model) * (4 + item)
    # a block's backward, the larger of two moments. Many positions: its
    # weights in the compute dtype (dequantised, cast or gathered) beside
    # gate / up / act / h with three cotangents and q / k / v / o with
    # theirs. Few positions: the weights twice (the dx matmuls read them
    # in another layout) beside the attention's tensors.
    in_block = (item * max(block_weights
                           + positions * (7 * d_ff + 4 * attn_io),
                           2 * block_weights + positions * 2 * attn_io)
                + trainable_full_bytes // cfg.n_layers)
    if routed:
        # (weights, values a position) of each kind of mixer the model has
        mixers = []
        if cfg.n_ssm_layers < cfg.n_layers:
            mixers.append((attn_weights, attn_io))
        if cfg.n_ssm_layers:
            mixers.append((cfg.d_model * _ssm_in_width(cfg)
                           + cfg.ssm_inner * cfg.d_model,
                           _ssm_io_width(cfg)))
        in_block = max(in_block if cfg.n_dense_layers else 0,
                       max(_routed_block_bytes(cfg, positions, model, item,
                                               weights, io)
                           for weights, io in mixers)
                       + trainable_full_bytes // cfg.n_layers)
        if cfg.n_repeats > 1 and len(cfg.block_pattern) > 1:
            in_block += _routed_loop_bytes(
                cfg, positions, model, item,
                max(weights for weights, _ in mixers))
    return (trainable_bytes + cast_bytes     # gradient accumulator, copy
            + cfg.n_layers * positions * cfg.d_model * item  # block inputs
            + max(at_loss, trainable_bytes + in_block))


def _routed_loop_bytes(cfg: ModelConfig, positions: int, model: int,
                       item: int, mixer_weights: int) -> int:
    """What XLA's peak holds more where the layers of a routed period
    run inside a real loop over the periods (several layers a period,
    each under its own checkpoint, more than one period scanned):
    fitted as one layer's weights in the compute dtype and one pair
    buffer's gathered rows once more. The Granite-4.0-H-Small share on
    one described v5e chip reads XLA's peak 1.17 GB (20 and 30 layers
    at 8192 positions) and 0.72 GB (20 layers at 4096) above the
    estimate without this term and 0.10 GB under it at one period (10
    layers, where the loop is no loop); with it the estimate reads
    0.08-0.20 GB above (PERF.md, PR 32)."""
    bank = math.ceil(cfg.n_experts_held / model) * 3 * cfg.d_model \
        * cfg.resolved_expert_d_ff
    shared = 3 * cfg.d_model * _shared_width(cfg, model)
    pairs = positions * min(cfg.expert_top_k, cfg.n_experts_held)
    return item * (bank + mixer_weights + shared + pairs * cfg.d_model)


def _routed_block_bytes(cfg: ModelConfig, positions: int, model: int,
                        item: int, attn_weights: int, attn_io: int) -> int:
    """The backward of one sigmoid-routed block at its fullest, fitted
    to XLA's peak for the K-EXAONE share on one described v5e chip (8
    layers; 8192 and 4096 positions; 16 and 8 experts held: the step's
    estimate reads 0.15-0.29 GB above XLA's; PERF.md, PR 26). The held
    bank, the attention's and the shared expert's weights in the
    compute dtype; the pair buffer at its worst-case rows, whatever the
    routing: four tensors of the model's width (the gathered rows, the
    experts' output and the cotangents of both) and three of the
    expert's; the attention's tensors once."""
    d_fe = math.ceil(cfg.resolved_expert_d_ff / model)
    bank = math.ceil(cfg.n_experts_held / model) * 3 * cfg.d_model \
        * cfg.resolved_expert_d_ff
    shared = 3 * cfg.d_model * _shared_width(cfg, model)
    pairs = positions * min(cfg.expert_top_k, cfg.n_experts_held)
    return item * (bank + attn_weights + shared
                   + pairs * (4 * cfg.d_model + 3 * d_fe)
                   + positions * attn_io)

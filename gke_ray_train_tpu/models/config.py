"""Model configuration — one config dataclass drives every model family.

The reference hardcodes one bespoke torch model (BasicLLM,
ray-jobs/pytorch_llm_ray.py:75-105) and delegates Llama to HF
``AutoModelForCausalLM`` (ray-jobs/fine_tune_llama_ray.py:240). Here a
single functional decoder core (models/transformer.py) covers Llama-3,
Mistral, Gemma-2 and the from-scratch BasicLM via this config, so every
family gets the same sharding specs, flash/ring attention, LoRA and
checkpointing for free.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

# The seven projection matrices of every decoder block — the canonical
# target list for LoRA adapters (reference LORA_TARGET_MODULES,
# fine_tune_config.json:33) and weight quantization. Lives here (leaf
# module, no deps) so ops/quant.py and train/lora.py can both import it
# without a train↔ops cycle.
PROJ_TARGETS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
# the shared expert of a routed layer (cfg.n_shared_experts): the MLP
# every token passes beside the routed experts, adapted and quantized
# like the dense MLP whose place it takes
SHARED_TARGETS = ("shared_gate", "shared_up", "shared_down")
# the four matrices a latent-attention layer has in the place of
# wq / wk / wv (cfg.latent_attention): the down- and up-projection of
# the query's latent and of the latent that keys and values share
LATENT_TARGETS = ("wq_a", "wq_b", "wkv_a", "wkv_b")
# the two projections of a state-space layer's mixer ("ssm" in
# cfg.block_pattern), which stand where a layer's attention matrices
# would: nothing else of the mixer is quantized or adapted
SSM_TARGETS = ("in_proj", "out_proj")


# fields that came with the sigmoid-routed decoder (PR 26), after model
# digests were first recorded (ModelConfig.to_dict)
_LATER_FIELDS = frozenset({
    "rope_kinds", "qk_norm", "router", "router_bias", "router_renorm",
    "router_scale", "n_shared_experts", "expert_d_ff", "n_dense_layers",
    "experts_held", "n_mtp_layers",
    # latent attention (PR 30)
    "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
    "v_head_dim",
    # state-space layers and Granite's multipliers (PR 32)
    "ssm_heads", "ssm_head_dim", "ssm_state", "ssm_groups", "ssm_conv",
    "ssm_chunk", "ssm_conv_bias", "embed_multiplier",
    "residual_multiplier", "logits_scaling", "shared_d_ff"})


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    vocab_size: int
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    head_dim: Optional[int] = None          # default d_model // n_heads
    max_seq_len: int = 2048
    norm_eps: float = 1e-5

    # positional encoding
    positional: str = "rope"                # "rope" | "sinusoidal"
    rope_theta: float = 10000.0
    # llama-3.1 NTK-by-parts params; dicts are normalized to sorted
    # (key, value) tuples in __post_init__ so the config stays hashable
    rope_scaling: Optional[object] = None

    # block structure; n_layers must divide by len(block_pattern).
    # "global" = full causal attention, "sliding" = windowed causal,
    # "ssm" = a state-space mixer in the attention's place (below).
    block_pattern: Tuple[str, ...] = ("global",)
    sliding_window: Optional[int] = None
    # the block kinds whose q and k are rotated (EXAONE-4 rotates in its
    # windowed layers only; its full-attention layers carry no position)
    rope_kinds: Tuple[str, ...] = ("global", "sliding")
    # RMSNorm over each head's q and k before the rotation, one scale
    # vector of head_dim a layer each (EXAONE-4, Qwen-3)
    qk_norm: bool = False
    # latent attention (DeepSeek-V2/V3's MLA; GLM-4.7-Flash): q comes
    # from a latent of q_lora_rank, keys and values from one of
    # kv_lora_rank (each RMSNormed, then projected up to the heads); a
    # head's q and k are qk_nope_head_dim values without position plus
    # qk_rope_head_dim rotated ones, and the rotated part of the key is
    # one vector a position, shared by all heads. Stated together or
    # not at all; n_kv_heads == n_heads (nothing is grouped)
    q_lora_rank: Optional[int] = None
    kv_lora_rank: Optional[int] = None
    qk_nope_head_dim: Optional[int] = None
    qk_rope_head_dim: Optional[int] = None
    v_head_dim: Optional[int] = None
    # the mixer of an "ssm" layer (Mamba-2; ops/ssm.py): ssm_heads
    # heads of ssm_head_dim values, each carrying a state of
    # [ssm_head_dim, ssm_state] along the sequence; B and C are shared
    # by the heads of a group; a causal depthwise conv of ssm_conv taps
    # before the scan, which works in chunks of ssm_chunk positions; the
    # two projections have no bias. Stated together where block_pattern
    # has the kind
    ssm_heads: Optional[int] = None
    ssm_head_dim: Optional[int] = None
    ssm_state: Optional[int] = None
    ssm_groups: int = 1
    ssm_conv: int = 4
    ssm_chunk: int = 256
    ssm_conv_bias: bool = True

    activation: str = "silu"                # "silu" | "gelu_tanh"

    # Mixture-of-Experts (ops/moe.py). n_experts=0 → dense MLP. When >0,
    # every block's MLP becomes a top-k routed expert bank (Mixtral
    # pattern); experts shard over the `model` axis = expert parallelism
    # under GSPMD (SURVEY.md §2c row EP).
    n_experts: int = 0
    expert_top_k: int = 2
    # per-expert token capacity = capacity_factor * top_k * S / E
    # (GShard-style static capacity; overflow tokens drop to the
    # residual path). Read by the "softmax" router only
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.01           # Switch load-balance loss weight
    # "softmax": Mixtral (probabilities, static capacity with drops, the
    # Switch aux loss). "sigmoid": DeepSeek-V3 (independent scores, no
    # capacity and no drop, no aux loss; grouped products over the pairs
    # sorted by expert). "topk_softmax": Granite's (the same layer
    # without drops; the k largest logits are selected and a token's
    # weights are the softmax over those k logits)
    router: str = "softmax"
    router_bias: bool = False       # frozen [E] added for selection only
    router_renorm: bool = True      # weights / sum over the selected
    router_scale: float = 1.0       # then times this
    n_shared_experts: int = 0       # always-on SwiGLU beside the routed
    expert_d_ff: Optional[int] = None       # default d_ff
    # width of the shared expert where it is not a multiple of the
    # routed experts' (default n_shared_experts * expert_d_ff)
    shared_d_ff: Optional[int] = None
    # leading layers whose MLP is dense (width d_ff) before the routed
    # ones (DeepSeek-V3's first_k_dense_replace)
    n_dense_layers: int = 0
    # [lo, hi) of the n_experts whose weights this program holds (one
    # expert-parallel rank's share): the router still scores all
    # n_experts and a token's weights are normalised over all it
    # selected, but only pairs that land in the range are computed.
    # None holds all
    experts_held: Optional[Tuple[int, int]] = None
    # multi-token-prediction layers the checkpoint carries after the
    # decoder (DeepSeek-V3's num_nextn_predict_layers). Nothing here
    # runs them: training leaves them out (an SFT loss has no term for
    # them), and serving refuses a model that has them, since they are
    # its draft head (models/kvcache.py::require_decodable)
    n_mtp_layers: int = 0

    tie_embeddings: bool = False
    embed_scale: bool = False               # x *= sqrt(d_model) after embed
    # Granite's multipliers: x *= embed_multiplier after the embedding,
    # every sublayer's output times residual_multiplier before it is
    # added, logits / logits_scaling (attn_scale is the fourth)
    embed_multiplier: Optional[float] = None
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    attn_qkv_bias: bool = False             # Qwen-2: bias on q/k/v proj only
    norm_scale_plus_one: bool = False       # Gemma (1 + scale) RMSNorm
    post_block_norm: bool = False           # Gemma-2 post-attn/post-mlp norms
    attn_softcap: Optional[float] = None    # Gemma-2: 50.0
    logit_softcap: Optional[float] = None   # Gemma-2: 30.0
    attn_scale: Optional[float] = None      # override head_dim**-0.5

    # numerics / execution
    dtype: str = "bfloat16"                 # activation/compute dtype
    param_dtype: str = "float32"
    remat: bool = True                      # checkpoint each block
    # what the per-block checkpoint saves: "full" recomputes the whole
    # block in backward (lowest memory, +2N recompute FLOPs/token);
    # "dots" saves matmul outputs and recomputes only elementwise ops
    # (more memory, near-zero recompute) — worth ~1/3 higher arithmetic
    # throughput when activations fit HBM
    remat_policy: str = "full"              # "full" | "dots"
    attn_impl: str = "auto"     # "auto" | "xla" | "flash" | "ring" | "a2a"
    # "auto" resolves at trace time: flash (Pallas) on TPU, xla oracle off-TPU

    # pipeline schedule (models/pipeline.py): virtual stage groups per
    # device. 1 = plain shift buffer; v>1 = circular/interleaved (each
    # device owns v non-contiguous layer groups; see the pipeline module
    # docstring for the honest bubble table). Only read on pipe>1 meshes.
    pipe_virtual: int = 1

    def __post_init__(self):
        # keep the config hashable (jit static arg): dicts → sorted tuples
        if isinstance(self.rope_scaling, dict):
            object.__setattr__(self, "rope_scaling",
                               tuple(sorted(self.rope_scaling.items())))
        for name in ("block_pattern", "rope_kinds", "experts_held"):
            if isinstance(getattr(self, name), list):
                object.__setattr__(self, name, tuple(getattr(self, name)))
        if self.n_layers % len(self.block_pattern) != 0:
            raise ValueError(
                f"n_layers={self.n_layers} not divisible by block pattern "
                f"length {len(self.block_pattern)}")
        if self.n_heads % self.n_kv_heads != 0:
            raise ValueError("n_heads must be a multiple of n_kv_heads")
        unknown = set(self.block_pattern) - {"global", "sliding", "ssm"}
        if unknown:
            raise ValueError(f"unknown block kinds {unknown}; "
                             "valid: global, sliding, ssm")
        if "ssm" in self.block_pattern and not (
                self.ssm_heads and self.ssm_head_dim and self.ssm_state):
            raise ValueError(
                "block_pattern contains 'ssm': state ssm_heads, "
                "ssm_head_dim and ssm_state")
        if self.ssm_heads and self.ssm_heads % self.ssm_groups:
            raise ValueError("ssm_heads must be a multiple of ssm_groups")
        if "sliding" in self.block_pattern and self.sliding_window is None:
            raise ValueError("block_pattern contains 'sliding' but "
                             "sliding_window is None — that would silently "
                             "run full global attention")
        if self.attn_impl not in ("auto", "xla", "flash", "ring", "a2a"):
            raise ValueError(f"unknown attn_impl {self.attn_impl!r}")
        if self.remat_policy not in ("full", "dots"):
            raise ValueError(f"unknown remat_policy {self.remat_policy!r}")
        if self.pipe_virtual < 1:
            raise ValueError(f"pipe_virtual={self.pipe_virtual} must be >= 1")
        latent = (self.q_lora_rank, self.kv_lora_rank,
                  self.qk_nope_head_dim, self.qk_rope_head_dim,
                  self.v_head_dim)
        if any(latent) and not all(latent):
            raise ValueError(
                "latent attention states q_lora_rank, kv_lora_rank, "
                "qk_nope_head_dim, qk_rope_head_dim and v_head_dim "
                f"together; got {latent}")
        if self.latent_attention:
            qk = self.qk_nope_head_dim + self.qk_rope_head_dim
            if self.v_head_dim != qk or (self.head_dim or qk) != qk:
                raise ValueError(
                    f"latent attention with heads of {qk} for q and k and "
                    f"{self.v_head_dim} for v (head_dim={self.head_dim}): "
                    "the attention kernels take one head size")
            if self.n_kv_heads != self.n_heads or self.qk_norm \
                    or self.attn_qkv_bias:
                raise ValueError(
                    "a latent-attention layer has a key and a value head "
                    "for every query head, no per-head q/k norm and no "
                    "bias")
        if self.router not in ("softmax", "sigmoid", "topk_softmax"):
            raise ValueError(f"unknown router {self.router!r}")
        if (self.n_shared_experts or self.n_dense_layers
                or self.experts_held or self.router_bias) \
                and not self.dropless_router:
            raise ValueError(
                "shared experts, leading dense layers, a held range and "
                "a router bias belong to the layer that drops nothing "
                "(n_experts > 0, router='sigmoid' or 'topk_softmax')")
        if self.n_dense_layers > self.n_layers:
            raise ValueError("n_dense_layers exceeds n_layers")
        if (self.n_layers - self.prologue_layers) \
                % len(self.block_pattern) != 0:   # pragma: no cover
            raise ValueError("layers after the prologue do not fill "
                             "whole periods of block_pattern")
        lo, hi = self.held_range
        if self.n_experts and not 0 <= lo < hi <= self.n_experts:
            raise ValueError(f"experts_held={self.experts_held} is no "
                             f"range of {self.n_experts} experts")

    def to_dict(self) -> dict:
        """JSON-serializable form (offline converter sidecar files, the
        autotune registry's model digest). The fields of
        :data:`_LATER_FIELDS` are left out at their defaults, so that a
        model which does not use them keeps the digest it was recorded
        under; ``from_dict`` fills them in again."""
        defaults = {f.name: f.default for f in dataclasses.fields(self)}
        return {k: v for k, v in dataclasses.asdict(self).items()
                if k not in _LATER_FIELDS or v != defaults[k]}

    @staticmethod
    def from_dict(d: dict) -> "ModelConfig":
        d = dict(d)
        # JSON turns the normalized tuple-of-pairs rope_scaling into
        # lists; restore hashability before __post_init__ validation
        if isinstance(d.get("rope_scaling"), list):
            d["rope_scaling"] = tuple(
                tuple(x) for x in d["rope_scaling"])
        return ModelConfig(**d)

    @property
    def latent_attention(self) -> bool:
        return self.kv_lora_rank is not None

    @property
    def dropless_router(self) -> bool:
        """The routed layer of ``ops/moe.py::routed_experts``: no
        capacity, no drop, no aux loss, a held range of experts."""
        return self.n_experts > 0 and self.router != "softmax"

    def block_kind(self, layer: int) -> str:
        """The kind of layer ``layer`` ("global" | "sliding" | "ssm"):
        the prologue is whole periods, so the pattern is aligned to 0."""
        return self.block_pattern[layer % len(self.block_pattern)]

    @property
    def n_ssm_layers(self) -> int:
        return sum(self.block_kind(i) == "ssm"
                   for i in range(self.n_layers))

    @property
    def ssm_inner(self) -> int:
        """Values a position the mixer's heads carry (d_inner)."""
        return (self.ssm_heads or 0) * (self.ssm_head_dim or 0)

    @property
    def ssm_conv_dim(self) -> int:
        """Columns the conv runs over: x, then B and C of every group."""
        return self.ssm_inner + 2 * self.ssm_groups * (self.ssm_state or 0)

    def ssm_leaf_shapes(self) -> dict:
        """``{leaf: shape}`` of one state-space layer's mixer, in
        creation order. ``in_proj``'s columns are ``[z | x | B | C |
        dt]``."""
        H, C = self.ssm_heads, self.ssm_conv_dim
        out = {"in_proj": (self.d_model, self.ssm_inner + C + H),
               "conv_w": (C, self.ssm_conv)}
        if self.ssm_conv_bias:
            out["conv_b"] = (C,)
        out.update(dt_bias=(H,), a_log=(H,), d_skip=(H,),
                   ssm_norm=(self.ssm_inner,),
                   out_proj=(self.ssm_inner, self.d_model))
        return out

    @property
    def resolved_head_dim(self) -> int:
        """The one head size of q, k and v as the attention sees them
        (a latent layer's: the assembled no-rotary + rotary parts)."""
        if self.latent_attention:
            return self.qk_nope_head_dim + self.qk_rope_head_dim
        return self.head_dim or self.d_model // self.n_heads

    @property
    def rope_dim(self) -> int:
        """Values of a head that the rotary embedding turns."""
        return self.qk_rope_head_dim if self.latent_attention \
            else self.resolved_head_dim

    def attn_leaf_shapes(self) -> dict:
        """``{leaf: (d_in, d_out)}`` of one layer's attention matrices,
        in creation order."""
        hd, D, H = self.resolved_head_dim, self.d_model, self.n_heads
        if not self.latent_attention:
            kv = self.n_kv_heads * hd
            return {"wq": (D, H * hd), "wk": (D, kv), "wv": (D, kv),
                    "wo": (H * hd, D)}
        return {"wq_a": (D, self.q_lora_rank),
                "wq_b": (self.q_lora_rank, H * hd),
                "wkv_a": (D, self.kv_lora_rank + self.qk_rope_head_dim),
                "wkv_b": (self.kv_lora_rank,
                          H * (self.qk_nope_head_dim + self.v_head_dim)),
                "wo": (H * self.v_head_dim, D)}

    @property
    def resolved_attn_impl(self) -> str:
        if self.attn_impl != "auto":
            return self.attn_impl
        from gke_ray_train_tpu.parallel.mesh import on_tpu
        return "flash" if on_tpu() else "xla"

    @property
    def prologue_layers(self) -> int:
        """Leading layers that run before the scan, one by one: the
        dense-MLP layers, rounded up to whole periods of the block
        pattern so that the scanned periods stay aligned to it."""
        period = len(self.block_pattern)
        return -(-self.n_dense_layers // period) * period

    @property
    def n_repeats(self) -> int:
        """Scanned periods (every layer of a model with no prologue)."""
        return (self.n_layers - self.prologue_layers) \
            // len(self.block_pattern)

    def mlp_kind(self, layer: int) -> str:
        """"dense" | "moe" for the MLP of layer ``layer``."""
        return "moe" if self.n_experts and layer >= self.n_dense_layers \
            else "dense"

    @property
    def scan_mlp_kind(self) -> str:
        """The MLP of every scanned layer (the dense ones lead)."""
        return self.mlp_kind(self.n_layers - 1)

    @property
    def resolved_expert_d_ff(self) -> int:
        return self.expert_d_ff or self.d_ff

    @property
    def resolved_shared_d_ff(self) -> int:
        return self.shared_d_ff \
            or self.n_shared_experts * self.resolved_expert_d_ff

    @property
    def held_range(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.n_experts)

    @property
    def n_experts_held(self) -> int:
        lo, hi = self.held_range
        return hi - lo if self.n_experts else 0

    def param_count(self) -> int:
        """Exact TOTAL param count (storage truth; for MoE this counts
        every expert held). MFU math uses active_param_count()."""
        return self._count_params(self.n_experts_held)

    def active_param_count(self) -> float:
        """Params touched per token: for MoE, the router, the shared
        expert and the top-k experts only — the FLOP-relevant count
        (train/metrics.py). Where only a range of the experts is held,
        a token meets ``top_k * held / n_experts`` of them here on
        average, and that is what is counted."""
        if not self.n_experts:
            return self._count_params(0)
        k = min(self.expert_top_k, self.n_experts)
        return self._count_params(
            k * self.n_experts_held / self.n_experts
            if self.experts_held else k)

    def _count_params(self, experts_counted) -> int:
        hd = self.resolved_head_dim
        attn = sum(a * b for a, b in self.attn_leaf_shapes().values())
        if self.latent_attention:
            attn += self.q_lora_rank + self.kv_lora_rank   # latent norms
        if self.attn_qkv_bias:
            attn += self.n_heads * hd + 2 * self.n_kv_heads * hd
        if self.qk_norm:
            attn += 2 * hd
        ffn = 3 * self.d_model * self.d_ff
        n_moe = self.n_layers - self.n_dense_layers if self.n_experts else 0
        moe = (self.d_model * self.n_experts               # router
               + (self.n_experts if self.router_bias else 0)
               + experts_counted * 3 * self.d_model
               * self.resolved_expert_d_ff
               + 3 * self.d_model * self.resolved_shared_d_ff)
        norms = 2 * self.d_model + (2 * self.d_model if self.post_block_norm
                                    else 0)
        embed = self.vocab_size * self.d_model
        head = 0 if self.tie_embeddings else self.vocab_size * self.d_model
        n_ssm = self.n_ssm_layers
        ssm = sum(math.prod(shape)
                  for shape in self.ssm_leaf_shapes().values()) \
            if n_ssm else 0
        return ((self.n_layers - n_ssm) * attn + n_ssm * ssm
                + self.n_layers * norms + n_moe * moe
                + (self.n_layers - n_moe) * ffn
                + embed + head + self.d_model)


# ---------------------------------------------------------------------------
# Family presets. Shapes follow the public architecture descriptions of each
# model family (not any particular implementation).
# ---------------------------------------------------------------------------

_LLAMA31_SCALING = dict(factor=8.0, low_freq_factor=1.0, high_freq_factor=4.0,
                        original_max_position_embeddings=8192)


def llama2_7b(**kw) -> ModelConfig:
    """Llama-2-7B: MHA (no GQA), rope theta 1e4, 32k vocab — runs on
    the same decoder core with zero new mechanisms; HF tensor names are
    identical to Llama-3's, so interop needs nothing new either."""
    return ModelConfig(
        name="llama2-7b", vocab_size=32000, d_model=4096, n_layers=32,
        n_heads=32, n_kv_heads=32, d_ff=11008, max_seq_len=4096,
        rope_theta=10000.0,
        **kw)


def llama2_13b(**kw) -> ModelConfig:
    return ModelConfig(
        name="llama2-13b", vocab_size=32000, d_model=5120, n_layers=40,
        n_heads=40, n_kv_heads=40, d_ff=13824, max_seq_len=4096,
        rope_theta=10000.0,
        **kw)


def llama2_70b(**kw) -> ModelConfig:
    # the one GQA member of the Llama-2 family (n_kv_heads = 8)
    return ModelConfig(
        name="llama2-70b", vocab_size=32000, d_model=8192, n_layers=80,
        n_heads=64, n_kv_heads=8, d_ff=28672, max_seq_len=4096,
        rope_theta=10000.0,
        **kw)


def llama3_8b(**kw) -> ModelConfig:
    kw.setdefault("rope_scaling", _LLAMA31_SCALING)
    return ModelConfig(
        name="llama3-8b", vocab_size=128256, d_model=4096, n_layers=32,
        n_heads=32, n_kv_heads=8, d_ff=14336, max_seq_len=8192,
        rope_theta=500000.0,
        **kw)


def llama3_70b(**kw) -> ModelConfig:
    kw.setdefault("rope_scaling", _LLAMA31_SCALING)
    return ModelConfig(
        name="llama3-70b", vocab_size=128256, d_model=8192, n_layers=80,
        n_heads=64, n_kv_heads=8, d_ff=28672, max_seq_len=8192,
        rope_theta=500000.0,
        **kw)


def mistral_7b(**kw) -> ModelConfig:
    # vocab 32768 = the extended v0.3 tokenizer; pass vocab_size=32000 for
    # v0.1/v0.2 checkpoints
    kw.setdefault("vocab_size", 32768)
    return ModelConfig(
        name="mistral-7b", d_model=4096, n_layers=32,
        n_heads=32, n_kv_heads=8, d_ff=14336, max_seq_len=4096,
        rope_theta=10000.0, block_pattern=("sliding",), sliding_window=4096,
        **kw)


def mixtral_8x7b(**kw) -> ModelConfig:
    """Mixtral 8x7B: Mistral-7B dims with an 8-expert top-2 MoE MLP per
    layer (public architecture description; 47B total / ~13B active)."""
    return ModelConfig(
        name="mixtral-8x7b", vocab_size=32000, d_model=4096, n_layers=32,
        n_heads=32, n_kv_heads=8, d_ff=14336, max_seq_len=4096,
        rope_theta=1e6, n_experts=8, expert_top_k=2,
        **kw)


def k_exaone_236b(**kw) -> ModelConfig:
    """K-EXAONE-236B-A23B (LGAI-EXAONE, ``model_type`` exaone_moe) at
    its published sizes: 48 layers of which the first has a dense MLP
    and the rest 128 sigmoid-routed experts (8 a token, weights
    renormalised and scaled by 2.5, a frozen selection bias) beside one
    shared expert; attention in periods of three window-128 layers with
    rotary and one full layer without, RMSNorm on q and k per head.
    The multi-token-prediction layer of the checkpoint is not part of
    this decoder (ROADMAP R8). Keywords override: a deployment's share
    of it states its own ``n_layers``, ``experts_held``, ``vocab_size``."""
    published = dict(
        name="k-exaone-236b", vocab_size=153600, d_model=6144,
        n_layers=48, n_heads=64, n_kv_heads=8, head_dim=128, d_ff=18432,
        max_seq_len=262144, rope_theta=1e6, norm_eps=1e-5,
        block_pattern=("sliding", "sliding", "sliding", "global"),
        sliding_window=128, rope_kinds=("sliding",), qk_norm=True,
        n_experts=128, expert_top_k=8, expert_d_ff=2048,
        n_shared_experts=1, n_dense_layers=1, router="sigmoid",
        router_bias=True, router_renorm=True, router_scale=2.5,
        n_mtp_layers=1)
    return ModelConfig(**{**published, **kw})


def glm_4_7_flash(**kw) -> ModelConfig:
    """GLM-4.7-Flash (zai-org, ``model_type`` glm4_moe_lite; 30B-A3B) at
    its published sizes: 47 layers of latent attention (a query latent
    of 768 and a key/value latent of 512, 20 heads of 192 values
    without position + 64 rotated, values of 256; the rotated part of
    the key is one vector a position for all heads), the first layer
    with a dense MLP of 10240 and the rest with 64 sigmoid-routed
    experts of 1536 (4 a token, weights renormalised and scaled by 1.8,
    a frozen selection bias, one group) beside one shared expert. The
    multi-token-prediction layer of the checkpoint is not part of this
    decoder (ROADMAP R8). Keywords override: a deployment's share of it
    states its own ``experts_held`` and ``vocab_size``."""
    published = dict(
        name="glm-4.7-flash", vocab_size=154880, d_model=2048,
        n_layers=47, n_heads=20, n_kv_heads=20, d_ff=10240,
        max_seq_len=202752, rope_theta=1e6, norm_eps=1e-5,
        q_lora_rank=768, kv_lora_rank=512, qk_nope_head_dim=192,
        qk_rope_head_dim=64, v_head_dim=256,
        n_experts=64, expert_top_k=4, expert_d_ff=1536,
        n_shared_experts=1, n_dense_layers=1, router="sigmoid",
        router_bias=True, router_renorm=True, router_scale=1.8,
        n_mtp_layers=1)
    return ModelConfig(**{**published, **kw})


def granite_4_0_h_small(**kw) -> ModelConfig:
    """Granite-4.0-H-Small (ibm-granite, ``model_type`` granitemoehybrid;
    32B-A9B) at its published sizes: 40 layers in periods of ten, nine
    Mamba-2 mixers (128 heads of 64, a state of 128, one group, conv of
    4 taps with bias, chunks of 256) around one layer of grouped-query
    attention without positions (32 / 8 heads of 128, softmax scale
    1/128); every layer's MLP is 72 routed experts of 768 (the 10
    largest logits a token, weights their softmax, nothing dropped)
    beside a shared expert of 1536; the embedding times 12, every
    sublayer's output times 0.22, the tied head's logits over 16.
    Keywords override: a deployment's share of it states its own
    ``n_layers``, ``experts_held`` and ``vocab_size``."""
    published = dict(
        name="granite-4.0-h-small", vocab_size=100352, d_model=4096,
        n_layers=40, n_heads=32, n_kv_heads=8, d_ff=768,
        max_seq_len=131072, norm_eps=1e-5,
        block_pattern=("ssm",) * 5 + ("global",) + ("ssm",) * 4,
        rope_kinds=(), attn_scale=0.0078125,
        ssm_heads=128, ssm_head_dim=64, ssm_state=128, ssm_groups=1,
        ssm_conv=4, ssm_chunk=256, ssm_conv_bias=True,
        n_experts=72, expert_top_k=10, expert_d_ff=768,
        n_shared_experts=1, shared_d_ff=1536, router="topk_softmax",
        tie_embeddings=True, embed_multiplier=12.0,
        residual_multiplier=0.22, logits_scaling=16.0)
    return ModelConfig(**{**published, **kw})


def qwen2_7b(**kw) -> ModelConfig:
    """Qwen-2/2.5 7B: Llama-style GQA decoder whose one architectural
    delta is bias on the q/k/v projections (public architecture; the HF
    checkpoints carry q_proj.bias etc.)."""
    return ModelConfig(
        name="qwen2-7b", vocab_size=152064, d_model=3584, n_layers=28,
        n_heads=28, n_kv_heads=4, d_ff=18944, max_seq_len=32768,
        rope_theta=1e6, attn_qkv_bias=True, norm_eps=1e-6,
        **kw)


def gemma2_9b(**kw) -> ModelConfig:
    return ModelConfig(
        name="gemma2-9b", vocab_size=256128, d_model=3584, n_layers=42,
        n_heads=16, n_kv_heads=8, d_ff=14336, head_dim=256, max_seq_len=8192,
        rope_theta=10000.0, block_pattern=("sliding", "global"),
        sliding_window=4096, activation="gelu_tanh", tie_embeddings=True,
        embed_scale=True, norm_scale_plus_one=True, post_block_norm=True,
        attn_softcap=50.0, logit_softcap=30.0,
        attn_scale=256 ** -0.5,  # 9B query_pre_attn_scalar = head_dim = 256
        norm_eps=1e-6,
        **kw)


def basic_lm(vocab_size: int, *, d_model: int = 2048, n_layers: int = 24,
             n_heads: int = 16, d_ff: int = 8192, max_seq_len: int = 1024,
             **kw) -> ModelConfig:
    """The from-scratch pre-train model — capability parity with the
    reference's ~1.2B BasicLLM (2048d/24L/16H/8192ff,
    ray-jobs/pytorch_llm_ray.py:328-332), TPU-redesigned: pre-LN RMSNorm +
    RoPE decoder rather than post-LN sinusoidal nn.TransformerEncoder."""
    return ModelConfig(
        name="basic-lm", vocab_size=vocab_size, d_model=d_model,
        n_layers=n_layers, n_heads=n_heads, n_kv_heads=n_heads, d_ff=d_ff,
        max_seq_len=max_seq_len, **kw)


def tiny(vocab_size: int = 256, **kw) -> ModelConfig:
    """Test-scale config (fits the 8-fake-device CPU mesh)."""
    defaults = dict(
        name="tiny", vocab_size=vocab_size, d_model=64, n_layers=2,
        n_heads=4, n_kv_heads=2, d_ff=128, max_seq_len=128,
        dtype="float32", param_dtype="float32", remat=False)
    defaults.update(kw)
    return ModelConfig(**defaults)


PRESETS = {
    "llama2-7b": llama2_7b,
    "llama2-13b": llama2_13b,
    "llama2-70b": llama2_70b,
    "llama3-8b": llama3_8b,
    "llama3-70b": llama3_70b,
    "mistral-7b": mistral_7b,
    "mixtral-8x7b": mixtral_8x7b,
    "k-exaone-236b": k_exaone_236b,
    "glm-4.7-flash": glm_4_7_flash,
    "granite-4.0-h-small": granite_4_0_h_small,
    "gemma2-9b": gemma2_9b,
    "qwen2-7b": qwen2_7b,
}


def preset_for_model_id(model_id: str, **kw) -> ModelConfig:
    """Map an HF-style MODEL_ID (fine_tune_config.json key) to a preset."""
    mid = model_id.lower()
    is_31 = any(t in mid for t in ("llama-3.1", "llama-3_1", "llama3.1"))
    if "llama-2" in mid or "llama2" in mid:
        if "70b" in mid:
            return llama2_70b(**kw)
        if "13b" in mid:
            return llama2_13b(**kw)
        return llama2_7b(**kw)
    if "llama-3" in mid or "llama3" in mid:
        fn = llama3_70b if "70b" in mid else llama3_8b
        # NTK rope scaling is a Llama-3.1 feature; plain Llama-3
        # checkpoints were trained without it
        kw.setdefault("rope_scaling", _LLAMA31_SCALING if is_31 else None)
        return fn(**kw)
    if "mixtral" in mid:
        return mixtral_8x7b(**kw)
    if "exaone" in mid:
        return k_exaone_236b(**kw)
    if "glm-4.7-flash" in mid:
        return glm_4_7_flash(**kw)
    if "granite-4.0-h-small" in mid:
        return granite_4_0_h_small(**kw)
    if "mistral" in mid:
        if any(t in mid for t in ("v0.1", "v0.2")):
            kw.setdefault("vocab_size", 32000)
        return mistral_7b(**kw)
    if "gemma-2" in mid or "gemma2" in mid:
        return gemma2_9b(**kw)
    if "qwen" in mid:
        return qwen2_7b(**kw)
    raise ValueError(f"no preset for MODEL_ID={model_id!r}; "
                     f"known families: {sorted(PRESETS)}")

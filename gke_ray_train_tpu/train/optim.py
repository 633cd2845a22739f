"""Optimizer & LR schedule.

Behavioral parity with the reference's hand-rolled loop
(ray-jobs/pytorch_llm_ray.py:236-258): AdamW(lr, weight_decay=0.01),
linear warmup over 5% of total steps, cosine decay to 1% of base LR,
global-norm gradient clipping at 1.0 (:277-279). The bitsandbytes
``paged_adamw_32bit`` of the fine-tune path (fine_tune_config.json:17) has
no TPU analogue and needs none: optimizer state is GSPMD-sharded over the
``fsdp`` axis via the same specs as the params, so memory paging is
replaced by sharding (SURVEY.md row D5).
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import optax

from gke_ray_train_tpu.obs.trace import scope


def warmup_cosine_schedule(base_lr: float, total_steps: int, *,
                           warmup_frac: float = 0.05,
                           min_lr_frac: float = 0.01) -> optax.Schedule:
    """Reference schedule (pytorch_llm_ray.py:243-252): 5% linear warmup
    from 0, cosine to min_lr_frac * base_lr."""
    warmup_steps = max(1, int(total_steps * warmup_frac))
    return optax.warmup_cosine_decay_schedule(
        init_value=0.0,
        peak_value=base_lr,
        warmup_steps=warmup_steps,
        decay_steps=max(total_steps, warmup_steps + 1),
        end_value=base_lr * min_lr_frac,
    )


# leaves AdamW must not decay: norm scales and projection biases. Keyed
# by NAME because the stacked block layout makes norm scales [R, D] and
# biases [R, dim] — an ndim>=2 test wrongly classified them as matrices
# (the pre-r5 mask decayed stacked norm scales despite its docstring).
_NO_DECAY_KEYS = frozenset({
    "attn_norm", "mlp_norm", "attn_post_norm", "mlp_post_norm",
    "final_norm", "bq", "bk", "bv"})


def default_weight_decay_mask(params: Any) -> Any:
    """Decay only weight matrices — norm scales and biases are excluded.

    (Deviation from the reference, which lets torch AdamW decay
    everything; decaying RMSNorm scales toward zero is simply wrong for
    pre-LN transformers, so we fix it rather than port it.)
    """
    def decay(path, p):
        key = next((e.key for e in reversed(path) if hasattr(e, "key")),
                   None)
        return key not in _NO_DECAY_KEYS and p.ndim >= 2

    return jax.tree_util.tree_map_with_path(decay, params)


def clip_by_global_norm(clip_norm: float) -> optax.GradientTransformation:
    """``optax.clip_by_global_norm`` whose device ops carry the ``clip``
    scope (obs/trace.py), so the profile tells the norm's reduction
    from the AdamW update."""
    tx = optax.clip_by_global_norm(clip_norm)

    def update(updates, state, params=None):
        with scope("clip"):
            return tx.update(updates, state, params)
    return optax.GradientTransformation(tx.init, update)


def make_optimizer(schedule: optax.Schedule | float, *,
                   weight_decay: float = 0.01,
                   clip_norm: Optional[float] = 1.0,
                   b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8,
                   weight_decay_mask: Optional[Callable] = None,
                   ) -> optax.GradientTransformation:
    txs = []
    if clip_norm is not None:
        txs.append(clip_by_global_norm(clip_norm))
    txs.append(optax.adamw(
        schedule, b1=b1, b2=b2, eps=eps, weight_decay=weight_decay,
        mask=weight_decay_mask or default_weight_decay_mask))
    return optax.chain(*txs)

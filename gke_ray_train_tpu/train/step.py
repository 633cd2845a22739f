"""The jitted train step — the visible, hackable hot loop.

This is the TPU re-design of the only training loop whose internals the
reference exposes (ray-jobs/pytorch_llm_ray.py:270-284: zero_grad →
forward → CrossEntropyLoss(flattened) → backward (DDP all-reduce) →
clip_grad_norm(1.0) → step → sched.step), plus the grad-accumulation the
fine-tune path gets from HF Trainer (fine_tune_config.json:14).

TPU-first differences:
- One jitted function does microbatch scan + loss + grad + clip + update;
  gradient sync is *implicit* — GSPMD inserts the psum/reduce-scatter the
  sharding specs imply (no DDP hooks, SURVEY.md row D4).
- Grad accumulation is ``lax.scan`` over microbatches inside the step
  (no python-side loop, no re-dispatch per microbatch).
- Loss is token-weighted (padding/prompt masking), accumulated exactly:
  grads of the nll *sum* are averaged by total token weight at the end.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from gke_ray_train_tpu.models.config import ModelConfig
from gke_ray_train_tpu.models.transformer import (
    Params, forward, init_params, param_specs)
from gke_ray_train_tpu.obs.trace import region, scope
from gke_ray_train_tpu.ops.moe import COUNTERS, stats_merge
from gke_ray_train_tpu.parallel.mesh import BATCH_AXES
from gke_ray_train_tpu.parallel.sharding import tree_shardings
from gke_ray_train_tpu.train.lora import LoraConfig, init_lora, lora_specs
from gke_ray_train_tpu.train.remat import StepRemat, shard_bytes

Batch = Dict[str, jnp.ndarray]

# trees at or under this many bytes init EAGERLY and are device_put onto
# the mesh (bitwise-identical to the plain path, zero init-program
# compiles); larger trees take the jitted sharded init (see
# make_train_state's docstring)
_EAGER_INIT_LIMIT = 256 * 2**20


class TrainState(NamedTuple):
    params: Params
    lora: Optional[Params]       # None unless LoRA mode
    opt_state: Any
    step: jnp.ndarray            # int32 scalar


def token_nll(logits: jnp.ndarray, targets: jnp.ndarray,
              weights: jnp.ndarray):
    """Sum of weighted token NLL + sum of weights (exact-mean bookkeeping).

    fp32 math regardless of compute dtype — same reduction the
    reference gets from CrossEntropyLoss over flattened logits
    (pytorch_llm_ray.py:233,275). Formulated as logsumexp(logits) -
    logits[target] rather than log_softmax + gather: identical values,
    but the [B, S, V] log-probability array (1 GB at 8B's 128k vocab)
    is never materialized — backward recomputes the softmax from the
    logits it already holds."""
    with scope("loss"):
        logits32 = logits.astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits32, axis=-1)
        tgt = jnp.take_along_axis(logits32, targets[..., None],
                                  axis=-1)[..., 0]
        w = weights.astype(jnp.float32)
        return jnp.sum((lse - tgt) * w), jnp.sum(w)


def opt_state_specs(optimizer: optax.GradientTransformation,
                    trainable_shapes: Any, trainable_specs: Any) -> Any:
    """PartitionSpec tree for an optax state: any subtree whose structure
    equals the trainable pytree (mu, nu, trace, ...) inherits the
    trainable's specs; every other leaf (counts, scalars) replicates.
    This is what makes optimizer state ZeRO-sharded by construction
    (SURVEY.md row D5)."""
    target_def = jax.tree.structure(trainable_shapes)

    def rec(node):
        if jax.tree.structure(node) == target_def and \
                jax.tree.leaves(node):
            return trainable_specs
        if hasattr(node, "_fields"):  # NamedTuple optax states
            return type(node)(*[rec(getattr(node, f)) for f in node._fields])
        if isinstance(node, (list, tuple)):
            return type(node)(rec(c) for c in node)
        if isinstance(node, dict):
            return {k: rec(v) for k, v in node.items()}
        return P()

    return rec(jax.eval_shape(optimizer.init, trainable_shapes))


def make_train_state(cfg: ModelConfig, optimizer: optax.GradientTransformation,
                     key: jax.Array, *, mesh: Optional[Mesh] = None,
                     lora_cfg: Optional[LoraConfig] = None,
                     params: Optional[Params] = None) -> TrainState:
    """Initialize params (sharded at creation when a mesh is given — an 8B
    fp32 init must never materialize on one host) and optimizer state.

    ``params``: pass pre-built weights (hub-loaded, quantized) to skip
    the random init entirely — without this, a QLoRA caller substituting
    its own base would still materialize the full fp32 tree here first
    and OOM a single chip at 8B dims.

    Optimizer state shardings are *propagated* from param shardings by
    jitting optimizer.init — mu/nu inherit the fsdp sharding, scalars
    replicate. This is the ZeRO analogue (SURVEY.md row D5).

    Meshed init is SHARDING-INVARIANT: the meshed and plain paths — and
    any two elastic topologies — produce IDENTICAL values from the same
    key (the pipeline/moe matches-plain oracles rely on it; under
    non-partitionable threefry a jitted draw's values otherwise CHANGE
    with its out_shardings). Small trees init eagerly and are placed
    with ``device_put`` — plain-path-identical by construction, and no
    init program to compile; trees past ``_EAGER_INIT_LIMIT`` (an 8B
    fp32 init must never materialize on one host) take the jitted
    sharded path under ``sharding_invariant_rng``.

    On the record as one ``state_build`` region (obs/trace.py; recorded
    always: it runs once, before any step), with the bytes one device
    holds of the state it made."""
    with region("state_build") as built:
        state = _make_train_state(cfg, optimizer, key, mesh=mesh,
                                  lora_cfg=lora_cfg, params=params)
        built.attrs["args_bytes"] = shard_bytes(state)
    return state


def _make_train_state(cfg, optimizer, key, *, mesh, lora_cfg,
                      params) -> TrainState:
    from gke_ray_train_tpu.parallel.sharding import (
        shard_tree, sharding_invariant_rng)

    def tree_bytes(shapes) -> int:
        return sum(x.size * x.dtype.itemsize
                   for x in jax.tree.leaves(shapes))

    if params is None:
        if mesh is not None:
            abstract = jax.eval_shape(lambda k: init_params(cfg, k), key)
            if tree_bytes(abstract) <= _EAGER_INIT_LIMIT:
                params = shard_tree(init_params(cfg, key), mesh,
                                    param_specs(cfg))
            else:
                with sharding_invariant_rng():
                    p_shard = tree_shardings(mesh, param_specs(cfg))
                    params = jax.jit(lambda k: init_params(cfg, k),
                                     out_shardings=p_shard)(key)
        else:
            params = init_params(cfg, key)

    lora = None
    if lora_cfg is not None:
        lkey = jax.random.fold_in(key, 1)
        if mesh is not None:
            abstract = jax.eval_shape(
                lambda k: init_lora(cfg, lora_cfg, k), lkey)
            if tree_bytes(abstract) <= _EAGER_INIT_LIMIT:
                lora = shard_tree(init_lora(cfg, lora_cfg, lkey), mesh,
                                  lora_specs(cfg, lora_cfg))
            else:
                with sharding_invariant_rng():
                    l_shard = tree_shardings(mesh,
                                             lora_specs(cfg, lora_cfg))
                    lora = jax.jit(lambda k: init_lora(cfg, lora_cfg, k),
                                   out_shardings=l_shard)(lkey)
        else:
            lora = init_lora(cfg, lora_cfg, lkey)

    trainable = lora if lora is not None else params
    step = jnp.zeros((), jnp.int32)
    if mesh is not None:
        # Explicit out_shardings for every optimizer-state leaf: jit
        # propagation alone leaves constants (adam count) and
        # replicated-param moments on a single device, which breaks the
        # jitted step after a checkpoint restore commits them there.
        t_specs = (lora_specs(cfg, lora_cfg) if lora is not None
                   else param_specs(cfg))
        os_specs = opt_state_specs(optimizer, trainable, t_specs)
        opt_state = jax.jit(optimizer.init,
                            out_shardings=tree_shardings(mesh, os_specs))(
            trainable)
        step = jax.device_put(step, NamedSharding(mesh, P()))
    else:
        opt_state = jax.jit(optimizer.init)(trainable)
    return TrainState(params=params, lora=lora, opt_state=opt_state,
                      step=step)


# sentinel: distinguishes "caller did not pass it" (plan supplies the
# value) from an explicit override
_UNSET: Any = object()


def make_train_step(cfg: ModelConfig,
                    optimizer: optax.GradientTransformation,
                    *,
                    mesh: Optional[Mesh] = None,
                    lora_cfg: Optional[LoraConfig] = None,
                    grad_accum: Any = _UNSET,
                    schedule: Optional[Callable] = None,
                    donate: Any = _UNSET,
                    donate_batch: Any = _UNSET,
                    pipe_microbatches: Any = _UNSET,
                    plan=None,
                    remat_keep: Tuple[str, ...] = ()
                    ) -> Callable[[TrainState, Batch], tuple]:
    """Build the jitted ``(state, batch) -> (state, metrics)`` function.

    ``plan``: an :class:`~gke_ray_train_tpu.plan.ExecutionPlan` — the
    declarative source for grad_accum / donation / pipeline
    microbatching (explicit kwargs still win), and the route through
    ``plan.compile_step_with_plan`` so training, the budgets and
    analysis share ONE compile surface.

    batch: dict with "inputs"/"targets" [B, S] int32, "weights" [B, S]
    float, optional "segment_ids"/"positions" [B, S]. B must be divisible
    by grad_accum; microbatches are scanned in sequence.

    ``donate_batch`` (with ``donate``): the batch argument is donated
    too — each step's device-resident batch buffers are freed eagerly
    instead of surviving until the Python reference dies. The input
    pipeline owns its own host copies and never re-feeds a placed batch
    (data/prefetch.py), so this is pure peak-memory headroom. Pass
    False when the SAME placed batch is fed repeatedly (the budget
    presets of perf/budget.py, analysis/jaxprcheck.py and the tests do)
    — a donated buffer must not be reused.

    ``pipe_microbatches``: pipeline microbatch count per forward when the
    mesh has a pipe axis > 1 (models/pipeline.py; default = stage count).

    ``remat_keep``: the named activations each checkpointed block saves
    beside its input (models/remat.py). A step that names none carries
    ``.remat`` (train/remat.py): the AOT build
    (``plan.py::compile_step_with_plan`` with abstract arguments) sizes
    from it the set that fits the device, and builds this step with
    that set in place of this one. The jitted step itself keeps what is
    named here and nothing else.
    """
    keep = tuple(remat_keep)
    # as passed, before the plan fills them in
    same_step = functools.partial(
        make_train_step, cfg, optimizer, mesh=mesh, lora_cfg=lora_cfg,
        grad_accum=grad_accum, schedule=schedule, donate=donate,
        donate_batch=donate_batch, pipe_microbatches=pipe_microbatches,
        plan=plan)
    if grad_accum is _UNSET:
        grad_accum = plan.grad_accum if plan is not None else 1
    if donate is _UNSET:
        donate = plan.donate_state if plan is not None else True
    if donate_batch is _UNSET:
        donate_batch = plan.donate_batch if plan is not None else True
    if pipe_microbatches is _UNSET:
        pipe_microbatches = (plan.pipe_microbatches or None) \
            if plan is not None else None
    lora_mode = lora_cfg is not None
    lora_dropout = lora_cfg.dropout if lora_mode else 0.0
    moe = cfg.n_experts > 0
    # the dropless routed layer counts its pairs (ops/moe.py); the
    # counts ride the micro-batch scan into the step's metrics
    counter_names = COUNTERS if cfg.dropless_router else ()
    overlap = plan.overlap if plan is not None else "off"
    fused_ops = plan.fused_ops if plan is not None else False
    # fused cross-entropy (ops/fused_ce.py) replaces materialized
    # logits + token_nll where its contract holds: no logit softcap
    # (the cap is applied to logits the kernel never forms) and no
    # pipeline mesh (the stage-folded batch spec is not the kernel
    # wrapper's row layout)
    fused_ce = (fused_ops and cfg.logit_softcap is None
                and (mesh is None or int(mesh.shape.get("pipe", 1)) == 1))

    manual_grad = None
    if overlap == "manual":
        from gke_ray_train_tpu.train.overlap import (
            check_manual_support, make_manual_grad_fn)
        check_manual_support(cfg, mesh, lora=lora_mode)
        manual_grad = make_manual_grad_fn(
            cfg, mesh,
            batch_keys=(plan.batch_keys() if plan is not None
                        else ("inputs", "targets", "weights")),
            fused_ops=fused_ops, use_fused_ce=fused_ce,
            # DCN-aware gradient sync (parallel/hierarchical.py): on a
            # multi-slice plan the reduction stages at the slice
            # boundary; DCN_SYNC picks the cross-slice payload and
            # DCN_COMPRESS=bf16 casts the hier hop with error feedback
            num_slices=plan.num_slices if plan is not None else 1,
            dcn_sync=plan.dcn_sync if plan is not None else "flat",
            dcn_compress=(plan.dcn_compress if plan is not None
                          else "none"),
            remat_keep=keep)

    def micro_loss(trainable: Params, frozen: Params, micro: Batch,
                   drop_rng=None):
        fkw = dict(positions=micro.get("positions"),
                   segment_ids=micro.get("segment_ids"),
                   mesh=mesh, pipe_microbatches=pipe_microbatches,
                   with_aux=moe,
                   token_weights=micro["weights"] if moe else None,
                   fused_ops=fused_ops,
                   return_pre_unembed=fused_ce, remat_keep=keep)
        if lora_mode:
            out = forward(frozen, micro["inputs"], cfg, lora=trainable,
                          lora_scale=lora_cfg.scale,
                          lora_dropout=lora_dropout,
                          lora_rng=drop_rng, **fkw)
        else:
            out = forward(trainable, micro["inputs"], cfg, **fkw)
        hidden, aux = out if moe else (out, {})
        if fused_ce:
            from gke_ray_train_tpu.models.transformer import unembed_head
            from gke_ray_train_tpu.ops.fused_ce import fused_cross_entropy
            dtype = jnp.dtype(cfg.dtype)
            # the head must come from the DIFFERENTIATED arg in full
            # fine-tuning (trainable == params is argnum 0 of grad_fn;
            # taking it from `frozen` would silently zero the lm_head /
            # tied-embed gradient). LoRA keeps the frozen base head —
            # adapters never train the unembedding.
            head_params = frozen if lora_mode else trainable
            with scope("loss"):
                nll, w = fused_cross_entropy(
                    hidden, unembed_head(head_params, cfg).astype(dtype),
                    micro["targets"], micro["weights"], mesh=mesh)
        else:
            nll, w = token_nll(hidden, micro["targets"], micro["weights"])
        if "router_aux" in aux:
            # Switch load-balance term, billed per token so the final
            # divide-by-total-weight recovers ce_mean + coef * aux_mean
            nll = nll + cfg.router_aux_coef * aux.pop("router_aux") * w
        # what is left of aux: the routed layer's counters (ops/moe.py)
        return nll, (w, aux)

    def train_step(state: TrainState, batch: Batch):
        trainable = state.lora if lora_mode else state.params
        frozen = state.params

        def reshape(x):
            return x.reshape((grad_accum, x.shape[0] // grad_accum)
                             + x.shape[1:])
        micros = jax.tree.map(reshape, batch)

        grad_fn = jax.value_and_grad(micro_loss, has_aux=True)

        # LoRA dropout rng: deterministic per (step, microbatch) — derived
        # from the step counter so resume reproduces the same masks and
        # the step fn keeps its (state, batch) signature
        drop_rngs = None
        if lora_mode and lora_dropout > 0.0:
            drop_rngs = jax.random.split(
                jax.random.fold_in(jax.random.key(0), state.step),
                grad_accum)

        dcn_residual = manual_grad is not None \
            and getattr(manual_grad, "compressed", False)

        def accum(carry, xs):
            micro = xs[0]
            drop_rng = xs[1] if drop_rngs is not None else None
            if dcn_residual:
                g_acc, nll_acc, w_acc, _, resid = carry
                # compressed DCN hop with error feedback: microbatch
                # k's bf16 quantization residual feeds microbatch
                # k+1's pre-quantization value (train/overlap.py);
                # the step-final residual is dropped with the carry
                (nll, w), g, resid = manual_grad(trainable, micro,
                                                 resid)
                return (jax.tree.map(jnp.add, g_acc, g),
                        nll_acc + nll, w_acc + w, {}, resid), None
            g_acc, nll_acc, w_acc, counters = carry
            if manual_grad is not None:
                # the shard_map microbatch pipeline (train/overlap.py):
                # per-layer fsdp all-gathers double-buffered behind
                # compute, grads reduced with GSPMD's exact
                # accumulation structure — bitwise-identical to the
                # grad_fn branch, asserted by tests/test_overlap.py
                (nll, w), g = manual_grad(trainable, micro)
            else:
                (nll, (w, seen)), g = grad_fn(trainable, frozen, micro,
                                              drop_rng)
                counters = stats_merge(counters, seen)
            return (jax.tree.map(jnp.add, g_acc, g),
                    nll_acc + nll, w_acc + w, counters), None

        zeros = jax.tree.map(jnp.zeros_like, trainable)
        scan_xs = (micros,) if drop_rngs is None else (micros, drop_rngs)
        carry0 = (zeros, jnp.zeros((), jnp.float32),
                  jnp.zeros((), jnp.float32),
                  {n: jnp.zeros((), jnp.float32) for n in counter_names})
        if dcn_residual:
            # the residual is params-shaped (sharded leaves carry the
            # DCN-hop error at local-shard granularity) and zeroed per
            # step — no TrainState change, no checkpoint-layout change
            carry0 = carry0 + (jax.tree.map(jnp.zeros_like, trainable),)
        (g_sum, nll_sum, w_sum, counters, *_), _ = jax.lax.scan(
            accum, carry0, scan_xs)

        with scope("optimizer"):
            inv_w = jnp.where(w_sum > 0, 1.0 / w_sum, 0.0)
            grads = jax.tree.map(lambda g: (g * inv_w).astype(g.dtype),
                                 g_sum)
            loss = nll_sum * inv_w
            updates, new_opt = optimizer.update(grads, state.opt_state,
                                                trainable)
            new_trainable = optax.apply_updates(trainable, updates)

        new_state = TrainState(
            params=state.params if lora_mode else new_trainable,
            lora=new_trainable if lora_mode else None,
            opt_state=new_opt,
            step=state.step + 1,
        )
        with scope("clip"):
            # the same reduction the optimizer's clip runs (XLA merges
            # the two), so it carries the same name
            grad_norm = optax.global_norm(grads)
        metrics = {"loss": loss, "grad_norm": grad_norm, "tokens": w_sum,
                   **counters}
        if schedule is not None:
            metrics["learning_rate"] = schedule(state.step)
        return new_state, metrics

    argnums = (0, 1) if (donate and donate_batch) else \
        ((0,) if donate else ())
    if plan is not None:
        # one compile surface: plan-routed steps jit through
        # compile_step_with_plan (which also tags donate_argnums)
        from gke_ray_train_tpu.plan import compile_step_with_plan
        fn = compile_step_with_plan(plan, mesh, train_step,
                                    donate_argnums=argnums)
    else:
        fn = jax.jit(train_step, donate_argnums=argnums)
        try:
            # introspection hook for tests/tooling: jit wrappers do not
            # expose their donate_argnums publicly
            fn.donate_argnums = argnums
        except (AttributeError, TypeError):  # pragma: no cover - frozen
            pass
    if not keep and cfg.remat and cfg.remat_policy == "full":
        fn.remat = StepRemat(
            cfg=cfg, mesh=mesh, grad_accum=grad_accum, lora=lora_mode,
            with_keep=lambda names: same_step(remat_keep=names))
    return fn


def make_eval_step(cfg: ModelConfig, *, mesh: Optional[Mesh] = None,
                   lora_cfg: Optional[LoraConfig] = None,
                   pipe_microbatches: Optional[int] = None,
                   batch_shardings: Optional[Dict[str, Any]] = None):
    """(state, batch) -> summed (nll, weight) — callers aggregate across
    batches/hosts then divide (exact eval loss, SURVEY.md §5.5).

    ``batch_shardings``: explicit per-key input shardings for the batch
    (the same :func:`batch_shardings` contract as the train step). With
    them pinned, eval compiles ONCE for the declared layout — numpy
    rows, pre-placed arrays, or arrays committed elsewhere all dispatch
    into that one executable instead of retracing per distinct input
    layout, and on multi-host meshes the batch is batch-axis-sharded by
    construction rather than silently replicated."""
    lora_mode = lora_cfg is not None

    def eval_step(state: TrainState, batch: Batch):
        logits = forward(state.params, batch["inputs"], cfg,
                         positions=batch.get("positions"),
                         segment_ids=batch.get("segment_ids"),
                         mesh=mesh,
                         lora=state.lora if lora_mode else None,
                         lora_scale=lora_cfg.scale if lora_mode else 1.0,
                         pipe_microbatches=pipe_microbatches)
        return token_nll(logits, batch["targets"], batch["weights"])

    if batch_shardings is not None:
        # None = leave the state's shardings to propagate from the args
        jitted = jax.jit(eval_step,
                         in_shardings=(None, dict(batch_shardings)))

        def placed_eval_step(state: TrainState, batch: Batch):
            # host rows are placed before the call: a numpy leaf and an
            # array on the mesh have different avals (the mesh's axis
            # types ride in the type), so they would trace twice
            return jitted(state, {
                k: v if isinstance(v, jax.Array)
                else jax.device_put(v, batch_shardings[k])
                for k, v in batch.items()})

        placed_eval_step.lower = jitted.lower
        return placed_eval_step
    return jax.jit(eval_step)


def batch_shardings(mesh: Mesh, batch_keys=("inputs", "targets", "weights"),
                    *, context_sharded: bool = False) -> Dict[str, Any]:
    seq = "context" if context_sharded else None
    return {k: NamedSharding(mesh, P(BATCH_AXES, seq)) for k in batch_keys}

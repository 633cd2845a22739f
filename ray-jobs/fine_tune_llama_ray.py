"""LLM fine-tune entry point — TPU-native flagship job.

Capability parity with the reference fine-tune
(/root/reference/ray-jobs/fine_tune_llama_ray.py): submitted via
``ray job submit -- python ray-jobs/fine_tune_llama_ray.py``, reads
``ray-jobs/fine_tune_config.json`` (same UPPER_CASE keys + mesh keys,
SURVEY.md §5.6), runs a per-worker train fn on every TPU host, saves
merged/full weights in HF layout to shared storage, optionally runs the
base-vs-tuned inference comparison (§3.4).

What replaces what (SURVEY.md §2b):
- TorchTrainer/ScalingConfig        → rayint.JaxTrainer / ScalingConfig
- Accelerate + NCCL process group    → jax.distributed + GSPMD mesh
- BitsAndBytes NF4 QLoRA             → LoRA adapter pytree over an
  NF4/int8-quantized frozen base (ops/quant.py; QUANT_KIND config key)
- TRL SFTTrainer                     → jitted train step + host loop
- HF Trainer checkpoints             → orbax manager w/ retention + resume
"""

from __future__ import annotations

import json
import logging
import os
import sys
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

logging.basicConfig(level=logging.INFO,
                    format="%(asctime)s %(name)s: %(message)s")
logger = logging.getLogger("fine_tune")


def train_loop_per_worker(config: dict):
    """Runs on every TPU host (same shape as the reference's worker fn,
    fine_tune_llama_ray.py:198): train/eval/save, then the final
    export, the optional inference comparison and the optional serving
    smoke. The four parts are separate functions over one ``run``
    namespace so a caller with less host RAM or time than the 8B export
    needs (chip_smoke.py) can run the parts it can afford."""
    run = train_eval_save(config)
    export_final(run)
    compare_inference(run)
    serve_after_train(run)
    return run.metrics


def train_eval_save(config: dict) -> types.SimpleNamespace:
    """Everything up to and including ``run_training`` (prefetch, eval,
    checkpoints). Returns the run's live objects for the parts below."""
    import jax
    import numpy as np

    from gke_ray_train_tpu.ckpt import CheckpointManager, load_hf_checkpoint
    from gke_ray_train_tpu.data import (
        ByteTokenizer, downsample, load_hf_tokenizer, pad_sft_rows,
        pack_examples, sft_epoch_batches, synthetic_sql_rows,
        tokenize_sft_example, format_gretel_sql_example)
    from gke_ray_train_tpu.models import (
        init_params, param_specs, preset_for_model_id, tiny)
    from gke_ray_train_tpu.parallel.mesh import distributed_init
    from gke_ray_train_tpu.parallel.placement import (
        host_batch_size, input_shard_layout, make_place_batch)
    from gke_ray_train_tpu.parallel.sharding import tree_shardings
    from gke_ray_train_tpu.rayint import get_context
    from gke_ray_train_tpu.train import (
        LoraConfig, ThroughputMeter, make_train_state, make_train_step,
        make_eval_step)
    from gke_ray_train_tpu.train.loop import run_training
    from gke_ray_train_tpu.train.profiling import (
        apply_debug_flags, profiler_from_config)
    from gke_ray_train_tpu.train.tb import writer_from_config

    from gke_ray_train_tpu.config import (
        audit_config, cadence_from_config, optimizer_from_config,
        quant_kind_from_config, schedule_from_config)

    ctx = get_context()
    if ctx.is_host0():
        audit_config(config)   # §5.6: every key honored or warned, never
                               # silently dropped
    # ONE declarative ExecutionPlan (plan.py) resolves every execution
    # knob — mesh, batch shape, donation, prefetch, compile-once
    # policy, runtime guards — from the config (env fallback), and its
    # fingerprint identifies the run in cache dirs, AOT sidecar keys
    # and budget records
    from gke_ray_train_tpu.plan import ExecutionPlan, compile_step_with_plan
    plan = ExecutionPlan.resolve(config)
    apply_debug_flags(config)
    distributed_init()
    # elastic mesh re-formation (rayint/elastic.py): a shrunken/grown
    # pool re-resolves the plan on the survivors (data/fsdp reflowed,
    # global batch preserved, budget pin dropped) and the mesh is built
    # on exactly those devices; the checkpoint restore below reshards
    # from the logical spec. A no-op when ELASTIC is off.
    from gke_ray_train_tpu.rayint.elastic import maybe_replan
    plan, devices = maybe_replan(plan, config=config, log=logger)
    # tuned-plan overlay (autotune/registry.py): with AUTOTUNE=1,
    # overlay the registry hit keyed by (model digest, topology,
    # surface) onto the resolved plan — AFTER the replan so a reshard
    # re-keys the lookup, BEFORE the cache/mesh so everything compiles
    # the tuned program. Loud apply, loud refusal on drift.
    from gke_ray_train_tpu.autotune.registry import maybe_apply
    plan, _ = maybe_apply(plan, config=config, log=logger)
    # persistent XLA compile cache (perf/cache.py): restarts and peer
    # hosts reuse the compiled binary. The trainer already enabled it;
    # the repeat covers a bare call of this function and is a no-op
    from gke_ray_train_tpu.perf.cache import enable_persistent_cache
    enable_persistent_cache(plan=plan)
    mesh = plan.build_mesh(devices)
    n_hosts = max(jax.process_count(), 1)
    host = jax.process_index()
    smoke = bool(config.get("SMOKE_TEST", False))
    logger.info("worker %d/%d; %d devices; mesh %s; plan %s", host,
                n_hosts, len(devices), dict(mesh.shape),
                plan.fingerprint())

    # ---- tokenizer + model config ------------------------------------
    model_id = config["MODEL_ID"]
    hf_token = os.environ.get("HF_TOKEN")
    try:
        tokenizer = load_hf_tokenizer(model_id, hf_token)
    except Exception as e:
        logger.warning("HF tokenizer unavailable (%s); using ByteTokenizer",
                       type(e).__name__)
        tokenizer = ByteTokenizer()

    max_seq = plan.max_seq_len
    use_lora = bool(config.get("USE_QLORA", False))
    # frozen-base (Q)LoRA keeps unquantized leaves (embed/lm_head/norms)
    # in the compute dtype — fp32 embeddings alone add ~4 GB at 8B dims
    # and the base takes no optimizer update; full FT defaults to fp32
    # master params (reference: bf16 base via BNB_4BIT_COMPUTE_DTYPE)
    train_dtype = config.get("TRAIN_DTYPE", "bfloat16")
    param_dtype = config.get("PARAM_DTYPE",
                             train_dtype if use_lora else "float32")
    if smoke:
        # smoke keeps its fp32-by-default dtypes (CPU numerics), but an
        # explicit PARAM_DTYPE rehearses the flagship memory behavior
        # size the smoke model's depth to the pipeline: layers must
        # divide by pipe stages x virtual groups or the forward raises
        pipe_depth = (int(mesh.shape.get("pipe", 1))
                      * int(config.get("PIPE_VIRTUAL_STAGES", 1)))
        cfg = tiny(vocab_size=max(getattr(tokenizer, "vocab_size", 260), 260),
                   max_seq_len=max_seq, dtype=config.get("TRAIN_DTYPE",
                                                         "float32"),
                   param_dtype=config.get("PARAM_DTYPE", "float32"),
                   attn_impl=config.get("ATTN_IMPL", "auto"),
                   n_layers=max(2, pipe_depth))
    else:
        cfg = preset_for_model_id(
            model_id,
            dtype=train_dtype,
            param_dtype=param_dtype,
            attn_impl=config.get("ATTN_IMPL", "auto"),
            remat_policy=config.get("REMAT_POLICY", "full"))

    # ---- weights ------------------------------------------------------
    # resolution order (reference: from_pretrained(MODEL_ID),
    # fine_tune_llama_ray.py:240): explicit local dir → hub snapshot →
    # random init (smoke/offline only, with a loud warning). Every
    # branch decision is COLLECTIVE — hosts disagreeing on which branch
    # to take would deadlock in the first collective or train garbage.
    ckpt_dir = config.get("PRETRAINED_CHECKPOINT_DIR")
    have_local = bool(ckpt_dir and os.path.exists(str(ckpt_dir)))
    if n_hosts > 1:
        from jax.experimental import multihost_utils
        have_local = bool(int(multihost_utils.broadcast_one_to_all(
            np.asarray(1 if have_local else 0, np.int32))))
        if have_local and not (ckpt_dir and os.path.exists(str(ckpt_dir))):
            raise FileNotFoundError(
                f"host 0 sees PRETRAINED_CHECKPOINT_DIR={ckpt_dir} but "
                f"host {host} does not — put it on shared storage "
                "(/mnt/pvc)")
    if not have_local and not smoke:
        from gke_ray_train_tpu.ckpt.hub import acquire_pretrained
        # cache location comes from HF_HOME (the RayCluster CR mounts
        # /mnt/hf_cache there), read by huggingface_hub itself.
        # acquire_pretrained's fallback decision is itself collective.
        ckpt_dir = acquire_pretrained(model_id, token=hf_token,
                                      num_hosts=n_hosts, host_id=host)
        have_local = ckpt_dir is not None
    quant_kind = quant_kind_from_config(config, use_lora)
    load_quant = quant_kind if (use_lora and quant_kind != "none") else None
    already_quantized = False
    if have_local:
        # QLoRA bases quantize DURING the stream (one layer-slice on
        # device at a time) — 8B fits a single 16 GB chip this way, the
        # same shape as the reference's BitsAndBytesConfig load
        params = load_hf_checkpoint(str(ckpt_dir), cfg, mesh=mesh,
                                    quantize=load_quant)
        already_quantized = load_quant is not None
        logger.info("loaded pretrained weights from %s%s", ckpt_dir,
                    f" (quantized {load_quant} on load)" if load_quant
                    else "")
    else:
        if not smoke:
            logger.warning(
                "no local checkpoint and hub unreachable; initializing "
                "RANDOM weights (fine-tuning semantics require a "
                "pretrained checkpoint)")
        if load_quant is not None:
            # QLoRA random init quantizes DURING init (one repeat-slice
            # at a time, models/qinit.py) — full-dim 8B never
            # materializes fp32, so offline flagship-dims runs fit one
            # 16 GB chip just like the stream-load path
            from gke_ray_train_tpu.models.qinit import init_quantized_params
            params = init_quantized_params(cfg, jax.random.key(0),
                                           kind=load_quant, mesh=mesh)
            already_quantized = True
        else:
            p_shard = tree_shardings(mesh, param_specs(cfg))
            params = jax.jit(lambda k: init_params(cfg, k),
                             out_shardings=p_shard)(jax.random.key(0))

    # ---- dataset ------------------------------------------------------
    n_train = int(config.get("NUM_TRAIN_SAMPLES", 1000))
    n_eval = int(config.get("NUM_EVAL_SAMPLES", 200))
    try:
        from datasets import load_dataset
        ds_train = list(load_dataset(config["DATASET_NAME"], split="train"))
        ds_test = list(load_dataset(config["DATASET_NAME"], split="test"))
    except Exception as e:
        logger.warning("dataset hub unavailable (%s); synthetic SQL rows",
                       type(e).__name__)
        ds_train = synthetic_sql_rows(max(n_train, 64), seed=0)
        ds_test = synthetic_sql_rows(max(n_eval, 16), seed=1)
    # downsample-with-seed parity (reference :288-289)
    ds_train = downsample(ds_train, n_train)
    ds_test = downsample(ds_test, n_eval)

    def tokenize_rows(rows):
        return [tokenize_sft_example(
            tokenizer, format_gretel_sql_example(r), max_len=max_seq + 1)
            for r in rows]

    train_exs = tokenize_rows(ds_train)
    eval_exs = tokenize_rows(ds_test)
    n_dead = sum(1 for ex in train_exs if ex["loss_weights"].sum() == 0)
    if n_dead:
        logger.warning(
            "%d/%d train examples have ZERO trainable tokens — the prompt "
            "fills MAX_SEQ_LENGTH=%d and truncation drops the completion; "
            "raise MAX_SEQ_LENGTH or shorten prompts", n_dead,
            len(train_exs), max_seq)
    if n_dead == len(train_exs):
        raise ValueError("every train example truncated to zero trainable "
                         "tokens; training would silently learn nothing")

    per_device_batch = plan.per_device_batch
    grad_accum = plan.grad_accum
    data_par = mesh.shape["data"] * mesh.shape["fsdp"]
    global_batch = per_device_batch * data_par * grad_accum
    # input partitioning follows the mesh, not process_count: hosts
    # spanned by model/context axes feed identical rows (placement.py)
    in_shards, in_shard_id = input_shard_layout(mesh)
    host_batch = host_batch_size(global_batch, num_shards=in_shards)

    packing = plan.packing
    if packing:
        packed = list(pack_examples(train_exs, max_seq))
        train_rows = {k: np.stack([r[k] for r in packed])
                      for k in packed[0]}
    else:
        train_rows = pad_sft_rows(train_exs, max_seq)
    eval_rows = pad_sft_rows(eval_exs, max_seq)

    # ceil: the final partial batch trains too (sft_epoch_batches keeps
    # the tail as a zero-weight-padded batch, HF drop_last=False parity)
    steps_per_epoch = max(
        -(-len(train_rows["inputs"]) // global_batch), 1)
    epochs = int(config.get("NUM_TRAIN_EPOCHS", 1))
    total_steps = steps_per_epoch * epochs

    # ---- optimizer / adapters ----------------------------------------
    lora_cfg = LoraConfig.from_dict(config) if use_lora else None
    # OPTIM / LR_SCHEDULER_TYPE honored (config.py; reference
    # fine_tune_config.json:15-17)
    schedule = schedule_from_config(config, total_steps)
    opt = optimizer_from_config(config, schedule)
    # QLoRA = LoRA adapters over a *quantized* frozen base (the
    # reference's BitsAndBytesConfig 4-bit NF4 load,
    # fine_tune_llama_ray.py:216-227) — here a pytree transform
    # (ops/quant.py), dequantized inside the jitted forward.
    if use_lora and quant_kind != "none" and not already_quantized:
        from gke_ray_train_tpu.ops.quant import quantize_params
        params = quantize_params(params, kind=quant_kind)
        logger.info("quantized frozen base weights to %s", quant_kind)
    # hand the acquired weights in — make_train_state must NOT random-init
    # its own full fp32 tree first (at 8B dims that alone OOMs one chip)
    state = make_train_state(cfg, opt, jax.random.key(1), mesh=mesh,
                             lora_cfg=lora_cfg, params=params)

    # pipeline-parallel meshes (MESH_PIPE>1) microbatch each forward;
    # 0/unset = default (one microbatch per stage) — all plan-resolved
    pipe_micro = plan.pipe_microbatches or None
    if "PIPE_VIRTUAL_STAGES" in config:
        import dataclasses as _dc
        # invalid values (0, negatives) must fail ModelConfig validation,
        # not silently fall back to the shift schedule
        cfg = _dc.replace(cfg, pipe_virtual=plan.pipe_virtual_stages)
    # grad_accum / donation / pipe microbatching come from the plan —
    # make_train_step routes through the one compile surface
    # (plan.compile_step_with_plan)
    step_fn = make_train_step(cfg, opt, mesh=mesh, lora_cfg=lora_cfg,
                              schedule=schedule, plan=plan)
    # explicit batch shardings pin eval to ONE compiled layout (no
    # retrace per distinct batch placement, no silent replication on
    # multi-host meshes) — the same contract the train step gets from
    # make_place_batch
    from gke_ray_train_tpu.train.step import batch_shardings
    # ground truth from the BUILT mesh (a declared -1 context axis may
    # have filled to >1; plan.context_sharded resolves, but the mesh is
    # authoritative at this point)
    ctx_sharded = mesh.shape["context"] > 1
    eval_fn_step = make_eval_step(
        cfg, mesh=mesh, lora_cfg=lora_cfg, pipe_microbatches=pipe_micro,
        batch_shardings=batch_shardings(
            mesh, ("inputs", "targets", "weights"),
            context_sharded=ctx_sharded))
    out_base = config.get("OUTPUT_DIR_BASE", "/tmp/grt_sft")
    sft_dir = os.path.join(out_base, config.get("SFT_SUBDIR_NAME", "sft"))
    # AOT train executable beside the checkpoint (perf/cache.py), under
    # the plan's policy: a preempted retry deserializes it and reaches
    # its first step with zero retracing; signature OR plan-fingerprint
    # drift falls back to the jitted step
    from gke_ray_train_tpu.perf.cache import make_abstract_batch
    step_fn = compile_step_with_plan(
        plan, mesh, step_fn, state,
        make_abstract_batch(mesh, global_batch, max_seq,
                            packed=packing,
                            context_sharded=ctx_sharded),
        sidecar=os.path.join(sft_dir, "aot_train_step.bin"),
        label="sft train_step")
    # SAVE_STRATEGY / EVALUATION_STRATEGY_SFT honored (config.py;
    # reference fine_tune_config.json:22-25)
    cadence = cadence_from_config(config)
    mgr = None
    if cadence["save_enabled"]:
        # recency retention, keep 2: the SFT manager exists to RESUME
        # (the final model is exported separately below) — best-by-loss
        # retention would garbage-collect a grace-window preemption
        # save whose loss is not among the best, and the
        # corrupt-checkpoint fallback (ckpt/manager.py) needs an
        # earlier restorable step to survive an interrupted latest save
        # goodput knobs (ASYNC_CKPT / PEER_REPLICATION /
        # CKPT_COMMIT_TIMEOUT_S): same dual-read + semantics as the
        # pretrain entry point — the RESUME manager commits async and
        # replicates to the peer slice; the export manager below stays
        # synchronous (a final artifact has no goodput to protect)
        def _goodput_flag(key):
            return str(config.get(key, os.environ.get(key, "0"))
                       ).strip().lower() not in ("", "0", "false", "no")
        peer = None
        if _goodput_flag("PEER_REPLICATION"):
            from gke_ray_train_tpu.ckpt.peer import PeerReplicator
            peer = PeerReplicator.from_env()
        mgr = CheckpointManager(
            sft_dir, max_to_keep=2, score_attribute=None,
            async_commit=_goodput_flag("ASYNC_CKPT"),
            commit_timeout_s=float(config.get(
                "CKPT_COMMIT_TIMEOUT_S",
                os.environ.get("CKPT_COMMIT_TIMEOUT_S", "120"))),
            peer=peer)

    group_by_length = bool(config.get("GROUP_BY_LENGTH", False))
    if group_by_length and packing:
        logger.warning("GROUP_BY_LENGTH is redundant under PACKING; "
                       "packed sequences have no padding to group away")
        group_by_length = False

    def epoch_batches(epoch):
        yield from sft_epoch_batches(
            train_rows, global_batch, num_hosts=in_shards,
            host_id=in_shard_id, epoch=epoch,
            group_by_length=group_by_length)

    def eval_fn(st):
        # eval rows are PARTITIONED across input-shard groups (the
        # reference gets the same from HF Trainer's DistributedSampler
        # eval): each group walks 1/in_shards of the rows, the jitted
        # step reduces over the global placed batch, zero-weight padding
        # keeps every shard in lockstep — exact eval loss at 1/in_shards
        # the per-host work (train/evaluate.py)
        from gke_ray_train_tpu.train.evaluate import sharded_eval_loss
        return {"eval_loss": sharded_eval_loss(
            st, eval_fn_step, eval_rows, host_batch=host_batch,
            in_shards=in_shards, in_shard_id=in_shard_id,
            place_batch=place)}

    # LoRA runs bill the 4N FLOP count (frozen base skips weight-grad
    # matmuls) so the logged MFU is honest (train/metrics.py)
    meter = ThroughputMeter(cfg, seq_len=max_seq,
                            n_devices=len(devices),
                            trainable="lora" if use_lora else "full")
    # LoRA checkpoints persist only adapters + optimizer state: the
    # frozen (possibly NF4-quantized) base is rebuilt from the pretrained
    # weights on resume — smaller checkpoints, and sub-byte code arrays
    # never hit the serializer.
    ckpt_view = None
    if use_lora:
        ckpt_view = (
            lambda st: st._replace(params={}),
            lambda st, v: v._replace(params=st.params),
        )
    # multi-host batch form-up (SURVEY.md row D9): host-local rows →
    # global sharded arrays; identical path single-host
    place = make_place_batch(mesh, context_sharded=ctx_sharded)

    # shardlint runtime guards (analysis/guards.py), resolved from the
    # plan (config-key-first, env fallback — same precedence as before)
    state, metrics = run_training(
        state, step_fn, epoch_batches,
        epochs=epochs,
        place_batch=place,
        guards=plan.runtime_guards(),
        # asynchronous input pipeline (data/prefetch.py): tokenize/pack +
        # sharded host→device transfer overlap the train step; depth 2
        # device-resident batches by default, 0 = synchronous
        prefetch=plan.prefetch,
        log_every=int(config.get("LOGGING_STEPS", 10)),
        meter=meter, ckpt_manager=mgr,
        report_fn=lambda m: ctx.report(m),
        eval_fn=eval_fn if cadence["eval_enabled"] else None,
        eval_every=cadence["eval_every"],
        eval_at_epoch_end=cadence["eval_at_epoch_end"],
        ckpt_every=cadence["ckpt_every"],
        ckpt_view=ckpt_view,
        # step-granular liveness reports for the heartbeat supervisor
        # (rayint/supervisor.py); a no-op when no sink is wired
        heartbeat_fn=ctx.heartbeat,
        profiler=profiler_from_config(
            config, os.path.join(out_base, "profile")),
        # REPORT_TO honored (reference fine_tune_config.json:26):
        # host-0 TB scalars incl. tokens/sec/chip + MFU
        tb_writer=writer_from_config(
            config, os.path.join(out_base, "tensorboard"),
            is_host0=ctx.is_host0()),
        is_host0=ctx.is_host0())
    return types.SimpleNamespace(
        config=config, plan=plan, mesh=mesh, cfg=cfg, tokenizer=tokenizer,
        ds_test=ds_test, state=state, metrics=metrics, step_fn=step_fn,
        ckpt_manager=mgr, ckpt_view=ckpt_view, use_lora=use_lora,
        lora_cfg=lora_cfg, out_base=out_base, ctx=ctx, n_hosts=n_hosts,
        have_local=have_local, ckpt_dir=ckpt_dir)


def export_final(run: types.SimpleNamespace) -> None:
    """Save the final artifacts in HF layout (§5.4). Single-host LoRA
    runs merge on the HOST: at 8B that is a ~30 GB fp32 tree plus
    dequantization temporaries (about 49 GB of host RAM, CHANGES.md
    PR 21)."""
    import jax

    from gke_ray_train_tpu.ckpt import CheckpointManager, save_hf_checkpoint
    from gke_ray_train_tpu.train import merge_lora
    config, state, cfg, ctx = run.config, run.state, run.cfg, run.ctx
    use_lora, lora_cfg, out_base = run.use_lora, run.lora_cfg, run.out_base
    tokenizer, n_hosts = run.tokenizer, run.n_hosts
    if use_lora:
        final_dir = os.path.join(
            out_base, config.get("MERGED_MODEL_SUBDIR_NAME", "merged"))
    else:
        merged = state.params
        final_dir = os.path.join(
            out_base, config.get("FULL_FT_MODEL_SUBDIR_NAME", "full"))
    if ctx.is_host0() and n_hosts == 1:
        if use_lora:
            # merge on the HOST: dequantizing an 8B NF4 base into a
            # merged fp32 tree (~32 GB) OOMs a single 16 GB chip, and
            # single-host means no other chip holds the rest
            merged = merge_lora(state.params, state.lora, lora_cfg,
                                on_host=True)
        save_hf_checkpoint(merged, cfg, final_dir)
        # tokenizer beside the weights — the output dir must be a
        # self-contained artifact the user can hand straight to
        # AutoTokenizer/from_pretrained, matching the reference
        # (fine_tune_llama_ray.py:355,374)
        from gke_ray_train_tpu.data import save_tokenizer
        save_tokenizer(tokenizer, final_dir)
        logger.info("saved final model + tokenizer to %s", final_dir)
        # obs: exports are run events too — `obs report` shows what
        # artifacts the run produced and when (no-op when obs is off)
        from gke_ray_train_tpu.obs import runtime as obs_runtime
        obs_runtime.emit("export", path=final_dir,
                         what="merged" if use_lora else "full")
    elif n_hosts > 1:
        if use_lora:
            # sharded across hosts: each device holds 1/N of the
            # dequantized tree — the on-device merge fits by design
            merged = merge_lora(state.params, state.lora, lora_cfg)
        # multi-host export path: orbax save (collective) + model-config
        # sidecar, then `python -m gke_ray_train_tpu.ckpt.convert
        # <dir>_orbax <dir>` offline (ckpt/convert.py). Block leaves are
        # saved per-layer (unstack_for_export) so the converter can
        # restore O(one layer) at a time at 70B scale.
        from gke_ray_train_tpu.ckpt.convert import (
            unstack_for_export, write_sidecar)
        # explicitly synchronous even under ASYNC_CKPT=1: a final
        # export has no goodput to protect, and the save must be
        # durable before write_sidecar runs
        export_mgr = CheckpointManager(final_dir + "_orbax", max_to_keep=1,
                                       score_attribute=None,
                                       async_commit=False, peer=False)
        export_mgr.save(int(jax.device_get(state.step)),
                        unstack_for_export(merged), force=True)
        export_mgr.wait()
        if ctx.is_host0():
            write_sidecar(cfg, final_dir + "_orbax")
            # tokenizer rides in a subdir of the orbax export; the
            # offline converter copies it into the final HF dir so the
            # multi-host artifact is self-contained too
            from gke_ray_train_tpu.data import save_tokenizer
            save_tokenizer(tokenizer,
                           os.path.join(final_dir + "_orbax", "tokenizer"))


def compare_inference(run: types.SimpleNamespace) -> None:
    """Optional base-vs-tuned inference comparison (§3.4)."""
    from gke_ray_train_tpu.ckpt import load_hf_checkpoint
    config, state, cfg, ctx = run.config, run.state, run.cfg, run.ctx
    use_lora, lora_cfg, out_base = run.use_lora, run.lora_cfg, run.out_base
    tokenizer, ds_test, mesh = run.tokenizer, run.ds_test, run.mesh
    have_local, ckpt_dir = run.have_local, run.ckpt_dir
    # COLLECTIVE: every host enters the comparison — the params are
    # mesh-sharded global arrays, so a host-0-only generate would
    # diverge the SPMD program (the reference's rank-0 gate at :381-395
    # is only valid because DDP replicates weights). is_host0 gates
    # printing and the JSON write inside run_inference_comparison; every
    # host holds identical ds_test rows (seeded downsample/synthetic).
    if bool(config.get("INFERENCE", False)):
        from gke_ray_train_tpu.inference import run_inference_comparison
        # NOTE: the pre-training `params` handle was donated into the train
        # step (buffer aliasing), so it must not be used here. In LoRA mode
        # the base weights sit unchanged in state.params; in full-FT mode
        # reload them (the reference reloads from the hub, :69-76).
        # `have_local` (not a fresh os.path.exists) keeps the branch
        # choice collective — it was agreed across hosts at load time.
        if use_lora:
            # tuned = frozen base + adapters applied at decode time — a
            # merged copy of a quantized 8B base would not fit on-device
            base_params = tuned_params = state.params
        elif have_local:
            base_params = load_hf_checkpoint(str(ckpt_dir), cfg, mesh=mesh)
            tuned_params = state.params
        else:
            if ctx.is_host0():
                logger.warning(
                    "full-FT smoke without a pretrained checkpoint: "
                    "comparing tuned model against itself")
            base_params = tuned_params = state.params
        run_inference_comparison(
            base_params, tuned_params, cfg, tokenizer, ds_test,
            num_samples=int(config.get("NUM_EVAL_SAMPLES_INFERENCE", 2)),
            max_new_tokens=int(
                config.get("MAX_NEW_GENERATION_TOKENS_INFERENCE", 300)),
            output_path=os.path.join(out_base, "inference_comparison.json"),
            row_filter=(lambda r: r.get("sql_complexity")
                        == "window functions"),
            mesh=mesh, is_host0=ctx.is_host0(),
            tuned_lora=state.lora if use_lora else None,
            lora_scale=lora_cfg.scale if use_lora else 1.0)


def serve_after_train(run: types.SimpleNamespace):
    """Optional post-train serving smoke (serve/, ROADMAP #2). Returns
    what ``post_train_smoke`` returned — (completions, stats) — or None
    when the smoke is off or was skipped."""
    import jax

    from gke_ray_train_tpu.data import format_gretel_sql_example
    from gke_ray_train_tpu.train.tb import writer_from_config
    config, state, cfg, ctx = run.config, run.state, run.cfg, run.ctx
    use_lora, lora_cfg, out_base = run.use_lora, run.lora_cfg, run.out_base
    tokenizer, ds_test, plan = run.tokenizer, run.ds_test, run.plan
    n_hosts = run.n_hosts
    out = None
    # train → serve in the same process: the comparison prompts run
    # through the continuous-batching engine on the just-trained
    # weights (LoRA runs serve base + adapters, never a merged tree).
    # Single-host only: the engine's host-side scheduler is per-replica
    # by design — a multi-host job serves via rayint/serving.py
    # replicas instead.
    # config-then-env (the README's "config and/or env" contract),
    # str-parsed like SMOKE_TEST: the documented disable value "0"
    # must actually disable (bool("0") is True)
    serve_flag = config.get("SERVE_AFTER_TRAIN",
                            os.environ.get("SERVE_AFTER_TRAIN", "0"))
    if str(serve_flag).strip().lower() in ("1", "true"):
        if n_hosts > 1:
            logger.warning(
                "SERVE_AFTER_TRAIN is single-host only (deploy "
                "rayint/serving.py replicas for multi-host serving); "
                "skipping")
        else:
            import numpy as np

            from gke_ray_train_tpu.data.sft import render_chat
            from gke_ray_train_tpu.serve import post_train_smoke
            eos = ([int(tokenizer.eos_token_id)]
                   if getattr(tokenizer, "eos_token_id", None) is not None
                   else [])
            prompts = []
            for row in ds_test[:int(
                    config.get("NUM_EVAL_SAMPLES_INFERENCE", 2))]:
                msgs = format_gretel_sql_example(row)
                text = render_chat(tokenizer, msgs,
                                   add_generation_prompt=True)
                prompts.append(np.asarray(
                    tokenizer(text, add_special_tokens=False)["input_ids"],
                    np.int32))
            out = post_train_smoke(
                state.params, cfg, plan, prompts, eos_ids=eos,
                lora=state.lora if use_lora else None,
                lora_scale=lora_cfg.scale if use_lora else 1.0,
                # LoRA runs tag every smoke request with the trained
                # adapter's id, so the smoke exercises the multi-tenant
                # batched-adapter decode path end to end (ISSUE 17) —
                # serve_smoke.json then records the adapter counters
                adapter_ids=(["tuned"] * len(prompts) if use_lora
                             else None),
                max_new_tokens=64)
            if out is not None and ctx.is_host0():
                comps, stats = out
                for c in comps:
                    logger.info("serve smoke %s (%s): %s", c.rid,
                                c.finish_reason,
                                tokenizer.decode(c.generated))
                # out_base may not exist yet (SAVE_STRATEGY=no and no
                # AOT sidecar = nothing else created it); a smoke must
                # not kill a finished training run
                os.makedirs(out_base, exist_ok=True)
                with open(os.path.join(out_base, "serve_smoke.json"),
                          "w") as f:
                    json.dump(stats, f, indent=2)
                # serving latency/occupancy -> TB, via the SAME obs
                # registry the engine exported into (train/tb.py
                # log_registry; the loop's writer is closed by now, so
                # a short-lived one publishes the post-train scalars)
                from gke_ray_train_tpu.obs import runtime as obs_runtime
                if obs_runtime.registry() is not None:
                    w = writer_from_config(
                        config, os.path.join(out_base, "tensorboard"),
                        is_host0=True)
                    if w is not None:
                        w.log_registry(int(jax.device_get(state.step)),
                                       obs_runtime.registry())
                        w.close()
    return out


if __name__ == "__main__":
    from gke_ray_train_tpu.rayint import JaxTrainer, RunConfig, ScalingConfig
    from gke_ray_train_tpu.rayint.trainer import FailureConfig

    cfg_path = os.environ.get(
        "FINE_TUNE_CONFIG",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "fine_tune_config.json"))
    try:
        with open(cfg_path) as f:
            config = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        logger.error("failed to load %s: %s", cfg_path, e)
        sys.exit(1)

    scaling = ScalingConfig.from_env()
    trainer = JaxTrainer(
        train_loop_per_worker,
        train_loop_config=config,
        scaling_config=scaling,
        run_config=RunConfig(
            name="llama-sft-tpu",
            storage_path=config.get("OUTPUT_DIR_BASE"),
            # fault-tolerance knobs (see README "Fault tolerance" and
            # ray-jobs/README.md): genuine failures retry with backoff
            # against MAX_FAILURES; spot preemptions (SIGTERM →
            # checkpoint within PREEMPT_GRACE_S) are budgeted separately
            failure_config=FailureConfig(
                max_failures=int(os.environ.get("MAX_FAILURES", "0")),
                max_preemptions=int(
                    os.environ.get("MAX_PREEMPTIONS", "8"))),
            # hang detection (rayint/trainer.py): unset = wait forever
            worker_timeout_s=(float(os.environ["WORKER_TIMEOUT_S"])
                              if "WORKER_TIMEOUT_S" in os.environ
                              else None),
            # step-granular supervision (rayint/supervisor.py): kill an
            # attempt — naming the stalled rank — when a worker makes no
            # step progress for this long; unset = no heartbeat watch
            heartbeat_timeout_s=(float(os.environ["HEARTBEAT_TIMEOUT_S"])
                                 if "HEARTBEAT_TIMEOUT_S" in os.environ
                                 else None)),
    )
    result = trainer.fit()
    if result.error:
        logger.error("training %s after %d attempt(s) "
                     "(%d preemption(s)): %s", result.status,
                     result.attempts, result.preemptions, result.error)
        sys.exit(1)
    logger.info("final metrics: %s (attempts=%d preemptions=%d)",
                result.metrics, result.attempts, result.preemptions)
    # unified telemetry (obs/): point the operator at the one merged
    # per-run view of what just happened
    from gke_ray_train_tpu.obs.runtime import resolve_obs_dir
    _obs_dir = resolve_obs_dir(None, config)
    if _obs_dir is not None:
        logger.info("run telemetry: python -m gke_ray_train_tpu.obs "
                    "report %s --text", _obs_dir)
    # one machine-readable line on stdout (logging goes to stderr) so
    # a driver or script can collect the job's meter numbers
    print(json.dumps({"metric": "flagship_final",
                      "attempts": result.attempts,
                      "preemptions": result.preemptions, **{
                          k: v for k, v in (result.metrics or {}).items()
                          if isinstance(v, (int, float))}}), flush=True)

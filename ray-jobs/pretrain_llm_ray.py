"""From-scratch LLM pre-train entry point — the visible, hackable loop.

Capability parity with the reference pre-train
(/root/reference/ray-jobs/pytorch_llm_ray.py): char-tokenize wikitext-2,
train a ~1.2B decoder-only transformer (2048d/24L/16H/8192ff) with
warmup-cosine AdamW, grad clip 1.0, rank-0 logging every 20 batches,
per-epoch checkpoints with keep-1-best-by-loss retention.

TPU redesigns worth noting:
- The reference's filesystem data barrier (rank 0 writes _DATA_PREP_DONE,
  others poll sleep(5), pytorch_llm_ray.py:156-188) is replaced by host-0
  prep + a real collective barrier
  (multihost_utils.sync_global_devices) — no eventually-consistent-FUSE
  race (SURVEY.md §5.2).
- DDP + DistributedSampler become mesh sharding + ShardedBatches.
- Resume-from-latest-checkpoint actually works (§5.3 gap-fix).
"""

from __future__ import annotations

import logging
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

logging.basicConfig(level=logging.INFO,
                    format="%(asctime)s %(name)s: %(message)s")
logger = logging.getLogger("pretrain")


def train_loop_per_worker(config: dict):
    import jax
    import numpy as np

    from gke_ray_train_tpu.ckpt import CheckpointManager
    from gke_ray_train_tpu.data import (
        CharTokenizer, ShardedBatches, SlidingWindowDataset,
        prepare_wikitext2)
    from gke_ray_train_tpu.models import basic_lm
    from gke_ray_train_tpu.parallel.mesh import distributed_init
    from gke_ray_train_tpu.parallel.placement import (
        input_shard_layout, make_place_batch)
    from gke_ray_train_tpu.rayint import get_context
    from gke_ray_train_tpu.train import (
        ThroughputMeter, make_optimizer, make_train_state, make_train_step,
        warmup_cosine_schedule)
    from gke_ray_train_tpu.train.loop import run_training

    ctx = get_context()
    distributed_init()
    seq_len = int(config.get("dataset_seq_len", 256))
    # ONE declarative ExecutionPlan (plan.py): env supplies the
    # guard/compile-cache knobs, the driver config supplies mesh +
    # batch shape via the kwargs dialect — identical plan (and
    # fingerprint) to the same settings spelled in the JSON dialect
    from gke_ray_train_tpu.plan import ExecutionPlan, compile_step_with_plan
    plan = ExecutionPlan.resolve(
        config={k: config[k] for k in
                ("MESH_DATA", "MESH_FSDP", "COMPILE_CACHE_DIR")
                if k in config},
        per_device_batch=int(config.get("batch_size_per_device", 16)),
        max_seq_len=seq_len,
        prefetch=int(config.get("prefetch_batches",
                                config.get("PREFETCH_BATCHES", 2))))
    # elastic mesh re-formation (rayint/elastic.py): when the trainer's
    # post-mortem shrank/grew the pool, re-resolve the plan on the
    # survivors (data/fsdp reflowed, global batch preserved) and build
    # the mesh on exactly those devices; restore below reshards from
    # the logical spec. A no-op when ELASTIC is off or the pool is full.
    from gke_ray_train_tpu.rayint.elastic import maybe_replan
    plan, devices = maybe_replan(plan, config=config, log=logger)
    # tuned-plan overlay (autotune/registry.py): AUTOTUNE=1 overlays a
    # registry hit AFTER the replan (the lookup keys on the attempt's
    # real topology) and BEFORE the cache/mesh. This entry's model is
    # data-derived (tokenizer vocab), so the static model-digest lookup
    # usually misses — the hook logs that loudly rather than guessing.
    from gke_ray_train_tpu.autotune.registry import maybe_apply
    plan, _ = maybe_apply(plan, config=config, log=logger)
    # persistent XLA compile cache (perf/cache.py): the first worker to
    # compile pays; every restart (and every other host) reuses the
    # binary. The trainer already enabled it; the repeat covers a bare
    # call of this function and is a no-op.
    from gke_ray_train_tpu.perf.cache import enable_persistent_cache
    enable_persistent_cache(plan=plan)
    mesh = plan.build_mesh(devices)
    n_hosts = max(jax.process_count(), 1)
    host = jax.process_index()
    logger.info("worker %d/%d; mesh %s; plan %s", host, n_hosts,
                dict(mesh.shape), plan.fingerprint())

    data_dir = config.get("data_dir", "/mnt/pvc/data")
    tok_path = os.path.join(data_dir, "char_tokenizer.json")
    ids_path = os.path.join(data_dir, "wikitext2_train_ids.npy")

    # ---- host-0 data prep + collective barrier -----------------------
    if host == 0 and not (os.path.exists(tok_path)
                          and os.path.exists(ids_path)):
        paths = prepare_wikitext2(data_dir, synthetic_fallback=True)
        text = open(paths["train"]).read()
        tok = CharTokenizer.fit(text)
        tok.save(tok_path)
        np.save(ids_path, tok.encode(text))
        logger.info("data prep done: %d tokens, vocab %d",
                    os.path.getsize(ids_path) // 4, tok.vocab_size)
    if n_hosts > 1:
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices("data_prep_done")

    tok = CharTokenizer.load(tok_path)
    ids = np.load(ids_path)
    dataset = SlidingWindowDataset(ids, seq_len)

    cfg = basic_lm(
        vocab_size=tok.vocab_size,
        d_model=int(config.get("d_model", 2048)),
        n_layers=int(config.get("n_layers", 24)),
        n_heads=int(config.get("n_heads", 16)),
        d_ff=int(config.get("d_ff", 8192)),
        max_seq_len=max(seq_len, int(config.get("model_max_seq_len", 1024))),
        dtype=config.get("dtype", "bfloat16"),
        remat_policy=config.get("remat_policy", "full"),
    )

    global_batch = plan.per_device_batch \
        * mesh.shape["data"] * mesh.shape["fsdp"]
    # test_run parity: cap at 16k samples (pytorch_llm_ray.py:198-201);
    # "max_samples" shrinks further for fast CI smoke
    max_samples = (int(config["max_samples"]) if "max_samples" in config
                   else (16_000 if config.get("test_run", True) else None))
    # input partitioning follows the mesh (hosts spanned by model/context
    # axes feed identical rows — parallel/placement.py)
    in_shards, in_shard_id = input_shard_layout(mesh)
    batches = ShardedBatches(
        dataset, global_batch, num_hosts=in_shards, host_id=in_shard_id,
        max_samples=max_samples)

    epochs = int(config.get("epochs", 1))
    total_steps = batches.steps_per_epoch() * epochs
    schedule = warmup_cosine_schedule(
        float(config.get("lr", 3e-4)), total_steps,
        warmup_frac=float(config.get("warmup_frac", 0.05)),
        min_lr_frac=float(config.get("min_lr_frac", 0.01)))
    opt = make_optimizer(schedule,
                         weight_decay=float(config.get("weight_decay", 0.01)),
                         clip_norm=float(config.get("grad_clip", 1.0)))
    state = make_train_state(cfg, opt, jax.random.key(0), mesh=mesh)

    step_fn = make_train_step(cfg, opt, mesh=mesh, schedule=schedule,
                              plan=plan)
    run_dir = os.path.join(
        config.get("storage_path", "/mnt/pvc/ray_llm_training_runs"),
        config.get("run_name", "basic_lm"))
    # AOT train executable beside the checkpoint (perf/cache.py),
    # under the plan's AOT policy: build once via
    # jit(...).lower(...).compile() and serialize; a preempted retry
    # deserializes it and reaches its first step without retracing.
    # Signature or plan-fingerprint drift falls back to the jitted step.
    from gke_ray_train_tpu.perf.cache import make_abstract_batch
    step_fn = compile_step_with_plan(
        plan, mesh, step_fn, state,
        make_abstract_batch(mesh, global_batch, seq_len),
        sidecar=os.path.join(run_dir, "aot_train_step.bin"),
        label="pretrain train_step")
    # recency retention, keep 2 (NOT the reference's keep-1-best): the
    # training manager exists to RESUME — best-by-loss retention would
    # garbage-collect a grace-window preemption save whose loss is not
    # among the best, and the corrupt-checkpoint fallback needs an
    # earlier restorable step to survive an interrupted latest save
    # goodput knobs: ASYNC_CKPT=1 moves the storage commit behind a
    # write-ahead marker on a background thread (the loop blocks only
    # for the device→host snapshot); PEER_REPLICATION=1 streams every
    # snapshot to the peer slice's hot store so a slice eviction
    # resumes without a storage read. Config-first with env fallback —
    # the SERVE_AFTER_TRAIN dual-read idiom.
    def _goodput_flag(key):
        return str(config.get(key, os.environ.get(key, "0"))
                   ).strip().lower() not in ("", "0", "false", "no")
    peer = None
    if _goodput_flag("PEER_REPLICATION"):
        from gke_ray_train_tpu.ckpt.peer import PeerReplicator
        peer = PeerReplicator.from_env()
    mgr = CheckpointManager(
        run_dir, max_to_keep=2, score_attribute=None,
        async_commit=_goodput_flag("ASYNC_CKPT"),
        commit_timeout_s=float(config.get(
            "CKPT_COMMIT_TIMEOUT_S",
            os.environ.get("CKPT_COMMIT_TIMEOUT_S", "120"))),
        peer=peer)
    if ctx.is_host0():
        # tokenizer beside the checkpoints: the run dir alone is enough
        # to decode/resume (reference saves the tokenizer with the
        # pre-train artifact too)
        from gke_ray_train_tpu.data import save_tokenizer
        save_tokenizer(tok, run_dir)

    meter = ThroughputMeter(cfg, seq_len=seq_len,
                            n_devices=len(devices))
    from gke_ray_train_tpu.train.profiling import profiler_from_config
    state, metrics = run_training(
        state, step_fn, lambda e: batches.iter_epoch(e),
        epochs=epochs,
        # shardlint runtime guards: TRANSFER_GUARD / DIVERGENCE_GUARD
        # (analysis/guards.py), plan-resolved (env dialect)
        guards=plan.runtime_guards(),
        # host-local rows → global sharded arrays (SURVEY.md row D9)
        place_batch=make_place_batch(
            mesh, context_sharded=mesh.shape["context"] > 1),
        # background prefetch overlaps the sliding-window slice + form-up
        # with the step (data/prefetch.py); 0 = synchronous
        prefetch=plan.prefetch,
        log_every=int(config.get("log_every", 20)),
        meter=meter, ckpt_manager=mgr,
        report_fn=lambda m: ctx.report(m),
        # step-granular liveness reports for the heartbeat supervisor
        # (rayint/supervisor.py); a no-op when no sink is wired
        heartbeat_fn=ctx.heartbeat,
        profiler=profiler_from_config(
            config, os.path.join(config.get("storage_path", "/tmp"),
                                 "profile")),
        is_host0=ctx.is_host0())

    # ---- optional post-train serving smoke (serve/, ROADMAP #2) ------
    # the just-pretrained LM serves a few continuations through the
    # continuous-batching engine — train → serve on the same process.
    # Single-host only (multi-host serves via rayint/serving.py).
    serve_flag = config.get("SERVE_AFTER_TRAIN",
                            os.environ.get("SERVE_AFTER_TRAIN", "0"))
    if str(serve_flag).strip().lower() in ("1", "true"):
        if n_hosts > 1:
            logger.warning("SERVE_AFTER_TRAIN is single-host only; "
                           "skipping")
        else:
            from gke_ray_train_tpu.serve import post_train_smoke
            # a few sliding-window prefixes of the training corpus;
            # no adapter_ids — pretraining trains the FULL weights, so
            # there is no adapter to tag (the fine-tune entry tags its
            # smoke with the trained LoRA and serves via AdapterPool)
            prompts = [ids[i * 257:i * 257 + 48] for i in range(4)]
            out = post_train_smoke(state.params, cfg, plan, prompts,
                                   max_new_tokens=48)
            if out is not None and ctx.is_host0():
                comps, stats = out
                for c in comps:
                    logger.info("serve smoke %s: %r", c.rid,
                                tok.decode(np.asarray(c.generated)))
                ctx.report({**metrics, "serve_smoke": stats})
    # obs: record the run's durable artifact (checkpoints + tokenizer
    # dir) as an event; the obs dir itself defaults to
    # <storage_path>/<run_name>/obs for this entry (obs/runtime.py)
    from gke_ray_train_tpu.obs import runtime as obs_runtime
    obs_runtime.emit("export", path=run_dir, what="checkpoint")
    return metrics


if __name__ == "__main__":
    from gke_ray_train_tpu.rayint import JaxTrainer, RunConfig, ScalingConfig
    from gke_ray_train_tpu.rayint.trainer import FailureConfig

    # hardcoded driver config, reference-style (pytorch_llm_ray.py:324-344),
    # with env overrides for smoke runs
    smoke = os.environ.get("SMOKE_TEST", "0") == "1"
    train_loop_config = {
        "d_model": 256 if smoke else 2048,
        "n_layers": 4 if smoke else 24,
        "n_heads": 8 if smoke else 16,
        "d_ff": 1024 if smoke else 8192,
        "dataset_seq_len": 128 if smoke else 256,
        "model_max_seq_len": 1024,
        "batch_size_per_device": 4 if smoke else 16,
        "lr": 3e-4, "weight_decay": 0.01,
        "warmup_frac": 0.05, "min_lr_frac": 0.01, "grad_clip": 1.0,
        "epochs": 1,
        "test_run": True,
        **({"max_samples": int(os.environ.get("MAX_SAMPLES", "1600"))}
           if smoke else {}),
        "log_every": 20,
        "prefetch_batches": int(os.environ.get("PREFETCH_BATCHES", "2")),
        "dtype": "float32" if smoke else "bfloat16",
        "data_dir": os.environ.get("DATA_DIR", "/mnt/pvc/data"),
        "storage_path": os.environ.get(
            "STORAGE_PATH", "/mnt/pvc/ray_llm_training_runs"),
        "run_name": "basic_lm_pretrain",
        "MESH_FSDP": int(os.environ.get("MESH_FSDP", "-1")),
        "MESH_DATA": int(os.environ.get("MESH_DATA", "1")),
    }
    trainer = JaxTrainer(
        train_loop_per_worker,
        train_loop_config=train_loop_config,
        scaling_config=ScalingConfig.from_env(),
        run_config=RunConfig(
            name="basic-lm-pretrain",
            storage_path=train_loop_config["storage_path"],
            # fault-tolerance knobs (README "Fault tolerance",
            # ray-jobs/README.md): failures vs preemptions are budgeted
            # separately — a spot eviction must not burn a retry slot
            failure_config=FailureConfig(
                max_failures=int(os.environ.get("MAX_FAILURES", "0")),
                max_preemptions=int(
                    os.environ.get("MAX_PREEMPTIONS", "8"))),
            # hang detection (rayint/trainer.py): unset = wait forever
            worker_timeout_s=(float(os.environ["WORKER_TIMEOUT_S"])
                              if "WORKER_TIMEOUT_S" in os.environ
                              else None),
            # step-granular supervision (rayint/supervisor.py)
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
    # unified telemetry (obs/): the one merged per-run view
    from gke_ray_train_tpu.obs.runtime import resolve_obs_dir
    _obs_dir = resolve_obs_dir(None, train_loop_config)
    if _obs_dir is not None:
        logger.info("run telemetry: python -m gke_ray_train_tpu.obs "
                    "report %s --text", _obs_dir)

"""A training cell: the job of ``ray-jobs/fine_tune_llama_ray.py::
train_eval_save`` wired from the mix's job keys, with the benchmark's
weights and rows, driven through ``train/loop.py::run_training``. One
body, ``run``, serves every training family.

Set-up builds ONE object, the compiled step with its state, drives it
through the first optimizer steps of the seed's stream (read back for
``correct``), and hands the same object to the window. The window is one
more ``run_training`` call whose batch iterator ends when the clock does.

A *family* is a module (or a namespace) that gives ``run`` only what
differs between model architectures; the mix's ``kind`` names the driver
module, which binds ``run`` to itself (``functools.partial(train.run,
family=...)``). What a new training family provides (:data:`SEAM`):

- ``model_config(config, *, dtype, param_dtype, attn_impl, remat_policy,
  max_seq_len)``: the program's ``ModelConfig`` from the published keys;
- ``build_params(cfg, config, seed, mesh, *, quant_kind)``: the
  benchmark's seeded tree in the program's layout, one jitted call;
- ``build_lora(cfg, config, seed, mesh, lora_cfg)``: the adapters, A from
  the seed and B zero;
- optionally ``layer_kinds(config, layer)``: ``(attention kind, MLP
  kind)`` of each layer run, for the readers that bill layers by kind
  (``work["layer_kinds"]``, and ``work["window"]`` where the model has a
  sliding window);
- optionally ``gradient_readings(table)``: compared numbers read off the
  first gradient tensor against tensor (``common.gradient_table``);
- optionally ``FAULTS``: name -> a function that plants that fault in the
  program, for ``tools/gradient_readings.py --fault``.

What is not the family's is read off the rest. The mix decides the rows
(documents dealt ``docs_per_row`` to a packed row, else padded or packed
by the job's ``PACKING``), LoRA or a full fine-tune (``USE_QLORA``) and
context sharding (the mesh). A configuration with experts
(``cfg.n_experts``) adds the routed layer's counters: pairs held a step
against the reference's, pairs dropped, the largest load.
"""

from __future__ import annotations

import gc
import inspect
import time
import types
from typing import Dict, List

import numpy as np

from benchmark import check, traffic
from benchmark import harness as hs
from benchmark.drivers import common


class StepLog:
    """Takes the place of the loop's TensorBoard writer: the loop hands
    it every logged step's host metrics."""

    def __init__(self):
        self.steps: List[dict] = []

    def log(self, step, metrics):
        # the loop logs its last step again when the epoch ends
        if "loss" in metrics and not (
                self.steps and self.steps[-1]["step"] == int(step)):
            self.steps.append({"step": int(step), "t": time.perf_counter(),
                               **{k: float(v) for k, v in metrics.items()
                                  if isinstance(v, (int, float))}})

    def log_registry(self, *a, **k):
        pass

    def flush(self):
        pass

    def close(self):
        pass


def doc_lengths(batch: Dict[str, np.ndarray]) -> List[int]:
    """Real tokens of a host batch, document by document."""
    if "segment_ids" in batch:
        out = []
        for row in np.asarray(batch["segment_ids"]):
            ids, counts = np.unique(row[row != 0], return_counts=True)
            out.extend(int(c) for c in counts)
        return out
    w = np.asarray(batch["weights"]) > 0
    # rows are right-padded: a row's length reaches its last weighted token
    last = np.where(w.any(1), w.shape[1] - np.argmax(w[:, ::-1], 1), 0)
    return [int(n) for n in last if n]


def find_adam_mu(opt_state):
    import jax
    found = [x for x in jax.tree.leaves(
        opt_state, is_leaf=lambda x: hasattr(x, "mu")) if hasattr(x, "mu")]
    if len(found) != 1:
        raise hs.BenchFailure("the optimizer state has no single Adam mu")
    return found[0].mu


def leaf_norms(tree) -> Dict[str, float]:
    import jax
    import jax.numpy as jnp
    sq = jax.jit(lambda t: jax.tree.map(
        lambda x: jnp.sum(jnp.square(x.astype(jnp.float32))), t))(tree)
    return {k: float(np.sqrt(v))
            for k, v in common.named_leaves(jax.device_get(sq)).items()}


def optimizer_facts(job: dict, total_steps: int) -> dict:
    """What the job states about AdamW (its HF-style keys; betas and eps
    are the defaults of every AdamW the key names)."""
    return {"lr": float(job["LEARNING_RATE"]),
            "warmup_ratio": float(job["WARMUP_RATIO"]),
            "total_steps": int(total_steps),
            "weight_decay": float(job["WEIGHT_DECAY"]),
            "clip": float(job["MAX_GRAD_NORM"]),
            "b1": 0.9, "b2": 0.999, "eps": 1e-8}


# what a family gives, as ``run`` calls it: name -> (positional
# arguments, keywords)
SEAM = {
    "model_config": (("config",), ("dtype", "param_dtype", "attn_impl",
                                   "remat_policy", "max_seq_len")),
    "build_params": (("cfg", "config", "seed", "mesh"), ("quant_kind",)),
    "build_lora": (("cfg", "config", "seed", "mesh", "lora_cfg"), ()),
    "layer_kinds": (("config", "layer"), ()),
    "gradient_readings": (("table",), ()),
}
OPTIONAL = ("layer_kinds", "gradient_readings")


def check_family(family) -> None:
    """Refuses a family that lacks a seam ``run`` calls, or whose seam
    cannot be called as ``run`` calls it."""
    for name, (args, keywords) in SEAM.items():
        fn = getattr(family, name, None)
        if fn is None and name in OPTIONAL:
            continue
        try:
            inspect.signature(fn).bind(*args, **dict.fromkeys(keywords))
        except (TypeError, ValueError) as e:
            raise hs.BenchFailure(
                f"training family {family!r}: {name} does not take "
                f"{args} and keywords {keywords}: {e}") from e


def _dense_model_config(config: dict, *, max_seq_len: int, **kwargs):
    """A dense decoder's sequence bound is its own (window or positions,
    ``common.model_config``), not the job's."""
    return common.model_config(config, **kwargs)


DENSE = types.SimpleNamespace(
    model_config=_dense_model_config, build_params=common.build_params,
    build_lora=common.build_lora)


def run(ctx: dict, family=DENSE) -> dict:
    """ctx: cell, config, mix, limits, seed, seconds, trace, devices,
    peaks, t_start, trace_dir. Returns the facts the readers reduce."""
    import jax

    from gke_ray_train_tpu.config import (
        optimizer_from_config, quant_kind_from_config, schedule_from_config)
    from gke_ray_train_tpu.data.packing import pack_examples
    from gke_ray_train_tpu.data.sft import pad_sft_rows, sft_epoch_batches
    from gke_ray_train_tpu.parallel.placement import (
        host_batch_size, input_shard_layout, make_place_batch)
    from gke_ray_train_tpu.perf.cache import (
        enable_persistent_cache, make_abstract_batch)
    from gke_ray_train_tpu.plan import ExecutionPlan, compile_step_with_plan
    from gke_ray_train_tpu.train import (
        LoraConfig, ThroughputMeter, make_train_state, make_train_step)
    from gke_ray_train_tpu.train.loop import run_training

    check_family(family)
    config, mix, devices = ctx["config"], ctx["mix"], ctx["devices"]
    job = dict(mix["job"])
    group = mix["rows"].get("docs_per_row")
    if group and not job.get("PACKING"):
        raise hs.BenchFailure("documents dealt to rows need PACKING")
    chip_family = "v5e" if devices[0].platform == "tpu" else "cpu"
    job["TOPOLOGY"] = f"{chip_family}-{len(devices)}"
    plan = ExecutionPlan.resolve(job)
    enable_persistent_cache(plan=plan)
    counter = hs.CompileCounter()
    mesh = plan.build_mesh(devices)

    use_lora = bool(job.get("USE_QLORA", False))
    train_dtype = job.get("TRAIN_DTYPE", "bfloat16")
    param_dtype = job.get("PARAM_DTYPE",
                          train_dtype if use_lora else "float32")
    seq = plan.max_seq_len
    cfg = family.model_config(
        config, dtype=train_dtype, param_dtype=param_dtype,
        attn_impl=job.get("ATTN_IMPL", "auto"),
        remat_policy=job.get("REMAT_POLICY", "full"), max_seq_len=seq)
    quant_kind = quant_kind_from_config(job, use_lora)
    routed = bool(cfg.n_experts)
    gradient = getattr(family, "gradient_readings", None) is not None
    layer_kinds = getattr(family, "layer_kinds", None)

    # ---- weights: the benchmark's, in the program's layout -----------
    t_init0 = time.perf_counter()
    params = family.build_params(cfg, config, ctx["seed"], mesh,
                                 quant_kind=quant_kind if use_lora else None)
    jax.block_until_ready(params)
    init_s = time.perf_counter() - t_init0

    # ---- rows from the seed, through the job's own batching ----------
    data_par = mesh.shape["data"] * mesh.shape["fsdp"]
    global_batch = plan.per_device_batch * data_par * plan.grad_accum
    rows_per_call = global_batch // plan.grad_accum // data_par
    if group:
        group = int(group)
        examples = traffic.train_examples(
            mix["rows"], cfg.vocab_size, seq, group, ctx["seed"])
        # a group of documents at a time: the generator dealt them so
        # that each group fills one row, and the program's packer lays it
        # out (a shrunk rehearsal's groups may take more rows than one)
        packed = [row for i in range(0, len(examples), group)
                  for row in pack_examples(examples[i:i + group], seq)]
        rows = {k: np.stack([r[k] for r in packed]) for k in packed[0]}
        total_steps = max(len(packed) // global_batch, 1)
    else:
        examples = traffic.train_examples(
            mix["rows"], cfg.vocab_size, seq, global_batch, ctx["seed"])
        if plan.packing:
            packed = list(pack_examples(examples, seq))
            rows = {k: np.stack([r[k] for r in packed]) for k in packed[0]}
        else:
            rows = pad_sft_rows(examples, seq)
        total_steps = max(-(-len(rows["inputs"]) // global_batch), 1)
    in_shards, in_shard_id = input_shard_layout(mesh)
    host_batch_size(global_batch, num_shards=in_shards)

    # ---- optimizer, state, the compiled step --------------------------
    t_build0 = time.perf_counter()
    lora_cfg = LoraConfig.from_dict(job) if use_lora else None
    schedule = schedule_from_config(job, total_steps)
    opt = optimizer_from_config(job, schedule)
    state = make_train_state(cfg, opt, jax.random.key(1), mesh=mesh,
                             lora_cfg=lora_cfg, params=params)
    if use_lora:
        state = state._replace(lora=family.build_lora(
            cfg, config, ctx["seed"], mesh, lora_cfg))
    del params
    step_fn = make_train_step(cfg, opt, mesh=mesh, lora_cfg=lora_cfg,
                              schedule=schedule, plan=plan)
    ctx_sharded = mesh.shape["context"] > 1
    step_fn = compile_step_with_plan(
        plan, mesh, step_fn, state,
        make_abstract_batch(mesh, global_batch, seq, packed=plan.packing,
                            context_sharded=ctx_sharded),
        sidecar=None, label="benchmark train_step")
    warm_build_s = time.perf_counter() - t_build0
    place = make_place_batch(mesh, context_sharded=ctx_sharded)
    meter = ThroughputMeter(cfg, seq_len=seq, n_devices=len(devices),
                            peak_flops=ctx["peaks"]["flops_bf16"],
                            trainable="lora" if use_lora else "full")

    fed: Dict[int, dict] = {}         # stream index -> host batch

    def stream(first: int, stop_at=None, deadline=None):
        """epoch_batches for one run_training call. The loop skips the
        ``first`` batches its step counter says were trained already."""
        def epoch_batches(epoch):
            it = iter(sft_epoch_batches(
                rows, global_batch, num_hosts=in_shards,
                host_id=in_shard_id, epoch=epoch, shuffle=False))
            i = 0
            while stop_at is None or i < stop_at:
                if deadline is not None and i > first and \
                        time.perf_counter() >= deadline[0]:
                    return
                with jax.profiler.TraceAnnotation("bench:next_batch"):
                    batch = next(it, None)
                if batch is None:
                    return
                if i >= first:
                    fed[i] = batch
                yield batch
                i += 1
        return epoch_batches

    def drive(state, batches, writer, log_every=1, profiler=None):
        """The one call that set-up and the window both make."""
        return run_training(
            state, step_fn, batches, epochs=1, place_batch=place,
            guards=plan.runtime_guards(), prefetch=plan.prefetch,
            log_every=log_every, meter=meter, tb_writer=writer,
            profiler=profiler)

    # ---- the first steps, read back for `correct` ---------------------
    n_check = int(mix["check"]["steps"])
    trainable0 = jax.device_get(state.lora if use_lora else state.params)
    first_log = StepLog()
    state, _ = drive(state, stream(0, stop_at=1), first_log)
    adam_mu = find_adam_mu(state.opt_state)
    b1 = optimizer_facts(job, total_steps)["b1"]
    program = {"grad_norm": {k: v / (1.0 - b1) for k, v in
                             common.by_leaf_name(leaf_norms(adam_mu)).items()}}
    if gradient:
        program["gradient"] = common.gradient_by_layer(
            cfg, jax.device_get(adam_mu), 1.0 / (1.0 - b1))
    del adam_mu
    state, _ = drive(state, stream(1, stop_at=n_check), first_log)
    trainable1 = jax.device_get(state.lora if use_lora else state.params)
    n0, n1 = common.named_leaves(trainable0), common.named_leaves(trainable1)
    program["change"] = common.by_leaf_name({k: float(np.linalg.norm(
        (np.asarray(n1[k], np.float32) - np.asarray(n0[k], np.float32)
         ).ravel())) for k in n0})
    program["loss"] = [s["loss"] for s in first_log.steps]
    if routed:
        program["pairs"] = [s["moe_pairs"] for s in first_log.steps]
    del trainable0, trainable1, n0, n1
    check_batches = [fed[i] for i in range(n_check)]
    fed.clear()

    # ---- the window ----------------------------------------------------
    log = StepLog()
    tracer = None
    counter.begin()
    t0 = time.perf_counter()
    setup_s = t0 - ctx["t_start"]
    deadline = [t0 + float(ctx["seconds"])]
    if ctx["trace"]:
        tracer = common.TraceSlice(ctx["trace_dir"],
                                   float(ctx["seconds"]) - hs.TRACE_SECONDS)
        tracer.step()
    state, last = drive(state, stream(n_check, deadline=deadline), log,
                        log_every=int(job["LOGGING_STEPS"]), profiler=tracer)
    t1 = time.perf_counter()
    if tracer is not None:
        tracer.finish()
    compiles = counter.in_window()
    trained = [fed[i] for i in sorted(fed)]
    step_docs = [doc_lengths(b) for b in trained]
    docs = [n for step in step_docs for n in step]
    device = hs.device_record(devices)
    step_info = getattr(step_fn, "info", {}) or {}

    # ---- free the program's state, then the reference -----------------
    del state, step_fn, place, meter
    gc.collect()
    ref_args = (ctx, cfg, job, quant_kind, use_lora, lora_cfg, total_steps,
                check_batches)
    t_ref0 = time.perf_counter()
    reference = reference_readings(*ref_args)
    reference_s = time.perf_counter() - t_ref0
    readings, table = compared(program, reference, family)
    program.pop("gradient", None)
    # for benchmark/tools/readings.py: the control and the faults are
    # read from the same first steps
    raw = {"program": program, "reference": reference,
           "reference_args": ref_args, "family": family}
    if table is not None:
        raw["gradient_table"] = table

    counters = {"data_stall_frac": last.get("data_stall_frac"),
                "compiles_in_window": compiles,
                "cache": counter.snapshot(),
                "train_step_source": step_info.get("source")}
    work = {"steps": len(trained), "doc_lengths": docs,
            "tokens": int(sum(docs)),
            "trainable": "lora" if use_lora else "full",
            "lora_rank": lora_cfg.r if use_lora else 0,
            "rows_per_call": rows_per_call, "seq": seq,
            "micro_steps": plan.grad_accum, "step_docs": step_docs,
            "step_times": [s["t"] for s in log.steps]}
    if layer_kinds is not None:
        work["layer_kinds"] = [layer_kinds(config, i)
                               for i in range(cfg.n_layers)]
        if cfg.sliding_window:
            work["window"] = int(cfg.sliding_window)
    first_note = {"note": "first steps", "program": program["loss"],
                  "reference": reference["loss"]}
    notes = [{"note": "compilations inside the window", "count": compiles,
              "cache": counter.snapshot(),
              "train_step": step_info if routed else step_info.get("source")},
             first_note]
    dropped = 0
    if routed:
        moe = {k: [s[k] for s in log.steps] for k in
               ("moe_pairs", "moe_max_load", "moe_pairs_dropped")}
        dropped = sum(moe["moe_pairs_dropped"]) + sum(
            s["moe_pairs_dropped"] for s in first_log.steps)
        counters["moe_pairs_dropped"] = dropped
        work.update(lora_targets=list(lora_cfg.targets) if use_lora else [],
                    step_pairs=moe["moe_pairs"])
        first_note.update({"held pairs, program": program["pairs"],
                           "held pairs, reference": reference["pairs"]})
        notes.append({"note": "routed layer, a step of the window",
                      "moe_pairs": moe["moe_pairs"][:4],
                      "moe_max_load": max(moe["moe_max_load"], default=None),
                      "moe_pairs_dropped": dropped})
    notes.append({"note": "seconds by phase", "setup_s": setup_s,
                  "window_s": t1 - t0, "reference_s": reference_s})

    return {
        "kind": "train", "chips": len(devices), "peaks": ctx["peaks"],
        "dims": dict(reference["dims"]),
        # what the kernels' patterns are filled from (benchmark/kernels)
        "sizes": dict(reference["dims"], seq=seq, rows=rows_per_call),
        "t0": t0, "t1": t1, "window_s": t1 - t0, "setup_s": setup_s,
        "spans": {"init_s": init_s, "warm_build_s": warm_build_s},
        "counters": counters, "work": work,
        "trace_window": (None if tracer is None
                         else (tracer.t0, tracer.t1)),
        "device": device, "readings": readings, "raw": raw,
        "attempted": len(trained), "failed": int(dropped > 0),
        "notes": notes,
    }


def reference_readings(ctx, cfg, job, quant_kind, use_lora, lora_cfg,
                       total_steps, batches, mode: str = "f32",
                       keep_rows=None) -> dict:
    """The plain reference over the same first steps. Which fine-tunes a
    reference can follow is the reference module's business (the
    configuration names the module)."""
    import importlib

    import jax
    ref = importlib.import_module(
        "benchmark.reference." + ctx["config"]["reference"])
    lora = None if not use_lora else {
        "rank": lora_cfg.r, "alpha": lora_cfg.alpha,
        "targets": tuple(lora_cfg.targets)}
    with jax.default_matmul_precision("highest"):
        model, trainer = ref.trainer(
            ctx["config"], ctx["seed"], store_dtype=cfg.param_dtype,
            quant_kind=quant_kind, lora=lora,
            optimizer=optimizer_facts(job, total_steps), mode=mode,
            keep_rows=keep_rows)
        steps = [trainer.step(b) for b in batches]
        change = trainer.change_norms()
    out = {"loss": [s["loss"] for s in steps],
           "grad_norm": steps[0]["grad_norm"],
           "grad_norms": [s["grad_norm"] for s in steps],
           "change": change, "dims": model.dims}
    # a routed reference also counts the pairs its held experts took, a
    # reference of a family that reads it keeps its first gradient
    for key, name in (("held_pairs", "pairs"), ("first_gradient", "gradient")):
        if key in out["dims"]:
            out[name] = out["dims"].pop(key)
    return out


def compared(program: dict, reference: dict, family):
    """The numbers ``correct`` compares, with the first gradient's table
    where the family reads it (else None). ``program`` is the program's
    readings or a reference put in its place (a control)."""
    readings = check.train_readings(program, reference)
    table = None
    if getattr(family, "gradient_readings", None) is not None:
        table = common.gradient_table(program["gradient"],
                                      reference["gradient"])
        readings.update(family.gradient_readings(table))
    if "pairs" in program:
        readings["pairs_gap"] = common.pairs_gap(program["pairs"],
                                                 reference["pairs"])
    return readings, table

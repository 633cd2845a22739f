"""A training cell of the hybrid state-space / attention routed decoder
(``granitemoehybrid``: Granite-4.0-H-Small): the same job as
``drivers/train.py``, ``drivers/train_moe.py`` and
``drivers/train_mla.py`` (``train_eval_save`` wired from the mix's job
keys, driven through ``train/loop.py::run_training``), with this
family's seam to the program: its model configuration from the published
keys (the layer pattern, the mixer's sizes, the four multipliers, the
softmax-of-the-selected router), the benchmark's seeded tree in the
program's layout (a state-space layer's leaves where such a layer
stands, no head: it is tied), adapters. What the other drivers and
``drivers/common.py`` export is used as it stands; ``run`` returns the
facts ``train_mla.run`` returns, so the readers that hold for the routed
cells serve this one, and reads ``grad_dir_gap`` as the latent cell does
and ``ssm_dir_gap`` beside it: the first gradient tensor against tensor
over the mixers' two projections alone.

``run`` is ``train_mla.run`` with the same calls exchanged again (model
configuration, seeded tree, adapters, the extra compared numbers):
``train_mla.run`` finds its own by name in its own module, so they
cannot be handed to it. This is the third copy of ``train.run``; the
seam the next ``benchmark`` issue gives it takes four sets of those
(PERF.md section 7).
"""

from __future__ import annotations

import gc
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import check, traffic
from benchmark import harness as hs
from benchmark import weights as wts
from benchmark import weights_ssm as ws
from benchmark.drivers import common
from benchmark.drivers.train import (
    StepLog, doc_lengths, find_adam_mu, leaf_norms, optimizer_facts,
    reference_readings)
from benchmark.drivers.train_mla import (
    direction_gap, gradient_by_layer, gradient_table, pairs_gap)
from benchmark.drivers.train_moe import BANK, by_leaf_name


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


def params_maker(cfg, config: dict, *, quant_kind: Optional[str],
                 quant_group: int = 64):
    """``make(key) -> tree`` in the program's layout and the types it is
    run in: projections and experts quantised slice by slice as they are
    drawn (never a full-precision tree first), the rest in
    ``cfg.param_dtype``."""
    from gke_ray_train_tpu.models.transformer import (
        block_layout, block_leaves)
    from gke_ray_train_tpu.ops.quant import QTensor, quantize_tensor

    dims = ws.dims_from_config(config)
    pdt = jnp.dtype(cfg.param_dtype)
    quant = quant_kind not in (None, "none")
    frozen = ws.ATTENTION + ws.MIXER + tuple(BANK) + ws.SHARED

    def leaf(key, name, layer, expert=None):
        """One layer's (one expert's) leaf as the program stores it."""
        bench_name = BANK.get(name, name)
        args = () if expert is None else (expert,)
        if not (quant and name in frozen):
            return ws.stored(dims, key, bench_name, layer, pdt, *args)
        w = ws.stored(dims, key, bench_name, layer, jnp.bfloat16, *args)
        qt = quantize_tensor(w[None], quant_kind, quant_group)
        return qt.codes[0], qt.scales[0]

    def stack(key, name, first, count, stride):
        experts = dims["held_lo"] + jnp.arange(dims["held"], dtype=jnp.int32)

        def one(r):
            layer = first + r * stride
            if name in BANK:
                return jax.lax.map(lambda e: leaf(key, name, layer, e),
                                   experts)
            return leaf(key, name, layer)
        out = jax.lax.map(one, jnp.arange(count, dtype=jnp.int32))
        if isinstance(out, tuple):
            return QTensor(out[0], out[1], quant_kind,
                           out[0].shape[-2] // out[1].shape[-2])
        return out

    def make(key):
        tree = {"embed": wts.stored(dims, key, "embed", 0, pdt),
                "final_norm": wts.stored(dims, key, "final_norm", 0, pdt)}
        for where, _, first, count, stride, mlp in block_layout(cfg):
            tree.setdefault(where, []).append(
                {name: stack(key, name, first, count, stride)
                 for name in block_leaves(cfg, count, mlp,
                                          cfg.block_kind(first))})
        return tree
    return make


def build_params(cfg, config: dict, seed: int, mesh, *,
                 quant_kind: Optional[str], quant_group: int = 64):
    """The whole tree in one jitted call from the seed."""
    make = params_maker(cfg, config, quant_kind=quant_kind,
                        quant_group=quant_group)
    key = wts.seed_key(seed)
    shardings = common.param_shardings(cfg, jax.eval_shape(make, key), mesh)
    return jax.jit(make, out_shardings=shardings)(key)


def build_lora(cfg, config: dict, seed: int, mesh, lora_cfg):
    """LoRA adapters in the program's layout: A from the seed, B zero."""
    from gke_ray_train_tpu.models.transformer import block_layout
    from gke_ray_train_tpu.parallel.sharding import tree_shardings
    from gke_ray_train_tpu.train.lora import lora_specs

    dims = ws.dims_from_config(config)
    specs = lora_specs(cfg, lora_cfg)

    def make(key):
        tree: Dict[str, list] = {}
        for where, i, first, count, stride, _ in block_layout(cfg):
            layers = first + stride * jnp.arange(count, dtype=jnp.int32)
            tree.setdefault(where, []).append({
                t: {"a": jax.lax.map(
                        lambda l, t=t: ws.lora_a(dims, key, t, l,
                                                 lora_cfg.r), layers),
                    "b": jnp.zeros((count,) + ws.lora_b_shape(
                        dims, t, lora_cfg.r), jnp.float32)}
                for t in specs[where][i]})
        return tree
    return jax.jit(make, out_shardings=tree_shardings(mesh, specs))(
        wts.seed_key(seed))


def gradient_readings(table) -> Dict[str, float]:
    """``grad_dir_gap`` over every adapter leaf (``train_mla``'s);
    ``ssm_dir_gap`` over the mixers' two projections alone, which a
    fault in the scan or the conv moves most: everything between
    ``in_proj``'s output and ``out_proj``'s input is theirs."""
    return {"grad_dir_gap": direction_gap(table),
            "ssm_dir_gap": direction_gap(table, ws.MIXER)}


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def run(ctx: dict) -> dict:
    """ctx: cell, config, mix, limits, seed, seconds, trace, devices,
    peaks, t_start, trace_dir. Returns the facts the readers reduce."""
    from gke_ray_train_tpu.config import (
        optimizer_from_config, quant_kind_from_config, schedule_from_config)
    from gke_ray_train_tpu.data.packing import pack_examples
    from gke_ray_train_tpu.data.sft import sft_epoch_batches
    from gke_ray_train_tpu.parallel.placement import (
        host_batch_size, input_shard_layout, make_place_batch)
    from gke_ray_train_tpu.perf.cache import (
        enable_persistent_cache, make_abstract_batch)
    from gke_ray_train_tpu.plan import ExecutionPlan, compile_step_with_plan
    from gke_ray_train_tpu.train import (
        LoraConfig, ThroughputMeter, make_train_state, make_train_step)
    from gke_ray_train_tpu.train.loop import run_training

    config, mix, devices = ctx["config"], ctx["mix"], ctx["devices"]
    job = dict(mix["job"])
    if not (job.get("USE_QLORA") and job.get("PACKING")):
        raise hs.BenchFailure("this driver runs packed QLoRA jobs")
    family = "v5e" if devices[0].platform == "tpu" else "cpu"
    job["TOPOLOGY"] = f"{family}-{len(devices)}"
    plan = ExecutionPlan.resolve(job)
    enable_persistent_cache(plan=plan)
    counter = hs.CompileCounter()
    mesh = plan.build_mesh(devices)
    seq = plan.max_seq_len
    train_dtype = job.get("TRAIN_DTYPE", "bfloat16")
    cfg = model_config(
        config, dtype=train_dtype,
        param_dtype=job.get("PARAM_DTYPE", train_dtype),
        attn_impl=job.get("ATTN_IMPL", "auto"),
        remat_policy=job.get("REMAT_POLICY", "full"), max_seq_len=seq)
    quant_kind = quant_kind_from_config(job, True)

    # ---- weights: the benchmark's, in the program's layout -----------
    t_init0 = time.perf_counter()
    params = build_params(cfg, config, ctx["seed"], mesh,
                          quant_kind=quant_kind)
    jax.block_until_ready(params)
    init_s = time.perf_counter() - t_init0

    # ---- rows from the seed, packed by the program --------------------
    data_par = mesh.shape["data"] * mesh.shape["fsdp"]
    global_batch = plan.per_device_batch * data_par * plan.grad_accum
    group = int(mix["rows"]["docs_per_row"])
    examples = traffic.train_examples(
        mix["rows"], cfg.vocab_size, seq, group, ctx["seed"])
    # a group of documents at a time: the generator dealt them so that
    # each group fills one row, and the program's packer lays it out (a
    # shrunk rehearsal's groups may take more rows than one)
    packed = [row for i in range(0, len(examples), group)
              for row in pack_examples(examples[i:i + group], seq)]
    rows = {k: np.stack([r[k] for r in packed]) for k in packed[0]}
    total_steps = max(len(packed) // global_batch, 1)
    in_shards, in_shard_id = input_shard_layout(mesh)
    host_batch_size(global_batch, num_shards=in_shards)

    # ---- optimizer, state, the compiled step --------------------------
    t_build0 = time.perf_counter()
    lora_cfg = LoraConfig.from_dict(job)
    schedule = schedule_from_config(job, total_steps)
    opt = optimizer_from_config(job, schedule)
    state = make_train_state(cfg, opt, jax.random.key(1), mesh=mesh,
                             lora_cfg=lora_cfg, params=params)
    state = state._replace(lora=build_lora(cfg, config, ctx["seed"], mesh,
                                           lora_cfg))
    del params
    step_fn = make_train_step(cfg, opt, mesh=mesh, lora_cfg=lora_cfg,
                              schedule=schedule, plan=plan)
    step_fn = compile_step_with_plan(
        plan, mesh, step_fn, state,
        make_abstract_batch(mesh, global_batch, seq, packed=True,
                            context_sharded=False),
        sidecar=None, label="benchmark train_step")
    warm_build_s = time.perf_counter() - t_build0
    place = make_place_batch(mesh, context_sharded=False)
    meter = ThroughputMeter(cfg, seq_len=seq, n_devices=len(devices),
                            peak_flops=ctx["peaks"]["flops_bf16"],
                            trainable="lora")

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
    trainable0 = jax.device_get(state.lora)
    first_log = StepLog()
    state, _ = drive(state, stream(0, stop_at=1), first_log)
    adam_mu = find_adam_mu(state.opt_state)
    mu = by_leaf_name(leaf_norms(adam_mu))
    b1 = optimizer_facts(job, total_steps)["b1"]
    program = {"grad_norm": {k: v / (1.0 - b1) for k, v in mu.items()},
               "gradient": gradient_by_layer(cfg, jax.device_get(adam_mu),
                                             1.0 / (1.0 - b1))}
    del adam_mu
    state, _ = drive(state, stream(1, stop_at=n_check), first_log)
    trainable1 = jax.device_get(state.lora)
    n0, n1 = common.named_leaves(trainable0), common.named_leaves(trainable1)
    program["change"] = by_leaf_name({k: float(np.linalg.norm(
        (np.asarray(n1[k], np.float32) - np.asarray(n0[k], np.float32)
         ).ravel())) for k in n0})
    program["loss"] = [s["loss"] for s in first_log.steps]
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
    moe = {k: [s[k] for s in log.steps] for k in
           ("moe_pairs", "moe_max_load", "moe_pairs_dropped")}

    # ---- free the program's state, then the reference -----------------
    del state, step_fn, place, meter
    gc.collect()
    ref_args = (ctx, cfg, job, quant_kind, True, lora_cfg, total_steps,
                check_batches)
    t_ref0 = time.perf_counter()
    reference = reference_readings(*ref_args)
    reference_s = time.perf_counter() - t_ref0
    ref_pairs = reference["dims"].pop("held_pairs")
    reference["gradient"] = reference["dims"].pop("first_gradient")
    readings = check.train_readings(program, reference)
    table = gradient_table(program.pop("gradient"), reference["gradient"])
    readings.update(gradient_readings(table))
    readings["pairs_gap"] = pairs_gap(program["pairs"], ref_pairs)
    dropped = sum(moe["moe_pairs_dropped"]) + sum(
        s["moe_pairs_dropped"] for s in first_log.steps)

    rows_per_call = global_batch // plan.grad_accum // data_par
    return {
        "kind": "train", "chips": len(devices), "peaks": ctx["peaks"],
        "dims": dict(reference["dims"]),
        # what the kernels' patterns are filled from (benchmark/kernels)
        "sizes": dict(reference["dims"], seq=seq, rows=rows_per_call),
        "t0": t0, "t1": t1, "window_s": t1 - t0, "setup_s": setup_s,
        "spans": {"init_s": init_s, "warm_build_s": warm_build_s},
        "counters": {"data_stall_frac": last.get("data_stall_frac"),
                     "compiles_in_window": compiles,
                     "cache": counter.snapshot(),
                     "train_step_source": step_info.get("source"),
                     "moe_pairs_dropped": dropped},
        "work": {"steps": len(trained), "doc_lengths": docs,
                 "tokens": int(sum(docs)), "trainable": "lora",
                 "lora_rank": lora_cfg.r, "lora_targets": list(
                     lora_cfg.targets),
                 "rows_per_call": rows_per_call, "seq": seq,
                 "micro_steps": plan.grad_accum, "step_docs": step_docs,
                 "step_times": [s["t"] for s in log.steps],
                 "step_pairs": moe["moe_pairs"],
                 "layer_kinds": [ws.layer_kinds(config, i)
                                 for i in range(cfg.n_layers)]},
        "trace_window": (None if tracer is None
                         else (tracer.t0, tracer.t1)),
        "device": device, "readings": readings,
        # for benchmark/tools/readings.py: the control and the faults
        # are read from the same first steps
        "raw": {"program": program, "reference": reference,
                "reference_args": ref_args, "reference_pairs": ref_pairs,
                "gradient_table": table},
        "attempted": len(trained), "failed": int(dropped > 0),
        "notes": [{"note": "compilations inside the window",
                   "count": compiles, "cache": counter.snapshot(),
                   "train_step": step_info},
                  {"note": "first steps", "program": program["loss"],
                   "reference": reference["loss"],
                   "held pairs, program": program["pairs"],
                   "held pairs, reference": ref_pairs},
                  {"note": "routed layer, a step of the window",
                   "moe_pairs": moe["moe_pairs"][:4],
                   "moe_max_load": max(moe["moe_max_load"], default=None),
                   "moe_pairs_dropped": dropped},
                  {"note": "seconds by phase", "setup_s": setup_s,
                   "window_s": t1 - t0, "reference_s": reference_s}],
    }

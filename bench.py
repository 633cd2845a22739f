"""Benchmark — one JSON line for the driver.

Default mode measures sustained training throughput (tokens/sec/chip)
and MFU for the flagship-architecture model at the largest size that
fits comfortably on the attached accelerator(s), using the real jitted
train step (loss+grad+clip+adamw, bf16 compute).

Timing: two sync methods are measured and reported — (a) a forced
device→host transfer of the final loss minus the measured transfer
round-trip, and (b) ``jax.block_until_ready``; (a) is the primary
number and the discrepancy field says how far they are apart. None of
this has run on the current installation (see CHANGES.md, PR 21);
ROADMAP D1 replaces this file with a ``workloads`` table.

Extra modes via BENCH_MODE env: ``qlora8b`` (full Llama-3.1-8B dims, NF4 frozen base + r=64
LoRA on one chip), ``mistral7b-lora`` (BASELINE config 4: full
Mistral-7B dims, sliding-window attention, NF4 base + LoRA),
``gemma2-4k`` (BASELINE config 5 shape: Gemma-2 pattern — alternating
sliding/global, softcaps, tied embeddings — packed seq 4096),
``seq4k`` (packed 4k llama-proxy), ``moe`` (Mixtral-pattern 8-expert
top-2 MoE proxy), ``qwen2-lora`` (full Qwen-2.5-7B dims incl. q/k/v
bias, NF4 base + LoRA), ``decode`` (KV-cache greedy decode tokens/sec),
``serve`` (continuous-batching serving A/B, serve/engine.py:
iteration-level batching across MAX_BATCH slots vs serial batch-1
greedy over the same request set, with p50/p99 per-token latency,
batch occupancy and the decode StepCostReport on the record),
``input-bound`` (async input pipeline A/B: real packing path behind a
deliberately slow host stall, prefetch on vs off on one JSON line),
``recovery`` (fault drill: time-to-recover from an injected kill +
checkpoint-save latency under SIGTERM, testing/faults.py; the record
separates recompile time from restore+fast-forward time),
``elastic`` (elastic-training drill on the canonical 8-fake-device CPU
mesh: injected pool shrink 8→4→8, mesh re-formed + checkpoint resumed
RESHARDED each time; value = goodput fraction from the per-attempt
goodput ledger, plus time-to-first-step-after-shrink and the per-attempt
shrink/grow event classification),
``compile`` (compile-once layer A/B, perf/: cold build vs warm
persistent-cache build vs deserialized AOT executable, plus the
compile-level StepCostReport — meaningful on ANY backend, including
the CPU mesh),
``overlap`` (OVERLAP=off vs =manual A/B through make_train_step:
bitwise-identical loss streams asserted, per-arm tokens/sec and the
scheduled-HLO overlap evidence — overlap_frac / exposed collective
bytes — on one record),
``autotune`` (default-vs-tuned A/B through the autotune search on the
canonical CPU mesh: the winner over the tiny_fsdp8 base plan, per-arm
StepCostReport + exposed bytes + plan fingerprints, modeled step-time
improvement as the value, and the tuned arm's real loss stream
asserted valid against the default arm's trajectory shape).

The selected mode runs in this process on whatever backend jax
attaches; every record carries ``"backend": devices[0].platform``. A
backend that fails to start raises — nothing re-runs on the CPU.

vs_baseline: ratio against this framework's own first-light number
(bench_baseline.json) — the reference publishes no numbers.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp

# A/B knob for every remat-enabled mode: "full" (recompute the block in
# backward, lowest memory) vs "dots" (save matmul outputs). Validated
# here so a typo fails before an expensive TPU run, not silently.
# Per-mode default when BENCH_REMAT is unset: "dots" where the saved
# matmul outputs fit (measured 25,587 tok/s/chip @ 55.8% MFU vs 24,285 @
# 53.0% for "full" on the default workload, v5e chip r4); "full" where
# they blow the 16 GB HBM — the full-family-dims LoRA modes (qlora8b
# with dots: 22.1 GB requested) and the packed-4k gemma mode, whose
# seq-4096 activations are the problem (dots: 19.2 GB requested).
_REMAT_DEFAULTS = {"qlora8b": "full", "mistral7b-lora": "full",
                   "qwen2-lora": "full", "gemma2-4k": "full"}
BENCH_REMAT_POLICY = os.environ.get("BENCH_REMAT") or _REMAT_DEFAULTS.get(
    os.environ.get("BENCH_MODE", "train"), "dots")
if BENCH_REMAT_POLICY not in ("full", "dots"):
    raise SystemExit(f"BENCH_REMAT={BENCH_REMAT_POLICY!r}; use full|dots")


def _measure_latency() -> float:
    probe = jax.jit(lambda x: x + 1)
    float(jax.device_get(probe(jnp.zeros(()))))
    t0 = time.perf_counter()
    for _ in range(3):
        float(jax.device_get(probe(jnp.zeros(()))))
    return (time.perf_counter() - t0) / 3


def _timed_loop(run_steps, steps: int, latency: float):
    """run_steps(n) executes n chained steps and returns the final
    device scalar. Returns (dt_device_get, dt_block_until_ready)."""
    t0 = time.perf_counter()
    out = run_steps(steps)
    jax.block_until_ready(out)
    dt_block = max(time.perf_counter() - t0, 1e-9)
    t0 = time.perf_counter()
    out = run_steps(steps)
    float(jax.device_get(out))
    dt_get = max(time.perf_counter() - t0 - latency, 1e-9)
    return dt_get, dt_block


# one stable id per bench process: records emitted OUTSIDE an obs
# session (the common bench path) still need a run identity, so `obs
# diff` / the report merge can key A/B arms deterministically instead
# of by file order. An active session's OBS_RUN_ID (exported by the
# trainer, or job-level env) always wins — those records must join the
# run's event stream under the same key.
_BENCH_RUN_ID = None


def _bench_run_id():
    global _BENCH_RUN_ID
    if os.environ.get("OBS_RUN_ID"):
        return os.environ["OBS_RUN_ID"]
    if _BENCH_RUN_ID is None:
        from gke_ray_train_tpu.obs.runtime import new_run_id
        _BENCH_RUN_ID = new_run_id()
    return _BENCH_RUN_ID


def _emit(metric, value, unit, extra, compare_baseline=True):
    baseline_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "bench_baseline.json")
    baseline = None
    devices = jax.devices()
    if compare_baseline and os.path.exists(baseline_path):
        try:
            with open(baseline_path) as f:
                recorded = json.load(f)
            if recorded.get("device_kind") == devices[0].device_kind:
                baseline = float(recorded["tokens_per_sec_per_chip"])
        except (OSError, ValueError, KeyError):
            pass
    result = {
        "metric": metric,
        "value": round(value, 1),
        "unit": unit,
        "run_id": _bench_run_id(),
        "vs_baseline": round(value / baseline, 3) if baseline else 1.0,
        "backend": devices[0].platform,
        **extra,
    }
    # the ExecutionPlan identity of this bench process (env dialect,
    # plan.py) — the same fingerprint budget JSONs and AOT sidecar
    # keys carry, so a BENCH record names the plan it measured
    try:
        from gke_ray_train_tpu.plan import ExecutionPlan
        result["plan_fingerprint"] = ExecutionPlan.from_env().fingerprint()
    except Exception as e:  # noqa: BLE001 - provenance is best-effort
        result["plan_fingerprint"] = f"unresolvable: {e}"[:80]
    print(json.dumps(result))
    # obs sink (ISSUE 11): with OBS_DIR set, the record ALSO lands in
    # the run's obs dir, where `python -m gke_ray_train_tpu.obs report`
    # merges it with the events/metrics/ledger of the same run (the
    # BENCH_MODE=elastic record beside its per-attempt event stream)
    obs_dir = os.environ.get("OBS_DIR")
    if obs_dir:
        try:
            os.makedirs(obs_dir, exist_ok=True)
            with open(os.path.join(obs_dir, "bench_records.jsonl"),
                      "a") as f:
                f.write(json.dumps(result) + "\n")
        except OSError as e:
            print(f"bench: obs record sink failed: {e}", file=sys.stderr)
    on_tpu = devices[0].platform != "cpu"
    if compare_baseline and baseline is None and on_tpu and \
            unit == "tokens/sec/chip":
        with open(baseline_path, "w") as f:
            json.dump({"device_kind": devices[0].device_kind,
                       "tokens_per_sec_per_chip": value}, f)


def bench_train():
    """Default driver-recorded bench: 0.69B llama3-arch full train step
    (identical workload to round 1 for vs_baseline continuity)."""
    import dataclasses

    from gke_ray_train_tpu.models import llama3_8b
    from gke_ray_train_tpu.parallel.mesh import MeshConfig, build_mesh
    from gke_ray_train_tpu.train import (
        make_optimizer, make_train_state, make_train_step,
        train_flops_per_token, warmup_cosine_schedule)
    from gke_ray_train_tpu.train.metrics import peak_flops_per_device
    from gke_ray_train_tpu.train.step import batch_shardings

    devices = jax.devices()
    n_dev = len(devices)
    on_tpu = devices[0].platform != "cpu"
    if on_tpu:
        size = dict(d_model=2048, n_layers=12, n_heads=16, n_kv_heads=8,
                    d_ff=5504, vocab_size=32768)
        B, S, steps = 8, 1024, 20
    else:  # CPU smoke fallback so the bench always emits a line
        size = dict(d_model=256, n_layers=2, n_heads=4, n_kv_heads=2,
                    d_ff=512, vocab_size=2048)
        B, S, steps = max(4, n_dev), 256, 3
    cfg = dataclasses.replace(
        llama3_8b(), name="llama3-bench", max_seq_len=S,
        dtype="bfloat16", param_dtype="float32", remat=True,
        remat_policy=BENCH_REMAT_POLICY, **size)

    mesh = build_mesh(MeshConfig(data=1, fsdp=-1), devices)
    schedule = warmup_cosine_schedule(3e-4, 1000)
    opt = make_optimizer(schedule)
    state = make_train_state(cfg, opt, jax.random.key(0), mesh=mesh)
    # donate_batch=False: the timing loop feeds the SAME placed batch
    # every step; a donated buffer must not be reused
    step = make_train_step(cfg, opt, mesh=mesh, schedule=schedule,
                           donate_batch=False)

    batch = jax.device_put(_rand_batch(B, S, cfg.vocab_size),
                           batch_shardings(mesh))
    # AOT lower+compile once: the SAME executable is timed below AND
    # feeds the compile-level cost report (perf/costs.py) — so every
    # bench record carries the hardware-independent numbers too
    from gke_ray_train_tpu.perf.costs import step_cost_report
    t0 = time.perf_counter()
    compiled = step.lower(state, batch).compile()
    compile_s = time.perf_counter() - t0
    cost = step_cost_report(compiled, tokens_per_step=B * S)
    dt_get, dt_block, loss = _run_timed_train(compiled, state, batch, steps)
    tokens = B * S * steps
    tps_chip = tokens / dt_get / n_dev
    mfu = (tokens / dt_get) * train_flops_per_token(cfg, S) / (
        peak_flops_per_device() * n_dev)
    _emit(
        "tokens/sec/chip llama3-arch causal-LM train step "
        f"({cfg.d_model}d/{cfg.n_layers}L seq {S}, bf16, "
        f"{devices[0].device_kind} x{n_dev})",
        tps_chip, "tokens/sec/chip",
        {"mfu": round(mfu, 4), "loss": round(loss, 4),
         "compile_s": round(compile_s, 3),
         "cost_report": cost.summary(),
         "timing": {"device_get_s": round(dt_get, 4),
                    "block_until_ready_s": round(dt_block, 4)}})


def _run_timed_train(step, state, batch, steps):
    """Shared timing scaffold: compile once, then time `steps` chained
    steps with both sync methods. Returns (dt_get, dt_block, last_loss)."""
    state, m = step(state, batch)
    float(jax.device_get(m["loss"]))
    latency = _measure_latency()
    holder = {"state": state, "m": m}

    def run_steps(n):
        for _ in range(n):
            holder["state"], holder["m"] = step(holder["state"], batch)
        return holder["m"]["loss"]

    dt_get, dt_block = _timed_loop(run_steps, steps, latency)
    return dt_get, dt_block, float(jax.device_get(holder["m"]["loss"]))


def _rand_batch(B, S, vocab):
    return {
        "inputs": jax.random.randint(jax.random.key(2), (B, S), 0, vocab),
        "targets": jax.random.randint(jax.random.key(3), (B, S), 0, vocab),
        "weights": jnp.ones((B, S), jnp.float32),
    }


def _bench_qlora_family(cfg, label, *, B, S, steps, lora_r=64):
    """NF4 frozen base + LoRA adapters at full family dims on the
    attached chip(s) — the measured shape for BASELINE configs that
    fine-tune with PEFT (quantize-during-init keeps the bf16 tree from
    ever materializing, models/qinit.py)."""
    from gke_ray_train_tpu.models.qinit import init_quantized_params
    from gke_ray_train_tpu.train import (
        LoraConfig, make_optimizer, make_train_step,
        train_flops_per_token, warmup_cosine_schedule)
    from gke_ray_train_tpu.train.lora import init_lora
    from gke_ray_train_tpu.train.metrics import peak_flops_per_device
    from gke_ray_train_tpu.train.step import TrainState

    devices = jax.devices()
    n_dev = len(devices)
    params = init_quantized_params(cfg, jax.random.key(0))
    lcfg = LoraConfig(r=lora_r, alpha=16)
    lora = init_lora(cfg, lcfg, jax.random.key(1))
    schedule = warmup_cosine_schedule(2e-4, 1000)
    opt = make_optimizer(schedule)
    opt_state = jax.jit(opt.init)(lora)
    state = TrainState(params=params, lora=lora, opt_state=opt_state,
                       step=jnp.zeros((), jnp.int32))
    # the timing loop re-feeds one placed batch -> no batch donation
    step = make_train_step(cfg, opt, lora_cfg=lcfg, schedule=schedule,
                           donate_batch=False)

    dt_get, dt_block, loss = _run_timed_train(
        step, state, _rand_batch(B, S, cfg.vocab_size), steps)
    tokens = B * S * steps
    tps_chip = tokens / dt_get / n_dev
    mfu = (tokens / dt_get) * train_flops_per_token(
        cfg, S, trainable="lora") / (peak_flops_per_device() * n_dev)
    _emit(
        f"tokens/sec/chip {label} (NF4 base, r={lora_r}) seq {S} "
        f"({devices[0].device_kind} x{n_dev})",
        tps_chip, "tokens/sec/chip",
        {"mfu_lora_flops": round(mfu, 4), "loss": round(loss, 4),
         "timing": {"device_get_s": round(dt_get, 4),
                    "block_until_ready_s": round(dt_block, 4)}},
        compare_baseline=False)


def _bench_lora_mode(preset_fn, name, label, tiny_overrides=None):
    """Shared scaffold for the full-family-dims NF4+LoRA modes: one
    protocol (seq 1024, B=4, 10 steps, bf16 leaves) so family rows stay
    comparable. ``tiny_overrides`` = pattern-faithful CPU-fallback dims
    (None = TPU-only mode; the flagship qlora8b shape has no meaningful
    CPU proxy)."""
    import dataclasses

    on_tpu = jax.devices()[0].platform != "cpu"
    common = dict(name=name, dtype="bfloat16", param_dtype="bfloat16",
                  remat=True, remat_policy=BENCH_REMAT_POLICY)
    if on_tpu or tiny_overrides is None:
        cfg = dataclasses.replace(preset_fn(), max_seq_len=1024, **common)
        B, S, steps = 4, 1024, 10
    else:
        cfg = dataclasses.replace(preset_fn(), **common, **tiny_overrides)
        B, S, steps = 2, 256, 2
    _bench_qlora_family(cfg, label, B=B, S=S, steps=steps)


_TINY_LORA_DIMS = dict(d_model=256, n_layers=2, n_heads=4, n_kv_heads=2,
                       d_ff=512, vocab_size=2048, max_seq_len=256)


def bench_qlora8b():
    """Flagship size on one chip: Llama-3.1-8B dims, NF4 frozen base,
    r=64 LoRA adapters trained (the reference's exact QLoRA workload,
    fine_tune_config.json)."""
    from gke_ray_train_tpu.models import llama3_8b
    _bench_lora_mode(llama3_8b, "llama3-8b-qlora-bench",
                     "Llama-3.1-8B QLoRA")


def bench_mistral7b_lora():
    """BASELINE config 4: Mistral-7B dims (sliding-window attention
    pattern) + LoRA adapters over an NF4 frozen base — the PEFT
    fine-tune shape at full family size on one chip."""
    from gke_ray_train_tpu.models import mistral_7b
    _bench_lora_mode(mistral_7b, "mistral7b-lora-bench",
                     "Mistral-7B LoRA",
                     tiny_overrides=dict(_TINY_LORA_DIMS,
                                         sliding_window=128))


def bench_qwen2_lora():
    """Qwen-2.5-7B dims (q/k/v projection bias) + LoRA over an NF4
    frozen base — same shape protocol as the Mistral row."""
    from gke_ray_train_tpu.models import qwen2_7b
    _bench_lora_mode(qwen2_7b, "qwen2-lora-bench", "Qwen-2.5-7B LoRA",
                     tiny_overrides=dict(_TINY_LORA_DIMS))


def bench_gemma2_4k():
    """BASELINE config 5 shape: Gemma-2 architectural pattern
    (sliding/global alternation, attn+logit softcaps, gelu, post-block
    norms, tied embeddings) at seq 4096 PACKED (segment-ID masks), sized
    to train full-FT on the attached chip(s)."""
    import dataclasses
    import numpy as np

    from gke_ray_train_tpu.models import gemma2_9b
    from gke_ray_train_tpu.train import (
        make_optimizer, make_train_state, make_train_step,
        train_flops_per_token, warmup_cosine_schedule)
    from gke_ray_train_tpu.train.metrics import peak_flops_per_device

    devices = jax.devices()
    n_dev = len(devices)
    on_tpu = devices[0].platform != "cpu"
    if on_tpu:
        # ~0.9B proxy with every Gemma-2 mechanism live; full 9B needs
        # the v5e-16 fsdp mesh, not one chip
        size = dict(d_model=2048, n_layers=12, n_heads=8, n_kv_heads=4,
                    d_ff=8192, vocab_size=32768, head_dim=256)
        B, S, steps = 2, 4096, 10
    else:
        size = dict(d_model=256, n_layers=2, n_heads=4, n_kv_heads=2,
                    d_ff=512, vocab_size=2048, head_dim=64)
        B, S, steps = 2, 512, 2
    cfg = dataclasses.replace(
        gemma2_9b(), name="gemma2-4k-bench", max_seq_len=S,
        dtype="bfloat16", param_dtype="float32", remat=True,
        remat_policy=BENCH_REMAT_POLICY,
        attn_scale=size["head_dim"] ** -0.5, **size)

    schedule = warmup_cosine_schedule(3e-4, 1000)
    opt = make_optimizer(schedule)
    state = make_train_state(cfg, opt, jax.random.key(0))
    # the timing loop re-feeds one placed batch -> no batch donation
    step = make_train_step(cfg, opt, schedule=schedule,
                           donate_batch=False)

    # packed rows: 4 documents per row, positions restart per segment
    seg_len = S // 4
    seg = np.repeat(np.arange(1, 5), seg_len)[None, :].repeat(B, 0)
    pos = np.tile(np.arange(seg_len), 4)[None, :].repeat(B, 0)
    batch = dict(_rand_batch(B, S, cfg.vocab_size),
                 segment_ids=jnp.asarray(seg, jnp.int32),
                 positions=jnp.asarray(pos, jnp.int32))

    dt_get, dt_block, loss = _run_timed_train(step, state, batch, steps)
    tokens = B * S * steps
    tps_chip = tokens / dt_get / n_dev
    # packed rows attend within segments only
    mfu = (tokens / dt_get) * train_flops_per_token(cfg, seg_len) / (
        peak_flops_per_device() * n_dev)
    _emit(
        f"tokens/sec/chip Gemma-2-pattern packed-seq{S} instruction-tune "
        f"({cfg.d_model}d/{cfg.n_layers}L, {devices[0].device_kind} "
        f"x{n_dev})",
        tps_chip, "tokens/sec/chip",
        {"mfu": round(mfu, 4), "loss": round(loss, 4),
         "timing": {"device_get_s": round(dt_get, 4),
                    "block_until_ready_s": round(dt_block, 4)}},
        compare_baseline=False)


def bench_seq4k():
    """BASELINE config 5 shape: packed 4k sequences (segment-ID masks),
    proxy-size model, flash attention."""
    import dataclasses
    import numpy as np

    from gke_ray_train_tpu.models import llama3_8b
    from gke_ray_train_tpu.train import (
        make_optimizer, make_train_state, make_train_step,
        train_flops_per_token, warmup_cosine_schedule)
    from gke_ray_train_tpu.train.metrics import peak_flops_per_device

    devices = jax.devices()
    n_dev = len(devices)
    on_tpu = devices[0].platform != "cpu"
    B, S, steps = (2, 4096, 10) if on_tpu else (2, 512, 2)
    size = (dict(d_model=2048, n_layers=12, n_heads=16, n_kv_heads=8,
                 d_ff=5504, vocab_size=32768) if on_tpu else
            dict(d_model=256, n_layers=2, n_heads=4, n_kv_heads=2,
                 d_ff=512, vocab_size=2048))
    cfg = dataclasses.replace(
        llama3_8b(), name="llama3-seq4k-bench", max_seq_len=S,
        dtype="bfloat16", param_dtype="float32", remat=True,
        remat_policy=BENCH_REMAT_POLICY, **size)

    schedule = warmup_cosine_schedule(3e-4, 1000)
    opt = make_optimizer(schedule)
    state = make_train_state(cfg, opt, jax.random.key(0))
    # the timing loop re-feeds one placed batch -> no batch donation
    step = make_train_step(cfg, opt, schedule=schedule,
                           donate_batch=False)

    # packed rows: 4 documents per row, positions restart per segment
    seg_len = S // 4
    seg = np.repeat(np.arange(1, 5), seg_len)[None, :].repeat(B, 0)
    pos = np.tile(np.arange(seg_len), 4)[None, :].repeat(B, 0)
    batch = dict(_rand_batch(B, S, cfg.vocab_size),
                 segment_ids=jnp.asarray(seg, jnp.int32),
                 positions=jnp.asarray(pos, jnp.int32))
    dt_get, dt_block, _loss = _run_timed_train(step, state, batch, steps)
    tokens = B * S * steps
    tps_chip = tokens / dt_get / n_dev
    # packed rows attend within segments only: attention FLOPs scale
    # with the segment length, not the packed row length
    mfu = (tokens / dt_get) * train_flops_per_token(cfg, seg_len) / (
        peak_flops_per_device() * n_dev)
    _emit(
        f"tokens/sec/chip packed-seq{S} train step "
        f"({devices[0].device_kind} x{n_dev})",
        tps_chip, "tokens/sec/chip",
        {"mfu": round(mfu, 4),
         "timing": {"device_get_s": round(dt_get, 4),
                    "block_until_ready_s": round(dt_block, 4)}},
        compare_baseline=False)


def bench_moe():
    """Mixtral-pattern MoE train step (8 experts, top-2, router aux) at
    a single-chip proxy size — the EP/MoE path's measured shape."""
    import dataclasses

    from gke_ray_train_tpu.models import mixtral_8x7b
    from gke_ray_train_tpu.train import (
        make_optimizer, make_train_state, make_train_step,
        train_flops_per_token, warmup_cosine_schedule)
    from gke_ray_train_tpu.train.metrics import peak_flops_per_device

    devices = jax.devices()
    n_dev = len(devices)
    on_tpu = devices[0].platform != "cpu"
    if on_tpu:
        # ~0.5B total / ~0.16B active with every MoE mechanism live —
        # fp32 params + Adam moments must fit 16 GB alongside the
        # dispatch/combine buffers (a 2.6B fp32 MoE needs ~31 GB)
        size = dict(d_model=1024, n_layers=8, n_heads=16, n_kv_heads=8,
                    d_ff=2048, vocab_size=32768)
        B, S, steps = 8, 1024, 10
    else:
        size = dict(d_model=128, n_layers=2, n_heads=4, n_kv_heads=2,
                    d_ff=256, vocab_size=2048)
        B, S, steps = 4, 128, 2
    cfg = dataclasses.replace(
        mixtral_8x7b(), name="moe-bench", max_seq_len=S,
        dtype="bfloat16", param_dtype="float32", remat=True,
        remat_policy=BENCH_REMAT_POLICY, **size)

    schedule = warmup_cosine_schedule(3e-4, 1000)
    opt = make_optimizer(schedule)
    state = make_train_state(cfg, opt, jax.random.key(0))
    # the timing loop re-feeds one placed batch -> no batch donation
    step = make_train_step(cfg, opt, schedule=schedule,
                           donate_batch=False)

    dt_get, dt_block, loss = _run_timed_train(
        step, state, _rand_batch(B, S, cfg.vocab_size), steps)
    tokens = B * S * steps
    tps_chip = tokens / dt_get / n_dev
    # active-param FLOPs (router + top-2 experts), ModelConfig.active_param_count
    mfu = (tokens / dt_get) * train_flops_per_token(cfg, S) / (
        peak_flops_per_device() * n_dev)
    _emit(
        f"tokens/sec/chip Mixtral-pattern MoE train step (8exp top2, "
        f"{cfg.d_model}d/{cfg.n_layers}L seq {S}, "
        f"{devices[0].device_kind} x{n_dev})",
        tps_chip, "tokens/sec/chip",
        {"mfu_active_flops": round(mfu, 4), "loss": round(loss, 4),
         "timing": {"device_get_s": round(dt_get, 4),
                    "block_until_ready_s": round(dt_block, 4)}},
        compare_baseline=False)


def bench_input_bound():
    """BENCH_MODE=input-bound: A/B the asynchronous input pipeline
    (data/prefetch.py) in the regime it targets — the host is the
    bottleneck. The REAL packing path (synthetic SQL rows → chat-format
    tokenize → pack_examples → batch_packed) produces every batch behind
    a deliberately slow host stall (a GIL-releasing per-batch sleep sized
    from the measured step time, standing in for the GCS-FUSE read), and
    feeds the real jitted train step once synchronously and once through
    the depth-2 background prefetcher (production parallelized across
    workers, delivery in order). One JSON line carries BOTH tokens/sec
    numbers; value = the speedup, so the overlap win is measured, not
    asserted."""
    import dataclasses

    from gke_ray_train_tpu.data import (
        ByteTokenizer, batch_packed, format_gretel_sql_example,
        make_batch_source, pack_examples, synthetic_sql_rows,
        tokenize_sft_example)
    from gke_ray_train_tpu.models import llama3_8b
    from gke_ray_train_tpu.parallel.mesh import MeshConfig, build_mesh
    from gke_ray_train_tpu.parallel.placement import make_place_batch
    from gke_ray_train_tpu.train import (
        make_optimizer, make_train_state, make_train_step,
        warmup_cosine_schedule)

    devices = jax.devices()
    n_dev = len(devices)
    on_tpu = devices[0].platform != "cpu"
    if on_tpu:
        size = dict(d_model=1024, n_layers=8, n_heads=16, n_kv_heads=8,
                    d_ff=2816, vocab_size=32768)
        B, S, steps = 8, 1024, 12
    else:
        size = dict(d_model=256, n_layers=2, n_heads=4, n_kv_heads=2,
                    d_ff=512, vocab_size=2048)
        B, S, steps = 4, 256, 12
    cfg = dataclasses.replace(
        llama3_8b(), name="llama3-input-bound", max_seq_len=S,
        dtype="bfloat16", param_dtype="float32", remat=True,
        remat_policy=BENCH_REMAT_POLICY, **size)

    mesh = build_mesh(MeshConfig(data=1, fsdp=-1), devices)
    schedule = warmup_cosine_schedule(3e-4, 1000)
    opt = make_optimizer(schedule)
    state = make_train_state(cfg, opt, jax.random.key(0), mesh=mesh)
    # donate=False: both arms start from the SAME initial state, so the
    # loss streams are comparable and the buffers survive arm 1
    step = make_train_step(cfg, opt, mesh=mesh, schedule=schedule,
                           donate=False)
    place = make_place_batch(mesh)

    tok = ByteTokenizer()
    rows = synthetic_sql_rows(64 * B, seed=0)
    chunk = 2 * B  # rows per batch's worth of production

    def chunks(n_batches):
        """Cheap stage: which rows feed each batch (the iterator side of
        the pipeline — a directory listing, not the read itself)."""
        for i in range(n_batches):
            lo = (i * chunk) % (len(rows) - chunk + 1)
            yield rows[lo:lo + chunk]

    def produce(row_chunk, delay_s):
        """The REAL packing path for one batch, behind an emulated
        storage stall: chat-format tokenize → greedy pack → fixed [B,S]
        rows. This is the stage the prefetcher parallelizes (the sleep
        releases the GIL exactly like the FUSE/network read it stands
        in for)."""
        time.sleep(delay_s)
        exs = (tokenize_sft_example(
            tok, format_gretel_sql_example(r), max_len=S + 1)
            for r in row_chunk)
        return next(batch_packed(pack_examples(exs, S), B,
                                 drop_last=False, seq_len=S))

    # compile once, then size the host stall from the measured step time
    # so the A/B sits squarely in the input-bound regime on any backend
    placed = place(produce(rows[:chunk], 0.0))
    st, m = step(state, placed)
    jax.block_until_ready(m["loss"])
    t0 = time.perf_counter()
    for _ in range(3):
        st, m = step(st, placed)
    jax.block_until_ready(m["loss"])
    step_s = max((time.perf_counter() - t0) / 3, 1e-4)
    delay_s = max(1.5 * step_s, 0.01)

    def run_arm(depth):
        src = make_batch_source(
            chunks(steps), depth=depth,
            place_fn=lambda c: place(produce(c, delay_s)))
        arm_state, arm_m = state, None
        t0 = time.perf_counter()
        try:
            for b in src:
                arm_state, arm_m = step(arm_state, b)
            jax.block_until_ready(arm_m["loss"])
        finally:
            src.close()
        dt = max(time.perf_counter() - t0, 1e-9)
        return B * S * steps / dt, float(jax.device_get(arm_m["loss"]))

    tps_off, loss_off = run_arm(0)
    tps_on, loss_on = run_arm(2)
    _emit(
        f"input-bound speedup prefetch-on vs prefetch-off (packed SFT "
        f"path + {delay_s * 1e3:.0f}ms/batch host stall, "
        f"{cfg.d_model}d/{cfg.n_layers}L seq {S}, "
        f"{devices[0].device_kind} x{n_dev})",
        tps_on / tps_off, "x",
        {"prefetch_on_tokens_per_sec_per_chip": round(tps_on / n_dev, 1),
         "prefetch_off_tokens_per_sec_per_chip": round(tps_off / n_dev, 1),
         "prefetch_depth": 2, "host_delay_s_per_batch": round(delay_s, 4),
         "step_time_s": round(step_s, 4),
         # determinism witness: same batches, same state → same loss
         "loss_prefetch_on": round(loss_on, 6),
         "loss_prefetch_off": round(loss_off, 6)},
        compare_baseline=False)


def bench_recovery():
    """BENCH_MODE=recovery: fault-tolerance drill on the attached
    chip(s), deterministic via testing/faults.py. Two measured numbers
    on one JSON line: value = time-to-recover (injected kill at step 6 →
    first post-resume step completion, covering restore + state rebuild
    + resume fast-forward), and the checkpoint-save latency under
    SIGTERM (the number that must fit PREEMPT_GRACE_S)."""
    import shutil
    import tempfile

    from gke_ray_train_tpu.ckpt import CheckpointManager
    from gke_ray_train_tpu.models import tiny
    from gke_ray_train_tpu.rayint import (
        FailureConfig, JaxTrainer, RunConfig)
    from gke_ray_train_tpu.testing.faults import (
        FaultInjector, parse_fault_spec, reset_fired)
    from gke_ray_train_tpu.train import (
        make_optimizer, make_train_state, make_train_step, preempt)
    from gke_ray_train_tpu.train.loop import run_training
    from gke_ray_train_tpu.train.preempt import Preempted

    devices = jax.devices()
    on_tpu = devices[0].platform != "cpu"
    if on_tpu:
        size = dict(d_model=512, n_layers=4, n_heads=8, n_kv_heads=4,
                    d_ff=1024, vocab_size=4096)
        B, S = 8, 256
    else:
        size = dict(d_model=64, n_layers=2, n_heads=2, n_kv_heads=2,
                    d_ff=128, vocab_size=256)
        B, S = 2, 32
    steps, kill_step, ckpt_every = 12, 6, 4
    cfg = tiny(**size, max_seq_len=S, dtype="float32",
               param_dtype="float32")
    opt = make_optimizer(1e-3)

    def batches(epoch):
        for i in range(steps):
            k = jax.random.key(epoch * 100 + i)
            yield {
                "inputs": jax.random.randint(k, (B, S), 0,
                                             cfg.vocab_size),
                "targets": jax.random.randint(k, (B, S), 0,
                                              cfg.vocab_size),
                "weights": jnp.ones((B, S), jnp.float32),
            }

    work = tempfile.mkdtemp(prefix="bench_recovery_")
    try:
        # ---- kill drill: time from the killed step to the first
        # post-resume step completion, through the real retry loop -----
        reset_fired()
        beats = []

        def worker(config):
            state = make_train_state(cfg, opt, jax.random.key(0))
            step_fn = make_train_step(cfg, opt, donate=False)
            mgr = CheckpointManager(
                os.path.join(work, "kill"), max_to_keep=2,
                score_attribute=None, async_save=False)
            inj = FaultInjector(
                parse_fault_spec(f"rank=0:kind=kill:step={kill_step}"),
                rank=0, ckpt_manager=mgr)
            try:
                final, last_m = run_training(
                    state, step_fn, batches, epochs=1,
                    ckpt_manager=mgr, ckpt_every=ckpt_every,
                    heartbeat_fn=lambda step, done=False: beats.append(
                        (step, time.perf_counter())),
                    fault_injector=inj)
            finally:
                mgr.close()
            # the successful (post-resume) attempt's loop timings:
            # compile_s isolates the retrace+recompile the retry paid,
            # restart_to_first_step_s additionally covers restore +
            # resume fast-forward (train/loop.py)
            return {"final_step": int(jax.device_get(final.step)),
                    "compile_s": last_m.get("compile_s"),
                    "restart_to_first_step_s":
                        last_m.get("restart_to_first_step_s")}

        res = JaxTrainer(
            worker, use_ray=False,
            run_config=RunConfig(
                failure_config=FailureConfig(max_failures=1),
                retry_backoff_s=0.0)).fit()
        if res.error or res.metrics.get("final_step") != steps:
            raise RuntimeError(f"recovery drill did not converge: {res}")
        # the restart shows up as the step sequence going backwards:
        # beats run (…, kill_step) then (resume_step+1, …) — the retry's
        # first beat is its first COMPLETED step after the resume point
        restart = next(i for i in range(1, len(beats))
                       if beats[i][0] < beats[i - 1][0])
        time_to_recover = beats[restart][1] - beats[restart - 1][1]
        resumed_step = beats[restart][0] - 1

        # ---- sigterm drill: grace-window checkpoint latency ----------
        reset_fired()
        preempt.reset()
        state = make_train_state(cfg, opt, jax.random.key(0))
        step_fn = make_train_step(cfg, opt, donate=False)
        mgr = CheckpointManager(os.path.join(work, "sigterm"),
                                max_to_keep=2, score_attribute=None,
                                async_save=False)
        inj = FaultInjector(
            parse_fault_spec(f"rank=0:kind=sigterm:step={kill_step}"),
            rank=0, ckpt_manager=mgr)
        try:
            run_training(state, step_fn, batches, epochs=1,
                         ckpt_manager=mgr, fault_injector=inj)
            raise RuntimeError("sigterm fault did not fire")
        except Preempted as p:
            sigterm_save_s = p.save_s
        finally:
            mgr.close()
            preempt.reset()
            preempt.uninstall()
    finally:
        shutil.rmtree(work, ignore_errors=True)

    # recompile vs restore split (ISSUE 4): the retry's first-step cost
    # decomposes into the retrace+recompile (compile_s — the part the
    # persistent cache / AOT sidecar eliminates) and everything else
    # (restore + state rebuild + resume fast-forward)
    recompile_s = res.metrics.get("compile_s")
    restart_s = res.metrics.get("restart_to_first_step_s")
    _emit(
        f"time-to-recover injected kill@step{kill_step} -> first "
        f"post-resume step ({cfg.d_model}d/{cfg.n_layers}L seq {S}, "
        f"{devices[0].device_kind})",
        time_to_recover, "s",
        {"sigterm_ckpt_save_s": round(sigterm_save_s, 4),
         "recompile_s": (round(recompile_s, 4)
                         if recompile_s is not None else None),
         "restore_and_ff_s": (round(restart_s - recompile_s, 4)
                              if None not in (restart_s, recompile_s)
                              else None),
         "kill_step": kill_step, "resumed_step": int(resumed_step),
         "ckpt_every": ckpt_every, "steps": steps,
         "attempts": res.attempts},
        compare_baseline=False)


def bench_elastic():
    """BENCH_MODE=elastic: the elastic-training drill (ROADMAP #1/#4)
    on the canonical 8-fake-device CPU mesh — an injected pool shrink
    8→4 at step k resumes RESHARDED on the 4-device survivors without
    human intervention, and a grow event recovers to the full 8 on the
    next attempt. One JSON line carries the two headline numbers:
    value = the run's goodput fraction (step time / total wall-clock,
    summed over attempts from the per-attempt goodput ledger), plus
    time-to-first-step-after-shrink (restore + fast-forward + compile
    of the attempt that re-formed the mesh — what an eviction actually
    costs). The record pins the full ledger, the per-attempt event
    classification (shrink/grow as preemptions, max_failures budget
    untouched) and each attempt's plan fingerprint."""
    import shutil
    import tempfile

    devices = jax.devices()
    if devices[0].platform != "cpu" or len(devices) != 8:
        # the drill is only meaningful on the canonical mesh (same
        # policy as the budget CLI): re-exec onto 8 fake CPU devices
        import subprocess

        from gke_ray_train_tpu.perf.cache import cpu_mesh_env
        env = cpu_mesh_env(BENCH_MODE="elastic")
        env.pop("GRAFT_FORCE_PROBE", None)
        sys.exit(subprocess.run(
            [sys.executable, os.path.abspath(__file__)], env=env,
            cwd=os.path.dirname(os.path.abspath(__file__))).returncode)

    import numpy as np

    from gke_ray_train_tpu.ckpt import CheckpointManager
    from gke_ray_train_tpu.models import tiny
    from gke_ray_train_tpu.parallel.placement import make_place_batch
    from gke_ray_train_tpu.plan import ExecutionPlan
    from gke_ray_train_tpu.rayint import (
        FailureConfig, JaxTrainer, RunConfig)
    from gke_ray_train_tpu.rayint.elastic import maybe_replan
    from gke_ray_train_tpu.testing.faults import (
        FaultInjector, parse_fault_spec, reset_fired, reset_pool)
    from gke_ray_train_tpu.train import (
        make_optimizer, make_train_state, make_train_step)
    from gke_ray_train_tpu.train.loop import run_training
    from gke_ray_train_tpu.train.metrics import LEDGER_TERMS

    cfg = tiny(vocab_size=256, d_model=64, n_layers=2, n_heads=2,
               n_kv_heads=2, d_ff=128, dtype="float32",
               param_dtype="float32")
    opt = make_optimizer(1e-3)
    steps, shrink_step, grow_step, ckpt_every = 12, 5, 8, 2
    B, S = 8, 32          # global batch: divisible by both pool sizes

    def batches(epoch):
        for i in range(steps):
            rng = np.random.default_rng(epoch * 1000 + i)
            yield {
                "inputs": rng.integers(
                    0, cfg.vocab_size, (B, S)).astype(np.int32),
                "targets": rng.integers(
                    0, cfg.vocab_size, (B, S)).astype(np.int32),
                "weights": np.ones((B, S), np.float32)}

    work = tempfile.mkdtemp(prefix="bench_elastic_")
    config = {"MESH_DATA": 1, "MESH_FSDP": -1,
              "PER_DEVICE_TRAIN_BATCH_SIZE": 1, "MAX_SEQ_LENGTH": S,
              "TOPOLOGY": "cpu-8", "ELASTIC": "1"}
    mesh_used = []

    def worker(c):
        plan, devs = maybe_replan(ExecutionPlan.resolve(c), config=c)
        mesh_used.append(len(devs))
        mesh = plan.build_mesh(devs)
        state = make_train_state(cfg, opt, jax.random.key(0), mesh=mesh)
        step_fn = make_train_step(cfg, opt, mesh=mesh, donate=False)
        mgr = CheckpointManager(os.path.join(work, "ckpt"),
                                max_to_keep=2, score_attribute=None,
                                async_save=False)
        inj = FaultInjector(parse_fault_spec(
            f"rank=0:kind=pool_shrink:to=4:step={shrink_step};"
            f"rank=0:kind=pool_shrink:to=8:step={grow_step}"),
            rank=0, ckpt_manager=mgr)
        try:
            final, _m = run_training(
                state, step_fn, batches, epochs=1, ckpt_manager=mgr,
                ckpt_every=ckpt_every,
                place_batch=make_place_batch(mesh), fault_injector=inj)
        finally:
            mgr.close()
        return {"final_step": int(jax.device_get(final.step))}

    reset_fired()
    reset_pool()
    try:
        res = JaxTrainer(
            worker, train_loop_config=config, use_ray=False,
            run_config=RunConfig(
                failure_config=FailureConfig(max_failures=0,
                                             max_preemptions=4),
                retry_backoff_s=0.0)).fit()
    finally:
        reset_pool()
        shutil.rmtree(work, ignore_errors=True)
    if res.error or res.metrics.get("final_step") != steps or \
            mesh_used != [8, 4, 8]:
        raise RuntimeError(
            f"elastic drill did not converge: status={res.status} "
            f"error={res.error} mesh_used={mesh_used} "
            f"metrics={res.metrics}")
    # the attempt AFTER the shrink re-formed the mesh: its restart cost
    # (restore resharded + fast-forward + recompile on the new shape)
    # is what a slice eviction actually costs before training resumes
    g_after_shrink = res.attempt_log[1]["goodput"]
    tfs = (g_after_shrink["restore_s"] + g_after_shrink["fast_forward_s"]
           + g_after_shrink["compile_s"])
    events = [{k: e.get(k) for k in ("status", "event", "pool",
                                     "resumed_step", "plan_fingerprint")
               if k in e} for e in res.attempt_log]
    _emit(
        f"elastic goodput, injected shrink 8->4->8 drill "
        f"({cfg.d_model}d/{cfg.n_layers}L seq {S}, {steps} steps, "
        f"shrink@{shrink_step} grow@{grow_step}, "
        f"{devices[0].device_kind} x8)",
        100.0 * res.goodput["goodput_frac"], "% of wall-clock",
        {"time_to_first_step_after_shrink_s": round(tfs, 4),
         "attempts": res.attempts, "preemptions": res.preemptions,
         "mesh_devices_per_attempt": mesh_used,
         "goodput": {k: round(float(v), 4)
                     for k, v in res.goodput.items()},
         "ledger_terms": list(LEDGER_TERMS),
         "events": events},
        compare_baseline=False)


def bench_compile():
    """BENCH_MODE=compile: the compile-once layer's A/B (perf/cache.py),
    meaningful with NO accelerator attached. One JSON line carries:

    - cold build: trace + lower + XLA compile with an empty persistent
      cache (what every restart paid before this layer existed);
    - warm build: identical rebuild after ``jax.clear_caches()`` — the
      compile hits the persistent cache, only trace+lower re-run;
    - AOT: ``serialize_executable`` round-trip — a deserialized
      executable skips trace AND compile (the preempted-retry path),
      verified bitwise-identical to the jit-built step;
    - the compile-level StepCostReport + cache hit/miss counters.

    value = cold/warm speedup; the acceptance gate is
    ``warm_frac_of_cold < 0.3`` (or the AOT fraction, whichever is
    smaller)."""
    import dataclasses
    import tempfile

    from gke_ray_train_tpu.models import llama3_8b
    from gke_ray_train_tpu.parallel.mesh import MeshConfig, build_mesh
    from gke_ray_train_tpu.perf.cache import (
        cache_stats, enable_persistent_cache, load_executable,
        save_executable, aot_signature)
    from gke_ray_train_tpu.perf.costs import step_cost_report
    from gke_ray_train_tpu.train import (
        make_optimizer, make_train_state, make_train_step,
        warmup_cosine_schedule)
    from gke_ray_train_tpu.train.step import batch_shardings

    devices = jax.devices()
    n_dev = len(devices)
    on_tpu = devices[0].platform != "cpu"
    if on_tpu:
        size = dict(d_model=2048, n_layers=12, n_heads=16, n_kv_heads=8,
                    d_ff=5504, vocab_size=32768)
        B, S = 8, 1024
    else:
        size = dict(d_model=256, n_layers=4, n_heads=4, n_kv_heads=2,
                    d_ff=512, vocab_size=2048)
        B, S = max(4, n_dev), 256
    cfg = dataclasses.replace(
        llama3_8b(), name="llama3-compile-bench", max_seq_len=S,
        dtype="bfloat16", param_dtype="float32", remat=True,
        remat_policy=BENCH_REMAT_POLICY, **size)
    mesh = build_mesh(MeshConfig(data=1, fsdp=-1), devices)
    schedule = warmup_cosine_schedule(3e-4, 1000)
    opt = make_optimizer(schedule)
    state = make_train_state(cfg, opt, jax.random.key(0), mesh=mesh)
    batch = jax.device_put(_rand_batch(B, S, cfg.vocab_size),
                           batch_shardings(mesh))

    cache_dir = os.environ.get("BENCH_COMPILE_CACHE_DIR")
    scratch = None
    if not cache_dir:
        # scratch dir (serialized executables + every cache entry) is
        # removed at exit — repeated bench runs must not fill /tmp; an
        # explicit BENCH_COMPILE_CACHE_DIR is kept (A/B across runs)
        cache_dir = scratch = tempfile.mkdtemp(
            prefix="bench_compile_cache_")
    enable_persistent_cache(cache_dir)
    s0 = cache_stats()

    def build():
        step = make_train_step(cfg, opt, mesh=mesh, schedule=schedule,
                               donate=False)
        t0 = time.perf_counter()
        compiled = step.lower(state, batch).compile()
        return compiled, time.perf_counter() - t0

    # cold: empty persistent cache — the full trace+lower+compile cost
    try:
        compiled_cold, cold_s = build()
        # AOT round-trip: serialize the FRESH executable, time deserialize
        aot_path = os.path.join(cache_dir, "bench_aot_step.bin")
        key = aot_signature(state, batch)
        aot = {"aot_serialized": save_executable(compiled_cold, aot_path,
                                                 key)}
        if aot["aot_serialized"]:
            t0 = time.perf_counter()
            loaded = load_executable(aot_path, key)
            deser_s = time.perf_counter() - t0
            aot["aot_deserialize_s"] = round(deser_s, 4)
            aot["aot_frac_of_cold"] = round(deser_s / cold_s, 4)
            if loaded is not None:
                _, m_a = loaded(state, batch)
                _, m_b = compiled_cold(state, batch)
                aot["aot_loss_bitwise_equal"] = bool(
                    jnp.array_equal(m_a["loss"], m_b["loss"]))
            else:
                aot["aot_deserialize_failed"] = True
        # warm: identical rebuild, in-memory jit caches dropped — the
        # compile consults the persistent cache, only trace+lower re-run
        jax.clear_caches()
        _compiled_warm, warm_s = build()
        s1 = cache_stats()
    finally:
        if scratch is not None:
            import shutil
            shutil.rmtree(scratch, ignore_errors=True)

    cost = step_cost_report(compiled_cold, tokens_per_step=B * S)
    _emit(
        f"compile-cache speedup cold vs warm train-step build "
        f"({cfg.d_model}d/{cfg.n_layers}L seq {S}, "
        f"{devices[0].device_kind} x{n_dev})",
        cold_s / max(warm_s, 1e-9), "x",
        {"cold_build_s": round(cold_s, 3),
         "warm_build_s": round(warm_s, 3),
         "warm_frac_of_cold": round(warm_s / cold_s, 4),
         "cache_hits": int(s1["hits"] - s0["hits"]),
         "cache_misses": int(s1["misses"] - s0["misses"]),
         "compile_time_saved_s": round(
             s1["compile_time_saved_s"] - s0["compile_time_saved_s"], 3),
         **aot,
         "cost_report": cost.summary()},
        compare_baseline=False)


def bench_overlap():
    """BENCH_MODE=overlap: off-vs-on A/B of the overlap execution path
    (ROADMAP #3, plan knob ``OVERLAP``). Both arms run the SAME model,
    init and batch stream through ``make_train_step``; the only delta
    is the plan's overlap mode — ``off`` (the GSPMD scan) vs ``manual``
    (the shard_map pipeline that double-buffers the per-layer FSDP
    all-gather, train/overlap.py). The record asserts the two loss
    streams are BITWISE-identical (the equivalence the manual path is
    built on) and carries each arm's compile-level overlap evidence —
    ``overlap_frac`` / ``exposed_collective_bytes`` from the scheduled
    HLO — which is the half of the claim that survives the dead
    accelerator backend. value = manual/off tokens-per-second ratio
    (on the CPU mesh the interesting number is the exposure delta, not
    wall-clock; shard_map adds trace overhead XLA:TPU amortizes)."""
    import dataclasses as _dc

    from gke_ray_train_tpu.models import tiny
    from gke_ray_train_tpu.perf.costs import step_cost_report
    from gke_ray_train_tpu.plan import ExecutionPlan
    from gke_ray_train_tpu.train import (
        make_optimizer, make_train_state, make_train_step)

    devices = jax.devices()
    n_dev = len(devices)
    on_tpu = devices[0].platform != "cpu"
    # an fsdp axis >= 2 is what gives the manual path gathers to hide
    fsdp = max(n_dev // 2, 1)
    data = n_dev // fsdp
    if on_tpu:
        size = dict(d_model=1024, n_layers=8, n_heads=8, n_kv_heads=4,
                    d_ff=2816, vocab_size=32768)
        # batch rows must tile data x fsdp = n_dev on pools > 8 chips
        B, S = max(8, n_dev), 1024
    else:
        # d_model pinned at 64 on CPU: XLA:CPU's blocked dot kernels
        # change fp32 accumulation order above that width, so the
        # bitwise off/manual equivalence (which the record asserts)
        # holds exactly on this family — GQA, 4 layers and the 1k
        # vocab still exercise every reduction class
        size = dict(d_model=64, n_layers=4, n_heads=4, n_kv_heads=2,
                    d_ff=256, vocab_size=1024)
        B, S = max(8, n_dev), 128
    cfg = tiny(max_seq_len=S, remat=True, **size)
    cfg = _dc.replace(cfg, remat_policy=BENCH_REMAT_POLICY)
    steps = 5

    def run(overlap):
        plan = ExecutionPlan.from_kwargs(
            data=data, fsdp=fsdp, per_device_batch=max(B // n_dev, 1),
            max_seq_len=S, overlap=overlap,
            donate_state=False, donate_batch=False,
            compile_cache=False, aot_train_step=False, obs=False,
            topology=f"{'v5e' if on_tpu else 'cpu'}-{n_dev}")
        mesh = plan.build_mesh(devices)
        opt = make_optimizer(3e-4)
        state = make_train_state(cfg, opt, jax.random.key(0), mesh=mesh)
        step = make_train_step(cfg, opt, mesh=mesh, plan=plan)
        batch = jax.device_put(_rand_batch(B, S, cfg.vocab_size),
                               plan.batch_shardings(mesh))
        compiled = step.lower(state, batch).compile()
        report = step_cost_report(compiled, tokens_per_step=B * S)
        # warmup (compile + first dispatch), then the timed stream
        state, m = step(state, batch)
        jax.block_until_ready(m["loss"])
        losses = []
        t0 = time.perf_counter()
        for _ in range(steps):
            state, m = step(state, batch)
            losses.append(m["loss"])
        losses = [float(v) for v in jax.device_get(losses)]
        dt = max(time.perf_counter() - t0, 1e-9)
        return losses, steps * B * S / dt / max(n_dev, 1), report

    loss_off, tps_off, rep_off = run("off")
    loss_on, tps_on, rep_on = run("manual")
    bitwise = loss_off == loss_on
    if not bitwise:
        print(f"bench overlap: LOSS STREAMS DIVERGED off={loss_off} "
              f"manual={loss_on}", file=sys.stderr)
    _emit(
        f"overlap off-vs-manual A/B ({cfg.d_model}d/{cfg.n_layers}L "
        f"seq {S}, data={data} fsdp={fsdp}, "
        f"{devices[0].device_kind} x{n_dev})",
        tps_on / max(tps_off, 1e-9), "x",
        {"tokens_per_sec_per_chip_off": round(tps_off, 1),
         "tokens_per_sec_per_chip_manual": round(tps_on, 1),
         "losses_bitwise_equal": bitwise,
         "loss_stream": loss_on,
         "overlap_frac_off": rep_off.overlap_frac,
         "overlap_frac_manual": rep_on.overlap_frac,
         "exposed_collective_bytes_off": rep_off.exposed_collective_bytes,
         "exposed_collective_bytes_manual":
             rep_on.exposed_collective_bytes,
         "collective_bytes_off": rep_off.collective_bytes,
         "collective_bytes_manual": rep_on.collective_bytes},
        compare_baseline=False)


def bench_dcn():
    """BENCH_MODE=dcn: flat-vs-hier A/B of the cross-slice gradient
    sync (plan knobs ``DCN_SYNC``/``DCN_COMPRESS``,
    parallel/hierarchical.py) on the emulated 2-slice hybrid mesh —
    the canonical 8-fake-device CPU mesh split 2 x 4 with the data
    axis spanning the slices (the PR-5 contract test_mesh.py pins).
    Both arms run the SAME model, init and batch stream through
    ``make_train_step`` with ``OVERLAP=manual``; the only delta is the
    cross-slice reduction: flat sends the full gradient payload over
    DCN, hier the 1/ici_size scattered shard. The record asserts the
    two loss streams BITWISE-identical (the shared slice-staged
    accumulation grouping) and carries each arm's compile-level
    network evidence — ``ici_bytes``/``dcn_bytes``/``overlap_frac``
    from the scheduled HLO + replica-group parse — the half of the
    claim that survives the dead accelerator backend. value =
    dcn_bytes(flat)/dcn_bytes(hier), the DCN traffic shrink factor
    (~= ici_size; wall-clock is meaningless for a DCN claim on one
    host)."""
    import dataclasses as _dc

    devices = jax.devices()
    if devices[0].platform != "cpu" or len(devices) != 8:
        # the emulated 2-slice layout is only meaningful on the
        # canonical mesh (same policy as bench_elastic): re-exec
        import subprocess

        from gke_ray_train_tpu.perf.cache import cpu_mesh_env
        env = cpu_mesh_env(BENCH_MODE="dcn")
        env.pop("GRAFT_FORCE_PROBE", None)
        sys.exit(subprocess.run(
            [sys.executable, os.path.abspath(__file__)], env=env,
            cwd=os.path.dirname(os.path.abspath(__file__))).returncode)

    from gke_ray_train_tpu.models import tiny
    from gke_ray_train_tpu.perf.costs import step_cost_report
    from gke_ray_train_tpu.plan import ExecutionPlan
    from gke_ray_train_tpu.train import (
        make_optimizer, make_train_state, make_train_step)

    n_dev = len(devices)
    # d_model pinned at 64 on CPU (the bitwise-verified family, see
    # bench_overlap); GQA + 4 layers + 1k vocab exercise every
    # reduction class; grad_accum=2 exercises the accum-scan carry the
    # compressed arm threads its residual through
    size = dict(d_model=64, n_layers=4, n_heads=4, n_kv_heads=2,
                d_ff=256, vocab_size=1024)
    B, S, accum, steps = 16, 128, 2, 5
    cfg = tiny(max_seq_len=S, remat=True, **size)
    cfg = _dc.replace(cfg, remat_policy=BENCH_REMAT_POLICY)

    def run(dcn_sync, dcn_compress="none"):
        plan = ExecutionPlan.from_kwargs(
            data=2, fsdp=n_dev // 2, num_slices=2,
            per_device_batch=B // n_dev // accum, grad_accum=accum,
            max_seq_len=S, overlap="manual", dcn_sync=dcn_sync,
            dcn_compress=dcn_compress,
            donate_state=False, donate_batch=False,
            compile_cache=False, aot_train_step=False, obs=False,
            topology=f"cpu-{n_dev}")
        mesh = plan.build_mesh(devices)
        opt = make_optimizer(3e-4)
        state = make_train_state(cfg, opt, jax.random.key(0), mesh=mesh)
        step = make_train_step(cfg, opt, mesh=mesh, plan=plan)
        batch = jax.device_put(_rand_batch(B, S, cfg.vocab_size),
                               plan.batch_shardings(mesh))
        compiled = step.lower(state, batch).compile()
        report = step_cost_report(compiled, tokens_per_step=B * S,
                                  num_slices=2)
        losses = []
        for _ in range(steps):
            state, m = step(state, batch)
            losses.append(m["loss"])
        return [float(v) for v in jax.device_get(losses)], report

    loss_flat, rep_flat = run("flat")
    loss_hier, rep_hier = run("hier")
    loss_comp, rep_comp = run("hier", "bf16")
    bitwise = loss_flat == loss_hier
    if not bitwise:
        print(f"bench dcn: LOSS STREAMS DIVERGED flat={loss_flat} "
              f"hier={loss_hier}", file=sys.stderr)
    comp_close = all(abs(a - b) <= 0.05 * max(abs(b), 1e-9)
                     for a, b in zip(loss_comp, loss_flat))
    _emit(
        f"dcn flat-vs-hier gradient sync A/B ({cfg.d_model}d/"
        f"{cfg.n_layers}L seq {S}, emulated 2-slice 2x{n_dev // 2} "
        f"hybrid mesh, grad_accum={accum})",
        rep_flat.dcn_bytes / max(rep_hier.dcn_bytes, 1), "x",
        {"losses_bitwise_equal": bitwise,
         "loss_stream": loss_hier,
         "compressed_loss_stream": loss_comp,
         "compressed_within_5pct": comp_close,
         "dcn_bytes_flat": rep_flat.dcn_bytes,
         "dcn_bytes_hier": rep_hier.dcn_bytes,
         "dcn_bytes_compressed": rep_comp.dcn_bytes,
         "ici_bytes_flat": rep_flat.ici_bytes,
         "ici_bytes_hier": rep_hier.ici_bytes,
         "overlap_frac_flat": rep_flat.overlap_frac,
         "overlap_frac_hier": rep_hier.overlap_frac,
         "collective_bytes_flat": rep_flat.collective_bytes,
         "collective_bytes_hier": rep_hier.collective_bytes},
        compare_baseline=False)


def bench_autotune():
    """BENCH_MODE=autotune: default-vs-tuned A/B through the autotune
    search (autotune/) on the canonical 8-fake-device CPU mesh (re-execs
    itself there, like the dcn/elastic modes). One record carries the
    search verdict AND the evidence: the winner found over the
    tiny_fsdp8 base plan, per-arm StepCostReport summaries + exposed
    collective bytes + plan fingerprints, modeled step times from the
    same ChipSpec scorer the registry persists, and both arms' REAL
    5-step loss streams — the tuned arm's trajectory asserted valid
    against the default arm's shape (finite, decreasing, within
    tolerance of the default stream: a tuned plan that "wins" the cost
    model by wrecking the optimization trajectory must fail here).
    value = modeled step-time improvement (default / tuned; >= 1.0 by
    construction — the default is candidate 0 of its own space)."""
    import dataclasses as _dc

    devices = jax.devices()
    if devices[0].platform != "cpu" or len(devices) != 8:
        import subprocess

        from gke_ray_train_tpu.perf.cache import cpu_mesh_env
        env = cpu_mesh_env(BENCH_MODE="autotune")
        env.pop("GRAFT_FORCE_PROBE", None)
        sys.exit(subprocess.run(
            [sys.executable, os.path.abspath(__file__)], env=env,
            cwd=os.path.dirname(os.path.abspath(__file__))).returncode)

    from gke_ray_train_tpu.autotune.search import search
    from gke_ray_train_tpu.autotune.space import TUNABLE_FIELDS
    from gke_ray_train_tpu.perf.budget import (
        plan_for_preset, preset_model_cfg)
    from gke_ray_train_tpu.train import (
        make_optimizer, make_train_state, make_train_step)

    base = plan_for_preset("tiny_fsdp8")
    cfg = preset_model_cfg("tiny_fsdp8")
    # the compile-heavy dims; batch/prefetch arms cannot move the score
    # on this space (product 1 / operational) and flash has no Pallas
    # attention grid on the cpu family
    result = search(base, cfg, dims=["mesh", "sync", "fused"])
    tuned = _dc.replace(base, **{
        f: result["winner_tuned_fields"][f]
        for f in TUNABLE_FIELDS["train"]})

    steps = 5
    B, S = base.global_batch(), base.max_seq_len

    def run_arm(plan):
        mesh = plan.build_mesh(devices)
        opt = make_optimizer(3e-4)
        state = make_train_state(cfg, opt, jax.random.key(0), mesh=mesh)
        step = make_train_step(cfg, opt, mesh=mesh, plan=plan)
        batch = jax.device_put(_rand_batch(B, S, cfg.vocab_size),
                               plan.batch_shardings(mesh))
        state, m = step(state, batch)          # compile + warmup
        jax.block_until_ready(m["loss"])
        losses = []
        t0 = time.perf_counter()
        for _ in range(steps):
            state, m = step(state, batch)
            losses.append(m["loss"])
        losses = [float(v) for v in jax.device_get(losses)]
        dt = max(time.perf_counter() - t0, 1e-9)
        return losses, steps * B * S / dt / len(devices), dt / steps

    loss_default, tps_default, step_s_default = run_arm(base)
    loss_tuned, tps_tuned, step_s_tuned = run_arm(tuned)
    # trajectory-shape assertion: finite, decreasing like the default,
    # and pointwise within 5% of the default stream (the arms share
    # init, data and global batch; only the partitioning differs)
    import math as _math
    valid = (all(_math.isfinite(v) for v in loss_tuned)
             and loss_tuned[-1] < loss_tuned[0]
             and loss_default[-1] < loss_default[0]
             and all(abs(t - d) <= 0.05 * max(abs(d), 1e-9)
                     for t, d in zip(loss_tuned, loss_default)))
    if not valid:
        print(f"bench autotune: TUNED LOSS TRAJECTORY INVALID "
              f"default={loss_default} tuned={loss_tuned}",
              file=sys.stderr)
    _emit(
        f"autotune default-vs-tuned modeled step time "
        f"({result['space']['scored']} candidates scored / "
        f"{result['space']['compiled']} compiled over tiny_fsdp8, "
        f"{devices[0].device_kind} x{len(devices)})",
        result["improvement"], "x",
        {"modeled_step_s_default":
             result["base"]["score"]["modeled_step_s"],
         "modeled_step_s_tuned":
             result["winner"]["score"]["modeled_step_s"],
         "winner_diff": result["winner"]["diff"],
         "plan_fingerprint_default": result["base"]["plan_fingerprint"],
         "plan_fingerprint_tuned": result["winner"]["plan_fingerprint"],
         # the MEASURED half of the calibration loop (obs/observe.py
         # reads these per-arm fields back out of the obs-dir copy of
         # this record; `autotune ingest` turns them into observed
         # registry rows keyed by the per-arm fingerprints above)
         "measured_step_s_default": round(step_s_default, 6),
         "measured_step_s_tuned": round(step_s_tuned, 6),
         "steps": steps,
         "topology": base.topology,
         "exposed_collective_bytes_default":
             result["base"]["report"]["exposed_collective_bytes"],
         "exposed_collective_bytes_tuned":
             result["winner"]["report"]["exposed_collective_bytes"],
         "cost_report_default": result["base"]["report"],
         "cost_report_tuned": result["winner"]["report"],
         "loss_stream_default": loss_default,
         "loss_stream_tuned": loss_tuned,
         "loss_trajectory_valid": valid,
         "tokens_per_sec_per_chip_default": round(tps_default, 1),
         "tokens_per_sec_per_chip_tuned": round(tps_tuned, 1),
         "space": result["space"]},
        compare_baseline=False)


def bench_serve():
    """BENCH_MODE=serve: the continuous-batching engine A/B
    (serve/engine.py). One JSON line carries BOTH serving throughputs —
    iteration-level continuous batching across ``MAX_BATCH`` slots vs
    batch-size-1 serial greedy (the pre-serve comparison path) over the
    SAME request set; value = the speedup, so the batching win is
    measured, not asserted. The record also carries p50/p99 per-token
    latency, mean batch occupancy, slot refill count, and the decode
    executable's StepCostReport (perf/costs.py) — the numbers that
    survive the dead accelerator backend."""
    import dataclasses

    import numpy as np

    from gke_ray_train_tpu.models import (
        greedy_generate_cached, init_params, llama3_8b)
    from gke_ray_train_tpu.plan import ExecutionPlan
    from gke_ray_train_tpu.serve.engine import BatchEngine, Request

    devices = jax.devices()
    n_dev = len(devices)
    on_tpu = devices[0].platform != "cpu"
    if on_tpu:
        size = dict(d_model=2048, n_layers=12, n_heads=16, n_kv_heads=8,
                    d_ff=5504, vocab_size=32768)
        bucket, max_new = 512, 96
    else:
        size = dict(d_model=256, n_layers=2, n_heads=4, n_kv_heads=2,
                    d_ff=512, vocab_size=2048)
        bucket, max_new = 128, 24
    # env dialect wins (MAX_BATCH / DECODE_BUCKETS / SERVE_QUANT tune
    # the A/B without editing this file); backend-sized defaults apply
    # only for knobs the env leaves unset. AOT stays ON so warm_up()
    # actually builds the executables — the timed arm must measure
    # serving, not compilation (and the cost report needs the AOT
    # executable to introspect).
    overrides = {"aot_train_step": True}
    if "MAX_BATCH" not in os.environ:
        overrides["max_batch"] = 8 if on_tpu else 4
    if "DECODE_BUCKETS" not in os.environ:
        overrides["decode_buckets"] = str(bucket)
    plan = ExecutionPlan.resolve(**overrides)
    buckets = plan.bucket_list()
    # the model's window follows the plan: max_seq_len = the LARGEST
    # declared bucket, so an env DECODE_BUCKETS of any widths just
    # works (every bucket usable, none silently dropped)
    cfg = dataclasses.replace(
        llama3_8b(), name="llama3-serve-bench", max_seq_len=buckets[-1],
        dtype="bfloat16" if on_tpu else "float32",
        param_dtype="bfloat16" if on_tpu else "float32",
        remat=False, **size)
    params = init_params(cfg, jax.random.key(0))
    eos_id = 2
    engine = BatchEngine(params, cfg, plan=plan, eos_ids=(eos_id,))
    engine.warm_up()
    cost = engine.decode_cost_report()

    rng = np.random.default_rng(0)
    n_requests = 4 * engine.max_batch
    # prompts sized to the SMALLEST bucket so every request is
    # servable under any env bucket list
    max_new = min(max_new, max(buckets[-1] - 16, 1))
    max_prompt = max(buckets[0] - max_new, 16)
    reqs = [Request(rid=f"r{i}",
                    token_ids=rng.integers(
                        3, cfg.vocab_size,
                        size=int(rng.integers(8, max(max_prompt // 2, 9)))
                    ).astype(np.int32),
                    max_new_tokens=max_new)
            for i in range(n_requests)]

    # arm A: continuous batching (compile excluded via warm_up above)
    t0 = time.perf_counter()
    comps = engine.run_until_drained(reqs)
    dt_cont = max(time.perf_counter() - t0, 1e-9)
    gen_cont = sum(c.length - c.prompt_len for c in comps)
    stats = engine.stats()

    # arm B: batch-size-1 serial greedy over the SAME requests (the
    # sequential oracle the engine is bitwise-tested against)
    def serial_one(r):
        # the same bucket the engine routed this request to — the
        # bitwise-equal premise behind reusing the engine's token
        # counts holds per bucket width
        from gke_ray_train_tpu.serve.bucketing import (
            form_prompt_buffer, pick_bucket)
        w = pick_bucket(len(r.token_ids), r.max_new_tokens, buckets)
        buf, _ = form_prompt_buffer(r.token_ids, w)
        # engine.params, NOT params: with SERVE_QUANT set the engine
        # serves the quantized tree — the arms must run the same model
        # or the bitwise-equal premise (and the copied token counts)
        # breaks
        out = greedy_generate_cached(
            engine.params, jnp.asarray(buf),
            jnp.asarray([len(r.token_ids)], jnp.int32), cfg,
            max_new_tokens=r.max_new_tokens, eos_ids=(eos_id,))
        return np.asarray(out[0]), len(r.token_ids)

    serial_one(reqs[0])                     # compile outside the clock
    t0 = time.perf_counter()
    for r in reqs:
        serial_one(r)
    dt_serial = max(time.perf_counter() - t0, 1e-9)
    # both arms are bitwise-identical (the drilled contract), so the
    # engine's exact per-request counts ARE the serial arm's counts —
    # re-inferring them from the raw buffer (zero can be a legitimate
    # token id) would bias the A/B
    gen_serial = gen_cont

    tps_cont = gen_cont / dt_cont / n_dev
    tps_serial = gen_serial / dt_serial / n_dev
    _emit(
        f"serve speedup continuous-batching (batch {engine.max_batch}) "
        f"vs serial batch-1 greedy ({cfg.d_model}d/{cfg.n_layers}L, "
        f"buckets {plan.decode_buckets}, {n_requests} requests, "
        f"{devices[0].device_kind} x{n_dev})",
        tps_cont / tps_serial, "x",
        {"continuous_tokens_per_sec_per_chip": round(tps_cont, 1),
         "serial_tokens_per_sec_per_chip": round(tps_serial, 1),
         "generated_tokens": int(gen_cont),
         "max_batch": engine.max_batch,
         "decode_buckets": plan.decode_buckets,
         "serve_quant": plan.serve_quant,
         "p50_token_latency_s": round(stats["p50_token_latency_s"], 5),
         "p99_token_latency_s": round(stats["p99_token_latency_s"], 5),
         "batch_occupancy": round(stats["batch_occupancy"], 4),
         "slot_refills": int(engine.refills),
         "decode_iterations": int(stats["iterations"]),
         "decode_cost_report": (cost.summary() if cost is not None
                                else None)},
        compare_baseline=False)

    _bench_serve_multilora(plan, cfg, engine.params, eos_id, n_dev)
    _bench_serve_speculative(plan, cfg, engine.params, eos_id, n_dev)


def _bench_serve_multilora(base_plan, cfg, params, eos_id, n_dev):
    """BENCH_MODE=serve multi-tenant arm (ISSUE 17): batched multi-LoRA
    decode — ONE mixed-tenant engine over a stacked adapter pool vs the
    pre-pool baseline of one single-adapter engine per tenant, run
    serially over the SAME requests. Three claims land on record:
    bitwise-identical outputs per request, ZERO decode recompiles after
    warmup across tenant churn in the batch, and the tokens/sec win
    (asserted >= 1.3x — the whole point of sharing the [max_batch, 1]
    decode across tenants is that an iteration costs the same no matter
    whose adapters are in it)."""
    import dataclasses

    import numpy as np

    from gke_ray_train_tpu.analysis.jaxprcheck import RecompileDetector
    from gke_ray_train_tpu.serve.adapters import AdapterPool
    from gke_ray_train_tpu.serve.engine import BatchEngine, Request
    from gke_ray_train_tpu.train.lora import LoraConfig, init_lora

    lcfg = LoraConfig(r=4, alpha=8)

    def tenant_tree(seed):
        # init_lora starts adapters at identity (b = 0); give every
        # tenant a distinct NON-zero delta so bitwise equality between
        # the arms is a real claim about adapter routing
        t = init_lora(cfg, lcfg, jax.random.key(seed))
        leaves, treedef = jax.tree.flatten(t)
        ks = jax.random.split(jax.random.key(seed + 1), len(leaves))
        return jax.tree.unflatten(treedef, [
            0.02 * jax.random.normal(k, l.shape, l.dtype)
            for k, l in zip(ks, leaves)])

    n_tenants = min(6, base_plan.max_adapters)
    tenants = {f"tenant{i}": tenant_tree(100 + 2 * i)
               for i in range(n_tenants)}
    pool = AdapterPool.from_template(
        next(iter(tenants.values())),
        max_adapters=base_plan.max_adapters)
    for aid, tree in tenants.items():
        pool.register(aid, tree)

    buckets = base_plan.bucket_list()
    max_new = min(24, max(buckets[0] - 24, 8))
    rng = np.random.default_rng(1)
    reqs = []
    for i in range(2 * n_tenants):   # 2 requests per tenant, few
        aid = f"tenant{i % n_tenants}"    # requests each — the shape
        plen = int(rng.integers(8, max(buckets[0] - max_new, 9)))
        reqs.append(Request(
            rid=f"ml{i}", adapter_id=aid,
            token_ids=rng.integers(3, cfg.vocab_size,
                                   size=plen).astype(np.int32),
            max_new_tokens=max_new))

    mixed = BatchEngine(params, cfg, plan=base_plan, eos_ids=(eos_id,),
                        adapters=pool, lora_scale=lcfg.scale)
    mixed.warm_up()
    with RecompileDetector() as det:
        t0 = time.perf_counter()
        comps_mixed = mixed.run_until_drained(reqs)
        dt_mixed = max(time.perf_counter() - t0, 1e-9)
    recompiles = det.findings()
    assert not recompiles, (
        "mixed-tenant decode recompiled after warmup: " +
        "; ".join(recompiles))

    # baseline: one single-adapter engine per tenant, drained serially
    # (warmed outside the clock — the A/B measures serving, and a
    # production per-adapter deployment would also be warm)
    serial_engines = {
        aid: BatchEngine(params, cfg, plan=base_plan,
                         eos_ids=(eos_id,), lora=tree,
                         lora_scale=lcfg.scale)
        for aid, tree in tenants.items()}
    for e in serial_engines.values():
        e.warm_up()
    t0 = time.perf_counter()
    comps_serial = []
    for aid, e in serial_engines.items():
        comps_serial.extend(e.run_until_drained(
            [dataclasses.replace(r, adapter_id=None) for r in reqs
             if r.adapter_id == aid]))
    dt_serial = max(time.perf_counter() - t0, 1e-9)

    by_rid = {c.rid: list(c.generated) for c in comps_serial}
    for c in comps_mixed:
        assert list(c.generated) == by_rid[c.rid], (
            f"mixed-tenant output for {c.rid} (adapter {c.adapter_id}) "
            "diverged from its single-adapter engine")

    gen = sum(c.length - c.prompt_len for c in comps_mixed)
    tps_mixed = gen / dt_mixed / n_dev
    tps_serial = gen / dt_serial / n_dev
    speedup = tps_mixed / tps_serial
    assert speedup >= 1.3, (
        f"multi-tenant batching speedup {speedup:.2f}x < 1.3x over "
        "per-adapter serial engines")
    stats = mixed.stats()
    _emit(
        f"serve speedup batched multi-LoRA ({n_tenants} tenants, pool "
        f"of {base_plan.max_adapters}) vs per-adapter serial engines "
        f"({len(reqs)} requests, batch {mixed.max_batch})",
        speedup, "x",
        {"mixed_tokens_per_sec_per_chip": round(tps_mixed, 1),
         "serial_tokens_per_sec_per_chip": round(tps_serial, 1),
         "generated_tokens": int(gen),
         "n_tenants": n_tenants,
         "max_adapters": base_plan.max_adapters,
         "adapter_hits": int(stats["adapter_hits"]),
         "adapter_misses": int(stats["adapter_misses"]),
         "adapter_evictions": int(stats["adapter_evictions"]),
         "bitwise_vs_per_adapter": True,
         "decode_recompiles_after_warmup": 0},
        compare_baseline=False)


def _bench_serve_speculative(base_plan, cfg, params, eos_id, n_dev):
    """BENCH_MODE=serve speculative arm (ISSUE 17): self-draft
    speculative decoding (SPEC_DRAFT=self — the draft IS the target, so
    every proposal verifies and the arm witnesses the mechanism's exact
    ceiling) vs the plain engine over the SAME requests. The on-record
    claims: bitwise-identical outputs, the acceptance rate, and the
    decode-iteration reduction (the wall win on real hardware needs a
    cheaper draft; the CPU A/B pins correctness + iteration
    arithmetic)."""
    import dataclasses

    import numpy as np

    from gke_ray_train_tpu.serve.engine import BatchEngine, Request

    spec_k = base_plan.spec_k or 4
    plan_spec = dataclasses.replace(base_plan, spec_draft="self",
                                    spec_k=spec_k)
    buckets = base_plan.bucket_list()
    max_new = min(24, max(buckets[0] - 16 - spec_k, 8))
    # speculative routing needs headroom for the verify window:
    # prompt + max_new + spec_k must fit the bucket
    max_prompt = max(buckets[0] - max_new - spec_k, 9)
    rng = np.random.default_rng(2)
    reqs = [Request(rid=f"sp{i}",
                    token_ids=rng.integers(
                        3, cfg.vocab_size,
                        size=int(rng.integers(8, max_prompt))
                    ).astype(np.int32),
                    max_new_tokens=max_new)
            for i in range(8)]

    plain = BatchEngine(params, cfg, plan=base_plan, eos_ids=(eos_id,))
    plain.warm_up()
    t0 = time.perf_counter()
    comps_plain = plain.run_until_drained(reqs)
    dt_plain = max(time.perf_counter() - t0, 1e-9)

    spec = BatchEngine(params, cfg, plan=plan_spec, eos_ids=(eos_id,))
    spec.warm_up()
    t0 = time.perf_counter()
    comps_spec = spec.run_until_drained(reqs)
    dt_spec = max(time.perf_counter() - t0, 1e-9)

    by_rid = {c.rid: list(c.generated) for c in comps_plain}
    for c in comps_spec:
        assert list(c.generated) == by_rid[c.rid], (
            f"speculative output for {c.rid} diverged from plain "
            "greedy decode")

    gen = sum(c.length - c.prompt_len for c in comps_spec)
    s_plain, s_spec = plain.stats(), spec.stats()
    proposed = int(s_spec["spec_proposed"])
    accepted = int(s_spec["spec_accepted"])
    iter_ratio = s_plain["iterations"] / max(s_spec["iterations"], 1)
    _emit(
        f"serve speculative decode iteration reduction (self-draft, "
        f"K={spec_k}, {len(reqs)} requests) vs plain greedy",
        iter_ratio, "x",
        {"plain_iterations": int(s_plain["iterations"]),
         "spec_iterations": int(s_spec["iterations"]),
         "spec_proposed": proposed,
         "spec_accepted": accepted,
         "acceptance_rate": round(accepted / max(proposed, 1), 4),
         "generated_tokens": int(gen),
         "plain_tokens_per_sec_per_chip": round(
             gen / dt_plain / n_dev, 1),
         "spec_tokens_per_sec_per_chip": round(
             gen / dt_spec / n_dev, 1),
         "bitwise_vs_plain": True},
        compare_baseline=False)


def bench_decode():
    """KV-cache greedy decode tokens/sec (models/kvcache.py)."""
    import dataclasses

    from gke_ray_train_tpu.models import greedy_generate_cached, llama3_8b
    from gke_ray_train_tpu.models import init_params

    devices = jax.devices()
    on_tpu = devices[0].platform != "cpu"
    cfg = dataclasses.replace(
        llama3_8b(), name="llama3-decode-bench",
        d_model=2048, n_layers=12, n_heads=16, n_kv_heads=8, d_ff=5504,
        vocab_size=32768, max_seq_len=1024,
        dtype="bfloat16", param_dtype="bfloat16", remat=False)
    if not on_tpu:
        cfg = dataclasses.replace(cfg, d_model=256, n_layers=2, n_heads=4,
                                  n_kv_heads=2, d_ff=512, vocab_size=2048)
    params = init_params(cfg, jax.random.key(0))
    B, Lp, new = 1, 512, 128
    prompt = jnp.zeros((B, Lp + new), jnp.int32).at[:, :Lp].set(
        jax.random.randint(jax.random.key(1), (B, Lp), 1, cfg.vocab_size))
    lens = jnp.full((B,), Lp, jnp.int32)

    out = greedy_generate_cached(params, prompt, lens, cfg,
                                 max_new_tokens=new)
    jax.device_get(out)
    latency = _measure_latency()
    t0 = time.perf_counter()
    out = greedy_generate_cached(params, prompt, lens, cfg,
                                 max_new_tokens=new)
    jax.device_get(out)
    dt = max(time.perf_counter() - t0 - latency, 1e-9)
    _emit(
        f"decode tokens/sec KV-cache greedy ({cfg.d_model}d/"
        f"{cfg.n_layers}L, prompt {Lp} + {new} new, "
        f"{devices[0].device_kind})",
        new * B / dt, "tokens/sec", {}, compare_baseline=False)


def main():
    mode = os.environ.get("BENCH_MODE", "train")
    {"train": bench_train, "qlora8b": bench_qlora8b,
     "mistral7b-lora": bench_mistral7b_lora,
     "gemma2-4k": bench_gemma2_4k,
     "seq4k": bench_seq4k, "moe": bench_moe,
     "qwen2-lora": bench_qwen2_lora,
     "input-bound": bench_input_bound,
     "recovery": bench_recovery,
     "compile": bench_compile,
     "elastic": bench_elastic,
     "decode": bench_decode,
     "overlap": bench_overlap,
     "dcn": bench_dcn,
     "autotune": bench_autotune,
     "serve": bench_serve}[mode]()


if __name__ == "__main__":
    main()

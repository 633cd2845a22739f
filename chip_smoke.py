"""Chip smoke: the fine-tune job, once, on the attached TPU.

Drives ``ray-jobs/fine_tune_llama_ray.py`` through ``JaxTrainer.fit()`` in
THIS process — train, eval, save, restore, then the post-train serving
smoke — with ``ray-jobs/fine_tune_config_offline_8b.json`` as shipped
(Llama-3.1-8B widths, NF4 base + r=64 LoRA, seq 1024, per-device batch 2,
grad-accum 4, ``OVERLAP: "xla"``). Only run-length and location keys are
overridden; depth is not cut. Weights are random from a seed, rows are
synthetic, the tokenizer is ``ByteTokenizer``: the machine has no network.

Exit code 0 only when every phase ran and every check in
:func:`check_run` held. Then stdout ends with two JSON lines: the summary
(device, steps, compile and cache facts, serve stats; it ends with
``"claim": null`` — set-up facts, not benchmark numbers) and, last, the
result ``{"ok": true, "device": {"platform", "kind", "count"}}`` with
exactly those keys. No TPU, a phase that raised, or a failed check exit
non-zero with the reason on stderr and neither line.

    python chip_smoke.py            # one process per chip host; never
                                    # launch it from a parent that has
                                    # already touched jax

The final export (host-side merge of the 8B LoRA into ~30 GB of fp32,
then 15 GB of safetensors) needs about 49 GB of host RAM and does not
fit the 40 GiB chip host, so the smoke runs the entry's other three
parts and reports ``"export": "not run"`` (CHANGES.md, PR 21).
"""

from __future__ import annotations

import importlib.util
import json
import logging
import math
import os
import resource
import shutil
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
ENTRY = os.path.join(REPO, "ray-jobs", "fine_tune_llama_ray.py")
CONFIG = os.path.join(REPO, "ray-jobs", "fine_tune_config_offline_8b.json")
# scratch disk, outside the checkout (nothing here is copied back)
OUT_DIR = os.path.join(tempfile.gettempdir(), "tpu_ray_train_chip_smoke")
OPT_STEPS = 4


class SmokeFailure(Exception):
    """A phase raised or a check did not hold."""


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class _Collect(logging.Handler):
    """Keeps the messages of the records it sees."""

    def __init__(self, level=logging.NOTSET):
        super().__init__(level)
        self.messages: list = []

    def emit(self, record):
        self.messages.append(record.getMessage())


def smoke_config(n_devices: int, family: str) -> dict:
    """The shipped config with run length and locations overridden —
    every width, the sequence length, the batch and OVERLAP stay."""
    with open(CONFIG) as f:
        config = json.load(f)
    global_batch = (config["PER_DEVICE_TRAIN_BATCH_SIZE"] * n_devices
                    * config["GRADIENT_ACCUMULATION_STEPS"])
    config.update(
        NUM_TRAIN_SAMPLES=OPT_STEPS * global_batch,
        NUM_EVAL_SAMPLES=2 * global_batch,
        LOGGING_STEPS=1,
        EVAL_STEPS_SFT=OPT_STEPS // 2,
        SAVE_STEPS_SFT=OPT_STEPS // 2,
        TOPOLOGY=f"{family}-{n_devices}",
        SERVE_AFTER_TRAIN=True,
        OUTPUT_DIR_BASE=OUT_DIR,
        # JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache — not
        # the cluster's PVC path the shipped config names
        COMPILE_CACHE_DIR=None,
    )
    return config


def shard_devices(leaf) -> list:
    return sorted({s.device.id for s in leaf.addressable_shards})


def check_run(result, run, served, warnings, n_devices: int) -> dict:
    """Every check of the smoke; returns the facts for the summary."""
    import jax

    from gke_ray_train_tpu.models.transformer import resolve_seq_impl
    from gke_ray_train_tpu.obs.events import iter_events
    from gke_ray_train_tpu.ops.flash_attention import interpret_default
    from gke_ray_train_tpu.ops.quant import is_qtensor
    from gke_ray_train_tpu.perf.cache import (
        DEFAULT_CACHE_DIR, GuardedStep, cache_stats)
    from gke_ray_train_tpu.train.metrics import peak_flops_per_device

    require(result.error is None, f"trainer: {result.error}")
    require(run is not None, "the worker never returned a run")
    facts: dict = {}

    # -- train / eval: the obs event stream the loop itself wrote ------
    events = list(iter_events(os.path.join(OUT_DIR, "obs")))
    steps = [e for e in events if e["kind"] == "step"]
    require(len(steps) >= 3, f"{len(steps)} logged optimizer steps < 3")
    for e in steps:
        for key in ("loss", "grad_norm", "mfu"):
            require(math.isfinite(e.get(key, math.nan)),
                    f"step {e['step']}: {key}={e.get(key)!r}")
    evals = [e for e in events if e["kind"] == "eval"]
    require(bool(evals), "no eval ran")
    eval_loss = evals[0]["metrics"]["eval_loss"]
    require(math.isfinite(eval_loss), f"eval_loss={eval_loss!r}")
    first = next(e for e in events if e["kind"] == "first_step")
    peak_flops_per_device()         # device_kind is in the peak table
    facts.update(
        steps=[e["step"] for e in steps],
        loss=[round(e["loss"], 4) for e in steps],
        grad_norm=[round(e["grad_norm"], 4) for e in steps],
        mfu_logged=[round(e["mfu"], 4) for e in steps],
        # host clock between consecutive log lines (each ends in a
        # device fetch); eval and save pauses fall inside some gaps
        step_gap_s=[round(b["ts"] - a["ts"], 2)
                    for a, b in zip(steps, steps[1:])],
        eval_loss=round(eval_loss, 4),
        first_step_call_s=round(first["compile_s"], 2))

    # -- save / restore ------------------------------------------------
    mgr = run.ckpt_manager
    latest = mgr.latest_step()
    require(latest is not None, "no checkpoint step exists")
    save_view = run.ckpt_view[0] if run.ckpt_view else (lambda st: st)
    restored, resumed = mgr.restore_if_available(save_view(run.state))
    require(resumed == latest,
            f"restore_if_available returned step {resumed}, latest {latest}")
    del restored
    facts["ckpt_step"] = latest

    # -- the attention that ran is the compiled Pallas kernel ----------
    seq = run.plan.max_seq_len
    impl = resolve_seq_impl(run.cfg, run.mesh, seq)
    require(impl == "flash", f"attention impl at seq {seq} is {impl!r}")
    require(interpret_default(None) is False,
            "Pallas kernels would run in interpret mode")
    fell = [m for m in warnings if "falling back" in m]
    require(not fell, f"a fallback fired: {fell}")

    # -- the train step is the AOT executable --------------------------
    step_fn = run.step_fn
    require(isinstance(step_fn, GuardedStep),
            f"train step is {type(step_fn).__name__}, not an AOT build")
    require(step_fn.info.get("source") in ("compiled", "deserialized"),
            f"train step source {step_fn.info.get('source')!r}")
    require(not step_fn.fell_back,
            "the AOT train step fell back to jit at call time")
    facts["train_step"] = {
        "source": step_fn.info["source"],
        "build_s": round(step_fn.info["build_s"], 2),
        "sidecar_persisted": "serialize_s" in step_fn.info}

    # -- serve smoke ---------------------------------------------------
    require(served is not None, "the serve smoke was skipped")
    comps, stats = served
    require(stats["completed"] == len(comps) and stats["pending"] == 0,
            f"serve smoke completed {stats['completed']} of {len(comps)}")
    require(all(c.length > c.prompt_len for c in comps)
            and stats["generated_tokens"] > 0,
            "a serve request generated no token")
    facts["serve"] = {k: round(stats[k], 4) for k in (
        "completed", "generated_tokens", "iterations", "wall_s",
        "batch_occupancy", "p50_token_latency_s", "p99_token_latency_s",
        "adapter_requests", "adapter_hits", "adapter_misses")
        if k in stats}
    facts["serve"]["buckets"] = sorted({c.bucket for c in comps})

    # -- compile cache in use where it was placed ----------------------
    cache = cache_stats()
    want = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR
    require(cache["dir"] == want, f"cache dir {cache['dir']!r} != {want!r}")
    require(cache["hits"] + cache["misses"] > 0 and os.listdir(want),
            f"compile cache at {want} saw no traffic")
    facts["cache"] = {"dir": cache["dir"], "hits": cache["hits"],
                      "misses": cache["misses"],
                      "retrieval_s": round(cache["retrieval_s"], 2)}

    # -- placement: every chip holds its share -------------------------
    mesh_shape = {k: int(v) for k, v in run.mesh.shape.items()}
    require(mesh_shape["data"] * mesh_shape["fsdp"] == n_devices,
            f"mesh {mesh_shape} does not span {n_devices} devices")
    base_leaf = next(x for x in jax.tree.leaves(
        run.state.params, is_leaf=is_qtensor) if is_qtensor(x)).codes
    leaves = {"base_codes": base_leaf,
              "lora": jax.tree.leaves(run.state.lora)[0],
              "opt": max(jax.tree.leaves(run.state.opt_state),
                         key=lambda x: x.size)}
    placement = {k: shard_devices(v) for k, v in leaves.items()}
    for name, devs in placement.items():
        require(len(devs) == n_devices,
                f"{name} leaf has shards on devices {devs}, "
                f"want {n_devices} distinct")
    mem = [d.memory_stats() for d in jax.devices()]
    peak = [m["peak_bytes_in_use"] for m in mem]
    require(max(peak) < 4 * min(peak),
            f"device memory is lopsided: peak bytes {peak}")
    facts.update(
        mesh=mesh_shape, shard_devices=placement,
        hbm_gb={"in_use": [round(m["bytes_in_use"] / 2**30, 2)
                           for m in mem],
                "peak": [round(p / 2**30, 2) for p in peak],
                "limit": round(mem[0]["bytes_limit"] / 2**30, 2)})
    return facts


def main() -> int:
    t0 = time.perf_counter()
    # the machine is sealed: fail the hub lookups at once and take the
    # entry's offline branches (ByteTokenizer, synthetic rows, random
    # init) instead of waiting on connection timeouts
    os.environ.setdefault("HF_HUB_OFFLINE", "1")
    os.environ.setdefault("HF_DATASETS_OFFLINE", "1")
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s: %(message)s")

    import jax
    import jaxlib

    # the program first: in a directory that holds nothing else of the
    # repo this raises before anything reaches stdout
    from gke_ray_train_tpu.perf.costs import chip_spec_for_devices
    from gke_ray_train_tpu.rayint import (
        JaxTrainer, RunConfig, ScalingConfig)

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    on_chip = device["platform"] == "tpu"
    # stdout stays empty unless there is a chip to report on
    print(f"chip_smoke: platform={device['platform']} "
          f"device_kind={device['kind']!r} count={device['count']}",
          file=sys.stdout if on_chip else sys.stderr, flush=True)
    if not on_chip:
        print(f"chip_smoke: FAILED: jax attached platform "
              f"{device['platform']!r}, not 'tpu'", file=sys.stderr)
        return 1

    spec = importlib.util.spec_from_file_location("fine_tune_entry", ENTRY)
    entry = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(entry)

    n = device["count"]
    config = smoke_config(n, chip_spec_for_devices().name)
    shutil.rmtree(OUT_DIR, ignore_errors=True)

    # every warning of the package, and the names jax reports as
    # persistent-cache misses (DEBUG lines of its compiler module)
    warnings = _Collect(logging.WARNING)
    logging.getLogger("gke_ray_train_tpu").addHandler(warnings)
    compiler_log = _Collect(logging.DEBUG)
    jax_compiler = logging.getLogger("jax._src.compiler")
    jax_compiler.addHandler(compiler_log)
    jax_compiler.setLevel(logging.DEBUG)
    jax_compiler.propagate = False

    seen: dict = {}

    def smoke_worker(cfg: dict):
        """train_loop_per_worker minus the export and the (disabled)
        inference comparison."""
        run = seen["run"] = entry.train_eval_save(cfg)
        seen["served"] = entry.serve_after_train(run)
        return run.metrics

    try:
        result = JaxTrainer(
            smoke_worker, train_loop_config=config,
            scaling_config=ScalingConfig(
                num_workers=1, resources_per_worker={"TPU": n}),
            run_config=RunConfig(name="chip-smoke", storage_path=OUT_DIR),
            use_ray=False).fit()
        facts = check_run(result, seen.get("run"), seen.get("served"),
                          warnings.messages, n)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        shutil.rmtree(OUT_DIR, ignore_errors=True)

    misses = sorted({m.split("'")[1] for m in compiler_log.messages
                     if m.startswith("PERSISTENT COMPILATION CACHE MISS")})
    facts["cache"]["missed"] = misses
    print(json.dumps({
        "summary": "chip_smoke", "device": device,
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": _libtpu_version()},
        "config": os.path.relpath(CONFIG, REPO),
        "model": f"{seen['run'].cfg.name} d{seen['run'].cfg.d_model} "
                 f"L{seen['run'].cfg.n_layers} seq{config['MAX_SEQ_LENGTH']}",
        **facts,
        "export": "not run",
        "host_peak_rss_gb": round(resource.getrusage(
            resource.RUSAGE_SELF).ru_maxrss / 2**20, 1),
        "wall_s": round(time.perf_counter() - t0, 1),
        "claim": None}), flush=True)
    print(result_line(device), flush=True)
    return 0


def result_line(device: dict) -> str:
    """The last line of stdout on a pass: exactly these keys."""
    return json.dumps({"ok": True, "device": {
        "platform": str(device["platform"]), "kind": str(device["kind"]),
        "count": int(device["count"])}})


def _libtpu_version() -> str:
    from importlib import metadata
    try:
        return metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        return "?"


if __name__ == "__main__":
    sys.exit(main())

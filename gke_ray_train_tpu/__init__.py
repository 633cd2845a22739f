"""gke_ray_train_tpu — a TPU-native distributed LLM training framework.

A from-scratch JAX/XLA/Pallas re-design of the capabilities of the
``ericehanley/gke-ray-train`` reference (Ray-on-GKE LLM fine-tuning on
A3/H100 + NCCL), rebuilt TPU-first:

- SPMD over a ``jax.sharding.Mesh`` (axes: data / fsdp / model / context)
  instead of DDP+NCCL (reference: ray-jobs/pytorch_llm_ray.py:362-364).
- GSPMD-sharded params/optimizer state (ZeRO/FSDP as sharding specs, not
  machinery) instead of bitsandbytes paged optimizers.
- Functional pytree models (Llama-3 / Mistral / Gemma-2 / BasicLM) with
  Pallas flash attention and ring attention for long context.
- orbax sharded checkpointing with retention + resume (the reference never
  wires resume — fine_tune_llama_ray.py has no resume_from_checkpoint).
- A Ray Train style ``JaxTrainer`` preserving the reference's
  ``train_loop_per_worker(config)`` API shape (fine_tune_llama_ray.py:198).
"""

__version__ = "0.1.0"

# Sharding-invariant init is a correctness contract here (meshed init ==
# plain init == init on any elastic topology): every init path wraps
# itself in parallel.sharding.sharding_invariant_rng (partitionable
# threefry — the default of the installed jax, pinned there in code).

# The package re-exports are LAZY (PEP 562): parallel.mesh imports jax
# at module level, but the obs/ CLI surface (`python -m
# gke_ray_train_tpu.obs report|diff|schema`) is stdlib-only by
# contract — it must run on a laptop pointed at a GCS-FUSE mount with
# no jax installed, and importing any submodule materializes this
# __init__ first. Attribute access (`gke_ray_train_tpu.MeshConfig`)
# resolves exactly as before.
_LAZY_EXPORTS = {
    "MeshConfig": "parallel.mesh",
    "build_mesh": "parallel.mesh",
    "batch_sharding": "parallel.mesh",
    "AXIS_DATA": "parallel.mesh",
    "AXIS_FSDP": "parallel.mesh",
    "AXIS_MODEL": "parallel.mesh",
    "AXIS_CONTEXT": "parallel.mesh",
    "AXIS_PIPE": "parallel.mesh",
    "MESH_AXES": "parallel.mesh",
    "ExecutionPlan": "plan",
    "compile_step_with_plan": "plan",
}

__all__ = sorted(_LAZY_EXPORTS)


def __getattr__(name):
    mod = _LAZY_EXPORTS.get(name)
    if mod is None:
        raise AttributeError(
            f"module {__name__!r} has no attribute {name!r}")
    import importlib
    return getattr(importlib.import_module(f"{__name__}.{mod}"), name)

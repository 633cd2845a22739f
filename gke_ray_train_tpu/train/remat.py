"""The train step keeps in its block checkpoints what the device has
room for.

``models/remat.py`` knows what each named activation costs and what a
step with nothing kept needs; here is what only a built step knows: the
bytes of its arguments and the limit its device reports. The step of
``train/step.py::make_train_step`` carries a :class:`StepRemat`, and
the one compile surface (``plan.py::compile_step_with_plan``) hands it
the abstract arguments: :meth:`StepRemat.build` sizes the keep set,
and has ``perf/cache.py::build_or_load_step`` build the step that keeps
it, with the step that keeps nothing as the fallback. The estimate
picks; the compiler judges.

The choice reads shapes, bytes and the device's reported limit, nothing
else. A device that reports no ``bytes_limit`` (XLA:CPU) keeps nothing.
"""

from __future__ import annotations

import dataclasses
import logging
import math
from typing import Any, Callable, Optional, Tuple

import jax

from gke_ray_train_tpu.models.config import ModelConfig
from gke_ray_train_tpu.models.remat import (
    KEPT_PEAK_SHARE, choose_keep, keep_candidates, working_set_bytes)
from gke_ray_train_tpu.models.transformer import (
    flash_grids, resolve_seq_impl, ssm_geometry)
from gke_ray_train_tpu.ops.moe import gather_geometry
from gke_ray_train_tpu.ops.quant import nf4_geometry
from gke_ray_train_tpu.ops.quant import stored_bits
from gke_ray_train_tpu.perf.cache import StepFallback, build_or_load_step

logger = logging.getLogger(__name__)

# HBM left alone beside XLA's peak for the step: the batches the
# prefetcher holds, the metrics, the allocator's fragmentation
RESERVE_BYTES = 128 * 2**20


def shard_bytes(tree: Any, *, whole: bool = False, dtype=None) -> int:
    """Bytes one device holds of a tree of (abstract) arrays: each
    leaf's shard shape under its sharding, its full shape without one
    (or with ``whole``: the bytes of the whole tree). ``dtype``: as if
    every leaf were of that type. A sub-byte leaf (NF4 codes, two a
    byte) is billed by its bits, rounded up a leaf: ``itemsize`` says 1
    for a type the device packs."""
    total = 0
    for leaf in jax.tree.leaves(tree):
        shape = tuple(leaf.shape)
        sharding = getattr(leaf, "sharding", None)
        if sharding is not None and not whole:
            shape = sharding.shard_shape(shape)
        total += math.ceil(
            math.prod(shape) * stored_bits(dtype or leaf.dtype) / 8)
    return total


def device_bytes_limit(mesh) -> Optional[int]:
    """The HBM that this process's first device of ``mesh`` (of the
    default backend without one) reports, None where it reports none
    (XLA:CPU). A device of another host cannot be asked, and every host
    of a slice has to reach the same keep set, so each asks its own: the
    chips of a slice are alike.

    A mesh over a described topology (``jax.experimental.topologies``:
    compiled for, never run on) has no device to ask either: None."""
    device = (jax.local_devices() if mesh is None
              else mesh.local_devices)[0]
    try:
        stats = device.memory_stats()
    except jax.errors.JaxRuntimeError as e:
        if "addressable" not in str(e):
            raise
        return None                   # a compile-only client's device
    limit = (stats or {}).get("bytes_limit")
    return int(limit) if limit else None


@dataclasses.dataclass(frozen=True)
class RematChoice:
    keep: Tuple[str, ...] = ()
    keep_bytes: int = 0
    budget_bytes: Optional[int] = None   # None: no limit reported
    limit_bytes: Optional[int] = None
    args_bytes: Optional[int] = None     # what the budget subtracted
    charged_bytes: int = 0               # what `keep` took of the budget

    def estimate_bytes(self, *, fallback: bool = False) -> Optional[int]:
        """The peak this arithmetic expects for the step that keeps the
        choice (with ``fallback``: nothing): the arguments, the working
        set of a step with nothing kept, and the kept names at what
        they were charged. None where no limit is reported. XLA's own
        is the ``step_build`` span's ``xla_memory``, beside it."""
        if self.budget_bytes is None:
            return None
        return (self.limit_bytes - RESERVE_BYTES - self.budget_bytes
                + (0 if fallback else self.charged_bytes))

    def attrs(self, *, fallback: bool = False) -> dict:
        """The ``step_build`` span's ``remat_*`` attributes (and
        ``GuardedStep.info``'s) for the step that keeps this choice, or
        for the one built in its place with nothing kept."""
        keep = () if fallback else self.keep
        return {"remat_keep": list(keep),
                "remat_keep_bytes": self.keep_bytes if keep else 0,
                "remat_budget_bytes": self.budget_bytes,
                "remat_args_bytes": self.args_bytes,
                "remat_keep_fallback": fallback,
                "remat_estimate_bytes": self.estimate_bytes(
                    fallback=fallback)}


@dataclasses.dataclass(frozen=True)
class StepRemat:
    """Attached to a jitted train step as ``.remat``: how to size the
    keep set for given abstract arguments, and how to make the same
    step with one."""

    cfg: ModelConfig
    mesh: Any
    grad_accum: int
    lora: bool
    with_keep: Callable[[Tuple[str, ...]], Callable]

    def micro_shape(self, batch) -> Tuple[int, int]:
        """(rows, positions a row) of one micro-batch on one device."""
        inputs = batch["inputs"]
        rows, seq = inputs.sharding.shard_shape(tuple(inputs.shape))
        return rows // self.grad_accum, seq

    def choose(self, state, batch) -> RematChoice:
        limit = device_bytes_limit(self.mesh)
        axes = {} if self.mesh is None else dict(self.mesh.shape)
        if limit is None or axes.get("pipe", 1) > 1:
            # models/pipeline.py keeps nothing, whatever is named
            return RematChoice()
        rows, seq = self.micro_shape(batch)
        flash = resolve_seq_impl(self.cfg, self.mesh, seq) == "flash"
        model = axes.get("model", 1)
        trainable = state.lora if self.lora else state.params
        args = shard_bytes((state, batch))
        budget = (limit - args
                  - working_set_bytes(
                      self.cfg, rows, seq, model=model,
                      trainable_bytes=shard_bytes(trainable),
                      trainable_full_bytes=shard_bytes(trainable,
                                                       whole=True),
                      # adapters are cast once, for all layers
                      cast_bytes=shard_bytes(trainable,
                                             dtype=self.cfg.dtype)
                      if self.lora else 0)
                  - RESERVE_BYTES)
        candidates = keep_candidates(self.cfg, rows, seq, model=model,
                                     flash=flash)
        keep = choose_keep(candidates, budget,
                           peak_share=KEPT_PEAK_SHARE)
        sizes = dict(candidates)
        return RematChoice(
            keep, sum(sizes[n] for n in keep), budget, limit, args,
            # as choose_keep charged them
            sum(math.ceil(sizes[n] * KEPT_PEAK_SHARE) for n in keep))

    def build(self, step: Callable, state, batch, *,
              label: str = "train_step", **build_kw):
        """``build_or_load_step`` for ``step`` (the one that keeps
        nothing) or, where the device has room, for the same step with
        the names that fit. One lower and one compile on the path that
        fits; a compile that ends out of HBM, or within the reserve of
        the limit, builds ``step`` instead."""
        choice = self.choose(state, batch)
        # the same for either step: the checkpoints move no kernel
        rows, seq = self.micro_shape(batch)
        grid = {"flash_grid": flash_grids(self.cfg, self.mesh, rows, seq),
                "ssm_scan": ssm_geometry(self.cfg, seq),
                "moe_gather": gather_geometry(self.cfg, rows * seq),
                "nf4_matmul": nf4_geometry(
                    state.params, rows * seq,
                    whole=self.mesh is not None and self.mesh.size == 1)}
        if choice.keep:
            built = build_or_load_step(
                self.with_keep(choice.keep), state, batch, label=label,
                variant=f"remat_keep={choice.keep}",
                attrs={**choice.attrs(), **grid},
                fallback=StepFallback(
                    step, {**choice.attrs(fallback=True), **grid},
                    peak_limit_bytes=choice.limit_bytes - RESERVE_BYTES),
                **build_kw)
        else:
            built = build_or_load_step(
                step, state, batch, label=label,
                attrs={**choice.attrs(), **grid}, **build_kw)
        if built.info["remat_keep_fallback"]:
            logger.warning(
                "%s: keeping %s (%.2f GB a device) in the block "
                "checkpoints does not fit; built with nothing kept",
                label, list(choice.keep), choice.keep_bytes / 1e9)
        logger.info(
            "%s: block checkpoints keep %s (%.2f GB a device; room for "
            "%s)", label, built.info["remat_keep"] or "their inputs only",
            built.info["remat_keep_bytes"] / 1e9,
            "no device limit reported" if choice.budget_bytes is None
            else f"{choice.budget_bytes / 1e9:.2f} GB beside "
                 f"{choice.args_bytes / 1e9:.2f} GB of arguments")
        logger.info(
            "%s: flash grid steps a call, visited / rectangular: %s",
            label, "; ".join(
                f"{kind} ({g['block_q']} x {g['block_kv']}) " + ", ".join(
                    f"{k} {g[k][0]}/{g[k][1]}" for k in ("fwd", "dq", "dkv"))
                for kind, g in grid["flash_grid"].items())
            or "no flash kernel walks its own rows")
        if grid["ssm_scan"]:
            g = grid["ssm_scan"]
            logger.info(
                "%s: state-space scan as %s: %d chunks of %d a row, %d "
                "heads a %s, %d grid steps a kernel call and row", label,
                g["impl"], g["chunks_a_row"], g["chunk"], g["head_block"],
                "grid step" if g["impl"] == "pallas" else "block",
                g["grid_steps_a_row"])
        if grid["moe_gather"]:
            g = grid["moe_gather"]
            logger.info(
                "%s: routed gather-and-sum as %s: %d tokens a grid step, "
                "%d picks a token from %d buffer rows of %d bytes", label,
                g["impl"], g["token_tile"], g["picks"], g["rows"],
                g["row_bytes"])
        if grid["nf4_matmul"]:
            g = grid["nf4_matmul"]
            logger.info(
                "%s: frozen products of %d rows, calls a micro-pass as the "
                "kernel %d, decoded before the product %d: %s", label,
                g["rows"], g["pallas"], g["xla"], "; ".join(
                    f"{k} {v['impl']} {v['calls']}"
                    for k, v in g["shapes"].items()))
        return built

"""Topology & distributed init — the TPU-native replacement for the
reference's process-group bootstrap + NCCL backend.

Reference behavior being replaced (see SURVEY.md §5.8):
- Ray sets MASTER_ADDR/PORT/WORLD_SIZE/RANK and Accelerate calls
  ``torch.distributed.init_process_group`` (narrated in the reference at
  ray-jobs/fine_tune_llama_ray.py:413-419); NCCL is selected explicitly at
  ray-jobs/pytorch_llm_ray.py:362-364.

TPU-native design: ``jax.distributed.initialize`` performs multi-host
rendezvous (coordinator = host 0, address supplied by the Ray trainer),
after which there is *no communication library to manage* — collectives
(psum / all_gather / reduce_scatter / ppermute) are emitted by GSPMD from
sharding specs and ride ICI within a slice, DCN between slices.

Mesh axes (fixed vocabulary across the framework):

==========  ========================================================
axis        what is sharded over it
==========  ========================================================
``data``    pure data parallelism — batch only (DCN-friendly, outermost)
``fsdp``    batch AND params/optimizer state (ZeRO-3-style, over ICI)
``model``   tensor parallelism — attention heads / ffn hidden
``context`` sequence/context parallelism — ring attention over ICI
``pipe``    pipeline parallelism — layer (repeat) dim of the stacked
            blocks; stages exchange activations via collective-permute
            (models/pipeline.py)
==========  ========================================================
"""

from __future__ import annotations

import dataclasses
import logging
import math
import os
from typing import Any, Optional, Sequence

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

logger = logging.getLogger(__name__)

AXIS_DATA = "data"
AXIS_FSDP = "fsdp"
AXIS_MODEL = "model"
AXIS_CONTEXT = "context"
AXIS_PIPE = "pipe"
MESH_AXES = (AXIS_DATA, AXIS_FSDP, AXIS_MODEL, AXIS_CONTEXT, AXIS_PIPE)

# Batch dims are sharded over both DP-like axes; this is the standard GSPMD
# trick that makes FSDP "just a sharding spec" (SURVEY.md §2c row FSDP).
BATCH_AXES = (AXIS_DATA, AXIS_FSDP)


def on_tpu() -> bool:
    """Whether the attached backend is a TPU — the ONE test behind every
    device-dependent choice: flash vs the XLA oracle
    (``ModelConfig.resolved_attn_impl``), compiled vs interpreted Pallas
    (``ops.flash_attention.interpret_default``, shared by ring/a2a) and
    whether ``OVERLAP=xla`` adds its compiler options (``plan.py``).
    A backend that fails to initialize raises here; nothing degrades to
    the CPU behind the caller's back."""
    return jax.default_backend() == "tpu"


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Logical mesh shape. Any axis may be -1 ("fill with what remains").

    Mirrors the reference's infra-shape env vars NUM_NODES /
    NUM_GPUS_PER_NODE (ray-jobs/fine_tune_llama_ray.py:439-441) but as a
    5-axis logical topology instead of a flat world size.
    """

    data: int = 1
    fsdp: int = -1
    model: int = 1
    context: int = 1
    # Pipeline stages. Last mesh dim → stages sit on adjacent ICI
    # neighbors, so the stage-to-stage activation permute is one hop.
    pipe: int = 1
    # Number of DCN-connected slices. When >1, the `data` axis is laid out
    # across slices (DCN-outermost) via a hybrid device mesh.
    num_slices: int = 1

    def resolve(self, n_devices: int) -> "MeshConfig":
        """Resolve -1 entries so the product equals ``n_devices``."""
        sizes = {f.name: getattr(self, f.name) for f in dataclasses.fields(self)
                 if f.name in MESH_AXES}
        fills = [k for k, v in sizes.items() if v == -1]
        if len(fills) > 1:
            raise ValueError(f"at most one mesh axis may be -1, got {fills}")
        bad = {k: v for k, v in sizes.items() if v != -1 and v < 1}
        if bad:
            raise ValueError(f"mesh axis sizes must be >=1 (or -1): {bad}")
        fixed = math.prod(v for v in sizes.values() if v != -1)
        if fills:
            if n_devices % fixed != 0:
                raise ValueError(
                    f"cannot fill axis {fills[0]}: {n_devices} devices not "
                    f"divisible by fixed product {fixed} ({sizes})")
            sizes[fills[0]] = n_devices // fixed
        if math.prod(sizes.values()) != n_devices:
            raise ValueError(
                f"mesh {sizes} has {math.prod(sizes.values())} slots but "
                f"{n_devices} devices are present")
        return dataclasses.replace(self, **sizes)

    @property
    def shape(self) -> tuple:
        return (self.data, self.fsdp, self.model, self.context, self.pipe)

    @staticmethod
    def from_dict(cfg: dict) -> "MeshConfig":
        """Build from the flat UPPER_CASE config convention the reference
        uses for fine_tune_config.json (SURVEY.md §5.6)."""
        return MeshConfig(
            data=int(cfg.get("MESH_DATA", 1)),
            fsdp=int(cfg.get("MESH_FSDP", -1)),
            model=int(cfg.get("MESH_MODEL", 1)),
            context=int(cfg.get("MESH_CONTEXT", 1)),
            pipe=int(cfg.get("MESH_PIPE", 1)),
            num_slices=int(cfg.get("NUM_SLICES", 1)),
        )


def build_mesh(config: MeshConfig | None = None,
               devices: Optional[Sequence[Any]] = None) -> Mesh:
    """Build the 5-axis device mesh.

    Single-slice: ``mesh_utils.create_device_mesh`` lets JAX pick a
    device order that maps logical neighbors onto physical ICI neighbors
    (critical for ring attention on ``context`` and all-gathers on
    ``fsdp``). Multi-slice: a hybrid mesh puts ``data`` across DCN.
    """
    devices = list(devices if devices is not None else jax.devices())
    config = (config or MeshConfig()).resolve(len(devices))

    if config.num_slices > 1:
        if config.data % config.num_slices != 0:
            raise ValueError(
                f"data axis ({config.data}) must be divisible by "
                f"num_slices ({config.num_slices})")
        per_slice = (config.data // config.num_slices, config.fsdp,
                     config.model, config.context, config.pipe)
        if all(getattr(d, "slice_index", None) is not None
               for d in devices):
            # real multi-slice hardware: failures here are config bugs
            # (slice count mismatch etc.) and must surface, not degrade
            dev_array = mesh_utils.create_hybrid_device_mesh(
                per_slice, (config.num_slices, 1, 1, 1, 1), devices=devices)
        else:
            # fake/CPU devices carry no slice_index attribute — emulate
            # the DCN-outermost layout: contiguous device blocks become
            # slices, the data axis (outermost, largest stride) spans
            # them, so only batch-gradient psums cross the slice
            # boundary (SURVEY.md §5.8)
            logger.warning(
                "devices report no slice_index (fake/CPU backend); "
                "emulating the %d-slice hybrid mesh row-major",
                config.num_slices)
            dev_array = np.asarray(devices).reshape(config.shape)
    elif devices[0].platform == "cpu":
        # fake CPU devices have no physical topology to honor
        dev_array = np.asarray(devices).reshape(config.shape)
    else:
        # a topology create_device_mesh cannot lay out raises: a silent
        # row-major order would put logical neighbors off ICI neighbors
        dev_array = mesh_utils.create_device_mesh(
            config.shape, devices=devices)
        logger.info("mesh %s device order: %s", config.shape,
                    [d.id for d in dev_array.flat])
    return Mesh(dev_array, MESH_AXES)


def slice_assignments(devices: Sequence[Any],
                      num_slices: Optional[int] = None) -> list:
    """Slice identity per device — THE ``slice_index`` contract.

    Real multi-slice TPU devices carry ``.slice_index``; fake/CPU
    devices emulate the hybrid layout :func:`build_mesh` uses
    (contiguous row-major blocks become slices), so device ``i`` of
    ``n`` belongs to slice ``i // (n // num_slices)``. Everything that
    needs slice identity — per-slice failure domains in
    ``rayint/supervisor.py``, the ``slice_evict`` fault in
    ``testing/faults.py``, the elastic pool emulation (evicting the
    LAST slice = truncating the device list) — reads it through this
    one function so the contract cannot fork.

    ``num_slices`` defaults to ``$NUM_SLICES`` (1 when unset — a
    single-slice pool is one failure domain).
    """
    devices = list(devices)
    if devices and all(getattr(d, "slice_index", None) is not None
                       for d in devices):
        return [int(d.slice_index) for d in devices]
    n = len(devices)
    ns = int(num_slices if num_slices is not None
             else os.environ.get("NUM_SLICES", "1"))
    if ns <= 1 or n == 0 or n % ns:
        return [0] * n
    per_slice = n // ns
    return [i // per_slice for i in range(n)]


def named_sharding(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, P(*spec))


def batch_sharding(mesh: Mesh, *, context_sharded: bool = False) -> NamedSharding:
    """Sharding for a [batch, seq, ...] array: batch over (data, fsdp),
    optionally sequence over context (sequence parallelism)."""
    seq = AXIS_CONTEXT if context_sharded else None
    return NamedSharding(mesh, P(BATCH_AXES, seq))


def _distributed_state_initialized() -> bool:
    """True if jax.distributed.initialize already ran in this process.

    Uses the distributed client handle rather than jax.process_count():
    the latter lazily initializes the XLA backend, which would make a
    subsequent jax.distributed.initialize raise.
    """
    try:
        from jax._src import distributed as _jd
        return _jd.global_state.client is not None
    except Exception:
        return False


def distributed_init(coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None) -> None:
    """Multi-host rendezvous — the analogue of the reference's
    MASTER_ADDR/MASTER_PORT + init_process_group handshake
    (ray-jobs/fine_tune_llama_ray.py:413-418).

    Arguments default from env (set by rayint.JaxTrainer on each worker):
    ``COORDINATOR_ADDRESS`` / ``NUM_PROCESSES`` / ``PROCESS_ID``. No-op in
    single-process mode or when already initialized.
    """
    coordinator_address = coordinator_address or os.environ.get(
        "COORDINATOR_ADDRESS")
    num_processes = num_processes if num_processes is not None else int(
        os.environ.get("NUM_PROCESSES", "1"))
    process_id = process_id if process_id is not None else int(
        os.environ.get("PROCESS_ID", "0"))
    if num_processes <= 1:
        logger.info("single-process run; skipping jax.distributed.initialize")
        return
    if _distributed_state_initialized():  # no-op if a launcher already did it
        return
    if coordinator_address is None:
        raise ValueError(
            f"multi-process run requested (NUM_PROCESSES={num_processes}) "
            "but no coordinator address given — set COORDINATOR_ADDRESS or "
            "pass coordinator_address=. Refusing to degrade to "
            f"{num_processes} independent single-process trainings.")
    # NOTE: must not touch jax.devices()/process_count() here — any backend
    # query initializes XLA, after which jax.distributed.initialize raises.
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    logger.info("jax.distributed initialized: process %d/%d, %d devices",
                process_id, num_processes, len(jax.devices()))

"""Persistent compilation cache + AOT train executables.

Two mechanisms make compilation a one-time cost across restarts:

1. **Persistent compilation cache** (:func:`enable_persistent_cache`):
   JAX's file cache, so every retry/resume — and every *other worker*
   of the slice — reuses the XLA binary instead of recompiling (minutes
   at 8B scale; the MaxText practice for GSPMD programs). The directory
   is placed from outside with ``JAX_COMPILATION_CACHE_DIR``, which JAX
   reads itself; only without it does the program name one
   (``COMPILE_CACHE_DIR``, else ``<checkout>/.jax_cache``) — always a
   fixed path, never one built from a temporary name, a pid or the
   time, because a directory that moves between runs never hits.
   Entries sit directly in it: JAX's own cache key already encodes
   program, platform and topology.

2. **AOT executables** (:func:`build_or_load_step`): the train/eval
   step is built ahead-of-time via ``jit(...).lower(...).compile()``
   and serialized (``jax.experimental.serialize_executable``) to a
   sidecar beside the checkpoint. A preempted retry deserializes the
   executable and reaches its first step with **zero retracing** —
   the persistent cache saves compile time, the sidecar saves trace
   + lowering time too.

A stale/mismatched sidecar falls back to a fresh compile. Nothing
else degrades: a cache directory that cannot be written, or a step
that fails to compile, is an error.

Gotchas this module owns so callers don't have to:

- JAX memoizes "is the cache usable" at the FIRST compile of the
  process (``compilation_cache.is_cache_used``). Enabling the cache
  after any jit has run silently no-ops unless the check is reset —
  :func:`enable_persistent_cache` always resets it.
- JAX leaves an instruction's metadata (``op_name``: the
  ``jax.named_scope`` path) out of the cache key, so an executable
  compiled before a scope existed would be served to the code that has
  it, and its HLO text would name nothing (``obs/trace.py::
  scope_table``). :func:`build_or_load_step` compiles under
  :func:`names_salt`, a digest of the names vocabulary that rides the
  key through JAX's own ``cache_key.custom_hook`` — the steps whose
  executables are kept and read, not every program of the process (a
  capped cache directory would hold two copies of everything).
"""

from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import json
import logging
import os
import pickle
import threading
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax

from gke_ray_train_tpu.obs import trace

logger = logging.getLogger(__name__)

# where the cache lives when nothing names a directory: a fixed path
# inside the checkout (listed in .gitignore)
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

# hit/miss counters fed by jax.monitoring events — the same counters
# the cache-hit tests assert on (ISSUE 4 satellite) — and the seconds
# of jax's own timed blocks: the process's totals; a region of
# :func:`build_or_load_step` reads the difference across itself
_STATS = {"hits": 0, "misses": 0, "compile_time_saved_s": 0.0,
          "retrieval_s": 0.0, "trace_s": 0.0, "to_mlir_s": 0.0,
          "backend_compile_s": 0.0}
# jax's timed blocks (``dispatch.log_elapsed_time``) -> their counter.
# They nest: a jitted function traced inside another reports its own
# trace, a trace inside a lowering its own, and every backend compile
# is asked for under one of them or alone. Each second is counted
# once, under the outermost block of its thread, so the three never
# sum past the wall clock (jax's `backend_compile_duration` holds the
# cache's lookup: on a hit it is the read, not a build)
_TIMED_BLOCKS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "to_mlir_s",
    "/jax/core/compile/backend_compile_duration": "backend_compile_s"}
_blocks = threading.local()      # .open: timed blocks open on a thread
_LISTENER_INSTALLED = False
_ENABLED_DIR: Optional[str] = None


def _on_event(event: str, **kw) -> None:
    if event.endswith("/cache_hits"):
        _STATS["hits"] += 1
    elif event.endswith("/cache_misses"):
        _STATS["misses"] += 1


def _on_scalar(event: str, value: float, **kw) -> None:
    # a timed block reports its start as a scalar under its own name
    if event in _TIMED_BLOCKS:
        _blocks.open = getattr(_blocks, "open", 0) + 1


def _on_duration(event: str, duration: float, **kw) -> None:
    if event.endswith("/compile_time_saved_sec"):
        _STATS["compile_time_saved_s"] += max(duration, 0.0)
    elif event.endswith("/cache_retrieval_time_sec"):
        _STATS["retrieval_s"] += max(duration, 0.0)
    elif event in _TIMED_BLOCKS:
        _blocks.open = still = max(getattr(_blocks, "open", 0) - 1, 0)
        if not still:
            _STATS[_TIMED_BLOCKS[event]] += max(duration, 0.0)


def _install_listener() -> None:
    global _LISTENER_INSTALLED
    if _LISTENER_INSTALLED:
        return
    from jax._src import monitoring
    monitoring.register_event_listener(_on_event)
    monitoring.register_scalar_listener(_on_scalar)
    monitoring.register_event_duration_secs_listener(_on_duration)
    _LISTENER_INSTALLED = True


def cache_stats() -> Dict[str, Any]:
    """Process-wide persistent-cache counters (hits/misses/seconds) and
    the directory in use (``dir``; None while disabled)."""
    return {**_STATS, "dir": _ENABLED_DIR}


def log_cache_summary(log: logging.Logger = logger) -> None:
    """One log line of compile-cache health — what the trainer prints
    at the end of every attempt (hit ratio ~1.0 on a warm restart)."""
    s = cache_stats()
    if _ENABLED_DIR is None:
        log.info("compile cache: disabled")
        return
    log.info(
        "compile cache %s: %d hits / %d misses, %.1fs compile time saved "
        "(retrieval %.2fs)", _ENABLED_DIR, s["hits"], s["misses"],
        s["compile_time_saved_s"], s["retrieval_s"])


def cpu_mesh_env(n_devices: int = 8, **extra: str) -> Dict[str, str]:
    """os.environ copy that forces an ``n_devices`` virtual CPU platform
    in a CHILD process (XLA_FLAGS must land before backend init, hence
    re-exec rather than in-process switching). The one canonical recipe
    shared by the budget, analysis and autotune CLIs — keep it here so
    they cannot drift."""
    env = dict(os.environ)
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    flags.append(f"--xla_force_host_platform_device_count={n_devices}")
    env["XLA_FLAGS"] = " ".join(flags)
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra)
    return env


def names_salt() -> str:
    """A digest of the scope and kernel names the program writes into
    its executables (obs/trace.py), for the persistent cache's key."""
    names = (trace.SCOPE_VERSION, trace.SCOPE_NAMES, trace.KERNEL_NAMES)
    return "grt-names:" + hashlib.sha256(
        repr(names).encode()).hexdigest()[:16]


@contextlib.contextmanager
def salted_cache_key():
    """Compiles inside carry :func:`names_salt` in their persistent
    cache key, beside JAX's own parts: an entry compiled under another
    vocabulary — or before there was one — is never served. Not
    ``jax_compilation_cache_include_metadata_in_key``: that keys on
    source lines too, so every edit of a traced file would recompile.
    The hook is the process's, so a compile on another thread meanwhile
    is salted too: a miss, never a wrong executable."""
    from jax._src import cache_key
    unsalted = cache_key.custom_hook
    cache_key.custom_hook = lambda: unsalted() + names_salt()
    try:
        yield
    finally:
        cache_key.custom_hook = unsalted


def _backend_initialized() -> bool:
    try:
        from jax._src import xla_bridge
        return bool(getattr(xla_bridge, "_backends", None))
    except Exception:  # noqa: BLE001 - private API; absence means unknown
        return False


def topology_fingerprint() -> Tuple[str, Dict[str, Any]]:
    """(short-hash, facts) identifying this process's compile topology.

    Device facts (kind/count) are included only when the backend is
    already up — probing them would *initialize* it, which must not
    happen before ``jax.distributed.initialize`` on multi-host. Before
    backend init the env-derived facts (``TPU_ACCELERATOR_TYPE`` on
    GKE TPU pods, ``JAX_PLATFORMS`` elsewhere) still separate v5e from
    v5p slices.
    """
    import jaxlib

    facts: Dict[str, Any] = {
        "jax": jax.__version__,
        "jaxlib": getattr(jaxlib, "__version__", "?"),
        "accelerator_type": os.environ.get("TPU_ACCELERATOR_TYPE", ""),
        "platforms_env": os.environ.get("JAX_PLATFORMS", ""),
    }
    if _backend_initialized():
        devs = jax.devices()
        facts.update(platform=devs[0].platform,
                     device_kind=devs[0].device_kind,
                     n_devices=len(devs),
                     n_processes=jax.process_count())
    digest = hashlib.sha256(
        json.dumps(facts, sort_keys=True).encode()).hexdigest()[:16]
    return digest, facts


def resolve_cache_dir(cache_dir: Optional[str] = None,
                      plan=None) -> Tuple[str, bool]:
    """(directory, placed_from_outside) of the persistent compile cache.

    ``JAX_COMPILATION_CACHE_DIR`` wins over everything — JAX reads it
    itself, so the program must not name another. Without it: explicit
    arg → ``plan.compile_cache_dir`` → ``$COMPILE_CACHE_DIR`` →
    ``<checkout>/.jax_cache``. Pure: no backend, no filesystem, so the
    answer is the same before and after backend init."""
    outside = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if outside:
        return outside, True
    return (cache_dir
            or (plan.compile_cache_dir if plan is not None else None)
            or os.environ.get("COMPILE_CACHE_DIR")
            or DEFAULT_CACHE_DIR), False


def enable_persistent_cache(cache_dir: Optional[str] = None,
                            plan=None) -> Optional[str]:
    """Turn on JAX's persistent compilation cache in the directory
    :func:`resolve_cache_dir` names, with no subdirectory under it.

    With ``JAX_COMPILATION_CACHE_DIR`` set the directory is JAX's own
    business and this sets none; it still drops the entry-size and
    compile-time floors, installs the hit/miss listener and un-memoizes
    JAX's "is the cache used" verdict. ``COMPILE_CACHE=0`` (or
    ``plan.compile_cache=False``) makes the program configure nothing.
    A directory that cannot be created or written raises.

    Safe to call more than once (the trainer enables before the entry
    script does): a repeat call that resolves to the current dir is a
    no-op.

    Returns the cache dir, or None when disabled.
    """
    global _ENABLED_DIR
    if plan is not None and not plan.compile_cache:
        logger.info("compile cache disabled by the execution plan "
                    "(COMPILE_CACHE=0)")
        return None
    if os.environ.get("COMPILE_CACHE", "1").lower() in ("0", "false"):
        logger.info("compile cache disabled via COMPILE_CACHE=0")
        return None
    resolved, outside = resolve_cache_dir(cache_dir, plan)
    if resolved == _ENABLED_DIR:
        return resolved
    try:
        os.makedirs(resolved, exist_ok=True)
        probe = os.path.join(resolved, f".writable.{os.getpid()}")
        with open(probe, "w") as f:
            f.write("1")
        os.remove(probe)
    except OSError as e:
        raise RuntimeError(
            f"compile cache dir {resolved} is unusable ({e}); name a "
            "writable one (JAX_COMPILATION_CACHE_DIR / COMPILE_CACHE_DIR) "
            "or set COMPILE_CACHE=0") from e

    if not outside:
        jax.config.update("jax_compilation_cache_dir", resolved)
    # persist everything: the whole point is that the NEXT process
    # skips the compile, so entry-size/compile-time floors are off
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(os.environ.get("COMPILE_CACHE_MIN_COMPILE_S",
                                           "0")))
    _install_listener()
    # un-memoize is_cache_used: any compile that already ran this
    # process (state init, a probe) froze the "no cache dir" verdict;
    # without this reset, late enabling silently no-ops
    from jax._src import compilation_cache
    compilation_cache.reset_cache()
    _ENABLED_DIR = resolved
    logger.info("persistent compile cache at %s%s", resolved,
                " (JAX_COMPILATION_CACHE_DIR)" if outside else "")
    return resolved


# ---------------------------------------------------------------------------
# AOT executables: serialize beside the checkpoint, deserialize on retry
# ---------------------------------------------------------------------------

def aot_enabled(config: Optional[dict] = None) -> bool:
    """Legacy parse of the AOT_TRAIN_STEP knob (config key wins over
    env; default on). The entry scripts now read it from
    ``ExecutionPlan.aot_train_step`` (plan.py) via
    ``compile_step_with_plan`` — kept for ad-hoc callers."""
    if config is not None and "AOT_TRAIN_STEP" in config:
        raw = config["AOT_TRAIN_STEP"]
    else:
        raw = os.environ.get("AOT_TRAIN_STEP", "1")
    return str(raw).lower() not in ("0", "false")


def make_abstract_batch(mesh, n_rows: int, seq_len: int, *,
                        packed: bool = False,
                        context_sharded: bool = False) -> Dict[str, Any]:
    """The abstract [n_rows, seq_len] batch both entry scripts lower
    against: inputs/targets int32 + weights float32 (+ segment_ids/
    positions int32 when packed), sharded per the train step's
    batch_shardings contract."""
    import jax.numpy as jnp

    from gke_ray_train_tpu.train.step import batch_shardings
    keys = ("inputs", "targets", "weights") + (
        ("segment_ids", "positions") if packed else ())
    shard = batch_shardings(mesh, keys, context_sharded=context_sharded)
    return {
        k: jax.ShapeDtypeStruct(
            (n_rows, seq_len),
            jnp.float32 if k == "weights" else jnp.int32,
            sharding=shard[k])
        for k in keys}


def _leaf_signature(leaf: Any) -> tuple:
    shape = tuple(getattr(leaf, "shape", ()))
    dtype = str(getattr(leaf, "dtype", type(leaf).__name__))
    sharding = getattr(leaf, "sharding", None)
    spec = getattr(sharding, "spec", None)
    return (shape, dtype, repr(spec) if spec is not None else None)


def aot_signature(*args_trees: Any, plan=None,
                  surface: str = "train") -> str:
    """Digest of the abstract input signature (treedef + per-leaf
    shape/dtype/partition-spec) + topology fingerprint + (when given)
    the ExecutionPlan's per-``surface`` COMPILE fingerprint — the
    validity key of a serialized executable. A sidecar whose key
    mismatches is stale (different mesh, model size, batch layout,
    chip, or a plan that compiles a different program on THIS surface)
    and is ignored rather than loaded; operational plan knobs — and
    the other surface's fields — deliberately do NOT invalidate it."""
    leaves, treedef = jax.tree.flatten(args_trees)
    payload = (topology_fingerprint()[0],
               plan.compile_fingerprint(surface)
               if plan is not None else None,
               str(treedef),
               [_leaf_signature(x) for x in leaves])
    return hashlib.sha256(repr(payload).encode()).hexdigest()


def abstractify(tree: Any) -> Any:
    """Concrete pytree → ShapeDtypeStruct pytree, shardings preserved —
    the abstract-argument form ``jit(...).lower`` wants for AOT."""
    def leaf(x):
        if isinstance(x, jax.ShapeDtypeStruct):
            return x
        sharding = getattr(x, "sharding", None)
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)
    return jax.tree.map(leaf, tree)


def save_executable(compiled, path: str, key: str) -> bool:
    """Serialize an AOT-compiled executable (atomic write). Best-effort:
    returns False instead of raising — persistence failures must not
    kill a training step that already compiled fine."""
    try:
        from jax.experimental import serialize_executable
        payload, in_tree, out_tree = serialize_executable.serialize(compiled)
        blob = pickle.dumps({
            "key": key, "payload": payload,
            "in_tree": in_tree, "out_tree": out_tree,
            # the devices the executable runs on, in its own order: a
            # load without them targets EVERY device of the backend, so
            # a one-device serve executable on an 8-device host (or a
            # mesh whose order is not jax.devices() order) would reject
            # its first call
            "device_ids": [d.id for d in
                           compiled.runtime_executable().local_devices()]})
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "wb") as f:
            f.write(blob)
        os.replace(tmp, path)
        return True
    except Exception as e:  # noqa: BLE001 - persistence is best-effort
        logger.warning("AOT executable serialize to %s failed (%s: %s)",
                       path, type(e).__name__, e)
        return False


def load_executable(path: str, key: str):
    """Deserialize a sidecar executable; None when missing, stale
    (key mismatch) or undeserializable — callers fall back to compile."""
    if not os.path.exists(path):
        return None
    try:
        with open(path, "rb") as f:
            blob = pickle.load(f)
        if blob.get("key") != key:
            logger.info("AOT sidecar %s is stale (topology/signature "
                        "changed); recompiling", path)
            return None
        from jax.experimental import serialize_executable
        by_id = {d.id: d for d in jax.devices()}
        return serialize_executable.deserialize_and_load(
            blob["payload"], blob["in_tree"], blob["out_tree"],
            execution_devices=[by_id[i] for i in blob["device_ids"]])
    except Exception as e:  # noqa: BLE001 - fall back to compile
        logger.warning("AOT sidecar %s unusable (%s: %s); recompiling",
                       path, type(e).__name__, e)
        return None


class GuardedStep:
    """AOT executable with a jit fallback.

    Calls the pre-compiled executable; if a call ever fails (an input
    whose layout drifted from the recorded signature), it logs ONCE and
    permanently falls back to the jitted function — a stale sidecar
    costs one retrace, never a crash. ``info`` records the build source
    ("deserialized" | "compiled"), beside it ``cache`` ("hit" | "miss"
    | None: whether the compile found its executable in the persistent
    cache) and seconds, for the loop's compile-time metrics;
    ``fell_back`` says whether a call ever left the executable for the
    jitted path.
    """

    def __init__(self, compiled, jitted_fn: Callable, info: Dict[str, Any]):
        self._compiled = compiled
        self._jitted = jitted_fn
        self.info = info
        self.fell_back = compiled is None

    def __call__(self, *args):
        if not self.fell_back:
            try:
                return self._compiled(*args)
            except Exception as e:  # noqa: BLE001 - classified below
                # only an input-signature rejection is retryable: it
                # raises at dispatch, BEFORE any donated buffer is
                # handed to the runtime. A failure mid-execution (OOM,
                # runtime error) may have consumed donated args —
                # retrying would die with a misleading "Array has been
                # deleted" burying the real error, so re-raise it.
                if any(getattr(x, "is_deleted", lambda: False)()
                       for x in jax.tree.leaves(args)):
                    raise
                self.fell_back = True
                logger.warning(
                    "AOT executable rejected the call (%s: %s); falling "
                    "back to the jitted step (one retrace)",
                    type(e).__name__, e)
        return self._jitted(*args)

    def lower(self, *args, **kw):  # pragma: no cover - passthrough
        return self._jitted.lower(*args, **kw)

    def note_scope_table(self) -> None:
        """Keep this executable's ``{instruction: op_name}`` table in
        the in-memory trace record under the step's label, so that the
        device events of a profiler window can be joined to the
        program's scopes. The loop calls it on its way out when a
        profiler was attached; a step that fell back runs another
        executable, whose instructions this one does not name."""
        if not self.fell_back:
            trace.note_scope_table(self.info["label"], self._compiled)


def _note_cost_report(compiled, plan) -> None:
    """Feed the obs network gauges (grt_ici_bytes / grt_dcn_bytes)
    from the StepCostReport of the executable this build already
    produced — only when a telemetry session is active (the HLO parse
    is not free), and never fatally (telemetry must not kill a
    build)."""
    from gke_ray_train_tpu.obs import runtime as obs_runtime
    if obs_runtime.active() is None:
        return
    try:
        from gke_ray_train_tpu.perf.costs import step_cost_report
        ns = getattr(plan, "num_slices", None) if plan is not None \
            else None
        obs_runtime.note_cost_report(
            step_cost_report(compiled, num_slices=ns))
    except Exception as e:  # noqa: BLE001 - telemetry is best-effort
        logger.warning("obs cost-report note skipped: %s", e)


@dataclasses.dataclass(frozen=True)
class StepFallback:
    """The step to build where the one asked for does not fit the
    device: its jitted function, the ``attrs`` that then replace the
    first step's, and the peak (``compiled.memory_analysis()``) past
    which the first step counts as not fitting although it compiled."""

    fn: Callable
    attrs: Dict[str, Any]
    peak_limit_bytes: Optional[int] = None


def _variant_key(key: str, variant: str) -> str:
    return hashlib.sha256(f"{key}|{variant}".encode()).hexdigest()


# the ``step_build`` span's ``xla_memory`` <- compiled.memory_analysis()
_XLA_MEMORY = {"peak": "peak_memory_in_bytes",
               "arguments": "argument_size_in_bytes",
               "outputs": "output_size_in_bytes",
               "aliased": "alias_size_in_bytes",
               "temporaries": "temp_size_in_bytes",
               "code": "generated_code_size_in_bytes"}


def _device_limit(args) -> Optional[int]:
    """``train/remat.py::device_bytes_limit`` for the mesh the
    arguments are laid out over (the default backend's first device
    where none says)."""
    from gke_ray_train_tpu.train.remat import device_bytes_limit
    meshes = (getattr(getattr(x, "sharding", None), "mesh", None)
              for x in jax.tree.leaves(args))
    return device_bytes_limit(next(
        (m for m in meshes if hasattr(m, "local_devices")), None))


def xla_memory(compiled, limit: Optional[int]) -> Dict[str, Any]:
    """The step's memory as XLA laid it out, in bytes, beside the
    device's ``limit``: one ``memory_analysis()`` call. {} where the
    executable gives none (a backend without the analysis, an
    executable that cannot serve it again after deserializing)."""
    t0 = time.perf_counter()
    try:
        stats = compiled.memory_analysis()
    except Exception as e:  # noqa: BLE001 - the build stands without
        logger.warning("memory analysis skipped: %s: %s",
                       type(e).__name__, e)
        return {}
    logger.debug("memory analysis in %.4fs", time.perf_counter() - t0)
    if stats is None:
        return {}
    return {**{k: int(getattr(stats, attr, 0) or 0)
               for k, attr in _XLA_MEMORY.items()}, "limit": limit}


def _past_peak(memory: Dict[str, Any],
               limit: Optional[int]) -> Optional[str]:
    """Says so where XLA's peak (``xla_memory``'s) passes ``limit``."""
    peak = memory.get("peak", 0)
    if limit is None or peak <= limit:
        return None
    return (f"the compiled step's peak of {peak / 1e9:.2f} GB passes "
            f"{limit / 1e9:.2f} GB")


def build_or_load_step(jitted_fn: Callable, *abstract_args: Any,
                       sidecar: Optional[str] = None,
                       label: str = "train_step",
                       plan=None, surface: str = "train",
                       variant: str = "",
                       attrs: Optional[Dict[str, Any]] = None,
                       fallback: Optional[StepFallback] = None
                       ) -> GuardedStep:
    """AOT-build a jitted step (or deserialize its sidecar) and return a
    :class:`GuardedStep`.

    - sidecar present + key matches → deserialize (no trace, no
      compile); a preempted retry reaches its first step in the time it
      takes to read the file.
    - otherwise → ``lower(*abstract_args).compile()`` (the compile
      itself hits the persistent cache when warm) and, when ``sidecar``
      is set, serialize for the next restart. Only process 0 writes —
      every host of a slice lowers the same program and the sidecar
      lives on shared storage.
    - ``variant``: what the arguments and the plan do not say about the
      program ``jitted_fn`` traces to; part of the sidecar's key.
      ``attrs``: further attributes of the ``step_build`` span, in
      ``info`` too.
    - ``fallback``: the compiler is the judge of what fits. A compile
      that ends out of device memory, or in a peak past the fallback's
      limit, builds the fallback's step instead and logs a warning. Its
      sidecar remembers that, so that a restart does not compile the
      first step again to learn the same.

    The build is on the record (``obs/trace.py``: ``step_build`` with
    ``step_lower`` and ``step_compile`` under it, recorded always):
    jax's own seconds for the trace and the lowering, whether the
    compile was a ``cache`` hit (``info`` has it beside ``source``),
    and ``xla_memory``, the memory of the executable that will run as
    XLA laid it out, which is also what the fallback's limit judges.
    """
    args = tuple(abstractify(a) for a in abstract_args)
    key = fb_key = aot_signature(*args, plan=plan, surface=surface)
    if variant:
        key = _variant_key(key, variant)
    if fallback is not None:
        fb_key = _variant_key(key, "fallback")
    info: Dict[str, Any] = {"label": label, "sidecar": sidecar,
                            "cache": None}
    if plan is not None:
        info["plan_fingerprint"] = plan.fingerprint()

    # jax's own seconds and the cache's verdict reach the regions
    # below through the listener, with or without a cache directory
    _install_listener()

    def since(before, *names):
        return {n: _STATS[n] - before[n] for n in names}

    def lower_and_compile(fn):
        # a step that cannot be lowered or compiled is an error, not a
        # reason to leave AOT: the jitted path would hit the same wall
        # at its first call, later and with less context
        before = dict(_STATS)
        with trace.region("step_lower") as low:
            lowered = fn.lower(*args)
            spent = time.perf_counter() - low.t0
            # never above the region's own seconds: jax times its
            # blocks on another clock, and on every thread
            low.attrs.update({k: min(v, spent) for k, v in since(
                before, "trace_s", "to_mlir_s").items()})
        before = dict(_STATS)
        with trace.region("step_compile") as comp, salted_cache_key():
            compiled = lowered.compile()
            got = since(before, "hits", "misses", "retrieval_s",
                        "backend_compile_s")
            cache = ("hit" if got["hits"] and not got["misses"]
                     else "miss" if got["misses"] else None)
            comp.attrs.update(
                cache=cache, retrieval_s=got["retrieval_s"],
                backend_compile_s=0.0 if cache == "hit"
                else got["backend_compile_s"])
        info["cache"] = cache
        return compiled, xla_memory(compiled, limit)

    fell_back = False
    limit = _device_limit(args)
    with trace.region("step_build", **(attrs or {})) as build:
        compiled = load_executable(sidecar, key) if sidecar else None
        if compiled is None and sidecar and fallback is not None:
            compiled = load_executable(sidecar, fb_key)
            fell_back = compiled is not None
        if compiled is not None:
            build.attrs["source"] = "deserialized"
            memory = xla_memory(compiled, limit)
        else:
            build.attrs["source"] = "compiled"
            why = None
            try:
                compiled, memory = lower_and_compile(jitted_fn)
                if fallback is not None:
                    why = _past_peak(memory, fallback.peak_limit_bytes)
            except jax.errors.JaxRuntimeError as e:
                if fallback is None or "RESOURCE_EXHAUSTED" not in str(e):
                    raise
                why = str(e).splitlines()[0]
            if why is not None:
                logger.warning(
                    "%s: the step asked for (%s) does not fit: %s; "
                    "building the fallback", label, variant, why)
                fell_back = True
                compiled, memory = lower_and_compile(fallback.fn)
        if fell_back:
            build.attrs.update(fallback.attrs)
            jitted_fn, key = fallback.fn, fb_key
        # of the executable that will run, the fallback's after one
        build.attrs["xla_memory"] = memory
    info.update(build.attrs, build_s=build.t1 - build.t0)
    logger.info(
        "%s: %s AOT executable in %.2fs%s; XLA's peak %s", label,
        info["source"], info["build_s"],
        f" ({sidecar})" if info["source"] == "deserialized"
        else {"hit": " (out of the compile cache)",
              "miss": " (built, and written to the compile cache)",
              None: " (built: no compile cache in use)"}[info["cache"]],
        "not reported" if not memory else
        f"{memory['peak'] / 1e9:.2f} GB"
        + ("" if limit is None else f" of {limit / 1e9:.2f}"))
    # a warm-restart attempt must feed the obs network gauges too — the
    # note guards internally against a deserialized executable that
    # cannot re-serve its analyses
    _note_cost_report(compiled, plan)
    if info["source"] == "deserialized":
        return GuardedStep(compiled, jitted_fn, info)
    if sidecar:
        is_writer = True
        if _backend_initialized():
            try:
                is_writer = jax.process_index() == 0
            except Exception:  # noqa: BLE001
                pass
        if is_writer:
            t0 = time.perf_counter()
            if save_executable(compiled, sidecar, key):
                # validate the round-trip NOW: a compile that was itself
                # a persistent-cache hit can serialize to a blob the
                # backend refuses to deserialize (observed on XLA:CPU,
                # "Symbols not found") — a sidecar that will fail every
                # future restart must not be left behind
                if load_executable(sidecar, key) is None:
                    try:
                        os.remove(sidecar)
                    except OSError:
                        pass
                    logger.info(
                        "%s: sidecar failed its deserialize check; "
                        "removed (restarts will use the persistent "
                        "compile cache instead)", label)
                else:
                    info["serialize_s"] = time.perf_counter() - t0
                    logger.info(
                        "%s: AOT executable persisted to %s (%.2fs)",
                        label, sidecar, info["serialize_s"])
    return GuardedStep(compiled, jitted_fn, info)

"""Test harness: distributed-without-a-cluster (SURVEY.md §4).

8 fake CPU devices let every test exercise the real mesh/pjit sharding
specs — DP/FSDP/TP partitioning, ring-attention ppermute, checkpoint shard
round-trips — with no TPU attached. Env vars must be set before jax import,
hence module scope here.
"""

import os

# XLA_FLAGS must land before first backend init (importing jax does not
# initialize one).
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
# the trainer's genuine-failure retries back off exponentially
# (rayint/trainer.py); the suite's deliberate-failure tests must not
# each pay real sleeps
os.environ.setdefault("RETRY_BACKOFF_S", "0")
# the trainer enables the persistent compile cache in every worker
# (perf/cache.py); under the suite that would persist every tiny test
# executable to <checkout>/.jax_cache and warm-poison later cold-compile
# measurements on the same machine. Tests that WANT the cache (
# tests/test_perf.py) re-enable it into a sandbox dir explicitly.
os.environ.setdefault("COMPILE_CACHE", "0")
# obs telemetry (obs/) defaults ON for runs with an output dir; under
# the suite that would write event/metric streams into every tmpdir
# and — worse — arm anomaly-triggered jax.profiler captures whose
# first start_trace costs tens of seconds on some hosts. Tests that
# WANT telemetry (tests/test_obs.py) opt back in via config/obs_dir.
os.environ.setdefault("OBS", "0")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_default_matmul_precision", "float32")

# NOTE: do NOT point jax_compilation_cache_dir at a suite-wide cache to
# speed the suite up. On this jaxlib a persistent-cache HIT returns an
# executable that (a) cannot be re-serialized into an AOT sidecar
# (XLA:CPU "Symbols not found" — the PR 4 poisoned-sidecar issue) and
# (b) was keyed WITHOUT the donation/aliasing spec, so a donate=True
# build can silently receive the undonated executable. Both were caught
# by test_perf/test_analysis when this was tried.

import pytest  # noqa: E402

from gke_ray_train_tpu.parallel.mesh import MeshConfig, build_mesh  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 fake devices, got {devs}"
    return devs


@pytest.fixture(scope="session")
def dp_mesh(devices):
    """Pure data-parallel mesh (8 data)."""
    return build_mesh(MeshConfig(data=8, fsdp=1), devices)


@pytest.fixture(scope="session")
def fsdp_mesh(devices):
    """2 data x 4 fsdp."""
    return build_mesh(MeshConfig(data=2, fsdp=4), devices)


@pytest.fixture(scope="session")
def hybrid_mesh(devices):
    """2 data x 4 fsdp with the data axis laid across 2 emulated slices
    (the DCN-outermost hybrid layout). ONE session build shared by the
    DCN sync drills (test_dcn) and the peer/goodput recovery drills —
    the per-arm mesh rebuilds were pure tier-1 wall."""
    return build_mesh(MeshConfig(data=2, fsdp=4, num_slices=2), devices)


@pytest.fixture(scope="session")
def tp_mesh(devices):
    """2 fsdp x 2 model x 2 context — every parallelism axis live."""
    return build_mesh(MeshConfig(data=1, fsdp=2, model=2, context=2), devices)


@pytest.fixture(scope="session")
def tiny_train_setup():
    """One meshless tiny model + ONE jitted train step, shared across
    the heaviest suites (test_obs and friends rebuilt this exact
    scaffolding per test, paying the same compile 6+ times). Safe to
    share: the state pytree is immutable and the step was built with
    donate=False, so every consumer starts from the identical step-0
    state and the suite compiles the program once. The loop's
    ``compile`` span/ledger term still books on every run — it times
    the first step CALL, warm or cold."""
    import jax as _jax

    from gke_ray_train_tpu.models import tiny
    from gke_ray_train_tpu.train import (
        make_optimizer, make_train_state, make_train_step)
    cfg = tiny(vocab_size=128, d_model=32, n_layers=1, n_heads=2,
               n_kv_heads=2, d_ff=64, dtype="float32",
               param_dtype="float32")
    opt = make_optimizer(1e-3)
    state = make_train_state(cfg, opt, _jax.random.key(0))
    step = make_train_step(cfg, opt, donate=False)
    return cfg, opt, state, step
